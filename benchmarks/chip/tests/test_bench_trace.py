"""The reduction from a profiler trace to busy time, idle gaps and the
level loop's device time."""
import json
import os

import pytest

import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return (plane, line, name, start, dur)


SYNTH = [
    _ev(HOST, "t0", "bench.window", 100, 1000),
    _ev(HOST, "t0", "bench.query", 100, 900),
    _ev(HOST, "t0", "bench.replay_accumulate", 300, 500),
    _ev(HOST, "t0", "bench.pad_plan", 300, 100),
    # two programs; a loop holds two ops, and one op starts before the
    # window and is clipped to it
    _ev(DEV, tr.PROGRAMS_LINE, "jit_run(7)", 450, 300),
    _ev(DEV, tr.PROGRAMS_LINE, "jit_other", 50, 100),
    _ev(DEV, tr.OPS_LINE, "%while.4 = (f32[9]) while(f32[9] %t)", 450, 300),
    _ev(DEV, tr.OPS_LINE, "%gather.1 = f32[8] gather()", 450, 150),
    _ev(DEV, tr.OPS_LINE, "%edan_level_step.3 = f32[8] custom-call()", 600, 150),
    _ev(DEV, tr.OPS_LINE, "%copy.2 = f32[8] copy()", 50, 100),
]


def test_busy_is_the_union_of_ops_inside_the_window():
    s = tr.reduce(SYNTH)
    assert s["window_s"] == pytest.approx(1000e-9)
    # [100, 150) from the clipped copy, [450, 750) from the loop
    assert s["busy_s"] == pytest.approx(350e-9)
    assert s["op_s"]["%copy.2"] == pytest.approx(50e-9)
    assert s["op_s"]["%gather.1"] == pytest.approx(150e-9)


def test_op_time_is_self_time():
    s = tr.reduce(SYNTH)
    # the loop's 300 ns hold its body's 150 + 150: nothing is left to
    # the loop itself
    assert "%while.4" not in s["op_s"]
    assert s["op_s"]["%edan_level_step.3"] == pytest.approx(150e-9)


def test_idle_gaps_go_to_the_innermost_host_span():
    s = tr.reduce(SYNTH)
    # gap [150, 450): query to 300, pad_plan to 400, replay_accumulate;
    # gap [750, 1100): replay_accumulate to 800, query to 1000, then no
    # span is open
    assert s["idle_gaps"] == {"query": pytest.approx(350e-9),
                              "pad_plan": pytest.approx(100e-9),
                              "replay_accumulate": pytest.approx(100e-9),
                              "host": pytest.approx(100e-9)}


def test_level_loop_is_selected_by_program_name():
    import levelloop
    s = tr.reduce(SYNTH)
    assert tr.program_seconds(s, levelloop.PROGRAM) == pytest.approx(300e-9)
    assert tr.program_seconds(s, r"^nothing$") is None


def test_a_trace_without_a_device_reduces_to_nothing():
    host_only = [e for e in SYNTH if e[0] == HOST]
    assert tr.reduce(host_only) is None
    assert tr.reduce([]) is None


def test_union_merges_touching_and_nested_intervals():
    assert tr.union_ns([(5, 9), (0, 3), (3, 4), (6, 7)]) == [[0, 4], [5, 9]]


# ----------------------------------------------- a recorded v5e trace

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "profile_trisolv_v5e.json")) as f:
        d = json.load(f)
    return d["passes"], [tuple(e) for e in d["events"]]


def _reader(name):
    import importlib.util
    path = os.path.join(os.path.dirname(DATA), os.pardir, "metrics",
                        name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_recorded_trace_busy_and_gaps_add_up(recorded):
    _, events = recorded
    s = tr.reduce(events)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert sum(s["idle_gaps"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-9)
    # the gaps fall inside the program's own host stages, not "host"
    assert {"device_pass", "verify_order", "mem_layers"} <= set(
        s["idle_gaps"])
    assert s["idle_gaps"].get("host", 0) < 0.01 * s["window_s"]


def test_recorded_trace_selects_the_two_level_loops(recorded):
    import levelloop
    _, events = recorded
    s = tr.reduce(events)
    loops = [n for n in s["program_s"] if n.startswith("jit_run(")]
    assert len(loops) == 2              # the analytic and simulated pass
    assert tr.program_seconds(s, levelloop.PROGRAM) == pytest.approx(
        sum(s["program_s"][n] for n in loops))
    # the device is busy only inside the loops here; a program's time
    # also holds the short waits between its own ops
    t = tr.program_seconds(s, levelloop.PROGRAM)
    assert s["busy_s"] * 0.99 < t < s["window_s"]


def test_recorded_trace_metrics(recorded):
    passes, events = recorded

    class Rec:
        pass
    run = type("Run", (), {})()
    run.trace = tr.reduce(events)
    run.recorder = Rec()
    run.recorder.passes = passes
    run.peaks = {"hbm_bytes_per_s": 819e9}
    t = tr.program_seconds(run.trace, r"^jit_run\b")
    levels = sum(p["levels"] for p in passes)
    assert _reader("level_us")(run) == pytest.approx(1e6 * t / levels)
    roof = _reader("replay_roofline")(run)
    assert 0 < roof < 100
    assert roof == pytest.approx(
        100 * sum(p["bytes"] for p in passes) / 819e9 / t)
    idle = _reader("device_idle_pct")(run)
    assert idle == pytest.approx(
        100 * (1 - run.trace["busy_s"] / run.trace["window_s"]))
    run.trace = None                    # nothing to read: left out
    assert _reader("level_us")(run) is None
    assert _reader("device_idle_pct")(run) is None
