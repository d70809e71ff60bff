"""The reduction of the program's own spans (``progspans``), the three
numbers computed from it, and the trace metrics that must read as
before."""
import importlib.util
import json
import os

import pytest

import progspans as ps
import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(DATA), os.pardir, "metrics")
DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW = ["dispatch_host_pct", "transfer_mb", "pad_efficiency_pct"]


def _new(name):
    return lambda run: ps.METRICS[name](run.program, run.latencies)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ev(plane, line, name, start, dur, **stats):
    return (plane, line, name, start, dur, stats)


SYNTH = [
    _ev(HOST, "t0", "bench.window", 100, 1000),
    _ev(HOST, "t0", "edan.query", 100, 900, entry="grid_report"),
    _ev(HOST, "t0", "edan.replay", 200, 600, levels=3),
    _ev(HOST, "t0", "edan.replay.cast", 200, 100),
    _ev(HOST, "t0", "edan.replay.upload", 300, 100, bytes=1000),
    _ev(HOST, "t0", "edan.replay.run", 400, 50, edges=6, slots=24),
    _ev(HOST, "t0", "edan.replay.download", 450, 250, bytes=400),
    _ev(HOST, "t0", "edan.replay.upload", 700, 50, bytes=10),
    # outside the window: dropped
    _ev(HOST, "t0", "edan.replay.upload", 1200, 50, bytes=99),
    _ev(DEV, tr.OPS_LINE, "%while.1", 420, 300),
]


def test_spans_total_self_count_and_summed_stats():
    p = ps.reduce(SYNTH)
    sp = p["spans"]
    assert sp["replay.upload"]["count"] == 2
    assert sp["replay.upload"]["stats"] == {"bytes": 1010}
    assert sp["replay"]["total_s"] == pytest.approx(600e-9)
    # 600 less cast 100, uploads 150, run 50 and download 250
    assert sp["replay"]["self_s"] == pytest.approx(50e-9)
    assert sp["query"]["self_s"] == pytest.approx(300e-9)
    # string stats are kept off the sums
    assert sp["query"]["stats"] == {}


def test_program_idle_gaps_go_to_the_innermost_program_span():
    p = ps.reduce(SYNTH)
    # gap [100, 420): query to 200, cast, upload, run to 420; gap
    # [720, 1100): upload to 750, replay to 800, query to 1000, host
    assert p["program_idle_gaps"] == {
        "query": pytest.approx(300e-9), "replay.cast": pytest.approx(100e-9),
        "replay.upload": pytest.approx(130e-9),
        "replay.run": pytest.approx(20e-9),
        "replay": pytest.approx(50e-9), "host": pytest.approx(100e-9)}
    s = tr.reduce([e[:5] for e in SYNTH])
    assert sum(p["program_idle_gaps"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])


def test_no_window_or_no_device_reduces_to_nothing():
    assert ps.reduce([e for e in SYNTH if e[0] == HOST]) is None
    assert ps.reduce([e for e in SYNTH if e[2] != tr.WINDOW]) is None
    assert ps.reduce([]) is None


def _run(program, latencies):
    run = type("Run", (), {})()
    run.program, run.latencies = program, latencies
    return run


def test_the_new_metrics_read_the_spans():
    run = _run(ps.reduce(SYNTH), [1e-6, 1e-6])
    assert _new("dispatch_host_pct")(run) == pytest.approx(
        100 * 100e-9 / 2e-6)
    assert _new("transfer_mb")(run) == pytest.approx(1410 / 1e6 / 2)
    assert _new("pad_efficiency_pct")(run) == pytest.approx(25.0)
    assert sorted(ps.METRICS) == sorted(NEW)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_reads_nothing(name):
    # the program before it had spans: a trace with the window and the
    # device only, or no trace at all
    bare = [e for e in SYNTH if not e[2].startswith(ps.PREFIX)]
    assert _new(name)(_run(ps.reduce(bare), [1.0])) is None
    assert _new(name)(_run(None, [1.0])) is None


# ------------------------------------- the committed traces of a v5e

@pytest.fixture(scope="module")
def before():
    with open(os.path.join(DATA, "profile_trisolv_v5e.json")) as f:
        d = json.load(f)
    with open(os.path.join(DATA, "profile_trisolv_v5e_reduced.json")) as f:
        want = json.load(f)
    return d, want


def test_the_old_trace_reduces_exactly_as_before(before):
    d, want = before
    s = tr.reduce([tuple(e) for e in d["events"]])
    assert s == want["reduce"]
    assert tr.top(s["op_s"]) == want["breakdown"]["device_ops"]
    assert tr.top(s["idle_gaps"]) == want["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("name", ["replay_share_pct", "level_us",
                                  "replay_roofline", "device_idle_pct"])
def test_the_old_metrics_read_exactly_as_before(before, name):
    d, want = before
    run = type("Run", (), {})()
    run.trace = tr.reduce([tuple(e) for e in d["events"]])
    run.recorder = type("Rec", (), {})()
    run.recorder.passes = d["passes"]
    run.recorder.seconds = {"replay_accumulate": sum(
        p["seconds"] for p in d["passes"])}
    run.peaks = {"hbm_bytes_per_s": 819e9}
    run.latencies = [run.trace["window_s"]]
    assert _reader(name)(run) == want["metrics"][name]


@pytest.fixture(scope="module")
def spanned():
    with open(os.path.join(DATA, "profile_trisolv_spans_v5e.json")) as f:
        d = json.load(f)
    return [tuple(e) for e in d["events"]]


def test_recorded_program_gaps_add_up_to_the_idle_time(spanned):
    p = ps.reduce(spanned)
    s = tr.reduce([e[:5] for e in spanned])
    assert sum(p["program_idle_gaps"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-9)
    # the query's host stages are named: little idle is left to the
    # query span itself or to no span
    unnamed = p["program_idle_gaps"].get("query", 0) + \
        p["program_idle_gaps"].get("host", 0)
    assert unnamed < 0.1 * (s["window_s"] - s["busy_s"])
    assert {"replay.upload", "replay.download", "verify", "report",
            "fill"} <= set(p["program_idle_gaps"])


def test_recorded_spans_feed_the_new_metrics(spanned):
    window = [e for e in spanned if e[2] == tr.WINDOW][0]
    run = _run(ps.reduce(spanned), [window[4] / 1e9])
    got = {n: _new(n)(run) for n in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["dispatch_host_pct"] <= 100
    assert got["pad_efficiency_pct"] <= 100
    sp = run.program["spans"]
    assert sp["query"]["count"] == 1
    # two passes (analytic and simulated), each one upload, run and
    # download
    assert sp["replay.run"]["count"] == sp["replay.upload"]["count"] == 2
