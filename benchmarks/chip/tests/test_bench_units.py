"""The yardstick's pieces: bytes, traffic, peaks, the stats gate, the
reference against the program, and the precision control."""
import itertools

import numpy as np
import pytest

import check
import reference
import traffic
import workload
import yardstick


# ------------------------------------------------------------ bytes

def test_replay_bytes_counts_the_unpadded_plan():
    from repro.core.backend import build_level_partition
    # 0 -> 2, 1 -> 2, 2 -> 3, 1 -> 3; levels 0, 0, 1, 2
    src, dst = np.array([0, 1, 2, 1]), np.array([2, 2, 3, 3])
    lv = build_level_partition(src, dst, np.array([0, 0, 1, 2]), 4)
    # 4 edge reads, 2 destinations (2 and 3) read base + write finish
    assert yardstick.replay_bytes(lv, 3) == (4 + 2 * 2) * 3 * 4
    # slot chains: vertex 3 queues behind 0, vertex 1 behind 0 (queue
    # only), sentinel 4 elsewhere
    lv.qpred = np.array([4, 0, 4, 0], dtype=np.int32)
    lv.qonly_dst = np.array([1], dtype=np.int32)
    assert yardstick.replay_bytes(lv, 3) == (4 + 2 + 2 * 3) * 3 * 4
    assert yardstick.replay_bytes(lv, 3, itemsize=8) == \
        2 * yardstick.replay_bytes(lv, 3)


# ---------------------------------------------------------- traffic

MIX = {"entry": "grid_report", "pick": "cycle",
       "members": ["a", "b", "c"], "ms": [4], "compute_slots": [0],
       "alphas": {"count": 11, "low": 50, "high": 300},
       "warmup_alphas": [50, 300]}


def _take(seed, n=30, mix=MIX):
    return list(itertools.islice(traffic.queries(mix, seed), n))


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 3, 2 ** 40 + 1, -5])
def test_traffic_is_deterministic_per_seed(seed):
    a, b = _take(seed), _take(seed)
    assert [q["member"] for q in a] == [q["member"] for q in b]
    assert all(np.array_equal(x["alphas"], y["alphas"]) for x, y in zip(a, b))


def test_traffic_differs_across_seeds_in_order_not_in_work():
    a, b = _take(1), _take(2)
    assert [q["member"] for q in a] != [q["member"] for q in b]
    assert not all(np.array_equal(x["alphas"], y["alphas"])
                   for x, y in zip(a, b))
    # every block of len(members) queries visits each member once
    for qs in (a, b):
        for i in range(0, 30, 3):
            assert sorted(q["member"] for q in qs[i:i + 3]) == ["a", "b", "c"]


def test_alphas_are_distinct_integers_in_range():
    for q in _take(3):
        al = q["alphas"]
        assert len(set(al)) == 11 and (al == np.round(al)).all()
        assert al.min() >= 50 and al.max() <= 300


def test_warmup_is_one_query_per_plan_at_fixed_alphas():
    w = traffic.warmup(MIX)
    assert [q["member"] for q in w] == ["a", "b", "c"]
    assert traffic.warmup(dict(MIX, pick="union"))[0]["member"] is None
    assert all(list(q["alphas"]) == [50.0, 300.0] for q in w)


# ----------------------------------------------------- peaks, gate

def test_unknown_device_kind_is_an_error():
    table = yardstick.load_peaks()
    assert yardstick.peaks_for("TPU v5 lite", table)["hbm_bytes_per_s"] \
        == 819e9
    with pytest.raises(yardstick.BenchError):
        yardstick.peaks_for("TPU v9 imaginary", table)


def test_stats_gate():
    base = dict(chunks=2, jax_chunks=2, jax_f64_chunks=0, numpy_chunks=0,
                certified_columns=9, demoted_columns=0, numpy_f64_passes=0)
    ok = dict(base, chunks=4, jax_chunks=4)
    assert yardstick.stats_gate(base, ok) == []
    for k in yardstick.OFF_DEVICE:
        bad = dict(ok, **{k: 1})
        assert any(k in w for w in yardstick.stats_gate(base, bad))
    assert yardstick.stats_gate(base, base) == \
        ["no replay chunk was dispatched"]


def test_trace_figures_ignore_edge_order_and_see_any_change():
    is_mem, src, dst = [True, False, True], [0, 0, 1], [1, 2, 2]
    f = yardstick.trace_figures(3, is_mem, src, dst)
    assert (f["vertices"], f["mem_vertices"], f["edges"]) == (3, 2, 3)
    assert yardstick.trace_figures(3, is_mem, src[::-1], dst[::-1]) == f
    assert yardstick.trace_figures(3, is_mem, [0, 1], [2, 2]) != f
    assert yardstick.trace_figures(3, [True, True, True], src, dst) != f
    assert yardstick.trace_figures(3, is_mem, [0, 0, 0], [1, 2, 2]) \
        ["sha256"] != f["sha256"]


@pytest.mark.parametrize("kernel", ["mvt", "lu"])
def test_the_bulk_tracer_gives_the_stated_figures(kernel):
    """The program's tracer and the original per-element one give the
    same trace, which is what a configuration states."""
    from conftest import stated_figures
    from repro.apps import polybench
    g = polybench.trace_kernel(kernel, 7)
    g._finalize()
    assert yardstick.trace_figures(g.n_vertices, g.is_mem, g.src, g.dst) \
        == stated_figures(kernel, 7)


def test_the_configuration_states_the_figures_of_every_trace():
    import json
    import os
    path = os.path.join(os.path.dirname(yardstick.HERE),
                        os.path.basename(yardstick.HERE), "configs",
                        "polybench-paper15.json")
    with open(path) as f:
        cfg = json.load(f)
    assert set(cfg["figures"]) == set(cfg["traces"])
    for name, t in cfg["traces"].items():
        # every size is the source's, or cut below it and named as cut
        dims = cfg["source_sizes"][name].values()
        assert t["N"] <= min(dims)
        assert (name in cfg["reduced"]) == (t["N"] != max(dims))


# ------------------------------------------- reference vs program

@pytest.fixture(scope="module")
def kernels():
    from repro.apps import polybench
    out = {}
    for k in ("atax", "lu", "gemver"):
        g = polybench.trace_kernel(k, 6)
        g._finalize()
        out[k] = (g, reference.Trace(g.n_vertices, g.is_mem, g.src, g.dst))
    return out


@pytest.mark.parametrize("k", ["atax", "lu", "gemver"])
def test_reference_simulation_matches_the_program(kernels, k):
    from repro.core import sweep_grid
    g, tr = kernels[k]
    al = np.array([50.0, 77.0, 299.0])
    got = sweep_grid(g, al, ms=(2, 4), compute_slots=(0, 8),
                     backend="numpy")
    want = [[[reference.simulate(tr, m, a, 1.0, cs) for cs in (0, 8)]
             for m in (2, 4)] for a in al]
    assert np.array_equal(got, np.array(want))


@pytest.mark.parametrize("k", ["atax", "lu"])
def test_reference_report_matches_the_program(kernels, k):
    from repro.core import grid_report
    g, tr = kernels[k]
    al = np.array([50.0, 123.0, 300.0])
    got = grid_report(g, al, ms=(4,), compute_slots=(0,),
                      simulate_points=True, backend="numpy")
    want = reference.report(tr, al, (4,), (0,))
    assert workload.compare_report(got, want) == \
        (sum(np.asarray(v).size for v in want.values()), 0)


def test_reference_refuses_edges_out_of_id_order():
    with pytest.raises(ValueError):
        reference.Trace(2, [True, False], [1], [0])


def test_roundings():
    assert reference.round_bf16(257.0) == 256.0
    assert reference.round_bf16(1.0) == 1.0
    assert reference.round_f32(16777217.0) == 16777216.0
    assert reference.round_f32(12.5) == 12.5


# ----------------------------------------------------- the control

def _answers(mix, work, n):
    qs = list(itertools.islice(traffic.queries(mix, 11), n))
    return [(q, None, workload.points(q, None, work.names)) for q in qs]


@pytest.fixture(scope="module")
def small_work():
    cfg = {"name": "t", "app": "polybench", "unit": 1.0,
           "traces": {k: {"kernel": k, "N": 8} for k in ("atax", "lu")}}
    return workload.Workload(cfg, ["atax", "lu"])


def test_the_bfloat16_control_fails_the_grid_check(small_work):
    mix = dict(MIX, entry="sweep_grid", members=["atax", "lu"],
               check={"points": 12})
    ans = _answers(mix, small_work, 4)
    ref = check.control(ans, small_work.inputs, mix["check"], 11, 1.0,
                        "float64")
    assert ref["correct"] and ref["numbers"]["mismatched_values"]["value"] == 0
    bf = check.control(ans, small_work.inputs, mix["check"], 11, 1.0,
                       "bfloat16")
    assert not bf["correct"]
    assert bf["numbers"]["mismatched_values"]["value"] >= 6


def test_the_float32_control_fails_the_report_check(small_work):
    mix = dict(MIX, members=["atax", "lu"], simulate_points=True,
               check={"reports": 3})
    ans = _answers(mix, small_work, 6)
    f32 = check.control(ans, small_work.inputs, mix["check"], 11, 1.0,
                        "float32")
    assert not f32["correct"]            # Lambda is a ratio: it rounds
    assert f32["numbers"]["mismatched_values"]["value"] > 0


@pytest.mark.parametrize("k, members, share", [(48, 15, 3), (14, 7, 2),
                                                (10, 4, 2)])
def test_the_sample_draws_the_same_share_of_every_trace(k, members, share):
    names = [f"t{i}" for i in range(members)]
    items = [("point", m, float(a), 4, 0, 1.0) for m in names
             for a in range(66)]
    sizes = {m: i for i, m in enumerate(names)}
    for seed in range(5):
        answers = [({"entry": "sweep_grid"}, None,
                    [it[1:] for it in items])]
        got = check.sample(answers, sizes, {"points": k}, seed)
        assert len(got) == k == len({it[1:5] for it in got})
        counts = [sum(it[1] == m for it in got) for m in names]
        assert min(counts) >= share


def test_the_sample_holds_the_largest_trace(small_work):
    mix = dict(MIX, entry="sweep_grid", members=["atax", "lu"],
               check={"points": 1})
    sizes = {m: v[0] for m, v in small_work.inputs.items()}
    for seed in range(8):
        ans = [(q, None, workload.points(q, None, small_work.names))
               for q in itertools.islice(traffic.queries(mix, seed), 4)]
        items = check.sample(ans, sizes, mix["check"], seed)
        assert [it[1] for it in items] == ["lu"]
