"""The shared level-synchronous (max,+) kernel: numpy vs jax/pallas backend.

The two backends implement the identical recurrence; in a fixed dtype the
results must agree bit-for-bit (max is exact, every add is a single IEEE
operation).  The jax path is exercised here on CPU (pallas in interpret
mode) in float32 — the dtype jax computes in without the x64 flag — so the
comparison against the numpy kernel run on the same float32 inputs is
exact equality, not a tolerance check.
"""
import os
from collections import OrderedDict

import numpy as np
import pytest

from repro.core import backend as bk
from repro.core import (EDag, level_accumulate, select_backend,
                        simulate_batch, simulate_reference)

jax = pytest.importorskip("jax")


def _random_edag(seed: int, n: int = 40) -> EDag:
    rng = np.random.default_rng(seed)
    g = EDag()
    for i in range(n):
        g.add_vertex(cost=float(rng.integers(1, 5)),
                     is_mem=bool(rng.random() < 0.5))
        for j in range(i):
            if rng.random() < 0.15:
                g.add_edge(j, i)
    g._finalize()
    return g


def test_select_backend_override_and_env(monkeypatch):
    assert select_backend("numpy") == "numpy"
    assert select_backend("jax") == "jax"
    with pytest.raises(ValueError):
        select_backend("tpu-go-brrr")
    monkeypatch.setenv("EDAN_BACKEND", "jax")
    assert select_backend() == "jax"
    monkeypatch.setenv("EDAN_BACKEND", "numpy")
    assert select_backend() == "numpy"
    monkeypatch.delenv("EDAN_BACKEND")
    assert select_backend() in ("numpy", "jax")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_jax_kernel_matches_numpy_bitwise_f32(seed):
    g = _random_edag(seed)
    lv = g._level_csr()
    rng = np.random.default_rng(seed + 100)
    base = rng.standard_normal((g.n_vertices, 4)).astype(np.float32)
    F_np = level_accumulate(lv, base.copy(), backend="numpy")
    F_jax = level_accumulate(lv, base.copy(), backend="jax")
    assert np.array_equal(F_np, F_jax)


def test_accumulate_batch_nk_jax_backend_matches():
    g = _random_edag(7)
    from repro.core import cost_matrix
    costs = cost_matrix(g, [25.0, 100.0, 300.0]).astype(np.float32)
    F_np = g._accumulate_batch_nk(np.ascontiguousarray(costs.T.copy()),
                                  backend="numpy")
    F_jx = g._accumulate_batch_nk(np.ascontiguousarray(costs.T.copy()),
                                  backend="jax")
    assert np.array_equal(F_np, F_jx)


def test_jax_kernel_with_slot_chain_f32():
    """The slot-update (queue predecessor) path of the pallas level step."""
    from repro.core.backend import LevelCSR, build_level_partition, levelize
    rng = np.random.default_rng(3)
    n = 30
    src = []
    dst = []
    for i in range(1, n):
        if rng.random() < 0.7:
            src.append(int(rng.integers(0, i)))
            dst.append(i)
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    # a 2-slot chain over the odd vertices
    chain = np.arange(1, n, 2)
    qpred = np.full(n, n, dtype=np.int64)
    qpred[chain[2:]] = chain[:-2]
    qdst = np.nonzero(qpred < n)[0]
    level = levelize(np.concatenate([src, qpred[qdst]]),
                     np.concatenate([dst, qdst]), n)
    lv = build_level_partition(src, dst, level, n)
    lv.qpred = qpred
    qonly = qdst[np.bincount(dst, minlength=n)[qdst] == 0]
    if len(qonly):
        qonly = qonly[np.argsort(level[qonly], kind="stable")]
        counts = np.bincount(level[qonly], minlength=lv.n_levels)
        lv.qonly_ptr = np.concatenate(([0], np.cumsum(counts))).astype(
            np.int64)
        lv.qonly_dst = qonly
    base = np.abs(rng.standard_normal((n + 1, 3))).astype(np.float32) + 0.5
    base[-1] = 0.0
    F_np = level_accumulate(lv, base.copy(), clamp=False, backend="numpy")
    F_jx = level_accumulate(lv, base.copy(), clamp=False, backend="jax")
    assert np.array_equal(F_np, F_jx)


def test_jax_kernel_R_out_matches_numpy_f32():
    """Ready times come out of the same fused pallas level loop as the
    finish times — no numpy round-trip — and match the numpy kernel's
    R_out bit-for-bit, with and without the clamp."""
    g = _random_edag(21)
    lv = g._level_csr()
    rng = np.random.default_rng(22)
    base = rng.standard_normal((g.n_vertices, 5)).astype(np.float32)
    for clamp in (True, False):
        R_np = np.zeros_like(base)
        R_jx = np.zeros_like(base)
        F_np = level_accumulate(lv, base.copy(), clamp=clamp, R_out=R_np,
                                backend="numpy")
        F_jx = level_accumulate(lv, base.copy(), clamp=clamp, R_out=R_jx,
                                backend="jax")
        assert np.array_equal(F_np, F_jx)
        assert np.array_equal(R_np, R_jx)


def test_jax_kernel_R_out_with_slot_chain_f32():
    """The full simulator-replay shape — qpred slot chains, queue-only
    vertices, zero sentinel row, clamp off — produces identical finish
    AND ready matrices on both backends."""
    from repro.core.scheduler import _ReplayPlan, _event_loop

    rng = np.random.default_rng(31)
    g = EDag()
    for i in range(50):
        g.add_vertex(is_mem=bool(rng.random() < 0.6))
        for j in range(i):
            if rng.random() < 0.1:
                g.add_edge(j, i)
    g._finalize()
    _, topo, O_mem, O_alu = _event_loop(
        g.is_mem, g._sim_lists(), 2, 80.0, 1.0, 3, record=True)
    plan = _ReplayPlan(g, topo, O_mem, O_alu, 2, 3)
    k = 4
    base = np.empty((g.n_vertices + 1, k), dtype=np.float32)
    base[:-1] = np.where(plan.is_mem_topo[:, None],
                         np.linspace(40, 160, k, dtype=np.float32)[None],
                         np.float32(1.0))
    base[-1] = 0.0
    R_np = np.zeros_like(base)
    R_jx = np.zeros_like(base)
    F_np = level_accumulate(plan.lv, base.copy(), clamp=False, R_out=R_np,
                            backend="numpy")
    F_jx = level_accumulate(plan.lv, base.copy(), clamp=False, R_out=R_jx,
                            backend="jax")
    assert np.array_equal(F_np, F_jx)
    assert np.array_equal(R_np, R_jx)


def test_jax_kernel_segmented_slot_chains_f32():
    """The union (multi-trace) replay shape: a block-diagonal partition
    whose slot chains are segmented by block boundaries — each member
    trace owns its own slot pool, chains never cross blocks, and all
    blocks share one zero sentinel row.  The two-output pallas level step
    must match the numpy kernel bit-for-bit on finish AND ready times.
    This closes the gap where only single-trace chains were covered."""
    from repro.core import EDagSuite
    from repro.core.suite import _build_suite_plan

    members = []
    for seed, n, p in ((61, 45, 0.10), (62, 25, 0.18), (63, 35, 0.07)):
        rng = np.random.default_rng(seed)
        g = EDag()
        for i in range(n):
            g.add_vertex(is_mem=bool(rng.random() < 0.6))
            for j in range(i):
                if rng.random() < p:
                    g.add_edge(j, i)
        g._finalize()
        members.append(g)
    suite = EDagSuite(members)
    plan = _build_suite_plan(suite, [(2, 3)], 1.0, 80.0, use_cache=False)

    # the segment invariant itself: every slot chain stays inside its
    # block (or points at the shared sentinel row n_union)
    n_u = suite.n_vertices
    assert plan.n == n_u                   # one pair: one block per member
    qp = plan.lv.qpred
    tid = suite.trace_id
    real = np.nonzero(qp < n_u)[0]
    assert len(real)                       # the chains are exercised
    assert np.array_equal(tid[real], tid[qp[real]])
    assert np.array_equal(plan.lv.seg_ptr, suite.offsets)

    k = 4
    base = np.full((n_u + 1, k), 1.0, dtype=np.float32)
    base[plan.mem_rows] = np.linspace(40, 160, k, dtype=np.float32)
    base[-1] = 0.0
    R_np = np.zeros_like(base)
    R_jx = np.zeros_like(base)
    F_np = level_accumulate(plan.lv, base.copy(), clamp=False, R_out=R_np,
                            backend="numpy")
    F_jx = level_accumulate(plan.lv, base.copy(), clamp=False, R_out=R_jx,
                            backend="jax")
    assert np.array_equal(F_np, F_jx)
    assert np.array_equal(R_np, R_jx)

    # and blockwise, the union pass equals each member's own plan run
    # on the same dtype (block-diagonal exactness on the jax path too)
    from repro.core.scheduler import _ReplayPlan, _event_loop
    for i, g in enumerate(members):
        _, topo, O_mem, O_alu = _event_loop(
            g.is_mem, g._sim_lists(), 2, 80.0, 1.0, 3, record=True)
        mplan = _ReplayPlan(g, topo, O_mem, O_alu, 2, 3)
        mb = np.concatenate(
            [base[suite.offsets[i]:suite.offsets[i + 1]], base[-1:]])
        mF = level_accumulate(mplan.lv, mb.copy(), clamp=False,
                              R_out=np.zeros_like(mb), backend="jax")
        assert np.array_equal(
            mF[:-1], F_jx[suite.offsets[i]:suite.offsets[i + 1]])


def test_segment_reductions():
    from repro.core import segment_max_rows, segment_sum_rows

    F = np.arange(12.0).reshape(6, 2)
    ptr = np.array([0, 2, 2, 5, 6])
    mx = segment_max_rows(F, ptr, empty=-1.0)
    assert np.array_equal(mx, [[2.0, 3.0], [-1.0, -1.0], [8.0, 9.0],
                               [10.0, 11.0]])
    sm = segment_sum_rows(F, ptr)
    assert np.array_equal(sm, [[2.0, 4.0], [0.0, 0.0], [18.0, 21.0],
                               [10.0, 11.0]])
    # 1-D values and the all-empty edge
    assert np.array_equal(segment_max_rows(np.arange(3.0), [0, 3]), [2.0])
    assert np.array_equal(segment_max_rows(np.zeros(0), [0, 0, 0]),
                          [0.0, 0.0])
    # rows beyond seg_ptr[-1] (e.g. the replay's sentinel row) belong to
    # no segment and must not leak into the last one
    assert np.array_equal(segment_max_rows(np.arange(10.0).reshape(5, 2),
                                           [0, 2]), [[2.0, 3.0]])
    assert np.array_equal(segment_sum_rows(np.arange(10.0).reshape(5, 2),
                                           [0, 2]), [[2.0, 4.0]])


def test_simulate_batch_jax_backend_exact():
    """The batched simulator stays bit-identical to the reference when the
    jax backend is requested (on non-x64 jax the replay runs through the
    error-bounded float32 device mode with per-column float64 demotion —
    see tests/test_replay_dtype.py; with x64, finish and ready times both
    come off the accelerator path in float64)."""
    g = _random_edag(11)
    alphas = [50.0, 125.0, 300.0]
    got = simulate_batch(g, alphas, m=3, compute_slots=2, backend="jax")
    want = np.array([simulate_reference(g, m=3, alpha=a, compute_slots=2)
                     for a in alphas])
    assert np.array_equal(got, want)


def test_t_inf_sweep_mem_auto_chunk_matches_fixed():
    g = _random_edag(5)
    alphas = np.linspace(10.0, 400.0, 23)
    auto = g.t_inf_sweep_mem(alphas)             # trace-size-aware default
    assert np.array_equal(auto, g.t_inf_sweep_mem(alphas, chunk=1))
    assert np.array_equal(auto, g.t_inf_sweep_mem(alphas, chunk=7))
    from repro.core.graph import _auto_sweep_chunk, _SWEEP_CHUNK_MAX
    assert _auto_sweep_chunk(10) == _SWEEP_CHUNK_MAX       # tiny trace
    assert _auto_sweep_chunk(10_000_000) == 4              # huge trace


def test_jax_backend_float64_stays_exact():
    """Without the x64 flag jax would truncate float64 to float32; the
    dispatch must keep such inputs bit-exact (numpy guard) rather than
    hand back silently drifted values in a float64 array."""
    g = _random_edag(13)
    lv = g._level_csr()
    rng = np.random.default_rng(99)
    base = rng.standard_normal((g.n_vertices, 3)) * 1e7
    F_np = level_accumulate(lv, base.copy(), backend="numpy")
    F_jx = level_accumulate(lv, base.copy(), backend="jax")
    assert F_jx.dtype == np.float64
    assert np.array_equal(F_np, F_jx)


# ------------------------------------------------- thread-safe stat counters

def test_stats_counters_exact_under_concurrency():
    """The analysis service runs concurrent batches; ``stats[k] += 1`` is
    a non-atomic read-modify-write, so the counters are a locked Stats
    map — hammered increments must land exactly."""
    import threading

    from repro.core.counters import Stats

    s = Stats(a=0, b=0)
    N, T = 5000, 8

    def worker():
        for _ in range(N):
            s.add("a")
            s.add("b", 2)

    threads = [threading.Thread(target=worker) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert s["a"] == N * T and s["b"] == 2 * N * T
    s.reset()
    assert s["a"] == 0 and dict(s) == {"a": 0, "b": 0}


def test_stats_keeps_dict_shaped_read_api():
    from repro.core.counters import Stats

    s = Stats(x=1, y=2)
    assert dict(s) == {"x": 1, "y": 2} and dict(**s) == {"x": 1, "y": 2}
    assert sorted(s.keys()) == ["x", "y"] and len(s) == 2 and "x" in s
    assert s.snapshot() == {"x": 1, "y": 2}
    s["x"] = 7
    assert s["x"] == 7
    with pytest.raises(KeyError):
        s.add("typo")
    with pytest.raises(KeyError):
        s["typo"] = 1


def test_backend_and_cache_stats_are_thread_safe_maps():
    from repro.core import backend as backend_mod
    from repro.core import schedule_cache as sched_cache
    from repro.core.counters import Stats

    assert isinstance(backend_mod.stats, Stats)
    assert isinstance(sched_cache.stats, Stats)


# ----------------------------- the device pass: tiling, failures, routing

@pytest.fixture
def x64_off():
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", was)


def _int_case(seed: int = 3, k: int = 3):
    g = _random_edag(seed, n=60)
    rng = np.random.default_rng(seed)
    base = rng.integers(1, 9, (g.n_vertices, k)).astype(np.float32)
    return g._level_csr(), base


def test_padded_rows_fill_whole_lanes():
    lv, _ = _int_case()
    segments = bk._jax_padded(lv).segments
    assert segments
    for gather, qg, off in segments:
        assert gather.shape[1] % 128 == 0 and qg is None
        assert off.shape == gather.shape[:1]


def test_row_block_tiles_the_level():
    assert bk._row_block(256, 2, 11) == 256           # one block fits
    tr = bk._row_block(22016, 7, 9)                   # a wide level
    assert tr < 22016 and 22016 % tr == 0 and tr % 8 == 0
    assert tr <= bk._ROW_TILE


def test_pallas_row_blocks_bit_identical(monkeypatch):
    """A level split over many grid steps gives the same bits as one."""
    monkeypatch.setattr(bk, "_STEP_VMEM_BYTES", 1)   # 8-row blocks
    monkeypatch.setattr(bk, "_JAX_CACHE", OrderedDict())
    lv, base = _int_case()
    F_np = level_accumulate(lv, base.copy(), backend="numpy")
    F_jx = level_accumulate(lv, base.copy(), backend="jax")
    assert np.array_equal(F_np, F_jx)


def _entry(name, lv, F):
    if name == "level_accumulate":
        return level_accumulate(lv, F, backend="jax")
    return bk.replay_accumulate(lv, F.astype(np.float64),
                                np.ones(F.shape[1]), backend="jax",
                                replay_dtype="float32")


@pytest.mark.parametrize("entry", ["level_accumulate", "replay_accumulate"])
def test_device_failure_propagates_with_plan_shape(monkeypatch, x64_off,
                                                   entry):
    """A device pass that fails (compile, run, memory) reaches the caller
    naming the plan shape; nothing quietly runs on numpy instead."""
    def broken(has_q, clamp, want_r):
        def run(*args):
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return run

    monkeypatch.setattr(bk, "_level_loop", broken)
    monkeypatch.setattr(bk, "_JAX_CACHE", OrderedDict())
    lv, base = _int_case()
    bk.reset_stats()
    with pytest.raises(bk.DeviceReplayError,
                       match=r"\(L, Rmax, Dmax\)=.*RESOURCE_EXHAUSTED") as ei:
        _entry(entry, lv, base)
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert bk.stats["numpy_chunks"] == 0 and bk.stats["jax_chunks"] == 0


@pytest.mark.parametrize("entry", ["level_accumulate", "replay_accumulate"])
def test_kernel_fault_hook_propagates(monkeypatch, x64_off, entry):
    class Boom(RuntimeError):
        pass

    def hook():
        raise Boom("injected")

    monkeypatch.setattr(bk, "fault_hook", hook)
    lv, base = _int_case()
    with pytest.raises(Boom):
        _entry(entry, lv, base)


def test_float64_routing_is_decided_up_front_and_counted(monkeypatch,
                                                         x64_off):
    """float64 input the device cannot run exactly goes to the numpy
    kernel before any device call, and is counted: without the x64 flag,
    and on a TPU even with it."""
    lv, base = _int_case()
    F64 = base.astype(np.float64)
    want = level_accumulate(lv, F64.copy(), backend="numpy")
    bk.reset_stats()
    assert np.array_equal(level_accumulate(lv, F64.copy(), backend="jax"),
                          want)
    level_accumulate(lv, base.copy(), backend="jax")     # f32: on device
    assert bk.stats["numpy_f64_passes"] == 1
    monkeypatch.setattr(bk, "on_tpu", lambda: True)
    monkeypatch.setattr(bk, "_accumulate_jax", None)     # never reached
    jax.config.update("jax_enable_x64", True)
    assert np.array_equal(level_accumulate(lv, F64.copy(), backend="jax"),
                          want)
    assert bk.stats["numpy_f64_passes"] == 2


def test_float64_device_policy_refused_on_tpu(monkeypatch):
    from repro.core.plan import ExecPolicy
    lv, base = _int_case()
    assert ("jax", "float64") in [(r.backend, r.replay_dtype)
                                  for r in ExecPolicy().ladder()]
    monkeypatch.setattr(bk, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="TPU"):
        bk.replay_accumulate(lv, base.astype(np.float64),
                             np.ones(base.shape[1]), backend="jax",
                             replay_dtype="float64")
    # the service ladder leaves the x64 rung out rather than run numpy
    # under its name
    rungs = [(r.backend, r.replay_dtype) for r in ExecPolicy().ladder()]
    assert rungs == [(None, None), ("numpy", None)]


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_dir_from_environment(monkeypatch, tmp_path,
                                            cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert bk.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_dir_defaults_to_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert bk.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
