"""Persistent schedule cache: digests, hit/miss/invalidation, safety.

The cache may only ever save time: every test that exercises a cache hit
also asserts bit-identical makespans against the retained heapq
reference, including adversarial cases where the cached entry is
corrupt, malformed, or a well-formed schedule for the *wrong* machine
configuration.
"""
import numpy as np
import pytest

from repro.core import (EDag, latency_sweep, simulate_reference,
                        sweep_grid, schedule_cache as sc)
from repro.core.scheduler import _build_plan, _schedule


def _disk_plan(g, m, cs, topo, O_mem, O_alu, level):
    """The plan the schedule lookup builds from arrays the disk tier hands
    it, or None where the lookup refuses them and records afresh (the
    plan builder recomputes a level assignment that does not fit the
    augmented edges)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sc, "load", lambda *key: (topo, O_mem, O_alu, level))
        mp.setattr(sc, "min_vertices", lambda: 0)
        sched, _, mk0 = _schedule(g, m, cs, 1.0, 100.0, lookup=True)
    return _build_plan([sched]) if mk0 is None else None


def build_graph(seed: int = 0, n: int = 60, p_edge: float = 0.1,
                label: str = "") -> EDag:
    rng = np.random.default_rng(seed)
    g = EDag()
    for i in range(n):
        g.add_vertex(is_mem=bool(rng.random() < 0.5), nbytes=8.0,
                     label=label)
        for j in range(i):
            if rng.random() < p_edge:
                g.add_edge(j, i)
    g._finalize()
    return g


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """Redirect the schedule cache to a private tmp dir, no size floor."""
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", str(tmp_path))
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MIN", "0")
    sc.reset_stats()
    return tmp_path


# ------------------------------------------------------------------ digests

def test_trace_digest_deterministic_across_objects():
    assert build_graph().trace_digest() == build_graph().trace_digest()


def test_trace_digest_ignores_costs_and_labels():
    a = build_graph(label="x")
    b = build_graph(label="y")
    assert a.trace_digest() == b.trace_digest()
    c = EDag()
    d = EDag()
    c.add_vertex(cost=1.0, is_mem=True)
    d.add_vertex(cost=7.0, is_mem=True, nbytes=64.0)
    assert c.trace_digest() == d.trace_digest()


def test_trace_digest_changes_on_mutation():
    g = build_graph()
    d0 = g.trace_digest()
    g.add_vertex(is_mem=False)
    d1 = g.trace_digest()
    assert d1 != d0
    g.add_edge(0, g.n_vertices - 1)
    d2 = g.trace_digest()
    assert d2 != d1
    # flipping a memory classification is a different trace too
    h = EDag()
    h.add_vertex(is_mem=True)
    k = EDag()
    k.add_vertex(is_mem=False)
    assert h.trace_digest() != k.trace_digest()


# ------------------------------------------------------------ store / load

def test_store_load_roundtrip(cache_env):
    g = build_graph()
    topo = np.arange(g.n_vertices, dtype=np.int64)
    O_mem = np.flatnonzero(g.is_mem).astype(np.int64)
    O_alu = np.zeros(0, dtype=np.int64)
    level = np.zeros(g.n_vertices, dtype=np.int64)
    assert sc.store(g.trace_digest(), 4, 0, g.n_vertices, 1.0,
                    topo, O_mem, O_alu, level)
    got = sc.load(g.trace_digest(), 4, 0, g.n_vertices, 1.0)
    assert got is not None
    t, om, oa, lv = got
    assert np.array_equal(t, topo) and np.array_equal(om, O_mem)
    assert np.array_equal(oa, O_alu) and np.array_equal(lv, level)
    # wrong key dimensions miss
    assert sc.load(g.trace_digest(), 3, 0, g.n_vertices, 1.0) is None
    assert sc.load(g.trace_digest(), 4, 1, g.n_vertices, 1.0) is None
    assert sc.load(g.trace_digest(), 4, 0, g.n_vertices, 2.0) is None
    assert sc.load(g.trace_digest(), 4, 0, g.n_vertices + 1, 1.0) is None


def test_delta_encoding_roundtrip_nonmonotone(cache_env):
    """Issue orders are not monotone — the int32 delta encoding must
    roundtrip arbitrary valid (in-range) schedules exactly, and the
    stored arrays must actually be int32 deltas (the compaction)."""
    g = build_graph(seed=5)
    n = g.n_vertices
    rng = np.random.default_rng(0)
    topo = rng.permutation(n).astype(np.int64)
    O_mem = rng.permutation(np.flatnonzero(g.is_mem)).astype(np.int64)
    O_alu = rng.permutation(np.flatnonzero(~g.is_mem)).astype(np.int64)
    level = rng.integers(0, n, size=n).astype(np.int64)
    assert sc.store(g.trace_digest(), 4, 2, n, 1.0, topo, O_mem, O_alu,
                    level)
    got = sc.load(g.trace_digest(), 4, 2, n, 1.0)
    assert got is not None
    for want, have in zip((topo, O_mem, O_alu, level), got):
        # decoded arrays stay int32 (the engine-wide index discipline —
        # adopting them costs no second full-width copy)
        assert have.dtype == np.int32 and np.array_equal(want, have)
    (entry,) = list(cache_env.glob("*.npz"))
    with np.load(entry) as z:
        assert int(z["format"]) == 3
        for key in sc._ARRAY_KEYS:
            assert z[key].dtype == np.int32


def test_store_refuses_unencodable_arrays(cache_env):
    """Schedules the int32 delta encoding cannot represent are refused at
    store time rather than written lossily."""
    g = build_graph()
    n = g.n_vertices
    topo = np.arange(n, dtype=np.int64)
    O_mem = np.flatnonzero(g.is_mem).astype(np.int64)
    O_alu = np.zeros(0, dtype=np.int64)
    ok_level = np.zeros(n, dtype=np.int64)
    bad = [
        dict(level=np.arange(n, dtype=np.int64) - 10 ** 6),  # negative
        dict(level=np.arange(n, dtype=np.int64) * 2 ** 40),  # > int32 ids
        dict(level=np.stack([ok_level, ok_level])),          # wrong ndim
        dict(topo=topo.astype(np.int64) + 2 ** 31),          # out of range
    ]
    for kw in bad:
        args = dict(topo=topo, O_mem=O_mem, O_alu=O_alu, level=ok_level)
        args.update(kw)
        assert not sc.store(g.trace_digest(), 4, 0, n, 1.0, **args)
    assert list(cache_env.glob("*.npz")) == []


def test_old_format_entry_rejected_and_rerecorded(cache_env):
    """A format-2 (pre-delta-encoding) entry at the right path must miss
    — no in-place migration, no crash — and the sweep re-records."""
    g = build_graph(seed=7)
    n = g.n_vertices
    alphas = [50.0, 100.0, 200.0]
    want = np.array([simulate_reference(g, alpha=a) for a in alphas])
    path = sc._entry_path(cache_env, g.trace_digest(), 4, 0, 1.0)
    np.savez_compressed(
        path, format=2, digest=g.trace_digest(), n=n, unit=1.0, m=4,
        compute_slots=0, topo=np.arange(n, dtype=np.int64),
        O_mem=np.flatnonzero(g.is_mem).astype(np.int64),
        O_alu=np.zeros(0, dtype=np.int64),
        level=np.zeros(n, dtype=np.int64))
    assert sc.load(g.trace_digest(), 4, 0, n, 1.0) is None
    sc.reset_stats()
    assert np.array_equal(latency_sweep(build_graph(seed=7), alphas), want)
    assert sc.stats["record_runs"] == 1


def test_wrong_dtype_delta_arrays_rejected(cache_env):
    """A format-3 entry whose stored arrays are not int32 deltas (a
    corrupt or foreign writer) must miss."""
    g = build_graph(seed=12)
    n = g.n_vertices
    topo = np.arange(n, dtype=np.int64)
    O_mem = np.flatnonzero(g.is_mem).astype(np.int64)
    assert sc.store(g.trace_digest(), 4, 0, n, 1.0, topo, O_mem,
                    np.zeros(0, dtype=np.int64),
                    np.zeros(n, dtype=np.int64))
    (entry,) = list(cache_env.glob("*.npz"))
    with np.load(entry) as z:
        fields = {k: z[k] for k in z.files}
    fields["topo_d"] = fields["topo_d"].astype(np.float64)
    np.savez_compressed(entry, **fields)
    assert sc.load(g.trace_digest(), 4, 0, n, 1.0) is None


def test_delta_encoding_compacts_entries(cache_env):
    """The point of the compaction: a real traced kernel's schedule (the
    structured, strongly-correlated case the ROADMAP scale target is
    about) stored via deltas takes well under half the bytes of the
    raw-int64 format-2 layout it replaces."""
    from repro.apps import polybench

    g = polybench.trace_kernel("gemm", 10)
    latency_sweep(g, [50.0, 100.0, 200.0], m=4)
    (entry,) = list(cache_env.glob("*.npz"))
    new_size = entry.stat().st_size
    with np.load(entry) as z:
        arrays = {k: np.cumsum(z[k].astype(np.int64))
                  for k in sc._ARRAY_KEYS}
    old = cache_env / "old_format.npz"
    with open(old, "wb") as f:
        np.savez_compressed(f, **arrays)
    assert new_size < 0.5 * old.stat().st_size


def test_load_rejects_corrupt_entry(cache_env):
    g = build_graph()
    topo = np.arange(g.n_vertices, dtype=np.int64)
    O_mem = np.flatnonzero(g.is_mem).astype(np.int64)
    sc.store(g.trace_digest(), 4, 0, g.n_vertices, 1.0, topo, O_mem,
             np.zeros(0, dtype=np.int64),
             np.zeros(g.n_vertices, dtype=np.int64))
    (entry,) = list(cache_env.glob("*.npz"))
    entry.write_bytes(b"definitely not a zip archive")
    assert sc.load(g.trace_digest(), 4, 0, g.n_vertices, 1.0) is None


def test_disabled_and_threshold_write_nothing(cache_env, monkeypatch):
    g = build_graph()
    alphas = [50.0, 100.0, 200.0]
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", "off")
    latency_sweep(g, alphas)
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", str(cache_env))
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MIN", "1000000")
    latency_sweep(build_graph(seed=1), alphas)
    assert list(cache_env.glob("*.npz")) == []


def test_prune_cap(cache_env, monkeypatch):
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MAX", "2")
    g = build_graph()
    alphas = [50.0, 100.0, 200.0]
    sweep_grid(g, alphas, ms=[1, 2, 3, 4], compute_slots=[0])
    assert len(list(cache_env.glob("*.npz"))) <= 2
    assert sc.clear() >= 1
    assert list(cache_env.glob("*.npz")) == []


# ------------------------------------------------------- hits and validity

def test_disk_hit_skips_recording_and_stays_exact(cache_env):
    alphas = [50.0, 100.0, 150.0, 300.0]
    cold = latency_sweep(build_graph(), alphas, m=3, compute_slots=2)
    assert sc.stats["record_runs"] == 1 and sc.stats["stores"] == 1

    sc.reset_stats()
    g2 = build_graph()            # fresh object: simulates a new process
    warm = latency_sweep(g2, alphas, m=3, compute_slots=2)
    assert sc.stats["disk_hits"] == 1 and sc.stats["record_runs"] == 0
    assert np.array_equal(cold, warm)
    want = np.array([simulate_reference(g2, m=3, alpha=a, compute_slots=2)
                     for a in alphas])
    assert np.array_equal(warm, want)

    # same object again: the in-process memo answers, not the disk
    sc.reset_stats()
    assert np.array_equal(
        latency_sweep(g2, alphas, m=3, compute_slots=2), want)
    assert sc.stats["memory_hits"] == 1 and sc.stats["disk_hits"] == 0
    assert sc.stats["record_runs"] == 0


def test_mutated_trace_misses_and_rerecords(cache_env):
    alphas = [50.0, 100.0, 200.0]
    g = build_graph()
    latency_sweep(g, alphas)
    g.add_vertex(is_mem=True)         # mutation: new digest, stale entry
    sc.reset_stats()
    got = latency_sweep(g, alphas)
    assert sc.stats["misses"] == 1 and sc.stats["record_runs"] == 1
    want = np.array([simulate_reference(g, alpha=a) for a in alphas])
    assert np.array_equal(got, want)


def test_wrong_machine_schedule_is_rejected_by_verification(cache_env):
    """A well-formed cached schedule for the wrong (m, compute_slots) must
    fall through per-point verification to a fresh recording, keeping the
    result bit-identical — the cache can never change answers."""
    from repro.core.scheduler import _event_loop

    g = build_graph(seed=3)
    alphas = [50.0, 100.0, 200.0]
    # record a legitimate schedule under m=1, then plant it under m=4's key
    _, topo, O_mem, O_alu = _event_loop(
        g.is_mem, g._sim_lists(), 1, 50.0, 1.0, 0, record=True)
    sc.store(g.trace_digest(), 4, 0, g.n_vertices, 1.0, topo, O_mem,
             O_alu, np.zeros(g.n_vertices, dtype=np.int64))
    got = latency_sweep(build_graph(seed=3), alphas, m=4)
    want = np.array([simulate_reference(g, m=4, alpha=a) for a in alphas])
    assert np.array_equal(got, want)


def test_plan_from_cache_rejects_malformed_arrays():
    g = build_graph(seed=4)
    n = g.n_vertices
    topo = np.arange(n, dtype=np.int64)
    O_mem = np.flatnonzero(g.is_mem).astype(np.int64)
    O_alu = np.flatnonzero(~g.is_mem).astype(np.int64)
    level = None
    # sane baseline: identity order is a linear extension (vids are topo)
    assert _disk_plan(g, 4, 2, topo, O_mem, O_alu, level) is not None
    bad = [
        (topo[:-1], O_mem, O_alu),                      # wrong length
        (np.zeros(n, dtype=np.int64), O_mem, O_alu),    # not a permutation
        (topo - 1, O_mem, O_alu),                       # out of range
        (topo, O_mem[::-1][1:], O_alu),                 # wrong O_mem length
        (topo, O_alu[:len(O_mem)], O_alu),              # not the mem set
        (topo, O_mem, O_alu[:-1]),                      # wrong O_alu length
    ]
    for t, om, oa in bad:
        assert _disk_plan(g, 4, 2, t, om, oa, None) is None
    # cs=0 requires an empty ALU order
    assert _disk_plan(g, 4, 0, topo, O_mem, O_alu, None) is None
    # a garbage persisted level is repaired (levelize fallback), not trusted
    junk_level = np.zeros(n, dtype=np.int64)
    plan = _disk_plan(g, 4, 2, topo, O_mem, O_alu, junk_level)
    assert plan is not None
    if g.n_edges:
        blk = plan.blocks[0]
        lv, rank = blk.level, blk.rank
        assert (lv[rank[g.src]] < lv[rank[g.dst]]).all()


def test_malformed_level_and_shape_entries_degrade_gracefully(cache_env):
    """Adversarial persisted arrays — monotone-but-negative levels, huge
    level values (a would-be OOM in the partition builder), 2-D arrays —
    must degrade to a fresh recording, never crash or change results."""
    g = build_graph(seed=6)
    n = g.n_vertices
    alphas = [50.0, 100.0, 200.0]
    want = np.array([simulate_reference(g, m=4, alpha=a) for a in alphas])
    topo = np.arange(n, dtype=np.int64)
    O_mem = np.flatnonzero(g.is_mem).astype(np.int64)
    O_alu = np.zeros(0, dtype=np.int64)
    digest = g.trace_digest()
    bad_levels = [
        np.arange(n, dtype=np.int64) - 10 ** 6,   # monotone but negative
        np.arange(n, dtype=np.int64) * 2 ** 40,   # monotone but enormous
        np.stack([np.arange(n)] * 2).astype(np.int64),  # wrong ndim
    ]
    for lvl in bad_levels:
        sc.store(digest, 4, 0, n, 1.0, topo, O_mem, O_alu, lvl)
        got = latency_sweep(build_graph(seed=6), alphas, m=4)
        assert np.array_equal(got, want)
    # 2-D topo in an otherwise plausible entry
    sc.store(digest, 4, 0, n, 1.0, np.stack([topo, topo]), O_mem, O_alu,
             np.zeros(n, dtype=np.int64))
    # store() flattens nothing — n-length check happens on load
    got = latency_sweep(build_graph(seed=6), alphas, m=4)
    assert np.array_equal(got, want)


def test_memo_keyed_by_unit_and_stale_plan_replaced(cache_env):
    """Different unit costs are different schedules: the memo must not
    serve a unit=1 plan to a unit=2 sweep, and once the fresh plan is
    recorded it must be memoized so later unit=2 sweeps skip recording."""
    g = build_graph(seed=8)
    alphas = [50.0, 100.0, 200.0]
    latency_sweep(g, alphas, m=4, unit=1.0)
    sc.reset_stats()
    got = latency_sweep(g, alphas, m=4, unit=2.0)
    want = np.array([simulate_reference(g, m=4, alpha=a, unit=2.0)
                     for a in alphas])
    assert np.array_equal(got, want)
    first_records = sc.stats["record_runs"]
    assert first_records >= 1          # unit=1 plan was not blindly reused
    sc.reset_stats()
    assert np.array_equal(latency_sweep(g, alphas, m=4, unit=2.0), want)
    assert sc.stats["record_runs"] == 0 and sc.stats["memory_hits"] == 1


def test_renamed_entry_rejected_by_stored_fields(cache_env):
    """Copying/renaming an entry to another (m, cs) key must miss: the
    stored fields are cross-checked against the requested key."""
    import shutil

    g = build_graph(seed=9)
    latency_sweep(g, [50.0, 100.0, 200.0], m=2)
    (entry,) = list(cache_env.glob("*.npz"))
    fake = cache_env / entry.name.replace("_m2_", "_m4_")
    shutil.copy(entry, fake)
    assert sc.load(g.trace_digest(), 4, 0, g.n_vertices, 1.0) is None


def test_backward_slot_chain_rejected():
    g = EDag()
    for _ in range(3):
        g.add_vertex(is_mem=True)
    g._finalize()
    topo = np.arange(3, dtype=np.int64)
    empty = np.zeros(0, dtype=np.int64)
    # O_mem chain 1 -> 0 runs backward in topo rank under m=1
    assert _disk_plan(g, 1, 0, topo, np.array([1, 0, 2], dtype=np.int64),
                      empty, None) is None
    assert _disk_plan(g, 1, 0, topo, np.array([0, 1, 2], dtype=np.int64),
                      empty, None) is not None


def test_foreign_digest_entry_rejected(cache_env):
    """An entry copied from a different trace with identical n/m/cs/unit
    must miss: the digest stored inside the entry is cross-checked."""
    import shutil

    g1 = build_graph(seed=10)
    g2 = build_graph(seed=11)       # same n, different edges/is_mem
    assert g1.n_vertices == g2.n_vertices
    assert g1.trace_digest() != g2.trace_digest()
    latency_sweep(g1, [50.0, 100.0, 200.0], m=2)
    (entry,) = list(cache_env.glob("*.npz"))
    fake = cache_env / (g2.trace_digest()[:32] +
                        entry.name[len(g1.trace_digest()[:32]):])
    shutil.copy(entry, fake)
    assert sc.load(g2.trace_digest(), 2, 0, g2.n_vertices, 1.0) is None


def test_partially_stale_plan_is_replaced(cache_env):
    """A reused plan that fails part of a sweep gets replaced by that
    sweep's fresh recording, so repeated sweeps converge instead of
    re-paying the serial recording forever."""
    g = build_graph(seed=0, n=80)
    latency_sweep(g, [50.0, 100.0, 200.0], m=2, compute_slots=1)
    tie_alphas = [0.5, 1.0, 2.0, 3.0]
    want = np.array([simulate_reference(g, m=2, alpha=a, compute_slots=1)
                     for a in tie_alphas])
    sc.reset_stats()
    assert np.array_equal(
        latency_sweep(g, tie_alphas, m=2, compute_slots=1), want)
    # the memoized 50-cycle schedule cannot certify the tie-heavy points;
    # the sweep re-records and persists the replacement
    assert sc.stats["record_runs"] >= 1 and sc.stats["stores"] >= 1


def test_reversed_topo_not_linear_extension():
    g = EDag()
    a = g.add_vertex(is_mem=True)
    b = g.add_vertex(is_mem=True)
    g.add_edge(a, b)
    g._finalize()
    topo = np.array([1, 0], dtype=np.int64)     # violates the edge
    O_mem = np.array([0, 1], dtype=np.int64)
    assert _disk_plan(g, 2, 0, topo, O_mem,
                      np.zeros(0, dtype=np.int64), None) is None


# -------------------------------------------------- concurrent store/prune

def _store_n_entries(g, count):
    """Persist ``count`` distinct entries for one graph (varying m)."""
    n = g.n_vertices
    topo = np.arange(n, dtype=np.int64)
    O_mem = np.flatnonzero(g.is_mem).astype(np.int64)
    O_alu = np.zeros(0, dtype=np.int64)
    level = np.zeros(n, dtype=np.int64)
    for m in range(1, count + 1):
        assert sc.store(g.trace_digest(), m, 0, n, 1.0, topo, O_mem,
                        O_alu, level)


def test_prune_tolerates_concurrently_vanished_entries(cache_env,
                                                       monkeypatch):
    """Deterministic replay of the race: an entry deleted between the
    pruner's directory listing and its ``stat`` must be skipped — not
    crash the pruner, and not abort pruning the remaining entries."""
    import os
    import pathlib

    g = build_graph(seed=21)
    _store_n_entries(g, 6)
    entries = sorted(cache_env.glob("*.npz"))
    assert len(entries) == 6
    victim = entries[0]
    orig_stat = pathlib.Path.stat

    def racy_stat(self, **kw):
        if self == victim and os.path.exists(str(self)):
            os.unlink(str(self))     # a concurrent process deletes it now
        return orig_stat(self, **kw)

    monkeypatch.setattr(pathlib.Path, "stat", racy_stat)
    gone = sc.prune(cap=2)
    monkeypatch.undo()
    # the victim vanished mid-prune; the survivors were still pruned to
    # the cap (5 statted entries, cap 2 -> 3 unlinked by the pruner)
    assert gone == 3
    assert len(list(cache_env.glob("*.npz"))) == 2


def test_prune_tolerates_unlink_race(cache_env, monkeypatch):
    """An entry deleted between ``stat`` and ``unlink`` (a concurrent
    pruner won) is skipped, and the rest still go."""
    import os
    import pathlib

    g = build_graph(seed=22)
    _store_n_entries(g, 5)
    victim = sorted(cache_env.glob("*.npz"))[0]
    orig_unlink = pathlib.Path.unlink

    def racy_unlink(self, **kw):
        if self == victim and os.path.exists(str(self)):
            os.unlink(str(self))     # the other pruner got there first
        return orig_unlink(self, **kw)

    monkeypatch.setattr(pathlib.Path, "unlink", racy_unlink)
    sc.prune(cap=1)
    monkeypatch.undo()
    assert len(list(cache_env.glob("*.npz"))) == 1


def test_concurrent_store_prune_two_processes(cache_env, monkeypatch):
    """Two live processes sharing one cache directory — one storing (and
    auto-pruning), one aggressively pruning — must both run to completion
    without an exception, alongside the single-process atomic-write
    coverage above."""
    import os
    import subprocess
    import sys
    import time

    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MAX", "4")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    child_code = (
        "import sys, time\n"
        f"sys.path.insert(0, {src!r})\n"
        "from repro.core import schedule_cache as sc\n"
        "deadline = time.time() + 3.0\n"
        "prunes = 0\n"
        "while time.time() < deadline:\n"
        "    sc.prune(cap=1)\n"
        "    prunes += 1\n"
        "print('PRUNES', prunes)\n")
    child = subprocess.Popen([sys.executable, "-c", child_code],
                             env=dict(os.environ),
                             stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True)
    g = build_graph(seed=23)
    deadline = time.time() + 2.5
    stored = 0
    while time.time() < deadline:
        _store_n_entries(g, 4)       # each store also prunes to the cap
        stored += 4
    out, err = child.communicate(timeout=30)
    assert child.returncode == 0, err
    assert "PRUNES" in out
    assert stored > 0
    # whatever survived the races is a well-formed, loadable set
    for p in cache_env.glob("*.npz"):
        try:
            with np.load(p) as z:
                assert int(z["format"]) == sc._FORMAT
        except OSError:
            pass                     # deleted between glob and open: fine


# ----------------------------------------------------- quarantine-on-load

def test_corrupt_entry_quarantined_then_warm(cache_env):
    """A corrupt entry is renamed to *.bad on load (freeing the key), the
    re-recording persists a replacement, and a later fresh process gets a
    disk hit — one recording warms everyone, instead of every process
    re-recording against the same damaged file forever."""
    alphas = [50.0, 100.0, 200.0]
    want = latency_sweep(build_graph(seed=30), alphas, m=3)
    (entry,) = list(cache_env.glob("*.npz"))
    entry.write_bytes(b"definitely not a zip archive")
    sc.reset_stats()
    got = latency_sweep(build_graph(seed=30), alphas, m=3)
    assert np.array_equal(got, want)
    assert sc.stats["quarantined"] == 1 and sc.stats["record_runs"] == 1
    assert (cache_env / (entry.name + ".bad")).exists()  # moved aside...
    assert len(list(cache_env.glob("*.npz"))) == 1       # ...re-recorded
    assert entry.exists()       # the key path now holds the fresh entry
    sc.reset_stats()
    warm = latency_sweep(build_graph(seed=30), alphas, m=3)
    assert np.array_equal(warm, want)
    assert sc.stats["disk_hits"] == 1 and sc.stats["record_runs"] == 0


def test_old_format_entry_quarantined(cache_env):
    """Old-format entries take the same quarantine path as corrupt ones:
    renamed aside, never migrated in place."""
    g = build_graph(seed=31)
    n = g.n_vertices
    path = sc._entry_path(cache_env, g.trace_digest(), 4, 0, 1.0)
    np.savez_compressed(
        path, format=2, digest=g.trace_digest(), n=n, unit=1.0, m=4,
        compute_slots=0, topo=np.arange(n, dtype=np.int64),
        O_mem=np.flatnonzero(g.is_mem).astype(np.int64),
        O_alu=np.zeros(0, dtype=np.int64),
        level=np.zeros(n, dtype=np.int64))
    sc.reset_stats()
    assert sc.load(g.trace_digest(), 4, 0, n, 1.0) is None
    assert sc.stats["quarantined"] == 1
    assert not path.exists()
    assert path.with_name(path.name + ".bad").exists()


def test_plain_miss_quarantines_nothing(cache_env):
    sc.reset_stats()
    assert sc.load("f" * 64, 4, 0, 10, 1.0) is None
    assert sc.stats["quarantined"] == 0
    assert list(cache_env.glob("*.bad")) == []


def test_quarantine_warns_once(cache_env, caplog, monkeypatch):
    import logging

    monkeypatch.setattr(sc, "_warned_quarantine", False)
    g1, g2 = build_graph(seed=32), build_graph(seed=33)
    for g in (g1, g2):
        latency_sweep(g, [50.0, 100.0], m=2)
    for p in cache_env.glob("*.npz"):
        p.write_bytes(b"garbage")
    with caplog.at_level(logging.WARNING, logger="repro.core.schedule_cache"):
        assert sc.load(g1.trace_digest(), 2, 0, g1.n_vertices, 1.0) is None
        assert sc.load(g2.trace_digest(), 2, 0, g2.n_vertices, 1.0) is None
    warned = [r for r in caplog.records if "quarantined" in r.message]
    assert len(warned) == 1
    assert sc.stats["quarantined"] >= 2


def test_bad_files_counted_against_prune_cap(cache_env, monkeypatch):
    """Quarantined *.bad files are bounded by the same cap as live
    entries — corruption must not grow the directory without limit."""
    g = build_graph(seed=34)
    _store_n_entries(g, 4)
    for p in list(cache_env.glob("*.npz"))[:3]:
        p.write_bytes(b"garbage")
        assert sc.load("x" * 64, 99, 0, 1, 1.0) is None  # unrelated miss
    # quarantine all three corrupted entries via keyed loads
    n = g.n_vertices
    for m in range(1, 5):
        sc.load(g.trace_digest(), m, 0, n, 1.0)
    assert len(list(cache_env.glob("*.npz.bad"))) == 3
    assert sc.prune(cap=2) >= 1
    survivors = (list(cache_env.glob("*.npz")) +
                 list(cache_env.glob("*.npz.bad")))
    assert len(survivors) <= 2


def test_crash_mid_store_leaves_nothing_or_valid(cache_env):
    """SIGKILL while the store's tempfile is being written: a survivor
    process sees either no entry (tmp debris only, which prune bounds) or
    a complete loadable one — never a torn keyed file."""
    import os
    import signal
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    child_code = (
        "import os, sys, time\n"
        f"sys.path.insert(0, {src!r})\n"
        "import numpy as np\n"
        "from repro.core import schedule_cache as sc\n"
        "real_replace = os.replace\n"
        "def slow_replace(a, b):\n"
        "    print('REPLACING', flush=True)\n"
        "    time.sleep(30)\n"
        "    real_replace(a, b)\n"
        "os.replace = slow_replace\n"
        "n = 50\n"
        "sc.store('a' * 64, 4, 0, n, 1.0,\n"
        "         np.arange(n, dtype=np.int64),\n"
        "         np.arange(n, dtype=np.int64),\n"
        "         np.zeros(0, dtype=np.int64),\n"
        "         np.zeros(n, dtype=np.int64))\n")
    child = subprocess.Popen([sys.executable, "-c", child_code],
                             env=dict(os.environ),
                             stdout=subprocess.PIPE, text=True)
    assert child.stdout.readline().strip() == "REPLACING"
    os.kill(child.pid, signal.SIGKILL)   # tmp written, replace pending
    child.wait(timeout=30)
    assert list(cache_env.glob("*.npz")) == []       # nothing keyed
    assert sc.load("a" * 64, 4, 0, 50, 1.0) is None  # survivor: clean miss
    # and the survivor can store + load the same key normally
    n = 50
    assert sc.store("a" * 64, 4, 0, n, 1.0,
                    np.arange(n, dtype=np.int64),
                    np.arange(n, dtype=np.int64),
                    np.zeros(0, dtype=np.int64),
                    np.zeros(n, dtype=np.int64))
    assert sc.load("a" * 64, 4, 0, n, 1.0) is not None


# ------------------------------------------- memory-mapped entries (format 4)

@pytest.fixture
def mmap_env(cache_env, monkeypatch):
    """Force every entry onto the format-4 directory layout."""
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MMAP_MIN", "0")
    return cache_env


def test_mmap_dir_roundtrip_and_backing(mmap_env):
    g = build_graph(seed=40)
    n = g.n_vertices
    rng = np.random.default_rng(1)
    topo = rng.permutation(n).astype(np.int64)
    O_mem = rng.permutation(np.flatnonzero(g.is_mem)).astype(np.int64)
    O_alu = np.zeros(0, dtype=np.int64)
    level = rng.integers(0, n, size=n).astype(np.int64)
    assert sc.store(g.trace_digest(), 4, 0, n, 1.0, topo, O_mem, O_alu,
                    level)
    assert list(mmap_env.glob("*.npz")) == []       # no compressed sibling
    (entry,) = list(mmap_env.glob("*.d"))
    assert entry.is_dir() and (entry / "meta.npz").exists()
    got = sc.load(g.trace_digest(), 4, 0, n, 1.0)
    assert got is not None
    for want, have in zip((topo, O_mem, O_alu, level), got):
        assert np.array_equal(want, have)
        base = have
        while base is not None and not isinstance(base, np.memmap):
            base = getattr(base, "base", None)
        if len(have):
            assert isinstance(base, np.memmap)      # zero-copy load
    # wrong key dimensions still miss
    assert sc.load(g.trace_digest(), 3, 0, n, 1.0) is None
    assert sc.load(g.trace_digest(), 4, 0, n + 1, 1.0) is None


def test_mmap_warm_sweep_bitexact(mmap_env):
    alphas = [50.0, 100.0, 200.0]
    cold = latency_sweep(build_graph(seed=41), alphas, m=3)
    assert sc.stats["record_runs"] == 1 and sc.stats["stores"] == 1
    assert list(mmap_env.glob("*.d")) != []
    sc.reset_stats()
    warm = latency_sweep(build_graph(seed=41), alphas, m=3)
    assert sc.stats["disk_hits"] == 1 and sc.stats["record_runs"] == 0
    assert np.array_equal(cold, warm)
    want = np.array([simulate_reference(build_graph(seed=41), m=3, alpha=a)
                     for a in alphas])
    assert np.array_equal(warm, want)


def test_mmap_corrupt_dir_quarantined_then_warm(mmap_env):
    alphas = [50.0, 100.0, 200.0]
    want = latency_sweep(build_graph(seed=42), alphas, m=2)
    (entry,) = list(mmap_env.glob("*.d"))
    (entry / "meta.npz").write_bytes(b"definitely not a zip archive")
    sc.reset_stats()
    got = latency_sweep(build_graph(seed=42), alphas, m=2)
    assert np.array_equal(got, want)
    assert sc.stats["quarantined"] == 1 and sc.stats["record_runs"] == 1
    assert (entry.parent / (entry.name + ".bad")).is_dir()
    assert entry.is_dir()             # key path holds the fresh entry
    sc.reset_stats()
    assert np.array_equal(latency_sweep(build_graph(seed=42), alphas, m=2),
                          want)
    assert sc.stats["disk_hits"] == 1 and sc.stats["record_runs"] == 0


def test_mmap_truncated_array_rejected(mmap_env):
    g = build_graph(seed=43)
    n = g.n_vertices
    topo = np.arange(n, dtype=np.int64)
    O_mem = np.flatnonzero(g.is_mem).astype(np.int64)
    assert sc.store(g.trace_digest(), 4, 0, n, 1.0, topo, O_mem,
                    np.zeros(0, dtype=np.int64),
                    np.zeros(n, dtype=np.int64))
    (entry,) = list(mmap_env.glob("*.d"))
    np.save(entry / "topo.npy", topo[: n // 2].astype(np.int32))
    assert sc.load(g.trace_digest(), 4, 0, n, 1.0) is None


def test_mmap_prune_removes_directories(mmap_env, monkeypatch):
    g = build_graph(seed=44)
    _store_n_entries(g, 5)
    assert len(list(mmap_env.glob("*.d"))) == 5
    assert sc.prune(cap=2) == 3
    assert len(list(mmap_env.glob("*.d"))) == 2
    assert sc.clear() == 2
    assert list(mmap_env.glob("*.d")) == []


def test_mmap_threshold_selects_format(cache_env, monkeypatch):
    """Below the threshold entries stay compressed .npz; at or above it
    they switch to the directory layout — same key, same contents."""
    g = build_graph(seed=45)
    n = g.n_vertices
    topo = np.arange(n, dtype=np.int64)
    O_mem = np.flatnonzero(g.is_mem).astype(np.int64)
    O_alu = np.zeros(0, dtype=np.int64)
    level = np.zeros(n, dtype=np.int64)
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MMAP_MIN", str(n + 1))
    assert sc.store(g.trace_digest(), 4, 0, n, 1.0, topo, O_mem, O_alu,
                    level)
    assert list(cache_env.glob("*.d")) == []
    assert len(list(cache_env.glob("*.npz"))) == 1
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MMAP_MIN", str(n))
    assert sc.store(g.trace_digest(), 5, 0, n, 1.0, topo, O_mem, O_alu,
                    level)
    assert len(list(cache_env.glob("*.d"))) == 1
    a = sc.load(g.trace_digest(), 4, 0, n, 1.0)
    b = sc.load(g.trace_digest(), 5, 0, n, 1.0)
    assert a is not None and b is not None
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
