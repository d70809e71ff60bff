"""Multi-trace union eDAG suites: the property/differential test layer.

The union engine's contract is blockwise bit-exactness: every per-trace
slice of a suite result must equal the single-trace engine (and hence the
retained heapq reference) exactly — across mixed machine grids, empty and
singleton suites, tie-heavy alphas, cache-cold and cache-warm runs, and
both kernel backends.
"""
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import (EDag, EDagSuite, concat_edags, grid_report,
                        latency_sweep, simulate_reference, suite_grid_report,
                        suite_latency_sweep, suite_sweep_grid,
                        suite_t_inf_sweep, sweep_grid, t_inf_sweep,
                        schedule_cache as sc)

# kernel backends the differential layer must agree under
try:
    import jax  # noqa: F401
    BACKENDS = ("numpy", "jax")
except Exception:  # pragma: no cover - jax ships in the CI image
    BACKENDS = ("numpy",)


def rand_edag(seed: int, n: int, p_edge: float = 0.12,
              p_mem: float = 0.5) -> EDag:
    rng = np.random.default_rng(seed)
    g = EDag()
    for i in range(n):
        g.add_vertex(is_mem=bool(rng.random() < p_mem), nbytes=8.0)
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


# ---------------------------------------------------------------- the union

def test_concat_edags_block_diagonal_structure():
    members = [rand_edag(0, 30), rand_edag(1, 0), rand_edag(2, 12)]
    suite = EDagSuite(members, names=["a", "b", "c"])
    u = suite.union
    assert u.n_vertices == sum(g.n_vertices for g in members)
    assert u.n_edges == sum(g.n_edges for g in members)
    assert np.array_equal(suite.offsets, [0, 30, 30, 42])
    assert np.array_equal(suite.trace_id,
                          np.repeat([0, 1, 2], [30, 0, 12]))
    # blockwise payloads survive the concat
    for k, g in enumerate(members):
        off = suite.offsets[k]
        assert np.array_equal(u.is_mem[off:off + g.n_vertices], g.is_mem)
        assert np.array_equal(u.cost[off:off + g.n_vertices], g.cost)
    # no union edge crosses a block boundary
    tid = suite.trace_id
    assert np.array_equal(tid[u.src], tid[u.dst])
    # union analyses decompose blockwise (t1 sums, spans segment)
    assert u.t1() == sum(g.t1() for g in members)
    lvl = u.level
    for k, g in enumerate(members):
        off = suite.offsets[k]
        assert np.array_equal(lvl[off:off + g.n_vertices], g.level)


def test_suite_rejects_bad_inputs():
    with pytest.raises(TypeError):
        EDagSuite([rand_edag(0, 4), "not an edag"])
    with pytest.raises(ValueError):
        EDagSuite([rand_edag(0, 4)], names=["a", "b"])


def test_suite_refuses_mutated_members():
    """EDags are append-only but mutable; a member grown after suite
    construction would silently misalign the frozen segment arrays, so
    every suite operation must refuse loudly instead."""
    g0, g1 = rand_edag(0, 10), rand_edag(1, 8)
    suite = EDagSuite([g0, g1])
    suite.union                               # build the memoized union
    g0.add_vertex(is_mem=True)                # vertex mutation
    for op in (lambda: suite.union,
               lambda: suite.segment_sum(np.zeros(suite.n_vertices)),
               lambda: suite_sweep_grid(suite, [50.0]),
               lambda: suite_t_inf_sweep(suite, [50.0])):
        with pytest.raises(ValueError, match="mutated"):
            op()
    # edge-only mutation (vertex count unchanged) is caught too
    g2, g3 = rand_edag(2, 10), rand_edag(3, 8)
    suite2 = EDagSuite([g2, g3])
    g3.add_edge(0, g3.n_vertices - 1)
    with pytest.raises(ValueError, match="mutated"):
        suite_sweep_grid(suite2, [50.0])


# ------------------------------------------------- property: grid identity

@st.composite
def suite_cases(draw):
    """Random suite (0-3 members, some possibly empty/tiny) + mixed
    machine grid + tie-heavy alphas (the adversarial case for issue-order
    verification across block boundaries)."""
    k = draw(st.integers(0, 3))
    seed = draw(st.integers(0, 2 ** 31))
    rng = np.random.default_rng(seed)
    sizes = [int(rng.integers(0, 45)) for _ in range(k)]
    members = [rand_edag(seed + i, n) for i, n in enumerate(sizes)]
    ms = sorted({draw(st.integers(1, 5)), draw(st.integers(1, 5))})
    css = sorted({draw(st.integers(0, 4)), draw(st.integers(0, 4))})
    alphas = rng.choice([0.5, 1.0, 2.0, 3.0, 50.0, 200.0, 333.25],
                        size=3, replace=False)
    return EDagSuite(members), ms, css, alphas


@given(suite_cases())
def test_suite_grid_bit_identical_to_stacked_singles(case):
    """Every per-trace slice of the union grid equals the single-trace
    engine exactly — the central differential property."""
    suite, ms, css, alphas = case
    grid = suite_sweep_grid(suite, alphas, ms=ms, compute_slots=css)
    assert grid.shape == (suite.n_traces, len(alphas), len(ms), len(css))
    for k, g in enumerate(suite.members):
        want = sweep_grid(g, alphas, ms=ms, compute_slots=css)
        assert np.array_equal(grid[k], want)


@given(suite_cases())
def test_suite_grid_bit_identical_to_reference(case):
    """And hence to the retained per-point heapq oracle."""
    suite, ms, css, alphas = case
    grid = suite_sweep_grid(suite, alphas, ms=ms, compute_slots=css)
    for k, g in enumerate(suite.members):
        for i, a in enumerate(alphas):
            for j, m in enumerate(ms):
                for l, cs in enumerate(css):
                    want = simulate_reference(g, m=m, alpha=float(a),
                                              compute_slots=cs)
                    assert grid[k, i, j, l] == want, (k, a, m, cs)


def test_empty_and_singleton_suites():
    alphas = [50.0, 200.0]
    empty = EDagSuite([])
    assert suite_sweep_grid(empty, alphas, ms=[2, 4]).shape == (0, 2, 2, 1)
    assert suite_t_inf_sweep(empty, alphas).shape == (0, 2)
    g = rand_edag(7, 35)
    single = EDagSuite([g])
    grid = suite_sweep_grid(single, alphas, ms=[2, 4], compute_slots=[0, 3])
    assert np.array_equal(grid[0], sweep_grid(g, alphas, ms=[2, 4],
                                              compute_slots=[0, 3]))
    # a suite whose only members are empty traces
    hollow = EDagSuite([EDag(), EDag()])
    assert np.array_equal(suite_sweep_grid(hollow, alphas),
                          np.zeros((2, 2, 1, 1)))


def test_suite_alphas_unsorted_and_duplicates_return_caller_order():
    suite = EDagSuite([rand_edag(3, 40), rand_edag(4, 20)])
    alphas = [200.0, 50.0, 200.0, 0.5, 50.0]
    grid = suite_sweep_grid(suite, alphas, ms=[2], compute_slots=[1])
    sweep = suite_latency_sweep(suite, alphas, m=2, compute_slots=1)
    for k, g in enumerate(suite.members):
        want = np.array([simulate_reference(g, m=2, alpha=a,
                                            compute_slots=1)
                         for a in alphas])
        assert np.array_equal(grid[k, :, 0, 0], want)
        assert np.array_equal(sweep[k], want)


def test_suite_degenerate_machine_models_keep_reference_semantics():
    suite = EDagSuite([rand_edag(5, 12), rand_edag(6, 8)])
    for alphas in ([0.0, 50.0], [-1.0, 2.0], [np.inf, 50.0]):
        grid = suite_sweep_grid(suite, alphas, ms=[2])
        for k, g in enumerate(suite.members):
            want = np.array([simulate_reference(g, m=2, alpha=float(a))
                             for a in alphas])
            assert np.array_equal(grid[k, :, 0, 0], want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_suite_grid_exact_under_both_backends(backend):
    """The union replay stays bit-identical whichever kernel backend is
    requested (the float64 guard keeps non-x64 jax on the numpy kernel,
    so exactness is unconditional)."""
    suite = EDagSuite([rand_edag(11, 50), rand_edag(12, 30),
                       rand_edag(13, 1)])
    alphas = [0.5, 2.0, 50.0, 300.0]
    grid = suite_sweep_grid(suite, alphas, ms=[2, 4], compute_slots=[0, 2],
                            backend=backend)
    for k, g in enumerate(suite.members):
        assert np.array_equal(
            grid[k], sweep_grid(g, alphas, ms=[2, 4], compute_slots=[0, 2]))


def test_suite_memory_budget_invariant():
    """Streaming the union replay in minimum-size chunks changes no bits."""
    suite = EDagSuite([rand_edag(21, 45), rand_edag(22, 35)])
    alphas = np.linspace(40.0, 300.0, 14)
    full = suite_sweep_grid(suite, alphas, ms=[1, 4], compute_slots=[0, 3])
    tiny = suite_sweep_grid(suite, alphas, ms=[1, 4], compute_slots=[0, 3],
                            mem_budget=1)
    assert np.array_equal(full, tiny)


# --------------------------------------------------------- schedule reuse

def test_suite_cache_cold_then_warm(cache_env):
    """A cold suite records one schedule per (member, m, cs) and persists
    them keyed by each member's trace digest; a warm suite (fresh objects,
    same cache dir) records none and produces identical bits; a third run
    on the same object hits the union-plan memo."""
    alphas = [50.0, 100.0, 200.0]
    ms, css = [2, 4], [0, 2]
    seeds_sizes = [(0, 50), (1, 30), (2, 40)]

    suite1 = EDagSuite([rand_edag(s, n) for s, n in seeds_sizes])
    cold = suite_sweep_grid(suite1, alphas, ms=ms, compute_slots=css)
    assert sc.stats["record_runs"] == len(seeds_sizes) * len(ms) * len(css)
    assert sc.stats["stores"] == sc.stats["record_runs"]

    sc.reset_stats()
    suite2 = EDagSuite([rand_edag(s, n) for s, n in seeds_sizes])
    warm = suite_sweep_grid(suite2, alphas, ms=ms, compute_slots=css)
    assert sc.stats["record_runs"] == 0
    assert sc.stats["disk_hits"] == len(seeds_sizes) * len(ms) * len(css)
    assert np.array_equal(cold, warm)

    sc.reset_stats()
    memo = suite_sweep_grid(suite2, alphas, ms=ms, compute_slots=css)
    assert sc.stats["record_runs"] == 0 and sc.stats["disk_hits"] == 0
    assert np.array_equal(memo, warm)


def test_suite_reuses_single_trace_schedules_and_vice_versa(cache_env):
    """The suite path shares the member-digest-keyed entries with the
    single-trace engine in both directions."""
    alphas = [50.0, 100.0, 200.0]
    g = rand_edag(9, 60)
    latency_sweep(g, alphas, m=3, compute_slots=2)     # single-trace cold
    sc.reset_stats()
    suite = EDagSuite([rand_edag(9, 60), rand_edag(10, 20)])
    got = suite_sweep_grid(suite, alphas, ms=[3], compute_slots=[2])
    assert sc.stats["record_runs"] == 1                # only the new member
    assert np.array_equal(got[0, :, 0, 0],
                          latency_sweep(g, alphas, m=3, compute_slots=2))

    sc.reset_stats()
    fresh = rand_edag(10, 20)                          # suite warmed this one
    latency_sweep(fresh, alphas, m=3, compute_slots=2)
    assert sc.stats["record_runs"] == 0 and sc.stats["disk_hits"] == 1


@pytest.mark.parametrize("mode", ["scalar", "classes"])
def test_suite_warms_member_memo_below_disk_floor(monkeypatch, mode):
    """With persistence disabled (or traces under the disk size floor),
    the member plan memo is the only reuse tier — a suite recording must
    still warm it, so later single-trace sweeps on the same member
    objects never re-pay the serial recording run.  Class mode has no
    disk tier at all, so its memo (keyed by the class overlay's digest)
    is warmed the same way."""
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", "off")
    sc.reset_stats()
    members = [rand_edag(61, 40), rand_edag(62, 25)]
    alphas = [50.0, 100.0, 200.0]
    if mode == "classes":
        for k, g in enumerate(members):
            rng = np.random.default_rng(300 + k)
            g.set_mem_classes(rng.integers(0, 2, size=g.n_vertices,
                                           dtype=np.int32))
        # rows in one ratio: the recording at the first row certifies
        # them all, so no point falls back and re-records
        alphas = np.array([[50.0, 120.0], [100.0, 240.0], [200.0, 480.0]])
    suite = EDagSuite(members)
    grid = suite_sweep_grid(suite, alphas, ms=[2, 4], compute_slots=[1])
    assert sc.stats["record_runs"] == 2 * 2
    sc.reset_stats()
    for k, g in enumerate(members):
        for j, m in enumerate([2, 4]):
            got = latency_sweep(g, alphas, m=m, compute_slots=1)
            assert np.array_equal(got, grid[k, :, j, 0])
    assert sc.stats["record_runs"] == 0
    assert sc.stats["memory_hits"] == 2 * 2


def test_suite_use_cache_false_records_and_persists_nothing(cache_env):
    suite = EDagSuite([rand_edag(14, 30), rand_edag(15, 25)])
    alphas = [50.0, 200.0]
    got = suite_sweep_grid(suite, alphas, ms=[2], use_cache=False)
    assert sc.stats["record_runs"] == 2
    assert list(cache_env.glob("*.npz")) == []
    assert len(suite._suite_plans) == 0
    for k, g in enumerate(suite.members):
        assert np.array_equal(
            got[k, :, 0, 0],
            latency_sweep(g, alphas, m=2, use_cache=False))


def test_suite_tie_heavy_fallback_stays_exact(cache_env):
    """A memoized union plan recorded at a benign alpha must not certify
    tie-heavy points it cannot order — those fall back per member and the
    result stays bit-identical."""
    suite = EDagSuite([rand_edag(31, 70), rand_edag(32, 55)])
    suite_sweep_grid(suite, [50.0, 100.0, 200.0], ms=[2],
                     compute_slots=[1])
    tie_alphas = [0.5, 1.0, 2.0, 3.0]
    got = suite_sweep_grid(suite, tie_alphas, ms=[2], compute_slots=[1])
    for k, g in enumerate(suite.members):
        want = np.array([simulate_reference(g, m=2, alpha=a,
                                            compute_slots=1)
                         for a in tie_alphas])
        assert np.array_equal(got[k, :, 0, 0], want)


# ------------------------------------------------------------ analytic side

def test_suite_t_inf_sweep_matches_members():
    suite = EDagSuite([rand_edag(41, 40), rand_edag(42, 0),
                       rand_edag(43, 55)])
    alphas = np.linspace(10.0, 400.0, 23)
    got = suite_t_inf_sweep(suite, alphas)
    assert got.shape == (3, len(alphas))
    for k, g in enumerate(suite.members):
        assert np.array_equal(got[k], t_inf_sweep(g, alphas))


def test_suite_grid_report_matches_member_grid_reports():
    suite = EDagSuite([rand_edag(51, 45), rand_edag(52, 30)],
                      names=["left", "right"])
    alphas = [50.0, 125.0, 300.0]
    ms, css = [1, 2, 4], [0, 2]
    rep = suite_grid_report(suite, alphas, ms=ms, compute_slots=css,
                            simulate_points=True)
    assert rep["names"] == ["left", "right"]
    for k, g in enumerate(suite.members):
        r1 = grid_report(g, alphas, ms=ms, compute_slots=css,
                         simulate_points=True)
        assert rep["W"][k] == r1["W"] and rep["D"][k] == r1["D"]
        assert rep["C"][k] == r1["C"]
        assert np.array_equal(rep["lam"][k], r1["lam"])
        assert np.array_equal(rep["t_inf"][k], r1["t_inf"])
        assert np.array_equal(rep["t_lower"][k], r1["t_lower"])
        assert np.array_equal(rep["t_upper"][k], r1["t_upper"])
        assert np.array_equal(rep["Lam"][k], r1["Lam"])
        assert np.array_equal(rep["simulated"][k], r1["simulated"])


def test_suite_class_vector_grid_matches_members_and_reference():
    """Class-vector (2-D alpha) grids through the suite entry points:
    every per-trace slice equals the single-trace class engine and the
    per-event class reference; members keep their own overlays."""
    from repro.core import simulate_reference_classes
    members = [rand_edag(61, 35), rand_edag(62, 20), rand_edag(63, 0)]
    for k, g in enumerate(members):
        rng = np.random.default_rng(100 + k)
        g.set_mem_classes(rng.integers(0, 2, size=g.n_vertices,
                                       dtype=np.int32))
    suite = EDagSuite(members)
    rows = np.array([[40.0, 300.0], [300.0, 300.0], [120.0, 60.0]])
    ms, css = [1, 3], [0, 2]
    got = suite_sweep_grid(suite, rows, ms=ms, compute_slots=css)
    assert got.shape == (3, len(rows), len(ms), len(css))
    for k, g in enumerate(suite.members):
        assert np.array_equal(
            got[k], sweep_grid(g, rows, ms=ms, compute_slots=css))
        for p, row in enumerate(rows):
            assert got[k, p, 1, 0] == simulate_reference_classes(
                g, row, m=3)
    tinf = suite_t_inf_sweep(suite, rows)
    assert tinf.shape == (3, len(rows))
    for k, g in enumerate(suite.members):
        assert np.array_equal(tinf[k], t_inf_sweep(g, rows))
    for g in members:
        g.set_mem_classes(None)


def test_suite_class_grid_honors_env_mem_budget(monkeypatch):
    """Class-vector suite grids go through the same union plan and
    ``$EDAN_REPLAY_MEM_BUDGET`` chunk accounting as scalar runs — the
    per-member silent fallback that used to skip budget accounting is
    gone.  A tiny budget must multiply replay dispatches (chunks of ~one
    point each) and change no bits."""
    from repro.core import backend as bk

    members = [rand_edag(71, 40), rand_edag(72, 30)]
    for k, g in enumerate(members):
        rng = np.random.default_rng(200 + k)
        g.set_mem_classes(rng.integers(0, 2, size=g.n_vertices,
                                       dtype=np.int32))
    suite = EDagSuite(members)
    rows = np.array([[40.0, 300.0], [120.0, 60.0],
                     [80.0, 200.0], [300.0, 45.0]])
    ms, css = [2, 4], [0]
    # prove the class grid really builds union plans (one per distinct
    # m), not a per-member loop
    import repro.core.suite as suite_mod
    built = []
    orig_build = suite_mod._build_plan

    def spy(scheds, n_classes=None):
        built.append((len(scheds), n_classes))
        return orig_build(scheds, n_classes)

    monkeypatch.setattr(suite_mod, "_build_plan", spy)
    bk.reset_stats()
    full = suite_sweep_grid(suite, rows, ms=ms, compute_slots=css)
    full_chunks = bk.stats["chunks"]
    assert full_chunks > 0
    # one class-mode plan per distinct m, each over both members' blocks
    assert built == [(len(members), 2)] * len(ms)
    monkeypatch.setenv("EDAN_REPLAY_MEM_BUDGET", "1")
    bk.reset_stats()
    tiny = suite_sweep_grid(suite, rows, ms=ms, compute_slots=css)
    assert bk.stats["chunks"] > full_chunks
    assert np.array_equal(full, tiny)
    for g in members:
        g.set_mem_classes(None)


def test_suite_axis_latency_grid_matches_per_step():
    from repro.core import (AxisSensitivity, axis_latency_grid, lambda_abs,
                            suite_axis_latency_grid)

    def axes(m0, scale):
        return {
            "model": AxisSensitivity(
                axis="model", W=64 * scale, D=8, bytes=2.0 ** 30,
                lam=lambda_abs(64 * scale, 8, m0),
                lam_seconds=lambda_abs(64 * scale, 8, m0) * 1e-6),
            "pod": AxisSensitivity(
                axis="pod", W=16, D=4 * scale, bytes=2.0 ** 28,
                lam=lambda_abs(16, 4 * scale, m0),
                lam_seconds=lambda_abs(16, 4 * scale, m0) * 1e-5),
        }

    per_step = {"step_a": axes(4, 1), "step_b": axes(4, 2)}
    secs = {"step_a": 1e-3, "step_b": 2e-3}
    alphas = [1e-6, 5e-6, 10e-6]
    ms = [2, 4, 8]
    got = suite_axis_latency_grid(per_step, alphas, ms, secs)
    for step, pa in per_step.items():
        want = axis_latency_grid(pa, alphas, ms, secs[step])
        assert set(got[step]) == set(want)
        for axis in pa:
            for key in ("lam", "lam_seconds", "Lam"):
                assert np.array_equal(got[step][axis][key],
                                      want[axis][key]), (step, axis, key)
    assert suite_axis_latency_grid({}, alphas, ms, {}) == {}
    assert suite_axis_latency_grid({"s": {}}, alphas, ms,
                                   {"s": 1e-3}) == {"s": {}}


# --------------------------------------------- heterogeneous-suite chunking

def test_member_groups_partition_streams_big_blocks():
    """A member too big to fit a full-width replay chunk in the budget
    becomes its own replay group; small members stay batched together;
    every member lands in exactly one group."""
    from repro.core.plan import ExecPolicy
    from repro.core.suite import _member_groups

    members = [rand_edag(40, 20), rand_edag(41, 600, p_edge=0.02),
               rand_edag(42, 25), rand_edag(43, 30)]
    suite = EDagSuite(members)
    P, n_pairs = 8, 2
    # budget sized so only the 600-vertex member overflows cap_rows
    pol = ExecPolicy.resolve(mem_budget=24 * P * 300 * n_pairs)
    groups = _member_groups(suite, n_pairs, P, pol)
    assert [1] in groups
    flat = sorted(i for grp in groups for i in grp)
    assert flat == [0, 1, 2, 3]
    covered = [i for grp in groups for i in grp]
    assert len(covered) == len(set(covered))
    # a huge budget keeps the whole suite in one batched group
    assert _member_groups(suite, n_pairs, P,
                          ExecPolicy.resolve(mem_budget=1 << 40)) \
        == [[0, 1, 2, 3]]


def test_heterogeneous_suite_grid_bit_identical_under_grouping():
    """Per-block chunking is invisible in the results: one dominant
    member among small ones, swept under budgets that force (a) the
    grouped path and (b) the minimum chunk, equals the per-member
    single-trace grids bit-for-bit."""
    members = [rand_edag(50, 20), rand_edag(51, 400, p_edge=0.03),
               rand_edag(52, 15)]
    suite = EDagSuite(members)
    alphas = [50.0, 100.0, 150.0, 200.0, 300.0]
    ms, css = [2, 4], [0, 2]
    want = [sweep_grid(g, alphas, ms=ms, compute_slots=css)
            for g in members]
    for budget in (None, 24 * len(alphas) * 200 * len(ms) * len(css), 1):
        got = suite_sweep_grid(suite, alphas, ms=ms, compute_slots=css,
                               mem_budget=budget)
        for k in range(len(members)):
            assert np.array_equal(got[k], want[k]), (k, budget)


def test_heterogeneous_suite_grouping_on_jax_backend():
    """Grouped replay through the error-bounded f32 device path (clean
    and dirty alphas mixed) still equals the numpy f64 grids exactly."""
    if len(BACKENDS) < 2:
        pytest.skip("jax not available")
    import jax
    from repro.core import backend as bk

    members = [rand_edag(60, 18), rand_edag(61, 300, p_edge=0.03),
               rand_edag(62, 22)]
    suite = EDagSuite(members)
    alphas = [50.0, 0.1, 125.0, 1.0 / 3.0, 300.0]
    ms, css = [2, 4], [0, 2]
    budget = 24 * len(alphas) * 150 * len(ms) * len(css)
    want = suite_sweep_grid(suite, alphas, ms=ms, compute_slots=css,
                            backend="numpy", mem_budget=budget,
                            use_cache=False)
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)  # pin the f32 mode
    try:
        bk.reset_stats()
        got = suite_sweep_grid(suite, alphas, ms=ms, compute_slots=css,
                               backend="jax", mem_budget=budget,
                               use_cache=False)
    finally:
        jax.config.update("jax_enable_x64", was)
    assert np.array_equal(got, want)
    assert bk.stats["jax_chunks"] > 0           # device replay ran
    assert bk.stats["demoted_columns"] > 0      # dirty columns demoted
