"""Accelerator-resident replay: dtype policy, the float32 exactness
certificate, per-column demotion, and the x64 opt-in.

Bit-exactness of *returned* results is unconditional under every policy —
float32 is an execution strategy, never an answer.  These tests pin that
contract on both backends, including adversarial traces whose float32
replay genuinely drifts past the error bound and must be detected and
demoted to the float64 numpy kernel.
"""
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import (EDag, column_quanta, replay_accumulate,
                        replay_dtype_policy, simulate_batch,
                        simulate_reference, sweep_grid, t_inf_sweep)
from repro.core import backend as bk

jax = pytest.importorskip("jax")

#: Alphas whose float32 replay can never certify: full-mantissa float64
#: values (0.1, 1/3) and a float32-representable value whose quantum is
#: far below the makespans it produces.
DIRTY_ALPHAS = (0.1, 1.0 / 3.0, 333.333, float(np.float32(1.0 / 3.0)) * 256)
#: Paper-protocol-style alphas: small integer multiples, coarse quanta.
CLEAN_ALPHAS = (50.0, 75.0, 125.0, 200.0, 300.0)


def _random_edag(seed: int, n: int = 50, p: float = 0.1,
                 mem: float = 0.5) -> EDag:
    rng = np.random.default_rng(seed)
    g = EDag()
    for i in range(n):
        g.add_vertex(is_mem=bool(rng.random() < mem))
        for j in range(i):
            if rng.random() < p:
                g.add_edge(j, i)
    g._finalize()
    return g


@pytest.fixture
def x64_off():
    """Run with the jax x64 flag off, restoring the entry state after."""
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", was)


# ----------------------------------------------------------- policy + quanta

def test_replay_dtype_policy_resolution(monkeypatch):
    monkeypatch.delenv("EDAN_X64", raising=False)
    monkeypatch.delenv("EDAN_REPLAY_DTYPE", raising=False)
    assert replay_dtype_policy() == "float32"
    assert replay_dtype_policy("float64") == "float64"
    monkeypatch.setenv("EDAN_X64", "1")
    assert replay_dtype_policy() == "float64"
    assert replay_dtype_policy("float32") == "float32"   # arg wins
    monkeypatch.setenv("EDAN_X64", "off")
    assert replay_dtype_policy() == "float32"
    monkeypatch.setenv("EDAN_REPLAY_DTYPE", "float64")
    assert replay_dtype_policy() == "float64"
    monkeypatch.setenv("EDAN_REPLAY_DTYPE", "float32")
    assert replay_dtype_policy() == "float32"


def test_replay_dtype_policy_invalid_values_raise(monkeypatch):
    monkeypatch.delenv("EDAN_X64", raising=False)
    monkeypatch.delenv("EDAN_REPLAY_DTYPE", raising=False)
    with pytest.raises(ValueError, match="float32"):
        replay_dtype_policy("f16")
    monkeypatch.setenv("EDAN_X64", "maybe")
    with pytest.raises(ValueError, match="EDAN_X64"):
        replay_dtype_policy()
    monkeypatch.delenv("EDAN_X64")
    monkeypatch.setenv("EDAN_REPLAY_DTYPE", "double")
    with pytest.raises(ValueError, match="EDAN_REPLAY_DTYPE"):
        replay_dtype_policy()


def test_column_quanta():
    # q divides every nonnegative integer combination of alpha and unit
    q = column_quanta([200.0, 50.0, 3.0], 1.0)
    assert np.array_equal(q, [1.0, 1.0, 1.0])
    assert column_quanta([200.0], 8.0)[0] == 8.0         # 200 = 25 * 8
    assert column_quanta([192.0], 64.0)[0] == 64.0
    # full-mantissa float64s have a ~2^-55-scale quantum
    assert column_quanta([0.1], 1.0)[0] < 1e-15
    # an f32-representable but fine-grained alpha: quantum = its f32 lsb
    a32 = float(np.float32(1.0 / 3.0))
    assert 0 < column_quanta([a32], 1.0)[0] <= a32 * 2.0 ** -23
    # degenerate inputs map to a zero quantum (never certifies)
    assert column_quanta([np.inf], 1.0)[0] == 0.0


def test_replay_accumulate_validates_inputs():
    g = _random_edag(0, n=10)
    lv = g._level_csr()
    with pytest.raises(ValueError, match="float64"):
        replay_accumulate(lv, np.zeros((10, 2), dtype=np.float32),
                          np.ones(2))
    with pytest.raises(ValueError, match="per column"):
        replay_accumulate(lv, np.zeros((10, 2)), np.ones(3))


# ------------------------------------------------- f32 certificate on device

def test_f32_certified_clean_grid_bit_identical(x64_off):
    """Clean paper-protocol alphas certify: the whole replay runs on the
    jax backend in float32, no column demotes, and every makespan is
    bit-identical to the float64 reference engine."""
    g = _random_edag(3, n=60)
    bk.reset_stats()
    got = simulate_batch(g, CLEAN_ALPHAS, m=3, compute_slots=2,
                         backend="jax", use_cache=False)
    want = np.array([simulate_reference(g, m=3, alpha=a, compute_slots=2)
                     for a in CLEAN_ALPHAS])
    assert np.array_equal(got, want)
    assert bk.stats["jax_chunks"] == bk.stats["chunks"] > 0
    assert bk.stats["numpy_chunks"] == 0
    assert bk.stats["demoted_columns"] == 0
    assert bk.stats["certified_columns"] >= len(CLEAN_ALPHAS)


def test_f32_demotion_dirty_alphas_bit_identical(x64_off):
    """Alphas the certificate rejects demote to the float64 numpy kernel
    — per column, not per grid — and results stay bit-identical."""
    g = _random_edag(7, n=60)
    alphas = DIRTY_ALPHAS + (50.0,)          # one clean point among dirty
    bk.reset_stats()
    got = simulate_batch(g, alphas, m=2, compute_slots=3, backend="jax",
                         use_cache=False)
    want = np.array([simulate_reference(g, m=2, alpha=a, compute_slots=3)
                     for a in alphas])
    assert np.array_equal(got, want)
    assert bk.stats["demoted_columns"] >= len(DIRTY_ALPHAS)
    assert bk.stats["certified_columns"] >= 1      # the clean column rode f32


def test_f32_drift_is_real_and_detected(x64_off):
    """The adversarial shape the bound exists for: a deep chain of memory
    accesses at an alpha that is float32-representable but fine-grained.
    Raw float32 accumulation provably drifts from the float64 value, the
    certificate detects it (demotion), and the returned makespans are
    the float64 ones bit-for-bit."""
    n = 400
    g = EDag()
    prev = None
    for _ in range(n):
        v = g.add_vertex(is_mem=True)
        if prev is not None:
            g.add_edge(prev, v)
        prev = v
    alpha = float(np.float32(1.0 / 3.0))
    # the drift is real: float32 summation of the chain disagrees with
    # float64 summation of the identical values
    f32_sum = np.float32(0.0)
    for _ in range(n):
        f32_sum = np.float32(f32_sum + np.float32(alpha))
    assert float(f32_sum) != n * alpha
    bk.reset_stats()
    got = simulate_batch(g, [alpha, 2 * alpha], m=1, backend="jax",
                         use_cache=False)
    want = np.array([simulate_reference(g, m=1, alpha=a)
                     for a in (alpha, 2 * alpha)])
    assert np.array_equal(got, want)
    assert got[0] == n * alpha               # the exact f64 chain sum
    assert bk.stats["demoted_columns"] >= 2
    assert bk.stats["certified_columns"] == 0


@st.composite
def drift_cases(draw):
    """Random tie-heavy DAGs with adversarial (mostly dirty) alphas."""
    n = draw(st.integers(5, 50))
    rng = np.random.default_rng(draw(st.integers(0, 2 ** 31)))
    g = EDag()
    for i in range(n):
        g.add_vertex(is_mem=bool(rng.random() < 0.6))
        for j in range(i):
            if rng.random() < 0.12:
                g.add_edge(j, i)
    m = draw(st.integers(1, 4))
    cs = draw(st.integers(0, 3))
    alphas = rng.choice(np.array(DIRTY_ALPHAS + CLEAN_ALPHAS), size=4,
                        replace=False)
    return g, m, cs, alphas


@given(drift_cases())
def test_f32_demotion_property_both_backends(case):
    """Satellite contract: adversarial traces whose f32 replay drifts
    past the bound are detected and produce bit-identical f64 results,
    on both backends."""
    g, m, cs, alphas = case
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    try:
        want = np.array([simulate_reference(g, m=m, alpha=float(a),
                                            compute_slots=cs)
                         for a in alphas])
        for backend in ("numpy", "jax"):
            got = simulate_batch(g, alphas, m=m, compute_slots=cs,
                                 backend=backend, use_cache=False)
            assert np.array_equal(got, want), backend
    finally:
        jax.config.update("jax_enable_x64", was)


def test_t_inf_sweep_negative_costs_certified_on_magnitude(x64_off):
    """Clamped analytic sweeps accept negative base costs, where the
    first inexact f32 operation can land on a large-magnitude *negative*
    value — the certificate must measure max(|F|), not max(F).  Exact
    equality with the numpy f64 kernel across negative alphas, both the
    certifiable and the demoting kind."""
    g = _random_edag(37, n=60)
    alphas = [-50.0, -3.0, 2.0, -2.0 ** 26, -0.1]
    got = g.t_inf_sweep_mem(alphas, backend="jax")
    want = g.t_inf_sweep_mem(alphas, backend="numpy")
    assert np.array_equal(got, want)
    # the decisive shape: every finish negative, magnitude just past the
    # f32-exact range — float32 rounds -(2^26 - 1) to -2^26, a plain max
    # certificate would accept the drifted matrix, abs-max demotes it
    h = EDag()
    for _ in range(5):
        h.add_vertex(is_mem=True)
    neg = [-(2.0 ** 26 - 1.0)]
    assert float(np.float32(neg[0])) != neg[0]
    got = h.t_inf_sweep_mem(neg, backend="jax")
    assert np.array_equal(got, h.t_inf_sweep_mem(neg, backend="numpy"))
    assert got[0] == neg[0]


def test_f32_lossy_base_cast_cannot_certify(x64_off):
    """A base cost just past the threshold is not f32-representable; its
    cast error happens *before* the pass, and cancellation against a
    positive predecessor can keep max|F32| under the threshold — so the
    pre-screen must demote on base magnitude, not trust the post-pass
    check.  Full matrix equality against the float64 kernel, not just
    the max (the returned matrices are the contract)."""
    g = EDag()
    u = g.add_vertex(is_mem=False)               # cost: unit = 2^23
    v = g.add_vertex(is_mem=True)                # cost: alpha, negative
    g.add_edge(u, v)
    g._finalize()
    lv = g._level_csr()
    alpha = -(2.0 ** 24 + 1.0)                   # q = 1, not in float32
    assert float(np.float32(alpha)) != alpha
    unit = 2.0 ** 23
    bk.reset_stats()
    F = np.array([[unit], [alpha]], dtype=np.float64)
    want = replay_accumulate(lv, F.copy(), column_quanta([alpha], unit),
                             clamp=True, backend="numpy")
    got = replay_accumulate(lv, F.copy(), column_quanta([alpha], unit),
                            clamp=True, backend="jax")
    assert np.array_equal(got, want)
    assert bk.stats["certified_columns"] == 0
    assert bk.stats["demoted_columns"] == 1


def test_t_inf_sweep_jax_bounded_matches_numpy(x64_off):
    """The analytic span sweep rides the same bounded dispatch: clean
    columns certify on device, dirty ones demote, results identical."""
    g = _random_edag(11, n=70)
    alphas = list(CLEAN_ALPHAS) + list(DIRTY_ALPHAS)
    bk.reset_stats()
    got = t_inf_sweep(g, alphas, backend="jax")
    assert np.array_equal(got, t_inf_sweep(g, alphas, backend="numpy"))
    assert bk.stats["certified_columns"] >= len(CLEAN_ALPHAS)
    assert bk.stats["demoted_columns"] >= len(DIRTY_ALPHAS)


def test_sweep_grid_jax_mostly_on_device(x64_off):
    """The acceptance shape at test scale: a clean alpha × m × slots grid
    with the jax backend runs every replay chunk on device and equals
    the float64 numpy grid bit-for-bit."""
    g = _random_edag(13, n=80)
    ms, css = [2, 4], [0, 3]
    want = sweep_grid(g, CLEAN_ALPHAS, ms=ms, compute_slots=css,
                      backend="numpy", use_cache=False)
    bk.reset_stats()
    got = sweep_grid(g, CLEAN_ALPHAS, ms=ms, compute_slots=css,
                     backend="jax", use_cache=False)
    assert np.array_equal(got, want)
    frac = bk.stats["jax_chunks"] / max(bk.stats["chunks"], 1)
    assert frac >= 0.9
    assert bk.stats["demoted_columns"] == 0


# ------------------------------------------------------------- x64 opt-in

def test_x64_mode_runs_float64_on_device():
    """replay_dtype="float64" enables jax x64 and runs the exact float64
    pass on device — dirty alphas included, no demotion machinery."""
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    try:
        g = _random_edag(17, n=50)
        alphas = [0.1, 50.0, 1.0 / 3.0]
        bk.reset_stats()
        got = simulate_batch(g, alphas, m=2, backend="jax",
                             replay_dtype="float64", use_cache=False)
        want = np.array([simulate_reference(g, m=2, alpha=a)
                         for a in alphas])
        assert np.array_equal(got, want)
        assert jax.config.jax_enable_x64          # the opt-in enabled it
        assert bk.stats["jax_f64_chunks"] == bk.stats["chunks"] > 0
        assert bk.stats["demoted_columns"] == 0
    finally:
        jax.config.update("jax_enable_x64", was)


def test_x64_env_opt_in(monkeypatch):
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    monkeypatch.setenv("EDAN_X64", "1")
    try:
        g = _random_edag(19, n=40)
        bk.reset_stats()
        got = simulate_batch(g, [0.1, 125.0], m=3, backend="jax",
                             use_cache=False)
        want = np.array([simulate_reference(g, m=3, alpha=a)
                         for a in (0.1, 125.0)])
        assert np.array_equal(got, want)
        assert bk.stats["jax_f64_chunks"] > 0
    finally:
        jax.config.update("jax_enable_x64", was)


def test_f32_policy_with_x64_flag_already_on_runs_f64_device():
    """A process already running jax with x64 (e.g. JAX_ENABLE_X64=1)
    needs no downcast: the default policy runs exact float64 on device."""
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", True)
    try:
        g = _random_edag(23, n=40)
        bk.reset_stats()
        got = simulate_batch(g, [0.1, 75.0], m=2, backend="jax",
                             use_cache=False)
        want = np.array([simulate_reference(g, m=2, alpha=a)
                         for a in (0.1, 75.0)])
        assert np.array_equal(got, want)
        assert bk.stats["jax_f64_chunks"] == bk.stats["chunks"] > 0
    finally:
        jax.config.update("jax_enable_x64", was)


# --------------------------------------- per-vertex latency classes on device

def _class_edag(seed: int, n: int = 60, C: int = 3) -> EDag:
    g = _random_edag(seed, n=n)
    rng = np.random.default_rng(seed + 1)
    g.set_mem_classes(rng.integers(0, C, size=g.n_vertices,
                                   dtype=np.int32))
    return g


def test_class_vector_f32_certified_bit_identical(x64_off):
    """Clean per-class alpha rows certify on device and come back
    bit-identical to the per-event class reference — the f32 certificate
    applies per replay column, and a class row is just a column."""
    from repro.core import simulate_reference_classes

    g = _class_edag(41)
    rng = np.random.default_rng(5)
    alphas = rng.choice(np.array(CLEAN_ALPHAS), size=(4, 3))
    want = np.array([simulate_reference_classes(g, row, m=3,
                                                compute_slots=2)
                     for row in alphas])
    bk.reset_stats()
    got = simulate_batch(g, alphas, m=3, compute_slots=2, backend="jax",
                         use_cache=False)
    assert np.array_equal(got, want)
    assert bk.stats["jax_chunks"] == bk.stats["chunks"] > 0
    assert bk.stats["demoted_columns"] == 0


def test_class_vector_x64_mode_bit_identical():
    """replay_dtype="float64" runs class rows exactly on device — dirty
    per-class alphas included."""
    from repro.core import simulate_reference_classes

    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    try:
        g = _class_edag(43)
        alphas = np.array([[0.1, 50.0, 1.0 / 3.0],
                           [333.333, 0.1, 75.0]])
        want = np.array([simulate_reference_classes(g, row, m=2)
                         for row in alphas])
        bk.reset_stats()
        got = simulate_batch(g, alphas, m=2, backend="jax",
                             replay_dtype="float64", use_cache=False)
        assert np.array_equal(got, want)
        assert bk.stats["jax_f64_chunks"] == bk.stats["chunks"] > 0
    finally:
        jax.config.update("jax_enable_x64", was)


@st.composite
def class_drift_cases(draw):
    """Random class overlays with adversarial (mostly dirty) alpha rows."""
    n = draw(st.integers(5, 50))
    seed = draw(st.integers(0, 2 ** 31))
    rng = np.random.default_rng(seed)
    g = EDag()
    for i in range(n):
        g.add_vertex(is_mem=bool(rng.random() < 0.6))
        for j in range(i):
            if rng.random() < 0.12:
                g.add_edge(j, i)
    C = draw(st.integers(1, 3))
    g._finalize()
    g.set_mem_classes(rng.integers(0, C, size=n, dtype=np.int32))
    m = draw(st.integers(1, 4))
    cs = draw(st.integers(0, 3))
    alphas = rng.choice(np.array(DIRTY_ALPHAS + CLEAN_ALPHAS),
                        size=(3, C))
    return g, m, cs, alphas


@given(class_drift_cases())
def test_class_vector_demotion_property_both_backends(case):
    """Satellite contract, class edition: adversarial class rows whose
    f32 replay drifts are detected and produce bit-identical f64
    results on both backends — and collapsed (all-classes-equal) rows
    stay bit-identical to the scalar path under the same policies."""
    from repro.core import simulate_reference_classes

    g, m, cs, alphas = case
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    try:
        want = np.array([simulate_reference_classes(g, row, m=m,
                                                    compute_slots=cs)
                         for row in alphas])
        flat = np.repeat(alphas[:, :1], alphas.shape[1], axis=1)
        for backend in ("numpy", "jax"):
            got = simulate_batch(g, alphas, m=m, compute_slots=cs,
                                 backend=backend, use_cache=False)
            assert np.array_equal(got, want), backend
            coll = simulate_batch(g, flat, m=m, compute_slots=cs,
                                  backend=backend, use_cache=False)
            scal = simulate_batch(g, flat[:, 0], m=m, compute_slots=cs,
                                  backend=backend, use_cache=False)
            assert np.array_equal(coll, scal), backend
    finally:
        jax.config.update("jax_enable_x64", was)


def test_column_quanta_class_rows():
    """2-D alpha grids get one quantum per row: the min over the row's
    per-class quanta (a row certifies only if its coarsest-safe quantum
    divides every class alpha)."""
    A = np.array([[200.0, 50.0],
                  [0.1, 50.0]])
    q = column_quanta(A, 1.0)
    assert q.shape == (2,)
    assert q[0] == 1.0
    assert 0 < q[1] < 1e-15
    assert np.array_equal(
        column_quanta(np.array([[200.0, 200.0]]), 8.0), [8.0])


# -------------------------------------------------------- jit cache bound

def test_jax_jit_cache_is_bounded_lru(monkeypatch, x64_off):
    """Sweeping many flag/dtype combinations must not accumulate compiled
    executables without bound."""
    from repro.core import level_accumulate

    g = _random_edag(29, n=30)
    lv = g._level_csr()
    monkeypatch.setattr(bk, "_JAX_CACHE_CAP", 2)
    bk._JAX_CACHE.clear()
    base = np.abs(np.random.default_rng(0).standard_normal(
        (g.n_vertices, 3))).astype(np.float32)
    for clamp in (True, False):
        for want_r in (False, True):
            R = np.zeros_like(base) if want_r else None
            level_accumulate(lv, base.copy(), clamp=clamp, R_out=R,
                             backend="jax")
            assert len(bk._JAX_CACHE) <= 2
    bk._JAX_CACHE.clear()


# ------------------------------------------------- float32 whole-chunk path

def _bases(g, alphas, unit=1.0, sentinel=False):
    """Base-cost matrix of a sweep: ``alpha`` on memory rows, ``unit``
    elsewhere, one column per alpha (plus a zero sentinel row)."""
    mem = np.asarray(g.is_mem, dtype=bool)
    F = np.where(mem[:, None], np.asarray(alphas, dtype=np.float64)[None],
                 unit)
    if sentinel:
        F = np.vstack([F, np.zeros((1, len(alphas)))])
    return np.ascontiguousarray(F)


@pytest.mark.parametrize("n", [90, 96, 20])
@pytest.mark.parametrize("clamp,alphas,with_r", [
    (True, [50.0, -3.0, 125.0, -50.0, 300.0], False),
    (False, [50.0, 75.0, 125.0, 200.0, 300.0], True),
])
def test_f32_whole_chunk_bit_identical(x64_off, n, clamp, alphas, with_r):
    """Every column live and certified: the device result lands straight
    in F (and R_out) and equals the float64 numpy kernel bit for bit —
    a clamped sweep with negative bases and no R_out, and an unclamped
    replay with R_out; with rows the screen's 32-row fold takes whole,
    with a ragged tail, and with the tail alone."""
    g = _random_edag(47, n=n)
    lv = g._level_csr()
    F = _bases(g, alphas)
    q = column_quanta(alphas, 1.0)
    R = np.zeros_like(F) if with_r else None
    Rw = np.zeros_like(F) if with_r else None
    want = replay_accumulate(lv, F.copy(), q, clamp=clamp, R_out=Rw,
                             backend="numpy")
    bk.reset_stats()
    got = replay_accumulate(lv, F, q, clamp=clamp, R_out=R, backend="jax")
    assert got is F
    assert np.array_equal(got, want)
    if with_r:
        assert np.array_equal(R, Rw) and R.any()
    assert bk.stats["f32_whole_chunks"] == bk.stats["jax_chunks"] == 1
    assert bk.stats["certified_columns"] == len(alphas)
    assert bk.stats["demoted_columns"] == bk.stats["numpy_chunks"] == 0


def test_f32_chunk_with_screened_and_uncertified_columns(x64_off):
    """One chunk, one column past the pre-screen (its base is 2^24 + 1)
    and one past the certificate (base 2^23 + 1, makespan past 2^24),
    beside clean ones: bit-identical to the float64 kernel, F and R_out,
    and not counted as a whole chunk."""
    g = EDag()
    for i in range(12):
        g.add_vertex(is_mem=True)
        if i:
            g.add_edge(i - 1, i)
    g._finalize()
    lv = g._level_csr()
    alphas = [50.0, 2.0 ** 24 + 1, 2.0 ** 23 + 1, 300.0]
    q = column_quanta(alphas, 1.0)
    thr = bk._f32_thresholds(q, lv.n_levels)
    assert alphas[1] >= thr[1] and alphas[2] < thr[2]
    F = _bases(g, alphas)
    R, Rw = np.zeros_like(F), np.zeros_like(F)
    want = replay_accumulate(lv, F.copy(), q, R_out=Rw, backend="numpy")
    bk.reset_stats()
    got = replay_accumulate(lv, F, q, R_out=R, backend="jax")
    assert np.array_equal(got, want) and np.array_equal(R, Rw)
    assert bk.stats["f32_whole_chunks"] == 0
    assert bk.stats["jax_chunks"] == bk.stats["chunks"] == 1
    assert bk.stats["certified_columns"] == 2
    assert bk.stats["demoted_columns"] == 2


def test_f32_overflow_to_inf_does_not_certify(x64_off, monkeypatch):
    """Bases of 2^127 with a quantum of 2^127 pass the pre-screen (the
    bound reads 2^151) and cast exactly, but two in a row overflow
    float32 to inf: the certificate gets inf as the column's device
    maximum and the column demotes.  The column at 2^100 certifies."""
    g = EDag()
    for i in range(3):
        g.add_vertex(is_mem=True)
        if i:
            g.add_edge(i - 1, i)
    g._finalize()
    lv = g._level_csr()
    big, fine = 2.0 ** 127, 2.0 ** 100
    F = _bases(g, [big, fine])
    q = np.array([big, fine])
    want = replay_accumulate(lv, F.copy(), q, backend="numpy")
    assert want[-1, 0] == 3 * big > float(np.finfo(np.float32).max)
    seen = []
    real = bk._certified_f32

    def spy(M32, quanta, n_levels):
        seen.append(M32.copy())
        return real(M32, quanta, n_levels)
    monkeypatch.setattr(bk, "_certified_f32", spy)
    bk.reset_stats()
    got = replay_accumulate(lv, F, q, backend="jax")
    assert np.array_equal(got, want)
    assert len(seen) == 1
    assert np.isinf(seen[0][0]) and seen[0][1] == 3 * fine
    assert bk.stats["certified_columns"] == 1
    assert bk.stats["demoted_columns"] == 1
    assert bk.stats["f32_whole_chunks"] == 0


def test_column_absmax_reads_nan_as_inf(x64_off):
    """The device certificate's reduction: max(|x|) per column, a NaN
    reads as inf (never certifies), an empty matrix reads 0."""
    x = np.array([[1.0, -7.0, np.nan], [-3.0, 2.0, 0.0]], dtype=np.float32)
    got = bk._column_absmax(jax.numpy.asarray(x))
    assert got.dtype == np.float64
    assert np.array_equal(got, [3.0, 7.0, np.inf])
    empty = jax.numpy.zeros((0, 2), dtype=jax.numpy.float32)
    assert np.array_equal(bk._column_absmax(empty), [0.0, 0.0])


@pytest.mark.parametrize("n", [0, 1, 31, 32, 8192 * 2 + 45])
def test_screen_cast_matches_abs_max_and_astype(n):
    """One blocked pass gives today's two answers: the float32 cast and
    the per-column max(|F|), NaN and infinities included."""
    rng = np.random.default_rng(n)
    F = rng.integers(-2 ** 30, 2 ** 30, size=(n, 5)).astype(np.float64)
    if n > 1:
        F[n // 2, 1] = np.nan
        F[n - 1, 3] = -np.inf
        F[0, 4] = 1e300                 # past float32: casts to inf
    F32, mag = bk._screen_cast(F)
    with np.errstate(over="ignore"):
        cast = F.astype(np.float32)
    assert F32.dtype == np.float32
    assert np.array_equal(F32, cast, equal_nan=True)
    want = np.abs(F).max(axis=0) if n else np.zeros(5)
    assert np.array_equal(mag, want, equal_nan=True)


def test_f32_whole_chunks_cover_a_clean_suite_grid(x64_off):
    """A clean union suite_sweep_grid takes the whole-chunk path in every
    chunk and matches the numpy grid bit for bit."""
    from repro.apps.polybench import trace_kernel
    from repro.core import EDagSuite, suite_sweep_grid

    suite = EDagSuite([trace_kernel("atax", 4), trace_kernel("mvt", 4),
                       trace_kernel("trisolv", 5)])
    want = suite_sweep_grid(suite, CLEAN_ALPHAS, ms=(2, 4),
                            compute_slots=(0, 3), backend="numpy",
                            use_cache=False)
    bk.reset_stats()
    got = suite_sweep_grid(suite, CLEAN_ALPHAS, ms=(2, 4),
                           compute_slots=(0, 3), backend="jax",
                           use_cache=False)
    assert np.array_equal(got, want)
    assert bk.stats["f32_whole_chunks"] == bk.stats["chunks"] > 0
    assert bk.stats["demoted_columns"] == bk.stats["numpy_chunks"] == 0
