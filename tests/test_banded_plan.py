"""The device level loop's plan padded per width band (``backend._band``,
``_band_segments``, ``_pad_levels``): every segment's result bit-identical
to the float64 numpy kernel, on plans with wide levels among narrow ones.

On the CPU the Pallas step runs in interpret mode; ``_MIN_BAND`` is
lowered where a small trace must still cut into several segments."""
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import repro.core as core                                  # noqa: E402
from repro.apps.hpcg import trace_cg                       # noqa: E402
from repro.apps.polybench import trace_kernel              # noqa: E402
from repro.core import backend as bk                       # noqa: E402

ALPHAS = [50.0, 175.0, 300.0]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def checked(monkeypatch, tmp_path):
    """Every device level pass, checked as it runs against the float64
    numpy kernel on the same input; returns the passes' segment shapes."""
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", str(tmp_path / "sched"))
    passes = []
    real = bk._accumulate_jax

    def spy(lv, F, clamp=True, R_out=None, land=None):
        want_F = F.astype(np.float64)
        want_R = None if R_out is None else R_out.astype(np.float64)
        bk._accumulate_numpy(lv, want_F, clamp=clamp, R_out=want_R)
        landed = []

        def keep(colmax):
            dst = land(colmax) if land is not None else (F, R_out)
            landed.append(dst)
            return dst
        out = real(lv, F, clamp=clamp, R_out=R_out, land=keep)
        got_F, got_R = landed[0]
        assert np.array_equal(got_F, want_F)
        if want_R is not None:
            assert np.array_equal(got_R, want_R)
        passes.append([seg[0].shape for seg in bk._jax_padded(lv).segments])
        return out
    monkeypatch.setattr(bk, "_accumulate_jax", spy)
    return passes


@pytest.fixture
def raw_bands(monkeypatch):
    """Small traces cut into several segments: a lower band floor, and a
    segment absorbed into a neighbour only where that adds at most 100
    padded row-levels."""
    monkeypatch.setattr(bk, "_MIN_BAND", 8)
    monkeypatch.setattr(bk, "_SEGMENT_ROWS", 100)


@pytest.mark.parametrize("n, iters, small", [(4, 2, True), (8, 1, False)])
def test_banded_replay_of_cg_matches_numpy(checked, request, n, iters,
                                           small):
    if small:
        request.getfixturevalue("raw_bands")
    g, _ = trace_cg(n, iters)
    got = core.sweep_grid(g, ALPHAS, ms=(4,), backend="jax")
    want = core.sweep_grid(g, ALPHAS, ms=(4,), backend="numpy")
    assert np.array_equal(got, want)
    assert checked and max(len(p) for p in checked) > 1


def _layered_dag(widths, seed):
    """A DAG whose level l holds ``widths[l]`` vertices, each with one to
    three predecessors on the level below and maybe one further down."""
    rng = np.random.default_rng(seed)
    start = np.concatenate(([0], np.cumsum(widths)))
    src, dst = [], []
    for lvl in range(1, len(widths)):
        for v in range(start[lvl], start[lvl + 1]):
            k = int(rng.integers(1, 4))
            preds = set(rng.integers(start[lvl - 1], start[lvl], size=k))
            if lvl > 1 and rng.random() < 0.3:
                preds.add(int(rng.integers(0, start[lvl - 1])))
            for u in sorted(preds):
                src.append(u)
                dst.append(v)
    n = int(start[-1])
    src = np.array(src, dtype=np.int32)
    dst = np.array(dst, dtype=np.int32)
    level = bk.levelize(src, dst, n)
    return bk.build_level_partition(src, dst, level, n)


def test_banded_pass_with_wide_levels_matches_numpy(checked, monkeypatch):
    monkeypatch.setattr(bk, "_SEGMENT_ROWS", 0)
    rng = np.random.default_rng(11)
    widths = ([3] * 40 + [1500] + [5] * 3 + [700] + [4] * 50 + [2000, 900]
              + [6] * 30 + [600])
    lv = _layered_dag(widths, seed=5)
    assert lv.n_levels == len(widths)
    base = rng.integers(-4, 9, (lv.n, 3)).astype(np.float32)
    F = bk.level_accumulate(lv, base.copy(), backend="jax")
    assert np.array_equal(F, bk._accumulate_numpy(lv, base.astype(
        np.float64)))
    R = np.zeros((lv.n, 3), dtype=np.float32)
    bk.level_accumulate(lv, base.copy(), clamp=False, R_out=R,
                        backend="jax")
    assert [(Ls, Rs) for Ls, Rs, _ in checked[0]] == [
        (39, 128), (1, 2048), (3, 128), (1, 1024), (50, 128), (1, 2048),
        (1, 1024), (30, 128), (1, 1024)]
    assert checked[1] == checked[0]


def test_banded_suite_replay_matches_numpy(checked, raw_bands):
    names = ["atax", "trisolv", "lu", "mvt"]
    suite = core.EDagSuite([trace_kernel(k, 6) for k in names], names=names)
    kw = dict(ms=(2, 4), compute_slots=(0, 2))
    got = core.suite_sweep_grid(suite, ALPHAS, backend="jax", **kw)
    want = core.suite_sweep_grid(suite, ALPHAS, backend="numpy", **kw)
    assert np.array_equal(got, want)
    assert max(len(p) for p in checked) > 1


def _chip_reference():
    path = os.path.join(REPO, "benchmarks", "chip", "reference.py")
    spec = importlib.util.spec_from_file_location("chip_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cg_sweep_grid_matches_the_plain_reference(checked, raw_bands):
    ref = _chip_reference()
    g, _ = trace_cg(4, 1)
    g._finalize()
    got = np.asarray(core.sweep_grid(g, ALPHAS, ms=(4,), backend="jax"))
    tr = ref.Trace(g.n_vertices, np.array(g.is_mem), np.array(g.src),
                   np.array(g.dst))
    want = [ref.simulate(tr, 4, a) for a in ALPHAS]
    assert got.reshape(-1).tolist() == want
    assert max(len(p) for p in checked) > 1


def test_band_rounds_up_to_a_power_of_two_lane_multiple():
    w = np.array([0, 1, 6, 128, 129, 256, 257, 511, 513, 4097, 8192])
    assert bk._band(w).tolist() == [128, 128, 128, 128, 256, 256, 512,
                                    512, 1024, 8192, 8192]


def test_segmenter_cuts_wide_levels_out():
    w = np.array([6] * 1000 + [8192] * 4 + [6] * 1000)
    segs = bk._band_segments(w)
    assert segs == [(0, 1000, 128), (1000, 1004, 8192), (1004, 2004, 128)]
    assert sum((b - a) * r for a, b, r in segs) == 2000 * 128 + 4 * 8192
    # PolyBench m = 2 / m = 4 plans: no level past 128 rows, one segment
    for top in (65, 128):
        prof = np.random.default_rng(top).integers(1, top + 1, 11_275)
        assert bk._band_segments(prof) == [(0, 11_275, 128)]
    assert bk._band_segments(np.zeros(0, dtype=np.int64)) == []


def test_segmenter_absorbs_a_short_segment_into_its_wider_neighbour():
    fits = bk._SEGMENT_ROWS // (512 - 128)
    for short, want in ((fits, 1), (fits + 1, 3)):
        w = np.array([300] * 10 + [6] * short + [300] * 10)
        segs = bk._band_segments(w)
        assert len(segs) == want
        assert [s[0] for s in segs][0] == 0 and segs[-1][1] == len(w)
    # a narrow segment joins the narrower of its two wider neighbours
    long = bk._SEGMENT_ROWS // (1024 - 512) + 1
    w = np.array([300] * long + [6] + [1000] * 10)
    assert bk._band_segments(w) == [(0, long + 1, 512),
                                    (long + 1, long + 11, 1024)]
