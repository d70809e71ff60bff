"""The device level loop in level-major rows (``backend.LevelPlan``).

Each level reads its bases and writes its rows as one contiguous slice of
the permuted F and R, so the compiled loop holds no scatter.  Every pass
here is checked bit for bit against the numpy kernel on the same input
and dtype (max is exact and every add one IEEE operation).  On the CPU
the Pallas step runs in interpret mode; ``_MIN_BAND`` is lowered so that
small DAGs cut into several segments of different widths."""
import re
from collections import OrderedDict

import numpy as np
import pytest

from repro.core import backend as bk

jax = pytest.importorskip("jax")


def _dag(seed: int, n: int = 160, slot_chains: bool = False):
    """A random DAG (edges src < dst) whose level-0 vertices are spread
    over the ids, with, if asked, a two-slot chain over a third of its
    vertices: some of them have no DAG predecessor (queue-only)."""
    rng = np.random.default_rng(seed)
    src, dst = [], []
    for v in range(1, n):
        if rng.random() < 0.75:
            for u in rng.choice(v, size=min(v, int(rng.integers(1, 4))),
                                replace=False):
                src.append(int(u))
                dst.append(v)
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if not slot_chains:
        level = bk.levelize(src, dst, n)
        return bk.build_level_partition(src, dst, level, n)
    chain = np.sort(rng.choice(n, size=n // 3, replace=False))
    qpred = np.full(n, n, dtype=np.int32)
    qpred[chain[2:]] = chain[:-2]
    qdst = np.flatnonzero(qpred < n)
    level = bk.levelize(np.concatenate([src, qpred[qdst]]),
                        np.concatenate([dst, qdst]), n)
    lv = bk.build_level_partition(src, dst, level, n)
    qonly = qdst[np.bincount(dst, minlength=n)[qdst] == 0]
    qonly = qonly[np.argsort(level[qonly], kind="stable")]
    lv.qpred = qpred
    lv.qonly_ptr = np.concatenate(([0], np.cumsum(np.bincount(
        level[qonly], minlength=lv.n_levels)))).astype(np.int32)
    lv.qonly_dst = qonly.astype(np.int32)
    return lv


@pytest.fixture
def narrow_bands(monkeypatch):
    """Bands from 8 rows up, one segment per band: small DAGs cut into
    several segments, and a fresh jit cache for every case."""
    monkeypatch.setattr(bk, "_MIN_BAND", 8)
    monkeypatch.setattr(bk, "_SEGMENT_ROWS", 0)
    monkeypatch.setattr(bk, "_JAX_CACHE", OrderedDict())


@pytest.fixture
def x64():
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("want_r", [False, True], ids=["F", "F+R"])
@pytest.mark.parametrize("slot_chains", [False, True],
                         ids=["sweep", "replay"])
def test_level_major_loop_matches_numpy(narrow_bands, request, dtype,
                                        want_r, slot_chains):
    if dtype == "float64":
        request.getfixturevalue("x64")
    lv = _dag(11 + 2 * want_r + slot_chains, slot_chains=slot_chains)
    plan = bk._jax_padded(lv)
    n = lv.n
    # level-0 vertices are interleaved with the others in id order, and
    # the permutation is one: each row once, its inverse consistent
    lvl0 = np.setdiff1d(np.arange(n), np.concatenate(
        [lv.run_dst, lv.qonly_dst if slot_chains else []]))
    assert lvl0.max() > plan.perm[len(lvl0)]
    assert np.array_equal(np.sort(plan.perm), np.arange(n))
    assert np.array_equal(plan.pos[plan.perm], np.arange(n))
    assert len(plan.segments) > 1
    # the last level's padded slice runs past the level rows
    gat, qg, off = plan.segments[-1]
    last = plan.perm[off[-1]:]
    assert off[-1] + gat.shape[1] > n
    assert np.isin(last, lv.run_dst).sum() == (gat[-1, :, 0] >= 0).sum()
    assert (qg is not None) == slot_chains
    if slot_chains:
        assert len(lv.qonly_dst)
    rows = n + slot_chains              # slot chains add the zero sentinel
    rng = np.random.default_rng(5)
    base = (rng.standard_normal((rows, 4)) + 2.0).astype(dtype)
    base[n:] = 0.0
    clamp = not slot_chains
    R_np = np.zeros_like(base) if want_r else None
    R_jx = np.zeros_like(base) if want_r else None
    F_np = bk._accumulate_numpy(lv, base.copy(), clamp=clamp, R_out=R_np)
    F_jx = bk._accumulate_jax(lv, base.copy(), clamp=clamp, R_out=R_jx)
    assert F_jx.dtype == np.dtype(dtype)
    assert np.array_equal(F_np, F_jx)
    if want_r:
        assert np.array_equal(R_np, R_jx)


def test_pad_rows_stay_out_of_the_certificate(narrow_bands, monkeypatch):
    """Pad rows holding values past every column's threshold leave the
    float32 certificate's column maxima alone: every column certifies,
    and the result is the float64 kernel's."""
    monkeypatch.setattr(bk, "_PAD_FILL", 2.0 ** 40)
    lv = _dag(3, slot_chains=True)
    rng = np.random.default_rng(9)
    F = np.zeros((lv.n + 1, 3))
    F[:lv.n] = rng.integers(1, 50, (lv.n, 3))
    want_F, want_R = F.copy(), np.zeros_like(F)
    bk._accumulate_numpy(lv, want_F, clamp=False, R_out=want_R)
    got_R = np.zeros_like(F)
    bk.reset_stats()
    bk.replay_accumulate(lv, F, np.ones(3), R_out=got_R, backend="jax",
                         replay_dtype="float32")
    assert bk.stats["certified_columns"] == 3
    assert bk.stats["demoted_columns"] == 0
    assert np.array_equal(F, want_F) and np.array_equal(got_R, want_R)


def test_level_loop_holds_no_scatter(narrow_bands):
    """Lowered for a small plan of several segments, the loop writes every
    level by slice: its program holds no scatter."""
    import jax.numpy as jnp
    lv = _dag(4, slot_chains=True)
    plan = jax.tree_util.tree_map(jnp.asarray, bk._jax_padded(lv))
    F = jnp.zeros((lv.n + 1, 3), jnp.float32)
    lowered = jax.jit(bk._level_loop(True, False, True)).lower(F, F, plan)
    assert "stablehlo.dynamic_update_slice" in lowered.as_text()
    assert "stablehlo.scatter" not in lowered.as_text()
    assert not re.search(r"\bscatter\(", lowered.compile().as_text())
