"""Numeric backends for the level-synchronous (max,+) kernels.

One kernel powers both halves of the engine: the batched longest-path
recurrence ``F[v] = base[v] + max(F[u] for u in preds(v))`` evaluated one
topological level at a time over a whole matrix of cost vectors.  The
analytic sweeps call it through ``EDag._accumulate_batch_nk``; the batched
§4 simulator (``scheduler.simulate_batch``) calls it over the
*order-augmented* eDAG, where each vertex may carry one extra "queue
predecessor" (the vertex issued ``m`` slots earlier on the same resource)
— the slot-update half of the discrete-event recurrence
``F(v) = max(R(v), F(qpred)) + service``.

Two implementations are provided:

* ``numpy`` — segmented maxima via offset stepping / ``maximum.reduceat``;
  always available, the default on CPU hosts.
* ``jax``   — a ``jax.jit``-compiled level loop whose per-level
  segmented-max/slot-update step is a pallas kernel (interpreted on CPU,
  compiled on TPU/GPU).  Auto-selected when jax sees an accelerator;
  opt in/out explicitly with ``EDAN_BACKEND=numpy|jax``.  The pallas step
  emits the ready times (``R_out``) alongside the finish times, so the
  batched simulator's verification pass stays on the accelerator too.

Both backends implement the same (max, +) recurrence.  max is exact and
every ``+ service`` is a single IEEE addition, so results are reproducible
bit-for-bit for a given dtype on either backend.

For the *replay* matrices (float64) the jax path additionally supports two
device-resident execution strategies behind ``replay_accumulate``:

* **x64 mode** (``EDAN_X64=1`` / ``replay_dtype="float64"``) enables
  jax's x64 flag and runs the exact float64 recurrence on device.  Not on
  a TPU, which has no float64 kernels: asking for it there raises.
* **error-bounded float32 mode** (the default on non-x64 jax) runs the
  stacked pass in float32 on device, then certifies each column against
  a per-level error bound on host: finish times are nonnegative integer
  multiples of the column's cost quantum ``q`` (``column_quanta``), so a
  computed makespan safely below ``2^24 * q`` proves the whole float32
  pass was *exact* — bit-identical to the float64 kernel.  Columns that
  fail the bound are demoted to the numpy float64 kernel, so returned
  results are unconditionally bit-exact; float32 is an execution
  strategy, never an answer.

A device pass that fails (compile, run, device memory) raises
``DeviceReplayError`` naming the plan shape.  The backend never demotes a
failed pass to numpy on its own; the analysis service's ladder
(``plan.ExecPolicy.ladder``) is the one place that does, and says so in
its result.  The numpy routings that remain on the jax backend are
decided before the device call (float64 input the device cannot run) or
after it (uncertified float32 columns), and each is counted in ``stats``.
"""
from __future__ import annotations

import functools
import os
from collections import OrderedDict
from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

from .counters import Stats, span

_BACKENDS = ("numpy", "jax")
_AUTO_BACKEND: Optional[str] = None
_REPLAY_DTYPES = ("float32", "float64")

#: Per-process execution counters for the replay dispatch
#: (``replay_accumulate``): ``chunks`` counts dispatches; ``jax_chunks``
#: those whose level pass ran on the jax backend (``jax_f64_chunks`` the
#: subset that ran in exact float64 under the x64 flag); ``numpy_chunks``
#: those the numpy kernel handled end to end (including chunks whose f32
#: pass certified no column at all); ``certified_columns`` /
#: ``demoted_columns`` count sweep columns the float32 certificate
#: accepted / demoted to the float64 numpy kernel.  ``f32_whole_chunks``
#: counts the float32 chunks that took the whole-chunk path: every
#: column passed the pre-screen and certified, so the device result cast
#: into ``F`` / ``R_out`` stands whole (nothing saved, nothing demoted).
#: ``numpy_f64_passes``
#: counts plain ``level_accumulate`` passes the jax backend routed to the
#: numpy kernel because the device cannot run their float64 input
#: exactly (no x64 flag, or a TPU).  Each device level pass
#: (``_accumulate_jax``) adds its predecessor edges to ``replay_edges``,
#: the slots of its padded gather tensors (levels x rows x width, summed
#: over the plan's segments) to ``replay_slots``, its segment count to
#: ``replay_segments`` and the levels it wrote, each as one slice of the
#: level-major F and R, to ``replay_slice_levels``.  Thread-safe: the
#: analysis service replays concurrent batches, and lost increments here
#: would skew the very counters its benchmarks and fault-injection gates
#: assert on.
stats = Stats(chunks=0, jax_chunks=0, jax_f64_chunks=0, numpy_chunks=0,
              certified_columns=0, demoted_columns=0, f32_whole_chunks=0,
              numpy_f64_passes=0, replay_edges=0, replay_slots=0,
              replay_segments=0, replay_slice_levels=0)

#: Fault-injection hook (``serve.faults``): when set, called with no
#: arguments at the top of the jax kernel path.  An exception it raises
#: propagates like a real device failure — the backend never demotes on
#: its own — so the fault-injection suite can *prove* that in-kernel
#: backend failures degrade through the service's ladder without
#: changing a bit of any result.  Never set outside tests/fault injection.
fault_hook = None


def reset_stats() -> None:
    """Zero the replay-dispatch counters (tests and benchmarks)."""
    stats.reset()


def select_backend(override: Optional[str] = None) -> str:
    """Pick the kernel backend: explicit arg > $EDAN_BACKEND > auto.

    Auto-selection returns ``jax`` only when jax is importable *and* sees a
    non-CPU device (the numpy kernels win on CPU hosts, where per-level
    dispatch, not FLOPs, dominates).  The device probe is memoized — jax
    enumerates its backends lazily and the first call is not cheap.

    An unrecognized value — from the argument or from a mistyped
    ``$EDAN_BACKEND`` — raises with the valid choices rather than being
    silently treated as auto."""
    global _AUTO_BACKEND
    env = os.environ.get("EDAN_BACKEND", "").strip().lower()
    choice = override or env
    if choice:
        if choice not in _BACKENDS:
            src = "backend" if override else "$EDAN_BACKEND"
            raise ValueError(f"unknown {src} value {choice!r}; pick from "
                             f"{_BACKENDS}")
        return choice
    if _AUTO_BACKEND is None:
        import jax
        _AUTO_BACKEND = ("jax" if any(d.platform != "cpu"
                                      for d in jax.devices()) else "numpy")
    return _AUTO_BACKEND


def on_tpu() -> bool:
    """Whether jax's default backend is a TPU."""
    import jax
    return jax.default_backend() == "tpu"


def _device_has_f64() -> bool:
    """Whether the device pass can run float64: jax must have the x64
    flag on, and the default backend must not be a TPU (Pallas on TPU
    has no float64; XLA refuses to rewrite the kernel call)."""
    import jax
    return bool(jax.config.jax_enable_x64) and not on_tpu()


#: The checkout root (``src/repro/core`` sits three levels below it).
_CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at a fixed directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set, then a directory
    already set in jax's config.  Otherwise the cache goes to
    ``.jax_cache/`` at the checkout root, a path derived only from where
    the package sits, so a later process in the same checkout finds the
    same entries.  Call it before the first jit; returns the directory in
    use."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir
            or os.path.join(_CHECKOUT_ROOT, ".jax_cache"))
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def replay_dtype_policy(override: Optional[str] = None) -> str:
    """Resolve the replay execution dtype policy for the jax backend.

    Precedence: explicit ``replay_dtype`` argument > ``$EDAN_X64``
    (truthy selects ``float64``) > ``$EDAN_REPLAY_DTYPE`` > the default
    ``float32``.

    ``float64`` is the opt-in x64 mode: ``replay_accumulate`` enables
    jax's x64 flag and runs the exact float64 recurrence on device.
    ``float32`` is the default error-bounded mode: float32 execution on
    device with per-column float64 certification and numpy demotion (see
    the module docstring).  The policy only matters when the jax backend
    is selected; the numpy kernel is always float64.  Unrecognized
    values — argument or environment — raise with the valid choices."""
    if override:
        if override not in _REPLAY_DTYPES:
            raise ValueError(f"unknown replay_dtype {override!r}; pick "
                             f"from {_REPLAY_DTYPES}")
        return override
    x64 = os.environ.get("EDAN_X64", "").strip().lower()
    if x64:
        if x64 in _TRUTHY:
            return "float64"
        if x64 not in _FALSY:
            raise ValueError(f"unknown $EDAN_X64 value {x64!r}; pick from "
                             f"{_TRUTHY + _FALSY}")
    env = os.environ.get("EDAN_REPLAY_DTYPE", "").strip().lower()
    if env:
        if env not in _REPLAY_DTYPES:
            raise ValueError(f"unknown $EDAN_REPLAY_DTYPE value {env!r}; "
                             f"pick from {_REPLAY_DTYPES}")
        return env
    return "float32"


@dataclass
class LevelCSR:
    """Edge partition of a DAG by destination topological level — the
    input structure of ``level_accumulate``.

    Built once per graph by ``build_level_partition`` (cached on the
    ``EDag`` at ``_finalize`` time; built per recorded schedule by the
    simulator for its order-augmented replay graphs).

    ``esrc`` holds edge sources sorted by (level(dst), dst); ``run_dst`` /
    ``run_starts`` / ``run_lens`` describe the runs of equal dst inside that
    order; ``run_ptr`` / ``elevel_ptr`` bound the runs / edges per level;
    ``run_maxlen`` is the largest run length per level (bounds the offset-
    stepping segmented max).  ``qpred[v]`` is an optional extra predecessor
    (slot chain) given as a row index into the cost matrix; vertices
    without one point at the zero sentinel row ``n`` (callers using qpred
    pass an (n+1, k) matrix whose last row stays 0).  ``qonly_ptr`` /
    ``qonly_dst`` partition by level the vertices whose only predecessor
    is their queue predecessor.

    A block-diagonal *union* graph (a multi-trace suite replay) is one
    partition too; its block boundaries live on the replay plan
    (``scheduler._Plan.seg_ptr``), not here.
    """

    n: int
    n_levels: int
    esrc: np.ndarray
    run_dst: np.ndarray
    run_starts: np.ndarray
    run_lens: np.ndarray
    run_ptr: np.ndarray
    elevel_ptr: np.ndarray
    run_maxlen: Optional[list] = None
    qpred: Optional[np.ndarray] = None
    qonly_ptr: Optional[np.ndarray] = None
    qonly_dst: Optional[np.ndarray] = None
    jax_padded: Optional[tuple] = None      # memoized LevelPlan

    def level_maxlens(self) -> list:
        if self.run_maxlen is None:
            if len(self.run_lens) and self.n_levels:
                idx = np.minimum(self.run_ptr[:-1], len(self.run_lens) - 1)
                mx = np.maximum.reduceat(self.run_lens, idx)
                mx[np.diff(self.run_ptr) == 0] = 0
                self.run_maxlen = mx.tolist()
            else:
                self.run_maxlen = [0] * self.n_levels
        return self.run_maxlen


def build_level_partition(src: np.ndarray, dst: np.ndarray,
                          level: np.ndarray, n: int) -> LevelCSR:
    """Partition edges by destination level (the _finalize invariant).

    Every output index array is int32 (the engine-wide index discipline:
    edge counts and vertex ids are guarded below 2^31 at eDAG build time),
    halving the partition's memory and device transfer."""
    n_levels = int(level.max()) + 1 if n else 0
    if len(dst):
        src = np.asarray(src, dtype=np.int32)
        dst = np.asarray(dst, dtype=np.int32)
        elevel = level[dst]
        order = np.lexsort((dst, elevel))
        esrc = src[order]
        edst = dst[order]
        counts = np.bincount(elevel, minlength=n_levels)
        elevel_ptr = np.concatenate(([0], np.cumsum(counts))).astype(np.int32)
        run_mask = np.empty(len(dst), dtype=bool)
        run_mask[0] = True
        np.not_equal(edst[1:], edst[:-1], out=run_mask[1:])
        run_starts = np.nonzero(run_mask)[0].astype(np.int32)
        run_dst = edst[run_starts]
        run_lens = np.diff(np.append(run_starts, len(dst))).astype(np.int32)
        rcounts = np.bincount(level[run_dst], minlength=n_levels)
        run_ptr = np.concatenate(([0], np.cumsum(rcounts))).astype(np.int32)
    else:
        esrc = np.zeros(0, dtype=np.int32)
        edst = esrc
        elevel_ptr = np.zeros(max(n_levels, 0) + 1, dtype=np.int32)
        run_starts = np.zeros(0, dtype=np.int32)
        run_dst = np.zeros(0, dtype=np.int32)
        run_lens = np.zeros(0, dtype=np.int32)
        run_ptr = np.zeros(max(n_levels, 0) + 1, dtype=np.int32)
    return LevelCSR(n=n, n_levels=n_levels, esrc=esrc, run_dst=run_dst,
                    run_starts=run_starts, run_lens=run_lens, run_ptr=run_ptr,
                    elevel_ptr=elevel_ptr)


def segment_max_rows(F: np.ndarray, seg_ptr: np.ndarray,
                     empty: float = 0.0) -> np.ndarray:
    """Per-segment maximum over the leading axis of ``F``.

    ``seg_ptr`` is a (K+1,) nondecreasing boundary array (a union graph's
    block boundaries); returns a (K,) or (K, k) array whose entry ``i``
    is ``F[seg_ptr[i]:seg_ptr[i+1]].max(axis=0)``, or ``empty`` for
    zero-length segments.  Rows beyond ``seg_ptr[-1]`` belong to no
    segment and are ignored (the union replay's zero sentinel row, for
    instance).  This is the reduction that maps a union replay's shared
    row matrix back to per-trace makespans / spans in one vectorized
    pass."""
    seg_ptr = np.asarray(seg_ptr, dtype=np.int64)
    K = len(seg_ptr) - 1
    out = np.full((K,) + F.shape[1:], empty, dtype=np.float64)
    lens = np.diff(seg_ptr)
    live = np.nonzero(lens > 0)[0]
    if len(live):
        # reduceat runs the last segment to the end of the array it is
        # given, so clip to the segmented span first
        out[live] = np.maximum.reduceat(F[:seg_ptr[-1]], seg_ptr[live],
                                        axis=0)
    return out


def segment_sum_rows(values: np.ndarray, seg_ptr: np.ndarray) -> np.ndarray:
    """Per-segment sum over the leading axis (see ``segment_max_rows``)."""
    seg_ptr = np.asarray(seg_ptr, dtype=np.int64)
    K = len(seg_ptr) - 1
    out = np.zeros((K,) + values.shape[1:], dtype=np.float64)
    lens = np.diff(seg_ptr)
    live = np.nonzero(lens > 0)[0]
    if len(live):
        out[live] = np.add.reduceat(values[:seg_ptr[-1]], seg_ptr[live],
                                    axis=0)
    return out


def levelize(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    """Topological levels of a DAG whose edges satisfy src < dst.

    ``level[v]`` is the length (edge count) of the longest path ending at
    ``v``; sources sit at level 0.  Feed the result to
    ``build_level_partition`` to obtain the ``LevelCSR`` that
    ``level_accumulate`` consumes.

    Runs the per-edge scalar recurrence over edges sorted by destination —
    a strict left-fold that is O(E) regardless of depth, which beats the
    level-synchronous Kahn sweep on the deep, skinny graphs the simulator
    replay builds (slot chains make depth ~ W/m).  Already-sorted edges
    (the ``_finalize`` invariant) skip the argsort; the accumulator is a
    memoryview over a flat int32 buffer and the edge stream is boxed in
    bounded chunks — a boxed-int list of a million-vertex level vector
    (or a full ``tolist()`` of its edges) holds hundreds of MB of int
    objects at once."""
    with span("levelize", vertices=n, edges=len(dst)):
        return _levelize(src, dst, n)


def _levelize(src, dst, n: int) -> np.ndarray:
    out = np.zeros(n, dtype=np.int32)
    if len(dst):
        src = np.asarray(src)
        dst = np.asarray(dst)
        if len(dst) > 1 and not bool((dst[1:] >= dst[:-1]).all()):
            order = np.argsort(dst, kind="stable")
            src, dst = src[order], dst[order]
        level = memoryview(out)
        chunk = 1 << 16
        for e0 in range(0, len(dst), chunk):
            for s, d in zip(src[e0:e0 + chunk].tolist(),
                            dst[e0:e0 + chunk].tolist()):
                v = level[s] + 1
                if v > level[d]:
                    level[d] = v
    return out


# --------------------------------------------------------------------- numpy

def _accumulate_numpy(lv: LevelCSR, F: np.ndarray, clamp: bool = True,
                      R_out: Optional[np.ndarray] = None) -> np.ndarray:
    """In-place level loop over an (n, k) matrix (F holds base on entry).

    With ``lv.qpred`` set, each destination additionally maxes with its
    queue predecessor's finish (the slot-update; missing predecessors
    point at the zero sentinel row, so no masking is needed).  ``R_out``,
    if given, receives the predecessor-only maxima (the simulator's ready
    times).  Loop bookkeeping stays in plain Python ints/lists — with the
    slot chains of the batched simulator the level count approaches W/m,
    so per-level dispatch is the cost that matters.
    """
    rptr = lv.run_ptr.tolist()
    rdst, rstart, rlens, src = lv.run_dst, lv.run_starts, lv.run_lens, \
        lv.esrc
    maxlens = lv.level_maxlens()
    qp = lv.qpred
    qptr = lv.qonly_ptr.tolist() if lv.qonly_ptr is not None else None
    for lvl in range(1, lv.n_levels):
        r0, r1 = rptr[lvl], rptr[lvl + 1]
        if r0 != r1:
            d = rdst[r0:r1]
            starts = rstart[r0:r1]
            # segmented max by offset stepping: in-degrees in real traces
            # are tiny, so a couple of vectorized maximum passes finish
            # every run (faster than np.maximum.reduceat over 2D)
            segmax = F[src[starts]]
            lens = rlens[r0:r1]
            for off in range(1, maxlens[lvl]):
                # off < the level's max run length, so at least one run
                # is always live — no early-exit check needed
                live = lens > off
                segmax[live] = np.maximum(segmax[live],
                                          F[src[starts[live] + off]])
            if R_out is not None:
                R_out[d] = segmax
            if qp is not None:
                segmax = np.maximum(segmax, F[qp[d]])
            if clamp:
                np.maximum(segmax, 0.0, out=segmax)
            segmax += F[d]
            F[d] = segmax
        if qptr is not None:
            q0, q1 = qptr[lvl], qptr[lvl + 1]
            if q0 != q1:
                d = lv.qonly_dst[q0:q1]
                Fq = F[qp[d]]
                if clamp:
                    np.maximum(Fq, 0.0, out=Fq)
                F[d] += Fq
    return F


# ----------------------------------------------------------------------- jax

#: Jitted level-loop cache.  Keyed by the traced flag tuple plus the
#: input dtype and the x64 flag state, and bounded as a small LRU: a
#: long-lived serving process sweeping many flag/dtype combinations must
#: not accumulate compiled executables without bound (each jit object
#: retains every shape-specialized executable it ever built).
_JAX_CACHE: OrderedDict = OrderedDict()
_JAX_CACHE_CAP = 8

#: Largest row block of one Pallas grid step, and the VMEM one step's
#: tiles may take.  A TPU core's scoped VMEM is 16 MiB by default; a
#: level wider than one block (analytic sweeps over wide traces reach
#: tens of thousands of rows) would not fit in it whole.
_ROW_TILE = 512
_STEP_VMEM_BYTES = 8 * 1024 * 1024

#: Name of the level loop's compiled program, as the profiler's trace
#: and the compiler's dumps show it: ``jax.jit`` names a program
#: ``jit_`` + the traced function's ``__name__``, which ``_level_loop``
#: sets from this constant.  Trace readers select the loop's device time
#: by it; renaming it leaves them nothing to match.
LEVEL_LOOP_NAME = "jit_run"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _row_block(R: int, D: int, k: int) -> int:
    """Rows per grid step of the Pallas level step for an (R, D, k) level.

    VMEM lays a 32-bit tile out in (8, 128) blocks of its last two dims,
    so one row costs the padded (D, k) slab of ``seg`` — double-buffered,
    plus the kernel's two temporaries of that size — the padded index row
    and the (k,) rows of ``fq``, ``base``, ``new`` and ``ready``, each
    double-buffered.  The block is the whole level when that fits the
    budget, else the largest multiple of 8 (at most ``_ROW_TILE``) that
    divides R; ``_jax_padded`` pads R so that one exists."""
    lanes = _round_up(k, 128)
    row = 4 * (4 * _round_up(D, 8) * lanes + 2 * _round_up(D, 128)
               + 8 * lanes)
    cap = max(_STEP_VMEM_BYTES // row, 8)
    if R <= cap:
        return R
    tr = min(cap, _ROW_TILE) // 8 * 8
    while R % tr:
        tr -= 8
    return tr


class LevelPlan(NamedTuple):
    """The device level loop's plan (``_jax_padded``), in level-major rows.

    The loop runs on F and R with their rows permuted so that each
    level's destinations are one contiguous run: row ``p`` of the
    permuted matrix holds vertex ``perm[p]``, and vertex ``v`` sits at
    row ``pos[v]``.  First come the vertices no level writes (level 0 in
    id order), then level 1's rows, level 2's, and so on, each level's
    runs first and then its queue-only vertices.  The loop adds as many
    pad rows after them as the widest segment's band, so that a level's
    padded slice neither runs off the end nor reaches the rows at and
    past ``n`` (the zero sentinel of the slot chains).  Those follow the
    pad, each at its own index plus the pad, so one plan serves F with or
    without them.

    ``segments`` cuts levels 1..L-1 into contiguous runs of one padded
    width ``Rs`` (``_band_segments``), each ``(gather, qg, off)``: the
    (Ls, Rs, Dmax) gather index of every row's predecessors, the (Ls, Rs)
    row of its queue predecessor (``None`` without slot chains) and the
    (Ls,) first row of each level, all in level-major rows; ``-1`` marks
    a gather slot of no edge."""

    perm: Optional[np.ndarray]
    pos: Optional[np.ndarray]
    segments: tuple


def _jax_padded(lv: LevelCSR) -> LevelPlan:
    """Pad the per-level runs to rectangles for the jitted level loop.

    Returns the ``LevelPlan``: levels 1..L-1 in level-major rows, cut
    into contiguous segments (``_band_segments``), each padded to its own
    row width.  Queue-only vertices (no DAG predecessor, just a slot
    chain) become zero-width runs -- their reduce sees only the folded-in
    queue predecessor.  The plan depends only on the partition, so it is
    memoized on the LevelCSR (chunked sweeps call the kernel several
    times)."""
    if lv.jax_padded is not None:
        return lv.jax_padded
    with span("replay.pad", levels=max(lv.n_levels - 1, 0)):
        lv.jax_padded = _pad_levels(lv)
    return lv.jax_padded


#: Narrowest padded row width: one 128-lane tile.
_MIN_BAND = 128

#: A segment joins its wider neighbour when widening it adds at most this
#: many padded row-levels (its levels times the rows each gains).  On a
#: TPU v5e (the level loop at k = 11 columns, Dmax = 2) one more segment
#: cost 0.091 s of compiling, once per plan, and no device time that 20
#: segments could show (under 2 us a call); one more padded row cost
#: 0.078 us per level per call.  Widening pays where the rows it adds cost
#: less, over 25 calls of the plan, than the compile it saves:
#: 0.091 s / (25 x 0.078 us) = 46,000 row-levels.
_SEGMENT_ROWS = 46_000


def _band(width: np.ndarray) -> np.ndarray:
    """Padded row width of levels ``width`` rows wide: the next power of
    two, at least ``_MIN_BAND``; a multiple of 128, and of ``_ROW_TILE``
    past it, so the Pallas step's row blocks tile it exactly.  On a TPU a
    row axis off the 128-lane width also makes XLA relayout the whole
    gather tensor on every call."""
    w = np.asarray(width, dtype=np.int64)
    # w - 1 < 2**e, so 2**e is the least power of two >= w
    e = np.frexp(np.maximum(w - 1, 0).astype(np.float64))[1]
    return np.maximum(_MIN_BAND, np.left_shift(1, e.astype(np.int64)))


def _band_segments(width) -> list:
    """Cut a sequence of levels into contiguous segments of one padded
    width: ``[(start, stop, rows), ...]`` over ``width``'s indices, in
    order.  Each level is rounded up to its band (``_band``); runs of
    equal band are segments, and, narrowest band first, a segment whose
    widening to its narrower wider neighbour adds at most
    ``_SEGMENT_ROWS`` padded row-levels joins that neighbour."""
    bands = _band(width)
    if not len(bands):
        return []
    cut = np.flatnonzero(np.diff(bands)) + 1
    segs = [[int(a), int(b), int(bands[a])] for a, b in
            zip(np.concatenate(([0], cut)), np.concatenate((cut, [len(bands)])))]
    for band in np.unique(bands):
        for i, seg in enumerate(segs):
            a, b, rows = seg
            if rows != band:
                continue
            wider = [nb[2] for nb in segs[max(i - 1, 0):i] + segs[i + 1:i + 2]
                     if nb[2] > rows]
            if wider and (b - a) * (min(wider) - rows) <= _SEGMENT_ROWS:
                seg[2] = min(wider)
        merged = [segs[0]]
        for seg in segs[1:]:
            if seg[2] == merged[-1][2]:
                merged[-1][1] = seg[1]
            else:
                merged.append(seg)
        segs = merged
    return [tuple(s) for s in segs]


def _pad_levels(lv: LevelCSR) -> LevelPlan:
    L, n = lv.n_levels, lv.n
    if L <= 1:
        return LevelPlan(None, None, ())
    rcounts = np.diff(lv.run_ptr).astype(np.int64)
    qptr = (lv.qonly_ptr.astype(np.int64) if lv.qonly_ptr is not None
            else np.zeros(L + 1, dtype=np.int64))
    qcounts = np.diff(qptr)
    width = rcounts + qcounts
    Dmax = int(lv.run_lens.max()) if len(lv.run_lens) else 1
    # every row's level and position in it: runs first, then the
    # queue-only vertices of the level; level l's rows start at start[l],
    # after the vertices no level writes
    start = (n - int(width.sum())) + np.concatenate(([0], np.cumsum(width)))
    run_level = np.repeat(np.arange(L), rcounts)
    run_pos = np.arange(len(run_level)) - lv.run_ptr[run_level]
    q_level = np.repeat(np.arange(L), qcounts)
    q_pos = (np.arange(len(q_level)) - qptr[q_level]
             + rcounts[q_level])
    qdst = (lv.qonly_dst if lv.qonly_dst is not None
            else np.zeros(0, dtype=np.int32))
    written = np.zeros(n, dtype=bool)
    written[lv.run_dst] = True
    written[qdst] = True
    perm = np.empty(n, dtype=np.int32)
    perm[:start[0]] = np.flatnonzero(~written)
    perm[start[run_level] + run_pos] = lv.run_dst
    perm[start[q_level] + q_pos] = qdst
    pos = np.empty(n, dtype=np.int32)
    pos[perm] = np.arange(n, dtype=np.int32)
    # every edge's run and offset in it
    edge_run = np.repeat(np.arange(len(lv.run_lens)), lv.run_lens)
    edge_off = np.arange(len(edge_run)) - lv.run_starts[edge_run]
    eptr = lv.elevel_ptr
    bands = [(a + 1, b + 1, R)                  # level 0 has no rows
             for a, b, R in _band_segments(width[1:])]
    pad = max(R for _, _, R in bands)
    segments = []
    for a, b, R in bands:
        gather = np.full((b - a, R, Dmax), -1, dtype=np.int32)
        e = slice(eptr[a], eptr[b])
        er = edge_run[e]
        gather[run_level[er] - a, run_pos[er], edge_off[e]] = pos[lv.esrc[e]]
        qg = None
        if lv.qpred is not None:
            # each row's queue predecessor, renumbered: a vertex to its
            # row, the sentinel (and any row past n) past the pad
            rows = slice(start[a], start[b])
            lvl = np.repeat(np.arange(b - a), width[a:b])
            q = lv.qpred[perm[rows]].astype(np.int64)
            qg = np.zeros((b - a, R), dtype=np.int32)
            qg[lvl, np.arange(len(lvl)) - (start[a:b][lvl] - start[a])] = \
                np.where(q < n, pos[np.minimum(q, n - 1)], q + pad)
        segments.append((gather, qg, start[a:b].astype(np.int32)))
    return LevelPlan(perm, pos, tuple(segments))


def _pallas_interpret() -> bool:
    """Run the Pallas level step in interpret mode: on CPU hosts only.

    On an accelerator the step is compiled (Mosaic on TPU); interpret
    mode exists for the CPU test hosts.  A compile rehearsal for a
    described chip steers this function from the test itself."""
    import jax
    return jax.default_backend() == "cpu"


def _pallas_level_step(seg, idx, fq, base, clamp: bool, has_q: bool,
                       want_r: bool):
    """Segmented-max/slot-update inner step as a pallas kernel.

    ``seg``  (R, D, k) gathered DAG-predecessor finish rows, ``idx``
    (R, D) int32 the gather index they came from (-1 marks padding, so
    validity is ``idx >= 0``), ``fq`` (R, k) the queue predecessor's
    finish rows (the slot chain; the zero sentinel row when absent —
    only consulted when ``has_q``), ``base`` (R, k) the dst base costs.
    Returns the pair ``(new, ready)``: the new (R, k) finish rows and,
    when ``want_r``, the DAG-predecessor-only maxima (the simulator's
    ready times, 0 where a destination has no DAG predecessor; ``None``
    otherwise, sparing the analytic sweeps the extra per-level output
    store).  Both halves of the recurrence come out of one kernel launch,
    so the verification pass of the batched simulator needs no numpy
    round-trip.

    Validity enters as the int32 index and is compared only after the
    reshape to (R, D, 1): Mosaic cannot reshape a boolean vector, so a
    bool mask input does not compile for the TPU.  Rows are independent,
    so the step runs as a grid over row blocks (``_row_block``) that each
    fit VMEM."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(seg_ref, idx_ref, fq_ref, base_ref, out_ref, r_ref=None):
        s = seg_ref[:]                          # (R, D, k)
        valid = idx_ref[:][:, :, None] >= 0     # (R, D, 1)
        neg = jnp.full_like(s, -jnp.inf)
        red = jnp.max(jnp.where(valid, s, neg), axis=1)
        has = jnp.any(valid, axis=1)            # (R, 1)
        if want_r:
            # ready times: max over DAG predecessors only (pre-clamp,
            # pre-slot fold), what the numpy kernel writes into R_out
            r_ref[:] = jnp.where(has, red, 0.0)
        if has_q:
            # fold the queue predecessor (slot chain) in; queue-only
            # vertices (no DAG predecessor) take the slot finish alone
            red = jnp.where(has, jnp.maximum(red, fq_ref[:]), fq_ref[:])
        else:
            red = jnp.where(has, red, 0.0)
        if clamp:
            red = jnp.maximum(red, 0.0)
        out_ref[:] = red + base_ref[:]

    R, D, k = seg.shape
    tr = _row_block(R, D, k)
    rows = pl.BlockSpec((tr, k), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct(base.shape, base.dtype)
    res = pl.pallas_call(
        kernel,
        out_shape=(shape, shape) if want_r else shape,
        grid=(R // tr,),
        in_specs=[pl.BlockSpec((tr, D, k), lambda i: (i, 0, 0)),
                  pl.BlockSpec((tr, D), lambda i: (i, 0)), rows, rows],
        out_specs=(rows, rows) if want_r else rows,
        interpret=_pallas_interpret(),
        name="edan_level_step",
    )(seg, idx, fq, base)
    return res if want_r else (res, None)


#: What the level-major F and R hold in their pad rows (``LevelPlan``)
#: before the loop writes over them.  No gather reads a pad row and the
#: loop's result leaves them out, so any value gives the same answer.
_PAD_FILL = 0.0


def _level_loop(has_q: bool, clamp: bool, want_r: bool):
    """The device level loop for one flag set, un-jitted.

    ``run(F, R, plan)`` lays F (and R, when ``want_r``) out in the
    plan's level-major rows (``LevelPlan``), walks its segments in order,
    each with a ``fori_loop`` over its levels, and returns them in vertex
    order.  A level gathers its predecessor rows, calls the Pallas step
    and writes its rows back as one slice: no level scatters.  F and R
    stay on the device from one segment to the next.  The plan's arrays
    are arguments, so one jitted ``run`` re-specializes per plan shape on
    its own."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def walk(B, gat, qg, off):
        Rs = gat.shape[1]

        def body(lvl, carry):
            Fcur, Rcur = carry
            g = gat[lvl]                        # (Rs, D)
            at = (off[lvl], jnp.int32(0))     # one index dtype under x64
            # the level's bases come from the level-major input B, which
            # the loop never writes.  (A slice of the carried F makes XLA
            # lay F out for the slice and relayout all of it for every
            # level's gather, on a v5e.)
            base = lax.dynamic_slice(B, at, (Rs, B.shape[1]))
            seg = Fcur[jnp.maximum(g, 0)]       # (Rs, D, k)
            # the queue predecessor's finish (slot chain); missing
            # predecessors hit the zero sentinel row, i.e. a slot that
            # is free at t=0
            fq = Fcur[qg[lvl]] if has_q else base
            new, r = _pallas_level_step(seg, g, fq, base, clamp, has_q,
                                        want_r)
            # past the level's width the slice covers later levels' rows,
            # each written again at its own level from its base in B, and
            # the pad: no gather reads them before, so they take any value
            Fcur = lax.dynamic_update_slice(Fcur, new, at)
            if want_r:
                Rcur = lax.dynamic_update_slice(Rcur, r, at)
            return Fcur, Rcur
        return body

    def run(Fin, Rin, plan):
        perm, pos, segments = plan
        if not segments:
            return Fin, Rin
        n = perm.shape[0]
        pad = max(gat.shape[1] for gat, *_ in segments)

        def to_levels(X):
            return jnp.concatenate(
                [X[perm], jnp.full((pad, X.shape[1]), _PAD_FILL, X.dtype),
                 X[n:]])

        def to_vertices(X):
            return jnp.concatenate([X[pos], X[n + pad:]])

        B = to_levels(Fin)
        carry = (B, to_levels(Rin) if want_r else Rin)
        for seg in segments:
            carry = lax.fori_loop(0, seg[0].shape[0], walk(B, *seg), carry)
        Fout, Rout = carry
        return to_vertices(Fout), to_vertices(Rout) if want_r else Rout

    run.__name__ = run.__qualname__ = LEVEL_LOOP_NAME[len("jit_"):]
    return run


class DeviceReplayError(RuntimeError):
    """A level pass failed on the jax backend (compile, run or device
    memory).  The message names the plan shape; the original error is
    chained as ``__cause__``.  The backend never turns such a failure
    into a numpy result: the caller decides (the analysis service's
    demotion ladder is the one place that demotes)."""


def _accumulate_jax(lv: LevelCSR, F: np.ndarray, clamp: bool = True,
                    R_out: Optional[np.ndarray] = None,
                    land=None) -> np.ndarray:
    """jax backend: jit-compiled level loop + pallas inner step.

    Computes the same (max,+) recurrence as the numpy kernel in the input
    dtype, which the caller has already checked the device can run
    (``level_accumulate`` / ``replay_accumulate`` route float64 without
    the x64 flag, and float64 on a TPU, to the numpy kernel before this
    call).  Queue predecessors (slot chains) are folded inside the pallas
    step, which also emits the DAG-predecessor-only maxima per level — so
    when ``R_out`` is requested (the batched simulator's ready-time /
    order-verification pass) the whole recurrence, finish times *and*
    ready times, runs on the accelerator in one fused level loop with no
    numpy round-trip.  Any device failure raises ``DeviceReplayError``.

    ``land``, when given, says where the result goes before any of it
    comes back: it is called with the per-column ``max(|F|)`` of the
    result, reduced on the device (``_column_absmax``), and returns the
    ``(F, R_out)`` pair to cast the result into.  Returns the matrix the
    finish times landed in.
    """
    import jax
    import jax.numpy as jnp

    if fault_hook is not None:
        # fault injection (serve.faults): the raised error propagates
        # like a device failure, up to the service's demotion ladder
        fault_hook()

    configure_compile_cache()
    plan = _jax_padded(lv)
    has_q = lv.qpred is not None
    want_r = R_out is not None
    # the traced function depends only on these flags (the plan's arrays
    # are arguments, so jax.jit re-specializes per shape on its own); the
    # dtype and x64 flag are part of the key so f32 replays, f64 analytic
    # sweeps and x64-mode replays each get their own bounded slot
    key = (has_q, clamp, want_r, F.dtype.str,
           bool(jax.config.jax_enable_x64))
    fn = _JAX_CACHE.get(key)
    if fn is None:
        fn = jax.jit(_level_loop(has_q, clamp, want_r))
        _JAX_CACHE[key] = fn
    _JAX_CACHE.move_to_end(key)
    while len(_JAX_CACHE) > _JAX_CACHE_CAP:
        _JAX_CACHE.popitem(last=False)
    shapes = [seg[0].shape for seg in plan.segments]
    levels = sum(sh[0] for sh in shapes)
    slots = sum(Ls * Rs * D for Ls, Rs, D in shapes)
    moved = F.nbytes + (R_out.nbytes if want_r else 0)
    leaves = jax.tree_util.tree_leaves(plan)
    try:
        with span("replay.upload",
                  bytes=moved + sum(a.nbytes for a in leaves)):
            Rin = jnp.asarray(R_out) if want_r else jnp.zeros(
                (1, F.shape[1]), dtype=F.dtype)
            args = (jnp.asarray(F), Rin, jax.tree_util.tree_map(
                jnp.asarray, plan))
        # the call returns once the loop is dispatched; the first read
        # of a result (the column maxima, else the download) waits for
        # the device to finish it
        with span("replay.run", levels=levels,
                  rows=max((sh[1] for sh in shapes), default=0),
                  width=shapes[0][2] if shapes else 0,
                  edges=len(lv.esrc), slots=slots, segments=len(shapes),
                  permuted=lv.n if shapes else 0):
            Fj, Rj = fn(*args)
            colmax = None if land is None else _column_absmax(Fj)
        dst = land(colmax) if land is not None else (F, R_out)
        with span("replay.download", bytes=moved):
            dst[0][:] = np.asarray(Fj)
            if want_r:
                dst[1][:] = np.asarray(Rj)
    except Exception as exc:
        raise DeviceReplayError(
            f"device level pass failed on {jax.default_backend()} for plan "
            f"segments (L, Rmax, Dmax)={shapes}, rows={F.shape[0]}, "
            f"k={F.shape[1]}, dtype={F.dtype}, has_q={has_q}, "
            f"clamp={clamp}, want_r={want_r}: {type(exc).__name__}: "
            f"{exc}") from exc
    stats.add("replay_edges", len(lv.esrc))
    stats.add("replay_slots", slots)
    stats.add("replay_slice_levels", levels)
    stats.add("replay_segments", len(shapes))
    return dst[0]


@functools.cache
def _absmax_program():
    """The jitted per-column ``max(|x|)`` of ``_column_absmax``: a program
    of its own, apart from the level loop's ``jit_run``."""
    import jax
    import jax.numpy as jnp

    def column_absmax(x):
        a = jnp.abs(x)
        # NaN reads as inf, so a column holding one never certifies;
        # initial 0 gives an empty matrix a maximum
        return jnp.max(jnp.where(jnp.isnan(a), jnp.inf, a), axis=0,
                       initial=0.0)
    return jax.jit(column_absmax)


def _column_absmax(Fj) -> np.ndarray:
    """Per-column ``max(|F|)`` of a device matrix, reduced on the device:
    k values come back (as float64, exactly), not the matrix."""
    return np.asarray(_absmax_program()(Fj), dtype=np.float64)


# ------------------------------------------------------------------ dispatch

def level_accumulate(lv: LevelCSR, F: np.ndarray, clamp: bool = True,
                     R_out: Optional[np.ndarray] = None,
                     backend: Optional[str] = None) -> np.ndarray:
    """Run the batched (max,+) level recurrence in-place on ``F``.

    This is the engine's one shared hot loop: the analytic latency sweeps
    (``EDag._accumulate_batch_nk``) and the batched §4 simulator replay
    (``scheduler._Plan.replay``, one trace or a union suite) both
    dispatch here.

    Parameters
    ----------
    lv : LevelCSR
        Edge partition from ``build_level_partition`` (optionally with
        ``qpred`` / ``qonly_*`` slot chains attached by the simulator).
    F : ndarray, shape (n,) or (n, k) — or (n+1, k) with slot chains
        Enters holding the per-vertex base costs (one column per sweep
        point) and leaves holding the finish times
        ``F[v] = base[v] + max(0?, F[u] for u in preds(v))``.  Callers
        using ``lv.qpred`` pass one extra row: the zero sentinel missing
        queue predecessors point at.
    clamp : bool
        Clamp predecessor maxima at 0 (a vertex can always start at t=0).
        The simulator replay passes False — its bases are all positive
        and the slot chains bottom out on the zero sentinel row instead.
    R_out : ndarray, optional
        Same shape as ``F``; receives the DAG-predecessor-only maxima
        (the simulator's ready times, before the slot-chain fold and the
        clamp).  Rows of vertices without DAG predecessors are left
        untouched (callers pass zeros).  Both backends produce it; on the
        jax path it comes out of the same fused pallas level loop.
    backend : str, optional
        ``"numpy"`` / ``"jax"``; default per ``select_backend``.

    Returns ``F`` (mutated in place).  For a fixed dtype the backends
    agree bit-for-bit: max is exact and every ``+ base`` is one IEEE add.

    On the jax backend a float64 ``F`` runs on the device only where the
    device computes float64 (x64 flag on, not a TPU); otherwise it is
    routed to the numpy kernel before any device call and counted in
    ``stats["numpy_f64_passes"]``.  A failing device pass raises
    ``DeviceReplayError``; it is never turned into a numpy result.
    """
    if select_backend(backend) == "jax":
        if F.dtype != np.float64 or _device_has_f64():
            return _accumulate_jax(lv, F, clamp=clamp, R_out=R_out)
        # float64 input the device cannot run exactly (no x64 flag: jax
        # would truncate to float32; a TPU: no float64 kernels)
        stats.add("numpy_f64_passes")
    return _accumulate_numpy(lv, F, clamp=clamp, R_out=R_out)


# ---------------------------------------------- error-bounded replay mode

#: Largest integer count exactly representable in a float32 significand.
_F32_EXACT_MULTIPLES = 2.0 ** 24


def _lsb_quantum(x) -> np.ndarray:
    """Value of the least significant set significand bit of each
    positive finite float64 — the power of two ``q`` with ``x`` an odd
    multiple of ``q``.  Zero / non-finite entries map to 0 (no quantum:
    such columns can never certify)."""
    x = np.asarray(x, dtype=np.float64)
    frac, exp = np.frexp(x)
    with np.errstate(invalid="ignore"):
        m = np.where(np.isfinite(frac), frac, 0.0) * 2.0 ** 53
    m = m.astype(np.int64)            # exact: a 53-bit significand
    return np.ldexp((m & -m).astype(np.float64), exp - 53)


def column_quanta(alphas, unit: float) -> np.ndarray:
    """Per-column exactness quantum of a replay cost matrix.

    Every finish/ready time the (max,+) recurrence produces from a
    column's base costs is a nonnegative integer combination
    ``k1 * alpha + k2 * unit`` — an integer multiple of
    ``q = min(lsb(alpha), lsb(unit))``, the coarsest power of two
    dividing both.  ``q`` is what the float32 exactness certificate in
    ``replay_accumulate`` is measured against: clean paper-protocol
    grids (integer alphas, unit 1.0) have large ``q``; an alpha needing
    all 52 significand bits has a tiny ``q`` and its column simply
    demotes to the float64 kernel.

    ``alphas`` may be 1-D (one scalar alpha per column) or 2-D
    ``(k, n_classes)`` (one latency-class vector per column): a class
    column's values are integer combinations of *all* its class alphas
    plus ``unit``, so its quantum is the minimum over the row."""
    alphas = np.atleast_1d(np.asarray(alphas, dtype=np.float64))
    q = _lsb_quantum(alphas)
    if q.ndim == 2:
        q = q.min(axis=1) if q.shape[1] else np.zeros(len(q))
    return np.minimum(q, float(_lsb_quantum(float(unit))))


def _certified_f32(M32: np.ndarray, quanta: np.ndarray,
                   n_levels: int) -> np.ndarray:
    """Columns of a float32 level pass that are provably exact, from
    ``M32``, the per-column ``max(|F32|)`` of the pass's finish matrix
    (``_column_absmax``, reduced on the device).

    Exactness argument: all true values of a column are nonnegative
    integer multiples of its quantum ``q`` (max is exact; every add sums
    two such multiples).  A multiple ``k * q`` with ``k < 2^24`` is
    exactly representable in float32 and the addition producing it is
    exact, so by induction the whole pass is exact — bit-identical to
    the float64 kernel — whenever every true value's magnitude stays
    below ``2^24 * q``.  Detection is sound a posteriori: if any
    addition rounded, the *first* one (all earlier values exact) had a
    true result of magnitude ``>= 2^24 * q``, its computed value lands
    in the finish matrix shrunk by at most one rounding, and the
    observed ``M32 = max(|F32|)`` bounds it from above (the absolute
    value matters for clamped analytic sweeps, whose base costs may be
    negative — a large-magnitude negative finish would be invisible to
    a plain max).  Testing ``M32`` strictly below the threshold
    slackened by a per-level error bound (a generous ``4 * 2^-24`` per
    level, ~4x the worst-case relative drift of one float32 add)
    therefore proves no rounding happened anywhere.  An alpha that does
    not fit float32's significand is itself ``>= 2^24 * q``, so
    non-representable inputs can never certify; the quantum floor keeps
    certified values clear of float32 subnormals (flushed to zero on
    some accelerators)."""
    thr = _f32_thresholds(quanta, n_levels)
    return np.isfinite(M32) & (M32 < thr)


def _f32_thresholds(quanta: np.ndarray, n_levels: int) -> np.ndarray:
    """Per-column certification thresholds: ``2^24 * q`` slackened by the
    per-level error bound, zeroed where certification is impossible (a
    subnormal-range quantum, or a level count past the bound's reach) —
    a zero threshold fails every ``M32 < thr`` test."""
    slack = 1.0 - (float(n_levels) + 2.0) * 2.0 ** -22
    if slack <= 0.5:                  # ~2M levels: bound no longer tight
        return np.zeros_like(quanta)
    return np.where(quanta >= 2.0 ** -100,
                    _F32_EXACT_MULTIPLES * quanta * slack, 0.0)


def replay_accumulate(lv: LevelCSR, F: np.ndarray, quanta: np.ndarray,
                      clamp: bool = False,
                      R_out: Optional[np.ndarray] = None,
                      backend: Optional[str] = None,
                      replay_dtype: Optional[str] = None) -> np.ndarray:
    """Run a float64 replay/sweep level pass under the dtype policy.

    The accelerator-resident entry point for cost-patterned matrices
    (replay and latency-sweep bases: ``alpha`` on memory rows, ``unit``
    elsewhere, optionally a zero sentinel row).  ``F`` / ``R_out`` are
    float64 ``(rows, k)`` matrices as for ``level_accumulate`` and are
    always returned bit-identical to the float64 numpy kernel — the
    policy only chooses how that answer is computed:

    * numpy backend selected: the float64 numpy kernel, unchanged.
    * jax + ``float64`` policy (``EDAN_X64=1`` / ``replay_dtype=
      "float64"``), or jax already running with the x64 flag: enable
      x64 and run the exact float64 pass on device.  A TPU has no
      float64 kernels: there the explicit policy raises ``ValueError``,
      and a process already running with the x64 flag takes the float32
      mode below.
    * jax + ``float32`` policy (the default): run the pass in float32 on
      device, certify each column against the ``column_quanta`` /
      per-level error bound (``_certified_f32``), and demote only the
      failing columns to the float64 numpy kernel.

    ``quanta`` is the per-column quantum from ``column_quanta`` (length
    k).  Execution counters land in ``backend.stats``.  A failing device
    pass raises ``DeviceReplayError`` and is never turned into a numpy
    result here: demotion on failure is the caller's decision."""
    if F.ndim != 2 or F.dtype != np.float64:
        raise ValueError("replay_accumulate expects a float64 (rows, k) "
                         f"matrix, got {F.dtype} ndim={F.ndim}")
    quanta = np.asarray(quanta, dtype=np.float64)
    if quanta.shape != (F.shape[1],):
        raise ValueError("quanta must have one entry per column")
    stats.add("chunks")
    with span("replay", levels=max(lv.n_levels - 1, 0), rows=F.shape[0],
              columns=F.shape[1]) as sp:
        sp.set_metadata(whole=int(_replay(lv, F, quanta, clamp, R_out,
                                          backend, replay_dtype)))
    return F


def _replay(lv: LevelCSR, F: np.ndarray, quanta: np.ndarray, clamp: bool,
            R_out: Optional[np.ndarray], backend: Optional[str],
            replay_dtype: Optional[str]) -> bool:
    """``replay_accumulate`` past its argument checks; each host stage of
    the float32 mode is a span of its own (``replay.*``).  Returns whether
    the chunk took the float32 whole-chunk path."""
    b = select_backend(backend)
    # an explicit replay_dtype argument is validated on every backend (a
    # typo'd argument is a caller bug and must not surface only once the
    # code reaches an accelerator host); environment knobs are resolved
    # lazily — they are inert unless the jax backend is selected
    pol = (replay_dtype_policy(replay_dtype)
           if (b == "jax" or replay_dtype) else "float64")
    if b != "jax" or F.shape[1] == 0:
        stats.add("numpy_chunks")
        _accumulate_numpy(lv, F, clamp=clamp, R_out=R_out)
        return False
    import jax
    if pol == "float64":
        if on_tpu():
            raise ValueError(
                "replay_dtype='float64' (or $EDAN_X64) asks for a float64 "
                "device pass, which the TPU cannot run; use the default "
                "float32 policy (exact by certificate) or backend='numpy'")
        if not jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", True)
    if _device_has_f64():
        # exact float64 on device (the opt-in x64 mode, or a process
        # already running jax with the x64 flag, off the TPU)
        _accumulate_jax(lv, F, clamp=clamp, R_out=R_out)
        stats.add("jax_chunks")
        stats.add("jax_f64_chunks")
        return False
    return _replay_f32(lv, F, quanta, clamp, R_out)


#: Rows folded into one reduction row by ``_screen_cast``: a C-order
#: (rows, k) matrix viewed as (rows / 32, 32 k) reduces along long
#: contiguous rows, where k-wide rows cost a ufunc call each.
_SCREEN_FOLD = 32


def _screen_cast(F: np.ndarray):
    """``F``'s float32 cast and its per-column ``max(|F|)`` (0 for no
    rows), as ``max(colmax, -colmin)`` in float64, exact and with no
    ``|F|`` temporary.  NaN propagates into the maximum.  A value past
    float32's range casts to inf quietly: its column fails the screen,
    and its cast is never used."""
    n, k = F.shape
    with np.errstate(over="ignore"):
        F32 = F.astype(np.float32)
    m = n - n % _SCREEN_FOLD
    head, tail = F[:m].reshape(-1, _SCREEN_FOLD * k), F[m:]
    hi = np.maximum(head.max(axis=0, initial=0.0).reshape(-1, k).max(axis=0),
                    tail.max(axis=0, initial=0.0))
    lo = np.minimum(head.min(axis=0, initial=0.0).reshape(-1, k).min(axis=0),
                    tail.min(axis=0, initial=0.0))
    return F32, np.maximum(hi, -lo)


def _replay_f32(lv: LevelCSR, F: np.ndarray, quanta: np.ndarray,
                clamp: bool, R_out: Optional[np.ndarray]) -> bool:
    """The error-bounded float32 mode of ``replay_accumulate``.

    No host matrix is sliced or staged on the way back: ``F`` is screened
    and cast (``_screen_cast``), ``R_out`` cast, and the whole device
    result is cast straight into ``F`` / ``R_out``.  Columns are
    independent in the recurrence, so a column that fails the pre-screen
    or the certificate rides along and is then replayed by the float64
    numpy kernel from its bases, saved before the result lands.  Returns
    whether every column was kept (the whole-chunk path)."""
    k = F.shape[1]
    # Pre-screen: only columns whose base costs all sit strictly below
    # the threshold may keep their device result.  This is load-bearing
    # for soundness, not just a fast path — the a-posteriori certificate
    # only detects rounding *inside* the pass, so the initial float32
    # cast of the bases must be lossless, which |base| < thr <= 2^24 * q
    # guarantees (such a base is a multiple of q with fewer than 25
    # significand bits).  A base at or past the threshold could cast
    # lossily and then cancel below the observed max|F32| (clamped sweeps
    # admit negative bases), so such columns always take the float64
    # numpy kernel.  For the monotone replay (clamp off, nonneg bases) a
    # base past the threshold also forces the makespan past it, so
    # nothing certifiable is ever screened off; for clamped sweeps the
    # screen is merely conservative.  F's cast is timed with it.
    with span("replay.prescreen"):
        thr = _f32_thresholds(quanta, lv.n_levels)
        F32, base_mag = _screen_cast(F)
        live = base_mag < thr
    if not live.any():
        stats.add("numpy_chunks")
        stats.add("demoted_columns", k)
        with span("replay.demote", columns=k):
            _accumulate_numpy(lv, F, clamp=clamp, R_out=R_out)
        return False
    with span("replay.cast"), np.errstate(over="ignore"):
        R32 = R_out.astype(np.float32) if R_out is not None else None
    ok = bad = Fb = Rb = None

    def land(colmax):
        nonlocal ok, bad, Fb, Rb
        with span("replay.certify"):
            ok = live & _certified_f32(colmax, quanta, lv.n_levels)
        bad = ~ok
        if bad.any():
            with span("replay.merge"):
                Fb = np.ascontiguousarray(F[:, bad])
                Rb = (np.ascontiguousarray(R_out[:, bad])
                      if R_out is not None else None)
        # certified columns are exact multiples of q below 2^24 * q — the
        # float32 values ARE the float64 values, the cast is lossless
        return F, R_out

    _accumulate_jax(lv, F32, clamp=clamp, R_out=R32, land=land)
    n_ok = int(ok.sum())
    stats.add("certified_columns", n_ok)
    if n_ok == k:
        stats.add("jax_chunks")
        stats.add("f32_whole_chunks")
        return True
    # a chunk with no certified column counts as a numpy chunk
    stats.add("jax_chunks" if n_ok else "numpy_chunks")
    stats.add("demoted_columns", k - n_ok)
    with span("replay.demote", columns=k - n_ok):
        _accumulate_numpy(lv, Fb, clamp=clamp, R_out=Rb)
        F[:, bad] = Fb
        if R_out is not None:
            R_out[:, bad] = Rb
    return False
