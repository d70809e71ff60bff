"""Multi-trace union eDAG suites: whole-suite sweep grids in one level pass.

EDAN's headline results are *suite-level* — Figures 10-13 characterize
latency sensitivity across all of PolyBench/HPCG/LULESH at once — yet the
single-trace engine pays one finalize/replay pipeline per kernel.  This
module batches the trace axis itself: ``EDagSuite`` concatenates K traces
into one block-diagonal union eDAG (``graph.concat_edags``) with a
per-vertex ``trace_id`` segment array, and ``suite_sweep_grid`` evaluates
the full alpha × m × compute_slots grid for *every member at once*:

* **One union replay plan for the whole grid.**  The plan's blocks span
  the full (member, m, compute_slots) product: each member's recorded
  schedule per machine pair (issue orders + augmented levels) is fetched
  from the usual reuse tiers — the member's in-process plan memo, then
  the persistent ``schedule_cache`` keyed by that member's
  ``trace_digest()`` — and only missing combinations pay the serial
  recording run.  The schedules are then concatenated in rank space:
  slot chains are offset with their block, so they never cross a block
  boundary (each trace owns its own m memory slots and ``compute_slots``
  ALU slots per machine configuration, exactly as if simulated alone),
  and the union's augmented levels are the per-block levels unchanged —
  a block-diagonal graph levelizes blockwise.  One
  ``build_level_partition`` call produces the union ``LevelCSR``.

* **One stacked (max,+) replay for the whole grid.**  Levels of
  independent blocks *interleave*: the shared numpy/jax level kernel
  (``backend.level_accumulate``) sees fatter levels and at most
  ``max_blocks n_levels`` serial steps instead of ``sum`` over K members
  × every (m, compute_slots) pair — per-level dispatch, not FLOPs,
  dominates deep replay graphs, so this is where the suite wins over
  independent pipelines.  Per-block makespans fall out of the shared row
  matrix via one segmented reduction (``backend.segment_max_rows`` over
  the plan's ``seg_ptr``); the alpha axis rides the matrix columns,
  chunked under the replay memory budget — per *replay group*
  (``_member_groups``), so a member too big to fit a full-width chunk
  streams its alpha axis alone while small members stay batched with
  wide chunks.  On the jax backend the stacked pass runs accelerator-
  resident under the replay dtype policy (``backend.replay_accumulate``:
  exact x64 on opt-in, error-bounded f32 with per-column f64 demotion by
  default) without changing a bit of any result.

* **Bit-exactness is per member, unconditional.**  The per-point
  ``(R, E, vid)`` issue-order verification runs on each member's block
  rows exactly as in the single-trace engine; any (member, point) the
  union schedule fails to certify falls back to that member's own
  ``simulate_batch`` (which re-records and, with ``use_cache``, persists
  the replacement).  Every entry of the suite grid is therefore
  bit-identical to single-trace ``sweep_grid`` — property-tested in
  ``tests/test_suite.py`` and asserted per trace in the suite benchmark.

* **Class-vector grids ride the same union.**  A 2-D alpha matrix of
  latency-class vectors builds the plan from class-mode block schedules:
  each block records slot *provenance* (``_event_loop_classes``) instead
  of the homogeneous slot chain, provenance edges are offset with their
  block exactly like slot chains, and the union F fill gathers each
  memory row's own class alpha through the plan's ``cls_mem`` column.
  Verification adds the per-block ``_verify_slots`` provenance
  certificate, so class grids run as one stacked level pass per distinct
  m — same chunking, same budget accounting, same fallback — instead of
  a per-member Python loop.

Sweep queries arrive normalized as one ``plan.SweepSpec`` (alphas
deduped/sorted once, caller order restored at the end) and execution
knobs as one frozen ``plan.ExecPolicy`` resolved at the public entry
point — see ``core/plan.py``.

The analytic side rides the same union: ``suite_t_inf_sweep`` runs one
batched span pass over the union and segments it per trace, and
``metrics.suite_grid_report`` emits per-trace Eq 1-4 tables from one
``mem_layers`` pass plus segmented reductions.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

from . import backend as _bk
from . import schedule_cache as _sc
from .counters import span
from .graph import EDag, _auto_sweep_chunk, concat_edags
from .plan import ExecPolicy, SweepSpec
from .scheduler import (_ReplayPlan, _aug_level_valid,
                        _attach_queue_partition, _event_loop,
                        _event_loop_classes, _memo_plan,
                        _prov_check_arrays, _prov_qpred, _slot_qpred,
                        _sweep_grid_spec, _validate_schedule,
                        _verify_class, _verify_slots, simulate_batch)

# Per-suite union-plan memo, keyed by (member group, pairs tuple, unit):
# one entry per replay group per distinct-m pairs subset, so a suite with
# several oversized (own-group) members consumes several slots per grid.
_SUITE_PLAN_CAP = 8


class EDagSuite:
    """K member eDAGs viewed as one block-diagonal union trace.

    ``members`` keeps the original graphs (verification and fallbacks run
    against them); ``offsets`` is the (K+1,) block-boundary array in
    union vertex space and ``trace_id`` the per-vertex segment array
    mapping union vertices back to members.  The union eDAG itself
    (``.union``) is built lazily — the simulator path never needs it,
    only the analytic suite passes do."""

    def __init__(self, members: Sequence[EDag],
                 names: Optional[Sequence[str]] = None):
        self.members = list(members)
        for g in self.members:
            if not isinstance(g, EDag):
                raise TypeError(f"suite members must be EDag, got {type(g)}")
            g._finalize()
        if names is None:
            names = [f"trace{i}" for i in range(len(self.members))]
        elif len(names) != len(self.members):
            raise ValueError("names length mismatch")
        self.names = list(names)
        counts = np.array([g.n_vertices for g in self.members],
                          dtype=np.int64)
        self.offsets = np.concatenate(([0], np.cumsum(counts)))
        self.trace_id = np.repeat(
            np.arange(len(self.members), dtype=np.int64), counts)
        self._edge_counts = [g.n_edges for g in self.members]
        self._union: Optional[EDag] = None
        self._suite_plans: OrderedDict = OrderedDict()

    @property
    def n_traces(self) -> int:
        return len(self.members)

    @property
    def n_vertices(self) -> int:
        return int(self.offsets[-1])

    def _check_members(self) -> None:
        """Refuse to operate on mutated members.

        ``EDag`` is append-only, so unchanged vertex *and* edge counts
        mean every member is exactly the graph it was at construction
        time; anything else would silently misalign the frozen
        ``offsets`` / ``trace_id`` segment arrays (and any memoized
        union), so it raises instead."""
        for k, g in enumerate(self.members):
            if (g.n_vertices != int(self.offsets[k + 1] - self.offsets[k])
                    or g.n_edges != self._edge_counts[k]):
                raise ValueError(
                    f"suite member {k} ({self.names[k]!r}) was mutated "
                    "after EDagSuite construction; build a new suite")

    @property
    def union(self) -> EDag:
        """The block-diagonal union eDAG (built once, on first use)."""
        self._check_members()
        if self._union is None:
            self._union = concat_edags(self.members)
            self._union._finalize()
        return self._union

    def segment_max(self, values: np.ndarray,
                    empty: float = 0.0) -> np.ndarray:
        """Per-trace max of a union-vertex-space array (rows = vertices)."""
        self._check_members()
        return _bk.segment_max_rows(np.asarray(values, dtype=np.float64),
                                    self.offsets, empty=empty)

    def segment_sum(self, values: np.ndarray) -> np.ndarray:
        """Per-trace sum of a union-vertex-space array (rows = vertices)."""
        self._check_members()
        return _bk.segment_sum_rows(np.asarray(values, dtype=np.float64),
                                    self.offsets)


# ------------------------------------------------------------- analytic side

def suite_t_inf_sweep(suite: EDagSuite, alphas, unit: float = 1.0,
                      backend: Optional[str] = None,
                      replay_dtype: Optional[str] = None, *,
                      policy: Optional[ExecPolicy] = None) -> np.ndarray:
    """Span T-inf per (trace, alpha) from one union-batched level pass.

    Returns a (K, n_alphas) array; row k is bit-identical to
    ``metrics.t_inf_sweep(member_k, alphas, unit)`` — the union is block-
    diagonal, so the level recurrence restricted to block k performs
    exactly the member's operations.  Chunked like ``t_inf_sweep_mem`` so
    the (n_union, chunk) working set stays cache-resident.  The pass runs
    through ``backend.replay_accumulate``, so on the jax backend it is
    accelerator-resident under the replay dtype policy (error-bounded f32
    with per-column f64 demotion by default; exact x64 on opt-in) without
    changing a bit of the result.

    A 2-D ``(P, n_classes)`` alpha matrix sweeps latency-class vectors:
    each member's ``set_mem_classes`` overlay prices its own vertices
    (class ids share one global space across the suite), via one
    concatenated gather column over the union."""
    pol = ExecPolicy.resolve(backend=backend, replay_dtype=replay_dtype,
                             policy=policy)
    alphas = np.asarray(alphas, dtype=np.float64)
    suite._check_members()
    K = suite.n_traces
    if K == 0 or suite.n_vertices == 0 or len(alphas) == 0:
        return np.zeros((K, len(alphas)))
    u = suite.union
    cls = (np.concatenate([g.mem_class_column(alphas.shape[1])
                           for g in suite.members])
           if alphas.ndim == 2 else None)
    chunk = _auto_sweep_chunk(u.n_vertices)
    lv = u._level_csr()
    out = []
    for i in range(0, len(alphas), chunk):
        with span("fill", rows=u.n_vertices,
                  columns=len(alphas[i:i + chunk])):
            if cls is not None:
                F = np.where(u.is_mem[:, None], alphas[i:i + chunk].T[cls],
                             float(unit))
            else:
                F = np.where(u.is_mem[:, None], alphas[None, i:i + chunk],
                             float(unit))
            quanta = _bk.column_quanta(alphas[i:i + chunk], unit)
        pol.accumulate(lv, F, quanta, clamp=True)
        with span("reduce"):
            out.append(_bk.segment_max_rows(F, suite.offsets))
    return np.concatenate(out, axis=1)


# ------------------------------------------------------------ the suite plan

class _BlockSched:
    """One (member, m, compute_slots) block of a union replay plan:
    everything the per-point (R, E, vid) verification and the fallback
    path need, in member-local rank space (F/R block views index with
    these directly), plus where the block's results land in the grid.

    On class-mode plans the block also carries the recorded slot
    provenance and its verification scaffolding — the same attribute
    names ``_verify_slots`` reads off a single-trace ``_ReplayPlan``, so
    the identical certifier runs on the block's F view."""

    __slots__ = ("g", "trace", "pair", "m", "cs", "off", "rank",
                 "O_mem", "Om_rel", "O_alu", "Oa_rel",
                 "prov", "prov_ok", "t_chk", "need_chk")

    def __init__(self, g: EDag, trace: int, pair: int, m: int, cs: int,
                 off: int, rank, O_mem, O_alu, prov=None):
        self.g = g
        self.trace, self.pair = trace, pair
        self.m, self.cs, self.off = m, cs, off
        self.rank = rank
        self.O_mem, self.O_alu = O_mem, O_alu
        self.Om_rel = rank[O_mem]
        self.Oa_rel = rank[O_alu] if cs else np.zeros(0, dtype=np.int64)
        self.prov = prov
        if prov is not None:
            self.prov_ok, self.t_chk, self.need_chk = \
                _prov_check_arrays(prov, m)
        else:
            self.prov_ok = True
            self.t_chk = self.need_chk = None


class _SuitePlan:
    """Union replay plan over the full (member, m, compute_slots) block
    product: one ``LevelCSR`` for the whole grid, per-block verification
    state, and the block boundary array (``seg_ptr``) the per-block
    makespan reduction runs over.  ``replay`` evaluates every grid
    configuration for every member at every sweep point of a chunk in a
    single ``level_accumulate`` call.

    ``cls_mem`` (class-mode plans only) is the per-memory-row latency
    class, aligned with ``mem_rows``: each member's ``set_mem_classes``
    overlay gathered through its block's pop order, so a class-vector
    chunk fills the union F matrix with one fancy-indexed gather."""

    __slots__ = ("n", "lv", "mem_rows", "seg_ptr", "blocks", "cls_mem")

    def __init__(self, n: int, lv, mem_rows, seg_ptr, blocks,
                 cls_mem=None):
        self.n = n
        self.lv = lv
        self.mem_rows = mem_rows
        self.seg_ptr = seg_ptr
        self.blocks = blocks
        self.cls_mem = cls_mem

    def replay(self, alphas: np.ndarray, unit: float,
               pol: Optional[ExecPolicy] = None):
        """All blocks × all points at once: finish and ready times,
        (n_rows + 1, k) in blockwise pop-order row space (the last row is
        the shared zero sentinel every block's slot chains bottom out
        on).  Runs through ``ExecPolicy.accumulate`` under the policy's
        replay dtype, so the matrices are always bit-identical to the
        float64 numpy kernel.  ``alphas`` is (k,) scalar latencies or,
        on a class-mode plan, (k, n_classes) class-vector rows."""
        pol = ExecPolicy.resolve(policy=pol)
        k = len(alphas)
        with span("fill", rows=self.n + 1, columns=k):
            F = np.empty((self.n + 1, k))
            F.fill(unit)
            if self.cls_mem is not None:
                F[self.mem_rows] = alphas.T[self.cls_mem]
            else:
                F[self.mem_rows] = alphas    # rows of memory vertices
            F[-1] = 0.0
            R = np.zeros_like(F)
            quanta = _bk.column_quanta(alphas, unit)
        pol.accumulate(self.lv, F, quanta, clamp=False, R_out=R)
        return F, R


def _member_schedule(g: EDag, m: int, cs: int, unit: float, a0: float,
                     use_cache: bool):
    """One member's recorded schedule ``(topo, O_mem, O_alu, level|None,
    fresh)`` — memo, then disk (keyed by the member's trace digest), then
    one instrumented recording run at alpha ``a0``."""
    n = g.n_vertices
    if use_cache:
        key = (m, cs, float(unit))
        memo = getattr(g, "_replay_plans", None)
        if memo is not None and key in memo:
            p = memo[key]
            memo.move_to_end(key)
            _sc.stats.add("memory_hits")
            return p.topo, p.O_mem, p.O_alu, p.level_aug, False
        if n >= _sc.min_vertices():
            with span("schedule.load") as sp:
                got = _sc.load(g.trace_digest(), m, cs, n, unit)
                hit = got is not None and _validate_schedule(
                    g, m, cs, *got[:3]) is not None
                sp.set_metadata(hit=int(hit))
            if hit:
                _sc.stats.add("disk_hits")
                topo, O_mem, O_alu, level = got
                return topo, O_mem, O_alu, level, False
        _sc.stats.add("misses")
    _sc.stats.add("record_runs")
    with span("schedule.record", vertices=n):
        _, topo, O_mem, O_alu = _event_loop(g.is_mem, g._sim_lists(), m,
                                            a0, unit, cs, record=True)
    return topo, O_mem, O_alu, None, True


def _member_schedule_classes(g: EDag, m: int, cs: int, unit: float,
                             a0, cls, use_cache: bool):
    """Class-mode member schedule ``(topo, O_mem, O_alu, prov,
    level|None, fresh)`` — the member's in-process plan memo (keyed by
    the class overlay's digest, exactly as the single-trace class engine
    keys it), then one instrumented ``_event_loop_classes`` recording at
    class-vector row ``a0``.  There is no disk tier: the persisted
    schedule format carries no provenance field, and the overlay is not
    part of the trace digest."""
    if use_cache:
        key = ("classes", m, cs, float(unit), g.mem_class_digest())
        memo = getattr(g, "_replay_plans", None)
        if memo is not None and key in memo:
            p = memo[key]
            memo.move_to_end(key)
            _sc.stats.add("memory_hits")
            return p.topo, p.O_mem, p.O_alu, p.prov, p.level_aug, False
        _sc.stats.add("misses")
    _sc.stats.add("record_runs")
    with span("schedule.record", vertices=g.n_vertices):
        _, topo, O_mem, O_alu, prov = _event_loop_classes(
            g.is_mem, g._sim_lists(), m, a0, cls, unit, cs, record=True)
    return topo, O_mem, O_alu, prov, None, True


def _build_suite_plan(suite: EDagSuite, pairs, unit: float, a0,
                      use_cache: bool,
                      member_idx: Optional[Sequence[int]] = None,
                      n_classes: Optional[int] = None) -> _SuitePlan:
    """Concatenate the (member, m, compute_slots) block schedules into one
    block-diagonal replay plan for the whole grid: slot chains and DAG
    edges are offset with their block, per-block augmented levels
    concatenate unchanged (blocks are disconnected), and a single
    ``build_level_partition`` call produces the union ``LevelCSR``.  The
    serial depth of the resulting replay is the *deepest block*, not the
    sum over members and machine pairs.  ``member_idx`` restricts the
    plan to a subset of members (a replay *group* — see
    ``_member_groups``); block ``trace`` ids stay global, so results
    scatter into the full suite grid unchanged.

    ``n_classes`` switches the plan to class mode: ``a0`` is then the
    master class-vector row, block schedules come from
    ``_member_schedule_classes`` (slot provenance instead of homogeneous
    chains, wired through ``_prov_qpred`` with block offsets), and the
    plan carries the per-memory-row class gather column ``cls_mem``."""
    if member_idx is None:
        member_idx = range(suite.n_traces)
    classes = n_classes is not None
    n_rows = sum(suite.members[k].n_vertices
                 for k in member_idx) * len(pairs)
    qpred_u = np.full(n_rows, n_rows, dtype=np.int64)
    is_mem_rows = np.zeros(n_rows, dtype=bool)
    cls_rows = np.zeros(n_rows, dtype=np.int64) if classes else None
    src_parts, dst_parts, lvl_parts = [], [], []
    blocks: list = []
    seg_ptr = [0]
    off = 0
    for pair, (m, cs) in enumerate(pairs):
        for k in member_idx:
            g = suite.members[k]
            n = g.n_vertices
            seg_ptr.append(off + n)
            if n == 0:
                blocks.append(None)
                continue
            if classes:
                cls_col = g.mem_class_column(n_classes)
                topo, O_mem, O_alu, prov, level, fresh = \
                    _member_schedule_classes(g, m, cs, unit, a0, cls_col,
                                             use_cache)
            else:
                cls_col = prov = None
                topo, O_mem, O_alu, level, fresh = _member_schedule(
                    g, m, cs, unit, a0, use_cache)
            rank = np.empty(n, dtype=np.int64)
            rank[topo] = np.arange(n)
            if classes:
                qpred = _prov_qpred(rank, O_mem, O_alu, prov, m, cs, n)
            else:
                qpred = _slot_qpred(rank, O_mem, O_alu, m, cs, n)
            src_r, dst_r = rank[g.src], rank[g.dst]
            qdst = np.nonzero(qpred < n)[0]
            asrc = np.concatenate([src_r, qpred[qdst]])
            adst = np.concatenate([dst_r, qdst])
            if level is not None:
                level = np.asarray(level)
                if not _aug_level_valid(level, asrc, adst, n):
                    level = None      # invalid persisted levels: recompute
            if level is None:
                level = _bk.levelize(asrc, adst, n)
            if fresh and use_cache:
                persisted = not classes and n >= _sc.min_vertices() and \
                    _sc.store(g.trace_digest(), m, cs, n, unit, topo,
                              O_mem, O_alu, level)
                if not persisted:
                    # below the disk floor (or persistence disabled, or
                    # class mode — which has no disk format) the member
                    # memo is the only tier that can make this recording
                    # reusable — "suite warms singles" must hold there
                    # too, so pay the one member plan build
                    mkey = (("classes", m, cs, float(unit),
                             g.mem_class_digest()) if classes
                            else (m, cs, float(unit)))
                    _memo_plan(g, mkey,
                               _ReplayPlan(g, topo, O_mem, O_alu, m, cs,
                                           level=level, prov=prov,
                                           classes=cls_col))
            # block offsets: slot chains stay inside their block, missing
            # predecessors retarget the shared sentinel row n_rows
            qpred_u[off:off + n] = np.where(qpred < n, qpred + off, n_rows)
            src_parts.append(src_r + off)
            dst_parts.append(dst_r + off)
            lvl_parts.append(level)
            is_mem_rows[off:off + n] = g.is_mem[topo]
            if classes:
                cls_rows[off:off + n] = cls_col[topo]
            blocks.append(_BlockSched(g, k, pair, m, cs, off, rank,
                                      O_mem, O_alu, prov=prov))
            off += n
    empty = np.zeros(0, dtype=np.int64)
    src_u = np.concatenate(src_parts) if src_parts else empty
    dst_u = np.concatenate(dst_parts) if dst_parts else empty
    level_u = np.concatenate(lvl_parts) if lvl_parts else empty
    lv = _bk.build_level_partition(src_u, dst_u, level_u, n_rows)
    _attach_queue_partition(lv, dst_u, qpred_u, level_u)
    lv.seg_ptr = np.asarray(seg_ptr, dtype=np.int64)
    mem_rows = np.flatnonzero(is_mem_rows)
    return _SuitePlan(n_rows, lv, mem_rows, lv.seg_ptr, blocks,
                      cls_mem=cls_rows[mem_rows] if classes else None)


def _memo_suite_plan(suite: EDagSuite, key, plan: _SuitePlan) -> None:
    memo = suite._suite_plans
    memo[key] = plan
    memo.move_to_end(key)
    while len(memo) > _SUITE_PLAN_CAP:
        memo.popitem(last=False)


def _member_groups(suite: EDagSuite, n_pairs: int, P: int,
                   pol: ExecPolicy) -> list:
    """Partition member indices into replay groups under the policy's
    memory budget — the heterogeneous-suite streaming rule.

    The alpha-chunk divisor of a union replay is the *plan's total row
    count*, so one million-vertex HPCG block in a union of small
    PolyBench members would shrink every member's chunks to the big
    block's streaming size.  A member whose own block rows
    (``n_vertices x n_pairs``) cannot fit a full-width (rows, P) replay
    chunk inside the budget is going to stream its alpha axis no matter
    what, so it replays as its own group; everything else stays batched
    in one union group with full-width (or near-full) chunks.
    Grouping only changes how chunks are cut — every block still runs
    the identical per-member recurrence, so results are unaffected."""
    cap_rows = pol.cap_rows(P)
    small: list = []
    groups: list = []
    for k, g in enumerate(suite.members):
        if g.n_vertices * n_pairs > cap_rows:
            groups.append([k])        # streams alone, own chunk size
        else:
            small.append(k)
    if small:
        groups.insert(0, small)       # batched together, wide chunks
    return groups


def _suite_grid_batch(suite: EDagSuite, alphas: np.ndarray, pairs,
                      unit: float, pol: ExecPolicy) -> np.ndarray:
    """The whole grid, one union plan + one chunked stacked replay per
    replay group: returns (K, n_alphas, n_pairs) makespans.  ``alphas``
    must arrive sorted, unique, finite and positive — 1-D scalars or
    2-D class-vector rows (``suite_sweep_grid`` guarantees it via its
    ``SweepSpec``)."""
    K, P = suite.n_traces, len(alphas)
    out = np.zeros((K, P, len(pairs)))
    if suite.n_vertices == 0 or P == 0 or not pairs:
        return out
    for idxs in _member_groups(suite, len(pairs), P, pol):
        _group_grid_batch(suite, idxs, out, alphas, pairs, unit, pol)
    return out


def _group_grid_batch(suite: EDagSuite, member_idx, out: np.ndarray,
                      alphas: np.ndarray, pairs, unit: float,
                      pol: ExecPolicy) -> None:
    """Evaluate one replay group's (member, pair, alpha) product into
    ``out`` (global trace indexing): one union plan over the group's
    blocks, one chunked stacked replay, per-block verification, and the
    per-member fallback for anything the union schedule fails to
    certify.  2-D ``alphas`` rows run the class-mode plan — provenance
    slot chains, class-gathered F fill, and the additional per-block
    ``_verify_slots`` certificate."""
    P = len(alphas)
    classes = alphas.ndim == 2
    cls_key = (tuple(suite.members[k].mem_class_digest()
                     for k in member_idx) if classes else None)
    key = (tuple(member_idx), tuple(pairs), float(unit), cls_key)
    plan = suite._suite_plans.get(key) if pol.use_cache else None
    with span("plan", hit=int(plan is not None)):
        if plan is not None:
            suite._suite_plans.move_to_end(key)
        else:
            a0 = alphas[0] if classes else float(alphas[0])
            plan = _build_suite_plan(
                suite, pairs, unit, a0, pol.use_cache,
                member_idx=member_idx,
                n_classes=alphas.shape[1] if classes else None)
            if pol.use_cache:
                _memo_suite_plan(suite, key, plan)
    B = len(plan.blocks)
    ok = np.zeros((B, P), dtype=bool)
    chunk = pol.points_chunk(plan.n, P)
    for c0 in range(0, P, chunk):
        cols = np.arange(c0, min(c0 + chunk, P))
        F, R = plan.replay(alphas[cols], unit, pol=pol)
        with span("reduce"):
            mk = _bk.segment_max_rows(F[:-1], plan.seg_ptr)
        for b, blk in enumerate(plan.blocks):
            if blk is None:           # empty member: makespan 0 everywhere
                ok[b, cols] = True
                continue
            off, n = blk.off, blk.g.n_vertices
            Fv, Rv = F[off:off + n], R[off:off + n]
            with span("verify"):
                okc = _verify_class(blk.g, blk.rank, Fv, Rv,
                                    blk.O_mem, blk.Om_rel)
                if blk.prov is not None:
                    okc &= _verify_slots(blk, Fv)
                if blk.cs:
                    okc &= _verify_class(blk.g, blk.rank, Fv, Rv,
                                         blk.O_alu, blk.Oa_rel)
            out[blk.trace, cols[okc], blk.pair] = mk[b, okc]
            ok[b, cols] = okc
    if not ok.all():
        # any (block, point) the union schedule failed to certify falls
        # back to that member's own batched engine (which re-records and,
        # with use_cache, persists/memoizes the replacement — the next
        # suite plan build picks it up through the member tiers), and the
        # stale union plan is dropped so repeated suite sweeps converge
        if pol.use_cache:
            suite._suite_plans.pop(key, None)
        for b, blk in enumerate(plan.blocks):
            if blk is None:
                continue
            bad = np.nonzero(~ok[b])[0]
            if len(bad):
                out[blk.trace, bad, blk.pair] = simulate_batch(
                    blk.g, alphas[bad], m=blk.m, unit=unit,
                    compute_slots=blk.cs, policy=pol)


# ------------------------------------------------------------- entry points

def _suite_sweep_grid_spec(suite: EDagSuite, spec: SweepSpec,
                           pol: ExecPolicy) -> np.ndarray:
    """``suite_sweep_grid`` on a pre-normalized query — the worker the
    report layer calls directly so one ``SweepSpec`` build covers both
    the analytic and the simulated side of a report."""
    K = suite.n_traces
    out = np.zeros((K, spec.n_points, len(spec.ms), len(spec.css)))
    suite._check_members()
    if K == 0 or spec.n_points == 0:
        return out
    if spec.bad_costs or min(spec.ms, default=1) < 1:
        # degenerate machine parameters delegate to the per-member
        # engine, which keeps exact reference semantics
        for k, g in enumerate(suite.members):
            out[k] = _sweep_grid_spec(g, spec, pol)
        return out
    pairs = spec.pairs
    res = np.zeros((K, spec.n_uniq, len(pairs)))
    # one union plan per distinct m: blocks sharing m have ~equal replay
    # depth (slot-chain depth scales with 1/m), so merging their
    # compute_slots variants widens levels without deepening the union,
    # while distinct m values stay separate — a shallow m=8 replay never
    # pays the m=2 serial depth, and smaller plans keep the whole alpha
    # axis inside one memory-budget chunk
    groups: OrderedDict = OrderedDict()
    for i, (mm, _cs) in enumerate(pairs):
        groups.setdefault(mm, []).append(i)
    for idxs in groups.values():
        sub = _suite_grid_batch(suite, spec.uniq,
                                [pairs[i] for i in idxs], spec.unit, pol)
        res[:, :, idxs] = sub
    with span("reduce"):
        out[:] = spec.restore(res, axis=1).reshape(
            K, spec.n_points, len(spec.ms), len(spec.css))
    return out


def suite_sweep_grid(suite: EDagSuite, alphas, ms=(4,), compute_slots=(0,),
                     unit: float = 1.0, backend: Optional[str] = None,
                     mem_budget: Optional[int] = None,
                     use_cache: bool = True,
                     replay_dtype: Optional[str] = None, *,
                     policy: Optional[ExecPolicy] = None) -> np.ndarray:
    """Simulated makespans for every member over the full grid, in one
    level pass per distinct m.

    Returns a ``(n_traces, len(alphas), len(ms), len(compute_slots))``
    array whose slice ``[k]`` is bit-identical to
    ``sweep_grid(suite.members[k], alphas, ms, compute_slots, unit)`` —
    the whole-suite entry point for paper-protocol runs.

    Cost structure: the suite pays ONE union plan for the whole grid
    (block schedules come from the member plan memos / the persistent
    ``schedule_cache`` keyed by each member's trace digest; only missing
    (member, m, compute_slots) combinations record) and one stacked
    alpha replay whose serial depth is the *deepest* block, not the sum
    over members and machine pairs — independent blocks interleave
    inside each level of the shared kernel, and the replay streams in
    alpha chunks under the policy's memory budget.  Heterogeneous suites
    are chunked *per replay group* (``_member_groups``): a member too
    big to fit a full-width replay chunk in the budget streams its
    alpha axis alone, while the small members stay batched with wide
    chunks — grouping changes chunk shapes only, never results.
    ``replay_dtype`` selects the jax-backend execution policy (opt-in
    exact x64, or the default error-bounded f32 mode with per-column
    f64 demotion); the grid is bit-identical under every policy.
    Duplicate or unsorted alphas are deduped and sorted internally; the
    returned alpha axis follows caller order.  Degenerate machine
    parameters (non-positive/non-finite alphas or unit, m < 1) delegate
    to the per-member engine, which keeps exact reference semantics.

    A 2-D ``(P, n_classes)`` alpha matrix evaluates the latency-class
    grid through the same union machinery: block schedules carry the
    recorded slot *provenance* (``_event_loop_classes``) instead of
    homogeneous slot chains, the union F fill gathers each memory row's
    own class alpha, and every (member, point) is certified by the
    issue-order check plus the per-block ``_verify_slots`` provenance
    check — one stacked level pass per distinct m, exactly like scalar
    grids, bit-identical to ``simulate_reference_classes``."""
    with span("query", entry="suite_sweep_grid"):
        pol = ExecPolicy.resolve(backend=backend, replay_dtype=replay_dtype,
                                 mem_budget=mem_budget, use_cache=use_cache,
                                 policy=policy)
        spec = SweepSpec.make(alphas, ms=ms, compute_slots=compute_slots,
                              unit=unit)
        return _suite_sweep_grid_spec(suite, spec, pol)


def suite_latency_sweep(suite: EDagSuite, alphas, m: int = 4,
                        unit: float = 1.0, compute_slots: int = 0,
                        backend: Optional[str] = None,
                        mem_budget: Optional[int] = None,
                        use_cache: bool = True,
                        replay_dtype: Optional[str] = None, *,
                        policy: Optional[ExecPolicy] = None) -> np.ndarray:
    """Single-axis suite sweep: ``(n_traces, len(alphas))`` makespans,
    row k bit-identical to ``latency_sweep(suite.members[k], ...)``."""
    pol = ExecPolicy.resolve(backend=backend, replay_dtype=replay_dtype,
                             mem_budget=mem_budget, use_cache=use_cache,
                             policy=policy)
    spec = SweepSpec.make(alphas, ms=(m,), compute_slots=(compute_slots,),
                          unit=unit)
    return _suite_sweep_grid_spec(suite, spec, pol)[:, :, 0, 0]
