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
  recording run.  The simulator's one plan builder
  (``scheduler._build_plan``; a single trace's plan is one block) then
  stacks the schedules in rank space: slot chains are offset with their
  block, so they never cross a block boundary (each trace owns its own
  m memory slots and ``compute_slots`` ALU slots per machine
  configuration, exactly as if simulated alone), and the union's
  augmented levels are the per-block levels unchanged — a
  block-diagonal graph levelizes blockwise.

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
  rows in the same loop as the single-trace engine's
  (``scheduler._replay_verify``); any (member, point) the union schedule
  fails to certify falls back to that member's own ``simulate_batch``
  (which re-records and, with ``use_cache``, persists the replacement).
  Every entry of the suite grid is therefore bit-identical to
  single-trace ``sweep_grid`` — property-tested in ``tests/test_suite.py``
  and asserted per trace in the suite benchmark.

* **Class-vector grids ride the same union.**  A 2-D alpha matrix of
  latency-class vectors builds the plan from class-mode block schedules:
  each block records slot *provenance* instead of the homogeneous slot
  chain, provenance edges are offset with their block exactly like slot
  chains, and the union F fill gathers each memory row's own class
  alpha.  Verification adds the per-block slot-provenance certificate,
  so class grids run as one stacked level pass per distinct m — same
  chunking, same budget accounting, same fallback — instead of a
  per-member Python loop.

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
from .counters import span
from .graph import EDag, _auto_sweep_chunk, concat_edags
from .plan import ExecPolicy, SweepSpec
from .scheduler import (_build_plan, _keep, _memo_get, _memo_put,
                        _replay_verify, _schedule, _sweep_grid_spec,
                        simulate_batch)

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


# ------------------------------------------------------------ union groups

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
    (pair, member) blocks, one chunked stacked replay with per-block
    verification (``_replay_verify``), and the per-member fallback for
    anything the union schedule fails to certify.  Block schedules come
    from each member's reuse tiers (``_schedule``); a fresh recording is
    kept for the member (``_keep``).  2-D ``alphas`` rows run the
    class-mode plan — provenance slot chains, class-gathered F fill,
    and the additional per-block ``_verify_slots`` certificate."""
    n_classes = alphas.shape[1] if alphas.ndim == 2 else None
    # empty members own no block: their makespans stay 0
    where = [(k, pair) for pair in range(len(pairs)) for k in member_idx
             if suite.members[k].n_vertices]
    if not where:
        return
    cls_key = (tuple(suite.members[k].mem_class_digest()
                     for k in member_idx) if n_classes is not None else None)
    key = (tuple(member_idx), tuple(pairs), float(unit), cls_key)
    plan = _memo_get(suite._suite_plans, key) if pol.use_cache else None
    with span("plan", hit=int(plan is not None)):
        if plan is None:
            got = [_schedule(suite.members[k], *pairs[pair], unit, alphas[0],
                             pol.use_cache, n_classes)
                   for k, pair in where]
            plan = _build_plan([sched for sched, _, _ in got], n_classes)
            if pol.use_cache:
                for blk, (_, _, mk0) in zip(plan.blocks, got):
                    if mk0 is not None:       # recorded now
                        _keep(blk, unit, n_classes)
                _memo_put(suite._suite_plans, key, plan, _SUITE_PLAN_CAP)
    mk, ok = _replay_verify(plan, alphas, unit, pol)
    for b, (k, pair) in enumerate(where):
        out[k, ok[b], pair] = mk[b, ok[b]]
    if not ok.all():
        # any (block, point) the union schedule failed to certify falls
        # back to that member's own batched engine (which re-records and,
        # with use_cache, persists/memoizes the replacement — the next
        # suite plan build picks it up through the member tiers), and the
        # stale union plan is dropped so repeated suite sweeps converge
        if pol.use_cache:
            suite._suite_plans.pop(key, None)
        for b, (k, pair) in enumerate(where):
            bad = np.flatnonzero(~ok[b])
            if len(bad):
                blk = plan.blocks[b]
                out[k, bad, pair] = simulate_batch(
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
