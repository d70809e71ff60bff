"""The plan layer: one normalized sweep query + one resolved execution policy.

Every sweep/report entry point in the engine answers the same shape of
question — *evaluate these alpha points (scalar latencies or latency-class
vectors) over these (m, compute_slots) machine configurations at this ALU
unit cost* — under the same execution knobs: which kernel backend runs the
stacked (max,+) passes, which replay dtype policy governs the device path,
how many bytes one replay chunk may hold, and whether recorded schedules
are reused.  Historically each entry point hand-threaded that
``(backend, replay_dtype, mem_budget, use_cache)`` tuple through every
internal call and re-implemented alpha normalization; this module is the
single place both live now.

* ``SweepSpec`` captures the *query*: alphas converted/validated once,
  deduped and sorted once (with the inverse permutation retained so
  results always come back in caller order), the machine axes as plain
  int tuples, and the degenerate-model screen the engines branch on.

* ``ExecPolicy`` captures the *execution environment*: resolved once from
  arguments + environment at the public entry point and carried through
  the engine as one frozen object.  Its ``accumulate`` method is the only
  place in the tree that unpacks the raw policy tuple into
  ``backend.replay_accumulate`` keyword arguments —
  ``tools/check_policy_plumbing.py`` enforces that no other module
  re-threads ``replay_dtype=`` / ``mem_budget=`` / ``use_cache=`` call
  kwargs (public entry-point *signatures* keep them, as thin shims that
  immediately fold them into a policy via ``ExecPolicy.resolve``).

Resolution semantics are deliberately asymmetric, matching the env
hardening contract (tests/test_env_hardening.py): the numeric tuning knob
``$EDAN_REPLAY_MEM_BUDGET`` is resolved eagerly and tolerantly (garbage
falls back to the default; a stray export must never raise mid-sweep),
while the mode knobs ``backend`` / ``replay_dtype`` are carried through
*unresolved* and validated at kernel dispatch exactly as before — a typo
in a mode knob must keep raising with the valid choices, at the same
point it always did.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from . import backend as _bk

# Point-chunk memory budget for the batched replay: the per-master pass
# holds ~3 (n_vertices, chunk) float64 matrices (base/finish, ready times,
# scratch) plus, on the jax backend's f32 mode, the float32 copies of the
# live columns (+8 bytes/cell worst case), so chunk ~ budget /
# (REPLAY_BYTES_PER_CELL * n).  Override per call with ``mem_budget=``
# or process-wide with $EDAN_REPLAY_MEM_BUDGET (bytes).  The per-cell
# constant is shared by the scheduler's chunk divisor, the suite's
# heterogeneous grouping rule and the service's admission packing, so the
# three accounting rules can never drift apart.
REPLAY_MEM_BUDGET = 512 * 1024 * 1024
REPLAY_BYTES_PER_CELL = 32


def replay_mem_budget(override: Optional[int] = None) -> int:
    """Replay working-set budget in bytes: arg > $EDAN_REPLAY_MEM_BUDGET >
    default.  Bounds the (n, chunk) matrices of one stacked pass so
    HPCG/LULESH-size traces stream through the level kernel.

    Environment values that are empty, unparseable or non-positive fall
    back to the default — a stray ``export EDAN_REPLAY_MEM_BUDGET=``
    must never raise mid-sweep (explicit override arguments stay strict:
    a wrong *argument* is a caller bug worth surfacing)."""
    if override is not None:
        return max(int(override), 1)
    try:
        env = int(os.environ.get("EDAN_REPLAY_MEM_BUDGET", ""))
    except (TypeError, ValueError):
        return REPLAY_MEM_BUDGET
    return env if env > 0 else REPLAY_MEM_BUDGET


@dataclass(frozen=True)
class ExecPolicy:
    """Resolved execution policy for one engine invocation (or many).

    ``backend`` / ``replay_dtype`` are the *requested* mode knobs (None =
    auto / environment), validated lazily at kernel dispatch so typo
    semantics and raise points are unchanged; ``mem_budget`` is the
    resolved chunk budget in bytes; ``use_cache`` gates every schedule
    reuse tier.  The object is frozen and hashable: resolve it once at a
    public entry point and pass the same instance through every internal
    call — repeated calls under one policy are the designed idiom (the
    service resolves one policy per demotion-ladder rung, grids resolve
    one per call)."""

    backend: Optional[str] = None
    replay_dtype: Optional[str] = None
    mem_budget: int = REPLAY_MEM_BUDGET
    use_cache: bool = True

    @classmethod
    def resolve(cls, backend: Optional[str] = None,
                replay_dtype: Optional[str] = None,
                mem_budget: Optional[int] = None,
                use_cache: bool = True,
                policy: Optional["ExecPolicy"] = None) -> "ExecPolicy":
        """Fold shim keyword arguments + environment into one policy.

        The universal shim idiom: every public entry point keeps its
        historical ``backend=/replay_dtype=/mem_budget=/use_cache=``
        signature and starts with ``pol = ExecPolicy.resolve(...)``,
        also accepting a pre-resolved ``policy=`` that wins outright
        (internal callers pass policies, never raw kwargs)."""
        if policy is not None:
            return policy
        return cls(backend=backend, replay_dtype=replay_dtype,
                   mem_budget=replay_mem_budget(mem_budget),
                   use_cache=bool(use_cache))

    # ---------------------------------------------------- kernel dispatch

    def accumulate(self, lv, F: np.ndarray, quanta,
                   clamp: bool = False,
                   R_out: Optional[np.ndarray] = None) -> np.ndarray:
        """One stacked (max,+) pass under this policy.

        The single site in the tree that unpacks the policy into
        ``backend.replay_accumulate`` keyword arguments — everything
        above this call passes ``ExecPolicy`` objects around."""
        return _bk.replay_accumulate(lv, F, quanta, clamp=clamp,
                                     R_out=R_out, backend=self.backend,
                                     replay_dtype=self.replay_dtype)

    # -------------------------------------------------- budget accounting

    def points_chunk(self, n: int, k: int) -> int:
        """Balanced point chunk under the replay memory budget: the level
        loop pays per-level dispatch once per chunk, so fewer, equal-sized
        chunks beat one full chunk plus a sliver.

        The floor is a single point — at million-vertex scale even one
        (n, 4) float64 pair is ~70 MB, so a higher floor would silently
        break the budget exactly where it matters."""
        cap = max(1, int(self.mem_budget //
                         max(REPLAY_BYTES_PER_CELL * n, 1)))
        n_chunks = -(-k // cap)
        return -(-k // n_chunks)

    def cap_rows(self, k: int) -> int:
        """Largest plan row count for which a full-width (rows, k) replay
        chunk fits the budget — the suite's heterogeneous grouping rule
        and the service's admission packing share this divisor with
        ``points_chunk`` by construction."""
        return max(self.mem_budget // max(REPLAY_BYTES_PER_CELL * k, 1), 1)

    # ---------------------------------------------------- degraded modes

    def ladder(self) -> Tuple["ExecPolicy", ...]:
        """Execution rungs for degraded-mode retries, most capable first:
        the policy as requested, then exact x64 on the device backend
        (dodges f32-certificate demotion storms), then plain numpy (no
        device at all).  A TPU has no float64 kernels, so there the x64
        rung is left out rather than run on numpy under its name.  Budget
        and cache policy carry through unchanged; rungs equal to an
        earlier rung are dropped."""
        rungs = [self,
                 ExecPolicy(backend="jax", replay_dtype="float64",
                            mem_budget=self.mem_budget,
                            use_cache=self.use_cache),
                 ExecPolicy(backend="numpy", replay_dtype=None,
                            mem_budget=self.mem_budget,
                            use_cache=self.use_cache)]
        if self.backend == "numpy" or _bk.on_tpu():
            del rungs[1]              # no float64 device rung to demote onto
        out: list = []
        for r in rungs:
            if r not in out:
                out.append(r)
        return tuple(out)


@dataclass(frozen=True, eq=False)
class SweepSpec:
    """One normalized sweep query: what to evaluate, independent of how.

    ``alphas`` is the caller's point axis as a float64 array — 1-D scalar
    latencies or a 2-D ``(P, n_classes)`` matrix of latency-class vectors
    (``class_mode``).  ``uniq`` is the sorted, deduplicated point axis the
    batched engines actually evaluate and ``inv`` the scatter index that
    restores caller order (None when the caller's axis is already sorted
    and unique — normalization is idempotent).  ``ms`` / ``css`` are the
    machine axes as int tuples, ``unit`` the ALU cost.  ``bad_costs``
    records the once-computed degenerate screen on costs (non-positive or
    non-finite alphas or unit); a degenerate query is never deduped — the
    reference loops replay the caller's axis literally."""

    alphas: np.ndarray
    uniq: np.ndarray
    inv: Optional[np.ndarray]
    ms: Tuple[int, ...]
    css: Tuple[int, ...]
    unit: float
    class_mode: bool
    bad_costs: bool

    @classmethod
    def make(cls, alphas, ms=(4,), compute_slots=(0,),
             unit: float = 1.0) -> "SweepSpec":
        """Normalize and validate a sweep query once.

        Accepts everything the entry points historically accepted —
        scalars, lists, arrays, 2-D class-vector matrices — and raises on
        anything of higher rank (silently mispricing a 3-D array would be
        worse than an error)."""
        a = np.asarray(list(np.atleast_1d(alphas)), dtype=np.float64)
        if a.ndim > 2:
            raise ValueError(
                f"alphas must be 1-D (scalar latencies) or 2-D "
                f"(latency-class vectors); got ndim={a.ndim}")
        ms_t = tuple(int(v) for v in np.atleast_1d(ms))
        css_t = tuple(int(v) for v in np.atleast_1d(compute_slots))
        unit = float(unit)
        class_mode = a.ndim == 2
        bad = (unit <= 0 or not np.isfinite(unit) or
               (len(a) > 0 and bool((a <= 0).any() or
                                    not np.isfinite(a).all())))
        uniq: np.ndarray = a
        inv: Optional[np.ndarray] = None
        if not bad and len(a):
            if class_mode:
                u, iv = np.unique(a, axis=0, return_inverse=True)
                iv = np.asarray(iv).reshape(-1)
            else:
                u, iv = np.unique(a, return_inverse=True)
            if len(u) != len(a) or not np.array_equal(u, a):
                uniq, inv = u, iv
        return cls(alphas=a, uniq=uniq, inv=inv, ms=ms_t, css=css_t,
                   unit=unit, class_mode=class_mode, bad_costs=bad)

    # ------------------------------------------------------------ queries

    @property
    def n_points(self) -> int:
        """Points on the caller's alpha axis."""
        return len(self.alphas)

    @property
    def n_uniq(self) -> int:
        """Points the batched engines evaluate (after dedupe)."""
        return len(self.uniq)

    @property
    def n_classes(self) -> Optional[int]:
        """Latency-class count (class mode), else None."""
        return int(self.alphas.shape[1]) if self.class_mode else None

    @property
    def pairs(self) -> list:
        """The (m, compute_slots) machine grid, row-major like the
        output axes of ``sweep_grid``."""
        return [(m, cs) for m in self.ms for cs in self.css]

    def degenerate(self, m: int) -> bool:
        """Whether configuration ``m`` must take the reference loop:
        degenerate machine models (m < 1, or any non-positive /
        non-finite cost) keep the seed engine's semantics exactly."""
        return m < 1 or self.bad_costs

    def restore(self, values: np.ndarray, axis: int = 0) -> np.ndarray:
        """Scatter uniq-axis results back to caller order along
        ``axis`` (identity when the caller's axis was already
        sorted-unique)."""
        if self.inv is None:
            return values
        return np.take(values, self.inv, axis=axis)
