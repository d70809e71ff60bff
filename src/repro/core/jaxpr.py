"""jaxpr frontend — array-granularity eDAG of a JAX program.

Vertices are jaxpr equations; edges are SSA true dependencies (the compiler
has already removed false dependencies, which is exactly the paper's §3.2.1
transformation).  ``scan`` bodies are unrolled (up to a limit) with carry
wiring so sequential-over-time structure shows up as depth, matching the
instruction-level eDAG's treatment of loops.

A vertex is a *memory-access vertex* when the arrays it touches exceed
``mem_threshold_bytes`` (stand-in for "does not fit in cache/VMEM" — the
paper's RAM-vs-cache split at array granularity).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
from jax.extend import core as jcore

from .graph import EDag

_ELEMENTWISE_COST = 1.0


def _aval_bytes(aval) -> float:
    try:
        return float(np.prod(aval.shape) * aval.dtype.itemsize)
    except Exception:
        return 0.0


def _eqn_flops(eqn) -> float:
    """Coarse per-primitive cost: 2*M*N*K for dot_general, element count
    otherwise (unit floor)."""
    prim = eqn.primitive.name
    out_elems = sum(float(np.prod(v.aval.shape)) for v in eqn.outvars
                    if hasattr(v.aval, "shape"))
    if prim == "dot_general":
        # out_elems already covers batch x M x N; k is the contraction
        # extent.  Read it from whichever operand's contracting dims index
        # validly — batched layouts put batch dims first, so a stale or
        # hand-built dims tuple can misindex one side; the other side's
        # contracting sizes are the same K by the dot_general contract.
        (lhs_c, rhs_c), _ = eqn.params["dimension_numbers"]
        k = 1.0
        for operand, contract in ((eqn.invars[0], lhs_c),
                                  (eqn.invars[1], rhs_c)):
            shape = getattr(getattr(operand, "aval", None), "shape", None)
            if shape is None:
                continue
            if not contract:
                k = 1.0
                break
            try:
                k = float(np.prod([shape[i] for i in contract]))
                break
            except IndexError:
                continue
        return max(2.0 * out_elems * k, 1.0)
    if prim in ("reduce_sum", "reduce_max", "reduce_min", "argmax", "argmin"):
        in_elems = sum(float(np.prod(v.aval.shape)) for v in eqn.invars
                       if hasattr(v.aval, "shape"))
        return max(in_elems, 1.0)
    return max(out_elems * _ELEMENTWISE_COST, 1.0)


def _call_jaxpr(eqn):
    """The sub-jaxpr of a call-like equation, or None.

    Any equation carrying a ``jaxpr`` / ``call_jaxpr`` parameter whose
    inputs and outputs line up one-to-one with the equation's own is
    inlined transparently: ``jit`` (``pjit`` in older jax), the custom
    derivative calls, ``remat``/``checkpoint`` and friends.  Matching the
    structure instead of primitive names keeps jitted and checkpointed
    bodies transparent across jax renames.  Kernel calls such as
    ``pallas_call`` carry a ref-based body whose arity differs (outputs are
    written through refs), so they stay one opaque vertex."""
    sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    inner = getattr(sub, "jaxpr", sub)
    if (inner is None or not hasattr(inner, "eqns")
            or len(inner.invars) != len(eqn.invars)
            or len(inner.outvars) != len(eqn.outvars)):
        return None
    return sub


def _jaxpr_cost(jaxpr, limit: int) -> float:
    """Total flop cost of a (sub-)jaxpr under the builder's traversal rules:
    scans count ``min(length, limit)`` body repeats, call primitives inline,
    and ``cond`` counts its max-cost branch.  Used to pick which cond branch
    to emit without mutating the real graph."""
    total = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            steps = min(int(eqn.params["length"]), limit)
            total += steps * _jaxpr_cost(eqn.params["jaxpr"].jaxpr, limit)
            continue
        if prim == "cond":
            branches = eqn.params.get("branches") or ()
            if branches:
                total += max(_jaxpr_cost(getattr(b, "jaxpr", b), limit)
                             for b in branches)
                continue
        sub = _call_jaxpr(eqn)
        if sub is not None:
            total += _jaxpr_cost(getattr(sub, "jaxpr", sub), limit)
            continue
        total += _eqn_flops(eqn)
    return total


class _Builder:
    def __init__(self, g: EDag, mem_threshold_bytes: float,
                 scan_unroll_limit: int):
        self.g = g
        self.thresh = mem_threshold_bytes
        self.limit = scan_unroll_limit

    def run(self, jaxpr, env: Dict) -> Dict:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "scan":
                self._scan(eqn, env)
                continue
            sub = _call_jaxpr(eqn)
            if prim == "cond":
                # A static eDAG cannot keep both sides of a data-dependent
                # branch, so emit the worst-case path: traverse every branch
                # and keep the max-total-cost one (ties break to the first
                # branch).  This matches the paper's pessimistic-latency
                # framing — the sensitivity bound must cover the expensive
                # side — and never silently drops a branch's cost/depth the
                # way "always branches[0]" did.
                branches = eqn.params.get("branches") or ()
                sub = max(branches, default=None, key=lambda b: _jaxpr_cost(
                    getattr(b, "jaxpr", b), self.limit))
            if sub is not None:
                inner = getattr(sub, "jaxpr", sub)
                sub_env = {}
                consts = getattr(sub, "consts", ()) or ()
                for cv, _ in zip(inner.constvars, consts):
                    sub_env[cv] = None
                args = eqn.invars
                if prim == "cond":        # first invar is the predicate
                    args = eqn.invars[1:]
                for iv, arg in zip(inner.invars, args):
                    sub_env[iv] = env.get(arg) if not isinstance(
                        arg, jcore.Literal) else None
                out_env = self.run(inner, sub_env)
                for ov, sv in zip(eqn.outvars, inner.outvars):
                    env[ov] = out_env.get(sv) if not isinstance(
                        sv, jcore.Literal) else None
                continue
            self._emit(eqn, env)
        result = {}
        for v, vid in env.items():
            result[v] = vid
        return result

    def _emit(self, eqn, env) -> None:
        nbytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                     if not isinstance(v, jcore.Literal))
        nbytes += sum(_aval_bytes(v.aval) for v in eqn.outvars)
        vid = self.g.add_vertex(cost=_eqn_flops(eqn),
                                is_mem=nbytes > self.thresh,
                                nbytes=nbytes, label=eqn.primitive.name)
        for iv in eqn.invars:
            if isinstance(iv, jcore.Literal):
                continue
            dep = env.get(iv)
            if dep is not None and dep < vid:
                self.g.add_edge(dep, vid)
        for ov in eqn.outvars:
            env[ov] = vid

    def _scan(self, eqn, env) -> None:
        params = eqn.params
        length = int(params["length"])
        n_carry = int(params["num_carry"])
        n_consts = int(params["num_consts"])
        closed = params["jaxpr"]
        inner = closed.jaxpr
        steps = min(length, self.limit)
        const_args = eqn.invars[:n_consts]
        carry_args = eqn.invars[n_consts:n_consts + n_carry]
        xs_args = eqn.invars[n_consts + n_carry:]
        carry_vids = [env.get(a) if not isinstance(a, jcore.Literal) else None
                      for a in carry_args]
        out_env: Dict = {}
        for _ in range(steps):
            sub_env: Dict = {}
            ivs = inner.invars
            for iv, arg in zip(ivs[:n_consts], const_args):
                sub_env[iv] = env.get(arg) if not isinstance(
                    arg, jcore.Literal) else None
            for iv, cv in zip(ivs[n_consts:n_consts + n_carry], carry_vids):
                sub_env[iv] = cv
            for iv, arg in zip(ivs[n_consts + n_carry:], xs_args):
                sub_env[iv] = env.get(arg) if not isinstance(
                    arg, jcore.Literal) else None
            out_env = self.run(inner, sub_env)
            carry_vids = [out_env.get(ov) if not isinstance(ov, jcore.Literal)
                          else None for ov in inner.outvars[:n_carry]]
        outs = eqn.outvars
        for ov, cv in zip(outs[:n_carry], carry_vids):
            env[ov] = cv
        # Stacked ys: each eqn outvar past the carries corresponds
        # positionally to a body outvar past the carries — wire it to the
        # final iteration's actual producer, not (as before) to the first
        # carry, which fabricated a dependency on an unrelated vertex.
        for ov, sv in zip(outs[n_carry:], inner.outvars[n_carry:]):
            env[ov] = (out_env.get(sv)
                       if not isinstance(sv, jcore.Literal) else None)


def edag_from_fn(fn, *args, mem_threshold_bytes: float = 0.0,
                 scan_unroll_limit: int = 64, **kwargs) -> EDag:
    """Trace ``fn(*args)`` to a jaxpr and build its array-level eDAG."""
    closed = jax.make_jaxpr(fn, **kwargs)(*args)
    return edag_from_jaxpr(closed, mem_threshold_bytes=mem_threshold_bytes,
                           scan_unroll_limit=scan_unroll_limit)


def edag_from_jaxpr(closed, mem_threshold_bytes: float = 0.0,
                    scan_unroll_limit: int = 64) -> EDag:
    g = EDag()
    b = _Builder(g, mem_threshold_bytes, scan_unroll_limit)
    env: Dict = {}
    jaxpr = closed.jaxpr
    for cv in jaxpr.constvars:
        env[cv] = None
    for iv in jaxpr.invars:
        env[iv] = None          # inputs: no producing vertex
    b.run(jaxpr, env)
    return g
