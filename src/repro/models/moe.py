"""Mixture-of-Experts FFN: sort-based capacity dispatch under shard_map.

Two parallelism modes (cfg.moe_parallelism):
  * "tp": experts replicated (FSDP-gathered), expert FFN hidden dim
    tensor-parallel over 'model'; dispatch is purely local; one psum per
    layer (same collective pattern as a dense TP FFN).
  * "ep": experts sharded over 'model'; tokens sequence-split over 'model';
    two all-to-alls per layer move token slots to/from their experts
    (the GShard pattern).  EDAN's collective analysis makes the tp-vs-ep
    trade-off measurable per mesh (see EXPERIMENTS.md §Perf).

The dispatch is the standard argsort + capacity construction: top-k experts
per token, tokens sorted by expert id, positions beyond capacity dropped
(capacity factor cfg.capacity_factor).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..sharding.rules import batch_axes_for, current_mesh


def _smap(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(np.ceil(cfg.top_k * n_tokens * cfg.capacity_factor / cfg.n_experts))
    return max(c, 1)


def _dispatch(x, router_w, cfg: ModelConfig, capacity: int):
    """x: (n,d) -> (buf (E,C,d), slot (n*k,), tok (n*k,), gate (n*k,), aux)."""
    n, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (n,E)
    gate, idx = jax.lax.top_k(probs, k)                     # (n,k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch-style): E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (n * k)
    aux = E * jnp.sum(me * ce)

    flat_e = idx.reshape(-1)                                # (n*k,)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    tok = order // k
    first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos = jnp.arange(n * k) - first[sorted_e]
    keep = pos < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos, E * capacity)
    buf = jnp.zeros((E * capacity + 1, d), x.dtype).at[slot].set(x[tok])
    return (buf[:-1].reshape(E, capacity, d), slot, tok,
            gate.reshape(-1)[order], aux)


def _combine(y, slot, tok, gate, n: int):
    """y: (E,C,d) expert outputs -> (n,d) token outputs."""
    d = y.shape[-1]
    flat = jnp.concatenate([y.reshape(-1, d),
                            jnp.zeros((1, d), y.dtype)], axis=0)
    vals = flat[slot] * gate[:, None].astype(y.dtype)
    return jnp.zeros((n, d), y.dtype).at[tok].add(vals)


def _expert_ffn(buf, wg, wu, wd):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _local_tp(x, router_w, wg, wu, wd, cfg: ModelConfig, axis, all_axes=(),
              defer_psum: bool = False):
    n = x.shape[0]
    C = _capacity(n, cfg)
    buf, slot, tok, gate, aux = _dispatch(x, router_w, cfg, C)
    y = _expert_ffn(buf, wg.astype(x.dtype), wu.astype(x.dtype),
                    wd.astype(x.dtype))
    if axis is not None and not defer_psum:
        y = jax.lax.psum(y, axis)           # ff hidden dim was model-sharded
    if all_axes:
        aux = jax.lax.pmean(aux, all_axes)
    # with defer_psum the partial sums ride through the (linear) combine and
    # are reduce-scattered by the caller
    return _combine(y, slot, tok, gate, n), aux


def _local_ep(x, router_w, wg, wu, wd, cfg: ModelConfig, axis, A, all_axes=()):
    n, d = x.shape
    E = cfg.n_experts
    C = _capacity(n, cfg)
    buf, slot, tok, gate, aux = _dispatch(x, router_w, cfg, C)
    # scatter expert blocks to their owners; gather all devices' slots.
    # split_axis == concat_axis keeps all_to_all's VJP shape-stable; the
    # source-device dim is moved with explicit swapaxes.
    buf = buf.reshape(A, E // A, C, d)
    buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0)
    buf = buf.swapaxes(0, 1).reshape(E // A, A * C, d)    # my experts, all slots
    y = _expert_ffn(buf, wg.astype(x.dtype), wu.astype(x.dtype),
                    wd.astype(x.dtype))
    y = y.reshape(E // A, A, C, d).swapaxes(0, 1)         # (A, E/A, C, d)
    y = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0)
    y = y.reshape(E, C, d)                                # global expert order
    if all_axes:
        aux = jax.lax.pmean(aux, all_axes)
    return _combine(y, slot, tok, gate, n), aux


def moe_ffn(x, wb, cfg: ModelConfig):
    """x: (B,T,d) -> ((B,T,d), aux load-balance loss)."""
    B, T, d = x.shape
    mesh = current_mesh()
    router_w, wg, wu, wd = wb["router"], wb["wg"], wb["wu"], wb["wd"]
    if mesh is None or "model" not in mesh.axis_names:
        y, aux = _local_tp(x.reshape(-1, d), router_w, wg, wu, wd, cfg, None)
        return y.reshape(B, T, d), aux

    all_axes = tuple(mesh.axis_names)
    baxes = batch_axes_for(B, mesh)
    bspec = baxes if baxes else None
    msz = mesh.shape["model"]
    use_ep = (cfg.moe_parallelism == "ep" and cfg.n_experts % msz == 0
              and T % msz == 0)
    if use_ep:
        def fn(xl, r, g, u, w):
            Bl, Tl, _ = xl.shape
            y, aux = _local_ep(xl.reshape(-1, d), r, g, u, w, cfg, "model",
                               msz, all_axes)
            return y.reshape(Bl, Tl, d), aux
        spec_x = P(bspec, "model", None)
        spec_w = (P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None))
    else:
        scatter = cfg.moe_scatter_out and T % msz == 0

        def fn(xl, r, g, u, w):
            Bl, Tl, _ = xl.shape
            y, aux = _local_tp(xl.reshape(-1, d), r, g, u, w, cfg, "model",
                               all_axes, defer_psum=scatter)
            y = y.reshape(Bl, Tl, d)
            if scatter:
                # reduce-scatter the combined output along seq instead of
                # all-reducing the (E,C,d) expert buffer: 1/msz the bytes,
                # and the result lands already seq_res-sharded
                y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                         tiled=True)
            return y, aux
        spec_x = P(bspec, None, None)
        spec_out = P(bspec, "model" if scatter else None, None)
        spec_w = (P(None, None), P(None, None, "model"),
                  P(None, None, "model"), P(None, "model", None))
        shmapped = _smap(fn, mesh, (spec_x,) + spec_w, (spec_out, P()))
        return shmapped(x, router_w, wg, wu, wd)
    shmapped = _smap(fn, mesh, (spec_x,) + spec_w, (spec_x, P()))
    y, aux = shmapped(x, router_w, wg, wu, wd)
    return y, aux


def ep_rules(cfg: ModelConfig) -> dict:
    """Sharding-rule override when experts are model-sharded."""
    if cfg.moe_parallelism == "ep":
        return {"expert": ("model",), "mlp": ()}
    return {}
