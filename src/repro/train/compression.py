"""Gradient compression: int8 quantized all-reduce with error feedback.

At 1000+ nodes the inter-pod (DCI) gradient reduction is the highest-latency,
lowest-bandwidth collective in the step (EDAN's per-axis lambda makes this
quantitative — see EXPERIMENTS.md).  Compressing that reduction 4x (f32 ->
int8 + per-tensor scale) cuts its bytes term; error feedback keeps
convergence (the quantization residual is carried into the next step).

Usage: inside a shard_map-over-data train step (``make_dp_train_step``) the
local, unreduced gradients go through ``compressed_psum_local`` instead of a
plain psum.  Tests verify convergence parity with the uncompressed path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _smap(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def quantize_int8(x):
    """Per-tensor symmetric int8.  Returns (q, scale)."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum_local(grads, err, axis):
    """Call INSIDE shard_map: quantize local grads (+error feedback), psum
    the int8 payload (as int32 — no overflow for <=2^23 replicas), share a
    pmax scale, return (mean f32 grads, new error residuals)."""
    n = jax.lax.psum(1, axis)

    def one(g, e):
        target = g.astype(jnp.float32) + e
        s_shared = jax.lax.pmax(
            jnp.maximum(jnp.max(jnp.abs(target)), 1e-12) / 127.0, axis)
        q = jnp.clip(jnp.round(target / s_shared), -127, 127)
        recon = q * s_shared
        tot = jax.lax.psum(q, axis)
        return (tot * s_shared / n).astype(g.dtype), target - recon

    flat, tdef = jax.tree_util.tree_flatten(grads)
    eflat = jax.tree_util.tree_leaves(err)
    outs = [one(g, e) for g, e in zip(flat, eflat)]
    return (jax.tree_util.tree_unflatten(tdef, [o[0] for o in outs]),
            jax.tree_util.tree_unflatten(tdef, [o[1] for o in outs]))


def init_error_state(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_dp_train_step(loss_fn, update_fn, mesh, axis="data",
                       compress: bool = True):
    """Manual data-parallel train step with explicit (optionally compressed)
    gradient all-reduce — the controllable path for the pod/DCI axis.

    loss_fn(params, batch)->scalar; update_fn(params, grads, opt)->(p,opt).
    Returns step(params, opt, err, batch)->(params, opt, err, loss)."""

    def local_step(params, opt, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis)
        if compress:
            grads, err = compressed_psum_local(grads, err, axis)
        else:
            grads = jax.lax.pmean(grads, axis)
        params, opt = update_fn(params, grads, opt)
        return params, opt, err, loss

    rep = jax.tree_util.tree_map(lambda _: P(), jax.tree_util.tree_structure)
    def step(params, opt, err, batch):
        in_specs = (P(), P(), P(),
                    jax.tree_util.tree_map(lambda _: P(axis), batch))
        out_specs = (P(), P(), P(), P())
        return _smap(local_step, mesh, in_specs, out_specs)(
            params, opt, err, batch)
    return step
