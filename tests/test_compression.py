"""int8 gradient compression with error feedback (train/compression.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import AxisType
from repro.train.compression import (compressed_psum_local, dequantize_int8,
                                     init_error_state, make_dp_train_step,
                                     quantize_int8)


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 10
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6


def test_error_feedback_accumulates():
    """With error feedback, the *running sum* of dequantized payloads tracks
    the running sum of true gradients (bias-free compression)."""
    rng = np.random.default_rng(0)
    err = jnp.zeros((64,))
    total_true = np.zeros(64)
    total_sent = np.zeros(64)
    for i in range(30):
        g = jnp.asarray(rng.standard_normal(64) * 0.01)
        total_true += np.asarray(g)
        target = g + err
        q, s = quantize_int8(target)
        sent = dequantize_int8(q, s)
        err = target - sent
        total_sent += np.asarray(sent)
    assert np.abs(total_sent - total_true).max() < 1e-3


def _mesh():
    return jax.make_mesh((jax.device_count(),), ("data",),
                         axis_types=(AxisType.Auto,))


def test_dp_train_step_compressed_matches_uncompressed():
    """On a tiny regression problem, the compressed DP step converges to the
    same loss as the exact step (error feedback keeps it unbiased)."""
    mesh = _mesh()
    W = jax.random.normal(jax.random.PRNGKey(0), (8, 1)) * 0.5

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    def update_fn(params, grads, opt):
        return ({"w": params["w"] - 0.05 * grads["w"]}, opt)

    def run(compress):
        params = {"w": jnp.zeros((8, 1))}
        err = init_error_state(params)
        step = make_dp_train_step(loss_fn, update_fn, mesh, compress=compress)
        rng = np.random.default_rng(1)
        losses = []
        for i in range(120):
            x = jnp.asarray(rng.standard_normal((16, 8)))
            y = x @ W + 0.01 * jnp.asarray(rng.standard_normal((16, 1)))
            params, _, err, l = step(params, None, err, {"x": x, "y": y})
            losses.append(float(l))
        return params, losses

    p_c, l_c = run(True)
    p_u, l_u = run(False)
    assert l_c[-1] < 0.01 and l_u[-1] < 0.01
    np.testing.assert_allclose(np.asarray(p_c["w"]), np.asarray(p_u["w"]),
                               atol=0.05)


def test_compressed_psum_local_single_device():
    """Inside shard_map on 1 device: payload == mean == input (+residual)."""
    mesh = _mesh()
    from jax.sharding import PartitionSpec as P

    g = {"w": jnp.asarray(np.linspace(-1, 1, 32), jnp.float32)}
    e = init_error_state(g)

    def f(gl, el):
        return compressed_psum_local(gl, el, "data")
    out, err = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                             out_specs=(P(), P()), check_vma=False)(g, e)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=0.02)
    np.testing.assert_allclose(np.asarray(out["w"] + err["w"]),
                               np.asarray(g["w"]), atol=1e-6)
