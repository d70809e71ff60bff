"""MoE parallelism equivalence: TP, EP (all-to-all), and reduce-scatter
output must produce identical results on a real multi-device mesh.

Runs in a subprocess so the 8-device host platform doesn't leak into the
rest of the suite (jax locks the device count at first init).
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import ARCHS
from repro.models import moe
from repro.sharding.rules import sharding_ctx
from jax.sharding import AxisType

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = dataclasses.replace(ARCHS["granite-moe-1b-a400m"].reduced(),
                          d_model=64, d_ff=32, n_experts=8, top_k=2,
                          capacity_factor=8.0)
key = jax.random.PRNGKey(0)
d, E, ff = cfg.d_model, cfg.n_experts, cfg.d_ff
wb = {"router": jax.random.normal(key, (d, E)) * 0.1,
      "wg": jax.random.normal(key, (E, d, ff)) * 0.1,
      "wu": jax.random.normal(jax.random.PRNGKey(1), (E, d, ff)) * 0.1,
      "wd": jax.random.normal(jax.random.PRNGKey(2), (E, ff, d)) * 0.1}
x = jax.random.normal(jax.random.PRNGKey(3), (4, 8, d))
y_ref, _ = moe.moe_ffn(x, wb, cfg)
for mode, knob in (("tp", {}), ("ep", {}),
                   ("tp", {"moe_scatter_out": True})):
    c = dataclasses.replace(cfg, moe_parallelism=mode, **knob)
    with sharding_ctx(mesh):
        y, _ = jax.jit(lambda x, wb: moe.moe_ffn(x, wb, c))(x, wb)
    assert np.allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4), \
        (mode, knob)
# gradients flow through both collectives
for mode in ("tp", "ep"):
    c = dataclasses.replace(cfg, moe_parallelism=mode)
    with sharding_ctx(mesh):
        g = jax.grad(lambda w: moe.moe_ffn(x, w, c)[0].sum())(wb)
    assert all(bool(jnp.isfinite(l).all())
               for l in jax.tree_util.tree_leaves(g)), mode
print("OK")
"""


def test_moe_tp_ep_scatter_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
