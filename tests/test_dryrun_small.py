"""End-to-end dry-run pipeline on a small faked-device mesh (subprocess so
the device count doesn't leak): lower + compile a sharded train step and a
decode step for a reduced arch, then run the full EDAN HLO analysis chain —
collectives per axis, trip-scaled FLOPs/bytes, roofline terms."""
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, TrainConfig
from repro.core.hlo import analyze_collectives, hlo_flops_estimate, \
    hlo_hbm_bytes_estimate
from repro.core.sensitivity import collective_sensitivity
from jax.sharding import AxisType
from repro.models import get_model
from repro.models.module import abstract_params
from repro.sharding import param_partition_specs, sharding_ctx
from repro.sharding.rules import DEFAULT_RULES, decode_cache_rules
from repro.train.optimizer import AdamState
from repro.train.train_loop import make_train_step

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = dataclasses.replace(ARCHS["qwen3-0.6b"].reduced(),
                          n_layers=3, d_model=128, n_heads=8, n_kv_heads=4,
                          head_dim=16, d_ff=256, vocab_size=512,
                          dtype="bfloat16")
api = get_model(cfg)
rules = dict(DEFAULT_RULES)
specs = api.specs()
pspecs = param_partition_specs(specs, mesh, rules)
aparams = abstract_params(specs)
ns = lambda t: jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), t,
                                      is_leaf=lambda x: isinstance(x, P))

# ---- train step ----
tc = TrainConfig(microbatches=2)
step = make_train_step(api, tc)
opt = AdamState(
    mu=jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), aparams),
    nu=jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), aparams),
    step=jax.ShapeDtypeStruct((), jnp.int32))
batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}

def fn(p, o, b):
    with sharding_ctx(mesh, rules):
        return step(p, o, b)
opt_sh = AdamState(mu=ns(pspecs), nu=ns(pspecs),
                   step=NamedSharding(mesh, P()))
jf = jax.jit(fn, in_shardings=(ns(pspecs), opt_sh,
                               {k: NamedSharding(mesh, P("data"))
                                for k in batch}),
             donate_argnums=(0, 1))
compiled = jf.lower(aparams, opt, batch).compile()
txt = compiled.as_text()
axes = [("data", 2), ("model", 4)]
coll = analyze_collectives(txt, axes)
assert coll["total"]["count"] > 0, "sharded train step must have collectives"
assert coll["multipliers"], "scan trip counts must be inferred"
assert any(v >= 3 for v in coll["multipliers"].values()), coll["multipliers"]
flops = hlo_flops_estimate(txt)
n_tok = 8 * 64
model_flops = 6 * api.n_params() * n_tok / 8           # per device
assert flops > 0.3 * model_flops, (flops, model_flops)
assert hlo_hbm_bytes_estimate(txt) > 0
sens = collective_sensitivity(txt, axes)
assert "model" in sens["per_axis"]
assert sens["per_axis"]["model"].D >= cfg.n_layers     # chained per layer
ma = compiled.memory_analysis()
assert ma.temp_size_in_bytes > 0

# ---- decode step ----
from repro.configs.base import ShapeConfig
shape = ShapeConfig("d", 64, 8, "decode")
rules2 = dict(DEFAULT_RULES)
rules2.update(decode_cache_rules(8, 64, mesh))
cspecs = api.cache_specs(shape)
cache_abs = abstract_params(cspecs)
cpspecs = param_partition_specs(cspecs, mesh, rules2)
b2 = {"tokens": jax.ShapeDtypeStruct((8, 1), jnp.int32),
      "cur_index": jax.ShapeDtypeStruct((), jnp.int32)}

def dfn(p, c, b):
    with sharding_ctx(mesh, rules2):
        return api.decode_fn(p, c, b)
jd = jax.jit(dfn, in_shardings=(ns(pspecs), ns(cpspecs),
                                {"tokens": NamedSharding(mesh, P("data")),
                                 "cur_index": NamedSharding(mesh, P())}),
             out_shardings=(None, ns(cpspecs)), donate_argnums=(1,))
dcompiled = jd.lower(aparams, cache_abs, b2).compile()
dcoll = analyze_collectives(dcompiled.as_text(), axes)
assert dcoll["total"]["count"] > 0
print("OK")
"""


def test_dryrun_pipeline_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    assert "OK" in r.stdout
