"""The device level loop compiles for a TPU v5e at real plan shapes.

Compiles the jitted level loop of the jax backend (``backend._level_loop``
with its Pallas step, not interpreted) for one chip of a described v5e
topology: the chip's compiler runs here without the chip.  It refuses
what the chip would refuse — a kernel Mosaic cannot lower, a program
that does not fit the device's memory — so these tests guard the device
path at no chip time.  A passing compile says nothing about results or
speed.

Shapes are plans the chip smoke run drives, each padded to one width
(the row axis to a multiple of 128, or of 512 past it): the PAPER_15
PolyBench union at N=20, m=8 (both compute-slot variants merged, 11
alphas; 449 rows wide before padding), the HPCG CG trace at n=8, 3
iterations (3 alphas), and the service's union of kernel, CG and model
traces, whose analytic sweep has levels 22,016 rows wide (9 alphas); and
the plan of the benchmark's HPCG cell as ``_jax_padded`` cuts it into
segments of one band each: CG at n=16, 1 iteration, m=4 (11 alphas),
46,871 levels of which 4 are 4,096 or 8,192 rows wide.
Each is compiled under the two flag sets in use: the batched simulator's
replay (slot chains and ready times, no clamp) and the analytic sweep
(clamp, no slot chain, no ready times).  Each compile must hold the
Pallas kernel and keep device temp under 1 GiB: a gather tensor whose
row axis is off the lane width is relaid out whole on every call.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import backend as bk

# (name, rows, segments [(levels, rows)], Dmax, k)
PLANS = [
    ("paper15_m8", 1_108_760, [(34_506, 512)], 2, 11),
    ("hpcg_n8_iters3", 104_342, [(14_928, 1024)], 2, 3),
    ("service_union", 216_331, [(5_247, 22_016)], 7, 9),
    ("hpcg16_banded", 321_650, [(39_696, 128), (2, 8192), (6_147, 128),
                                (2, 4096), (1_024, 128)], 2, 11),
]
# (has_q, clamp, want_r)
FLAGS = [
    pytest.param((True, False, True), id="replay"),
    pytest.param((False, True, False), id="sweep"),
]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for_chip(monkeypatch):
    """Compile the Pallas step for the chip (not interpreted) and keep
    the persistent compilation cache out of it: an entry compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(bk, "_pallas_interpret", lambda: False)
    was = bool(jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("flags", FLAGS)
@pytest.mark.parametrize("plan", PLANS, ids=[p[0] for p in PLANS])
def test_level_loop_compiles_for_v5e(one_chip, compiled_for_chip, plan,
                                     flags):
    _, rows, segments, dmax, k = plan
    has_q, clamp, want_r = flags

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n = rows + 1 if has_q else rows     # slot chains add a sentinel row
    plan = bk.LevelPlan(
        arg((rows,), jnp.int32), arg((rows,), jnp.int32),
        tuple((arg((L, R, dmax), jnp.int32),
               arg((L, R), jnp.int32) if has_q else None,
               arg((L,), jnp.int32))
              for L, R in segments))
    args = (arg((n, k), jnp.float32),
            arg((n, k) if want_r else (1, k), jnp.float32), plan)
    run = bk._level_loop(has_q, clamp, want_r)
    compiled = jax.jit(run).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # every level writes its rows as one slice of the level-major F and R
    assert not re.search(r"\bscatter\(", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("plan", PLANS, ids=[p[0] for p in PLANS])
def test_column_absmax_compiles_for_v5e(one_chip, compiled_for_chip, plan):
    """The float32 certificate's device reduction (``_column_absmax``)
    over a plan's finish matrix: k values out, no temp the size of the
    matrix."""
    _, rows, _, _, k = plan
    x = jax.ShapeDtypeStruct((rows + 1, k), jnp.float32, sharding=one_chip)
    compiled = bk._absmax_program().lower(x).compile()
    assert compiled.out_info.shape == (k,)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
