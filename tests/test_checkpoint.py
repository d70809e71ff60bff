"""Checkpointing: atomic roundtrip, GC, async, elastic re-shard, and the
fault-tolerant loop with injected failures."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ckpt
from repro.train.fault import FaultTolerantLoop, StragglerStats
from jax.sharding import AxisType


def tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
            "step_count": jnp.int32(5)}


def test_roundtrip(tmp_path):
    t = tree()
    ckpt.save(t, str(tmp_path), step=3)
    got, meta = ckpt.restore(t, str(tmp_path))
    assert meta["step"] == 3
    for a, b in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(got)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_latest_and_gc(tmp_path):
    t = tree()
    for s in (1, 2, 3, 4):
        ckpt.save(t, str(tmp_path), step=s, keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 4
    steps = sorted(os.listdir(tmp_path))
    assert steps == ["step_00000003", "step_00000004"]


def test_no_tmp_dirs_left(tmp_path):
    ckpt.save(tree(), str(tmp_path), step=1)
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_async_save(tmp_path):
    th = ckpt.save_async(tree(), str(tmp_path), step=9)
    th.join()
    assert ckpt.latest_step(str(tmp_path)) == 9


def test_restore_with_shardings(tmp_path):
    """Elastic restore: device_put onto explicit shardings (re-shard path)."""
    t = tree()
    ckpt.save(t, str(tmp_path), step=1)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), t)
    got, _ = ckpt.restore(t, str(tmp_path), shardings=sh)
    assert jax.tree_util.tree_leaves(got)[0].sharding == NamedSharding(mesh, P())


def test_fault_loop_recovers_from_injected_failures(tmp_path):
    """Failures at arbitrary steps must replay from the last checkpoint and
    still produce the exact same final state as a failure-free run."""
    def step_fn(state, s):
        return {"x": state["x"] + s}

    def run(inject):
        loop = FaultTolerantLoop({"x": jnp.float32(0)}, str(tmp_path / name),
                                 save_every=3, inject_failure=inject)
        return loop.run(step_fn, 10)

    name = "clean"
    clean = run(None)
    name = "faulty"
    fails = {4: True, 8: True}
    seen = set()

    def inject(s):
        if s in fails and s not in seen:
            seen.add(s)
            return True
        return False
    faulty = run(inject)
    assert float(clean["x"]) == float(faulty["x"]) == sum(range(10))


def test_fault_loop_resumes_across_instances(tmp_path):
    def step_fn(state, s):
        return {"x": state["x"] + 1}
    d = str(tmp_path / "resume")
    loop1 = FaultTolerantLoop({"x": jnp.float32(0)}, d, save_every=2)
    loop1.run(step_fn, 4)
    loop2 = FaultTolerantLoop({"x": jnp.float32(0)}, d, save_every=2)
    assert loop2.start_step == 4
    out = loop2.run(step_fn, 7)
    assert float(out["x"]) == 7


def test_straggler_stats():
    st = StragglerStats(window=10, k=3.0)
    for _ in range(8):
        assert not st.record(1.0)
    assert st.record(10.0)
    assert st.flagged == 1
