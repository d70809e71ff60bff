"""chip_smoke.py: its phases at tiny sizes on the CPU, and its refusals.

The phases run the jax backend with the Pallas step interpreted (the CPU
stand-in for the device path); the device check itself is left out, since
it must refuse a host without a TPU — which the last tests check.
"""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def device_path(monkeypatch, tmp_path):
    """The jax backend in its default float32 replay mode, under a
    private schedule cache and no injected faults."""
    from repro.serve import faults
    monkeypatch.setenv("EDAN_BACKEND", "jax")
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", str(tmp_path / "sched"))
    for var in ("EDAN_X64", "EDAN_REPLAY_DTYPE", "EDAN_FAULTS"):
        monkeypatch.delenv(var, raising=False)
    faults.reset()
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", was)
    faults.reset()


def _on_device(st):
    return (st["chunks"] > 0 and st["jax_chunks"] == st["chunks"]
            and st["numpy_chunks"] == 0 and st["demoted_columns"] == 0)


def test_paper_phase_tiny(smoke, device_path):
    rec = smoke.phase_paper(kernels=("atax", "mvt"), n=4,
                            alphas=(50.0, 100.0, 150.0), ms=(2, 4),
                            css=(0, 8))
    assert rec["phase"] == "paper" and rec["grid_points"] == 2 * 3 * 2 * 2
    assert _on_device(rec["stats"])
    assert rec["first_call_s"] > 0 and rec["wall_s"] > 0


def test_hpcg_phase_tiny(smoke, device_path):
    rec = smoke.phase_hpcg(n=3, iters=1, alphas=(50.0, 300.0))
    assert rec["phase"] == "hpcg" and rec["grid_points"] == 2
    assert _on_device(rec["stats"])


def test_service_phase_tiny(smoke, device_path):
    rec = smoke.phase_service(kernels=("atax", "mvt"), n=4, cg_n=3,
                              alphas=(50.0, 100.0))
    assert rec["phase"] == "service" and rec["requests"] == 4
    assert _on_device(rec["stats"])


def test_phase_fails_when_a_column_leaves_the_device(smoke, device_path):
    """An alpha whose quantum cannot certify in float32 demotes its
    column to numpy: still exact, but no longer a device run, so the
    phase must fail."""
    with pytest.raises(smoke.SmokeFailure, match="left the device"):
        smoke.phase_hpcg(n=3, iters=1, alphas=(50.0, 0.1))


def test_device_check_refuses_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.check_device()


def _run(script, cwd):
    """Run the script by path with no PYTHONPATH: it must find the
    package beside itself."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_fails_without_tpu():
    p = _run(SCRIPT, ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"ok"' not in p.stdout


def test_script_fails_without_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    p = _run(str(alone), str(tmp_path))
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
