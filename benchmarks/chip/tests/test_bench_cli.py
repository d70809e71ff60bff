"""The command refuses to measure where it cannot: no TPU, or a checkout
that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
HOME = os.path.relpath(BENCH, REPO)

ARGS = ["--workload", "small-kernel-report", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(HOME, "run.py"), *ARGS],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_a_checkout_of_only_the_benchmark_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / HOME,
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout == ""
