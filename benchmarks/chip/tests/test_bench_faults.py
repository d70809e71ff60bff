"""The check fails a run whose timed path is broken underneath."""
import numpy as np
import pytest


def _alter(result):
    """Every answer one cycle late, where the entry produces it."""
    if isinstance(result, dict):
        result = dict(result)
        result["simulated"] = np.asarray(result["simulated"]) + 1.0
        return result
    return np.asarray(result) + 1.0


@pytest.mark.parametrize("cell", ["paper15-rank", "small-kernel-report"])
def test_an_altered_answer_is_not_correct(run_tiny, monkeypatch, cell):
    import workload
    real = workload.Workload.run
    monkeypatch.setattr(workload.Workload, "run",
                        lambda self, q: _alter(real(self, q)))
    out, _ = run_tiny(cell)
    assert out["correct"] is False
    assert out["checks"]["mismatched_values"]["value"] > 0


def test_a_stale_answer_is_not_correct(run_tiny, monkeypatch):
    """Each query answered with the grid of the query before it, as a
    result cache keyed on the wrong thing would."""
    import workload
    real = workload.Workload.run
    last = []

    def stale(self, q):
        res = real(self, q)
        out = last[-1] if last else res
        last.append(res)
        return out
    monkeypatch.setattr(workload.Workload, "run", stale)
    out, _ = run_tiny("small-kernel-report")
    assert out["correct"] is False
    assert out["checks"]["mismatched_values"]["value"] > 0


def test_a_query_that_raises_is_a_missing_answer(run_tiny, monkeypatch):
    import workload
    real = workload.Workload.run
    calls = []

    def flaky(self, q):
        calls.append(q)
        if len(calls) == 3:          # set-up runs one warm-up query
            raise RuntimeError("device lost")
        return real(self, q)
    monkeypatch.setattr(workload.Workload, "run", flaky)
    out, failures = run_tiny("paper15-rank", seconds=0.3)
    assert out["checks"]["missing_answers"]["value"] == 1
    assert out["correct"] is False
    assert any("device lost" in w for f in failures for w in f["why"])


def test_a_trace_unlike_the_configuration_is_not_correct(run_tiny,
                                                         monkeypatch):
    """One memory flag flipped where the trace is built: the program and
    the reference both read the altered trace and agree, and the stated
    figures catch it."""
    import workload
    real = workload.build_trace

    def altered(app, params, home):
        g = real(app, params, home)
        g._finalize()
        if params["kernel"] == "atax":
            g.is_mem[-1] = not g.is_mem[-1]
        return g
    monkeypatch.setattr(workload, "build_trace", altered)
    out, _ = run_tiny("small-kernel-report")
    assert out["checks"]["traces_unlike_config"]["value"] == 1
    assert out["correct"] is False
