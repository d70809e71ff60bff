"""The HPCG cell on the CPU at a tiny size (CG at n = 4): the result
line, the check against the plain reference, and the padding share read
from the backend's counters."""


def test_the_hpcg_cell_runs_and_checks_at_tiny_size(run_tiny):
    out, _ = run_tiny("hpcg16-sweep")
    assert out["correct"] is True and out["attempted"] >= 1
    assert set(out["metrics"]) == {"query_s", "setup_s"}
    assert out["checks"] == {"mismatched_values": {"value": 0, "limit": 0},
                             "missing_answers": {"value": 0, "limit": 0},
                             "traces_unlike_config": {"value": 0,
                                                      "limit": 0}}


def test_the_hpcg_cell_reads_its_padding_share_on_the_device_path(
        run_tiny, monkeypatch):
    monkeypatch.setenv("EDAN_BACKEND", "jax")   # Pallas in interpret mode
    out, failures = run_tiny("hpcg16-sweep", trace=True)
    assert failures == [] and out["correct"] is True
    assert set(out["metrics"]) == {"replay_share_pct", "pad_efficiency_pct"}
    assert 0 < out["metrics"]["pad_efficiency_pct"]["value"] <= 100


def test_the_padding_share_is_left_out_without_a_device_pass(run_tiny):
    # the numpy kernel runs on the CPU: no padded plan, nothing to read
    out, _ = run_tiny("hpcg16-sweep", trace=True)
    assert "pad_efficiency_pct" not in out["metrics"]
