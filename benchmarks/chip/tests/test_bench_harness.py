"""One run of each cell on the CPU at tiny sizes: the result line, the
failed count, and files found by name."""
import json
import os

import pytest

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
CELLS = ["paper15-rank", "small-kernel-report"]


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_has_exactly_the_contract_keys(run_tiny, cell):
    out, _ = run_tiny(cell)
    assert list(out) == KEYS                 # checks comes last
    assert out["correct"] is True
    assert out["attempted"] >= 1
    want = {"query_s", "setup_s"}
    if cell == "small-kernel-report":
        want.add("query_p95_s")
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["checks"] == {"mismatched_values": {"value": 0, "limit": 0},
                             "missing_answers": {"value": 0, "limit": 0},
                             "traces_unlike_config": {"value": 0,
                                                      "limit": 0}}
    json.dumps(out)


def test_traced_run_reports_no_device_metric_from_the_cpu(run_tiny):
    out, _ = run_tiny("small-kernel-report", trace=True)
    # the CPU gives no device trace and no memory counter: those
    # metrics are left out, never read as 0
    assert set(out["metrics"]) == {"replay_share_pct"}
    assert 0 < out["metrics"]["replay_share_pct"]["value"] <= 100
    assert "breakdown" not in out and "busy_s" not in out["device"]
    assert list(out)[-1] == "checks"


def test_a_query_that_leaves_the_device_counts_as_failed(run_tiny):
    # on the CPU the engine picks the numpy kernel: every query fails
    # the stats gate, and its answers are still checked
    out, failures = run_tiny("paper15-rank")
    assert out["failed"] == out["attempted"] > 0
    assert any("numpy_chunks" in w for f in failures for w in f["why"])
    assert out["correct"] is True


def test_the_device_path_counts_no_failure(run_tiny, monkeypatch):
    monkeypatch.setenv("EDAN_BACKEND", "jax")   # Pallas in interpret mode
    out, failures = run_tiny("small-kernel-report", seconds=0.2)
    assert failures == [] and out["failed"] == 0
    assert out["correct"] is True


def test_a_config_app_mix_and_metric_added_as_files_are_found(run_tiny):
    def edit(spec, home):
        from conftest import stated_figures
        cfg = {"name": "extra", "app": "mvt_app", "unit": 1.0,
               "traces": {"mvt": {"N": 4}},
               "figures": {"mvt": stated_figures("mvt", 4)}}
        with open(os.path.join(home, "apps", "mvt_app.py"), "w") as f:
            f.write("def build(p):\n"
                    "    from repro.apps import polybench\n"
                    "    return polybench.trace_kernel('mvt', p['N'])\n")
        with open(os.path.join(home, "configs", "extra.json"), "w") as f:
            json.dump(cfg, f)
        mix = {"entry": "sweep_grid", "pick": "cycle", "members": ["mvt"],
               "alphas": {"count": 3, "low": 10, "high": 20},
               "warmup_alphas": [10, 15, 20], "ms": [2],
               "compute_slots": [0], "trace_queries": 2,
               "check": {"points": 4}}
        with open(os.path.join(home, "traffic", "extra-mix.json"), "w") as f:
            json.dump(mix, f)
        with open(os.path.join(home, "metrics", "queries_done.py"), "w") as f:
            f.write("def read(run):\n    return len(run.latencies)\n")
        spec["configs"].append({"name": "extra", "source": "test",
                                "file": "bench/configs/extra.json",
                                "reduced": [], "why": "test"})
        spec["workloads"].append({"name": "extra-cell", "config": "extra",
                                  "traffic": "extra-mix", "chips": 1,
                                  "why": "test"})
        spec["end_to_end"].append({"name": "queries_done", "unit": "1",
                                   "better": "higher", "bound": 0.25,
                                   "source": "host_clock",
                                   "workloads": ["extra-cell"]})
    out, _ = run_tiny("extra-cell", edit=edit)
    assert out["metrics"]["queries_done"]["value"] == out["attempted"]
    assert out["correct"] is True
