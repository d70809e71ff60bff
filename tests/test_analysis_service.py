"""Fault-tolerant analysis service (serve.analysis).

The acceptance properties of the request engine, driven by deterministic
injected faults (serve.faults):

* batching is invisible in results — co-batched members are
  bit-identical to solo runs;
* a poisoned member never corrupts its neighbours: the union is torn
  down into solo re-runs, the poison is quarantined, the healthy
  members' results stay bit-identical;
* every injected transient recovers within the retry budget (with the
  demotion ladder reported honestly);
* a deadline-exceeded request fails alone, with a structured error.

Most tests pin a clean fault environment (the CI fault-injection job
forces ``$EDAN_FAULTS`` globally; these tests assert exact behaviours of
*specific* faults).  ``test_service_survives_ambient_faults`` is the one
that deliberately runs under whatever the environment forces.
"""
import json

import numpy as np
import pytest

from repro.core import EDag, Tracer
from repro.core.metrics import grid_report
from repro.core.placement import search_placement
from repro.core.plan import REPLAY_BYTES_PER_CELL
from repro.serve import (AnalysisRequest, AnalysisService, faults,
                         default_deadline_s, default_max_retries)

try:
    import jax  # noqa: F401
    BACKENDS = ("numpy", "jax")
except Exception:  # pragma: no cover - jax ships in the CI image
    BACKENDS = ("numpy",)

ALPHAS = (60.0, 140.0)
GRID = dict(alphas=ALPHAS, ms=(2, 4), compute_slots=(0,))

# captured before the autouse fixture scrubs it: the spec the CI
# fault-injection matrix forces, replayed by the ambient smoke test
import os                                              # noqa: E402
AMBIENT_FAULTS = os.environ.get("EDAN_FAULTS", "")


@pytest.fixture(autouse=True)
def clean_env(monkeypatch, tmp_path):
    """Deterministic fault + cache environment for exact assertions."""
    monkeypatch.delenv("EDAN_FAULTS", raising=False)
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", str(tmp_path / "sched"))
    faults.reset()
    # the jax-float64 demotion rung flips the process-global x64 flag;
    # restore it so tests of the seed model stack (int32 cache indices)
    # are unaffected by ladder walks here
    x64_was = (bool(jax.config.jax_enable_x64)
               if "jax" in BACKENDS else None)
    yield
    faults.reset()
    if x64_was is not None:
        jax.config.update("jax_enable_x64", x64_was)


def rand_edag(seed: int, n: int = 40, p_edge: float = 0.12) -> EDag:
    rng = np.random.default_rng(seed)
    g = EDag()
    for i in range(n):
        g.add_vertex(is_mem=bool(rng.random() < 0.5))
        for j in range(i):
            if rng.random() < p_edge:
                g.add_edge(j, i)
    return g


def svc(**kw):
    kw.setdefault("start", False)
    kw.setdefault("backoff_s", 0.0)
    return AnalysisService(**kw)


def req(seed: int, **kw):
    for k, v in GRID.items():
        kw.setdefault(k, v)
    return AnalysisRequest(trace=rand_edag(seed), **kw)


def assert_reports_equal(a: dict, b: dict):
    for key in ("alphas", "ms", "compute_slots", "lam", "t_inf",
                "t_lower", "t_upper", "Lam", "simulated"):
        assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key
    for key in ("W", "D", "C"):
        assert a[key] == b[key]


# ---------------------------------------------------------------- happy path

def test_single_request_matches_grid_report():
    g = rand_edag(0)
    (res,) = svc().process([AnalysisRequest(trace=g, **GRID)])
    assert res.ok and res.error is None and res.retries == 0
    assert res.batch_rids == (res.rid,)
    want = grid_report(rand_edag(0), list(ALPHAS), ms=GRID["ms"],
                       compute_slots=GRID["compute_slots"],
                       simulate_points=True)
    assert np.array_equal(res.report["simulated"], want["simulated"])
    assert np.array_equal(res.report["t_inf"], want["t_inf"])
    assert res.report["W"] == float(want["W"])


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_results_bit_identical_to_solo(backend):
    reqs = [req(s, backend=backend) for s in (0, 1, 2)]
    batched = svc().process(reqs)
    assert all(r.ok for r in batched)
    assert all(len(r.batch_rids) == 3 for r in batched)
    for s, got in zip((0, 1, 2), batched):
        (solo,) = svc().process([req(s, backend=backend)])
        assert solo.ok and solo.batch_rids == (solo.rid,)
        assert_reports_equal(got.report, solo.report)


def test_union_alpha_slicing():
    """Requests with different alpha sets still batch; each gets exactly
    its own alphas back, bit-identical to a solo run."""
    r0 = req(0, alphas=(60.0, 140.0))
    r1 = req(1, alphas=(100.0, 220.0))
    a, b = svc().process([r0, r1])
    assert a.ok and b.ok and len(a.batch_rids) == 2
    assert a.report["alphas"].tolist() == [60.0, 140.0]
    assert b.report["alphas"].tolist() == [100.0, 220.0]
    (sa,) = svc().process([req(0, alphas=(60.0, 140.0))])
    (sb,) = svc().process([req(1, alphas=(100.0, 220.0))])
    assert_reports_equal(a.report, sa.report)
    assert_reports_equal(b.report, sb.report)


def test_incompatible_grids_do_not_batch():
    r0 = req(0, ms=(2,))
    r1 = req(1, ms=(4,))
    a, b = svc().process([r0, r1])
    assert a.ok and b.ok
    assert a.batch_rids == (a.rid,) and b.batch_rids == (b.rid,)


def test_memory_budget_splits_batches_and_priority_packs_first():
    # budget fits exactly two 40-vertex graphs on this grid: their
    # stacked replay cells plus their trace footprints (packing charges
    # member CSRs too — union construction copies them)
    def trace_bytes(seed):
        g = rand_edag(seed)
        g._finalize()
        return sum(g.array_nbytes().values())

    n_pairs = len(GRID["ms"]) * len(GRID["compute_slots"])
    rows2 = 2 * 40 * n_pairs
    budget = (rows2 * len(ALPHAS) * REPLAY_BYTES_PER_CELL
              + trace_bytes(1) + trace_bytes(2))
    reqs = [req(0, priority=0), req(1, priority=5), req(2, priority=5)]
    out = svc(mem_budget=budget).process(reqs)
    assert all(r.ok for r in out)
    lo, hi1, hi2 = out
    # the two priority-5 requests share the first batch; the priority-0
    # one spills into its own
    assert set(hi1.batch_rids) == {hi1.rid, hi2.rid}
    assert lo.batch_rids == (lo.rid,)


def test_kernel_traced_server_side():
    (res,) = svc().process([AnalysisRequest(kernel="atax", n=6, **GRID)])
    assert res.ok and res.report["name"] == "atax"
    with_trace = svc().process(
        [AnalysisRequest(kernel="cg", n=3, alphas=(100.0,))])
    assert with_trace[0].ok


def test_unknown_kernel_fails_with_choices():
    (res,) = svc().process(
        [AnalysisRequest(kernel="ataxx", n=6, alphas=(100.0,),
                         max_retries=0)])
    assert not res.ok and res.error["code"] == "load-error"
    assert "atax" in res.error["message"]


def test_request_validation():
    with pytest.raises(ValueError):
        AnalysisRequest(alphas=(100.0,))             # neither trace nor kernel
    with pytest.raises(ValueError):
        AnalysisRequest(trace=rand_edag(0), kernel="atax")
    with pytest.raises(ValueError):
        AnalysisRequest(kernel="atax", deadline_s=0.0)
    with pytest.raises(ValueError):
        AnalysisRequest(kernel="atax", max_retries=-1)


# ------------------------------------------------------- retries + demotion

def test_transient_load_fault_recovers():
    faults.install("load", "io", count=1)
    (res,) = svc().process([req(0)])
    assert res.ok and res.retries == 1


def test_transient_finalize_fault_recovers():
    faults.install("finalize", "backend", count=1)
    (res,) = svc().process([req(0)])
    assert res.ok and res.retries == 1


def test_transient_replay_fault_demotes_and_recovers():
    faults.install("replay", "backend", count=1)
    (res,) = svc().process([req(0)])
    assert res.ok and res.retries == 1
    assert res.policy["demotions"] == 1
    assert (res.policy["backend"], res.policy["replay_dtype"]) == \
        ("jax", "float64")
    # demoted result is still bit-identical to the clean solo run
    faults.reset()
    (clean,) = svc().process([req(0)])
    assert_reports_equal(res.report, clean.report)


def test_kernel_fault_degrades_inside_backend():
    """A fault inside the jax kernel itself (backend.fault_hook) is not
    swallowed by the backend: it reaches the service's demotion ladder.
    Firing on every device pass, it walks the request down to the numpy
    rung, which reports the demotions and a result bit-identical to a
    clean run."""
    if len(BACKENDS) < 2:
        pytest.skip("jax not available")
    faults.install("kernel", "backend")
    (res,) = svc().process([req(0, backend="jax")])
    assert res.ok
    assert res.policy["backend"] == "numpy"
    assert res.policy["demotions"] >= 1 and res.retries >= 1
    assert faults.fire_log
    faults.reset()
    (clean,) = svc().process([req(0, backend="jax")])
    assert clean.policy["demotions"] == 0
    assert_reports_equal(res.report, clean.report)


def test_retry_budget_exhaustion_is_structured():
    faults.install("replay", "backend")          # hard fault, all rungs
    (res,) = svc().process([req(0, max_retries=1)])
    assert not res.ok
    e = res.error
    assert e["code"] == "replay-error" and e["stage"] == "replay"
    assert set(e) == {"code", "stage", "message", "retries"}
    assert res.retries >= 1


def test_transient_report_fault_recovers():
    faults.install("report", "io", count=1)
    (res,) = svc().process([req(0)])
    assert res.ok and res.retries == 1


# --------------------------------------------------------- poison isolation

@pytest.mark.parametrize("backend", BACKENDS)
def test_poisoned_member_never_corrupts_cobatched_results(backend):
    """THE acceptance property: one poisoned member in a union batch is
    isolated and quarantined; every healthy member's report is
    bit-identical to a clean solo run."""
    # clean solo references first
    refs = {}
    for s in (0, 1, 2):
        (r,) = svc().process([req(s, backend=backend)])
        assert r.ok
        refs[s] = r.report

    service = svc()
    # the union pass always fails; rid 1's solo re-run also fails
    faults.install("replay", "backend", min_batch=2)
    faults.install("replay", "backend", rid=1)
    out = service.process([req(s, backend=backend) for s in (0, 1, 2)])
    healthy0, poisoned, healthy2 = out
    assert healthy0.ok and healthy2.ok
    assert not poisoned.ok
    assert poisoned.error["code"] == "replay-error"
    # isolation: healthy members were re-run solo
    assert healthy0.batch_rids == (healthy0.rid,)
    assert healthy2.batch_rids == (healthy2.rid,)
    # bit-identity with the clean solo references
    assert_reports_equal(healthy0.report, refs[0])
    assert_reports_equal(healthy2.report, refs[2])

    # quarantine: the same trace fails fast on the same service, even
    # with all faults cleared, and costs no neighbour anything
    faults.reset()
    again = service.process([req(1, backend=backend),
                             req(2, backend=backend)])
    assert not again[0].ok and again[0].error["code"] == "quarantined"
    assert again[1].ok
    assert_reports_equal(again[1].report, refs[2])


def test_quarantine_is_per_service_not_global():
    faults.install("replay", "backend")
    service = svc()
    (bad,) = service.process([req(7, max_retries=0)])
    assert not bad.ok
    faults.reset()
    (fresh,) = svc().process([req(7)])       # a new service has no memory
    assert fresh.ok


# ------------------------------------------------------------------ deadline

def test_deadline_exceeded_fails_alone():
    faults.install("load", "latency", rid=0, delay=0.3)
    out = svc().process([
        req(0, deadline_s=0.05, max_retries=0),
        req(1, deadline_s=60.0),
    ])
    slow, fast = out
    assert not slow.ok
    assert slow.error["code"] == "deadline"
    assert slow.error["stage"] == "load"
    assert fast.ok
    (ref,) = svc().process([req(1)])
    assert_reports_equal(fast.report, ref.report)


def test_deadline_checked_between_retries():
    """Backoff must never outlive the deadline: a hard fault with a big
    retry budget still resolves as a deadline error, promptly."""
    import time
    faults.install("replay", "backend")
    t0 = time.monotonic()
    (res,) = svc(backoff_s=0.05).process(
        [req(0, deadline_s=0.2, max_retries=1000)])
    assert not res.ok and res.error["code"] == "deadline"
    assert time.monotonic() - t0 < 30.0


def test_env_defaults_applied_at_admission(monkeypatch):
    monkeypatch.setenv("EDAN_DEADLINE_S", "0.0001")
    faults.install("load", "latency", delay=0.05)
    (res,) = svc().process([req(0)])
    assert not res.ok and res.error["code"] == "deadline"
    monkeypatch.setenv("EDAN_DEADLINE_S", "60")
    monkeypatch.setenv("EDAN_MAX_RETRIES", "0")
    faults.reset()
    faults.install("replay", "backend", count=1)
    (res2,) = svc().process([req(0, backend="numpy")])
    # zero retries and a one-rung numpy ladder: the transient is fatal
    assert not res2.ok and res2.error["code"] == "replay-error"


# --------------------------------------------------------------- placement

def placement_trace(seed: int = 0, n_obj: int = 3, n_ops: int = 24):
    """A deterministic multi-object trace (same seed => same digest)."""
    rng = np.random.default_rng(seed)
    tr = Tracer()
    arrs = [tr.array(np.arange(8.0 * (i + 1)), f"obj{i}")
            for i in range(n_obj)]
    acc = tr.const(0.0)
    for _ in range(n_ops):
        a = arrs[rng.integers(n_obj)]
        acc = tr.alu("+", acc, a.load(int(rng.integers(len(a.arr)))))
        if rng.random() < 0.4:
            b = arrs[rng.integers(n_obj)]
            b.store(int(rng.integers(len(b.arr))), acc)
    return tr.g, tr.object_sizes()


def preq(seed: int = 0, **kw):
    g, sizes = placement_trace(seed)
    kw.setdefault("object_sizes", sizes)
    kw.setdefault("local_budget", sum(sizes.values()) // 2)
    return AnalysisRequest(trace=g, kind="placement", **kw)


def assert_placement_reports_equal(a: dict, b: dict):
    for key in ("method", "local", "makespan", "all_local", "all_remote",
                "budget"):
        assert a[key] == b[key], key
    for key in ("budgets", "curve"):
        assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key


def test_placement_request_matches_direct_search():
    (res,) = svc().process([preq(0)])
    assert res.ok and res.error is None and res.retries == 0
    rep = res.report
    assert rep["kind"] == "placement"
    g, sizes = placement_trace(0)
    want = search_placement(g, 1.0, 200.0, sum(sizes.values()) // 2,
                            sizes=sizes, m=4, compute_slots=0)
    assert rep["method"] == want.method
    assert tuple(rep["local"]) == want.local
    assert rep["makespan"] == want.makespan
    assert rep["all_local"] == want.all_local
    assert rep["all_remote"] == want.all_remote
    assert np.array_equal(np.asarray(rep["budgets"]), want.budgets)
    assert np.array_equal(np.asarray(rep["curve"]), want.curve)
    assert set(rep["marginal"]) == set(want.marginal)


def test_placement_runs_solo_in_a_mixed_wave():
    """A placement request in a wave with grid requests never joins their
    union batch, and the grid members' results stay bit-identical."""
    refs = [svc().process([req(s)])[0].report for s in (0, 1)]
    out = svc().process([req(0), preq(3), req(1)])
    grid0, place, grid1 = out
    assert all(r.ok for r in out)
    assert place.batch_rids == (place.rid,)
    assert place.report["kind"] == "placement"
    assert_reports_equal(grid0.report, refs[0])
    assert_reports_equal(grid1.report, refs[1])
    # the two grid members still co-batched with each other
    assert len(grid0.batch_rids) == 2 and len(grid1.batch_rids) == 2


def test_transient_placement_fault_demotes_and_recovers():
    faults.install("placement", "backend", count=1)
    (res,) = svc().process([preq(0)])
    assert res.ok and res.retries == 1
    assert res.policy["demotions"] == 1
    assert (res.policy["backend"], res.policy["replay_dtype"]) == \
        ("jax", "float64")
    faults.reset()
    (clean,) = svc().process([preq(0)])
    assert clean.policy["demotions"] == 0
    assert_placement_reports_equal(res.report, clean.report)


def test_hard_placement_fault_structured_and_quarantined():
    faults.install("placement", "backend")       # hard: survives the ladder
    service = svc()
    (res,) = service.process([preq(7, max_retries=1)])
    assert not res.ok
    e = res.error
    assert e["code"] == "replay-error" and e["stage"] == "placement"
    assert set(e) == {"code", "stage", "message", "retries"}
    # quarantine: the same trace digest fails fast on this service...
    faults.reset()
    (again,) = service.process([preq(7)])
    assert not again.ok and again.error["code"] == "quarantined"
    # ...but a fresh service has no memory of it
    (fresh,) = svc().process([preq(7)])
    assert fresh.ok


def test_placement_deadline_checked_between_retries():
    import time
    faults.install("placement", "backend")
    t0 = time.monotonic()
    (res,) = svc(backoff_s=0.05).process(
        [preq(0, deadline_s=0.2, max_retries=1000)])
    assert not res.ok
    assert res.error["code"] == "deadline"
    assert res.error["stage"] == "placement"
    assert time.monotonic() - t0 < 30.0


def test_placement_request_validation():
    g, _ = placement_trace(0)
    with pytest.raises(ValueError, match="local_budget"):
        AnalysisRequest(trace=g, kind="placement")
    with pytest.raises(ValueError, match="placement_method"):
        AnalysisRequest(trace=g, kind="placement", local_budget=0,
                        placement_method="magic")
    with pytest.raises(ValueError, match="kind"):
        AnalysisRequest(trace=g, kind="disaggregate")


def test_placement_result_persisted_as_valid_json(tmp_path):
    out_dir = tmp_path / "results"
    (res,) = svc(results_dir=out_dir).process([preq(0)])
    assert res.ok and res.stored is True
    (f,) = sorted(out_dir.glob("result_*.json"))
    doc = json.loads(f.read_text())
    assert doc["rid"] == res.rid
    assert doc["report"]["kind"] == "placement"
    assert doc["report"]["makespan"] == res.report["makespan"]
    assert doc["report"]["curve"] == \
        np.asarray(res.report["curve"]).tolist()


# ------------------------------------------------------- model-zoo requests

def mreq(config="qwen3-0.6b", phase="decode", **kw):
    for k, v in GRID.items():
        kw.setdefault(k, v)
    return AnalysisRequest(config=config, phase=phase, kind="model", **kw)


def test_model_request_matches_direct_grid_report():
    """kind='model' server-traces the config and the grid is bit-identical
    to tracing + grid_report by hand."""
    from repro.models.tracing import trace_model
    (res,) = svc().process([mreq()])
    assert res.ok and res.error is None
    assert res.report["name"] == "qwen3-0.6b:decode"
    g = trace_model("qwen3-0.6b", "decode", use_store=False)
    want = grid_report(g, list(ALPHAS), ms=GRID["ms"],
                       compute_slots=GRID["compute_slots"],
                       simulate_points=True)
    assert res.report["W"] == float(want["W"])
    assert res.report["D"] == float(want["D"])
    assert np.array_equal(res.report["simulated"], want["simulated"])
    assert np.array_equal(res.report["t_inf"], want["t_inf"])


def test_model_requests_join_union_batches():
    """Model requests are ordinary grid members: two configs plus an
    uploaded trace co-batch into one union, every result bit-identical
    to its solo run."""
    reqs = [mreq("qwen3-0.6b"), mreq("rwkv6-7b"), req(0)]
    batched = svc().process(reqs)
    assert all(r.ok for r in batched)
    assert all(len(r.batch_rids) == 3 for r in batched)
    for r, solo_req in zip(batched, [mreq("qwen3-0.6b"), mreq("rwkv6-7b"),
                                     req(0)]):
        (solo,) = svc().process([solo_req])
        assert_reports_equal(r.report, solo.report)


def test_transient_trace_model_fault_recovers():
    faults.install("trace-model", "io", count=1)
    (res,) = svc().process([mreq()])
    assert res.ok and res.retries == 1


def test_hard_trace_model_fault_structured():
    faults.install("trace-model", "io")          # hard fault, every attempt
    (res,) = svc().process([mreq(max_retries=1)])
    assert not res.ok
    assert res.error["code"] == "load-error"
    assert res.error["stage"] == "trace-model"
    assert res.retries >= 1


def test_unknown_config_fails_with_choices():
    (res,) = svc().process([mreq("not-a-model", max_retries=0)])
    assert not res.ok and res.error["code"] == "load-error"
    assert "qwen3-0.6b" in res.error["message"]


def test_model_request_validation():
    with pytest.raises(ValueError, match="phase"):
        AnalysisRequest(config="qwen3-0.6b", kind="model", phase="serve")
    with pytest.raises(ValueError, match="kind='model'"):
        AnalysisRequest(config="qwen3-0.6b")
    with pytest.raises(ValueError, match="exactly one"):
        AnalysisRequest(config="qwen3-0.6b", kernel="atax", kind="model")
    with pytest.raises(ValueError, match="config="):
        AnalysisRequest(kind="model")


# ------------------------------------------------------ background admission

def test_background_submit_and_run():
    service = AnalysisService(batch_window_s=0.01, backoff_s=0.0)
    try:
        out = service.run([req(0), req(1)], timeout=120.0)
        assert all(r.ok for r in out)
        assert out[0].rid != out[1].rid
    finally:
        service.close()
    with pytest.raises(RuntimeError):
        service.submit(req(2))


def test_close_drains_pending():
    service = AnalysisService(batch_window_s=0.05, backoff_s=0.0)
    tickets = [service.submit(req(s)) for s in (0, 1)]
    service.close()
    for t in tickets:
        assert t.event.wait(60.0)
        assert t.result is not None and t.result.ok


# ------------------------------------------------------------- result store

def test_results_persisted_as_valid_json(tmp_path):
    out_dir = tmp_path / "results"
    service = svc(results_dir=out_dir)
    (res,) = service.process([req(0)])
    assert res.ok and res.stored is True
    (f,) = sorted(out_dir.glob("result_*.json"))
    doc = json.loads(f.read_text())
    assert doc["rid"] == res.rid
    assert doc["report"]["simulated"] == \
        np.asarray(res.report["simulated"]).tolist()


def test_store_failure_degrades_not_fails(tmp_path):
    faults.install("store", "io")                # hard store fault
    service = svc(results_dir=tmp_path / "results")
    (res,) = service.process([req(0)])
    assert res.ok and res.stored is False        # degraded, not failed
    assert res.report is not None
    assert list((tmp_path / "results").glob("*.json")) == []


# ------------------------------------------- ambient (CI-forced) fault smoke

def test_service_survives_ambient_faults(monkeypatch):
    """Runs under whatever ``$EDAN_FAULTS`` the CI fault-injection
    matrix forces — every transient class must recover within the
    default budgets."""
    if AMBIENT_FAULTS:
        monkeypatch.setenv("EDAN_FAULTS", AMBIENT_FAULTS)
    faults.reset()                                # re-arm from the env
    try:
        service = AnalysisService(start=False, backoff_s=0.001)
        out = service.process([req(s, deadline_s=300.0)
                               for s in (0, 1, 2)])
        assert all(r.ok for r in out), [r.error for r in out]
        for s in (0, 1):                 # enough waves to reach every=K
            (solo,) = service.process([req(s, deadline_s=300.0)])
            assert solo.ok, solo.error
        for s in (0, 1):                 # the placement stage, too
            (place,) = service.process([preq(s, deadline_s=300.0)])
            assert place.ok, place.error
        # the trace-model stage, too: enough requests to reach every=K
        for ph in ("prefill", "decode", "decode"):
            (mdl,) = service.process([mreq(phase=ph, deadline_s=300.0)])
            assert mdl.ok, mdl.error
        if AMBIENT_FAULTS:
            assert sum(faults.fire_log.values()) > 0   # it really fired
    finally:
        faults.reset()


def test_crash_mid_result_write_leaves_nothing_or_valid(tmp_path):
    """SIGKILL while a result JSON is being persisted: a survivor sees
    either no result file or a complete parseable one — never a torn
    write (tempfile + os.replace, same recipe as the schedule cache)."""
    import os
    import signal
    import subprocess
    import sys

    out_dir = tmp_path / "results"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    child_code = (
        "import os, sys, time\n"
        f"sys.path.insert(0, {src!r})\n"
        "real_replace = os.replace\n"
        "def slow_replace(a, b):\n"
        "    print('REPLACING', flush=True)\n"
        "    time.sleep(30)\n"
        "    real_replace(a, b)\n"
        "import numpy as np\n"
        "from repro.core import EDag\n"
        "from repro.serve import AnalysisService, AnalysisRequest\n"
        "g = EDag()\n"
        "prev = None\n"
        "for i in range(12):\n"
        "    v = g.add_vertex(is_mem=(i % 2 == 0))\n"
        "    if prev is not None:\n"
        "        g.add_edge(prev, v)\n"
        "    prev = v\n"
        f"svc = AnalysisService(start=False, results_dir={str(out_dir)!r})\n"
        "os.replace = slow_replace\n"
        "svc.process([AnalysisRequest(trace=g, alphas=(100.0,))])\n")
    child = subprocess.Popen([sys.executable, "-c", child_code],
                             env=dict(os.environ),
                             stdout=subprocess.PIPE, text=True)
    line = child.stdout.readline().strip()
    assert line == "REPLACING", line
    os.kill(child.pid, signal.SIGKILL)
    child.wait(timeout=30)
    # no torn result: either nothing keyed, or valid JSON (here: nothing,
    # because the replace never ran — only tmp debris may remain)
    assert list(out_dir.glob("result_*.json")) == []
    for f in out_dir.glob("result_*.json"):
        json.loads(f.read_text())       # any keyed file must parse
    # a survivor service reuses the directory cleanly
    (res,) = svc(results_dir=out_dir).process([req(0)])
    assert res.ok and res.stored is True
    (kept,) = sorted(out_dir.glob("result_*.json"))
    json.loads(kept.read_text())
