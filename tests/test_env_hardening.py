"""Environment-variable hardening for the sweep engine.

Numeric tuning knobs ($EDAN_REPLAY_MEM_BUDGET, $EDAN_SCHEDULE_CACHE_MIN,
$EDAN_SCHEDULE_CACHE_MAX) must fall back to their defaults on empty,
whitespace, unparseable or negative values — a stray export must never
raise mid-sweep.  Mode-selecting knobs ($EDAN_BACKEND, $EDAN_X64,
$EDAN_REPLAY_DTYPE) are the opposite: a typo silently changing which
engine runs is worse than an error, so they raise with the valid
choices (the enum cases live in test_replay_dtype.py).
"""
import numpy as np
import pytest

from repro.core import (EDag, latency_sweep, select_backend,
                        simulate_reference, schedule_cache as sc)
from repro.core.plan import REPLAY_MEM_BUDGET, replay_mem_budget

BAD_NUMERIC = ["", "  ", "abc", "-5"]


def _chain(n: int = 12) -> EDag:
    g = EDag()
    prev = None
    for i in range(n):
        v = g.add_vertex(is_mem=(i % 2 == 0))
        if prev is not None:
            g.add_edge(prev, v)
        prev = v
    return g


@pytest.mark.parametrize("val", BAD_NUMERIC)
def test_replay_mem_budget_env_falls_back(monkeypatch, val):
    monkeypatch.setenv("EDAN_REPLAY_MEM_BUDGET", val)
    assert replay_mem_budget() == REPLAY_MEM_BUDGET
    # and a sweep under the bad value completes, bit-identical
    g = _chain()
    alphas = [50.0, 100.0, 200.0]
    want = np.array([simulate_reference(g, m=2, alpha=a) for a in alphas])
    assert np.array_equal(latency_sweep(g, alphas, m=2), want)


def test_replay_mem_budget_env_zero_falls_back(monkeypatch):
    monkeypatch.setenv("EDAN_REPLAY_MEM_BUDGET", "0")
    assert replay_mem_budget() == REPLAY_MEM_BUDGET


def test_replay_mem_budget_valid_env_and_override(monkeypatch):
    monkeypatch.setenv("EDAN_REPLAY_MEM_BUDGET", "4096")
    assert replay_mem_budget() == 4096
    assert replay_mem_budget(128) == 128       # explicit arg wins


@pytest.mark.parametrize("val", BAD_NUMERIC)
def test_schedule_cache_min_env_falls_back(monkeypatch, val):
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MIN", val)
    assert sc.min_vertices() == sc._DEFAULT_MIN_VERTICES


def test_schedule_cache_min_zero_is_valid(monkeypatch):
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MIN", "0")
    assert sc.min_vertices() == 0               # persist everything


@pytest.mark.parametrize("val", BAD_NUMERIC)
def test_schedule_cache_max_env_falls_back(monkeypatch, val):
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MAX", val)
    assert sc.max_entries() == sc._DEFAULT_MAX_ENTRIES


def test_schedule_cache_max_valid_env(monkeypatch):
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MAX", "7")
    assert sc.max_entries() == 7
    # an explicit 0 keeps its pre-hardening meaning: smallest cache (1)
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MAX", "0")
    assert sc.max_entries() == 1


def test_bad_numeric_envs_do_not_break_cached_sweeps(monkeypatch, tmp_path):
    """The full cache-backed sweep path survives all three knobs being
    garbage at once (the mid-sweep scenario the fallback exists for)."""
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", str(tmp_path))
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MIN", "  ")
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE_MAX", "abc")
    monkeypatch.setenv("EDAN_REPLAY_MEM_BUDGET", "-1")
    g = _chain(20)
    alphas = [50.0, 150.0, 250.0]
    want = np.array([simulate_reference(g, m=3, alpha=a, compute_slots=2)
                     for a in alphas])
    got = latency_sweep(g, alphas, m=3, compute_slots=2)
    assert np.array_equal(got, want)


# ---------------------------------------------------------- $EDAN_BACKEND

def test_backend_env_typo_raises_with_choices(monkeypatch):
    monkeypatch.setenv("EDAN_BACKEND", "palas")
    with pytest.raises(ValueError) as ei:
        select_backend()
    msg = str(ei.value)
    assert "EDAN_BACKEND" in msg and "numpy" in msg and "jax" in msg
    # an explicit valid argument still beats the broken environment
    assert select_backend("numpy") == "numpy"


def test_backend_argument_typo_raises_with_choices():
    with pytest.raises(ValueError) as ei:
        select_backend("cuda")
    msg = str(ei.value)
    assert "numpy" in msg and "jax" in msg


def test_backend_env_empty_means_auto(monkeypatch):
    monkeypatch.setenv("EDAN_BACKEND", "   ")
    assert select_backend() in ("numpy", "jax")


# --------------------------------------- service knobs (serve.analysis)

@pytest.mark.parametrize("val", BAD_NUMERIC)
def test_deadline_env_falls_back(monkeypatch, val):
    from repro.serve import default_deadline_s
    from repro.serve.analysis import DEFAULT_DEADLINE_S
    monkeypatch.setenv("EDAN_DEADLINE_S", val)
    assert default_deadline_s() == DEFAULT_DEADLINE_S


def test_deadline_env_valid_zero_and_inf(monkeypatch):
    from repro.serve import default_deadline_s
    from repro.serve.analysis import DEFAULT_DEADLINE_S
    monkeypatch.setenv("EDAN_DEADLINE_S", "2.5")
    assert default_deadline_s() == 2.5
    monkeypatch.setenv("EDAN_DEADLINE_S", "0")     # non-positive: fallback
    assert default_deadline_s() == DEFAULT_DEADLINE_S
    monkeypatch.setenv("EDAN_DEADLINE_S", "inf")   # non-finite: fallback
    assert default_deadline_s() == DEFAULT_DEADLINE_S


@pytest.mark.parametrize("val", BAD_NUMERIC)
def test_max_retries_env_falls_back(monkeypatch, val):
    from repro.serve import default_max_retries
    from repro.serve.analysis import DEFAULT_MAX_RETRIES
    monkeypatch.setenv("EDAN_MAX_RETRIES", val)
    assert default_max_retries() == DEFAULT_MAX_RETRIES


def test_max_retries_env_zero_is_valid(monkeypatch):
    from repro.serve import default_max_retries
    monkeypatch.setenv("EDAN_MAX_RETRIES", "0")
    assert default_max_retries() == 0              # retries disabled
    monkeypatch.setenv("EDAN_MAX_RETRIES", "5")
    assert default_max_retries() == 5


# ------------------------------------------------- $EDAN_FAULTS (mode knob)

def test_faults_env_typo_raises_with_choices(monkeypatch):
    """$EDAN_FAULTS selects *behaviour*, so like $EDAN_BACKEND a typo
    must raise with the valid choices — silently disarming the fault
    layer would un-test every degradation path."""
    from repro.serve import faults
    faults.reset()
    try:
        monkeypatch.setenv("EDAN_FAULTS", "reply:io")
        with pytest.raises(ValueError) as ei:
            faults.check("load")
        assert "replay" in str(ei.value) and "EDAN_FAULTS" in str(ei.value)
        monkeypatch.setenv("EDAN_FAULTS", "load:oi")
        with pytest.raises(ValueError) as ei:
            faults.check("load")
        assert "io" in str(ei.value) and "backend" in str(ei.value)
        monkeypatch.setenv("EDAN_FAULTS", "load:io:conut=1")
        with pytest.raises(ValueError) as ei:
            faults.check("load")
        assert "count" in str(ei.value)
    finally:
        faults.reset()


def test_faults_env_empty_means_disarmed(monkeypatch):
    from repro.serve import faults
    faults.reset()
    try:
        monkeypatch.setenv("EDAN_FAULTS", "   ")
        faults.check("load")                       # no fault armed
        assert faults.active() == []
    finally:
        faults.reset()
