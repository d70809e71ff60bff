"""Model-zoo tracing (models.tracing): jaxpr eDAGs of real model configs
through the full analysis pipeline.

Pins the eDAG shape (vertex / edge / mem-vertex counts) and digest
stability for one small config per family (prefill + decode), property-
tests suite-vs-solo bit-identity of model grids, and smokes the trace
store dedup, placement-object recovery, component traces and the HLO
roofline companion.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import grid_report, report, suite_grid_report
from repro.core.placement import search_placement
from repro.core.suite import EDagSuite
from repro.models import tracing

# One small config per family: (V, E, mem-vertex count) per phase.  Any
# change to the jaxpr frontend's emission rules, the models' layer
# structure, the reduced shapes or jax's own lowering of the layers shows
# up as a concrete diff here.  Traced with the x64 flag off (the jaxpr,
# and so the eDAG, differs under x64: index arithmetic widens).
PINS = {
    "qwen3-0.6b": {"prefill": (421, 533, 191), "decode": (357, 444, 34)},
    "granite-moe-1b-a400m": {"prefill": (810, 1122, 203),
                             "decode": (605, 802, 54)},
    "rwkv6-7b": {"prefill": (610, 779, 389), "decode": (339, 416, 30)},
    "zamba2-7b": {"prefill": (716, 906, 328), "decode": (412, 490, 36)},
    "seamless-m4t-large-v2": {"prefill": (967, 1222, 461),
                              "decode": (354, 416, 32)},
    "internvl2-2b": {"prefill": (387, 491, 177), "decode": (321, 400, 34)},
}


@pytest.fixture
def x64_off():
    """Trace with the x64 flag off, whatever earlier tests in this
    process left it at; restored afterwards."""
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", was)


def test_zoo_covers_every_family_once():
    assert sorted(tracing.ZOO) == ["dense", "encdec", "hybrid", "moe",
                                   "ssm", "vlm"]
    assert sorted(tracing.ZOO.values()) == sorted(PINS)


@pytest.mark.parametrize("name", sorted(PINS))
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_family_shape_and_digest_pinned(name, phase, x64_off):
    g = tracing.trace_model(name, phase, use_store=False)
    dg = g.trace_digest()
    assert (g.n_vertices, g.n_edges,
            int(g.is_mem.sum())) == PINS[name][phase]
    assert len(dg) == 64
    # re-tracing the same request is digest-stable
    g2 = tracing.trace_model(name, phase, use_store=False)
    assert g2.trace_digest() == dg
    # whole-model traces must show real memory parallelism: W above D
    # (a collapsed opaque trace degenerates to a chain, W == D)
    r = report(g)
    assert r.W == PINS[name][phase][2]
    assert r.D < r.W


def test_train_phase_traces_grad_graph():
    g = tracing.trace_model("qwen3-0.6b", "train", use_store=False)
    gp = tracing.trace_model("qwen3-0.6b", "prefill", use_store=False)
    # the backward pass roughly doubles the graph; definitely bigger
    assert g.n_vertices > 2 * gp.n_vertices


@settings(deadline=None, max_examples=8)
@given(st.lists(st.sampled_from([1.0, 2.0, 8.0, 50.0, 200.0, 1000.0]),
                min_size=1, max_size=3),
       st.lists(st.sampled_from([1.0, 4.0, 64.0, 400.0]),
                min_size=1, max_size=3))
def test_suite_vs_solo_bit_identity_property(alphas_a, alphas_b):
    """Two model eDAGs with *different* request alphas, run as one union
    suite over the merged alpha axis: every per-trace field must equal
    the solo ``grid_report`` bit-for-bit at the shared points."""
    alphas_a, alphas_b = set(alphas_a), set(alphas_b)
    ga = tracing.trace_model("qwen3-0.6b", "decode", use_store=False)
    gb = tracing.trace_model("rwkv6-7b", "decode", use_store=False)
    union = np.array(sorted(set(alphas_a) | set(alphas_b)))
    suite = EDagSuite([ga, gb], names=["a", "b"])
    sr = suite_grid_report(suite, union, ms=(2, 8), compute_slots=(0, 4),
                           simulate_points=True)
    for k, (g, mine) in enumerate([(ga, alphas_a), (gb, alphas_b)]):
        solo = grid_report(g, np.array(sorted(mine)), ms=(2, 8),
                           compute_slots=(0, 4), simulate_points=True)
        idx = np.searchsorted(union, np.array(sorted(mine)))
        assert float(solo["W"]) == float(np.asarray(sr["W"])[k])
        assert float(solo["D"]) == float(np.asarray(sr["D"])[k])
        assert float(solo["C"]) == float(np.asarray(sr["C"])[k])
        assert np.array_equal(solo["lam"], np.asarray(sr["lam"])[k])
        for key in ("t_inf", "t_lower", "t_upper", "Lam", "simulated"):
            assert np.array_equal(np.asarray(solo[key]),
                                  np.asarray(sr[key])[k][idx]), key


def test_trace_store_dedup_roundtrip(tmp_path, monkeypatch):
    """Second identical request is served from the digest-addressed
    store via the request-key index — same digest, same analysis
    arrays, no re-trace (the store path drops labels; analysis fields
    are what the digest covers)."""
    monkeypatch.setenv("EDAN_TRACE_STORE", str(tmp_path))
    g1 = tracing.trace_model("qwen3-0.6b", "decode")
    idx = tmp_path / "model_traces.json"
    assert idx.exists()
    g2 = tracing.trace_model("qwen3-0.6b", "decode")
    assert g2.trace_digest() == g1.trace_digest()
    assert np.array_equal(g2.cost, g1.cost)
    assert np.array_equal(g2.is_mem, g1.is_mem)
    # a different phase is a different key and a different digest
    g3 = tracing.trace_model("qwen3-0.6b", "prefill")
    assert g3.trace_digest() != g1.trace_digest()


def test_model_objects_feed_placement_search():
    """Placement over a model decode step: primitive-label objects ride
    ``search_placement`` and the documented bound holds."""
    g = tracing.trace_model("qwen3-0.6b", "decode", use_store=False)
    objs = tracing.model_objects(g)
    assert len(objs) >= 2
    assert all(o.traffic > 0 and len(o.vertices) for o in objs)
    total = sum(o.nbytes for o in objs)
    rep = search_placement(g, alpha_local=2.0, alpha_remote=400.0,
                           budget=total // 2, objects=objs, m=4)
    assert rep.all_local <= rep.makespan <= rep.all_remote
    assert set(rep.local) <= {o.name for o in objs}


def test_model_objects_require_labels():
    g = tracing.trace_model("qwen3-0.6b", "decode", use_store=False)
    stripped = type(g).from_arrays(g.cost, g.is_mem, g.nbytes,
                                   g.src, g.dst)
    with pytest.raises(ValueError, match="labels"):
        tracing.model_objects(stripped)


@pytest.mark.parametrize("kind", tracing.COMPONENTS)
def test_component_traces_are_parallel_not_chains(kind):
    g = tracing.trace_component(kind)
    r = report(g)
    assert g.n_vertices > 1
    assert r.D <= r.W
    if kind in ("attention", "ssm"):
        # chunked scans leave real width: many accesses per mem layer
        assert r.W > 2 * r.D


def test_component_unknown_kind_raises():
    with pytest.raises(ValueError, match="mlp"):
        tracing.trace_component("conv")


def test_hlo_summary_roofline_terms():
    h = tracing.model_hlo_summary("qwen3-0.6b", "prefill")
    assert h["flops"] > 0 and h["hbm_bytes"] > 0
    assert h["n_computations"] >= 1
