"""Spans of the query path (``counters.span``) as a profiler trace shows
them: names, nesting and counts, and no effect without a trace."""
import glob
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.apps.polybench import trace_kernel            # noqa: E402
from repro.core import EDagSuite, grid_report, suite_sweep_grid  # noqa: E402
from repro.core import backend as bk                      # noqa: E402
from repro.core import counters                           # noqa: E402
from repro.core import schedule_cache as sc               # noqa: E402

#: Every span the query path opens, and the span each opens under.
PARENT = {
    "query": None,
    "plan": "query",
    "schedule.load": "plan",
    "schedule.record": "plan",
    "levelize": "plan",
    "fill": "query",
    "replay": "query",
    "replay.prescreen": "replay",
    "replay.cast": "replay",
    "replay.pad": "replay",
    "replay.upload": "replay",
    "replay.run": "replay",
    "replay.download": "replay",
    "replay.certify": "replay",
    "replay.merge": "replay",
    "replay.demote": "replay",
    "verify": "query",
    "reduce": "query",
    "report": "query",
}
#: 2^23 + 1: its makespans pass 2^24, so its column fails the float32
#: certificate and is demoted
ALPHAS = [50.0, 120.0, 2.0 ** 23 + 1]


def _read_spans(log_dir):
    """``edan.*`` host events of the newest profile as dicts with
    ``name`` (prefix dropped), ``s``, ``e``, ``stats`` and ``parent``
    (the innermost ``edan.*`` event enclosing it on its thread)."""
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = sorted(((int(e.start_ns), -int(e.duration_ns), e)
                          for e in line.events
                          if e.name.startswith(counters.SPAN_PREFIX)),
                         key=lambda x: x[:2])
            stack = []
            for s, negd, e in evs:
                end = s - negd
                while stack and stack[-1]["e"] <= s:
                    stack.pop()
                sp = {"name": e.name[len(counters.SPAN_PREFIX):], "s": s,
                      "e": end, "stats": dict(e.stats),
                      "parent": stack[-1]["name"] if stack else None}
                out.append(sp)
                stack.append(sp)
    return out


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One ``grid_report`` (cold: records and stores its schedules), the
    same report on a fresh copy of the trace (warm: loads them from
    disk) and a two-member ``suite_sweep_grid``, all on the jax backend
    under one trace; with the arrays each device pass moved."""
    root = tmp_path_factory.mktemp("spans")
    mp = pytest.MonkeyPatch()
    mp.setenv("EDAN_SCHEDULE_CACHE", str(root / "sched"))
    mp.setenv("EDAN_SCHEDULE_CACHE_MIN", "0")
    passes = []
    real = bk._accumulate_jax

    def spy(lv, F, clamp=True, R_out=None, land=None):
        res = real(lv, F, clamp=clamp, R_out=R_out, land=land)
        plan = bk._jax_padded(lv)
        arrays = [plan.perm, plan.pos] + [a for seg in plan.segments
                                          for a in seg if a is not None]
        moved = F.nbytes + (R_out.nbytes if R_out is not None else 0)
        passes.append({"upload": moved + sum(a.nbytes for a in arrays),
                       "download": moved,
                       "shapes": [seg[0].shape for seg in plan.segments],
                       "vertices": lv.n,
                       "levels": lv.n_levels - 1, "edges": len(lv.esrc)})
        return res
    mp.setattr(bk, "_accumulate_jax", spy)
    log_dir = str(root / "profile")
    try:
        results = []
        with jax.profiler.trace(log_dir):
            for _ in range(2):
                results.append(grid_report(
                    trace_kernel("trisolv", 6), ALPHAS, ms=(2, 4),
                    simulate_points=True, backend="jax"))
            suite = EDagSuite([trace_kernel("atax", 4),
                               trace_kernel("mvt", 4)])
            results.append(suite_sweep_grid(suite, ALPHAS[:2], ms=(2,),
                                            backend="jax"))
    finally:
        mp.undo()
    return _read_spans(log_dir), passes, results


def test_every_documented_span_appears_nested_as_documented(profiled):
    spans, _, _ = profiled
    assert {s["name"] for s in spans} == set(PARENT)
    for s in spans:
        want = PARENT[s["name"]]
        if want is None:
            assert s["parent"] is None
        elif s["name"] == "levelize":
            # a schedule read from disk or recorded now is levelized
            # inside the lookup that builds its plan
            assert s["parent"] in ("plan", "schedule.load",
                                   "schedule.record")
        else:
            assert s["parent"] == want, s
    entries = sorted(s["stats"]["entry"] for s in spans
                     if s["name"] == "query")
    assert entries == ["grid_report", "grid_report", "suite_sweep_grid"]


def test_plan_and_schedule_spans_say_what_was_hit(profiled):
    spans, _, _ = profiled
    loads = [s["stats"]["hit"] for s in spans
             if s["name"] == "schedule.load"]
    # the first report stores its two schedules; the fresh copy of the
    # trace finds both on disk
    assert loads.count(1) >= 2 and 0 in loads
    assert {s["stats"]["hit"] for s in spans if s["name"] == "plan"} <= {0, 1}


def test_transfer_spans_count_the_bytes_moved(profiled):
    spans, passes, _ = profiled
    ups = [s["stats"]["bytes"] for s in spans if s["name"] == "replay.upload"]
    downs = [s["stats"]["bytes"] for s in spans
             if s["name"] == "replay.download"]
    assert ups == [p["upload"] for p in passes]
    assert downs == [p["download"] for p in passes]


def test_run_span_counts_the_padded_level_rectangle(profiled):
    spans, passes, _ = profiled
    runs = [s["stats"] for s in spans if s["name"] == "replay.run"]
    assert len(runs) == len(passes) > 0
    for st, p in zip(runs, passes):
        shapes = p["shapes"]
        assert st["levels"] == sum(Ls for Ls, _, _ in shapes) == p["levels"]
        assert st["rows"] == max(Rs for _, Rs, _ in shapes)
        assert {st["width"]} == {D for _, _, D in shapes}
        assert st["segments"] == len(shapes)
        assert st["slots"] == sum(Ls * Rs * D for Ls, Rs, D in shapes)
        assert st["edges"] == p["edges"] <= st["slots"]
        assert st["permuted"] == p["vertices"]


def test_demote_span_counts_the_demoted_columns(profiled):
    spans, _, _ = profiled
    demoted = [s["stats"]["columns"] for s in spans
               if s["name"] == "replay.demote"]
    assert demoted and all(c == 1 for c in demoted)


def test_replay_span_says_whether_the_chunk_was_whole(profiled):
    spans, _, _ = profiled
    replays = [s for s in spans if s["name"] == "replay"]
    # the reports' 2^23 + 1 column fails the certificate; the suite's
    # clean alphas take the whole-chunk path
    assert {s["stats"]["whole"] for s in replays} == {0, 1}
    for r in replays:
        inside = {s["name"] for s in spans if r["s"] <= s["s"] < r["e"]}
        if r["stats"]["whole"]:
            assert not inside & {"replay.merge", "replay.demote"}
        else:
            assert {"replay.merge", "replay.demote"} <= inside


def test_no_span_is_opened_per_level(profiled):
    spans, passes, _ = profiled
    levels = sum(p["levels"] for p in passes)
    assert len(spans) < levels


class _NoSpan:
    """Stands in for ``TraceAnnotation``: the engine without spans."""

    def __init__(self, name, **counts):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts):
        pass


def _query_and_stats():
    bk.reset_stats()
    sc.reset_stats()
    g = trace_kernel("trisolv", 5)
    rep = grid_report(g, ALPHAS, ms=(2,), simulate_points=True,
                      backend="jax", use_cache=False)
    suite = EDagSuite([trace_kernel("atax", 4), trace_kernel("mvt", 4)])
    grid = suite_sweep_grid(suite, ALPHAS, ms=(2,), backend="jax",
                            use_cache=False)
    return rep, grid, bk.stats.snapshot(), sc.stats.snapshot()


def test_spans_without_a_trace_change_nothing(monkeypatch):
    rep, grid, bstats, sstats = _query_and_stats()
    monkeypatch.setattr(counters, "_annotation", _NoSpan)
    rep0, grid0, bstats0, sstats0 = _query_and_stats()
    assert np.array_equal(grid, grid0)
    assert rep.keys() == rep0.keys()
    for k in rep:
        assert np.array_equal(rep[k], rep0[k]), k
    assert bstats == bstats0 and sstats == sstats0
    assert bstats["jax_chunks"] > 0 and bstats["demoted_columns"] > 0


def test_level_loop_program_is_named_by_the_constant():
    import jax.numpy as jnp
    F = jnp.zeros((5, 2), jnp.float32)
    gat = jnp.full((3, 8, 2), -1, jnp.int32)
    qg = jnp.zeros((3, 8), jnp.int32)
    off = jnp.zeros(3, jnp.int32)
    plan = bk.LevelPlan(jnp.arange(4, dtype=jnp.int32),
                        jnp.arange(4, dtype=jnp.int32),
                        ((gat, qg, off), (gat[:1], qg[:1], off[:1])))
    lowered = jax.jit(bk._level_loop(True, False, True)).lower(F, F, plan)
    name = re.match(r"module @(\S+)", lowered.as_text()).group(1)
    assert name == bk.LEVEL_LOOP_NAME == "jit_run"


def test_replay_counters_add_up_the_run_spans(tmp_path, monkeypatch):
    """``backend.stats`` counts the edges, padded slots, segments and
    levels of every device pass as the ``edan.replay.run`` spans state
    them: every level the device ran was written as one slice."""
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", str(tmp_path / "sched"))
    log_dir = str(tmp_path / "profile")
    before = bk.stats.snapshot()
    with jax.profiler.trace(log_dir):
        grid_report(trace_kernel("lu", 6), ALPHAS[:2], ms=(2, 4),
                    simulate_points=True, backend="jax")
    after = bk.stats.snapshot()
    runs = [s["stats"] for s in _read_spans(log_dir)
            if s["name"] == "replay.run"]
    assert runs
    for counter, stat in (("replay_edges", "edges"), ("replay_slots", "slots"),
                          ("replay_segments", "segments"),
                          ("replay_slice_levels", "levels")):
        assert after[counter] - before[counter] == sum(r[stat] for r in runs)
