"""The one generator of analysis queries, driven by a traffic file.

A traffic file (``traffic/<name>.json``) holds only parameters:

* ``entry``     -- the library entry each query calls (see ``workload``);
* ``members``   -- the configuration's traces the mix draws on;
* ``pick``      -- ``union``: every query covers all members at once;
                   ``cycle``: each query takes one member, and every run
                   of ``len(members)`` queries visits each member once,
                   in an order drawn from the seed;
* ``alphas``    -- ``count`` distinct integer latencies drawn per query,
                   uniformly from [``low``, ``high``];
* ``ms``, ``compute_slots`` -- the machine grid of every query;
* ``warmup_alphas`` -- the fixed latencies of the set-up queries, so that
                   every seed records and compiles the same work;
* ``trace_queries`` -- how many queries a ``--trace 1`` run profiles;
* ``check``     -- how many answers the run compares with the reference.

The same seed gives the same queries; every seed gives the same members
in the same proportions, in another order.
"""
from __future__ import annotations

import zlib
from typing import Iterator

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of one seed.  Seeds may be any
    integer, negative or past 64 bits; streams never share draws."""
    words = [int(seed) >> s & 0xFFFFFFFF for s in range(0, 128, 32)]
    return np.random.default_rng(words + [zlib.crc32(stream.encode()),
                                          int(seed < 0)])


def draw_alphas(spec: dict, g: np.random.Generator) -> np.ndarray:
    """``count`` distinct integer latencies in [low, high], sorted."""
    lo, hi, k = int(spec["low"]), int(spec["high"]), int(spec["count"])
    if not 0 < k <= hi - lo + 1:
        raise ValueError(f"cannot draw {k} distinct alphas from "
                         f"[{lo}, {hi}]")
    return np.sort(g.choice(np.arange(lo, hi + 1), size=k,
                            replace=False)).astype(np.float64)


def _query(traffic: dict, member, alphas) -> dict:
    return {"entry": traffic["entry"], "member": member,
            "alphas": np.asarray(alphas, dtype=np.float64),
            "ms": tuple(int(m) for m in traffic["ms"]),
            "compute_slots": tuple(int(c) for c in traffic["compute_slots"]),
            "simulate_points": bool(traffic.get("simulate_points", False))}


def _members(traffic: dict) -> list:
    return [None] if traffic["pick"] == "union" else list(traffic["members"])


def warmup(traffic: dict) -> list:
    """One query per distinct plan: the whole union, or each member once,
    at the fixed warm-up latencies."""
    return [_query(traffic, m, traffic["warmup_alphas"])
            for m in _members(traffic)]


def queries(traffic: dict, seed: int) -> Iterator[dict]:
    """The endless query stream of one seed."""
    pick = traffic["pick"]
    if pick not in ("union", "cycle"):
        raise ValueError(f"unknown pick {pick!r}")
    g = rng(seed, "queries")
    members = _members(traffic)
    while True:
        order = (members if pick == "union"
                 else [members[i] for i in g.permutation(len(members))])
        for m in order:
            yield _query(traffic, m, draw_alphas(traffic["alphas"], g))
