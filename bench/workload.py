"""Cells, deployments and query mixes, found by the names in ``BENCHMARK.json``.

A cell names a deployment (``bench/configs/<config>.json``) and a query mix
(``bench/traffic/<traffic>.json``).  This module reads both and turns them
into what the platform takes: one ``ScenarioConfig`` and the ``QuerySpec``
list of one replay.  A new cell needs new data files and entries, not code.

The deployment is fixed by its file (the world seed included), so every run
of a cell tracks the same city.  ``--seed`` draws the queries: the order in
which they register, which of them look for a stranger, and the strangers'
embeddings.  Every seed gets the same arrivals with the same peak speeds,
so the work of a replay does not move with the seed.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]

    @property
    def engine(self) -> str:
        return self.config["engine"]

    @property
    def embed_dim(self) -> int:
        return int(self.config["scenario"].get("embed_dim", 0))


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its config and mix."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(os.path.join(BENCH_DIR, "configs", w["config"] + ".json")),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")),
    )


def scenario_config(cell: Cell, **override):
    """The deployment's ``ScenarioConfig``.  Every key of the file's
    ``scenario`` group must be a field; ``engine`` is set only where the
    config still has that field."""
    from repro.sim import ScenarioConfig

    fields = {f.name for f in dataclasses.fields(ScenarioConfig)}
    kw = dict(cell.config["scenario"], **override)
    unknown = sorted(set(kw) - fields)
    if unknown:
        raise KeyError(f"{cell.config['name']}: not ScenarioConfig fields: {unknown}")
    for k in ("fc_cost", "va_cost", "cr_cost"):
        if k in kw:
            kw[k] = tuple(kw[k])
    if "engine" in fields:
        kw["engine"] = cell.engine
    return ScenarioConfig(**kw)


def _rng(seed: int, salt: int) -> np.random.Generator:
    # SeedSequence takes any non-negative int, so seeds past 2**32 are fine.
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)), salt]))


def arrival_times(traffic: Dict[str, Any], period_s: float) -> List[float]:
    """Submit times of the mix's query slots, the same for every seed.

    ``poisson_quantiles``: the n - 1 gaps are the quantiles of an
    exponential law of the given mean at (k + 1/2) / (n - 1), put in the
    fixed order drawn from ``order_seed``; the first query arrives at t = 0.
    With ``between_ticks`` every later arrival moves to the middle of its
    frame period, so that no query starts or lapses while a frame of the
    period before is still in VA."""
    n = int(traffic["queries"])
    arr = traffic["arrivals"]
    if arr["kind"] == "at_zero":
        return [0.0] * n
    if arr["kind"] == "poisson_quantiles":
        gaps = [-float(arr["mean_gap_s"]) * math.log(1.0 - (k + 0.5) / (n - 1))
                for k in range(n - 1)]
        order = np.random.default_rng(int(arr["order_seed"])).permutation(n - 1)
        times = [0.0] + [float(t) for t in np.cumsum([gaps[i] for i in order])]
        if arr.get("between_ticks"):
            times = [0.0] + [(math.floor(t / period_s) + 0.5) * period_s for t in times[1:]]
        return times
    raise ValueError(f"unknown arrivals kind {arr['kind']!r}")


def query_plans(cell: Cell, seed: int) -> List[Dict[str, Any]]:
    """The queries of one replay, drawn from ``seed``, as plain data.

    Slot ``i`` arrives at ``arrival_times(...)[i]`` with peak speed
    ``speeds[i % len(speeds)]``: that pairing sets the work and is the same
    for every seed.  The seed draws the order in which the slots register
    (their query ids), which ``queries // stranger_every`` slots carry a
    stranger's embedding rather than the tracked entity's, and the
    strangers' embeddings.  Without re-ID no slot carries one."""
    t = cell.traffic
    n = int(t["queries"])
    speeds = [float(s) for s in t["peak_speeds_mps"]]
    every = int(t.get("stranger_every", 0))
    submits = arrival_times(t, 1.0 / float(cell.config["scenario"]["fps"]))
    order = _rng(seed, 1).permutation(n)
    strangers = set()
    if cell.embed_dim > 0 and every > 0:
        strangers = {int(i) for i in _rng(seed, 2).permutation(n)[: n // every]}
    stranger_seeds = _rng(seed, 3).integers(0, 2**62, size=n)
    return [dict(tl=t["tl"], tl_peak_speed=speeds[int(i) % len(speeds)],
                 submit_at=float(submits[i]), ttl_s=t.get("ttl_s"),
                 embedding_seed=int(stranger_seeds[i]) if i in strangers else None)
            for i in order]


def query_specs(plans: List[Dict[str, Any]]):
    """The platform's ``QuerySpec`` list for ``query_plans``."""
    from repro.query import QuerySpec

    return [QuerySpec(**p) for p in plans]
