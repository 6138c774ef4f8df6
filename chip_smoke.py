"""Smoke run of the tracking platform's main path on one TPU chip.

    python chip_smoke.py [--seed 0] [--chips 4]

One process drives the chip through the entry points a user calls, at the
paper's deployment (1000 cameras, 300 s, 16 concurrent queries), with
worlds built from ``--seed`` and the on-disk world cache off:

  A  re-ID analytics: ``MultiQueryScenario`` with ``embed_dim=128`` (the
     App 4 width); every VA batch goes through
     ``dispatch.reid_match_multi`` on the chip.  Per-query books must equal
     a run whose matcher is the host reference (``kernels/reid_ref``
     on the CPU backend).
  B  spotlight kernel: ``TLProbabilistic.spotlight_multi(use_kernel=True)``
     (the Pallas min-plus relaxation) on the 1000-camera road graph must
     equal the incremental Dijkstra, and its distances must equal the jnp
     reference on the CPU backend bit for bit.
  C  fused engine: ``ScenarioConfig(engine="megastep")`` at the sharded
     family's full shape (1000 cameras / 300 s) and the megastep family's
     (10 000 cameras / 600 s), 16 queries each, against the interpreted
     pipeline.  Either the device scan ran and the results are equal
     (outcome a), or the chip's f64 is not IEEE binary64 and the engine
     refused the device with the recorded reason ``x64-emulated`` and ran
     the host mirror (outcome b); the phase then forces the scan onto the
     emulated f64 once and prints the first field that differed.

``--chips 4`` runs only the camera-sharded engine: the 1000-camera run on a
4-chip ``camera_mesh`` against the same run on one chip.

Each phase prints one line with its XLA compile seconds and the rest of
its wall (every device result is pulled to the host, so the wall ends
after the device finished).  The script exits non-zero, and prints no
result line, unless JAX's first device is a TPU and every phase passed.
The last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.tracking import TLProbabilistic  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.kernels.megastep import ops as megastep_ops  # noqa: E402
from repro.kernels.reid_ref import reid_match_ref  # noqa: E402
from repro.kernels.spotlight_ball.ref import (  # noqa: E402
    dense_adjacency,
    spotlight_ball_ref,
)
from repro.query import MultiQueryScenario, QuerySpec  # noqa: E402
from repro.sim import ScenarioConfig, WorldKey, get_world  # noqa: E402

QUERIES = 16
EMBED_DIM = 128
PAPER_SHAPE = (1000, 300.0)
# benchmarks/run.py: _sharded_shape and _megastep_shape, full mode.
ENGINE_SHAPES = (PAPER_SHAPE, (10_000, 600.0))
MESH_CHIPS = 4


class PhaseFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# --------------------------------------------------------------------- #
# Timing: XLA compile seconds, from JAX's own compile events             #
# --------------------------------------------------------------------- #
_COMPILE_S = [0.0]


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += secs


@contextlib.contextmanager
def timed(out: dict, prefix: str):
    """Wall of the block split into ``<prefix>compile_s`` (XLA backend
    compiles inside it) and ``<prefix>run_s`` (the rest)."""
    c0 = _COMPILE_S[0]
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    compile_s = _COMPILE_S[0] - c0
    out[prefix + "compile_s"] = compile_s
    out[prefix + "run_s"] = wall - compile_s


@contextlib.contextmanager
def phase(name: str):
    """Collect a phase's fields and print them as one line, also when the
    phase fails."""
    fields: dict = {}
    try:
        yield fields
    except Exception as e:
        fields["failed"] = repr(e)
        raise
    finally:
        body = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"phase {name}: {body}", flush=True)


# --------------------------------------------------------------------- #
# Shared helpers                                                          #
# --------------------------------------------------------------------- #
def paper_config(seed: int, cams: int, dur: float, **kw) -> ScenarioConfig:
    return ScenarioConfig(num_cameras=cams, duration_s=dur, seed=seed,
                          tl="bfs", batching="dynamic", m_max=25, **kw)


def tracking_specs():
    """16 weighted-ball queries tracking the entity at mixed peak speeds
    (the engine benchmarks' steady-tracking workload)."""
    return [QuerySpec(tl="wbfs", tl_peak_speed=3.0 + (i % 3))
            for i in range(QUERIES)]


def observable(res) -> dict:
    """Everything observable about a MultiQueryResult, exactly (the
    engine gate's field set, ``tests/test_megastep.py``)."""
    out = {
        "global": res.result.summary(),
        "g_lat": res.result.latencies,
        "g_active": res.result.active_timeline,
        "g_batch": res.result.batch_sizes,
        "g_drops": res.result.drops_by_task,
        "states": res.states,
        "per": {},
    }
    for qid, r in res.per_query.items():
        st = res.registry.get(qid)
        out["per"][qid] = {
            "summary": res.per_query_summary(qid),
            "lat": r.latencies,
            "active": r.active_timeline,
            "sourced": st.sourced,
            "reid_matched": st.reid_matched,
            "requested": sorted(st.requested),
            "applied": sorted(st.applied),
        }
    return out


def first_diff(a, b, path: str = ""):
    """Path and values of the first field where ``a`` and ``b`` differ
    (None when equal)."""
    if isinstance(a, dict) and isinstance(b, dict):
        for k in sorted(set(a) | set(b), key=str):
            if k not in a or k not in b:
                return f"{path}/{k}", a.get(k, "<missing>"), b.get(k, "<missing>")
            d = first_diff(a[k], b[k], f"{path}/{k}")
            if d is not None:
                return d
        return None
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        for i, (x, y) in enumerate(zip(a, b)):
            d = first_diff(x, y, f"{path}[{i}]")
            if d is not None:
                return d
        if len(a) != len(b):
            return f"{path}.len", len(a), len(b)
        return None
    return None if a == b else (path, a, b)


def run_scenario(cfg, engine: str, **kw):
    c = copy.deepcopy(cfg)
    c.engine = engine
    scn = MultiQueryScenario(c, tracking_specs(), **kw)
    return scn, scn.run()


@contextlib.contextmanager
def device_scan_forced():
    """Take the chip's f64 as exact for one run: the device scan then runs
    on it, so its result can be set against the reference."""
    backend = jax.default_backend()
    was = megastep_ops.x64_exact()
    megastep_ops._X64_EXACT[backend] = True
    try:
        yield
    finally:
        megastep_ops._X64_EXACT[backend] = was


# --------------------------------------------------------------------- #
# Phase A: re-ID analytics through the dispatch plane                    #
# --------------------------------------------------------------------- #
def host_reference_matcher(gallery, queries, *, mask=None, threshold=0.5):
    """``dispatch.reid_match_multi``'s contract computed by the re-ID
    reference, one query column at a time, on the CPU backend."""
    gallery = np.asarray(gallery, dtype=np.float32)
    queries = np.asarray(queries, dtype=np.float32)
    mask = np.asarray(mask, dtype=bool)
    with jax.default_device(jax.devices("cpu")[0]):
        sim = np.stack([
            np.asarray(reid_match_ref(gallery, queries[q:q + 1],
                                      threshold=threshold)[0])
            for q in range(queries.shape[0])
        ], axis=1)
    sim = np.where(mask, sim, -np.inf)
    return sim, mask & (sim >= threshold)


def phase_a(seed: int) -> None:
    cams, dur = PAPER_SHAPE
    cfg = paper_config(seed, cams, dur, embed_dim=EMBED_DIM)

    def specs():
        # Even queries carry the entity's embedding, odd ones a stranger's.
        return [QuerySpec(tl="wbfs", tl_peak_speed=3.0 + (i % 3),
                          embedding_seed=None if i % 2 == 0 else seed + 100 + i)
                for i in range(QUERIES)]

    real = dispatch.reid_match_multi
    platforms = set()

    def on_chip(gallery, queries, **kw):
        answer = real(gallery, queries, **kw)
        # The answer comes back on the host; the matcher ran on the device
        # that holds the query block it was handed.
        _, block = dispatch._DEVICE_CACHE[id(queries)]
        platforms.update(d.platform for d in block.devices())
        return answer

    with phase("A reid_match_multi") as fields:
        fields.update(cameras=cams, duration_s=dur, queries=QUERIES,
                      embed_dim=EMBED_DIM)
        dispatch.reset_stats()
        dispatch.reid_match_multi = on_chip
        try:
            with timed(fields, ""):
                res = MultiQueryScenario(cfg, specs()).run()
            calls = dispatch.stats()["reid_multi_calls"]
            dispatch.reid_match_multi = host_reference_matcher
            with timed(fields, "ref_"):
                ref = MultiQueryScenario(cfg, specs()).run()
        finally:
            dispatch.reid_match_multi = real
        got, want = observable(res), observable(ref)
        matched = sum(q["reid_matched"] for q in got["per"].values())
        sourced = sum(q["sourced"] for q in got["per"].values())
        diff = first_diff(got, want)
        fields.update(reid_calls=calls, platforms=",".join(sorted(platforms)),
                      events=got["global"]["source_events"],
                      reid_matched=matched, equal=diff is None)
        check(calls > 0 and platforms == {"tpu"},
              "re-ID batches did not run on the chip")
        check(0 < matched < sourced, "re-ID matched nothing or everything")
        check(diff is None, f"chip vs host-reference re-ID differ at {diff}")


# --------------------------------------------------------------------- #
# Phase B: the spotlight-ball Pallas kernel                              #
# --------------------------------------------------------------------- #
def phase_b(seed: int) -> None:
    cams, dur = PAPER_SHAPE
    with phase("B spotlight_ball") as fields:
        world = get_world(WorldKey.from_config(paper_config(seed, cams, dur)))
        road, cam_vertices = world.road, world.cameras.camera_vertices
        tl = TLProbabilistic(road, cam_vertices, entity_speed=4.0, coverage=0.9)
        rng = np.random.default_rng(seed)
        seen_cams = rng.choice(cams, size=QUERIES, replace=False)
        seen_t = rng.uniform(0.0, 30.0, size=QUERIES)
        for e in range(QUERIES):
            tl.track(e, int(seen_cams[e]), float(seen_t[e]))
        fields.update(vertices=road.num_vertices, queries=QUERIES)
        check(dispatch._use_pallas() and not dispatch.pallas_interpret(),
              "the Pallas kernels would not compile for the chip")

        nows = (40.0, 80.0, 160.0)
        with timed(fields, ""):
            kernel_sets = [tl.spotlight_multi(now, use_kernel=True)
                           for now in nows]
        dijkstra_sets = [tl.spotlight_multi(now) for now in nows]

        indptr, indices, weights = road.csr()
        src = np.array([cam_vertices[int(c)] for c in seen_cams], dtype=np.int32)
        rad = (4.0 * (nows[-1] - seen_t)).astype(np.float32)
        dist = dispatch.spotlight_ball(indptr, indices, weights, src, rad)
        platform = next(iter(dist.devices())).platform
        dist = np.asarray(jax.block_until_ready(dist))
        with jax.default_device(jax.devices("cpu")[0]):
            W = jnp.asarray(dense_adjacency(indptr, indices,
                                            weights.astype(np.float32)))
            want = np.asarray(spotlight_ball_ref(W, jnp.asarray(src),
                                                 jnp.asarray(rad)))
        fields.update(platform=platform,
                      cameras_lit=",".join(str(len(s)) for s in kernel_sets),
                      sets_equal=kernel_sets == dijkstra_sets,
                      dist_equal=bool(np.array_equal(dist, want)))
        check(platform == "tpu", "the spotlight kernel did not run on the chip")
        check(all(kernel_sets), "a spotlight came back empty")
        check(fields["sets_equal"], "kernel spotlight != Dijkstra spotlight")
        check(fields["dist_equal"], "chip distances != CPU reference distances")


# --------------------------------------------------------------------- #
# Phase C: the fused mega-step engine                                    #
# --------------------------------------------------------------------- #
def engine_outcome(scn, got, want, x64_exact: bool, fields: dict) -> str:
    """Decide outcome (a) or (b) for one engine run against the
    interpreted reference; raise on anything else."""
    diff = first_diff(got, want)
    fields.update(engine=scn.engine_used,
                  reason=repr(scn.engine_fallback_reason), equal=diff is None)
    check(diff is None, f"engine result differs from interpreted at {diff}")
    if x64_exact:
        check(scn.engine_used == "megastep-device",
              f"device scan not used: {scn.engine_fallback_reason!r}")
        return "a"
    check(scn.engine_used == "megastep-host"
          and scn.engine_fallback_reason == "x64-emulated",
          "emulated f64 not refused with its reason")
    return "b"


def forced_scan(cfg, want, fields: dict, **kw) -> dict:
    """Run the device scan on the chip's emulated f64 and record where it
    first departs from the reference; returns its observable result."""
    with device_scan_forced():
        with timed(fields, "forced_"):
            scn, res = run_scenario(cfg, "megastep", **kw)
    got = observable(res)
    diff = first_diff(got, want)
    fields.update(forced_engine=scn.engine_used,
                  forced_reason=repr(scn.engine_fallback_reason),
                  forced_shards=scn.shards_used,
                  forced_equal=diff is None,
                  first_diff=repr(diff[0]) if diff else "none",
                  first_diff_values=repr(diff[1:]) if diff else "none")
    return got


def phase_c(seed: int) -> str:
    x64_exact = megastep_ops.x64_exact()
    outcomes = []
    for cams, dur in ENGINE_SHAPES:
        with phase(f"C megastep {cams}x{int(dur)}s") as fields:
            cfg = paper_config(seed, cams, dur)
            fields.update(cameras=cams, duration_s=dur, queries=QUERIES,
                          x64_exact=x64_exact)
            get_world(WorldKey.from_config(cfg))
            with timed(fields, "ref_"):
                _, ref = run_scenario(cfg, "interpreted")
            want = observable(ref)
            with timed(fields, ""):
                scn, res = run_scenario(cfg, "megastep")
            outcome = engine_outcome(scn, observable(res), want, x64_exact,
                                     fields)
            if outcome == "b":
                forced_scan(cfg, want, fields)
            fields["outcome"] = outcome
            outcomes.append(outcome)
    check(len(set(outcomes)) == 1, f"shapes disagree on the outcome: {outcomes}")
    return outcomes[0]


# --------------------------------------------------------------------- #
# --chips 4: the camera-sharded engine                                   #
# --------------------------------------------------------------------- #
def phase_mesh(seed: int) -> None:
    from repro.distributed import camera_mesh

    cams, dur = PAPER_SHAPE
    with phase(f"mesh megastep {cams}x{int(dur)}s") as fields:
        check(len(jax.devices()) >= MESH_CHIPS, f"needs {MESH_CHIPS} chips")
        x64_exact = megastep_ops.x64_exact()
        cfg = paper_config(seed, cams, dur)
        mesh = camera_mesh(jax.devices()[:MESH_CHIPS])
        fields.update(cameras=cams, duration_s=dur, queries=QUERIES,
                      chips=MESH_CHIPS, x64_exact=x64_exact)
        with timed(fields, "ref_"):
            _, ref = run_scenario(cfg, "interpreted")
        want = observable(ref)
        with timed(fields, "one_chip_"):
            one_scn, one = run_scenario(cfg, "megastep")
        with timed(fields, ""):
            scn, res = run_scenario(cfg, "megastep", mesh=mesh)
        got = observable(res)
        fields.update(shards=scn.shards_used,
                      shard_reason=repr(scn.shard_fallback_reason),
                      one_chip_engine=one_scn.engine_used,
                      equal_one_chip=first_diff(got, observable(one)) is None)
        outcome = engine_outcome(scn, got, want, x64_exact, fields)
        check(fields["equal_one_chip"], "4-chip run differs from the 1-chip run")
        if outcome == "a":
            check(scn.shards_used == MESH_CHIPS
                  and scn.shard_fallback_reason == "",
                  f"sharded scan not used: {scn.shard_fallback_reason!r}")
        else:
            check(scn.shard_fallback_reason == "x64-emulated",
                  "sharded path did not record the x64 refusal")
            four = forced_scan(cfg, want, fields, mesh=mesh)
            with device_scan_forced():
                _, forced_one = run_scenario(cfg, "megastep")
            fields["forced_equal_one_chip"] = first_diff(
                four, observable(forced_one)) is None
        fields["outcome"] = outcome


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS), default=1,
                    help=f"{MESH_CHIPS}: run only the camera-sharded engine")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    os.environ["REPRO_WORLD_CACHE"] = "0"  # worlds are built from --seed
    cache = dispatch.enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    try:
        if args.chips == MESH_CHIPS:
            phase_mesh(args.seed)
        else:
            phase_a(args.seed)
            phase_b(args.seed)
            print(f"outcome: {phase_c(args.seed)}", flush=True)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
