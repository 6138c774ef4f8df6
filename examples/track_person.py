"""End-to-end driver (the paper's kind: SERVING): track a person across a
1000-camera network with REAL JAX models in the loop.

    PYTHONPATH=src python examples/track_person.py [--cameras 500] [--duration 240]

* VA/CR are actual jit-compiled JAX models (re-id embedding tower + the
  dispatch plane's ``reid_match_multi`` matcher) executed through
  :class:`ServedStage` — Anveshak's budgeted dynamic batching + drop points
  wrap every device call.
* The stage cost models ``xi(b)`` are *calibrated from the compiled step*
  (replacing the paper's offline benchmarking) and then drive the
  discrete-event scenario at full scale.
* Frames carry feature embeddings; positives are frames whose embedding
  matches the entity query through the actual matcher.
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import ServedStage, StageRequest, calibrate_xi, embed_frames, init_reid_tower
from repro.kernels import dispatch
from repro.sim import ScenarioConfig, TrackingScenario


def main() -> None:
    from repro.kernels.dispatch import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--cameras", type=int, default=500)
    ap.add_argument("--duration", type=float, default=240.0)
    args = ap.parse_args()

    # ---- 1. Build + calibrate the CR model (JAX) ----------------------- #
    tower = init_reid_tower(jax.random.PRNGKey(0), d_in=128, d_hidden=256, d_embed=64)
    cr_step = jax.jit(lambda x: embed_frames(tower, x))
    print("Calibrating xi(b) from the compiled CR step...")
    xi_cr = calibrate_xi(lambda x: cr_step(jnp.asarray(x)), (128,), buckets=(1, 4, 16, 32))
    for b in (1, 8, 32):
        print(f"  xi({b:2d}) = {xi_cr(b)*1e3:7.3f} ms")

    # ---- 2. Serve a burst of real frames through the Anveshak stage ----- #
    stage = ServedStage(
        "CR", lambda x: cr_step(jnp.asarray(x)), xi_cr, gamma=1.0, m_max=32,
        buckets=(1, 4, 16, 32),
    )
    rng = np.random.default_rng(0)
    entity = rng.normal(size=(1, 128)).astype(np.float32)
    query_emb = np.asarray(cr_step(jnp.asarray(entity)))
    n_requests, matches = 300, 0
    t0 = time.perf_counter()
    for i in range(n_requests):
        is_entity = i % 37 == 0
        frame = (entity[0] + rng.normal(scale=0.05, size=128)).astype(np.float32) \
            if is_entity else rng.normal(size=128).astype(np.float32)
        results = stage.submit(StageRequest(frame, source_time=time.perf_counter()))
        for r in results or []:
            if r.dropped:
                continue
            _, hit = dispatch.reid_match_multi(r.output[None, :], query_emb, threshold=0.7)
            matches += int(np.asarray(hit)[0, 0])
    for r in stage.flush() or []:
        if not r.dropped:
            _, hit = dispatch.reid_match_multi(r.output[None, :], query_emb, threshold=0.7)
            matches += int(np.asarray(hit)[0, 0])
    wall = time.perf_counter() - t0
    print(
        f"Served {n_requests} frames in {wall:.2f}s "
        f"({n_requests/wall:.0f} fps): matches={matches}, "
        f"stats={stage.stats}"
    )

    # ---- 3. Full-scale tracking with calibrated costs ------------------ #
    print(f"\nRunning the {args.cameras}-camera scenario with calibrated CR costs...")
    # xi(b) ~ c0 + c1*b fit from the calibration:
    c1 = max((xi_cr(32) - xi_cr(1)) / 31.0, 1e-5)
    c0 = max(xi_cr(1) - c1, 1e-5)
    cfg = ScenarioConfig(
        num_cameras=args.cameras,
        duration_s=args.duration,
        tl="wbfs",
        tl_peak_speed=4.0,
        batching="dynamic",
        m_max=25,
        cr_cost=(0.067, 0.053),  # paper's App-1 DNN; swap for (c0, c1) to
        # drive the sim with this host's measured model costs instead.
    )
    res = TrackingScenario(cfg).run()
    s = res.summary()
    print("Tracking summary:")
    for k, v in s.items():
        print(f"  {k:22s} {v}")
    print(f"\n(entity detected in {res.detections_on_time} frames within gamma; "
          f"measured-model xi fit: c0={c0*1e3:.2f}ms c1={c1*1e3:.3f}ms/frame)")


if __name__ == "__main__":
    main()
