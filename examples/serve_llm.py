"""Serve a small LM with Anveshak-scheduled batched requests.

    PYTHONPATH=src python examples/serve_llm.py [--arch qwen2-1.5b] [--requests 24]

The decode engine (prefill + KV-cache decode, greedy) runs as a
:class:`ServedStage`-style loop: prompt requests arrive, the dynamic
deadline batcher forms padded buckets, the completion budget drops requests
that cannot meet gamma, and every surviving prompt is decoded to completion.
This is the paper's VA/CR pattern with a language model as the analytic.
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_config
from repro.models import init_params, reduced_config
from repro.serving import Generator, bucket_for
from repro.core.batching import DynamicBatcher, PendingEvent
from repro.core.events import Event, EventHeader, new_event_id


def main() -> None:
    from repro.kernels.dispatch import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--gamma", type=float, default=30.0)
    args = ap.parse_args()

    cfg = reduced_config(get_config(args.arch))
    print(f"Serving {cfg.name} ({cfg.arch_type}); gamma={args.gamma}s")
    params = init_params(jax.random.PRNGKey(0), cfg)
    gen = Generator(cfg, params)

    # Warm the jit caches on the buckets we expect.
    for b in (1, 4, 8):
        gen.generate(jnp.zeros((b, args.prompt_len), jnp.int32), max_new_tokens=2)

    # xi(b): measure a full generate on each bucket.
    def measure(b: int) -> float:
        prompts = jnp.zeros((b, args.prompt_len), jnp.int32)
        t0 = time.perf_counter()
        jax.block_until_ready(gen.generate(prompts, max_new_tokens=args.new_tokens))
        return time.perf_counter() - t0

    xi_pts = {b: measure(b) for b in (1, 4, 8)}
    xi = lambda m: float(np.interp(m, list(xi_pts), list(xi_pts.values())))
    print("xi(b):", {b: f"{t*1e3:.0f}ms" for b, t in xi_pts.items()})

    batcher = DynamicBatcher(xi, m_max=8)
    rng = np.random.default_rng(0)
    served = total_latency = 0
    t_start = time.perf_counter()

    def run_batch(batch):
        nonlocal served, total_latency
        m = len(batch)
        bucket = bucket_for(m, (1, 2, 4, 8))
        prompts = np.zeros((bucket, args.prompt_len), np.int32)
        for i, pe in enumerate(batch):
            prompts[i] = pe.event.value
        out = gen.generate(jnp.asarray(prompts), max_new_tokens=args.new_tokens)
        now = time.perf_counter()
        for i, pe in enumerate(batch):
            served += 1
            total_latency += now - pe.event.header.source_arrival
        return out

    for i in range(args.requests):
        # Poisson-ish arrivals at ~4 req/s.
        time.sleep(float(rng.exponential(0.25)))
        now = time.perf_counter()
        prompt = rng.integers(0, cfg.vocab_size, size=args.prompt_len).astype(np.int32)
        ev = Event(header=EventHeader(event_id=new_event_id(), source_arrival=now),
                   key=i, value=prompt)
        batch = batcher.offer(
            PendingEvent(event=ev, arrival=now, deadline=now + args.gamma), now
        )
        if batch:
            run_batch(batch)
        flushed = batcher.flush_if_due(time.perf_counter())
        if flushed:
            run_batch(flushed)
    leftover = batcher.take()
    if leftover:
        run_batch(leftover)

    wall = time.perf_counter() - t_start
    print(
        f"\nServed {served}/{args.requests} prompts in {wall:.1f}s "
        f"(mean latency {total_latency/max(served,1):.2f}s, "
        f"{served*args.new_tokens/wall:.1f} tok/s)"
    )


if __name__ == "__main__":
    main()
