"""Serving CLI — a thin front over the decode engine in ``repro.serve``
(continuous batching, paged KV, per-request dropout schedules,
optional draft/verify speculative decoding):

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --requests 8 --prompt-len 64 --max-new 32 --spec-k 4

The engine owns the request lifecycle; this module only parses flags,
builds the synthetic request set, and prints the ``ServeReport``.

``PackedMaskCache`` (now ``repro.serve.mask_cache``) is re-exported and
``verify_replay_demo`` kept here for compatibility: both predate the
engine and demonstrate the core serving claim in isolation —
speculative-verify mask fetches are pure replays of identities the
draft pass already generated, so the cache serves them with zero RNG.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.config import get_arch
from repro.core.schedule import DropoutSchedule
# re-export: tests and older callers import the cache from here
from repro.serve.mask_cache import PackedMaskCache  # noqa: F401


def verify_replay_demo(cfg, sched: DropoutSchedule, batch: int,
                       seq: int, steps, replays: int) -> PackedMaskCache:
    """Simulate speculative-decoding verification: the draft pass
    generates each (layer, step) mask once; every verification replay
    re-fetches the same identities and must hit the cache (RNG skipped).
    Returns the cache so the caller can report the hit rate."""
    cache = PackedMaskCache()
    consumers = [a.layer for a in sched.assignments if a.consumes]
    shape = (batch, cfg.n_heads, seq, seq)
    for step in steps:                       # draft pass: masks created
        for layer in consumers:
            cache.get_or_create(sched, layer, step, shape)
    for _ in range(replays):                 # verification: pure replay
        for step in steps:
            for layer in consumers:
                cache.get_or_create(sched, layer, step, shape)
    return cache


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import ServeConfig, ServeEngine

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="0 = sized for max_slots full-length requests")
    ap.add_argument("--max-model-len", type=int, default=0,
                    help="0 = round up prompt+max_new")
    ap.add_argument("--spec-k", type=int, default=0,
                    help=">1 enables draft/verify speculative decoding")
    ap.add_argument("--no-mask", action="store_true",
                    help="disable decode-time dropout rows")
    ap.add_argument("--json", action="store_true",
                    help="print the ServeReport as JSON")
    args = ap.parse_args()

    cfg = get_arch(args.arch, reduced=args.reduced)
    cap = args.prompt_len + args.max_new
    # max_model_len must divide into pages AND packed mask rows
    import math
    quantum = (32 * args.page_size
               // math.gcd(32, args.page_size))
    max_len = args.max_model_len or cap
    max_len = -(-max_len // quantum) * quantum
    num_pages = args.num_pages or (
        args.max_slots * -(-max_len // args.page_size) + args.max_slots)
    serve = ServeConfig(
        max_slots=args.max_slots, page_size=args.page_size,
        num_pages=num_pages, max_model_len=max_len,
        mask_decode=not args.no_mask, spec_k=args.spec_k)
    engine = ServeEngine(cfg, serve=serve, init_seed=args.seed)
    print(f"[serve] arch={cfg.name} slots={serve.max_slots} "
          f"pages={serve.num_pages}x{serve.page_size} "
          f"max_len={serve.max_model_len} spec_k={serve.spec_k} "
          f"masked={engine.masked}")

    rng = np.random.default_rng(args.seed)
    requests = [
        engine.make_request(
            prompt=rng.integers(0, cfg.vocab_size,
                                args.prompt_len).tolist(),
            max_new_tokens=args.max_new)
        for _ in range(args.requests)
    ]
    report = engine.run(requests)
    if args.json:
        print(json.dumps(report.to_dict(), indent=2, default=str))
        return
    d = report.to_dict()
    print(f"[serve] {d['n_requests']} requests, "
          f"{d['total_new_tokens']} new tokens in {d['wall_s']:.2f}s "
          f"({d['tokens_per_s']:,.0f} tok/s)")
    print(f"[serve] first-token p50={d['latency_first_token_s']['p50']*1e3:.0f}ms "
          f"p99={d['latency_first_token_s']['p99']*1e3:.0f}ms; "
          f"completion p50={d['latency_completion_s']['p50']*1e3:.0f}ms")
    mc = d["mask_cache"]
    print(f"[serve] mask cache: {mc['hits']} hits / {mc['misses']} "
          f"Philox execs / {mc['evictions']} evictions")
    print(f"[serve] schedule cache: {d['schedule_cache']}  "
          f"step cache: {d['step_cache']}")
    if d["spec"]["rounds"]:
        sp = d["spec"]
        print(f"[serve] spec: {sp['rounds']} rounds, "
              f"acceptance={sp.get('acceptance_rate', 0.0):.2f}, "
              f"verify Philox execs={sp['verify_philox_execs']} "
              f"(target 0), verify mask fetches="
              f"{sp['verify_mask_fetches']}")


if __name__ == "__main__":
    main()
