"""Device time of the three flash-attention kernels at each tile, on a TPU.

    python3 scripts/flash_tile_sweep.py [--src src] [--tiles 128,256,512]
        [--shapes musicgen,yi] [--calls 8] [--out sweep.json]

For each attention shape of the benchmark's cells (causal, replay-mode
dropout at p=0.1, f32 operands, as the train step runs them) and each
(block_q, block_k) that divides the sequence, runs the forward kernel
and both backward kernels ``--calls`` times under the profiler, and
reduces the trace with ``bench/trace.py``: milliseconds a call of
``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``. ``--src`` points
at the ``src`` directory whose kernels are timed, so an older checkout
can be measured by the same script. TPU only: it refuses to run on
another backend. Prints one JSON line a tile and writes them all to
``--out``.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# name: (batch, q heads, kv heads, seq, head_dim), as the cells run them
SHAPES = {"musicgen": (4, 32, 32, 1536, 64), "yi": (1, 8, 1, 4096, 128)}
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--src", default=os.path.join(ROOT, "src"))
    ap.add_argument("--tiles", default="128,256,512,768,1024")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.abspath(args.src), ROOT]

    import jax
    import jax.numpy as jnp
    from bench import trace
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.kernels.flash_attention_bwd import flash_attention_bwd
    from repro.kernels.philox_common import seed_salt_smem

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    tiles = [int(t) for t in args.tiles.split(",")]
    rows = []
    for name in args.shapes.split(","):
        b, h, kv, s, d = SHAPES[name]
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, kv, s, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, kv, s, d), jnp.float32)
        g = jax.random.normal(ks[3], (b, h, s, d), jnp.float32)
        operand = seed_salt_smem(3, 5)
        for bq, bk in itertools.product(tiles, tiles):
            if s % bq or s % bk:
                continue
            kw = dict(causal=True, dropout_p=0.1, mode="replay",
                      block_q=bq, block_k=bk)
            fwd = jax.jit(lambda q, k, v, m: flash_attention_fwd(
                q, k, v, m, return_lse=True, **kw))
            bwd = jax.jit(lambda q, k, v, o, lse, g, m: flash_attention_bwd(
                q, k, v, o, lse, g, m, **kw))
            o, lse = jax.block_until_ready(fwd(q, k, v, operand))
            jax.block_until_ready(bwd(q, k, v, o, lse, g, operand))
            with tempfile.TemporaryDirectory() as tdir:
                jax.profiler.start_trace(tdir)
                with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                    for _ in range(args.calls):
                        o, lse = fwd(q, k, v, operand)
                        out = bwd(q, k, v, o, lse, g, operand)
                    jax.block_until_ready(out)
                jax.profiler.stop_trace()
                summary = trace.load(tdir)
            row = {"shape": name, "seq": s, "head_dim": d, "bq": bq,
                   "bk": bk, "device": dev.device_kind}
            row.update({kern: 1e3 * summary.kernel_s(kern) / args.calls
                        for kern in KERNELS})
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
