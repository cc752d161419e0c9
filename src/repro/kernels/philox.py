"""Standalone dropout-RNG Pallas TPU kernel.

Generates the packed keep-bit tensor (B, H, SQ//32, SK) for one attention
layer — the paper's "stand-alone RNG kernel storing bits representing random
numbers in HBM for later use by the Attention kernel" (§3.1). Pure VPU work:
no MXU op appears in the body, which is what lets Mosaic (and the paper's
scheduler) co-execute it with matmul-bound producers.

Seed and salt enter as a (3,) uint32 SMEM operand rather than closed-over
literals, so the kernel also serves the training path where the step/layer
folding makes them traced scalars (the producer-site scheduler calls it as
the paper's Region-3 fallback inside the layer scan).

Grid: (B*H, SQ32 // rows32_blk, SK // bk). Each step emits a
(rows32_blk, bk) block of packed words.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.philox_common import (
    global_bh,
    packed_tile_from_counters,
    seed_salt_smem,
    threshold_from_p,
)

# default emission-block shape, clamped to (sq32, sk) at call time.
# Public: the static verifier (repro.analysis.counters) re-enumerates
# this kernel's grid from these — keep in sync with philox_dropout_mask.
DEFAULT_ROWS32_BLK = 8
DEFAULT_BK = 512


def _philox_kernel(s_ref, o_ref, *, rows32_blk: int, bk: int,
                   threshold, rounds: int, heads_local: int,
                   heads_global: int):
    bh = global_bh(pl.program_id(0), heads_local, heads_global, s_ref[3])
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q32_start = qi * rows32_blk
    k_start = ki * bk
    o_ref[...] = packed_tile_from_counters(
        q32_start, k_start, bh, s_ref[2], s_ref[0], s_ref[1], threshold,
        rows32_blk, bk, rounds)[None]


@functools.partial(
    jax.jit,
    static_argnames=("batch", "n_heads", "sq", "sk", "p", "rounds",
                     "rows32_blk", "bk", "interpret", "heads_global"))
def _philox_dropout_mask(sd, *, batch: int, n_heads: int, sq: int, sk: int,
                         p: float, rounds: int, rows32_blk: int, bk: int,
                         interpret: bool,
                         heads_global: int) -> jnp.ndarray:
    sq32 = sq // 32
    rows32_blk = min(rows32_blk, sq32)
    bk = min(bk, sk)
    assert sq32 % rows32_blk == 0 and sk % bk == 0
    thr = threshold_from_p(p)
    grid = (batch * n_heads, sq32 // rows32_blk, sk // bk)
    out = pl.pallas_call(
        functools.partial(
            _philox_kernel, rows32_blk=rows32_blk, bk=bk,
            threshold=thr, rounds=rounds, heads_local=n_heads,
            heads_global=heads_global),
        name="philox_mask",
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(
            (1, rows32_blk, bk), lambda bh, qi, ki: (bh, qi, ki)),
        out_shape=jax.ShapeDtypeStruct((batch * n_heads, sq32, sk),
                                       jnp.uint32),
        interpret=interpret,
    )(sd)
    return out.reshape(batch, n_heads, sq32, sk)


def philox_dropout_mask(batch: int, n_heads: int, sq: int, sk: int,
                        p: float, seed, salt=0,
                        rounds: int = 7,
                        rows32_blk: int = DEFAULT_ROWS32_BLK,
                        bk: int = DEFAULT_BK,
                        interpret: Optional[bool] = None,
                        heads_global: int = 0,
                        bh_offset=0) -> jnp.ndarray:
    """Packed keep-mask (B, H, SQ//32, SK) uint32 from the canonical
    counter scheme. ``seed``/``salt`` may be python ints or traced uint32
    scalars. Defaults: (8, 512) blocks = 16 KiB VMEM per step —
    deliberately tiny so the kernel can be co-scheduled against a GEMM
    without VMEM pressure (the paper's 6%/7% RF/SMEM carve-out analogue).

    ``heads_global``/``bh_offset`` make the call shard-local: the output
    is the (batch, n_heads) tile of the global (B, H_global) mask plane
    starting at flattened index ``bh_offset`` — bit-identical to slicing
    the whole-mask call (see philox_common.global_bh).
    """
    assert sq % 32 == 0, "sq must be a multiple of 32 (bit packing)"
    return _philox_dropout_mask(
        seed_salt_smem(seed, salt, bh_offset), batch=batch,
        n_heads=n_heads, sq=sq, sk=sk, p=p, rounds=rounds,
        rows32_blk=rows32_blk, bk=bk,
        interpret=resolve_interpret(interpret),
        heads_global=heads_global or n_heads)
