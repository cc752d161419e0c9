"""Public entry points for the Pallas kernels.

The kernels resolve their execution mode through
``backend.default_interpret``: Mosaic on a TPU, the Pallas interpreter on
any other backend, so the same call sites validate on CPU and compile for
the chip.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels.backend import default_interpret
from repro.kernels.flash_attention import flash_attention, flash_attention_fwd
from repro.kernels.gemm_rng import (
    gemm_with_rng,
    gemm_with_rng_fp8,
    gemm_with_rng_grouped,
    gemm_with_rng_grouped_fp8,
)
from repro.kernels.philox import philox_dropout_mask

__all__ = [
    "default_interpret",
    "dropout_mask",
    "flash_attention",
    "flash_attention_fwd",
    "fused_gemm_rng_fp8",
    "fused_gemm_rng_grouped",
    "fused_gemm_rng_grouped_fp8",
    "fused_qkv_gemm_rng",
    "gemm_with_rng",
    "gemm_with_rng_fp8",
    "gemm_with_rng_grouped",
    "gemm_with_rng_grouped_fp8",
]


def dropout_mask(batch: int, n_heads: int, sq: int, sk: int, p: float,
                 seed, salt=0, rounds: int = 7, heads_global: int = 0,
                 bh_offset=0) -> jnp.ndarray:
    """Standalone-RNG kernel: packed keep-bits (B, H, SQ//32, SK).
    ``seed``/``salt`` may be python ints or traced uint32 scalars.
    ``heads_global``/``bh_offset`` select a shard-local (b, h) tile of
    the global mask plane (bit-identical to slicing the full mask)."""
    return philox_dropout_mask(batch, n_heads, sq, sk, p, seed, salt,
                               rounds, heads_global=heads_global,
                               bh_offset=bh_offset)


def fused_qkv_gemm_rng(x: jnp.ndarray, w_qkv: jnp.ndarray, *,
                       mask_batch: int, mask_heads: int, mask_sq: int,
                       mask_sk: int, p: float, seed, salt=0,
                       rounds: int = 7, block_m: int = 256,
                       block_n: int = 256, block_k: int = 512,
                       mask_block_cols: int = 2048,
                       heads_global: int = 0, bh_offset=0,
                       ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """QKV projection with the dropout mask for the *following* attention
    layer generated under the GEMM (the paper's Fig. 4 overlap topology).
    Falls back to (plain GEMM, None) when the GEMM cannot host the RNG —
    the caller should then invoke ``dropout_mask`` (exposed RNG, paper
    Region 3). ``seed``/``salt`` may be traced uint32 scalars — the
    training path folds (step, layer) in under the jit."""
    return gemm_with_rng(
        x, w_qkv, mask_batch=mask_batch, mask_heads=mask_heads,
        mask_sq=mask_sq, mask_sk=mask_sk, p=p, seed=seed, salt=salt,
        rounds=rounds, block_m=block_m, block_n=block_n, block_k=block_k,
        mask_block_cols=mask_block_cols,
        heads_global=heads_global, bh_offset=bh_offset)


def fused_gemm_rng_grouped(a: jnp.ndarray, b: jnp.ndarray, *,
                           mask_batch: int, mask_heads: int, mask_sq: int,
                           mask_sk: int, p: float, seed, salt=0,
                           rounds: int = 7, block_m: int = 256,
                           block_n: int = 256, block_k: int = 512,
                           mask_block_cols: int = 2048,
                           heads_global: int = 0, bh_offset=0,
                           ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Grouped expert GEMM C[e] = a[e] @ b[e] with the dropout mask
    generated under the combined (E, i, j) grid — the MoE-expert /
    RWKV-channel-mix host. The RNG emission grid is decoupled from the
    GEMM grid: bits index the (b, h, q, k) counter space, never token
    identity, so expert permutation and capacity drops cannot reach the
    mask. Falls back to (plain grouped GEMM, None) in Region 3."""
    return gemm_with_rng_grouped(
        a, b, mask_batch=mask_batch, mask_heads=mask_heads,
        mask_sq=mask_sq, mask_sk=mask_sk, p=p, seed=seed, salt=salt,
        rounds=rounds, block_m=block_m, block_n=block_n, block_k=block_k,
        mask_block_cols=mask_block_cols,
        heads_global=heads_global, bh_offset=bh_offset)


def fused_gemm_rng_grouped_fp8(a: jnp.ndarray, b: jnp.ndarray, *,
                               mask_batch: int, mask_heads: int,
                               mask_sq: int, mask_sk: int, p: float,
                               seed, salt=0, rounds: int = 7,
                               block_m: int = 256, block_n: int = 256,
                               block_k: int = 512,
                               mask_block_cols: int = 2048,
                               heads_global: int = 0, bh_offset=0,
                               ) -> Tuple[jnp.ndarray,
                                          Optional[jnp.ndarray]]:
    """Grouped expert GEMM on per-tile-scaled e4m3 operands with the
    dropout mask generated under it — mask bits identical to the f32
    grouped host."""
    return gemm_with_rng_grouped_fp8(
        a, b, mask_batch=mask_batch, mask_heads=mask_heads,
        mask_sq=mask_sq, mask_sk=mask_sk, p=p, seed=seed, salt=salt,
        rounds=rounds, block_m=block_m, block_n=block_n, block_k=block_k,
        mask_block_cols=mask_block_cols,
        heads_global=heads_global, bh_offset=bh_offset)


def fused_gemm_rng_fp8(x: jnp.ndarray, w: jnp.ndarray, *,
                       mask_batch: int, mask_heads: int, mask_sq: int,
                       mask_sk: int, p: float, seed, salt=0,
                       rounds: int = 7, block_m: int = 256,
                       block_n: int = 256, block_k: int = 512,
                       mask_block_cols: int = 2048,
                       heads_global: int = 0, bh_offset=0,
                       ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Producer GEMM on per-tile-scaled e4m3 operands with the dropout
    mask generated under it — the paper's measured FP8 serving regime.
    The mask is bit-identical to the f32 host's; the GEMM matches f32
    within the documented e4m3 error bound (kernels/quant.py). Falls back
    to (plain fp8 GEMM, None) in Region 3. Differentiable (straight-
    through quantization, bf16 dgrad)."""
    return gemm_with_rng_fp8(
        x, w, mask_batch=mask_batch, mask_heads=mask_heads,
        mask_sq=mask_sq, mask_sk=mask_sk, p=p, seed=seed, salt=salt,
        rounds=rounds, block_m=block_m, block_n=block_n, block_k=block_k,
        mask_block_cols=mask_block_cols,
        heads_global=heads_global, bh_offset=bh_offset)
