"""Attention cores.

``attention_xla`` — q-chunked attention in pure jnp (lowers everywhere,
    memory O(chunk * SK)); used by the distributed train/serve paths. The
    dropout plan threads through it: fused mode generates Philox bits per
    chunk inside the attention body; overlap mode consumes precomputed
    packed bits (paper topology).
``attention_pallas`` — the flash-attention Pallas kernel (TPU target,
    interpret-validated); used by examples/benchmarks and small-scale runs.
``attention_decode`` — single-token decode against a KV cache, sequence-
    sharded (flash-decoding-style under GSPMD).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dropout_rng
from repro.core.overlap import DropoutPlan
from repro.distributed.sharding import constrain

_NEG = -1e30


def _chunk_attend(qc, k, v, q_start, sk, causal, local_window, scale,
                  keep_mask, dropout_p, probs_dtype=jnp.float32):
    """One q-chunk: qc (B,H,cq,D) vs k,v (B,H,SK,D) (kv pre-repeated so
    every tensor here — scores included — shards on the heads axis)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", qc, k,
                        preferred_element_type=jnp.float32) * scale
    scores = constrain(scores, "batch", "heads", None, None)
    cq = qc.shape[2]
    if causal or local_window:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (cq, sk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (cq, sk), 1)
        valid = None
        if causal:
            valid = k_pos <= q_pos
        if local_window:
            local_ok = k_pos > q_pos - local_window
            valid = local_ok if valid is None else jnp.logical_and(
                valid, local_ok)
        scores = jnp.where(valid, scores, _NEG)
    m = jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    # §Perf: post-softmax the probabilities tolerate bf16; halves the
    # dominant HBM traffic of the materialized P chain
    p = (p / denom).astype(probs_dtype)
    if keep_mask is not None:
        p = jnp.where(keep_mask, p, 0.0).astype(probs_dtype) \
            / jnp.asarray(1.0 - dropout_p, probs_dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def attention_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, local_window: int = 0,
                  plan: Optional[DropoutPlan] = None,
                  layer_idx=0, step=0,
                  packed_mask: Optional[jnp.ndarray] = None,
                  chunk_q: int = 1024,
                  scale: Optional[float] = None,
                  probs_dtype=jnp.float32) -> jnp.ndarray:
    """q (B,H,SQ,D); k,v (B,KV,SK,D); H % KV == 0. Returns (B,H,SQ,D).

    When ``plan`` is in overlap mode, ``packed_mask`` carries the
    precomputed keep-bits from the producer-GEMM site; in fused mode the
    bits are generated inside each chunk body (same counters, same bits).
    """
    b, h, sq, d = q.shape
    kv, sk = k.shape[1], k.shape[2]
    g = h // kv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    dropped = plan is not None and plan.enabled
    p_drop = plan.cfg.p if dropped else 0.0

    # head-major: repeat kv to H so scores/probs shard on 'model' (GQA
    # repeat of a replicated kv is a local slice under GSPMD)
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    k = constrain(k, "batch", "heads", None, None)
    v = constrain(v, "batch", "heads", None, None)
    q = constrain(q, "batch", "heads", None, None)
    cq = min(chunk_q, sq)
    pad = (-sq) % cq
    if pad:
        # padded query rows produce garbage rows that are sliced off below
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    sq_p = sq + pad
    n_chunks = sq_p // cq

    def one_chunk(ci):
        q_start = ci * cq
        qc = jax.lax.dynamic_slice_in_dim(q, q_start, cq, axis=2)
        keep = None
        if dropped:
            if packed_mask is not None:
                pm = jax.lax.dynamic_slice_in_dim(
                    packed_mask, ci * (cq // 32), cq // 32, axis=2)
                keep = dropout_rng.unpack_block(pm, cq)
            else:
                keep = plan.chunk_keep_mask(b, h, q_start, cq, sk,
                                            layer_idx, step)
            keep = constrain(keep, "batch", "heads", None, None)
        return _chunk_attend(qc, k, v, q_start, sk, causal, local_window,
                             scale, keep, p_drop, probs_dtype)

    # §Perf: remat each chunk body. Without this, lax.map's linearization
    # saves the (n_chunks, B, H, cq, SK) f32 probability stack as a bwd
    # residual — the single largest HBM stream in training. With it, the
    # bwd recomputes each chunk's probs from the (tiny) q-chunk instead.
    chunk_fn = jax.checkpoint(one_chunk)

    if n_chunks == 1:
        out = chunk_fn(0)
    else:
        outs = jax.lax.map(chunk_fn, jnp.arange(n_chunks))
        out = jnp.moveaxis(outs, 0, 2)  # (B,H,nc,cq,D)
        out = out.reshape(b, h, sq_p, d)
    if pad:
        out = out[:, :, :sq]
    return out


def attention_pallas(q, k, v, *, causal=True, local_window=0,
                     plan: Optional[DropoutPlan] = None,
                     layer_salt: int = 0, seed: int = 0,
                     packed_mask=None, block_q=128, block_k=128):
    """Flash-attention Pallas kernel path (static seed/salt — see DESIGN)."""
    from repro.kernels import flash_attention
    dropped = plan is not None and plan.enabled
    mode = "none"
    p = 0.0
    rounds = 7
    if dropped:
        p = plan.cfg.p
        rounds = plan.cfg.philox_rounds
        mode = "premask" if packed_mask is not None else "fused"
    return flash_attention(
        q, k, v, packed_mask, causal, local_window, p, mode, seed,
        layer_salt, rounds, block_q, block_k, None)


def attention_decode(q1: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len,
                     local_window: int = 0,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """One-token decode: q1 (B,H,1,D) vs caches (B,KV,S,D) of which
    ``cache_len`` entries are valid. Sequence dim stays sharded ("kv_seq")
    — the softmax reductions become small collectives (flash-decoding).
    No dropout at inference."""
    b, h, _, d = q1.shape
    kv, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qg = q1.reshape(b, kv, g, d)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, s), 3)
    valid = pos < cache_len
    if local_window:
        valid = jnp.logical_and(valid, pos >= cache_len - local_window)
    scores = jnp.where(valid, scores, _NEG)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bksd->bkgd", p.astype(v_cache.dtype), v_cache)
    out = constrain(out, "batch", "kv_heads", None, None)
    return out.reshape(b, h, 1, d)
