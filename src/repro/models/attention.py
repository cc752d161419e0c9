"""GQA attention block: projections, rope, qk-norm, dropout plan, caches.

This is where the paper's topology lives: in overlap mode the packed
dropout mask is generated NEXT TO the QKV projection (``qkv+RNG`` site) and
consumed downstream by the attention core — Fig. 4 of the paper. On TPU the
fused gemm_rng kernel realizes the same site physically (MXU ∥ VPU).
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import (
    CARRIED_DROPOUT_SITES,
    AttentionKind,
    ModelConfig,
)
from repro.core.attention import attention_decode, attention_xla
from repro.core.overlap import DropoutPlan
from repro.distributed.sharding import constrain
from repro.models.layers import apply_rope, dense_init, rms_head_norm

log = logging.getLogger(__name__)


def attn_init(key, cfg: ModelConfig) -> Dict[str, Any]:
    d, nq, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    p = {
        "w_q": dense_init(ks[0], d, nq * hd),
        "w_k": dense_init(ks[1], d, nkv * hd),
        "w_v": dense_init(ks[2], d, nkv * hd),
        "w_o": dense_init(ks[3], nq * hd, d),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((nq * hd,), jnp.float32)
        p["b_k"] = jnp.zeros((nkv * hd,), jnp.float32)
        p["b_v"] = jnp.zeros((nkv * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _finish_qkv(p, q, k, v, b, s, cfg: ModelConfig, positions):
    """Shared post-GEMM half of the projection: bias, head split,
    sharding constraints, qk-norm, rope. q/k/v arrive as (B, S, dim)."""
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = q.dtype
    if cfg.qkv_bias:
        q = q + p["b_q"].astype(dt)
        k = k + p["b_k"].astype(dt)
        v = v + p["b_v"].astype(dt)
    q = constrain(q.reshape(b, s, nq, hd), "batch", None, "heads", None)
    k = constrain(k.reshape(b, s, nkv, hd), "batch", None, "kv_heads", None)
    v = constrain(v.reshape(b, s, nkv, hd), "batch", None, "kv_heads", None)
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _project_qkv(p, x, cfg: ModelConfig, positions):
    """x (B, S, D) -> q (B,H,S,hd), k/v (B,KV,S,hd)."""
    b, s, _ = x.shape
    dt = x.dtype
    q = x @ p["w_q"].astype(dt)
    k = x @ p["w_k"].astype(dt)
    v = x @ p["w_v"].astype(dt)
    return _finish_qkv(p, q, k, v, b, s, cfg, positions)


def _project_qkv_fused(p, x, cfg: ModelConfig, positions, plan,
                       layer_idx, step, how=None, policy=None):
    """Fused QKV projection: one concatenated GEMM with this layer's
    packed dropout mask physically generated under it (the paper's
    ``qkv+RNG`` site, kernel-realized; shard-local under a policy).
    ``how`` is the schedule's planned producer. Returns
    (q, k, v, packed, how) — ``how`` the realized producer tag
    ("gemm_rng" | "standalone" | "xla")."""
    from repro.core import producer
    b, s, d = x.shape
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    w_qkv = jnp.concatenate(
        [p["w_q"].astype(dt), p["w_k"].astype(dt), p["w_v"].astype(dt)],
        axis=1)
    y2d, packed, how = producer.gemm_with_mask(
        x.reshape(b * s, d), w_qkv, plan, (b, nq, s, s), layer_idx, step,
        how=how, policy=policy)
    y = y2d.reshape(b, s, -1)
    q = y[..., :nq * hd]
    k = y[..., nq * hd:(nq + nkv) * hd]
    v = y[..., (nq + nkv) * hd:]
    q, k, v = _finish_qkv(p, q, k, v, b, s, cfg, positions)
    return q, k, v, packed, how


def attn_apply(p, x, cfg: ModelConfig, *, kind: AttentionKind,
               plan: Optional[DropoutPlan], layer_idx, step,
               chunk_q: int = 1024, probs_dtype=None,
               impl: str = "xla", policy=None,
               mask_in=None, emit_next: bool = False, asg=None):
    """Training / prefill forward (full sequence). x (B, S, D).

    ``asg`` — this layer's HostAssignment from the compiled
    DropoutSchedule (core/schedule.py) — names the mask producer:
      site "xla"        — XLA bits generated next to the QKV GEMM
      site "qkv"        — bits generated under the fused QKV-GEMM kernel
                          (asg.how records the planned realization;
                          shard-local when a policy is installed)
      carried sites /   — ``mask_in`` carries this layer's mask (made
      "standalone"        under the previous attention layer's host GEMM
                          or the standalone bootstrap); with
                          ``emit_next`` and asg.emit_site="prev_gemm"
                          the call returns (out, mask_next) where
                          mask_next is the NEXT attention layer's mask
                          (layer_idx + asg.emit_stride) generated under
                          THIS layer's out-projection. "ffn_up" /
                          "ffn_down" emissions happen in the FFN half
                          (models/transformer.py routes them through
                          layers.ffn_apply), so this call passes the
                          carry through for them.
    All sites emit bit-identical masks. Direct calls may omit ``asg``;
    a single-layer assignment is compiled on the spot (sugar).
    Returns out, or (out, mask_next) when ``emit_next``.
    """
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    local = cfg.local_window if kind == AttentionKind.LOCAL else 0
    overlap = plan is not None and plan.enabled and plan.overlapped
    if overlap and asg is None:
        from repro.core import schedule as schedule_mod
        asg = schedule_mod.inline_assignment(cfg, plan, b, s,
                                             policy=policy,
                                             attn_impl=impl)
    site = asg.site if overlap else "xla"
    replay = False
    if overlap:
        from repro.core import producer
        replay = asg.how == producer.HOW_REPLAY

    # --- the paper's overlap site: mask produced at a producer GEMM ---
    packed = None
    if replay:
        # zero-HBM consumption: the flash kernels re-derive the keep
        # bits in-register from the plan's counters — no plane is
        # built or fed here. A retained qkv host (asg.host_how) still
        # runs its fused GEMM+RNG and the returned plane is discarded
        # (the RNG stays hidden under the GEMM, bits contract-identical
        # to what the kernel replays).
        if site == "qkv" and asg.host_how:
            q, k, v, _discarded, _how = _project_qkv_fused(
                p, x, cfg, positions, plan, layer_idx, step,
                how=asg.host_how, policy=policy)
        else:
            q, k, v = _project_qkv(p, x, cfg, positions)
    elif overlap and site == "qkv":
        q, k, v, packed, _how = _project_qkv_fused(
            p, x, cfg, positions, plan, layer_idx, step, how=asg.how,
            policy=policy)
    else:
        q, k, v = _project_qkv(p, x, cfg, positions)
        if overlap and (site in CARRIED_DROPOUT_SITES
                        or site == "standalone"):
            from repro.core import producer
            packed = mask_in
            if packed is None:
                # bootstrap / direct call without a scan carry: the
                # standalone producer makes the identical bits in-layer
                use_kernel = asg.how == producer.HOW_STANDALONE
                packed = producer.standalone_packed_mask(
                    plan, b, cfg.n_heads, s, s, layer_idx, step,
                    use_kernel=use_kernel,
                    policy=policy if asg.sharded else None)
        elif overlap:
            packed = plan.precompute_mask(b, cfg.n_heads, s, s,
                                          layer_idx, step)

    pallas = impl == "pallas" and _pallas_ok(plan, policy, cfg, s)
    if impl == "pallas" and not pallas:
        log.warning("attn_impl='pallas' refused for seq=%d heads=%d "
                    "(fused-mode dropout, seq %% 128, or a kv-indivisible "
                    "head mesh): running XLA attention", s, cfg.n_heads)
    if pallas:
        out = _attn_pallas_sharded(
            q, k, v, packed, plan, local, policy,
            replay_key=(layer_idx, step) if replay else None)
    else:
        if replay:
            # fallback chain replay -> premask -> xla: this runtime
            # cannot replay in-kernel, so regenerate the identical
            # plane and consume it the premask way
            from repro.core import producer
            producer.note_realized(producer.HOW_REPLAY, producer.HOW_XLA,
                                   "attention consumer")
            packed = plan.precompute_mask(b, cfg.n_heads, s, s,
                                          layer_idx, step)
        import jax.numpy as _jnp
        out = attention_xla(
            q, k, v, causal=True, local_window=local, plan=plan,
            layer_idx=layer_idx, step=step, packed_mask=packed,
            chunk_q=chunk_q, probs_dtype=probs_dtype or _jnp.float32)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    out = constrain(out, "batch", None, "heads")
    w_o = p["w_o"].astype(x.dtype)
    if emit_next and overlap and asg.emit_site == "prev_gemm":
        # cross-layer pipelining: the NEXT attention layer's mask rides
        # under this layer's out-projection (the paper's "previous GEMM
        # layers" site; emit_stride skips non-attention layers in mixed
        # Griffin-style patterns)
        from repro.core import producer
        y2d, mask_next, _how = producer.gemm_with_mask(
            out.reshape(b * s, -1), w_o, plan, (b, cfg.n_heads, s, s),
            layer_idx + asg.emit_stride, step, how=asg.emit_how,
            policy=policy)
        return y2d.reshape(b, s, -1), mask_next
    y = out @ w_o
    return (y, mask_in) if emit_next else y


def _pallas_ok(plan, policy, cfg, s) -> bool:
    """The flash fwd+bwd kernels need premask-or-none dropout (dynamic
    seeds never enter the kernel — the paper's decoupling makes the RNG
    producer-side) and shard-local full kv (batch-only sharding or
    kv-divisible head sharding)."""
    if plan is not None and plan.enabled and not plan.overlapped:
        return False  # fused mode would need in-kernel dynamic seeds
    if s % 128 != 0:
        return False
    if policy is None:
        return True
    h_ax = policy.mesh_axes_for("heads", cfg.n_heads)
    kv_ax = policy.mesh_axes_for("kv_heads", cfg.n_kv_heads)
    return h_ax is None or kv_ax is not None


def _attn_pallas_sharded(q, k, v, packed, plan, local, policy,
                         replay_key=None):
    """shard_map over the mesh; each shard runs the Pallas flash kernels
    (Mosaic on TPU; interpret lowering here). ``replay_key`` =
    (layer_idx, step) selects mode="replay": the kernels re-derive the
    keep bits in-register from the plan's counters and the only dropout
    operand is the 16-byte (4,) uint32 seed-salt vector in SMEM — no
    mask plane touches HBM. Under a policy each shard folds its global
    (b, h) window offset into the operand (producer.shard_mask_tile),
    so shard-local replay equals the global plane's slice exactly."""
    from jax.sharding import PartitionSpec as P
    from repro.kernels.flash_attention import flash_attention_mosaic

    p_drop = plan.cfg.p if (plan is not None and plan.enabled) else 0.0
    if replay_key is not None and p_drop > 0.0:
        mode = "replay"
    elif packed is not None and p_drop > 0.0:
        mode = "premask"
    else:
        mode = "none"
    rounds = plan.cfg.philox_rounds if plan is not None else 7
    n_heads = q.shape[1]

    def body(q_, k_, v_, m_, heads_global=0):
        # block sizes resolve through the tuned-table hook (else a rule
        # over the shape); analysis/counters._replay_blocks uses the
        # same hook, so the verified replay grid is the executed grid
        from repro.core.producer import attn_flash_blocks
        bq, bk = attn_flash_blocks(q_.shape[2], k_.shape[2], local)
        return flash_attention_mosaic(
            q_, k_, v_, m_, True, local, p_drop, mode, 0, 0, rounds,
            bq, bk, None, heads_global)

    if mode == "replay":
        from repro.kernels.philox_common import seed_salt_smem
        layer_idx, step = replay_key
        seed_salt = seed_salt_smem(plan.step_seed(step),
                                   plan.salt(layer_idx))
        if policy is None:
            return body(q, k, v, seed_salt)
    elif policy is None:
        return body(q, k, v, packed if mode == "premask" else None)

    mesh = policy.mesh
    bsz = q.shape[0]
    b_ax = policy.mesh_axes_for("batch", bsz)
    h_ax = policy.mesh_axes_for("heads", q.shape[1])
    qs = P(b_ax, h_ax, None, None)
    kvs = P(b_ax,
            policy.mesh_axes_for("kv_heads", k.shape[1]), None, None)
    ms = P(b_ax, h_ax, None, None)
    if mode == "replay":
        from repro.core import producer
        shard = producer.shard_exec(policy, bsz, n_heads)
        sq, sk = q.shape[2], k.shape[2]

        def rbody(q_, k_, v_, m_):
            if shard is None:
                return body(q_, k_, v_, m_, n_heads)
            _shape, hg, off = producer.shard_mask_tile(
                shard, bsz, n_heads, sq, sk)
            return body(q_, k_, v_, m_.at[3].set(off), hg)

        return jax.shard_map(
            rbody, mesh=mesh, in_specs=(qs, kvs, kvs, P()),
            out_specs=qs, check_vma=False)(q, k, v, seed_salt)
    if mode == "premask":
        return jax.shard_map(
            body, mesh=mesh, in_specs=(qs, kvs, kvs, ms),
            out_specs=qs, check_vma=False)(q, k, v, packed)
    return jax.shard_map(
        lambda q_, k_, v_: body(q_, k_, v_, None), mesh=mesh,
        in_specs=(qs, kvs, kvs), out_specs=qs,
        check_vma=False)(q, k, v)


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------

def attn_cache_init(cfg: ModelConfig, kind: AttentionKind, batch: int,
                    max_len: int, dtype,
                    kv_bits: int = 16) -> Dict[str, jnp.ndarray]:
    size = (min(max_len, cfg.local_window)
            if kind == AttentionKind.LOCAL else max_len)
    shape = (batch, cfg.n_kv_heads, size, cfg.head_dim)
    if kv_bits == 8:
        # §Perf serving knob: int8 cache + per-(token, head) scales —
        # halves the decode memory floor (the KV-cache read)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:3] + (1,), jnp.float32),
            "v_scale": jnp.zeros(shape[:3] + (1,), jnp.float32),
            "len": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def quantize_kv(x: jnp.ndarray):
    """(B,KV,S,D) -> (int8 values, f32 per-row scales)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def attn_prefill(p, x, cfg: ModelConfig, *, kind: AttentionKind,
                 plan, layer_idx, step, chunk_q: int = 1024,
                 capacity: int = 0
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prefill: full-sequence attention + cache construction. ``capacity``
    reserves decode room in FULL caches (>= s + new tokens)."""
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions)
    local = cfg.local_window if kind == AttentionKind.LOCAL else 0
    out = attention_xla(q, k, v, causal=True, local_window=local,
                        plan=None, chunk_q=chunk_q)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    y = out @ p["w_o"].astype(x.dtype)
    if kind == AttentionKind.LOCAL:
        w = cfg.local_window
        if s >= w:
            # ring layout slot = pos % w: roll the last-w tail by s so
            # cache[(s - w + i) % w] = key(s - w + i)
            k_cache = jnp.roll(k[:, :, -w:], s % w, axis=2)
            v_cache = jnp.roll(v[:, :, -w:], s % w, axis=2)
        else:
            pad = ((0, 0), (0, 0), (0, w - s), (0, 0))
            k_cache = jnp.pad(k, pad)
            v_cache = jnp.pad(v, pad)
    else:
        cap = max(capacity, s)
        pad = ((0, 0), (0, 0), (0, cap - s), (0, 0))
        k_cache = jnp.pad(k, pad)
        v_cache = jnp.pad(v, pad)
    # kv-heads on 'model' when divisible, else sequence (flash-decoding)
    from repro.distributed.sharding import current_policy
    pol = current_policy()
    kv_ax = ("kv_heads", None)
    if pol is not None and pol.mesh_axes_for("kv_heads",
                                             cfg.n_kv_heads) is None:
        kv_ax = (None, "kv_seq")
    cache = {"k": constrain(k_cache, "batch", kv_ax[0], kv_ax[1], None),
             "v": constrain(v_cache, "batch", kv_ax[0], kv_ax[1], None),
             "len": jnp.asarray(s, jnp.int32)}
    return y, cache


def attn_decode(p, x1, cache, cfg: ModelConfig, *, kind: AttentionKind
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-token decode, cache READ-ONLY. x1 (B, 1, D).

    Returns (y, update) where update = {"k_tok", "v_tok", "len"} — the
    caller writes the token column into the stacked cache *outside* the
    layer scan (one tiny DUS for all layers instead of a full cache
    write-back per layer, the difference between O(cache) and O(token)
    write traffic per decode step).
    """
    b = x1.shape[0]
    pos = cache["len"]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _project_qkv(p, x1, cfg, positions)   # (B,H,1,hd)/(B,KV,1,hd)
    size = cache["k"].shape[2]
    quantized = "k_scale" in cache
    # attend over valid cached positions + the current token (virtual)
    out = attention_decode_appended(
        q, cache["k"], cache["v"], k, v, pos, size,
        kind == AttentionKind.LOCAL,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"))
    y = out.transpose(0, 2, 1, 3).reshape(b, 1, -1) @ p["w_o"].astype(
        x1.dtype)
    if quantized:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        update = {"k_tok": kq, "v_tok": vq, "k_scale_tok": ks,
                  "v_scale_tok": vs, "len": pos + 1}
    else:
        update = {"k_tok": k.astype(cache["k"].dtype),
                  "v_tok": v.astype(cache["v"].dtype),
                  "len": pos + 1}
    return y, update


def attn_decode_paged(p, x, cfg: ModelConfig, pool_k, pool_v, phys_idx,
                      positions, *, keep=None, p_drop: float = 0.0
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Multi-token decode against a PAGED KV pool (the serve engine's
    attention): keys/values are gathered through the request page table
    instead of read from a contiguous per-request cache.

    x (B, G, D) — G query tokens per request slot (G=1 plain decode,
        G=k speculative verify; one code path, so verify IS decode).
    pool_k/pool_v (KV, S_phys, hd) — the physical page pool, shared by
        every request. ``phys_idx`` (B, CAP) int32 maps each slot's
        logical position i to its physical pool slot
        (page_table[i // page_size] * page_size + i % page_size),
        resolved host-side once at admission.
    positions (B, G) — absolute logical positions of the G tokens.
    keep (B, H, G, CAP) bool — optional decode-time dropout keep rows,
        sliced from the request's cached packed mask plane (row q of the
        training-identical (q, k) plane); applied post-softmax exactly
        like ``core.attention._chunk_attend``.

    Validity is ``k_pos <= q_pos``: every logical position at or below a
    query is either already written to its page (context/draft tokens)
    or one of the G fresh tokens scattered in below — so one causal rule
    covers plain decode, draft steps, and the chunked verify pass.
    Returns (y (B, G, D), k_new, v_new (B, KV, G, hd)); the caller
    writes the fresh columns into the pool outside the layer scan."""
    from repro.core.attention import _NEG
    b, g, _ = x.shape
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    kv, hd = k_new.shape[1], k_new.shape[3]
    cap = phys_idx.shape[1]
    # gather the logical view through the page table: (B, KV, CAP, hd)
    k_ctx = jnp.take(pool_k, phys_idx, axis=1).transpose(1, 0, 2, 3)
    v_ctx = jnp.take(pool_v, phys_idx, axis=1).transpose(1, 0, 2, 3)
    # scatter the G fresh tokens at their logical positions (their pool
    # pages are written after the step, outside the scan)
    bi = jnp.arange(b)[:, None]
    pos_c = jnp.clip(positions, 0, cap - 1)
    k_all = k_ctx.at[bi, :, pos_c, :].set(
        k_new.transpose(0, 2, 1, 3).astype(k_ctx.dtype))
    v_all = v_ctx.at[bi, :, pos_c, :].set(
        v_new.transpose(0, 2, 1, 3).astype(v_ctx.dtype))
    grp = cfg.n_heads // kv
    if grp > 1:
        k_all = jnp.repeat(k_all, grp, axis=1)
        v_all = jnp.repeat(v_all, grp, axis=1)
    scale = 1.0 / (hd ** 0.5)
    scores = jnp.einsum("bhgd,bhkd->bhgk", q, k_all.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, cap), 3)
    valid = k_ids <= positions[:, None, :, None]
    scores = jnp.where(valid, scores, _NEG)
    m = jnp.max(scores, axis=-1, keepdims=True)
    pr = jnp.exp(scores - m)
    pr = jnp.where(valid, pr, 0.0)
    pr = pr / jnp.sum(pr, axis=-1, keepdims=True)
    if keep is not None:
        pr = jnp.where(keep, pr, 0.0) / (1.0 - p_drop)
    out = jnp.einsum("bhgk,bhkd->bhgd", pr.astype(v_all.dtype), v_all)
    y = out.transpose(0, 2, 1, 3).reshape(b, g, -1) @ p["w_o"].astype(
        x.dtype)
    return y, k_new, v_new


def _decode_scores_partial(qg, k_chunk, v_chunk, slot_offset, n_slots,
                           pos, size, is_local, scale,
                           k_scale=None, v_scale=None):
    """Unnormalized partial softmax over one cache chunk.
    Returns (m (b,kv,g,1), l (b,kv,g,1), num (b,kv,g,d)) f32."""
    from repro.core.attention import _NEG
    if k_scale is not None:  # int8 cache: dequantize the tile
        k_chunk = k_chunk.astype(jnp.float32) * k_scale
        v_chunk = (v_chunk.astype(jnp.float32) * v_scale).astype(qg.dtype)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg,
                        k_chunk.astype(qg.dtype),
                        preferred_element_type=jnp.float32) * scale
    slot_ids = slot_offset + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, 1, n_slots), 3)
    if is_local:
        valid = slot_ids < jnp.minimum(pos, size)
        # ring full: the slot being replaced leaves the window
        valid = jnp.logical_and(
            valid, jnp.logical_or(pos < size, slot_ids != pos % size))
    else:
        valid = slot_ids < pos
    scores = jnp.where(valid, scores, _NEG)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    num = jnp.einsum("bkgs,bksd->bkgd", p.astype(v_chunk.dtype),
                     v_chunk).astype(jnp.float32)
    return m, l, num


def attention_decode_appended(q, k_cache, v_cache, k_new, v_new, pos,
                              size, is_local: bool,
                              k_scale=None, v_scale=None):
    """Decode attention over (read-only cache ++ current token).

    When the cache sequence dim is sharded over 'model' (small-KV GQA),
    this runs as explicit flash-decoding inside shard_map: each shard
    computes an unnormalized partial softmax over its cache slice; the
    (m, l, num) triples combine with pmax/psum. Otherwise a plain jnp
    path (kv-head-sharded or unsharded) is used.
    """
    from repro.distributed.sharding import current_policy
    b, h, _, d = q.shape
    kv = k_cache.shape[1]
    g = h // kv
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, kv, g, d)
    s_self = jnp.einsum("bkgd,bkxd->bkgx", qg,
                        k_new[:, :, 0:1].astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale

    policy = current_policy()
    seq_ax = (policy.mesh_axes_for("kv_seq", size)
              if (policy is not None
                  and policy.mesh_axes_for("kv_heads", kv) is None)
              else None)

    if seq_ax is None:
        m, l, num = _decode_scores_partial(qg, k_cache, v_cache, 0, size,
                                           pos, size, is_local, scale,
                                           k_scale, v_scale)
    else:
        from jax.sharding import PartitionSpec as P
        seq_name = seq_ax if isinstance(seq_ax, str) else seq_ax[0]
        batch_ax = policy.mesh_axes_for("batch", b)
        rep = P(batch_ax, None, None, None)
        cache_spec = P(batch_ax, None, seq_name, None)

        def body(qg_, kc, vc, pos_, ks_, vs_):
            n_loc = kc.shape[2]
            off = jax.lax.axis_index(seq_name) * n_loc
            m_loc, l_loc, num_loc = _decode_scores_partial(
                qg_, kc, vc, off, n_loc, pos_, size, is_local, scale,
                ks_, vs_)
            m_g = jax.lax.pmax(m_loc, seq_name)
            corr = jnp.exp(m_loc - m_g)
            l_g = jax.lax.psum(l_loc * corr, seq_name)
            num_g = jax.lax.psum(num_loc * corr, seq_name)
            return m_g, l_g, num_g

        if k_scale is None:
            k_scale = jnp.ones(k_cache.shape[:3] + (1,), jnp.float32)
            v_scale = k_scale
            # dequant-by-ones keeps one code path; XLA folds it away
        m, l, num = jax.shard_map(
            body, mesh=policy.mesh,
            in_specs=(rep, cache_spec, cache_spec, P(), cache_spec,
                      cache_spec),
            out_specs=(rep, rep, rep), check_vma=False,
        )(qg, k_cache, v_cache, jnp.asarray(pos, jnp.int32),
          k_scale, v_scale)

    # fold in the current token (softmax over cache ++ self)
    m_all = jnp.maximum(m, s_self)
    num = (num * jnp.exp(m - m_all)
           + jnp.exp(s_self - m_all)
           * v_new[:, :, 0:1].astype(jnp.float32))
    den = l * jnp.exp(m - m_all) + jnp.exp(s_self - m_all)
    out = (num / den).astype(q.dtype)
    return out.reshape(b, h, 1, d)
