"""Mixture-of-Experts with explicit expert parallelism.

Layout (production posture):
  * experts sharded over the **data** axis (EP groups == DP groups, the
    Megatron/DeepSpeed-MoE convention) — dispatch/combine are
    ``all_to_all`` collectives along "data";
  * each expert's FFN hidden dim sharded over **model** (TP) with a psum
    after the down-projection;
  * capacity-based top-k routing (GShard) with per-source capacity
    C = ceil(T_local * k * cf / E), position-in-expert via one-hot cumsum,
    overflow dropped (standard).

The same body runs without a mesh (single-device smoke tests) by skipping
the collectives. Shared experts (DeepSeek) and the Arctic dense residual
run as ordinary dense FFNs outside this module.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config.base import ModelConfig, MoEConfig
from repro.distributed.sharding import ShardingPolicy
from repro.models.layers import dense_init


def producer_capacity(moe: MoEConfig, tokens: int) -> int:
    """Per-source expert capacity C. Single source of truth lives in
    core/producer.moe_expert_capacity — the schedule compiler plans the
    grouped host on the SAME (E, C) grid these dispatch bodies walk, so
    the formula must never fork (deferred import: core.producer is a
    heavier module than this shim needs at import time)."""
    from repro.core.producer import moe_expert_capacity
    return moe_expert_capacity(moe, tokens)


@dataclasses.dataclass(frozen=True)
class _GroupedHostCtx:
    """Static grouped-host context for the dispatch bodies: which expert
    GEMM hosts the dropout-mask producer (site "ffn_up" = gate
    projection, "ffn_down" = down projection), the GLOBAL mask shape,
    and the shard-local execution context (producer.ShardExec, None when
    unsharded). Seed/salt are traced and ride in as body operands."""
    plan: Any
    site: str
    mask_shape: Tuple[int, int, int, int]
    shard: Any = None


def _expert_ffn(recv, w_gate, w_up, w_down, dt, hs=None, sd=None,
                sl=None):
    """The expert SwiGLU einsums, shared by every dispatch layout. With
    ``hs`` (a _GroupedHostCtx) the gate (site "ffn_up") or down (site
    "ffn_down") einsum runs through the grouped GEMM+RNG producer and
    this device's tile of the packed mask rides back with the output.
    The emission grid indexes the (b, h, q, k) Philox counter space —
    never token identity — so routing decisions, capacity overflow and
    the expert permutation in ``recv`` cannot reach the bits. Returns
    (out, mask-or-None); ``out`` is bit-identical to the plain einsum
    path for an f32 host (single-k-block accumulation)."""
    from repro.core import producer
    mask = None
    tile = None
    if hs is not None:
        b, nh, sq, sk = hs.mask_shape
        tile = producer.shard_mask_tile(hs.shard, b, nh, sq, sk)
    if hs is not None and hs.site == "ffn_up":
        local_shape, hg, off = tile
        h_g, mask, how = producer.grouped_gemm_seeded(
            recv, w_gate.astype(dt), hs.plan, local_shape, sd, sl,
            heads_global=hg, bh_offset=off)
        producer.note_realized(producer.HOW_GEMM_GROUPED, how,
                               "MoE expert gate host")
    else:
        h_g = jnp.einsum("ecd,edf->ecf", recv, w_gate.astype(dt))
    h_u = jnp.einsum("ecd,edf->ecf", recv, w_up.astype(dt))
    h = jax.nn.silu(h_g.astype(jnp.float32)).astype(dt) * h_u
    if hs is not None and hs.site == "ffn_down":
        local_shape, hg, off = tile
        out, mask, how = producer.grouped_gemm_seeded(
            h, w_down.astype(dt), hs.plan, local_shape, sd, sl,
            heads_global=hg, bh_offset=off)
        producer.note_realized(producer.HOW_GEMM_GROUPED, how,
                               "MoE expert down host")
    else:
        out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))
    return out, mask


def moe_init(key, cfg: ModelConfig) -> Dict[str, Any]:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    ks = jax.random.split(key, 4)
    scale = 1.0 / (d ** 0.5)
    return {
        "router": dense_init(ks[0], d, e, scale=0.02),
        "w_gate": jax.random.normal(ks[1], (e, d, f)) * scale,
        "w_up": jax.random.normal(ks[2], (e, d, f)) * scale,
        "w_down": jax.random.normal(ks[3], (e, f, d)) * (1.0 / f ** 0.5),
    }


def _dispatch_combine(x2d, router_w, w_gate, w_up, w_down, *rng,
                      moe: MoEConfig, ep_axis: Optional[str],
                      tp_axis: Optional[str], dp_axes: Tuple[str, ...],
                      hs: Optional[_GroupedHostCtx] = None):
    """Local body. x2d (T_loc, D). Expert weights are LOCAL shards
    (E_loc, D, F_loc). Returns (y (T_loc, D), aux_loss scalar), plus
    this device's packed-mask tile when ``hs`` hosts a grouped RNG
    emission (``rng`` = (seed, salt) operands)."""
    t, d = x2d.shape
    e = moe.n_experts
    k = moe.top_k
    dt = x2d.dtype

    logits = (x2d @ router_w.astype(dt)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                        # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # per-source capacity (the formula the schedule compiler plans on:
    # producer.moe_expert_capacity)
    cap = producer_capacity(moe, t)

    # position-in-expert via one-hot cumsum over (token, slot) order
    flat_idx = idx.reshape(t * k)
    flat_gate = gate.reshape(t * k)
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.float32)    # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0)
    pos = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)     # (T*k,)
    keep = pos < cap
    dest = jnp.where(keep, flat_idx * cap + pos, 0)

    # aux load-balance loss (GShard): E * sum_e f_e * P_e
    f_e = jnp.mean(onehot * keep[:, None].astype(jnp.float32), axis=0) * k
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e) / k

    # scatter tokens into (E * cap, D) send buffer
    x_rep = jnp.repeat(x2d, k, axis=0)                         # (T*k, D)
    upd = jnp.where(keep[:, None], x_rep, 0)
    send = jnp.zeros((e * cap, d), dt).at[dest].add(upd)
    send = send.reshape(e, cap, d)

    if ep_axis is not None:
        # (E, cap, D) -> (E_loc, n_src * cap, D)
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0,
                                  concat_axis=1, tiled=True)
    else:
        recv = send                                            # E_loc == E

    # expert FFN (swiglu), TP over tp_axis; optionally hosting the
    # grouped RNG emission under the gate / down expert GEMM
    out, mask = _expert_ffn(recv, w_gate, w_up, w_down, dt, hs, *rng)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)

    if ep_axis is not None:
        back = jax.lax.all_to_all(out, ep_axis, split_axis=1,
                                  concat_axis=0, tiled=True)
    else:
        back = out                                             # (E, cap, D)

    # combine on the source shard
    flat_out = back.reshape(e * cap, d)[dest]                  # (T*k, D)
    flat_out = jnp.where(keep[:, None], flat_out, 0)
    y = jnp.sum(
        (flat_out.astype(jnp.float32)
         * flat_gate[:, None]).reshape(t, k, d), axis=1).astype(dt)

    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)
    if hs is not None:
        return y, aux, mask
    return y, aux


def _dispatch_combine_dedup(x2d, router_w, w_gate, w_up, w_down, *rng,
                            moe: MoEConfig, ep_axis: str, tp_axis: str,
                            dp_axes: Tuple[str, ...],
                            hs: Optional[_GroupedHostCtx] = None):
    """§Perf variant: tokens arrive ALREADY split over the tp axis (the
    residual stream is sequence-sharded there), so the EP all-to-all
    carries each token once instead of once per TP shard (16x dedup).
    The TP shards then all-gather expert inputs along the capacity axis
    (paying the unavoidable TP input cost once) and reduce-scatter the
    expert outputs back to their own token chunk."""
    t, d = x2d.shape                       # t = T / (dp * tp)
    e = moe.n_experts
    k = moe.top_k
    dt = x2d.dtype

    logits = (x2d @ router_w.astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    cap = producer_capacity(moe, t)
    flat_idx = idx.reshape(t * k)
    flat_gate = gate.reshape(t * k)
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.float32)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0)
    pos = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
    keep = pos < cap
    dest = jnp.where(keep, flat_idx * cap + pos, 0)

    f_e = jnp.mean(onehot * keep[:, None].astype(jnp.float32), axis=0) * k
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e) / k

    x_rep = jnp.repeat(x2d, k, axis=0)
    upd = jnp.where(keep[:, None], x_rep, 0)
    send = jnp.zeros((e * cap, d), dt).at[dest].add(upd)
    send = send.reshape(e, cap, d)

    # EP a2a over 'data' — payload is this shard's 1/tp token slice only
    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=1,
                              tiled=True)        # (E_loc, nsrc*cap, D)
    # TP shards need every token of their experts: one gather, not 16 a2as
    full = jax.lax.all_gather(recv, tp_axis, axis=1, tiled=True)

    out, mask = _expert_ffn(full, w_gate, w_up, w_down, dt, hs, *rng)
    # sum the TP partials AND return only this shard's token chunk
    own = jax.lax.psum_scatter(out, tp_axis, scatter_dimension=1,
                               tiled=True)       # (E_loc, nsrc*cap, D)

    back = jax.lax.all_to_all(own, ep_axis, split_axis=1, concat_axis=0,
                              tiled=True)        # (E, cap, D)

    flat_out = back.reshape(e * cap, d)[dest]
    flat_out = jnp.where(keep[:, None], flat_out, 0)
    y = jnp.sum(
        (flat_out.astype(jnp.float32)
         * flat_gate[:, None]).reshape(t, k, d), axis=1).astype(dt)
    aux = jax.lax.pmean(aux, dp_axes + (tp_axis,))
    if hs is not None:
        return y, aux, mask
    return y, aux


def _dispatch_combine_ep_model(x2d, router_w, w_gate, w_up, w_down, *rng,
                               moe: MoEConfig, ep_axis: str,
                               fsdp_axis: str,
                               dp_axes: Tuple[str, ...],
                               hs: Optional[_GroupedHostCtx] = None):
    """§Perf layout for small-d_ff experts: experts sharded over 'model'
    (= ep_axis here), expert weights FSDP'd over 'data' (= fsdp_axis) and
    gathered per layer, tokens chunked over (data x model). The dispatch
    a2a runs over 'model' WITHIN each data row, every token moves once,
    and no expert-input gather exists (each data row computes only its
    own tokens at full per-expert d_ff — intact arithmetic intensity).
    """
    t, d = x2d.shape                       # t = T / (dp * model)
    e = moe.n_experts
    k = moe.top_k
    dt = x2d.dtype

    logits = (x2d @ router_w.astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    cap = producer_capacity(moe, t)
    flat_idx = idx.reshape(t * k)
    flat_gate = gate.reshape(t * k)
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.float32)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0)
    pos = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
    keep = pos < cap
    dest = jnp.where(keep, flat_idx * cap + pos, 0)

    f_e = jnp.mean(onehot * keep[:, None].astype(jnp.float32), axis=0) * k
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e) / k

    x_rep = jnp.repeat(x2d, k, axis=0)
    upd = jnp.where(keep[:, None], x_rep, 0)
    send = jnp.zeros((e * cap, d), dt).at[dest].add(upd)
    send = send.reshape(e, cap, d)

    # dispatch a2a over the model axis (within the data row)
    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=1,
                              tiled=True)        # (E_loc, nchunk*cap, D)

    # FSDP weight gather over 'data' (weights are the small tensor here)
    wg = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
    wu = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
    wd = jax.lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)

    out, mask = _expert_ffn(recv, wg, wu, wd, dt, hs, *rng)

    back = jax.lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                              tiled=True)        # (E, cap, D)

    flat_out = back.reshape(e * cap, d)[dest]
    flat_out = jnp.where(keep[:, None], flat_out, 0)
    y = jnp.sum(
        (flat_out.astype(jnp.float32)
         * flat_gate[:, None]).reshape(t, k, d), axis=1).astype(dt)
    aux = jax.lax.pmean(aux, dp_axes + (ep_axis,))
    if hs is not None:
        return y, aux, mask
    return y, aux


def moe_apply(params: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig,
              policy: Optional[ShardingPolicy] = None,
              seq_dispatch: bool = False, host=None):
    """x (B, S, D) -> (y (B, S, D), aux scalar).

    ``host`` (a core/producer.FFNHost with a grouped ``how``) asks the
    expert FFN to physically host the dropout-mask producer under one of
    its grouped GEMMs — "ffn_up" = the gate projection einsum,
    "ffn_down" = the down projection. The return value then grows a
    third element: the packed mask (B, H, SQ//32, SK), generated
    shard-local inside the SAME shard_map the dispatch runs in (each
    device emits its (b_loc, h_loc) tile of the mask plane via
    position-based counters — bit-identical to the global mask's slice
    for every EP layout, because emission indexes the counter space,
    never token identity)."""
    from repro.distributed.sharding import constrain
    b, s, d = x.shape
    moe = cfg.moe
    # pin the boundary layout: without these constraints GSPMD may
    # propagate the flat (B*S) token sharding back through the reshape as
    # batch-over-all-axes, conflict with the residual stream's
    # (batch->data, seq->model) layout, and fall back to full per-device
    # replication of the activation (+8.6 GB/device/layer observed)
    x = constrain(x, "batch", "seq", "embed")
    x2d = x.reshape(b * s, d)

    rng_args = ()
    hs = None
    mask_spec = None
    if host is not None:
        from repro.core import producer
        mb, mh, _msq, _msk = host.mask_shape
        shard = producer.shard_exec(policy, mb, mh)
        hs = _GroupedHostCtx(plan=host.plan, site=host.site,
                             mask_shape=host.mask_shape, shard=shard)
        rng_args = (jnp.asarray(host.plan.step_seed(host.step),
                                jnp.uint32),
                    jnp.asarray(host.plan.salt(host.layer_idx),
                                jnp.uint32))
        mask_spec = (P() if shard is None
                     else P(shard.b_spec, shard.h_spec, None, None))

    if policy is None:
        out = _dispatch_combine(
            x2d, params["router"], params["w_gate"], params["w_up"],
            params["w_down"], *rng_args, moe=moe, ep_axis=None,
            tp_axis=None, dp_axes=(), hs=hs)
        if hs is not None:
            y, aux, mask = out
            return y.reshape(b, s, d), aux, mask
        y, aux = out
        return y.reshape(b, s, d), aux

    mesh = policy.mesh
    names = set(mesh.axis_names)
    ep = "data" if "data" in names else None
    tp = "model" if "model" in names else None
    dp = tuple(a for a in ("pod", "data") if a in names)
    # capacity/expert divisibility guards
    if ep is not None and moe.n_experts % mesh.shape[ep] != 0:
        ep = None
    if tp is not None and moe.d_ff_expert % mesh.shape[tp] != 0:
        tp = None

    ew_spec = P(ep, None, tp)
    ew2_spec = P(ep, tp, None)
    rng_specs = (P(), P()) if hs is not None else ()

    def _run(body, tok_spec, in_specs):
        out_specs = ((tok_spec, P()) if hs is None
                     else (tok_spec, P(), mask_spec))
        out = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs + rng_specs,
            out_specs=out_specs, check_vma=False,
        )(x2d, params["router"], params["w_gate"], params["w_up"],
          params["w_down"], *rng_args)
        if hs is None:
            y2d, aux = out
            mask = None
        else:
            y2d, aux, mask = out
        y = constrain(y2d.reshape(b, s, d), "batch", "seq", "embed")
        return (y, aux, mask) if hs is not None else (y, aux)

    # ep_model layout: experts over 'model', weights FSDP'd over 'data'
    ep_model = (policy.mesh_axes_for("expert", moe.n_experts) == "model")
    if (seq_dispatch and ep_model and tp is not None
            and moe.n_experts % mesh.shape[tp] == 0
            and (b * s) % (mesh.shape[tp]
                           * int(np.prod([mesh.shape[a] for a in dp])))
            == 0 and "data" in names
            and cfg.d_model % mesh.shape["data"] == 0):
        tok_spec = P(dp + (tp,), None)
        body = functools.partial(_dispatch_combine_ep_model, moe=moe,
                                 ep_axis=tp, fsdp_axis="data",
                                 dp_axes=dp, hs=hs)
        return _run(body, tok_spec,
                    (tok_spec, P(None, None), P(tp, "data", None),
                     P(tp, "data", None), P(tp, None, "data")))

    if (seq_dispatch and not ep_model and ep is not None
            and tp is not None
            and (b * s) % (mesh.shape[tp]
                           * int(np.prod([mesh.shape[a] for a in dp])))
            == 0):
        tok_spec = P(dp + (tp,), None)
        body = functools.partial(_dispatch_combine_dedup, moe=moe,
                                 ep_axis=ep, tp_axis=tp, dp_axes=dp,
                                 hs=hs)
        return _run(body, tok_spec,
                    (tok_spec, P(None, None), ew_spec, ew_spec,
                     ew2_spec))

    tok_spec = P(dp if len(dp) > 1 else (dp[0] if dp else None), None)
    body = functools.partial(_dispatch_combine, moe=moe, ep_axis=ep,
                             tp_axis=tp, dp_axes=dp, hs=hs)
    return _run(body, tok_spec,
                (tok_spec, P(None, None), ew_spec, ew_spec, ew2_spec))
