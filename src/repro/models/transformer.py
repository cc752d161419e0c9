"""Model assembly: stacks of scanned layer units covering all 10 assigned
architectures (dense GQA / MoE / RWKV6 / Griffin hybrid / modality stubs).

Layers are grouped into *stacks* — a repeating unit (e.g. Griffin's
(R, R, A)) scanned ``count`` times with stacked params — keeping HLO size
O(1) in depth, which matters when compiling 80-layer models for 512
devices. Remat wraps the unit body ("block" policy).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import AttentionKind, FFNKind, ModelConfig
from repro.core.overlap import DropoutPlan
from repro.distributed.sharding import ShardingPolicy, constrain
from repro.models import moe as moe_mod
from repro.models.attention import (
    attn_apply,
    attn_cache_init,
    attn_decode,
    attn_decode_paged,
    attn_init,
    attn_prefill,
)
from repro.models.layers import (
    embed_init,
    ffn_apply,
    ffn_init,
    norm_apply,
    norm_init,
    token_shift,
)
from repro.models.rglru import (
    rglru_apply,
    rglru_cache_init,
    rglru_decode,
    rglru_init,
    rglru_prefill,
)
from repro.models.rwkv import (
    rwkv_apply,
    rwkv_cache_init,
    rwkv_decode,
    rwkv_init,
    rwkv_prefill,
)


@dataclasses.dataclass
class Runtime:
    """Per-call execution context threaded through the model.

    ``schedule`` carries the compiled DropoutSchedule
    (core/schedule.py). When None and a plan is set, ``forward``
    compiles one from the plan's site sugar at trace time — same cached
    artifact the launch layer would have compiled explicitly."""
    plan: Optional[DropoutPlan] = None
    step: Any = 0
    compute_dtype: Any = jnp.float32
    policy: Optional[ShardingPolicy] = None
    chunk_q: int = 1024
    remat: str = "none"            # none | block
    probs_dtype: Any = None        # None -> f32; bf16 = §Perf knob
    moe_seq_dispatch: bool = False
    attn_impl: str = "xla"         # xla | pallas
    schedule: Optional[Any] = None  # compiled DropoutSchedule


@dataclasses.dataclass(frozen=True)
class StackSpec:
    unit: Tuple[Tuple[AttentionKind, str], ...]  # (kind, "dense"|"moe")
    count: int
    base: int                                     # first layer index


def build_stacks(cfg: ModelConfig) -> List[StackSpec]:
    kinds = cfg.layer_kinds()
    n = cfg.n_layers
    first_dense = cfg.moe.first_dense_layers if cfg.moe else 0
    tag = lambda i: ("moe" if (cfg.moe is not None and i >= first_dense)
                     else "dense")
    stacks: List[StackSpec] = []
    start = 0
    if first_dense:
        assert len(cfg.block_pattern) == 1, \
            "first_dense_layers requires a uniform block pattern"
        stacks.append(StackSpec(
            unit=tuple((kinds[i], "dense") for i in range(first_dense)),
            count=1, base=0))
        start = first_dense
    p = len(cfg.block_pattern)
    rem = n - start
    cnt = rem // p
    if cnt:
        unit = tuple((kinds[start + j], tag(start + j)) for j in range(p))
        stacks.append(StackSpec(unit=unit, count=cnt, base=start))
        start += cnt * p
    if start < n:
        unit = tuple((kinds[i], tag(i)) for i in range(start, n))
        stacks.append(StackSpec(unit=unit, count=1, base=start))
    return stacks


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, kind: AttentionKind, tag: str):
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {
        "norm_mix": norm_init(cfg),
        "norm_ffn": norm_init(cfg),
    }
    if kind in (AttentionKind.FULL, AttentionKind.LOCAL):
        p["mix"] = attn_init(ks[0], cfg)
    elif kind == AttentionKind.RECURRENT:
        p["mix"] = rglru_init(ks[0], cfg)
    else:
        p["mix"] = rwkv_init(ks[0], cfg)
    if tag == "moe":
        m = cfg.moe
        p["moe"] = moe_mod.moe_init(ks[1], cfg)
        if m.n_shared_experts:
            p["shared"] = ffn_init(ks[2], cfg,
                                   d_ff=m.n_shared_experts * m.d_ff_expert)
        if m.dense_residual:
            p["dense_res"] = ffn_init(
                ks[3], cfg, d_ff=m.dense_residual_ff or m.d_ff_expert)
    else:
        p["ffn"] = ffn_init(ks[1], cfg)
    return p


def model_init(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 4 + len(build_stacks(cfg)))
    params: Dict[str, Any] = {"final_norm": norm_init(cfg)}
    if cfg.frontend == "token":
        params["embed"] = embed_init(ks[0], cfg.vocab_size, cfg.d_model)
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(ks[1], cfg.vocab_size,
                                           cfg.d_model).T
    else:
        params["unembed"] = embed_init(ks[1], cfg.vocab_size,
                                       cfg.d_model).T
    stacks = []
    for si, spec in enumerate(build_stacks(cfg)):
        def unit_init(k, _spec=spec):
            uks = jax.random.split(k, len(_spec.unit))
            return {f"l{j}": _layer_init(uks[j], cfg, kind, tag)
                    for j, (kind, tag) in enumerate(_spec.unit)}
        stacks.append(jax.vmap(unit_init)(
            jax.random.split(ks[3 + si], spec.count)))
    params["stacks"] = stacks
    return params


# --------------------------------------------------------------------------
# block forward
# --------------------------------------------------------------------------

def _mix_forward(p, x, cfg, rt: Runtime, kind, layer_idx,
                 mask_in=None, emit_next=False, asg=None):
    """Returns (y, mask_next). mask_next threads the carried-mask
    pipeline; it is None unless ``emit_next`` (carried scan buffer)."""
    if kind in (AttentionKind.FULL, AttentionKind.LOCAL):
        y = attn_apply(p, x, cfg, kind=kind, plan=rt.plan,
                       layer_idx=layer_idx, step=rt.step,
                       chunk_q=rt.chunk_q,
                       probs_dtype=rt.probs_dtype or jnp.float32,
                       impl=rt.attn_impl, policy=rt.policy,
                       mask_in=mask_in, emit_next=emit_next, asg=asg)
        return y if emit_next else (y, None)
    if kind == AttentionKind.RECURRENT:
        return rglru_apply(p, x, cfg), None
    return rwkv_apply(p, x, cfg), None


def _ffn_forward(p, x, cfg, rt: Runtime, tag, layer_idx=0,
                 asg=None, mask_shape=None):
    """Returns (out, aux, mask_next). When the schedule assigns this
    block an FFN emission (asg.emit_site "ffn_up"/"ffn_down"), the FFN
    hosts the NEXT attention layer's mask producer under one of its
    GEMMs (the carried-scan pipeline); blocks whose FFN has no hostable
    GEMM (MoE, RWKV channel-mix) were planned HOW_STANDALONE/HOW_XLA by
    the compiler — identical bits, uniform scan carry."""
    from repro.core import producer
    mask_next = None
    host = None
    if (asg is not None and mask_shape is not None
            and asg.emit_site in ("ffn_up", "ffn_down")):
        host = producer.FFNHost(
            plan=rt.plan, site=asg.emit_site, mask_shape=mask_shape,
            layer_idx=layer_idx + asg.emit_stride, step=rt.step,
            how=asg.emit_how, policy=rt.policy)
    if tag == "moe":
        if (host is not None
                and host.how == producer.HOW_GEMM_GROUPED):
            # the expert einsum hosts the emission through the grouped
            # kernel — the RNG grid indexes the (b, h, q, k) counter
            # space, so the permuted/capacity-dropped token layout of
            # the dispatch never reaches the bits
            y, aux, mask_next = moe_mod.moe_apply(
                p["moe"], x, cfg, rt.policy,
                seq_dispatch=rt.moe_seq_dispatch, host=host)
        else:
            y, aux = moe_mod.moe_apply(p["moe"], x, cfg, rt.policy,
                                       seq_dispatch=rt.moe_seq_dispatch)
            if host is not None:
                # infeasible grouped shape (see the schedule's per-layer
                # reason): keep the carry alive with the standalone
                # producer, as planned (host.how)
                b, h_, sq, sk = mask_shape
                mask_next = producer.standalone_packed_mask(
                    rt.plan, b, h_, sq, sk, host.layer_idx, rt.step,
                    use_kernel=host.how == producer.HOW_STANDALONE,
                    policy=rt.policy)
        if "shared" in p:
            y = y + ffn_apply(p["shared"], x, cfg)
        if "dense_res" in p:
            y = y + ffn_apply(p["dense_res"], x, cfg)
        return y, aux, mask_next
    shifted = None
    if cfg.ffn == FFNKind.RWKV_CHANNEL:
        shifted = token_shift(x)
    if host is not None:
        y, mask_next = ffn_apply(p["ffn"], x, cfg, shifted=shifted,
                                 host=host)
        return y, jnp.float32(0.0), mask_next
    return (ffn_apply(p["ffn"], x, cfg, shifted=shifted),
            jnp.float32(0.0), None)


def block_apply(p, x, cfg, rt: Runtime, kind, tag, layer_idx,
                asg=None, mask_in=None, emit=False):
    """Returns (x, aux, mask_next); mask_next carries the carried-site
    pipeline buffer (None when the plan doesn't pipeline masks). ``asg``
    is this block's HostAssignment from the compiled schedule: with
    emit_site="prev_gemm" the next consumer's mask is emitted under
    attention's out-proj; with "ffn_up"/"ffn_down" by the FFN half — the
    block's largest GEMMs (the regime the paper benchmarks).
    Non-attention blocks (Griffin R layers, RWKV mixers) pass the carry
    through untouched — the mixed-pattern pipeline the per-layer
    schedule exists for."""
    x = constrain(x, "batch", "seq", "embed")
    is_attn = kind in (AttentionKind.FULL, AttentionKind.LOCAL)
    ffn_hosts = (emit and is_attn and asg is not None
                 and asg.emit_site in ("ffn_up", "ffn_down"))
    # the scope names are read by the benchmark's device-trace
    # attribution (bench/scopes.py): keep them stable
    with jax.named_scope("attn"):
        h = norm_apply(p["norm_mix"], x, cfg)
        y, mask_next = _mix_forward(
            p["mix"], h, cfg, rt, kind, layer_idx, mask_in=mask_in,
            emit_next=emit and is_attn and not ffn_hosts, asg=asg)
        x = x + y
    with jax.named_scope("ffn"):
        h2 = norm_apply(p["norm_ffn"], x, cfg)
        if ffn_hosts:
            b, s = x.shape[0], x.shape[1]
            f, aux, mask_next = _ffn_forward(
                p, h2, cfg, rt, tag, layer_idx=layer_idx, asg=asg,
                mask_shape=(b, cfg.n_heads, s, s))
        else:
            f, aux, _ = _ffn_forward(p, h2, cfg, rt, tag)
        x = x + f
    if emit and not is_attn:
        mask_next = mask_in        # carry rides through mixer-only blocks
    if mask_next is not None and asg is not None:
        from repro.core import producer
        if asg.how == producer.HOW_REPLAY:
            # replay-planned consumers never read a plane: a retained
            # gemm-hosted emission ran for the RNG-under-GEMM overlap
            # only — drop its output here, nothing reaches the carry
            mask_next = None
    return x, aux, mask_next


# --------------------------------------------------------------------------
# full forward (training)
# --------------------------------------------------------------------------

@jax.named_scope("embed")
def embed_inputs(params, cfg: ModelConfig, inputs, rt: Runtime):
    if cfg.frontend == "token":
        x = jnp.take(params["embed"], inputs, axis=0)
    else:
        x = inputs                                  # precomputed embeddings
    return x.astype(rt.compute_dtype)


def unembed(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        w = params["embed"].T
    else:
        w = params["unembed"]
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    return constrain(logits, "batch", None, "vocab")


def forward(params, cfg: ModelConfig, rt: Runtime, inputs
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training/eval forward. inputs: tokens (B,S) or embeds (B,S,D).
    Returns (logits f32 (B,S,V), aux_loss).

    Mask production follows the compiled DropoutSchedule (rt.schedule,
    or compiled here from the plan's site sugar — static data only, so
    this happens once per trace and hits the compile cache). With a
    carried site ("prev_gemm" / "ffn_up" / "ffn_down") the scan carry
    additionally threads the packed mask buffer: the next attention
    layer's mask is generated under the current attention block's
    out-proj or FFN up/down GEMM (paper's "previous GEMM layers" site —
    the FFN GEMMs are the block's largest hosts). In mixed Griffin-style
    patterns the buffer rides through the recurrent blocks untouched and
    the emission targets the *next attention layer* (asg.emit_stride).
    The first consumer has no producer GEMM before it, so its mask
    bootstraps from the standalone producer — the cross-layer analogue
    of the Region-3 remainder."""
    x = embed_inputs(params, cfg, inputs, rt)
    sched = rt.schedule
    if sched is not None and (sched.batch, sched.seq) != (x.shape[0],
                                                          x.shape[1]):
        sched = None               # stale artifact: recompile for shape
    from repro.core import producer
    if (sched is not None and sched.active and cfg.moe is not None
            and sched.moe_seq_dispatch != rt.moe_seq_dispatch
            and any(producer.HOW_GEMM_GROUPED in (a.how, a.emit_how)
                    for a in sched.assignments)):
        # fail fast at build time: the grouped expert-host grid was
        # planned for the OTHER dispatch layout — executing it anyway
        # would silently emit a mask plan that belongs to a different
        # expert GEMM grid. Schedules without a grouped host are
        # dispatch-layout-independent and pass through.
        raise ValueError(
            f"compiled DropoutSchedule for model={cfg.name!r} was "
            f"planned for moe_seq_dispatch={sched.moe_seq_dispatch} but "
            f"the runtime has moe_seq_dispatch={rt.moe_seq_dispatch}; "
            "recompile with compile_schedule(..., moe_seq_dispatch=...) "
            "matching ShardingConfig.moe_seq_dispatch")
    if sched is None and rt.plan is not None:
        from repro.core import schedule as schedule_mod
        sched = schedule_mod.compile_schedule(
            cfg, rt.plan.cfg, x.shape[0], x.shape[1], policy=rt.policy,
            attn_impl=rt.attn_impl,
            moe_seq_dispatch=rt.moe_seq_dispatch)
    active = sched is not None and sched.active
    carry_mask = active and sched.carried
    aux_total = jnp.float32(0.0)
    mask_buf = None
    if carry_mask and not sched.replay:
        # replay consumption needs no bootstrap and no carried plane:
        # the scan still threads the (None) carry slot so retained
        # gemm-hosted emissions keep their uniform body, but no mask
        # bit is materialized for the consumers
        from repro.core import producer
        basg = sched.for_layer(sched.first_consumer)
        b, s = x.shape[0], x.shape[1]
        with jax.named_scope("mask"):
            mask_buf = producer.standalone_packed_mask(
                rt.plan, b, cfg.n_heads, s, s, sched.first_consumer,
                rt.step, use_kernel=basg.how == producer.HOW_STANDALONE,
                policy=rt.policy if basg.sharded else None)
    # the scans' own slices of the stacked parameters and their stacked
    # writes fall in "layers", outside any block's "attn" / "ffn"
    with jax.named_scope("layers"):
        for spec, stack_params in zip(build_stacks(cfg),
                                      params["stacks"]):
            unit_len = len(spec.unit)
            # static per-unit-position assignments: the scan compiles ONE
            # body, so the schedule guarantees positional periodicity
            # within each stack (schedule._check_scan_periodicity)
            unit_asgs = tuple(
                sched.for_layer(spec.base + j) if active else None
                for j in range(unit_len))

            def unit_apply(x, mask, up, pos, _spec=spec, _ul=unit_len,
                           _asgs=unit_asgs):
                aux = jnp.float32(0.0)
                for j, (kind, tag) in enumerate(_spec.unit):
                    lidx = _spec.base + pos * _ul + j
                    x, a, mask = block_apply(up[f"l{j}"], x, cfg, rt,
                                             kind, tag, lidx,
                                             asg=_asgs[j], mask_in=mask,
                                             emit=carry_mask)
                    aux = aux + a
                return x, aux, mask

            if rt.remat == "block":
                unit_apply = jax.checkpoint(
                    unit_apply,
                    policy=jax.checkpoint_policies.nothing_saveable)

            if carry_mask:
                def body(carry, xs, _ua=unit_apply):
                    xc, aux, mask = carry
                    up, pos = xs
                    xn, a, mask = _ua(xc, mask, up, pos)
                    return (xn, aux + a, mask), None

                (x, aux_total, mask_buf), _ = jax.lax.scan(
                    body, (x, aux_total, mask_buf),
                    (stack_params, jnp.arange(spec.count)))
            else:
                def body(carry, xs, _ua=unit_apply):
                    xc, aux = carry
                    up, pos = xs
                    xn, a, _ = _ua(xc, None, up, pos)
                    return (xn, aux + a), None

                (x, aux_total), _ = jax.lax.scan(
                    body, (x, aux_total),
                    (stack_params, jnp.arange(spec.count)))
    # the last attention layer's emitted mask (consumer index beyond
    # n_layers) has no consumer — dropped here. The scan compiles ONE
    # body for all iterations, so that final generation cannot be
    # peeled away: carried sites pay one extra B*H*(S/32)*S mask per
    # forward (hidden under the GEMM when fused; cheap but real in the
    # XLA path).
    with jax.named_scope("unembed"):
        x = norm_apply(params["final_norm"], x, cfg)
        return unembed(params, cfg, x), aux_total


# --------------------------------------------------------------------------
# caches / prefill / decode
# --------------------------------------------------------------------------

def _layer_cache_init(cfg, kind, batch, max_len, dtype, kv_bits=16):
    if kind in (AttentionKind.FULL, AttentionKind.LOCAL):
        return attn_cache_init(cfg, kind, batch, max_len, dtype, kv_bits)
    if kind == AttentionKind.RECURRENT:
        return rglru_cache_init(cfg, batch, dtype)
    return rwkv_cache_init(cfg, batch, dtype)


def cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype,
               prefilled_len: int = 0, kv_bits: int = 16) -> List[Any]:
    """Zero caches for decode, stacked to match params['stacks']. If
    prefilled_len > 0 the caches advertise that many valid positions
    (dry-run decode cells construct state this way, without a prefill)."""
    caches = []
    for spec in build_stacks(cfg):
        unit_cache = {}
        for j, (kind, _) in enumerate(spec.unit):
            c = _layer_cache_init(cfg, kind, batch, max_len, dtype,
                                  kv_bits)
            if prefilled_len:
                c["len"] = jnp.asarray(prefilled_len, jnp.int32)
            unit_cache[f"l{j}"] = c
        stacked = jax.tree.map(
            lambda a: jnp.zeros((spec.count,) + a.shape, a.dtype)
            + a, unit_cache)
        caches.append(stacked)
    return caches


def _layer_prefill(p, x, cfg, rt, kind, tag, layer_idx, capacity):
    x = constrain(x, "batch", "seq", "embed")
    h = norm_apply(p["norm_mix"], x, cfg)
    if kind in (AttentionKind.FULL, AttentionKind.LOCAL):
        y, cache = attn_prefill(p["mix"], h, cfg, kind=kind, plan=None,
                                layer_idx=layer_idx, step=rt.step,
                                chunk_q=rt.chunk_q, capacity=capacity)
    elif kind == AttentionKind.RECURRENT:
        y, cache = rglru_prefill(p["mix"], h, cfg)
    else:
        y, cache = rwkv_prefill(p["mix"], h, cfg)
    x = x + y
    h2 = norm_apply(p["norm_ffn"], x, cfg)
    if kind == AttentionKind.WKV:
        cache["shift_cm"] = h2[:, -1, :]
    f, _, _ = _ffn_forward(p, h2, cfg, rt, tag)
    return x + f, cache


def _layer_decode(p, x1, cache, cfg, rt, kind, tag):
    """Cache is READ-ONLY here. Returns (x, update) — for attention
    layers the update is the token kv column ({"k_tok","v_tok","len"}),
    applied to the stacked cache outside the layer scan; recurrent/wkv
    states are small and returned in full."""
    h = norm_apply(p["norm_mix"], x1, cfg)
    if kind in (AttentionKind.FULL, AttentionKind.LOCAL):
        y, update = attn_decode(p["mix"], h, cache, cfg, kind=kind)
    elif kind == AttentionKind.RECURRENT:
        y, update = rglru_decode(p["mix"], h, cache, cfg)
    else:
        y, update = rwkv_decode(p["mix"], h, cache, cfg)
    x1 = x1 + y
    h2 = norm_apply(p["norm_ffn"], x1, cfg)
    shifted_cm = None
    if kind == AttentionKind.WKV:
        shifted_cm = cache["shift_cm"]
        update = dict(update)
        update["shift_cm"] = h2[:, 0, :]
    if tag == "moe":
        f, _, _ = _ffn_forward(p, h2, cfg, rt, tag)
    else:
        sh = (shifted_cm[:, None, :].astype(h2.dtype)
              if cfg.ffn == FFNKind.RWKV_CHANNEL else None)
        f = ffn_apply(p["ffn"], h2, cfg, shifted=sh)
    return x1 + f, update


def _token_column_write(cache_arr, tok, slot, policy, cfg):
    """cache_arr (count,B,KV,size,D); tok (count,B,KV,1,D). When the cache
    sequence dim is sharded (small-KV flash-decoding layout), a dynamic
    DUS on that dim would make GSPMD all-gather the cache; instead each
    shard resolves the write locally inside shard_map."""
    zero = jnp.zeros((), jnp.int32)
    seq_sharded = (
        policy is not None
        and policy.mesh_axes_for("kv_heads", cfg.n_kv_heads) is None
        and policy.mesh_axes_for("kv_seq", cache_arr.shape[3]) is not None)
    if not seq_sharded:
        start = (zero, zero, zero, slot.astype(jnp.int32), zero)
        return jax.lax.dynamic_update_slice(cache_arr, tok, start)

    from jax.sharding import PartitionSpec as P
    mesh = policy.mesh
    b = cache_arr.shape[1]
    batch_ax = policy.mesh_axes_for("batch", b)
    seq_ax = policy.mesh_axes_for("kv_seq", cache_arr.shape[3])
    seq_name = seq_ax if isinstance(seq_ax, str) else seq_ax[0]
    cache_spec = P(None, batch_ax, None, seq_ax, None)
    tok_spec = P(None, batch_ax, None, None, None)

    def body(c, t, s):
        size_loc = c.shape[3]
        off = jax.lax.axis_index(seq_name) * size_loc
        loc = jnp.clip(s - off, 0, size_loc - 1)
        cur = jax.lax.dynamic_slice_in_dim(c, loc, 1, axis=3)
        hit = jnp.logical_and(s >= off, s < off + size_loc)
        val = jnp.where(hit, t.astype(c.dtype), cur)
        return jax.lax.dynamic_update_slice_in_dim(c, val, loc, axis=3)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(cache_spec, tok_spec, P()),
        out_specs=cache_spec, check_vma=False,
    )(cache_arr, tok, slot.astype(jnp.int32))


def _apply_cache_updates(spec: StackSpec, stack_cache, updates, cfg,
                         policy=None):
    """Merge per-layer scan updates back into the stacked caches with one
    token-column write per attention cache (write O(L*token), not
    O(L*cache))."""
    new_stack = {}
    for j, (kind, _) in enumerate(spec.unit):
        key = f"l{j}"
        cache = stack_cache[key]
        upd = updates[key]
        if kind in (AttentionKind.FULL, AttentionKind.LOCAL):
            size = cache["k"].shape[3]          # (count,B,KV,size,D)
            pos = cache["len"][0]               # equal across the stack
            slot = (pos % size) if kind == AttentionKind.LOCAL else pos
            new_entry = {
                "k": _token_column_write(cache["k"], upd["k_tok"], slot,
                                         policy, cfg),
                "v": _token_column_write(cache["v"], upd["v_tok"], slot,
                                         policy, cfg),
                "len": upd["len"],
            }
            if "k_scale" in cache:  # int8 cache: write the scale column
                new_entry["k_scale"] = _token_column_write(
                    cache["k_scale"], upd["k_scale_tok"], slot, policy,
                    cfg)
                new_entry["v_scale"] = _token_column_write(
                    cache["v_scale"], upd["v_scale_tok"], slot, policy,
                    cfg)
            new_stack[key] = new_entry
        else:
            new_stack[key] = upd                # full small state
    return new_stack


def prefill(params, cfg: ModelConfig, rt: Runtime, inputs,
            capacity: int = 0, last_pos=None
            ) -> Tuple[jnp.ndarray, List[Any]]:
    """Returns (last-position logits (B,1,V), caches).

    ``last_pos`` (traced scalar, optional) selects which position's
    logits to return instead of the final one — the serve engine
    right-pads prompts to a shape bucket so one prefill trace covers
    every prompt length in the bucket, and the real last prompt token
    sits at ``plen - 1``, not at the padded end."""
    x = embed_inputs(params, cfg, inputs, rt)
    caches = []
    for spec, stack_params in zip(build_stacks(cfg), params["stacks"]):
        unit_len = len(spec.unit)

        def unit_prefill(x, up, pos, _spec=spec, _ul=unit_len):
            ucache = {}
            for j, (kind, tag) in enumerate(_spec.unit):
                lidx = _spec.base + pos * _ul + j
                x, c = _layer_prefill(up[f"l{j}"], x, cfg, rt, kind, tag,
                                      lidx, capacity)
                ucache[f"l{j}"] = c
            return x, ucache

        def body(xc, xs, _up=unit_prefill):
            up, pos = xs
            xn, uc = _up(xc, up, pos)
            return xn, uc

        x, stack_cache = jax.lax.scan(
            body, x, (stack_params, jnp.arange(spec.count)))
        caches.append(stack_cache)
    x = norm_apply(params["final_norm"], x, cfg)
    if last_pos is not None:
        x_last = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(last_pos, jnp.int32), 1, axis=1)
    else:
        x_last = x[:, -1:, :]
    logits = unembed(params, cfg, x_last)
    return logits, caches


def decode_step(params, cfg: ModelConfig, rt: Runtime, inputs, caches
                ) -> Tuple[jnp.ndarray, List[Any]]:
    """One token for every sequence. inputs (B,1) tokens or (B,1,D)
    embeds. Returns (logits (B,1,V), new caches)."""
    x = embed_inputs(params, cfg, inputs, rt)
    new_caches = []
    for spec, stack_params, stack_cache in zip(
            build_stacks(cfg), params["stacks"], caches):

        def unit_decode(x, up, cache, _spec=spec):
            updates = {}
            for j, (kind, tag) in enumerate(_spec.unit):
                x, u = _layer_decode(up[f"l{j}"], x, cache[f"l{j}"], cfg,
                                     rt, kind, tag)
                updates[f"l{j}"] = u
            return x, updates

        def body(xc, xs, _ud=unit_decode):
            up, cache = xs
            xn, uc = _ud(xc, up, cache)
            return xn, uc

        # caches ride through xs READ-ONLY (no per-layer write-back);
        # the token column is written once below
        x, updates = jax.lax.scan(
            body, x, (stack_params, stack_cache))
        new_caches.append(
            _apply_cache_updates(spec, stack_cache, updates, cfg,
                                 rt.policy))
    x = norm_apply(params["final_norm"], x, cfg)
    logits = unembed(params, cfg, x)
    return logits, new_caches


# --------------------------------------------------------------------------
# paged decode (serve engine)
# --------------------------------------------------------------------------

def paged_supported_reason(cfg: ModelConfig) -> Optional[str]:
    """None when the paged decode path covers this arch, else why not.
    The serve engine admits dense full-attention token models: paging
    targets the O(S) KV state; recurrent/wkv layers keep O(1) state and
    LOCAL ring caches / MoE decode dispatch are not paged yet."""
    if cfg.frontend != "token":
        return f"frontend {cfg.frontend!r} is a stub (no token ids)"
    bad = {k.value for k in cfg.layer_kinds()
           if k != AttentionKind.FULL}
    if bad:
        return f"non-FULL layer kinds {sorted(bad)} not paged yet"
    if cfg.moe is not None:
        return "MoE decode dispatch not paged yet"
    return None


def paged_pools_init(cfg: ModelConfig, n_phys_slots: int, dtype
                     ) -> List[Dict[str, Dict[str, jnp.ndarray]]]:
    """Physical KV page pools, stacked to match params['stacks']: one
    (count, KV, n_phys_slots, head_dim) k/v pair per scanned attention
    layer. ``n_phys_slots`` = num_pages * page_size (+ scratch tail);
    all requests share the pool and address it through page tables."""
    reason = paged_supported_reason(cfg)
    assert reason is None, reason
    pools = []
    for spec in build_stacks(cfg):
        stack = {}
        for j, (kind, _) in enumerate(spec.unit):
            stack[f"l{j}"] = {
                "k": jnp.zeros((spec.count, cfg.n_kv_heads, n_phys_slots,
                                cfg.head_dim), dtype),
                "v": jnp.zeros((spec.count, cfg.n_kv_heads, n_phys_slots,
                                cfg.head_dim), dtype),
            }
        pools.append(stack)
    return pools


def decode_step_paged(params, cfg: ModelConfig, rt: Runtime, tokens,
                      pools, phys_idx, positions, keep_rows=None,
                      p_drop: float = 0.0):
    """G tokens for every request slot through the paged KV pools.

    tokens (B, G) ids; phys_idx (B, CAP) logical→physical map;
    positions (B, G) absolute positions. ``keep_rows`` — optional
    per-stack dict mirror of ``pools`` with (count, B, H, G, CAP) bool
    decode-dropout keep rows per layer (the serve engine slices them
    from cached packed mask planes; None = no decode-time dropout).

    One function serves plain decode (G=1), speculative DRAFT steps
    (G=1) and the speculative VERIFY pass (G=k): the verify replay
    guarantee — same masks, same code path — is structural, not a
    property the caller must re-establish.

    Returns (logits (B, G, V), updates) where updates mirrors ``pools``
    with the fresh (count, B, KV, G, hd) k/v columns; the engine writes
    them at the physical slots via ``paged_kv_write`` (pool writes stay
    O(tokens), outside the layer scan, like ``decode_step``)."""
    x = embed_inputs(params, cfg, tokens, rt)
    all_updates = []
    for spec, stack_params, stack_pools in zip(
            build_stacks(cfg), params["stacks"], pools):
        stack_keep = (keep_rows[len(all_updates)]
                      if keep_rows is not None else None)

        def unit_decode(x, up, pool, kr, _spec=spec):
            ups = {}
            for j, (kind, _tag) in enumerate(_spec.unit):
                lp = up[f"l{j}"]
                h = norm_apply(lp["norm_mix"], x, cfg)
                y, k_new, v_new = attn_decode_paged(
                    lp["mix"], h, cfg, pool[f"l{j}"]["k"],
                    pool[f"l{j}"]["v"], phys_idx, positions,
                    keep=None if kr is None else kr[f"l{j}"],
                    p_drop=p_drop)
                x = x + y
                h2 = norm_apply(lp["norm_ffn"], x, cfg)
                x = x + ffn_apply(lp["ffn"], h2, cfg)
                ups[f"l{j}"] = {"k": k_new, "v": v_new}
            return x, ups

        if stack_keep is None:
            def body(xc, xs, _ud=unit_decode):
                up, pool = xs
                return _ud(xc, up, pool, None)
            x, ups = jax.lax.scan(body, x, (stack_params, stack_pools))
        else:
            def body(xc, xs, _ud=unit_decode):
                up, pool, kr = xs
                return _ud(xc, up, pool, kr)
            x, ups = jax.lax.scan(
                body, x, (stack_params, stack_pools, stack_keep))
        all_updates.append(ups)
    x = norm_apply(params["final_norm"], x, cfg)
    return unembed(params, cfg, x), all_updates


def paged_kv_write(pools, updates, slots):
    """Write the fresh token columns into the physical pools at their
    per-token physical slots. slots (B, G) int32 — disjoint across
    active requests by construction (page tables never share pages);
    idle slots point into the scratch tail. One scatter per layer,
    O(B*G) traffic — the paged analogue of ``_apply_cache_updates``."""
    flat = slots.reshape(-1)
    new_pools = []
    for stack_pools, ups in zip(pools, updates):
        stack = {}
        for key, pool in stack_pools.items():
            u = ups[key]
            count, b, kv, g, hd = u["k"].shape
            vals_k = u["k"].transpose(0, 2, 1, 3, 4).reshape(
                count, kv, b * g, hd)
            vals_v = u["v"].transpose(0, 2, 1, 3, 4).reshape(
                count, kv, b * g, hd)
            stack[key] = {
                "k": pool["k"].at[:, :, flat, :].set(
                    vals_k.astype(pool["k"].dtype)),
                "v": pool["v"].at[:, :, flat, :].set(
                    vals_v.astype(pool["v"].dtype)),
            }
        new_pools.append(stack)
    return new_pools
