"""Flash-attention backward Pallas kernels (FA-2 style).

Two kernels over the recomputed score tiles (nothing O(SQ*SK) is ever
read from HBM — the paper's stored artifact stays 1 bit/element):

  dq pass : grid (B, H, q_blk, k_blk), accumulates dq in VMEM scratch;
  dkv pass: grid (B, H, k_blk, q_blk), accumulates dk/dv in VMEM scratch
            per q-head (GQA group-summed outside, an O(S*D) reduction).

Dropout follows the paper's semantics exactly: with keep-mask K and
P = softmax(S),  O = (K ∘ P / (1-p)) V, so

  dV = (K ∘ P / (1-p))^T dO
  dP = K/(1-p) ∘ (dO V^T)
  dS = P ∘ (dP - D),   D = rowsum(dO ∘ O) = rowsum(P ∘ dP)

The same Philox counters (premask bits or in-kernel regeneration) make
the gradients see exactly the dropped elements of the forward pass. In
"replay" mode there is no saved mask residual at all: both kernels
re-derive each tile's keep bits from the (4,) uint32 seed-salt SMEM
operand carried in the mask slot — identical counters to the forward
pass, zero mask HBM traffic in the backward re-read.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.flash_attention import (
    premask_blocks,
    run_kv_block,
    run_q_block,
)
from repro.kernels.philox_common import (
    global_bh,
    seed_salt_smem,
    seed_to_key,
    threshold_from_p,
    tile_keep_mask,
    unpack_bits_q32,
)

_NEG_BIG = np.float32(-0.7 * np.finfo(np.float32).max)


def _mask_and_p(s, lse_blk, q_start, k_start, bq, bk, causal,
                local_window, q_offset):
    if causal or local_window > 0:
        q_pos = (q_start + q_offset
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = jnp.bool_(True)
        if causal:
            valid = jnp.logical_and(valid, k_pos <= q_pos)
        if local_window > 0:
            valid = jnp.logical_and(valid, k_pos > q_pos - local_window)
        s = jnp.where(valid, s, _NEG_BIG)
    return jnp.exp(s - lse_blk)


def _row_to_col(row, bq):
    """(1, bq) lane-dense row -> (bq, 1) column: a sublane broadcast and
    a 2-D transpose, both of which Mosaic lowers."""
    return jnp.broadcast_to(row, (128, bq)).T[:, :1]


def _keep_tile(mode, mask_ref, q_start, k_start, bh, bq, bk, salt, k0, k1,
               threshold, rounds, heads_local=0, heads_global=0):
    if mode == "premask":
        return unpack_bits_q32(mask_ref[0, 0, 0], bq)
    if mode == "replay":
        # mask_ref is the (4,) uint32 [k0, k1, salt, bh_offset] SMEM
        # operand — replay the forward tile's counters in-register
        bh = global_bh(bh, heads_local, heads_global, mask_ref[3])
        return tile_keep_mask(q_start, k_start, bh, mask_ref[2],
                              mask_ref[0], mask_ref[1], threshold, bq, bk,
                              rounds)
    return tile_keep_mask(q_start, k_start, bh, salt, k0, k1, threshold,
                          bq, bk, rounds)


def _dq_kernel(*refs, bq, bk, scale, causal, local_window, q_offset,
               mode, threshold, inv_keep, salt, k0, k1, rounds,
               out_dtype, heads_local=0, heads_global=0):
    if mode in ("premask", "replay"):
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
         acc) = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    q_start, k_start = qi * bq, ki * bk
    run = jnp.bool_(True)
    if causal:
        q_hi = q_start + bq - 1 + q_offset
        run = jnp.logical_and(run, k_start <= q_hi)
        if local_window > 0:
            run = jnp.logical_and(
                run, k_start + bk - 1 > q_start + q_offset - local_window)

    @pl.when(run)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = _row_to_col(lse_ref[0, 0], bq)
        delta = _row_to_col(delta_ref[0, 0], bq)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = _mask_and_p(s, lse, q_start, k_start, bq, bk, causal,
                        local_window, q_offset)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if mode != "none":
            keep = _keep_tile(mode,
                              refs[6] if mode != "fused" else None,
                              q_start, k_start,
                              b * pl.num_programs(1) + h, bq, bk, salt,
                              k0, k1, threshold, rounds,
                              heads_local, heads_global)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = p * (dp - delta)
        acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _flush():
        dq_ref[...] = acc[...][None, None].astype(out_dtype)


def _dkv_kernel(*refs, bq, bk, scale, causal, local_window, q_offset,
                mode, threshold, inv_keep, salt, k0, k1, rounds,
                out_dtype, heads_local=0, heads_global=0):
    if mode in ("premask", "replay"):
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dk_ref, dv_ref, acck, accv) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
         dv_ref, acck, accv) = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        acck[...] = jnp.zeros_like(acck)
        accv[...] = jnp.zeros_like(accv)

    q_start, k_start = qi * bq, ki * bk
    run = jnp.bool_(True)
    if causal:
        q_hi = q_start + bq - 1 + q_offset
        run = jnp.logical_and(run, k_start <= q_hi)
        if local_window > 0:
            run = jnp.logical_and(
                run, k_start + bk - 1 > q_start + q_offset - local_window)

    @pl.when(run)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = _row_to_col(lse_ref[0, 0], bq)
        delta = _row_to_col(delta_ref[0, 0], bq)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = _mask_and_p(s, lse, q_start, k_start, bq, bk, causal,
                        local_window, q_offset)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if mode != "none":
            keep = _keep_tile(mode,
                              refs[6] if mode != "fused" else None,
                              q_start, k_start,
                              b * pl.num_programs(1) + h, bq, bk, salt,
                              k0, k1, threshold, rounds,
                              heads_local, heads_global)
            p_drop = jnp.where(keep, p * inv_keep, 0.0)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        else:
            p_drop = p
        # dv += P_drop^T dO ; dk += dS^T q
        accv[...] += jax.lax.dot_general(
            p_drop, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acck[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[...] = acck[...][None, None].astype(out_dtype)
        dv_ref[...] = accv[...][None, None].astype(out_dtype)


def flash_attention_bwd(q, k, v, o, lse, do,
                        mask_packed: Optional[jnp.ndarray] = None, *,
                        causal=True, local_window=0, dropout_p=0.0,
                        mode="none", seed=0, salt=0, rounds=7,
                        scale=None, block_q=128, block_k=128,
                        interpret=None,
                        heads_global=0) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                 jnp.ndarray]:
    """Returns (dq, dk, dv). k/v gradients are computed per q-head and
    group-summed for GQA outside the kernel. In "replay" mode
    ``mask_packed`` carries the (4,) uint32 seed-salt operand (built from
    seed/salt when omitted) and both passes re-derive the forward keep
    bits from counters — no mask plane is read."""
    batch, n_heads, sq, d = q.shape
    kv_heads, sk = k.shape[1], k.shape[2]
    group = n_heads // kv_heads
    if mode == "none" or dropout_p == 0.0:
        mode = "none"
    if mode == "replay" and mask_packed is None:
        mask_packed = seed_salt_smem(seed, salt)
    bq, bk = min(block_q, sq), min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    k0, k1 = seed_to_key(seed)
    common = dict(bq=bq, bk=bk, scale=float(scale), causal=causal,
                  local_window=int(local_window), q_offset=sk - sq,
                  mode=mode, threshold=threshold_from_p(dropout_p),
                  inv_keep=float(1.0 / (1.0 - dropout_p))
                  if mode != "none" else 1.0,
                  salt=salt, k0=k0, k1=k1, rounds=rounds, out_dtype=q.dtype,
                  heads_local=n_heads,
                  heads_global=heads_global or n_heads)

    # per-row stats ride lane-dense as (B, H, 1, SQ), like the fwd lse
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    interpret = resolve_interpret(interpret)
    if mode == "premask":
        mask_packed = premask_blocks(mask_packed, bq)

    # a skipped causal tile reads the block its nearest running
    # neighbour reads, so it starts no DMA (dq: k/v, dkv: q-side rows)
    if causal:
        clamp = dict(bq=bq, bk=bk, q_offset=sk - sq,
                     local_window=int(local_window))
        kblk = functools.partial(run_kv_block, nk=sk // bk, **clamp)
        qblk = functools.partial(run_q_block, nq=sq // bq, **clamp)
    else:
        kblk = qblk = lambda i, j: j
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0))
    kq_spec = pl.BlockSpec((1, 1, bq, d),
                           lambda b, h, i, j: (b, h, qblk(i, j), 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda b, h, i, j: (b, h // group, kblk(i, j), 0))
    kvk_spec = pl.BlockSpec((1, 1, bk, d),
                            lambda b, h, i, j: (b, h // group, i, 0))
    row_spec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i))
    rowq_spec = pl.BlockSpec((1, 1, 1, bq),
                             lambda b, h, i, j: (b, h, 0, qblk(i, j)))
    mask_spec = pl.BlockSpec((1, 1, 1, bq // 32, bk),
                             lambda b, h, i, j: (b, h, i, 0, kblk(i, j)))
    maskk_spec = pl.BlockSpec((1, 1, 1, bq // 32, bk),
                              lambda b, h, i, j: (b, h, qblk(i, j), 0, i))

    # ---- dq pass: grid (B, H, nq, nk) --------------------------------
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    args = [q, k, v, do, lse, delta]
    if mode == "premask":
        in_specs.append(mask_spec)
        args.append(mask_packed)
    elif mode == "replay":
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(mask_packed)
    with jax.named_scope("pallas_kernel_region"):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **common),
            name="flash_bwd_dq",
            grid=(batch, n_heads, sq // bq, sk // bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda b, h, i, j: (b, h, i, 0)),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
        )(*args)

    # ---- dkv pass: grid (B, H, nk, nq) -------------------------------
    in_specs = [kq_spec, kvk_spec, kvk_spec, kq_spec, rowq_spec,
                rowq_spec]
    args = [q, k, v, do, lse, delta]
    if mode == "premask":
        in_specs.append(maskk_spec)
        args.append(mask_packed)
    elif mode == "replay":
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(mask_packed)
    with jax.named_scope("pallas_kernel_region"):
        dk_h, dv_h = pl.pallas_call(
            functools.partial(_dkv_kernel, **common),
            name="flash_bwd_dkv",
            grid=(batch, n_heads, sk // bk, sq // bq),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bk, d),
                             lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b, h, i, j: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((batch, n_heads, sk, d), q.dtype),
                jax.ShapeDtypeStruct((batch, n_heads, sk, d), q.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)],
            interpret=interpret,
        )(*args)
    if group > 1:  # GQA: sum q-head gradients within each kv group
        dk = dk_h.reshape(batch, kv_heads, group, sk, d).sum(axis=2)
        dv = dv_h.reshape(batch, kv_heads, group, sk, d).sum(axis=2)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)
