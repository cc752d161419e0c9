"""Flash-attention Pallas TPU kernel with the paper's three dropout modes.

    mode "none"    — no dropout.
    mode "fused"   — Philox RNG *inside* the attention kernel (the paper's
                     baseline, Fig. 4 top): RNG VPU work serializes against
                     the softmax VPU work, which is why its latency is
                     exposed on real hardware.
    mode "premask" — the paper's technique (Fig. 4 bottom): the kernel reads
                     precomputed packed keep-bits from HBM (produced by the
                     standalone philox kernel or the fused GEMM+RNG kernel)
                     and performs only the cheap element-dropping step
                     (~12% overhead in the paper's measurements).
    mode "replay"  — zero-HBM consumption (the cuDNN SDP seed+offset
                     design): the kernel re-derives each (bq, bk) tile's
                     keep bits in-register from the SAME position-based
                     Philox counters the producer was planned with. No
                     mask operand exists — the only dropout state is the
                     (4,) uint32 [key_lo, key_hi, salt, bh_offset] SMEM
                     operand (``philox_common.seed_salt_smem``), so seeds
                     may be traced and shard-local consumers replay
                     global-position counters via ``global_bh``. Unlike
                     "fused" (static literals, bits drawn under softmax
                     pressure), replay is the planned realization: bits
                     are bit-identical to the materialized premask plane
                     while the mask's q·k-scaling HBM traffic drops to 0.

Tiling: grid (B, H, SQ/bq, SK/bk), k-minor so the online-softmax running
stats (m, l, acc) live in VMEM scratch across the k sweep. Causal and
sliding-window blocks that are fully masked are skipped with pl.when,
and their index maps repeat the nearest running block, so a skipped
step fetches nothing. The tile is the caller's: the model path takes it
from ``core/producer.attn_flash_blocks``.
Dropout semantics match ref.attention_ref bit-exactly: softmax normalizer l
accumulates *undropped* probabilities; the keep-mask zeroes the numerator
contributions; the 1/(1-p) rescale is applied once at finalization.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.philox_common import (
    global_bh,
    philox4x32,
    seed_salt_smem,
    seed_to_key,
    threshold_from_p,
    tile_keep_mask,
    unpack_bits_q32,
)

_NEG_BIG = np.float32(-0.7 * np.finfo(np.float32).max)


def _flash_kernel(*refs, bq: int, bk: int, d: int, n_heads: int,
                  kv_heads: int, scale: float, causal: bool,
                  local_window: int, q_offset: int, mode: str,
                  threshold: int, inv_keep: float, salt: int,
                  k0: int, k1: int, rounds: int, out_dtype,
                  heads_global: int = 0, with_lse: bool = False):
    # in "replay" mode the mask_ref slot holds the (4,) uint32 SMEM
    # seed-salt operand instead of a packed-bit block
    lse_ref = None
    if mode in ("premask", "replay"):
        if with_lse:
            (q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr, l_scr,
             acc_scr) = refs
        else:
            q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr \
                = refs
    else:
        if with_lse:
            q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr \
                = refs
        else:
            q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs

    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk

    # Block-level skip for fully-masked tiles (causal / sliding window).
    run = jnp.bool_(True)
    if causal:
        # lowest q position in this tile (positions are kv-aligned)
        q_lo = q_start + q_offset
        q_hi = q_start + bq - 1 + q_offset
        run = jnp.logical_and(run, k_start <= q_hi)
        if local_window > 0:
            run = jnp.logical_and(run, k_start + bk - 1 > q_lo - local_window)

    @pl.when(run)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

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

        m_prev = m_scr[...]                           # (bq, 128)
        l_prev = l_scr[...]                           # (bq, 128)
        m_cur = jnp.max(s, axis=-1, keepdims=True)    # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)            # (bq, 128)
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # (bq, 1)
        p = jnp.exp(s - m_new[:, :1])                 # (bq, bk)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        l_scr[...] = l_new

        if mode == "fused":
            bh = b * n_heads + h
            keep = tile_keep_mask(q_start, k_start, bh, salt, k0, k1,
                                  threshold, bq, bk, rounds)
            p_acc = jnp.where(keep, p, 0.0)
        elif mode == "replay":
            bh = global_bh(b * n_heads + h, n_heads, heads_global,
                           mask_ref[3])
            keep = tile_keep_mask(q_start, k_start, bh, mask_ref[2],
                                  mask_ref[0], mask_ref[1], threshold,
                                  bq, bk, rounds)
            p_acc = jnp.where(keep, p, 0.0)
        elif mode == "premask":
            packed = mask_ref[0, 0, 0]                # (bq//32, bk)
            keep = unpack_bits_q32(packed, bq)
            p_acc = jnp.where(keep, p, 0.0)
        else:
            p_acc = p

        pv = jax.lax.dot_general(
            p_acc, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bq, d)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        out = acc_scr[...] / l * inv_keep
        o_ref[...] = out[None, None].astype(out_dtype)
        if lse_ref is not None:
            # the running stats are lane-broadcast (bq, 128) tiles; one
            # 2-D transpose turns them into the lane-dense (1, bq) row
            # the (B, H, 1, SQ) lse layout stores
            l_all = l_scr[...]
            lse = m_scr[...] + jnp.log(jnp.where(l_all == 0.0, 1.0, l_all))
            lse_ref[...] = lse.T[:1][None, None]


def run_kv_block(qi, ki, *, bq: int, bk: int, q_offset: int,
                 local_window: int, nk: int):
    """The key block grid step (qi, ki) of a causal grid reads: ki where
    the tile runs, else the nearest block that runs, so a skipped step
    repeats its neighbour's block index and starts no k/v DMA. Shared by
    the fwd and dq passes (grid (B, H, SQ/bq, SK/bk))."""
    last = jax.lax.div(qi * bq + (bq - 1 + q_offset), bk)
    ki = jnp.minimum(ki, jnp.minimum(last, nk - 1))
    if local_window > 0:
        first = jax.lax.div(
            jnp.maximum(qi * bq + (q_offset - local_window + 1), 0), bk)
        ki = jnp.maximum(ki, first)
    return ki


def run_q_block(ki, qi, *, bq: int, bk: int, q_offset: int,
                local_window: int, nq: int):
    """The query block grid step (ki, qi) of the dkv pass's causal grid
    (B, H, SK/bk, SQ/bq) reads, by the rule of ``run_kv_block``."""
    first = jax.lax.div(jnp.maximum(ki * bk - q_offset, 0), bq)
    qi = jnp.maximum(qi, jnp.minimum(first, nq - 1))
    if local_window > 0:
        last = jax.lax.div(
            jnp.maximum(ki * bk + (bk - 2 - q_offset + local_window), 0),
            bq)
        qi = jnp.minimum(qi, jnp.minimum(last, nq - 1))
    return qi


def _check_premask(mask_packed, batch, n_heads, sq, sk):
    """Fail fast on a mis-packed premask plane (the alternative is an
    opaque Pallas grid/BlockSpec error deep inside pallas_call)."""
    if mask_packed is None:
        raise ValueError("premask mode requires mask_packed")
    if sq % 32:
        raise ValueError(
            f"premask mode requires SQ % 32 == 0 (bit packing); got "
            f"SQ={sq}")
    expect = (batch, n_heads, sq // 32, sk)
    got = tuple(mask_packed.shape)
    if got != expect or mask_packed.dtype != jnp.uint32:
        raise ValueError(
            f"premask mask_packed must be (B, H, SQ//32, SK) uint32 = "
            f"{expect}, got shape {got} dtype {mask_packed.dtype} — "
            "pack with philox.philox_dropout_mask / "
            "dropout_rng.packed_mask")
    return mask_packed


def _check_replay_operand(seed_salt):
    """The replay-mode mask slot holds the (4,) uint32 seed-salt operand
    [key_lo, key_hi, salt, bh_offset] (philox_common.seed_salt_smem)."""
    if tuple(seed_salt.shape) != (4,) or seed_salt.dtype != jnp.uint32:
        raise ValueError(
            "replay mode takes the (4,) uint32 [key_lo, key_hi, salt, "
            "bh_offset] operand (philox_common.seed_salt_smem) in the "
            f"mask_packed slot, got shape {tuple(seed_salt.shape)} dtype "
            f"{seed_salt.dtype}")
    return seed_salt


def premask_blocks(mask_packed, bq: int):
    """(B, H, SQ//32, SK) packed plane viewed as (B, H, SQ//bq, bq//32,
    SK): a free row-major reshape whose trailing (bq//32, bk) block is
    legal for Mosaic at any bq. Shared by the fwd and both bwd passes."""
    b, h, sq32, sk = mask_packed.shape
    return mask_packed.reshape(b, h, sq32 * 32 // bq, bq // 32, sk)


def replay_keep_plane(seed_salt, batch: int, n_heads: int, sq: int,
                      sk: int, dropout_p: float, rounds: int = 7,
                      heads_global: int = 0) -> jnp.ndarray:
    """(B, H, SQ, SK) bool keep plane replayed from the (4,) seed-salt
    operand — the vectorized XLA mirror of the kernels' in-register tile
    derivation (bit-identical to unpacking the premask plane). Used by
    the reference backward and the replay-mode tests."""
    assert sq % 4 == 0
    hg = heads_global or n_heads
    thr = np.uint32(threshold_from_p(dropout_p))
    lb = jax.lax.broadcasted_iota(jnp.uint32, (batch * n_heads, 1, 1), 0)
    bh = global_bh(lb, n_heads, hg, seed_salt[3])
    q4 = jax.lax.broadcasted_iota(jnp.uint32, (1, sq // 4, 1), 1)
    kk = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, sk), 2)
    w = philox4x32(kk, q4, bh, seed_salt[2], seed_salt[0], seed_salt[1],
                   rounds)
    u = jnp.stack(w, axis=2).reshape(batch * n_heads, sq, sk)
    return (u >= thr).reshape(batch, n_heads, sq, sk)


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        mask_packed: Optional[jnp.ndarray] = None,
                        *, causal: bool = True, local_window: int = 0,
                        dropout_p: float = 0.0, mode: str = "none",
                        seed: int = 0, salt: int = 0, rounds: int = 7,
                        scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None,
                        heads_global: int = 0,
                        return_lse: bool = False):
    """Forward flash attention. q: (B,H,SQ,D); k,v: (B,KV,SK,D).

    mode "premask" requires mask_packed (B,H,SQ//32,SK) uint32 from the
    canonical counter scheme. mode "replay" takes the (4,) uint32
    seed-salt operand in the mask_packed slot (built from seed/salt when
    omitted); ``heads_global`` (0 = n_heads) makes a shard-local call
    replay global-position counters. ``return_lse`` adds the f32
    log-sum-exp of every score row, laid out lane-dense as (B,H,1,SQ).
    """
    batch, n_heads, sq, d = q.shape
    kv_heads, sk = k.shape[1], k.shape[2]
    assert n_heads % kv_heads == 0
    if mode == "none" or dropout_p == 0.0:
        mode = "none"
    if mode == "premask":
        mask_packed = _check_premask(mask_packed, batch, n_heads, sq, sk)
    elif mode == "replay":
        if mask_packed is None:
            mask_packed = seed_salt_smem(seed, salt)
        mask_packed = _check_replay_operand(mask_packed)
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    if mode == "premask":
        assert bq % 32 == 0
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    k0, k1 = seed_to_key(seed)
    grid = (batch, n_heads, sq // bq, sk // bk)
    group = n_heads // kv_heads
    if causal:
        kv_block = functools.partial(
            run_kv_block, bq=bq, bk=bk, q_offset=sk - sq,
            local_window=int(local_window), nk=sk // bk)
    else:
        kv_block = lambda qi, ki: ki

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d),
        lambda b, h, qi, ki: (b, h // group, kv_block(qi, ki), 0))
    o_spec = pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k, v]
    if mode == "premask":
        # a (bq//32, bk) slab of (SQ//32, SK) is not (8, 128)-aligned for
        # bq = 128; split SQ//32 into (SQ//bq, bq//32) so the block's
        # second-minor dim spans its whole array dim (same words)
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, bq // 32, bk),
            lambda b, h, qi, ki: (b, h, qi, 0, kv_block(qi, ki))))
        args.append(premask_blocks(mask_packed, bq))
    elif mode == "replay":
        # the whole dropout state: 16 bytes of SMEM, not a q*k plane
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(mask_packed)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, d=d, n_heads=n_heads,
        kv_heads=kv_heads, scale=float(scale), causal=causal,
        local_window=int(local_window), q_offset=sk - sq, mode=mode,
        threshold=threshold_from_p(dropout_p),
        inv_keep=float(1.0 / (1.0 - dropout_p)) if mode != "none" else 1.0,
        salt=salt, k0=k0, k1=k1, rounds=rounds, out_dtype=q.dtype,
        heads_global=heads_global or n_heads, with_lse=return_lse)

    out_specs = o_spec
    out_shape = jax.ShapeDtypeStruct((batch, n_heads, sq, d), q.dtype)
    if return_lse:
        out_specs = [o_spec,
                     pl.BlockSpec((1, 1, 1, bq),
                                  lambda b, h, qi, ki: (b, h, 0, qi))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((batch, n_heads, 1, sq),
                                          jnp.float32)]
    # the named_scope marks interpret-mode emulation loops so the
    # roofline analyzer charges this region by its call-boundary I/O
    # (= the kernel's true HBM traffic; tiles live in VMEM on TPU)
    with jax.named_scope("pallas_kernel_region"):
        return pl.pallas_call(
            kernel,
            name="flash_fwd",
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),   # running max m
                pltpu.VMEM((bq, 128), jnp.float32),   # running denom l
                pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
            ],
            interpret=resolve_interpret(interpret),
        )(*args)


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14))
def flash_attention(q, k, v, mask_packed=None, causal=True, local_window=0,
                    dropout_p=0.0, mode="none", seed=0, salt=0, rounds=7,
                    block_q=128, block_k=128, interpret=None,
                    heads_global=0):
    """Differentiable flash attention (forward = Pallas kernel; backward =
    the mathematically identical reference formulas, reusing the same
    Philox mask so gradients see the exact dropped elements). In
    "replay" mode the mask_packed slot carries the (4,) seed-salt
    operand (it must enter as data — nondiff_argnums can't hold traced
    seeds) and gets a float0 cotangent like the uint32 mask."""
    return flash_attention_fwd(
        q, k, v, mask_packed, causal=causal, local_window=local_window,
        dropout_p=dropout_p, mode=mode, seed=seed, salt=salt, rounds=rounds,
        block_q=block_q, block_k=block_k, interpret=interpret,
        heads_global=heads_global)


def _fa_fwd(q, k, v, mask_packed, causal, local_window, dropout_p, mode,
            seed, salt, rounds, block_q, block_k, interpret, heads_global):
    out = flash_attention_fwd(
        q, k, v, mask_packed, causal=causal, local_window=local_window,
        dropout_p=dropout_p, mode=mode, seed=seed, salt=salt, rounds=rounds,
        block_q=block_q, block_k=block_k, interpret=interpret,
        heads_global=heads_global)
    return out, (q, k, v, mask_packed)


def _zero_ct(x):
    """Cotangent for a non-float primal (the uint32 mask)."""
    if x is None:
        return None
    import numpy as _np
    return _np.zeros(x.shape, jax.dtypes.float0)


def _fa_bwd(causal, local_window, dropout_p, mode, seed, salt, rounds,
            block_q, block_k, interpret, heads_global, res, g):
    from repro.kernels import ref as _ref
    q, k, v, mask_packed = res
    eff_p = 0.0 if mode == "none" else dropout_p

    def f(q_, k_, v_):
        keep = None
        if eff_p > 0.0:
            if mode == "replay":
                keep = replay_keep_plane(
                    mask_packed, q_.shape[0], q_.shape[1], q_.shape[2],
                    k.shape[2], dropout_p, rounds, heads_global)
            elif mask_packed is not None:
                b, h, sq32, sk = mask_packed.shape
                keep = jax.vmap(jax.vmap(
                    lambda m: unpack_bits_q32(m, sq32 * 32)))(mask_packed)
            # else: ref regenerates from the canonical counters
        return _ref.attention_ref(
            q_, k_, v_, causal=causal, dropout_p=eff_p, dropout_seed=seed,
            dropout_salt=salt, philox_rounds=rounds, dropout_mask=keep,
            local_window=local_window)

    _, vjp = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, _zero_ct(mask_packed)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# Fully-Pallas differentiable attention (forward AND backward kernels).
# ---------------------------------------------------------------------------

@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14))
def flash_attention_mosaic(q, k, v, mask_packed=None, causal=True,
                           local_window=0, dropout_p=0.0, mode="none",
                           seed=0, salt=0, rounds=7, block_q=128,
                           block_k=128, interpret=None, heads_global=0):
    """Flash attention with Pallas forward *and* backward kernels —
    nothing O(SQ*SK) ever reaches HBM in either direction. In "premask"
    mode (the paper's overlap technique) the dropout bits come from HBM,
    so no RNG state enters the kernels and seeds may be traced values on
    the producer side. In "replay" mode even the bits stay out of HBM:
    fwd and both bwd kernels re-derive them from the (4,) seed-salt
    operand carried in the mask_packed slot (traced seeds enter as data;
    the operand gets a float0 cotangent)."""
    return flash_attention_fwd(
        q, k, v, mask_packed, causal=causal, local_window=local_window,
        dropout_p=dropout_p, mode=mode, seed=seed, salt=salt,
        rounds=rounds, block_q=block_q, block_k=block_k,
        interpret=interpret, heads_global=heads_global)


def _fam_fwd(q, k, v, mask_packed, causal, local_window, dropout_p, mode,
             seed, salt, rounds, block_q, block_k, interpret,
             heads_global):
    o, lse = flash_attention_fwd(
        q, k, v, mask_packed, causal=causal, local_window=local_window,
        dropout_p=dropout_p, mode=mode, seed=seed, salt=salt,
        rounds=rounds, block_q=block_q, block_k=block_k,
        interpret=interpret, heads_global=heads_global, return_lse=True)
    return o, (q, k, v, mask_packed, o, lse)


def _fam_bwd(causal, local_window, dropout_p, mode, seed, salt, rounds,
             block_q, block_k, interpret, heads_global, res, g):
    from repro.kernels.flash_attention_bwd import flash_attention_bwd
    q, k, v, mask_packed, o, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, o, lse, g, mask_packed, causal=causal,
        local_window=local_window, dropout_p=dropout_p, mode=mode,
        seed=seed, salt=salt, rounds=rounds, block_q=block_q,
        block_k=block_k, interpret=interpret, heads_global=heads_global)
    return dq, dk, dv, _zero_ct(mask_packed)


flash_attention_mosaic.defvjp(_fam_fwd, _fam_bwd)
