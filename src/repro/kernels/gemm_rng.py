"""Fused GEMM + dropout-RNG Pallas TPU kernel — the paper's overlap,
TPU-native.

The paper runs a standalone RNG kernel on a second CUDA stream, concurrent
with the QKV GEMM, exploiting disjoint bottlenecks (GEMM: MMA math; RNG:
issue/ALU). TPUs have no streams; the equivalent concurrency lives *inside*
a kernel: the MXU executes the matmul dots while the VPU — an independent
unit — executes the Philox chain. Mosaic's scheduler interleaves the two
instruction streams per grid step, hiding the RNG latency under the MXU
work exactly as the paper hides it under SM tensor pipes.

Work assignment: the packed mask (flattened 2D layout (BH*SQ32, SK), row-
padded) is partitioned into (rb x ck) blocks; block s is produced by the
s-th (i, j) GEMM tile at its k==0 step (the mask buffer stays resident
across the k sweep, so the single write is flushed exactly once, when the
(i, j) tile retires). GEMM steps beyond the number of mask blocks write a
dummy trailing block that is sliced off. If the GEMM grid is too *small*
to host the mask work within the VMEM row budget, the caller falls back to
the standalone philox kernel — the paper's Region 3 (RNG runtime exceeds
GEMM; the remainder runs exposed).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quant
from repro.kernels.backend import resolve_interpret
from repro.kernels.philox_common import (
    packed_rows_tile,
    seed_salt_smem,
    threshold_from_p,
)


def _mask_layout(n_steps: int, mask_batch: int, mask_heads: int,
                 sq32: int, mask_sk: int, mask_block_cols: int,
                 max_mask_rows_per_block: int):
    """Partition of the flattened packed mask (BH*SQ32, SK) over GEMM grid
    steps. Returns (ck, n_cb, rb, n_rb_valid, n_valid_blocks,
    mask_rows_alloc), or None when the GEMM grid cannot host the mask
    within the row budget (the paper's Region 3). Shared by the f32/bf16
    and fp8 fused kernels so both hosts produce the identical layout."""
    mr = mask_batch * mask_heads * sq32          # valid packed rows
    ck = min(mask_block_cols, mask_sk)
    assert mask_sk % ck == 0
    n_cb = mask_sk // ck
    rows_per_block = max(1, n_steps // n_cb)
    rb = -(-mr // rows_per_block)                # ceil
    rb = -(-rb // 8) * 8                         # sublane multiple
    n_rb_valid = -(-mr // rb)
    n_valid_blocks = n_rb_valid * n_cb
    if rb > max_mask_rows_per_block or n_valid_blocks > n_steps:
        return None
    mask_rows_alloc = (n_rb_valid + 1) * rb      # +1 dummy overflow block
    return ck, n_cb, rb, n_rb_valid, n_valid_blocks, mask_rows_alloc


@dataclasses.dataclass(frozen=True)
class MaskEmissionLayout:
    """Static description of WHICH packed-mask rectangle each GEMM grid
    step emits — the counter-layout metadata of the fused kernels,
    exposed so repro.analysis can prove coverage/disjointness without
    re-deriving (or executing) the kernel's work assignment.

    The flattened local mask plane is (rows_valid, sk) packed words
    (rows_valid = B_loc * H_loc * SQ//32). ``blocks()`` yields one
    half-open rectangle per mask-producing grid step; steps beyond
    ``n_valid_blocks`` write only the dummy overflow block that the
    caller slices off (not yielded — it holds no consumed bits)."""
    n_steps: int
    rows_valid: int
    sk: int
    rb: int                 # rows per block (sublane-padded)
    ck: int                 # cols per block
    n_cb: int               # column blocks per row band
    n_rb_valid: int         # valid row bands
    n_valid_blocks: int
    rows_alloc: int         # incl. the dummy overflow band

    def blocks(self):
        """Yield (step, r0, r1, c0, c1) — rows [r0, r1) x cols [c0, c1)
        of the local plane written by GEMM step ``step`` (mirrors
        ``_mask_block_idx``). The last row band is clipped to
        rows_valid, exactly as consumers slice the padded buffer."""
        for s in range(self.n_valid_blocks):
            rb_idx, cb_idx = s // self.n_cb, s % self.n_cb
            r0 = rb_idx * self.rb
            r1 = min(r0 + self.rb, self.rows_valid)
            c0 = cb_idx * self.ck
            yield s, r0, r1, c0, c0 + self.ck


def mask_emission_layout(n_steps: int, mask_batch: int, mask_heads: int,
                         sq: int, mask_sk: int,
                         mask_block_cols: int = 2048,
                         max_mask_rows_per_block: int = 256
                         ) -> Optional[MaskEmissionLayout]:
    """Public form of ``_mask_layout``: the emission layout a fused host
    with ``n_steps`` grid steps would use for a (mask_batch, mask_heads,
    sq, mask_sk) mask, or None in the paper's Region 3."""
    lay = _mask_layout(n_steps, mask_batch, mask_heads, sq // 32,
                       mask_sk, mask_block_cols, max_mask_rows_per_block)
    if lay is None:
        return None
    ck, n_cb, rb, n_rb_valid, n_valid_blocks, rows_alloc = lay
    return MaskEmissionLayout(
        n_steps=n_steps,
        rows_valid=mask_batch * mask_heads * (sq // 32), sk=mask_sk,
        rb=rb, ck=ck, n_cb=n_cb, n_rb_valid=n_rb_valid,
        n_valid_blocks=n_valid_blocks, rows_alloc=rows_alloc)


def mask_layout_feasible(n_steps: int, mask_batch: int, mask_heads: int,
                         sq: int, mask_sk: int,
                         mask_block_cols: int = 2048,
                         max_mask_rows_per_block: int = 256) -> bool:
    """True when a GEMM grid of ``n_steps`` (i, j) tiles can host the
    (mask_batch, mask_heads, sq, mask_sk) mask — i.e. NOT the paper's
    Region 3. The exact predicate the fused kernels apply at trace time,
    exposed so core/schedule.py can plan the Region-3 fallback ahead of
    trace instead of discovering it mid-scan."""
    return _mask_layout(n_steps, mask_batch, mask_heads, sq // 32,
                        mask_sk, mask_block_cols,
                        max_mask_rows_per_block) is not None


def _mask_block_idx(s, n_valid_blocks: int, n_cb: int, n_rb_valid: int):
    """Block coords for GEMM step s: valid steps get their own block;
    overflow steps share the dummy trailing row-block."""
    over = s >= n_valid_blocks
    rb_idx = jnp.where(over, n_rb_valid, s // n_cb)
    cb_idx = jnp.where(over, 0, s % n_cb)
    return rb_idx, cb_idx


# A mask block is emitted in (8, 512)-word chunks — the standalone
# kernel's block shape — so the Philox temporaries, (64, 512) uint32
# arrays, stay a few hundred KiB of VMEM whatever (rb, ck) the layout
# picked. Each chunk's words depend only on its global position.
_EMIT_ROWS = 8
_EMIT_COLS = 512


def _emit_mask_block(m_ref, s_ref, r_start, c_start, *, rb: int, ck: int,
                     sq32: int, threshold: int, rounds: int,
                     heads_local: int, heads_global: int):
    """Write the (rb, ck) packed-mask block whose top-left word is global
    packed row ``r_start``, column ``c_start`` into ``m_ref``."""
    cc = _EMIT_COLS if ck % _EMIT_COLS == 0 else ck
    n_c = ck // cc

    def chunk(t, carry):
        r = (t // n_c) * _EMIT_ROWS
        c = (t % n_c) * cc
        m_ref[pl.ds(r, _EMIT_ROWS), pl.ds(c, cc)] = packed_rows_tile(
            r_start + r, c_start + c, sq32, s_ref[2], s_ref[0], s_ref[1],
            threshold, _EMIT_ROWS, cc, rounds, heads_local=heads_local,
            heads_global=heads_global, bh_offset=s_ref[3])
        return carry

    jax.lax.fori_loop(0, (rb // _EMIT_ROWS) * n_c, chunk, 0)


def _gemm_rng_kernel(s_ref, a_ref, b_ref, c_ref, m_ref, acc_scr, *,
                     n_cb: int, rb: int, ck: int, sq32: int,
                     threshold: int, rounds: int,
                     n_valid_blocks: int, n_rb_valid: int, out_dtype,
                     heads_local: int, heads_global: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kk = pl.program_id(2)
    nk = pl.num_programs(2)
    gn = pl.num_programs(1)

    @pl.when(kk == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # --- MXU stream: tiled matmul accumulation --------------------------
    acc_scr[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # --- VPU stream: Philox mask chunk (no MXU op in this path) ---------
    @pl.when(kk == 0)
    def _rng():
        s = i * gn + j
        rb_idx, cb_idx = _mask_block_idx(s, n_valid_blocks, n_cb,
                                         n_rb_valid)
        _emit_mask_block(m_ref, s_ref, rb_idx * rb, cb_idx * ck, rb=rb,
                         ck=ck, sq32=sq32, threshold=threshold,
                         rounds=rounds, heads_local=heads_local,
                         heads_global=heads_global)

    @pl.when(kk == nk - 1)
    def _flush():
        c_ref[...] = acc_scr[...].astype(out_dtype)


def gemm_with_rng(a: jnp.ndarray, b: jnp.ndarray, *,
                  mask_batch: int, mask_heads: int, mask_sq: int,
                  mask_sk: int, p: float, seed: int, salt: int = 0,
                  rounds: int = 7,
                  block_m: int = 256, block_n: int = 256,
                  block_k: int = 512, mask_block_cols: int = 2048,
                  max_mask_rows_per_block: int = 256,
                  interpret: Optional[bool] = None,
                  heads_global: int = 0, bh_offset=0,
                  ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """C = a @ b, plus the packed dropout keep-mask (B, H, SQ//32, SK)
    generated under the GEMM. Returns (C, mask) — mask is None when the
    GEMM grid cannot host the mask work (caller falls back to the
    standalone kernel; the paper's Region 3). ``seed``/``salt`` may be
    python ints or traced uint32 scalars (the training path folds the
    step/layer in); they ride into the kernel as a (4,) SMEM operand.
    ``heads_global``/``bh_offset`` (see philox_common.global_bh) make the
    call shard-local: the mask is the (mask_batch, mask_heads) tile of
    the global plane starting at flattened (b*H + h) = bh_offset.
    """
    m, kdim = a.shape
    k2, n = b.shape
    assert kdim == k2
    interpret = resolve_interpret(interpret)
    bm, bn, bkk = min(block_m, m), min(block_n, n), min(block_k, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bkk == 0
    gm, gn, gk = m // bm, n // bn, kdim // bkk
    n_steps = gm * gn

    assert mask_sq % 32 == 0
    sq32 = mask_sq // 32
    layout = _mask_layout(n_steps, mask_batch, mask_heads, sq32, mask_sk,
                          mask_block_cols, max_mask_rows_per_block)
    if layout is None:
        # GEMM too small to hide this much RNG (paper Region 3): bail out.
        return _plain_gemm(a, b, bm, bn, bkk, interpret), None
    ck, n_cb, rb, n_rb_valid, n_valid_blocks, mask_rows_alloc = layout

    static = (gm, gn, gk, bm, bn, bkk, n_cb, rb, ck, sq32,
              threshold_from_p(p), rounds, n_valid_blocks, n_rb_valid,
              mask_rows_alloc, mask_sk, interpret,
              mask_batch, mask_heads, heads_global or mask_heads)
    return _gemm_rng_call(static,
                          seed_salt_smem(seed, salt, bh_offset), a, b)


def _gemm_rng_impl(static, sd, a, b):
    (gm, gn, gk, bm, bn, bkk, n_cb, rb, ck, sq32, threshold, rounds,
     n_valid_blocks, n_rb_valid, mask_rows_alloc, mask_sk,
     interpret, mask_batch, mask_heads, heads_global) = static
    m, n = a.shape[0], b.shape[1]
    kernel = functools.partial(
        _gemm_rng_kernel, n_cb=n_cb, rb=rb, ck=ck, sq32=sq32,
        threshold=threshold, rounds=rounds,
        n_valid_blocks=n_valid_blocks, n_rb_valid=n_rb_valid,
        out_dtype=a.dtype, heads_local=mask_heads,
        heads_global=heads_global)

    def _mask_index_map(i, j, kk, _gn=gn):
        rb_idx, cb_idx = _mask_block_idx(i * _gn + j, n_valid_blocks,
                                         n_cb, n_rb_valid)
        return rb_idx, cb_idx

    c, mask2d = pl.pallas_call(
        kernel,
        name="gemm_rng",
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bkk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((rb, ck), _mask_index_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), a.dtype),
            jax.ShapeDtypeStruct((mask_rows_alloc, mask_sk), jnp.uint32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(sd, a, b)
    mr = mask_batch * mask_heads * sq32
    # the dummy-row slice lives INSIDE the custom_vjp so AD never has to
    # transpose a slice of the integer mask (float0 cotangents)
    return c, mask2d[:mr].reshape(mask_batch, mask_heads, sq32, mask_sk)


# The training path differentiates through the fused projection GEMM.
# Only the FORWARD GEMM hosts RNG (the backward regenerates nothing — it
# consumes the stored 1-bit mask), so the bwd is the textbook pair of
# dgrad GEMMs as XLA dots; the integer outputs/inputs (mask, seed) carry
# float0 cotangents.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gemm_rng_call(static, sd, a, b):
    return _gemm_rng_impl(static, sd, a, b)


def _gemm_rng_fwd(static, sd, a, b):
    return _gemm_rng_impl(static, sd, a, b), (a, b)


def _dgrad_pair(a, b, dc):
    """Textbook GEMM backward in f32: (dA, dB) from dC."""
    dcf = dc.astype(jnp.float32)
    da = (dcf @ b.astype(jnp.float32).T).astype(a.dtype)
    db = (a.astype(jnp.float32).T @ dcf).astype(b.dtype)
    return da, db


def _gemm_rng_bwd(static, res, cts):
    a, b = res
    da, db = _dgrad_pair(a, b, cts[0])
    dsd = np.zeros((4,), jax.dtypes.float0)
    return dsd, da, db


_gemm_rng_call.defvjp(_gemm_rng_fwd, _gemm_rng_bwd)


def _plain_gemm_impl(a, b, static):
    bm, bn, bkk, interpret = static
    m, kdim = a.shape
    _, n = b.shape

    def kern(a_ref, b_ref, c_ref, acc_scr):
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _zero():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        acc_scr[...] += jax.lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(kk == pl.num_programs(2) - 1)
        def _flush():
            c_ref[...] = acc_scr[...].astype(a.dtype)

    return pl.pallas_call(
        kern,
        name="gemm",
        grid=(m // bm, n // bn, kdim // bkk),
        in_specs=[
            pl.BlockSpec((bm, bkk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _plain_gemm_call(a, b, static):
    return _plain_gemm_impl(a, b, static)


def _plain_gemm_fwd(a, b, static):
    return _plain_gemm_impl(a, b, static), (a, b)


def _plain_gemm_bwd(static, res, dc):
    a, b = res
    return _dgrad_pair(a, b, dc)


_plain_gemm_call.defvjp(_plain_gemm_fwd, _plain_gemm_bwd)


def _plain_gemm(a, b, bm, bn, bkk, interpret):
    """Tiled matmul without the RNG side-channel (fallback / baseline)."""
    return _plain_gemm_call(a, b, (bm, bn, bkk, interpret))


# --------------------------------------------------------------------------
# fp8(e4m3) operand path with per-tile scales
# --------------------------------------------------------------------------
#
# The paper's measured regime: the producer GEMM runs on quantized e4m3
# operands (the serving precision on GH100) while the VPU still hides the
# Philox chain in its shadow. Operands are quantized per GEMM tile — A per
# (block_m, block_k), B per (block_k, block_n) — so every grid step reads
# ONE scalar scale per operand from SMEM and rescales its f32 partial
# product: acc += dot(a_q, b_q) * (a_scale[i,k] * b_scale[k,j]). The mask
# work assignment is byte-for-byte the layout of the f32 kernel
# (_mask_layout), keeping the counter-based bits identical across hosting
# dtypes — determinism survives the re-scheduling (DASH, 2026).
#
# Gradients: quantization is straight-through (the residual stores the
# UNQUANTIZED operands) and the dgrad pair runs in bf16 — the paper's
# training arrangement, where only the forward GEMM is fp8.

def _gemm_rng_fp8_kernel(s_ref, as_ref, bs_ref, a_ref, b_ref, c_ref,
                         m_ref, acc_scr, *, n_cb: int, rb: int, ck: int,
                         sq32: int, threshold: int, rounds: int,
                         n_valid_blocks: int, n_rb_valid: int, out_dtype,
                         heads_local: int, heads_global: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kk = pl.program_id(2)
    nk = pl.num_programs(2)
    gn = pl.num_programs(1)

    @pl.when(kk == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # --- MXU stream: e4m3 tile product, per-tile rescale on the f32 acc
    prod = jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_scr[...] += prod * (as_ref[i, kk] * bs_ref[kk, j])

    # --- VPU stream: identical mask assignment to the f32 kernel --------
    @pl.when(kk == 0)
    def _rng():
        s = i * gn + j
        rb_idx, cb_idx = _mask_block_idx(s, n_valid_blocks, n_cb,
                                         n_rb_valid)
        _emit_mask_block(m_ref, s_ref, rb_idx * rb, cb_idx * ck, rb=rb,
                         ck=ck, sq32=sq32, threshold=threshold,
                         rounds=rounds, heads_local=heads_local,
                         heads_global=heads_global)

    @pl.when(kk == nk - 1)
    def _flush():
        c_ref[...] = acc_scr[...].astype(out_dtype)


def gemm_with_rng_fp8(a: jnp.ndarray, b: jnp.ndarray, *,
                      mask_batch: int, mask_heads: int, mask_sq: int,
                      mask_sk: int, p: float, seed: int, salt: int = 0,
                      rounds: int = 7,
                      block_m: int = 256, block_n: int = 256,
                      block_k: int = 512, mask_block_cols: int = 2048,
                      max_mask_rows_per_block: int = 256,
                      interpret: Optional[bool] = None,
                      heads_global: int = 0, bh_offset=0,
                      ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """C ~= a @ b computed on per-tile-scaled e4m3 operands, plus the
    packed dropout keep-mask generated under the GEMM. The mask is
    bit-identical to the f32 host's (same _mask_layout, same counters);
    C matches the f32 GEMM within the documented e4m3 per-tile-scale
    error bound (see kernels/quant.py). Returns (C, mask) — mask is None
    in the paper's Region 3 (grid too small; caller falls back to the
    standalone kernel). Differentiable: straight-through quantization
    with a bf16 dgrad pair."""
    if not quant.have_fp8():
        raise NotImplementedError(
            "fp8 path requires jnp.float8_e4m3fn; gate on "
            "quant.have_fp8()")
    m, kdim = a.shape
    k2, n = b.shape
    assert kdim == k2
    interpret = resolve_interpret(interpret)
    bm, bn, bkk = min(block_m, m), min(block_n, n), min(block_k, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bkk == 0
    gm, gn, gk = m // bm, n // bn, kdim // bkk
    n_steps = gm * gn

    assert mask_sq % 32 == 0
    sq32 = mask_sq // 32
    layout = _mask_layout(n_steps, mask_batch, mask_heads, sq32, mask_sk,
                          mask_block_cols, max_mask_rows_per_block)
    if layout is None:
        # Region 3: still run the quantized GEMM, just without the mask.
        return _plain_gemm_fp8_call(a, b, (bm, bn, bkk, interpret)), None
    ck, n_cb, rb, n_rb_valid, n_valid_blocks, mask_rows_alloc = layout

    static = (gm, gn, gk, bm, bn, bkk, n_cb, rb, ck, sq32,
              threshold_from_p(p), rounds, n_valid_blocks, n_rb_valid,
              mask_rows_alloc, mask_sk, interpret,
              mask_batch, mask_heads, heads_global or mask_heads)
    return _gemm_rng_fp8_call(static,
                              seed_salt_smem(seed, salt, bh_offset), a, b)


def _gemm_rng_fp8_impl(static, sd, a, b):
    (gm, gn, gk, bm, bn, bkk, n_cb, rb, ck, sq32, threshold, rounds,
     n_valid_blocks, n_rb_valid, mask_rows_alloc, mask_sk,
     interpret, mask_batch, mask_heads, heads_global) = static
    m, n = a.shape[0], b.shape[1]
    a_q, a_s = quant.quantize_tiled(a, bm, bkk)      # scales (gm, gk)
    b_q, b_s = quant.quantize_tiled(b, bkk, bn)      # scales (gk, gn)
    kernel = functools.partial(
        _gemm_rng_fp8_kernel, n_cb=n_cb, rb=rb, ck=ck, sq32=sq32,
        threshold=threshold, rounds=rounds,
        n_valid_blocks=n_valid_blocks, n_rb_valid=n_rb_valid,
        out_dtype=a.dtype, heads_local=mask_heads,
        heads_global=heads_global)

    def _mask_index_map(i, j, kk, _gn=gn):
        rb_idx, cb_idx = _mask_block_idx(i * _gn + j, n_valid_blocks,
                                         n_cb, n_rb_valid)
        return rb_idx, cb_idx

    c, mask2d = pl.pallas_call(
        kernel,
        name="gemm_rng_fp8",
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bkk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((rb, ck), _mask_index_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), a.dtype),
            jax.ShapeDtypeStruct((mask_rows_alloc, mask_sk), jnp.uint32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(sd, a_s, b_s, a_q, b_q)
    mr = mask_batch * mask_heads * sq32
    return c, mask2d[:mr].reshape(mask_batch, mask_heads, sq32, mask_sk)


def _dgrad_pair_bf16(a, b, dc):
    """bf16 dgrad pair for the fp8 forward: quantization is straight-
    through (grads w.r.t. the unquantized operands), accumulation f32."""
    dcb = dc.astype(jnp.bfloat16)
    da = jax.lax.dot_general(
        dcb, b.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(a.dtype)
    db = jax.lax.dot_general(
        a.astype(jnp.bfloat16), dcb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(b.dtype)
    return da, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gemm_rng_fp8_call(static, sd, a, b):
    return _gemm_rng_fp8_impl(static, sd, a, b)


def _gemm_rng_fp8_fwd(static, sd, a, b):
    return _gemm_rng_fp8_impl(static, sd, a, b), (a, b)


def _gemm_rng_fp8_bwd(static, res, cts):
    a, b = res
    da, db = _dgrad_pair_bf16(a, b, cts[0])
    dsd = np.zeros((4,), jax.dtypes.float0)
    return dsd, da, db


_gemm_rng_fp8_call.defvjp(_gemm_rng_fp8_fwd, _gemm_rng_fp8_bwd)


# --------------------------------------------------------------------------
# grouped (expert) GEMM host: GEMM grid decoupled from the RNG emission grid
# --------------------------------------------------------------------------
#
# MoE expert FFNs compute C[e] = A[e] @ B[e] over E experts — an einsum
# whose row space is the PERMUTED, capacity-dropped token layout of the
# dispatch, not the token order the dense hosts assume. The paper's claim
# survives anyway: RNG emission never needs to know which token a GEMM
# tile is computing, because the mask is indexed by (b, h, q, k) Philox
# counters (philox_common.global_bh), not by token identity. So the
# grouped kernel walks mask tiles round-robin across expert tiles: GEMM
# grid step s = (e * gm + i) * gn + j hosts mask block s of the same
# flattened (BH*SQ32, SK) layout the dense hosts use (_mask_layout) —
# the iteration-space decoupling the CUTLASS FA-2 case study argues for
# (arXiv 2312.11918). Routing decisions, capacity overflow, and expert
# permutation are invisible to the bits by construction. RWKV channel-mix
# GEMMs reuse the same shim with E=1.

def _gemm_rng_grouped_kernel(s_ref, a_ref, b_ref, c_ref, m_ref, acc_scr, *,
                             gm: int, gn: int, n_cb: int, rb: int, ck: int,
                             sq32: int, threshold: int, rounds: int,
                             n_valid_blocks: int, n_rb_valid: int,
                             out_dtype, heads_local: int,
                             heads_global: int):
    e = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    kk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kk == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # --- MXU stream: this expert's tiled matmul accumulation ------------
    acc_scr[...] += jax.lax.dot_general(
        a_ref[0], b_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # --- VPU stream: mask block s of the EMISSION grid — s linearizes
    # the whole (e, i, j) GEMM grid, so which expert (and which permuted
    # tokens) the MXU is chewing on is irrelevant to the bits ------------
    @pl.when(kk == 0)
    def _rng():
        s = (e * gm + i) * gn + j
        rb_idx, cb_idx = _mask_block_idx(s, n_valid_blocks, n_cb,
                                         n_rb_valid)
        _emit_mask_block(m_ref, s_ref, rb_idx * rb, cb_idx * ck, rb=rb,
                         ck=ck, sq32=sq32, threshold=threshold,
                         rounds=rounds, heads_local=heads_local,
                         heads_global=heads_global)

    @pl.when(kk == nk - 1)
    def _flush():
        c_ref[0] = acc_scr[...].astype(out_dtype)


def gemm_with_rng_grouped(a: jnp.ndarray, b: jnp.ndarray, *,
                          mask_batch: int, mask_heads: int, mask_sq: int,
                          mask_sk: int, p: float, seed, salt=0,
                          rounds: int = 7,
                          block_m: int = 256, block_n: int = 256,
                          block_k: int = 512, mask_block_cols: int = 2048,
                          max_mask_rows_per_block: int = 256,
                          interpret: Optional[bool] = None,
                          heads_global: int = 0, bh_offset=0,
                          ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """C[e] = a[e] @ b[e] for a (E, C, K), b (E, K, N), plus the packed
    dropout keep-mask (B, H, SQ//32, SK) generated under the grouped
    GEMM. The RNG emission grid is independent of the GEMM grid: mask
    blocks are assigned round-robin over the E*gm*gn expert tiles and
    indexed purely by Philox counters, so the expert permutation /
    capacity-dropped token layout never reaches the bits. Returns
    (C, mask) — mask is None when the combined grid cannot host the mask
    work (paper Region 3; caller falls back to the standalone kernel).
    Bit-identical to every other producer for the same
    (seed, salt, layer, step). Shard-local via ``heads_global`` /
    ``bh_offset`` exactly like the dense hosts."""
    e, c, kdim = a.shape
    e2, k2, n = b.shape
    assert e == e2 and kdim == k2
    interpret = resolve_interpret(interpret)
    bm, bn, bkk = min(block_m, c), min(block_n, n), min(block_k, kdim)
    assert c % bm == 0 and n % bn == 0 and kdim % bkk == 0
    gm, gn, gk = c // bm, n // bn, kdim // bkk
    n_steps = e * gm * gn

    assert mask_sq % 32 == 0
    sq32 = mask_sq // 32
    layout = _mask_layout(n_steps, mask_batch, mask_heads, sq32, mask_sk,
                          mask_block_cols, max_mask_rows_per_block)
    if layout is None:
        # combined expert grid too small to hide this much RNG: Region 3.
        return _plain_gemm_grouped(a, b, bm, bn, bkk, interpret), None
    ck, n_cb, rb, n_rb_valid, n_valid_blocks, mask_rows_alloc = layout

    static = (e, gm, gn, gk, bm, bn, bkk, n_cb, rb, ck, sq32,
              threshold_from_p(p), rounds, n_valid_blocks, n_rb_valid,
              mask_rows_alloc, mask_sk, interpret,
              mask_batch, mask_heads, heads_global or mask_heads)
    return _gemm_rng_grouped_call(
        static, seed_salt_smem(seed, salt, bh_offset), a, b)


def _gemm_rng_grouped_impl(static, sd, a, b):
    (e, gm, gn, gk, bm, bn, bkk, n_cb, rb, ck, sq32, threshold, rounds,
     n_valid_blocks, n_rb_valid, mask_rows_alloc, mask_sk,
     interpret, mask_batch, mask_heads, heads_global) = static
    c_dim, n = a.shape[1], b.shape[2]
    kernel = functools.partial(
        _gemm_rng_grouped_kernel, gm=gm, gn=gn, n_cb=n_cb, rb=rb, ck=ck,
        sq32=sq32, threshold=threshold, rounds=rounds,
        n_valid_blocks=n_valid_blocks, n_rb_valid=n_rb_valid,
        out_dtype=a.dtype, heads_local=mask_heads,
        heads_global=heads_global)

    def _mask_index_map(ei, i, j, kk, _gm=gm, _gn=gn):
        rb_idx, cb_idx = _mask_block_idx((ei * _gm + i) * _gn + j,
                                         n_valid_blocks, n_cb, n_rb_valid)
        return rb_idx, cb_idx

    cc, mask2d = pl.pallas_call(
        kernel,
        name="gemm_rng_grouped",
        grid=(e, gm, gn, gk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm, bkk), lambda ei, i, j, kk: (ei, i, kk)),
            pl.BlockSpec((1, bkk, bn), lambda ei, i, j, kk: (ei, kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bn), lambda ei, i, j, kk: (ei, i, j)),
            pl.BlockSpec((rb, ck), _mask_index_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e, c_dim, n), a.dtype),
            jax.ShapeDtypeStruct((mask_rows_alloc, mask_sk), jnp.uint32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(sd, a, b)
    mr = mask_batch * mask_heads * sq32
    return cc, mask2d[:mr].reshape(mask_batch, mask_heads, sq32, mask_sk)


def _grouped_dgrad_pair(a, b, dc):
    """Per-expert GEMM backward in f32: y[e] = a[e] @ b[e]."""
    dcf = dc.astype(jnp.float32)
    da = jnp.einsum("ecf,edf->ecd", dcf,
                    b.astype(jnp.float32)).astype(a.dtype)
    db = jnp.einsum("ecd,ecf->edf", a.astype(jnp.float32),
                    dcf).astype(b.dtype)
    return da, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gemm_rng_grouped_call(static, sd, a, b):
    return _gemm_rng_grouped_impl(static, sd, a, b)


def _gemm_rng_grouped_fwd(static, sd, a, b):
    return _gemm_rng_grouped_impl(static, sd, a, b), (a, b)


def _gemm_rng_grouped_bwd(static, res, cts):
    a, b = res
    da, db = _grouped_dgrad_pair(a, b, cts[0])
    dsd = np.zeros((4,), jax.dtypes.float0)
    return dsd, da, db


_gemm_rng_grouped_call.defvjp(_gemm_rng_grouped_fwd,
                              _gemm_rng_grouped_bwd)


def _plain_grouped_impl(a, b, static):
    bm, bn, bkk, interpret = static
    e, c, kdim = a.shape
    n = b.shape[2]

    def kern(a_ref, b_ref, c_ref, acc_scr):
        kk = pl.program_id(3)

        @pl.when(kk == 0)
        def _zero():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        acc_scr[...] += jax.lax.dot_general(
            a_ref[0], b_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(kk == pl.num_programs(3) - 1)
        def _flush():
            c_ref[0] = acc_scr[...].astype(a.dtype)

    return pl.pallas_call(
        kern,
        name="gemm_grouped",
        grid=(e, c // bm, n // bn, kdim // bkk),
        in_specs=[
            pl.BlockSpec((1, bm, bkk), lambda ei, i, j, kk: (ei, i, kk)),
            pl.BlockSpec((1, bkk, bn), lambda ei, i, j, kk: (ei, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda ei, i, j, kk: (ei, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _plain_grouped_call(a, b, static):
    return _plain_grouped_impl(a, b, static)


def _plain_grouped_fwd(a, b, static):
    return _plain_grouped_impl(a, b, static), (a, b)


def _plain_grouped_bwd(static, res, dc):
    a, b = res
    return _grouped_dgrad_pair(a, b, dc)


_plain_grouped_call.defvjp(_plain_grouped_fwd, _plain_grouped_bwd)


def _plain_gemm_grouped(a, b, bm, bn, bkk, interpret):
    """Grouped matmul without the RNG side-channel (Region-3 fallback /
    baseline)."""
    return _plain_grouped_call(a, b, (bm, bn, bkk, interpret))


def _gemm_rng_grouped_fp8_kernel(s_ref, as_ref, bs_ref, a_ref, b_ref,
                                 c_ref, m_ref, acc_scr, *, gm: int,
                                 gn: int, gk: int, n_cb: int, rb: int,
                                 ck: int, sq32: int, threshold: int,
                                 rounds: int, n_valid_blocks: int,
                                 n_rb_valid: int, out_dtype,
                                 heads_local: int, heads_global: int):
    e = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    kk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kk == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # --- MXU stream: e4m3 expert-tile product, per-tile rescale ---------
    prod = jax.lax.dot_general(
        a_ref[0].astype(jnp.float32), b_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_scr[...] += prod * (as_ref[e * gm + i, kk] * bs_ref[e * gk + kk, j])

    # --- VPU stream: identical emission-grid assignment to the f32 host
    @pl.when(kk == 0)
    def _rng():
        s = (e * gm + i) * gn + j
        rb_idx, cb_idx = _mask_block_idx(s, n_valid_blocks, n_cb,
                                         n_rb_valid)
        _emit_mask_block(m_ref, s_ref, rb_idx * rb, cb_idx * ck, rb=rb,
                         ck=ck, sq32=sq32, threshold=threshold,
                         rounds=rounds, heads_local=heads_local,
                         heads_global=heads_global)

    @pl.when(kk == nk - 1)
    def _flush():
        c_ref[0] = acc_scr[...].astype(out_dtype)


def gemm_with_rng_grouped_fp8(a: jnp.ndarray, b: jnp.ndarray, *,
                              mask_batch: int, mask_heads: int,
                              mask_sq: int, mask_sk: int, p: float,
                              seed, salt=0, rounds: int = 7,
                              block_m: int = 256, block_n: int = 256,
                              block_k: int = 512,
                              mask_block_cols: int = 2048,
                              max_mask_rows_per_block: int = 256,
                              interpret: Optional[bool] = None,
                              heads_global: int = 0, bh_offset=0,
                              ) -> Tuple[jnp.ndarray,
                                         Optional[jnp.ndarray]]:
    """Grouped expert GEMM on per-tile-scaled e4m3 operands with the
    dropout mask generated under it. Operands quantize per expert tile —
    A per (e, block_m, block_k), B per (e, block_k, block_n) — via one
    reshape through ``quant.quantize_tiled`` (the expert dim folds into
    the tile-row index). Mask bits identical to the f32 grouped host
    (same _mask_layout, same counters). Returns (C, mask); in Region 3
    the GEMM runs in f32 (mask None, caller falls back) — the fp8 plain
    pair is not worth a third kernel for a path the scheduler plans
    around. Straight-through quantization, bf16 dgrad pair."""
    if not quant.have_fp8():
        raise NotImplementedError(
            "fp8 path requires jnp.float8_e4m3fn; gate on "
            "quant.have_fp8()")
    e, c, kdim = a.shape
    e2, k2, n = b.shape
    assert e == e2 and kdim == k2
    interpret = resolve_interpret(interpret)
    bm, bn, bkk = min(block_m, c), min(block_n, n), min(block_k, kdim)
    assert c % bm == 0 and n % bn == 0 and kdim % bkk == 0
    gm, gn, gk = c // bm, n // bn, kdim // bkk
    n_steps = e * gm * gn

    assert mask_sq % 32 == 0
    sq32 = mask_sq // 32
    layout = _mask_layout(n_steps, mask_batch, mask_heads, sq32, mask_sk,
                          mask_block_cols, max_mask_rows_per_block)
    if layout is None:
        return _plain_gemm_grouped(a, b, bm, bn, bkk, interpret), None
    ck, n_cb, rb, n_rb_valid, n_valid_blocks, mask_rows_alloc = layout

    static = (e, gm, gn, gk, bm, bn, bkk, n_cb, rb, ck, sq32,
              threshold_from_p(p), rounds, n_valid_blocks, n_rb_valid,
              mask_rows_alloc, mask_sk, interpret,
              mask_batch, mask_heads, heads_global or mask_heads)
    return _gemm_rng_grouped_fp8_call(
        static, seed_salt_smem(seed, salt, bh_offset), a, b)


def _gemm_rng_grouped_fp8_impl(static, sd, a, b):
    (e, gm, gn, gk, bm, bn, bkk, n_cb, rb, ck, sq32, threshold, rounds,
     n_valid_blocks, n_rb_valid, mask_rows_alloc, mask_sk,
     interpret, mask_batch, mask_heads, heads_global) = static
    c_dim, kdim, n = a.shape[1], a.shape[2], b.shape[2]
    # the expert dim folds into quantize_tiled's tile rows: (E*C, K) in
    # (bm, bk) tiles == per-(e, i, kk) expert tiles, scales (E*gm, gk)
    a_q, a_s = quant.quantize_tiled(a.reshape(e * c_dim, kdim), bm, bkk)
    b_q, b_s = quant.quantize_tiled(b.reshape(e * kdim, n), bkk, bn)
    a_q = a_q.reshape(e, c_dim, kdim)
    b_q = b_q.reshape(e, kdim, n)
    kernel = functools.partial(
        _gemm_rng_grouped_fp8_kernel, gm=gm, gn=gn, gk=gk, n_cb=n_cb,
        rb=rb, ck=ck, sq32=sq32, threshold=threshold, rounds=rounds,
        n_valid_blocks=n_valid_blocks, n_rb_valid=n_rb_valid,
        out_dtype=a.dtype, heads_local=mask_heads,
        heads_global=heads_global)

    def _mask_index_map(ei, i, j, kk, _gm=gm, _gn=gn):
        rb_idx, cb_idx = _mask_block_idx((ei * _gm + i) * _gn + j,
                                         n_valid_blocks, n_cb, n_rb_valid)
        return rb_idx, cb_idx

    cc, mask2d = pl.pallas_call(
        kernel,
        name="gemm_rng_grouped_fp8",
        grid=(e, gm, gn, gk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm, bkk), lambda ei, i, j, kk: (ei, i, kk)),
            pl.BlockSpec((1, bkk, bn), lambda ei, i, j, kk: (ei, kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bn), lambda ei, i, j, kk: (ei, i, j)),
            pl.BlockSpec((rb, ck), _mask_index_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e, c_dim, n), a.dtype),
            jax.ShapeDtypeStruct((mask_rows_alloc, mask_sk), jnp.uint32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(sd, a_s, b_s, a_q, b_q)
    mr = mask_batch * mask_heads * sq32
    return cc, mask2d[:mr].reshape(mask_batch, mask_heads, sq32, mask_sk)


def _grouped_dgrad_pair_bf16(a, b, dc):
    """bf16 dgrad pair for the grouped fp8 forward (straight-through
    quantization, f32 accumulation)."""
    dcb = dc.astype(jnp.bfloat16)
    da = jnp.einsum("ecf,edf->ecd", dcb.astype(jnp.float32),
                    b.astype(jnp.bfloat16).astype(jnp.float32)
                    ).astype(a.dtype)
    db = jnp.einsum("ecd,ecf->edf",
                    a.astype(jnp.bfloat16).astype(jnp.float32),
                    dcb.astype(jnp.float32)).astype(b.dtype)
    return da, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gemm_rng_grouped_fp8_call(static, sd, a, b):
    return _gemm_rng_grouped_fp8_impl(static, sd, a, b)


def _gemm_rng_grouped_fp8_fwd(static, sd, a, b):
    return _gemm_rng_grouped_fp8_impl(static, sd, a, b), (a, b)


def _gemm_rng_grouped_fp8_bwd(static, res, cts):
    a, b = res
    da, db = _grouped_dgrad_pair_bf16(a, b, cts[0])
    dsd = np.zeros((4,), jax.dtypes.float0)
    return dsd, da, db


_gemm_rng_grouped_fp8_call.defvjp(_gemm_rng_grouped_fp8_fwd,
                                  _gemm_rng_grouped_fp8_bwd)


def _plain_fp8_kernel(as_ref, bs_ref, a_ref, b_ref, c_ref, acc_scr, *,
                      out_dtype):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    prod = jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_scr[...] += prod * (as_ref[i, kk] * bs_ref[kk, j])

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        c_ref[...] = acc_scr[...].astype(out_dtype)


def _plain_gemm_fp8_impl(a, b, static):
    bm, bn, bkk, interpret = static
    m, kdim = a.shape
    _, n = b.shape
    a_q, a_s = quant.quantize_tiled(a, bm, bkk)
    b_q, b_s = quant.quantize_tiled(b, bkk, bn)
    return pl.pallas_call(
        functools.partial(_plain_fp8_kernel, out_dtype=a.dtype),
        name="gemm_fp8",
        grid=(m // bm, n // bn, kdim // bkk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bkk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a_s, b_s, a_q, b_q)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _plain_gemm_fp8_call(a, b, static):
    return _plain_gemm_fp8_impl(a, b, static)


def _plain_gemm_fp8_fwd(a, b, static):
    return _plain_gemm_fp8_impl(a, b, static), (a, b)


def _plain_gemm_fp8_bwd(static, res, dc):
    a, b = res
    return _dgrad_pair_bf16(a, b, dc)


_plain_gemm_fp8_call.defvjp(_plain_gemm_fp8_fwd, _plain_gemm_fp8_bwd)
