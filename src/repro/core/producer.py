"""Producer-site RNG executors — the physical mask producers behind the
compiled DropoutSchedule (core/schedule.py).

The paper hides dropout RNG under producer GEMMs (QKV projection, the
previous layer's out-projection, or — in the regime the paper actually
benchmarks — the FFN up/down projections, the largest GEMMs in the block).
Since the schedule redesign, the DECISION of where each layer's mask is
generated is made once, ahead of trace, by ``compile_schedule``; this
module holds the shared capability predicates the compiler consults and
the executors the model calls with the planned ``how``:

  "gemm_rng"         — inside the fused GEMM+RNG Pallas kernel
                       (MXU ∥ VPU), f32/bf16 operands or the
                       per-tile-scaled fp8(e4m3) path
  "gemm_rng_grouped" — inside the grouped expert-GEMM kernel: the MoE
                       (E, C, D)x(E, D, F) einsum or an RWKV channel-mix
                       GEMM (E=1) hosts the RNG; the emission grid is
                       decoupled from the GEMM grid, so the permuted /
                       capacity-dropped token layout never reaches the
                       bits (they index the (b, h, q, k) counter space)
  "standalone"       — the standalone philox Pallas kernel (paper
                       Region 3: the GEMM could not host the RNG, the
                       remainder runs exposed — but still producer-side,
                       before attention)
  "xla"              — XLA-generated bits (non-Pallas path / 8-bit
                       Philox scheme, which only the XLA producer knows)
  "replay"           — consumer-side: no plane is materialized at all;
                       the flash-attention fwd/bwd kernels re-derive
                       each tile's keep bits from the SAME position-
                       based counters (zero mask HBM). Planned by the
                       schedule whenever the counter tiling is exactly
                       reconstructible (replay_unsupported_reason); a
                       gemm-hosted producer is retained run-and-discard

Fallback chain for a grouped host: gemm_rng_grouped → standalone (the
kernel's own layout check stays authoritative at run time) → xla.
Fallback chain for replay consumption: replay → premask → xla.

With a sharding policy installed, the kernel producers run SHARD-LOCAL
inside ``jax.shard_map``: each shard generates its (b_loc, h_loc)
tile of the mask plane under its slice of the host GEMM. The Philox
counter scheme is position-based (philox_common.global_bh), so
shard-local bits equal the global mask's slice exactly.

Every producer is bit-identical for the same (seed, salt, layer, step) —
the invariant the sites ablation and checkpoint-restart reproducibility
rest on — and the bits never depend on the host GEMM's dtype.

Scheduling telemetry lives on the compiled schedule itself
(``DropoutSchedule.records`` / ``explain``), not in a mutable module
global: records attached to the artifact cannot double-count under jit
retraces and are trace-safe by construction.

The static mask-safety verifier (``repro.analysis.counters``) re-derives
each planned emission's grid from the SAME shape helpers exported here
(``block_gemm_shapes`` / ``grouped_host_shapes`` / ``pick_gemm_blocks``)
— changing their arithmetic changes what the verifier proves, and
``tests/test_analysis.py`` holds every shipped config to a clean lint,
so a divergence between planner and kernels fails fast.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import FFNKind, ModelConfig
from repro.core import dropout_rng
from repro.core.overlap import DropoutPlan

HOW_GEMM = "gemm_rng"
HOW_GEMM_GROUPED = "gemm_rng_grouped"
HOW_STANDALONE = "standalone"
HOW_XLA = "xla"
# Consumer-side realization: the flash-attention kernels replay the
# plan's position-based Philox counters in-register (mode="replay") and
# no packed plane is materialized for the consumer — zero mask HBM on
# the attention path. A gemm-hosted producer is RETAINED run-and-discard
# (HostAssignment.host_how) so the RNG still hides under the GEMM and
# the bits stay contract-identical to what the consumer derives.
HOW_REPLAY = "replay"

log = logging.getLogger(__name__)

# interpret-mode-friendly caps, matching the fused kernel's defaults
_BLOCK_M_CAP = 256
_BLOCK_N_CAP = 256
_BLOCK_K_CAP = 512
# the fused kernels' mask-column block (gemm_rng.py mask_block_cols)
_MASK_COLS_CAP = 2048
# the standalone philox kernel's column block
_PHILOX_COLS_CAP = 512
# flash-attention tile sides, largest first: 512 x 512 ran all three
# flash kernels fastest at (S, D) = (1536, 64) and (4096, 128) on a TPU
# v5e, where each grid step costs about 0.35 us whatever it computes
_FLASH_TILES = (512, 256, 128)

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "fp8": 1}


# --------------------------------------------------------------------------
# capability predicates (shared with the schedule compiler)
# --------------------------------------------------------------------------

def _largest_divisor(dim: int, cap: int) -> int:
    for c in range(min(cap, dim), 0, -1):
        if dim % c == 0:
            return c
    return 1


def _tuned_tables():
    """The active tuned-table module (repro.tune.tables), or None. Lazy:
    core must import without the tune subsystem, and no installed table
    must mean exactly the shipped defaults."""
    try:
        from repro.tune import tables
    except ImportError:          # pragma: no cover - trimmed installs
        return None
    return tables


def mask_cols_cap(sq: int, sk: int) -> int:
    """The fused kernels' RNG emission-grid column block for this mask
    plane: the active tuned table's (proven) choice, else the shipped
    default. Planner feasibility, the executed kernel grid, and the
    verifier's emission layout all resolve through THIS function."""
    t = _tuned_tables()
    if t is not None:
        return t.active_mask_cols(sq, sk, default=_MASK_COLS_CAP)
    return _MASK_COLS_CAP


def _flash_tile(n: int, local_window: int) -> int:
    """The largest tile side that divides ``n`` and leaves at least two
    blocks along it, so the causal skip still has tiles to drop, and that
    a sliding window does not undercut, so window skipping keeps its
    granularity; 128 where none does (every n <= 256)."""
    for t in _FLASH_TILES:
        if n % t == 0 and 2 * t <= n and (local_window <= 0
                                           or t <= local_window):
            return t
    return _FLASH_TILES[-1]


def attn_flash_blocks(sq: int, sk: int,
                      local_window: int = 0) -> Tuple[int, int]:
    """The flash-attention (block_q, block_k) for this plane, shared by
    the fwd, dq and dkv kernels: the active tuned table's
    (bit-identity-proven) choice, else chosen from the shape. Both the
    executing kernel call (models/attention) and the verifier's replay
    grid (analysis/counters._replay_blocks) resolve through here."""
    blocks = (_flash_tile(sq, local_window), _flash_tile(sk, local_window))
    t = _tuned_tables()
    if t is not None:
        return t.active_flash_blocks(sq, sk, default=blocks)
    return blocks


def pick_gemm_blocks(m: int, n: int, k: int
                     ) -> Optional[Tuple[int, int, int]]:
    """Block shape for a model-path fused GEMM, or None when the operand
    shapes don't tile cleanly (oddly-sized dims would force degenerate
    blocks; the caller then keeps the plain GEMM and the XLA producer).

    An installed tuned table (repro.tune.tables) overrides the answer
    for exact shapes it carries a bit-identity-proven entry for; the
    schedule compiler, the shard-local executor, and repro.analysis all
    derive their grids from THIS function, so a tuned override
    propagates to planner, kernels and verifier consistently."""
    t = _tuned_tables()
    if t is not None:
        tuned = t.active_blocks(m, n, k)
        if tuned is not None:
            return tuned
    bm = _largest_divisor(m, _BLOCK_M_CAP)
    bn = _largest_divisor(n, _BLOCK_N_CAP)
    bk = _largest_divisor(k, _BLOCK_K_CAP)
    if bm % 8 or bn % 8 or bk % 8:
        return None
    return bm, bn, bk


def shard_host_gemm(m: int, n: int, k: int, batch_shards: int = 1,
                    head_shards: int = 1) -> Tuple[int, int, int]:
    """Per-shard (m_loc, n_loc, k) of a dense host GEMM under the
    mask-plane shard layout: rows follow the batch shards, columns
    follow the head (model-axis) shards — each model-axis shard computes
    a DISTINCT N-slice of the host GEMM instead of recomputing the full
    product redundantly, so head-only-sharded meshes stop paying the
    whole GEMM per shard. A dim that doesn't divide stays global (that
    dim is then replicated across its shards — the pre-N-sharding
    behavior). The schedule compiler, the shard-local executor, and
    repro.analysis all derive the local grid from THIS function, so the
    planned emission layout, the executed kernel grid, and the verified
    counter tiling can never disagree."""
    m_loc = m // batch_shards if batch_shards > 1 and m % batch_shards == 0 \
        else m
    n_loc = n // head_shards if head_shards > 1 and n % head_shards == 0 \
        else n
    return m_loc, n_loc, k


def mask_kernel_unsupported_reason(plan: DropoutPlan, sq: int, sk: int,
                                   fused: bool = True) -> Optional[str]:
    """Why the Pallas mask producers cannot represent this plan/shape —
    None when they can. The single predicate behind every call site
    (qkv, prev_gemm, ffn_up, ffn_down, standalone fallback): the Pallas
    kernels implement the paper-faithful 32-bit Philox scheme only, need
    32-packable query rows, and tile the mask columns in 512-column
    blocks; the GEMM-fused hosts (``fused=True``) additionally partition
    the mask in 2048-column blocks. The standalone kernel
    (``fused=False``) has no 2048 constraint."""
    if plan.cfg.philox_bits != 32:
        return f"philox_bits={plan.cfg.philox_bits} (XLA-only scheme)"
    if sq % 32:
        return f"sq={sq} not 32-packable"
    sq32 = sq // 32
    if sq32 % min(8, sq32):
        return f"sq32={sq32} breaks the packed-row tiling"
    if sk % min(_PHILOX_COLS_CAP, sk):
        return f"sk={sk} breaks the {_PHILOX_COLS_CAP}-column tiling"
    cols = mask_cols_cap(sq, sk)
    if fused and sk % min(cols, sk):
        return f"sk={sk} breaks the {cols}-column mask blocks"
    return None


def replay_unsupported_reason(plan: DropoutPlan, sq: int, sk: int,
                              attn_impl: str = "pallas"
                              ) -> Optional[str]:
    """Why the flash-attention consumer cannot replay this plan's
    counters in-register (mode="replay") — None when it can. Replay is
    exact only when the consumer reconstructs the producer's counter
    tiling bit-for-bit: the 32-bit Philox scheme (8-bit planes are an
    XLA-only byte layout with no tile counters) on a flash grid of
    128-multiple tiles (``attn_flash_blocks``). The runtime fallback
    chain on a refused cell is replay -> premask -> xla
    (models/attention.attn_apply)."""
    if plan.cfg.attn_replay == "off":
        return "disabled by plan (attn_replay=off)"
    if attn_impl != "pallas":
        return "impl != pallas (no in-kernel counter replay)"
    if plan.cfg.philox_bits != 32:
        return f"philox_bits={plan.cfg.philox_bits} (XLA-only scheme)"
    if sq % 128 or sk % 128:
        return f"seq ({sq}, {sk}) not 128-tileable for the flash kernels"
    return None


def note_realized(planned: Optional[str], realized: str,
                  where: str) -> str:
    """Return ``realized``, warning when it is not the schedule's
    ``planned`` producer: a runtime fallback (replay -> premask -> xla,
    gemm -> standalone -> xla) keeps the bits but moves the work, and
    must not pass silently. ``planned`` None (a direct call that let the
    executor decide) never warns."""
    if planned is not None and realized != planned:
        log.warning("%s: planned mask producer %r ran as %r", where,
                    planned, realized)
    return realized


# --------------------------------------------------------------------------
# shard-local execution context
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardExec:
    """Live mesh context for shard-local producers, rebuilt from the
    installed ShardingPolicy at execute time (the compiled schedule
    carries only the hashable ShardInfo distillation)."""
    mesh: Any
    batch_axes: Tuple[str, ...]
    head_axes: Tuple[str, ...]
    batch_shards: int
    head_shards: int

    def _spec_axes(self, axes: Tuple[str, ...]):
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]

    @property
    def b_spec(self):
        return self._spec_axes(self.batch_axes)

    @property
    def h_spec(self):
        return self._spec_axes(self.head_axes)


def shard_exec(policy, batch: int, n_heads: int) -> Optional[ShardExec]:
    """Shard-local context for a (batch, n_heads) mask plane under
    ``policy``, or None when no mesh axis divides either dim (the
    schedule then plans XLA production and GSPMD shards it)."""
    if policy is None:
        return None
    from repro.distributed.sharding import mask_plane_shards
    (b_axes, nb), (h_axes, nh) = mask_plane_shards(policy, batch,
                                                   n_heads)
    if nb * nh == 1:
        return None
    return ShardExec(mesh=policy.mesh, batch_axes=b_axes, head_axes=h_axes,
                     batch_shards=nb, head_shards=nh)


def _flat_axis_index(axes: Tuple[str, ...], mesh) -> jnp.ndarray:
    """Flattened (row-major) index of this shard along ``axes``."""
    idx = jnp.zeros((), jnp.uint32)
    for a in axes:
        idx = idx * jnp.uint32(mesh.shape[a]) + jax.lax.axis_index(
            a).astype(jnp.uint32)
    return idx


def shard_mask_tile(shard: ShardExec, batch: int, n_heads: int, sq: int,
                    sk: int):
    """This device's tile of the (batch, n_heads) mask plane — callable
    only INSIDE a shard_map body over ``shard.mesh``. Returns
    (local_mask_shape, heads_global, bh_offset) for the kernel
    producers' global-position counters; with ``shard`` None, the
    whole-mask identity ((batch, n_heads, sq, sk), 0, 0)."""
    if shard is None:
        return (batch, n_heads, sq, sk), 0, 0
    b_loc = batch // shard.batch_shards
    h_loc = n_heads // shard.head_shards
    b0 = _flat_axis_index(shard.batch_axes, shard.mesh) \
        * jnp.uint32(b_loc)
    h0 = _flat_axis_index(shard.head_axes, shard.mesh) \
        * jnp.uint32(h_loc)
    return ((b_loc, h_loc, sq, sk), n_heads,
            b0 * jnp.uint32(n_heads) + h0)


# --------------------------------------------------------------------------
# producers
# --------------------------------------------------------------------------

def standalone_packed_mask(plan: DropoutPlan, batch: int, n_heads: int,
                           sq: int, sk: int, layer_idx, step,
                           use_kernel: bool = True,
                           policy=None) -> jnp.ndarray:
    """Packed mask from a producer-side standalone generator: the philox
    Pallas kernel when it can represent the plan, else the XLA producer.
    Used for the Region-3 remainder and to bootstrap the first consumer
    of the carried-site pipelines (no previous GEMM exists yet). With a
    policy installed the kernel runs shard-local (per-shard (b, h) tile,
    identical bits)."""
    seed = plan.step_seed(step)
    salt = plan.salt(layer_idx)
    reason = mask_kernel_unsupported_reason(plan, sq, sk, fused=False)
    if use_kernel and reason is None:
        from repro.kernels import ops
        shard = shard_exec(policy, batch, n_heads)
        if shard is None:
            return ops.dropout_mask(batch, n_heads, sq, sk, plan.cfg.p,
                                    seed, salt, plan.cfg.philox_rounds)
        from jax.sharding import PartitionSpec as P

        def body(sd_, sl_):
            (b_loc, h_loc, _sq, _sk), hg, off = shard_mask_tile(
                shard, batch, n_heads, sq, sk)
            return ops.dropout_mask(
                b_loc, h_loc, sq, sk, plan.cfg.p, sd_, sl_,
                plan.cfg.philox_rounds, heads_global=hg,
                bh_offset=off)

        return jax.shard_map(
            body, mesh=shard.mesh, in_specs=(P(), P()),
            out_specs=P(shard.b_spec, shard.h_spec, None, None),
            check_vma=False,
        )(jnp.asarray(seed, jnp.uint32), jnp.asarray(salt, jnp.uint32))
    return dropout_rng.packed_mask(
        batch, n_heads, sq, sk, plan.cfg.p, seed, salt,
        plan.cfg.philox_rounds, plan.cfg.philox_bits)


def _fused_gemm_call(x2d, w2d, plan, mask_shape, seed, salt, blocks,
                     gemm_dtype, heads_global=0, bh_offset=0):
    """One fused GEMM+RNG kernel invocation in the plan's host dtype.
    Returns (y2d, mask-or-None, effective_dtype)."""
    from repro.kernels import ops
    batch, n_heads, sq, sk = mask_shape
    bm, bn, bk = blocks
    if gemm_dtype == "fp8":
        from repro.kernels import quant
        if quant.have_fp8():
            y, mask = ops.fused_gemm_rng_fp8(
                x2d, w2d, mask_batch=batch, mask_heads=n_heads,
                mask_sq=sq, mask_sk=sk, p=plan.cfg.p, seed=seed,
                salt=salt, rounds=plan.cfg.philox_rounds, block_m=bm,
                block_n=bn, block_k=bk,
                mask_block_cols=mask_cols_cap(sq, sk),
                heads_global=heads_global, bh_offset=bh_offset)
            return y, mask, "fp8"
        gemm_dtype = "f32"      # fp8 unavailable in this build: f32 host
    a = x2d.astype(jnp.bfloat16) if gemm_dtype == "bf16" else x2d
    w = w2d.astype(jnp.bfloat16) if gemm_dtype == "bf16" else w2d
    y, mask = ops.fused_qkv_gemm_rng(
        a, w, mask_batch=batch, mask_heads=n_heads, mask_sq=sq,
        mask_sk=sk, p=plan.cfg.p, seed=seed, salt=salt,
        rounds=plan.cfg.philox_rounds, block_m=bm, block_n=bn,
        block_k=bk, mask_block_cols=mask_cols_cap(sq, sk),
        heads_global=heads_global, bh_offset=bh_offset)
    if gemm_dtype == "bf16":
        y = y.astype(x2d.dtype)
    return y, mask, gemm_dtype


def gemm_with_mask(x2d: jnp.ndarray, w2d: jnp.ndarray, plan: DropoutPlan,
                   mask_shape: Tuple[int, int, int, int], layer_idx, step,
                   allow_fused: bool = True, how: Optional[str] = None,
                   policy=None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, str]:
    """y = x2d @ w2d with the packed mask for ``mask_shape`` = (B, H, SQ,
    SK) produced at this GEMM. Returns (y2d, mask, how) with ``how`` the
    realized producer tag (see module docstring).

    ``how`` is the schedule's planned producer (HOW_GEMM /
    HOW_STANDALONE / HOW_XLA); None derives it locally from the same
    capability predicates the compiler uses (direct calls, benches).
    ``plan.gemm_dtype`` selects the fused GEMM's operand precision:
    "f32" | "bf16" run the standard fused kernel (f32 accumulation);
    "fp8" runs the per-tile-scaled e4m3 kernel — same mask bits, GEMM
    within the documented quantization error bound (kernels/quant.py).

    With ``policy`` installed and a kernel ``how``, the fused call runs
    shard-local: GEMM rows follow the batch shards, the mask tile
    follows the (batch, heads) shards, bits match the global mask's
    slice exactly (position-based counters).

    allow_fused=False forces the XLA producer (used when the GEMM itself
    must stay an XLA op: impl="xla")."""
    batch, n_heads, sq, sk = mask_shape
    m, kdim = x2d.shape
    n = w2d.shape[1]
    gemm_dtype = plan.gemm_dtype
    if how is None:
        blocks = pick_gemm_blocks(m, n, kdim) if allow_fused else None
        reason = mask_kernel_unsupported_reason(plan, sq, sk)
        how = (HOW_GEMM if (blocks is not None and reason is None)
               else HOW_XLA)
    if how == HOW_XLA:
        y = x2d @ w2d
        mask = dropout_rng.packed_mask(
            batch, n_heads, sq, sk, plan.cfg.p, plan.step_seed(step),
            plan.salt(layer_idx), plan.cfg.philox_rounds,
            plan.cfg.philox_bits)
        return y, mask, HOW_XLA

    shard = shard_exec(policy, batch, n_heads)
    if shard is not None:
        y, mask, done = _gemm_with_mask_sharded(x2d, w2d, plan, mask_shape,
                                                layer_idx, step, shard)
        return y, mask, note_realized(how, done, "gemm_with_mask")

    blocks = pick_gemm_blocks(m, n, kdim)
    if blocks is None:
        # planned a kernel host on an untileable GEMM — only reachable
        # from direct calls that bypass the compiler; degrade like it
        # would have planned
        note_realized(how, HOW_XLA, "gemm_with_mask")
        return gemm_with_mask(x2d, w2d, plan, mask_shape, layer_idx,
                              step, how=HOW_XLA)
    seed = plan.step_seed(step)
    salt = plan.salt(layer_idx)
    y, mask, _dt = _fused_gemm_call(x2d, w2d, plan, mask_shape, seed,
                                    salt, blocks, gemm_dtype)
    if mask is None:
        # Region 3: the GEMM grid is too small to hide this much RNG;
        # the remainder runs exposed in the standalone kernel. The
        # schedule plans this (HOW_STANDALONE); the kernel's own layout
        # check stays authoritative at run time.
        mask = standalone_packed_mask(plan, batch, n_heads, sq, sk,
                                      layer_idx, step)
        if how == HOW_GEMM:
            note_realized(how, HOW_STANDALONE, "gemm_with_mask")
        return y, mask, HOW_STANDALONE
    return y, mask, HOW_GEMM


def _gemm_with_mask_sharded(x2d, w2d, plan, mask_shape, layer_idx, step,
                            shard: ShardExec
                            ) -> Tuple[jnp.ndarray, jnp.ndarray, str]:
    """Shard-local fused GEMM+RNG: each shard runs the Pallas kernel on
    its batch rows x head-axis columns of the GEMM and generates its
    (b_loc, h_loc) tile of the mask plane (global-position counters,
    bit-exact slices). GEMM rows follow the batch shards and — when N
    divides — columns follow the head (model) shards, so a head-only
    mesh computes a distinct N-slice per shard instead of redundantly
    recomputing the full product; an indivisible N falls back to
    replicated columns (the pre-N-sharding layout)."""
    from jax.sharding import PartitionSpec as P
    from repro.kernels import ops
    batch, n_heads, sq, sk = mask_shape
    b_loc = batch // shard.batch_shards
    h_loc = n_heads // shard.head_shards
    m, kdim = x2d.shape
    n = w2d.shape[1]
    m_loc, n_loc, _ = shard_host_gemm(m, n, kdim, shard.batch_shards,
                                      shard.head_shards)
    blocks = pick_gemm_blocks(m_loc, n_loc, kdim)
    # Region 3 is a static property of (local GEMM grid, local mask):
    # decide the realized producer here so the returned tag matches
    # what the body actually does (the unsharded path's semantics)
    fused = False
    if blocks is not None:
        from repro.kernels.gemm_rng import mask_layout_feasible
        bm, bn, _bk = blocks
        fused = mask_layout_feasible((m_loc // bm) * (n_loc // bn),
                                     b_loc, h_loc, sq, sk,
                                     mask_block_cols=mask_cols_cap(sq, sk))
    seed = jnp.asarray(plan.step_seed(step), jnp.uint32)
    salt = jnp.asarray(plan.salt(layer_idx), jnp.uint32)
    xs = P(shard.b_spec, None)
    ws = P(None, shard.h_spec if n_loc != n else None)
    ys = P(shard.b_spec, shard.h_spec if n_loc != n else None)
    ms = P(shard.b_spec, shard.h_spec, None, None)

    def body(x_, w_, sd_, sl_):
        local_shape, hg, off = shard_mask_tile(shard, batch, n_heads,
                                               sq, sk)
        if fused:
            y, mask, _dt = _fused_gemm_call(
                x_, w_, plan, local_shape, sd_, sl_, blocks,
                plan.gemm_dtype, heads_global=hg, bh_offset=off)
        else:
            y = x_ @ w_ if blocks is None else _fused_gemm_call(
                x_, w_, plan, local_shape, sd_, sl_, blocks,
                plan.gemm_dtype, heads_global=hg, bh_offset=off)[0]
            mask = None
        if mask is None:        # Region 3, shard-local remainder
            mask = ops.dropout_mask(
                local_shape[0], local_shape[1], sq, sk, plan.cfg.p, sd_,
                sl_, plan.cfg.philox_rounds, heads_global=hg,
                bh_offset=off)
        return y, mask

    y, mask = jax.shard_map(
        body, mesh=shard.mesh, in_specs=(xs, ws, P(), P()),
        out_specs=(ys, ms), check_vma=False,
    )(x2d, w2d, seed, salt)
    return y, mask, HOW_GEMM if fused else HOW_STANDALONE


# --------------------------------------------------------------------------
# grouped (MoE expert / RWKV channel-mix) hosting
# --------------------------------------------------------------------------

def grouped_layout_feasible(e: int, c: int, kdim: int, n: int, batch: int,
                            n_heads: int, sq: int, sk: int
                            ) -> Tuple[bool, Optional[Tuple[int, int, int]]]:
    """(feasible, blocks) of hosting a (batch, n_heads, sq, sk) mask
    under the combined grid of E (c, kdim)x(kdim, n) expert GEMMs —
    the exact predicate the grouped kernel applies at trace time."""
    blocks = pick_gemm_blocks(c, n, kdim)
    if blocks is None:
        return False, None
    from repro.kernels.gemm_rng import mask_layout_feasible
    bm, bn, _ = blocks
    n_steps = e * (c // bm) * (n // bn)
    return mask_layout_feasible(
        n_steps, batch, n_heads, sq, sk,
        mask_block_cols=mask_cols_cap(sq, sk)), blocks


def grouped_gemm_seeded(a3: jnp.ndarray, b3: jnp.ndarray,
                        plan: DropoutPlan,
                        mask_shape: Tuple[int, int, int, int],
                        seed, salt, heads_global: int = 0, bh_offset=0
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, str]:
    """y[e] = a3[e] @ b3[e] with the packed mask for ``mask_shape``
    (LOCAL (B, H, SQ, SK)) produced under the grouped GEMM. ``seed`` /
    ``salt`` are pre-folded uint32 scalars, so this executor is callable
    from INSIDE a shard_map body (the MoE dispatch paths) — the caller
    owns the shard-local offsets (``heads_global``/``bh_offset``) and
    the mask out-spec. Returns (y, mask, how); Region 3 and untileable
    shapes degrade to the standalone kernel (same bits, plain einsum —
    for an fp8 plan the Region-3 GEMM runs unquantized, a path the
    scheduler plans around)."""
    from repro.kernels import ops
    batch, n_heads, sq, sk = mask_shape
    e, c, kdim = a3.shape
    n = b3.shape[2]

    def _standalone_mask(y):
        mask = ops.dropout_mask(batch, n_heads, sq, sk, plan.cfg.p, seed,
                                salt, plan.cfg.philox_rounds,
                                heads_global=heads_global,
                                bh_offset=bh_offset)
        return y, mask, HOW_STANDALONE

    blocks = pick_gemm_blocks(c, n, kdim)
    if blocks is None:
        return _standalone_mask(jnp.einsum("ecd,edf->ecf", a3, b3))
    bm, bn, bk = blocks
    kw = dict(mask_batch=batch, mask_heads=n_heads, mask_sq=sq,
              mask_sk=sk, p=plan.cfg.p, seed=seed, salt=salt,
              rounds=plan.cfg.philox_rounds, block_m=bm, block_n=bn,
              block_k=bk, mask_block_cols=mask_cols_cap(sq, sk),
              heads_global=heads_global, bh_offset=bh_offset)
    gemm_dtype = plan.gemm_dtype
    if gemm_dtype == "fp8":
        from repro.kernels import quant
        if quant.have_fp8():
            y, mask = ops.fused_gemm_rng_grouped_fp8(a3, b3, **kw)
            if mask is None:
                return _standalone_mask(y)
            return y, mask, HOW_GEMM_GROUPED
        gemm_dtype = "f32"          # fp8 unavailable: f32 grouped host
    a = a3.astype(jnp.bfloat16) if gemm_dtype == "bf16" else a3
    b = b3.astype(jnp.bfloat16) if gemm_dtype == "bf16" else b3
    y, mask = ops.fused_gemm_rng_grouped(a, b, **kw)
    if gemm_dtype == "bf16":
        y = y.astype(a3.dtype)
    if mask is None:
        return _standalone_mask(y)
    return y, mask, HOW_GEMM_GROUPED


def grouped_gemm_with_mask(a3: jnp.ndarray, b3: jnp.ndarray,
                           plan: DropoutPlan,
                           mask_shape: Tuple[int, int, int, int],
                           layer_idx, step, how: Optional[str] = None,
                           policy=None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, str]:
    """Whole-mask grouped host: y[e] = a3[e] @ b3[e] plus the packed
    mask for the GLOBAL ``mask_shape``, produced at this grouped GEMM.
    The direct-call / RWKV-channel-mix (E=1) entry point — MoE dispatch
    calls ``grouped_gemm_seeded`` from inside its own shard_map instead.

    With ``policy`` installed and a kernel ``how``, production runs
    shard-local: the C rows follow the batch shards (valid only for the
    token-ordered E=1 channel-mix host), the mask tile follows the
    (batch, heads) shards — bits equal the global mask's slice exactly."""
    batch, n_heads, sq, sk = mask_shape
    e, c, kdim = a3.shape
    n = b3.shape[2]
    if how is None:
        reason = mask_kernel_unsupported_reason(plan, sq, sk)
        feasible, _ = grouped_layout_feasible(e, c, kdim, n, batch,
                                              n_heads, sq, sk)
        if reason is not None:
            how = HOW_XLA
        elif feasible:
            how = HOW_GEMM_GROUPED
        else:
            how = HOW_STANDALONE
    if how == HOW_XLA:
        y = jnp.einsum("ecd,edf->ecf", a3, b3)
        mask = dropout_rng.packed_mask(
            batch, n_heads, sq, sk, plan.cfg.p, plan.step_seed(step),
            plan.salt(layer_idx), plan.cfg.philox_rounds,
            plan.cfg.philox_bits)
        return y, mask, HOW_XLA
    if how == HOW_STANDALONE:
        # honor the planned realization BEFORE the shard branch: a
        # standalone plan under a policy runs the shard-local standalone
        # kernel, never a recomputed grouped attempt
        y = jnp.einsum("ecd,edf->ecf", a3, b3)
        mask = standalone_packed_mask(plan, batch, n_heads, sq, sk,
                                      layer_idx, step, policy=policy)
        return y, mask, HOW_STANDALONE
    shard = shard_exec(policy, batch, n_heads)
    if shard is not None:
        y, mask, done = _grouped_gemm_with_mask_sharded(
            a3, b3, plan, mask_shape, layer_idx, step, shard)
    else:
        seed = jnp.asarray(plan.step_seed(step), jnp.uint32)
        salt = jnp.asarray(plan.salt(layer_idx), jnp.uint32)
        y, mask, done = grouped_gemm_seeded(a3, b3, plan, mask_shape,
                                            seed, salt)
    return y, mask, note_realized(how, done, "grouped_gemm_with_mask")


def _grouped_gemm_with_mask_sharded(a3, b3, plan, mask_shape, layer_idx,
                                    step, shard: ShardExec
                                    ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                               str]:
    """Shard-local grouped host (E=1 channel-mix): each shard runs the
    grouped kernel on its batch rows of the token-ordered C dim and
    emits its (b_loc, h_loc) tile of the mask plane."""
    from jax.sharding import PartitionSpec as P
    batch, n_heads, sq, sk = mask_shape
    b_loc = batch // shard.batch_shards
    h_loc = n_heads // shard.head_shards
    e, c, kdim = a3.shape
    n = b3.shape[2]
    c_loc = c // shard.batch_shards
    fused, _ = grouped_layout_feasible(e, c_loc, kdim, n, b_loc, h_loc,
                                       sq, sk)
    seed = jnp.asarray(plan.step_seed(step), jnp.uint32)
    salt = jnp.asarray(plan.salt(layer_idx), jnp.uint32)
    xs = P(None, shard.b_spec, None)
    ms = P(shard.b_spec, shard.h_spec, None, None)

    def body(a_, b_, sd_, sl_):
        local_shape, hg, off = shard_mask_tile(shard, batch, n_heads,
                                               sq, sk)
        return grouped_gemm_seeded(
            a_, b_, plan, local_shape, sd_, sl_,
            heads_global=hg, bh_offset=off)[:2]

    y, mask = jax.shard_map(
        body, mesh=shard.mesh,
        in_specs=(xs, P(None, None, None), P(), P()),
        out_specs=(xs, ms), check_vma=False,
    )(a3, b3, seed, salt)
    return y, mask, HOW_GEMM_GROUPED if fused else HOW_STANDALONE


# --------------------------------------------------------------------------
# FFN hosting (site="ffn_up" / "ffn_down")
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FFNHost:
    """Instruction to the block's FFN half to host the mask producer
    under one of its GEMMs — models/layers.ffn_apply for dense FFNs
    (dense fused kernel) and RWKV channel-mix (grouped kernel, E=1),
    models/moe.moe_apply for MoE expert FFNs (grouped kernel over the
    expert einsum). ``layer_idx`` is the CONSUMER layer (the transformer
    passes the next attention layer: the mask rides the carried scan
    buffer there). ``how`` is the schedule's planned producer for the
    emission; ``policy`` enables shard-local runs."""
    plan: DropoutPlan
    site: str                           # "ffn_up" | "ffn_down"
    mask_shape: Tuple[int, int, int, int]
    layer_idx: Any
    step: Any
    how: str = HOW_GEMM
    policy: Any = None


# --------------------------------------------------------------------------
# block-aware host selection (site="auto")
# --------------------------------------------------------------------------

def block_gemm_shapes(cfg: ModelConfig, batch: int, seq: int,
                      dense_ffn: Optional[bool] = None
                      ) -> Dict[str, Tuple[int, int, int]]:
    """(m, n, k) of each candidate DENSE host GEMM in one transformer
    block. FFN sites only exist for blocks with a GEMM-shaped dense FFN;
    MoE expert and RWKV channel-mix FFNs host through the grouped
    kernel instead (``grouped_host_shapes``). ``dense_ffn`` overrides
    the default (non-MoE model) judgment — the schedule compiler passes
    True for the first-dense layers of a DeepSeek-style MoE stack, whose
    FFN is an ordinary dense GEMM."""
    d = cfg.d_model
    toks = batch * seq
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    shapes = {
        "qkv": (toks, (nq + 2 * nkv) * hd, d),
        "prev_gemm": (toks, d, nq * hd),
    }
    if dense_ffn is None:
        dense_ffn = cfg.moe is None
    if dense_ffn and cfg.ffn in (FFNKind.SWIGLU, FFNKind.GEGLU,
                                 FFNKind.GELU):
        gated = cfg.ffn in (FFNKind.SWIGLU, FFNKind.GEGLU)
        shapes["ffn_up"] = (toks, (2 if gated else 1) * cfg.d_ff, d)
        shapes["ffn_down"] = (toks, d, cfg.d_ff)
    return shapes


def moe_expert_capacity(moe, tokens: int) -> int:
    """Per-source expert capacity C — the EXACT arithmetic of the
    dispatch paths in models/moe.py, shared so the schedule compiler
    plans the grouped host on the same (E, C) grid the runtime walks."""
    return max(1, -(-tokens * moe.top_k
                    * int(round(moe.capacity_factor * 100))
                    // (100 * moe.n_experts)))


def grouped_host_shapes(cfg: ModelConfig, batch: int, seq: int,
                        batch_shards: int = 1, head_shards: int = 1,
                        seq_dispatch: bool = False,
                        moe_block: Optional[bool] = None
                        ) -> Dict[str, Tuple[int, int, int, int]]:
    """(E, C, k, n) of the grouped candidate host GEMMs for blocks whose
    FFN has no dense 2D GEMM: the MoE expert einsum (E, C, D)x(E, D, F)
    — "ffn_up" hosts under the gate projection, "ffn_down" under the
    down projection — and the RWKV channel-mix key/value GEMMs as the
    E=1 degenerate case.

    Sharded runs are ESTIMATED from the mask-plane shard counts with the
    matching dispatch arithmetic (models/moe.py): dense dispatch chunks
    tokens over the batch shards (≈ the 'data'/EP axis), splits experts
    over the same axis with recv rows concatenating across sources, and
    TP-shards each expert's width over the model axis (≈
    ``head_shards``, mirroring moe_apply's d_ff_expert divisibility
    guard); ``seq_dispatch`` layouts additionally chunk tokens over the
    model axis and re-gather the capacity rows across it. The
    mask-plane axes only approximate the EP/TP axes for exotic
    policies, so the runtime kernel's own layout check stays
    authoritative: a plan/runtime divergence degrades the realized
    producer to the standalone kernel (telemetry optimistic), never a
    mask bit.

    ``moe_block`` selects the PER-LAYER block kind (a MoE stack's
    first-dense layers can carry an RWKV channel-mix FFN); None defaults
    to the whole-model judgment (cfg.moe set)."""
    d = cfg.d_model
    tok_shards = max(1, batch_shards) * (max(1, head_shards)
                                         if seq_dispatch else 1)
    toks = (batch * seq) // tok_shards
    if moe_block is None:
        moe_block = cfg.moe is not None
    if moe_block:
        m = cfg.moe
        e, cap = m.n_experts, moe_expert_capacity(m, toks)
        if batch_shards > 1 and e % batch_shards == 0:
            e, cap = e // batch_shards, tok_shards * cap
        f = m.d_ff_expert
        if head_shards > 1 and f % head_shards == 0:
            f //= head_shards       # TP over the expert width
        return {"ffn_up": (e, cap, d, f), "ffn_down": (e, cap, f, d)}
    if cfg.ffn == FFNKind.RWKV_CHANNEL:
        toks = (batch * seq) // max(1, batch_shards)
        return {"ffn_up": (1, toks, d, cfg.d_ff),
                "ffn_down": (1, toks, cfg.d_ff, d)}
    return {}


def rank_host_sites(cfg: ModelConfig, plan: DropoutPlan, batch: int,
                    seq: int, hw=None, batch_shards: int = 1,
                    head_shards: int = 1, seq_dispatch: bool = False
                    ) -> Tuple[Tuple[str, float], ...]:
    """Tileable candidate host GEMMs ranked by the Region-1 headroom
    estimate (perfmodel.rank_host_gemms), best first. ``batch_shards``
    shrinks the GEMM rows to the per-shard size when the host will run
    shard-local. MoE expert and RWKV channel-mix blocks contribute their
    GROUPED FFN hosts (perfmodel.grouped_gemm_host_headroom learns the
    combined-grid Region-1 arithmetic), so site="auto" can rank an
    expert einsum against the block's dense attention GEMMs —
    ``head_shards``/``seq_dispatch`` keep the ranked grid the SAME grid
    the per-layer capability later judges (grouped_host_shapes)."""
    from repro.perfmodel.hardware import TPU_V5E
    from repro.perfmodel.model import rank_host_gemms
    if hw is None:
        t = _tuned_tables()
        if t is not None:
            hw = t.active_hardware()    # calibrated ranking when tuned
    mask_elems = float(batch) * cfg.n_heads * seq * seq
    dtype_bytes = _DTYPE_BYTES.get(plan.gemm_dtype, 4)
    shapes = {}
    for site, (m, n, k) in block_gemm_shapes(cfg, batch, seq).items():
        m_loc = m // batch_shards
        if pick_gemm_blocks(m_loc, n, k) is not None:
            shapes[site] = (m_loc, n, k)
    grouped = {}
    for site, (e, c, k, n) in grouped_host_shapes(
            cfg, batch, seq, batch_shards=batch_shards,
            head_shards=head_shards,
            seq_dispatch=seq_dispatch).items():
        if pick_gemm_blocks(c, n, k) is not None:
            grouped[site] = (e, c, n, k)
    if not shapes and not grouped:
        return ()
    return rank_host_gemms(shapes, mask_elems, hw=hw or TPU_V5E,
                           rounds=plan.cfg.philox_rounds,
                           dtype_bytes=dtype_bytes, grouped=grouped)


def pick_host_site(cfg: ModelConfig, plan: DropoutPlan, batch: int,
                   seq: int, fuse_ok: bool = True, hw=None,
                   batch_shards: int = 1) -> str:
    """Resolve site="auto" to a concrete host. Candidates are the block's
    GEMMs that (a) tile for the fused kernel and (b) can legally host
    this plan's mask — carried sites qualify for ANY pattern with
    attention layers now that the schedule routes masks to the next
    attention layer. Ranked by Region-1 headroom: the GEMM with the most
    RNG-hiding shadow wins. Falls back to "xla" when nothing qualifies."""
    if not (plan.enabled and plan.overlapped):
        return "xla"
    if not fuse_ok or mask_kernel_unsupported_reason(
            plan, seq, seq) is not None:
        return "xla"
    ranked = rank_host_sites(cfg, plan, batch, seq, hw=hw,
                             batch_shards=batch_shards)
    return ranked[0][0] if ranked else "xla"
