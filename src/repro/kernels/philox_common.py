"""Philox-4x32 counter-based RNG, shared by every mask producer.

The same functions run inside Pallas TPU kernel bodies and inside the pure
jnp reference oracles, guaranteeing bit-exact masks regardless of *where*
the RNG executes (fused in attention, standalone, or hidden under a GEMM) —
the equivalence the paper's baseline/overlap comparison relies on.

Counter scheme (DESIGN.md §4): for attention-score element (b, h, q, k)

    ctr = (x0=k, x1=q//4, x2=b*nH+h, x3=layer_salt), key = (seed_lo, seed_hi)
    u32 = philox4x32_r(ctr, key)[q % 4]
    keep = u32 >= floor(p * 2**32)

TPU notes:
  * no 64-bit vector multiply -> mul_hi from 16-bit partial products (exact).
  * all scalar constants are ``np.uint32`` so they inline as jaxpr literals —
    Pallas kernel bodies cannot capture device-array constants.
  * uint32 ops wrap in both numpy and jnp, which Philox requires.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Philox 4x32 round constants (Salmon et al., 2011).
PHILOX_M0 = np.uint32(0xD2511F53)
PHILOX_M1 = np.uint32(0xCD9E8D57)
PHILOX_W0 = np.uint32(0x9E3779B9)  # golden-ratio Weyl increment
PHILOX_W1 = np.uint32(0xBB67AE85)

# Round counts philox4x32 implements exactly (paper sweeps 3/5/7; 10 is
# the original Salmon et al. strength). Other values would silently run
# a different chain length in every producer — config validation and
# repro.analysis both check against this set.
SUPPORTED_PHILOX_ROUNDS = (3, 5, 7, 10)

# Counter-identity folding constants (DESIGN.md §4): the layer index
# folds into x3 as layer * LAYER_SALT_PRIME + stream, the train step
# into the Philox key as step * STEP_SEED_MULT + seed — both mod 2^32.
# core/overlap.DropoutPlan applies these to traced scalars; the pure-int
# mirrors below are the metadata repro.analysis enumerates counter
# windows with, so the analyzer can never drift from the kernels.
LAYER_SALT_PRIME = 1000003
STEP_SEED_MULT = 2654435761


def fold_layer_salt(layer: int, stream: int = 0) -> int:
    """uint32 salt for (layer, stream) — the int mirror of
    ``DropoutPlan.salt``."""
    return (int(layer) * LAYER_SALT_PRIME + int(stream)) & 0xFFFFFFFF


def fold_step_seed(step: int, seed: int) -> int:
    """uint32 Philox key-lo for (step, seed) — the int mirror of
    ``DropoutPlan.step_seed``."""
    return (int(step) * STEP_SEED_MULT + (int(seed) & 0xFFFFFFFF)) \
        & 0xFFFFFFFF

_U16 = np.uint32(0xFFFF)
_SIXTEEN = np.uint32(16)


def as_u32(x):
    """Coerce python ints to np.uint32 literals; arrays to uint32 dtype."""
    if isinstance(x, (int, np.integer)):
        return np.uint32(int(x) & 0xFFFFFFFF)
    return x.astype(jnp.uint32)


def _mul32_hilo(a, b):
    """Exact (hi, lo) of a 32x32->64 unsigned multiply via 16-bit partials.

    Exactness: a*b = [ah*bh + (v>>16) + (w>>16) + (mid>>16)] * 2^32
                     + (mid & 0xffff) * 2^16 + (u & 0xffff)
    with u=al*bl, v=ah*bl, w=al*bh, mid=(u>>16)+(v&0xffff)+(w&0xffff).
    The bracket is the true hi word and never overflows uint32.
    """
    al = a & _U16
    ah = a >> _SIXTEEN
    bl = b & _U16
    bh = b >> _SIXTEEN
    u = al * bl
    v = ah * bl
    w = al * bh
    mid = (u >> _SIXTEEN) + (v & _U16) + (w & _U16)
    hi = ah * bh + (v >> _SIXTEEN) + (w >> _SIXTEEN) + (mid >> _SIXTEEN)
    lo = a * b  # uint32 wrap == low word
    return hi, lo


def philox4x32(x0, x1, x2, x3, k0, k1, rounds: int = 7):
    """Philox-4x32 with a configurable round count (paper: 3 / 5 / 7).

    Inputs broadcast against each other (python ints / np scalars / arrays);
    outputs are four uint32 values of the common broadcast shape.
    """
    x0, x1, x2, x3 = as_u32(x0), as_u32(x1), as_u32(x2), as_u32(x3)
    k0, k1 = as_u32(k0), as_u32(k1)
    # np.errstate: uint32 wraparound is intentional (numpy warns on scalar
    # overflow; jnp never does).
    with np.errstate(over="ignore"):
        for _ in range(rounds):
            hi0, lo0 = _mul32_hilo(PHILOX_M0, x0)
            hi1, lo1 = _mul32_hilo(PHILOX_M1, x2)
            y0 = hi1 ^ x1 ^ k0
            y1 = lo1
            y2 = hi0 ^ x3 ^ k1
            y3 = lo0
            x0, x1, x2, x3 = y0, y1, y2, y3
            k0 = k0 + PHILOX_W0
            k1 = k1 + PHILOX_W1
    return x0, x1, x2, x3


def philox_vector_op_count(rounds: int) -> int:
    """Vector-ALU op count per counter (4 outputs) for the perf model:
    each round = 2 mul_hi (10 ops each after 16-bit decomposition)
    + 2 mul_lo + 4 xors + 2 key adds."""
    return rounds * (2 * 10 + 2 + 4 + 2)


def threshold_from_p(p: float) -> int:
    """keep iff u32 >= threshold; P(keep) = 1 - p exactly at p=0.

    Plain int so kernels close over it as a literal."""
    return min(max(int(round(p * 4294967296.0)), 0), 0xFFFFFFFF)


def seed_to_key(seed: int) -> Tuple[int, int]:
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    return seed & 0xFFFFFFFF, seed >> 32


def split_seed(seed) -> Tuple:
    """seed -> (key_lo, key_hi). Python ints use the full 64-bit key;
    traced scalars land in key_lo with key_hi = 0. THE canonical split,
    shared by the XLA producer and the SMEM kernel operand — every mask
    producer must key Philox identically or the cross-site bit-identity
    invariant breaks."""
    if isinstance(seed, (int, np.integer)):
        lo, hi = seed_to_key(int(seed))
        return np.uint32(lo), np.uint32(hi)
    return seed.astype(jnp.uint32), jnp.zeros((), jnp.uint32)


def seed_salt_smem(seed, salt, bh_offset=0) -> jnp.ndarray:
    """(4,) uint32 [key_lo, key_hi, salt, bh_offset] — the SMEM operand of
    the dynamic-seed kernels (training folds the step/layer into seed/salt
    as traced scalars, so they must enter the kernel as data, not
    literals). ``bh_offset`` is the global flattened (b*H + h) index of
    this producer's first mask row — 0 for a whole-mask producer; shard-
    local producers pass their shard's offset so the counters, and hence
    the bits, match the global mask's slice exactly.
    """
    k0, k1 = split_seed(seed)
    s = as_u32(np.uint32(int(salt) & 0xFFFFFFFF)
               if isinstance(salt, (int, np.integer)) else salt)
    off = as_u32(np.uint32(int(bh_offset) & 0xFFFFFFFF)
                 if isinstance(bh_offset, (int, np.integer)) else bh_offset)
    return jnp.stack([jnp.asarray(k0, jnp.uint32),
                      jnp.asarray(k1, jnp.uint32),
                      jnp.asarray(s, jnp.uint32),
                      jnp.asarray(off, jnp.uint32)])


def global_bh(local_bh, heads_local: int, heads_global: int, bh_offset):
    """Map a shard-local flattened (b, h) index to the global flattened
    counter index: shards own a (b_loc, h_loc) tile of the (B, H) mask
    plane, so  global = offset + local_b * H_global + local_h.  With
    heads_local == heads_global and offset 0 this is the identity —
    whole-mask producers take that path untouched."""
    if heads_local == heads_global:
        return as_u32(local_bh) + as_u32(bh_offset)
    lb = as_u32(local_bh)
    hl = np.uint32(heads_local)
    return (as_u32(bh_offset) + (lb // hl) * np.uint32(heads_global)
            + lb % hl)


def shard_plane_windows(batch: int, heads: int, batch_shards: int = 1,
                        head_shards: int = 1
                        ) -> Tuple[Tuple[int, int, int], ...]:
    """(bh_offset, batch_local, heads_local) of every shard-local
    producer's tile of the (B, H) mask plane under a (batch_shards x
    head_shards) split — the pure-int enumeration of what
    ``producer.shard_mask_tile`` computes per device from live mesh
    indices. The single source for three consumers that must agree:
    repro.analysis proves the windows tile the plane (MS-C4), the
    elastic-determinism tests slice the global mask with them, and a
    resharded restore re-derives the windows a new topology will emit.
    Dims that don't divide stay unsplit (that shard dimension is
    replicated, matching ``mask_plane_shards``'s divisibility guard)."""
    if batch % max(batch_shards, 1):
        batch_shards = 1
    if heads % max(head_shards, 1):
        head_shards = 1
    b_loc = batch // batch_shards
    h_loc = heads // head_shards
    return tuple((ib * b_loc * heads + ih * h_loc, b_loc, h_loc)
                 for ib in range(batch_shards)
                 for ih in range(head_shards))


def shard_bh_intervals(bh_offset: int, batch_local: int,
                       heads_local: int, heads_global: int
                       ) -> Tuple[Tuple[int, int], ...]:
    """Half-open intervals of GLOBAL flattened (b*H + h) counter indices
    a shard-local producer covers — the int mirror of ``global_bh``: a
    (b_loc, h_loc) tile starting at ``bh_offset`` owns h_loc contiguous
    indices per local batch row, strided by H_global. repro.analysis
    uses this to prove the shard windows tile the (B, H) mask plane."""
    off = int(bh_offset)
    if heads_local == heads_global:
        # identity mapping: one contiguous run of b_loc * h_loc rows
        return ((off, off + batch_local * heads_local),)
    return tuple((off + b * heads_global,
                  off + b * heads_global + heads_local)
                 for b in range(batch_local))


def tile_random_u32(q_start, k_start, bh, salt, k0, k1,
                    bq: int, bk: int, rounds: int = 7,
                    iota_fn=None) -> jnp.ndarray:
    """Random uint32 for an attention-score tile rows [q_start, q_start+bq)
    x cols [k_start, k_start+bk). bq must be a multiple of 4.

    One Philox call covers 4 consecutive q rows (the 4 output words), with
    lanes spanning k — all 128 VPU lanes stay busy and the word interleave
    is a cheap sublane reshape.
    """
    assert bq % 4 == 0, "tile q-size must be a multiple of 4"
    if iota_fn is None:
        iota_fn = _default_iota
    q4 = (as_u32(q_start) >> np.uint32(2)) + iota_fn((bq // 4, bk), 0)
    kk = as_u32(k_start) + iota_fn((bq // 4, bk), 1)
    w0, w1, w2, w3 = philox4x32(kk, q4, bh, salt, k0, k1, rounds)
    # out[4*g + w, k] = word_w[g, k]
    return jnp.stack([w0, w1, w2, w3], axis=1).reshape(bq, bk)


def tile_keep_mask(q_start, k_start, bh, salt, k0, k1, threshold,
                   bq: int, bk: int, rounds: int = 7,
                   iota_fn=None) -> jnp.ndarray:
    """Boolean keep-mask for a score tile (True = keep)."""
    u = tile_random_u32(q_start, k_start, bh, salt, k0, k1, bq, bk,
                        rounds, iota_fn)
    return u >= as_u32(threshold)


def pack_bits_q32(bits: jnp.ndarray) -> jnp.ndarray:
    """(bq, bk) bool -> (bq//32, bk) uint32; bit (q%32) of word q//32."""
    bq, bk = bits.shape
    assert bq % 32 == 0
    b = bits.reshape(bq // 32, 32, bk).astype(jnp.uint32)
    shifts = _default_iota((bq // 32, 32, bk), 1)
    return _or_disjoint(b << shifts, axis=1)


def unpack_bits_q32(packed: jnp.ndarray, bq: int) -> jnp.ndarray:
    """(bq//32, bk) uint32 -> (bq, bk) bool."""
    n32, bk = packed.shape
    assert n32 * 32 == bq
    rep = jnp.repeat(packed, 32, axis=0)  # rows q//32 expanded
    shifts = _default_iota((bq, bk), 0) % np.uint32(32)
    return ((rep >> shifts) & np.uint32(1)).astype(jnp.bool_)


def packed_tile_from_counters(q32_start, k_start, bh, salt, k0, k1,
                              threshold, rows32: int, bk: int,
                              rounds: int = 7, iota_fn=None) -> jnp.ndarray:
    """Directly produce packed words for rows32 packed-rows starting at
    q32_start (each packed row = 32 q rows). Returns (rows32, bk) uint32.

    Equivalent to pack_bits_q32(tile_keep_mask(q32_start*32, ...)) — used by
    the standalone-RNG and GEMM-fused kernels.
    """
    q_start = as_u32(q32_start) * np.uint32(32)
    bits = tile_keep_mask(q_start, k_start, bh, salt, k0, k1,
                          threshold, rows32 * 32, bk, rounds, iota_fn)
    return pack_bits_q32(bits)


def packed_rows_tile(r_start, k_start, sq32: int, salt, k0, k1, threshold,
                     rows: int, bk: int, rounds: int = 7,
                     iota_fn=None, heads_local: int = 0,
                     heads_global: int = 0, bh_offset=0) -> jnp.ndarray:
    """Packed mask words for ``rows`` packed-rows of the *flattened* 2D mask
    layout (BH*SQ32, SK), starting at global packed-row ``r_start`` and
    column ``k_start``. Rows may cross (b, h) boundaries: the head index is
    recovered per-row as r // SQ32 and the packed-row within the head as
    r % SQ32. Used by the GEMM-fused kernel, whose work assignment follows
    the GEMM grid rather than the attention layout.

    ``heads_local``/``heads_global``/``bh_offset`` (see ``global_bh``)
    remap the recovered (b, h) index when the producer runs shard-local
    on a (b_loc, h_loc) tile of the mask plane; the defaults (0, 0, 0)
    keep the whole-mask identity mapping.

    Bit-exact with packed_tile_from_counters / philox_mask_ref.
    """
    if iota_fn is None:
        iota_fn = _default_iota
    # one Philox call covers 4 q rows; a packed row (32 q) needs t = 0..7
    sub = iota_fn((rows * 8, bk), 0)          # r_local*8 + t
    r_local = sub >> np.uint32(3)
    t = sub & np.uint32(7)
    r_glob = as_u32(r_start) + r_local
    q32 = r_glob % np.uint32(sq32)
    bh = r_glob // np.uint32(sq32)
    if heads_local:
        bh = global_bh(bh, heads_local, heads_global or heads_local,
                       bh_offset)
    x1 = q32 * np.uint32(8) + t               # q//4
    kk = as_u32(k_start) + iota_fn((rows * 8, bk), 1)
    w0, w1, w2, w3 = philox4x32(kk, x1, bh, salt, k0, k1, rounds)
    thr = as_u32(threshold)
    packed = None
    for w, word in enumerate((w0, w1, w2, w3)):
        bits = (word >= thr).astype(jnp.uint32).reshape(rows, 8, bk)
        shifts = iota_fn((rows, 8, bk), 1) * np.uint32(4) + np.uint32(w)
        contrib = _or_disjoint(bits << shifts, axis=1)
        packed = contrib if packed is None else packed | contrib
    return packed


def _or_disjoint(words: jnp.ndarray, axis: int) -> jnp.ndarray:
    """OR-reduce uint32 words whose set bits are disjoint along ``axis``.
    Disjoint bits never carry, so the OR equals the sum; the sum runs in
    int32 (wrapping) because Mosaic implements no unsigned reductions."""
    total = jnp.sum(jax.lax.bitcast_convert_type(words, jnp.int32),
                    axis=axis)
    return jax.lax.bitcast_convert_type(total, jnp.uint32)


def _default_iota(shape, dimension: int) -> jnp.ndarray:
    """broadcasted_iota that works both under Pallas and plain jnp."""
    import jax.lax as lax
    return lax.broadcasted_iota(jnp.uint32, shape, dimension)
