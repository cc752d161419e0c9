"""Layer 1 of the static mask-safety verifier: Philox counter-space
analysis of a compiled DropoutSchedule.

Every mask producer in this repo draws from the same counter scheme
(philox_common): element (b, h, q, k) of layer L at step S reads counter
(x0=k, x1=q//4, x2=b*H+h, x3=salt(L)) under key step_seed(S). A compiled
schedule is mask-safe iff, per (layer, step) identity,

  * the producing grid steps write pairwise-disjoint rectangles of the
    packed plane that exactly tile it (no double draw, no dead bits),
  * shard-local producers' (bh_offset, b_loc, h_loc) windows exactly
    tile the global (B, H) counter plane,
  * every consumer has exactly one emission, the carried ``emit_stride``
    pipeline lands on the layer that consumes it, and
  * no two (layer, stream) identities fold to the same uint32 salt.

All of that is static data: this module symbolically enumerates the
counter intervals each ``HostAssignment`` will emit — fused dense grids,
grouped (e, i, j) linearizations, the standalone kernel's
(BH, q32, k)-block grid, the flash kernels' in-register replay grid,
carried pipelines, shard windows — and proves the properties by
interval arithmetic. No kernel (interpret or otherwise) executes.

Replay-planned cells (HOW_REPLAY) consume no emitted plane: the
consumer-side derivation is emitted as the layer's one live draw, and
any retained run-and-discard host plane is marked ``dropped`` — its
tiling and salt are still proven (the RNG really draws), but it does
not count toward the one-draw-per-consumer linkage.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.analysis import rules
from repro.config.base import (
    CARRIED_DROPOUT_SITES,
    AttentionKind,
    ModelConfig,
)
from repro.core import producer
from repro.core.overlap import SALT_ATTN, SALT_EMBED, SALT_RESID
from repro.core.schedule import DropoutSchedule, HostAssignment
from repro.kernels.gemm_rng import mask_emission_layout
from repro.kernels.philox import DEFAULT_BK, DEFAULT_ROWS32_BLK
from repro.kernels.philox_common import (
    fold_layer_salt,
    shard_bh_intervals,
    shard_plane_windows,
)

# (step, r0, r1, c0, c1): rows [r0, r1) x cols [c0, c1) of the local
# packed plane written by grid step ``step`` (-1 = monolithic producer)
Block = Tuple[int, int, int, int, int]


@dataclasses.dataclass(frozen=True)
class ShardWindow:
    """One shard-local producer's tile of the global (B, H) mask plane,
    in the coordinates the kernels consume (philox_common.global_bh)."""
    bh_offset: int
    batch_local: int
    heads_local: int
    heads_global: int

    def intervals(self) -> Tuple[Tuple[int, int], ...]:
        return shard_bh_intervals(self.bh_offset, self.batch_local,
                                  self.heads_local, self.heads_global)


@dataclasses.dataclass(frozen=True)
class MaskEmission:
    """One planned mask emission, fully resolved to counter space:
    identity (salt of the target layer), the shard windows it runs
    over, and the per-grid-step blocks of the local packed plane."""
    producer_layer: int           # -1 = standalone bootstrap
    target_layer: int             # consumer whose salt the bits use
    salt: int
    site: str
    how: str
    windows: Tuple[ShardWindow, ...]
    blocks: Tuple[Block, ...]
    rows_valid: int               # local plane: b_loc * h_loc * sq32
    sk: int
    # plane never consumed: a tail emission past the last layer, or a
    # retained run-and-discard host on a replay-planned cell (the RNG
    # still draws — tiling/salt are still proven — but the bits are
    # discarded, so it does not count toward the one-draw-per-consumer
    # linkage)
    dropped: bool = False
    infeasible: bool = False      # planned fused, but the grid can't host

    def describe(self) -> str:
        src = ("bootstrap" if self.producer_layer < 0
               else f"L{self.producer_layer}")
        return (f"{src} -> L{self.target_layer} under {self.site} "
                f"how={self.how}")


# --------------------------------------------------------------------------
# schedule -> emissions
# --------------------------------------------------------------------------

def _shard_windows(cfg: ModelConfig, sched: DropoutSchedule,
                   shard_local: bool) -> Tuple[ShardWindow, ...]:
    b, h = sched.batch, cfg.n_heads
    sh = sched.shard
    if not (shard_local and sh.active):
        return (ShardWindow(0, b, h, h),)
    # the single source of the window arithmetic: the same enumeration
    # producer.shard_mask_tile derives per device from live mesh indices
    return tuple(
        ShardWindow(off, b_loc, h_loc, h)
        for off, b_loc, h_loc in shard_plane_windows(
            b, h, sh.batch_shards, sh.head_shards))


def _fused_blocks(cfg: ModelConfig, sched: DropoutSchedule, site: str,
                  layer: int, grouped: bool
                  ) -> Tuple[Optional[Tuple[Block, ...]], int]:
    """(blocks, rows_valid) of a fused dense/grouped emission on the
    LOCAL plane — the exact work assignment gemm_rng's kernels derive at
    trace time, recomputed from the same shape arithmetic the schedule
    compiler planned with. blocks=None marks plan/kernel divergence."""
    seq = sched.seq
    sh = sched.shard
    shard_local = sh.policy_installed and sh.active
    b_loc = sched.batch // sh.batch_shards if shard_local else sched.batch
    h_loc = (cfg.n_heads // sh.head_shards if shard_local
             else cfg.n_heads)
    rows_valid = b_loc * h_loc * (seq // 32)
    first_dense = cfg.moe.first_dense_layers if cfg.moe else 0
    block_is_moe = cfg.moe is not None and layer >= first_dense
    if grouped:
        g = producer.grouped_host_shapes(
            cfg, sched.batch, seq, batch_shards=sh.batch_shards,
            head_shards=sh.head_shards,
            seq_dispatch=sched.moe_seq_dispatch,
            moe_block=block_is_moe).get(site)
        if g is None:
            return None, rows_valid
        e, c, kdim, n = g
        blocks = producer.pick_gemm_blocks(c, n, kdim)
        if blocks is None:
            return None, rows_valid
        bm, bn, _ = blocks
        n_steps = e * (c // bm) * (n // bn)
    else:
        dense_ffn = (True if (cfg.moe is not None and not block_is_moe
                              and site in ("ffn_up", "ffn_down"))
                     else None)
        gemm = producer.block_gemm_shapes(
            cfg, sched.batch, seq, dense_ffn=dense_ffn).get(site)
        if gemm is None:
            return None, rows_valid
        m, n, k = gemm
        # rows follow the batch shards, columns the head shards — the
        # same local grid _fused_capability planned and
        # _gemm_with_mask_sharded executes
        m_loc, n_loc, _k = (producer.shard_host_gemm(
            m, n, k, sh.batch_shards, sh.head_shards) if shard_local
            else (m, n, k))
        blocks = producer.pick_gemm_blocks(m_loc, n_loc, k)
        if blocks is None:
            return None, rows_valid
        bm, bn, _ = blocks
        n_steps = (m_loc // bm) * (n_loc // bn)
    layout = mask_emission_layout(
        n_steps, b_loc, h_loc, seq, seq,
        mask_block_cols=producer.mask_cols_cap(seq, seq))
    if layout is None:
        return None, rows_valid
    return tuple(layout.blocks()), rows_valid


def _standalone_blocks(cfg: ModelConfig, sched: DropoutSchedule
                       ) -> Tuple[Tuple[Block, ...], int]:
    """The standalone philox kernel's grid: (BH, SQ32/rows32_blk,
    SK/bk) steps, each writing one (rows32_blk, bk) tile of its head's
    packed rows (kernels/philox.py)."""
    seq = sched.seq
    sh = sched.shard
    shard_local = sh.policy_installed and sh.active
    b_loc = sched.batch // sh.batch_shards if shard_local else sched.batch
    h_loc = (cfg.n_heads // sh.head_shards if shard_local
             else cfg.n_heads)
    sq32 = seq // 32
    rows_blk = min(DEFAULT_ROWS32_BLK, sq32)
    bk = min(DEFAULT_BK, seq)
    n_q = sq32 // rows_blk
    n_k = seq // bk
    blocks: List[Block] = []
    s = 0
    for bh in range(b_loc * h_loc):
        for qi in range(n_q):
            r0 = bh * sq32 + qi * rows_blk
            for ki in range(n_k):
                blocks.append((s, r0, r0 + rows_blk, ki * bk,
                               (ki + 1) * bk))
                s += 1
    return tuple(blocks), b_loc * h_loc * sq32


def _replay_blocks(cfg: ModelConfig, sched: DropoutSchedule,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None
                   ) -> Tuple[Tuple[Block, ...], int]:
    """The flash-attention consumer's replay grid: one in-register
    tile_keep_mask derivation per (bh, q-block, k-block) kernel cell,
    each covering (block_q // 32) packed rows x block_k cols of the
    local plane. The default blocks resolve through the SAME tuned-table
    hook models/attention uses (128x128 with no table installed), so
    the verified replay grid is always the executed kernel grid. Proving
    this grid exactly tiles the plane is the replay analogue of proving
    a producer's emission grid double-draws nothing."""
    seq = sched.seq
    if block_q is None or block_k is None:
        dq, dk = producer.attn_flash_blocks(seq, seq)
        block_q = dq if block_q is None else block_q
        block_k = dk if block_k is None else block_k
    sh = sched.shard
    shard_local = sh.policy_installed and sh.active
    b_loc = sched.batch // sh.batch_shards if shard_local else sched.batch
    h_loc = (cfg.n_heads // sh.head_shards if shard_local
             else cfg.n_heads)
    sq32 = seq // 32
    rows_blk = block_q // 32
    n_q = seq // block_q
    n_k = seq // block_k
    blocks: List[Block] = []
    s = 0
    for bh in range(b_loc * h_loc):
        for qi in range(n_q):
            r0 = bh * sq32 + qi * rows_blk
            for ki in range(n_k):
                blocks.append((s, r0, r0 + rows_blk, ki * block_k,
                               (ki + 1) * block_k))
                s += 1
    return tuple(blocks), b_loc * h_loc * sq32


def _emission(cfg: ModelConfig, sched: DropoutSchedule, *,
              producer_layer: int, target_layer: int, site: str,
              how: str, shard_local: bool,
              cache: Dict, dropped: bool = False) -> MaskEmission:
    """Resolve one planned emission to counter space. ``cache`` shares
    block tuples across the (periodic) layers of one schedule."""
    key = (site, how,
           cfg.moe is not None
           and max(producer_layer, 0) >= cfg.moe.first_dense_layers)
    if key not in cache:
        if how == producer.HOW_GEMM:
            blocks, rows = _fused_blocks(cfg, sched, site,
                                         max(producer_layer, 0),
                                         grouped=False)
        elif how == producer.HOW_GEMM_GROUPED:
            blocks, rows = _fused_blocks(cfg, sched, site,
                                         max(producer_layer, 0),
                                         grouped=True)
        elif how == producer.HOW_STANDALONE:
            blocks, rows = _standalone_blocks(cfg, sched)
        elif how == producer.HOW_REPLAY:
            blocks, rows = _replay_blocks(cfg, sched)
        else:                      # HOW_XLA: one monolithic draw
            sh = sched.shard
            shard_ok = sh.policy_installed and sh.active and shard_local
            b_loc = (sched.batch // sh.batch_shards if shard_ok
                     else sched.batch)
            h_loc = (cfg.n_heads // sh.head_shards if shard_ok
                     else cfg.n_heads)
            rows = b_loc * h_loc * (sched.seq // 32)
            blocks = ((-1, 0, rows, 0, sched.seq),)
        cache[key] = (blocks, rows)
    blocks, rows = cache[key]
    return MaskEmission(
        producer_layer=producer_layer, target_layer=target_layer,
        salt=fold_layer_salt(target_layer, SALT_ATTN), site=site,
        how=how,
        windows=_shard_windows(cfg, sched, shard_local),
        blocks=blocks if blocks is not None else (),
        rows_valid=rows, sk=sched.seq,
        dropped=dropped or target_layer >= cfg.n_layers,
        infeasible=blocks is None)


def schedule_emissions(cfg: ModelConfig, sched: DropoutSchedule
                       ) -> Tuple[MaskEmission, ...]:
    """Enumerate every mask emission the schedule plans, resolved to
    counter space. Pure shape/int arithmetic — nothing executes."""
    if not sched.active:
        return ()
    out: List[MaskEmission] = []
    cache: Dict = {}
    sh = sched.shard
    for a in sched.assignments:
        if a.consumes and a.how == producer.HOW_REPLAY:
            # replay-planned consumer: the flash kernels re-derive the
            # plane in-register from position-based counters. Emit the
            # consumer-side derivation as this layer's (only live)
            # draw — the tiling proof covers the kernel replay grid.
            out.append(_emission(
                cfg, sched, producer_layer=a.layer,
                target_layer=a.layer, site=a.site, how=a.how,
                shard_local=a.sharded, cache=cache))
            if a.host_how and a.site not in CARRIED_DROPOUT_SITES:
                # retained run-and-discard in-layer host (qkv): its RNG
                # still draws under the GEMM (tiling/salt still proven)
                # but the bits are discarded before consumption
                out.append(_emission(
                    cfg, sched, producer_layer=a.layer,
                    target_layer=a.layer, site=a.site, how=a.host_how,
                    shard_local=sh.policy_installed and sh.active,
                    cache=cache, dropped=True))
        elif a.consumes and a.site not in CARRIED_DROPOUT_SITES:
            # in-layer producer (xla / qkv) or the standalone bootstrap:
            # emits its OWN layer's mask
            out.append(_emission(
                cfg, sched,
                producer_layer=(-1 if a.producer < 0 else a.layer),
                target_layer=a.layer, site=a.site, how=a.how,
                shard_local=a.sharded, cache=cache))
        if a.emit_site is not None:
            # carried pipeline: this block hosts layer
            # (a.layer + emit_stride)'s mask under one of its GEMMs.
            # When the target consumes by replay the plane is a retained
            # run-and-discard host (never consumed) — mark it dropped.
            tgt = a.layer + a.emit_stride
            tgt_replay = (tgt < cfg.n_layers
                          and sched.assignments[tgt].how
                          == producer.HOW_REPLAY)
            out.append(_emission(
                cfg, sched, producer_layer=a.layer,
                target_layer=tgt, site=a.emit_site,
                how=a.emit_how,
                shard_local=(a.emit_how != producer.HOW_XLA
                             and sh.policy_installed and sh.active),
                cache=cache, dropped=tgt_replay))
    return tuple(out)


# --------------------------------------------------------------------------
# draw counts
# --------------------------------------------------------------------------

# the kernel (pallas_call name) that draws a materialized emission; "xla"
# draws it with XLA ops
DRAW_KERNELS = {producer.HOW_GEMM: "gemm_rng",
                producer.HOW_GEMM_GROUPED: "gemm_rng_grouped",
                producer.HOW_STANDALONE: "philox_mask",
                producer.HOW_XLA: "xla"}
# the flash kernels that re-derive a replay-planned layer's bits: the
# forward, and in a train step both backward kernels
REPLAY_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


@dataclasses.dataclass(frozen=True)
class DrawCount:
    """One producer kernel's Philox keep decisions in a step, summed
    over every shard's window: ``live`` ones some consumer reads,
    ``dropped`` ones nothing reads (a run-and-discard host, a tail
    emission past the last layer)."""
    kernel: str
    calls: int                    # calls a step on each device
    live: int
    dropped: int

    @property
    def drawn(self) -> int:
        return self.live + self.dropped


def _replay_tile_runs(block: Block, sk: int, window: int) -> bool:
    """Whether the causal flash grid runs this replay cell: the kernels
    skip (and draw nothing for) a tile whose every key lies after its
    last query, or, with a sliding window, before its first."""
    _, r0, r1, c0, c1 = block
    q_lo = (r0 % (sk // 32)) * 32
    q_hi = q_lo + (r1 - r0) * 32 - 1
    return c0 <= q_hi and (window <= 0 or c1 - 1 > q_lo - window)


def draw_counts(cfg: ModelConfig, sched: DropoutSchedule,
                train: bool = True) -> Tuple[DrawCount, ...]:
    """Calls and keep decisions of each producer kernel in one step
    (``train``: forward and backward, else the forward alone), from the
    schedule's emissions. A materialized emission draws its whole plane
    once; a replay-planned layer's bits are drawn again by every flash
    kernel that re-derives them, over the tiles its causal grid runs."""
    totals: Dict[str, List[int]] = {}
    for em in schedule_emissions(cfg, sched):
        if em.how == producer.HOW_REPLAY:
            kinds = cfg.layer_kinds()
            window = (cfg.local_window
                      if kinds[em.target_layer] == AttentionKind.LOCAL
                      else 0)
            blocks = [b for b in em.blocks
                      if _replay_tile_runs(b, em.sk, window)]
            kernels = REPLAY_KERNELS if train else REPLAY_KERNELS[:1]
        else:
            blocks = em.blocks
            kernels = (DRAW_KERNELS[em.how],)
        drawn = 32 * len(em.windows) * sum(
            (r1 - r0) * (c1 - c0) for _, r0, r1, c0, c1 in blocks)
        for k in kernels:
            calls, live, dropped = totals.setdefault(k, [0, 0, 0])
            totals[k] = [calls + 1, live + (0 if em.dropped else drawn),
                         dropped + (drawn if em.dropped else 0)]
    return tuple(DrawCount(k, *v) for k, v in totals.items())


def live_draw_share(counts: Tuple[DrawCount, ...]) -> Optional[float]:
    """Keep decisions some consumer reads over all drawn, or None when
    nothing draws."""
    drawn = sum(c.drawn for c in counts)
    return sum(c.live for c in counts) / drawn if drawn else None


def explain_draws(counts: Tuple[DrawCount, ...]) -> str:
    """One line for the launch log, printed beside the schedule."""
    if not counts:
        return "dropout draws a step: none"
    parts = [f"{c.kernel} {c.calls} calls {c.live / 1e6:.1f}M live "
             f"{c.dropped / 1e6:.1f}M dropped" for c in counts]
    return ("dropout draws a step: " + " | ".join(parts)
            + f" | live share {100 * live_draw_share(counts):.1f}%")


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------

def _check_plane_tiling(em: MaskEmission) -> List[rules.Finding]:
    """Exact-cover proof for one emission's local packed plane: every
    rectangle in bounds, pairwise disjoint (incremental sweep over row
    bands), and total area == plane area. Disjoint + full area + in
    bounds ⇔ exact tiling."""
    plane = em.rows_valid * em.sk
    found: List[rules.Finding] = []
    area = 0
    add: Dict[int, List[Tuple[int, int, int, int]]] = {}
    rem: Dict[int, List[Tuple[int, int, int, int]]] = {}
    for s, r0, r1, c0, c1 in em.blocks:
        if r0 < 0 or c0 < 0 or r1 > em.rows_valid or c1 > em.sk \
                or r0 >= r1 or c0 >= c1:
            found.append(rules.Finding(
                rules.EMISSION_GAP, f"{em.describe()}: grid step {s} "
                f"writes rows [{r0},{r1}) x cols [{c0},{c1}) outside "
                f"the {em.rows_valid}x{em.sk} packed plane",
                layer=em.producer_layer, other_layer=em.target_layer))
            continue
        area += (r1 - r0) * (c1 - c0)
        iv = (c0, c1, s, r0)
        add.setdefault(r0, []).append(iv)
        rem.setdefault(r1, []).append(iv)
    # sweep row cuts: within each elementary row band the active blocks'
    # column intervals must be pairwise disjoint. The active set only
    # changes at a cut, so disjointness is re-checked per cut, not per
    # row.
    active: Dict[Tuple[int, int, int, int], bool] = {}
    for cut in sorted(set(add) | set(rem)):
        for iv in rem.get(cut, ()):
            active.pop(iv, None)
        for iv in add.get(cut, ()):
            active[iv] = True
        ivals = sorted(active)
        for (c0a, c1a, sa, _), (c0b, c1b, sb, _) in zip(ivals,
                                                        ivals[1:]):
            if c1a > c0b:
                found.append(rules.Finding(
                    rules.COUNTER_OVERLAP,
                    f"{em.describe()}: grid steps {sa} and {sb} both "
                    f"draw packed rows around {cut}, cols "
                    f"[{c0b},{min(c1a, c1b)}) — double draw",
                    layer=em.producer_layer,
                    other_layer=em.target_layer))
                return found          # one pair is enough evidence
    if not found and area < plane:
        found.append(rules.Finding(
            rules.EMISSION_GAP,
            f"{em.describe()}: grid covers {area} of {plane} packed "
            f"words — {plane - area} dead (never-drawn) mask bits",
            layer=em.producer_layer, other_layer=em.target_layer))
    return found


def _check_shard_windows(em: MaskEmission, batch: int, n_heads: int
                         ) -> List[rules.Finding]:
    """The emission's shard windows must exactly tile the global (B, H)
    counter plane: merge every window's global_bh intervals and demand
    one gapless, overlap-free run [0, B*H)."""
    ivals = sorted(iv for w in em.windows for iv in w.intervals())
    plane = batch * n_heads
    pos = 0
    for lo, hi in ivals:
        if lo < pos:
            return [rules.Finding(
                rules.SHARD_WINDOW_MISMATCH,
                f"{em.describe()}: shard windows double-draw global "
                f"counter rows [{lo},{min(pos, hi)}) of the (B={batch},"
                f" H={n_heads}) plane",
                layer=em.producer_layer, other_layer=em.target_layer)]
        if lo > pos:
            return [rules.Finding(
                rules.SHARD_WINDOW_MISMATCH,
                f"{em.describe()}: no shard window draws global counter"
                f" rows [{pos},{lo}) of the (B={batch}, H={n_heads}) "
                f"plane", layer=em.producer_layer,
                other_layer=em.target_layer)]
        pos = hi
    if pos != plane:
        return [rules.Finding(
            rules.SHARD_WINDOW_MISMATCH,
            f"{em.describe()}: shard windows cover [0,{pos}) of the "
            f"[0,{plane}) global (b*H+h) counter range",
            layer=em.producer_layer, other_layer=em.target_layer)]
    return []


def _check_consumer_linkage(sched: DropoutSchedule,
                            emissions: Tuple[MaskEmission, ...]
                            ) -> List[rules.Finding]:
    found: List[rules.Finding] = []
    by_target: Dict[int, List[MaskEmission]] = {}
    for em in emissions:
        if em.dropped:
            # run-and-discard plane: RNG draws but nothing consumes the
            # bits, so it is neither a live draw nor a stride target
            continue
        by_target.setdefault(em.target_layer, []).append(em)
    for a in sched.assignments:
        if not a.consumes:
            # a non-consuming layer must not be the target of a live
            # emission (a stride bug pointing a pipeline at a mixer)
            for em in by_target.get(a.layer, ()):
                found.append(rules.Finding(
                    rules.STRIDE_MISMATCH,
                    f"{em.describe()}: target layer L{a.layer} "
                    f"({a.kind}) consumes no attention-score mask",
                    layer=em.producer_layer, other_layer=a.layer))
            continue
        ems = by_target.get(a.layer, [])
        if not ems:
            found.append(rules.Finding(
                rules.EMISSION_GAP,
                f"L{a.layer} consumes a mask but no assignment emits "
                f"for it (expected producer "
                + ("bootstrap" if a.producer < 0 else f"L{a.producer}")
                + ")", layer=a.layer))
        elif len(ems) > 1:
            found.append(rules.Finding(
                rules.COUNTER_OVERLAP,
                f"L{a.layer}'s mask is drawn {len(ems)} times ("
                + "; ".join(em.describe() for em in ems)
                + ") — double draw of one counter window",
                layer=a.layer, other_layer=ems[0].producer_layer))
        if a.site in CARRIED_DROPOUT_SITES and a.producer >= 0:
            p = sched.assignments[a.producer]
            if p.emit_site is None:
                # a replay consumer tolerates a cleared pipeline (it
                # re-derives in-register); a materialized one does not
                if a.how != producer.HOW_REPLAY:
                    found.append(rules.Finding(
                        rules.STRIDE_MISMATCH,
                        f"L{a.layer} consumes from L{a.producer} but "
                        "that block's emission does not exist",
                        layer=a.producer, other_layer=a.layer))
            elif p.layer + p.emit_stride != a.layer:
                # applies even under replay: a retained run-and-discard
                # host is only contract-identical if its pipeline still
                # lands on the consumer it was planned for
                found.append(rules.Finding(
                    rules.STRIDE_MISMATCH,
                    f"L{a.layer} consumes from L{a.producer} but that "
                    f"block's emission targets "
                    f"L{p.layer + p.emit_stride}",
                    layer=a.producer, other_layer=a.layer))
    return found


def _check_salts(cfg: ModelConfig) -> List[rules.Finding]:
    seen: Dict[int, Tuple[int, str]] = {}
    found: List[rules.Finding] = []
    streams = (("attn", SALT_ATTN), ("resid", SALT_RESID),
               ("embed", SALT_EMBED))
    for layer in range(cfg.n_layers):
        for name, stream in streams:
            s = fold_layer_salt(layer, stream)
            if s in seen:
                o_layer, o_name = seen[s]
                found.append(rules.Finding(
                    rules.SALT_COLLISION,
                    f"salt({layer}, {name}) == salt({o_layer}, "
                    f"{o_name}) == {s:#010x}: two RNG streams share "
                    "one Philox counter identity",
                    layer=layer, other_layer=o_layer))
            else:
                seen[s] = (layer, name)
    return found


def check_emissions(cfg: ModelConfig, sched: DropoutSchedule,
                    emissions: Tuple[MaskEmission, ...]
                    ) -> List[rules.Finding]:
    """Run every counter-space check over derived emissions."""
    found: List[rules.Finding] = []
    # block tuples are shared across a schedule's (periodic) layers —
    # prove each distinct plane layout once
    clean_planes: set = set()
    for em in emissions:
        if em.infeasible:
            found.append(rules.Finding(
                rules.REGION_MISMATCH,
                f"{em.describe()}: planned as a fused host but the "
                "GEMM grid cannot host the mask (Region 3 at run "
                "time) — schedule/kernel divergence",
                layer=em.producer_layer, other_layer=em.target_layer))
            continue
        plane_key = (id(em.blocks), em.rows_valid, em.sk)
        if plane_key not in clean_planes:
            tiling = _check_plane_tiling(em)
            found.extend(tiling)
            if not tiling:
                clean_planes.add(plane_key)
        found.extend(_check_shard_windows(em, sched.batch, cfg.n_heads))
    found.extend(_check_consumer_linkage(sched, emissions))
    found.extend(_check_salts(cfg))
    return found


def analyze_schedule(cfg: ModelConfig, sched: DropoutSchedule,
                     cell: str = "") -> rules.Report:
    """Counter-space verdict for one compiled schedule."""
    emissions = schedule_emissions(cfg, sched)
    findings = check_emissions(cfg, sched, emissions)
    return rules.Report(
        cell=cell or f"{sched.model} site={sched.plan.site} "
                     f"dtype={sched.plan.gemm_dtype}",
        findings=tuple(findings), checked_emissions=len(emissions))


# --------------------------------------------------------------------------
# mutation harness (tests + `lint --mutate`)
# --------------------------------------------------------------------------

def corrupt_emissions(emissions: Tuple[MaskEmission, ...], kind: str
                      ) -> Tuple[MaskEmission, ...]:
    """Inject one counter-space corruption into a derived emission set —
    the negative half of the analyzer's test surface. ``kind``:
      "counter-overlap" — one grid step re-draws another's rectangle
      "emission-gap"    — one grid step's rectangle is never drawn
      "shard-window"    — one producer's bh_offset is off by one
      "reshard-window"  — a resharded restore re-derives a window from
                          the OLD topology: one shard's window is
                          replaced by a copy of another's, so one tile
                          of the (B, H) plane is double-drawn and
                          another never drawn
      "replay-counter-drift" — a replay consumer re-derives from a
                          drifted counter base (bh_offset off by one):
                          its in-register draw no longer coincides with
                          the planned draw, so the target layer's bits
                          come from two disagreeing counter windows
    """
    if not emissions:
        raise ValueError("no emissions to corrupt (inert schedule)")
    idx = max(range(len(emissions)),
              key=lambda i: len(emissions[i].blocks))
    em = emissions[idx]
    if kind == "counter-overlap":
        s, r0, r1, c0, c1 = em.blocks[0]
        mutated = dataclasses.replace(
            em, blocks=em.blocks + ((len(em.blocks), r0, r1, c0, c1),))
    elif kind == "emission-gap":
        mutated = dataclasses.replace(em, blocks=em.blocks[:-1])
    elif kind == "shard-window":
        w = em.windows[0]
        mutated = dataclasses.replace(
            em, windows=(dataclasses.replace(
                w, bh_offset=w.bh_offset + 1),) + em.windows[1:])
    elif kind == "reshard-window":
        # pick an emission with >= 2 windows (a genuinely sharded one)
        for idx, em in enumerate(emissions):
            if len(em.windows) >= 2:
                break
        else:
            raise ValueError(
                "reshard-window needs a sharded emission (>= 2 shard "
                "windows); compile the schedule on a multi-shard "
                "topology first")
        mutated = dataclasses.replace(
            em, windows=(em.windows[0], em.windows[0]) + em.windows[2:])
    elif kind == "replay-counter-drift":
        # the consumer's kernels replay from a drifted counter base:
        # alongside the planned draw the target now sees a second,
        # disagreeing derivation — a double draw of its counter window
        for idx, em in enumerate(emissions):
            if em.how == producer.HOW_REPLAY:
                break
        else:
            raise ValueError(
                "replay-counter-drift needs a replay-planned cell "
                "(HOW_REPLAY consumption); compile with "
                "attn_impl='pallas' on a replay-feasible schedule "
                "first")
        w = em.windows[0]
        drifted = dataclasses.replace(
            em, windows=(dataclasses.replace(
                w, bh_offset=w.bh_offset + 1),) + em.windows[1:])
        return emissions[:idx] + (em, drifted) + emissions[idx + 1:]
    else:
        raise ValueError(f"unknown corruption {kind!r}")
    return emissions[:idx] + (mutated,) + emissions[idx + 1:]


def corrupt_schedule_stride(sched: DropoutSchedule) -> DropoutSchedule:
    """Corrupt the first emitting HostAssignment's ``emit_stride`` (the
    wrong-stride pipeline bug the linter must catch)."""
    asgs = list(sched.assignments)
    for i, a in enumerate(asgs):
        if a.emit_site is not None:
            asgs[i] = dataclasses.replace(a,
                                          emit_stride=a.emit_stride + 1)
            return dataclasses.replace(sched, assignments=tuple(asgs))
    raise ValueError("schedule has no emitting assignment to corrupt")
