"""Compiled per-layer dropout schedule: plan → compile → execute.

The paper's claim is that dropout RNG can hide under *any* producer GEMM
with headroom. A one-string knob (``DropoutPlanConfig.site``) resolved
lazily inside the trace cannot express that: mixed-pattern stacks
(Griffin's (R, R, A)) need per-layer consumer routing, sharded meshes
need per-shard host planning, and serving-side mask reuse needs a stable
mask identity — all static decisions, all previously scattered through
trace-time branches in ``models/transformer.py`` / ``models/layers.py``.

``compile_schedule`` makes every one of those decisions ONCE, ahead of
trace, and freezes them into a hashable ``DropoutSchedule``: one
``HostAssignment`` per layer recording which layer's mask is consumed,
which GEMM site hosts its production, which physical producer realizes
it (dense fused kernel / GROUPED fused kernel for MoE-expert and RWKV
channel-mix GEMMs / standalone kernel / XLA ops), whether production
runs shard-local, and — when a fused kernel was NOT chosen — why. The model
executes by schedule lookup; ``DropoutPlanConfig.site`` survives as
sugar that compiles to a uniform schedule. ``explain()`` renders the
whole plan for dry-runs and train-loop logs, so a silent Region-3 or
philox_bits=8 fallback is visible before a single step runs.

Scheduling follows the deterministic ahead-of-trace style of DASH
(arXiv 2601.21824) and the schedule/execution split argued by the
CUTLASS FlashAttention-2 case study (arXiv 2312.11918).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

from repro.config.base import (
    CARRIED_DROPOUT_SITES,
    AttentionKind,
    DropoutPlanConfig,
    FFNKind,
    ModelConfig,
)
from repro.core import producer
from repro.core.overlap import DropoutPlan

HOW_GEMM = producer.HOW_GEMM
HOW_GEMM_GROUPED = producer.HOW_GEMM_GROUPED
HOW_STANDALONE = producer.HOW_STANDALONE
HOW_XLA = producer.HOW_XLA
HOW_REPLAY = producer.HOW_REPLAY

_ATTN = (AttentionKind.FULL, AttentionKind.LOCAL)


@dataclasses.dataclass(frozen=True)
class ShardInfo:
    """Hashable distillation of the sharding policy's mask-plane layout:
    how many ways the mask's (b, h) dims split, and over which mesh axes.
    Derived once by ``shard_info``; the execution layer rebuilds the live
    mesh context from the installed policy (meshes don't hash)."""
    batch_shards: int = 1
    head_shards: int = 1
    batch_axes: Tuple[str, ...] = ()
    head_axes: Tuple[str, ...] = ()
    policy_installed: bool = False

    @property
    def active(self) -> bool:
        """True when shard-local production is worthwhile: some mask dim
        actually splits over the mesh."""
        return self.batch_shards * self.head_shards > 1


def shard_info(policy, batch: int, n_heads: int) -> ShardInfo:
    """Distill a ShardingPolicy into the mask plane's shard layout."""
    if policy is None:
        return ShardInfo()
    from repro.distributed.sharding import mask_plane_shards
    (b_axes, nb), (h_axes, nh) = mask_plane_shards(policy, batch,
                                                   n_heads)
    return ShardInfo(batch_shards=nb, head_shards=nh, batch_axes=b_axes,
                     head_axes=h_axes, policy_installed=True)


@dataclasses.dataclass(frozen=True)
class HostAssignment:
    """One layer's slot in the compiled schedule.

    Consumption side (this layer's OWN mask):
      consumes — this layer applies attention-score dropout at all
      site     — producer site class ("xla" | "qkv" | carried sites |
                 "standalone" for the bootstrap / non-carried remainder)
      producer — layer index hosting this layer's mask: ``layer`` for
                 in-layer sites, the previous attention layer for
                 carried sites, -1 for the standalone bootstrap
      how      — planned physical producer (HOW_GEMM / HOW_STANDALONE /
                 HOW_XLA), or HOW_REPLAY: the flash-attention consumer
                 re-derives the bits in-register from the plan's
                 counters and NO plane is materialized for this layer
      host_how — replay only: the retained run-and-discard host
                 realization (HOW_GEMM / HOW_GEMM_GROUPED — the GEMM
                 still hides the RNG; "" = no host GEMM retained)
      sharded  — production runs shard-local inside jax.shard_map
                 (for HOW_REPLAY: consumption replays shard-local
                 counter windows inside the attention shard_map)
      reason   — why ``how`` degraded from the fused kernel ("" = fused
                 or the site never targets the kernel)

    Emission side (a DOWNSTREAM layer's mask hosted by this block):
      emit_site   — which of this block's GEMMs hosts it (None = none)
      emit_stride — consumer layer = this layer + emit_stride (0 = none)
      emit_how    — planned physical producer of the emission
      emit_reason — why the emission degraded ("" = fused)
    """
    layer: int
    kind: str
    consumes: bool = False
    site: str = "none"
    producer: int = -1
    how: str = HOW_XLA
    host_how: str = ""
    sharded: bool = False
    reason: str = ""
    emit_site: Optional[str] = None
    emit_stride: int = 0
    emit_how: str = ""
    emit_reason: str = ""


@dataclasses.dataclass(frozen=True)
class DropoutSchedule:
    """Frozen, hashable artifact of ``compile_schedule``. Equality and
    hash cover every scheduling decision, so the schedule can key jit
    caches and serving-side mask caches, and "same inputs → same
    schedule" is testable as plain object equality."""
    model: str
    plan: DropoutPlanConfig          # original plan (site may be "auto")
    resolved_site: str               # concrete site after resolution
    batch: int
    seq: int
    attn_impl: str
    shard: ShardInfo
    carried: bool
    assignments: Tuple[HostAssignment, ...]
    headroom: Tuple[Tuple[str, float], ...] = ()   # auto-ranking table
    # which MoE dispatch layout the grouped-host grid was planned for;
    # forward() fails fast on a Runtime.moe_seq_dispatch mismatch
    # instead of silently executing a schedule whose expert-GEMM grid
    # belongs to the other layout
    moe_seq_dispatch: bool = False

    # ---------------------------------------------------------- lookup
    @property
    def active(self) -> bool:
        """Overlap-mode plan with at least one mask consumer."""
        return any(a.consumes for a in self.assignments)

    @property
    def sharded(self) -> bool:
        return any(a.sharded for a in self.assignments)

    @property
    def replay(self) -> bool:
        """True when consumption is counter-replay (zero-HBM masks):
        the flash kernels re-derive bits in-register, no plane is
        carried or fed to attention. Uniform across consumers by
        construction (the feasibility gates are schedule-global)."""
        return any(a.how == HOW_REPLAY for a in self.assignments)

    @property
    def first_consumer(self) -> int:
        for a in self.assignments:
            if a.consumes:
                return a.layer
        return -1

    def for_layer(self, layer: int) -> HostAssignment:
        return self.assignments[layer]

    def mask_key(self, layer: int, step: int) -> Tuple[int, ...]:
        """Canonical identity of one layer-step packed mask: (seed,
        salt, layer, step) plus the plan knobs the bits depend on (keep
        threshold, Philox rounds/width). Two schedules agreeing on this
        key generate bit-identical masks whatever site/how/shard
        produced them — the invariant serving-side mask reuse keys on;
        plans differing only in host site or GEMM dtype share keys."""
        from repro.kernels.philox_common import threshold_from_p
        plan = DropoutPlan(self.plan)
        return (int(plan.step_seed(int(step))),
                int(plan.salt(int(layer))), int(layer), int(step),
                threshold_from_p(self.plan.p), self.plan.philox_rounds,
                self.plan.philox_bits)

    # ------------------------------------------------------- telemetry
    def records(self) -> Tuple[Tuple[str, str, str, str], ...]:
        """Deduplicated (site, how, gemm_dtype, note) scheduling records
        — the compiled replacement for the old mutable trace-event
        global: attached to the artifact, identical across retraces."""
        dtype = self.plan.gemm_dtype
        seen, out = set(), []
        for a in self.assignments:
            rows = []
            if a.consumes:
                rows.append((a.site, a.how, dtype, a.reason))
            if a.emit_site is not None:
                rows.append((a.emit_site, a.emit_how, dtype,
                             a.emit_reason))
            for r in rows:
                if r not in seen:
                    seen.add(r)
                    out.append(r)
        return tuple(out)

    def explain(self) -> str:
        """Human-readable rendering of every per-layer decision — logged
        by the train loop and printed by launch/dryrun.py so fallbacks
        are visible before any step runs."""
        p = self.plan
        head = (f"dropout schedule: model={self.model} "
                f"batch={self.batch} seq={self.seq} mode={p.mode} "
                f"p={p.p} site={p.site}")
        if p.site != self.resolved_site:
            head += f" -> {self.resolved_site}"
        head += (f" gemm_dtype={p.gemm_dtype} impl={self.attn_impl} "
                 f"carried={'yes' if self.carried else 'no'}")
        lines = [head]
        if self.shard.policy_installed:
            s = self.shard
            lines.append(
                f"  sharding: mask plane (b x h) = "
                f"{s.batch_shards} x {s.head_shards} shards "
                f"(batch axes {list(s.batch_axes)}, "
                f"head axes {list(s.head_axes)}) -> "
                + ("shard-local producers" if self.sharded
                   else "replicated/XLA producers"))
        for site, hr in self.headroom:
            lines.append(f"  auto candidate {site}: "
                         f"headroom {hr * 1e6:+.2f}us")
        if not self.active:
            lines.append("  inert: no attention-score dropout to "
                         "schedule")
            return "\n".join(lines)
        for a in self.assignments:
            if not a.consumes:
                lines.append(f"  L{a.layer:<3d} {a.kind:<9s} -")
                continue
            src = ("bootstrap" if a.producer < 0
                   else f"L{a.producer}" if a.producer != a.layer
                   else "in-layer")
            row = (f"  L{a.layer:<3d} {a.kind:<9s} "
                   f"mask<-{src}:{a.site} how={a.how}")
            if a.host_how:
                row += f" host={a.host_how}"
            if a.sharded:
                row += " shard-local"
            if a.reason:
                row += f" ({a.reason})"
            if a.emit_site is not None:
                tgt = a.layer + a.emit_stride
                tgt_s = f"L{tgt}" if tgt < len(self.assignments) \
                    else "dropped"
                row += (f" | emits->{tgt_s} under {a.emit_site} "
                        f"how={a.emit_how}")
                # standalone-fallback layers share one fallback reason
                # between the consume and emit halves — print it once
                if a.emit_reason and a.emit_reason != a.reason:
                    row += f" ({a.emit_reason})"
            lines.append(row)
        return "\n".join(lines)

    def summary(self) -> Dict:
        """Machine-readable digest for BENCH_block.json / dry-run
        reports: per-layer host assignments plus the knobs that chose
        them, so perf records are attributable across PRs."""
        return {
            "model": self.model,
            "site": self.plan.site,
            "resolved_site": self.resolved_site,
            "gemm_dtype": self.plan.gemm_dtype,
            "philox_bits": self.plan.philox_bits,
            "attn_impl": self.attn_impl,
            "batch": self.batch,
            "seq": self.seq,
            "carried": self.carried,
            "sharded": self.sharded,
            "moe_seq_dispatch": self.moe_seq_dispatch,
            "shards": [self.shard.batch_shards, self.shard.head_shards],
            "layers": [
                {"layer": a.layer, "kind": a.kind, "site": a.site,
                 "producer": a.producer, "how": a.how,
                 "sharded": a.sharded,
                 **({"host_how": a.host_how} if a.host_how else {}),
                 **({"reason": a.reason} if a.reason else {}),
                 **({"emit_site": a.emit_site,
                     "emit_to": a.layer + a.emit_stride,
                     "emit_how": a.emit_how} if a.emit_site else {})}
                for a in self.assignments if a.consumes
            ],
        }


# --------------------------------------------------------------------------
# compilation
# --------------------------------------------------------------------------

def _next_attn_stride(kinds: Tuple[AttentionKind, ...], period: int,
                      l: int) -> int:
    """Distance from layer l to the next attention layer in the periodic
    extension of the block pattern. For the last attention layer this
    walks past n_layers (the scan compiles one body, so the tail
    emission happens and is dropped — same as the uniform case)."""
    for d in range(1, period + 1):
        if kinds[(l + d) % period] in _ATTN:
            return d
    return 0


def _host_gemm_shape(cfg: ModelConfig, batch: int, seq: int, site: str,
                     dense_ffn: Optional[bool] = None
                     ) -> Optional[Tuple[int, int, int]]:
    """(m, n, k) of the dense GEMM class hosting ``site``, or None when
    the block has no such GEMM (MoE / RWKV channel-mix FFNs host through
    the GROUPED kernel — see ``_grouped_capability``)."""
    shapes = producer.block_gemm_shapes(cfg, batch, seq,
                                        dense_ffn=dense_ffn)
    return shapes.get(site)


def _kernel_host_gates(plan: DropoutPlan, cfg: ModelConfig, batch: int,
                       seq: int, shard: ShardInfo, attn_impl: str):
    """The gates every kernel-realized host (dense fused AND grouped)
    must clear, shared so dense and grouped planning can never judge
    the same model by different rules. Returns a (how, sharded, reason)
    early-out, or None plus the (b_loc, h_loc) mask tile when the gates
    pass: (early_out, b_loc, h_loc)."""
    if attn_impl != "pallas":
        return (HOW_XLA, False, "impl != pallas (no fused kernels)"), 0, 0
    reason = producer.mask_kernel_unsupported_reason(plan, seq, seq)
    if reason is not None:
        return (HOW_XLA, False, reason), 0, 0
    if shard.policy_installed and not shard.active:
        return (HOW_XLA, False,
                "mask (b, h) not shardable on this mesh"), 0, 0
    return (None, batch // shard.batch_shards,
            cfg.n_heads // shard.head_shards)


def _fused_capability(plan: DropoutPlan, cfg: ModelConfig, batch: int,
                      seq: int, site: str, shard: ShardInfo,
                      attn_impl: str, dense_ffn: Optional[bool] = None
                      ) -> Tuple[str, bool, str]:
    """Decide (how, sharded, reason) for hosting one mask under the
    ``site`` GEMM of one block — the single ahead-of-trace capability
    judgment replacing the old in-trace fuse_ok/allow_fused threading.

    Shard-aware: with a policy installed the fused kernel runs
    shard-local on the per-shard (b_loc, h_loc) mask slice and the
    per-shard GEMM rows, so capability (tiling, Region 3) is judged on
    LOCAL shapes. The position-based counter scheme keeps shard-local
    bits exactly equal to the global mask's slice."""
    early, b_loc, h_loc = _kernel_host_gates(plan, cfg, batch, seq,
                                             shard, attn_impl)
    if early is not None:
        return early
    sharded = shard.policy_installed
    gemm = _host_gemm_shape(cfg, batch, seq, site, dense_ffn=dense_ffn)
    if gemm is None:
        return (HOW_STANDALONE, sharded,
                f"no hostable {site} GEMM in this block")
    m, n, k = gemm
    # GEMM rows follow the batch shards, columns the head shards —
    # the exact local grid _gemm_with_mask_sharded will execute
    m_loc, n_loc, _k = producer.shard_host_gemm(
        m, n, k, shard.batch_shards, shard.head_shards)
    blocks = producer.pick_gemm_blocks(m_loc, n_loc, k)
    if blocks is None:
        return (HOW_XLA, False,
                f"GEMM ({m_loc},{n_loc},{k}) does not tile")
    from repro.kernels.gemm_rng import mask_layout_feasible
    bm, bn, _ = blocks
    n_steps = (m_loc // bm) * (n_loc // bn)
    if not mask_layout_feasible(
            n_steps, b_loc, h_loc, seq, seq,
            mask_block_cols=producer.mask_cols_cap(seq, seq)):
        return (HOW_STANDALONE, sharded,
                f"Region 3: GEMM ({m_loc},{n_loc},{k}) too small for "
                f"{b_loc}x{h_loc}x{seq}x{seq} mask")
    if plan.gemm_dtype == "fp8":
        from repro.kernels import quant
        if not quant.have_fp8():
            # still the fused host, but the executor runs it in f32 —
            # keep that attribution visible in records()/explain()
            return (HOW_GEMM, sharded,
                    "fp8 unavailable in this JAX build; f32 host")
    return HOW_GEMM, sharded, ""


def _grouped_capability(plan: DropoutPlan, cfg: ModelConfig, batch: int,
                        seq: int, site: str, shard: ShardInfo,
                        attn_impl: str, moe_seq_dispatch: bool = False,
                        block_is_moe: Optional[bool] = None
                        ) -> Tuple[str, bool, str]:
    """(how, sharded, reason) for hosting one mask under the GROUPED
    GEMM of a block whose FFN has no dense 2D host: the MoE expert
    einsum or the RWKV channel-mix key/value GEMM (E=1). Feasibility is
    judged on EXPERT-LOCAL shapes (producer.grouped_host_shapes mirrors
    the dispatch arithmetic of models/moe.py, shrunk to the per-shard
    token count); the emission grid is Philox-counter-indexed, so the
    permuted token layout never enters the judgment — only the combined
    grid's step count does. Each infeasible shape reports a reason
    naming ITS block kind (MoE expert vs RWKV channel-mix), so a mixed
    stack's explain() attributes every fallback to the right layer.
    ``block_is_moe`` is the caller's LAYER-LOCAL judgment — a MoE
    stack's first-dense layers plan on their own (E=1 channel-mix)
    grid, not the expert grid."""
    if block_is_moe is None:
        block_is_moe = cfg.moe is not None
    kind_name = "MoE expert" if block_is_moe else "RWKV channel-mix"
    early, b_loc, h_loc = _kernel_host_gates(plan, cfg, batch, seq,
                                             shard, attn_impl)
    if early is not None:
        return early
    sharded = shard.policy_installed
    g = producer.grouped_host_shapes(
        cfg, batch, seq, batch_shards=shard.batch_shards,
        head_shards=shard.head_shards,
        seq_dispatch=moe_seq_dispatch,
        moe_block=block_is_moe).get(site)
    if g is None:
        return (HOW_STANDALONE, sharded,
                f"no hostable {site} GEMM in this block")
    e, c, kdim, n = g
    feasible, blocks = producer.grouped_layout_feasible(
        e, c, kdim, n, b_loc, h_loc, seq, seq)
    if blocks is None:
        return (HOW_STANDALONE, sharded,
                f"{kind_name} grouped GEMM ({e}x({c},{kdim})x({kdim},{n}))"
                f" does not tile")
    if not feasible:
        return (HOW_STANDALONE, sharded,
                f"Region 3: {kind_name} grouped GEMM "
                f"({e}x({c},{kdim})x({kdim},{n})) too small for "
                f"{b_loc}x{h_loc}x{seq}x{seq} mask")
    if plan.gemm_dtype == "fp8":
        from repro.kernels import quant
        if not quant.have_fp8():
            return (HOW_GEMM_GROUPED, sharded,
                    "fp8 unavailable in this JAX build; f32 host")
    return HOW_GEMM_GROUPED, sharded, ""


def _standalone_capability(plan: DropoutPlan, shard: ShardInfo,
                           seq: int, attn_impl: str
                           ) -> Tuple[str, bool, str]:
    """(how, sharded, reason) for a standalone (bootstrap / Region-3 /
    non-carried) producer."""
    if attn_impl != "pallas":
        return HOW_XLA, False, "impl != pallas (no fused kernels)"
    reason = producer.mask_kernel_unsupported_reason(plan, seq, seq,
                                                     fused=False)
    if reason is not None:
        return HOW_XLA, False, reason
    if shard.policy_installed and not shard.active:
        return HOW_XLA, False, "mask (b, h) not shardable on this mesh"
    return HOW_STANDALONE, shard.policy_installed, ""


@functools.lru_cache(maxsize=256)
def _compile(cfg: ModelConfig, plan_cfg: DropoutPlanConfig, batch: int,
             seq: int, shard: ShardInfo, attn_impl: str, hw,
             moe_seq_dispatch: bool = False) -> DropoutSchedule:
    plan = DropoutPlan(plan_cfg)
    kinds = cfg.layer_kinds()
    period = len(cfg.block_pattern)
    attn_layers = [i for i, k in enumerate(kinds) if k in _ATTN]
    overlap = plan_cfg.enabled and plan_cfg.mode == "overlap"

    inert = DropoutSchedule(
        model=cfg.name, plan=plan_cfg, resolved_site=plan_cfg.site,
        batch=batch, seq=seq, attn_impl=attn_impl, shard=shard,
        carried=False,
        assignments=tuple(
            HostAssignment(layer=i, kind=kinds[i].value)
            for i in range(cfg.n_layers)),
        moe_seq_dispatch=moe_seq_dispatch)
    if not overlap or not attn_layers:
        return inert

    # -------- resolve site="auto" by Region-1 headroom, per model/shape
    site = plan_cfg.site
    headroom: Tuple[Tuple[str, float], ...] = ()
    if site == "auto":
        site, headroom = _resolve_auto(cfg, plan, batch, seq, shard,
                                       attn_impl, hw, moe_seq_dispatch)

    carried = site in CARRIED_DROPOUT_SITES
    moe_first_dense = cfg.moe.first_dense_layers if cfg.moe else 0

    asgs = []
    for l in range(cfg.n_layers):
        kind = kinds[l]
        if kind not in _ATTN:
            asgs.append(HostAssignment(layer=l, kind=kind.value))
            continue
        if site == "xla":
            asgs.append(HostAssignment(
                layer=l, kind=kind.value, consumes=True, site="xla",
                producer=l, how=HOW_XLA))
            continue
        if site == "qkv":
            how, sh, reason = _fused_capability(
                plan, cfg, batch, seq, "qkv", shard, attn_impl)
            asgs.append(HostAssignment(
                layer=l, kind=kind.value, consumes=True, site="qkv",
                producer=l, how=how, sharded=sh and how != HOW_XLA,
                reason=reason))
            continue
        # ---- carried sites: mask from the previous attention layer ----
        prev = max((a for a in attn_layers if a < l), default=-1)
        stride = _next_attn_stride(kinds, period, l)
        emit_site = site
        # the host GEMM lives in THIS block. Dense FFNs and attention
        # projections host through the dense fused kernel; MoE expert
        # and RWKV channel-mix FFNs host through the GROUPED kernel,
        # whose emission grid is decoupled from the expert tile grid —
        # the permuted/capacity-dropped token layout is irrelevant to
        # the bits, so these blocks are first-class hosts now.
        block_is_moe = cfg.moe is not None and l >= moe_first_dense
        if emit_site in ("ffn_up", "ffn_down") and (
                block_is_moe or cfg.ffn == FFNKind.RWKV_CHANNEL):
            e_how, e_sh, e_reason = _grouped_capability(
                plan, cfg, batch, seq, emit_site, shard, attn_impl,
                moe_seq_dispatch=moe_seq_dispatch,
                block_is_moe=block_is_moe)
        else:
            # first-dense layers of a MoE stack carry an ordinary dense
            # FFN: let the dense capability see its GEMM shapes
            dense_ffn = True if (cfg.moe is not None
                                 and not block_is_moe) else None
            e_how, e_sh, e_reason = _fused_capability(
                plan, cfg, batch, seq, emit_site, shard, attn_impl,
                dense_ffn=dense_ffn)
        if prev < 0:
            b_how, b_sh, b_reason = _standalone_capability(
                plan, shard, seq, attn_impl)
            asgs.append(HostAssignment(
                layer=l, kind=kind.value, consumes=True,
                site="standalone", producer=-1, how=b_how,
                sharded=b_sh and b_how != HOW_XLA,
                reason=b_reason or "bootstrap: no producer GEMM before "
                                   "the first attention layer",
                emit_site=emit_site, emit_stride=stride, emit_how=e_how,
                emit_reason=e_reason))
        else:
            # my mask was emitted by ``prev`` under the same host class
            p_asg = asgs[prev]
            asgs.append(HostAssignment(
                layer=l, kind=kind.value, consumes=True, site=site,
                producer=prev, how=p_asg.emit_how,
                sharded=p_asg.emit_how != HOW_XLA and shard.policy_installed
                and shard.active,
                reason=p_asg.emit_reason,
                emit_site=emit_site, emit_stride=stride, emit_how=e_how,
                emit_reason=e_reason))

    # -------- zero-HBM upgrade: counter replay at the consumer --------
    # Whenever the flash kernels can reconstruct the producer's counter
    # tiling exactly, consumption flips to HOW_REPLAY: no plane is
    # materialized, carried, or fed to attention. A gemm-hosted producer
    # is retained run-and-discard (host_how) so the RNG still hides
    # under the GEMM; standalone/XLA emissions — whose only purpose was
    # the plane — are dropped entirely.
    if _replay_reason(plan, cfg, seq, shard, attn_impl) is None:
        consume_sharded = shard.policy_installed and shard.active
        asgs = [_replay_assignment(a, consume_sharded) for a in asgs]

    sched = DropoutSchedule(
        model=cfg.name, plan=plan_cfg, resolved_site=site, batch=batch,
        seq=seq, attn_impl=attn_impl, shard=shard, carried=carried,
        assignments=tuple(asgs), headroom=headroom,
        moe_seq_dispatch=moe_seq_dispatch)
    _check_scan_periodicity(cfg, sched)
    return sched


def _replay_reason(plan: DropoutPlan, cfg: ModelConfig, seq: int,
                   shard: ShardInfo, attn_impl: str) -> Optional[str]:
    """Why this schedule cannot plan HOW_REPLAY consumption — None when
    it can. On top of the kernel-level predicate
    (producer.replay_unsupported_reason) the planner refuses meshes
    where the pallas attention path itself would fall back to XLA
    (models/attention._pallas_ok): a replay plan the runtime cannot
    honor would make the MS-D4 no-mask-operand proof fail."""
    reason = producer.replay_unsupported_reason(plan, seq, seq,
                                                attn_impl=attn_impl)
    if reason is not None:
        return reason
    if (shard.policy_installed and shard.head_shards > 1
            and cfg.n_kv_heads % shard.head_shards):
        return ("head-sharded mesh without kv-divisible heads "
                "(pallas attention falls back to XLA)")
    return None


def _replay_assignment(a: HostAssignment,
                       consume_sharded: bool) -> HostAssignment:
    """Rewrite one assignment for counter-replay consumption. The
    consuming side becomes HOW_REPLAY (host_how records the retained
    run-and-discard GEMM host, if any); emissions that only existed to
    materialize the plane (standalone / XLA) are cleared, gemm-hosted
    emissions stay (the RNG-under-GEMM overlap is the paper's benefit
    and keeps the bits contract-identical on the producer side)."""
    changes = {}
    if a.consumes:
        host_how = (a.how if a.how in (HOW_GEMM, HOW_GEMM_GROUPED)
                    else "")
        changes.update(how=HOW_REPLAY, host_how=host_how,
                       sharded=consume_sharded, reason="")
    if a.emit_site is not None and a.emit_how not in (HOW_GEMM,
                                                      HOW_GEMM_GROUPED):
        changes.update(emit_site=None, emit_stride=0, emit_how="",
                       emit_reason="")
    return dataclasses.replace(a, **changes) if changes else a


def _resolve_auto(cfg: ModelConfig, plan: DropoutPlan, batch: int,
                  seq: int, shard: ShardInfo, attn_impl: str, hw,
                  moe_seq_dispatch: bool = False):
    """site="auto": rank the block's candidate host GEMMs by Region-1
    headroom (producer.rank_host_sites → perfmodel.rank_host_gemms) and
    take the best one the fused kernel can actually realize; degrade to
    "xla" when none qualifies. The shard counts and dispatch layout ride
    along so the grouped candidates are ranked on the SAME grid the
    per-layer capability later judges."""
    if attn_impl != "pallas":
        return "xla", ()
    if producer.mask_kernel_unsupported_reason(plan, seq, seq) is not None:
        return "xla", ()
    if shard.policy_installed and not shard.active:
        return "xla", ()
    ranked = producer.rank_host_sites(cfg, plan, batch, seq, hw=hw,
                                      batch_shards=shard.batch_shards,
                                      head_shards=shard.head_shards,
                                      seq_dispatch=moe_seq_dispatch)
    return (ranked[0][0], ranked) if ranked else ("xla", ())


def _scan_static_key(a: HostAssignment):
    """The parts of an assignment the scan body actually branches on.
    Consumption of a carried mask and of the standalone bootstrap are
    the same code path (read the carry buffer), so the bootstrap's
    special consumption fields are not a periodicity violation — the
    emission side and the in-layer consumption sites must match
    exactly."""
    carries = a.site in CARRIED_DROPOUT_SITES or a.site == "standalone"
    return (a.kind, a.consumes, "carry" if carries else a.site,
            None if carries else a.how,
            None if carries else a.sharded,
            a.how == HOW_REPLAY, None if carries else a.host_how,
            a.emit_site, a.emit_stride, a.emit_how, a.emit_reason)


def _check_scan_periodicity(cfg: ModelConfig, sched: DropoutSchedule):
    """The layer scan compiles ONE body per stack, indexed by the first
    instance's assignments — every later instance of the same unit
    position must have compiled to the same static decision. Holds by
    construction (assignments derive from periodic static data); this
    assert keeps it an invariant rather than a coincidence."""
    from repro.models.transformer import build_stacks
    for spec in build_stacks(cfg):
        ul = len(spec.unit)
        for j in range(ul):
            ref = sched.for_layer(spec.base + j)
            for pos in range(1, spec.count):
                inst = sched.for_layer(spec.base + pos * ul + j)
                assert _scan_static_key(inst) == _scan_static_key(ref), (
                    "non-periodic schedule inside a scanned stack:\n"
                    f"{ref}\nvs\n{inst}")


def compile_schedule(model_cfg: ModelConfig, plan, batch: int, seq: int,
                     *, policy=None, attn_impl: str = "xla",
                     hw=None, moe_seq_dispatch: bool = False,
                     verify: bool = False,
                     shard: Optional[ShardInfo] = None
                     ) -> DropoutSchedule:
    """Compile the per-layer dropout schedule for one (model, plan,
    shape, mesh/sharding) cell — the plan→compile→execute entry point.

    ``plan`` is a DropoutPlanConfig or DropoutPlan (site may be "auto");
    ``policy`` the installed ShardingPolicy or None; ``attn_impl`` the
    kernel availability knob ("pallas" enables the fused producers);
    ``moe_seq_dispatch`` the MoE dispatch layout the grouped expert
    hosts are planned for — forward() validates it against the runtime
    flag at build time, so a schedule compiled for the dense-dispatch
    layout fails fast instead of silently executing against the
    seq-dispatch expert grid. Pure function of static data — results
    are cached, so the in-trace sugar path (models/transformer.forward
    compiling on first use) and the explicit launch-time call return
    the identical object.

    ``verify=True`` runs the static mask-safety verifier
    (repro.analysis, Layer 1) over the compiled schedule and raises
    ``repro.analysis.MaskSafetyError`` on any finding — pure counter
    arithmetic, no kernel executes.

    ``shard`` overrides the ShardInfo distilled from ``policy`` — the
    pure-arithmetic hook the per-topology lint sweep and the elastic
    re-mesh contract check use to plan for a mesh this process doesn't
    hold (no devices needed; mutually exclusive with ``policy``).
    """
    plan_cfg = plan.cfg if isinstance(plan, DropoutPlan) else plan
    if plan_cfg is None:
        raise ValueError("compile_schedule requires a dropout plan")
    if shard is not None and policy is not None:
        raise ValueError("pass either policy or shard, not both")
    if shard is None:
        shard = shard_info(policy, batch, model_cfg.n_heads)
    sched = _compile(model_cfg, plan_cfg, batch, seq, shard, attn_impl,
                     hw, moe_seq_dispatch)
    if verify:
        # imported lazily: analysis depends on this module
        from repro.analysis import verify_schedule
        verify_schedule(model_cfg, sched)
    return sched


def inline_assignment(model_cfg: ModelConfig, plan: DropoutPlan,
                      batch: int, seq: int, *, policy=None,
                      attn_impl: str = "xla") -> HostAssignment:
    """Single-layer sugar for direct ``attn_apply`` calls made without a
    compiled schedule (tests, microbenches): the first consumer's
    assignment of a uniform schedule, minus the carry (a lone call has
    no scan buffer, so carried sites degrade to the standalone producer
    with identical bits)."""
    sched = compile_schedule(model_cfg, plan.cfg, batch, seq,
                             policy=policy, attn_impl=attn_impl)
    if not sched.active:
        return HostAssignment(layer=0, kind="full")
    asg = sched.for_layer(sched.first_consumer)
    if asg.site in CARRIED_DROPOUT_SITES and asg.how != HOW_REPLAY:
        # (a replay consumer needs no carry at all — keep it as-is)
        how, sh, reason = _standalone_capability(
            plan, sched.shard, seq, attn_impl)
        asg = dataclasses.replace(
            asg, site="standalone", how=how,
            sharded=sh and how != HOW_XLA,
            reason=reason or "no scan carry outside the model")
    return asg


@dataclasses.dataclass(frozen=True)
class ScheduleBucket:
    """Hashable shape-bucket key for compiled-schedule caches — the
    ``MHAParams``/``ParamsHash`` graph-cache idiom: every knob the
    *structure* of a compiled schedule depends on, packed into one
    frozen dataclass that keys a dict of compiled artifacts.

    Deliberately excludes the plan ``seed``: host-assignment planning
    never reads it (capability is pure shape/knob arithmetic), so all
    requests sharing a shape bucket share one compiled template and
    per-request identity is restored by ``reseed_schedule``. The serve
    engine keys its schedule cache and its jitted-step cache on this."""
    model: str
    batch: int
    seq: int
    attn_impl: str
    mode: str
    p: float
    site: str
    gemm_dtype: str
    philox_rounds: int
    philox_bits: int
    shard: ShardInfo = ShardInfo()
    moe_seq_dispatch: bool = False

    @staticmethod
    def of(cfg: ModelConfig, plan_cfg: DropoutPlanConfig, batch: int,
           seq: int, *, attn_impl: str = "xla",
           shard: Optional[ShardInfo] = None,
           moe_seq_dispatch: bool = False) -> "ScheduleBucket":
        return ScheduleBucket(
            model=cfg.name, batch=batch, seq=seq, attn_impl=attn_impl,
            mode=plan_cfg.mode, p=plan_cfg.p, site=plan_cfg.site,
            gemm_dtype=plan_cfg.gemm_dtype,
            philox_rounds=plan_cfg.philox_rounds,
            philox_bits=plan_cfg.philox_bits,
            shard=shard or ShardInfo(),
            moe_seq_dispatch=moe_seq_dispatch)


def reseed_schedule(sched: DropoutSchedule, seed: int) -> DropoutSchedule:
    """The same compiled schedule under a different base seed.

    Assignments are seed-independent (every capability judgment in
    ``_compile`` is shape/knob arithmetic — the seed only enters the
    Philox key at execution), so swapping the seed on the frozen
    artifact is exact, not an approximation: ``mask_key`` changes,
    producers don't. This is what lets a serving bucket compile ONE
    template and stamp out per-request schedules for free."""
    if seed == sched.plan.seed:
        return sched
    return dataclasses.replace(
        sched, plan=dataclasses.replace(sched.plan, seed=seed))


def clear_cache() -> None:
    """Drop compiled schedules (tests exercising determinism)."""
    _compile.cache_clear()
