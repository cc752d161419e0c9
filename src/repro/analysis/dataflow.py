"""Layer 2 of the static mask-safety verifier: jaxpr dataflow analysis.

``jax.make_jaxpr`` traces the compiled forward (and the remat-wrapped
backward) with abstract values only — no kernel, interpreted or
otherwise, executes. Mask-producing equations are tagged by dtype/shape
against the schedule's packed-mask layouts (uint32 planes derived from
the schedule's records), then taint is propagated through the graph:

  * taint flows through integer/bool equations and structural ops, and
    recurses into scan / pjit / cond / while / remat / custom-vjp /
    shard_map inner jaxprs (scan carries run to a fixpoint);
  * taint DIES when the bits merge into float compute (``select_n`` /
    ``where`` of scores) — that is the mask's one sanctioned exit.

Violations:
  MS-D1 mask-residual-leak      tainted scan ``ys`` (per-layer stacking
                                outside the carried buffer) or tainted
                                top-level outputs. Forward-trace only:
                                reverse-mode AD of a scan legitimately
                                saves its carries per iteration, so the
                                carried buffer appearing in grad-trace
                                residuals is the known cost of the
                                pipeline, not a leak — the forward check
                                already proves the mask never leaves
                                the carry in the primal graph.
  MS-D2 mask-collective-crossing tainted operand of a collective
  MS-D3 mask-token-gather        tainted data operand of gather /
                                scatter / sort (token-identity routing;
                                PR 4's MoE-dispatch invariant)
  MS-D4 mask-operand-on-replay   a mask-shaped plane is an operand of
                                any pallas_call while the schedule is
                                replay-planned — replay kernels take a
                                (4,) seed-salt word and re-derive keep
                                bits in-register, so a plane operand
                                means the zero-HBM contract degraded
                                to premask traffic
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.analysis import rules
from repro.config.base import ModelConfig
from repro.core.overlap import DropoutPlan
from repro.core.schedule import DropoutSchedule

_COLLECTIVES = frozenset({
    "psum", "psum2", "all_gather", "all_to_all", "ppermute",
    "pbroadcast", "reduce_scatter", "pmax", "pmin", "pgather",
})
# ops that route data by (possibly token-dependent) indices: a
# position-keyed mask entering one means its bits follow token identity
_TOKEN_IDENTITY = frozenset({
    "gather", "scatter", "scatter-add", "scatter-mul", "scatter-min",
    "scatter-max", "sort",
})


def mask_shapes(cfg: ModelConfig, sched: DropoutSchedule
                ) -> Set[Tuple[int, ...]]:
    """Every packed-mask aval shape the schedule's producers emit:
    global and shard-local (B, H, SQ//32, SK) planes plus the kernels'
    flattened (BH, SQ32, SK) / (BH*SQ32, SK) layouts."""
    b, h, sk = sched.batch, cfg.n_heads, sched.seq
    sq32 = sk // 32
    pairs = {(b, h)}
    sh = sched.shard
    if sh.active:
        pairs.add((b // sh.batch_shards, h // sh.head_shards))
    shapes: Set[Tuple[int, ...]] = set()
    for bb, hh in pairs:
        shapes.add((bb, hh, sq32, sk))
        shapes.add((bb * hh, sq32, sk))
        shapes.add((bb * hh * sq32, sk))
    return shapes


def _is_mask_aval(aval, shapes: Set[Tuple[int, ...]], sk: int,
                  sq32: int) -> bool:
    if getattr(aval, "dtype", None) != jnp.uint32:
        return False
    shape = tuple(getattr(aval, "shape", ()))
    if shape in shapes:
        return True
    # row-padded flattened plane of the fused emission (rows_alloc, SK):
    # sublane-padded row count, mask columns
    return (len(shape) == 2 and shape[1] == sk and shape[0] >= sq32
            and shape[0] % 8 == 0)


def _taintable(aval) -> bool:
    """Dtypes taint survives through: ints and bools. Merging into float
    compute is the mask's sanctioned consumption point."""
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return False
    return jnp.issubdtype(dt, jnp.integer) or dt == jnp.bool_


class _Walker:
    """Single-pass (per jaxpr) taint propagation with recursion into
    inner jaxprs. ``record=False`` runs silently (fixpoint iterations);
    the final pass records findings."""

    def __init__(self, shapes: Set[Tuple[int, ...]], sk: int, sq32: int,
                 check_residuals: bool, replay: bool = False):
        self.shapes = shapes
        self.sk = sk
        self.sq32 = sq32
        self.check_residuals = check_residuals
        self.replay = replay
        self.findings: List[rules.Finding] = []
        self.eqns = 0

    # ------------------------------------------------------------ helpers
    def _origin(self, var) -> bool:
        return _is_mask_aval(var.aval, self.shapes, self.sk, self.sq32)

    def _finding(self, record: bool, rule: str, msg: str):
        if record:
            f = rules.Finding(rule, msg)
            if f not in self.findings:
                self.findings.append(f)

    # --------------------------------------------------------------- walk
    def walk(self, jaxpr, taint_in: Sequence[bool],
             record: bool = True) -> List[bool]:
        """Propagate taint through one jaxpr; returns outvar taint."""
        tainted: Set[int] = set()

        def mark(v):
            if isinstance(v, jcore.Var):
                tainted.add(id(v))

        def is_t(v):
            return isinstance(v, jcore.Var) and id(v) in tainted

        for v, t in zip(jaxpr.invars, taint_in):
            if t:
                mark(v)
        for v in jaxpr.constvars:
            if self._origin(v):
                mark(v)

        for eqn in jaxpr.eqns:
            self.eqns += 1
            name = eqn.primitive.name
            in_t = [is_t(x) for x in eqn.invars]
            any_in = any(in_t)

            if any_in and name in _COLLECTIVES:
                self._finding(
                    record, rules.MASK_COLLECTIVE_CROSSING,
                    f"packed mask bits cross collective `{name}` — "
                    "shard-local counter windows must never leave "
                    "their shard")
            if name in _TOKEN_IDENTITY and in_t and in_t[0]:
                self._finding(
                    record, rules.MASK_TOKEN_GATHER,
                    f"packed mask bits are data operand of `{name}` — "
                    "position-keyed bits routed by token identity "
                    "(MoE-dispatch permutation invariant)")
            if self.replay and name == "pallas_call":
                # zero-HBM contract: replay kernels take a (4,)
                # seed-salt word, never a packed plane (nor a reshaped
                # view of one, e.g. the premask kernels' block layout)
                for x, t in zip(eqn.invars, in_t):
                    if t or _is_mask_aval(getattr(x, "aval", None),
                                          self.shapes, self.sk, self.sq32):
                        self._finding(
                            record, rules.MASK_OPERAND_REPLAY,
                            "packed mask plane "
                            f"{tuple(x.aval.shape)} is an operand of a "
                            "pallas_call on a replay-planned schedule "
                            "— zero-HBM replay degraded to premask "
                            "traffic")

            out_t = self._eqn_taint(eqn, in_t, record)
            for i, v in enumerate(eqn.outvars):
                if out_t[i] or self._origin(v):
                    mark(v)
        return [is_t(v) for v in jaxpr.outvars]

    # --------------------------------------------------- per-eqn transfer
    def _eqn_taint(self, eqn, in_t: List[bool], record: bool
                   ) -> List[bool]:
        name = eqn.primitive.name
        params = eqn.params
        if name == "scan":
            return self._scan(eqn, in_t, record)
        if name == "while":
            return self._while(eqn, in_t, record)
        if name == "cond":
            outs = [self.walk(br.jaxpr, in_t[1:], record)
                    for br in params["branches"]]
            return [any(o[i] for o in outs)
                    for i in range(len(eqn.outvars))]
        inner = self._call_jaxpr(eqn)
        if inner is not None and len(inner.invars) == len(eqn.invars):
            return self.walk(inner, in_t, record)
        if not any(in_t):
            return [False] * len(eqn.outvars)
        # default transfer: taint survives on integer/bool outputs,
        # dies on float outputs (select_n of scores, etc.)
        return [_taintable(v.aval) for v in eqn.outvars]

    @staticmethod
    def _call_jaxpr(eqn):
        """Inner jaxpr of a call-like eqn (pjit / remat / custom-vjp /
        shard_map / closed_call), or None. pallas_call is deliberately
        opaque: its outputs are judged by aval (mask origins), and its
        inner IR operates on refs, not values."""
        if eqn.primitive.name == "pallas_call":
            return None
        for key in ("jaxpr", "call_jaxpr"):
            j = eqn.params.get(key)
            if j is None:
                continue
            if isinstance(j, jcore.ClosedJaxpr):
                return j.jaxpr
            if isinstance(j, jcore.Jaxpr):
                return j
        return None

    def _scan(self, eqn, in_t: List[bool], record: bool) -> List[bool]:
        params = eqn.params
        body = params["jaxpr"].jaxpr
        n_const = params["num_consts"]
        n_carry = params["num_carry"]
        const_t = in_t[:n_const]
        carry_t = in_t[n_const:n_const + n_carry]
        xs_t = in_t[n_const + n_carry:]
        for _ in range(n_carry + 1):          # monotone fixpoint
            body_out = self.walk(body, const_t + carry_t + xs_t,
                                 record=False)
            new_carry = [a or b for a, b in zip(carry_t,
                                                body_out[:n_carry])]
            if new_carry == carry_t:
                break
            carry_t = new_carry
        body_out = self.walk(body, const_t + carry_t + xs_t, record)
        ys_t = body_out[n_carry:]
        if self.check_residuals and any(ys_t):
            self._finding(
                record, rules.MASK_RESIDUAL_LEAK,
                "packed mask bits leave a layer scan as stacked `ys` "
                "output — masks materialized per-layer outside the "
                "carried scan buffer")
        return body_out[:n_carry] + ys_t

    def _while(self, eqn, in_t: List[bool], record: bool) -> List[bool]:
        params = eqn.params
        body = params["body_jaxpr"].jaxpr
        cn = params["cond_nconsts"]
        bn = params["body_nconsts"]
        body_const_t = in_t[cn:cn + bn]
        carry_t = in_t[cn + bn:]
        for _ in range(len(carry_t) + 1):
            out = self.walk(body, body_const_t + carry_t, record=False)
            new_carry = [a or b for a, b in zip(carry_t, out)]
            if new_carry == carry_t:
                break
            carry_t = new_carry
        return self.walk(body, body_const_t + carry_t, record)


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------

def analyze_jaxpr(closed, cfg: ModelConfig, sched: DropoutSchedule, *,
                  check_residuals: bool = True,
                  check_outputs: bool = True, cell: str = ""
                  ) -> rules.Report:
    """Walk one traced jaxpr for mask-scope violations."""
    shapes = mask_shapes(cfg, sched)
    walker = _Walker(shapes, sched.seq, sched.seq // 32,
                     check_residuals, replay=sched.replay)
    jaxpr = closed.jaxpr if isinstance(closed, jcore.ClosedJaxpr) \
        else closed
    out_t = walker.walk(jaxpr, [walker._origin(v) for v in jaxpr.invars])
    if check_outputs and any(out_t):
        walker.findings.append(rules.Finding(
            rules.MASK_RESIDUAL_LEAK,
            "packed mask bits reach a top-level output of the traced "
            "function — masks must stay internal to the step"))
    return rules.Report(cell=cell or "jaxpr",
                        findings=tuple(walker.findings),
                        checked_eqns=walker.eqns)


def _trace_inputs(cfg: ModelConfig, batch: int, seq: int):
    from repro.models.transformer import model_init
    params = jax.eval_shape(
        functools.partial(model_init, jax.random.PRNGKey(0), cfg))
    if cfg.frontend == "token":
        tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    else:
        tokens = jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                      jnp.float32)
    return params, tokens


def analyze_model(cfg: ModelConfig, plan_cfg, batch: int, seq: int, *,
                  attn_impl: str = "pallas", with_grad: bool = True,
                  moe_seq_dispatch: bool = False, cell: str = ""
                  ) -> rules.Report:
    """Trace the real transformer forward (and its remat-wrapped
    backward) for one cell and walk the jaxprs. Abstract tracing only —
    zero kernel executions."""
    from repro.core.schedule import compile_schedule
    from repro.models.transformer import Runtime, forward
    sched = compile_schedule(cfg, plan_cfg, batch, seq,
                             attn_impl=attn_impl,
                             moe_seq_dispatch=moe_seq_dispatch)
    params, tokens = _trace_inputs(cfg, batch, seq)
    cell = cell or (f"{cfg.name} site={plan_cfg.site} "
                    f"dtype={plan_cfg.gemm_dtype}")

    def fwd(p, t, remat):
        rt = Runtime(plan=DropoutPlan(plan_cfg), step=0,
                     attn_impl=attn_impl, schedule=sched, remat=remat,
                     moe_seq_dispatch=moe_seq_dispatch)
        return forward(p, cfg, rt, t)

    closed = jax.make_jaxpr(lambda p, t: fwd(p, t, "none"))(params,
                                                            tokens)
    rep = analyze_jaxpr(closed, cfg, sched, cell=cell + " [fwd]")
    findings = list(rep.findings)
    eqns = rep.checked_eqns
    if with_grad:
        def loss(p, t):
            logits, aux = fwd(p, t, "block")
            return jnp.sum(logits) + jnp.sum(aux)

        closed_g = jax.make_jaxpr(jax.grad(loss))(params, tokens)
        # residual/stacking checks are forward-only (see module doc):
        # grad-of-scan saves its carries per iteration by construction
        rep_g = analyze_jaxpr(closed_g, cfg, sched,
                              check_residuals=False,
                              check_outputs=False,
                              cell=cell + " [bwd]")
        findings.extend(rep_g.findings)
        eqns += rep_g.checked_eqns
    return rules.Report(cell=cell, findings=tuple(findings),
                        checked_eqns=eqns)


def analyze_leaky_model(cfg: ModelConfig, plan_cfg, batch: int,
                        seq: int, *, attn_impl: str = "pallas"
                        ) -> rules.Report:
    """Negative control for MS-D1 (`lint --mutate residual-leak`):
    trace a forward that ALSO returns its packed mask plane — the
    analyzer must flag the escape."""
    from repro.core import dropout_rng
    from repro.core.schedule import compile_schedule
    from repro.models.transformer import Runtime, forward
    sched = compile_schedule(cfg, plan_cfg, batch, seq,
                             attn_impl=attn_impl)
    params, tokens = _trace_inputs(cfg, batch, seq)
    plan = DropoutPlan(plan_cfg)

    def leaky(p, t):
        rt = Runtime(plan=plan, step=0, attn_impl=attn_impl,
                     schedule=sched)
        logits, aux = forward(p, cfg, rt, t)
        mask = dropout_rng.packed_mask(
            batch, cfg.n_heads, seq, seq, plan_cfg.p,
            plan.step_seed(0), plan.salt(0), plan_cfg.philox_rounds,
            32)
        return logits, aux, mask            # the leak

    closed = jax.make_jaxpr(leaky)(params, tokens)
    return analyze_jaxpr(closed, cfg, sched,
                         cell=f"{cfg.name} [leak-mutant]")
