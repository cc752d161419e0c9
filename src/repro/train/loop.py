"""Train/serve step functions + the fault-tolerant training runner.

TrainState (pytree):
    master — f32 master params (ZeRO-1-sharded by the distribution layer)
    opt    — {"m", "v"} AdamW moments (f32, same sharding)
    step   — int32 scalar

Mixed precision: the step casts master -> compute dtype for the forward;
gradients are taken w.r.t. master (f32). Remat ("block") wraps each layer
unit. Microbatching accumulates grads over a lax.scan.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, OptimizerConfig, RunConfig
from repro.core.overlap import DropoutPlan, plan_from_config
from repro.core.schedule import compile_schedule
from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.models import Runtime, decode_step, forward, model_init
from repro.optim import adamw_init, adamw_update

AUX_WEIGHT = 0.01

log = logging.getLogger("repro.train")


def init_train_state(key, cfg: ModelConfig) -> Dict[str, Any]:
    params = model_init(key, cfg)
    return {
        "master": params,
        "opt": adamw_init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """logits f32 (B,S,V); labels int32 (B,S)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def _validate_dropout_plan(run: RunConfig) -> None:
    """Cross-field check the per-field __post_init__ validation cannot
    express: the producer-site knob only makes sense for decoupled RNG —
    fused mode generates bits inside attention, so there is no producer
    GEMM to host them. Catch the bad combo at step-build time, not
    mid-scan."""
    d = run.dropout
    if d.site != "xla" and d.mode == "fused":
        raise ValueError(
            f"site={d.site!r} requires mode='overlap' (fused mode has no "
            "producer-GEMM site)")


def _log_schedule(context: str, sched) -> None:
    """Surface the compiled schedule's per-layer host assignments. The
    HOW_* tags are the observable: a host silently degrading to the XLA
    producer (Region 3 shrinkage, philox_bits=8, lost tiling, an
    unshardable mesh) is a host-selection regression this log makes
    visible — before any step runs, and exactly once (the schedule is a
    frozen artifact, so jit retraces cannot double-count it)."""
    for site, how, gemm_dtype, note in sched.records():
        log.info("%s: dropout mask producer site=%s how=%s "
                 "gemm_dtype=%s%s", context, site, how, gemm_dtype,
                 f" ({note})" if note else "")
    log.info("%s:\n%s", context, sched.explain())


def compile_run_schedule(cfg: ModelConfig, run: RunConfig,
                         policy: Optional[ShardingPolicy] = None):
    """The train step's compiled DropoutSchedule for one RunConfig —
    factored out so launch/train.py (dropout-contract construction) and
    the chaos harness compile the IDENTICAL artifact the step executes:
    microbatching splits the leading batch dim, so the schedule is
    compiled for the per-microbatch shape the forward actually sees."""
    micro = run.train.microbatch
    b_eff = run.shape.global_batch // micro if micro and micro > 1 \
        else run.shape.global_batch
    return compile_schedule(cfg, run.dropout, b_eff, run.shape.seq_len,
                            policy=policy,
                            attn_impl=run.sharding.attn_impl,
                            moe_seq_dispatch=run.sharding
                            .moe_seq_dispatch)


def make_train_step(cfg: ModelConfig, run: RunConfig,
                    policy: Optional[ShardingPolicy] = None,
                    compute_dtype=jnp.float32) -> Callable:
    """Returns train_step(state, x, y) -> (state, metrics). Pure function
    of its inputs — jit/lower it with explicit shardings. The dropout
    plan's producer site ("xla" | "qkv" | "prev_gemm") threads through
    Runtime.plan into the model (see core/producer.py)."""
    _validate_dropout_plan(run)
    plan = plan_from_config(run.dropout)
    remat = run.sharding.remat
    micro = run.train.microbatch
    # plan -> compile: all producer-site decisions freeze here, ahead of
    # trace; forward() executes by schedule lookup
    sched = compile_run_schedule(cfg, run, policy)
    _log_schedule(f"train_step[site={run.dropout.site}]", sched)

    def loss_fn(master, x, y, step):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), master)
        rt = Runtime(plan=plan, step=step, compute_dtype=compute_dtype,
                     policy=policy, remat=remat,
                     probs_dtype=(jnp.bfloat16
                                  if run.sharding.attn_probs_bf16
                                  else None),
                     moe_seq_dispatch=run.sharding.moe_seq_dispatch,
                     attn_impl=run.sharding.attn_impl,
                     schedule=sched)
        with use_policy(policy):
            logits, aux = forward(params, cfg, rt, x)
            with jax.named_scope("loss"):
                ce = cross_entropy(logits, y)
        loss = ce + AUX_WEIGHT * aux
        return loss, (ce, aux)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, x, y):
        step = state["step"]
        if micro and micro > 1:
            bsz = x.shape[0]
            assert bsz % micro == 0
            xm = x.reshape(micro, bsz // micro, *x.shape[1:])
            ym = y.reshape(micro, bsz // micro, *y.shape[1:])

            def acc_body(carry, xs):
                gacc, lacc = carry
                xi, yi = xs
                (loss, (ce, aux)), g = grad_fn(state["master"], xi, yi,
                                               step)
                gacc = jax.tree.map(jnp.add, gacc, g)
                return (gacc, lacc + jnp.stack([loss, ce, aux])), None

            zeros = jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32),
                state["master"])
            (gsum, lsum), _ = jax.lax.scan(
                acc_body, (zeros, jnp.zeros((3,), jnp.float32)), (xm, ym))
            grads = jax.tree.map(lambda g: g / micro, gsum)
            loss, ce, aux = lsum[0] / micro, lsum[1] / micro, lsum[2] / micro
        else:
            (loss, (ce, aux)), grads = grad_fn(state["master"], x, y, step)

        with jax.named_scope("optimizer"):
            master, _, opt, om = adamw_update(
                grads, state["opt"], state["master"], run.train.optimizer,
                step, compute_dtype)
        new_state = {"master": master, "opt": opt, "step": step + 1}
        metrics = {"loss": loss, "ce": ce, "aux": aux, **om}
        return new_state, metrics

    return train_step


def make_eval_step(cfg: ModelConfig, run: RunConfig,
                   policy: Optional[ShardingPolicy] = None,
                   compute_dtype=jnp.float32) -> Callable:
    def eval_step(master, x, y):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), master)
        rt = Runtime(plan=None, step=0, compute_dtype=compute_dtype,
                     policy=policy)
        with use_policy(policy):
            logits, _ = forward(params, cfg, rt, x)
            return cross_entropy(logits, y)
    return eval_step


def make_serve_step(cfg: ModelConfig,
                    policy: Optional[ShardingPolicy] = None,
                    compute_dtype=jnp.float32) -> Callable:
    """serve_step(params, inputs, caches) -> (logits, caches)."""
    def serve_step(params, inputs, caches):
        rt = Runtime(plan=None, step=0, compute_dtype=compute_dtype,
                     policy=policy)
        with use_policy(policy):
            return decode_step(params, cfg, rt, inputs, caches)
    return serve_step


def make_prefill_step(cfg: ModelConfig,
                      policy: Optional[ShardingPolicy] = None,
                      compute_dtype=jnp.float32,
                      capacity: int = 0) -> Callable:
    from repro.models import prefill as _prefill

    def prefill_step(params, inputs):
        rt = Runtime(plan=None, step=0, compute_dtype=compute_dtype,
                     policy=policy)
        with use_policy(policy):
            return _prefill(params, cfg, rt, inputs, capacity=capacity)
    return prefill_step
