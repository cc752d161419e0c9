"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch musicgen-large \\
        --layers 4 --batch 4 --seq 1536 --steps 5 --log-every 1 \\
        --dropout overlap --site prev_gemm --attn-impl pallas \\
        --gemm-dtype bf16

Trains on JAX's default device: the Pallas kernels compile for a TPU when
one is present and run in the interpreter on any other backend (keep
``--reduced`` shapes there). Fault tolerance: checkpoints every
--ckpt-every steps into --ckpt-dir, auto-resumes from the latest
checkpoint found there, straggler stats printed at exit.

Flags that select where the dropout bits are drawn (the paper's path):

  --dropout      none | fused | overlap (RNG decoupled from attention)
  --site         producer GEMM that hosts a layer's mask in overlap mode:
                 xla | qkv | prev_gemm | ffn_up | ffn_down | auto
  --attn-impl    xla | pallas (flash-attention fwd+bwd Pallas kernels;
                 the GEMM+RNG hosts need pallas too)
  --gemm-dtype   f32 | bf16 | fp8 operands of the fused GEMM+RNG host
  --attn-replay  auto (flash kernels replay the keep bits in-register
                 where feasible) | off (always read a materialized plane)
  --layers N     depth cut: the first N layers of --arch at its published
                 widths (0 keeps the full depth)

``main(argv)`` returns the run's ``RunnerReport`` (per-step losses,
restarts, failed checkpoint writes), so other programs drive the same
path in-process.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.counters import draw_counts, explain_draws
from repro.checkpoint import (
    Checkpointer,
    contract_from_schedule,
    verify_resume,
)
from repro.config import (
    DropoutPlanConfig,
    OptimizerConfig,
    RunConfig,
    ShapeConfig,
    ShardingConfig,
    StepKind,
    TrainConfig,
    get_arch,
)
from repro.config.base import DROPOUT_SITES, GEMM_DTYPES
from repro.data import batch_for_step, embed_batch_for_step
from repro.distributed.fault import RunnerReport, StragglerDetector, \
    TrainRunner
from repro.launch.compile_cache import enable_compile_cache
from repro.train.loop import (
    compile_run_schedule,
    init_train_state,
    make_train_step,
)


def build_run(args) -> RunConfig:
    cfg = get_arch(args.arch, reduced=args.reduced)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch,
                        kind=StepKind.TRAIN)
    return RunConfig(
        model=cfg,
        shape=shape,
        sharding=ShardingConfig(remat=args.remat,
                                attn_impl=args.attn_impl),
        dropout=DropoutPlanConfig(mode=args.dropout, p=args.dropout_p,
                                  site=args.site,
                                  gemm_dtype=args.gemm_dtype,
                                  attn_replay=args.attn_replay),
        train=TrainConfig(
            optimizer=OptimizerConfig(
                lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                total_steps=args.steps),
            microbatch=args.microbatch,
            checkpoint_every=args.ckpt_every,
            checkpoint_dir=args.ckpt_dir,
        ),
    )


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: keep the first N layers (0 = all)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--remat", default="block", choices=("none", "block"))
    ap.add_argument("--dropout", default="overlap",
                    choices=("none", "fused", "overlap"))
    ap.add_argument("--dropout-p", type=float, default=0.1)
    ap.add_argument("--site", default="xla", choices=DROPOUT_SITES)
    ap.add_argument("--attn-impl", default="xla", choices=("xla", "pallas"))
    ap.add_argument("--gemm-dtype", default="f32", choices=GEMM_DTYPES)
    ap.add_argument("--attn-replay", default="auto", choices=("auto", "off"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> RunnerReport:
    enable_compile_cache()
    args = parse_args(argv)
    run = build_run(args)
    cfg = run.model
    print(f"[train] arch={cfg.name} layers={cfg.n_layers} "
          f"params={cfg.param_count()/1e6:.1f}M "
          f"devices={len(jax.devices())} dropout={args.dropout}")

    # the dropout contract: frozen mask lineage saved with every
    # checkpoint, verified on every resume/recovery (checkpoint/contract)
    sched = compile_run_schedule(cfg, run)
    contract = contract_from_schedule(cfg, sched)
    print(sched.explain())
    print(explain_draws(draw_counts(cfg, sched)))

    state = init_train_state(jax.random.PRNGKey(args.seed), cfg)
    ckpt = Checkpointer(args.ckpt_dir)
    latest = ckpt.latest_step()
    if latest is not None:
        saved = ckpt.load_contract(latest)
        if saved is not None:
            # ContractMismatchError propagates: resuming would replay
            # different mask bits than the checkpointed trajectory
            status = verify_resume(saved, contract, cfg=cfg,
                                   sched=sched)
            print(f"[train] dropout contract {status} for step {latest}")
        print(f"[train] resuming from step {latest}")
        state = ckpt.restore(latest, state)

    def batch_fn(step):
        if cfg.frontend == "token":
            x, y = batch_for_step(cfg, run.shape, step, args.seed)
        else:
            x, y = embed_batch_for_step(cfg, run.shape, step, args.seed)
        return jnp.asarray(x), jnp.asarray(y)

    # compile ahead of the first step so its time is reported apart
    # from step time
    t0 = time.perf_counter()
    step_fn = jax.jit(make_train_step(cfg, run)).lower(
        state, *batch_fn(0)).compile()
    print(f"[train] compiled train step in "
          f"{time.perf_counter() - t0:.2f}s")

    straggler = StragglerDetector()
    t_start = time.perf_counter()
    last = {"t": t_start, "step": int(jax.device_get(state["step"]))}

    def logging_step(state, x, y):
        state, metrics = step_fn(state, x, y)
        step = int(jax.device_get(state["step"]))
        if step % args.log_every == 0:
            now = time.perf_counter()
            dt = now - last["t"]
            n = step - last["step"]
            tok_s = (n * run.shape.global_batch * run.shape.seq_len
                     / max(dt, 1e-9))
            print(f"[train] step={step} loss={float(metrics['loss']):.6f} "
                  f"ce={float(metrics['ce']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} tok/s={tok_s:,.0f}")
            last["t"], last["step"] = now, step
        return state, metrics

    runner = TrainRunner(logging_step, state, batch_fn, ckpt,
                         checkpoint_every=args.ckpt_every,
                         straggler=straggler, contract=contract,
                         model_cfg=cfg, schedule=sched)
    report = runner.run(args.steps)
    wall = time.perf_counter() - t_start
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[train] done: steps={report.steps_completed} "
          f"restarts={report.restarts} "
          f"stragglers={report.straggler_steps} "
          f"failed_saves={report.failed_saves} wall={wall:.1f}s "
          f"final_loss={report.final_metrics.get('loss', float('nan')):.6f}"
          f" peak_bytes_in_use="
          f"{peak if peak is not None else 'not reported'}")
    return report


if __name__ == "__main__":
    main()
