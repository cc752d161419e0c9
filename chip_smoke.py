"""Bring-up smoke test: train the dropout path on a TPU through the normal
entry point (``repro.launch.train.main``) and check what comes out.

    python3 chip_smoke.py              # one TPU chip
    python3 chip_smoke.py --chips 4    # 2x2 (data, model) mesh vs one chip
    python3 chip_smoke.py --rehearse   # reduced avatar on a CPU backend

Configuration: musicgen-large at its published widths (d_model 2048, 32
heads x head_dim 64, d_ff 8192, vocab 2048) cut to 4 layers, batch 4,
seq 1536, random weights from --seed, attention-score dropout p=0.1.
Every number printed is smoke output, not a benchmark number.

One chip runs four phases in this process:
  0. kernels vs the pure-jnp oracles (kernels/ref.py) at a small shape:
     mask bits bitwise, flash attention and the host GEMM within
     tolerance, replay == premask bitwise;
  1. overlap mode, mask of layer l+1 drawn under layer l's out-projection
     by the fused GEMM+RNG kernel (bf16 host), premask flash fwd/bwd;
  2. the same with in-kernel replay: losses bitwise equal to phase 1;
  3. the same plan with XLA attention and XLA producers: the mask bits
     are identical, losses agree with phase 1 within LOSS_RTOL.
Phases 1 and 2 run without remat, phase 3 with block remat (see PHASES).
``--chips 4`` runs phase 1's plan under a 2x2 (data, model) sharding
policy and, for comparison, on device 0 alone; nothing else.

The last line of stdout is {"ok": true, "device": {...}} on success and
is printed only then. Without --rehearse the script exits non-zero when
JAX's default device is not a TPU.
"""
from __future__ import annotations

import argparse
import json
import logging
import math
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH, LAYERS, BATCH, SEQ, STEPS = "musicgen-large", 4, 4, 1536, 5
# --rehearse: the reduced avatar at a shape the Pallas interpreter runs
# in seconds (seq stays 128-tileable for the flash kernels)
REHEARSE_BATCH, REHEARSE_SEQ = 2, 256

# Phase 1 vs phase 3 (and sharded vs one chip): the same mask bits, but
# phase 1's out-projection rounds its operands to bf16 (the host's
# gemm_dtype) and kernels accumulate in a different order than XLA. One
# bf16 rounding, unit roundoff 2**-9, bounds the relative change of a
# loss averaged over B*S tokens.
LOSS_RTOL = 2.0 ** -9

PLAN = ["--dropout", "overlap", "--site", "prev_gemm", "--gemm-dtype",
        "bf16"]
# Phases 1 and 2 keep every activation (--remat none). Under block remat
# the TPU compiler tiles some of XLA's own f32 dots differently in the
# premask and the replay program (different K windows, so a different
# summation order), and the gradients part by an ulp although every
# kernel output is bitwise equal. Without remat the two programs get the
# same tiles. Phase 3's score planes need block remat to fit in 16 GB;
# it is compared within LOSS_RTOL anyway.
PHASES = {
    1: PLAN + ["--attn-impl", "pallas", "--attn-replay", "off",
               "--remat", "none"],
    2: PLAN + ["--attn-impl", "pallas", "--attn-replay", "auto",
               "--remat", "none"],
    3: PLAN + ["--attn-impl", "xla", "--attn-replay", "off",
               "--remat", "block"],
}
# the Pallas kernels each phase's train step must contain, by name
KERNELS = {
    1: {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "gemm_rng",
        "philox_mask"},
    2: {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "gemm_rng"},
    3: set(),
}


class _Warnings(logging.Handler):
    """Collects the library's WARNING records: every runtime fallback
    (pallas -> xla attention, gemm -> standalone -> xla producer)
    logs one."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.records = []

    def emit(self, record):
        self.records.append(record.getMessage())


def _fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        _fail(msg)


def _train_argv(phase: int, rehearse: bool, seed: int, ckpt_dir: str):
    argv = ["--arch", ARCH, "--layers", str(LAYERS), "--steps", str(STEPS),
            "--log-every", "1", "--ckpt-every", str(10 * STEPS),
            "--ckpt-dir", ckpt_dir, "--seed", str(seed)]
    if rehearse:
        argv += ["--reduced", "--batch", str(REHEARSE_BATCH),
                 "--seq", str(REHEARSE_SEQ)]
    else:
        argv += ["--batch", str(BATCH), "--seq", str(SEQ)]
    return argv + PHASES[phase]


def _pallas_calls(closed):
    """(name, interpret) of every pallas_call in a traced program."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], eqn.params["interpret"]))
                continue
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(closed.jaxpr)
    return found


def _census(run, policy=None):
    """Kernel names and interpret flags of the train step ``run`` traces
    to (abstract shapes only; nothing executes)."""
    import jax
    import jax.numpy as jnp
    from repro.train.loop import init_train_state, make_train_step
    cfg, shape = run.model, run.shape
    state = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg))
    b, s = shape.global_batch, shape.seq_len
    x = (jax.ShapeDtypeStruct((b, s), jnp.int32) if cfg.frontend == "token"
         else jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.float32))
    y = jax.ShapeDtypeStruct((b, s), jnp.int32)
    return _pallas_calls(jax.make_jaxpr(
        make_train_step(cfg, run, policy))(state, x, y))


def _check_schedule(phase: int, sched) -> None:
    """Every layer shows the realization its phase plans."""
    from repro.core import producer as pr
    for a in sched.assignments:
        # the first layer's mask has no GEMM before it: a standalone
        # bootstrap (phase 1), or replay with no host (phase 2)
        first = a.layer == sched.first_consumer
        if phase == 3:
            ok = a.how == pr.HOW_XLA and a.emit_how == pr.HOW_XLA
        elif phase == 2:
            ok = (a.how == pr.HOW_REPLAY and a.emit_how == pr.HOW_GEMM
                  and a.host_how == ("" if first else pr.HOW_GEMM))
        else:
            ok = (a.how == (pr.HOW_STANDALONE if first else pr.HOW_GEMM)
                  and a.emit_how == pr.HOW_GEMM)
        _check(a.consumes and ok,
               f"phase {phase}: layer {a.layer} planned {a}\n"
               + sched.explain())


def train_phase(phase: int, rehearse: bool, seed: int, warnings):
    import jax
    from repro.launch import train
    from repro.train.loop import compile_run_schedule
    print(f"[smoke] ---- phase {phase}: train "
          f"{' '.join(PHASES[phase])} ----", flush=True)
    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        argv = _train_argv(phase, rehearse, seed, ckpt_dir)
        run = train.build_run(train.parse_args(argv))
        _check_schedule(phase, compile_run_schedule(run.model, run))
        calls = _census(run)
        names = {n for n, _ in calls}
        _check(names == KERNELS[phase],
               f"phase {phase}: kernels {sorted(names)}, expected "
               f"{sorted(KERNELS[phase])}")
        _check(all(i == rehearse for _, i in calls),
               f"phase {phase}: interpret flags {calls}")
        n_warn = len(warnings.records)
        report = train.main(argv)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    _check(len(warnings.records) == n_warn,
           f"phase {phase}: fallback warnings "
           f"{warnings.records[n_warn:]}")
    _check(report.restarts == 0 and report.failed_saves == 0,
           f"phase {phase}: restarts={report.restarts} "
           f"failed_saves={report.failed_saves}")
    losses = [report.losses.get(i) for i in range(STEPS)]
    _check(all(v is not None and math.isfinite(v) for v in losses),
           f"phase {phase}: losses {losses}")
    print(f"[smoke] phase {phase} losses {losses} "
          f"({jax.devices()[0].device_kind})", flush=True)
    return losses


def _compare(name, got, want, rtol):
    worst = max(abs(g - w) / abs(w) for g, w in zip(got, want))
    print(f"[smoke] {name}: max relative loss difference {worst!r} "
          f"(limit {rtol!r})", flush=True)
    _check(worst <= rtol, f"{name}: {got} vs {want}")


def kernel_phase(rehearse: bool, seed: int) -> None:
    """Phase 0: the kernels against kernels/ref.py at a small shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention import flash_attention_mosaic
    from repro.kernels.gemm_rng import gemm_with_rng
    from repro.kernels.philox import philox_dropout_mask
    from repro.kernels.philox_common import seed_salt_smem
    from repro.kernels.ref import attention_ref, philox_mask_ref
    print("[smoke] ---- phase 0: kernels vs kernels/ref.py ----",
          flush=True)
    b, h, s, d, p, salt = 1, 4, 256 if rehearse else 512, 64, 0.1, 11
    want = np.asarray(philox_mask_ref(b, h, s, s, p, seed, salt))
    got = np.asarray(philox_dropout_mask(b, h, s, s, p, seed, salt))
    _check(np.array_equal(got, want), "standalone Philox mask bits")
    kx, kw, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (b * s, 256), jnp.float32)
    w = jax.random.normal(kw, (256, 512), jnp.float32)
    y, mask = gemm_with_rng(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                            mask_batch=b, mask_heads=h, mask_sq=s,
                            mask_sk=s, p=p, seed=seed, salt=salt)
    _check(mask is not None and np.array_equal(np.asarray(mask), want),
           "GEMM-hosted mask bits")
    y_ref = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - y_ref))
                / jnp.max(jnp.abs(y_ref)))
    _check(err < 2.0 ** -7, f"GEMM vs jnp.dot: relative error {err}")
    q, k, v = jax.random.normal(kq, (3, b, h, s, d), jnp.float32)
    keep = philox_mask_ref(b, h, s, s, p, seed, salt, packed=False)
    # a TPU runs f32 matmuls in bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        o_ref = attention_ref(q, k, v, dropout_p=p, dropout_mask=keep)

    def run(mode, operand):
        def loss(q, k, v):
            o = flash_attention_mosaic(q, k, v, operand, True, 0, p, mode,
                                       seed, salt, 7, 128, 128, None, 0)
            return jnp.sum(o * o), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
        return o, grads

    o_pre, g_pre = run("premask", jnp.asarray(want))
    o_rep, g_rep = run("replay", seed_salt_smem(seed, salt))
    # the kernel's dots may run as one bf16 pass (unit roundoff 2**-9)
    # on both q.k and p.v; a wrong tile or mask is off by O(p) = 0.1
    err = float(jnp.max(jnp.abs(o_pre - o_ref)) / jnp.max(jnp.abs(o_ref)))
    _check(err < 2.0 ** -6, f"flash premask vs attention_ref: {err}")
    _check(np.array_equal(np.asarray(o_pre), np.asarray(o_rep))
           and all(np.array_equal(np.asarray(a), np.asarray(c))
                   for a, c in zip(g_pre, g_rep)),
           "flash replay != premask (fwd or grads)")
    print(f"[smoke] phase 0 ok: mask bits bitwise, flash vs ref relative "
          f"error {err!r}", flush=True)


def sharded_phase(rehearse: bool, seed: int, warnings) -> None:
    """--chips 4: phase 1's plan under a 2x2 (data, model) policy, then
    the same config and seed on device 0 alone."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.data import batch_for_step, embed_batch_for_step
    from repro.distributed.sharding import ShardingPolicy
    from repro.distributed.specs import to_shardings, train_state_specs
    from repro.launch import train
    from repro.train.loop import (compile_run_schedule, init_train_state,
                                  make_train_step)
    _check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, want 4")
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
    policy = ShardingPolicy(mesh)
    argv = _train_argv(1, rehearse, seed, "unused")
    run = train.build_run(train.parse_args(argv))
    cfg, shape = run.model, run.shape
    print("[smoke] ---- 2x2 (data, model) mesh ----", flush=True)
    sched = compile_run_schedule(cfg, run, policy)
    print(sched.explain(), flush=True)
    _check(sched.sharded, "schedule did not plan shard-local producers")
    names = {n for n, _ in _census(run, policy)}
    _check(names == KERNELS[1], f"sharded kernels {sorted(names)}")
    state_shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(seed), cfg))
    st_sh = to_shardings(
        train_state_specs(state_shapes, policy, fsdp=False), mesh)
    # built sharded in place: nothing of the state lands on device 0 first
    state = jax.jit(lambda: init_train_state(jax.random.PRNGKey(seed), cfg),
                    out_shardings=st_sh)()
    x_sh = NamedSharding(mesh, P("data"))
    step = jax.jit(make_train_step(cfg, run, policy),
                   in_shardings=(st_sh, x_sh, x_sh),
                   out_shardings=(st_sh, None))
    n_warn = len(warnings.records)
    losses = []
    for i in range(STEPS):
        x, y = (batch_for_step if cfg.frontend == "token"
                else embed_batch_for_step)(cfg, shape, i, seed)
        state, metrics = step(state, jax.device_put(jnp.asarray(x), x_sh),
                              jax.device_put(jnp.asarray(y), x_sh))
        losses.append(float(metrics["loss"]))
        print(f"[smoke] mesh step={i + 1} loss={losses[-1]!r}", flush=True)
    _check(len(warnings.records) == n_warn,
           f"sharded fallback warnings {warnings.records[n_warn:]}")
    _check(all(math.isfinite(v) for v in losses), f"losses {losses}")
    del state
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] mesh peak_bytes_in_use(device 0)="
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    one = train_phase(1, rehearse, seed, warnings)
    _compare("2x2 mesh vs one chip", losses, one, LOSS_RTOL)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="run the reduced avatar on a CPU backend")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"[smoke] no TPU: JAX's default device is {dev.platform}",
              file=sys.stderr)
        return 2
    from repro.kernels.backend import default_interpret
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[smoke] compile cache: {enable_compile_cache()}")
    _check(default_interpret() == args.rehearse,
           f"default_interpret() is {default_interpret()}")
    warnings = _Warnings()
    logging.getLogger("repro").addHandler(warnings)
    print(f"[smoke] {ARCH}: depth cut 48 -> {LAYERS} layers at published "
          "widths; smoke output, not benchmark numbers", flush=True)
    if args.chips == 4:
        sharded_phase(args.rehearse, args.seed, warnings)
    else:
        kernel_phase(args.rehearse, args.seed)
        losses = {p: train_phase(p, args.rehearse, args.seed, warnings)
                  for p in PHASES}
        _check(losses[2] == losses[1],
               f"replay {losses[2]} != premask {losses[1]} (bitwise)")
        print("[smoke] phase 2 == phase 1 bitwise", flush=True)
        _compare("pallas vs xla", losses[1], losses[3], LOSS_RTOL)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
