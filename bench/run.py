"""Chip benchmark of the dropout train step.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); its limits for ``correct`` are in
``bench/limits/<cell>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``. Adding a cell adds files and entries only.

A configuration file holds the program's ``--arch``, its ModelConfig
fields as run (enums by value, tuples as lists, ``moe`` as an object),
each checked against the program, and the benchmark's own keys
(``OWN_KEYS``): ``reduced`` and ``published`` (dotted for a key of a
nested group, ``moe.n_experts``), ``reference`` (its plain reference
under ``bench/``, default ``reference.py``), ``scopes`` (the program's
scopes beyond ``bench/scopes.SCOPES``), precision and remat.

One run, in one process:
  1. set-up: the compile cache, the program's run built from the cell's
     flags (``repro.launch.train.build_run``), weights and a ring of
     batches made on the device from --seed, the jitted
     ``make_train_step`` driven by ``TrainRunner`` through the checked
     steps (their losses, first gradient and update are kept) and a
     short warm-up;
  2. the window: the same runner, one step per call, for --seconds
     (with --trace 1 under the profiler);
  3. the peak device memory, then the program's state is freed and the
     plain reference that the configuration names (default reference.py)
     runs the checked steps;
  4. the last line of stdout: one JSON object with ``correct``, the
     metrics and the device. The numbers compared, each with its limit,
     are also the last lines of stderr.

It runs only on a TPU whose kind is in ``bench/peaks.json`` and exits
non-zero, printing no result, anywhere else.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import enum  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

# the TPU runtime logs under /tmp unless told otherwise; a run writes
# nothing outside its checkout and its own temporary directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the checkout's root, not this directory, goes first: bench/trace.py
# must not shadow the standard library's trace module
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, traffic, weights, work  # noqa: E402

SPEC = os.path.join(ROOT, "BENCHMARK.json")
LIMITS = os.path.join(BENCH, "limits")

END_TO_END = ("train_tokens_per_s", "step_ms_p90", "peak_hbm_gb", "setup_s")
NEVER = 10 ** 9                    # checkpoint interval beyond any window
# the benchmark's own keys of a configuration file; every other key names
# a field of the program's ModelConfig and is checked against it
OWN_KEYS = ("arch", "reduced", "published", "deployment", "precision",
            "assumed", "reference", "scopes", "compute_dtype",
            "matmul_precision", "attention_precision", "remat")
# ModelConfig fields that every configuration file states
REQUIRED = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "ffn", "norm", "norm_eps", "rope",
            "rope_theta", "frontend", "tie_embeddings", "qkv_bias",
            "qk_norm")
# ModelConfig fields that neither the counts nor a reference read: labels,
# the dropout rates (the mix states dropout) and the rope table's length.
# Any other field that the program runs at a value other than ModelConfig's
# default (block_pattern, local_window, moe, ...) has to be stated too.
UNREAD = ("name", "family", "source", "attn_dropout", "resid_dropout",
          "max_seq_len")


class NoChip(RuntimeError):
    """The device this run needs is not there."""


def load_cell(workload: str) -> dict:
    """The cell's configuration, mix, limits and per-layer metric names,
    found by name from BENCHMARK.json."""
    with open(SPEC) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    if config["reduced"] != conf["reduced"]:
        raise ValueError(f"{conf['file']} and BENCHMARK.json list "
                         "different cuts")
    limits_path = os.path.join(LIMITS, f"{workload}.json")
    with open(limits_path) as f:
        limits = json.load(f)
    per_layer = [m["name"] for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return {"name": workload, "config": config,
            "mix": traffic.load(cell["traffic"]), "limits": limits,
            "chips": cell["chips"], "per_layer": per_layer}


def read_metric(name: str, ctx: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def device_info(chips: int) -> dict:
    """The devices JAX sees; raises NoChip unless they are TPUs of a kind
    in peaks.json, at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's devices are {devs[0].platform}, not tpu")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} TPU chips, the cell needs {chips}")
    try:
        work.peaks(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def plain(value):
    """A value of the program's config as a configuration file states it:
    an enum by its value, a tuple as a list, a dataclass as an object."""
    if isinstance(value, enum.Enum):
        return value.value
    if dataclasses.is_dataclass(value):
        return {f.name: plain(getattr(value, f.name))
                for f in dataclasses.fields(value)}
    if isinstance(value, (tuple, list)):
        return [plain(v) for v in value]
    return value


def apply_cuts(model, config: dict):
    """``model`` with the configuration's cuts (``reduced``, less
    ``n_layers``, which the flags set) at the file's values. A dotted cut
    (``moe.n_experts``) is a key of a nested dataclass."""
    missing = sorted({k.partition(".")[0] for k in config["reduced"]}
                     - set(config))
    if missing:
        raise ValueError(f"the configuration file leaves out fields that "
                         f"it cuts: {missing}")
    top, nested = {}, {}
    for key in config["reduced"]:
        group, _, inner = key.partition(".")
        if inner:
            nested.setdefault(group, {})[inner] = config[group][inner]
        elif key != "n_layers":
            top[key] = config[key]
    for group, cuts in nested.items():
        if getattr(model, group) is None:
            raise ValueError(f"a cut of {group}, which the program's "
                             "model does not have")
        top[group] = dataclasses.replace(getattr(model, group), **cuts)
    return dataclasses.replace(model, **top)


def unstated(model, config: dict) -> list:
    """The fields that the configuration file has to state and leaves out:
    every one of ``REQUIRED``, every other field outside ``UNREAD`` that
    the program runs at a value other than ModelConfig's default, and
    every key of a nested group that it states (a group is copied
    whole)."""
    missing = [k for k in REQUIRED if k not in config]
    for f in dataclasses.fields(model):
        if f.name in REQUIRED or f.name in UNREAD:
            continue
        value = getattr(model, f.name)
        if f.name not in config and value != f.default:
            missing.append(f.name)
        elif dataclasses.is_dataclass(value) and isinstance(
                config.get(f.name), dict):
            missing += [f"{f.name}.{g.name}" for g in
                        dataclasses.fields(value)
                        if g.name not in config[f.name]]
    return missing


def stated_mismatch(model, config: dict) -> dict:
    """{key: (stated, run)} of every key of the configuration file that
    the program runs otherwise; a nested group (``moe``) is compared key
    by key. A key that is neither the benchmark's own nor a field of the
    program's model, or a field that the file has to state and leaves out
    (``unstated``), is an error."""
    got = plain(model)
    unknown = sorted(k for k in config if k not in OWN_KEYS and k not in got)
    mismatch = {}
    for key in (k for k in config if k not in OWN_KEYS and k in got):
        want, have = config[key], got[key]
        if isinstance(want, dict) and isinstance(have, dict):
            unknown += [f"{key}.{k}" for k in want if k not in have]
            mismatch.update({f"{key}.{k}": (v, have[k])
                             for k, v in want.items()
                             if k in have and v != have[k]})
        elif want != have:
            mismatch[key] = (want, have)
    if unknown:
        raise ValueError(f"configuration keys that name no field of the "
                         f"program's model: {unknown}")
    missing = unstated(model, config)
    if missing:
        raise ValueError(f"the configuration file leaves out fields that "
                         f"the program runs: {missing}")
    return mismatch


def build_run(config: dict, mix: dict, seed: int, ckpt_dir: str):
    """The program's RunConfig for the cell, built from its flags the way
    ``launch/train.py`` builds it, with the configuration's cuts applied,
    every stated value checked against what the program runs, and the
    configuration's reference asked whether it covers the model."""
    import jax
    from repro.launch import train
    flags = ["--arch", config["arch"], "--layers", str(config["n_layers"]),
             "--batch", str(mix["batch"]), "--seq", str(mix["seq"]),
             "--remat", config["remat"], "--ckpt-every", str(NEVER),
             "--ckpt-dir", ckpt_dir, "--seed", str(seed)] + mix["flags"]
    run = train.build_run(train.parse_args(flags))
    # the program has no precision setting of its own: its XLA matmuls run
    # at JAX's default, which the configuration has to state
    if config["matmul_precision"] != (
            jax.config.jax_default_matmul_precision or "default"):
        raise ValueError(f"the configuration states matmul precision "
                         f"{config['matmul_precision']!r}; the program runs "
                         f"{jax.config.jax_default_matmul_precision!r}")
    model = apply_cuts(run.model, config)
    mismatch = stated_mismatch(model, config)
    # the reference sees the configuration as run: its file's keys over
    # every field of the program's model
    why = check.reference_module(config).covers({**plain(model), **config})
    if why:
        mismatch["reference"] = why
    d = run.dropout
    for k, v in mix["dropout"].items():
        if k != "host_dtype" and getattr(d, k) != v:
            mismatch[f"dropout.{k}"] = (v, getattr(d, k))
    if mismatch:
        raise ValueError(f"the program does not run the stated cell: "
                         f"{mismatch}")
    # the command line sets the learning rate and its schedule's length;
    # the rest of the mix's optimizer (decay, betas, clipping) goes in here
    train = dataclasses.replace(run.train, optimizer=dataclasses.replace(
        run.train.optimizer, **mix["optimizer"]))
    return run.with_(model=model, train=train)


class _StepTimes:
    """A StragglerDetector that also keeps every step time the runner
    measures (its host clock around the step, through block_until_ready)."""

    def __init__(self, base):
        self.base = base
        self.times = []

    def observe(self, duration_s: float) -> bool:
        self.times.append(duration_s)
        return self.base.observe(duration_s)

    def __getattr__(self, name):
        return getattr(self.base, name)


def p90(values):
    """The 90th percentile (inclusive method) of at least two values."""
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def make_runner(shapes, seed: int, ring, step, ckpt_dir: str,
                times=None):
    """The program's TrainRunner over ``step`` (the jitted train step),
    from the benchmark's weights for ``seed``, fed from ``ring``. The
    host spans bench.batch and bench.step label the trace's idle gaps."""
    from jax.profiler import TraceAnnotation
    from repro.checkpoint import Checkpointer
    from repro.distributed.fault import StragglerDetector, TrainRunner

    def batch_fn(i):
        with TraceAnnotation("bench.batch"):
            return ring[i % len(ring)]

    def step_fn(state, x, y):
        with TraceAnnotation("bench.step"):
            return step(state, x, y)

    return TrainRunner(step_fn, weights.make_state(shapes, seed), batch_fn,
                       Checkpointer(ckpt_dir), checkpoint_every=NEVER,
                       straggler=times or StragglerDetector())


def checked_steps(runner, mix: dict) -> dict:
    """Drive the runner through the mix's checked steps and read, on the
    host, what the comparison needs: each step's loss, the first gradient
    from Adam's first moment, and each leaf's change over the steps."""
    n = mix["checked_steps"]
    start = check.host_leaves(runner.state["master"])
    losses = runner.run(1).losses
    b1 = np.float32(1.0 - mix["optimizer"]["b1"])
    grad = {k: v / b1
            for k, v in check.host_leaves(runner.state["opt"]["m"]).items()}
    losses.update(runner.run(n).losses)
    return {"losses": [losses[i] for i in range(n)], "grad": grad,
            "change": check.change(check.host_leaves(runner.state["master"]),
                                   start)}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             wrap_step=None) -> dict:
    """One run of ``cell`` (see load_cell). ``wrap_step`` (tests only)
    replaces the jitted step by ``wrap_step(step)``."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.distributed.fault import StragglerDetector
    from repro.train.loop import (compile_run_schedule, init_train_state,
                                  make_train_step)
    config, mix = cell["config"], cell["mix"]
    tmp = tempfile.mkdtemp(prefix="bench_")
    try:
        run = build_run(config, mix, seed, os.path.join(tmp, "ckpt"))
        model = run.model
        print(compile_run_schedule(model, run).explain(), file=sys.stderr)
        shapes = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), model))
        key = traffic.seed_key(seed)
        ring = traffic.make_ring(config, mix, seed)
        step = jax.jit(make_train_step(model, run))
        if wrap_step is not None:
            step = wrap_step(step)
        times = _StepTimes(StragglerDetector())
        runner = make_runner(shapes, seed, ring, step,
                             os.path.join(tmp, "ckpt"), times)
        # the checked steps go through the runner, as the window's do
        prog = checked_steps(runner, mix)
        done = runner.run(mix["checked_steps"]
                          + mix["untimed_steps"]).steps_completed
        setup_s = time.perf_counter() - T_START

        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: compiles.append(event)
            if event == "/jax/core/compile/backend_compile_duration" else None)
        trace_dir = os.path.join(tmp, "trace")
        if trace:
            jax.profiler.start_trace(trace_dir)
        times.times.clear()
        first = done
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                done = runner.run(done + 1).steps_completed
            window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        steps = done - first
        compiles_in_window = len(compiles)
        stats = jax.devices()[0].memory_stats() or {}
        peak_bytes = stats.get("peak_bytes_in_use")
        restarts = runner.restarts
        runner.state = None
        del runner
        gc.collect()

        ref = check.reference_readings(config, mix, ring,
                                       weights.master_fn(shapes), key)
        numbers, worst = check.compare(prog, ref)
        checks, correct = check.judge(numbers, cell["limits"])

        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": peak_bytes}
        result = {"correct": correct, "attempted": steps, "failed": restarts}
        if trace:
            from bench import trace as trace_mod
            summary = trace_mod.load(trace_dir)
            ctx = {"config": config, "mix": mix, "chips": cell["chips"],
                   "peak": work.peaks(dev.device_kind), "trace": summary,
                   "steps": steps, "window_s": window_s}
            metrics = {}
            for name in cell["per_layer"]:
                value = read_metric(name, ctx)
                if value is not None:
                    metrics[name] = {"value": value, "unit": "%"}
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
            result.update(metrics=metrics, device=device, breakdown={
                "device_ops": [[n, s] for n, s in summary.top_ops(10)],
                "idle_gaps": [[n, s] for n, s in summary.idle_gaps[:10]]})
        else:
            tokens = steps * mix["batch"] * mix["seq"]
            result.update(metrics={
                "train_tokens_per_s": {"value": tokens / window_s,
                                       "unit": "tokens/s"},
                "step_ms_p90": {"value": 1e3 * p90(times.times),
                                "unit": "ms"},
                "peak_hbm_gb": {"value": (peak_bytes or 0) / 1e9,
                                "unit": "GB"},
                "setup_s": {"value": setup_s, "unit": "s"}}, device=device)
        result["window"] = {
            "steps": steps, "seconds": window_s,
            "step_ms_median": 1e3 * statistics.median(times.times),
            "compiles": compiles_in_window,
            "leaves": {k: v for k, v in worst.items()
                       if k not in ("grad", "change")},
            "not_compared": {k: v for k, v in numbers.items()
                             if k not in checks},
            "program_losses": prog["losses"],
            "reference_losses": ref["losses"]}
        result["checks"] = checks
        return result
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        info = device_info(cell["chips"])
    except NoChip as e:
        print(f"[bench] no result: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    # small programs (weights, batches, norms) are cached as well
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[bench] {args.workload} seed={args.seed} on {info}",
          file=sys.stderr)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"[bench] check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
