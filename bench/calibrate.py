"""Readings that the limits of ``correct`` are set from (on the chip).

    python3 bench/calibrate.py --workload <cell> --seeds 12 [--first-seed N]
                               [--decay D]

For each seed, in one process: the configuration's reference first, then
the program's checked steps as the benchmark runs them, the control (the
program's own bfloat16 compute path, ``make_train_step(...,
compute_dtype=bfloat16)``) and the half-batch fault (faults.py), each
compared with it. With ``--decay D`` the program also runs with AdamW's
weight decay at D, against the reference at D. Prints one JSON line per
seed and a summary: the largest reading of the program (the lower
reading) and the smallest of each other variant (the upper readings).
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import tempfile

# the TPU runtime logs under /tmp unless told otherwise; a run writes
# nothing outside its checkout and its own temporary directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, faults, run, traffic, weights  # noqa: E402


def variants(config: dict, mix: dict, ckpt: str, decay=None):
    """The model and {name: (jitted step, its mix)} of the program, the
    control, the fault and, with ``decay``, the program at that decay."""
    import jax
    import jax.numpy as jnp
    from repro.train.loop import make_train_step
    run_cfg = run.build_run(config, mix, 0, ckpt)
    model = run_cfg.model
    program = jax.jit(make_train_step(model, run_cfg))
    control = jax.jit(make_train_step(model, run_cfg,
                                      compute_dtype=jnp.bfloat16))
    out = {"program": (program, mix), "control": (control, mix),
           "half_batch": (faults.half_batch(program), mix)}
    if decay is not None:
        mix_d = copy.deepcopy(mix)
        mix_d["optimizer"]["weight_decay"] = decay
        run_d = run.build_run(config, mix_d, 0, ckpt)
        out["decay"] = (jax.jit(make_train_step(run_d.model, run_d)), mix_d)
    return model, out


def decay_shares(got: dict, ref: dict, mix: dict, master_fn, key) -> dict:
    """{weight matrix: [program, reference]}: how much of each matrix's
    change lies along its starting weights, -<change, start> / (<start,
    start> * the sum of the checked steps' learning rates). Adam's own
    steps give both sides the same share, to round-off; the weight decay
    adds its rate to it. So the reference's share less the program's
    reads the decay on a leaf that the program leaves undecayed, and
    about 0 on the rest."""
    import jax
    import numpy as np
    from bench import reference
    opt = mix["optimizer"]
    lr_sum = sum(float(reference.lr_at(opt, t))
                 for t in range(mix["checked_steps"]))
    start = check.host_leaves(jax.jit(master_fn)(key))
    out = {}
    for k, p0 in start.items():
        name = k.split("'")[-2]           # "['ffn']['w_up']" -> "w_up"
        if name in ("scale", "bias") or name.startswith("b_"):
            continue
        p0 = p0.astype(np.float64).ravel()
        scale = float(p0 @ p0) * lr_sum
        out[k] = [-float(side["change"][k].astype(np.float64).ravel() @ p0)
                  / scale for side in (got, ref)]
    return out


def readings(cell: dict, seeds, decay=None) -> list:
    """One dict per seed: {variant: the numbers, variant_worst: where}."""
    import gc
    import jax
    from repro.train.loop import init_train_state
    config, mix = cell["config"], cell["mix"]
    tmp = tempfile.mkdtemp(prefix="bench_cal_")
    out = []
    try:
        ckpt = os.path.join(tmp, "ckpt")
        model, steps = variants(config, mix, ckpt, decay)
        shapes = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), model))
        for seed in seeds:
            key = traffic.seed_key(seed)
            ring = traffic.make_ring(config, mix, seed)
            refs = {}
            for _, mx in steps.values():
                wd = mx["optimizer"]["weight_decay"]
                if wd not in refs:
                    refs[wd] = check.reference_readings(
                        config, mx, ring, weights.master_fn(shapes), key)
            row = {"seed": seed, "losses": {}}
            for name, (step, mx) in steps.items():
                runner = run.make_runner(shapes, seed, ring, step, ckpt)
                got = run.checked_steps(runner, mx)
                runner.state = None
                del runner
                ref = refs[mx["optimizer"]["weight_decay"]]
                row[name], where = check.compare(got, ref)
                if name == "decay":
                    row["decay_share"] = decay_shares(
                        got, ref, mx, weights.master_fn(shapes), key)
                row[name + "_worst"] = {k: where[k] for k in check.NUMBERS
                                        if k in where}
                row["losses"][name] = got["losses"]
                del got
                gc.collect()
            row["reference_losses"] = {str(wd): r["losses"]
                                       for wd, r in refs.items()}
            del refs
            print(json.dumps(row), flush=True)
            out.append(row)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def summary(rows: list) -> dict:
    names = [k for k in rows[0] if isinstance(rows[0][k], dict)
             and set(rows[0][k]) == set(check.NUMBERS)]
    out = {}
    for number in check.NUMBERS:
        out[number] = {"program_max": max(r["program"][number] for r in rows)}
        for name in names:
            if name != "program":
                out[number][name + "_min"] = min(r[name][number]
                                                 for r in rows)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--decay", type=float, default=None)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    try:
        run.device_info(cell["chips"])
    except run.NoChip as e:
        print(f"[calibrate] {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = readings(cell, range(args.first_seed,
                                args.first_seed + args.seeds), args.decay)
    print(json.dumps({"workload": args.workload, "summary": summary(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
