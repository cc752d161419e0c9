"""Attribution of a traced window to the program's scopes and spans.

The program names its layers with ``jax.named_scope``: ``embed``,
``layers`` (the stack scans' own slices and stacked writes), ``attn`` and
``ffn`` in each block, ``mask`` (the bootstrap producer), ``unembed``,
``loss`` and ``optimizer``; a configuration file's ``"scopes"`` list
names those the program gives its layers beyond these, which a cell of
that configuration attributes too. A scope is metadata of the optimized
HLO: each instruction's ``op_name`` holds the scope path, wrapped in
``jvp(...)`` in the forward and in ``transpose(jvp(...))`` in the
backward. A device op of the trace is named by its HLO instruction, so
the step's optimized HLO text maps it to a (scope, direction). A fusion
takes the scope of the first dot or convolution it computes, else its
own; an op the compiler made without metadata takes the scope of the
first op that reads it.

The program's runner names its host time with spans inside the profiler's
step markers (``train``): ``runner.batch``, ``runner.step`` (the
dispatch), ``runner.wait``, ``runner.loss``, ``runner.checkpoint``,
``runner.recover``, ``runner.report``; ``runner.sync`` reads the state's
step before them, and ``python.gc`` covers each of Python's garbage
collections. ``label_gaps`` names each idle gap of the device by the
innermost program span that covers it, a benchmark span only where none
does.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

sets a cell up as ``run.py`` does, traces one window and prints, as one
JSON line, the device milliseconds a step of each (scope, direction),
the idle gaps so labelled and each kernel's events a step beside the
calls the program's draw counter plans. The per-layer metrics
``ffn_roofline`` and ``optimizer_roofline`` read ``cell_scope_seconds``.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    # the checkout's root, not this directory, goes first: bench/trace.py
    # must not shadow the standard library's trace module
    _BENCH = os.path.dirname(os.path.abspath(__file__))
    if sys.path and os.path.abspath(sys.path[0]) == _BENCH:
        sys.path.pop(0)
    sys.path[:0] = [os.path.dirname(_BENCH),
                    os.path.join(os.path.dirname(_BENCH), "src")]

SCOPES = ("embed", "layers", "attn", "ffn", "mask", "unembed", "loss",
          "optimizer")
UNSCOPED = "unscoped"
OUTSIDE = "outside spans"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_MATMUL = re.compile(r"\s(?:dot|convolution)\(")
_WRAPPER = re.compile(r"^(?!jit\()[\w\-]+\((.*)\)$")
# an opcode follows its result type, which ends in "]", "}" or ")"
_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_NAME = re.compile(r"%([\w.\-]+)")
_BODY = re.compile(r"body=%?([\w.\-]+)")


def cell_scopes(config: dict) -> Tuple[str, ...]:
    """The scopes a cell of ``config`` attributes: the shared ones and
    the configuration's own."""
    return SCOPES + tuple(config.get("scopes", ()))


def scope_of(op_name: str, names: Sequence[str] = SCOPES
             ) -> Optional[Tuple[str, str]]:
    """(innermost program scope among ``names``, "fwd" | "bwd") of an
    ``op_name``, or None when it holds no scope. Transformation wrappers
    (``jvp(...)``, ``transpose(...)``) are looked through; a ``jit(...)``
    is a function's name and never a scope."""
    found = None
    for part in op_name.split("/"):
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        if part in names:
            found = part
    if found is None:
        return None
    return found, "bwd" if "transpose(" in op_name else "fwd"


def _operands(line: str) -> List[str]:
    """The instruction operands of an HLO text line, in order."""
    rest = line.split(" = ", 1)[1]
    m = _OPCODE.search(rest)
    if m is None:
        return []
    depth = 0
    for k in range(m.end() - 1, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[k], 0)
        if depth == 0:
            return _NAME.findall(rest[m.end() - 1:k])
    return []


def hlo_scopes(hlo_text: str, names: Sequence[str] = SCOPES
               ) -> Dict[str, Tuple[str, str]]:
    """HLO instruction name -> (scope among ``names``, direction), for
    every instruction of the optimized module's text whose scope is
    known.

    A fusion takes the scope of the first dot or convolution it computes,
    else its own. An op the compiler made without metadata (a weight's
    cast hoisted out of the layer scan, a relayout) takes the scope of
    the first op that reads it, followed through copies and, for an
    operand of a ``while``, to the ops of its body that read that
    element."""
    comps: Dict[str, List[Tuple[str, str]]] = {}   # name -> instructions
    current: Optional[List[Tuple[str, str]]] = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                head = line.split("(", 1)[0].replace("ENTRY", "").strip()
                current = comps.setdefault(head.lstrip("%"), [])
            else:
                current = None
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            current.append((m.group(1), line))

    @functools.lru_cache(maxsize=None)
    def matmul_scope(comp: str) -> Optional[Tuple[str, str]]:
        for _, line in comps.get(comp, ()):
            if _MATMUL.search(line):
                m = _OP_NAME.search(line)
                got = scope_of(m.group(1), names) if m else None
                if got is not None:
                    return got
            called = _CALLS.search(line)
            if called and " fusion(" in line:
                got = matmul_scope(called.group(1))
                if got is not None:
                    return got
        return None

    out: Dict[str, Tuple[str, str]] = {}
    for instrs in comps.values():
        for name, line in instrs:
            called = _CALLS.search(line)
            got = (matmul_scope(called.group(1))
                   if called and " fusion(" in line else None)
            if got is None:
                m = _OP_NAME.search(line)
                got = scope_of(m.group(1), names) if m else None
            if got is not None:
                out[name] = got

    readers: Dict[str, Dict[str, List[Tuple[str, str]]]] = {}
    for comp, instrs in comps.items():
        by_operand = readers.setdefault(comp, {})
        for name, line in instrs:
            for operand in _operands(line):
                by_operand.setdefault(operand, []).append((name, line))

    def element_scope(body: str, index: int, depth: int):
        params = [n for n, line in comps.get(body, ())
                  if " parameter(0)" in line]
        for gte, line in comps.get(body, ()):
            if (params and f"get-tuple-element(%{params[0]})" in line
                    and f"index={index}" in line.split(")", 1)[-1]):
                return out.get(gte) or reader_scope(body, gte, depth + 1)
        return None

    def reader_scope(comp: str, name: str, depth: int = 0):
        if depth > 4:
            return None
        for user, line in readers.get(comp, {}).get(name, ()):
            if user in out:
                return out[user]
            if " tuple(" in line:
                index = _operands(line).index(name)
                for _, wline in readers[comp].get(user, ()):
                    body = _BODY.search(wline)
                    if body and " while(" in wline:
                        got = element_scope(body.group(1), index, depth)
                        if got is not None:
                            return got
            got = reader_scope(comp, user, depth + 1)
            if got is not None:
                return got
        return None

    by_reader = {}
    for comp, instrs in comps.items():
        if comp.startswith(("fused_", "wrapped_")):
            continue
        for name, _ in instrs:
            if name not in out:
                got = reader_scope(comp, name)
                if got is not None:
                    by_reader[name] = got
    out.update(by_reader)
    return out


def scope_seconds(op_s: Dict[str, float],
                  scopes: Dict[str, Tuple[str, str]]) -> Dict[str, float]:
    """Own device seconds (``Summary.op_s``) summed by ``scope.fwd`` /
    ``scope.bwd``; ops with no known scope go to ``unscoped``."""
    out: Dict[str, float] = {UNSCOPED: 0.0}
    for op, secs in op_s.items():
        got = scopes.get(op)
        key = f"{got[0]}.{got[1]}" if got else UNSCOPED
        out[key] = out.get(key, 0.0) + secs
    return out


def label_gaps(gaps: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[str, float, float]]) -> List[str]:
    """A label for each (start, end) gap: at each instant the innermost
    (shortest) span that covers it, a program span before a ``bench.*``
    one, and the label that covers most of the gap wins; ``OUTSIDE`` where
    no span does. ``spans``: (name, start, duration) on the gap's clock."""
    order = sorted(spans, key=lambda e: e[1])
    starts = [s for _, s, _ in order]
    longest = max((d for _, _, d in order), default=0.0)
    labels = []
    for a, b in gaps:
        near = [e for e in order[bisect.bisect_left(starts, a - longest):
                                 bisect.bisect_left(starts, b)]
                if e[1] + e[2] > a]
        ranked = sorted(near, key=lambda e: (e[0].startswith("bench."),
                                             e[2]))
        cuts = sorted({a, b} | {t for _, s, d in near
                                for t in (s, s + d) if a < t < b})
        share: Dict[str, float] = {}
        for lo, hi in zip(cuts, cuts[1:]):
            name = next((n for n, s, d in ranked
                         if s <= lo and hi <= s + d), OUTSIDE)
            share[name] = share.get(name, 0.0) + hi - lo
        labels.append(max(share, key=share.get) if share else OUTSIDE)
    return labels


def idle_gaps(device: Sequence[Tuple[str, float, float]], w0: float,
              w1: float) -> List[Tuple[float, float]]:
    """The (start, end) intervals of [w0, w1) in which no op of one chip
    ran, longest first."""
    from bench.trace import _union
    busy = _union([(max(s, w0), min(s + d, w1)) for _, s, d in device
                   if min(s + d, w1) > max(s, w0)])
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return sorted(gaps, key=lambda g: g[0] - g[1])


# --------------------------------------------------------------------------
# the cell's step, for the per-layer metrics
# --------------------------------------------------------------------------

def step_hlo(config: dict, mix: dict) -> str:
    """The optimized HLO text of the cell's jitted train step, built from
    the cell's flags as ``run.py`` builds the step it times (a compile
    cache hit where the cache holds it)."""
    import jax
    import jax.numpy as jnp
    from bench.layer_work import cell_run
    from repro.train.loop import init_train_state, make_train_step
    program = cell_run(config, mix)
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
        jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0),
                                                program.model)))
    b, s = mix["batch"], mix["seq"]
    x = (jax.ShapeDtypeStruct((b, s, config["d_model"]), jnp.float32,
                              sharding=dev)
         if config["frontend"] == "embed_stub"
         else jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=dev))
    y = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=dev)
    step = jax.jit(make_train_step(program.model, program))
    return step.lower(state, x, y).compile().as_text()


def cell_scope_seconds(ctx: dict) -> Dict[str, float]:
    """``scope_seconds`` of a metric context's traced window, per chip:
    worked out once a run (kept in ``ctx``) and logged to stderr as
    milliseconds a step."""
    if "scope_s" not in ctx:
        tr = ctx["trace"]
        config = ctx["config"]
        secs = scope_seconds(tr.op_s, hlo_scopes(
            step_hlo(config, ctx["mix"]), cell_scopes(config)))
        ctx["scope_s"] = {k: v / tr.chips for k, v in secs.items()}
        per_step = {k: round(1e3 * v / max(ctx["steps"], 1), 3)
                    for k, v in sorted(ctx["scope_s"].items())}
        print(f"[bench] device ms a step by scope: {json.dumps(per_step)}",
              file=sys.stderr)
    return ctx["scope_s"]


# --------------------------------------------------------------------------
# one traced window, attributed
# --------------------------------------------------------------------------

def _read_xplane(trace_dir: str):
    """(op events of each TPU chip, the host spans ``label_gaps`` reads)
    of the one ``.xplane.pb`` under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    device, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            device += [[(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
                       for line in plane.lines if line.name == "XLA Ops"]
        elif plane.name.startswith("/host:"):
            host += [(e.name, e.start_ns, e.duration_ns)
                     for line in plane.lines for e in line.events
                     if e.name.startswith(("runner.", "bench."))
                     or e.name in ("train", "python.gc")]
    return device, host


def attribute(cell: dict, seed: int, seconds: float, trace_dir: str,
              n_gaps: int = 10) -> dict:
    """Set ``cell`` up as ``run.py`` does, trace one window of about
    ``seconds`` into ``trace_dir`` and attribute it (module docstring)."""
    import statistics
    import tempfile
    import time

    import jax
    from jax.profiler import TraceAnnotation
    from bench import run, traffic
    from bench.trace import WINDOW_SPAN, base_name, reduce_events
    from repro.analysis.counters import draw_counts
    from repro.distributed.fault import StragglerDetector
    from repro.train.loop import (compile_run_schedule, init_train_state,
                                  make_train_step)
    config, mix = cell["config"], cell["mix"]
    ckpt = os.path.join(tempfile.mkdtemp(prefix="scopes_"), "ckpt")
    program = run.build_run(config, mix, seed, ckpt)
    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), program.model))
    ring = traffic.make_ring(config, mix, seed)
    step = jax.jit(make_train_step(program.model, program))
    times = run._StepTimes(StragglerDetector())
    runner = run.make_runner(shapes, seed, ring, step, ckpt, times)
    done = runner.run(mix["checked_steps"]
                      + mix["untimed_steps"]).steps_completed
    scopes = hlo_scopes(step.lower(runner.state, *ring[0])
                        .compile().as_text(), cell_scopes(config))

    jax.profiler.start_trace(trace_dir)
    times.times.clear()
    first = done
    with TraceAnnotation(WINDOW_SPAN):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            done = runner.run(done + 1).steps_completed
    jax.profiler.stop_trace()
    steps = done - first

    device, host = _read_xplane(trace_dir)
    summary = reduce_events(device, host, n_gaps)
    ((w0, wd),) = [(s, d) for n, s, d in host if n == WINDOW_SPAN]
    spans = [e for e in host if e[0] != WINDOW_SPAN]
    gaps = sorted((g for chip in device for g in idle_gaps(chip, w0,
                                                           w0 + wd)),
                  key=lambda g: g[0] - g[1])
    labels = label_gaps(gaps, spans)
    by_label: Dict[str, float] = {}
    for label, (a, b) in zip(labels, gaps):
        by_label[label] = by_label.get(label, 0.0) + (b - a) * 1e-9
    secs = scope_seconds(summary.op_s, scopes)
    per_step = max(steps, 1) * summary.chips

    def scope(op):
        return "%s.%s" % scopes[op] if op in scopes else UNSCOPED

    return {
        "steps": steps, "window_s": summary.window_s,
        "step_ms_median": 1e3 * statistics.median(times.times),
        "device_idle_share": 100.0 * (1 - summary.busy_s
                                      / summary.window_s),
        "scope_ms_per_step": {k: 1e3 * v / per_step
                              for k, v in sorted(secs.items())},
        "unscoped_share": (100.0 * secs[UNSCOPED] / sum(secs.values())
                           if summary.op_s else None),
        "top_ops": [[op, s, scope(op)] for op, s in summary.top_ops(12)],
        "top_unscoped": sorted(
            ([op, s] for op, s in summary.op_s.items()
             if op not in scopes), key=lambda kv: -kv[1])[:5],
        "idle_gaps": [[label, (b - a) * 1e-9] for label, (a, b)
                      in zip(labels[:n_gaps], gaps)],
        "idle_s_by_label": by_label,
        "events_per_step": {
            k: sum(n for op, n in summary.op_n.items()
                   if base_name(op) == k) / per_step
            for k in ("gemm_rng", "philox_mask", "flash_fwd",
                      "flash_bwd_dq", "flash_bwd_dkv")},
        "draw_calls": {c.kernel: c.calls for c in draw_counts(
            program.model, compile_run_schedule(program.model, program))},
    }


def main(argv=None) -> int:
    import argparse
    import shutil
    import tempfile
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir", default="",
                    help="keep the trace here (default: a temporary "
                    "directory, removed)")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    try:
        run.device_info(cell["chips"])
    except run.NoChip as e:
        print(f"[scopes] no result: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="scopes_trace_")
    try:
        result = attribute(cell, args.seed, args.seconds, trace_dir)
    finally:
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
