"""scopes.py: op -> scope from the optimized HLO, the scope breakdown,
idle-gap labels by the innermost program span, and the three per-layer
metrics that read them.

``data/tiny-yi-step.v5e.hlo.txt`` is an excerpt of the optimized HLO of
the tiny yi cell's train step compiled for a described v5e (backend
configs and source locations stripped), with one stacked-gradient write
added in the form the chip's compiler gives the full-size step: a
``bitcast_dynamic-update-slice_fusion`` whose own metadata is the scan's
``dynamic_update_slice`` and whose convolution is an FFN weight
gradient, and one weight cast hoisted out of the layer scan into the
scan's operand tuple, as the compiler leaves it without metadata."""
import os

import pytest

from bench import layer_work, run, scopes, trace
from bench.tests.common import tiny_cell
from bench.tests.test_bench_trace import DEVICE, HOST

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def fixture_scopes():
    with open(os.path.join(HERE, "data", "tiny-yi-step.v5e.hlo.txt")) as f:
        return scopes.hlo_scopes(f.read())


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/attn/"
     "dot_general", ("attn", "bwd")),
    ("jit(train_step)/jvp(layers)/while/body/dynamic_slice",
     ("layers", "fwd")),
    ("jit(train_step)/optimizer/mul", ("optimizer", "fwd")),
    ("jit(train_step)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add",
     ("loss", "bwd")),
    ("jit(mask)/add", None),                # a function's name, no scope
    ("jit(train_step)/add", None),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_fusions_take_their_matmul_scope(fixture_scopes):
    got = fixture_scopes
    # the stacked write's own metadata is the scan's; its convolution is
    # the FFN's weight gradient
    assert got["bitcast_dynamic-update-slice_fusion.4"] == ("ffn", "bwd")
    assert got["convolution_add_fusion.4"] == ("attn", "bwd")
    # no matmul inside: the fusion's own metadata
    assert got["fusion.339"] == ("ffn", "fwd")
    assert got["multiply_reduce_fusion.3"] == ("optimizer", "fwd")
    assert got["flash_fwd.7"] == ("attn", "fwd")
    assert got["flash_bwd_dq.12"] == ("attn", "bwd")
    assert got["gemm_rng.7"] == ("attn", "fwd")
    assert got["copy.233"] == ("layers", "fwd")
    assert got["copy.155"] == ("loss", "bwd")
    # a weight's cast the compiler hoisted out of the scan has no
    # metadata: it takes the scope of the body's op that reads it
    assert got["convert.900"] == ("ffn", "fwd")
    # an argument's copy that nothing scoped reads stays unscoped
    assert "copy-start.1" not in got and "copy-done.1" not in got


def test_scope_seconds_split_and_unscoped(fixture_scopes):
    device = [
        ("%flash_fwd.7 = (f32[1,4,256,16]) custom-call(%a)", 100, 40),
        ("%gemm_rng.7 = (bf16[256,64]) custom-call(%b)", 140, 10),
        ("%fusion.339 = f32[64] fusion(%c)", 150, 5),
        ("%flash_bwd_dq.12 = f32[1,4,256,16] custom-call(%d)", 200, 30),
        ("%bitcast_dynamic-update-slice_fusion.4 = f32[2,64,128] "
         "fusion(%e)", 230, 20),
        ("%multiply_reduce_fusion.3 = f32[] fusion(%f)", 250, 15),
        ("%copy-done.1 = f32[2,128,64] copy-done(%g)", 265, 5),
        ("%fusion.9999 = f32[2] fusion(%h)", 270, 25),   # not in the HLO
    ]
    summary = trace.reduce_events([device], [("bench.window", 100, 200)])
    secs = scopes.scope_seconds(summary.op_s, fixture_scopes)
    ns = {k: round(v * 1e9) for k, v in secs.items()}
    assert ns == {"attn.fwd": 50, "ffn.fwd": 5, "attn.bwd": 30,
                  "ffn.bwd": 20, "optimizer.fwd": 15, "unscoped": 30}
    # the attribution reads the summary and leaves it as it was
    assert summary.busy_s == pytest.approx(150e-9)
    assert sum(summary.op_s.values()) == pytest.approx(150e-9)


def test_existing_metrics_unchanged_on_the_synthetic_trace():
    """The four accepted metrics, read on trace.py's synthetic trace,
    keep their values with the new readers beside them."""
    cell = tiny_cell("tiny-yi")
    summary = trace.reduce_events([DEVICE], HOST)
    ctx = {"config": cell["config"], "mix": cell["mix"], "chips": 1,
           "peak": PEAK, "trace": summary, "steps": 2,
           "window_s": summary.window_s}
    model, mix = cell["config"], cell["mix"]
    b, s = mix["batch"], mix["seq"]
    from bench import work
    want = {
        "device_idle_share": 40.0,
        "step_mfu": 100.0 * work.model_flops_per_step(model, b, s) * 2
        / 1000e-9 / 197e12,
        "flash_attn_roofline": 100.0 * 2 * work.attention_roofline_s(
            model, b, s, PEAK) / 300e-9,
        "gemm_rng_roofline": 100.0 * 2 * work.out_proj_roofline_s(
            model, b, s, "bfloat16", PEAK) / 50e-9,
    }
    for name, value in want.items():
        assert run.read_metric(name, ctx) == pytest.approx(value), name
    # none of the synthetic ops is in the tiny step's HLO: the new
    # rooflines have nothing to read, as on a program without scopes
    assert run.read_metric("ffn_roofline", ctx) is None
    assert run.read_metric("optimizer_roofline", ctx) is None


def test_new_metrics_on_the_tiny_step():
    """The three new metrics on the tiny cell's compiled step, with every
    op of the step given 1 us: each roofline is its least time over the
    scope's ops' time, and the draw share comes from the counter."""
    cell = tiny_cell("tiny-yi")
    config, mix = cell["config"], cell["mix"]
    got = scopes.hlo_scopes(scopes.step_hlo(config, mix))
    op_s = {op: 1e-6 for op in got}
    summary = trace.Summary(window_s=1.0, busy_s=0.5, op_s=op_s,
                            op_n={op: 1 for op in got}, idle_gaps=[],
                            chips=1)
    ctx = {"config": config, "mix": mix, "chips": 1, "peak": PEAK,
           "trace": summary, "steps": 3, "window_s": 1.0}
    n_ffn = sum(1 for s in got.values() if s[0] == "ffn")
    n_opt = sum(1 for s in got.values() if s[0] == "optimizer")
    assert n_ffn and n_opt
    assert run.read_metric("ffn_roofline", ctx) == pytest.approx(
        100.0 * 3 * layer_work.ffn_roofline_s(
            config, mix["batch"], mix["seq"], PEAK) / (n_ffn * 1e-6))
    n_params = layer_work.param_count(config, mix)
    assert n_params > layer_work.ffn_matmul_params(config)
    assert run.read_metric("optimizer_roofline", ctx) == pytest.approx(
        100.0 * 3 * 24 * n_params / PEAK["hbm_bytes_per_s"]
        / (n_opt * 1e-6))
    # 2 layers at 1 x 256 with 128-blocks: 3 flash kernels re-derive
    # 3/4 of each plane, the dropped gemm_rng emissions draw it whole
    assert run.read_metric("rng_live_draw_share", ctx) == pytest.approx(
        100.0 * 2.25 / 3.25)


def test_label_gaps_innermost_program_span():
    spans = [("train", 0, 100), ("runner.batch", 2, 3),
             ("bench.batch", 2, 3), ("runner.step", 10, 20),
             ("bench.step", 11, 18), ("runner.wait", 30, 50),
             ("runner.loss", 80, 10), ("python.gc", 84, 4),
             ("bench.batch", 148, 10), ("train", 200, 100)]
    gaps = [(35, 45),        # inside runner.wait
            (12, 20),        # bench.step inside runner.step
            (70, 85),        # 10 of 15 in runner.wait, 5 in runner.loss
            (84, 88),        # the collector, inside runner.loss
            (94, 105),       # 6 in the marker, 5 outside any span
            (170, 180),      # between steps
            (150, 156)]      # a benchmark span alone
    assert scopes.label_gaps(gaps, spans) == [
        "runner.wait", "runner.step", "runner.wait", "python.gc",
        "train", scopes.OUTSIDE, "bench.batch"]


def test_idle_gaps_of_one_chip():
    events = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 90, 20)]
    assert scopes.idle_gaps(events, 0, 100) == [(35, 90), (15, 30)]
