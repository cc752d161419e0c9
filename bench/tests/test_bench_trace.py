"""trace.py on a small synthetic trace."""
import pytest

from bench import trace

# the window spans t = 100 .. 1100 ns
HOST = [("bench.window", 100, 1000),
        ("bench.batch", 90, 15),
        ("bench.step", 105, 20),
        ("bench.step", 590, 30),
        ("outer", 0, 5000)]
DEVICE = [
    ("%copy.1 = f32[4] copy(%x)", 50, 100),           # clipped to 100..150
    ("%while.3 = (s32[]) while(%t), body=%b", 200, 300),
    ("%flash_fwd.7 = (f32[2]) custom-call(%q)", 210, 100),
    ("%gemm_rng.2 = bf16[2] custom-call(%x)", 350, 50),
    ("%flash_fwd.7 = (f32[2]) custom-call(%q)", 600, 200),
    ("%fusion.9 = f32[2] fusion(%a)", 750, 100),      # overlaps flash_fwd
    ("%fusion.9 = f32[2] fusion(%a)", 1150, 10),      # after the window
]


def test_busy_kernels_and_gaps():
    s = trace.reduce_events([DEVICE], HOST, n_gaps=3)
    assert s.window_s == pytest.approx(1000e-9)
    # union: 100-150, 200-500, 600-850
    assert s.busy_s == pytest.approx(600e-9)
    assert s.kernel_s("flash_fwd") == pytest.approx(300e-9)
    assert s.kernel_s("gemm_rng") == pytest.approx(50e-9)
    # the while's own time excludes the flash_fwd and gemm_rng inside it
    assert s.op_s["while.3"] == pytest.approx(150e-9)
    assert s.op_n["fusion.9"] == 1
    # gaps: 850-1100 (250, no span), 500-600 (100, step at 590 covers
    # 10), 150-200 (50, no span)
    assert [g for g, _ in s.idle_gaps] == [
        "outside bench spans", "bench.step", "outside bench spans"]
    assert [d for _, d in s.idle_gaps] == pytest.approx(
        [250e-9, 100e-9, 50e-9])
    assert s.top_ops(1)[0][0] == "flash_fwd.7"


def test_chips_average():
    s = trace.reduce_events([DEVICE, DEVICE[:1]], HOST)
    assert s.chips == 2
    assert s.busy_s == pytest.approx((600e-9 + 50e-9) / 2)


def test_window_span_required():
    with pytest.raises(ValueError):
        trace.reduce_events([DEVICE], HOST[1:])
