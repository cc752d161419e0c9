"""work.py against counts made by hand."""
import pytest

from bench import work

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_attention_counts():
    # B=1, Hq=2, Hkv=1, S=4, D=8, causal: each of the six S x S matmuls
    # is 2*1*2*4*4*8 = 512 FLOPs, halved by the mask
    assert work.attention_flops(1, 2, 4, 8) == 6 * 256
    assert work.attention_flops(1, 2, 4, 8, causal=False) == 6 * 512
    # q, o, do, dq (1*2*4*8 = 64 elements each) moved 6 times between
    # them, k, v, dk, dv (32 each) likewise, at 4 bytes
    assert work.attention_bytes(1, 2, 1, 4, 8, 4) == 4 * (6 * 64 + 6 * 32)


def test_gemm_counts():
    # (3 x 5) @ (5 x 7)
    assert work.gemm_flops(3, 7, 5) == 2 * 3 * 7 * 5
    assert work.gemm_bytes(3, 7, 5, 2, 4) == 2 * (15 + 35) + 4 * 21
    assert work.roofline_s(1000.0, 10.0, PEAK) == 10.0      # compute-bound
    assert work.roofline_s(10.0, 1000.0, PEAK) == 100.0     # memory-bound


def test_model_flops():
    model = {"n_layers": 2, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
             "head_dim": 2, "d_ff": 8, "vocab_size": 10, "ffn": "swiglu"}
    # per layer: q and o 4*2*2 each, k and v 4*1*2 each, FFN 3*4*8;
    # the head 4*10
    n = 2 * (16 + 16 + 8 + 8 + 96) + 40
    assert work.matmul_params(model) == n
    assert work.model_flops_per_step(model, 3, 4) == (
        6 * n * 12 + 2 * work.attention_flops(3, 2, 4, 2))


def test_unknown_device_raises():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")
