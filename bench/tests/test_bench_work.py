"""work.py against counts made by hand."""
import json
import os

import pytest

from bench import traffic, work

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


def test_local_layer_counts():
    # B=1, Hq=2, S=8, D=4. A window of 4 keeps 8*4 - 4*4/2 = 24 of the
    # pairs; a window of S keeps S*S/2 = 32, as the causal mask alone
    assert work.attention_flops(1, 2, 8, 4, window=4) == 6 * 2 * 2 * 24 * 4
    assert work.attention_flops(1, 2, 8, 4, window=8) == 6 * 2 * 2 * 32 * 4
    assert work.attention_flops(1, 2, 8, 4, window=8) == \
        work.attention_flops(1, 2, 8, 4)
    # three local layers to one full over 4 layers: L L F L
    model = {"n_layers": 4, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
             "head_dim": 4, "d_ff": 8, "vocab_size": 10, "ffn": "gelu",
             "block_pattern": ["local", "local", "full"], "local_window": 4,
             "compute_dtype": "float32"}
    assert work.layer_windows(model) == {4: 3, 0: 1}
    attn = 3 * 6 * 2 * 2 * 24 * 4 + 6 * 2 * 2 * 32 * 4
    assert work.model_flops_per_step(model, 1, 8) == (
        6 * work.matmul_params(model) * 8 + attn)
    # compute-bound at 1 FLOP/s
    peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    assert work.attention_roofline_s(model, 1, 8, peak) == attn


def test_moe_layer_counts():
    # 3 layers, the first dense; 2 of 8 experts held here, top-4, one
    # shared expert. Attention a layer: q and o 4*2*2 each, k and v 4*1*2
    # each = 48; the dense FFN 3*4*8 = 96; an expert 3*4*6 = 72. An
    # expert layer: the router 4*8 = 32 (the published count), the shared
    # expert 72, the routed experts 4 * 2/8 = 1 expert FFN a token, 72.
    model = {"n_layers": 3, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
             "head_dim": 2, "d_ff": 8, "vocab_size": 10, "ffn": "swiglu",
             "moe": {"n_experts": 2, "top_k": 4, "d_ff_expert": 6,
                     "n_shared_experts": 1, "first_dense_layers": 1},
             "published": {"moe.n_experts": 8}}
    assert work.matmul_params(model) == 3 * 48 + 96 + 2 * (32 + 72 + 72) + 40
    # every expert held: the router's width and the routed share follow
    model["published"] = {}
    assert work.matmul_params(model) == 3 * 48 + 96 + 2 * (8 + 72 + 4 * 72) + 40


@pytest.mark.parametrize("config, mix, flops, attn_s", [
    ("musicgen-large-5l", "b4s1536.drop10", 10011568766976.0,
     0.00368730021978022),
    ("yi-6b-tp4-2l", "b1s4096.drop10", 8929237008384.0,
     0.0010464894934416244),
])
def test_existing_configs_pinned(config, mix, flops, attn_s):
    """The counts of the accepted cells, exactly as before the counts
    followed layer kinds and experts."""
    with open(os.path.join(work.BENCH, "configs", f"{config}.json")) as f:
        model = json.load(f)
    m = traffic.load(mix)
    assert work.model_flops_per_step(model, m["batch"], m["seq"]) == flops
    assert work.attention_roofline_s(model, m["batch"], m["seq"],
                                     work.peaks("TPU v5 lite")) == attn_s
