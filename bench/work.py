"""Operations and bytes of each kernel class, from a cell's shapes.

The counts are those of the algorithm, not of any one realization: the
same attention is counted whether the keep bits are read from a premask
plane, replayed in registers, or the scores run through XLA. A roofline
share is the least time the chip could take (``roofline_s``) over the
measured device time, so nothing here may count work the algorithm does
not need: recomputation, padding and dropout bits are left out.
"""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error, never a
    default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time for the work: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def attention_flops(b: int, hq: int, s: int, d: int,
                    causal: bool = True, window: int = 0) -> float:
    """One attention layer's forward and backward: the forward's two
    S x S matmuls (q.k and p.v) and the backward's four (dv, dp, dq,
    dk), each 2*B*Hq*S*S*D, halved under a causal mask. A sliding window
    of W (query q sees key k when q - W < k <= q, as the flash kernel
    masks it) keeps S*W - W*W/2 of the pairs in place of S*S/2; the two
    agree at W = S."""
    if window:
        w = min(window, s)
        return 6.0 * 2.0 * b * hq * (s * w - w * w / 2.0) * d
    per_matmul = 2.0 * b * hq * s * s * d * (0.5 if causal else 1.0)
    return 6.0 * per_matmul


def attention_bytes(b: int, hq: int, hkv: int, s: int, d: int,
                    itemsize: int) -> float:
    """HBM bytes one attention layer's forward and backward must move:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv. Softmax statistics and dropout bits are not
    counted."""
    q_like = b * hq * s * d          # q, o, do, dq
    kv_like = b * hkv * s * d        # k, v, dk, dv
    return float(itemsize * (6 * q_like + 6 * kv_like))


def gemm_flops(m: int, n: int, k: int) -> float:
    return 2.0 * m * n * k


def gemm_bytes(m: int, n: int, k: int, in_itemsize: int,
               out_itemsize: int) -> float:
    """x (m, k) and w (k, n) read once, y (m, n) written once."""
    return float(in_itemsize * (m * k + k * n) + out_itemsize * m * n)


def layer_windows(model: dict) -> dict:
    """{attention window: number of layers}, 0 for full causal attention:
    ``block_pattern`` (default ``["full"]``) repeated over ``n_layers`` as
    the program's ``ModelConfig.layer_kinds`` repeats it, a ``local``
    layer at ``local_window``."""
    pattern = model.get("block_pattern", ["full"])
    out: dict = {}
    for i in range(model["n_layers"]):
        kind = pattern[i % len(pattern)]
        if kind not in ("full", "local"):
            raise ValueError(f"no attention count for {kind!r} layers")
        w = model.get("local_window", 0) if kind == "local" else 0
        out[w] = out.get(w, 0) + 1
    return out


def matmul_params(model: dict) -> float:
    """Parameters that enter a matmul per token: the attention
    projections, the FFN and the output head. The embedding lookup is a
    gather, and norms and biases are elementwise.

    With ``moe``, every layer from ``moe.first_dense_layers`` on has, in
    place of the ``d_ff`` FFN, the router (d_model x the published expert
    count: ``published["moe.n_experts"]``, else ``moe.n_experts``), the
    shared experts whole, and the routed experts at top_k x held /
    published expert FFNs a token: the expected share of this chip's
    experts under even routing."""
    d, hd = model["d_model"], model["head_dim"]
    nq, nkv = model["n_heads"], model["n_kv_heads"]
    ffn_mats = 3 if model["ffn"] in ("swiglu", "geglu") else 2
    attn = d * nq * hd * 2 + d * nkv * hd * 2
    dense_ffn = ffn_mats * d * model["d_ff"]
    head = d * model["vocab_size"]
    moe = model.get("moe")
    if moe is None:
        return model["n_layers"] * (attn + dense_ffn) + head
    if moe.get("dense_residual"):
        raise ValueError("no count for a dense residual FFN beside experts")
    n_dense = min(moe.get("first_dense_layers", 0), model["n_layers"])
    held = moe["n_experts"]
    published = model.get("published", {}).get("moe.n_experts", held)
    expert = ffn_mats * d * moe["d_ff_expert"]
    moe_ffn = (d * published + moe.get("n_shared_experts", 0) * expert
               + moe["top_k"] * held / published * expert)
    return (model["n_layers"] * attn + n_dense * dense_ffn
            + (model["n_layers"] - n_dense) * moe_ffn + head)


def model_flops_per_step(model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: 6 per matmul parameter per token
    (forward 2, backward 4) plus each layer's attention score matmuls,
    causal or windowed. Recomputation is not counted."""
    dense = 6.0 * matmul_params(model) * batch * seq
    attn = sum(n * attention_flops(batch, model["n_heads"], seq,
                                   model["head_dim"], window=w)
               for w, n in layer_windows(model).items())
    return dense + attn


def attention_roofline_s(model: dict, batch: int, seq: int,
                         peak: dict) -> float:
    """Least time for one step's attention, forward and backward, over
    every layer (causal or windowed), with operands in the
    configuration's compute dtype."""
    itemsize = DTYPE_BYTES[model["compute_dtype"]]
    nbytes = attention_bytes(batch, model["n_heads"], model["n_kv_heads"],
                             seq, model["head_dim"], itemsize)
    return sum(n * roofline_s(
        attention_flops(batch, model["n_heads"], seq, model["head_dim"],
                        window=w), nbytes, peak)
        for w, n in layer_windows(model).items())


def out_proj_roofline_s(model: dict, batch: int, seq: int,
                        host_dtype: str, peak: dict) -> float:
    """Least time for one step's attention out-projections (forward), the
    GEMMs that host the next layer's dropout bits under ``prev_gemm``:
    (B*S, Hq*D) x (Hq*D, d_model), operands and result in the host's
    dtype."""
    m = batch * seq
    k = model["n_heads"] * model["head_dim"]
    n = model["d_model"]
    item = DTYPE_BYTES[host_dtype]
    one = roofline_s(gemm_flops(m, n, k), gemm_bytes(m, n, k, item, item),
                     peak)
    return model["n_layers"] * one
