"""Plain float32 reference of the train step the benchmark drives.

It imports nothing of the program. It is written from the configuration
file (``bench/configs``), the mix (``bench/traffic``) and the program's
published dropout contract, and it starts from the benchmark's own weights
(weights.py) and batches (traffic.py).

The model: pre-norm decoder layers. Attention is causal softmax attention
with grouped kv heads (query head h reads kv head h // (Hq / Hkv)), rotary
embedding on the two halves of each head when the configuration has rope,
and dropout on the normalized probabilities: a dropped probability is 0,
a kept one is scaled by 1 / (1 - p). The FFN is SwiGLU, or a GELU MLP with
biases (GELU in its tanh form). Norms are LayerNorm or RMSNorm. A final
norm, an output head and the mean cross-entropy over every position.

The keep bits are the program's dropout contract: counter-based
Philox-4x32 with the configured number of rounds. The element (b, h, q, k)
of layer l at train step t draws the counter (k, q // 4, b * Hq + h,
l * 1000003) under the key ((t * 2654435761 + seed) mod 2**32, 0), takes
word q % 4 of the result, and is kept when that word is at least
round(p * 2**32). Whether the program reads those bits from a plane,
replays them inside flash attention or draws them under a GEMM, they are
the same bits, so the reference covers every realization.

The optimizer is AdamW with the mix's hyperparameters: global-norm
clipping, linear warmup then cosine decay to a tenth, bias correction,
and decoupled weight decay on weight matrices (not on norm scales or
biases).

Everything is float32, at the precision the configuration states:
``matmul_precision`` for the dense matmuls (``default`` is the TPU's one
bfloat16 pass), ``attention_precision`` for the score and value products,
and the out-projection that hosts the dropout bits with its operands in
the mix's ``host_dtype`` (operands and result). It runs layer by layer under rematerialization
and attention one batch row at a time, so that it fits on the chip once
the program's state is freed.

A configuration file names its reference (``"reference"``, default this
file); every reference exports ``covers(config)`` and ``make_step(cfg,
mix)``. Another reference may import the shared parts of this one:
``keep_bits``, ``_norm``, ``_rope``, ``lr_at``, ``decays`` and the AdamW
step of ``make_step``, which takes its loss as ``loss_fn``.
"""
from __future__ import annotations

import math

import numpy as np

PHILOX_M0, PHILOX_M1 = 0xD2511F53, 0xCD9E8D57
PHILOX_W0, PHILOX_W1 = 0x9E3779B9, 0xBB67AE85
LAYER_SALT_PRIME = 1000003
STEP_SEED_MULT = 2654435761


def _mulhilo(m: int, a):
    """(hi, lo) words of the 64-bit product of the constant m and the
    uint32 array a, from 16-bit halves (no 64-bit integers needed)."""
    import jax.numpy as jnp
    mask16 = np.uint32(0xFFFF)
    a_lo, a_hi = a & mask16, a >> np.uint32(16)
    m_lo, m_hi = np.uint32(m & 0xFFFF), np.uint32(m >> 16)
    ll, lh = a_lo * m_lo, a_lo * m_hi
    hl, hh = a_hi * m_lo, a_hi * m_hi
    mid = (ll >> np.uint32(16)) + (lh & mask16) + (hl & mask16)
    hi = hh + (lh >> np.uint32(16)) + (hl >> np.uint32(16)) \
        + (mid >> np.uint32(16))
    lo = a * np.uint32(m)
    return hi.astype(jnp.uint32), lo.astype(jnp.uint32)


def philox4x32(x0, x1, x2, x3, k0, k1, rounds: int):
    """Philox-4x32 (Salmon et al., SC 2011) over broadcast uint32 arrays."""
    import jax.numpy as jnp
    x0, x1, x2, x3 = (jnp.asarray(v, jnp.uint32) for v in (x0, x1, x2, x3))
    k0, k1 = jnp.asarray(k0, jnp.uint32), jnp.asarray(k1, jnp.uint32)
    for _ in range(rounds):
        hi0, lo0 = _mulhilo(PHILOX_M0, x0)
        hi1, lo1 = _mulhilo(PHILOX_M1, x2)
        x0, x1, x2, x3 = hi1 ^ x1 ^ k0, lo1, hi0 ^ x3 ^ k1, lo0
        k0 = k0 + np.uint32(PHILOX_W0)
        k1 = k1 + np.uint32(PHILOX_W1)
    return x0, x1, x2, x3


def keep_bits(b, n_heads: int, s: int, p: float, rounds: int, step,
              seed: int, layer: int):
    """Bool (Hq, S, S) keep bits of batch row b (may be traced) of layer
    ``layer`` at train step ``step`` (may be traced)."""
    import jax.numpy as jnp
    key_lo = (jnp.asarray(step, jnp.uint32) * np.uint32(STEP_SEED_MULT)
              + np.uint32(seed & 0xFFFFFFFF))
    salt = np.uint32((layer * LAYER_SALT_PRIME) & 0xFFFFFFFF)
    h = jnp.arange(n_heads, dtype=jnp.uint32).reshape(n_heads, 1, 1)
    bh = jnp.asarray(b, jnp.uint32) * np.uint32(n_heads) + h
    q4 = jnp.arange(s // 4, dtype=jnp.uint32).reshape(1, s // 4, 1)
    k = jnp.arange(s, dtype=jnp.uint32).reshape(1, 1, s)
    words = philox4x32(k, q4, bh, salt, key_lo, np.uint32(0), rounds)
    u = jnp.stack(words, axis=2).reshape(n_heads, s, s)
    threshold = min(max(int(round(p * 2.0 ** 32)), 0), 0xFFFFFFFF)
    return u >= np.uint32(threshold)


PRECISION = {"default": "DEFAULT", "high": "HIGH", "highest": "HIGHEST"}


def _precision(name: str):
    import jax
    return getattr(jax.lax.Precision, PRECISION[name])


def _mm(a, b, cfg):
    """A dense matmul at the configuration's stated precision."""
    import jax.numpy as jnp
    return jnp.matmul(a, b, precision=_precision(cfg["matmul_precision"]))


def _host_mm(a, b, cfg, mix):
    """The out-projection: operands and result in the mix's host dtype,
    float32 accumulation."""
    import jax.numpy as jnp
    dt = jnp.dtype(mix["dropout"]["host_dtype"])
    out = jnp.matmul(a.astype(dt), b.astype(dt),
                     precision=_precision(cfg["matmul_precision"]),
                     preferred_element_type=jnp.float32)
    return out.astype(dt).astype(jnp.float32)


def _norm(x, p, cfg):
    import jax
    import jax.numpy as jnp
    eps = cfg["norm_eps"]
    if cfg["norm"] == "layernorm":
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    if cfg["norm"] == "rmsnorm":
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + eps) * p["scale"]
    raise ValueError(f"unknown norm {cfg['norm']!r}")


def _rope(x, theta: float):
    """x (B, H, S, D): rotate (x[:D/2], x[D/2:]) pairs by position."""
    import jax.numpy as jnp
    d, s = x.shape[-1], x.shape[-2]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, cfg, mix, step, layer):
    """q (B, Hq, S, D), k/v (B, Hkv, S, D) -> (B, Hq, S, D)."""
    import jax
    import jax.numpy as jnp
    drop = mix["dropout"]
    p, rounds, seed = drop["p"], drop["philox_rounds"], drop["seed"]
    hq, s, d = q.shape[1], q.shape[2], q.shape[3]
    group = hq // k.shape[1]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    prec = _precision(cfg["attention_precision"])

    @jax.checkpoint
    def row(args):
        qb, kb, vb, b = args
        kb = jnp.repeat(kb, group, axis=0)
        vb = jnp.repeat(vb, group, axis=0)
        scores = jnp.einsum("hqd,hkd->hqk", qb, kb,
                            precision=prec) / math.sqrt(d)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        if p > 0.0:
            keep = keep_bits(b, hq, s, p, rounds, step, seed, layer)
            probs = jnp.where(keep, probs, 0.0) / (1.0 - p)
        return jnp.einsum("hqk,hkd->hqd", probs, vb, precision=prec)

    return jax.lax.map(row, (q, k, v, jnp.arange(q.shape[0])))


def _layer(h, lp, cfg, mix, step, layer):
    import jax
    import jax.numpy as jnp
    b, s, _ = h.shape
    hq, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    att = lp["mix"]
    a = _norm(h, lp["norm_mix"], cfg)
    q = _mm(a, att["w_q"], cfg).reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    k = _mm(a, att["w_k"], cfg).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    v = _mm(a, att["w_v"], cfg).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    if cfg["rope"]:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    o = _attention(q, k, v, cfg, mix, step, layer)
    h = h + _host_mm(o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd),
                     att["w_o"], cfg, mix)
    f = _norm(h, lp["norm_ffn"], cfg)
    ffn = lp["ffn"]
    if cfg["ffn"] == "swiglu":
        out = _mm(jax.nn.silu(_mm(f, ffn["w_gate"], cfg))
                  * _mm(f, ffn["w_up"], cfg), ffn["w_down"], cfg)
    elif cfg["ffn"] == "gelu":
        act = jax.nn.gelu(_mm(f, ffn["w_up"], cfg) + ffn["b_up"],
                          approximate=True)
        out = _mm(act, ffn["w_down"], cfg) + ffn["b_down"]
    else:
        raise ValueError(f"unknown ffn {cfg['ffn']!r}")
    return h + out


def covers(config: dict):
    """None when this reference computes the configuration as run (the
    configuration file's keys over every field of the program's model),
    else the reason it does not."""
    if config.get("moe") is not None or set(
            config.get("block_pattern", ["full"])) != {"full"}:
        return "the reference covers dense full-attention blocks"
    if config["qkv_bias"] or config["qk_norm"] or config["tie_embeddings"]:
        return "the reference has no qkv bias, qk-norm or tied embeddings"
    return None


def loss(master, x, y, step, cfg, mix):
    """Mean cross-entropy of one batch."""
    import jax
    import jax.numpy as jnp
    (stack,) = master["stacks"]
    if set(stack) != {"l0"}:
        raise ValueError("the reference runs a uniform stack of layers")
    h = x if cfg["frontend"] == "embed_stub" else master["embed"][x]
    for layer in range(cfg["n_layers"]):
        lp = jax.tree.map(lambda a, i=layer: a[i], stack["l0"])
        body = jax.checkpoint(
            lambda h_, lp_, t_, i=layer: _layer(h_, lp_, cfg, mix, t_, i))
        h = body(h, lp, step)
    h = _norm(h, master["final_norm"], cfg)
    logits = _mm(h, master["unembed"], cfg)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def lr_at(opt: dict, step):
    import jax.numpy as jnp
    t = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(t / max(opt["warmup_steps"], 1), 1.0)
    if opt["schedule"] != "cosine":
        raise ValueError(f"unknown schedule {opt['schedule']!r}")
    frac = jnp.clip((t - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1),
                    0.0, 1.0)
    return opt["lr"] * warm * (0.1 + 0.45 * (1.0 + jnp.cos(jnp.pi * frac)))


def decays(path) -> bool:
    """Weight decay applies to weight matrices, not to norms or biases."""
    names = [str(getattr(k, "key", k)) for k in path]
    last = names[-1]
    return not (last in ("scale", "bias") or last.startswith("b_")
                or any("norm" in n for n in names))


def make_step(cfg: dict, mix: dict, loss_fn=loss):
    """train_step(master, m, v, x, y, step) -> (master, m, v, loss,
    clipped_grads): one reference AdamW step on ``loss_fn(master, x, y,
    step, cfg, mix)``."""
    import jax
    import jax.numpy as jnp
    opt = mix["optimizer"]

    def step_fn(master, m, v, x, y, step):
        value, grads = jax.value_and_grad(
            lambda p_: loss_fn(p_, x, y, step, cfg, mix))(master)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        lr = lr_at(opt, step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        bc1, bc2 = 1.0 - opt["b1"] ** t, 1.0 - opt["b2"] ** t

        def upd(path, g, m_, v_, p_):
            m_ = opt["b1"] * m_ + (1.0 - opt["b1"]) * g
            v_ = opt["b2"] * v_ + (1.0 - opt["b2"]) * jnp.square(g)
            delta = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + opt["eps"])
            if decays(path):
                delta = delta + opt["weight_decay"] * p_
            return p_ - lr * delta, m_, v_

        out = jax.tree_util.tree_map_with_path(upd, grads, m, v, master)
        is_triple = lambda t_: isinstance(t_, tuple)
        new_p = jax.tree.map(lambda o: o[0], out, is_leaf=is_triple)
        new_m = jax.tree.map(lambda o: o[1], out, is_leaf=is_triple)
        new_v = jax.tree.map(lambda o: o[2], out, is_leaf=is_triple)
        return new_p, new_m, new_v, value, grads

    return step_fn
