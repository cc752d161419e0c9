"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``
and makes its batches on the device from the seed.

A mix gives the batch, the sequence length and the size of the ring of
distinct batches the run cycles through. Token ids (and labels) are
log-uniform over the configuration's vocabulary: rank r has probability
about 1/(r+1), the Zipf-like shape of language data, as in the program's
own synthetic pipeline. A configuration with a stubbed frontend
(``"frontend": "embed_stub"``) gets standard-normal frame embeddings in
place of token ids. Every seed gives batches of the same shapes, so the
work of a run does not depend on the seed.
"""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_ring(model: dict, mix: dict, seed: int):
    """``mix["ring"]`` distinct (x, y) batches, made on the default device
    in one jitted call. x is (B, S) int32 ids, or (B, S, d_model) f32
    embeddings for a stubbed frontend; y is (B, S) int32 labels."""
    import jax
    import jax.numpy as jnp
    b, s, ring = mix["batch"], mix["seq"], mix["ring"]
    vocab, d = model["vocab_size"], model["d_model"]
    stub = model["frontend"] == "embed_stub"

    def gen(key):
        k_ids, k_emb = jax.random.split(jax.random.fold_in(key, 0x7A11C))
        u = jax.random.uniform(k_ids, (ring, b, s + 1), jnp.float32)
        ranks = jnp.floor(jnp.exp(u * jnp.log(float(vocab)))) - 1.0
        ids = jnp.clip(ranks, 0, vocab - 1).astype(jnp.int32)
        y = ids[:, :, 1:]
        x = (jax.random.normal(k_emb, (ring, b, s, d), jnp.float32)
             if stub else ids[:, :, :-1])
        return [(x[i], y[i]) for i in range(ring)]

    return jax.jit(gen)(seed_key(seed))
