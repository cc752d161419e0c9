"""Weights made by the benchmark from the seed, in one jitted call on the
device, in the layout of the program's train state.

The program's own initializer is not used: the reference (reference.py)
must take nothing the program made, and both start from these weights.
Matrices are normal with std 1/sqrt(fan_in) (the embedding and output
head 0.02), norm scales one, biases zero; Adam's moments start at zero
and the step at 0.
"""
from __future__ import annotations

import math

from bench.traffic import seed_key


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def init_rule(path, shape) -> tuple:
    """("ones" | "zeros" | "normal", std) for the leaf at ``path``."""
    name = _leaf_name(path)
    if name == "scale":
        return "ones", 0.0
    if name == "bias" or name.startswith("b_"):
        return "zeros", 0.0
    if len(shape) < 2:
        raise ValueError(f"no init rule for the vector leaf {path}")
    if name in ("embed", "unembed"):
        return "normal", 0.02
    return "normal", 1.0 / math.sqrt(shape[-2])


def master_fn(state_shapes):
    """A pure function key -> master weights shaped like
    ``state_shapes["master"]``; jit it, or call it inside a jitted
    function."""
    import jax
    import jax.numpy as jnp
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        state_shapes["master"])

    def gen(key):
        leaves = []
        for i, (path, sds) in enumerate(flat):
            kind, std = init_rule(path, sds.shape)
            if kind == "ones":
                leaf = jnp.ones(sds.shape, sds.dtype)
            elif kind == "zeros":
                leaf = jnp.zeros(sds.shape, sds.dtype)
            else:
                leaf = std * jax.random.normal(jax.random.fold_in(key, i),
                                               sds.shape, sds.dtype)
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return gen


def make_state(state_shapes, seed: int):
    """A train state shaped like ``state_shapes`` (a pytree of
    ShapeDtypeStruct with keys master, opt {m, v}, step), made in one
    jitted call."""
    import jax
    import jax.numpy as jnp
    if set(state_shapes) != {"master", "opt", "step"}:
        raise ValueError(f"unexpected train state keys {sorted(state_shapes)}")
    gen_master = master_fn(state_shapes)

    def gen(key):
        master = gen_master(key)
        return {"master": master,
                "opt": {"m": jax.tree.map(jnp.zeros_like, master),
                        "v": jax.tree.map(jnp.zeros_like, master)},
                "step": jnp.zeros(state_shapes["step"].shape,
                                  state_shapes["step"].dtype)}

    state = jax.jit(gen)(seed_key(seed))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), state)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), state_shapes)
    if got != want:
        raise ValueError("the made state does not match the program's "
                         "train state layout")
    return state
