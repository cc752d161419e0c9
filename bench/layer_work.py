"""Least time of single layers of the step, beside work.py's kernel
classes: the FFN's matmuls and the optimizer's pass over its state.

As in work.py, the counts are the algorithm's: nothing a realization
adds (recomputation, padding, the gradients' own traffic) is counted, so
a share of this least time cannot pass 100% unless the device time left
out part of the work.
"""
from __future__ import annotations

import functools
import json

# f32 master weights and Adam's two moments, each read and written once
OPTIMIZER_BYTES_PER_PARAM = 24


def ffn_matmul_params(model: dict) -> int:
    """Parameters of every layer's FFN matmuls (up and down, and the gate
    of a gated FFN)."""
    mats = 3 if model["ffn"] in ("swiglu", "geglu") else 2
    return model["n_layers"] * mats * model["d_model"] * model["d_ff"]


def ffn_roofline_s(model: dict, batch: int, seq: int, peak: dict) -> float:
    """Least time of one step's FFN matmuls at the bf16 peak: 6 FLOPs per
    parameter and token (forward 2, backward 4)."""
    flops = 6.0 * ffn_matmul_params(model) * batch * seq
    return flops / peak["bf16_flops_per_s"]


def optimizer_roofline_s(n_params: int, peak: dict) -> float:
    """Least time of one AdamW update over ``n_params`` parameters: its
    state's bytes over the HBM bandwidth."""
    return OPTIMIZER_BYTES_PER_PARAM * n_params / peak["hbm_bytes_per_s"]


@functools.lru_cache(maxsize=None)
def _cell_run(key: str):
    import tempfile

    from bench import run
    config, mix = json.loads(key)
    return run.build_run(config, mix, 0, tempfile.gettempdir())


def cell_run(config: dict, mix: dict):
    """The program's RunConfig for the cell, as run.py builds it (the
    seed and checkpoint directory do not enter the step)."""
    return _cell_run(json.dumps([config, mix], sort_keys=True))


def param_count(config: dict, mix: dict) -> int:
    """Parameters of the cell's train state, in the program's layout (the
    one run.py's weights are made in)."""
    import math

    import jax
    from repro.train.loop import init_train_state
    program = cell_run(config, mix)
    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), program.model))
    return sum(math.prod(a.shape)
               for a in jax.tree_util.tree_leaves(shapes["master"]))
