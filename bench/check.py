"""The comparison that decides ``correct``.

The program's first steps and the reference's (reference.py) start from
the same weights and batches. Each side hands over, on the host, each
checked step's loss, the first gradient as the optimizer got it (for the
program: Adam's first moment after one step, over 1 - b1) and each
leaf's change over the checked steps. Five numbers compare them:

- ``loss_gap``: the largest relative gap of a checked step's loss.
- ``grad_norm_gap``: leaf by leaf, the gap between the program's norm of
  the first gradient and the reference's, over the larger of the
  reference leaf's norm and the median leaf's norm; the worst leaf.
- ``update_norm_gap``: the same for each leaf's change.
- ``grad_diff``: leaf by leaf, the norm of the elementwise difference of
  the first gradients over the same floor; the median leaf. It sees what
  a norm cannot: rounding in storage or compute, which a norm or a mean
  averages away, and a wrong element or direction. The median, not the
  worst leaf, since a leaf or two (the query and key projections, under
  flash attention's own order of rounding) read several times the rest.
- ``change_diff``: the same for each leaf's change: the update rule
  applied element by element.

A leaf whose reference gradient is under a thousandth of the median
leaf's is left out of every leaf number: Adam moves such a leaf by
round-off alone.

A cell's limits (``bench/limits/<cell>.json``) give each number a limit,
or ``null`` for a number that has no upper reading and is not compared.

The reference is the module that the configuration file names under
``"reference"``, a path under ``bench/`` (default ``reference.py``).
"""
from __future__ import annotations

import importlib
import statistics
from typing import Dict, List, Tuple

import numpy as np

NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff",
           "change_diff")
QUIET_LEAF = 1e-3

Leaves = Dict[str, np.ndarray]


def host_leaves(tree) -> Leaves:
    """{leaf path: float32 numpy copy} of a pytree of device arrays."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    got = jax.device_get([leaf for _, leaf in flat])
    return {jax.tree_util.keystr(p): np.asarray(a, np.float32)
            for (p, _), a in zip(flat, got)}


def change(final: Leaves, start: Leaves) -> Leaves:
    return {k: final[k] - start[k] for k in final}


def _norm(a: np.ndarray) -> float:
    return float(np.linalg.norm(a.ravel()))


def per_leaf(got: Leaves, want: Leaves) -> Dict[str, Dict[str, float]]:
    """{leaf: {"got", "want", "diff"}}: the two norms and the norm of the
    elementwise difference."""
    return {k: {"got": _norm(got[k]), "want": _norm(want[k]),
                "diff": _norm(got[k] - want[k])} for k in want}


def _gaps(leaves: Dict[str, Dict[str, float]], counted: List[str],
          of) -> Dict[str, float]:
    floor = statistics.median(v["want"] for v in leaves.values())
    return {k: of(leaves[k]) / max(leaves[k]["want"], floor)
            for k in counted}


def _worst(gaps: Dict[str, float]) -> Tuple[float, str]:
    where = max(sorted(gaps), key=gaps.get)
    return gaps[where], where


def _median(gaps: Dict[str, float]) -> Tuple[float, str]:
    value = statistics.median(gaps.values())
    where = min(sorted(gaps), key=lambda k: abs(gaps[k] - value))
    return value, where


def compare(prog: dict, ref: dict) -> Tuple[Dict[str, float], dict]:
    """``prog``/``ref``: {"losses": [...], "grad": Leaves, "change":
    Leaves}. Returns the five numbers and where they come from: the leaf
    each leaf number was read from, the leaves left out as quiet, and
    every leaf's norms."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("program and reference leaves differ")
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    grad = per_leaf(prog["grad"], ref["grad"])
    upd = per_leaf(prog["change"], ref["change"])
    median_grad = statistics.median(v["want"] for v in grad.values())
    counted = sorted(k for k, v in grad.items()
                     if v["want"] >= QUIET_LEAF * median_grad)
    norm_gap = lambda v: abs(v["got"] - v["want"])  # noqa: E731
    diff = lambda v: v["diff"]                      # noqa: E731
    numbers, where = {"loss_gap": loss_gap}, {}
    for name, leaves, of, pick in (
            ("grad_norm_gap", grad, norm_gap, _worst),
            ("update_norm_gap", upd, norm_gap, _worst),
            ("grad_diff", grad, diff, _median),
            ("change_diff", upd, diff, _median)):
        numbers[name], where[name] = pick(_gaps(leaves, counted, of))
    where["quiet_leaves"] = sorted(set(grad) - set(counted))
    where["grad"], where["change"] = grad, upd
    return numbers, where


def reference_module(cfg: dict):
    """The plain reference that the configuration file names, a module
    under ``bench/`` (``reference.py`` is ``bench.reference``)."""
    rel = cfg.get("reference", "reference.py")
    parts = rel[:-3].split("/")
    if not rel.endswith(".py") or not all(p.isidentifier() for p in parts):
        raise ValueError(f"a reference is a module's .py file under bench/, "
                         f"not {rel!r}")
    return importlib.import_module(".".join(["bench"] + parts))


def reference_readings(cfg: dict, mix: dict, ring, master_fn, key) -> dict:
    """The reference's readings over the mix's checked steps, from the
    weights ``master_fn(key)`` and the batches ``ring``."""
    import jax
    import jax.numpy as jnp
    reference = reference_module(cfg)
    n = mix["checked_steps"]
    step = jax.jit(reference.make_step(cfg, mix), donate_argnums=(0, 1, 2))
    master = jax.jit(master_fn)(key)
    start = host_leaves(master)
    m = jax.tree.map(jnp.zeros_like, master)
    v = jax.tree.map(jnp.zeros_like, master)
    losses, grad = [], None
    for t in range(n):
        x, y = ring[t % len(ring)]
        master, m, v, loss, g = step(master, m, v, x, y, jnp.int32(t))
        losses.append(float(loss))
        if t == 0:
            grad = host_leaves(g)
        del g
    del m, v
    return {"losses": losses, "grad": grad,
            "change": change(host_leaves(master), start)}


def judge(numbers: Dict[str, float], limits: dict
          ) -> Tuple[Dict[str, dict], bool]:
    """The compared numbers beside their limits, and whether all hold."""
    checks = {k: {"value": numbers[k], "limit": limits[k]["limit"]}
              for k in NUMBERS if limits[k]["limit"] is not None}
    if not checks:
        raise ValueError("a cell must compare at least one number")
    return checks, all(c["value"] <= c["limit"] for c in checks.values())
