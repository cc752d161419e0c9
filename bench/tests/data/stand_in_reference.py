"""A stand-in reference, for the harness's tests only: it covers every
configuration, and its loss is no model's. It shows that a configuration
file and a reference module, added as files, reach ``run.build_run`` and
``check.reference_readings``; it checks nothing of the program."""
from bench import reference


def covers(config):
    return None


def loss(master, x, y, step, cfg, mix):
    import jax
    import jax.numpy as jnp
    weights = sum(jnp.mean(jnp.square(a)) for a in jax.tree.leaves(master))
    return weights * (1.0 + jnp.mean(y.astype(jnp.float32)) / cfg["vocab_size"])


def make_step(cfg, mix):
    return reference.make_step(cfg, mix, loss_fn=loss)
