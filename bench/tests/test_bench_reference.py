"""The reference's keep bits are the program's, and a seed wider than 32
bits keeps its high bits."""
import jax
import numpy as np

from bench import reference, traffic


def test_keep_bits_match_the_program_oracle():
    from repro.core.overlap import DropoutPlan
    from repro.config import DropoutPlanConfig
    from repro.kernels.ref import keep_mask_ref
    plan = DropoutPlan(DropoutPlanConfig(mode="overlap", p=0.1))
    step, layer = 5, 3
    want = keep_mask_ref(2, 4, 64, 64, 0.1, int(plan.step_seed(step)),
                         int(plan.salt(layer)))
    for b in range(2):
        got = reference.keep_bits(b, 4, 64, 0.1, 7, step, 0, layer)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want[b]))


def test_wide_seeds_differ():
    a = jax.random.key_data(traffic.seed_key(5))
    b = jax.random.key_data(traffic.seed_key(2 ** 33 + 5))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
