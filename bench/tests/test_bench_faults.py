"""A run with the timed path broken underneath comes out not correct.

The harness's look for a chip is skipped; the rest of a run (set-up,
checked steps, a short window, the reference, the comparison) runs on
the CPU at a tiny size, with the train step wrapped by each fault."""
import pytest

from bench import faults, run
from bench.tests.common import tiny_cell


@pytest.mark.parametrize("cell,fault", [
    ("tiny-musicgen", None),
    ("tiny-musicgen", "unchanged"),
    ("tiny-musicgen", "half_batch"),
    ("tiny-musicgen-nodrop", None),
    ("tiny-musicgen-nodrop", "unchanged"),
    ("tiny-musicgen-nodrop", "half_batch"),
    ("tiny-yi", None),
    ("tiny-yi", "unchanged"),
    ("tiny-yi", "half_batch"),
])
def test_fault_is_caught(cell, fault):
    wrap = faults.FAULTS[fault] if fault else None
    result = run.run_cell(tiny_cell(cell), 2 ** 31 + 11, 0.5, False,
                          wrap_step=wrap)
    assert result["correct"] is (fault is None), result["checks"]
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == set(run.END_TO_END)
    assert list(result)[-1] == "checks"
