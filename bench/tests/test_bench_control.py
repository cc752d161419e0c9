"""The control (the program's own bfloat16 compute path) and the
half-batch fault are judged not correct, and the program is judged
correct, when read against the reference as ``calibrate.py`` reads them
on the chip; the decay share reads the weight decay of each side."""
from bench import calibrate, check
from bench.tests.common import tiny_cell


def test_control_and_fault_readings():
    control_and_fault_readings("tiny-yi")


def test_control_and_fault_readings_nodrop():
    control_and_fault_readings("tiny-musicgen-nodrop")


def control_and_fault_readings(cell_name):
    cell = tiny_cell(cell_name)
    (row,) = calibrate.readings(cell, [2 ** 32 + 5], decay=0.1)
    for name in ("program", "control", "half_batch"):
        assert set(row[name]) == set(check.NUMBERS)
    checks, ok = check.judge(row["program"], cell["limits"])
    assert ok, checks
    checks, ok = check.judge(row["control"], cell["limits"])
    assert not ok, checks
    checks, ok = check.judge(row["half_batch"], cell["limits"])
    assert not ok, checks
    # the decay share of a weight matrix moves by the decay between a side
    # that decays it and one that does not, and by round-off otherwise
    for leaf, (prog, ref) in row["decay_share"].items():
        gap = ref - prog
        assert abs(gap) < 0.01 or abs(gap - 0.1) < 0.01, (leaf, prog, ref)
