"""Every cell of BENCHMARK.json resolves its files by name."""
import json
import os
import re

import pytest

from bench import run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(workload):
    cell = run.load_cell(workload)
    assert cell["config"]["n_layers"] >= 2        # the carried mask crosses a layer
    assert cell["mix"]["ring"] >= cell["mix"]["checked_steps"]
    assert cell["per_layer"], "every cell reports a per-layer metric"
    for name in cell["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           f"{name}.py"))
    compared = [k for k, v in cell["limits"].items()
                if v["limit"] is not None]
    assert compared
    for k in compared:
        lim = cell["limits"][k]
        assert lim["lower"] < lim["limit"] < lim["upper"]


def test_names_and_references():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    configs = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in configs
        traffic.load(w["traffic"])
    assert {m["name"] for m in SPEC["end_to_end"]} == set(run.END_TO_END)
    moves = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in moves for m in SPEC["per_layer"])


def test_configs_state_their_cuts():
    for conf in SPEC["configs"]:
        with open(os.path.join(ROOT, conf["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == conf["reduced"]
        for key in conf["reduced"]:
            assert not key.endswith(("_dim", "_rank"))
            assert key not in ("d_model", "d_ff", "head_dim")
