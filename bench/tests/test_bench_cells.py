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


WIDTHS = ("d_model", "d_ff", "head_dim", "d_ff_expert", "top_k",
          "local_window", "dense_residual_ff", "rwkv_head_dim")


def names_a_width(key: str) -> bool:
    """A cut that changes a width; for a dotted cut (``moe.d_ff_expert``)
    its last part is the key."""
    last = key.split(".")[-1]
    return last.endswith(("_dim", "_rank")) or last in WIDTHS


@pytest.mark.parametrize("key, width", [
    ("n_layers", False), ("moe.n_experts", False), ("vocab_size", False),
    ("d_model", True), ("moe.d_ff_expert", True), ("moe.top_k", True),
    ("kv_lora_rank", True), ("local_window", True)])
def test_width_rule(key, width):
    assert names_a_width(key) is width


def test_configs_state_their_cuts():
    for conf in SPEC["configs"]:
        with open(os.path.join(ROOT, conf["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == conf["reduced"]
        for key in conf["reduced"]:
            assert not names_a_width(key), key
