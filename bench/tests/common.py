"""A cell at a size the Pallas interpreter runs in seconds on a CPU: the
registry's reduced musicgen-large (MHA) and yi-6b (GQA 4:1), batch 2 (or
1) x 256, under a chip cell's mix (dropout plan, optimizer) and limits."""
import json
import os

from bench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELLS = {  # name: (configuration in data/, mix, batch, chip cell)
    "tiny-musicgen": ("tiny-musicgen", "b4s1536.drop10", 2,
                      "musicgen-large-5l.b4s1536.drop10"),
    "tiny-musicgen-nodrop": ("tiny-musicgen", "b4s1536.nodrop", 2,
                             "musicgen-large-5l.b4s1536.nodrop"),
    "tiny-yi": ("tiny-yi", "b1s4096.drop10", 1,
                "yi-6b-tp4-2l.b1s4096.drop10"),
}


def tiny_cell(name: str) -> dict:
    config_name, mix_name, batch, chip_cell = CELLS[name]
    with open(os.path.join(HERE, "data", f"{config_name}.json")) as f:
        config = json.load(f)
    mix = traffic.load(mix_name)
    mix.update(batch=batch, seq=256)
    with open(os.path.join(BENCH, "limits", f"{chip_cell}.json")) as f:
        limits = json.load(f)
    return {"name": name, "config": config, "mix": mix, "limits": limits,
            "chips": 1, "per_layer": []}
