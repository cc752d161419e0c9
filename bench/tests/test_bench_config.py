"""A configuration enters the harness as files: the configuration file
(checked key by key against the program's model, with nested and dotted
cuts), the reference it names, and the scopes it declares."""
import json
import os

import jax
import numpy as np
import pytest

from bench import check, reference, run, traffic, weights

HERE = os.path.dirname(os.path.abspath(__file__))
DENSE_WORDS = "the reference covers dense full-attention blocks"


def tiny_moe() -> dict:
    with open(os.path.join(HERE, "data", "tiny-moe.json")) as f:
        return json.load(f)


def small_mix() -> dict:
    mix = traffic.load("b4s1536.nodrop")
    mix.update(batch=2, seq=256)
    return mix


@pytest.mark.parametrize("config, why", [
    ({"qkv_bias": False, "qk_norm": False, "tie_embeddings": False}, None),
    ({"block_pattern": ["full"], "moe": None, "qkv_bias": False,
      "qk_norm": False, "tie_embeddings": False}, None),
    ({"moe": {"n_experts": 8}}, DENSE_WORDS),
    ({"block_pattern": ["local", "local", "local", "full"]}, DENSE_WORDS),
    ({"qkv_bias": True, "qk_norm": False, "tie_embeddings": False},
     "the reference has no qkv bias, qk-norm or tied embeddings"),
])
def test_dense_reference_covers(config, why):
    assert reference.covers(config) == why


def test_moe_refused_by_the_dense_reference(tmp_path):
    config = tiny_moe()
    del config["reference"]
    with pytest.raises(ValueError, match=DENSE_WORDS):
        run.build_run(config, small_mix(), 0, str(tmp_path))


def as_run(arch: str, n_layers: int) -> dict:
    """A configuration file that states every field of the program's
    ``arch`` at ``n_layers`` as the program runs it."""
    from repro.config.registry import get_arch
    model = get_arch(arch)
    config = {k: v for k, v in run.plain(model).items()
              if k not in run.UNREAD}
    config.update(arch=arch, n_layers=n_layers, remat="none",
                  compute_dtype="float32", matmul_precision="default",
                  attention_precision="default", reduced=["n_layers"])
    return config


def test_windowed_refused_by_the_dense_reference(tmp_path):
    # recurrent, recurrent, local: the file states what the program runs
    config = as_run("recurrentgemma-9b", 3)
    assert set(config["block_pattern"]) == {"recurrent", "local"}
    with pytest.raises(ValueError, match=DENSE_WORDS):
        run.build_run(config, small_mix(), 0, str(tmp_path))


def test_file_leaving_out_a_field_refused(tmp_path):
    """A field that the program runs off its default, or one the counts
    always read, has to be stated: left out, the counts would take a
    windowed or expert layer for a dense full one."""
    cases = []
    windowed = as_run("recurrentgemma-9b", 3)
    for key in ("block_pattern", "local_window"):
        cases.append((dict(windowed), key))
    for key in ("moe", "d_ff", "rope_theta"):
        cases.append((tiny_moe(), key))
    for config, key in cases:
        del config[key]
        with pytest.raises(ValueError, match=f"leaves out.*'{key}'"):
            run.build_run(config, small_mix(), 0, str(tmp_path))
    config = tiny_moe()
    del config["moe"]["first_dense_layers"]
    with pytest.raises(ValueError, match="'moe.first_dense_layers'"):
        run.build_run(config, small_mix(), 0, str(tmp_path))


def test_dotted_cut_applied(tmp_path):
    program = run.build_run(tiny_moe(), small_mix(), 0, str(tmp_path))
    moe = program.model.moe
    # the held experts, top-k and expert width are the file's; the rest of
    # the group is the published model's
    assert (moe.n_experts, moe.top_k, moe.d_ff_expert) == (4, 2, 32)
    assert (moe.n_shared_experts, moe.first_dense_layers) == (2, 1)


@pytest.mark.parametrize("key, value, error", [
    ("d_modle", 64, "name no field"),
    ("moe", dict(tiny_moe()["moe"], n_expert=4), "name no field"),
    ("n_shared_experts", 1, "name no field"),
    ("moe", dict(tiny_moe()["moe"], n_shared_experts=1),
     "moe.n_shared_experts"),
    ("block_pattern", ["local", "full"], "block_pattern"),
    ("rope_theta", 1.0, "rope_theta"),
])
def test_stated_keys_checked(tmp_path, key, value, error):
    config = dict(tiny_moe(), **{key: value})
    with pytest.raises(ValueError, match=error):
        run.build_run(config, small_mix(), 0, str(tmp_path))


def test_files_alone_reach_the_harness(tmp_path, monkeypatch):
    """load_cell, build_run and reference_readings take a configuration
    with experts and its own reference from their files alone."""
    config = tiny_moe()
    cell_name = "tiny-moe.b4s1536.nodrop"
    spec = {"configs": [{"name": "tiny-moe",
                         "file": "bench/tests/data/tiny-moe.json",
                         "reduced": config["reduced"]}],
            "workloads": [{"name": cell_name, "config": "tiny-moe",
                           "traffic": "b4s1536.nodrop", "chips": 1}],
            "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with open(os.path.join(run.LIMITS,
                           "musicgen-large-5l.b4s1536.nodrop.json")) as f:
        (tmp_path / f"{cell_name}.json").write_text(f.read())
    monkeypatch.setattr(run, "SPEC", str(tmp_path / "BENCHMARK.json"))
    monkeypatch.setattr(run, "LIMITS", str(tmp_path))
    cell = run.load_cell(cell_name)
    assert cell["config"] == config
    mix = dict(cell["mix"], batch=2, seq=256)
    program = run.build_run(cell["config"], mix, 0, str(tmp_path / "ckpt"))

    from repro.train.loop import init_train_state
    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), program.model))
    seed = 2 ** 31 + 21
    ring = traffic.make_ring(cell["config"], mix, seed)
    got = check.reference_readings(cell["config"], mix, ring,
                                   weights.master_fn(shapes),
                                   traffic.seed_key(seed))
    assert len(got["losses"]) == mix["checked_steps"]
    assert all(np.isfinite(got["losses"]))
    leaves = {jax.tree_util.keystr(p) for p, _ in
              jax.tree_util.tree_flatten_with_path(shapes["master"])[0]}
    assert set(got["grad"]) == set(got["change"]) == leaves
    assert any("router" in k for k in leaves)
