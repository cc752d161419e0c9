"""Multi-device dry-run smoke: the production lowering path on a small
host-device mesh, in a subprocess (XLA device count is locked at first
jax init, so the main test process must stay single-device)."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

sys.path.insert(0, "src")
import repro.launch.dryrun as dr
from repro.launch import mesh as mesh_mod
from repro.launch.mesh import auto_mesh

# monkeypatch the production mesh down to host scale
def small_mesh(*, multi_pod=False):
    if multi_pod:
        return auto_mesh((2, 2, 2), ("pod", "data", "model"))
    return auto_mesh((2, 4), ("data", "model"))

dr.make_production_mesh = small_mesh

# reduced shapes so CPU compiles in seconds
from repro.config.registry import ALL_SHAPES
from repro.config.base import ShapeConfig, StepKind
ALL_SHAPES["train_4k"] = ShapeConfig("train_4k", 256, 8, StepKind.TRAIN)
ALL_SHAPES["decode_32k"] = ShapeConfig("decode_32k", 512, 8,
                                       StepKind.DECODE)
ALL_SHAPES["prefill_32k"] = ShapeConfig("prefill_32k", 256, 4,
                                        StepKind.PREFILL)

# reduced model configs
import repro.config.registry as reg
_orig = reg.get_arch
reg.get_arch = lambda a, reduced=False: _orig(a, reduced=True)
dr.get_arch = reg.get_arch

failures = []
for arch, shape in [("yi-6b", "train_4k"), ("yi-6b", "decode_32k"),
                    ("moonshot-v1-16b-a3b", "train_4k"),
                    ("recurrentgemma-9b", "prefill_32k"),
                    ("rwkv6-7b", "decode_32k")]:
    for mp in (False, True):
        try:
            r = dr.run_cell(arch, shape, mp, out_dir=None, verbose=False)
            assert r["roofline"]["flops_per_device"] > 0
        except Exception as e:
            failures.append((arch, shape, mp, repr(e)))
if failures:
    for f in failures:
        print("FAIL", f)
    sys.exit(1)
print("ALL-CELLS-OK")
"""


@pytest.mark.slow
def test_small_mesh_dryrun():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, cwd=os.path.join(
            os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=1200)
    assert "ALL-CELLS-OK" in proc.stdout, (
        proc.stdout[-3000:], proc.stderr[-3000:])


@pytest.mark.slow
def test_elastic_remesh_restore(tmp_path):
    """Save a sharded train state on a 4-device mesh, restore it onto a
    2-device mesh (elastic scaling path)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import Checkpointer
from repro.config import get_arch
from repro.train.loop import init_train_state
from repro.launch.mesh import auto_mesh

cfg = get_arch("yi-6b", reduced=True)
state = init_train_state(jax.random.PRNGKey(0), cfg)

mesh4 = auto_mesh((4,), ("data",))
sh4 = NamedSharding(mesh4, P())
state = jax.tree.map(lambda a: jax.device_put(a, sh4), state)
ck = Checkpointer(r"%s", async_save=False)
ck.save(3, state)

# restore onto a DIFFERENT mesh (2 of the 4 devices)
mesh2 = auto_mesh((2,), ("data",), devices=jax.devices()[:2])
sh2 = NamedSharding(mesh2, P())
shardings = jax.tree.map(lambda a: sh2, state)
restored = ck.restore(3, state, shardings=shardings)
import numpy as np
jax.tree.map(lambda a, b: np.testing.assert_array_equal(
    np.asarray(a), np.asarray(b)), state, restored)
leaf = jax.tree_util.tree_leaves(restored)[0]
assert len(leaf.sharding.device_set) == 2
print("REMESH-OK")
""" % str(tmp_path)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, cwd=os.path.join(
            os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=600)
    assert "REMESH-OK" in proc.stdout, (proc.stdout[-2000:],
                                        proc.stderr[-2000:])
