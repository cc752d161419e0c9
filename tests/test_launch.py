"""The training entry point and chip_smoke.py, driven on the CPU.

chip_smoke.py runs as a subprocess here: it must refuse a backend without
a TPU, and its --rehearse mode drives the same phases as on the chip at
the reduced size (Pallas kernels interpreted).
"""
import json
import math
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache
from repro.launch import train

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _smoke(args, cwd=ROOT, env_extra=None, script=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)        # the script finds src/ itself
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, script or os.path.join(ROOT, "chip_smoke.py"),
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=600)


def test_chip_smoke_refuses_cpu():
    proc = _smoke([])
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """Copied away from the repository it cannot run, and says nothing."""
    script = shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _smoke(["--rehearse"], cwd=tmp_path, script=script)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal(tmp_path, chips):
    extra = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    if chips == 4:
        extra["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = _smoke(["--rehearse", "--chips", str(chips)], env_extra=extra)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": chips}}


def test_train_main_reports_and_resumes(tmp_path, monkeypatch):
    # with the variable set, main() leaves JAX's cache config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    argv = ["--arch", "musicgen-large", "--reduced", "--layers", "2",
            "--batch", "2", "--seq", "128", "--ckpt-every", "2",
            "--ckpt-dir", str(tmp_path / "ckpt"), "--log-every", "1",
            "--site", "prev_gemm", "--attn-impl", "pallas",
            "--gemm-dtype", "bf16", "--attn-replay", "off"]
    first = train.main(argv + ["--steps", "2"])
    assert (first.restarts, first.failed_saves) == (0, 0)
    assert sorted(first.losses) == [0, 1]
    assert all(math.isfinite(v) for v in first.losses.values())
    resumed = train.main(argv + ["--steps", "3"])
    assert sorted(resumed.losses) == [2]     # resumed at the checkpoint
    assert resumed.restarts == 0


def test_enable_compile_cache_prefers_the_environment(tmp_path,
                                                      monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
