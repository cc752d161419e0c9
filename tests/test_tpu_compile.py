"""The main-path kernels, and the smoke config's whole train step, compile
for a TPU v5e at real widths — with no chip attached.

Mosaic refuses what the Pallas interpreter accepts (blocks not aligned to
the (8, 128) tiling, unsigned reductions, more VMEM than a kernel may
use), so these compiles guard every change to the kernels at no chip
time. The topology is described inside a fixture, never at import: only
one process at a time may load the TPU compiler's library.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.producer import attn_flash_blocks
from repro.kernels.flash_attention import flash_attention_mosaic
from repro.kernels.gemm_rng import gemm_with_rng, gemm_with_rng_grouped
from repro.kernels.philox import philox_dropout_mask

V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not HLO
    return compiled


# (batch, q heads, kv heads, seq, head_dim, dtype): yi-6b's GQA attention
# in bf16, musicgen-large's MHA attention as the smoke step runs it (f32)
ATTN = {"yi-6b": (1, 32, 4, 2048, 128, jnp.bfloat16),
        "musicgen-large": (4, 32, 32, 1536, 64, jnp.float32)}


@pytest.mark.parametrize("mode", ["premask", "replay"])
@pytest.mark.parametrize("arch", sorted(ATTN))
def test_flash_fwd_bwd_compiles(one_chip, arch, mode):
    b, h, kv, s, d, dt = ATTN[arch]
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    q, k = sds((b, h, s, d), dt), sds((b, kv, s, d), dt)
    operand = (sds((b, h, s // 32, s), jnp.uint32) if mode == "premask"
               else sds((4,), jnp.uint32))

    bq, bk = attn_flash_blocks(s, s)      # the tiles the model runs

    def loss(q, k, v, m):
        o = flash_attention_mosaic(q, k, v, m, True, 0, 0.1, mode, 0, 0,
                                   7, bq, bk, False, 0)
        return jnp.sum(o.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k, operand)


# (m, k, n, mask (batch, heads, seq)): yi-6b's QKV-sized host GEMM and
# musicgen-large's out-projection hosting the next layer's mask
GEMMS = {"yi-6b": (2048, 4096, 6144, (1, 32, 2048)),
         "musicgen-large": (6144, 2048, 2048, (4, 32, 1536))}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("arch", sorted(GEMMS))
def test_gemm_with_rng_compiles(one_chip, arch, dtype):
    m, kdim, n, (mb, mh, ms) = GEMMS[arch]
    a = jax.ShapeDtypeStruct((m, kdim), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((kdim, n), dtype, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)

    def host(a, w, seed):
        y, mask = gemm_with_rng(a, w, mask_batch=mb, mask_heads=mh,
                                mask_sq=ms, mask_sk=ms, p=0.1, seed=seed,
                                interpret=False)
        assert mask is not None        # not the paper's Region 3
        return y, mask

    _compile(host, a, w, seed)


def test_grouped_host_compiles(one_chip):
    """8 experts of (512, 2048) x (2048, 1024) hosting a yi-6b mask."""
    a = jax.ShapeDtypeStruct((8, 512, 2048), jnp.bfloat16,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct((8, 2048, 1024), jnp.bfloat16,
                             sharding=one_chip)
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)

    def host(a, b, seed):
        y, mask = gemm_with_rng_grouped(
            a, b, mask_batch=1, mask_heads=32, mask_sq=2048, mask_sk=2048,
            p=0.1, seed=seed, interpret=False)
        assert mask is not None
        return y, mask

    _compile(host, a, b, seed)


@pytest.mark.parametrize("arch", sorted(GEMMS))
def test_standalone_philox_compiles(one_chip, arch):
    mb, mh, ms = GEMMS[arch][3]
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)
    _compile(lambda s: philox_dropout_mask(mb, mh, ms, ms, 0.1, s, 3,
                                           interpret=False), seed)


@pytest.mark.parametrize("phase", [1, 2])
def test_smoke_train_step_compiles_and_fits(one_chip, phase, monkeypatch):
    """chip_smoke.py's Pallas phases: musicgen-large at published widths,
    4 layers, batch 4, seq 1536 — the whole step fits one v5e."""
    import os
    from repro.kernels import backend
    from repro.train.loop import init_train_state, make_train_step
    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(__file__), ".."))
    import chip_smoke
    from repro.launch import train
    # the model asks the default backend (the CPU here) for the kernel
    # mode; steer it to Mosaic for this compile
    monkeypatch.setattr(backend, "default_interpret", lambda: False)
    run = train.build_run(train.parse_args(
        chip_smoke._train_argv(phase, False, 0, "unused")))
    cfg, shape = run.model, run.shape
    b, s = shape.global_batch, shape.seq_len
    state = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0),
                                                cfg)))
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.float32,
                             sharding=one_chip)
    y = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)
    mem = _compile(make_train_step(cfg, run), state, x, y).memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
