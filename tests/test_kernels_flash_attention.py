"""Flash-attention Pallas kernel vs the pure-jnp oracle: shape/dtype
sweeps, GQA, causal/local masking, all three dropout modes, gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention, \
    flash_attention_fwd
from repro.kernels.philox import philox_dropout_mask


def _qkv(key, b, h, kv, sq, sk, d, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, sk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("dims", [
    (1, 1, 1, 128, 128, 32),
    (2, 4, 2, 256, 256, 64),   # GQA 2:1
    (1, 8, 1, 128, 256, 64),   # MQA, decode-style sk > sq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matches_ref_no_dropout(rng_key, dims, dtype):
    b, h, kv, sq, sk, d = dims
    q, k, v = _qkv(rng_key, b, h, kv, sq, sk, d, dtype)
    out = flash_attention_fwd(q, k, v, causal=True, block_q=128,
                              block_k=128)
    want = ref.attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_non_causal(rng_key):
    q, k, v = _qkv(rng_key, 1, 2, 2, 128, 128, 32, jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=False)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_local_window(rng_key):
    q, k, v = _qkv(rng_key, 1, 2, 1, 256, 256, 32, jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=True, local_window=64)
    want = ref.attention_ref(q, k, v, causal=True, local_window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rounds", [3, 7])
def test_fused_dropout_matches_ref(rng_key, rounds):
    q, k, v = _qkv(rng_key, 2, 2, 2, 128, 128, 32, jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=True, dropout_p=0.2,
                              mode="fused", seed=5, salt=3, rounds=rounds)
    want = ref.attention_ref(q, k, v, causal=True, dropout_p=0.2,
                             dropout_seed=5, dropout_salt=3,
                             philox_rounds=rounds)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_premask_bit_identical_to_fused(rng_key):
    """The paper's requirement: relocating RNG must not change results."""
    b, h, s, d = 2, 4, 256, 64
    q, k, v = _qkv(rng_key, b, h, h, s, s, d, jnp.float32)
    fused = flash_attention_fwd(q, k, v, causal=True, dropout_p=0.15,
                                mode="fused", seed=3, salt=9)
    mask = philox_dropout_mask(b, h, s, s, 0.15, 3, salt=9)
    pre = flash_attention_fwd(q, k, v, mask_packed=mask, causal=True,
                              dropout_p=0.15, mode="premask", seed=3,
                              salt=9)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(pre))


def test_block_shape_invariance(rng_key):
    q, k, v = _qkv(rng_key, 1, 2, 2, 256, 256, 32, jnp.float32)
    a = flash_attention_fwd(q, k, v, causal=True, block_q=128, block_k=128)
    b = flash_attention_fwd(q, k, v, causal=True, block_q=256, block_k=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


def test_gradients_match_ref(rng_key):
    q, k, v = _qkv(rng_key, 1, 2, 2, 128, 128, 32, jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, 0, 0.1,
                                       "fused", 7, 1, 7, 128, 128, True))

    def f_ref(q, k, v):
        return jnp.sum(ref.attention_ref(q, k, v, causal=True,
                                         dropout_p=0.1, dropout_seed=7,
                                         dropout_salt=1))

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ------------------------------------------- tiles chosen by the shape

def test_flash_blocks_rule():
    """The tile rule: the largest of 512 / 256 / 128 that divides the
    length, leaves two blocks along it, and fits a sliding window."""
    from repro.core.producer import attn_flash_blocks
    assert attn_flash_blocks(256, 256) == (128, 128)
    assert attn_flash_blocks(1024, 1024) == (512, 512)
    assert attn_flash_blocks(1536, 1536) == (512, 512)
    assert attn_flash_blocks(4096, 4096) == (512, 512)
    assert attn_flash_blocks(512, 512) == (256, 256)
    assert attn_flash_blocks(640, 640) == (128, 128)     # 640 % 256
    assert attn_flash_blocks(1280, 1280) == (256, 256)   # 1280 % 512
    assert attn_flash_blocks(256, 1024) == (128, 512)    # prefill, sq < sk
    assert attn_flash_blocks(1536, 1536, 200) == (128, 128)
    assert attn_flash_blocks(1536, 1536, 300) == (256, 256)


def _grads(q, k, v, operand, mode, bq, bk, window):
    from repro.kernels.flash_attention import flash_attention_mosaic

    def f(q, k, v):
        o = flash_attention_mosaic(q, k, v, operand, True, window, 0.1,
                                   mode, 3, 5, 7, bq, bk, None, 0)
        return jnp.sum(o * jnp.cos(o)), o

    (_, o), g = jax.value_and_grad(f, argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    return (o,) + g


@pytest.mark.parametrize("mode", ["none", "premask", "replay"])
@pytest.mark.parametrize("kv, d, window", [(2, 64, 0), (1, 128, 0),
                                           (2, 64, 300)])
def test_rule_tiles_match_128_tiles(rng_key, mode, kv, d, window):
    """At S=1024 the rule's tiles (512 x 512, 256 x 256 under a 300-key
    window) give the 128 x 128 kernels' output and gradients within
    float tolerance in every mode (GQA in the D=128 case); replay's
    bits equal premask's at the rule's tiles, bit for bit."""
    from repro.core.producer import attn_flash_blocks
    from repro.kernels.philox_common import seed_salt_smem
    s = 1024
    bq, bk = attn_flash_blocks(s, s, window)
    assert bq > 128 and bk > 128
    q, k, v = _qkv(rng_key, 1, 2, kv, s, s, d, jnp.float32)
    operand = {"none": None, "premask": philox_dropout_mask(
        1, 2, s, s, 0.1, 3, salt=5), "replay": seed_salt_smem(3, 5)}
    got = _grads(q, k, v, operand[mode], mode, bq, bk, window)
    want = _grads(q, k, v, operand[mode], mode, 128, 128, window)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    if mode == "replay":
        pre = _grads(q, k, v, operand["premask"], "premask", bq, bk,
                     window)
        for a, b in zip(got, pre):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_replay_plane_equals_premask_plane_at_1024():
    from repro.kernels.flash_attention import replay_keep_plane
    from repro.kernels.philox_common import seed_salt_smem, unpack_bits_q32
    s = 1024
    plane = philox_dropout_mask(1, 2, s, s, 0.1, 3, salt=5)
    keep = jax.vmap(jax.vmap(lambda m: unpack_bits_q32(m, s)))(plane)
    np.testing.assert_array_equal(
        np.asarray(replay_keep_plane(seed_salt_smem(3, 5), 1, 2, s, s,
                                     0.1)),
        np.asarray(keep))
