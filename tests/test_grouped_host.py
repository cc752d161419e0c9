"""Grid-decoupled RNG hosts: MoE expert and RWKV channel-mix GEMMs.

The grouped GEMM+RNG kernel walks mask tiles round-robin across expert
tiles; emission indexes the (b, h, q, k) Philox counter space, never
token identity — so the permuted / capacity-dropped token layout of the
dispatch is irrelevant to the bits. This file holds the acceptance
surface: producer-level bit-identity vs the reference oracle across all
gemm_dtype values, zero standalone/XLA fallbacks planned on a
(dense, moe, moe) stack and an RWKV hybrid with hostable shapes,
end-to-end logits identical to the XLA site, mask invariance under
router perturbation and capacity overflow, the moe_seq_dispatch
build-time validation, and the 2-device EP shard_map acceptance run.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import (
    AttentionKind,
    DropoutPlanConfig,
    FFNKind,
    ModelConfig,
    MoEConfig,
)
from repro.core import producer, schedule as schedule_mod
from repro.core.overlap import plan_from_config
from repro.core.schedule import compile_schedule
from repro.kernels.ref import philox_mask_ref
from repro.models import moe as moe_mod
from repro.models.transformer import Runtime, forward, model_init

_P = 0.25
_SEED = 5

_GROUPED_HOWS = (producer.HOW_GEMM, producer.HOW_GEMM_GROUPED)


def _plan_cfg(site, **kw):
    return DropoutPlanConfig(mode="overlap", p=_P, seed=_SEED, site=site,
                             **kw)


def _moe_cfg(**kw):
    """(dense, moe, moe) stack: DeepSeek-style first dense layer, then
    two MoE blocks — the layer mix the grouped host exists for."""
    base = dict(name="dmm", family="moe", n_layers=3, d_model=64,
                n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64,
                head_dim=32, block_pattern=(AttentionKind.FULL,),
                attn_dropout=_P,
                moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=128,
                              first_dense_layers=1, capacity_factor=2.0))
    base.update(kw)
    return ModelConfig(**base)


def _rwkv_hybrid_cfg(**kw):
    """(WKV, FULL) hybrid with RWKV channel-mix FFNs — the attention
    blocks' channel-mix GEMMs host through the grouped kernel (E=1)."""
    base = dict(name="rwkv-hyb", family="hybrid", n_layers=4, d_model=64,
                n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64,
                head_dim=32, rwkv_head_dim=32,
                block_pattern=(AttentionKind.WKV, AttentionKind.FULL),
                ffn=FFNKind.RWKV_CHANNEL, attn_dropout=_P)
    base.update(kw)
    return ModelConfig(**base)


# ------------------------------------------------------------- producer

@pytest.mark.parametrize("gemm_dtype", ["f32", "bf16", "fp8"])
def test_grouped_producer_bits_match_oracle(rng_key, gemm_dtype):
    """The grouped host's mask is bit-identical to the reference oracle
    whatever dtype hosts the GEMM — the bits never depend on the host."""
    from repro.kernels import quant
    if gemm_dtype == "fp8" and not quant.have_fp8():
        pytest.skip("no float8_e4m3fn in this JAX build")
    plan = plan_from_config(_plan_cfg("ffn_up", gemm_dtype=gemm_dtype))
    e, c, d, f = 4, 256, 64, 128
    b, h, s = 2, 2, 128
    layer, step = 2, 7
    a3 = jax.random.normal(rng_key, (e, c, d), jnp.float32)
    b3 = jax.random.normal(rng_key, (e, d, f), jnp.float32)
    y, mask, how = producer.grouped_gemm_with_mask(
        a3, b3, plan, (b, h, s, s), layer, step)
    assert how == producer.HOW_GEMM_GROUPED
    want = philox_mask_ref(b, h, s, s, _P, int(plan.step_seed(step)),
                           int(plan.salt(layer)))
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(want))
    y_ref = jnp.einsum("ecd,edf->ecf", a3, b3)
    if gemm_dtype == "f32":
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    else:
        # non-f32 hosts move the GEMM precision, never the bits: the
        # Frobenius-relative error stays inside the documented bound
        from repro.kernels import quant
        rel = (np.linalg.norm(np.asarray(y - y_ref))
               / np.linalg.norm(np.asarray(y_ref)))
        assert rel < quant.quantize_error_bound(), rel


def test_grouped_region3_falls_back_to_standalone(rng_key):
    """A combined expert grid too small to hide the mask (Region 3)
    must hand the bits to the standalone kernel — same bits, realized
    ``how`` reported truthfully."""
    plan = plan_from_config(_plan_cfg("ffn_up"))
    # 2 experts x (128, 64)x(64, 8): 2 grid steps vs a 1x32x1024x1024
    # mask -> rb exceeds the row budget
    e, c, d, f = 2, 128, 64, 8
    b, h, s = 1, 32, 1024
    a3 = jax.random.normal(rng_key, (e, c, d), jnp.float32)
    b3 = jax.random.normal(rng_key, (e, d, f), jnp.float32)
    y, mask, how = producer.grouped_gemm_with_mask(
        a3, b3, plan, (b, h, s, s), 1, 0)
    assert how == producer.HOW_STANDALONE
    want = philox_mask_ref(b, h, s, s, _P, int(plan.step_seed(0)),
                           int(plan.salt(1)))
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.einsum("ecd,edf->ecf", a3, b3)),
        rtol=3e-5, atol=3e-5)


def test_grouped_grads_flow(rng_key):
    """Gradients flow through the grouped fused kernel (custom_vjp
    per-expert dgrad pair; the mask carries a float0 cotangent)."""
    plan = plan_from_config(_plan_cfg("ffn_up"))
    a3 = jax.random.normal(rng_key, (4, 256, 64), jnp.float32)
    b3 = jax.random.normal(rng_key, (4, 64, 128), jnp.float32)

    def loss(a, b):
        y, _mask, _how = producer.grouped_gemm_with_mask(
            a, b, plan, (2, 2, 128, 128), 1, 0,
            how=producer.HOW_GEMM_GROUPED)
        return jnp.sum(y ** 2)

    da, db = jax.grad(loss, argnums=(0, 1))(a3, b3)
    ref = jax.grad(
        lambda a, b: jnp.sum(jnp.einsum("ecd,edf->ecf", a, b) ** 2),
        argnums=(0, 1))(a3, b3)
    np.testing.assert_allclose(np.asarray(da), np.asarray(ref[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(ref[1]),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------- schedule

@pytest.mark.parametrize("site", ["ffn_up", "ffn_down"])
def test_moe_stack_plans_grouped_hosts(site):
    """Acceptance: on the (dense, moe, moe) stack compile_schedule plans
    ZERO standalone/XLA fallbacks for hostable shapes — the dense block
    emits under the dense fused kernel, the MoE blocks under the grouped
    kernel. Only the bootstrap consumption (no producer GEMM exists
    before the first attention layer) stays standalone, by design.
    (attn_replay="off" pins the materialized-plane pipeline whose host
    selection this test locks; replay planning lives in test_replay.py.)"""
    sched = compile_schedule(_moe_cfg(),
                             _plan_cfg(site, attn_replay="off"), 2, 128,
                             attn_impl="pallas")
    emits = [(a.layer, a.emit_how, a.emit_reason)
             for a in sched.assignments if a.emit_site]
    assert [e[1] for e in emits] == [
        producer.HOW_GEMM, producer.HOW_GEMM_GROUPED,
        producer.HOW_GEMM_GROUPED], sched.explain()
    assert all(r == "" for _, _, r in emits), sched.explain()
    for a in sched.assignments:
        if a.consumes and a.producer >= 0:
            assert a.how in _GROUPED_HOWS, sched.explain()


@pytest.mark.parametrize("site", ["ffn_up", "ffn_down"])
def test_rwkv_hybrid_plans_grouped_hosts(site):
    """Acceptance: the RWKV hybrid's channel-mix GEMMs are first-class
    hosts (E=1 grouped) — no standalone/XLA fallback planned."""
    sched = compile_schedule(_rwkv_hybrid_cfg(), _plan_cfg(site), 2, 128,
                             attn_impl="pallas")
    emits = [a for a in sched.assignments if a.emit_site]
    assert emits, sched.explain()
    for a in emits:
        assert a.emit_how == producer.HOW_GEMM_GROUPED, sched.explain()
        assert a.emit_reason == "", sched.explain()


def test_infeasible_grouped_shapes_report_distinct_reasons():
    """Satellite: an infeasible grouped shape reports a reason naming
    ITS block kind — MoE expert vs RWKV channel-mix are no longer
    conflated into one ternary — and explain() renders it per-layer."""
    # capacity 11 does not tile (no 8-multiple divisor): MoE reason
    moe_cfg = _moe_cfg(
        n_layers=2,
        moe=MoEConfig(n_experts=6, top_k=1, d_ff_expert=128,
                      first_dense_layers=0, capacity_factor=1.0))
    sched = compile_schedule(moe_cfg, _plan_cfg("ffn_up"), 1, 64,
                             attn_impl="pallas")
    reasons = {a.emit_reason for a in sched.assignments if a.emit_site}
    assert any("MoE expert" in r and "does not tile" in r
               for r in reasons), sched.explain()
    assert any("MoE expert" in r for r in sched.explain().splitlines()
               if "emits->" in r), sched.explain()
    # d_ff=12 does not tile: RWKV channel-mix reason, distinct text
    hyb = _rwkv_hybrid_cfg(d_ff=12)
    sched_h = compile_schedule(hyb, _plan_cfg("ffn_up"), 1, 64,
                               attn_impl="pallas")
    reasons_h = {a.emit_reason for a in sched_h.assignments
                 if a.emit_site}
    assert any("RWKV channel-mix" in r and "does not tile" in r
               for r in reasons_h), sched_h.explain()
    assert reasons.isdisjoint(reasons_h)
    # Region 3 on a grouped shape names the block kind too
    r3_cfg = _moe_cfg(
        n_layers=2, n_heads=32, n_kv_heads=32, head_dim=2,
        moe=MoEConfig(n_experts=2, top_k=1, d_ff_expert=8,
                      first_dense_layers=0, capacity_factor=0.25))
    # attn_replay="off": at seq=1024 the default plan would replay the
    # consumer and clear the standalone emission whose reason we check
    sched_r3 = compile_schedule(r3_cfg,
                                _plan_cfg("ffn_up", attn_replay="off"),
                                1, 1024, attn_impl="pallas")
    reasons_r3 = {a.emit_reason for a in sched_r3.assignments
                  if a.emit_site}
    assert any("Region 3" in r and "MoE expert" in r
               for r in reasons_r3), sched_r3.explain()
    # the per-layer rendering is what launch/dryrun.py prints
    assert any("Region 3" in line
               for line in sched_r3.explain().splitlines()), \
        sched_r3.explain()


def test_first_dense_channel_mix_plans_on_its_own_grid(rng_key):
    """A MoE stack whose first-dense layer carries an RWKV channel-mix
    FFN plans THAT layer on the E=1 channel-mix grid, not the expert
    grid (the block kind is judged per layer) — and the executed
    pipeline still matches the XLA site bit-for-bit. Planning
    introspection pins attn_replay="off"; the executed comparison runs
    the default (replay) plan, which must not move a bit."""
    cfg = _moe_cfg(ffn=FFNKind.RWKV_CHANNEL)
    sched = compile_schedule(cfg, _plan_cfg("ffn_up", attn_replay="off"),
                             2, 128, attn_impl="pallas")
    emits = {a.layer: a for a in sched.assignments if a.emit_site}
    assert emits[0].emit_how == producer.HOW_GEMM_GROUPED, \
        sched.explain()
    assert emits[1].emit_how == producer.HOW_GEMM_GROUPED, \
        sched.explain()
    # an infeasible first-dense channel-mix shape reports the RWKV
    # reason, not a mislabelled "MoE expert" one
    bad = compile_schedule(_moe_cfg(ffn=FFNKind.RWKV_CHANNEL, d_ff=12),
                           _plan_cfg("ffn_up", attn_replay="off"), 2,
                           128, attn_impl="pallas")
    bad_emits = {a.layer: a for a in bad.assignments if a.emit_site}
    assert "RWKV channel-mix" in bad_emits[0].emit_reason, bad.explain()
    assert bad_emits[1].emit_reason == "", bad.explain()
    params = model_init(rng_key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0,
                                cfg.vocab_size)

    def run(site_):
        rt = Runtime(plan=plan_from_config(_plan_cfg(site_)), step=4,
                     attn_impl="pallas")
        return jax.jit(
            lambda pr, t: forward(pr, cfg, rt, t))(params, tokens)[0]

    np.testing.assert_array_equal(np.asarray(run("xla")),
                                  np.asarray(run("ffn_up")))


def test_auto_ranks_expert_hosts():
    """site="auto" can rank the grouped expert einsum against the dense
    attention GEMMs (perfmodel.grouped_gemm_host_headroom)."""
    sched = compile_schedule(_moe_cfg(), _plan_cfg("auto"), 2, 128,
                             attn_impl="pallas")
    assert sched.resolved_site in ("ffn_up", "ffn_down")
    sites = [s for s, _ in sched.headroom]
    assert "ffn_up" in sites and "qkv" in sites
    emits = [a.emit_how for a in sched.assignments if a.emit_site]
    assert producer.HOW_GEMM_GROUPED in emits, sched.explain()


def test_moe_seq_dispatch_in_schedule_identity():
    """The dispatch-layout knob is part of the compiled artifact's
    identity: two schedules differing only in it are distinct objects."""
    cfg = _moe_cfg()
    s1 = compile_schedule(cfg, _plan_cfg("ffn_up"), 2, 128,
                          attn_impl="pallas")
    s2 = compile_schedule(cfg, _plan_cfg("ffn_up"), 2, 128,
                          attn_impl="pallas", moe_seq_dispatch=True)
    assert s1 != s2
    assert s1.summary()["moe_seq_dispatch"] is False
    assert s2.summary()["moe_seq_dispatch"] is True


def test_moe_seq_dispatch_mismatch_fails_fast(rng_key):
    """Satellite: a schedule planned for the dense-dispatch layout must
    fail fast against a seq-dispatch runtime (and vice versa), not
    silently emit a mask plan for the wrong expert grid."""
    cfg = _moe_cfg()
    params = model_init(rng_key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0,
                                cfg.vocab_size)
    plan = plan_from_config(_plan_cfg("ffn_up"))
    sched = compile_schedule(cfg, plan.cfg, 2, 128, attn_impl="pallas")
    rt_bad = Runtime(plan=plan, step=0, attn_impl="pallas",
                     schedule=sched, moe_seq_dispatch=True)
    with pytest.raises(ValueError, match="moe_seq_dispatch"):
        forward(params, cfg, rt_bad, tokens)
    # the matching flag passes (and the sugar path compiles to match)
    rt_ok = Runtime(plan=plan, step=0, attn_impl="pallas",
                    schedule=sched)
    logits, _ = forward(params, cfg, rt_ok, tokens)
    assert logits.shape == (2, 128, cfg.vocab_size)
    # a schedule WITHOUT a grouped expert host is dispatch-layout-
    # independent: a flag mismatch must pass through, not false-positive
    plan_qkv = plan_from_config(_plan_cfg("qkv"))
    sched_qkv = compile_schedule(cfg, plan_qkv.cfg, 2, 128,
                                 attn_impl="pallas")
    rt_qkv = Runtime(plan=plan_qkv, step=0, attn_impl="pallas",
                     schedule=sched_qkv, moe_seq_dispatch=True)
    logits_qkv, _ = forward(params, cfg, rt_qkv, tokens)
    assert logits_qkv.shape == (2, 128, cfg.vocab_size)


# -------------------------------------------------------------- execute

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("site", ["ffn_up", "ffn_down", "auto"])
def test_moe_stack_sites_bit_identical(rng_key, site, impl):
    """Acceptance: on the (dense, moe, moe) stack every grouped-hosted
    site reproduces the per-layer XLA site exactly — identical masks →
    identical logits (the f32 grouped kernel's single-k-block
    accumulation matches the einsum bitwise)."""
    cfg = _moe_cfg()
    params = model_init(rng_key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0,
                                cfg.vocab_size)

    def run(site_):
        rt = Runtime(plan=plan_from_config(_plan_cfg(site_)), step=4,
                     attn_impl=impl)
        logits, _ = jax.jit(
            lambda pr, t: forward(pr, cfg, rt, t))(params, tokens)
        return logits

    np.testing.assert_array_equal(np.asarray(run("xla")),
                                  np.asarray(run(site)))


@pytest.mark.parametrize("site", ["ffn_up", "ffn_down"])
def test_rwkv_hybrid_sites_bit_identical(rng_key, site):
    """Acceptance: the RWKV hybrid's channel-mix-hosted pipeline (E=1
    grouped kernel, carry riding through the WKV blocks) reproduces the
    XLA site exactly."""
    cfg = _rwkv_hybrid_cfg()
    params = model_init(rng_key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0,
                                cfg.vocab_size)

    def run(site_):
        rt = Runtime(plan=plan_from_config(_plan_cfg(site_)), step=4,
                     attn_impl="pallas")
        logits, _ = jax.jit(
            lambda pr, t: forward(pr, cfg, rt, t))(params, tokens)
        return logits

    np.testing.assert_array_equal(np.asarray(run("xla")),
                                  np.asarray(run(site)))


@pytest.mark.parametrize("gemm_dtype", ["bf16", "fp8"])
def test_moe_stack_nondefault_dtypes_same_masks(rng_key, gemm_dtype):
    """gemm_dtype moves the GEMM's precision, never the bits: the
    grouped-hosted forward stays finite and the producer-level masks
    equal the f32 host's for every dtype (the bit claim; logits shift
    within quantization error because the host GEMM's OUTPUT changes)."""
    from repro.kernels import quant
    if gemm_dtype == "fp8" and not quant.have_fp8():
        pytest.skip("no float8_e4m3fn in this JAX build")
    cfg = _moe_cfg()
    params = model_init(rng_key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 128), 0,
                                cfg.vocab_size)
    rt = Runtime(plan=plan_from_config(
        _plan_cfg("ffn_up", gemm_dtype=gemm_dtype)), step=4,
        attn_impl="pallas")
    logits, _ = forward(params, cfg, rt, tokens)
    assert bool(jnp.isfinite(logits).all())


def test_moe_train_step_grads_through_grouped_host(rng_key):
    """Gradients flow through the grouped-hosted expert GEMMs inside the
    real train step, and the loss matches the XLA site (same bits)."""
    from repro.config.base import (OptimizerConfig, RunConfig,
                                   ShapeConfig, ShardingConfig, StepKind,
                                   TrainConfig)
    from repro.train.loop import init_train_state, make_train_step
    cfg = _moe_cfg()
    shape = ShapeConfig("t", 128, 1, StepKind.TRAIN)
    x = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                           cfg.vocab_size)
    y = jax.random.randint(jax.random.PRNGKey(2), (1, 128), 0,
                           cfg.vocab_size)

    def one_step(site_, impl_):
        run = RunConfig(
            model=cfg, shape=shape,
            dropout=DropoutPlanConfig(mode="overlap", p=_P, seed=_SEED,
                                      site=site_),
            sharding=ShardingConfig(remat="block", attn_impl=impl_),
            train=TrainConfig(optimizer=OptimizerConfig()))
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        state, m = jax.jit(make_train_step(cfg, run))(state, x, y)
        return float(m["loss"]), state

    loss_ref, _ = one_step("xla", "xla")
    loss, state = one_step("ffn_up", "pallas")
    assert abs(loss - loss_ref) < 1e-4, (loss, loss_ref)
    leaves = jax.tree_util.tree_leaves(state["master"])
    assert all(bool(jnp.isfinite(l).all()) for l in leaves)


# ------------------------------------------------- routing invariance

@pytest.mark.parametrize("perturb", ["router", "capacity"])
def test_mask_invariant_to_routing(rng_key, perturb):
    """Property: the emitted mask is a pure function of
    (seed, salt, layer, step) — perturbing the router weights (different
    expert assignment) or slashing the capacity factor (overflow drops)
    changes which tokens flow through which expert tile, and must NOT
    change a single mask bit."""
    cfg = _moe_cfg(n_layers=2,
                   moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=128,
                                 first_dense_layers=0,
                                 capacity_factor=2.0))
    plan = plan_from_config(_plan_cfg("ffn_up"))
    b, h, s = 2, 2, 128
    x = jax.random.normal(rng_key, (b, s, cfg.d_model), jnp.float32)
    host = producer.FFNHost(plan=plan, site="ffn_up",
                            mask_shape=(b, h, s, s), layer_idx=1, step=7,
                            how=producer.HOW_GEMM_GROUPED)
    params = moe_mod.moe_init(jax.random.PRNGKey(2), cfg)
    _, _, mask_ref = moe_mod.moe_apply(params, x, cfg, None, host=host)

    if perturb == "router":
        # flip the routing wholesale: outputs move, bits must not
        p2 = dict(params)
        p2["router"] = -params["router"] + 0.3 * jax.random.normal(
            jax.random.PRNGKey(9), params["router"].shape)
        _, _, mask_got = moe_mod.moe_apply(p2, x, cfg, None, host=host)
    else:
        # capacity overflow: cf=0.5 drops half the assignments (and
        # changes C, hence the whole GEMM grid)
        cfg2 = _moe_cfg(n_layers=2,
                        moe=MoEConfig(n_experts=4, top_k=2,
                                      d_ff_expert=128,
                                      first_dense_layers=0,
                                      capacity_factor=0.5))
        _, _, mask_got = moe_mod.moe_apply(params, x, cfg2, None,
                                           host=host)

    np.testing.assert_array_equal(np.asarray(mask_got),
                                  np.asarray(mask_ref))
    want = philox_mask_ref(b, h, s, s, _P, int(plan.step_seed(7)),
                           int(plan.salt(1)))
    np.testing.assert_array_equal(np.asarray(mask_ref),
                                  np.asarray(want))


# ------------------------------------------------------------- sharded

_EP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np
from repro.config.base import (AttentionKind, DropoutPlanConfig,
                               ModelConfig, MoEConfig)
from repro.core.overlap import plan_from_config
from repro.core import producer
from repro.core.schedule import compile_schedule
from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.kernels.ref import philox_mask_ref
from repro.models import moe as moe_mod
from repro.launch.mesh import auto_mesh
from repro.models.transformer import Runtime, forward, model_init

P_, SEED_ = 0.25, 5
cfg = ModelConfig(
    name="dmm", family="moe", n_layers=3, d_model=64, n_heads=2,
    n_kv_heads=2, d_ff=128, vocab_size=64, head_dim=32,
    block_pattern=(AttentionKind.FULL,), attn_dropout=P_,
    moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=128,
                  first_dense_layers=1, capacity_factor=2.0))
params = model_init(jax.random.PRNGKey(0), cfg)
tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0,
                            cfg.vocab_size)
plan = plan_from_config(DropoutPlanConfig(mode="overlap", p=P_,
                                          seed=SEED_, site="ffn_up"))
policy = ShardingPolicy(auto_mesh((2,), ("data",)))

# 1) schedule: EP mesh keeps the grouped kernel, shard-local, no degrade
sched = compile_schedule(cfg, plan.cfg, 2, 128, policy=policy,
                         attn_impl="pallas")
hows = {a.emit_how for a in sched.assignments if a.emit_site}
assert producer.HOW_GEMM_GROUPED in hows, sched.explain()
assert producer.HOW_XLA not in hows, sched.explain()
assert sched.sharded, sched.explain()

# 2) producer: the mask emitted from INSIDE the EP shard_map dispatch is
#    bit-identical to the reference oracle and to the unsharded host
want = philox_mask_ref(2, 2, 128, 128, P_, int(plan.step_seed(7)),
                       int(plan.salt(2)))
host = producer.FFNHost(plan=plan, site="ffn_up",
                        mask_shape=(2, 2, 128, 128), layer_idx=2, step=7,
                        how=producer.HOW_GEMM_GROUPED, policy=policy)
x = jax.random.normal(jax.random.PRNGKey(9), (2, 128, 64), jnp.float32)
mp = moe_mod.moe_init(jax.random.PRNGKey(2), cfg)
with use_policy(policy):
    y_sh, _, mask_sh = jax.jit(
        lambda p_, x_: moe_mod.moe_apply(p_, x_, cfg, policy,
                                         host=host))(mp, x)
np.testing.assert_array_equal(np.asarray(mask_sh), np.asarray(want))
host_l = producer.FFNHost(plan=plan, site="ffn_up",
                          mask_shape=(2, 2, 128, 128), layer_idx=2,
                          step=7, how=producer.HOW_GEMM_GROUPED)
y_l, _, mask_l = moe_mod.moe_apply(mp, x, cfg, None, host=host_l)
np.testing.assert_array_equal(np.asarray(mask_l), np.asarray(want))
np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_l),
                           rtol=2e-4, atol=2e-4)

# 3) model: sharded logits match the unsharded run (same bits; GSPMD
#    reassociates float reductions, so tight allclose)
def run(policy_):
    rt = Runtime(plan=plan, step=4, attn_impl="pallas", policy=policy_)
    with use_policy(policy_):
        return jax.jit(lambda pr, t: forward(pr, cfg, rt, t))(
            params, tokens)[0]
np.testing.assert_allclose(np.asarray(run(policy)),
                           np.asarray(run(None)), rtol=2e-5, atol=2e-5)
print("EP-GROUPED-OK")
"""


@pytest.mark.slow
def test_grouped_host_2dev_ep():
    """Acceptance: under 2-device EP sharding the grouped expert host
    runs shard-local inside the dispatch's own shard_map, emitting each
    device's (b_loc, h) tile of the mask plane bit-identically to the
    global mask (subprocess: the main process must stay single-device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", _EP_SCRIPT], env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=1200)
    assert "EP-GROUPED-OK" in proc.stdout, (proc.stdout[-3000:],
                                            proc.stderr[-3000:])


@pytest.mark.slow
def test_bench_smoke_mode():
    """CI satellite: ``benchmarks/run.py --smoke`` runs one tiny MoE and
    one dense block per site and asserts the BENCH JSON schema."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke"], env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, (proc.stdout[-3000:],
                                  proc.stderr[-3000:])
    assert "smoke OK" in proc.stdout
    assert "smoke_moe,ffn_up" in proc.stdout
    assert "gemm_rng_grouped" in proc.stdout
