"""Compiled per-layer DropoutSchedule: plan → compile → execute.

Covers the schedule redesign's acceptance surface: bit-identity of every
producer site under a mixed Griffin-style (R, R, A) pattern, shard-local
fused production on a 2-device shard_map mesh (no HOW_XLA degrade when
the kernel is capable), compilation determinism (same inputs → same
hashable artifact), the explain() rendering, and the serving-side
packed-mask reuse cache keyed on the schedule's mask identity.
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
    ModelConfig,
)
from repro.core import producer, schedule as schedule_mod
from repro.core.overlap import plan_from_config
from repro.core.schedule import compile_schedule
from repro.kernels.ref import philox_mask_ref
from repro.models.transformer import Runtime, forward, model_init

_P = 0.25
_SEED = 5


def _plan_cfg(site, **kw):
    return DropoutPlanConfig(mode="overlap", p=_P, seed=_SEED, site=site,
                             **kw)


def _griffin_cfg(**kw):
    """(RECURRENT, RECURRENT, FULL) hybrid — the mixed-pattern regime
    the per-layer schedule exists for."""
    base = dict(name="grif", family="hybrid", n_layers=6, d_model=64,
                n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64,
                head_dim=32, local_window=32,
                block_pattern=(AttentionKind.RECURRENT,
                               AttentionKind.RECURRENT,
                               AttentionKind.FULL),
                attn_dropout=_P)
    base.update(kw)
    return ModelConfig(**base)


def _dense_cfg(**kw):
    base = dict(name="t", family="dense", n_layers=3, d_model=64,
                n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64,
                head_dim=32, block_pattern=(AttentionKind.FULL,),
                attn_dropout=_P)
    base.update(kw)
    return ModelConfig(**base)


# --------------------------------------------------------------- compile

def test_compile_is_deterministic_and_hashable():
    """Same inputs -> equal, equally-hashed artifacts, even across a
    cleared compile cache (the artifact is a pure function of static
    data, not an accumulation of trace-time events)."""
    cfg = _griffin_cfg()
    s1 = compile_schedule(cfg, _plan_cfg("ffn_up"), 2, 128,
                          attn_impl="pallas")
    schedule_mod.clear_cache()
    s2 = compile_schedule(cfg, _plan_cfg("ffn_up"), 2, 128,
                          attn_impl="pallas")
    assert s1 is not s2
    assert s1 == s2
    assert hash(s1) == hash(s2)
    # and a different input changes the artifact
    s3 = compile_schedule(cfg, _plan_cfg("ffn_down"), 2, 128,
                          attn_impl="pallas")
    assert s3 != s1


def test_mixed_pattern_routes_to_next_attention_layer():
    """Griffin-style stacks must CARRY: attention layer l's block emits
    the mask for the *next attention layer* (emit_stride spans the
    recurrent layers) instead of degrading to standalone per-layer
    generation. (attn_replay="off" pins the materialized-plane pipeline
    this test is about; replay planning is covered by test_replay.py.)"""
    cfg = _griffin_cfg()
    sched = compile_schedule(cfg, _plan_cfg("ffn_up", attn_replay="off"),
                             1, 128, attn_impl="pallas")
    assert sched.carried and sched.active
    assert sched.first_consumer == 2
    a2, a5 = sched.for_layer(2), sched.for_layer(5)
    assert a2.site == "standalone" and a2.producer == -1  # bootstrap
    assert a2.emit_site == "ffn_up" and a2.emit_stride == 3
    assert a2.emit_how == producer.HOW_GEMM
    assert a5.site == "ffn_up" and a5.producer == 2
    assert a5.how == producer.HOW_GEMM
    # recurrent layers neither consume nor emit
    for l in (0, 1, 3, 4):
        asg = sched.for_layer(l)
        assert not asg.consumes and asg.emit_site is None


def test_region3_planned_ahead_of_trace():
    """A GEMM too small to host the mask must be planned HOW_STANDALONE
    (paper Region 3) by the compiler — not discovered mid-scan. A
    64-head mask over the d_model=64 out-projection exceeds the fused
    kernel's per-step row budget. (attn_replay="off": Region 3 is a
    property of the materialized-plane pipeline.)"""
    cfg = _dense_cfg(n_heads=64, n_kv_heads=64, head_dim=8)
    sched = compile_schedule(cfg,
                             _plan_cfg("prev_gemm", attn_replay="off"),
                             1, 512, attn_impl="pallas")
    asg = sched.for_layer(0)
    assert asg.emit_how == producer.HOW_STANDALONE
    assert "Region 3" in asg.emit_reason
    asg1 = sched.for_layer(1)
    assert asg1.how == producer.HOW_STANDALONE
    assert "Region 3" in asg1.reason


def test_explain_snapshot_replay_default():
    """explain() under the DEFAULT plan: feasible pallas cells are
    replay-planned — consumers render how=replay, a retained
    run-and-discard GEMM host renders as host=..., and the retained
    emission rows keep their how."""
    cfg = _griffin_cfg()
    sched = compile_schedule(cfg, _plan_cfg("ffn_up"), 1, 128,
                             attn_impl="pallas")
    want = """\
dropout schedule: model=grif batch=1 seq=128 mode=overlap p=0.25 \
site=ffn_up gemm_dtype=f32 impl=pallas carried=yes
  L0   recurrent -
  L1   recurrent -
  L2   full      mask<-bootstrap:standalone how=replay | emits->L5 \
under ffn_up how=gemm_rng
  L3   recurrent -
  L4   recurrent -
  L5   full      mask<-L2:ffn_up how=replay host=gemm_rng | \
emits->dropped under ffn_up how=gemm_rng"""
    assert sched.explain() == want


def test_explain_snapshot():
    """explain() is the operator-facing contract — lock its shape
    (attn_replay="off" pins the materialized-plane rendering)."""
    cfg = _griffin_cfg()
    sched = compile_schedule(cfg, _plan_cfg("ffn_up", attn_replay="off"),
                             1, 128, attn_impl="pallas")
    want = """\
dropout schedule: model=grif batch=1 seq=128 mode=overlap p=0.25 \
site=ffn_up gemm_dtype=f32 impl=pallas carried=yes
  L0   recurrent -
  L1   recurrent -
  L2   full      mask<-bootstrap:standalone how=standalone (bootstrap: \
no producer GEMM before the first attention layer) | emits->L5 under \
ffn_up how=gemm_rng
  L3   recurrent -
  L4   recurrent -
  L5   full      mask<-L2:ffn_up how=gemm_rng | emits->dropped under \
ffn_up how=gemm_rng"""
    assert sched.explain() == want


def test_explain_snapshot_standalone_fallback():
    """Standalone-fallback layers share one fallback reason between the
    consume and emit halves of a row — explain() must print it once,
    not twice (it used to repeat the raw reason string).
    attn_replay="off": the fallback rows are premask machinery."""
    cfg = _dense_cfg(n_heads=64, n_kv_heads=64, head_dim=8)
    sched = compile_schedule(cfg,
                             _plan_cfg("prev_gemm", attn_replay="off"),
                             1, 512, attn_impl="pallas")
    want = """\
dropout schedule: model=t batch=1 seq=512 mode=overlap p=0.25 \
site=prev_gemm gemm_dtype=f32 impl=pallas carried=yes
  L0   full      mask<-bootstrap:standalone how=standalone (bootstrap: \
no producer GEMM before the first attention layer) | emits->L1 under \
prev_gemm how=standalone (Region 3: GEMM (512,64,512) too small for \
1x64x512x512 mask)
  L1   full      mask<-L0:prev_gemm how=standalone (Region 3: GEMM \
(512,64,512) too small for 1x64x512x512 mask) | emits->L2 under \
prev_gemm how=standalone
  L2   full      mask<-L1:prev_gemm how=standalone (Region 3: GEMM \
(512,64,512) too small for 1x64x512x512 mask) | emits->dropped under \
prev_gemm how=standalone"""
    assert sched.explain() == want
    # the shared fallback reason appears exactly once per row
    for row in sched.explain().splitlines()[2:]:
        assert row.count("Region 3") <= 1


def test_auto_resolution_recorded_with_headroom():
    cfg = _dense_cfg()
    sched = compile_schedule(cfg, _plan_cfg("auto"), 2, 128,
                             attn_impl="pallas")
    assert sched.resolved_site == "ffn_up"      # largest Region-1 host
    assert sched.headroom and sched.headroom[0][0] == "ffn_up"
    assert "auto candidate" in sched.explain()
    # xla impl has no fused kernels: auto must degrade to "xla"
    sched_xla = compile_schedule(cfg, _plan_cfg("auto"), 2, 128,
                                 attn_impl="xla")
    assert sched_xla.resolved_site == "xla"


def test_summary_is_json_ready():
    import json
    cfg = _griffin_cfg()
    sched = compile_schedule(cfg, _plan_cfg("prev_gemm"), 1, 128,
                             attn_impl="pallas")
    summary = json.loads(json.dumps(sched.summary()))
    assert summary["carried"] is True
    assert [l["layer"] for l in summary["layers"]] == [2, 5]


# --------------------------------------------------------------- execute

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("site", ["prev_gemm", "ffn_up", "ffn_down",
                                  "qkv", "auto"])
def test_griffin_sites_bit_identical(rng_key, site, impl):
    """Acceptance: on a (R, R, A) pattern every site — including the
    carried pipelines now routed across the recurrent layers — must
    reproduce the per-layer XLA site exactly (identical masks →
    identical logits), with compile_schedule choosing the hosts."""
    cfg = _griffin_cfg()
    params = model_init(rng_key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 128), 0,
                                cfg.vocab_size)

    def run(site_):
        rt = Runtime(plan=plan_from_config(_plan_cfg(site_)), step=4,
                     attn_impl=impl)
        logits, _ = jax.jit(
            lambda pr, t: forward(pr, cfg, rt, t))(params, tokens)
        return logits

    np.testing.assert_array_equal(np.asarray(run("xla")),
                                  np.asarray(run(site)))


def test_explicit_schedule_in_runtime_matches_sugar(rng_key):
    """plan → compile → execute: passing the compiled artifact through
    Runtime.schedule must produce exactly what the site-sugar path
    compiles internally."""
    cfg = _griffin_cfg()
    params = model_init(rng_key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 128), 0,
                                cfg.vocab_size)
    plan = plan_from_config(_plan_cfg("ffn_up"))
    sched = compile_schedule(cfg, plan.cfg, 1, 128, attn_impl="pallas")
    rt_explicit = Runtime(plan=plan, step=4, attn_impl="pallas",
                          schedule=sched)
    rt_sugar = Runtime(plan=plan, step=4, attn_impl="pallas")
    a, _ = forward(params, cfg, rt_explicit, tokens)
    b, _ = forward(params, cfg, rt_sugar, tokens)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------- sharded

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.config.base import AttentionKind, DropoutPlanConfig, ModelConfig
from repro.core.overlap import plan_from_config
from repro.core import producer
from repro.core.schedule import compile_schedule
from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.kernels.ref import philox_mask_ref
from repro.models.transformer import Runtime, forward, model_init
from repro.launch.mesh import auto_mesh

P_, SEED_ = 0.25, 5
cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64,
                  head_dim=32, block_pattern=(AttentionKind.FULL,),
                  attn_dropout=P_)
params = model_init(jax.random.PRNGKey(0), cfg)
tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0,
                            cfg.vocab_size)

# attn_replay="off": this script locks the sharded MATERIALIZED-plane
# pipeline (shard-local fused producers, no XLA degrade); the sharded
# replay-consumption case is tests/test_replay.py's subprocess script
def pcfg(site):
    return DropoutPlanConfig(mode="overlap", p=P_, seed=SEED_, site=site,
                             attn_replay="off")

def run(site, policy, impl):
    rt = Runtime(plan=plan_from_config(pcfg(site)), step=4,
                 attn_impl=impl, policy=policy)
    with use_policy(policy):
        return jax.jit(lambda pr, t: forward(pr, cfg, rt, t))(
            params, tokens)[0]

# 1) producer-level: the sharded fused GEMM+RNG emits masks bit-identical
#    to the XLA reference oracle on batch- AND head-sharded meshes
plan = plan_from_config(pcfg("qkv"))
b, h, s = 2, 2, 128
want = philox_mask_ref(b, h, s, s, P_, int(plan.step_seed(7)),
                       int(plan.salt(3)))
x2d = jax.random.normal(jax.random.PRNGKey(0), (b * s, 64))
w = jax.random.normal(jax.random.PRNGKey(1), (64, 192))
y_ref, _, _ = producer.gemm_with_mask(x2d, w, plan, (b, h, s, s), 3, 7)
# XLA's CPU dot does not keep its bits when N is split, so the model-axis
# GEMM (each shard owns an N-slice) is held bitwise to the same kernel
# run on each slice
y_cols = np.concatenate([np.asarray(producer.gemm_with_mask(
    x2d, w[:, c:c + 96], plan, (b, h, s, s), 3, 7)[0]) for c in (0, 96)],
    axis=1)
for axes in (("data",), ("model",)):
    policy = ShardingPolicy(auto_mesh((2,), axes))
    y, mask, how = producer.gemm_with_mask(
        x2d, w, plan, (b, h, s, s), 3, 7, how=producer.HOW_GEMM,
        policy=policy)
    assert how == producer.HOW_GEMM, how
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(y), y_cols if axes == ("model",) else np.asarray(y_ref))
    m2 = producer.standalone_packed_mask(plan, b, h, s, s, 3, 7,
                                         policy=policy)
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(want))

# 2) schedule-level: with a policy installed the compiler must KEEP the
#    fused kernel (no HOW_XLA degrade) and mark production shard-local
# 3) model-level: sharded logits == unsharded logits, bitwise, per site
for axes in (("data",), ("model",)):
    policy = ShardingPolicy(auto_mesh((2,), axes))
    for site in ("qkv", "prev_gemm", "ffn_up", "ffn_down"):
        sched = compile_schedule(cfg, pcfg(site), 2, 128, policy=policy,
                                 attn_impl="pallas")
        hows = {a.how for a in sched.assignments if a.consumes}
        hows |= {a.emit_how for a in sched.assignments if a.emit_site}
        assert producer.HOW_GEMM in hows, (axes, site, sched.explain())
        assert producer.HOW_XLA not in hows, (axes, site,
                                              sched.explain())
        assert sched.sharded, (axes, site)
        # masks are bitwise (asserted above at the producer level);
        # logits get a tight allclose — GSPMD reassociates the psum
        # reductions of sharded contractions, so float sums differ in
        # the last ulps
        got = np.asarray(run(site, policy, "pallas"))
        ref = np.asarray(run(site, None, "pallas"))
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
print("SHARDED-SCHEDULE-OK")
"""


@pytest.mark.slow
def test_sharded_schedule_bit_identical_2dev():
    """Acceptance: on a 2-device shard_map mesh the fused producers run
    shard-local (schedule keeps HOW_GEMM; no XLA degrade) and masks are
    bit-identical to the XLA reference (subprocess: the main test
    process must stay single-device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT], env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=1200)
    assert "SHARDED-SCHEDULE-OK" in proc.stdout, (
        proc.stdout[-3000:], proc.stderr[-3000:])


# ------------------------------------------------------- mask-reuse cache

def test_serving_mask_reuse_cache():
    """Speculative-decoding verification replays the draft's
    (seed, salt, layer, step) identities: every replay fetch must be a
    cache hit (RNG skipped), keyed by the schedule's mask identity."""
    from repro.launch.serve import PackedMaskCache, verify_replay_demo
    cfg = _dense_cfg()
    sched = compile_schedule(cfg, _plan_cfg("xla"), 1, 64)
    cache = PackedMaskCache()
    m1 = cache.get_or_create(sched, 1, 7, (1, cfg.n_heads, 64, 64))
    m2 = cache.get_or_create(sched, 1, 7, (1, cfg.n_heads, 64, 64))
    assert m1 is m2                       # replay: no RNG ran
    assert cache.stats() == {"hits": 1, "misses": 1, "entries": 1,
                             "evictions": 0}
    # bits match the reference oracle for the schedule's identity
    seed, salt = sched.mask_key(1, 7)[:2]
    want = philox_mask_ref(1, cfg.n_heads, 64, 64, _P, seed, salt)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(want))
    # distinct (layer, step) -> distinct masks
    m3 = cache.get_or_create(sched, 2, 7, (1, cfg.n_heads, 64, 64))
    assert not np.array_equal(np.asarray(m1), np.asarray(m3))
    # the key covers everything the bits depend on: a plan differing
    # only in p must NOT share cache entries
    sched_p = compile_schedule(cfg, DropoutPlanConfig(
        mode="overlap", p=0.5, seed=_SEED, site="xla"), 1, 64)
    assert sched_p.mask_key(1, 7) != sched.mask_key(1, 7)
    # and shapes the Pallas kernel cannot tile fall back to the XLA
    # producer instead of crashing (sq32=12 breaks the packed-row tile)
    m384 = cache.get_or_create(sched, 1, 8, (1, cfg.n_heads, 384, 384))
    s384, t384 = sched.mask_key(1, 8)[:2]
    np.testing.assert_array_equal(
        np.asarray(m384),
        np.asarray(philox_mask_ref(1, cfg.n_heads, 384, 384, _P,
                                   s384, t384)))
    # the full draft+verify flow: replays are 100% hits
    cache2 = verify_replay_demo(cfg, sched, 1, 64, steps=range(3),
                                replays=2)
    st = cache2.stats()
    n_masks = 3 * len([a for a in sched.assignments if a.consumes])
    assert st["misses"] == n_masks
    assert st["hits"] == 2 * n_masks


def test_cache_eviction_bounded():
    from repro.launch.serve import PackedMaskCache
    cfg = _dense_cfg()
    sched = compile_schedule(cfg, _plan_cfg("xla"), 1, 64)
    cache = PackedMaskCache(capacity=4)
    for step in range(8):
        cache.get_or_create(sched, 0, step, (1, cfg.n_heads, 64, 64))
    assert cache.stats()["entries"] == 4
