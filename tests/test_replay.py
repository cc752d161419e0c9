"""Zero-HBM replay consumption (mode="replay"): planning, fwd+bwd
bit-identity against the materialized premask path, kernel operand
validation, static-verifier coverage (replay emissions, MS-C1 drift,
MS-D4 plane-operand), and the 2-device global-position counter case.

The load-bearing contract: replay re-derives each (bq, bk) tile's keep
bits in-register from the SAME position-based Philox counters the
host-GEMM producer was planned with, so logits AND grads are bitwise
identical to consuming the materialized plane — while no mask bit
touches HBM (proven statically by MS-D4, not just asserted here).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import counters, dataflow, rules
from repro.config.base import (
    AttentionKind,
    DropoutPlanConfig,
    ModelConfig,
)
from repro.core import producer, schedule as schedule_mod
from repro.core.overlap import plan_from_config
from repro.core.schedule import compile_schedule
from repro.kernels import quant
from repro.models.transformer import Runtime, forward, model_init

_P = 0.25
_SEED = 5
_SITES = ("xla", "qkv", "prev_gemm", "ffn_up", "ffn_down", "auto")


def _plan_cfg(site, **kw):
    return DropoutPlanConfig(mode="overlap", p=_P, seed=_SEED, site=site,
                             **kw)


def _dense_cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=64,
                n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64,
                head_dim=32, block_pattern=(AttentionKind.FULL,),
                attn_dropout=_P)
    base.update(kw)
    return ModelConfig(**base)


def _local_cfg(**kw):
    """Sliding-window + full hybrid: replay must honor local_window."""
    return _dense_cfg(name="tl", local_window=64,
                      block_pattern=(AttentionKind.LOCAL,
                                     AttentionKind.FULL), **kw)


# ------------------------------------------------------------- planning

def test_replay_planned_on_feasible_cells():
    """pallas + 32-bit Philox + 128-tileable seq -> every consumer is
    HOW_REPLAY; gemm-hosted emissions are retained (run-and-discard,
    recorded in host_how / emit_how), standalone ones cleared."""
    cfg = _dense_cfg(n_layers=3)
    for site in _SITES:
        sched = compile_schedule(cfg, _plan_cfg(site), 1, 128,
                                 attn_impl="pallas")
        assert sched.replay, site
        for a in sched.assignments:
            if a.consumes:
                assert a.how == producer.HOW_REPLAY, (site, a)
                assert a.host_how in ("", producer.HOW_GEMM,
                                      producer.HOW_GEMM_GROUPED)
            if a.emit_site is not None:
                # only run-and-discard GEMM hosts keep their emission —
                # a standalone/xla emission's sole purpose was the plane
                assert a.emit_how in (producer.HOW_GEMM,
                                      producer.HOW_GEMM_GROUPED), (site,
                                                                   a)
        assert "replay" in sched.explain()


def test_replay_off_knob_restores_premask_planning():
    cfg = _dense_cfg(n_layers=3)
    off = compile_schedule(cfg, _plan_cfg("ffn_up", attn_replay="off"),
                           1, 128, attn_impl="pallas")
    assert not off.replay
    assert all(a.how != producer.HOW_REPLAY for a in off.assignments)
    assert all(not a.host_how for a in off.assignments)


def test_replay_feasibility_gates():
    cfg = _dense_cfg()
    # xla attention: no in-kernel replay
    s = compile_schedule(cfg, _plan_cfg("xla"), 1, 128, attn_impl="xla")
    assert not s.replay
    # 8-bit Philox planes are an XLA-only byte layout
    s = compile_schedule(cfg, _plan_cfg("xla", philox_bits=8), 1, 128,
                         attn_impl="pallas")
    assert not s.replay
    # non-128-tileable sequence
    s = compile_schedule(cfg, _plan_cfg("xla"), 1, 96,
                         attn_impl="pallas")
    assert not s.replay


# ---------------------------------------------------------- bit-identity

def _run(cfg, site, dtype="f32", replay="auto", seq=128):
    params = model_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, seq), 0,
                                cfg.vocab_size)
    plan = plan_from_config(_plan_cfg(site, gemm_dtype=dtype,
                                      attn_replay=replay))
    rt = Runtime(plan=plan, step=4, attn_impl="pallas")

    def loss(pr, t):
        logits, aux = forward(pr, cfg, rt, t)
        return jnp.sum(logits) + jnp.sum(aux), logits

    (l, logits), grads = jax.value_and_grad(loss, has_aux=True)(params,
                                                                tokens)
    sched = compile_schedule(cfg, plan.cfg, 1, seq, attn_impl="pallas")
    return logits, grads, sched


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("site", _SITES)
def test_replay_bit_identical_to_premask_all_sites(site):
    """Acceptance: fwd logits AND every grad leaf bitwise equal between
    replay consumption and the materialized premask plane."""
    cfg = _dense_cfg()
    lr, gr, sr = _run(cfg, site, replay="auto")
    lp, gp, sp = _run(cfg, site, replay="off")
    assert sr.replay and not sp.replay
    _assert_bitwise(lr, lp)
    jax.tree_util.tree_map(_assert_bitwise, gr, gp)


@pytest.mark.parametrize("dtype", ["bf16", "fp8"])
@pytest.mark.parametrize("site", ["qkv", "ffn_up"])
def test_replay_bit_identical_across_host_dtypes(site, dtype):
    """The host GEMM's dtype moves the GEMM outputs, never the counter
    bits: replay stays bitwise equal to premask under bf16/fp8 hosts."""
    if dtype == "fp8" and not quant.have_fp8():
        pytest.skip("no float8_e4m3fn in this JAX build")
    cfg = _dense_cfg()
    lr, gr, sr = _run(cfg, site, dtype=dtype, replay="auto")
    lp, gp, sp = _run(cfg, site, dtype=dtype, replay="off")
    assert sr.replay and not sp.replay
    _assert_bitwise(lr, lp)
    jax.tree_util.tree_map(_assert_bitwise, gr, gp)


def test_replay_bit_identical_sliding_window():
    """local_window masking composes with replayed dropout tiles."""
    cfg = _local_cfg()
    lr, gr, sr = _run(cfg, "ffn_up", replay="auto")
    lp, gp, sp = _run(cfg, "ffn_up", replay="off")
    assert sr.replay and not sp.replay
    _assert_bitwise(lr, lp)
    jax.tree_util.tree_map(_assert_bitwise, gr, gp)


# -------------------------------------------------------------- kernels

def test_kernel_replay_matches_premask_fwd_bwd():
    """Kernel-level contract, no model: flash_attention with
    mode="replay" equals mode="premask" fed the plane drawn from the
    same (seed, salt) — values and input grads."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.philox import philox_dropout_mask
    from repro.kernels.philox_common import seed_salt_smem
    B, H, S, D = 1, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    seed, salt = 11, 7
    mask = philox_dropout_mask(B, H, S, S, _P, seed, salt=salt)
    seed_salt = seed_salt_smem(seed, salt)

    def f_pre(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, mask, causal=True, dropout_p=_P, mode="premask"))

    def f_rep(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, seed_salt, causal=True, dropout_p=_P, seed=seed,
            salt=salt, mode="replay"))

    (vp, gp) = jax.value_and_grad(f_pre, argnums=(0, 1, 2))(q, k, v)
    (vr, gr) = jax.value_and_grad(f_rep, argnums=(0, 1, 2))(q, k, v)
    _assert_bitwise(vp, vr)
    jax.tree_util.tree_map(_assert_bitwise, gp, gr)


def test_kernel_operand_validation():
    """Satellite: fail fast with a clear ValueError on a mis-packed
    premask plane or a malformed replay seed-salt operand."""
    from repro.kernels.flash_attention import flash_attention_fwd
    B, H, S, D = 1, 2, 128, 32
    q = jnp.zeros((B, H, S, D), jnp.float32)
    with pytest.raises(ValueError, match="premask mode requires"):
        flash_attention_fwd(q, q, q, None, causal=True, dropout_p=_P,
                            mode="premask")
    bad_plane = jnp.zeros((B, H, S, S), jnp.uint32)   # unpacked rows
    with pytest.raises(ValueError, match=r"\(B, H, SQ//32, SK\)"):
        flash_attention_fwd(q, q, q, bad_plane, causal=True,
                            dropout_p=_P, mode="premask")
    bad_dtype = jnp.zeros((B, H, S // 32, S), jnp.int32)
    with pytest.raises(ValueError, match="uint32"):
        flash_attention_fwd(q, q, q, bad_dtype, causal=True,
                            dropout_p=_P, mode="premask")
    with pytest.raises(ValueError, match=r"\(4,\) uint32"):
        flash_attention_fwd(q, q, q, jnp.zeros((3,), jnp.uint32),
                            causal=True, dropout_p=_P, mode="replay")


# ------------------------------------------------------ static verifier

def test_replay_emissions_one_live_draw_per_consumer():
    """Counter-space: each replay consumer has exactly ONE live
    emission (its own in-register derivation); retained run-and-discard
    host planes are present but dropped; the whole cell proves clean."""
    cfg = _dense_cfg(n_layers=4)
    sched = compile_schedule(cfg, _plan_cfg("ffn_up"), 1, 128,
                             attn_impl="pallas")
    assert sched.replay
    emissions = counters.schedule_emissions(cfg, sched)
    live = [e for e in emissions if not e.dropped]
    consumers = [a.layer for a in sched.assignments if a.consumes]
    assert sorted(e.target_layer for e in live) == sorted(consumers)
    assert all(e.how == producer.HOW_REPLAY for e in live)
    # the retained hosts still draw (and still get tiling/salt proofs)
    retained = [e for e in emissions if e.dropped
                and e.how == producer.HOW_GEMM]
    assert retained
    rep = counters.analyze_schedule(cfg, sched)
    assert rep.ok, rep.render()


def test_replay_counter_drift_trips_ms_c1():
    """ISSUE negative control: perturbing the consumer's counter base
    (bh_offset drift) must trip MS-C1 (double draw)."""
    cfg = _dense_cfg(n_layers=4)
    sched = compile_schedule(cfg, _plan_cfg("ffn_up"), 1, 128,
                             attn_impl="pallas")
    emissions = counters.corrupt_emissions(
        counters.schedule_emissions(cfg, sched), "replay-counter-drift")
    findings = counters.check_emissions(cfg, sched, emissions)
    assert any(f.rule == rules.COUNTER_OVERLAP for f in findings), \
        findings


def test_replay_counter_drift_requires_replay_cell():
    cfg = _dense_cfg(n_layers=4)
    sched = compile_schedule(cfg, _plan_cfg("ffn_up", attn_replay="off"),
                             1, 128, attn_impl="pallas")
    with pytest.raises(ValueError, match="replay-planned cell"):
        counters.corrupt_emissions(
            counters.schedule_emissions(cfg, sched),
            "replay-counter-drift")


def test_ms_d4_replay_cell_traces_clean():
    """Dataflow: the real fwd+bwd trace of a replay-planned cell has no
    mask-shaped operand on ANY pallas_call (the zero-HBM proof)."""
    cfg = _dense_cfg()
    rep = dataflow.analyze_model(cfg, _plan_cfg("ffn_up"), 1, 128,
                                 attn_impl="pallas")
    assert rep.ok, rep.render()
    sched = compile_schedule(cfg, _plan_cfg("ffn_up"), 1, 128,
                             attn_impl="pallas")
    assert sched.replay   # the clean verdict is about the replay path


def test_ms_d4_flags_plane_operand_on_replay_cell():
    """Negative control: a packed plane reaching a pallas_call while
    the schedule is replay-planned must raise MS-D4."""
    from repro.kernels.flash_attention import flash_attention_fwd
    cfg = _dense_cfg()
    sched = compile_schedule(cfg, _plan_cfg("ffn_up"), 1, 128,
                             attn_impl="pallas")
    assert sched.replay
    B, H, S, D = 1, cfg.n_heads, 128, 32
    q = jnp.zeros((B, H, S, D), jnp.float32)
    plane = jnp.zeros((B, H, S // 32, S), jnp.uint32)

    closed = jax.make_jaxpr(
        lambda q_, m_: flash_attention_fwd(q_, q_, q_, m_, causal=True,
                                           dropout_p=_P,
                                           mode="premask"))(q, plane)
    rep = dataflow.analyze_jaxpr(closed, cfg, sched,
                                 check_outputs=False)
    assert any(f.rule == rules.MASK_OPERAND_REPLAY
               for f in rep.findings), rep.render()
    # the same jaxpr is sanctioned when the schedule is NOT replay-planned
    sched_off = compile_schedule(cfg,
                                 _plan_cfg("ffn_up", attn_replay="off"),
                                 1, 128, attn_impl="pallas")
    rep_off = dataflow.analyze_jaxpr(closed, cfg, sched_off,
                                     check_outputs=False)
    assert not any(f.rule == rules.MASK_OPERAND_REPLAY
                   for f in rep_off.findings)


# --------------------------------------------------------------- sharded

_SHARDED_REPLAY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.config.base import AttentionKind, DropoutPlanConfig, ModelConfig
from repro.core import producer
from repro.core.overlap import plan_from_config
from repro.core.schedule import compile_schedule
from repro.distributed.sharding import ShardingPolicy, use_policy
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

def pcfg(site, replay):
    return DropoutPlanConfig(mode="overlap", p=P_, seed=SEED_, site=site,
                             attn_replay=replay)

def run(site, policy, replay):
    rt = Runtime(plan=plan_from_config(pcfg(site, replay)), step=4,
                 attn_impl="pallas", policy=policy)
    with use_policy(policy):
        return jax.jit(lambda pr, t: forward(pr, cfg, rt, t))(
            params, tokens)[0]

# batch-sharded (shard-local bh windows) AND head-sharded (global_bh
# remap from the (4,)-word's bh_offset: shard-local calls must replay
# GLOBAL-position counters)
for axes in (("data",), ("model",)):
    policy = ShardingPolicy(auto_mesh((2,), axes))
    for site in ("qkv", "ffn_up"):
        sched = compile_schedule(cfg, pcfg(site, "auto"), 2, 128,
                                 policy=policy, attn_impl="pallas")
        assert sched.replay, (axes, site, sched.explain())
        for a in sched.assignments:
            if a.consumes:
                assert a.how == producer.HOW_REPLAY, (axes, site, a)
                assert a.sharded, (axes, site, a)
        # same mesh, same float reassociation: replay vs materialized
        # premask must be BITWISE equal (identical keep bits, identical
        # kernel tile math)
        got = np.asarray(run(site, policy, "auto"))
        ref = np.asarray(run(site, policy, "off"))
        np.testing.assert_array_equal(got, ref)
        # and the sharded replay run matches the unsharded one up to
        # GSPMD reduction reassociation
        solo = np.asarray(run(site, None, "auto"))
        np.testing.assert_allclose(got, solo, rtol=2e-5, atol=2e-5)
print("SHARDED-REPLAY-OK")
"""


@pytest.mark.slow
def test_sharded_replay_global_counters_2dev():
    """Acceptance: on a 2-device mesh (batch- and head-sharded) replay
    consumption stays bitwise identical to the materialized premask
    path — the (4,)-word's bh_offset makes each shard replay
    global-position counters (subprocess: the main test process must
    stay single-device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_REPLAY_SCRIPT], env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=1200)
    assert "SHARDED-REPLAY-OK" in proc.stdout, (
        proc.stdout[-3000:], proc.stderr[-3000:])
