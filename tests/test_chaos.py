"""Elastic-determinism acceptance: crash-recovered and resharded runs
replay the uninterrupted trajectory BIT FOR BIT.

In-process half: ChaosMonkey kills steps mid-forward and mid-backward,
delays one step past the straggler threshold, and ChaosCheckpointer
kills an async checkpoint write mid-flight; TrainRunner must recover to
the bitwise loss/mask trajectory of the uninterrupted reference, charge
the failed save to ``failed_saves`` (not the restart budget), and flag
the straggler. Contract half: restoring under a drifted dropout contract
fails fast (mask_identity) or re-proves the new realization through
repro.analysis (topology drift). Subprocess half (slow): a 1-device
checkpoint restores onto a 2-device model-axis mesh — whose host GEMM is
N-dim sharded, each shard computing a distinct column slice — and back,
with per-shard mask tiles proven bitwise-identical to the global mask.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    Checkpointer,
    ContractMismatchError,
    DropoutContract,
    contract_from_schedule,
    verify_resume,
)
from repro.config import (
    DropoutPlanConfig,
    OptimizerConfig,
    RunConfig,
    ShapeConfig,
    ShardingConfig,
    StepKind,
    TrainConfig,
    get_arch,
)
from repro.core.overlap import plan_from_config
from repro.core.schedule import compile_schedule
from repro.data import batch_for_step
from repro.distributed.chaos import (
    ChaosCheckpointer,
    ChaosMonkey,
    Fault,
    TrajectoryRecorder,
)
from repro.distributed.fault import StragglerDetector, TrainRunner
from repro.train.loop import (
    compile_run_schedule,
    init_train_state,
    make_train_step,
)

pytestmark = pytest.mark.chaos


def _setup():
    cfg = get_arch("llama2-7b", reduced=True)
    shape = ShapeConfig("chaos", seq_len=32, global_batch=2,
                        kind=StepKind.TRAIN)
    run = RunConfig(model=cfg, shape=shape,
                    dropout=DropoutPlanConfig(mode="overlap", p=0.1),
                    sharding=ShardingConfig(remat="block"),
                    train=TrainConfig(optimizer=OptimizerConfig(
                        lr=1e-3, warmup_steps=2, total_steps=30)))
    step_fn = jax.jit(make_train_step(cfg, run))

    def batch_fn(step):
        x, y = batch_for_step(cfg, shape, step)
        return jnp.asarray(x), jnp.asarray(y)

    return cfg, run, step_fn, batch_fn


# ------------------------------------------------------- kill phases

def test_kill_phases_recover_bitwise(tmp_path):
    """Mid-forward, mid-backward, and mid-checkpoint-write kills plus a
    straggler delay: the recovered run's loss bits and mask digests are
    identical to the uninterrupted reference, the failed save is counted
    separately from restarts, and every replayed step reproduces its
    original bits."""
    cfg, run, step_fn, batch_fn = _setup()
    plan = plan_from_config(run.dropout)
    sched = compile_run_schedule(cfg, run)
    contract = contract_from_schedule(cfg, sched)
    n_steps = 12
    shape = run.shape

    def recorder():
        return TrajectoryRecorder(plan, shape.global_batch, cfg.n_heads,
                                  shape.seq_len, shape.seq_len)

    # uninterrupted reference
    ref = recorder()
    rec_step = ref.wrap_step(step_fn)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    for s in range(n_steps):
        state, _ = rec_step(state, *batch_fn(s))
    ref_master = state["master"]

    # chaotic run: delay@3 (straggler), forward-kill@5, backward-kill@7
    # (both after the checkpoint at 4), async-write-kill@8
    rec = recorder()
    monkey = ChaosMonkey((Fault(3, "delay", delay_s=1.0),
                          Fault(5, "forward"), Fault(7, "backward")))
    ckpt = ChaosCheckpointer(str(tmp_path), kill_steps={8},
                             async_save=True)
    detector = StragglerDetector(window=16, k=4.0, warmup=2)
    state2 = init_train_state(jax.random.PRNGKey(0), cfg)
    runner = TrainRunner(monkey.wrap_step(rec.wrap_step(step_fn)),
                         state2, batch_fn, ckpt, checkpoint_every=4,
                         max_restarts=5, straggler=detector,
                         contract=contract, model_cfg=cfg,
                         schedule=sched)
    report = runner.run(n_steps)

    assert report.steps_completed == n_steps
    assert report.restarts == 2                  # forward + backward
    assert report.failed_saves == 1              # ckpt-write, uncharged
    assert ckpt.killed_writes == [8]
    assert monkey.injected == [(3, "delay"), (5, "forward"),
                               (7, "backward")]
    assert not monkey.pending
    assert report.straggler_steps >= 1           # the delayed step
    assert rec.replays >= 1                      # recovery re-ran steps
    # the bitwise acceptance: same steps, same loss bits, same mask bits
    ref.assert_identical(rec)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        ref_master, runner.state["master"])


def test_killed_write_never_publishes_partial(tmp_path):
    """Atomicity under the injected mid-write kill: the tmp file exists,
    no ckpt_<step>.npz was published, and latest_step still points at
    the previous checkpoint."""
    ckpt = ChaosCheckpointer(str(tmp_path), kill_steps={8},
                             async_save=False)
    state = {"step": jnp.asarray(4, jnp.int32), "w": jnp.ones((3,))}
    ckpt.save(4, state)
    ckpt.save(8, {**state, "step": jnp.asarray(8, jnp.int32)})
    from repro.checkpoint import CheckpointWriteError
    with pytest.raises(CheckpointWriteError, match="never published"):
        ckpt.wait()
    assert ckpt.latest_step() == 4
    assert os.path.exists(os.path.join(str(tmp_path), "tmp.8"))
    assert not os.path.exists(
        os.path.join(str(tmp_path), "ckpt_8.npz"))


# ------------------------------------------------------- the contract

def _contract(seed=0, site="qkv", batch=2):
    cfg = get_arch("llama2-7b", reduced=True)
    plan = DropoutPlanConfig(mode="overlap", p=0.1, seed=seed, site=site)
    sched = compile_schedule(cfg, plan, batch, 128, attn_impl="pallas")
    return cfg, sched, contract_from_schedule(cfg, sched)


def test_contract_roundtrip_verified():
    _, _, c = _contract()
    c2 = DropoutContract.from_json(c.to_json())
    assert c2 == c
    assert verify_resume(c2, c) == "verified"


def test_contract_identity_mismatch_fails_fast():
    """Seed drift changes every mask bit — refuse, naming the field."""
    _, _, saved = _contract(seed=0)
    _, _, cur = _contract(seed=1)
    with pytest.raises(ContractMismatchError) as ei:
        verify_resume(saved, cur)
    msg = str(ei.value)
    assert "seed" in msg and "checkpoint=0" in msg and "run=1" in msg
    assert "different mask bits" in msg.lower()


def test_contract_realization_drift_needs_proof():
    """A site change produces the same bits from a different producer:
    legal, but only with the new schedule re-proven by repro.analysis;
    without the proof inputs the restore refuses."""
    _, _, saved = _contract(site="qkv")
    cfg, sched, cur = _contract(site="ffn_up")
    with pytest.raises(ContractMismatchError, match="realization"):
        verify_resume(saved, cur)
    assert verify_resume(saved, cur, cfg=cfg, sched=sched) == \
        "recompiled"


def test_contract_reshard_recompile_lints_per_topology():
    """The elastic path: a checkpoint saved unsharded restores onto
    2-way data- and model-axis topologies — same mask identity, drifted
    realization — and each new schedule (including the N-dim-sharded
    host GEMM) lints clean through the recompile path."""
    from repro.analysis.lint import topology_shards
    cfg = get_arch("llama2-7b")
    plan = DropoutPlanConfig(mode="overlap", p=0.1, site="qkv")
    sched1 = compile_schedule(cfg, plan, 8, 1024, attn_impl="pallas")
    saved = contract_from_schedule(cfg, sched1)
    for shard in topology_shards(2):
        sched2 = compile_schedule(cfg, plan, 8, 1024,
                                  attn_impl="pallas", shard=shard)
        assert sched2.shard.active
        cur = contract_from_schedule(cfg, sched2)
        assert cur.realization["shards"] != saved.realization["shards"]
        assert verify_resume(saved, cur, cfg=cfg, sched=sched2) == \
            "recompiled"


def test_runner_contract_mismatch_fails_fast(tmp_path):
    """Recovery restores a checkpoint whose contract names a different
    seed: TrainRunner must raise ContractMismatchError instead of
    silently resuming under different mask bits."""
    cfg, sched, saved = _contract(seed=0)
    _, _, current = _contract(seed=1)
    ckpt = Checkpointer(str(tmp_path), async_save=False)
    state = {"step": jnp.asarray(4, jnp.int32), "w": jnp.ones((3,))}
    ckpt.save(4, state, contract=saved)

    def step_fn(st, x, y):
        if int(st["step"]) == 5:
            raise RuntimeError("injected crash")
        return ({**st, "step": st["step"] + 1},
                {"loss": jnp.float32(0.0)})

    runner = TrainRunner(
        step_fn, dict(state), lambda s: (jnp.zeros(()), jnp.zeros(())),
        ckpt, checkpoint_every=100, max_restarts=3, contract=current,
        model_cfg=cfg, schedule=sched)
    with pytest.raises(ContractMismatchError, match="seed"):
        runner.run(8)
    assert runner.restarts == 1     # the crash, not the contract check


# --------------------------------------------------- elastic re-mesh

_REMESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys, tempfile
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import Checkpointer, contract_from_schedule, \
    verify_resume
from repro.config.base import (AttentionKind, DropoutPlanConfig,
    ModelConfig, OptimizerConfig, RunConfig, ShapeConfig,
    ShardingConfig, StepKind, TrainConfig)
from repro.core import producer
from repro.core.overlap import plan_from_config
from repro.data import batch_for_step
from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.kernels.ref import philox_mask_ref
from repro.launch.mesh import auto_mesh
from repro.kernels.philox_common import shard_plane_windows
from repro.train.loop import (compile_run_schedule, init_train_state,
    make_train_step)

P_, SEED_ = 0.25, 5
B, S = 2, 128
cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64,
                  head_dim=32, block_pattern=(AttentionKind.FULL,),
                  attn_dropout=P_)
shape = ShapeConfig("remesh", seq_len=S, global_batch=B,
                    kind=StepKind.TRAIN)
run = RunConfig(model=cfg, shape=shape,
    dropout=DropoutPlanConfig(mode="overlap", p=P_, seed=SEED_,
                              site="qkv"),
    sharding=ShardingConfig(remat="block", attn_impl="pallas"),
    train=TrainConfig(optimizer=OptimizerConfig(
        lr=1e-3, warmup_steps=2, total_steps=20)))

def batch_fn(step):
    x, y = batch_for_step(cfg, shape, step)
    return jnp.asarray(x), jnp.asarray(y)

mesh_model = auto_mesh((2,), ("model",))
policy = ShardingPolicy(mesh_model)
plan = plan_from_config(run.dropout)

# ---- 1) per-shard mask tiles == global mask, bitwise; host GEMM N-dim
#         sharded over the model axis (distinct column slices, no
#         redundant recompute)
want = philox_mask_ref(B, cfg.n_heads, S, S, P_,
                       int(plan.step_seed(7)), int(plan.salt(1)))
x2d = jax.random.normal(jax.random.PRNGKey(0), (B * S, 64))
w = jax.random.normal(jax.random.PRNGKey(1), (64, 192))
# XLA's CPU dot does not keep its bits when N is split, so the sharded
# GEMM is held bitwise to the same kernel run on each shard's N-slice
y_ref = np.concatenate([np.asarray(producer.gemm_with_mask(
    x2d, w[:, c:c + 96], plan, (B, cfg.n_heads, S, S), 1, 7)[0])
    for c in (0, 96)], axis=1)
y, mask, how = producer.gemm_with_mask(
    x2d, w, plan, (B, cfg.n_heads, S, S), 1, 7,
    how=producer.HOW_GEMM, policy=policy)
assert how == producer.HOW_GEMM, how
want_np = np.asarray(want)
np.testing.assert_array_equal(np.asarray(mask), want_np)
np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
# the GEMM result's columns live on the model axis: each shard computed
# its own N-slice (the PR 3 follow-on: previously replicated)
assert tuple(y.sharding.spec) == (None, "model"), y.sharding.spec
# each device's mask shard is exactly its shard_plane_windows tile of
# the global plane, bit for bit
wins = set(shard_plane_windows(B, cfg.n_heads, 1, 2))
got = set()
for sh in mask.addressable_shards:
    bs, hs = sh.index[0], sh.index[1]
    b0, h0 = bs.start or 0, hs.start or 0
    b_loc = (bs.stop if bs.stop is not None else B) - b0
    h_loc = (hs.stop if hs.stop is not None else cfg.n_heads) - h0
    got.add((b0 * cfg.n_heads + h0, b_loc, h_loc))
    np.testing.assert_array_equal(np.asarray(sh.data),
                                  want_np[sh.index])
assert got == wins, (got, wins)

# ---- 2) elastic 1-dev -> 2-dev -> 1-dev training with contract gates
step1 = jax.jit(make_train_step(cfg, run))
sched1 = compile_run_schedule(cfg, run)
c1 = contract_from_schedule(cfg, sched1)
step2 = jax.jit(make_train_step(cfg, run, policy=policy))
sched2 = compile_run_schedule(cfg, run, policy=policy)
c2 = contract_from_schedule(cfg, sched2)
assert sched2.shard.head_shards == 2 and sched2.sharded

N1, N2, N3 = 4, 8, 10
state = init_train_state(jax.random.PRNGKey(0), cfg)
ref_losses = []
for s in range(N3):
    state, m = step1(state, *batch_fn(s))
    ref_losses.append(float(m["loss"]))
ref_final = state["master"]

d = tempfile.mkdtemp()
ckpt = Checkpointer(d, async_save=False)
state = init_train_state(jax.random.PRNGKey(0), cfg)
losses = []
for s in range(N1):
    state, m = step1(state, *batch_fn(s))
    losses.append(float(m["loss"]))
ckpt.save(N1, state, contract=c1)

# restore the 1-dev checkpoint onto the 2-dev mesh: identity matches,
# realization drifted -> the new schedule must lint clean (MS-C4 etc)
saved = ckpt.load_contract(ckpt.latest_step())
assert verify_resume(saved, c2, cfg=cfg, sched=sched2) == "recompiled"
repl = jax.tree.map(lambda _: NamedSharding(mesh_model, P()), state)
state = ckpt.restore(N1, state, shardings=repl)
for s in range(N1, N2):
    with use_policy(policy):
        state, m = step2(state, *batch_fn(s))
    losses.append(float(m["loss"]))
ckpt.save(N2, state, contract=c2)

# and back: 2-dev checkpoint onto the single device
saved = ckpt.load_contract(N2)
assert verify_resume(saved, c1, cfg=cfg, sched=sched1) == "recompiled"
state = ckpt.restore(N2, state)
for s in range(N2, N3):
    state, m = step1(state, *batch_fn(s))
    losses.append(float(m["loss"]))

# masks are bitwise (proven above); float loss/params get a tight
# allclose — GSPMD reassociates sharded-contraction reductions, so
# cross-topology float sums differ in the last ulps
np.testing.assert_allclose(np.array(losses), np.array(ref_losses),
                           rtol=2e-5, atol=2e-5)
jax.tree.map(lambda a, b: np.testing.assert_allclose(
    np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5),
    ref_final, state["master"])
print("REMESH-OK")
"""


@pytest.mark.slow
def test_elastic_remesh_1_to_2_dev():
    """Acceptance: a 1-device checkpoint restores onto a 2-device
    model-axis mesh (and back) through the contract's recompile-and-lint
    gate; per-shard mask tiles are bitwise-identical to the global mask
    and the host GEMM's N dim is sharded over the model axis
    (subprocess: the main test process must stay single-device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", _REMESH_SCRIPT], env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=1200)
    assert "REMESH-OK" in proc.stdout, (
        proc.stdout[-3000:], proc.stderr[-3000:])
