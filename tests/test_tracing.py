"""The program's own tracing: layer scopes in the train step's HLO, the
runner's spans inside the profiler's step markers, and the per-step
Philox draw counter."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.counters import (
    draw_counts,
    explain_draws,
    live_draw_share,
)
from repro.config.base import AttentionKind, DropoutPlanConfig, ModelConfig
from repro.core.schedule import compile_schedule

SCOPES = ("embed", "layers", "attn", "ffn", "mask", "unembed", "loss",
          "optimizer")


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=3, d_model=64,
                n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64,
                head_dim=32, block_pattern=(AttentionKind.FULL,),
                attn_dropout=0.25)
    base.update(kw)
    return ModelConfig(**base)


def _sched(cfg, site, replay, batch=2, seq=256):
    plan = DropoutPlanConfig(mode="overlap", p=0.25, seed=5, site=site,
                             attn_replay=replay)
    return compile_schedule(cfg, plan, batch, seq, attn_impl="pallas")


# ---------------------------------------------------------------- scopes

def _scope_forms(hlo_text):
    """{(scope, "fwd" | "bwd")} found in the HLO's op_name metadata."""
    forms = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo_text):
        direction = "bwd" if "transpose(" in op_name else "fwd"
        for s in SCOPES:
            if re.search(rf"(^|/)(\w+\()*{s}\)*(/|$)", op_name):
                forms.add((s, direction))
    return forms


def test_train_step_hlo_carries_every_scope():
    """Every layer scope shows in the compiled step, forward under
    jvp(...) and backward under transpose(jvp(...)); the bootstrap mask
    and the optimizer are not differentiated, so they show forward only."""
    from repro.config import (OptimizerConfig, RunConfig, ShapeConfig,
                              ShardingConfig, StepKind, TrainConfig)
    from repro.train.loop import init_train_state, make_train_step
    cfg = _cfg(n_layers=2)
    run = RunConfig(
        model=cfg,
        shape=ShapeConfig("t", seq_len=128, global_batch=1,
                          kind=StepKind.TRAIN),
        sharding=ShardingConfig(remat="none", attn_impl="xla"),
        # a materialized prev_gemm plan: the first layer's mask comes
        # from the bootstrap producer
        dropout=DropoutPlanConfig(mode="overlap", p=0.25, seed=5,
                                  site="prev_gemm", attn_replay="off"),
        train=TrainConfig(optimizer=OptimizerConfig(lr=1e-3)))
    state = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg))
    ids = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    text = jax.jit(make_train_step(cfg, run)).lower(
        state, ids, ids).compile().as_text()
    forms = _scope_forms(text)
    for s in SCOPES:
        assert (s, "fwd") in forms, s
    for s in ("embed", "layers", "attn", "ffn", "unembed", "loss"):
        assert (s, "bwd") in forms, s
    assert ("optimizer", "bwd") not in forms


# ----------------------------------------------------------------- spans

class _MemoryCheckpointer:
    def __init__(self):
        self.saved = {}

    def save(self, step, state):
        self.saved[step] = state

    def wait(self):
        pass

    def latest_step(self):
        return max(self.saved) if self.saved else None

    def restore(self, step, like):
        return self.saved[step]


def test_runner_spans_nest_in_step_markers(tmp_path):
    """A CPU profiler trace of a TrainRunner: each step is a ``train``
    marker with its step number, and the runner's spans lie inside the
    markers; the read of the state's step and the report lie outside."""
    from jax.profiler import ProfileData
    from repro.distributed.fault import TrainRunner

    step_fn = jax.jit(lambda s, x, y: (
        {"w": s["w"] - 0.1 * x, "step": s["step"] + 1},
        {"loss": jnp.sum(s["w"] * y)}))
    failed = []

    def failure_hook(step):
        if step == 3 and not failed:
            failed.append(step)
            raise RuntimeError("injected")

    ones = jnp.ones(4)
    runner = TrainRunner(step_fn, {"w": ones, "step": jnp.int32(0)},
                         lambda i: (ones, ones), _MemoryCheckpointer(),
                         checkpoint_every=2, failure_hook=failure_hook)
    runner.run(1)                            # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    report = runner.run(5)
    jax.profiler.stop_trace()
    assert report.steps_completed == 5 and report.restarts == 1

    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "train" or e.name.startswith("runner.")]
    markers = [e for e in events if e[0] == "train"]
    # steps 1, 2, 3 (crashed, recovered from the checkpoint at 2), 2, 3, 4
    assert [m[3]["step_num"] for m in markers] == [1, 2, 3, 2, 3, 4]
    spans = [e for e in events if e[0] != "train"]
    names = {s[0] for s in spans}
    assert {"runner.sync", "runner.batch", "runner.step", "runner.wait",
            "runner.loss", "runner.checkpoint", "runner.recover",
            "runner.report"} <= names

    def inside(s):
        return any(m[1] <= s[1] and s[2] <= m[2] for m in markers)

    for s in spans:
        assert inside(s) == (s[0] not in ("runner.sync", "runner.report")), s
    recover = [s for s in spans if s[0] == "runner.recover"]
    (crashed,) = [m for m in markers if m[1] <= recover[0][1] <= m[2]]
    assert crashed[3]["step_num"] == 3


# --------------------------------------------------------------- counter

PLANE = 2 * 2 * 256 * 256          # batch x heads x seq x seq
CAUSAL = 3 * PLANE // 4            # 128-blocks: 3 of each head's 4 tiles


def test_draws_replay_prev_gemm():
    """Replay under a prev_gemm host: each flash kernel re-derives every
    layer's bits over the tiles its causal grid runs; the host GEMM draws
    each emission's whole plane, and nothing reads it."""
    cfg = _cfg()
    counts = {c.kernel: c for c in draw_counts(cfg, _sched(
        cfg, "prev_gemm", "auto"))}
    for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert (counts[k].calls, counts[k].live, counts[k].dropped) == (
            3, 3 * CAUSAL, 0)
    g = counts["gemm_rng"]
    assert (g.calls, g.live, g.dropped) == (3, 0, 3 * PLANE)
    assert sum(c.live for c in counts.values()) == 3 * 3 * CAUSAL
    assert live_draw_share(tuple(counts.values())) == pytest.approx(
        9 * CAUSAL / (9 * CAUSAL + 3 * PLANE))
    # a forward-only step re-derives the bits once
    fwd = {c.kernel: c for c in draw_counts(
        cfg, _sched(cfg, "prev_gemm", "auto"), train=False)}
    assert set(fwd) == {"flash_fwd", "gemm_rng"}
    assert "live share" in explain_draws(tuple(counts.values()))


@pytest.mark.parametrize("site, kernel", [("qkv", "gemm_rng"),
                                          ("xla", "xla")])
def test_draws_premask_drop_nothing(site, kernel):
    """A materialized plane drawn in its own layer is read: nothing is
    dropped, and the flash kernels draw nothing."""
    cfg = _cfg()
    (c,) = draw_counts(cfg, _sched(cfg, site, "off"))
    assert (c.kernel, c.calls, c.live, c.dropped) == (kernel, 3,
                                                      3 * PLANE, 0)
    assert live_draw_share((c,)) == 1.0


def test_draws_premask_prev_gemm_drops_the_tail():
    """A carried premask plan bootstraps layer 0 with the standalone
    kernel; the last layer's emission has no consumer."""
    cfg = _cfg()
    counts = {c.kernel: c for c in draw_counts(cfg, _sched(
        cfg, "prev_gemm", "off"))}
    assert (counts["philox_mask"].calls, counts["philox_mask"].live) == (
        1, PLANE)
    g = counts["gemm_rng"]
    assert (g.calls, g.live, g.dropped) == (3, 2 * PLANE, PLANE)


def test_draws_skip_tiles_outside_the_window():
    """A sliding-window layer's replay skips the tiles wholly before its
    window as well as those after the diagonal."""
    full, local = _cfg(n_layers=2), _cfg(
        n_layers=2, local_window=64,
        block_pattern=(AttentionKind.LOCAL, AttentionKind.FULL))
    per_kernel = {}
    for name, cfg in (("full", full), ("local", local)):
        counts = draw_counts(cfg, _sched(cfg, "qkv", "auto", batch=1,
                                         seq=512))
        per_kernel[name] = {c.kernel: c.live for c in counts}["flash_fwd"]
    # a full layer runs 256-blocks at 512: 3 causal tiles a head. The
    # window caps the local layer's tiles at 128: of 4 query blocks' 10
    # causal tiles a head it drops 3 (keys 0-127 for queries 384-511 and
    # 256-383, keys 128-255 for queries 384-511)
    tile = 128 * 128
    assert per_kernel["full"] == 2 * 2 * 3 * 4 * tile
    assert per_kernel["local"] == 2 * (3 * 4 + 7) * tile


def test_draws_inert_schedule():
    cfg = _cfg(attn_dropout=0.0)
    plan = DropoutPlanConfig(mode="none")
    sched = compile_schedule(cfg, plan, 2, 256, attn_impl="pallas")
    assert draw_counts(cfg, sched) == ()
    assert live_draw_share(()) is None
    assert explain_draws(()) == "dropout draws a step: none"


@pytest.mark.parametrize("seq, n_blk", [(1536, 3), (4096, 8)])
def test_draws_follow_the_rule_tiles(seq, n_blk):
    """At the cells' lengths the flash kernels run 512 x 512 tiles: each
    kernel's live draws are its run tiles x 512 x 512, the line names the
    tile and the grid steps, and the live share is 3a / (3a + P) for the
    causal area a and the plane P that the dropped host draws whole."""
    cfg = _cfg(n_heads=1, n_kv_heads=1, n_layers=2)
    counts = {c.kernel: c for c in draw_counts(cfg, _sched(
        cfg, "prev_gemm", "auto", batch=1, seq=seq))}
    run = n_blk * (n_blk + 1) // 2         # causal tiles a head
    skipped = n_blk * n_blk - run
    for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        c = counts[k]
        assert c.tiles == ((512, 512),)
        assert (c.steps_run, c.steps_skipped) == (2 * run, 2 * skipped)
        assert c.live == c.steps_run * 512 * 512
    plane = seq * seq
    area = run * 512 * 512
    assert counts["gemm_rng"].dropped == 2 * plane
    assert live_draw_share(tuple(counts.values())) == pytest.approx(
        3 * area / (3 * area + plane))
    line = explain_draws(tuple(counts.values()))
    assert (f"flash_fwd 2 calls {2 * area / 1e6:.1f}M live 0.0M dropped "
            f"tile 512x512 grid steps {2 * run} run {2 * skipped} "
            "skipped") in line
