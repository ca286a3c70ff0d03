"""Training loop: convergence on learnable data, checkpoint/restart,
failure-injection recovery (DESIGN.md §5)."""

import os

import jax
import numpy as np
import pytest
from jax.errors import JaxRuntimeError

from repro import jaxcompat as compat
from repro.comms.faults import FaultPlan, StepCrash
from repro.configs.base import ArchConfig
from repro.data import SyntheticConfig, SyntheticStream
from repro.launch.mesh import make_local_mesh
from repro.models.transformer import LM
from repro.optim import OptConfig, lr_schedules
from repro.train import TrainLoopConfig, init_state, train_loop
from repro.train import checkpoint as ckpt
from repro.train.step import StepConfig

TINY = ArchConfig(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64, remat="none",
)


def _stream(batch=8, seq=32, vocab=64):
    return SyntheticStream(SyntheticConfig(
        vocab_size=vocab, seq_len=seq, global_batch=batch, kind="markov"))


def test_loss_decreases_on_markov_data(tmp_path):
    model = LM(TINY)
    opt = OptConfig(kind="adamw", lr=3e-3)
    step_cfg = StepConfig(mode="pjit")
    mesh = make_local_mesh()
    state = init_state(jax.random.PRNGKey(0), model, opt)
    stream = _stream()
    loop_cfg = TrainLoopConfig(total_steps=60, log_every=5,
                               lr_schedule=lr_schedules.constant())
    with compat.set_mesh(mesh):
        out = train_loop(model, opt, step_cfg, mesh, state, stream, loop_cfg)
    hist = out["history"]
    first, last = hist[0]["loss"], hist[-1]["loss"]
    floor = np.log(TINY.vocab_size)
    assert last < first - 0.3, f"no learning: {first:.3f} -> {last:.3f}"
    assert last < floor  # better than uniform guessing


def test_checkpoint_save_restore_exact(tmp_path):
    model = LM(TINY)
    opt = OptConfig(kind="adamw", lr=1e-3)
    state = init_state(jax.random.PRNGKey(1), model, opt)
    path = str(tmp_path / "ck")
    ckpt.save(path, 7, state)
    assert ckpt.latest_step(path) == 7
    restored, step = ckpt.restore(path, state)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.array(a), np.array(b))


def test_resume_is_bit_exact(tmp_path):
    """Train 20 straight vs 10 + restart + 10: identical final params."""
    mesh = make_local_mesh()
    model = LM(TINY)
    opt = OptConfig(kind="adamw", lr=1e-3)
    stream = _stream()
    step_cfg = StepConfig(mode="pjit")

    def fresh_state():
        return init_state(jax.random.PRNGKey(2), model, opt)

    with compat.set_mesh(mesh):
        out_straight = train_loop(
            model, opt, step_cfg, mesh, fresh_state(), stream,
            TrainLoopConfig(total_steps=20, log_every=100))

        ck = str(tmp_path / "resume")
        train_loop(model, opt, step_cfg, mesh, fresh_state(), stream,
                   TrainLoopConfig(total_steps=10, ckpt_dir=ck, ckpt_every=10,
                                   log_every=100))
        out_resumed = train_loop(
            model, opt, step_cfg, mesh, fresh_state(), stream,
            TrainLoopConfig(total_steps=20, ckpt_dir=ck, ckpt_every=10,
                            log_every=100))

    a = jax.tree_util.tree_leaves(out_straight["state"]["params"])
    b = jax.tree_util.tree_leaves(out_resumed["state"]["params"])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.array(x), np.array(y))


def test_failure_injection_rolls_back(tmp_path):
    """A step that blows up mid-run recovers from the last checkpoint and
    completes (fleet-scale requirement: node failure != job failure).  The
    crash is a typed FaultPlan event (DESIGN.md §19) — it fires exactly
    once, the loop rolls back to the newest checkpoint, and the retried
    run finishes."""
    mesh = make_local_mesh()
    model = LM(TINY)
    opt = OptConfig(kind="adamw", lr=1e-3)
    stream = _stream()
    plan = FaultPlan(events=(StepCrash(step=12),))

    loop_cfg = TrainLoopConfig(total_steps=16, ckpt_dir=str(tmp_path / "fi"),
                               ckpt_every=5, log_every=100, faults=plan)
    with compat.set_mesh(mesh):
        out = train_loop(
            model, opt, StepConfig(mode="pjit"), mesh,
            init_state(jax.random.PRNGKey(3), model, opt), stream, loop_cfg)
    assert int(out["state"]["step"]) == 16
    assert loop_cfg.fired_faults == {0}  # the crash fired exactly once


def test_checkpoint_gc_keeps_last_k(tmp_path):
    model = LM(TINY)
    opt = OptConfig(kind="sgd")
    state = init_state(jax.random.PRNGKey(4), model, opt)
    mgr = ckpt.CheckpointManager(str(tmp_path / "gc"), every=1, keep=2)
    for s in range(1, 6):
        mgr.maybe_save(s, state)
    kept = sorted(os.listdir(str(tmp_path / "gc")))
    assert kept == ["step_00000004", "step_00000005"]


@pytest.mark.parametrize("error", [
    JaxRuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying to "
                    "allocate 17179869184 bytes."),
    JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: "
                    "unsupported shape cast"),
    ValueError("The Pallas TPU lowering currently requires that the last "
               "two dimensions of your block shape are divisible by 8 and 128"),
], ids=["out_of_memory", "compile_runtime", "compile_lowering"])
def test_device_errors_propagate_without_retry_or_degradation(
        monkeypatch, error):
    """Out-of-memory and compile failures are deterministic: the step error
    surfaces on its first occurrence, with no retry and no ladder rung, even
    for a compressed exchange that has rungs left to walk."""
    from repro.comms.reducers import ReducerConfig
    from repro.train import loop as loop_mod

    calls = []

    def failing_build(*args, **kwargs):
        def step(state, batch):
            calls.append(1)
            raise error
        return step

    monkeypatch.setattr(loop_mod, "build_train_step", failing_build)
    model = LM(TINY)
    opt = OptConfig(kind="adamw", lr=1e-3)
    step_cfg = StepConfig(mode="compressed_dp", reducer=ReducerConfig(
        kind="fft", axis="data", backend="pallas"))
    mesh = make_local_mesh()
    with compat.set_mesh(mesh), pytest.raises(type(error)) as got:
        train_loop(model, opt, step_cfg, mesh,
                   init_state(jax.random.PRNGKey(5), model, opt), _stream(),
                   TrainLoopConfig(total_steps=4, log_every=100))
    assert got.value is error
    assert len(calls) == 1  # never retried, never rebuilt on a lower rung
