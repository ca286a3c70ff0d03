"""The window and the correctness comparison on a reduced configuration,
through the harness's own functions (the command line refuses a CPU).

A sound run comes out ``correct``; so must not the control (the reference
computed in float8 in the trainer's place) nor a run whose timed path is
broken underneath: a step that returns its state unchanged, half of the
batch left out, the exchange between chips left out."""

import json
import os
import subprocess
import sys
import time

import pytest

from bench import compare, harness, reference, spec

ROOT = spec.ROOT


def run(cell, seed=2**31 + 5, trace=False):
    return harness.run_cell(cell, seed, 0.5, trace, t_start=time.perf_counter(),
                            platform="cpu")


@pytest.mark.parametrize("cell", ["tiny_fft", "tiny_dense"])
def test_sound_run_is_correct(tiny_root, cell):
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}
    assert r["metrics"]["tokens_per_s"]["value"] > 0
    assert r["device"]["count"] == 1
    assert list(r)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics(tiny_root):
    r = run("tiny_fft", trace=True)
    assert r["correct"]
    # the CPU has no device trace: only the counts from the program remain
    assert set(r["metrics"]) == {"step_mfu", "step_hbm_gib"}
    assert r["device"]["window_s"] > 0 and "breakdown" in r


def test_control_fails(tiny_root):
    cfg, traffic = spec.config("tiny"), spec.traffic("tiny_fft")
    ref = reference.run(cfg, traffic, 7, 1)
    control = reference.run(cfg, traffic, 7, 1, precision="float8")
    ok, checks = compare.judge(compare.numbers(control, ref), spec.limits("tiny_fft"))
    assert not ok, checks


def test_readings_separate_sound_runs_from_the_control(tiny_root):
    from bench import readings

    rows, summary = readings.readings("tiny_fft", [3, 2**33 + 1], 1, platform="cpu",
                                      out=lambda s: None)
    assert [r["kind"] for r in rows] == ["program", "control", "half_batch", "program"]
    lim = spec.limits("tiny_fft")
    for k in compare.NUMBERS:
        assert summary["lower"][k] <= lim[k]["limit"]
    assert any(summary["upper"][k]["control"] > lim[k]["limit"] for k in compare.NUMBERS)


def _break_state(monkeypatch):
    from repro.train import step as step_mod

    def unchanged(opt_cfg, step_cfg, state, grads, lr_scale):
        return dict(state, step=state["step"] + 1), step_mod.jnp.float32(0.0)
    monkeypatch.setattr(step_mod, "_optimizer_update", unchanged)


def _break_half_batch(monkeypatch):
    from repro.train import step as step_mod

    whole = step_mod._loss_and_grad

    def half(model, mesh_ctx):
        vg = whole(model, mesh_ctx)
        return lambda params, batch: vg(params, {
            k: v[: v.shape[0] // 2] for k, v in batch.items()})
    monkeypatch.setattr(step_mod, "_loss_and_grad", half)


@pytest.mark.parametrize("fault", [_break_state, _break_half_batch])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    r = run("tiny_fft")
    assert not r["correct"], r["checks"]


_TWO_CHIPS = r'''
import json, sys, time
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness, spec
from bench.tests.conftest import make_tiny_root
root = make_tiny_root(Path({tmp!r}), chips=2)
spec.BENCH_DIR, spec.ROOT, harness.PEAKS = root / "bench", root, root / "peaks.json"
if {broken}:
    import jax
    from repro.comms import reducers
    real = reducers.make_reducer
    def no_exchange(config, **kw):
        if config.kind == "dense":
            return lambda grads: grads
        return real(config, **kw)
    reducers.make_reducer = no_exchange
    from repro.train import step as step_mod
    step_mod.make_reducer = no_exchange
r = harness.run_cell("tiny_dense", 11, 0.3, False, t_start=time.perf_counter(),
                     platform="cpu")
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
'''


@pytest.mark.parametrize("broken", [False, True])
def test_exchange_left_out_is_not_correct(tmp_path, broken):
    code = _TWO_CHIPS.format(root=str(ROOT), src=str(ROOT / "src"),
                             tmp=str(tmp_path / "root"), broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"] is (not broken), r["checks"]


def _cli(args, cwd, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    e.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "-m", "bench.run", *args], cwd=cwd,
                          env=e, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_with_no_result():
    out = _cli(["--workload", "phi3m_fft_1chip", "--seed", str(2**31 + 9),
                "--seconds", "1", "--trace", "0"], ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no fallback" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(["--workload", "phi3m_fft_1chip", "--seed", "3", "--seconds", "1",
                "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
