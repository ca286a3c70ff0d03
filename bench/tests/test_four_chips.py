"""The compressed exchange across four workers, through the harness: a tiny
cell at InternLM2's shape ratios (six query heads a key/value head, rotary
theta 1e6) with the fft all-gather exchange, on four CPU devices in a child
process.  The sound run comes out ``correct``; a run whose exchange is left
out, and one that leaves half of each worker's batch out, do not.

The cell is judged by ``tiny_limits_four_chips.json``, set from CPU readings
at this size: each worker's 8-bit codes round the trainer's bfloat16
gradient and the reference's float32 one apart now and then, and four
workers' such flips in the mean read about twice the one-worker
``grad_gap``."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec

ROOT = spec.ROOT
# InternLM2-20B's ratios at a width the CPU runs: 48 / 8 heads of 128 there
TINY_INTERNLM2 = dict(name="tiny", hidden_size=192, intermediate_size=512,
                      num_attention_heads=6, num_key_value_heads=1, head_dim=32,
                      vocab_size=512)
FAULTS = ("none", "no_exchange", "half_batch")

_FOUR_CHIPS = r'''
import json, sys, time
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness, spec
from bench.tests.conftest import make_tiny_root
root = make_tiny_root(Path({tmp!r}), chips=4)
cfg = json.loads((Path({root!r}) / "bench/configs/internlm2_20b_l1_vocab8th.json").read_text())
cfg.update({tiny!r})
(root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
(root / "bench/limits/tiny_fft.json").write_text(Path({limits!r}).read_text())
spec.BENCH_DIR, spec.ROOT, harness.PEAKS = root / "bench", root, root / "peaks.json"
from repro.comms import reducers
from repro.train import step as step_mod
real_reducer, real_grad = reducers.make_reducer, step_mod._loss_and_grad

def no_exchange(config, **kw):
    return lambda grads: grads

def half_batch(model, mesh_ctx):
    vg = real_grad(model, mesh_ctx)
    return lambda params, batch: vg(params, {{
        k: v[: v.shape[0] // 2] for k, v in batch.items()}})

out = {{}}
for fault in {faults!r}:
    step_mod.make_reducer = no_exchange if fault == "no_exchange" else real_reducer
    step_mod._loss_and_grad = half_batch if fault == "half_batch" else real_grad
    r = harness.run_cell("tiny_fft", 2**31 + 11, 0.3, False,
                         t_start=time.perf_counter(), platform="cpu")
    out[fault] = {{"correct": r["correct"], "checks": r["checks"],
                  "chips": r["device"]["count"], "attempted": r["attempted"]}}
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def four_chip_runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("four_chips")
    code = _FOUR_CHIPS.format(root=str(ROOT), src=str(ROOT / "src"),
                              tmp=str(tmp / "root"), tiny=TINY_INTERNLM2,
                              limits=str(Path(__file__).parent / "tiny_limits_four_chips.json"),
                              faults=FAULTS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", FAULTS)
def test_four_worker_exchange_is_judged(four_chip_runs, fault):
    r = four_chip_runs[fault]
    assert r["chips"] == 4 and r["attempted"] >= 1
    assert r["correct"] is (fault == "none"), r["checks"]
