"""One run of one cell: set-up, checked steps, the measured window, the
per-layer readings, and the comparison with the reference.

The flow (``run_cell``):

1. build the trainer's step for the cell (``bench.job``), its state from
   the seed in one jitted call, and compile the step ahead of time (from
   the persistent cache after a cell's first run);
2. drive that one compiled step through the cell's checked steps, through
   the same call and feed as the window, and keep the readings the
   reference is compared with;
3. measure: call the step on the next batch, read ``skipped`` and the loss
   back to the host (as ``train_loop`` does), until ``seconds`` have passed;
   with ``trace`` the same loop runs under the profiler instead;
4. read the device's peak memory, free the trainer's state, run the
   reference over the same steps and judge.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import shutil
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench import compare, feed, hlo, job as job_mod, reference, spec, trace as trace_mod
from bench import weights

PEAKS = Path(__file__).resolve().parent / "peaks.json"
MAX_TRACED_STEPS = 12


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise spec.SpecError(f"no peaks for device kind {device_kind!r} in "
                             f"bench/peaks.json")
    return table[device_kind]


def devices_for(chips: int, platform: str = "tpu"):
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"JAX found {devs[0].platform}, not {platform}; no fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


@dataclasses.dataclass
class Run:
    """What the per-layer readers read."""

    workload: str
    cfg: dict
    traffic: dict
    chips: int
    device_kind: str
    global_batch: int
    seq: int
    param_count: int
    hlo_text: str
    step_bytes: int
    steps: int = 0  # steps in the traced window
    trace: Optional[trace_mod.Trace] = None
    window_ns: tuple = (0.0, 0.0)

    @property
    def peaks(self) -> dict:
        return peaks(self.device_kind)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def device_events(self, with_async: bool = False):
        """Each chip's operation events inside the traced window (and its
        asynchronous operations with ``with_async``)."""
        lo, hi = self.window_ns
        out = []
        for plane, evs in sorted(self.trace.devices.items()):
            if with_async:
                evs = evs + self.trace.async_ops.get(plane, [])
            out.append([e for e in evs if e.end_ns > lo and e.start_ns < hi])
        return out


def _leaf_norms_fn():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
         for x in jax.tree_util.tree_leaves(t)]))


@dataclasses.dataclass
class Prepared:
    """A cell's compiled step and feed, ready for any seed."""

    workload: str
    cfg: dict
    traffic: dict
    chips: int
    devs: list
    job: object
    compiled: object
    batches: object
    hlo_text: str
    step_bytes: int


def prepare(workload: str, platform: str = "tpu") -> Prepared:
    """Build the trainer's step for the cell and compile it ahead of time
    (from the persistent cache after a checkout's first run)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    w = spec.workload(workload)
    cfg, traffic = spec.config(w["config"]), spec.traffic(w["traffic"])
    chips = w["chips"]
    devs = devices_for(chips, platform)
    job = job_mod.build(cfg, traffic, Mesh(np.array(devs), ("data",)))
    batches = jax.jit(feed.batch_fn(traffic, cfg["vocab_size"], job.global_batch),
                      out_shardings=job.step.batch_sharding)
    shaped = job_mod.abstract_state(job, NamedSharding(job.mesh, P()))
    batch = jax.eval_shape(batches, weights.base_key(0), 0)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=job.step.batch_sharding)
             for k, v in batch.items()}
    compiled = job.step.lower(shaped, batch).compile()
    hlo_text = compiled.as_text()
    compressed = traffic["exchange"]["reducer"] != "dense"
    if compressed and platform == "tpu" and hlo.custom_call_count(hlo_text) == 0:
        raise RuntimeError("the compressed step holds no Pallas kernel")
    return Prepared(workload, cfg, traffic, chips, devs, job, compiled, batches,
                    hlo_text, hlo.step_bytes(compiled.memory_analysis()))


def checked_steps(p: Prepared, seed: int) -> tuple:
    """The state from ``seed`` driven through the cell's checked steps, by
    the same compiled step and feed as the window: (state, key, readings)."""
    import jax

    key = weights.base_key(seed)
    state = job_mod.state_fn(p.job)(key)
    norms = _leaf_norms_fn()
    b1 = p.cfg["training"]["optimizer"]["b1"]
    change = jax.jit(lambda params, k: norms(jax.tree_util.tree_map(
        lambda a, b: a - b, params, weights.init(p.cfg, k))))
    ours = {"loss": []}
    for s in range(p.traffic["checked_steps"]):
        state, m = p.compiled(state, p.batches(key, s))
        loss, skipped = (float(v) for v in jax.device_get((m["loss"], m["skipped"])))
        if skipped or not math.isfinite(loss):
            raise RuntimeError(f"checked step {s}: skipped {skipped}, loss {loss}")
        ours["loss"].append(loss)
        if s == 0:
            ours["grad_norms"] = np.asarray(norms(state["opt"]["mu"]), np.float64) / (1 - b1)
    ours["change_norms"] = np.asarray(change(state["params"], key), np.float64)
    return state, key, ours


def free(state) -> None:
    import jax

    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, platform: str = "tpu") -> dict:
    import jax

    p = prepare(workload, platform)
    cfg, traffic, chips, devs, job = p.cfg, p.traffic, p.chips, p.devs, p.job
    lim = spec.limits(workload)
    compiled, batches = p.compiled, p.batches
    state, key, ours = checked_steps(p, seed)
    n_checked = traffic["checked_steps"]

    setup_s = time.perf_counter() - t_start
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    span = (jax.profiler.TraceAnnotation if trace
            else lambda name: contextlib.nullcontext())
    steps, failed = 0, 0
    profiler = jax.profiler.trace(trace_dir) if trace else contextlib.nullcontext()
    with profiler:
        with span("bench.window"):
            t0 = time.perf_counter()
            s = n_checked
            while True:
                with span("bench.batch"):
                    batch = batches(key, s)
                with span("bench.step"):
                    state, m = compiled(state, batch)
                with span("bench.sync"):
                    loss, skipped = (float(v) for v in
                                     jax.device_get((m["loss"], m["skipped"])))
                failed += int(bool(skipped) or not math.isfinite(loss))
                steps += 1
                s += 1
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds or (trace and steps >= MAX_TRACED_STEPS):
                    break
    window_s = elapsed
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)

    run = Run(workload, cfg, traffic, chips, devs[0].device_kind, job.global_batch,
              job.seq, sum(x.size for x in jax.tree_util.tree_leaves(state["params"])),
              p.hlo_text, p.step_bytes, steps)
    free(state)
    del state, compiled, p

    in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs)
    result = {"attempted": steps, "failed": failed}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": int(peak)}
    if trace:
        try:
            run.trace = trace_mod.load(trace_dir, f"/device:{platform.upper()}:")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.window_ns = run.trace.window()
        per_chip = run.device_events()
        lo, hi = run.window_ns
        busy = [trace_mod.busy_ns(evs, lo, hi) * 1e-9 for evs in per_chip]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = run.window_s
        metrics = {}
        for m in spec.per_layer_metrics(workload):
            value = spec.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [list(x) for x in trace_mod.top_ops(per_chip[0])] if per_chip else [],
            "idle_gaps": [list(x) for x in trace_mod.idle_gaps(
                per_chip[0], lo, hi, run.trace.host)[:10]] if per_chip else [],
        }
    else:
        metrics = {
            "tokens_per_s": {"value": steps * job.tokens_per_step / window_s,
                             "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    t_ref = time.perf_counter()
    ref = reference.run(cfg, traffic, seed, chips, steps=n_checked, devices=devs)
    timings = {"setup_s": setup_s, "window_s": window_s, "steps": steps,
               "reference_s": time.perf_counter() - t_ref,
               "bytes_in_use_before_reference": int(in_use)}
    nums = compare.numbers(ours, ref, reference.leaf_names(cfg))
    ok, checks = compare.judge(nums, lim)
    result.update(correct=bool(ok and failed == 0), metrics=metrics, device=device,
                  timings=timings,
                  readings={"trainer": _plain(ours), "reference": _plain(ref),
                            "worst_leaf": {k: nums[k].get("leaf") for k in compare.NUMBERS}},
                  checks=checks)
    return result


def _plain(readings: dict) -> dict:
    return {k: [float(x) for x in np.asarray(v).ravel()] for k, v in readings.items()}
