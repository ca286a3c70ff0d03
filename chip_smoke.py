#!/usr/bin/env python3
"""Chip smoke run: the compressed-exchange trainer on a TPU, end to end.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip data-parallel phase only

One chip: ``gemma2_2b`` at its published widths (d_model 2304, 8 query and 4
KV heads of 256, d_ff 9216 GeGLU, softcaps, 4096 sliding window with
local/global period 2), cut in depth to 4 layers (two local/global periods)
and in vocabulary to 32,000 (an eighth) so that parameters, gradients, AdamW
state and the compress buffers fit one 16 GB chip.  It trains a few steps
through the normal entry points (``launch.train.prepare`` -> ``registry.build``
-> ``init_state`` -> ``build_train_step`` / ``train_loop``) with the FFT
compressed exchange (``--backend auto``, theta 0.7, stacked schedule) on a
``(1,)`` data mesh, then checks the fused Pallas compress/decompress against
the jnp reference backend on one gradient-sized buffer, on the chip.

Four chips: the same model on a ``(4,)`` data mesh, a few steps each with the
compressed exchange over the ``allgather`` and ``psum`` transports and with
dense ``psum`` (``--reducer dense``) as the comparison.

Every phase fails the run (non-zero exit, no result line) on: no TPU, a
non-finite loss, a step skipped by the guard, a degradation of the exchange,
no Pallas kernel in the compiled step, or a check that disagrees.  Timings
are from a smoke run, not a benchmark.  The last line of standard output is
one JSON object naming the device.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
STEPS = 4
BATCH, SEQ = 8, 1024
CUT = {"n_layers": 4, "vocab_size": 32_000}
# tolerance of the fused decompress against the reference reconstruction at
# this data scale (tests/test_engine.py::test_backend_parity_codes_bitwise)
DATA_SCALE, RECON_ATOL = 0.05, 5e-5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def cut_config(registry):
    full = registry.get_config("gemma2_2b")
    cfg = dataclasses.replace(full, **CUT)
    log(f"gemma2_2b at published widths: d_model {cfg.d_model}, "
        f"{cfg.n_heads} query / {cfg.n_kv_heads} KV heads of {cfg.head_dim}, "
        f"d_ff {cfg.d_ff} {cfg.mlp_activation}, softcaps "
        f"{cfg.attn_softcap}/{cfg.final_softcap}, window {cfg.sliding_window} "
        f"with local/global period {cfg.local_global_period}")
    log(f"cuts: n_layers {full.n_layers} -> {cfg.n_layers} (two local/global "
        f"periods); vocab {full.vocab_size} -> {cfg.vocab_size} (an eighth); "
        f"params {full.param_count() / 1e6:.1f}M -> "
        f"{cfg.param_count() / 1e6:.1f}M")
    return cfg


def train_args(train, *extra):
    return train.parse_args([
        "--arch", "gemma2_2b", "--steps", str(STEPS), "--batch", str(BATCH),
        "--seq", str(SEQ), "--mode", "compressed_dp", "--theta", "0.7",
        "--backend", "auto", "--schedule", "stacked", "--seed", str(SEED),
        *extra])


def compile_step(jax, job):
    """AOT-compile the job's training step (the loop's own compile of the
    same program then comes from the persistent cache)."""
    from repro.train.step import build_train_step

    step = build_train_step(job.model, job.opt_cfg, job.step_cfg, job.mesh,
                            job.stream.batch_at(0))
    # the exchange is named outright: no cost-model pricing picked it
    check(step.schedule_decision is None and step.transport_decision is None,
          "the step resolved its schedule or transport by pricing")
    batch = jax.device_put(job.stream.batch_at(0), step.batch_sharding)
    t0 = time.perf_counter()
    compiled = step.lower(job.state, batch).compile()
    return compiled, time.perf_counter() - t0


def hlo_count(hlo: str, op: str) -> int:
    if op == "tpu_custom_call":
        return hlo.count('custom_call_target="tpu_custom_call"')
    return sum(1 for line in hlo.splitlines() if f" {op}(" in line
               or f" {op}-start(" in line)


def train_and_check(jax, train, job, label: str):
    """Compile, train ``STEPS`` steps, and hold the run to the smoke's
    contract.  Returns (result, compiled HLO text, step times)."""
    compiled, compile_s = compile_step(jax, job)
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    n_kernels = hlo_count(hlo, "tpu_custom_call")
    log(f"{label}: step compiled in {compile_s:.3f} s (smoke timing); "
        f"tpu_custom_call x{n_kernels}, all-gather x"
        f"{hlo_count(hlo, 'all-gather')}, all-reduce x"
        f"{hlo_count(hlo, 'all-reduce')}; compiled bytes per device: "
        f"arguments {mem.argument_size_in_bytes}, temp "
        f"{mem.temp_size_in_bytes}, output {mem.output_size_in_bytes}")
    result = train.run(job)
    hist = result["history"]
    health = result["health"]
    check(len(hist) == STEPS, f"{label}: {len(hist)} logged steps, "
          f"expected {STEPS}")
    for row in hist:
        check(math.isfinite(row["loss"]) and math.isfinite(row["grad_norm"]),
              f"{label}: non-finite loss or grad-norm at step {row['step']}")
        check(row["skipped"] == 0.0,
              f"{label}: step {row['step']} skipped by the guard")
    check(not health["skip_steps"], f"{label}: skipped steps "
          f"{health['skip_steps']}")
    check(not health["transitions"], f"{label}: exchange degraded: "
          f"{health['transitions']}")
    times = [row["dt"] for row in hist]
    steady = sorted(times[1:])[len(times[1:]) // 2]
    log(f"{label}: losses {[row['loss'] for row in hist]}; grad-norms "
        f"{[row['grad_norm'] for row in hist]}; 0 skipped, 0 transitions")
    log(f"{label}: first step {times[0]:.4f} s, steady step (median of "
        f"steps 1..{STEPS - 1}) {steady:.4f} s — smoke timing, not a benchmark")
    return result, hlo, n_kernels


def roundtrip_check(jax, n: int):
    """Compress + decompress one gradient-sized buffer with the pallas and
    the reference backends on the chip; codes must agree bitwise (in index
    order), reconstructions within the engine test's tolerance."""
    import jax.numpy as jnp

    from repro.core.compressor import FFTCompressor, FFTCompressorConfig

    x = jax.jit(lambda k: jax.random.normal(k, (n,)) * DATA_SCALE)(
        jax.random.PRNGKey(SEED + 1))
    out = {}
    for backend in ("pallas", "reference"):
        comp = FFTCompressor(FFTCompressorConfig(theta=0.7, backend=backend))
        t0 = time.perf_counter()
        payload = jax.jit(comp.compress)(x)
        recon = jax.jit(comp.decompress)(payload)
        recon.block_until_ready()
        log(f"roundtrip {backend}: {payload.re.shape[0]} chunks x "
            f"{payload.re.shape[1]} kept, compiled + ran in "
            f"{time.perf_counter() - t0:.3f} s (smoke timing)")
        out[backend] = (payload, recon)

    @jax.jit
    def canonical(p):
        order = jnp.argsort(p.idx, axis=-1, stable=True)
        return tuple(jnp.take_along_axis(a, order, axis=-1)
                     for a in (p.re, p.im, p.idx))

    (p_pal, r_pal), (p_ref, r_ref) = out["pallas"], out["reference"]
    mismatched = [int(jnp.sum(a != b)) for a, b in
                  zip(canonical(p_pal), canonical(p_ref))]
    same_fit = (float(p_pal.quant.eps) == float(p_ref.quant.eps)
                and int(p_pal.quant.p_codes) == int(p_ref.quant.p_codes))
    # as in the engine test, each backend also decompresses the other's
    # payload: the fused decompress kernel is then checked on its own
    comps = {b: FFTCompressor(FFTCompressorConfig(theta=0.7, backend=b))
             for b in out}
    err = {
        "pallas": float(jnp.max(jnp.abs(r_pal - r_ref))),
        "pallas(reference payload)": float(jnp.max(jnp.abs(
            jax.jit(comps["pallas"].decompress)(p_ref) - r_ref))),
        "reference(pallas payload)": float(jnp.max(jnp.abs(
            jax.jit(comps["reference"].decompress)(p_pal) - r_ref))),
    }
    log(f"roundtrip pallas vs reference on {n} values: mismatched re/im/idx "
        f"codes {mismatched}, same quantizer fit {same_fit}, max |recon "
        f"difference| {err} (limit {RECON_ATOL:.0e})")
    check(same_fit, "quantizer fits differ between backends")
    check(mismatched == [0, 0, 0], f"codes differ: {mismatched}")
    check(max(err.values()) <= RECON_ATOL,
          f"reconstructions differ: {err}")


def one_chip(jax, registry, train):
    from repro.models.sharding import count_params

    cfg = cut_config(registry)
    job = train.prepare(cfg, train_args(train, "--transport", "allgather"))
    check(dict(job.mesh.shape) == {"data": len(jax.devices())},
          f"unexpected mesh {dict(job.mesh.shape)}")
    _, _, n_kernels = train_and_check(jax, train, job, "train (1 chip)")
    check(n_kernels > 0, "no Pallas kernel in the compiled step")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use after training: {peak}")
    n = count_params(job.model.spec())
    del job  # free the training state before the roundtrip buffers
    roundtrip_check(jax, n)


def four_chips(jax, registry, train):
    import jax.numpy as jnp

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    cfg = cut_config(registry)
    losses = {}
    for label, extra in (
            ("allgather", ("--transport", "allgather")),
            ("psum", ("--transport", "psum")),
            ("dense", ("--reducer", "dense"))):
        job = train.prepare(cfg, train_args(train, *extra))
        placed = set(job.mesh.devices.flat)
        check(dict(job.mesh.shape) == {"data": 4} and placed == set(devices),
              f"{label}: mesh {dict(job.mesh.shape)} over {len(placed)} "
              f"devices")
        result, hlo, n_kernels = train_and_check(jax, train, job,
                                                 f"{label} (4 chips)")
        if label != "dense":
            check(n_kernels > 0, f"{label}: no Pallas kernel in the step")
        if label == "allgather":
            check(hlo_count(hlo, "all-gather") > 0,
                  "allgather: no all-gather in the compiled step")
        # replicas hold bitwise-identical parameters
        for leaf in jax.tree_util.tree_leaves(result["state"]["params"]):
            shards = [s.data for s in leaf.addressable_shards]
            check(len(shards) == 4, f"{label}: {len(shards)} replicas")
            ref = jax.lax.bitcast_convert_type(shards[0], jnp.uint32)
            for s in shards[1:]:
                other = jax.lax.bitcast_convert_type(
                    jax.device_put(s, shards[0].devices().pop()), jnp.uint32)
                check(bool(jnp.array_equal(ref, other)),
                      f"{label}: parameters differ across replicas")
        log(f"{label}: parameters bitwise replicated on all 4 devices")
        losses[label] = [row["loss"] for row in result["history"]]
        del job, result
    log(f"losses by exchange over {STEPS} steps: {losses}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip data-parallel phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke: the repository's src/repro is not next to this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              f"run on another backend", file=sys.stderr)
        return 2

    from repro.launch import train
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import registry

    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    log(f"compile cache: {cache} ({'warm' if warm else 'cold'} at start)")
    log(f"devices: {len(jax.devices())} x {dev.device_kind}; jax "
        f"{jax.__version__}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(jax, registry, train)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
