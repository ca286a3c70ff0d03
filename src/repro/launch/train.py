"""Training CLI.

Examples (CPU-scale):
  PYTHONPATH=src python -m repro.launch.train --arch gemma2_2b --reduced \\
      --steps 50 --batch 8 --seq 128 --mode compressed_dp --theta 0.7
  PYTHONPATH=src python -m repro.launch.train --arch xlstm_1_3b --reduced \\
      --steps 20 --ckpt-dir /tmp/ckpt

On a real fleet the same entrypoint runs under the production mesh
(--mesh production[:multi_pod]); on CPU it builds a mesh over however many
host devices exist.

``main`` is ``parse_args`` -> ``prepare`` (an ``ArchConfig`` plus the parsed
options -> a ``TrainJob``) -> ``run``; a caller that builds its own
configuration (``chip_smoke.py`` cuts a registry model to one chip) enters
at ``prepare``.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import jaxcompat as compat

from repro.comms.reducers import ReducerConfig
from repro.configs.base import ArchConfig
from repro.core import schedules as theta_schedules
from repro.data import SyntheticConfig, SyntheticStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (
    TWO_LEVEL_AXES,
    make_local_mesh,
    make_production_mesh,
    make_two_level_mesh,
)
from repro.models import registry
from repro.optim import OptConfig, lr_schedules
from repro.train import TrainLoopConfig, init_state, train_loop
from repro.train.step import StepConfig


@dataclasses.dataclass
class TrainJob:
    """Everything ``train_loop`` needs, built by :func:`prepare`."""

    model: Any
    mesh: Any
    opt_cfg: OptConfig
    step_cfg: StepConfig
    stream: SyntheticStream
    state: dict
    loop_cfg: TrainLoopConfig
    publisher: Optional[Any] = None


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2_2b", choices=registry.ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mode", default="pjit",
                    choices=["pjit", "compressed_dp", "hierarchical"])
    ap.add_argument("--reducer", default="fft",
                    choices=["fft", "timedomain", "terngrad", "qsgd", "dense"])
    ap.add_argument("--theta", type=float, default=0.7)
    ap.add_argument("--theta-schedule", default="constant",
                    choices=["constant", "step", "thm35"])
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="bucketed exchange: target bucket size in MB "
                         "(default: one monolithic bucket)")
    ap.add_argument("--transport", default="allgather",
                    choices=["allgather", "sequenced", "psum",
                             "hierarchical", "reduce_scatter", "auto"],
                    help="collective strategy for the compressed exchange; "
                         "hierarchical/reduce_scatter need a two-level mesh "
                         "(--nodes), auto picks flat psum vs hierarchical "
                         "from the (calibrated) cost model")
    ap.add_argument("--backend", default="auto",
                    choices=["reference", "pallas", "auto"],
                    help="compressor stage-execution engine: fused Pallas "
                         "kernels, the jnp reference path, or auto "
                         "(pallas when the platform compiles Mosaic)")
    ap.add_argument("--no-stacked", action="store_true",
                    help="disable the batched bucket executor and run the "
                         "per-bucket compress/collective loop instead "
                         "(bitwise-identical; one collective per bucket)")
    ap.add_argument("--schedule", default="stacked",
                    choices=["stacked", "streamed", "auto"],
                    help="exchange dispatch schedule (DESIGN.md §15): one "
                         "collective after backprop (stacked), readiness-"
                         "ordered bucket streaming interleaved with backprop "
                         "(streamed; bitwise-identical trajectory), or the "
                         "cost-model policy (auto)")
    ap.add_argument("--stream-groups", type=int, default=None,
                    help="streamed dispatch groups (default: one per bucket)")
    ap.add_argument("--selector", default="auto",
                    choices=["sort", "sampled", "bisect", "auto"],
                    help="top-k selection engine (DESIGN.md §16): exact "
                         "lax.top_k sort, O(n) DGC-style sampled threshold, "
                         "full value-axis bisection, or auto (sampled on "
                         "wide rows)")
    ap.add_argument("--sample-rate", type=float, default=1.0 / 64.0,
                    help="sampled selector: fraction of magnitudes in the "
                         "tau-estimation subsample")
    ap.add_argument("--calibrate", action="store_true",
                    help="run the cost-model calibration pass on the live "
                         "mesh before training (DESIGN.md §17): time real "
                         "collectives, fit α–β, measure the compression "
                         "stages and this model's backward pass; the auto "
                         "schedule then prices with measurements")
    ap.add_argument("--calibration-path", default=None,
                    help="calibration artifact path: loaded when it exists "
                         "(key-checked against this platform/mesh/model/jax), "
                         "written after --calibrate so later jobs skip the "
                         "profiling pass")
    ap.add_argument("--publish-dir", default=None,
                    help="serving publish path (DESIGN.md §20): append "
                         "compressed weight deltas to this ring-buffer "
                         "directory every --publish-every steps; replicas "
                         "tail it with `launch.serve --follow <dir>`")
    ap.add_argument("--publish-every", type=int, default=1,
                    help="trainer steps between published deltas")
    ap.add_argument("--publish-theta", type=float, default=0.0,
                    help="spectrum drop-out of the delta codec (0.0: "
                         "lossless spectrum, quantization only)")
    ap.add_argument("--publish-capacity", type=int, default=64,
                    help="ring depth: deltas buffered for lagging replicas")
    ap.add_argument("--publish-snapshot-every", type=int, default=16,
                    help="deltas between dense snapshots (rebase points)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default="local", choices=["local", "production", "multi_pod"])
    ap.add_argument("--nodes", type=int, default=None,
                    help="two-level local mesh (DESIGN.md §18): split the "
                         "host devices into this many NVLink-island nodes "
                         "((nodes, local) x ('node', 'local')); the reducer "
                         "exchanges over both axes and the hierarchical "
                         "transports become available")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.nodes is not None and args.mesh != "local":
        ap.error("--nodes builds a two-level LOCAL mesh; drop --mesh")
    return args


def prepare(cfg: ArchConfig, args: argparse.Namespace) -> TrainJob:
    """Model, mesh, exchange, data, state and loop for ``cfg`` under the
    parsed options (``--arch``/``--reduced`` are the caller's business)."""
    model = registry.build(cfg)

    if args.nodes is not None:
        mesh = make_two_level_mesh(args.nodes)
    elif args.mesh == "local":
        mesh = make_local_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi_pod")

    # the gradient-sync axes: both two-level axes on a --nodes mesh
    data_axes = TWO_LEVEL_AXES if args.nodes is not None else None
    exchange_axis = TWO_LEVEL_AXES if args.nodes is not None else "data"
    reducer = None
    if args.mode != "pjit":
        reducer = ReducerConfig(
            kind=args.reducer if args.mode == "compressed_dp" else "hierarchical",
            axis=exchange_axis,
            pod_axis="pod" if "pod" in mesh.axis_names else None,
            theta=args.theta,
            error_feedback=args.error_feedback,
            bucket_bytes=int(args.bucket_mb * (1 << 20)) if args.bucket_mb else None,
            transport=args.transport,
            backend=args.backend,
            stacked=not args.no_stacked,
            schedule=args.schedule,
            stream_groups=args.stream_groups,
            selector=args.selector,
            sample_rate=args.sample_rate,
        )
    step_cfg = StepConfig(
        mode=args.mode,
        multi_pod="pod" in mesh.axis_names,
        reducer=reducer,
        calibration_path=args.calibration_path,
        data_axes=data_axes,
    )
    opt_cfg = OptConfig(kind="adamw", lr=args.lr)

    stream = SyntheticStream(SyntheticConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        frontend_dim=cfg.d_model if cfg.frontend != "none" else 0,
        frontend_len=(args.seq if cfg.frontend == "audio_frames"
                      else cfg.n_frontend_tokens),
        seed=args.seed,
    ))

    theta_sched = None
    if args.mode != "pjit":
        if args.theta_schedule == "constant":
            theta_sched = theta_schedules.constant(args.theta)
        elif args.theta_schedule == "step":
            theta_sched = theta_schedules.step_decay(
                [(0, args.theta), (args.steps // 2, 0.0)])
        else:
            theta_sched = theta_schedules.thm35_schedule(
                1.0, lambda s: args.lr * lr_schedules.rsqrt_decay()(s))

    state = init_state(jax.random.PRNGKey(args.seed), model, opt_cfg,
                       error_feedback=args.error_feedback)
    if args.error_feedback:
        # per-worker residual rows over the manual axes
        import jax.numpy as jnp
        w = 1
        for ax in step_cfg.manual_axes:
            w *= dict(mesh.shape)[ax]
        n = state["residual"].shape[0]
        state["residual"] = jnp.zeros((w, n), jnp.float32)
    if args.mode == "compressed_dp":
        # place the state as the step returns it (replicated; the EF residual
        # one row per worker), so that the second step reuses the first
        # step's executable instead of compiling again for new shardings
        shard = lambda spec: NamedSharding(mesh, spec)
        state = {k: jax.device_put(v, shard(
                     P(step_cfg.manual_axes) if k == "residual" else P()))
                 for k, v in state.items()}

    if args.calibrate and args.mode != "pjit":
        import tempfile

        from repro.comms import calibrate as cal

        with compat.set_mesh(mesh):
            # calibrate over the axes the exchange actually rides: on a
            # two-level mesh that also records per-axis (node/local) fits
            profile = cal.calibrate(
                mesh, exchange_axis, model=model, params=state["params"],
                batch=stream.batch_at(0))
        path = args.calibration_path
        if path is None:  # the step loads the profile by path
            fd, path = tempfile.mkstemp(suffix=".calibration.json")
            import os

            os.close(fd)
        profile.save(path)
        step_cfg = dataclasses.replace(step_cfg, calibration_path=path)
        for fit in profile.fits:
            print(f"[calibrate] {fit.family}: α={fit.alpha_s * 1e6:.1f} µs  "
                  f"1/β={fit.t_comm / 1e9:.2f} GB/s")
        print(f"[calibrate] backprop {profile.backprop_flops_per_s / 1e12:.2f} "
              f"TFLOP/s; artifact at {path}")

    publisher = None
    if args.publish_dir is not None:
        from repro.serve import PublishConfig, WeightDeltaPublisher

        publisher = WeightDeltaPublisher(
            args.publish_dir, state["params"],
            PublishConfig(
                publish_every=args.publish_every,
                capacity=args.publish_capacity,
                snapshot_every=args.publish_snapshot_every,
                theta=args.publish_theta,
            ),
            extra_meta={"arch": args.arch, "reduced": bool(args.reduced)})
        print(f"[publish] ring at {args.publish_dir} "
              f"(every {args.publish_every} steps, "
              f"theta={args.publish_theta})")

    loop_cfg = TrainLoopConfig(
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        log_every=max(1, args.steps // 20),
        theta_schedule=theta_sched,
        lr_schedule=lr_schedules.warmup_cosine(max(2, args.steps // 10), args.steps),
        publish_hook=publisher.hook() if publisher is not None else None,
    )
    return TrainJob(model, mesh, opt_cfg, step_cfg, stream, state, loop_cfg,
                    publisher)


def run(job: TrainJob) -> dict:
    """Train; prints one row per logged step and returns ``train_loop``'s
    result (state, history, health)."""
    try:
        with compat.set_mesh(job.mesh):
            result = train_loop(job.model, job.opt_cfg, job.step_cfg, job.mesh,
                                job.state, job.stream, job.loop_cfg)
    finally:
        if job.publisher is not None:
            job.publisher.close()
            print(f"[publish] closed ring at v{job.publisher.version} "
                  f"({job.publisher.delta_bytes_total} delta bytes)")
    for row in result["history"]:
        print({k: (round(v, 4) if isinstance(v, float) else v) for k, v in row.items()})
    return result


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    return run(prepare(cfg, args))


if __name__ == "__main__":
    main()
