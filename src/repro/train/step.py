"""Train-step builders: pjit baseline + the paper's compressed variants.

Three modes (StepConfig.mode):

* ``pjit`` — everything auto-sharded; XLA inserts all collectives.  This is
  the dense baseline every dry-run cell lowers, and what the roofline table
  measures.  FSDP (params additionally sharded over ``data``) turns on per
  config for the >20B models.

* ``compressed_dp`` — the paper's setting: pure data parallelism over the
  (``pod``, ``data``) axes (manual via shard_map), tensor parallelism over
  ``model`` stays AUTO (partial-manual shard_map).  Per-shard gradients are
  exchanged with the configured reducer (FFT compression etc.).  Parameters
  are replicated over the manual axes, so this mode fits <= ~7B models — which
  covers the paper-faithful experiments (the paper ran AlexNet/VGG/ResNet).

* ``hierarchical`` — the multi-pod adaptation for big FSDP models: only the
  ``pod`` axis is manual; within a pod, XLA runs the usual FSDP collectives
  over (``data``, ``model``); ACROSS pods the gradient sync is the compressed
  exchange over DCN.  "Compress the bandwidth-limited hop" (DESIGN.md §2).

All modes share: grad -> [reduce] -> global-norm clip -> optimizer -> new
state, with theta threaded statically (a theta-schedule change rebuilds the
step — bounded recompiles, see core/schedules.py).

Every operation of the step runs under one of three named scopes, which the
compiled program keeps on each instruction and the device trace shows:
``step.fwd_bwd`` (forward and backward; backward operations also carry
JAX's ``transpose(...)``), ``step.exchange`` (the reducer, whose stages carry
``exchange.*`` scopes of their own) and ``step.optimizer`` (the loss and
metric means, clipping, the optimizer and the guard's commit).  Metadata
only: the scopes change no operation.

The compressed exchange is bucketed and transport-pluggable (DESIGN.md
§8-§9): ``ReducerConfig.bucket_bytes`` splits the flat gradient into
chunk-aligned buckets and ``ReducerConfig.transport`` picks the collective
(``allgather`` | ``sequenced`` | ``psum``).  The EF residual stays ONE flat
vector in the state; per-bucket slices are taken inside the reducer.

Overlap engine (DESIGN.md §15): ``ReducerConfig.schedule`` picks the
exchange's dispatch shape.  With ``streamed`` the step is STAGED — the
reducer splits the exchange into readiness-ordered dispatch groups
(``comms/scheduler.py``), and because each group's compress+collective
subgraph consumes only its own slice of the flat gradient (the slice
backprop finalizes first), XLA's latency-hiding scheduler is free to issue
group g's collective while lower-offset gradients are still being computed
— communication hides behind the backward pass instead of serializing after
it.  With ``auto`` this builder resolves the schedule ONCE per step build
via the cost-model policy (`scheduler.resolve_schedule`), using the model's
true parameter count, the batch's token count, the exchange axis's REAL
mesh size, and — when ``StepConfig.calibration_path`` names a persisted
calibration artifact (DESIGN.md §17) — the measured ``CostProfile`` in
place of the static pricing constants; the resolved decision is
exposed on the returned step object (``.schedule_decision``).  Either way
the trajectory is bitwise-identical to the stacked path, and jit-level
buffer donation of the state is preserved (the streamed groups read gradient
slices, not donated state buffers).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import jaxcompat as compat
from repro.comms import collectives, scheduler
from repro.comms import faults as faults_mod
from repro.comms.reducers import ReducerConfig, make_reducer
from repro.models.sharding import count_params, spec_tree_to_pspecs
from repro.models.transformer import MeshCtx
from repro.optim import OptConfig, apply_updates, clip_by_global_norm

__all__ = ["StepConfig", "build_train_step", "state_pspecs", "batch_pspecs"]


@dataclasses.dataclass(frozen=True)
class StepConfig:
    mode: str = "pjit"  # pjit | compressed_dp | hierarchical
    fsdp: bool = False
    multi_pod: bool = False
    clip_norm: float = 1.0
    reducer: Optional[ReducerConfig] = None  # compressed modes
    # batch/data axes override (DESIGN.md §18): on a two-level mesh the
    # batch shards over ("node", "local") instead of ("data",) — set this to
    # the mesh's data axes and give the reducer the same tuple as its
    # exchange axis.  None keeps the 1-D default (("data",), or
    # ("pod", "data") with multi_pod).
    data_axes: Optional[Tuple[str, ...]] = None
    # calibration artifact (DESIGN.md §17): path to a persisted CostProfile
    # measured on this (platform, mesh, model, jax) — the auto-schedule
    # policy then prices with fitted α–β, measured stage throughputs and the
    # measured backprop rate instead of the static defaults.  A key mismatch
    # raises calibrate.ProfileKeyMismatch at step-build time.
    calibration_path: Optional[str] = None
    # non-finite guard (DESIGN.md §19, compressed modes): every step, all
    # workers agree (one pmin over the manual axes) that the local gradient,
    # the reduced mean, the EF residual update, and every payload validation
    # are finite/sound; a failed step commits NOTHING — params, optimizer
    # moments, and the EF residual carry over unchanged (only the step
    # counter advances), so one poisoned worker cannot sneak a NaN into the
    # DGC recurrence.  The decision is bitwise-replicated; on a clean step
    # the select is the identity, so guarded and unguarded trajectories are
    # bitwise-identical.
    guard: bool = True

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        if self.data_axes is not None:
            return tuple(self.data_axes)
        return ("pod", "data") if self.multi_pod else ("data",)

    @property
    def manual_axes(self):
        if self.mode == "compressed_dp":
            return tuple(self.batch_axes)
        if self.mode == "hierarchical":
            return ("pod",)
        return ()


def state_pspecs(model, opt_cfg: OptConfig, step_cfg: StepConfig, mesh) -> Dict:
    """PartitionSpec tree for the TrainState under this mesh/mode."""
    axis_sizes = dict(mesh.shape)
    # params sharded over 'model' (+FSDP over 'data'); NEVER over 'pod'
    fsdp = step_cfg.fsdp and step_cfg.mode != "compressed_dp"
    param_specs = spec_tree_to_pspecs(model.spec(), axis_sizes, fsdp=fsdp)
    out = {
        "params": param_specs,
        "opt": {"mu": param_specs, "count": P()},
        "step": P(),
    }
    if opt_cfg.kind == "adamw":
        out["opt"]["nu"] = param_specs
    if step_cfg.reducer is not None and step_cfg.reducer.error_feedback:
        out["residual"] = P(step_cfg.batch_axes)  # per-worker rows
    return out


def batch_pspecs(step_cfg: StepConfig, batch_tree) -> Dict:
    """Batch rows over the batch axes (leading dim of every input)."""
    return jax.tree_util.tree_map(lambda _: P(step_cfg.batch_axes), batch_tree)


def _loss_and_grad(model, mesh_ctx):
    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch, ctx=mesh_ctx)
        return loss, metrics

    return jax.value_and_grad(loss_fn, has_aux=True)


def _optimizer_update(opt_cfg, step_cfg, state, grads, lr_scale):
    grads, gnorm = clip_by_global_norm(grads, step_cfg.clip_norm)
    new_params, new_opt = apply_updates(
        opt_cfg, state["params"], grads, state["opt"], lr_scale
    )
    new_state = dict(state)
    new_state.update(params=new_params, opt=new_opt, step=state["step"] + 1)
    return new_state, gnorm


def build_train_step(
    model,
    opt_cfg: OptConfig,
    step_cfg: StepConfig,
    mesh,
    batch_tree,
    *,
    lr_scale: float = 1.0,
    donate: bool = True,
) -> Callable:
    """Returns jitted step(state, batch) -> (state, metrics).

    ``batch_tree`` is any pytree with the batch's structure (abstract ok) —
    used to build input shardings.
    """
    axes = dict(mesh.shape)
    mesh_ctx = MeshCtx(
        batch=step_cfg.batch_axes,
        model="model" if "model" in axes else None,
        model_size=axes.get("model", 1),
    )
    sharding = lambda spec_tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    batch_sh = sharding(batch_pspecs(step_cfg, batch_tree))

    if step_cfg.mode == "pjit":
        vg = _loss_and_grad(model, mesh_ctx)

        def step(state, batch):
            with jax.named_scope("step.fwd_bwd"):
                (loss, metrics), grads = vg(state["params"], batch)
            with jax.named_scope("step.optimizer"):
                new_state, gnorm = _optimizer_update(
                    opt_cfg, step_cfg, state, grads, lr_scale)
            metrics = dict(metrics, loss=loss, grad_norm=gnorm)
            return new_state, metrics

        state_sh = sharding(state_pspecs(model, opt_cfg, step_cfg, mesh))
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,) if donate else (),
        )

        class _PjitStep:
            # device_put against these before calling (freshly generated
            # batches may be mesh-committed as replicated, which conflicts
            # with explicit in_shardings)
            batch_sharding = batch_sh
            state_sharding = state_sh

            def __call__(self, st, batch):
                return jitted(st, jax.device_put(batch, batch_sh))

            def lower(self, st, batch):
                return jitted.lower(st, batch)

        return _PjitStep()

    # ---- compressed modes: partial-manual shard_map ------------------------
    assert step_cfg.reducer is not None, "compressed modes need a ReducerConfig"
    # overlap-engine auto policy (DESIGN.md §15): resolve the dispatch
    # schedule HERE, where the model's parameter count and the batch's token
    # count are known — the reducer then traces a concrete schedule
    reducer_cfg = step_cfg.reducer
    batch_tokens = _batch_tokens(batch_tree)
    # the compressed exchange's collective runs over one axis (pod for
    # hierarchical, the data axis otherwise) OR a tuple of axes (the
    # two-level ("node", "local") topology); its mesh size is the worker
    # count the wire model must price — NOT a hardcoded 2
    exchange_axis = (reducer_cfg.pod_axis if reducer_cfg.kind == "hierarchical"
                     else reducer_cfg.axis)
    if exchange_axis is None:
        exchange_axes: Tuple[str, ...] = ()
    elif isinstance(exchange_axis, str):
        exchange_axes = (exchange_axis,)
    else:
        exchange_axes = tuple(exchange_axis)
    exchange_workers = 1
    for a in exchange_axes:
        exchange_workers *= axes.get(a, 1)
    # the (nodes, local) shape the transport policy prices — only a 2-axis
    # exchange spec has a two-level topology to exploit
    topology = (tuple(axes.get(a, 1) for a in exchange_axes)
                if len(exchange_axes) == 2 else None)
    profile = None
    if step_cfg.calibration_path is not None:
        from repro.comms import calibrate

        profile = calibrate.load_profile_for(
            step_cfg.calibration_path, mesh, model=model)
    transport_decision = None
    if reducer_cfg.transport == "auto":
        resolved_t, transport_decision = scheduler.resolve_transport(
            reducer_cfg, count_params(model.spec()),
            topology=topology, profile=profile)
        reducer_cfg = dataclasses.replace(reducer_cfg, transport=resolved_t)
    schedule_decision = None
    if reducer_cfg.schedule == "auto":
        resolved, schedule_decision = scheduler.resolve_schedule(
            reducer_cfg, count_params(model.spec()), batch_tokens,
            workers=exchange_workers, profile=profile, topology=topology)
        reducer_cfg = dataclasses.replace(reducer_cfg, schedule=resolved)
    reducer = make_reducer(reducer_cfg, batch_tokens=batch_tokens,
                           workers=exchange_workers, profile=profile,
                           topology=topology)
    manual = step_cfg.manual_axes
    ef = step_cfg.reducer.error_feedback

    # Inside the shard_map the manual axes are stripped; model-axis
    # constraints still apply through the auto axes.  In hierarchical mode
    # 'data' remains auto so batch constraints over it stay valid.
    inner_ctx = None if step_cfg.mode == "compressed_dp" else MeshCtx(
        batch=("data",),
        model="model" if "model" in axes else None,
        model_size=axes.get("model", 1),
    )
    vg_inner = _loss_and_grad(model, inner_ctx)

    plan = reducer_cfg.faults
    resilient = reducer_cfg.resilient
    guard = step_cfg.guard

    def inner(state, batch):
        step_no = state["step"]
        if ef:
            state = dict(state, residual=state["residual"][0])
        with jax.named_scope("step.fwd_bwd"):
            (loss, metrics), grads = vg_inner(state["params"], batch)
        if plan is not None and plan.nan_events:
            # deterministic gradient poisoning (FaultPlan.nan_grad): the
            # worker coordinate is the row-major linear index over the
            # manual axes, the step coordinate the replicated counter —
            # both traced, so the chaos run shares the clean run's jaxpr
            widx = collectives.axis_linear_index(manual)
            poison = faults_mod.match_events(plan.nan_events, step_no, widx)
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(poison, jnp.asarray(jnp.nan, g.dtype), g),
                grads)
        pay_ok = jnp.bool_(True)
        with jax.named_scope("step.exchange"):
            if ef:
                if resilient:
                    reduced, new_residual, pay_ok = reducer(
                        grads, state["residual"], step=step_no)
                else:
                    reduced, new_residual = reducer(grads, state["residual"])
            else:
                if resilient:
                    reduced, pay_ok = reducer(grads, step=step_no)
                else:
                    reduced = reducer(grads)
        # one scope for the update and the guard's commit: XLA fuses AdamW's
        # update with the guard's select, and a fusion carries the name of
        # its root, so two scopes would read one of them as empty
        with jax.named_scope("step.optimizer"):
            loss = jax.lax.pmean(loss, manual)
            metrics = jax.lax.pmean(metrics, manual)
            new_state, gnorm = _optimizer_update(
                opt_cfg, step_cfg, state, reduced, lr_scale)
            if ef:
                new_state["residual"] = new_residual
            skipped = jnp.float32(0.0)
            if guard:
                # all-workers-agree finiteness flag: local gradient, reduced
                # mean, residual update, and payload validation must all be
                # sound EVERYWHERE — one pmin makes the verdict bitwise-
                # replicated, so workers can never diverge on whether the
                # update committed
                ok_local = (pay_ok
                            & faults_mod.tree_finite(grads)
                            & faults_mod.tree_finite(reduced))
                if ef:
                    ok_local = ok_local & jnp.isfinite(new_residual).all()
                keep = jax.lax.pmin(ok_local.astype(jnp.int32), manual) > 0
                # a skipped step commits nothing but the step counter: params
                # and moments stay put, and the EF residual is QUARANTINED —
                # carrying e_{t-1} over unchanged keeps the DGC recurrence on
                # clean inputs instead of folding a poisoned error in
                old_state = dict(state, step=state["step"] + 1)
                new_state = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(keep, new, old),
                    new_state, old_state)
                skipped = 1.0 - keep.astype(jnp.float32)
            if ef:
                new_state["residual"] = new_state["residual"][None]
            metrics = dict(metrics, loss=loss, grad_norm=gnorm, skipped=skipped)
            return new_state, metrics

    def state_in_specs(state_like):
        specs = jax.tree_util.tree_map(lambda _: P(), state_like)
        if ef:
            specs["residual"] = P(manual)
        return specs

    def step(state, batch):
        # partial-manual shard_map: in_specs may reference MANUAL axes only;
        # the auto ('data'/'model') sharding of the batch comes from the
        # model's internal constraints
        batch_specs = jax.tree_util.tree_map(lambda _: P(manual), batch)
        step_sm = compat.shard_map(
            inner,
            mesh,
            in_specs=(state_in_specs(state), batch_specs),
            out_specs=(state_in_specs(state), P()),
            manual_axes=manual,
        )
        return step_sm(state, batch)

    # NOTE: composing jit-level in_shardings (FSDP over the auto axes) with
    # the partial-manual shard_map check-fails inside XLA's SPMD partitioner
    # (spmd_partitioner_util.cc:504; same family as b/433785288 pending the
    # Shardy partitioner).  Until then the compressed modes run with params
    # replicated over the manual axes — fine for the paper-scale models the
    # compressed_dp mode targets; the hierarchical mode's FSDP composition is
    # documented as blocked-on-upstream in EXPERIMENTS.md §Perf.
    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
    batch_sh_manual = NamedSharding(mesh, P(manual))

    _resolved_cfg, _decision, _t_decision = (
        reducer_cfg, schedule_decision, transport_decision)

    class _Step:
        batch_sharding = batch_sh_manual
        # the concrete config the step traced (auto resolved) and, when the
        # auto policies ran, the cost-model numbers behind their verdicts
        reducer_config = _resolved_cfg
        schedule_decision = _decision
        transport_decision = _t_decision

        def __call__(self, state, batch):
            with compat.set_mesh(mesh):
                return jitted(state, jax.device_put(batch, batch_sh_manual))

        def lower(self, state, batch):
            with compat.set_mesh(mesh):
                return jitted.lower(state, batch)

    return _Step()


def _batch_tokens(batch_tree) -> Optional[int]:
    """Per-step token count for the auto-schedule policy's backprop model.

    Sequence batches ('tokens' of shape (B, S)) yield B·S; otherwise the
    leading (batch) dimension of the first leaf.  A policy hint, not an
    accounting quantity."""
    if isinstance(batch_tree, dict) and "tokens" in batch_tree:
        shape = batch_tree["tokens"].shape
        n = 1
        for s in shape:
            n *= int(s)
        return n
    leaves = jax.tree_util.tree_leaves(batch_tree)
    if not leaves:
        return None
    return int(leaves[0].shape[0]) if leaves[0].shape else None
