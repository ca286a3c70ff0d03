"""The system under test: the trainer's own step, built as its CLI builds it.

``build`` follows ``repro.launch.train.prepare`` for the options a cell
names (``registry.build`` -> ``StepConfig``/``ReducerConfig`` ->
``train.step.build_train_step`` on a one-axis ``data`` mesh), with the
exchange named outright so no cost-model pricing picks it.  The state is the
trainer's (``optim.init_opt_state``), holding the benchmark's seeded
weights, placed replicated as ``prepare`` places it.
"""

from __future__ import annotations

import dataclasses

from bench import spec, weights


@dataclasses.dataclass
class Job:
    cfg: dict
    traffic: dict
    arch: object  # repro ArchConfig
    model: object
    mesh: object
    opt_cfg: object
    step_cfg: object
    step: object  # the trainer's step object (build_train_step)
    global_batch: int
    seq: int

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch * self.seq


def arch_config(cfg: dict):
    from repro.configs.base import ArchConfig

    return ArchConfig(**spec.arch_fields(cfg))


def build(cfg: dict, traffic: dict, mesh) -> Job:
    import jax
    import jax.numpy as jnp

    from repro.comms.reducers import ReducerConfig
    from repro.models import registry
    from repro.models.layers import COMPUTE_DTYPE
    from repro.optim import OptConfig
    from repro.train.step import StepConfig, build_train_step

    train = cfg["training"]
    if jnp.dtype(COMPUTE_DTYPE) != jnp.dtype(train["compute_dtype"]):
        raise spec.SpecError(
            f"the trainer computes in {jnp.dtype(COMPUTE_DTYPE)}, the "
            f"configuration states {train['compute_dtype']}")
    arch = arch_config(cfg)
    model = registry.build(arch)
    ex = traffic["exchange"]
    chips = mesh.devices.size
    reducer = ReducerConfig(
        kind=ex["reducer"], axis="data", theta=ex["theta"],
        n_bits=ex["n_bits"], m_bits=ex["m_bits"], chunk=ex["chunk"],
        error_feedback=ex["error_feedback"], bucket_bytes=None,
        transport=ex["transport"], backend=ex["backend"], stacked=True,
        schedule=ex["schedule"], selector=ex["selector"])
    step_cfg = StepConfig(mode=ex["mode"], multi_pod=False, reducer=reducer,
                          clip_norm=train["clip_norm"])
    o = train["optimizer"]
    opt_cfg = OptConfig(kind=o["kind"], lr=o["lr"], b1=o["b1"], b2=o["b2"],
                        eps=o["eps"], weight_decay=o["weight_decay"])
    global_batch = traffic["rows_per_chip"] * chips
    seq = traffic["seq_len"]
    batch_like = {k: jax.ShapeDtypeStruct((global_batch, seq), jnp.int32)
                  for k in ("tokens", "targets")}
    step = build_train_step(model, opt_cfg, step_cfg, mesh, batch_like)
    if step.schedule_decision is not None or step.transport_decision is not None:
        raise spec.SpecError("the step priced its schedule or transport")
    check_layout(cfg, model)
    return Job(cfg, traffic, arch, model, mesh, opt_cfg, step_cfg, step,
               global_batch, seq)


def check_layout(cfg: dict, model) -> None:
    """The benchmark's weight layout is the trainer's parameter tree."""
    import jax

    from repro.models.sharding import ParamSpec

    ours = jax.tree_util.tree_map(tuple, weights.shapes(cfg),
                                  is_leaf=lambda x: isinstance(x, tuple))
    theirs = jax.tree_util.tree_map(lambda s: tuple(s.shape), model.spec(),
                                    is_leaf=lambda x: isinstance(x, ParamSpec))
    if ours != theirs:
        raise spec.SpecError(f"weight layout {ours} is not the trainer's {theirs}")


def state_fn(job: Job):
    """``fn(key) -> state``: the trainer's state around seeded weights, made
    in one jitted call, replicated over the mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.optim import init_opt_state

    def make(key):
        params = weights.init(job.cfg, key)
        return {"params": params, "opt": init_opt_state(job.opt_cfg, params),
                "step": jnp.zeros((), jnp.int32)}

    return jax.jit(make, out_shardings=NamedSharding(job.mesh, P()))


def abstract_state(job: Job, sharding=None):
    """ShapeDtypeStruct tree of the state (for compiling without arrays)."""
    import jax

    shaped = jax.eval_shape(state_fn(job), jax.random.key(0))
    if sharding is None:
        return shaped
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shaped)
