"""Gradient reducers: the paper's compressed exchange as a pluggable stage.

All reducers run inside ``shard_map`` and average a *gradient pytree* over one
or two named mesh axes.  Variants:

* ``dense``        — jax.lax.pmean (the paper's "orig" baseline).
* ``fft``          — the paper: per-shard FFT -> theta-drop -> range-quant ->
                     pack -> compressed exchange -> frequency-domain sum ->
                     single inverse FFT per bucket.  FFT linearity (sum of
                     spectra = spectrum of sum) means one iFFT regardless of
                     the worker count (beyond-paper; DESIGN.md §10).
* ``timedomain``   — DGC/Aji-style top-k exchange (paper Fig. 12 baseline).
* ``terngrad`` / ``qsgd`` — quantization baselines (paper Table I).
* ``hierarchical`` — multi-pod: dense psum_scatter intra-pod (fast ICI),
                     compressed exchange over the ``pod`` axis (slow DCN),
                     all-gather intra-pod.  This is the faithful adaptation of
                     "compress the bandwidth-limited exchange" to a TPU fleet.

The compressed exchange is a three-layer subsystem (DESIGN.md §8-§9):

1. **bucketing** — the gradient pytree is flattened, concatenated, and split
   into size-targeted, chunk-aligned buckets (``comms.bucketing``).  With
   ``bucket_bytes=None`` the whole buffer is one bucket (seed behavior).
2. **transport** — the exchange rides a pluggable collective strategy
   (``comms.transport``): ``allgather`` (one monolithic payload all_gather),
   ``sequenced`` (bucketed all_gather), or ``psum`` (spectrum-psum:
   dequantize locally, psum spectra, one iFFT — O(k) wire instead of
   O(P·k)).  With ``stacked=True`` (default, DESIGN.md §14) the bucketed
   transports compress every bucket in one batched kernel pass and issue ONE
   collective per exchange (a ``StackedPayload``); ``stacked=False`` runs
   the per-bucket loop (one collective per bucket), bitwise-identically.
3. **schedule** — the overlap engine (``comms.scheduler``, DESIGN.md §15):
   ``ReducerConfig.schedule`` picks the dispatch shape — ``stacked`` (one
   collective after backprop), ``streamed`` (readiness-ordered dispatch
   groups interleaved with the backward pass; bitwise-identical
   trajectories), or ``auto`` (the cost-model policy, resolved per model).
4. **this module** — flatten/split, hierarchical axis composition, and the
   per-bucket (and, streamed, per-readiness-group) error-feedback residual
   slices.

Leaves smaller than a chunk still ride their bucket — correctness is
unaffected because unpadding is exact, and because interior bucket boundaries
are chunk multiples the per-chunk top-k selection is identical at every
bucket granularity.

Error feedback (optional, default off — the paper's method is memoryless):
``make_reducer`` returns a (reduce_fn, init_residual_fn) pair when
``config.error_feedback`` is set; the train step threads the residual as one
flat vector, and this module slices it per bucket with the same layout that
splits the gradient, so each bucket accumulates exactly what ITS transport
granularity dropped (per-bucket quantizers included).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comms import bucketing, collectives, scheduler
from repro.comms import faults as faults_mod
from repro.comms.transport import TRANSPORT_NAMES, collective, get_transport
from repro.core import baselines as B
from repro.core.compressor import (
    FFTCompressor,
    FFTCompressorConfig,
    TimeDomainCompressor,
)
from repro.kernels.engine import BACKEND_NAMES

__all__ = [
    "ReducerConfig",
    "make_reducer",
    "degrade_config",
    "flatten_tree",
    "unflatten_tree",
    "residual_size",
]


# ---------------------------------------------------------------------------
# pytree <-> flat buffer
# ---------------------------------------------------------------------------


def flatten_tree(tree) -> Tuple[jnp.ndarray, list, list]:
    """Concatenate all leaves into one f32 vector; returns (flat, shapes, treedef)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [(l.shape, l.dtype) for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    return flat, shapes, treedef


def unflatten_tree(flat: jnp.ndarray, shapes, treedef):
    leaves = []
    offset = 0
    for shape, dtype in shapes:
        size = 1
        for s in shape:
            size *= s
        leaves.append(flat[offset : offset + size].reshape(shape).astype(dtype))
        offset += size
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# reducer construction
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReducerConfig:
    kind: str = "dense"  # dense|fft|timedomain|terngrad|qsgd|hierarchical
    # gradient-sync mesh axis: one name, or a tuple of names for two-level
    # topologies (("node", "local") — required by transport="hierarchical",
    # accepted by every flat transport; None: auto-handled)
    axis: Optional[object] = "data"
    pod_axis: Optional[str] = None  # set for hierarchical (compressed) axis
    theta: float = 0.7
    n_bits: int = 8
    m_bits: int = 3
    chunk: int = 4096
    quantize: bool = True
    range_mode: str = "auto"
    fixed_range: Tuple[float, float] = (-1.0, 1.0)
    error_feedback: bool = False
    # bucketed exchange (DESIGN.md §8-§9): target bucket size in bytes of the
    # f32 gradient (None = one monolithic bucket) and the collective strategy
    bucket_bytes: Optional[int] = None
    # allgather|sequenced|psum|hierarchical|reduce_scatter, or "auto" (the
    # cost-model transport policy: flat psum vs hierarchical, resolved per
    # topology by scheduler.resolve_transport)
    transport: str = "allgather"
    # compressor stage-execution engine (DESIGN.md §13): reference|pallas|auto
    backend: str = "reference"
    # batched bucket executor (DESIGN.md §14): compress every bucket in one
    # batched kernel pass and move one StackedPayload per exchange (bitwise-
    # equal to the loop); False forces the per-bucket loop
    stacked: bool = True
    # overlap engine (DESIGN.md §15): exchange dispatch schedule.
    #   stacked  — one collective after backprop (§14)
    #   streamed — one collective per readiness group, issued while backprop
    #              still runs (comms/scheduler.py); bitwise-equal trajectories
    #   auto     — cost-model policy picks per model (scheduler.choose_schedule)
    schedule: str = "stacked"
    # streamed dispatch groups (None: one group per bucket — finest grain)
    stream_groups: Optional[int] = None
    # selection engine (DESIGN.md §16): sort|sampled|bisect|auto top-k
    # selector on the compression hot path, plus the sampled estimator's
    # subsample rate and bracket-refinement sweep count
    selector: str = "sort"
    sample_rate: float = 1.0 / 64.0
    tau_refine_iters: int = 16
    # resilience layer (DESIGN.md §19): payload validation level
    # (off | cheap | full) and a deterministic FaultPlan of injected
    # events.  With validate="off" and faults=None (the defaults) the
    # reducer keeps its historical signature and adds zero work; otherwise
    # the reduce functions take a ``step=`` kwarg and return an extra
    # worker-local ``ok`` flag the step guard folds across workers.
    validate: str = "off"
    faults: Optional[faults_mod.FaultPlan] = None

    @property
    def resilient(self) -> bool:
        """True when the reduce functions carry the (step, ok) contract.

        Dense reduction has no payloads to corrupt or validate, so a dense
        config (including one reached down the degradation ladder, which
        keeps the FaultPlan for gradient-level events) is never resilient.
        """
        if self.kind == "dense":
            return False
        return (self.validate != "off"
                or (self.faults is not None
                    and bool(self.faults.corrupt_events)))

    def __post_init__(self):
        from repro.core.selection import SELECTOR_NAMES

        if self.selector not in SELECTOR_NAMES:
            raise ValueError(
                f"unknown selector {self.selector!r}; expected one of "
                f"{SELECTOR_NAMES}")
        if self.transport not in TRANSPORT_NAMES + ("auto",):
            raise ValueError(
                f"unknown transport {self.transport!r}; expected one of "
                f"{TRANSPORT_NAMES + ('auto',)}"
            )
        if self.axis is not None and not isinstance(self.axis, str):
            # normalize sequence specs to tuples so the config stays hashable
            object.__setattr__(self, "axis", tuple(self.axis))
        if self.bucket_bytes is not None and self.bucket_bytes <= 0:
            raise ValueError(f"bucket_bytes must be positive, got {self.bucket_bytes}")
        if self.backend not in BACKEND_NAMES:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of {BACKEND_NAMES}")
        if self.schedule not in scheduler.SCHEDULE_NAMES:
            raise ValueError(
                f"unknown schedule {self.schedule!r}; expected one of "
                f"{scheduler.SCHEDULE_NAMES}")
        # the monolithic all-gather fits ONE quantizer over the whole buffer;
        # streaming it per group would change the fit (different numerics),
        # so the streamed schedule requires a bucketed transport
        if self.schedule == "streamed" and self.transport == "allgather":
            raise ValueError(
                "schedule='streamed' needs a bucketed transport "
                "(sequenced|psum); allgather is monolithic by definition")
        if self.stream_groups is not None and self.stream_groups < 1:
            raise ValueError(
                f"stream_groups must be >= 1, got {self.stream_groups}")
        if self.validate not in faults_mod.VALIDATE_LEVELS:
            raise ValueError(
                f"unknown validate level {self.validate!r}; expected one of "
                f"{faults_mod.VALIDATE_LEVELS}")
        if self.faults is not None and not isinstance(
                self.faults, faults_mod.FaultPlan):
            raise TypeError(
                f"faults must be a comms.faults.FaultPlan, got "
                f"{type(self.faults).__name__}")

    def compressor_config(self) -> FFTCompressorConfig:
        return FFTCompressorConfig(
            theta=self.theta,
            n_bits=self.n_bits,
            m_bits=self.m_bits,
            chunk=self.chunk,
            quantize=self.quantize,
            range_mode=self.range_mode,
            fixed_range=self.fixed_range,
            backend=self.backend,
            selector=self.selector,
            sample_rate=self.sample_rate,
            tau_refine_iters=self.tau_refine_iters,
        )

    def layout_for(self, total: int) -> bucketing.BucketLayout:
        return bucketing.build_layout(total, self.bucket_bytes, self.chunk)


def _mean_over(x, axis):
    return collective(jax.lax.pmean, x, axis)


def _make_compressor(config: ReducerConfig):
    if config.kind in ("fft", "hierarchical"):
        return FFTCompressor(config.compressor_config())
    if config.kind == "timedomain":
        return TimeDomainCompressor(config.compressor_config())
    if config.kind == "terngrad":
        return B.TernGrad()
    if config.kind == "qsgd":
        return B.QSGD()
    raise ValueError(f"unknown compressed reducer kind {config.kind!r}")


def make_reducer(config: ReducerConfig, *, batch_tokens: Optional[int] = None,
                 workers: Optional[int] = None, profile=None,
                 topology: Optional[Tuple[int, int]] = None):
    """Returns reduce_fn(grads[, residual]) for use INSIDE shard_map.

    Without error feedback: reduce_fn(grads) -> mean_grads.
    With error feedback:    reduce_fn(grads, residual) -> (mean_grads, residual').

    Resilient contract (``config.resilient`` — validate != "off" or a
    FaultPlan with payload-corruption events, DESIGN.md §19): the reduce
    functions accept an extra ``step=`` kwarg (traced i32 scalar; drives
    deterministic fault matching) and return one extra WORKER-LOCAL ``ok``
    bool — the AND of every payload validation this worker saw.  The step
    guard combines it across workers (pmin) so skip decisions replicate.

    ``batch_tokens``, ``workers``, ``profile`` and ``topology`` are the
    policy layers' pricing inputs (DESIGN.md §15/§17/§18): the train-step
    builder passes the real per-step token count, the gradient axes' mesh
    size, (when ``StepConfig.calibration_path`` names one) the measured
    ``calibrate.CostProfile``, and — on a two-level mesh — the (nodes,
    local) shape of the exchange axes, so ``schedule='auto'`` prices the
    actual backward pass on the actual topology and ``transport='auto'``
    can pick flat psum vs hierarchical.  Direct callers may omit all four
    (documented defaults keep the decisions deterministic).
    """
    if config.kind == "dense":
        if config.error_feedback:
            raise ValueError("error feedback is meaningless for dense reduction")

        def dense_reduce(grads):
            axes = (config.axis,) if config.pod_axis is None else (
                config.axis,
                config.pod_axis,
            )
            out = grads
            for ax in axes:
                out = _mean_over(out, ax)
            return out

        return dense_reduce

    comp = _make_compressor(config)
    resilient = config.resilient

    def _monitor(step):
        """One ExchangeMonitor per traced reduce call (None when inert)."""
        if not resilient:
            return None
        axes = []
        for a in (config.axis, config.pod_axis):
            if a is None:
                continue
            axes.extend(a if isinstance(a, tuple) else (a,))
        worker = collectives.axis_linear_index(tuple(axes))
        step_t = (jnp.asarray(-1, jnp.int32) if step is None
                  else jnp.asarray(step, jnp.int32))
        corrupt = (config.faults.corrupt_events
                   if config.faults is not None else ())
        return faults_mod.ExchangeMonitor(
            config.validate, step=step_t, worker=worker, corrupt=corrupt)

    def _concrete(total: int) -> ReducerConfig:
        """The config with ``transport='auto'`` resolved for a flat buffer
        of this size — a pure host-side computation per trace (the flat
        length is static inside jit), like the schedule resolution below."""
        name, _ = scheduler.resolve_transport(
            config, total, topology=topology, profile=profile)
        if name == config.transport:
            return config
        return dataclasses.replace(config, transport=name)

    def _schedule_for(cfg: ReducerConfig, total: int) -> str:
        """Concrete dispatch schedule for a flat buffer of this size —
        resolved at trace time (the flat length is static inside jit), so
        an auto decision is one pure host-side computation per trace."""
        resolved, _ = scheduler.resolve_schedule(
            cfg, total, batch_tokens, workers=workers, profile=profile,
            topology=topology)
        return resolved

    def _dispatch_spec(cfg: ReducerConfig, total: int) -> dict:
        """layout= or plan= kwargs for ``Transport.run`` — plan when the
        resolved schedule streams over a multi-bucket layout, one stacked
        layout dispatch otherwise (DESIGN.md §20)."""
        layout = cfg.layout_for(total)
        if _schedule_for(cfg, total) == "streamed" and layout.n_buckets > 1:
            return {"plan": scheduler.build_plan(layout, cfg.stream_groups)}
        return {"layout": layout}

    def _exchange_flat(flat: jnp.ndarray, axis, monitor=None) -> jnp.ndarray:
        cfg = _concrete(flat.shape[0])
        transport = get_transport(cfg.transport)
        return transport.run(flat, comp=comp, axis=axis, stacked=cfg.stacked,
                             monitor=monitor,
                             **_dispatch_spec(cfg, flat.shape[0]))

    def _local_roundtrip_flat(flat: jnp.ndarray) -> jnp.ndarray:
        cfg = _concrete(flat.shape[0])
        transport = get_transport(cfg.transport)
        return transport.run(flat, comp=comp, stacked=cfg.stacked,
                             **_dispatch_spec(cfg, flat.shape[0]))

    def compressed_reduce(grads, step=None):
        monitor = _monitor(step)
        flat, shapes, treedef = flatten_tree(grads)
        if config.kind == "hierarchical":
            # 1) dense mean over the fast intra-pod axis (ICI).  axis=None
            # means the intra-pod reduction is handled by the AUTO partitioner
            # (partial-manual shard_map where only 'pod' is manual).
            if config.axis:
                flat = _mean_over(flat, config.axis)
            # 2) compressed exchange over the slow pod axis (DCN)
            if config.pod_axis is not None:
                flat = _exchange_flat(flat, config.pod_axis, monitor)
        else:
            flat = _exchange_flat(flat, config.axis, monitor)
            if config.pod_axis is not None:
                flat = _mean_over(flat, config.pod_axis)
        mean = unflatten_tree(flat, shapes, treedef)
        if resilient:
            return mean, monitor.ok()
        return mean

    if not config.error_feedback:
        return compressed_reduce

    def ef_reduce(grads, residual_flat, step=None):
        monitor = _monitor(step)
        flat, shapes, treedef = flatten_tree(grads)
        if config.kind == "hierarchical" and config.axis:
            flat = _mean_over(flat, config.axis)
        corrected = flat + residual_flat
        # residual at the exchange's own compression AND dispatch granularity:
        # what THIS schedule's transport dropped on this worker (per-bucket
        # quantizer fits, per-readiness-group slices and all).  The local
        # roundtrip is NOT monitored: the residual never crosses the wire,
        # and a skipped step quarantines it regardless (DESIGN.md §19).
        local_hat = _local_roundtrip_flat(corrected)
        new_residual = corrected - local_hat
        axis = config.pod_axis if config.kind == "hierarchical" else config.axis
        mean_flat = _exchange_flat(corrected, axis, monitor)
        if config.kind != "hierarchical" and config.pod_axis is not None:
            mean_flat = _mean_over(mean_flat, config.pod_axis)
        mean = unflatten_tree(mean_flat, shapes, treedef)
        if resilient:
            return mean, new_residual, monitor.ok()
        return mean, new_residual

    return ef_reduce


# ---------------------------------------------------------------------------
# degradation ladder (DESIGN.md §19)
# ---------------------------------------------------------------------------


def degrade_config(config: ReducerConfig) -> Optional[Tuple[ReducerConfig, str]]:
    """One rung down the degradation ladder: (simpler config, rung label).

    Returns ``None`` when the ladder is exhausted (already dense).  Rung
    order drops the most sophisticated machinery first, preserving as much
    compression as possible at each step:

    1. fused pallas kernels (or auto)      -> reference backend
    2. streamed/auto dispatch              -> stacked (one collective)
    3. hierarchical/reduce_scatter fabric  -> flat spectrum psum
    4. any compressed kind                 -> dense pmean (error feedback
       off — dense drops nothing, so there is nothing to accumulate; the
       train loop pops the residual from the state when it takes this rung)

    The FaultPlan is kept (gradient-level events must keep replaying under
    a degraded exchange) but validation is retired with the payloads on
    the dense rung.
    """
    if config.kind == "dense":
        return None
    if config.backend != "reference":
        return (dataclasses.replace(config, backend="reference"),
                f"backend:{config.backend}->reference")
    if config.schedule != "stacked":
        return (dataclasses.replace(config, schedule="stacked"),
                f"schedule:{config.schedule}->stacked")
    if config.transport in ("hierarchical", "reduce_scatter", "auto"):
        return (dataclasses.replace(config, transport="psum"),
                f"transport:{config.transport}->psum")
    return (dataclasses.replace(config, kind="dense", error_feedback=False,
                                validate="off"),
            f"kind:{config.kind}->dense")


def residual_size(params) -> int:
    """Flat residual length for error-feedback state allocation."""
    return bucketing.residual_size(params)
