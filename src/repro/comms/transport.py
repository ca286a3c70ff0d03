"""Pluggable exchange strategies for compressed gradient buckets.

Layer (2) of the bucketed exchange (DESIGN.md §9).  A transport turns a list
of per-bucket flat gradients into the list of their cross-worker means, using
one compressor.  All transports compute the SAME mean — mean over the axis of
the per-worker dequantized reconstructions — they differ in which collective
carries the bytes and at what granularity:

============== =========================== ============================== =========
name           collective                  per-worker wire (cost model)   overlap
============== =========================== ============================== =========
allgather      one all_gather of the       P · B  (P payloads land on     none
               monolithic payload          every worker)
sequenced      one all_gather PER BUCKET   P · B  total, issued as        buckets
               (independent collectives)   n_buckets independent ops      pipeline
psum           per-bucket psum of the      B      (in-network reduction:  buckets
               locally dequantized         each worker injects its kept
               spectrum                    coefficients once; P-free)
hierarchical   intra-node spectra psum     inter-node: nodes·B per NODE   buckets
               ('local' axis) -> ONE       (one compressed payload per
               re-compressed payload per   island crosses the fabric);
               island -> inter-node        intra-node: dense-spectrum
               all_gather ('node' axis)    psum on the fast link
reduce_scatter psum_scatter of spectra     2·(P-1)/P of the dense         buckets
               over the BUCKET axis; each  planes (ring-allreduce-
               worker iFFTs its own        shaped: gather-path wire
               contiguous sub_layout       stops growing with P)
               range, then all_gather
============== =========================== ============================== =========

``B = comp.wire_bits(n)`` at equal theta; see ``cost_model.transport_wire_bits``
for the model the acceptance tests assert against (the psum column prices the
sparse-allreduce endpoint; today's lowering is a dense-spectrum psum — see
``_psum_mean_payload``).

The psum transport exploits FFT linearity (DESIGN.md §10): sum of spectra ==
spectrum of the sum, so workers dequantize locally, sum spectra with a single
``psum``, and run ONE inverse FFT on the mean spectrum.  For non-spectral
compressors (timedomain/terngrad/qsgd) it degrades gracefully to a psum of the
dense local reconstruction — still numerically identical to the all-gather
mean, still O(1) payloads per worker in the cost model.

Quantizer granularity: the monolithic ``allgather`` transport fits ONE
quantizer over the whole buffer (seed behavior); ``sequenced`` and ``psum``
compress per bucket, so each bucket fits its own range (small buckets stop
inheriting a global range — see ``FFTCompressor.compress_buckets``).

Two-level topology (DESIGN.md §18): the ``hierarchical`` transport takes a
TUPLE axis spec ``(node_axis, local_axis)`` over a 2-D mesh
(``launch.mesh.make_two_level_mesh``).  FFT linearity makes the intra-node
hop a plain ``psum`` of dequantized spectra over the fast link; the node
mean is re-compressed ONCE so the slow fabric moves exactly one compressed
``StackedPayload`` per island; the inter-node all_gather's result is
replicated over the local axis by construction (the psum already
broadcast), so the intra-node broadcast costs nothing extra.  The
``reduce_scatter`` transport is flat (one axis or a tuple treated as one
flattened axis) but partitions the BUCKET axis: ``psum_scatter`` hands each
worker the reduced spectra of its own contiguous ``sub_layout`` range, the
worker runs the inverse FFT only on its shard, and a tiled all_gather
rebuilds the flat buffer — per-worker wire is ring-allreduce-shaped
(2·(P-1)/P of the dense planes) instead of growing with P like the gather
transports.

One entry point (DESIGN.md §20): every consumer — the stacked executor
(§14), the streamed overlap engine (§15), error feedback, and the serving
publisher — calls ``Transport.run(flat, comp=..., ...)``:

* ``layout=``            one stacked dispatch over the whole layout;
* ``plan=``              a ``StreamPlan``: one dispatch per readiness group,
                         issued first-ready first, reassembled in index
                         order (bitwise the stacked result);
* ``axis=None``          no collective: the local compress->decompress
                         roundtrip at the exchange's own granularity (what
                         error feedback accumulates against);
* ``axis="data"``/tuple  the cross-worker mean over that mesh axis.

The legacy names (``exchange``, ``exchange_flat``, ``local_roundtrip``,
``local_roundtrip_flat``, and ``scheduler.exchange_streamed`` /
``local_roundtrip_streamed``) remain as thin deprecated shims over ``run``
and emit ``DeprecationWarning``.

With ``stacked=True`` (the default) and a stacked-capable compressor, each
dispatch compresses EVERY bucket with one batched kernel pass
(``compress_stacked``) and moves ONE ``StackedPayload`` per collective —
while staying bitwise-equal to the per-bucket loop (per-bucket quantizers
included).  ``stacked=False`` or a loop-only compressor (terngrad/qsgd)
falls back to the per-bucket path.
"""

from __future__ import annotations

import warnings
from typing import List, Sequence

import jax
import jax.numpy as jnp

from repro.comms import bucketing
from repro.comms.collectives import axis_size
from repro.core import fft as cfft

__all__ = ["Transport", "get_transport", "TRANSPORT_NAMES", "two_level_axes", "collective"]

TRANSPORT_NAMES = ("allgather", "sequenced", "psum", "hierarchical",
                   "reduce_scatter")


def two_level_axes(axis) -> tuple:
    """Validate a hierarchical transport's axis spec -> (node_axis, local_axis).

    The hierarchical transport is the only one whose two hops ride DIFFERENT
    links, so it refuses a flat axis instead of silently degenerating: the
    caller must say which axis is the slow fabric and which the fast
    intra-node link.
    """
    if (isinstance(axis, (tuple, list)) and len(axis) == 2
            and all(isinstance(a, str) for a in axis)):
        return tuple(axis)
    raise ValueError(
        f"hierarchical transport needs axis=(node_axis, local_axis) over a "
        f"2-D mesh (launch.mesh.make_two_level_mesh), got {axis!r}")


def _warn_deprecated(old: str) -> None:
    warnings.warn(
        f"Transport.{old}() is deprecated; call Transport.run(flat, "
        f"comp=..., layout=/plan=..., axis=...) instead (DESIGN.md §20)",
        DeprecationWarning, stacklevel=3)


def _concat_index_order(parts):
    """Readiness-ordered group results -> flat buffer in index order.

    ``StreamPlan`` groups are strictly descending in the flat space
    (validated in ``StreamPlan.__post_init__``), so index order is exactly
    the reverse of dispatch order."""
    ordered = list(reversed(parts))
    return ordered[0] if len(ordered) == 1 else jnp.concatenate(ordered)


def _compress_all(buckets: Sequence[jnp.ndarray], comp, monitor=None) -> List:
    """Per-bucket payloads; FFTCompressor fits one quantizer per bucket.

    ``monitor`` (comms.faults.ExchangeMonitor, DESIGN.md §19) intercepts
    every locally created payload before it reaches a collective: planned
    wire corruption is injected and the validation verdict accumulated.
    ``None`` (the default) is the zero-overhead path.
    """
    if hasattr(comp, "compress_buckets"):
        payloads = comp.compress_buckets(buckets)
    else:
        payloads = [comp.compress(b) for b in buckets]
    if monitor is not None:
        payloads = [monitor.on_payload(p) for p in payloads]
    return payloads


def _can_stack(comp) -> bool:
    return hasattr(comp, "compress_stacked")


def _compress_stacked(flat: jnp.ndarray, layout, comp, monitor=None):
    """ONE batched compress of every bucket (same quantizer granularity as
    the per-bucket loop: one fit per bucket row)."""
    payload = comp.compress_stacked(
        bucketing.stack_buckets(flat, layout), layout.sizes())
    return payload if monitor is None else monitor.on_payload(payload)


def _irfft_rows(mean_spectrum: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """(B, max_chunks, f) mean spectrum -> (B, padded_size) time domain."""
    with jax.named_scope("exchange.irfft"):
        x = cfft.irfft_rows(mean_spectrum, chunk)
    return x.reshape(mean_spectrum.shape[0], -1)


def _ordered_worker_mean(stacked: jnp.ndarray) -> jnp.ndarray:
    """Mean over the leading (worker) axis as a left-to-right fold.

    The fold order matters for bitwise reproducibility, not correctness: the
    CPU backend's all-reduce sums contributions in worker order, so folding the
    gathered reconstructions the same way makes the gather transports produce
    bit-identical means to the psum transport (seeded-determinism contract,
    tests/test_transports.py).  ``jnp.mean``'s pairwise reduction would differ
    by ~1 ulp and the divergence compounds over training steps.
    """
    p = stacked.shape[0]
    acc = stacked[0]
    for w in range(1, p):
        acc = acc + stacked[w]
    return acc * (1.0 / p)


def _gather_mean_payload(payload, comp, axis: str) -> jnp.ndarray:
    """Seed exchange: all_gather one payload -> mean reconstruction.

    For spectral compressors the mean is taken in the frequency domain and a
    single inverse FFT recovers the time-domain mean (FFT linearity).
    """
    gathered = collective(jax.lax.all_gather, payload, axis)  # leading axis: workers
    if hasattr(comp, "decompress_spectrum"):
        mean_spectrum = comp.mean_spectrum(gathered)
        with jax.named_scope("exchange.irfft"):
            return cfft.chunked_irfft(mean_spectrum, payload.orig_len, payload.chunk)
    decompressed = jax.vmap(comp.decompress)(gathered)
    return _ordered_worker_mean(decompressed)


def _psum_mean_payload(payload, comp, axis: str) -> jnp.ndarray:
    """Dequantize locally -> psum -> /P (-> one iFFT if spectral).

    NOTE: ``jax.lax.psum`` here moves the DENSE dequantized spectrum — this
    is the reference implementation of the psum semantics, not the O(k)
    wire-optimal sparse allreduce the cost model prices (see
    ``cost_model.transport_wire_bits``).  Even dense it beats the payload
    all-gather once P > 2F/k, and XLA may further optimize the reduction.
    """
    inv_p = 1.0 / axis_size(axis)
    if hasattr(comp, "decompress_spectrum"):
        spec = comp.decompress_spectrum(payload)
        # psum real/imag planes separately: complex psum support varies by
        # backend, and two f32 reductions lower to one fused collective anyway
        summed = collective(jax.lax.psum, jnp.stack([spec.real, spec.imag]), axis)
        mean_spectrum = (summed[0] + 1j * summed[1]) * inv_p
        with jax.named_scope("exchange.irfft"):
            return cfft.chunked_irfft(mean_spectrum, payload.orig_len, payload.chunk)
    return collective(jax.lax.psum, comp.decompress(payload), axis) * inv_p


class Transport:
    """Exchange interface.

    The single public entry point is :meth:`run`; subclasses implement the
    private dispatch hooks:

    * ``_exchange_flat`` / ``_roundtrip_flat`` — the batched-executor paths
      (whole flat buffer + bucket layout), overridden with stacked
      single-collective implementations;
    * ``_exchange_buckets`` / ``_roundtrip_buckets`` — the per-bucket loop
      fallback (and the path for compressors with no stacked support).

    ``run(axis=None)`` exposes the compress->decompress reconstruction at
    the SAME granularity the transport ships at, so error feedback
    accumulates exactly what this transport drops (per-bucket quantizers
    and all).
    """

    name: str = "base"

    # -- the single public entry point (DESIGN.md §20) ----------------------

    def run(self, flat: jnp.ndarray, *, comp, layout=None, axis=None,
            plan=None, stacked: bool = True, monitor=None) -> jnp.ndarray:
        """One dispatch surface for every exchange shape.

        Args:
          flat: the whole flat f32 buffer (gradient, delta, ...).
          comp: the compressor carrying the wire codec.
          layout: ``BucketLayout`` for one stacked dispatch over the whole
            buffer.  Mutually exclusive with ``plan``.
          axis: mesh axis name (or tuple for two-level transports) to mean
            over; ``None`` runs the LOCAL compress->decompress roundtrip —
            no collective — at the transport's own granularity.
          plan: a ``scheduler.StreamPlan``: dispatch one collective per
            readiness group, first-ready first, and reassemble in index
            order (bitwise the ``layout=`` result; DESIGN.md §15).
          stacked: batched single-collective path (default) vs the
            per-bucket loop.
          monitor: ``comms.faults.ExchangeMonitor`` threading the resilience
            layer through every payload-creation site; the roundtrip
            (error-feedback) path is deliberately NOT monitored — the
            residual never crosses the wire (DESIGN.md §19).

        Returns the flat mean (``axis`` given) or the flat reconstruction
        (``axis=None``), same shape as ``flat``.
        """
        if plan is not None:
            if layout is not None:
                raise ValueError("run() takes layout= or plan=, not both")
            parts = [
                self._run_one(flat[lo:hi], sub, comp, axis, stacked, monitor)
                for lo, hi, sub in plan.group_slices()  # readiness order
            ]
            return _concat_index_order(parts)
        if layout is None:
            raise ValueError("run() needs a layout= or a plan=")
        return self._run_one(flat, layout, comp, axis, stacked, monitor)

    def _run_one(self, flat, layout, comp, axis, stacked, monitor):
        if axis is None:
            return self._roundtrip_flat(flat, layout, comp, stacked)
        return self._exchange_flat(flat, layout, comp, axis, stacked, monitor)

    # -- deprecated shims (kept for one release; DESIGN.md §20) -------------

    def exchange(self, buckets: Sequence[jnp.ndarray], comp, axis: str,
                 monitor=None) -> List[jnp.ndarray]:
        _warn_deprecated("exchange")
        return self._exchange_buckets(buckets, comp, axis, monitor=monitor)

    def local_roundtrip(self, buckets: Sequence[jnp.ndarray],
                        comp) -> List[jnp.ndarray]:
        _warn_deprecated("local_roundtrip")
        return self._roundtrip_buckets(buckets, comp)

    def exchange_flat(self, flat: jnp.ndarray, layout, comp, axis: str,
                      stacked: bool = True, monitor=None) -> jnp.ndarray:
        _warn_deprecated("exchange_flat")
        return self.run(flat, comp=comp, layout=layout, axis=axis,
                        stacked=stacked, monitor=monitor)

    def local_roundtrip_flat(self, flat: jnp.ndarray, layout, comp,
                             stacked: bool = True) -> jnp.ndarray:
        _warn_deprecated("local_roundtrip_flat")
        return self.run(flat, comp=comp, layout=layout, stacked=stacked)

    # -- per-bucket loop hooks ----------------------------------------------

    def _exchange_buckets(self, buckets: Sequence[jnp.ndarray], comp,
                          axis: str, monitor=None) -> List[jnp.ndarray]:
        raise NotImplementedError

    def _roundtrip_buckets(self, buckets: Sequence[jnp.ndarray],
                           comp) -> List[jnp.ndarray]:
        return [comp.decompress(p) for p in _compress_all(buckets, comp)]

    # -- flat (batched-executor) hooks, DESIGN.md §14 ------------------------

    def _exchange_flat(self, flat: jnp.ndarray, layout, comp, axis: str,
                       stacked: bool = True, monitor=None) -> jnp.ndarray:
        """Whole-gradient exchange over a bucket layout -> flat mean.

        Default: the per-bucket loop (split -> exchange -> concat).  Stacked
        transports override this with the single-collective path.
        """
        del stacked  # loop fallback ignores the flag
        buckets = bucketing.split_buckets(flat, layout)
        return bucketing.concat_buckets(
            self._exchange_buckets(buckets, comp, axis, monitor=monitor),
            layout)

    def _roundtrip_flat(self, flat: jnp.ndarray, layout, comp,
                        stacked: bool = True) -> jnp.ndarray:
        del stacked
        buckets = bucketing.split_buckets(flat, layout)
        return bucketing.concat_buckets(
            self._roundtrip_buckets(buckets, comp), layout)


class AllGatherTransport(Transport):
    """Seed behavior: ONE monolithic payload all_gather, global quantizer."""

    name = "allgather"

    def _exchange_buckets(self, buckets, comp, axis, monitor=None):
        sizes = [int(b.shape[0]) for b in buckets]
        flat = buckets[0] if len(buckets) == 1 else jnp.concatenate(list(buckets))
        payload = comp.compress(flat)
        if monitor is not None:
            payload = monitor.on_payload(payload)
        mean = _gather_mean_payload(payload, comp, axis)
        return _resplit(mean, sizes)

    def _roundtrip_buckets(self, buckets, comp):
        sizes = [int(b.shape[0]) for b in buckets]
        flat = buckets[0] if len(buckets) == 1 else jnp.concatenate(list(buckets))
        return _resplit(comp.decompress(comp.compress(flat)), sizes)

    # monolithic by definition: already one payload, one collective — the
    # flat entry points skip the bucket split/concat entirely
    def _exchange_flat(self, flat, layout, comp, axis, stacked=True,
                       monitor=None):
        del layout, stacked
        payload = comp.compress(flat)
        if monitor is not None:
            payload = monitor.on_payload(payload)
        return _gather_mean_payload(payload, comp, axis)

    def _roundtrip_flat(self, flat, layout, comp, stacked=True):
        del layout, stacked
        return comp.decompress(comp.compress(flat))


class SequencedTransport(Transport):
    """Bucketed all_gather with per-bucket quantizer ranges.

    Stacked (default): ONE all_gather of the whole exchange's
    ``StackedPayload`` — a single collective launch carrying every bucket's
    codes, indices, and quantizer params as struct-of-arrays planes.  Looped
    fallback: one independent all_gather PER BUCKET (XLA's latency-hiding
    scheduler may pipeline them, at n_buckets collective launches).  Both
    paths realize the same mean bitwise.
    """

    name = "sequenced"

    def _exchange_buckets(self, buckets, comp, axis, monitor=None):
        payloads = _compress_all(buckets, comp, monitor)
        return [_gather_mean_payload(p, comp, axis) for p in payloads]

    def _exchange_flat(self, flat, layout, comp, axis, stacked=True,
                       monitor=None):
        if not (stacked and _can_stack(comp)):
            return super()._exchange_flat(flat, layout, comp, axis, stacked,
                                          monitor=monitor)
        payload = _compress_stacked(flat, layout, comp, monitor)
        gathered = collective(jax.lax.all_gather, payload, axis)  # ONE collective
        if hasattr(comp, "decompress_spectrum"):
            mean = comp.mean_spectrum(gathered)  # (B, max_chunks, f)
            return bucketing.unstack_buckets(
                _irfft_rows(mean, layout.chunk), layout)
        recon = jax.vmap(comp.decompress_stacked)(gathered)  # (W, B, padded)
        return bucketing.unstack_buckets(_ordered_worker_mean(recon), layout)

    def _roundtrip_flat(self, flat, layout, comp, stacked=True):
        if not (stacked and _can_stack(comp)):
            return super()._roundtrip_flat(flat, layout, comp, stacked)
        payload = _compress_stacked(flat, layout, comp)
        return bucketing.unstack_buckets(
            comp.decompress_stacked(payload), layout)


class SpectrumPsumTransport(Transport):
    """Psum of dequantized spectra: O(k) wire, P-independent.

    Stacked (default): every bucket's dequantized spectrum rides ONE psum of
    the ``(2, n_buckets, max_chunks, f)`` plane stack — a single collective
    launch — followed by one batched inverse FFT.  Looped fallback: one psum
    per bucket.
    """

    name = "psum"

    def _exchange_buckets(self, buckets, comp, axis, monitor=None):
        payloads = _compress_all(buckets, comp, monitor)
        return [_psum_mean_payload(p, comp, axis) for p in payloads]

    def _exchange_flat(self, flat, layout, comp, axis, stacked=True,
                       monitor=None):
        if not (stacked and _can_stack(comp)):
            return super()._exchange_flat(flat, layout, comp, axis, stacked,
                                          monitor=monitor)
        payload = _compress_stacked(flat, layout, comp, monitor)
        inv_p = 1.0 / axis_size(axis)
        if hasattr(comp, "decompress_spectrum"):
            spec = comp.decompress_spectrum(payload)  # (B, max_chunks, f)
            summed = collective(jax.lax.psum, jnp.stack([spec.real, spec.imag]), axis)
            mean = (summed[0] + 1j * summed[1]) * inv_p
            return bucketing.unstack_buckets(
                _irfft_rows(mean, layout.chunk), layout)
        summed = collective(jax.lax.psum, comp.decompress_stacked(payload), axis)
        return bucketing.unstack_buckets(summed * inv_p, layout)

    def _roundtrip_flat(self, flat, layout, comp, stacked=True):
        if not (stacked and _can_stack(comp)):
            return super()._roundtrip_flat(flat, layout, comp, stacked)
        payload = _compress_stacked(flat, layout, comp)
        return bucketing.unstack_buckets(
            comp.decompress_stacked(payload), layout)


class HierarchicalTransport(Transport):
    """Two-level exchange over a (node, local) mesh (DESIGN.md §18).

    Dataflow per exchange (stacked path, spectral compressor):

    1. every worker runs the chunked rfft of its buckets — the DENSE
       spectrum, no thresholding: the intra-node psum moves dense spectra
       planes either way (the psum semantics, ``_psum_mean_payload``), so a
       leaf-level top-k would add loss without saving a single intra byte;
    2. intra-node: ONE ``psum`` of the dense spectra planes over the fast
       ``local`` axis — FFT linearity accumulates the deltas in the
       spectrum, and the psum's result is already replicated across the
       island (the "broadcast" of step 4 is free);
    3. compress the node-mean signal ONCE per island — the ONLY lossy step
       — so the slow inter-node fabric moves exactly one compressed
       ``StackedPayload`` per node instead of one per worker;
    4. inter-node: all_gather of the per-node payloads over ``node``, folded
       left-to-right (``_ordered_worker_mean``) so every worker — and every
       run — produces bit-identical means.

    The node-level compression keeps top-k of the ISLAND MEAN's spectrum
    rather than per-worker top-k of each leaf spectrum, so the hierarchical
    mean tracks the flat psum mean within the lab's tolerance envelope
    rather than bitwise — the accuracy claim ``hierarchical_matches_flat``
    (lab/evaluate.py) guards the gap.  Determinism is still exact: fixed
    psum order on an island, fixed fold order across islands.

    Degrades gracefully for non-spectral compressors: the intra-node psum
    runs on the raw time-domain bucket rows (equal by linearity, same wire).
    """

    name = "hierarchical"

    def _exchange_buckets(self, buckets, comp, axis, monitor=None):
        node_ax, local_ax = two_level_axes(axis)
        inv_l = 1.0 / axis_size(local_ax)
        # loop fallback psums the raw time-domain buckets (== the spectra
        # psum by FFT linearity, same dense wire), then compresses the node
        # mean once per island
        node_means = [collective(jax.lax.psum, b, local_ax) * inv_l for b in buckets]
        node_payloads = _compress_all(node_means, comp, monitor)
        return [_gather_mean_payload(p, comp, node_ax) for p in node_payloads]

    def _exchange_flat(self, flat, layout, comp, axis, stacked=True,
                       monitor=None):
        node_ax, local_ax = two_level_axes(axis)
        if not (stacked and _can_stack(comp)):
            return super()._exchange_flat(flat, layout, comp, axis, stacked,
                                          monitor=monitor)
        inv_l = 1.0 / axis_size(local_ax)
        rows = bucketing.stack_buckets(flat, layout)  # (B, padded)
        if hasattr(comp, "decompress_spectrum"):
            x3 = rows.reshape(layout.n_buckets, -1, layout.chunk)
            with jax.named_scope("exchange.rfft"):
                spec = cfft.rfft_rows(x3)  # DENSE spectra — no top-k
            summed = collective(jax.lax.psum, jnp.stack([spec.real, spec.imag]), local_ax)
            node_mean = bucketing.unstack_buckets(
                _irfft_rows((summed[0] + 1j * summed[1]) * inv_l, layout.chunk),
                layout)
        else:
            node_mean = bucketing.unstack_buckets(
                collective(jax.lax.psum, rows, local_ax) * inv_l, layout)
        # compress ONCE per island: this payload is the only thing the
        # inter-node fabric carries (every island worker holds the same
        # node_mean after the psum, so the fabric sees one copy per node)
        node_payload = _compress_stacked(node_mean, layout, comp, monitor)
        gathered = collective(jax.lax.all_gather, node_payload, node_ax)
        if hasattr(comp, "decompress_spectrum"):
            mean = comp.mean_spectrum(gathered)
            return bucketing.unstack_buckets(
                _irfft_rows(mean, layout.chunk), layout)
        recon = jax.vmap(comp.decompress_stacked)(gathered)
        return bucketing.unstack_buckets(_ordered_worker_mean(recon), layout)

    def _roundtrip_flat(self, flat, layout, comp, stacked=True):
        # EF residual: the exchange's only loss is the island-level compress
        # of the node MEAN — per-worker state can't hold island-shared loss,
        # so the residual accumulates this worker's own compress roundtrip
        # as the local estimate of what the island compress drops (same
        # compressor, same theta, same bucket granularity as the flat
        # transports); see DESIGN.md §18
        if not (stacked and _can_stack(comp)):
            return super()._roundtrip_flat(flat, layout, comp, stacked)
        payload = _compress_stacked(flat, layout, comp)
        return bucketing.unstack_buckets(
            comp.decompress_stacked(payload), layout)


class ReduceScatterTransport(Transport):
    """Bucket-partitioned reduce: psum_scatter over the bucket axis.

    Stacked path: the dequantized spectra planes (leading axis = buckets,
    padded to a multiple of P with zero rows) ride ONE ``psum_scatter``;
    worker i receives the reduced planes of the contiguous bucket range
    ``[i·B/P, (i+1)·B/P)`` — exactly a ``bucketing.sub_layout`` ownership
    range — runs the inverse FFT only on its own rows, and a tiled
    ``all_gather`` of the TIME-DOMAIN rows rebuilds the flat buffer.
    Per-worker wire is ring-allreduce-shaped (2·(P-1)/P of the dense
    planes): unlike the gather transports it stops growing with P.

    ``axis`` may be one name or a tuple (the tuple is treated as one
    flattened worker axis — ``psum_scatter``/``all_gather`` accept both).
    Per-bucket loop fallback degrades to the psum transport's per-bucket
    exchange (same mean; a single bucket has nothing to scatter).
    """

    name = "reduce_scatter"

    def _exchange_buckets(self, buckets, comp, axis, monitor=None):
        payloads = _compress_all(buckets, comp, monitor)
        return [_psum_mean_payload(p, comp, axis) for p in payloads]

    def _exchange_flat(self, flat, layout, comp, axis, stacked=True,
                       monitor=None):
        if not (stacked and _can_stack(comp)):
            return super()._exchange_flat(flat, layout, comp, axis, stacked,
                                          monitor=monitor)
        p = axis_size(axis)
        inv_p = 1.0 / p
        payload = _compress_stacked(flat, layout, comp, monitor)
        if hasattr(comp, "decompress_spectrum"):
            spec = comp.decompress_spectrum(payload)  # (B, max_chunks, f)
            planes = jnp.stack([spec.real, spec.imag], axis=1)  # (B, 2, c, f)
        else:
            planes = comp.decompress_stacked(payload)[:, None, :]  # (B, 1, n)
        b = planes.shape[0]
        pad_rows = (-b) % p
        if pad_rows:
            planes = jnp.concatenate(
                [planes, jnp.zeros((pad_rows,) + planes.shape[1:],
                                   planes.dtype)])
        shard = collective(jax.lax.psum_scatter, planes, axis,
                           scatter_dimension=0, tiled=True)  # (B'/P, 2, c, f)
        if hasattr(comp, "decompress_spectrum"):
            mean_spec = (shard[:, 0] + 1j * shard[:, 1]) * inv_p
            rows = _irfft_rows(mean_spec, layout.chunk)  # (B'/P, padded)
        else:
            rows = shard[:, 0] * inv_p
        full = collective(jax.lax.all_gather, rows, axis, tiled=True)  # (B', padded)
        return bucketing.unstack_buckets(full[:b], layout)

    def _roundtrip_flat(self, flat, layout, comp, stacked=True):
        if not (stacked and _can_stack(comp)):
            return super()._roundtrip_flat(flat, layout, comp, stacked)
        payload = _compress_stacked(flat, layout, comp)
        return bucketing.unstack_buckets(
            comp.decompress_stacked(payload), layout)


def collective(op, *args, **kwargs):
    """``op(*args, **kwargs)`` under the ``exchange.collective`` scope: the
    device trace names the collective's time apart from the exchange's
    stages (the scope is metadata; the compiled program is the same)."""
    with jax.named_scope("exchange.collective"):
        return op(*args, **kwargs)


def _resplit(flat: jnp.ndarray, sizes: List[int]) -> List[jnp.ndarray]:
    out, off = [], 0
    for s in sizes:
        out.append(flat[off : off + s])
        off += s
    return out


_TRANSPORTS = {
    t.name: t for t in (AllGatherTransport(), SequencedTransport(),
                        SpectrumPsumTransport(), HierarchicalTransport(),
                        ReduceScatterTransport())
}


def get_transport(name: str) -> Transport:
    try:
        return _TRANSPORTS[name]
    except KeyError:
        raise ValueError(
            f"unknown transport {name!r}; expected one of {TRANSPORT_NAMES}"
        ) from None
