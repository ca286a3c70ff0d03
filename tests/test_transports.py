"""Transport equivalence across 8 fake devices: every transport must produce
the same mean as the seed all_gather path (ISSUE 1 acceptance), including the
error-feedback and hierarchical modes.

With quantization OFF the bucketed paths are bit-identical to the monolithic
seed path (chunk-aligned bucket boundaries keep per-chunk top-k selection
unchanged; FFT linearity keeps the means equal), so the comparison is exact
up to f32 reduction order.  With quantization ON, per-bucket quantizer fits
differ from the global fit, so agreement is within quantization tolerance.
"""

from helpers import run_with_devices

SMAP_COMPAT = """
import jax
from repro.jaxcompat import make_auto_mesh, shard_map as smap
"""


def test_all_transports_match_seed_allgather_mean():
    out = run_with_devices(SMAP_COMPAT + """
import dataclasses
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comms import ReducerConfig, make_reducer

mesh = make_auto_mesh((8,), ("data",))
grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 3 * 4096 + 173)) * 0.1,
         "b": jax.random.normal(jax.random.PRNGKey(1), (8, 64)) * 0.1}
dense = jax.tree.map(lambda x: np.asarray(x.mean(0)), grads)

def run(cfg):
    r = make_reducer(cfg)
    f = smap(lambda g: r(jax.tree.map(lambda x: x[0], g)),
             mesh=mesh, in_specs=P("data"), out_specs=P())
    return jax.tree.map(np.asarray, jax.jit(f)(grads))

def flat(t):
    return np.concatenate([np.ravel(t[k]) for k in sorted(t)])

for kind in ("fft", "timedomain"):
    # seed path: monolithic all_gather, no bucketing
    seed_cfg = ReducerConfig(kind=kind, axis="data", theta=0.5, quantize=False)
    seed = run(seed_cfg)
    for transport in ("allgather", "sequenced", "psum"):
        got = run(dataclasses.replace(seed_cfg, transport=transport,
                                      bucket_bytes=4096 * 4))
        err = np.abs(flat(got) - flat(seed)).max()
        assert err < 1e-5, (kind, transport, err)
    # quantized: per-bucket fits agree with the global fit within quant tol
    seed_q = run(dataclasses.replace(seed_cfg, quantize=True))
    for transport in ("sequenced", "psum"):
        got = run(dataclasses.replace(seed_cfg, quantize=True,
                                      transport=transport, bucket_bytes=4096 * 4))
        rel = (np.linalg.norm(flat(got) - flat(seed_q))
               / np.linalg.norm(flat(seed_q)))
        assert rel < 0.1, (kind, transport, rel)
    # and every transport still approximates the dense mean (Assumption 3.1)
    rel_dense = (np.linalg.norm(flat(seed) - flat(dense))
                 / np.linalg.norm(flat(dense)))
    assert rel_dense < 0.5 ** 0.5 + 1e-3, (kind, rel_dense)
print("TRANSPORTS_OK")
""")
    assert "TRANSPORTS_OK" in out


def test_error_feedback_identical_across_transports():
    out = run_with_devices(SMAP_COMPAT + """
import dataclasses
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comms import ReducerConfig, make_reducer

mesh = make_auto_mesh((4,), ("data",))
n = 2 * 4096 + 301
g = {"w": jnp.tile(jnp.sin(jnp.arange(n) / 50.0)[None] * 0.1, (4, 1))}

def run_ef(cfg):
    r = make_reducer(cfg)
    def step(grads, res):
        out, new_res = r(jax.tree.map(lambda x: x[0], grads), res[0])
        return out["w"], new_res[None]
    f = smap(step, mesh=mesh, in_specs=(P("data"), P("data")),
             out_specs=(P(), P("data")))
    res = jnp.zeros((4, n))
    outs = []
    for _ in range(3):
        got, res = jax.jit(f)(g, res)
        outs.append(np.asarray(got))
    return outs, np.asarray(res)

seed_cfg = ReducerConfig(kind="fft", axis="data", theta=0.9,
                         error_feedback=True, quantize=False)
seed_outs, seed_res = run_ef(seed_cfg)
for transport in ("allgather", "sequenced", "psum"):
    outs, res = run_ef(dataclasses.replace(seed_cfg, transport=transport,
                                           bucket_bytes=4096 * 4))
    for a, b in zip(outs, seed_outs):
        assert np.abs(a - b).max() < 1e-5, transport
    assert np.abs(res - seed_res).max() < 1e-5, transport
# EF still does its job: residual is exactly what compression dropped
assert np.linalg.norm(seed_res) > 0.0
print("EF_TRANSPORTS_OK")
""", devices=4)
    assert "EF_TRANSPORTS_OK" in out


def test_seeded_determinism_bitwise_across_transports(tmp_path):
    """Identical seed + config must produce bitwise-identical checkpoints
    regardless of transport: the gather transports fold worker contributions
    in the same order the CPU backend's all-reduce sums them (see
    transport._ordered_worker_mean), so allgather/sequenced/psum realize the
    SAME f32 mean bit-for-bit, and a rerun of any transport is bitwise
    reproducible.  This is what makes transport choice a pure performance
    knob: switching transports mid-experiment can never change the training
    trajectory."""
    out = run_with_devices(SMAP_COMPAT + f"""
import dataclasses, os
import numpy as np
from repro.comms.reducers import ReducerConfig
from repro.configs.base import ArchConfig
from repro.data import SyntheticConfig, SyntheticStream
from repro.jaxcompat import set_mesh
from repro.models.transformer import LM
from repro.optim import OptConfig
from repro.train import TrainLoopConfig, init_state, train_loop
from repro.train import checkpoint as ckpt
from repro.train.step import StepConfig

TINY = ArchConfig(name="tiny", family="dense", n_layers=1, d_model=32,
                  n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                  vocab_size=32, remat="none")
mesh = make_auto_mesh((4,), ("data",))
model = LM(TINY)
opt = OptConfig(kind="adamw", lr=3e-3)
stream = SyntheticStream(SyntheticConfig(vocab_size=32, seq_len=16, global_batch=8))

def run(transport, tag):
    cfg = StepConfig(mode="compressed_dp", reducer=ReducerConfig(
        kind="fft", axis="data", theta=0.7, quantize=True, transport=transport))
    state = init_state(jax.random.PRNGKey(7), model, opt)
    ckdir = os.path.join({str(tmp_path)!r}, tag)
    with set_mesh(mesh):
        train_loop(model, opt, cfg, mesh, state, stream,
                   TrainLoopConfig(total_steps=8, ckpt_dir=ckdir,
                                   ckpt_every=8, log_every=100))
    return ckdir

def arrays(ckdir):
    d = np.load(os.path.join(ckdir, "step_00000008", "arrays.npz"))
    return {{k: d[k] for k in d.files}}

base = arrays(run("allgather", "ag"))
rerun = arrays(run("allgather", "ag2"))
for k in base:
    assert np.array_equal(base[k], rerun[k]), ("rerun nondeterminism", k)
for transport in ("sequenced", "psum"):
    got = arrays(run(transport, transport))
    assert set(got) == set(base)
    for k in base:
        assert base[k].dtype == got[k].dtype and np.array_equal(base[k], got[k]), (
            transport, k, np.abs(base[k].astype(np.float64)
                                 - got[k].astype(np.float64)).max())
print("DETERMINISM_OK")
""", devices=4, timeout=560)
    assert "DETERMINISM_OK" in out


def test_hierarchical_mode_across_transports():
    out = run_with_devices(SMAP_COMPAT + """
import dataclasses
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comms import ReducerConfig, make_reducer

mesh = make_auto_mesh((2, 4), ("pod", "data"))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 2 * 4096 + 87)) * 0.1
expect = np.asarray(g.mean(0))

def run(cfg):
    r = make_reducer(cfg)
    f = smap(lambda v: r({"g": v[0]})["g"],
             mesh=mesh, in_specs=P(("pod", "data")), out_specs=P())
    return np.asarray(jax.jit(f)(g))

seed_cfg = ReducerConfig(kind="hierarchical", axis="data", pod_axis="pod",
                         theta=0.3, quantize=False)
seed = run(seed_cfg)
for transport in ("allgather", "sequenced", "psum"):
    got = run(dataclasses.replace(seed_cfg, transport=transport,
                                  bucket_bytes=4096 * 4))
    assert np.abs(got - seed).max() < 1e-5, transport
    # intra-pod mean is exact; only the pod-axis exchange is lossy
    rel = np.linalg.norm(got - expect) / np.linalg.norm(expect)
    assert rel < 0.35, (transport, rel)
print("HIER_TRANSPORTS_OK")
""")
    assert "HIER_TRANSPORTS_OK" in out


# ---------------------------------------------------------------------------
# Two-level (node x local) topology suite — DESIGN.md §18
# ---------------------------------------------------------------------------

def test_two_level_transports_match_flat_psum_mean():
    """hierarchical and reduce_scatter on a (2, 4) mesh track the flat psum
    transport over the same 8 workers: reduce_scatter realizes the identical
    mean (same dequantize -> reduce -> iFFT numerics, just bucket-partitioned),
    and hierarchical — whose only loss is the single island-level compress of
    the node mean — stays inside the lab's 5% envelope on CORRELATED worker
    gradients with energy-concentrated spectra (what real data-parallel
    gradients look like; on WHITE iid noise every coefficient sits at the
    top-k threshold, kept sets churn, and the envelope is meaningless by
    design — the lab rows measure the realistic case end-to-end)."""
    out = run_with_devices(SMAP_COMPAT + """
import dataclasses
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comms import ReducerConfig, make_reducer

mesh = make_auto_mesh((2, 4), ("node", "local"))
n = 3 * 4096 + 173

def lowpass(key, shape):
    # moving-average filter concentrates spectral energy like real gradients
    raw = jax.random.normal(key, shape[:-1] + (n + 64,))
    k = jnp.ones(64) / 64.0
    f = lambda r: jnp.convolve(r, k, mode="valid")[:n]
    return f(raw) if raw.ndim == 1 else jax.vmap(f)(raw)

base = lowpass(jax.random.PRNGKey(0), (n,))
noise = lowpass(jax.random.PRNGKey(1), (8, n)) * 0.1
g = {"w": base[None] + noise}  # correlated workers: shared signal, small jitter
dense = np.asarray(g["w"].mean(0))

def run(cfg):
    r = make_reducer(cfg)
    f = smap(lambda v: r({"w": v[0]})["w"],
             mesh=mesh, in_specs=P(("node", "local")), out_specs=P())
    return np.asarray(jax.jit(f)(g["w"]))

base_cfg = ReducerConfig(kind="fft", axis=("node", "local"), theta=0.7,
                         quantize=False, bucket_bytes=4096 * 4)
flat = run(dataclasses.replace(base_cfg, transport="psum"))
rs = run(dataclasses.replace(base_cfg, transport="reduce_scatter"))
hier = run(dataclasses.replace(base_cfg, transport="hierarchical"))

# reduce_scatter: identical mean, only the dispatch differs
assert np.abs(rs - flat).max() < 1e-5, np.abs(rs - flat).max()
# hierarchical: one island-level compress of the node mean; 5% envelope
rel = np.linalg.norm(hier - flat) / np.linalg.norm(flat)
assert rel < 0.05, rel
# and all three track the dense mean closely on energy-concentrated data
for name, got in (("psum", flat), ("hier", hier), ("rs", rs)):
    rel_d = np.linalg.norm(got - dense) / np.linalg.norm(dense)
    assert rel_d < 0.2, (name, rel_d)
# quantized run: per-bucket quantizer fits stay within the same envelope
flat_q = run(dataclasses.replace(base_cfg, transport="psum", quantize=True))
hier_q = run(dataclasses.replace(base_cfg, transport="hierarchical",
                                 quantize=True))
rel_q = np.linalg.norm(hier_q - flat_q) / np.linalg.norm(flat_q)
assert rel_q < 0.05, rel_q
print("TWO_LEVEL_MEANS_OK")
""")
    assert "TWO_LEVEL_MEANS_OK" in out


def test_two_level_error_feedback_residual_parity():
    """EF residual parity through reducers.py: the residual accumulates each
    worker's OWN compress roundtrip at the exchange's bucket granularity on
    every transport — psum, hierarchical, and reduce_scatter must produce the
    same residual state given the same inputs (the hierarchical mean differs;
    the residual contract does not), and the residual must be nonzero (EF is
    actually accumulating dropped signal)."""
    out = run_with_devices(SMAP_COMPAT + """
import dataclasses
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comms import ReducerConfig, make_reducer

mesh = make_auto_mesh((2, 4), ("node", "local"))
n = 2 * 4096 + 301
g = jnp.tile(jnp.sin(jnp.arange(n) / 50.0)[None] * 0.1, (8, 1))

def run_ef(cfg):
    r = make_reducer(cfg)
    def step(grads, res):
        out, new_res = r({"w": grads[0]}, res[0])
        return out["w"], new_res[None]
    f = smap(step, mesh=mesh, in_specs=(P(("node", "local")),) * 2,
             out_specs=(P(), P(("node", "local"))))
    res = jnp.zeros((8, n))
    for _ in range(3):
        got, res = jax.jit(f)(g, res)
    return np.asarray(got), np.asarray(res)

base_cfg = ReducerConfig(kind="fft", axis=("node", "local"), theta=0.8,
                         error_feedback=True, quantize=False,
                         bucket_bytes=4096 * 4)
out_p, res_p = run_ef(dataclasses.replace(base_cfg, transport="psum"))
out_h, res_h = run_ef(dataclasses.replace(base_cfg, transport="hierarchical"))
out_r, res_r = run_ef(dataclasses.replace(base_cfg, transport="reduce_scatter"))
assert np.linalg.norm(res_p) > 0.0
assert np.abs(res_h - res_p).max() < 1e-6, np.abs(res_h - res_p).max()
assert np.abs(res_r - res_p).max() < 1e-6, np.abs(res_r - res_p).max()
# reduce_scatter's EF-corrected mean equals psum's (same exchange numerics)
assert np.abs(out_r - out_p).max() < 1e-5
print("TWO_LEVEL_EF_OK")
""")
    assert "TWO_LEVEL_EF_OK" in out


def test_two_level_backend_parity_bitwise():
    """Payloads stay bitwise-comparable across engine backends on the 2-D
    mesh: the pallas and reference backends produce identical codes/spectra
    (test_engine.py), so the hierarchical and reduce_scatter means — which
    compress/decompress through the SAME engine seam — must be bit-identical
    across backends too."""
    out = run_with_devices(SMAP_COMPAT + """
import dataclasses
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comms import ReducerConfig, make_reducer

mesh = make_auto_mesh((2, 4), ("node", "local"))
n = 2 * 4096 + 87
g = jax.random.normal(jax.random.PRNGKey(3), (8, n)) * 0.1

def run(cfg):
    r = make_reducer(cfg)
    f = smap(lambda v: r({"w": v[0]})["w"],
             mesh=mesh, in_specs=P(("node", "local")), out_specs=P())
    return np.asarray(jax.jit(f)(g))

for transport in ("hierarchical", "reduce_scatter"):
    cfg = ReducerConfig(kind="fft", axis=("node", "local"), theta=0.6,
                        quantize=True, bucket_bytes=4096 * 4,
                        transport=transport)
    ref = run(dataclasses.replace(cfg, backend="reference"))
    pal = run(dataclasses.replace(cfg, backend="pallas"))
    dev = np.abs(ref - pal).max()
    assert dev == 0.0, (transport, dev)
print("TWO_LEVEL_BACKENDS_OK")
""")
    assert "TWO_LEVEL_BACKENDS_OK" in out


def test_two_level_inter_node_wire_beats_flat_psum():
    """Cost-model acceptance assertion (ISSUE 8): on every swept (nodes,
    local) shape with >= 4 nodes, the modeled per-worker inter-node wire of
    the hierarchical transport is STRICTLY below the flat psum transport's
    runtime per-worker wire at the same worker count, and for fixed nodes it
    strictly shrinks as the island grows (each worker's share of the fabric
    hop is nodes*B/local)."""
    from repro.comms import cost_model
    from repro.core.compressor import FFTCompressor, FFTCompressorConfig

    n = 6 * 4096 + 321
    comp = FFTCompressor(FFTCompressorConfig(theta=0.7))
    payload = float(comp.wire_bits(n))
    for nodes in (4, 8):
        prev = None
        for local in (2, 4, 8):
            wire = cost_model.two_level_wire_bits(
                payload, nodes, local, mode="runtime", n_elems=n)
            flat = cost_model.transport_wire_bits(
                "psum", payload, nodes * local, mode="runtime", n_elems=n)
            assert wire.inter_bits_per_worker < flat, (
                nodes, local, wire.inter_bits_per_worker, flat)
            assert wire.inter_bits_per_node == nodes * payload
            if prev is not None:
                assert wire.inter_bits_per_worker < prev, (nodes, local)
            prev = wire.inter_bits_per_worker


def test_collectives_tuple_axes_on_2d_mesh():
    """comms/collectives.py multi-axis helpers: axis_size/axis_sizes accept a
    tuple of names (product semantics), axis_linear_index enumerates workers
    row-major over the tuple, and normalize_axes rejects junk specs."""
    out = run_with_devices(SMAP_COMPAT + """
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.comms.collectives import (
    axis_linear_index, axis_size, axis_sizes, normalize_axes)

assert normalize_axes("data") == "data"
assert normalize_axes(["node", "local"]) == ("node", "local")
assert normalize_axes(("local",)) == ("local",)
for bad in ((), ["node", 3]):
    try:
        normalize_axes(bad)
    except ValueError:
        pass
    else:
        raise AssertionError(f"normalize_axes({bad!r}) should raise")

mesh = make_auto_mesh((2, 4), ("node", "local"))

def probe(_):
    sizes = (axis_size("node"), axis_size("local"),
             axis_size(("node", "local")), axis_sizes(("node", "local")))
    assert sizes[:3] == (2, 4, 8), sizes
    assert sizes[3] == (2, 4), sizes
    return axis_linear_index(("node", "local"))[None]

import jax.numpy as jnp
f = smap(probe, mesh=mesh, in_specs=P(("node", "local")),
         out_specs=P(("node", "local")))
idx = np.asarray(jax.jit(f)(jnp.zeros((8,))))
assert list(idx) == list(range(8)), idx  # row-major worker enumeration
print("TUPLE_AXES_OK")
""")
    assert "TUPLE_AXES_OK" in out


def test_two_level_mesh_validation_names_device_count():
    """launch/mesh.py validation: an impossible 2-D shape fails with an error
    naming the device count (not a bare reshape failure), and an uneven
    make_two_level_mesh split names the divisor problem."""
    out = run_with_devices("""
from repro.launch.mesh import make_local_mesh, make_two_level_mesh

mesh = make_local_mesh((2, 4))  # default axes = ("node", "local")
assert mesh.axis_names == ("node", "local"), mesh.axis_names
assert dict(mesh.shape) == {"node": 2, "local": 4}
assert make_two_level_mesh(4).shape["local"] == 2

try:
    make_local_mesh((4, 4), ("node", "local"))
except ValueError as e:
    msg = str(e)
    assert "16 devices" in msg and "8 host devices" in msg, msg
else:
    raise AssertionError("oversized mesh should raise")

try:
    make_two_level_mesh(3)
except ValueError as e:
    assert "do not split evenly" in str(e), e
else:
    raise AssertionError("uneven node split should raise")

try:
    make_local_mesh((2, 2, 2))
except ValueError as e:
    assert "explicit axes" in str(e), e
else:
    raise AssertionError("3-D shape without axes should raise")
print("MESH_VALIDATION_OK")
""")
    assert "MESH_VALIDATION_OK" in out


def test_local_mesh_places_every_device():
    """The default data mesh spans every device, each once — four chips
    train as four replicas, not one."""
    out = run_with_devices("""
import jax
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh()
assert dict(mesh.shape) == {"data": 4}, dict(mesh.shape)
placed = list(mesh.devices.flat)
assert sorted(d.id for d in placed) == sorted(d.id for d in jax.devices())
print("LOCAL_MESH_OK")
""", devices=4)
    assert "LOCAL_MESH_OK" in out
