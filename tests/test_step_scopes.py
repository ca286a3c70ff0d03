"""Every stage of the compiled train step carries its name.

The step runs under three named scopes (``step.fwd_bwd``, ``step.exchange``,
``step.optimizer``) and the FFT exchange's stages under five more
(``exchange.rfft``, ``.select``, ``.pack``, ``.fold``, ``.irfft``).  XLA
keeps each instruction's scope path in its ``op_name`` metadata, fusions
carrying their root's, and the benchmark reads per-stage device time from
those names.  The collectives, and the flattening of the gradient around
them, are the exchange's own time, outside its stages.  These tests compile
tiny steps on the CPU and read the names back from ``compiled.as_text()``.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro import jaxcompat as compat
from repro.comms.reducers import ReducerConfig
from repro.configs.base import ArchConfig
from repro.launch.mesh import make_local_mesh
from repro.models.transformer import LM
from repro.optim import OptConfig
from repro.train import init_state
from repro.train.step import StepConfig, build_train_step

TINY = ArchConfig(name="tiny", family="dense", n_layers=1, d_model=64, n_heads=4,
                  n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64)

STEP_SCOPES = ("step.fwd_bwd", "step.exchange", "step.optimizer")
EXCHANGE_STAGES = ("exchange.rfft", "exchange.select", "exchange.pack",
                   "exchange.fold", "exchange.irfft")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
               "all-to-all")
# operations that do the step's work: each must carry exactly one step scope
WORK = ("fusion", "dot", "convolution", "scatter", "sort", "custom-call", "fft")
# operations of the exchange that must also carry one of its stages
STAGED = ("fft", "sort", "scatter", "custom-call")
# what the CPU compiler makes itself, with no op_name at all: moves, casts
# and the partial sums it splits a long reduction into
COMPILER_ROOTS = {"copy", "convert", "transpose", "bitcast", "broadcast",
                  "reshape", "slice", "concatenate", "pad", "reduce-window"}

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (?:\([^=]*?\)|\S+) ([a-z][a-z0-9\-]*)\((\)?)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")


def _opcode(op: str) -> str:
    return op[:-len("-start")] if op.endswith("-start") else op


def instructions(hlo_text: str):
    """(name, opcode, op_name path or None, root opcode of a fusion's body,
    whether it takes no operands)."""
    roots, current, rows = {}, None, []
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head and line.rstrip().endswith("{"):
            current = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        if line.lstrip().startswith("ROOT ") and current is not None:
            roots[current] = m.group(2)
        path = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        rows.append((m.group(1), _opcode(m.group(2)),
                     path.group(1) if path else None,
                     calls.group(1) if calls else None, bool(m.group(3))))
    return [(name, op, path, roots.get(calls), nullary)
            for name, op, path, calls, nullary in rows]


def components(path: str):
    return path.split("/")


def _compile(mode: str, reducer=None) -> str:
    model = LM(TINY)
    opt = OptConfig(kind="adamw", lr=1e-3)
    mesh = make_local_mesh()
    step_cfg = StepConfig(mode=mode, reducer=reducer)
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32) for k in ("tokens", "targets")}
    state = jax.eval_shape(lambda k: init_state(k, model, opt), jax.random.PRNGKey(0))
    step = build_train_step(model, opt, step_cfg, mesh, batch)
    with compat.set_mesh(mesh):
        return step.lower(state, batch).compile().as_text()


def _fft(transport, **kw):
    return ReducerConfig(kind="fft", axis="data", theta=0.7, chunk=256,
                         transport=transport, backend="reference", **kw)


STEPS = {
    "pjit": ("pjit", None),
    "dense": ("compressed_dp", ReducerConfig(kind="dense", axis="data")),
    "fft_allgather_sort": ("compressed_dp", _fft("allgather", selector="sort")),
    "fft_allgather_sampled": ("compressed_dp", _fft("allgather", selector="sampled")),
    "fft_psum_bucketed": ("compressed_dp",
                          _fft("psum", selector="sort", bucket_bytes=256 * 4 * 64)),
}
_compiled = {}


def compiled(case: str):
    if case not in _compiled:
        _compiled[case] = instructions(_compile(*STEPS[case]))
    return _compiled[case]


@pytest.mark.parametrize("case", list(STEPS))
def test_every_operation_carries_one_step_scope(case):
    for name, op, path, root, nullary in compiled(case):
        if op not in WORK + COLLECTIVES:
            continue
        if path is None:
            # made by the compiler from no JAX operation: no scope can name it
            assert op == "fusion" and root in COMPILER_ROOTS, (name, op, root)
            continue
        scopes = [c for c in components(path) if c in STEP_SCOPES]
        if not scopes and nullary:
            # a constant (the rotary frequencies) that XLA computes once for
            # the forward pass and the recompute, named by what they share
            assert path.count("/") == 1, (name, path)
            continue
        assert len(scopes) == 1, (name, op, path)
        if case == "pjit":
            assert scopes != ["step.exchange"], (name, path)


@pytest.mark.parametrize("case", [c for c in STEPS if c.startswith("fft")])
def test_exchange_operations_carry_their_stage(case):
    seen = set()
    for name, op, path, _, _ in compiled(case):
        if path is None or op not in WORK + COLLECTIVES:
            continue
        parts = components(path)
        stages = [c for c in parts if c.startswith("exchange.")]
        assert len(stages) <= 1, (name, path)  # stages never nest
        assert set(stages) <= set(EXCHANGE_STAGES), (name, path)
        if stages:
            assert "step.exchange" in parts, (name, path)
            seen.add(stages[0])
        if "step.exchange" in parts and op in STAGED:
            assert stages, (name, op, path)
        if op in COLLECTIVES:
            assert not stages, (name, op, path)
    assert seen == set(EXCHANGE_STAGES), seen


@pytest.mark.parametrize("case", ["pjit", "dense", "fft_allgather_sort"])
def test_backward_operations_carry_transpose(case):
    fwd, bwd = [], []
    for name, op, path, _, _ in compiled(case):
        if path is None or op not in ("dot", "fusion"):
            continue
        parts = components(path)
        backward = any(c.startswith("transpose(") for c in parts)
        if backward:
            assert "step.fwd_bwd" in parts, (name, path)
        if "step.fwd_bwd" in parts and op == "dot":
            (bwd if backward else fwd).append(name)
    # every forward matmul has two in the backward pass (input and weight)
    assert fwd and len(bwd) >= len(fwd), (len(fwd), len(bwd))


def test_dense_exchange_has_no_stage():
    stages = {c for _, op, path, _, _ in compiled("dense") if path
              for c in components(path) if c.startswith("exchange.")}
    assert stages == set()
