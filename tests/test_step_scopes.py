"""Every stage of the compiled train step carries its name.

The step runs under three named scopes (``step.fwd_bwd``, ``step.exchange``,
``step.optimizer``) and the FFT exchange's stages under five more
(``exchange.rfft``, ``.select``, ``.pack``, ``.fold``, ``.irfft``).  XLA
keeps each instruction's scope path in its ``op_name`` metadata, fusions
carrying their root's, and the benchmark reads per-stage device time from
those names.  The exchange's collectives carry ``exchange.collective`` and
no stage; the flattening of the gradient around them is the exchange's own
time, outside its stages.  These tests compile tiny steps on the CPU (on
one device, and on four in a child process) and read the names back from
``compiled.as_text()``.
"""

import json
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

from helpers import REPO, run_with_devices

TINY = ArchConfig(name="tiny", family="dense", n_layers=1, d_model=64, n_heads=4,
                  n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64)

STEP_SCOPES = ("step.fwd_bwd", "step.exchange", "step.optimizer")
EXCHANGE_STAGES = ("exchange.rfft", "exchange.select", "exchange.pack",
                   "exchange.fold", "exchange.irfft")
COLLECTIVE_SCOPE = "exchange.collective"
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

_INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = (?:\([^=]*?\)|\S+) ([a-z][a-z0-9\-]*)\((\)?)")
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


def _compile(mode: str, reducer=None, rows: int = 2, lowered: bool = False) -> str:
    model = LM(TINY)
    opt = OptConfig(kind="adamw", lr=1e-3)
    mesh = make_local_mesh()
    step_cfg = StepConfig(mode=mode, reducer=reducer)
    batch = {k: jax.ShapeDtypeStruct((rows, 32), jnp.int32) for k in ("tokens", "targets")}
    state = jax.eval_shape(lambda k: init_state(k, model, opt), jax.random.PRNGKey(0))
    step = build_train_step(model, opt, step_cfg, mesh, batch)
    with compat.set_mesh(mesh):
        low = step.lower(state, batch)
        if lowered:  # the program as JAX writes it, before XLA's passes
            return low.as_text(dialect="hlo", debug_info=True)
        return low.compile().as_text()


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
        named = [c for c in parts if c.startswith("exchange.")]
        assert len(named) <= 1, (name, path)  # stages never nest
        assert set(named) <= set(EXCHANGE_STAGES) | {COLLECTIVE_SCOPE}, (name, path)
        if named:
            assert "step.exchange" in parts, (name, path)
            seen.add(named[0])
        if "step.exchange" in parts and op in STAGED:
            assert set(named) & set(EXCHANGE_STAGES), (name, op, path)
        if op in COLLECTIVES and "step.exchange" in parts:
            assert named == [COLLECTIVE_SCOPE], (name, op, path)
    assert seen - {COLLECTIVE_SCOPE} == set(EXCHANGE_STAGES), seen


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
    # the dense mean is one collective: its scope and nothing else
    stages = {c for _, op, path, _, _ in compiled("dense") if path
              for c in components(path) if c.startswith("exchange.")}
    assert stages == {COLLECTIVE_SCOPE}


FOUR_DEVICE_CASES = ("dense", "fft_allgather_sort", "fft_psum_bucketed")
_FOUR_DEVICES = """
import json, sys
sys.path[:0] = [{tests!r}]
import test_step_scopes as t
print(json.dumps({{case: [t._compile(*t.STEPS[case], rows=4, lowered=True),
                         t._compile(*t.STEPS[case], rows=4)]
                  for case in t.FOUR_DEVICE_CASES}}))
"""


@pytest.fixture(scope="module")
def four_device_steps():
    """Per case, the instructions of the four-device step as lowered and as
    compiled."""
    out = run_with_devices(_FOUR_DEVICES.format(tests=f"{REPO}/tests"), devices=4)
    return {case: [instructions(text) for text in texts]
            for case, texts in json.loads(out.strip().splitlines()[-1]).items()}


@pytest.mark.parametrize("case", FOUR_DEVICE_CASES)
def test_collectives_across_four_devices_carry_the_collective_scope(
        four_device_steps, case):
    lowered, compiled_step = four_device_steps[case]
    kinds = set()
    for name, op, path, _, _ in lowered:
        if op not in COLLECTIVES:
            continue
        parts = components(path)
        named = [c for c in parts if c.startswith("exchange.")]
        if "step.exchange" in parts:
            # the exchange's own collective: its scope, and no stage
            assert named == [COLLECTIVE_SCOPE], (name, op, path)
            kinds.add(op)
        else:
            # the loss and metric means and the guard's agreement
            assert not named and "step.optimizer" in parts, (name, op, path)
    assert kinds == {"all-gather" if "allgather" in case else "all-reduce"}, kinds
    # XLA may combine the dense mean's all-reduces with the optimizer's into
    # one, named by one of them; the all-gathers of the payload stay apart
    gathers = [(name, path) for name, op, path, _, _ in compiled_step
               if op == "all-gather"]
    assert ("allgather" in case) is bool(gathers), gathers
    assert all(COLLECTIVE_SCOPE in components(p) for _, p in gathers), gathers
