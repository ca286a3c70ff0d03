"""``fold_kernel_share``: the fold kernel's share of the ``exchange.fold``
scope's device time, on constructed traces of a step whose fold runs the
Pallas kernel, of one whose fold runs XLA's sort and scatter, and of runs
with no fold or no trace."""

import pytest

from bench import spec
from bench.harness import Run
from bench.trace import Event, Trace

J = "jit(step)/shard_map"
FOLD = f"{J}/step.exchange/exchange.fold"
HLO = f'''HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  ROOT %mul.1 = f32[8]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{J}/step.fwd_bwd/jvp()/mul"}}
}}

%fused_computation.2 (param_0.1: f32[16], param_1.1: f32[16]) -> c64[8] {{
  %param_0.1 = f32[16]{{0}} parameter(0)
  %param_1.1 = f32[16]{{0}} parameter(1)
  ROOT %complex.1 = c64[8]{{0}} complex(%param_0.1, %param_1.1), metadata={{op_name="{FOLD}/complex"}}
}}

ENTRY %main.6 (Arg_0.1: f32[8]) -> c64[8] {{
  %Arg_0.1 = f32[8]{{0}} parameter(0), metadata={{op_name="state"}}
  %fusion.1 = f32[8]{{0}} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %spectrum_fold_pallas.2 = (f32[16]{{0}}, f32[16]{{0}}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{FOLD}/jit(spectrum_fold_pallas)/pallas_call"}}
  %get-tuple-element.3 = f32[16]{{0}} get-tuple-element(%spectrum_fold_pallas.2), index=0
  %get-tuple-element.4 = f32[16]{{0}} get-tuple-element(%spectrum_fold_pallas.2), index=1
  ROOT %fusion.5 = c64[8]{{0}} fusion(%get-tuple-element.3, %get-tuple-element.4), kind=kLoop, calls=%fused_computation.2
}}
'''
# the fallback's fold: XLA's sort and scatter fusion under the same scope
SORTED = (HLO.replace("%spectrum_fold_pallas.2", "%sort.2")
          .replace(", custom_call_target=\"tpu_custom_call\"", ""))

MS = 1e6  # ns
STEPS = 2


def ev(text, start_ms, dur_ms):
    return Event(text, start_ms * MS, dur_ms * MS)


def events(t0, fold_op):
    call = "custom-call" if fold_op.startswith("%spectrum") else "sort"
    target = ', custom_call_target="tpu_custom_call"' if call == "custom-call" else ""
    return [
        ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %Arg_0.1), kind=kLoop, "
           "calls=%fused_computation.1", t0, 5),
        ev(f"{fold_op} = (f32[16]{{0}}, f32[16]{{0}}) {call}(f32[8]{{0}} %fusion.1)"
           f"{target}", t0 + 5, 8),
        ev("%fusion.5 = c64[8]{0} fusion(f32[16]{0} %get-tuple-element.3, "
           "f32[16]{0} %get-tuple-element.4), kind=kLoop, calls=%fused_computation.2",
           t0 + 13, 2),
    ]


def make_run(hlo, fold_op, traced=True, only_forward=False):
    run = Run("cell", {}, {}, 1, "TPU v5 lite", 2, 8, 8, hlo, 0, STEPS)
    if not traced:
        return run
    evs = []
    for k in range(STEPS):
        evs += events(100 * k, fold_op)[:1 if only_forward else None]
    t = Trace({"/device:TPU:0": evs}, [ev("bench.window", 0, 100 * STEPS)])
    run.trace, run.window_ns = t, t.window()
    return run


@pytest.mark.parametrize("case, expected", [
    ("kernel", 100.0 * 8 / (8 + 2)),  # the launch and the combine fusion
    ("sort and scatter", 0.0),  # the fallback's fold holds no kernel
    ("no fold", None),  # no operation carries the fold's scope
    ("no trace", None),  # a run without a device trace (the CPU)
])
def test_fold_kernel_share(case, expected):
    run = {
        "kernel": lambda: make_run(HLO, "%spectrum_fold_pallas.2"),
        "sort and scatter": lambda: make_run(SORTED, "%sort.2"),
        "no fold": lambda: make_run(HLO, "%spectrum_fold_pallas.2", only_forward=True),
        "no trace": lambda: make_run(HLO, "%spectrum_fold_pallas.2", traced=False),
    }[case]()
    value = spec.metric_reader("fold_kernel_share").read(run)
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)
