"""The transport's two readers on constructed runs: ``exchange_collective_ms``
(device time under the ``exchange.collective`` scope, an asynchronous
all-gather counted from its start to its done) and ``wire_mb_per_step``
(bytes across chip links under the ring model, from a four-device HLO)."""

import pytest

from bench import spec
from bench.harness import Run
from bench.trace import Event, Trace

J = "jit(step)/shard_map/step.exchange"
COLLECTIVE = "exchange.collective"
HLO = f'''HloModule jit_step, is_scheduled=true

ENTRY %main.9 (Arg_0.1: u8[8]) -> f32[8] {{
  %Arg_0.1 = u8[8]{{0}} parameter(0), metadata={{op_name="state"}}
  %all-gather-start.3 = (u8[8]{{0}}, u8[32]{{0}}) all-gather-start(%Arg_0.1), channel_id=1, replica_groups={{{{0,1,2,3}}}}, dimensions={{0}}, metadata={{op_name="{J}/{COLLECTIVE}/all_gather"}}
  %all-gather-done.3 = u8[32]{{0}} all-gather-done(%all-gather-start.3), metadata={{op_name="{J}/{COLLECTIVE}/all_gather"}}
  %fusion.4 = f32[8]{{0}} fusion(%all-gather-done.3), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{J}/exchange.fold/add"}}
  %all-reduce.5 = f32[8]{{0}} all-reduce(%fusion.4), channel_id=2, replica_groups={{{{0,1,2,3}}}}, to_apply=%add.1, metadata={{op_name="{J}/{COLLECTIVE}/psum"}}
  ROOT %fusion.6 = f32[8]{{0}} fusion(%all-reduce.5), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="jit(step)/shard_map/step.optimizer/mul"}}
}}
'''
MS = 1e6  # ns
STEPS = 2


def ev(text, start_ms, dur_ms):
    return Event(text, start_ms * MS, dur_ms * MS)


START = ("%all-gather-start.3 = (u8[8]{0}, u8[32]{0}) all-gather-start(u8[8]{0} "
         "%Arg_0.1), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}")


def step_events(t0):
    return [
        ev(START, t0, 0.5),
        ev("%all-gather-done.3 = u8[32]{0} all-gather-done((u8[8]{0}, u8[32]{0}) "
           "%all-gather-start.3)", t0 + 6, 0.5),
        ev("%fusion.4 = f32[8]{0} fusion(u8[32]{0} %all-gather-done.3), kind=kLoop, "
           "calls=%fused_computation.1", t0 + 7, 3),
        ev("%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %fusion.4), channel_id=2, "
           "replica_groups={{0,1,2,3}}, to_apply=%add.1", t0 + 10, 2),
        ev("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %all-reduce.5), kind=kLoop, "
           "calls=%fused_computation.2", t0 + 12, 1),
    ]


def make_run(hlo=HLO, chips=2):
    """Two steps on each chip; the all-gather's asynchronous span runs 6.5 ms
    from its start to its done, with an unrelated copy beside it."""
    devices, spans, host = {}, {}, [ev("bench.window", 0, 50 * STEPS)]
    for c in range(chips):
        plane = f"/device:TPU:{c}"
        devices[plane], spans[plane] = [], []
        for k in range(STEPS):
            t0 = 50 * k + c  # the chips drift apart by a millisecond
            devices[plane] += step_events(t0)
            spans[plane] += [ev(START, t0, 6.5),
                             ev("%copy-start.1 = (f32[8]{0}, f32[8]{0}) copy-start("
                                "f32[8]{0} %Arg_0.1)", t0, 20)]
    t = Trace(devices, host, spans)
    run = Run("cell", {}, {}, chips, "TPU v5 lite", 8, 8, 8, hlo, 0, STEPS)
    run.trace, run.window_ns = t, t.window()
    return run


def test_collective_time_counts_the_async_gather_from_start_to_done():
    # the all-gather's 6.5 ms span (its start and done events lie inside
    # it) and the 2 ms all-reduce; not the fold, the copy or the optimizer
    value = spec.metric_reader("exchange_collective_ms").read(make_run())
    assert value == pytest.approx(6.5 + 2.0)


def test_collective_time_is_none_without_the_scope():
    hlo = HLO.replace(f"/{COLLECTIVE}", "")
    assert spec.metric_reader("exchange_collective_ms").read(make_run(hlo)) is None


def test_collective_time_counts_the_collective_by_name_too():
    # the name-based reader counts every all-gather and all-reduce event,
    # the async span and its start and done events each once
    value = spec.metric_reader("collective_ms").read(make_run())
    assert value == pytest.approx(6.5 + 0.5 + 0.5 + 2.0)


# the payload's all-gather as the TPU compiler leaves it for four chips: a
# synchronous all-gather of each plane (codes, codes, exponents)
WIRE_HLO = """HloModule jit_step, is_scheduled=true

ENTRY %main.9 (a: u8[1,130,615], b: s16[1,130,615]) -> s16[4,130,615] {
  %all-gather.18 = u8[4,130,615]{1,2,0:T(8,128)(4,1)} all-gather(%a), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_ids=true
  %all-gather.19 = u8[4,130,615]{1,2,0:T(8,128)(4,1)} all-gather(%a), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_ids=true
  %all-gather.20 = s16[4,130,615]{1,2,0:T(8,128)(2,1)} all-gather(%b), channel_id=4, replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_ids=true
  ROOT %all-reduce.5 = f32[8]{0} all-reduce(%c), channel_id=5, replica_groups={{0,1,2,3}}, to_apply=%add.1
}
"""


def test_wire_bytes_follow_the_ring_model_on_four_devices():
    run = make_run(WIRE_HLO, chips=4)
    # each all-gather's result is four workers' planes, and 3/4 of it
    # arrives over links; the all-reduce moves twice its bytes times 3/4
    gathered = 4 * 130 * 615 * (1 + 1 + 2)
    expected = gathered * 3 / 4 + 2 * 8 * 4 * 3 / 4
    value = spec.metric_reader("wire_mb_per_step").read(run)
    assert value == pytest.approx(expected / 1e6)
    one_chip = make_run(WIRE_HLO.replace("{0,1,2,3}", "{0}"), chips=1)
    assert spec.metric_reader("wire_mb_per_step").read(one_chip) is None
