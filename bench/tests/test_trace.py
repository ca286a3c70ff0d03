"""The reduction from a profiler trace to busy time, idle gaps and kernel
time, on constructed traces and on one recorded on the CPU."""

import pytest

from bench import trace
from bench.trace import Event, Trace


def ev(name, start, dur, **stats):
    return Event(name, float(start), float(dur), tuple(stats.items()))


OPS = [ev("fusion.1", 100, 50), ev("fusion.2", 140, 30),  # overlap: 100..170
       ev("scatter.7", 200, 100, long_name="%scatter.7 = f32[12,2049]{1,0} scatter(..)"),
       ev("_fused_body", 400, 20), ev("all-gather-start.3", 450, 10)]
HOST = [ev("bench.window", 50, 500), ev("bench.sync", 170, 30),
        ev("bench.batch", 300, 90), ev("bench.step", 320, 50)]


def test_merge_and_busy():
    assert trace.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    # union inside the window [50, 550]: 70 + 100 + 20 + 10
    assert trace.busy_ns(OPS, 50, 550) == 200
    # clipped to a narrower window
    assert trace.busy_ns(OPS, 120, 250) == 50 + 50


def test_idle_gaps_named_by_innermost_host_span():
    gaps = trace.idle_gaps(OPS, 50, 550, HOST)
    assert [round(s * 1e9) for _, s in gaps] == [100, 90, 50, 30, 30]
    # 300..400: bench.batch holds bench.step, and the innermost names it
    assert gaps[0][0] == "bench.step"
    assert gaps[1][0] == "no harness span"  # 460..550
    assert ("bench.sync", 30e-9) in [(n, round(s, 12)) for n, s in gaps]  # 170..200


def test_select_by_name_and_kind():
    kernels = trace.select(OPS, trace.named(r"fused_compress|_fused_body"))
    assert [e.name for e in kernels] == ["_fused_body"]
    folds = trace.select(OPS, trace.named(r"scatter.*f32\[\d+,2049\]"))
    assert [e.name for e in folds] == ["scatter.7"]
    assert trace.device_seconds(folds) == pytest.approx(100e-9)
    top = trace.top_ops(OPS, 2)
    assert [n for n, _ in top] == ["scatter.7", "fusion.1"]
    assert [s for _, s in top] == pytest.approx([100e-9, 50e-9])


def test_window_needs_the_harness_span():
    assert Trace({}, HOST).window() == (50, 550)
    with pytest.raises(ValueError):
        Trace({}, []).window()


def test_load_reads_host_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
    t = trace.load(tmp_path)
    lo, hi = t.window()
    assert hi > lo
    steps = [e for e in t.host if e.name == "bench.step"]
    assert len(steps) == 2 and all(lo <= e.start_ns <= hi for e in steps)
    # the CPU has no device plane with an "XLA Ops" line
    assert t.devices == {}


def test_innermost_drops_loops_that_hold_their_body():
    loop = ev("%while.1 = (s32[]) while(..)", 0, 100)
    body = [ev("%fusion.2 = f32[8]", 10, 20), ev("%fusion.3 = f32[8]", 40, 30)]
    after = ev("%fusion.9 = f32[8]", 120, 5)
    assert trace.innermost([loop, *body, after]) == [*body, after]
    (name, seconds), = trace.top_ops([loop, *body, after], 1)
    assert name == "%fusion.3 = f32[8]" and seconds == pytest.approx(30e-9)


def test_short_name():
    assert (trace.short_name("%fusion.4 = f32[191466756]{0:T(1024)} fusion(s32[5]{0} %a)")
            == "%fusion.4 = f32[191466756]")


# names as the TPU profiler writes them: the operation's HLO text
TPU_OPS = [
    ev("%fusion.4 = f32[24]{0:T(1024)} fusion(s32[6]{0:T(1024)} %get-tuple-element.1, "
       "f32[6]{0:T(1024)} %get-tuple-element.2, f32[]{:T(128)} %constant.485), kind=kCustom", 0, 50),
    ev("%sort.1 = (s32[6]{0:T(1024)}, f32[6]{0:T(1024)}) sort(s32[6]{0:T(1024)} %r.5, "
       "f32[6]{0:T(1024)} %r.7), dimensions={0}", 60, 10),
    ev("%fused_compress_pallas.1 = (u8[2,128]{1,0}) custom-call(f32[2,12]{1,0} %g.1), "
       "custom_call_target=\"tpu_custom_call\"", 80, 7),
    ev("%slice.43 = u8[2,3]{1,0} slice(u8[2,128]{1,0} %fused_compress_pallas.1)", 90, 1),
    ev("%fusion.5 = f32[24]{0} fusion(f32[24]{0} %x)", 95, 3),
]


def test_kernel_and_fold_are_found_by_their_own_name_and_shape():
    from bench import spec

    kernel = spec.metric_reader("fused_compress_roofline").KERNEL
    assert [e.name[:24] for e in trace.select(TPU_OPS, trace.named(kernel))] == [
        "%fused_compress_pallas.1"]
    # 2 rows of a 4-value chunk (3 bins), k = 3 kept: payload 6, plane 6 = 2 * 3
    fold = spec.metric_reader("spectrum_fold_roofline")._is_fold(8, 3, 3 // 4 + 1, 1)
    assert fold(TPU_OPS[0]) is False  # 8 rows * 3 bins = 24, payload 8 * 1 = 8
    fold = spec.metric_reader("spectrum_fold_roofline")._is_fold(2, 12, 3, 1)
    assert [e.name[:9] for e in trace.select(TPU_OPS, fold)] == ["%fusion.4", "%sort.1 ="]
