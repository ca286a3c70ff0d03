"""Per-stage device time from the program's named scopes: which step
instruction a device event is, the scope path it carries (from the compiled
HLO's metadata, with the compiler's unnamed instructions resolved through
their neighbours), and the readers built on it, on constructed traces; and
the paths of a small training step compiled for a described TPU v5e."""

import json
import os

import pytest

from bench import scopes, spec
from bench.harness import Run
from bench.trace import Event, Trace

J = "jit(step)/shard_map"
HLO = f'''HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  ROOT %mul.1 = f32[8]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{J}/step.fwd_bwd/jvp()/mul"}}
}}

%fused_computation.2 (param_0.1: s32[8], param_1.1: f32[8]) -> f32[24] {{
  %param_0.1 = s32[8]{{0}} parameter(0)
  %param_1.1 = f32[8]{{0}} parameter(1)
  ROOT %scatter.1 = f32[24]{{0}} scatter(%param_0.1, %param_1.1)
}}

%compare.1 (a: s32[], b: s32[]) -> pred[] {{
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %lt = pred[] compare(%a, %b), direction=LT, metadata={{op_name="lt"}}
}}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[24] {{
  %Arg_0.1 = f32[8]{{0}} parameter(0), metadata={{op_name="state"}}
  %fusion.1 = f32[8]{{0:T(128)}} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[8]{{0}} dot(%fusion.1, %Arg_0.1), lhs_contracting_dims={{}}, metadata={{op_name="{J}/step.fwd_bwd/transpose(jvp())/dot_general"}}
  %all-gather-start.3 = (f32[8]{{0}}, f32[32]{{0}}) all-gather-start(%dot.2), dimensions={{0}}, backend_config={{"flag_configs":[]}}, metadata={{op_name="{J}/step.exchange/all_gather"}}
  %all-gather-done.3 = f32[32]{{0}} all-gather-done(%all-gather-start.3), metadata={{op_name="{J}/step.exchange/all_gather"}}
  %reshape.4 = s32[8]{{0}} reshape(%all-gather-done.3), metadata={{op_name="{J}/step.exchange/exchange.fold/select_n"}}
  %sort.5 = (s32[8]{{0}}, f32[8]{{0}}) sort(%reshape.4, %all-gather-done.3), dimensions={{0}}, to_apply=%compare.1
  %get-tuple-element.5 = s32[8]{{0}} get-tuple-element(%sort.5), index=0
  %fusion.6 = f32[24]{{0}} fusion(%get-tuple-element.5, %all-gather-done.3), kind=kCustom, calls=%fused_computation.2, backend_config={{"used_scoped_memory_configs":[]}}
  %fft.7 = f32[24]{{0}} fft(%fusion.6), fft_type=IRFFT, fft_length={{24}}, metadata={{op_name="{J}/step.exchange/exchange.irfft/jit(fft)/fft"}}
  ROOT %fusion.8 = f32[24]{{0}} fusion(%fft.7), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{J}/step.optimizer/mul"}}
}}
'''

MS = 1e6  # ns


def ev(text, start_ms, dur_ms, **stats):
    return Event(text, start_ms * MS, dur_ms * MS, tuple(stats.items()))


# each event as the TPU profiler names it: the instruction with operand types
def step_events(t0):
    return [
        ev("%fusion.1 = f32[8]{0:T(128)} fusion(f32[8]{0} %Arg_0.1), kind=kLoop, "
           "calls=%fused_computation.1", t0, 5),
        ev("%dot.2 = f32[8]{0} dot(f32[8]{0:T(128)} %fusion.1, f32[8]{0} %Arg_0.1), "
           "lhs_contracting_dims={}", t0 + 5, 10),
        ev("%all-gather-start.3 = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} "
           "%dot.2), dimensions={0}", t0 + 15, 0.5),
        ev("%all-gather-done.3 = f32[32]{0} all-gather-done((f32[8]{0}, f32[32]{0}) "
           "%all-gather-start.3)", t0 + 19, 0.5),
        ev("%reshape.4 = s32[8]{0} reshape(f32[32]{0} %all-gather-done.3)", t0 + 20, 1),
        ev("%sort.5 = (s32[8]{0}, f32[8]{0}) sort(s32[8]{0} %reshape.4, f32[32]{0} "
           "%all-gather-done.3), dimensions={0}, to_apply=%compare.1", t0 + 21, 3),
        ev("%fusion.6 = f32[24]{0} fusion(s32[8]{0} %get-tuple-element.5, f32[32]{0} "
           "%all-gather-done.3), kind=kCustom, calls=%fused_computation.2", t0 + 24, 6),
        ev("%fft.7 = f32[24]{0} fft(f32[24]{0} %fusion.6), fft_type=IRFFT, "
           "fft_length={24}", t0 + 30, 2),
        ev("%fusion.8 = f32[24]{0} fusion(f32[24]{0} %fft.7), kind=kLoop, "
           "calls=%fused_computation.1", t0 + 32, 2),
    ]


# the feed's module reuses the name %fusion.1 with other operands
FEED = "%fusion.1 = u32[2]{0} fusion(u32[2]{0} %key.1), kind=kLoop, calls=%fused_computation.3"
STEPS = 2


def make_run(ops=None):
    evs, async_evs, host = [], [], [ev("bench.window", 0, 100 * STEPS)]
    for k in range(STEPS):
        t0 = 100 * k
        evs += step_events(t0) + [ev(FEED, t0 + 16, 1)]
        # the all-gather's start-to-done span: stage times count the
        # operations of the XLA Ops line alone
        async_evs.append(ev(step_events(t0)[2].name, t0 + 15, 4))
        host.append(ev("bench.step", t0, 40))
    if ops is not None:
        evs, async_evs = ops, []
    t = Trace({"/device:TPU:0": evs}, host, {"/device:TPU:0": async_evs})
    run = Run("cell", {}, {}, 1, "TPU v5 lite", 2, 8, 8, HLO, 0, STEPS)
    run.trace, run.window_ns = t, t.window()
    return run


@pytest.fixture
def step():
    return scopes.StepScopes(HLO)


def test_events_are_the_steps_only_when_the_whole_instruction_matches(step):
    ours = step_events(0)
    assert all(step.owns(e) for e in ours)
    assert not step.owns(ev(FEED, 0, 1))  # same name, another module
    assert not step.owns(ev("bench.step", 0, 1))


@pytest.mark.parametrize("index, stage", [
    (0, ["step.fwd_bwd", "jvp()"]),  # unnamed fusion: its body's root
    (1, ["step.fwd_bwd", "transpose(jvp())"]),  # own metadata
    (2, ["step.exchange", "all_gather"]),
    (5, ["step.exchange", "exchange.fold"]),  # unnamed sort: its named operand
    (6, ["step.exchange", "exchange.fold"]),  # unnamed body and operand: deeper
    (7, ["step.exchange", "exchange.irfft"]),
    (8, ["step.optimizer"]),
])
def test_hlo_route_names_each_instruction(step, index, stage):
    parts = step.parts(step_events(0)[index])
    assert parts[0] == "jit(step)" and parts[2:2 + len(stage)] == stage


def test_unnamed_loop_body_takes_its_callers_path():
    hlo = HLO.replace(
        "ENTRY %main.9", '''%body.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %dynamic-update-slice.1 = f32[8]{0} dynamic-update-slice(%p, %p)
}

ENTRY %main.9''').replace(
        "  ROOT %fusion.8", f'''  %while.9 = f32[8]{{0}} while(%dot.2), body=%body.1, metadata={{op_name="{J}/step.exchange/exchange.rfft/while"}}
  ROOT %fusion.8''')
    s = scopes.StepScopes(hlo)
    assert "exchange.rfft" in s.path("dynamic-update-slice.1").split("/")


def test_unnamed_instruction_takes_its_deepest_operands_path():
    # XLA names an instruction it merged from several by what their names
    # share: here the module's root alone
    hlo = HLO.replace(
        "  %sort.5 = (s32[8]{0}, f32[8]{0}) sort(%reshape.4, %all-gather-done.3)",
        f'''  %reshape.40 = s32[8]{{0}} reshape(%all-gather-done.3), metadata={{op_name="{J}"}}
  %sort.5 = (s32[8]{{0}}, f32[8]{{0}}) sort(%reshape.40, %reshape.4)''')
    parts = scopes.StepScopes(hlo).path("sort.5").split("/")
    assert parts[2:4] == ["step.exchange", "exchange.fold"]


def test_unnamed_instruction_takes_its_latest_operands_stage():
    # the backward path is the deeper one, but an operation runs after the
    # stages it reads
    hlo = HLO.replace(
        "  %sort.5 = (s32[8]{0}, f32[8]{0}) sort(%reshape.4, %all-gather-done.3)",
        f'''  %reshape.40 = s32[8]{{0}} reshape(%dot.2), metadata={{op_name="{J}/step.fwd_bwd/transpose(jvp())/while/body/sub"}}
  %sort.5 = (s32[8]{{0}}, f32[8]{{0}}) sort(%reshape.40, %reshape.4)''')
    parts = scopes.StepScopes(hlo).path("sort.5").split("/")
    assert parts[2:4] == ["step.exchange", "exchange.fold"]


def test_a_fused_bodys_constant_names_nothing():
    # XLA keeps one of equal constants, named by the stage it kept: the
    # fusion takes its operands' path instead
    hlo = HLO.replace(
        "  ROOT %scatter.1 = f32[24]{0} scatter(%param_0.1, %param_1.1)",
        f'''  %constant.1 = s32[] constant(-1), metadata={{op_name="{J}/step.fwd_bwd/transpose(jvp())/sub"}}
  ROOT %scatter.1 = f32[24]{{0}} scatter(%param_0.1, %param_1.1)''')
    parts = scopes.StepScopes(hlo).path("fusion.6").split("/")
    assert parts[2:4] == ["step.exchange", "exchange.fold"]


def test_unnamed_instruction_with_no_named_operand_takes_its_users_path():
    # the zeros the fold scatters into: a broadcast with no operand
    hlo = HLO.replace(
        "  %fusion.6 = f32[24]{0} fusion(%get-tuple-element.5, %all-gather-done.3)",
        '''  %broadcast.9 = f32[24]{0} broadcast()
  %fusion.6 = f32[24]{0} fusion(%get-tuple-element.5, %all-gather-done.3, %broadcast.9)''')
    parts = scopes.StepScopes(hlo).path("broadcast.9").split("/")
    assert parts[2:4] == ["step.exchange", "exchange.fold"]


@pytest.mark.parametrize("metric, expected", [
    ("forward_ms", 5.0),
    ("backward_ms", 10.0),
    ("optimizer_ms", 2.0),
    ("exchange_ms", 0.5 + 0.5 + 1 + 3 + 6 + 2),
    ("exchange_fold_ms", 1 + 3 + 6),
    ("exchange_fft_ms", 2.0),
    ("exchange_select_ms", None),
    ("exchange_pack_ms", None),
])
def test_readers_on_a_constructed_run(metric, expected):
    value = spec.metric_reader(metric).read(make_run())
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)


@pytest.mark.parametrize("metric", ["forward_ms", "backward_ms", "exchange_fold_ms"])
def test_readers_read_nothing_without_the_steps_operations(metric):
    assert spec.metric_reader(metric).read(make_run(ops=[ev(FEED, 1, 1)])) is None


def test_stages_cover_the_step():
    run = make_run()
    busy = sum(e.dur_ns for e, _ in scopes.step_ops(run)[0]) / MS / STEPS
    covered = sum(spec.metric_reader(m).read(run) for m in
                  ("forward_ms", "backward_ms", "optimizer_ms", "exchange_ms"))
    assert covered == pytest.approx(busy)


# A small step at the fft cell's exchange (4096-point chunks, the Pallas
# backend), with a vocabulary large enough that the TPU compiler sorts the
# fold's scatter indices as it does at the cell's size (above 2**20 kept
# coefficients), so the compiler's unnamed sorts and scatter fusions appear.
SMALL = dict(name="small", hidden_size=128, intermediate_size=384,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             vocab_size=32000)
WORK = ("fusion", "dot", "convolution", "scatter", "sort", "custom-call", "fft",
        "all-gather", "all-reduce", "all-gather-start", "all-reduce-start")


@pytest.fixture(scope="module")
def v5e_step():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench import job as job_mod
    from repro.kernels import engine, runtime

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cfg = json.loads((spec.BENCH_DIR / "configs/phi3_medium_14b_l1.json").read_text())
    cfg.update(SMALL)
    traffic = json.loads((spec.BENCH_DIR / "traffic/fft_allgather_2x4096.json").read_text())
    traffic["seq_len"] = 128
    # the described chip compiles Mosaic; the trainer's auto backend asks
    saved = engine.mosaic_available, runtime.mosaic_available
    engine.mosaic_available = runtime.mosaic_available = lambda: True
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        job = job_mod.build(cfg, traffic, mesh)
        state = job_mod.abstract_state(job, NamedSharding(mesh, P()))
        batch = {k: jax.ShapeDtypeStruct((job.global_batch, job.seq), jnp.int32,
                                         sharding=job.step.batch_sharding)
                 for k in ("tokens", "targets")}
        text = job.step.lower(state, batch).compile().as_text()
    finally:
        engine.mosaic_available, runtime.mosaic_available = saved
        jax.config.update("jax_enable_compilation_cache", cache_on)
    return scopes.StepScopes(text)


def _reads_an_argument(s, name, seen):
    """Whether the instruction depends on a parameter of the entry or of a
    loop or call (not on constants alone)."""
    if name in seen:
        return seen[name]
    seen[name] = False
    ins = s.instrs[name]
    out = (ins.key[2] == "parameter"
           or any(_reads_an_argument(s, o, seen) for o in ins.operands))
    seen[name] = out
    return out


def _device_ops(s):
    """The instructions that run as operations of their own: not those
    inside a fusion's body."""
    for name, ins in s.instrs.items():
        caller = s.callers.get(ins.computation)
        if caller is not None and s.instrs[caller].key[2] == "fusion":
            continue
        if ins.key[2] in WORK:
            yield name, ins


def test_every_operation_compiled_for_v5e_has_one_step_stage(v5e_step):
    seen, n = {}, 0
    for name, ins in _device_ops(v5e_step):
        p = v5e_step.path(name)
        stages = [c for c in (p or "").split("/") if c in scopes.STAGES]
        if not stages and not _reads_an_argument(v5e_step, name, seen):
            continue  # constants the compiler folds (rotary frequencies, buffers)
        assert len(stages) == 1, (name, ins.key[2], p)
        n += 1
    assert n > 100


def _holds_a_scatter(s, name):
    """Whether a fusion's body, or a body nested in it, scatters."""
    return any(s.instrs[b].key[2] == "scatter" or _holds_a_scatter(s, b)
               for c in s.instrs[name].callees for b in s.bodies.get(c, []))


def test_sorts_and_scatters_compiled_for_v5e_are_the_fold(v5e_step):
    fold = {}
    for name, ins in _device_ops(v5e_step):
        parts = (v5e_step.path(name) or "").split("/")
        if ins.key[2] == "sort" or (ins.key[2] == "fusion"
                                    and _holds_a_scatter(v5e_step, name)):
            if scopes.FWD_BWD in parts:
                # the embedding's gradient, a scatter-add of the backward pass
                assert any(c.startswith(scopes.BACKWARD_PREFIX) for c in parts), name
                continue
            fold.setdefault(ins.key[2], []).append(name)
            assert scopes.EXCHANGE in parts and scopes.FOLD in parts, (name, parts)
    # both planes' sort and scatter: the cell's fold, unnamed by the compiler
    assert len(fold.get("sort", [])) == 2 and len(fold.get("fusion", [])) == 2, fold
    assert all(v5e_step.instrs[n].op_name is None for ns in fold.values() for n in ns)
