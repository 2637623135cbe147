"""The program's own spans and named scopes, and their reduction
(``pb/program.py``): a toy engine traced on the CPU, synthetic events, a
toy decode step's compiled HLO, and two recorded chip traces: the
benchmark's first (``trace_yi-6b.decode.json``), whose existing readings
stay as they were, and one with the program's spans."""
import json
import shutil
import time

import pytest

import benchroot
from benchroot import LIMIT_DECODE, make_root
from pb import program, tracing
from pb.program import Span
from pb.tracing import Event

SEED = 2**33 + 23
DEV = "/device:TPU:0"
HOST = "/host:CPU"
EMIT_CHILDREN = ("emit.wait", "emit.fetch", "emit.sample", "emit.finish")


class StepClock:
    """``time`` with a ``monotonic`` that advances 1 ms at each reading,
    so that the window holds the same ticks on a busy machine."""

    now = 0.0

    def monotonic(self) -> float:
        self.now += 1e-3
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Half a second of the toy offline cell by the harness's clock,
    traced on the CPU with the benchmark's own phase spans on: the run,
    the harness's ``Driver``, the benchmark's events and the program's
    spans."""
    import jax

    from pb import harness, weights, work
    from repro.models import build_model

    root = make_root(tmp_path_factory.mktemp("bench"))
    with pytest.MonkeyPatch.context() as mp:
        benchroot.stub_chip(mp, LIMIT_DECODE)
        mp.setattr(harness, "time", StepClock())
        cell = harness.load_cell(root, "tiny.decode")
        model = build_model(harness.arch_config(cell.config))
        params = weights.make(model, SEED)
        eng, _ = harness.build_engine(cell, model, params, jax.devices()[:1])
        harness.warm_up(eng)
        run = harness.Run(cell=cell, seed=SEED,
                          shapes=work.Shapes.from_config(cell.config),
                          device_kind="cpu", seconds=0.5)
        drv = harness.Driver(eng, trace=True)
        tracer = harness.Tracer(True)
        harness.drive_backlog(run, drv, tracer, 0.5,
                              harness.time.monotonic())
    try:
        events = tracing.load_xspace(tracer.dir)
        spans = program.load_spans(tracer.dir)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    return run, drv, events, spans


def _inside(inner, outer):
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_engine_phases_lie_inside_the_benchmarks(traced):
    run, _, events, spans = traced
    for phase in ("schedule", "dispatch", "emit"):
        outer = [e for e in events if e.name == f"perfbench.{phase}"]
        inner = _named(spans, f"engine.{phase}")
        assert len(outer) == len(run.ticks) and inner
        assert all(any(_inside(s, o) for o in outer) for s in inner), phase


def test_emit_children_tile_it(traced):
    _, _, _, spans = traced
    emits = _named(spans, "engine.emit")
    children = [s for s in spans
                if s.name in {f"engine.{c}" for c in EMIT_CHILDREN}]
    assert len(children) == 4 * len(emits)
    assert all(any(_inside(c, e) for e in emits) for c in children)
    total = sum(e.dur_ns for e in emits)
    assert 0.95 * total <= sum(c.dur_ns for c in children) <= total


def test_prefill_chunk_spans_match_the_reconstructed_chunks(traced):
    """Each tick's ``engine.prefill_chunk`` spans name the chunks that the
    harness reconstructs from the lanes, in order, and the request whose
    prompt the chunk ends when the harness marks it final."""
    run, drv, events, spans = traced
    sched = sorted((e for e in events if e.name == "perfbench.schedule"),
                   key=lambda e: e.start_ns)
    chunks = _named(spans, "engine.prefill_chunk")
    assert chunks and len(sched) == len(run.ticks)
    for tick, outer in zip(run.ticks, sched):
        mine = sorted((c for c in chunks if _inside(c, outer)),
                      key=lambda c: c.start_ns)
        assert [(c.meta["start"], c.meta["end"]) for c in mine] \
            == [(a, b) for a, b, _ in tick.chunks]
        for c, (_, end, final) in zip(mine, tick.chunks):
            assert final == (end == len(drv.reqs[c.meta["rid"]].prompt))


def test_no_span_per_lane(traced):
    """Every tick writes the same engine spans, whatever its lanes do:
    one per phase and step, plus one per prefill chunk."""
    run, _, _, spans = traced
    per_tick = {s.name: len(_named(spans, s.name)) for s in spans
                if s.name != "engine.prefill_chunk"}
    assert set(per_tick) == {
        "engine.schedule", "engine.admit", "engine.dispatch",
        "engine.dispatch.inputs", "engine.dispatch.call", "engine.emit",
        *(f"engine.{c}" for c in EMIT_CHILDREN)}
    assert set(per_tick.values()) == {len(run.ticks)}
    assert all(s.meta["lanes"] >= 1
               for s in _named(spans, "engine.dispatch.call"))


def _ms(a, b):
    return a * 1e6, (b - a) * 1e6


def _span(name, a, b):
    return Span(name, *_ms(a, b))


def test_idle_by_span_charges_the_innermost():
    """A 100 ms window, the device busy 10-40 and 60-80 ms."""
    events = [Event(HOST, "python", tracing.WINDOW_SPAN, *_ms(0, 100)),
              Event(DEV, tracing.OPS_LINE, "%fusion.1 = f32[8]",
                    *_ms(10, 40)),
              Event(DEV, tracing.OPS_LINE, "%fusion.2 = f32[8]",
                    *_ms(60, 80))]
    spans = [_span("engine.schedule", 0, 10), _span("engine.admit", 0, 4),
             _span("engine.prefill_chunk", 4, 9),
             _span("engine.dispatch.call", 12, 13),
             _span("engine.emit", 40, 55), _span("engine.emit.wait", 40, 42),
             _span("engine.emit.fetch", 42, 50),
             _span("engine.emit.sample", 50, 53),
             _span("engine.emit.finish", 53, 55),
             _span("engine.dispatch", 85, 90),
             _span("engine.dispatch.inputs", 85, 88)]
    idle = program.idle_by_span(events, spans)
    want = {"engine.schedule": 1, "engine.admit": 4,
            "engine.prefill_chunk": 5, "engine.dispatch.call": 0,
            "engine.emit": 0, "engine.emit.wait": 2,
            "engine.emit.fetch": 8, "engine.emit.sample": 3,
            "engine.emit.finish": 2, "engine.dispatch": 2,
            "engine.dispatch.inputs": 3, "none": 20}
    assert idle == pytest.approx({k: v * 1e-3 for k, v in want.items()})
    # parents are never charged what their children were: the split sums
    # to the window's idle time
    reduced = tracing.reduce(events)
    assert sum(idle.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s)


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.7 (param_0.1: bf16[64]) -> bf16[64] {
  %param_0.1 = bf16[64]{0} parameter(0)
  ROOT %scatter.3 = bf16[64]{0} scatter(%param_0.1), metadata={op_name="jit(step)/layers/while/body/closed_call/attn/kv_write/scatter"}
}

%fused_computation.8 (param_0.2: bf16[64]) -> bf16[64] {
  %param_0.2 = bf16[64]{0} parameter(0)
  ROOT %copy.4 = bf16[64]{0} copy(%param_0.2)
}

ENTRY %main.9 (Arg_0.1: bf16[64]) -> bf16[64] {
  %Arg_0.1 = bf16[64]{0} parameter(0)
  %fusion.1 = bf16[64]{0} fusion(%Arg_0.1), kind=kCustom, calls=%fused_computation.7
  %fusion.2 = bf16[64]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.8
  ROOT %dynamic-slice.5 = bf16[64]{0} dynamic-slice(%fusion.2), metadata={op_name="jit(step)/layers/while/body/dynamic_slice" stack_frame_id=3}
}
"""


def test_hlo_scopes_a_fusion_takes_its_roots():
    scopes = program.hlo_scopes(HLO)
    assert scopes["fusion.1"].endswith("/attn/kv_write/scatter")
    assert scopes["fusion.2"] == ""               # a copy XLA put in
    assert scopes["dynamic-slice.5"] \
        == "jit(step)/layers/while/body/dynamic_slice"
    assert [program.is_scan_copy(scopes[k]) for k in
            ("fusion.1", "fusion.2", "dynamic-slice.5")] \
        == [False, True, True]
    assert not program.is_scan_copy(None)         # not in the map
    assert not program.is_scan_copy("jit(step)/lm_head/dot_general")


def test_scopes_of_the_compiled_decode_step():
    """The toy model's decode step, compiled for the CPU: the scan's
    slicing of its operands and stacking of its outputs fall in the
    scan's own bucket; the KV scatter, attention and the MLP do not."""
    import jax
    import jax.numpy as jnp

    from pb import harness
    from repro.models import build_model

    conf = json.loads((benchroot.DATA / "tiny.json").read_text())
    model = build_model(harness.arch_config(conf))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: model.init_paged_caches(9, 16))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    text = jax.jit(model.paged_decode_step).lower(
        params, caches, i32(2, 4), i32(2, 1), i32(2)).compile().as_text()
    scopes = program.hlo_scopes(text)
    names = set(scopes.values())

    def under(tail):
        return [n for n in names if n.endswith(tail)]

    scan = under("/layers/while/body/dynamic_slice") \
        + under("/layers/while/body/dynamic_update_slice")
    assert len(scan) == 2 and all(map(program.is_scan_copy, scan))
    scatter = under("/attn/kv_write/scatter")
    assert scatter and not any(map(program.is_scan_copy, scatter))
    for scope in ("/attn/", "/mlp/", "/lm_head/"):
        inside = [n for n in names if scope in n]
        assert inside and not any(map(program.is_scan_copy, inside)), scope


#: every per-layer reading of ``trace_yi-6b.decode.json``, as the
#: benchmark's readers gave it before the program wrote spans
RECORDED_READINGS = {
    "emit_idle_ms_per_tick.offline": 2.510819666666667,
    "decode_step_ms.offline": 65.55979933333333,
    "decode_attn_roofline.offline": 3.4664047554403936,
    "step_mfu.offline": 2.2902283747411167,
    "device_idle_share.offline": 5.787276000000007,
}


def test_existing_readings_of_the_recorded_trace_stay():
    from pb import harness, work
    conf = json.loads((benchroot.REPO / "perfbench/configs/yi-6b.json")
                      .read_text())
    r = tracing.reduce(tracing.load_events(
        benchroot.DATA / "trace_yi-6b.decode.json"))
    ticks = [harness.Tick(i, 0.05 * i, 0.05 * i, 0.05 * i + 0.002,
                          0.05 * (i + 1), [300 + 25 * j + i
                                           for j in range(19)], [])
             for i in range(3)]
    run = harness.Run(cell=None, seed=1,
                      shapes=work.Shapes.from_config(conf),
                      device_kind="TPU v5 lite", t_open=0.0, t_close=0.15,
                      ticks=ticks, reduced=r)
    got = {name: harness.load_reader(name)(run)
           for name in RECORDED_READINGS}
    assert got == pytest.approx(RECORDED_READINGS, rel=1e-12)
    assert r.breakdown()["idle_gaps"] == [
        ["emit", pytest.approx(0.007532459)],
        ["dispatch", pytest.approx(0.0008959450000000002)],
        ["none", pytest.approx(0.00022117100000000001)],
        ["schedule", pytest.approx(3.1339000000000006e-05)]]


def test_readings_are_none_without_the_programs_spans_and_scopes():
    """``trace_yi-6b.decode.json`` holds no program span, and a program
    without named scopes gives no op under ``layers``: nothing to read,
    and nothing raises."""
    events = tracing.load_events(benchroot.DATA / "trace_yi-6b.decode.json")
    unscoped = {tracing.op_key(e.name): "jit(paged_decode_step)/while/body"
                for e in events if e.line == tracing.OPS_LINE}
    assert program.readings(events, [], unscoped, ticks=3) == {
        "emit_fetch_idle_ms_per_tick": None,
        "emit_sample_idle_ms_per_tick": None,
        "layer_scan_copy_ms_per_step": None}


def _recorded_program_trace():
    """Three ticks of yi-6b.decode on one TPU v5e (seed 2147915001),
    written by ``perfbench/trace_program.py --record``: the device's ops
    and programs, the benchmark's and the program's spans, and the decode
    step's scope map."""
    rec = json.loads((benchroot.DATA / "trace_yi-6b.decode.program.json")
                     .read_text())
    return ([Event(*row) for row in rec["events"]],
            [Span(*row) for row in rec["spans"]], rec["scopes"])


def test_readings_of_the_recorded_program_trace():
    events, spans, scopes = _recorded_program_trace()
    lo, hi = program.window(events)
    ticks = [e for e in events
             if e.name == "perfbench.schedule" and lo <= e.start_ns < hi]
    assert len(ticks) == 3
    assert program.readings(events, spans, scopes, len(ticks)) \
        == pytest.approx({"emit_fetch_idle_ms_per_tick": 1.2644366666666669,
                          "emit_sample_idle_ms_per_tick": 0.7227,
                          "layer_scan_copy_ms_per_step": 18.461129666666597})
    # the emit's four children hold the idle the benchmark charges to its
    # own emit phase, and program spans cover nearly all of the idle
    reduced = tracing.reduce(events)
    idle = program.idle_by_span(events, spans)
    children = sum(idle[f"engine.{c}"] for c in EMIT_CHILDREN)
    assert children == pytest.approx(reduced.idle_by_phase["emit"],
                                     rel=0.05)
    assert idle["none"] < 0.1 * (reduced.window_s - reduced.busy_s)
    # the whole-pool copies, one layer's K or V pool each, are the scan's
    whole_pool = {tracing.op_key(e.name) for e in events
                  if e.line == tracing.OPS_LINE and "bf16[12820480]" in e.name}
    assert whole_pool == {"fusion.110", "fusion.112"}
    assert all(program.is_scan_copy(scopes[k]) for k in whole_pool)
