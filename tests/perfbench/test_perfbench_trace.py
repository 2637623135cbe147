"""The reduction from a profiler trace to busy time, op times, program
times and idle gaps charged to the host's phases."""
import pytest

import benchroot
from pb import tracing
from pb.tracing import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"
RECORDED = benchroot.DATA / "trace_yi-6b.decode.json"


def _ms(a, b):
    return a * 1e6, (b - a) * 1e6


def _synthetic():
    """A 100 ms window: a decode program 10-40 ms with two ops and a
    kernel, a prefill program 60-80 ms; the host schedules 0-10 ms, emits
    40-55 ms and waits 80-100 ms."""
    ev = [Event(HOST, "python", "perfbench.window", *_ms(0, 100)),
          Event(HOST, "python", "perfbench.schedule", *_ms(0, 10)),
          Event(HOST, "python", "perfbench.dispatch", *_ms(10, 11)),
          Event(HOST, "python", "perfbench.emit", *_ms(40, 55)),
          Event(HOST, "python", "perfbench.schedule", *_ms(55, 60)),
          Event(HOST, "python", "perfbench.wait", *_ms(80, 100)),
          Event(DEV, "XLA Modules", "jit_paged_decode_step(123)",
                *_ms(10, 40)),
          Event(DEV, "XLA Modules", "jit_paged_prefill_step(456)",
                *_ms(60, 80)),
          Event(DEV, "XLA Ops", "%while.5 = (s32[]) while(...)",
                *_ms(10, 38)),
          Event(DEV, "XLA Ops",
                "%flash_paged_decode.9 = bf16[8,4,8,128] custom-call(...)",
                *_ms(12, 30)),
          Event(DEV, "XLA Ops", "%fusion.1 = bf16[8,4096] fusion(...)",
                *_ms(30, 40)),
          Event(DEV, "XLA Ops",
                "%flash_paged_prefill.2 = bf16[1,32,256,128] custom-call()",
                *_ms(60, 70)),
          Event(DEV, "XLA Ops", "%fusion.1 = bf16[1,256,4096] fusion(...)",
                *_ms(70, 80)),
          # outside the window: ignored
          Event(DEV, "XLA Ops", "%fusion.1 = bf16[8,4096] fusion(...)",
                *_ms(120, 130))]
    return ev


def test_busy_idle_and_phases():
    r = tracing.reduce(_synthetic())
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.05)     # 10-40 and 60-80 ms
    assert r.idle_share == pytest.approx(0.5)
    gaps = r.idle_by_phase
    assert gaps["schedule"] == pytest.approx(0.015)   # 0-10, 55-60
    assert gaps["emit"] == pytest.approx(0.015)       # 40-55
    assert gaps["wait"] == pytest.approx(0.02)        # 80-100
    assert "dispatch" not in gaps                     # device was busy
    assert sum(gaps.values()) == pytest.approx(0.05)


def test_ops_and_programs():
    r = tracing.reduce(_synthetic())
    # containers are not ops of their own; ops of one name but another
    # shape (another program) stay apart
    assert not any(k.startswith("%while") for k in r.ops)
    assert r.op_seconds(r"flash_paged_decode") == pytest.approx(0.018)
    assert r.op_seconds(r"flash_paged_prefill") == pytest.approx(0.010)
    assert r.op_seconds(r"fusion\.1$") == pytest.approx(0.020)
    assert len([k for k in r.ops if k.startswith("%fusion.1")]) == 2
    assert r.programs["jit_paged_decode_step"] == [pytest.approx(0.03)]
    assert r.programs["jit_paged_prefill_step"] == [pytest.approx(0.02)]
    top = r.breakdown()
    assert top["device_ops"][0][0].startswith("%flash_paged_decode")
    assert top["idle_gaps"][0][0] == "wait"
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10


def test_window_is_required():
    ev = [e for e in _synthetic() if e.name != tracing.WINDOW_SPAN]
    with pytest.raises(ValueError):
        tracing.reduce(ev)


def test_save_and_load_round_trip(tmp_path):
    ev = _synthetic()
    tracing.save_events(ev, tmp_path / "t.json")
    assert tracing.load_events(tmp_path / "t.json") == ev


def test_recorded_chip_trace():
    """150 ms of a traced window of yi-6b.decode on one TPU v5e (19 lanes):
    three decode steps, the device idle while the host waits in emit."""
    r = tracing.reduce(tracing.load_events(RECORDED))
    assert r.window_s == pytest.approx(0.15)
    assert r.devices == 1
    assert 0.0 < r.idle_share < 0.2
    assert sum(r.idle_by_phase.values()) == pytest.approx(
        r.window_s - r.busy_s)
    assert max(r.idle_by_phase, key=r.idle_by_phase.get) == "emit"
    steps = r.programs["jit_paged_decode_step"]
    assert len(steps) == 3 and all(0.06 < s < 0.07 for s in steps)
    top = r.breakdown()["device_ops"]
    assert top[0][0].startswith("%flash_paged_decode")
    assert r.op_seconds(r"flash_paged_decode") == pytest.approx(top[0][1])
    assert r.op_seconds(r"flash_paged_decode") < r.busy_s


def test_readers_on_the_recorded_trace():
    """Every per-layer reader of the offline cells reads the recorded
    trace with ticks like those it held; no share passes 100%."""
    import json

    from pb import harness, work
    spec = json.loads((benchroot.REPO / "BENCHMARK.json").read_text())
    conf = json.loads((benchroot.REPO / "perfbench/configs/yi-6b.json")
                      .read_text())
    r = tracing.reduce(tracing.load_events(RECORDED))
    ticks = [harness.Tick(i, 0.05 * i, 0.05 * i, 0.05 * i + 0.002,
                          0.05 * (i + 1), [300 + 25 * j + i
                                           for j in range(19)], [])
             for i in range(3)]
    run = harness.Run(cell=None, seed=1,
                      shapes=work.Shapes.from_config(conf),
                      device_kind="TPU v5 lite", t_open=0.0, t_close=0.15,
                      ticks=ticks, reduced=r)
    names = [m for m in spec["per_layer"]
             if "yi-6b.decode" in m["workloads"]]
    assert names
    for m in names:
        v = harness.load_reader(m["name"])(run)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100.0, m["name"]
