"""From a profiler trace to device busy time, op times and idle gaps.

The reduction reads three kinds of event:

* device operations: line ``XLA Ops`` of each ``/device:<kind>:<n>`` plane;
* device programs: line ``XLA Modules`` of the same planes, one event per
  call of a compiled program (``jit_paged_decode_step(<hash>)``);
* the benchmark's own host spans, ``perfbench.<phase>``, which it writes
  with ``jax.profiler.TraceAnnotation`` around the calls into the engine.

All three share the trace's clock.  Busy time is the union of the device
operations' intervals inside the window (the ``perfbench.window`` span);
every idle stretch is charged to the host phase whose span covers it.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "perfbench."
WINDOW_SPAN = "perfbench.window"
#: ops that only contain other ops: counted in busy time through their
#: children, never as an op of their own
CONTAINER_OPS = ("while", "conditional", "call")
LABEL_CHARS = 64


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


def load_xspace(log_dir: str) -> list[Event]:
    """The events this reduction reads, from the one ``*.xplane.pb`` that
    ``jax.profiler`` wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, "
                           f"found {paths}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def save_events(events: list[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([[e.plane, e.line, e.name, e.start_ns, e.dur_ns]
                   for e in events], f)


def load_events(path: str) -> list[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def op_key(name: str) -> str:
    """``%fusion.112 = bf16[...] fusion(...)`` -> ``fusion.112``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def program_key(name: str) -> str:
    """``jit_paged_decode_step(9405...)`` -> ``jit_paged_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


@dataclass
class Reduced:
    """What a traced window held, in seconds."""

    window_s: float
    busy_s: float                              # averaged over devices
    devices: int
    ops: dict = field(default_factory=dict)    # label -> seconds
    programs: dict = field(default_factory=dict)   # name -> [seconds]
    idle_by_phase: dict = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose key matches ``pattern`` (a
        regular expression, matched from the key's start)."""
        rx = re.compile(pattern)
        return sum(s for label, s in self.ops.items()
                   if rx.match(op_key(label)))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda v: -v[1])[:n]
        gaps = sorted(self.idle_by_phase.items(), key=lambda v: -v[1])[:n]
        return {"device_ops": [[label, s] for label, s in ops],
                "idle_gaps": [[p, s] for p, s in gaps]}


def reduce(events: list[Event]) -> Reduced:
    """Reduce one traced window; its bounds are the ``perfbench.window``
    host span."""
    win = [e for e in events if e.name == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(win)}")
    lo, hi = win[0].start_ns, win[0].end_ns
    dev_ops = defaultdict(list)
    ops: dict = {}
    programs = defaultdict(list)
    for e in events:
        if not is_device_plane(e.plane):
            continue
        a, b = _clip(e.start_ns, e.end_ns, lo, hi)
        if b <= a:
            continue
        if e.line == MODULES_LINE:
            programs[program_key(e.name)].append(e.dur_ns * 1e-9)
            continue
        dev_ops[e.plane].append((a, b))
        if op_key(e.name).split(".")[0] in CONTAINER_OPS:
            continue
        # an op's name is unique within its program only: its output
        # shape, which follows the name, tells the programs' ops apart
        label = e.name[:LABEL_CHARS]
        ops[label] = ops.get(label, 0.0) + (b - a) * 1e-9
    if not dev_ops:
        raise ValueError("no device operation ran inside the window")
    busy = {p: _union(iv) for p, iv in dev_ops.items()}
    busy_s = sum(sum(b - a for a, b in iv) for iv in busy.values()) \
        / len(busy) * 1e-9
    # idle stretches of the first device, charged to the host phase whose
    # span covers them (phases do not overlap: the benchmark runs them in
    # turn on one thread)
    spans = sorted((e.start_ns, e.end_ns, e.name[len(SPAN_PREFIX):])
                   for e in events
                   if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW_SPAN)
    first = busy[sorted(busy)[0]]
    idle, t = [], lo
    for a, b in first:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    if t < hi:
        idle.append((t, hi))
    starts = [s[0] for s in spans]
    by_phase = defaultdict(float)
    for a, b in idle:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(spans) and spans[i][0] < b:
            ca, cb = _clip(spans[i][0], spans[i][1], a, b)
            if cb > ca:
                by_phase[spans[i][2]] += (cb - ca) * 1e-9
                covered += cb - ca
            i += 1
        if b - a - covered > 0:
            by_phase["none"] += (b - a - covered) * 1e-9
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_s,
                   devices=len(busy), ops=ops, programs=dict(programs),
                   idle_by_phase=dict(by_phase))
