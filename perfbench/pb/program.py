"""The program's own spans and scopes, on the trace's clock.

The program writes host spans named ``repro.<name>`` (``src/repro/trace.py``)
and names its decode step's device ops with ``jax.named_scope``
(``layers``; inside it ``attn``, ``kv_write``, ``mlp``; then ``lm_head``).
This module reduces them, beside :mod:`pb.tracing`, which reads the device
and the benchmark's own ``perfbench.<phase>`` spans:

* :func:`idle_by_span`: each idle stretch of the device inside the window,
  charged to the innermost program span that covers it, or to ``none``;
* :func:`hlo_scopes`: each instruction of a compiled program's optimised
  HLO text to its ``op_name`` (a fusion without one takes its root's);
* :func:`scope_seconds`: a program's device time in the window by
  ``op_name``, through that map.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

from . import tracing

PREFIX = "repro."
DECODE = "jit_paged_decode_step"
#: the scopes inside the layer scan's body; the scan's own slicing and
#: stacking of its operands lies under ``layers`` and none of these
SCAN_BODY_SCOPES = ("attn", "kv_write", "mlp")


@dataclass
class Span:
    name: str                  # without the ``repro.`` prefix
    start_ns: float
    dur_ns: float
    meta: dict = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_spans(log_dir: str) -> list[Span]:
    """The program's host spans, with their metadata, from the one
    ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, "
                           f"found {paths}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if tracing.is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name[len(PREFIX):],
                                    float(ev.start_ns),
                                    float(ev.duration_ns), dict(ev.stats)))
    return out


def window(events: list[tracing.Event]) -> tuple[float, float]:
    win = [e for e in events if e.name == tracing.WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {tracing.WINDOW_SPAN} span, "
                         f"found {len(win)}")
    return win[0].start_ns, win[0].end_ns


def idle_stretches(events: list[tracing.Event]) -> list[tuple[float, float]]:
    """The first device's idle stretches inside the window, as
    :func:`pb.tracing.reduce` finds them."""
    lo, hi = window(events)
    ops = defaultdict(list)
    for e in events:
        if tracing.is_device_plane(e.plane) and e.line == tracing.OPS_LINE:
            a, b = max(e.start_ns, lo), min(e.end_ns, hi)
            if b > a:
                ops[e.plane].append((a, b))
    if not ops:
        raise ValueError("no device operation ran inside the window")
    idle, t = [], lo
    for a, b in tracing._union(ops[sorted(ops)[0]]):
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    if t < hi:
        idle.append((t, hi))
    return idle


def innermost(spans: list[Span]) -> list[tuple[float, float, str]]:
    """Disjoint stretches ``(start, end, name)``, each charged to the
    innermost span open in it.  Spans of one thread nest, so the innermost
    is the one opened last."""
    out = []
    stack: list[tuple[float, str]] = []        # (end, name) of open spans
    t = 0.0

    def close_until(s):
        nonlocal t
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for sp in sorted(spans, key=lambda s: (s.start_ns, -s.end_ns)):
        close_until(sp.start_ns)
        if stack and sp.start_ns > t:
            out.append((t, sp.start_ns, stack[-1][1]))
        stack.append((sp.end_ns, sp.name))
        t = max(t, sp.start_ns)
    close_until(float("inf"))
    return out


def idle_by_span(events: list[tracing.Event], spans: list[Span]) -> dict:
    """Idle seconds of the window by innermost program span, and ``none``
    where no program span covers the device's idle; every span seen in
    the window has an entry, 0.0 where the device never idled in it."""
    lo, hi = window(events)
    segs = innermost(spans)
    starts = [s[0] for s in segs]
    out = {s.name: 0.0 for s in spans if s.end_ns > lo and s.start_ns < hi}
    out["none"] = 0.0
    for a, b in idle_stretches(events):
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            ca, cb = max(segs[i][0], a), min(segs[i][1], b)
            if cb > ca:
                out[segs[i][2]] += (cb - ca) * 1e-9
                covered += cb - ca
            i += 1
        out["none"] += (b - a - covered) * 1e-9
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def hlo_scopes(text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` in one optimised HLO module's text
    (``compiled.as_text()``); ``""`` where an instruction carries none.  A
    fusion that carries none takes its fused computation's root's."""
    own, calls, root = {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if m.group(1):
            root[comp] = name

    def scope(name, depth=0):
        if own[name] or name not in calls or depth > 8:
            return own[name]
        r = root.get(calls[name])
        return scope(r, depth + 1) if r in own else ""

    return {name: scope(name) for name in own}


def scope_seconds(events: list[tracing.Event], program: str,
                  scopes: dict[str, str]) -> tuple[dict, int]:
    """Device seconds inside the window of the ops that ran inside calls
    of ``program`` (``jit_paged_decode_step``), by ``op_name`` through
    ``scopes`` (``None`` for an op the map lacks), and the number of the
    program's calls in the window."""
    lo, hi = window(events)
    calls = defaultdict(list)
    for e in events:
        if e.line == tracing.MODULES_LINE \
                and tracing.program_key(e.name) == program \
                and e.start_ns < hi and e.end_ns > lo:
            calls[e.plane].append((e.start_ns, e.end_ns))
    for iv in calls.values():
        iv.sort()
    out = defaultdict(float)
    for e in events:
        if e.plane not in calls or e.line != tracing.OPS_LINE:
            continue
        key = tracing.op_key(e.name)
        if key.split(".")[0] in tracing.CONTAINER_OPS:
            continue
        iv = calls[e.plane]
        i = bisect.bisect_right(iv, (e.start_ns, float("inf"))) - 1
        if i < 0 or e.start_ns >= iv[i][1]:
            continue
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            out[scopes.get(key)] += (b - a) * 1e-9
    return dict(out), sum(len(iv) for iv in calls.values())


def is_scan_copy(op_name: str | None) -> bool:
    """An op of the layer scan's own: under ``layers`` and under none of
    the body's scopes, or with no ``op_name`` at all (a copy the compiler
    put in)."""
    if op_name is None:
        return False
    parts = re.split("[/;]", op_name)     # XLA joins merged ops' names: ";"
    return op_name == "" or ("layers" in parts
                             and not set(parts) & set(SCAN_BODY_SCOPES))


def readings(events: list[tracing.Event], spans: list[Span],
             scopes: dict[str, str], ticks: int) -> dict:
    """What the program's spans and scopes give a traced window of
    ``ticks`` ticks: device-idle ms per tick inside ``engine.emit.fetch``
    and ``engine.emit.sample``, and device ms per decode step of the layer
    scan's own copies.  ``None`` where the program wrote no such span or
    scope (a program from before them)."""
    idle = idle_by_span(events, spans)
    by_scope, calls = scope_seconds(events, DECODE, scopes)
    scoped = any("layers" in re.split("[/;]", k or "") for k in by_scope)

    def per_tick(name):
        return idle[name] / ticks * 1e3 if name in idle and ticks else None

    return {
        "emit_fetch_idle_ms_per_tick": per_tick("engine.emit.fetch"),
        "emit_sample_idle_ms_per_tick": per_tick("engine.emit.sample"),
        "layer_scan_copy_ms_per_step":
            sum(s for k, s in by_scope.items() if is_scan_copy(k))
            / calls * 1e3 if scoped and calls else None,
    }
