"""Trace one window of a cell and split it by the program's own spans and
named scopes.

    python3 perfbench/trace_program.py --workload yi-6b.decode --seed 7 \
        --seconds 51 [--record trace.json]

Runs the cell as ``run.py --trace 1`` does, up to the window's end (no
reference check), and prints one JSON line: the traced window's existing
per-layer metrics; the device's idle time charged to the innermost
``repro.*`` span (:func:`pb.program.idle_by_span`), in all and per tick;
the decode step's device time by ``op_name`` (the map comes from the
step's optimised HLO text, compiled after the warm-up at the window's
shapes), its longest ops with their scope, and the ms per step of the
layer scan's own copies (the trace's op events carry no ``op_name`` on
a v5e).
``--record`` writes three ticks from the middle of the window, with their
spans and the ops' scopes, in the form ``tests/perfbench`` reads.
"""
import argparse
import json
import os
import shutil
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

from pb import harness, program, tracing, work  # noqa: E402


def decode_hlo(eng) -> str:
    """The decode step's optimised HLO at the shapes the window calls it
    with (the compile cache holds it after :func:`harness.warm_up`)."""
    import jax.numpy as jnp

    n = eng.n_lanes
    tbl = jnp.zeros_like(eng.kv.decode_extra()[0])
    return eng._decode.lower(eng.params, eng.kv.caches, tbl,
                             jnp.zeros((n, 1), jnp.int32),
                             jnp.zeros((n,), jnp.int32)).compile().as_text()


def record(events, spans, scopes, ticks: int = 3) -> dict:
    """``ticks`` ticks from the middle of the window, from the start of
    one ``perfbench.schedule`` span to that of the ``ticks``-th after it,
    on a clock that starts there; a ``perfbench.window`` span covers
    them."""
    sched = sorted(e.start_ns for e in events
                   if e.name == "perfbench.schedule")
    mid = len(sched) // 2
    lo, hi = sched[mid], sched[mid + ticks]

    def inside(a, d):
        return a < hi and a + d > lo

    rows = [[e.plane, e.line, e.name[:80], e.start_ns - lo, e.dur_ns]
            for e in events
            if e.name != tracing.WINDOW_SPAN and inside(e.start_ns, e.dur_ns)]
    rows.append(["/host:CPU", "python3", tracing.WINDOW_SPAN, 0.0, hi - lo])
    keys = {tracing.op_key(r[2]) for r in rows if r[1] == tracing.OPS_LINE}
    return {"events": rows,
            "spans": [[s.name, s.start_ns - lo, s.dur_ns, s.meta]
                      for s in spans if inside(s.start_ns, s.dur_ns)],
            "scopes": {k: v for k, v in scopes.items() if k in keys}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--record", default=None)
    args = ap.parse_args()

    from repro.models import build_model

    from pb import weights

    cell = harness.load_cell(harness.REPO, args.workload)
    devs = harness.find_devices(cell.chips)
    harness.enable_cache()
    run = harness.Run(cell=cell, seed=args.seed,
                      shapes=work.Shapes.from_config(cell.config),
                      device_kind=devs[0].device_kind, seconds=args.seconds)
    model = build_model(harness.arch_config(cell.config))
    params = weights.make(model, args.seed)
    eng, sizes = harness.build_engine(cell, model, params, devs)
    harness.warm_up(eng)
    t0 = time.monotonic()
    scopes = program.hlo_scopes(decode_hlo(eng))
    hlo_s = time.monotonic() - t0
    drv = harness.Driver(eng, trace=True)
    tracer = harness.Tracer(True)
    harness.drive_backlog(run, drv, tracer, args.seconds, T_START)
    try:
        events = tracing.load_xspace(tracer.dir)
        spans = program.load_spans(tracer.dir)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    run.reduced = tracing.reduce(events)
    ticks = len(run.ticks)
    idle = program.idle_by_span(events, spans)
    by_scope, calls = program.scope_seconds(events, program.DECODE, scopes)
    per_op = {}
    lo, hi = program.window(events)
    for e in events:
        if e.line == tracing.OPS_LINE and e.end_ns > lo and e.start_ns < hi:
            key = tracing.op_key(e.name)
            per_op.setdefault(key, [e.name[:64], 0.0])
            per_op[key][1] += (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9
    top = sorted(((k, v[0], v[1], scopes.get(k)) for k, v in per_op.items()
                  if k in scopes and k.split(".")[0]
                  not in tracing.CONTAINER_OPS), key=lambda r: -r[2])[:16]
    emit_children = sum(v for k, v in idle.items()
                        if k.startswith("engine.emit."))
    existing = {}
    for m in cell.per_layer:
        v = harness.load_reader(m["name"])(run)
        if v is not None:
            existing[m["name"]] = v
    idle_s = run.reduced.window_s - run.reduced.busy_s
    line = {
        "workload": args.workload, "seed": args.seed, "sizes": sizes,
        "ticks": ticks, "window_s": run.window_s,
        "output_tok_s": sum(t.tokens for t in run.ticks) / run.window_s,
        "existing": existing,
        **program.readings(events, spans, scopes, ticks),
        "idle_s": idle_s,
        "idle_by_span": dict(sorted(idle.items(), key=lambda v: -v[1])),
        "emit_children_idle_over_emit_phase":
            emit_children / run.reduced.idle_by_phase.get("emit", 1e-12),
        "none_share_of_idle": idle["none"] / idle_s,
        "decode_calls": calls,
        "decode_ms_by_scope": {k if k is not None else "<not in map>":
                               s / max(calls, 1) * 1e3 for k, s in
                               sorted(by_scope.items(), key=lambda v: -v[1])
                               [:40]},
        "top_decode_ops": top,
        "hlo_compile_s": hlo_s,
    }
    if args.record:
        with open(args.record, "w") as f:
            json.dump(record(events, spans, scopes), f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
