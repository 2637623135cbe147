"""Run one cell of the benchmark once.

A cell names a configuration and a traffic mix in ``BENCHMARK.json``.
Everything that belongs to one of them is a file of its own, found by name:

* ``perfbench/configs/<config>.json`` (the path is the cell's ``file``):
  the published sizes, the program's architecture id, the serving sizes,
  the plain reference (``perfbench/references/<reference>.py``) and the
  limit of the comparison;
* ``perfbench/traffic/<traffic>.json``: the mix, read by ``traffic.py``;
* ``perfbench/metrics/<metric>.py``: one reader per metric, ``read(run)``,
  which returns the number or ``None`` where it finds nothing to read.

The timed path is the program's ``ServingEngine`` (paged KV pool, chunked
prefill, prefix cache where the mix asks for it, greedy sampling), built
as ``repro.launch.serve.build_serving`` builds it for ``serve --full`` but
with the weights the benchmark made, and driven one tick at a time through
its ``schedule`` / ``dispatch`` / ``emit`` phases.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import heapq
import importlib.util
import inspect
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import traffic, work

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def find_devices(chips: int):
    """The chips this run measures.  A run that finds none fails: no
    measurement falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


# -- the cell ----------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    chips: int
    end_to_end: list
    per_layer: list


def load_cell(root: Path, workload: str) -> Cell:
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(root / conf["file"]) as f:
        config = json.load(f)
    mix = traffic.load(str(root / "perfbench" / "traffic"
                           / f"{w['traffic']}.json"))
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m["workloads"]] \
        + [m for m in spec["per_layer"]
           if "workloads" not in m and m["moves"] in moves]
    return Cell(workload, w["config"], config, w["traffic"], mix,
                int(w["chips"]), e2e, per_layer)


def _load(kind: str, name: str):
    """The module ``perfbench/<kind>/<name>.py``; a name may hold dots."""
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_')}",
        BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return _load("metrics", name).read


def load_reference(name: str):
    return _load("references", name)


def arch_config(config: dict):
    """The program's architecture config at the sizes the file states.

    The file's ``rms_norm_eps`` is served as the config's ``norm_eps``
    where the program's config carries that field.  Where it does not, the
    program's norms take ``rms_norm``'s default eps, and a configuration
    that states another is refused: a run that serves another eps than the
    file states is no sound run of that configuration."""
    from repro.configs import get_arch
    from repro.models import layers

    if "rms_norm_eps" not in config:
        raise ValueError("the configuration states no rms_norm_eps")
    heads = config["num_attention_heads"]
    dtype = config["torch_dtype"]
    arch = dataclasses.replace(
        get_arch(config["program_arch"]),
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=heads, n_kv_heads=config["num_key_value_heads"],
        d_head=config.get("head_dim", config["hidden_size"] // heads),
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]), param_dtype=dtype,
        compute_dtype=dtype, cache_dtype=dtype)
    eps = float(config["rms_norm_eps"])
    if hasattr(arch, "norm_eps"):
        return dataclasses.replace(arch, norm_eps=eps)
    fixed = inspect.signature(layers.rms_norm).parameters["eps"].default
    if eps != fixed:
        raise ValueError(f"the program's rms_norm eps is fixed at {fixed}; "
                         f"the configuration states {eps}")
    return arch


# -- records of a run ----------------------------------------------------------------

@dataclass
class Tick:
    index: int
    t_start: float
    t_scheduled: float
    t_dispatched: float
    t_end: float
    decode_lens: list            # context of each decoding lane, new token in
    chunks: list                 # (start, end, final) of each prefill chunk

    @property
    def tokens(self) -> int:
        """Output tokens the tick emitted: one per decoding lane, and the
        first token of every prompt whose last chunk ran."""
        return len(self.decode_lens) + sum(1 for c in self.chunks if c[2])


@dataclass
class Sent:
    req: object                  # the engine's Request
    due: float                   # monotonic time it was due
    session: int = -1
    turn: int = 0


@dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Cell
    seed: int
    shapes: work.Shapes
    device_kind: str
    setup_s: float = 0.0
    seconds: float = 0.0         # the window's nominal length
    t_open: float = 0.0          # window opens (monotonic)
    t_close: float = 0.0         # window closes
    ticks: list = field(default_factory=list)    # the window's ticks
    sent: list = field(default_factory=list)
    reduced: object = None       # tracing.Reduced of a traced window
    log: list = field(default_factory=list)
    admitted: dict = field(default_factory=dict)   # rid -> tick index

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @property
    def peak(self) -> dict:
        return work.peaks(self.device_kind)

    def due_in_window(self) -> list:
        return [s for s in self.sent
                if self.t_open <= s.due < self.t_open + self.seconds]


# -- driving the engine -------------------------------------------------------------

class Driver:
    """Ticks the engine and records what each tick did, from the
    scheduler's lanes before and after the tick's phases."""

    def __init__(self, engine, trace: bool):
        self.eng = engine
        self.trace = trace
        self.n = 0
        self.reqs: dict = {}
        self.admitted: dict = {}         # rid -> index of its first tick

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"perfbench.{name}")

    def _lanes(self):
        return {l.rid: (l.phase, l.pos) for l in self.eng.scheduler.lanes
                if l.rid is not None}

    def tick(self) -> Tick:
        eng = self.eng
        pre = self._lanes()
        t0 = time.monotonic()
        with self.span("schedule"):
            eng.schedule()
        t1 = time.monotonic()
        chunks = []
        for rid, (phase, pos) in self._lanes().items():
            req = self.reqs[rid]
            if rid in pre:
                if pre[rid][0] != "prefill":
                    continue
                start = pre[rid][1]
            elif req.preemptions == 0:
                start = req.cached_tokens       # admitted in this tick
                self.admitted[rid] = self.n
            else:
                continue                        # resumed: not a new chunk
            end = pos if phase == "prefill" else len(req.prompt)
            if end > start:
                chunks.append((start, end, phase != "prefill"))
        with self.span("dispatch"):
            tw = eng.dispatch()
        t2 = time.monotonic()
        lens = [] if tw is None else \
            [eng.scheduler.lanes[i].pos + 1 for i in tw.decoding]
        with self.span("emit"):
            eng.emit(tw)
        t3 = time.monotonic()
        self.n += 1
        return Tick(self.n - 1, t0, t1, t2, t3, lens, chunks)


class Tracer:
    """The profiler around the window, writing under a temporary
    directory that is removed once the trace is read."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-") if on else None
        self._win = None

    def open(self):
        if not self.on:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._win = jax.profiler.TraceAnnotation("perfbench.window")
        self._win.__enter__()

    def close(self):
        if not self.on or self._win is None:
            return
        import jax
        self._win.__exit__(None, None, None)
        self._win = None
        jax.profiler.stop_trace()

    def reduce(self):
        from . import tracing
        try:
            return tracing.reduce(tracing.load_xspace(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def enable_cache() -> None:
    """JAX's persistent compilation cache, at the one directory that
    ``JAX_COMPILATION_CACHE_DIR`` names or else at ``.jax_cache`` in the
    checkout: a fixed path, so that only a cell's first run compiles."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def bytes_limit(devs) -> int:
    """Device memory a run may fill, on the fullest chip."""
    return min(int(d.memory_stats()["bytes_limit"]) for d in devs)


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


#: device bytes left free beside the weights and the two copies of the
#: pool, for the step programs' working memory (1.4 GiB)
HEADROOM_BYTES = 1_503_238_554


def build_engine(cell: Cell, model, params, devs):
    """The engine at the cell's sizes: the pool fills the chip's memory
    beside the weights and :data:`HEADROOM_BYTES` (the step returns a new
    pool beside the one it read, so two copies are live), and an offline
    cell takes as many lanes as the pool holds at its longest request."""
    from repro.serving import ServingEngine

    from . import weights

    conf, mix = cell.config, cell.mix
    serving = conf["serving"]
    psz = serving["page_size"]
    cfg = model.cfg
    page_bytes = (cfg.n_layers * 2 * cfg.n_kv_heads * psz * cfg.head_dim
                  * np.dtype(cfg.cache_dtype).itemsize)
    limit = bytes_limit(devs)
    w_bytes = weights.nbytes(params)
    n_pages = int((limit - w_bytes - HEADROOM_BYTES)
                  // (2 * page_bytes))
    if mix["kind"] == "backlog":
        max_len = traffic.Backlog(mix, 0, 1, 0).max_len
        lanes = (n_pages - 1) // math.ceil(max_len / psz)
    else:
        max_len = traffic.session_max_len(mix)
        lanes = int(mix["lanes"])
    max_len = psz * math.ceil((max_len + 1) / psz)
    if lanes < 1 or n_pages < math.ceil(max_len / psz) + 1:
        raise RuntimeError(f"{n_pages} pages of {page_bytes} bytes do not "
                           f"hold one request of {max_len} tokens")
    eng = ServingEngine(model, params, n_lanes=lanes, max_len=max_len,
                        eos_id=None, autotuner=None, cache="paged",
                        n_pages=n_pages, page_size=psz, timeslice=None,
                        prefill_chunk=serving["prefill_chunk"],
                        prefix_cache=bool(mix.get("prefix_cache", False)),
                        kv_dtype="fp")
    return eng, dict(pages=n_pages, page_bytes=page_bytes, lanes=lanes,
                     max_len=max_len, weight_bytes=w_bytes,
                     bytes_limit=limit)


def warm_up(eng) -> None:
    """Compile (or load from the cache) both step programs at the shapes
    the window calls them with, on the null page: outputs are dropped."""
    import jax
    import jax.numpy as jnp

    n, c = eng.n_lanes, eng.prefill_chunk
    kv = eng.kv
    zero_tbl = jnp.zeros_like(kv.decode_extra()[0])
    out = eng._decode(eng.params, kv.caches, zero_tbl,
                      jnp.zeros((n, 1), jnp.int32),
                      jnp.zeros((n,), jnp.int32))
    jax.block_until_ready(out)
    del out                    # its pool is a full copy of the engine's
    # the same host-side conversions and indexing as the engine's prefill
    # tick, which compile small programs of their own
    logits, caches = eng._prefill_step(
        eng.params, kv.caches, kv.table_row(0),
        jnp.asarray([[0] * c], jnp.int32), jnp.asarray([0], jnp.int32),
        jnp.asarray([1], jnp.int32), jnp.asarray([0], jnp.int32))
    np.asarray(logits[0])
    del logits, caches


def drive_backlog(run: Run, drv: Driver, tracer: Tracer, seconds: float,
                  t_start: float) -> None:
    eng, mix = drv.eng, run.cell.mix
    gen = traffic.Backlog(mix, run.seed, run.shapes.vocab, first=eng.n_lanes)
    from repro.serving import Request

    nxt = 0

    def top_up():
        nonlocal nxt
        while eng.scheduler.pending < eng.n_lanes:
            prompt, new = gen.request(nxt)
            req = Request(rid=nxt, prompt=prompt, max_new_tokens=new)
            drv.reqs[nxt] = req
            run.sent.append(Sent(req, time.monotonic()))
            eng.submit(req)
            nxt += 1

    for _ in range(int(mix["window_opens_at_tick"])):
        top_up()
        drv.tick()
    tracer.open()
    run.t_open = time.monotonic()
    run.setup_s = run.t_open - t_start
    while True:
        top_up()
        t = drv.tick()
        run.ticks.append(t)
        if t.t_end - run.t_open >= seconds:
            break
    tracer.close()
    run.t_close = run.ticks[-1].t_end


def warm_corpus(drv: Driver, docs) -> None:
    """Set-up of a mix whose documents are already cached when its users
    come: one request per document, one token each, so that every full
    page of every document is in the prefix index."""
    from repro.serving import Request

    eng = drv.eng
    for i, doc in enumerate(docs):
        rid = WARM_RID + i
        drv.reqs[rid] = Request(rid=rid, prompt=list(doc), max_new_tokens=1)
        eng.submit(drv.reqs[rid])
    while eng.active or eng.scheduler.has_queued:
        drv.tick()


#: request ids of set-up work, apart from the traffic's own
WARM_RID = 1 << 40


def drive_sessions(run: Run, drv: Driver, tracer: Tracer, seconds: float,
                   t_start: float, rate: float | None = None) -> None:
    """Open loop: sessions arrive at their fixed times whether or not the
    engine keeps up; a session's next question is due ``think_s`` after
    its answer finished.  Every request is timed from when it was due."""
    from repro.serving import Request

    eng, mix = drv.eng, run.cell.mix
    sessions = traffic.sessions(mix, run.seed, seconds, run.shapes.vocab,
                                rate_per_s=rate)
    if mix.get("warm_documents"):
        warm_corpus(drv, {id(s.doc): s.doc for s in sessions}.values())
    t_base = time.monotonic()
    due: list = [(t_base + s.arrival_s, s.sid, 0) for s in sessions]
    heapq.heapify(due)
    by_rid: dict = {}
    n_done = len(eng.finished)
    t_open_at = t_base + float(mix["lead_in_s"])
    grace = float(mix["grace_s"])
    opened = closed = False
    rid = 0
    while True:
        now = time.monotonic()
        if not opened and now >= t_open_at:
            tracer.open()
            now = run.t_open = time.monotonic()
            run.setup_s = now - t_start
            opened = True
        while due and due[0][0] <= now:
            t_due, sid, turn = heapq.heappop(due)
            s = sessions[sid]
            req = Request(rid=rid, prompt=s.prompt(turn),
                          max_new_tokens=s.answers[turn], submit_t=t_due)
            drv.reqs[rid] = req
            sent = Sent(req, t_due, sid, turn)
            run.sent.append(sent)
            by_rid[rid] = sent
            eng.submit(req)
            rid += 1
        if eng.active or eng.scheduler.has_queued:
            t = drv.tick()
            if opened and not closed:
                run.ticks.append(t)
            for req in eng.finished[n_done:]:
                sent = by_rid[req.rid]
                s = sessions[sent.session]
                s.served.append(list(req.out_tokens))
                if sent.turn + 1 < len(s.questions):
                    heapq.heappush(due, (req.finish_t + s.think_s[sent.turn],
                                         s.sid, sent.turn + 1))
            n_done = len(eng.finished)
        else:
            with drv.span("wait"):
                wake = due[0][0] if due else now + 0.01
                time.sleep(max(0.0, min(wake - now, 0.01)))
        now = time.monotonic()
        if opened and not closed and now - run.t_open >= seconds:
            tracer.close()
            run.t_close = now
            closed = True
        if closed:
            waiting = [s for s in run.due_in_window()
                       if s.req.first_token_t is None]
            if not waiting or now > run.t_close + grace:
                break
            if not due and not (eng.active or eng.scheduler.has_queued):
                break


# -- the comparison that decides `correct` -------------------------------------------

def sample_for_check(run: Run, n_tokens: int) -> list:
    """Finished requests to compare, drawn from the seed: the longest, the
    longest that the prefix cache did not serve (where one did), then
    others in a seeded order until ``n_tokens`` served tokens are in."""
    done = [s.req for s in run.sent if s.req.done
            and s.req.finish_t is not None and s.req.finish_t >= run.t_open]
    if not done:
        return []
    total = lambda r: len(r.prompt) + len(r.out_tokens)  # noqa: E731
    picked = {}
    first = [max(done, key=total)]
    if any(r.cached_tokens for r in done):
        first += [max((r for r in done if not r.cached_tokens), key=total,
                      default=first[0])]
    order = traffic.rng(run.seed, "tokens", 2_000_000).permutation(len(done))
    for r in first + [done[i] for i in order]:
        if sum(len(p.out_tokens) for p in picked.values()) >= n_tokens:
            break
        picked.setdefault(r.rid, r)
    return list(picked.values())


#: the lower precisions of the control, each a step below bfloat16
CONTROLS = ("int8", "fp8")


def compare(run: Run, params, picked: list, pad_to: int,
            control: bool = False) -> dict:
    """Run the plain reference once over each picked prompt with its served
    tokens.  The number compared is the widest gap by which a served
    token's reference logit lies below the reference's best at its
    position.  ``control`` also reads, at the same positions, the gap of
    the token that the reference computed in each lower precision of
    :data:`CONTROLS` puts first."""
    ref = load_reference(run.cell.config["reference"])
    conf = run.cell.config
    worst = {"gap": 0.0}
    worst_c = {q: {"gap": 0.0} for q in CONTROLS}
    n_tok = 0
    t0 = time.monotonic()
    for r in picked:
        seq = list(r.prompt) + list(r.out_tokens)
        rows = list(range(len(r.prompt) - 1, len(seq) - 1))
        logits = ref.logits(params, conf, seq, rows, pad_to)
        best = logits.max(-1)
        served = np.asarray(r.out_tokens)
        gaps = best - logits[np.arange(len(rows)), served]
        j = int(np.argmax(gaps))
        n_tok += len(rows)
        if gaps[j] >= worst["gap"]:
            worst = {"gap": float(gaps[j]), "rid": int(r.rid),
                     "token": j, "of": len(rows), "prompt": len(r.prompt),
                     "cached": int(r.cached_tokens)}
        for q in CONTROLS if control else ():
            low = ref.logits(params, conf, seq, rows, pad_to, quant=q)
            cg = best - logits[np.arange(len(rows)), low.argmax(-1)]
            jc = int(np.argmax(cg))
            if cg[jc] >= worst_c[q]["gap"]:
                worst_c[q] = {"gap": float(cg[jc]), "rid": int(r.rid),
                              "token": jc}
    out = {"gap": worst, "tokens": n_tok, "requests": len(picked),
           "seconds": time.monotonic() - t0}
    if control:
        out["control"] = worst_c
    return out


# -- one run -------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = REPO, t_start: float | None = None,
             control: bool = False, rate: float | None = None) -> dict:
    """Run ``workload`` once and return its result line (a dict), with the
    run's records under the key ``_run`` for callers in this package."""
    t_start = time.monotonic() if t_start is None else t_start
    cell = load_cell(root, workload)
    devs = find_devices(cell.chips)
    import jax

    from repro.models import build_model

    from . import weights

    enable_cache()
    kind = devs[0].device_kind
    shapes = work.Shapes.from_config(cell.config)
    run = Run(cell=cell, seed=seed, shapes=shapes, device_kind=kind,
              seconds=seconds)
    if trace:
        work.peaks(kind)              # a device missing from the table fails
    model = build_model(arch_config(cell.config))
    params = weights.make(model, seed)
    eng, sizes = build_engine(cell, model, params, devs)
    log(f"{workload} seed {seed} on {len(devs)} x {kind}; {sizes}")
    warm_up(eng)
    drv = Driver(eng, trace)
    tracer = Tracer(trace)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, s, **kw: compiles.append(ev)
        if ev == "/jax/core/compile/backend_compile_duration"
        and run.t_open and not run.t_close else None)
    if cell.mix["kind"] == "backlog":
        drive_backlog(run, drv, tracer, seconds, t_start)
    else:
        drive_sessions(run, drv, tracer, seconds, t_start, rate)
    peak = peak_bytes(devs)
    run.log.append(
        f"window {run.window_s!r} s: {len(run.ticks)} ticks, "
        f"{sum(t.tokens for t in run.ticks)} tokens, "
        f"{sum(len(t.chunks) for t in run.ticks)} prefill chunks, "
        f"{len(run.due_in_window())} requests due, compiles in window "
        f"{len(compiles)}, preemptions {eng.scheduler.preemptions}, "
        f"kv {json.dumps(eng.kv.stats().get('prefix'))}")
    run.admitted = drv.admitted
    # the reference runs once the program's state is freed
    eng.kv.caches = None
    del eng, drv
    gc.collect()
    check = cell.config["check"]
    picked = sample_for_check(run, int(cell.mix["check_tokens"]))
    pad_to = 512 * math.ceil(sizes["max_len"] / 512)
    cmp = compare(run, params, picked, pad_to, control=control)
    run.log.append(f"reference: {cmp}")
    missing = [s for s in run.due_in_window() if s.req.first_token_t is None] \
        if cell.mix["kind"] == "sessions" else []
    checks, correct = judge(check, cmp["gap"]["gap"], cmp["tokens"],
                            len(missing))
    if trace:
        run.reduced = tracer.reduce()
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = run.due_in_window() if cell.mix["kind"] == "sessions" \
        else [s for s in run.sent if _worked(s.req, run)]
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": len(attempted),
            "failed": len(missing), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.reduced.busy_s
        device["window_s"] = run.reduced.window_s
        line["breakdown"] = run.reduced.breakdown()
    if control:
        # each lower precision's tokens in the served ones' place, judged
        # by the same comparison as the program's
        line["control"] = {}
        for q, worst_c in cmp["control"].items():
            c_checks, c_correct = judge(check, worst_c["gap"], cmp["tokens"],
                                        len(missing))
            line["control"][q] = {"correct": c_correct, "checks": c_checks,
                                  "worst": worst_c}
    line["checks"] = checks
    line["_run"] = run
    return line


def judge(check: dict, gap: float, tokens: int, missing: int):
    """The numbers compared, each beside its limit, and whether they make
    the run correct: no served token further than ``logit_gap`` below the
    reference's best, at least ``min_compared_tokens`` compared, and every
    request due in the window given its first token."""
    checks = {
        "logit_gap": {"value": gap, "limit": check["logit_gap"]},
        "compared_tokens": {"value": tokens,
                            "limit": check["min_compared_tokens"]},
        "missing_first_tokens": {"value": missing, "limit": 0},
    }
    correct = (gap <= check["logit_gap"]
               and tokens >= check["min_compared_tokens"] and missing == 0)
    return checks, correct


def _worked(req, run: Run) -> bool:
    """An offline request had work in the window: it was admitted before
    the window closed and finished, if at all, after it opened."""
    return (req.admit_t is not None and req.admit_t < run.t_close
            and (req.finish_t is None or req.finish_t >= run.t_open))


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=t_start)
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    run = line.pop("_run")
    for msg in run.log:
        log(msg)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
