"""Serving benchmark — dense vs paged vs chunked-prefill engines.

Runs the serving stack end-to-end (prefill, scheduler, KV backend, decode
dispatch) and emits ``BENCH_serving.json``:

* **uniform** cells — dense vs paged engines on short uniform prompts
  (the serving-latency quartet: tokens/s, p50/p99 TTFT, p50/p99 ITL);
* **mixed** cells — one long prompt ahead of several short ones on the
  paged engine, monolithic prefill vs chunked prefill.  The headline
  number is ``ttft_short_p50_s``: with chunked prefill the short requests
  decode while the long prompt streams in chunk by chunk, so their TTFT
  must drop vs the head-of-line-blocked monolithic run.
* **spec** cells — greedy speculative decoding on the paged engine, one
  cell per draft length k: a self-speculative draft (the target's
  leading layers) proposes k tokens per tick and the target verifies
  them in one chunked call.  Cells report tokens/s per k plus the
  measured ``accept_rate``, which ``compare.py`` gates above zero.  Spec
  cells run a 4-layer variant of the reduced config (draft = 3 layers):
  acceptance is a draft/target *agreement* property, and at random init
  a 1-of-2-layer draft almost never agrees while 3-of-4 reliably does.
* **gateway** cells — closed-loop load (``loadgen.py`` in-process)
  through the async HTTP/SSE gateway: Poisson session arrivals,
  heavy-tailed lengths, multi-turn prefix re-hits, bounded admission
  queue.  Headline numbers are ``slo_attainment`` and ``goodput_tok_s``
  (tokens/s from within-SLO requests), both gated by ``compare.py``;
  latencies in these cells are client-side (queueing + network +
  compute).
* **kv_dtype** cells — fp vs int8 KV pages on the paged engine at a
  *fixed byte budget*: both cells get the same pool bytes, so the int8
  cell (int8 pages + per-row fp32 scales, dequantized in-kernel) buys
  ~3x the pages and admits more concurrent tokens before swapping.
  Cells report ``capacity_tokens`` / ``max_concurrent_seqs`` / swap
  counts plus ``greedy_agreement`` — the int8 cell's token-level match
  against the fp cell's greedy outputs — both gated by ``compare.py``.
* **mesh** cells — tensor-parallel paged serving over a 2x2 device
  mesh (KV-head axis sharded over the model axis).  On the CPU each cell
  runs in a subprocess (``XLA_FLAGS`` must force 4 host devices before
  jax initializes); on an accelerator it runs in this process over its
  devices.  The cell serves the same greedy workload unsharded and on
  the mesh, and reports the mesh run's
  throughput plus ``greedy_agreement`` — its token-level match against
  the unsharded outputs, which the sharded dispatch keeps bit-identical
  (gated by ``compare.py``).
* **long_context** cells — 1-lane long-prompt decode on the paged
  engine, one cell pair per ``--long-lens`` entry: split-KV committed
  (run-time AT over the ``num_splits`` ladder, warm-loaded for the
  timed repeats) vs forced ``num_splits=1`` (the sequential kernel
  spelling).  Headline is ``itl_p50_s`` — the per-step critical path
  the Flash-Decoding split axis shortens; ``compare.py`` gates the
  committed cell's greedy agreement against forced-1 at 100% and its
  p50 ITL at <= forced-1.
* **shared_prefix** cells — every request carries the same long system
  prompt (the production shape: few-shot templates, multi-turn history)
  on the chunked paged engine, prefix cache off vs on.  The cached cell
  reports ``prefix_hit_rate`` (gated above zero by ``compare.py``) and
  its headline is ``ttft_p50_s``: admissions that seed the shared-prompt
  pages from the hash index skip those prefill chunks entirely.

Numbers are CPU-proxy (interpret-mode kernels on reduced configs) — the
*trajectory* across PRs is the signal, not the absolute values.
``benchmarks/compare.py`` gates that trajectory in CI against the
committed baseline.

Usage::

    PYTHONPATH=src python -m benchmarks.bench_serving \
        [--out BENCH_serving.json] [--requests 6] [--max-new 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

DEFAULT_ARCHS = ("yi-6b", "deepseek-7b")


def bench_one(arch: str, cache: str, n_requests: int, n_lanes: int,
              max_len: int, max_new: int, page_size: int,
              timeslice: int | None, seed: int = 0) -> dict:
    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    # undersized page pool (~60% of lane parity, floor: one full sequence
    # + null page + slack) so the paged engine actually experiences page
    # pressure rather than degenerating to dense
    n_pages = None
    if cache == "paged":
        blocks_per_seq = -(-max_len // page_size)
        parity = n_lanes * blocks_per_seq + 1
        n_pages = max(blocks_per_seq + 2, int(parity * 0.6))
    engine = ServingEngine(model, params, n_lanes=n_lanes, max_len=max_len,
                           cache=cache, n_pages=n_pages,
                           page_size=page_size, timeslice=timeslice)
    rng = np.random.default_rng(seed)
    t0 = time.time()
    for rid in range(n_requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(4, 12))).tolist()
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=max_new))
    finished = engine.run(max_steps=n_requests * (max_new + 6))
    wall = time.time() - t0
    s = engine.metrics.summary()
    return {
        "arch": arch, "cache": cache, "workload": "uniform",
        "n_lanes": n_lanes,
        "requests": n_requests, "finished": len(finished),
        "decode_steps": engine.steps,
        "generated_tokens": s["generated_tokens"],
        "tokens_per_s": s["generated_tokens"] / wall if wall else 0.0,
        "ttft_p50_s": s["ttft_s"]["p50"], "ttft_p99_s": s["ttft_s"]["p99"],
        "itl_p50_s": s["itl_s"]["p50"], "itl_p99_s": s["itl_s"]["p99"],
        "preemptions": s["preemptions"],
        "cache_stats": engine.kv.stats(),
        "wall_s": wall,
    }


def bench_kv_dtype(arch: str, kv_dtype: str, n_requests: int, n_lanes: int,
                   max_len: int, max_new: int, page_size: int,
                   timeslice: int | None, seed: int = 0):
    """fp vs int8 KV pages at a fixed byte budget (one cell per dtype).

    The budget is what the *fp* pool would spend at the uniform cells'
    undersized parity; each precision buys as many pages as fit in it.
    int8 pages cost ~1/3 the bytes (int8 payload + per-row fp32 scales
    vs f32), so the int8 cell runs the identical workload with ~3x the
    pages — more resident tokens, fewer preemption swaps.

    Returns ``(row, outputs)`` — outputs maps rid -> greedy tokens so
    the caller can score the int8 cell's agreement against the fp cell.
    """
    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    blocks_per_seq = -(-max_len // page_size)

    def per_page_bytes(quantized):
        caches = jax.eval_shape(
            lambda: model.init_paged_caches(4, page_size,
                                            quantized=quantized))
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(caches)) / 4

    parity = n_lanes * blocks_per_seq + 1
    budget = max(blocks_per_seq + 2, int(parity * 0.6)) \
        * per_page_bytes(False)
    n_pages = max(blocks_per_seq + 2,
                  int(budget // per_page_bytes(kv_dtype == "int8")))
    engine = ServingEngine(model, params, n_lanes=n_lanes, max_len=max_len,
                           cache="paged", n_pages=n_pages,
                           page_size=page_size, timeslice=timeslice,
                           kv_dtype=kv_dtype)
    rng = np.random.default_rng(seed)
    t0 = time.time()
    for rid in range(n_requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(4, 12))).tolist()
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=max_new))
    finished = engine.run(max_steps=n_requests * (max_new + 6))
    wall = time.time() - t0
    s = engine.metrics.summary()
    st = engine.kv.stats()
    row = {
        "arch": arch, "cache": "paged", "workload": "kv_dtype",
        "kv_dtype": kv_dtype, "n_lanes": n_lanes,
        "budget_bytes": int(budget), "n_pages": n_pages,
        "pool_bytes": st["pool_bytes"],
        "kv_bytes_per_token": st["kv_bytes_per_token"],
        "capacity_tokens": st["capacity_tokens"],
        "max_concurrent_seqs": (n_pages - 1) // blocks_per_seq,
        "swap_outs": st["swap_outs"], "swap_ins": st["swap_ins"],
        "requests": n_requests, "finished": len(finished),
        "decode_steps": engine.steps,
        "generated_tokens": s["generated_tokens"],
        "tokens_per_s": s["generated_tokens"] / wall if wall else 0.0,
        "ttft_p50_s": s["ttft_s"]["p50"], "ttft_p99_s": s["ttft_s"]["p99"],
        "itl_p50_s": s["itl_s"]["p50"], "itl_p99_s": s["itl_s"]["p99"],
        "preemptions": s["preemptions"],
        "wall_s": wall,
    }
    outputs = {r.rid: list(r.out_tokens) for r in finished}
    return row, outputs


def _mesh_cell(cell: dict) -> dict:
    """Serve one greedy workload unsharded and on ``cell["mesh"]`` in this
    process, over whatever devices ``jax.devices()`` reports; returns the
    mesh run's row with its ``greedy_agreement`` against the unsharded
    outputs."""
    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.distributed.sharding import make_serving_mesh
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    acfg = get_arch(cell["arch"]).reduced()
    model = build_model(acfg)
    params = model.init(jax.random.PRNGKey(cell["seed"]))

    def run(mesh_spec):
        engine = ServingEngine(model, params, n_lanes=cell["lanes"],
                               max_len=cell["max_len"], cache="paged",
                               page_size=cell["page_size"],
                               prefill_chunk=cell["prefill_chunk"],
                               mesh=make_serving_mesh(mesh_spec))
        rng = np.random.default_rng(cell["seed"])
        t0 = time.time()
        for rid in range(cell["requests"]):
            prompt = rng.integers(0, acfg.vocab_size,
                                  size=int(rng.integers(4, 12))).tolist()
            engine.submit(Request(rid=rid, prompt=prompt,
                                  max_new_tokens=cell["max_new"]))
        finished = engine.run(
            max_steps=cell["requests"] * (cell["max_new"] + 6))
        wall = time.time() - t0
        outs = {int(r.rid): [int(t) for t in r.out_tokens]
                for r in finished}
        return engine, outs, wall, len(finished)

    _, ref_outs, _, _ = run(None)
    engine, outs, wall, n_fin = run(cell["mesh"])
    s = engine.metrics.summary()
    match = total = 0
    for rid, ref in ref_outs.items():
        got = outs.get(rid, [])
        total += max(len(ref), len(got))
        match += sum(a == b for a, b in zip(ref, got))
    return {
        "n_devices": len(jax.devices()),
        "finished": n_fin,
        "decode_steps": engine.steps,
        "prefill_chunks": engine.prefill_chunks,
        "generated_tokens": s["generated_tokens"],
        "tokens_per_s": s["generated_tokens"] / wall if wall else 0.0,
        "ttft_p50_s": s["ttft_s"]["p50"], "ttft_p99_s": s["ttft_s"]["p99"],
        "itl_p50_s": s["itl_s"]["p50"], "itl_p99_s": s["itl_s"]["p99"],
        "preemptions": s["preemptions"],
        "greedy_agreement": match / total if total else 1.0,
        "wall_s": wall,
    }


# CPU only: XLA_FLAGS (forced host device count) takes effect only before
# jax initializes, and this process has already run jax cells
_MESH_CHILD = r"""
import json, sys
sys.path[:0] = sys.argv[2:4]
from bench_serving import _mesh_cell
print("MESH_ROW " + json.dumps(_mesh_cell(json.loads(sys.argv[1]))))
"""


def bench_mesh(arch: str, mesh: str, n_requests: int, n_lanes: int,
               max_len: int, max_new: int, page_size: int,
               prefill_chunk: int, seed: int = 0) -> dict:
    """Tensor-parallel paged serving on a device mesh.

    The cell serves the identical greedy workload unsharded and on the
    mesh, so ``greedy_agreement`` scores the sharded dispatch against
    single-device truth (1.0 = bit-identical, the design invariant the
    kernels' head-sharded shard_map + exact all-gather guarantees).  On
    an accelerator the cell runs in this process over its devices (a
    chip belongs to the one process that opened it); on the CPU a child
    interpreter forces the host device count."""
    import subprocess

    import jax

    cell = {"arch": arch, "mesh": mesh, "requests": n_requests,
            "lanes": n_lanes, "max_len": max_len, "max_new": max_new,
            "page_size": page_size, "prefill_chunk": prefill_chunk,
            "seed": seed}
    if jax.default_backend() != "cpu":
        row = _mesh_cell(cell)
    else:
        devices = 1
        for d in mesh.split("x"):
            devices *= int(d)
        here = os.path.dirname(os.path.abspath(__file__))
        src = os.path.join(here, "..", "src")
        env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_"
                   f"device_count={devices}")
        proc = subprocess.run(
            [sys.executable, "-c", _MESH_CHILD, json.dumps(cell), src,
             here],
            capture_output=True, text=True, timeout=900, env=env)
        if proc.returncode != 0:
            raise RuntimeError(f"mesh bench child failed:\n{proc.stderr}")
        line = next(ln for ln in proc.stdout.splitlines()
                    if ln.startswith("MESH_ROW "))
        row = json.loads(line[len("MESH_ROW "):])
    return {"arch": arch, "cache": "paged", "workload": "mesh",
            "mesh": mesh, "prefill_chunk": prefill_chunk,
            "n_lanes": n_lanes, "requests": n_requests, **row}


def bench_mixed(arch: str, prefill_chunk: int | None, n_short: int,
                n_lanes: int, max_len: int, max_new: int, page_size: int,
                long_len: int = 48, seed: int = 0) -> dict:
    """Mixed workload: one long prompt submitted *ahead of* short ones.

    Monolithic prefill (``prefill_chunk=None``) head-of-line-blocks the
    shorts behind the long prompt's one-shot prefill; chunked prefill
    streams the long prompt in while the shorts decode.  Both run the
    paged engine so the only variable is the prefill strategy.
    """
    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    engine = ServingEngine(model, params, n_lanes=n_lanes, max_len=max_len,
                           cache="paged", page_size=page_size,
                           prefill_chunk=prefill_chunk)
    rng = np.random.default_rng(seed)
    t0 = time.time()
    long_prompt = (rng.integers(0, cfg.vocab_size,
                                size=long_len) % cfg.vocab_size).tolist()
    engine.submit(Request(rid=0, prompt=long_prompt,
                          max_new_tokens=max_new))
    for rid in range(1, n_short + 1):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(4, 8))).tolist()
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=max_new))
    finished = engine.run(max_steps=(n_short + 1) * (max_new + 6)
                          + long_len)
    wall = time.time() - t0
    s = engine.metrics.summary()
    by_rid = {r.rid: r for r in finished}
    short_ttfts = sorted(r.first_token_t - r.submit_t
                         for r in finished if r.rid != 0)
    short_p50 = short_ttfts[len(short_ttfts) // 2] if short_ttfts else None
    long_ttft = (by_rid[0].first_token_t - by_rid[0].submit_t
                 if 0 in by_rid else None)
    return {
        "arch": arch, "cache": "paged", "workload": "mixed",
        "prefill_chunk": prefill_chunk, "n_lanes": n_lanes,
        "requests": n_short + 1, "finished": len(finished),
        "decode_steps": engine.steps,
        "prefill_chunks": engine.prefill_chunks,
        "generated_tokens": s["generated_tokens"],
        "tokens_per_s": s["generated_tokens"] / wall if wall else 0.0,
        "ttft_p50_s": s["ttft_s"]["p50"], "ttft_p99_s": s["ttft_s"]["p99"],
        "ttft_short_p50_s": short_p50, "ttft_long_s": long_ttft,
        "itl_p50_s": s["itl_s"]["p50"], "itl_p99_s": s["itl_s"]["p99"],
        "preemptions": s["preemptions"],
        "wall_s": wall,
    }


def bench_long_context(arch: str, long_len: int, n_requests: int,
                       max_new: int, page_size: int, repeats: int,
                       seed: int = 0) -> list:
    """Long-context decode: split-KV committed vs forced-sequential.

    One lane, long prompts: every decode step walks a long page table,
    so p50 ITL tracks the serial KV walk the Flash-Decoding split axis
    is meant to shorten.  Two cells per length, both through the serve
    harness with run-time tuning on: ``num_splits`` forced to 1 (the
    legacy sequential kernel spelling — only the ``block_k`` ladder is
    tuned) and the autotuned split ladder ({1, 2, 4}, committed per
    length bucket).  Each mode pays one cold run that tunes into its own
    workdir, then the timed repeats warm-load the committed DB
    (``measurements == 0`` — steady state, no tuning overhead in the
    rows) and the min-ITL repeat is kept.  The committed cell reports
    ``greedy_agreement`` against the forced-1 outputs — the split-KV
    combine is exact up to fp32 rounding and greedy argmax must not
    flip — and ``compare.py`` gates agreement at 100% plus committed
    p50 ITL <= forced-1 (the tuner may *pick* the sequential spelling,
    it must never commit something slower).
    """
    import tempfile

    from repro import at
    from repro.launch.serve import serve

    rows = []
    seq_outputs: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        for mode in (1, "auto"):
            kw = {"autotune": True}
            if mode != "auto":
                kw["num_splits"] = mode
            workdir = os.path.join(tmp, f"ns_{mode}")
            os.makedirs(workdir, exist_ok=True)
            best = None
            for rep in range(1 + max(1, repeats)):
                at.clear_published()
                try:
                    report = serve(
                        arch=arch, cache="paged", page_size=page_size,
                        n_requests=n_requests, n_lanes=1,
                        max_len=long_len + max_new + 4,
                        prompt_len=long_len, max_new=max_new,
                        workdir=workdir, seed=seed, **kw)
                finally:
                    at.clear_published()
                if rep == 0:
                    continue  # cold run pays the tuning measurements
                if best is None or \
                        (report["p50_itl_s"] if report["p50_itl_s"]
                         is not None else float("inf")) < \
                        (best["p50_itl_s"] if best["p50_itl_s"]
                         is not None else float("inf")):
                    best = report
            outputs = best["outputs"]
            if mode != "auto":
                seq_outputs = outputs
                agreement = 1.0
            else:
                match = total = 0
                for rid, ref in seq_outputs.items():
                    got = outputs.get(rid, [])
                    total += max(len(ref), len(got))
                    match += sum(a == b for a, b in zip(ref, got))
                agreement = match / total if total else 1.0
            committed = best.get("committed_buckets") or {}
            rows.append({
                "arch": arch, "cache": "paged", "workload": "long_context",
                "long_len": long_len, "num_splits": mode, "n_lanes": 1,
                "committed_splits": {
                    str(b): (pp or {}).get("num_splits")
                    for b, pp in committed.items()},
                "requests": n_requests, "finished": best["finished"],
                "decode_steps": best["decode_steps"],
                "generated_tokens": best["generated_tokens"],
                "tokens_per_s": best["tokens_per_s"],
                "ttft_p50_s": best["p50_ttft_s"],
                "ttft_p99_s": best["p99_ttft_s"],
                "itl_p50_s": best["p50_itl_s"],
                "itl_p99_s": best["p99_itl_s"],
                "warm_measurements": (best["autotune"] or {}).get(
                    "measurements"),
                "greedy_agreement": agreement,
                "preemptions": best["preemptions"],
                "wall_s": best["wall_s"],
            })
    return rows


def bench_spec(arch: str, spec_k: int, n_requests: int, n_lanes: int,
               max_len: int, max_new: int, page_size: int,
               seed: int = 0) -> dict:
    """Greedy speculative decoding on the paged engine (one cell per k).

    The draft is self-speculative: ``draft_config(depth_frac=0.75)`` of a
    4-layer variant of the reduced config, parameters sliced from the
    target's own leading layers with shared embed/head (see module
    docstring for why the depth is bumped for these cells).
    """
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    cfg = dataclasses.replace(get_arch(arch).reduced(), n_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    draft_model = model.draft_model(depth_frac=0.75)
    draft_params = model.slice_draft_params(params, draft_model)
    engine = ServingEngine(model, params, n_lanes=n_lanes, max_len=max_len,
                           cache="paged", page_size=page_size,
                           draft_model=draft_model,
                           draft_params=draft_params, spec_k=spec_k)
    rng = np.random.default_rng(seed)
    t0 = time.time()
    for rid in range(n_requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(4, 12))).tolist()
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=max_new))
    finished = engine.run(max_steps=n_requests * (max_new + 6))
    wall = time.time() - t0
    s = engine.metrics.summary()
    spec = engine.spec_stats()
    return {
        "arch": arch, "cache": "paged", "workload": "spec",
        "spec_k": spec_k, "n_layers": cfg.n_layers,
        "draft_layers": draft_model.cfg.n_layers, "n_lanes": n_lanes,
        "requests": n_requests, "finished": len(finished),
        "decode_steps": engine.steps, "spec_ticks": spec["spec_ticks"],
        "drafted_tokens": spec["drafted_tokens"],
        "accepted_tokens": spec["accepted_tokens"],
        "accept_rate": spec["accept_rate"],
        "generated_tokens": s["generated_tokens"],
        "tokens_per_s": s["generated_tokens"] / wall if wall else 0.0,
        "tokens_per_step": (s["generated_tokens"] / engine.steps
                            if engine.steps else 0.0),
        "ttft_p50_s": s["ttft_s"]["p50"], "ttft_p99_s": s["ttft_s"]["p99"],
        "itl_p50_s": s["itl_s"]["p50"], "itl_p99_s": s["itl_s"]["p99"],
        "preemptions": s["preemptions"],
        "cache_stats": engine.kv.stats(),
        "wall_s": wall,
    }


def bench_shared_prefix(arch: str, prefix_cache: bool, n_requests: int,
                        n_lanes: int, max_len: int, max_new: int,
                        page_size: int, prefix_len: int = 32,
                        prefill_chunk: int = 8, seed: int = 0) -> dict:
    """Shared-system-prompt workload on the chunked paged engine.

    Every request = one common ``prefix_len``-token prompt + a short
    unique tail.  With ``prefix_cache=True`` the first admission
    publishes the prefix's pages into the hash index and later
    admissions seed them (refcounted / copy-on-write), starting chunked
    prefill at the first uncached token — their TTFT drops by the
    skipped chunks.  Outputs are bit-identical either way; only the
    work changes.
    """
    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    engine = ServingEngine(model, params, n_lanes=n_lanes, max_len=max_len,
                           cache="paged", page_size=page_size,
                           prefill_chunk=prefill_chunk,
                           prefix_cache=prefix_cache)
    rng = np.random.default_rng(seed)
    t0 = time.time()
    prefix = rng.integers(0, cfg.vocab_size, size=prefix_len).tolist()
    for rid in range(n_requests):
        tail = rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(3, 7))).tolist()
        engine.submit(Request(rid=rid, prompt=prefix + tail,
                              max_new_tokens=max_new))
    finished = engine.run(
        max_steps=n_requests * (max_new + 6 + prefix_len))
    wall = time.time() - t0
    s = engine.metrics.summary()
    pc = s["prefix_cache"]
    kvp = engine.kv.stats().get("prefix", {})
    return {
        "arch": arch, "cache": "paged", "workload": "shared_prefix",
        "prefix_cache": prefix_cache, "prefix_len": prefix_len,
        "prefill_chunk": prefill_chunk, "n_lanes": n_lanes,
        "requests": n_requests, "finished": len(finished),
        "decode_steps": engine.steps,
        "prefill_chunks": engine.prefill_chunks,
        "prefix_hit_rate": pc["hit_rate"],
        "prefix_hit_tokens": pc["hit_tokens"],
        "pages_saved": kvp.get("pages_saved", 0),
        "cow_copies": kvp.get("cow_copies", 0),
        "generated_tokens": s["generated_tokens"],
        "tokens_per_s": s["generated_tokens"] / wall if wall else 0.0,
        "ttft_p50_s": s["ttft_s"]["p50"], "ttft_p99_s": s["ttft_s"]["p99"],
        "itl_p50_s": s["itl_s"]["p50"], "itl_p99_s": s["itl_s"]["p99"],
        "preemptions": s["preemptions"],
        "wall_s": wall,
    }


def bench_gateway(arch: str, n_requests: int, rate: float, turns: int,
                  max_new: int, queue_limit: int, seed: int = 0) -> dict:
    """Closed-loop load through the HTTP/SSE gateway (``loadgen.py``
    in-process: real localhost TCP, Poisson session arrivals, multi-turn
    prefix re-hits, bounded admission queue).  The cell's headline
    numbers are the two ``compare.py`` gates serving quality on:
    ``slo_attainment`` and ``goodput_tok_s`` (tokens/s from within-SLO
    requests only).  Latencies here are *client-side* — queueing,
    network and compute together."""
    import asyncio

    try:
        from .loadgen import run_in_process
    except ImportError:
        sys.path.insert(0, os.path.dirname(__file__))
        from loadgen import run_in_process

    report = asyncio.run(run_in_process(
        arch=arch, queue_limit=queue_limit, seed=seed,
        n_requests=n_requests, rate=rate, turns=turns,
        out_mean=max(2.0, max_new * 0.75), max_out=max_new))
    server = report["server"]
    return {
        "arch": arch, "cache": "paged", "workload": "gateway",
        "prefill_chunk": 16, "prefix_cache": True,
        "requests": report["requests"], "finished": report["completed"],
        "sessions": report["sessions"], "turns": report["turns"],
        "arrival_rate_per_s": report["arrival_rate_per_s"],
        "queue_limit": queue_limit,
        "rejected_429": report["rejected_429"],
        "generated_tokens": report["generated_tokens"],
        "tokens_per_s": report["tokens_per_s"],
        "goodput_tok_s": report["goodput_tok_s"],
        "slo_attainment": report["slo_attainment"],
        "slo_ok": report["slo_ok"],
        "slo_ttft_s": report["slo_ttft_s"],
        "slo_itl_s": report["slo_itl_s"],
        "queue_wait_p50_s": report["queue_wait_s"]["p50"],
        "queue_wait_p99_s": report["queue_wait_s"]["p99"],
        "ttft_p50_s": report["ttft_s"]["p50"],
        "ttft_p99_s": report["ttft_s"]["p99"],
        "itl_p50_s": report["itl_s"]["p50"],
        "itl_p99_s": report["itl_s"]["p99"],
        "prefix_hit_tokens": report["prefix_hit_tokens"],
        "ticks": server["ticks"],
        "overlapped_ticks": server["overlapped_ticks"],
        "preemptions": server["preemptions"],
        "wall_s": report["wall_s"],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--archs", nargs="+", default=list(DEFAULT_ARCHS))
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--lanes", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--timeslice", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="chunk size for the mixed-workload chunked cells")
    ap.add_argument("--long-len", type=int, default=48,
                    help="long-prompt length in the mixed workload")
    ap.add_argument("--long-lens", type=int, nargs="+", default=[32, 64],
                    help="prompt-length sweep for the long_context "
                         "split-KV cells (committed vs forced-1, one "
                         "cell pair per length)")
    ap.add_argument("--spec-ks", type=int, nargs="+", default=[1, 4],
                    help="draft lengths for the speculative cells "
                         "(one cell per k)")
    ap.add_argument("--mesh", default="2x2",
                    help="device mesh 'RxC' (data x model) for the "
                         "tensor-parallel cells; forced host devices, "
                         "run in a subprocess")
    ap.add_argument("--prefix-len", type=int, default=64,
                    help="shared system-prompt length for the "
                         "shared_prefix cells (cache off vs on)")
    ap.add_argument("--gateway-requests", type=int, default=24,
                    help="total requests the gateway load cell drives "
                         "through the HTTP/SSE front-end")
    ap.add_argument("--gateway-rate", type=float, default=50.0,
                    help="Poisson session-arrival rate for the gateway "
                         "cell (sessions/s)")
    ap.add_argument("--gateway-turns", type=int, default=2,
                    help="closed-loop turns per gateway session")
    ap.add_argument("--gateway-queue-limit", type=int, default=32,
                    help="gateway admission-queue bound (429 beyond)")
    ap.add_argument("--repeats", type=int, default=2,
                    help="run each cell N times, keep the best run: the "
                         "first repeat pays jit compile time, later ones "
                         "reuse the in-process cache, so best-of-N "
                         "measures steady-state serving rather than "
                         "compile jitter")
    args = ap.parse_args()

    def fmt(x, spec):
        return format(x, spec) if x is not None else "n/a"

    def best_of(run):
        rows = [run() for _ in range(max(1, args.repeats))]
        return max(rows, key=lambda r: r["tokens_per_s"])

    results = []
    for arch in args.archs:
        for cache in ("dense", "paged"):
            ts = args.timeslice if cache == "paged" else None
            row = best_of(lambda: bench_one(
                arch, cache, args.requests, args.lanes, args.max_len,
                args.max_new, args.page_size, ts))
            results.append(row)
            print(f"[bench_serving] {arch:14s} {cache:6s} uniform  "
                  f"{row['tokens_per_s']:8.1f} tok/s  "
                  f"ttft p50 {fmt(row['ttft_p50_s'], '.3f')}s "
                  f"p99 {fmt(row['ttft_p99_s'], '.3f')}s  "
                  f"itl p50 {fmt(row['itl_p50_s'], '.4f')}s  "
                  f"preempt {row['preemptions']}")
        # kv precision at fixed bytes: the int8 cell buys ~3x the pages
        # for the same budget and must track the fp cell's greedy
        # outputs (compare.py gates capacity ratio and agreement)
        kv_outputs: dict = {}
        for kvd in ("fp", "int8"):
            runs = [bench_kv_dtype(arch, kvd, args.requests, args.lanes,
                                   args.max_len, args.max_new,
                                   args.page_size, args.timeslice)
                    for _ in range(max(1, args.repeats))]
            row, outs = max(runs, key=lambda t: t[0]["tokens_per_s"])
            kv_outputs[kvd] = outs
            if kvd == "fp":
                row["greedy_agreement"] = 1.0
            else:
                match = total = 0
                for rid, ref in kv_outputs["fp"].items():
                    got = outs.get(rid, [])
                    total += max(len(ref), len(got))
                    match += sum(a == b for a, b in zip(ref, got))
                row["greedy_agreement"] = match / total if total else 1.0
            results.append(row)
            print(f"[bench_serving] {arch:14s} paged  kv/{kvd:9s} "
                  f"{row['tokens_per_s']:8.1f} tok/s  "
                  f"cap {row['capacity_tokens']} tok "
                  f"({row['n_pages']} pages, "
                  f"{row['kv_bytes_per_token']:.0f} B/tok)  "
                  f"swaps {row['swap_outs']}  "
                  f"agree {row['greedy_agreement']:.0%}")
        # tensor-parallel mesh: the sharded engine must reproduce the
        # unsharded greedy outputs exactly (compare.py gates agreement).
        # One run, not best-of: the subprocess pays jit compile twice
        # (reference + mesh) and the cell's signal is agreement, not
        # steady-state throughput.
        row = bench_mesh(arch, args.mesh, args.requests, args.lanes,
                         args.max_len, args.max_new, args.page_size,
                         args.prefill_chunk)
        results.append(row)
        print(f"[bench_serving] {arch:14s} paged  mesh/{args.mesh:8s} "
              f"{row['tokens_per_s']:8.1f} tok/s  "
              f"{row['n_devices']} devices  "
              f"agree {row['greedy_agreement']:.0%}")
        # mixed long/short workload: monolithic vs chunked prefill.  The
        # mixed max_len must fit long_len + max_new headroom.
        mixed_len = max(args.max_len, args.long_len + args.max_new + 2)
        for chunk in (None, args.prefill_chunk):
            row = best_of(lambda: bench_mixed(
                arch, chunk, args.requests, args.lanes, mixed_len,
                args.max_new, args.page_size, long_len=args.long_len))
            results.append(row)
            mode = f"chunk={chunk}" if chunk else "monolithic"
            print(f"[bench_serving] {arch:14s} paged  mixed/{mode:11s} "
                  f"short-ttft p50 {fmt(row['ttft_short_p50_s'], '.3f')}s  "
                  f"long ttft {fmt(row['ttft_long_s'], '.3f')}s  "
                  f"{row['tokens_per_s']:6.1f} tok/s")
        # long-context decode: split-KV committed (autotuned ladder) vs
        # forced num_splits=1 (the sequential spelling), 1 lane so p50
        # ITL is a single decode step's critical path.  compare.py gates
        # agreement at 100% and committed ITL <= forced-1 per length.
        for ll in args.long_lens:
            for row in bench_long_context(arch, ll, args.requests,
                                          args.max_new, args.page_size,
                                          args.repeats):
                results.append(row)
                mode = f"ns={row['num_splits']}"
                print(f"[bench_serving] {arch:14s} paged  "
                      f"long/{ll:<4d}{mode:8s} "
                      f"itl p50 {fmt(row['itl_p50_s'], '.4f')}s  "
                      f"{row['tokens_per_s']:6.1f} tok/s  "
                      f"agree {row['greedy_agreement']:.0%}")
        # shared system prompt: prefix cache off vs on.  The cached cell
        # must show a TTFT drop (admissions skip the prefix's chunks)
        # and a nonzero hit rate (gated by compare.py).  One lane, so
        # request 0 publishes the prefix before request 1 admits — the
        # hit rate is structural ((n-1)/n), not a concurrency accident.
        # The off/on repeats INTERLEAVE (off, on, off, on, ...) so both
        # variants sample the same machine-noise windows, and each cell
        # keeps its min-TTFT repeat — the pairing + floor is what lets a
        # 10-25% structural saving survive CPU-proxy scheduler jitter.
        sp_len = max(args.max_len, args.prefix_len + args.max_new + 10)
        sp_rows: dict = {False: [], True: []}
        for _ in range(max(2, args.repeats + 1)):
            for cached in (False, True):
                sp_rows[cached].append(bench_shared_prefix(
                    arch, cached, args.requests, 1, sp_len,
                    args.max_new, args.page_size,
                    prefix_len=args.prefix_len,
                    prefill_chunk=args.prefill_chunk))
        for cached in (False, True):
            # a repeat that finished nothing has no TTFT: sort it last,
            # never let it masquerade as the fastest run
            row = min(sp_rows[cached],
                      key=lambda r: (r["ttft_p50_s"]
                                     if r["ttft_p50_s"] is not None
                                     else float("inf")))
            results.append(row)
            mode = "cache=on " if cached else "cache=off"
            print(f"[bench_serving] {arch:14s} paged  prefix/{mode:11s} "
                  f"ttft p50 {fmt(row['ttft_p50_s'], '.3f')}s  "
                  f"hit {row['prefix_hit_rate']:.0%}  "
                  f"{row['pages_saved']} pages saved  "
                  f"{row['tokens_per_s']:6.1f} tok/s")
        # gateway load: Poisson arrivals through the HTTP/SSE front-end.
        # best-of keeps the max-goodput repeat (first pays jit compile)
        g_rows = [bench_gateway(arch, args.gateway_requests,
                                args.gateway_rate, args.gateway_turns,
                                args.max_new, args.gateway_queue_limit)
                  for _ in range(max(1, args.repeats))]
        row = max(g_rows, key=lambda r: r["goodput_tok_s"])
        results.append(row)
        print(f"[bench_serving] {arch:14s} paged  gateway      "
              f"{row['goodput_tok_s']:8.1f} good tok/s  "
              f"SLO {row['slo_attainment']:.0%}  "
              f"queue p99 {fmt(row['queue_wait_p99_s'], '.3f')}s  "
              f"{row['rejected_429']} bounced  "
              f"{row['overlapped_ticks']}/{row['ticks']} overlapped")
        # speculative decode: tokens/s + accept rate per draft length k
        for k in args.spec_ks:
            row = best_of(lambda: bench_spec(
                arch, k, args.requests, args.lanes, args.max_len,
                args.max_new, args.page_size))
            results.append(row)
            print(f"[bench_serving] {arch:14s} paged  spec/k={k:<2d}     "
                  f"{row['tokens_per_s']:8.1f} tok/s  "
                  f"accept {row['accepted_tokens']}/{row['drafted_tokens']} "
                  f"({row['accept_rate']:.0%})  "
                  f"{row['tokens_per_step']:.2f} tok/step")

    # the run shape is stamped into the payload so compare.py can refuse
    # to diff two benchmarks that measured different workloads
    config = {"archs": list(args.archs), "requests": args.requests,
              "lanes": args.lanes, "max_len": args.max_len,
              "max_new": args.max_new, "page_size": args.page_size,
              "timeslice": args.timeslice,
              "kv_dtypes": ["fp", "int8"],
              "mesh": args.mesh,
              "prefill_chunk": args.prefill_chunk,
              "long_len": args.long_len, "spec_ks": list(args.spec_ks),
              "long_lens": list(args.long_lens),
              "split_modes": [1, "auto"],
              "prefix_len": args.prefix_len,
              "gateway_requests": args.gateway_requests,
              "gateway_rate": args.gateway_rate,
              "gateway_turns": args.gateway_turns,
              "gateway_queue_limit": args.gateway_queue_limit,
              "repeats": args.repeats}
    payload = {"benchmark": "serving", "config": config, "results": results}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"[bench_serving] wrote {args.out} ({len(results)} cells)")


if __name__ == "__main__":
    main()
