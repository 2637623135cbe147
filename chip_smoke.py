"""Smoke test of the serving hot path on a TPU, at yi-6b published widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # tensor-parallel path on four chips

One chip, three phases in one process:

1. kernels — the Pallas paged decode and chunked-prefill kernels (fp and
   int8 pools, ``num_splits`` 1 and 2) against the ``ref.py`` oracles, at
   yi-6b attention widths;
2. serve — ``serve.build_serving`` at published widths (as deep as the
   chip's memory holds, whole layers), paged KV with page size 16 and
   chunked prefill, greedy requests driven tick by tick until all finish,
   every decode tick's logits checked finite, and the compiled decode
   step checked to hold the Pallas kernel (``tpu_custom_call``);
3. autotune — one ``--autotune`` serve in a fresh tuning workdir, then a
   warm restart of it that must take zero measurements.

``--chips 4`` runs only the tensor-parallel path: the same workload
unsharded on one chip, then on a ``1x4`` mesh (one yi-6b KV head per
chip, weights replicated), comparing the first prefill and first decode
logits and the greedy tokens.

The script exits non-zero, printing no result, where JAX finds no TPU.
Its last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: serving workload of the one-chip serve phase: prompts uniform in
#: [4, 1024) tokens, 32 greedy new tokens each
SERVE = dict(arch="yi-6b", reduced=False, cache="paged", page_size=16,
             prefill_chunk=256, n_requests=12, n_lanes=8, prompt_len=1024,
             max_new=32, max_len=1072)
#: autotune phase: prompts below 120 tokens and 32-token chunks, so every
#: region the run visits (decode and prefill, length bucket 128) sees all
#: of its candidates and commits before the run ends
AUTOTUNE = dict(arch="yi-6b", reduced=False, cache="paged", page_size=16,
                prefill_chunk=32, n_requests=3, n_lanes=2, prompt_len=120,
                max_new=8, max_len=144, seed=3, autotune=True)
#: four-chip phase: the serve workload, shorter
MESH = dict(SERVE, n_requests=8, n_lanes=4, prompt_len=512, max_new=16,
            max_len=544)

#: Bytes kept free beside weights and two copies of the KV pool (the step
#: returns a new pool next to the one it read).  The compile rehearsal
#: for a described v5e chip gave 0.15 GiB of decode-step temporaries at
#: 8 lanes and under 0.01 GiB for a 256-token prefill chunk; 1 GiB covers
#: those and the runtime's own allocations.
HEADROOM_BYTES = 1 << 30

#: Kernel vs oracle, element-wise: |kernel - oracle| <= KERNEL_ATOL +
#: KERNEL_RTOL * |oracle|.  Both outputs are bf16, so each is rounded to
#: within half an ulp (2^-8 relative) and they may differ by one ulp,
#: 2^-7 relative; the matrix unit may also take the fp32 operands of the
#: kernel's dots (softmax weights, dequantized int8 keys and values) in
#: one bf16 pass, ~2^-9 relative per product.  RTOL is two ulps (2^-6);
#: ATOL covers outputs near zero, where fp32 summation order over up to
#: 1024 keys leaves ~1e-3.  A wrong page, mask or split merge is off by
#: the size of the output itself.
KERNEL_ATOL = 1e-2
KERNEL_RTOL = 2.0 ** -6

#: Mesh vs unsharded logits, max abs error as a share of max |logit|.
#: Both programs compute in bf16 (8 mantissa bits, 2^-9 per rounding);
#: the partitioned program may fuse or reduce in another valid order, so
#: each of 32 layers may flip roundings: sqrt(32) * 2^-9 is about 1.1%
#: of the logit scale.  3% leaves margin; a wrong head or page shard is
#: off by the whole scale.
MESH_RTOL = 3e-2


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def agreement(ref: dict, got: dict) -> float:
    """Share of greedy token positions, per request id, on which two
    runs emitted the same token."""
    match = total = 0
    for rid, want in ref.items():
        have = got.get(rid, [])
        total += max(len(want), len(have))
        match += sum(a == b for a, b in zip(want, have))
    return match / total if total else 1.0


def tree_bytes(tree) -> int:
    import jax
    return int(sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree)))


# -- phase 1: kernels against the oracles ------------------------------------

def kernel_phase(arch: str = "yi-6b", page_size: int = 16,
                 seed: int = 0) -> None:
    """Compiled Pallas paged kernels vs the gather oracles at the
    published attention widths of ``arch``: decode (4 lanes, ragged
    lengths up to 1024) and one 128-token prefill chunk per lane (2 lanes,
    one at position 0, one ragged mid-sequence), fp and int8 pools,
    ``num_splits`` 1 and 2.  The oracle runs at ``highest`` matmul
    precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.distributed.compression import quantize_int8_rows
    from repro.kernels import ops

    require(not ops.on_cpu(), "kernel dispatch took the CPU branch")
    cfg = get_arch(arch)
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    nblk = 1024 // page_size
    rng = np.random.default_rng(seed)
    n_pages = 4 * nblk + 1
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                        .reshape(4, nblk).astype(np.int32))
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool_shape = (n_pages, hkv, page_size, d)
    k = jax.random.normal(keys[0], pool_shape, jnp.float32)
    v = jax.random.normal(keys[1], pool_shape, jnp.float32)
    kq, ks = quantize_int8_rows(k)
    vq, vs = quantize_int8_rows(v)
    pools = {"fp": ops.PagedPools(k.astype(jnp.bfloat16),
                                  v.astype(jnp.bfloat16)),
             "int8": ops.PagedPools(kq, vq, ks, vs)}
    q_dec = jax.random.normal(keys[2], (4, h, 1, d), jnp.bfloat16)
    kv_dec = jnp.asarray([1, 100, 517, 1024], jnp.int32)
    q_pre = jax.random.normal(keys[3], (2, h, 128, d), jnp.bfloat16)
    start = jnp.asarray([0, 384], jnp.int32)
    kv_pre = start + jnp.asarray([128, 77], jnp.int32)
    cases = {
        "decode": (ops.paged_decode, (q_dec, table, kv_dec)),
        "prefill": (ops.paged_prefill, (q_pre, table[:2], start, kv_pre)),
    }
    for op_name, (op, (q, *rest)) in cases.items():
        for kv_dtype, pl in pools.items():
            with jax.default_matmul_precision("highest"):
                want = jax.jit(functools.partial(op, use_kernel=False))(
                    q, pl, *rest)
            for ns in (1, 2):
                # decode at its default multi-page tile, prefill at a page
                block_k = page_size if op_name == "prefill" else None
                fn = jax.jit(functools.partial(op, use_kernel=True,
                                               num_splits=ns,
                                               block_k=block_k))
                compiled = fn.lower(q, pl, *rest).compile()
                require("tpu_custom_call" in compiled.as_text(),
                        f"{op_name}/{kv_dtype}: no Pallas kernel in HLO")
                got = compiled(q, pl, *rest).astype(jnp.float32)
                ref = want.astype(jnp.float32)
                diff = jnp.abs(got - ref)
                ratio = float(jnp.max(
                    diff / (KERNEL_ATOL + KERNEL_RTOL * jnp.abs(ref))))
                log(f"kernel {op_name:7s} {kv_dtype:4s} num_splits={ns} "
                    f"max|kernel - oracle| = {float(jnp.max(diff))!r}, "
                    f"max share of tolerance {ratio!r}")
                require(ratio <= 1.0,
                        f"{op_name}/{kv_dtype}/ns={ns}: outside tolerance "
                        f"({ratio} of atol {KERNEL_ATOL} + rtol "
                        f"{KERNEL_RTOL} * |oracle|)")


# -- phase 2: serve at published widths --------------------------------------

def fit_layers(scfg, bytes_limit: int) -> tuple[int, int, int]:
    """Deepest whole-layer cut of ``scfg`` whose weights, two copies of
    the KV pool and :data:`HEADROOM_BYTES` fit in ``bytes_limit``.
    Returns (layers, weight bytes, pool bytes), sized from shapes only."""
    import jax

    from repro.launch.serve import serving_arch_config
    from repro.models import build_model

    pages = scfg.n_pages or (scfg.n_lanes * math.ceil(
        scfg.max_len / scfg.page_size) + 1)
    published = serving_arch_config(
        dataclasses.replace(scfg, layers=None)).n_layers
    for n in range(published, 0, -1):
        model = build_model(serving_arch_config(
            dataclasses.replace(scfg, layers=n)))
        w = tree_bytes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        p = tree_bytes(jax.eval_shape(
            lambda: model.init_paged_caches(pages, scfg.page_size)))
        if w + 2 * p + HEADROOM_BYTES <= bytes_limit:
            log(f"depth {n} of {published} layers: weight bytes {w}, pool "
                f"bytes {p} ({pages} pages), headroom {HEADROOM_BYTES}, "
                f"device bytes_limit {bytes_limit}")
            return n, w, p
    raise RuntimeError(f"no depth of {scfg.arch} fits {bytes_limit} bytes")


def drive(engine, prompts, max_new: int, record: dict | None = None):
    """Submit greedy requests and tick the engine until all finish,
    checking every decode tick's logits are finite.  With ``record``,
    the logits of the first prefill chunk and of the decoding lanes of
    the first decode tick are kept there."""
    import numpy as np

    from repro.serving import Request

    if record is not None:
        step = engine._prefill_step

        def first_prefill(*args):
            out = step(*args)
            if "prefill" not in record:
                record["prefill"] = np.asarray(out[0])
            return out
        engine._prefill_step = first_prefill
    for rid, prompt in enumerate(prompts):
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=max_new))
    budget = len(prompts) * (max_new + 8 + max(map(len, prompts)))
    ticks = 0
    while engine.scheduler.has_queued or engine.active:
        require(ticks < budget, f"engine did not finish in {budget} ticks")
        engine.schedule()
        work = engine.dispatch()
        if work is not None:
            rows = np.asarray(work.logits)[work.decoding]
            require(bool(np.isfinite(rows).all()),
                    f"non-finite decode logits at tick {ticks}")
            if record is not None:
                record.setdefault("decode", rows)
        engine.emit(work)
        ticks += 1
    require(len(engine.finished) == len(prompts),
            f"{len(engine.finished)}/{len(prompts)} requests finished")
    return {int(r.rid): [int(t) for t in r.out_tokens]
            for r in engine.finished}


def serve_phase(scfg) -> None:
    import jax
    import jax.numpy as jnp

    from repro import at
    from repro.launch.serve import build_serving

    at.clear_published()
    t0 = time.perf_counter()
    engine, _, prompts = build_serving(scfg)
    jax.block_until_ready((engine.params, engine.kv.caches))
    t_build = time.perf_counter() - t0
    log(f"serve: layers {engine.model.cfg.n_layers}, weight bytes "
        f"{tree_bytes(engine.params)}, pool bytes {engine.kv.pool_bytes()} "
        f"({engine.kv.n_pages} pages of {scfg.page_size}), build "
        f"{t_build!r} s")
    t0 = time.perf_counter()
    drive(engine, prompts, scfg.max_new)
    wall = time.perf_counter() - t0
    summary = engine.metrics.summary()
    log(f"serve: {len(engine.finished)}/{scfg.n_requests} requests, "
        f"{summary['generated_tokens']} tokens, prompt tokens "
        f"{sum(map(len, prompts))}, wall {wall!r} s including compiles, "
        f"{engine.steps} decode ticks, {engine.prefill_chunks} prefill "
        "chunks")
    n = engine.n_lanes
    hlo = engine._decode.lower(
        engine.params, engine.kv.caches, *engine.kv.decode_extra(),
        jnp.zeros((n, 1), jnp.int32), jnp.zeros((n,), jnp.int32)
    ).compile().as_text()
    require("tpu_custom_call" in hlo,
            "compiled decode step holds no Pallas kernel")
    log("serve: compiled decode step holds tpu_custom_call")


def autotune_phase(scfg) -> None:
    from repro import at
    from repro.launch.serve import serve_config

    with tempfile.TemporaryDirectory(prefix="at-smoke-") as wd:
        reports = []
        for run in ("cold", "warm"):
            at.clear_published()
            t0 = time.perf_counter()
            rep = serve_config(dataclasses.replace(scfg, workdir=wd))
            a = rep["autotune"]
            log(f"autotune {run}: {rep['finished']}/{rep['requests']} "
                f"requests, measurements {a['measurements']}, measured "
                f"{a['measured_regions']}, warm {a['warm_regions']}, "
                f"{time.perf_counter() - t0!r} s")
            require(rep["finished"] == rep["requests"],
                    f"autotune {run}: not every request finished")
            reports.append(rep)
            gc.collect()
    cold, warm = reports
    require(cold["autotune"]["measurements"] > 0,
            "the cold autotune run measured nothing")
    require(warm["autotune"]["measurements"] == 0,
            "the warm restart measured again")
    # the cold run measures a different kernel variant on each tick, and
    # in bf16 the variants round differently: near-tied greedy tokens may
    # flip, so agreement is reported, not required
    log(f"autotune: warm vs cold greedy token agreement "
        f"{agreement(cold['outputs'], warm['outputs'])!r}")


# -- four chips: tensor-parallel serving against unsharded --------------------

def mesh_phase(scfg, mesh: str) -> None:
    import numpy as np

    from repro import at
    from repro.launch.serve import build_serving

    runs = {}
    for spec in (None, mesh):
        at.clear_published()
        t0 = time.perf_counter()
        engine, _, prompts = build_serving(
            dataclasses.replace(scfg, mesh=spec))
        record: dict = {}
        outs = drive(engine, prompts, scfg.max_new, record)
        log(f"mesh {spec or 'none'}: {len(outs)} requests, layers "
            f"{engine.model.cfg.n_layers}, pool bytes "
            f"{engine.kv.pool_bytes()}, {time.perf_counter() - t0!r} s")
        runs[spec] = (outs, record)
        # drop this run's weights before the next one places its own
        del engine
        gc.collect()
    (ref_outs, ref), (outs, got) = runs[None], runs[mesh]
    for key in ("prefill", "decode"):
        scale = float(np.max(np.abs(ref[key])))
        err = float(np.max(np.abs(got[key] - ref[key])))
        log(f"mesh {mesh} {key} logits: max|mesh - unsharded| = {err!r}, "
            f"max|logit| = {scale!r} (rtol {MESH_RTOL})")
        require(err <= MESH_RTOL * scale,
                f"mesh {key} logits off by {err} (scale {scale})")
    log(f"mesh {mesh} greedy token agreement "
        f"{agreement(ref_outs, outs)!r}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    require(len(devices) >= args.chips,
            f"--chips {args.chips} but JAX sees {len(devices)} devices")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import enable_compile_cache
    from repro.launch.serve import ServeConfig
    log(f"compile cache: {enable_compile_cache(ROOT)}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    limit = int(devices[0].memory_stats()["bytes_limit"])

    t0 = time.perf_counter()
    if args.chips == 4:
        scfg = ServeConfig(**MESH)
        layers, _, _ = fit_layers(scfg, limit)
        mesh_phase(dataclasses.replace(scfg, layers=layers), "1x4")
    else:
        kernel_phase()
        log(f"kernels done at {time.perf_counter() - t0!r} s")
        scfg = ServeConfig(**SERVE)
        layers, _, _ = fit_layers(scfg, limit)
        serve_phase(dataclasses.replace(scfg, layers=layers))
        gc.collect()
        log(f"serve done at {time.perf_counter() - t0!r} s")
        autotune_phase(ServeConfig(**AUTOTUNE, layers=layers))
    stats = devices[0].memory_stats()
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}, total "
        f"{time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
