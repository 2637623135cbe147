"""Serving driver: scheduler-driven engine + run-time auto-tuning.

Submits a stream of synthetic requests to the reduced (CPU smoke-test)
config of ``--arch``, or with ``--full`` to its published widths (bf16
weights, ``--layers N`` to cut the depth), reports throughput/latency
percentiles from the serving metrics layer,
and demonstrates the run-time AT path (decode bucket variants tuned on
the first calls through a ``repro.at`` session, then committed; committed
winners persist in the session's record store, so a restarted server
starts warm).

``--cache paged`` runs the paged-KV backend: memory scales with live
tokens, and with ``--timeslice`` the engine serves more concurrent
requests than it has decode lanes (preempted sequences' pages swap to
host and back).  ``--prefill-chunk N`` adds chunked prefill: prompts
stream into the paged cache N tokens per tick interleaved with decode,
and the prefill tile space (block_q x block_k per prompt bucket) becomes
a second run-time tuning region next to the decode buckets.

``--prefix-cache`` (paged + chunked prefill) turns on content-addressed
prefix caching: committed full pages publish into a hash index, new
admissions seed their page tables with matching shared pages (refcounted,
copy-on-write) and prefill only the uncached suffix.  With ``--autotune``
the cache's reuse policy (min-match granularity x eviction strategy)
becomes the ``PrefixPolicy`` tuning region.  ``--shared-prefix N`` makes
the synthetic workload share an N-token system prompt so the index
actually gets hits.

``--kv-dtype int8`` (paged) stores KV pages as int8 with per-row fp32
scales — ~3x more tokens per byte at this scale — dequantizing inside
the attention kernels; ``--kv-dtype auto`` (with ``--autotune``) lets
the ``KVPrecision_{b}`` regions calibrate fp vs int8 (x block_k) per
length bucket under a greedy-agreement quality guard, then builds the
pool from the majority winner.

``--draft`` turns on speculative decoding (paged only): a reduced-depth
draft sliced from the target's own layers proposes ``--spec-k`` tokens
per tick and the target verifies them in one chunked call; with
``--autotune`` the (k x verify tile) space becomes a third tuning region
family (``SpecBucket_{b}``).  ``--temperature/--top-k/--top-p`` switch
the synthetic requests from greedy to sampled decoding (per-request
seeds, reproducible).

Usage::

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --requests 8 \
        --cache paged --pages 64 --page-size 16 --prefill-chunk 8 \
        --draft --spec-k 4 --autotune --workdir /tmp/at
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .. import at
from ..configs import get_arch
from ..distributed.sharding import make_serving_mesh
from ..models import build_model
from ..serving import REDUCED_BUCKETS, Request, SamplingParams, ServingEngine
from . import enable_compile_cache


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """The serve run shape as one typed object.

    Replaces the 27-keyword ``serve(...)`` signature and the flat argparse
    namespace as the source of truth: :meth:`from_args` builds it from the
    CLI, :meth:`to_dict` stamps it verbatim into the serve report and the
    bench payload config, so ``compare.py`` cell keys and the report both
    derive from the same record.  ``mesh`` is the tensor-parallel device
    mesh spec (``"RxC"``, data x model — e.g. ``"1x4"``; None = unsharded).
    ``reduced`` serves the CPU smoke-test widths of ``arch`` (the default);
    False serves its published widths.  ``layers`` cuts the depth in whole
    layers (None keeps the config's own depth).
    """

    arch: str = "yi-6b"
    reduced: bool = True
    layers: int | None = None
    n_requests: int = 8
    n_lanes: int = 4
    max_len: int = 96
    prompt_len: int = 16
    max_new: int = 12
    seed: int = 0
    autotune: bool = False
    workdir: str = "."
    cache: str = "dense"
    n_pages: int | None = None
    page_size: int = 16
    timeslice: int | None = None
    prefill_chunk: int | None = None
    draft: bool = False
    spec_k: int = 4
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    prefix_cache: bool = False
    shared_prefix: int = 0
    gateway: bool = False
    port: int = 0
    queue_limit: int = 64
    policy_window: int = 2
    slo_ttft_s: float = 30.0
    slo_itl_s: float = 5.0
    kv_dtype: str = "fp"
    num_splits: int | None = None
    mesh: str | None = None
    tuning_backend: str = "jsonl"
    golden_db: str | None = None

    #: argparse dest -> field, for the names that differ
    _ARG_FIELDS = {"requests": "n_requests", "lanes": "n_lanes",
                   "pages": "n_pages", "slo_ttft": "slo_ttft_s",
                   "slo_itl": "slo_itl_s"}

    @classmethod
    def from_args(cls, args: argparse.Namespace) -> "ServeConfig":
        """Build from an argparse namespace (ignores unknown attributes,
        keeps dataclass defaults for flags the parser doesn't expose)."""
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {}
        for dest, value in vars(args).items():
            name = cls._ARG_FIELDS.get(dest, dest)
            if name in fields:
                kw[name] = value
        return cls(**kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def serving_arch_config(scfg: ServeConfig):
    """The architecture a serve run builds: the reduced or published
    config of ``scfg.arch``, depth-cut to ``scfg.layers``.  Published
    configs serve with weights held in the compute dtype: fp32 master
    weights are a training concern and would double the device bytes
    (yi-6b: 24.2 GB in fp32 against 16 GB of HBM on one v5e chip)."""
    cfg = get_arch(scfg.arch)
    if scfg.reduced:
        cfg = cfg.reduced()
    else:
        cfg = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
    if scfg.layers is not None:
        if not 1 <= scfg.layers <= cfg.n_layers:
            raise ValueError(f"layers={scfg.layers} outside 1..{cfg.n_layers}"
                             f" for {cfg.name!r}")
        cfg = dataclasses.replace(cfg, n_layers=scfg.layers)
    return cfg


def init_serving_params(model, seed: int, mesh=None):
    """Random weights made on the device under ``jit``: the RNG
    temporaries stay inside one program instead of landing on the device
    as eager full-size arrays next to the weights.  With a mesh the
    weights come out replicated on every device of it (only the KV pools
    shard), so no step has to copy them there."""
    out = None if mesh is None else NamedSharding(mesh, PartitionSpec())
    return jax.jit(model.init, out_shardings=out)(jax.random.PRNGKey(seed))


def _make_kv_precision_bench(model, page_size: int, lanes: int = 2,
                             decode_steps: int = 3):
    """Calibration microbench for the KVPrecision regions.

    ``make_variant(bucket, kv_dtype, block_k)`` builds one candidate: a
    throwaway paged pool of the requested precision, one prefill over a
    synthetic prompt plus a few greedy decode steps, timed end to end.
    The variant reports ``time_per_token`` and ``agreement`` — the
    fraction of its greedy tokens matching the fp reference for the same
    bucket (fp candidates *are* the reference: agreement 1.0 by
    construction, so the quality-guarded pool is never empty).

    The prompt is capped at 4 pages so every bucket's calibration shares
    one trace per cache structure (fp / int8); buckets differ in prompt
    *content*, standing in for length-dependent behaviour at CPU scale
    without a retrace per bucket.
    """
    jnp = jax.numpy
    prefill_jit = jax.jit(model.paged_prefill_step)
    decode_jit = jax.jit(model.paged_decode_step)
    ref_tokens: dict[int, list] = {}

    def run(bucket, kv_dtype, block_k, params):
        plen = min(bucket, 4 * page_size)
        blocks = -(-(plen + decode_steps) // page_size)
        caches = model.init_paged_caches(lanes * blocks + 1, page_size,
                                         quantized=kv_dtype == "int8")
        table = jnp.asarray(
            (np.arange(lanes)[:, None] * blocks
             + np.arange(blocks)[None, :] + 1).astype(np.int32))
        rng = np.random.default_rng(bucket)
        prompt = jnp.asarray(np.tile(
            rng.integers(0, model.cfg.vocab_size, size=plen),
            (lanes, 1)).astype(np.int32))
        start = jnp.zeros((lanes,), jnp.int32)
        kv_len = jnp.full((lanes,), plen, jnp.int32)
        # block_k is a prefill tile (a divisor of the page); decode keeps
        # its default multi-page tile
        at.publish("flash_paged_prefill", block_k=block_k)
        t0 = time.perf_counter()
        logits, caches = prefill_jit(params, caches, table, prompt,
                                     start, kv_len, kv_len - 1)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks = [int(tok[0])]
        pos = kv_len
        for _ in range(decode_steps):
            logits, caches = decode_jit(params, caches, table,
                                        tok[:, None], pos)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(int(tok[0]))
            pos = pos + 1
        dt = time.perf_counter() - t0
        return toks, dt / (1 + decode_steps)

    def make_variant(bucket, kv_dtype, block_k):
        def variant(params, bucket=bucket, kv_dtype=kv_dtype,
                    block_k=block_k):
            toks, tpt = run(bucket, kv_dtype, block_k, params)
            if kv_dtype == "fp":
                ref_tokens.setdefault(bucket, toks)
                agreement = 1.0
            else:
                if bucket not in ref_tokens:   # int8 measured first
                    ref_tokens[bucket], _ = run(bucket, "fp", block_k,
                                                params)
                ref = ref_tokens[bucket]
                agreement = sum(a == r for a, r in zip(toks, ref)) \
                    / max(len(ref), 1)
            return {"kv_dtype": kv_dtype, "block_k": block_k,
                    "time_per_token": tpt, "agreement": agreement}
        return variant

    return make_variant


def _make_autotuner(model, workdir: str, cache: str, page_size: int,
                    gateway: bool = False,
                    prefill_chunk: int | None = None,
                    spec_k: int | None = None,
                    prefix_cache: bool = False,
                    kv_precision: bool = False,
                    num_splits: int | None = None,
                    mesh=None, mesh_shape=None,
                    tuning_backend: str = "jsonl",
                    golden_db: str | None = None,
                    table_pages: int | None = None):
    """Per-bucket dynamic select over decode variants (repro.at session).

    Each candidate gets its own jit cache and publishes its block PPs
    before its first trace, so the kernel path reads its own block_k /
    page-gather granularity at trace time (on CPU the reference path
    ignores them and the select exercises the paper's run-time measurement
    flow rather than a real kernel trade-off).

    With chunked prefill the session also declares the prefill region
    family: one select per (prompt bucket × chunk size) over the
    ``flash_paged_prefill`` (block_q × block_k) tile space.

    ``mesh`` (a device Mesh) is closed into every variant's jit so
    tuner-routed calls run the same sharded computation as the engine's
    committed steady state; ``mesh_shape`` keys the region names so each
    mesh shape tunes and persists its own winners (a 1-device mesh keeps
    the legacy names and warm-loads existing DBs unchanged).  The
    KV-precision calibration bench stays unsharded: it measures on
    throwaway pools as a cost proxy, and its greedy-agreement guard
    compares like with like either way.

    ``table_pages`` (the page table's width) caps the decode tiles.

    ``tuning_backend`` selects the record store behind the session
    (``at.record_backends``: jsonl default, sqlite for concurrent
    workers); ``golden_db`` overlays a read-only fleet winner DB so a
    fresh deployment warm-loads committed optima it never measured.
    """
    from ..tuning import DecodeAutoTuner, divisor_block_ks
    from ..tuning.dynamic import DEFAULT_BLOCK_KS, page_multiple_block_ks
    session = at.AutoTuner(workdir, record_backend=tuning_backend,
                           golden_db=golden_db)

    def _jit_step(fn, **jit_kw):
        if mesh is not None:
            fn = functools.partial(fn, mesh=mesh)
        return jax.jit(fn, **jit_kw)

    if cache == "paged":
        # the decode kernel's run-time PPs are its tile, a whole number
        # of pages capped at the table's width (page size itself is
        # structural, fixed at pool build), and the split-KV parallelism
        # degree, so the per-bucket space is block_k in page multiples of
        # DEFAULT_BLOCK_KS x num_splits; a forced --num-splits pins the
        # candidate ladder to that single degree (1 always leads, keeping
        # legacy winner indices valid)
        splits = (1, 2, 4) if num_splits is None else (int(num_splits),)

        def make_decode(block_k, n_split):
            decode_bk = _jit_step(model.paged_decode_step)

            def variant(p, caches, table, token, pos, block_k=block_k,
                        n_split=n_split):
                at.publish("flash_paged_decode", block_k=block_k,
                           num_splits=n_split)
                return decode_bk(p, caches, table, token, pos)
            return variant

        tuner = DecodeAutoTuner(session, make_decode,
                                buckets=REDUCED_BUCKETS,
                                block_ks=page_multiple_block_ks(
                                    page_size, DEFAULT_BLOCK_KS,
                                    table_pages),
                                num_splits=splits,
                                mesh_shape=mesh_shape)
        if prefill_chunk is not None:
            def make_prefill(block_q, block_k):
                prefill_jit = _jit_step(model.paged_prefill_step)

                def variant(p, caches, table, tokens, start, kv_len,
                            logit_idx, block_q=block_q, block_k=block_k):
                    at.publish("flash_paged_prefill", block_q=block_q,
                               block_k=block_k)
                    return prefill_jit(p, caches, table, tokens, start,
                                       kv_len, logit_idx)
                return variant

            tuner.add_prefill(
                make_prefill, chunk_sizes=(prefill_chunk,),
                buckets=REDUCED_BUCKETS,
                block_qs=(max(1, prefill_chunk // 2), prefill_chunk),
                block_ks=divisor_block_ks(
                    page_size, (max(1, page_size // 2), page_size)))
        if spec_k is not None:
            # the accept-window k is itself tuned: a variant verifies only
            # its first k drafts (narrower chunk, fewer acceptable tokens)
            # — greedy output is bit-identical for every k, so the region
            # measures the acceptance-vs-verify-cost trade-off freely.
            # Each variant reports time_per_token (its call time over the
            # tokens its window would emit under the greedy accept rule):
            # raw per-call latency would always elect the narrowest k, so
            # the region commits on throughput, not verify cost alone.
            def make_verify(k, block_q, block_k):
                verify_jit = _jit_step(model.speculative_step)

                def variant(p, caches, table, tokens, start, kv_len,
                            k=k, block_q=block_q, block_k=block_k,
                            measure=True):
                    at.publish("flash_paged_verify", block_q=block_q,
                               block_k=block_k)
                    args = (p, caches, table, tokens[:, :k + 1], start,
                            jax.numpy.minimum(kv_len, start + k + 1))
                    if not measure:
                        # committed steady state: no sync, no host-side
                        # acceptance proxy — just the verify itself
                        return verify_jit(*args)
                    t0 = time.perf_counter()
                    logits, caches_out = verify_jit(*args)
                    logits.block_until_ready()
                    dt = time.perf_counter() - t0
                    # greedy-acceptance proxy over the live lanes (kv_len
                    # > start; masked/idle rows are excluded): leading
                    # draft/argmax matches + 1 bonus each = tokens this
                    # window emits per call
                    am = np.asarray(jax.numpy.argmax(logits[:, :-1], -1))
                    dr = np.asarray(tokens[:, 1:k + 1])
                    st_np = np.asarray(start)
                    kl = np.asarray(kv_len)
                    emitted = 0
                    for b in range(dr.shape[0]):
                        if kl[b] <= st_np[b]:
                            continue
                        w = min(k, int(kl[b] - st_np[b]) - 1)
                        a = 0
                        while a < w and dr[b, a] == am[b, a]:
                            a += 1
                        emitted += a + 1
                    return {"logits": logits, "caches": caches_out,
                            "time_per_token": dt / max(emitted, 1)}
                return variant

            tuner.add_spec(
                make_verify,
                ks=tuple(sorted({1, max(1, spec_k // 2), spec_k})),
                buckets=REDUCED_BUCKETS,
                block_qs=(spec_k + 1,),
                block_ks=divisor_block_ks(
                    page_size, (max(1, page_size // 2), page_size)))
        if prefix_cache:
            # the cache's REUSE POLICY is the tuned object (minimum match
            # granularity x eviction strategy): each alternative applies
            # its knobs to the live pool and performs one real admission
            # match.  Outputs are bit-identical under every policy, and
            # the region commits on the smallest uncached PROMPT FRACTION
            # (raw call latency would elect whichever policy matches
            # nothing; unnormalized token counts would let prompt length
            # pick the winner instead of the policy).
            def make_policy(min_match, eviction):
                def variant(kv, lane_id, prompt, min_match=min_match,
                            eviction=eviction):
                    kv.set_prefix_policy(min_match=min_match,
                                         eviction=eviction)
                    cached = kv.seed_prefix(lane_id, prompt)
                    miss = (len(prompt) - cached) / max(len(prompt), 1)
                    return {"cached": cached, "miss_fraction": miss}
                return variant

            tuner.add_prefix_policy(make_policy)
        if kv_precision:
            # pool precision is lossy, so the region couples latency to a
            # quality guard: an int8 candidate may only win while its
            # greedy tokens track the fp reference (fp reports agreement
            # 1.0, keeping the guarded pool non-empty)
            tuner.add_kv_precision(
                _make_kv_precision_bench(model, page_size),
                block_ks=divisor_block_ks(
                    page_size, (max(1, page_size // 2), page_size)),
                buckets=REDUCED_BUCKETS)
        if gateway:
            # the gateway's concurrency product (pipeline depth x
            # admission batch) — measured over traffic windows and
            # committed on time-per-good-token (inverse goodput)
            tuner.add_gateway(max_inflights=(1, 2), admit_batches=(2, 8))
        return tuner

    def make_decode(block_k):
        decode_bk = jax.jit(model.decode_step)

        def variant(p, caches, token, pos, block_k=block_k):
            at.publish("flash_decode", block_k=block_k)
            return decode_bk(p, caches, token, pos)
        return variant

    tuner = DecodeAutoTuner(session, make_decode,
                            buckets=REDUCED_BUCKETS,
                            block_ks=(256, 512))
    if gateway:
        tuner.add_gateway(max_inflights=(1, 2), admit_batches=(2, 8))
    return tuner


def _serve_gateway(engine, tuner, prompts, *, max_new: int, port: int,
                   queue_limit: int, policy_window: int,
                   slo_ttft_s: float, slo_itl_s: float,
                   temperature: float, top_k: int, top_p: float,
                   seed: int):
    """Serve the workload through the HTTP/SSE gateway: every request is
    a real localhost TCP client streaming SSE frames, the engine ticks in
    the pipelined asyncio loop, and the report carries goodput / SLO
    attainment next to the engine's own metrics."""
    import asyncio

    from ..serving.gateway import GatewayServer, PipelinedEngine, sse_generate
    from ..serving.gateway.pipeline import goodput_stats

    sampling = None
    if temperature > 0.0 or top_k or top_p < 1.0:
        sampling = {"temperature": temperature, "top_k": top_k,
                    "top_p": top_p}

    async def _run():
        pipe = PipelinedEngine(engine, queue_limit=queue_limit, tuner=tuner,
                               policy_window=policy_window,
                               slo_ttft_s=slo_ttft_s, slo_itl_s=slo_itl_s)
        srv = GatewayServer(pipe, port=port)
        await srv.start()
        t0 = time.monotonic()

        async def one(i, prompt):
            n_tokens, bounced = 0, 0
            while True:        # honor Retry-After on a 429 bounce
                final = None
                async for kind, payload in sse_generate(
                        "127.0.0.1", srv.port, prompt,
                        max_new_tokens=max_new,
                        sampling=dict(sampling, seed=seed + i)
                        if sampling else None):
                    if kind == "tokens":
                        n_tokens += len(payload)
                    else:
                        final = (kind, payload)
                if final and final[0] == "http_error" \
                        and final[1]["status"] == 429:
                    bounced += 1
                    await asyncio.sleep(
                        float(final[1].get("retry_after") or 1))
                    continue
                return n_tokens, bounced, final

        results = await asyncio.gather(
            *[one(i, p) for i, p in enumerate(prompts)])
        wall = time.monotonic() - t0
        await srv.drain()
        return pipe, results, wall

    pipe, results, wall = asyncio.run(_run())
    report = {
        "requests": len(prompts),
        "wall_s": wall,
        "client_retries_429": sum(r[1] for r in results),
        **{k: v for k, v in pipe.stats().items() if k != "draining"},
        **goodput_stats(engine.finished, wall, slo_ttft_s, slo_itl_s),
    }
    return engine.finished, report


def serve(**kwargs) -> dict:
    """Back-compat wrapper: build a :class:`ServeConfig` from keyword
    arguments and delegate to :func:`serve_config`."""
    return serve_config(ServeConfig(**kwargs))


def build_serving(scfg: ServeConfig):
    """Model, weights, tuner and engine of a serve run, plus its synthetic
    prompts: everything :func:`serve_config` drives, for callers that
    drive the engine tick by tick.  Returns ``(engine, tuner, prompts)``."""
    n_lanes, max_len, prompt_len = scfg.n_lanes, scfg.max_len, scfg.prompt_len
    seed, autotune, workdir = scfg.seed, scfg.autotune, scfg.workdir
    cache, n_pages, page_size = scfg.cache, scfg.n_pages, scfg.page_size
    prefill_chunk, draft, spec_k = scfg.prefill_chunk, scfg.draft, scfg.spec_k
    kv_dtype = scfg.kv_dtype
    if kv_dtype not in ("fp", "int8", "auto"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    if kv_dtype == "auto" and not (cache == "paged" and autotune):
        raise ValueError("--kv-dtype auto needs --cache paged --autotune")
    mesh = make_serving_mesh(scfg.mesh)
    cfg = serving_arch_config(scfg)
    model = build_model(cfg)
    params = init_serving_params(model, seed, mesh)
    if scfg.num_splits is not None and cache == "paged":
        # forced split-KV degree: published before any engine or variant
        # jit trace so the tuned and untuned paths both read it
        # (num_splits=1 is the explicit legacy / sequential spelling)
        at.publish("flash_paged_decode", num_splits=int(scfg.num_splits))
        at.publish("flash_paged_verify", num_splits=int(scfg.num_splits))
    draft_model = draft_params = None
    if draft:
        # self-speculative draft: the target's own leading layers (shared
        # embed/head), so the draft's argmax actually agrees with the
        # target often enough for acceptances to happen at random init
        draft_model = model.draft_model()
        draft_params = model.slice_draft_params(params, draft_model)
    tuner = _make_autotuner(model, workdir, cache, page_size,
                            gateway=scfg.gateway,
                            prefill_chunk=prefill_chunk,
                            spec_k=spec_k if draft else None,
                            prefix_cache=scfg.prefix_cache,
                            kv_precision=kv_dtype == "auto",
                            num_splits=scfg.num_splits,
                            mesh=mesh, mesh_shape=scfg.mesh,
                            tuning_backend=scfg.tuning_backend,
                            golden_db=scfg.golden_db,
                            table_pages=-(-max_len // page_size)) \
        if autotune else None
    resolved_kv = kv_dtype
    if kv_dtype == "auto":
        # calibrate every (precision x block_k) candidate per bucket,
        # then collapse the committed winners into one structural pool
        # dtype (majority vote) — the pool is built once, up front.  A
        # warm restart finds every region already committed and runs
        # zero measurements.
        for b in tuner.kv_buckets:
            while not tuner.kv_precision_committed(b):
                tuner.kv_precision(b, params)
        resolved_kv = tuner.resolve_kv_dtype()
    engine = ServingEngine(model, params, n_lanes=n_lanes, max_len=max_len,
                           autotuner=tuner, cache=cache, n_pages=n_pages,
                           page_size=page_size, timeslice=scfg.timeslice,
                           prefill_chunk=prefill_chunk,
                           draft_model=draft_model,
                           draft_params=draft_params,
                           spec_k=spec_k if draft else None,
                           prefix_cache=scfg.prefix_cache,
                           kv_dtype=resolved_kv, mesh=mesh)
    rng = np.random.default_rng(seed)
    # shared_prefix > 0 prepends one common system prompt to every
    # request — the workload that makes the prefix cache earn its keep
    prefix = rng.integers(0, cfg.vocab_size,
                          size=scfg.shared_prefix).tolist() \
        if scfg.shared_prefix else []
    prompts = [prefix + rng.integers(
        0, cfg.vocab_size, size=rng.integers(4, prompt_len)).tolist()
        for _ in range(scfg.n_requests)]
    return engine, tuner, prompts


def serve_config(scfg: ServeConfig) -> dict:
    n_requests, max_new, seed = scfg.n_requests, scfg.max_new, scfg.seed
    draft, shared_prefix, gateway = scfg.draft, scfg.shared_prefix, \
        scfg.gateway
    temperature, top_k, top_p = scfg.temperature, scfg.top_k, scfg.top_p
    engine, tuner, prompts = build_serving(scfg)
    gateway_report = None
    if gateway:
        finished, gateway_report = _serve_gateway(
            engine, tuner, prompts, max_new=max_new, port=scfg.port,
            queue_limit=scfg.queue_limit, policy_window=scfg.policy_window,
            slo_ttft_s=scfg.slo_ttft_s, slo_itl_s=scfg.slo_itl_s,
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed)
    else:
        for rid in range(n_requests):
            engine.submit(Request(rid=rid, prompt=prompts[rid],
                                  max_new_tokens=max_new,
                                  sampling=SamplingParams(
                                      temperature=temperature, top_k=top_k,
                                      top_p=top_p, seed=seed + rid)))
        finished = engine.run(
            max_steps=n_requests * (max_new + 4 + shared_prefix))
    summary = engine.metrics.summary()
    prefix_stats = None
    if scfg.prefix_cache:
        kvp = engine.kv.stats().get("prefix", {})
        prefix_stats = {**summary["prefix_cache"],
                        "pages_saved": kvp.get("pages_saved", 0),
                        "cow_copies": kvp.get("cow_copies", 0),
                        "evictions": kvp.get("evictions", 0),
                        "cached_pages": kvp.get("cached_pages", 0)}
    return {
        "config": scfg.to_dict(),
        "mesh": scfg.mesh,
        "layers": engine.model.cfg.n_layers,
        "param_bytes": int(sum(a.nbytes for a in
                               jax.tree_util.tree_leaves(engine.params))),
        # rid -> greedy token ids, the bit-identity surface for the
        # mesh-vs-unsharded correctness checks (CI + bench mesh cells)
        "outputs": {int(r.rid): [int(t) for t in r.out_tokens]
                    for r in finished},
        # zero-re-tuning surface: a warm restart from a committed DB must
        # report measurements == 0 / measured_regions [] — every region
        # shows up in warm_regions instead (mesh-suffixed regions tune
        # fresh the first time, then warm-load like the rest)
        "autotune": ({
            "executor_calls": tuner.session.executor_calls,
            "measurements": sum(
                len(st.tried)
                for st in tuner.ctx.dynamic_state.values()),
            "measured_regions": sorted(
                name for name, st in tuner.ctx.dynamic_state.items()
                if st.tried),
            "warm_regions": sorted(
                {name for _, name in tuner.session.warm_hits}),
        } if tuner else None),
        # which durability layer the winners live in (backend, path,
        # record count, golden overlay) — None when serving untuned
        "tuning_db": engine.tuning_db(),
        "finished": len(finished), "requests": n_requests,
        "decode_steps": engine.steps,
        "generated_tokens": summary["generated_tokens"],
        "tokens_per_s": summary["tokens_per_s"],
        "p50_queue_wait_s": summary["queue_wait_s"]["p50"],
        "p99_queue_wait_s": summary["queue_wait_s"]["p99"],
        "mean_ttft_s": summary["ttft_s"]["mean"],
        "p50_ttft_s": summary["ttft_s"]["p50"],
        "p99_ttft_s": summary["ttft_s"]["p99"],
        "p50_itl_s": summary["itl_s"]["p50"],
        "p99_itl_s": summary["itl_s"]["p99"],
        "wall_s": summary["wall_s"],
        "preemptions": summary["preemptions"],
        "prefill_chunks": engine.prefill_chunks,
        "spec": engine.spec_stats() if draft else None,
        "cache": engine.kv.stats(),
        "prefix_cache": prefix_stats,
        "committed_buckets": tuner.committed_params() if tuner else None,
        "committed_prefill": (
            {f"{b}_c{cs}": pp for (b, cs), pp
             in tuner.committed_prefill_params().items()}
            if tuner and tuner.prefill_regions else None),
        "committed_spec": (tuner.committed_spec_params()
                           if tuner and tuner.spec_regions else None),
        "committed_prefix": (tuner.committed_prefix_params()
                             if tuner and tuner.prefix_region is not None
                             else None),
        "gateway": gateway_report,
        "committed_gateway": (tuner.committed_gateway_params()
                              if tuner and tuner.gateway_region is not None
                              else None),
        "kv_dtype": engine.kv.kv_dtype,
        "committed_kv_precision": (tuner.committed_kv_precision_params()
                                   if tuner and tuner.kv_regions else None),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="serve the published widths of --arch (default: "
                         "its reduced CPU smoke-test config)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N whole layers")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--cache", choices=("dense", "paged"), default="dense",
                    help="KV backend: dense lanes or paged block pool")
    ap.add_argument("--pages", type=int, default=None,
                    help="paged: physical page count (default: lane parity)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged: tokens per KV page")
    ap.add_argument("--timeslice", type=int, default=None,
                    help="preempt a lane after N decode steps when work is "
                         "queued (serve more requests than lanes)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="paged: stream prompts in N-token chunks "
                         "interleaved with decode (chunked prefill / "
                         "continuous batching); default: monolithic")
    ap.add_argument("--draft", action="store_true",
                    help="paged: speculative decoding with a reduced-depth "
                         "self-speculative draft (target's leading layers)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculative tick")
    ap.add_argument("--num-splits", type=int, default=None,
                    help="paged: split-KV parallelism degree for decode/"
                         "verify (Flash-Decoding two-phase) — 1 forces "
                         "the sequential kernel; default: tuned per "
                         "length bucket over {1,2,4} with --autotune, "
                         "else 1")
    ap.add_argument("--kv-dtype", choices=("fp", "int8", "auto"),
                    default="fp",
                    help="paged: KV page precision — fp pool dtype, int8 "
                         "pages with per-row scales and in-kernel "
                         "dequant, or auto (KVPrecision regions "
                         "calibrate fp vs int8 under a greedy-agreement "
                         "guard; needs --autotune)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged+chunked: content-addressed prefix caching "
                         "(refcounted shared pages, copy-on-write)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend one common N-token system prompt to "
                         "every request (the prefix-cache workload)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling filter (0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 disables)")
    ap.add_argument("--gateway", action="store_true",
                    help="serve through the async HTTP/SSE gateway "
                         "(pipelined ticks, real localhost TCP clients)")
    ap.add_argument("--port", type=int, default=0,
                    help="gateway: listen port (0 = ephemeral)")
    ap.add_argument("--queue-limit", type=int, default=64,
                    help="gateway: admission-queue bound (429 beyond)")
    ap.add_argument("--policy-window", type=int, default=2,
                    help="gateway: finished requests per GatewayPolicy "
                         "measurement window")
    ap.add_argument("--slo-ttft", type=float, default=30.0,
                    help="gateway: TTFT SLO in seconds (goodput counts "
                         "only requests inside it)")
    ap.add_argument("--slo-itl", type=float, default=5.0,
                    help="gateway: p95 inter-token-latency SLO in seconds")
    ap.add_argument("--mesh", default=None,
                    help="tensor-parallel device mesh 'RxC' (data x "
                         "model), e.g. '1x4': paged KV pools and "
                         "attention heads shard over the model axis; "
                         "'1x1' is bit-identical to no mesh")
    ap.add_argument("--autotune", action="store_true",
                    help="run-time AT over decode buckets (repro.at)")
    ap.add_argument("--workdir", default=".",
                    help="AT session workdir (param files + record store)")
    ap.add_argument("--tuning-backend", default="jsonl",
                    help="tuning-DB backend under --workdir "
                         "(at.record_backends: jsonl | sqlite)")
    ap.add_argument("--golden-db", default=None,
                    help="read-only golden winner DB overlaid under the "
                         "local store (exported via 'python -m repro.at "
                         "export'): local record beats golden, golden "
                         "beats cold")
    args = ap.parse_args()
    enable_compile_cache()
    out = serve_config(ServeConfig.from_args(args))
    def fmt(x, spec):
        return format(x, spec) if x is not None else "n/a"

    spec_note = ""
    if out["spec"] is not None:
        s = out["spec"]
        spec_note = (f", spec k={s['spec_k']} accept "
                     f"{s['accepted_tokens']}/{s['drafted_tokens']} "
                     f"({s['accept_rate']:.0%})")
    if out["prefix_cache"] is not None:
        p = out["prefix_cache"]
        spec_note += (f", prefix hit {p['hit_requests']}/"
                      f"{out['requests']} ({p['hit_rate']:.0%}, "
                      f"{p['hit_tokens']} tok, "
                      f"{p['pages_saved']} pages saved)")
    if out["kv_dtype"] != "fp":
        c = out["cache"]
        spec_note += (f", kv {out['kv_dtype']} "
                      f"({c['kv_bytes_per_token']:.0f} B/tok, "
                      f"cap {c['capacity_tokens']} tok)")
    if out["gateway"] is not None:
        g = out["gateway"]
        spec_note += (f", gateway {g['goodput_tok_s']:.1f} good tok/s "
                      f"(SLO {g['slo_attainment']:.0%}, "
                      f"{g['overlapped_ticks']}/{g['ticks']} ticks "
                      f"overlapped, {g['rejected_429']} bounced)")
    print(f"[serve] {out['finished']}/{out['requests']} requests, "
          f"{out['generated_tokens']} tokens in {out['wall_s']:.1f}s "
          f"({out['tokens_per_s']:.1f} tok/s, "
          f"queue p50 {fmt(out['p50_queue_wait_s'], '.3f')}s, "
          f"ttft p50 {fmt(out['p50_ttft_s'], '.3f')}s "
          f"p99 {fmt(out['p99_ttft_s'], '.3f')}s, "
          f"itl p50 {fmt(out['p50_itl_s'], '.4f')}s, "
          f"preemptions {out['preemptions']}{spec_note})")


if __name__ == "__main__":
    main()
