"""Serving engine: the orchestration layer of the serving stack.

The engine wires four components together and owns none of their policy:

* :mod:`~repro.serving.scheduler` — FIFO admission queues + preemption
  decisions (continuous batching over fixed decode lanes);
* :mod:`~repro.serving.kvcache` — the KV backend: ``dense`` per-lane
  strips or ``paged`` block allocation with host swap;
* :mod:`~repro.serving.metrics` — TTFT / inter-token latency / throughput
  aggregation over finished requests;
* the decode dispatch — a jit'd fixed-shape decode step, optionally routed
  through a :class:`~repro.tuning.dynamic.DecodeAutoTuner` whose
  per-length-bucket ``dynamic select`` regions pick the decode variant at
  run time (the paper's Sample 6/7 pattern applied to serving).

With the paged backend the engine *serves* more concurrent requests than
it has decode lanes: queued work triggers time-slice preemption, the
victim's pages are swapped to host memory, and the sequence later resumes
by swap-in — no prefill re-run, bit-identical continuation.

With ``prefill_chunk=N`` (paged only) prompts stream into the KV cache
N tokens per scheduler tick instead of prefilling monolithically at
admission: each tick runs one prefill chunk per mid-prefill lane, then
one batched decode step over the decoding lanes — long prompts stop
head-of-line-blocking short requests (chunked prefill / continuous
batching; see docs/SERVING.md for the tick anatomy).

With ``prefix_cache=True`` (paged + chunked prefill) admissions first
match the prompt against the pool's content-addressed prefix index: the
lane's leading page-table rows are seeded with the shared pages
(refcounted, copy-on-write) and chunked prefill starts at the first
uncached token — a request behind an identical system prompt skips that
prompt's prefill entirely, and TTFT drops by exactly the skipped chunks.
Each committed full chunk-page is published back into the index.
Outputs are bit-identical with caching on or off.

With ``spec_k=k`` plus a draft model (paged only) the decode step is
*speculative*: a small draft proposes up to k tokens per tick, the
target scores all k+1 positions in ONE verify call (the chunked-prefill
kernel over ``[last token, draft_1..draft_k]``), and the accept/reject
rule emits 1..k+1 tokens per tick — decode throughput stops being
bounded by one paged-attention dispatch per emitted token.  Rejected
drafts' KV rolls back via ``PagedKVCache.truncate_to``.  Token choice
everywhere (greedy or sampled) routes through per-request
:class:`~repro.serving.sampling.SamplingParams`; greedy speculative
output is bit-identical to plain greedy decode.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..trace import span
from . import sampling
from .buckets import LENGTH_BUCKETS
from .kvcache import DenseKVCache, PagedKVCache, make_kv_cache
from .metrics import ServingMetrics
from .scheduler import LaneState, Request, Scheduler

__all__ = ["ServingEngine", "Request", "LaneState", "TickWork",
           "length_bucket"]


def length_bucket(n: int, buckets=LENGTH_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


@dataclass
class TickWork:
    """One dispatched-but-unmaterialised decode tick.

    ``dispatch()`` returns the tick's device work as jax arrays (async
    futures under jax's dispatch model) plus the host-side batch context
    the emission needs.  Nothing has blocked yet: :meth:`block` (or the
    ``np.asarray`` inside :meth:`ServingEngine.emit`) is the first point
    the host waits on the device — which is what lets a pipelined caller
    overlap other host work (network I/O, queue drain) with the device
    compute of the current tick.
    """

    logits: Any                # (n_lanes, 1, V) jax array, un-materialised
    decoding: list[int]        # lane ids in this tick's decode batch
    reqs: list[Request]        # the lanes' requests, same order

    def block(self) -> None:
        """Wait for the tick's device compute (callable off-thread)."""
        jax.block_until_ready(self.logits)


class ServingEngine:
    def __init__(self, model, params, n_lanes: int = 4,
                 max_len: int = 512, eos_id: int | None = None,
                 decode_fn: Callable | None = None,
                 prefill_fn: Callable | None = None,
                 greedy: bool = True, autotuner=None,
                 cache: str = "dense", n_pages: int | None = None,
                 page_size: int = 16, timeslice: int | None = None,
                 prefill_chunk: int | None = None,
                 draft_model=None, draft_params=None,
                 spec_k: int | None = None,
                 prefix_cache: bool = False,
                 prefix_min_match: int = 1,
                 prefix_eviction: str = "lru",
                 kv_dtype: str = "fp",
                 swap_compress: bool = False,
                 mesh=None):
        self.model = model
        self.params = params
        self.n_lanes = n_lanes
        self.max_len = max_len
        self.eos_id = eos_id
        # tensor-parallel serving: a mesh with a multi-device "model" axis
        # shards the page pools' KV-head axis across devices and runs the
        # paged attention ops under shard_map (kernels/ops.py); the mesh
        # is closed over the jit'd step functions below (it is a static
        # hashable, not a traced argument).  A 1-device mesh (or None)
        # takes the unsharded code paths unchanged.
        self.mesh = mesh
        self.kv = make_kv_cache(model, cache, n_lanes, max_len,
                                n_pages=n_pages, page_size=page_size,
                                prefix_cache=prefix_cache,
                                prefix_min_match=prefix_min_match,
                                prefix_eviction=prefix_eviction,
                                kv_dtype=kv_dtype,
                                swap_compress=swap_compress,
                                mesh=mesh)
        if prefill_chunk is not None and self.kv.kind != "paged":
            raise ValueError(
                "chunked prefill streams the prompt into the paged KV "
                "cache; use cache='paged' (dense keeps monolithic prefill)")
        if prefix_cache and prefill_chunk is None:
            raise ValueError(
                "prefix caching admits at the first uncached token via "
                "chunked prefill; pass prefill_chunk")
        self.prefill_chunk = prefill_chunk
        self.scheduler = Scheduler(n_lanes, timeslice=timeslice)
        self.metrics = ServingMetrics()
        step_fn = model.paged_decode_step if self.kv.kind == "paged" \
            else model.decode_step
        if mesh is not None and self.kv.kind == "paged":
            step_fn = functools.partial(step_fn, mesh=mesh)
        self._decode = decode_fn or jax.jit(step_fn)
        prefill_base = model.prefill if mesh is None \
            else functools.partial(model.prefill, mesh=mesh)
        self._prefill = prefill_fn or jax.jit(
            prefill_base, static_argnums=(3,))
        if prefill_chunk is not None:
            chunk_fn = model.paged_prefill_step if mesh is None \
                else functools.partial(model.paged_prefill_step, mesh=mesh)
            self._prefill_step = jax.jit(chunk_fn)
        # -- speculative decoding ------------------------------------------
        self.spec_k = spec_k
        self.draft_model = draft_model
        self.draft_params = draft_params
        if spec_k is not None:
            if spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "speculative decoding needs a draft model: pass "
                    "draft_model + draft_params (see ArchConfig."
                    "draft_config / Model.slice_draft_params)")
            if self.kv.kind != "paged":
                raise ValueError(
                    "speculative decoding verifies drafts through the "
                    "paged chunk kernel; use cache='paged'")
            # the draft keeps plain dense per-lane KV strips: rollback is
            # a position reset (stale KV past the committed point is
            # masked by kv_len and overwritten by the next draft), and a
            # preempted lane just rebuilds by draft-prefill on resume —
            # the draft's KV never needs to swap with the sequence
            self.draft_kv = DenseKVCache(draft_model, n_lanes, max_len)
            self.draft_pos = [0] * n_lanes   # tokens in the draft's cache
            verify_fn = model.speculative_step if mesh is None \
                else functools.partial(model.speculative_step, mesh=mesh)
            self._verify = jax.jit(verify_fn)
            self._draft_decode = jax.jit(draft_model.decode_step)
            self._draft_prefill = jax.jit(draft_model.prefill,
                                          static_argnums=(3,))
        # run-time AT hook (repro.at): a tuning/dynamic.DecodeAutoTuner
        # routing each decode step through the per-bucket dynamic select
        # region (and, when chunked prefill is on, each prefill chunk
        # through the per-(prompt-bucket x chunk) prefill region; with
        # speculation, each verify through the per-bucket SpecBucket
        # region); None keeps the plain jit'd paths.
        self.autotuner = autotuner
        self.active: dict[int, Request] = {}
        self.finished: list[Request] = []
        self.cancelled: list[Request] = []
        self.steps = 0
        self.prefill_chunks = 0          # chunk-steps executed (chunked)
        self.spec_ticks = 0              # speculative ticks executed
        self.drafted_tokens = 0          # draft tokens offered to verify
        self.accepted_tokens = 0         # draft tokens accepted

    # -- compat views -------------------------------------------------------
    @property
    def lanes(self) -> list[LaneState]:
        return self.scheduler.lanes

    @property
    def queue(self):
        return self.scheduler.waiting

    @property
    def caches(self):
        return self.kv.caches

    # -- admission ---------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.scheduler.submit(req)

    def _finish(self, lane_id: int, req: Request, now: float) -> None:
        req.done = True
        req.finish_t = now
        self.finished.append(req)
        self.metrics.observe(req)
        self.active.pop(req.rid, None)
        self.kv.release(lane_id)
        self.scheduler.vacate(lane_id)

    def cancel_request(self, rid: int) -> bool:
        """Abort a request wherever it lives — queued, preempted, or on a
        lane — releasing its lane and KV pages (refcounts on shared prefix
        pages drop cleanly; the pool's three-state accounting balances).

        The client-disconnect path of the gateway.  Cancelled requests do
        NOT feed the latency metrics (a half-served stream has no honest
        TTFT/ITL) and are tracked in :attr:`cancelled` instead of
        :attr:`finished`.  Must be called at a tick boundary, never
        between :meth:`dispatch` and :meth:`emit` — the pending tick's
        batch context still references the lane.  Returns False when
        ``rid`` is unknown (already finished or never submitted).
        """
        req = self.active.pop(rid, None)
        if req is not None:
            lane_id = next(i for i, l in enumerate(self.scheduler.lanes)
                           if l.rid == rid)
            self.kv.release(lane_id)
            self.scheduler.vacate(lane_id)
            self._reset_draft(lane_id)
        else:
            req = self.scheduler.remove_queued(rid)
            if req is None:
                return False
        req.done = True
        req.cancelled = True
        req.finish_t = time.monotonic()
        self.cancelled.append(req)
        return True

    def _is_eos(self, tok: int) -> bool:
        """Explicit EOS guard: ``eos_id=0`` is a valid stop token and
        ``eos_id=None`` disables EOS stopping entirely."""
        return self.eos_id is not None and tok == self.eos_id

    def _next_token(self, req: Request, logits) -> int:
        """Pick the request's next token from one logits row through its
        sampling params (greedy = exact argmax), keyed by the emission
        index so fixed seeds are independent of lane/batch layout."""
        return sampling.sample_token(np.asarray(logits), req.sampling,
                                     len(req.out_tokens))

    def _reset_draft(self, lane_id: int) -> None:
        """Invalidate the draft's cache for a (re)occupied lane; the next
        speculative tick rebuilds it with one draft prefill."""
        if self.spec_k is not None:
            self.draft_pos[lane_id] = 0

    def _seed_prefix(self, lane_id: int, req: Request) -> int:
        """Match + seed one admission through the prefix cache, routed
        through the ``PrefixPolicy`` dynamic-select region when a tuner
        declares one (the region's alternatives apply their
        (min-match x eviction) knobs before seeding; outputs are
        bit-identical under every policy, so the region measures freely).
        Returns the prefill start position and stamps the request."""
        if not getattr(self.kv, "prefix_cache", False):
            return 0
        if self.autotuner is not None \
                and getattr(self.autotuner, "prefix_region", None) \
                is not None:
            out = self.autotuner.prefix_policy(self.kv, lane_id,
                                               req.prompt)
            cached = out["cached"] if isinstance(out, dict) else int(out)
        else:
            cached = self.kv.seed_prefix(lane_id, req.prompt)
        req.cached_tokens = cached
        return cached

    def _preempt_lane(self, lane_id: int, priority: bool = False) -> None:
        lane = self.scheduler.lanes[lane_id]
        req = self.active.pop(lane.rid)
        handle = self.kv.swap_out(lane_id)
        self.scheduler.preempt(lane_id, req, handle, priority=priority)

    def _admit(self) -> None:
        for lane_id in self.scheduler.free_lanes():
            nxt = self.scheduler.next_admission()
            if nxt is None:
                return
            kind, item = nxt
            if kind == "resume":
                if not self.kv.swap_in(lane_id, item.handle):
                    self.scheduler.push_back(kind, item)
                    return                 # no pages yet; retry next step
                self.scheduler.occupy(lane_id, item.req, item.pos,
                                      item.remaining, phase=item.phase)
                self._reset_draft(lane_id)
                self.active[item.req.rid] = item.req
                continue
            req = item
            if self.prefill_chunk is not None:
                # chunked admission: the lane enters prefill phase with no
                # compute — the prefill tick streams the prompt in chunk
                # by chunk.  Gate on pages for the *first chunk* only.
                first = min(self.prefill_chunk, len(req.prompt))
                if not self.kv.can_admit(first):
                    self.scheduler.push_back(kind, req)
                    return                 # page pressure; stay queued
                # prefix caching: match the prompt against the hash index
                # and seed the lane's leading page-table rows with the
                # shared pages — prefill then starts at the first uncached
                # token (TTFT shrinks by exactly the skipped chunks)
                cached = self._seed_prefix(lane_id, req)
                if req.admit_t is None:
                    req.admit_t = time.monotonic()   # queue wait ends here
                self.scheduler.occupy(lane_id, req, cached,
                                      req.max_new_tokens, phase="prefill")
                self._reset_draft(lane_id)
                self.active[req.rid] = req
                continue
            if isinstance(self.kv, PagedKVCache) \
                    and not self.kv.can_admit(len(req.prompt)):
                self.scheduler.push_back(kind, req)
                return                     # page pressure; stay queued
            if req.admit_t is None:
                req.admit_t = time.monotonic()   # queue wait ends here
            plen = self.kv.prefill_len(len(req.prompt))
            logits, cache1 = self._prefill(
                self.params, jnp.asarray([req.prompt], jnp.int32),
                None, plen)
            if not self.kv.admit(lane_id, cache1, len(req.prompt)):
                self.scheduler.push_back(kind, req)
                return
            tok = self._next_token(req, logits[0])
            now = time.monotonic()
            req.out_tokens.append(tok)
            req.first_token_t = now
            req.token_ts.append(now)
            self.scheduler.occupy(lane_id, req, len(req.prompt),
                                  req.max_new_tokens - 1)
            self._reset_draft(lane_id)
            self.active[req.rid] = req
            if req.max_new_tokens <= 1 or self._is_eos(tok):
                self._finish(lane_id, req, now)

    def _prefill_tick(self) -> None:
        """One prefill chunk for every mid-prefill lane.

        Each lane streams ``prefill_chunk`` prompt tokens into its paged
        KV cache per tick (pages allocated chunk-granularly, the ragged
        last chunk padded into the null page), so long prompts never
        head-of-line-block the decode step that follows in the same tick.
        The final chunk's last-valid-token logits seed decode — that is
        the request's first token (TTFT stamps here).
        """
        if self.prefill_chunk is None:
            return
        c = self.prefill_chunk
        for lane_id in self.scheduler.prefill_lanes():
            lane = self.scheduler.lanes[lane_id]
            req = self.active[lane.rid]
            plen = len(req.prompt)
            start, end = lane.pos, min(lane.pos + c, plen)
            # the COW guard covers the seeded-prefix edge: a fully-cached
            # page-aligned prompt starts prefill at plen-1, *inside* the
            # last shared page, which must be privately copied before the
            # recomputed KV write lands
            if not self.kv.ensure_tokens(lane_id, end) \
                    or not self.kv.cow_writable(lane_id, start):
                if len(self.active) == 1:
                    raise RuntimeError(
                        f"page pool too small: sequence {lane.rid} needs "
                        f"pages for prompt positions [{start}, {end}) and "
                        "no other lane can be evicted")
                self._preempt_lane(lane_id, priority=True)
                continue
            with span("engine.prefill_chunk", rid=lane.rid, start=start,
                      end=end):
                chunk = req.prompt[start:end] + [0] * (c - (end - start))
                args = (self.params, self.kv.caches,
                        self.kv.table_row(lane_id),
                        jnp.asarray([chunk], jnp.int32),
                        jnp.asarray([start], jnp.int32),
                        jnp.asarray([end], jnp.int32),
                        jnp.asarray([end - start - 1], jnp.int32))
                if self.autotuner is not None \
                        and getattr(self.autotuner, "prefill_regions", None):
                    logits, new_caches = self.autotuner.prefill(plen, c,
                                                                *args)
                else:
                    logits, new_caches = self._prefill_step(*args)
                self.kv.caches = new_caches
                self.prefill_chunks += 1
                lane.pos = end
                # every newly-FULL committed chunk-page becomes a shared,
                # content-addressed index entry other admissions can hit
                self.kv.publish_prefix(lane_id, req.prompt, end)
                if end < plen:
                    continue               # prompt still streaming in
                tok = self._next_token(req, logits[0])
                now = time.monotonic()
                req.out_tokens.append(tok)
                req.first_token_t = now
                req.token_ts.append(now)
                lane.phase = "decode"
                lane.remaining = req.max_new_tokens - 1
                if req.max_new_tokens <= 1 or self._is_eos(tok):
                    self._finish(lane_id, req, now)

    def _ensure_capacity(self) -> None:
        """Pre-decode page check: every decoding lane must own the page its
        next token writes to; a lane that cannot allocate one is preempted
        (its pages swap out, freeing room for the rest).  Mid-prefill lanes
        are skipped — the prefill tick does its own chunk-granular
        allocation."""
        for lane_id in self.scheduler.decode_lanes():
            lane = self.scheduler.lanes[lane_id]
            if self.kv.ensure_capacity(lane_id, lane.pos):
                continue
            if len(self.active) == 1:
                raise RuntimeError(
                    f"page pool too small: sequence {lane.rid} needs "
                    f"another page at pos {lane.pos} and no other lane "
                    "can be evicted")
            self._preempt_lane(lane_id, priority=True)

    # -- speculative decode: draft, verify, accept, roll back ---------------
    def _spec_capacity(self) -> tuple[dict[int, int], int]:
        """Per-lane effective draft length for this tick, plus the
        sequence-length key the tick's SpecBucket routing must reuse.

        ``k_eff`` is ``spec_k`` clamped by the lane's remaining token
        budget (a tick may emit at most ``remaining`` tokens), by
        ``max_len`` (the verify step writes KV at ``pos .. pos + k_eff``),
        and by page availability — on page pressure the chunk shrinks
        toward a plain decode step before the lane is evicted.

        The key is computed ONCE here and returned so that the region
        that capped the drafting is exactly the region that verifies it
        — recomputing it after this loop's page-pressure preemptions
        could land on a different bucket.
        """
        seq = max((self.scheduler.lanes[j].pos + 1
                   for j in self.scheduler.decode_lanes()), default=1)
        cap = self.spec_k
        if self.autotuner is not None \
                and getattr(self.autotuner, "spec_regions", None):
            # once the bucket's region has committed, stop drafting past
            # the winner's accept window — those draft-decode steps buy
            # tokens the committed verify would never even look at
            cap = self.autotuner.spec_draft_k(seq, self.spec_k)
        k_eff: dict[int, int] = {}
        for i in list(self.scheduler.decode_lanes()):
            lane = self.scheduler.lanes[i]
            ke = max(0, min(cap, lane.remaining - 1,
                            self.max_len - 1 - lane.pos))
            while ke >= 0 and not self.kv.ensure_tokens(
                    i, lane.pos + ke + 1):
                ke -= 1
            if ke < 0:
                if len(self.active) == 1:
                    raise RuntimeError(
                        f"page pool too small: sequence {lane.rid} needs "
                        f"another page at pos {lane.pos} and no other "
                        "lane can be evicted")
                self._preempt_lane(i, priority=True)
                continue
            k_eff[i] = ke
        return k_eff, seq

    def _draft_propose(self, k_eff: dict[int, int]
                       ) -> tuple[dict, dict]:
        """Propose up to ``k_eff[i]`` draft tokens per decoding lane.

        The draft's dense cache trails the committed sequence; a lane
        whose cache is empty or far behind (fresh admission, resume after
        preemption) catches up with ONE draft prefill over the committed
        tokens, otherwise the 1-2 missing tokens (the previous tick's
        un-fed last draft and/or bonus token) feed through the batched
        draft decode step along with the proposals themselves.  Lanes
        with nothing to feed ride the fixed-shape batch masked to the
        dead slot ``max_len - 1`` (never read: live lanes finish at
        ``max_len - 2``).  Returns ({lane: [draft tokens]},
        {lane: [draft probs]}).
        """
        drafts: dict[int, list[int]] = {i: [] for i in k_eff}
        dprobs: dict[int, list] = {i: [] for i in k_eff}
        pending: dict[int, list[int]] = {}
        rngs: dict[int, np.random.Generator] = {}
        sps = {}
        for i, ke in k_eff.items():
            if ke == 0:
                continue
            lane = self.scheduler.lanes[i]
            req = self.active[lane.rid]
            sps[i] = req.sampling
            rngs[i] = sampling.draft_rng(req.sampling, len(req.out_tokens))
            all_toks = req.prompt + req.out_tokens   # len == lane.pos + 1
            gap = len(all_toks) - self.draft_pos[i]
            if self.draft_pos[i] == 0 or gap > 2:
                # variable-length trace, like the admission prefill: one
                # retrace per distinct committed length (known cost; a
                # fixed-shape draft catch-up would need a logit_idx-style
                # padded prefill)
                logits_d, dcache = self._draft_prefill(
                    self.draft_params,
                    jnp.asarray([all_toks], jnp.int32), None, self.max_len)
                self.draft_kv.admit(i, dcache, len(all_toks))
                self.draft_pos[i] = len(all_toks)
                tok, q = sampling.propose_token(
                    np.asarray(logits_d[0]), sps[i], rngs[i])
                drafts[i].append(tok)
                dprobs[i].append(q)
                pending[i] = []
            else:
                pending[i] = all_toks[self.draft_pos[i]:]
        while True:
            feed: dict[int, int] = {}
            for i, ke in k_eff.items():
                if ke == 0:
                    continue
                if pending[i]:
                    feed[i] = pending[i][0]
                elif 0 < len(drafts[i]) < ke:
                    feed[i] = drafts[i][-1]   # extend the draft chain
            if not feed:
                break
            token = np.zeros((self.n_lanes, 1), np.int32)
            pos = np.full((self.n_lanes,), self.max_len - 1, np.int32)
            for i, t in feed.items():
                token[i, 0] = t
                pos[i] = self.draft_pos[i]
            logits_d, self.draft_kv.caches = self._draft_decode(
                self.draft_params, self.draft_kv.caches,
                jnp.asarray(token), jnp.asarray(pos))
            logits_np = np.asarray(logits_d)
            for i in feed:
                self.draft_pos[i] += 1
                if pending[i]:
                    pending[i].pop(0)
                    if pending[i]:
                        continue           # still catching up
                tok, q = sampling.propose_token(logits_np[i], sps[i],
                                                rngs[i])
                drafts[i].append(tok)
                dprobs[i].append(q)
        return drafts, dprobs

    def _spec_tick(self) -> None:
        """One speculative decode tick over the decoding lanes.

        Draft proposes, the target verifies the whole candidate chunk in
        one batched ``speculative_step`` (KV for every candidate is
        written into the pages), the accept rule emits 1..k+1 tokens per
        lane, and ``truncate_to`` returns the pages past the committed
        point to the pool.  Mid-prefill lanes ride along masked to the
        null page exactly as in the plain decode step.
        """
        k_eff, seq = self._spec_capacity()
        decoding = self.scheduler.decode_lanes()
        if not decoding:
            return
        drafts, dprobs = self._draft_propose(k_eff)
        c = self.spec_k + 1
        tokens = np.zeros((self.n_lanes, c), np.int32)
        start = np.zeros((self.n_lanes,), np.int32)
        kv_len = np.zeros((self.n_lanes,), np.int32)
        for i in decoding:
            lane = self.scheduler.lanes[i]
            req = self.active[lane.rid]
            row = [req.out_tokens[-1]] + drafts[i]
            tokens[i, :len(row)] = row
            start[i] = lane.pos
            kv_len[i] = lane.pos + len(row)
        extra = self.kv.decode_extra(
            mask_lanes=self.scheduler.prefill_lanes())
        args = (self.params, self.kv.caches, *extra,
                jnp.asarray(tokens), jnp.asarray(start),
                jnp.asarray(kv_len))
        if self.autotuner is not None \
                and getattr(self.autotuner, "spec_regions", None):
            # seq is the key _spec_capacity capped the drafting with —
            # reusing it (not recomputing post-preemption) keeps the
            # capping and verifying region the same
            out = self.autotuner.spec(
                seq, *args, measure=not self.autotuner.spec_committed(seq))
            if isinstance(out, dict):
                # tuned variants return an env dict so the region can
                # commit on time_per_token rather than raw call latency
                logits, new_caches = out["logits"], out["caches"]
            else:
                logits, new_caches = out
        else:
            logits, new_caches = self._verify(*args)
        self.kv.caches = new_caches
        logits_np = np.asarray(logits)
        # a tuner variant may verify a narrower chunk (tuned k): drafts
        # past its window are auto-rejected — their KV was never written
        window_max = logits_np.shape[1] - 1
        now = time.monotonic()
        self.steps += 1
        self.spec_ticks += 1
        for i in decoding:
            lane = self.scheduler.lanes[i]
            req = self.active[lane.rid]
            w = min(len(drafts[i]), window_max)
            emitted, n_acc = sampling.speculative_accept(
                drafts[i][:w], dprobs[i][:w], logits_np[i, :w + 1],
                req.sampling, len(req.out_tokens))
            self.drafted_tokens += w
            self.accepted_tokens += n_acc
            committed = lane.pos + n_acc + 1
            self.kv.truncate_to(i, committed)
            emit = []
            for tok in emitted:
                emit.append(tok)
                if self._is_eos(tok):
                    break
            req.out_tokens.extend(emit)
            req.token_ts.extend([now] * len(emit))
            lane.pos = committed
            lane.remaining -= len(emit)
            lane.steps_served += 1
            lane.tokens_served += len(emit)
            self.draft_pos[i] = min(self.draft_pos[i], committed)
            if lane.remaining <= 0 or self._is_eos(emit[-1]) \
                    or lane.pos >= self.max_len - 1:
                self._finish(i, req, now)

    def spec_stats(self) -> dict:
        """Speculation counters (zeros when speculation is off)."""
        return {
            "spec_k": self.spec_k,
            "spec_ticks": self.spec_ticks,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "accept_rate": (self.accepted_tokens / self.drafted_tokens
                            if self.drafted_tokens else 0.0),
        }

    def tuning_db(self) -> dict | None:
        """Identity of the tuning DB feeding this engine's AT regions —
        backend name, path, record count and any golden overlay (``None``
        when serving untuned).  Surfaces which durability layer the
        committed winners live in, next to the winners themselves in the
        serve report."""
        session = getattr(self.autotuner, "session", None)
        if session is None:
            return None
        return session.records.describe()

    # -- one scheduler tick: schedule -> dispatch -> emit --------------------
    # step() is the synchronous composition; the gateway's pipelined loop
    # calls the three phases itself so the host can do other work (drain
    # arrivals, flush token streams over the network) between dispatch and
    # emit, while the device computes the tick.
    def schedule(self) -> None:
        """Host-side scheduling half of a tick: time-slice victim, queue
        admissions, prefill chunks (their device work dispatches async;
        only a final chunk's token emission materialises)."""
        with span("engine.schedule"):
            with span("engine.admit"):
                victim = self.scheduler.pick_victim()
                if victim is not None:
                    self._preempt_lane(victim)
                self._admit()
            self._prefill_tick()

    def dispatch(self) -> TickWork | None:
        """Dispatch the tick's batched decode step without waiting on it.

        ``kv.caches`` is advanced to the (asynchronously computing) output
        caches immediately, so any later work composes on the right value;
        the logits stay un-materialised inside the returned
        :class:`TickWork` until :meth:`emit`.  Speculative ticks run
        internally (their accept/reject rule is host-side by nature) and
        return None, as does a tick with no decoding lanes.
        """
        with span("engine.dispatch"):
            if self.spec_k is not None:
                self._spec_tick()
                return None
            with span("engine.dispatch.inputs"):
                self._ensure_capacity()
                decoding = self.scheduler.decode_lanes()
                if not decoding:
                    return None
                token = np.zeros((self.n_lanes, 1), np.int32)
                pos = np.zeros((self.n_lanes,), np.int32)
                for i in decoding:
                    lane = self.scheduler.lanes[i]
                    req = self.active[lane.rid]
                    token[i, 0] = req.out_tokens[-1]
                    pos[i] = lane.pos
                # mid-prefill lanes ride along in the fixed-shape batched
                # step with a zeroed page-table row: their dummy KV write
                # lands in the null page, never in their live prefill pages
                extra = self.kv.decode_extra(
                    mask_lanes=self.scheduler.prefill_lanes())
                args = (self.params, self.kv.caches, *extra,
                        jnp.asarray(token), jnp.asarray(pos))
            meta = {"lanes": len(decoding)}
            if self.kv.kind == "paged":
                # the decode kernel's tile, the blocks its walk takes and
                # the live K and V bytes it reads (a lane's context is
                # pos + 1 tokens)
                tile = self.kv.decode_tile()
                meta["kv_tile"] = tile
                meta["kv_blocks"] = int(
                    ((pos[decoding] + tile) // tile).sum())
                meta["kv_bytes"] = int((pos[decoding] + 1).sum()
                                       * self.kv.kv_bytes_per_token())
            with span("engine.dispatch.call", **meta):
                if self.autotuner is not None:
                    kv_len = int(pos.max()) + 1
                    logits, new_caches = self.autotuner.decode(kv_len, *args)
                else:
                    logits, new_caches = self._decode(*args)
            self.kv.caches = new_caches
            reqs = [self.active[self.scheduler.lanes[i].rid]
                    for i in decoding]
            return TickWork(logits=logits, decoding=decoding, reqs=reqs)

    def emit(self, work: TickWork | None) -> None:
        """Materialise a dispatched tick's logits (the first host-device
        sync of the tick), sample each lane's token, and run the finish
        bookkeeping.  No-op for ``None`` (spec/idle ticks)."""
        if work is None:
            return
        with span("engine.emit"):
            with span("engine.emit.wait"):
                # start the logits' copy before waiting, as a bare
                # np.asarray would: the copy then follows the step on the
                # device, with no host round trip between the two
                work.logits.copy_to_host_async()
                work.block()
            with span("engine.emit.fetch"):
                logits_np = np.asarray(work.logits)
            with span("engine.emit.sample"):
                toks = sampling.sample_batch(
                    logits_np[work.decoding], [r.sampling for r in work.reqs],
                    [len(r.out_tokens) for r in work.reqs])
            with span("engine.emit.finish"):
                now = time.monotonic()
                self.steps += 1
                for i, req, tok in zip(work.decoding, work.reqs, toks):
                    lane = self.scheduler.lanes[i]
                    req.out_tokens.append(tok)
                    req.token_ts.append(now)
                    lane.pos += 1
                    lane.remaining -= 1
                    lane.steps_served += 1
                    lane.tokens_served += 1
                    if lane.remaining <= 0 or self._is_eos(tok) \
                            or lane.pos >= self.max_len - 1:
                        self._finish(i, req, now)

    def step(self) -> None:
        self.schedule()
        self.emit(self.dispatch())

    def run(self, max_steps: int = 1000) -> list[Request]:
        while (self.scheduler.has_queued or self.active) \
                and self.steps < max_steps:
            steps_before, done_before = self.steps, len(self.finished)
            self.step()
            if not self.active and self.scheduler.has_queued \
                    and self.steps == steps_before \
                    and len(self.finished) == done_before:
                raise RuntimeError(
                    "admission stalled: queued work cannot obtain a lane "
                    "or pages (page pool smaller than one sequence?)")
        return self.finished
