"""Paged-KV serving stack: cache backends, scheduler, metrics, engine.

Correctness contract: the paged engine (with or without preemption) is an
*implementation detail* — greedy outputs must be bit-identical to the
dense engine, which in turn matches a plain prefill+decode loop
(test_serving_tuning.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import build_model
from repro.serving import (PagedKVCache, Request, Scheduler,
                           ServingEngine, ServingMetrics)
from repro.serving.kvcache import _lane_set


@pytest.fixture(scope="module")
def paged_model():
    cfg = ARCHS["yi-6b"].reduced()      # plain GQA: paged-capable
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(n, max_new=6, plen=4):
    return [Request(rid=i, prompt=[1 + i] + list(range(2, 2 + plen - 1)),
                    max_new_tokens=max_new) for i in range(n)]


# --------------------------------------------------------------------------
# kernels: paged decode oracle + Pallas kernel
# --------------------------------------------------------------------------


class TestPagedDecodeKernel:
    def test_paged_ref_matches_dense_ref(self):
        """Gathering pages through a table == the dense decode oracle."""
        from repro.kernels import ref
        key = jax.random.PRNGKey(0)
        b, h, hkv, d, psz, nblk = 2, 4, 2, 16, 8, 3
        q = jax.random.normal(key, (b, h, 1, d)) * 0.3
        kd = jax.random.normal(jax.random.PRNGKey(1),
                               (b, hkv, nblk * psz, d)) * 0.3
        vd = jax.random.normal(jax.random.PRNGKey(2),
                               (b, hkv, nblk * psz, d)) * 0.3
        kv_len = jnp.asarray([20, 13], jnp.int32)
        # scatter the dense caches into a shuffled pool
        table = np.array([[3, 7, 1], [5, 2, 6]], np.int32)
        pool_shape = (9, hkv, psz, d)
        kp = jnp.zeros(pool_shape)
        vp = jnp.zeros(pool_shape)
        for bi in range(b):
            for blk in range(nblk):
                sl = slice(blk * psz, (blk + 1) * psz)
                kp = kp.at[table[bi, blk]].set(kd[bi, :, sl, :])
                vp = vp.at[table[bi, blk]].set(vd[bi, :, sl, :])
        # lanes may not share pages for this equivalence to hold
        want = ref.decode_ref(q, kd, vd, kv_len)
        got = ref.paged_decode_ref(q, kp, vp, jnp.asarray(table), kv_len)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_flash_paged_decode_interpret(self):
        from repro.kernels import ref
        from repro.kernels.flash_attention import flash_paged_decode
        b, h, hkv, d, psz, p, nblk = 2, 4, 2, 16, 8, 10, 3
        q = jax.random.normal(jax.random.PRNGKey(0), (b, h, 1, d)) * 0.3
        kp = jax.random.normal(jax.random.PRNGKey(1), (p, hkv, psz, d)) * 0.3
        vp = jax.random.normal(jax.random.PRNGKey(2), (p, hkv, psz, d)) * 0.3
        table = jnp.asarray([[3, 7, 1], [5, 2, 0]], jnp.int32)
        kv_len = jnp.asarray([20, 13], jnp.int32)
        want = ref.paged_decode_ref(q, kp, vp, table, kv_len)
        got = flash_paged_decode(q, kp, vp, table, kv_len, interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)
        # block_k is a whole number of pages per grid step (the kernel's
        # run-time AT PP): one page, and two over the 3-page table
        for block_k in (psz, 2 * psz):
            got_tile = flash_paged_decode(q, kp, vp, table, kv_len,
                                          block_k=block_k, interpret=True)
            np.testing.assert_allclose(got_tile, want, atol=1e-5)
        # a block_k that is not a multiple of the page rounds up to one
        with pytest.warns(RuntimeWarning, match="block_k=3"):
            got_bad = flash_paged_decode(q, kp, vp, table, kv_len,
                                         block_k=3, interpret=True)
        np.testing.assert_allclose(got_bad, want, atol=1e-5)

    def test_ops_dispatch_cpu(self):
        from repro.kernels import ops, ref
        b, h, hkv, d, psz, p = 1, 2, 1, 8, 4, 5
        q = jnp.ones((b, h, 1, d)) * 0.1
        kp = jnp.ones((p, hkv, psz, d)) * 0.2
        vp = jnp.ones((p, hkv, psz, d)) * 0.3
        table = jnp.asarray([[1, 2]], jnp.int32)
        kv_len = jnp.asarray([6], jnp.int32)
        got = ops.paged_decode_attention(q, kp, vp, table, kv_len)
        want = ref.paged_decode_ref(q, kp, vp, table, kv_len)
        np.testing.assert_allclose(got, want, atol=1e-6)


# --------------------------------------------------------------------------
# PagedKVCache backend
# --------------------------------------------------------------------------


class TestPagedKVCache:
    def test_alloc_accounting_and_null_page(self, paged_model):
        cfg, model, params = paged_model
        kv = PagedKVCache(model, n_lanes=2, max_len=64, n_pages=17,
                          page_size=8)
        assert kv.free_pages == 16          # page 0 reserved
        logits, c1 = model.prefill(params, jnp.asarray([[1, 2, 3]]),
                                   None, kv.prefill_len(3))
        assert kv.admit(0, c1, 3)
        assert kv.used_pages == 1           # 3 tokens -> one 8-token page
        assert kv.cache_tokens() == 8       # memory scales with live tokens
        assert 0 not in kv.table[0, :kv.n_blocks[0]]
        # page-boundary growth
        assert kv.ensure_capacity(0, 7)     # still page 0 of the lane
        assert kv.used_pages == 1
        assert kv.ensure_capacity(0, 8)     # crosses into block 1
        assert kv.used_pages == 2
        kv.release(0)
        assert kv.used_pages == 0 and kv.free_pages == 16

    def test_swap_out_in_roundtrip(self, paged_model):
        cfg, model, params = paged_model
        kv = PagedKVCache(model, n_lanes=2, max_len=32, n_pages=9,
                          page_size=8)
        logits, c1 = model.prefill(params, jnp.asarray([[5, 6, 7, 8]]),
                                   None, kv.prefill_len(4))
        kv.admit(0, c1, 4)
        before = jax.tree.map(
            lambda pool: np.asarray(pool[:, kv.table[0, :1]]), kv.caches)
        h = kv.swap_out(0)
        assert kv.used_pages == 0
        assert kv.swap_in(1, h)             # resume on a different lane
        after = jax.tree.map(
            lambda pool: np.asarray(pool[:, kv.table[1, :1]]), kv.caches)
        jax.tree.map(np.testing.assert_array_equal, before, after)

    def test_alloc_failure(self, paged_model):
        cfg, model, params = paged_model
        kv = PagedKVCache(model, n_lanes=1, max_len=64, n_pages=3,
                          page_size=8)   # 2 usable pages
        assert not kv.can_admit(24)      # would need 3 pages
        logits, c1 = model.prefill(params, jnp.asarray([[1] * 16]),
                                   None, kv.prefill_len(16))
        assert kv.admit(0, c1, 16)
        assert not kv.ensure_capacity(0, 16)   # pool exhausted

    def test_swa_arch_rejected(self):
        cfg = ARCHS["h2o-danube-1.8b"].reduced()   # sliding window
        model = build_model(cfg)
        with pytest.raises(ValueError, match="paged"):
            PagedKVCache(model, n_lanes=1, max_len=32, n_pages=5,
                         page_size=8)


# --------------------------------------------------------------------------
# _lane_set regression (satellite: full-width branch clobbered other lanes)
# --------------------------------------------------------------------------


class TestLaneSet:
    def test_full_width_source_writes_only_target_lane(self):
        full = jnp.arange(2 * 2 * 4 * 3, dtype=jnp.float32
                          ).reshape(2, 2, 4, 3)
        one = jnp.full((2, 2, 4, 3), -1.0)       # full-width source
        out = _lane_set(full, one, 1)
        np.testing.assert_array_equal(out[:, 0], full[:, 0])  # untouched
        np.testing.assert_array_equal(out[:, 1], one[:, 0])

    def test_two_concurrent_lanes_no_crosstalk(self, paged_model):
        """Second admission must not perturb the first lane's generation."""
        cfg, model, params = paged_model

        def solo(prompt):
            eng = ServingEngine(model, params, n_lanes=2, max_len=48)
            eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=5))
            return eng.run(max_steps=30)[0].out_tokens

        want = {0: solo([3, 1, 4, 1]), 1: solo([2, 7, 1, 8])}
        eng = ServingEngine(model, params, n_lanes=2, max_len=48)
        eng.submit(Request(rid=0, prompt=[3, 1, 4, 1], max_new_tokens=5))
        eng.submit(Request(rid=1, prompt=[2, 7, 1, 8], max_new_tokens=5))
        done = {r.rid: r.out_tokens for r in eng.run(max_steps=30)}
        assert done == want


# --------------------------------------------------------------------------
# scheduler + engine
# --------------------------------------------------------------------------


class TestScheduler:
    def test_fifo_deque(self):
        from collections import deque
        s = Scheduler(n_lanes=1)
        assert isinstance(s.waiting, deque)
        for i in range(5):
            s.submit(Request(rid=i, prompt=[1]))
        order = []
        while s.has_queued:
            kind, item = s.next_admission()
            order.append(item.rid)
        assert order == [0, 1, 2, 3, 4]

    def test_timeslice_victim_yields_to_queue(self):
        """A time-slice victim re-queues at the BACK: the waiting request
        gets the lane (rotation), not an immediate self-re-admission."""
        s = Scheduler(n_lanes=1)
        s.submit(Request(rid=9, prompt=[1]))
        req = Request(rid=1, prompt=[1])
        s.occupy(0, req, pos=4, remaining=2)
        s.preempt(0, req, handle="h")
        kind, item = s.next_admission()
        assert kind == "new" and item.rid == 9
        kind, item = s.next_admission()
        assert kind == "resume" and item.req.rid == 1
        assert item.pos == 4 and item.remaining == 2

    def test_page_pressure_victim_resumes_first(self):
        """A page-pressure victim re-queues at the FRONT so freeing memory
        never starves the evicted sequence."""
        s = Scheduler(n_lanes=1)
        s.submit(Request(rid=9, prompt=[1]))
        req = Request(rid=1, prompt=[1])
        s.occupy(0, req, pos=4, remaining=2)
        s.preempt(0, req, handle="h", priority=True)
        kind, item = s.next_admission()
        assert kind == "resume" and item.req.rid == 1

    def test_pick_victim_timeslice(self):
        s = Scheduler(n_lanes=2, timeslice=3)
        s.occupy(0, Request(rid=0, prompt=[1]), 4, 8)
        s.occupy(1, Request(rid=1, prompt=[1]), 4, 8)
        s.lanes[0].steps_served = 5
        s.lanes[1].steps_served = 2
        assert s.pick_victim() is None       # nothing queued
        s.submit(Request(rid=2, prompt=[1]))
        assert s.pick_victim() == 0          # longest-served past slice
        s.lanes[0].steps_served = 1
        assert s.pick_victim() is None       # nobody past the slice


class TestServingEngineFIFO:
    def test_fifo_fairness_under_pressure(self, paged_model):
        """8 requests through 1 lane: service order == submission order."""
        cfg, model, params = paged_model
        eng = ServingEngine(model, params, n_lanes=1, max_len=48)
        for r in _requests(8, max_new=3):
            eng.submit(r)
        done = eng.run(max_steps=200)
        assert [r.rid for r in done] == list(range(8))
        firsts = [r.first_token_t for r in done]
        assert firsts == sorted(firsts)


class TestPagedEngine:
    def test_paged_matches_dense(self, paged_model):
        cfg, model, params = paged_model
        reqs = _requests(3, max_new=6)
        dense = ServingEngine(model, params, n_lanes=2, max_len=48)
        for r in reqs:
            dense.submit(r)
        want = {r.rid: r.out_tokens for r in dense.run(max_steps=100)}
        paged = ServingEngine(model, params, n_lanes=2, max_len=48,
                              cache="paged", page_size=8)
        for r in _requests(3, max_new=6):
            paged.submit(r)
        got = {r.rid: r.out_tokens for r in paged.run(max_steps=100)}
        assert got == want

    def test_preemption_more_requests_than_lanes(self, paged_model):
        """2 lanes, 5 requests, tiny pool + timeslice: the scheduler must
        preempt (pages swap out/in) and every request still finishes with
        the exact dense-engine output."""
        cfg, model, params = paged_model
        dense = ServingEngine(model, params, n_lanes=2, max_len=48)
        for r in _requests(5, max_new=6):
            dense.submit(r)
        want = {r.rid: r.out_tokens for r in dense.run(max_steps=300)}

        eng = ServingEngine(model, params, n_lanes=2, max_len=48,
                            cache="paged", page_size=8, n_pages=9,
                            timeslice=3)
        for r in _requests(5, max_new=6):
            eng.submit(r)
        done = eng.run(max_steps=400)
        assert len(done) == 5                # served 5 > 2 lanes
        assert eng.scheduler.preemptions > 0
        assert eng.kv.swap_outs > 0 and eng.kv.swap_ins > 0
        assert {r.rid: r.out_tokens for r in done} == want
        assert eng.metrics.summary()["preemptions"] > 0
        # genuine rotation: a request beyond the lane count got its first
        # token before ANY request finished (preemption actually yielded
        # the lane to the queue, not an immediate self-re-admission)
        by_rid = {r.rid: r for r in done}
        first_finish = min(r.finish_t for r in done)
        assert by_rid[2].first_token_t <= first_finish

    def test_dense_timeslice_preemption(self, paged_model):
        """Preemption also works on the dense backend (lane-strip swap)."""
        cfg, model, params = paged_model
        eng = ServingEngine(model, params, n_lanes=1, max_len=48,
                            timeslice=2)
        for r in _requests(3, max_new=6):
            eng.submit(r)
        done = eng.run(max_steps=200)
        assert len(done) == 3
        assert eng.scheduler.preemptions > 0

    def test_pool_too_small_raises(self, paged_model):
        cfg, model, params = paged_model
        eng = ServingEngine(model, params, n_lanes=1, max_len=64,
                            cache="paged", page_size=8, n_pages=3)
        eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=40))
        with pytest.raises(RuntimeError, match="page pool too small"):
            eng.run(max_steps=100)


# --------------------------------------------------------------------------
# EOS guard (satellite: eos_id=0 is a valid stop token, None disables)
# --------------------------------------------------------------------------


class TestEOSGuard:
    def _zero_logit_engine(self, model, params, eos_id):
        """Engine whose decode always emits token 0."""
        def prefill_fn(p, tokens, fe, max_len):
            logits, caches = model.prefill(p, tokens, fe, max_len)
            return jnp.zeros_like(logits).at[:, 0].set(1.0), caches

        def decode_fn(p, caches, token, pos):
            logits, caches = model.decode_step(p, caches, token, pos)
            return jnp.zeros_like(logits).at[:, 0].set(1.0), caches

        return ServingEngine(model, params, n_lanes=1, max_len=48,
                             eos_id=eos_id, decode_fn=decode_fn,
                             prefill_fn=prefill_fn)

    def test_eos_zero_stops(self, paged_model):
        cfg, model, params = paged_model
        eng = self._zero_logit_engine(model, params, eos_id=0)
        eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=10))
        done = eng.run(max_steps=40)
        assert len(done) == 1
        assert len(done[0].out_tokens) < 10    # stopped on token 0

    def test_eos_none_never_stops_on_zero(self, paged_model):
        cfg, model, params = paged_model
        eng = self._zero_logit_engine(model, params, eos_id=None)
        eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=10))
        done = eng.run(max_steps=40)
        assert len(done[0].out_tokens) == 10   # token 0 is not EOS


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------


class TestMetrics:
    def test_percentiles(self):
        m = ServingMetrics()
        for i in range(4):
            r = Request(rid=i, prompt=[1], submit_t=float(i))
            r.first_token_t = float(i) + 0.5
            r.token_ts = [float(i) + 0.5, float(i) + 0.6, float(i) + 0.8]
            r.out_tokens = [7, 7, 7]
            r.finish_t = float(i) + 0.8
            m.observe(r)
        s = m.summary()
        assert s["requests"] == 4
        assert s["generated_tokens"] == 12
        assert s["ttft_s"]["p50"] == pytest.approx(0.5)
        # ITL samples alternate 0.1 / 0.2 -> p50 between them, p99 ~ 0.2
        assert 0.1 <= s["itl_s"]["p50"] <= 0.2
        assert s["itl_s"]["p99"] == pytest.approx(0.2, abs=0.01)
        assert s["wall_s"] == pytest.approx(3.8)
        assert s["tokens_per_s"] == pytest.approx(12 / 3.8)

    def test_empty_summary(self):
        s = ServingMetrics().summary()
        assert s["requests"] == 0 and s["ttft_s"]["p50"] is None

    def test_percentile_interpolates_on_tiny_samples(self):
        """Pinned 5-element series: linear interpolation between order
        statistics, not nearest-rank (which would report p99 == max and
        snap p50 to a sample)."""
        from repro.serving.metrics import percentile
        xs = [30.0, 10.0, 50.0, 20.0, 40.0]     # unsorted on purpose
        assert percentile(xs, 50) == pytest.approx(30.0)
        assert percentile(xs, 99) == pytest.approx(49.6)   # not 50.0
        assert percentile(xs, 0) == pytest.approx(10.0)
        assert percentile(xs, 100) == pytest.approx(50.0)
        assert percentile(xs, 25) == pytest.approx(20.0)
        assert percentile([7.0], 99) == pytest.approx(7.0)
        assert percentile([], 50) is None
        with pytest.raises(ValueError):
            percentile(xs, 101)
