"""Speculative decoding: verify dispatch, KV rollback, bit-identity,
sampler determinism, and the SpecBucket tuning region.

Correctness contract: speculative decoding is an *implementation detail*
of the paged engine — greedy outputs must be bit-identical to the dense
engine token-for-token for every draft length k, through mid-stream
rejections, EOS inside an accepted run, and mid-spec swap-out/resume.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import build_model
from repro.serving import (PagedKVCache, Request, SamplingParams,
                           ServingEngine)
from repro.serving import sampling


@pytest.fixture(scope="module")
def spec_model():
    cfg = ARCHS["yi-6b"].reduced()      # plain GQA: paged-capable
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    draft_model = model.draft_model()
    draft_params = model.slice_draft_params(params, draft_model)
    return cfg, model, params, draft_model, draft_params


def _requests(n=3, max_new=6, plen=11):
    return [Request(rid=i, prompt=[1 + i] + [(3 * i + j) % 90 + 2
                                             for j in range(plen - 1)],
                    max_new_tokens=max_new) for i in range(n)]


def _dense_want(model, params, reqs_fn, max_len=48, max_steps=200,
                eos_id=None):
    eng = ServingEngine(model, params, n_lanes=2, max_len=max_len,
                        eos_id=eos_id)
    for r in reqs_fn():
        eng.submit(r)
    return {r.rid: r.out_tokens for r in eng.run(max_steps=max_steps)}


def _spec_engine(model, params, dmodel, dparams, k, **kw):
    kw.setdefault("n_lanes", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", 8)
    return ServingEngine(model, params, cache="paged", draft_model=dmodel,
                         draft_params=dparams, spec_k=k, **kw)


# --------------------------------------------------------------------------
# draft config + params
# --------------------------------------------------------------------------


class TestDraftConfig:
    def test_reduced_depth_same_vocab(self):
        cfg = ARCHS["yi-6b"].reduced()
        d = cfg.draft_config()
        assert d.n_layers == max(1, cfg.n_layers // 2)
        assert d.vocab_size == cfg.vocab_size
        assert d.d_model == cfg.d_model          # self-slicing width

    def test_every_registry_config_has_a_draft(self):
        for cfg in ARCHS.values():
            d = cfg.draft_config()
            assert 1 <= d.n_layers < max(2, cfg.n_layers)
            assert d.family == cfg.family

    def test_width_reduced_draft(self):
        cfg = ARCHS["yi-6b"].reduced()
        d = cfg.draft_config(width_frac=0.5)
        assert d.d_model == cfg.d_model // 2

    def test_slice_draft_params(self, spec_model):
        cfg, model, params, dmodel, dparams = spec_model
        stacked = jax.tree.leaves(params["layers"])[0]
        sliced = jax.tree.leaves(dparams["layers"])[0]
        assert sliced.shape[0] == dmodel.cfg.n_layers
        np.testing.assert_array_equal(
            np.asarray(sliced), np.asarray(stacked[:dmodel.cfg.n_layers]))
        assert dparams["embed"] is params["embed"]   # shared head/embed

    def test_slice_rejects_width_mismatch(self, spec_model):
        cfg, model, params, *_ = spec_model
        narrow = build_model(cfg.draft_config(width_frac=0.5))
        with pytest.raises(ValueError, match="width"):
            model.slice_draft_params(params, narrow)


# --------------------------------------------------------------------------
# verify dispatch: ops + speculative_step
# --------------------------------------------------------------------------


class TestVerifyDispatch:
    def test_ops_verify_matches_prefill_oracle(self):
        from repro.kernels import ops, ref
        b, h, hkv, d, psz, p = 2, 4, 2, 16, 8, 9
        q = jax.random.normal(jax.random.PRNGKey(0), (b, h, 5, d)) * 0.3
        kp = jax.random.normal(jax.random.PRNGKey(1), (p, hkv, psz, d)) * 0.3
        vp = jax.random.normal(jax.random.PRNGKey(2), (p, hkv, psz, d)) * 0.3
        table = jnp.asarray([[3, 7, 1], [5, 2, 6]], jnp.int32)
        start = jnp.asarray([10, 0], jnp.int32)
        kv_len = jnp.asarray([15, 3], jnp.int32)
        got = ops.paged_verify_attention(q, kp, vp, table, start, kv_len)
        want = ref.paged_prefill_ref(q, kp, vp, table, start, kv_len)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_speculative_step_c1_matches_decode_step(self, spec_model):
        """A 1-wide verify chunk is a decode step: same logits row."""
        cfg, model, params, *_ = spec_model
        kv = PagedKVCache(model, n_lanes=1, max_len=32, n_pages=9,
                          page_size=8)
        prompt = [5, 6, 7, 8]
        logits, c1 = model.prefill(params, jnp.asarray([prompt]), None,
                                   kv.prefill_len(len(prompt)))
        assert kv.admit(0, c1, len(prompt))
        kv.ensure_capacity(0, len(prompt))
        tok = jnp.asarray([[int(jnp.argmax(logits[0]))]], jnp.int32)
        pos = jnp.asarray([len(prompt)], jnp.int32)
        table = kv.decode_extra()[0]
        want, _ = model.paged_decode_step(params, kv.caches, table, tok, pos)
        got, _ = model.speculative_step(params, kv.caches, table, tok,
                                        pos, pos + 1)
        np.testing.assert_allclose(np.asarray(got[0, 0]),
                                   np.asarray(want[0]), atol=1e-4)


# --------------------------------------------------------------------------
# KV rollback (truncate_to)
# --------------------------------------------------------------------------


class TestTruncateTo:
    def test_frees_exactly_overallocated_pages(self, spec_model):
        cfg, model, params, *_ = spec_model
        kv = PagedKVCache(model, n_lanes=2, max_len=64, n_pages=17,
                          page_size=8)
        assert kv.ensure_tokens(0, 20)          # 3 pages for [0, 20)
        assert kv.used_pages == 3
        free_before = kv.free_pages
        held = [int(p) for p in kv.table[0, :3]]
        # commit only 10 tokens: pages 2 (covering [0, 16)) stay, page 3 goes
        assert kv.truncate_to(0, 10) == 1
        assert kv.used_pages == 2
        assert kv.free_pages == free_before + 1
        assert kv.n_blocks[0] == 2
        assert [int(p) for p in kv.table[0, :2]] == held[:2]
        assert int(kv.table[0, 2]) == 0          # vacated row -> null page
        assert held[2] in kv._free               # back in the pool
        # idempotent: already tight
        assert kv.truncate_to(0, 10) == 0
        assert kv.truncate_to(0, 16) == 0        # same page count

    def test_truncate_other_lane_untouched(self, spec_model):
        cfg, model, params, *_ = spec_model
        kv = PagedKVCache(model, n_lanes=2, max_len=64, n_pages=17,
                          page_size=8)
        kv.ensure_tokens(0, 24)
        kv.ensure_tokens(1, 24)
        lane1 = [int(p) for p in kv.table[1, :3]]
        kv.truncate_to(0, 1)
        assert [int(p) for p in kv.table[1, :3]] == lane1
        assert kv.n_blocks[1] == 3

    def test_dense_truncate_is_noop(self, spec_model):
        from repro.serving import DenseKVCache
        cfg, model, params, *_ = spec_model
        kv = DenseKVCache(model, n_lanes=1, max_len=32)
        assert kv.truncate_to(0, 4) == 0

    def test_full_spec_cycle_leaks_zero_pages(self, spec_model):
        """admit -> speculate -> reject -> decode -> finish returns every
        page to the pool."""
        cfg, model, params, dmodel, dparams = spec_model
        eng = _spec_engine(model, params, dmodel, dparams, k=4, n_pages=17)
        for r in _requests(3, max_new=6):
            eng.submit(r)
        done = eng.run(max_steps=200)
        assert len(done) == 3
        assert eng.drafted_tokens > eng.accepted_tokens   # rejections hit
        assert eng.kv.used_pages == 0
        assert eng.kv.free_pages == eng.kv.n_pages - 1    # null page apart
        assert np.all(np.asarray(eng.kv.table) == 0)


# --------------------------------------------------------------------------
# engine bit-identity
# --------------------------------------------------------------------------


class TestSpecBitIdentity:
    def test_spec_requires_paged_and_draft(self, spec_model):
        cfg, model, params, dmodel, dparams = spec_model
        with pytest.raises(ValueError, match="paged"):
            ServingEngine(model, params, n_lanes=1, max_len=32,
                          draft_model=dmodel, draft_params=dparams,
                          spec_k=2)
        with pytest.raises(ValueError, match="draft"):
            ServingEngine(model, params, n_lanes=1, max_len=32,
                          cache="paged", spec_k=2)

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_spec_greedy_matches_dense(self, spec_model, k):
        """Speculative greedy == plain greedy token-for-token, with
        mid-stream rejections exercised (random-init draft disagrees)."""
        cfg, model, params, dmodel, dparams = spec_model
        want = _dense_want(model, params, _requests)
        eng = _spec_engine(model, params, dmodel, dparams, k)
        for r in _requests():
            eng.submit(r)
        got = {r.rid: r.out_tokens for r in eng.run(max_steps=200)}
        assert got == want
        assert eng.spec_ticks > 0
        assert eng.drafted_tokens > 0
        assert eng.drafted_tokens >= eng.accepted_tokens

    def test_spec_greedy_matches_dense_moe_arch(self):
        """Second bench config (deepseek-7b, dense MHA stack): same contract."""
        cfg = ARCHS["deepseek-7b"].reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        dmodel = model.draft_model()
        dparams = model.slice_draft_params(params, dmodel)
        want = _dense_want(model, params, lambda: _requests(2))
        eng = _spec_engine(model, params, dmodel, dparams, k=4)
        for r in _requests(2):
            eng.submit(r)
        got = {r.rid: r.out_tokens for r in eng.run(max_steps=200)}
        assert got == want
        assert eng.spec_ticks > 0

    def test_spec_with_eos_matches_dense(self, spec_model):
        """EOS inside an accepted run truncates the emission exactly
        where the dense engine stops."""
        cfg, model, params, dmodel, dparams = spec_model
        plain = _dense_want(model, params, _requests)
        # pick a token the dense run emits mid-stream as the EOS id
        eos = plain[0][2]
        want = _dense_want(model, params, _requests, eos_id=eos)
        assert want != plain                    # EOS actually fired
        eng = _spec_engine(model, params, dmodel, dparams, k=4, eos_id=eos)
        for r in _requests():
            eng.submit(r)
        got = {r.rid: r.out_tokens for r in eng.run(max_steps=200)}
        assert got == want

    def test_mid_spec_swap_out_resume(self, spec_model):
        """Tiny pool + timeslice: lanes are preempted between speculative
        ticks (pages swap out/in, the draft cache rebuilds) and outputs
        stay bit-identical."""
        cfg, model, params, dmodel, dparams = spec_model
        want = _dense_want(model, params,
                           lambda: _requests(5, max_new=6), max_steps=300)
        eng = _spec_engine(model, params, dmodel, dparams, k=2,
                           n_pages=9, timeslice=3)
        for r in _requests(5, max_new=6):
            eng.submit(r)
        done = eng.run(max_steps=400)
        assert len(done) == 5
        assert eng.scheduler.preemptions > 0
        assert eng.kv.swap_outs > 0 and eng.kv.swap_ins > 0
        assert {r.rid: r.out_tokens for r in done} == want

    def test_spec_with_chunked_prefill(self, spec_model):
        """Chunked prefill + speculation in the same engine: prefill lanes
        ride the verify step masked to the null page."""
        cfg, model, params, dmodel, dparams = spec_model
        def reqs():
            return [Request(rid=0, prompt=list(range(1, 25)),
                            max_new_tokens=6),
                    Request(rid=1, prompt=[5, 6, 7], max_new_tokens=6)]
        want = _dense_want(model, params, reqs, max_len=64)
        eng = _spec_engine(model, params, dmodel, dparams, k=2,
                           max_len=64, prefill_chunk=4)
        for r in reqs():
            eng.submit(r)
        got = {r.rid: r.out_tokens for r in eng.run(max_steps=300)}
        assert got == want
        assert eng.prefill_chunks > 0 and eng.spec_ticks > 0

    def test_sampled_spec_runs_to_completion(self, spec_model):
        """Sampled speculation: right token counts, reproducible reruns."""
        cfg, model, params, dmodel, dparams = spec_model
        def reqs():
            return [Request(rid=i, prompt=[3 + i, 1, 4, 1],
                            max_new_tokens=6,
                            sampling=SamplingParams(temperature=0.8,
                                                    top_k=50, seed=7 + i))
                    for i in range(3)]
        outs = []
        for _ in range(2):
            eng = _spec_engine(model, params, dmodel, dparams, k=2)
            for r in reqs():
                eng.submit(r)
            done = eng.run(max_steps=200)
            assert all(len(r.out_tokens) == 6 for r in done)
            outs.append({r.rid: r.out_tokens for r in done})
        assert outs[0] == outs[1]               # seeded determinism


# --------------------------------------------------------------------------
# sampler unit behavior (deterministic; property tests in test_sampling.py)
# --------------------------------------------------------------------------


class TestSamplerUnits:
    def test_greedy_is_exact_argmax(self):
        logits = np.asarray([0.1, 2.0, -1.0, 2.0])
        sp = SamplingParams()
        assert sp.greedy
        assert sampling.sample_token(logits, sp, 0) == int(np.argmax(logits))

    def test_greedy_speculative_accept_rule(self):
        V = 8
        tl = np.zeros((3, V))
        tl[0, 2] = 1.0          # target argmax: 2
        tl[1, 5] = 1.0          # target argmax: 5
        tl[2, 6] = 1.0          # bonus row
        sp = SamplingParams()
        # both drafts agree -> all accepted + bonus
        emitted, a = sampling.speculative_accept(
            [2, 5], [None, None], tl, sp, 0)
        assert (emitted, a) == ([2, 5, 6], 2)
        # second draft disagrees -> correction from its target row
        emitted, a = sampling.speculative_accept(
            [2, 4], [None, None], tl, sp, 0)
        assert (emitted, a) == ([2, 5], 1)
        # immediate rejection -> single corrected token
        emitted, a = sampling.speculative_accept(
            [0, 4], [None, None], tl, sp, 0)
        assert (emitted, a) == ([2], 0)

    def test_sampled_accept_emits_in_support(self):
        rng = np.random.default_rng(0)
        sp = SamplingParams(temperature=1.0, seed=3)
        tl = rng.normal(size=(3, 16))
        q0 = sampling.filtered_probs(rng.normal(size=16), sp)
        q1 = sampling.filtered_probs(rng.normal(size=16), sp)
        emitted, a = sampling.speculative_accept([4, 9], [q0, q1], tl, sp, 0)
        assert 1 <= len(emitted) == a + 1 <= 3
        assert all(0 <= t < 16 for t in emitted)

    def test_spec_stats_off_by_default(self, spec_model):
        cfg, model, params, *_ = spec_model
        eng = ServingEngine(model, params, n_lanes=1, max_len=32)
        st = eng.spec_stats()
        assert st["spec_k"] is None and st["accept_rate"] == 0.0


# --------------------------------------------------------------------------
# SpecBucket tuning region (repro.at dynamic select)
# --------------------------------------------------------------------------


class TestSpecTuningRegion:
    def _mk(self, calls):
        def make_verify(k, bq, bk):
            def fn():
                calls.append((k, bq, bk))
                return {"k": k, "bq": bq, "bk": bk}
            return fn
        return make_verify

    def test_k_by_tile_product_space_commits(self, tmp_path):
        from repro import at
        from repro.tuning import DecodeAutoTuner
        session = at.AutoTuner(str(tmp_path))
        tuner = DecodeAutoTuner(session, lambda bk: (lambda: bk),
                                buckets=(512,), block_ks=(256,))
        calls: list = []
        tuner.add_spec(self._mk(calls), ks=(1, 4), buckets=(512, 2048),
                       block_qs=(5,), block_ks=(4, 8))
        assert len(tuner.spec_regions) == 2
        assert all(len(r.subregions) == 4           # k x block_k
                   for r in tuner.spec_regions.values())
        for _ in range(4):                          # one call per candidate
            tuner.spec(300)
        pp = tuner.committed_spec_params()[512]
        assert pp["k"] in (1, 4) and pp["block_k"] in (4, 8)
        assert tuner.committed_spec_params()[2048] is None

    def test_commits_on_time_per_token_not_call_latency(self, tmp_path):
        """A narrower verify is always cheaper per call, so the region
        must commit on reported time_per_token (throughput), not raw
        latency — and the engine-facing spec_draft_k then caps drafting
        at the winner's window."""
        import time as _time

        from repro import at
        from repro.tuning import DecodeAutoTuner
        session = at.AutoTuner(str(tmp_path))
        tuner = DecodeAutoTuner(session, lambda bk: (lambda: bk),
                                buckets=(512,), block_ks=(256,))

        def make_verify(k, bq, bk):
            def fn():
                # k=1 is the fastest CALL but the worst per emitted token
                _time.sleep(0.001 * k)
                return {"k": k, "time_per_token": 1.0 / k}
            return fn

        tuner.add_spec(make_verify, ks=(1, 4), buckets=(512,),
                       block_qs=(5,), block_ks=(8,))
        assert tuner.spec_draft_k(100, 4) == 4     # uncommitted: full width
        for _ in range(2):
            tuner.spec(100)
        assert tuner.committed_spec_params()[512]["k"] == 4
        assert tuner.spec_draft_k(100, 4) == 4
        assert tuner.spec_draft_k(100, 2) == 2     # engine cap still wins

    def test_warm_restart_zero_tuning(self, tmp_path):
        """A second session on the same workdir starts with the spec
        bucket committed — zero tuning-executor invocations."""
        from repro import at
        from repro.tuning import DecodeAutoTuner

        s1 = at.AutoTuner(str(tmp_path))
        t1 = DecodeAutoTuner(s1, lambda bk: (lambda: bk),
                             buckets=(512,), block_ks=(256,))
        t1.add_spec(self._mk([]), ks=(1, 4), buckets=(512,),
                    block_qs=(5,), block_ks=(8,))
        for _ in range(2):
            t1.spec(100)
        winner = t1.committed_spec()[512]
        assert winner is not None

        calls2: list = []
        s2 = at.AutoTuner(str(tmp_path))
        t2 = DecodeAutoTuner(s2, lambda bk: (lambda: bk),
                             buckets=(512,), block_ks=(256,))
        t2.add_spec(self._mk(calls2), ks=(1, 4), buckets=(512,),
                    block_qs=(5,), block_ks=(8,))
        assert t2.committed_spec()[512] == winner
        assert s2.executor_calls == 0
        assert ("dynamic", "SpecBucket_512") in s2.warm_hits
        out = t2.spec(100)
        assert out["k"] == (1, 4)[winner]
        assert calls2 == [((1, 4)[winner], 5, 8)]   # no re-measurement

    def test_engine_routes_through_spec_region(self, spec_model, tmp_path):
        """End-to-end: the engine's speculative tick goes through the
        tuner's SpecBucket region and greedy outputs stay bit-identical
        (even while candidates with different k are being measured)."""
        cfg, model, params, dmodel, dparams = spec_model
        from repro.launch.serve import _make_autotuner
        want = _dense_want(model, params, _requests)
        tuner = _make_autotuner(model, str(tmp_path), "paged", 8, spec_k=4)
        assert tuner.spec_regions
        eng = _spec_engine(model, params, dmodel, dparams, k=4,
                           autotuner=tuner)
        for r in _requests():
            eng.submit(r)
        got = {r.rid: r.out_tokens for r in eng.run(max_steps=200)}
        assert got == want
        assert eng.spec_ticks > 0
