"""Quantized paged KV: int8 pages, per-row scales, in-kernel dequant.

Correctness contract: an int8 pool is a *lossy but bounded* stand-in for
the fp pool — per-row round-trip error is bounded by half a quantization
step of that row, the quantized kernels match the quantized gather
oracle exactly (same dequant, different schedule), and end-to-end greedy
serving tracks the fp engine's outputs above the KVPrecision quality
floor.  Swaps of int8 pools are bit-exact (the payload is already the
canonical representation); fp-pool swap compression is opt-in and lossy.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.distributed.compression import (compress_roundtrip_error,
                                           compress_roundtrip_error_rows,
                                           dequantize_int8,
                                           dequantize_int8_rows,
                                           quantize_int8,
                                           quantize_int8_rows)
from repro.models import build_model
from repro.serving import PagedKVCache, Request, ServingEngine

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:               # pragma: no cover - dev deps include it
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def paged_model():
    cfg = ARCHS["yi-6b"].reduced()      # plain GQA: paged-capable
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# --------------------------------------------------------------------------
# per-row quantization: round-trip error bounds
# --------------------------------------------------------------------------


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
class TestRoundTripBounds:
    if HAVE_HYPOTHESIS:
        @given(seed=st.integers(0, 2**31 - 1), rows=st.integers(1, 6),
               cols=st.integers(1, 32), scale_exp=st.integers(-6, 6))
        @settings(max_examples=40, deadline=None)
        def test_rowwise_error_half_step(self, seed, rows, cols, scale_exp):
            """Round-to-nearest over 127 steps: the worst element errs by
            at most half a step of its own row's scale, so the global max
            error is bounded by the largest row amax / 254 (with fp
            slack) — independent of the data's absolute magnitude."""
            rng = np.random.default_rng(seed)
            x = jnp.asarray(
                rng.standard_normal((rows, cols)) * 10.0 ** scale_exp,
                jnp.float32)
            amax = float(np.max(np.abs(np.asarray(x))))
            err = float(compress_roundtrip_error_rows(x))
            assert err <= max(amax / 250.0, 1e-9)

        @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 64))
        @settings(max_examples=40, deadline=None)
        def test_tensorwise_error_half_step(self, seed, n):
            rng = np.random.default_rng(seed)
            x = jnp.asarray(rng.standard_normal(n), jnp.float32)
            amax = float(np.max(np.abs(np.asarray(x))))
            err = float(compress_roundtrip_error(x))
            assert err <= max(amax / 250.0, 1e-9)


class TestRowQuantization:
    def test_rowwise_beats_tensorwise_on_skewed_rows(self):
        """The reason pages carry per-row scales: one hot row must not
        flatten every other row's resolution to zero."""
        rng = np.random.default_rng(0)
        x = jnp.asarray(np.stack([rng.standard_normal(32) * 1e3,
                                  rng.standard_normal(32) * 1e-3]),
                        jnp.float32)
        qr, sr = quantize_int8_rows(x)
        row_err = float(jnp.max(jnp.abs(
            dequantize_int8_rows(qr, sr)[1] - x[1])))
        qt, st_ = quantize_int8(x)
        tensor_err = float(jnp.max(jnp.abs(
            dequantize_int8(qt, st_)[1] - x[1])))
        assert row_err < tensor_err / 100

    def test_zero_rows_roundtrip_to_zero(self):
        """Untouched pool rows (all-zero, scale 0) must dequantize to
        exactly 0.0 — the null page stays null under quantization."""
        q, s = quantize_int8_rows(jnp.zeros((3, 8)))
        assert float(jnp.max(jnp.abs(dequantize_int8_rows(q, s)))) == 0.0


# --------------------------------------------------------------------------
# quantized kernels vs the quantized gather oracle
# --------------------------------------------------------------------------


def _quantized_pools(p, hkv, psz, d, scale=0.3):
    kp = jax.random.normal(jax.random.PRNGKey(1), (p, hkv, psz, d)) * scale
    vp = jax.random.normal(jax.random.PRNGKey(2), (p, hkv, psz, d)) * scale
    k8, ks = quantize_int8_rows(kp)
    v8, vs = quantize_int8_rows(vp)
    return kp, vp, k8, ks, v8, vs


class TestQuantKernels:
    def test_decode_quant_matches_ref(self):
        from repro.kernels import ref
        from repro.kernels.flash_attention import flash_paged_decode_quant
        b, h, hkv, d, psz, p = 2, 4, 2, 16, 8, 10      # GQA group of 2
        q = jax.random.normal(jax.random.PRNGKey(0), (b, h, 1, d)) * 0.3
        kp, vp, k8, ks, v8, vs = _quantized_pools(p, hkv, psz, d)
        table = jnp.asarray([[3, 7, 1], [5, 2, 0]], jnp.int32)
        kv_len = jnp.asarray([20, 13], jnp.int32)      # ragged
        want = ref.paged_decode_ref(q, k8, v8, table, kv_len,
                                    k_scale=ks, v_scale=vs)
        got = flash_paged_decode_quant(q, k8, v8, ks, vs, table, kv_len,
                                       interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)
        # a one-page tile (the tuned PP) must not change the result
        got_sub = flash_paged_decode_quant(q, k8, v8, ks, vs, table,
                                           kv_len, block_k=psz,
                                           interpret=True)
        np.testing.assert_allclose(got_sub, want, atol=1e-5)
        # the quantized answer tracks the fp pools it was built from
        fp = ref.paged_decode_ref(q, kp, vp, table, kv_len)
        np.testing.assert_allclose(got, fp, atol=0.05)

    def test_prefill_quant_matches_ref(self):
        from repro.kernels import ref
        from repro.kernels.flash_attention import flash_paged_prefill_quant
        b, h, hkv, d, psz, p, c = 2, 4, 2, 16, 8, 10, 5
        q = jax.random.normal(jax.random.PRNGKey(0), (b, h, c, d)) * 0.3
        kp, vp, k8, ks, v8, vs = _quantized_pools(p, hkv, psz, d)
        # lane 1's tail pages route to the null page (ragged chunk)
        table = jnp.asarray([[3, 7, 1], [5, 0, 0]], jnp.int32)
        start = jnp.asarray([16, 3], jnp.int32)
        kv_len = jnp.asarray([21, 5], jnp.int32)
        want = ref.paged_prefill_ref(q, k8, v8, table, start, kv_len,
                                     k_scale=ks, v_scale=vs)
        got = flash_paged_prefill_quant(q, k8, v8, ks, vs, table, start,
                                        kv_len, interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)
        got_sub = flash_paged_prefill_quant(q, k8, v8, ks, vs, table,
                                            start, kv_len, block_q=2,
                                            block_k=psz // 2,
                                            interpret=True)
        np.testing.assert_allclose(got_sub, want, atol=1e-5)
        fp = ref.paged_prefill_ref(q, kp, vp, table, start, kv_len)
        np.testing.assert_allclose(got, fp, atol=0.05)

    def test_ops_dispatch_quant_cpu(self):
        """Passing scales through the ops layer routes every paged entry
        point (decode / prefill / verify) to the quantized backend."""
        from repro.kernels import ops, ref
        b, h, hkv, d, psz, p, c = 1, 2, 1, 8, 4, 6, 3
        _, _, k8, ks, v8, vs = _quantized_pools(p, hkv, psz, d)
        table = jnp.asarray([[1, 2]], jnp.int32)
        kv_len = jnp.asarray([6], jnp.int32)
        qd = jnp.ones((b, h, 1, d)) * 0.1
        got = ops.paged_decode_attention(qd, k8, v8, table, kv_len,
                                         k_scale=ks, v_scale=vs)
        want = ref.paged_decode_ref(qd, k8, v8, table, kv_len,
                                    k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(got, want, atol=1e-6)
        qc = jnp.ones((b, h, c, d)) * 0.1
        start = jnp.asarray([3], jnp.int32)
        klen = jnp.asarray([6], jnp.int32)
        want_c = ref.paged_prefill_ref(qc, k8, v8, table, start, klen,
                                       k_scale=ks, v_scale=vs)
        for entry in (ops.paged_prefill_attention,
                      ops.paged_verify_attention):
            got_c = entry(qc, k8, v8, table, start, klen,
                          k_scale=ks, v_scale=vs)
            np.testing.assert_allclose(got_c, want_c, atol=1e-6)


# --------------------------------------------------------------------------
# PagedKVCache: int8 pools, stats, swap round trips
# --------------------------------------------------------------------------


class TestQuantizedPagedCache:
    def test_pool_bytes_follow_dtype(self, paged_model):
        cfg, model, params = paged_model
        fp = PagedKVCache(model, n_lanes=2, max_len=64, n_pages=9,
                          page_size=8)
        q8 = PagedKVCache(model, n_lanes=2, max_len=64, n_pages=9,
                          page_size=8, kv_dtype="int8")
        sf, s8 = fp.stats(), q8.stats()
        assert sf["kv_dtype"] == "fp" and s8["kv_dtype"] == "int8"
        # int8 payload + fp32 per-row scales must be well under half the
        # fp pool at the same page count; capacity (pages) is unchanged
        assert s8["pool_bytes"] < sf["pool_bytes"] / 2
        assert s8["kv_bytes_per_token"] < sf["kv_bytes_per_token"] / 2
        assert s8["capacity_tokens"] == sf["capacity_tokens"]
        # stats derive from the actual pool leaves, not an assumed dtype
        assert sf["pool_bytes"] == sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(fp.caches))
        assert s8["pool_bytes"] == sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(q8.caches))

    def test_dense_int8_rejected(self, paged_model):
        cfg, model, params = paged_model
        from repro.serving.kvcache import make_kv_cache
        with pytest.raises(ValueError, match="paged"):
            make_kv_cache(model, "dense", n_lanes=1, max_len=32,
                          kv_dtype="int8")
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedKVCache(model, n_lanes=1, max_len=32, n_pages=5,
                         page_size=8, kv_dtype="int4")

    def test_int8_swap_roundtrip_bit_exact(self, paged_model):
        """int8 pools swap their native payload: the handle is int8
        pages + fp32 scales (compact) and the round trip is bit-exact."""
        cfg, model, params = paged_model
        kv = PagedKVCache(model, n_lanes=2, max_len=32, n_pages=9,
                          page_size=8, kv_dtype="int8")
        _, pre = model.prefill(params, jnp.asarray([[1, 2, 3, 4, 5]]),
                               max_len=8)
        assert kv.admit(0, pre, 5)
        pages = np.asarray(kv.table[0, :kv.n_blocks[0]])
        before = jax.tree.map(lambda pool: np.asarray(pool[:, pages]),
                              kv.caches)
        h = kv.swap_out(0)
        assert h.packed is None                 # native, not repacked
        leaves = jax.tree.leaves(h.chunks)
        assert {leaf.dtype for leaf in leaves} \
            == {np.dtype(np.int8), np.dtype(np.float32)}
        assert kv.swap_in(0, h)
        fresh = np.asarray(kv.table[0, :kv.n_blocks[0]])
        after = jax.tree.map(lambda pool: np.asarray(pool[:, fresh]),
                             kv.caches)
        jax.tree.map(np.testing.assert_array_equal, before, after)
        assert kv.stats()["swap_outs"] == kv.stats()["swap_ins"] == 1

    def test_int8_swap_handle_is_smaller(self, paged_model):
        """Same admitted tokens: the int8 handle's host bytes undercut
        the fp handle's (the dense-lane byte halving, paged form)."""
        cfg, model, params = paged_model
        _, pre = model.prefill(params, jnp.asarray([[1, 2, 3, 4, 5]]),
                               max_len=8)
        sizes = {}
        for kd in ("fp", "int8"):
            kv = PagedKVCache(model, n_lanes=1, max_len=32, n_pages=5,
                              page_size=8, kv_dtype=kd)
            assert kv.admit(0, pre, 5)
            sizes[kd] = kv.swap_out(0).host_bytes()
        assert sizes["int8"] < sizes["fp"] / 2

    def test_fp_swap_compress_packs_and_roundtrips(self, paged_model):
        """Opt-in fp swap compression: the handle is a PackedTree at
        ~1/4 the raw f32 bytes, and the round trip is int8-accurate
        (bounded error, not bit-exact — which is why it's opt-in)."""
        cfg, model, params = paged_model
        kv = PagedKVCache(model, n_lanes=1, max_len=32, n_pages=5,
                          page_size=8, swap_compress=True)
        _, pre = model.prefill(params, jnp.asarray([[1, 2, 3, 4, 5]]),
                               max_len=8)
        assert kv.admit(0, pre, 5)
        pages = np.asarray(kv.table[0, :kv.n_blocks[0]])
        before = jax.tree.map(lambda pool: np.asarray(pool[:, pages]),
                              kv.caches)
        raw = sum(leaf.nbytes for leaf in jax.tree.leaves(before))
        h = kv.swap_out(0)
        assert h.packed is not None and h.chunks is None
        assert h.host_bytes() < raw / 3
        assert kv.swap_in(0, h)
        fresh = np.asarray(kv.table[0, :kv.n_blocks[0]])
        after = jax.tree.map(lambda pool: np.asarray(pool[:, fresh]),
                             kv.caches)
        for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            bound = max(float(np.max(np.abs(b))) / 120.0, 1e-6)
            assert float(np.max(np.abs(a - b))) <= bound

    def test_int8_pool_ignores_swap_compress(self, paged_model):
        """swap_compress is an fp-pool knob: int8 payloads are already
        compact and must keep their lossless native swap."""
        cfg, model, params = paged_model
        kv = PagedKVCache(model, n_lanes=1, max_len=32, n_pages=5,
                          page_size=8, kv_dtype="int8",
                          swap_compress=True)
        assert kv.swap_compress is False


# --------------------------------------------------------------------------
# end-to-end: int8 engine tracks the fp engine's greedy outputs
# --------------------------------------------------------------------------


class TestQuantizedServing:
    def test_greedy_agreement_above_floor(self, paged_model):
        cfg, model, params = paged_model
        outs = {}
        for kd in ("fp", "int8"):
            eng = ServingEngine(model, params, n_lanes=2, max_len=48,
                                cache="paged", page_size=8, kv_dtype=kd)
            for rid in range(3):
                eng.submit(Request(rid=rid,
                                   prompt=[1 + rid, 2, 3, 4],
                                   max_new_tokens=6))
            done = eng.run(max_steps=60)
            assert len(done) == 3
            assert eng.kv.stats()["kv_dtype"] == kd
            outs[kd] = {r.rid: r.out_tokens for r in done}
        total = match = 0
        for rid, ref_toks in outs["fp"].items():
            got = outs["int8"][rid]
            total += max(len(ref_toks), len(got))
            match += sum(a == b for a, b in zip(ref_toks, got))
        # the KVPrecision quality floor, enforced end to end
        assert match / total >= 0.95

    def test_int8_with_chunked_prefill_and_timeslice(self, paged_model):
        """Quantized pages compose with the rest of the serving stack:
        chunked prefill scatter + preemption swaps, all int8."""
        cfg, model, params = paged_model
        eng = ServingEngine(model, params, n_lanes=1, max_len=48,
                            cache="paged", page_size=8, n_pages=13,
                            timeslice=2, prefill_chunk=4,
                            kv_dtype="int8")
        for rid in range(2):
            eng.submit(Request(rid=rid, prompt=list(range(1, 10)),
                               max_new_tokens=4))
        done = eng.run(max_steps=80)
        assert len(done) == 2
        assert all(len(r.out_tokens) == 4 for r in done)


# --------------------------------------------------------------------------
# KVPrecision dynamic-select regions
# --------------------------------------------------------------------------


class TestKVPrecisionRegion:
    def _tuner(self, workdir, make_variant, buckets=(512,)):
        from repro import at
        from repro.tuning import DecodeAutoTuner
        session = at.AutoTuner(str(workdir))
        tuner = DecodeAutoTuner(session, lambda bk: (lambda: {"bk": bk}),
                                buckets=(512,), block_ks=(256,))
        tuner.add_kv_precision(make_variant, buckets=buckets,
                               block_ks=(16,))
        return session, tuner

    def test_agreement_guard_blocks_fast_int8(self, tmp_path):
        """A 10x-faster int8 candidate below the agreement floor must
        lose to the slower fp candidate — latency never outvotes the
        quality guard."""
        def make_variant(bucket, kv_dtype, block_k):
            def fn():
                fast = kv_dtype == "int8"
                return {"kv_dtype": kv_dtype, "block_k": block_k,
                        "time_per_token": 0.001 if fast else 0.01,
                        "agreement": 0.5 if fast else 1.0}
            return fn

        _, tuner = self._tuner(tmp_path, make_variant)
        while not tuner.kv_precision_committed(512):
            tuner.kv_precision(512)
        assert tuner.committed_kv_precision_params()[512] \
            == {"kv_dtype": "fp", "block_k": 16}
        assert tuner.resolve_kv_dtype() == "fp"

    def test_fast_agreeing_int8_wins(self, tmp_path):
        def make_variant(bucket, kv_dtype, block_k):
            def fn():
                fast = kv_dtype == "int8"
                return {"kv_dtype": kv_dtype, "block_k": block_k,
                        "time_per_token": 0.001 if fast else 0.01,
                        "agreement": 1.0 if fast else 1.0}
            return fn

        _, tuner = self._tuner(tmp_path, make_variant)
        while not tuner.kv_precision_committed(512):
            tuner.kv_precision(512)
        assert tuner.committed_kv_precision_params()[512]["kv_dtype"] \
            == "int8"
        assert tuner.resolve_kv_dtype() == "int8"

    def test_resolve_majority_and_tie_break(self, tmp_path):
        """Per-bucket winners collapse by majority vote; a tie breaks
        toward int8 (capacity is the point); no commits -> default."""
        def make_variant(bucket, kv_dtype, block_k):
            def fn():
                # fp wins bucket 128, int8 wins bucket 512
                wins = (kv_dtype == "fp") == (bucket == 128)
                return {"kv_dtype": kv_dtype, "block_k": block_k,
                        "time_per_token": 0.001 if wins else 0.01,
                        "agreement": 1.0}
            return fn

        _, tuner = self._tuner(tmp_path, make_variant, buckets=(128, 512))
        assert tuner.resolve_kv_dtype() == "fp"       # nothing committed
        assert tuner.resolve_kv_dtype(default="int8") == "int8"
        for b in (128, 512):
            while not tuner.kv_precision_committed(b):
                tuner.kv_precision(b)
        params = tuner.committed_kv_precision_params()
        assert params[128]["kv_dtype"] == "fp"
        assert params[512]["kv_dtype"] == "int8"
        assert tuner.resolve_kv_dtype() == "int8"     # 1-1 tie -> int8

    def test_warm_restart_zero_tuning(self, tmp_path):
        """Satellite acceptance: a second session on the same workdir
        starts with every KVPrecision region committed and performs zero
        tuning-executor invocations."""
        from repro import at
        from repro.tuning import DecodeAutoTuner

        def make_variant(bucket, kv_dtype, block_k):
            def fn():
                return {"kv_dtype": kv_dtype, "block_k": block_k,
                        "time_per_token": 0.01 if kv_dtype == "fp"
                        else 0.002,
                        "agreement": 1.0}
            return fn

        def build(workdir):
            session = at.AutoTuner(str(workdir))
            tuner = DecodeAutoTuner(session,
                                    lambda bk: (lambda: {"bk": bk}),
                                    buckets=(512,), block_ks=(256,))
            tuner.add_kv_precision(make_variant, buckets=(128, 512),
                                   block_ks=(16,))
            return session, tuner

        _, t1 = build(tmp_path)
        for b in (128, 512):
            while not t1.kv_precision_committed(b):
                t1.kv_precision(b)
        assert all(v is not None
                   for v in t1.committed_kv_precision().values())

        s2, t2 = build(tmp_path)            # fresh process, same workdir
        assert t2.committed_kv_precision() == t1.committed_kv_precision()
        assert t2.resolve_kv_dtype() == "int8"
        assert s2.executor_calls == 0
