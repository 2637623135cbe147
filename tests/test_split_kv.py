"""Flash-Decoding split-KV: combine math, kernels, tuner space, e2e.

The two-phase contract under test: phase 1 walks ``num_splits``
independent page-table segments in parallel, each emitting a partial
(m, l, acc) softmax state; phase 2 merges with the max-shift rescale and
normalizes.  ``num_splits=1`` IS the sequential kernel (bit-identical),
and every ``num_splits > 1`` must agree with the gather oracle within
fp32 rounding.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import at
from repro.distributed.compression import quantize_int8_rows
from repro.kernels import ops, ref
from repro.kernels.flash_attention import (flash_paged_decode,
                                           flash_paged_decode_quant,
                                           flash_paged_prefill,
                                           flash_paged_prefill_quant)


@pytest.fixture(autouse=True)
def _clean_published():
    at.clear_published()
    yield
    at.clear_published()


# --------------------------------------------------------------------------
# partial-softmax combine: property tests (hypothesis)
# --------------------------------------------------------------------------


def _segment_states(scores: np.ndarray, values: np.ndarray):
    """The (m, l, acc) triple one split emits for its score slice —
    an empty slice carries the kernel's skip convention (NEG_INF, 0, 0)."""
    if scores.shape[-1] == 0:
        d = values.shape[-1]
        return (np.full(scores.shape[:-1], -1e30, np.float32),
                np.zeros(scores.shape[:-1], np.float32),
                np.zeros((*scores.shape[:-1], d), np.float32))
    m = scores.max(axis=-1)
    p = np.exp(scores - m[..., None])
    return (m.astype(np.float32), p.sum(axis=-1).astype(np.float32),
            (p @ values).astype(np.float32))


def _stack_states(states):
    """[(m, l, acc), ...] -> the (ns, rows[, d]) arrays combine expects."""
    return (jnp.stack([s[0] for s in states]),
            jnp.stack([s[1] for s in states]),
            jnp.stack([s[2] for s in states]))


def _finalized(states):
    m, l, acc = _stack_states(states)
    _, l_star, acc_star = ref.combine_split_states(m, l, acc)
    return np.asarray(ref.finalize_split_states(l_star, acc_star))


def _combine_case(seed: int, n: int, rows: int = 2, d: int = 4):
    rng = np.random.default_rng(seed)
    scores = rng.normal(size=(rows, n)).astype(np.float32) * 3.0
    values = rng.normal(size=(n, d)).astype(np.float32)
    return scores, values


def _direct_softmax(scores: np.ndarray, values: np.ndarray) -> np.ndarray:
    p = np.exp(scores - scores.max(axis=-1, keepdims=True))
    return (p / p.sum(axis=-1, keepdims=True)) @ values


def _check_segmentation(scores, values, bounds):
    """ANY segmentation of the key axis (including empty segments)
    combines back to the plain softmax-weighted average."""
    states = [_segment_states(scores[:, a:b], values[a:b])
              for a, b in zip(bounds, bounds[1:])]
    np.testing.assert_allclose(_finalized(states),
                               _direct_softmax(scores, values),
                               rtol=1e-5, atol=1e-6)


def _check_order_invariant(scores, values, perm_seed):
    """Shuffling the split axis does not change the combined output (the
    merge is a max + weighted sums, all symmetric)."""
    half = scores.shape[-1] // 2
    states = [_segment_states(scores[:, :half], values[:half]),
              _segment_states(scores[:, half:], values[half:]),
              _segment_states(scores[:, :0], values[:0])]
    perm = np.random.default_rng(perm_seed).permutation(len(states))
    np.testing.assert_allclose(
        _finalized([states[i] for i in perm]), _finalized(states),
        rtol=1e-6, atol=1e-7)


def _check_associative(scores, values):
    """combine(combine(A, B), C) == combine(A, B, C) after the final
    normalize — merging is hierarchy-free, so a tree reduction and a
    flat reduction agree."""
    n = scores.shape[-1]
    a, b = n // 3, 2 * n // 3
    A = _segment_states(scores[:, :a], values[:a])
    B = _segment_states(scores[:, a:b], values[a:b])
    C = _segment_states(scores[:, b:], values[b:])
    ab = ref.combine_split_states(*_stack_states([A, B]))
    np.testing.assert_allclose(
        _finalized([tuple(np.asarray(x) for x in ab), C]),
        _finalized([A, B, C]), rtol=1e-6, atol=1e-7)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                              # container without dev deps
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    class TestCombineProperties:
        """Hypothesis property tests over :func:`ref.combine_split_states`
        (the exact math both combine implementations share)."""

        common = settings(max_examples=30, deadline=None)

        @common
        @given(seed=st.integers(0, 10_000), n=st.integers(1, 24),
               ns=st.integers(1, 6), data=st.data())
        def test_segmentation_matches_unsegmented(self, seed, n, ns, data):
            scores, values = _combine_case(seed, n)
            cuts = sorted(data.draw(st.lists(
                st.integers(0, n), min_size=ns - 1, max_size=ns - 1)))
            _check_segmentation(scores, values, [0, *cuts, n])

        @common
        @given(seed=st.integers(0, 10_000), n=st.integers(2, 24),
               perm_seed=st.integers(0, 10_000))
        def test_order_invariant(self, seed, n, perm_seed):
            scores, values = _combine_case(seed, n)
            _check_order_invariant(scores, values, perm_seed)

        @common
        @given(seed=st.integers(0, 10_000), n=st.integers(3, 24))
        def test_associative(self, seed, n):
            scores, values = _combine_case(seed, n)
            _check_associative(scores, values)


class TestCombineDeterministic:
    """Pinned-seed coverage of the same combine properties, so the math
    stays tested on containers without hypothesis."""

    @pytest.mark.parametrize("seed,n,bounds", [
        (0, 16, [0, 4, 8, 16]),
        (1, 16, [0, 0, 16, 16]),      # leading + trailing empty segments
        (2, 7, [0, 2, 3, 5, 7]),      # ragged odd cuts
        (3, 1, [0, 1]),               # single key, single segment
    ])
    def test_segmentation_matches_unsegmented(self, seed, n, bounds):
        scores, values = _combine_case(seed, n)
        _check_segmentation(scores, values, bounds)

    @pytest.mark.parametrize("seed", [0, 7, 42])
    def test_order_invariant(self, seed):
        scores, values = _combine_case(seed, 12)
        _check_order_invariant(scores, values, seed + 1)

    @pytest.mark.parametrize("seed", [0, 7, 42])
    def test_associative(self, seed):
        scores, values = _combine_case(seed, 15)
        _check_associative(scores, values)

    def test_all_empty_is_zero(self):
        """Every split empty -> the l* == 0 guard yields exact zeros
        (the sequential kernel's all-masked convention)."""
        states = [_segment_states(np.zeros((2, 0), np.float32),
                                  np.zeros((0, 4), np.float32))] * 3
        np.testing.assert_array_equal(_finalized(states),
                                      np.zeros((2, 4), np.float32))


# --------------------------------------------------------------------------
# split-KV decode kernel vs oracles
# --------------------------------------------------------------------------


def _paged_case(b=3, h=4, hkv=2, d=16, psz=8, p=10, nblk=4, qscale=0.3):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, 1, d)) * qscale
    kp = jax.random.normal(jax.random.PRNGKey(1), (p, hkv, psz, d)) * 0.3
    vp = jax.random.normal(jax.random.PRNGKey(2), (p, hkv, psz, d)) * 0.3
    # entries past kv_len route to the null page (page 0) — they must be
    # masked out, not attended
    table = jnp.asarray([[3, 7, 0, 0],
                         [5, 2, 6, 9],
                         [1, 4, 0, 0]], jnp.int32)[:b]
    # full-ish / ragged / shorter than one segment at every tested split
    kv_len = jnp.asarray([13, 26, 2], jnp.int32)[:b]
    return q, kp, vp, table, kv_len


class TestSplitDecodeKernel:
    @pytest.mark.parametrize("block_k", [None, 8, 24])
    @pytest.mark.parametrize("num_splits", [1, 2, 4, 8])
    def test_matches_oracles(self, num_splits, block_k):
        q, kp, vp, table, kv_len = _paged_case()
        want = ref.paged_decode_ref(q, kp, vp, table, kv_len)
        got = flash_paged_decode(q, kp, vp, table, kv_len,
                                 block_k=block_k, num_splits=num_splits,
                                 interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)
        # and the structural split-aware oracle (per-segment states)
        want_split = ref.paged_decode_split_ref(q, kp, vp, table, kv_len,
                                                num_splits)
        np.testing.assert_allclose(got, want_split, atol=1e-5)

    def test_ns1_bit_identical_to_sequential(self):
        """num_splits=1 is the legacy spelling: the exact same kernel,
        bitwise, as calling without the parameter."""
        q, kp, vp, table, kv_len = _paged_case()
        base = flash_paged_decode(q, kp, vp, table, kv_len, interpret=True)
        ns1 = flash_paged_decode(q, kp, vp, table, kv_len, num_splits=1,
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(ns1))

    def test_clamps_to_kv_walk(self):
        """num_splits beyond the number of KV steps clamps (never an
        empty grid) and still matches the oracle."""
        q, kp, vp, table, kv_len = _paged_case(b=2, nblk=4)
        want = ref.paged_decode_ref(q, kp, vp, table, kv_len)
        got = flash_paged_decode(q, kp, vp, table, kv_len, num_splits=64,
                                 interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)

    @pytest.mark.parametrize("num_splits", [1, 4])
    def test_zero_length_lane_outputs_zero(self, num_splits):
        """An idle lane (kv_len 0): every split is empty, the combine's
        l* == 0 guard must reproduce the sequential kernel's exact-zero
        output, not NaN."""
        q, kp, vp, table, _ = _paged_case(b=2)
        kv_len = jnp.asarray([0, 0], jnp.int32)
        got = flash_paged_decode(q, kp, vp, table, kv_len,
                                 num_splits=num_splits, interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.zeros_like(np.asarray(got)))

    @pytest.mark.parametrize("num_splits", [1, 2, 4])
    def test_quant_matches_oracles(self, num_splits):
        """int8 pools: dequant stays in-kernel next to the tile load, so
        the split path must agree with the quantized gather oracle."""
        q, kp, vp, table, kv_len = _paged_case()
        k8, ks = quantize_int8_rows(kp)
        v8, vs = quantize_int8_rows(vp)
        want = ref.paged_decode_ref(q, k8, v8, table, kv_len,
                                    k_scale=ks, v_scale=vs)
        got = flash_paged_decode_quant(q, k8, v8, ks, vs, table, kv_len,
                                       num_splits=num_splits,
                                       interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)
        np.testing.assert_allclose(
            got,
            ref.paged_decode_split_ref(q, k8, v8, table, kv_len,
                                       num_splits, k_scale=ks, v_scale=vs),
            atol=1e-5)

    def test_quant_ns1_bit_identical(self):
        q, kp, vp, table, kv_len = _paged_case()
        k8, ks = quantize_int8_rows(kp)
        v8, vs = quantize_int8_rows(vp)
        base = flash_paged_decode_quant(q, k8, v8, ks, vs, table, kv_len,
                                        interpret=True)
        ns1 = flash_paged_decode_quant(q, k8, v8, ks, vs, table, kv_len,
                                       num_splits=1, interpret=True)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(ns1))


# --------------------------------------------------------------------------
# split-KV prefill / verify (verify rides the prefill kernel)
# --------------------------------------------------------------------------


class TestSplitPrefillKernel:
    @staticmethod
    def _case():
        b, h, hkv, d, psz, p, c = 2, 4, 2, 16, 8, 10, 5
        q = jax.random.normal(jax.random.PRNGKey(0), (b, h, c, d)) * 0.3
        kp = jax.random.normal(jax.random.PRNGKey(1),
                               (p, hkv, psz, d)) * 0.3
        vp = jax.random.normal(jax.random.PRNGKey(2),
                               (p, hkv, psz, d)) * 0.3
        table = jnp.asarray([[3, 7, 1], [5, 0, 0]], jnp.int32)
        start = jnp.asarray([16, 3], jnp.int32)
        kv_len = jnp.asarray([21, 5], jnp.int32)
        return q, kp, vp, table, start, kv_len

    @pytest.mark.parametrize("num_splits", [2, 4, 8])
    def test_matches_oracle(self, num_splits):
        q, kp, vp, table, start, kv_len = self._case()
        want = ref.paged_prefill_ref(q, kp, vp, table, start, kv_len)
        got = flash_paged_prefill(q, kp, vp, table, start, kv_len,
                                  block_q=2, block_k=4,
                                  num_splits=num_splits, interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_ns1_bit_identical_to_sequential(self):
        q, kp, vp, table, start, kv_len = self._case()
        base = flash_paged_prefill(q, kp, vp, table, start, kv_len,
                                   block_q=2, interpret=True)
        ns1 = flash_paged_prefill(q, kp, vp, table, start, kv_len,
                                  block_q=2, num_splits=1, interpret=True)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(ns1))

    @pytest.mark.parametrize("num_splits", [2, 4])
    def test_quant_matches_oracle(self, num_splits):
        q, kp, vp, table, start, kv_len = self._case()
        k8, ks = quantize_int8_rows(kp)
        v8, vs = quantize_int8_rows(vp)
        want = ref.paged_prefill_ref(q, k8, v8, table, start, kv_len,
                                     k_scale=ks, v_scale=vs)
        got = flash_paged_prefill_quant(q, k8, v8, ks, vs, table, start,
                                        kv_len, block_q=2, block_k=4,
                                        num_splits=num_splits,
                                        interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_verify_dispatch_reads_published_splits(self):
        """paged_verify rides the prefill kernel: a published num_splits
        under its tuned key flows through the ops dispatch."""
        q, kp, vp, table, start, kv_len = self._case()
        pools = ops.PagedPools(kp, vp)
        want = ref.paged_prefill_ref(q, kp, vp, table, start, kv_len)
        at.publish("flash_paged_verify", num_splits=4)
        got = ops.paged_verify(q, pools, table, start, kv_len,
                               use_kernel=True)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_decode_dispatch_reads_published_splits(self):
        q, kp, vp, table, kv_len = _paged_case(b=2)
        pools = ops.PagedPools(kp, vp)
        want = ref.paged_decode_ref(q, kp, vp, table, kv_len)
        at.publish("flash_paged_decode", block_k=8, num_splits=4)
        got = ops.paged_decode(q, pools, table, kv_len, use_kernel=True)
        np.testing.assert_allclose(got, want, atol=1e-5)


# --------------------------------------------------------------------------
# block_k divisor hygiene (satellite 1)
# --------------------------------------------------------------------------


class TestBlockKResolution:
    def test_non_divisor_warns_and_falls_back(self):
        q, kp, vp, table, kv_len = _paged_case(b=2)
        want = flash_paged_decode(q, kp, vp, table, kv_len, interpret=True)
        with pytest.warns(RuntimeWarning,
                          match="flash_paged_decode.*block_k=3"):
            got = flash_paged_decode(q, kp, vp, table, kv_len, block_k=3,
                                     interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_divisor_block_k_does_not_warn(self):
        """Decode tiles are whole pages: a multiple of the page is taken
        as it is."""
        q, kp, vp, table, kv_len = _paged_case(b=2)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            flash_paged_decode(q, kp, vp, table, kv_len, block_k=16,
                               interpret=True)

    def test_divisor_block_ks_filter(self):
        from repro.tuning import divisor_block_ks
        # non-divisors dropped, order preserved, clamp + dedup
        assert divisor_block_ks(16, (3, 8, 16, 5)) == (8, 16)
        assert divisor_block_ks(16, (32, 8)) == (16, 8)   # clamp to page
        assert divisor_block_ks(16, (8, 8, 16)) == (8, 16)
        # nothing survives -> whole page fallback
        assert divisor_block_ks(16, (3, 5, 7)) == (16,)
        assert divisor_block_ks(8, ()) == (8,)

    def test_page_multiple_block_ks_filter(self):
        """The decode ladder keeps whole pages, clamped to the table."""
        from repro.tuning import page_multiple_block_ks
        from repro.tuning.dynamic import DEFAULT_BLOCK_KS
        assert page_multiple_block_ks(16, DEFAULT_BLOCK_KS, 81) \
            == (256, 512, 1024)
        # clamp to the table's width, then dedup
        assert page_multiple_block_ks(16, DEFAULT_BLOCK_KS, 20) == (256, 320)
        assert page_multiple_block_ks(16, DEFAULT_BLOCK_KS, 6) == (96,)
        # sub-page and other non-multiples dropped, order preserved
        assert page_multiple_block_ks(16, (8, 48, 3, 16, 48)) == (48, 16)
        # nothing survives -> one page
        assert page_multiple_block_ks(16, (3, 8), 81) == (16,)


# --------------------------------------------------------------------------
# tuner region growth: (block_k x page_size x num_splits)
# --------------------------------------------------------------------------


class TestTunerSplitAxis:
    def test_variant_order_keeps_legacy_prefix(self, tmp_path):
        """The ns=1 block leads and preserves the legacy variant order,
        so winner indices from a pre-split-KV DB name the same variants."""
        from repro.tuning import DecodeAutoTuner
        session = at.AutoTuner(str(tmp_path))
        built = []

        def make_decode(bk, ns):
            built.append((bk, ns))
            return lambda: {"bk": bk, "ns": ns}

        tuner = DecodeAutoTuner(session, make_decode, buckets=(512,),
                                block_ks=(256, 512), num_splits=(2, 4))
        assert tuner.param_names == ("block_k", "num_splits")
        assert tuner.variants == [(256, 1), (512, 1), (256, 2), (512, 2),
                                  (256, 4), (512, 4)]
        assert built == tuner.variants
        assert len(tuner.regions[512].subregions) == 6

    def test_forced_split_keeps_one_ladder_with_ns1_leading(self, tmp_path):
        from repro.tuning import DecodeAutoTuner
        session = at.AutoTuner(str(tmp_path))
        tuner = DecodeAutoTuner(session, lambda bk, ns: lambda: (bk, ns),
                                buckets=(512,), block_ks=(8, 16),
                                num_splits=(4,))
        assert tuner.variants == [(8, 1), (16, 1), (8, 4), (16, 4)]
        # forcing ns=1 dedupes to exactly the legacy variant count
        t1 = DecodeAutoTuner(at.AutoTuner(str(tmp_path)),
                             lambda bk, ns: lambda: (bk, ns),
                             buckets=(2048,), block_ks=(8, 16),
                             num_splits=(1,))
        assert t1.variants == [(8, 1), (16, 1)]

    def test_commits_over_grown_space(self, tmp_path):
        from repro.tuning import DecodeAutoTuner
        session = at.AutoTuner(str(tmp_path))
        tuner = DecodeAutoTuner(session, lambda bk, ns:
                                lambda: {"bk": bk, "ns": ns},
                                buckets=(512,), block_ks=(8,),
                                num_splits=(2,))
        for _ in range(len(tuner.variants)):
            tuner.decode(300)
        pp = tuner.committed_params()[512]
        assert pp["block_k"] == 8 and pp["num_splits"] in (1, 2)


class TestNumAltInvalidation:
    """OAT_NUMALT: a persisted winner index is only valid against the
    variant-space size that measured it."""

    @staticmethod
    def _mk(calls):
        def make(bk, *rest):
            def fn():
                calls.append((bk, *rest))
                return bk
            return fn
        return make

    def test_same_space_warm_loads(self, tmp_path):
        from repro.tuning import DecodeAutoTuner
        calls1: list = []
        s1 = at.AutoTuner(str(tmp_path))
        t1 = DecodeAutoTuner(s1, self._mk(calls1), buckets=(512,),
                             block_ks=(8,), num_splits=(2,))
        for _ in range(2):
            t1.decode(300)
        assert t1.committed()[512] is not None

        calls2: list = []
        s2 = at.AutoTuner(str(tmp_path))
        t2 = DecodeAutoTuner(s2, self._mk(calls2), buckets=(512,),
                             block_ks=(8,), num_splits=(2,))
        assert t2.committed()[512] == t1.committed()[512]
        assert s2.executor_calls == 0
        assert ("dynamic", "DecodeBucket_512") in s2.warm_hits

    def test_grown_space_re_measures(self, tmp_path):
        """A legacy (2-variant) winner must NOT warm-load into the grown
        (6-variant) region — the index would name a different variant."""
        from repro.tuning import DecodeAutoTuner
        s1 = at.AutoTuner(str(tmp_path))
        t1 = DecodeAutoTuner(s1, self._mk([]), buckets=(512,),
                             block_ks=(8, 16))
        for _ in range(2):
            t1.decode(300)
        assert t1.committed()[512] is not None
        rec = s1.records.lookup("dynamic", "DecodeBucket_512", {})
        assert rec.pp["OAT_NUMALT"] == 2

        s2 = at.AutoTuner(str(tmp_path))
        t2 = DecodeAutoTuner(s2, self._mk([]), buckets=(512,),
                             block_ks=(8, 16), num_splits=(2, 4))
        assert t2.committed()[512] is None          # cold: must re-measure
        assert ("dynamic", "DecodeBucket_512") not in s2.warm_hits
        for _ in range(len(t2.variants)):
            t2.decode(300)
        assert t2.committed()[512] is not None
        rec2 = s2.records.lookup("dynamic", "DecodeBucket_512", {})
        assert rec2.pp["OAT_NUMALT"] == 6

    def test_markerless_legacy_record_still_warm_loads(self, tmp_path):
        """Records written before the OAT_NUMALT stamp carry no marker;
        they keep warm-loading unchanged (same-sized spaces only ever
        existed when they were written)."""
        from repro.tuning import DecodeAutoTuner
        s0 = at.AutoTuner(str(tmp_path))
        s0.records.put("dynamic", "DecodeBucket_512", {},
                       {"DecodeBucket_512_SELECT": 1})
        s1 = at.AutoTuner(str(tmp_path))
        t1 = DecodeAutoTuner(s1, self._mk([]), buckets=(512,),
                             block_ks=(8, 16))
        assert t1.committed()[512] == 1
        assert ("dynamic", "DecodeBucket_512") in s1.warm_hits


# --------------------------------------------------------------------------
# e2e: greedy bit-identity, splits on vs off, through the engine
# --------------------------------------------------------------------------


class TestEndToEndSplits:
    def _serve(self, tmp_path, tag, **kw):
        from repro.launch.serve import serve
        (tmp_path / tag).mkdir(exist_ok=True)
        return serve(arch="yi-6b", cache="paged", page_size=8,
                     n_requests=2, n_lanes=1, max_len=48, prompt_len=8,
                     max_new=5, workdir=str(tmp_path / tag), **kw)

    def test_greedy_outputs_identical_across_split_degrees(self, tmp_path):
        """Forced num_splits=4, forced num_splits=1 and the default (no
        splits configured) must produce bit-identical greedy tokens —
        split-KV is a pure execution-schedule change."""
        base = self._serve(tmp_path, "base")
        at.clear_published()
        forced1 = self._serve(tmp_path, "ns1", num_splits=1)
        at.clear_published()
        forced4 = self._serve(tmp_path, "ns4", num_splits=4)
        assert base["outputs"] == forced1["outputs"] == forced4["outputs"]
        assert base["finished"] == 2
        assert forced4["config"]["num_splits"] == 4

    def test_autotuned_splits_match_forced_sequential(self, tmp_path):
        """The tuned ladder (1, 2, 4) measures every candidate yet emits
        the same greedy tokens as the forced-sequential run — candidate
        measurement must never leak into outputs."""
        tuned = self._serve(tmp_path, "auto", autotune=True)
        committed = tuned["committed_buckets"]
        assert any(pp is not None and "num_splits" in pp
                   for pp in committed.values())
        at.clear_published()
        forced1 = self._serve(tmp_path, "seq", num_splits=1)
        assert tuned["outputs"] == forced1["outputs"]
