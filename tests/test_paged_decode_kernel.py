"""The paged decode kernel's multi-page tiles against the gather oracle.

One grid step of ``flash_paged_decode`` takes one lane, all its KV heads
and a block of ``block_k`` tokens, a whole number of pages; only the
lane's live pages are copied.  These interpret-mode cases cover the tile
ladder, contexts of one token, mid-page, on a block edge and the whole
table, a table whose width is not a multiple of the tile, lanes on the
null page (as the engine sends mid-prefill lanes), G=8 and G=1, fp and
int8 pools, one walk and two splits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import quantize_int8_rows
from repro.kernels import ref
from repro.kernels.flash_attention import (PAGED_DECODE_VMEM_BUDGET,
                                           flash_paged_decode,
                                           flash_paged_decode_quant,
                                           paged_decode_block_k,
                                           paged_decode_page_bytes)

PSZ, NBLK, D, N_PAGES = 8, 5, 16, 28


def _case(group, hkv, tile):
    """Five lanes: one on the null page with one token, then one token,
    mid-page, exactly one tile and the whole (5-page) table."""
    h = group * hkv
    keys = jax.random.split(jax.random.PRNGKey(group * 10 + hkv), 3)
    q = jax.random.normal(keys[0], (5, h, 1, D)) * 0.3
    kp = jax.random.normal(keys[1], (N_PAGES, hkv, PSZ, D)) * 0.3
    vp = jax.random.normal(keys[2], (N_PAGES, hkv, PSZ, D)) * 0.3
    pages = np.random.default_rng(0).permutation(np.arange(1, N_PAGES))
    table = pages[:5 * NBLK].reshape(5, NBLK).astype(np.int32)
    table[0] = 0                       # the null page, as mid-prefill lanes
    kv_len = jnp.asarray([1, 1, 13, tile, NBLK * PSZ], jnp.int32)
    return q, kp, vp, jnp.asarray(table), kv_len


@pytest.mark.parametrize("pool,group,hkv,num_splits,block_k", [
    ("fp", 8, 4, 1, PSZ),              # the ladder: 1, 2, 3 pages and the
    ("fp", 8, 4, 1, 2 * PSZ),          # default, which is the whole table
    ("fp", 8, 4, 1, 3 * PSZ),          # here; 5 pages is no multiple of
    ("fp", 8, 4, 1, None),             # 2 or 3
    ("fp", 8, 4, 2, 2 * PSZ),
    ("fp", 8, 4, 2, 3 * PSZ),
    ("fp", 1, 4, 1, 2 * PSZ),
    ("fp", 1, 4, 2, 3 * PSZ),
    ("int8", 8, 4, 1, 2 * PSZ),
    ("int8", 8, 4, 2, 3 * PSZ),
    ("int8", 1, 4, 1, 3 * PSZ),
    ("int8", 1, 4, 2, 2 * PSZ),
])
def test_tiles_match_oracle(pool, group, hkv, num_splits, block_k):
    tile = paged_decode_block_k(
        block_k, page_size=PSZ, table_pages=NBLK,
        page_bytes=paged_decode_page_bytes(hkv, PSZ, D, 4, pool == "int8"))
    q, kp, vp, table, kv_len = _case(group, hkv, tile)
    if pool == "fp":
        want = ref.paged_decode_ref(q, kp, vp, table, kv_len)
        got = flash_paged_decode(q, kp, vp, table, kv_len, block_k=block_k,
                                 num_splits=num_splits, interpret=True)
    else:
        k8, ks = quantize_int8_rows(kp)
        v8, vs = quantize_int8_rows(vp)
        want = ref.paged_decode_ref(q, k8, v8, table, kv_len,
                                    k_scale=ks, v_scale=vs)
        got = flash_paged_decode_quant(q, k8, v8, ks, vs, table, kv_len,
                                       block_k=block_k,
                                       num_splits=num_splits,
                                       interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_bf16_pool_matches_oracle():
    """bf16 q and K go to the MXU as they are, with f32 accumulation."""
    q, kp, vp, table, kv_len = _case(8, 4, 2 * PSZ)
    q, kp, vp = (a.astype(jnp.bfloat16) for a in (q, kp, vp))
    want = ref.paged_decode_ref(*(a.astype(jnp.float32) for a in (q, kp, vp)),
                                table, kv_len)
    got = flash_paged_decode(q, kp, vp, table, kv_len, block_k=2 * PSZ,
                             interpret=True)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=1e-2)


@pytest.mark.parametrize("kv_heads", [4, 32])
def test_default_tile_fills_the_vmem_budget(kv_heads):
    """The default tile follows from the bytes of one page of all heads:
    yi-6b's page (4 KV heads, 16 KiB of K) and deepseek-7b's (32 heads,
    128 KiB) get different tiles from the same rule, each the most pages
    whose two K and V slots fit the budget, capped by the table."""
    page_bytes = paged_decode_page_bytes(kv_heads, 16, 128, 2)
    assert page_bytes == 2 * kv_heads * 16 * 128 * 2
    tile = paged_decode_block_k(None, page_size=16, table_pages=81,
                                page_bytes=page_bytes)
    pages = tile // 16
    assert tile % 16 == 0 and 1 <= pages <= 81
    assert 2 * pages * page_bytes <= PAGED_DECODE_VMEM_BUDGET
    assert pages == 81 or 2 * (pages + 1) * page_bytes \
        > PAGED_DECODE_VMEM_BUDGET
    # the table's width caps it
    assert paged_decode_block_k(None, page_size=16, table_pages=2,
                                page_bytes=page_bytes) <= 32


def test_default_tile_differs_by_page_bytes():
    yi = paged_decode_block_k(
        None, page_size=16, table_pages=81,
        page_bytes=paged_decode_page_bytes(4, 16, 128, 2))
    deepseek = paged_decode_block_k(
        None, page_size=16, table_pages=81,
        page_bytes=paged_decode_page_bytes(32, 16, 128, 2))
    assert yi == 8 * deepseek
    # int8 pages bring f32 scales beside the payload
    assert paged_decode_page_bytes(4, 16, 128, 1, quantized=True) \
        == 2 * 4 * 16 * 128 * 5


@pytest.mark.parametrize("block_k,want", [(16, 16), (40, 48), (1, 16),
                                          (512, 512), (2048, 81 * 16)])
def test_requested_tile_is_whole_pages_within_the_table(block_k, want):
    def resolve():
        return paged_decode_block_k(block_k, page_size=16, table_pages=81,
                                    page_bytes=1)
    if block_k % 16:
        with pytest.warns(RuntimeWarning, match="block_k=%d" % block_k):
            assert resolve() == want
    else:
        assert resolve() == want
