"""GQA attention: train/prefill path, decode path with KV cache, cross-attn.

Modes:
* ``full(params, x, cfg)`` — training / prefill over a whole sequence
  (flash kernel on TPU, jnp oracle on CPU), causal with optional sliding
  window; returns attention output and (optionally) the KV cache.
* ``decode(params, x, cache, pos, cfg)`` — one new token against the cache
  (flash-decode kernel on TPU).  Sliding-window archs use a ring-buffer
  cache of O(window) memory, which is what makes ``long_500k`` runnable.
* ``cross_full`` / ``cross_decode`` — encoder-decoder cross attention
  (whisper): KV computed once from encoder states.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..distributed.compression import quantize_int8_rows
from ..kernels import ops
from .layers import apply_rope, dense_init
from .sharding_ctx import constrain


class AttnConfig(NamedTuple):
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    rope_theta: float = 10000.0
    window: int | None = None
    causal: bool = True
    use_rope: bool = True
    qkv_bias: bool = False


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * cfg.d_head, dtype),
        "wk": dense_init(ks[1], cfg.d_model,
                         cfg.n_kv_heads * cfg.d_head, dtype),
        "wv": dense_init(ks[2], cfg.d_model,
                         cfg.n_kv_heads * cfg.d_head, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * cfg.d_head, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * cfg.d_head,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * cfg.d_head,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * cfg.d_head,), dtype)
    return p


def _project_qkv(p: dict, x: jax.Array, cfg: AttnConfig, positions):
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.d_head).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.d_head).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.d_head).transpose(0, 2, 1, 3)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def full(p: dict, x: jax.Array, cfg: AttnConfig,
         positions: jax.Array | None = None, return_cache: bool = False,
         mesh=None):
    """Whole-sequence attention.  x: (B, S, d).  ``mesh`` routes long
    causal sequences through the ring sequence-parallel tail (see
    :func:`repro.kernels.ops.attention`)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = constrain(q, "heads")
    k = constrain(k, "kv_heads")
    v = constrain(v, "kv_heads")
    out = ops.attention(q, k, v, causal=cfg.causal, window=cfg.window,
                        mesh=mesh)
    out = constrain(out, "heads")
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.d_head)
    out = out @ p["wo"]
    if return_cache:
        return out, (k, v)
    return out


def decode(p: dict, x: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
           pos: jax.Array, cfg: AttnConfig):
    """One-token decode.  x: (B, 1, d); caches (B, Hkv, W, Dh); ``pos`` (B,)
    is the absolute position of the new token.  Returns (out, new_k, new_v).

    With a sliding window the cache is a ring buffer indexed ``pos % W`` —
    RoPE is applied at absolute positions before caching, so softmax over an
    unordered window is exact.
    """
    b, one, _ = x.shape
    w = cache_k.shape[2]
    q, k, v = _project_qkv(p, x, cfg, pos[:, None])
    slot = pos % w if cfg.window is not None else pos
    idx = slot[:, None, None, None]
    bidx = jnp.arange(b)[:, None, None, None]
    hidx = jnp.arange(cfg.n_kv_heads)[None, :, None, None]
    didx = jnp.arange(cfg.d_head)[None, None, None, :]
    cache_k = cache_k.at[bidx, hidx, idx, didx].set(
        k.transpose(0, 1, 2, 3)[:, :, :1, :].astype(cache_k.dtype))
    cache_v = cache_v.at[bidx, hidx, idx, didx].set(
        v[:, :, :1, :].astype(cache_v.dtype))
    kv_len = jnp.minimum(pos + 1, w).astype(jnp.int32)
    out = ops.decode_attention(q, cache_k, cache_v, kv_len)
    out = out.transpose(0, 2, 1, 3).reshape(b, one, cfg.n_heads * cfg.d_head)
    return out @ p["wo"], cache_k, cache_v


def paged_decode(p: dict, x: jax.Array, k_pool: jax.Array,
                 v_pool: jax.Array, page_table: jax.Array,
                 pos: jax.Array, cfg: AttnConfig, scales=None, mesh=None):
    """One-token decode against a paged KV cache.

    x: (B, 1, d); pools (P, Hkv, psz, Dh) are shared by every sequence,
    ``page_table`` (B, nblk) maps logical KV blocks to physical pages (the
    allocator guarantees pages are lane-exclusive, so the scatter below
    cannot race between lanes).  The new token's KV lands in page
    ``table[b, pos // psz]`` at slot ``pos % psz``.  Sliding-window archs
    are not supported on this path (their ring buffer is already O(W)).

    ``scales``: for int8 pools, ``(k_scale, v_scale)`` fp32 arrays of
    shape (P, Hkv, psz) — one scale per (page, head, slot) row.  The new
    token's KV is quantized on the way in and attention dequantizes
    in-kernel.  Returns ``(out, k_pool, v_pool, scales)``; ``scales`` is
    None on the fp path.

    ``mesh``: a mesh with a multi-device ``model`` axis runs the
    attention op head-sharded (the op's output is gathered back to
    replicated before the output projection, so results stay bit
    identical to the unsharded path — see
    :func:`repro.kernels.ops.paged_decode`).
    """
    assert cfg.window is None, "paged decode does not support SWA archs"
    b, one, _ = x.shape
    psz = k_pool.shape[2]
    q, k, v = _project_qkv(p, x, cfg, pos[:, None])
    with jax.named_scope("kv_write"):
        phys = jnp.take_along_axis(page_table, (pos // psz)[:, None],
                                   axis=1)[:, 0]                  # (B,)
        slot = pos % psz
        pidx = phys[:, None, None, None]
        hidx = jnp.arange(cfg.n_kv_heads)[None, :, None, None]
        sidx = slot[:, None, None, None]
        didx = jnp.arange(cfg.d_head)[None, None, None, :]
        k_scale = v_scale = None
        if scales is not None:
            k_scale, v_scale = scales
            kq, ks = quantize_int8_rows(k[:, :, :1, :])   # ks: (B, Hkv, 1)
            vq, vs = quantize_int8_rows(v[:, :, :1, :])
            k_pool = k_pool.at[pidx, hidx, sidx, didx].set(kq)
            v_pool = v_pool.at[pidx, hidx, sidx, didx].set(vq)
            sp = phys[:, None, None]
            sh = jnp.arange(cfg.n_kv_heads)[None, :, None]
            ss = slot[:, None, None]
            k_scale = k_scale.at[sp, sh, ss].set(ks)
            v_scale = v_scale.at[sp, sh, ss].set(vs)
            scales = (k_scale, v_scale)
        else:
            k_pool = k_pool.at[pidx, hidx, sidx, didx].set(
                k[:, :, :1, :].astype(k_pool.dtype))
            v_pool = v_pool.at[pidx, hidx, sidx, didx].set(
                v[:, :, :1, :].astype(v_pool.dtype))
    kv_len = (pos + 1).astype(jnp.int32)
    pools = ops.PagedPools(k_pool, v_pool, k_scale, v_scale)
    out = ops.paged_decode(q, pools, page_table, kv_len, mesh=mesh)
    out = out.transpose(0, 2, 1, 3).reshape(b, one, cfg.n_heads * cfg.d_head)
    return out @ p["wo"], k_pool, v_pool, scales


def paged_prefill(p: dict, x: jax.Array, k_pool: jax.Array,
                  v_pool: jax.Array, page_table: jax.Array,
                  start: jax.Array, kv_len: jax.Array, cfg: AttnConfig,
                  scales=None, mesh=None):
    """One prompt *chunk* against a paged KV cache.

    x: (B, C, d) — chunk tokens whose first token sits at absolute
    position ``start[b]``; pools (P, Hkv, psz, Dh); ``page_table``
    (B, nblk); ``kv_len`` (B,) = ``start + valid_chunk_len``.  RoPE runs
    at absolute positions, the chunk's KV is scattered into its pages
    (padded tail positions — ``pos >= kv_len`` — are redirected to the
    null page 0 so ragged chunks can never corrupt live pages), then
    attention runs over the committed prefix plus the chunk's causal
    triangle.  Returns (out, k_pool, v_pool, scales) — ``scales`` is the
    updated ``(k_scale, v_scale)`` pair for int8 pools, None for fp.
    """
    q, k_pool, v_pool, scales = _paged_chunk_scatter(
        p, x, k_pool, v_pool, page_table, start, kv_len, cfg, scales)
    k_scale, v_scale = scales if scales is not None else (None, None)
    pools = ops.PagedPools(k_pool, v_pool, k_scale, v_scale)
    out = ops.paged_prefill(q, pools, page_table, start, kv_len, mesh=mesh)
    b, c, _ = x.shape
    out = out.transpose(0, 2, 1, 3).reshape(b, c, cfg.n_heads * cfg.d_head)
    return out @ p["wo"], k_pool, v_pool, scales


def paged_verify(p: dict, x: jax.Array, k_pool: jax.Array,
                 v_pool: jax.Array, page_table: jax.Array,
                 start: jax.Array, kv_len: jax.Array, cfg: AttnConfig,
                 scales=None, mesh=None):
    """Speculative-verify attention: one *candidate* chunk against a paged
    KV cache.

    Identical math to :func:`paged_prefill` — the chunk here is
    ``[last committed token, draft_1 .. draft_k]`` rather than prompt
    tokens, causal at absolute positions over the committed prefix plus
    the chunk's own triangle — but dispatched through
    :func:`~repro.kernels.ops.paged_verify_attention`, whose tile space is
    tuned separately (verify chunks are k+1 tokens wide, not a prefill
    chunk).  Rejected drafts' KV lands in the pages and is rolled back by
    the cache layer (``truncate_to``); padded rows (``pos >= kv_len``)
    route to the null page as in prefill.  Returns
    (out, k_pool, v_pool, scales).
    """
    q, k_pool, v_pool, scales = _paged_chunk_scatter(
        p, x, k_pool, v_pool, page_table, start, kv_len, cfg, scales)
    k_scale, v_scale = scales if scales is not None else (None, None)
    pools = ops.PagedPools(k_pool, v_pool, k_scale, v_scale)
    out = ops.paged_verify(q, pools, page_table, start, kv_len, mesh=mesh)
    b, c, _ = x.shape
    out = out.transpose(0, 2, 1, 3).reshape(b, c, cfg.n_heads * cfg.d_head)
    return out @ p["wo"], k_pool, v_pool, scales


def _paged_chunk_scatter(p: dict, x: jax.Array, k_pool: jax.Array,
                         v_pool: jax.Array, page_table: jax.Array,
                         start: jax.Array, kv_len: jax.Array,
                         cfg: AttnConfig, scales=None):
    """Project a chunk's QKV at absolute positions and scatter its KV into
    the pages (write-before-read contract shared by prefill and verify).
    Padded tail positions — ``pos >= kv_len`` — are redirected to the
    null page 0 so ragged chunks can never corrupt live pages.  For int8
    pools (``scales`` given) the chunk's KV is quantized per row on the
    way in and the matching scale rows are scattered alongside."""
    assert cfg.window is None, "paged chunk attention does not support SWA"
    b, c, _ = x.shape
    psz = k_pool.shape[2]
    positions = start[:, None] + jnp.arange(c)[None, :]       # (B, C)
    q, k, v = _project_qkv(p, x, cfg, positions)
    with jax.named_scope("kv_write"):
        phys = jnp.take_along_axis(page_table, positions // psz, axis=1)
        # null-page sink
        phys = jnp.where(positions < kv_len[:, None], phys, 0)
        slot = positions % psz
        pidx = phys[:, None, :, None]                         # (B, 1, C, 1)
        hidx = jnp.arange(cfg.n_kv_heads)[None, :, None, None]
        sidx = slot[:, None, :, None]
        didx = jnp.arange(cfg.d_head)[None, None, None, :]
        if scales is not None:
            k_scale, v_scale = scales
            kq, ks = quantize_int8_rows(k)                # ks: (B, Hkv, C)
            vq, vs = quantize_int8_rows(v)
            k_pool = k_pool.at[pidx, hidx, sidx, didx].set(kq)
            v_pool = v_pool.at[pidx, hidx, sidx, didx].set(vq)
            sp = phys[:, None, :]                             # (B, 1, C)
            sh = jnp.arange(cfg.n_kv_heads)[None, :, None]
            ss = slot[:, None, :]
            k_scale = k_scale.at[sp, sh, ss].set(ks)
            v_scale = v_scale.at[sp, sh, ss].set(vs)
            scales = (k_scale, v_scale)
        else:
            k_pool = k_pool.at[pidx, hidx, sidx, didx].set(
                k.astype(k_pool.dtype))
            v_pool = v_pool.at[pidx, hidx, sidx, didx].set(
                v.astype(v_pool.dtype))
    return q, k_pool, v_pool, scales


def init_paged_pool(n_pages: int, cfg: AttnConfig, page_size: int,
                    dtype=jnp.bfloat16):
    """Physical page pool for one layer: (P, Hkv, psz, Dh) k and v."""
    shape = (n_pages, cfg.n_kv_heads, page_size, cfg.d_head)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_paged_scales(n_pages: int, cfg: AttnConfig, page_size: int):
    """Per-row fp32 scales for an int8 page pool: (P, Hkv, psz) k and v.
    Zero scales dequantize untouched rows to exactly 0.0, matching the
    zero-initialized fp pool."""
    shape = (n_pages, cfg.n_kv_heads, page_size)
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def init_cross_attention(key, cfg: AttnConfig, dtype=jnp.float32) -> dict:
    return init_attention(key, cfg, dtype)


def cross_kv(p: dict, enc: jax.Array, cfg: AttnConfig):
    """Precompute cross-attention KV from encoder states (B, Se, d)."""
    b, se, _ = enc.shape
    k = (enc @ p["wk"]).reshape(b, se, cfg.n_kv_heads,
                                cfg.d_head).transpose(0, 2, 1, 3)
    v = (enc @ p["wv"]).reshape(b, se, cfg.n_kv_heads,
                                cfg.d_head).transpose(0, 2, 1, 3)
    return k, v


def cross_full(p: dict, x: jax.Array, k: jax.Array, v: jax.Array,
               cfg: AttnConfig):
    """Cross attention (no RoPE, not causal).  x: (B, Sd, d)."""
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads,
                              cfg.d_head).transpose(0, 2, 1, 3)
    out = ops.attention(q, k, v, causal=False)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.d_head)
    return out @ p["wo"]


def cross_decode(p: dict, x: jax.Array, k: jax.Array, v: jax.Array,
                 cfg: AttnConfig):
    b, one, _ = x.shape
    q = (x @ p["wq"]).reshape(b, one, cfg.n_heads,
                              cfg.d_head).transpose(0, 2, 1, 3)
    out = ops.decode_attention(q, k, v)
    out = out.transpose(0, 2, 1, 3).reshape(b, one, cfg.n_heads * cfg.d_head)
    return out @ p["wo"]


def init_cache(batch: int, cfg: AttnConfig, max_len: int,
               dtype=jnp.bfloat16):
    """KV cache for one layer; O(window) when sliding-window."""
    w = min(max_len, cfg.window) if cfg.window is not None else max_len
    shape = (batch, cfg.n_kv_heads, w, cfg.d_head)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
