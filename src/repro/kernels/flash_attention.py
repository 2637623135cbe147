"""Flash attention Pallas kernel (causal / GQA / sliding-window) + decode.

Online-softmax attention with explicit VMEM tiling, IO-aware in the
FlashAttention sense but re-blocked for the TPU memory hierarchy: the MXU
consumes (block_q x d_head) x (d_head x block_k) tiles; running max /
denominator live in VMEM scratch.

Performance parameters (install-time AT): ``block_q``, ``block_k``.
Layout parameters (before-execute-time AT): which attention path (this
kernel vs the jnp reference vs ring-SP) is selected per (arch x shape x
mesh) — see tuning/static.py.

The kernels:

* :func:`flash_attention` — self-attention over (B, H, S, D) with causal
  and/or sliding-window masking and GQA head mapping (kv_head = h // G).
* :func:`flash_decode` — one-token decode against a (B, Hkv, S, D) KV
  cache, blocked over S (FlashDecoding-style), fp32 LSE merge.
* :func:`flash_paged_decode` — one-token decode against a paged KV cache
  (scalar-prefetched page table, vLLM-style): each grid step takes a
  lane's KV heads over a block of whole pages, copied by hand, live
  pages only; fp and int8 pools share the body.
* :func:`flash_paged_prefill` — one prompt *chunk* against a paged KV
  cache: causal at absolute positions over the committed prefix plus the
  chunk's own triangle (chunked-prefill serving path).
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int | None,
                 block_q: int, block_k: int, n_k: int, k_valid: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    padded = k_valid != n_k * block_k

    def body():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or window is not None or padded:
            qi = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
            kj = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                mask &= qi >= kj
            if window is not None:
                mask &= (qi - kj) < window
            if padded:
                mask &= kj < k_valid
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                         # (bq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    if causal or window is not None:
        # skip fully-masked tiles (upper triangle / outside the window)
        live = True
        if causal:
            live = q_start + block_q - 1 >= k_start
        if window is not None:
            live = jnp.logical_and(
                live, k_start + block_k - 1 > q_start - window)
        pl.when(live)(body)
    else:
        body()

    @pl.when(ik == n_k - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "scale", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    scale: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """Self-attention.  q: (B, H, S, D); k, v: (B, Hkv, S, D), H % Hkv == 0.
    """
    b, h, s, d = q.shape
    _, hkv, sk, _ = k.shape
    assert s == sk, "flash_attention is self-attention (use flash_decode)"
    group = h // hkv
    scale = float(scale if scale is not None else d ** -0.5)
    bq, bk = min(block_q, s), min(block_k, s)

    def pad_seq(a, blk):
        p = (-a.shape[2]) % blk
        if p:
            return jnp.pad(a, ((0, 0), (0, 0), (0, p), (0, 0)))
        return a

    qp = pad_seq(q, bq)
    kp, vp = pad_seq(k, bk), pad_seq(v, bk)
    sq, skk = qp.shape[2], kp.shape[2]
    grid = (b, h, sq // bq, skk // bk)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, n_k=grid[3], k_valid=s)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, hh, iq, ik, g=group: (bb, hh // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, hh, iq, ik, g=group: (bb, hh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :s, :]


# --------------------------------------------------------------------------
# decode: one query token against a long KV cache
# --------------------------------------------------------------------------


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, block_k: int, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[0]
    k_start = ik * block_k

    @pl.when(k_start < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # (1, d) -> use (G, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kj < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "scale", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 kv_len: jax.Array | None = None, *, block_k: int = 512,
                 scale: float | None = None,
                 interpret: bool = False) -> jax.Array:
    """Decode attention: q (B, H, 1, D) against caches (B, Hkv, S, D).

    The query's G = H/Hkv grouped heads are folded into the MXU sublane dim
    so a GQA decode step still feeds (G x d) @ (d x bk) tiles — the TPU
    adaptation of FlashDecoding's split-K (no warp shuffles here; the lane
    reduction is the VPU's job).
    ``kv_len`` (B,) masks the valid prefix of the cache.
    """
    b, h, one, d = q.shape
    _, hkv, s, _ = k.shape
    assert one == 1
    g = h // hkv
    scale = float(scale if scale is not None else d ** -0.5)
    bk = min(block_k, s)
    p = (-s) % bk
    if p:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, p), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, p), (0, 0)))
    sp = k.shape[2]
    if kv_len is None:
        kv_len = jnp.full((b,), s, jnp.int32)
    # fold grouped heads: (B, Hkv, G, D)
    qg = q.reshape(b, hkv, g, d)
    grid = (b, hkv, sp // bk)
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=bk,
                               n_k=grid[2])
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, hh, ik: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, hh, ik: (bb, hh, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, hh, ik: (bb, hh, ik, 0)),
            pl.BlockSpec((1,), lambda bb, hh, ik: (bb,)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bb, hh, ik: (bb, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, k, v, kv_len)
    return out.reshape(b, h, 1, d)


# --------------------------------------------------------------------------
# split-KV support (Flash-Decoding two-phase): shared helpers
# --------------------------------------------------------------------------


def _resolve_block_k(block_k: int | None, page_size: int,
                     kernel_name: str) -> tuple[int, int]:
    """Resolve the split-K tile within a page: (block_k, sub_blocks).

    The tile must divide ``page_size`` exactly; a non-divisor request is
    coerced to the whole page *with a warning* (it used to be discarded
    silently, which hid tuned values the AT layer thought it had
    committed).  Candidate grids should be pre-filtered with
    :func:`repro.tuning.dynamic.divisor_block_ks` so this never fires in
    a tuned run.
    """
    bk = min(block_k, page_size) if block_k else page_size
    if page_size % bk:
        warnings.warn(
            f"{kernel_name}: requested block_k={block_k} does not divide "
            f"page_size={page_size}; falling back to block_k={page_size} "
            "(whole page) — filter candidates to divisors of page_size",
            RuntimeWarning,
            stacklevel=3,
        )
        bk = page_size
    return bk, page_size // bk


def _split_combine_kernel(m_ref, l_ref, acc_ref, o_ref):
    """Phase 2 of split-KV attention: merge per-split partial softmax
    states with the standard max-shift rescale, then normalize.

    Blocks: m, l (1, 1, ns, rows, 1); acc (1, 1, ns, rows, d); out
    (1, 1, rows, d).  The trailing unit axis keeps m/l in the (rows, 1)
    column layout of the phase-1 scratch, which the TPU block tiling
    accepts and which broadcasts against acc with no relayout.  An empty
    split carries (m=NEG_INF, l=0, acc=0): its rescale weight
    exp(NEG_INF - m*) underflows to exactly 0.0, so it contributes
    nothing; if *every* split is empty the l* == 0 guard reproduces the
    sequential kernel's zero output.
    """
    m = m_ref[0, 0]                                  # (ns, rows, 1)
    l = l_ref[0, 0]                                  # (ns, rows, 1)
    acc = acc_ref[0, 0]                              # (ns, rows, d)
    m_star = m.max(axis=0, keepdims=True)            # (1, rows, 1)
    alpha = jnp.exp(m - m_star)                      # (ns, rows, 1)
    l_star = (l * alpha).sum(axis=0)                 # (rows, 1)
    acc_star = (acc * alpha).sum(axis=0)             # (rows, d)
    l_star = jnp.where(l_star == 0.0, 1.0, l_star)
    o_ref[0, 0] = (acc_star / l_star).astype(o_ref.dtype)


def _combine_splits(m: jax.Array, l: jax.Array, acc: jax.Array,
                    out_dtype, interpret: bool) -> jax.Array:
    """Run the combine kernel over canonical partial-state arrays.

    m, l: (B, R, ns, rows, 1) fp32; acc: (B, R, ns, rows, d) fp32, where
    R is whatever the phase-1 grid parallelised over besides batch (kv
    heads for decode, head x q-tile for prefill).  Returns
    (B, R, rows, d) in ``out_dtype``.
    """
    bb, rr, ns, rows, _ = m.shape
    d = acc.shape[-1]
    return pl.pallas_call(
        _split_combine_kernel,
        grid=(bb, rr),
        in_specs=[
            pl.BlockSpec((1, 1, ns, rows, 1), lambda i, j: (i, j, 0, 0, 0)),
            pl.BlockSpec((1, 1, ns, rows, 1), lambda i, j: (i, j, 0, 0, 0)),
            pl.BlockSpec((1, 1, ns, rows, d),
                         lambda i, j: (i, j, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bb, rr, rows, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(m, l, acc)


def _num_splits(num_splits: int | None, n_steps: int) -> tuple[int, int]:
    """Clamp the requested parallelism degree to the actual KV walk and
    return (n_splits, steps_per_split).  1 selects the single-phase
    (sequential) kernel — the legacy spelling."""
    ns = max(1, min(int(num_splits or 1), n_steps))
    return ns, -(-n_steps // ns)


# --------------------------------------------------------------------------
# paged decode: one query token against a paged (block) KV cache
# --------------------------------------------------------------------------

#: VMEM for the double-buffered K and V blocks of :func:`flash_paged_decode`
#: (both slots, scales included).  It sets the default tile: as many pages
#: of all KV heads as fit.  Timed on a TPU v5e at yi-6b's widths (19
#: lanes, 81-page table), tiles of 64/128/256/512 tokens read
#: 15/26/26/30% of the kernel's roofline; 2 MiB gives yi-6b 512.
PAGED_DECODE_VMEM_BUDGET = 2 << 20


def paged_decode_page_bytes(kv_heads: int, page_size: int, head_dim: int,
                            itemsize: int, quantized: bool = False) -> int:
    """VMEM bytes one page of all KV heads takes in a decode block, K and V
    together; an int8 page brings its f32 scales broadcast over the row."""
    row = head_dim * (itemsize + (4 if quantized else 0))
    return 2 * kv_heads * page_size * row


def paged_decode_block_k(block_k: int | None, *, page_size: int,
                         table_pages: int, page_bytes: int,
                         kernel_name: str = "flash_paged_decode") -> int:
    """Tokens per grid step of the paged decode kernel: a whole number of
    pages, at most the page table's width.

    ``None`` takes as many pages as fit :data:`PAGED_DECODE_VMEM_BUDGET`
    twice (one slot computes while the next block lands in the other),
    given ``page_bytes`` (:func:`paged_decode_page_bytes`).  A request that
    is not a multiple of the page is rounded up to one, with a warning;
    candidate ladders should be filtered with
    :func:`repro.tuning.dynamic.page_multiple_block_ks` so that it never
    fires in a tuned run.
    """
    if block_k:
        pages = -(-int(block_k) // page_size)
        if pages * page_size != block_k:
            warnings.warn(
                f"{kernel_name}: requested block_k={block_k} is not a "
                f"multiple of page_size={page_size}; rounding up to "
                f"block_k={pages * page_size} — filter candidates to "
                "multiples of page_size",
                RuntimeWarning,
                stacklevel=3,
            )
    else:
        pages = PAGED_DECODE_VMEM_BUDGET // (2 * page_bytes)
    return max(1, min(pages, table_pages)) * page_size


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, *refs, scale: float,
                         page_size: int, pages_per_block: int,
                         table_pages: int, blocks_per_split: int,
                         quantized: bool, split: bool):
    """One grid step is one lane (and one split of its walk): every KV head
    of the lane over the live blocks of ``pages_per_block`` pages.

    Pages come from HBM by one async copy per live page and pool (a page
    of all heads is one contiguous run), into two VMEM slots: while one
    block computes, the next one's copies are in flight, across lanes and
    splits too.  Pages at or past the lane's live length are neither
    copied nor computed.  int8 pools bring their scales beside the
    payload and dequantize on load.
    """
    n_pools = 4 if quantized else 2
    pools, refs = refs[:n_pools], refs[n_pools:]
    n_out = 3 if split else 1
    outs, refs = refs[:n_out], refs[n_out:]
    bufs = refs[:n_pools]
    sem, slot_ref, m_ref, l_ref, acc_ref = refs[n_pools:]
    b, isp = pl.program_id(0), pl.program_id(1)
    n_lanes, n_splits = pl.num_programs(0), pl.num_programs(1)
    ppb = pages_per_block
    bk = ppb * page_size
    hkv, g = q_ref.shape[1], q_ref.shape[2]

    def live_pages(lane):
        return jnp.minimum(pl.cdiv(len_ref[lane], page_size), table_pages)

    def block_copies(lane, blk, slot, op):
        """Start or wait the copies of block ``blk`` of ``lane``'s walk
        into ``slot``: one per live page and pool."""
        n_live = live_pages(lane)
        for i in range(ppb):
            @pl.when(blk * ppb + i < n_live)
            def _page(i=i):
                page = tbl_ref[lane * table_pages + blk * ppb + i]
                for src, dst in zip(pools, bufs):
                    copy = pltpu.make_async_copy(src.at[page],
                                                 dst.at[slot, i],
                                                 sem.at[slot])
                    getattr(copy, op)()

    # the grid runs in order: the step after this one, and its first block
    if split:
        last = isp + 1 == n_splits
        nxt_b = jnp.where(last, b + 1, b)
        nxt_blk = jnp.where(last, 0, (isp + 1) * blocks_per_split)
    else:
        nxt_b, nxt_blk = b + 1, 0
    nxt_lane = jnp.minimum(nxt_b, n_lanes - 1)
    nxt_live = jnp.logical_and(nxt_b < n_lanes,
                               nxt_blk * ppb < live_pages(nxt_lane))

    lo = isp * blocks_per_split
    hi = jnp.minimum(lo + blocks_per_split, pl.cdiv(live_pages(b), ppb))
    n_blk = jnp.maximum(hi - lo, 0)

    @pl.when(jnp.logical_and(b == 0, isp == 0))
    def _first():
        # V rows past a lane's live pages meet p = 0, and 0 x NaN is NaN:
        # the slots of V (and of its scales) start as zeros, not as
        # whatever VMEM held.  K rows there are masked in the scores.
        for buf in bufs[1::2]:
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
        slot_ref[0] = 0

        @pl.when(n_blk > 0)
        def _():
            block_copies(b, lo, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    kv_len = len_ref[b]

    def rows(buf, slot, h):
        x = buf[slot, :, h]                           # (ppb, psz, last)
        if page_size % (32 // x.dtype.itemsize):      # off the sublane tile
            x = x.astype(jnp.float32)
        return x.reshape(bk, x.shape[-1])

    def body(t, slot):
        blk = lo + t

        @pl.when(t + 1 < n_blk)
        def _():
            block_copies(b, blk + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(t + 1 == n_blk, nxt_live))
        def _():
            block_copies(nxt_lane, nxt_blk, 1 - slot, "start")

        block_copies(b, blk, slot, "wait")
        kj = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        live = kj < kv_len
        for h in range(hkv):
            q = q_ref[0, h]                           # (G, d)
            k = rows(bufs[0], slot, h)                # (bk, d)
            v = rows(bufs[1], slot, h).astype(jnp.float32)
            if quantized:
                k = k.astype(jnp.float32) * rows(bufs[2], slot, h)
                v = v * rows(bufs[3], slot, h)
            if k.dtype != q.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            # bf16 q and k go to the MXU as they are: their products are
            # exact in the f32 accumulator
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s * scale, NEG_INF)
            m_prev = m_ref[h]
            m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_cur)
            alpha = jnp.exp(m_prev - m_cur)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
            m_ref[h] = m_cur
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
        return 1 - slot

    slot = jax.lax.fori_loop(0, n_blk, body, slot_ref[0])

    @pl.when(jnp.logical_and(n_blk == 0, nxt_live))
    def _():
        block_copies(nxt_lane, nxt_blk, slot, "start")

    slot_ref[0] = slot
    if split:
        m_out, l_out, acc_out = outs
        m_out[0, :, 0] = m_ref[...]
        l_out[0, :, 0] = l_ref[...]
        acc_out[0, :, 0] = acc_ref[...]
    else:
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        outs[0][0] = (acc_ref[...] / l).astype(outs[0].dtype)


def _paged_decode(q, pools, page_table, kv_len, *, block_k, num_splits,
                  scale, interpret, name):
    """Shared wrapper of the fp and int8 decode: ``pools`` is (k, v), or
    (k, v, k_scale, v_scale) with the scales broadcast to the pools'
    shape."""
    b, h, one, d = q.shape
    n_pages, hkv, psz, _ = pools[0].shape
    assert one == 1
    g = h // hkv
    nblk = page_table.shape[1]
    quantized = len(pools) == 4
    scale = float(scale if scale is not None else d ** -0.5)
    bk = paged_decode_block_k(
        block_k, page_size=psz, table_pages=nblk, kernel_name=name,
        page_bytes=paged_decode_page_bytes(hkv, psz, d,
                                           pools[0].dtype.itemsize,
                                           quantized))
    ppb = bk // psz
    ns, bps = _num_splits(num_splits, pl.cdiv(nblk, ppb))
    split = ns > 1
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, page_size=psz,
        pages_per_block=ppb, table_pages=nblk, blocks_per_split=bps,
        quantized=quantized, split=split)
    lane_block = lambda bb, isp, tbl, ln: (bb, 0, 0, 0)
    split_block = lambda bb, isp, tbl, ln: (bb, 0, isp, 0, 0)
    if split:
        out_shape = [jax.ShapeDtypeStruct((b, hkv, ns, g, 1), jnp.float32),
                     jax.ShapeDtypeStruct((b, hkv, ns, g, 1), jnp.float32),
                     jax.ShapeDtypeStruct((b, hkv, ns, g, d), jnp.float32)]
        out_specs = [pl.BlockSpec((1, hkv, 1, g, 1), split_block),
                     pl.BlockSpec((1, hkv, 1, g, 1), split_block),
                     pl.BlockSpec((1, hkv, 1, g, d), split_block)]
    else:
        out_shape = jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype)
        out_specs = pl.BlockSpec((1, hkv, g, d), lane_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, ns),
        in_specs=[pl.BlockSpec((1, hkv, g, d), lane_block),
                  *[pl.BlockSpec(memory_space=pl.ANY) for _ in pools]],
        out_specs=out_specs,
        scratch_shapes=[
            *[pltpu.VMEM((2, ppb) + p.shape[1:], p.dtype) for p in pools],
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # both axes "arbitrary": each step prefetches the next step's
        # first block, so the grid must run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(page_table.reshape(-1).astype(jnp.int32), kv_len.astype(jnp.int32),
      q.reshape(b, hkv, g, d), *pools)
    if split:
        out = _combine_splits(*out, q.dtype, interpret)
    return out.reshape(b, h, 1, d)


@functools.partial(jax.jit, static_argnames=("block_k", "num_splits",
                                             "scale", "interpret"))
def flash_paged_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       page_table: jax.Array, kv_len: jax.Array, *,
                       block_k: int | None = None,
                       num_splits: int | None = None,
                       scale: float | None = None,
                       interpret: bool = False) -> jax.Array:
    """Decode attention over a paged KV cache (vLLM-style PagedAttention).

    q: (B, H, 1, D); pools (P, Hkv, psz, D); ``page_table`` (B, nblk) int32
    maps each sequence's logical KV block to a physical page.  The table is
    scalar-prefetched and the kernel copies each live page of all heads
    straight from its owner — the KV working set never materialises
    densely, and HBM traffic is O(live tokens), not O(B * max_len).
    ``kv_len`` (B,) masks the valid prefix; table entries past it may point
    anywhere (page 0 by convention) and are never read.

    ``block_k`` is the tokens per grid step (the run-time-AT performance
    parameter of this kernel), a multiple of ``page_size`` capped at the
    table's width; the default fills :data:`PAGED_DECODE_VMEM_BUDGET`
    (:func:`paged_decode_block_k`).  ``num_splits`` partitions each lane's
    blocks into that many independent segments (Flash-Decoding two-phase,
    merged by :func:`_combine_splits`); 1 (the default) is one walk.
    """
    return _paged_decode(q, (k_pool, v_pool), page_table, kv_len,
                         block_k=block_k, num_splits=num_splits, scale=scale,
                         interpret=interpret, name="flash_paged_decode")


@functools.partial(jax.jit, static_argnames=("block_k", "num_splits",
                                             "scale", "interpret"))
def flash_paged_decode_quant(q: jax.Array, k_pool: jax.Array,
                             v_pool: jax.Array, k_scale: jax.Array,
                             v_scale: jax.Array, page_table: jax.Array,
                             kv_len: jax.Array, *,
                             block_k: int | None = None,
                             num_splits: int | None = None,
                             scale: float | None = None,
                             interpret: bool = False) -> jax.Array:
    """:func:`flash_paged_decode` over int8 pools.

    Pools (P, Hkv, psz, D) int8; ``k_scale``/``v_scale`` (P, Hkv, psz)
    fp32, one scale per (page, head, slot) row.  The scales are broadcast
    over the row (a copy of a page is a whole number of (8, 128) tiles),
    each page's are copied beside its payload, and the kernel dequantizes
    in VMEM right before the fp32 dots — the softmax accumulator path is
    the fp one.
    """
    scales = [jnp.broadcast_to(sc[..., None], k_pool.shape)
              for sc in (k_scale, v_scale)]
    return _paged_decode(q, (k_pool, v_pool, *scales),
                         page_table, kv_len, block_k=block_k,
                         num_splits=num_splits, scale=scale,
                         interpret=interpret,
                         name="flash_paged_decode_quant")

# --------------------------------------------------------------------------
# paged prefill: one prompt chunk against a paged (block) KV cache
# --------------------------------------------------------------------------


def _paged_prefill_kernel(tbl_ref, start_ref, len_ref, q_ref, k_ref, v_ref,
                          o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                          block_q: int, block_k: int, n_k: int):
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    q_start = start_ref[b] + iq * block_q     # absolute pos of q row 0
    k_start = ik * block_k

    # live iff some key in the tile is (a) committed and (b) causally
    # visible to the *last* query row of the q tile
    live = jnp.logical_and(k_start < kv_len,
                           k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
        mask = (kj <= qi) & (kj < kv_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_prefill_split_kernel(tbl_ref, start_ref, len_ref, q_ref, k_ref,
                                v_ref, m_out, l_out, acc_out, m_ref, l_ref,
                                acc_ref, *, scale: float, block_q: int,
                                block_k: int, n_steps: int,
                                steps_per_split: int):
    """Phase 1 of split-KV prefill/verify: the KV walk for each q tile is
    partitioned into parallel segments emitting partial (m, l, acc).
    Partials for (q-tile iq, split isp) land at folded row iq*ns+isp so
    the outputs stay <= 5-D."""
    b = pl.program_id(0)
    iq = pl.program_id(2)
    isp, j = pl.program_id(3), pl.program_id(4)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    q_start = start_ref[b] + iq * block_q     # absolute pos of q row 0
    ik = isp * steps_per_split + j
    k_start = ik * block_k

    live = jnp.logical_and(
        ik < n_steps,
        jnp.logical_and(k_start < kv_len,
                        k_start <= q_start + block_q - 1))

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
        mask = (kj <= qi) & (kj < kv_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    @pl.when(j == steps_per_split - 1)
    def _done():
        m_out[0, 0, 0] = m_ref[...]
        l_out[0, 0, 0] = l_ref[...]
        acc_out[0, 0, 0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "num_splits", "scale",
                                             "interpret"))
def flash_paged_prefill(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        page_table: jax.Array, start: jax.Array,
                        kv_len: jax.Array, *, block_q: int = 128,
                        block_k: int | None = None,
                        num_splits: int | None = None,
                        scale: float | None = None,
                        interpret: bool = False) -> jax.Array:
    """Chunked-prefill attention over a paged KV cache.

    q: (B, H, C, D) — one prompt chunk per sequence, first token at
    absolute position ``start[b]``; pools (P, Hkv, psz, D); ``page_table``
    (B, nblk) int32.  The chunk's KV must already be scattered into its
    pages (write-before-read, same contract as the oracle); ``kv_len``
    (B,) = ``start + chunk_len`` masks the valid key prefix.  Query rows
    attend causally at *absolute* positions, so a chunk sees the whole
    committed prefix plus its own lower triangle.

    The page table and both scalar vectors are scalar-prefetched: each
    grid step DMAs its (block_k, D) key tile straight from the owning
    physical page — the committed prefix never materialises densely.

    Performance parameters (the prefill region's run-time AT space):
    ``block_q`` tiles the chunk, ``block_k`` the split-K tile *within* a
    page (must divide ``page_size``; defaults to the whole page),
    ``num_splits`` the parallel split-KV degree (1 = sequential walk).
    """
    b, h, c, d = q.shape
    n_pages, hkv, psz, _ = k_pool.shape
    g = h // hkv
    nblk = page_table.shape[1]
    scale = float(scale if scale is not None else d ** -0.5)
    bq = min(block_q, c)
    pq = (-c) % bq
    if pq:                       # pad the chunk to a whole q tile; padded
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))   # rows discard
    cp = q.shape[2]
    bk, sub = _resolve_block_k(block_k, psz, "flash_paged_prefill")
    n_steps = nblk * sub
    nq = cp // bq
    ns, sps = _num_splits(num_splits, n_steps)
    if ns == 1:
        grid = (b, h, nq, n_steps)
        kernel = functools.partial(_paged_prefill_kernel, scale=scale,
                                   block_q=bq, block_k=bk, n_k=grid[3])
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda bb, hh, iq, ik, tbl, st, ln:
                             (bb, hh, iq, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda bb, hh, iq, ik, tbl, st, ln, g=g, s=sub:
                             (tbl[bb, ik // s], hh // g, ik % s, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda bb, hh, iq, ik, tbl, st, ln, g=g, s=sub:
                             (tbl[bb, ik // s], hh // g, ik % s, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda bb, hh, iq, ik, tbl, st, ln:
                                   (bb, hh, iq, 0)),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, d), jnp.float32)],
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, cp, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(page_table.astype(jnp.int32), start.astype(jnp.int32),
          kv_len.astype(jnp.int32), q, k_pool, v_pool)
        return out[:, :, :c, :]

    grid = (b, h, nq, ns, sps)
    kernel = functools.partial(_paged_prefill_split_kernel, scale=scale,
                               block_q=bq, block_k=bk, n_steps=n_steps,
                               steps_per_split=sps)

    def kv_idx(bb, hh, iq, isp, j, tbl, st, ln, g=g, s=sub, sp=sps,
               n=n_steps):
        ik = jnp.minimum(isp * sp + j, n - 1)
        return (tbl[bb, ik // s], hh // g, ik % s, 0)

    def row_idx(bb, hh, iq, isp, j, tbl, st, ln, ns=ns):
        return (bb, hh, iq * ns + isp, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bb, hh, iq, isp, j, tbl, st, ln:
                         (bb, hh, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_idx),
            pl.BlockSpec((1, 1, bk, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, bq, 1), row_idx),
            pl.BlockSpec((1, 1, 1, bq, 1), row_idx),
            pl.BlockSpec((1, 1, 1, bq, d), row_idx),
        ],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
    )
    pm, pll, pacc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nq * ns, bq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nq * ns, bq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nq * ns, bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      kv_len.astype(jnp.int32), q, k_pool, v_pool)
    # fold (h, nq) into the combine's R axis; the nq*ns rows are laid out
    # q-tile-major so a plain reshape separates them
    out = _combine_splits(pm.reshape(b, h * nq, ns, bq, 1),
                          pll.reshape(b, h * nq, ns, bq, 1),
                          pacc.reshape(b, h * nq, ns, bq, d),
                          q.dtype, interpret)
    return out.reshape(b, h, cp, d)[:, :, :c, :]


# --------------------------------------------------------------------------
# quantized paged kernels: int8 pages + per-row fp32 scales, dequantized
# in-kernel into the same fp32 online-softmax accumulator path
# --------------------------------------------------------------------------


def _scale_columns(k_scale: jax.Array, v_scale: jax.Array):
    """(P, Hkv, psz) per-row scales -> (P, Hkv, psz, 1) column view.

    A (1, 1, bk) block of the 3-D scales is refused by the TPU block
    tiling (its second-minor dim 1 neither spans Hkv nor is a multiple
    of 8); the (1, 1, bk, 1) column block is accepted and lands in VMEM
    already shaped to broadcast over the (bk, d) key/value tile rows.
    """
    return k_scale[..., None], v_scale[..., None]


def _paged_prefill_quant_kernel(tbl_ref, start_ref, len_ref, q_ref, k_ref,
                                v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref,
                                acc_ref, *, scale: float, block_q: int,
                                block_k: int, n_k: int):
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    q_start = start_ref[b] + iq * block_q     # absolute pos of q row 0
    k_start = ik * block_k

    live = jnp.logical_and(k_start < kv_len,
                           k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
        mask = (kj <= qi) & (kj < kv_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_cur
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_prefill_split_quant_kernel(tbl_ref, start_ref, len_ref, q_ref,
                                      k_ref, v_ref, ks_ref, vs_ref, m_out,
                                      l_out, acc_out, m_ref, l_ref,
                                      acc_ref, *, scale: float,
                                      block_q: int, block_k: int,
                                      n_steps: int, steps_per_split: int):
    """Split-KV phase 1 for prefill/verify over int8 pools."""
    b = pl.program_id(0)
    iq = pl.program_id(2)
    isp, j = pl.program_id(3), pl.program_id(4)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    q_start = start_ref[b] + iq * block_q     # absolute pos of q row 0
    ik = isp * steps_per_split + j
    k_start = ik * block_k

    live = jnp.logical_and(
        ik < n_steps,
        jnp.logical_and(k_start < kv_len,
                        k_start <= q_start + block_q - 1))

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
        mask = (kj <= qi) & (kj < kv_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_cur
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == steps_per_split - 1)
    def _done():
        m_out[0, 0, 0] = m_ref[...]
        l_out[0, 0, 0] = l_ref[...]
        acc_out[0, 0, 0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "num_splits", "scale",
                                             "interpret"))
def flash_paged_prefill_quant(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, k_scale: jax.Array,
                              v_scale: jax.Array, page_table: jax.Array,
                              start: jax.Array, kv_len: jax.Array, *,
                              block_q: int = 128,
                              block_k: int | None = None,
                              num_splits: int | None = None,
                              scale: float | None = None,
                              interpret: bool = False) -> jax.Array:
    """:func:`flash_paged_prefill` over int8 pools (verify rides this too).

    Same contract as the fp kernel plus ``k_scale``/``v_scale``
    (P, Hkv, psz) per-row fp32 scales, dequantized in VMEM ahead of the
    fp32 score/accumulate dots.
    """
    b, h, c, d = q.shape
    n_pages, hkv, psz, _ = k_pool.shape
    g = h // hkv
    nblk = page_table.shape[1]
    scale = float(scale if scale is not None else d ** -0.5)
    bq = min(block_q, c)
    pq = (-c) % bq
    if pq:                       # pad the chunk to a whole q tile; padded
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))   # rows discard
    cp = q.shape[2]
    bk, sub = _resolve_block_k(block_k, psz, "flash_paged_prefill_quant")
    k_scale, v_scale = _scale_columns(k_scale, v_scale)
    n_steps = nblk * sub
    nq = cp // bq
    ns, sps = _num_splits(num_splits, n_steps)
    if ns == 1:
        grid = (b, h, nq, n_steps)
        kernel = functools.partial(_paged_prefill_quant_kernel, scale=scale,
                                   block_q=bq, block_k=bk, n_k=grid[3])
        pool_spec = pl.BlockSpec(
            (1, 1, bk, d),
            lambda bb, hh, iq, ik, tbl, st, ln, g=g, s=sub:
            (tbl[bb, ik // s], hh // g, ik % s, 0))
        scale_spec = pl.BlockSpec(
            (1, 1, bk, 1),
            lambda bb, hh, iq, ik, tbl, st, ln, g=g, s=sub:
            (tbl[bb, ik // s], hh // g, ik % s, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda bb, hh, iq, ik, tbl, st, ln:
                             (bb, hh, iq, 0)),
                pool_spec,
                pool_spec,
                scale_spec,
                scale_spec,
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda bb, hh, iq, ik, tbl, st, ln:
                                   (bb, hh, iq, 0)),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, d), jnp.float32)],
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, cp, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(page_table.astype(jnp.int32), start.astype(jnp.int32),
          kv_len.astype(jnp.int32), q, k_pool, v_pool, k_scale, v_scale)
        return out[:, :, :c, :]

    grid = (b, h, nq, ns, sps)
    kernel = functools.partial(_paged_prefill_split_quant_kernel,
                               scale=scale, block_q=bq, block_k=bk,
                               n_steps=n_steps, steps_per_split=sps)

    def kv_idx(bb, hh, iq, isp, j, tbl, st, ln, g=g, s=sub, sp=sps,
               n=n_steps):
        ik = jnp.minimum(isp * sp + j, n - 1)
        return (tbl[bb, ik // s], hh // g, ik % s, 0)

    def sc_idx(bb, hh, iq, isp, j, tbl, st, ln, g=g, s=sub, sp=sps,
               n=n_steps):
        ik = jnp.minimum(isp * sp + j, n - 1)
        return (tbl[bb, ik // s], hh // g, ik % s, 0)

    def row_idx(bb, hh, iq, isp, j, tbl, st, ln, ns=ns):
        return (bb, hh, iq * ns + isp, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bb, hh, iq, isp, j, tbl, st, ln:
                         (bb, hh, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_idx),
            pl.BlockSpec((1, 1, bk, d), kv_idx),
            pl.BlockSpec((1, 1, bk, 1), sc_idx),
            pl.BlockSpec((1, 1, bk, 1), sc_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, bq, 1), row_idx),
            pl.BlockSpec((1, 1, 1, bq, 1), row_idx),
            pl.BlockSpec((1, 1, 1, bq, d), row_idx),
        ],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
    )
    pm, pll, pacc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nq * ns, bq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nq * ns, bq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nq * ns, bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      kv_len.astype(jnp.int32), q, k_pool, v_pool, k_scale, v_scale)
    out = _combine_splits(pm.reshape(b, h * nq, ns, bq, 1),
                          pll.reshape(b, h * nq, ns, bq, 1),
                          pacc.reshape(b, h * nq, ns, bq, d),
                          q.dtype, interpret)
    return out.reshape(b, h, cp, d)[:, :, :c, :]


def attention_vmem_bytes(block_q: int, block_k: int, d: int,
                         bytes_per_el: int = 2) -> int:
    """Analytic VMEM footprint per grid step (CPU-side AT cost model)."""
    return (block_q * d + 2 * block_k * d) * bytes_per_el \
        + block_q * block_k * 4 + block_q * (d + 2) * 4
