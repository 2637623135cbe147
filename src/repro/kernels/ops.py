"""Public jit'd kernel wrappers with interpret/TPU dispatch + AT hookup.

The model stack calls these, never ``pl.pallas_call`` directly.  On CPU
(this container) kernels run in ``interpret=True`` mode; on TPU they
compile for real.  Block-shape performance parameters default to
MXU-aligned values and are overridden by install-time AT results published
through :func:`repro.at.tuned` (see tuning/install.py).

Paged attention goes through one typed entry point per op
(:func:`paged_decode` / :func:`paged_prefill` / :func:`paged_verify`),
each taking a :class:`PagedPools` bundle — pools, optional int8 scales
and the page geometry travel together instead of being sniffed from
``k_scale=None`` keywords.  The same entry points are where
tensor-parallel dispatch lives: given a ``mesh`` with a multi-device
``"model"`` axis they wrap the kernel in ``shard_map`` with the
(GQA-grouped) head axes partitioned and page tables replicated.  The old
``paged_*_attention`` keyword-sniffing entries remain as thin
deprecation shims.

``set_tuned`` is a deprecation shim over :func:`repro.at.publish`; new
code publishes via ``autotune(..., publish=(kernel, mapping))`` and reads
via ``at.tuned(kernel)``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..at.session import publish as _publish
from ..at.session import tuned as _tuned
from . import ref
from .flash_attention import (flash_attention, flash_decode,
                              flash_paged_decode, flash_paged_decode_quant,
                              flash_paged_prefill, flash_paged_prefill_quant)
from .matmul import matmul
from .ssm_scan import selective_scan


def set_tuned(name: str, **pps) -> None:
    """Deprecated: use ``repro.at.publish`` (kept as a thin shim)."""
    _publish(name, **pps)


def tuned(name: str, **bps) -> dict:
    return _tuned(name, **bps)


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def mm(x, y, bias=None, *, epilogue="none", use_kernel: bool | None = None,
       **pps):
    """GEMM entry point.  Falls back to the jnp reference on CPU unless the
    caller forces the kernel (tests do, with interpret=True)."""
    if use_kernel is None:
        use_kernel = not on_cpu()
    if not use_kernel:
        return ref.matmul_ref(x, y, bias, epilogue)
    kw = tuned("matmul")
    kw.update(pps)
    return matmul(x, y, bias, epilogue=epilogue, interpret=on_cpu(), **kw)


CHUNKED_THRESHOLD = 2048     # above this seq, the jnp path goes flash-style


def attention(q, k, v, *, causal=True, window=None, mesh=None,
              use_kernel: bool | None = None, **pps):
    """Full (prefill) attention.  On a mesh with a multi-device ``model``
    axis, long causal sequences take the ring sequence-parallel tail
    (:func:`repro.distributed.ring_attention.make_ring_attention`) —
    each device holds one sequence shard and passes KV blocks around the
    ring instead of all-gathering the whole sequence.  Short sequences,
    windowed attention and indivisible lengths fall through to the
    single-device paths unchanged.
    """
    seq = q.shape[2]
    m = mesh_model_axis(mesh)
    if (m > 1 and causal and window is None and seq == k.shape[2]
            and seq % m == 0 and seq > CHUNKED_THRESHOLD):
        from ..distributed.ring_attention import make_ring_attention
        if k.shape[1] != q.shape[1]:    # GQA: ring keeps heads replicated
            g = q.shape[1] // k.shape[1]
            k = jnp.repeat(k, g, axis=1)
            v = jnp.repeat(v, g, axis=1)
        return make_ring_attention(mesh, causal=True)(q, k, v)
    if use_kernel is None:
        use_kernel = not on_cpu()
    if not use_kernel:
        if q.shape[2] > CHUNKED_THRESHOLD:
            kw = tuned("chunked_attention")
            kw.update(pps)
            return ref.chunked_attention(q, k, v, causal=causal,
                                         window=window, **kw)
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    kw = tuned("flash_attention")
    kw.update(pps)
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=on_cpu(), **kw)


def decode_attention(q, k, v, kv_len=None, *, use_kernel: bool | None = None,
                     **pps):
    if use_kernel is None:
        use_kernel = not on_cpu()
    if not use_kernel:
        return ref.decode_ref(q, k, v, kv_len)
    kw = tuned("flash_decode")
    kw.update(pps)
    return flash_decode(q, k, v, kv_len, interpret=on_cpu(), **kw)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PagedPools:
    """One layer's paged KV state as a typed bundle.

    ``k``/``v`` are the physical page pools, shape ``(P, Hkv, page_size,
    D)``; ``k_scale``/``v_scale`` are the per-row fp32 dequant scales
    ``(P, Hkv, page_size)`` carried only by int8 pools — their presence
    *is* the precision flag, replacing the old ``k_scale=None`` keyword
    sniffing.  Registered as a pytree so a bundle flows through ``jit`` /
    ``scan`` / ``shard_map`` like its bare arrays did (``None`` scales
    are empty subtrees, so fp and int8 bundles stay distinct treedefs).
    """

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array | None = None
    v_scale: jax.Array | None = None

    def tree_flatten(self):
        return (self.k, self.v, self.k_scale, self.v_scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def n_kv_heads(self) -> int:
        return self.k.shape[1]

    def head_specs(self, axis: str = "model") -> "PagedPools":
        """shard_map PartitionSpecs partitioning the KV-head axis (pool
        axis 1) over ``axis``; the page axis stays replicated so page
        tables need no translation."""
        pool = P(None, axis, None, None)
        scale = None if self.k_scale is None else P(None, axis, None)
        return PagedPools(pool, pool, scale, scale)


def mesh_model_axis(mesh) -> int:
    """Size of the tensor-parallel ``"model"`` axis of ``mesh`` (1 == no
    sharding: no mesh, no model axis, or a 1-device axis — those all run
    the unsharded code path and stay bit-identical to it)."""
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return int(mesh.shape["model"])


def _check_pools(pools: PagedPools) -> None:
    if (pools.k_scale is None) != (pools.v_scale is None):
        raise ValueError(
            "PagedPools carries k_scale without v_scale (or vice versa): "
            "int8 pools quantize both sides, fp pools neither")


def _check_head_sharding(q, pools: PagedPools, m: int) -> None:
    h, hkv = q.shape[1], pools.n_kv_heads
    if hkv % m or h % m:
        raise ValueError(
            f"tensor-parallel paged attention shards the KV-head axis: "
            f"kv_heads={hkv} (q heads {h}) is not divisible by the mesh's "
            f"'model' axis of size {m} — pick a mesh whose model axis "
            f"divides the head counts, or run unsharded")


def _head_sharded(mesh, fn, q, pools: PagedPools, *rest):
    """Run ``fn(q, pools, *rest)`` under ``shard_map`` with the head axes
    partitioned over the mesh's ``"model"`` axis.

    Contiguous sharding of both the q-head and kv-head axes keeps every q
    head on the same shard as its GQA kv group (group size H/Hkv divides
    evenly once both axes divide the mesh), so each device runs exactly
    the unsharded math on its head slice; page tables and lane metadata
    (``*rest``) are replicated.  The output is constrained back to
    replicated — an exact all-gather — so the caller's output projection
    computes bitwise identically to the unsharded engine.
    """
    qspec = P(None, "model", None, None)
    shardfn = jax.shard_map(fn, mesh=mesh,
                            in_specs=(qspec, pools.head_specs(),
                                      *(P() for _ in rest)),
                            out_specs=qspec, check_vma=False)
    out = shardfn(q, pools, *rest)
    return jax.lax.with_sharding_constraint(
        out, NamedSharding(mesh, P(None, None, None, None)))


def _paged_decode_local(q, pools: PagedPools, page_table, kv_len, *,
                        use_kernel: bool | None = None, **pps):
    if use_kernel is None:
        use_kernel = not on_cpu()
    if not use_kernel:
        return ref.paged_decode_ref(q, pools.k, pools.v, page_table, kv_len,
                                    k_scale=pools.k_scale,
                                    v_scale=pools.v_scale)
    kw = tuned("flash_paged_decode")
    kw.update(pps)
    kw = {k: v for k, v in kw.items()
          if k in ("block_k", "num_splits", "scale")}
    if pools.quantized:
        return flash_paged_decode_quant(q, pools.k, pools.v, pools.k_scale,
                                        pools.v_scale, page_table, kv_len,
                                        interpret=on_cpu(), **kw)
    return flash_paged_decode(q, pools.k, pools.v, page_table, kv_len,
                              interpret=on_cpu(), **kw)


def _paged_chunk_local(q, pools: PagedPools, page_table, start, kv_len, *,
                       tuned_key: str, use_kernel: bool | None = None, **pps):
    """Shared local body for prefill and verify (same math, different
    tuning surface — ``tuned_key`` selects which published PPs apply)."""
    if use_kernel is None:
        use_kernel = not on_cpu()
    if not use_kernel:
        return ref.paged_prefill_ref(q, pools.k, pools.v, page_table,
                                     start, kv_len,
                                     k_scale=pools.k_scale,
                                     v_scale=pools.v_scale)
    kw = tuned(tuned_key)
    kw.update(pps)
    kw = {k: v for k, v in kw.items()
          if k in ("block_q", "block_k", "num_splits", "scale")}
    if pools.quantized:
        return flash_paged_prefill_quant(q, pools.k, pools.v, pools.k_scale,
                                         pools.v_scale, page_table, start,
                                         kv_len, interpret=on_cpu(), **kw)
    return flash_paged_prefill(q, pools.k, pools.v, page_table, start, kv_len,
                               interpret=on_cpu(), **kw)


def paged_decode(q, pools: PagedPools, page_table, kv_len, *, mesh=None,
                 use_kernel: bool | None = None, **pps):
    """Decode attention over a paged KV cache (serving hot path).

    Dispatch mirrors :func:`decode_attention`: the Pallas PagedAttention
    kernel on TPU, the gather+oracle reference on CPU.  Tuned PPs
    published under ``flash_paged_decode`` (the serving
    ``DecodeAutoTuner`` publishes the per-bucket ``block_k`` tile, a
    whole number of pages, and the split-KV ``num_splits`` parallelism
    degree) flow into
    the kernel call; the page size itself is structural — it is fixed
    when the pool is built, not a per-call knob.  An int8 ``pools``
    bundle (scales present) switches both backends to the
    in-kernel-dequant variant.  A ``mesh`` with a multi-device ``model``
    axis runs the op under ``shard_map`` with heads partitioned
    (:func:`_head_sharded`); a 1-device mesh takes the unsharded path
    unchanged — tuned PPs are read inside the per-shard body, so
    ``num_splits`` splits each device's *local* head slice's KV walk.
    """
    _check_pools(pools)
    m = mesh_model_axis(mesh)
    if m > 1:
        _check_head_sharding(q, pools, m)
        fn = functools.partial(_paged_decode_local, use_kernel=use_kernel,
                               **pps)
        return _head_sharded(mesh, fn, q, pools, page_table, kv_len)
    return _paged_decode_local(q, pools, page_table, kv_len,
                               use_kernel=use_kernel, **pps)


def paged_prefill(q, pools: PagedPools, page_table, start, kv_len, *,
                  mesh=None, use_kernel: bool | None = None, **pps):
    """Chunked-prefill attention over a paged KV cache (serving hot path).

    One prompt chunk (q: (B, H, C, D), first token at absolute position
    ``start``) attends causally to the committed prefix *plus* its own
    lower triangle, reading keys straight from the physical pages.  The
    chunk's KV must already be scattered into the pages (write before
    read).  Tuned PPs published under ``flash_paged_prefill`` — the
    serving prefill region tunes the (block_q x block_k) tile per prompt
    bucket x chunk size — flow into the kernel call; on CPU the gather
    oracle runs instead.  int8 bundles and ``mesh`` dispatch exactly as
    in :func:`paged_decode`.
    """
    _check_pools(pools)
    m = mesh_model_axis(mesh)
    fn = functools.partial(_paged_chunk_local,
                           tuned_key="flash_paged_prefill",
                           use_kernel=use_kernel, **pps)
    if m > 1:
        _check_head_sharding(q, pools, m)
        return _head_sharded(mesh, fn, q, pools, page_table, start, kv_len)
    return fn(q, pools, page_table, start, kv_len)


def paged_verify(q, pools: PagedPools, page_table, start, kv_len, *,
                 mesh=None, use_kernel: bool | None = None, **pps):
    """Speculative-decode verify attention over a paged KV cache.

    The chunk is ``[last committed token, draft_1 .. draft_k]`` (q:
    (B, H, k+1, D), first token at absolute position ``start``): no new
    kernel math — it is exactly the chunked-prefill computation (causal
    over the committed prefix plus the chunk's own triangle), reusing the
    Pallas ``flash_paged_prefill`` kernel on TPU and the gather oracle on
    CPU.  What differs is the tuning surface: verify chunks are k+1
    tokens wide, so tuned PPs are read from ``flash_paged_verify`` (the
    serving ``SpecBucket`` regions tune k and the (block_q x block_k)
    tile per length bucket) instead of the prefill entry.
    """
    _check_pools(pools)
    m = mesh_model_axis(mesh)
    fn = functools.partial(_paged_chunk_local,
                           tuned_key="flash_paged_verify",
                           use_kernel=use_kernel, **pps)
    if m > 1:
        _check_head_sharding(q, pools, m)
        return _head_sharded(mesh, fn, q, pools, page_table, start, kv_len)
    return fn(q, pools, page_table, start, kv_len)


# -- deprecated keyword-sniffing entries ------------------------------------
# Thin shims over the typed entry points so pre-PagedPools callers keep
# working while they migrate; new code passes a PagedPools bundle.

def paged_decode_attention(q, k_pool, v_pool, page_table, kv_len, *,
                           k_scale=None, v_scale=None,
                           use_kernel: bool | None = None, **pps):
    """Deprecated: use :func:`paged_decode` with a :class:`PagedPools`."""
    return paged_decode(q, PagedPools(k_pool, v_pool, k_scale, v_scale),
                        page_table, kv_len, use_kernel=use_kernel, **pps)


def paged_prefill_attention(q, k_pool, v_pool, page_table, start, kv_len, *,
                            k_scale=None, v_scale=None,
                            use_kernel: bool | None = None, **pps):
    """Deprecated: use :func:`paged_prefill` with a :class:`PagedPools`."""
    return paged_prefill(q, PagedPools(k_pool, v_pool, k_scale, v_scale),
                         page_table, start, kv_len,
                         use_kernel=use_kernel, **pps)


def paged_verify_attention(q, k_pool, v_pool, page_table, start, kv_len, *,
                           k_scale=None, v_scale=None,
                           use_kernel: bool | None = None, **pps):
    """Deprecated: use :func:`paged_verify` with a :class:`PagedPools`."""
    return paged_verify(q, PagedPools(k_pool, v_pool, k_scale, v_scale),
                        page_table, start, kv_len,
                        use_kernel=use_kernel, **pps)


def ssm_scan(x, dt, a, b, c, d, *, use_kernel: bool | None = None,
             return_final_state: bool = False, **pps):
    if use_kernel is None:
        use_kernel = not on_cpu()
    if not use_kernel or return_final_state:
        return ref.selective_scan_ref(x, dt, a, b, c, d,
                                      return_final_state)
    kw = tuned("ssm_scan")
    kw.update(pps)
    return selective_scan(x, dt, a, b, c, d, interpret=on_cpu(), **kw)
