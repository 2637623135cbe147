"""KV-cache backends for the serving engine: dense lanes and paged blocks.

Two implementations of one interface:

* :class:`DenseKVCache` — the classic layout: every decode lane owns a
  contiguous ``max_len`` strip in a stacked ``(L, n_lanes, ...)`` cache.
  Memory is O(n_lanes * max_len) regardless of how many tokens are live.
* :class:`PagedKVCache` — vLLM-style paging: a shared physical pool of
  ``n_pages`` pages of ``page_size`` tokens each (per layer), with a
  per-lane page table mapping logical KV blocks to physical pages.
  Memory scales with *live tokens* (rounded up to page granularity), lane
  admission is page allocation instead of a pad/crop splice, and a
  preempted sequence's pages can be swapped out to host memory and later
  swapped back in without re-running prefill.

Page 0 of the pool is reserved as a *null page*: idle lanes decode with
``pos = 0`` and a zeroed page-table row, so their (discarded) KV writes
land there and can never corrupt a live sequence.

The paged pool can store K/V as **int8 pages** (``kv_dtype="int8"``):
pool tensors are int8 with fp32 per-(page, head, slot)-row scales in
``caches["kv_scale"]``, writes quantize on the way in (admit scatter,
chunk scatter, decode append) and the attention kernels dequantize
in-VMEM into the fp32 softmax accumulator.  Scale arrays keep the page
axis at position 1, so page-indexed treemaps (COW copies, swap
gather/scatter) cover them with no special cases.

With ``prefix_cache=True`` the paged pool is additionally
**content-addressed and refcounted**: every committed full page carries a
rolling hash key (its token ids chained with the parent page's key), a
global prefix index maps key -> physical page, and a new prompt whose
leading pages hash to indexed entries is *seeded* with those pages
instead of re-running prefill over them.  Seeded pages are shared —
``refcount[p]`` counts the lanes referencing page ``p`` — and shared
pages are copy-on-write: a lane that must append KV into a shared page
first allocates a private copy.  Releasing a reference never frees an
indexed page outright; a refcount-zero indexed page stays resident as
*cached* capacity and is only reclaimed by the allocator under pool
pressure (LRU by last-hit tick, or FIFO by publish order — the
``PrefixPolicy`` tuning knobs).  Cached KV is bit-exact (the same
tokens at the same positions through the same kernels), so outputs with
caching on are bit-identical to caching off.

The engine talks to both backends through the same methods::

    admit(lane, prefill_caches, prompt_len) -> bool
    ensure_capacity(lane, pos) -> bool        # page alloc on boundary
    ensure_tokens(lane, n_tokens) -> bool     # chunk-granular growth
    swap_out(lane) -> handle                  # preemption
    swap_in(lane, handle) -> bool
    release(lane)
    decode_extra(mask_lanes) -> tuple         # (page_table,) when paged;
                                              # mid-prefill lanes masked
"""
from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..at.session import tuned
from ..distributed.compression import (dequantize_int8, quantize_int8,
                                       quantize_int8_rows)
from ..kernels.flash_attention import (paged_decode_block_k,
                                       paged_decode_page_bytes)

NULL_PAGE = 0

PREFIX_EVICTION_POLICIES = ("lru", "fifo")

KV_DTYPES = ("fp", "int8")


def _mesh_model_axis(mesh) -> int:
    """Size of the mesh's tensor-parallel ``model`` axis (1 == no mesh /
    no model axis / 1-device axis — all take the unsharded path)."""
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return int(mesh.shape["model"])


def _pool_shardings(caches: dict, mesh):
    """NamedShardings placing the KV-head axis of every stacked pool leaf
    over the mesh's ``model`` axis: pools are (L, P, Hkv, psz, D), scales
    (L, P, Hkv, psz) — head axis 2 in both; everything else replicated."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def spec(leaf):
        if leaf.ndim == 5:          # (L, P, Hkv, psz, D) pool
            return NamedSharding(mesh, P(None, None, "model", None, None))
        if leaf.ndim == 4:          # (L, P, Hkv, psz) scale
            return NamedSharding(mesh, P(None, None, "model", None))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(spec, caches)


@dataclass
class PackedTree:
    """int8-quantized host copy of a cache pytree (one scale per leaf).

    The lossy host-swap representation for *fp* pools
    (``swap_compress=True``): each leaf is stored as an int8 array plus
    one fp32 scale, quartering bf16 host bytes vs a raw fp32 copy and
    halving them vs bf16.  int8 pools never need this — their page
    payload is already int8 + per-row scales and round-trips bit-exactly.
    """

    payload: list[tuple[np.ndarray, float]]
    treedef: Any

    def host_bytes(self) -> int:
        return sum(q.nbytes + 4 for q, _ in self.payload)


def _pack_tree(tree) -> PackedTree:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    payload = []
    for leaf in leaves:
        q, s = quantize_int8(jnp.asarray(leaf))
        payload.append((np.asarray(q), float(s)))
    return PackedTree(payload, treedef)


def _unpack_tree(packed: PackedTree):
    leaves = [dequantize_int8(jnp.asarray(q), s)
              for q, s in packed.payload]
    return jax.tree_util.tree_unflatten(packed.treedef, leaves)


def _tree_bytes(tree) -> int:
    return int(sum(np.asarray(leaf).nbytes
                   for leaf in jax.tree_util.tree_leaves(tree)))


def chain_hash(parent: str, tokens: Sequence[int]) -> str:
    """Rolling page key: the page's token ids chained with the parent
    page's key, so a hit on page *b* implies the whole prefix through
    *b* matches (content addressing over prefixes, not bags of pages)."""
    data = parent + "|" + ",".join(str(int(t)) for t in tokens)
    return hashlib.sha1(data.encode()).hexdigest()


def _lane_set(full: jax.Array, one: jax.Array, lane: int) -> jax.Array:
    """Write batch entry 0 of ``one`` into lane ``lane`` of the stacked
    cache.

    Leaves are (L, B, ...) (layer-stacked) or (napp, B, ...); the batch
    axis is axis 1.  Only the target lane is ever written — even when the
    source happens to be full-width — so concurrent lanes' state is never
    clobbered.
    """
    src = one[:, 0]
    # pad/crop trailing dims (prefill cache len == prompt len)
    dst_shape = full.shape[2:]
    pads = []
    slices = [slice(None)] * src.ndim
    for i, (s, d) in enumerate(zip(src.shape[1:], dst_shape)):
        if s < d:
            pads.append((0, d - s))
        else:
            pads.append((0, 0))
            slices[i + 1] = slice(0, d)
    src = src[tuple(slices)]
    if any(p != (0, 0) for p in pads):
        src = jnp.pad(src, [(0, 0)] + pads)
    return full.at[:, lane].set(src.astype(full.dtype))


class DenseKVCache:
    """Per-lane contiguous KV strips (the pre-paging layout)."""

    kind = "dense"
    prefix_cache = False
    kv_dtype = "fp"

    def __init__(self, model, n_lanes: int, max_len: int,
                 swap_compress: bool = False):
        self.n_lanes = n_lanes
        self.max_len = max_len
        self.swap_compress = swap_compress
        self.caches = model.init_caches(n_lanes, max_len)

    # -- engine interface ---------------------------------------------------
    def prefill_len(self, prompt_len: int) -> int:
        return self.max_len

    def admit(self, lane: int, prefill_caches: Any, prompt_len: int) -> bool:
        self.caches = jax.tree.map(
            lambda full, one: _lane_set(full, one, lane),
            self.caches, prefill_caches)
        return True

    def ensure_capacity(self, lane: int, pos: int) -> bool:
        return pos < self.max_len

    def ensure_tokens(self, lane: int, n_tokens: int) -> bool:
        """Capacity for the first ``n_tokens`` positions (no-op when dense:
        the lane's strip is pre-sized)."""
        return n_tokens <= self.max_len

    def truncate_to(self, lane: int, committed_len: int) -> int:
        """Speculative rollback (no-op when dense: the lane's strip is
        pre-sized and stale KV past ``committed_len`` is masked by the
        decode step's ``kv_len``).  Returns pages freed (always 0)."""
        return 0

    def release(self, lane: int) -> None:
        pass

    def swap_out(self, lane: int) -> Any:
        handle = jax.tree.map(lambda a: np.asarray(a[:, lane]), self.caches)
        if self.swap_compress:
            return _pack_tree(handle)
        return handle

    def swap_in(self, lane: int, handle: Any) -> bool:
        if isinstance(handle, PackedTree):
            handle = _unpack_tree(handle)
        self.caches = jax.tree.map(
            lambda full, one: full.at[:, lane].set(
                jnp.asarray(one).astype(full.dtype)),
            self.caches, handle)
        return True

    def decode_extra(self, mask_lanes=()) -> tuple:
        return ()

    # -- accounting ---------------------------------------------------------
    def cache_tokens(self) -> int:
        """Token capacity held in device memory (fixed for dense)."""
        return self.n_lanes * self.max_len

    def pool_bytes(self) -> int:
        """Device bytes held by the cache, from the actual array dtypes."""
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(self.caches)))

    def kv_bytes_per_token(self) -> float:
        return self.pool_bytes() / float(self.n_lanes * self.max_len)

    def capacity_tokens(self) -> int:
        return self.n_lanes * self.max_len

    def stats(self) -> dict:
        return {"kind": self.kind, "kv_dtype": self.kv_dtype,
                "cache_tokens": self.cache_tokens(),
                "pool_bytes": self.pool_bytes(),
                "kv_bytes_per_token": self.kv_bytes_per_token(),
                "capacity_tokens": self.capacity_tokens()}


@dataclass
class PageHandle:
    """Host-side copy of a swapped-out sequence's pages.

    ``chunks`` — pytree of np arrays (page axis at position 1) for raw
    swaps; ``packed`` — the int8 :class:`PackedTree` form when the cache
    compresses fp-pool swaps (exactly one of the two is set).
    """

    chunks: Any          # pytree of np arrays, page axis at position 1
    n_blocks: int
    packed: PackedTree | None = None

    def host_bytes(self) -> int:
        if self.packed is not None:
            return self.packed.host_bytes()
        return _tree_bytes(self.chunks)


class PagedKVCache:
    """Block/paged KV cache with a refcounted free-page pool, host swap
    space, and an optional content-addressed prefix index.

    ``n_pages`` pages of ``page_size`` tokens (per layer) back every lane;
    a lane's logical block *b* lives in physical page ``table[lane, b]``.
    Without prefix caching pages are lane-exclusive while allocated; with
    ``prefix_cache=True`` full committed pages publish into the hash
    index and may be referenced by several lanes at once (``refcount``),
    in which case writes go through :meth:`cow_writable` first so the
    decode/prefill scatter still never races between lanes.
    """

    kind = "paged"

    def __init__(self, model, n_lanes: int, max_len: int, n_pages: int,
                 page_size: int = 16, prefix_cache: bool = False,
                 prefix_min_match: int = 1, prefix_eviction: str = "lru",
                 kv_dtype: str = "fp", swap_compress: bool = False,
                 mesh=None):
        if not model.supports_paged_cache:
            raise ValueError(
                f"arch {model.cfg.name!r} does not support the paged KV "
                "cache; use cache='dense'")
        if n_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                             f"(choose from {KV_DTYPES})")
        self.mesh = mesh
        model_axis = _mesh_model_axis(mesh)
        if model_axis > 1:
            hkv, h = model.cfg.n_kv_heads, model.cfg.n_heads
            if hkv % model_axis or h % model_axis:
                raise ValueError(
                    f"tensor-parallel paged serving shards the KV-head "
                    f"axis: arch {model.cfg.name!r} has kv_heads={hkv} "
                    f"(q heads {h}), not divisible by the mesh's 'model' "
                    f"axis of size {model_axis} — choose a mesh whose "
                    f"model axis divides the head counts, or drop --mesh")
        self.n_lanes = n_lanes
        self.max_len = max_len
        self.page_size = page_size
        self.n_pages = n_pages
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        # int8 pools swap their (already-compact) int8 payload losslessly;
        # the opt-in flag additionally compresses *fp*-pool swaps (lossy:
        # the bit-identical swap-continuation guarantee becomes int8-
        # round-trip-identical)
        self.swap_compress = swap_compress and not self.quantized
        self.max_blocks = math.ceil(max_len / page_size)
        self.caches = model.init_paged_caches(n_pages, page_size,
                                              quantized=self.quantized)
        if model_axis > 1:
            # place each device's KV-head slice of every pool on its own
            # device up front: the per-layer stacked pools are
            # (L, P, Hkv, psz, D) / scales (L, P, Hkv, psz), head axis 2
            self.caches = jax.device_put(
                self.caches, _pool_shardings(self.caches, mesh))
        self.table = np.zeros((n_lanes, self.max_blocks), np.int32)
        self.n_blocks = [0] * n_lanes
        # page 0 is the null page (idle-lane write sink), never allocated
        self._free = list(range(n_pages - 1, 0, -1))
        self.refcount = np.zeros(n_pages, np.int32)   # lane refs per page
        self.swap_outs = 0
        self.swap_ins = 0
        # -- prefix cache (content-addressed index over full pages) --------
        self.prefix_cache = prefix_cache
        self.prefix_min_match = max(1, int(prefix_min_match))
        self.set_prefix_policy(eviction=prefix_eviction)
        self._index: dict[str, int] = {}      # chain hash -> physical page
        self._page_key: dict[int, str] = {}   # physical page -> chain hash
        self._last_hit: dict[int, int] = {}   # page -> last match/publish
        self._pub_order: dict[int, int] = {}  # page -> publish tick (FIFO)
        self._chain: list[tuple[str, int]] = [("", 0)] * n_lanes
        self._tick = 0
        self._n_cached = 0
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.hit_tokens = 0                   # prompt tokens never re-run
        self.pages_saved = 0                  # seeded (not re-prefilled)
        self.cow_copies = 0
        self.index_evictions = 0

    # -- page pool ----------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def cached_pages(self) -> int:
        """Refcount-zero pages kept resident by the prefix index — the
        reclaimable middle state between used and free.  A maintained
        counter (updated at the refcount 0<->1 and publish/unpublish
        transitions), not a scan: ``_alloc``/``can_admit`` read it on
        the admission and page-boundary hot paths."""
        return self._n_cached

    @property
    def used_pages(self) -> int:
        """Pages referenced by at least one lane (shared pages count
        once)."""
        return (self.n_pages - 1) - len(self._free) - self.cached_pages

    def _alloc(self, n: int) -> list[int] | None:
        """Take ``n`` private pages (refcount 1 each): free pages first,
        then refcount-zero index entries evicted by the reuse policy."""
        if n > len(self._free) + self.cached_pages:
            return None
        pages = []
        for _ in range(n):
            p = self._free.pop() if self._free else self._evict_one()
            self.refcount[p] = 1
            pages.append(p)
        return pages

    def _evict_one(self) -> int:
        """Reclaim one refcount-zero index entry.  Policy: ``lru`` evicts
        the page whose index entry was hit longest ago; ``fifo`` evicts
        the oldest-published page regardless of hits."""
        order = self._last_hit if self.prefix_eviction == "lru" \
            else self._pub_order
        victim = min(
            (p for p in self._page_key if self.refcount[p] == 0),
            key=lambda p: order.get(p, 0))
        self._unpublish(victim)
        self.index_evictions += 1
        return victim

    def _unpublish(self, p: int) -> None:
        key = self._page_key.pop(p, None)
        if key is not None:
            if self._index.get(key) == p:
                del self._index[key]
            if self.refcount[p] == 0:
                self._n_cached -= 1
        self._last_hit.pop(p, None)
        self._pub_order.pop(p, None)

    def _unref(self, p: int) -> None:
        """Drop one lane reference.  A page only returns to the free list
        at refcount zero AND outside the index — indexed pages stay
        resident as cached capacity until the allocator evicts them."""
        p = int(p)
        if p == NULL_PAGE:
            return
        self.refcount[p] -= 1
        if self.refcount[p] <= 0:
            if p in self._page_key:
                self._n_cached += 1
            else:
                self._free.append(p)

    def _free_lane(self, lane: int) -> None:
        nblk = self.n_blocks[lane]
        for p in self.table[lane, :nblk]:
            self._unref(p)
        self.table[lane, :] = NULL_PAGE
        self.n_blocks[lane] = 0
        self._chain[lane] = ("", 0)

    # -- engine interface ---------------------------------------------------
    def prefill_len(self, prompt_len: int) -> int:
        """Page-aligned prefill cache length (tight, not max_len)."""
        return math.ceil(prompt_len / self.page_size) * self.page_size

    def can_admit(self, prompt_len: int) -> bool:
        return math.ceil(prompt_len / self.page_size) \
            <= len(self._free) + self.cached_pages

    def admit(self, lane: int, prefill_caches: Any, prompt_len: int) -> bool:
        nblk = math.ceil(prompt_len / self.page_size)
        pages = self._alloc(nblk)
        if pages is None:
            return False
        arr = np.asarray(pages, np.int32)

        def chunked(dense):
            # dense: (L, 1, Hkv, nblk*psz, D) -> (L, nblk, Hkv, psz, D)
            l, _, hkv, s, d = dense.shape
            return dense[:, 0].reshape(
                l, hkv, nblk, self.page_size, d).transpose(0, 2, 1, 3, 4)

        if self.quantized:
            # quantize-on-admit: the monolithic prefill cache is fp, the
            # pool is int8 + per-row scales
            k8, v8 = self.caches["kv"]
            ks, vs = self.caches["kv_scale"]
            kq, ksc = quantize_int8_rows(chunked(prefill_caches["kv"][0]))
            vq, vsc = quantize_int8_rows(chunked(prefill_caches["kv"][1]))
            self.caches = {
                "kv": (k8.at[:, arr].set(kq), v8.at[:, arr].set(vq)),
                "kv_scale": (ks.at[:, arr].set(ksc),
                             vs.at[:, arr].set(vsc)),
            }
        else:
            self.caches = jax.tree.map(
                lambda pool, dense: pool.at[:, arr].set(
                    chunked(dense).astype(pool.dtype)),
                self.caches, prefill_caches)
        self.table[lane, :nblk] = arr
        self.n_blocks[lane] = nblk
        return True

    def ensure_capacity(self, lane: int, pos: int) -> bool:
        """Make sure the page holding ``pos`` is allocated (called before
        every decode step; allocation happens on page-boundary crossings)."""
        if pos >= self.max_len:
            return False
        blk = pos // self.page_size
        if blk < self.n_blocks[lane]:
            return True
        page = self._alloc(1)
        if page is None:
            return False
        self.table[lane, blk] = page[0]
        self.n_blocks[lane] = blk + 1
        return True

    def ensure_tokens(self, lane: int, n_tokens: int) -> bool:
        """Chunk-granular growth: allocate pages until the lane covers
        positions ``[0, n_tokens)``.  Pages acquired before a failure stay
        allocated (they are tracked in ``n_blocks`` and either used by a
        later retry or freed wholesale on release/swap-out)."""
        if n_tokens > self.max_len:
            return False
        need = math.ceil(n_tokens / self.page_size)
        while self.n_blocks[lane] < need:
            page = self._alloc(1)
            if page is None:
                return False
            self.table[lane, self.n_blocks[lane]] = page[0]
            self.n_blocks[lane] += 1
        return True

    def truncate_to(self, lane: int, committed_len: int) -> int:
        """Speculative rollback: return the lane's over-allocated pages.

        Keeps exactly the pages covering positions ``[0, committed_len)``
        and frees the rest back to the pool, pointing the vacated page-
        table rows at the null page.  KV *within* the last kept page past
        ``committed_len`` may be stale (rejected drafts) — that is fine:
        reads mask by ``kv_len`` and the next accepted token overwrites
        its slot.  Returns the number of pages freed.
        """
        keep = math.ceil(committed_len / self.page_size)
        nblk = self.n_blocks[lane]
        if keep >= nblk:
            return 0
        for p in self.table[lane, keep:nblk]:
            self._unref(p)          # never frees a page another lane or
            #                         the prefix index still holds
        self.table[lane, keep:nblk] = NULL_PAGE
        self.n_blocks[lane] = keep
        return nblk - keep

    def release(self, lane: int) -> None:
        self._free_lane(lane)

    def swap_out(self, lane: int) -> PageHandle:
        """Copy the lane's pages to host memory and free them.

        int8 pools swap their native payload (int8 pages + fp32 per-row
        scales: already ~half the fp bytes, and the round trip is
        bit-exact).  fp pools copy raw unless ``swap_compress`` is set,
        which packs each leaf through :func:`quantize_int8` instead —
        half the bf16 host bytes, int8-round-trip accuracy.
        """
        nblk = self.n_blocks[lane]
        pages = np.asarray(self.table[lane, :nblk], np.int32)
        chunks = jax.tree.map(lambda pool: np.asarray(pool[:, pages]),
                              self.caches)
        self._free_lane(lane)
        self.swap_outs += 1
        if self.swap_compress:
            return PageHandle(chunks=None, n_blocks=nblk,
                              packed=_pack_tree(chunks))
        return PageHandle(chunks=chunks, n_blocks=nblk)

    def swap_in(self, lane: int, handle: PageHandle) -> bool:
        pages = self._alloc(handle.n_blocks)
        if pages is None:
            return False
        arr = np.asarray(pages, np.int32)
        chunks = handle.chunks if handle.packed is None \
            else _unpack_tree(handle.packed)
        self.caches = jax.tree.map(
            lambda pool, chunk: pool.at[:, arr].set(
                jnp.asarray(chunk).astype(pool.dtype)),
            self.caches, chunks)
        self.table[lane, :handle.n_blocks] = arr
        self.table[lane, handle.n_blocks:] = NULL_PAGE
        self.n_blocks[lane] = handle.n_blocks
        self._chain[lane] = ("", 0)   # resumed prefill re-walks the chain
        self.swap_ins += 1
        return True

    def decode_extra(self, mask_lanes=()) -> tuple:
        """Page table for the decode step.  ``mask_lanes`` (mid-prefill
        lanes) get a zeroed row: the batched decode step still runs over
        every lane slot, and masking routes those lanes' dummy KV writes
        to the null page instead of their live prefill pages."""
        tbl = self.table
        if mask_lanes:
            tbl = tbl.copy()
            tbl[list(mask_lanes), :] = NULL_PAGE
        return (jnp.asarray(tbl),)

    def decode_tile(self) -> int:
        """Tokens per grid step of the paged decode kernel over this pool
        and table, under the published ``block_k``: the kernel's own
        resolver, with the KV heads one device holds."""
        k = self.caches["kv"][0]                  # (L, P, Hkv, psz, D)
        hkv = k.shape[2] // _mesh_model_axis(self.mesh)
        return paged_decode_block_k(
            tuned("flash_paged_decode").get("block_k"),
            page_size=self.page_size, table_pages=self.max_blocks,
            page_bytes=paged_decode_page_bytes(hkv, self.page_size,
                                               k.shape[-1],
                                               k.dtype.itemsize,
                                               self.quantized))

    def table_row(self, lane: int) -> jax.Array:
        """This lane's logical->physical mapping, shaped (1, nblk) for the
        single-sequence prefill-chunk step."""
        return jnp.asarray(self.table[lane:lane + 1])

    # -- prefix cache: match / seed / publish / copy-on-write --------------
    def set_prefix_policy(self, min_match: int | None = None,
                          eviction: str | None = None) -> None:
        """Reuse-policy knobs (the ``PrefixPolicy`` tuning region's PPs):
        ``min_match`` — minimum consecutive page hits before a match is
        used at all (tiny hits may not pay for their bookkeeping);
        ``eviction`` — ``lru`` | ``fifo`` reclaim order for refcount-zero
        index entries."""
        if min_match is not None:
            self.prefix_min_match = max(1, int(min_match))
        if eviction is not None:
            if eviction not in PREFIX_EVICTION_POLICIES:
                raise ValueError(
                    f"unknown prefix eviction policy {eviction!r} "
                    f"(choose from {PREFIX_EVICTION_POLICIES})")
            self.prefix_eviction = eviction

    def match_prefix(self, prompt: Sequence[int]
                     ) -> tuple[list[int], str]:
        """Walk the prompt's full pages through the chained-hash index.

        Returns (matched physical pages, chain key of the last hit) —
        the longest indexed prefix, cut to empty when shorter than the
        ``min_match`` granularity.  Pure lookup: no refcounts move.
        """
        pages: list[int] = []
        chain = ""
        if not self.prefix_cache:
            return pages, chain
        psz = self.page_size
        for b in range(len(prompt) // psz):
            key = chain_hash(chain, prompt[b * psz:(b + 1) * psz])
            p = self._index.get(key)
            if p is None:
                break
            pages.append(p)
            chain = key
        if len(pages) < self.prefix_min_match:
            return [], ""
        return pages, chain

    def seed_prefix(self, lane: int, prompt: Sequence[int]) -> int:
        """Admission-time reuse: point the lane's leading page-table rows
        at the matched shared pages (refcount++) instead of re-running
        prefill over them.

        Returns the position prefill starts at — the cached token count,
        capped at ``len(prompt) - 1`` so the final prompt position is
        always recomputed (its logits emit the request's first token).
        With a fully-cached page-aligned prompt that recomputed position
        lands *inside* the last shared page; the write triggers the
        copy-on-write path in :meth:`cow_writable`.
        """
        if not self.prefix_cache:
            return 0
        self._tick += 1
        self.prefix_lookups += 1
        pages, chain = self.match_prefix(prompt)
        if not pages:
            self._chain[lane] = ("", 0)
            return 0
        for p in pages:
            if self.refcount[p] == 0:       # cached -> used transition
                self._n_cached -= 1
            self.refcount[p] += 1
            self._last_hit[p] = self._tick
        self.table[lane, :len(pages)] = np.asarray(pages, np.int32)
        self.table[lane, len(pages):] = NULL_PAGE
        self.n_blocks[lane] = len(pages)
        self._chain[lane] = (chain, len(pages))
        start = min(len(pages) * self.page_size, len(prompt) - 1)
        self.prefix_hits += 1
        self.hit_tokens += start
        self.pages_saved += len(pages)
        return start

    def publish_prefix(self, lane: int, prompt: Sequence[int],
                       upto: int) -> None:
        """Publish the lane's newly-full committed prompt pages into the
        index (called after each prefill chunk lands; ``upto`` = prompt
        tokens committed so far).  Only *full* pages publish — a partial
        page's KV is still being appended to.  A key already indexed
        (another lane published the same prefix first) keeps its existing
        entry; this lane's copy stays private."""
        if not self.prefix_cache:
            return
        psz = self.page_size
        chain, done = self._chain[lane]
        n_full = min(int(upto), len(prompt)) // psz
        for b in range(done, n_full):
            key = chain_hash(chain, prompt[b * psz:(b + 1) * psz])
            self._tick += 1
            p = self._index.get(key)
            if p is None:
                p = int(self.table[lane, b])
                if p != NULL_PAGE:
                    self._index[key] = p
                    self._page_key[p] = key
                    self._pub_order[p] = self._tick
            self._last_hit[p] = self._tick
            chain = key
        self._chain[lane] = (chain, max(done, n_full))

    def cow_writable(self, lane: int, pos: int) -> bool:
        """Copy-on-write guard: make the page holding ``pos`` privately
        writable before a KV write lands there.

        A page is *not* writable in place when another lane references it
        (refcount > 1) or when it backs an index entry (writing would
        silently diverge its content from its hash).  Either way the lane
        gets a fresh private copy of the page's pool content and drops
        its shared reference.  Returns False only when the pool cannot
        supply the copy (page pressure — caller preempts).
        """
        if not self.prefix_cache:
            return True
        blk = int(pos) // self.page_size
        if blk >= self.n_blocks[lane]:
            return True                  # page not allocated yet: fresh
        p = int(self.table[lane, blk])
        if p == NULL_PAGE:
            return True
        if self.refcount[p] <= 1 and p not in self._page_key:
            return True                  # already private
        fresh = self._alloc(1)
        if fresh is None:
            return False
        q = fresh[0]
        self.caches = jax.tree.map(
            lambda pool: pool.at[:, q].set(pool[:, p]), self.caches)
        self._unref(p)
        self.table[lane, blk] = q
        self.cow_copies += 1
        return True

    # -- accounting ---------------------------------------------------------
    def cache_tokens(self) -> int:
        """Token capacity currently held by live sequences."""
        return self.used_pages * self.page_size

    def pool_bytes(self) -> int:
        """Device bytes held by the pool, from the *actual* leaf dtypes
        (int8 pools count 1 byte/element plus their fp32 scale rows, not
        the model compute dtype)."""
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(self.caches)))

    def kv_bytes_per_token(self) -> float:
        return self.pool_bytes() / float(self.n_pages * self.page_size)

    def capacity_tokens(self) -> int:
        """Allocatable token capacity (page 0 is the reserved null page)."""
        return (self.n_pages - 1) * self.page_size

    def stats(self) -> dict:
        out = {"kind": self.kind, "page_size": self.page_size,
               "n_pages": self.n_pages, "used_pages": self.used_pages,
               "free_pages": self.free_pages,
               "cached_pages": self.cached_pages,
               "cache_tokens": self.cache_tokens(),
               "kv_dtype": self.kv_dtype,
               "pool_bytes": self.pool_bytes(),
               "kv_bytes_per_token": self.kv_bytes_per_token(),
               "capacity_tokens": self.capacity_tokens(),
               "swap_outs": self.swap_outs, "swap_ins": self.swap_ins}
        if self.prefix_cache:
            out["prefix"] = {
                "lookups": self.prefix_lookups,
                "hits": self.prefix_hits,
                "hit_tokens": self.hit_tokens,
                "pages_saved": self.pages_saved,
                "cached_pages": self.cached_pages,
                "cow_copies": self.cow_copies,
                "evictions": self.index_evictions,
                "min_match": self.prefix_min_match,
                "eviction": self.prefix_eviction,
            }
        return out


def make_kv_cache(model, cache: str, n_lanes: int, max_len: int,
                  n_pages: int | None = None, page_size: int = 16,
                  prefix_cache: bool = False, prefix_min_match: int = 1,
                  prefix_eviction: str = "lru", kv_dtype: str = "fp",
                  swap_compress: bool = False, mesh=None):
    """Build a KV-cache backend by name (``dense`` | ``paged``)."""
    if cache == "dense":
        if prefix_cache:
            raise ValueError(
                "prefix caching shares pages of the paged pool; "
                "use cache='paged'")
        if kv_dtype != "fp":
            raise ValueError(
                "quantized KV storage is a paged-pool feature; "
                "use cache='paged'")
        if _mesh_model_axis(mesh) > 1:
            raise ValueError(
                "tensor-parallel serving shards the paged page pools; "
                "use cache='paged' with --mesh")
        return DenseKVCache(model, n_lanes, max_len,
                            swap_compress=swap_compress)
    if cache == "paged":
        if n_pages is None:
            # default pool: enough for every lane at full length (parity
            # with dense), callers shrink it to see paging pay off
            n_pages = n_lanes * math.ceil(max_len / page_size) + 1
        return PagedKVCache(model, n_lanes, max_len, n_pages, page_size,
                            prefix_cache=prefix_cache,
                            prefix_min_match=prefix_min_match,
                            prefix_eviction=prefix_eviction,
                            kv_dtype=kv_dtype, swap_compress=swap_compress,
                            mesh=mesh)
    raise ValueError(f"unknown cache backend {cache!r}")
