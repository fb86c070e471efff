"""Paged KV cache for the decode engine: block tables + page pool.

The round-2 engine kept a dense per-slot slab ``[n_layers, S, T, KH, hd]`` —
O(S·T) HBM regardless of use, which caps serving at short contexts (a 1.5B
model at S=128, T=32K would need ~118 GB; VERDICT r02 "What's missing" #1).
This module replaces it with the design SURVEY §7.1 names ("paged KV cache
(Pallas), continuous batching, prefix cache") and the role SGLang's
paged/radix allocator plays for the reference
(reference blog/AReaL_v0_3.md:266 trains 27K-token generations on it):

- **PagePool** (host): refcounted free-list allocator over a fixed pool of
  ``n_pages`` pages of ``page_size`` tokens. Page 0 is reserved as a trash
  page — padded prefill rows scatter there harmlessly.
- **device cache**: ``k``/``v`` are ``[n_layers, KH, n_pages, page_size, hd]``
  (the layout jax's TPU paged-attention kernel expects per layer). KV memory
  is proportional to *used* tokens, not slots × max_len.
- **page aliasing** replaces the dense engine's KV row copy for GRPO
  prefix sharing: duplicate prompts share full prompt pages (refcount++)
  and copy only the final partial page (copy-on-write boundary: decode
  writes land at ``pos >= prompt_len``, so shared full pages are immutable).

Attention over pages:
- TPU: ``ops/paged_attention_q8.py paged_attention_stacked`` (flash-style
  kernel reading only each sequence's pages, over the layer-stacked pool).
- elsewhere (CPU tests / TP fallback): ``paged_attention_xla`` gathers the
  window's pages and runs the same grouped masked einsum the dense engine
  used — identical numerics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class PagePool:
    """Host-side refcounted page allocator.

    Page 0 is reserved (trash page for padded scatter targets); ``alloc``
    never returns it. Not thread-safe — the decode loop is the only caller.
    """

    def __init__(self, n_pages: int):
        assert n_pages >= 2, "pool needs at least one allocatable page"
        self.n_pages = n_pages
        self._free: list[int] = list(range(n_pages - 1, 0, -1))  # pop() -> 1 first
        self._rc = np.zeros(n_pages, np.int32)
        self._rc[0] = 1  # trash page: permanently held

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def used(self) -> int:
        return self.n_pages - 1 - len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """Allocate n pages (rc=1 each) or None if the pool can't cover it."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._rc[pages] = 1
        return pages

    def ref(self, pages: list[int]) -> None:
        """Increment refcounts (page aliasing for shared prefixes)."""
        for p in pages:
            assert self._rc[p] > 0, f"ref of unallocated page {p}"
            self._rc[p] += 1

    def free(self, pages: list[int]) -> None:
        """Decrement refcounts; pages reaching zero return to the free list."""
        for p in pages:
            if p == 0:
                continue
            assert self._rc[p] > 0, f"double free of page {p}"
            self._rc[p] -= 1
            if self._rc[p] == 0:
                self._free.append(p)


class _RadixNode:
    """One full page of cached prompt KV: ``key`` is the page's token-id
    tuple (length = page_size), ``page`` the pool page holding its KV, and
    ``version`` the policy version the KV was computed under (stamped at
    allocation; a page whose rows span a weight commit keeps the OLDER
    stamp, so staleness checks stay conservative)."""

    __slots__ = ("key", "page", "version", "children", "parent", "last_access")

    def __init__(self, key, page, version, parent, tick):
        self.key = key
        self.page = page
        self.version = version
        self.parent = parent
        self.children: dict[tuple, _RadixNode] = {}
        self.last_access = tick


class RadixPrefixCache:
    """Cross-request prefix cache over the refcounted page pool.

    A radix tree keyed on token ids at PAGE granularity: every node is one
    full page (``page_size`` tokens), children keyed by the next page's
    token tuple — so the longest cached prefix of any prompt is a plain
    walk, with no edge-splitting (prefixes are page-aligned by
    construction; the decode head's write page is never published). This is
    the cross-request generalization of the engine's GRPO same-prompt
    aliasing — the role SGLang's RadixAttention plays for the reference.

    Ownership: the tree holds ONE pool reference per node page (taken at
    ``insert``, released at evict/flush). Matched pages are aliased by the
    caller with its own ``pool.ref`` — so eviction/flush never invalidates
    a live slot, it only drops the tree's claim.

    LRU: a monotonic tick (not wall clock) stamps every matched/inserted
    path; eviction removes least-recently-used LEAVES only, so an interior
    node can never be removed while live children still chain through it.

    Not thread-safe — the decode loop is the only caller (same contract as
    PagePool).
    """

    def __init__(self, pool: PagePool, page_size: int, max_pages: int):
        assert page_size > 0 and max_pages >= 0
        self.pool = pool
        self.page_size = page_size
        self.max_pages = max_pages
        self.root = _RadixNode((), -1, -1, None, 0)
        self._n_pages = 0
        self._tick = 0
        # structural stats only: HIT accounting (hits/hit_tokens) belongs
        # to the caller, which can de-duplicate retried lookups for the
        # same admission (a backlogged task re-matches every wave)
        self.stats = {
            "lookups": 0,
            "inserts": 0,
            "inserted_pages": 0,
            "evicted_pages": 0,
            "flushes": 0,
        }

    @property
    def pages_held(self) -> int:
        return self._n_pages

    def pages(self) -> list[int]:
        """The page of every node: what the tree holds one reference each on."""
        out = []
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            out.append(n.page)
        return out

    def _touch(self) -> int:
        self._tick += 1
        return self._tick

    def match(
        self, ids, max_pages: int | None = None
    ) -> tuple[list[int], list[int]]:
        """Longest cached page-aligned prefix of ``ids``.

        Returns (pages, versions), one entry per matched page. ``max_pages``
        caps the walk (callers pass ``(plen-1)//page_size`` so the page the
        decode head writes into is never aliased). The caller must take its
        own pool refs on the returned pages before using them."""
        psz = self.page_size
        tick = self._touch()
        self.stats["lookups"] += 1
        node = self.root
        pages: list[int] = []
        versions: list[int] = []
        limit = len(ids) // psz
        if max_pages is not None:
            limit = min(limit, max_pages)
        for i in range(limit):
            key = tuple(ids[i * psz : (i + 1) * psz])
            child = node.children.get(key)
            if child is None:
                break
            child.last_access = tick
            pages.append(child.page)
            versions.append(child.version)
            node = child
        return pages, versions

    def lookup_extension(self, ids, k: int) -> list[int]:
        """Draft continuation tokens for ``ids`` from the tree (speculative
        decoding's radix prompt-lookup source): walk the cached full pages
        of ``ids``, then follow children whose keys continue the
        partial-page tail and return up to ``k`` cached tokens beyond
        ``len(ids)``. Read-only — no pool refs, no LRU touch; the result
        is a draft PROPOSAL the verify forward scores before anything is
        emitted, so a stale or mid-eviction answer only lowers acceptance,
        never correctness."""
        psz = self.page_size
        node = self.root
        for i in range(len(ids) // psz):
            child = node.children.get(tuple(ids[i * psz : (i + 1) * psz]))
            if child is None:
                return []
            node = child
        tail = tuple(ids[(len(ids) // psz) * psz :])
        out: list[int] = []
        while len(out) < k:
            step = None
            for key, child in node.children.items():
                if key[: len(tail)] == tail:
                    step = (key, child)
                    break
            if step is None:
                break
            key, node = step
            out.extend(key[len(tail) :])
            tail = ()
        return out[:k]

    def insert(self, ids, pages, versions) -> int:
        """Publish full prompt pages: one node per page of ``ids``
        (``len(pages)`` pages; ids beyond ``len(pages) * page_size`` are
        ignored). Existing nodes keep their page (the caller's duplicate
        page follows its normal free path); NEW nodes take a tree-owned
        ``pool.ref`` on the caller's page. Returns pages newly adopted.

        Capacity: before adopting beyond ``max_pages``, LRU leaves evict —
        excluding this very insertion path (evicting the chain's own tail
        would detach everything chained below it, leaking the pages); if
        nothing else is evictable, the remaining suffix is simply not
        published."""
        psz = self.page_size
        tick = self._touch()
        node = self.root
        adopted = 0
        path_ids: set[int] = set()
        for i, page in enumerate(pages):
            key = tuple(ids[i * psz : (i + 1) * psz])
            if len(key) < psz:
                break
            child = node.children.get(key)
            if child is None:
                if self._n_pages >= self.max_pages:
                    self.evict(
                        self._n_pages - self.max_pages + 1, _exclude=path_ids
                    )
                if self._n_pages >= self.max_pages:
                    break
                child = _RadixNode(key, page, versions[i], node, tick)
                node.children[key] = child
                self.pool.ref([page])
                self._n_pages += 1
                adopted += 1
            else:
                child.last_access = tick
            node = child
            path_ids.add(id(node))
        if adopted:
            self.stats["inserts"] += 1
            self.stats["inserted_pages"] += adopted
        return adopted

    def _leaves(self) -> list[_RadixNode]:
        out = []
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            else:
                out.append(n)
        return out

    def evict(self, n_pages: int, _exclude: set[int] | None = None) -> int:
        """Free up to ``n_pages`` tree-held pages, LRU leaves first. A
        parent becomes evictable only once all its children are gone —
        interior nodes are never removed out from under live children.
        ``_exclude``: node ids an in-progress insert is chaining through
        (its own path must never be evicted from under it).

        One DFS builds a leaf min-heap; a parent enters the heap the
        moment its last child is removed — so a multi-page reclaim is
        O(tree + evicted·log leaves), not one full traversal per page."""
        import heapq

        def allowed(n: _RadixNode) -> bool:
            return _exclude is None or id(n) not in _exclude

        heap = [
            (n.last_access, id(n), n) for n in self._leaves() if allowed(n)
        ]
        heapq.heapify(heap)
        freed = 0
        while freed < n_pages and heap:
            _, _, victim = heapq.heappop(heap)
            parent = victim.parent
            self._remove_leaf(victim)
            freed += 1
            if parent is not self.root and not parent.children and allowed(parent):
                heapq.heappush(heap, (parent.last_access, id(parent), parent))
        self.stats["evicted_pages"] += freed
        return freed

    def _remove_leaf(self, node: _RadixNode) -> None:
        assert not node.children, "evicting an interior node would orphan children"
        del node.parent.children[node.key]
        self.pool.free([node.page])
        self._n_pages -= 1

    def flush(self) -> int:
        """Drop every node (the across-updates "flush" policy at weight
        commit: cached KV is stale under the new policy). Pages also aliased
        by live slots survive in the pool until those slots free them."""
        freed = 0
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self.pool.free([n.page])
            freed += 1
        self.root.children.clear()
        self._n_pages = 0
        self.stats["flushes"] += 1
        self.stats["evicted_pages"] += freed
        return freed


# KV quantization convention: scale = max|x| over head_dim, q = rint(
# x * 127.5 / scale). fp8 (float8_e4m3fn) pages keep the SAME stored-value
# semantics (q = x * 127.5 / scale, no rounding clip — the values sit well
# inside e4m3's ±448 range), so ONE dequant formula
# ``q.astype(f32) * scale / 127.5`` serves both dtypes through every kernel.
#
# Scales in the page pool are LANE-MAJOR: [..., n_pages, 1, page_size], one
# f32 per token vector with the page's tokens as the minor dimension. The
# natural trailing-1 shape ([..., page_size, 1]) pads each scale to a whole
# 128-lane row in HBM and cannot be DMA-sliced by a TPU kernel; lane-major
# is compact and lets the kernels scale logits/probabilities by column
# (ops/paged_attention_q8.py).
_MAX_INT8 = 127.5
_QUANT_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


def page_quant(name) -> "str | bool":
    """``ServerConfig.kv_quantization`` as the ``quant`` every function here
    takes: "int8" -> int8 pages, "fp8" -> float8_e4m3fn pages (both carry
    narrow f32 scales and share one dequant formula), False for pages in the
    model's dtype. An unknown name is refused."""
    if name not in (None, "", "none", "int8", "fp8"):
        raise ValueError(f"unknown kv_quantization {name!r}")
    return name if name in ("int8", "fp8") else False


def quant_dtype(quant) -> "jnp.dtype | None":
    """Normalize a quant flag (bool | "int8" | "fp8") to a page dtype.
    ``True`` keeps the historical int8 meaning."""
    if not quant:
        return None
    if quant is True:
        return jnp.int8
    if quant in _QUANT_DTYPES:
        return _QUANT_DTYPES[quant]
    raise ValueError(f"unknown kv quant mode {quant!r}")


def quantize_kv(x: jax.Array, dtype=jnp.int8) -> tuple[jax.Array, jax.Array]:
    """[..., hd] float -> (int8/fp8 [..., hd], f32 scale [..., 1])."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True), 1e-12)
    q = x32 * (_MAX_INT8 / scale)
    if dtype == jnp.int8:
        # clip: rint(127.5) would be 128, which wraps in int8 (a latent bug
        # in the library's own to_int8)
        q = jnp.clip(jnp.rint(q), -127, 127)
    return q.astype(dtype), scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * (scale / _MAX_INT8)).astype(dtype)


def quantize_pages(pages: jax.Array, dtype=jnp.int8) -> tuple[jax.Array, jax.Array]:
    """[..., psz, hd] float pages -> (int8/fp8 pages, f32 scales in the
    pool's lane-major layout [..., 1, psz])."""
    q, scale = quantize_kv(pages, dtype)
    return q, jnp.swapaxes(scale, -1, -2)


def kv_token_bytes(pools: dict[str, tuple[int, int]], n_layers: int, itemsize: int, quant=False) -> int:
    """Bytes a cached token holds across ``n_layers`` layers in the pools
    ``{name: (heads, lanes)}`` of ``cfg.kv_pools``: K and V, a latent row, or
    a latent row and an index key of another width beside it. ``quant``
    (bool | "int8" | "fp8"): both quantized dtypes are 1 byte per element
    plus a 4-byte f32 scale per token vector."""
    return n_layers * sum(heads * (lanes * (1 if quant else itemsize) + (4 if quant else 0)) for heads, lanes in pools.values())


def n_pages_for_budget(
    budget_bytes: int, n_layers: int, num_kv_heads: int, page_size: int,
    head_dim: int, itemsize: int, quant=False, pools: "int | dict[str, tuple[int, int]]" = 2,
) -> int:
    """Pages fitting a KV HBM budget (every pool across all layers per
    page). ``pools`` is ``cfg.kv_pools``, whose rows may differ in width, or
    a count of pools of ``num_kv_heads`` x ``head_dim`` each (K and V: 2)."""
    if isinstance(pools, int):
        pools = {str(i): (num_kv_heads, head_dim) for i in range(pools)}
    return max(2, budget_bytes // (page_size * kv_token_bytes(pools, n_layers, itemsize, quant)))


# The cache holds two kinds of thing under one dict, donated and returned
# whole by every serving program. K and V PAGES (``k``, ``v`` and their
# scales) exist for the layers that attend (``cfg.num_kv_layers``): paged,
# aliased between requests by refcount, harmless to write twice. A
# latent-attention model (``cfg.kv_pools`` names no ``v``) keeps ONE row a
# token and layer there, ``[c | k_r | 0]`` under a single "head": the key all
# query heads share, whose first ``kv_lora_rank`` lanes are its value too;
# the pool, the refcounts, a group's aliasing and the copy of the last
# partial page are the same code on a row of another width. Where its layers
# have a learned index, the token's index key lies in a second pool ``idx`` of
# its own width (128 lanes beside 640) under the SAME page ids: one page
# table, one refcount, every program writes and copies both. The
# slot-indexed recurrent STATE of a model with state-space layers
# (``cfg.state_shapes(slots)``; none for a model that only attends) is none
# of these: one row per decode slot, never aliased, and it cannot be cut back
# to a token boundary after the fact. The invariant every program keeps:
#
#     a slot's state is the state after exactly the tokens the host believes
#     the slot has consumed.
#
# So prefill masks what it feeds the state instead of relying on overwrite,
# a decode step changes live slots only, a GRPO sibling gets a COPY of its
# primary's post-prompt state, and a parked slot keeps its rows untouched.
# Three tenants (models/hybrid.py): a Mamba-2 layer's SSM state and conv window
# (``ssm`` + ``conv``, 76 MB a slot at granite-4.0-h-micro), a short-conv
# layer's window alone (``conv``: the last two gate products, 90 KB a slot at
# LFM2-8B-A1B's 11 conv layers), and a gated-delta-rule layer's matrix state
# and its three conv windows (``gdn`` + ``conv``: 30 heads of 96 x 192 in
# float32, 2.2 MB a layer and slot at Olmo-Hybrid-7B). All go through the same
# programs.
# A fourth tenant is a Mamba-1 layer's state and window (``ssm`` [state size,
# channels] + ``conv``: 0.36 MB a layer and slot at Phi-4-mini-flash), a fifth
# the matrix state of a delta rule whose decay is a vector over the key
# channels, and its three conv windows (``kda`` [layers, slots, H, K, V] +
# ``conv``: 64 heads of 128 x 128 in float32, 4.19 MB a layer and slot at
# Solar-Open2, beside an expert share; the fan-out copy of a group of 8 moves
# 7 x 25 MB of it).
STATE_LEAVES = ("ssm", "conv", "gdn", "kda")
# Between the two stand the RINGS of layers that attend to the last
# ``sliding_window`` tokens only (``cfg.kv_groups``: which layers a group of
# pools serves and how long they keep a token, is the model configuration's
# to say): ``ring_k`` / ``ring_v`` [window layers, heads, slots + 1, pages a
# ring, page, lanes]. A slot owns ``cfg.ring_pages(page_size)`` pages a
# window layer, the same ones for as long as it lives, whatever its context:
# token t lies at ring position t % sliding_window, so after a write at
# position p the ring holds exactly tokens p - sliding_window + 1 .. p, the
# keys the query at p attends to, and min(p + 1, sliding_window) of its
# positions are valid. Without a positional embedding the order of the keys
# under a softmax says nothing, so a read is ``paged attention`` over the
# ring's pages at that length: no lower bound, no mask of its own. Like the
# state a ring is slot-indexed, never aliased, written for live slots only,
# COPIED to a group's siblings and left as it is while its request is
# parked; like pages it takes a token's row twice without harm. The block
# past the last slot takes a padding row's prefill and the decode writes of
# slots that are not live. Merging axes 2 and 3 gives the pools' own layout
# [layers, heads, pages, page, lanes] (``ring_pool``) under the page table
# ``ring_table``.
# WITH a rotary embedding (``cohere2_moe``'s window layers) a key is written
# ALREADY rotated at its own position: q_t . k_s then depends on t - s alone
# wherever k_s lies in the ring, so the read is still paged attention over the
# ring as it lies. A ring may be a slot's ONLY tenant beside its pages (no
# STATE leaf at all: ``cfg.has_slot_tenant`` without ``has_recurrent_state``):
# the sibling copy, parking, preemption and re-prefill run for it alone, the
# radix cache stays off (``serving_limits`` reason ``window_rings``: a cached
# prefix of the full layers' pages is no prefix of a ring) and
# ``areal_decode_state_copies_total`` / ``_state_prefills_total`` count its
# copies and rebuilds. At a window of 4,096 a ring is 32 pages of 128 tokens
# a slot and layer (16.8 MB at 8 KV heads of 128; 50.3 MB a slot over three
# window layers, copied to 7 siblings a group of 8).
RING_LEAVES = ("ring_k", "ring_v")


def ring_pool(ring: jax.Array) -> jax.Array:
    """A ring leaf as a page pool [layers, heads, (slots + 1) * pages a ring,
    page, lanes]: slot s's pages are s * pages .. (s + 1) * pages - 1."""
    n, heads, blocks, pages, psz, lanes = ring.shape
    return ring.reshape(n, heads, blocks * pages, psz, lanes)


def ring_table(slots: int, pages: int) -> jax.Array:
    """The rings' page table [slots, pages a ring] into ``ring_pool``."""
    return (jnp.arange(slots, dtype=jnp.int32)[:, None] * pages + jnp.arange(pages, dtype=jnp.int32)[None, :])


def ring_of_rows(rows: jax.Array, n_tokens: jax.Array, window: int, ring: tuple[int, int]) -> jax.Array:
    """A slot's ring after a prompt: ``rows`` [L, heads, lanes] (a window
    layer's K or V of every prompt position), of which the first ``n_tokens``
    are real -> [heads, pages a ring, page, lanes] with, at ring position r,
    the LAST real token t with t % window == r (positions no token has
    reached yet hold what row 0 holds: a read's length leaves them out)."""
    pages, psz = ring
    r = jnp.arange(window, dtype=jnp.int32)
    last = n_tokens.astype(jnp.int32) - 1
    t = last - (last - r) % window  # the largest t <= last with t % window == r; negative where none
    picked = rows[jnp.clip(t, 0, rows.shape[0] - 1)]  # [window, heads, lanes]
    picked = jnp.pad(picked, ((0, pages * psz - window), (0, 0), (0, 0)))
    return picked.reshape(pages, psz, *rows.shape[1:]).transpose(2, 0, 1, 3)


def band_pairs(n_tokens: int, window: int) -> int:
    """(query, key) pairs inside the band of a prompt of ``n_tokens`` in ONE
    window layer: ``sum_t min(t + 1, window)``, the work of its prompt pass
    whatever computes it (``areal_decode_window_prompt_pairs_total``)."""
    full = min(n_tokens, window)
    return full * (full + 1) // 2 + max(0, n_tokens - window) * window


def init_paged_cache(
    cfg, n_pages: int, page_size: int, dtype=None, quant=False, slots: int = 0
) -> dict:
    """The page pools ``cfg.kv_pools`` names, each [n_kv_layers, heads,
    n_pages, page_size, lanes]: k and v, or a latent model's k alone. With
    ``quant`` (True/"int8" or "fp8") the pages are int8 or float8_e4m3fn
    plus per-token-vector f32 scales, lane-major ([..., 1, psz]) — halved
    KV HBM traffic, the decode bottleneck at long context. Beside them the
    zeroed recurrent state of ``slots`` decode slots, where the model has
    any (see STATE_LEAVES above), and the window layers' rings (RING_LEAVES)."""
    dtype = dtype or cfg.jax_dtype
    qdtype = quant_dtype(quant)
    cache = {}
    for name, (heads, lanes) in cfg.kv_pools.items():
        shape = (cfg.num_kv_layers, heads, n_pages, page_size, lanes)
        cache[name] = jnp.zeros(shape, qdtype or dtype)
        if qdtype is not None:
            cache[f"{name}_scale"] = jnp.ones(shape[:-2] + (1, page_size), jnp.float32)
    for name, (sshape, sdtype) in cfg.state_shapes(slots).items():
        cache[name] = jnp.zeros(sshape, sdtype)
    for name, (rshape, rdtype) in cfg.ring_shapes(slots, page_size).items():
        cache[name] = jnp.zeros(rshape, rdtype)
    return cache


def paged_cache_specs(quant: bool = False, pools=("k", "v")):
    """PartitionSpecs of the page pools ``pools`` (``cfg.kv_pools``) and,
    under ``quant``, their scales: KV heads shard over the TP axis when they
    divide."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, "model", None, None, None)
    return {name: spec for pool in pools for name in ((pool, f"{pool}_scale") if quant else (pool,))}


def scatter_prefill(cache: dict, ks: jax.Array, vs: jax.Array | None, flat_pages: jax.Array, page_size: int) -> dict:
    """Write a batched prefill's KV into pages.

    ks/vs: [n_layers, A, bucket, KH, hd] from qwen.forward_prefill (vs None
    for a latent model: its rows are ks alone);
    flat_pages: [A * ceil(bucket/page_size)] int32 page ids row-major per
    prompt (padded positions -> trash page 0; duplicate trash writes are
    benign). A bucket shorter than one page (tiny max_seq_len) pads up.
    """
    L, A, bucket, KH, hd = ks.shape
    rows = {"k": ks} if vs is None else {"k": ks, "v": vs}
    if bucket % page_size:
        pad = page_size - bucket % page_size
        rows = {n: jnp.pad(r, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))) for n, r in rows.items()}
        bucket += pad
    npg = bucket // page_size
    quant = "k_scale" in cache
    for name, new in rows.items():
        # [L, A, bucket, KH, hd] -> [L, KH, A*npg, page_size, hd]
        r = jnp.transpose(new, (0, 3, 1, 2, 4)).reshape(
            L, KH, A * npg, page_size, hd
        )
        if quant:
            q, s = quantize_pages(r, dtype=cache[name].dtype)
            cache[name] = cache[name].at[:, :, flat_pages].set(q)
            cache[f"{name}_scale"] = cache[f"{name}_scale"].at[:, :, flat_pages].set(s)
        else:
            cache[name] = cache[name].at[:, :, flat_pages].set(
                r.astype(cache[name].dtype)
            )
    return cache


def scatter_prefill_layer(pool: jax.Array, layer: jax.Array, rows: jax.Array, flat_pages: jax.Array, page_size: int) -> jax.Array:
    """One layer of ``scatter_prefill`` for a pool of ONE row a token (a
    latent model's rows, or its index keys: the lanes are the pool's own):
    ``rows`` [A, bucket, 1, lanes] of layer ``layer`` (traced) into ``pool``
    [n_layers, 1, N, page_size, lanes] at the rows' pages."""
    A, bucket, one, lanes = rows.shape
    if bucket % page_size:
        rows = jnp.pad(rows, ((0, 0), (0, page_size - bucket % page_size), (0, 0), (0, 0)))
    r = rows.reshape(-1, page_size, one, lanes).swapaxes(1, 2)  # [A * npg, 1, page_size, lanes]
    return pool.at[layer, :, flat_pages].set(r.astype(pool.dtype))


def scatter_token_rows(
    cache: dict,
    ks: jax.Array,
    vs: jax.Array,
    flat_pages: jax.Array,
    flat_rows: jax.Array,
) -> dict:
    """Row-granular KV write: token n lands at cache[.., flat_pages[n],
    flat_rows[n]]. scatter_prefill writes whole pages; speculative verify
    needs per-row routing because only the ACCEPTED tree path may land in
    real pages — rejected/off-path rows are steered to trash page 0 by the
    caller (duplicate trash writes are benign, exactly like prefill
    padding).

    ks/vs: [n_layers, N, KH, hd] — one flattened row per verify-tree node.
    """
    quant = "k_scale" in cache
    for name, new in (("k", ks), ("v", vs)):
        r = jnp.transpose(new, (0, 2, 1, 3))  # [L, KH, N, hd]
        if quant:
            q, s = quantize_kv(r, dtype=cache[name].dtype)
            cache[name] = cache[name].at[:, :, flat_pages, flat_rows].set(q)
            cache[f"{name}_scale"] = (
                cache[f"{name}_scale"]
                .at[:, :, flat_pages, 0, flat_rows]
                .set(s[..., 0])
            )
        else:
            cache[name] = cache[name].at[:, :, flat_pages, flat_rows].set(
                r.astype(cache[name].dtype)
            )
    return cache


def write_decode_rows(
    cache: dict,
    layer: jax.Array,  # scalar int32
    k: jax.Array,  # [S, KH, hd]: this step's token of every slot
    v: jax.Array | None,  # None: a latent model's one row is ``k``
    write_page: jax.Array,  # [S] int32
    write_off: jax.Array,  # [S] int32
    live: tuple[jax.Array, jax.Array] | None = None,
    more: dict[str, jax.Array] | None = None,
    pools: tuple[str, str] = ("k", "v"),
) -> dict:
    """A decode step's KV write of one layer: slot s's row lands at
    cache[layer, :, write_page[s], write_off[s]], quantized with its scale
    where the cache holds quantized pages. ``more`` = {pool: rows [S, heads,
    lanes]} names what else the token leaves behind on the same page and row
    (a latent model's index key, in a pool of its own width): the same launch.
    ``pools`` names the leaves K and V go to (the window layers' rings, as
    ``ring_pool`` lays them out, under their own pages and offsets).

    ``live`` = (slots with the live ones first, how many are live) puts the
    write on the Pallas launch (ops/paged_kv_write.py): the live slots' rows
    only, every pool in place. Without it: XLA scatters over every slot (an
    ended one's row goes to the trash page), ONE PER KV HEAD, because a
    scatter whose update window spans (KH, hd) makes the TPU compiler lay the
    whole carried pool out KH-minor, and the paged kernels need the default
    layout (compiled for a described v5e: a pool-sized temporary and two
    pool-sized copies a layer; per head: none). That path serves off the TPU
    and under tensor parallelism, and is what the tests hold the kernel to."""
    cache = dict(cache)
    rows = {pools[0]: k, **(more or {})} if v is None else {pools[0]: k, pools[1]: v}
    pages, scales = tuple(rows), ()
    if f"{pools[0]}_scale" in cache:
        scales = tuple(f"{n}_scale" for n in pages)
        for name in pages:
            rows[name], scale = quantize_kv(rows[name], dtype=cache[name].dtype)
            rows[f"{name}_scale"] = scale[..., 0]  # [S, KH]
    rows = {name: new.astype(cache[name].dtype) for name, new in rows.items()}
    if live is not None:
        from areal_tpu.ops.paged_kv_write import paged_kv_write

        new_pages, new_scales = paged_kv_write(
            tuple(cache[n] for n in pages),
            tuple(rows[n] for n in pages),
            layer,
            write_page,
            write_off,
            *live,
            scales=tuple(cache[n] for n in scales),
            scale_rows=tuple(rows[n] for n in scales),
        )
        cache.update(zip(pages, new_pages))
        cache.update(zip(scales, new_scales))
        return cache
    for n in pages:
        for h in range(rows[n].shape[1]):
            cache[n] = cache[n].at[layer, h, write_page, write_off].set(rows[n][:, h])
    for n in scales:  # lane-major in the pool: [L, KH, N, 1, psz]
        for h in range(rows[n].shape[1]):
            cache[n] = cache[n].at[layer, h, write_page, 0, write_off].set(rows[n][:, h])
    return cache


def copy_pages(
    cache: dict,
    dst: jax.Array,
    src: jax.Array,
    dst_slots: jax.Array,
    src_slots: jax.Array,
) -> dict:
    """What a request admitted beside an identical prompt takes from its
    primary: page contents src[i] -> dst[i] (the one page decode writes
    into; a few pages, all layers at once) and, where the model keeps a
    recurrent state, the primary slot's post-prompt state
    src_slots[i] -> dst_slots[i] (a state cannot be shared by reference)."""
    # a slice in, a slice out a pair, every leaf in place: no gather or
    # scatter over the state arrays (models/hybrid.py prefill_into_cache),
    # and none over a page pool either: as ``pool.at[:, :, dst].set(pool[:,
    # :, src])`` the program reserves a second pool (3 GB for a latent
    # model's one pool, which the chip did not have: PERF.md, PR 37)
    def copied(leaf, axis, dst_at, src_at):
        def one(i, leaf):
            row = jax.lax.dynamic_slice_in_dim(leaf, src_at[i], 1, axis=axis)
            return jax.lax.dynamic_update_slice_in_dim(leaf, row, dst_at[i], axis=axis)

        return jax.lax.fori_loop(0, dst_at.shape[0], one, leaf)

    for name in cache:
        if name in STATE_LEAVES:
            with jax.named_scope("state_write"):
                cache[name] = copied(cache[name], 1, dst_slots, src_slots)
        elif name in RING_LEAVES:  # a sibling's rings are its own: the primary's window of the prompt, copied
            with jax.named_scope("kv_write"):
                cache[name] = copied(cache[name], 2, dst_slots, src_slots)
        else:  # k/v (+ k_scale/v_scale under int8 KV), or a latent model's k (and its index keys, idx)
            cache[name] = copied(cache[name], 2, dst, src)
    return cache


def choose_ppcb(window_pages: int, default: int = 4) -> int:
    """Largest pages-per-compute-block <= default dividing the window."""
    ppcb = default
    while window_pages % ppcb:
        ppcb //= 2
    return max(1, ppcb)


def paged_attention_xla(
    q: jax.Array,  # [S, H, hd]
    k_pages: jax.Array,  # [KH, N, psz, hd] (one layer)
    v_pages: jax.Array,
    lengths: jax.Array,  # [S] int32 valid rows per slot
    page_table: jax.Array,  # [S, wp] int32 (window's pages)
    k_scales: jax.Array | None = None,  # [KH, N, 1, psz] (int8/fp8 KV)
    v_scales: jax.Array | None = None,
    sm_scale: float | None = None,  # softmax scale; default 1/sqrt(hd)
    select: jax.Array | None = None,  # [S, W] bool: the cached tokens each slot attends to; default all
) -> jax.Array:
    """Reference/CPU path: gather the window's pages, grouped masked einsum —
    numerically identical to the dense engine's attention."""
    S, H, hd = q.shape
    KH, _, psz, _ = k_pages.shape
    G = H // KH
    wp = page_table.shape[1]
    W = wp * psz
    # [KH, S, wp, psz, hd] -> [S, W, KH, hd]
    kk = jnp.transpose(k_pages[:, page_table], (1, 2, 3, 0, 4)).reshape(
        S, W, KH, hd
    )
    vv = jnp.transpose(v_pages[:, page_table], (1, 2, 3, 0, 4)).reshape(
        S, W, KH, hd
    )
    if k_scales is not None:
        # lane-major [KH, S, wp, 1, psz] -> [S, wp, psz, KH, 1] -> [S, W, KH, 1]
        ks_g = jnp.transpose(k_scales[:, page_table], (1, 2, 4, 0, 3)).reshape(
            S, W, KH, 1
        )
        vs_g = jnp.transpose(v_scales[:, page_table], (1, 2, 4, 0, 3)).reshape(
            S, W, KH, 1
        )
        kk = dequantize_kv(kk, ks_g, q.dtype)
        vv = dequantize_kv(vv, vs_g, q.dtype)
    qg = q.reshape(S, KH, G, hd)
    logits = jnp.einsum("skgd,stkd->skgt", qg, kk).astype(jnp.float32) * (
        hd**-0.5 if sm_scale is None else sm_scale
    )
    valid = jnp.arange(W)[None, :] < lengths[:, None]
    if select is not None:
        valid = valid & select
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(vv.dtype)
    return jnp.einsum("skgt,stkd->skgd", probs, vv).reshape(S, H, hd)
