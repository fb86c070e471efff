"""Shadow prefix index: the client's estimate of each replica's radix cache.

Prefix-locality routing needs to answer "which replica already holds this
prompt's KV pages?" per request, without an RPC per request. The shadow
index answers it from the client's own routing history: every completed
generation inserts the page-aligned token-id prefix of (prompt + output)
under the replica it ran on — exactly the pages the engine publishes into
its radix tree at completion (``SlotCache.publish``). A lookup
then walks the replica's shadow tree for the longest cached page-aligned
prefix, mirroring ``RadixPrefixCache.match``.

The shadow is an *estimate*, reconciled and invalidated so it can only
under-promise:

- **weight commits flush it** (the PR 5 ``across_updates="flush"``
  contract: the engines drop their trees at every commit, so the shadow
  must too — kept even for ``"keep"`` fleets, where underestimating is the
  safe direction);
- **reconciliation** against each replica's ``prefix_cache`` /statusz
  section trims the shadow when the replica reports fewer pages than the
  shadow claims (LRU evictions / pool-pressure reclaims on the replica),
  and drops the replica's whole tree when its flush counter advances or
  its cache reads disabled — a respawned replica therefore reads cold;
- a **per-replica page cap** LRU-evicts leaves, like the real tree.

A wrong estimate can misplace a request (cold prefill on latency), never
corrupt it — the radix match on the replica is authoritative.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict

from areal_tpu.utils import logging as alog

logger = alog.getLogger("routing.shadow")


class _ShadowNode:
    """One full page of presumed-cached KV: keyed by the page's token-id
    tuple, like paged_kv._RadixNode but with no pool to own."""

    __slots__ = ("key", "children", "parent", "last_tick")

    def __init__(self, key, parent, tick):
        self.key = key
        self.parent = parent
        self.children: dict[tuple, _ShadowNode] = {}
        self.last_tick = tick


class _ReplicaTree:
    def __init__(self):
        self.root = _ShadowNode((), None, 0)
        self.n_pages = 0
        self.flushes_seen: int | None = None


class ShadowPrefixIndex:
    """Per-replica page-granular radix over routed token-id prefixes.

    Thread-safe: lookups come from the request path (asyncio loop),
    inserts from response handling, reconciliation from the snapshot
    poller thread.
    """

    def __init__(self, page_size: int = 128, max_pages_per_replica: int = 8192):
        assert page_size > 0
        self.page_size = page_size
        self.max_pages_per_replica = max(1, max_pages_per_replica)
        self._lock = threading.Lock()
        self._trees: dict[str, _ReplicaTree] = {}
        self._tick = 0
        self._version: int | None = None  # policy version the index is valid for
        self.stats = {"inserted_pages": 0, "evicted_pages": 0, "flushes": 0}

    # -- helpers -----------------------------------------------------------
    def _touch(self) -> int:
        self._tick += 1
        return self._tick

    def set_page_size(self, page_size: int) -> None:
        """Learn the fleet's real page size from a replica's prefix_cache
        stats; a mismatch flushes (page keys are size-dependent)."""
        if page_size <= 0 or page_size == self.page_size:
            return
        with self._lock:
            self.page_size = page_size
            self._trees.clear()

    # -- writes ------------------------------------------------------------
    def note_routed(self, addr: str, ids, version: int | None = None) -> int:
        """Record that ``ids`` (prompt + generated tokens) now presumably
        sit in ``addr``'s radix tree. Only FULL pages strictly below the
        final position are recorded — the page the decode head last wrote
        is never published by the engine. Returns pages inserted."""
        with self._lock:
            if version is not None:
                if self._version is None:
                    self._version = version
                elif version != self._version:
                    # a sequence generated under another policy version is
                    # not publishable under the flush-on-commit contract
                    return 0
            psz = self.page_size
            n_pages = max(0, (len(ids) - 1) // psz)
            if n_pages == 0:
                return 0
            tree = self._trees.setdefault(addr, _ReplicaTree())
            tick = self._touch()
            node = tree.root
            inserted = 0
            path_ids: set[int] = set()
            for i in range(n_pages):
                key = tuple(ids[i * psz : (i + 1) * psz])
                child = node.children.get(key)
                if child is None:
                    if tree.n_pages >= self.max_pages_per_replica:
                        # evict a batch: the leaf walk is O(tree), so at
                        # the cap it must amortize over many inserts, not
                        # run once per page while the request path waits
                        # on this lock
                        self._evict_locked(
                            tree,
                            tree.n_pages
                            - self.max_pages_per_replica
                            + 1
                            + self.max_pages_per_replica // 16,
                            _exclude=path_ids,
                        )
                    if tree.n_pages >= self.max_pages_per_replica:
                        break
                    child = _ShadowNode(key, node, tick)
                    node.children[key] = child
                    tree.n_pages += 1
                    inserted += 1
                else:
                    child.last_tick = tick
                node = child
                path_ids.add(id(node))
            self.stats["inserted_pages"] += inserted
            return inserted

    def drop_replica(self, addr: str) -> None:
        """Forget everything about a replica (evicted/respawned: its radix
        tree restarted empty)."""
        with self._lock:
            self._trees.pop(addr, None)

    def on_weight_commit(self, version: int | None = None) -> None:
        """Weight commit: every replica flushed its radix tree (PR 5
        ``across_updates="flush"``), so the whole shadow is invalid. Under
        a ``"keep"`` fleet this underestimates — the safe direction."""
        with self._lock:
            self._trees.clear()
            self._version = version
            self.stats["flushes"] += 1

    def reconcile(self, addr: str, prefix_stats: dict) -> None:
        """Fold a replica's own ``prefix_cache`` /statusz section into the
        shadow. The shadow must never claim more pages than the replica
        reports holding: overestimation routes toward cold caches."""
        if not isinstance(prefix_stats, dict):
            return
        if not prefix_stats.get("enabled", False):
            self.drop_replica(addr)
            return
        self.set_page_size(int(prefix_stats.get("page_size", 0) or 0))
        flushes = int(prefix_stats.get("flushes", 0) or 0)
        pages_held = int(prefix_stats.get("pages_held", 0) or 0)
        with self._lock:
            tree = self._trees.get(addr)
            if tree is None:
                return
            if tree.flushes_seen is None:
                tree.flushes_seen = flushes
            elif flushes > tree.flushes_seen:
                # the replica flushed (weight commit we haven't folded yet,
                # or the /flush_prefix_cache ops endpoint): shadow is void
                self._trees.pop(addr, None)
                return
            if tree.n_pages > pages_held:
                self._evict_locked(tree, tree.n_pages - pages_held)

    # -- reads -------------------------------------------------------------
    def overlap_pages(self, addr: str, ids) -> int:
        """Longest presumed-cached page-aligned prefix of ``ids`` on
        ``addr``, in pages — mirroring the engine's match limit (the decode
        head's write page is never matchable)."""
        with self._lock:
            tree = self._trees.get(addr)
            if tree is None:
                return 0
            psz = self.page_size
            limit = max(0, (len(ids) - 1) // psz)
            tick = self._touch()
            node = tree.root
            n = 0
            for i in range(limit):
                child = node.children.get(tuple(ids[i * psz : (i + 1) * psz]))
                if child is None:
                    break
                child.last_tick = tick
                node = child
                n += 1
            return n

    def pages_for(self, addr: str) -> int:
        with self._lock:
            tree = self._trees.get(addr)
            return tree.n_pages if tree is not None else 0

    # -- eviction (lock held) ---------------------------------------------
    def _evict_locked(
        self, tree: _ReplicaTree, n: int, _exclude: set[int] | None = None
    ) -> int:
        """LRU-leaf eviction, parents becoming evictable as their last
        child goes (same interior-node invariant as RadixPrefixCache)."""
        import heapq

        def allowed(node: _ShadowNode) -> bool:
            return _exclude is None or id(node) not in _exclude

        leaves = []
        stack = list(tree.root.children.values())
        while stack:
            nd = stack.pop()
            if nd.children:
                stack.extend(nd.children.values())
            elif allowed(nd):
                leaves.append((nd.last_tick, id(nd), nd))
        heapq.heapify(leaves)
        freed = 0
        while freed < n and leaves:
            _, _, victim = heapq.heappop(leaves)
            parent = victim.parent
            del parent.children[victim.key]
            tree.n_pages -= 1
            freed += 1
            if (
                parent is not tree.root
                and not parent.children
                and allowed(parent)
            ):
                heapq.heappush(leaves, (parent.last_tick, id(parent), parent))
        self.stats["evicted_pages"] += freed
        return freed


class AffinityMap:
    """rid -> replica affinity with an idle-TTL sweep.

    The inference client's resume loop and abort path both key on this
    map; entries whose rid never completes (crashed caller, abandoned
    workflow) used to accumulate forever. Mirroring the gateway's
    ``sweep_stale_routes``: every *active* rid refreshes its entry on each
    get/set (a parked-and-resumed request touches it per attempt), and the
    sweep — amortized into ``set`` — expires entries idle past ``ttl_s``.
    Thread-safe (asyncio loop + abort-pool threads).
    """

    def __init__(self, ttl_s: float = 3600.0, sweep_every: int = 64):
        self.ttl_s = ttl_s
        self._sweep_every = max(1, sweep_every)
        self._lock = threading.Lock()
        self._d: "OrderedDict[str, tuple[str, float]]" = OrderedDict()
        self._sets_since_sweep = 0
        self.swept_total = 0

    def get(self, rid: str) -> str | None:
        with self._lock:
            ent = self._d.get(rid)
            if ent is None:
                return None
            addr, _ = ent
            self._d[rid] = (addr, time.monotonic())
            self._d.move_to_end(rid)
            return addr

    def set(self, rid: str, addr: str) -> None:
        with self._lock:
            self._d[rid] = (addr, time.monotonic())
            self._d.move_to_end(rid)
            self._sets_since_sweep += 1
            if self._sets_since_sweep >= self._sweep_every:
                self._sweep_locked()

    def pop(self, rid: str, default=None) -> str | None:
        with self._lock:
            ent = self._d.pop(rid, None)
            return ent[0] if ent is not None else default

    def sweep(self, now: float | None = None) -> int:
        with self._lock:
            return self._sweep_locked(now)

    def _sweep_locked(self, now: float | None = None) -> int:
        self._sets_since_sweep = 0
        now = now if now is not None else time.monotonic()
        n = 0
        # insertion order is touch order: the idle entries sit at the head
        while self._d:
            rid, (_, ts) = next(iter(self._d.items()))
            if now - ts <= self.ttl_s:
                break
            self._d.popitem(last=False)
            n += 1
        if n:
            self.swept_total += n
            logger.debug(f"swept {n} idle rid-affinity entries")
        return n

    def __contains__(self, rid: str) -> bool:
        with self._lock:
            return rid in self._d

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)
