"""The decode engine's page-and-slot ledger: who owns a page, and what a
slot's row of the cache is.

One of the three owners of what serves a request (docs/serving.md):
``decode_engine.DecodeEngine`` schedules passes and asks this module for
pages, ``decode_programs.DecodePrograms`` builds the programs that read and
write them. ``SlotCache`` is host state only: the refcounted pool and the
prefix tree of ``paged_kv``, every slot's page list and page-table row, and
the requests parked with their pages. It knows no request, callback or
timeline and no jitted program; the device arrays the pages index
(``DecodeEngine.cache``) stay with the scheduler, which threads them through
the donated programs, and come in here as an argument where bytes are
counted. Not thread-safe: the decode loop is the only caller of anything
that changes it (the contract of ``PagePool`` and ``RadixPrefixCache``).

**Ownership, stated once.** A page is held by a live slot (``pages(slot)``),
by a parked request (``parked[rid].pages``), by the prefix tree, or by any
mix of them, and the pool's refcount of a page is the number of its
holders: a holder takes one reference when it gets the page (``alloc`` for a
fresh one, ``ref`` for a page another holder already has) and gives exactly
that one back when it lets go. A page moves between holders without
touching the count (slot -> parked at ``park``, parked -> slot at
``resume``). ``leaked_pages()`` is the pool's used pages less the distinct
pages the holders name: 0 whenever nothing outside this ledger holds one.

Below the free pool stands the eviction ladder (``take``): the tree's
least-recently-used leaves first (pure cache: any published page is
re-creatable by a prefill), then the oldest parked request (rid-affinity
state whose loss costs a re-prefill). Preempting a live slot is a
scheduling decision and stays the scheduler's.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Iterable

import jax.numpy as jnp
import numpy as np

from areal_tpu.api.config import ServerConfig
from areal_tpu.inference import paged_kv
from areal_tpu.observability import catalog as obs_catalog
from areal_tpu.observability import hw_accounting as hw
from areal_tpu.utils import logging as alog

# one component, one logger: the three owners log as the decode engine
logger = alog.getLogger("decode_engine")


@dataclass
class Parked:
    """KV retained across abort/resume (rid affinity).

    The client's interruptible-generation loop resubmits ``prompt + emitted``
    with the same rid after continue_generation (client.py agenerate loop;
    reference intent remote_inf_engine.py:753-763). If the slot's pages are
    intact we restore decode state directly — zero re-prefill. The parked
    entry owns the slot's KV pages until resume or eviction."""

    slot: int
    full_ids: list[int]  # prompt + emitted; cache holds all but the last
    pos: int  # decode position of the pending (last) token
    pages: list[int] = field(default_factory=list)  # owned KV pages
    # policy version each page's KV was created under (parallel to pages;
    # radix publication and the flush-on-commit staleness check need it)
    page_versions: list[int] = field(default_factory=list)
    n_emitted: int = 0  # completion tokens so far (freq-penalty restore)
    park_time: float = field(default_factory=time.monotonic)


class SlotCache:
    """Pool, prefix tree, slot page lists, page table and parked requests of
    one model replica."""

    def __init__(
        self,
        config: ServerConfig,
        model_cfg,
        no_prefix: str | None = None,
        record: Callable[..., None] | None = None,
    ):
        """Pool size comes from ``kv_hbm_gb`` when set (long-context serving:
        KV HBM ∝ used tokens), else a dense-equivalent S×T tokens (short
        contexts, tests). ``no_prefix`` is why the model's module can serve no
        cached prefix (``serving_limits``; None where it can); ``record``
        takes the flight recorder's events (kind, then fields)."""
        cfg = self.config = config
        S, T, psz = cfg.max_batch_size, cfg.max_seq_len, cfg.page_size
        self.maxp = -(-T // psz)  # pages per sequence (ceil)
        self.kv_quant = paged_kv.page_quant(cfg.kv_quantization)
        if cfg.kv_hbm_gb is not None:
            # what a page row is, is the model configuration's to say: its
            # pools may differ in width (a latent row beside an index key)
            heads, lanes = model_cfg.kv_pools["k"]
            n_pages = paged_kv.n_pages_for_budget(
                int(cfg.kv_hbm_gb * (1 << 30)),
                model_cfg.num_kv_layers,
                heads,
                psz,
                lanes,
                jnp.dtype(model_cfg.jax_dtype).itemsize,
                quant=self.kv_quant,
                pools=model_cfg.kv_pools,
            )
        else:
            n_pages = S * self.maxp + 1  # +1: trash page 0
        self.pool = paged_kv.PagePool(n_pages)
        self._pages: list[list[int]] = [[] for _ in range(S)]
        # policy version each slot page's KV was created under (parallel to
        # _pages): radix publication skips stale pages under the default
        # flush-on-commit policy
        self._versions: list[list[int]] = [[] for _ in range(S)]
        self._table = np.zeros((S, self.maxp), np.int32)
        self.parked: dict[str, Parked] = {}  # rid -> retained-KV slot
        # rids whose slot (KV and recurrent state) was dropped under them: a
        # prefill of one of these rebuilds a state (state_prefills counter)
        self._dropped: set[str] = set()
        self._record = record or (lambda kind, **fields: None)
        self._obs = obs_catalog.prefix_cache_metrics()
        pc = cfg.prefix_cache
        wanted = pc.enabled and cfg.enable_prefix_caching
        # cross-request prefix cache; None = neither matches nor inserts
        self.radix: paged_kv.RadixPrefixCache | None = None
        if no_prefix:
            # what the model's module cannot serve a cached prefix of (a page
            # prefix says nothing of a recurrent state behind it: ROADMAP
            # Reach A.7; latent pages have no suffix prefill: A.5)
            if wanted:
                logger.info(f"prefix cache off: {no_prefix}")
        elif wanted:
            cap = pc.max_pages
            if cap is None:
                cap = int((n_pages - 1) * pc.max_fraction)
            self.radix = paged_kv.RadixPrefixCache(
                self.pool, psz, max(0, min(cap, n_pages - 1))
            )

    # -- what the pool holds ------------------------------------------------
    @property
    def n_pages(self) -> int:
        return self.pool.n_pages

    @property
    def free_pages(self) -> int:
        return self.pool.available

    @property
    def radix_pages(self) -> int:
        return self.radix.pages_held if self.radix is not None else 0

    def leaked_pages(self) -> int:
        """The audit: the pool's used pages less the DISTINCT pages its
        holders name (a page a parked request and the tree both hold is one
        page)."""
        held: set[int] = set()
        for pages in self._pages:
            held.update(pages)
        for p in self.parked.values():
            held.update(p.pages)
        if self.radix is not None:
            held.update(self.radix.pages())
        held.discard(0)
        return self.pool.used - len(held)

    @staticmethod
    def state_bytes(cache: dict | None) -> int:
        """Device bytes of the slot-indexed recurrent state in ``cache`` (0
        for a model without recurrent layers, or while the cache is
        released)."""
        cache = cache or {}
        return hw.tree_bytes({k: cache[k] for k in paged_kv.STATE_LEAVES if k in cache})

    def hbm_rows(self, cache: dict | None) -> dict[str, int]:
        """The cache's rows of the HBM ledger: the page pool, the recurrent
        state beside it, and the tree's held-page share (a view INTO the
        pool)."""
        state_bytes = self.state_bytes(cache)
        ring_bytes = hw.tree_bytes({k: cache[k] for k in paged_kv.RING_LEAVES if cache and k in cache})
        kv_bytes = hw.tree_bytes(cache) - state_bytes - ring_bytes
        return {
            "kv_page_pool": kv_bytes,
            "recurrent_state": state_bytes,
            # the window layers' rings: a fixed share a slot, under no page table (paged_kv.RING_LEAVES)
            **({"window_rings": ring_bytes} if ring_bytes else {}),
            "radix_cache": int(self.radix_pages * (kv_bytes / self.n_pages)),
        }

    # -- a slot's pages -----------------------------------------------------
    def pages(self, slot: int) -> list[int]:
        return self._pages[slot]

    def page_versions(self, slot: int) -> list[int]:
        return self._versions[slot]

    def page_table(self, wp: int | None = None) -> np.ndarray:
        """The block table, [slots, pages a sequence]; its first ``wp``
        columns (a window, bucketed in pages) for a program's argument. A
        row is zero past the slot's pages: page 0 is the trash page."""
        return self._table if wp is None else self._table[:, :wp]

    def take(self, n: int, keep: int = 0) -> list[int] | None:
        """``n`` fresh pages (one reference each, the caller's), walking the
        eviction ladder while the pool cannot cover them and leave ``keep``
        free; None when the ladder is exhausted."""
        while self.pool.available < n + keep:
            if not self._reclaim(n + keep):
                return None
        return self.pool.alloc(n)

    def _reclaim(self, n: int) -> bool:
        """One rung of the ladder: radix LRU leaves first, then the oldest
        parked request. True when anything was freed (the caller re-tries)."""
        if self.radix is not None:
            freed = self.radix.evict(n)
            if freed > 0:
                self._obs.evicted_pages.inc(freed)
                self._record("evict_radix", pages=freed)
                return True
        slot = self.evict_oldest_parked()
        if slot is not None:
            self._record("evict_parked", severity="warn", slot=slot)
        return slot is not None

    def assign(self, slot: int, pages: list[int], versions: list[int]) -> None:
        """``slot`` becomes the holder of ``pages`` (references the caller
        already took), stamped with the policy ``versions`` their KV was
        made under."""
        self._pages[slot] = pages
        self._versions[slot] = versions
        self._table[slot] = 0
        self._table[slot, : len(pages)] = pages

    def extend(self, slot: int, need: int, version: int) -> bool:
        """Grow ``slot`` to ``need`` pages with fresh ones (the ladder
        below them); False when they cannot be had, the slot as it was."""
        pages = self._pages[slot]
        if len(pages) >= need:
            return True
        got = self.take(need - len(pages))
        if got is None:
            return False
        self._table[slot, len(pages) : len(pages) + len(got)] = got
        pages.extend(got)
        self._versions[slot].extend([version] * len(got))
        return True

    def trim(self, slot: int, need: int) -> int:
        """Give back ``slot``'s pages past its first ``need`` (speculation's
        over-allocation; nothing committed was written there). Returns how
        many."""
        pages = self._pages[slot]
        if len(pages) <= need:
            return 0
        tail = pages[need:]
        self.pool.free(tail)
        self._pages[slot] = pages[:need]
        del self._versions[slot][need:]
        self._table[slot, need : need + len(tail)] = 0
        return len(tail)

    def alias(self, slot: int, src_slot: int, n_shared: int) -> tuple[int, int] | None:
        """Shared-prefix admission by page aliasing: ``slot`` takes a
        reference on ``src_slot``'s first ``n_shared`` pages (pages decode
        never writes) and one fresh private page for the boundary page.
        Returns (private page, the page to copy into it); None when the
        source holds no boundary page or no page can be had."""
        prim = self._pages[src_slot]
        if len(prim) <= n_shared:
            return None
        priv = self.take(1)
        if priv is None:
            return None
        shared = prim[:n_shared]
        self.pool.ref(shared)
        # the private page is a byte COPY of prim[n_shared], so it inherits
        # that page's KV version, not the current one — under the "keep"
        # ablation the two can differ across a commit
        self.assign(slot, list(shared) + priv, list(self._versions[src_slot][: n_shared + 1]))
        return priv[0], prim[n_shared]

    def _clear(self, slot: int) -> list[int]:
        """``slot`` stops being a holder: its page list, version list and
        page-table row are emptied (an in-flight chunk's stale write for the
        slot then lands in the trash page). Returns the pages it held, whose
        references are now the caller's to hand on or give back."""
        pages = self._pages[slot]
        self._pages[slot] = []
        self._versions[slot] = []
        self._table[slot] = 0
        return pages

    def release(self, slot: int) -> None:
        """``slot`` lets go of its pages (a freed page's next owner rewrites
        it in full before reading)."""
        self.pool.free(self._clear(slot))

    # -- parked requests ----------------------------------------------------
    def free_slots(self, busy: Iterable[bool]) -> list[int]:
        """Slots that neither run a task (``busy``, the scheduler's word, by
        slot) nor stand behind a parked request."""
        parked = {p.slot for p in self.parked.values()}
        return [i for i, b in enumerate(busy) if not b and i not in parked]

    def park(self, rid: str, slot: int, full_ids: list[int], pos: int, n_emitted: int, version: int) -> Parked:
        """Slot -> parked: ``rid``'s entry becomes the holder of the slot's
        pages, so that releasing the slot afterwards frees nothing. The
        prefix is published first: if this parking is later evicted (or the
        rid resubmits with EXTENDED content — a multi-turn episode's next
        turn), the tree still serves the prior turns' pages."""
        versions = list(self._versions[slot])
        p = Parked(
            slot=slot, full_ids=full_ids, pos=pos, pages=self._clear(slot), page_versions=versions,
            n_emitted=n_emitted,
        )
        self.parked[rid] = p
        self.publish(p.full_ids, p.pages, p.page_versions, p.pos, version)
        return p

    def resume(self, rid: str, ids: list[int]) -> Parked | None:
        """Parked -> slot: if ``rid`` is parked with exactly ``ids``, its
        slot is the holder of its pages again (page-table row restored: it
        was zeroed at park time so in-flight chunks couldn't write into
        retained pages) and the entry is returned. A rid reused with other
        content drops the stale parking and its pages."""
        p = self.parked.pop(rid, None)
        if p is None:
            return None
        if ids != p.full_ids:
            self._dropped.add(rid)
            self.pool.free(p.pages)
            return None
        self.assign(p.slot, p.pages, list(p.page_versions))
        return p

    def drop_parked(self, rid: str) -> bool:
        """A cancelled rid's parking lets go of its pages."""
        p = self.parked.pop(rid, None)
        if p is not None:
            self.pool.free(p.pages)
        return p is not None

    def evict_oldest_parked(self) -> int | None:
        """Free the least-recently-parked slot and its KV pages (a resume
        for that rid falls back to prefill)."""
        if not self.parked:
            return None
        rid = min(self.parked, key=lambda r: self.parked[r].park_time)
        p = self.parked.pop(rid)
        self._dropped.add(rid)
        self.pool.free(p.pages)
        return p.slot

    def evict_all_parked(self) -> None:
        while self.evict_oldest_parked() is not None:
            pass

    def mark_dropped(self, rid: str) -> None:
        """``rid``'s slot is about to be dropped under it (a preemption)."""
        self._dropped.add(rid)

    def readmitted(self, rids: Iterable[str]) -> int:
        """How many of ``rids``, now prefilled afresh, had their slot dropped
        under them; they are forgotten, and so is a backlog of rids that
        never came back."""
        rebuilt = [rid for rid in rids if rid in self._dropped]
        self._dropped.difference_update(rebuilt)
        if len(self._dropped) > 4096:
            self._dropped.clear()
        return len(rebuilt)

    # -- the prefix tree ----------------------------------------------------
    def match(self, ids: list[int]) -> tuple[list[int], list[int]] | None:
        """Longest cached page-aligned prefix of a fresh admission's ``ids``
        as (pages, versions), None on a miss. Takes the pool refs on the
        matched pages IMMEDIATELY (before any further eviction-ladder
        activity in this admission wave could free them); a task that later
        backlogs must release them (``unmatch``). The page holding row
        ``plen-1`` is never matched — the decode head writes there, and
        aliased pages are immutable."""
        limit = (len(ids) - 1) // self.config.page_size
        pages, versions = self.radix.match(ids, max_pages=limit)
        self._obs.lookups.inc()
        if not pages:
            return None
        self.pool.ref(pages)
        return pages, versions

    def unmatch(self, pages: list[int]) -> None:
        self.pool.free(pages)

    def publish(self, full_ids: list[int], pages: list[int], versions: list[int], pos: int, version: int) -> None:
        """Publish a request's full KV pages into the radix tree. Only pages
        strictly below ``pos`` are publishable (the page holding ``pos``
        still takes decode writes — possibly from an in-flight chunk).
        Under the default flush-on-commit policy, pages stamped with an
        older policy version than ``version`` are stale and the publishable
        prefix truncates at the first one (prefixes cannot have holes)."""
        if self.radix is None:
            return
        psz = self.config.page_size
        n_pub = min(pos // psz, len(pages), len(full_ids) // psz)
        if self.config.prefix_cache.across_updates == "flush":
            k = 0
            while k < n_pub and versions[k] == version:
                k += 1
            n_pub = k
        if n_pub <= 0:
            return
        adopted = self.radix.insert(
            full_ids[: n_pub * psz], pages[:n_pub], versions[:n_pub]
        )
        if adopted:
            self._obs.inserted_pages.inc(adopted)

    def flush_prefix(self) -> int:
        """Drop every radix-cached page (only the tree's own refs drop —
        pages aliased by live slots survive until those slots free them).
        Returns how many."""
        freed = self.radix.flush() if self.radix is not None else 0
        if freed:
            self._obs.evicted_pages.inc(freed)
        return freed

    def set_prefix_fraction(self, frac: float) -> None:
        """The tree's cap as a fraction of the pool (an autopilot setpoint);
        ``shrink_prefix_to_cap`` converges onto a smaller one."""
        n = self.n_pages
        self.radix.max_pages = max(0, min(int((n - 1) * frac), n - 1))

    def shrink_prefix_to_cap(self) -> None:
        r = self.radix
        if r is not None and r.pages_held > r.max_pages:
            freed = r.evict(r.pages_held - r.max_pages)
            if freed:
                self._obs.evicted_pages.inc(freed)

    def prefix_stats(self) -> dict | None:
        """The tree's point-in-time state; None while it is off."""
        if self.radix is None:
            return None
        return {
            "pages_held": self.radix.pages_held,
            "max_pages": self.radix.max_pages,
            # page granularity, so the client-side shadow prefix index
            # (routing/shadow_index.py) keys its radix on the same pages
            "page_size": self.config.page_size,
            **self.radix.stats,
        }
