"""Pipeline-shared cache: bounded gathered-expert-weight residency with
double-buffered prefetch (paper §4.5; DESIGN.md §2).

Data-centric execution all-gathers each MoE layer's expert weights at use.
Done naively, either (a) every layer re-gathers on the critical path (gather
latency exposed), or (b) all gathered copies stay live (residency = L layers
— the Janus baseline). The paper's pipeline-shared cache is the middle
point: at most C layers' gathered params are resident, and layer l+1's
gather is issued while layer l computes so the interconnect overlaps the
MXU.

``PipelineSharedCache`` realises this as a *trace-time* structure: the LM
forward's unrolled layer loop (``models.lm.run_layers`` with
``scan_layers=False`` and ``cache_layers > 0``) fetches layer l's gathered
tree (a hit — it was prefetched) and then prefetches layer l+1 BEFORE
emitting layer l's compute ops. In the lowered program the layer-(l+1)
all-gather therefore precedes, and is data-independent of, layer-l compute —
exactly the overlap XLA's latency-hiding scheduler needs — while eviction
drops the last reference to layer l-C+1's gathered buffers, bounding their
liveness. Residency accounting (resident/peak layers and bytes, hit/miss
counters) is exposed for the tests that bound it.

The gather itself is ``gather_ffn_params``: a GSPMD-level all-gather
expressed as a sharding constraint that drops the "fsdp" factor from each
weight's logical spec. ``moe_parallel.moe_layer(..., pregathered=True)``
then skips the island-internal fsdp gather and adjusts its in_specs.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, Optional, Sequence

from repro.common import tree_bytes  # noqa: F401  (re-exported: cache API)
from repro import obs


class PipelineSharedCache:
    """Bounded FIFO cache of gathered parameter trees.

    capacity_layers: maximum simultaneously-resident gathered layers. 2 is
    the double-buffer (current + prefetched next); the Janus baseline is
    effectively capacity = num_layers.
    """

    def __init__(self, capacity_layers: int = 2):
        if capacity_layers < 1:
            raise ValueError("capacity_layers must be >= 1")
        self.capacity_layers = capacity_layers
        self._resident: "OrderedDict[Hashable, Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0      # fetch-path gathers (critical-path stalls)
        self.prefetches = 0  # gathers issued ahead of use (overlapped)
        self.evictions = 0
        self.peak_resident_layers = 0
        self.peak_resident_bytes = 0
        obs.maybe_register(self)

    # -- core ---------------------------------------------------------------

    def fetch(self, key: Hashable, gather_fn: Callable[[], Any]) -> Any:
        """Return the gathered tree for ``key``, gathering on a miss."""
        if key in self._resident:
            self.hits += 1
            return self._resident[key]
        self.misses += 1
        value = gather_fn()
        self._insert(key, value)
        return value

    def prefetch(self, key: Hashable, gather_fn: Callable[[], Any]) -> None:
        """Issue the gather for ``key`` now (no-op if already resident).

        Call AFTER fetching the current layer and BEFORE emitting its
        compute: the prefetched gather then has no data dependence on the
        current layer's ops and can overlap them. Counted separately from
        misses — a prefetched gather is off the critical path.
        """
        if key not in self._resident:
            self.prefetches += 1
            self._insert(key, gather_fn())

    def _insert(self, key: Hashable, value: Any) -> None:
        self._resident[key] = value
        while len(self._resident) > self.capacity_layers:
            self._resident.popitem(last=False)
            self.evictions += 1
        self.peak_resident_layers = max(
            self.peak_resident_layers, len(self._resident)
        )
        self.peak_resident_bytes = max(
            self.peak_resident_bytes, self.resident_bytes()
        )

    # -- accounting ---------------------------------------------------------

    @property
    def resident_layers(self) -> int:
        return len(self._resident)

    def resident_bytes(self) -> int:
        return sum(tree_bytes(v) for v in self._resident.values())

    def stats(self) -> Dict[str, int]:
        return {
            "capacity_layers": self.capacity_layers,
            "resident_layers": self.resident_layers,
            "resident_bytes": self.resident_bytes(),
            "peak_resident_layers": self.peak_resident_layers,
            "peak_resident_bytes": self.peak_resident_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "prefetches": self.prefetches,
            "evictions": self.evictions,
        }

    def obs_metrics(self) -> Dict[str, float]:
        """Snapshot for the observability registry (DESIGN.md §12): one
        ``repro_cache_<stat>`` gauge per ``stats()`` entry, disambiguated
        across cache kinds by the registry's ``kind`` label."""
        return {f"repro_cache_{k}": float(v) for k, v in self.stats().items()}


class PlanCache(PipelineSharedCache):
    """Bounded FIFO cache of compiled step functions keyed by hetero-plan
    tuples (``core.hetero.HeteroPlan.key()``) — the re-trace bound of the
    straggler→replan loop (DESIGN.md §6).

    Every distinct plan is a distinct trace (the Eq. 1 shares are baked in
    as constants), so an unbounded replanner would accumulate compiled
    executables without limit; this reuses the pipeline-shared cache's FIFO
    residency + hit/miss accounting, with ``capacity_layers`` re-read as
    "simultaneously-retained plans". A replan that oscillates between two
    plans therefore re-traces exactly twice and then only hits. Values are
    callables, not arrays, so byte accounting is disabled.
    """

    def resident_bytes(self) -> int:
        return 0


# ---------------------------------------------------------------------------
# the gather the cache holds
# ---------------------------------------------------------------------------

def _drop_axes(logical: tuple, which=("fsdp",)) -> tuple:
    out = []
    for entry in logical:
        if entry in which:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a not in which)
            out.append(kept if kept else None)
        else:
            out.append(entry)
    return tuple(out)


def _drop_fsdp(logical: tuple) -> tuple:
    return _drop_axes(logical, ("fsdp",))


def gather_ffn_params(ffn: dict, cfg, mesh, *, collectives: str = "fsdp") -> dict:
    """All-gather the fsdp factor of every MoE FFN weight leaf.

    Expressed as a sharding constraint (GSPMD inserts the all-gather), so it
    composes with jit/scan and is a no-op without a mesh. The router stays
    replicated; TP factors stay sharded — per-layer data-centric dispatch
    gathers those inside the island (see moe_parallel).

    ``collectives="all"`` (the overlap schedule, DESIGN.md §10) gathers the
    tp factor too: the unrolled layer loop prefetches the NEXT data-centric
    layer's full expert weights while the current layer computes —
    generalizing this cache's double buffering from fsdp gathers to the MoE
    expert collectives themselves. The gathered values are exactly the ones
    the in-island gather would produce, so the overlap schedule is
    bit-identical to the eager one.
    """
    from repro.parallel.moe_parallel import MOE_PARAM_LOGICAL
    from repro.parallel.sharding import constrain

    drop = ("fsdp", "tp") if collectives == "all" else ("fsdp",)
    out = {}
    for name, v in ffn.items():
        logical = MOE_PARAM_LOGICAL.get(name)
        if v is None or logical is None or name == "router":
            out[name] = v
            continue
        out[name] = constrain(v, _drop_axes(logical, drop), cfg, mesh)
    return out


# ---------------------------------------------------------------------------
# serving page pool (paged KV cache residency, DESIGN.md §7)
# ---------------------------------------------------------------------------

class PagePool:
    """Host-side free-list allocator + copy-on-write refcounts + residency
    accounting over the shared KV page pool of
    ``models.lm.init_paged_cache`` (DESIGN.md §7).

    Physical page 0 is the write sink for inactive slots and is never
    allocated; ``num_pages - 1`` pages are allocatable. The scheduler's
    admission invariant is two-phase:

      * ``try_reserve(n, group)`` at admission — the request's WORST-CASE
        page count is debited from the (group's) free budget up front, so
        preemption-free decode can never hit an empty pool mid-request;
      * ``alloc(group)`` converts one reserved page into a physical page id
        (a chunk's worth at prefill, on demand at decode page boundaries);
      * ``release(pages, group, unused_reserved)`` drops one reference per
        page at completion.

    Copy-on-write prefix sharing (DESIGN.md §7) layers refcounts on top:
    ``alloc`` hands out a page at refcount 1 owned by its group; ``fork``
    maps the same physical page into another holder at refcount+1 (the
    radix prefix index and every borrowing slot hold one reference each —
    a fork consumes NO page budget, which is the whole capacity win);
    ``release`` decrements and only a 0-refcount page returns to the free
    list (credited to its OWNER group); ``cow`` is the write trigger —
    writing a refcount>1 page surrenders the shared reference and converts
    one reservation into a private copy's page id. Releasing a page below
    refcount 0 (the classic double-free) raises instead of corrupting the
    free list.

    Heterogeneous plans (DESIGN.md §6) express per-device capacity as
    per-group page-pool ``shares`` instead of masked tail slots: physical
    pages stay fungible in one free list, but each group's
    reserve/alloc/release is budgeted against its own share. A forked page
    stays charged to the group that allocated it until its LAST reference
    dies, so cross-group sharing can pin another group's budget — the
    documented cost of keeping pages fungible.

    Per-group invariant, checked by ``assert_consistent``:
    ``free + reserved_unallocated + in_use == share``.
    """

    def __init__(self, num_pages: int, *, page_bytes: int = 0,
                 shares: Optional[Sequence[int]] = None):
        if num_pages < 2:
            raise ValueError("need at least one allocatable page + the sink")
        usable = num_pages - 1
        self.num_pages = num_pages
        self.page_bytes = page_bytes
        self.shares = list(shares) if shares is not None else [usable]
        if any(s < 0 for s in self.shares):
            raise ValueError(f"negative page share: {self.shares}")
        if sum(self.shares) > usable:
            raise ValueError(
                f"shares {self.shares} exceed {usable} allocatable pages"
            )
        self._free_list = list(range(num_pages - 1, 0, -1))
        g = len(self.shares)
        self._free = list(self.shares)
        self._reserved = [0] * g
        self._in_use = [0] * g
        self._ref: Dict[int, int] = {}    # page -> live references
        self._owner: Dict[int, int] = {}  # page -> group charged for it
        self.total_allocs = 0
        self.total_frees = 0
        self.total_forks = 0
        self.total_cow_copies = 0
        self.total_rollbacks = 0
        self.peak_in_use_pages = 0

    # -- admission / allocation ---------------------------------------------

    def try_reserve(self, n: int, group: int = 0) -> bool:
        """Debit ``n`` worst-case pages from ``group``'s budget (admission
        by free-page budget). False leaves the pool untouched."""
        if n < 0:
            raise ValueError(n)
        if self._free[group] < n:
            return False
        self._free[group] -= n
        self._reserved[group] += n
        return True

    def alloc(self, group: int = 0) -> int:
        """Turn one reserved page into a physical page id (>= 1) at
        refcount 1, owned by (charged to) ``group``."""
        if self._reserved[group] <= 0:
            raise RuntimeError(
                f"group {group} allocating beyond its reservation"
            )
        self._reserved[group] -= 1
        self._in_use[group] += 1
        self.total_allocs += 1
        page = self._free_list.pop()
        self._ref[page] = 1
        self._owner[page] = group
        self.peak_in_use_pages = max(self.peak_in_use_pages,
                                     self.in_use_pages)
        return page

    def fork(self, pages: Sequence[int]) -> None:
        """Add one reference to each live page (prefix sharing: a borrowing
        slot or the radix index maps the page without copying it). Costs no
        group budget — that is the capacity win. Forking a free page or the
        sink is a scheduler bug and raises."""
        for p in pages:
            if not 1 <= p < self.num_pages:
                raise ValueError(f"bad page id {p}")
            if self._ref.get(p, 0) <= 0:
                raise RuntimeError(f"fork of free page {p}")
        for p in pages:
            self._ref[p] += 1
            self.total_forks += 1

    def refcount(self, page: int) -> int:
        """Live references on ``page`` (0 = free)."""
        return self._ref.get(page, 0)

    def cow(self, page: int, group: int = 0) -> int:
        """Write trigger for a shared page: at refcount 1 the caller owns
        the page exclusively and may write in place (returned unchanged);
        at refcount>1 the caller's reference is surrendered and one of its
        ``group`` reservations converts into a fresh private page id. The
        caller must copy the page's payload (``launch.steps.
        make_page_copy_step``) and repoint its table entry."""
        if self._ref.get(page, 0) <= 0:
            raise RuntimeError(f"cow on free page {page}")
        if self._ref[page] == 1:
            return page
        self._ref[page] -= 1
        self.total_cow_copies += 1
        return self.alloc(group)

    def release(self, pages: Sequence[int], group: int = 0,
                unused_reserved: int = 0) -> None:
        """Drop one reference per page (returning 0-refcount pages to the
        free list, credited to their owner group) plus any reservation the
        caller never converted. Releasing a free page raises — the
        double-free guard the refcount layer exists for."""
        for p in pages:
            if not 1 <= p < self.num_pages:
                raise ValueError(f"bad page id {p}")
            if self._ref.get(p, 0) <= 0:
                raise RuntimeError(
                    f"double release of page {p} (refcount already 0)"
                )
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                owner = self._owner.pop(p)
                self._free_list.append(p)
                self._in_use[owner] -= 1
                self._free[owner] += 1
                self.total_frees += 1
        self._reserved[group] -= unused_reserved
        self._free[group] += unused_reserved
        if self._reserved[group] < 0:
            raise RuntimeError(f"group {group} over-released")

    def rollback(self, pages: Sequence[int], group: int = 0) -> None:
        """Return decode-granted pages to the caller's **reservation** —
        the speculative-decoding rollback path (DESIGN.md §11).

        ``release`` credits a freed page to its owner's FREE budget, where
        the next admission can immediately claim it; a rolled-back request
        is still live and must be able to re-grow to its admitted
        worst-case length, so its truncated pages convert ``in_use`` back
        into ``reserved`` instead (the alloc-cannot-fail invariant of
        decode-boundary grants survives mid-request truncation).

        Only exclusively-held (refcount-1) pages owned by ``group`` may
        roll back: a refcount>1 page is prefix-shared content whose other
        holders must survive (CoW semantics, DESIGN.md §7). The engine
        never truncates into one — rollback pops strictly decode-region
        tail pages, past any matched prompt prefix — so hitting a shared
        or foreign page here is a scheduler bug and raises before any
        state changes."""
        for p in pages:
            if not 1 <= p < self.num_pages:
                raise ValueError(f"bad page id {p}")
            r = self._ref.get(p, 0)
            if r != 1:
                raise RuntimeError(
                    f"rollback of page {p} at refcount {r} (only "
                    f"exclusively-held decode pages may roll back)")
            if self._owner[p] != group:
                raise RuntimeError(
                    f"rollback of page {p} owned by group "
                    f"{self._owner[p]}, not caller group {group}")
        for p in pages:
            del self._ref[p]
            del self._owner[p]
            self._free_list.append(p)
            self._in_use[group] -= 1
            self._reserved[group] += 1
            self.total_frees += 1
            self.total_rollbacks += 1

    # -- accounting -----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return sum(self._free)

    @property
    def in_use_pages(self) -> int:
        return sum(self._in_use)

    @property
    def reserved_pages(self) -> int:
        return sum(self._reserved)

    def group_free(self, group: int) -> int:
        return self._free[group]

    def reshare(self, shares: Sequence[int]) -> None:
        """Rebind the per-group share budgets — the elastic-shrink path
        (DESIGN.md §9): after a device class drops out, the serving engine
        re-derives ``page_shares`` over the survivors and rebinds the pool
        to the new group set. Only legal on a fully **drained** pool (no
        live or reserved pages): live pages are charged to their owner
        group, and re-binning them across a changed group set would break
        the per-group conservation invariant — the engine aborts live
        slots back to the queue first, which is also what carries their
        requests across the shrink."""
        if self.in_use_pages or self.reserved_pages:
            raise RuntimeError(
                f"reshare on a non-drained pool ({self.in_use_pages} live, "
                f"{self.reserved_pages} reserved pages)")
        usable = self.num_pages - 1
        shares = list(shares)
        if any(s < 0 for s in shares):
            raise ValueError(f"negative page share: {shares}")
        if sum(shares) > usable:
            raise ValueError(
                f"shares {shares} exceed {usable} allocatable pages")
        self.shares = shares
        g = len(shares)
        self._free = list(shares)
        self._reserved = [0] * g
        self._in_use = [0] * g
        self._free_list = list(range(self.num_pages - 1, 0, -1))
        self.assert_consistent()

    def assert_consistent(self) -> None:
        for g, share in enumerate(self.shares):
            total = self._free[g] + self._reserved[g] + self._in_use[g]
            assert total == share, (g, self._free[g], self._reserved[g],
                                    self._in_use[g], share)
        assert len(self._free_list) == (self.num_pages - 1
                                        - self.in_use_pages)
        assert len(set(self._free_list)) == len(self._free_list)
        # refcount layer: live pages and the free list partition the pool
        assert all(r > 0 for r in self._ref.values()), self._ref
        assert set(self._ref) == set(self._owner)
        assert not (set(self._ref) & set(self._free_list)), (
            "page both live and free")
        assert len(self._ref) == self.in_use_pages
        for g in range(len(self.shares)):
            assert self._in_use[g] == sum(
                1 for o in self._owner.values() if o == g)

    def stats(self) -> Dict[str, int]:
        return {
            "num_pages": self.num_pages,
            "page_bytes": self.page_bytes,
            "free_pages": self.free_pages,
            "in_use_pages": self.in_use_pages,
            "reserved_pages": self.reserved_pages,
            "shared_pages": sum(1 for r in self._ref.values() if r > 1),
            "peak_in_use_pages": self.peak_in_use_pages,
            "peak_in_use_bytes": self.peak_in_use_pages * self.page_bytes,
            "total_allocs": self.total_allocs,
            "total_frees": self.total_frees,
            "total_forks": self.total_forks,
            "total_cow_copies": self.total_cow_copies,
            "total_rollbacks": self.total_rollbacks,
        }

    def obs_metrics(self) -> Dict[str, float]:
        """Snapshot for the observability registry (DESIGN.md §12)."""
        return {f"repro_cache_{k}": float(v) for k, v in self.stats().items()}


def page_shares(weights: Sequence[float], usable_pages: int) -> list[int]:
    """Largest-remainder split of the allocatable pages proportional to
    ``weights`` (a hetero plan's Eq. 1 ``token_counts``): the per-device
    page-pool shares that replace masked tail slots (DESIGN.md §7)."""
    import numpy as np

    w = np.asarray(weights, dtype=np.float64)
    if np.any(w < 0) or w.sum() <= 0:
        raise ValueError(f"bad share weights {weights}")
    raw = w / w.sum() * usable_pages
    base = np.floor(raw).astype(np.int64)
    order = np.argsort(-(raw - base))
    base[order[: usable_pages - int(base.sum())]] += 1
    assert base.sum() == usable_pages
    return [int(v) for v in base]


# ---------------------------------------------------------------------------
# radix prefix index (CoW prefix sharing, DESIGN.md §7)
# ---------------------------------------------------------------------------

class _TrieNode:
    """One page-granular edge of the prefix trie: ``key`` is the tuple of
    ``page_size`` token ids this node's page holds, ``page`` the physical
    page id the index keeps one ``PagePool`` reference on."""

    __slots__ = ("key", "page", "children", "parent", "last_used")

    def __init__(self, key, page, parent):
        self.key = key
        self.page = page
        self.children: Dict[tuple, "_TrieNode"] = {}
        self.parent = parent
        self.last_used = 0


class PrefixIndex:
    """Radix/trie prefix index keyed on token ids at page granularity
    (DESIGN.md §7): admission matches the longest cached prefix of a
    prompt against whole pages already resident in the paged-KV pool and
    maps them into the new slot's page table at refcount+1 instead of
    re-prefilling them.

    Only FULL pages are indexed — a page is inserted when the prompt that
    wrote it finishes prefill and covers the page end-to-end, so cached
    content is immutable by construction (decode writes land strictly past
    the prompt, never inside an indexed page) and CoW copies stay a
    defensive guard rather than a steady-state cost. K/V rows depend only
    on the token prefix and the absolute position (RoPE/window masks are
    position-absolute), so identical token chunks at identical depths are
    bitwise-shareable across slots; int8 pools share their scale pages
    through the same physical index (DESIGN.md §8).

    Every node holds exactly ONE pool reference on its page. ``evict_lru``
    frees the least-recently-used leaf whose page has refcount 1 (cached
    but borrowed by no live slot — interior nodes and borrowed pages are
    pinned), feeding pages back to the admission budget.
    """

    def __init__(self, page_size: int):
        if page_size < 1:
            raise ValueError(page_size)
        self.page_size = page_size
        self.root = _TrieNode(None, 0, None)
        self._clock = 0
        self.lookups = 0
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.evictions = 0

    def __len__(self) -> int:
        """Number of cached pages (trie nodes below the root)."""
        n, stack = 0, [self.root]
        while stack:
            node = stack.pop()
            n += len(node.children)
            stack.extend(node.children.values())
        return n

    def _chunks(self, tokens, max_pages: int):
        P = self.page_size
        limit = min(max_pages, len(tokens) // P)
        return [tuple(int(t) for t in tokens[i * P:(i + 1) * P])
                for i in range(limit)]

    def match(self, tokens, max_pages: int) -> list:
        """Longest cached prefix of ``tokens``: physical page ids of the
        leading whole-page chunks present in the trie (at most
        ``max_pages`` — the scheduler caps at ``(prompt_len - 1) // P`` so
        at least one suffix token is always left to prefill, which is what
        produces the first generated token's logits). Bumps LRU clocks
        along the matched path; the caller must ``PagePool.fork`` the
        returned pages before anything else can evict them."""
        self.lookups += 1
        self.lookup_tokens += len(tokens)
        node, pages = self.root, []
        self._clock += 1
        for key in self._chunks(tokens, max_pages):
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = self._clock
            pages.append(child.page)
            node = child
        self.hit_tokens += len(pages) * self.page_size
        return pages

    def insert(self, tokens, pages: Sequence[int], pool: PagePool) -> int:
        """Index the leading ``len(pages)`` whole-page chunks of ``tokens``
        under their physical ``pages``, forking one pool reference per
        NEWLY-created node (chunks already cached keep their existing page
        — two requests racing the same prefix do not double-index). Returns
        the number of pages newly indexed."""
        node = self.root
        self._clock += 1
        added = 0
        for key, page in zip(self._chunks(tokens, len(pages)), pages):
            child = node.children.get(key)
            if child is None:
                pool.fork([page])
                child = _TrieNode(key, page, node)
                child.last_used = self._clock
                node.children[key] = child
                added += 1
            node = child
        return added

    def evict_lru(self, pool: PagePool) -> bool:
        """Release the least-recently-used evictable page back to the pool
        (refcount-1 leaf: cached but borrowed by no slot and shadowing no
        longer chain). False when nothing is evictable — the admission
        loop's stop condition."""
        best = None
        stack = [self.root]
        while stack:
            node = stack.pop()
            for child in node.children.values():
                if not child.children and pool.refcount(child.page) == 1:
                    if best is None or child.last_used < best.last_used:
                        best = child
                stack.append(child)
        if best is None:
            return False
        pool.release([best.page])
        del best.parent.children[best.key]
        self.evictions += 1
        return True

    def pages(self):
        """Yield the physical page id of every trie node — one pool
        reference each. The serving engine's structural audit
        (``PagedServer.assert_page_invariants``, DESIGN.md §9) recomputes
        refcounts as slot holders + these."""
        stack = [self.root]
        while stack:
            node = stack.pop()
            for child in node.children.values():
                yield child.page
                stack.append(child)

    def clear(self, pool: PagePool) -> int:
        """Drop every cached reference (leaf-first). Servers call this to
        drain the cache so end-of-run leak checks see the whole pool."""
        dropped = 0
        while self.evict_lru(pool):
            dropped += 1
        # anything left is pinned by live borrowers; detach the index's
        # references anyway only when unpinned — a non-empty remainder
        # means slots still hold forks, which is not a leak.
        return dropped

    def stats(self) -> Dict[str, int]:
        """Hit-rate counters the serving benchmark reports."""
        return {
            "cached_pages": len(self),
            "lookups": self.lookups,
            "hit_tokens": self.hit_tokens,
            "lookup_tokens": self.lookup_tokens,
            "evictions": self.evictions,
        }

    def obs_metrics(self) -> Dict[str, float]:
        """Snapshot for the observability registry (DESIGN.md §12)."""
        return {f"repro_cache_{k}": float(v) for k, v in self.stats().items()}


def gathered_layer_bytes(d: int, f: int, e: int, *, glu: bool = True,
                         bytes_per_el: int = 2) -> int:
    """Bytes of ONE layer's fully-gathered expert weights (the unit the
    residency bound multiplies)."""
    n_mats = 3 if glu else 2
    total = e * n_mats * d * f * bytes_per_el
    if not glu:
        total += e * (f + d) * 4  # f32 biases
    return total
