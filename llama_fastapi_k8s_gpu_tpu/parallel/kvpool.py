"""Block-paged KV pool with a shared radix-tree prefix index.

The dense engines provision KV worst-case: one ``n_ctx`` ring per lane,
and prefix reuse that dies with its lane (``LFKT_LANE_PREFIX_CACHE``) or
with the next request (the serial claim).  This module turns the KV
budget into a **shared, dynamically partitioned resource**
("Transformer-Lite", PAPERS.md): a preallocated HBM arena of fixed-size
token *pages*, fronted by a radix tree keyed on token prefixes, so

- a shared system prompt prefills ONCE per process and every later
  request restores its pages instead of recomputing them;
- a multi-turn conversation resumes from its last committed page
  regardless of which lane it lands on;
- warm-but-idle conversations spill to host RAM (the K-in-HBM /
  V-offloaded split of "Efficient LLM Inference with Kcache", PAPERS.md,
  generalized to whole pages) and restore on their next hit.

Layout — **page-contiguous**, not gathered: a page is ``page_tokens``
consecutive token slots across ALL layers/heads of the cache pytree
(leaf-generic: the bf16 ``{k, v}`` layout and the int8 four-leaf layout
both slice their token axis, which is axis 2 in every leaf —
models/llama.py ``init_cache``).  On a prefix hit the matched pages are
copied **contiguously** into the front of an ordinary dense ring and the
suffix prefills from there, so every downstream consumer — the jit'd
prefill/decode programs, the flash-attention kernel's ring contract
(ops/pallas/attention.py), the int8 fused-dequant reads — is untouched,
and greedy decode under ``LFKT_KV_PAGED=1`` is bit-identical to the
dense path (pinned by tests/test_kv_paged_engines.py).  The price is one
page copy per hit/commit; the alternative (a page-table-indexed gather
inside the attention kernel) buys nothing until pages stop being
materialized, which is the disaggregated-prefill step (ROADMAP item 6 —
this module's page pytree is that wire format).

Concurrency: one internal lock guards the tree, the free list, the
refcounts and the arena reference; the serial engines call under their
generation mutex, the continuous scheduler from its own thread.  Pages
referenced by an in-flight request are pinned (per-page refcounts) and
can never be evicted; eviction is LRU over unpinned leaf nodes.

Namespaces (multi-model serving, docs/MULTIMODEL.md): every public
index operation takes a ``namespace`` key — one radix root per
namespace, so co-resident models sharing the arena can NEVER match each
other's prefixes (two models produce different KV for the same token
ids, and tenant A's system prompt must not leak into tenant B's cache).
The page arena, free list and LRU clock stay shared: N models partition
the same HBM page budget dynamically instead of each provisioning
worst-case, and eviction pressure from a hot model reclaims a cold
model's pages.  ``compatible()`` says whether another model's cache
geometry can share this arena at all (same leaf shapes/dtypes per page).

Compiled-shape bound: page moves dispatch in groups of at most
``_GROUP`` pages with traced offsets/ids, so the whole pool compiles at
most ``2 * _GROUP`` small copy programs per cache layout — page ops are
NOT part of the engines' warmed serving set and compile on first use.

Machine-checked contracts (lfkt-lint v2, docs/LINT.md): every caller of
:meth:`KVPool.acquire` must release or hand off the lease on every path
(RES001 — the PR-6 leak class), and the donating copy jits below feed
the DON donor registry — ``restore``'s ring parameter is donated
transitively, so engine call sites must rebind or drop their ref across
the call (DON001/DON002).
"""

from __future__ import annotations

import functools
import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig
from ..models.llama import init_cache
from ..obs.devtime import timed_jit
from ..obs.memledger import register_component

logger = logging.getLogger(__name__)

#: max pages per jitted copy dispatch — bounds the compiled-program set
#: (group sizes 1.._GROUP, for store/restore/lane-store/upload each)
_GROUP = 8


# ---------------------------------------------------------------------------
# jitted page movement (leaf-generic: token axis is 2 in every cache leaf)
# ---------------------------------------------------------------------------

def _block_to_pages(block, n: int, page_tokens: int):
    """(L, n_kv, n*T, ...) token block -> (n, L, n_kv, T, ...) pages."""
    lead = block.shape[:2]
    tail = block.shape[3:]
    pages = block.reshape(lead + (n, page_tokens) + tail)
    perm = (2, 0, 1, 3) + tuple(range(4, 4 + len(tail)))
    return pages.transpose(perm)


def _pages_to_block(pages, n: int, page_tokens: int):
    """(n, L, n_kv, T, ...) pages -> (L, n_kv, n*T, ...) token block."""
    perm = (1, 2, 0, 3) + tuple(range(4, pages.ndim))
    stacked = pages.transpose(perm)
    lead = stacked.shape[:2]
    tail = stacked.shape[4:]
    return stacked.reshape(lead + (n * page_tokens,) + tail)


@functools.partial(jax.jit, donate_argnames=("arena",))
def _store_pages_jit(arena: dict, ring: dict, page_ids, offset):
    """Copy ring token slots [offset, offset + n*T) into arena pages
    ``page_ids`` (n traced via the ids' shape; offset traced)."""
    n = page_ids.shape[0]

    def per_leaf(al, rl):
        T = al.shape[3]
        block = jax.lax.dynamic_slice_in_dim(rl, offset, n * T, axis=2)
        return al.at[page_ids].set(_block_to_pages(block, n, T))

    return jax.tree.map(per_leaf, arena, ring)


# the four page programs carry no done stamp (``leaf=None``): each returns
# the arena or the ring alone, which the next program takes by donation
_store_pages_jit = timed_jit("kvpool_store", _store_pages_jit,
                             site="parallel.kvpool", leaf=None)


@functools.partial(jax.jit, donate_argnames=("arena",))
def _store_lane_pages_jit(arena: dict, bcache: dict, lane, page_ids, offset):
    """As :func:`_store_pages_jit`, reading lane ``lane`` of a batched
    cache (leading batch dim) — the gather + slice + scatter fuse into one
    program, so no full lane ring is ever materialized (the peak-HBM trap
    the lane-snapshot path hit on 16 GB chips)."""
    n = page_ids.shape[0]

    def per_leaf(al, bl):
        T = al.shape[3]
        rl = jax.lax.dynamic_index_in_dim(bl, lane, axis=0, keepdims=False)
        block = jax.lax.dynamic_slice_in_dim(rl, offset, n * T, axis=2)
        return al.at[page_ids].set(_block_to_pages(block, n, T))

    return jax.tree.map(per_leaf, arena, bcache)


_store_lane_pages_jit = timed_jit("kvpool_lane_store", _store_lane_pages_jit,
                                  site="parallel.kvpool", leaf=None)


@functools.partial(jax.jit, donate_argnames=("ring",))
def _restore_pages_jit(arena: dict, ring: dict, page_ids, offset):
    """Copy arena pages ``page_ids`` into ring token slots
    [offset, offset + n*T), contiguously."""
    n = page_ids.shape[0]

    def per_leaf(al, rl):
        T = al.shape[3]
        block = _pages_to_block(al[page_ids], n, T)
        return jax.lax.dynamic_update_slice_in_dim(rl, block, offset, axis=2)

    return jax.tree.map(per_leaf, arena, ring)


_restore_pages_jit = timed_jit("kvpool_restore", _restore_pages_jit,
                               site="parallel.kvpool", leaf=None)


@functools.partial(jax.jit, donate_argnames=("arena",))
def _upload_pages_jit(arena: dict, pages: dict, page_ids):
    """Write host-restored page stacks back into arena slots (spill tier
    restore path)."""
    return jax.tree.map(lambda al, p: al.at[page_ids].set(p), arena, pages)


_upload_pages_jit = timed_jit("kvpool_upload", _upload_pages_jit,
                              site="parallel.kvpool", leaf=None)


# ---------------------------------------------------------------------------
# radix tree (page-granular: every edge is a run of whole pages)
# ---------------------------------------------------------------------------

class _Node:
    """One radix edge: a run of whole pages.  ``edge`` holds the token
    content as page tuples; ``pages`` the arena page ids (None when the
    node is spilled — ``host`` then holds the page pytree on host RAM).
    Children are keyed by their edge's FIRST page tuple, so two sequences
    diverging mid-page land under different keys (pages are the sharing
    unit: a partially shared page cannot be shared).  ``ns`` is the radix
    namespace the node lives under — the memory ledger's per-model
    attribution key (the tree itself never consults it)."""

    __slots__ = ("edge", "pages", "host", "children", "parent", "stamp",
                 "ns")

    def __init__(self, edge, pages, parent, ns: str = ""):
        self.edge: list[tuple] = edge          # page token tuples
        self.pages: list[int] | None = pages   # arena ids | None (spilled)
        self.host = None                       # host pytree when spilled
        self.children: dict[tuple, _Node] = {}
        self.parent: _Node | None = parent
        self.stamp = 0                         # LRU clock value
        self.ns = ns


class _Lease:
    """Pinned pages backing one in-flight request's prefix reuse."""

    __slots__ = ("tokens", "page_ids")

    def __init__(self, tokens: int, page_ids: list[int]):
        self.tokens = tokens
        self.page_ids = page_ids


def page_geometry(cfg: ModelConfig, page_tokens: int) -> tuple:
    """((page shape, dtype name), ...) of one page of ``page_tokens``
    positions, a leaf of the cache each, in leaf order: (L, n_kv, T[, hd])
    with L the cache's leaves (a layer, and a layer AND pass where layers
    run several times).  From shapes: nothing is allocated."""
    T = int(page_tokens)
    return tuple(
        (s.shape[:2] + (T,) + s.shape[3:], str(jnp.dtype(s.dtype)))
        for s in jax.tree.leaves(jax.eval_shape(lambda: init_cache(cfg))))


class KVPool:
    """The process-wide paged KV arena + radix prefix index.

    ``sink_host`` is the owning engine (or any object with a
    ``metrics_sink`` attribute): hit/miss/eviction/spill/restore events
    are emitted into its metrics registry when the server injected one
    (obs/catalog.py families), and silently dropped otherwise — telemetry
    must never fail serving.
    """

    # -- lock discipline (machine-checked: lfkt-lint LOCK001-004) ----------
    # one mutex guards every mutable: tree, free list, refcounts, arena
    # reference, counters.  Device copies dispatch under the lock (they
    # are async enqueues); callers on any thread.
    _GUARDED_BY = {
        "arena": "_lock",
        "_free": "_lock",
        "_page_refs": "_lock",
        "_roots": "_lock",
        "_clock": "_lock",
        "_spill_used": "_lock",
        "_busy": "_lock",
        "counters": "_lock",
        "_ns_pages": "_lock",
    }

    def __init__(self, cfg: ModelConfig, page_tokens: int = 128,
                 n_pages: int = 0, spill_pages: int = 0, sink_host=None):
        T = int(page_tokens)
        if T < 1:
            raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
        if T >= cfg.n_ctx:
            raise ValueError(
                f"page_tokens {T} must be smaller than n_ctx {cfg.n_ctx} "
                "(a usable prefix must leave >= 1 token to prefill)")
        self.page_tokens = T
        if n_pages <= 0:
            # auto: four full contexts' worth of pages — enough for a
            # system prompt + a handful of warm conversations per chip;
            # production sizes via LFKT_KV_POOL_PAGES (docs/RUNBOOK.md
            # "Sizing the KV page pool")
            n_pages = 4 * max(1, cfg.n_ctx // T)
        self.n_pages = int(n_pages)
        self.spill_pages = max(0, int(spill_pages))
        self._sink_host = sink_host
        spec = jax.eval_shape(lambda: init_cache(cfg))
        #: per-page-leaf geometry fingerprint: what another ModelConfig
        #: must reproduce to share this arena (see :meth:`compatible`)
        self._page_spec = page_geometry(cfg, T)
        #: the paged arena: one leaf per cache leaf, page-major
        #: (n_pages, L, n_kv, T[, hd]), L the cache's leaves
        #: (``cfg.cache_leaves``) — allocated once, updated in place (the
        #: copy jits donate it)
        self.arena = jax.tree.unflatten(jax.tree.structure(spec), [
            jnp.zeros((self.n_pages,) + shape, dtype)
            for shape, dtype in self._page_spec])
        self.page_nbytes = sum(
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize
            for shape, dtype in self._page_spec)
        self._lock = threading.Lock()
        self._free: list[int] = list(range(self.n_pages))
        self._page_refs: dict[int, int] = {}
        #: one radix root per namespace (model) — prefixes never match
        #: across namespaces; the arena/free-list/LRU stay shared
        self._roots: dict[str, _Node] = {}
        #: DEVICE-resident indexed pages per namespace, maintained
        #: incrementally at the four mutation sites (commit / spill /
        #: drop / spill-restore) so the memory ledger's per-model
        #: attribution is O(namespaces) per scrape instead of a radix DFS
        #: under the allocation lock (invariant pinned by test against a
        #: fresh tree walk)
        self._ns_pages: dict[str, int] = {}
        self._clock = 0
        self._spill_used = 0
        #: node ids an in-progress walk depends on — evict/age must skip
        self._busy: set[int] = set()
        #: monotonic event counters (tests + /health introspection; the
        #: Prometheus families are inc'd at event time via the sink)
        self.counters = {
            "hits": 0, "misses": 0, "reused_tokens": 0, "commits": 0,
            "stored_pages": 0, "evictions": 0, "spills": 0, "restores": 0,
            "store_skips": 0, "exported_pages": 0, "imported_pages": 0,
            "drained_pages": 0,
        }
        # lfkt-mem: attribute the arena into the process memory ledger —
        # indexed pages per namespace (model), the free list, and the
        # host spill tier.  A shared multi-model pool registers ONCE
        # (here, at construction), so the rows never double-count.
        register_component("kv_arena_used", self, KVPool._ledger_used)
        register_component("kv_arena_free", self, KVPool._ledger_free)
        register_component("host_spill", self, KVPool._ledger_spill)

    @property
    def _root(self) -> _Node:
        """Default-namespace radix root (white-box tests and single-model
        introspection; multi-model callers go through ``namespace=``).
        Lock-free — callers may already hold ``_lock`` (the white-box
        tests do); the dict setdefault is GIL-atomic."""
        root = self._roots.get("")
        if root is None:
            root = self._roots.setdefault("", _Node([], [], None, ""))  # lfkt: noqa[LOCK001] -- GIL-atomic setdefault (a losing racer's node is discarded); taking _lock here would deadlock the white-box callers that already hold it
        return root

    # -- telemetry (never fails serving) -----------------------------------
    def _metrics(self):
        host = self._sink_host
        return getattr(host, "metrics_sink", None) if host is not None \
            else None

    def _emit(self, kind: str, name: str, value: float = 1.0) -> None:
        m = self._metrics()
        if m is None:
            return
        try:
            getattr(m, kind)(name, value)
        except Exception:  # noqa: BLE001 — telemetry must never fail serving
            pass

    @property
    def arena_nbytes(self) -> int:
        """HBM bytes of the page arena (shape metadata; donation-safe)."""
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.arena))

    def compatible(self, cfg: ModelConfig, page_tokens: int | None = None
                   ) -> bool:
        """Whether a model with ``cfg``'s cache geometry can share this
        arena: same page size and the same per-leaf page shapes/dtypes
        (layers, kv heads, head dim, kv dtype layout).  Models that differ
        get their own pool — the registry attributes that at load time
        (docs/MULTIMODEL.md)."""
        if page_tokens is not None and int(page_tokens) != self.page_tokens:
            return False
        return page_geometry(cfg, self.page_tokens) == self._page_spec

    def page_spec(self) -> tuple:
        """The per-leaf page geometry fingerprint ((shape, dtype_str), ...)
        + ``page_tokens`` is everything a peer pool must reproduce to
        exchange pages with this one — the disagg wire handshake's
        geometry block (serving/disagg/wire.py).  Immutable metadata: no
        lock needed."""
        return self._page_spec

    # ------------------------------------------------------------------
    # public surface (each entry point takes the lock once)
    # ------------------------------------------------------------------
    def match_len(self, ids, *, namespace: str = "") -> int:
        """Tokens of ``ids`` covered by cached whole pages (device OR
        spilled) in ``namespace`` — a pure peek: no pin, no counters, no
        restore."""
        with self._lock:
            return self._match(list(ids), namespace)[0] * self.page_tokens

    def note_miss(self) -> None:
        """Count one prefix-cache miss (the engine consulted the index and
        could not use it — no match, too short, or bucket-unfittable)."""
        with self._lock:
            self.counters["misses"] += 1
        self._emit("inc", "prefix_cache_misses_total")

    def acquire(self, ids, tokens: int, span=None, *,  # lfkt: blocks-under[_lock] -- spill-tier restore/evict moves pages device<->host: the radix+refcount walk and the copy must be atomic (bounded by page-group size)
                namespace: str = "") -> _Lease | None:
        """Pin the pages covering ``ids[:tokens]`` (``tokens`` a multiple
        of the page size, at most :meth:`match_len`).  Spilled pages on the
        path are restored into freshly allocated arena slots first; if that
        allocation cannot be satisfied (pool pinned solid) the acquire
        degrades to a miss (None) — requests proceed with a full prefill
        rather than block or OOM.  On success the matched region is
        LRU-touched and counted as a hit."""
        T = self.page_tokens
        want = tokens // T
        if want < 1:
            return None
        with self._lock:
            matched, path = self._match(list(ids), namespace)
            ok = matched >= want
            page_ids: list[int] = []
            if ok:
                # pin AS WE WALK (and mark the whole path busy): a later
                # node's spill-restore may evict, and eviction must never
                # take a page — or unlink a node — this lease is about to
                # reference
                self._busy.update(id(node) for node, _n in path)
                self._clock += 1
                try:
                    for node, n_pages in path:
                        if len(page_ids) >= want:
                            break
                        if node.pages is None and not self._restore_node(
                                node, span=span):
                            ok = False
                            break
                        node.stamp = self._clock
                        take = min(n_pages, want - len(page_ids))
                        for pid in node.pages[:take]:
                            self._page_refs[pid] = \
                                self._page_refs.get(pid, 0) + 1
                            page_ids.append(pid)
                except Exception as e:  # noqa: BLE001 — degrade to a miss
                    # (full prefill); ok=False routes through the unref
                    # cleanup below so pages pinned earlier in the walk
                    # don't leak into a permanently unevictable set
                    logger.warning("paged acquire failed; degrading to a "
                                   "full prefill: %s", e)
                    ok = False
                finally:
                    self._busy.clear()
            if not ok:
                for pid in page_ids:
                    self._unref(pid)
                self.counters["misses"] += 1
                self._emit("inc", "prefix_cache_misses_total")
                return None
            self.counters["hits"] += 1
            self.counters["reused_tokens"] += want * T
        self._emit("observe", "prefix_reuse_tokens", want * T)
        return _Lease(want * T, page_ids)

    def release(self, lease: _Lease | None) -> None:
        """Unpin a lease's pages (idempotent-safe only via the engines'
        single-live-lease bookkeeping — call exactly once per lease)."""
        if lease is None:
            return
        with self._lock:
            for pid in lease.page_ids:
                self._unref(pid)

    def restore(self, lease: _Lease, ring: dict, span=None) -> dict:
        """Copy the lease's pages contiguously into ring slots
        [0, lease.tokens) and return the updated ring (donated in place).
        The ring then serves the suffix prefill exactly as if those
        positions had been prefilled locally."""
        t0 = time.time()
        T = self.page_tokens
        with self._lock:
            off = 0
            ids = lease.page_ids
            while off < len(ids):
                g = ids[off:off + _GROUP]
                ring = _restore_pages_jit(
                    self.arena, ring, jnp.asarray(g, jnp.int32),
                    jnp.int32(off * T))
                off += len(g)
        if span is not None:
            span.event("kv_restore", pages=len(lease.page_ids),
                       tokens=lease.tokens,
                       bytes=len(lease.page_ids) * self.page_nbytes,
                       host_s=round(time.time() - t0, 6))
        return ring

    def export_pages(self, lease: _Lease) -> list:  # lfkt: blocks-under[_lock] -- the export gather is a synchronous DMA exactly like the spill path's; the pin+copy must be atomic against eviction
        """Host copies of the lease's pages, one stacked array per cache
        leaf (leading axis = page, in lease order) — the disagg wire's
        payload unit (serving/disagg/wire.py).  The lease pins the pages,
        so the gather can never race an eviction; the device_get is a
        synchronous DMA exactly like the spill path's."""
        pids = jnp.asarray(lease.page_ids, jnp.int32)
        with self._lock:
            leaves = jax.device_get(
                [al[pids] for al in jax.tree.leaves(self.arena)])
            self.counters["exported_pages"] += len(lease.page_ids)
        return leaves

    def import_pages(self, ids, leaves, *, namespace: str = "",  # lfkt: blocks-under[_lock] -- wire-page upload indexes into the radix as it copies: the index+arena move must be atomic (bounded by page-group size)
                     span=None) -> int:
        """Index externally produced KV pages — the disagg decode side
        (serving/disagg/decoder.py): the whole-page prefix of ``ids``
        arrives as host page stacks (one array per cache leaf, leading
        axis = page, covering ``len(ids)//page_tokens`` pages, the
        :meth:`export_pages` layout).  Pages already cached deduplicate
        (LRU touch only); the new tail uploads into freshly allocated
        arena pages and joins the tree via the SAME index-attach
        machinery as :meth:`commit` (:meth:`_index_tail` — the radix
        invariants cannot drift between local commits and wire imports),
        so the next :meth:`acquire` for this prefix restores it like any
        local commit.  Degrades exactly like commit — to the leading
        portion that fits, or to nothing, when the pool is pinned solid
        or a device copy fails; never blocks, never OOMs.  Returns the
        tokens the tree now covers for this prefix (cached + newly
        imported)."""
        ids = list(ids)
        T = self.page_tokens
        with self._lock:
            n_want = len(ids) // T
            if n_want < 1:
                return 0
            if any(leaf.shape[0] != n_want for leaf in leaves):
                raise ValueError(
                    f"page stacks cover "
                    f"{[leaf.shape[0] for leaf in leaves]} pages, ids "
                    f"cover {n_want} (geometry drift on the wire?)")
            treedef = jax.tree.structure(self.arena)

            def upload(pids: list, matched: int, n_tail: int) -> None:
                off = 0
                while off < n_tail:
                    g = pids[off:off + _GROUP]
                    stack = [
                        jnp.asarray(leaf[matched + off:
                                         matched + off + len(g)])
                        for leaf in leaves]
                    self.arena = _upload_pages_jit(
                        self.arena, jax.tree.unflatten(treedef, stack),
                        jnp.asarray(g, jnp.int32))
                    off += len(g)

            matched, stored = self._index_tail(ids, namespace, span,
                                               upload)
            if stored:
                self.counters["imported_pages"] += stored
            return (matched + stored) * T

    def commit(self, ids, ring: dict, span=None, *,
               namespace: str = "") -> int:
        """Index the whole-page prefix of ``ids`` whose KV sits in ring
        slots [0, len(ids)): pages already cached are deduplicated (LRU
        touch only), the new tail is copied into freshly allocated arena
        pages and inserted into the tree.  When the whole tail cannot be
        allocated (pool smaller than the conversation, or pinned solid)
        the commit degrades to the LEADING portion that fits — a squeezed
        pool still caches the conversation head, which is where the
        shared system prompt lives — and skips entirely only when not
        even one page can be had; serving never blocks on the cache.
        Returns the number of NEW pages stored."""
        return self._commit_impl(list(ids), ring=ring, span=span,
                                 namespace=namespace)

    def commit_lane(self, ids, bcache: dict, lane: int, span=None, *,
                    namespace: str = "") -> int:
        """As :meth:`commit`, reading lane ``lane`` of a batched cache —
        the continuous scheduler's freed-lane path."""
        return self._commit_impl(list(ids), bcache=bcache, lane=lane,
                                 span=span, namespace=namespace)

    def drain_namespace(self, namespace: str) -> int:
        """Retire one namespace's index (live model removal — serving/
        registry.py ``reload_manifest``): DROP every droppable node of
        ``namespace`` — device pages go straight to the free list, spilled
        stacks are released — and return the device pages the namespace
        still holds (pages pinned by in-flight leases, or nodes an
        in-progress walk marked busy; the caller polls until 0 under its
        drain budget).  Dropping, not spilling: the model is leaving, so
        its KV is garbage — and only THIS namespace is touched, so
        retiring a model can never evict a surviving tenant's warm pages
        (no cross-namespace eviction storm — pinned by test).  When the
        namespace empties, its root (and ledger row) is removed; a
        namespace never committed to is a no-op."""
        with self._lock:
            root = self._roots.get(namespace)
            if root is None:
                self._ns_pages.pop(namespace, None)
                return 0
            order: list[_Node] = []
            stack = list(root.children.values())
            while stack:
                n = stack.pop()
                order.append(n)
                stack.extend(n.children.values())
            drained = 0
            # children-first (reversed DFS order): dropping a subtree's
            # leaves turns its interior nodes into droppable leaves within
            # the same pass
            for node in reversed(order):
                if node.children or id(node) in self._busy:
                    continue
                if node.pages is not None:
                    if any(p in self._page_refs for p in node.pages):
                        continue        # pinned by an in-flight lease
                    n = len(node.pages)
                    self._free.extend(node.pages)
                    node.pages = None
                    self._ns_pages[namespace] = max(
                        0, self._ns_pages.get(namespace, 0) - n)
                    drained += n
                else:
                    self._spill_used -= len(node.edge)
                    node.host = None
                self._unlink(node)
            if drained:
                self.counters["drained_pages"] += drained
            if not root.children:
                self._roots.pop(namespace, None)
                self._ns_pages.pop(namespace, None)
                return 0
            return self._ns_pages.get(namespace, 0)

    def reset(self) -> None:
        """Drop the index (EVERY namespace) and free every page (watchdog
        recovery: lane contents are of unknown validity, so nothing
        resident is trustworthy — with a shared multi-model pool, one
        engine's trip resets all tenants' cache, conservatively).  Arena
        contents need no zeroing — unindexed pages are unreachable."""
        with self._lock:
            self._roots = {}
            self._free = list(range(self.n_pages))
            self._page_refs = {}
            self._spill_used = 0
            self._ns_pages = {}
            self._busy.clear()

    # -- memory-ledger providers (obs/memledger.py; called at snapshot
    # time from scrape/incident threads) -----------------------------------
    def _ledger_used(self) -> dict:
        """Indexed device pages per namespace, in bytes — read from the
        incrementally maintained ``_ns_pages`` counters, so a scrape
        holds the allocation lock for O(namespaces), never a radix DFS
        (the occupancy() no-stall rule; counter==tree invariant pinned by
        test).  Pages allocated but not (yet) reachable from any tree —
        an in-flight commit, or a store that failed before indexing —
        land under ``(unindexed)`` so the arena's used+free always sums
        to its full allocation."""
        with self._lock:
            per_ns = {ns: pages * self.page_nbytes
                      for ns, pages in self._ns_pages.items() if pages}
            inflight = (self.n_pages - len(self._free)) \
                - sum(self._ns_pages.values())
        if inflight > 0:
            per_ns["(unindexed)"] = inflight * self.page_nbytes
        return per_ns

    def _ledger_used_slow(self) -> dict:
        """The DFS ground truth ``_ledger_used`` must agree with — test
        oracle only (holds the lock for a full tree walk)."""
        with self._lock:
            per_ns: dict[str, int] = {}
            for ns, root in self._roots.items():
                pages = 0
                stack = list(root.children.values())
                while stack:
                    n = stack.pop()
                    stack.extend(n.children.values())
                    if n.pages is not None:
                        pages += len(n.pages)
                if pages:
                    per_ns[ns] = pages * self.page_nbytes
        return per_ns

    def _ledger_free(self) -> int:
        with self._lock:
            return len(self._free) * self.page_nbytes

    def _ledger_spill(self) -> int:
        with self._lock:
            return self._spill_used * self.page_nbytes

    def occupancy(self) -> dict:
        """Point-in-time pool occupancy for /health, the
        ``kv_pool_pages_{used,free}`` gauges and /debug/memory's
        fragmentation line (largest run of CONSECUTIVE free page ids vs
        the free count: a fragmented arena can hold many pages but no
        contiguous run — informational here, load-bearing once pages
        stream as the disaggregated-prefill wire format)."""
        with self._lock:
            free_ids = list(self._free)
            pinned = len(self._page_refs)
            spill = self._spill_used
            namespaces = len(self._roots)
        # the O(n log n) run scan happens OUTSIDE the lock (a /metrics
        # scrape must never stall a decode-path allocation on it); the
        # copied snapshot may be an instant stale, which is fine for an
        # occupancy report
        free = len(free_ids)
        run = best = 0
        prev = None
        for pid in sorted(free_ids):
            run = run + 1 if prev is not None and pid == prev + 1 else 1
            best = max(best, run)
            prev = pid
        return {
            "largest_free_run": best,
            "page_tokens": self.page_tokens,
            "page_bytes": self.page_nbytes,
            "pages_total": self.n_pages,
            "pages_used": self.n_pages - free,
            "pages_free": free,
            "pages_pinned": pinned,
            "spill_pages_total": self.spill_pages,
            "spill_pages_used": spill,
            "arena_bytes": self.arena_nbytes,
            "namespaces": namespaces,
        }

    def stats(self) -> dict:
        with self._lock:
            return dict(self.counters)

    def hot_prefixes(self, k: int = 8) -> list[dict]:
        """The ``k`` most recently used cached prefixes across every
        namespace, hottest first — the unit of fleet KV migration
        (serving/fleet/migrate.py): graceful drain pushes these to the
        rendezvous successors, scale-out warm-up pulls peers' lists.
        Each row is ``{"namespace", "ids", "tokens"}`` with ``ids`` the
        full root→leaf token run (whole pages only — exactly what
        ``match_len``/``acquire`` on the far side can use).  Leaf chains
        only: an interior node's run is a prefix of its children's, so
        shipping leaves ships the interiors for free (``import_pages``
        dedups).  Hotness = the leaf's LRU stamp; spilled leaves count
        (their content is intact and exportable after restore)."""
        rows: list[tuple[int, str, list[int]]] = []
        with self._lock:
            for node in self._nodes():
                if node.children:
                    continue
                ids: list[int] = []
                chain: list[_Node] = []
                n: _Node | None = node
                while n is not None and n.parent is not None:
                    chain.append(n)
                    n = n.parent
                for n in reversed(chain):
                    for page in n.edge:
                        ids.extend(int(t) for t in page)
                if ids:
                    rows.append((node.stamp, node.ns, ids))
        rows.sort(key=lambda r: r[0], reverse=True)
        return [{"namespace": ns, "ids": ids, "tokens": len(ids)}
                for _, ns, ids in rows[:max(0, int(k))]]

    # ------------------------------------------------------------------
    # internals (lock held)
    # ------------------------------------------------------------------
    def _pages_of(self, ids: list) -> list[tuple]:
        T = self.page_tokens
        n = len(ids) // T
        return [tuple(ids[i * T:(i + 1) * T]) for i in range(n)]

    def _root_for(self, ns: str) -> _Node:  # lfkt: holds[_lock]
        root = self._roots.get(ns)
        if root is None:
            root = self._roots[ns] = _Node([], [], None, ns)
        return root

    def _match(self, ids: list, ns: str = ""):  # lfkt: holds[_lock]
        """Greedy page-wise walk of ``ns``'s tree.  Returns
        (matched_pages, path) where path is
        [(node, pages_matched_in_node), ...] root-first."""
        want = self._pages_of(ids)
        node = self._roots.get(ns)
        if node is None:
            return 0, []
        i = 0
        path: list[tuple[_Node, int]] = []
        while i < len(want):
            child = node.children.get(want[i])
            if child is None:
                break
            j = 0
            while j < len(child.edge) and i + j < len(want) \
                    and child.edge[j] == want[i + j]:
                j += 1
            path.append((child, j))
            i += j
            if j < len(child.edge):
                break
            node = child
        return i, path

    def _unref(self, pid: int) -> None:  # lfkt: holds[_lock]
        left = self._page_refs.get(pid, 0) - 1
        if left > 0:
            self._page_refs[pid] = left
        else:
            self._page_refs.pop(pid, None)

    def _restore_node(self, node: _Node,
                      span=None) -> bool:  # lfkt: holds[_lock]
        """Bring one spilled node's pages back into the arena (allocating,
        which may evict LRU unpinned nodes).  False when the allocation
        cannot be satisfied — the caller degrades to a miss."""
        n = len(node.edge)
        pids = self._alloc(n)
        if pids is None:
            return False
        t0 = time.time()
        try:
            self.arena = _upload_pages_jit(
                self.arena,
                jax.tree.map(lambda h: jnp.asarray(h), node.host),
                jnp.asarray(pids, jnp.int32))
        except Exception as e:  # noqa: BLE001 — degrade to a miss: the
            # caller takes a full prefill; the just-allocated (unpinned,
            # unindexed) slots must go back on the free list or they leak
            # for the life of the process
            self._free.extend(pids)
            logger.warning("spill restore failed; degrading to a full "
                           "prefill: %s", e)
            return False
        node.pages = pids
        node.host = None
        self._spill_used -= n
        self._ns_pages[node.ns] = self._ns_pages.get(node.ns, 0) + n
        self.counters["restores"] += 1
        self._emit("inc", "prefix_cache_restores_total")
        if span is not None:
            span.event("kv_spill_restore", pages=n,
                       bytes=n * self.page_nbytes,
                       host_s=round(time.time() - t0, 6))
        return True

    def _commit_impl(self, ids: list, ring=None, bcache=None, lane=None,  # lfkt: blocks-under[_lock] -- commit indexes the tail into the radix as it stores: spill-tier evictions on the alloc path are part of the atomic move
                     span=None, namespace: str = "") -> int:
        with self._lock:
            if len(ids) < self.page_tokens:
                return 0
            self.counters["commits"] += 1
            T = self.page_tokens

            def store(pids: list, matched: int, n_tail: int) -> None:
                off = 0
                while off < n_tail:
                    g = jnp.asarray(pids[off:off + _GROUP], jnp.int32)
                    go = jnp.int32((matched + off) * T)
                    if ring is not None:
                        self.arena = _store_pages_jit(self.arena, ring,
                                                      g, go)
                    else:
                        self.arena = _store_lane_pages_jit(
                            self.arena, bcache, jnp.int32(lane), g, go)
                    off += len(g)

            _matched, stored = self._index_tail(ids, namespace, span,
                                                store)
            return stored

    def _index_tail(self, ids: list, namespace: str, span,
                    store) -> tuple:  # lfkt: holds[_lock]
        """THE index-attach skeleton shared by :meth:`commit` /
        :meth:`commit_lane` (device-side ring/lane store) and
        :meth:`import_pages` (host-stack upload, the disagg wire): match
        + LRU-touch, busy-pin the match path, allocate the tail with the
        halving degrade, split/attach, run ``store(pids, matched_pages,
        n_tail)`` (the ONLY varying part — it performs the device
        copies), then insert the node and maintain the counters.
        Returns ``(matched_pages, stored_pages)``.

        Degrade contract: the cache is an optimization — a failed page
        copy must not fail the finished request (or the scheduler loop,
        on the freed-lane path), so a raising ``store`` returns the
        not-yet-indexed pids to the free list (partially copied groups
        are unreachable without a tree node, hence harmless) and reports
        0 stored."""
        want = self._pages_of(ids)
        if not want:
            return 0, 0
        matched, path = self._match(ids, namespace)
        self._clock += 1
        for node, _n in path:
            node.stamp = self._clock
        if matched >= len(want):
            return matched, 0              # fully cached already
        tail = want[matched:]
        # mark the match path busy: the tail's allocation may evict, and
        # evicting (= unlinking) a path node would orphan the subtree
        # this commit is about to attach to
        self._busy.update(id(node) for node, _n in path)
        try:
            n = len(tail)
            pids = self._alloc(n, span=span)
            while pids is None and n > 1:
                # degrade to the leading portion that fits (halving:
                # O(log) alloc attempts, each of which may evict)
                n //= 2
                pids = self._alloc(n, span=span)
        finally:
            self._busy.clear()
        if pids is None:
            self.counters["store_skips"] += 1
            return matched, 0
        tail = tail[:n]
        # attach point: deepest fully-matched node, splitting a
        # partially-matched edge at its page boundary first
        if path and path[-1][1] < len(path[-1][0].edge):
            parent = self._split(path[-1][0], path[-1][1])
        elif path:
            parent = path[-1][0]
        else:
            parent = self._root_for(namespace)
        try:
            store(pids, matched, len(tail))
        except Exception as e:  # noqa: BLE001 — skip the store (see the
            # degrade contract in the docstring)
            self._free.extend(pids)
            self.counters["store_skips"] += 1
            logger.warning("page store failed; commit skipped: %s", e)
            return matched, 0
        child = _Node(tail, pids, parent, namespace)
        child.stamp = self._clock
        parent.children[tail[0]] = child
        self._ns_pages[namespace] = \
            self._ns_pages.get(namespace, 0) + len(tail)
        self.counters["stored_pages"] += len(tail)
        return matched, len(tail)

    def _split(self, node: _Node, at: int) -> _Node:  # lfkt: holds[_lock]
        """Split ``node``'s edge after ``at`` pages; returns the new upper
        node (the attach point for a diverging sibling).  ``at`` >= 1 by
        construction (children are keyed by their first page)."""
        upper = _Node(node.edge[:at],
                      node.pages[:at] if node.pages is not None else None,
                      node.parent, node.ns)
        upper.stamp = node.stamp
        if node.pages is None:
            # spilled: split the host page stacks along the page axis
            upper.host = jax.tree.map(lambda h: h[:at], node.host)
            node.host = jax.tree.map(lambda h: h[at:], node.host)
        else:
            node.pages = node.pages[at:]
        node.edge = node.edge[at:]
        node.parent.children[upper.edge[0]] = upper
        upper.children[node.edge[0]] = node
        node.parent = upper
        return upper

    def _nodes(self) -> list:  # lfkt: holds[_lock]
        """Every tree node across ALL namespaces — eviction/spill/aging
        are pool-wide (one LRU clock), so a hot model's pressure reclaims
        a cold model's pages."""
        out = []
        stack = [c for root in self._roots.values()
                 for c in root.children.values()]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            out.append(n)
        return out

    def _evictable(self) -> list:  # lfkt: holds[_lock]
        """Non-busy device-resident nodes with every page unpinned —
        spill-eligible; drop-eligible additionally requires no children
        (dropping an interior node would orphan its subtree)."""
        return [n for n in self._nodes()
                if n.pages is not None and id(n) not in self._busy
                and not any(p in self._page_refs for p in n.pages)]

    def _spilled_leaves(self) -> list:  # lfkt: holds[_lock]
        """Non-busy spilled leaves — the spill-tier aging set."""
        return [n for n in self._nodes()
                if n.pages is None and not n.children
                and id(n) not in self._busy]

    def _unlink(self, node: _Node) -> None:  # lfkt: holds[_lock]
        node.parent.children.pop(node.edge[0], None)
        node.parent = None

    def _evict_one(self, span=None) -> bool:  # lfkt: holds[_lock]
        """Evict one node, LRU-first: spill its pages to host RAM when the
        spill tier has room (aging out the LRU *spilled* leaf when it
        doesn't), otherwise drop it — interior nodes can only take the
        spill path (dropping one would orphan its subtree), so under a
        full spill tier the LRU droppable *leaf* is taken instead.  False
        when nothing is evictable (every resident page pinned)."""
        cands = sorted(self._evictable(), key=lambda n: n.stamp)
        if not cands:
            return False
        for victim in cands:
            n = len(victim.pages)
            if self.spill_pages:
                # age the spill tier: drop LRU spilled leaves until the
                # victim fits (a spilled conversation colder than the one
                # being evicted is the right one to forget) — but ONLY
                # when aging can actually make it fit: pages held by
                # spilled INTERIOR nodes cannot be aged away (dropping
                # one would orphan its subtree), so a victim that cannot
                # fit past them — or past the tier size itself — skips
                # straight to the drop path instead of destroying every
                # warm leaf for zero benefit.  (Conservative: cascading
                # unlinks could turn an interior node into an ageable
                # leaf mid-loop; we forgo that to keep the guard simple.)
                unageable = self._spill_used - sum(
                    len(s.edge) for s in self._spilled_leaves())
                while n + unageable <= self.spill_pages \
                        and self._spill_used + n > self.spill_pages:
                    spilled = self._spilled_leaves()
                    if not spilled:
                        break
                    aged = min(spilled, key=lambda s: s.stamp)
                    self._spill_used -= len(aged.edge)
                    aged.host = None
                    self._unlink(aged)
            if self.spill_pages and self._spill_used + n <= self.spill_pages:
                t0 = time.time()
                # DMA the victim's pages to host, then free the arena
                # slots; the node stays matchable, restoring on its next
                # hit (works for interior nodes: the tree is untouched)
                victim.host = jax.device_get(jax.tree.map(
                    lambda al: al[jnp.asarray(victim.pages, jnp.int32)],
                    self.arena))
                self._spill_used += n
                self.counters["spills"] += 1
                self._emit("inc", "prefix_cache_spills_total")
                if span is not None:
                    span.event("kv_spill", pages=n,
                               bytes=n * self.page_nbytes,
                               host_s=round(time.time() - t0, 6))
                self._free.extend(victim.pages)
                victim.pages = None
                self._ns_pages[victim.ns] = max(
                    0, self._ns_pages.get(victim.ns, 0) - n)
            elif not victim.children:
                self._free.extend(victim.pages)
                victim.pages = None
                self._ns_pages[victim.ns] = max(
                    0, self._ns_pages.get(victim.ns, 0) - n)
                self._unlink(victim)
            else:
                continue        # interior, no spill room: try the next LRU
            self.counters["evictions"] += 1
            self._emit("inc", "prefix_cache_evictions_total")
            return True
        return False

    def _alloc(self, n: int, span=None):  # lfkt: holds[_lock]
        """``n`` free page ids, evicting LRU unpinned nodes as needed;
        None when the demand cannot be met (pinned solid)."""
        if n > self.n_pages:
            return None
        while len(self._free) < n:
            if not self._evict_one(span=span):
                return None
        out = self._free[:n]
        del self._free[:n]
        return out
