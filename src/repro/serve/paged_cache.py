"""Block-table paged KV cache over the model's ``cache_specs`` layouts.

The dense slot engine allocates ``batch_slots x max_len`` of cache and wastes
``max_len - len(request)`` of it on every short request.  Here the cache is a
pool of fixed-size pages plus a per-lane block table — the serving analogue
of the paper's vault-interleaved SMC memory: request state lives scattered
across near-memory pages, a free-list hands pages out on demand, and the
decode step streams each lane's pages through the compute.

Layout (stacked decode layout, ``decode_unroll_layers=False``):

* seq-carrying leaves (``SEQ_CACHE_KEYS``: attention k/v, MLA latent/k_rope)
  become pools ``(layers, n_pages, page_size, *tail)`` shared by all lanes;
* recurrent-state leaves (SSD state, RG-LRU h, conv rings) keep the per-lane
  ``(layers, lanes, *tail)`` layout — fixed-size state is its own "page".

``gather_views`` / ``absorb_decode`` are pure-jnp tree transforms used inside
the engine's jitted decode; the Pallas read kernel (``kernels/paged_attn``)
is selectable via ``impl='pallas'``.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.ownership import admission_api, pool_mutator
from repro.models.common import SEQ_CACHE_KEYS, cache_leaf_key


def _is_seq(path) -> bool:
    return cache_leaf_key(path) in SEQ_CACHE_KEYS


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_lane_rows(pools, rows, lane):
    """Write each row (layers, 1, *tail) into ``lane`` of its state pool
    (layers, lanes, *tail), all in one program.  The pools are donated, so
    each row is written in place and no second copy of the state exists."""
    return [jax.lax.dynamic_update_slice_in_dim(p, r.astype(p.dtype), lane, 1)
            for p, r in zip(pools, rows)]


# ---------------------------------------------------------------------------
# Refcounted page allocator (host side) — the ownership choke point
# ---------------------------------------------------------------------------


class PageAllocator:
    """LIFO free list + per-page refcounts over ``n_pages`` physical pages.

    The ownership API (the old raw ``alloc``/``free`` surface, redesigned
    for prefix sharing):

    * ``acquire(n)``        — take n pages out of the free list, each with
      refcount 1 (the caller is the sole owner);
    * ``share(pages)``      — add one owner per page (prefix index adopting
      a lane's pages, a second request matching a resident prefix);
    * ``release(pages)``    — drop one owner per page; pages whose count
      hits zero return to the free list (the return value), shared pages
      survive their co-owners;
    * ``fork_for_write(p)`` — copy-on-write bookkeeping: exchange the
      caller's reference to a *shared* page for a fresh private page id
      (the caller copies the bytes — ``PagedKVCache.fork_pages``).

    Callers serialize access (the serving engine holds its bookkeeping lock
    around every acquire/release — the admission pipeline thread and the
    decode loop share this free list).  The refcount map makes the
    cross-thread failure modes loud instead of silent: a page over-released
    (or released by one thread while handed out by another) trips the
    assert the moment it happens, not steps later as token corruption.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, -1, -1))
        self._free_set = set(self._free)
        # page -> live reference count; a page is in exactly one of
        # (_free_set, refs) at all times
        self.refs: dict[int, int] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        """Live owners of ``page`` (0 = free)."""
        return self.refs.get(page, 0)

    @pool_mutator("free_list")
    def acquire(self, n: int) -> list[int] | None:
        """n fresh pages at refcount 1 each, or None (and no allocation)
        if the pool can't cover it."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(pages)
        for p in pages:
            self.refs[p] = 1
        return pages

    @pool_mutator("free_list")
    def share(self, pages: list[int]) -> None:
        """Add one owner to each (already live) page."""
        for p in pages:
            assert 0 <= p < self.n_pages
            n = self.refs.get(p, 0)
            assert n >= 1 and p not in self._free_set, (
                f"page {p} shared while free"
            )
            self.refs[p] = n + 1

    @pool_mutator("free_list")
    def release(self, pages: list[int]) -> list[int]:
        """Drop one owner per page; returns the subset whose refcount hit
        zero and went back to the free list."""
        freed = []
        for p in pages:
            assert 0 <= p < self.n_pages
            n = self.refs.get(p, 0)
            assert n >= 1 and p not in self._free_set, (
                f"page {p} released while free (double release)"
            )
            if n == 1:
                del self.refs[p]
                self._free.append(p)
                self._free_set.add(p)
                freed.append(p)
            else:
                self.refs[p] = n - 1
        return freed

    @pool_mutator("free_list")
    def fork_for_write(self, page: int) -> int | None:
        """Copy-on-write bookkeeping: give the caller a private page id in
        exchange for its reference to ``page``.  Returns ``page`` itself
        when the caller is already the sole owner, a fresh page id (whose
        bytes the caller must copy) when it is shared, or None when the
        pool cannot cover the fork."""
        if self.refs.get(page, 0) <= 1:
            return page
        got = self.acquire(1)
        if got is None:
            return None
        self.release([page])
        return got[0]

    def check_invariant(self) -> None:
        """Free list + refcounts sane: no duplicates, every entry in range,
        set and list agree, and every non-free page has a live owner.
        Cheap enough for tests to call between stress steps."""
        assert len(self._free) == len(self._free_set), (
            "free list/set diverged (double-release or lost page)"
        )
        assert self._free_set <= set(range(self.n_pages))
        assert set(self.refs) == set(range(self.n_pages)) - self._free_set, (
            "refcount map out of sync with the free list"
        )
        assert all(n >= 1 for n in self.refs.values())


# ---------------------------------------------------------------------------
# Prefix index (radix trie over page-sized token chunks)
# ---------------------------------------------------------------------------


@dataclass
class PrefixClaim:
    """Result of a successful prefix match at admission.

    ``kind == "full"`` means the whole prompt (and its first sampled token)
    is known — prefill is skipped entirely; ``kind == "partial"`` means the
    leading ``matched_tokens`` (a page- and chunk-aligned span) are shared
    device pages and the prefill seeds from them.  ``pages`` is the
    request's complete logical page list (shared + fresh), ``restore``
    carries ``(holder, host_page, device_page)`` triples for host-resident
    prefix pages that still need a host→device copy.
    """

    kind: str
    matched_tokens: int
    pages: list[int]
    restore: list = field(default_factory=list)
    first_token: int = -1
    state: object = None        # numpy recurrent-state snapshot (full match)
    seed_pages: int = 0         # partial: leading shared device pages


class _PrefixNode:
    """One page-sized chunk of some prompt.  ``page`` is the resident
    device page holding that chunk's KV rows (the index owns one allocator
    reference to it), ``host_page`` a retired host-tier copy; either, both,
    or neither may be set.  ``pending`` counts in-flight restores."""

    __slots__ = ("children", "terminals", "page", "host_page", "pending",
                 "last_used")

    def __init__(self):
        self.children: dict[bytes, _PrefixNode] = {}
        self.terminals: dict[bytes, _Terminal] = {}
        self.page: int | None = None
        self.host_page: int | None = None
        self.pending = 0
        self.last_used = 0


class _Terminal:
    """A complete prompt ending at a node: the sub-page tail (``rem``
    tokens on ``page``), the greedy first sampled token, and — for
    recurrent families — a numpy snapshot of the post-prefill state."""

    __slots__ = ("page", "host_page", "pending", "last_used", "rem",
                 "first_token", "state", "length")

    def __init__(self, rem: int, first_token: int, length: int, state):
        self.page: int | None = None
        self.host_page: int | None = None
        self.pending = 0
        self.last_used = 0
        self.rem = rem
        self.first_token = first_token
        self.state = state
        self.length = length


class PrefixIndex:
    """Radix trie over per-page prompt content → resident KV pages.

    All mutation happens under the owning engine's lock; the decode loop
    inserts finished prefills (:meth:`insert`), admission claims matches
    (:meth:`claim` — shares device pages / books host restores), and
    reclaim runs from both sides (:meth:`drop` is admission-safe release-
    only; :meth:`retire` additionally copies cold pages into the host tier
    and is decode-loop-only because it reads the device pools).

    Families without seq-carrying cache leaves (pure-SSD: mamba2) index
    prompts structurally and share by *state snapshot* at the terminal —
    every claimed page is fresh.  Hybrid families (RG-LRU) share the seq
    pages and restore state on full-terminal matches only.
    """

    _STAT_KEYS = ("hits", "misses", "hit_tokens", "lookup_tokens", "forks",
                  "retired_pages", "restored_pages", "dropped_pages")

    def __init__(self, allocator: PageAllocator, page_size: int,
                 has_seq: bool, has_state: bool = False, host=None,
                 metrics=None, max_terminals: int = 512):
        from repro.obs.metrics import MetricsRegistry

        self.allocator = allocator
        self.page_size = page_size
        self.has_seq = has_seq
        self.has_state = has_state
        self.host = host
        self.max_terminals = max_terminals
        self.root = _PrefixNode()
        self.by_page: dict[int, object] = {}   # device page -> holder
        self._terminals: list[tuple[_PrefixNode, bytes, _Terminal]] = []
        self._clock = 0
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._c = {k: self.metrics.counter("prefix." + k)
                   for k in self._STAT_KEYS}

    # -- plumbing ----------------------------------------------------------

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _keys(self, tokens) -> tuple[list[bytes], bytes]:
        toks = np.asarray(tokens, np.int64)
        ps = self.page_size
        full = len(toks) // ps
        chunks = [toks[i * ps:(i + 1) * ps].tobytes() for i in range(full)]
        return chunks, toks[full * ps:].tobytes()

    def _walk(self, tokens):
        """Longest structurally-matched chain (content-resident for seq
        families) plus the exact terminal when the whole prompt is known."""
        chunk_keys, rem_key = self._keys(tokens)
        node, chain = self.root, []
        for k in chunk_keys:
            child = node.children.get(k)
            if child is None:
                return chain, None
            if self.has_seq and child.page is None and child.host_page is None:
                return chain, None          # content dropped → unreachable
            chain.append(child)
            node = child
        term = node.terminals.get(rem_key)
        if (term is not None and self.has_seq and term.rem
                and term.page is None and term.host_page is None):
            term = None
        return chain, term

    def preview(self, tokens) -> int:
        """Router affinity probe: resident-prefix tokens for ``tokens``
        (no side effects, no allocation)."""
        chain, term = self._walk(tokens)
        if term is not None and len(chain) == len(tokens) // self.page_size:
            return len(tokens)
        if not self.has_seq or self.has_state:
            return 0                # partial matches need seq-only caches
        return len(chain) * self.page_size

    # -- admission side (claim / drop) -------------------------------------

    def _acquire_fresh(self, n: int) -> list[int] | None:
        """Acquire with one drop-reclaim retry — admission must be able to
        shrink the index itself, or a pool full of cold prefixes deadlocks
        an idle engine (nothing running → no decode-side reclaim)."""
        if n == 0:
            return []
        got = self.allocator.acquire(n)
        if got is None and self.drop(n):
            got = self.allocator.acquire(n)
        return got

    def claim(self, tokens, chunk: int) -> PrefixClaim | None:
        """Match ``tokens`` against the index and take ownership for one
        request: shares resident pages, acquires fresh ones for host
        restores and the unmatched tail.  Returns None (no side effects)
        on a miss or when the pool can't cover the fresh pages."""
        L = len(tokens)
        self._c["lookup_tokens"].inc(L)
        claim = self._claim_inner(tokens, chunk) if L else None
        if claim is None:
            self._c["misses"].inc()
        else:
            self._c["hits"].inc()
            self._c["hit_tokens"].inc(claim.matched_tokens)
        return claim

    def _claim_inner(self, tokens, chunk: int) -> PrefixClaim | None:
        L = len(tokens)
        ps = self.page_size
        chain, term = self._walk(tokens)
        full, rem = L // ps, L % ps
        total = -(-(L + 1) // ps)          # pages incl. the decode slot
        if term is not None and len(chain) == full:
            holders = (list(chain) + ([term] if rem else [])
                       if self.has_seq else [])
            if (any(h.page is None for h in holders)
                    and self.host is None):
                term = None                 # host copy gone with the tier
            else:
                n_fresh = total - sum(1 for h in holders
                                      if h.page is not None)
                # pin the claim's own holders across the acquire: on a
                # shortfall _acquire_fresh reclaims through drop(), which
                # would otherwise evict exactly these cold pages and leave
                # the fresh list short of the holders it nulled
                for h in holders:
                    h.pending += 1
                try:
                    got = self._acquire_fresh(n_fresh)
                finally:
                    for h in holders:
                        h.pending -= 1
                if got is None:
                    return None
                pages, restore, gi = [], [], 0
                for h in holders:
                    h.last_used = self._tick()
                    if h.page is not None:
                        self.allocator.share([h.page])
                        pages.append(h.page)
                    else:
                        dev = got[gi]
                        gi += 1
                        h.pending += 1
                        restore.append((h, h.host_page, dev))
                        pages.append(dev)
                pages.extend(got[gi:])
                term.last_used = self._tick()
                return PrefixClaim(
                    kind="full", matched_tokens=L, pages=pages,
                    restore=restore, first_token=term.first_token,
                    state=term.state,
                )
        # partial: leading device-resident pages seed a chunked prefill.
        # Attention-only families: a hybrid's recurrent state at token m is
        # NOT reconstructable from seq pages alone, so state-carrying
        # families only ever match full terminals (state snapshot in hand)
        if not self.has_seq or self.has_state or chunk <= 0:
            return None
        dev_chain = 0
        for node in chain:
            if node.page is None:
                break
            dev_chain += 1
        m = min(dev_chain * ps, L - 1)
        m -= m % ps
        while m > 0 and m % chunk:
            m -= ps
        if m < ps:
            return None
        k = m // ps
        # same pin as the full path: drop()-reclaim inside the acquire must
        # not evict the chain pages this claim is about to share
        for i in range(k):
            chain[i].pending += 1
        try:
            got = self._acquire_fresh(total - k)
        finally:
            for i in range(k):
                chain[i].pending -= 1
        if got is None:
            return None
        shared = [chain[i].page for i in range(k)]
        self.allocator.share(shared)
        for i in range(k):
            chain[i].last_used = self._tick()
        return PrefixClaim(kind="partial", matched_tokens=m,
                           pages=shared + got, seed_pages=k)

    def abort(self, claim: PrefixClaim) -> None:
        """Undo the restore bookkeeping of an unconsumed claim (early
        retire): holders stay host-resident, the fresh device pages ride
        the request's page list into its release."""
        for h, _hp, _dev in claim.restore:
            h.pending -= 1

    def finish_restore(self, claim: PrefixClaim) -> None:
        """Device residency restored: adopt the fresh page into each holder
        that is still without one (keeping the host copy — a future retire
        is then free).  Runs under the lock after ``commit_swap_in``."""
        for h, _hp, dev in claim.restore:
            h.pending -= 1
            if h.page is None:
                self.allocator.share([dev])
                h.page = dev
                self.by_page[dev] = h
            h.last_used = self._tick()
        self._c["restored_pages"].inc(len(claim.restore))

    def drop(self, n: int) -> int:
        """Release up to ``n`` cold device-resident pages outright (no
        host copy — content without a ``host_page`` is lost).  Admission-
        safe: touches only the free list."""
        freed = 0
        for p, h in sorted(self.by_page.items(),
                           key=lambda kv: kv[1].last_used):
            if freed >= n:
                break
            if h.pending or self.allocator.refcount(p) != 1:
                continue
            self.allocator.release([p])
            del self.by_page[p]
            h.page = None
            freed += 1
        self._c["dropped_pages"].inc(freed)
        return freed

    # -- decode side (insert / retire) -------------------------------------

    def insert(self, tokens, pages: list[int], state, first_token: int) -> None:
        """Adopt a finished prefill's pages: walk/extend the trie, share
        each chunk page into a node that lacks one, and register the
        terminal (tail page + first greedy token + state snapshot).
        Decode-loop-only, under the lock, after ``write_prefill``."""
        L = len(tokens)
        ps = self.page_size
        full, rem = L // ps, L % ps
        chunk_keys, rem_key = self._keys(tokens)
        node = self.root
        for i, key in enumerate(chunk_keys):
            child = node.children.get(key)
            if child is None:
                child = node.children[key] = _PrefixNode()
            if (self.has_seq and child.page is None
                    and child.host_page is None and child.pending == 0):
                p = pages[i]
                self.allocator.share([p])
                child.page = p
                self.by_page[p] = child
            child.last_used = self._tick()
            node = child
        term = node.terminals.get(rem_key)
        if term is None:
            if len(self._terminals) >= self.max_terminals:
                self._evict_terminal()
            term = _Terminal(rem=rem, first_token=first_token, length=L,
                             state=state)
            node.terminals[rem_key] = term
            self._terminals.append((node, rem_key, term))
            if rem and self.has_seq:
                p = pages[full]
                self.allocator.share([p])
                term.page = p
                self.by_page[p] = term
        term.last_used = self._tick()

    def _evict_terminal(self) -> None:
        """LRU-evict one idle terminal (cap on state snapshots held)."""
        idx = None
        for i, (_node, _key, t) in enumerate(self._terminals):
            if t.pending:
                continue
            if idx is None or t.last_used < self._terminals[idx][2].last_used:
                idx = i
        if idx is None:
            return
        node, key, t = self._terminals.pop(idx)
        del node.terminals[key]
        if t.page is not None:
            del self.by_page[t.page]
            self.allocator.release([t.page])
        if t.host_page is not None and self.host is not None:
            self.host.allocator.release([t.host_page])

    def retire_candidates(self, n: int) -> list[tuple[int, object]]:
        """Up to ``n`` cold sole-owned device pages without a host copy,
        LRU first — the decode loop copies these out via ``put_pages``."""
        cands = [(p, h) for p, h in self.by_page.items()
                 if not h.pending and h.host_page is None
                 and self.allocator.refcount(p) == 1]
        cands.sort(key=lambda kv: kv[1].last_used)
        return cands[:n]

    def release_host_backed(self, n: int) -> int:
        """Free up to ``n`` cold device pages that already have a host
        copy — residency can be restored later at zero copy cost."""
        freed = 0
        for p, h in sorted(self.by_page.items(),
                           key=lambda kv: kv[1].last_used):
            if freed >= n:
                break
            if (h.pending or h.host_page is None
                    or self.allocator.refcount(p) != 1):
                continue
            self.allocator.release([p])
            del self.by_page[p]
            h.page = None
            freed += 1
        self._c["retired_pages"].inc(freed)
        return freed

    def note_retired(self, entries) -> None:
        """Commit a put_pages copy-out: mark holders host-resident and
        release their device pages."""
        for (p, h), hp in entries:
            h.host_page = hp
            self.allocator.release([p])
            del self.by_page[p]
            h.page = None
        self._c["retired_pages"].inc(len(entries))

    def note_fork(self, n: int = 1) -> None:
        self._c["forks"].inc(n)


# ---------------------------------------------------------------------------
# Pure tree transforms (run inside the engine's jitted decode)
# ---------------------------------------------------------------------------


def gather_views(pools, block_tables: jax.Array, impl: str = "xla"):
    """Materialize per-lane contiguous views from the page pools.

    seq leaves: (layers, n_pages, PS, *t) + table (lanes, P) →
    (layers, lanes, P*PS, *t); unallocated (-1) pages read as zeros so a
    fresh lane's view is bit-identical to the dense engine's zero-init
    cache.  State leaves pass through unchanged.
    """

    def leaf(path, x):
        if not _is_seq(path):
            return x
        reps, n, ps = x.shape[0], x.shape[1], x.shape[2]
        lanes, p = block_tables.shape
        if impl == "pallas":
            from repro.kernels import ops as kops

            # (n, layers, PS, *t) page rows → (lanes, P, layers, PS, *t)
            got = kops.paged_gather(jnp.moveaxis(x, 0, 1), block_tables)
            view = jnp.moveaxis(got, 2, 0)            # (layers, lanes, P, PS, *t)
        else:
            view = jnp.take(x, jnp.clip(block_tables, 0, n - 1), axis=1)
            mask = (block_tables >= 0).reshape(
                (1, lanes, p) + (1,) * (view.ndim - 3)
            )
            view = jnp.where(mask, view, jnp.zeros((), x.dtype))
        return view.reshape((reps, lanes, p * ps) + x.shape[3:])

    return jax.tree_util.tree_map_with_path(leaf, pools)


def absorb_decode(pools, new_views, block_tables, positions, active,
                  page_size: int):
    """Fold one decode step's cache updates back into the pools.

    seq leaves: scatter the column each lane wrote at ``positions`` into its
    page (inactive lanes scatter to page -1 → dropped).  State leaves: keep
    the new state only for active lanes.
    """
    lanes = positions.shape[0]
    rows = jnp.arange(lanes)

    def leaf(path, pool, view):
        if _is_seq(path):
            col = view[:, rows, positions]              # (layers, lanes, *t)
            page = jnp.take_along_axis(
                block_tables, (positions // page_size)[:, None], axis=1
            )[:, 0]
            # inactive/unallocated lanes must scatter out of bounds so
            # mode='drop' discards them — a negative index is NOT out of
            # bounds (jax normalizes it to n_pages-1 first, corrupting the
            # last physical page), so the sentinel is n_pages
            page = jnp.where(active & (page >= 0), page, pool.shape[1])
            off = positions % page_size
            return pool.at[:, page, off].set(col.astype(pool.dtype),
                                             mode="drop")
        keep = active.reshape((1, lanes) + (1,) * (pool.ndim - 2))
        return jnp.where(keep, view.astype(pool.dtype), pool)

    return jax.tree_util.tree_map_with_path(leaf, pools, new_views)


# (the per-lane gather/scatter extend helpers — gather_lane_view,
# merge_lane_state, strip_seq_leaves, scatter_lane_view — were removed with
# the two-loop engine: chunked prefill now computes into a PRIVATE
# capacity-length cache tree on the admission pipeline and the decode loop
# folds it into the pages at lane assignment via write_prefill)


# ---------------------------------------------------------------------------
# The cache object (pools + tables + allocator)
# ---------------------------------------------------------------------------


class PagedKVCache:
    """Page pools + per-lane block tables + free list for one engine.

    ``host_pages > 0`` attaches a second storage tier (``host_tier.
    HostPagePool``): host-DRAM twins of the seq-leaf pools that preemption
    swaps victim pages out to instead of freeing them — see ``swap_out`` /
    ``swap_in``.  ``host_shardings`` optionally carries a replicated
    ``NamedSharding`` tree (``dist.sharding.host_tier_shardings``) for the
    ``device_put`` staging on a mesh.  ``device`` places the pools (None:
    JAX's default device).
    """

    def __init__(self, model, lanes: int, n_pages: int, page_size: int,
                 max_len: int, host_pages: int = 0, host_shardings=None,
                 metrics=None, prefix_sharing: bool = False, device=None):
        if not hasattr(model, "cache_page_specs"):
            raise TypeError(
                f"{type(model).__name__} has no paged-cache layout "
                "(cache_page_specs); serve it with the dense slot engine"
            )
        self.model = model
        self.lanes = lanes
        self.page_size = page_size
        self.n_pages = n_pages
        self.pages_per_lane = math.ceil(max_len / page_size)
        self.capacity = self.pages_per_lane * page_size   # per-lane view len
        specs = model.cache_page_specs(lanes, n_pages, page_size)
        self.pools = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype, device=device), specs
        )
        self.allocator = PageAllocator(n_pages)
        self.block_tables = np.full((lanes, self.pages_per_lane), -1, np.int32)
        self.host = None
        self.host_shardings = host_shardings
        if host_pages:
            from .host_tier import HostPagePool

            self.host = HostPagePool(self.pools, host_pages, page_size,
                                     metrics=metrics)
        self.prefix = None
        if prefix_sharing:
            self.prefix = PrefixIndex(
                self.allocator, page_size, has_seq=self._has_seq_leaves(),
                has_state=self.has_state_leaves(),
                host=self.host, metrics=metrics,
            )

    def _has_seq_leaves(self) -> bool:
        found = []
        jax.tree_util.tree_map_with_path(
            lambda path, x: found.append(1) if _is_seq(path) else None,
            self.pools,
        )
        return bool(found)

    # -- host-side bookkeeping ---------------------------------------------

    def pages_for(self, n_tokens: int) -> int:
        return math.ceil(n_tokens / self.page_size)

    def acquire(self, n_tokens: int) -> list[int] | None:
        return self.allocator.acquire(self.pages_for(n_tokens))

    @pool_mutator("pools")
    def assign_lane(self, lane: int, pages: list[int]) -> None:
        self.block_tables[lane] = -1
        self.block_tables[lane, : len(pages)] = pages

    @pool_mutator("pools")
    def extend_lane(self, lane: int, page: int, n_owned: int) -> None:
        self.block_tables[lane, n_owned] = page

    @pool_mutator("pools")
    def clear_lane(self, lane: int) -> None:
        self.block_tables[lane] = -1

    def occupancy(self) -> float:
        return 1.0 - self.allocator.n_free / self.n_pages

    def check_invariant(self) -> None:
        """Pool/table consistency: the free lists are sane, no physical page
        is mapped by more lanes than it has owners, and no mapped page sits
        in the free list.  Cheap (one pass over a lanes x pages_per_lane int
        table); the sanitizer runs it after every mutating op, tests at
        checkpoints."""
        self.allocator.check_invariant()
        mapped = self.block_tables[self.block_tables >= 0].tolist()
        counts: dict[int, int] = {}
        for p in mapped:
            counts[p] = counts.get(p, 0) + 1
        for p, c in counts.items():
            assert c <= self.allocator.refcount(p), (
                f"page {p} mapped by {c} lanes with refcount "
                f"{self.allocator.refcount(p)} (block-table aliasing)"
            )
        stale = set(mapped) & self.allocator._free_set
        assert not stale, f"free pages still mapped by a lane: {sorted(stale)}"
        if self.prefix is not None:
            for p, h in self.prefix.by_page.items():
                assert h.page == p, "prefix index reverse map out of sync"
                assert self.allocator.refcount(p) >= 1, (
                    f"prefix index holds freed page {p}"
                )
        if self.host is not None:
            self.host.allocator.check_invariant()

    # -- eager (per-request) writes ----------------------------------------

    @pool_mutator("pools")
    def write_prefill(self, pages: list[int], cache, lane: int | None = None,
                      skip_pages: int = 0):
        """Scatter a prefill cache (leaves (layers, 1, s, *t)) into
        ``pages``; state leaves go to ``lane``'s row when given.  Seq leaves
        shorter than the page span are zero-padded; longer ones (a chunked
        prefill's capacity-length private tree) are sliced — positions past
        the reserved pages are unwritten zeros by construction.

        ``skip_pages`` leading pages are left untouched: a partial prefix
        match seeded the prefill from those *shared* pages, whose pool
        content is already bit-identical (and co-owned by other lanes)."""
        ps = self.page_size
        dst = pages[skip_pages:]
        if not dst and lane is None:
            return
        pages_arr = jnp.asarray(dst, jnp.int32)

        def leaf(path, pool, pc):
            if _is_seq(path):
                if not dst:
                    return pool
                reps = pc.shape[0]
                pc = pc[:, :, skip_pages * ps:]
                s = pc.shape[2]
                cap = len(dst) * ps
                if s > cap:
                    pc = pc[:, :, :cap]
                else:
                    pad = [(0, 0)] * pc.ndim
                    pad[2] = (0, cap - s)
                    pc = jnp.pad(pc, pad)
                paged = pc.reshape((reps, len(dst), ps) + pc.shape[3:])
                return pool.at[:, pages_arr].set(paged.astype(pool.dtype))
            return pool

        self.pools = jax.tree_util.tree_map_with_path(leaf, self.pools, cache)
        if lane is not None:
            self.write_state(lane, cache)

    @pool_mutator("pools")
    def write_state(self, lane: int, cache) -> None:
        """Copy only the recurrent-state leaves of a held prefill cache
        (leaves (layers, 1, *tail)) into ``lane``'s row, in place: the old
        state leaves are donated to the write."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.pools)
        src = jax.tree_util.tree_leaves(cache)
        idx = [i for i, (path, _) in enumerate(flat) if not _is_seq(path)]
        if not idx:
            return
        leaves = [x for _, x in flat]
        new = _set_lane_rows([leaves[i] for i in idx], [src[i] for i in idx],
                             lane)
        for i, x in zip(idx, new):
            leaves[i] = x
        self.pools = jax.tree_util.tree_unflatten(treedef, leaves)

    def has_state_leaves(self) -> bool:
        found = []
        jax.tree_util.tree_map_with_path(
            lambda path, x: found.append(1) if not _is_seq(path) else None,
            self.pools,
        )
        return bool(found)

    # -- host tier (swap-vs-recompute preemption) --------------------------

    def swap_reserve(self, st):
        """Bookkeeping half of a swap-out for one victim: reserve host
        pages and compute the dirty list.  Returns ``(handle, dirty)`` or
        None (host tier absent/exhausted → recompute fallback).  Call under
        the engine lock."""
        if self.host is None:
            return None
        return self.host.reserve(st.swap_handle, len(st.pages))

    @pool_mutator("pools")
    def swap_out_batch(self, swap_items) -> None:
        """DMA half for a victim set: ``swap_items`` is ``[(st, dirty)]``
        with host pages already reserved.  ONE device→host read per cache
        leaf covers every victim (vs one per victim before)."""
        self.host.commit_many(self.pools, [
            (st.swap_handle, list(st.pages), dirty, st.lane, st.length)
            for st, dirty in swap_items
        ])

    @pool_mutator("pools")
    def swap_out(self, pages: list[int], lane: int, length: int,
                 handle=None):
        """Copy a victim's pages + lane state to the host tier.  Returns a
        ``SwapHandle`` or None (host tier absent/exhausted — the caller
        falls back to recompute-preemption, with no host pages held)."""
        if self.host is None:
            return None
        return self.host.swap_out(self.pools, pages, lane, length, handle)

    @admission_api
    def stage_in(self, handle):
        """Host→device staging for a restore — pure DMA, pools untouched
        (safe on the admission pipeline thread).  Returns
        ``(staged_tree, state_tree)`` for ``commit_swap_in``."""
        return self.host.stage_in(handle, self.host_shardings)

    @pool_mutator("pools")
    def commit_swap_in(self, staged, pages: list[int]) -> None:
        """Scatter a staged restore into freshly allocated device ``pages``
        (decode-loop-owned: the only thread that writes the pools).
        ``pages`` may carry one extra growth-slack page beyond the staged
        rows (see ``Scheduler.admit_next``) — only the staged prefix is
        written."""

        def leaf(path, pool, chunk):
            if not _is_seq(path):
                return pool
            dev_idx = jnp.asarray(pages[: chunk.shape[1]], jnp.int32)
            return pool.at[:, dev_idx].set(chunk)

        self.pools = jax.tree_util.tree_map_with_path(
            leaf, self.pools, staged
        )

    @pool_mutator("pools")
    def swap_in(self, handle, pages: list[int]):
        """Restore a swapped request into freshly allocated device ``pages``;
        returns the captured recurrent-state tree (None for stateless
        models) to be written once a lane is assigned."""
        self.pools, state = self.host.swap_in(
            self.pools, handle, pages, self.host_shardings
        )
        return state

    def host_free(self, handle) -> None:
        if self.host is not None:
            self.host.free(handle)

    def host_occupancy(self) -> float:
        return self.host.occupancy() if self.host is not None else 0.0

    # -- prefix sharing (radix index + copy-on-write) ----------------------

    @admission_api
    def claim_match(self, tokens, chunk: int):
        """Admission-side prefix lookup: a :class:`PrefixClaim` with pages
        already owned by the request (shared + fresh), or None.  Under the
        engine lock."""
        if self.prefix is None or not len(tokens):
            return None
        return self.prefix.claim(tokens, chunk)

    @admission_api
    def seed_prefix(self, tree, pages: list[int]):
        """Copy ``pages``' pool rows into positions ``[0, len(pages)*ps)``
        of a private prefill tree (admission thread).  Pure: reads a
        snapshot of ``self.pools`` — shared prefix pages are never written
        in place (copy-on-write), so the read races with nothing."""
        ps = self.page_size
        idx = jnp.asarray(pages, jnp.int32)
        span = len(pages) * ps

        def leaf(path, pc, pool):
            if not _is_seq(path):
                return pc
            take = jnp.take(pool, idx, axis=1)   # (layers, P, ps, *t)
            flat = take.reshape(
                (take.shape[0], 1, span) + take.shape[3:]
            )
            return pc.at[:, :, :span].set(flat.astype(pc.dtype))

        return jax.tree_util.tree_map_with_path(leaf, tree, self.pools)

    def snapshot_state(self, cache):
        """Numpy copy of the recurrent-state leaves of a prefill tree for
        the prefix index (seq leaves become 0-d placeholders, mirroring
        ``SwapHandle.state``); None for stateless families.  Device reads —
        call outside the lock."""
        if not self.has_state_leaves():
            return None
        return jax.tree_util.tree_map_with_path(
            lambda path, x: (np.zeros((), x.dtype) if _is_seq(path)
                             else np.asarray(x)),
            cache,
        )

    @pool_mutator("pools")
    def fork_pages(self, copies: list[tuple[int, int]]) -> None:
        """Device half of copy-on-write: duplicate each ``(src, dst)``
        page's rows in every seq-leaf pool — one gather+scatter per leaf
        for the whole fork batch."""
        if not copies:
            return
        src = jnp.asarray([a for a, _ in copies], jnp.int32)
        dst = jnp.asarray([b for _, b in copies], jnp.int32)

        def leaf(path, pool):
            if not _is_seq(path):
                return pool
            return pool.at[:, dst].set(jnp.take(pool, src, axis=1))

        self.pools = jax.tree_util.tree_map_with_path(leaf, self.pools)

    def prefix_insert(self, tokens, pages, state, first_token: int) -> None:
        """Adopt a finished prefill into the index (decode loop, under the
        lock, after ``write_prefill``)."""
        if self.prefix is not None:
            self.prefix.insert(tokens, pages, state, first_token)

    def prefix_drop(self, n: int) -> int:
        """Admission-safe index shrink: release cold pages outright."""
        if self.prefix is None:
            return 0
        return self.prefix.drop(n)

    def prefix_retire(self, n: int) -> int:
        """Decode-side index shrink: free host-backed cold pages first
        (zero-copy), then copy the coldest unbacked pages into the host
        tier via one device→host read per leaf; falls back to dropping
        content when the host tier is absent or exhausted.  Returns pages
        returned to the free list."""
        if self.prefix is None:
            return 0
        freed = self.prefix.release_host_backed(n)
        if freed >= n:
            return freed
        if self.host is None or not self.prefix.has_seq:
            return freed + self.prefix.drop(n - freed)
        cands = self.prefix.retire_candidates(n - freed)
        if cands:
            host_pages = self.host.put_pages(
                self.pools, [p for p, _h in cands]
            )
            if host_pages is None:
                return freed + self.prefix.drop(n - freed)
            self.prefix.note_retired(list(zip(cands, host_pages)))
            freed += len(cands)
        if freed < n:
            freed += self.prefix.drop(n - freed)
        return freed

    def prefix_finish_restore(self, claim) -> None:
        """Flip restored holders back to device-resident (under the lock,
        after ``commit_swap_in`` of the staged prefix pages)."""
        if self.prefix is not None:
            self.prefix.finish_restore(claim)

    def abort_match(self, claim) -> None:
        """Drop the restore bookkeeping of a claim that retires before its
        lane fill (early EOS on the stored first token)."""
        if self.prefix is not None:
            self.prefix.abort(claim)
