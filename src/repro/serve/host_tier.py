"""Host-DRAM page tier behind the device page pools (KV-cache offload).

The paper's memory hierarchy keeps data near compute ("the host only
coordinates") — throwing a preempted request's KV pages away and
*recomputing* them re-crosses the main-memory bottleneck PIM systems exist
to avoid.  This module is the second tier that makes eviction a *move*
instead: a pool of host-memory pages (plain numpy buffers, staged back with
``jax.device_put``) keyed by the same block-table abstraction as the device
pools, so the scheduler can swap a victim's pages out to host DRAM and
restore them on resume without re-running prefill.

Layout mirrors ``paged_cache``: every seq-carrying leaf
``(layers, n_pages, *page)`` gets a host twin
``(layers, n_host_pages, *page)``, whole pages copied in their stored
order; recurrent-state leaves (SSD state,
RG-LRU h, conv rings) have no pages — a swap captures the victim lane's
state rows wholesale into the request's ``SwapHandle`` (they mutate every
decode step, so they are always dirty).

Dirty-page bookkeeping: decode appends — a page that was *full* at swap-out
time can never change after resume, so its host copy stays valid.  The
handle keeps the host pages across a resume and records the clean prefix;
a second preemption of the same request copies only the pages written since
(the partially-filled tail page and anything grown after it) plus the
recurrent state.

Both directions are split into a *bookkeeping* half and a *DMA* half so the
serving engine can batch and overlap them:

* swap-out: ``reserve`` (host-page allocation + dirty list, under the
  engine lock) then ``commit_many`` (ONE ``device_get`` per cache leaf for
  a whole victim set — under a preemption storm the per-victim round-trips
  dominated);
* swap-in:  ``stage_in`` (host→device ``device_put``, pools-free, runs on
  the admission pipeline thread) then ``PagedKVCache.commit_swap_in`` (the
  scatter into the pools, decode-loop-owned).

``swap_out`` / ``swap_in`` remain as the single-victim compositions.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.ownership import admission_api, pool_mutator
from repro.obs.metrics import BYTES_EDGES, MetricsRegistry

from .paged_cache import PageAllocator, _is_seq


@dataclass
class SwapHandle:
    """Per-request record of where its pages live in the host tier."""

    host_pages: list[int] = field(default_factory=list)  # logical order
    clean_pages: int = 0        # prefix whose host copy is still valid
    length: int = 0             # kv tokens valid at last swap-out
    state: object = None        # captured recurrent-state tree (numpy)


class HostPagePool:
    """Host-memory twin of the device seq-leaf pools + a free list.

    Buffers are ordinary numpy arrays — host DRAM, never sharded (see
    ``dist.sharding.host_cache_axes``); ``stage_in`` stages them back onto
    the device with ``jax.device_put`` (optionally through a replicated
    ``NamedSharding`` tree when serving on a mesh).

    Thread-safety: allocator/handle mutation (``reserve``/``free``) happens
    under the engine lock; the copy halves touch disjoint host rows per
    handle (a request is never staged and swapped out at the same time —
    it is either admitting or running, never both), so ``commit_many`` on
    the decode loop may overlap ``stage_in`` on the admission thread.
    """

    _STAT_KEYS = (
        "swap_outs", "swap_ins",
        "pages_out", "pages_in",
        "bytes_out", "bytes_in",
        "device_gets",                      # host-blocking device→host reads
        "dirty_pages_skipped",              # clean-prefix reuse
        "exhausted_fallbacks",              # host pool couldn't cover a swap
    )

    def __init__(self, device_pools, n_pages: int, page_size: int,
                 metrics: MetricsRegistry | None = None):
        self.n_pages = n_pages
        self.page_size = page_size
        self.allocator = PageAllocator(n_pages)

        def leaf(path, pool):
            if not _is_seq(path):
                # structure-preserving placeholder (state leaves ride the
                # SwapHandle, not the host pool)
                return np.zeros((), np.dtype(pool.dtype))
            shape = (pool.shape[0], n_pages) + tuple(pool.shape[2:])
            return np.zeros(shape, np.dtype(pool.dtype))

        self.buffers = jax.tree_util.tree_map_with_path(leaf, device_pools)
        # staging (admission thread) and batched swap-out (decode loop) may
        # overlap: counters live in a MetricsRegistry whose (shared engine)
        # lock makes bumps atomic AND telemetry reads coherent — the old
        # private stats lock let telemetry iterate the dict mid-update
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._c = {k: self.metrics.counter("host." + k)
                   for k in self._STAT_KEYS}
        self._h_bytes = self.metrics.histogram("host.swap_bytes", BYTES_EDGES)

    @property
    def stats(self) -> dict[str, int]:
        """Point-in-time copy of the host-tier counters (one lock cut)."""
        return self.metrics.counters("host.")

    def _bump(self, **kv) -> None:
        with self.metrics.lock:
            for k, v in kv.items():
                self._c[k].inc(v)

    @property
    def n_free(self) -> int:
        return self.allocator.n_free

    def occupancy(self) -> float:
        return 1.0 - self.allocator.n_free / self.n_pages if self.n_pages else 0.0

    # -- swap-out ----------------------------------------------------------

    @pool_mutator("free_list")
    def reserve(self, handle: SwapHandle | None, n_logical: int):
        """Bookkeeping half of a swap-out: grow the handle's host pages to
        ``n_logical`` and return ``(handle, dirty_logical_indices)``, or
        None — with no host allocation held — when the pool cannot cover
        the growth (the caller falls back to recompute-preemption)."""
        if handle is None:
            handle = SwapHandle()
        grow = n_logical - len(handle.host_pages)
        if grow > 0:
            got = self.allocator.acquire(grow)
            if got is None:
                self._bump(exhausted_fallbacks=1)
                self.free(handle)
                return None
            handle.host_pages.extend(got)
        dirty = list(range(handle.clean_pages, n_logical))
        self._bump(dirty_pages_skipped=handle.clean_pages)
        return handle, dirty

    @pool_mutator("pools")
    def commit_many(self, device_pools, items) -> None:
        """DMA half of a swap-out for a whole victim set: ``items`` is a
        list of ``(handle, device_pages, dirty, lane, length)``.  All
        victims' dirty pages are gathered with ONE ``device_get`` per seq
        leaf (and their lane states with one per state leaf) instead of a
        round-trip per victim — the swap-out *batching* the nightly bench
        trend motivated."""
        if not items:
            return
        dev_flat, splits, total = [], [], 0
        for _handle, device_pages, dirty, _lane, _length in items:
            dev_flat.extend(device_pages[i] for i in dirty)
            total += len(dirty)
            splits.append(total)
        dev_idx = jnp.asarray(dev_flat, jnp.int32) if dev_flat else None
        lanes_idx = jnp.asarray([it[3] for it in items], jnp.int32)
        has_state = []

        def copy(path, buf, pool):
            if _is_seq(path):
                if dev_idx is not None:
                    chunk = np.asarray(jnp.take(pool, dev_idx, axis=1))
                    self._bump(device_gets=1, bytes_out=chunk.nbytes)
                    self.metrics.observe("host.swap_bytes",
                                         float(chunk.nbytes), BYTES_EDGES)
                    lo = 0
                    for (handle, _pg, dirty, _ln, _len), hi in zip(items,
                                                                   splits):
                        if hi > lo:
                            host_idx = np.asarray(
                                [handle.host_pages[i] for i in dirty])
                            buf[:, host_idx] = chunk[:, lo:hi]
                        lo = hi
                return np.zeros((), np.dtype(pool.dtype))
            has_state.append(path)
            return np.asarray(jnp.take(pool, lanes_idx, axis=1))

        states = jax.tree_util.tree_map_with_path(copy, self.buffers,
                                                  device_pools)
        if has_state:
            self._bump(device_gets=len(has_state))
        for vi, (handle, device_pages, dirty, _lane, length) in enumerate(items):
            if has_state:
                # (layers, 1, *tail): the shape write_state expects back
                handle.state = jax.tree_util.tree_map_with_path(
                    lambda path, s, _vi=vi: (
                        s[:, _vi: _vi + 1] if not _is_seq(path)
                        else np.zeros((), s.dtype)),
                    states,
                )
            else:
                handle.state = None
            handle.length = length
            # pages full at swap time can never change after resume (decode
            # appends) — they form the clean prefix for the next preemption
            handle.clean_pages = min(length // self.page_size,
                                     len(device_pages))
            self._bump(swap_outs=1, pages_out=len(dirty))

    @pool_mutator("pools")
    def swap_out(self, device_pools, device_pages: list[int], lane: int,
                 length: int, handle: SwapHandle | None = None):
        """Single-victim swap-out (reserve + commit_many of one).  Returns
        the (possibly reused) handle, or None when the host tier is
        exhausted."""
        reserved = self.reserve(handle, len(device_pages))
        if reserved is None:
            return None
        handle, dirty = reserved
        self.commit_many(device_pools,
                         [(handle, list(device_pages), dirty, lane, length)])
        return handle

    # -- prefix tier (page-granular, handle-free) --------------------------

    @pool_mutator("pools")
    def put_pages(self, device_pools, device_pages: list[int]):
        """Copy individual device pages into freshly acquired host pages
        (the prefix index retiring cold shared prefixes).  One device→host
        read per seq leaf for the whole batch.  Returns the host page list
        (caller owns them), or None — with nothing held — when the host
        pool cannot cover it.  Decode-loop-only: reads the device pools."""
        got = self.allocator.acquire(len(device_pages))
        if got is None:
            self._bump(exhausted_fallbacks=1)
            return None
        dev_idx = jnp.asarray(device_pages, jnp.int32)
        host_idx = np.asarray(got)

        def copy(path, buf, pool):
            if _is_seq(path):
                chunk = np.asarray(jnp.take(pool, dev_idx, axis=1))
                self._bump(device_gets=1, bytes_out=chunk.nbytes)
                self.metrics.observe("host.swap_bytes",
                                     float(chunk.nbytes), BYTES_EDGES)
                buf[:, host_idx] = chunk
            return buf

        jax.tree_util.tree_map_with_path(copy, self.buffers, device_pools)
        self._bump(pages_out=len(device_pages))
        return got

    @admission_api
    def get_pages(self, host_pages: list[int], shardings=None):
        """Host→device staging of individual host pages (prefix restore) —
        pools untouched, so it is safe on the admission pipeline thread.
        Returns a staged tree shaped for ``PagedKVCache.commit_swap_in``;
        the host pages stay allocated (the prefix stays host-resident)."""
        host_idx = np.asarray(host_pages)

        def leaf(path, buf, sh):
            if not _is_seq(path):
                return np.zeros((), buf.dtype)
            chunk = buf[:, host_idx]
            self._bump(bytes_in=chunk.nbytes)
            self.metrics.observe("host.swap_bytes",
                                 float(chunk.nbytes), BYTES_EDGES)
            return (jax.device_put(chunk, sh) if sh is not None
                    else jnp.asarray(chunk))

        sh_tree = (shardings if shardings is not None
                   else jax.tree.map(lambda _: None, self.buffers))
        staged = jax.tree_util.tree_map_with_path(leaf, self.buffers, sh_tree)
        self._bump(pages_in=len(host_pages))
        return staged

    # -- swap-in -----------------------------------------------------------

    @admission_api
    def stage_in(self, handle: SwapHandle, shardings=None):
        """Host→device DMA half of a restore: stage every host page of
        ``handle`` (and its captured state) onto the device WITHOUT touching
        any pool — safe to run on the admission pipeline thread while the
        decode loop owns the pools.  Returns ``(staged_tree, state_tree)``;
        the decode loop folds them in via ``PagedKVCache.commit_swap_in`` /
        ``write_state``.  Host pages stay allocated — the clean prefix is
        reused if the request is preempted again."""
        host_idx = np.asarray(handle.host_pages)

        def leaf(path, buf, sh):
            if not _is_seq(path):
                # structure-preserving placeholder (state rides separately)
                return np.zeros((), buf.dtype)
            chunk = buf[:, host_idx]
            self._bump(bytes_in=chunk.nbytes)
            self.metrics.observe("host.swap_bytes",
                                 float(chunk.nbytes), BYTES_EDGES)
            return (jax.device_put(chunk, sh) if sh is not None
                    else jnp.asarray(chunk))

        sh_tree = (shardings if shardings is not None
                   else jax.tree.map(lambda _: None, self.buffers))
        staged = jax.tree_util.tree_map_with_path(leaf, self.buffers, sh_tree)
        self._bump(swap_ins=1, pages_in=len(handle.host_pages))
        state = (jax.tree.map(jnp.asarray, handle.state)
                 if handle.state is not None else None)
        return staged, state

    @pool_mutator("pools")
    def swap_in(self, device_pools, handle: SwapHandle,
                device_pages: list[int], shardings=None):
        """Single-shot restore (stage_in + scatter): returns
        ``(new_device_pools, state_tree-or-None for write_state)``."""
        assert len(device_pages) == len(handle.host_pages)
        staged, state = self.stage_in(handle, shardings)
        dev_idx = jnp.asarray(device_pages, jnp.int32)

        def leaf(path, pool, chunk):
            if not _is_seq(path):
                return pool
            return pool.at[:, dev_idx].set(chunk)

        pools = jax.tree_util.tree_map_with_path(leaf, device_pools, staged)
        return pools, state

    @pool_mutator("free_list")
    def free(self, handle: SwapHandle | None) -> None:
        """Release a request's host pages (retire, or recompute fallback
        invalidating the copy)."""
        if handle is None or not handle.host_pages:
            return
        self.allocator.release(handle.host_pages)
        handle.host_pages = []
        handle.clean_pages = 0
        handle.state = None


__all__ = ["HostPagePool", "SwapHandle"]
