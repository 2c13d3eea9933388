"""Serving subsystem: paged-KV continuous batching over an SMC cube mesh.

This module is the ONE public surface of ``repro.serve`` — import engines,
configs, routers, and telemetry types from here, not from the submodules
(their layout is an implementation detail and has moved before; see
MIGRATION.md):

* :class:`ServeEngine` + :class:`EngineConfig` (with its nested
  :class:`CacheConfig` / :class:`AdmissionConfig` / :class:`ObsConfig`
  groups) — the paged two-loop engine;
* :class:`CubeRouter` — hash / least-loaded / prefix-affinity routing over
  CUBE_AXIS replicas, one engine per device, in one process (see
  docs/architecture.md, "Cube network");
* :class:`Scheduler` / :class:`SchedulerConfig` — admission + preemption;
* :class:`PagedKVCache` / :class:`PageAllocator` / :class:`PrefixIndex` /
  :class:`PrefixClaim` — the refcounted page pool and the prefix-sharing
  radix index over it;
* :class:`HostPagePool` / :class:`SwapHandle` — the host-DRAM tier;
* :class:`AdmissionPipeline` — the async prefill/restore worker;
* :class:`DenseSlotEngine` — the v1 dense reference the paged engine is
  proven bit-exact against.
"""
from .admission import AdmissionPipeline
from .dense_engine import DenseSlotEngine
from .engine import (
    AdmissionConfig,
    CacheConfig,
    EngineConfig,
    ObsConfig,
    Request,
    ServeEngine,
)
from .host_tier import HostPagePool, SwapHandle
from .paged_cache import (
    PageAllocator,
    PagedKVCache,
    PrefixClaim,
    PrefixIndex,
)
from .router import CubeRouter
from .scheduler import RequestState, Scheduler, SchedulerConfig

__all__ = [
    "AdmissionConfig",
    "AdmissionPipeline",
    "CacheConfig",
    "CubeRouter",
    "DenseSlotEngine",
    "EngineConfig",
    "HostPagePool",
    "ObsConfig",
    "PageAllocator",
    "PagedKVCache",
    "PrefixClaim",
    "PrefixIndex",
    "Request",
    "RequestState",
    "ServeEngine",
    "Scheduler",
    "SchedulerConfig",
    "SwapHandle",
]
