"""Request routing across the SMC cube mesh (paper §VI-C, serving form).

The paper's scalable paradigm is a *network* of SMCs each independently
streaming its own requests with the host only coordinating.  The router is
that host: one paged ``ServeEngine`` per cube slot along ``CUBE_AXIS``
(coefficients replicated per cube, KV pages local to the cube), requests
spread by

* ``hash``            — uid-stable assignment, no coordination state at all;
* ``least_loaded``    — queue-depth telemetry picks the emptiest cube (the
  dataflow-aware choice under mixed-length traffic);
* ``prefix_affinity`` — the cube whose prefix index already holds the
  longest resident prefix of the prompt wins (ties broken least-loaded;
  falls back to least-loaded on a universal miss).  Keeps shared-prompt
  traffic landing where its KV pages already live — only useful with
  ``CacheConfig.prefix_sharing`` on.

``devices`` spreads the cubes over real devices: one engine per device,
each with its own copy of the params, its own page pools and its own jitted
steps there — four one-chip replicas in one process on a four-chip host.
Without it every engine shares JAX's default device (the 1-device CPU test
host, where each cube's sharding degrades to replication via
``dist.sharding.cube_rules``); the routing logic and telemetry are the same.
"""
from __future__ import annotations

from repro.core.smc import CUBE_AXIS, make_cube_mesh
from repro.obs.metrics import MetricsRegistry

from .engine import EngineConfig, Request, ServeEngine


class CubeRouter:
    """Hash / least-loaded routing of requests over per-cube engines."""

    def __init__(self, model, params, ecfg: EngineConfig, n_cubes: int = 2,
                 policy: str = "least_loaded", rules=None, mesh=None,
                 devices=None):
        if policy not in ("hash", "least_loaded", "prefix_affinity"):
            raise ValueError(f"unknown router policy: {policy!r}")
        if devices is not None:
            devices = list(devices)
            if len(devices) != n_cubes:
                raise ValueError(
                    f"{n_cubes} cubes need {n_cubes} devices, got "
                    f"{len(devices)}")
            if len(set(devices)) != n_cubes:
                raise ValueError("each cube needs a device of its own")
        if rules is None:
            from repro.dist.sharding import cube_rules

            mesh = mesh if mesh is not None else make_cube_mesh(n_cubes)
            rules = cube_rules(mesh)
        self.mesh = mesh
        self.policy = policy
        self.axis = CUBE_AXIS
        self.engines = [
            ServeEngine(model, params, ecfg, rules,
                        device=None if devices is None else devices[i])
            for i in range(n_cubes)
        ]
        # routing counters get their own registry (the router has no lock
        # of its own to share); per-cube keys "routed.<axis><i>"
        self.metrics = MetricsRegistry()
        self._c_routed = [
            self.metrics.counter(f"routed.{self.axis}{i}")
            for i in range(n_cubes)
        ]

    @property
    def n_cubes(self) -> int:
        return len(self.engines)

    @property
    def routed(self) -> list[int]:
        """Per-cube dispatch counts — one coherent cut of the counters."""
        with self.metrics.lock:
            return [c.value for c in self._c_routed]

    # -- routing --------------------------------------------------------------

    def _pick(self, req: Request) -> int:
        if self.policy == "hash":
            return req.uid % self.n_cubes
        loads = [e.load for e in self.engines]
        if self.policy == "prefix_affinity":
            match = [e.prefix_match_tokens(req.prompt)
                     for e in self.engines]
            best = max(match)
            if best > 0:
                # longest resident prefix wins; ties go least-loaded
                return int(min(
                    (i for i in range(self.n_cubes) if match[i] == best),
                    key=loads.__getitem__,
                ))
        return int(min(range(self.n_cubes), key=loads.__getitem__))

    def submit(self, req: Request) -> int:
        cube = self._pick(req)
        eng = self.engines[cube]
        # the dispatch instant lands on the TARGET engine's trace, so a
        # request's timeline starts with where the router sent it
        eng.tracer.instant(eng.tracer.EV_DISPATCH, req.uid, cube)
        eng.submit(req)
        self.metrics.inc(f"routed.{self.axis}{cube}")
        return cube

    # -- stepping -------------------------------------------------------------

    def step(self, key=None) -> bool:
        return any([e.step(key) for e in self.engines])

    def run(self, key=None) -> list[Request]:
        """Step every cube in lockstep (the cubes run concurrently in the
        paper's network; here one host interleaves them) until drained."""
        marks = [len(e.completed) for e in self.engines]
        while any(e.load for e in self.engines):
            self.step(key)
        done: list[Request] = []
        for e, m in zip(self.engines, marks):
            done.extend(e.completed[m:])
        return sorted(done, key=lambda r: r.uid)

    # -- telemetry (per-cube queue depth — the least-loaded signal) -----------

    def telemetry(self) -> dict:
        """Deep point-in-time snapshot: one lock acquisition per engine
        (each ``e.telemetry()`` is itself a single-lock deep cut) plus one
        for the routing counters — mutating the result never perturbs live
        stats."""
        routed = self.routed
        per_cube: dict = {
            f"{self.axis}{i}": dict(e.telemetry(), routed=routed[i])
            for i, e in enumerate(self.engines)
        }
        per_cube["total_routed"] = sum(routed)
        return per_cube

    def save_trace(self, path: str) -> dict:
        """Export every cube's ring buffer into ONE Perfetto JSON — each
        engine becomes a named process track."""
        from repro.obs.export import write_chrome_trace

        return write_chrome_trace(
            path,
            {f"{self.axis}{i}": e.tracer
             for i, e in enumerate(self.engines)},
        )
