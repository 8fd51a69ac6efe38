"""Vector-search serving facade: QA-style request routing over SquashIndex.

Callers talk to the index through this service rather than calling
``SquashIndex.search`` directly, so the data-plane/deployment becomes a
routing decision:

* ``backend="numpy"``      — per-query reference loop (debug / tiny batches).
* ``backend="jax"``        — batched jitted plane (the production hot path).
* ``backend="serverless"`` — the full event-driven Coordinator → QA → QP
  runtime (``repro.serverless``): same ids as the jax plane, plus per-node
  latency / payload / DRE / cost traces (kept on ``last_trace``). With
  ``ServiceConfig(cache_enabled=True)`` the runtime's §5.6 result cache
  serves repeated queries at the Coordinator; ``swap_index`` invalidates
  it when the index is rebuilt.
* ``backend="auto"``       — route by batch size: single-query lookups take
  the loop (no trace/dispatch overhead), real batches the batched plane.

The service also plays the QueryAllocator's accounting role: it accumulates
:class:`~repro.core.pipeline.SearchStats` across requests and counts the
queries each backend served. With the obs registry enabled each call is one
``squash.request`` span on the JAX profiler's clock (``repro.obs.spans``),
parent of the search's Stage 1, Algorithm 1 and plane spans, and counts one
``serve.requests``.

With ``ServiceConfig(recall_target=…)`` the service additionally runs the
recall-targeted Hamming autotune (``core/autotune.py``) against the bound
index at bind time and again on every ``swap_index`` — per-partition keep
budgets replace the static ``hamming_perc`` in all backends, ids staying
bitwise-identical across them.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core import attributes as attr_mod
from repro.core.pipeline import SearchStats, SquashIndex
from repro.obs.metrics import REGISTRY as _METRICS
from repro.obs.spans import span

__all__ = ["ServiceConfig", "VectorSearchService"]

_AUTO_BATCH_THRESHOLD = 4  # ≥ this many queries → batched jax plane

# Backends a request may name explicitly ("auto" resolves before dispatch).
_CALL_BACKENDS = ("numpy", "jax", "serverless")


@dataclasses.dataclass
class ServiceConfig:
    backend: str = "auto"              # numpy | jax | serverless | auto
    default_k: int = 10
    serverless: Optional[object] = None  # repro.serverless.RuntimeConfig
    # §5.6 result-cache knobs for the serverless backend. They overlay onto
    # the RuntimeConfig (an explicit ``serverless`` config that already
    # enables the cache wins), so callers can turn caching on per service
    # without hand-building a runtime config.
    cache_enabled: bool = False
    result_cache_bytes: int = 64 * 1024 * 1024
    # Execution substrate of the serverless backend (serverless.transport):
    # None keeps the RuntimeConfig's choice; "local" pins the in-process
    # virtual-time scheduler, "process" the real multi-process worker pool,
    # "socket" the TCP worker fleet (ids bitwise-identical in every case).
    transport: Optional[str] = None
    # Socket-transport host fleet ("host:port", ...). None keeps the
    # RuntimeConfig's choice (auto-spawned loopback hosts by default).
    hosts: Optional[Tuple[str, ...]] = None
    # Recall-targeted Hamming autotune (core/autotune.py). When set, the
    # service calibrates a per-partition keep-budget profile against the
    # bound index (and re-calibrates on ``swap_index``); every backend —
    # numpy, jax, serverless — then consumes the same profile, so ids stay
    # bitwise-identical across them at strictly fewer ADC evaluations.
    recall_target: Optional[float] = None
    calibration_sample: int = 64
    calibration_seed: int = 0


class VectorSearchService:
    """One QueryAllocator front-end bound to a resident SquashIndex."""

    def __init__(self, index: SquashIndex, config: Optional[ServiceConfig] = None):
        base = getattr(index, "base", None)     # accept a LiveIndex wrapper
        self.index = base if isinstance(base, SquashIndex) else index
        self.config = config or ServiceConfig()
        if self.config.backend not in _CALL_BACKENDS + ("auto",):
            raise ValueError(f"unknown backend {self.config.backend!r}")
        self.stats = SearchStats()
        self.requests = 0
        self.queries_served: Dict[str, int] = {b: 0 for b in _CALL_BACKENDS}
        self._runtime = None
        self.last_trace = None         # RunTrace of the last serverless call
        self._calibrate()

    def _calibrate(self) -> None:
        """(Re)derive the autotune profile for the currently-bound index."""
        if self.config.recall_target is None:
            return
        self.index.autotune(
            recall_target=self.config.recall_target,
            k=self.config.default_k,
            sample=self.config.calibration_sample,
            seed=self.config.calibration_seed)

    @property
    def profile(self):
        """The bound index's active CalibrationProfile (None if untuned)."""
        return self.index.profile

    def resolve_backend(self, num_queries: int) -> str:
        if self.config.backend != "auto":
            return self.config.backend
        return "jax" if num_queries >= _AUTO_BATCH_THRESHOLD else "numpy"

    def runtime(self):
        """The lazily-built serverless runtime bound to this index."""
        if self._runtime is None:
            from repro.serverless import RuntimeConfig, ServerlessRuntime

            cfg = self.config.serverless or RuntimeConfig()
            if self.config.cache_enabled and not cfg.cache_enabled:
                cfg = dataclasses.replace(
                    cfg, cache_enabled=True,
                    result_cache_bytes=self.config.result_cache_bytes)
            if (self.config.transport is not None
                    and cfg.transport != self.config.transport):
                cfg = dataclasses.replace(cfg,
                                          transport=self.config.transport)
            if (self.config.hosts is not None
                    and cfg.hosts != self.config.hosts):
                cfg = dataclasses.replace(cfg, hosts=self.config.hosts)
            self._runtime = ServerlessRuntime(self.index, cfg)
        return self._runtime

    @property
    def result_cache(self):
        """The serverless backend's §5.6 ResultCache (None if unbuilt/off)."""
        return self._runtime.result_cache if self._runtime else None

    def swap_index(self, index: SquashIndex) -> None:
        """Rebind the service to a rebuilt (or live-wrapped) index.

        The serverless runtime survives the swap via
        ``ServerlessRuntime.rebind``: its container pools keep their warm
        containers while the version bump stales every fetch/derived
        singleton key and the epoch bump drains in-flight leases — cached
        results and retained state from the old index can never be served,
        without the old cost of discarding the whole runtime (and its real
        worker fleet's warmth model) on every swap. Process/socket workers
        holding old shards are still shut down and respawn with fresh
        bundles on the next call.
        """
        base = getattr(index, "base", None)     # accept a LiveIndex wrapper
        self.index = base if isinstance(base, SquashIndex) else index
        if self._runtime is not None:
            self._runtime.rebind(self.index)
        self._calibrate()

    def close(self) -> None:
        """Release backend resources (process-transport worker pools)."""
        if self._runtime is not None:
            self._runtime.close()
            self._runtime = None

    def warmup(self, num_queries: int, k: Optional[int] = None) -> None:
        """Pre-trace the jax plane for a batch shape (DRE-style warm start)."""
        k = k or self.config.default_k
        q = np.zeros((num_queries, self.index.dim))
        self.index.search(q, [], k=k, backend="jax")

    def query(
        self,
        queries: np.ndarray,
        predicates: Sequence[attr_mod.Predicate] = (),
        k: Optional[int] = None,
        backend: Optional[str] = None,
    ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
        """Serve one request batch; returns (ids, dists, per-request stats).

        ``backend`` must be one of ``_CALL_BACKENDS`` or ``"auto"``/None; an
        unknown string fails here, before any index state is touched.
        """
        if backend not in (None, "auto") + _CALL_BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; expected one of "
                f"{('auto',) + _CALL_BACKENDS}")
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float64))
        k = k or self.config.default_k
        chosen = (self.resolve_backend(queries.shape[0])
                  if backend in (None, "auto") else backend)
        _METRICS.counter("serve.requests").inc()
        with span("squash.request", request=self.requests, backend=chosen,
                  queries=queries.shape[0]):
            if chosen == "serverless":
                result = self.runtime().search(queries, list(predicates),
                                               k=k)
                ids, dists, stats = result.ids, result.dists, result.stats
                self.last_trace = result.trace
            else:
                ids, dists, stats = self.index.search(
                    queries, list(predicates), k=k, backend=chosen
                )
        self.requests += 1
        self.stats.merge(stats)
        self.queries_served[chosen] += queries.shape[0]
        return ids, dists, stats
