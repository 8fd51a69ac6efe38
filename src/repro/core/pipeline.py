"""SQUASH multi-stage search pipeline (paper §2.4, Fig. 4 + Fig. 5 data plane).

Build: balanced partitions → per-partition KLT → variance-greedy bit
allocation → Lloyd-Max scalar quantizers → segment-packed primary OSQ index +
1-bit low-bit OSQ index → quantized attribute index.

Search: predicate parse → R lookup → filter mask F → Algorithm 1 partition
selection → per-partition: low-bit Hamming prune → ADC lookup-table LB
distances → optional R·k full-precision post-refinement → single-pass
MPI-style top-k merge.

Two query data planes execute Stages 3–5, selected by
``SquashConfig.backend`` (or per-call via ``search(..., backend=...)``):

* ``"numpy"`` — the per-query reference loop in this module: per visited
  partition, NumPy stage math with deterministic (score, row) tie-breaking.
* ``"jax"`` — the batched plane in ``repro.core.dataplane``: all queries ×
  all partitions stacked to fixed shapes, jit-compiled end to end (one trace
  per (Q, k, index shape)), kernels dispatched via ``repro.kernels.ops``
  (Pallas on TPU, XLA twins on CPU). Returns bitwise-identical ids to the
  NumPy plane; the dynamic per-(query, partition) keep/take counts are
  computed on host and applied as masks inside the traced function.

``repro.core.distributed`` shards the same batched plane over a TPU mesh and
``repro.serve`` drives it under the simulated serverless runtime.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import adc, attributes as attr_mod, lowbit, osq, partitions, segments
from repro.obs.spans import span

__all__ = ["SquashConfig", "PartitionIndex", "SquashIndex", "SearchStats",
           "QueryPlan", "plan_queries", "build_partition"]

BACKENDS = ("numpy", "jax")


@dataclasses.dataclass
class SquashConfig:
    """Index + search hyper-parameters (paper §5.1/§5.3 defaults)."""

    num_partitions: int = 10
    bits_per_dim: float = 4.0          # bit budget b = bits_per_dim * d
    segment_bits: int = 8              # S
    use_klt: bool = True               # unitary decorrelating transform
    hamming_perc: float = 10.0         # H_perc — % of candidates kept (static;
                                       # superseded per-partition by an
                                       # installed autotune CalibrationProfile)
    refine_ratio: float = 2.0          # R — full-precision re-rank multiplier
    beta: float = 0.001                # Eq. 1 β
    threshold_override: Optional[float] = None
    kmeans_iters: int = 10
    lloyd_iters: int = 15
    max_bits_per_dim: int = 12
    enable_refine: bool = True
    min_hamming_keep: int = 64         # floor so tiny candidate sets survive
    backend: str = "numpy"             # Stage 3–5 data plane: numpy | jax
    shards: int = 1                    # chips the jax plane's partition
                                       # stack spans (DESIGN.md §5)


@dataclasses.dataclass
class PartitionIndex:
    """Per-partition OSQ index — what one QueryProcessor holds (paper §3.1)."""

    vector_ids: np.ndarray           # (n_p,) global ids, local order
    klt: Optional[np.ndarray]        # (d, d) unitary transform (or None)
    mean: np.ndarray                 # (d,) transform centering
    quant: osq.OSQQuantizer
    layout: segments.SegmentLayout
    packed: np.ndarray               # (n_p, G) packed primary codes
    codes: np.ndarray                # (n_p, d) unpacked codes (in-memory Q-index)
    low: lowbit.LowBitIndex          # 1-bit secondary index
    vectors: np.ndarray              # (n_p, d) full precision (the 'EFS' copy)

    @property
    def size(self) -> int:
        return int(self.vector_ids.shape[0])

    def transform(self, q: np.ndarray) -> np.ndarray:
        q = q - self.mean
        return q @ self.klt if self.klt is not None else q

    def index_bytes(self) -> int:
        return int(self.packed.nbytes + self.low.packed.nbytes)


@dataclasses.dataclass
class SearchStats:
    """Per-stage pruning accounting (drives the cost model + EXPERIMENTS.md)."""

    queries: int = 0
    filter_pass: int = 0
    partitions_visited: int = 0
    hamming_in: int = 0
    hamming_kept: int = 0
    adc_evals: int = 0
    refined: int = 0

    def merge(self, other: "SearchStats") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))


def build_partition(config: SquashConfig, ids: np.ndarray,
                    x: np.ndarray) -> PartitionIndex:
    """One partition's OSQ index over its rows ``x`` (global ids ``ids``):
    KLT → variance-greedy bits → Lloyd-Max quantizers → packed codes, plus
    the 1-bit index. ``SquashIndex.build`` and live compaction share it."""
    d = x.shape[1]
    mean = x.mean(axis=0)
    xc = x - mean
    if config.use_klt and x.shape[0] > d:
        cov = (xc.T @ xc) / max(x.shape[0] - 1, 1)
        _, eigvec = np.linalg.eigh(cov)
        # Descending-variance order, copied: a matmul on the reversed view
        # runs off BLAS, ~150x slower at d = 960.
        klt = np.ascontiguousarray(eigvec[:, ::-1])
        xt = xc @ klt
    else:
        klt = None
        xt = xc
    budget = int(round(config.bits_per_dim * d))
    bits = osq.allocate_bits(xt.var(axis=0), budget,
                             max_bits=config.max_bits_per_dim)
    quant = osq.design_quantizers(xt, bits, iters=config.lloyd_iters)
    codes = osq.encode(quant, xt)
    layout = segments.build_layout(bits, seg_bits=config.segment_bits)
    # The low-bit index binarizes the *raw* (centered) space: KLT compacts
    # energy into few dims, and post-KLT standardization would amplify the
    # near-noise trailing dims into uninformative random bits.
    return PartitionIndex(
        vector_ids=ids, klt=klt, mean=mean, quant=quant, layout=layout,
        packed=segments.pack_codes(layout, codes),
        codes=codes.astype(np.int32), low=lowbit.build_lowbit_index(xc),
        vectors=x)


class SquashIndex:
    """End-to-end SQUASH index over a vector dataset + attribute table."""

    def __init__(
        self,
        config: SquashConfig,
        partitioning: partitions.Partitioning,
        parts: List[PartitionIndex],
        attr_index: attr_mod.AttributeIndex,
        dim: int,
    ):
        self.config = config
        self.partitioning = partitioning
        self.parts = parts
        self.attr_index = attr_index
        self.dim = dim
        # Liveness bitmap over global vector ids (core/live.py). None for a
        # frozen index — zero overhead on the static path. When set, dead
        # (tombstoned) rows are excluded from the Stage 1 filter mask and
        # defensively masked again in Stage 3 on every backend.
        self.live_mask: Optional[np.ndarray] = None
        # Back-reference to the owning LiveIndex (set by core/live.py) so
        # the serverless runtime can pull mutation events lazily.
        self.live_owner = None
        # Optional recall-targeted calibration (core/autotune.py): when set,
        # per-partition keep fractions + a calibrated floor replace the
        # static hamming_perc / min_hamming_keep in every data plane.
        self.profile = None
        # jax-backend caches: stacked device payload per dtype (and mesh),
        # jitted plane per (k, keep_s, take_s, refine[, mesh]). jit itself
        # caches per (Q, d) shape, so each (Q, k, index shape) traces
        # exactly once (see ``_trace_counter``, asserted by the
        # backend-parity tests).
        self._stacked_cache: Dict = {}
        self._plane_cache: Dict = {}
        self._trace_counter = [0]
        self._mesh = None          # plane_mesh(), made on first use

    def set_profile(self, profile) -> None:
        """Install (or clear) a calibration profile for this index.

        ``profile`` is a :class:`repro.core.autotune.CalibrationProfile`
        whose partition count must match; ``None`` restores the static
        config knobs. The jitted-plane cache is dropped because the static
        keep/take shapes derive from the active profile.
        """
        if profile is not None and profile.num_partitions != len(self.parts):
            raise ValueError(
                f"profile covers {profile.num_partitions} partitions, index "
                f"has {len(self.parts)}")
        self.profile = profile
        self._plane_cache.clear()

    def autotune(self, queries=None, *, recall_target: float = 0.95,
                 k: int = 10, sample: int = 64, seed: int = 0, **kw):
        """Calibrate + install a recall-targeted profile; returns it."""
        from repro.core import autotune as at

        profile = at.calibrate(self, queries, recall_target=recall_target,
                               k=k, sample=sample, seed=seed, **kw)
        self.set_profile(profile)
        return profile

    # ------------------------------------------------------------------ build

    @classmethod
    def build(
        cls,
        vectors: np.ndarray,
        attrs: np.ndarray,
        config: Optional[SquashConfig] = None,
        attr_bits: Optional[Sequence[int]] = None,
        seed: int = 0,
    ) -> "SquashIndex":
        config = config or SquashConfig()
        vectors = np.asarray(vectors, dtype=np.float64)
        n, d = vectors.shape
        cent, assign = partitions.balanced_kmeans(
            vectors, config.num_partitions, iters=config.kmeans_iters, seed=seed
        )
        t = (
            config.threshold_override
            if config.threshold_override is not None
            else partitions.compute_threshold(vectors, cent, assign, beta=config.beta)
        )
        part_obj = partitions.Partitioning(centroids=cent, assign=assign, threshold=t)
        # Partitions build independently, and NumPy's heavy calls (BLAS,
        # sorts, searches) let go of the GIL, so they build side by side.
        def one(pid: int) -> PartitionIndex:
            ids = np.where(assign == pid)[0]
            return build_partition(config, ids, vectors[ids])

        workers = min(config.num_partitions, len(os.sched_getaffinity(0)))
        with concurrent.futures.ThreadPoolExecutor(max(workers, 1)) as pool:
            parts = list(pool.map(one, range(config.num_partitions)))
        attr_index = attr_mod.build_attribute_index(attrs, bits=attr_bits)
        return cls(config, part_obj, parts, attr_index, dim=d)

    # ----------------------------------------------------------------- search

    def search(
        self,
        queries: np.ndarray,
        predicates: Sequence[attr_mod.Predicate],
        k: int = 10,
        collect_stats: bool = False,
        backend: Optional[str] = None,
        mesh=None,
    ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
        """Batched hybrid top-k. Returns (ids (Q,k), dists (Q,k), stats).

        ``backend`` overrides ``config.backend`` for this call: ``"numpy"``
        runs the per-query reference loop, ``"jax"`` the batched jitted data
        plane (identical ids, same stats counters). ``mesh`` (jax backend
        only) runs the plane partition-sharded over that mesh's ``model``
        axis, queries over its ``data`` axis, in place of the index's own
        placement (:meth:`plane_mesh`).
        """
        backend = backend or self.config.backend
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected "
                             f"{BACKENDS}")
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float64))
        plan = plan_queries(self, queries, predicates, k)
        stats = SearchStats(queries=queries.shape[0],
                            filter_pass=plan.filter_pass,
                            partitions_visited=int(plan.visit.sum()))
        if backend == "jax":
            return self._search_jax(queries, plan, k, stats, mesh)
        return self._search_numpy(queries, plan.cands, k, stats)

    def _search_numpy(
        self,
        queries: np.ndarray,
        cands,
        k: int,
        stats: SearchStats,
    ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
        """Reference Stage 3–5 plane: per-query loop over visited partitions.

        Candidate streams are consumed in ascending-partition order and every
        sort is stable, so ties resolve as (score, partition, row) — exactly
        the order ``lax.top_k`` produces in the jax plane.
        """
        qn = queries.shape[0]
        all_ids = np.full((qn, k), -1, dtype=np.int64)
        all_dists = np.full((qn, k), np.inf, dtype=np.float64)
        for qi in range(qn):
            heap: List[Tuple[float, int]] = []
            for pid in sorted(cands[qi]):
                ids, dists = self._search_partition(
                    self.parts[pid], pid, queries[qi], cands[qi][pid], k,
                    stats
                )
                heap.extend(zip(dists.tolist(), ids.tolist()))
            # Single-pass MPI-style reduce: merge per-partition local top-k.
            # Stable sort on distance alone keeps (partition, rank) tie order.
            heap.sort(key=lambda t: t[0])
            top = heap[:k]
            for r_i, (dist, vid) in enumerate(top):
                all_ids[qi, r_i] = vid
                all_dists[qi, r_i] = dist
        return all_ids, all_dists, stats

    def _search_jax(
        self,
        queries: np.ndarray,
        plan: "QueryPlan",
        k: int,
        stats: SearchStats,
        mesh=None,
    ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
        """Batched Stage 3–5 plane (repro.core.dataplane), jitted end to end.

        Host side prepares dense masks + per-(query, partition) keep/take
        counts; one jitted call executes Hamming prune, ADC lower bounds,
        refinement and the cross-partition merge for the whole batch. On a
        mesh (``mesh``, or ``config.shards`` > 1) the call is the shard_map
        plane of ``repro.core.distributed``: each chip runs the same stages
        over its slice of the stack, then one all-gather merges them.
        """
        from repro.core import dataplane

        cfg = self.config
        qn = queries.shape[0]
        if mesh is None and cfg.shards > 1:
            mesh = self.plane_mesh()
        stacked = self.device_stack() if mesh is None else \
            self._sharded_stack(mesh)
        dtype = stacked.vectors.dtype
        p, n_max = stacked.num_partitions, stacked.n_max

        with span("squash.plane.setup"):
            cand_mask = dataplane.build_cand_arrays(plan.cands, qn, p, n_max)
            # Zero counts for the sharded stack's pad partitions, if any.
            n_cand = np.pad(plan.n_cand,
                            ((0, 0), (0, p - plan.n_cand.shape[1])))
            keep, take = dataplane.stage_counts(n_cand, cfg, k, self.profile)
            keep_s, take_s = dataplane.static_counts(n_max, cfg, k,
                                                     self.profile)

            # Bucket Q to the next power of two so a service seeing naturally
            # varying batch sizes pays O(log Q) traces, not one per size.
            # Padded queries are dead (keep=0, empty mask) and sliced off
            # below. A mesh's data axis takes a whole share of the bucket.
            bucket = 1 << (qn - 1).bit_length() if qn > 1 else 1
            if mesh is not None:
                rows = mesh.shape["data"]
                bucket = -(-bucket // rows) * rows
            if bucket != qn:
                pad = bucket - qn
                queries = np.pad(queries, ((0, pad), (0, 0)))
                cand_mask = np.pad(cand_mask, ((0, pad), (0, 0), (0, 0)))
                keep = np.pad(keep, ((0, pad), (0, 0)))
                take = np.pad(take, ((0, pad), (0, 0)))
            key = (k, keep_s, take_s, cfg.enable_refine)
            if mesh is not None:
                key += (mesh,)
            plane = self._plane_cache.get(key)
            if plane is None:
                if mesh is None:
                    plane = dataplane.make_plane(
                        k=k, keep_s=keep_s, take_s=take_s,
                        refine=cfg.enable_refine,
                        trace_counter=self._trace_counter,
                    )
                else:
                    from repro.core import distributed

                    plane = distributed.make_search_fn(
                        mesh, k=k, keep_s=keep_s, take_s=take_s,
                        refine=cfg.enable_refine,
                        trace_counter=self._trace_counter)
                self._plane_cache[key] = plane
        with span("squash.plane.upload"):
            if mesh is None:
                args = (dataplane.upload(queries, dtype), stacked,
                        dataplane.upload(cand_mask), dataplane.upload(keep),
                        dataplane.upload(take))
            else:
                from jax.sharding import NamedSharding, PartitionSpec as P

                by_query = NamedSharding(mesh, P("data"))
                by_pair = NamedSharding(mesh, P("data", "model"))
                args = (dataplane.upload(queries, dtype, by_query),
                        dataplane.upload(cand_mask, sharding=by_pair),
                        dataplane.upload(keep, sharding=by_pair),
                        dataplane.upload(take, sharding=by_pair), stacked)
        with span("squash.plane.dispatch"):
            ids, dists = plane(*args)
        stats.hamming_in += int(n_cand.sum())
        stats.hamming_kept += int(keep.sum())
        stats.adc_evals += int(keep.sum())
        if cfg.enable_refine:
            stats.refined += int(take.sum())
        with span("squash.plane.fetch"):
            return (np.asarray(ids[:qn], dtype=np.int64),
                    np.asarray(dists[:qn], dtype=np.float64), stats)

    def device_stack(self):
        """The jax plane's resident payload, stacked and uploaded once.

        float64 when x64 is enabled (bitwise parity with the NumPy plane),
        float32 otherwise (the deployment configuration). With
        ``config.shards`` > 1 the stack is padded to a multiple of the
        shards and each chip of :meth:`plane_mesh` holds its slice.
        """
        from repro.core import dataplane

        if self.config.shards > 1:
            return self._sharded_stack(self.plane_mesh())
        dtype = dataplane.plane_dtype()
        stacked = self._stacked_cache.get(dtype)
        if stacked is None:
            stacked = dataplane.stack_index(self, dtype=dtype)
            self._stacked_cache[dtype] = stacked
        return stacked

    def plane_mesh(self):
        """The (data = 1, model = ``config.shards``) mesh over the first
        ``shards`` devices that the sharded plane runs on."""
        if self._mesh is None:
            import jax
            from jax.sharding import Mesh

            shards = self.config.shards
            devices = jax.devices()
            if len(devices) < shards:
                raise ValueError(f"config.shards = {shards}, but JAX has "
                                 f"{len(devices)} device(s)")
            self._mesh = Mesh(np.array(devices[:shards]).reshape(1, shards),
                              ("data", "model"))
        return self._mesh

    def _sharded_stack(self, mesh):
        """The stack padded to a multiple of ``mesh``'s ``model`` axis, each
        chip's slice placed straight from the host, once per mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core import dataplane
        from repro.obs.metrics import REGISTRY

        dtype = dataplane.plane_dtype()
        key = (dtype, mesh)
        stacked = self._stacked_cache.get(key)
        if stacked is None:
            shards = mesh.shape["model"]
            with span("squash.plane.place", shards=shards):
                stacked = dataplane.stack_index(
                    self, pad_to_multiple=shards, dtype=dtype,
                    sharding=NamedSharding(mesh, P("model")))
            self._stacked_cache[key] = stacked
            REGISTRY.gauge("dataplane.shards").set(shards)
            REGISTRY.gauge("dataplane.shard_partitions").set(
                stacked.num_partitions // shards)
        return stacked

    def _search_partition(
        self,
        part: PartitionIndex,
        pid: int,
        query: np.ndarray,
        local_rows: np.ndarray,
        k: int,
        stats: SearchStats,
    ) -> Tuple[np.ndarray, np.ndarray]:
        from repro.core import autotune

        cfg = self.config
        # Stage 3 tombstone mask (defense in depth): Stage 1 already fails
        # dead rows, but requests constructed outside `search` (e.g. a raw
        # QP request) must still never return a tombstoned id.
        if self.live_mask is not None:
            alive = self.live_mask[part.vector_ids[local_rows]]
            if not alive.all():
                local_rows = local_rows[alive]
        if local_rows.size == 0:
            return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.float64))
        qt = part.transform(query)

        # Stage 3 — low-bit OSQ Hamming pruning (only rows passing the filter).
        # Binary codes live in the raw centered space (see build()).
        qbits = part.low.encode_queries((query - part.mean)[None, :])[0]
        cand_packed = part.low.packed[local_rows]
        x = np.bitwise_xor(cand_packed, qbits[None, :])
        ham = _popcount_u32(x).sum(axis=1)
        stats.hamming_in += local_rows.size
        # Keep budget: the partition's calibrated fraction under an active
        # profile, the static config knob otherwise, above the global floor
        # (raised to k) — the keep_count formula stage_counts applies in
        # the batched plane.
        frac = (cfg.hamming_perc if self.profile is None
                else float(self.profile.keep_frac[pid]))
        floor = autotune.keep_floor(cfg, self.profile, k)
        keep = autotune.keep_count(local_rows.size, frac, floor)
        # Total-order composite key (ham, row): keeps the O(n) argpartition
        # while resolving ties by ascending row — the order the jax plane's
        # lax.top_k produces, required for backend id parity.
        n_c = local_rows.size
        comp = ham.astype(np.int64) * n_c + np.arange(n_c)
        kept_sel = np.argpartition(comp, keep - 1)[:keep]
        kept_sel = kept_sel[np.argsort(comp[kept_sel])]
        kept_rows = local_rows[kept_sel]
        stats.hamming_kept += keep

        # Stage 4 — ADC lookup-table LB distances on survivors.
        table = adc.build_adc_table(qt, part.quant.boundaries, part.quant.cells)
        codes = part.codes[kept_rows]
        safe = np.where(np.isfinite(table), table, 0.0)
        lb = np.sqrt(safe[codes, np.arange(self.dim)[None, :]].sum(axis=1))
        stats.adc_evals += keep

        take = min(int(np.ceil(cfg.refine_ratio * k)), keep) if cfg.enable_refine \
            else min(k, keep)
        order = np.argsort(lb, kind="stable")[:take]
        cand = kept_rows[order]

        if cfg.enable_refine:
            # Stage 5 — post-refinement on full-precision rows ('EFS' reads).
            full = part.vectors[cand]
            exact = np.sqrt(((full - query[None, :]) ** 2).sum(axis=1))
            stats.refined += cand.size
            fin = np.argsort(exact, kind="stable")[:k]
            return part.vector_ids[cand[fin]], exact[fin]
        return part.vector_ids[cand[:k]], lb[order][:k]

    # ------------------------------------------------------------- accounting

    def index_bytes(self) -> Dict[str, int]:
        primary = sum(p.packed.nbytes for p in self.parts)
        low = sum(p.low.packed.nbytes for p in self.parts)
        attrs = self.attr_index.codes.nbytes
        full = sum(p.vectors.nbytes for p in self.parts)
        return {
            "primary_osq": int(primary),
            "lowbit_osq": int(low),
            "attr_codes": int(attrs),
            "full_precision": int(full),
        }


@dataclasses.dataclass
class QueryPlan:
    """Stage 1 and Algorithm 1 for one batch: what every plane starts from."""

    f_one: np.ndarray                    # (N,) filter F ∧ live rows
    visit: np.ndarray                    # (Q, P) partitions each query visits
    cands: List[Dict[int, np.ndarray]]   # per query: pid → local rows
    n_cand: np.ndarray                   # (Q, P) int32 candidate counts
    filter_pass: int                     # rows passing F, summed over Q
    escalations: int                     # visits past the Eq. 1 cut (§2.5)


def plan_queries(index, queries: np.ndarray,
                 predicates: Sequence[attr_mod.Predicate],
                 k: int) -> QueryPlan:
    """The one host planner: Stage 1 filtering, then Algorithm 1.

    ``index`` is a :class:`SquashIndex`, or any view carrying its
    ``attr_index``, ``live_mask``, ``partitioning`` and ``parts`` (the
    serverless worker's ``_QAIndexView``). ``SquashIndex.search`` (both
    backends) and the serverless ``QueryAllocator`` call it, so the
    tombstone rule and Algorithm 1's call are decided here alone.
    """
    from repro.core import dataplane

    qn = queries.shape[0]
    # Stage 1 — attribute filtering: one mask F the batch shares. Dead
    # (tombstoned) rows fail the filter outright: they can never become
    # Stage 2 candidates on any plane, which is what keeps mutation
    # bitwise-invisible to the downstream stages.
    with span("squash.stage1"):
        r = attr_mod.build_r_lookup(index.attr_index, predicates)
        f_one = np.asarray(attr_mod.filter_mask(
            dataplane.upload(r), dataplane.upload(index.attr_index.codes)))
        if index.live_mask is not None:
            f_one = f_one & index.live_mask
        filter_pass = int(f_one.sum()) * qn

    # Stage 2 — Algorithm 1 partition ranking/selection over the shared
    # (N,) filter; it counts the §2.5 escalations where the cut is decided.
    escalations = [0]
    with span("squash.alg1"):
        visit, cands = partitions.select_partitions(
            queries,
            index.partitioning.centroids,
            f_one,
            [pt.vector_ids for pt in index.parts],
            index.partitioning.threshold,
            k,
            escalations=escalations,
        )
    n_cand = np.zeros(visit.shape, dtype=np.int32)
    for qi, per_part in enumerate(cands):
        for pid, rows in per_part.items():
            n_cand[qi, pid] = rows.size
    return QueryPlan(f_one=f_one, visit=visit, cands=cands, n_cand=n_cand,
                     filter_pass=filter_pass, escalations=escalations[0])


_POP_TABLE = np.array([bin(i).count("1") for i in range(256)], dtype=np.uint8)


def _popcount_u32(x: np.ndarray) -> np.ndarray:
    """Byte-table popcount for uint32 arrays (NumPy reference path)."""
    b = x.view(np.uint8).reshape(*x.shape, 4)
    return _POP_TABLE[b].sum(axis=-1).astype(np.int32)
