"""Distributed SQUASH search over a TPU mesh (DESIGN.md §5).

The serverless topology maps onto the mesh:

* ``model`` axis = QueryProcessors: each shard holds a fixed-size stack of
  partitions (packed low-bit codes, primary codes, full-precision rows).
* ``data`` axis (optionally ``("pod", "data")``) = QueryAllocators: the query
  batch is sharded.
* The paper's single-parallel-pass guarantee becomes a single collective
  round: each shard computes local top-k for (its queries × its partitions),
  then one ``all_gather`` over ``model`` + merge produces global results — the
  MPI-style reduce of §2.4.5 on the ICI collective tree.

Stages 3–5 inside the shard body are the **same batched data plane** the
single-host jax backend uses (``repro.core.dataplane.batched_stage345``) —
each shard simply runs it over its local partition stack, so single-host and
distributed search cannot drift apart. The dynamic stages (predicate parsing,
Algorithm 1) run on host and enter as dense masks plus per-(query, partition)
keep/take counts, mirroring how QAs ship bitmaps to QPs in request payloads.

The served path reaches this plane through ``SquashIndex.search``: with
``SquashConfig(shards=n)`` (or a ``mesh``) the index places its stack once,
sharded, and keeps one :func:`make_search_fn` per static shape, so a request
neither re-stacks nor retraces. :func:`distributed_search` is that path.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import dataplane
from repro.core.dataplane import StackedIndex, stack_index
from repro.core.pipeline import SquashIndex
from repro.obs.metrics import REGISTRY as _METRICS

__all__ = ["StackedIndex", "stack_index", "distributed_search",
           "make_search_fn"]


def make_search_fn(
    mesh: Mesh,
    *,
    k: int,
    keep_s: int,
    take_s: int,
    refine: bool = True,
    data_axes=("data",),
    model_axis: str = "model",
    trace_counter: Optional[list] = None,
):
    """Build the jitted shard_map search function for ``mesh``.

    Inputs (global shapes):
      queries     (Q, d)        — sharded over data axes
      cand_mask   (Q, P, n_max) — filter ∧ residency ∧ visit (from Alg. 1)
      keep, take  (Q, P) int32  — per-pair dynamic stage counts
      stacked     StackedIndex  — partition axis sharded over ``model``
    Output: ids (Q, k) int32, dists (Q, k) float — sharded like queries.

    ``keep_s``/``take_s`` are the static top_k sizes (see
    ``dataplane.static_counts``). ``trace_counter`` (a one-element list) is
    incremented on each trace, as ``dataplane.make_plane``'s is.
    """
    dq = data_axes if len(data_axes) > 1 else data_axes[0]
    query_spec = P(dq)                       # (Q, d): Q over data axes
    mask_spec = P(dq, model_axis)            # (Q, P, n_max) / (Q, P)

    # jax's trace cache is keyed on the *wrapper's identity*, so the old
    # `return jax.jit(fn)(...)` built a fresh wrapper per search and
    # recompiled the shard_map kernel on every call. Cache one jitted
    # wrapper per stacked-index treedef instead (the treedef is the only
    # call-to-call structural variation; shape changes within a treedef hit
    # jax's own signature cache inside the retained wrapper).
    jit_cache = {}

    def _build(treedef):
        def _shard_body(queries, cand_mask, keep, take, *stacked_leaves):
            # Runs at trace time only: counts traces, not calls.
            if trace_counter is not None:
                trace_counter[0] += 1
            stacked = jax.tree_util.tree_unflatten(treedef, stacked_leaves)
            _METRICS.gauge("dataplane.adc.lanes").set(
                stacked.lane_dim.shape[-1])
            # Local batched Stage 3–5 over this shard's partition stack.
            ids, dists = dataplane.batched_stage345(
                queries, stacked, cand_mask, keep, take,
                k=k, keep_s=keep_s, take_s=take_s, refine=refine,
            )                                                   # (Qs, k)
            # Single-pass MPI-style reduce over the model axis (§2.4.5).
            with jax.named_scope("squash.merge"):
                all_ids = jax.lax.all_gather(ids, model_axis, axis=1,
                                             tiled=True)
                all_d = jax.lax.all_gather(dists, model_axis, axis=1,
                                           tiled=True)
                neg, sel = jax.lax.top_k(-all_d, k)
                return jnp.take_along_axis(all_ids, sel, axis=1), -neg

        in_specs = (query_spec, mask_spec, mask_spec, mask_spec,
                    *(P(model_axis) for _ in range(treedef.num_leaves)))
        out_specs = (query_spec, query_spec)
        # Replication checking rejects the data-dependent masks.
        fn = jax.shard_map(
            _shard_body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(fn)

    def search(queries, cand_mask, keep, take, stacked: StackedIndex):
        leaves, treedef = jax.tree_util.tree_flatten(stacked)
        fn = jit_cache.get(treedef)
        if fn is None:
            fn = jit_cache[treedef] = _build(treedef)
        return fn(queries, cand_mask, keep, take, *leaves)

    return search


def distributed_search(
    index: SquashIndex,
    queries: np.ndarray,
    predicates,
    k: int,
    mesh: Optional[Mesh] = None,
    data_axes=("data",),
    model_axis: str = "model",
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-orchestrated distributed hybrid search (QA plane + QP plane).

    ``index.search(backend="jax")`` on ``mesh``: the same host planner
    (Stage 1, Algorithm 1, stage counts), then the shard_map plane over the
    index's stack, placed once per mesh, and its plane, traced once per
    shape. The mesh's axes are ``data_axes`` (queries, flattened into one)
    then ``model_axis`` (partitions). Without a mesh the index serves as it
    is placed (``config.shards``). Ids match ``index.search`` on either
    backend.
    """
    if mesh is not None:
        names = tuple(data_axes) + (model_axis,)
        if tuple(mesh.axis_names) != names:
            raise ValueError(f"mesh axes {mesh.axis_names}, want {names}")
        rows = int(np.prod([mesh.shape[a] for a in data_axes]))
        mesh = Mesh(mesh.devices.reshape(rows, mesh.shape[model_axis]),
                    ("data", "model"))
    ids, dists, _ = index.search(queries, predicates, k=k, backend="jax",
                                 mesh=mesh)
    return ids, dists
