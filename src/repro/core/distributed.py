"""Distributed SQUASH search over a TPU mesh (DESIGN.md §6).

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
):
    """Build the jitted shard_map search function for ``mesh``.

    Inputs (global shapes):
      queries     (Q, d)        — sharded over data axes
      cand_mask   (Q, P, n_max) — filter ∧ residency ∧ visit (from Alg. 1)
      keep, take  (Q, P) int32  — per-pair dynamic stage counts
      stacked     StackedIndex  — partition axis sharded over ``model``
    Output: ids (Q, k) int32, dists (Q, k) float — sharded like queries.

    ``keep_s``/``take_s`` are the static top_k sizes (see
    ``dataplane.static_counts``).
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
            stacked = jax.tree_util.tree_unflatten(treedef, stacked_leaves)
            # Local batched Stage 3–5 over this shard's partition stack.
            ids, dists = dataplane.batched_stage345(
                queries, stacked, cand_mask, keep, take,
                k=k, keep_s=keep_s, take_s=take_s, refine=refine,
            )                                                   # (Qs, k)
            # Single-pass MPI-style reduce over the model axis (§2.4.5).
            all_ids = jax.lax.all_gather(ids, model_axis, axis=1, tiled=True)
            all_d = jax.lax.all_gather(dists, model_axis, axis=1, tiled=True)
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

    Runs the dynamic stages (predicate parse → filter mask → Algorithm 1) on
    host, then dispatches the jitted shard_map kernel. Results match
    ``index.search`` (either backend) bit-for-bit on ids up to cross-shard
    padding of the partition axis.
    """
    from repro.core import attributes as am, partitions as pm

    queries = np.atleast_2d(np.asarray(queries, dtype=np.float64))
    qn = queries.shape[0]
    cfg = index.config
    r = am.build_r_lookup(index.attr_index, predicates)
    f_one = np.asarray(am.filter_mask(r, index.attr_index.codes))
    if getattr(index, "live_mask", None) is not None:
        f_one = f_one & index.live_mask   # tombstoned rows fail Stage 1
    f = np.broadcast_to(f_one, (qn, f_one.shape[0]))
    visit, cands = pm.select_partitions(
        queries, index.partitioning.centroids, f,
        index.partitioning.assign, index.partitioning.threshold, k,
    )

    if mesh is None:
        devs = np.array(jax.devices()[:1]).reshape(1, 1)
        mesh = Mesh(devs, (data_axes[0], model_axis))

    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    model_size = int(np.prod([mesh.shape[a] for a in (model_axis,)]))
    stacked = stack_index(index, pad_to_multiple=model_size, dtype=dtype)
    p, n_max = stacked.num_partitions, stacked.n_max

    # Dense per-(query, partition) payloads: mask + dynamic stage counts.
    cand_mask, n_cand = dataplane.build_cand_arrays(cands, qn, p, n_max)
    profile = getattr(index, "profile", None)
    keep, take = dataplane.stage_counts(n_cand, cfg, k, profile)
    keep_s, take_s = dataplane.static_counts(n_max, cfg, k, profile)

    data_size = int(np.prod([mesh.shape[a] for a in data_axes]))
    pad_q = -(-qn // data_size) * data_size
    if pad_q != qn:
        queries = np.pad(queries, ((0, pad_q - qn), (0, 0)))
        cand_mask = np.pad(cand_mask, ((0, pad_q - qn), (0, 0), (0, 0)))
        keep = np.pad(keep, ((0, pad_q - qn), (0, 0)))
        take = np.pad(take, ((0, pad_q - qn), (0, 0)))

    search = make_search_fn(
        mesh, k=k, keep_s=keep_s, take_s=take_s, refine=cfg.enable_refine,
        data_axes=data_axes, model_axis=model_axis,
    )
    with mesh:
        ids, dists = search(
            jnp.asarray(queries, dtype), jnp.asarray(cand_mask),
            jnp.asarray(keep), jnp.asarray(take), stacked,
        )
    return np.asarray(ids)[:qn], np.asarray(dists)[:qn]
