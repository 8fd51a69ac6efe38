"""The control: the plain reference put in the program's place, computed in
bfloat16, the precision below the float32 the configurations state.

It is exact filtered brute force like ``reference.py``, run on the device
with every vector, query and distance in bfloat16. ``correct`` has to come
out false for it; ``python bench/run.py ... --control`` runs a cell with it
in place of the index, and ``tests/test_bench_harness.py`` keeps it at a
small size.
"""

from __future__ import annotations

import functools

import numpy as np


class Bf16BruteForce:
    """Serves requests as the index would: (ids, dists) per request."""

    def __init__(self, vectors: np.ndarray, attributes: np.ndarray, k: int):
        import jax
        import jax.numpy as jnp

        self.k = k
        self.vectors = jax.device_put(jnp.asarray(vectors, jnp.bfloat16))
        self.attributes = jax.device_put(jnp.asarray(attributes, jnp.int32))
        self._search = jax.jit(functools.partial(_search, k=k))

    def query(self, queries: np.ndarray, ranges):
        import jax.numpy as jnp

        lo = np.array([r[1] for r in ranges], np.int32)
        hi = np.array([r[2] for r in ranges], np.int32)
        attrs = np.array([r[0] for r in ranges], np.int32)
        ids, dists = self._search(self.vectors, self.attributes,
                                  jnp.asarray(queries, jnp.bfloat16),
                                  jnp.asarray(attrs), jnp.asarray(lo),
                                  jnp.asarray(hi))
        return np.asarray(ids, np.int64), np.asarray(dists, np.float64)


def _search(vectors, attributes, queries, attrs, lo, hi, *, k):
    import jax
    import jax.numpy as jnp

    cols = attributes[:, attrs]                                # (N, R)
    mask = jnp.all((cols >= lo) & (cols <= hi), axis=1)        # (N,)

    def one(q):
        diff = vectors - q[None, :]                            # bfloat16
        dist = jnp.sqrt(jnp.sum(diff * diff, axis=-1, dtype=jnp.bfloat16))
        dist = jnp.where(mask, dist, jnp.inf).astype(jnp.bfloat16)
        neg, idx = jax.lax.top_k(-dist, k)
        return jnp.where(jnp.isfinite(neg), idx, -1), -neg

    ids, dists = jax.lax.map(one, queries)
    return ids, dists.astype(jnp.float32)
