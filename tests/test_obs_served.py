"""Spans and counters of the served path (``VectorSearchService.query`` →
``SquashIndex.search`` → ``_search_jax``) in the JAX profiler's trace.

A small index serves Q = 16 requests through the jax backend under
``jax.profiler.start_trace`` twice: once with the obs registry enabled and
once with it off. The enabled run must write one of each ``squash.*`` layer
span per request, nested in that request's ``squash.request``, and count the
bytes it put on the device exactly; the off run must record no ``squash.*``
span and return bitwise the same answers.
"""

import gc
import glob
import os

import numpy as np
import pytest
from jax.profiler import ProfileData

import jax
from repro.obs import spans
from repro.obs.metrics import REGISTRY
from repro.core.pipeline import SquashConfig, SquashIndex
from repro.data import synthetic
from repro.serve.vector_service import ServiceConfig, VectorSearchService

Q = 16
REQUESTS = 3
# The leaf spans of one request, in the order the served path enters them.
LAYERS = ("squash.stage1", "squash.alg1", "squash.plane.setup",
          "squash.plane.upload", "squash.plane.dispatch",
          "squash.plane.fetch")


@pytest.fixture(scope="module")
def built():
    ds = synthetic.make_vector_dataset("sift1m", scale=0.008,
                                       num_queries=REQUESTS * Q, seed=13)
    preds = synthetic.default_predicates()
    cfg = SquashConfig(num_partitions=6, kmeans_iters=5, lloyd_iters=8)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=13)
    svc = VectorSearchService(index, ServiceConfig(backend="jax"))
    svc.query(ds.queries[:Q], preds)            # compile outside the traces
    return ds, preds, index, svc


def _serve_traced(built, trace_dir, obs: bool):
    """Serve the requests under a profiler trace; (answers, trace, counters)."""
    ds, preds, _, svc = built
    REGISTRY.reset()
    if obs:
        REGISTRY.enable()
    try:
        jax.profiler.start_trace(str(trace_dir))
        try:
            answers = [svc.query(ds.queries[i * Q:(i + 1) * Q], preds)
                       for i in range(REQUESTS)]
            gc.collect()
        finally:
            jax.profiler.stop_trace()
        counters = REGISTRY.snapshot()["counters"]
    finally:
        REGISTRY.disable()
        REGISTRY.reset()
    found = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    return answers, ProfileData.from_file(found[0]), counters


def _squash_events(pd):
    """(name, start, end, stats, line id) of every ``squash.*`` host event."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("squash."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), (plane.name, li)))
    return out


@pytest.fixture(scope="module")
def runs(built, tmp_path_factory):
    on = _serve_traced(built, tmp_path_factory.mktemp("obs_on"), obs=True)
    off = _serve_traced(built, tmp_path_factory.mktemp("obs_off"), obs=False)
    return on, off


def test_each_request_has_one_of_each_layer_span_in_order(runs):
    (_, pd, _), _ = runs
    events = _squash_events(pd)
    requests = sorted((e for e in events if e[0] == "squash.request"),
                      key=lambda e: e[1])
    assert len(requests) == REQUESTS
    numbers = [r[3]["request"] for r in requests]
    assert numbers == list(range(numbers[0], numbers[0] + REQUESTS))
    for name, s, e, stats, line in requests:
        assert stats["backend"] == "jax" and stats["queries"] == Q
        inside = sorted((ev for ev in events
                         if ev[0] in LAYERS and s <= ev[1] and ev[2] <= e),
                        key=lambda ev: ev[1])
        assert tuple(ev[0] for ev in inside) == LAYERS
        assert all(ev[4] == line for ev in inside)
        # Leaves: each ends before the next one starts.
        for a, b in zip(inside, inside[1:]):
            assert a[2] <= b[1]
    layer_events = [ev for ev in events if ev[0] in LAYERS]
    assert len(layer_events) == len(LAYERS) * REQUESTS


def test_upload_bytes_match_the_shapes(built, runs):
    _, _, index, _ = built
    (_, _, counters), _ = runs
    m1, a = index.attr_index.boundaries.shape
    n = index.attr_index.codes.shape[0]
    st = index.device_stack()
    p, n_max = st.num_partitions, st.n_max
    d = int(st.vectors.shape[-1])
    int_bytes = np.dtype(jax.numpy.int32).itemsize
    per_request = (m1 * a                           # r_lookup, uint8
                   + n * a * int_bytes              # attribute codes
                   + Q * d * st.vectors.dtype.itemsize   # queries
                   + Q * p * n_max                  # cand_mask, bool
                   + 2 * Q * p * int_bytes)         # keep, take
    assert counters["dataplane.upload.bytes"] == REQUESTS * per_request
    assert counters["serve.requests"] == REQUESTS


def test_obs_off_is_bitwise_identical_and_records_no_span(runs):
    (on, _, _), (off, pd_off, counters_off) = runs
    for (ids_a, d_a, s_a), (ids_b, d_b, s_b) in zip(on, off):
        np.testing.assert_array_equal(ids_a, ids_b)
        assert d_a.tobytes() == d_b.tobytes()
        assert s_a == s_b
    assert _squash_events(pd_off) == []
    assert counters_off == {}


def test_gc_collections_are_spans_only_while_enabled(runs):
    (_, pd_on, _), (_, pd_off, _) = runs
    collections = [ev for ev in _squash_events(pd_on)
                   if ev[0] == "squash.gc"]
    assert any(ev[3].get("generation") == 2 for ev in collections)
    assert all(ev[2] >= ev[1] for ev in collections)
    assert not [ev for ev in _squash_events(pd_off) if ev[0] == "squash.gc"]


def test_span_is_one_shared_null_context_while_disabled():
    assert not REGISTRY.enabled
    assert spans.span("squash.a") is spans.span("squash.b", request=1)
    with spans.span("squash.a", request=1) as entered:
        assert entered is None
