"""Where the device's idle time went, by the host spans that cover it.

The program writes its own layer spans (``squash.*``: request, Stage 1,
Algorithm 1, plane set-up, upload, dispatch, fetch, and ``squash.gc`` for a
Python collection) into the JAX profiler's trace while its obs registry is
enabled, on the same clock as the device's ops and the harness's
``bench.*`` spans. This reduction puts each device-idle gap of the
``bench.window`` down to one span, and sums each span's in-window seconds.

A gap is named by the innermost span that covers at least half of it (a
gap inside ``squash.alg1`` within ``squash.request`` within
``bench.request`` is ``squash.alg1``); where no span covers half, by the
span that covers most of it, the shorter on a tie; ``other`` where none
covers it. With only the harness's spans in a trace every gap keeps the
name ``traces.reduce`` gives it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

from squashbench import traces

SPAN_PREFIXES = ("bench.", "squash.")
REQUEST_SPAN = "squash.request"

Span = Tuple[str, int, int]


def host_spans(pd) -> List[Span]:
    """Every ``bench.*`` and ``squash.*`` event on the host planes."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith(traces.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            spans.extend(ev for ev in traces._events(line)
                         if ev[0].startswith(SPAN_PREFIXES))
    return spans


def first_chip_busy(pd, lo: int, hi: int) -> List[traces.Interval]:
    """Merged op intervals, clipped to [lo, hi), of the first chip (by plane
    name) that ran an op in the window: the chip ``traces.reduce`` names
    the idle gaps of."""
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith(traces.DEVICE_PREFIX):
            continue
        ops = [(s, e) for line in plane.lines if line.name == traces.OPS_LINE
               for _, s, e in traces._events(line) if e > lo and s < hi]
        if ops:
            return traces.union(traces.clip(ops, lo, hi))
    raise ValueError("no operation ran on a device inside the window")


def name_gap(spans: Sequence[Span], s: int, e: int) -> str:
    """The span a gap [s, e) is put down to (see the module docstring)."""
    covering = []
    for name, a, b in spans:
        c = min(b, e) - max(a, s)
        if c > 0:
            covering.append((c, b - a, name))
    if not covering:
        return "other"
    half = [(length, name) for c, length, name in covering
            if 2 * c >= e - s]
    if half:
        return min(half)[1]
    return min(covering, key=lambda t: (-t[0], t[1]))[2]


@dataclasses.dataclass
class SpanReduction:
    window_s: float
    idle_s: float                       # the first chip's, as idle_gaps
    span_seconds: Dict[str, float]      # in-window seconds per span name
    idle_by_span: Dict[str, float]      # idle seconds put down to each name
    idle_gaps: List[Tuple[str, float]]  # the longest gaps, named

    def named_share(self, prefix: str = "squash.") -> float:
        """Share of the idle time put down to spans named ``prefix*``."""
        named = sum(s for n, s in self.idle_by_span.items()
                    if n.startswith(prefix))
        return named / self.idle_s if self.idle_s else 0.0


def reduce(pd, top: int = 10) -> SpanReduction:
    """Span seconds and idle attribution over the ``bench.window`` span."""
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == traces.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {traces.WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    inner = [(n, s, e) for n, s, e in spans if n != traces.WINDOW_SPAN]
    span_seconds: Dict[str, float] = {}
    for name, s, e in inner:
        c = min(e, hi) - max(s, lo)
        if c > 0:
            span_seconds[name] = span_seconds.get(name, 0.0) + c * 1e-9
    idle_by_span: Dict[str, float] = {}
    named = []
    for s, e in traces.gaps(first_chip_busy(pd, lo, hi), lo, hi):
        name = name_gap(inner, s, e)
        idle_by_span[name] = idle_by_span.get(name, 0.0) + (e - s) * 1e-9
        named.append((name, (e - s) * 1e-9))
    named.sort(key=lambda t: -t[1])
    return SpanReduction(window_s=(hi - lo) * 1e-9,
                         idle_s=sum(idle_by_span.values()),
                         span_seconds=span_seconds,
                         idle_by_span=idle_by_span, idle_gaps=named[:top])


def longest_requests(pd, top: int = 3) -> List[dict]:
    """The ``top`` longest ``squash.request`` spans, each with the seconds
    of every span inside it by name: where a stalled request spent its
    time."""
    spans = host_spans(pd)
    requests = sorted((sp for sp in spans if sp[0] == REQUEST_SPAN),
                      key=lambda sp: sp[1] - sp[2])[:top]
    out = []
    for _, a, b in requests:
        inside: Dict[str, float] = {}
        for name, s, e in spans:
            if a <= s and e <= b and (s, e) != (a, b):
                inside[name] = inside.get(name, 0.0) + (e - s) * 1e-9
        out.append({"seconds": (b - a) * 1e-9, "spans": inside})
    return out


# The per-layer readings these spans and the program's counters give, per
# request served in the window (see PERF.md §3).
LAYER_SPANS = {
    "stage1_ms.batch": ("squash.stage1",),
    "alg1_ms.batch": ("squash.alg1",),
    "plane_setup_ms.batch": ("squash.plane.setup", "squash.plane.upload"),
}
UPLOAD_COUNTER = "dataplane.upload.bytes"
REQUEST_COUNTER = "serve.requests"


def layer_readings(red: SpanReduction, counters: Dict[str, float],
                   requests: int) -> Dict[str, float]:
    """``stage1_ms.batch``, ``alg1_ms.batch``, ``plane_setup_ms.batch`` (ms
    per request, from the spans) and ``upload_mib.batch`` (MiB per request,
    from the counters); each left out where nothing was recorded."""
    out = {}
    for metric, names in LAYER_SPANS.items():
        secs = [red.span_seconds[n] for n in names if n in red.span_seconds]
        if secs and requests:
            out[metric] = 1e3 * sum(secs) / requests
    served = counters.get(REQUEST_COUNTER, 0)
    if served and UPLOAD_COUNTER in counters:
        out["upload_mib.batch"] = counters[UPLOAD_COUNTER] / served / 2**20
    return out
