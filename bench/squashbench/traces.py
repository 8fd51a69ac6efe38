"""Reduction of a JAX profiler trace to the device's busy time, per-kernel
time and the breakdown of where the window went.

Input is ``jax.profiler.ProfileData`` (or anything with its planes, lines
and events). Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation run on the chip. The harness's own spans
(``bench.*``, written with ``jax.profiler.TraceAnnotation``) sit on a host
thread line of ``/host:CPU`` on the same clock. ``bench.window`` brackets
the measured window.

An op event is named by its HLO text (``%sort.1 = (f32[...]) sort(...)``);
the reduction keys it by the jitted module that ran it and its instruction
name, ``jit_plane/sort.1``, so a kernel is found by its instruction name
alone and never by an operand that names it.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

Interval = Tuple[int, int]


def load(path: str):
    """ProfileData from an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of merged ``busy`` within [lo, hi)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over the chips that ran ops
    chips: int
    op_seconds: Dict[str, float]      # device seconds per op name, summed
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, kernel: str) -> Optional[float]:
        """Device seconds of the instructions named ``kernel`` (any numeric
        suffix, any module); None if none ran."""
        hits = [s for name, s in self.op_seconds.items()
                if instruction(name) == kernel]
        return sum(hits) if hits else None


def op_key(hlo_text: str, module: str) -> str:
    """``module/instruction`` for one op event."""
    return f"{module}/{hlo_text.split(' = ', 1)[0].lstrip('%')}"


def instruction(key: str) -> str:
    """The instruction name of an op key without its numeric suffix."""
    return re.sub(r"\.\d+$", "", key.rsplit("/", 1)[-1])


def _module_of(modules, starts, t: int) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][2]:
        return modules[i][0]
    return "?"


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def host_spans(pd) -> List[Tuple[str, int, int]]:
    spans = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            spans.extend(ev for ev in _events(line)
                         if ev[0].startswith(SPAN_PREFIX))
    return spans


def reduce(pd, top: int = 10) -> Reduced:
    """Busy time, op time and breakdown over the ``bench.window`` span."""
    spans = host_spans(pd)
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    busy_total, chips = 0, 0
    op_seconds: Dict[str, float] = {}
    first_busy: List[Interval] = []
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = [ev for line in plane.lines if line.name == OPS_LINE
               for ev in _events(line)]
        ops = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        modules = sorted(((n.split("(", 1)[0], s, e)
                          for line in plane.lines if line.name == MODULES_LINE
                          for n, s, e in _events(line)), key=lambda m: m[1])
        starts = [m[1] for m in modules]
        ops = [(op_key(n, _module_of(modules, starts, s)), s, e)
               for n, s, e in ops]
        if not ops:
            continue
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        chips += 1
        if not first_busy:
            first_busy = busy
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) * 1e-9
    if chips == 0:
        raise ValueError("no operation ran on a device inside the window")
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    idle = []
    for s, e in gaps(first_busy, lo, hi):
        idle.append((_span_at(inner, s, e), (e - s) * 1e-9))
    idle.sort(key=lambda t: -t[1])
    ops_sorted = sorted(op_seconds.items(), key=lambda t: -t[1])
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total / chips * 1e-9, chips=chips,
                   op_seconds=op_seconds, device_ops=ops_sorted[:top],
                   idle_gaps=idle[:top])


def _span_at(spans, s: int, e: int) -> str:
    """The harness span that covers most of [s, e), or "other"."""
    best, cover = "other", 0
    for name, a, b in spans:
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = name, c
    return best
