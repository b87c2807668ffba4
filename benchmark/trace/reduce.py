"""Reduce a profiler trace (``.xplane.pb``) to busy/idle time, per-operation
time and idle gaps attributed to what the host was doing.

Only ``jax.profiler.ProfileData`` is needed to read the file.  Layout as
the v5e writes it (looked at by hand, PR 23): one plane ``/device:TPU:<n>``
per chip whose line ``XLA Ops`` holds one event per executed HLO
operation, named by the operation's HLO text (``%fusion.26 = f32[4096,
16384]{...} fusion(...)``); the plane ``/host:CPU`` holds one line per host
thread, and ``jax.profiler.TraceAnnotation`` regions appear there under
their own names.  Device and host events share one clock (ns since the
trace began).  Host regions kept: the benchmark's ``bench.*`` spans around
its calls into the program and, below them, the decode worker's own
``perf.decode.*`` regions (same trace, same clock), so that an idle gap
is named by what the worker was doing wherever it says.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
# outermost layer first: where regions of two layers are open at once, the
# deeper layer's names the gap (a span of the load generator's thread may
# well have begun after the worker's region it waits on)
SPAN_PREFIXES = ("bench.", "perf.decode.")
OPS_LINE = "XLA Ops"
_RESULT = re.compile(r"^%?(?P<op>[^\s=]+) = \(?(?P<dtype>[a-z]+[0-9]*)"
                     r"\[(?P<dims>[0-9,]*)\]")
_SHAPE = re.compile(r"(?P<dtype>[a-z]+[0-9]*)\[(?P<dims>[0-9,]*)\]")


def find_xplane(logdir):
    """-> path of the newest ``.xplane.pb`` under a profiler log directory."""
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def op_label(hlo_text):
    """``%fusion.26 = f32[4096,16384]{...} fusion(...)`` ->
    ``fusion.26_f32_4096_16384_``: the operation under the name the trace
    prints, with its result's type and shape, free of spaces."""
    m = _RESULT.match(hlo_text)
    if not m:
        return re.sub(r"[^A-Za-z0-9_.-]", "_", hlo_text)[:64]
    dims = m.group("dims").replace(",", "_")
    return f"{m.group('op')}_{m.group('dtype')}_{dims}_"


def op_name(hlo_text):
    """The bare operation name (``fusion.26``) of an ``XLA Ops`` event."""
    m = _RESULT.match(hlo_text)
    return m.group("op") if m else hlo_text.lstrip("%").split(" ", 1)[0]


def shapes_in(hlo_text):
    """Every ``dtype[dims]`` in an event's HLO text, result first ->
    ``[(dtype, (dims...)), ...]``."""
    out = []
    for m in _SHAPE.finditer(hlo_text):
        dims = tuple(int(d) for d in m.group("dims").split(",") if d)
        out.append((m.group("dtype"), dims))
    return out


def _union_length(intervals):
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _gaps(intervals, lo, hi):
    """Idle gaps of one device inside [lo, hi] -> [(a, b), ...]."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


class Trace:
    """Device operations and host annotations of one trace, in seconds."""

    def __init__(self, device_ops, host_spans, span_prefixes=SPAN_PREFIXES):
        # {chip: [(start, end, hlo_text)]}, [(start, end, name)]
        self.device_ops = device_ops
        self.host_spans = host_spans
        self.span_prefixes = tuple(span_prefixes)

    @classmethod
    def from_file(cls, path, span_prefixes=SPAN_PREFIXES):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        device_ops, host_spans = {}, []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                ops = device_ops.setdefault(int(m.group(1)), [])
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        t0 = ev.start_ns * 1e-9
                        ops.append((t0, t0 + ev.duration_ns * 1e-9,
                                    ev.name))
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(tuple(span_prefixes)):
                            t0 = ev.start_ns * 1e-9
                            host_spans.append(
                                (t0, t0 + ev.duration_ns * 1e-9, ev.name))
        return cls(device_ops, host_spans, span_prefixes)

    def window(self, name):
        """[start, end] of the one host span called ``name`` (the traced
        steady window), or the extent of all device work without it."""
        hits = [(a, b) for a, b, n in self.host_spans if n == name]
        if hits:
            return min(a for a, _ in hits), max(b for _, b in hits)
        every = [x for ops in self.device_ops.values() for x in ops]
        if not every:
            return 0.0, 0.0
        return min(a for a, _, _ in every), max(b for _, b, _ in every)

    def clipped(self, lo, hi):
        """Device operations clipped to [lo, hi] -> {chip: [(a, b, text)]}."""
        out = {}
        for chip, ops in self.device_ops.items():
            out[chip] = [(max(a, lo), min(b, hi), t) for a, b, t in ops
                         if b > lo and a < hi]
        return out

    def reduce(self, window_span="bench.window"):
        """-> dict with ``window_s``, ``busy_s`` (union of device operation
        intervals, averaged over chips), ``ops`` {label: seconds, averaged
        over chips}, ``events`` [(seconds, hlo_text)] of chip 0,
        ``idle_gaps`` {host span open at the time: seconds}, of chip 0, and
        ``host_spans``, the names of the host spans kept."""
        lo, hi = self.window(window_span)
        ops = self.clipped(lo, hi)
        chips = sorted(ops) or [0]
        busy = sum(_union_length([(a, b) for a, b, _ in ops.get(c, [])])
                   for c in chips) / len(chips)
        per_op = {}
        for c in chips:
            for a, b, text in ops.get(c, []):
                label = op_label(text)
                per_op[label] = per_op.get(label, 0.0) + (b - a) / len(chips)
        first = ops.get(chips[0], [])
        spans = [(a, b, n) for a, b, n in self.host_spans
                 if n != window_span]
        idle = {}
        for a, b in _gaps([(x, y) for x, y, _ in first], lo, hi):
            for name, seconds in _attribute(
                    a, b, spans, self.span_prefixes).items():
                idle[name] = idle.get(name, 0.0) + seconds
        return {
            "window_s": hi - lo,
            "busy_s": busy,
            "ops": per_op,
            "events": [(b - a, text) for a, b, text in first],
            "idle_gaps": idle,
            "host_spans": sorted({n for _, _, n in spans}),
        }


def _attribute(a, b, spans, prefixes=("",)):
    """Split the gap [a, b] among the host spans open during it: the
    innermost span wins each instant, which is the one of the deepest
    layer (the last of ``prefixes`` its name starts with) and there the
    latest started; time under no span goes to ``_no_span_``."""
    def depth(name):
        return max((i for i, p in enumerate(prefixes)
                    if name.startswith(p)), default=-1)

    cuts = {a, b}
    live = [(s, e, n) for s, e, n in spans if e > a and s < b]
    for s, e, _ in live:
        cuts.update(x for x in (s, e) if a < x < b)
    edges = sorted(cuts)
    out = {}
    for x, y in zip(edges, edges[1:]):
        mid = (x + y) / 2
        open_now = [(depth(n), s, n) for s, e, n in live if s <= mid < e]
        name = max(open_now)[2] if open_now else "_no_span_"
        out[name] = out.get(name, 0.0) + (y - x)
    return out


def top(mapping, n=10):
    """The ``n`` largest entries of {name: seconds} -> [[name, seconds]]."""
    return [[k, v] for k, v in sorted(mapping.items(),
                                      key=lambda kv: -kv[1])[:n]]
