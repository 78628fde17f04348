"""Reduction of a profiler trace to device busy time, idle gaps and ops.

``load`` reads an ``.xplane.pb`` written by ``jax.profiler`` with
``jax.profiler.ProfileData`` alone: the device planes' op events and the
host's benchmark spans (``TraceAnnotation`` names that start with
``bench.``).  ``reduce`` then works on plain (name, start, end) tuples in
nanoseconds, inside the traced window (the ``bench.window`` span):

* busy: the union of the intervals in which an operation ran, per device,
  averaged over the devices;
* idle share: 1 - busy / window;
* the operations that took most device time, summed by name (loops and
  calls, which hold other ops, are left out of this ranking);
* the longest idle gaps, each named by the benchmark spans that were open
  on the host at its middle.
"""
from __future__ import annotations

from collections import defaultdict

WINDOW_SPAN = 'bench.window'
SPAN_PREFIX = 'bench.'
OP_LINES = ('XLA Ops',)
CONTAINERS = ('%while', '%conditional', '%call')


def union(intervals):
    """Merge (start, end) intervals -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def load(path: str):
    """-> (devices: {plane: [(name, start, end)]}, spans: [(name, s, e)])."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith('/device:'):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name in OP_LINES]
            # an op event's name is its whole HLO instruction; keep the
            # instruction's own name ("%fusion.917")
            evs = [(e.name.split(' = ')[0], e.start_ns, e.end_ns)
                   for ln in ops for e in ln.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith('/host:'):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
    return devices, spans


def reduce(devices: dict, spans: list, top: int = 10) -> dict:
    """Busy, idle share, top device ops and longest gaps in the window."""
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win or not devices:
        return None
    t0, t1 = win[0]
    window_ns = t1 - t0
    busy, ops, gaps = [], defaultdict(float), []
    for evs in devices.values():
        merged = clip(union([(s, e) for _, s, e in evs]), t0, t1)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in evs:
            c = clip([(s, e)], t0, t1)
            # a loop or call holds the ops inside it: rank the ops alone
            if c and not name.startswith(CONTAINERS):
                ops[name] += c[0][1] - c[0][0]
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]

    def what(mid):
        open_ = sorted({n[len(SPAN_PREFIX):] for n, s, e in host
                        if s <= mid < e})
        return '+'.join(open_) if open_ else 'no_span'

    gaps.sort(key=lambda g: g[0] - g[1])
    busy_ns = sum(busy) / len(busy)
    return dict(
        busy_s=busy_ns * 1e-9, window_s=window_ns * 1e-9,
        idle_share=1.0 - busy_ns / window_ns,
        device_ops=[[n, v * 1e-9] for n, v in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[what(0.5 * (s + e)), (e - s) * 1e-9]
                   for s, e in gaps[:top]])
