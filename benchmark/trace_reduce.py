"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to device busy time,
per-operation device time and idle gaps attributed to host spans.

Reads the file with ``jax.profiler.ProfileData`` alone.  What the planes of a
v5e trace look like, and why these lines are the ones read, is in PERF.md
(section 3, "Reading the trace").

* A *device plane* is one whose name starts with ``/device:TPU:``.  Its line
  ``XLA Ops`` holds one event per executed HLO operation; control-flow
  operations (``while``, ``conditional``, ``call``) span their bodies, so
  busy time is the UNION of the intervals and an operation's time is its
  SELF time (its span less what its children cover).
* The *window* is the span from the start of the first to the end of the
  last host event named ``WINDOW_SPAN`` (the harness wraps every traced
  query in one); device events are clipped to it.
* An *idle gap* is a maximal interval of the window in which no operation
  ran on the device.  It is attributed to the innermost host event (any
  line of a ``/host:`` plane, so ``jax.profiler.TraceAnnotation`` spans of
  the harness and of the program both count) that covers its midpoint, else
  to ``unattributed host``.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench:query"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
_DIGITS = re.compile(r"\d+")
_SERIAL = re.compile(r"(\.\d+)+$")
#: a gap this short is the device's own turn-around between two operations,
#: not the host's doing; and only the longest gaps are looked up one by one
SHORT_GAP_NS = 20_000.0
MAX_ATTRIBUTED_GAPS = 5000
SHORT_GAPS = "gaps under 20 us between device ops (or beyond the 5000 longest)"


def _arrays(line) -> Tuple[List[str], np.ndarray, np.ndarray]:
    names, start, end = [], [], []
    for ev in line.events:
        names.append(ev.name)
        start.append(ev.start_ns)
        end.append(ev.start_ns + ev.duration_ns)
    return names, np.asarray(start, np.float64), np.asarray(end, np.float64)


def union(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merged, sorted, disjoint intervals covering the same points."""
    if not len(start):
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    first = np.concatenate(([True], s[1:] > e[:-1]))
    last = np.concatenate((first[1:], [True]))
    return s[first], e[last]


def self_times(names: List[str], start: np.ndarray, end: np.ndarray
               ) -> Dict[str, float]:
    """Nanoseconds per operation name, each event counted less the part its
    children cover (events of one line nest, they never straddle)."""
    out: Dict[str, float] = {}
    order = np.lexsort((-end, start))
    stack: List[List] = []   # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for i in order:
        s, e = float(start[i]), float(end[i])
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([names[i], e, e - s])
    close(float("inf"))
    return out


def _host_events(planes) -> Tuple[List[str], np.ndarray, np.ndarray]:
    names: List[str] = []
    starts, ends = [], []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            n, s, e = _arrays(line)
            names += n
            starts.append(s)
            ends.append(e)
    if not names:
        return names, np.zeros(0), np.zeros(0)
    return names, np.concatenate(starts), np.concatenate(ends)


def op_label(name: str) -> str:
    """``%fusion.7 = pred[1048576]{0:T(1024)...} fusion(...)`` (the whole HLO
    line is the event's name on a TPU) -> ``fusion pred[1048576]``: the kind of
    operation and the type it produces (of a tuple, the first element's).  The
    compiler's serial number goes, since it changes with every compile, so
    operations of one kind and shape are summed."""
    head, sep, rest = name.partition(" = ")
    kind = _SERIAL.sub("", head.lstrip("%"))
    if not sep:
        return kind[:80]
    return (kind + " " + re.split(r"[{ ]", rest.lstrip("("), 1)[0])[:80]


def _label(name: str) -> str:
    """One label per kind of span: ids inside a name (``12:stage3``) vary
    from query to query and would scatter one cause over many rows."""
    return _DIGITS.sub("#", name)[:80]


def reduce_trace(path: str, top: int = 10) -> Optional[dict]:
    """The reduced trace, or None where the file holds no device plane or no
    window span (a CPU rehearsal): nothing to read is not a reading of 0."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    h_names, h_start, h_end = _host_events(planes)
    in_window = np.asarray([n == WINDOW_SPAN for n in h_names], bool)
    devices = [p for p in planes if _DEVICE_PLANE.match(p.name)]
    if not devices or not in_window.any():
        return None
    w0, w1 = h_start[in_window].min(), h_end[in_window].max()

    busy_ns: List[float] = []
    op_ns: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    n_events = 0
    for p in devices:
        lines = {ln.name: ln for ln in p.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{p.name}: no '{OPS_LINE}' line among "
                             f"{sorted(lines)}")
        names, s, e = _arrays(lines[OPS_LINE])
        keep = (e > w0) & (s < w1)
        names = [op_label(n) for n, k in zip(names, keep) if k]
        s, e = np.clip(s[keep], w0, w1), np.clip(e[keep], w0, w1)
        n_events += len(names)
        us, ue = union(s, e)
        busy_ns.append(float((ue - us).sum()))
        for name, ns in self_times(names, s, e).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns
        if p is devices[0]:   # gaps are attributed on the first device
            gs = np.concatenate(([w0], ue))
            ge = np.concatenate((us, [w1]))
            gaps = [(a, b) for a, b in zip(gs, ge) if b > a]

    gap_ns: Dict[str, float] = {}
    gaps.sort(key=lambda ab: ab[0] - ab[1])   # longest first
    for i, (a, b) in enumerate(gaps):
        if b - a < SHORT_GAP_NS or i >= MAX_ATTRIBUTED_GAPS:
            label = SHORT_GAPS
        else:
            mid = (a + b) / 2
            cover = np.nonzero((h_start <= mid) & (h_end >= mid)
                               & ~in_window)[0]
            label = _label(h_names[cover[np.argmin(
                h_end[cover] - h_start[cover])]]) if len(cover) \
                else "unattributed host"
        gap_ns[label] = gap_ns.get(label, 0.0) + (b - a)

    def ranked(d: Dict[str, float]) -> List[List]:
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / 1e9] for k, v in rows]

    return {
        "window_s": float(w1 - w0) / 1e9,
        "busy_s": float(np.mean(busy_ns)) / 1e9,
        "queries": int(in_window.sum()),
        "device_events": n_events,
        "device_planes": [p.name for p in devices],
        "device_ops": ranked(op_ns),
        "idle_gaps": ranked(gap_ns),
    }


def describe(path: str, per_line: int = 5) -> List[dict]:
    """Planes, lines, event counts and a few names: what one looks at by
    hand before trusting :func:`reduce_trace` on a new kind of trace."""
    from jax.profiler import ProfileData
    out = []
    for p in ProfileData.from_file(path).planes:
        for line in p.lines:
            names, s, e = _arrays(line)
            counts: Dict[str, int] = {}
            for n in names:
                counts[n] = counts.get(n, 0) + 1
            common = sorted(counts.items(), key=lambda kv: -kv[1])[:per_line]
            out.append({"plane": p.name, "line": line.name,
                        "events": len(names),
                        "span_s": float((e.max() - s.min()) / 1e9)
                        if names else 0.0,
                        "first_start_ns": float(s.min()) if names else None,
                        "common": common})
    return out


if __name__ == "__main__":
    import json
    import sys
    for row in describe(sys.argv[1]):
        print(json.dumps(row))
    print(json.dumps(reduce_trace(sys.argv[1])))
