"""Read a ``jax.profiler`` trace (``.xplane.pb``) by operator: device
self-time by stage program -> operator scope -> kernel or expression
scope, and idle gaps by the program span (``srt/<site>/<name>``) the
host was in.

``tools/rapidsprof.py --xplane`` prints what :func:`reduce_xplane`
returns.  The file is parsed with tsl's generated ``xplane_pb2`` (see
:func:`_xplane_pb2`; no jax), because what ties a device operation to an
operator is NOT reachable through ``jax.profiler.ProfileData`` (looked
at by hand on a v5e trace, PERF.md section 3 "Reading the trace"):

* ``XLA Modules`` events are named ``jit_<program>(<program_id>)`` —
  ``<program>`` is the label ``instrumented_jit`` gave the function;
* an ``XLA Ops`` event's name is its HLO line, without metadata; the
  ``jax.named_scope`` path (``jit(stage_X)/TpuFilterExec.3/k.layout.
  gather_rows/gather:``) is the stat ``tf_op`` of the event's
  *XEventMetadata*, beside ``program_id`` and ``hlo_category`` —
  ``ProfileData`` shows an event's own stats only.

The busy/idle reduction repeats ``benchmark/trace_reduce.py``'s (union,
self-times, innermost-span gaps) because this round's contract freezes
the harness; ROADMAP B3's follow-up makes the two share one reducer.
"""

from __future__ import annotations

import importlib.util
import os
import re
from typing import Dict, List, Optional, Tuple

PREFIX = "srt/"
WINDOW_SPAN = "bench:query"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: a gap this short is the device's own turn-around between two
#: operations, not the host's doing (benchmark/trace_reduce.py's limit)
SHORT_GAP_PS = 20_000_000

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")
_OPERATOR = re.compile(r"^[A-Za-z_]\w*\.\d+$")
NO_SCOPE = "(no scope)"

#: (metadata id, start ps, duration ps)
Event = Tuple[int, int, int]


# -- the file -----------------------------------------------------------------

_PB2 = None


def _xplane_pb2():
    """tsl's generated ``xplane_pb2``, imported on first use (only
    ``--xplane`` needs it).  It ships inside the installed ``tensorflow``
    package and needs only ``google.protobuf``; it is loaded from its
    file because ``import tensorflow...`` runs the whole package's
    ``__init__`` first (about 28 s here)."""
    global _PB2
    if _PB2 is None:
        spec = importlib.util.find_spec("tensorflow")  # imports nothing
        if spec is None or not spec.submodule_search_locations:
            raise ImportError("reading an .xplane.pb needs the xplane_pb2 "
                              "module of an installed tensorflow")
        path = os.path.join(list(spec.submodule_search_locations)[0],
                            "tsl", "profiler", "protobuf", "xplane_pb2.py")
        mod_spec = importlib.util.spec_from_file_location(
            "rapidsprof_xplane_pb2", path)
        _PB2 = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(_PB2)
    return _PB2


def read_xspace(path: str):
    """The trace as an ``XSpace`` message."""
    with open(path, "rb") as f:
        return _xplane_pb2().XSpace.FromString(f.read())


def _events(plane, line_name: str) -> List[Event]:
    """Events of the plane's lines of that name."""
    return [(ev.metadata_id, ln.timestamp_ns * 1000 + ev.offset_ps,
             ev.duration_ps)
            for ln in plane.lines if ln.name == line_name
            for ev in ln.events]


def _metadata_stats(plane, mid: int) -> Dict[str, object]:
    """Stats of an event's METADATA by name (``tf_op``, ``program_id``)."""
    out: Dict[str, object] = {}
    for st in plane.event_metadata[mid].stats:
        kind = st.WhichOneof("value")
        val = getattr(st, kind) if kind else None
        if kind == "ref_value":   # a string interned as a stat's name
            val = plane.stat_metadata[val].name
        out[plane.stat_metadata[st.metadata_id].name] = val
    return out


# -- reduction ----------------------------------------------------------------


def scopes_of(tf_op: str) -> Tuple[str, str]:
    """(operator scope, inner scope) of an ``op_name`` path: the
    innermost ``<Class>.<k>`` component (``plan/pipeline._scoped``) and
    the innermost ``k.<module>.<fn>`` (a kernel entry point,
    ``utils/tracing.kernel_scope``) or ``e.<Class>`` (an expression's
    device evaluation, ``exprs/base.Expression``)."""
    operator = inner = NO_SCOPE
    for part in (tf_op or "").rstrip(":").split("/"):
        if part.startswith(("k.", "e.")):
            inner = part
        elif _OPERATOR.match(part):
            operator = part
    return operator, inner


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(events: List[Event]) -> Dict[int, int]:
    """Picoseconds per metadata id, each event counted less the part its
    children cover (control-flow operations span their bodies)."""
    out: Dict[int, int] = {}
    stack: List[List[int]] = []   # [metadata id, end, self]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            mid, _e, own = stack.pop()
            out[mid] = out.get(mid, 0) + max(own, 0)

    for mid, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(s + d, stack[-1][1]) - s
        stack.append([mid, s + d, d])
    close(1 << 62)
    return out


def reduce_xplane(path: str) -> Optional[dict]:
    """Device time by module/operator/kernel and idle gaps by program
    span, over the window the trace's queries span (the harness's
    ``bench:query`` spans; else first to last ``srt/`` span).  None
    where the file holds no TPU device plane."""
    planes = read_xspace(path).planes
    devices = [p for p in planes if _DEVICE_PLANE.match(p.name)
               and _events(p, OPS_LINE)]
    if not devices:
        return None
    host: List[Tuple[str, int, int]] = []
    for p in planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                t0_ps = ln.timestamp_ns * 1000
                host += [(p.event_metadata[ev.metadata_id].name,
                          t0_ps + ev.offset_ps,
                          t0_ps + ev.offset_ps + ev.duration_ps)
                         for ev in ln.events]
    marks = [h for h in host if h[0] == WINDOW_SPAN] or \
        [h for h in host if h[0].startswith(PREFIX)]
    dev = devices[0]
    ops = _events(dev, OPS_LINE)
    if marks:
        w0, w1 = min(h[1] for h in marks), max(h[2] for h in marks)
    else:
        w0 = min(s for _m, s, _d in ops)
        w1 = max(s + d for _m, s, d in ops)
    ops = [(m, max(s, w0), min(s + d, w1) - max(s, w0))
           for m, s, d in ops if s + d > w0 and s < w1]
    modules = {}
    for mid, _s, _d in _events(dev, MODULES_LINE):
        m = _MODULE.match(dev.event_metadata[mid].name)
        if m:
            modules[int(m.group(2))] = m.group(1)

    by_scope: Dict[Tuple[str, str, str], int] = {}
    for mid, ps in _self_times(ops).items():
        st = _metadata_stats(dev, mid)
        module = modules.get(st.get("program_id"), "(unknown module)")
        key = (module,) + scopes_of(st.get("tf_op") or "")
        by_scope[key] = by_scope.get(key, 0) + ps
    busy = _union([(s, s + d) for _m, s, d in ops])
    busy_ps = sum(e - s for s, e in busy)

    spans = [h for h in host if h[0].startswith(PREFIX)]
    gaps: Dict[str, int] = {}
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        if b - a < SHORT_GAP_PS:
            label = "(gaps under 20 us between device ops)"
        else:
            mid_t = (a + b) // 2
            cover = [h for h in spans if h[1] <= mid_t <= h[2]]
            label = min(cover, key=lambda h: h[2] - h[1])[0] if cover \
                else "(no srt/ span)"
        gaps[label] = gaps.get(label, 0) + (b - a)

    total = sum(by_scope.values()) or 1
    named_module = sum(ps for (mod, _o, _k), ps in by_scope.items()
                       if mod.startswith("jit_") and mod != "jit_run")
    under_operator = sum(ps for (_m, op, _k), ps in by_scope.items()
                         if op != NO_SCOPE)
    idle = sum(gaps.values())
    long_idle = sum(ps for k, ps in gaps.items() if not k.startswith("("))
    attributable = sum(ps for k, ps in gaps.items()
                       if not k.startswith("(gaps under"))
    return {
        "window_s": (w1 - w0) / 1e12,
        "busy_s": busy_ps / 1e12,
        "queries": sum(1 for h in host if h[0] == WINDOW_SPAN),
        "device_events": len(ops),
        "by_scope": sorted(((m, o, k, ps / 1e12)
                            for (m, o, k), ps in by_scope.items()),
                           key=lambda r: -r[3]),
        "idle_gaps": sorted(((k, ps / 1e12) for k, ps in gaps.items()),
                            key=lambda r: -r[1]),
        "idle_s": idle / 1e12,
        "named_module_share": named_module / total,
        "operator_scope_share": under_operator / total,
        "srt_gap_share": long_idle / attributable if attributable else 1.0,
        "host_spans": _span_totals(spans, w0, w1),
    }


def _span_totals(spans, w0: int, w1: int) -> List[Tuple[str, int, float]]:
    """(span name, count, seconds) of every program span in the window."""
    tot: Dict[str, List[float]] = {}
    for name, s, e in spans:
        if e <= w0 or s >= w1:
            continue
        t = tot.setdefault(name, [0, 0.0])
        t[0] += 1
        t[1] += (e - s) / 1e12
    return sorted(((k, int(c), s) for k, (c, s) in tot.items()),
                  key=lambda r: -r[2])


def format_report(r: dict, top: int = 25) -> str:
    q = max(r["queries"], 1)
    lines = [
        f"window {r['window_s']:.4f} s, device busy {r['busy_s']:.4f} s "
        f"({100 * r['busy_s'] / r['window_s']:.2f}%), "
        f"{r['queries']} bench:query span(s), {r['device_events']} "
        f"device op events",
        f"device self-time under a named stage program: "
        f"{100 * r['named_module_share']:.2f}%; under an operator scope: "
        f"{100 * r['operator_scope_share']:.2f}%",
        "", "== device self-time: module -> operator -> kernel (k.) or "
            "expression (e.) scope =="]
    by_module: Dict[str, float] = {}
    by_operator: Dict[Tuple[str, str], float] = {}
    for m, o, _k, s in r["by_scope"]:
        by_module[m] = by_module.get(m, 0.0) + s
        by_operator[(m, o)] = by_operator.get((m, o), 0.0) + s
    busy = sum(by_module.values()) or 1.0
    shown = 0
    for m, ms in sorted(by_module.items(), key=lambda kv: -kv[1]):
        lines.append(f"{m}: {ms:.6f} s ({100 * ms / busy:.1f}%, "
                     f"{1e3 * ms / q:.3f} ms a query)")
        for (m2, o), os_ in sorted(by_operator.items(),
                                   key=lambda kv: -kv[1]):
            if m2 != m:
                continue
            lines.append(f"  {o}: {os_:.6f} s ({100 * os_ / busy:.1f}%)")
            for m3, o3, k, s in r["by_scope"]:
                if (m3, o3) == (m, o) and shown < top:
                    lines.append(f"    {k}: {s:.6f} s "
                                 f"({100 * s / busy:.1f}%)")
                    shown += 1
    lines += ["", f"== idle gaps by innermost srt/ span: "
                  f"{r['idle_s']:.6f} s; {100 * r['srt_gap_share']:.1f}% "
                  f"of the gaps over 20 us fall under a span =="]
    for k, s in r["idle_gaps"][:top]:
        lines.append(f"  {k}: {s:.6f} s ({1e3 * s / q:.3f} ms a query)")
    lines += ["", "== program spans in the window (host wall) =="]
    for k, c, s in r["host_spans"][:top]:
        lines.append(f"  {k}: {c} x, {s:.6f} s ({1e3 * s / q:.3f} ms "
                     f"a query)")
    return "\n".join(lines)
