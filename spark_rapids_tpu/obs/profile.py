"""Fold a query's event-bus timeline into a :class:`QueryProfile`.

``session.execute`` builds one profile per query from the drained events
and keeps a bounded history (``session.query_history()``, conf
``spark.rapids.sql.tpu.obs.history.maxQueries``) — the SQL-UI role of
the reference's per-exec ``GpuMetric`` tables, answering "which operator's
programs did the host enqueue, and where did it wait" and "when did the
spill storm start" from data the chokepoints already produced.  (Which
operator ate the DEVICE's time is the device trace's to say:
``tools/rapidsprof.py --xplane``.)

Engine-free (stdlib only): ``tools/rapidsprof.py`` builds the same
profiles from a JSONL event log, so events are accessed duck-typed via
:func:`~spark_rapids_tpu.obs.events.field` (Event objects in-process,
plain dicts after a log round-trip).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from .events import SPAN, field


def _new_rollup(name: str) -> Dict[str, Any]:
    return {
        "name": name, "dispatches": 0, "enqueue_ns": 0, "errors": 0,
        "rows": 0, "batches": 0, "shuffle_bytes": 0, "shuffle_rows": 0,
        "shuffle_pieces": 0, "adaptive": {},
    }


class QueryProfile:
    """Per-operator rollups + per-site totals + wall-clock bounds for one
    query's event window.

    ``op_rollups`` is keyed by physical-plan ``op_id`` (enqueue spans
    carry the op_id of the stage root they were dispatched for, or the
    program's label where no stage was; exchange spans carry the
    exchange's); each rollup keeps the operator's display ``name``.
    A rollup's ``enqueue_ns`` is the host wall of its enqueues: what the
    host spent handing the operator's programs to the chip, not what the
    chip spent running them (the device trace answers that).  ``site_totals`` maps
    site -> {count, wall_ns, bytes}.  ``metrics`` / ``op_metrics`` are the
    query's ``last_metrics`` scalars and per-op metric dicts, stashed so a
    history entry is self-contained.
    """

    def __init__(self, query_id: int, events: List, dropped: int = 0,
                 wall_ns: int = 0,
                 metrics: Optional[Dict[str, Any]] = None,
                 op_metrics: Optional[Dict[str, Dict[str, Any]]] = None,
                 dropped_by_site: Optional[Dict[str, int]] = None,
                 session_id: int = 0, qt0_ns: int = 0, qt1_ns: int = 0):
        self.query_id = query_id
        self.events = list(events)
        self.dropped = int(dropped)
        self.wall_ns = int(wall_ns)
        self.metrics = dict(metrics or {})
        self.op_metrics = dict(op_metrics or {})
        self.dropped_by_site = dict(dropped_by_site or {})
        self.session_id = int(session_id)
        self.qt0_ns = int(qt0_ns)
        self.qt1_ns = int(qt1_ns)
        self.op_rollups: Dict[str, Dict[str, Any]] = {}
        self.site_totals: Dict[str, Dict[str, int]] = {}
        self.t_min = 0
        self.t_max = 0
        self._fold()

    # -- folding ------------------------------------------------------------

    def _rollup(self, op_id: str, name: str) -> Dict[str, Any]:
        r = self.op_rollups.get(op_id)
        if r is None:
            r = self.op_rollups[op_id] = _new_rollup(name)
        elif name and not r["name"]:
            r["name"] = name
        return r

    def _fold(self) -> None:
        for ev in self.events:
            kind = field(ev, "kind")
            site = field(ev, "site") or "?"
            name = field(ev, "name") or ""
            op_id = field(ev, "op_id") or ""
            t0 = int(field(ev, "t0", 0) or 0)
            t1 = int(field(ev, "t1", 0) or 0)
            pay = field(ev, "payload") or {}
            st = self.site_totals.setdefault(
                site, {"count": 0, "wall_ns": 0, "bytes": 0})
            st["count"] += 1
            st["wall_ns"] += max(0, t1 - t0)
            st["bytes"] += int(pay.get("bytes", 0) or 0)
            if t0:
                self.t_min = t0 if not self.t_min else min(self.t_min, t0)
                self.t_max = max(self.t_max, t1)
            if site == "enqueue":
                # under an operator: its class (op_id is <Class>#<k>);
                # else the program's own label
                r = self._rollup(
                    op_id or f"jit:{name}",
                    op_id.split("#")[0].split("@")[0] or name)
                r["dispatches"] += 1
                r["enqueue_ns"] += max(0, t1 - t0)
                r["rows"] += int(pay.get("rows", 0) or 0)
                r["batches"] += int(pay.get("batches", 0) or 0)
                if pay.get("error"):
                    r["errors"] += 1
            elif site == "exchange" and kind == SPAN:
                r = self._rollup(op_id, name or "exchange")
                r["shuffle_bytes"] += int(pay.get("bytes", 0) or 0)
                r["shuffle_rows"] += int(pay.get("rows", 0) or 0)
                r["shuffle_pieces"] += int(pay.get("pieces", 0) or 0)
            elif site == "adaptive" and op_id:
                r = self._rollup(op_id, "")
                r["adaptive"][name] = r["adaptive"].get(name, 0) + 1

    # -- derived ------------------------------------------------------------

    @property
    def event_count(self) -> int:
        return len(self.events)

    @property
    def attributed_enqueue_ns(self) -> int:
        """Enqueue wall the profile ties to operators and programs:
        equal to the ``enqueue`` site's total by construction."""
        return sum(r["enqueue_ns"] for r in self.op_rollups.values())

    def top_operators(self, n: int = 10) -> List[Dict[str, Any]]:
        """Rollups sorted by enqueue wall (then shuffle bytes), op_id
        attached under ``op_id``."""
        rows = [dict(r, op_id=op) for op, r in self.op_rollups.items()]
        rows.sort(key=lambda r: (r["enqueue_ns"], r["shuffle_bytes"]),
                  reverse=True)
        return rows[:n]

    def site(self, name: str) -> Dict[str, int]:
        return self.site_totals.get(
            name, {"count": 0, "wall_ns": 0, "bytes": 0})

    def query_record(self) -> Dict[str, Any]:
        """The JSONL event-log header line for this query (scalars only —
        the per-event lines follow it)."""
        return {
            "type": "query", "id": self.query_id, "wall_ns": self.wall_ns,
            "event_count": self.event_count, "dropped": self.dropped,
            "dropped_by_site": self.dropped_by_site,
            "session": self.session_id,
            "t0_ns": self.qt0_ns, "t1_ns": self.qt1_ns,
            "metrics": self.metrics,
        }

    def summary(self) -> str:
        """Top-of-profile text block (rapidsprof's per-query header)."""
        lines = [
            f"query {self.query_id}: wall {self.wall_ns / 1e6:.2f} ms, "
            f"{self.event_count} events ({self.dropped} dropped), "
            f"enqueue {self.attributed_enqueue_ns / 1e6:.2f} ms, "
            f"waiting on the device "
            f"{self.site('device_wait')['wall_ns'] / 1e6:.2f} ms"
        ]
        if self.dropped:
            sites = ", ".join(
                f"{s}={n}" for s, n in sorted(self.dropped_by_site.items(),
                                              key=lambda kv: -kv[1])) \
                or "unknown sites"
            lines.append(
                f"  !! TRUNCATED: {self.dropped} events dropped at the "
                f"ring ({sites}) — per-site totals undercount; raise "
                f"spark.rapids.sql.tpu.obs.ring.maxEvents")
        for r in self.top_operators(5):
            lines.append(
                f"  {r['name'] or r['op_id'] or '?'}: "
                f"{r['enqueue_ns'] / 1e6:.2f} ms enqueue, "
                f"{r['dispatches']} dispatches"
                + (f", {r['errors']} errored" if r["errors"] else ""))
        return "\n".join(lines)


def _fmt_rollup(r: Dict[str, Any], ms: Dict[str, Any]) -> str:
    parts = []
    if r:
        if r["dispatches"]:
            parts.append(f"dispatches={r['dispatches']}")
        if r["enqueue_ns"]:
            parts.append(f"enqueue={r['enqueue_ns'] / 1e6:.2f}ms")
        if r["errors"]:
            parts.append(f"errors={r['errors']}")
        if r["shuffle_bytes"]:
            parts.append(f"shuffleBytes={r['shuffle_bytes']}")
        if r["shuffle_pieces"]:
            parts.append(f"pieces={r['shuffle_pieces']}")
        if r["adaptive"]:
            parts.append("adaptive=" + ",".join(
                f"{k}x{v}" for k, v in sorted(r["adaptive"].items())))
    # per-op metric dict entries the events don't carry (AQE stats) ride
    # along from last_metrics
    for key in ("aqeCoalescedPartitions", "aqeSkewSplits"):
        v = ms.get(key)
        if v:
            parts.append(f"{key}={v}")
    return " ".join(parts) if parts else "-"


def annotate_plan(root, profile: "QueryProfile") -> str:
    """Render the physical tree with each node's rollup attached — the
    ``session.explain_last(metrics=True)`` body (the reference SQL UI's
    exec-metric annotations).  Duck-typed over PhysicalOp (``name``,
    ``op_id``, ``children``); rollups that match no tree node (e.g. the
    whole-pipeline dispatch bucket) land in a footer."""
    lines: List[str] = []
    seen: set = set()

    def walk(op, depth: int) -> None:
        op_id = getattr(op, "op_id", "")
        seen.add(op_id)
        r = profile.op_rollups.get(op_id)
        ms = profile.op_metrics.get(op_id, {})
        lines.append("  " * depth + f"{getattr(op, 'name', type(op).__name__)}"
                     f"  [{_fmt_rollup(r, ms)}]")
        for c in getattr(op, "children", ()) or ():
            walk(c, depth + 1)

    walk(root, 0)
    extras = [(op, r) for op, r in profile.op_rollups.items()
              if op not in seen]
    if extras:
        lines.append("unattributed:")
        for op, r in extras:
            lines.append(f"  {r['name'] or op}  [{_fmt_rollup(r, {})}]")
    return "\n".join(lines)
