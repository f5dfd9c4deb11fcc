"""Event-log export.

The **JSONL event log** (the Spark event-log analogue, conf
``spark.rapids.sql.tpu.obs.eventLogDir``): one ``{"type": "query"}``
header line per query followed by its ``{"type": "event"}`` lines —
append-only, so one file accumulates a session's queries and
``tools/rapidsprof.py`` post-processes it offline.  (A timeline next to
the device's operations is the profiler's: ``rapidsprof --xplane``.)

Engine-free (stdlib only) and duck-typed over events — Event objects
in-process, dicts after a JSONL round-trip.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterable, List


def _event_dict(ev) -> Dict[str, Any]:
    if isinstance(ev, dict):
        return ev
    return ev.to_dict()


def write_event_log(path: str, query_record: Dict[str, Any],
                    events: Iterable) -> None:
    """Append one query (header + events) to the JSONL log at ``path``."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    qid = query_record.get("id", 0)
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(query_record) + "\n")
        for ev in events:
            rec = dict(_event_dict(ev))
            rec["type"] = "event"
            rec["q"] = qid
            f.write(json.dumps(rec) + "\n")


def read_event_log(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL event log back into a list of query dicts, each the
    header record with its ``"events"`` list attached (rapidsprof's
    input).  Unknown/blank lines are skipped so a log a crashed process
    truncated mid-line still loads."""
    queries: List[Dict[str, Any]] = []
    by_id: Dict[Any, Dict[str, Any]] = {}
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("type") == "query":
                rec["events"] = []
                queries.append(rec)
                by_id[rec.get("id")] = rec
            elif rec.get("type") == "event":
                q = by_id.get(rec.get("q"))
                if q is not None:
                    q["events"].append(rec)
    return queries
