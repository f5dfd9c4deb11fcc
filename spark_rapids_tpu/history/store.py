"""Persistent plan-fingerprint statistics store (history/).

The cross-query half of adaptive execution: at query end the session
appends one JSONL record of runtime facts keyed by the plan fingerprint
(per-exchange row/byte counts, observed skew, spill pressure, compile
wall); before the next execution of the same fingerprint the seeding
pass (history.seeding) reads the record back to make AQE v1's runtime
decisions up front.  The store is the RAPIDS qualification/profiling
store role folded into the engine itself.

Deliberately stdlib-only with no package-relative imports:
``tools/rapidshist.py`` loads this file standalone (the same
runtime-free discipline as ``rapidslint``/``rapidsprof``), so a store
written on a TPU host can be inspected and pruned on any laptop.

Layout: ``<dir>/stats.jsonl``, append-per-query, one JSON object per
line (schema below, ``docs/history.md``).  Loads are lazy, cached per
directory and invalidated on file (mtime, size) change; the newest
record per fingerprint wins.  All module state is lock-guarded — the
store is process-shared across sessions exactly like serve/excache.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

#: Schema version stamped into every record.  v2: records additionally
#: carry ``dispatches``/``shuffle_bytes`` and the loader folds a robust
#: per-fingerprint aggregate (median/MAD over recent runs) alongside the
#: newest-wins record — the regression sentinel's baseline.
STORE_VERSION = 2

#: File the store lives in, under spark.rapids.sql.tpu.history.dir.
STORE_FILENAME = "stats.jsonl"

#: Numeric record keys folded into the per-fingerprint aggregate.
AGGREGATE_KEYS = ("wall_ns", "dispatches", "compile_count",
                  "shuffle_bytes", "spill_host_bytes", "spill_disk_bytes")

#: Per-fingerprint bound on runs the loader retains for aggregation
#: (``history.aggregateRuns`` asks for at most this many).
AGG_MAX_RUNS = 32

#: Conf-key prefixes excluded from the plan-relevant conf signature —
#: observability, history, sentinel and fault-injection knobs never
#: change the plan (faults distort a run's RUNTIME, which is exactly
#: what the regression sentinel must see compared against the same
#: fingerprint's clean baseline, not forked into a separate one).
_SIG_EXCLUDE_PREFIXES = (
    "spark.rapids.sql.tpu.obs.",
    "spark.rapids.sql.tpu.history.",
    "spark.rapids.sql.tpu.sentinel.",
    "spark.rapids.sql.tpu.faults.",
)

_lock = threading.Lock()
#: dir -> (mtime_ns, size, {fp_hash: record}, {fp_hash: [recent runs]})
_cache: Dict[str, Tuple[int, int, Dict[str, dict],
                        Dict[str, List[dict]]]] = {}
_stats = {
    "history_store_queries": 0,
    "history_store_hits": 0,
    "history_store_appends": 0,
}


def fingerprint_hash(fingerprint: str) -> str:
    """Stable short hash of a plan-fingerprint string (store key)."""
    return hashlib.sha1(fingerprint.encode("utf-8")).hexdigest()[:16]


def conf_signature(settings: Iterable[Tuple[str, Any]]) -> str:
    """Hash of the plan-relevant conf items.

    Seeded decisions recorded under one configuration must not leak
    into sessions planned under another, so records carry this
    signature and lookups require it to match.  The
    ``_SIG_EXCLUDE_PREFIXES`` families are excluded — they never alter
    the plan.
    """
    items = sorted((k, str(v)) for k, v in settings
                   if not k.startswith(_SIG_EXCLUDE_PREFIXES))
    blob = "\x1f".join(f"{k}\x1e{v}" for k, v in items)
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:16]


def store_path(dir_path: str) -> str:
    return os.path.join(dir_path, STORE_FILENAME)


def _parse_lines(path: str) -> List[dict]:
    records: List[dict] = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail write — ignore the line
                if isinstance(rec, dict) and rec.get("fp"):
                    records.append(rec)
    except OSError:
        return []
    return records


def _fold(records: List[dict], max_records: int
          ) -> Tuple[Dict[str, dict], Dict[str, List[dict]]]:
    """(newest record per fingerprint, recent runs per fingerprint);
    overall bounded to max_records newest (file order is append order,
    so later lines are newer); per-fingerprint runs bounded to
    AGG_MAX_RUNS newest."""
    if max_records and max_records > 0:
        records = records[-max_records:]
    folded: Dict[str, dict] = {}
    runs: Dict[str, List[dict]] = {}
    for rec in records:  # later lines overwrite earlier ones
        fp = str(rec["fp"])
        folded[fp] = rec
        lst = runs.setdefault(fp, [])
        lst.append(rec)
        if len(lst) > AGG_MAX_RUNS:
            del lst[0]
    return folded, runs


def _load_all(dir_path: str, max_records: int = 0
              ) -> Tuple[Dict[str, dict], Dict[str, List[dict]]]:
    """Load (cached) both fold shapes for a store dir."""
    path = store_path(dir_path)
    try:
        st = os.stat(path)
        stamp = (st.st_mtime_ns, st.st_size)
    except OSError:
        with _lock:
            _cache.pop(dir_path, None)
        return {}, {}
    with _lock:
        cached = _cache.get(dir_path)
        if cached is not None and (cached[0], cached[1]) == stamp:
            return cached[2], cached[3]
    folded, runs = _fold(_parse_lines(path), max_records)
    with _lock:
        _cache[dir_path] = (stamp[0], stamp[1], folded, runs)
    return folded, runs


def load(dir_path: str, max_records: int = 0) -> Dict[str, dict]:
    """Load (cached) the folded {fp_hash: record} map for a store dir."""
    return _load_all(dir_path, max_records)[0]


def runs_for(dir_path: str, fp_hash: str, conf_sig: str = "",
             max_records: int = 0) -> List[dict]:
    """The retained recent runs of one fingerprint, oldest first,
    restricted to ``conf_sig`` when given (a run recorded under a
    different plan-relevant configuration is a different workload)."""
    runs = _load_all(dir_path, max_records)[1].get(fp_hash, [])
    if conf_sig:
        runs = [r for r in runs if r.get("conf_sig") == conf_sig]
    return runs


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    if not n:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def aggregate_records(recs: List[dict]) -> Dict[str, Any]:
    """Fold a run list into the sentinel's baseline shape:
    ``{"n": len(recs), "keys": {key: {"median", "mad"}}}`` for every
    AGGREGATE_KEYS key."""
    keys: Dict[str, Dict[str, float]] = {}
    for key in AGGREGATE_KEYS:
        vals = [float(r.get(key, 0) or 0) for r in recs]
        med = _median(vals)
        mad = _median([abs(v - med) for v in vals])
        keys[key] = {"median": med, "mad": mad}
    return {"n": len(recs), "keys": keys}


def aggregate(dir_path: str, fp_hash: str, conf_sig: str = "",
              runs: int = 8, max_records: int = 0) -> Dict[str, Any]:
    """Robust aggregate over the last ``runs`` retained runs of a
    fingerprint — the regression sentinel's baseline, also shown by
    ``rapidshist --json``."""
    recs = runs_for(dir_path, fp_hash, conf_sig, max_records)
    if runs and runs > 0:
        recs = recs[-runs:]
    return aggregate_records(recs)


def lookup(dir_path: str, fp_hash: str, conf_sig: str,
           max_age_sec: float = 0.0, max_records: int = 0,
           now: Optional[float] = None) -> Optional[dict]:
    """Fetch the newest fresh record for a fingerprint, or None.

    Freshness: the record's conf signature must equal ``conf_sig`` and,
    when ``max_age_sec > 0``, its timestamp must be within the horizon.
    A miss (absent or stale) is the seeding pass's signal to degrade to
    exactly the unseeded plan.
    """
    with _lock:
        _stats["history_store_queries"] += 1
    rec = load(dir_path, max_records).get(fp_hash)
    if rec is None:
        return None
    if conf_sig and rec.get("conf_sig") != conf_sig:
        return None
    if max_age_sec and max_age_sec > 0:
        ts = float(rec.get("ts", 0.0) or 0.0)
        if (now if now is not None else time.time()) - ts > max_age_sec:
            return None
    with _lock:
        _stats["history_store_hits"] += 1
    return rec


def append(dir_path: str, record: dict) -> None:
    """Append one query record; creates the dir/file on first write."""
    record = dict(record)
    record.setdefault("v", STORE_VERSION)
    record.setdefault("ts", time.time())
    path = store_path(dir_path)
    line = json.dumps(record, sort_keys=True, separators=(",", ":"))
    with _lock:
        os.makedirs(dir_path, exist_ok=True)
        with open(path, "a", encoding="utf-8") as f:
            f.write(line + "\n")
        _cache.pop(dir_path, None)  # force reload on next lookup
        _stats["history_store_appends"] += 1


def prune(dir_path: str, max_records: int) -> Tuple[int, int]:
    """Rewrite the store keeping the newest record per fingerprint,
    bounded to the ``max_records`` newest overall.  Returns
    (records_before, records_after).  Used by tools/rapidshist.py."""
    path = store_path(dir_path)
    records = _parse_lines(path)
    before = len(records)
    folded = _fold(records, max_records)[0]
    # preserve append order among survivors
    keep_ids = {id(rec) for rec in folded.values()}
    survivors = [rec for rec in records if id(rec) in keep_ids]
    with _lock:
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            for rec in survivors:
                f.write(json.dumps(rec, sort_keys=True,
                                   separators=(",", ":")) + "\n")
        os.replace(tmp, path)
        _cache.pop(dir_path, None)
    return before, len(survivors)


def stats() -> Dict[str, int]:
    """Process-cumulative store counters (serve stats() rollup keys)."""
    with _lock:
        return dict(_stats)


def reset_stats() -> None:
    with _lock:
        for k in _stats:
            _stats[k] = 0


def invalidate_cache(dir_path: Optional[str] = None) -> None:
    with _lock:
        if dir_path is None:
            _cache.clear()
        else:
            _cache.pop(dir_path, None)
