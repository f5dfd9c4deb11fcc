"""Network front door (serve/): out-of-process serving over TCP.

:class:`ServeScheduler` gives *in-process* callers weighted fairness,
deadlines and micro-batching; everything still rode in one Python
process.  :class:`FrontDoorServer` puts a long-lived, stdlib-only
(``socketserver``) network face on that same scheduler so clients in
other processes — other languages, even — get the identical guarantees
over the newline-delimited JSON protocol of
:mod:`~spark_rapids_tpu.serve.protocol`:

* one ``ServeScheduler`` (and hence one Session, one shared plan
  cache, one device runtime) behind any number of connections — the
  second client's repeat of the first client's query compiles nothing
  (``compileCount == 0``);
* a **result cache** (:mod:`~spark_rapids_tpu.serve.resultcache`):
  a repeat query over unchanged inputs answers from catalog-registered
  spillables with zero compiles AND zero dispatches — the request
  never enters ``session.execute``;
* **sentinel-driven admission control**: before executing, the front
  door consults the history store's median/MAD wall-time aggregate for
  the query's fingerprint; a query whose *predicted* latency already
  misses its deadline is shed immediately (DeadlineExceeded classification,
  counted per tenant) instead of burning device time on a doomed run —
  the serving analogue of the PR-15 regression sentinel, pointed
  forward instead of backward.

Request handling is thread-per-connection (``ThreadingTCPServer``,
daemon threads); every accept/read wait is a bounded <=0.25s slice
(``serve_forever(poll_interval=...)`` + socket timeouts in
protocol.LineChannel), honoring the R2/R3 blocking discipline.
Observability: per-request spans on the ``serve.frontend`` site,
connection/queue gauges, and per-tenant queue/inflight/deadline-miss
gauges (registered by the scheduler) in the Prometheus export.
"""

from __future__ import annotations

import socketserver
import threading
import time
from typing import Any, Dict, Optional, Tuple

from spark_rapids_tpu.serve import protocol
from spark_rapids_tpu.serve.resultcache import (
    ResultCache, cache_key, result_cache,
)
from spark_rapids_tpu.serve.scheduler import DeadlineExceeded, ServeScheduler

_WAIT_SLICE_S = 0.25


def _error_class(e: BaseException) -> str:
    """The fault-classification name for the wire (fault/errors discipline):
    prefer the exception's declared rapids_error_class context, fall
    back to the exception type name."""
    if isinstance(e, DeadlineExceeded):
        return "DeadlineExceeded"
    if isinstance(e, protocol.ProtocolError):
        return "ProtocolError"
    return type(e).__name__


class _Handler(socketserver.BaseRequestHandler):
    """One connection: a request/response loop until EOF."""

    def handle(self) -> None:
        server: "FrontDoorServer" = self.server.front_door  # type: ignore
        chan = protocol.LineChannel(self.request, max_line=server.max_line)
        server._conn_delta(+1)
        try:
            while not server._closing.is_set():
                try:
                    req = chan.recv(timeout=_WAIT_SLICE_S)
                except TimeoutError:
                    continue  # idle connection; re-check _closing
                except protocol.ProtocolError as e:
                    chan.send({"ok": False, "error": str(e),
                               "error_class": "ProtocolError"})
                    return  # framing is gone; the stream can't recover
                if req is None:
                    return  # clean EOF
                chan.send(server.handle_request(req))
        except OSError:
            pass  # peer vanished mid-response; nothing to tell it
        finally:
            server._conn_delta(-1)
            chan.close()


class _TCPServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True
    front_door: "FrontDoorServer"


class FrontDoorServer:
    """The serve front door: a TCP listener feeding one ServeScheduler.

    >>> server = FrontDoorServer(session)
    >>> server.start()
    >>> server.port  # 0 in conf -> ephemeral; read the bound port here
    >>> ...
    >>> server.close()

    ``scheduler`` may be passed in (tests share one with in-process
    submitters); otherwise one is built over ``session``.  Use as a
    context manager or call :meth:`close`."""

    def __init__(self, session, scheduler: Optional[ServeScheduler] = None,
                 cache: Optional[ResultCache] = None):
        from spark_rapids_tpu.config import (
            SERVE_ADMISSION_ENABLED, SERVE_ADMISSION_MAD_K,
            SERVE_ADMISSION_MIN_RUNS, SERVE_FRONTEND_HOST,
            SERVE_FRONTEND_MAX_LINE, SERVE_FRONTEND_PORT,
            SERVE_RESULT_CACHE_ENABLED, SERVE_RESULT_CACHE_MAX_BYTES,
            SERVE_RESULT_CACHE_MAX_ENTRIES,
            SERVE_RESULT_CACHE_MIN_NS_PER_BYTE,
        )
        self.session = session
        self.conf = session.conf
        self.scheduler = scheduler or ServeScheduler(session)
        self.host = SERVE_FRONTEND_HOST.get(self.conf)
        self._conf_port = SERVE_FRONTEND_PORT.get(self.conf)
        self.max_line = SERVE_FRONTEND_MAX_LINE.get(self.conf)
        self._cache_enabled = SERVE_RESULT_CACHE_ENABLED.get(self.conf)
        self.cache = cache or result_cache()
        self.cache.configure(
            SERVE_RESULT_CACHE_MAX_ENTRIES.get(self.conf),
            SERVE_RESULT_CACHE_MAX_BYTES.get(self.conf),
            SERVE_RESULT_CACHE_MIN_NS_PER_BYTE.get(self.conf))
        self._admission_enabled = SERVE_ADMISSION_ENABLED.get(self.conf)
        self._admission_min_runs = SERVE_ADMISSION_MIN_RUNS.get(self.conf)
        self._admission_mad_k = SERVE_ADMISSION_MAD_K.get(self.conf)
        self._templates: Dict[str, Any] = {}
        self._lock = threading.Lock()
        self._connections = 0
        self._requests = 0
        self._admission_shed = 0
        self._admission_shed_by_tenant: Dict[str, int] = {}
        self._closing = threading.Event()
        self._tcp: Optional[_TCPServer] = None
        self._accept_thread: Optional[threading.Thread] = None

    # -- lifecycle ----------------------------------------------------------

    @property
    def port(self) -> int:
        """The bound port (valid after start())."""
        return self._tcp.server_address[1] if self._tcp else self._conf_port

    def start(self) -> "FrontDoorServer":
        if self._tcp is not None:
            return self
        self.scheduler.start()
        self._tcp = _TCPServer((self.host, self._conf_port), _Handler)
        self._tcp.front_door = self
        self._accept_thread = threading.Thread(
            # poll_interval bounds the accept wait (R3 slice): close()
            # is observed within one slice
            target=lambda: self._tcp.serve_forever(
                poll_interval=_WAIT_SLICE_S),
            daemon=True, name="serve-frontend-accept")
        self._accept_thread.start()
        from spark_rapids_tpu.obs import timeseries as obs_ts
        obs_ts.register_gauge("serve.frontend.connections",
                              lambda: float(self._connections))
        obs_ts.register_gauge("serve.frontend.requests",
                              lambda: float(self._requests))
        obs_ts.register_gauge("serve.frontend.admission_shed",
                              lambda: float(self._admission_shed))
        return self

    def close(self, timeout: float = 10.0) -> None:
        """Stop accepting, then stop the scheduler.  In-flight handler
        threads notice ``_closing`` within one wait slice."""
        self._closing.set()
        if self._tcp is not None:
            self._tcp.shutdown()
            self._tcp.server_close()
        t = self._accept_thread
        if t is not None:
            deadline = time.monotonic() + timeout
            while t.is_alive() and time.monotonic() < deadline:
                t.join(_WAIT_SLICE_S)
        self.scheduler.close(timeout=timeout)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()
        return False

    # -- templates ----------------------------------------------------------

    def register_template(self, template) -> None:
        """Expose a QueryTemplate to wire clients under its key."""
        with self._lock:
            self._templates[template.key] = template

    # -- request handling ---------------------------------------------------

    def _conn_delta(self, d: int) -> None:
        with self._lock:
            self._connections += d

    def handle_request(self, req: Dict[str, Any]) -> Dict[str, Any]:
        """One wire request -> one wire response (never raises; every
        failure becomes an ``ok: false`` response)."""
        from spark_rapids_tpu.utils.tracing import span
        with self._lock:
            self._requests += 1
        op = req.get("op")
        with span("serve.frontend", f"op_{op}", "serve") as sp:
            try:
                if op == "submit":
                    resp = self._handle_submit(req)
                elif op == "stats":
                    resp = {"ok": True, "scheduler": self.scheduler.stats(),
                            "frontend": self.stats()}
                elif op == "drain":
                    resp = self._handle_drain(req)
                elif op == "ping":
                    resp = {"ok": True}
                else:
                    resp = {"ok": False, "error": f"unknown op: {op!r}",
                            "error_class": "ProtocolError"}
            except Exception as e:
                # a failed request must not take down the connection loop
                resp = {"ok": False, "error": f"{type(e).__name__}: {e}",
                        "error_class": _error_class(e)}
            sp.set(ok=bool(resp.get("ok")))
        return resp

    def _handle_drain(self, req: Dict[str, Any]) -> Dict[str, Any]:
        drained = self.scheduler.drain(
            timeout=float(req.get("timeout", 60.0)))
        rt = self.session.runtime
        held = rt.semaphore.held_depth() if rt is not None else 0
        return {"ok": True, "drained": drained, "held_depth": held}

    def _handle_submit(self, req: Dict[str, Any]) -> Dict[str, Any]:
        tenant = str(req.get("tenant", "default"))
        deadline_sec = float(req.get("deadline_sec", 0.0))
        encoding = str(req.get("encoding", "json"))
        if req.get("template") is not None:
            return self._submit_template(req, tenant, deadline_sec,
                                         encoding)
        sql = req.get("sql")
        if not isinstance(sql, str) or not sql.strip():
            raise protocol.ProtocolError("submit needs 'sql' or 'template'")
        # a parse a request: the shared plan cache (serve/excache) keys
        # on the plan's SHAPE and lives by LRU, so the text's executables
        # are found again with nothing pinned here
        plan = self.session.sql(sql).plan
        key = cache_key(self.session, plan)
        use_cache = self._cache_enabled and bool(req.get("cache", True)) \
            and key[2] is not None
        if use_cache:
            hit = self.cache.fetch(key)
            if hit is not None:
                # answered without entering session.execute: zero
                # compiles, zero dispatches, zero scheduler queueing —
                # and no admission check, since the prediction models
                # the execution a hit never performs
                return {"ok": True,
                        "result": protocol.batch_to_wire(hit, encoding),
                        "metrics": {"resultCacheHits": 1,
                                    "admissionShed": 0,
                                    "compileCount": 0,
                                    "dispatchCount": 0}}
        shed = self._admission_check(key, tenant, deadline_sec)
        if shed is not None:
            return shed
        t0_ns = time.monotonic_ns()
        # wire deadline 0 means "none requested": fall back to the
        # scheduler's conf default rather than forcing deadline-free
        fut = self.scheduler.submit(
            plan, tenant=tenant,
            deadline_sec=deadline_sec if deadline_sec > 0 else None)
        out = fut.result(
            timeout=deadline_sec + 30.0 if deadline_sec > 0 else 600.0)
        wall_ns = time.monotonic_ns() - t0_ns
        if use_cache:
            # submit->result wall as the recorded compute cost: it
            # includes queueing, which is the latency a cache hit
            # actually saves the next client
            self.cache.insert(key, plan, out, wall_ns, self.conf)
        metrics = dict(fut.metrics or {})
        metrics.setdefault("resultCacheHits", 0)
        metrics.setdefault("admissionShed", 0)
        return {"ok": True,
                "result": protocol.batch_to_wire(out, encoding),
                "metrics": metrics}

    def _submit_template(self, req: Dict[str, Any], tenant: str,
                         deadline_sec: float, encoding: str
                         ) -> Dict[str, Any]:
        # template path: no result cache (each request carries fresh
        # in-memory rows, so the input identity never repeats) and no
        # admission prediction (micro-batch latency is dominated by the
        # coalescing linger, which history's per-query walls don't model)
        name = str(req.get("template"))
        with self._lock:
            template = self._templates.get(name)
        if template is None:
            raise protocol.ProtocolError(f"unknown template: {name!r}")
        batch = protocol.wire_to_batch(req.get("batch") or {})
        fut = self.scheduler.submit_micro(
            template, batch, tenant=tenant,
            deadline_sec=deadline_sec if deadline_sec > 0 else None)
        out = fut.result(
            timeout=deadline_sec + 30.0 if deadline_sec > 0 else 600.0)
        metrics = dict(fut.metrics or {})
        metrics.setdefault("resultCacheHits", 0)
        metrics.setdefault("admissionShed", 0)
        return {"ok": True,
                "result": protocol.batch_to_wire(out, encoding),
                "metrics": metrics}

    def _admission_check(self, key: Tuple[str, str, Optional[str]],
                         tenant: str, deadline_sec: float
                         ) -> Optional[Dict[str, Any]]:
        """Shed-before-execute: None to admit, or the error response
        for a query whose predicted wall already misses its deadline."""
        if not self._admission_enabled or deadline_sec <= 0:
            return None
        from spark_rapids_tpu.history import predicted_wall_ns
        pred_ns = predicted_wall_ns(
            self.conf, key[0], key[1],
            min_runs=self._admission_min_runs,
            mad_k=self._admission_mad_k)
        if pred_ns is None or pred_ns / 1e9 <= deadline_sec:
            return None
        self.scheduler.record_shed(tenant)
        with self._lock:
            self._admission_shed += 1
            self._admission_shed_by_tenant[tenant] = \
                self._admission_shed_by_tenant.get(tenant, 0) + 1
        from spark_rapids_tpu.obs import events as obs_events
        obs_events.emit_instant("serve.frontend", "admission_shed", "serve",
                                tenant=tenant, fp=key[0],
                                predicted_ms=pred_ns / 1e6,
                                deadline_ms=deadline_sec * 1e3)
        return {"ok": False,
                "error": (f"admission control: predicted wall "
                          f"{pred_ns / 1e9:.3f}s exceeds deadline "
                          f"{deadline_sec:g}s for tenant {tenant!r}"),
                "error_class": "DeadlineExceeded", "shed": True,
                "metrics": {"admissionShed": 1, "resultCacheHits": 0}}

    # -- stats --------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = {
                "connections": self._connections,
                "requests": self._requests,
                "admission_shed": self._admission_shed,
                "admission_shed_by_tenant":
                    dict(self._admission_shed_by_tenant),
            }
        out.update(self.cache.stats())
        return out
