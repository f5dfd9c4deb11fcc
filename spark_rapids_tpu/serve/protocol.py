"""Front-door wire protocol (serve/): newline-delimited JSON over TCP.

One request per line, one response per line, UTF-8 JSON with no
embedded newlines — trivially speakable from any language (`nc` included)
while still carrying columnar payloads.  Requests and responses share
one batch encoding so a client can both send template rows and receive
results:

* ``json`` — ``{"encoding": "json", "names": [...], "types": [...],
  "data": {col: [values...]}}``; type names are the engine's
  ``DataType.name`` strings (``long``, ``double``, ``string``, ...),
  values are plain JSON scalars with ``null`` for SQL NULL.
* ``arrow`` — the same ``names``/``types`` plus ``ipc_b64``: a
  base64-encoded Arrow IPC stream.  Used only when pyarrow is
  importable on both ends; the server silently falls back to ``json``
  when a client asks for arrow it cannot produce.

Requests (``op`` field): ``submit`` (``sql`` text or ``template`` name
+ ``batch``, with ``tenant``, ``deadline_sec``, ``cache``,
``encoding``), ``stats``, ``drain``, ``ping``.  Responses carry
``ok``; a submit response adds ``result`` (encoded batch) and
``metrics`` (the query's camelCase metrics dict plus the front door's
``resultCacheHits``/``admissionShed``), or on failure ``error`` +
``error_class`` (the fault classification name — ``DeadlineExceeded`` for
deadline/admission sheds).

Blocking discipline: every socket read waits in bounded <=0.25s slices
(lint rule R3's contract) under an overall per-call deadline, so a
drain or watchdog async-exc can always land on a serving thread.
"""

from __future__ import annotations

import base64
import json
import socket
import time
from typing import Any, Dict, Optional, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import HostBatch

_WAIT_SLICE_S = 0.25
DEFAULT_MAX_LINE = 64 << 20


class ProtocolError(RuntimeError):
    """Malformed or oversized protocol traffic."""


class FrontDoorError(RuntimeError):
    """A server-side failure relayed to the client.

    ``error_class`` carries the server's fault-classification class name so
    callers can branch without string-matching messages."""

    def __init__(self, message: str, error_class: str = ""):
        super().__init__(message)
        self.error_class = error_class


def have_arrow() -> bool:
    try:
        import pyarrow  # noqa: F401
        return True
    except ImportError:
        return False


# -- batch <-> wire ----------------------------------------------------------


def batch_to_wire(batch: HostBatch, encoding: str = "json"
                  ) -> Dict[str, Any]:
    """Encode a HostBatch for one protocol line."""
    names = list(batch.schema.names)
    type_names = [f.dtype.name for f in batch.schema.fields]
    if encoding == "arrow" and have_arrow():
        import pyarrow as pa
        data = batch.to_pydict()
        table = pa.table({n: data[n] for n in names})
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, table.schema) as writer:
            writer.write_table(table)
        return {"encoding": "arrow", "names": names, "types": type_names,
                "ipc_b64": base64.b64encode(
                    sink.getvalue().to_pybytes()).decode("ascii")}
    return {"encoding": "json", "names": names, "types": type_names,
            "data": batch.to_pydict()}


def wire_to_batch(obj: Dict[str, Any]) -> HostBatch:
    """Decode one protocol batch object back into a HostBatch."""
    names = obj.get("names") or []
    type_names = obj.get("types") or []
    if len(names) != len(type_names):
        raise ProtocolError("batch names/types length mismatch")
    if obj.get("encoding") == "arrow":
        import pyarrow as pa
        buf = base64.b64decode(obj["ipc_b64"])
        with pa.ipc.open_stream(pa.BufferReader(buf)) as reader:
            table = reader.read_all()
        data = {c: table.column(c).to_pylist() for c in table.column_names}
    else:
        data = obj.get("data") or {}
    return HostBatch.from_pydict({
        name: (T.type_from_name(tn), data.get(name, []))
        for name, tn in zip(names, type_names)})


# -- line transport ----------------------------------------------------------


def encode_line(obj: Dict[str, Any]) -> bytes:
    return json.dumps(obj, separators=(",", ":")).encode("utf-8") + b"\n"


class LineChannel:
    """Newline-delimited JSON over one socket, both directions.

    Reads wait in bounded slices (socket timeout = 0.25s) under the
    per-call ``timeout`` so the owning thread stays interruptible."""

    def __init__(self, sock: socket.socket,
                 max_line: int = DEFAULT_MAX_LINE):
        self._sock = sock
        self._buf = bytearray()
        self._max_line = max(1024, int(max_line))
        self._sock.settimeout(_WAIT_SLICE_S)

    def send(self, obj: Dict[str, Any]) -> None:
        self._sock.sendall(encode_line(obj))

    def recv(self, timeout: float = 60.0) -> Optional[Dict[str, Any]]:
        """One decoded message; None on clean EOF; TimeoutError past
        ``timeout``; ProtocolError on junk or an oversized line."""
        deadline = time.monotonic() + max(0.0, float(timeout))
        while True:
            nl = self._buf.find(b"\n")
            if nl >= 0:
                raw = bytes(self._buf[:nl])
                del self._buf[:nl + 1]
                if not raw.strip():
                    continue
                try:
                    msg = json.loads(raw.decode("utf-8"))
                except (UnicodeDecodeError, json.JSONDecodeError) as e:
                    raise ProtocolError(f"bad protocol line: {e}")
                if not isinstance(msg, dict):
                    raise ProtocolError("protocol line is not an object")
                return msg
            if len(self._buf) > self._max_line:
                raise ProtocolError(
                    f"protocol line exceeds {self._max_line} bytes")
            try:
                chunk = self._sock.recv(1 << 16)
            except socket.timeout:
                chunk = None
            except OSError:
                return None  # peer reset / socket closed under us
            if chunk == b"":
                return None  # clean EOF
            if chunk:
                self._buf.extend(chunk)
            elif time.monotonic() >= deadline:
                raise TimeoutError(
                    f"no complete protocol line within {timeout:g}s")

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


# -- client ------------------------------------------------------------------


class FrontDoorClient:
    """Out-of-process client of one serve front door.

    >>> c = FrontDoorClient("127.0.0.1", port)
    >>> rows, metrics = c.submit_sql("SELECT k, SUM(v) AS s "
    ...                              "FROM events GROUP BY k")
    >>> c.close()

    One request in flight per client (the protocol is strictly
    request/response per connection); open one client per concurrent
    stream.  Context-manager friendly."""

    def __init__(self, host: str, port: int, timeout: float = 120.0,
                 max_line: int = DEFAULT_MAX_LINE):
        self._timeout = float(timeout)
        sock = socket.create_connection((host, port), timeout=10.0)
        self._chan = LineChannel(sock, max_line=max_line)

    def _rpc(self, req: Dict[str, Any],
             timeout: Optional[float] = None) -> Dict[str, Any]:
        self._chan.send(req)
        resp = self._chan.recv(
            self._timeout if timeout is None else timeout)
        if resp is None:
            raise FrontDoorError("server closed the connection",
                                 "ConnectionClosed")
        if not resp.get("ok", False):
            msg = str(resp.get("error", "front door error"))
            klass = str(resp.get("error_class", ""))
            if klass == "DeadlineExceeded":
                from spark_rapids_tpu.serve.scheduler import DeadlineExceeded
                raise DeadlineExceeded(msg)
            raise FrontDoorError(msg, klass)
        return resp

    def submit_sql(self, sql: str, tenant: str = "default",
                   deadline_sec: float = 0.0, cache: bool = True,
                   encoding: str = "json",
                   timeout: Optional[float] = None
                   ) -> Tuple[HostBatch, Dict[str, Any]]:
        """Execute ``sql`` on the server; (rows, metrics)."""
        resp = self._rpc({"op": "submit", "sql": sql, "tenant": tenant,
                          "deadline_sec": float(deadline_sec),
                          "cache": bool(cache), "encoding": encoding},
                         timeout=timeout)
        return wire_to_batch(resp["result"]), dict(resp.get("metrics") or {})

    def submit_template(self, template: str, batch: HostBatch,
                        tenant: str = "default", deadline_sec: float = 0.0,
                        encoding: str = "json",
                        timeout: Optional[float] = None
                        ) -> Tuple[HostBatch, Dict[str, Any]]:
        """Run a server-registered micro-query template over ``batch``
        (eligible for server-side coalescing); (rows, metrics)."""
        resp = self._rpc({"op": "submit", "template": template,
                          "batch": batch_to_wire(batch, encoding),
                          "tenant": tenant,
                          "deadline_sec": float(deadline_sec),
                          "encoding": encoding}, timeout=timeout)
        return wire_to_batch(resp["result"]), dict(resp.get("metrics") or {})

    def stats(self) -> Dict[str, Any]:
        resp = self._rpc({"op": "stats"})
        return {"scheduler": resp.get("scheduler", {}),
                "frontend": resp.get("frontend", {})}

    def drain(self, timeout: float = 60.0) -> Dict[str, Any]:
        resp = self._rpc({"op": "drain", "timeout": float(timeout)},
                         timeout=timeout + 30.0)
        return {"drained": bool(resp.get("drained", False)),
                "held_depth": int(resp.get("held_depth", 0))}

    def ping(self) -> bool:
        return bool(self._rpc({"op": "ping"}).get("ok", False))

    def close(self) -> None:
        self._chan.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
