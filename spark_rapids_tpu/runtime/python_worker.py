"""Python-worker admission + out-of-process execution for pandas execs.

Reference analogues:
* PythonWorkerSemaphore (python/PythonWorkerSemaphore.scala:97) — bounds
  how many python workers may run concurrently so python memory stays
  within ``spark.rapids.python.concurrentPythonWorkers``.
* GpuArrowPythonRunner (GpuArrowEvalPythonExec.scala:365) + the patched
  worker (python/rapids/worker.py:22-67) — user python runs in a SEPARATE
  worker process, batches stream to/from it over Arrow IPC, and the device
  semaphore is released while the worker runs so TPU slots are not held
  hostage by slow python.

Here :func:`run_python_task` forks a worker per partition task (fork, not
spawn: pandas UDFs are arbitrary closures — fork inherits them without
cloudpickle).  Batches stream over pipes as length-prefixed frames of the
engine's native batch serializer (native/batch_runtime.cc — the project's
Arrow-IPC-analogue wire format, the same one the spill tiers use).  A
worker crash surfaces as :class:`PythonWorkerError` on the task, never a
hang, and leaves the engine reusable.
"""

from __future__ import annotations

import contextlib
import os
import struct
import threading
from typing import Optional

from spark_rapids_tpu.config import RapidsConf, conf_bool, conf_int

CONCURRENT_PYTHON_WORKERS = conf_int(
    "spark.rapids.python.concurrentPythonWorkers", 4,
    "Concurrent python (pandas UDF / pandas exec) evaluations allowed "
    "per process (PythonWorkerSemaphore analogue).")
PYTHON_OOP_ENABLED = conf_bool(
    "spark.rapids.python.outOfProcess.enabled", True,
    "Run pandas UDF / pandas-exec python in a forked worker process, "
    "streaming batches over framed IPC pipes (GpuArrowPythonRunner "
    "analogue): user code is isolated from the engine process and the "
    "device semaphore is released while it runs.  Off = in-process.")

_lock = threading.Lock()
_sem: Optional[threading.Semaphore] = None
_sem_permits = 0


def _semaphore(conf: RapidsConf) -> threading.Semaphore:
    global _sem, _sem_permits
    with _lock:
        permits = max(1, CONCURRENT_PYTHON_WORKERS.get(conf))
        if _sem is None or permits != _sem_permits:
            _sem = threading.Semaphore(permits)
            _sem_permits = permits
        return _sem


@contextlib.contextmanager
def python_worker_slot(ctx):
    """Bound python concurrency; release the device semaphore while python
    runs (the GpuSemaphore release in GpuArrowEvalPythonExec.scala:484).

    Only a permit this thread actually HOLDS is released/re-acquired —
    release() at depth 0 is a no-op, so blindly re-acquiring afterwards
    would leak a permit and eventually deadlock device admission.
    """
    sem = _semaphore(ctx.conf)
    released_device = False
    if ctx.semaphore is not None and \
            getattr(ctx.semaphore, "task_depth", lambda: 0)() > 0:
        ctx.semaphore.release()
        released_device = True
    sem.acquire()
    try:
        yield
    finally:
        sem.release()
        if released_device:
            ctx.semaphore.acquire()


class PythonWorkerError(RuntimeError):
    """A python worker task failed or its process died."""


# frame tags on both pipes
_MSG_BATCH = 0
_MSG_END = 1
_MSG_ERROR = 2

# pid of the most recent worker (observable by tests: != engine pid)
last_worker_pid: Optional[int] = None


def _write_frame(fd: int, tag: int, schema_idx: int, payload: bytes):
    buf = struct.pack("<BBI", tag, schema_idx, len(payload)) + payload
    view = memoryview(buf)
    while view:
        n = os.write(fd, view)
        view = view[n:]


def _read_exact(fd: int, n: int) -> Optional[bytes]:
    """Read exactly n bytes; None on clean EOF at a frame boundary."""
    chunks = []
    got = 0
    while got < n:
        b = os.read(fd, n - got)
        if not b:
            return None if not chunks else b"".join(chunks)
        chunks.append(b)
        got += len(b)
    return b"".join(chunks)


def run_python_task(ctx, task, inputs, in_schemas, out_schema):
    """Execute ``task`` in a forked worker process, streaming batches both
    ways (GpuArrowPythonRunner / python/rapids/worker.py analogue).

    ``task``: Callable[[Iterator[(schema_idx, HostBatch)]], Iterator[HostBatch]]
    — runs IN THE WORKER; receives the streamed inputs, yields outputs.
    ``inputs``: iterable of (schema_idx, HostBatch) streamed to the worker.
    ``in_schemas``: schema per index (deserialization in the worker).
    Yields output HostBatches as they stream back.  The python-worker
    semaphore bounds concurrent workers; the device semaphore is released
    for the worker's lifetime.  A dead worker raises PythonWorkerError.
    """
    from spark_rapids_tpu.native_rt import (
        deserialize_host_batch, serialize_host_batch,
    )
    if not PYTHON_OOP_ENABLED.get(ctx.conf):
        with python_worker_slot(ctx):
            yield from task(iter(inputs))
        return

    with python_worker_slot(ctx):
        in_r, in_w = os.pipe()
        out_r, out_w = os.pipe()
        import warnings
        with warnings.catch_warnings():
            # deliberate: fork is the only way to ship arbitrary UDF
            # closures without cloudpickle; the child never touches JAX
            # or its locks (numpy/pandas/ctypes only) and exits via
            # os._exit, so the generic fork-vs-threads warnings from
            # python 3.12 and jax's at-fork hook do not apply
            warnings.filterwarnings("ignore", category=DeprecationWarning)
            warnings.filterwarnings("ignore", category=RuntimeWarning,
                                    message=".*fork.*")
            pid = os.fork()
        if pid == 0:  # ---- worker ----
            try:
                os.close(in_w)
                os.close(out_r)

                def input_iter():
                    while True:
                        hdr = _read_exact(in_r, 6)
                        if hdr is None or len(hdr) < 6:
                            return
                        tag, sidx, ln = struct.unpack("<BBI", hdr)
                        if tag == _MSG_END:
                            return
                        payload = _read_exact(in_r, ln) if ln else b""
                        yield sidx, deserialize_host_batch(
                            payload, in_schemas[sidx])

                for hb in task(input_iter()):
                    _write_frame(out_w, _MSG_BATCH, 0,
                                 serialize_host_batch(hb))
                _write_frame(out_w, _MSG_END, 0, b"")
                os._exit(0)
            except BaseException:
                import traceback
                try:
                    _write_frame(out_w, _MSG_ERROR, 0,
                                 traceback.format_exc().encode())
                except BaseException:
                    pass
                os._exit(1)

        # ---- engine side ----
        global last_worker_pid
        last_worker_pid = pid
        os.close(in_r)
        os.close(out_w)

        feed_error = []
        # the feeder drives the upstream plan: under the query's bound
        # literal parameters, like the thread that spawned it
        from spark_rapids_tpu.utils import params
        bound = params.current()

        def feed():
            try:
                with params.executing(bound):
                    for sidx, hb in inputs:
                        _write_frame(in_w, _MSG_BATCH, sidx,
                                     serialize_host_batch(hb))
                _write_frame(in_w, _MSG_END, 0, b"")
            except BrokenPipeError:
                pass  # worker died; the read loop reports it
            except BaseException as e:  # UPSTREAM failure (scan, expr...)
                # must reach the consumer — a swallowed upstream error
                # would look like clean EOF to the worker and surface as
                # silently truncated results
                feed_error.append(e)
            finally:
                try:
                    os.close(in_w)
                except OSError:
                    pass

        feeder = threading.Thread(target=feed, daemon=True)
        feeder.start()
        reaped = False
        try:
            while True:
                hdr = _read_exact(out_r, 6)
                if hdr is None or len(hdr) < 6:
                    _, status = os.waitpid(pid, 0)
                    reaped = True
                    raise PythonWorkerError(
                        f"python worker {pid} died mid-stream "
                        f"(wait status {status})")
                tag, _sidx, ln = struct.unpack("<BBI", hdr)
                payload = _read_exact(out_r, ln) if ln else b""
                if ln and (payload is None or len(payload) < ln):
                    # header arrived but the payload didn't: the worker
                    # died mid-write — report death, not garbage frames
                    _, status = os.waitpid(pid, 0)
                    reaped = True
                    raise PythonWorkerError(
                        f"python worker {pid} died mid-frame "
                        f"(wait status {status})")
                if tag == _MSG_END:
                    os.waitpid(pid, 0)
                    reaped = True
                    feeder.join(timeout=5)
                    if feed_error:
                        raise feed_error[0]
                    return
                if tag == _MSG_ERROR:
                    os.waitpid(pid, 0)
                    reaped = True
                    raise PythonWorkerError(
                        "python worker task failed:\n" +
                        payload.decode(errors="replace"))
                yield deserialize_host_batch(payload, out_schema)
        finally:
            for fd in (out_r,):
                try:
                    os.close(fd)
                except OSError:
                    pass
            feeder.join(timeout=5)
            if not reaped:
                # consumer abandoned the stream: stop the worker
                try:
                    os.kill(pid, 9)
                except ProcessLookupError:
                    pass
                try:
                    os.waitpid(pid, 0)
                except ChildProcessError:
                    pass


def run_single_input_task(ctx, task, part, in_schema, out_schema):
    """Single-input-schema convenience over :func:`run_python_task` (the
    shape every non-cogrouped pandas exec uses)."""
    return run_python_task(ctx, task, ((0, hb) for hb in part),
                           [in_schema], out_schema)
