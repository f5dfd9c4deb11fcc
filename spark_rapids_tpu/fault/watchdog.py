"""Per-partition deadline watchdog.

Round-5 on-chip evidence (pre-round records, in git history):
test_hashagg / test_tpch_like hung 40+ minutes on a single dot with no
watchdog.  This module arms a
deadline around each driven partition (conf
``spark.rapids.sql.tpu.partition.timeoutSec``; 0 = off, the tier-1
default — the bench driver turns it on): a monitor thread waits on an
event with the timeout and, on expiry, raises a classified
:class:`~spark_rapids_tpu.fault.errors.PartitionTimeout` INTO the
driving thread via ``PyThreadState_SetAsyncExc``.  The exception then
propagates through the partition driver's existing except/finally paths
(semaphore permits released, read-ahead workers stopped) and enters the
normal recovery machinery as a DEVICE_LOST-class error.

Limits (documented, inherent to in-process watchdogs): an async
exception is delivered between Python bytecodes, so a thread wedged
inside one long C call (a single giant XLA execute) sees it only when
that call returns.  Python-level stalls — polling loops, sliced sleeps,
iterator-driven pipelines — are interrupted within milliseconds of the
deadline.  Truly wedged C calls need process-level supervision (the CI
harness's per-test SIGALRM remains that backstop).
"""

from __future__ import annotations

import ctypes
import threading

from spark_rapids_tpu.fault.errors import PartitionTimeout


def _async_raise(tid: int, exc_class) -> None:
    ctypes.pythonapi.PyThreadState_SetAsyncExc(
        ctypes.c_ulong(tid), ctypes.py_object(exc_class))


def _async_revoke(tid: int) -> None:
    ctypes.pythonapi.PyThreadState_SetAsyncExc(ctypes.c_ulong(tid), None)


class partition_deadline:
    """Context manager arming a deadline for the current thread.

    ``partition_deadline(conf, label)`` reads
    ``spark.rapids.sql.tpu.partition.timeoutSec`` from ``conf``;
    ``partition_deadline(seconds, label)`` takes an explicit timeout.
    Timeout <= 0 disarms (zero overhead beyond one comparison).

    ``exc_type`` overrides the raised class (default
    :class:`PartitionTimeout`, which classifies DEVICE_LOST and enters
    recovery).  The serving scheduler arms per-submission deadlines with
    its own NON_RETRYABLE exception so an expired query aborts out of
    ``session.execute`` instead of being replayed by the retry ladder.
    """

    def __init__(self, conf_or_secs, label: str = "partition",
                 exc_type=PartitionTimeout):
        if isinstance(conf_or_secs, (int, float)):
            self.timeout = float(conf_or_secs)
        else:
            from spark_rapids_tpu.config import PARTITION_TIMEOUT_SEC
            self.timeout = float(PARTITION_TIMEOUT_SEC.get(conf_or_secs))
        self.label = label
        self.exc_type = exc_type
        self.fired = False
        self._thread = None

    def __enter__(self):
        if self.timeout <= 0:
            return self
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.utils.tracing import span
        # the monitor's start is the arming half of the hand-off the
        # join below is the other half of
        with span("fault", "watchdog_start"):
            self._tid = threading.get_ident()
            self._cancel = threading.Event()
            self._lock = threading.Lock()
            self._done = False
            # adopt the arming query's scope on the monitor so the fire
            # event lands in the right query's timeline under concurrency
            self._scope = obs_events.current_scope()
            self._thread = threading.Thread(
                target=self._watch, daemon=True,
                name=f"partition-deadline:{self.label}")
            self._thread.start()
        return self

    def _watch(self):
        if self._cancel.wait(self.timeout):
            return
        with self._lock:
            if self._done:
                return
            self.fired = True
            from spark_rapids_tpu.obs import events as obs_events
            with obs_events.adopt(self._scope):
                obs_events.emit_instant("fault", "watchdog_fire",
                                        label=self.label,
                                        timeout_s=self.timeout)
            _async_raise(self._tid, self.exc_type)

    def __exit__(self, exc_type, exc, tb):
        if self._thread is None:
            return False
        # the monitor wakes and exits: a thread hand-off the query waits
        # for on every partition/collect, so it is on the timeline
        from spark_rapids_tpu.utils.tracing import span
        with span("fault", "watchdog_join"):
            with self._lock:
                self._done = True
            self._cancel.set()
            self._thread.join(timeout=1.0)
        if self.fired:
            if exc_type is None:
                # fired in the gap between the body's last bytecode and
                # this __exit__: the async exception is pending but
                # undelivered — revoke it and raise synchronously so the
                # timeout can neither be lost nor pop at a random later
                # point
                _async_revoke(self._tid)
                raise self.exc_type(
                    f"{self.label} exceeded partition.timeoutSec="
                    f"{self.timeout:g}s")
            if exc_type is not self.exc_type:
                # the body raised its OWN error in the same instant the
                # deadline expired: the async PartitionTimeout is still
                # pending and would otherwise detonate at an arbitrary
                # later bytecode — revoke it; the body's error (already
                # classified by the recovery ladder) wins
                _async_revoke(self._tid)
        return False
