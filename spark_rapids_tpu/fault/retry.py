"""The engine's single retry policy.

One :class:`RetryPolicy` (max attempts + exponential backoff) stands
behind every retry loop: the OOM spill-retry
(mem.catalog.run_with_oom_retry), the partition replay
(plan.physical.run_partition_with_retry -> fault.recovery) and the
whole-pipeline recovery.  Backoff delays are DETERMINISTIC — a pure
function of the attempt index (base * 2^(attempt-1)), no jitter and no
``random`` — so a faulted run replays identically, which the
fault-injection tests rely on.
"""

from __future__ import annotations

import time

from spark_rapids_tpu.fault import metrics as fault_metrics


class RetryPolicy:
    """Max attempts + deterministic exponential backoff.

    ``max_attempts`` counts TOTAL attempts (the first try included), so
    ``max_attempts=3`` means up to two replays after the initial
    failure.  ``delay_s(attempt)`` is the sleep taken AFTER the given
    1-based attempt failed.
    """

    def __init__(self, max_attempts: int, backoff_ms: float):
        self.max_attempts = max(1, int(max_attempts))
        self.backoff_ms = max(0.0, float(backoff_ms))

    @classmethod
    def from_conf(cls, conf) -> "RetryPolicy":
        from spark_rapids_tpu.config import (
            RETRY_BACKOFF_MS, RETRY_MAX_ATTEMPTS,
        )
        return cls(RETRY_MAX_ATTEMPTS.get(conf), RETRY_BACKOFF_MS.get(conf))

    def delay_s(self, attempt: int) -> float:
        """Deterministic per-attempt delay: backoffMs * 2^(attempt-1)."""
        return self.backoff_ms * (2 ** max(0, attempt - 1)) / 1000.0

    def backoff(self, attempt: int) -> None:
        """Sleep the attempt's delay, accounting the wall into
        ``backoffWallNs``."""
        d = self.delay_s(attempt)
        if d <= 0:
            return
        from spark_rapids_tpu.utils.tracing import span
        with span("retry", "backoff", attempt=attempt) as sp:
            time.sleep(d)
        fault_metrics.record("backoff_wall_ns", sp.elapsed_ns)

    def __repr__(self):
        return (f"RetryPolicy(max_attempts={self.max_attempts}, "
                f"backoff_ms={self.backoff_ms})")
