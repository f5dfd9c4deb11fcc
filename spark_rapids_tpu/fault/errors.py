"""Error classes: every raised error maps to one retry class.

The reference engine distinguishes retryable allocation failures
(RmmRapidsRetryIterator's RetryOOM/SplitAndRetryOOM) from fatal device
state loss (executor death -> Spark task retry on another executor).
XLA surfaces both through the same ``XlaRuntimeError`` channel, carrying
the ABSL status-code name in the message — classification is therefore
by status code + message shape, with an explicit escape hatch: an error
object carrying a ``rapids_error_class`` attribute (set by the fault
injector and by the donated-dispatch fail-fast path) classifies as
exactly that.

A compile refusal is NOT a lost device.  XLA reports a kernel the chip's
compiler rejects as ``INTERNAL: Mosaic failed to compile TPU kernel``,
which by status code alone would read as DEVICE_LOST and be replayed
``retry.maxAttempts`` times before the partition quietly finishes on the
CPU.  Replaying a program that cannot compile can never succeed, so
anything raised while a program is being lowered or compiled — a
``MosaicError``, an XLA error that says it failed to compile, or any
error ``utils.compile_registry`` saw unwinding a compile phase (it pins
those via :func:`mark_non_retryable`) — is NON_RETRYABLE.
"""

from __future__ import annotations

import enum


class ErrorClass(enum.Enum):
    #: RESOURCE_EXHAUSTED allocation failures: spill-and-retry is sound.
    RETRYABLE_OOM = "retryable_oom"
    #: The device (or its runtime) is gone or wedged: XLA worker
    #: crashed/restarted, kernel fault, DATA_LOSS/INTERNAL/UNAVAILABLE
    #: status, or a partition deadline expiry.  Recovery = runtime reset
    #: + device-tier invalidation + replay, then per-partition CPU
    #: fallback.
    DEVICE_LOST = "device_lost"
    #: User errors, compile refusals (a program the compiler rejects is
    #: rejected on every replay), donated-dispatch OOM (inputs consumed
    #: at dispatch — a retry cannot re-present them),
    #: KeyboardInterrupt/SystemExit.  Never retried.
    NON_RETRYABLE = "non_retryable"


class PartitionTimeout(RuntimeError):
    """A partition exceeded ``spark.rapids.sql.tpu.partition.timeoutSec``.

    Raised asynchronously into the driving thread by the deadline
    watchdog; classifies as DEVICE_LOST (a wedged device is
    indistinguishable from a lost one — recovery resets and replays)."""

    rapids_error_class = ErrorClass.DEVICE_LOST


class DeviceLostError(RuntimeError):
    """Raised by a spillable handle whose device-tier data did not
    survive a device loss (no host/disk copy existed to rescue)."""

    rapids_error_class = ErrorClass.DEVICE_LOST


#: XLA status-code names that mean the device/runtime is gone, and
#: message fragments the TPU runtime emits on worker death (the SF1 q2
#: crash shape from round 5).
_DEVICE_LOST_CODES = ("DATA_LOSS", "INTERNAL", "UNAVAILABLE", "ABORTED")
_DEVICE_LOST_FRAGMENTS = ("worker crashed", "worker restarted",
                          "kernel fault", "device lost", "device failed")

#: Exception type names jax raises for XLA runtime failures (the string
#: check mirrors mem.catalog.is_device_oom: the classes live in private
#: jaxlib modules that move between versions).
_XLA_ERROR_TYPES = ("XlaRuntimeError", "JaxRuntimeError")

#: Pallas re-raises a kernel the TPU compiler refuses as MosaicError
#: (VerificationError subclasses it); the XLA-level text of the same
#: refusal, should it arrive unconverted, names the compile.
_COMPILE_REFUSAL_TYPES = ("MosaicError", "VerificationError")
_COMPILE_REFUSAL_FRAGMENTS = ("failed to compile", "mosaic",
                              "during compilation")


def classify_error(err: BaseException) -> ErrorClass:
    """Map a raised error to its :class:`ErrorClass`."""
    if not isinstance(err, Exception):
        # KeyboardInterrupt / SystemExit / GeneratorExit: never retried
        return ErrorClass.NON_RETRYABLE
    explicit = getattr(err, "rapids_error_class", None)
    if isinstance(explicit, ErrorClass):
        return explicit
    if type(err).__name__ in _COMPILE_REFUSAL_TYPES:
        return ErrorClass.NON_RETRYABLE
    if type(err).__name__ in _XLA_ERROR_TYPES:
        msg = str(err)
        low = msg.lower()
        if any(frag in low for frag in _COMPILE_REFUSAL_FRAGMENTS):
            return ErrorClass.NON_RETRYABLE
        if "RESOURCE_EXHAUSTED" in msg:
            return ErrorClass.RETRYABLE_OOM
        if any(code in msg for code in _DEVICE_LOST_CODES) or \
                any(frag in low for frag in _DEVICE_LOST_FRAGMENTS):
            return ErrorClass.DEVICE_LOST
    return ErrorClass.NON_RETRYABLE


def mark_non_retryable(err: Exception) -> Exception:
    """Pin ``err`` to NON_RETRYABLE (the donated-dispatch OOM path: the
    dispatch consumed its inputs, so no level of replay may re-present
    them to the same program)."""
    try:
        err.rapids_error_class = ErrorClass.NON_RETRYABLE
    except Exception:  # noqa: BLE001 — exceptions with __slots__
        pass
    return err
