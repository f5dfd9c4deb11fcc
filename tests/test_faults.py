"""Fault-tolerance subsystem tests: error classes, unified retry policy,
deterministic injection per site x error class, deadline watchdog,
device-lost recovery with bit-identical replay, and per-partition CPU
fallback (the reference's "anything the GPU cannot finish must still
produce the Spark CPU answer" contract)."""

import time

import pytest

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.fault import inject
from spark_rapids_tpu.fault.errors import (
    DeviceLostError, ErrorClass, PartitionTimeout, classify_error,
    mark_non_retryable,
)
from spark_rapids_tpu.fault.inject import InjectedFault, parse_spec
from spark_rapids_tpu.fault.retry import RetryPolicy
from spark_rapids_tpu.fault.watchdog import partition_deadline
from spark_rapids_tpu.session import TpuSparkSession

from compare import tpu_session


@pytest.fixture(autouse=True)
def _clean_registry():
    """The injection registry is process-global: never leak an armed
    spec into the next test."""
    yield
    inject.uninstall()


def _xla_err(msg):
    return type("XlaRuntimeError", (Exception,), {})(msg)


DATA = {"k": [i % 5 for i in range(200)], "v": list(range(200))}


def _query(s):
    df = s.create_dataframe(DATA, num_partitions=2)
    return df.group_by("k").sum("v")


def _clean_rows():
    return sorted(_query(tpu_session()).collect())


# -- error classes ----------------------------------------------------------


def test_classify_oom():
    assert classify_error(
        _xla_err("RESOURCE_EXHAUSTED: Out of memory allocating 1 bytes")
    ) is ErrorClass.RETRYABLE_OOM


@pytest.mark.parametrize("msg", [
    "INTERNAL: TPU worker crashed",
    "DATA_LOSS: checkpoint unreadable",
    "UNAVAILABLE: worker restarted mid-program",
    "INTERNAL: kernel fault detected",
])
def test_classify_device_lost(msg):
    assert classify_error(_xla_err(msg)) is ErrorClass.DEVICE_LOST


def test_classify_non_retryable():
    # user errors — even when the message mentions a status code
    assert classify_error(
        ValueError("RESOURCE_EXHAUSTED mentioned but wrong type")
    ) is ErrorClass.NON_RETRYABLE
    assert classify_error(KeyError("x")) is ErrorClass.NON_RETRYABLE
    # KeyboardInterrupt / SystemExit: never retried
    assert classify_error(KeyboardInterrupt()) is ErrorClass.NON_RETRYABLE
    assert classify_error(SystemExit(1)) is ErrorClass.NON_RETRYABLE
    # timeout classifies as device-lost (wedged == lost)
    assert classify_error(PartitionTimeout("t")) is ErrorClass.DEVICE_LOST
    # the donated-dispatch tag overrides message classification
    err = mark_non_retryable(_xla_err("RESOURCE_EXHAUSTED: donated"))
    assert classify_error(err) is ErrorClass.NON_RETRYABLE


def _mosaic_err(msg):
    from jax._src.pallas.mosaic.error_handling import MosaicError
    return MosaicError(msg)


def _jax_runtime_err(msg):
    import jax
    return jax.errors.JaxRuntimeError(msg)


_REFUSAL = "INTERNAL: Mosaic failed to compile TPU kernel: Invalid input layout"


@pytest.mark.parametrize("make,msg,phase,want", [
    (_mosaic_err, _REFUSAL, "bare", ErrorClass.NON_RETRYABLE),
    (_xla_err, _REFUSAL, "bare", ErrorClass.NON_RETRYABLE),
    (_mosaic_err, _REFUSAL, "lowering", ErrorClass.NON_RETRYABLE),
    (_jax_runtime_err, _REFUSAL, "lowering", ErrorClass.NON_RETRYABLE),
    # pinned by WHERE it was raised, not by its text
    (_jax_runtime_err, "INTERNAL: opaque", "lowering",
     ErrorClass.NON_RETRYABLE),
    (_jax_runtime_err, "INTERNAL: opaque", "executing",
     ErrorClass.DEVICE_LOST),
    (_xla_err, "INTERNAL: opaque", "bare", ErrorClass.DEVICE_LOST),
], ids=["mosaic-bare", "xla-text-bare", "mosaic-lowering", "xla-lowering",
        "opaque-lowering", "opaque-executing", "opaque-bare"])
def test_compile_refusal_is_not_a_lost_device(make, msg, phase, want):
    """A compile refusal reads INTERNAL like a crashed worker, but no
    replay can fix it: anything raised while a program is lowered or
    compiled is NON_RETRYABLE; INTERNAL from an EXECUTING program still
    means the device is gone."""
    import jax
    import jax.numpy as jnp
    from jax.extend.core import Primitive
    from jax.interpreters import mlir

    from spark_rapids_tpu.utils.compile_registry import instrumented_jit
    if phase == "bare":
        assert classify_error(make(msg)) is want
        return
    if phase == "lowering":
        # the raise happens when the ENCLOSING program is lowered, after
        # tracing succeeded — where a refused Pallas kernel surfaces
        prim = Primitive(f"refused_kernel_{abs(hash((make, msg)))}")
        prim.def_abstract_eval(lambda x: x)

        def lower(ctx, x):
            raise make(msg)

        mlir.register_lowering(prim, lower)
        program = instrumented_jit(prim.bind, label="refused")
    else:
        def boom(x):
            raise RuntimeError("worker went away")

        program = instrumented_jit(
            lambda x: jax.pure_callback(
                boom, jax.ShapeDtypeStruct(x.shape, x.dtype), x),
            label="lost")
    with pytest.raises(Exception) as ei:
        jax.block_until_ready(program(jnp.zeros(4, jnp.float32)))
    assert classify_error(ei.value) is want, repr(ei.value)


def test_retry_policy_deterministic_backoff():
    p = RetryPolicy(4, 50)
    # pure function of the attempt index: 50, 100, 200ms — no jitter
    assert [p.delay_s(a) for a in (1, 2, 3)] == [0.05, 0.1, 0.2]
    assert RetryPolicy.from_conf(RapidsConf()).max_attempts == 3


# -- injection spec ----------------------------------------------------------


def test_parse_spec_grammar():
    rules = parse_spec("dispatch:oom@3;d2h:device_lost@1;"
                       "spill:slow=200ms@2;h2d:oom@4+")
    assert [(r.site, r.kind, r.at, r.persistent) for r in rules] == [
        ("dispatch", "oom", 3, False), ("d2h", "device_lost", 1, False),
        ("spill", "slow", 2, False), ("h2d", "oom", 4, True)]
    assert rules[2].duration_s == pytest.approx(0.2)
    assert parse_spec("") == [] and parse_spec(None) == []


@pytest.mark.parametrize("bad", [
    "nope:oom@1", "dispatch:frob@1", "dispatch:oom@0", "dispatch:oom",
    "dispatch:oom=5ms@1",
])
def test_parse_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_spec(bad)


def test_injection_matrix_site_by_class():
    """Every (site, error kind) pair fires exactly at its call index,
    with the declared classification."""
    for site in inject.SITES:
        for kind, cls in (("oom", ErrorClass.RETRYABLE_OOM),
                          ("device_lost", ErrorClass.DEVICE_LOST)):
            inject.install(f"{site}:{kind}@2")
            inject.maybe_fire(site)  # call 1: no fire
            with pytest.raises(InjectedFault) as ei:
                inject.maybe_fire(site)
            assert classify_error(ei.value) is cls
            inject.maybe_fire(site)  # call 3: one-shot, spent
        inject.install(f"{site}:slow=50ms@1")
        t0 = time.monotonic()
        inject.maybe_fire(site)
        assert time.monotonic() - t0 >= 0.04
    inject.uninstall()


def test_persistent_rule_fires_repeatedly():
    inject.install("dispatch:oom@2+")
    inject.maybe_fire("dispatch")
    for _ in range(3):
        with pytest.raises(InjectedFault):
            inject.maybe_fire("dispatch")


# -- end-to-end recovery -----------------------------------------------------


@pytest.mark.parametrize("spec", [
    "dispatch:oom@2", "dispatch:device_lost@1",
    "h2d:oom@1", "h2d:device_lost@1",
    "d2h:oom@1", "d2h:device_lost@1",
])
def test_injected_fault_recovers_with_identical_results(spec):
    """A fault at any data-plane site mid-query recovers (spill-retry or
    device replay) and the results are bit-identical to a clean run."""
    want = _clean_rows()
    s = tpu_session(**{"spark.rapids.sql.tpu.faults.spec": spec})
    got = sorted(_query(s).collect())
    assert got == want, (spec, got[:3], want[:3])
    m = s.last_metrics
    assert m["faultsInjected"] >= 1, m
    assert m["retryCount"] >= 1, m
    if "device_lost" in spec:
        assert m["deviceLostCount"] >= 1, m
    assert m["partitionFallbackCount"] == 0, m  # device replay sufficed


def test_exchange_site_recovers_split_path():
    """A device loss at the (non-collapsed) exchange split replays and
    the split cache's generation check recomputes from lineage."""
    confs = {"spark.rapids.sql.tpu.exchange.collapseLocal": False,
             "spark.sql.shuffle.partitions": 3}
    want = sorted(_query(tpu_session(**confs)).collect())
    s = tpu_session(**confs,
                    **{"spark.rapids.sql.tpu.faults.spec":
                       "exchange:device_lost@1"})
    got = sorted(_query(s).collect())
    assert got == want
    assert s.last_metrics["deviceLostCount"] >= 1


def test_spill_site_injection():
    """The catalog's spill-to-host path is instrumented: a slow fault
    stalls it, an injected OOM surfaces from the registering call.
    Synchronous mode pins the v1 contract (the async-writer surfacing of
    the same faults is covered in test_spill_async.py)."""
    from spark_rapids_tpu.batch import HostBatch, host_to_device
    from spark_rapids_tpu.mem.catalog import BufferCatalog

    def batch():
        return host_to_device(HostBatch.from_pydict(
            {"x": (__import__("spark_rapids_tpu.types", fromlist=["INT"])
                   .INT, list(range(64)))}))

    conf = RapidsConf({"spark.rapids.memory.tpu.spillBudgetBytes": 64,
                       "spark.rapids.sql.tpu.spill.async.enabled": False})
    inject.install("spill:oom@1")
    cat = BufferCatalog(conf)
    cat.register(batch(), priority=1)
    with pytest.raises(InjectedFault):
        cat.register(batch(), priority=2)  # budget forces the spill
    inject.install("spill:slow=50ms@1")
    cat2 = BufferCatalog(conf)
    cat2.register(batch(), priority=1)
    t0 = time.monotonic()
    cat2.register(batch(), priority=2)
    assert time.monotonic() - t0 >= 0.04
    assert cat2.metrics["spilled_to_host"] >= 1


def test_cpu_fallback_partition_parity():
    """Persistent device loss exhausts device replays; the partition
    completes through ops/cpu_exec with Spark-CPU-identical results —
    per-partition fallback, never whole-query abort."""
    want = _clean_rows()
    s = tpu_session(**{
        "spark.rapids.sql.tpu.faults.spec": "dispatch:device_lost@1+",
        "spark.rapids.sql.tpu.retry.backoffMs": 1,
    })
    got = sorted(_query(s).collect())
    assert got == want
    m = s.last_metrics
    assert m["partitionFallbackCount"] >= 1, m
    assert m["deviceLostCount"] >= 1, m
    assert m["backoffWallNs"] > 0, m


def test_fallback_disabled_surfaces_raw_error():
    s = tpu_session(**{
        "spark.rapids.sql.tpu.faults.spec": "dispatch:device_lost@1+",
        "spark.rapids.sql.tpu.retry.backoffMs": 1,
        "spark.rapids.sql.tpu.fallback.onDeviceError": False,
    })
    with pytest.raises(InjectedFault, match="injected device loss"):
        _query(s).collect()


def test_keyboard_interrupt_never_retried():
    """BaseException (KeyboardInterrupt/SystemExit) passes straight
    through the partition driver — no replay, no fallback."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.plan.physical import (
        ExecContext, PhysicalOp, _drive_partitions,
    )

    calls = {"n": 0}

    class Boom(PhysicalOp):
        def __init__(self):
            super().__init__([], T.Schema([]))

        def partitions(self, ctx):
            def gen():
                calls["n"] += 1
                raise KeyboardInterrupt()
                yield  # pragma: no cover

            return [gen()]

    ctx = ExecContext(RapidsConf(
        {"spark.rapids.sql.tpu.fallback.onDeviceError": True}))
    with pytest.raises(KeyboardInterrupt):
        _drive_partitions(Boom(), ctx, release_partial=False)
    assert calls["n"] == 1  # exactly one attempt


def test_user_error_not_retried():
    """NON_RETRYABLE user errors raise immediately: no replay burns
    attempts on a deterministic failure."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.plan.physical import (
        ExecContext, PhysicalOp, _drive_partitions,
    )

    calls = {"n": 0}

    class Bad(PhysicalOp):
        def __init__(self):
            super().__init__([], T.Schema([]))

        def partitions(self, ctx):
            def gen():
                calls["n"] += 1
                raise KeyError("user bug")
                yield  # pragma: no cover

            return [gen()]

    with pytest.raises(KeyError):
        _drive_partitions(Bad(), ExecContext(RapidsConf()),
                          release_partial=False)
    assert calls["n"] == 1


# -- deadline watchdog -------------------------------------------------------


def test_watchdog_context_manager_fires():
    with pytest.raises(PartitionTimeout):
        with partition_deadline(0.2, "unit"):
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                time.sleep(0.005)


def test_watchdog_disarmed_is_noop():
    with partition_deadline(0.0, "unit"):
        time.sleep(0.05)
    with partition_deadline(RapidsConf(), "unit"):  # default conf: off
        pass


def test_hung_partition_fails_fast_and_releases_permits():
    """Acceptance: under partition.timeoutSec=2 a hung partition fails
    with PartitionTimeout instead of stalling the suite, permits are
    released via the existing finally paths, and the next query on the
    same process works."""
    s = tpu_session(**{
        "spark.rapids.sql.tpu.partition.timeoutSec": 2.0,
        "spark.rapids.sql.tpu.retry.maxAttempts": 1,
        "spark.rapids.sql.tpu.fallback.onDeviceError": False,
        "spark.rapids.sql.tpu.faults.spec": "dispatch:slow=60000ms@1",
    })
    t0 = time.monotonic()
    with pytest.raises(PartitionTimeout):
        _query(s).collect()
    assert time.monotonic() - t0 < 15
    assert s.runtime.semaphore.held_depth() == 0
    # same process recovers: a clean session answers normally
    assert sorted(_query(tpu_session()).collect()) == _clean_rows()


def test_hung_partition_recovers_when_retries_allowed():
    """With replays allowed the timeout enters device-lost recovery and
    the query completes (the stall was one-shot)."""
    want = _clean_rows()
    s = tpu_session(**{
        "spark.rapids.sql.tpu.partition.timeoutSec": 1.0,
        "spark.rapids.sql.tpu.faults.spec": "dispatch:slow=60000ms@1",
    })
    got = sorted(_query(s).collect())
    assert got == want
    assert s.last_metrics["deviceLostCount"] >= 1


# -- device-lost recovery internals ------------------------------------------


def test_invalidate_device_tier_rescues_to_host():
    """Live device buffers are rescued to host on invalidation (the
    injected-loss case); host/disk tiers are untouched and handles
    re-upload lazily."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.batch import (
        HostBatch, device_to_host, host_to_device,
    )
    from spark_rapids_tpu.mem.catalog import BufferCatalog, SpillableBatch
    from conftest import assert_batches_equal

    data = {"x": (T.INT, [1, 2, None, 4])}
    cat = BufferCatalog(RapidsConf())
    h = cat.register(host_to_device(HostBatch.from_pydict(data)))
    assert h.tier == SpillableBatch.TIER_DEVICE
    assert cat.invalidate_device_tier() == 1
    assert h.tier == SpillableBatch.TIER_HOST
    got = device_to_host(h.get()).to_pydict()
    assert_batches_equal(HostBatch.from_pydict(data).to_pydict(), got)
    assert cat.metrics["device_invalidated"] == 1


def test_lost_handle_raises_classified_error():
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.batch import HostBatch, host_to_device
    from spark_rapids_tpu.mem.catalog import BufferCatalog, SpillableBatch

    cat = BufferCatalog(RapidsConf())
    h = cat.register(host_to_device(HostBatch.from_pydict(
        {"x": (T.INT, [1, 2, 3])})))
    # simulate an unrescuable loss (real device death: D2H fails too)
    h._device = None
    h.tier = SpillableBatch.TIER_LOST
    with pytest.raises(DeviceLostError) as ei:
        h.get()
    assert classify_error(ei.value) is ErrorClass.DEVICE_LOST


def test_runtime_recover_keeps_catalog_and_bumps_generation():
    from spark_rapids_tpu.runtime.device import DeviceRuntime

    DeviceRuntime.reset()
    try:
        conf = RapidsConf()
        rt = DeviceRuntime.get(conf)
        cat = rt.catalog
        g0 = DeviceRuntime.generation()
        rt2 = DeviceRuntime.recover(conf)
        assert DeviceRuntime.generation() == g0 + 1
        assert rt2.catalog is cat           # spill tiers survive
        assert rt2.semaphore is not rt.semaphore  # wedged permits don't
        assert DeviceRuntime.get(conf) is rt2
    finally:
        DeviceRuntime.reset()


def test_oom_retry_uses_unified_policy():
    """catalog.run_with_oom_retry is a thin wrapper over the unified
    policy: conf maxAttempts bounds it and injected OOMs (explicit
    classification) trigger the same spill machinery as real ones."""
    from spark_rapids_tpu.batch import HostBatch, host_to_device
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.mem.catalog import (
        BufferCatalog, SpillableBatch, run_with_oom_retry,
    )

    conf = RapidsConf({"spark.rapids.sql.tpu.retry.maxAttempts": 2,
                       "spark.rapids.sql.tpu.retry.backoffMs": 1})
    cat = BufferCatalog(conf)
    h = cat.register(host_to_device(HostBatch.from_pydict(
        {"x": (T.INT, [1, 2, 3])})))
    calls = {"n": 0}

    def thunk():
        calls["n"] += 1
        if calls["n"] == 1:
            raise InjectedFault("RESOURCE_EXHAUSTED (unit)",
                                ErrorClass.RETRYABLE_OOM)
        return "ok"

    assert run_with_oom_retry(cat, thunk) == "ok"
    assert calls["n"] == 2
    assert h.tier == SpillableBatch.TIER_HOST  # spilled by the handler

    # maxAttempts=2 -> a thunk failing twice exhausts the policy (a
    # fresh device-tier handle keeps the spill pass productive, so the
    # early freed==0 give-up doesn't shortcut the bound)
    cat.register(host_to_device(HostBatch.from_pydict(
        {"x": (T.INT, [4, 5, 6])})))
    calls2 = {"n": 0}

    def always():
        calls2["n"] += 1
        raise InjectedFault("RESOURCE_EXHAUSTED (unit)",
                            ErrorClass.RETRYABLE_OOM)

    with pytest.raises(InjectedFault):
        run_with_oom_retry(cat, always)
    assert calls2["n"] == 2


def test_session_metrics_clean_query_all_zero():
    s = tpu_session()
    _query(s).collect()
    m = s.last_metrics
    assert m["retryCount"] == 0 and m["deviceLostCount"] == 0
    assert m["partitionFallbackCount"] == 0 and m["faultsInjected"] == 0
    assert m["backoffWallNs"] == 0


def test_registry_uninstalled_after_query():
    """Persistent @N+ rules must not outlive the query: sites reached
    outside execute (no recovery machinery there) stay un-instrumented."""
    s = tpu_session(**{
        "spark.rapids.sql.tpu.faults.spec": "h2d:device_lost@1+",
        "spark.rapids.sql.tpu.retry.backoffMs": 1,
    })
    _query(s).collect()  # completes via recovery/fallback
    assert not inject.active()
    # a bare host_to_device outside any query must not raise
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.batch import HostBatch, host_to_device
    host_to_device(HostBatch.from_pydict({"x": (T.INT, [1, 2])}))


def test_recovery_repoints_ctx_at_live_runtime():
    """recover_device_lost must re-point the query context at the
    REBUILT runtime: replays dispatch to the live device and take
    admission on the live semaphore, not the dead ones."""
    from spark_rapids_tpu.fault.recovery import recover_device_lost
    from spark_rapids_tpu.plan.physical import ExecContext
    from spark_rapids_tpu.runtime.device import DeviceRuntime

    DeviceRuntime.reset()
    try:
        conf = RapidsConf()
        rt = DeviceRuntime.get(conf)
        ctx = ExecContext(conf, semaphore=rt.semaphore, device=rt.device)
        recover_device_lost(ctx)
        rt2 = DeviceRuntime.get(conf)
        assert rt2 is not rt
        assert ctx.semaphore is rt2.semaphore
        assert ctx.device is rt2.device
    finally:
        DeviceRuntime.reset()


def test_timeout_recovery_skips_rescue_copy():
    """A PartitionTimeout-triggered recovery must not attempt the rescue
    D2H (the device is wedged — a copy against it would block the
    recovery path): device-tier handles go straight to TIER_LOST."""
    from spark_rapids_tpu.batch import HostBatch, host_to_device
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.fault.recovery import recover_device_lost
    from spark_rapids_tpu.plan.physical import ExecContext
    from spark_rapids_tpu.mem.catalog import SpillableBatch
    from spark_rapids_tpu.runtime.device import DeviceRuntime

    DeviceRuntime.reset()
    try:
        conf = RapidsConf()
        rt = DeviceRuntime.get(conf)
        h = rt.catalog.register(host_to_device(HostBatch.from_pydict(
            {"x": (T.INT, [1, 2, 3])})))
        ctx = ExecContext(conf, semaphore=rt.semaphore, device=rt.device)
        recover_device_lost(ctx, PartitionTimeout("wedged"))
        assert h.tier == SpillableBatch.TIER_LOST
        with pytest.raises(DeviceLostError):
            h.get()
        # a crash-classified recovery on a responsive device DOES rescue
        rt2 = DeviceRuntime.get(conf)
        h2 = rt2.catalog.register(host_to_device(HostBatch.from_pydict(
            {"x": (T.INT, [4, 5])})))
        recover_device_lost(ctx, _xla_err("INTERNAL: worker crashed"))
        assert h2.tier == SpillableBatch.TIER_HOST
    finally:
        DeviceRuntime.reset()
