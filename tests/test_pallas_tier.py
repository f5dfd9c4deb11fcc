"""Pallas kernel tier: interpret-mode parity matrix vs the XLA formulations.

Every registered kernel must be BIT-identical to the XLA formulation it
replaces (docs/kernels.md).  On the CPU test backend the kernels engage
through the Pallas interpreter (`spark.rapids.sql.tpu.pallas.interpret`),
which executes the kernel's own program — so these tests pin the kernel
logic, not just the fallback.  Each family is exercised across empty,
single-row, NULL-heavy, capacity-boundary and string/varlen inputs, plus
the take_head-truncated live-bytes case for the pack kernel.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (
    HostBatch, device_to_host, host_to_device, round_up_capacity,
)
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exprs import strings as S
from spark_rapids_tpu.exprs.base import DevVal
from spark_rapids_tpu.kernels import pallas_tier as PT
from spark_rapids_tpu.kernels.join import join_pairs_static
from spark_rapids_tpu.kernels.layout import concat_kway, gather_segments_kway
from spark_rapids_tpu.kernels import take_head

INTERPRET_KEY = "spark.rapids.sql.tpu.pallas.interpret"


@contextlib.contextmanager
def tier(extra=None):
    PT.configure(RapidsConf(dict(extra or {})))
    try:
        yield
    finally:
        PT.configure(None)


def on_conf():
    # three of the four kernels default OFF (the v5e compiler refuses
    # them — tests/test_chip_compile.py); the parity matrix enables all
    return {spec.entry.key: True for spec in PT.registered()}


def interp_conf():
    return {INTERPRET_KEY: True, **on_conf()}


def off_conf():
    return {spec.entry.key: False for spec in PT.registered()}


def make_batch(pydict):
    return host_to_device(HostBatch.from_pydict(pydict))


def assert_batch_bits(a, b):
    """Raw-buffer equality: same bytes, same dtypes, dead lanes included."""
    assert int(jax.device_get(a.num_rows)) == int(jax.device_get(b.num_rows))
    assert len(a.columns) == len(b.columns)
    for ca, cb in zip(a.columns, b.columns):
        for field in ("data", "validity", "offsets", "codes", "lengths"):
            va, vb = getattr(ca, field, None), getattr(cb, field, None)
            assert (va is None) == (vb is None), field
            if va is not None:
                ga, gb = jax.device_get(va), jax.device_get(vb)
                assert ga.dtype == gb.dtype, field
                np.testing.assert_array_equal(ga, gb, err_msg=field)


def run_both(fn):
    """fn() under the interpreted tier and under kernel-off; no fallbacks
    may fire while the tier is engaged (the kernel really ran)."""
    with tier(interp_conf()):
        before = PT.fallback_count()
        got = jax.block_until_ready(fn())
        assert PT.fallback_count() == before, "kernel fell back under interpret"
    with tier(off_conf()):
        want = jax.block_until_ready(fn())
    return got, want


MIXED = {
    "i": (T.INT, [3, None, 7, 1, 7, None, 0]),
    "f": (T.FLOAT, [1.5, -2.0, None, 0.0, float("nan"), 3.25, -0.0]),
    "s": (T.STRING, ["bb", "", None, "apple", "bb", "zed", "aa"]),
    "b": (T.BOOLEAN, [True, False, None, True, False, True, None]),
}
NULLY = {
    "i": (T.INT, [None, None, 5, None]),
    "f": (T.FLOAT, [None, 1.0, 2.0, None]),
    "s": (T.STRING, [None, "x", None, None]),
    "b": (T.BOOLEAN, [True, None, False, True]),
}
SINGLE = {
    "i": (T.INT, [42]),
    "f": (T.FLOAT, [0.5]),
    "s": (T.STRING, ["one"]),
    "b": (T.BOOLEAN, [None]),
}


@pytest.mark.parametrize("dicts,cap", [
    ([MIXED, NULLY], round_up_capacity(11)),
    ([SINGLE, SINGLE], 2),                      # capacity-boundary: cap == rows
    ([{"i": (T.INT, []), "s": (T.STRING, [])},
      {"i": (T.INT, [1]), "s": (T.STRING, ["z"])}], 8),   # empty input
], ids=["mixed-nully", "single-boundary", "empty"])
def test_concat_kway_parity(dicts, cap):
    batches = [make_batch(d) for d in dicts]
    got, want = run_both(lambda: concat_kway(batches, cap))
    assert_batch_bits(got, want)


def test_concat_kway_take_head_live_bytes():
    """A take_head-truncated input contributes offsets[num_rows] bytes —
    the kernel must not leak the stale tail bytes past the truncation."""
    b1 = take_head(make_batch(MIXED), 2)
    b2 = make_batch(SINGLE)
    got, want = run_both(
        lambda: concat_kway([b1, b2], round_up_capacity(3)))
    assert_batch_bits(got, want)
    out = device_to_host(got).to_pydict()
    assert out["s"] == ["bb", "", "one"]


@pytest.mark.parametrize("starts,counts", [
    ([1, 0], [3, 2]),          # interior + prefix segments
    ([0, 3], [0, 1]),          # empty segment from input 0
    ([0, 0], [7, 4]),          # whole-batch segments, boundary cap
], ids=["interior", "empty-seg", "whole"])
def test_gather_segments_kway_parity(starts, counts):
    batches = [make_batch(MIXED), make_batch(NULLY)]
    cap = max(sum(counts), 1)
    got, want = run_both(lambda: gather_segments_kway(
        batches,
        [jnp.asarray(s, jnp.int32) for s in starts],
        [jnp.asarray(c, jnp.int32) for c in counts], cap))
    assert_batch_bits(got, want)


def _devvals(batch, idxs):
    return [DevVal(c.dtype, c.data, c.validity, c.offsets)
            for i, c in enumerate(batch.columns) if i in idxs]


@pytest.mark.parametrize("left,right,key_idx,pair_cap", [
    # int keys, duplicates both sides
    ({"k": (T.INT, [1, 2, 2, None, 3, 1, 2])},
     {"k": (T.INT, [2, 2, 1, None])}, [0], 64),
    # string keys incl. empties and NULLs
    ({"k": (T.STRING, ["ab", "", None, "zzz", "ab", "q"])},
     {"k": (T.STRING, ["", "ab", None, "q", "nope"])}, [0], 64),
    # composite int+string key
    ({"k": (T.INT, [1, 1, 2, 2]), "s": (T.STRING, ["a", "b", "a", None])},
     {"k": (T.INT, [1, 2, 2]), "s": (T.STRING, ["a", "a", None])},
     [0, 1], 32),
    # empty probe side
    ({"k": (T.INT, [])}, {"k": (T.INT, [5, 6])}, [0], 8),
    # overflow boundary: true pair total exceeds pair_cap; the truncated
    # buffers and the overflow flag must still match bit-for-bit
    ({"k": (T.INT, [7] * 6)}, {"k": (T.INT, [7] * 6)}, [0], 16),
], ids=["int", "string", "composite", "empty", "overflow"])
def test_join_pairs_static_parity(left, right, key_idx, pair_cap):
    lb, rb = make_batch(left), make_batch(right)
    lk, rk = _devvals(lb, key_idx), _devvals(rb, key_idx)
    got, want = run_both(lambda: join_pairs_static(
        lk, lb.num_rows, rk, rb.num_rows, pair_cap))
    for g, w in zip(got, want):
        ga, wa = jax.device_get(g), jax.device_get(w)
        assert ga.dtype == wa.dtype
        np.testing.assert_array_equal(ga, wa)
    if pair_cap == 16:
        assert bool(jax.device_get(got[-1]))  # 36 pairs > 16: overflow set


@pytest.mark.parametrize("vals", [
    ["hello", "", None, "a" * 40, "hello", "x"],
    [None, None],
    [""],
    [],
], ids=["mixed", "all-null", "one-empty", "empty"])
def test_string_hash2_parity(vals):
    b = make_batch({"s": (T.STRING, vals)})
    c = b.columns[0]
    v = DevVal(c.dtype, c.data, c.validity, c.offsets)
    got, want = run_both(lambda: S.string_hash2(v))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(jax.device_get(g), jax.device_get(w))


def test_rows_with_match_parity():
    b = make_batch({"s": (T.STRING, ["abc", None, "xabx", "", "ab"])})
    c = b.columns[0]
    v = DevVal(c.dtype, c.data, c.validity, c.offsets)
    got, want = run_both(lambda: S._rows_with_match(v, b"ab"))
    np.testing.assert_array_equal(jax.device_get(got), jax.device_get(want))


def test_cpu_without_interpret_counts_backend_fallback():
    """Kernels enabled on a non-TPU backend: the XLA formulation runs
    (reason "backend") and each such decision is counted."""
    if jax.default_backend() == "tpu":
        pytest.skip("backend fallback only observable off-TPU")
    b = make_batch({"s": (T.STRING, ["fallback", "probe"])})
    c = b.columns[0]
    v = DevVal(c.dtype, c.data, c.validity, c.offsets)
    with tier(on_conf()):  # kernels on, interpret off
        before = PT.fallback_count()
        got = jax.block_until_ready(S.string_hash2(v))
        assert PT.fallback_count() > before
    with tier(off_conf()):
        want = jax.block_until_ready(S.string_hash2(v))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(jax.device_get(g), jax.device_get(w))


def test_decide_reasons():
    with tier(off_conf()):
        d = PT.decide("stringHash")
        assert not d.engaged and d.reason == "off"
    with tier(interp_conf()):
        d = PT.decide("stringHash")
        assert d.engaged and d.interpret and d.reason == ""
    with tier({**interp_conf(),
               "spark.rapids.sql.tpu.pallas.vmemBudgetBytes": 1024}):
        d = PT.decide("joinProbe", resident_bytes=4096)
        assert not d.engaged and d.reason == "budget"
    if jax.default_backend() != "tpu":
        with tier({}):
            d = PT.decide("strings")
            assert not d.engaged and d.reason == "backend"


def test_registry_names():
    assert [s.name for s in PT.registered()] == [
        "gatherScatter", "joinProbe", "stringHash", "strings"]


def test_gates_are_per_kernel_confs():
    # each gate is its own conf; the interpret conf alone engages only
    # what is enabled (defaults: strings on, the refused three off)
    strings_key = "spark.rapids.sql.tpu.pallas.strings.enabled"
    with tier({**interp_conf(), strings_key: False}):
        assert not PT.decide("strings").engaged
        assert PT.decide("stringHash").engaged  # gates are independent
    with tier({INTERPRET_KEY: True}):
        d = PT.decide("strings")
        assert d.engaged and d.interpret
        assert PT.decide("joinProbe").reason == "off"


def test_enabled_kernel_failure_raises_with_its_name(monkeypatch):
    """No except between an enabled kernel and its caller substitutes
    the XLA formulation: a trace-time failure raises, naming the kernel,
    counts no fallback, and classifies NON_RETRYABLE."""
    from spark_rapids_tpu.fault.errors import ErrorClass, classify_error

    def refuse(*a, **k):
        raise NotImplementedError("Only 2D gather is supported")

    monkeypatch.setattr(PT, "string_hash_rows", refuse)
    b = make_batch({"s": (T.STRING, ["refused", "kernel"])})
    c = b.columns[0]
    v = DevVal(c.dtype, c.data, c.validity, c.offsets)
    with tier(interp_conf()):
        before = PT.fallback_count()
        with pytest.raises(PT.PallasKernelError, match="stringHash") as ei:
            S.string_hash2(v)
        assert PT.fallback_count() == before
    assert "Only 2D gather" in str(ei.value)
    assert classify_error(ei.value) is ErrorClass.NON_RETRYABLE


def test_compile_refusal_fails_query_without_retry_or_cpu(monkeypatch):
    """A kernel the backend's compiler refuses fails the QUERY: here the
    strings kernel is engaged non-interpreted on the CPU backend, which
    refuses it when the enclosing stage program is lowered (outside any
    try in the tier).  Default recovery confs (3 attempts, CPU fallback
    on) must neither replay it as a device loss nor finish it on the
    host."""
    if jax.default_backend() == "tpu":
        pytest.skip("the CPU backend's refusal is the injected fault")
    from spark_rapids_tpu.fault import metrics as FM
    from spark_rapids_tpu.session import TpuSparkSession
    monkeypatch.setattr(PT.jax, "default_backend", lambda: "tpu")
    s = TpuSparkSession(RapidsConf({"spark.rapids.sql.enabled": True}))
    df = s.create_dataframe({
        "uniq_refusal_probe_col": ["aa", "abq", None, "b", "xaby"]})
    before = FM.snapshot()
    with pytest.raises(ValueError, match="interpret mode") as ei:
        df.filter(df["uniq_refusal_probe_col"].contains("ab")).collect()
    assert any("compile refusal" in n for n in ei.value.__notes__)
    d = FM.delta(before, FM.snapshot())
    assert not any(d.values()), d


def test_session_counts_fallbacks():
    """A default-conf CPU session surfaces the per-query fallback delta
    as last_metrics['pallasFallbackCount'] (unique schema: a cached
    trace would skip the trace-time tier decision entirely)."""
    if jax.default_backend() == "tpu":
        pytest.skip("no fallbacks on the real kernel backend")
    from spark_rapids_tpu.session import TpuSparkSession
    s = TpuSparkSession(RapidsConf({"spark.rapids.sql.enabled": True}))
    df = s.create_dataframe({
        "uniq_pallas_probe_col": ["aa", "abq", None, "b", "xaby"],
        "uniq_pallas_probe_val": [1, 2, 3, 4, 5]})
    out = df.filter(
        df["uniq_pallas_probe_col"].contains("ab")).collect()
    assert len(out) == 2
    assert s.last_metrics["pallasFallbackCount"] >= 1
