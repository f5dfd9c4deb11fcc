"""CPU-vs-TPU query compare harness — the SparkQueryCompareTestSuite
analogue (reference tests/: every test body runs under a CPU session and a
TPU session and the collected results must match)."""

import jax
import pytest

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession
from spark_rapids_tpu.utils import compile_registry as CR

from conftest import FLOAT_ABS, FLOAT_REL, TEST_PLATFORM


def cpu_session(**confs) -> TpuSparkSession:
    conf = RapidsConf({"spark.rapids.sql.enabled": False,
                       "spark.sql.shuffle.partitions": 4})
    for k, v in confs.items():
        conf.set(k, v)
    return TpuSparkSession(conf)


def tpu_session(**confs) -> TpuSparkSession:
    conf = RapidsConf({"spark.rapids.sql.enabled": True,
                       "spark.sql.shuffle.partitions": 4})
    for k, v in confs.items():
        conf.set(k, v)
    return TpuSparkSession(conf)


def _canon(rows, approx, ignore_order):
    approx = approx or TEST_PLATFORM == "tpu"

    def enc(v):
        if v is None:
            return (0, "")
        if isinstance(v, float):
            if v != v:
                return (1, "NaN")
            # No absolute-decimal rounding: _row_approx_eq compares with
            # RELATIVE tolerance, so large magnitudes (where 3 decimals is
            # far tighter than f64-emulation error) and tiny ones (where
            # it is uselessly loose) are both judged proportionally.
            return (1, v)
        if isinstance(v, bool):
            return (2, v)
        return (3, str(v)) if not isinstance(v, (int, float)) else (1, v)

    def sort_key(r):
        # floats keyed by a relative (significant-digit) canonicalization
        # so near-equal CPU/TPU values land in the same sort position
        return str(tuple(
            (t, float(f"{v:.6g}")) if isinstance(v, float) else (t, v)
            for t, v in r))

    out = [tuple(enc(v) for v in r) for r in rows]
    if ignore_order:
        out = sorted(out, key=sort_key)
    return out


def assert_tpu_cpu_equal(build_fn, approx=False, ignore_order=True,
                         confs=None, expect_fallback=None,
                         forbid_fallback=None):
    """build_fn(session) -> DataFrame; runs on both engines and compares.

    expect_fallback: optional operator-name substring expected in the explain
    output's cannot-run list (assert_gpu_fallback_collect analogue).
    forbid_fallback: operator-name substring that must NOT appear in the
    cannot-run list — guards against a regression test silently comparing
    CPU against CPU.
    """
    confs = confs or {}
    cpu = cpu_session(**confs)
    tpu = tpu_session(**confs)
    cpu_rows = build_fn(cpu).collect()
    df = build_fn(tpu)
    tpu_rows = df.collect()
    if expect_fallback:
        explain = tpu.last_explain
        assert expect_fallback in explain and "cannot run on TPU" in explain, \
            f"expected fallback of {expect_fallback}; explain:\n{explain}"
    if forbid_fallback:
        explain = tpu.last_explain
        assert not any(forbid_fallback in ln for ln in
                       explain.splitlines() if "cannot run on TPU" in ln), \
            f"unexpected fallback of {forbid_fallback}; explain:\n{explain}"
    a = _canon(cpu_rows, approx, ignore_order)
    b = _canon(tpu_rows, approx, ignore_order)
    assert len(a) == len(b), \
        f"row count: cpu={len(a)} tpu={len(b)}\ncpu={a[:10]}\ntpu={b[:10]}"
    for i, (ra, rb) in enumerate(zip(a, b)):
        if approx or TEST_PLATFORM == "tpu":
            _row_approx_eq(ra, rb, i)
        else:
            assert ra == rb, f"row {i}: cpu={ra} tpu={rb}"


def _row_approx_eq(ra, rb, i):
    assert len(ra) == len(rb), f"row {i} width"
    for (ta, va), (tb, vb) in zip(ra, rb):
        assert ta == tb, f"row {i}: {va!r} vs {vb!r}"
        if isinstance(va, float) and isinstance(vb, float):
            # rel dominates for large magnitudes; the abs floor covers
            # near-zero values (where the old 6-decimal rounding was
            # effectively a ~5e-7 absolute tolerance)
            assert vb == pytest.approx(va, rel=max(FLOAT_REL, 1e-5),
                                       abs=max(FLOAT_ABS, 1e-6)), f"row {i}"
        else:
            assert va == vb, f"row {i}: {va!r} vs {vb!r}"


class _LoweringJax:
    """Stands in for ``jax`` inside compile_registry: ``jit`` keeps the
    lowered text (with debug info) of every program's first call."""

    def __init__(self, texts):
        self._texts = texts

    def __getattr__(self, name):
        return getattr(jax, name)

    def jit(self, fn, **kw):
        real, texts = jax.jit(fn, **kw), self._texts

        class Jitted:
            def __call__(self, *a, **k):
                if fn.__name__ not in texts:
                    texts[fn.__name__] = real.lower(*a, **k).as_text(
                        debug_info=True)
                return real(*a, **k)

            def _cache_size(self):
                return real._cache_size()

        return Jitted()


def lowered_stage_texts(monkeypatch, build_df, **confs):
    texts = {}
    monkeypatch.setattr(CR, "jax", _LoweringJax(texts))
    s = tpu_session(**confs)
    build_df(s).collect()
    return s, texts
