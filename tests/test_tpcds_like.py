"""TPC-DS-like query correctness at SF0.1: every query runs on the TPU
engine and the CPU engine and must agree (TpcdsLikeSpark suite analogue).

This file runs the first half of the (sorted) query list and
tests/test_tpcds_like_2.py the second: as one file it took 1118 s of a
1133 s six-worker run (``--dist loadfile`` hands a whole file to one
worker), so it alone set the suite's wall time."""

import pytest

from spark_rapids_tpu.benchmarks.tpcds_like import QUERIES, register_tpcds

from compare import assert_tpu_cpu_equal

SF = 0.1


# The reference runs its whole tpcds suite with variableFloatAgg on,
# except q67/q70 (tpcds_test.py:21-50) — mirror that so float sums/avgs
# genuinely run on the device plan instead of falling back.
NO_VAR_AGG = {"q67", "q70"}


_SORTED = sorted(QUERIES.keys())
FIRST_HALF = _SORTED[:len(_SORTED) // 2]
SECOND_HALF = _SORTED[len(_SORTED) // 2:]


def check_query(qname):
    def build(s):
        register_tpcds(s, sf=SF, num_partitions=3)
        return s.sql(QUERIES[qname])

    confs = {} if qname in NO_VAR_AGG else \
        {"spark.rapids.sql.variableFloatAgg.enabled": True}
    assert_tpu_cpu_equal(build, approx=True, ignore_order=False,
                         confs=confs)


@pytest.mark.parametrize("qname", FIRST_HALF)
def test_tpcds_like_query(qname):
    check_query(qname)


def test_tpcds_reference_coverage_has_no_holes():
    """The suite covers the reference's FULL 103-query tpcds list
    (tpcds_test.py: q1..q99 with the q14/q23/q24/q39 a/b variants) with
    no holes and no skip markers — q72 and q77 in particular run as
    first-class parametrized cases, not gaps."""
    ab = {14, 23, 24, 39}
    reference = []
    for i in range(1, 100):
        if i in ab:
            reference += [f"q{i}a", f"q{i}b"]
        else:
            reference.append(f"q{i}")
    assert len(reference) == 103
    missing = [q for q in reference if q not in QUERIES]
    assert not missing, f"tpcds coverage holes: {missing}"
    assert "q72" in QUERIES and "q77" in QUERIES
    # every query is a live parametrized case: the conf split (NO_VAR_AGG)
    # only changes confs, it never skips
    assert NO_VAR_AGG < set(QUERIES)


def test_tpcds_bench_report(tmp_path):
    from compare import tpu_session
    from spark_rapids_tpu.benchmarks.bench_utils import run_bench
    s = tpu_session()
    register_tpcds(s, sf=0.05, num_partitions=2)
    path = str(tmp_path / "tpcds_report.json")
    rep = run_bench(s, "q55", lambda: s.sql(QUERIES["q55"]),
                    iterations=1, warmups=0, report_path=path)
    assert rep["result_rows"] >= 1
