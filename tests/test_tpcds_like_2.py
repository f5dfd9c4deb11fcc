"""TPC-DS-like query correctness, second half of the query list (see
tests/test_tpcds_like.py, which holds the first half and the checks)."""

import pytest

from test_tpcds_like import SECOND_HALF, check_query


@pytest.mark.parametrize("qname", SECOND_HALF)
def test_tpcds_like_query(qname):
    check_query(qname)
