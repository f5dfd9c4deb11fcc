"""Pallas contains-scan kernel vs the XLA formulation (interpret mode on
the CPU backend; the real-TPU lowering is exercised by the chip run)."""

import numpy as np
import pytest


def _make_col(strings):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.batch import HostBatch, host_to_device
    hb = HostBatch.from_pydict({"s": (T.STRING, strings)})
    db = host_to_device(hb)
    return db.columns[0], db.num_rows, db.capacity


@pytest.mark.parametrize("needle", ["ab", "aba", "x", "needle", "zz"])
def test_pallas_contains_matches_xla(needle):
    from spark_rapids_tpu.exprs.base import DevVal
    from spark_rapids_tpu.exprs import strings as S
    from spark_rapids_tpu.kernels import pallas_strings as PS

    rng = np.random.RandomState(7)
    alphabet = list("abnexzle")
    strs = ["".join(rng.choice(alphabet, rng.randint(0, 12)))
            for _ in range(200)]
    strs[3] = ""
    strs[5] = needle
    strs[7] = "q" + needle + "q"
    col, num_rows, cap = _make_col(strs)
    v = DevVal(col.dtype, col.data, col.validity, col.offsets)

    got = np.asarray(PS.rows_with_match(
        v.data, v.offsets, v.validity, cap, needle.encode(),
        interpret=True))
    want = np.asarray(S._find_matches_reference(v, needle.encode())) \
        if hasattr(S, "_find_matches_reference") else None
    # oracle: python substring check
    expect = np.zeros(cap, dtype=bool)
    for i, s in enumerate(strs):
        expect[i] = needle in s
    np.testing.assert_array_equal(got[:len(strs)], expect[:len(strs)])
    if want is not None:
        np.testing.assert_array_equal(got, want)


def test_pallas_boundary_no_cross():
    """A needle split across two adjacent rows must NOT match."""
    from spark_rapids_tpu.exprs.base import DevVal
    from spark_rapids_tpu.kernels import pallas_strings as PS

    strs = ["xxa", "bxx", "ab", "a", "b"]
    col, num_rows, cap = _make_col(strs)
    v = DevVal(col.dtype, col.data, col.validity, col.offsets)
    got = np.asarray(PS.rows_with_match(
        v.data, v.offsets, v.validity, cap, b"ab", interpret=True))
    np.testing.assert_array_equal(
        got[:5], np.array([False, False, True, False, False]))
