"""Compile for the chip without the chip (on-chip-measurement guide, §2.3).

The TPU compiler is installed here and compiles for a v5e that is
DESCRIBED, not attached.  Three things no CPU test can see are pinned:

* every registered Pallas kernel's default gate is ON if and only if the
  v5e compiler accepts the kernel at the shapes TPC-H SF1 produces
  (interpret mode accepts all four; the chip's compiler does not), and
* the branches taken only when ``jax.default_backend() == "tpu"`` (the
  float-float f64 sort words, the LSD argsort, the 3-word double count in
  the join key encoding) compile for the chip, and
* Q6's keyless update batch at SF1's shape compiles to reductions, with
  no contraction left in the v5e program.

Nothing here runs on a device, so nothing here is a result or a time.
The topology is described inside a module-scoped fixture — never at
import — and every compile happens in this process (the worker that owns
this file owns libtpu's lock); this is the ONE file that may do so.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import DevVal
from spark_rapids_tpu.kernels import pallas_strings as PS
from spark_rapids_tpu.kernels import pallas_tier as PT

Mi = 1 << 20
# TPC-H SF1: lineitem = 6,000,000 rows -> the 8 Mi-row capacity bucket;
# l_shipmode is ~4.3 bytes/row -> the 32 Mi-byte buffer bucket
SF1_ROWS = 8 * Mi
SF1_STRING_BYTES = 32 * Mi
BRANCH_ROWS = 1 * Mi
# The v5e compiler's time for a program that SORTS grows with the rows:
# measured here (PR 23, compile only, nothing ran) argsort_by_words 1/3
# words 2.3/4.7 s at 16 Ki, 19.9/24.9 s at 1 Mi, 24.3/32.3 s at 8 Mi;
# join_pairs_static 10.6 s at 16 Ki, 47.8 s at 64 Ki, 70.7 s at 1 Mi.  The
# suite sits at the edge of its clock, and what these cases pin is that
# the TPU-only branch LOWERS, which does not depend on the rows — so the
# sort-bearing cases use the engine's minimum batch bucket.
SORT_BRANCH_ROWS = 16 * 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def on_chip(topo):
    """Shape factory: arguments placed on one described (not attached)
    v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return S


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without the chip (the next run warns and
    recompiles): switch the cache off around this file's compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *shapes, **static).compile()


# ---------------------------------------------------------------------------
# (i) Pallas kernels at SF1 shapes: default on <=> the compiler accepts
# ---------------------------------------------------------------------------


def _lower_strings(S):
    return _compile(
        lambda d, o: PS.contains_match(d, o, (65, 73), False),
        S((SF1_STRING_BYTES,), jnp.uint8), S((SF1_ROWS + 1,), jnp.int32))


def _lower_gather_scatter(S):
    # coalescing two 4 Mi-row batches of one int32 column into 8 Mi rows
    half = SF1_ROWS // 2
    i32 = S((), jnp.int32)
    return _compile(
        lambda a, b, l0, h0, l1, h1: PT.pack_segments(
            [a, b], [l0, l1], [h0, h1], SF1_ROWS, interpret=False),
        S((half,), jnp.int32), S((half,), jnp.int32), i32, i32, i32, i32)


def _lower_join_probe(S):
    # int64 key = 2 exact words; the largest build side the residency
    # budget admits (bytes/row: hash + perm + words + validity)
    n_words = 2
    budget = PT.PALLAS_VMEM_BUDGET.default
    r_cap = 1 << ((budget // (4 + 4 + 4 * n_words + 4)).bit_length() - 1)
    l_cap = SF1_ROWS
    return _compile(
        lambda lh, lm, rs, pm, aw, av, bw, bv: PT.probe_join(
            lh, lm, rs, pm, aw, av, bw, bv, l_cap, interpret=False),
        S((l_cap,), jnp.uint32), S((l_cap,), jnp.bool_),
        S((r_cap,), jnp.uint32), S((r_cap,), jnp.int32),
        S((n_words, l_cap), jnp.uint32), S((l_cap,), jnp.bool_),
        S((n_words, r_cap), jnp.uint32), S((r_cap,), jnp.bool_))


def _lower_string_hash(S):
    return _compile(
        lambda d, o: PT.string_hash_rows(d, o, SF1_ROWS, (31, 131),
                                         interpret=False),
        S((SF1_STRING_BYTES,), jnp.uint8), S((SF1_ROWS + 1,), jnp.int32))


_KERNEL_PROBES = {
    "strings": _lower_strings,
    "gatherScatter": _lower_gather_scatter,
    "joinProbe": _lower_join_probe,
    "stringHash": _lower_string_hash,
}


def test_every_registered_kernel_has_a_probe():
    assert sorted(_KERNEL_PROBES) == [s.name for s in PT.registered()]


@pytest.mark.parametrize("name", sorted(_KERNEL_PROBES))
def test_kernel_default_matches_v5e_compiler(name, on_chip):
    """The gate's default tells the truth: on iff the chip's compiler
    takes the kernel at SF1 shapes.  A kernel that starts compiling must
    have its default flipped ON here (and one that stops, OFF)."""
    spec = {s.name: s for s in PT.registered()}[name]
    try:
        compiled = _KERNEL_PROBES[name](on_chip)
    except Exception as e:  # noqa: BLE001 — the refusal IS the datum
        refusal = f"{type(e).__name__}: {str(e).strip()[:300]}"
    else:
        refusal = None
        assert "tpu_custom_call" in compiled.as_text(), \
            f"{name}: compiled, but no Mosaic kernel is in the program"
    assert bool(spec.entry.default) == (refusal is None), (
        f"{spec.entry.key} defaults to {spec.entry.default} but the v5e "
        f"compiler " + (f"refuses it: {refusal}" if refusal
                        else "accepts it at SF1 shapes"))


# ---------------------------------------------------------------------------
# (ii) the jax.default_backend() == "tpu" branches
# ---------------------------------------------------------------------------


@pytest.fixture
def as_tpu(monkeypatch, on_chip):
    """Steer the engine's backend probes onto their TPU branch (a
    described chip is not the default backend); hands out ``on_chip``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return on_chip


@pytest.mark.parametrize("n_words", [1, 3])
def test_argsort_by_words_compiles(n_words, as_tpu):
    from spark_rapids_tpu.kernels import sortkeys
    words = [as_tpu((SORT_BRANCH_ROWS,), jnp.uint32)] * n_words
    text = _compile(
        lambda *w: sortkeys.argsort_by_words(list(w), SORT_BRANCH_ROWS),
        *words).as_text()
    # 1 word: one direct sort; 3 words: the LSD chain, one sort per word
    assert text.count(" sort(") >= n_words, text.count(" sort(")


def test_encode_double_words_float_float_compiles(as_tpu):
    from spark_rapids_tpu.kernels import sortkeys
    c = _compile(lambda x: tuple(sortkeys._encode_double_words(x)),
                 as_tpu((BRANCH_ROWS,), jnp.float64))
    # the TPU branch is the 3-word (nan-class, hi, lo) float-float form
    assert len(c.out_info) == 3


def test_join_pairs_static_xla_compiles(as_tpu):
    from spark_rapids_tpu.kernels.join import join_pairs_static
    assert not PT.decide("joinProbe").engaged  # the XLA formulation
    n = SORT_BRANCH_ROWS

    def join(ld, lv, ln, rd, rv, rn, fd, fv, gd, gv):
        # int64 + f64 composite key: the f64 half takes the 3-word count
        return join_pairs_static(
            [DevVal(T.LONG, ld, lv), DevVal(T.DOUBLE, fd, fv)], ln,
            [DevVal(T.LONG, rd, rv), DevVal(T.DOUBLE, gd, gv)], rn, n)

    i64, f64 = as_tpu((n,), jnp.int64), as_tpu((n,), jnp.float64)
    ok, rows = as_tpu((n,), jnp.bool_), as_tpu((), jnp.int32)
    _compile(join, i64, ok, rows, i64, ok, rows, f64, ok, f64, ok)


def test_flagship_groupby_stage_compiles(as_tpu):
    """__graft_entry__.entry(): filter -> project -> groupby_aggregate,
    the engine's hot path as one program (int + f64 columns)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "__graft_entry__.py"))
    graft = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(graft)
    step, (batch,) = graft.entry()
    big = graft._flagship_batch(SORT_BRANCH_ROWS)
    shapes = jax.tree_util.tree_map(
        lambda a: as_tpu(a.shape, a.dtype), big)
    _compile(step, shapes)


def test_keyless_update_batch_compiles_to_reductions(on_chip):
    """Q6's update batch at SF1's shape (a million rows, f64 columns, the
    predicate inside the sum's argument) through ``keyless_aggregate``:
    the v5e program holds reductions and no contraction — no ``dot`` or
    ``convolution``, nothing 8,194 slots wide (PR 29; PERF.md section 5)."""
    from spark_rapids_tpu.batch import ColumnBatch
    from spark_rapids_tpu.exprs.aggregates import Sum
    from spark_rapids_tpu.exprs.base import ColumnRef
    from spark_rapids_tpu.kernels.hashagg import keyless_aggregate
    cap, none = BRANCH_ROWS, T.Schema([])
    fns = [Sum(ColumnRef("revenue", T.DOUBLE))]

    def update(price, discount, quantity, ok, rows):
        keep = (discount >= 0.05) & (discount <= 0.07) & (quantity < 24.0)
        value = DevVal(T.DOUBLE, price * discount, ok & keep)
        _keys, bufs, flag = keyless_aggregate(
            ColumnBatch(none, [], rows, cap), [value], fns, none)
        return [(b.data, b.validity) for bs in bufs for b in bs], flag

    f64 = on_chip((cap,), jnp.float64)
    text = _compile(update, f64, f64, f64, on_chip((cap,), jnp.bool_),
                    on_chip((), jnp.int32)).as_text()
    entry = text[text.index("ENTRY"):]
    assert " dot(" not in text and " convolution(" not in text
    assert "8194" not in entry
    assert " reduce(" in text or "reduce_fusion" in entry


def test_q1_update_batch_compiles_to_a_narrow_contraction(on_chip):
    """Q1's update batch at SF1's shape — a million rows, the filter's
    compaction, two dictionary-encoded string keys of a few entries,
    seven DOUBLE sums/averages and a count — through
    ``hash_group_aggregate``: the v5e program sorts nothing, and its slot
    table is the one lane tile the dictionaries allow, not 8,194 wide."""
    from spark_rapids_tpu.batch import ColumnBatch, DeviceColumn
    from spark_rapids_tpu.exprs.aggregates import (
        Average, Sum, count_star,
    )
    from spark_rapids_tpu.exprs.base import ColumnRef
    from spark_rapids_tpu.kernels.hashagg import hash_group_aggregate
    from spark_rapids_tpu.kernels.layout import compact
    cap, entries, n_f = BRANCH_ROWS, 8, 4
    D = [ColumnRef(f"d{i}", T.DOUBLE) for i in range(n_f)]
    fns = [Sum(D[0]), Sum(D[1]), Sum(D[2]), Sum(D[3]), Average(D[0]),
           Average(D[1]), Average(D[2]), count_star()]
    keys = T.Schema([("flag", T.STRING), ("status", T.STRING)])
    cols = T.Schema(list(keys.fields) + [(d.column, T.DOUBLE) for d in D])

    def update(dict_bytes, dict_offsets, codes_a, codes_b, ok, keep, rows,
               *doubles):
        batch = compact(ColumnBatch(cols, [
            DeviceColumn(T.STRING, dict_bytes, ok, dict_offsets, c, cap)
            for c in (codes_a, codes_b)] + [
            DeviceColumn(T.DOUBLE, d, ok) for d in doubles], rows, cap),
            keep, keep_encoded=True)
        vals = [DevVal.from_column(c) for c in batch.columns[2:]]
        inputs = vals + vals[:3] + [DevVal(
            T.INT, jnp.ones(cap, jnp.int32), jnp.ones(cap, jnp.bool_))]
        group_keys, bufs, n, flag = hash_group_aggregate(
            batch, [DevVal.from_column_encoded(c)
                    for c in batch.columns[:2]], inputs, fns, keys, keys)
        return ([(c.data, c.validity, c.offsets) for c in group_keys.columns],
                [(b.data, b.validity) for bs in bufs for b in bs], n, flag)

    i32, flags = on_chip((cap,), jnp.int32), on_chip((cap,), jnp.bool_)
    text = _compile(
        update, on_chip((16,), jnp.uint8), on_chip((entries + 1,), jnp.int32),
        i32, i32, flags, flags, on_chip((), jnp.int32),
        *[on_chip((cap,), jnp.float64)] * n_f).as_text()
    assert " sort(" not in text
    assert "8194" not in text[text.index("ENTRY"):]
    assert " convolution(" in text or " dot(" in text   # the contraction
