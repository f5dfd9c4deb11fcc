"""Pallas TPU kernel for the string contains/LIKE '%needle%' scan
(reference: stringFunctions.scala's dedicated native contains kernel
over libcudf).

The XLA path (exprs/strings.py:_find_matches + _rows_with_match) costs:
L shifted gathers over the byte buffer, a per-byte ``searchsorted`` over
the offsets (log(cap) passes) and a segment-sum.  This kernel folds the
whole match scan into ONE pass over the byte buffer:

  match[p] = (AND_k data[p+k] == needle[k])        # needle bytes, static
           & NOT (OR_{k=1..L-1} is_start[p+k])     # stays inside one row

with the needle bytes baked into the program (literal needles only — the
same restriction the planner already enforces for device execution).
The per-row reduction then avoids ``rows_of_positions`` entirely:

  has[r] = cumsum(match)[off[r+1]] - cumsum(match)[off[r]] > 0

which is one cumsum pass + O(cap) gathers instead of O(nbytes log cap).

Layout: the byte buffer rides as 1-D u8 blocks; each program reads its
block AND the next block (a second BlockSpec shifted by one — Pallas
blocks cannot overlap, so the halo is expressed as a duplicate input)
and emits BLOCK match flags via L static slices of the concatenation.
The u8 halves are widened to i32 BEFORE the concatenate: the v5e Mosaic
compiler refuses ``tpu.concatenate`` of two ``vector<16384xi8>``
("Invalid input layout"), and accepts the i32 form.

Used automatically for Contains/Like-contains when the backend is a real
TPU: exprs/strings.py routes through the kernel tier's ``strings`` entry
(kernels.pallas_tier — conf gate ``spark.rapids.sql.tpu.pallas.strings.
enabled``, interpret mode under ``pallas.interpret``); the XLA
formulation remains the CPU-backend path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from spark_rapids_tpu.utils.compile_registry import instrumented_jit

BLOCK = 16384  # bytes of match output per program (128-aligned)


def _match_kernel(cur_ref, nxt_ref, scur_ref, snxt_ref, out_ref, *,
                  needle: tuple, block: int):
    i32 = jnp.int32
    x = jnp.concatenate([cur_ref[...].astype(i32), nxt_ref[...].astype(i32)])
    m = x[0:block] == i32(needle[0])
    for k in range(1, len(needle)):
        m = m & (x[k:k + block] == i32(needle[k]))
    if len(needle) > 1:
        s = jnp.concatenate([scur_ref[...].astype(i32),
                             snxt_ref[...].astype(i32)])
        cross = s[1:1 + block] != 0
        for k in range(2, len(needle)):
            cross = cross | (s[k:k + block] != 0)
        m = m & ~cross
    out_ref[...] = m.astype(jnp.int32)


@instrumented_jit(label="pallas:contains",
                  static_argnames=("needle", "interpret"))
def contains_match(data, offsets, needle: tuple, interpret: bool = False):
    """int32[nbytes_padded]: 1 where ``needle`` (tuple of byte values)
    matches starting at this byte position without crossing a row
    boundary.  ``data`` u8[nbytes], ``offsets`` int32[cap+1]."""
    from jax.experimental import pallas as pl

    nbytes = int(data.shape[0])
    padded = -(-nbytes // BLOCK) * BLOCK
    nblocks = padded // BLOCK
    if padded != nbytes:
        data = jnp.concatenate(
            [data, jnp.zeros(padded - nbytes, jnp.uint8)])
    # row-start mask: one O(cap) scatter.  ALL offsets are marked
    # (including the live-data end) so a match cannot extend into the
    # garbage region past the last row; index==padded drops harmlessly.
    starts = jnp.zeros(padded, jnp.uint8).at[offsets].set(1, mode="drop")

    spec_cur = pl.BlockSpec((BLOCK,), lambda i: (i,))
    spec_nxt = pl.BlockSpec(
        (BLOCK,), lambda i: (jnp.minimum(i + 1, nblocks - 1),))
    kernel = functools.partial(_match_kernel, needle=needle, block=BLOCK)
    out = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[spec_cur, spec_nxt, spec_cur, spec_nxt],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded,), jnp.int32),
        interpret=interpret,
    )(data, data, starts, starts)
    # the last block's halo duplicates itself (there is no next block);
    # kill any match that would need bytes past the live end — also
    # covers garbage bytes beyond offsets[-1] (buffer caps > live bytes)
    pos = jnp.arange(padded, dtype=jnp.int32)
    return out * (pos + len(needle) <= offsets[-1]).astype(jnp.int32)


def rows_with_match(data, offsets, validity, cap: int, needle: bytes,
                    interpret: bool):
    """bool[cap]: row contains ``needle`` — the Pallas-backed analogue of
    exprs.strings._rows_with_match (``interpret`` is the tier's
    decision)."""
    if len(needle) == 0:
        return jnp.ones(cap, dtype=jnp.bool_)
    match = contains_match(data, offsets, tuple(needle), interpret)
    # exclusive cumsum -> per-row match counts via two O(cap) gathers
    c = jnp.concatenate([jnp.zeros(1, jnp.int32),
                         jnp.cumsum(match).astype(jnp.int32)])
    padded = int(match.shape[0])
    off = jnp.clip(offsets.astype(jnp.int32), 0, padded)
    return (c[off[1:]] - c[off[:-1]]) > 0
