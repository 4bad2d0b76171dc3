"""Pallas LSD radix sort over the hash-key lanes + fused partition plan.

The reference's whole shuffle is a sort (per-mapper sorted k/v files plus
a k-way heap merge, fs.lua/heap.lua); the device twin inherited that as a
``lax.sort`` comparator — the ~100s cold-compile monster that forced the
argsort tier.  Keys here are already uint32 hashes, so radix — not
comparison — is the natural formulation.  This module provides:

``radix_sort_pairs(k1, k2)``
    Stable least-significant-digit radix sort of the 64-bit key formed by
    ``(k1 hi, k2 lo)``, returning ``(k1s, k2s, perm)`` bit-identical to
    ``jax.lax.sort((k1, k2, iota), num_keys=2)``: 4-bit digits, 8 passes
    per 32-bit lane (16 total), each pass a tile-local digit histogram
    kernel (``radix_hist``) → exclusive prefix-sums across tiles via the
    segscan ladder → a stable in-kernel scatter by rank
    (``radix_scatter``).  Stability is structural: within a tile the rank
    is an input-order cumulative count, across tiles the prefix offsets
    preserve tile order, so equal keys keep input order in every pass and
    LSD induction pins the whole sort — no comparator, no iota tie-break
    lane in the sort itself (``perm`` rides along as a payload lane).

``radix_partition_plan(dest, num_partitions)``
    The fused-exchange half: one histogram pass over the destination
    digit yields BOTH the per-destination row counts (the exchange
    traffic-matrix row, bit-equal to the classic
    ``onehot.sum(axis=0)`` count pass it deletes) and the stable
    per-destination scatter ranks that place each record in its
    destination bucket (``radix_rank`` kernel).

Unsigned bit order == unsigned numeric order, so the full uint32 range
(including sign-bit edge values 0x7FFFFFFF/0x80000000 and the 0xFFFFFFFF
sentinel) sorts correctly with no bias step.

Off-TPU the kernels run under the Pallas interpreter via
``pallas_compat`` (the in-kernel scatter is jnp ``.at[].set`` — exact in
interpret mode; on TPU it lowers through Mosaic's scatter path, the one
stage of this module that is TPU-generation sensitive).  Like every
kernel module this file is under the monotonic-only AST lint: it must
read no clocks at all.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import pallas_compat
from .segscan import ladder_cumsum

#: Digit width of one LSD pass.  4 bits / 16 buckets keeps the per-pass
#: onehot-rank work at 16 lanes per element (~256 ops over all 16 passes,
#: comparable to the comparator formulation's n·log n) while the pass
#: count stays low enough that 2 lanes × 8 passes cover uint32.
RADIX_BITS = 4
RADIX = 1 << RADIX_BITS
_DIGIT_MASK = np.uint32(RADIX - 1)
#: Passes over the 64-bit (k1 hi, k2 lo) key.
RADIX_PASSES = 2 * (32 // RADIX_BITS)
#: Default elements per tile (one grid step); multiple of the 128-lane
#: TPU vector width.
RADIX_BLOCK = 4096
_LANES = 128
_SENT = np.uint32(0xFFFFFFFF)


def _blocking(n: int, block: Optional[int]) -> Tuple[int, int, int]:
    """Round ``n`` up to tiles: returns (npad, tiles, block)."""
    b = RADIX_BLOCK if block is None else int(block)
    b = max(_LANES, (b // _LANES) * _LANES)
    npad = -(-max(int(n), 1) // b) * b
    return npad, npad // b, b


def _tile_offsets(hist: jax.Array) -> jax.Array:
    """Exclusive prefix over the tile axis, per digit: [T, R] -> [T, R].

    Reuses the segscan ladder (inclusive cumsum along the last axis) by
    transposing the tile axis into lane position.
    """
    return ladder_cumsum(hist.T).T - hist


def _digit_base(hist: jax.Array) -> jax.Array:
    """Exclusive prefix of digit totals: [T, R] -> [R]."""
    tot = jnp.sum(hist, axis=0)
    return ladder_cumsum(tot) - tot


# -- kernels -----------------------------------------------------------------


def _hist_kernel(pids, d_ref, h_ref, *, nbuckets):
    """Per-tile digit histogram: d [1, B] int32 -> h [1, R] int32."""
    d = d_ref[0, :]
    iota = jax.lax.broadcasted_iota(jnp.int32, (d.shape[0], nbuckets), 1)
    onehot = (d[:, None] == iota).astype(jnp.int32)
    h_ref[0, :] = jnp.sum(onehot, axis=0)


def _stable_rank(d, nbuckets):
    """Input-order rank of each element among equal digits in its tile."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (d.shape[0], nbuckets), 1)
    onehot = (d[:, None] == iota).astype(jnp.int32)
    csum = jnp.cumsum(onehot, axis=0)
    return jnp.take_along_axis(csum - 1, d[:, None], axis=1)[:, 0]


def _rank_kernel(pids, d_ref, off_ref, r_ref, *, nbuckets):
    """Global stable rank within each digit bucket (fused-exchange path):
    d [1, B], off [1, R] (exclusive tile offsets) -> r [1, B]."""
    d = d_ref[0, :]
    r_ref[0, :] = off_ref[0, :][d] + _stable_rank(d, nbuckets)


def _scatter_kernel(pids, d_ref, off_ref, a1_ref, a2_ref, p_ref,
                    o1_ref, o2_ref, op_ref, *, nbuckets):
    """Stable scatter of one tile's lanes to global sorted positions.

    Outputs are full-array blocks revisited by every grid step; each
    global position is written exactly once across the grid because the
    per-pass destination map is a permutation.
    """
    from jax.experimental import pallas as pl

    @pl.when(pids[0] == 0)
    def _init():
        for ref in (o1_ref, o2_ref, op_ref):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    d = d_ref[0, :]
    pos = off_ref[0, :][d] + _stable_rank(d, nbuckets)
    for src, dst in ((a1_ref, o1_ref), (a2_ref, o2_ref), (p_ref, op_ref)):
        cur = dst[...]
        dst[...] = cur.at[0, pos].set(src[0, :])


# -- kernel callers ----------------------------------------------------------


def _tile_hist(d2, nbuckets, interpret):
    """d2 [T, B] int32 -> per-tile digit histogram [T, R] int32."""
    from jax.experimental import pallas as pl

    tiles, block = d2.shape
    return pallas_compat.pallas_call(
        functools.partial(_hist_kernel, nbuckets=nbuckets),
        name="radix_hist",
        interpret=interpret,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, nbuckets), lambda i: (i, 0)),
        out_shape=pallas_compat.sds((tiles, nbuckets), jnp.int32, d2),
    )(d2)


def _tile_rank(d2, off, nbuckets, interpret):
    """Global stable ranks: d2 [T, B], off [T, R] -> [T, B] int32."""
    from jax.experimental import pallas as pl

    tiles, block = d2.shape
    return pallas_compat.pallas_call(
        functools.partial(_rank_kernel, nbuckets=nbuckets),
        name="radix_rank",
        interpret=interpret,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0)),
                  pl.BlockSpec((1, nbuckets), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, block), lambda i: (i, 0)),
        out_shape=pallas_compat.sds((tiles, block), jnp.int32, d2),
    )(d2, off)


def _tile_scatter(d2, off, a1, a2, pr, interpret):
    """One stable scatter pass: tile lanes -> globally sorted lanes."""
    from jax.experimental import pallas as pl

    tiles, block = d2.shape
    npad = tiles * block
    tile = pl.BlockSpec((1, block), lambda i: (i, 0))
    full = pl.BlockSpec((1, npad), lambda i: (0, 0))
    o1, o2, op_ = pallas_compat.pallas_call(
        functools.partial(_scatter_kernel, nbuckets=RADIX),
        name="radix_scatter",
        interpret=interpret,
        grid=(tiles,),
        in_specs=[tile, pl.BlockSpec((1, RADIX), lambda i: (i, 0)),
                  tile, tile, tile],
        out_specs=[full, full, full],
        out_shape=[pallas_compat.sds((1, npad), jnp.uint32, a1),
                   pallas_compat.sds((1, npad), jnp.uint32, a2),
                   pallas_compat.sds((1, npad), jnp.int32, a1)],
    )(d2, off, a1, a2, pr)
    return o1[0], o2[0], op_[0]


# -- public API --------------------------------------------------------------


def _radix_pass(digits, a1, a2, pr, tiles, block, interpret):
    d2 = digits.reshape(tiles, block)
    hist = _tile_hist(d2, RADIX, interpret)
    off = _digit_base(hist)[None, :] + _tile_offsets(hist)
    return _tile_scatter(d2, off, a1.reshape(tiles, block),
                         a2.reshape(tiles, block),
                         pr.reshape(tiles, block), interpret)


def radix_sort_pairs(k1: jax.Array, k2: jax.Array, *,
                     block: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stable radix sort by the 64-bit key ``(k1 hi, k2 lo)``.

    Returns ``(k1s, k2s, perm)`` bit-identical to
    ``jax.lax.sort((k1, k2, arange(n, int32)), num_keys=2)``; gather any
    further record lanes by ``perm``.  ``k1``/``k2`` must be uint32.
    """
    n = int(k1.shape[0])
    if n == 0:
        return k1, k2, jnp.zeros((0,), jnp.int32)
    npad, tiles, blk = _blocking(n, block)
    pad = npad - n
    # Pad rows carry the maximal key and come after every real row, so
    # stability keeps them in the tail slots [n:npad] and the truncation
    # below is exact.
    a1 = jnp.pad(k1, (0, pad), constant_values=_SENT)
    a2 = jnp.pad(k2, (0, pad), constant_values=_SENT)
    pr = jnp.arange(npad, dtype=jnp.int32)
    # the permutation lane leaves every pass device-varying like the
    # keys it follows; it must enter the scan carry typed the same way
    vma = tuple(jax.typeof(a1).vma)
    if vma:
        pr = jax.lax.pcast(pr, vma, to="varying")

    # One lax.scan per key lane over the 8 digit shifts: the pass body
    # (two kernel programs) is traced ONCE per lane instead of 8 times,
    # an ~8x cut in trace/compile work with bit-identical semantics —
    # the shift rides as a traced scalar through the digit extraction.
    def _lane_pass(lane):
        def body(carry, shift):
            a1, a2, pr = carry
            src = a2 if lane == 1 else a1
            digits = ((src >> shift) & _DIGIT_MASK).astype(jnp.int32)
            return _radix_pass(digits, a1, a2, pr, tiles, blk,
                               interpret), None
        return body

    shifts = jnp.arange(0, 32, RADIX_BITS, dtype=jnp.uint32)
    for lane in (1, 0):  # low lane first: LSD over the 64-bit key
        (a1, a2, pr), _ = jax.lax.scan(_lane_pass(lane), (a1, a2, pr),
                                       shifts)
    return a1[:n], a2[:n], pr[:n]


def radix_partition_plan(dest: jax.Array, num_partitions: int, *,
                         block: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         ) -> Tuple[jax.Array, jax.Array]:
    """Fused-exchange plan from one destination-digit histogram pass.

    ``dest`` is int32 in ``[0, P]`` where ``P == num_partitions`` marks a
    dropped (invalid) row — the encoding ``partition_exchange`` already
    produces.  Returns ``(rank, counts)``:

    - ``rank`` [n] int32: stable input-order index of each row within
      its destination bucket (rows marked ``P`` rank among themselves
      and are dropped by the out-of-bounds scatter downstream);
    - ``counts`` [P] int32: valid rows per destination **before**
      capacity capping — the exchange traffic-matrix row, bit-equal to
      the classic ``onehot.sum(axis=0)`` recompute this plan deletes.

    One histogram kernel feeds both: the per-tile exclusive prefix is
    the scatter offset ladder, the digit totals are the matrix row.
    """
    p = int(num_partitions)
    nbuckets = p + 1  # one overflow bucket for dropped rows
    n = int(dest.shape[0])
    if n == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.zeros((p,), jnp.int32))
    npad, tiles, blk = _blocking(n, block)
    d = jnp.pad(dest.astype(jnp.int32), (0, npad - n), constant_values=p)
    d2 = d.reshape(tiles, blk)
    hist = _tile_hist(d2, nbuckets, interpret)
    rank = _tile_rank(d2, _tile_offsets(hist), nbuckets, interpret)
    counts = jnp.sum(hist, axis=0)[:p]
    return rank.reshape(-1)[:n], counts
