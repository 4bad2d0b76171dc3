"""Unrolled log-step scans and sorted-run reduction for huge record arrays.

The aggregation core of the sort-hierarchy engine: after ``lax.sort``
groups equal keys into runs, everything else is O(N) elementwise work plus
O(log N) shifted passes — no scatters at record granularity, the operation
TPU XLA executes pathologically (measured ~100M el/s on v5e vs ~160M
rows/s for its tuned sort and near-peak elementwise throughput).

All scans here are Hillis-Steele ladders of STATIC shifts (pad + slice),
the same formulation as ops/tokenize: log2(N) full-array passes that XLA
compiles in seconds and runs at HBM bandwidth.  ``jnp.cumsum`` /
``associative_scan`` are avoided on multi-million-element arrays because
their recursive lowering compiles pathologically on TPU (>10 min at 4M,
measured in round 1).

``segmented_scan`` takes an ARBITRARY traceable associative ``op`` — this
is what lets the device path accept any user monoid, not just
{sum,min,max} (the compiler-visible form of the reference's
associative/commutative/idempotent reducer flags, reducefn.lua:10-14).

The post-sort stage also has a fused Pallas formulation
(``segment_impl='pallas'``, the ``_segreduce_kernel`` below): boundary
detection + segmented combine + run-end count in ONE VMEM-tiled pass
instead of the ladders' log2(N) full-array passes, bit-identical for
the engine's integer monoids and pinned by tests/test_pallas_ops.py.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_compat

# jax.experimental.pallas is imported lazily inside the kernel/wrapper
# functions: this module rides every engine import, and processes that
# never select segment_impl='pallas' should not pay the pallas import

#: sentinel key lane value marking invalid rows (sorts to the end);
#: real keys equal to the sentinel pair are remapped to (0, 0) — here and
#: at record-buffer build time (device_engine step) — so
#: (SENTINEL, SENTINEL) is unambiguous.
SENTINEL = jnp.uint32(0xFFFFFFFF)
#: its int32 bit pattern for Pallas kernel bodies, which see the key
#: lanes bitcast to int32 (a module-level jnp constant would be a
#: captured traced array, which pallas_call refuses)
_SENT = np.int32(-1)

#: lane width of the fused segmented-reduce kernel's 2-D layout (the
#: flattened record order is row-major over [rows, _SEG_LANES])
_SEG_LANES = 128
#: default elements per VMEM-tiled kernel block (multiple of _SEG_LANES;
#: EngineConfig.segment_block overrides and fingerprints it)
SEGMENT_BLOCK = 4096


def _shift_right(x: jax.Array, d: int, fill) -> jax.Array:
    pad = jnp.full(x.shape[:-1] + (d,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-d]], axis=-1)


def ladder_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive cumsum along the last axis (log2(N) shifted adds)."""
    L = x.shape[-1]
    d = 1
    while d < L:
        x = x + _shift_right(x, d, 0)
        d *= 2
    return x


def ladder_cummax(x: jax.Array) -> jax.Array:
    """Inclusive running max along the last axis."""
    L = x.shape[-1]
    lowest = (jnp.iinfo(x.dtype).min
              if jnp.issubdtype(x.dtype, jnp.integer) else -jnp.inf)
    d = 1
    while d < L:
        x = jnp.maximum(x, _shift_right(x, d, lowest))
        d *= 2
    return x


def segmented_scan(op: Callable, starts: jax.Array,
                   values: jax.Array) -> jax.Array:
    """Inclusive scan of *values* with *op*, restarting at each set bit of
    *starts* (segment heads).  ``op`` must be associative; values [N] or
    [N, D] (the ladder shifts along axis 0, so D lanes ride along).

    The classic segmented-combine is itself associative, so the ladder
    applies: ``(f_l, v_l) then (f, v) -> (f | f_l, f ? v : op(v_l, v))``.

    Precondition: ``starts[0]`` must be True unless the entire input is
    dead weight (positions before the first segment head produce junk —
    sorted_unique_reduce guarantees this by making row 0 a head).
    """
    N = starts.shape[0]
    f = starts
    v = values
    d = 1
    while d < N:
        f_l = jnp.concatenate([jnp.ones((d,), bool), f[:-d]])
        v_l = jnp.concatenate([v[:d], v[:-d]], axis=0)  # fill junk, masked
        blocked = f  # segment head: left neighbour is another segment
        combined = op(v_l, v)
        if v.ndim > 1:
            take = blocked[:, None] if v.ndim == 2 else blocked.reshape(
                (-1,) + (1,) * (v.ndim - 1))
        else:
            take = blocked
        v = jnp.where(take, v, combined)
        f = f | f_l
        d *= 2
    return v


# -- the fused Pallas segmented-reduce kernel (segment_impl='pallas') --------
#
# One VMEM-tiled pass over the sorted lanes replaces the lax ladder
# chain: run-boundary detection (shifted key compares, the previous/next
# element carried across blocks), the segmented combine (or run-length
# count), and the run-end cumulative count all happen per block, with
# the cross-block state — last key, running combine value, running end
# count — in kernel scratch that persists across the sequential grid.
# The lax formulation pays log2(N) full-array HBM passes per ladder
# (segmented_scan + ladder_cumsum + ladder_cummax); the kernel reads and
# writes each record once.  Bit-identity to the lax path holds for any
# integer monoid (the engine's contract): integer ops are associative in
# machine arithmetic, so the kernel's two-level association order
# produces identical bits, and the boundary/count lanes are exact by
# construction (the golden suite pins it, ops- and engine-level).


def _seg_scan(f, v, op: Callable, shift, idx, span: int):
    """Inclusive segmented Hillis-Steele scan along one block axis.
    *f* (int32 0/1 segment-start flags) and *v* (values, [R, L] or
    [R, L, D]) are scanned with the *shift* primitive
    (pallas_compat.shift_lanes / shift_rows) over *span* positions
    indexed by the iota *idx*.  Returns ``(seen, v)``: ``seen`` = a flag
    exists at or before this position, ``v`` = op-fold from max(last
    flag, axis start) through it.  The POSITIONAL guard (positions < d
    are already complete) keeps unflagged axis starts exact without an
    op identity."""
    d = 1
    while d < span:
        done = (f > 0) | (idx < d)
        v = jnp.where(pallas_compat.stacked_mask(done, v), v,
                      op(shift(v, d, v, idx), v))
        f = f | shift(f, d, 0, idx)
        d *= 2
    return f > 0, v


def _segreduce_kernel(pids, k1_ref, k2_ref, nk1_ref, nk2_ref, *refs,
                      op: Callable, n_lanes: int, unit: bool):
    """One grid step = one [R, _SEG_LANES] block of the sorted lanes
    (key lanes arrive bitcast to int32: only equality is taken).
    refs layout: n_lanes value in-refs (none when *unit*), then n_out
    reduced out-refs (1 when *unit*), csum out-ref, then scratch, each a
    [1, _SEG_LANES] VMEM row: the previous block's last key row (x2),
    the running combine value and the running end count (both held in
    every lane)."""
    from jax.experimental import pallas as pl

    shift_l, shift_r = pallas_compat.shift_lanes, pallas_compat.shift_rows
    n_out = 1 if unit else n_lanes
    val_refs = () if unit else refs[:n_lanes]
    red_refs = refs[0 if unit else n_lanes:][:n_out]
    csum_ref = refs[(0 if unit else n_lanes) + n_out]
    ck1_ref, ck2_ref, cv_ref, cc_ref = refs[-4:]

    @pl.when(pids[0] == 0)
    def _init():
        ck1_ref[...] = jnp.full_like(ck1_ref, _SENT)
        ck2_ref[...] = jnp.full_like(ck2_ref, _SENT)
        cv_ref[...] = jnp.zeros_like(cv_ref)
        cc_ref[...] = jnp.zeros_like(cc_ref)

    k1 = k1_ref[...]
    k2 = k2_ref[...]
    R, L = k1.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, L), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, L), 0)
    valid = jnp.logical_not((k1 == _SENT) & (k2 == _SENT))
    pk1 = pallas_compat.shift1_flat(k1, ck1_ref[...], lane, row)
    pk2 = pallas_compat.shift1_flat(k2, ck2_ref[...], lane, row)
    is_start = valid & ((k1 != pk1) | (k2 != pk2))
    nk1 = nk1_ref[...]
    nk2 = nk2_ref[...]
    nvalid = jnp.logical_not((nk1 == _SENT) & (nk2 == _SENT))
    is_end = valid & ((k1 != nk1) | (k2 != nk2)
                      | jnp.logical_not(nvalid))

    if unit:
        v = jnp.ones(k1.shape, jnp.int32)
        op_eff = jnp.add
    else:
        lanes = [r[...] for r in val_refs]
        v = jnp.stack(lanes, axis=-1) if n_lanes > 1 else lanes[0]
        op_eff = op

    def bsel(mask, a, b):
        return jnp.where(pallas_compat.stacked_mask(mask, a), a, b)

    seen, v = _seg_scan(is_start.astype(jnp.int32), v, op_eff, shift_l,
                        lane, L)
    # compose rows + the block carry: each row's last lane is its
    # (flag, value) summary; scanning those summaries down the rows
    # under the same segmented monoid — then folding in the carry value
    # — gives every row the value of the run continuing into it
    r_seen, r_inc = _seg_scan(
        pallas_compat.last_lane(seen.astype(jnp.int32), lane),
        pallas_compat.last_lane(v, lane), op_eff, shift_r, row, R)
    carry_v = jnp.broadcast_to(cv_ref[...], v.shape)
    r_inc = bsel(r_seen, r_inc, op_eff(carry_v, r_inc))
    final = bsel(seen, v, op_eff(shift_r(r_inc, 1, carry_v, row), v))
    if n_out > 1:
        for i in range(n_out):
            red_refs[i][...] = final[..., i]
    else:
        red_refs[0][...] = final
    cv_ref[...] = r_inc[R - 1:R]

    cumsum = functools.partial(pallas_compat.ladder_scan, op=jnp.add,
                               identity=0)
    e = cumsum(is_end.astype(jnp.int32), shift=shift_l, idx=lane, span=L)
    r_tot = cc_ref[...] + cumsum(pallas_compat.last_lane(e, lane),
                                 shift=shift_r, idx=row, span=R)
    csum_ref[...] = e + shift_r(r_tot, 1,
                                jnp.broadcast_to(cc_ref[...], e.shape), row)
    ck1_ref[...] = k1[R - 1:R]
    ck2_ref[...] = k2[R - 1:R]
    cc_ref[...] = r_tot[R - 1:R]


def _segment_reduce_pallas(k1s: jax.Array, k2s: jax.Array,
                           vals_s: Sequence[jax.Array], op: Callable,
                           unit_values: bool, block: int,
                           interpret: Optional[bool]):
    """The fused kernel path: returns ``(reduced_lanes, end_csum)`` over
    the sorted key/value lanes, matching the lax formulation bit for bit
    at every run-end position (the only rows the compaction gathers) and
    in the end count everywhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N = k1s.shape[0]
    L = _SEG_LANES
    R = pallas_compat.block_rows(block, L, 8, interpret)
    block = R * L
    npad = -(-N // block) * block
    pad = npad - N

    def padded(x, fill):
        if not pad:
            return x
        return jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])

    k1p = padded(k1s, SENTINEL)
    k2p = padded(k2s, SENTINEL)
    # next-element key lanes: ONE elementwise shift each (vs the lax
    # ladders' log2(N) passes), SENTINEL-filled at the end so the last
    # real row is a run end exactly as the lax path forces it
    nk1 = jnp.concatenate([k1p[1:], jnp.full((1,), SENTINEL, jnp.uint32)])
    nk2 = jnp.concatenate([k2p[1:], jnp.full((1,), SENTINEL, jnp.uint32)])
    rows = npad // L
    shape2 = (rows, L)
    ins = [jax.lax.bitcast_convert_type(a, jnp.int32).reshape(shape2)
           for a in (k1p, k2p, nk1, nk2)]
    if unit_values:
        n_lanes, n_out = 0, 1
        out_dtype = jnp.int32
    else:
        n_lanes = n_out = len(vals_s)
        # the scanned dtype the lax path would produce (a promoting
        # custom monoid widens it); integer promotion is exact, so
        # casting up front keeps bit-identity
        probe = jax.eval_shape(
            lambda a: op(a, a),
            jax.ShapeDtypeStruct((2, 2) if n_lanes == 1 else
                                 (2, 2, n_lanes), vals_s[0].dtype))
        out_dtype = probe.dtype
        ins += [padded(v, jnp.zeros((), v.dtype)).astype(out_dtype)
                .reshape(shape2) for v in vals_s]
    spec = pl.BlockSpec((R, L), lambda i: (i, 0))
    carry_v = (1, L, n_out) if n_out > 1 else (1, L)
    outs = pallas_compat.pallas_call(
        functools.partial(_segreduce_kernel, op=op, n_lanes=n_lanes,
                          unit=unit_values),
        name="segreduce",
        interpret=interpret,
        grid=(npad // block,),
        in_specs=[spec] * len(ins),
        out_specs=[spec] * (n_out + 1),
        out_shape=[pallas_compat.sds(shape2, out_dtype, k1s)] * n_out
        + [pallas_compat.sds(shape2, jnp.int32, k1s)],
        scratch_shapes=[pltpu.VMEM((1, L), jnp.int32),
                        pltpu.VMEM((1, L), jnp.int32),
                        pltpu.VMEM(carry_v, out_dtype),
                        pltpu.VMEM((1, L), jnp.int32)],
    )(*ins)
    reduced = [o.reshape(-1)[:N] for o in outs[:n_out]]
    end_csum = outs[n_out].reshape(-1)[:N]
    return reduced, end_csum


def _segment_reduce_lax(k1s: jax.Array, k2s: jax.Array,
                        vals_s: Sequence[jax.Array], op: Callable,
                        unit_values: bool):
    """The ladder formulation (shifted compares + segmented_scan /
    run-length cummax + ladder_cumsum) — the original reference path the
    kernel is pinned bit-identical to."""
    N = k1s.shape[0]
    row_valid = ~((k1s == SENTINEL) & (k2s == SENTINEL))
    prev1 = _shift_right(k1s, 1, 0)
    prev2 = _shift_right(k2s, 1, 0)
    is_start = row_valid & ((k1s != prev1) | (k2s != prev2))
    # row 0 is always a segment head if valid (the shift fill of 0 would
    # otherwise miss a genuine leading (0,0) key)
    is_start = is_start.at[0].set(row_valid[0])
    next1 = jnp.concatenate([k1s[1:], jnp.zeros((1,), jnp.uint32)])
    next2 = jnp.concatenate([k2s[1:], jnp.zeros((1,), jnp.uint32)])
    is_end = row_valid & ((k1s != next1) | (k2s != next2)
                          | ~jnp.concatenate([row_valid[1:],
                                              jnp.zeros((1,), bool)]))
    is_end = is_end.at[-1].set(row_valid[-1])

    idx = jnp.arange(N, dtype=jnp.int32)
    if unit_values:
        run_start = ladder_cummax(jnp.where(is_start, idx, jnp.int32(-1)))
        reduced = [(idx - run_start + 1).astype(jnp.int32)]
    else:
        stacked = (jnp.stack(vals_s, axis=-1) if len(vals_s) > 1
                   else vals_s[0])
        scanned = segmented_scan(op, is_start, stacked)
        reduced = ([scanned[..., i] for i in range(len(vals_s))]
                   if len(vals_s) > 1 else [scanned])
    end_csum = ladder_cumsum(is_end.astype(jnp.int32))
    return reduced, end_csum


class SortedUnique(NamedTuple):
    keys: jax.Array       # [capacity, 2] uint32, ascending among valid
    values: jax.Array     # [capacity, ...] run reductions
    payload: jax.Array    # [capacity, Q] representative payload (run end)
    valid: jax.Array      # [capacity] bool
    n_unique: jax.Array   # [] int32 (may exceed capacity: overflow signal)


def sorted_unique_reduce(keys: jax.Array, values, payload: jax.Array,
                         valid: jax.Array, capacity: int,
                         op, unit_values: bool = False,
                         rank_sort: bool = True,
                         sort_impl: str = "variadic",
                         segment_impl: str = "lax",
                         segment_block: int = SEGMENT_BLOCK,
                         interpret: Optional[bool] = None) -> SortedUnique:
    """Group-by-key reduction for LARGE record batches: one sort, then
    shifted-compare run boundaries, a segmented scan (or run-length
    count when ``unit_values``), and gather-based compaction of the run
    ends — the only scatter-free group-by that runs at sort speed on TPU.

    ``op`` is a traceable associative fn ``(a, b) -> c`` or one of
    "sum" / "min" / "max".  With ``unit_values=True`` the values operand
    is ignored and each key's result is its occurrence count (int32) —
    the wordcount fast path, which also drops a sort operand.

    With ``rank_sort`` (the default) the sort carries only
    ``[k1, k2, iota]`` — three lanes whatever the value/payload arity —
    and the value/payload lanes are permuted afterwards by gathers.
    This decouples the ``lax.sort`` comparator (whose cold compile
    dominates the engine's cold compile at bench shapes and whose
    runtime grows with every carried operand) from the record width.
    ``lax.sort`` is stable, so the rank permutation reorders the lanes
    bit-identically to the variadic sort; ``rank_sort=False`` keeps the
    old variadic path for the golden-equivalence suite.

    ``sort_impl`` picks the permutation program itself:

    * ``"variadic"`` (default) — ONE 2-key sort of ``[k1, k2, ...]``
      (lane transport per ``rank_sort`` above); the steady-state
      tier-1 program: best runtime, worst comparator compile.
    * ``"argsort"`` — TWO stable 1-key sorts, each carrying only
      ``[key_lane, perm]``: sort by ``k2`` first, then stably by
      ``k1``.  ``lax.sort`` stability makes the composed permutation
      exactly the 2-key sort's permutation — equal-``k1`` rows keep
      ascending-``k2`` order, and equal ``(k1, k2)`` pairs keep input
      order — so the result is BIT-identical to the variadic path
      (the golden suite pins it).  The rank-sort trick applied to
      *compile* time: the comparator cost scales with num_keys ×
      operand count, and 1 key / 2 operands lowers ~3x faster than
      2 keys / 3 — the tier-0 program the tiered engine serves cold
      buckets on, at the cost of the extra permutation gathers
      (measured ~2.6x slower end to end at bench shapes, which is why
      it is a serving tier and not the steady state).
    * ``"radix"`` — NO comparator: the Pallas LSD radix sort of
      ops/radix_sort (4-bit digits, 16 passes over the 64-bit key),
      bit-identical to the variadic permutation (the golden suite pins
      it); record lanes always use the rank-sort gather transport.
      The comparator lowering — the dominant cold-compile cost —
      disappears entirely from the program.

    ``segment_impl`` picks the post-sort segmented-reduce formulation:

    * ``"lax"`` (default) — the ladder chain above: shifted-compare
      boundaries + segmented_scan / run-length cummax + ladder_cumsum,
      each a log2(N)-pass Hillis-Steele over the full arrays;
    * ``"pallas"`` — ONE fused VMEM-tiled kernel pass over the sorted
      lanes (run-boundary detection, segmented combine or run-length
      count, and the run-end cumulative count together, cross-block
      state in kernel scratch), bit-identical to ``"lax"`` for the
      engine's integer monoids (the golden suite pins it).  *
      ``segment_block`` sets the kernel's elements-per-block tile;
      ``interpret=None`` auto-selects the Pallas interpreter off-TPU
      (ops/pallas_compat — CPU runs validate semantics, not speed).
      The run-end compaction below is gather-based either way and is
      shared verbatim between the two implementations.
    """
    if sort_impl not in ("variadic", "argsort", "radix"):
        raise ValueError(f"sort_impl must be 'variadic', 'argsort' or "
                         f"'radix' here, got {sort_impl!r} (the tiered "
                         "policies are resolved by the engine before "
                         "tracing)")
    if segment_impl not in ("lax", "pallas"):
        raise ValueError(f"segment_impl must be 'lax' or 'pallas', "
                         f"got {segment_impl!r}")
    if isinstance(op, str):
        try:
            op = {"sum": jnp.add, "min": jnp.minimum,
                  "max": jnp.maximum}[op]
        except KeyError:
            raise ValueError(f"unknown reduce op {op!r}")
    N = keys.shape[0]
    # remap the (astronomically unlikely) real sentinel pair, then encode
    # invalid rows as the sentinel pair so they sort last
    is_sent = (keys[:, 0] == SENTINEL) & (keys[:, 1] == SENTINEL)
    k1 = jnp.where(is_sent, jnp.uint32(0), keys[:, 0])
    k2 = jnp.where(is_sent, jnp.uint32(0), keys[:, 1])
    k1 = jnp.where(valid, k1, SENTINEL)
    k2 = jnp.where(valid, k2, SENTINEL)

    Q = payload.shape[1]
    if unit_values:
        v2 = None
        n_val_lanes = 0
    else:
        v2 = values if values.ndim == 2 else values[:, None]
        n_val_lanes = v2.shape[1]
    # the three stages are named for the device trace (metadata only):
    # obs/compile's stage map books every operation to the innermost
    # scope on its path
    with jax.named_scope("sur.sort"):
        if sort_impl == "argsort":
            # tier-0: two-pass stable argsort — each pass sorts ONE key
            # lane plus the running permutation (2 operands, 1 key), and
            # stability composes them into the exact 2-key permutation
            iota = jnp.arange(N, dtype=jnp.int32)
            _k2s, p1 = jax.lax.sort((k2, iota), num_keys=1)
            k1s, perm = jax.lax.sort((k1[p1], p1), num_keys=1)
            k2s = k2[perm]
            v2s = v2[perm] if n_val_lanes else None
            vals_s = [v2s[:, i] for i in range(n_val_lanes)]
            pay_s = payload[perm]
            pays_s = [pay_s[:, i] for i in range(Q)]
        elif sort_impl == "radix":
            # no comparator at all: Pallas LSD radix over the hash-key
            # lanes (ops/radix_sort), bit-identical to the variadic
            # permutation; record lanes always ride the rank-sort gather
            # transport
            from .radix_sort import radix_sort_pairs
            k1s, k2s, perm = radix_sort_pairs(k1, k2, interpret=interpret)
            v2s = v2[perm] if n_val_lanes else None
            vals_s = [v2s[:, i] for i in range(n_val_lanes)]
            pay_s = payload[perm]
            pays_s = [pay_s[:, i] for i in range(Q)]
        elif rank_sort:
            iota = jnp.arange(N, dtype=jnp.int32)
            k1s, k2s, perm = jax.lax.sort((k1, k2, iota), num_keys=2)
            v2s = v2[perm] if n_val_lanes else None
            vals_s = [v2s[:, i] for i in range(n_val_lanes)]
            pay_s = payload[perm]
            pays_s = [pay_s[:, i] for i in range(Q)]
        else:
            pay_lanes = [payload[:, i] for i in range(Q)]
            val_lanes = [v2[:, i] for i in range(n_val_lanes)]
            sorted_ops = jax.lax.sort(
                tuple([k1, k2] + val_lanes + pay_lanes), num_keys=2)
            k1s, k2s = sorted_ops[0], sorted_ops[1]
            vals_s = list(sorted_ops[2:2 + len(val_lanes)])
            pays_s = list(sorted_ops[2 + len(val_lanes):])

    with jax.named_scope("sur.segreduce"):
        if segment_impl == "pallas":
            reduced, end_csum = _segment_reduce_pallas(
                k1s, k2s, vals_s, op, unit_values, segment_block, interpret)
        else:
            reduced, end_csum = _segment_reduce_lax(
                k1s, k2s, vals_s, op, unit_values)

    with jax.named_scope("sur.compact"):
        # compact run ends by GATHER: searchsorted over the cumulative
        # end count finds the j-th run-end row (no O(N) scatter).  Shared
        # verbatim between the two segment_impls, so the kernel's
        # equivalence surface is exactly (reduced lanes, end_csum).
        n_unique = end_csum[-1] if N > 0 else jnp.int32(0)
        targets = jnp.arange(1, capacity + 1, dtype=jnp.int32)
        out_idx = jnp.searchsorted(end_csum, targets, side="left")
        out_idx = jnp.clip(out_idx, 0, N - 1)
        out_valid = targets <= n_unique

        out_keys = jnp.stack([k1s[out_idx], k2s[out_idx]], axis=-1)
        out_vals = [r[out_idx] for r in reduced]
        out_vals = (jnp.stack(out_vals, axis=-1) if len(out_vals) > 1
                    else out_vals[0])
        out_pay = jnp.stack([p[out_idx] for p in pays_s], axis=-1)
        zero = jnp.zeros((), out_vals.dtype)
        out_vals = jnp.where(
            out_valid.reshape((-1,) + (1,) * (out_vals.ndim - 1)), out_vals,
            zero)
        out_keys = jnp.where(out_valid[:, None], out_keys, jnp.uint32(0))
        out_pay = jnp.where(out_valid[:, None], out_pay, jnp.int32(0))
    return SortedUnique(out_keys, out_vals, out_pay, out_valid,
                        n_unique.astype(jnp.int32))
