"""Grouped matrix products over the experts a device holds.

``lhs [M, K]`` holds rows in GROUP order, each group's rows starting at a
multiple of ``block_m`` and padded with zero rows to a whole number of
tiles (at least one, so that every group's weight gradient is written);
``rhs [G, K, N]`` one matrix a group.  A tile of ``block_m`` rows then
belongs to exactly one group, which ``tile_group [max_tiles] int32``
names, and only the first ``n_tiles`` tiles hold anything:

    out[tile i] = lhs[tile i] @ rhs[tile_group[i]]        i < n_tiles

The kernels' grids end at ``n_tiles``, a value of the run and not of the
shapes (a dynamic grid bound; the group of a tile is read from the
prefetched table), so the arithmetic follows the rows that are there,
not ``M``.  Rows of tiles past ``n_tiles`` are NOT written: whoever
reads the result reads no row it did not fill.

Two kernels, through ``ops/pallas_compat.pallas_call`` like the others:

* ``moe_gmm``: the product above, and with ``transpose_rhs`` the
  gradient for ``lhs`` (``dout @ rhs[g]^T``);
* ``moe_tgmm``: the gradient for ``rhs``, ``lhs[rows of g]^T @ dout[rows
  of g]`` summed over the group's tiles in float32 — the padding rows are
  zero in ``lhs`` and add nothing.

:func:`grouped_matmul` ties them into one differentiable product; it
takes the float32 master weights, multiplies in ``lhs``'s type and
returns the weight gradient in float32 as the kernel accumulated it.
Off the TPU, inside ``shard_map``, where the Pallas interpreter cannot
run them, the same products are ``jax.lax.ragged_dot`` over the padded
group sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_compat import (default_interpret, pallas_call, pick_lane_block,
                            sds)

#: columns of ``rhs`` a grid step of ``moe_gmm`` produces, and the
#: (contraction, column) block of the weight gradient ``moe_tgmm`` holds
#: across a group's tiles: requests, met by the nearest block a lane
#: dimension takes (``pick_lane_block``: 2048 x 1536 run at 512 and
#: 1024 / 768, 2304 x 896 at 384 / 896 and 1152).  Not swept: PERF.md
#: section 7
BLOCK_N = 512
BLOCK_TK = 1024
#: VMEM the kernels may use: a (512 x 2048) operand tile, a (2048 x 512)
#: weight tile and the output tile, double-buffered, pass the 16 MiB a
#: v5e kernel gets by default
_VMEM_BYTES = 48 << 20


def _gmm_kernel(pids, group_ref, n_ref, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs):
    del pids, group_ref, n_ref
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0], dims,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _gmm(lhs, rhs, tile_group, n_tiles, block_m, transpose_rhs, interpret):
    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    block_n = pick_lane_block(N, BLOCK_N)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, block_n, K),
                                lambda n, i, grp, _n: (grp[i], n, 0))
    else:
        rhs_spec = pl.BlockSpec((1, K, block_n),
                                lambda n, i, grp, _n: (grp[i], 0, n))
    return pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name="moe_gmm",
        # columns outer, tiles inner: a group's weight block stays in
        # VMEM across the group's tiles
        grid=(N // block_n, n_tiles[0]),
        num_scalar_prefetch=2,
        in_specs=[pl.BlockSpec((block_m, K), lambda n, i, *_: (i, 0)),
                  rhs_spec],
        out_specs=pl.BlockSpec((block_m, block_n), lambda n, i, *_: (i, n)),
        out_shape=sds((M, N), lhs.dtype, lhs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(tile_group, n_tiles, lhs, rhs)


def _tgmm_kernel(pids, group_ref, n_ref, lhs_ref, dout_ref, out_ref):
    i = pids[2]
    first = jnp.logical_or(
        i == 0, group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0] += jax.lax.dot_general(
        lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _tgmm(lhs, dout, tile_group, n_tiles, n_groups, block_m, interpret):
    M, K = lhs.shape
    N = dout.shape[1]
    block_k = pick_lane_block(K, BLOCK_TK)
    block_n = pick_lane_block(N, BLOCK_N)
    return pallas_call(
        _tgmm_kernel,
        name="moe_tgmm",
        # tiles innermost: a group's (block_k x block_n) float32 sum
        # stays in VMEM from its first tile to its last
        grid=(K // block_k, N // block_n, n_tiles[0]),
        num_scalar_prefetch=2,
        in_specs=[pl.BlockSpec((block_m, block_k),
                               lambda k, n, i, *_: (i, k)),
                  pl.BlockSpec((block_m, block_n),
                               lambda k, n, i, *_: (i, n))],
        out_specs=pl.BlockSpec((1, block_k, block_n),
                               lambda k, n, i, grp, _n: (grp[i], k, n)),
        out_shape=sds((n_groups, K, N), jnp.float32, lhs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(tile_group, n_tiles, lhs, dout)


def _tile_rows(tile_group, n_tiles, n_groups, block_m):
    """Rows each group owns, padding included: what ``ragged_dot`` takes
    as its group sizes."""
    live = jnp.arange(tile_group.shape[0]) < n_tiles[0]
    tiles = jnp.zeros((n_groups,), jnp.int32).at[tile_group].add(
        live.astype(jnp.int32))
    return tiles * block_m


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _grouped(lhs, rhs, tile_group, n_tiles, block_m, kernels, interpret):
    return _grouped_fwd(lhs, rhs, tile_group, n_tiles, block_m, kernels,
                        interpret)[0]


def _grouped_fwd(lhs, rhs, tile_group, n_tiles, block_m, kernels, interpret):
    w = rhs.astype(lhs.dtype)
    if kernels:
        out = _gmm(lhs, w, tile_group, n_tiles, block_m, False, interpret)
    else:
        out = jax.lax.ragged_dot(
            lhs, w, _tile_rows(tile_group, n_tiles, rhs.shape[0], block_m))
    return out, (lhs, w, tile_group, n_tiles)


def _grouped_bwd(block_m, kernels, interpret, res, dout):
    lhs, w, tile_group, n_tiles = res
    if kernels:
        dlhs = _gmm(dout, w, tile_group, n_tiles, block_m, True, interpret)
        dw = _tgmm(lhs, dout, tile_group, n_tiles, w.shape[0], block_m,
                   interpret)
    else:
        rows = _tile_rows(tile_group, n_tiles, w.shape[0], block_m)
        dlhs = jax.lax.ragged_dot(dout, jnp.swapaxes(w, 1, 2), rows)
        # rows past the groups' end multiply nothing, whatever they hold
        live = jnp.arange(lhs.shape[0]) < rows.sum()
        dw = jax.lax.ragged_dot_general(
            jnp.where(live[:, None], lhs, 0), dout, rows,
            jax.lax.RaggedDotDimensionNumbers(
                dot_dimension_numbers=(((0,), (0,)), ((), ())),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
            preferred_element_type=jnp.float32)
    return dlhs, dw, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
                   n_tiles: jax.Array, *, block_m: int,
                   interpret=None) -> jax.Array:
    """``out [M, N]`` of ``lhs [M, K]`` in the module's tile-aligned group
    order against ``rhs [G, K, N]`` (float32 master weights, multiplied
    in ``lhs``'s type); ``tile_group [max_tiles] int32`` with ``M ==
    max_tiles * block_m``, ``n_tiles [1] int32``.  Differentiable in
    ``lhs`` and ``rhs``; the gradient for ``rhs`` is float32."""
    if lhs.shape[0] != tile_group.shape[0] * block_m:
        raise ValueError(f"lhs has {lhs.shape[0]} rows, the table "
                         f"{tile_group.shape[0]} tiles of {block_m}")
    # inside shard_map: weights replicated over an axis the rows vary
    # over take their gradient's sum over it (the cast's transpose)
    missing = tuple(jax.typeof(lhs).vma - jax.typeof(rhs).vma)
    if missing:
        rhs = jax.lax.pcast(rhs, missing, to="varying")
    interpret = default_interpret(interpret)
    # the tables are values of the run, each device's its own, and the
    # Pallas interpreter cannot slice a table that varies over
    # shard_map's mesh by a grid index that does not (jax 0.9: its
    # discharge of the read fails the varying-axes check).  Off the TPU
    # the trainer's expert products are therefore ragged_dot; the
    # kernels run interpreted outside shard_map (tests/test_moe_layer.py)
    # and compiled on the chip
    kernels = not (interpret and jax.typeof(tile_group).vma)
    return _grouped(lhs, rhs, tile_group, n_tiles, int(block_m), kernels,
                    interpret)
