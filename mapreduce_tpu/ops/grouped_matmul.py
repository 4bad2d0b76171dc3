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

Four kernels, through ``ops/pallas_compat.pallas_call`` like the others:

* ``moe_gmm``: the product above, and with ``transpose_rhs`` the
  gradient for ``lhs`` (``dout @ rhs[g]^T``);
* ``moe_tgmm``: the gradient for ``rhs``, ``lhs[rows of g]^T @ dout[rows
  of g]`` summed over the group's tiles in float32 — the padding rows are
  zero in ``lhs`` and add nothing;
* ``moe_gate``: what stands between two products of a gated layer,
  ``silu(a) * u`` tile by tile, and its transpose, on the same bound;
* ``moe_add``: the sum of two gradients for the same rows, which is the
  transpose of handing one ``lhs`` to two products.

The last two know no group and hold to the same contract: rows of tiles
past ``n_tiles`` are NOT written, and the products and loops that read
their results read none of them.

:func:`grouped_matmul` ties the first two into one differentiable
product; it takes the float32 master weights, multiplies in ``lhs``'s
type and returns the weight gradient in float32 as the kernel
accumulated it.  :func:`gated` is the third with its transpose,
:func:`twice` the fan-out whose transpose is the fourth.  Off the TPU,
inside ``shard_map``, where the Pallas interpreter cannot run them
(:func:`_runs_kernels`), the products are ``jax.lax.ragged_dot`` over
the padded group sizes, and the gate and the sum are XLA's own over
every row.

The contract runs the other way too: a buffer whose every reader ends at
``n_tiles`` needs no value past it.  :func:`rows_buffer` hands the loops
that fill such buffers (``models/moe.py``) one that nobody writes, a
Pallas call that allocates it and does nothing (``moe_rows_unwritten``),
where XLA would fill every row of ``jnp.zeros``; where the kernels
cannot run it is zeros, because the products and the gate there read
every row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import metrics as _obs
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


def _runs_kernels(interpret: bool, table: jax.Array) -> bool:
    """Whether a call takes the kernels.  The tables are values of the
    run, each device's its own, and the Pallas interpreter cannot slice
    a table that varies over shard_map's mesh by a grid index that does
    not (jax 0.9: its discharge of the read fails the varying-axes
    check).  Off the TPU the trainer's expert layer is therefore
    ragged_dot and a plain gate; the kernels run interpreted outside
    shard_map (tests/test_moe_layer.py) and compiled on the chip."""
    return not (interpret and jax.typeof(table).vma)


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
    return _grouped(lhs, rhs, tile_group, n_tiles, int(block_m),
                    _runs_kernels(interpret, tile_group), interpret)


# -- between the products: the gate, and the sum of two gradients -----------
#
# Elementwise over the ``(block_m, block_f)`` tiles of the row tiles in
# use; XLA's own passes run over every row of buffers sized for every
# pair.


def _silu_mul(a, u):
    """``silu(a) * u`` in float32, as the reference writes it."""
    return jax.nn.silu(a.astype(jnp.float32)) * u.astype(jnp.float32)


def _gate_kernel(pids, n_ref, a_ref, u_ref, act_ref):
    del pids, n_ref
    act_ref[...] = _silu_mul(a_ref[...], u_ref[...]).astype(act_ref.dtype)


def _gate_bwd_kernel(pids, n_ref, d_ref, a_ref, u_ref, da_ref, du_ref):
    del pids, n_ref
    a = a_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    s = jax.nn.sigmoid(a)
    da_ref[...] = (d * u_ref[...].astype(jnp.float32)
                   * (s * (1 + a * (1 - s)))).astype(da_ref.dtype)
    du_ref[...] = (d * (a * s)).astype(du_ref.dtype)


def _add_kernel(pids, n_ref, a_ref, b_ref, out_ref):
    del pids, n_ref
    out_ref[...] = (a_ref[...].astype(jnp.float32)
                    + b_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _tile_call(kernel, name, n_out, n_tiles, block_m, interpret, *operands,
               into_first=False):
    """*kernel* over the tiles of the row tiles in use: every operand
    and each of the *n_out* results is ``[M, F]`` like the first.  With
    *into_first* the one result takes the first operand's buffer (a grid
    step reads its tile of every operand before it writes its own)."""
    like = operands[0]
    block_f = pick_lane_block(like.shape[1], BLOCK_N)
    tile = pl.BlockSpec((block_m, block_f), lambda i, f, _n: (i, f))
    out = sds(like.shape, like.dtype, like)
    return pallas_call(
        kernel,
        name=name,
        grid=(n_tiles[0], like.shape[1] // block_f),
        num_scalar_prefetch=1,
        in_specs=[tile] * len(operands),
        out_specs=[tile] * n_out,
        out_shape=[out] * n_out,
        # the call's operand 0 is the prefetched bound
        input_output_aliases={1: 0} if into_first else {},
        # what the pass costs over EVERY tile, as XLA reckoned its own
        # (the bytes decide): a call with no estimate counts as a short
        # one, the compiler starts the next products' weight prefetches
        # before it and the step reserves 50 MB more at the LFM2 cell's
        # shape (PERF.md section 6, PR 38)
        cost_estimate=pl.CostEstimate(
            flops=4 * len(operands) * like.size, transcendentals=like.size,
            bytes_accessed=(len(operands) + n_out) * like.size
            * like.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(n_tiles, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gated(a, u, n_tiles, block_m, interpret):
    return _tile_call(_gate_kernel, "moe_gate", 1, n_tiles, block_m,
                      interpret, a, u)[0]


def _gated_fwd(a, u, n_tiles, block_m, interpret):
    return _gated(a, u, n_tiles, block_m, interpret), (a, u, n_tiles)


def _gated_bwd(block_m, interpret, res, d_act):
    a, u, n_tiles = res
    da, du = _tile_call(_gate_bwd_kernel, "moe_gate", 2, n_tiles, block_m,
                        interpret, d_act, a, u)
    return da, du, None


_gated.defvjp(_gated_fwd, _gated_bwd)


def _whole_tiles(x, block_m):
    if x.shape[0] % block_m:
        raise ValueError(f"{x.shape[0]} rows are not whole tiles of "
                         f"{block_m}")


def gated(a: jax.Array, u: jax.Array, n_tiles: jax.Array, *, block_m: int,
          interpret=None) -> jax.Array:
    """``silu(a) * u`` for ``a, u [M, F]`` of one type, computed in
    float32 and returned in theirs, on the rows of the first ``n_tiles
    [1] int32`` tiles of ``block_m``; the other rows of the result are
    not written.  Differentiable in ``a`` and ``u``, on the same rows.
    Where the kernels cannot run (:func:`_runs_kernels`) it is the
    expression over every row."""
    if a.shape != u.shape:
        raise ValueError(f"a {a.shape} and u {u.shape} are not one shape")
    _whole_tiles(a, block_m)
    interpret = default_interpret(interpret)
    if not _runs_kernels(interpret, n_tiles):
        return _silu_mul(a, u).astype(a.dtype)
    return _gated(a, u, n_tiles, int(block_m), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _twice(x, n_tiles, block_m, interpret):
    return x, x


def _twice_fwd(x, n_tiles, block_m, interpret):
    return (x, x), n_tiles


def _twice_bwd(block_m, interpret, n_tiles, d):
    return _tile_call(_add_kernel, "moe_add", 1, n_tiles, block_m, interpret,
                      *d, into_first=True)[0], None


_twice.defvjp(_twice_fwd, _twice_bwd)


def twice(x: jax.Array, n_tiles: jax.Array, *, block_m: int,
          interpret=None):
    """``(x, x)`` for ``x [M, F]`` that two products take: the gradient
    for ``x`` is then the sum of theirs, formed on the rows of the first
    ``n_tiles`` tiles of ``block_m`` alone (in float32, rounded once);
    its other rows are not written.  Where the kernels cannot run it is
    a plain pair, and the sum autodiff's over every row."""
    _whole_tiles(x, block_m)
    interpret = default_interpret(interpret)
    if not _runs_kernels(interpret, n_tiles):
        return x, x
    return _twice(x, n_tiles, int(block_m), interpret)


# -- a buffer for the rows of the tiles in use --------------------------------

_ROW_BUFFERS = _obs.gauge(
    "mrtpu_moe_row_buffers",
    "rows' buffers in expert order (xs, d_ys: [M, E]) that the expert "
    "layer's loops start from, counted as a program traces them (labels: "
    "kind): kind=unwritten a Pallas call allocates and nobody writes "
    "(where the grouped kernels run, and every reader ends at the tiles in "
    "use), "
    "kind=zeroed filled with zeros (where the products are ragged_dot and "
    "may read every row); a layer traced once counts once, whatever the "
    "steps")


def _leave_unwritten(pids, *refs):
    del pids, refs


def rows_buffer(shape, dtype, n_tiles: jax.Array, like, *,
                interpret=None) -> jax.Array:
    """The buffer a loop over the first ``n_tiles [1] int32`` tiles
    writes its rows into; *like* are the arrays the loop reads.  Where
    the kernels run (:func:`_runs_kernels`) nothing reads a row past
    those tiles, and it is one Pallas call whose output stays in device
    memory (``pl.ANY``) and whose body touches nothing,
    ``moe_rows_unwritten``: no operation fills it (under the interpreter
    it reads NaN, so a read past the tiles poisons what reads it).  The
    call takes *like* and reads none of them: it cannot be placed before
    they are made, where one with no operand is placed at the step's
    start and every such buffer lives through the whole step.  Where the
    kernels cannot run it is zeros: ``ragged_dot`` and the plain gate
    there take every row, and NaN times 0 is NaN.  Counted in
    ``mrtpu_moe_row_buffers``."""
    interpret = default_interpret(interpret)
    if not _runs_kernels(interpret, n_tiles):
        _ROW_BUFFERS.inc(kind="zeroed")
        return jnp.zeros(shape, dtype)
    _ROW_BUFFERS.inc(kind="unwritten")
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pallas_call(
        _leave_unwritten,
        name="moe_rows_unwritten",
        grid=(1,),
        in_specs=[anywhere] * len(like),
        out_specs=anywhere,
        out_shape=jax.ShapeDtypeStruct(
            tuple(shape), dtype,
            vma=frozenset().union(*(jax.typeof(a).vma for a in like))),
        # it does no work: said, not left to the scheduler's guess
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0,
                                      bytes_accessed=0),
        interpret=interpret,
    )(*like)
