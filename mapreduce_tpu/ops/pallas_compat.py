"""Shared Pallas plumbing: ONE spelling of the CPU-fallback policy.

Every Pallas kernel in this repo (ops/flash_attention.py, the fused
wave-program hot-path kernels in ops/segscan.py / ops/tokenize.py, and
ops/radix_sort.py) wants the same pieces of glue:

* **interpret-mode default** — ``interpret = jax.default_backend() !=
  "tpu"``: compiled Mosaic on a real TPU, the Pallas interpreter
  everywhere else, so the tier-1 CPU test mesh executes the REAL kernel
  logic (grid sequencing, scratch carries, block index maps) rather
  than a shadow jnp implementation.  Interpret-mode numbers validate
  semantics, never speed.
* **block-size fitting** — :func:`pick_block` shrinks a requested block
  to one that divides the dimension and satisfies Mosaic's sublane
  rule, so ANY shape works without the caller raising.
* **vma-aware out shapes** — :func:`sds` builds ShapeDtypeStructs that
  inherit an exemplar's varying-mesh-axes set, so a kernel composes
  with ``shard_map``'s vma checking (the kernels are purely per-device:
  outputs vary exactly as their inputs do).
* **scan-kernel building blocks** — :func:`block_rows`,
  :func:`shift_lanes`, :func:`shift_rows`, :func:`last_lane`,
  :func:`shift1_flat`, :func:`ladder_scan`: the roll-and-mask forms of
  the shifts a blocked scan needs, written in what Mosaic lowers (no
  unaligned concatenate, no vector->scalar extraction).

:func:`pallas_call` is the thin entry point the kernel modules dispatch
through: it resolves the interpret default in ONE place, forwards an
optional ``pl.CostEstimate`` hint, and counts kernel-program traces in
the metrics registry (``mrtpu_pallas_kernel_builds_total``).  The count
is TRACE-time: compiles and abstract shape probes (the engine's
``jax.eval_shape`` aval derivations reach here too) both increment it,
while warm executable-cache dispatches add nothing — so a nonzero delta
is the registry witness that a config actually routes through the
kernel programs (what the bench smoke asserts), not a count of XLA
kernel compiles.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from ..obs import metrics as _obs

# NOTE: jax.experimental.pallas is imported lazily inside
# :func:`pallas_call` — this module rides every package import (ops/
# __init__), and the suite spawns many short-lived subprocesses that
# never build a kernel; they should not pay the pallas import.

_KERNEL_BUILDS = _obs.counter(
    "mrtpu_pallas_kernel_builds_total",
    "Pallas kernel programs traced (labels: kernel, "
    "mode=interpret|mosaic) — a trace-time count: incremented whenever "
    "an enclosing program traces the kernel (compiles AND abstract "
    "shape probes like the engine's eval_shape aval derivations), zero "
    "on warm executable-cache dispatches.  A nonzero delta therefore "
    "witnesses 'this config routes through the kernel', not 'XLA "
    "compiled N kernels'")


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """THE interpret-mode policy: compiled Mosaic on TPU, the Pallas
    interpreter everywhere else (``None`` = auto).  An explicit bool
    wins — tests force either mode deterministically."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def pick_block(t: int, want: int) -> int:
    """Largest block <= *want* that divides *t* and satisfies Mosaic's
    sublane rule (multiple of 8, or the whole dimension).  Falls back to
    the smallest valid divisor above *want* (worst case *t* itself, one
    VMEM-resident tile) so ANY dimension works — a shape that ran on the
    jnp path must not start raising here."""
    if t <= want:
        return t
    for b in range(want, 7, -1):
        if t % b == 0 and b % 8 == 0:
            return b
    for b in range(want + 1, t):
        if t % b == 0 and (b % 8 == 0 or b == t):
            return b
    return t


def pick_lane_block(t: int, want: int) -> int:
    """The block nearest *want*, by ratio, of those Mosaic takes on a
    LANE (last) dimension of length *t*: a multiple of 128 that divides
    *t*, or *t* whole; the smaller of two equally near.  Where *want*
    itself is such a block it is the answer, as with :func:`pick_block`
    (1536 or 2048 columns at 512: 512); where it is not, the nearest may
    lie above it (896 columns at 512: 128 is four times under, the whole
    896 not twice over) — :func:`pick_block` knows the sublane rule
    alone and would halve 896 to 448, which no lane dimension takes."""
    if t <= want:
        return t
    valid = [b for b in range(128, t, 128) if t % b == 0] + [t]
    return min(valid, key=lambda b: (max(b, want) / min(b, want), b))


def sds(shape: Sequence[int], dtype: Any, like: Any) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct inheriting *like*'s varying-mesh-axes set, so the
    kernel composes with shard_map's vma checking (the kernel is purely
    per-device: outputs vary exactly as its inputs do).  The set is
    passed even when EMPTY: under ``check_vma`` a pallas_call refuses an
    out_shape whose vma is None."""
    return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                vma=jax.typeof(like).vma)


def pallas_call(kernel, *, name: str, grid: Sequence[int],
                interpret: Optional[bool] = None,
                cost_estimate: Optional[Any] = None,
                num_scalar_prefetch: int = 0, **kwargs):
    """``pl.pallas_call`` with the repo-wide CPU-fallback policy applied
    and the build counted (*name* labels the kernel family in
    ``mrtpu_pallas_kernel_builds_total``).  *cost_estimate* forwards a
    ``pl.CostEstimate`` scheduling hint when the caller has one.

    Kernels here take ``kernel(pids, *refs)``: *pids* is the tuple of
    program ids, one per *grid* axis, read ONCE at the top of the body.
    Under the interpreter the rest of the body then runs as one
    conditional branch.  The reason is ``shard_map``'s vma checking:
    the HLO interpreter re-binds a kernel's top-level equations on the
    device-varying input blocks, and refuses any that mixes a block
    with a literal or a scratch value (every non-trivial kernel); a
    branch jaxpr keeps the types of kernel trace time, where the
    checking is off — but cannot resolve ``program_id`` itself.  Mosaic
    lowering never re-types the body and gets it unwrapped.

    With *num_scalar_prefetch* = n the first n operands of the call are
    small int32 tables that reach scalar memory before the grid starts
    (``pltpu.PrefetchScalarGridSpec``): every index map takes them as
    refs after its grid indices, and the kernel as
    ``kernel(pids, *table_refs, *refs)`` — which block a grid step
    works on is then read from a table, not computed from its index."""
    from jax.experimental import pallas as pl  # lazy: see module note

    interp = default_interpret(interpret)
    _KERNEL_BUILDS.inc(kernel=name,
                       mode="interpret" if interp else "mosaic")
    if cost_estimate is not None:
        kwargs["cost_estimate"] = cost_estimate
    grid = tuple(grid)
    n_axes = len(grid)
    if num_scalar_prefetch:
        from jax.experimental.pallas import tpu as pltpu

        kwargs["grid_spec"] = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_scalar_prefetch, grid=grid,
            in_specs=kwargs.pop("in_specs"),
            out_specs=kwargs.pop("out_specs"),
            scratch_shapes=kwargs.pop("scratch_shapes", ()))
    else:
        kwargs["grid"] = grid

    def body(*refs):
        pids = tuple(pl.program_id(a) for a in range(n_axes))
        if interp:
            pl.when(pids[0] >= 0)(lambda: kernel(pids, *refs))
        else:
            kernel(pids, *refs)

    return pl.pallas_call(body, name=name, interpret=interp, **kwargs)


# -- in-kernel building blocks for the blocked scan kernels ------------------
#
# ops/segscan and ops/tokenize scan a flattened record stream laid out
# row-major as [R, LANES] blocks.  Mosaic lowers neither unaligned
# concatenates nor vector->scalar extraction, so every shift is a
# ``pltpu.roll`` plus an iota mask, and every cross-block carry is a
# [1, LANES] VMEM row (never an SMEM scalar).  *lane*/*row* are the
# block's int32 iotas along axes 1/0.


def block_rows(block: int, lanes: int, tile_rows: int,
               interpret: Optional[bool]) -> int:
    """Rows of a [rows, *lanes*] kernel block holding ~*block* elements.
    Mosaic tiles a block in whole (*tile_rows*, 128) tiles — 8 rows of
    32-bit, 32 of 8-bit elements — so the compiled kernel rounds up to
    them; the interpreter takes any row count (the tier-1 suite's small
    blocks keep the cross-block carries exercised)."""
    unit = 1 if default_interpret(interpret) else tile_rows
    return -(-max(int(block) // lanes, 1) // unit) * unit


def stacked_mask(m, x):
    """*m* ([R, L]) broadcast over *x*'s trailing stacked-lane axis."""
    return m[..., None] if x.ndim == 3 else m


def shift_lanes(x, d: int, fill, lane):
    """x shifted right by *d* along the lane axis, *fill* shifted in."""
    from jax.experimental.pallas import tpu as pltpu

    return jnp.where(stacked_mask(lane >= d, x), pltpu.roll(x, d, 1), fill)


def shift_rows(x, d: int, fill, row):
    """x shifted down by *d* rows, *fill* shifted in."""
    from jax.experimental.pallas import tpu as pltpu

    return jnp.where(stacked_mask(row >= d, x), pltpu.roll(x, d, 0), fill)


def last_lane(x, lane):
    """Each row's last-lane element, broadcast over the row's lanes."""
    last = jnp.where(stacked_mask(lane == x.shape[1] - 1, x), x,
                     jnp.zeros((), x.dtype))
    return jnp.broadcast_to(jnp.sum(last, axis=1, keepdims=True), x.shape)


def ladder_scan(x, op, identity, shift, idx, span: int):
    """Inclusive Hillis-Steele scan of *x* under the elementwise monoid
    (*op*, *identity*) along one block axis: *shift* is
    :func:`shift_lanes` or :func:`shift_rows`, *idx* that axis's iota,
    *span* its length."""
    d = 1
    while d < span:
        x = op(shift(x, d, identity, idx), x)
        d *= 2
    return x


def shift1_flat(x, carry_row, lane, row):
    """*x* ([R, L]) shifted right by one in flattened row-major order;
    position [0, 0] takes the last lane of *carry_row* ([1, L], the
    previous block's last row)."""
    from jax.experimental.pallas import tpu as pltpu

    by_lane = pltpu.roll(x, 1, 1)       # lane 0 holds the row's OWN end
    from_above = jnp.where(
        row == 0, jnp.broadcast_to(pltpu.roll(carry_row, 1, 1), x.shape),
        pltpu.roll(by_lane, 1, 0))
    return jnp.where(lane == 0, from_above, by_lane)
