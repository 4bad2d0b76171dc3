"""The shuffle: hash-partition + capacity-bounded ``all_to_all``.

This is the device-native replacement for the reference's entire shuffle
machinery — partitionfn hashing on the host (partitionfn.lua:2-15),
per-partition intermediate *files* (job.lua:196-221), reduce jobs pulling
those files over GridFS/NFS/scp (fs.lua:141-181), and the k-way merge
(utils.lua:206-271).  Here a record's partition is ``key_hi mod P``; every
device packs its records into a ``[P, C, lanes]`` send buffer and one
``lax.all_to_all`` over the mesh axis moves partition *p*'s records to
device *p* over ICI, inside the compiled program.

Static shapes on a dynamic problem (SURVEY.md §7 hard part (a)): the
per-destination capacity ``C`` is fixed; rows beyond it are counted in
``overflow`` (never silently lost — callers check and re-run with a larger
C).  Packing is scatter-based (O(N)), not sort-based.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Exchanged(NamedTuple):
    keys: jax.Array      # [(A+)P*C, 2] uint32 — records received here
    values: jax.Array    # [(A+)P*C, ...]
    payload: jax.Array   # [(A+)P*C, Q] int32
    valid: jax.Array     # [(A+)P*C] bool
    overflow: jax.Array  # [] int32 — rows dropped on the SEND side here
    max_count: jax.Array  # [] int32 — largest per-destination row count
    #                       BEFORE capping (what capacity SHOULD have been)
    counts: jax.Array    # [P] int32 — valid rows this device ROUTED to
    #                       each destination, before capacity capping:
    #                       THIS device's row of the src×dst exchange
    #                       traffic matrix (obs/comms)


def routing_plan(dest: jax.Array, n_dest: int,
                 impl: str = "lax") -> Tuple[jax.Array, jax.Array]:
    """The routing plan of rows bound for ``dest [N] int32``: ``(rank
    [N], counts [n_dest])``, each row's rank among the rows of its
    destination (in row order) and the rows each destination gets.  A
    row whose ``dest`` is ``n_dest`` or more goes nowhere: it is counted
    for no destination and its rank means nothing.  The exchange packs
    its send buffer by ``(dest, rank)`` and never inverts that, so it
    needs no sort.  The routed expert layer (models/moe.py) takes the
    experts' load from ``counts``; the pairs in expert order are a sort
    by destination of its own (``moe.expert_order``), and the ranks say
    where that order puts each pair.  ``impl`` as in
    :func:`partition_exchange`."""
    if impl == "radix":
        # fused plan: one histogram kernel pass feeds both outputs
        from ..ops.radix_sort import radix_partition_plan
        return radix_partition_plan(dest, n_dest)
    # one-hot cumsum: rank[i] = #{j < i : dest[j] == dest[i]}
    # (O(N * n_dest) elementwise, n_dest small; no sort), read at a
    # row's own column by a compare and a sum over the columns: one
    # term is not zero, and no row is moved by its index.  A row out of
    # range reads the column it is clipped to, as it always has.
    at = jnp.arange(n_dest)[None, :]
    onehot = (dest[:, None] == at).astype(jnp.int32)
    mine = jnp.clip(dest, 0, n_dest - 1)[:, None] == at
    rank = jnp.where(mine, jnp.cumsum(onehot, axis=0) - 1, 0).sum(axis=1)
    return rank, onehot.sum(axis=0)


def partition_exchange(keys: jax.Array, values: jax.Array,
                       payload: jax.Array, valid: jax.Array,
                       axis_name: str, capacity: int,
                       carry: Optional[Tuple] = None,
                       pmap: Optional[jax.Array] = None,
                       impl: str = "lax") -> Exchanged:
    """Exchange records so device ``p`` ends up with every record whose
    ``key_hi % P == p``.  Must run inside ``shard_map`` over *axis_name*.

    ``capacity`` bounds rows per (source, destination) pair.

    ``carry`` is the accumulator-carrying spec for the fused wave fold:
    an optional ``(keys [A,2], values [A,...], payload [A,Q],
    valid [A])`` of rows ALREADY belonging to this device's partition
    (the running per-partition uniques of earlier waves).  They are
    prepended to the received rows — before, not after, so a stable
    downstream sort keeps accumulator rows ahead of same-key wave rows
    and the fold order stays ``acc ⊕ wave`` — letting the caller's
    merge reduce accumulator + fresh records in ONE pass with no extra
    dispatch or concatenate allocation outside the compiled program.

    ``pmap`` (the skew-control hook, engine/autotune.py) generalizes
    the partition function to an indirection table: a replicated
    ``[B] int32`` array mapping hash bucket ``key_hi % B`` to its
    destination partition.  The identity table
    (``pmap[b] = b % P``, with ``P | B``) reproduces ``key_hi % P``
    EXACTLY — ``(k % B) % P == k % P`` whenever P divides B — so a run
    that never rebalances is bit-identical to ``pmap=None``; a
    rebalanced table routes each hot bucket wherever the controller
    binned it, inside the same compiled program (the table is an
    input, not a constant — no recompile per rebalance).

    ``impl`` picks the routing-plan formulation: ``"lax"`` (default)
    is the one-hot cumsum below; ``"radix"`` fuses the plan into the
    radix kernel program (ops/radix_sort.radix_partition_plan) — ONE
    destination-digit histogram kernel yields both the scatter ranks
    and the ``counts`` traffic-matrix row, deleting the separate
    count pass.  Both are bit-identical in every output field (the
    golden suite pins it); buffer packing, the collective, and the
    carry prepend are shared verbatim.
    """
    if impl not in ("lax", "radix"):
        raise ValueError(f"exchange impl must be 'lax' or 'radix', "
                         f"got {impl!r}")
    P = jax.lax.psum(1, axis_name)
    n = keys.shape[0]
    if pmap is None:
        dest = (keys[:, 0] % jnp.uint32(P)).astype(jnp.int32)
    else:
        B = pmap.shape[0]
        bucket = (keys[:, 0] % jnp.uint32(B)).astype(jnp.int32)
        dest = pmap[bucket].astype(jnp.int32)
    dest = jnp.where(valid, dest, P)  # invalid -> out-of-range, dropped

    rank, counts = routing_plan(dest, P, impl)
    overflow = jnp.maximum(counts - capacity, 0).sum()

    def scatter(arr, fill=0):
        buf = jnp.full((P, capacity) + arr.shape[1:], fill, dtype=arr.dtype)
        return buf.at[dest, rank].set(arr, mode="drop")

    send_keys = scatter(keys)
    send_vals = scatter(values)
    send_pay = scatter(payload)
    send_live = scatter(valid.astype(jnp.int32))

    # one collective moves the whole shuffle over ICI: slot [d] of the
    # send buffer goes to device d; slot [s] of the result came from s
    recv_keys = jax.lax.all_to_all(send_keys, axis_name, 0, 0, tiled=False)
    recv_vals = jax.lax.all_to_all(send_vals, axis_name, 0, 0, tiled=False)
    recv_pay = jax.lax.all_to_all(send_pay, axis_name, 0, 0, tiled=False)
    recv_live = jax.lax.all_to_all(send_live, axis_name, 0, 0, tiled=False)

    flat = lambda a: a.reshape((P * capacity,) + a.shape[2:])
    out_keys = flat(recv_keys)
    out_vals = flat(recv_vals)
    out_pay = flat(recv_pay)
    out_valid = flat(recv_live) == 1
    if carry is not None:
        ck, cv, cp, cvalid = carry
        out_keys = jnp.concatenate([ck, out_keys], axis=0)
        out_vals = jnp.concatenate([cv, out_vals], axis=0)
        out_pay = jnp.concatenate([cp, out_pay], axis=0)
        out_valid = jnp.concatenate([cvalid, out_valid], axis=0)
    return Exchanged(
        keys=out_keys,
        values=out_vals,
        payload=out_pay,
        valid=out_valid,
        overflow=overflow,
        max_count=counts.max().astype(jnp.int32),
        counts=counts.astype(jnp.int32),
    )
