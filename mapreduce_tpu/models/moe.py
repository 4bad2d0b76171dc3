"""The routed expert layer: a share of a mixture of experts.

The layer is told which experts it holds (``TransformerConfig.moe_held``
experts from ``moe_held_offset`` on, split evenly over the ``model``
axis), routes every token over ALL ``moe_experts`` and computes the part
of the result its own experts give:

    s = sigmoid(h W_g)                        (float32, all moe_experts)
    S = the moe_top_k experts with the largest s + b   (b: a buffer, not
                                                        trained; 0 without)
    g_e = s_e / (sum_{e' in S} s_e' + 1e-6)
    (``moe_router_score="softmax"``: s = softmax(h W_g) over all
     moe_experts, g_e = s_e / sum_{e' in S} s_e')
    out = sum_{e in S and held here} g_e W2_e (silu(W1_e h) * W3_e h)
    (``moe_routed_scale`` c: g_e = c s_e / (...);  ``shared_ffn``: out +=
     Ws2 (silu(Ws1 h) * Ws3 h), a SHARED expert every token takes, whole
     on every rank and added once, after the ranks' parts are summed)

``b`` moves only by the rule of ``moe_bias_rate``
(``transformer.TransformerTrainer``'s optimizer step: after the step,
``b_e += rate sign(mean load - load_e)`` over all ``moe_experts``).
``g`` is normalised over all of ``S``, held or not; what the absent
experts would add is left out, and a ``psum`` over the ``model`` axis adds
the ranks' parts (one held expert a rank is classic expert parallelism;
tokens are replicated over that axis, so there is no exchange).

No (token, expert) pair is dropped and none is cut at a capacity: the
buffers have room for every pair landing here, and the work follows the
pairs that did.  The products are grouped over the held experts
(``ops/grouped_matmul.py``), whose grids end at the tiles in use, and
the rows are moved into expert order and back (``_dispatch``,
``_combine`` and their transposes) by loops that end at the same bound,
a tile a trip, from the row side: no operation of theirs touches the
buffers whole, and the gate ``silu(a) * u`` between the products and the
sum of the first two products' gradients for the rows are kernels on
that bound too (``ops/grouped_matmul.gated``, ``twice``).  Where the
kernels run, the rows' buffers the loops fill (``xs`` into the products,
``d_ys`` out of the combine's transpose) are not zeroed first: no reader
looks past the tiles in use (``ops/grouped_matmul.rows_buffer``).  What
does not follow the tiles in use: the plan over all ``N k`` pairs, and
the zeros of the carries whose every row is read (the ``[N, E]`` sums
into token order, ``d_w``, ``pair_of_row``; PERF.md section 5).  The
plan costs the pairs, whatever lands, but moves none of them by its
index: the chosen scores are a compare and a sum over the experts
(``route``), a held expert's load a compare and a sum over the pairs
(the counts of the exchange's plan, ``parallel/shuffle.routing_plan``,
whose ranks only ``expert_order``'s ``pos`` reads), and the pairs in
expert order one stable sort by destination, from which a tile takes a
contiguous run (``expert_order``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import gated, grouped_matmul, rows_buffer, twice
from ..parallel.shuffle import routing_plan

#: rows of a tile of the grouped products; every held expert's rows start
#: at a multiple of it (at most one tile of zero rows an expert)
BLOCK_M = 512
#: columns a layer's statistics carry after the held experts' loads
STAT_DROPPED, STAT_ROUTED = -2, -1


def route(flat: jax.Array, w_router: jax.Array, bias, top_k: int,
          score: str = "sigmoid", scale: float = 1.0):
    """``(chosen [N, k] int32, weights [N, k] float32)`` of tokens ``flat
    [N, E]``: the module's ``S`` and ``g``, the scores ``s`` by *score*:
    ``"sigmoid"`` of each expert's logit, the weights' denominator with
    its 1e-6, or ``"softmax"`` over all the experts' logits, the weights
    the chosen probabilities over their plain sum; times *scale* where
    it is not 1.  Product and score in float32.  The choice carries no gradient; the weights carry the
    router's."""
    logits = jnp.einsum(
        "ne,ex->nx", flat.astype(jnp.float32), w_router,
        precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        s, eps = jax.nn.softmax(logits, axis=-1), 0.0
    else:
        s, eps = jax.nn.sigmoid(logits), 1e-6
    biased = jax.lax.stop_gradient(s)
    if bias is not None:
        biased = biased + jax.lax.stop_gradient(bias)
    _, chosen = jax.lax.top_k(biased, top_k)
    # s at the chosen columns, by a compare and a sum over the experts:
    # one term a sum is not zero, so the value is the gathered one, and
    # the transpose is the same compare, not a scatter of N k scalars
    at = chosen[:, :, None] == jnp.arange(s.shape[1])[None, None, :]
    picked = jnp.where(at, s[:, None, :], 0.0).sum(axis=-1)
    weights = picked / (picked.sum(axis=-1, keepdims=True) + eps)
    if scale != 1.0:
        weights = weights * jnp.float32(scale)
    return chosen, weights


def expert_order(dest: jax.Array, plan, n_held: int, block_m: int,
                 max_tiles: int):
    """Where each routed pair goes in the grouped products' row order.

    ``dest [P] int32`` is a pair's expert among the ``n_held`` held here,
    ``n_held`` for a pair that landed elsewhere; *plan* is
    ``routing_plan(dest, n_held)``.  Returns ``(pos [P], pair_of_row [M],
    tile_group [max_tiles], n_tiles [1])`` with ``M = max_tiles *
    block_m``: a held pair's row (``M`` for the others), the pair a row
    holds (``P`` for a padding row), the expert of each tile and the
    tiles in use — each expert's rows start at a tile and it owns at
    least one; *max_tiles* is :func:`tiles_for` the pairs.

    ``pair_of_row`` inverts ``pos`` without moving a pair by its index:
    the pairs in expert order are ONE sort of the pairs by destination
    (stable: inside an expert the pairs keep their order), and a tile
    of an expert is a contiguous run of ``block_m`` of them, read from
    the row side by a loop over the tiles in use.  ``pos`` itself is
    read by tests alone; the layer needs the plan's counts and not its
    ranks."""
    P = dest.shape[0]
    M = max_tiles * block_m
    rank, counts = plan
    tiles = jnp.maximum(-(-counts // block_m), 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * block_m
    held = dest < n_held
    pos = jnp.where(held, row_start[jnp.minimum(dest, n_held - 1)] + rank, M)
    tile_group = jnp.minimum(
        (jnp.arange(max_tiles)[:, None] >= tile_end[None, :]).sum(axis=1),
        n_held - 1).astype(jnp.int32)
    # the held pairs first, expert by expert; a run may start at the
    # last pair, so a tile of padding follows them
    _, in_order = jax.lax.sort_key_val(
        jnp.minimum(dest, n_held), jnp.arange(P, dtype=jnp.int32))
    in_order = jnp.concatenate(
        [in_order, jnp.full((block_m,), P, jnp.int32)])
    # tile t of expert g holds the pairs from its first row's place
    # among g's on, as many as g has left there; a row of no tile in use
    # holds none
    first = jnp.arange(max_tiles) * block_m - row_start[tile_group]
    start = (jnp.cumsum(counts) - counts)[tile_group] + first
    left = counts[tile_group] - first
    n_tiles = tile_end[-1:].astype(jnp.int32)

    def tile(t, rows):
        run = jax.lax.dynamic_slice_in_dim(in_order, start[t], block_m)
        run = jnp.where(jnp.arange(block_m) < left[t], run, P)
        return jax.lax.dynamic_update_slice_in_dim(rows, run, t * block_m, 0)

    pair_of_row = _over_tiles(tile, n_tiles, jnp.full((M,), P, jnp.int32),
                              (dest, counts))
    return pos.astype(jnp.int32), pair_of_row, tile_group, n_tiles


def block_rows(pairs: int) -> int:
    """Rows of a tile where a device routes *pairs* pairs: ``BLOCK_M``,
    16 at toy sizes."""
    return BLOCK_M if pairs >= 16 * BLOCK_M else 16


def tiles_for(pairs: int, n_held: int, block_m: int) -> int:
    """Tiles that hold *pairs* pairs however they fall on *n_held*
    experts, each expert's rows starting at a tile."""
    return -(-pairs // block_m) + n_held


# Tokens into expert order and back: every row is moved from the ROW
# side, by a loop over the tiles in use (the grouped kernels' own bound,
# ``n_tiles``, a value of the run), so what a call costs follows the
# pairs that landed here as the arithmetic does, not the buffers' size.
# Rows past the tiles in use are never gathered and never read, so the
# ``[M, E]`` buffers a loop fills start unwritten where the kernels run
# (``rows_buffer``: one Pallas call that writes nothing, not a fill of
# every row); where they do not, ragged_dot reads every row and they
# start as zeros.  The carries in token order (``[N, E]``, ``d_w``) and
# ``expert_order``'s ``pair_of_row`` keep their fill: every row of them
# is read.  A tile a trip: two or four read the same on the chip
# (PERF.md section 6).

def _over_tiles(tile, n_tiles, carry, like):
    """``tile(t, carry) -> carry`` for the tiles in use, ``t <
    n_tiles[0]``, in order, as a ``lax.fori_loop``.  The carry takes the
    varying mesh axes of the arrays *like* (a loop's carry keeps its
    type)."""
    axes = frozenset().union(*(jax.typeof(a).vma for a in like))

    def cast(c):
        missing = tuple(axes - jax.typeof(c).vma)
        return jax.lax.pcast(c, missing, to="varying") if missing else c

    return jax.lax.fori_loop(0, n_tiles[0], tile, jax.tree.map(cast, carry))


def _tile_of(table, t, block_m: int):
    """Rows ``[t block_m, (t + 1) block_m)`` of *table* ``[M]`` or
    ``[M, E]``."""
    return jax.lax.dynamic_slice_in_dim(table, t * block_m, block_m)


def _rows_in(src, tok_of_row, n_tiles, block_m: int):
    """``[M, E]`` in *src*'s type: row r of a tile in use holds
    ``src[tok_of_row[r]]``, zeros where that is out of range (a padding
    row, as ``moe_tgmm`` needs); rows past the tiles in use are not
    gathered, and hold nothing where the kernels run
    (:func:`rows_buffer`) and zeros where they do not."""
    def tile(t, xs):
        rows = src.at[_tile_of(tok_of_row, t, block_m)].get(
            mode="fill", fill_value=0)
        return jax.lax.dynamic_update_slice_in_dim(xs, rows, t * block_m, 0)

    like = (src, tok_of_row, n_tiles)
    return _over_tiles(
        tile, n_tiles,
        rows_buffer((tok_of_row.shape[0], src.shape[1]), src.dtype, n_tiles,
                    like), like)


def _rows_out(rows, tok_of_row, n_tiles, block_m: int, n_tokens: int,
              weights=None, slot_of_row=None):
    """``[n_tokens, E]`` float32: ``out[tok_of_row[r]] += w_r rows[r]``
    over the rows of the tiles in use, ``w_r = weights[tok_of_row[r],
    slot_of_row[r]]`` (1 without *weights*), one scatter-add a tile.  A
    tile is one expert's, so no token occurs twice in it and the update
    is ``unique_indices`` (a padding row gets an index of its own past
    the tokens, dropped); tiles are taken in order, so a token's terms
    are added in expert order whatever the run."""
    past = n_tokens + jnp.arange(block_m, dtype=tok_of_row.dtype)

    def tile(t, out):
        tok = _tile_of(tok_of_row, t, block_m)
        vals = _tile_of(rows, t, block_m).astype(jnp.float32)
        if weights is not None:
            vals = vals * weights.at[
                tok, _tile_of(slot_of_row, t, block_m)].get(
                    mode="fill", fill_value=0)[:, None]
        return out.at[jnp.where(tok < n_tokens, tok, past)].add(
            vals, mode="drop", unique_indices=True)

    return _over_tiles(
        tile, n_tiles, jnp.zeros((n_tokens, rows.shape[1]), jnp.float32),
        (rows, tok_of_row, n_tiles)
        + (() if weights is None else (weights,)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(flat, tok_of_row, n_tiles, block_m):
    """``xs [M, E]``: row r of a tile in use holds token
    ``tok_of_row[r]`` (zeros where that is out of range: padding
    rows)."""
    return _rows_in(flat, tok_of_row, n_tiles, block_m)


def _dispatch_fwd(flat, tok_of_row, n_tiles, block_m):
    return (_dispatch(flat, tok_of_row, n_tiles, block_m),
            (tok_of_row, n_tiles, flat.shape[0]))


def _dispatch_bwd(block_m, res, d_xs):
    tok_of_row, n_tiles, n_tokens = res
    d_flat = _rows_out(d_xs, tok_of_row, n_tiles, block_m, n_tokens)
    return d_flat.astype(d_xs.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(ys, weights, tok_of_row, slot_of_row, n_tiles, block_m):
    """``out [N, E]`` float32: ``sum_k weights[n, k] * ys[row of pair
    (n, k)]``, a pair that landed elsewhere adding nothing."""
    return _rows_out(ys, tok_of_row, n_tiles, block_m, weights.shape[0],
                     weights, slot_of_row)


def _combine_fwd(ys, weights, tok_of_row, slot_of_row, n_tiles, block_m):
    return (_combine(ys, weights, tok_of_row, slot_of_row, n_tiles, block_m),
            (ys, weights, tok_of_row, slot_of_row, n_tiles))


def _combine_bwd(block_m, res, d_out):
    """One loop over the tiles in use, with ``g = d_out[tok_of_row[r]]``:
    ``d_ys[r] = w_r g``, and ``<ys[r], g>`` is ``d_w`` of the pair row r
    holds (a pair that landed elsewhere keeps 0).  ``d_ys`` past the
    tiles in use is what :func:`rows_buffer` leaves there, as in
    ``_rows_in``; every entry of ``d_w`` is read, and starts at 0."""
    ys, weights, tok_of_row, slot_of_row, n_tiles = res
    past = weights.shape[0] + jnp.arange(block_m, dtype=tok_of_row.dtype)

    def tile(t, carry):
        d_ys, d_w = carry
        tok = _tile_of(tok_of_row, t, block_m)
        slot = _tile_of(slot_of_row, t, block_m)
        g = d_out.at[tok].get(mode="fill", fill_value=0)
        w = weights.at[tok, slot].get(mode="fill", fill_value=0)
        dw = (_tile_of(ys, t, block_m).astype(jnp.float32) * g).sum(axis=-1)
        return (jax.lax.dynamic_update_slice_in_dim(
                    d_ys, (g * w[:, None]).astype(ys.dtype), t * block_m, 0),
                d_w.at[jnp.where(tok < weights.shape[0], tok, past),
                       slot].set(dw, mode="drop", unique_indices=True))

    like = (ys, weights, d_out, tok_of_row, n_tiles)
    d_ys, d_w = _over_tiles(
        tile, n_tiles,
        (rows_buffer(ys.shape, ys.dtype, n_tiles, like),
         jnp.zeros(weights.shape, jnp.float32)), like)
    return d_ys, d_w, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def shared_expert(h: jax.Array, lp, cfg) -> jax.Array:
    """``Ws2 (silu(Ws1 h) * Ws3 h)`` in float32 for ``h [B, T_local,
    E]``: the gated FFN every token takes (``shared_w_gate``,
    ``shared_w_in``, ``shared_w_out``).  Its tensors are whole on every
    rank and ``h`` is the same on every rank of the ``model`` axis, so
    every rank computes the same and no collective follows."""
    dt = cfg.dtype
    gate = jnp.einsum("bte,ef->btf", h, lp["shared_w_gate"].astype(dt))
    up = jnp.einsum("bte,ef->btf", h, lp["shared_w_in"].astype(dt))
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(dt)
    return jnp.einsum("btf,fe->bte", act, lp["shared_w_out"].astype(dt),
                      preferred_element_type=jnp.float32)


def routed_experts(h: jax.Array, lp, cfg, n_model: int, data_axis: str,
                   model_axis: str):
    """``(out, loads, chosen, weights)`` for ``h [B, T_local, E]``: the
    module's ``out`` (float32, summed over the ``model`` axis, then the
    shared expert's added once where the model has one); the
    layer's statistics ``[held + 2]`` int32, whole over both mesh axes:
    the pairs each held expert took, the pairs held but not placed (0:
    nothing is dropped), and the pairs routed, here or elsewhere; and the
    experts each local token chose with their weights ``g``, ``[B,
    T_local, k]`` int32 and float32.

    The rows' buffers have room for EVERY pair (all ``N k`` may land
    here); the kernels' grids (the products, the gate between them,
    the sum of two products' gradients) and the loops into and out of
    expert order end at the tiles in use: rows past them are neither
    written nor read."""
    B, T, E = h.shape
    N, k = B * T, cfg.moe_top_k
    held = cfg.experts_held
    n_loc = held // n_model
    block_m = block_rows(N * k)
    flat = h.reshape(N, E)
    me = jax.lax.axis_index(model_axis)
    with jax.named_scope("tf.moe_route"):
        chosen, weights = route(flat, lp["w_router"], lp.get("router_bias"),
                                k, cfg.moe_router_score,
                                cfg.moe_routed_scale)
        routed = (chosen.reshape(B, T, k), weights.reshape(B, T, k))
        local = chosen - (cfg.moe_held_offset + me * n_loc)
        dest = jnp.where((local >= 0) & (local < n_loc), local,
                         n_loc).reshape(N * k)
        plan = routing_plan(dest, n_loc)
        counts = plan[1]
        _, pair_of_row, tile_group, n_tiles = expert_order(
            dest, plan, n_loc, block_m, tiles_for(N * k, n_loc, block_m))
        placed = pair_of_row < N * k
        tok_of_row = jnp.where(placed, pair_of_row // k, N)
        slot_of_row = pair_of_row % k
        # what follows is this rank's part: its transposes sum over
        # the model axis into the replicated tokens and weights
        flat, weights = (
            a if model_axis in jax.typeof(a).vma
            else jax.lax.pcast(a, model_axis, to="varying")
            for a in (flat, weights))
    with jax.named_scope("tf.moe_dispatch"):
        xs = _dispatch(flat, tok_of_row, n_tiles, block_m)
    with jax.named_scope("tf.moe_experts"):
        def product(x, w):
            return grouped_matmul(x, w, tile_group, n_tiles,
                                  block_m=block_m)

        # two products take the rows: their gradients' sum is a kernel's
        for_gate, for_up = twice(xs, n_tiles, block_m=block_m)
        gate = product(for_gate, lp["moe_w_gate"])
        up = product(for_up, lp["moe_w_in"])
        act = gated(gate, up, n_tiles, block_m=block_m)
        ys = product(act, lp["moe_w_out"])
    with jax.named_scope("tf.moe_combine"):
        out = _combine(ys, weights, tok_of_row, slot_of_row, n_tiles,
                       block_m)
        out = jax.lax.psum(out, model_axis)
        # this rank's experts' loads at their place among the held ones
        mine = jnp.arange(held) // n_loc == me
        loads = jnp.where(mine, jnp.tile(counts, n_model), 0)
        dropped = counts.sum() - placed.sum()
        stats = jax.lax.psum(jnp.concatenate(
            [loads, dropped[None], jnp.where(me == 0, N * k, 0)[None]]
        ).astype(jnp.int32), model_axis)
        if data_axis in jax.typeof(stats).vma:   # the shards' tokens differ
            stats = jax.lax.psum(stats, data_axis)
    out = out.reshape(B, T, E)
    if cfg.shared_ffn:
        with jax.named_scope("tf.shared_expert"):
            out = out + shared_expert(h, lp, cfg)
    return (out, stats) + routed
