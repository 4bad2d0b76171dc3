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

``g`` is normalised over all of ``S``, held or not; what the absent
experts would add is left out, and a ``psum`` over the ``model`` axis adds
the ranks' parts (one held expert a rank is classic expert parallelism;
tokens are replicated over that axis, so there is no exchange).

No (token, expert) pair is dropped and none is cut at a capacity: the
buffers have room for every pair landing here, and the arithmetic
follows the pairs that did — the products are grouped over the held
experts (``ops/grouped_matmul.py``), whose grids end at the tiles in
use.  The plan that puts pairs into expert order (rank within
destination, count a destination) is the exchange's,
``parallel/shuffle.routing_plan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import grouped_matmul
from ..parallel.shuffle import routing_plan

#: rows of a tile of the grouped products; every held expert's rows start
#: at a multiple of it (at most one tile of zero rows an expert)
BLOCK_M = 512
#: columns a layer's statistics carry after the held experts' loads
STAT_DROPPED, STAT_ROUTED = -2, -1


def route(flat: jax.Array, w_router: jax.Array, bias, top_k: int,
          score: str = "sigmoid"):
    """``(chosen [N, k] int32, weights [N, k] float32)`` of tokens ``flat
    [N, E]``: the module's ``S`` and ``g``, the scores ``s`` by *score*:
    ``"sigmoid"`` of each expert's logit, the weights' denominator with
    its 1e-6, or ``"softmax"`` over all the experts' logits, the weights
    the chosen probabilities over their plain sum.  Product and score in
    float32.  The choice carries no gradient; the weights carry the
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
    picked = jnp.take_along_axis(s, chosen, axis=1)
    weights = picked / (picked.sum(axis=-1, keepdims=True) + eps)
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
    least one; *max_tiles* is :func:`tiles_for` the pairs."""
    P = dest.shape[0]
    M = max_tiles * block_m
    rank, counts = plan
    tiles = jnp.maximum(-(-counts // block_m), 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * block_m
    held = dest < n_held
    pos = jnp.where(held, row_start[jnp.minimum(dest, n_held - 1)] + rank, M)
    pair_of_row = jnp.full((M,), P, jnp.int32).at[pos].set(
        jnp.arange(P, dtype=jnp.int32), mode="drop", unique_indices=True)
    tile_group = jnp.minimum(
        (jnp.arange(max_tiles)[:, None] >= tile_end[None, :]).sum(axis=1),
        n_held - 1).astype(jnp.int32)
    return (pos.astype(jnp.int32), pair_of_row, tile_group,
            tile_end[-1:].astype(jnp.int32))


def tiles_for(pairs: int, n_held: int, block_m: int) -> int:
    """Tiles that hold *pairs* pairs however they fall on *n_held*
    experts, each expert's rows starting at a tile."""
    return -(-pairs // block_m) + n_held


# Tokens into expert order and back are permutations of the pairs held
# here, so each one's transpose is the other's gather: no scatter-add.

@jax.custom_vjp
def _dispatch(flat, tok_of_row, pos):
    """``xs [M, E]``: row r holds token ``tok_of_row[r]`` (zeros where
    that is out of range: padding rows); ``pos [N, k]`` is only for the
    transpose."""
    return flat.at[tok_of_row].get(mode="fill", fill_value=0)


def _dispatch_fwd(flat, tok_of_row, pos):
    return _dispatch(flat, tok_of_row, pos), pos


def _dispatch_bwd(pos, d_xs):
    d_flat = d_xs.at[pos].get(mode="fill", fill_value=0)       # [N, k, E]
    return (d_flat.astype(jnp.float32).sum(axis=1).astype(d_xs.dtype),
            None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, weights, pos, tok_of_row, slot_of_row):
    """``out [N, E]`` float32: ``sum_k weights[n, k] * ys[pos[n, k]]``,
    a pair that landed elsewhere (``pos`` out of range) adding nothing."""
    picked = ys.at[pos].get(mode="fill", fill_value=0)         # [N, k, E]
    return (picked.astype(jnp.float32) * weights[..., None]).sum(axis=1)


def _combine_fwd(ys, weights, pos, tok_of_row, slot_of_row):
    return (_combine(ys, weights, pos, tok_of_row, slot_of_row),
            (ys, weights, pos, tok_of_row, slot_of_row))


def _combine_bwd(res, d_out):
    ys, weights, pos, tok_of_row, slot_of_row = res
    w_row = weights.at[tok_of_row, slot_of_row].get(
        mode="fill", fill_value=0)                             # [M]
    d_ys = (d_out.at[tok_of_row].get(mode="fill", fill_value=0)
            * w_row[:, None]).astype(ys.dtype)
    picked = ys.at[pos].get(mode="fill", fill_value=0)
    d_w = (picked.astype(jnp.float32) * d_out[:, None, :]).sum(axis=-1)
    return d_ys, d_w, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(h: jax.Array, lp, cfg, n_model: int, data_axis: str,
                   model_axis: str):
    """``(out, loads, chosen, weights)`` for ``h [B, T_local, E]``: the
    module's ``out`` (float32, summed over the ``model`` axis); the
    layer's statistics ``[held + 2]`` int32, whole over both mesh axes:
    the pairs each held expert took, the pairs held but not placed (0:
    nothing is dropped), and the pairs routed, here or elsewhere; and the
    experts each local token chose with their weights ``g``, ``[B,
    T_local, k]`` int32 and float32.

    The rows' buffers have room for EVERY pair (all ``N k`` may land
    here); the kernels' grids end at the tiles in use, while what is not
    a kernel — the gathers into and out of expert order, the gates —
    reads and writes the buffers whole (PERF.md section 5)."""
    B, T, E = h.shape
    N, k = B * T, cfg.moe_top_k
    held = cfg.experts_held
    n_loc = held // n_model
    block_m = BLOCK_M if N * k >= 16 * BLOCK_M else 16
    flat = h.reshape(N, E)
    me = jax.lax.axis_index(model_axis)
    with jax.named_scope("tf.moe_route"):
        chosen, weights = route(flat, lp["w_router"], lp.get("router_bias"),
                                k, cfg.moe_router_score)
        routed = (chosen.reshape(B, T, k), weights.reshape(B, T, k))
        local = chosen - (cfg.moe_held_offset + me * n_loc)
        dest = jnp.where((local >= 0) & (local < n_loc), local,
                         n_loc).reshape(N * k)
        plan = routing_plan(dest, n_loc)
        counts = plan[1]
        pos, pair_of_row, tile_group, n_tiles = expert_order(
            dest, plan, n_loc, block_m, tiles_for(N * k, n_loc, block_m))
        pos = pos.reshape(N, k)
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
        xs = _dispatch(flat, tok_of_row, pos)
    with jax.named_scope("tf.moe_experts"):
        def product(x, w):
            return grouped_matmul(x, w, tile_group, n_tiles,
                                  block_m=block_m)

        gate, up = product(xs, lp["moe_w_gate"]), product(xs, lp["moe_w_in"])
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(cfg.dtype)
        ys = product(act, lp["moe_w_out"])
    with jax.named_scope("tf.moe_combine"):
        out = _combine(ys, weights, pos, tok_of_row, slot_of_row)
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
    return (out.reshape(B, T, E), stats) + routed
