"""The pieces PR 33 added under the trainer: the grouped products' kernels
(``ops/grouped_matmul.py``), the routed expert layer and its row order
(``models/moe.py``), the gated short convolution and grouped-query
attention (``models/operators.py``), and the flash kernels at head width
64.  CPU, toy sizes, seeded; the kernels run under the Pallas interpreter
here and are compiled for a described v5e at the cell's widths in the
last tests of the file (no number here is a device number).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, SingleDeviceSharding

from mapreduce_tpu.models import moe, operators
from mapreduce_tpu.models.transformer import (TransformerConfig,
                                              init_transformer, loss_local,
                                              transformer_param_spec)
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.ops.flash_attention import flash_attention
from mapreduce_tpu.ops.grouped_matmul import (_grouped, gated,
                                              grouped_matmul, twice)
from mapreduce_tpu.parallel import make_mesh

RNG = np.random.default_rng(5)


# -- the row order ----------------------------------------------------------

#: pairs' destinations among 3 held experts (3 = landed elsewhere)
DESTS = {
    "mixed": RNG.integers(0, 4, size=100),
    "all held": RNG.integers(0, 3, size=96),
    "none held": np.full(64, 3),
    "one expert takes every pair": np.zeros(80, np.int64),
    "an expert takes none": RNG.choice([0, 2, 3], size=70),
}


@pytest.mark.parametrize("name", sorted(DESTS))
def test_expert_order_places_every_held_pair_once(name):
    dest, G, bm = jnp.asarray(DESTS[name], jnp.int32), 3, 16
    plan = moe.routing_plan(dest, G)
    counts = np.asarray(plan[1])
    d = np.asarray(dest)
    n = len(d)
    assert moe.tiles_for(n, G, bm) == -(-n // bm) + G    # room for every pair
    pos, pair_of_row, tile_group, n_tiles = map(np.asarray, moe.expert_order(
        dest, plan, G, bm, moe.tiles_for(n, G, bm)))
    M = len(pair_of_row)
    assert counts.tolist() == [(d == g).sum() for g in range(G)]
    held = d < G
    assert (pos[~held] == M).all() and (pos[held] < M).all()
    assert len(set(pos[held])) == held.sum()      # no two pairs share a row
    assert (pair_of_row[pos[held]] == np.nonzero(held)[0]).all()
    assert (pair_of_row < n).sum() == held.sum()  # nothing dropped, no ghost
    # a pair's row lies in a tile of its expert, in use; an expert's
    # rows keep the pairs' order; every expert owns a tile
    tile = pos[held] // bm
    assert (tile_group[tile] == d[held]).all() and (tile < n_tiles[0]).all()
    for g in range(G):
        rows = pos[d == g]
        assert (np.diff(rows) == 1).all() and (
            len(rows) == 0 or rows[0] % bm == 0)
        assert (tile_group[:n_tiles[0]] == g).sum() == max(
            1, -(-len(rows) // bm))


# The plain reference of the plan: a stable argsort by destination, the
# held experts' pairs laid out a tile-aligned run each.

def _plain_order(dest, n_held, bm, max_tiles):
    P, M = len(dest), max_tiles * bm
    in_order = np.argsort(np.minimum(dest, n_held), kind="stable")
    counts = np.array([(dest == g).sum() for g in range(n_held)])
    pos, pair_of_row = np.full(P, M), np.full(M, P)
    tile_group, row, taken = [], 0, 0
    for g, n in enumerate(counts):
        mine = in_order[taken:taken + n]
        pair_of_row[row:row + n] = mine
        pos[mine] = row + np.arange(n)
        tiles = max(-(-n // bm), 1)
        tile_group += [g] * tiles
        row, taken = row + tiles * bm, taken + n
    n_tiles = len(tile_group)
    tile_group += [n_held - 1] * (max_tiles - n_tiles)
    return pos, pair_of_row, np.array(tile_group), n_tiles, counts


def _routed_dest(score, k, n_tokens):
    """Destinations as the layer makes them: ``route`` over 64 experts
    of which the first 8 are held (8 = landed elsewhere)."""
    flat = jnp.asarray(RNG.normal(size=(n_tokens, 32)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(32, 64)), jnp.float32)
    chosen, _ = moe.route(flat, w, None, k, score)
    return np.where(np.asarray(chosen) < 8, np.asarray(chosen), 8).reshape(-1)


#: ``dest [P]`` among 8 held experts; 768 pairs are `train-mellum2-long`'s
#: 196,608 scaled down: not a power of two
PLAN_CASES = {
    "sigmoid top-4, 512 pairs": lambda: _routed_dest("sigmoid", 4, 128),
    "softmax top-8, 768 pairs": lambda: _routed_dest("softmax", 8, 96),
    "every pair on one held expert": lambda: np.full(600, 5),
    "no pair held": lambda: np.full(768, 8),
    "an expert ends on a tile's last row": lambda: np.repeat(
        [0, 8, 3, 3, 7, 8], [512, 40, 16, 496, 1, 87]),
}


@pytest.mark.parametrize("bm", [16, 512])
@pytest.mark.parametrize("name", sorted(PLAN_CASES))
def test_the_plan_is_a_stable_argsort_by_destination(name, bm):
    """``routing_plan`` and ``expert_order`` against the plain
    reference, in every entry, rows past the tiles in use included."""
    dest, G = PLAN_CASES[name](), 8
    max_tiles = moe.tiles_for(len(dest), G, bm)
    d = jnp.asarray(dest, jnp.int32)
    plan = jax.jit(lambda d: moe.routing_plan(d, G))(d)
    got = jax.jit(lambda d, plan: moe.expert_order(
        d, plan, G, bm, max_tiles))(d, plan)
    pos, pair_of_row, tile_group, n_tiles, counts = _plain_order(
        dest, G, bm, max_tiles)
    np.testing.assert_array_equal(plan[1], counts)
    np.testing.assert_array_equal(got[0], pos)
    np.testing.assert_array_equal(got[1], pair_of_row)
    np.testing.assert_array_equal(got[2], tile_group)
    assert got[3].tolist() == [n_tiles]
    assert all(np.asarray(x).dtype == np.int32 for x in got)


def _route_by_gather(flat, w_router, bias, top_k, score="sigmoid",
                     scale=1.0):
    """``moe.route`` as it picked the chosen scores until PR 40: one
    scalar an index."""
    logits = jnp.einsum("ne,ex->nx", flat.astype(jnp.float32), w_router,
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
    return chosen, weights * jnp.float32(scale) if scale != 1.0 else weights


@pytest.mark.parametrize("score, k, biased, scale", [
    ("sigmoid", 4, True, 1.0), ("softmax", 8, False, 1.0),
    ("sigmoid", 4, True, 1.8)],
    ids=["sigmoid top-4", "softmax top-8", "sigmoid top-4 scaled"])
def test_route_picks_the_chosen_scores_as_a_gather_does(score, k, biased,
                                                        scale):
    """The choice, the weights and the weights' gradient for the tokens
    and the router, against ``take_along_axis`` and its scatter-add."""
    flat = jnp.asarray(RNG.normal(size=(96, 32)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(32, 64)), jnp.float32)
    bias = jnp.asarray(0.1 * RNG.normal(size=64), jnp.float32) if biased \
        else None
    d_weights = jnp.asarray(RNG.normal(size=(96, k)), jnp.float32)

    def with_gradients(route):
        def f(flat, w):
            chosen, weights = route(flat, w, bias, k, score, scale)
            return (weights * d_weights).sum(), (chosen, weights)

        (_, routed), grads = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True))(flat, w)
        return [np.asarray(x) for x in (*routed, *grads)]

    got, want = with_gradients(moe.route), with_gradients(_route_by_gather)
    np.testing.assert_array_equal(got[0], want[0])
    # one term of a sum is not zero; the quotient after it is compiled
    # in another fusion: at most float32's last place
    np.testing.assert_allclose(got[1], want[1], rtol=2.5e-7, atol=0)
    assert np.abs(want[2]).max() > 1e-3 and np.abs(want[3]).max() > 1e-3
    for g, w_ in zip(got[2:], want[2:]):
        np.testing.assert_allclose(g, w_, rtol=1e-5, atol=1e-7)


# -- rows into expert order and back ----------------------------------------
#
# The plain reference: every row gathered, whether its tile is in use or
# not (``M`` rows by ``tok_of_row``, ``N k`` rows by ``pos``), as
# ``models/moe.py`` moved them until PR 36.

@jax.custom_vjp
def _gather_dispatch(flat, tok_of_row, pos):
    return flat.at[tok_of_row].get(mode="fill", fill_value=0)


def _gather_dispatch_fwd(flat, tok_of_row, pos):
    return _gather_dispatch(flat, tok_of_row, pos), pos


def _gather_dispatch_bwd(pos, d_xs):
    d_flat = d_xs.at[pos].get(mode="fill", fill_value=0)       # [N, k, E]
    return (d_flat.astype(jnp.float32).sum(axis=1).astype(d_xs.dtype),
            None, None)


_gather_dispatch.defvjp(_gather_dispatch_fwd, _gather_dispatch_bwd)


@jax.custom_vjp
def _gather_combine(ys, weights, pos, tok_of_row, slot_of_row):
    picked = ys.at[pos].get(mode="fill", fill_value=0)         # [N, k, E]
    return (picked.astype(jnp.float32) * weights[..., None]).sum(axis=1)


def _gather_combine_fwd(ys, weights, pos, tok_of_row, slot_of_row):
    return (_gather_combine(ys, weights, pos, tok_of_row, slot_of_row),
            (ys, weights, pos, tok_of_row, slot_of_row))


def _gather_combine_bwd(res, d_out):
    ys, weights, pos, tok_of_row, slot_of_row = res
    w_row = weights.at[tok_of_row, slot_of_row].get(
        mode="fill", fill_value=0)                             # [M]
    d_ys = (d_out.at[tok_of_row].get(mode="fill", fill_value=0)
            * w_row[:, None]).astype(ys.dtype)
    picked = ys.at[pos].get(mode="fill", fill_value=0)
    d_w = (picked.astype(jnp.float32) * d_out[:, None, :]).sum(axis=-1)
    return d_ys, d_w, None, None, None


_gather_combine.defvjp(_gather_combine_fwd, _gather_combine_bwd)


def _top_k_of(n_tokens, experts, k):
    """k distinct experts a token, as a router chooses them."""
    return np.stack([RNG.permutation(experts)[:k] for _ in range(n_tokens)])


#: ``(dest [N, k], held experts)``: the cases above a pair a token, then
#: the loops' edges among 8 held experts (8 = landed elsewhere)
ROW_CASES = {
    **{name: (d[:, None], 3) for name, d in DESTS.items()},
    "no pair lands, an empty tile an expert": (np.full((24, 4), 8), 8),
    "every pair on one expert": (np.full((90, 1), 5), 8),
    "one expert over five tiles beside seven empty ones": (
        np.concatenate([np.full((70, 1), 3), np.full((70, 3), 8)], axis=1),
        8),
    "top-4 of 16 experts, 8 held": (np.minimum(_top_k_of(50, 16, 4), 8), 8),
}
ROW_TILES = {"no pair lands, an empty tile an expert": 8,
             "every pair on one expert": 6 + 7,
             "one expert over five tiles beside seven empty ones": 5 + 7}


def _row_case(name, E=24):
    dest, G = ROW_CASES[name]
    (N, k), bm = dest.shape, 16
    flat_dest = jnp.asarray(dest.reshape(-1), jnp.int32)
    pos, pair_of_row, _, n_tiles = moe.expert_order(
        flat_dest, moe.routing_plan(flat_dest, G), G, bm,
        moe.tiles_for(N * k, G, bm))
    tok_of_row = jnp.where(pair_of_row < N * k, pair_of_row // k, N)
    tables = dict(pos=pos.reshape(N, k), tok_of_row=tok_of_row,
                  slot_of_row=pair_of_row % k, n_tiles=n_tiles)
    M, U = pair_of_row.shape[0], int(n_tiles[0]) * bm
    assert U // bm == ROW_TILES.get(name, U // bm) and U <= M
    # what the kernels leave past the tiles in use is not zeros
    unwritten = np.where(np.arange(M)[:, None] < U, 0.0, np.nan)
    normal = lambda *shape: RNG.normal(size=shape).astype(np.float32)
    return tables, bm, U, dict(
        flat=normal(N, E), rows=normal(M, E) + unwritten,
        weights=np.abs(normal(N, k)), d_out=normal(N, E))


@pytest.mark.parametrize("name", sorted(ROW_CASES))
def test_dispatch_moves_the_rows_in_use_as_the_gather_of_every_row(name):
    t, bm, U, a = _row_case(name)

    @jax.jit
    def loops(flat, d_xs):
        xs, vjp = jax.vjp(lambda f: moe._dispatch(
            f, t["tok_of_row"], t["n_tiles"], bm), flat)
        return xs, vjp(d_xs)[0]

    @jax.jit
    def gathers(flat, d_xs):
        xs, vjp = jax.vjp(lambda f: _gather_dispatch(
            f, t["tok_of_row"], t["pos"]), flat)
        return xs, vjp(d_xs)[0]

    xs, d_flat = map(np.asarray, loops(a["flat"], a["rows"]))
    want_xs, want_d_flat = map(np.asarray, gathers(a["flat"], a["rows"]))
    assert np.array_equal(xs[:U], want_xs[:U])     # a copy: to the bit
    # past the tiles in use nothing is written: the interpreter's NaN
    assert np.isnan(xs[U:]).all()
    np.testing.assert_allclose(d_flat, want_d_flat, rtol=1e-6, atol=1e-6)
    again = loops(a["flat"], a["rows"])
    assert (np.array_equal(xs, again[0], equal_nan=True)
            and np.array_equal(d_flat, again[1]))


@pytest.mark.parametrize("name", sorted(ROW_CASES))
def test_combine_sums_the_rows_in_use_as_the_gather_of_every_pair(name):
    t, bm, U, a = _row_case(name)
    rows = (t["tok_of_row"], t["slot_of_row"])

    @jax.jit
    def loops(ys, weights, d_out):
        out, vjp = jax.vjp(lambda y, w: moe._combine(
            y, w, *rows, t["n_tiles"], bm), ys, weights)
        return (out,) + vjp(d_out)

    @jax.jit
    def gathers(ys, weights, d_out):
        out, vjp = jax.vjp(lambda y, w: _gather_combine(
            y, w, t["pos"], *rows), ys, weights)
        return (out,) + vjp(d_out)

    got = [np.asarray(x) for x in loops(a["rows"], a["weights"], a["d_out"])]
    want = [np.asarray(x) for x in gathers(
        np.nan_to_num(a["rows"]), a["weights"], a["d_out"])]
    for g, w, rows in zip(got, want, (None, U, None)):   # out, d_ys, d_w
        np.testing.assert_allclose(g[:rows], w[:rows], rtol=1e-6, atol=1e-6)
    again = loops(a["rows"], a["weights"], a["d_out"])
    assert all(np.array_equal(g, b, equal_nan=True)
               for g, b in zip(got, again))


# -- the grouped products ---------------------------------------------------


def _grouped_case(name, K, N):
    dest, G, bm = jnp.asarray(DESTS[name], jnp.int32), 3, 16
    pos, pair_of_row, tile_group, n_tiles = moe.expert_order(
        dest, moe.routing_plan(dest, G), G, bm,
        moe.tiles_for(len(DESTS[name]), G, bm))
    M = pair_of_row.shape[0]
    filled = (np.asarray(pair_of_row) < len(DESTS[name]))[:, None]
    x = jnp.asarray(np.where(filled, RNG.normal(size=(M, K)), 0.0),
                    jnp.float32)
    w = jnp.asarray(RNG.normal(size=(G, K, N)), jnp.float32)
    live = np.arange(M)[:, None] < int(n_tiles[0]) * bm
    return x, w, tile_group, n_tiles, bm, live


@pytest.mark.parametrize("name", sorted(DESTS))
def test_grouped_kernels_equal_ragged_dot_and_the_dense_product(name):
    x, w, tile_group, n_tiles, bm, live = _grouped_case(name, 32, 48)

    def run(kernels):
        def f(x, w):
            # grouped_matmul's two paths: the kernels, interpreted, and
            # what the trainer runs off the TPU
            y = _grouped(x, w, tile_group, n_tiles, bm, kernels, True)
            y = jnp.where(live, y, 0.0)    # rows past the tiles in use
            return (y * jnp.cos(y)).sum(), y

        (_, y), (dx, dw) = jax.value_and_grad(f, argnums=(0, 1),
                                              has_aux=True)(x, w)
        return np.asarray(y), np.where(live, dx, 0.0), np.asarray(dw)

    kernel, ragged = run(True), run(False)
    for a, b in zip(kernel, ragged):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3)
    by_row = np.asarray(w)[np.repeat(np.asarray(tile_group), bm)]
    dense = np.einsum("mk,mkn->mn", np.asarray(x), by_row)
    np.testing.assert_allclose(kernel[0], np.where(live, dense, 0.0),
                               rtol=1e-4, atol=1e-3)
    assert REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                        kernel="moe_gmm", mode="interpret") > 0
    assert REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                        kernel="moe_tgmm", mode="interpret") > 0


def test_grouped_matmul_refuses_rows_that_are_not_the_tables():
    x, w, tile_group, n_tiles, bm, _ = _grouped_case("mixed", 16, 16)
    with pytest.raises(ValueError, match="tiles of"):
        grouped_matmul(x[:-bm], w, tile_group, n_tiles, block_m=bm)


# -- the gate between the products -------------------------------------------


def _plain_gate(a, u):
    """What ``models/moe.py`` ran until PR 38, over every row."""
    return (jax.nn.silu(a.astype(jnp.float32))
            * u.astype(jnp.float32)).astype(a.dtype)


def _with_both_gradients(gate):
    def f(a, u, d_act):
        act, vjp = jax.vjp(gate, a, u)
        return (act,) + vjp(d_act)

    return jax.jit(f)


@pytest.mark.parametrize("in_use", ["one tile", "a part", "every tile"])
@pytest.mark.parametrize("Fe", [1536, 896])
@pytest.mark.parametrize("bm", [16, 512])
def test_the_gate_kernel_is_silu_times_up_on_the_tiles_in_use(bm, Fe, in_use):
    """Values and both gradients, interpreted, at the cells' widths and
    both tile sizes; rows past the tiles in use hold whatever they held
    and are compared with nothing."""
    max_tiles = 3
    n = {"one tile": 1, "a part": 2, "every tile": max_tiles}[in_use]
    a, u, d_act = (jnp.asarray(RNG.normal(size=(max_tiles * bm, Fe)),
                               jnp.bfloat16) for _ in range(3))
    before = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                          kernel="moe_gate", mode="interpret")
    got = _with_both_gradients(functools.partial(
        gated, n_tiles=jnp.asarray([n], jnp.int32), block_m=bm))(a, u, d_act)
    want = _with_both_gradients(_plain_gate)(a, u, d_act)
    exact = np.testing.assert_array_equal
    # float32 between the same roundings, the factors of silu' taken in
    # another order: at most the last place
    close = functools.partial(np.testing.assert_allclose, rtol=2 ** -7,
                              atol=1e-6)
    for g, w, compare in zip(got, want, (exact, close, exact)):
        assert g.dtype == w.dtype == jnp.bfloat16 and g.shape == w.shape
        compare(*(np.asarray(x, np.float32)[:n * bm] for x in (g, w)))
    # forward and backward, each traced once
    assert REGISTRY.sum("mrtpu_pallas_kernel_builds_total", kernel="moe_gate",
                        mode="interpret") == before + 2


@pytest.mark.parametrize("in_use", [1, 2, 3])
@pytest.mark.parametrize("E", [2048, 2304])
@pytest.mark.parametrize("bm", [16, 512])
def test_rows_taken_twice_get_their_gradients_sum_on_the_tiles_in_use(
        bm, E, in_use):
    """``twice`` hands the rows to two products; the gradient for the
    rows is the two gradients' sum, in float32 and rounded once, on the
    rows of the tiles in use (3 tiles in all; the rest is compared with
    nothing)."""
    x, d1, d2 = (jnp.asarray(RNG.normal(size=(3 * bm, E)), jnp.bfloat16)
                 for _ in range(3))
    before = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                          kernel="moe_add", mode="interpret")

    def f(x, d1, d2):
        both, vjp = jax.vjp(lambda x: twice(
            x, jnp.asarray([in_use], jnp.int32), block_m=bm), x)
        return both + vjp((d1, d2))

    x1, x2, d_x = jax.jit(f)(x, d1, d2)
    assert np.array_equal(x1, x) and np.array_equal(x2, x)
    assert d_x.dtype == jnp.bfloat16 and d_x.shape == x.shape
    want = (d1.astype(jnp.float32) + d2.astype(jnp.float32)).astype(
        jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(d_x, np.float32)[:in_use * bm],
        np.asarray(want, np.float32)[:in_use * bm])
    assert REGISTRY.sum("mrtpu_pallas_kernel_builds_total", kernel="moe_add",
                        mode="interpret") == before + 1


def test_the_gate_and_the_fan_out_refuse_rows_that_are_not_whole_tiles():
    a, n = jnp.zeros((40, 128), jnp.float32), jnp.asarray([1], jnp.int32)
    with pytest.raises(ValueError, match="whole tiles of 16"):
        gated(a, a, n, block_m=16)
    with pytest.raises(ValueError, match="whole tiles of 16"):
        twice(a, n, block_m=16)
    with pytest.raises(ValueError, match="not one shape"):
        gated(a[:32], a[:32, :64], n, block_m=16)


# -- rows past the tiles in use: nobody reads them --------------------------
#
# Under the interpreter the rows' buffers the loops start from read NaN
# (``ops/grouped_matmul.rows_buffer``): a read of a row past the tiles in
# use would carry it into the result.

#: ``(dest [N k], held experts, every tile in use)``: pairs' experts
POISON_CASES = {
    "no pair lands, one tile": (np.full(40, 1), 1, False),
    "some pairs land": (RNG.integers(0, 4, size=120), 3, False),
    "every pair lands": (RNG.integers(0, 3, size=120), 3, False),
    "every tile in use": (RNG.integers(0, 3, size=120), 3, True),
}


@pytest.mark.parametrize("name", sorted(POISON_CASES))
def test_no_reader_looks_past_the_tiles_in_use(name, monkeypatch):
    """``_dispatch``, the grouped products with the gate between them,
    and ``_combine``, with every gradient, kernels interpreted: ``xs``
    and ``d_ys`` hold NaN past the tiles in use, and yet the output and
    the gradients for the tokens, the weights and the three matrices are
    finite and, to the bit, what the chain gives from zeroed buffers."""
    dest, G, whole = POISON_CASES[name]
    k, bm, E, Fe = 4, 16, 32, 48
    N = len(dest) // k
    dest = jnp.asarray(dest, jnp.int32)
    plan = moe.routing_plan(dest, G)
    max_tiles = moe.tiles_for(len(dest), G, bm)
    if whole:
        max_tiles = int(moe.expert_order(dest, plan, G, bm, max_tiles)[3][0])
    _, pair_of_row, tile_group, n_tiles = moe.expert_order(
        dest, plan, G, bm, max_tiles)
    U, M = int(n_tiles[0]) * bm, max_tiles * bm
    assert (U == M) == whole
    tok = jnp.where(pair_of_row < N * k, pair_of_row // k, N)
    slot = pair_of_row % k
    flat = jnp.asarray(RNG.normal(size=(N, E)), jnp.bfloat16)
    weights = jnp.asarray(np.abs(RNG.normal(size=(N, k))), jnp.float32)
    mats = tuple(jnp.asarray(RNG.normal(size=shape) / 4, jnp.float32)
                 for shape in ((G, E, Fe), (G, E, Fe), (G, Fe, E)))
    d_out = jnp.asarray(RNG.normal(size=(N, E)), jnp.float32)

    def chain(flat, weights, w_gate, w_in, w_out):
        product = lambda x, w: grouped_matmul(x, w, tile_group, n_tiles,
                                              block_m=bm)
        xs = moe._dispatch(flat, tok, n_tiles, bm)
        for_gate, for_up = twice(xs, n_tiles, block_m=bm)
        act = gated(product(for_gate, w_gate), product(for_up, w_in),
                    n_tiles, block_m=bm)
        ys = product(act, w_out)
        return moe._combine(ys, weights, tok, slot, n_tiles, bm), (xs, ys)

    def run():
        @jax.jit
        def f(flat, weights, mats, d_out):
            out, vjp, (xs, ys) = jax.vjp(chain, flat, weights, *mats,
                                         has_aux=True)
            _, vjp_ys = jax.vjp(lambda y: moe._combine(
                y, weights, tok, slot, n_tiles, bm), ys)
            return (out,) + vjp(d_out), (xs, vjp_ys(d_out)[0])

        got, buffers = f(flat, weights, mats, d_out)
        return ([np.asarray(x, np.float32) for x in got],
                [np.asarray(x, np.float32) for x in buffers])

    unwritten = REGISTRY.sum("mrtpu_moe_row_buffers", kind="unwritten")
    poisoned, (xs, d_ys) = run()
    assert REGISTRY.sum("mrtpu_moe_row_buffers", kind="unwritten") \
        > unwritten
    for rows in (xs, d_ys):
        assert rows.shape == (M, E) and np.isnan(rows[U:]).all()
        assert np.isfinite(rows[:U]).all()
    assert all(np.isfinite(x).all() for x in poisoned)
    if (dest < G).any():
        assert np.abs(poisoned[0]).max() > 0.1

    monkeypatch.setattr(moe, "rows_buffer",
                        lambda shape, dtype, *_: jnp.zeros(shape, dtype))
    zeroed, (xs0, d_ys0) = run()
    assert not xs0[U:].any() and not d_ys0[U:].any()
    assert np.array_equal(xs[:U], xs0[:U])
    for a, b in zip(poisoned, zeroed):     # out, d_flat, d_w, three mats
        np.testing.assert_array_equal(a, b)


# -- the routed layer: the shares add up -------------------------------------

SHARE = dict(vocab=32, embed=32, n_layers=1, n_heads=2, head_dim=16, ffn=32,
             dtype=jnp.float32, flash=False, layer_ffns=("moe",),
             moe_experts=64, moe_top_k=4, moe_ffn=16, moe_held=8,
             moe_router_bias=True)


def _layer_on_one_device(cfg, h, lp):
    mesh = make_mesh(devices=jax.devices()[:1])
    f = jax.shard_map(
        lambda h, lp: moe.routed_experts(h, lp, cfg, 1, "data", "model"),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P(), P(), P()))
    return jax.jit(f)(h, lp)


def test_the_eight_shares_of_a_layer_sum_to_the_uncut_reference():
    """8 of 64 experts a share: nothing is computed alike on every chip
    but the router, so the eight partial outputs add up to the whole
    layer, and the loads to every pair routed."""
    from benchmark import reference_lfm2moe

    E, X, Fe = SHARE["embed"], SHARE["moe_experts"], SHARE["moe_ffn"]
    h = jnp.asarray(RNG.normal(size=(2, 24, E)), jnp.float32)
    full = {"w_router": jnp.asarray(RNG.normal(size=(E, X)), jnp.float32),
            "router_bias": jnp.asarray(0.1 * RNG.normal(size=X), jnp.float32),
            "moe_w_gate": jnp.asarray(RNG.normal(size=(X, E, Fe)) / 6,
                                      jnp.float32),
            "moe_w_in": jnp.asarray(RNG.normal(size=(X, E, Fe)) / 6,
                                    jnp.float32),
            "moe_w_out": jnp.asarray(RNG.normal(size=(X, Fe, E)) / 4,
                                     jnp.float32)}
    with jax.default_matmul_precision("highest"):
        want, (want_chosen, _, want_loads) = reference_lfm2moe.routed_layer(
            h.reshape(-1, E), full["w_router"], full["router_bias"],
            full["moe_w_gate"], full["moe_w_in"], full["moe_w_out"],
            top_k=4)
    total, loads = 0.0, []
    for share in range(8):
        cfg = TransformerConfig(moe_held_offset=8 * share, **SHARE)
        lp = dict(full, **{n: full[n][8 * share:8 * share + 8]
                           for n in ("moe_w_gate", "moe_w_in", "moe_w_out")})
        out, stats, chosen, _ = _layer_on_one_device(cfg, h, lp)
        total = total + np.asarray(out)
        loads += np.asarray(stats)[:moe.STAT_DROPPED].tolist()
        assert np.asarray(stats)[moe.STAT_DROPPED] == 0
        assert np.asarray(stats)[moe.STAT_ROUTED] == 2 * 24 * 4
        assert (np.sort(np.asarray(chosen).reshape(-1, 4))
                == np.sort(np.asarray(want_chosen))).all()
    np.testing.assert_allclose(total.reshape(-1, E), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert loads == np.asarray(want_loads).tolist()
    assert sum(loads) == 2 * 24 * 4


def test_the_layer_on_the_cpu_is_the_layer_with_the_plain_gate(monkeypatch):
    """``routed_experts`` on the CPU, output and every gradient, against
    itself with the gate written out over every row and the rows handed
    to the two products as a plain pair: under ``shard_map``'s checking
    the tables vary over the mesh, so the rule of the products takes the
    plain expressions and builds no kernel.
    With the checking off nothing varies and the forward runs the three
    kernels interpreted (the transposes of the layer's casts need the
    checking)."""
    cfg = TransformerConfig(**SHARE)
    E, Fe = SHARE["embed"], SHARE["moe_ffn"]
    h = jnp.asarray(RNG.normal(size=(2, 24, E)), jnp.float32)
    lp = {"w_router": jnp.asarray(RNG.normal(size=(E, 64)), jnp.float32),
          "router_bias": jnp.zeros((64,), jnp.float32),
          "moe_w_gate": jnp.asarray(RNG.normal(size=(8, E, Fe)) / 6,
                                    jnp.float32),
          "moe_w_in": jnp.asarray(RNG.normal(size=(8, E, Fe)) / 6,
                                  jnp.float32),
          "moe_w_out": jnp.asarray(RNG.normal(size=(8, Fe, E)) / 4,
                                   jnp.float32)}
    d_out = jnp.asarray(RNG.normal(size=h.shape), jnp.float32)
    mesh = make_mesh(devices=jax.devices()[:1])
    built = lambda: REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                                 kernel="moe_gate", mode="interpret")

    def layer(check_vma=True):
        return jax.jit(jax.shard_map(
            lambda h, lp: moe.routed_experts(h, lp, cfg, 1, "data",
                                             "model")[0],
            mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=check_vma))

    def with_gradients():
        out, vjp = jax.vjp(layer(), h, lp)
        d_h, d_lp = vjp(d_out)
        return [np.asarray(x) for x in
                (out, d_h, *(d_lp[n] for n in sorted(lp)))]

    buffers = lambda kind: REGISTRY.sum("mrtpu_moe_row_buffers", kind=kind)
    before = built()
    unwritten, zeroed = buffers("unwritten"), buffers("zeroed")
    now = with_gradients()
    assert built() == before
    # ragged_dot may read every row: the loops' buffers start as zeros
    assert buffers("unwritten") == unwritten and buffers("zeroed") > zeroed
    assert np.abs(now[0]).max() > 0.1 and np.abs(now[1]).max() > 0.1
    np.testing.assert_allclose(np.asarray(layer(check_vma=False)(h, lp)),
                               now[0], rtol=1e-4, atol=1e-5)
    assert built() == before + 1
    assert buffers("unwritten") == unwritten + 1
    monkeypatch.setattr(moe, "gated",
                        lambda a, u, n_tiles, block_m: _plain_gate(a, u))
    monkeypatch.setattr(moe, "twice", lambda x, n_tiles, block_m: (x, x))
    for a, b in zip(now, with_gradients()):
        np.testing.assert_array_equal(a, b)


# -- grouped-query attention --------------------------------------------------

GQA = dict(vocab=64, embed=64, n_layers=1, n_heads=4, head_dim=64, ffn=64,
           dtype=jnp.float32, rope_theta=1e4)
GQA_TOKENS = RNG.integers(0, 64, size=(2, 65)).astype(np.int32)


def _loss_and_grads(cfg, params):
    mesh = make_mesh(devices=jax.devices()[:1])
    f = jax.shard_map(
        lambda p, x, y: loss_local(p, x, y, cfg, 1), mesh=mesh,
        in_specs=({n: transformer_param_spec(n) for n in params},
                  P(None, "data"), P(None, "data")), out_specs=P())
    loss, grads = jax.jit(jax.value_and_grad(f))(
        params, GQA_TOKENS[:, :-1], GQA_TOKENS[:, 1:])
    return float(loss), {n: np.asarray(g) for n, g in grads.items()}


@pytest.mark.parametrize("flash", [False, True], ids=["jnp", "flash"])
def test_grouped_query_attention_is_attention_over_repeated_heads(flash):
    """2 key/value heads under 4 query heads against the block's fused
    multi-head attention whose K and V projections are the two heads'
    repeated: the loss to rounding, and the gradient of a key/value head
    the sum over the query heads it serves (head width 64, the kernels
    interpreted)."""
    grouped = TransformerConfig(n_kv_heads=2, flash=flash, **GQA)
    fused = TransformerConfig(flash=flash, **GQA)
    gp = init_transformer(jax.random.key(2), grouped)
    E, H, Hkv, D = 64, 4, 2, 64
    kv = gp["L0.wkv"].reshape(E, 2, Hkv, D)
    repeated = jnp.repeat(kv, H // Hkv, axis=2).reshape(E, 2, H * D)
    fp = {n: a for n, a in gp.items() if n not in ("L0.wq", "L0.wkv")}
    fp["L0.wqkv"] = jnp.concatenate([gp["L0.wq"][:, None], repeated], axis=1)
    loss_g, grads_g = _loss_and_grads(grouped, gp)
    loss_f, grads_f = _loss_and_grads(fused, fp)
    assert abs(loss_g - loss_f) < 1e-5 * abs(loss_f)
    np.testing.assert_allclose(grads_g["L0.wq"], grads_f["L0.wqkv"][:, 0],
                               rtol=1e-4, atol=1e-6)
    summed = grads_f["L0.wqkv"][:, 1:].reshape(E, 2, Hkv, H // Hkv, D).sum(3)
    np.testing.assert_allclose(grads_g["L0.wkv"].reshape(E, 2, Hkv, D),
                               summed, rtol=1e-4, atol=1e-6)


def test_flash_kernels_at_head_width_64_equal_plain_attention():
    q, k, v = (jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.float32)
               for _ in range(3))

    def plain(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 8.0
        mask = jnp.tril(jnp.ones((128, 128), bool))
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(jnp.where(mask, s, -jnp.inf)), v)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64,
                               block_kv=64, interpret=True)

    w = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.float32)
    for f, g in zip(
            jax.value_and_grad(lambda *a: (kernel(*a) * w).sum(),
                               argnums=(0, 1, 2))(q, k, v)[1],
            jax.value_and_grad(lambda *a: (plain(*a) * w).sum(),
                               argnums=(0, 1, 2))(q, k, v)[1]):
        np.testing.assert_allclose(f, g, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(kernel(q, k, v), plain(q, k, v), rtol=1e-4,
                               atol=1e-5)


def test_the_head_norm_scales_each_head_before_the_rotation():
    """``qk_norm``: q and k leave ``grouped_qkv`` with every head's 64
    dims at unit mean square times the scale, rotated after."""
    cfg = TransformerConfig(n_kv_heads=2, qk_norm=True, norm_eps=1e-5,
                            flash=True, **{**GQA, "rope_theta": None})
    lp = {n[3:]: a for n, a in init_transformer(
        jax.random.key(1), cfg).items() if n.startswith("L0.")}
    lp["q_norm_scale"] = jnp.full((64,), 2.0)
    h = jnp.asarray(RNG.normal(size=(1, 16, 64)), jnp.float32)
    q, k, v = operators.grouped_qkv(h, lp, cfg, 1, None)
    assert q.shape == k.shape == v.shape == (1, 4, 16, 64)
    np.testing.assert_allclose(np.square(q).mean(-1), 4.0, rtol=1e-3)
    np.testing.assert_allclose(np.square(k).mean(-1), 1.0, rtol=1e-3)
    assert (np.asarray(k[:, 0]) == np.asarray(k[:, 1])).all()  # one group


# -- the gated short convolution ---------------------------------------------


@functools.lru_cache(maxsize=None)
def _conv_setup():
    cfg = TransformerConfig(vocab=32, embed=16, n_layers=1, n_heads=2,
                            head_dim=8, ffn=16, dtype=jnp.float32,
                            layer_ops=("conv",), conv_taps=3)
    lp = {n[3:]: a for n, a in init_transformer(
        jax.random.key(4), cfg).items() if n.startswith("L0.conv")}
    mesh = make_mesh(n_model=2)                 # 2 x 4: channels x sequence
    run = jax.jit(jax.shard_map(
        lambda h, lp: jax.lax.psum(
            operators.short_conv(h, lp, cfg, "data"), "model"),
        mesh=mesh, in_specs=(P(None, "data"), {
            n: transformer_param_spec("L0." + n) for n in lp}),
        out_specs=P(None, "data")))
    return lp, run


def test_short_conv_equals_a_loop_over_its_taps():
    """Channels split over 2 model ranks, the sequence over 4 shards (a
    shard's first two positions read the previous shard's last two)."""
    lp, run = _conv_setup()
    h = jnp.asarray(RNG.normal(size=(2, 32, 16)), jnp.float32)
    b, c, u = (np.asarray(h) @ np.asarray(lp["conv_in"][:, j])
               for j in range(3))
    v, w = b * u, np.asarray(lp["conv_w"])
    y = np.zeros_like(v)
    for t in range(32):
        for j in range(3):
            if t - 2 + j >= 0:
                y[:, t] += w[:, j] * v[:, t - 2 + j]
    want = (c * y) @ np.asarray(lp["conv_out"])
    np.testing.assert_allclose(run(h, lp), want, rtol=1e-4, atol=1e-5)


def test_short_conv_position_t_never_reads_t_plus_1():
    lp, run = _conv_setup()
    h = jnp.asarray(RNG.normal(size=(1, 32, 16)), jnp.float32)
    for t in (0, 7, 8, 30):                     # 7 | 8: a shard boundary
        moved = h.at[:, t + 1:].add(1.0)
        a, b = np.asarray(run(h, lp)), np.asarray(run(moved, lp))
        assert (a[:, :t + 1] == b[:, :t + 1]).all()
        assert not (a[:, t + 1] == b[:, t + 1]).all()


# -- compiled for the chip, at the cell's widths ------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Off the TPU the kernels default to the interpreter."""
    from mapreduce_tpu.ops import flash_attention as fa, grouped_matmul as gm

    for module in (fa, gm):
        monkeypatch.setattr(module, "default_interpret", lambda i=None: False)
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def test_grouped_kernels_compile_for_a_v5e_at_the_cells_widths(one_chip,
                                                               mosaic):
    """2048 x 1536, 264 tiles of 512 rows, 8 experts: forward, the rows'
    gradient and the weights' gradient; the grids' bound is a value."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)

    def f(x, w, tile_group, n_tiles):
        g = lambda x, w: grouped_matmul(
            x, w, tile_group, n_tiles, block_m=512).astype(jnp.float32).sum()
        return jax.grad(g, argnums=(0, 1))(x, w)

    text = jax.jit(f).lower(
        sds((264 * 512, 2048), jnp.bfloat16), sds((8, 2048, 1536),
                                                  jnp.float32),
        sds((264,), jnp.int32), sds((1,), jnp.int32)).compile().as_text()
    assert text.count("moe_gmm") >= 2 and "moe_tgmm" in text


def test_grouped_kernels_compile_for_a_v5e_at_2304_by_896(one_chip, mosaic):
    """The widths of `train-mellum2-long`: a lane block of 896 columns
    whole (`pick_block` would halve it to 448, which Mosaic refuses) and
    of 1152 rows of the weight gradient; 392 tiles of 512 rows."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)

    def f(x, w, tile_group, n_tiles):
        g = lambda x, w: grouped_matmul(
            x, w, tile_group, n_tiles, block_m=512).astype(jnp.float32).sum()
        return jax.grad(g, argnums=(0, 1))(x, w)

    for K, N in ((2304, 896), (896, 2304)):
        text = jax.jit(f).lower(
            sds((392 * 512, K), jnp.bfloat16), sds((8, K, N), jnp.float32),
            sds((392,), jnp.int32), sds((1,), jnp.int32)
        ).compile().as_text()
        assert text.count("moe_gmm") >= 2 and "moe_tgmm" in text


def _hlo_shapes(text):
    """``{%name: its array shape}`` of every value a compiled program's
    text defines."""
    import re

    return {name: tuple(int(d) for d in dims.split(",") if d)
            for name, dims in re.findall(
                r"(%[\w.\-]+) = \w+\[([\d,]*)\]", text)}


@pytest.mark.parametrize("N, E, k", [(32768, 2048, 4), (24576, 2304, 8)],
                         ids=["train-lfm2moe-8k", "train-mellum2-long"])
def test_no_row_mover_compiled_for_a_v5e_touches_every_row(one_chip, mosaic,
                                                           N, E, k):
    """``_dispatch`` and ``_combine`` with their transposes at a cell's
    shape, 8 held experts: no gather or scatter of the compiled programs
    has an operand or a result of ``M`` or ``N k`` rows of ``E`` (the
    loops move 512 at a time; a scatter's ``[N, E]`` carry is updated in
    place), and the two programs reserve less than the gathers of every
    row do.  Each function alone: composed, the buffers BETWEEN them
    (``xs``, ``d_ys``) are temporaries of either formulation."""
    import re

    bm = 512
    M = moe.tiles_for(N * k, 8, bm) * bm
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    order = (sds((N, k), jnp.int32), sds((M,), jnp.int32),
             sds((M,), jnp.int32), sds((1,), jnp.int32))

    def vjp_of(dispatch, combine):
        def rows_in(flat, d_xs, pos, tok, slot, n_tiles):
            xs, vjp = jax.vjp(
                lambda f: dispatch(f, pos, tok, slot, n_tiles), flat)
            return xs, vjp(d_xs)

        def rows_out(ys, weights, d_out, pos, tok, slot, n_tiles):
            out, vjp = jax.vjp(
                lambda y, w: combine(y, w, pos, tok, slot, n_tiles),
                ys, weights)
            return out, vjp(d_out)

        return (jax.jit(rows_in).lower(
                    sds((N, E), jnp.bfloat16), sds((M, E), jnp.bfloat16),
                    *order).compile(),
                jax.jit(rows_out).lower(
                    sds((M, E), jnp.bfloat16), sds((N, k), jnp.float32),
                    sds((N, E), jnp.float32), *order).compile())

    loops = vjp_of(
        lambda f, pos, tok, slot, n: moe._dispatch(f, tok, n, bm),
        lambda y, w, pos, tok, slot, n: moe._combine(y, w, tok, slot, n,
                                                     bm))
    gathers = vjp_of(
        lambda f, pos, tok, slot, n: _gather_dispatch(f, tok, pos),
        lambda y, w, pos, tok, slot, n: _gather_combine(y, w, pos, tok,
                                                        slot))

    def whole_buffers_moved(compiled):
        text = compiled.as_text()
        shape_of = _hlo_shapes(text)
        moved = []
        for line in re.findall(r"^.* (?:gather|scatter)\(.*$", text, re.M):
            for name in re.findall(r"%[\w.\-]+", line.split(", metadata")[0]):
                shape = shape_of.get(name, ())
                if (len(shape) >= 2 and shape[-1] == E
                        and int(np.prod(shape[:-1])) in (M, N * k)):
                    moved.append((name, shape))
        return moved

    assert not any(whole_buffers_moved(c) for c in loops)
    assert all(whole_buffers_moved(c) for c in gathers)   # the check sees
    reserved = lambda pair: sum(
        c.memory_analysis().temp_size_in_bytes for c in pair)
    assert reserved(loops) < reserved(gathers)


def _moves_by_index(text, entries):
    """The gathers and scatters of a compiled program's text whose index
    operand has one of *entries* index vectors."""
    import re

    shape_of = _hlo_shapes(text)
    moved = []
    for line in re.findall(r"^.* (?:gather|scatter)\(.*$", text, re.M):
        head = line.split(", metadata")[0]
        indices = re.findall(r"%[\w.\-]+", head.split("(", 1)[1])[1]
        shape = list(shape_of.get(indices, ()))
        at = re.search(r"index_vector_dim=(\d+)", head)
        if at and int(at.group(1)) < len(shape):
            del shape[int(at.group(1))]
        if int(np.prod(shape)) in entries:
            moved.append((indices, shape_of.get(indices)))
    return moved


@pytest.mark.parametrize(
    "N, E, k, score",
    [(32768, 2048, 4, "sigmoid"), (24576, 2304, 8, "softmax"),
     (8192, 2048, 4, "sigmoid")],
    ids=["train-lfm2moe-8k", "train-mellum2-long", "train-glm47flash-mla"])
def test_no_move_a_pair_in_the_plan_compiled_for_a_v5e(one_chip, mosaic, N,
                                                       E, k, score):
    """``route``, ``routing_plan`` and ``expert_order`` as the layer
    calls them at a cell's shape, 8 of 64 experts held, value and
    gradient: no gather and no scatter of the compiled program has an
    index operand of ``N k`` or ``M`` entries (the chosen scores, the
    rank and their transposes are compares and sums; the inversion is a
    sort and a run of 512 a tile).  Written with ``take_along_axis`` the
    program has them, so the check sees."""
    bm = 512
    max_tiles = moe.tiles_for(N * k, 8, bm)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)

    def compiled(route):
        def routed(flat, w_router, bias):
            chosen, weights = route(flat, w_router, bias, k, score)
            dest = jnp.where(chosen < 8, chosen, 8).reshape(N * k)
            plan = moe.routing_plan(dest, 8)
            order = moe.expert_order(dest, plan, 8, bm, max_tiles)
            return weights, (chosen, plan, order)

        def f(flat, w_router, bias, d_weights):
            weights, vjp, tables = jax.vjp(
                lambda a, b: routed(a, b, bias), flat, w_router,
                has_aux=True)
            return weights, tables, vjp(d_weights)

        return jax.jit(f).lower(
            sds((N, E), jnp.bfloat16), sds((E, 64), jnp.float32),
            sds((64,), jnp.float32), sds((N, k), jnp.float32)
        ).compile().as_text()

    entries = (N * k, max_tiles * bm)
    assert not _moves_by_index(compiled(moe.route), entries)
    assert _moves_by_index(compiled(_route_by_gather), entries)


@pytest.mark.parametrize(
    "N, E, k", [(32768, 2048, 4), (24576, 2304, 8), (8192, 2048, 4)],
    ids=["train-lfm2moe-8k", "train-mellum2-long", "train-glm47flash-mla"])
def test_no_row_buffer_compiled_for_a_v5e_is_filled_first(one_chip, mosaic,
                                                          monkeypatch, N, E,
                                                          k):
    """``_dispatch`` forward and ``_combine`` with its transpose at a
    cell's shape, 8 held experts: the ``[M, E]`` buffers the loops fill
    (``xs``, ``d_ys``) come from ``moe_rows_unwritten``, and no broadcast
    or copy of the compiled programs has a result of ``M`` rows of
    ``E``.  Every such call takes operands: one with none is placed at
    the start of the whole training step, where twelve of them outgrow
    a v5e's memory at `train-mellum2-long`'s shape.  Started from
    ``jnp.zeros`` the programs have such a broadcast, so the check
    sees."""
    import re

    bm = 512
    M = moe.tiles_for(N * k, 8, bm) * bm
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)

    def compiled():     # traced anew: jit's cache keys on the function
        def rows_in(flat, tok, n_tiles):
            return moe._dispatch(flat, tok, n_tiles, bm)

        def rows_out(ys, weights, d_out, tok, slot, n_tiles):
            out, vjp = jax.vjp(lambda y, w: moe._combine(
                y, w, tok, slot, n_tiles, bm), ys, weights)
            return out, vjp(d_out)

        table, n = sds((M,), jnp.int32), sds((1,), jnp.int32)
        return [jax.jit(rows_in).lower(sds((N, E), jnp.bfloat16), table,
                                       n).compile().as_text(),
                jax.jit(rows_out).lower(
                    sds((M, E), jnp.bfloat16), sds((N, k), jnp.float32),
                    sds((N, E), jnp.float32), table, table,
                    n).compile().as_text()]

    def filled(text):
        return re.findall(r"^.* = \w+\[%d,%d\][^ ]* (?:broadcast|copy)\("
                          % (M, E), text, re.M)

    texts = compiled()
    for t in texts:
        calls = re.findall(
            r"%[\w.]*moe_rows_unwritten[\w.]* = \S+ custom-call\((.*?)\)", t)
        assert calls and all(calls)
    assert not any(filled(t) for t in texts)
    monkeypatch.setattr(moe, "rows_buffer",
                        lambda shape, dtype, *_: jnp.zeros(shape, dtype))
    assert all(filled(t) for t in compiled())


@pytest.mark.parametrize("N, E, Fe, k",
                         [(32768, 2048, 1536, 4), (24576, 2304, 896, 8)],
                         ids=["train-lfm2moe-8k", "train-mellum2-long"])
def test_nothing_between_the_products_compiled_for_a_v5e_touches_every_row(
        one_chip, mosaic, N, E, Fe, k):
    """The expert stage (rows to two products, the gate, the third
    product) with every gradient at a cell's shape, 8 held experts: the
    compiled program holds ``moe_gate`` forward and backward and
    ``moe_add``, and outside the kernels no operation has an operand or
    a result of ``M`` rows of ``Fe`` or of ``E``.  The plain
    expressions' program has, so the check sees."""
    import re

    bm = 512
    tiles = moe.tiles_for(N * k, 8, bm)
    M = tiles * bm
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)

    def compiled(fan, gate):
        def f(xs, w_gate, w_in, w_out, d_ys, tile_group, n_tiles):
            def stage(xs, w_gate, w_in, w_out):
                product = lambda x, w: grouped_matmul(
                    x, w, tile_group, n_tiles, block_m=bm)
                for_gate, for_up = fan(xs, n_tiles)
                act = gate(product(for_gate, w_gate), product(for_up, w_in),
                           n_tiles)
                return product(act, w_out)

            ys, vjp = jax.vjp(stage, xs, w_gate, w_in, w_out)
            return ys, vjp(d_ys)

        return jax.jit(f).lower(
            sds((M, E), jnp.bfloat16), sds((8, E, Fe), jnp.float32),
            sds((8, E, Fe), jnp.float32), sds((8, Fe, E), jnp.float32),
            sds((M, E), jnp.bfloat16), sds((tiles,), jnp.int32),
            sds((1,), jnp.int32)).compile().as_text()

    def whole_buffers_touched(text):
        shape_of = _hlo_shapes(text)
        touched = []
        for name, op, rest in re.findall(
                r"^\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\((.*)$", text,
                re.M):
            if op in ("parameter", "custom-call", "tuple",
                      "get-tuple-element", "bitcast"):
                continue
            names = [name] + re.findall(r"%[\w.\-]+",
                                        rest.split(", metadata")[0])
            if any(shape_of.get(n) in ((M, Fe), (M, E)) for n in names):
                touched.append((name, op))
        return touched

    kernels = compiled(lambda x, n: twice(x, n, block_m=bm),
                       lambda a, u, n: gated(a, u, n, block_m=bm))
    assert kernels.count("moe_gate") >= 2 and "moe_add" in kernels
    assert not whole_buffers_touched(kernels)
    plain = whole_buffers_touched(compiled(
        lambda x, n: (x, x), lambda a, u, n: _plain_gate(a, u)))
    assert len(plain) >= 3      # the gate, its transpose, the sum


def test_windowed_flash_kernels_compile_for_a_v5e_at_the_cells_shape(
        one_chip, mosaic):
    """One sequence of 24,576 positions, 32 heads of 128, a window of
    1,024: the three windowed programs, by their own names."""
    x = jax.ShapeDtypeStruct((1, 32, 24576, 128), jnp.bfloat16,
                             sharding=one_chip)
    f = lambda q, k, v: flash_attention(q, k, v, window=1024).astype(
        jnp.float32).sum()
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    for name in ("flash_fwd_win", "flash_dkv_win", "flash_dq_win"):
        assert name in text


def test_flash_kernels_compile_for_a_v5e_at_head_width_64(one_chip, mosaic):
    x = jax.ShapeDtypeStruct((1, 32, 8192, 64), jnp.bfloat16,
                             sharding=one_chip)
    f = lambda q, k, v: flash_attention(q, k, v, causal=True).astype(
        jnp.float32).sum()
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert "flash_fwd" in text and "flash_dkv" in text


def test_flash_kernels_compile_for_a_v5e_at_head_width_256(one_chip, mosaic):
    """`train-glm47flash-mla`'s call: one sequence of 8,192 positions, 20
    heads of 256 (latent attention's q/k width, 192 + 64, and its v's),
    the default 1024 x 1024 tiles: forward, and the backward tile loop
    with the float32 dQ of all 8 Q tiles (8 MiB) resident beside a tile
    reserve that follows the head width."""
    x = jax.ShapeDtypeStruct((1, 20, 8192, 256), jnp.bfloat16,
                             sharding=one_chip)
    f = lambda q, k, v: flash_attention(q, k, v, causal=True).astype(
        jnp.float32).sum()
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    for name in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert name in text


def test_the_chunked_loss_compiles_to_three_products_a_chunk_backward(
        one_chip, mosaic):
    """The loss stage of ``train-lfm2moe-8k`` alone (no layer: B 4, T
    8192, ``loss_block`` 2048, E 2048, vocabulary 8192, tied head).
    Under ``jax.checkpoint`` the backward pass's recomputed row maximum
    compiled to a ``reduce-window`` of 16,383 columns over ``[4, 2048,
    8192]``, 94 ms of that cell's step (PERF.md section 6, PR 34); the
    rule of ``chunk_nll`` has no maximum to recompute, and a chunk's
    backward pass is its logits, ``dx`` and ``dw``."""
    import re

    from jax.sharding import NamedSharding

    cfg = TransformerConfig(vocab=8192, embed=2048, n_layers=0, n_heads=32,
                            head_dim=64, ffn=2048, loss_block=2048,
                            tied_embeddings=True)
    mesh = make_mesh(devices=[next(iter(one_chip.device_set))])
    shapes = jax.eval_shape(lambda: init_transformer(jax.random.key(0), cfg))
    specs = {n: transformer_param_spec(n) for n in shapes}
    tokens = jax.ShapeDtypeStruct(
        (4, 8192), jnp.int32, sharding=NamedSharding(mesh, P(None, "data")))
    loss = jax.shard_map(lambda p, x, y: loss_local(p, x, y, cfg, 1),
                         mesh=mesh,
                         in_specs=(specs, P(None, "data"), P(None, "data")),
                         out_specs=P())
    text = jax.jit(jax.grad(loss)).lower(
        {n: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                 sharding=NamedSharding(mesh, specs[n]))
         for n, a in shapes.items()}, tokens, tokens).compile().as_text()
    assert "reduce-window(" not in text
    # the matrix products (the chip's compiler writes them as
    # convolutions, each the root of a fusion) of each loop body: the
    # forward scan's, then the backward scan's
    computations = dict(re.findall(
        r"^(?:ENTRY )?%([\w.\-]+) \(.*?\{\n(.*?)^\}", text, re.M | re.S))
    products = sorted(
        sum(" convolution(" in computations[called]
            for called in re.findall(r"calls=%([\w.\-]+)",
                                     computations[body]))
        for body in set(re.findall(r"body=%([\w.\-]+)", text)))
    assert products == [1, 3]
