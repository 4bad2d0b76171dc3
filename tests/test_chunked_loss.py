"""The chunked loss's own backward rule (PR 34):
``models/transformer.chunk_nll`` keeps each row's log-sum-exp and its
backward pass reads the softmax off logits it makes once more, with no
``jax.checkpoint`` around it.  Here: value and gradients against the
plain formula written out below, in float32, on meshes that shard the
vocabulary and the sequence; what the loss scans keep between the
passes; and the gauge that says so.  CPU, toy sizes; no time here is a
device number.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mapreduce_tpu.models.transformer import (TransformerConfig,
                                              TransformerTrainer, chunk_nll,
                                              init_transformer,
                                              loss_kept_bytes, loss_local,
                                              transformer_param_spec)
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")

B, T, E, V = 2, 32, 16, 64
RNG = np.random.default_rng(34)


def plain_nll(x, t, w):
    """[B, T, E], [B, T], [E, V] -> [B, T]: the formula, whole."""
    logits = jnp.einsum("bte,ev->btv", x, w)
    return (jax.scipy.special.logsumexp(logits, axis=-1)
            - jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0])


def close(got, want, tol=2e-5):
    """Float32 against float32: the two differ by the order of their
    sums (shards, chunks), a few units of rounding."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


# -- the rule itself, under a cotangent that differs row by row -------------


@pytest.mark.parametrize("n_model, n_data", [(1, 1), (2, 1), (2, 2)])
def test_the_rule_against_the_plain_formula(n_model, n_data):
    x = jnp.asarray(RNG.normal(size=(B, T, E)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(E, V)), jnp.float32)
    t = jnp.asarray(RNG.integers(0, V, size=(B, T)), jnp.int32)
    g = jnp.asarray(RNG.normal(size=(B, T)), jnp.float32)
    # targets in this rank's shard and in the other's, on every rank
    assert {int(c) for c in np.unique(np.asarray(t) // (V // 2))} == {0, 1}

    def local(x, t, w):
        w = jax.lax.pcast(w, "data", to="varying")
        return chunk_nll(x, t, w, jnp.float32, "model")

    mesh = make_mesh(devices=jax.devices()[:n_model * n_data],
                     n_model=n_model)
    rows = P(None, "data")
    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(rows, rows, P(None, "model")),
                      out_specs=rows)
    got, vjp = jax.vjp(lambda x, w: f(x, t, w), x, w)
    want, plain_vjp = jax.vjp(lambda x, w: plain_nll(x, t, w), x, w)
    close(got, want)
    for a, b in zip(vjp(g), plain_vjp(g)):
        close(a, b)


# -- through loss_local: chunked or whole, tied head or its own -------------

TOKENS = RNG.integers(0, V, size=(B, T + 1), dtype=np.int32)


def head_only(tied, loss_block):
    """No layer: the hidden state is the embedding's rows, so the plain
    loss is three lines."""
    return TransformerConfig(vocab=V, embed=E, n_layers=0, n_heads=2,
                             head_dim=8, ffn=32, dtype=jnp.float32,
                             tied_embeddings=tied, loss_block=loss_block)


def plain_loss(params, tokens, targets):
    x = params["embed"][tokens]
    w = params["unembed"] if "unembed" in params else params["embed"].T
    return plain_nll(x, targets, w).mean()


def sharded(cfg, n_model, n_data):
    params = init_transformer(jax.random.key(5), cfg)
    # an embedding of the head's scale, so that neither term of a tied
    # table's gradient is lost in the other
    params["embed"] = params["embed"] * 50.0
    mesh = make_mesh(devices=jax.devices()[:n_model * n_data],
                     n_model=n_model)
    f = jax.shard_map(
        lambda p, x, y: loss_local(p, x, y, cfg, n_model), mesh=mesh,
        in_specs=({n: transformer_param_spec(n) for n in params},
                  P(None, "data"), P(None, "data")),
        out_specs=P())
    return f, params


@pytest.mark.parametrize("tied", [False, True], ids=["own_head", "tied"])
@pytest.mark.parametrize("loss_block", [None, 8], ids=["whole", "chunks"])
@pytest.mark.parametrize("n_model, n_data", [(1, 1), (2, 1), (2, 2)])
def test_loss_and_gradients_match_the_plain_formula(n_model, n_data,
                                                    loss_block, tied):
    f, params = sharded(head_only(tied, loss_block), n_model, n_data)
    x, y = TOKENS[:, :-1], TOKENS[:, 1:]
    got, got_g = jax.jit(jax.value_and_grad(f))(params, x, y)
    want, want_g = jax.value_and_grad(plain_loss)(params, x, y)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert set(got_g) == set(want_g) == (
        {"embed"} if tied else {"embed", "unembed"})
    for n in want_g:
        close(got_g[n], want_g[n])


# -- what the loss keeps between the passes ----------------------------------


def kept_by(cfg):
    """``[(shape, dtype)]`` of what the forward pass of ``loss_local``
    keeps for the backward pass, the arguments aside."""
    from jax._src.ad_checkpoint import saved_residuals

    f, params = sharded(cfg, 1, 1)
    return [(aval.shape, aval.dtype) for aval, why in saved_residuals(
        f, params, TOKENS[:, :-1], TOKENS[:, 1:])
        if "from the argument" not in why]


def test_the_dense_scan_keeps_row_statistics_and_no_logits():
    Tc = 8
    kept = kept_by(head_only(tied=False, loss_block=Tc))
    assert ((T // Tc, B, Tc), jnp.float32) in kept       # lse
    assert not any(shape[-1] == V and len(shape) >= 3 for shape, _ in kept)


LOOPED = dict(vocab=V, embed=E, n_layers=1, n_heads=2, head_dim=8, ffn=32,
              loss_block=8, loop_steps=3, rope_theta=1e4, ffn_gated=True,
              sandwich_norm=True, final_norm=True, exit_entropy_weight=0.1)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_looped_scan_keeps_no_logits_and_no_float32_copy_of_its_rows(
        dtype):
    """A trip keeps its rows as the passes left them (``cfg.dtype``) for
    the loss's rule and for the exit gate's checkpoint, and its row
    statistics; a float32 stack of the rows beside that would be the
    gate's operand saved (268 MB in ``train-ouro-4k``)."""
    cfg = TransformerConfig(dtype=dtype, **LOOPED)
    R, Tc = cfg.loop_steps, cfg.loss_block
    trips = R * (T // Tc)
    kept = kept_by(cfg)
    assert ((trips, B, Tc), jnp.float32) in kept          # lse
    assert not any(shape[-1] == V and len(shape) >= 3 for shape, _ in kept)
    stacks = [d for shape, d in kept if shape == (trips, B, Tc, E)]
    assert stacks and all(d == dtype for d in stacks)
    if dtype == jnp.bfloat16:
        assert not any(d == jnp.float32 and len(shape) == 4
                       and shape[0] == trips for shape, d in kept)


# -- the gauge ---------------------------------------------------------------


@pytest.mark.parametrize("config", ["dense-168m-32k", "ouro-2.6b-l8",
                                    "lfm2-24b-a2b-l5-e8"])
def test_loss_kept_bytes_at_the_cells_sizes(config):
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        config = json.load(f)
    cfg = TransformerConfig(**config["model"])
    # passes x sequences x positions x 4 bytes, the same in all three
    assert loss_kept_bytes(cfg, config["train"]["batch"],
                           config["train"]["seq_len"]) == 131_072


@pytest.mark.parametrize("program, loop_steps, n_data",
                         [("tf_step", 1, 1), ("tf_step", 1, 2),
                          ("tf_step_opt", 1, 1), ("tf_step_opt", 3, 1)])
def test_loss_kept_bytes_gauge(program, loop_steps, n_data):
    """``mrtpu_train_loss_kept_bytes{program}`` is set when a step is
    dispatched, to the bytes of the rows' statistics at the batch's
    shape on one device; and that is what jax says the loss adds to the
    saved set."""
    import optax

    cfg = TransformerConfig(dtype=jnp.float32, **dict(
        LOOPED, loop_steps=loop_steps))
    tr = TransformerTrainer(
        make_mesh(devices=jax.devices()[:n_data]), cfg,
        optimizer=optax.adamw(1e-3) if program == "tf_step_opt" else None)
    want = loop_steps * B * (T // n_data) * 4
    REGISTRY.gauge("mrtpu_train_loss_kept_bytes").set(-1, program=program)
    if program == "tf_step_opt":
        tr.step_opt(*tr.init_state(), TOKENS)
    else:
        tr.step(tr.init_params(), TOKENS)
    assert REGISTRY.value("mrtpu_train_loss_kept_bytes",
                          program=program) == want
    assert loss_kept_bytes(cfg, B, T // n_data) == want
    if n_data == 1:
        trips = loop_steps * (T // cfg.loss_block)
        rows = [(shape, d) for shape, d in kept_by(cfg)
                if shape == (trips, B, cfg.loss_block) and d == jnp.float32]
        # the looped scan's other [trips, B, Tc] float32 output kept is
        # none: the gate's logit is made again under its checkpoint
        assert sum(int(np.prod(s)) * 4 for s, _ in rows) == want
