"""Sliding-window attention layers beside full ones, YaRN rotary on the
full ones only, a softmax router (PR 35: Mellum2-12B-A2.5B) — through
``loss_local`` and ``TransformerTrainer.step_opt`` against the plain
float32 reference ``benchmark/reference_mellum2.py`` on seeded weights.

CPU, toy size (E 48, two periods of ``window, window, window, attn``
with a window of 24 positions under T 64, 4 heads over 2 key/value heads
of 16, YaRN over 32 original positions, 8 experts top-2 of width 32 of
which experts 2 to 5 are held, vocabulary 128, an untied head): the
loss, the routing, the gradient of every parameter and AdamW's first
update, the flash kernels interpreted and the jnp path, the sequence
whole and on two ``data`` shards; the rotary tables and the router
against their formulas written out here with the configuration's own
numbers; the eight shares of a layer against the uncut reference.  No
time here is a device number.
"""

import functools
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mapreduce_tpu.models import moe
from mapreduce_tpu.models.looplm import rope_tables, yarn_ramp
from mapreduce_tpu.models.transformer import (TransformerConfig,
                                              TransformerTrainer,
                                              init_transformer, loss_local,
                                              remat_kept_bytes,
                                              transformer_param_spec)
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.ops.pallas_compat import pick_block, pick_lane_block
from mapreduce_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG = os.path.join(BENCH, "configs", "mellum2-12b-a2.5b-l4-e8.json")

ATTENTION_FACTOR = 1.2772588722239782
MODEL = dict(vocab=128, embed=48, n_layers=8, n_heads=4, head_dim=16,
             ffn=64, loss_block=32, attn_block=16, rope_theta=5e5,
             yarn_factor=16.0, yarn_original_positions=32,
             yarn_attention_factor=ATTENTION_FACTOR, final_norm=True,
             layer_ops=("window", "window", "window", "attn") * 2,
             layer_ffns=("moe",) * 8, attn_window=24, n_kv_heads=2,
             qk_norm=True, moe_experts=8, moe_top_k=2, moe_ffn=32,
             moe_held=4, moe_held_offset=2, moe_router_score="softmax")
YARN = dict(factor=16.0, original_max_position_embeddings=32,
            beta_fast=32.0, beta_slow=1.0, attention_factor=ATTENTION_FACTOR)
REFERENCE = dict(
    layer_types=("sliding_attention",) * 3 + ("full_attention",)
    + ("sliding_attention",) * 3 + ("full_attention",),
    n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=5e5, yarn=YARN,
    window=24, eps=1e-6, top_k=2, held=(2, 4), block=16)
TOKENS = np.random.default_rng(0).integers(0, MODEL["vocab"], size=(2, 65),
                                           dtype=np.int32)

#: (dtype, remat, flash, devices on the data axis)
CASES = [("float32", False, False, 1), ("float32", True, True, 1),
         ("float32", True, False, 2), ("bfloat16", True, True, 1)]
TOL = {"float32": dict(loss=1e-5, grad=2e-4, routing=1e-3),
       "bfloat16": dict(loss=5e-3, grad=0.5, routing=0.1)}


def case_id(case):
    dtype, remat, flash, n_data = case
    return (f"{dtype}-{'remat' if remat else 'saved'}-"
            f"{'flash' if flash else 'jnp'}-data{n_data}")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def seeded_params(cfg=None):
    """The program's init with every vector moved off its start, so that
    each one's gradient and its place in the mathematics are tested."""
    params = init_transformer(jax.random.key(3),
                              cfg or TransformerConfig(**MODEL))
    key = jax.random.key(7)
    return {n: (a + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                            a.shape) if a.ndim == 1 else a)
            for i, (n, a) in enumerate(sorted(params.items()))}


@functools.lru_cache(maxsize=None)
def reference(**wrong):
    from benchmark import reference_mellum2

    kw = dict(REFERENCE, **wrong)
    (loss, chosen, weights, loads), grads = jax.jit(
        lambda p: reference_mellum2.reference_gradients(
            p, TOKENS[:, :-1], TOKENS[:, 1:], **kw))(seeded_params())
    return (float(loss), np.asarray(chosen), np.asarray(loads),
            {n: np.asarray(g) for n, g in grads.items()},
            np.asarray(weights))


@functools.lru_cache(maxsize=None)
def system(case, **model):
    """``loss_local`` under ``shard_map`` exactly as the trainer wraps
    it, differentiated: (loss, chosen, stats rows, gradients, weights)."""
    dtype, remat, flash, n_data = case
    cfg = TransformerConfig(dtype=jnp.dtype(dtype), remat=remat, flash=flash,
                            **dict(MODEL, **model))
    mesh = make_mesh(devices=jax.devices()[:n_data], n_model=1)
    params = seeded_params(cfg)
    f = jax.shard_map(
        lambda p, x, y: loss_local(p, x, y, cfg, 1), mesh=mesh,
        in_specs=({n: transformer_param_spec(n) for n in params},
                  P(None, "data"), P(None, "data")),
        out_specs=(P(), {"loads": P(),
                         "chosen": P(None, None, "data", None),
                         "weights": P(None, None, "data", None)}))
    (loss, stats), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(
        params, TOKENS[:, :-1], TOKENS[:, 1:])
    return (float(loss), np.asarray(stats["chosen"]),
            np.asarray(stats["loads"]),
            {n: np.asarray(g) for n, g in grads.items()},
            np.asarray(stats["weights"]))


# -- the system against the reference ----------------------------------------


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_loss_and_routing_match_the_reference(case):
    loss, chosen, stats, _, weights = system(case)
    want, want_chosen, want_loads, _, want_weights = reference()
    tol = TOL[case[0]]
    assert abs(loss - want) / want < tol["loss"]
    assert (chosen != want_chosen).mean() <= tol["routing"]
    assert stats[:, moe.STAT_DROPPED].sum() == 0
    assert (stats[:, moe.STAT_ROUTED] == 2 * 64 * 2).all()
    if case[0] == "float32":
        assert stats[:, :moe.STAT_DROPPED].tolist() == want_loads.tolist()
        np.testing.assert_allclose(weights, want_weights, atol=1e-5)


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_every_gradient_matches_the_reference(case):
    grads, want = system(case)[3], reference()[3]
    assert set(grads) == set(want)
    tol = TOL[case[0]]["grad"]
    for n in sorted(want):
        gap = np.linalg.norm(grads[n] - want[n]) / np.linalg.norm(want[n])
        assert gap < tol, (n, gap)


@pytest.mark.parametrize("given", ["own", "given"])
def test_reference_gradient_a_layer_at_a_time_is_the_whole_models(given):
    """``gradient_programs`` keeps every layer's input and takes each
    layer's backward pass by itself; autodiff through the whole forward
    pass, written here from the same pieces, gives the same loss,
    routing and gradients, with the layers' own choices and with
    another's (every second choice moved to the next expert)."""
    from benchmark import reference_mellum2 as ref

    params = seeded_params()
    tokens, targets = TOKENS[:, :-1], TOKENS[:, 1:]
    m = ref.Model(**REFERENCE)
    chose = None
    if given == "given":
        chose = (reference()[1] + np.arange(2)) % MODEL["moe_experts"]

    def whole(p):
        with jax.default_matmul_precision("highest"):
            ce = []
            for b in range(tokens.shape[0]):
                x = p["embed"][tokens[b]]
                for i, kind in enumerate(m.layer_types):
                    x, _ = m.layer(ref.layer_params(p, i), x,
                                   m.kind(kind, x.shape[0]),
                                   None if chose is None else chose[i, b])
                ce.append(m.losses(p, x, targets[b]))
            return jnp.stack(ce).mean()

    want_loss, want = jax.jit(jax.value_and_grad(whole))(params)
    (loss, chosen, _, loads), grads = ref.reference_gradients(
        params, tokens, targets, given=chose, **REFERENCE)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    # the choices and loads returned stay the layer's own either way
    # (the first layer's: a later one's input has passed given experts)
    assert (np.asarray(chosen)[0] == reference()[1][0]).all()
    assert np.asarray(loads)[0].tolist() == reference()[2][0].tolist()
    for n in want:
        np.testing.assert_allclose(grads[n], want[n], rtol=1e-4, atol=1e-7,
                                   err_msg=n)


WRONG = {"no_window": dict(window=None), "plain_rope": dict(yarn=None),
         "sigmoid": dict(score="sigmoid"),
         "another_window": dict(window=23)}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_a_wrong_model_fails_the_comparison(wrong):
    """The window left out (or one position off), plain rotary on the
    full layers, a sigmoid for the softmax: each is told from the
    configuration's model by the loss and by the gradients."""
    loss, _, _, grads, _ = system(CASES[1])
    want, _, _, want_grads, _ = reference(**WRONG[wrong])
    assert abs(loss - want) / want > 1e-4
    worst = max(np.linalg.norm(grads[n] - want_grads[n])
                / np.linalg.norm(want_grads[n]) for n in want_grads)
    assert worst > 0.01


def test_a_window_as_wide_as_the_sequence_is_full_attention():
    """Window layers with ``attn_window`` >= T compute what "attn"
    layers with the same (plain) rotary tables do."""
    wide = system(CASES[1], attn_window=64, yarn_factor=0.0)
    full = system(CASES[1], layer_ops=("attn",) * 8, yarn_factor=0.0)
    assert abs(wide[0] - full[0]) < 1e-6
    for n in wide[3]:
        np.testing.assert_allclose(wide[3][n], full[3][n], atol=1e-6,
                                   rtol=1e-4)


# -- one AdamW step through the trainer ---------------------------------------


@pytest.fixture(scope="module")
def trainer():
    import optax

    return TransformerTrainer(
        make_mesh(devices=jax.devices()[:1]),
        TransformerConfig(dtype=jnp.float32, remat=True, flash=True,
                          **MODEL),
        optimizer=optax.adamw(3e-4, b1=0.9, b2=0.95, eps=1e-8,
                              weight_decay=0.1))


def test_one_adamw_step_against_the_reference(trainer):
    """``step_opt`` from the seeded weights: the loss, AdamW's first
    moment as the gradient, and what the step added to every tensor
    against AdamW's first step, written out, of the reference's
    gradient.  The first step is a sign, so an element whose gradient
    lies under the rounding may flip: the bound is on the 2-norm."""
    from benchmark.reference_looplm import adamw_first_step

    old = jax.device_get(seeded_params())     # the step donates its own
    params = {n: jax.device_put(a, trainer.init_params()[n].sharding)
              for n, a in old.items()}
    opt_state = trainer.init_opt_state(params)
    read = lambda op: REGISTRY.sum(
        "mrtpu_train_operator_applications_total", operator=op)
    before = REGISTRY.sum("mrtpu_train_operator_applications_total")
    by_op = {op: read(op) for op in ("window", "attn")}
    new, opt_state, loss, stats = trainer.step_opt(params, opt_state, TOKENS)
    want, _, _, want_grads, _ = reference()
    assert abs(float(loss) - want) / want < 1e-5
    mu = opt_state[0].mu
    for n, g in want_grads.items():
        assert np.linalg.norm(np.asarray(mu[n]) / 0.1 - g) \
            / np.linalg.norm(g) < 2e-4, n
        step = np.asarray(adamw_first_step(
            old[n], g, learning_rate=3e-4, b1=0.9, b2=0.95, eps=1e-8,
            weight_decay=0.1))
        moved = np.asarray(new[n]) - np.asarray(old[n])
        assert np.linalg.norm(moved - step) / np.linalg.norm(step) < 0.2, n
    # the step counted its layers by operator (the registry is the
    # process's: another file's steps may have counted before this one)
    assert REGISTRY.sum("mrtpu_train_operator_applications_total") \
        == before + 8
    assert read("window") - by_op["window"] == 6
    assert read("attn") - by_op["attn"] == 2
    trainer.observe_experts(stats)


def test_remat_keeps_the_kernel_results_of_both_attention_kinds(trainer):
    """A windowed layer's kernel output and row statistics are kept as a
    full layer's are: 8 attention layers of [B, H, T, D] float32 and
    [B, H, T]."""
    assert remat_kept_bytes(trainer.cfg, 1, 2, 64) \
        == 8 * 2 * 4 * 64 * (16 * 4 + 4)
    assert REGISTRY.value("mrtpu_train_remat_kept_bytes",
                          program="tf_step_opt") in (
        0.0, 8 * 2 * 4 * 64 * (16 * 4 + 4))


def test_windowed_layers_run_the_windowed_programs(trainer):
    """Six ``flash_fwd_win`` and two ``flash_fwd`` calls in the traced
    forward pass of two periods, each under ``tf.flash``."""
    from tests.kernel_calls import eqns

    x, y = trainer.place_batch(TOKENS)
    jaxpr = jax.make_jaxpr(trainer._loss._jit)(seeded_params(), x, y)
    names = [e.params["name"] for e, _ in eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert names.count("flash_fwd_win") == 6
    assert names.count("flash_fwd") == 2
    assert REGISTRY.value("mrtpu_flash_grid_steps", kernel="flash_fwd_win",
                          kind="needed") == 9     # 4 tiles of 16, window 24
    assert REGISTRY.value("mrtpu_flash_grid_steps", kernel="flash_fwd",
                          kind="needed") == 10


@pytest.mark.parametrize("other", [
    dict(attn_window=25), dict(moe_router_score="sigmoid"),
    dict(yarn_factor=8.0), dict(yarn_original_positions=64),
    dict(layer_ops=("window", "window", "attn", "window") * 2)],
    ids=lambda o: "-".join(o))
def test_arch_tag_tells_the_new_fields_apart(trainer, other):
    import optax

    theirs = TransformerTrainer(
        make_mesh(devices=jax.devices()[:1]),
        TransformerConfig(dtype=jnp.float32, remat=True, flash=True,
                          **dict(MODEL, **other)),
        optimizer=optax.adamw(3e-4))
    assert theirs._arch_tag() != trainer._arch_tag()


def test_a_model_without_the_new_fields_keeps_its_arch_tag():
    """The tags PR 33's checkpoints were written under: no ``.win``."""
    from tests.test_lfm2moe import MODEL as LFM2

    tag = TransformerTrainer(make_mesh(devices=jax.devices()[:1]),
                             TransformerConfig(**LFM2))._arch_tag()
    assert tag.endswith(".held4at4.bias1") and ".win" not in tag


@pytest.mark.parametrize("bad,message", [
    (dict(attn_window=0), "attn_window"),
    (dict(yarn_original_positions=0), "YaRN"),
    (dict(rope_theta=None), "YaRN"),
    (dict(moe_router_score="tanh"), None),
    (dict(layer_ops=("window", "local") * 4), "one of")])
def test_validate_refuses(bad, message):
    with pytest.raises(AssertionError, match=message):
        TransformerConfig(**dict(MODEL, **bad)).validate(1)


def test_the_kernel_ring_refuses_a_window():
    """Over a sharded sequence the jnp ring masks the window by global
    positions (CASES' data2); the kernel ring has no windowed path and
    says so."""
    from mapreduce_tpu.parallel.ring import ring_attention

    q = jnp.zeros((1, 32, 2, 16))
    mesh = make_mesh(devices=jax.devices()[:2], n_model=1)
    f = jax.shard_map(
        lambda q: ring_attention(q, q, q, "data", window=8, use_flash=True),
        mesh=mesh, in_specs=P(None, "data"), out_specs=P(None, "data"))
    with pytest.raises(ValueError, match="no kernel path"):
        jax.jit(f)(q)


# -- the rotary tables and the router against their formulas -----------------


def _tables(cfg, T, yarn):
    mesh = make_mesh(devices=jax.devices()[:1])
    f = jax.shard_map(lambda: rope_tables(cfg, T, "data", yarn=yarn),
                      mesh=mesh, in_specs=(),
                      out_specs=(P("data"), P("data")))
    return [np.asarray(t, np.float64) for t in jax.jit(f)()]


def test_yarn_tables_are_the_formula_with_the_configurations_numbers():
    """The configuration's own rotary keys, written out: pairs 0 to 18
    keep the plain frequency, pairs 35 to 63 a sixteenth of it, a linear
    blend between; cos and sin times the attention factor.  Plain tables
    (the windowed layers') are untouched by the YaRN fields."""
    rope = load(CONFIG)["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    m = load(CONFIG)["model"]
    cfg = TransformerConfig(**m)
    assert (cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_positions,
            cfg.yarn_beta_fast, cfg.yarn_beta_slow,
            cfg.yarn_attention_factor) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"])
    assert sliding == {"rope_type": "default", "rope_theta": 500000}
    D, theta, T = 128, 500000.0, 4096

    def c(r):
        return D * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(theta))

    low, high = max(math.floor(c(32)), 0), min(math.ceil(c(1)), D - 1)
    assert (low, high) == (18, 35)
    i = np.arange(64, dtype=np.float64)
    extra = theta ** (-2 * i / D)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv_freq = (extra / 16) * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(yarn_ramp(cfg), ramp, atol=1e-7)
    assert (inv_freq[:19] == extra[:19]).all()
    np.testing.assert_allclose(inv_freq[35:], extra[35:] / 16)
    assert abs(full["attention_factor"] - (0.1 * math.log(16) + 1)) < 1e-12
    # positions far apart, where float32 angles still resolve the pairs
    at = np.array([0, 1, 17, 1023, 4095])
    angle = at[:, None] * inv_freq[None, :]
    cos, sin = _tables(cfg, T, yarn=True)
    np.testing.assert_allclose(cos[at], np.cos(angle) * ATTENTION_FACTOR,
                               atol=2e-3)
    np.testing.assert_allclose(sin[at], np.sin(angle) * ATTENTION_FACTOR,
                               atol=2e-3)
    # the slow pairs, whose angles stay small: tight
    np.testing.assert_allclose(cos[at][:, 30:], (np.cos(angle)
                               * ATTENTION_FACTOR)[:, 30:], rtol=1e-5)
    plain = _tables(cfg, T, yarn=False)
    np.testing.assert_allclose(plain[1][at][:, 30:],
                               np.sin(at[:, None] * extra[None, 30:]),
                               rtol=1e-4, atol=1e-7)
    # past the ramp's start the two kinds of layer turn differently
    assert np.abs(cos[4095, 40] / ATTENTION_FACTOR - plain[0][4095, 40]) \
        > 1e-3
    # None for the factor is the formula's default
    default = TransformerConfig(**dict(m, yarn_attention_factor=None))
    np.testing.assert_allclose(_tables(default, 64, True)[0],
                               cos[:64], rtol=1e-6)


def test_softmax_router_is_the_written_out_top_8():
    """``p = softmax(h W)`` in float32 over 64 experts, the 8 largest,
    weights ``p_e / sum`` over the chosen with no epsilon; the sigmoid
    router is what it was."""
    rng = np.random.default_rng(5)
    h = rng.normal(size=(40, 32)).astype(np.float32)
    w = rng.normal(size=(32, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        chosen, weights = moe.route(jnp.asarray(h), jnp.asarray(w), None, 8,
                                    "softmax")
        chosen_s, weights_s = moe.route(jnp.asarray(h), jnp.asarray(w),
                                        None, 8)
    logits = h.astype(np.float64) @ w.astype(np.float64)
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    for n in range(40):
        order = np.argsort(-p[n])[:8]
        assert np.asarray(chosen)[n].tolist() == order.tolist()
        np.testing.assert_allclose(np.asarray(weights)[n],
                                   p[n, order] / p[n, order].sum(),
                                   rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 1.0,
                               rtol=1e-6)
    s = 1 / (1 + np.exp(-logits))
    assert (np.sort(np.asarray(chosen_s)) == np.sort(np.asarray(chosen))
            ).all()            # both are monotone in the logit
    picked = np.take_along_axis(s, np.asarray(chosen_s), axis=1)
    np.testing.assert_allclose(
        np.asarray(weights_s),
        picked / (picked.sum(axis=1, keepdims=True) + 1e-6), rtol=1e-5)


def test_the_softmax_weights_carry_the_routers_gradient():
    h = jnp.asarray(np.random.default_rng(1).normal(size=(6, 8)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(2).normal(size=(8, 16)),
                    jnp.float32)
    g = jax.grad(lambda w: moe.route(h, w, None, 4, "softmax")[1][:, 0].sum()
                 )(w)
    assert float(jnp.abs(g).max()) > 1e-3


# -- the shares add up -------------------------------------------------------


SHARE = dict(vocab=64, embed=32, n_layers=1, n_heads=2, head_dim=16, ffn=64,
             dtype=jnp.float32, layer_ffns=("moe",), moe_experts=64,
             moe_top_k=8, moe_ffn=16, moe_held=8, moe_router_score="softmax")


def test_the_eight_shares_of_a_layer_sum_to_the_uncut_reference():
    """8 of 64 softmax-routed experts a share, 8 a token: nothing is
    computed alike on every chip but the router, so the eight partial
    outputs add up to the uncut reference's whole layer, and the loads
    to every pair routed."""
    from benchmark import reference_mellum2

    rng = np.random.default_rng(11)
    E, X, Fe = SHARE["embed"], SHARE["moe_experts"], SHARE["moe_ffn"]
    h = jnp.asarray(rng.normal(size=(2, 24, E)), jnp.float32)
    full = {"w_router": jnp.asarray(rng.normal(size=(E, X)), jnp.float32),
            "moe_w_gate": jnp.asarray(rng.normal(size=(X, E, Fe)) / 6,
                                      jnp.float32),
            "moe_w_in": jnp.asarray(rng.normal(size=(X, E, Fe)) / 6,
                                    jnp.float32),
            "moe_w_out": jnp.asarray(rng.normal(size=(X, Fe, E)) / 4,
                                     jnp.float32)}
    with jax.default_matmul_precision("highest"):
        want, (want_chosen, _, want_loads) = reference_mellum2.routed_layer(
            h.reshape(-1, E), full["w_router"], full["moe_w_gate"],
            full["moe_w_in"], full["moe_w_out"], top_k=8)
    mesh = make_mesh(devices=jax.devices()[:1])
    total, loads = 0.0, []
    for share in range(8):
        cfg = TransformerConfig(moe_held_offset=8 * share, **SHARE)
        lp = dict(full, **{n: full[n][8 * share:8 * share + 8]
                           for n in ("moe_w_gate", "moe_w_in", "moe_w_out")})
        out, stats, chosen, _ = jax.jit(jax.shard_map(
            lambda h, lp, cfg=cfg: moe.routed_experts(h, lp, cfg, 1, "data",
                                                      "model"),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P(), P(), P())))(
            h, lp)
        total = total + np.asarray(out)
        loads += np.asarray(stats)[:moe.STAT_DROPPED].tolist()
        assert np.asarray(stats)[moe.STAT_DROPPED] == 0
        assert np.asarray(stats)[moe.STAT_ROUTED] == 2 * 24 * 8
        assert (np.sort(np.asarray(chosen).reshape(-1, 8))
                == np.sort(np.asarray(want_chosen))).all()
    np.testing.assert_allclose(total.reshape(-1, E), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert loads == np.asarray(want_loads).tolist()
    assert sum(loads) == 2 * 24 * 8


# -- the grouped products' blocks at the new widths --------------------------


def test_lane_blocks_at_the_cells_widths():
    """K = 2304, N = 896: ``pick_block`` would halve 896 to 448, which
    no lane dimension takes; the nearest lane block is the whole 896.
    The widths the grouped kernels have run at keep their blocks."""
    assert pick_block(896, 512) == 448
    assert pick_lane_block(896, 512) == 896
    assert pick_lane_block(2304, 512) == 384
    assert pick_lane_block(2304, 1024) == 1152
    for t, want in ((1536, 512), (2048, 512), (2048, 1024), (1536, 1024),
                    (32, 512), (11776, 512)):
        assert pick_lane_block(t, want) == pick_block(t, want)
    for t in (896, 2304, 1536, 2048, 640, 1000):
        for want in (512, 1024):
            b = pick_lane_block(t, want)
            assert t % b == 0 and (b % 128 == 0 or b == t)


# -- the benchmark's files ---------------------------------------------------


def test_reference_is_independent_and_sets_highest_precision():
    with open(os.path.join(BENCH, "reference_mellum2.py")) as f:
        code = f.read().split('"""', 2)[2]
    assert "mapreduce_tpu" not in code and "import jax" in code
    assert 'default_matmul_precision("highest")' in code
    assert "pallas" not in code and "shard_map" not in code
    assert "ragged" not in code and "reference_lfm2moe" not in code


def test_configuration_file_is_the_catalogs_row_cut_as_it_says():
    config = load(CONFIG)
    published = {"attention_bias": False, "head_dim": 128,
                 "hidden_size": 2304, "intermediate_size": 7168,
                 "max_position_embeddings": 131072, "max_window_layers": 0,
                 "moe_intermediate_size": 896, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_experts": 64,
                 "num_experts_per_tok": 8, "num_hidden_layers": 28,
                 "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
                 "sliding_window": 1024, "tie_word_embeddings": False,
                 "vocab_size": 98304, "use_sliding_window": True}
    cut = {"num_hidden_layers": 4, "num_experts": 8, "vocab_size": 12288}
    assert sorted(config["reduced"]) == sorted(cut)
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
        if key in cut:
            assert config["published"][key] == value
    assert config["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 7
    assert config["mlp_layer_types"] == ["sparse"] * 28
    assert config["model_type"] == "mellum" and config["hidden_act"] == "silu"
    m = config["model"]
    kinds = {"sliding_attention": "window", "full_attention": "attn"}
    assert m["layer_ops"] == [kinds[t] for t in config["layer_types"][:4]]
    assert m["layer_ffns"] == ["moe"] * 4
    assert (m["embed"], m["moe_ffn"], m["n_heads"], m["n_kv_heads"],
            m["head_dim"], m["moe_top_k"], m["moe_experts"], m["moe_held"],
            m["vocab"], m["norm_eps"], m["attn_window"],
            m["moe_router_score"], m["tied_embeddings"], m["qk_norm"]) == (
        2304, 896, 32, 4, 128, 8, 64, 8, 12288, 1e-06, 1024, "softmax",
        False, True)
    assert set(config["program"]["kernels"]) == {
        "flash_fwd", "flash_dq", "flash_dkv", "flash_fwd_win",
        "flash_dq_win", "flash_dkv_win", "moe_gmm", "moe_tgmm"}
    taken = config["memory"]["taken"]
    assert taken["seq_len"] == config["train"]["seq_len"] == 24576
    assert taken["total_gb"] <= 13.5 < config["memory"]["refused"]["total_gb"]


def test_configuration_files_parameter_count_is_the_initialisers():
    config = load(CONFIG)
    shapes = jax.eval_shape(lambda: init_transformer(
        jax.random.key(0), TransformerConfig(**config["model"])))
    count = lambda names: sum(int(np.prod(shapes[n].shape)) for n in names)
    par = config["parameters"]
    assert count(shapes) == par["trained_total"] == 340350208
    for i in range(4):
        names = [n for n in shapes if n.startswith(f"L{i}.")]
        assert count(names) == par["layer"]["total"] == 70931200
    assert count(["embed"]) == count(["unembed"]) == par["embedding"] \
        == par["head"] == 12288 * 2304
    assert par["layer"]["attention"] == 2304 * 4096 * 2 + 2304 * 1024 + 256
    assert par["layer"]["experts_8"] == 8 * 3 * 2304 * 896
    assert par["layer"]["router"] == 2304 * 64


def test_required_operations_against_a_count_by_hand():
    from benchmark import flops_mellum2

    m = load(CONFIG)["model"]
    E, T, W = 2304, 24576, 1024
    assert flops_mellum2.attended_pairs(T, W) == W * T - W * (W - 1) / 2
    assert flops_mellum2.attended_pairs(T) == T * T / 2
    assert flops_mellum2.attended_pairs(512, W) == 512 * 512 / 2
    layer = E * 4096 + E * 2 * 512 + 4096 * E + E * 64
    assert flops_mellum2.dense_macs_per_token(m) == 4 * layer + E * 12288
    assert flops_mellum2.expert_macs_per_pair(m) == 3 * E * 896
    pairs = flops_mellum2.expected_pairs_held(m, 1, T)
    assert pairs == 4 * T * 8 * 8 / 64
    product = lambda p: 2 * 32 * 128 * p
    by_hand = (6 * (product(T * T / 2)
                    + 3 * product(W * T - W * (W - 1) / 2))
               + 6 * ((4 * layer + E * 12288) * T + 3 * E * 896 * pairs))
    assert flops_mellum2.train_step_flops(m, 1, T, pairs) == by_hand
    assert 3.8e13 < by_hand < 4.0e13
    fwd = flops_mellum2.window_call_work("flash_fwd_win", m, 1, T)
    dkv = flops_mellum2.window_call_work("flash_dkv_win", m, 1, T)
    dq = flops_mellum2.window_call_work("flash_dq_win", m, 1, T)
    assert fwd["flops"] == 2 * product(W * T - W * (W - 1) / 2)
    assert dkv["flops"] == 2 * fwd["flops"] and dq["flops"] == 0
    rows = 32 * T
    assert fwd["bytes"] == rows * 128 * 2 * 4 + rows * 4
    assert dkv["bytes"] == rows * 128 * (2 * 6 + 4) + rows * 4 * 2
    assert dq["bytes"] == rows * 128 * (2 + 4)
    assert flops_mellum2.window_call_work("flash_fwd", m, 1, T) is None


def _tiny_cell():
    from benchmark.tests.test_mellum_trainer import tiny

    return tiny()


def test_kind_holds_step_0_to_the_reference_in_float32():
    """The benchmark's own comparison at toy size: in float32 every gap
    is rounding, nothing is dropped."""
    from benchmark.kinds import mellum_trainer

    _, cell, config = _tiny_cell()
    c = mellum_trainer.Cell(config, cell, 2**31 + 5, jax.devices()[:1])
    c.warm(2)
    assert c.gaps["loss"] < 1e-5 and c.gaps["gradient"] < 1e-3
    assert c.gaps["update_rule"] < 1e-3 and c.gaps["pairs_held"] == 0
    assert c.gaps["routing"] < 1e-3 and c.gaps["update"] < 0.2
    assert c.gaps["weights"] < 1e-5
    assert list(c.faults()) == []
    r = c.unit()
    assert r["ok"] and r["pairs_held"] == sum(map(sum, r["loads"]))
    derived = c.derived({"train_tok_rate": 1.0}, 1, "cpu")
    assert "mfu" not in derived                      # no peak for a CPU
    assert 1.0 <= derived["load_max_over_mean"] < 2.0


def test_controls_come_out_not_correct():
    """``mellum_controls.py``: 8-bit operands, an unchanged state and
    each wrong model fault, the trainer does not (float32, toy size: the
    limits are the cell's, so only the direction is held here)."""
    from benchmark import mellum_controls
    from benchmark.kinds import mellum_trainer

    _, cell, config = _tiny_cell()
    c = mellum_trainer.Cell(config, cell, 11, jax.devices()[:1])
    out = mellum_controls.controls(c, 11)
    assert set(out) == {"trainer", "unchanged", "half_sequence",
                        "float8_e4m3fn", "no_window", "plain_rope", "sigmoid"}
    assert out["trainer"]["faults"] == []
    assert set(mellum_controls.controls(c, 12, full=False)) == {"trainer"}
    assert out["unchanged"]["gaps"]["gradient"] == pytest.approx(1.0)
    assert len(out["unchanged"]["faults"]) >= 3
    for control in ("half_sequence", "float8_e4m3fn", "no_window",
                    "plain_rope", "sigmoid"):
        assert out[control]["faults"], control
        assert out[control]["gaps"]["gradient"] \
            > 100 * out["trainer"]["gaps"]["gradient"], control
    assert out["sigmoid"]["gaps"]["weights"] \
        > 100 * out["trainer"]["gaps"]["weights"] + 0.01
