"""Latent attention (MLA), a shared expert beside the routed ones, a
selection bias that follows the loads and a multi-token-prediction block
(PR 39: GLM-4.7-Flash) — through ``loss_local`` and
``TransformerTrainer.step_opt`` against the plain float32 reference
``benchmark/reference_glm47.py`` on seeded weights.

CPU, toy widths that keep the ratios (E 32; 4 heads of 12 + 4 for q and k
and 16 for v over latents of 12 and 8 + 4; a dense layer, two expert
layers and a prediction block; 16 sigmoid-routed experts top-4 of width
24 of which experts 4 to 7 are held, a shared expert as wide; routed
scale 1.8; vocabulary 128, an untied head): both losses, the routing, the
gradient of every parameter, AdamW's first update and the bias's move,
the flash kernels interpreted and the jnp path, one and two ``model``
ranks, the sequence whole and on two ``data`` shards (the block's target
crosses the shards' edge); MLA's projections, the partial rotary embedding and the shared key
against their formulas written out here; the eight shares of a layer
against the uncut reference.  No time here is a device number.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mapreduce_tpu.models import moe
from mapreduce_tpu.models.looplm import rope_tables
from mapreduce_tpu.models.operators import latent_qkv
from mapreduce_tpu.models.transformer import (TransformerConfig,
                                              TransformerTrainer,
                                              init_transformer, loss_local,
                                              remat_kept_bytes,
                                              transformer_param_spec)
from mapreduce_tpu.obs.compile import LEDGER
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG = os.path.join(BENCH, "configs", "glm-4.7-flash-l5-e8.json")

MODEL = dict(vocab=128, embed=32, n_layers=3, n_heads=4, head_dim=16,
             ffn=80, loss_block=32, attn_block=16, rope_theta=1e6,
             final_norm=True, ffn_gated=True,
             layer_ffns=("dense", "moe", "moe"), norm_eps=1e-5,
             q_lora_rank=12, kv_lora_rank=8, qk_nope_dim=12, qk_rope_dim=4,
             v_head_dim=16, moe_experts=16, moe_top_k=4, moe_ffn=24,
             moe_held=4, moe_held_offset=4, moe_router_bias=True,
             shared_ffn=24, moe_routed_scale=1.8, moe_bias_rate=0.001,
             mtp_blocks=1, mtp_weight=0.3)
REFERENCE = dict(n_layers=3, n_heads=4, rope_dim=4, rope_theta=1e6, eps=1e-5,
                 top_k=4, held=(4, 4), routed_scale=1.8, mtp_weight=0.3,
                 bias_rate=0.001, block=16)
TOKENS = np.random.default_rng(0).integers(0, MODEL["vocab"], size=(2, 65),
                                           dtype=np.int32)

#: (dtype, remat, flash, ranks on the model axis, shards of the sequence)
CASES = [("float32", False, False, 1, 1), ("float32", True, True, 1, 1),
         ("float32", True, False, 2, 1), ("float32", True, False, 1, 2),
         ("bfloat16", True, True, 1, 1)]
TOL = {"float32": dict(loss=1e-5, grad=2e-4, routing=1e-3),
       "bfloat16": dict(loss=5e-3, grad=0.5, routing=0.1)}
#: the controls' wrong models (benchmark/glm_controls.MODELS)
WRONG = {"no_rope": {"rope": False}, "key_per_head": {"shared_key": False},
         "no_latent_norms": {"latent_norms": False},
         "no_shared": {"shared": False}, "scale_1": {"routed_scale": 1.0},
         "no_mtp": {"mtp_weight": 0.0}}


def case_id(case):
    dtype, remat, flash, n_model, n_data = case
    return (f"{dtype}-{'remat' if remat else 'saved'}-"
            f"{'flash' if flash else 'jnp'}-model{n_model}-data{n_data}")


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
    """``(main loss, chosen, loads, gradients, weights, {"mtp_loss",
    "objective", "bias"})`` of the float32 reference."""
    from benchmark import reference_glm47

    kw = dict(REFERENCE, **wrong)
    (loss, chosen, weights, loads), grads, extra = jax.jit(
        lambda p: reference_glm47.reference_gradients(
            p, TOKENS[:, :-1], TOKENS[:, 1:], **kw))(seeded_params())
    return (float(loss), np.asarray(chosen), np.asarray(loads),
            {n: np.asarray(g) for n, g in grads.items()},
            np.asarray(weights), jax.device_get(extra))


STATS_SPECS = {"loads": P(), "chosen": P(None, None, "data", None),
               "weights": P(None, None, "data", None), "losses": P()}


@functools.lru_cache(maxsize=None)
def system(case, **model):
    """``loss_local`` under ``shard_map`` exactly as the trainer wraps
    it, differentiated: (objective, chosen, stats rows, gradients,
    weights, [L_main, L_mtp])."""
    dtype, remat, flash, n_model, n_data = case
    cfg = TransformerConfig(dtype=jnp.dtype(dtype), remat=remat, flash=flash,
                            **dict(MODEL, **model))
    mesh = make_mesh(devices=jax.devices()[:n_model * n_data],
                     n_model=n_model)
    params = seeded_params(cfg)
    f = jax.shard_map(
        lambda p, x, y: loss_local(p, x, y, cfg, n_model), mesh=mesh,
        in_specs=({n: transformer_param_spec(n) for n in params},
                  P(None, "data"), P(None, "data")),
        out_specs=(P(), STATS_SPECS))
    (loss, stats), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(
        params, TOKENS[:, :-1], TOKENS[:, 1:])
    return (float(loss), np.asarray(stats["chosen"]),
            np.asarray(stats["loads"]),
            {n: np.asarray(g) for n, g in grads.items()},
            np.asarray(stats["weights"]), np.asarray(stats["losses"]))


# -- the system against the reference ----------------------------------------


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_both_losses_and_routing_match_the_reference(case):
    loss, chosen, stats, _, weights, losses = system(case)
    want, want_chosen, want_loads, _, want_weights, extra = reference()
    tol = TOL[case[0]]
    assert abs(losses[0] - want) / want < tol["loss"]
    assert abs(losses[1] - extra["mtp_loss"]) / extra["mtp_loss"] \
        < tol["loss"]
    assert abs(loss - extra["objective"]) / extra["objective"] < tol["loss"]
    assert loss == pytest.approx(losses[0] + 0.3 * losses[1], rel=1e-6)
    assert (chosen != want_chosen).mean() <= tol["routing"]
    assert chosen.shape == (3, 2, 64, 4)            # the block's is last
    assert stats[:, moe.STAT_DROPPED].sum() == 0
    assert (stats[:, moe.STAT_ROUTED] == 2 * 64 * 4).all()
    if case[0] == "float32":
        assert stats[:, :moe.STAT_DROPPED].tolist() == want_loads.tolist()
        np.testing.assert_allclose(weights, want_weights, atol=1e-5)
        # the routed weights sum to the scale, less the denominator's 1e-6
        np.testing.assert_allclose(weights.sum(-1), 1.8, rtol=1e-5)


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_every_gradient_matches_the_reference(case):
    grads, want = system(case)[3], reference()[3]
    assert set(grads) == set(want)
    tol = TOL[case[0]]["grad"]
    for n in sorted(want):
        if n.endswith(".router_bias"):      # it only selects
            assert not grads[n].any() and not want[n].any()
            continue
        gap = np.linalg.norm(grads[n] - want[n]) / np.linalg.norm(want[n])
        assert gap < tol, (n, gap)


@pytest.mark.parametrize("given", ["own", "given"])
def test_reference_gradient_a_layer_at_a_time_is_the_whole_models(given):
    """``gradient_programs`` keeps every layer's input and takes each
    layer's, the block's joining's and the two heads' backward passes by
    themselves; autodiff through ``Model.objective`` is the same
    gradient in one piece."""
    from benchmark import reference_glm47

    params = seeded_params()
    choices = None
    if given == "given":        # another's choices: experts 4..7 in turn
        choices = jnp.asarray(
            (np.arange(3 * 2 * 64 * 4).reshape(3, 2, 64, 4) % 16)
            .astype(np.int32))
    model = reference_glm47.Model(**REFERENCE)
    with jax.default_matmul_precision("highest"):
        (objective, aux), whole = jax.jit(jax.value_and_grad(
            lambda p: model.objective(p, TOKENS[:, :-1], TOKENS[:, 1:],
                                      choices), has_aux=True))(params)
    (main, chosen, g, loads), grads, extra = \
        reference_glm47.reference_gradients(
            params, TOKENS[:, :-1], TOKENS[:, 1:], given=choices,
            **REFERENCE)
    assert float(extra["objective"]) == pytest.approx(float(objective),
                                                      rel=1e-6)
    assert float(main) == pytest.approx(float(aux[0]), rel=1e-6)
    assert float(extra["mtp_loss"]) == pytest.approx(float(aux[1]), rel=1e-6)
    assert np.array_equal(np.asarray(chosen), np.asarray(aux[2]))
    np.testing.assert_allclose(np.asarray(g), np.asarray(aux[3]), atol=1e-6)
    for n in sorted(grads):
        if n.endswith(".router_bias"):
            continue
        gap = np.linalg.norm(np.asarray(grads[n]) - np.asarray(whole[n])) \
            / np.linalg.norm(np.asarray(whole[n]))
        assert gap < 1e-4, (n, gap)


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_a_wrong_model_fails_the_comparison(wrong):
    """What each control leaves out moves the objective or a gradient
    far outside the float32 tolerance the system is held to."""
    grads = system(CASES[1])[3]
    loss = system(CASES[1])[0]
    _, _, _, want, _, extra = reference(**WRONG[wrong])
    worst = max(np.linalg.norm(grads[n] - want[n])
                / max(np.linalg.norm(want[n]), 1e-30)
                for n in want if not n.endswith(".router_bias")
                and np.linalg.norm(grads[n]) > 0)
    assert worst > 100 * TOL["float32"]["grad"], worst
    assert abs(loss - extra["objective"]) / loss > 100 * TOL["float32"]["loss"]


# -- the trainer's step: AdamW and the bias's rule ---------------------------


@pytest.fixture(scope="module")
def trainer():
    import optax

    return TransformerTrainer(
        make_mesh(devices=jax.devices()[:1]),
        TransformerConfig(dtype=jnp.float32, remat=True, flash=True,
                          **MODEL),
        optimizer=optax.adamw(3e-4, b1=0.9, b2=0.95, eps=1e-8,
                              weight_decay=0.1))


def test_one_adamw_step_and_the_biass_move_against_the_reference(trainer):
    """``step_opt`` from the seeded weights: both losses, AdamW's first
    moment as the gradient, what the step added to every tensor against
    AdamW's first step, written out, of the reference's gradient, and
    every selection bias where the loads over ALL 16 experts move it (no
    gradient, no weight decay)."""
    from benchmark.reference_looplm import adamw_first_step

    old = jax.device_get(seeded_params())     # the step donates its own
    params = {n: jax.device_put(a, trainer.init_params()[n].sharding)
              for n, a in old.items()}
    opt_state = trainer.init_opt_state(params)
    before = REGISTRY.sum("mrtpu_train_layer_applications_total")
    new, opt_state, loss, stats = trainer.step_opt(params, opt_state, TOKENS)
    want, want_chosen, _, want_grads, _, extra = reference()
    assert abs(float(loss) - extra["objective"]) / extra["objective"] < 1e-5
    losses = np.asarray(stats["losses"])
    assert abs(losses[0] - want) / want < 1e-5
    assert abs(losses[1] - extra["mtp_loss"]) / extra["mtp_loss"] < 1e-5
    mu = opt_state[0].mu
    for n, g in want_grads.items():
        moved = np.asarray(new[n]) - np.asarray(old[n])
        if n.endswith(".router_bias"):
            assert not np.asarray(mu[n]).any()
            np.testing.assert_allclose(np.asarray(new[n]), extra["bias"][n],
                                       atol=1e-7)
            loads = np.bincount(want_chosen[(1, 2, 3).index(int(n[1]))]
                                .ravel(), minlength=16)
            np.testing.assert_allclose(
                moved, 0.001 * np.sign(loads.mean() - loads), atol=1e-7)
            continue
        assert np.linalg.norm(np.asarray(mu[n]) / 0.1 - g) \
            / np.linalg.norm(g) < 2e-4, n
        step = np.asarray(adamw_first_step(
            old[n], g, learning_rate=3e-4, b1=0.9, b2=0.95, eps=1e-8,
            weight_decay=0.1))
        assert np.linalg.norm(moved - step) / np.linalg.norm(step) < 0.2, n
    # the block's layer is an application; the gauges and the record
    assert REGISTRY.sum("mrtpu_train_layer_applications_total") == before + 4
    trainer.observe_experts(stats)
    assert REGISTRY.sum("mrtpu_train_mtp_loss") == pytest.approx(losses[1])
    rec = trainer.step_log()[-1]
    assert rec["mtp_loss"] == pytest.approx(losses[1])
    assert len(rec["load_max_over_mean"]) == 3
    for layer, b in zip((1, 2, 3), np.asarray(stats["bias_abs_max"])):
        assert REGISTRY.sum("mrtpu_moe_router_bias_abs_max", layer=layer) \
            == pytest.approx(np.abs(np.asarray(new[f"L{layer}.router_bias"])
                                    ).max()) == pytest.approx(float(b))


def test_a_held_bias_stays_as_it_is(trainer):
    """``moe_bias_rate`` 0, as every configuration before this one: the
    step leaves the buffer alone and returns no ``bias_abs_max``."""
    import optax

    held = TransformerTrainer(
        trainer.mesh, TransformerConfig(dtype=jnp.float32, **dict(
            MODEL, moe_bias_rate=0.0)), optimizer=optax.adamw(3e-4))
    params, opt_state = held.init_state()
    old = {n: np.asarray(a) for n, a in params.items()
           if n.endswith(".router_bias")}
    new, _, _, stats = held.step_opt(params, opt_state, TOKENS)
    assert "bias_abs_max" not in stats and len(old) == 3
    for n, a in old.items():
        assert np.array_equal(a, np.asarray(new[n])), n


@pytest.mark.parametrize("scope", [
    "tf.mla_down", "tf.mla_up", "tf.rope", "tf.flash", "tf.shared_expert",
    "tf.mtp", "tf.bias_update", "tf.moe_route", "tf.loss", "tf.update"])
def test_stage_map_books_the_new_stages(trainer, scope):
    from benchmark import stages

    trainer.step_opt(*trainer.init_state(), TOKENS)
    (paths,) = LEDGER.stage_map("tf_step_opt").values()
    chains = [stages.stage_chain(p) for p in paths.values()]
    assert any(c and c[-1] == scope for c in chains)
    if scope in ("tf.mla_down", "tf.mla_up", "tf.shared_expert", "tf.mtp"):
        assert any("transpose(" in p and scope in stages.stage_chain(p)
                   for p in paths.values())


def test_remat_keeps_the_kernel_results_of_the_blocks_layer_too(trainer):
    cfg = trainer.cfg
    assert remat_kept_bytes(cfg, 1, 2, 64) == 4 * 2 * 4 * 64 * (16 * 4 + 4)
    assert cfg.moe_layers == (1, 2, 3) and cfg.layer_kind(3) == ("attn",
                                                                  "moe")


# -- the architecture's tag, the placement, the refusals ---------------------


#: the admitted configurations' tags, as their checkpoints carry them
OLD_TAGS = {
    "dense-168m-32k": "v32768.e1024.l8.h8.d128.f4096.moe0",
    "ouro-2.6b-l8": "v49152.e2048.l8.h16.d128.f5632.moe0.loop4."
                    "rope1000000.0.gated1.sandwich1.final1",
    "lfm2-24b-a2b-l5-e8":
        "v8192.e2048.l5.h32.d64.f11776.moe64.loop1.rope1000000.0.gated1."
        "sandwich0.final1.kindscdamcmcmcm.taps3.kv8.qkn1.eps1e-05.tied1."
        "top4.xf1536.held8at0.bias1",
    "mellum2-12b-a2.5b-l4-e8":
        "v12288.e2304.l4.h32.d128.f7168.moe64.loop1.rope500000.0.gated0."
        "sandwich0.final1.kindswmwmwmam.taps3.kv4.qkn1.eps1e-06.tied0."
        "top8.xf896.held8at0.bias0.win1024.scoresoftmax."
        "yarn16.0x8192x32.0x1.0x1.2772588722239782"}


@pytest.mark.parametrize("name", sorted(OLD_TAGS))
def test_an_old_configuration_keeps_its_arch_tag(trainer, name):
    model = load(BENCH, "configs", f"{name}.json")["model"]
    old = TransformerTrainer(trainer.mesh, TransformerConfig(**model),
                             optimizer="adamw")
    assert old._arch_tag() == OLD_TAGS[name]
    assert not old.cfg.mtp_blocks and not old.cfg.kv_lora_rank


@pytest.mark.parametrize("other", [
    dict(qk_nope_dim=8, qk_rope_dim=8), dict(moe_routed_scale=1.0),
    dict(moe_bias_rate=0.01), dict(mtp_weight=0.1), dict(shared_ffn=16),
    dict(mtp_blocks=0)])
def test_arch_tag_tells_the_new_fields_apart(trainer, other):
    """None of these changes every tensor's shape, all of them the
    function the tensors are loaded into."""
    changed = TransformerTrainer(
        trainer.mesh, TransformerConfig(dtype=jnp.float32,
                                        **dict(MODEL, **other)))
    assert changed._arch_tag() != trainer._arch_tag()
    assert ".mla12x8x" in trainer._arch_tag()


def test_placement_splits_the_up_projections_by_heads():
    assert transformer_param_spec("L1.wq_b") == P(None, "model")
    assert transformer_param_spec("L1.wkv_b") == P(None, "model")
    assert transformer_param_spec("L1.wo") == P("model", None)
    for whole in ("L1.wq_a", "L1.wkv_a", "L1.q_a_norm_scale",
                  "L1.kv_a_norm_scale", "L1.shared_w_in", "L1.shared_w_gate",
                  "L1.shared_w_out", "L3.w_eh", "L3.enorm_scale",
                  "L3.hnorm_scale", "L3.final_scale", "L1.router_bias"):
        assert transformer_param_spec(whole) == P(), whole


@pytest.mark.parametrize("bad,message", [
    (dict(v_head_dim=12), "B10 \\(d\\)"),
    (dict(qk_nope_dim=8), "query/key width"),
    (dict(layer_ffns=("dense",) * 3), "a shared expert stands beside"),
    (dict(moe_router_bias=False), "moe_bias_rate moves the selection bias"),
    (dict(mtp_blocks=2), "one multi-token-prediction block"),
    (dict(q_lora_rank=0), "latent attention"),
    (dict(n_kv_heads=2), "its own projections and norms")])
def test_validate_refuses(bad, message):
    with pytest.raises(AssertionError, match=message):
        TransformerConfig(**dict(MODEL, **bad)).validate(1)


# -- MLA's projections, the partial rotary, the shared key: written out ------


def test_latent_qkv_is_the_formulas_written_out():
    """q from the query latent, k and v from the key/value latent, the
    rotary embedding on the last 4 of a head's 16 query/key dimensions
    alone, ONE rotary key for all 4 heads, in both layouts."""
    rng = np.random.default_rng(5)
    E, H, Rq, Rkv, dn, dr, dv, T = 32, 4, 12, 8, 12, 4, 16, 24
    h = rng.normal(size=(2, T, E)).astype(np.float32)
    lp = {"wq_a": rng.normal(size=(E, Rq)), "wq_b": rng.normal(
              size=(Rq, H * (dn + dr))),
          "wkv_a": rng.normal(size=(E, Rkv + dr)),
          "wkv_b": rng.normal(size=(Rkv, H * (dn + dv))),
          "q_a_norm_scale": 1 + rng.normal(size=(Rq,)) / 4,
          "kv_a_norm_scale": 1 + rng.normal(size=(Rkv,)) / 4}
    lp = {n: a.astype(np.float32) for n, a in lp.items()}

    def rms(x, scale):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * scale

    def rope(x):                         # [B, T, ..., dr], rotate-half
        angle = np.arange(T)[:, None] * 1e6 ** (-np.arange(0, dr, 2) / dr)
        shape = (1, T) + (1,) * (x.ndim - 3) + (dr // 2,)
        cos, sin = np.cos(angle).reshape(shape), np.sin(angle).reshape(shape)
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    c_q = rms(h @ lp["wq_a"], lp["q_a_norm_scale"])
    q = (c_q @ lp["wq_b"]).reshape(2, T, H, dn + dr)
    kv_a = h @ lp["wkv_a"]
    kv = (rms(kv_a[..., :Rkv], lp["kv_a_norm_scale"])
          @ lp["wkv_b"]).reshape(2, T, H, dn + dv)
    k_r = rope(kv_a[..., Rkv:])                              # [B, T, dr]
    want_q = np.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
    want_k = np.concatenate([kv[..., :dn], np.broadcast_to(
        k_r[:, :, None, :], (2, T, H, dr))], -1)
    want_v = kv[..., dn:]

    mesh = make_mesh(devices=jax.devices()[:1])
    for flash in (False, True):
        cfg = TransformerConfig(dtype=jnp.float32, flash=flash, **MODEL)

        def run(h, lp, cfg=cfg):
            tables = rope_tables(cfg, T, "data", dim=cfg.qk_rope_dim)
            return latent_qkv(h, lp, cfg, 1, tables)

        # the tables are of this shard's positions: T lies on "data"
        out = P(None, None, "data") if flash else P(None, "data")
        q_, k_, v_ = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P(), P()), out_specs=(out, out, out)))(
            h, lp)
        for got, want in ((q_, want_q), (k_, want_k), (v_, want_v)):
            got = np.asarray(got)
            if flash:                    # [B, H, T, D] for the kernels
                got = got.transpose(0, 2, 1, 3)
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        # the rotary key really is one: every head's last 4 are equal
        k_ = np.asarray(k_) if not flash else np.asarray(k_).transpose(
            0, 2, 1, 3)
        assert (k_[:, :, :1, dn:] == k_[:, :, :, dn:]).all()


def test_rope_tables_turn_the_rotary_dimensions_alone():
    """The tables of a latent-attention model are over qk_rope_dim
    dimensions, ``inv_freq_j = theta^(-2j/4)``; every other model's stay
    over head_dim."""
    mesh = make_mesh(devices=jax.devices()[:1])
    cfg = TransformerConfig(**MODEL)

    def tables(dim):
        return jax.jit(jax.shard_map(
            lambda: rope_tables(cfg, 8, "data", dim=dim), mesh=mesh,
            in_specs=(), out_specs=(P("data"), P("data"))))()

    cos, sin = tables(cfg.qk_rope_dim)
    angle = np.arange(8)[:, None] * np.array([1.0, 1e6 ** -0.5])[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(angle), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(angle), atol=1e-6)
    assert tables(None)[0].shape == (8, 8)          # all of head_dim 16


# -- the share of the experts, the shared expert counted once ----------------

SHARE = dict(embed=32, moe_experts=64, moe_top_k=4, moe_ffn=16, moe_held=8,
             moe_router_bias=True, moe_routed_scale=1.8, shared_ffn=16,
             layer_ffns=("dense", "moe"), n_layers=2, dtype=jnp.float32)


def test_the_eight_shares_of_a_layer_sum_to_the_uncut_reference():
    """8 of 64 sigmoid-routed experts a share, 4 a token, scale 1.8: the
    eight shares' routed parts and the shared expert, which every chip
    computes alike, counted ONCE, add up to the uncut reference's whole
    layer, and the loads to every pair routed."""
    from benchmark import reference_glm47

    rng = np.random.default_rng(11)
    E, X, Fe = SHARE["embed"], SHARE["moe_experts"], SHARE["moe_ffn"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    h = f32(rng.normal(size=(2, 24, E)))
    full = {"w_router": f32(rng.normal(size=(E, X))),
            "router_bias": f32(0.1 * rng.normal(size=(X,))),
            "moe_w_gate": f32(rng.normal(size=(X, E, Fe)) / 6),
            "moe_w_in": f32(rng.normal(size=(X, E, Fe)) / 6),
            "moe_w_out": f32(rng.normal(size=(X, Fe, E)) / 4),
            "shared_w_gate": f32(rng.normal(size=(E, Fe)) / 6),
            "shared_w_in": f32(rng.normal(size=(E, Fe)) / 6),
            "shared_w_out": f32(rng.normal(size=(Fe, E)) / 4)}
    with jax.default_matmul_precision("highest"):
        want, (want_chosen, _, want_loads, all_loads) = \
            reference_glm47.routed_layer(h.reshape(-1, E), full, top_k=4,
                                         routed_scale=1.8)
        routed_alone, _ = reference_glm47.routed_layer(
            h.reshape(-1, E), full, top_k=4, routed_scale=1.8, shared=False)
    assert np.array_equal(np.asarray(want_loads), np.asarray(all_loads))
    mesh = make_mesh(devices=jax.devices()[:1])
    total, loads, shared = 0.0, [], None
    for share in range(8):
        cfg = TransformerConfig(moe_held_offset=8 * share, **SHARE)
        lp = dict(full, **{n: full[n][8 * share:8 * share + 8]
                           for n in ("moe_w_gate", "moe_w_in", "moe_w_out")})

        def layer(h, lp, cfg=cfg):
            out, stats, chosen, _ = moe.routed_experts(h, lp, cfg, 1, "data",
                                                       "model")
            return out, stats, chosen, moe.shared_expert(h, lp, cfg)

        out, stats, chosen, alike = jax.jit(jax.shard_map(
            layer, mesh=mesh, in_specs=(P(), P()),
            out_specs=(P(), P(), P(), P())))(h, lp)
        if shared is None:
            shared = np.asarray(alike)
        # what every chip computes alike is the same on every chip
        np.testing.assert_allclose(np.asarray(alike), shared, atol=1e-6)
        total = total + (np.asarray(out) - np.asarray(alike))
        loads += np.asarray(stats)[:moe.STAT_DROPPED].tolist()
        assert np.asarray(stats)[moe.STAT_DROPPED] == 0
        assert np.asarray(stats)[moe.STAT_ROUTED] == 2 * 24 * 4
        assert (np.sort(np.asarray(chosen).reshape(-1, 4))
                == np.sort(np.asarray(want_chosen))).all()
    np.testing.assert_allclose(total.reshape(-1, E),
                               np.asarray(routed_alone), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose((total + shared).reshape(-1, E),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    # counted eight times it would be off by seven shared experts
    assert np.abs(7 * shared).max() > 100 * 1e-4
    assert loads == np.asarray(want_loads).tolist()
    assert sum(loads) == 2 * 24 * 4


def test_the_routed_weights_are_the_written_out_top_4_times_the_scale():
    rng = np.random.default_rng(2)
    flat = rng.normal(size=(40, 32)).astype(np.float32)
    w = rng.normal(size=(32, 16)).astype(np.float32)
    bias = (0.5 * rng.normal(size=(16,))).astype(np.float32)
    chosen, weights = jax.jit(lambda f, w, b: moe.route(
        f, w, b, 4, "sigmoid", 1.8))(flat, w, bias)
    s = 1 / (1 + np.exp(-(flat.astype(np.float64) @ w)))
    want = np.argsort(-(s + bias), axis=1)[:, :4]
    assert (np.sort(np.asarray(chosen)) == np.sort(want)).all()
    picked = np.take_along_axis(s, np.asarray(chosen), axis=1)
    np.testing.assert_allclose(
        np.asarray(weights),
        1.8 * picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    plain = jax.jit(lambda f, w, b: moe.route(f, w, b, 4))(flat, w, bias)[1]
    np.testing.assert_allclose(np.asarray(weights), 1.8 * np.asarray(plain),
                               rtol=1e-6)


# -- the benchmark's files ---------------------------------------------------


def test_reference_is_independent_and_sets_highest_precision():
    with open(os.path.join(BENCH, "reference_glm47.py")) as f:
        code = f.read().split('"""', 2)[2]
    assert "mapreduce_tpu" not in code and "import jax" in code
    assert 'default_matmul_precision("highest")' in code
    assert "pallas" not in code and "shard_map" not in code
    assert "ragged" not in code and "bfloat16" not in code


#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def test_configuration_file_is_the_catalogs_row_cut_as_it_says():
    config = load(CONFIG)
    cut = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "vocab_size": 19360}
    assert sorted(config["reduced"]) == sorted(cut)
    for key, value in PUBLISHED.items():
        assert config[key] == cut.get(key, value), key
        if key in cut:
            assert config["published"][key] == value
    assert config["source"] == ("https://huggingface.co/zai-org/"
                                "GLM-4.7-Flash/blob/main/config.json")
    m = config["model"]
    assert m["layer_ffns"] == ["dense"] + ["moe"] * 4 and "layer_ops" not in m
    assert (m["embed"], m["n_heads"], m["head_dim"], m["qk_nope_dim"],
            m["qk_rope_dim"], m["v_head_dim"], m["q_lora_rank"],
            m["kv_lora_rank"], m["ffn"], m["moe_ffn"], m["shared_ffn"],
            m["moe_experts"], m["moe_top_k"], m["moe_held"], m["vocab"],
            m["norm_eps"], m["moe_routed_scale"], m["mtp_blocks"],
            m["rope_theta"], m["tied_embeddings"], m["moe_router_score"],
            m["moe_router_bias"], m["n_layers"]) == (
        2048, 20, 256, 192, 64, 256, 768, 512, 10240, 1536, 1536, 64, 4, 8,
        19360, 1e-05, 1.8, 1, 1e6, False, "sigmoid", True, 5)
    assert m["vocab"] * 8 == PUBLISHED["vocab_size"]
    assert m["moe_bias_rate"] == 0.001 and m["mtp_weight"] == 0.3
    assumed = " ".join(config["assumed"])
    for said in ("gamma = 0.001", "lambda = 0.3", "BEFORE the main model's "
                 "final norm", "rotate-half", "1e-6", "balanced_bias"):
        assert said in assumed, said
    assert set(config["program"]["kernels"]) == {
        "flash_fwd", "flash_dq", "flash_dkv", "moe_gmm", "moe_tgmm"}
    taken = config["memory"]["taken"]
    assert taken["seq_len"] == config["train"]["seq_len"] == 8192
    assert taken["total_gb"] <= 13.5 and config["train"]["batch"] == 1
    TransformerConfig(**m).validate(1)


def test_configuration_files_parameter_count_is_the_initialisers():
    config = load(CONFIG)
    shapes = jax.eval_shape(lambda: init_transformer(
        jax.random.key(0), TransformerConfig(**config["model"])))
    count = lambda names: sum(int(np.prod(shapes[n].shape)) for n in names)
    par = config["parameters"]
    assert count(shapes) == par["tree_total"] == 706518848
    buffers = [n for n in shapes if n.endswith(".router_bias")]
    assert count(buffers) == par["buffers"] == 5 * 64
    assert par["trained_total"] == par["tree_total"] - par["buffers"]
    assert abs(par["trained_total"] - 706.5e6) / 706.5e6 < 0.01
    E, H = 2048, 20
    attention = (E * 768 + 768 * H * 256 + E * (512 + 64)
                 + 512 * H * (192 + 256) + H * 256 * E)
    assert attention == par["attention"]["weights"] == 21757952
    assert par["attention"]["latent_norms"] == 768 + 512
    expert = 3 * E * 1536
    by_hand = {0: attention + 1280 + 3 * E * 10240 + 2 * E}
    for i in (1, 2, 3, 4):
        by_hand[i] = (attention + 1280 + 2 * E + E * 64 + 64 + 9 * expert)
    by_hand[5] = by_hand[4] + 2 * E * E + 3 * E
    for i, want in by_hand.items():
        assert count(n for n in shapes if n.startswith(f"L{i}.")) == want, i
    assert par["dense_layer"]["total"] == by_hand[0]
    assert par["expert_layer"]["total"] == by_hand[1] == 106829120
    assert par["expert_layer"]["shared_expert"] == expert == 9437184
    assert par["expert_layer"]["experts_8"] == 8 * expert
    assert par["prediction_block"]["total"] == by_hand[5]
    assert count(["embed"]) == count(["unembed"]) == par["embedding"] \
        == par["head"] == 19360 * E
    assert par["bytes_each"] == 16


def test_required_operations_against_a_count_by_hand():
    from benchmark import flops_glm47, kernel_work

    m = load(CONFIG)["model"]
    E, T, H, V = 2048, 8192, 20, 19360
    latent = (E * 768 + 768 * H * 256 + E * 576 + 512 * H * 448
              + H * 256 * E)
    assert flops_glm47.latent_macs_per_token(m) == latent == 21757952
    products = H * (256 + 256) * T / 2
    assert flops_glm47.attention_product_macs_per_token(m, T) == products
    assert flops_glm47.attention_layers(m) == 6
    assert flops_glm47.expert_layers(m) == 5
    dense = (6 * (latent + products) + 3 * E * 10240
             + 5 * (E * 64 + 3 * E * 1536) + 2 * E * E + 2 * E * V)
    assert flops_glm47.dense_macs_per_token(m, T) == dense
    assert flops_glm47.expert_macs_per_pair(m) == 3 * E * 1536
    pairs = flops_glm47.expected_pairs_held(m, 1, T)
    assert pairs == 5 * T * 4 * 8 / 64 == 5 * 8 * 512
    by_hand = 6 * (dense * T + 3 * E * 1536 * pairs)
    assert flops_glm47.train_step_flops(m, 1, T, pairs) == by_hand
    assert 2.7e13 < by_hand < 3.3e13
    # latent attention, its products included, is about three fifths
    assert 0.55 < 6 * 6 * (latent + products) * T / by_hand < 0.65
    # the flash kernels' work: the rule reads the head width, 256
    fwd = kernel_work.kernel_call_work("flash_fwd", m, 1, T)
    assert fwd["flops"] == 2 * H * T * T * 256
    assert fwd["bytes"] == 4 * H * T * 256 * 2 + H * T * 4


def test_benchmark_lists_the_cell_and_its_metrics():
    manifest = load(ROOT, "BENCHMARK.json")
    (cell,) = [w for w in manifest["workloads"]
               if w["name"] == "train-glm47flash-mla"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-4.7-flash-l5-e8", "fresh-batch-b1-adamw-mtp", 1)
    (rate,) = [e for e in manifest["end_to_end"]
               if e["name"] == "train_tok_rate"]
    assert rate["workloads"][-1] == "train-glm47flash-mla"
    listed = {p["name"] for p in manifest["per_layer"]
              if p.get("workloads") == ["train-glm47flash-mla"]}
    assert listed == {"glm.step_ms", "glm.mfu", "glm.idle_share",
                      "glm.flash_share", "glm.gmm_share",
                      "glm.load_max_over_mean", "glm.mtp_loss_share"}
    waiting = {"glm.mla_down_share": "tf.mla_down",
               "glm.mla_up_share": "tf.mla_up",
               "glm.shared_expert_share": "tf.shared_expert",
               "glm.mtp_share": "tf.mtp",
               "glm.bias_update_share": "tf.bias_update"}
    for name, scope in waiting.items():
        assert load(BENCH, "layer_metrics", name + ".json")["read"] == {
            "stage": scope, "over": "busy"}
    for kernel in ("flash_fwd", "flash_dkv"):
        assert load(BENCH, "layer_metrics", f"glm.{kernel}_roofline.json")[
            "read"] == {"kernel_roofline": kernel}
