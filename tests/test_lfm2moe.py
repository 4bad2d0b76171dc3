"""A model with layers of two kinds and routed experts (PR 33): the gated
short convolution, grouped-query attention with q/k norms, a leading
dense FFN, routed experts of which the mesh holds a share, tied
embeddings, ``norm_eps`` — through ``loss_local`` and
``TransformerTrainer.step_opt`` against the plain float32 reference
``benchmark/reference_lfm2moe.py`` on seeded weights.

CPU, toy size (E 64, 3 layers conv/attn/conv with dense/moe/moe FFNs, 4
heads over 2 key/value heads of 16, 16 experts top-4 of width 32 of
which experts 4 to 7 are held, vocabulary 256, T 128): the loss, the
routing, the gradient of EVERY parameter and AdamW's first update, with
recomputation on and off, the flash kernel interpreted and the jnp path,
on one and on two ``model`` ranks; routings made adversarial through the
selection bias; routing rules that are NOT the published one, which the
comparison must tell apart.  No time here is a device number.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mapreduce_tpu.models.transformer import (BUFFERS, TransformerConfig,
                                              TransformerTrainer,
                                              init_transformer, loss_local,
                                              transformer_param_spec)
from mapreduce_tpu.obs.compile import LEDGER
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

MODEL = dict(vocab=256, embed=64, n_layers=3, n_heads=4, head_dim=16,
             ffn=128, loss_block=64, rope_theta=1e6, ffn_gated=True,
             final_norm=True, layer_ops=("conv", "attn", "conv"),
             layer_ffns=("dense", "moe", "moe"), conv_taps=3, n_kv_heads=2,
             qk_norm=True, norm_eps=1e-5, tied_embeddings=True,
             moe_experts=16, moe_top_k=4, moe_ffn=32, moe_held=4,
             moe_held_offset=4, moe_router_bias=True)
REFERENCE = dict(layer_ops=MODEL["layer_ops"], layer_ffns=MODEL["layer_ffns"],
                 n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=1e6,
                 eps=1e-5, top_k=4, held=(4, 4), block=32)
TOKENS = np.random.default_rng(0).integers(0, MODEL["vocab"], size=(2, 129),
                                           dtype=np.int32)
PAIRS = 2 * 128 * 4

#: (dtype, remat, flash, devices on the model axis)
CASES = [("float32", False, False, 1), ("float32", True, False, 1),
         ("float32", True, True, 1), ("float32", True, False, 2),
         ("float32", True, True, 2), ("bfloat16", True, True, 1)]
#: Tolerances.  In float32 the system differs from the reference by the
#: order of its sums alone (and a choice can flip only at an exact tie);
#: in bfloat16 a token's fourth choice flips where two scores lie within
#: the rounding of the layer's input, and each flip moves one pair's
#: gradient from one expert's tensors to another's.
TOL = {"float32": dict(loss=1e-5, grad=2e-4, routing=1e-3),
       "bfloat16": dict(loss=2e-3, grad=0.5, routing=0.1)}
GROUPS = {"embedding": ("embed",), "conv": (".conv_in", ".conv_w",
                                            ".conv_out"),
          "attention": (".wq", ".wkv", ".wo"),
          "dense_ffn": (".w_in", ".w_gate", ".w_out"),
          "router": (".w_router", ".router_bias"),
          "experts": (".moe_w_in", ".moe_w_gate", ".moe_w_out"),
          "norms": ("_scale",)}
#: selection biases that force a routing: +10 on the experts named
BIASES = {"every choice held": (4, 5, 6, 7), "none held": (0, 1, 2, 3),
          "one expert takes every token": (5, 12, 13, 14)}


def case_id(case):
    dtype, remat, flash, n_model = case
    return (f"{dtype}-{'remat' if remat else 'saved'}-"
            f"{'flash' if flash else 'jnp'}-model{n_model}")


def seeded_params(cfg=None, forced=None):
    """The program's init with every vector moved off its start, so that
    each one's gradient and its place in the mathematics are tested;
    *forced* names a routing of ``BIASES``."""
    params = init_transformer(jax.random.key(3),
                              cfg or TransformerConfig(**MODEL))
    key = jax.random.key(7)
    params = {n: (a + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                              a.shape)
                  if a.ndim == 1 and not n.endswith(BUFFERS) else a)
              for i, (n, a) in enumerate(sorted(params.items()))}
    if forced is not None:
        for n in params:
            if n.endswith(BUFFERS):
                params[n] = jnp.zeros_like(params[n]).at[
                    jnp.asarray(BIASES[forced])].set(10.0)
    return params


@functools.lru_cache(maxsize=None)
def _reference_fn(rule="published", eps=1e-5):
    from benchmark import reference_lfm2moe

    kw = dict(REFERENCE, eps=eps, rule=rule)
    return jax.jit(lambda p: reference_lfm2moe.reference_gradients(
        p, TOKENS[:, :-1], TOKENS[:, 1:], **kw))


@functools.lru_cache(maxsize=None)
def reference(forced=None, rule="published"):
    (loss, chosen, weights, loads), grads = _reference_fn(rule)(
        seeded_params(forced=forced))
    return (float(loss), np.asarray(chosen), np.asarray(loads),
            {n: np.asarray(g) for n, g in grads.items()},
            np.asarray(weights))


@functools.lru_cache(maxsize=None)
def _system_fn(case, **model):
    dtype, remat, flash, n_model = case
    cfg = TransformerConfig(dtype=jnp.dtype(dtype), remat=remat, flash=flash,
                            **dict(MODEL, **model))
    mesh = make_mesh(devices=jax.devices()[:n_model], n_model=n_model)
    f = jax.shard_map(
        lambda p, x, y: loss_local(p, x, y, cfg, n_model), mesh=mesh,
        in_specs=({n: transformer_param_spec(n)
                   for n in seeded_params(cfg)},
                  P(None, "data"), P(None, "data")),
        out_specs=(P(), {"loads": P(),
                         "chosen": P(None, None, "data", None),
                         "weights": P(None, None, "data", None)}))
    return jax.jit(jax.value_and_grad(f, has_aux=True))


@functools.lru_cache(maxsize=None)
def system(case, forced=None):
    """``loss_local`` under ``shard_map`` exactly as the trainer wraps
    it, differentiated: (loss, chosen, stats rows, gradients)."""
    (loss, stats), grads = _system_fn(case)(
        seeded_params(forced=forced), TOKENS[:, :-1], TOKENS[:, 1:])
    return (float(loss), np.asarray(stats["chosen"]),
            np.asarray(stats["loads"]),
            {n: np.asarray(g) for n, g in grads.items()},
            np.asarray(stats["weights"]))


# -- the system against the reference ----------------------------------------


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_loss_matches_the_reference(case):
    assert abs(system(case)[0] - reference()[0]) \
        <= TOL[case[0]]["loss"] * reference()[0]


def routing_gaps(chosen, weights, want_chosen, want_weights):
    """``(1 - share of the system's chosen experts that the reference
    chose too, mean |weight gap| over those)`` against a reference left
    to its own choices, the worst expert layer's."""
    same = chosen[..., :, None] == want_chosen[..., None, :]    # [.., k, k]
    common = same.any(-1)
    theirs = (same * want_weights[..., None, :]).sum(-1)
    gap = (np.abs(weights - theirs) * common).sum(axis=(1, 2, 3)) \
        / np.maximum(common.sum(axis=(1, 2, 3)), 1)
    return 1.0 - common.mean(axis=(1, 2, 3)).min(), gap.max()


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_routing_matches_the_reference_and_nothing_is_dropped(case):
    _, chosen, stats, _, weights = system(case)
    _, want_chosen, want_loads, _, want_weights = reference()
    assert chosen.shape == want_chosen.shape == (2, 2, 128, 4)
    flipped, weight_gap = routing_gaps(chosen, weights, want_chosen,
                                       want_weights)
    assert flipped <= TOL[case[0]]["routing"]
    assert weight_gap <= (1e-5 if case[0] == "float32" else 5e-3)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-4)
    assert (stats[:, -2] == 0).all() and (stats[:, -1] == PAIRS).all()
    if case[0] == "float32":
        assert stats[:, :-2].tolist() == want_loads.tolist()
    # about a quarter of the pairs land on the 4 of 16 held
    assert 0.1 * PAIRS < stats[0, :-2].sum() < 0.45 * PAIRS


@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_every_gradient_matches_the_reference(case, group):
    grads, want = system(case)[3], reference()[3]
    names = [n for n in want if n.endswith(GROUPS[group])]
    assert names
    for n in names:
        scale = np.abs(want[n]).max()
        if n.endswith(BUFFERS):
            assert not grads[n].any() and not want[n].any()
            continue
        assert np.abs(grads[n] - want[n]).max() \
            <= TOL[case[0]]["grad"] * scale, n


def test_every_parameter_is_in_a_gradient_group():
    for n in reference()[3]:
        assert sum(n.endswith(s) for s in GROUPS.values()) == 1, n


@pytest.mark.parametrize("forced", sorted(BIASES))
@pytest.mark.parametrize("flash", [False, True], ids=["jnp", "flash"])
def test_a_forced_routing_is_exact_and_drops_nothing(forced, flash):
    """Every token's four choices held here (4 N pairs, the buffers'
    whole room), none of them, and one held expert taking every token:
    loss, loads and every gradient as the reference's."""
    case = ("float32", True, flash, 1)
    loss, chosen, stats, grads, _ = system(case, forced)
    want_loss, want_chosen, want_loads, want, _ = reference(forced)
    assert (np.sort(chosen) == np.sort(want_chosen)).all()
    assert (np.sort(chosen)[..., :] == np.sort(BIASES[forced])).all()
    assert stats[:, :-2].tolist() == want_loads.tolist()
    assert (stats[:, -2] == 0).all()
    held = {"every choice held": PAIRS, "none held": 0,
            "one expert takes every token": PAIRS // 4}[forced]
    assert (stats[:, :-2].sum(axis=1) == held).all()
    assert abs(loss - want_loss) <= 1e-5 * want_loss
    for n in want:
        assert np.abs(grads[n] - want[n]).max() \
            <= 2e-4 * max(np.abs(want[n]).max(), 1e-6), n


@pytest.mark.parametrize("rule", ["biased_weights", "held_norm", "softmax"])
def test_a_wrong_routing_rule_fails_the_comparison(rule):
    """Weights from score + bias, normalising over the held experts
    only, softmax for sigmoid: each is further from the system than the
    float32 tolerances, by the loss and by a gradient."""
    loss, chosen, stats, grads, weights = system(
        ("float32", True, False, 1))
    want_loss, want_chosen, want_loads, want, want_weights = reference(
        rule=rule)
    assert abs(loss - want_loss) > 2 * TOL["float32"]["loss"] * want_loss
    worst = max(np.abs(grads[n] - want[n]).max() / np.abs(want[n]).max()
                for n in want if n.endswith(GROUPS["experts"]))
    assert worst > 10 * TOL["float32"]["grad"]
    # and by the weights of the choices both made, however few flipped
    _, weight_gap = routing_gaps(chosen, weights, want_chosen, want_weights)
    assert weight_gap > 1e-2


def test_norm_eps_is_the_configurations():
    """A large epsilon moves the loss, and the reference given the same
    epsilon moves with it."""
    case = ("float32", False, False, 1)
    params = seeded_params()
    (loss, _), _ = _system_fn(case, norm_eps=0.5)(
        params, TOKENS[:, :-1], TOKENS[:, 1:])
    (want, *_), _ = _reference_fn(eps=0.5)(params)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    assert abs(float(loss) - system(case)[0]) > 1e-3 * float(want)


# -- the expert layer's older cases, on the one layer the tree has -----------


def test_one_expert_equals_the_dense_gated_ffn():
    """One expert, one choice: the weight is s / (s + 1e-6) and the
    expert IS the dense FFN."""
    base = dict(vocab=32, embed=32, n_layers=2, n_heads=4, head_dim=8,
                ffn=64, ffn_gated=True, dtype=jnp.float32)
    dense = TransformerConfig(**base)
    routed = TransformerConfig(layer_ffns=("moe", "moe"), moe_experts=1,
                               moe_top_k=1, moe_ffn=64, **base)
    dp = init_transformer(jax.random.key(0), dense)
    rp = init_transformer(jax.random.key(0), routed)
    for i in range(2):
        for n in ("w_in", "w_gate", "w_out"):
            rp[f"L{i}.moe_{n}"] = dp[f"L{i}.{n}"][None]
    toks = np.random.default_rng(4).integers(0, 32, size=(2, 17),
                                             dtype=np.int32)
    mesh = make_mesh(devices=jax.devices()[:1])

    def loss(cfg, params):
        f = jax.shard_map(
            lambda p, x, y: loss_local(p, x, y, cfg, 1), mesh=mesh,
            in_specs=({n: P() for n in params}, P(None, "data"),
                      P(None, "data")),
            out_specs=P() if cfg is dense else (
                P(), {"loads": P(), "chosen": P(None, None, "data", None),
                      "weights": P(None, None, "data", None)}))
        out = jax.jit(f)(params, toks[:, :-1], toks[:, 1:])
        return float(out if cfg is dense else out[0])

    assert abs(loss(dense, dp) - loss(routed, rp)) < 2e-5 * loss(dense, dp)


@pytest.fixture(scope="module")
def two_rank_trainer():
    import optax

    cfg = TransformerConfig(flash=False, **dict(MODEL, loss_block=32))
    return TransformerTrainer(make_mesh(n_model=2), cfg,
                              optimizer=optax.adamw(3e-3))


def test_two_ranks_of_held_experts_train(two_rank_trainer):
    """2 held experts a rank on a 2 x 4 mesh (sequence-sharded: the
    convolution's halo, the loads summed over the shards): the loss of
    the first step is the one-rank system's, and it falls."""
    tr = two_rank_trainer
    params = tr._place_params(seeded_params())
    opt_state = tr.init_opt_state(params)
    losses = []
    for _ in range(12):
        params, opt_state, loss, stats = tr.step_opt(params, opt_state,
                                                     TOKENS)
        losses.append(float(loss))
    want = system(("float32", True, False, 1))[0]
    assert abs(losses[0] - want) < 2e-3 * want      # bfloat16 products
    assert losses[-1] < losses[0] - 0.5
    loads = tr.observe_experts(stats)
    assert loads.shape == (2, 6) and (loads[:, -1] == PAIRS).all()


def test_held_experts_that_do_not_divide_over_the_ranks_are_refused():
    cfg = TransformerConfig(**dict(MODEL, moe_held=3))
    with pytest.raises(AssertionError, match="held experts do not divide"):
        TransformerTrainer(make_mesh(n_model=2), cfg)


def test_sgd_step_refuses_a_routed_model(two_rank_trainer):
    with pytest.raises(RuntimeError, match="routed expert layers"):
        two_rank_trainer.step(two_rank_trainer.init_params(), TOKENS)


# -- the trainer: buffers, counters, checkpoints, stages ---------------------


@pytest.fixture(scope="module")
def trainer():
    import optax

    cfg = TransformerConfig(remat=True, flash=True, **MODEL)
    return TransformerTrainer(
        make_mesh(devices=jax.devices()[:1]), cfg,
        optimizer=optax.adamw(3e-3, weight_decay=0.1))


def test_a_step_leaves_the_selection_bias_as_it_was(trainer):
    params, opt_state = trainer.init_state()
    before = {n: np.asarray(a) for n, a in params.items()}
    params, *_ = trainer.step_opt(params, opt_state, TOKENS)
    for n, a in params.items():
        same = np.array_equal(before[n], np.asarray(a))
        assert same == n.endswith(BUFFERS), n


def test_observe_experts_counts_and_sets_the_gauges(trainer):
    held0 = REGISTRY.sum("mrtpu_moe_pairs_held_total")
    apps0 = REGISTRY.sum("mrtpu_train_layer_applications_total")
    *_, stats = trainer.step_opt(*trainer.init_state(), TOKENS)
    loads = trainer.observe_experts(stats)
    assert REGISTRY.sum("mrtpu_train_layer_applications_total") - apps0 == 3
    assert REGISTRY.sum("mrtpu_moe_pairs_held_total") - held0 \
        == loads[:, :-2].sum()
    assert REGISTRY.sum("mrtpu_moe_dropped_pairs_total") == 0
    for layer, row in zip((1, 2), loads):
        assert REGISTRY.sum("mrtpu_moe_pairs_held_share", layer=layer) \
            == pytest.approx(row[:-2].sum() / PAIRS)
        assert REGISTRY.sum("mrtpu_moe_expert_load_max_over_mean",
                            layer=layer) \
            == pytest.approx(row[:-2].max() / row[:-2].mean())


def test_rows_in_use_share_is_the_loads_in_whole_tiles(trainer):
    """1,024 pairs a step on 4 held experts: tiles of 16 rows, 64 + 4 of
    them in the buffers; every expert owns at least one."""
    by_hand = {1: ([0, 16, 17, 100], (1 + 1 + 2 + 7) / 68),
               2: ([256, 256, 256, 256], 64 / 68)}
    trainer.observe_experts({"loads": np.array(
        [loads + [0, PAIRS] for loads, _ in by_hand.values()])})
    for layer, (_, share) in by_hand.items():
        assert REGISTRY.sum("mrtpu_moe_rows_in_use_share", layer=layer) \
            == pytest.approx(share)


def test_save_and_load_carry_the_new_tensors(trainer, tmp_path):
    params, opt_state = trainer.init_state()
    params, opt_state, *_ = trainer.step_opt(params, opt_state, TOKENS)
    trainer.save(str(tmp_path), params, step=1, opt_state=opt_state)
    loaded, opt_loaded, step = trainer.load_state(str(tmp_path))
    assert step == 1 and set(loaded) == set(params)
    for n in ("L0.conv_w", "L1.wkv", "L1.q_norm_scale", "L2.router_bias",
              "L2.moe_w_gate", "embed"):
        assert np.array_equal(np.asarray(loaded[n]), np.asarray(params[n]))
    assert "unembed" not in loaded
    a = trainer.step_opt(params, opt_state, TOKENS)[2]
    b = trainer.step_opt(loaded, opt_loaded, TOKENS)[2]
    assert float(a) == float(b)


@pytest.mark.parametrize("other", [
    dict(moe_held_offset=8), dict(layer_ops=("conv", "conv", "attn")),
    dict(moe_top_k=2), dict(norm_eps=1e-6), dict(qk_norm=False),
    dict(moe_router_bias=False), dict(conv_taps=4)], ids=lambda o: next(
        iter(o)))
def test_arch_tag_tells_the_layouts_apart(trainer, tmp_path, other):
    """The same shapes (or nearly) under another function: other experts
    held, the operators in another order, another top-k or epsilon."""
    import optax

    b = TransformerTrainer(
        make_mesh(devices=jax.devices()[:1]),
        TransformerConfig(remat=True, flash=True, **dict(MODEL, **other)),
        optimizer=optax.adamw(3e-3))
    assert b._arch_tag() != trainer._arch_tag()
    trainer.save(str(tmp_path), trainer.init_params())
    with pytest.raises(ValueError, match="do not match this config"):
        b.load(str(tmp_path))


def test_a_dense_block_keeps_its_arch_tag():
    mesh = make_mesh(devices=jax.devices()[:1])
    cfg = TransformerConfig(vocab=512, embed=64, n_layers=2, n_heads=4,
                            head_dim=16, ffn=128)
    assert TransformerTrainer(mesh, cfg)._arch_tag() \
        == "v512.e64.l2.h4.d16.f128.moe0"


@pytest.mark.parametrize("scope", [
    "tf.conv_op", "tf.qk_norm", "tf.moe_route", "tf.moe_dispatch",
    "tf.moe_experts", "tf.moe_combine", "tf.attn_proj", "tf.rope",
    "tf.flash", "tf.ffn", "tf.loss", "tf.update"])
def test_stage_map_books_the_new_stages(trainer, scope):
    from benchmark import stages

    trainer.step_opt(*trainer.init_state(), TOKENS)
    (paths,) = LEDGER.stage_map("tf_step_opt").values()
    chains = [stages.stage_chain(p) for p in paths.values()]
    assert any(c and c[-1] == scope for c in chains)
    if scope.startswith("tf.moe") or scope == "tf.conv_op":
        assert any("transpose(" in p and scope in stages.stage_chain(p)
                   for p in paths.values())


# -- the benchmark's side: reference, operations, configuration, kind --------


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_reference_is_independent_and_sets_highest_precision():
    with open(os.path.join(BENCH, "reference_lfm2moe.py")) as f:
        code = f.read().split('"""', 2)[2]
    assert "mapreduce_tpu" not in code and "import jax" in code
    assert 'default_matmul_precision("highest")' in code
    assert "pallas" not in code and "shard_map" not in code
    assert "ragged" not in code


def test_configuration_file_is_the_catalogs_row_cut_as_it_says():
    config = load(BENCH, "configs", "lfm2-24b-a2b-l5-e8.json")
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                 "intermediate_size": 11776, "max_position_embeddings":
                 128000, "moe_intermediate_size": 1536, "norm_eps": 1e-05,
                 "norm_topk_prob": True, "num_attention_heads": 32,
                 "num_dense_layers": 2, "num_experts": 64,
                 "num_experts_per_tok": 4, "num_hidden_layers": 40,
                 "num_key_value_heads": 8, "routed_scaling_factor": 1,
                 "use_expert_bias": True, "vocab_size": 65536}
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 8192}
    assert sorted(config["reduced"]) == sorted(cut)
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
        if key in cut:
            assert config["published"][key] == value
    assert config["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    assert len(config["layer_types"]) == 40
    m = config["model"]
    kinds = {"conv": "conv", "full_attention": "attn"}
    assert m["layer_ops"] == [kinds[t] for t in config["layer_types"][1:6]]
    assert m["layer_ffns"] == ["dense"] + ["moe"] * 4
    assert (m["embed"], m["ffn"], m["moe_ffn"], m["n_heads"],
            m["n_kv_heads"], m["head_dim"], m["conv_taps"], m["moe_top_k"],
            m["moe_experts"], m["moe_held"], m["vocab"], m["norm_eps"]) == (
        2048, 11776, 1536, 32, 8, 64, 3, 4, 64, 8, 8192, 1e-05)
    assert set(config["program"]["kernels"]) == {
        "flash_fwd", "flash_dq", "flash_dkv", "moe_gmm", "moe_tgmm"}


def test_configuration_files_parameter_count_is_the_initialisers():
    config = load(BENCH, "configs", "lfm2-24b-a2b-l5-e8.json")
    shapes = jax.eval_shape(lambda: init_transformer(
        jax.random.key(0), TransformerConfig(**config["model"])))
    count = lambda names: sum(int(np.prod(shapes[n].shape)) for n in names)
    buffers = [n for n in shapes if n.endswith(BUFFERS)]
    par = config["parameters"]
    assert count(buffers) == par["untrained_bias_values"] == 256
    assert count(shapes) - 256 == par["trained_total"] == 469284992
    layer = lambda i: [n for n in shapes if n.startswith(f"L{i}.")
                       and n not in buffers]
    assert count(layer(0)) == par["dense_layer"]["total"]
    assert count(layer(1)) == par["attention_expert_layer"]["total"]
    for i in (2, 3, 4):
        assert count(layer(i)) == par["conv_expert_layer"]["total"]
    assert count(["embed"]) == par["embedding_tied"]
    assert "unembed" not in shapes


def test_required_operations_against_a_count_by_hand():
    from benchmark import flops_moe

    m = load(BENCH, "configs", "lfm2-24b-a2b-l5-e8.json")["model"]
    E, T = 2048, 8192
    conv = 3 * E * E + E * E
    attn = E * 2048 + E * 2 * 512 + 2048 * E + 2 * 32 * 64 * T / 2
    by_hand = (4 * conv + attn + 3 * E * 11776 + 4 * E * 64 + E * 8192)
    assert flops_moe.dense_macs_per_token(m, T) == by_hand
    assert flops_moe.expert_macs_per_pair(m) == 3 * E * 1536
    pairs = flops_moe.expected_pairs_held(m, 4, T)
    assert pairs == 4 * 4 * T * 4 * 8 / 64
    step = flops_moe.train_step_flops(m, 4, T, pairs)
    assert step == 6 * (by_hand * 4 * T + 3 * E * 1536 * pairs)
    assert 3.9e13 < step < 4.1e13            # ISSUE 33 reckoned 3.99e13
    work = flops_moe.grouped_product_work(m, 16384, 8)
    assert work["flops"] == 2 * 16384 * E * 1536
    assert work["bytes"] == 16384 * (E + 1536) * 2 + 8 * E * 1536 * 2


def _tiny_cell():
    """The cell at toy widths in float32, as the benchmark's own tests
    make it."""
    from benchmark.tests.test_moe_trainer import tiny

    return tiny()


def test_kind_holds_step_0_to_the_reference_in_float32():
    """The benchmark's own comparison at toy size: in float32 every gap
    is rounding, the selection bias stays, nothing is dropped."""
    from benchmark.kinds import moe_trainer

    _, cell, config = _tiny_cell()
    c = moe_trainer.Cell(config, cell, 2**31 + 5, jax.devices()[:1])
    c.warm(2)
    assert c.gaps["loss"] < 1e-5 and c.gaps["gradient"] < 1e-3
    assert c.gaps["update_rule"] < 1e-3 and c.gaps["pairs_held"] == 0
    assert c.gaps["routing"] < 1e-3 and c.gaps["update"] < 0.2
    assert c.gaps["weights"] < 1e-5
    assert list(c.faults()) == []
    r = c.unit()
    assert r["ok"] and r["pairs_held"] == sum(map(sum, r["loads"]))
    derived = c.derived({"train_tok_rate": 1.0}, 1, "cpu")
    assert set(derived) == {"load_max_over_mean"}    # no peak for a CPU
    assert 1.0 <= derived["load_max_over_mean"] < 2.0


def test_controls_come_out_not_correct():
    """``moe_controls.py``: 8-bit operands and an unchanged state fault,
    a wrong rule faults, the trainer does not (float32, toy size: the
    limits are the cell's, so only the direction is held here)."""
    from benchmark import moe_controls

    from benchmark.kinds import moe_trainer

    _, cell, config = _tiny_cell()
    c = moe_trainer.Cell(config, cell, 11, jax.devices()[:1])
    out = moe_controls.controls(c, 11, rules=("softmax",))
    assert out["trainer"]["faults"] == []
    assert out["half_batch"]["faults"]
    assert out["half_batch"]["gaps"]["gradient"] > 0.3
    assert set(moe_controls.controls(c, 12, full=False)) == {"trainer"}
    assert out["unchanged"]["gaps"]["gradient"] == pytest.approx(1.0)
    assert len(out["unchanged"]["faults"]) >= 3
    assert out["float8_e4m3fn"]["faults"]
    assert out["float8_e4m3fn"]["gaps"]["routing"] \
        > 10 * out["trainer"]["gaps"]["routing"] + 0.01
    assert out["softmax"]["faults"]
    assert out["softmax"]["gaps"]["weights"] \
        > 100 * out["trainer"]["gaps"]["weights"] + 0.01


def test_the_reference_given_the_systems_choices_differs_by_rounding_alone():
    """In bfloat16 a few fourth choices flip, and each moves a pair's
    gradient from one expert's tensors to another's.  Given the system's
    choices the reference computes THOSE experts' weights, output and
    gradients, while the choices and loads it returns stay its own: the
    routers' and the experts' gradients then differ from the system's as
    every other tensor's does."""
    from benchmark import reference_lfm2moe
    from benchmark.kinds import moe_trainer

    _, chosen, stats, grads, weights = system(("bfloat16", True, False, 1))
    _, free_chosen, free_loads, free, _ = reference()
    assert (chosen != free_chosen).any()                 # a flip to show
    (_, own, given_weights, loads), given = jax.jit(
        lambda p, c: reference_lfm2moe.reference_gradients(
            p, TOKENS[:, :-1], TOKENS[:, 1:], given=c, **REFERENCE))(
        seeded_params(), chosen)
    # the first expert layer's input is the free reference's
    assert (np.asarray(own)[0] == free_chosen[0]).all()
    assert np.asarray(loads)[0].tolist() == free_loads[0].tolist()
    _, weight_gap, _ = moe_trainer.routing_gaps(
        (chosen, weights, stats[:, :-2]), (own, given_weights, loads))
    assert weight_gap <= 5e-3

    def gap(want, suffixes):
        names = [n for n in want if n.endswith(suffixes)]
        return max(np.linalg.norm(grads[n] - np.asarray(want[n]))
                   / np.linalg.norm(want[n]) for n in names)

    routed = GROUPS["router"][:1] + GROUPS["experts"]
    others = GROUPS["conv"] + GROUPS["attention"] + GROUPS["dense_ffn"]
    assert gap(given, routed) < 0.5 * gap(free, routed)
    assert gap(given, routed) < 0.1 and gap(given, others) < 0.1
