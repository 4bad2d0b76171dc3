"""The ``glm_trainer`` kind's own tests, beside ``test_benchmark.py``
(which checks every manifest and data file, the new ones included): the
kind's loop and its controls at a tiny size on the CPU, through
``run.measure`` as the command drives it.

    python -m pytest benchmark/tests -q
"""

import json
import time

import pytest

from benchmark.tests.test_benchmark import RESULT_KEYS, UNIT


def tiny():
    """The cell and its configuration at toy widths that keep the
    ratios, in float32: a dense layer, two expert layers and the
    prediction block; 4 heads of 12 + 4 / 16 over latents of 12 and 8 +
    4; 16 experts top-4 of which 4 are held, a shared expert as wide as
    a routed one.  The limits are set for the cell's 8,192 positions in
    bfloat16; at 128 positions only float32 lies inside them."""
    import jax.numpy as jnp

    from benchmark import run

    m, _, cell, config = run.load_cell("train-glm47flash-mla")
    config["model"].update(
        vocab=256, embed=32, n_layers=3, n_heads=4, head_dim=16, ffn=80,
        loss_block=32, attn_block=32, layer_ffns=["dense", "moe", "moe"],
        q_lora_rank=12, kv_lora_rank=8, qk_nope_dim=12, qk_rope_dim=4,
        v_head_dim=16, moe_experts=16, moe_top_k=4, moe_ffn=24, moe_held=4,
        moe_held_offset=4, shared_ffn=24, dtype=jnp.float32)
    config["train"].update(batch=2, seq_len=128, reference_block=32)
    return m, cell, config


@pytest.mark.parametrize("traced", [False, True])
def test_kind_loop_yields_the_contracts_object(traced):
    import jax

    from benchmark import run
    from mapreduce_tpu.obs.metrics import REGISTRY

    REGISTRY.reset()
    m, cell, config = tiny()
    result = run.measure(m, cell, config, seed=2**31 + 13, seconds=1.0,
                         traced=traced, devices=jax.devices()[:1],
                         t_start=time.monotonic())
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    names = set(result["metrics"])
    # no TPU plane in a CPU trace and no peak for a CPU: the trace's
    # metrics and the mfu are left out of the line
    assert names == ({"glm.step_ms", "glm.load_max_over_mean",
                      "glm.mtp_loss_share"}
                     if traced else {"train_tok_rate", "setup_s"})
    for v in result["metrics"].values():
        assert v["value"] > 0 and UNIT.match(v["unit"])
    if traced:      # lambda L_mtp / L with both losses near log(256)
        assert 20 < result["metrics"]["glm.mtp_loss_share"]["value"] < 26
    json.dumps(result)


def test_two_units_read_both_losses_and_the_bias_follows_the_loads():
    import jax
    import numpy as np

    from benchmark.kinds import counter, glm_trainer

    _, cell, config = tiny()
    c = glm_trainer.Cell(config, cell, 7, jax.devices()[:1])
    assert c.trainer.cfg.moe_layers == (1, 2, 3)     # the block's is last
    before = {n: np.asarray(a) for n, a in c.params.items()
              if n.endswith(".router_bias")}
    assert sorted(before) == ["L1.router_bias", "L2.router_bias",
                              "L3.router_bias"]
    c.warm(1)
    first, second = c.unit(), c.unit()
    for r in (first, second):
        assert r["ok"] and r["work"] == 2 * 128
        assert len(r["loads"]) == 3 and len(r["loads"][0]) == 4
        assert r["pairs_held"] == sum(map(sum, r["loads"]))
        assert 4.0 < r["mtp_loss"] < 7.0 and 4.0 < r["loss"] < 7.0
        assert r["objective"] == pytest.approx(
            r["loss"] + 0.3 * r["mtp_loss"])
    assert first["loads"] != second["loads"]        # a fresh batch a step
    assert counter("mrtpu_moe_dropped_pairs_total") == 0
    assert list(c.faults()) == []
    # three steps of the trainer's rule: every expert's bias moved by
    # -3, -1, 1 or 3 rates (a load on the mean moves it by 0)
    for n, b in before.items():
        moved = np.round((np.asarray(c.params[n]) - b) / 0.001).astype(int)
        assert np.abs(moved).max() <= 3 and np.abs(moved).sum() > 0, n
    assert c.trainer.step_log()[-1]["mtp_loss"] == second["mtp_loss"]
    assert c.gaps["bias"] < 1e-3 and c.gaps["mtp_loss"] < 1e-5


def test_set_up_evens_the_bias_of_every_expert_layer():
    """``moe_trainer.balanced_bias`` on the first batch, the prediction
    block's layer among the layers: the busiest of ALL 16 experts comes
    down, and only the selection biases move."""
    import jax
    import numpy as np

    from benchmark.kinds import glm_trainer
    from benchmark.kinds.trainer import _fold_seed

    _, cell, config = tiny()
    c = glm_trainer.Cell(config, cell, 7, jax.devices()[:1])
    start = c._init(jax.random.key(_fold_seed(7)))

    def spread(p):
        _, stats = c.trainer._loss(p, *c.trainer.place_batch(c.tokens))
        return [np.bincount(of.ravel(), minlength=16).max() / (of.size / 16)
                for of in np.asarray(stats["chosen"])]

    before, after = spread(start), spread(c.params)
    assert len(before) == 3
    assert all(a < b for a, b in zip(after, before)) and max(after) < 1.5
    for n in start:
        same = np.array_equal(np.asarray(start[n]), np.asarray(c.params[n]))
        assert same == (not n.endswith(".router_bias")), n


def test_controls_come_out_not_correct():
    """``glm_controls.py``: 8-bit operands, an unchanged state and each
    wrong model fault, the trainer does not (float32, toy size: the
    limits are the cell's, so only the direction is held here)."""
    import jax

    from benchmark import glm_controls
    from benchmark.kinds import glm_trainer

    _, cell, config = tiny()
    c = glm_trainer.Cell(config, cell, 11, jax.devices()[:1])
    out = glm_controls.controls(c, 11)
    assert set(out) == {"trainer", "unchanged", "float8_e4m3fn", "no_rope",
                        "key_per_head", "no_latent_norms", "no_shared",
                        "scale_1", "no_mtp"}
    assert out["trainer"]["faults"] == []
    assert set(glm_controls.controls(c, 12, full=False)) == {"trainer"}
    assert out["unchanged"]["gaps"]["gradient"] == pytest.approx(1.0)
    assert out["unchanged"]["gaps"]["bias"] > 0.6    # 1 less the loads
    #                                                   on the mean
    assert len(out["unchanged"]["faults"]) >= 4
    for control in set(out) - {"trainer", "unchanged"}:
        assert out[control]["faults"], control
        assert out[control]["gaps"]["gradient"] \
            > 100 * out["trainer"]["gaps"]["gradient"], control
    assert out["scale_1"]["gaps"]["weights"] > 0.1
    # the main model's outputs stand; the block's tensors get nothing
    assert out["no_mtp"]["gaps"]["loss"] < 1e-5
    assert out["no_mtp"]["gaps"]["mtp_loss"] < 1e-5
    assert out["no_mtp"]["gaps"]["gradient"] == pytest.approx(1.0)


def test_derived_reads_the_second_losss_share():
    import jax
    import numpy as np

    from benchmark.kinds import glm_trainer

    _, cell, config = tiny()
    c = glm_trainer.Cell(config, cell, 3, jax.devices()[:1])
    c.loads, c.pairs = np.array([[1, 1, 1, 1], [4, 0, 2, 2]]), [12]
    assert "mtp_loss_share" not in c.derived({}, 1, "cpu")
    c.mtp_loss, c.objective = 5.0, 6.5
    derived = c.derived({"train_tok_rate": 1.0}, 1, "cpu")
    assert derived["mtp_loss_share"] == pytest.approx(100 * 1.5 / 6.5)
    assert derived["load_max_over_mean"] == 2.0 and "mfu" not in derived
