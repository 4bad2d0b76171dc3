"""The ``mellum_trainer`` kind's own tests, beside ``test_benchmark.py``
(which checks every manifest and data file, the new ones included): the
kind's loop at a tiny size on the CPU, through ``run.measure`` as the
command drives it.

    python -m pytest benchmark/tests -q
"""

import json
import time

import pytest

from benchmark.tests.test_benchmark import RESULT_KEYS, UNIT


def tiny():
    """The cell and its configuration at toy widths, in float32: two
    periods of the layer pattern, a window of 24 positions under 128,
    YaRN over 32 original positions, 8 experts top-2 of which 4 are
    held.  The limits are set for the cell's 24,576 positions in
    bfloat16; at 256 positions only float32 lies inside them."""
    import jax.numpy as jnp

    from benchmark import run

    m, _, cell, config = run.load_cell("train-mellum2-long")
    config["model"].update(
        vocab=256, embed=64, n_layers=8, n_heads=4, head_dim=16,
        n_kv_heads=2, ffn=128, loss_block=64, attn_block=32,
        layer_ops=["window", "window", "window", "attn"] * 2,
        layer_ffns=["moe"] * 8, attn_window=24, yarn_original_positions=32,
        moe_experts=8, moe_top_k=2, moe_held=4, moe_ffn=32,
        dtype=jnp.float32)
    config["train"].update(batch=2, seq_len=128, reference_block=32)
    return m, cell, config


@pytest.mark.parametrize("traced", [False, True])
def test_kind_loop_yields_the_contracts_object(traced):
    import jax

    from benchmark import run
    from mapreduce_tpu.obs.metrics import REGISTRY

    REGISTRY.reset()       # a flash grid traced earlier in the process
    m, cell, config = tiny()
    result = run.measure(m, cell, config, seed=2**31 + 13, seconds=1.0,
                         traced=traced, devices=jax.devices()[:1],
                         t_start=time.monotonic())
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    names = set(result["metrics"])
    # no TPU plane in a CPU trace, no peak for a CPU, and off the TPU
    # the trainer's auto choice is the jnp path, which traces no flash
    # grid: the trace's metrics, the mfu and the tiles' ratio are left
    # out of the line
    assert names == ({"mellum.step_ms", "mellum.load_max_over_mean"}
                     if traced else {"train_tok_rate", "setup_s"})
    for v in result["metrics"].values():
        assert v["value"] > 0 and UNIT.match(v["unit"])
    json.dumps(result)


def test_two_units_read_the_loads_and_drop_nothing():
    import jax

    from benchmark.kinds import counter, mellum_trainer

    _, cell, config = tiny()
    c = mellum_trainer.Cell(config, cell, 7, jax.devices()[:1])
    c.warm(1)
    first, second = c.unit(), c.unit()
    for r in (first, second):
        assert r["ok"] and r["work"] == 2 * 128
        assert len(r["loads"]) == 8 and len(r["loads"][0]) == 4
        assert r["pairs_held"] == sum(map(sum, r["loads"]))
    assert first["loads"] != second["loads"]        # a fresh batch a step
    assert counter("mrtpu_moe_dropped_pairs_total") == 0
    assert list(c.faults()) == []


def test_set_up_evens_the_routers_and_moves_nothing_else():
    """A virtual bias along each layer input's mean direction, moved by
    the loads of the first batch, layer by layer: every layer's busiest
    expert comes down to the mean, over all 8 experts, in the trainer's
    own routing, and only the routers move."""
    import jax
    import numpy as np

    from benchmark.kinds import mellum_trainer

    _, cell, config = tiny()
    c = mellum_trainer.Cell(config, cell, 7, jax.devices()[:1])
    start = c._init(jax.random.key(mellum_trainer._fold_seed(7)))

    def spread(p):
        _, stats = c.trainer._loss(p, *c.trainer.place_batch(c.tokens))
        return [np.bincount(of.ravel(), minlength=8).max() / (of.size / 8)
                for of in np.asarray(stats["chosen"])]

    before, after = spread(start), spread(c.params)
    assert all(a < b for a, b in zip(after, before)) and max(after) < 1.1
    assert c.spreads.shape == (8, 2)
    assert (c.spreads[:, 1] <= 1.02).all()      # balanced_routers' within
    # a later layer's start is read behind routers already moved
    np.testing.assert_allclose(c.spreads[0, 0], before[0], rtol=1e-6)
    for n in start:
        same = np.array_equal(np.asarray(start[n]), np.asarray(c.params[n]))
        assert same == (not n.endswith(".w_router")), n


def test_derived_reads_the_tiles_ratio_from_the_programs_gauge():
    """47 of a 24K head's 300 needed tiles: the gauge the flash kernels
    set where their tables are made; nothing where it was never set."""
    import jax
    import numpy as np

    from benchmark.kinds import mellum_trainer
    from mapreduce_tpu.obs.metrics import REGISTRY
    from mapreduce_tpu.ops import flash_attention as fa

    _, cell, config = tiny()
    c = mellum_trainer.Cell(config, cell, 3, jax.devices()[:1])
    c.loads, c.pairs = np.ones((8, 4)), [32]
    REGISTRY.reset()
    assert "window_tiles_of_full" not in c.derived({}, 1, "cpu")
    fa._count_steps("flash_fwd", fa._fwd_tables(24, 24, 1024, 1024, True,
                                                None)[0][-1])
    fa._count_steps("flash_fwd_win", fa._fwd_tables(24, 24, 1024, 1024, True,
                                                    1024)[0][-1])
    assert c.derived({}, 1, "cpu")["window_tiles_of_full"] == 47 / 300
