"""The ``moe_trainer`` kind's own tests, beside ``test_benchmark.py``
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
    """The cell and its configuration at toy widths, in float32: the
    limits are set for the cell's 32,768 positions in bfloat16, and at
    256 positions only float32 lies inside them."""
    import jax.numpy as jnp

    from benchmark import run

    m, _, cell, config = run.load_cell("train-lfm2moe-8k")
    config["model"].update(vocab=256, embed=64, n_heads=4, head_dim=16,
                           n_kv_heads=2, ffn=128, loss_block=64,
                           moe_experts=16, moe_held=4, moe_ffn=32,
                           dtype=jnp.float32)
    config["train"].update(batch=2, seq_len=128, reference_block=32)
    return m, cell, config


@pytest.mark.parametrize("traced", [False, True])
def test_kind_loop_yields_the_contracts_object(traced):
    import jax

    from benchmark import run

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
    assert names == ({"moe.step_ms", "moe.load_max_over_mean"} if traced
                     else {"train_tok_rate", "setup_s"})
    for v in result["metrics"].values():
        assert v["value"] > 0 and UNIT.match(v["unit"])
    json.dumps(result)


def test_two_units_read_the_loads_and_drop_nothing():
    import jax

    from benchmark.kinds import counter, moe_trainer

    _, cell, config = tiny()
    c = moe_trainer.Cell(config, cell, 7, jax.devices()[:1])
    c.warm(1)
    first, second = c.unit(), c.unit()
    for r in (first, second):
        assert r["ok"] and r["work"] == 2 * 128
        assert len(r["loads"]) == 4 and len(r["loads"][0]) == 4
        assert r["pairs_held"] == sum(map(sum, r["loads"]))
    assert first["loads"] != second["loads"]        # a fresh batch a step
    assert counter("mrtpu_moe_dropped_pairs_total") == 0
    assert list(c.faults()) == []


def test_set_up_balances_the_selection_bias():
    """The load-driven rule on the first batch: every expert layer's
    busiest expert comes down towards the mean, over all 16 experts, and
    only the bias moves."""
    import jax
    import numpy as np

    from benchmark.kinds import moe_trainer

    _, cell, config = tiny()
    c = moe_trainer.Cell(config, cell, 7, jax.devices()[:1])
    start = c._init(jax.random.key(moe_trainer._fold_seed(7)))

    def spread(p):
        _, stats = c.trainer._loss(p, *c.trainer.place_batch(c.tokens))
        return [np.bincount(of.ravel(), minlength=16).max() / (of.size / 16)
                for of in np.asarray(stats["chosen"])]

    before, after = spread(start), spread(c.params)
    assert all(a < b for a, b in zip(after, before)) and max(after) < 1.5
    for n in start:
        same = np.array_equal(np.asarray(start[n]), np.asarray(c.params[n]))
        assert same == (not n.endswith(c.buffers)), n
