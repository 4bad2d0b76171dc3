"""Kind ``mellum_trainer``: ``TransformerTrainer.step_opt`` of a model
with sliding-window attention layers beside full ones (YaRN rotary on the
full ones) and softmax-routed experts, of which this chip holds a share,
under AdamW, on a fresh seeded batch each step; the loss, the pairs held
and every held expert's load are read every step.

The loop is ``kinds/moe_trainer.py``'s, unit for unit (dispatch, the next
batch drawn while the device runs, loads and loss read), and so is the
form of the step-0 comparison: step 0 of the timed program on the cell's
own first batch, at the timed sizes, against the float32 reference
(``benchmark/reference_mellum2.py``, given the same share of the experts)
on the same weights — the routing first, then, with the reference GIVEN
the step's choices, the loss, the choices' weights, every tensor's
gradient from AdamW's first moment and every tensor's update.  What is
this kind's own: the reference and what it is told of the model, the
limits, the set-up's load rule (the model has no selection bias: the
routers themselves are evened, :func:`balanced_routers`), and the derived
metrics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, flops_mellum2, reference_mellum2
from benchmark.kinds import counter, kernel_faults, moe_trainer
from benchmark.kinds.moe_trainer import end_to_end  # noqa: F401
from benchmark.kinds.trainer import _fold_seed
from benchmark.reference_looplm import _rmsnorm

#: Limits of the step-0 comparison, by the name ``Cell.gaps`` gives each
#: number (``kinds/moe_trainer.py`` says what each is).  Each lies
#: between two readings on the v5e at the cell's sizes, all with the
#: reference GIVEN the choices of what it is compared with (PERF.md
#: section 6, PR 35): the worst the trainer (bfloat16 operands, float32
#: accumulation, the router's product and softmax in float32) gave over
#: 47 seeds, and what ``benchmark/mellum_controls.py`` puts in its
#: place on two, at the configuration as committed (seeds 3500000857 and
#: 3500000863): the reference with every product's operands rounded to 8
#: bits, the window left out, plain rotary on the full layer, a sigmoid
#: for the softmax, the gradient of half the sequence, an unchanged
#: state, each of which comes out as not correct.
LIMITS = {
    # |loss - reference| / reference, a mean over 24,576 positions of a
    # loss near log(12288).  Trainer 9.6e-8 to 1.2e-5 over 47 seeds; no
    # window 8.6e-4 and 1.0e-3, plain rotary 1.2e-4 and 9.2e-5.  The
    # mean averages rounding away: 8-bit operands read 7.5e-6 (passes)
    # and 1.7e-4, the sigmoid router 1.6e-5 and 2.9e-6 (passes; it
    # changes no choice of the first layer); both fail by four others
    "loss": 4e-5,
    # the worst trained tensor's |g - reference gradient| / |reference
    # gradient|, g from AdamW's first moment: a later layer's router.
    # Trainer 0.045 to 0.095; 8-bit operands 1.08 and 1.15, no window
    # 2.03 and 1.79, plain rotary 0.89 and 0.91, sigmoid 0.89 and 0.85,
    # half the sequence 1.26 and 1.17, unchanged 1
    "gradient": 0.2,
    # the worst tensor's |what step 0 added - AdamW's first step of the
    # REFERENCE gradient| / |that step|: the first step is a sign, an
    # element under the rounding flips and counts 2.  Trainer 0.28 to
    # 0.40; the controls 0.86 (sigmoid) to 1.41 (no window)
    "update": 0.6,
    # the same against AdamW's first step of the trainer's OWN gradient:
    # float32's resolution of a step of 3e-4.  Trainer 1.0e-4 to
    # 1.1e-4, and so every control that puts its own gradient's step in
    # place; unchanged 1
    "update_rule": 1e-2,
    # 1 - the share of (token, chosen expert) pairs the reference chose
    # too on the same layer input, the worst layer.  Trainer 0.0080 to
    # 0.0101; sigmoid 0.068 and 0.073 (both rules are monotone in the
    # logit, so only its later layers' inputs differ), plain rotary
    # 0.215, 8-bit operands 0.27 and 0.29, no window 0.84
    "routing": 0.03,
    # mean |g - reference's g| over every token's eight choices, the
    # worst layer: the weights are near 1/8, the layer's input is
    # bfloat16.  Trainer 6.2e-4 to 7.2e-4; plain rotary 0.019 and 0.021,
    # 8-bit operands 0.027, sigmoid 0.037, no window 0.073
    "weights": 3e-3,
    # |pairs held - reference's| / reference's, the worst layer, of some
    # 24,600 pairs.  Trainer 7.3e-4 to 7.6e-3 (not counting noise alone:
    # 188 pairs fewer on one seed, two of the layer's experts 44 and 76
    # under the reference's loads); 8-bit operands 0.033 and 0.25,
    # plain rotary 0.067 and 0.12, no window 0.50 and 1.27; the sigmoid
    # router 0.010 and 0.013 (passes)
    "pairs_held": 1.5e-2,
}


def balanced_routers(cell, params: dict, tokens: np.ndarray) -> tuple:
    """``(params, spreads)``: *params* with every layer's router weights
    moved until the layer's experts share the pairs of *tokens* ``[B,
    T+1]`` evenly, and each layer's busiest expert over the mean of ALL
    the router's experts, before and after.  The model has no selection
    bias, so the router gets the equivalent of one: with ``m`` the mean
    of the layer's input ``h`` over the batch's tokens and ``u = m / |m|^2``
    (``u . h`` is 1 on average), ``W_r += u c^T`` adds ``c_e`` to expert
    e's logit for every token alike, and ``c`` follows the loads:
    ``c_e -= rate * log(load_e / mean load)`` a round, for *rounds*
    rounds at most, until the busiest expert is within *within* of the
    mean.  Layer by layer, on the layer's input as the float32 reference
    computes it from the layers before (their routers already moved).
    64 numbers a layer cannot fit a batch: the loads they even on the
    first batch stay even on the next (descending the load-balancing
    loss's gradient with all 147,456 weights of a router evened the
    first batch alone: the next batches landed 0.87 to 1.2 times the
    pairs here, by the seed; PERF.md section 6).  The cell's own, for
    its set-up: a trained router is balanced, a random one is not, and
    the trainer has no such rule (ROADMAP B3)."""
    rounds, rate, within = 100, 0.25, 1.02   # not swept: PERF.md section 7
    X, k = cell.model["moe_experts"], cell.model["moe_top_k"]
    ref = reference_mellum2.Model(**cell.told)

    def shares(logits):
        """Every expert's share of the pairs, over the mean share."""
        _, chosen = jax.lax.top_k(logits, k)
        return jnp.zeros((X,), jnp.float32).at[chosen.reshape(-1)].add(
            X / chosen.size)

    def even(h, w):
        mean = h.mean(axis=0)
        u = mean / (mean @ mean)
        logits, along = h @ w, h @ u

        def step(state):
            c, n, f = state
            c = c - rate * jnp.log(jnp.maximum(f, 1e-3))
            return c, n + 1, shares(logits + along[:, None] * c)

        start = shares(logits)
        c, _, end = jax.lax.while_loop(
            lambda s: (s[1] < rounds) & (s[2].max() > within), step,
            (jnp.zeros((X,), jnp.float32), 0, start))
        return w + u[:, None] * c, jnp.stack([start.max(), end.max()])

    def run(p, tok):
        with jax.default_matmul_precision("highest"):
            x = p["embed"][tok]                                 # [B, T, E]
            routers, spreads = {}, []
            for i, kind in enumerate(ref.layer_types):
                lp = reference_mellum2.layer_params(p, i)
                of_kind = ref.kind(kind, x.shape[1])
                x = jax.lax.map(lambda xs: ref.attend(lp, xs, of_kind), x)
                h = _rmsnorm(x, lp["ln2_scale"], ref.eps)
                lp["w_router"], spread = even(h.reshape(-1, h.shape[-1]),
                                              lp["w_router"])
                x = jax.lax.map(lambda xs: ref.route(lp, xs)[0], x)
                routers[f"L{i}.w_router"] = lp["w_router"]
                spreads.append(spread)
        return routers, jnp.stack(spreads)

    if cell._balance is None:
        cell._balance = jax.jit(run)
    routers, spreads = cell._balance(params, tokens[:, :-1])
    return ({n: jax.device_put(routers[n], a.sharding) if n in routers
             else a for n, a in params.items()}, np.asarray(spreads))


class Cell(moe_trainer.Cell):

    def __init__(self, config: dict, cell: dict, seed: int, devices) -> None:
        m, t = config["model"], config["train"]
        kinds = {"attn": "full_attention", "window": "sliding_attention"}
        #: what the reference is told of the model (its ``Model``)
        self.told = dict(
            layer_types=tuple(kinds[op] for op in m["layer_ops"]),
            n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
            head_dim=m["head_dim"], eps=float(m["norm_eps"]),
            rope_theta=float(m["rope_theta"]), window=m["attn_window"],
            yarn={"factor": m["yarn_factor"],
                  "original_max_position_embeddings":
                      m["yarn_original_positions"],
                  "beta_fast": m["yarn_beta_fast"],
                  "beta_slow": m["yarn_beta_slow"],
                  "attention_factor": m["yarn_attention_factor"]},
            top_k=m["moe_top_k"], score=m["moe_router_score"],
            held=(m["moe_held_offset"], m["moe_held"]),
            block=int(t["reference_block"]))
        self._balance = None          # balanced_routers' program
        self.spreads = None           # its busiest-over-mean, [layers, 2]
        super().__init__(config, cell, seed, devices)

    def reseed(self, seed: int) -> None:
        """Weights, first batch and a fresh optimizer state of *seed*,
        as a new cell's; the routers as a deployment's: evened on the
        first batch (left at their random start the pairs landing here,
        and with them the step's time, go by the seed)."""
        self.params = self._init(jax.random.key(_fold_seed(seed)))
        self.rng = np.random.default_rng(seed)
        self.tokens = self._batch()
        self.params, self.spreads = balanced_routers(self, self.params,
                                                     self.tokens)
        self.opt_state = self._init_opt(self.params)
        self.stats = self.gaps = self.worst_tensors = None
        self.by_tensor = self.buffers_moved = self.loads = None
        self.pairs = []

    def reference(self, params: dict, tokens: np.ndarray, given=None,
                  operand_dtype=None, **wrong) -> tuple:
        """``((loss, chosen, weights, loads), gradients)`` of the float32
        reference on *params* and the batch *tokens* ``[B, T+1]``, given
        this chip's share of the experts and, with *given*, a step's
        choices; a layer at a time, so that what it reserves on the
        device stays under what the timed step does.  *wrong* describes
        another model than the configuration's (``window=None``,
        ``yarn=None``, ``score="sigmoid"``): the controls'."""
        key = (operand_dtype, repr(sorted(wrong.items())))
        if key not in self._references:
            self._references[key] = reference_mellum2.gradient_programs(
                **dict(self.told, **wrong), operand_dtype=operand_dtype)
        return jax.device_get(self._references[key](
            params, tokens[:, :-1], tokens[:, 1:], given))

    def faults(self):
        for name, limit in LIMITS.items():
            gap = None if self.gaps is None else self.gaps[name]
            if gap is None or not gap <= limit:
                yield (f"step-0 {name} is {gap} from the float32 "
                       f"reference, over {limit}")
        dropped = counter("mrtpu_moe_dropped_pairs_total")
        if dropped:
            yield f"{dropped:.0f} routed pairs were dropped"
        if self.on_tpu:      # off the TPU the trainer calls no kernel
            yield from kernel_faults(self.kernels, "mosaic")

    def derived(self, values: dict, n_chips: int, device_kind: str) -> dict:
        """``mfu``: required operations per second over the chips' peak
        (``flops_mellum2``), the experts' term from the pairs that
        really landed here, the mean over the run's steps.
        ``load_max_over_mean``: as ``kinds/moe_trainer.py``'s.
        ``window_tiles_of_full``: the needed tiles of a windowed forward
        call over a full one's, from the program's gauge of the grids it
        traced; left out where the program has no such gauge."""
        peak = flops.peak_flops(device_kind)
        if peak is None and self.on_tpu:
            raise KeyError(f"peaks.json has no device kind {device_kind!r}")
        out = {"load_max_over_mean": float(np.max(
            self.loads.max(axis=1) / np.maximum(self.loads.mean(axis=1),
                                                1e-9)))}
        needed = [counter("mrtpu_flash_grid_steps", kernel=kernel,
                          kind="needed")
                  for kernel in ("flash_fwd_win", "flash_fwd")]
        if all(needed):
            out["window_tiles_of_full"] = needed[0] / needed[1]
        if peak is not None and "train_tok_rate" in values:
            per_token = (flops_mellum2.train_step_flops(
                self.model, self.B, self.T, float(np.mean(self.pairs)))
                / (self.B * self.T))
            out["mfu"] = (100.0 * values["train_tok_rate"] * per_token
                          / (n_chips * peak))
        return out
