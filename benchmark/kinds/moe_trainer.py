"""Kind ``moe_trainer``: ``TransformerTrainer.step_opt`` of a model with
layers of several kinds and routed experts, of which this chip holds a
share, under AdamW, on a fresh seeded batch each step; the loss, the
pairs held and every held expert's load are read every step.

A unit is one step: dispatch, then — while the device runs it — the next
step's batch is drawn on the host, then the step's expert loads and its
loss are read, which closes the step's clock.

Before the window, step 0 of the timed program on the cell's own first
batch, at the timed sizes, is held against the float32 reference
(``benchmark/reference_lfm2moe.py``, given the same share of the
experts) on the same weights, forward AND backward AND update AND
routing.  The routing first: the share of every token's chosen experts
that the reference, on the same layer input, chose too, in each expert
layer, and the pairs that landed on the held experts.  Then, with the
reference GIVEN the step's choices (a fourth choice that flips between
two experts of near-equal score moves a pair's gradient from one
expert's tensors to another's, which is rounding and is counted under
``routing``, not again in every gradient): the loss; the weights of the
choices; the gradient of every trained tensor, read from AdamW's first
moment after the step (``mu = (1 - b1) g`` exactly, from zero); what the
step added to every tensor against what AdamW's first step, written
out, makes of the reference's gradient, and of the trainer's own.  A
state the step leaves unchanged reads 1 in the gradient and in both
updates.
"""

from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, flops_moe, reference_lfm2moe
from benchmark.kinds import counter, kernel_faults
from benchmark.kinds.trainer import _fold_seed, end_to_end  # noqa: F401
from benchmark.reference_looplm import adamw_first_step

#: Limits of the step-0 comparison, by the name ``Cell.gaps`` gives each
#: number.  Each lies between two readings on the v5e at the cell's
#: sizes, all of the cell as it is set up (the bias balanced) and with
#: the reference given the choices of what it is compared with (PERF.md
#: section 6, PR 33, calls 9 to 12): the worst the trainer (bfloat16
#: operands, float32 accumulation, the router's product in float32) gave
#: over 26 seeds, and what ``benchmark/moe_controls.py`` puts in its
#: place on two seeds: the reference with every product's operands
#: rounded to 8 bits, a wrong rule, half the batch, an unchanged state,
#: each of which comes out as not correct.
LIMITS = {
    # |loss - reference| / reference: a mean over 32,768 positions of a
    # loss near log(8192), so rounding mostly averages out, 8-bit
    # rounding nearly as well: the least telling of the seven, held by
    # the trainer's own scatter more than by the control.  Trainer
    # 1.0e-7 to 2.2e-5 (r.m.s. 1.0e-5: the limit is four of them); 8-bit
    # operands 5.3e-5 and 2.0e-4
    "loss": 4e-5,
    # |g - reference gradient| / |reference gradient| (2-norms), the
    # worst trained tensor; g is AdamW's first moment after step 0 over
    # 1 - b1.  Trainer 0.079 to 0.107, a router of the later expert
    # layers (the first expert layer's tensors 0.03, as every tensor
    # outside the expert layers; against a reference left to its own
    # choices the same routers read 0.31 to 0.35, the 2% of flipped
    # choices each moving a pair's gradient to another expert); 8-bit
    # operands 0.56 and 0.57; half the batch 1.06 and 1.14; a state left
    # unchanged 1
    "gradient": 0.2,
    # |what step 0 added - AdamW's first step of the REFERENCE gradient|
    # / |that step|, the worst tensor: the first step is a sign, so
    # every element under the rounding flips and counts 2 (tenths, as in
    # kinds/looped_trainer.py).  Trainer 0.29 to 0.35 (0.41 once, with
    # the compiler's grouped products); 8-bit operands 0.87 and 0.88;
    # half the batch 1.11 and 1.15; unchanged 1
    "update": 0.6,
    # the same against AdamW's first step of the trainer's OWN gradient:
    # the update rule alone, to float32 rounding.  Trainer 9.7e-5 to
    # 1.2e-4; unchanged 1
    "update_rule": 1e-2,
    # 1 - the share of (token, chosen expert) pairs that the reference
    # chose for that token too, on the same layer input, the worst
    # expert layer.  A flipped choice near a tie is rounding (the
    # layer's input is bfloat16); a different selection rule is not.
    # Trainer 0.0198 to 0.0213; 8-bit operands 0.195 and 0.197; softmax
    # scores 0.63 (read against a reference left to its own choices)
    "routing": 0.07,
    # mean |g - reference's g| over every token's four choices, the
    # worst expert layer: the WEIGHTS' rule (sigmoid scores, the bias
    # only selects, normalised over all four chosen).  The balanced bias
    # is small, so weights from score + bias lie close and only this
    # number tells them (their gradient reads 0.03 to 0.05).  Trainer
    # 4.5e-4 to 4.7e-4 on every seed; weights from score + bias 1.35e-3
    # and 1.54e-3; 8-bit operands 5.4e-3
    "weights": 8e-4,
    # |pairs held - reference's| / reference's, the worst expert layer:
    # flipped choices in less flipped choices out, of some 16,400 pairs,
    # so it is the flips' counting noise (a standard deviation of 1.6e-3
    # a layer at 2% flipped; the limit is under four of them) and goes
    # by the seed.  Trainer 4.3e-4 to 3.7e-3; 8-bit operands 7.9e-3 and
    # 1.3e-2 (5 times the noise, and not over the limit on every seed:
    # they fail by four other limits); softmax scores 1.1
    "pairs_held": 6e-3,
}


def routing_gaps(got: tuple, want: tuple) -> tuple:
    """``(1 - share of chosen experts in common, mean |weight gap|,
    relative gap of the pairs held)``, each the worst expert layer's;
    *got* and *want* are ``(chosen [n_moe, B, T, k], weights [n_moe, B,
    T, k], loads [n_moe, held])``, *want* the reference's given *got*'s
    choices: its weights are theirs slot by slot, its choices and loads
    its own."""
    (got_c, got_w, got_l), (want_c, want_w, want_l) = (
        tuple(np.asarray(a) for a in side) for side in (got, want))
    common = (got_c[..., :, None] == want_c[..., None, :]).any(-1)
    held, want_held = (np.asarray(l, np.float64).sum(axis=1)
                       for l in (got_l, want_l))
    return (float(1.0 - common.mean(axis=(1, 2, 3)).min()),
            float(np.abs(got_w - want_w).mean(axis=(1, 2, 3)).max()),
            float(np.max(np.abs(held - want_held)
                         / np.maximum(want_held, 1.0))))


def balanced_bias(trainer, params: dict, tokens: np.ndarray) -> dict:
    """*params* with every routed layer's selection bias moved against
    its experts' loads on *tokens* ``[B, T+1]``: the load-driven rule of
    bias-balanced routing, ``b_e += rate_t * clip((mean load - load_e) /
    mean load, -1, 1)`` over ALL the router's experts (held here or not:
    what lands here depends on every expert's bias), one forward pass a
    round.  The cell's own, for its set-up: the trainer holds the bias
    fixed (``BUFFERS``) and has no rule that moves it (ROADMAP B3)."""
    rounds, rate, decay = 30, 0.1, 0.85     # not swept: PERF.md section 7
    cfg = trainer.cfg
    x, y = trainer.place_batch(tokens)
    params = dict(params)
    for t in range(rounds):
        _, stats = trainer._loss(params, x, y)       # the forward alone
        chosen = np.asarray(stats["chosen"])         # [layers, B, T, k]
        for layer, of_layer in zip(cfg.moe_layers, chosen):
            load = np.bincount(of_layer.ravel(), minlength=cfg.moe_experts)
            mean = of_layer.size / cfg.moe_experts
            step = rate * decay ** t * np.clip((mean - load) / mean, -1, 1)
            name = f"L{layer}.router_bias"
            params[name] = jax.device_put(
                np.asarray(params[name]) + step.astype(np.float32),
                params[name].sharding)
    return params


class Cell:
    SPANS = ("train.step", "train.batch")
    WINDOW_SPAN = "train.step"

    def __init__(self, config: dict, cell: dict, seed: int, devices) -> None:
        import optax

        from mapreduce_tpu.models.transformer import (BUFFERS,
                                                      TransformerConfig,
                                                      TransformerTrainer)
        from mapreduce_tpu.parallel import make_mesh

        self.config = config
        self.model, self.train = config["model"], config["train"]
        if config.get("routed_scaling_factor", 1) != 1:
            raise ValueError("the routed layer has no scaling factor: "
                             "routed_scaling_factor must be 1")
        self.kernels = list(config["program"]["kernels"])
        self.on_tpu = devices[0].platform == "tpu"
        self.B, self.T = int(self.train["batch"]), int(self.train["seq_len"])
        self.adamw = {k: float(self.train[k]) for k in (
            "learning_rate", "b1", "b2", "eps", "weight_decay")}
        self.trainer = TransformerTrainer(
            make_mesh(devices=devices), TransformerConfig(**self.model),
            optimizer=optax.adamw(**self.adamw))
        cfg = self.trainer.cfg
        self.held = (cfg.moe_held_offset, cfg.experts_held)
        self.buffers = BUFFERS
        # weights made on the device; the seed is the program's ARGUMENT,
        # so one compiled program serves every seed
        self._init = jax.jit(self.trainer.init_params)
        self._init_opt = jax.jit(self.trainer.init_opt_state)
        self._references = {}         # reference()'s programs
        self.reseed(seed)

        def norms(want_g, moment, old, new):
            """Squared 2-norms behind the three gaps of one tensor."""
            g = moment / (1.0 - self.adamw["b1"])
            moved = new - old
            by_reference = adamw_first_step(old, want_g, **self.adamw)
            by_rule = adamw_first_step(old, g, **self.adamw)
            sq = lambda a: jnp.sum(jnp.square(a.astype(jnp.float32)))
            return jnp.stack([sq(g - want_g), sq(moved - by_reference),
                              sq(moved - by_rule), sq(want_g),
                              sq(by_reference), sq(by_rule)])

        self._norms = jax.jit(norms)

    def reseed(self, seed: int) -> None:
        """Weights, first batch and a fresh optimizer state of *seed*,
        as a new cell's (``moe_controls.py`` reads several seeds through
        one cell's compiled programs)."""
        self.params = self._init(jax.random.key(_fold_seed(seed)))
        self.rng = np.random.default_rng(seed)
        self.tokens = self._batch()
        # the selection bias as a deployment's: moved against the
        # experts' loads (on the first batch), then held fixed.  Left at
        # its random start the pairs landing here, and with them the
        # step's time, go by the seed (PERF.md section 6, PR 33)
        self.params = balanced_bias(self.trainer, self.params, self.tokens)
        self.opt_state = self._init_opt(self.params)
        self.stats = None             # the last step's, on the device
        self.gaps = None              # step 0 against the reference
        self.worst_tensors = None     # of gradient, update, update_rule
        self.by_tensor = None         # those three gaps of every tensor
        self.buffers_moved = None     # names of buffers step 0 changed
        self.pairs = []               # pairs held, every step of the run
        self.loads = None             # the last step's, [layers, held]

    def _batch(self) -> np.ndarray:
        return self.rng.integers(0, self.model["vocab"],
                                 size=(self.B, self.T + 1), dtype=np.int32)

    def reference(self, params: dict, tokens: np.ndarray, given=None,
                  operand_dtype=None, rule: str = "published") -> tuple:
        """``((loss, chosen, weights, loads), gradients)`` of the float32
        reference on *params* and the batch *tokens* ``[B, T+1]``, given
        this chip's share of the experts and, with *given*, a step's
        choices; on the host."""
        m = self.model
        key = (given is None, operand_dtype, rule)
        if key not in self._references:
            self._references[key] = jax.jit(
                lambda p, x, y, given:
                reference_lfm2moe.reference_gradients(
                    p, x, y, layer_ops=tuple(m["layer_ops"]),
                    layer_ffns=tuple(m["layer_ffns"]), n_heads=m["n_heads"],
                    n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
                    rope_theta=float(m["rope_theta"]),
                    eps=float(m["norm_eps"]), top_k=m["moe_top_k"],
                    held=self.held, block=int(self.train["reference_block"]),
                    operand_dtype=operand_dtype, rule=rule, given=given))
        return jax.device_get(self._references[key](
            params, tokens[:, :-1], tokens[:, 1:], given))

    def gaps_to(self, want: tuple, got: tuple, old: dict, moment: dict,
                new: dict) -> dict:
        """The numbers ``LIMITS`` bounds: *want* is :meth:`reference`'s
        given *got*'s choices, *got* a step's ``(loss, chosen, weights,
        loads)``, *old* and *new* the parameters before and after it,
        *moment* AdamW's first moment after it."""
        (loss, *routed), grads = want
        names = [n for n in old if not n.endswith(self.buffers)]
        # one tensor at a time, each read back before the next is sent
        # (kinds/looped_trainer.py)
        norms = np.array([np.asarray(self._norms(
            grads[n], moment[n], old[n], new[n])) for n in names],
            dtype=np.float64)
        by_tensor = np.sqrt(norms[:, :3] / norms[:, 3:])      # [names, 3]
        self.by_tensor = {n: row.tolist() for n, row in zip(names,
                                                            by_tensor)}
        worst = by_tensor.max(axis=0)           # a NaN stays a NaN
        self.worst_tensors = [names[i] for i in np.argmax(
            np.nan_to_num(by_tensor, nan=np.inf), axis=0)]
        self.buffers_moved = [
            n for n in old if n.endswith(self.buffers)
            and not np.array_equal(np.asarray(old[n]), np.asarray(new[n]))]
        routing, weights, pairs = routing_gaps(got[1:], routed)
        return {"loss": float(abs(got[0] - loss) / abs(loss)),
                "gradient": float(worst[0]), "update": float(worst[1]),
                "update_rule": float(worst[2]), "routing": routing,
                "weights": weights, "pairs_held": pairs}

    def warm(self, units: int) -> None:
        """*units* steps; the first is held against the reference given
        its choices."""
        old, first = jax.device_get(self.params), self.tokens
        r = self.unit()
        got = self.step_outputs(r)
        # the reference's gradient needs the room: the training state
        # waits on the host meanwhile
        state = (self.params, self.opt_state)
        placed = jax.tree.map(lambda a: a.sharding, state)
        state = jax.device_get(state)
        self.params = self.opt_state = None
        want = self.reference(old, first, given=got[1])
        self.gaps = self.gaps_to(want, got, old, state[1][0].mu, state[0])
        print(f"# step-0 loss {r['loss']:.6f}, float32 reference "
              f"{float(want[0][0]):.6f}; loads {r['loads']} against "
              f"{want[0][3].tolist()}; gaps {self.gaps}; worst tensors "
              f"{self.worst_tensors}", file=sys.stderr, flush=True)
        del want, old
        self.params, self.opt_state = jax.device_put(state, placed)
        del state
        for _ in range(units - 1):
            self.unit()

    def step_outputs(self, record: dict) -> tuple:
        """``(loss, chosen, weights, loads)`` of the step *record* is
        :meth:`unit`'s of, while its statistics are still the last."""
        return (record["loss"], *jax.device_get(
            (self.stats["chosen"], self.stats["weights"])), record["loads"])

    def unit(self) -> dict:
        from mapreduce_tpu.models.moe import STAT_DROPPED

        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("train.step"):
            (self.params, self.opt_state, loss,
             self.stats) = self.trainer.step_opt(
                self.params, self.opt_state, self.tokens)
            with jax.profiler.TraceAnnotation("train.batch"):
                self.tokens = self._batch()   # the device is running
            stats = self.trainer.observe_experts(self.stats)  # closes it
            loss = float(loss)
        seconds = time.monotonic() - t0
        self.loads = stats[:, :STAT_DROPPED]
        self.pairs.append(int(self.loads.sum()))
        return {"ok": math.isfinite(loss), "seconds": seconds,
                "work": self.B * self.T, "loss": loss,
                "pairs_held": self.pairs[-1], "loads": self.loads.tolist()}

    def faults(self):
        for name, limit in LIMITS.items():
            gap = None if self.gaps is None else self.gaps[name]
            if gap is None or not gap <= limit:
                yield (f"step-0 {name} is {gap} from the float32 "
                       f"reference, over {limit}")
        if self.buffers_moved:
            yield f"step 0 moved the buffers {self.buffers_moved}"
        dropped = counter("mrtpu_moe_dropped_pairs_total")
        if dropped:
            yield f"{dropped:.0f} routed pairs were dropped"
        if self.on_tpu:      # off the TPU the trainer calls no kernel
            yield from kernel_faults(self.kernels, "mosaic")

    def derived(self, values: dict, n_chips: int, device_kind: str) -> dict:
        """``mfu``: required operations per second over the chips' peak
        (``flops_moe``), the experts' term from the pairs that really
        landed here, the mean over the run's steps.
        ``load_max_over_mean``: the busiest held expert's pairs over the
        held experts' mean, the worst expert layer's, at the run's LAST
        step (set-up balanced the loads on the first batch)."""
        peak = flops.peak_flops(device_kind)
        if peak is None and self.on_tpu:
            raise KeyError(f"peaks.json has no device kind {device_kind!r}")
        out = {"load_max_over_mean": float(np.max(
            self.loads.max(axis=1) / np.maximum(self.loads.mean(axis=1),
                                                1e-9)))}
        if peak is not None and "train_tok_rate" in values:
            per_token = (flops_moe.train_step_flops(
                self.model, self.B, self.T, float(np.mean(self.pairs)))
                / (self.B * self.T))
            out["mfu"] = (100.0 * values["train_tok_rate"] * per_token
                          / (n_chips * peak))
        return out
