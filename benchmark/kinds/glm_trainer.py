"""Kind ``glm_trainer``: ``TransformerTrainer.step_opt`` of a model with
latent attention (MLA), a shared expert beside sigmoid-routed ones of
which this chip holds a share, a selection bias that follows the loads
and a multi-token-prediction block, under AdamW, on a fresh seeded batch
each step; both losses, the pairs held and every held expert's load are
read every step.

The loop is ``kinds/moe_trainer.py``'s, unit for unit (dispatch, the next
batch drawn while the device runs, loads and objective read), and so is
the form of the step-0 comparison: step 0 of the timed program on the
cell's own first batch, at the timed sizes, against the float32
reference (``benchmark/reference_glm47.py``, given the same share of the
experts) on the same weights — the routing first, then, with the
reference GIVEN the step's choices, the main loss, the choices' weights,
every tensor's gradient from AdamW's first moment and every tensor's
update.  What is this kind's own: the reference and what it is told of
the model, the limits, two more numbers of the comparison (the
prediction block's loss; where the selection bias stands after the step
against where the rule, written out, moves it from the step's choices),
and the derived metrics.  Set-up evens the selection bias on the first batch with
``moe_trainer.balanced_bias`` as LFM2's cell does; from then on the
TRAINER's rule moves it (``moe_bias_rate``).
"""

from __future__ import annotations

import sys

import jax
import numpy as np

from benchmark import flops, flops_glm47, reference_glm47
from benchmark.kinds import counter, kernel_faults, moe_trainer
from benchmark.kinds.moe_trainer import end_to_end  # noqa: F401

#: Limits of the step-0 comparison, by the name ``Cell.gaps`` gives each
#: number (``kinds/moe_trainer.py`` says what the first seven are).  Each
#: lies between two readings on the v5e at the cell's sizes, all with
#: the reference GIVEN the choices of what it is compared with (PERF.md
#: section 6, PR 39, calls 1 to 4): the worst the trainer (bfloat16
#: operands, float32 accumulation, the router's product in float32) gave
#: over 21 seeds, and what ``benchmark/glm_controls.py`` puts in its
#: place on four (the readings below are of 3900000201 and 3900000203;
#: 3900000343 and 3900000347 read alike, PERF.md): the reference with every
#: product's operands rounded to 8 bits, rotary left off, a rotary key a
#: head, the latent norms left out, the shared expert left out, the
#: routed weights unscaled, the second loss out of the objective, an
#: unchanged state, each of which comes out as not correct.
LIMITS = {
    # |main loss - reference| / reference, a mean over 8,192 positions
    # of a loss near log(19360): a quarter of LFM2's positions, twice its
    # scatter.  Trainer 7.4e-7 to 6.4e-5 (r.m.s. 2.5e-5: the limit is
    # four of them); the wrong models 1.1e-4 to 1.8e-3 but for one seed
    # each of no latent norms (7.3e-5) and no shared expert (6.4e-5), and
    # 8-bit operands 6.5e-5 and 4.7e-4: the mean averages rounding away,
    # so those three pass here on a seed and fail by six other limits
    "loss": 1e-4,
    # the same of the prediction block's own loss, the same kind of
    # mean.  Trainer 1.3e-6 to 5.1e-5; the controls 3.4e-5 (unscaled
    # weights, one seed; it fails by six others) to 8.0e-4
    "mtp_loss": 1e-4,
    # the worst trained tensor's |g - reference gradient| / |reference
    # gradient|, g from AdamW's first moment: a router's, a sum over
    # 8,192 tokens where LFM2's 0.08 to 0.11 is one over 32,768.  Trainer
    # 0.129 to 0.188; unscaled weights 0.494 and 0.499, every other
    # control 0.95 to 1.69, unchanged and the second loss left out 1
    "gradient": 0.3,
    # the worst tensor's |what step 0 added - AdamW's first step of the
    # REFERENCE gradient| / |that step|: the first step is a sign.
    # Trainer 0.418 to 0.475; unscaled weights 0.682 and 0.695, the
    # other controls 1.0 to 1.38
    "update": 0.57,
    # the same against AdamW's first step of the trainer's OWN gradient:
    # float32's resolution of a step of 3e-4.  Trainer 1.0e-4 (and so
    # every control that puts its own gradient's step in place, 1.0e-4
    # to 8.4e-4); unchanged 1
    "update_rule": 1e-2,
    # 1 - the share of (token, chosen expert) pairs the reference chose
    # too on the same layer input, the worst layer.  Trainer 0.0189 to
    # 0.0218; no latent norms 0.111 and 0.118, unscaled weights 0.13,
    # the other wrong models and 8-bit operands 0.49 to 0.79
    "routing": 0.05,
    # mean |g - reference's g| over every token's four choices, the
    # worst layer (a token's weights sum to the routed scale, 1.8).
    # Trainer 7.7e-4 to 9.4e-4; no latent norms 4.7e-3 and 5.5e-3, the
    # others 0.033 to 0.091, unscaled weights 0.2
    "weights": 2e-3,
    # |pairs held - reference's| / reference's, the worst layer, of some
    # 4,100 pairs: the flips' counting noise.  Trainer 2.4e-3 to 9.9e-3;
    # no latent norms 0.034 and 0.048, unscaled weights 0.039 and 0.043,
    # the others 0.058 to 1.07
    "pairs_held": 2e-2,
    # mean |b after the step - b as the rule, written out, moves it from
    # the step's own choices| / the rate, the worst layer: the rule
    # alone, as update_rule is AdamW's alone.  Trainer 0 on every seed;
    # a bias the step left as it was 0.906 and 0.969 (1 less the experts
    # whose load is the mean)
    "bias": 0.1,
}


class Cell(moe_trainer.Cell):

    def __init__(self, config: dict, cell: dict, seed: int, devices) -> None:
        m, t = config["model"], config["train"]
        if config.get("routed_scaling_factor", 1) != m["moe_routed_scale"]:
            raise ValueError("the model's moe_routed_scale is not the "
                             "configuration's routed_scaling_factor")
        #: what the reference is told of the model (its ``Model``)
        self.told = dict(
            n_layers=m["n_layers"], n_heads=m["n_heads"],
            rope_dim=m["qk_rope_dim"], rope_theta=float(m["rope_theta"]),
            eps=float(m["norm_eps"]), top_k=m["moe_top_k"],
            held=(m["moe_held_offset"], m["moe_held"]),
            routed_scale=float(m["moe_routed_scale"]),
            mtp_weight=float(m["mtp_weight"]),
            bias_rate=float(m["moe_bias_rate"]),
            block=int(t["reference_block"]))
        # moe_trainer.Cell refuses a configuration with a scaling
        # factor: the routed layer it was written for had none.  Here it
        # is the trainer's moe_routed_scale and the reference's
        # routed_scale, held equal above
        super().__init__({k: v for k, v in config.items()
                          if k != "routed_scaling_factor"}, cell, seed,
                         devices)
        self.config = config

    def reseed(self, seed: int) -> None:
        super().reseed(seed)
        self.skew0 = None             # load_max_over_mean of step 0
        self.mtp_loss = None          # the block's loss, the last step's
        self.objective = None         # L_main + lambda L_mtp, the last's

    def reference(self, params: dict, tokens: np.ndarray, given=None,
                  operand_dtype=None, **wrong) -> tuple:
        """``((main loss, chosen, weights, loads), gradients, {"mtp_loss",
        "objective", "bias"})`` of the float32 reference on *params* and
        the batch *tokens* ``[B, T+1]``, given this chip's share of the
        experts and, with *given*, a step's choices; a layer at a time.
        *wrong* describes another model than the configuration's
        (``rope=False``, ``shared_key=False``, ...): the controls'."""
        key = (operand_dtype, repr(sorted(wrong.items())))
        if key not in self._references:
            self._references[key] = reference_glm47.gradient_programs(
                **dict(self.told, **wrong), operand_dtype=operand_dtype)
        return jax.device_get(self._references[key](
            params, tokens[:, :-1], tokens[:, 1:], given))

    def step_outputs(self, record: dict) -> tuple:
        """``(main loss, chosen, weights, loads, the block's loss)`` of
        the step *record* is :meth:`unit`'s of, while its statistics are
        still the last."""
        chosen, weights, losses = jax.device_get(
            (self.stats["chosen"], self.stats["weights"],
             self.stats["losses"]))
        return (float(losses[0]), chosen, weights, record["loads"],
                float(losses[1]))

    def gaps_to(self, want: tuple, got: tuple, old: dict, moment: dict,
                new: dict) -> dict:
        """The numbers ``LIMITS`` bounds: ``moe_trainer``'s seven, the
        prediction block's loss, and the selection bias after the step
        against the reference's (*want* is :meth:`reference`'s given
        *got*'s choices, *got* :meth:`step_outputs`'s)."""
        outputs, grads, extra = want
        gaps = super().gaps_to((outputs, grads), got[:4], old, moment, new)
        gaps["mtp_loss"] = float(abs(got[4] - extra["mtp_loss"])
                                 / abs(extra["mtp_loss"]))
        gaps["bias"] = float(max(
            np.abs(np.asarray(new[n]) - moved).mean()
            for n, moved in extra["bias"].items()) / self.told["bias_rate"])
        return gaps

    def unit(self) -> dict:
        """``moe_trainer``'s unit; the record's ``loss`` is the main
        loss, ``objective`` what the step minimises, ``mtp_loss`` the
        block's own."""
        r = super().unit()
        # observe_experts read the block's loss with the loads
        self.mtp_loss = r["mtp_loss"] = counter("mrtpu_train_mtp_loss")
        self.objective = r["objective"] = r["loss"]
        r["loss"] = r["objective"] - self.told["mtp_weight"] * self.mtp_loss
        if self.skew0 is None:
            self.skew0 = self.load_max_over_mean()
        return r

    def load_max_over_mean(self) -> float:
        """The busiest held expert's pairs over the held experts' mean,
        the worst expert layer's, at the last step."""
        return float(np.max(self.loads.max(axis=1)
                            / np.maximum(self.loads.mean(axis=1), 1e-9)))

    def faults(self):
        if self.loads is not None:
            bias = [counter("mrtpu_moe_router_bias_abs_max", layer=layer)
                    for layer in self.trainer.cfg.moe_layers]
            print(f"# load_max_over_mean {self.skew0:.4f} at step 0, "
                  f"{self.load_max_over_mean():.4f} at the last step; the "
                  f"largest |bias| a layer {np.round(bias, 4).tolist()}",
                  file=sys.stderr, flush=True)
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
        (``flops_glm47``), the experts' term from the pairs that really
        landed here, the mean over the run's steps.
        ``load_max_over_mean``: as ``kinds/moe_trainer.py``'s, the
        prediction block's layer among the layers.  ``mtp_loss_share``:
        ``lambda L_mtp / L`` in percent at the run's last step; 0 means
        the second loss fell out of the objective."""
        peak = flops.peak_flops(device_kind)
        if peak is None and self.on_tpu:
            raise KeyError(f"peaks.json has no device kind {device_kind!r}")
        out = {"load_max_over_mean": self.load_max_over_mean()}
        if self.mtp_loss is not None:
            weighted = self.told["mtp_weight"] * self.mtp_loss
            out["mtp_loss_share"] = 100.0 * weighted / self.objective
        if peak is not None and "train_tok_rate" in values:
            per_token = (flops_glm47.train_step_flops(
                self.model, self.B, self.T, float(np.mean(self.pairs)))
                / (self.B * self.T))
            out["mfu"] = (100.0 * values["train_tok_rate"] * per_token
                          / (n_chips * peak))
        return out
