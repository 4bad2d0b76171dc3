"""Kind ``looped_trainer``: ``TransformerTrainer.step_opt`` of a looped
(weight-shared-depth) language model under AdamW, on a fresh seeded batch
each step; the objective and the per-pass statistics are read every
step.

A unit is one step: dispatch, then — while the device runs it — the next
step's batch is drawn on the host, then the step's statistics and its
objective are read, which closes the step's clock.

Before the window, step 0 of the timed program on the cell's own first
batch, at the timed sizes, is held against the float32 reference
(``benchmark/reference_looplm.py``) on the same weights, forward AND
backward AND update: the objective, the loss of each pass's head and the
exit mass of each pass; the gradient of every parameter, read from
AdamW's first moment after the step (``mu = (1 - b1) g`` exactly, from
zero), against the reference's gradient; and what the step added to
every parameter against what AdamW's first step, written out in the
reference, makes of that gradient.  A state the step leaves unchanged
reads 1 in all three of the latter.
"""

from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, flops_looplm, reference_looplm
from benchmark.kinds import kernel_faults
from benchmark.kinds.trainer import _fold_seed, end_to_end  # noqa: F401

#: Limits of the step-0 comparison, by the name ``Cell.gaps`` gives each
#: number.  Each lies between two readings on the v5e at the cell's
#: sizes (PERF.md section 6, PR 28): the largest the trainer (bfloat16
#: operands, float32 accumulation) gave over 48 seeds (25 for the last
#: three), and what the reference itself gives in the nearest precision
#: below, every product's operands rounded to 8 bits
#: (``Cell.reference(operand_dtype=float8_e4m3fn)``), which comes out as
#: not correct; ``benchmark/looplm_controls.py`` reads that, and a state
#: left unchanged, through ``faults()``.
LIMITS = {
    # |objective - reference| / reference: a mean over 8,192 positions
    # of a mix of four losses near log(vocabulary), so the trainer's
    # rounding mostly averages out, and 8-bit rounding nearly as well:
    # the least room of the six.  Trainer up to 5.1e-5; 8-bit operands
    # 1.3e-4 to 4.0e-4
    "objective": 1e-4,
    # the same for the mean loss of each pass's head, the worst pass.
    # Trainer up to 4.7e-5; 8-bit operands 4.8e-4 to 1.3e-3
    "pass_loss": 2e-4,
    # |mean exit mass - reference|, absolute (the masses sum to 1), the
    # worst pass.  The gate is a sigmoid of ONE dot product a position,
    # so rounding in the hidden state moves it directly and only the
    # mean over positions averages it.  Trainer up to 2.8e-3; 8-bit
    # operands 1.4e-2 to 2.8e-2
    "exit_mass": 8e-3,
    # |g - reference gradient| / |reference gradient| (2-norms), the
    # worst tensor; g is AdamW's first moment after step 0 over 1 - b1.
    # A shared weight's gradient is the sum over the four passes: one
    # pass alone, or none, reads near 1.  Trainer 0.020 to 0.042; 8-bit
    # operands 0.69 and 0.72; a state left unchanged 1
    "gradient": 0.15,
    # |what step 0 added to the tensor - AdamW's first step of the
    # REFERENCE gradient| / |that step|, the worst tensor.  The first
    # step is lr * g / (|g| + eps), a sign: every element whose gradient
    # lies under the trainer's rounding flips and then counts 2, so this
    # reads tenths, not rounding (the reference in bfloat16 operands
    # reads 0.19).  Trainer 0.18 to 0.27; 8-bit operands 1.14 and 1.15;
    # unchanged 1
    "update": 0.6,
    # the same against AdamW's first step of the trainer's OWN gradient
    # g: the update rule alone, to float32 rounding (the norm scales sit
    # at 1 and move by 3.3e-4).  Trainer 1.0e-4; unchanged 1
    "update_rule": 1e-2,
}
#: the exit masses of every step sum to 1 within this (float32 means)
MASS_SUM_TOLERANCE = 1e-5


def _tensor(tree: dict, name: str):
    """One tensor of a name -> array tree.  The exit gate's bias is ONE
    number, whose gradient can lie near zero and whose relative gap is
    then no reading: it rides with the gate's weights."""
    if name == "exit_w":
        return jnp.concatenate([jnp.asarray(tree["exit_w"]),
                                jnp.asarray(tree["exit_b"])])
    return tree[name]


class Cell:
    SPANS = ("train.step", "train.batch")
    WINDOW_SPAN = "train.step"

    def __init__(self, config: dict, cell: dict, seed: int, devices) -> None:
        import optax

        from mapreduce_tpu.models.transformer import (TransformerConfig,
                                                      TransformerTrainer)
        from mapreduce_tpu.parallel import make_mesh

        self.config = config
        self.model, self.train = config["model"], config["train"]
        self.kernels = list(config["program"]["kernels"])
        self.on_tpu = devices[0].platform == "tpu"
        self.B, self.T = int(self.train["batch"]), int(self.train["seq_len"])
        self.adamw = {k: float(self.train[k]) for k in (
            "learning_rate", "b1", "b2", "eps", "weight_decay")}
        self.trainer = TransformerTrainer(
            make_mesh(devices=devices), TransformerConfig(**self.model),
            optimizer=optax.adamw(**self.adamw))
        # weights made on the device; the seed is the program's ARGUMENT,
        # so one compiled program serves every seed
        self.params = jax.jit(self.trainer.init_params)(
            jax.random.key(_fold_seed(seed)))
        self.opt_state = None         # warm() makes it, see there
        self.rng = np.random.default_rng(seed)
        self.tokens = self._batch()
        self.gaps = None              # step 0 against the reference
        self.worst_tensors = None     # of gradient, update, update_rule
        self.mass_sum_gap = 0.0       # worst |sum of exit masses - 1|

        def norms(want_g, moment, old, new):
            """Squared 2-norms behind the three gaps of one tensor."""
            g = moment / (1.0 - self.adamw["b1"])
            moved = new - old
            by_reference = reference_looplm.adamw_first_step(
                old, want_g, **self.adamw)
            by_rule = reference_looplm.adamw_first_step(old, g, **self.adamw)
            sq = lambda a: jnp.sum(jnp.square(a.astype(jnp.float32)))
            return jnp.stack([sq(g - want_g), sq(moved - by_reference),
                              sq(moved - by_rule), sq(want_g),
                              sq(by_reference), sq(by_rule)])

        self._norms = jax.jit(norms)

    def _batch(self) -> np.ndarray:
        return self.rng.integers(0, self.model["vocab"],
                                 size=(self.B, self.T + 1), dtype=np.int32)

    def reference(self, operand_dtype=None) -> tuple:
        """``((objective, pass losses, exit masses), gradients)`` of the
        float32 reference on the current weights and batch, on the host:
        the step needs the device's room."""
        ref = jax.jit(lambda p, x, y: reference_looplm.reference_gradients(
            p, x, y, n_layers=self.model["n_layers"],
            n_heads=self.model["n_heads"], head_dim=self.model["head_dim"],
            loop_steps=self.model["loop_steps"],
            rope_theta=float(self.model["rope_theta"]),
            beta=float(self.model["exit_entropy_weight"]),
            eps=float(self.config["rms_norm_eps"]),
            block=int(self.train["reference_block"]),
            operand_dtype=operand_dtype))
        return jax.device_get(ref(self.params, self.tokens[:, :-1],
                                  self.tokens[:, 1:]))

    def gaps_to(self, want: tuple, got: tuple, old: dict, moment: dict,
                new: dict) -> dict:
        """The numbers ``LIMITS`` bounds: *want* is :meth:`reference`'s,
        *got* a step's ``(objective, pass losses, exit masses)``, *old*
        and *new* the parameters before and after it, *moment* AdamW's
        first moment after it."""
        (objective, losses, masses), grads = want
        names = [n for n in old if n != "exit_b"]
        # one tensor at a time, each read back before the next is sent:
        # dispatched together, the host's copies of every tensor would
        # sit on the device beside the training state
        norms = np.array([np.asarray(self._norms(*(_tensor(t, n) for t in (
            grads, moment, old, new)))) for n in names], dtype=np.float64)
        by_tensor = np.sqrt(norms[:, :3] / norms[:, 3:])      # [names, 3]
        worst = by_tensor.max(axis=0)           # a NaN stays a NaN
        self.worst_tensors = [names[i] for i in np.argmax(
            np.nan_to_num(by_tensor, nan=np.inf), axis=0)]
        return {
            "objective": float(abs(got[0] - objective) / abs(objective)),
            "pass_loss": float(np.max(np.abs(np.asarray(got[1]) - losses)
                                      / np.abs(losses))),
            "exit_mass": float(np.max(np.abs(np.asarray(got[2]) - masses))),
            "gradient": float(worst[0]), "update": float(worst[1]),
            "update_rule": float(worst[2])}

    def warm(self, units: int) -> None:
        """The reference on the first batch, then *units* steps; the
        first step is held against the reference."""
        want = self.reference()
        old = jax.device_get(self.params)     # the step takes them over
        # AdamW's moments only now: the reference's gradient needed the
        # room (PERF.md section 4)
        self.opt_state = jax.jit(self.trainer.init_opt_state)(self.params)
        r = self.unit()
        self.gaps = self.gaps_to(
            want, (r["loss"], r["pass_losses"], r["exit_masses"]), old,
            self.opt_state[0].mu, self.params)
        print(f"# step-0 objective {r['loss']:.6f}, float32 reference "
              f"{float(want[0][0]):.6f}; pass losses {r['pass_losses']} "
              f"against {want[0][1].tolist()}; exit masses "
              f"{r['exit_masses']} against {want[0][2].tolist()}; gaps "
              f"{self.gaps}; worst tensors {self.worst_tensors}",
              file=sys.stderr, flush=True)
        del want, old
        for _ in range(units - 1):
            self.unit()

    def unit(self) -> dict:
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("train.step"):
            self.params, self.opt_state, loss, stats = self.trainer.step_opt(
                self.params, self.opt_state, self.tokens)
            with jax.profiler.TraceAnnotation("train.batch"):
                self.tokens = self._batch()   # the device is running
            stats = self.trainer.observe_passes(stats)   # closes the step
            loss = float(loss)
        seconds = time.monotonic() - t0
        finite = math.isfinite(loss) and bool(np.isfinite(stats).all())
        if finite:
            self.mass_sum_gap = max(self.mass_sum_gap,
                                    abs(float(stats[1].sum()) - 1.0))
        return {"ok": finite, "seconds": seconds, "work": self.B * self.T,
                "loss": loss, "pass_losses": stats[0].tolist(),
                "exit_masses": stats[1].tolist()}

    def faults(self):
        for name, limit in LIMITS.items():
            gap = None if self.gaps is None else self.gaps[name]
            if gap is None or not gap <= limit:
                yield (f"step-0 {name} is {gap} from the float32 "
                       f"reference, over {limit}")
        if not self.mass_sum_gap <= MASS_SUM_TOLERANCE:
            yield (f"a step's exit masses sum to 1 +- {self.mass_sum_gap}, "
                   f"over {MASS_SUM_TOLERANCE}")
        if self.on_tpu:      # off the TPU the trainer calls no kernel
            yield from kernel_faults(self.kernels, "mosaic")

    def derived(self, values: dict, n_chips: int, device_kind: str) -> dict:
        """``mfu``: required operations per second over the chips' peak
        (``flops_looplm``); as in ``kinds/trainer.py`` a TPU that
        ``peaks.json`` does not know is an error and the CPU has none."""
        peak = flops.peak_flops(device_kind)
        if peak is None and self.on_tpu:
            raise KeyError(f"peaks.json has no device kind {device_kind!r}")
        if peak is None or "train_tok_rate" not in values:
            return {}
        per_token = (flops_looplm.train_step_flops(self.model, self.B, self.T)
                     / (self.B * self.T))
        return {"mfu": 100.0 * values["train_tok_rate"] * per_token
                / (n_chips * peak)}
