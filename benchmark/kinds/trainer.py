"""Kind ``trainer``: ``TransformerTrainer.step`` on a fresh seeded batch
each step, the loss read every step.

A unit is one step: dispatch, then — while the device runs it — the next
step's batch is drawn on the host, then the loss is read, which closes
the step's clock.  Before the window the loss of step 0 on the cell's own
first batch is compared with the float32 reference
(``benchmark/reference_lm.py``) on the same weights.
"""

from __future__ import annotations

import math
import sys
import time

import jax
import numpy as np

from benchmark import flops, reference_lm
from benchmark.kinds import kernel_faults

#: |step-0 loss - float32 reference| / reference may be at most this.
#: The trainer multiplies in bfloat16 (8 significant bits, 4e-3 a
#: product) and accumulates and takes the softmax in float32; the mean
#: over the positions averages most of that out, and what the gap came to
#: on the v5e is in PERF.md section 6 (PR 24).  The bound is a few times
#: that: room for another seed, and an order under what 8-bit operands
#: or bfloat16 accumulation would give.
LOSS_TOLERANCE = 1e-3


def _fold_seed(seed: int) -> int:
    """``--seed`` may pass 2**31; ``jax.random.key`` takes 31 bits."""
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


class Cell:
    SPANS = ("train.step", "train.batch")
    WINDOW_SPAN = "train.step"

    def __init__(self, config: dict, cell: dict, seed: int, devices) -> None:
        from mapreduce_tpu.models.transformer import (TransformerConfig,
                                                      TransformerTrainer)
        from mapreduce_tpu.parallel import make_mesh

        self.model, self.train = config["model"], config["train"]
        self.kernels = list(config["program"]["kernels"])
        self.on_tpu = devices[0].platform == "tpu"
        self.B, self.T = int(self.train["batch"]), int(self.train["seq_len"])
        self.trainer = TransformerTrainer(
            make_mesh(devices=devices), TransformerConfig(**self.model),
            learning_rate=float(self.train["learning_rate"]),
            seed=_fold_seed(seed))
        # weights on the device in one jitted call from the seed
        self.params = jax.jit(self.trainer.init_params)()
        self.rng = np.random.default_rng(seed)
        self.tokens = self._batch()
        self.reference_gap = None

    def _batch(self) -> np.ndarray:
        return self.rng.integers(0, self.model["vocab"],
                                 size=(self.B, self.T + 1), dtype=np.int32)

    def warm(self, units: int) -> None:
        """The reference loss on the first batch, then *units* steps; the
        first step's loss is held against the reference."""
        tokens = self.tokens
        ref = jax.jit(lambda p, x, y: reference_lm.reference_loss(
            p, x, y, n_layers=self.model["n_layers"],
            n_heads=self.model["n_heads"], head_dim=self.model["head_dim"],
            block=int(self.train["reference_block"])))
        want = float(ref(self.params, tokens[:, :-1], tokens[:, 1:]))
        del ref                       # its arrays are freed with it
        got = self.unit()["loss"]
        self.reference_gap = abs(got - want) / abs(want)
        print(f"# step-0 loss {got:.6f}, float32 reference {want:.6f}, "
              f"relative gap {self.reference_gap:.2e}",
              file=sys.stderr, flush=True)
        for _ in range(units - 1):
            self.unit()

    def unit(self) -> dict:
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("train.step"):
            self.params, loss = self.trainer.step(self.params, self.tokens)
            with jax.profiler.TraceAnnotation("train.batch"):
                self.tokens = self._batch()   # the device is running
            loss = float(loss)                # closes the step
        seconds = time.monotonic() - t0
        return {"ok": math.isfinite(loss), "seconds": seconds,
                "work": self.B * self.T, "loss": loss}

    def faults(self):
        if self.reference_gap is None or \
                not self.reference_gap <= LOSS_TOLERANCE:
            yield (f"step-0 loss is {self.reference_gap} from the float32 "
                   f"reference, over {LOSS_TOLERANCE}")
        if self.on_tpu:      # off the TPU the trainer calls no kernel
            yield from kernel_faults(self.kernels, "mosaic")

    def derived(self, values: dict, n_chips: int, device_kind: str) -> dict:
        """``mfu``: required operations per second over the chips' peak.
        A TPU that ``peaks.json`` does not know is an error, not a
        default; the CPU of the tests has no peak and no ``mfu``."""
        peak = flops.peak_flops(device_kind)
        if peak is None and self.on_tpu:
            raise KeyError(f"peaks.json has no device kind {device_kind!r}")
        if peak is None or "train_tok_rate" not in values:
            return {}
        per_token = (flops.train_step_flops(self.model, self.B, self.T)
                     / (self.B * self.T))
        return {"mfu": 100.0 * values["train_tok_rate"] * per_token
                / (n_chips * peak)}


def end_to_end(records: list, window_s: float) -> dict:
    """``train_tok_rate``: tokens of all completed steps over the
    window's seconds."""
    done = [r for r in records if r["ok"]]
    if not done:
        return {}
    return {"train_tok_rate": sum(r["work"] for r in done) / window_s}
