"""What the ``glm_trainer`` kind's limits let through, and what they must
not: readings of one cell, each through ``Cell.faults()``.

    python3 benchmark/glm_controls.py --workload <cell> --seed <n> [<n> ...]

Every reading puts something in the place of step 0 of the timed program
and holds it, as ``Cell.warm`` does, to the float32 reference given ITS
choices (``benchmark/mellum_controls.py`` is the pattern):

* ``trainer``: step 0 itself, as ``run.py`` holds it.  No fault.
* ``unchanged``: the step's own outputs with a state the step left as it
  was (zero moment, the old parameters, the old bias).  Not correct.
* ``float8_e4m3fn`` (and any other ``--operands``): the reference
  computed again with every product's operands rounded to that type, the
  router's too: the nearest precision under the trainer's bfloat16.  Not
  correct.
* ``no_rope``, ``key_per_head``, ``no_latent_norms``, ``no_shared``,
  ``scale_1``, ``no_mtp`` (``--models``): the reference of ANOTHER model
  — no rotary embedding on the 64 rotary dimensions; a rotary key a head
  (head ``j`` reads the one key rolled ``j`` places) where all heads
  share one; the two latents left unnormed; the shared expert left out;
  the routed weights times 1.0 where 1.8 belongs; the second loss out of
  the objective (weight 0) — in the step's place the same way: what a
  program that left that piece of the mathematics out would give.  Not
  correct.

``--seed`` takes several: the first *--full* of them (default 1) are read
in every way above, the others as ``trainer`` alone, through the same
compiled programs; no new seed is started once *--budget-s* seconds have
passed.  Every reading is a line of stderr when it is made; the last line
of stdout is ``{seed: {reading: {"gaps": ..., "faults": [...]}}}``;
PERF.md gives the readings behind each limit.  Like ``run.py`` it runs on
whatever devices the caller has; a number from the CPU is no device
number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402  (needs ROOT)
from benchmark.kinds import glm_trainer as kind  # noqa: E402
from benchmark.reference_looplm import adamw_first_step  # noqa: E402

#: what each wrong model tells the reference in the configuration's place
MODELS = {"no_rope": {"rope": False}, "key_per_head": {"shared_key": False},
          "no_latent_norms": {"latent_norms": False},
          "no_shared": {"shared": False}, "scale_1": {"routed_scale": 1.0},
          "no_mtp": {"mtp_weight": 0.0}}


def controls(c, seed: int, *, operands=("float8_e4m3fn",),
             models=tuple(MODELS), full: bool = True) -> dict:
    """The readings of *seed* through the cell *c* (``kind.Cell``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c.reseed(seed)
    old, first = jax.device_get(c.params), c.tokens
    r = c.unit()
    step = c.step_outputs(r)
    new, opt_state = jax.device_get((c.params, c.opt_state))
    c.params = c.opt_state = None      # the references need the room
    first_step = jax.jit(lambda p, g: p + adamw_first_step(p, g, **c.adamw))
    out = {}

    def read(name, want, got, moment, new):
        c.gaps = c.gaps_to(want, got, old, moment, new)
        out[name] = {"gaps": c.gaps, "faults": [
            f for f in c.faults() if f.startswith("step")]}
        run.log(f"seed {seed}, {name}: {out[name]}")
        run.log(f"seed {seed}, {name}, gradient by tensor: " + ", ".join(
            f"{n} {g[0]:.3f}" for n, g in sorted(
                c.by_tensor.items(), key=lambda kv: -kv[1][0])))

    def in_its_place(name, theirs):
        """A reference's outputs, AdamW's first step of its gradient and
        its own move of the bias where the step's belong, held to the
        published reference given ITS choices."""
        (loss, chosen, weights, loads), grads, extra = theirs
        got = (float(loss), chosen, weights, loads, float(extra["mtp_loss"]))
        new = {n: extra["bias"][n] if n in extra["bias"]
               else np.asarray(first_step(old[n], g))
               for n, g in grads.items()}
        read(name, c.reference(old, first, given=chosen), got,
             {n: (1.0 - c.adamw["b1"]) * g for n, g in grads.items()}, new)

    want = c.reference(old, first, given=step[1])
    read("trainer", want, step, opt_state[0].mu, new)
    if not full:
        return out
    read("unchanged", want, step,
         {n: np.zeros_like(a) for n, a in old.items()}, old)
    del want, new, opt_state
    for name, kw in ([(d, {"operand_dtype": jnp.dtype(d)}) for d in operands]
                     + [(m, MODELS[m]) for m in models]):
        in_its_place(name, c.reference(old, first, **kw))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--full", type=int, default=1)
    ap.add_argument("--budget-s", type=float, default=float("inf"))
    ap.add_argument("--operands", nargs="*", default=["float8_e4m3fn"])
    ap.add_argument("--models", nargs="*", default=list(MODELS),
                    choices=list(MODELS))
    args = ap.parse_args(argv)
    _manifest, _entry, cell, config = run.load_cell(args.workload)
    run.enable_compile_cache()
    import jax

    t0 = time.monotonic()
    c = kind.Cell(config, cell, args.seed[0], jax.devices())
    out = {}
    for i, seed in enumerate(args.seed):
        if time.monotonic() - t0 > args.budget_s:
            run.log(f"budget spent: seeds {args.seed[i:]} not read")
            break
        out[seed] = controls(c, seed, operands=args.operands,
                             models=args.models, full=i < args.full)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
