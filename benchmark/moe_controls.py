"""What the ``moe_trainer`` kind's limits let through, and what they must
not: readings of one cell, each through ``Cell.faults()``.

    python3 benchmark/moe_controls.py --workload <cell> --seed <n> [<n> ...]

Every reading puts something in the place of step 0 of the timed program
and holds it, as ``Cell.warm`` does, to the float32 reference given ITS
choices:

* ``trainer``: step 0 itself, as ``run.py`` holds it.  No fault.
* ``float8_e4m3fn`` (and any other ``--operands``): the reference
  computed again with every product's operands rounded to that type —
  the router's product too, so choices flip: its loss, its choices,
  weights and loads, its gradient as the first moment, AdamW's first step
  of that gradient as the parameters' change.  8 bits is the nearest
  precision under the trainer's bfloat16 and has to come out as not
  correct.
* ``--rules``: the reference under a routing rule that is NOT the
  published one (``reference_lfm2moe.py``: ``biased_weights``,
  ``held_norm``, ``softmax``), in the step's place the same way.  Not
  correct.
* ``half_batch``: the step's own loss and routing with the gradient of
  the first half of the batch's sequences alone (the float32
  reference's, given the step's choices there).  Not correct.
* ``unchanged``: the step's own outputs with a state the step left as it
  was (zero moment, the old parameters).  Not correct either.

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
from benchmark.kinds import moe_trainer as kind  # noqa: E402
from benchmark.reference_looplm import adamw_first_step  # noqa: E402


def controls(c, seed: int, *, operands=("float8_e4m3fn",), rules=(),
             full: bool = True) -> dict:
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

    def in_its_place(name, want, got, grads):
        """A reference's gradient as the step's moment and update."""
        new = {n: (old[n] if n.endswith(c.buffers)
                   else np.asarray(first_step(old[n], g)))
               for n, g in grads.items()}
        read(name, want, got,
             {n: (1.0 - c.adamw["b1"]) * g for n, g in grads.items()}, new)

    want = c.reference(old, first, given=step[1])
    read("trainer", want, step, opt_state[0].mu, new)
    if not full:
        return out
    read("unchanged", want, step,
         {n: np.zeros_like(a) for n, a in old.items()}, old)
    half = c.B // 2
    _, grads = c.reference(old, first[:half], given=step[1][:, :half])
    in_its_place("half_batch", want, step, grads)
    del want, new, opt_state
    for name, kw in ([(d, {"operand_dtype": jnp.dtype(d)}) for d in operands]
                     + [(rule, {"rule": rule}) for rule in rules]):
        got, grads = c.reference(old, first, **kw)
        in_its_place(name, c.reference(old, first, given=got[1]), got, grads)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--full", type=int, default=1)
    ap.add_argument("--budget-s", type=float, default=float("inf"))
    ap.add_argument("--operands", nargs="*", default=["float8_e4m3fn"])
    ap.add_argument("--rules", nargs="*", default=[])
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
                             rules=args.rules, full=i < args.full)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
