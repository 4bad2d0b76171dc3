"""What the ``looped_trainer`` kind's limits let through, and what they
must not: three readings of one cell, each through ``Cell.faults()``.

    python3 benchmark/looplm_controls.py --workload <cell> --seed <n>

* ``trainer``: step 0 of the timed program, as ``run.py`` holds it.  No
  fault.
* ``float8_e4m3fn`` (and any other ``--operands``): the float32
  reference computed again with every product's operands rounded to that
  type, put in the trainer's place: its outputs, its gradient as the
  first moment, AdamW's first step of that gradient as the parameters'
  change.  8 bits is the nearest precision under the trainer's bfloat16
  and has to come out as not correct.
* ``unchanged``: the trainer's own outputs with a state the step left as
  it was (zero moment, the old parameters).  Not correct either.

The last line of stdout is ``{reading: {"gaps": ..., "faults": [...]}}``;
PERF.md gives the readings behind each limit.  Like ``run.py`` it runs on
whatever devices the caller has; a number from the CPU is no device
number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_looplm, run  # noqa: E402  (needs ROOT)
from benchmark.kinds import looped_trainer as kind  # noqa: E402


def controls(cell: dict, config: dict, *, seed: int, devices,
             operands=("float8_e4m3fn",)) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = kind.Cell(config, cell, seed, devices)
    want = c.reference()
    old = jax.device_get(c.params)
    first_step = jax.jit(lambda p, g: p + reference_looplm.adamw_first_step(
        p, g, **c.adamw))
    out = {}

    def read(name, got, moment, new):
        c.gaps = c.gaps_to(want, got, old, moment, new)
        out[name] = {"gaps": c.gaps, "faults": [
            f for f in c.faults() if f.startswith("step-0")]}
        run.log(f"{name}: {out[name]}")

    for dtype in operands:
        got, grads = c.reference(operand_dtype=jnp.dtype(dtype))
        read(dtype, got,
             {n: (1.0 - c.adamw["b1"]) * g for n, g in grads.items()},
             {n: np.asarray(first_step(old[n], g))
              for n, g in grads.items()})
        del grads
    c.opt_state = jax.jit(c.trainer.init_opt_state)(c.params)
    r = c.unit()
    got = (r["loss"], r["pass_losses"], r["exit_masses"])
    read("trainer", got, c.opt_state[0].mu, c.params)
    read("unchanged", got, {n: np.zeros_like(a) for n, a in old.items()},
         old)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--operands", nargs="*", default=["float8_e4m3fn"])
    args = ap.parse_args(argv)
    _manifest, _entry, cell, config = run.load_cell(args.workload)
    run.enable_compile_cache()
    import jax

    print(json.dumps(controls(cell, config, seed=args.seed,
                              devices=jax.devices(),
                              operands=args.operands)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
