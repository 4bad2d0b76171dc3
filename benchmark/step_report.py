"""One cell's measured window, step by step, from the program's own record.

    python3 benchmark/step_report.py --workload <cell> --seed <n> [--seconds 30] [--profile 1]

Sets a training cell up and warms it as ``run.measure`` does, runs
``run.run_units`` for ``--seconds`` (``--profile 1``: under the profiler,
what tracing costs) and prints as its last line ``{"steps":
TransformerTrainer.step_log(), "summary": {...}}``.  Only on the cell's TPU
chips, like ``run.py``, whose result line takes it over (ROADMAP C9 (6)).
"""

import argparse
import collections
import contextlib
import importlib
import json
import os
import statistics
import sys
import tempfile
import time
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # noqa: E402  (needs the checkout on the path)


def fullest(samples) -> dict:
    return max(samples, default={}, key=lambda d: (
        d.get("bytes_in_use", 0) + d.get("bytes_reserved", 0)))


def spread(values) -> dict:
    v = sorted(values)
    return {"median": statistics.median(v),
            "p99": v[min(len(v) - 1, int(0.99 * len(v)))]} if v else {}


def report(cell: dict, config: dict, *, seed: int, seconds: float, devices,
           profile: bool = False) -> dict:
    """Set up *cell* on *devices*, run its window and return the object
    the command prints: every record of the trainer, warm-up's first, and
    the window's summed up; ``mem`` is the fullest device's, by phase."""
    import jax
    from mapreduce_tpu.obs.memory import sample_device_memory

    def memory() -> dict:
        return fullest(sample_device_memory(devices)["devices"].values())

    kind = importlib.import_module(f"benchmark.kinds.{config['kind']}")
    kind_cell = kind.Cell(config, cell, seed, devices)
    mem = {"cell_made": memory()}
    kind_cell.warm(int(cell["traffic"]["warm_units"]))
    mem["warmed"] = memory()
    t_window = time.monotonic()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # TraceAnnotation spans stay
    with tempfile.TemporaryDirectory(prefix="step_trace_") as tdir, (
            jax.profiler.trace(tdir, profiler_options=opts) if profile
            else contextlib.nullcontext()):
        records, window_s = run.run_units(kind_cell, seconds=seconds)
    mem["window_done"] = memory()
    steps = kind_cell.trainer.step_log()
    window = [s for s in steps if s["t_enter"] >= t_window]
    mem["in_flight"] = fullest(s["mem"] for s in steps if "mem" in s)
    summary = dict(
        kind.end_to_end(records, window_s), window_s=window_s, mem=mem,
        steps=len(window), failed=sum(1 for r in records if not r["ok"]),
        slow=collections.Counter(s["slow"] for s in window if "slow" in s),
        compiled=sum(s["compiled"] for s in window), profiled=profile,
        mem_sample_us=timeit.timeit(memory, number=20) / 20 * 1e6,
        tokens_over_step_s=(sum(s["tokens"] for s in window)
                            / sum(s["step_s"] for s in window)),
        **{key: spread(s[key] for s in window if key in s)
           for key in ("step_s", "wait_s", "turnaround_s", "overlap_s")})
    return {"steps": steps, "summary": summary}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _manifest, entry, cell, config = run.load_cell(args.workload)
    run.enable_compile_cache()
    devices = importlib.import_module("jax").devices()
    if devices[0].platform != "tpu" or len(devices) != entry["chips"]:
        sys.exit(f"step report: {args.workload!r} needs {entry['chips']} "
                 "TPU chip(s). No result.")
    print(json.dumps(report(
        cell, config, seed=args.seed, seconds=args.seconds, devices=devices,
        profile=bool(args.profile))), flush=True)


if __name__ == "__main__":
    main()
