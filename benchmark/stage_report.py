"""One cell's traced units, read by stage: the command beside ``run.py``
for the per-layer metrics ``run.py`` cannot read yet.

    python3 benchmark/stage_report.py --workload <cell> --seed <n> [--manifest <file>]

``run.read_layer_metric`` raises on a ``read`` group it does not know,
and only a ``benchmark`` PR may edit ``run.py`` (README.md beside this
file).  Until one hands the open trace to ``stages.py`` there, the
metric files of ``stages.READ_GROUPS`` wait in ``layer_metrics/``
without an entry in ``BENCHMARK.json``, and this command reads them:
it sets the cell up and warms it exactly as ``run.measure`` does, runs
the cell's ``trace_units`` under the profiler, and prints

* on stderr the stage table (every stage scope's share of device busy
  time, summing to 100%) and which bound each kernel's roofline took;
* as the last line of stdout one object in ``run.py``'s shape:
  ``correct``, ``attempted``, ``failed``, ``metrics`` (every metric file
  that names the cell in its ``workloads``, whichever reader reads it),
  ``device`` and ``breakdown`` — ``stages`` as ``[stage, seconds]`` and
  ``idle_gaps`` labelled by the innermost span among the kind's ``SPANS``
  and the program's own spans.

There is no measured window here, so no end-to-end metric and nothing
``derived`` from one (``train.mfu``).  Like ``run.py`` it runs only on
the cell's TPU chips and prints no result anywhere else.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run, stages  # noqa: E402  (needs ROOT)
from benchmark import trace as trace_reader  # noqa: E402


def metric_files(cell: str) -> list:
    """The per-layer metric files that name *cell* in their ``workloads``,
    listed in a manifest or waiting to be."""
    files = (run.load_json(path) for path in sorted(
        glob.glob(os.path.join(HERE, "layer_metrics", "*.json"))))
    return [m for m in files if cell in m["workloads"]]


def traced_units(kind_cell, n: int) -> tuple:
    """*n* units under the profiler; ``(records, summary, program)``:
    ``trace.summarize``'s numbers with the idle gaps labelled by the
    kind's spans and the program's together, and ``stages.reduce``'s."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # TraceAnnotation spans stay
    with tempfile.TemporaryDirectory(prefix="stage_trace_") as tdir:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            records, _ = run.run_units(kind_cell, n=n)
        finally:
            jax.profiler.stop_trace()
        xplane = trace_reader.find_xplane(tdir)
        devices, spans = trace_reader.read_xplane(xplane, kind_cell.SPANS)
        staged, program_spans = stages.read_xplane(
            xplane, stages.ledger_paths())
    window = kind_cell.WINDOW_SPAN
    program = stages.reduce(
        staged, program_spans,
        [(s, s + d) for name, s, d in spans if name == window])
    return records, trace_reader.summarize(
        devices, spans + program_spans, window), program


def report(cell: dict, config: dict, *, seed: int, devices) -> dict:
    """Set up *cell* on *devices*, trace its ``trace_units`` and return
    the result object.  The platform is whatever *devices* are: the
    command line refuses anything but the cell's TPU chips, the CPU
    tests do not (and read no device metric there)."""
    kind = importlib.import_module(f"benchmark.kinds.{config['kind']}")
    kind_cell = kind.Cell(config, cell, seed, devices)
    traffic = cell["traffic"]
    kind_cell.warm(int(traffic["warm_units"]))
    records, summary, program = traced_units(
        kind_cell, int(traffic["trace_units"]))
    if not summary and devices[0].platform == "tpu":
        raise RuntimeError("the trace holds no operation on a TPU plane")
    program.update(config=config, device_kind=devices[0].device_kind)
    for line in stages.table(program):
        run.log(line)

    metrics = {}
    for m in metric_files(cell["name"]):
        if any(group in m["read"] for group in stages.READ_GROUPS):
            value = stages.read_layer_metric(m["read"], program)
        else:
            value = run.read_layer_metric(m["read"], records, summary, {})
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    faults = list(kind_cell.faults())
    for fault in faults:
        run.log(f"FAULT: {fault}")
    failed = sum(1 for r in records if not r["ok"])
    result = {"correct": not faults and failed == 0,
              "attempted": len(records), "failed": failed,
              "metrics": metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": run.memory_peak_bytes(devices)}}
    if summary:
        result["device"].update(busy_s=summary["busy_s"],
                                window_s=summary["window_s"])
        by_stage = stages.by_stage(program["by_key"])
        result["breakdown"] = {
            "stages": [[stage, ns / 1e9] for stage, ns in
                       sorted(by_stage.items(), key=lambda kv: -kv[1])],
            "idle_gaps": summary["idle_gaps"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="manifest to find the cell in, as for run.py")
    args = ap.parse_args(argv)
    _manifest, entry, cell, config = run.load_cell(args.workload,
                                                   args.manifest)
    cache = run.enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != entry["chips"]:
        print(f"stage report: cell {args.workload!r} needs {entry['chips']} "
              f"TPU chip(s); JAX shows {len(devices)} x "
              f"{devices[0].platform!r}. No result.", file=sys.stderr)
        return 1
    run.log(f"{args.workload} on {len(devices)} x {devices[0].device_kind}, "
            f"seed {args.seed}, compile cache at {cache}")
    print(json.dumps(report(cell, config, seed=args.seed, devices=devices)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
