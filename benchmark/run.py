"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration or per-layer metric is
a data file found by the name ``BENCHMARK.json`` gives (README.md beside
this file); the loop for a KIND of configuration is
``kinds/<kind>.py``.  This file is the part all of them share: the device
check, set-up and its clock, the measured window, the traced units, the
per-layer readers and the result line.

The last line of stdout is the result object; everything else goes to
stderr.  Anywhere but on the cell's TPU chips the command exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()          # set-up's clock starts with the process

import argparse
import importlib
import json
import os
import statistics
import sys
import tempfile
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace as trace_reader  # noqa: E402  (needs ROOT)

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: where the compile cache lives when the environment names no place: a
#: fixed path inside the checkout (the path is part of the cache's key)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, manifest_path: str = "BENCHMARK.json") -> tuple:
    """``(manifest, manifest entry, cell file, configuration file)`` of
    cell *name*; the cell's file and the manifest must agree."""
    manifest = load_json(ROOT, manifest_path)
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise SystemExit(f"{manifest_path} has no cell named {name!r}")
    entry = entries[0]
    cell = load_json(HERE, "workloads", f"{name}.json")
    for key, mine in (("config", cell["config"]), ("chips", cell["chips"]),
                      ("traffic", cell["traffic"]["name"])):
        if entry[key] != mine:
            raise SystemExit(f"cell {name!r}: {manifest_path} says {key}="
                             f"{entry[key]!r}, its file says {mine!r}")
    config = load_json(HERE, "configs", f"{cell['config']}.json")
    return manifest, entry, cell, config


def layer_metrics_of(manifest: dict, cell: str) -> list:
    """The per-layer metric files of the metrics *cell* reports."""
    out = []
    for m in manifest["per_layer"]:
        if cell in m.get("workloads", [cell]):
            out.append(load_json(HERE, "layer_metrics", f"{m['name']}.json"))
    return out


def enable_compile_cache() -> str:
    """JAX's persistent cache where the environment says, else at the
    fixed path in the checkout; every program is kept, however short its
    compile, so that a second run compiles nothing."""
    import jax

    path = os.environ.get(CACHE_ENV) or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs JAX made ready, by its own events (``jax.monitoring``),
    the program's ledger aside: ``count`` rises for every program that
    is compiled OR fetched from the persistent cache, ``hits`` for the
    fetched ones.  The window must see neither."""

    ACQUIRED = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_kw) -> None:
        if event == self.ACQUIRED:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.CACHE_HIT:
            self.hits += 1


def ledger_acquisitions() -> int:
    """Programs the compile ledger lowered and compiled or fetched from
    the persistent cache so far — everything but its ``cached`` outcome."""
    from mapreduce_tpu.obs.compile import LEDGER

    programs = LEDGER.snapshot().get("programs", {})
    return sum(p["compiled"] + p["persistent_hit"] for p in programs.values())


def memory_peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip: the allocator's peak of
    live buffers plus the peak the runtime RESERVED for loaded programs'
    temporaries.  On the v5e the two are booked apart — after 32K
    training steps ``peak_bytes_in_use`` read 0.82 GB and
    ``peak_bytes_reserved`` 8.48 GB, the step program's 8.69 GB of
    ``memory_analysis`` temporaries (PR 24) — so the first alone misses
    most of what a step holds.  0 where the backend reports nothing
    (the CPU)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def run_units(kind_cell, n: int = 0, seconds: float = 0.0) -> tuple:
    """Run units back to back: *n* of them, or until the one in flight at
    *seconds* completes.  Returns ``(records, elapsed seconds)``; a unit
    that raises is recorded as failed and ends the loop."""
    records = []
    t0 = time.monotonic()
    while True:
        try:
            records.append(kind_cell.unit())
        except Exception:
            traceback.print_exc(file=sys.stderr)
            records.append({"ok": False, "seconds": 0.0, "work": 0,
                            "raised": True})
            break
        if n and len(records) >= n:
            break
        if not n and time.monotonic() - t0 >= seconds:
            break
    return records, time.monotonic() - t0


def traced_units(kind_cell, n: int, window_span: str) -> tuple:
    """*n* units under the profiler; returns ``(records, summary)`` where
    summary is :func:`benchmark.trace.summarize`'s, ``{}`` when the
    trace holds no TPU plane."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # TraceAnnotation spans stay
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            records, _ = run_units(kind_cell, n=n)
        finally:
            jax.profiler.stop_trace()
        devices, spans = trace_reader.read_xplane(
            trace_reader.find_xplane(tdir), kind_cell.SPANS)
    return records, trace_reader.summarize(devices, spans, window_span)


def read_layer_metric(read: dict, records: list, summary: dict,
                      derived: dict):
    """One per-layer metric from what the run observed, by the ``read``
    group of its file; ``None`` when there is nothing to read."""
    good = [r for r in records if not r.get("raised")]
    if "timings" in read:
        vals = [r["timings"][read["timings"]] for r in good
                if read["timings"] in r.get("timings", {})]
        return statistics.median(vals) if vals else None
    if "unit_seconds" in read:
        return (statistics.median(r["seconds"] for r in good)
                * read["unit_seconds"]) if good else None
    if "derived" in read:
        return derived.get(read["derived"])
    if not summary:
        return None
    if read.get("trace") == "idle":
        return 100.0 * summary["idle_share"]
    if "trace_events" in read and read.get("over") == "busy":
        ns = trace_reader.matching_ns(summary["by_name"], read["trace_events"])
        return 100.0 * ns / summary["busy_ns_worst"] if ns else None
    raise ValueError(f"per-layer metric: unknown reader {read!r}")


def measure(manifest: dict, cell: dict, config: dict, *, seed: int,
            seconds: float, traced: bool, devices, t_start: float) -> dict:
    """Set up cell *cell* on *devices*, measure for *seconds*, and return
    the result object.  The platform is whatever *devices* are: the
    command line refuses anything but the cell's TPU chips before it
    gets here, the CPU tests do not."""
    kind = importlib.import_module(f"benchmark.kinds.{config['kind']}")
    compiles = CompileCounter()
    kind_cell = kind.Cell(config, cell, seed, devices)
    traffic = cell["traffic"]
    kind_cell.warm(int(traffic["warm_units"]))
    before = (compiles.count, ledger_acquisitions())
    setup_s = time.monotonic() - t_start
    log(f"set-up {setup_s:.1f}s, of which {compiles.seconds:.1f}s making "
        f"{compiles.count} programs ready ({compiles.hits} fetched from the "
        f"persistent cache); window {seconds}s")

    records, window_s = run_units(kind_cell, seconds=seconds)
    in_window = (compiles.count - before[0],
                 ledger_acquisitions() - before[1])
    values = dict(kind.end_to_end(records, window_s), setup_s=setup_s)
    summary = {}
    if traced:
        # per-layer metrics: the trace covers a few units after the
        # window, the timings-based medians every unit of the run
        more, summary = traced_units(
            kind_cell, int(traffic["trace_units"]), kind_cell.WINDOW_SPAN)
        records = records + more
        if not summary and devices[0].platform == "tpu":
            raise RuntimeError("the trace holds no operation on a TPU plane")
        derived = kind_cell.derived(values, len(devices),
                                    devices[0].device_kind)
        values = {}
        for m in layer_metrics_of(manifest, cell["name"]):
            v = read_layer_metric(m["read"], records, summary, derived)
            if v is not None:
                values[m["name"]] = v

    faults = list(kind_cell.faults())
    if any(in_window):
        faults.append(f"compiled inside the window: {in_window[0]} programs "
                      f"by JAX's count, {in_window[1]} by the ledger's")
    for fault in faults:
        log(f"FAULT: {fault}")
    failed = sum(1 for r in records if not r["ok"])
    units = {m["name"]: m["unit"] for m in
             manifest["end_to_end"] + manifest["per_layer"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(devices)}
    result = {"correct": not faults and failed == 0,
              "attempted": len(records), "failed": failed,
              "metrics": {n: {"value": v, "unit": units[n]}
                          for n, v in values.items()},
              "device": device}
    if summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {
            "device_ops": trace_reader.top_ops(
                summary["by_name"], kind_cell.kernels),
            "idle_gaps": summary["idle_gaps"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="manifest to find the cell in, relative to the "
                    "checkout (benchmark/parked/*.json hold cells that "
                    "BENCHMARK.json does not list yet)")
    args = ap.parse_args(argv)
    manifest, entry, cell, config = load_cell(args.workload, args.manifest)

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != entry["chips"]:
        print(f"benchmark: cell {args.workload!r} needs {entry['chips']} TPU "
              f"chip(s); JAX shows {len(devices)} x {devices[0].platform!r}. "
              "No result.", file=sys.stderr)
        return 1
    log(f"{args.workload} on {len(devices)} x {devices[0].device_kind}, "
        f"seed {args.seed}, compile cache at {cache}")
    result = measure(manifest, cell, config, seed=args.seed,
                     seconds=args.seconds, traced=bool(args.trace),
                     devices=devices, t_start=_T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
