"""Device-plane cost model, MFU/roofline accounting, and profile bundles.

The engine's wave timings say how long the device worked; this module
says how much work that was.  Per compiled program it derives FLOPs and
bytes-accessed from XLA's own cost model (``Compiled.cost_analysis()``)
with an analytic sort-hierarchy fallback for backends that expose none,
publishes the totals as counters, and derives the two standard "as fast
as the hardware allows" lenses:

* **MFU** — model FLOP/s utilisation: achieved FLOP/s ÷ the device's
  peak (Chowdhery et al., PaLM §B.2 — the metric BENCH_TRAIN.json's
  bench scripts previously computed ad hoc);
* **roofline fraction** — achieved FLOP/s ÷ the roofline-attainable
  rate ``min(peak_flops, intensity × peak_bytes/s)`` (Williams et al.,
  CACM '09), which is the honest ceiling for a memory-bound workload
  like sort-heavy MapReduce: MFU alone would under-report an engine
  already running at the bandwidth wall.

Peak numbers come from a small per-device-kind table (datasheet bf16 /
peak-HBM values) overridable with ``MAPREDUCE_TPU_PEAK_FLOPS`` and
``MAPREDUCE_TPU_PEAK_BYTES_PER_S`` — they are denominators for a ratio,
not measurements, and the table says so via the ``peak_source`` field.

**Profile bundles** (:func:`write_bundle` / :func:`load_bundle`): one
self-contained directory — Chrome trace JSON + ``/metrics`` snapshot +
``/statusz`` snapshot + manifest (+ an optional ``jax.profiler`` trace
dir) — capturing a run or a live cluster for offline analysis.  The
loader re-validates everything with the strict parsers (``
parse_prometheus``, :func:`validate_trace`), so a bundle that loads is
a bundle Perfetto and Prometheus will accept.

Wall-clock use: the bundle manifest's ``created_time`` is a persisted
TIMESTAMP minted through ``coord/docstore.now`` (the one allowed mint
point); every duration in this module is somebody else's monotonic
measurement.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

from .metrics import REGISTRY, Registry, counter, gauge, parse_prometheus
from .trace import TRACER, Tracer

# -- peak table --------------------------------------------------------------

#: (peak FLOP/s, peak HBM bytes/s) per device kind — datasheet numbers
#: (bf16 matmul peak, peak memory bandwidth), matched by substring of
#: ``device.device_kind.lower()``.  First hit wins; order matters (v5p
#: before v5).
_PEAKS_BY_KIND = (
    ("v6", (918e12, 1640e9)),
    ("v5p", (459e12, 2765e9)),
    ("v5", (197e12, 819e9)),       # v5e / "TPU v5 lite"
    ("v4", (275e12, 1228e9)),
    ("h100", (989e12, 3350e9)),
    ("a100", (312e12, 2039e9)),
)

#: the CPU carries no device_kind worth matching: a nominal few-core
#: figure so tier-1 MFU is a small-but-nonzero ratio, not a lie of
#: precision; override via env for real CPU runs.
_CPU_PEAKS = (5e10, 5e10)


def device_peaks(device: Any = None) -> Dict[str, Any]:
    """Assumed peak FLOP/s and bytes/s for *device* (any object with
    ``device_kind``/``platform`` attrs, e.g. a jax Device), with env
    overrides; ``peak_source`` says where the numbers came from.  An
    accelerator whose ``device_kind`` the table does not know is an
    error, never a default: a utilisation over a guessed peak is a
    made-up number."""
    env_f = os.environ.get("MAPREDUCE_TPU_PEAK_FLOPS")
    env_b = os.environ.get("MAPREDUCE_TPU_PEAK_BYTES_PER_S")
    kind = str(getattr(device, "device_kind", "") or "").lower()
    platform = str(getattr(device, "platform", "") or "").lower()
    for sub, (flops, nbytes) in _PEAKS_BY_KIND:
        if sub in kind:
            source = f"kind:{sub}"
            break
    else:
        if platform not in ("cpu", "") and not (env_f and env_b):
            raise ValueError(
                f"no peak FLOP/s / bytes/s known for device kind "
                f"{kind!r} (platform {platform!r}): add it to "
                "obs/profile._PEAKS_BY_KIND with its source, or set "
                "MAPREDUCE_TPU_PEAK_FLOPS and "
                "MAPREDUCE_TPU_PEAK_BYTES_PER_S")
        flops, nbytes = _CPU_PEAKS
        source = "platform:cpu"
    if env_f:
        flops, source = float(env_f), "env"
    if env_b:
        nbytes = float(env_b)
        source = "env" if env_f else source + "+env_bw"
    return {"flops_per_s": float(flops), "bytes_per_s": float(nbytes),
            "peak_source": source}


# -- program costs -----------------------------------------------------------


def program_costs(compiled: Any) -> Optional[Dict[str, float]]:
    """FLOPs / bytes-accessed of one executable from XLA's cost model
    (``Compiled.cost_analysis()``, a dict).  None when the backend
    exposes no usable analysis — callers then fall back to
    :func:`analytic_costs`."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # backend without a cost model: use the fallback
        return None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return {"flops": max(flops, 0.0), "bytes": max(nbytes, 0.0)}


#: analytic model constants: a multi-operand compare-exchange touches
#: two 64-bit keys plus carried lanes (~16 scalar ops), and the
#: segmented-scan/compaction tail is ~32 ops per record.
_SORT_CMP_FLOPS = 16
_SEGSCAN_FLOPS = 32
#: the two-pass argsort tier's extra work per record: one more stable
#: sort ladder of the [key, perm] pair plus a full-record permutation
#: gather per stage (index arithmetic; the traffic is in the bytes term)
_GATHER_FLOPS = 4
#: the fused Pallas segmented-reduce kernel's per-record work (boundary
#: compares + one combine + the end-count add, in ONE pass) — the
#: kernel-formulation twin of _SEGSCAN_FLOPS, so a pallas-served run's
#: roofline models the program that actually ran (ops/segscan kernel)
_SEGREDUCE_KERNEL_FLOPS = 12
#: scan-ladder HBM passes per record the LAX segmented-reduce pays
#: beyond the sort (segmented_scan + ladder_cumsum, each log2(N) full
#: read+write passes — modelled as this flat factor on the record
#: buffer) vs the kernel's single read+write pass
_SEGSCAN_LAX_BYTE_PASSES = 8
_SEGREDUCE_KERNEL_BYTE_PASSES = 1
#: the radix formulation (ops/radix_sort): 4-bit digits over the
#: 64-bit key = 16 digit passes, independent of record count — NO
#: comparator ladder at all.  Per record per pass: the 16-lane onehot
#: histogram/rank work plus the scatter index arithmetic.
_RADIX_PASSES = 16
_RADIX_HIST_FLOPS = 16   # onehot compare+add across the 16 buckets
_RADIX_SCATTER_FLOPS = 8  # rank gather + offset add + scatter address
#: bytes per radix pass: the kernel moves only the three sort lanes
#: (k1, k2, perm = 12B/row) each pass; the full record is gathered
#: ONCE by the rank-sort transport after the final pass.
_RADIX_LANE_BYTES = 12


def analytic_costs(input_bytes: int, n_records: int,
                   record_bytes: int,
                   fold_records: int = 0,
                   argsort: bool = False,
                   segment_impl: str = "lax",
                   sort_impl: Optional[str] = None) -> Dict[str, float]:
    """Rough cost of one engine wave when XLA's model is unavailable:
    the program is sort-dominated (device_engine.py module doc), so
    FLOPs ≈ records × log2(records) compare-exchanges + a
    segmented-reduce term, and bytes ≈ the input read plus one
    read+write of the record buffer per sort pass plus the
    segmented-reduce passes.  ``fold_records`` accounts for the fused
    wave fold — the accumulator rows (``out_capacity`` running uniques)
    re-sorted into the final per-partition merge every wave, which the
    single-dispatch program pays in place of the old separate merge
    dispatch.  With ``argsort`` (the tier-0 serving program) each sort
    site pays a SECOND stable 1-key pass over the ``[key, perm]`` pair
    plus a full-record permutation gather — the runtime price of the
    fast-compiling formulation (measured ~2.6x end to end at bench
    shapes), modelled so a run served on tier-0 doesn't report tier-1's
    cheaper roofline.  ``segment_impl`` picks the segmented-reduce
    formulation the same way (the PR-12 argsort-term pattern):
    ``"lax"`` models the ladder chain (shifted compares +
    segmented_scan + ladder_cumsum — several full read+write passes
    over the sorted records), ``"pallas"`` the fused kernel's single
    VMEM-tiled pass, so MFU/roofline gauges and the ``cost_analysis``
    fallback agree on which program actually ran.  ``sort_impl="radix"``
    replaces the comparator ``n·log2(n)`` terms entirely with the
    radix formulation (ops/radix_sort): a FIXED 16 digit passes over
    the 64-bit key, each paying the 16-bucket histogram + stable
    scatter per record and moving only the three 12-byte sort lanes,
    plus one full-record gather after the final pass — no comparator
    ladder ran, so none is modelled.  An estimate with the right
    shape and order of magnitude — labelled ``source="analytic"``
    everywhere it lands so nobody mistakes it for a measurement."""
    import math

    if segment_impl == "pallas":
        seg_flops = _SEGREDUCE_KERNEL_FLOPS
        seg_byte_passes = _SEGREDUCE_KERNEL_BYTE_PASSES
    else:
        seg_flops = _SEGSCAN_FLOPS
        seg_byte_passes = _SEGSCAN_LAX_BYTE_PASSES
    radix = sort_impl == "radix"
    rb = max(int(record_bytes), 1)
    n = max(int(n_records), 1)
    passes = max(int(math.ceil(math.log2(n))), 1)
    if radix:
        # per-record, record-count-independent pass structure
        sort_flops_per_rec = (_RADIX_PASSES
                              * (_RADIX_HIST_FLOPS + _RADIX_SCATTER_FLOPS))
        # lanes moved each pass + the one post-sort record gather
        sort_bytes_per_rec = (2 * _RADIX_LANE_BYTES * _RADIX_PASSES
                              + 2 * rb)
        flops = float(n * sort_flops_per_rec + n * seg_flops)
        nbytes = float(max(int(input_bytes), 0)
                       + n * sort_bytes_per_rec
                       + 2 * n * rb * seg_byte_passes)
        if fold_records > 0:
            m = int(fold_records)
            flops += float(m * sort_flops_per_rec + m * seg_flops)
            nbytes += float(m * sort_bytes_per_rec
                            + 2 * m * rb * seg_byte_passes)
        return {"flops": flops, "bytes": nbytes}
    flops = float(n * passes * _SORT_CMP_FLOPS + n * seg_flops)
    nbytes = float(max(int(input_bytes), 0)
                   + 2 * n * rb * passes
                   + 2 * n * rb * seg_byte_passes)
    if fold_records > 0:
        m = int(fold_records)
        fold_passes = max(int(math.ceil(math.log2(m))), 1)
        flops += float(m * fold_passes * _SORT_CMP_FLOPS
                       + m * seg_flops)
        nbytes += float(2 * m * rb * (fold_passes + seg_byte_passes))
    if argsort:
        # second sort ladder (the [key, perm] pair: ~12B/row) + one
        # permutation gather of every record lane, per sorted batch
        total = n + max(int(fold_records), 0)
        flops += float(total * passes * _SORT_CMP_FLOPS
                       + total * _GATHER_FLOPS)
        nbytes += float(2 * total * 12 * passes
                        + 2 * total * max(int(record_bytes), 1))
    return {"flops": flops, "bytes": nbytes}


# -- registry instruments ----------------------------------------------------

_FLOPS = counter(
    "mrtpu_device_flops_total",
    "device-engine FLOPs executed (labels: source=measured|analytic, "
    "task)")
_BYTES = counter(
    "mrtpu_device_bytes_total",
    "device-engine bytes accessed per XLA cost model or analytic "
    "fallback (labels: source, task)")
_MFU = gauge(
    "mrtpu_device_mfu",
    "model FLOP/s utilisation of the last device run (achieved / peak)")
_FLOPS_PER_S = gauge(
    "mrtpu_device_model_flops_per_s",
    "achieved model FLOP/s of the last device run (flops / compute_s)")
_INTENSITY = gauge(
    "mrtpu_device_arith_intensity",
    "arithmetic intensity of the last device run (flops / byte)")
_ROOFLINE = gauge(
    "mrtpu_device_roofline_frac",
    "achieved FLOP/s over the roofline-attainable rate "
    "min(peak_flops, intensity * peak_bw) for the last device run")
_PEAK_FLOPS = gauge(
    "mrtpu_device_peak_flops_per_s",
    "assumed aggregate peak FLOP/s (mesh devices x per-device peak)")
_PEAK_BW = gauge(
    "mrtpu_device_peak_bytes_per_s",
    "assumed aggregate peak memory bytes/s")


def record_run(costs: Dict[str, Any], waves: int, compute_s: float,
               n_dev: int, device: Any = None,
               task: str = "-") -> Dict[str, Any]:
    """Publish one device run's cost accounting (counters + derived
    MFU/roofline gauges) and return the derived fields — the engine
    folds them into its ``timings`` dict so they also reach the
    persisted stats doc and ``/statusz`` per-task stats.  *task* is the
    low-cardinality accounting label (the task database name; "-" when
    the engine runs outside the task machinery) the cluster collector
    rolls FLOPs up by."""
    source = str(costs.get("source", "measured"))
    task = task or "-"
    flops = float(costs.get("flops", 0.0)) * max(int(waves), 0)
    nbytes = float(costs.get("bytes", 0.0)) * max(int(waves), 0)
    _FLOPS.inc(flops, source=source, task=task)
    _BYTES.inc(nbytes, source=source, task=task)
    peaks = device_peaks(device)
    peak_f = peaks["flops_per_s"] * max(int(n_dev), 1)
    peak_b = peaks["bytes_per_s"] * max(int(n_dev), 1)
    _PEAK_FLOPS.set(peak_f)
    _PEAK_BW.set(peak_b)
    out: Dict[str, Any] = {
        "flops": flops, "cost_bytes": nbytes, "cost_source": source,
        "peak_source": peaks["peak_source"],
    }
    if compute_s > 0.0 and flops > 0.0:
        fps = flops / compute_s
        intensity = flops / max(nbytes, 1.0)
        attainable = min(peak_f, intensity * peak_b)
        mfu = fps / peak_f
        roof = fps / attainable if attainable > 0 else 0.0
        _FLOPS_PER_S.set(fps)
        _INTENSITY.set(intensity)
        _MFU.set(mfu)
        _ROOFLINE.set(roof)
        out.update({
            "model_flops_per_s": round(fps, 1),
            "arith_intensity": round(intensity, 4),
            "mfu": round(mfu, 8),
            "roofline_frac": round(roof, 6),
        })
    return out


def device_snapshot(registry: Registry = REGISTRY) -> Dict[str, Any]:
    """The device section of /statusz and the ``status`` CLI: this
    PROCESS's device-plane registry state (the engine runs in the
    server/bench process — see the README's per-process scope caveat).
    Zero everywhere simply means no device run happened here."""
    val = registry.value
    # the engine's counters carry a per-task accounting label; the
    # process-wide device section sums over it (superset match)
    return {
        "waves": int(registry.sum("mrtpu_device_waves_total")),
        "retries": int(registry.sum("mrtpu_device_retries_total")),
        "seconds": {
            stage: round(registry.sum("mrtpu_device_seconds_total",
                                      stage=stage), 4)
            for stage in ("upload", "compute", "readback")},
        "flops_total": registry.sum("mrtpu_device_flops_total"),
        "bytes_total": registry.sum("mrtpu_device_bytes_total"),
        "model_flops_per_s": val("mrtpu_device_model_flops_per_s"),
        "mfu": val("mrtpu_device_mfu"),
        "arith_intensity": val("mrtpu_device_arith_intensity"),
        "roofline_frac": val("mrtpu_device_roofline_frac"),
        "peak_flops_per_s": val("mrtpu_device_peak_flops_per_s"),
        "peak_bytes_per_s": val("mrtpu_device_peak_bytes_per_s"),
        "trace_spans": int(registry.sum("mrtpu_trace_spans_total")),
        "trace_dropped": int(val("mrtpu_trace_dropped_total")),
    }


# -- profile bundles ---------------------------------------------------------

#: files every bundle contains (the manifest lists what actually landed)
BUNDLE_FILES = ("manifest.json", "metrics.prom", "statusz.json",
                "trace.json")


def validate_trace(doc: Any) -> None:
    """Strict structural check of a Chrome trace-event object: the shape
    Perfetto accepts, enforced the way parse_prometheus enforces
    exposition — any violation raises ValueError."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("trace: not a Chrome trace-event object "
                         "(missing traceEvents)")
    events = doc["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("trace: traceEvents is not a list")
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            raise ValueError(f"trace event {i}: not an object")
        if e.get("ph") == "M":
            # metadata events (process_name tracks in the merged cluster
            # timeline) carry no interval — only identity
            missing = {"name", "pid"} - set(e)
            if missing:
                raise ValueError(
                    f"trace event {i}: metadata missing {sorted(missing)}")
            continue
        missing = {"name", "ph", "ts", "dur", "pid", "tid"} - set(e)
        if missing:
            raise ValueError(f"trace event {i}: missing {sorted(missing)}")
        if e["ph"] != "X":
            raise ValueError(f"trace event {i}: ph {e['ph']!r} != 'X'")
        if not isinstance(e["ts"], (int, float)) or e["ts"] < 0:
            raise ValueError(f"trace event {i}: bad ts {e['ts']!r}")
        if not isinstance(e["dur"], (int, float)) or e["dur"] < 0:
            raise ValueError(f"trace event {i}: bad dur {e['dur']!r}")


def validate_compile_ledger(doc: Any) -> None:
    """Strict structural check of a bundle's ``compile_ledger.json``:
    every bucket must name its program and carry numeric compile
    seconds and a memory footprint dict — enforced on write AND reload
    so a bundle that loads is a bundle the analysis tools accept."""
    if not isinstance(doc, dict) or doc.get("kind") != \
            "mrtpu-compile-ledger":
        raise ValueError("compile ledger: not a mrtpu-compile-ledger "
                         "document")
    buckets = doc.get("buckets")
    if not isinstance(buckets, list):
        raise ValueError("compile ledger: buckets is not a list")
    for i, b in enumerate(buckets):
        if not isinstance(b, dict):
            raise ValueError(f"compile ledger bucket {i}: not an object")
        if not b.get("program"):
            raise ValueError(f"compile ledger bucket {i}: no program")
        for field in ("compile_s", "lowering_s"):
            if not isinstance(b.get(field), (int, float)):
                raise ValueError(
                    f"compile ledger bucket {i}: bad {field} "
                    f"{b.get(field)!r}")
        if not isinstance(b.get("avals"), list):
            raise ValueError(f"compile ledger bucket {i}: no avals")
        if not isinstance(b.get("memory"), dict):
            raise ValueError(
                f"compile ledger bucket {i}: no memory footprint")


def write_bundle(out_dir: str, store: Any = None,
                 metrics_text: Optional[str] = None,
                 statusz_doc: Optional[Dict[str, Any]] = None,
                 trace_doc: Optional[Dict[str, Any]] = None,
                 jax_trace_dir: Optional[str] = None,
                 cluster_doc: Optional[Dict[str, Any]] = None,
                 history: Any = None,
                 registry: Registry = REGISTRY,
                 tracer: Tracer = TRACER) -> str:
    """Capture a self-contained profile bundle into *out_dir*.

    Defaults snapshot THIS process (the bench / in-process cluster
    case): the global registry's exposition, the global tracer's Chrome
    trace, and — with a *store* — the full /statusz cluster snapshot
    (without one, a statusz document carrying just the device section).
    The ``profile`` CLI instead passes the text/docs it fetched from a
    live docserver.  *jax_trace_dir* (a ``jax.profiler`` trace
    directory, typically ``<out_dir>/jax_trace``) is recorded in the
    manifest when it exists.  *cluster_doc* (a ``/clusterz`` merged
    cluster timeline) additionally lands as ``cluster_trace.json`` with
    its structured diagnosis (obs/analysis) as ``diagnosis.json``.
    Returns *out_dir*."""
    from ..coord import docstore  # lazy: the wall-clock mint point

    os.makedirs(out_dir, exist_ok=True)
    if metrics_text is None:
        metrics_text = registry.render()
    parse_prometheus(metrics_text)  # refuse to write a corrupt bundle
    if statusz_doc is None:
        if store is not None:
            from .statusz import cluster_status
            statusz_doc = cluster_status(store)
        else:
            from .statusz import (
                comms_snapshot_section, compile_snapshot,
                memory_snapshot_section)

            statusz_doc = {"tasks": {},
                           "device": device_snapshot(registry)}
            comp = compile_snapshot()
            if comp:
                statusz_doc["compile"] = comp
            mem = memory_snapshot_section()
            if mem:
                statusz_doc["memory"] = mem
            comms_sec = comms_snapshot_section()
            if comms_sec:
                statusz_doc["comms"] = comms_sec
    if trace_doc is None:
        trace_doc = tracer.chrome_trace()
    validate_trace(trace_doc)
    if cluster_doc is not None:
        validate_trace(cluster_doc)

    with open(os.path.join(out_dir, "metrics.prom"), "w",
              encoding="utf-8") as f:
        f.write(metrics_text)
    with open(os.path.join(out_dir, "statusz.json"), "w",
              encoding="utf-8") as f:
        json.dump(statusz_doc, f, indent=1, default=float)
    with open(os.path.join(out_dir, "trace.json"), "w",
              encoding="utf-8") as f:
        json.dump(trace_doc, f)

    files = ["metrics.prom", "statusz.json", "trace.json"]
    # the compile ledger (obs/compile): per-shape-bucket compile
    # seconds, outcomes, per-program memory_analysis footprints and
    # donation savings — the capturing process's record of what it
    # lowered and what that cost
    from .compile import LEDGER

    ledger_doc = {"kind": "mrtpu-compile-ledger", "version": 1,
                  "snapshot": LEDGER.snapshot(),
                  "buckets": LEDGER.buckets()}
    validate_compile_ledger(ledger_doc)
    with open(os.path.join(out_dir, "compile_ledger.json"), "w",
              encoding="utf-8") as f:
        json.dump(ledger_doc, f, indent=1, default=float)
    files.append("compile_ledger.json")
    # the comms plane (obs/comms): the capturing process's exchange
    # traffic matrix roll-ups + overlap fraction — strict-validated on
    # write AND reload like everything else in the bundle.  Only
    # written when an instrumented run happened here: an empty comms
    # file would read as "the exchange sent nothing", which is a lie.
    from .comms import comms_snapshot, validate_comms

    comms_snap = comms_snapshot()
    if comms_snap:
        comms_doc = {"kind": "mrtpu-comms", "version": 1,
                     "snapshot": comms_snap}
        validate_comms(comms_doc)
        with open(os.path.join(out_dir, "comms.json"), "w",
                  encoding="utf-8") as f:
            json.dump(comms_doc, f, indent=1, default=float)
        files.append("comms.json")
    # the serving-SLO plane (obs/slo): per-tenant objective evaluation
    # at capture time — strict-validated on write AND reload.  Only
    # written when some tenant actually produced SLO observations: an
    # empty file would read as "every objective green", which is a lie.
    from .slo import slo_snapshot, validate_slo

    slo_snap = slo_snapshot()
    if slo_snap:
        slo_doc = {"kind": "mrtpu-slo", "version": 1,
                   "snapshot": slo_snap}
        validate_slo(slo_doc)
        with open(os.path.join(out_dir, "slo.json"), "w",
                  encoding="utf-8") as f:
            json.dump(slo_doc, f, indent=1, default=float)
        files.append("slo.json")
    # the control plane (obs/control): every automatic decision with
    # its evidence and measured outcome — strict-validated on write AND
    # reload.  Only written when some controller actually decided
    # something: an empty file would read as "the loop ran and did
    # nothing", which a controllers-disabled run must not claim.
    from .control import control_snapshot, validate_control

    ctrl_snap = control_snapshot()
    if ctrl_snap:
        ctrl_doc = {"kind": "mrtpu-control", "version": 1,
                    "snapshot": ctrl_snap}
        validate_control(ctrl_doc)
        with open(os.path.join(out_dir, "control_ledger.json"), "w",
                  encoding="utf-8") as f:
            json.dump(ctrl_doc, f, indent=1, default=float)
        files.append("control_ledger.json")
    # the alerting plane (obs/alerts): configured rules, instance
    # lifecycle states and silences — same only-when-armed contract as
    # the control ledger, same validate-on-write-AND-reload discipline
    from .alerts import alerts_snapshot, validate_alerts

    alert_snap = alerts_snapshot()
    if alert_snap:
        alert_doc = {"kind": "mrtpu-alerts", "version": 1,
                     "snapshot": alert_snap}
        validate_alerts(alert_doc)
        with open(os.path.join(out_dir, "alerts.json"), "w",
                  encoding="utf-8") as f:
            json.dump(alert_doc, f, indent=1, default=float)
        files.append("alerts.json")
    if cluster_doc is not None:
        from .analysis import diagnose

        with open(os.path.join(out_dir, "cluster_trace.json"), "w",
                  encoding="utf-8") as f:
            json.dump(cluster_doc, f, default=float)
        with open(os.path.join(out_dir, "diagnosis.json"), "w",
                  encoding="utf-8") as f:
            json.dump(diagnose(cluster_doc), f, indent=1, default=float)
        files += ["cluster_trace.json", "diagnosis.json"]
    # the durable history plane (obs/history): the live segment files,
    # copied and RE-VALIDATED after landing (the write-then-reload
    # discipline every artifact here gets) — a bundle then replays the
    # run's whole metric history, not just its final snapshot.  Only
    # written when the history actually holds entries: an empty
    # history/ dir would read as "nothing ever changed", which is a
    # lie.
    history_dir_rel = None
    if history is not None and history.snapshot().get("entries"):
        history.copy_segments(os.path.join(out_dir, "history"))
        history_dir_rel = "history"

    manifest: Dict[str, Any] = {
        "kind": "mrtpu-profile-bundle",
        "version": 1,
        "created_time": docstore.now(),
        "files": files,
        "trace_events": len(trace_doc.get("traceEvents", [])),
    }
    if jax_trace_dir and os.path.isdir(jax_trace_dir):
        manifest["jax_trace_dir"] = os.path.relpath(jax_trace_dir, out_dir)
    if history_dir_rel is not None:
        manifest["history_dir"] = history_dir_rel
    try:
        import jax
        manifest["jax_version"] = jax.__version__
    except ImportError:
        pass  # bundles from engine-less processes are fine
    with open(os.path.join(out_dir, "manifest.json"), "w",
              encoding="utf-8") as f:
        json.dump(manifest, f, indent=1)
    return out_dir


def load_bundle(path: str) -> Dict[str, Any]:
    """Load + re-validate a bundle: the metrics snapshot must survive
    the strict Prometheus parser and the trace must be structurally
    Perfetto-loadable, so a bundle that loads is a bundle the tools
    accept.  Returns ``{"manifest", "metrics_text", "metrics",
    "statusz", "trace"}``."""
    with open(os.path.join(path, "manifest.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    if manifest.get("kind") != "mrtpu-profile-bundle":
        raise ValueError(f"{path}: not a profile bundle manifest")
    with open(os.path.join(path, "metrics.prom"), encoding="utf-8") as f:
        metrics_text = f.read()
    with open(os.path.join(path, "statusz.json"), encoding="utf-8") as f:
        statusz_doc = json.load(f)
    with open(os.path.join(path, "trace.json"), encoding="utf-8") as f:
        trace_doc = json.load(f)
    validate_trace(trace_doc)
    out = {
        "manifest": manifest,
        "metrics_text": metrics_text,
        "metrics": parse_prometheus(metrics_text),
        "statusz": statusz_doc,
        "trace": trace_doc,
    }
    ledger_path = os.path.join(path, "compile_ledger.json")
    if os.path.exists(ledger_path):
        with open(ledger_path, encoding="utf-8") as f:
            ledger_doc = json.load(f)
        validate_compile_ledger(ledger_doc)
        out["compile_ledger"] = ledger_doc
    comms_path = os.path.join(path, "comms.json")
    if os.path.exists(comms_path):
        from .comms import validate_comms

        with open(comms_path, encoding="utf-8") as f:
            comms_doc = json.load(f)
        validate_comms(comms_doc)
        out["comms"] = comms_doc
    slo_path = os.path.join(path, "slo.json")
    if os.path.exists(slo_path):
        from .slo import validate_slo

        with open(slo_path, encoding="utf-8") as f:
            slo_doc = json.load(f)
        validate_slo(slo_doc)
        out["slo"] = slo_doc
    ctrl_path = os.path.join(path, "control_ledger.json")
    if os.path.exists(ctrl_path):
        from .control import validate_control

        with open(ctrl_path, encoding="utf-8") as f:
            ctrl_doc = json.load(f)
        validate_control(ctrl_doc)
        out["control_ledger"] = ctrl_doc
    alerts_path = os.path.join(path, "alerts.json")
    if os.path.exists(alerts_path):
        from .alerts import validate_alerts

        with open(alerts_path, encoding="utf-8") as f:
            alert_doc = json.load(f)
        validate_alerts(alert_doc)
        out["alerts"] = alert_doc
    cluster_path = os.path.join(path, "cluster_trace.json")
    if os.path.exists(cluster_path):
        with open(cluster_path, encoding="utf-8") as f:
            cluster_doc = json.load(f)
        validate_trace(cluster_doc)
        out["cluster_trace"] = cluster_doc
    diag_path = os.path.join(path, "diagnosis.json")
    if os.path.exists(diag_path):
        with open(diag_path, encoding="utf-8") as f:
            out["diagnosis"] = json.load(f)
    hist_dir = os.path.join(path, str(manifest.get("history_dir")
                                      or "history"))
    if os.path.isdir(hist_dir):
        # every entry re-validated; a corrupt segment refuses the load
        # loudly (obs/history.HistoryCorruptError) instead of serving a
        # silently wrong series
        from .history import read_history

        out["history"] = read_history(hist_dir)
    return out
