"""Device-memory observability: per-program HBM footprints, live
device-memory gauges, donation accounting, capacity-retry forensics.

Device memory is the force behind the engine's whole capacity/retry
machinery — static capacities exist so a wave's working set FITS — yet
until this module nothing observed it.  Three sources, mirrored on
:mod:`.profile`'s cost-model design (measured when the backend offers
it, a labelled analytic estimate when it does not, never a silent
blank):

* **per-program footprints** — ``Compiled.memory_analysis()``
  (argument / output / temp / generated-code bytes, plus the aliased
  bytes donation actually reclaimed).  Backends without a usable
  analysis fall back to :func:`analytic_program_memory`, labelled
  ``source="analytic"`` exactly like the cost model's fallback.
* **live per-device memory** — ``Device.memory_stats()``
  (bytes_in_use / peak_bytes_in_use / bytes_limit), sampled per engine
  wave and per train epoch.  The CPU backend returns ``None``; the
  caller then supplies its own first-party estimate (the engine's wave
  ledger + accumulator bytes) so the gauges still render, labelled
  analytic.
* **donation effectiveness** — bytes the donated accumulator /
  epoch-batch actually save versus an undonated footprint: the
  compiled module's ``alias_size_in_bytes`` when nonzero, else the
  donated argument bytes clipped to the output bytes they could alias.

**Capacity-retry forensics**: every engine capacity retry emits ONE
structured ``capacity_retry`` trace event carrying the program
footprint and the per-device memory state, so ``cli diagnose`` can say
"retry was HBM-bound: footprint X of Y" instead of "it retried".

The module keeps a small last-sample mirror of everything it publishes
(:func:`memory_snapshot`) because gauges are write-only through the
registry API — /statusz and the profile bundles read the mirror, the
exposition plane reads the gauges, and both come from the same
``record_*`` call so they cannot drift.

Monotonic-only module (AST-linted): it emits trace events.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .metrics import counter, gauge
from .trace import TRACER

# -- instruments -------------------------------------------------------------

_DEVICE_MEMORY = gauge(
    "mrtpu_device_memory_bytes",
    "live per-device memory (labels: device, stat=bytes_in_use|"
    "peak_bytes_in_use|bytes_reserved|peak_bytes_reserved|bytes_limit, "
    "source=measured|analytic; analytic "
    "= the engine's own held-bytes ledger on backends without "
    "memory_stats)")
_PROGRAM_MEMORY = gauge(
    "mrtpu_program_memory_bytes",
    "per-program HBM footprint from Compiled.memory_analysis (labels: "
    "program, kind=arguments|outputs|temp|generated_code|total, "
    "source=measured|analytic)")
_DONATION_SAVED = gauge(
    "mrtpu_device_donation_saved_bytes",
    "bytes the program's donated inputs save vs an undonated footprint "
    "(labels: program, source): measured = the compiled module's "
    "aliased bytes, analytic = donated argument bytes clipped to the "
    "outputs they could alias")
_RETRY_EVENTS = counter(
    "mrtpu_device_capacity_retry_events_total",
    "engine capacity retries that emitted a memory-forensics event "
    "(labels: task, bound=hbm|capacity)")

#: bytes_in_use / bytes_limit above this ratio classifies a capacity
#: retry (and a diagnose note) as HBM-bound rather than merely
#: static-capacity-bound
HBM_PRESSURE_RATIO = 0.8

# -- last-sample mirror (what /statusz and bundles read) ---------------------

_STATE_LOCK = threading.Lock()
_STATE: Dict[str, Dict[str, Any]] = {
    "devices": {}, "programs": {}, "donation": {}}


_FOOTPRINT_FIELDS: Tuple[Tuple[str, str], ...] = (
    ("arguments", "argument_size_in_bytes"),
    ("outputs", "output_size_in_bytes"),
    ("temp", "temp_size_in_bytes"),
    ("generated_code", "generated_code_size_in_bytes"),
    ("alias", "alias_size_in_bytes"),
)


def _nbytes(aval: Any) -> int:
    """Bytes of one shaped leaf (ShapeDtypeStruct or array)."""
    import numpy as np

    shape = tuple(getattr(aval, "shape", ()) or ())
    size = 1
    for d in shape:
        size *= int(d)
    return size * np.dtype(getattr(aval, "dtype", "uint8")).itemsize


# -- per-program footprints --------------------------------------------------


def program_memory(compiled: Any) -> Optional[Dict[str, Any]]:
    """Normalised HBM footprint of one executable from XLA's own
    ``memory_analysis()``.  ``None`` when the backend exposes none (or
    an unusable all-zero one) — callers then fall back to
    :func:`analytic_program_memory`, mirroring
    :func:`.profile.program_costs`."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # backend without a memory model: use the fallback
        return None
    if ma is None:
        return None
    out: Dict[str, Any] = {}
    for key, attr in _FOOTPRINT_FIELDS:
        try:
            out[key] = max(int(getattr(ma, attr)), 0)
        except (AttributeError, TypeError, ValueError):
            out[key] = 0
    total = (out["arguments"] + out["outputs"] + out["temp"]
             + out["generated_code"])
    if total <= 0:
        return None
    out["total"] = total
    out["source"] = "measured"
    return out


def analytic_program_memory(arg_avals: Sequence[Any],
                            out_avals: Sequence[Any] = (),
                            ) -> Dict[str, Any]:
    """Rough footprint when XLA's analysis is unavailable: the argument
    and (known) output bytes are exact from the avals; temp is taken as
    one argument-sized working copy (the engine's programs are
    sort-dominated — one extra record-buffer copy is the right order of
    magnitude).  Labelled ``source="analytic"`` everywhere it lands."""
    import jax

    args = sum(_nbytes(a) for a in jax.tree_util.tree_leaves(arg_avals))
    outs = sum(_nbytes(a) for a in jax.tree_util.tree_leaves(out_avals))
    return {"arguments": args, "outputs": outs, "temp": args,
            "generated_code": 0, "alias": 0,
            "total": args + outs + args, "source": "analytic"}


def record_program_memory(program: str, mem: Dict[str, Any]) -> None:
    """Publish one program's footprint (gauges + the snapshot mirror)."""
    source = str(mem.get("source", "measured"))
    for kind in ("arguments", "outputs", "temp", "generated_code",
                 "total"):
        _PROGRAM_MEMORY.set(float(mem.get(kind, 0)), program=program,
                            kind=kind, source=source)
    with _STATE_LOCK:
        _STATE["programs"][program] = dict(mem)


def donation_savings(mem: Optional[Dict[str, Any]],
                     arg_avals: Sequence[Any],
                     donate_argnums: Iterable[int]) -> Dict[str, Any]:
    """Bytes the donated inputs save vs an undonated footprint.  The
    compiled module's aliased bytes are the measurement (an undonated
    build would have allocated them twice); when the backend reports
    none, the donated argument bytes clipped to the output bytes they
    could alias stand in, labelled analytic."""
    donated = 0
    args = list(arg_avals)
    for i in donate_argnums:
        if 0 <= int(i) < len(args):
            donated += sum(_nbytes(a) for a in
                           _tree_leaves(args[int(i)]))
    if mem and int(mem.get("alias", 0)) > 0:
        return {"bytes": int(mem["alias"]), "donated_bytes": donated,
                "source": "measured"}
    outs = int(mem.get("outputs", 0)) if mem else 0
    saved = min(donated, outs) if outs else donated
    return {"bytes": saved, "donated_bytes": donated,
            "source": "analytic"}


def _tree_leaves(x: Any) -> List[Any]:
    import jax

    return jax.tree_util.tree_leaves(x)


def record_donation(program: str, sav: Dict[str, Any]) -> None:
    _DONATION_SAVED.set(float(sav.get("bytes", 0)), program=program,
                        source=str(sav.get("source", "analytic")))
    with _STATE_LOCK:
        _STATE["donation"][program] = dict(sav)


# -- live device memory ------------------------------------------------------


def device_memory(devices: Sequence[Any]) -> List[Dict[str, Any]]:
    """Raw per-device ``memory_stats()`` readings: one dict per device
    with ``stats=None`` where the backend exposes nothing (CPU)."""
    out: List[Dict[str, Any]] = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # backends raise instead of returning None
            stats = None
        out.append({"device": str(getattr(d, "id", "?")),
                    "platform": str(getattr(d, "platform", "?")),
                    "stats": stats})
    return out


def sample_device_memory(devices: Sequence[Any],
                         analytic_bytes_in_use: Optional[int] = None,
                         ) -> Dict[str, Any]:
    """Sample every device's memory into the gauges (the per-wave /
    per-epoch hook).  Where ``memory_stats()`` is absent the caller's
    own estimate (*analytic_bytes_in_use*, e.g. the engine's held-wave
    + accumulator bytes) renders instead, labelled analytic — the
    gauges never silently vanish on the CPU backend.  Returns the
    summary dict that also lands in retry-forensics events."""
    summary: Dict[str, Any] = {"devices": {}, "source": "measured"}
    measured = False
    for row in device_memory(devices):
        dev = row["device"]
        stats = row["stats"]
        if stats:
            measured = True
            entry = {}
            # a TPU books a loaded program's temporaries apart from
            # the live buffers, as bytes_reserved
            for stat in ("bytes_in_use", "peak_bytes_in_use",
                         "bytes_reserved", "peak_bytes_reserved",
                         "bytes_limit"):
                v = stats.get(stat)
                if v is None:
                    continue
                _DEVICE_MEMORY.set(float(v), device=dev, stat=stat,
                                   source="measured")
                entry[stat] = int(v)
            summary["devices"][dev] = entry
        elif analytic_bytes_in_use is not None:
            share = float(analytic_bytes_in_use) / max(len(devices), 1)
            _DEVICE_MEMORY.set(share, device=dev, stat="bytes_in_use",
                               source="analytic")
            summary["devices"][dev] = {"bytes_in_use": int(share)}
    if not measured:
        summary["source"] = "analytic"
    with _STATE_LOCK:
        _STATE["devices"] = dict(summary["devices"])
        _STATE["device_source"] = summary["source"]
    return summary


# -- capacity-retry forensics ------------------------------------------------


def capacity_retry_event(task: str, attempt: int, overflow_rows: int,
                         program_memory_doc: Optional[Dict[str, Any]],
                         devices: Sequence[Any],
                         old_capacities: Dict[str, int],
                         new_capacities: Dict[str, int],
                         tracer=TRACER) -> str:
    """Emit the structured forensics event for ONE engine capacity
    retry: a zero-duration ``capacity_retry`` span whose args carry the
    memory breakdown (program footprint + live device memory), plus the
    counter ``cli diagnose`` keys its memory-pressure notes off.
    Returns the classification (``"hbm"`` when the device was measurably
    near its byte limit, else ``"capacity"`` — static capacities
    overflowed with HBM headroom unknown or ample)."""
    import time

    mem = sample_device_memory(devices)
    bound = "capacity"
    footprint = int((program_memory_doc or {}).get("total", 0))
    for entry in mem["devices"].values():
        limit = entry.get("bytes_limit")
        in_use = entry.get("bytes_in_use", 0)
        if limit and (max(in_use, footprint) >= HBM_PRESSURE_RATIO
                      * limit):
            bound = "hbm"
            break
    _RETRY_EVENTS.inc(task=task or "-", bound=bound)
    now = time.monotonic()
    tracer.end(
        tracer.begin("capacity_retry", start=now, task=task or "-"),
        now, attempt=int(attempt), overflow_rows=int(overflow_rows),
        bound=bound, program_memory=program_memory_doc,
        device_memory=mem, old_capacities=dict(old_capacities),
        new_capacities=dict(new_capacities))
    return bound


# -- snapshots ---------------------------------------------------------------


def memory_snapshot() -> Dict[str, Any]:
    """The memory section of /statusz, the ``status`` CLI and profile
    bundles: this process's last device samples, per-program
    footprints, and donation savings (empty dict when nothing was ever
    recorded — the section then stays off the page)."""
    with _STATE_LOCK:
        devices = dict(_STATE["devices"])
        programs = {p: dict(m) for p, m in _STATE["programs"].items()}
        donation = {p: dict(s) for p, s in _STATE["donation"].items()}
        source = _STATE.get("device_source")
    if not (devices or programs or donation):
        return {}
    out: Dict[str, Any] = {"programs": programs, "donation": donation}
    if devices:
        out["devices"] = devices
        out["device_source"] = source
    return out


def reset_state() -> None:
    """Tests only: forget the last-sample mirror."""
    with _STATE_LOCK:
        _STATE["devices"] = {}
        _STATE["programs"] = {}
        _STATE["donation"] = {}
        _STATE.pop("device_source", None)
