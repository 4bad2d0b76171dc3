"""The program's own spans and stage scopes, read from a profiler trace.

``trace.py`` knows an operation by the name the trace gives it
(``fusion.151``) and a host span by the name the KIND wrapped around a
call.  This module reads what the program writes itself:

* **program spans** — every span of ``mapreduce_tpu.obs.trace.TRACER`` is
  also a ``TraceAnnotation`` with a ``span_id`` stat, on the host plane,
  on the device operations' clock (``train_step ⊃ {place_batch,
  dispatch}``, ``wordcount ⊃ {split, device_run ⊃ wave ..., readback,
  materialize}``);
* **stage scopes** — both device programs name their stages with
  ``jax.named_scope`` (``tf.ffn``, ``wave.local/sur.compact``).  A scope
  is metadata of the HLO instruction, which the v5e's trace does not
  carry (its events are named by the instruction's text without
  metadata and have timing stats only; read by hand, PR 25), so an
  operation's stage is found by joining the trace's ``(module,
  instruction)`` with the path the program's compile ledger keeps
  (``LEDGER.stage_map``).  An operation belongs to the
  innermost scope on its path; a fusion XLA formed across a scope
  boundary is booked whole to its root's scope.

The reduction works on ``trace.py``'s plain tuples: an operation is
``((instruction, stages), start_ns, duration_ns)``, *stages* the scopes
on its path from the outermost in, so that ``trace.clip``,
``trace.busy_ns`` and ``trace.self_time_by_name`` apply unchanged and the
stages and ``(unscoped)`` sum to the busy time ``trace.summarize``
reports.

A program that writes no span and names no scope (the parent of the PR
that added this file) gives nothing to read: every reader here then
returns ``None`` and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import re
import statistics
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import kernel_work
from benchmark import trace as trace_reader

#: a stage scope inside an op_name path: ``jit(f)/jvp(tf.ffn)/dot_general``
STAGE = re.compile(r"(?<![\w.])(?:tf|wave|sur)\.[a-z_]+")
UNSCOPED = "(unscoped)"
MODULES_LINE = "XLA Modules"
#: the ``read`` groups of ``layer_metrics/*.json`` this module reads
READ_GROUPS = ("stage", "kernel_roofline", "program_span", "idle_under")

Stages = Tuple[str, ...]
StagedOp = Tuple[Tuple[str, Stages], int, int]


def stage_chain(path: Optional[str]) -> Stages:
    """The stage scopes on an op_name *path*, outermost first; JAX wraps
    a scope of the backward pass (``transpose(jvp(tf.ffn))``), which
    still names it."""
    return tuple(STAGE.findall(path)) if path else ()


def ledger_paths() -> Dict[str, Dict[str, str]]:
    """``{HLO module: {instruction: op_name path}}`` of every program the
    compile ledger retains; empty for a program whose ledger has no
    ``stage_map``.  Reads ``Compiled.as_text()``: call it after the
    measured window, never inside."""
    from mapreduce_tpu.obs.compile import LEDGER

    stage_map = getattr(LEDGER, "stage_map", None)
    out: Dict[str, Dict[str, str]] = {}
    if stage_map is not None:
        for program in LEDGER.snapshot().get("programs", {}):
            for module, paths in stage_map(program).items():
                out.setdefault(module, {}).update(paths)
    return out


def _module_name(event_name: str) -> str:
    """``jit_train_step(3912847)`` on the ``XLA Modules`` line is module
    ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def read_xplane(path: str, paths: Dict[str, Dict[str, str]],
                host_ops: bool = False
                ) -> Tuple[Dict[str, List[StagedOp]], list]:
    """``({device plane: its staged operations}, program spans)`` of one
    trace file; *paths* is :func:`ledger_paths`'s.

    A TPU plane's operations are the events of its ``XLA Ops`` line,
    named by the instruction's HLO text without its metadata; their
    module is the event of the ``XLA Modules`` line that encloses them
    (``jit_train_step(<fingerprint>)``).  With *host_ops* the CPU
    backend's operations (events with ``hlo_op`` and ``hlo_module``
    stats on the host plane's thread lines) are read as one more device,
    which is how the join is tested without a chip.  A program span is a
    host event with a ``span_id`` stat, as ``(name, start_ns,
    duration_ns)``."""
    from jax.profiler import ProfileData

    def staged(instr: str, module: Optional[str], event) -> StagedOp:
        chain = stage_chain(paths.get(module, {}).get(instr))
        return ((instr, chain), int(event.start_ns), int(event.duration_ns))

    devices: Dict[str, List[StagedOp]] = {}
    spans: list = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reader.DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if trace_reader.OPS_LINE not in lines:
                continue
            runs = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns),
                 _module_name(e.name))
                for e in getattr(lines.get(MODULES_LINE), "events", ()))
            starts = [r[0] for r in runs]
            ops = devices[plane.name] = []
            for e in lines[trace_reader.OPS_LINE].events:
                at = bisect.bisect_right(starts, int(e.start_ns)) - 1
                module = (runs[at][2] if at >= 0
                          and e.start_ns < runs[at][1] else None)
                ops.append(staged(trace_reader.op_name(e.name), module, e))
        elif plane.name == trace_reader.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "span_id" in stats:
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
                    elif host_ops and "hlo_op" in stats:
                        devices.setdefault(plane.name, []).append(staged(
                            str(stats["hlo_op"]), stats.get("hlo_module"),
                            e))
    return devices, spans


def reduce(devices: Dict[str, List[StagedOp]], program_spans: list,
           window_marks: Iterable[trace_reader.Interval]) -> dict:
    """What the readers below read, by ``trace.summarize``'s rules: the
    window is the hull of *window_marks* (the kind's window spans), the
    chip is the one with least busy time, every busy instant belongs to
    the operation that started last.  Holds the window and busy
    nanoseconds, self time by ``(instruction, stages)``, the number of
    events of each instruction, idle nanoseconds by the innermost
    program span, and the program spans' durations by name."""
    marks = list(window_marks)
    out = {"durations": {}, "by_key": {}, "calls": {}, "idle_by_span": {},
           "window_ns": 0, "busy_ns": 0}
    for name, _start, dur in program_spans:
        out["durations"].setdefault(name, []).append(dur)
    if not marks or not devices:
        return out
    window = (min(lo for lo, _ in marks), max(hi for _, hi in marks))
    per = {name: trace_reader.clip(ops, window)
           for name, ops in devices.items()}
    busy = {name: trace_reader.busy_ns(ops) for name, ops in per.items()}
    worst = min(busy, key=busy.get)
    if not busy[worst]:
        return out
    for (instr, _chain), _s, _d in per[worst]:
        out["calls"][instr] = out["calls"].get(instr, 0) + 1
    out.update(
        window_ns=window[1] - window[0], busy_ns=busy[worst],
        by_key=trace_reader.self_time_by_name(per[worst]),
        idle_by_span=trace_reader.label_gaps(
            trace_reader.gaps(per[worst], window), program_spans))
    return out


def by_stage(by_key: Dict[Tuple[str, Stages], int]) -> Dict[str, int]:
    """Self nanoseconds by innermost stage; operations on no stage's
    path are ``(unscoped)``.  The values sum to the busy time."""
    out: Dict[str, int] = {}
    for (_instr, chain), ns in by_key.items():
        stage = chain[-1] if chain else UNSCOPED
        out[stage] = out.get(stage, 0) + ns
    return out


def stage_ns(by_key: Dict[Tuple[str, Stages], int], stage: str,
             except_events: Sequence[str] = ()) -> int:
    """Self nanoseconds of the operations with *stage* anywhere on their
    path (``wave.local`` holds its ``sur.*`` stages), those named in
    *except_events* left out; ``(unscoped)`` is the operations on no
    stage's path."""
    total = 0
    for (instr, chain), ns in by_key.items():
        if (stage in chain) if stage != UNSCOPED else not chain:
            if not any(_matches(instr, n) for n in except_events):
                total += ns
    return total


def _matches(instr: str, name: str) -> bool:
    """Kernel ``flash_fwd`` runs as ``flash_fwd`` or ``flash_fwd.3``
    (``trace.matching_ns``'s rule)."""
    return instr == name or instr.startswith(name + ".")


def kernel_roofline(program: dict, kernel: str) -> Optional[float]:
    """% of its roofline the calls of *kernel* reached: the least time
    the chip could take for the work they require over their traced self
    time."""
    config = program["config"]
    train = config.get("train", {})
    self_ns = sum(ns for (instr, _chain), ns in program["by_key"].items()
                  if _matches(instr, kernel))
    calls = sum(n for instr, n in program["calls"].items()
                if _matches(instr, kernel))
    peak = kernel_work.peaks(program["device_kind"])
    work = kernel_work.kernel_call_work(
        kernel, config.get("model", {}), int(train.get("batch", 0)),
        int(train.get("seq_len", 0)))
    if not (self_ns and calls and peak and work):
        return None
    least, bound = kernel_work.least_seconds(work, peak)
    print(f"# {kernel}: {calls} calls in {self_ns / 1e6:.1f} ms, least "
          f"{calls * least * 1e3:.1f} ms ({bound}-bound)", file=sys.stderr)
    return 100.0 * calls * least / (self_ns / 1e9)


def read_layer_metric(read: dict, program: dict) -> Optional[float]:
    """One per-layer metric by a ``read`` group of :data:`READ_GROUPS`;
    ``None`` where the trace holds nothing to read it from."""
    if "program_span" in read:
        durs = program["durations"].get(read["program_span"])
        return (statistics.median(durs) / 1e9 * read.get("scale", 1.0)
                if durs else None)
    if "idle_under" in read:
        ns = program["idle_by_span"].get(read["idle_under"])
        return 100.0 * ns / program["window_ns"] if ns else None
    if not any(chain for _instr, chain in program["by_key"]):
        return None              # the program names no stage
    if "kernel_roofline" in read:
        return kernel_roofline(program, read["kernel_roofline"])
    ns = stage_ns(program["by_key"], read["stage"],
                  read.get("except_events", ()))
    return 100.0 * ns / program["busy_ns"]


def table(program: dict) -> List[str]:
    """The stage table as lines for stderr: every stage by innermost
    scope with its share of busy time, then the largest operations no
    stage claims."""
    busy = program["busy_ns"]
    if not busy:
        return []
    rows = sorted(by_stage(program["by_key"]).items(), key=lambda kv: -kv[1])
    lines = [f"stage table: {busy / 1e6:.1f} ms busy of "
             f"{program['window_ns'] / 1e6:.1f} ms"]
    lines += [f"  {stage:<28}{ns / 1e6:>10.2f} ms {100.0 * ns / busy:>6.2f}%"
              for stage, ns in rows]
    loose = sorted(((ns, instr) for (instr, chain), ns
                    in program["by_key"].items() if not chain),
                   reverse=True)[:8]
    if loose:
        lines.append("  largest (unscoped): " + ", ".join(
            f"{instr} {ns / 1e6:.2f} ms" for ns, instr in loose))
    return lines
