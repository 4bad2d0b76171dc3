"""From a profiler trace to numbers: device busy time, idle share, time
by operation name, and the idle gaps labelled by what the host was doing.

The reduction works on plain tuples ``(name, start_ns, duration_ns)`` so
that ``tests/test_benchmark.py`` can check it on a hand-made list;
:func:`read_xplane` turns a ``*.xplane.pb`` file into such tuples with
nothing but ``jax.profiler.ProfileData``.

What the v5e's trace looks like (read by hand, PR 24): one plane per chip
named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO operation, a ``while``/``conditional`` event enclosing the
events of its body on the same line; ``XLA Modules`` holds one event per
program execution; the host's ``TraceAnnotation`` spans sit on the
``/host:CPU`` plane's thread lines.  All lines share one clock.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start_ns, duration_ns
Interval = Tuple[int, int]            # start_ns, end_ns

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of *intervals* as sorted, disjoint intervals: overlapping
    and touching ones are united once."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    """*events* cut to *window*; those wholly outside are dropped."""
    w0, w1 = window
    out = []
    for name, start, dur in events:
        lo, hi = max(start, w0), min(start + dur, w1)
        if hi > lo:
            out.append((name, lo, hi - lo))
    return out


def busy_ns(events: Iterable[Event]) -> int:
    """Nanoseconds in which at least one of *events* ran."""
    return sum(hi - lo for lo, hi in
               merge((s, s + d) for _, s, d in events))


def self_time_by_name(events: Iterable[Event]) -> Dict[str, int]:
    """Nanoseconds by event name, every instant counted once, for the
    event that started last among those running: what an enclosing
    event (a ``while`` around its body) shares with the events inside it
    goes to the inner ones.  The values sum to :func:`busy_ns`."""
    out: Dict[str, int] = {}
    stack: List[Tuple[int, str]] = []     # (end, name), by start
    at = 0

    def advance(to: int) -> None:
        nonlocal at
        while at < to:
            while stack and stack[-1][0] <= at:
                stack.pop()
            if not stack:
                at = to
                break
            end, name = stack[-1]
            upto = min(end, to)
            out[name] = out.get(name, 0) + upto - at
            at = upto

    last = 0
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        if dur <= 0:
            continue
        advance(start)
        at = max(at, start)
        stack.append((start + dur, name))
        last = max(last, start + dur)
    advance(last)
    return out


def matching_ns(by_name: Dict[str, int], names: Sequence[str]) -> int:
    """Total of the entries of *by_name* that belong to one of *names*:
    the trace names an operation after its HLO instruction, so kernel
    ``flash_fwd`` appears as ``flash_fwd`` or ``flash_fwd.3``."""
    return sum(ns for op, ns in by_name.items()
               if any(op == n or op.startswith(n + ".") for n in names))


def gaps(events: Iterable[Event], window: Interval) -> List[Interval]:
    """The idle intervals of *window*, longest first."""
    w0, w1 = window
    out, at = [], w0
    for lo, hi in merge((s, s + d) for _, s, d in clip(events, window)):
        if lo > at:
            out.append((at, lo))
        at = hi
    if w1 > at:
        out.append((at, w1))
    return sorted(out, key=lambda g: g[0] - g[1])


def label_gaps(idle: Iterable[Interval],
               spans: Iterable[Event]) -> Dict[str, int]:
    """Idle nanoseconds by the host span that covered them; where
    spans nest, the innermost (shortest) one names the time, and time
    under no span is ``(none)``."""
    spans = sorted(spans, key=lambda e: e[2])     # shortest first
    out: Dict[str, int] = {}
    for lo, hi in idle:
        left = [(lo, hi)]
        for name, start, dur in spans:
            nxt = []
            for a, b in left:
                c, d = max(a, start), min(b, start + dur)
                if d > c:
                    out[name] = out.get(name, 0) + (d - c)
                    nxt += [(a, c)] if c > a else []
                    nxt += [(d, b)] if b > d else []
                else:
                    nxt.append((a, b))
            left = nxt
        for a, b in left:
            out["(none)"] = out.get("(none)", 0) + (b - a)
    return out


def top_ops(by_name: Dict[str, int], kernels: Sequence[str],
            n: int = 10) -> List[list]:
    """The *n* operations with most self time, ``[name, seconds]``.  A
    kernel's calls (``flash_dkv.8`` ... ``flash_dkv.15``, one per layer)
    are summed under the kernel's name; every other operation keeps the
    name the trace gives it."""
    out: Dict[str, int] = {}
    for op, ns in by_name.items():
        base = next((k for k in kernels
                     if op == k or op.startswith(k + ".")), op)
        out[base] = out.get(base, 0) + ns
    top = sorted(out.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def op_name(event_name: str) -> str:
    """The instruction's name from the HLO text the trace gives an
    operation: ``%flash_fwd.8 = bf16[...] custom-call(...)`` is
    ``flash_fwd.8``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def read_xplane(path: str, span_names: Sequence[str]
                ) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """``({device plane name: its XLA-op events}, host spans)`` of one
    trace file; the host spans are the events named in *span_names* on
    any line of the host plane."""
    from jax.profiler import ProfileData

    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    wanted = set(span_names)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events if e.name in wanted]
    return devices, spans


def summarize(devices: Dict[str, List[Event]], spans: List[Event],
              window_span: str) -> dict:
    """The numbers the harness reports from one traced run.

    The window is the hull of the *window_span* host spans (``wc.job`` or
    ``train.step``): the traced jobs or steps with whatever lies between
    them.  ``busy_s`` is averaged over the chips, ``idle_share`` is the
    worst chip's, and the shares by name and the gaps are the worst
    chip's too (the one the others wait for)."""
    marks = [(s, s + d) for n, s, d in spans if n == window_span]
    if not marks or not devices:
        return {}
    window = (min(lo for lo, _ in marks), max(hi for _, hi in marks))
    per = {name: clip(evs, window) for name, evs in devices.items()}
    busy = {name: busy_ns(evs) for name, evs in per.items()}
    if not any(busy.values()):
        return {}
    worst = min(busy, key=busy.get)
    by_name = self_time_by_name(per[worst])
    idle = gaps(per[worst], window)
    by_span = label_gaps(idle, spans)
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "idle_share": 1.0 - busy[worst] / (window[1] - window[0]),
        "busy_ns_worst": busy[worst],
        "by_name": by_name,
        "idle_gaps": [[n, ns / 1e9] for n, ns in
                      sorted(by_span.items(), key=lambda kv: -kv[1])[:10]],
    }
