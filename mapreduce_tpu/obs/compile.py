"""Compile observability: the shape-bucket compile ledger.

Cold compile of the engine's fused wave program is ~100s at bench
shapes (the ``lax.sort`` comparator — utils/compile_cache.py has the
analysis), the persistent XLA cache exists to amortise it, and until
this module NOTHING observed any of it: no hit/miss counters, no
per-program compile seconds, no record of which shapes were ever
lowered.  This is the compile-time analogue of :mod:`.profile`'s
FLOPs/MFU accounting — built on the same compiled-executable
introspection — and the substrate ROADMAP 2's AOT warm-start rides on.

One instrumented helper, :meth:`CompileLedger.compile`, that every
``lower()``/``compile()``/``jax.jit`` first-call in the engine and the
trainers routes through (via :func:`wrap_jit`).  Per acquisition it:

* emits ``compile ⊃ {lowering, backend_compile}`` spans on the PR-2
  tracer, so compiles are visible in the same Perfetto timeline as the
  waves they delay;
* observes per-program compile seconds into the
  ``mrtpu_compile_seconds`` histogram and counts the acquisition in
  ``mrtpu_compile_total{program, outcome}``:

  - ``cached`` — served from the ledger's in-process executable cache
    (zero XLA work; a second same-shape engine build lands here);
  - ``persistent_hit`` — XLA compiled, but the shape bucket was already
    on disk next to an enabled persistent cache, so the backend compile
    was a cache deserialization, not a fresh lowering of the sort
    ladder (classified from the ledger's own on-disk registry — the
    same source of truth ``warmup --replay`` primes from);
  - ``compiled`` — a genuinely fresh backend compile (persistent cache
    cold or disabled; the latter also counts
    ``mrtpu_compile_cache_disabled_total``);

* records the program's HBM footprint and donation savings
  (:mod:`.memory`) off the same compiled executable;
* appends the ``(program, avals, dtypes, shardings, mesh, compile_s)``
  bucket to the **on-disk JSON shape registry** next to the persistent
  cache dir — the record ``cli warmup --replay`` walks to AOT-prime
  *every* program this machine ever lowered, not just the
  DeviceWordCount default.

The in-process executable cache is a bounded LRU shared process-wide:
callers that pass a stable ``key`` (the engine: map_fn + config + mesh
device ids) get genuine cross-instance reuse — building the same
engine twice compiles once — while callers whose closures embed live
hyperparameters (the trainers) omit the key and get observation
without sharing.

Module-level imports stay stdlib (the obs/ contract); jax is touched
lazily and only when already loaded by the caller.

Monotonic-only module (AST-linted): every clock read feeds span
timestamps or compile-seconds histograms.
"""

from __future__ import annotations

import collections
import hashlib
import json
import logging
import os
import re
import sys
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import memory as obs_memory
from .metrics import counter, gauge, histogram
from .trace import TRACER

logger = logging.getLogger("mapreduce_tpu.obs.compile")

#: the shape-bucket registry file, kept next to (inside) the persistent
#: cache dir so the two artifacts travel together: the cache holds the
#: executables, the registry holds the shapes that produced them.
REGISTRY_BASENAME = "mrtpu_shape_registry.json"

#: compile-seconds histogram ladder: 10ms jit trivia up to the ~100s
#: sort-comparator compiles (LATENCY_BUCKETS tops out at 30s).
COMPILE_BUCKETS: Tuple[float, ...] = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
    10.0, 30.0, 60.0, 120.0, 300.0, float("inf"))

_COMPILES = counter(
    "mrtpu_compile_total",
    "instrumented compiled-program acquisitions (labels: program, "
    "outcome=cached [in-process ledger hit, zero XLA work] | "
    "persistent_hit [backend compile served by the persistent cache, "
    "classified from the on-disk shape registry] | compiled [fresh])")
_COMPILE_SECONDS = histogram(
    "mrtpu_compile_seconds",
    "per-program compile seconds (labels: program, "
    "stage=lowering|backend_compile)",
    buckets=COMPILE_BUCKETS)
_CACHE_DISABLED = counter(
    "mrtpu_compile_cache_disabled_total",
    "compiles executed with NO persistent cache configured — every one "
    "is a candidate ~100s the next process re-pays (labels: program)")
_BUCKET_GAUGE = gauge(
    "mrtpu_compile_shape_buckets",
    "shape buckets known to the compile ledger (labels: "
    "scope=memory|disk)")


#: ``Compiled.as_text()``, line by line: a computation's header, one
#: instruction (``%fusion.151 = ... metadata={op_name="jit(f)/..." ...}``),
#: and the computations an instruction calls (a ``while``'s body, a
#: fusion's fused computation, a conditional's branches)
_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.MULTILINE)
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="([^"]*)"')
_HLO_CALLED = re.compile(
    r"\b(?:body|condition|calls|to_apply|branch_computations)="
    r"(?:\{([^}]*)\}|(%?[\w.\-]+))")


def hlo_op_paths(text: str) -> Dict[str, str]:
    """``{instruction name: op_name path}`` of one HLO module's text.
    An instruction the compiler made itself (a copy, a fusion it split)
    carries no path: it takes that of the instruction whose computation
    it sits in, so what runs inside a ``while`` of the loss is the
    loss's."""
    paths: Dict[str, str] = {}
    inside: Dict[str, str] = {}       # instruction -> its computation
    called_by: Dict[str, str] = {}    # computation -> calling instruction
    computation = ""
    for line in text.splitlines():
        instr = _HLO_INSTRUCTION.match(line)
        if instr is None:
            header = _HLO_COMPUTATION.match(line)
            if header is not None:
                computation = header.group(1)
            continue
        name = instr.group(1)
        inside[name] = computation
        op_name = _HLO_OP_NAME.search(line)
        if op_name is not None and op_name.group(1):
            paths[name] = op_name.group(1)
        for several, one in _HLO_CALLED.findall(line):
            for callee in (several or one).split(", "):
                called_by.setdefault(callee.lstrip("%"), name)
    for name, computation in inside.items():
        seen = set()
        while name not in paths and computation in called_by \
                and computation not in seen:
            seen.add(computation)
            caller = called_by[computation]
            if caller in paths:
                paths[name] = paths[caller]
            computation = inside.get(caller, "")
    return paths


def cache_dir() -> Optional[str]:
    """The persistent-cache dir jax is configured with, or None.  Reads
    only an ALREADY-imported jax — a jax-free process asking about the
    cache must not pay a jax import for the answer."""
    mod = sys.modules.get("jax")
    if mod is None:
        return None
    try:
        return mod.config.jax_compilation_cache_dir or None
    except AttributeError:
        return None


def registry_path(dir: Optional[str] = None) -> Optional[str]:
    d = dir or cache_dir()
    return os.path.join(d, REGISTRY_BASENAME) if d else None


# -- fingerprints ------------------------------------------------------------


def _leaf_fp(a: Any) -> Tuple[Any, ...]:
    """In-process signature of one shaped leaf.  Shardings participate
    as OBJECTS (their __eq__/__hash__ are exactly what jax's own
    dispatch cache keys on), so a wave program's output accumulator —
    which carries a NamedSharding equal to the input's — re-dispatches
    without a spurious recompile."""
    return (tuple(a.shape), str(a.dtype), getattr(a, "sharding", None))


def fingerprint(avals: Sequence[Any]) -> Tuple[Any, ...]:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tuple(avals))
    return (treedef,) + tuple(_leaf_fp(a) for a in leaves)


def _aval_doc(a: Any) -> Dict[str, Any]:
    sh = getattr(a, "sharding", None)
    doc: Dict[str, Any] = {"shape": [int(d) for d in a.shape],
                           "dtype": str(a.dtype)}
    if sh is not None:
        doc["sharding"] = str(sh)
    return doc


def _mesh_doc(avals: Sequence[Any]) -> Dict[str, Any]:
    """Mesh/backend identity for the bucket: device count and kind from
    the first sharded aval (the persistent cache keys on the same)."""
    import jax

    for a in jax.tree_util.tree_leaves(tuple(avals)):
        sh = getattr(a, "sharding", None)
        if sh is None:
            continue
        try:
            devs = sorted(sh.device_set, key=lambda d: d.id)
        except (AttributeError, TypeError):
            continue
        if devs:
            return {"n_devices": len(devs),
                    "device_kind": str(getattr(devs[0], "device_kind",
                                               "?")),
                    "platform": str(getattr(devs[0], "platform", "?"))}
    mod = sys.modules.get("jax")
    backend = "?"
    if mod is not None:
        try:
            backend = mod.default_backend()
        except RuntimeError:
            pass  # backend not initialisable: identity stays unknown
    return {"n_devices": 1, "device_kind": "?", "platform": backend}


def bucket_id(program: str, avals: Sequence[Any],
              extra: Sequence[Any] = ()) -> str:
    """Stable cross-process identity of one shape bucket: program name,
    every leaf's shape/dtype/sharding string, the mesh identity, the
    caller's extra tokens (map_fn path, config key), and the jax
    version (persistent-cache entries do not survive version bumps, so
    neither should a bucket's warm-start claim)."""
    import jax

    doc = {
        "program": program,
        "avals": [_aval_doc(a)
                  for a in jax.tree_util.tree_leaves(tuple(avals))],
        "extra": [str(x) for x in extra],
        "mesh": _mesh_doc(avals),
        "jax": jax.__version__,
    }
    blob = json.dumps(doc, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def op_token(op: Any) -> str:
    """Stable cross-process spelling of a reduce op / map fn for bucket
    identity: strings pass through, functions become module:qualname
    (an id()-bearing repr would fracture buckets across processes)."""
    if isinstance(op, str):
        return op
    mod = getattr(op, "__module__", None)
    qual = getattr(op, "__qualname__", None)
    if mod and qual:
        return f"{mod}:{qual}"
    return repr(op)


def fn_path(fn: Any) -> Optional[str]:
    """``module:qualname`` when *fn* is importable from its module (the
    replay contract); None for lambdas/locals, which cannot replay."""
    mod = getattr(fn, "__module__", None)
    qual = getattr(fn, "__qualname__", None)
    if not mod or not qual or "<" in qual:
        return None
    return f"{mod}:{qual}"


def resolve_fn(path: str) -> Any:
    """Inverse of :func:`fn_path` (used by ``warmup --replay``)."""
    import importlib

    mod_name, _, qual = path.partition(":")
    obj: Any = importlib.import_module(mod_name)
    for part in qual.split("."):
        obj = getattr(obj, part)
    return obj


# -- the ledger --------------------------------------------------------------


class CompileLedger:
    """Process-wide compile accounting + bounded executable reuse."""

    def __init__(self, tracer=TRACER,
                 max_executables: Optional[int] = None) -> None:
        self._tracer = tracer
        self._lock = threading.Lock()
        #: exec-cache: (program, key, sig) -> (Compiled, bucket_id).
        #: Bounded LRU — eviction only forfeits reuse, never correctness.
        self._execs: "collections.OrderedDict[Any, Tuple[Any, str]]" = \
            collections.OrderedDict()
        self._records: Dict[str, Dict[str, Any]] = {}
        #: (registry path, mtime_ns, bucket count) — snapshot() serves
        #: /statusz scrapes (typically every second) from this instead
        #: of re-parsing the whole registry file per scrape
        self._disk_count_cache: Optional[Tuple[str, int, int]] = None
        self._disk_buckets_cache: Optional[
            Tuple[str, int, Dict[str, Dict[str, Any]]]] = None
        if max_executables is None:
            max_executables = int(os.environ.get(
                "MAPREDUCE_TPU_EXEC_CACHE", "32"))
        self.max_executables = max(1, max_executables)

    # -- disk registry -----------------------------------------------------

    def _load_disk(self, path: str) -> Dict[str, Dict[str, Any]]:
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return {}
        buckets = doc.get("buckets")
        return buckets if isinstance(buckets, dict) else {}

    def _persist(self, path: str, bucket: str,
                 record: Dict[str, Any]) -> None:
        """Read-merge-write the on-disk registry (atomic replace; a
        concurrent writer's losing bucket re-appends on its next
        compile — best effort by design, never a compile failure)."""
        try:
            buckets = self._load_disk(path)
            prev = buckets.get(bucket) or {}
            merged = dict(record)
            merged["count"] = int(prev.get("count", 0)) + 1
            if prev.get("best_compile_s") is not None:
                merged["best_compile_s"] = min(
                    float(prev["best_compile_s"]),
                    float(record["compile_s"]))
            else:
                merged["best_compile_s"] = float(record["compile_s"])
            buckets[bucket] = merged
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                # schema v2: records MAY carry a tier (the tiered wave
                # programs do) — and since sort_impl is part of the
                # bucket id, a bucket's every compile (best_compile_s
                # included) comes from that one tier.  The loader
                # accepts v1 files unchanged — the field just reads as
                # absent.
                json.dump({"kind": "mrtpu-shape-registry", "version": 2,
                           "buckets": buckets}, f, indent=1,
                          default=float)
            os.replace(tmp, path)
            _BUCKET_GAUGE.set(len(buckets), scope="disk")
            try:
                with self._lock:
                    self._disk_count_cache = (
                        path, os.stat(path).st_mtime_ns, len(buckets))
            except OSError:
                pass
        except OSError as exc:
            # str(exc), never the live exception: a retained LogRecord
            # (pytest caplog, buffering handlers) holding exc would pin
            # its traceback's whole call stack — including the dispatch
            # frame's donated wave arrays — past their free point
            logger.warning("shape registry %s not updated: %s",
                           path, str(exc))

    def disk_buckets(self,
                     dir: Optional[str] = None,
                     ) -> Dict[str, Dict[str, Any]]:
        """The on-disk shape registry next to the (given or configured)
        cache dir; empty when no cache dir is configured.  Mtime-cached
        like :meth:`_disk_count`: the capacity controller consults this
        at every autotuned run entry, which must not cost a full JSON
        parse in steady state (callers treat the result as read-only)."""
        path = registry_path(dir)
        if not path:
            return {}
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:
            return {}
        with self._lock:
            cached = self._disk_buckets_cache
        if cached and cached[0] == path and cached[1] == mtime:
            return cached[2]
        buckets = self._load_disk(path)
        with self._lock:
            self._disk_buckets_cache = (path, mtime, buckets)
        return buckets

    def _disk_count(self, cdir: str) -> int:
        """Bucket count of the on-disk registry, mtime-cached: the
        scrape path must not pay a full JSON parse per /statusz hit."""
        path = registry_path(cdir)
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:
            return 0
        with self._lock:
            cached = self._disk_count_cache
        if cached and cached[0] == path and cached[1] == mtime:
            return cached[2]
        n = len(self._load_disk(path))
        with self._lock:
            self._disk_count_cache = (path, mtime, n)
        return n

    # -- warmness probe (the tiered-dispatch policy input) -------------------

    def warmness(self, program: str, key: Any, arg_structs: Sequence[Any],
                 bucket_extra: Sequence[Any] = ()) -> str:
        """How warm one (program, key, shapes) bucket is WITHOUT
        compiling anything: ``"cached"`` (the in-process executable LRU
        would serve it outright), ``"persistent"`` (a configured
        persistent cache already holds the bucket per the on-disk shape
        registry, so the backend compile would be a fast
        deserialization), or ``"cold"`` (a fresh backend compile — the
        case the tiered engine serves on tier-0 while tier-1 builds in
        the background)."""
        sig = fingerprint(arg_structs)
        with self._lock:
            if (program, key, sig) in self._execs:
                return "cached"
        cdir = cache_dir()
        if cdir and bucket_id(program, arg_structs,
                              bucket_extra) in self.disk_buckets(cdir):
            return "persistent"
        return "cold"

    # -- the instrumented helper -------------------------------------------

    def compile(self, jitted: Any, arg_structs: Sequence[Any], *,
                program: str, key: Any = None,
                donate_argnums: Sequence[int] = (),
                replay: Optional[Dict[str, Any]] = None,
                bucket_extra: Sequence[Any] = (),
                tier: Optional[int] = None) -> Tuple[Any, str]:
        """Acquire the compiled executable for *jitted* at
        *arg_structs*, instrumented.  Returns ``(compiled, outcome)``.

        *key* opts into cross-instance executable sharing: callers must
        pass one ONLY when it captures everything the program closes
        over (the engine's map_fn + config + mesh device ids); with
        ``key=None`` the jit object itself keys the entry, so distinct
        instances never alias."""
        import time

        sig = fingerprint(arg_structs)
        ck = (program, key if key is not None else jitted, sig)
        with self._lock:
            hit = self._execs.get(ck)
            if hit is not None:
                self._execs.move_to_end(ck)
        if hit is not None:
            compiled, bucket = hit
            _COMPILES.inc(program=program, outcome="cached")
            with self._lock:
                rec = self._records.get(bucket)
                if rec is not None:
                    rec["count"] += 1
                    rec["outcomes"]["cached"] = (
                        rec["outcomes"].get("cached", 0) + 1)
            return compiled, "cached"

        cdir = cache_dir()
        bucket = bucket_id(program, arg_structs, bucket_extra)
        known_on_disk = bool(cdir) and bucket in self.disk_buckets(cdir)
        t0 = time.monotonic()
        with self._tracer.span("compile", program=program) as sp:
            with self._tracer.span("lowering", program=program):
                lowered = jitted.lower(*arg_structs)
            t_low = time.monotonic() - t0
            t1 = time.monotonic()
            with self._tracer.span("backend_compile", program=program):
                compiled = lowered.compile()
            t_comp = time.monotonic() - t1
            outcome = ("persistent_hit" if (cdir and known_on_disk)
                       else "compiled")
            sp.args.update(outcome=outcome,
                           lowering_s=round(t_low, 4),
                           backend_compile_s=round(t_comp, 4))
        _COMPILES.inc(program=program, outcome=outcome)
        if not cdir:
            _CACHE_DISABLED.inc(program=program)
        _COMPILE_SECONDS.observe(t_low, program=program,
                                 stage="lowering")
        _COMPILE_SECONDS.observe(t_comp, program=program,
                                 stage="backend_compile")

        mem = obs_memory.program_memory(compiled)
        if mem is None:
            mem = obs_memory.analytic_program_memory(arg_structs)
        obs_memory.record_program_memory(program, mem)
        donation = None
        if donate_argnums:
            donation = obs_memory.donation_savings(
                mem, list(arg_structs), donate_argnums)
            obs_memory.record_donation(program, donation)

        import jax

        record: Dict[str, Any] = {
            "program": program,
            "avals": [_aval_doc(a) for a in
                      jax.tree_util.tree_leaves(tuple(arg_structs))],
            "mesh": _mesh_doc(arg_structs),
            "extra": [str(x) for x in bucket_extra],
            "compile_s": round(t_comp, 4),
            "lowering_s": round(t_low, 4),
            "memory": mem,
            "jax": jax.__version__,
            "count": 1,
            "outcomes": {outcome: 1},
        }
        if tier is not None:
            # which compile tier produced this bucket (0 = fast-compile
            # argsort serving tier, 1 = steady-state variadic) — the
            # registry's schema-v2 field; v1 registries simply lack it
            record["tier"] = int(tier)
        if donation is not None:
            record["donation"] = donation
        if replay is not None:
            record["replay"] = replay
        with self._lock:
            prev = self._records.get(bucket)
            if prev is not None:
                record["count"] = prev["count"] + 1
                outs = dict(prev["outcomes"])
                outs[outcome] = outs.get(outcome, 0) + 1
                record["outcomes"] = outs
            self._records[bucket] = record
            self._execs[ck] = (compiled, bucket)
            while len(self._execs) > self.max_executables:
                self._execs.popitem(last=False)
            n_mem = len(self._records)
        _BUCKET_GAUGE.set(n_mem, scope="memory")
        if cdir:
            self._persist(registry_path(cdir), bucket, record)
        return compiled, outcome

    # -- from instruction to stage -------------------------------------------

    def stage_map(self, program: str) -> Dict[str, Dict[str, str]]:
        """``{HLO module name: {instruction name: op_name path}}`` of the
        executables of *program* the ledger retains: the program's own
        account of which ``jax.named_scope`` each instruction came from
        (``fusion.151 -> jit(wave)/.../wave.local/sur.compact/gather``).
        A device trace names an operation by its instruction and its
        module; this is the other half of that join
        (:func:`hlo_op_paths`).  A fusion carries its root's path, so
        one that XLA formed across a scope boundary is booked whole to
        its root's scope.  Two retained executables
        whose modules share a name share an entry, the newer one's
        instructions winning.

        Computed on demand from ``Compiled.as_text()`` — megabytes for
        the wave program — so a trace reader calls it after the run,
        never inside one."""
        with self._lock:
            execs = [compiled for (prog, _key, _sig), (compiled, _bucket)
                     in self._execs.items() if prog == program]
        out: Dict[str, Dict[str, str]] = {}
        for compiled in execs:
            text = compiled.as_text()
            module = _HLO_MODULE.search(text)
            out.setdefault(module.group(1) if module else "", {}).update(
                hlo_op_paths(text))
        return out

    # -- snapshots ---------------------------------------------------------

    def buckets(self) -> List[Dict[str, Any]]:
        """The in-process ledger's buckets (id + record), for the
        profile bundle's ``compile_ledger.json``."""
        with self._lock:
            return [dict(rec, bucket=b)
                    for b, rec in self._records.items()]

    def snapshot(self) -> Dict[str, Any]:
        """The compile section of /statusz and the ``status`` CLI:
        per-program acquisition counts/outcomes and compile seconds,
        plus where the persistent artifacts live."""
        with self._lock:
            records = [dict(r) for r in self._records.values()]
        programs: Dict[str, Dict[str, Any]] = {}
        for rec in records:
            p = programs.setdefault(rec["program"], {
                "buckets": 0, "compiled": 0, "cached": 0,
                "persistent_hit": 0, "compile_s": 0.0,
                "last_compile_s": 0.0})
            p["buckets"] += 1
            outs = rec.get("outcomes") or {}
            p["compiled"] += int(outs.get("compiled", 0))
            p["cached"] += int(outs.get("cached", 0))
            p["persistent_hit"] += int(outs.get("persistent_hit", 0))
            # each record keeps its LAST real compile's seconds; summed
            # per program this is the "seconds XLA spent" answer (the
            # histogram carries the full distribution)
            secs = float(rec.get("compile_s", 0.0)) \
                + float(rec.get("lowering_s", 0.0))
            p["compile_s"] = round(p["compile_s"] + secs, 4)
            p["last_compile_s"] = round(secs, 4)
        out: Dict[str, Any] = {}
        if programs:
            out["programs"] = programs
            out["buckets"] = len(records)
            out["total_compile_s"] = round(
                sum(p["compile_s"] for p in programs.values()), 4)
        cdir = cache_dir()
        if cdir:
            out["cache_dir"] = cdir
            out["registry_path"] = registry_path(cdir)
            out["disk_buckets"] = self._disk_count(cdir)
        return out

    def reset(self) -> None:
        """Tests only: drop executables and records (disk untouched)."""
        with self._lock:
            self._execs.clear()
            self._records.clear()


#: the process-global ledger (the registry/tracer's sibling).
LEDGER = CompileLedger()


# -- the jit wrapper ---------------------------------------------------------


class LedgeredJit:
    """``jax.jit`` with its first-call-per-shape routed through the
    ledger.  Dispatch goes through the ledger's :class:`Compiled`
    executable (measured here: same per-call latency as the C++ jit
    fast path), so an executable borrowed from the process cache —
    the second same-shape engine build — runs with ZERO new compiles.
    ``.lower()`` passes through for callers that inspect HLO."""

    def __init__(self, fn: Callable, *, program: str, key: Any = None,
                 ledger: CompileLedger = LEDGER,
                 replay: Optional[Callable[[Sequence[Any]],
                                           Optional[Dict[str, Any]]]]
                 = None,
                 bucket_extra: Sequence[Any] = (),
                 tier: Optional[int] = None,
                 **jit_kw: Any) -> None:
        import jax

        self._jit = jax.jit(fn, **jit_kw)
        self._ledger = ledger
        self.program = program
        self._key = key
        self._replay = replay
        self._bucket_extra = tuple(bucket_extra)
        #: compile tier this program belongs to (0 = argsort serving
        #: tier, 1 = steady-state variadic, None = untiered) — recorded
        #: on its shape-registry buckets
        self.tier = tier
        self._donate = tuple(jit_kw.get("donate_argnums") or ())
        self._compiled: Dict[Any, Any] = {}
        self._plain: set = set()

    def warmness(self, structs: Sequence[Any]) -> str:
        """The ledger's :meth:`CompileLedger.warmness` for THIS program
        at *structs* — ``cached`` / ``persistent`` / ``cold``."""
        key = self._key if self._key is not None else self._jit
        return self._ledger.warmness(self.program, key, tuple(structs),
                                     self._bucket_extra)

    def _structs(self, args: Tuple[Any, ...]):
        import jax

        def leaf(a):
            if isinstance(a, jax.Array):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=a.sharding)
            raise TypeError("non-Array leaf")

        return jax.tree_util.tree_map(leaf, args)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if kwargs:
            return self._jit(*args, **kwargs)
        try:
            sig = fingerprint(args)
        except (TypeError, AttributeError):
            return self._jit(*args)
        if sig in self._plain:
            return self._jit(*args)
        compiled = self._compiled.get(sig)
        if compiled is None:
            try:
                structs = self._structs(args)
            except TypeError:
                # non-array leaves (python scalars): observe nothing,
                # jit handles weak types the ledger would misrepresent
                self._plain.add(sig)
                return self._jit(*args)
            compiled = self._acquire(structs, sig)
        try:
            return compiled(*args)
        except (TypeError, ValueError):
            # aval/layout mismatch the AOT path is stricter about than
            # jit dispatch (weak types, uncommitted inputs): fall back
            # permanently for this signature, loudly
            logger.warning(
                "ledgered executable for %s rejected its arguments; "
                "falling back to plain jit dispatch", self.program)
            self._compiled.pop(sig, None)
            self._plain.add(sig)
            return self._jit(*args)

    def _acquire(self, structs, sig) -> Any:
        """Compile through the ledger.  A compile error propagates —
        once, with its own traceback: retrying the same program under
        plain ``jit`` would only pay the compile again to raise it
        again."""
        import jax

        replay_doc = None
        if self._replay is not None:
            try:
                replay_doc = self._replay(
                    jax.tree_util.tree_leaves(structs))
            except Exception as exc:
                # str(exc) — see _persist: a retained record must not
                # pin the dispatch stack through the traceback
                logger.warning("replay-info probe for %s failed: %s",
                               self.program, str(exc))
        compiled, _outcome = self._ledger.compile(
            self._jit, structs, program=self.program,
            key=self._key, donate_argnums=self._donate,
            replay=replay_doc, bucket_extra=self._bucket_extra,
            tier=self.tier)
        self._compiled[sig] = compiled
        return compiled

    def aot(self, structs: Sequence[Any]) -> Any:
        """AOT-compile at explicit avals (the engine's ``precompile``
        and cost/memory model), returning the Compiled.  The signature
        is remembered, so the dispatch that follows reuses this exact
        executable instead of re-entering XLA."""
        structs = tuple(structs)
        sig = fingerprint(structs)
        compiled = self._compiled.get(sig)
        if compiled is None:
            compiled = self._acquire(structs, sig)
        return compiled

    def lower(self, *args: Any, **kwargs: Any) -> Any:
        return self._jit.lower(*args, **kwargs)


def wrap_jit(fn: Callable, *, program: str, **kw: Any) -> LedgeredJit:
    """Module-level convenience over the global :data:`LEDGER` — the
    drop-in for ``jax.jit`` at every instrumented call site."""
    return LedgeredJit(fn, program=program, **kw)
