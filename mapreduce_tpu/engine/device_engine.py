"""Generic device MapReduce: user-supplied traceable map fn, monoid reduce.

The device-path user contract (the traceable analogue of the host path's
``mapfn``/``reducefn`` modules, SURVEY.md §7 hard part (c)): the user gives

  * ``map_fn(chunk_data, chunk_index, cfg) -> (keys [T,2] uint32, values,
    payload [T,Q] int32, valid [T], overflow [] int32)`` — a traceable
    function emitting a fixed-capacity batch of hashed records from one
    input chunk (overflow = records it had to drop for capacity), and
  * ``reduce_op`` — EITHER "sum"/"min"/"max" OR any traceable associative
    + commutative ``(a, b) -> c`` — the compiler-visible form of the
    reference's associative/commutative/idempotent reducer flags
    (reducefn.lua:10-14): declaring the algebra is what licenses
    reordering and partial combining (job.lua:264-284 does the same
    check dynamically).  Non-ACI reducers stay on the host path.

Execution per device (inside ``shard_map`` over the mesh's ``data`` axis)
is a SORT HIERARCHY, fused into ONE dispatch per wave:

  1. ``lax.scan`` over the device's chunks: map_fn emits records, which
     are appended (dynamic_update_slice — contiguous, cheap) into a
     device-resident record buffer.  With ``combine_in_scan`` each
     chunk's records are first pre-reduced (the on-device combiner —
     sort + shifted-compare run-combine at chunk scale, licensed by the
     declared ACI monoid exactly as reducefn.lua's flags license the
     reference's host combiner), shrinking the big-sort row count on
     duplicate-heavy workloads like wordcount.
  2. ONE RANK-SORT of the whole buffer by 64-bit key — ``lax.sort``
     carries only ``[k1, k2, iota]`` and the value/payload lanes are
     permuted by gathers afterwards (ops/segscan.py), so the comparator
     (whose cold compile dominates the ~100s bench-shape compile) is
     independent of record width.  XLA's tuned TPU sort runs at ~160M
     rows/s (measured v5e), where the round-1 scatter hash table
     managed ~3MB/s end to end.
  3. Run boundaries by shifted compare; per-run reduction by an unrolled
     segmented scan (any monoid) or run-length count; run ends compacted
     by searchsorted+gather (ops/segscan.py).  Zero record-granularity
     scatters anywhere.
  4. One ``partition_exchange`` (all_to_all over ICI) of the device's
     UNIQUE records only — carrying the RUNNING ACCUMULATOR (the
     per-partition uniques of the waves already folded, threaded into
     the program as donated arguments) — then a final sorted-unique
     pass that merges exchange rows AND accumulator in the same sort.
     Each wave is therefore map→sort→exchange→fold in a single ``jit``
     dispatch: no separate merge program, no per-wave concatenate
     copies, no per-wave merge-overflow readbacks, and the donated
     buffers free HBM the moment the program consumes them.

All capacities are static; overflows are *counted* and surfaced, and
:meth:`DeviceEngine.run` retries with capacities RIGHT-SIZED from the
failed run's measured needs (per-stage unique counts ride out of the
program; tile_records doubles only when the map stage itself dropped) —
never a silent truncation.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import comms as _comms
from ..obs import compile as _compile_obs
from ..obs import memory as _memory_obs
from ..obs import metrics as _obs
from ..obs import profile as _profile
from ..obs.trace import TRACER
from ..ops.segscan import SENTINEL, sorted_unique_reduce
from ..parallel.shuffle import partition_exchange
from ..utils.jax_compat import quiet_unusable_donation

AXIS = "data"

# -- device-plane instruments (obs/): live counters for the exposition
#    plane plus per-wave histograms on the µs-capable DEVICE_BUCKETS
#    (LATENCY_BUCKETS' 1ms floor collapses sub-millisecond waves) -----------
_WAVES = _obs.counter("mrtpu_device_waves_total",
                      "device-engine waves executed (labels: task)")
_DISPATCHES = _obs.counter(
    "mrtpu_device_dispatches_total",
    "compiled programs dispatched by the device engine (labels: "
    "program, task; the fused engine issues exactly one program=wave "
    "dispatch per wave — a nonzero program=merge count would mean the "
    "deleted two-dispatch path came back)")
_RETRIES = _obs.counter("mrtpu_device_retries_total",
                        "capacity-overflow recompile retries "
                        "(labels: task)")
_STAGE_SECONDS = _obs.counter(
    "mrtpu_device_seconds_total",
    "device-engine wall seconds by stage (labels: stage, task)")
_WAVE_SECONDS = _obs.histogram(
    "mrtpu_device_wave_seconds",
    "per-wave device-plane stage seconds on the DEVICE_BUCKETS preset "
    "(labels: stage=wave|upload|compute|readback; compute is the "
    "dispatch+fold time — device execution is async until readback).  "
    "Deliberately task-agnostic: per-task accounting rides the "
    "counters, not the histogram's bucket fan-out",
    buckets=_obs.DEVICE_BUCKETS)
# per-partition skew inputs for obs/analysis: the live row count (and
# approximate bytes) of each partition's uniques after the last run's
# exchange+fold — a lopsided hash partition shows here directly
_PARTITION_RECORDS = _obs.gauge(
    "mrtpu_device_partition_records",
    "live unique rows per partition after the last device run "
    "(labels: task, partition)")
_PARTITION_BYTES = _obs.gauge(
    "mrtpu_device_partition_bytes",
    "approximate bytes of live rows per partition after the last "
    "device run (labels: task, partition)")


@dataclass(frozen=True)
class EngineConfig:
    """Static capacities (each a per-device row bound)."""

    local_capacity: int = 1 << 16     # unique keys per device, pre-shuffle
    exchange_capacity: int = 1 << 14  # rows per (src, dst) pair
    out_capacity: int = 1 << 16       # unique keys per partition
    tile: int = 512                   # positions per compaction tile
    tile_records: int = 128           # record slots per tile (map side)
    reduce_op: Union[str, Callable] = "sum"
    unit_values: bool = False         # values are all 1: count runs instead
    #: on-device combiner: pre-reduce each chunk's records inside the
    #: map scan (sort + run-combine at chunk scale) before they enter
    #: the device-wide buffer — valid ONLY because reduce_op declares an
    #: ACI monoid (the compiler-visible reducefn.lua flags); shrinks the
    #: big-sort row count on duplicate-heavy workloads.  Off by default;
    #: the wordcount engine turns it on.
    combine_in_scan: bool = False
    #: record slots the combiner compacts one chunk into (0 = auto:
    #: T//4 floored at 256, clamped to T); per-chunk uniques beyond it
    #: are counted as overflow and right-sized by the retry loop
    combine_capacity: int = 0
    #: rank-sort (sort [k1,k2,iota] only, permute lanes by gather);
    #: False restores the variadic all-lanes sort — kept for the
    #: golden-equivalence suite, not for production use
    rank_sort: bool = True
    #: exchange traffic matrix (obs/comms): accumulate, on device, a
    #: P×P src×dst matrix of records each device routed to each
    #: partition — an extra tiny donated lane of the fused wave
    #: program, read back once per run with n_live.  Default on; the
    #: golden suite pins that it never changes fold values, and the
    #: bench smoke that it adds no dispatches.
    exchange_stats: bool = True
    #: sort formulation (ops/segscan.sorted_unique_reduce):
    #:   'variadic' — ONE 2-key sort per stage (best runtime, worst
    #:     comparator compile; the steady-state tier-1 program);
    #:   'argsort' — two-pass stable 1-key argsort (compiles ~3x
    #:     faster, runs slower; the tier-0 serving program);
    #:   'radix' — the Pallas LSD radix sort (ops/radix_sort): no
    #:     comparator at all, so the dominant cold-compile cost
    #:     disappears; the partition exchange fuses its routing plan
    #:     into the same kernel family (one histogram pass yields both
    #:     scatter ranks and the traffic-matrix row).  Bit-identical
    #:     to 'variadic' (golden suite);
    #:   'tiered'  — dispatch-level policy (engine/tiering.py): a COLD
    #:     shape bucket is served on tier-0 immediately while one
    #:     background thread compiles tier-1, hot-swapped at a wave
    #:     boundary (bit-identical by lax.sort stability, so the swap
    #:     is invisible in results); warm buckets go straight to
    #:     tier-1 and nothing changes;
    #:   'tiered-radix' — same policy with the radix program as the
    #:     steady-state tier (serve argsort cold, hot-swap to radix).
    sort_impl: str = "variadic"
    #: skew-aware partition assignment (engine/autotune.py): route each
    #: record through a replicated ``[B] int32`` bucket->partition
    #: indirection table instead of the hard-wired ``key_hi % P``.  The
    #: identity table reproduces ``key_hi % P`` bit-for-bit (``P | B``),
    #: so turning this on changes nothing until a controller actually
    #: rebalances; OFF by default — the table is one more program input,
    #: and embedders who never rebalance should not carry it.
    partition_map: bool = False
    #: buckets in the indirection table (0 = auto: PARTITION_MAP_GRANULARITY
    #: per device) — more buckets = finer-grained rebalancing
    partition_buckets: int = 0
    #: post-sort segmented-reduce formulation (ops/segscan):
    #:   'lax'    — the shifted-compare + segmented_scan ladder +
    #:     ladder_cumsum chain (log2(N) full-array passes per ladder);
    #:   'pallas' — the fused VMEM-tiled kernel: boundary detection,
    #:     segmented combine / run-length count, and the run-end
    #:     cumulative count in ONE pass, bit-identical (golden suite).
    #: Selected per config so the equivalence suite pins both; the CPU
    #: tier runs the kernel under the Pallas interpreter
    #: (ops/pallas_compat's ONE interpret-mode policy).
    segment_impl: str = "lax"
    #: elements per segmented-reduce kernel block (multiple of 128);
    #: part of the cache key so block retunes recompile cleanly
    segment_block: int = 4096
    #: tokenizer formulation for map_fns that tokenize (the wordcount
    #: family reads it): 'lax' = the tiled Hillis-Steele affine ladders,
    #: 'pallas' = the fused tokenizing map-scan kernel (classify + all
    #: hash lanes + boundary cummax in one blocked pass, bit-identical)
    tokenize_impl: str = "lax"
    #: bytes per tokenize kernel block (multiple of 128)
    tokenize_block: int = 4096

    def cache_key(self):
        # the op object itself is part of the key: keeping it in the
        # compiled-program cache holds a strong reference, so a collected
        # lambda's id can never be reused to hit a stale program
        return (self.local_capacity, self.exchange_capacity,
                self.out_capacity, self.tile, self.tile_records,
                self.reduce_op, self.unit_values, self.combine_in_scan,
                self.combine_capacity, self.rank_sort,
                self.exchange_stats, self.sort_impl,
                self.partition_map, self.partition_buckets,
                self.segment_impl, self.segment_block,
                self.tokenize_impl, self.tokenize_block)

    def scan_combine_slots(self, T: int) -> int:
        """Static buffer slots one chunk's pre-reduced records occupy
        when the combiner is on, clamped to [1, T] (at T the combiner
        degenerates to a per-chunk dedup — still correct)."""
        cap = self.combine_capacity or max(T // 4, 256)
        return max(1, min(T, cap))


#: the wave program's donated positions — the accumulator
#: (keys/vals/pay/valid) and the wave inputs; n_real (argnum 2) is
#: reused by every wave and stays undonated.  One source shared by
#: _program and the run epilogue's donation accounting, so the two
#: cannot drift.  With exchange_stats the traffic-matrix accumulator
#: rides as donated argnum 7 (it aliases the program's traffic output
#: exactly as the record accumulator aliases the fold outputs).
_WAVE_DONATE_ARGNUMS = (0, 1, 3, 4, 5, 6)


def _wave_donate_argnums(cfg: "EngineConfig"):
    return (_WAVE_DONATE_ARGNUMS + (7,) if cfg.exchange_stats
            else _WAVE_DONATE_ARGNUMS)


_SORT_IMPLS = ("variadic", "argsort", "radix", "tiered", "tiered-radix")
#: concrete (traceable) sort programs — what _program may be handed
_CONCRETE_SORT_IMPLS = ("variadic", "argsort", "radix")


def _is_tiered(sort_impl: str) -> bool:
    """True for the dispatch-level tier policies (resolved by the engine
    into concrete per-tier configs before any tracing)."""
    return sort_impl in ("tiered", "tiered-radix")
_SEGMENT_IMPLS = ("lax", "pallas")
_TOKENIZE_IMPLS = ("lax", "pallas")

#: auto bucket count per device for the partition-map indirection
#: table: enough granularity that a single hot partition's buckets can
#: be spread across the whole mesh, small enough that the replicated
#: table is noise (8·P int32s)
PARTITION_MAP_GRANULARITY = 8


def partition_buckets_for(cfg: EngineConfig, n_dev: int) -> int:
    """The indirection table's bucket count B (a multiple of the
    partition count, so the identity table reproduces ``key_hi % P``)."""
    B = cfg.partition_buckets or PARTITION_MAP_GRANULARITY * n_dev
    if B % n_dev:
        raise ValueError(
            f"partition_buckets {B} must be a multiple of the device "
            f"count {n_dev} (the identity table's bit-identity to "
            "key_hi % P depends on P | B)")
    return B


def identity_pmap(B: int, n_dev: int) -> np.ndarray:
    """The identity bucket->partition table: ``pmap[b] = b % P`` —
    bit-identical routing to the hard-wired ``key_hi % P``."""
    return (np.arange(B, dtype=np.int64) % n_dev).astype(np.int32)


def validate_partition_map(pmap, buckets: int,
                           n_dev: int) -> np.ndarray:
    """Normalize + validate a bucket->partition table (shared by the
    engine's batch path and the session's mid-stream rebalance — ONE
    spelling of the contract).  The table IS the partition function:
    a malformed one routes records into nonexistent partitions, so
    both failure modes raise loudly.  Returns the int32 host copy."""
    pmap = np.asarray(pmap, dtype=np.int32).reshape(-1)
    if pmap.shape[0] != buckets:
        raise ValueError(f"partition map has {pmap.shape[0]} buckets, "
                         f"config says {buckets}")
    if pmap.size and (pmap.min() < 0 or pmap.max() >= n_dev):
        raise ValueError(
            f"partition map routes outside [0, {n_dev})")
    return pmap


def _tier_cfgs(cfg: EngineConfig):
    """The two concrete per-tier program configs a tier policy resolves
    to: (tier-0 argsort, steady tier).  ``'tiered'`` steadies on the
    variadic program, ``'tiered-radix'`` on the radix program.  The
    accumulator layout is identical across them — only the sort
    formulation inside the program differs — so the donated carry
    threads straight through a mid-run hot swap."""
    steady = "radix" if cfg.sort_impl == "tiered-radix" else "variadic"
    return (replace(cfg, sort_impl="argsort"),
            replace(cfg, sort_impl=steady))


def _steady_cfg(cfg: EngineConfig) -> EngineConfig:
    """The steady-state program config: a tier policy normalizes to its
    steady tier's config so shared satellites (accumulator-init
    program, fin-row avals) key identically to an untiered engine."""
    return (_tier_cfgs(cfg)[1] if _is_tiered(cfg.sort_impl) else cfg)


def _capacities(cfg: EngineConfig) -> dict:
    """The static capacities a retry right-sizes — the before/after
    payload of the capacity-retry forensics event."""
    return {"local_capacity": cfg.local_capacity,
            "exchange_capacity": cfg.exchange_capacity,
            "out_capacity": cfg.out_capacity,
            "tile_records": cfg.tile_records,
            "combine_capacity": cfg.combine_capacity}


def _cfg_token(cfg: EngineConfig) -> str:
    """Stable cross-process spelling of a config's cache key for the
    shape-bucket registry (callable reduce ops become module:qualname,
    never an id()-bearing repr)."""
    return "|".join(_compile_obs.op_token(v) if callable(v) else repr(v)
                    for v in cfg.cache_key())


def _stage_ops(cfg: EngineConfig):
    """``(local_op, local_unit, fin_op)`` — the per-stage reduce algebra.
    With the in-scan combiner on, buffer rows are already per-chunk
    partial reductions, so the local stage must COMBINE them (unit-value
    run counts combine by sum) instead of counting rows again."""
    if cfg.combine_in_scan and cfg.unit_values:
        local_op, local_unit = "sum", False
    else:
        local_op, local_unit = cfg.reduce_op, cfg.unit_values
    fin_op = "sum" if cfg.unit_values else cfg.reduce_op
    return local_op, local_unit, fin_op


class DeviceResult(NamedTuple):
    keys: np.ndarray      # [P, out_capacity, 2] uint32
    values: np.ndarray    # [P, out_capacity, ...]
    payload: np.ndarray   # [P, out_capacity, Q]
    valid: np.ndarray     # [P, out_capacity]
    overflow: int         # total dropped rows across all stages (0 = exact)


class _WaveFeeder:
    """Streams the chunk batch to the device wave by wave.

    Waves are contiguous per-device blocks (full waves are zero-copy numpy
    views of the caller's array; only the final partial wave pays a pad
    copy), each placed sharded over the data axis with one
    ``jax.device_put`` carrying *global* chunk indices so payload byte
    offsets stay corpus-global across waves.

    ``get(w)`` resolves wave *w*, submitting background ``device_put``\\ s
    for at most *prefetch* waves ahead (``device_put`` pays a synchronous
    host staging copy before the DMA, so puts run on worker threads to
    overlap that memcpy with compute).  ``release(w)`` drops the device
    references so wave *w*'s HBM is reclaimed as soon as its consuming
    program finishes — peak input memory is ~*prefetch* waves, never the
    corpus.  ``reset()`` forgets consumed waves so a capacity retry
    re-uploads.  ``close()`` cancels outstanding uploads and joins the
    pool, so a failed wave never leaves orphan upload threads.
    """

    def __init__(self, engine: "DeviceEngine", chunks: np.ndarray,
                 waves: int = None, prefetch: int = None,
                 k: int = None) -> None:
        self._chunks = chunks
        S = chunks.shape[0]
        self.n_dev = engine.n_dev
        if k is None:  # explicit wave count (tests, user tuning)
            k = -(-S // (waves * self.n_dev))  # chunks per device per wave
        self.rpw = k * self.n_dev          # rows per wave
        self.waves = -(-S // self.rpw)  # drop waves that would be all-pad
        self.S = S
        self.prefetch = (self.waves if prefetch is None
                         else max(1, prefetch))
        self._sharding = NamedSharding(engine.mesh, P(AXIS))
        self._pool = None
        self._futs: dict = {}
        self._ready: dict = {}
        self._submitted = 0
        # first-party HBM-bound accounting: bytes of input waves held
        # (submitted and not yet released).  The CPU backend exposes no
        # memory_stats(), so tier-1 asserts the bound on this ledger
        # plus a jax.live_arrays() cross-check
        # (tests/test_device_engine.py).
        self._wave_nbytes = int(
            self.rpw * int(np.prod(chunks.shape[1:], dtype=np.int64))
            * chunks.dtype.itemsize + self.rpw * 4)  # + i32 indices
        self._accounted: set = set()
        self.held_bytes = 0
        self.peak_held_bytes = 0

    @property
    def n_real(self):
        """True chunk count (a COMMITTED replicated device scalar, so the
        jit compile key matches precompile's replicated aval); indices
        beyond it are padding whose records the program masks out."""
        if not hasattr(self, "_n_real"):
            self._n_real = jax.device_put(
                np.int32(self.S),
                NamedSharding(self._sharding.mesh, P()))
        return self._n_real

    def _put_wave(self, w: int):
        lo = w * self.rpw
        chunks = self._chunks
        if lo + self.rpw <= self.S:
            block = chunks[lo:lo + self.rpw]  # zero-copy view
        else:  # final wave: pad with zero chunks (masked via n_real) —
            # allocating and zeroing ONLY the pad rows; the real rows
            # ride the concatenate's single copy instead of a full
            # wave-sized zero fill plus a second copy over it
            pad = np.zeros((lo + self.rpw - self.S,) + chunks.shape[1:],
                           dtype=chunks.dtype)
            block = np.concatenate([chunks[lo:], pad])
        dev_chunks = jax.device_put(block, self._sharding)
        idx = np.arange(lo, lo + self.rpw, dtype=np.int32)
        dev_idx = jax.device_put(idx, self._sharding)
        return dev_chunks, dev_idx

    def _ensure_submitted(self, upto: int) -> None:
        import concurrent.futures as cf

        upto = min(upto, self.waves - 1)
        if self._submitted > upto:
            return
        if self._pool is None:
            self._pool = cf.ThreadPoolExecutor(
                max_workers=min(self.waves, 8))
        for w in range(self._submitted, upto + 1):
            self._futs[w] = self._pool.submit(self._put_wave, w)
            if w not in self._accounted:
                self._accounted.add(w)
                self.held_bytes += self._wave_nbytes
                self.peak_held_bytes = max(self.peak_held_bytes,
                                           self.held_bytes)
        self._submitted = upto + 1

    def get(self, w: int):
        """Resolved ``(dev_chunks [k*n_dev, ...], dev_idx [k*n_dev])``."""
        self._ensure_submitted(w + self.prefetch - 1)
        if w not in self._ready:
            self._ready[w] = self._futs.pop(w).result()
        return self._ready[w]

    def release(self, w: int) -> None:
        self._ready.pop(w, None)
        if w in self._accounted:
            self._accounted.discard(w)
            self.held_bytes -= self._wave_nbytes

    def reset(self) -> None:
        self.close()
        self._submitted = 0

    def close(self) -> None:
        for f in self._futs.values():
            f.cancel()
        if self._pool is not None:
            # wait: a put mid-flight holds a chunks view; freeing device
            # buffers is then just the dict clears below
            self._pool.shutdown(wait=True)
            self._pool = None
        self._futs.clear()
        self._ready.clear()
        self._accounted.clear()
        self.held_bytes = 0


class DeviceEngine:
    """Compile-once, run-many device MapReduce over a mesh.

    ``map_fn`` must be traceable and return fixed-shape record batches
    (the payload width Q and the per-record value shape are inferred from
    tracing ``map_fn`` once — there is nothing to declare up front).
    """

    def __init__(self, mesh: Mesh, map_fn: Callable,
                 config: EngineConfig = EngineConfig(),
                 task: str = "-", autotune=None) -> None:
        if config.sort_impl not in _SORT_IMPLS:
            raise ValueError(
                f"EngineConfig.sort_impl must be one of {_SORT_IMPLS}, "
                f"got {config.sort_impl!r}")
        if config.segment_impl not in _SEGMENT_IMPLS:
            raise ValueError(
                f"EngineConfig.segment_impl must be one of "
                f"{_SEGMENT_IMPLS}, got {config.segment_impl!r}")
        if config.tokenize_impl not in _TOKENIZE_IMPLS:
            raise ValueError(
                f"EngineConfig.tokenize_impl must be one of "
                f"{_TOKENIZE_IMPLS}, got {config.tokenize_impl!r}")
        self.mesh = mesh
        self.map_fn = map_fn
        self.config = config
        self.n_dev = mesh.shape[AXIS]
        #: the observe->act loop (engine/autotune.AutoTuner): None (the
        #: default) is the pre-control engine bit-for-bit — no decision
        #: is ever recorded, no capacity is ever pre-sized
        self.autotune = autotune
        #: the batch path's bucket->partition table (partition_map
        #: configs only); identity until set_partition_map installs a
        #: rebalanced one.  Sessions carry a table PER STREAM instead.
        self._pmap_host: np.ndarray = None
        self._pmap_dev = None
        #: ONE background tier-1 compile thread per engine
        #: (engine/tiering.py), created on the first cold tiered
        #: dispatch
        self._tier_spec = None
        #: low-cardinality accounting label on every metric this engine
        #: emits (the owning task's database name; "-" outside the task
        #: machinery) — the cluster collector rolls device seconds and
        #: FLOPs up by it
        self.task_label = task or "-"
        self._compiled = {}
        #: mesh identity for the compile ledger's cross-engine
        #: executable sharing: two engines with the same map_fn, config
        #: AND device set run the same program (a mesh over a different
        #: device subset must not alias)
        self._mesh_fp = tuple(int(d.id) for d in mesh.devices.flat)
        self._devices = list(mesh.devices.flat)

    # -- the SPMD program --------------------------------------------------

    def _program(self, cfg: EngineConfig):
        # a tier policy never reaches tracing: the dispatch layer
        # (engine/tiering.py) resolves it to one of the concrete
        # per-tier configs first
        assert cfg.sort_impl in _CONCRETE_SORT_IMPLS, cfg.sort_impl
        map_fn = self.map_fn
        local_op, local_unit, fin_op = _stage_ops(cfg)

        def per_device(chunks: jax.Array, chunk_idx: jax.Array,
                       n_real: jax.Array, acc_k: jax.Array,
                       acc_v: jax.Array, acc_p: jax.Array,
                       acc_valid: jax.Array, *extra: jax.Array):
            # trailing args, in order: the donated traffic-matrix
            # accumulator row (exchange_stats) then the replicated
            # bucket->partition table (partition_map) — an INPUT only,
            # never donated, never an output lane
            acc_tr = extra[:1] if cfg.exchange_stats else ()
            pmap = extra[-1] if cfg.partition_map else None
            # chunks: [k, ...chunk_shape], chunk_idx: [k] global indices,
            # n_real: [] count of genuine chunks — indices >= n_real are
            # padding added to even out the mesh; their records (and any
            # overflow they report) are masked out after map_fn.
            # acc_*: [1, out_capacity, ...] — the RUNNING per-partition
            # uniques of the waves already folded (all-invalid on the
            # first wave), threaded through as donated inputs so the
            # whole wave is one dispatch and the accumulator buffers are
            # updated in place
            k = chunks.shape[0]
            keys0, vals0, pay0, valid0, _ = map_fn(chunks[0], chunk_idx[0],
                                                   cfg)
            T = keys0.shape[0]
            Q = pay0.shape[1]
            combine = cfg.combine_in_scan
            Tc = cfg.scan_combine_slots(T) if combine else T
            N = k * Tc

            # buffer row avals: the combiner changes the per-chunk slot
            # count and (for unit_values) the value lane to int32 counts
            if combine:
                cu0 = jax.eval_shape(
                    lambda kk, vv, pp, mm: sorted_unique_reduce(
                        kk, vv, pp, mm, Tc, cfg.reduce_op,
                        unit_values=cfg.unit_values,
                        rank_sort=cfg.rank_sort,
                        sort_impl=cfg.sort_impl,
                        segment_impl=cfg.segment_impl,
                        segment_block=cfg.segment_block),
                    keys0, vals0, pay0, valid0)
                v_shape, v_dtype = cu0.values.shape[1:], cu0.values.dtype
            else:
                v_shape, v_dtype = vals0.shape[1:], vals0.dtype

            def varying(a):
                return jax.lax.pcast(a, AXIS, to="varying")

            # phase 1: map (+ optional combine) + append into the
            # device-resident record buffer
            buf_k = varying(jnp.full((N, 2), SENTINEL, jnp.uint32))
            buf_v = varying(jnp.zeros((N,) + v_shape, v_dtype))
            buf_p = varying(jnp.zeros((N, Q), pay0.dtype))
            zero0 = varying(jnp.int32(0))

            def step(state, xs):
                buf_k, buf_v, buf_p, map_oflow, comb_oflow, comb_max = state
                chunk, idx, j = xs
                with jax.named_scope("wave.map"):
                    keys, vals, pay, valid, m_oflow = map_fn(chunk, idx, cfg)
                live = idx < n_real
                valid = valid & live
                map_oflow = map_oflow + jnp.where(live, m_oflow, 0)
                if combine:
                    # the on-device combiner: the declared ACI monoid
                    # licenses partial reduction at any grouping
                    # (reducefn.lua:10-14 / job.lua:264-284 do the same
                    # check dynamically), so the chunk's duplicates are
                    # folded HERE — a chunk-scale sort + shifted-compare
                    # run-combine — and the big sort sees Tc rows per
                    # chunk instead of T
                    with jax.named_scope("wave.combine"):
                        cu = sorted_unique_reduce(
                            keys, vals, pay, valid, Tc, cfg.reduce_op,
                            unit_values=cfg.unit_values,
                            rank_sort=cfg.rank_sort,
                            sort_impl=cfg.sort_impl,
                            segment_impl=cfg.segment_impl,
                            segment_block=cfg.segment_block)
                    keys, vals, pay, valid = (cu.keys, cu.values,
                                              cu.payload, cu.valid)
                    comb_oflow = comb_oflow + jnp.maximum(
                        cu.n_unique - Tc, 0)
                    comb_max = jnp.maximum(comb_max, cu.n_unique)
                # a VALID record whose key is literally the sentinel pair
                # is remapped to (0,0) — matching sorted_unique_reduce's
                # remap — so buf_valid below cannot mistake it for padding
                # (the map_fn contract promises drops are always counted,
                # never silent)
                is_sent = ((keys[:, 0] == SENTINEL)
                           & (keys[:, 1] == SENTINEL))
                keys = jnp.where(is_sent[:, None], jnp.uint32(0), keys)
                # invalid rows -> sentinel keys (sort to the end)
                kk = jnp.where(valid[:, None], keys, SENTINEL)
                with jax.named_scope("wave.append"):
                    buf_k = jax.lax.dynamic_update_slice(buf_k, kk,
                                                         (j * Tc, 0))
                    buf_v = jax.lax.dynamic_update_slice(
                        buf_v, vals, (j * Tc,) + (0,) * (buf_v.ndim - 1))
                    buf_p = jax.lax.dynamic_update_slice(buf_p, pay,
                                                         (j * Tc, 0))
                return (buf_k, buf_v, buf_p, map_oflow, comb_oflow,
                        comb_max), None

            (buf_k, buf_v, buf_p, map_oflow, comb_oflow, comb_max), _ = \
                jax.lax.scan(
                    step, (buf_k, buf_v, buf_p, zero0, zero0, zero0),
                    (chunks, chunk_idx, jnp.arange(k, dtype=jnp.int32)))

            # phases 2+3: one big rank-sort, segmented reduce, compact
            buf_valid = ~((buf_k[:, 0] == SENTINEL)
                          & (buf_k[:, 1] == SENTINEL))
            with jax.named_scope("wave.local"):
                local = sorted_unique_reduce(
                    buf_k, buf_v, buf_p, buf_valid, cfg.local_capacity,
                    local_op, unit_values=local_unit,
                    rank_sort=cfg.rank_sort, sort_impl=cfg.sort_impl,
                    segment_impl=cfg.segment_impl,
                    segment_block=cfg.segment_block)
            local_oflow = (map_oflow + comb_oflow
                           + jnp.maximum(local.n_unique
                                         - cfg.local_capacity, 0))

            # phase 4: shuffle uniques to their partition over ICI, the
            # accumulator riding along as the exchange's carry spec
            # (prepended, so the stable fold order stays acc ⊕ wave) —
            # the final sorted-unique pass then merges the fresh rows
            # WITH the running uniques in one sort, replacing the old
            # separate merge dispatch and its concatenate copies
            with jax.named_scope("wave.exchange"):
                ex = partition_exchange(
                    local.keys, local.values, local.payload, local.valid,
                    AXIS, cfg.exchange_capacity,
                    carry=(acc_k[0], acc_v[0], acc_p[0], acc_valid[0]),
                    pmap=pmap,
                    # radix programs fuse the routing plan into the
                    # kernel family: one histogram pass yields both the
                    # scatter ranks and ex.counts
                    impl=("radix" if cfg.sort_impl == "radix" else "lax"))

            with jax.named_scope("wave.fold"):
                fin = sorted_unique_reduce(
                    ex.keys, ex.values, ex.payload, ex.valid,
                    cfg.out_capacity, fin_op, unit_values=False,
                    rank_sort=cfg.rank_sort, sort_impl=cfg.sort_impl,
                    segment_impl=cfg.segment_impl,
                    segment_block=cfg.segment_block)
            fin_oflow = jnp.maximum(fin.n_unique - cfg.out_capacity, 0)

            # LOCAL overflow per device — the host sums across devices
            # (a psum here would get double-counted by that host sum).
            # The fold's overflow is fin_oflow: it lands here, in the
            # same per-wave overflow lane the readback already fetches.
            local_oflow = local_oflow + ex.overflow + fin_oflow
            # capacity NEEDS per device, so a retry can jump straight to
            # right-sized capacities instead of blind doubling (each lane
            # is a lower bound if an earlier stage truncated, so the
            # retry loop still iterates — but converges in one or two
            # right-sized compiles):
            # [local uniques, exchange per-dest max, final uniques
            #  (cumulative: the accumulator is folded in), map-stage
            #  drops, combiner per-chunk unique max]
            needs = jnp.stack([local.n_unique, ex.max_count,
                               fin.n_unique, map_oflow, comb_max])
            # keep leading device axis for the host: [1, ...] per shard
            expand = lambda a: a[None]
            outs = (expand(fin.keys), expand(fin.values),
                    expand(fin.payload), expand(fin.valid),
                    expand(local_oflow), expand(needs))
            if cfg.exchange_stats:
                # the exchange traffic matrix (obs/comms): this device's
                # per-destination routed-row counts — already computed by
                # the exchange for overflow accounting — accumulated into
                # the donated [1, P] running row across waves.  A tiny
                # extra output lane of the SAME dispatch, read back once
                # per run with n_live: no new program, no new readback.
                outs = outs + (acc_tr[0] + ex.counts[None, :],)
            return outs

        sharded = P(AXIS)
        n_extra = 1 if cfg.exchange_stats else 0
        # the partition-map table is a replicated INPUT with no output
        # twin — in_specs grows, out_specs does not
        pmap_specs = (P(),) if cfg.partition_map else ()
        fn = jax.shard_map(
            per_device, mesh=self.mesh,
            in_specs=(sharded, sharded, P(), sharded, sharded, sharded,
                      sharded) + (sharded,) * n_extra + pmap_specs,
            out_specs=(sharded,) * (6 + n_extra),
        )
        # donate the accumulator (its buffers alias the fin outputs —
        # the fold updates it in place) AND the wave inputs (HBM freed
        # the moment the program consumes them, no explicit del dance);
        # n_real is reused by every wave and stays undonated.  Routed
        # through the compile ledger (obs/compile): first-call compiles
        # emit compile⊃{lowering,backend_compile} spans, land in the
        # shape-bucket registry, and a second engine with the same
        # map_fn/config/mesh reuses the executable outright.
        return _compile_obs.wrap_jit(
            fn, program="wave",
            key=("wave", self.map_fn, cfg.cache_key(), self._mesh_fp),
            bucket_extra=("wave", _compile_obs.op_token(self.map_fn),
                          _cfg_token(cfg)),
            replay=lambda structs: self._replay_info(cfg, structs),
            # which compile tier this formulation is (registry schema
            # v2: buckets record where their best_compile_s came from)
            tier={"argsort": 0, "variadic": 1,
                  "radix": 2}[cfg.sort_impl],
            donate_argnums=_wave_donate_argnums(cfg))

    def _get_compiled(self, cfg: EngineConfig):
        key = cfg.cache_key()
        if key not in self._compiled:
            self._compiled[key] = self._program(cfg)
        return self._compiled[key]

    # -- the partition map (skew-aware routing, engine/autotune) -----------

    @property
    def partition_buckets(self) -> int:
        return partition_buckets_for(self.config, self.n_dev)

    def partition_map(self) -> np.ndarray:
        """The batch path's current bucket->partition table (host
        copy); identity until :meth:`set_partition_map`."""
        if self._pmap_host is None:
            self._pmap_host = identity_pmap(self.partition_buckets,
                                            self.n_dev)
        return self._pmap_host

    def set_partition_map(self, pmap: np.ndarray) -> None:
        """Install a rebalanced bucket->partition table for future runs
        (requires ``config.partition_map``).  Validated loudly: the
        table is the partition function — a malformed one would route
        records into nonexistent partitions."""
        if not self.config.partition_map:
            raise ValueError("set_partition_map needs "
                             "EngineConfig.partition_map=True")
        self._pmap_host = validate_partition_map(
            pmap, self.partition_buckets, self.n_dev)
        self._pmap_dev = None  # re-commit lazily with the run's mesh

    def device_pmap(self, pmap_host: np.ndarray = None):
        """A committed replicated device copy of *pmap_host* (default:
        the engine's own table)."""
        if pmap_host is not None:
            return jax.device_put(
                np.asarray(pmap_host, dtype=np.int32),
                NamedSharding(self.mesh, P()))
        if self._pmap_dev is None:
            self._pmap_dev = jax.device_put(
                self.partition_map(), NamedSharding(self.mesh, P()))
        return self._pmap_dev

    def _tier_specializer(self):
        if self._tier_spec is None:
            from .tiering import TierSpecializer

            self._tier_spec = TierSpecializer()
        return self._tier_spec

    def _wave_fn(self, cfg: EngineConfig):
        """The wave-program callable an attempt dispatches: the
        compiled program itself, or — under a tiered policy — a
        fresh :class:`~.tiering.TieredWaveDispatcher` that serves cold
        buckets on tier-0 and hot-swaps to the steady tier at a wave
        boundary.  Per-attempt on purpose: a capacity retry re-probes
        warmness at the NEW capacities and re-enters tier-0 instead of
        paying the full steady-tier compile mid-retry."""
        if not _is_tiered(cfg.sort_impl):
            return self._get_compiled(cfg)
        from .tiering import TieredWaveDispatcher

        return TieredWaveDispatcher(self, cfg, task=self.task_label)

    def _fin_row_avals(self, cfg: EngineConfig, row_shape, row_dtype):
        """Per-partition accumulator row avals — ``[(C,2) u32 keys,
        (C,...) values, (C,Q) payload, (C,) valid]`` — for the fused
        fold, derived by shape-tracing map_fn → (combiner) → local →
        fin exactly as the program computes them, so value-dtype
        promotion through a custom monoid is honoured.  Cached per
        (cfg, row aval)."""
        key = ("acc_aval", cfg.cache_key(), tuple(row_shape),
               str(np.dtype(row_dtype)))
        if key not in self._compiled:
            local_op, local_unit, fin_op = _stage_ops(cfg)

            def probe(chunk, ci):
                keys, vals, pay, valid, _ = self.map_fn(chunk, ci, cfg)
                if cfg.combine_in_scan:
                    cu = sorted_unique_reduce(
                        keys, vals, pay, valid, 8, cfg.reduce_op,
                        unit_values=cfg.unit_values)
                    keys, vals, pay, valid = (cu.keys, cu.values,
                                              cu.payload, cu.valid)
                local = sorted_unique_reduce(keys, vals, pay, valid, 8,
                                             local_op,
                                             unit_values=local_unit)
                return sorted_unique_reduce(
                    local.keys, local.values, local.payload, local.valid,
                    8, fin_op, unit_values=False)

            row = jax.ShapeDtypeStruct(tuple(row_shape), row_dtype)
            idx = jax.ShapeDtypeStruct((), np.int32)
            fin = jax.eval_shape(probe, row, idx)
            C = cfg.out_capacity
            self._compiled[key] = (
                jax.ShapeDtypeStruct((C, 2), np.uint32),
                jax.ShapeDtypeStruct((C,) + tuple(fin.values.shape[1:]),
                                     fin.values.dtype),
                jax.ShapeDtypeStruct((C,) + tuple(fin.payload.shape[1:]),
                                     fin.payload.dtype),
                jax.ShapeDtypeStruct((C,), np.bool_),
            )
        return self._compiled[key]

    def _acc_init(self, cfg: EngineConfig, row_shape, row_dtype):
        """Fresh all-invalid accumulator ``[n_dev, C, ...]`` arrays for
        an attempt — built ON DEVICE by a cached zeros program with the
        run's shardings (never a multi-megabyte host transfer of zeros
        over the slow link).  With ``exchange_stats`` the zeroed
        ``[n_dev, P]`` traffic-matrix accumulator rides along as a fifth
        array."""
        avals = self._fin_row_avals(cfg, row_shape, row_dtype)
        if cfg.exchange_stats:
            avals = avals + (
                jax.ShapeDtypeStruct((self.n_dev,), np.int32),)
        key = ("acc_init", cfg.cache_key(),
               tuple((a.shape, str(a.dtype)) for a in avals))
        if key not in self._compiled:
            sh = NamedSharding(self.mesh, P(AXIS))
            n_dev = self.n_dev
            self._compiled[key] = _compile_obs.wrap_jit(
                lambda: tuple(jnp.zeros((n_dev,) + a.shape, a.dtype)
                              for a in avals),
                program="acc_init",
                key=key + (self._mesh_fp,),
                bucket_extra=("acc_init", _cfg_token(cfg)),
                out_shardings=(sh,) * len(avals))
        return list(self._compiled[key]())

    # -- host driver -------------------------------------------------------

    #: target host bytes per pipeline wave (auto wave count).  Sized on
    #: an earlier rig; not re-measured on current hardware (ROADMAP A)
    WAVE_BYTES = 48 << 20

    def _rows_per_wave(self, row_bytes: int) -> int:
        """THE wave-size formula — precompile and the auto run path must
        agree byte-for-byte or the primed persistent-cache entry is never
        the one a run looks up."""
        return max(1, round(self.WAVE_BYTES / max(1, row_bytes)))

    def _auto_rows(self, chunks: np.ndarray) -> int:
        """Chunks per device per wave for the auto path: a FIXED function
        of the row byte size (not of the corpus), so the per-wave program
        shape — and with it the persistent-cache entry — is identical for
        every corpus larger than one wave.  The cold compile of the
        engine programs is dominated by the lax.sort comparator;
        shape-stable waves mean a machine pays it once, not once per
        corpus size.  Streaming keeps peak HBM at
        ~STREAM_PREFETCH waves whatever the resulting wave count; only
        sub-wave inputs shrink k (tests, tiny corpora)."""
        S = chunks.shape[0]
        row_bytes = max(1, chunks.nbytes // max(1, S))
        return min(self._rows_per_wave(row_bytes), -(-S // self.n_dev))

    def _multiprocess(self) -> bool:
        """True when the mesh spans devices of other JAX processes
        (multi-controller SPMD under jax.distributed)."""
        pid = jax.process_index()
        return any(d.process_index != pid for d in self.mesh.devices.flat)

    def _host(self, *arrays):
        """Bring device arrays to host numpy.  On a single-process mesh
        this is plain np.asarray; when the mesh spans processes, shards on
        other hosts are not addressable, so the arrays are first
        replicated (one all-gather) — every process then returns the
        identical full value, keeping the engine's host surface (counts,
        overflow checks) SPMD-consistent."""
        if self._multiprocess():
            key = ("host_gather", len(arrays))
            if key not in self._compiled:
                rep = NamedSharding(self.mesh, P())
                self._compiled[key] = _compile_obs.wrap_jit(
                    lambda *a: a, program="host_gather",
                    key=key + (self._mesh_fp,),
                    bucket_extra=("host_gather",),
                    out_shardings=(rep,) * len(arrays))
            arrays = self._compiled[key](*arrays)
        out = [np.asarray(a) for a in arrays]
        return out[0] if len(out) == 1 else out

    #: waves of input kept in flight ahead of the consuming program in the
    #: streaming run path: upload of wave w+1 overlaps compute of wave w,
    #: while peak device input memory stays ~2 waves instead of the whole
    #: corpus (the reference streams unbounded inputs through bounded
    #: iterators, utils.lua:133-200; this is the HBM analogue)
    STREAM_PREFETCH = 2

    def _max_inflight_programs(self) -> int:
        """Wave programs allowed in the dispatch queue before the driver
        blocks on an older wave's completion.  On TPU the per-device queue
        executes serially and a modest depth keeps dispatch pipelined
        (the fused fold chains each wave through the donated accumulator,
        so queued waves hold only their input buffers).  On the CPU backend
        every queued shard occupies a thread-pool worker, so shards of
        later waves can starve an earlier wave's all_to_all rendezvous of
        its participants — a deadlock XLA aborts after 40s; strict
        serialization is the only safe depth there."""
        platform = next(iter(self.mesh.devices.flat)).platform
        return 4 if platform == "tpu" else 1

    @staticmethod
    def _fit(need: int) -> int:
        """Round a measured need up to a power of two with ~25% margin."""
        need = int(need * 1.25) + 16
        return 1 << max(need - 1, 1).bit_length()

    def _resize(self, cfg: EngineConfig, need_arrays) -> EngineConfig:
        """Right-size capacities from the failed run's measured needs
        (program output lane 5: [local uniques, exchange per-dest max,
        final uniques, map drops, combiner per-chunk max] per device) —
        one informed recompile instead of blind doubling (SURVEY §7(a)
        count-then-size, done as measure-then-size on the run we already
        paid for).  Needs are lower bounds when an earlier stage
        truncated, so the loop may take a second sizing pass; it never
        regresses a capacity."""
        hosted = self._host(*need_arrays)  # one batched gather
        needs = np.stack(hosted if len(need_arrays) > 1 else [hosted])
        # [W, dev, 5]
        local_need = int(needs[:, :, 0].max())
        ex_need = int(needs[:, :, 1].max())
        # the fused fold's fin count is CUMULATIVE (the accumulator is
        # folded into every wave's final pass), so the max across waves
        # is already the per-partition union bound
        fin_need = int(needs[:, :, 2].max())
        map_dropped = int(needs[:, :, 3].sum())
        comb_need = int(needs[:, :, 4].max())
        out = replace(
            cfg,
            local_capacity=max(cfg.local_capacity, self._fit(local_need)),
            exchange_capacity=max(cfg.exchange_capacity,
                                  self._fit(ex_need)),
            out_capacity=max(cfg.out_capacity, self._fit(fin_need)),
            tile_records=(min(cfg.tile_records * 2, cfg.tile)
                          if map_dropped else cfg.tile_records),
        )
        if cfg.combine_in_scan and comb_need > 0:
            # explicit combiner slots from the measured per-chunk unique
            # max (scan_combine_slots clamps to T at trace time, where
            # the combiner degenerates to a correct per-chunk dedup)
            out = replace(out, combine_capacity=max(cfg.combine_capacity,
                                                    self._fit(comb_need)))
        return out

    # -- cost model (obs/profile.py: FLOPs/MFU accounting) ------------------

    def _program_costs(self, cfg: EngineConfig, shapes) -> dict:
        """FLOPs / bytes-accessed of ONE wave program.  Prefers XLA's
        own cost model: the ledger's ``aot()`` on the shapes the run
        dispatched returns the exact executable the run used (the
        ledger remembered it — zero XLA work, not a recompile), and
        ``cost_analysis()`` reads the compiled module.  Backends
        without a usable analysis fall back to the analytic
        sort-hierarchy estimate, labelled ``source="analytic"``.
        Cached per (cfg, shape) — one trace per engine config."""
        key = ("cost", cfg.cache_key(),
               tuple((tuple(s.shape), str(s.dtype)) for s in shapes))
        if key not in self._compiled:
            try:
                with quiet_unusable_donation():
                    compiled = self._get_compiled(cfg).aot(shapes)
                costs = _profile.program_costs(compiled)
            except Exception:
                costs = None  # fall through to the analytic estimate
            if costs is None:
                costs = self._analytic_costs(cfg, shapes)
                costs["source"] = "analytic"
            else:
                costs["source"] = "measured"
            self._compiled[key] = costs
        return self._compiled[key]

    def _program_memory(self, cfg: EngineConfig, shapes) -> dict:
        """HBM footprint of ONE wave program (obs/memory): XLA's
        ``memory_analysis()`` off the executable the run dispatched,
        with the labelled analytic fallback for backends without one.
        Cached per (cfg, shape) like the cost model."""
        key = ("mem", cfg.cache_key(),
               tuple((tuple(s.shape), str(s.dtype)) for s in shapes))
        if key not in self._compiled:
            mem = None
            try:
                with quiet_unusable_donation():
                    compiled = self._get_compiled(cfg).aot(shapes)
                mem = _memory_obs.program_memory(compiled)
            except Exception:
                mem = None  # fall through to the analytic estimate
            if mem is None:
                mem = _memory_obs.analytic_program_memory(shapes)
            self._compiled[key] = mem
        return self._compiled[key]

    def autotune_key(self) -> str:
        """The capacity controller's learning key: everything that
        identifies the PROGRAM FAMILY minus the capacities themselves
        (two runs of one workload at different capacities must share a
        key, or nothing would ever be learned across a resize)."""
        cfg = self.config
        return "|".join([
            _compile_obs.op_token(self.map_fn),
            _compile_obs.op_token(cfg.reduce_op)
            if callable(cfg.reduce_op) else str(cfg.reduce_op),
            str(cfg.unit_values), str(cfg.combine_in_scan),
            str(cfg.sort_impl), str(cfg.tile), str(self.n_dev)])

    def _replay_info(self, cfg: EngineConfig, structs):
        """The shape-bucket registry's replay record: enough to rebuild
        and AOT-prime this exact wave program in a fresh process
        (``cli warmup --replay``).  None when the program cannot replay
        — a lambda map_fn or a callable reduce op has no stable
        cross-process spelling."""
        path = _compile_obs.fn_path(self.map_fn)
        if path is None or not isinstance(cfg.reduce_op, str):
            return None
        chunks = structs[0]
        from dataclasses import asdict

        return {
            "kind": "device_engine",
            "map_fn": path,
            "config": asdict(cfg),
            "k": int(chunks.shape[0]) // self.n_dev,
            "row_shape": [int(d) for d in chunks.shape[1:]],
            "row_dtype": str(chunks.dtype),
            "n_dev": self.n_dev,
        }

    def _analytic_costs(self, cfg: EngineConfig, shapes) -> dict:
        """Analytic fallback: the record count comes from tracing
        map_fn's output aval on one chunk (exact T — nothing declared up
        front, matching the engine's shape-inference contract), record
        width from the value/payload dtypes; obs/profile.analytic_costs
        turns that into the sort-dominated flops/bytes estimate."""
        chunk_rows = int(shapes[0].shape[0])
        row_shape = tuple(shapes[0].shape[1:])
        input_bytes = int(chunk_rows
                          * np.prod(row_shape, dtype=np.int64).item()
                          * np.dtype(shapes[0].dtype).itemsize)
        try:
            row = jax.ShapeDtypeStruct(row_shape, shapes[0].dtype)
            idx = jax.ShapeDtypeStruct((), np.int32)
            k0, v0, p0, _valid, _of = jax.eval_shape(
                lambda c, i: self.map_fn(c, i, cfg), row, idx)
            T = int(k0.shape[0])
            Q = int(p0.shape[1])
            val_bytes = (int(np.prod(v0.shape[1:], dtype=np.int64).item()
                             or 1)
                         * np.dtype(v0.dtype).itemsize)
        except Exception:
            # un-traceable aval probe: assume wordcount-ish density
            L = int(np.prod(row_shape, dtype=np.int64).item()) or 1
            T = max(L // max(cfg.tile, 1), 1) * cfg.tile_records
            Q, val_bytes = 1, 4
        n_records = chunk_rows * T
        record_bytes = 8 + val_bytes + 4 * Q + 1  # key + value + payload
        # the fused fold re-sorts the accumulator rows (out_capacity
        # running uniques) into every wave's final merge pass; the
        # argsort tier additionally pays the second sort pass and the
        # permutation gathers (tier-0's runtime price); the radix tier
        # replaces the comparator n·log(n) terms with the digit-pass
        # formulation (passes × lane bytes + histogram/scatter flops);
        # segment_impl picks between the scan-ladder term and the
        # fused-kernel term (one pass over the records instead of
        # log2(N) ladder passes) so a kernel-served run's MFU/roofline
        # gauges model the program that actually ran
        return _profile.analytic_costs(input_bytes, n_records,
                                       record_bytes,
                                       fold_records=cfg.out_capacity,
                                       argsort=(cfg.sort_impl
                                                == "argsort"),
                                       segment_impl=cfg.segment_impl,
                                       sort_impl=cfg.sort_impl)

    def precompile(self, row_shape, row_dtype=np.uint8,
                   k: int = None) -> float:
        """AOT-compile the fused per-wave program at the AUTO wave shape
        for rows of *row_shape*, returning the seconds spent.  (There is
        no separate merge program anymore — the wave fold is fused into
        the one dispatch, so this primes the engine's entire compiled
        surface.)  With ``jax.config.jax_compilation_cache_dir`` set,
        this populates XLA's persistent cache — the lax.sort comparator
        dominates the cold compile (decoupled from record width by the
        rank-sort) and the auto wave split is corpus-size-independent,
        so one warmup serves every future corpus on the machine.
        (bench.py runs this synchronously after staging.)"""
        import time

        t0 = time.monotonic()
        if k is None:
            row_bytes = int(np.dtype(row_dtype).itemsize
                            * np.prod(row_shape))
            k = self._rows_per_wave(row_bytes)
        cfg = self.config
        # lower with the RUN path's shardings: the persistent-cache key
        # covers input shardings, so an unsharded AOT lowering would
        # prime entries the real jit dispatch never hits
        row_sh = NamedSharding(self.mesh, P(AXIS))
        rep = NamedSharding(self.mesh, P())
        shapes = (
            jax.ShapeDtypeStruct((k * self.n_dev,) + tuple(row_shape),
                                 row_dtype, sharding=row_sh),
            jax.ShapeDtypeStruct((k * self.n_dev,), np.int32,
                                 sharding=row_sh),
            jax.ShapeDtypeStruct((), np.int32, sharding=rep),
        ) + tuple(
            jax.ShapeDtypeStruct((self.n_dev,) + a.shape, a.dtype,
                                 sharding=row_sh)
            for a in self._fin_row_avals(_steady_cfg(cfg), row_shape,
                                         row_dtype))
        if cfg.exchange_stats:
            shapes += (jax.ShapeDtypeStruct(
                (self.n_dev, self.n_dev), np.int32, sharding=row_sh),)
        if cfg.partition_map:
            shapes += (jax.ShapeDtypeStruct(
                (self.partition_buckets,), np.int32, sharding=rep),)
        # a tier policy primes BOTH per-tier programs: a warmed
        # machine must never fall back to tier-0 serving (the warmness
        # probe sees the steady-tier bucket and skips tiering outright)
        cfgs = _tier_cfgs(cfg) if _is_tiered(cfg.sort_impl) else (cfg,)
        with quiet_unusable_donation():
            for c in cfgs:
                self._get_compiled(c).aot(shapes)
        return time.monotonic() - t0

    def stage_inputs(self, chunks: np.ndarray, waves: int = None):
        """Issue and COMPLETE the host->device transfer of *chunks*,
        returning an opaque staged handle for :meth:`run`.

        Upload and compute can be legitimately decoupled: a user
        streaming a corpus can stage the next batch while deciding what
        to run, and a benchmark can separate ingress cost from pipeline
        cost.  ``run(chunks, staged=...)`` then charges no upload.

        Residency is VERIFIED, not assumed: this method runs a checksum
        program over every staged buffer and fetches the scalar — the
        return therefore means the bytes are on the device.  (Whether
        ``jax.block_until_ready`` alone suffices on current hardware is
        not measured; ROADMAP A.)

        Unlike the streaming run path (bounded at ~STREAM_PREFETCH waves),
        a staged handle holds the WHOLE corpus in device memory — that is
        its point.  The handle is single-use: :meth:`run` consumes it,
        freeing each wave as soon as its program completes."""
        if waves is None:
            feeder = _WaveFeeder(self, chunks, k=self._auto_rows(chunks))
        else:
            feeder = _WaveFeeder(self, chunks, max(1, waves))
        resolved = [feeder.get(w) for w in range(feeder.waves)]
        n_real = feeder.n_real
        feeder.close()  # resolved list owns the references now
        jax.block_until_ready([a for pair in resolved for a in pair])
        # residency barrier: a scalar depending on a slice of every
        # staged buffer cannot be produced until the transfers finish
        key = ("stage_barrier", len(resolved))
        if key not in self._compiled:
            self._compiled[key] = _compile_obs.wrap_jit(
                lambda *cs: sum(jnp.sum(c[..., ::4096].astype(jnp.int32))
                                for c in cs),
                program="stage_barrier",
                key=key + (self._mesh_fp,),
                bucket_extra=("stage_barrier",))
        np.asarray(self._compiled[key](*[ci for ci, _ in resolved]))
        return resolved, n_real

    def run(self, chunks: np.ndarray, max_retries: int = 3,
            timings: dict = None, waves: int = None,
            staged=None, on_overflow: str = "raise") -> DeviceResult:
        """Execute over *chunks* ([S, ...] host array, sharded over the
        mesh), growing capacities until no stage overflowed.

        *waves* (default: auto from input size) pipelines the host->device
        link against the TPU AND bounds device memory: each wave's input
        is uploaded (at most STREAM_PREFETCH waves in flight), ONE fused
        map/sort/shuffle/fold program dispatched (the running
        per-partition uniques ride through it as donated arguments), and
        its input FREED by that donation — peak HBM is ~2 wave inputs +
        the accumulated uniques, never the corpus (the reference's
        bounded-memory input iterators, utils.lua:133-200, done for HBM).

        Pass ``timings={}`` to receive per-stage wall seconds — the
        device-path analogue of the host server's per-phase stats
        (server.lua:555-600).  With waves > 1 the stages genuinely
        overlap: ``upload_s`` is the wall time the driver spent *waiting*
        on transfers, ``compute_s`` the rest of the attempt.

        With ``staged`` (from :meth:`stage_inputs`) the *chunks* and
        *waves* arguments don't pick the data: the handle fixes both the
        data and its wave split, and no upload is charged to timings.
        The handle is CONSUMED — each wave is freed after its fold (pass
        the same *chunks* the handle was built from to keep capacity
        retries possible; they re-upload, streaming).

        If capacities still overflow after *max_retries* right-sized
        recompiles, raises ``RuntimeError`` — a truncated result never
        escapes accidentally.  Pass ``on_overflow="return"`` to receive
        the truncated ``DeviceResult`` (``.overflow`` > 0) instead."""
        if staged is not None and waves is not None:
            raise ValueError(
                "run(staged=...) uses the handle's wave split; "
                "pass waves to stage_inputs instead")
        if on_overflow not in ("raise", "return"):
            raise ValueError(f"on_overflow must be 'raise' or 'return', "
                             f"got {on_overflow!r}")
        import time

        cfg = self.config
        # observe->act: a configured capacity controller pre-sizes this
        # run's capacities from prior retry forensics / the shape
        # registry (engine/autotune.py; every jump lands in the control
        # ledger).  autotune=None — the default — changes NOTHING.
        if self.autotune is not None:
            cfg = self.autotune.recommend_config(
                cfg, self.autotune_key(), task=self.task_label)
        t_start = time.monotonic()
        feeder = None
        pairs = None  # staged, pre-resolved waves (consumed in place)
        if staged is not None:
            staged_list, n_real = staged
            W = len(staged_list)
            if W == 0:
                raise RuntimeError(
                    "staged handle already consumed (handles are "
                    "single-use: each wave is freed as it is folded); "
                    "stage_inputs again for another run")
            pairs = {w: staged_list[w] for w in range(W)}
            # remember the handle's per-wave row split so a capacity
            # retry re-uploads at the SAME program shape (no recompile)
            staged_k = staged_list[0][0].shape[0] // self.n_dev
            row_shape = tuple(staged_list[0][0].shape[1:])
            row_dtype = staged_list[0][0].dtype
            # consume the handle: freeing below must work even while the
            # caller still holds it
            staged_list.clear()
        else:
            if waves is None:
                feeder = _WaveFeeder(self, chunks,
                                     k=self._auto_rows(chunks),
                                     prefetch=self.STREAM_PREFETCH)
            else:
                feeder = _WaveFeeder(self, chunks, max(1, waves),
                                     prefetch=self.STREAM_PREFETCH)
            W = feeder.waves  # clamped to data-bearing waves
            n_real = feeder.n_real
            row_shape = tuple(chunks.shape[1:])
            row_dtype = chunks.dtype

        t_upload = 0.0
        t_compute = 0.0
        t_attempt_compute = 0.0  # final attempt only (the MFU clock)
        retries = 0
        cost_shapes = None  # avals of the dispatched wave (cost model)
        tiered = _is_tiered(cfg.sort_impl)
        #: monotonic instant the FIRST wave program of the run was
        #: dispatched — run-entry to here is the cold time-to-serving
        #: the tiered formulation exists to shrink (bench.py gates it
        #: as cold_first_dispatch_s)
        t_first_dispatch = None
        # the replicated bucket->partition table rides every dispatch of
        # a partition_map run (an input, so a rebalance between runs
        # never recompiles); constant across attempts — capacities
        # resize, the bucket count does not
        pmap_args = ((self.device_pmap(),) if cfg.partition_map else ())
        try:
            depth = self._max_inflight_programs()
            for attempt in range(max_retries + 1):
                fn = self._wave_fn(cfg)
                # fresh all-invalid accumulator per attempt (capacities
                # may have grown; the prior attempt's buffers were
                # donated away wave by wave).  cost_shapes resets with
                # it: the accumulator avals are sized by the attempt's
                # cfg, so the cost model must see the FINAL attempt's
                # shapes — lowering the resized program against a stale
                # attempt's avals would miss the executable cache (a
                # fresh ~100s compile at bench shapes) and record costs
                # for a program that never ran.
                acc = self._acc_init(_steady_cfg(cfg), row_shape,
                                     row_dtype)
                cost_shapes = None
                # per-attempt span tree: device_run ⊃ wave ⊃ {upload,
                # compute, readback}, joined (via the thread's current
                # span) under the owning job's trace.  Waves OVERLAP —
                # wave w+1 uploads while wave w computes and a wave's
                # readback lands depth waves later — so they are
                # detached spans closed by the readback that proves the
                # wave's device work finished, not lexical scopes.  Each
                # begins at the instant its interval starts (never
                # backdated), so the profiler's trace holds it too.
                t0 = time.monotonic()
                run_sp = TRACER.begin("device_run", attempt=attempt,
                                      waves=W)
                t_blocked = 0.0
                wave_oflows = []
                wave_oflow_vals = {}
                need_arrays = []
                # upload/compute overlap accounting (obs/comms): the
                # attempt's upload-wait intervals and a device-busy
                # proxy per wave (dispatch -> the readback that proved
                # the wave's device work finished).  Reset per attempt:
                # the FINAL attempt's feeder behaviour is the one the
                # overlap fraction reports, matching the cost model.
                upload_ivals = []
                busy_ivals = []
                dispatch_t = {}
                wave_spans = {}
                #: the upload or compute child span now open, closed by
                #: the attempt's ``finally`` if its stage raises
                stage_sp = None

                def _read_wave_oflow(j: int) -> None:
                    # the (tiny) overflow VALUE readback both bounds the
                    # dispatch queue and proves wave j's program
                    # finished — so it records the wave's readback child
                    # and closes the wave span
                    sp = wave_spans.get(j)
                    tr0 = time.monotonic()
                    rb_sp = (TRACER.begin("readback", parent=sp,
                                          kind="overflow")
                             if sp is not None else None)
                    try:
                        wave_oflow_vals[j] = int(
                            self._host(wave_oflows[j]).sum())
                    finally:
                        tr1 = time.monotonic()
                        if rb_sp is not None:
                            TRACER.end(rb_sp, tr1)
                    if sp is not None:
                        del wave_spans[j]
                        TRACER.end(sp, tr1)
                        _WAVE_SECONDS.observe(tr1 - sp.t0, stage="wave")
                    _WAVE_SECONDS.observe(tr1 - tr0, stage="readback")
                    if j in dispatch_t:
                        # wave j's device-busy proxy: its program was in
                        # flight from dispatch until this readback
                        busy_ivals.append((dispatch_t.pop(j), tr1))
                    # per-wave HBM gauges: device memory_stats where the
                    # backend has them, else the engine's own first-party
                    # estimate (held input waves + the live accumulator),
                    # labelled analytic so nobody mistakes it
                    held = feeder.held_bytes if feeder is not None else 0
                    acc_bytes = sum(int(a.nbytes) for a in acc
                                    if hasattr(a, "nbytes"))
                    _memory_obs.sample_device_memory(
                        self._devices,
                        analytic_bytes_in_use=held + acc_bytes)

                try:
                    # ONE scoped unusable-donation filter per attempt
                    # (the expected warning fires at lowering — at
                    # most the attempt's first wave — and entering
                    # catch_warnings once per attempt instead of per
                    # dispatch minimises global filter churn)
                    with quiet_unusable_donation():
                        for w in range(W):
                            tb = time.monotonic()
                            wave_spans[w] = TRACER.begin("wave", parent=run_sp,
                                                         wave=w)
                            stage_sp = TRACER.begin("upload",
                                                    parent=wave_spans[w])
                            if pairs is not None:
                                ci, ii = pairs[w]
                            else:
                                ci, ii = feeder.get(w)
                            # wave w's program does not queue against an
                            # in-flight transfer (whether it needs to on
                            # current hardware is not measured; ROADMAP
                            # A); the wait is charged to upload
                            jax.block_until_ready(ci)
                            t_up = time.monotonic()
                            TRACER.end(stage_sp, t_up)
                            stage_sp = None
                            _WAVE_SECONDS.observe(t_up - tb, stage="upload")
                            t_blocked += t_up - tb
                            upload_ivals.append((tb, t_up))
                            if w >= depth:
                                # bound the dispatch queue via a VALUE
                                # readback: it cannot return before
                                # execution finishes, which holds both
                                # the HBM bound and the CPU rendezvous
                                # serialization
                                _read_wave_oflow(w - depth)
                            tc0 = time.monotonic()
                            stage_sp = TRACER.begin("compute",
                                                    parent=wave_spans[w],
                                                    async_dispatch=True)
                            if cost_shapes is None:
                                # capture BEFORE the dispatch: donation
                                # invalidates the inputs at call time
                                cost_shapes = tuple(
                                    jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                         sharding=a.sharding)
                                    for a in (ci, ii, n_real, *acc,
                                              *pmap_args))
                            # ONE dispatch per wave: map→sort→exchange→fold,
                            # the running uniques threaded through as
                            # donated args (out[:4] reuse their buffers)
                            out = fn(ci, ii, n_real, *acc, *pmap_args)
                            if t_first_dispatch is None:
                                t_first_dispatch = time.monotonic()
                            _DISPATCHES.inc(1, program="wave",
                                            task=self.task_label)
                            wave_oflows.append(out[4])
                            need_arrays.append(out[5])
                            # lanes 0-3 are the record accumulator; lane
                            # 6 (when exchange_stats) the traffic-matrix
                            # accumulator — both thread into the next
                            # wave in arg order
                            acc = list(out[:4]) + list(out[6:])
                            dispatch_t[w] = tc0
                            tc1 = time.monotonic()
                            TRACER.end(stage_sp, tc1)
                            stage_sp = None
                            _WAVE_SECONDS.observe(tc1 - tc0, stage="compute")
                            del out
                            # wave w is consumed: drop its input references
                            # so the HBM frees the moment its program
                            # completes
                            if pairs is not None:
                                pairs.pop(w, None)
                            else:
                                feeder.release(w)
                            del ci, ii
                    keys, vals, pay, valid = acc[:4]
                    traffic = acc[4] if cfg.exchange_stats else None
                    # the (tiny) overflow readbacks force program
                    # completion — and close each wave's span.  The
                    # fold's overflow is already inside each wave's
                    # lane: there are NO separate merge readbacks.
                    for w in range(W):
                        if w not in wave_oflow_vals:
                            _read_wave_oflow(w)
                    total_oflow = sum(wave_oflow_vals.values())
                finally:
                    # a failed attempt must not leak open wave spans
                    # into the next attempt's timeline
                    t_now = time.monotonic()
                    if stage_sp is not None:
                        TRACER.end(stage_sp, t_now, truncated=True)
                    for sp in wave_spans.values():
                        TRACER.end(sp, t_now, truncated=True)
                    wave_spans.clear()
                    TRACER.end(run_sp)
                # every attempt's transfer waits count: capacity retries
                # re-upload (inputs were freed wave by wave) and that cost
                # must show in the stats meant to expose it
                t_upload += t_blocked
                t_attempt_compute = time.monotonic() - t0 - t_blocked
                t_compute += t_attempt_compute
                if total_oflow == 0 or attempt == max_retries:
                    break  # done, or out of retries (don't size a cfg
                    # that will never run)
                retries = attempt + 1
                new_cfg = self._resize(cfg, need_arrays)
                # capacity-retry forensics (obs/memory): one structured
                # event carrying the attempt's program footprint and the
                # live device-memory state, so `cli diagnose` can say
                # whether the retry was HBM-bound or merely out-sized
                pm = (self._program_memory(
                          fn.effective_cfg if tiered else cfg,
                          cost_shapes)
                      if cost_shapes is not None else None)
                _memory_obs.capacity_retry_event(
                    task=self.task_label, attempt=attempt,
                    overflow_rows=total_oflow, program_memory_doc=pm,
                    devices=self._devices,
                    old_capacities=_capacities(cfg),
                    new_capacities=_capacities(new_cfg))
                if self.autotune is not None:
                    # the capacity controller learns the right-sized
                    # capacities, so the NEXT run (or session) with this
                    # program starts there instead of retrying again
                    self.autotune.note_retry(
                        self.autotune_key(), _capacities(cfg),
                        _capacities(new_cfg), task=self.task_label)
                cfg = new_cfg
                del acc, keys, vals, pay, valid, traffic
                # inputs were freed wave by wave: the retry re-uploads
                if pairs is not None:
                    if chunks is None:
                        raise RuntimeError(
                            "capacity retry needs the input re-uploaded, "
                            "but the staged handle is consumed and no "
                            "chunks were passed; call run(chunks, "
                            "staged=handle) with the handle's source "
                            "array")
                    feeder = _WaveFeeder(self, chunks, k=staged_k,
                                         prefetch=self.STREAM_PREFETCH)
                    pairs = None
                else:
                    feeder.reset()
        finally:
            if feeder is not None:
                feeder.close()
            if pairs:
                pairs.clear()
        if self.autotune is not None:
            # the next control window's measurement: zero retries after
            # a pre-sized start resolves the pending capacity decision
            self.autotune.note_run(self.autotune_key(), retries,
                                   task=self.task_label)
        if total_oflow and on_overflow == "raise":
            raise RuntimeError(
                f"device run still overflowed {total_oflow} rows after "
                f"{retries} right-sized retries; raise EngineConfig "
                "capacities (or max_retries), or pass "
                "on_overflow='return' to inspect the truncated result")
        # sliced readback: only the live prefix of each partition's
        # capacity-padded result crosses the (slow) device->host link.
        # The exchange traffic matrix rides the SAME n_live fetch: one
        # batched gather, not a second readback.
        t0 = time.monotonic()
        traffic_h = None
        with TRACER.span("readback", stage="result"):
            if traffic is not None:
                n_live, traffic_h = self._host(valid.sum(axis=1),
                                               traffic)
            else:
                n_live = self._host(valid.sum(axis=1))
            width = max(1, int(n_live.max()))
            keys_h, vals_h, pay_h, valid_h = self._host(
                keys[:, :width], vals[:, :width], pay[:, :width],
                valid[:, :width])
        result = DeviceResult(keys_h, vals_h, pay_h, valid_h, total_oflow)
        t_readback = time.monotonic() - t0
        # live counters for the exposition plane regardless of whether
        # the caller asked for a timings dict: per-wave upload/compute/
        # readback seconds are the device-path hot-path metrics
        _WAVES.inc(W, task=self.task_label)
        _RETRIES.inc(retries, task=self.task_label)
        _STAGE_SECONDS.inc(t_upload, stage="upload", task=self.task_label)
        _STAGE_SECONDS.inc(t_compute, stage="compute",
                           task=self.task_label)
        _STAGE_SECONDS.inc(t_readback, stage="readback",
                           task=self.task_label)
        # per-partition skew inputs: the exchange's live row count per
        # partition (n_live) and its approximate byte mass
        row_bytes = sum(
            a.dtype.itemsize * int(np.prod(a.shape[2:], dtype=np.int64))
            if a.ndim > 2 else a.dtype.itemsize
            for a in (keys_h, vals_h, pay_h))
        for p, n in enumerate(np.asarray(n_live).reshape(-1)):
            _PARTITION_RECORDS.set(int(n), task=self.task_label,
                                   partition=f"P{p:05d}")
            _PARTITION_BYTES.set(int(n) * row_bytes,
                                 task=self.task_label,
                                 partition=f"P{p:05d}")
        # comms observability (obs/comms): the run's exchange traffic
        # matrix -> per-(src,dst) counters, imbalance gauges, link-class
        # roll-up + modeled exchange seconds vs this attempt's compute;
        # and the feeder-effectiveness number — how much of the upload
        # waiting hid under device execution.  On a multi-controller
        # mesh every process holds the identical replicated matrix (the
        # _host all-gather), and the collector SUMS counter families
        # across processes — so only process 0 publishes the matrix, or
        # /clusterz would report N_procs x the true traffic.  The
        # timings dict still carries it everywhere (SPMD-consistent).
        comms_derived: dict = {}
        if traffic_h is not None:
            comms_derived = _comms.record_exchange(
                np.asarray(traffic_h).tolist(), row_bytes=row_bytes,
                task=self.task_label, devices=self._devices,
                compute_s=t_attempt_compute,
                publish=jax.process_index() == 0)
        overlap = _comms.record_upload_overlap(
            _comms.overlap_fraction(upload_ivals, busy_ivals),
            task=self.task_label)
        # cost model: FLOPs/bytes of the final wave program (XLA
        # cost_analysis, analytic fallback on backends without one) ->
        # flop/byte counters + derived MFU / roofline gauges.  The MFU
        # clock is the FINAL attempt's compute seconds — a retried
        # attempt ran a differently-sized program whose flops aren't the
        # ones counted.
        derived = {}
        # a tiered run's cost/memory models lower the config of the
        # tier that actually dispatched last — the ledger's aot() then
        # re-serves the exact executable the run used, never a fresh
        # compile of the other tier
        cost_cfg = fn.effective_cfg if tiered else cfg
        if cost_shapes is not None:
            costs = self._program_costs(cost_cfg, cost_shapes)
            derived = _profile.record_run(
                costs, waves=W, compute_s=t_attempt_compute,
                n_dev=self.n_dev,
                device=next(iter(self.mesh.devices.flat)),
                task=self.task_label)
            # per-program HBM footprint rides the same timings dict the
            # cost model does, so the stats doc / statusz per-task
            # stats carry it (obs/memory publishes the gauges)
            mem = self._program_memory(cost_cfg, cost_shapes)
            derived["program_memory_bytes"] = int(mem.get("total", 0))
            derived["memory_source"] = mem.get("source", "measured")
            sav = _memory_obs.donation_savings(
                mem, list(cost_shapes), _wave_donate_argnums(cfg))
            _memory_obs.record_donation("wave", sav)
            derived["donation_saved_bytes"] = int(sav["bytes"])
        if timings is not None:
            timings.update(derived)
            timings.update(comms_derived)
            timings["upload_overlap_frac"] = round(overlap, 4)
            timings["waves"] = W
            timings["retries"] = retries
            if t_first_dispatch is not None:
                # run-entry -> first wave program dispatched: the cold
                # serving latency (covers compile of whichever tier
                # served wave 0 plus its upload)
                timings["first_dispatch_s"] = round(
                    t_first_dispatch - t_start, 3)
            if tiered:
                timings["tier_swaps"] = fn.swaps
                timings["tier_cold_start"] = fn.cold
                timings["serving_tier"] = fn.tier
            if feeder is not None:
                # the HBM-bound witness: peak bytes of input waves ever
                # held at once (~STREAM_PREFETCH waves), vs the corpus
                timings["peak_input_wave_bytes"] = feeder.peak_held_bytes
                if chunks is not None:
                    timings["input_bytes"] = int(chunks.nbytes)
            if staged is None:  # staged callers timed the upload already
                timings["upload_s"] = round(t_upload, 3)
            elif t_upload > 0.01:  # resolved-handle waits are ~0
                # capacity retries re-upload even under a staged handle;
                # that wait must surface somewhere (a separate key, so it
                # never double-counts the caller's own staging time)
                timings["retry_upload_s"] = round(t_upload, 3)
            timings["compute_s"] = round(t_compute, 3)
            timings["readback_s"] = round(t_readback, 3)
            if staged is None:
                # staged callers assemble their own run total (their
                # upload happened elsewhere); an engine-local total here
                # would contradict it
                timings["total_s"] = round(time.monotonic() - t_start, 3)
        return result


# -- shape-registry replay (cli warmup --replay) -----------------------------


def replay_registry(mesh: Mesh, registry_dir: str = None) -> list:
    """AOT-prime EVERY replayable bucket in the on-disk shape registry
    (obs/compile) against *mesh* — the full warm start, not just the
    DeviceWordCount default.  A bucket replays when it recorded a
    ``device_engine`` replay spec (importable map_fn, string reduce op)
    and its device count matches this mesh; anything else is reported
    as skipped with the reason, never silently dropped.  Returns one
    result dict per bucket."""
    from ..obs.compile import LEDGER, resolve_fn

    results = []
    buckets = LEDGER.disk_buckets(registry_dir)
    engines: dict = {}
    for bucket, rec in sorted(buckets.items()):
        row = {"bucket": bucket, "program": rec.get("program"),
               "tier": rec.get("tier")}
        replay = rec.get("replay")
        if not isinstance(replay, dict) or \
                replay.get("kind") != "device_engine":
            row["skipped"] = "no replay spec recorded"
            results.append(row)
            continue
        if int(replay.get("n_dev", 0)) != mesh.shape[AXIS]:
            row["skipped"] = (
                f"recorded for {replay.get('n_dev')} devices, mesh has "
                f"{mesh.shape[AXIS]}")
            results.append(row)
            continue
        try:
            map_fn = resolve_fn(replay["map_fn"])
            cfg = EngineConfig(**replay["config"])
            ekey = (replay["map_fn"], _cfg_token(cfg))
            eng = engines.get(ekey)
            if eng is None:
                eng = engines[ekey] = DeviceEngine(mesh, map_fn, cfg)
            secs = eng.precompile(
                tuple(replay["row_shape"]),
                np.dtype(replay["row_dtype"]),
                k=int(replay["k"]))
            row["seconds"] = round(secs, 3)
        except Exception as exc:  # a bad bucket must not stop the rest
            row["skipped"] = f"replay failed: {exc}"
        results.append(row)
    return results
