"""Device WordCount: the end-to-end "aha" slice (SURVEY.md §7 step 4).

The reference's flagship workload — Europarl word-count, 197 splits, its
whole performance story (README.md:40-113, BASELINE.md) — runs here as one
SPMD program: on-device tokenization + hashing (ops/tokenize.py),
scatter-free tile compaction of word records (ops/compaction.py), ONE
device-wide sort + segmented count (ops/segscan.py via the engine),
hash-partition + all_to_all, then host-side materialisation of the unique
words by slicing the original bytes at one representative occurrence per
hash.  The host never loops over tokens; it only loops over *unique
words* (the vocabulary, thousands of times smaller than the corpus), and
that loop is numpy window-gather, not per-element Python.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from jax.sharding import Mesh

from ..obs.trace import TRACER
from ..ops.compaction import tile_compact
from ..ops.tokenize import (
    HASH_A1, HASH_A2, HASH_A3, tokenize_hash, shard_text)
from .device_engine import DeviceEngine, EngineConfig

#: whitespace byte values (must match ops/tokenize._WS)
_WS_BYTES = (32, 9, 10, 13, 12, 11)
#: host materialisation window: words longer than this fall back to a
#: per-row Python scan (vanishingly rare in natural language)
_WINDOW = 128


def _wordcount_map_fn(chunk, chunk_index, cfg: EngineConfig):
    """map_fn: one padded byte chunk -> (hash-keys, count=1, payload) with
    payload = the word's global start byte offset (chunk_index * L +
    local start), from which the host slices the word's bytes back out.

    Tile compaction (one-hot matmul, no scatter) packs the per-byte
    token stream into at most ``L // cfg.tile * cfg.tile_records``
    records; drops are counted and the engine retries with tile_records
    grown to fit (DeviceEngine._resize)."""
    import jax.numpy as jnp

    L = chunk.shape[0]
    toks = tokenize_hash(chunk, impl=cfg.tokenize_impl,
                         block=cfg.tokenize_block)
    gstart = chunk_index * L + toks.start  # global byte offset, fits i32
    tc = tile_compact(toks.is_end, cfg.tile, cfg.tile_records,
                      toks.keys[:, 0], toks.keys[:, 1], gstart)
    k1, k2, gs = tc.arrays
    keys = jnp.stack([k1, k2], axis=-1)
    values = tc.valid.astype(jnp.int32)
    payload = gs.astype(jnp.int32)[:, None]
    return keys, values, payload, tc.valid, tc.overflow


#: public name for modules wiring the engine through the unified device
#: fast path (spec.DeviceSpec.map_fn)
wordcount_map_fn = _wordcount_map_fn


def _verify_reduce_op(a, b):
    """Associative+commutative: lane 0 count sum, lanes 1/2 min/max of the
    third (independent) word hash.  After full reduction, lane1 != lane2
    for a unique key proves two DISTINCT byte strings shared both key
    lanes (a 64-bit collision) — detection the host alone cannot do,
    since the device-side merge leaves it only one representative."""
    import jax.numpy as jnp

    return jnp.stack([a[..., 0] + b[..., 0],
                      jnp.minimum(a[..., 1], b[..., 1]),
                      jnp.maximum(a[..., 2], b[..., 2])], axis=-1)


def _wordcount_map_fn_verify(chunk, chunk_index, cfg: EngineConfig):
    """Collision-verify variant: values = [count=1, h3, h3] where h3 is a
    third polynomial hash lane, reduced with (sum, min, max)."""
    import jax.numpy as jnp

    L = chunk.shape[0]
    toks = tokenize_hash(chunk, multipliers=(HASH_A1, HASH_A2, HASH_A3),
                         impl=cfg.tokenize_impl, block=cfg.tokenize_block)
    gstart = chunk_index * L + toks.start
    tc = tile_compact(toks.is_end, cfg.tile, cfg.tile_records,
                      toks.keys[:, 0], toks.keys[:, 1],
                      toks.keys[:, 2], gstart)
    k1, k2, k3, gs = tc.arrays
    keys = jnp.stack([k1, k2], axis=-1)
    h3 = k3.astype(jnp.int32)
    values = jnp.stack([tc.valid.astype(jnp.int32), h3, h3], axis=-1)
    payload = gs.astype(jnp.int32)[:, None]
    return keys, values, payload, tc.valid, tc.overflow


def bench_engine_config() -> EngineConfig:
    """The flagship bench's engine capacities (bench.py and the
    ``warmup`` CLI must agree bit-for-bit for the persistent compilation
    cache to hit).  tile_records 104: ~25% headroom over the ~83 words
    per 512-byte tile of natural text, and fewer half-empty record
    slots to sort than 128 (the gain is not measured on current
    hardware).
    combine_in_scan: natural text is duplicate-heavy (a 4MB chunk holds
    ~850K running words but well under 100K uniques), so the in-scan
    combiner shrinks the device-wide sort ~4x; combine_capacity 1<<17
    (~131K slots per chunk) clears any natural-language vocabulary with
    headroom while keeping the wave program shape fixed.
    segment_impl/tokenize_impl 'pallas': the flagship bench serves the
    fused hot-path kernels (ops/segscan, ops/tokenize) — bit-identical
    to the lax formulations (golden suite + the bench's own pallas
    smoke gate), selected here so `europarl_wordcount_compute_s` and
    the gated `wordcount_mfu` key measure the kernel-served program."""
    return EngineConfig(local_capacity=1 << 18,
                        exchange_capacity=1 << 17,
                        out_capacity=1 << 18,
                        tile=512, tile_records=104,
                        combine_in_scan=True,
                        combine_capacity=1 << 17,
                        segment_impl="pallas",
                        tokenize_impl="pallas")


class DeviceWordCount:
    """Count words of a text corpus on a TPU mesh.

    ``chunk_len`` is the static per-chunk byte length; capacities default
    to values sized for natural-language vocabularies and are grown
    automatically on overflow, right-sized from the failed run's
    measured needs (DeviceEngine.run/_resize).

    ``verify_collisions=True`` detects 64-bit hash-key collisions (two
    distinct words merged on device; odds ~3e-8 at a 1M vocabulary) by
    carrying a third independent hash lane reduced with (min, max) — at
    the cost of three extra sort operands per stage.
    """

    def __init__(self, mesh: Mesh, chunk_len: int = 1 << 22,
                 config: Optional[EngineConfig] = None,
                 verify_collisions: bool = False) -> None:
        self.mesh = mesh
        self.chunk_len = chunk_len
        self.verify_collisions = verify_collisions
        # the default config runs the on-device combiner: wordcount is
        # the duplicate-heavy workload it exists for (counting IS an ACI
        # monoid), and the per-chunk pre-reduce shrinks the device-wide
        # sort.  An explicit *config* keeps full control (tests exercise
        # both paths).
        cfg = config or EngineConfig(
            local_capacity=1 << 17, exchange_capacity=1 << 15,
            out_capacity=1 << 17, combine_in_scan=True)
        from dataclasses import replace
        if verify_collisions:
            # carry [count, h3, h3] value lanes reduced with
            # (sum, min, max): min != max after full reduction proves a
            # 64-bit key collision (checked in materialize_counts)
            cfg = replace(cfg, unit_values=False,
                          reduce_op=_verify_reduce_op,
                          tile=min(cfg.tile, chunk_len))
        else:
            # wordcount records are unit counts: run lengths replace a
            # value lane (drops one sort operand)
            cfg = replace(cfg, unit_values=True, reduce_op="sum",
                          tile=min(cfg.tile, chunk_len))
        self.config = cfg
        self._map_fn = (_wordcount_map_fn_verify if verify_collisions
                        else _wordcount_map_fn)
        self._engines: Dict[int, DeviceEngine] = {}

    def warm(self) -> float:
        """AOT-compile the engine programs at the EXACT shape every run
        executes (the fixed ``_row_len`` chunk rows and the auto wave
        split are both corpus-independent), priming XLA's persistent
        cache (see DeviceEngine.precompile); returns seconds spent."""
        return self._engine_for(self._row_len()).precompile(
            (self._row_len(),), np.uint8)

    def _engine_for(self, padded_len: int) -> DeviceEngine:
        """One engine per padded chunk length."""
        if padded_len not in self._engines:
            self._engines[padded_len] = DeviceEngine(
                self.mesh, self._map_fn, self.config)
        return self._engines[padded_len]

    @property
    def engine(self) -> DeviceEngine:
        """Most recently used engine (exposed for inspection/benchmarks)."""
        return next(reversed(self._engines.values())) if self._engines \
            else self._engine_for(self.chunk_len)

    def count_bytes(self, data: bytes, timings: Optional[dict] = None,
                    waves: Optional[int] = None) -> Dict[bytes, int]:
        """Count whitespace-separated words of *data* (the user surface:
        same answer as examples/naive.wordcount on the same bytes).

        Counts are int32 end-to-end: a single key is exact up to 2**31-1
        occurrences (~8 GB of one repeated 3-byte word) — beyond that the
        count wraps.  Corpora near that bound need a wider value lane.

        Pass ``timings={}`` to receive per-stage wall seconds (split /
        upload / compute / readback / materialize) — the device-path
        analogue of the reference server's per-phase stats report
        (server.lua:555-600)."""
        import time

        # spans: wordcount ⊃ {split, device_run ⊃ wave..., readback,
        # materialize}; split and materialize are host work with the
        # device empty, the same instants timings reports as durations
        with TRACER.span("wordcount", bytes=len(data)):
            t0 = time.monotonic()
            with TRACER.span("split"):
                # chunk count rounds up to a mesh multiple so every
                # device participates
                chunks, L = self._to_chunks(data)
            t_split = time.monotonic() - t0
            result = self._engine_for(L).run(chunks, timings=timings,
                                             waves=waves)
            out = self._finish(chunks, result, timings)
        if timings is not None:
            timings["split_s"] = round(t_split, 3)
        return out

    def count_files(self, paths) -> Dict[bytes, int]:
        parts = []
        for p in paths:
            with open(p, "rb") as f:
                parts.append(f.read())
        return self.count_bytes(b"\n".join(parts))

    # -- decoupled upload (DeviceEngine.stage_inputs rationale) ------------

    def stage(self, data: bytes, waves: Optional[int] = None):
        """Ship *data*'s chunks to the device now; count later with
        :meth:`count_staged`.  Returns an opaque staged handle."""
        chunks, L = self._to_chunks(data)
        staged = self._engine_for(L).stage_inputs(chunks, waves)
        return chunks, L, staged

    def count_staged(self, handle,
                     timings: Optional[dict] = None) -> Dict[bytes, int]:
        """Count a corpus previously uploaded with :meth:`stage`."""
        chunks, L, staged = handle
        with TRACER.span("wordcount", staged=True):
            result = self._engine_for(L).run(chunks, timings=timings,
                                             staged=staged)
            return self._finish(chunks, result, timings)

    def _finish(self, chunks, result,
                timings: Optional[dict]) -> Dict[bytes, int]:
        """Shared post-run tail: host materialisation.  (Truncation cannot
        reach here: run() raises on exhausted retries by default.)"""
        import time

        t0 = time.monotonic()
        with TRACER.span("materialize"):
            out = materialize_counts(chunks, result)
        if timings is not None:
            timings["materialize_s"] = round(time.monotonic() - t0, 3)
        return out

    def host_exchange_matrix(self, data: bytes,
                             waves: Optional[int] = None) -> np.ndarray:
        """Host recompute of the exchange traffic matrix a
        ``count_bytes(data, waves=waves)`` run accumulates on device
        (obs/comms): per wave, each device's buffer holds its chunks'
        records, the local reduce collapses them to the device's unique
        hash keys, and every unique routes to partition ``k1 % P`` —
        so entry ``[src][dst]`` is the number of distinct word keys of
        *src*'s per-wave chunk block whose hash lands on *dst*, summed
        over waves.  Pure numpy/Python over the SAME chunking the run
        uses; the comms test suite, the multichip dryrun and the bench
        smoke assert bit-equality against the device matrix."""
        from ..ops.tokenize import word_hashes_host

        chunks, L = self._to_chunks(data)
        eng = self._engine_for(L)
        n_dev = eng.n_dev
        S = chunks.shape[0]
        if waves is None:
            k = eng._auto_rows(chunks)
        else:
            k = -(-S // (max(1, waves) * n_dev))
        rpw = k * n_dev
        matrix = np.zeros((n_dev, n_dev), dtype=np.int64)
        for w in range(-(-S // rpw)):
            for d in range(n_dev):
                lo = w * rpw + d * k
                block = chunks[lo:min(lo + k, S)]
                if block.size == 0:
                    continue
                words: set = set()
                for row in block:
                    # per row, never concatenated: a chunk whose content
                    # runs to its final byte must not merge its last
                    # word with the next chunk's first
                    words.update(row.tobytes().split())
                # dedupe by the (k1, k2) KEY pair exactly as the device
                # local reduce does (two words colliding on both lanes
                # would be one device record), then route by k1 % P
                keys = set(word_hashes_host(b" ".join(words)).values())
                for k1, _k2 in keys:
                    matrix[d, k1 % n_dev] += 1
        return matrix

    def _row_len(self) -> int:
        """The ONE padded chunk length every corpus maps to: chunk_len
        plus one tile of slack for the whitespace-boundary overhang
        (spans shift forward to the next space, bounded by the longest
        word).  Corpus-independent, so warm()'s precompiled shape is the
        shape every run actually executes — a data-dependent max-span
        length would recompile per corpus size and never hit the primed
        cache entry."""
        return self.chunk_len + self.config.tile

    def _to_chunks(self, data: bytes):
        n_chunks = max(1, -(-len(data) // self.chunk_len))
        n_dev = self.mesh.shape["data"]
        n_chunks = -(-n_chunks // n_dev) * n_dev
        return shard_text(data, n_chunks, pad_multiple=self.config.tile,
                          pad_to=self._row_len())


def materialize_counts(chunks: np.ndarray, result) -> Dict[bytes, int]:
    """Host materialisation, vectorised: gather a fixed window of bytes at
    every unique word's start offset with one numpy fancy-index, find each
    word's end as the first whitespace in its window, then build the dict
    over uniques only.  (Round 1 looped Python over every unique with
    per-element array slicing — on the timed path of the flagship bench.)
    """
    S, L = chunks.shape
    valid = result.valid.reshape(-1)
    starts = result.payload.reshape(-1, result.payload.shape[-1])[:, 0]
    # verify mode carries [count, min(h3), max(h3)] value lanes
    verify = result.values.ndim == 3
    if verify:
        vals3 = result.values.reshape(-1, 3)
        vals = vals3[:, 0]
    else:
        vals = result.values.reshape(-1)
    live_rows = np.nonzero(valid)[0]
    if live_rows.size == 0:
        return {}
    gstart = starts[live_rows].astype(np.int64)
    counts = vals[live_rows]
    if verify:
        # two DISTINCT words sharing both 32-bit key lanes would have
        # been merged on device; their third-lane hashes differ (w.p.
        # 1 - 2^-32), so min(h3) != max(h3) exposes the merge.  The host
        # cannot see this any other way — the merged unique keeps only
        # one representative occurrence.
        mins = vals3[live_rows, 1]
        maxs = vals3[live_rows, 2]
        bad = np.nonzero(mins != maxs)[0]
        if bad.size:
            raise RuntimeError(
                f"64-bit hash collision detected for {bad.size} key(s): "
                "distinct words were merged on device. Re-run with "
                "different HASH_A1/HASH_A2 multipliers (ops/tokenize.py).")

    words = gather_words(chunks, gstart)
    out: Dict[bytes, int] = {}
    for word, c in zip(words, counts):
        out[word] = out.get(word, 0) + int(c)
    return out


def gather_words(chunks: np.ndarray, gstarts: np.ndarray):
    """The word bytes at each padded-space start offset (``chunk*L +
    local``), as a list aligned with *gstarts* — one numpy window-gather
    over all offsets, with a per-row Python scan only for words longer
    than the window (shared by every device workload that materialises
    string keys from payload offsets)."""
    S, L = chunks.shape
    flat = chunks.reshape(-1)
    gstarts = np.asarray(gstarts, dtype=np.int64)
    # windows[i] = corpus bytes [gstart_i, gstart_i + _WINDOW)
    offs = gstarts[:, None] + np.arange(_WINDOW)[None, :]
    np.clip(offs, 0, flat.size - 1, out=offs)
    windows = flat[offs]  # [U, W] uint8
    is_ws = np.isin(windows, _WS_BYTES)
    # words never span chunks (shard_text cuts at whitespace) and chunks
    # are space-padded, so a separator always exists inside the window
    # for words shorter than it
    has_end = is_ws.any(axis=1)
    lengths = np.where(has_end, is_ws.argmax(axis=1), _WINDOW)

    out = []
    win_bytes = windows.tobytes()
    W = _WINDOW
    for i in range(gstarts.size):
        if has_end[i]:
            out.append(win_bytes[i * W:i * W + int(lengths[i])])
        else:  # overlong word: rare fallback, scan the original bytes
            g = int(gstarts[i])
            row, col = divmod(g, L)
            end = col
            crow = chunks[row]
            while end < L and crow[end] not in _WS_BYTES:
                end += 1
            out.append(crow[col:end].tobytes())
    return out
