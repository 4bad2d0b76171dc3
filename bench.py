"""Benchmark: Europarl-scale word count on the device engine.

Reference headline (BASELINE.md): word-count over Europarl-v7 English —
1,965,734 lines / 49,158,635 running words — in 47.372 s cluster time on
4 CPU workers (reference README.md:70).  This bench runs the same-scale
workload (a deterministic synthetic corpus with Zipf-distributed vocabulary
matching the reference corpus' line/word counts) through the SPMD device
engine on whatever accelerator is present and prints ONE JSON line:

    {"metric": "europarl_wordcount_wall_s", "value": <seconds>,
     "unit": "s", "vs_baseline": <47.372 / seconds>}

Flags:

* ``--smoke`` — 1/500-scale quick self-check of the bench itself;
* ``--check`` — REGRESSION GATE: after the run, compare against the
  recorded ``BENCH.json`` history (per-metric tolerances, median
  baseline — obs/benchgate.py), exit nonzero on regression, append the
  accepted run to the history;
* ``--check --smoke`` — the tier-1-safe gate self-check: exercises the
  gate against the committed history with SYNTHETIC entries derived
  from the history itself (median must pass, an injected 2x slowdown
  must fail) plus a tiny CPU-sized device run asserted purely from the
  metrics registry — no wall-clock comparisons, cannot flake on load;
* ``--profile DIR`` — capture a profile bundle (Chrome trace +
  /metrics + statusz device section + ``jax.profiler`` trace when the
  backend supports it) of the timed runs into DIR.

Clock semantics match the reference's: its 47.372s times map+reduce with
the Europarl splits ALREADY in cluster storage (taskfn emits file paths;
the corpus was split and loaded before the benchmark,
execute_BIG_server.sh), so this bench times the pipeline — tokenize,
hash, combine, shuffle, reduce, device->host readback, and host
materialisation of every unique word — from a VERIFIED-resident corpus
in HBM (our storage tier for the device plane).  Host->device ingress is
measured separately, behind stage_inputs' checksum residency barrier,
and reported in the JSON (`ingress_s`).  Compilation is likewise
excluded (the reference excludes Lua/mongod startup) and reported as
`compile_s`.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_S = 47.372          # reference README.md:70, 4 workers
N_WORDS = 49_158_635         # reference README.md:43-45
N_LINES = 1_965_734

REPO = os.path.dirname(os.path.abspath(__file__))
#: the enforced perf trajectory (obs/benchgate.py): --check compares
#: against this history and appends accepted runs
HISTORY_PATH = os.path.join(REPO, "BENCH.json")


def gate_specs():
    """Per-metric tolerances for --check, sized to this fixture's
    measured variance: compute_s is stable (±5% across the recorded
    history), the best-of-N wall value swings more (readback rides the
    host link), materialize depends on host load.
    europarl_wordcount_compute_s is the device-plane headline — the
    fused-engine metric the perf PRs move — gated as its own top-level
    key with the wall key's tolerance and REQUIRED so a run that stops
    reporting it fails loudly."""
    from mapreduce_tpu.obs.benchgate import MetricSpec

    return [
        MetricSpec("value", rel_tol=0.50, required=True),
        MetricSpec("europarl_wordcount_compute_s", rel_tol=0.50,
                   required=True),
        # the Pallas hot path (ops/segscan + ops/tokenize, PR 15): the
        # timed run serves the fused kernels (bench_engine_config sets
        # segment_impl/tokenize_impl='pallas', bit-identical to lax —
        # the smoke's pallas gate pins it) and reports its MFU as a
        # gated top-level key.  Higher is better; the tolerance is WIDE
        # (down to 10% of the median) because the history mixes
        # platforms — the seed is a CPU-mesh measurement and a real TPU
        # raises the bar as it appends.  REQUIRED so a run that stops
        # reporting the kernel-served utilisation fails loudly.
        MetricSpec("wordcount_mfu", rel_tol=0.90, direction="higher",
                   required=True),
        MetricSpec("timings.compute_s", rel_tol=0.35),
        MetricSpec("timings.readback_s", rel_tol=1.00),
        MetricSpec("timings.materialize_s", rel_tol=1.50),
        # ROADMAP 2(c): the warm-start trajectory.  cold_compile_s is a
        # fresh process against an EMPTY persistent cache (the ~100s
        # lax.sort comparator); warm_start_s the fresh-process rebuild
        # through the cache the cold probe just filled.  Both measured
        # by subprocess probes (measure_cold_warm), both REQUIRED so a
        # run that stops reporting them fails loudly; the < 0.2 ratio
        # is gated separately in main() because it relates the two
        # keys, which MetricSpec medians cannot.
        MetricSpec("cold_compile_s", rel_tol=0.75, required=True),
        MetricSpec("warm_start_s", rel_tol=1.50, required=True),
        # the tiered-serving key (engine/tiering): a COLD fresh process
        # submits through sort_impl='tiered' and the clock stops at the
        # first wave-program dispatch — tier-0's fast compile plus the
        # first wave upload, i.e. cold time-to-serving.  REQUIRED, and
        # the >= 2x relation against cold_compile_s is gated separately
        # in main() (a within-run ratio MetricSpec medians cannot
        # express, like the warm-start ratio above it).
        MetricSpec("cold_first_dispatch_s", rel_tol=0.75, required=True),
        # comms observability (obs/comms): recv-side exchange imbalance
        # (max-row/mean-row of the device traffic matrix; 1.0 on the
        # single-chip fixture, and a skew regression on a real mesh
        # must not merge silently) and the feeder-effectiveness
        # fraction (staged runs upload nothing mid-run, so ~1.0; a
        # feeder regression shows as the fraction collapsing).  Both
        # REQUIRED: a run that stops reporting them fails loudly.
        MetricSpec("exchange_imbalance", rel_tol=0.50, required=True),
        MetricSpec("upload_overlap_frac", rel_tol=0.90,
                   direction="higher", required=True),
        # the always-on service plane (sched/ + engine/session):
        # records/s a resident EngineSession sustains while tenants are
        # submitted/cancelled on a live scheduler mid-stream
        # (measure_sustained).  Higher is better, REQUIRED, and the
        # tolerance is WIDE (allow down to 10% of the median) because
        # the history mixes platforms — the first seeded entry is a CPU
        # measurement and a real TPU raises the bar as it appends.
        MetricSpec("sustained_records_per_s", rel_tol=0.90,
                   direction="higher", required=True),
        # the serving-SLO plane (obs/slo): submit -> first-snapshot and
        # snapshot-staleness p99 under the same sustained-churn
        # harness, estimated from the per-tenant SLO histogram bucket
        # counts (obs/metrics.estimate_percentile) — the latency half
        # of the serving gate next to the throughput key above.  Both
        # REQUIRED (a run that stops reporting them fails loudly);
        # tolerances are VERY wide (one order of magnitude) because the
        # history mixes platforms AND scales and the bucket ladder
        # quantizes log-spaced (~2.5x per rung): the gate exists to
        # catch a serving path that got qualitatively slower, not to
        # police a rung.
        MetricSpec("submit_first_snapshot_p99_s", rel_tol=9.0,
                   required=True),
        MetricSpec("snapshot_staleness_p99_s", rel_tol=9.0,
                   required=True),
        # the durability plane (coord/ha + engine/spill): kill-the-
        # board failover time (primary dead-to-clients -> first
        # successful mutation against the promoted standby; dominated
        # by the HA lease period, so the measurement records the lease
        # it ran with) and session evict -> lazy-restore serving
        # latency.  Both REQUIRED; tolerances WIDE because both are
        # host-load-sensitive sub-second-to-seconds quantities on a
        # shared box and the gate exists to catch a path that got
        # qualitatively slower (a lost warm path, an accidental full
        # re-replay), not scheduler jitter.
        MetricSpec("board_failover_s", rel_tol=3.0, required=True),
        MetricSpec("session_restore_s", rel_tol=3.0, required=True),
        # the engine-host fleet plane (coord/fleet + engine/migrate):
        # live-migration serving latency — migrate() evict on the
        # source host to the first consistent snapshot on the
        # DESTINATION through the shared checkpoint plane (spill +
        # guarded route flip + lazy restore + readback), bit-identity
        # asserted inside the measure — and the aggregate records/s
        # TWO registered hosts sustain concurrently.  Both REQUIRED;
        # the migration tolerance is WIDE like session_restore_s above
        # (host-load-sensitive sub-second quantity, the gate catches a
        # path that got qualitatively slower); the fleet rate is
        # higher-is-better with the same wide platform-mixing
        # tolerance as sustained_records_per_s, and its must-exceed-
        # the-RECORDED-one-host-rate relation is gated separately in
        # main() (a cross-key relation MetricSpec medians cannot
        # express).
        MetricSpec("session_migration_s", rel_tol=3.0, required=True),
        MetricSpec("fleet_sustained_records_per_s", rel_tol=0.90,
                   direction="higher", required=True),
        # the control plane (engine/autotune + obs/control): wall-clock
        # overhead of serving an adversarially skewed stream vs a
        # uniform one through the SAME program, with the skew
        # controller rebalancing the partition map mid-stream
        # (measure_skew_rebalance).  REQUIRED; lower is better; the
        # acceptance ceiling (<= SKEWED_WALL_MAX_RATIO) is gated
        # separately in main() as an absolute within-run bound the
        # history median cannot express.
        MetricSpec("skewed_wall_ratio", rel_tol=0.50, required=True),
    ]


#: the acceptance ceiling for the skew-control bench: a rebalanced
#: skewed-corpus run must finish within this factor of the uniform run
SKEWED_WALL_MAX_RATIO = 1.3
VOCAB = 80_000
N_PUNCT_VOCAB = 10_000       # vocab entries that are word+punctuation
N_LONG = 5                   # distinct >128-byte tokens (tail words)
LONG_REPEATS = 8             # occurrences of each tail word


def make_corpus(n_words: int = N_WORDS, n_lines: int = N_LINES,
                vocab_size: int = VOCAB, seed: int = 0) -> bytes:
    """Europarl-shaped text at Europarl scale, built with vectorised numpy
    (no Python loop over 49M tokens): variable Zipf-ranked token lengths
    (natural ~5-char mean instead of fixed-width cells), ~12% of the
    vocabulary carrying attached punctuation ("word," and "word" co-occur
    as distinct whitespace tokens, as in the real corpus), and a tail of
    >128-byte tokens so the materialise window-overflow fallback
    (engine/wordcount.py) runs at full scale."""
    rng = np.random.default_rng(seed)
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", dtype=np.uint8)
    MAXW = 16

    # vocabulary: variable lengths ~Binomial(12,.35)+1 (mean ~5.2 chars)
    n_base = vocab_size - N_PUNCT_VOCAB
    lengths = (1 + rng.binomial(12, 0.35, size=vocab_size)).astype(np.int32)
    np.minimum(lengths, MAXW - 1, out=lengths)
    vocab = np.zeros((vocab_size, MAXW), dtype=np.uint8)
    mask = np.arange(MAXW)[None, :] < lengths[:, None]
    vocab[mask] = letters[rng.integers(0, 26, size=int(mask.sum()))]
    # punctuation-attached variants: copies of base words + one of .,;:!?
    punct = np.frombuffer(b".,;:!?", dtype=np.uint8)
    base_of = rng.integers(0, n_base, size=N_PUNCT_VOCAB)
    vocab[n_base:] = vocab[base_of]
    lengths[n_base:] = lengths[base_of]
    vocab[np.arange(n_base, vocab_size),
          lengths[n_base:]] = punct[rng.integers(0, 6, N_PUNCT_VOCAB)]
    lengths[n_base:] += 1

    # Zipf-ranked draw (punct variants ride their base word's rank zone)
    p = 1.0 / (np.arange(vocab_size) + 10.0)
    p /= p.sum()
    n_tail = N_LONG * LONG_REPEATS if n_words > 2 * N_LONG * LONG_REPEATS \
        else 0
    ids = rng.choice(vocab_size, size=n_words - n_tail, p=p)

    # variable-width assembly: scatter word bytes at cumsum offsets,
    # chunked so the [C, W] index temporaries stay ~100MB
    widths = (lengths[ids] + 1).astype(np.int64)  # +1 separator byte
    offsets = np.concatenate([np.zeros(1, np.int64), np.cumsum(widths)])
    out = np.empty(int(offsets[-1]), dtype=np.uint8)
    CH = 1 << 22
    for lo in range(0, ids.size, CH):
        idc = ids[lo:lo + CH]
        L = lengths[idc]
        W = int(L.max())
        span = np.arange(W)
        m = span[None, :] < L[:, None]
        flat = (offsets[lo:lo + idc.size, None] + span[None, :])[m]
        out[flat] = vocab[idc][:, :W][m]
    sep_pos = offsets[1:] - 1
    out[sep_pos] = ord(" ")
    # newline terminators at the line cadence of the reference corpus
    line_every = max(n_words // n_lines, 1)
    out[sep_pos[line_every - 1::line_every]] = ord("\n")

    if not n_tail:
        return out.tobytes()
    # >128-byte tail words (window is 128; these must take the fallback)
    tail_words = []
    for i in range(N_LONG):
        ln = int(rng.integers(140, 200))
        tail_words.append(bytes(letters[rng.integers(0, 26, ln)]))
    tail = bytearray()
    for r in range(LONG_REPEATS):
        for w in tail_words:
            tail += w + (b"\n" if r % 3 == 2 else b" ")
    return out.tobytes() + bytes(tail)


#: ratio the acceptance gate enforces between the two compile keys: a
#: warm start that costs more than this fraction of the cold compile
#: means the persistent cache is not actually serving the programs
WARM_START_MAX_FRACTION = 0.2

#: ratio the acceptance gate enforces between tiered cold serving and
#: the variadic cold compile: a cold tiered submit must reach its first
#: wave dispatch in under half the variadic cold-compile seconds (the
#: "2x faster" floor; the measured v5e argsort compile advantage is
#: ~3x), or tier-0 is not actually decoupling serving from the big
#: comparator compile.  NOTE this relation is comparator-bound and
#: holds on backends whose wave-program compile the lax.sort comparator
#: dominates (TPU; the CPU backend's compile is tokenizer/fusion-bound
#: and nearly tier-independent — measured on the 8-dev CPU container:
#: ~9.2s vs ~9.3s — so like the warm-start ratio above, this gate is
#: meaningful on the bench fixture, not on a CPU dev box).
TIERED_FIRST_DISPATCH_MAX_FRACTION = 0.5


def _probe_wordcount(smoke: bool, sort_impl: str = None):
    """The engine the compile probes build: the flagship bench config,
    or a CPU-seconds-sized one for --smoke (same code path, same cache
    machinery, just a small sort)."""
    from dataclasses import replace

    from mapreduce_tpu.engine import DeviceWordCount
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.engine.wordcount import bench_engine_config
    from mapreduce_tpu.parallel import make_mesh

    if smoke:
        cfg = EngineConfig(local_capacity=4096, exchange_capacity=2048,
                           out_capacity=4096, tile=512, tile_records=104,
                           combine_in_scan=True, combine_capacity=1024)
        chunk_len = 4096
    else:
        cfg = bench_engine_config()
        chunk_len = 1 << 22
    if sort_impl:
        cfg = replace(cfg, sort_impl=sort_impl)
    return DeviceWordCount(make_mesh(), chunk_len=chunk_len, config=cfg)


def compile_probe(cache_dir: str, smoke: bool,
                  sort_impl: str = None) -> int:
    """Subprocess body for the cold/warm measurement: point the
    persistent cache at *cache_dir* BEFORE any compile (a fresh process
    is the only place that guarantee holds — XLA latches the cache at
    its first compile), AOT-build the bench engine program, and print
    the compile ledger's account as one JSON line."""
    from mapreduce_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache(cache_dir)
    import jax

    # the probes persist EVERYTHING they compile: the smoke program
    # compiles in under the default 1s persistence floor, and a warm
    # probe that finds nothing persisted would measure a second cold
    # compile and call the cache broken
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    wc = _probe_wordcount(smoke, sort_impl=sort_impl)
    secs = wc.warm()
    from mapreduce_tpu.obs.compile import LEDGER

    snap = LEDGER.snapshot()
    wave = (snap.get("programs") or {}).get("wave") or {}
    print(json.dumps({
        "probe_wall_s": round(secs, 3),
        "compile_s": snap.get("total_compile_s", 0.0),
        "wave_outcome": ("persistent_hit" if wave.get("persistent_hit")
                         else "compiled" if wave.get("compiled")
                         else "cached"),
        "disk_buckets": snap.get("disk_buckets", 0),
    }, default=float))
    return 0


def tiered_probe(cache_dir: str, smoke: bool,
                 sort_impl: str = "tiered") -> int:
    """Subprocess body for the cold-serving measurement: a genuinely
    COLD process (fresh empty *cache_dir*, nothing in the in-process
    ledger) submits a one-wave corpus through ``sort_impl='tiered'``
    and reports ``first_dispatch_s`` — run-entry to the first wave
    program dispatched, i.e. tier-0's compile plus the first wave
    upload.  The probe also witnesses the tier mechanics: the run must
    have served cold on tier-0 (a fresh dir that reads warm would mean
    the warmness probe is broken and the number a lie)."""
    from mapreduce_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache(cache_dir)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    # the probe's tier witnesses (cold start, serving tier) only exist
    # under a tiered policy — a concrete impl here would measure the
    # wrong path and report vacuous tier fields
    assert sort_impl in ("tiered", "tiered-radix"), sort_impl
    wc = _probe_wordcount(smoke, sort_impl=sort_impl)
    eng = wc.engine
    # exactly ONE full wave: first_dispatch_s covers wave 0 only, and a
    # one-wave corpus keeps the probe's tail (the remaining waves the
    # metric ignores) off the bench's clock
    phrase = b"tier zero serves while tier one specializes "
    need = eng._rows_per_wave(wc._row_len()) * eng.n_dev * wc.chunk_len
    corpus = phrase * (need // len(phrase))
    tm: dict = {}
    counts = wc.count_bytes(corpus, timings=tm)
    total = sum(counts.values())
    assert total == len(corpus) // len(phrase) * 7, total  # 7-word phrase
    print(json.dumps({
        # submit -> first wave dispatched: the host-side split plus the
        # engine's run-entry-to-dispatch stamp (tier-0 compile + wave-0
        # upload)
        "first_dispatch_s": round(tm.get("split_s", 0.0)
                                  + tm["first_dispatch_s"], 3),
        "tier_cold_start": bool(tm.get("tier_cold_start")),
        "tier_swaps": int(tm.get("tier_swaps", 0)),
        "serving_tier": tm.get("serving_tier"),
        "waves": tm.get("waves"),
    }, default=float))
    return 0


def _run_probe(cache_dir: str, smoke: bool, tiered: bool = False,
               sort_impl: str = None) -> dict:
    import subprocess

    cmd = [sys.executable, os.path.abspath(__file__),
           "--tiered-probe" if tiered else "--compile-probe", cache_dir]
    if smoke:
        cmd.append("--smoke")
    if sort_impl:
        cmd += ["--sort-impl", sort_impl]
    # the throwaway dir reaches the child the way any launcher places
    # the cache: in $JAX_COMPILATION_CACHE_DIR (utils/compile_cache)
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=1800,
                          env={**os.environ,
                               "JAX_COMPILATION_CACHE_DIR": cache_dir})
    if proc.returncode != 0:
        raise RuntimeError(
            f"compile probe failed (rc {proc.returncode}): "
            f"{proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    raise RuntimeError(f"compile probe printed no JSON: "
                       f"{proc.stdout[-2000:]}")


def measure_cold_warm(smoke: bool, sort_impl: str = None) -> dict:
    """ROADMAP 2(c)'s two gated numbers, measured honestly: a FRESH
    temp cache dir makes the first fresh-process probe genuinely cold
    even on a machine whose real cache is warm, and the second probe —
    a fresh process against the cache the first one filled — is
    exactly the "warmup → restart" production path warm_start_s claims
    to measure.  The parent process's own cache config is untouched."""
    import tempfile

    # sort_impl (opt-in; None keeps the gated flagship config) points
    # BOTH probes at a non-default concrete sort — e.g. 'radix' measures
    # the no-comparator program's cold compile and its warm restart
    with tempfile.TemporaryDirectory(prefix="mrtpu_coldwarm_") as td:
        cold = _run_probe(td, smoke, sort_impl=sort_impl)
        warm = _run_probe(td, smoke, sort_impl=sort_impl)
    # the tiered cold-serving probe needs its OWN fresh cache dir: the
    # cold probe above just filled td with the variadic program, and a
    # tiered probe that found it would (correctly) skip tier-0 and
    # measure the warm path instead of cold serving
    with tempfile.TemporaryDirectory(prefix="mrtpu_tiered_") as td2:
        tiered = _run_probe(td2, smoke, tiered=True)
    assert tiered.get("tier_cold_start"), (
        "tiered probe against a fresh cache dir did not serve tier-0 — "
        "the warmness probe is broken and cold_first_dispatch_s would "
        f"be measuring the wrong path: {tiered}")
    return {
        "cold_compile_s": round(float(cold["compile_s"]), 2),
        "warm_start_s": round(float(warm["compile_s"]), 2),
        "cold_outcome": cold.get("wave_outcome"),
        "warm_outcome": warm.get("wave_outcome"),
        # ROADMAP 4(a) / the tiered engine: cold submit -> first wave
        # dispatched through sort_impl='tiered' (tier-0 compile + first
        # upload), plus the probe's tier witnesses for the record
        "cold_first_dispatch_s": round(float(tiered["first_dispatch_s"]),
                                       2),
        "tiered_cold_start": bool(tiered.get("tier_cold_start")),
        "tiered_swaps": int(tiered.get("tier_swaps", 0)),
    }


def measure_failover(smoke: bool) -> dict:
    """Board-HA kill-the-board recovery (coord/ha.py): two in-process
    docserver replicas over one shared HA dir; the primary is made
    dead-to-clients (its HA loop stopped with the lease UNRELEASED —
    the silent-death path, so the standby must wait out the full lease
    expiry — its validity horizon zeroed, and its listener closed) and
    the clock runs from the kill to the first successful MUTATION
    acknowledged by the promoted standby, through one multi-endpoint
    client carrying one rid across the rotation.  Upper-bounded by
    lease + probe rotation; the chaos suite separately proves the
    exactly-once witness across the same kill."""
    import tempfile

    from mapreduce_tpu.coord.docserver import DocServer, HttpDocStore

    lease = 0.5 if smoke else 1.0
    with tempfile.TemporaryDirectory(prefix="mrtpu_ha_bench_") as td:
        a = DocServer(ha_dir=td, ha_lease=lease).start_background()
        b = DocServer(ha_dir=td, ha_lease=lease).start_background()
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not (
                    a.ha.is_primary() or b.ha.is_primary()):
                time.sleep(0.01)
            prim, stby = (a, b) if a.ha.is_primary() else (b, a)
            cli = HttpDocStore(f"{a.host}:{a.port},{b.host}:{b.port}")
            try:
                cli.insert("bench.docs", {"_id": "x", "v": 0})
                cli.update("bench.docs", {"_id": "x"},
                           {"$inc": {"v": 1}})
                t0 = time.monotonic()
                prim.ha._stop.set()
                prim.ha._thread.join(timeout=10)
                prim.ha._valid_until = 0.0
                prim.httpd.shutdown()
                prim.httpd.server_close()
                n = cli.update("bench.docs", {"_id": "x"},
                               {"$inc": {"v": 1}})
                failover_s = time.monotonic() - t0
                assert n == 1 and stby.ha.is_primary(), (n, stby.ha.role)
                doc = cli.find_one("bench.docs", {"_id": "x"})
                assert doc and doc["v"] == 2, doc
            finally:
                cli.close()
        finally:
            for srv in (a, b):
                try:
                    srv.shutdown()
                except Exception:
                    pass
    return {"board_failover_s": round(failover_s, 3),
            "board_failover_lease_s": lease}


def measure_session_restore(mesh, smoke: bool) -> dict:
    """Session evict -> restore serving latency (engine/spill.py): a
    resident wordcount stream is spilled + dropped from HBM, and the
    clock runs over the next snapshot — the lazy restore path a
    reawakened idle tenant pays (manifest read, digest-verified shard
    fetch, device placement).  The restored snapshot is asserted
    bit-identical to the pre-evict one, so the number can never go
    fast by going wrong."""
    import numpy as np

    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.engine.session import EngineSession
    from mapreduce_tpu.engine.spill import SessionSpillStore
    from mapreduce_tpu.engine.wordcount import wordcount_map_fn
    from mapreduce_tpu.ops.tokenize import shard_text
    from mapreduce_tpu.storage.memory import MemoryStorage

    cfg = EngineConfig(local_capacity=4096, exchange_capacity=2048,
                       out_capacity=4096, tile=512, tile_records=128,
                       combine_in_scan=True, unit_values=True,
                       reduce_op="sum")
    corpus = b"restore gate alpha beta gamma delta " * (
        1000 if smoke else 8000)
    chunks, _ = shard_text(corpus, max(1, len(corpus) // 4096),
                           pad_multiple=512, pad_to=4096 + 512)
    sess = EngineSession(mesh, wordcount_map_fn, cfg,
                         task="restore-bench",
                         spill=SessionSpillStore(MemoryStorage()))
    sess.feed(chunks)
    before = sess.snapshot()
    t0 = time.monotonic()
    sess.evict()
    spill_s = time.monotonic() - t0
    t1 = time.monotonic()
    after = sess.snapshot()  # lazy restore + readback
    restore_s = time.monotonic() - t1
    for field in ("keys", "values", "payload", "valid"):
        assert np.array_equal(np.asarray(getattr(after, field)),
                              np.asarray(getattr(before, field))), (
            f"restored snapshot diverged on {field}")
    sess.close()
    return {"session_restore_s": round(restore_s, 4),
            "session_spill_s": round(spill_s, 4)}


def measure_session_migration(mesh, smoke: bool) -> dict:
    """Live-migration serving latency (coord/fleet + engine/migrate):
    a 2-host in-process fleet fixture — two generation-fenced host
    leases registered on one board, two :class:`EngineSession`\\ s
    sharing one checkpoint plane — and the clock runs from the
    ``migrate()`` evict on the source to the first consistent snapshot
    on the DESTINATION (spill + guarded route flip + lazy restore +
    readback): the end-to-end wall a tenant pays for one rebalance /
    drain / recovery move.  The destination snapshot is asserted
    bit-identical to the pre-migration source snapshot and the route
    flip is asserted in the fleet registry, so the number can never go
    fast by going wrong."""
    import numpy as np

    from mapreduce_tpu.coord.docstore import MemoryDocStore
    from mapreduce_tpu.coord.fleet import FleetMember, FleetRegistry
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.engine.migrate import migrate
    from mapreduce_tpu.engine.session import EngineSession
    from mapreduce_tpu.engine.spill import SessionSpillStore
    from mapreduce_tpu.engine.wordcount import wordcount_map_fn
    from mapreduce_tpu.ops.tokenize import shard_text
    from mapreduce_tpu.storage.memory import MemoryStorage

    cfg = EngineConfig(local_capacity=4096, exchange_capacity=2048,
                       out_capacity=4096, tile=512, tile_records=128,
                       combine_in_scan=True, unit_values=True,
                       reduce_op="sum")
    corpus = b"migrate gate alpha beta gamma delta " * (
        1000 if smoke else 8000)
    chunks, _ = shard_text(corpus, max(1, len(corpus) // 4096),
                           pad_multiple=512, pad_to=4096 + 512)
    board = MemoryDocStore()
    reg = FleetRegistry(board)
    hosts = [FleetMember(board, host_id=h)
             for h in ("bench-a", "bench-b")]
    for m in hosts:
        m.join(timeout=5.0, warm_programs=["wordcount"], hbm_frac=0.2)
    spill = SessionSpillStore(MemoryStorage())  # the shared plane
    task = "migration-bench"
    src = EngineSession(mesh, wordcount_map_fn, cfg, task=task,
                        spill=spill)
    dst = EngineSession(mesh, wordcount_map_fn, cfg, task=task,
                        spill=spill)
    reg.assign(task, "bench-a", program="wordcount")
    src.feed(chunks)
    before = src.snapshot()
    t0 = time.monotonic()
    moved = migrate(task, src, dst, registry=reg,
                    src_host="bench-a", dst_host="bench-b",
                    reason="explicit")
    after = dst.snapshot()  # lazy restore + readback on the new host
    migration_s = time.monotonic() - t0
    route = reg.route(task)
    assert route and route["host"] == "bench-b", route
    for field in ("keys", "values", "payload", "valid"):
        assert np.array_equal(np.asarray(getattr(after, field)),
                              np.asarray(getattr(before, field))), (
            f"migrated snapshot diverged on {field}")
    src.close(drop_spill=False)
    dst.close()
    for m in hosts:
        m.leave()
    return {"session_migration_s": round(migration_s, 4),
            "session_migration_spill_s": round(moved["spill_s"], 4)}


def measure_fleet_sustained(mesh, smoke: bool) -> dict:
    """Aggregate serving rate of a 2-host fleet (coord/fleet): two
    registered engine hosts — two resident :class:`EngineSession`\\ s,
    each holding a live host lease with heartbeat facts on the shared
    board — each serve their own tenant stream from their own feeder
    thread, and the reported number is total records/s folded across
    BOTH hosts over the concurrent window's wall time.  Same clock
    semantics as measure_sustained (pre-chunked corpus, pre-warmed
    program, records = word occurrences exact from the unit-count
    snapshots); the ``--check`` relation in main() asserts this
    aggregate exceeds the RECORDED one-host rate (the BENCH.json
    history median of ``sustained_records_per_s``) — a fleet entry
    must beat the one-host record, not just add a registry row.  (The
    same-run one-host rate is NOT the bar on purpose: on a fixture
    where both in-process hosts share one physical device pool — this
    CPU container — concurrent hosts add no device capacity, while on
    a real multi-host mesh each host brings its own chips.)"""
    import threading

    from mapreduce_tpu.coord.docstore import MemoryDocStore
    from mapreduce_tpu.coord.fleet import (
        FleetMember, FleetRegistry, fleet_snapshot)
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.engine.session import EngineSession
    from mapreduce_tpu.engine.wordcount import wordcount_map_fn
    from mapreduce_tpu.ops.tokenize import shard_text

    if smoke:
        chunk_len, rounds, slice_words = 4096, 2, 4_000
        cfg = EngineConfig(local_capacity=8192, exchange_capacity=4096,
                           out_capacity=16384, tile=512,
                           tile_records=128, combine_in_scan=True,
                           combine_capacity=2048,
                           unit_values=True, reduce_op="sum")
    else:
        chunk_len, rounds, slice_words = 1 << 20, 3, 1_500_000
        cfg = EngineConfig(local_capacity=1 << 17,
                           exchange_capacity=1 << 15,
                           out_capacity=1 << 17, tile=512,
                           tile_records=104, combine_in_scan=True,
                           combine_capacity=1 << 17,
                           unit_values=True, reduce_op="sum")
    board = MemoryDocStore()
    reg = FleetRegistry(board)
    host_ids = ["bench-h0", "bench-h1"]
    members = [FleetMember(board, host_id=h) for h in host_ids]
    for m in members:
        m.join(timeout=5.0, warm_programs=["wordcount"], hbm_frac=0.3)

    corpus = make_corpus(slice_words, max(slice_words // 25, 1))
    n_chunks = max(1, -(-len(corpus) // chunk_len))
    chunks, _L = shard_text(corpus, n_chunks, pad_multiple=cfg.tile,
                            pad_to=chunk_len + cfg.tile)
    sessions = []
    for h in host_ids:
        sess = EngineSession(mesh, wordcount_map_fn, cfg,
                             task=f"fleet-{h}")
        eng = sess.engine
        row_bytes = max(1, chunks.nbytes // len(chunks))
        sess.k = max(1, min(eng._rows_per_wave(row_bytes),
                            -(-len(chunks) // eng.n_dev)))
        # warm the program AND the snapshot/readback path per host so
        # the window times serving, not a compile or a ledger hit
        sess.feed(chunks[: min(len(chunks), eng.n_dev)], task="warm")
        sess.snapshot("warm")
        sess.close("warm")
        reg.assign(f"tenant-{h}", h, program="wordcount")
        sessions.append(sess)
    snap = fleet_snapshot(board)
    assert len(snap.get("hosts", {})) == len(host_ids), snap
    assert all(h["state"] == "live"
               for h in snap["hosts"].values()), snap

    def _total(sess, t) -> int:
        s = sess.snapshot(t)
        assert s.overflow == 0, (
            f"fleet stream {t} overflowed {s.overflow} rows — size "
            "the config up, the number would be a lie")
        vals = np.asarray(s.values).reshape(-1)
        valid = np.asarray(s.valid).reshape(-1)
        return int(vals[valid.nonzero()[0]].sum())

    def _serve(sess, t, n):
        for _r in range(n):
            sess.feed(chunks, task=t)

    def _concurrent(n) -> float:
        threads = [threading.Thread(target=_serve,
                                    args=(sess, f"tenant-{h}", n))
                   for h, sess in zip(host_ids, sessions)]
        t0 = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return time.monotonic() - t0

    # first feed per tenant: the resident aggregate exists before the
    # window, so every timed feed is the steady-state fold
    for h, sess in zip(host_ids, sessions):
        sess.feed(chunks, task=f"tenant-{h}")
    # one UNTIMED concurrent round: the first time both hosts dispatch
    # at once, jax re-lowers the wave program without input donation
    # (the other host's in-flight execution holds the would-be-donated
    # buffer) — a one-time per-process build that must not bill the
    # steady-state window
    _concurrent(1)
    before = [_total(sess, f"tenant-{h}")
              for h, sess in zip(host_ids, sessions)]
    wall = _concurrent(rounds)
    records = 0
    for h, sess, b in zip(host_ids, sessions, before):
        records += _total(sess, f"tenant-{h}") - b
        sess.close()
    for m in members:
        m.leave()
    return {
        "fleet_sustained_records_per_s": round(
            records / max(wall, 1e-9), 1),
        "fleet_sustained_hosts": len(host_ids),
        "fleet_sustained_records": records,
        "fleet_sustained_wall_s": round(wall, 4),
        "fleet_sustained_rounds": rounds,
    }


def _control_map_fn(chunk, chunk_index, cfg):
    """Synthetic record stream for the skew-control bench: the chunk
    VALUES are the key_hi hashes verbatim, so the corpus construction
    chooses exactly which partition/bucket every record lands on —
    a skewed corpus and a uniform one run the IDENTICAL compiled
    program and differ only in routing."""
    import jax.numpy as jnp

    k1 = chunk.astype(jnp.uint32)
    k2 = (chunk % 17).astype(jnp.uint32)
    keys = jnp.stack([k1, k2], axis=-1)
    vals = jnp.ones_like(k1, dtype=jnp.int32)
    pay = (chunk % 97).astype(jnp.int32)[:, None]
    valid = jnp.ones(k1.shape, dtype=bool)
    return keys, vals, pay, valid, jnp.int32(0)


def measure_skew_rebalance(mesh, smoke: bool) -> dict:
    """The observe->act gate (engine/autotune + obs/control): an
    adversarially skewed stream — every key congruent to ONE partition
    under the identity map, spread across hash buckets — served by a
    resident session with the skew controller attached, timed against
    a uniform stream of the same size through the same program.

    The capacity story makes the ratio meaningful: ``out_capacity`` is
    sized so the BALANCED key population fits comfortably per
    partition while the skewed population can NOT fit one partition —
    an un-rebalanced skewed run overflows loudly by round 2.  The
    controller's between-feed rebalance (evidence: the PR-9 exchange
    matrix's recv totals; action: greedy re-bin of the resident
    buckets; both in the control ledger) is what lets the skewed run
    finish at all — an un-rebalanced run overflows before the final
    round completes — and ``skewed_wall_ratio`` is its total overhead.

    Returns the gated ``skewed_wall_ratio`` plus the per-window
    imbalance trajectory (first vs last window of the SAME run — the
    acceptance criterion's measurably-reduced witness)."""
    from mapreduce_tpu.engine.autotune import AutoTuner
    from mapreduce_tpu.engine.device_engine import (
        EngineConfig, partition_buckets_for)
    from mapreduce_tpu.engine.session import EngineSession
    from mapreduce_tpu.obs.comms import matrix_stats
    from mapreduce_tpu.obs.metrics import REGISTRY

    n_dev = mesh.shape["data"]
    # smoke right-sizing (the suite-budget pattern: check_smoke runs
    # in-process on every tier-1): half-size capacities and the
    # minimum window count that still witnesses the loop — window 1
    # (pre-rebalance, full imbalance) -> rebalance -> window 2 (the
    # measured drop)
    C = 512 if smoke else 4096
    rounds = 2 if smoke else 4
    keys_per_round = max(64, int(C * 0.4))
    rows = 32
    # exchange_capacity right-sized to what actually routes: each
    # device's per-wave uniques are <= k*rows = 64, so 256 per
    # (src,dst) pair is 4x headroom — a 2*C capacity would only fatten
    # the fin-sort (P*ex + C rows) the fixture compiles and runs
    # a 1-device mesh has ONE partition holding EVERY key: the
    # multi-device sizing (balanced population fits per partition, the
    # skewed one cannot fit one) would overflow by construction, so
    # fit the whole population — the run still times, the rebalance
    # asserts below are already n_dev-guarded
    out_cap = C if n_dev > 1 else max(C, 2 * keys_per_round * rounds)
    cfg = EngineConfig(
        local_capacity=4 * C, exchange_capacity=256,
        out_capacity=out_cap,
        tile=64, tile_records=rows, partition_map=True)
    B = partition_buckets_for(cfg, n_dev)
    hot = 5 % n_dev
    rng = np.random.default_rng(7)

    def corpus_round(r: int, skewed: bool) -> np.ndarray:
        """One round's chunks: keys_per_round NEW distinct keys (round
        r's id range), repeated to fill the round's record volume."""
        ids = np.arange(r * keys_per_round, (r + 1) * keys_per_round,
                        dtype=np.int64)
        if skewed:
            # key = bucket_group*B + (group picks the bucket, value
            # stays ≡ hot mod P): every key routes to partition `hot`
            # under the identity map, yet occupies many distinct
            # buckets the controller can spread
            group = ids % (B // n_dev)
            k = ids * np.int64(B) + group * np.int64(n_dev) + hot
        else:
            k = ids * np.int64(B) + (ids % np.int64(B))
        draw = rng.choice(k, size=keys_per_round * 4)
        pad = (-draw.size) % rows
        draw = np.concatenate([draw, draw[:pad]])
        return draw.reshape(-1, rows).astype(np.int32)

    def run(skewed: bool):
        tuner = AutoTuner(min_records=keys_per_round // 2)
        sess = EngineSession(mesh, _control_map_fn, cfg, k=2,
                             autotune=tuner, task="skew-bench")
        task = "skewed" if skewed else "uniform"
        # warm feed (compile + program warm) OUTSIDE the timed window,
        # on round 0's keys so the timed rounds still grow the key set
        sess.feed(corpus_round(0, skewed)[:2], task=task)
        imb = []
        last = sess.traffic_matrix(task).astype(np.int64)
        t0 = time.monotonic()
        for r in range(rounds):
            sess.feed(corpus_round(r, skewed), task=task)
            cur = sess.traffic_matrix(task).astype(np.int64)
            imb.append(matrix_stats(
                (cur - last).tolist())["imbalance_recv"])
            last = cur
        wall = time.monotonic() - t0
        stats = sess.stats(task)
        sess.close()
        return wall, imb, stats

    def _recorded():
        # record-time outcomes only: the counter also ticks at
        # RESOLUTION (improved/neutral/regressed), which would double-
        # count every measured decision
        return sum(REGISTRY.sum("mrtpu_control_decisions_total",
                                controller="repartition", outcome=o)
                   for o in ("pending", "applied", "refused"))

    d0 = _recorded()
    uniform_wall, uniform_imb, _ = run(skewed=False)
    skew_wall, skew_imb, skew_stats = run(skewed=True)
    decisions = _recorded() - d0
    if n_dev > 1:
        assert skew_imb[-1] < skew_imb[0], (
            "exchange imbalance did not decrease across control "
            f"windows: {skew_imb}")
        assert skew_stats.get("rebalances", 0) >= 1, skew_stats
    return {
        "skewed_wall_ratio": round(skew_wall / max(uniform_wall, 1e-9),
                                   4),
        "skew_uniform_wall_s": round(uniform_wall, 4),
        "skew_skewed_wall_s": round(skew_wall, 4),
        "skew_imbalance_first": round(skew_imb[0], 4),
        "skew_imbalance_last": round(skew_imb[-1], 4),
        "skew_rebalance_decisions": int(decisions),
        "skew_rounds": rounds,
    }


def measure_sustained(mesh, smoke: bool) -> dict:
    """Sustained-throughput under tenant churn (the always-on service
    mode): a resident :class:`EngineSession` serves several tenant
    streams over ONE mesh while a churn thread submits and cancels
    scheduler tasks mid-stream, and the reported number is records/s
    folded into the resident aggregates over the feed loop's wall time
    (records = word occurrences, exact from the unit-count snapshots).

    Pre-chunked inputs and a pre-warmed program keep the number the
    SERVING rate (upload + fused dispatch + overflow readback), not a
    text-splitting or compile benchmark — matching the main bench's
    clock semantics (corpus staged, compile excluded).

    The serving-SLO keys ride the same harness: each tenant's
    submit→first-snapshot is measured from its scheduler submit stamp
    to its first consistent snapshot, and snapshot staleness is
    sampled at every snapshot the harness takes; the gated p99s are
    estimated from the per-tenant SLO histogram bucket counts
    (obs/metrics.estimate_percentile — the same estimator the /statusz
    SLO section uses), over exactly this run's observations (bucket
    deltas against a baseline captured before the first submit)."""
    import threading

    import jax  # noqa: F401  (the session dispatches engine programs)

    from mapreduce_tpu.coord.docstore import MemoryDocStore
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.engine.session import EngineSession
    from mapreduce_tpu.engine.topk import TopKWords
    from mapreduce_tpu.engine.wordcount import wordcount_map_fn
    from mapreduce_tpu.obs import slo as slo_mod
    from mapreduce_tpu.obs.metrics import estimate_percentile
    from mapreduce_tpu.ops.tokenize import shard_text
    from mapreduce_tpu.sched.scheduler import (
        Scheduler, SchedulerConfig)

    if smoke:
        chunk_len, rounds, slice_words = 4096, 2, 4_000
        # combine_capacity explicit: a session stream cannot
        # capacity-retry, so the per-chunk combiner slots must cover a
        # dense Zipf chunk up front (T = L/tile*tile_records = 1152)
        cfg = EngineConfig(local_capacity=8192, exchange_capacity=4096,
                           out_capacity=16384, tile=512,
                           tile_records=128, combine_in_scan=True,
                           combine_capacity=2048,
                           unit_values=True, reduce_op="sum")
    else:
        chunk_len, rounds, slice_words = 1 << 20, 3, 1_500_000
        cfg = EngineConfig(local_capacity=1 << 17,
                           exchange_capacity=1 << 15,
                           out_capacity=1 << 17, tile=512,
                           tile_records=104, combine_in_scan=True,
                           combine_capacity=1 << 17,
                           unit_values=True, reduce_op="sum")
    tenants = ["t0", "t1", "t2"]
    scheduler = Scheduler(MemoryDocStore(),
                          config=SchedulerConfig(
                              max_inflight=len(tenants) + 1))

    # one corpus slice, pre-chunked; every (tenant, round) feeds a copy
    # (streams accumulate counts, so re-feeding the same block is a
    # legitimate — and deterministic — sustained load)
    corpus = make_corpus(slice_words, max(slice_words // 25, 1))
    n_chunks = max(1, -(-len(corpus) // chunk_len))
    chunks, _L = shard_text(corpus, n_chunks, pad_multiple=cfg.tile,
                            pad_to=chunk_len + cfg.tile)
    # k passed EXPLICITLY, sized from the FULL per-feed chunk count:
    # letting the small warm feed latch it would pin minimum-size waves
    # (k=1) and depress the gated rate with per-wave dispatch overhead
    session = EngineSession(mesh, wordcount_map_fn, cfg, task="sustained")
    eng = session.engine
    row_bytes = max(1, chunks.nbytes // len(chunks))
    session.k = max(1, min(eng._rows_per_wave(row_bytes),
                           -(-len(chunks) // eng.n_dev)))
    session.feed(chunks[: min(len(chunks),
                              session.engine.n_dev)], task="warm")
    session.snapshot("warm")  # warm the snapshot/readback path too:
    # the first-result phase measures the SERVING path, not a compile
    session.close("warm")  # programs compiled; drop the warm stream

    def _snap_total(t) -> int:
        snap = session.snapshot(t)
        assert snap.overflow == 0, (
            f"sustained stream {t} overflowed {snap.overflow} rows — "
            "size the config up, the number would be a lie")
        vals = np.asarray(snap.values).reshape(-1)
        valid = np.asarray(snap.valid).reshape(-1)
        return int(vals[valid.nonzero()[0]].sum())

    # SLO baseline: bucket counts BEFORE the first submit, so the gated
    # p99s are estimated from exactly this run's observations
    slo_bounds, sub_base = slo_mod.merged_counts(
        slo_mod.FIRST_RESULT_FAMILY, tenants)
    _, stale_base = slo_mod.merged_counts(
        slo_mod.STALENESS_FAMILY, tenants)

    churn_stop = threading.Event()
    churn_counts = {"submitted": 0, "cancelled": 0}

    def _churn():
        i = 0
        while not churn_stop.is_set():
            doc = scheduler.submit("churn", db=f"churn_{i}",
                                   kind="session", est_jobs=1)
            scheduler.tick()
            churn_counts["submitted"] += 1
            if scheduler.cancel(doc["_id"]) is not None:
                churn_counts["cancelled"] += 1
            i += 1
            churn_stop.wait(0.02)

    churn_t = threading.Thread(target=_churn, daemon=True)
    churn_t.start()

    # phase 1 — submit -> first snapshot, per tenant: the scheduler
    # submit stamps the monotonic start (obs/slo), the first consistent
    # snapshot is the first visible result.  The program is pre-warmed,
    # so this measures the SERVING path, not a compile.
    first_result = {}
    before = {}
    for t in tenants:
        doc = scheduler.submit(t, db=f"sess_{t}", kind="session",
                               est_jobs=rounds)
        scheduler.tick()
        session.feed(chunks, task=t)
        before[t] = _snap_total(t)   # staleness sampled here too
        first_result[t] = slo_mod.observe_first_result(doc["_id"], t)

    # phase 2 — the timed sustained window (feeds only, the gated rate)
    t0 = time.monotonic()
    for _r in range(rounds):
        for t in tenants:
            session.feed(chunks, task=t)
    wall = time.monotonic() - t0

    # phase 3 — staleness sampling under multiplexing: snapshot every
    # tenant right after the window (tenant 0 is then the stalest —
    # every later tenant's feed aged its aggregate); with phase 1's
    # post-feed snapshots that is two staleness samples per tenant,
    # spanning the fresh and the multiplexed-aged cases
    after = {t: _snap_total(t) for t in tenants}
    churn_stop.set()
    churn_t.join(timeout=5)

    records = 0
    waves = 0
    for t in tenants:
        records += after[t] - before[t]
        waves += session.stats(t)["waves"]
        scheduler.note_served(t, after[t])

    # the gated SLO keys: p50/p99 estimated from this run's bucket
    # deltas (the same estimator the /statusz SLO section rides)
    _, sub_now = slo_mod.merged_counts(slo_mod.FIRST_RESULT_FAMILY,
                                       tenants)
    _, stale_now = slo_mod.merged_counts(slo_mod.STALENESS_FAMILY,
                                         tenants)
    sub_counts = [b - a for a, b in zip(sub_base, sub_now)]
    stale_counts = [b - a for a, b in zip(stale_base, stale_now)]
    slo_keys = {
        "submit_first_snapshot_p99_s": estimate_percentile(
            slo_bounds, sub_counts, 0.99),
        "submit_first_snapshot_p50_s": estimate_percentile(
            slo_bounds, sub_counts, 0.50),
        "snapshot_staleness_p99_s": estimate_percentile(
            slo_bounds, stale_counts, 0.99),
        "snapshot_staleness_p50_s": estimate_percentile(
            slo_bounds, stale_counts, 0.50),
    }
    slo_keys = {k: (None if v is None else round(v, 4))
                for k, v in slo_keys.items()}

    # the top-K bench entry: a streaming TopKWords over one slice, the
    # mid-stream snapshot+selection timed (the bounded-output read the
    # workload exists for)
    tk = TopKWords(mesh, k=20, chunk_len=chunk_len, config=cfg)
    tk.feed(corpus)
    t1 = time.monotonic()
    top = tk.topk()
    topk_s = time.monotonic() - t1
    session.close()

    return {
        "sustained_records_per_s": round(records / max(wall, 1e-9), 1),
        "sustained_records": records,
        "sustained_wall_s": round(wall, 4),
        "sustained_tenants": len(tenants),
        "sustained_rounds": rounds,
        "sustained_waves": waves,
        "sustained_churn_submitted": churn_counts["submitted"],
        "sustained_churn_cancelled": churn_counts["cancelled"],
        "topk_k": len(top),
        "topk_snapshot_s": round(topk_s, 4),
        # the gated serving-SLO keys (obs/slo) + context: per-tenant
        # measured submit->first-snapshot seconds for the record
        "submit_first_snapshot_s": {
            t: (None if s is None else round(s, 4))
            for t, s in first_result.items()},
        **slo_keys,
    }


def check_smoke() -> int:
    """``--check --smoke``: the tier-1-safe regression-gate self-check.
    No accelerator requirement and ZERO wall-clock comparisons (so it
    cannot flake on a loaded CI host):

    1. gate logic against the COMMITTED history — synthetic entries are
       derived from the history itself (obs/benchgate.synthetic_entry):
       the medians must pass, an injected 2x slowdown must be flagged;
    2. a tiny CPU-sized device-engine wordcount, judged purely from the
       obs registry: waves ran, the FUSED execution model held (exactly
       one program dispatch per wave, zero merge-program dispatches —
       i.e. zero per-wave merge readbacks), the cost model recorded
       FLOPs (analytic fallback included), the MFU gauge landed.
    """
    from mapreduce_tpu.obs import benchgate
    from mapreduce_tpu.obs.metrics import REGISTRY
    from mapreduce_tpu.obs.profile import analytic_costs

    specs = gate_specs()
    _, history = benchgate.load_history(HISTORY_PATH)
    assert history, f"no committed history in {HISTORY_PATH}"
    ok_probs = benchgate.gate(
        benchgate.synthetic_entry(history, specs), history, specs)
    assert not ok_probs, (
        f"gate flagged the history's own medians: {ok_probs}")
    bad_probs = benchgate.gate(
        benchgate.synthetic_entry(history, specs, scale=2.0),
        history, specs)
    assert bad_probs, "gate did not flag a 2x synthetic slowdown"

    # analytic fallback must produce usable numbers on its own (it is
    # the only cost path on backends without cost_analysis)
    est = analytic_costs(1 << 20, 1 << 15, 16)
    assert est["flops"] > 0 and est["bytes"] > 0, est

    from mapreduce_tpu.engine import DeviceWordCount
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.parallel import make_mesh

    # tile_records 128: the smoke corpus is denser than natural text
    # (~90 words per 512-byte tile), and the dispatch-count assertion
    # below needs a retry-free run — a capacity retry re-dispatches
    # every wave and would muddy "exactly one program per wave"
    wc = DeviceWordCount(
        make_mesh(), chunk_len=4096,
        config=EngineConfig(local_capacity=4096, exchange_capacity=2048,
                            out_capacity=4096, tile=512, tile_records=128,
                            combine_in_scan=True))
    # 3000 repeats: enough chunks that the requested 3-way split yields
    # a genuinely multi-wave run (>= 2 waves) on a 1-device bench host
    # AND on the 8-device test mesh, so the fold path actually runs
    corpus = b"gate smoke alpha beta gamma delta " * 3000
    # the engine's counters carry a per-task accounting label, so the
    # smoke reads sum over it (superset label match)
    f0 = REGISTRY.sum("mrtpu_device_flops_total")
    w0 = REGISTRY.sum("mrtpu_device_waves_total")
    d0 = REGISTRY.sum("mrtpu_device_dispatches_total", program="wave")
    er0 = REGISTRY.sum("mrtpu_exchange_records_total")
    tm = {}
    counts = wc.count_bytes(corpus, timings=tm, waves=3)
    assert counts[b"alpha"] == 3000, counts.get(b"alpha")
    waves_ran = REGISTRY.sum("mrtpu_device_waves_total") - w0
    assert waves_ran == tm["waves"] >= 2, (waves_ran, tm)
    # the fused execution model, asserted from the registry: EXACTLY one
    # program dispatch per wave (the fold rides inside it), zero merge
    # dispatches — and hence zero per-wave merge readbacks, since the
    # program that would have produced them no longer exists
    assert tm["retries"] == 0, tm  # retries would recount dispatches
    dispatches = (REGISTRY.sum("mrtpu_device_dispatches_total",
                               program="wave") - d0)
    assert dispatches == waves_ran, (
        f"fused path dispatched {dispatches} programs for "
        f"{waves_ran} waves (expected exactly one per wave)")
    merge_disp = REGISTRY.sum("mrtpu_device_dispatches_total",
                              program="merge")
    assert merge_disp == 0, (
        f"{merge_disp} merge-program dispatches recorded — the "
        "two-dispatch wave fold came back")
    flops = REGISTRY.sum("mrtpu_device_flops_total") - f0
    assert flops > 0, "device run recorded no FLOPs (cost model broken)"

    # comms observability gate (registry-only, zero wall clock): the
    # exchange traffic matrix rode the ONE n_live readback of the run
    # just asserted to dispatch exactly one program per wave — and its
    # row sums equal the records the run actually processed, derived
    # on the host from the same chunk/wave split (engine local reduce =
    # per-device-per-wave unique words, routed by hash).
    host_m = wc.host_exchange_matrix(corpus, waves=3)
    sent = REGISTRY.sum("mrtpu_exchange_records_total") - er0
    assert sent == tm["exchange_records"] == int(host_m.sum()) > 0, (
        f"exchange matrix total {tm.get('exchange_records')} (registry "
        f"delta {sent}) != host-derived records processed "
        f"{int(host_m.sum())}")
    smoke_m = np.asarray(tm["exchange"]["matrix"], dtype=np.int64)
    assert np.array_equal(smoke_m, host_m), (
        "smoke exchange matrix diverged from the host recompute")
    assert 0.0 <= tm["upload_overlap_frac"] <= 1.0, tm
    # the two gated comms keys must have seeded history to baseline on
    for key in ("exchange_imbalance", "upload_overlap_frac"):
        assert any(benchgate.lookup(h, key) is not None
                   for h in history), (
            f"no BENCH.json history entry carries {key!r}")
        assert benchgate.lookup(tm, key) is not None, (
            f"run timings missing gated comms key {key!r}")

    # compile-ledger gate (the warm-start story inside ONE process): a
    # second same-shape engine build must be served by the in-process
    # ledger — outcome=cached with ZERO new compile-seconds, asserted
    # purely from the registry (the compile-seconds histogram gains no
    # observation), never from a wall clock.
    cached0 = REGISTRY.sum("mrtpu_compile_total", outcome="cached")
    # compiled OR persistent_hit: both are real ledgered XLA builds —
    # a developer environment with $JAX_COMPILATION_CACHE_DIR exported
    # classifies a re-run's first build persistent_hit (the smoke
    # bucket is already in the shape registry), which must not read as
    # "the helper is not on the compile path"
    compiled0 = (REGISTRY.sum("mrtpu_compile_total", program="wave",
                              outcome="compiled")
                 + REGISTRY.sum("mrtpu_compile_total", program="wave",
                                outcome="persistent_hit"))
    obs0 = REGISTRY.value("mrtpu_compile_seconds", program="wave",
                          stage="backend_compile")
    assert compiled0 > 0, (
        "first engine build recorded no ledgered wave compile — the "
        "instrumented helper is not on the compile path")
    wc2 = DeviceWordCount(
        make_mesh(), chunk_len=4096,
        config=EngineConfig(local_capacity=4096, exchange_capacity=2048,
                            out_capacity=4096, tile=512, tile_records=128,
                            combine_in_scan=True))
    counts2 = wc2.count_bytes(corpus, waves=3)
    assert counts2 == counts, "ledger-cached engine diverged"
    cached_delta = (REGISTRY.sum("mrtpu_compile_total", outcome="cached")
                    - cached0)
    assert cached_delta >= 1, (
        "second same-shape engine build did not report outcome=cached")
    new_obs = (REGISTRY.value("mrtpu_compile_seconds", program="wave",
                              stage="backend_compile") - obs0)
    assert new_obs == 0, (
        f"second same-shape engine build spent compile-seconds "
        f"({new_obs} new backend_compile observation(s)) — the "
        "executable cache is not serving it")

    # Pallas hot-path gate (ops/segscan + ops/tokenize; registry- and
    # ledger-asserted, zero wall-clock comparisons): a kernel-config
    # smoke run must (1) actually build the two hot-path kernels
    # (trace-time build counter, interpret mode on this CPU tier),
    # (2) keep the fused execution model — still exactly one
    # wave-program dispatch per wave, zero merge dispatches, (3) fold
    # bit-identically to the lax smoke run above (same corpus, same
    # wave split, same capacities), (4) land a wave bucket whose config
    # token names the pallas impls in the compile ledger, and (5) carry
    # the MFU the gated wordcount_mfu key is derived from.
    from mapreduce_tpu.obs.compile import LEDGER
    from mapreduce_tpu.ops import segscan as _segscan
    from mapreduce_tpu.ops import tokenize as _tokenize_mod

    kb_seg0 = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                           kernel="segreduce")
    kb_tok0 = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                           kernel="tokenize")
    pw0 = REGISTRY.sum("mrtpu_device_waves_total")
    pd0 = REGISTRY.sum("mrtpu_device_dispatches_total", program="wave")
    # capacities SMALLER than the lax smoke engine's on purpose: the
    # fold result is capacity-independent below overflow (6 uniques),
    # and the smaller static shapes keep this extra compile cheap on
    # the CPU tier (suite-budget sizing)
    wc_p = DeviceWordCount(
        make_mesh(), chunk_len=4096,
        config=EngineConfig(local_capacity=1024, exchange_capacity=512,
                            out_capacity=1024, tile=512, tile_records=128,
                            combine_in_scan=True,
                            segment_impl="pallas", tokenize_impl="pallas",
                            segment_block=2048, tokenize_block=2048))
    tm_p = {}
    counts_p = wc_p.count_bytes(corpus, timings=tm_p, waves=3)
    assert counts_p == counts, (
        "pallas kernel-config fold diverged from the lax smoke run")
    assert tm_p["retries"] == 0, tm_p
    p_waves = REGISTRY.sum("mrtpu_device_waves_total") - pw0
    p_disp = (REGISTRY.sum("mrtpu_device_dispatches_total",
                           program="wave") - pd0)
    assert p_waves == tm_p["waves"] >= 2 and p_disp == p_waves, (
        f"pallas config broke one-dispatch-per-wave: {p_disp} dispatches "
        f"for {p_waves} waves")
    assert REGISTRY.sum("mrtpu_device_dispatches_total",
                        program="merge") == 0
    kb_seg = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                          kernel="segreduce") - kb_seg0
    kb_tok = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                          kernel="tokenize") - kb_tok0
    assert kb_seg >= 1 and kb_tok >= 1, (
        f"kernel-config run built no hot-path kernels (segreduce "
        f"{kb_seg}, tokenize {kb_tok}) — the config did not dispatch "
        "the kernel programs")
    pallas_buckets = [
        rec for rec in LEDGER.buckets()
        if rec.get("program") == "wave"
        and any("'pallas'" in e for e in rec.get("extra", []))]
    assert pallas_buckets, (
        "no wave bucket in the compile ledger carries the pallas config "
        "token — the kernel config never compiled a wave program")
    assert tm_p.get("mfu") is not None and tm_p["flops"] > 0, (
        f"pallas-served run carries no MFU in its timings: {tm_p}")
    # interpret-mode policy sanity: off-TPU, the kernels must have been
    # built under the interpreter (CPU numbers validate semantics)
    import jax as _jax

    if _jax.default_backend() != "tpu":
        assert REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                            mode="interpret") >= kb_seg + kb_tok
    # the gated key must be seeded in history (main() derives
    # wordcount_mfu from the kernel-served best run)
    assert any(benchgate.lookup(h, "wordcount_mfu") is not None
               for h in history), (
        "no BENCH.json history entry carries 'wordcount_mfu'")
    # the ops-level defaults stay importable constants (block sizes ride
    # the config fingerprint; a drifted default is a silent recompile)
    assert _segscan.SEGMENT_BLOCK % 128 == 0
    assert _tokenize_mod.TOKENIZE_BLOCK % 128 == 0

    # radix hot-path gate (ops/radix_sort; registry- and ledger-
    # asserted, zero wall-clock comparisons): a sort_impl='radix'
    # smoke run must (1) actually build the radix kernel programs
    # (histogram + rank/scatter; trace-time build counter, interpret
    # mode on this CPU tier), (2) keep the fused execution model —
    # still exactly one wave-program dispatch per wave, zero merge
    # dispatches, (3) fold bit-identically to the lax smoke run above
    # (same corpus, same wave split), (4) bucket the radix wave
    # program in the compile ledger WITHOUT adding any comparator-sort
    # wave bucket (the radix program replaces lax.sort inside the wave
    # — zero comparator compiles, not a comparator riding alongside),
    # and (5) keep the exchange traffic matrix bit-equal to the host
    # recompute — the fused in-kernel partition plan must not change
    # the PR 9 matrix semantics.
    def _comparator_wave_buckets() -> int:
        return sum(
            1 for rec in LEDGER.buckets()
            if rec.get("program") == "wave"
            and any("'variadic'" in e or "'argsort'" in e
                    for e in rec.get("extra", [])))

    kb_rh0 = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                          kernel="radix_hist")
    kb_rs0 = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                          kernel="radix_scatter")
    rw0 = REGISTRY.sum("mrtpu_device_waves_total")
    rd0 = REGISTRY.sum("mrtpu_device_dispatches_total", program="wave")
    cmp_buckets0 = _comparator_wave_buckets()
    # same capacity sizing rationale as the pallas gate above: the fold
    # is capacity-independent below overflow, and the small shapes keep
    # the 16-pass interpreter-run radix program cheap on the CPU tier
    wc_r = DeviceWordCount(
        make_mesh(), chunk_len=4096,
        config=EngineConfig(local_capacity=1024, exchange_capacity=512,
                            out_capacity=1024, tile=512, tile_records=128,
                            combine_in_scan=True, sort_impl="radix"))
    tm_r = {}
    counts_r = wc_r.count_bytes(corpus, timings=tm_r, waves=3)
    assert counts_r == counts, (
        "radix-sorted fold diverged from the lax smoke run")
    assert tm_r["retries"] == 0, tm_r
    r_waves = REGISTRY.sum("mrtpu_device_waves_total") - rw0
    r_disp = (REGISTRY.sum("mrtpu_device_dispatches_total",
                           program="wave") - rd0)
    assert r_waves == tm_r["waves"] >= 2 and r_disp == r_waves, (
        f"radix config broke one-dispatch-per-wave: {r_disp} dispatches "
        f"for {r_waves} waves")
    assert REGISTRY.sum("mrtpu_device_dispatches_total",
                        program="merge") == 0
    kb_rh = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                         kernel="radix_hist") - kb_rh0
    kb_rs = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                         kernel="radix_scatter") - kb_rs0
    assert kb_rh >= 1 and kb_rs >= 1, (
        f"radix config built no radix kernels (hist {kb_rh}, scatter "
        f"{kb_rs}) — the config did not dispatch the radix programs")
    radix_buckets = [
        rec for rec in LEDGER.buckets()
        if rec.get("program") == "wave"
        and any("'radix'" in e for e in rec.get("extra", []))]
    assert radix_buckets, (
        "no wave bucket in the compile ledger carries the radix config "
        "token — the radix config never compiled a wave program")
    assert _comparator_wave_buckets() == cmp_buckets0, (
        "the radix run added a comparator-sort wave bucket to the "
        "compile ledger — lax.sort compiled alongside the radix program")
    # the fused partition plan rides the same dispatch: its counts ARE
    # the traffic-matrix row, and must stay bit-equal both to the host
    # recompute and to the lax run's matrix over the same chunking
    host_m_r = wc_r.host_exchange_matrix(corpus, waves=3)
    r_m = np.asarray(tm_r["exchange"]["matrix"], dtype=np.int64)
    assert np.array_equal(r_m, host_m_r), (
        "radix fused partition plan diverged from the host-recomputed "
        "exchange traffic matrix")
    assert np.array_equal(host_m_r, host_m), (
        "host recompute drifted between the lax and radix smoke runs — "
        "the matrix comparison above is not comparing like for like")

    # always-on-service gate (registry-only): the sustained mode runs
    # with the SESSION layer active — the fused execution model must
    # hold there too (exactly one wave-program dispatch per session
    # wave, zero merge dispatches), the new gated key must be present
    # and seeded in history, and a session snapshot must agree with a
    # from-scratch batch count of the same bytes.
    sd0 = REGISTRY.sum("mrtpu_device_dispatches_total", program="wave")
    sw0 = REGISTRY.sum("mrtpu_session_waves_total")
    sustained = measure_sustained(make_mesh(), smoke=True)
    sess_waves = REGISTRY.sum("mrtpu_session_waves_total") - sw0
    sess_disp = (REGISTRY.sum("mrtpu_device_dispatches_total",
                              program="wave") - sd0)
    assert sess_waves > 0 and sess_disp == sess_waves, (
        f"session layer dispatched {sess_disp} programs for "
        f"{sess_waves} session waves (expected exactly one per wave)")
    assert REGISTRY.sum("mrtpu_device_dispatches_total",
                        program="merge") == 0
    assert sustained["sustained_records_per_s"] > 0, sustained
    assert sustained["sustained_churn_submitted"] > 0, (
        "churn thread never ran — the 'under tenant churn' claim "
        "would be vacuous")
    assert benchgate.lookup(sustained, "sustained_records_per_s") \
        is not None
    assert any(benchgate.lookup(h, "sustained_records_per_s") is not None
               for h in history), (
        "no BENCH.json history entry carries 'sustained_records_per_s'")
    # the serving-SLO gate (obs/slo): both gated latency keys must be
    # present in the run's timings AND seeded in history — presence
    # only, zero wall-clock comparisons (the values are real latencies
    # of this host and would flake under load)
    for key in ("submit_first_snapshot_p99_s",
                "snapshot_staleness_p99_s"):
        assert benchgate.lookup(sustained, key) is not None, (
            f"measure_sustained stopped reporting gated SLO key {key!r}")
        assert any(benchgate.lookup(h, key) is not None
                   for h in history), (
            f"no BENCH.json history entry carries {key!r}")
    # every sustained tenant produced SLO observations (first-result
    # once per stream, staleness at each snapshot)
    for t in ("t0", "t1", "t2"):
        assert REGISTRY.value("mrtpu_slo_submit_first_result_seconds",
                              tenant=t) >= 1, t
        assert REGISTRY.value("mrtpu_slo_snapshot_staleness_seconds",
                              tenant=t) >= 2, t

    from mapreduce_tpu.engine.session import EngineSession
    from mapreduce_tpu.engine.wordcount import wordcount_map_fn

    sess = EngineSession(
        make_mesh(), wordcount_map_fn,
        EngineConfig(local_capacity=4096, exchange_capacity=2048,
                     out_capacity=4096, tile=512, tile_records=128,
                     combine_in_scan=True, unit_values=True,
                     reduce_op="sum"),
        task="smoke-session")
    from mapreduce_tpu.ops.tokenize import shard_text

    sm_chunks, _L = shard_text(corpus, max(1, len(corpus) // 4096),
                               pad_multiple=512, pad_to=4096 + 512)
    half = max(1, len(sm_chunks) // 2)
    sess.feed(sm_chunks[:half])
    sess.feed(sm_chunks[half:])
    snap = sess.snapshot()
    svals = np.asarray(snap.values).reshape(-1)
    svalid = np.asarray(snap.valid).reshape(-1)
    session_total = int(svals[svalid.nonzero()[0]].sum())
    assert session_total == sum(counts.values()), (
        f"session aggregate {session_total} != batch word total "
        f"{sum(counts.values())}")
    sess.close()

    # tiered-serving gate (engine/tiering; registry-only, the swap made
    # deterministic by waiting on the background specializer between
    # feeds — zero wall-clock comparisons): a FORCED-COLD tiered
    # session must (1) dispatch its first wave on tier-0, (2) hot-swap
    # EXACTLY once at the next wave boundary after tier-1 lands,
    # (3) keep the one-dispatch-per-wave invariant within each tier,
    # and (4) produce a fold bit-identical to the pure-variadic session
    # above (same chunks, same feed split, same capacities).
    from dataclasses import replace as _dc_replace

    from mapreduce_tpu.engine import tiering

    t0d = REGISTRY.sum("mrtpu_compile_tier_total", tier="0")
    t1d = REGISTRY.sum("mrtpu_compile_tier_total", tier="1")
    sw0 = REGISTRY.sum("mrtpu_tier_swaps_total")
    wd0 = REGISTRY.sum("mrtpu_device_dispatches_total", program="wave")
    cold0 = REGISTRY.sum("mrtpu_tier_cold_starts_total")
    stw0 = REGISTRY.sum("mrtpu_session_waves_total", tier="0")
    stw1 = REGISTRY.sum("mrtpu_session_waves_total", tier="1")
    sess_t = EngineSession(
        make_mesh(), wordcount_map_fn,
        _dc_replace(sess.config, sort_impl="tiered"),
        task="smoke-tiered")
    with tiering.force_cold():
        sess_t.feed(sm_chunks[:half])   # cold: wave 0 serves on tier-0
    assert sess_t._dispatcher is not None and sess_t._dispatcher.tier == 0
    spec = sess_t.engine._tier_spec
    assert spec is not None and spec.wait(sess_t._dispatcher._key,
                                          timeout=600), (
        "background tier-1 specialization did not finish")
    sess_t.feed(sm_chunks[half:])       # next wave boundary: hot swap
    snap_t = sess_t.snapshot()
    assert sess_t._dispatcher.tier == 1
    tier0 = REGISTRY.sum("mrtpu_compile_tier_total", tier="0") - t0d
    tier1 = REGISTRY.sum("mrtpu_compile_tier_total", tier="1") - t1d
    swaps = REGISTRY.sum("mrtpu_tier_swaps_total") - sw0
    wave_d = (REGISTRY.sum("mrtpu_device_dispatches_total",
                           program="wave") - wd0)
    assert REGISTRY.sum("mrtpu_tier_cold_starts_total") - cold0 == 1
    assert tier0 >= 1 and tier1 >= 1, (tier0, tier1)
    assert swaps == 1, f"expected exactly one tier swap, saw {swaps}"
    assert tier0 + tier1 == wave_d == 2, (
        f"one-dispatch-per-wave broke across the swap: tier0={tier0} "
        f"tier1={tier1} wave dispatches={wave_d}")
    # the session tier labels the SLO plane attributes cold serving by
    assert REGISTRY.sum("mrtpu_session_waves_total", tier="0") \
        - stw0 == tier0
    assert REGISTRY.sum("mrtpu_session_waves_total", tier="1") \
        - stw1 == tier1
    # fold bit-identity across the swap, against the variadic session
    for field in ("keys", "values", "payload", "valid"):
        a = np.asarray(getattr(snap_t, field))
        b = np.asarray(getattr(snap, field))
        assert np.array_equal(a, b), (
            f"tiered session fold diverged from pure variadic: {field}")
    sess_t.close()
    # the new gated key must be seeded in history (the full bench also
    # gates its 2x relation against cold_compile_s within each run)
    assert any(benchgate.lookup(h, "cold_first_dispatch_s") is not None
               for h in history), (
        "no BENCH.json history entry carries 'cold_first_dispatch_s'")

    # control-plane gate (engine/autotune + obs/control; registry- and
    # ledger-asserted, zero wall-clock comparisons — the RATIO is a
    # wall measurement but only its presence/seeding gates here): the
    # smoke skew fixture must produce >= 1 rebalance decision, the
    # per-window exchange imbalance must DROP inside the same run, the
    # control-ledger artifact must validate, and the one-dispatch-per-
    # wave invariant must hold through the rebalancing session.
    from mapreduce_tpu.obs import control as obs_control

    rd0 = REGISTRY.sum("mrtpu_control_decisions_total",
                       controller="repartition")
    cg_d0 = REGISTRY.sum("mrtpu_device_dispatches_total",
                         program="wave")
    cg_w0 = REGISTRY.sum("mrtpu_session_waves_total")
    skew_mesh = make_mesh()
    skew = measure_skew_rebalance(skew_mesh, smoke=True)
    rebalances = REGISTRY.sum("mrtpu_control_decisions_total",
                              controller="repartition") - rd0
    if skew_mesh.shape["data"] > 1:
        # a 1-device mesh cannot be imbalanced (measure_skew_rebalance
        # guards its own asserts the same way) — the controller gates
        # only where a rebalance is even possible
        assert rebalances >= 1, (
            "smoke skew fixture produced no repartition decision")
        assert skew["skew_imbalance_last"] < \
            skew["skew_imbalance_first"], (
            f"exchange imbalance did not drop across control windows: "
            f"{skew['skew_imbalance_first']} -> "
            f"{skew['skew_imbalance_last']}")
        ctrl_snap = obs_control.control_snapshot()
        assert ctrl_snap.get("decisions"), (
            "control ledger empty after a rebalancing run")
        obs_control.validate_control({"kind": "mrtpu-control",
                                      "version": 1,
                                      "snapshot": ctrl_snap})
    cg_disp = (REGISTRY.sum("mrtpu_device_dispatches_total",
                            program="wave") - cg_d0)
    cg_waves = REGISTRY.sum("mrtpu_session_waves_total") - cg_w0
    assert cg_waves > 0 and cg_disp == cg_waves, (
        f"one-dispatch-per-wave broke under the skew controller: "
        f"{cg_disp} dispatches for {cg_waves} session waves")
    assert benchgate.lookup(skew, "skewed_wall_ratio") is not None
    assert any(benchgate.lookup(h, "skewed_wall_ratio") is not None
               for h in history), (
        "no BENCH.json history entry carries 'skewed_wall_ratio'")

    # durability gate (coord/ha + engine/spill; the chaos suite proves
    # the exactly-once witness — this is the presence/seeding gate plus
    # one real in-process kill and one real evict->restore, both
    # correctness-asserted inside their measure functions): the two
    # gated keys must be present in this run AND seeded in history.
    failover = measure_failover(smoke=True)
    restored = measure_session_restore(make_mesh(), smoke=True)
    for key, run in (("board_failover_s", failover),
                     ("session_restore_s", restored)):
        assert benchgate.lookup(run, key) is not None, (
            f"durability measure stopped reporting gated key {key!r}")
        assert any(benchgate.lookup(h, key) is not None
                   for h in history), (
            f"no BENCH.json history entry carries {key!r}")
    # the failover client rotated at least once getting off the dead
    # primary (registry-asserted, no wall clock)
    assert REGISTRY.sum("mrtpu_client_failovers_total") >= 1, (
        "failover measure completed without a single client rotation")
    assert REGISTRY.sum("mrtpu_session_restores_total") >= 1
    assert REGISTRY.sum("mrtpu_session_spills_total") >= 1

    # fleet gate (coord/fleet + engine/migrate; the chaos suite proves
    # the SIGKILL-the-host recovery — this is one REAL live migration
    # on the 2-host in-process fixture: destination-snapshot
    # bit-identity and the registry route flip are asserted inside the
    # measure, the move's audit trail is asserted here from the
    # metrics registry, and both gated fleet keys must be present in
    # this run AND seeded in history).
    mg0 = REGISTRY.sum("mrtpu_session_migrations_total")
    migrated = measure_session_migration(make_mesh(), smoke=True)
    assert benchgate.lookup(
        migrated, "session_migration_s") is not None, (
        "migration measure stopped reporting 'session_migration_s'")
    for key in ("session_migration_s", "fleet_sustained_records_per_s"):
        assert any(benchgate.lookup(h, key) is not None
                   for h in history), (
            f"no BENCH.json history entry carries {key!r}")
    mg_delta = REGISTRY.sum("mrtpu_session_migrations_total") - mg0
    assert mg_delta == 1, (
        f"the smoke migration landed {mg_delta} "
        "mrtpu_session_migrations_total increments (expected exactly "
        "one — the move must be visible in the audit plane)")

    # collector overhead gate: telemetry for the whole engine run must
    # fit a bounded number of push batches (the pusher batches the span
    # ring, it does not chat per span/wave), lose NOTHING in a
    # fault-free run, and yield a parseable merged timeline carrying
    # the run's wave spans.
    from mapreduce_tpu.coord.docserver import DocServer, HttpDocStore
    from mapreduce_tpu.obs.collector import TelemetryPusher
    from mapreduce_tpu.obs.profile import validate_trace

    p0 = REGISTRY.sum("mrtpu_telemetry_pushes_total")
    dr0 = REGISTRY.sum("mrtpu_telemetry_dropped_total")
    srv = DocServer().start_background()
    pusher = TelemetryPusher(f"{srv.host}:{srv.port}",
                             role="bench-smoke", interval=60.0)
    try:
        assert pusher.flush(), \
            "telemetry push failed against a healthy collector"
        # delta, not absolute: the suite may have run chaos pushers in
        # this process before the smoke
        drops = REGISTRY.sum("mrtpu_telemetry_dropped_total") - dr0
        assert drops == 0, (
            f"{drops} spans dropped in a fault-free smoke run")
        pushes = REGISTRY.sum("mrtpu_telemetry_pushes_total") - p0
        assert pushes <= max(2, waves_ran), (
            f"collector overhead unbounded: {pushes} push batches for "
            f"{waves_ran} waves (expected one batch for the whole run)")
        client = HttpDocStore(f"{srv.host}:{srv.port}")
        try:
            cluster = client.clusterz()
        finally:
            client.close()
        validate_trace(cluster)
        wave_spans = sum(1 for e in cluster["traceEvents"]
                         if e.get("name") == "wave")
        assert wave_spans >= waves_ran, (
            f"merged timeline carries {wave_spans} wave spans for "
            f"{waves_ran} waves")
    finally:
        pusher.stop(flush=False)
        srv.shutdown()

    # durable-history gate (obs/history): one live docserver with the
    # history plane attached.  Every assertion reads the metrics
    # registry or the /queryz wire — never a wall clock — so it cannot
    # flake on load: append overhead is bounded per push batch, the
    # /queryz increase of a probe counter must match the registry's
    # cumulative value BIT-EXACTLY (first-entry delta carries the full
    # cumulative, so total increase == final cum), and a corrupt
    # segment must refuse to load rather than serve wrong numbers.
    import shutil
    import tempfile

    from mapreduce_tpu.obs.history import (HistoryCorruptError,
                                           MetricHistory)
    from mapreduce_tpu.obs.metrics import counter

    hist_dir = tempfile.mkdtemp(prefix="bench-history-")
    probe = counter("mrtpu_bench_history_probe_total",
                    "bench-only durable-history smoke probe")
    a0 = REGISTRY.sum("mrtpu_history_appends_total")
    o0 = REGISTRY.sum("mrtpu_history_append_seconds")
    hp0 = REGISTRY.sum("mrtpu_telemetry_pushes_total")
    srv = DocServer(history_dir=hist_dir).start_background()
    pusher = TelemetryPusher(f"{srv.host}:{srv.port}",
                             role="bench-history", interval=60.0)
    try:
        assert pusher.flush(), "history-plane telemetry push failed"
        probe.inc(7)
        assert pusher.flush(), "history-plane telemetry push failed"
        hist_pushes = REGISTRY.sum("mrtpu_telemetry_pushes_total") - hp0
        hist_appends = REGISTRY.sum("mrtpu_history_appends_total") - a0
        assert 1 <= hist_appends <= hist_pushes, (
            f"history append overhead unbounded: {hist_appends} "
            f"appends for {hist_pushes} push batches (expected at "
            "most one append per push)")
        observed = REGISTRY.sum("mrtpu_history_append_seconds") - o0
        assert observed >= hist_appends, (
            "append latency histogram missed appends "
            f"({observed} observations, {hist_appends} appends)")
        client = HttpDocStore(f"{srv.host}:{srv.port}")
        try:
            res = client.queryz(
                {"metric": "mrtpu_bench_history_probe_total",
                 "fn": "increase", "start": -3600})
        finally:
            client.close()
        hist_got = sum(v for s in res["series"]
                       for _t, v in s["points"])
        want = REGISTRY.sum("mrtpu_bench_history_probe_total")
        assert hist_got == want, (
            f"/queryz increase diverged from the registry: history "
            f"says {hist_got}, registry says {want}")
    finally:
        pusher.stop(flush=False)
        srv.shutdown()
    bad_dir = tempfile.mkdtemp(prefix="bench-history-bad-")
    with open(os.path.join(bad_dir, "seg-00000001.jsonl"), "w") as f:
        f.write('{"v":1,"garbled":true}\n')
    try:
        MetricHistory(bad_dir).load()
    except HistoryCorruptError:
        pass
    else:
        raise AssertionError("a corrupt history segment loaded "
                             "silently instead of refusing")
    shutil.rmtree(hist_dir, ignore_errors=True)
    shutil.rmtree(bad_dir, ignore_errors=True)

    # alerting gate (obs/alerts): a synthetic rule walks
    # pending->firing on an injected series under EXPLICIT wall
    # stamps (no sleeps, nothing to flake), the webhook sink records
    # exactly ONE delivery across two pumps (the per-sink durable
    # cursor), and the alerts.json bundle doc survives its strict
    # validator after a JSON round trip.
    import http.server as _http_server
    import threading

    from mapreduce_tpu.obs import alerts as _alerts

    hits = []

    class _Hook(_http_server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(
                int(self.headers.get("Content-Length", 0)))
            hits.append(json.loads(body))
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *a):
            pass

    hook = _http_server.ThreadingHTTPServer(("127.0.0.1", 0), _Hook)
    hook_thread = threading.Thread(target=hook.serve_forever,
                                   daemon=True)
    hook_thread.start()
    alert_dir = tempfile.mkdtemp(prefix="bench-alerts-")
    gate_hist = MetricHistory(os.path.join(alert_dir, "hist"))
    t0 = 1_000_000.0
    gate_hist.append_snapshot(
        "bench",
        {("mrtpu_bench_alert_probe_total", (("task", "gate"),)): 9.0},
        t=t0)
    nd0 = REGISTRY.sum("mrtpu_alert_notifications_total",
                       sink="bench-hook", outcome="delivered")
    plane = _alerts.AlertPlane(flap_damp_s=0.0)
    try:
        plane.configure(
            [_alerts.parse_alert(
                "gate:increase(mrtpu_bench_alert_probe_total[60])"
                ":gt:5:5")],
            log_dir=os.path.join(alert_dir, "log"),
            sinks=[_alerts.WebhookSink(
                "bench-hook", f"127.0.0.1:{hook.server_address[1]}")])
        plane.evaluate(history=gate_hist, now=t0 + 1)
        counts = plane.snapshot(now=t0 + 1).get("counts") or {}
        assert counts.get("pending") == 1, (
            f"alert gate: expected pending after first sweep, "
            f"got {counts}")
        plane.evaluate(history=gate_hist, now=t0 + 7)
        counts = plane.snapshot(now=t0 + 7).get("counts") or {}
        assert counts.get("firing") == 1, (
            f"alert gate: expected firing after for-duration, "
            f"got {counts}")
        plane.pump()
        plane.pump()  # idempotent: the durable cursor already advanced
        delivered = REGISTRY.sum("mrtpu_alert_notifications_total",
                                 sink="bench-hook",
                                 outcome="delivered") - nd0
        assert delivered == 1 and len(hits) == 1, (
            f"alert gate: wanted exactly one webhook delivery, "
            f"counter says {delivered}, receiver saw {len(hits)}")
        assert hits[0]["rule"] == "gate" and hits[0]["to"] == "firing"
        alerts_doc = json.loads(json.dumps(
            {"kind": "mrtpu-alerts", "version": 1,
             "snapshot": plane.snapshot(now=t0 + 7)}, default=float))
        _alerts.validate_alerts(alerts_doc)
    finally:
        plane.reset()
        gate_hist.close()
        hook.shutdown()
        hook.server_close()
        shutil.rmtree(alert_dir, ignore_errors=True)

    print(json.dumps({
        "mode": "check_smoke", "ok": True,
        "history_gate": {"appends": hist_appends,
                         "queryz_increase": hist_got,
                         "corrupt_refused": True},
        "alert_gate": {"lifecycle": "pending->firing",
                       "webhook_deliveries": delivered,
                       "alerts_json_valid": True},
        "history_runs": len(history),
        "gate_flagged_2x": bad_probs,
        "dispatches_per_wave": dispatches / waves_ran,
        "device_flops_recorded": flops,
        "mfu_gauge": REGISTRY.value("mrtpu_device_mfu"),
        "pallas_fold_identical": True,
        "pallas_kernel_builds": {"segreduce": kb_seg, "tokenize": kb_tok},
        "pallas_mfu": tm_p.get("mfu"),
        "radix_fold_identical": True,
        "radix_kernel_builds": {"hist": kb_rh, "scatter": kb_rs},
        "radix_exchange_matrix_bit_equal": True,
        "second_build_cached": cached_delta,
        "sustained_records_per_s": sustained["sustained_records_per_s"],
        "submit_first_snapshot_p99_s":
            sustained["submit_first_snapshot_p99_s"],
        "snapshot_staleness_p99_s":
            sustained["snapshot_staleness_p99_s"],
        "session_dispatches_per_wave": sess_disp / sess_waves,
        "skewed_wall_ratio": skew["skewed_wall_ratio"],
        "skew_imbalance_first": skew["skew_imbalance_first"],
        "skew_imbalance_last": skew["skew_imbalance_last"],
        "skew_rebalance_decisions": skew["skew_rebalance_decisions"],
        "board_failover_s": failover["board_failover_s"],
        "session_restore_s": restored["session_restore_s"],
        "session_migration_s": migrated["session_migration_s"],
        "exchange_records": tm["exchange_records"],
        "exchange_imbalance": tm["exchange_imbalance"],
        "upload_overlap_frac": tm["upload_overlap_frac"],
        "telemetry_push_batches": pushes,
        "telemetry_dropped": drops,
        "cluster_timeline_wave_spans": wave_spans,
    }, default=float))
    return 0


def main() -> None:
    scale = float(os.environ.get("BENCH_SCALE", "1.0"))
    if "--smoke" in sys.argv:  # quick self-check mode
        scale = 0.002
    prof_dir = None
    for i, a in enumerate(sys.argv):
        if a == "--profile":
            if i + 1 >= len(sys.argv):
                sys.exit("--profile needs a bundle directory argument")
            prof_dir = sys.argv[i + 1]

    # ROADMAP 2(c): cold vs warm compile, measured by fresh-process
    # probes against a throwaway cache dir (cold is genuinely cold even
    # on a machine whose real cache is warm; warm is the literal
    # "warmup → restarted process" production path).  They run FIRST,
    # before this process touches a device: a chip belongs to one
    # process at a time, and a parent that has built its mesh holds it
    # against its own children.
    print("# measuring cold/warm compile (fresh-process probes; the "
          "cold one pays the full sort-comparator compile) ...",
          file=sys.stderr, flush=True)
    coldwarm = measure_cold_warm(smoke="--smoke" in sys.argv)
    print(f"# cold_compile_s={coldwarm['cold_compile_s']} "
          f"warm_start_s={coldwarm['warm_start_s']} "
          f"(warm wave outcome: {coldwarm['warm_outcome']}); "
          f"cold_first_dispatch_s={coldwarm['cold_first_dispatch_s']} "
          f"(tiered cold serving: tier-0 dispatched="
          f"{coldwarm['tiered_cold_start']}, "
          f"swaps={coldwarm['tiered_swaps']})",
          file=sys.stderr, flush=True)

    # persistent XLA compilation cache (utils/compile_cache): the
    # engine's wave split is corpus-size-independent so one cache entry
    # serves every corpus, and `cli warmup --bench` primes it off the
    # critical path.
    from mapreduce_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax

    from mapreduce_tpu.engine import DeviceWordCount
    from mapreduce_tpu.engine.wordcount import bench_engine_config
    from mapreduce_tpu.parallel import make_mesh

    mesh = make_mesh()
    wc = DeviceWordCount(mesh, chunk_len=1 << 22,
                         config=bench_engine_config())

    t0 = time.monotonic()
    corpus = make_corpus(int(N_WORDS * scale), max(int(N_LINES * scale), 1))
    gen_s = time.monotonic() - t0

    n_runs = 1 if "--smoke" in sys.argv else 3

    # Stage each timed run's corpus copy with VERIFIED residency
    # (stage_inputs runs a checksum barrier over every staged buffer —
    # the reported seconds are the true ingress cost, not the optimistic
    # early return of block_until_ready).  The staged copies coexist
    # until their runs consume them — HBM holds up to n_runs copies BY
    # CHOICE; the engine itself streams (count_bytes peaks at ~2 waves
    # whatever the corpus), and each run frees its waves as it folds
    # them.
    print(f"# corpus ready ({len(corpus)/1e6:.0f} MB, {gen_s:.1f}s); "
          f"staging {n_runs} input copies ...", file=sys.stderr, flush=True)
    staged_runs = []
    for r in range(n_runs):
        t1 = time.monotonic()
        handle = wc.stage(corpus)
        staged_runs.append((handle, time.monotonic() - t1))
    ingress = [round(sec, 2) for _, sec in staged_runs]
    rate = len(corpus) / 1e6 / max(min(ingress), 1e-3)
    print(f"# ingress (verified resident): {ingress}s "
          f"({rate:.0f} MB/s link); warmup (compile) ...",
          file=sys.stderr, flush=True)

    # AOT compile AFTER staging (with a primed persistent cache — cli
    # warmup --bench — this is seconds).  The end-to-end priming run
    # uses a SLICE: the engine's programs are corpus-size-independent
    # (fixed chunk shapes), so the slice pays every first-dispatch cost
    # (executable deserialization, readback program warm, device
    # priming) without a second full-corpus upload inside compile_s.
    # Full-corpus validation happens on the first TIMED run's output
    # (oracle diff below).
    t_w = time.monotonic()
    aot_s = wc.warm()
    # the priming slice must be EXACTLY two full waves: the auto wave
    # split shrinks k for sub-wave corpora (different program shape —
    # priming a 1/16 slice of arbitrary size can compile the WRONG
    # program and leave the timed run to pay the ~100s sort compile),
    # and W=2 exercises the wave-merge program
    eng = wc.engine
    prime_chunks = 2 * eng._rows_per_wave(wc._row_len()) * eng.n_dev
    prime = corpus[: prime_chunks * wc.chunk_len]
    wc.count_bytes(prime)
    compile_s = time.monotonic() - t_w
    print(f"# warmup done in {compile_s:.1f}s (AOT {aot_s:.1f}s, "
          "priming on a two-wave slice)", file=sys.stderr, flush=True)

    # optional jax.profiler capture around the timed runs (rides the
    # --profile bundle).  A CPU run degrades to a bundle without the
    # jax trace; on the TPU the device trace is what --profile is FOR,
    # so a profiler that cannot start is an error there
    jax_trace_dir = None
    if prof_dir:
        jax_trace_dir = os.path.join(prof_dir, "jax_trace")
        try:
            jax.profiler.start_trace(jax_trace_dir)
        except Exception as exc:
            if jax.devices()[0].platform == "tpu":
                raise
            print(f"# jax.profiler unavailable ({exc}); bundle will "
                  "carry no jax trace", file=sys.stderr)
            jax_trace_dir = None

    # best of N timed runs (per-run stages go to stderr so the variance
    # stays visible)
    runs = []
    counts = None
    for r in range(len(staged_runs)):
        handle, ingress_s = staged_runs[r]
        staged_runs[r] = None  # free each run's device copy after use
        tm = {"ingress_s": round(ingress_s, 4)}
        t1 = time.monotonic()
        got = wc.count_staged(handle, timings=tm)
        del handle
        tm["wall_s"] = round(time.monotonic() - t1, 4)
        if counts is None:
            counts = got
        else:
            assert got == counts, "runs disagree"
        runs.append(tm)
        print(f"# run{r}: {json.dumps(tm)}", file=sys.stderr, flush=True)
    best = min(runs, key=lambda tm: tm["wall_s"])
    wall = best["wall_s"]
    if jax_trace_dir:
        jax.profiler.stop_trace()

    total = sum(counts.values())
    assert total == int(N_WORDS * scale), total

    # full-scale independent oracle: the in-tree C++ tokenizer/aggregator
    # (native/mr_native.cpp) counts the same bytes through a completely
    # separate code path; ANY mismatch — missing word, wrong count — is a
    # hard failure (the reference's perf table is backed by the same kind
    # of oracle diff, test.sh:11-15)
    from mapreduce_tpu import native

    if native.native_available():
        t_o = time.monotonic()
        oracle = native.wordcount_bytes(corpus)
        if counts != oracle:
            only_dev = set(counts) - set(oracle)
            only_orc = set(oracle) - set(counts)
            bad = [w for w in (set(counts) & set(oracle))
                   if counts[w] != oracle[w]]
            print(f"ORACLE MISMATCH: {len(only_dev)} device-only words, "
                  f"{len(only_orc)} oracle-only, {len(bad)} wrong counts "
                  f"(e.g. {bad[:3]})", file=sys.stderr)
            sys.exit(1)
        print(f"# native oracle agrees: {len(oracle)} uniques, "
              f"{time.monotonic() - t_o:.1f}s", file=sys.stderr, flush=True)
    else:
        print("# WARNING: native oracle unavailable (no g++); "
              "only the total-count check ran", file=sys.stderr)

    # the always-on service mode (sched/ + engine/session): sustained
    # records/s while tenants churn on a live scheduler mid-stream
    print("# measuring sustained throughput under tenant churn "
          "(resident session, 3 tenants + churn) ...",
          file=sys.stderr, flush=True)
    sustained = measure_sustained(mesh, smoke="--smoke" in sys.argv)
    print(f"# sustained_records_per_s="
          f"{sustained['sustained_records_per_s']} over "
          f"{sustained['sustained_waves']} waves, churn "
          f"{sustained['sustained_churn_submitted']} submits / "
          f"{sustained['sustained_churn_cancelled']} cancels; "
          f"submit_first_snapshot_p99_s="
          f"{sustained['submit_first_snapshot_p99_s']} "
          f"snapshot_staleness_p99_s="
          f"{sustained['snapshot_staleness_p99_s']}",
          file=sys.stderr, flush=True)

    # the control plane (engine/autotune + obs/control): skew-control
    # serving overhead + the in-run imbalance trajectory
    print("# measuring skew-aware repartition (adversarial skewed "
          "stream vs uniform, controller rebalancing mid-stream) ...",
          file=sys.stderr, flush=True)
    skew = measure_skew_rebalance(mesh, smoke="--smoke" in sys.argv)
    print(f"# skewed_wall_ratio={skew['skewed_wall_ratio']} "
          f"(imbalance {skew['skew_imbalance_first']}x -> "
          f"{skew['skew_imbalance_last']}x over {skew['skew_rounds']} "
          f"windows, {skew['skew_rebalance_decisions']} rebalance "
          "decision(s))", file=sys.stderr, flush=True)

    # the durability plane (coord/ha + engine/spill): board failover
    # and session evict->restore serving latency
    print("# measuring board failover (kill primary, standby takes "
          "over) and session evict->restore ...",
          file=sys.stderr, flush=True)
    failover = measure_failover(smoke="--smoke" in sys.argv)
    restore = measure_session_restore(mesh, smoke="--smoke" in sys.argv)
    print(f"# board_failover_s={failover['board_failover_s']} (lease "
          f"{failover['board_failover_lease_s']}s); "
          f"session_restore_s={restore['session_restore_s']} "
          f"(spill {restore['session_spill_s']}s)",
          file=sys.stderr, flush=True)

    # the fleet plane (coord/fleet + engine/migrate): one live
    # migration on the 2-host fixture, then the 2-host aggregate
    # sustained rate
    print("# measuring live migration (2-host fleet fixture, evict -> "
          "destination snapshot) and the 2-host aggregate sustained "
          "rate ...", file=sys.stderr, flush=True)
    migration = measure_session_migration(mesh, smoke="--smoke" in sys.argv)
    fleet_sus = measure_fleet_sustained(mesh, smoke="--smoke" in sys.argv)
    print(f"# session_migration_s={migration['session_migration_s']} "
          f"(spill {migration['session_migration_spill_s']}s); "
          f"fleet_sustained_records_per_s="
          f"{fleet_sus['fleet_sustained_records_per_s']} over "
          f"{fleet_sus['fleet_sustained_hosts']} hosts",
          file=sys.stderr, flush=True)

    result = {
        "metric": "europarl_wordcount_wall_s",
        "value": round(wall, 4),
        "unit": "s",
        "vs_baseline": round(BASELINE_S / wall, 2),
        # the gated device-plane headline: the best run's fused-engine
        # compute seconds (and the per-wave figure, since wave counts
        # can legitimately change with WAVE_BYTES tuning)
        "europarl_wordcount_compute_s": best.get("compute_s"),
        "compute_s_per_wave": (
            round(best["compute_s"] / best["waves"], 4)
            if best.get("compute_s") and best.get("waves") else None),
        "compile_s": round(compile_s, 1),
        "ingress_s": best["ingress_s"],
        "ingress_note": "host->device transfer of the corpus, measured "
                        "with a residency barrier. Excluded from "
                        "value, matching the reference clock (its corpus "
                        "pre-exists in cluster storage).",
        "timings": {k: v for k, v in best.items() if k != "wall_s"},
        # system-computed MFU/roofline (obs/profile.py — no longer an
        # ad-hoc bench-script derivation): XLA cost_analysis flops over
        # the best run's compute seconds against the device peak table
        "mfu": best.get("mfu"),
        "roofline_frac": best.get("roofline_frac"),
        "cost_source": best.get("cost_source"),
        # the gated Pallas hot-path key: the kernel-served run's MFU
        # (bench_engine_config serves segment_impl/tokenize_impl=
        # 'pallas'), as its own REQUIRED higher-is-better top-level key
        "wordcount_mfu": best.get("mfu"),
        "segment_impl": wc.config.segment_impl,
        "tokenize_impl": wc.config.tokenize_impl,
        # the gated warm-start keys (ROADMAP 2(c))
        "cold_compile_s": coldwarm["cold_compile_s"],
        "warm_start_s": coldwarm["warm_start_s"],
        "warm_outcome": coldwarm["warm_outcome"],
        # the gated tiered-serving key (ROADMAP 4(a), engine/tiering):
        # cold submit -> first wave dispatched via sort_impl='tiered'
        "cold_first_dispatch_s": coldwarm["cold_first_dispatch_s"],
        "tiered_cold_start": coldwarm["tiered_cold_start"],
        "tiered_swaps": coldwarm["tiered_swaps"],
        # the gated comms keys (obs/comms): recv-side exchange
        # imbalance of the device traffic matrix and the feeder
        # overlap fraction of the best run
        "exchange_imbalance": best.get("exchange_imbalance"),
        "upload_overlap_frac": best.get("upload_overlap_frac"),
        "exchange_records": best.get("exchange_records"),
        "modeled_exchange_s": best.get("modeled_exchange_s"),
        # the gated always-on-service key (+ its context and the top-K
        # workload's bench entry), from measure_sustained
        **sustained,
        # the gated durability keys (coord/ha + engine/spill)
        **failover,
        **restore,
        # the gated fleet keys (coord/fleet + engine/migrate): live
        # migration wall and the 2-host aggregate sustained rate
        **migration,
        **fleet_sus,
        # the gated control-plane key (+ its in-run imbalance
        # trajectory), from measure_skew_rebalance
        **skew,
    }
    print(json.dumps(result))
    print(f"# {len(counts)} unique words, {total} total; "
          f"devices={len(mesh.devices.flat)} "
          f"platform={jax.devices()[0].platform}; corpus gen {gen_s:.1f}s",
          file=sys.stderr)

    if prof_dir:
        from mapreduce_tpu.obs import profile as obs_profile

        obs_profile.write_bundle(prof_dir, jax_trace_dir=jax_trace_dir)
        print(f"# profile bundle -> {prof_dir} (trace.json opens in "
              "https://ui.perfetto.dev)", file=sys.stderr)

    if "--check" in sys.argv:
        from mapreduce_tpu.obs import benchgate

        # the warm-start ratio relates two keys of THIS run, which
        # per-metric history medians cannot express: gate it here, and
        # keep a ratio-failing run OUT of the history
        ratio_problems = []
        if (result["warm_start_s"]
                >= WARM_START_MAX_FRACTION * result["cold_compile_s"]):
            ratio_problems.append(
                f"warm_start_s {result['warm_start_s']} >= "
                f"{WARM_START_MAX_FRACTION:g} x cold_compile_s "
                f"{result['cold_compile_s']} — the persistent cache is "
                "not serving the engine programs")
        if (result["cold_first_dispatch_s"]
                >= TIERED_FIRST_DISPATCH_MAX_FRACTION
                * result["cold_compile_s"]):
            ratio_problems.append(
                f"cold_first_dispatch_s {result['cold_first_dispatch_s']}"
                f" >= {TIERED_FIRST_DISPATCH_MAX_FRACTION:g} x "
                f"cold_compile_s {result['cold_compile_s']} — tiered "
                "cold serving is not beating the variadic cold compile "
                "by 2x (tier-0 is not decoupling first results from "
                "the comparator compile)")
        # the fleet relation: the 2-host aggregate must beat the
        # RECORDED one-host rate (history median — not the same-run
        # value: on a fixture where both in-process hosts share one
        # physical device pool, concurrent hosts add no device
        # capacity, while the recorded bar tracks the platform as
        # entries append)
        _, _hist = benchgate.load_history(HISTORY_PATH)
        _one_host = [v for v in (benchgate.lookup(
            h, "sustained_records_per_s") for h in _hist)
            if v is not None]
        if _one_host:
            import statistics
            recorded_rate = statistics.median(_one_host)
            if (result["fleet_sustained_records_per_s"]
                    <= recorded_rate):
                ratio_problems.append(
                    f"fleet_sustained_records_per_s "
                    f"{result['fleet_sustained_records_per_s']} <= "
                    f"the recorded one-host sustained_records_per_s "
                    f"median {recorded_rate} — the 2-host fleet entry "
                    "does not beat the one-host record")
        if result["skewed_wall_ratio"] > SKEWED_WALL_MAX_RATIO:
            ratio_problems.append(
                f"skewed_wall_ratio {result['skewed_wall_ratio']} > "
                f"{SKEWED_WALL_MAX_RATIO:g} — the rebalanced "
                "skewed-corpus run is not within the acceptance "
                "ceiling of the uniform run")
        problems = ratio_problems + benchgate.check_and_append(
            HISTORY_PATH, result, gate_specs(),
            append=not ratio_problems)
        if problems:
            print("REGRESSION GATE FAILED vs BENCH.json history:",
                  file=sys.stderr)
            for p in problems:
                print(f"  {p}", file=sys.stderr)
            sys.exit(1)
        print(f"# gate OK; run appended to {HISTORY_PATH}",
              file=sys.stderr)


if __name__ == "__main__":
    _si = (sys.argv[sys.argv.index("--sort-impl") + 1]
           if "--sort-impl" in sys.argv else None)
    if "--compile-probe" in sys.argv:
        _i = sys.argv.index("--compile-probe")
        raise SystemExit(compile_probe(sys.argv[_i + 1],
                                       smoke="--smoke" in sys.argv,
                                       sort_impl=_si))
    if "--tiered-probe" in sys.argv:
        _i = sys.argv.index("--tiered-probe")
        raise SystemExit(tiered_probe(sys.argv[_i + 1],
                                      smoke="--smoke" in sys.argv,
                                      sort_impl=_si or "tiered"))
    if "--check" in sys.argv and "--smoke" in sys.argv:
        raise SystemExit(check_smoke())
    main()
