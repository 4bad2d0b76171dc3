"""Compile-observability tests: the shape-bucket compile ledger
(obs/compile) — outcome taxonomy (compiled / cached / persistent_hit),
compile ⊃ {lowering, backend_compile} spans, the on-disk shape
registry + warmup --replay, ledgered engine builds (second build = zero
new compile-seconds), bundle compile_ledger.json round-trip, and the
capacity-retry forensics event flowing into cli diagnose."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mapreduce_tpu.obs import compile as compile_obs
from mapreduce_tpu.obs import profile as obs_profile
from mapreduce_tpu.obs.compile import CompileLedger, LEDGER
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.obs.trace import TRACER, Tracer


@pytest.fixture
def cache_dir(tmp_path):
    """Point jax's cache-dir CONFIG at a temp dir for the duration.
    (XLA itself latched its cache state at this process's first compile
    — the config is only read by the ledger's classification and
    registry-path logic, which is exactly what these tests exercise.)"""
    prev = jax.config.jax_compilation_cache_dir
    d = str(tmp_path / "cache")
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    try:
        yield d
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def _jit_sort():
    return jax.jit(lambda x: jnp.sort(x * 2.0))


def _structs(n=256):
    return (jax.ShapeDtypeStruct((n,), jnp.float32),)


# -- outcome taxonomy --------------------------------------------------------


def test_ledger_compiles_then_caches():
    led = CompileLedger(tracer=Tracer())
    f = _jit_sort()
    c1, out1 = led.compile(f, _structs(), program="t_sort")
    assert out1 == "compiled"
    c2, out2 = led.compile(f, _structs(), program="t_sort")
    assert out2 == "cached" and c2 is c1
    # a different shape is a different bucket
    _c3, out3 = led.compile(f, _structs(512), program="t_sort")
    assert out3 == "compiled"
    snap = led.snapshot()
    prog = snap["programs"]["t_sort"]
    assert prog["compiled"] == 2 and prog["cached"] == 1
    assert prog["buckets"] == 2
    assert prog["compile_s"] > 0


def test_ledger_cross_object_reuse_needs_key():
    led = CompileLedger(tracer=Tracer())
    c1, out1 = led.compile(_jit_sort(), _structs(), program="t_key",
                           key=("shared",))
    c2, out2 = led.compile(_jit_sort(), _structs(), program="t_key",
                           key=("shared",))
    assert out2 == "cached" and c2 is c1
    # keyless: distinct jit objects never alias
    _c3, out3 = led.compile(_jit_sort(), _structs(), program="t_key")
    assert out3 == "compiled"


def test_persistent_hit_classified_from_disk_registry(cache_dir):
    """A fresh-process rebuild (modelled by a fresh ledger) whose bucket
    is already in the on-disk registry next to an enabled cache is a
    persistent_hit — the classification warm restarts report."""
    led1 = CompileLedger(tracer=Tracer())
    _, out1 = led1.compile(_jit_sort(), _structs(), program="t_hit",
                           bucket_extra=("x",))
    assert out1 == "compiled"
    reg = compile_obs.registry_path(cache_dir)
    assert os.path.exists(reg), "shape registry not written"
    led2 = CompileLedger(tracer=Tracer())  # fresh-process equivalent
    _, out2 = led2.compile(_jit_sort(), _structs(), program="t_hit",
                           bucket_extra=("x",))
    assert out2 == "persistent_hit"
    # different bucket_extra = different bucket = genuinely cold
    _, out3 = led2.compile(_jit_sort(), _structs(), program="t_hit",
                           bucket_extra=("y",))
    assert out3 == "compiled"


def test_disk_registry_merges_and_counts(cache_dir):
    led = CompileLedger(tracer=Tracer())
    led.compile(_jit_sort(), _structs(), program="t_merge")
    led2 = CompileLedger(tracer=Tracer())
    led2.compile(_jit_sort(), _structs(), program="t_merge")
    buckets = led2.disk_buckets(cache_dir)
    (rec,) = [r for r in buckets.values() if r["program"] == "t_merge"]
    assert rec["count"] == 2
    assert rec["best_compile_s"] <= rec["compile_s"]
    assert rec["avals"][0]["shape"] == [256]


# -- spans + metrics ---------------------------------------------------------


def test_compile_spans_nest_lowering_and_backend():
    tr = Tracer()
    led = CompileLedger(tracer=tr)
    led.compile(_jit_sort(), _structs(), program="t_span")
    ev = {e["name"]: e for e in tr.events()}
    assert {"compile", "lowering", "backend_compile"} <= set(ev)
    comp = ev["compile"]
    assert comp["args"]["program"] == "t_span"
    assert comp["args"]["outcome"] == "compiled"
    for child in ("lowering", "backend_compile"):
        assert (ev[child]["args"]["parent_id"]
                == comp["args"]["span_id"])
    # and the registry carries the histogram + counter families
    assert REGISTRY.sum("mrtpu_compile_total", outcome="compiled") > 0
    assert REGISTRY.value("mrtpu_compile_seconds", program="t_span",
                          stage="backend_compile") == 1


def test_cache_disabled_counted_without_cache_dir():
    assert jax.config.jax_compilation_cache_dir is None, \
        "test assumes the tier-1 process runs cache-less"
    d0 = REGISTRY.sum("mrtpu_compile_cache_disabled_total")
    CompileLedger(tracer=Tracer()).compile(
        _jit_sort(), _structs(), program="t_disabled")
    assert REGISTRY.sum("mrtpu_compile_cache_disabled_total") == d0 + 1


# -- the wrapped jit ---------------------------------------------------------


def test_wrap_jit_dispatch_and_lower_passthrough():
    led = CompileLedger(tracer=Tracer())
    calls = []
    fn = compile_obs.LedgeredJit(
        lambda x: x + 1, program="t_wrap", ledger=led)
    x = jnp.arange(8.0)
    out = fn(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) + 1)
    out2 = fn(jnp.arange(8.0))  # same sig: the stored executable
    np.testing.assert_allclose(np.asarray(out2), np.arange(8.0) + 1)
    assert led.snapshot()["programs"]["t_wrap"]["compiled"] == 1
    # .lower() passes through for HLO inspection
    txt = fn.lower(jax.ShapeDtypeStruct((8,), jnp.float32)).as_text()
    assert "module" in txt
    del calls


def test_wrap_jit_python_scalars_fall_back():
    """Non-Array leaves (python scalars carry weak types the AOT path
    would misrepresent) dispatch through plain jit, un-ledgered."""
    led = CompileLedger(tracer=Tracer())
    fn = compile_obs.LedgeredJit(lambda x, s: x * s, program="t_weak",
                                 ledger=led)
    out = fn(jnp.arange(4.0), 2.0)
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0) * 2)
    assert "t_weak" not in (led.snapshot().get("programs") or {})


# -- engine integration ------------------------------------------------------


def _tiny_wc():
    from mapreduce_tpu.engine import DeviceWordCount
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.parallel import make_mesh

    return DeviceWordCount(
        make_mesh(), chunk_len=2048,
        config=EngineConfig(local_capacity=2048, exchange_capacity=1024,
                            out_capacity=2048, tile=512, tile_records=64))


def test_engine_routes_compiles_through_ledger():
    from mapreduce_tpu.engine import DeviceWordCount
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.parallel import make_mesh

    TRACER.reset()
    # a config no other test uses: the run must pay a FRESH ledgered
    # compile (the process-wide executable cache would otherwise serve
    # an earlier test's build and record no compile span)
    wc = DeviceWordCount(
        make_mesh(), chunk_len=2048,
        config=EngineConfig(local_capacity=2560, exchange_capacity=1024,
                            out_capacity=2048, tile=512,
                            tile_records=96))
    t = {}
    counts = wc.count_bytes(b"ledger alpha beta beta " * 200, timings=t)
    assert counts[b"beta"] == 400
    names = [e["name"] for e in TRACER.events()]
    assert "compile" in names and "backend_compile" in names
    # the wave program's bucket landed in the in-process ledger with a
    # memory footprint and the engine's donation accounting
    waves = [b for b in LEDGER.buckets() if b["program"] == "wave"]
    assert waves, "wave program not in the compile ledger"
    assert waves[-1]["memory"]["total"] > 0
    assert waves[-1]["memory"]["source"] in ("measured", "analytic")
    assert "donation" in waves[-1]
    # run timings carry the footprint + donation fields
    assert t["program_memory_bytes"] > 0
    assert t["donation_saved_bytes"] >= 0


def test_second_engine_build_is_cached_with_zero_compile_seconds():
    """The satellite's contract, test-level: rebuild the SAME engine
    (map_fn + config + mesh) and the ledger serves the executable —
    outcome=cached, no new compile-seconds observation."""
    wc1 = _tiny_wc()
    c1 = wc1.count_bytes(b"twice built engine " * 150)
    cached0 = REGISTRY.sum("mrtpu_compile_total", outcome="cached")
    obs0 = REGISTRY.value("mrtpu_compile_seconds", program="wave",
                          stage="backend_compile")
    wc2 = _tiny_wc()
    c2 = wc2.count_bytes(b"twice built engine " * 150)
    assert c2 == c1
    assert REGISTRY.sum("mrtpu_compile_total",
                        outcome="cached") > cached0
    assert REGISTRY.value("mrtpu_compile_seconds", program="wave",
                          stage="backend_compile") == obs0


def test_engine_replay_info_recorded_and_replayable(cache_dir):
    """precompile records a replayable bucket (module-level map_fn,
    string reduce op) and replay_registry primes it on a fresh-built
    engine — the warmup --replay path, minus the subprocess."""
    from mapreduce_tpu.engine.device_engine import replay_registry
    from mapreduce_tpu.parallel import make_mesh

    wc = _tiny_wc()
    wc.warm()
    buckets = LEDGER.disk_buckets(cache_dir)
    replayable = [r for r in buckets.values()
                  if (r.get("replay") or {}).get("kind")
                  == "device_engine"]
    assert replayable, "no replayable wave bucket recorded"
    rep = replayable[-1]["replay"]
    assert rep["map_fn"].endswith(":_wordcount_map_fn")
    assert rep["row_shape"] == [2048 + 512]  # chunk_len + tile slack

    results = replay_registry(make_mesh(), cache_dir)
    primed = [r for r in results if "seconds" in r]
    assert primed, f"replay primed nothing: {results}"


def test_warmup_cli_replay_and_unwritable_cache(tmp_path, monkeypatch,
                                                capsys):
    import mapreduce_tpu.engine as engine_pkg
    from mapreduce_tpu import cli
    from mapreduce_tpu.engine.device_engine import EngineConfig

    # the test pins the warmup/replay/unwritable-dir plumbing, not a
    # full default-capacity compile: shrink the capacities cmd_warmup's
    # DeviceWordCount builds with (the flag/replay path is identical —
    # the replay spec records, and replays, this small config)
    real_wc = engine_pkg.DeviceWordCount

    def small_wc(mesh, chunk_len=1 << 22, config=None, **kw):
        cfg = EngineConfig(local_capacity=512, exchange_capacity=128,
                           out_capacity=512, tile=512, tile_records=64)
        return real_wc(mesh, chunk_len=chunk_len, config=cfg, **kw)

    monkeypatch.setattr(engine_pkg, "DeviceWordCount", small_wc)

    # cmd_warmup legitimately points the PROCESS-WIDE cache config (it
    # is a CLI entrypoint); the shared test process must get it back
    prev = jax.config.jax_compilation_cache_dir
    try:
        # happy path: tiny engine, explicit cache dir, --replay runs
        rc = cli.cmd_warmup(["--chunk-len", "2048",
                             "--cache-dir", str(tmp_path / "c"),
                             "--replay"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "shape registry" in out and "replay:" in out

        # no writable dir anywhere -> nonzero exit, not a log-line shrug
        monkeypatch.setattr(
            "mapreduce_tpu.utils.compile_cache.writable_dir",
            lambda path: False)
        rc = cli.cmd_warmup(["--chunk-len", "2048"])
        assert rc == 1
        assert "not writable" in capsys.readouterr().err
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# -- where the cache lives (utils/compile_cache) -----------------------------


def test_cache_dir_env_var_wins_and_is_left_alone(tmp_path, monkeypatch):
    """Where $JAX_COMPILATION_CACHE_DIR is set, both enable forms keep
    the cache THERE — an explicit argument loses, jax.config ends up
    equal to the variable, the variable itself is never rewritten —
    and the shape registry lands in the same directory."""
    from mapreduce_tpu.utils import compile_cache

    d = str(tmp_path / "placed")
    os.makedirs(d)
    monkeypatch.setenv(compile_cache.ENV_VAR, d)
    prev = jax.config.jax_compilation_cache_dir
    try:
        for enable in (compile_cache.enable_persistent_cache,
                       compile_cache.enable_persistent_cache_lazy):
            jax.config.update("jax_compilation_cache_dir", None)
            assert enable() == d
            assert enable(str(tmp_path / "elsewhere")) == d
            assert jax.config.jax_compilation_cache_dir == d
            assert os.environ[compile_cache.ENV_VAR] == d
        CompileLedger(tracer=Tracer()).compile(
            _jit_sort(), _structs(), program="t_placed")
        assert os.path.exists(compile_obs.registry_path())
        assert os.path.dirname(compile_obs.registry_path()) == d
        assert not os.path.exists(str(tmp_path / "elsewhere"))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_dir_unset_is_the_checkout_default(tmp_path, monkeypatch):
    """Unset, the cache is <checkout>/.jax_cache; an explicit directory
    (warmup --cache-dir, these tests) applies only then."""
    from mapreduce_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert (compile_cache.enable_persistent_cache()
                == compile_cache.DEFAULT_DIR)
        explicit = str(tmp_path / "explicit")
        assert compile_cache.enable_persistent_cache(explicit) == explicit
        assert jax.config.jax_compilation_cache_dir == explicit
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_files_land_where_the_variable_says(tmp_path):
    """A fresh process started with the variable set — the jax-free
    lazy form first, as the CLI entry points do — writes its XLA cache
    entries and the shape registry under it, and nothing new under the
    checkout default."""
    import subprocess
    import sys

    from mapreduce_tpu.utils import compile_cache

    d = str(tmp_path / "placed")
    repo = os.path.dirname(compile_cache.DEFAULT_DIR)
    default_before = (set(os.listdir(compile_cache.DEFAULT_DIR))
                      if os.path.isdir(compile_cache.DEFAULT_DIR) else None)
    code = """
import sys
from mapreduce_tpu.utils.compile_cache import enable_persistent_cache_lazy
assert "jax" not in sys.modules
path = enable_persistent_cache_lazy()
import jax, jax.numpy as jnp
from mapreduce_tpu.obs.compile import wrap_jit
wrap_jit(lambda x: jnp.sort(x * 2.0), program="t_env")(jnp.arange(256.0))
assert jax.config.jax_compilation_cache_dir == path, path
print(path)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=d,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                          capture_output=True, text=True, timeout=90)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == d
    files = set(os.listdir(d))
    assert compile_obs.REGISTRY_BASENAME in files
    assert files - {compile_obs.REGISTRY_BASENAME}, files
    default_after = (set(os.listdir(compile_cache.DEFAULT_DIR))
                     if os.path.isdir(compile_cache.DEFAULT_DIR) else None)
    assert default_after == default_before


# -- bundles -----------------------------------------------------------------


def test_bundle_carries_compile_ledger(tmp_path):
    wc = _tiny_wc()
    wc.count_bytes(b"bundle ledger words words " * 100)
    out = obs_profile.write_bundle(str(tmp_path / "b"))
    loaded = obs_profile.load_bundle(out)
    doc = loaded["compile_ledger"]
    assert doc["kind"] == "mrtpu-compile-ledger"
    progs = {b["program"] for b in doc["buckets"]}
    assert "wave" in progs
    (wave,) = [b for b in doc["buckets"] if b["program"] == "wave"
               and b["avals"][0]["shape"][1:] == [2048 + 512]][-1:]
    assert wave["memory"]["total"] > 0
    assert "compile_ledger.json" in loaded["manifest"]["files"]
    # corrupting it fails the reload loudly
    with open(os.path.join(out, "compile_ledger.json"), "w") as f:
        json.dump({"kind": "mrtpu-compile-ledger",
                   "buckets": [{"program": "x"}]}, f)
    with pytest.raises(ValueError):
        obs_profile.load_bundle(out)


# -- capacity-retry forensics ------------------------------------------------


def test_capacity_retry_emits_forensics_event(tmp_path):
    """An under-sized engine retries; the retry must leave ONE
    structured capacity_retry event carrying the memory breakdown, the
    diagnose CLI must turn it into a note, and a bundle must carry it
    through load_bundle."""
    from mapreduce_tpu.engine import DeviceWordCount
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.obs import analysis
    from mapreduce_tpu.parallel import make_mesh

    TRACER.reset()
    r0 = REGISTRY.sum("mrtpu_device_capacity_retry_events_total")
    # out_capacity 64 cannot hold this vocabulary: guaranteed retry
    wc = DeviceWordCount(
        make_mesh(), chunk_len=2048,
        config=EngineConfig(local_capacity=256, exchange_capacity=128,
                            out_capacity=64, tile=512, tile_records=64))
    words = b" ".join(b"w%04d" % i for i in range(600))
    counts = wc.count_bytes(words)
    assert len(counts) == 600
    assert REGISTRY.sum("mrtpu_device_capacity_retry_events_total") > r0

    events = [e for e in TRACER.events()
              if e["name"] == "capacity_retry"]
    assert events, "no capacity_retry forensics event"
    args = events[0]["args"]
    assert args["bound"] in ("hbm", "capacity")
    assert args["overflow_rows"] > 0
    assert args["program_memory"]["total"] > 0
    assert (args["new_capacities"]["out_capacity"]
            > args["old_capacities"]["out_capacity"])

    # diagnose over a clusterz-shaped doc names the retry
    doc = TRACER.chrome_trace()
    report = analysis.diagnose(doc)
    retries = report["memory"]["capacity_retries"]
    assert retries and retries[0]["overflow_rows"] > 0
    assert any("capacity retry" in n for n in report["notes"])
    rendered = analysis.render_diagnosis(report)
    assert "capacity retry" in rendered

    # and the acceptance bundle: compile spans + shape buckets +
    # footprints + the forensics event, re-validated by load_bundle
    out = obs_profile.write_bundle(str(tmp_path / "forensics"))
    loaded = obs_profile.load_bundle(out)
    names = {e["name"] for e in loaded["trace"]["traceEvents"]}
    assert {"compile", "capacity_retry"} <= names
    assert loaded["compile_ledger"]["buckets"]


# -- diagnose compile hotspots ----------------------------------------------


def test_diagnose_compile_hotspots_from_spans_and_metrics():
    from mapreduce_tpu.obs import analysis

    doc = {
        "traceEvents": [
            # three spans for one program: a span-only document (an
            # offline bundle predating the metrics) must aggregate ALL
            # of them, not stop at the first
            {"name": "compile", "ph": "X", "ts": 0.0, "dur": 7.5e6,
             "pid": 1, "tid": 1,
             "args": {"program": "wave", "outcome": "compiled"}},
            {"name": "compile", "ph": "X", "ts": 8e6, "dur": 2.5e6,
             "pid": 1, "tid": 1,
             "args": {"program": "wave", "outcome": "compiled"}},
            {"name": "compile", "ph": "X", "ts": 11e6, "dur": 5.0e6,
             "pid": 1, "tid": 1,
             "args": {"program": "wave", "outcome": "compiled"}},
        ],
        "mrtpuCluster": {"metrics": [
            ["mrtpu_compile_seconds_sum",
             {"program": "mlp_epoch", "stage": "backend_compile"}, 2.0],
            ["mrtpu_compile_seconds_count",
             {"program": "mlp_epoch", "stage": "backend_compile"}, 2.0],
        ]},
    }
    report = analysis.diagnose(doc)
    hot = report["compile_hotspots"]
    assert [h["program"] for h in hot] == ["wave", "mlp_epoch"]
    assert hot[0]["total_s"] == 15.0
    assert hot[0]["compiles"] == 3
    assert hot[0]["max_s"] == 7.5
    assert any("compile hotspot" in n for n in report["notes"])
    assert "compile hotspots" in analysis.render_diagnosis(report)


def test_diagnose_hbm_bound_note_survives_missing_footprint():
    """A retry the ENGINE classified bound=hbm must never render as
    "HBM had headroom" just because the program footprint or device
    limit went unrecorded."""
    from mapreduce_tpu.obs import analysis

    doc = {"traceEvents": [
        {"name": "capacity_retry", "ph": "X", "ts": 0.0, "dur": 1.0,
         "pid": 1, "tid": 1,
         "args": {"task": "t", "attempt": 0, "overflow_rows": 5,
                  "bound": "hbm", "program_memory": None,
                  "device_memory": {}, "new_capacities": {}}}]}
    report = analysis.diagnose(doc)
    notes = [n for n in report["notes"] if "capacity retry" in n]
    assert notes and "HBM-bound" in notes[0]
    assert "had headroom" not in notes[0]


# -- statusz / status CLI ----------------------------------------------------


def test_statusz_and_status_cli_render_compile_section():
    from mapreduce_tpu.cli import render_status
    from mapreduce_tpu.coord.docstore import MemoryDocStore
    from mapreduce_tpu.obs.statusz import cluster_status

    _tiny_wc().count_bytes(b"statusz compile section " * 50)
    snap = cluster_status(MemoryDocStore())
    assert snap["compile"]["programs"]["wave"]["buckets"] >= 1
    out = render_status(snap)
    assert "compile ledger" in out
    assert "wave:" in out


# -- cold/warm probe machinery (subprocess; slow) ----------------------------


@pytest.mark.slow
def test_measure_cold_warm_probes(tmp_path):
    """The bench's fresh-process cold/warm measurement: the first probe
    against an empty cache compiles, the second is a persistent-cache
    hit and measurably cheaper.  (The < 0.2 ratio is asserted only at
    full bench scale, where backend compile dwarfs lowering.)"""
    import bench

    out = bench.measure_cold_warm(smoke=True)
    assert out["cold_outcome"] == "compiled"
    assert out["warm_outcome"] == "persistent_hit"
    assert 0 < out["warm_start_s"] < out["cold_compile_s"]
