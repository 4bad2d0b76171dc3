"""Device-plane profiling tests: DEVICE_BUCKETS preset, tracer ring +
detached spans, cost model (XLA cost_analysis + analytic fallback),
MFU/roofline gauges, profile-bundle round-trip through the strict
parsers, wave-span nesting under the owning job span, the /tracez
endpoint + profile CLI, and the bench regression gate (fails on an
injected 2x synthetic slowdown, passes within tolerance)."""

import json
import uuid

import pytest

from mapreduce_tpu import spec
from mapreduce_tpu.obs import benchgate
from mapreduce_tpu.obs import profile as obs_profile
from mapreduce_tpu.obs.metrics import (
    DEVICE_BUCKETS, LATENCY_BUCKETS, REGISTRY, parse_prometheus)
from mapreduce_tpu.obs.trace import TRACER, Tracer


@pytest.fixture(autouse=True)
def fresh_modules():
    spec.clear_caches()
    yield
    spec.clear_caches()


# -- DEVICE_BUCKETS preset ---------------------------------------------------


def test_device_buckets_resolve_microseconds():
    """The preset exists because LATENCY_BUCKETS' 1ms floor collapses
    sub-millisecond device waves into one bucket."""
    assert DEVICE_BUCKETS[0] <= 1e-5
    assert sum(1 for b in DEVICE_BUCKETS if b < 1e-3) >= 4
    assert list(DEVICE_BUCKETS) == sorted(DEVICE_BUCKETS)
    assert DEVICE_BUCKETS[-1] == float("inf")
    assert DEVICE_BUCKETS[0] < LATENCY_BUCKETS[0]


def test_engine_wave_histogram_uses_device_buckets():
    from mapreduce_tpu.engine import device_engine as de

    assert de._WAVE_SECONDS.buckets == tuple(sorted(DEVICE_BUCKETS))


# -- tracer ring + detached spans --------------------------------------------


def test_tracer_ring_drops_oldest_and_counts():
    tr = Tracer(max_events=3)
    d0 = REGISTRY.value("mrtpu_trace_dropped_total")
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    names = [e["name"] for e in tr.events()]
    # ring semantics: the NEWEST spans survive, the oldest are evicted
    assert names == ["s2", "s3", "s4"]
    assert REGISTRY.value("mrtpu_trace_dropped_total") - d0 == 2


def test_detached_spans_parent_explicitly():
    tr = Tracer()
    root = tr.begin("root")
    child = tr.begin("child", parent=root)
    tr.end(child)
    tr.end(root, outcome="done")
    ev = {e["name"]: e for e in tr.events()}
    assert ev["child"]["args"]["trace_id"] == ev["root"]["args"]["trace_id"]
    assert ev["child"]["args"]["parent_id"] == ev["root"]["args"]["span_id"]
    assert ev["root"]["args"]["outcome"] == "done"
    # without an explicit parent, begin() adopts the thread's current span
    with tr.span("lexical") as lex:
        loose = tr.begin("loose")
    tr.end(loose)
    loose_ev = tr.events()[-1]
    assert loose_ev["args"]["parent_id"] == lex.span_id


# -- cost model --------------------------------------------------------------


def test_analytic_costs_positive_and_monotone():
    small = obs_profile.analytic_costs(1 << 16, 1 << 10, 16)
    big = obs_profile.analytic_costs(1 << 20, 1 << 16, 16)
    assert small["flops"] > 0 and small["bytes"] > 0
    assert big["flops"] > small["flops"]
    assert big["bytes"] >= (1 << 20)  # at least the input read


def test_program_costs_normalizes_cost_analysis():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x * 2.0))
    compiled = f.lower(
        jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
    costs = obs_profile.program_costs(compiled)
    if costs is None:
        pytest.skip("backend exposes no cost model")
    assert costs["flops"] > 0

    class NoCost:
        def cost_analysis(self):
            raise NotImplementedError

    assert obs_profile.program_costs(NoCost()) is None


def test_device_peaks_env_override(monkeypatch):
    monkeypatch.setenv("MAPREDUCE_TPU_PEAK_FLOPS", "123.0")
    p = obs_profile.device_peaks()
    assert p["flops_per_s"] == 123.0
    assert p["peak_source"] == "env"


def test_device_peaks_unknown_accelerator_is_an_error():
    """One table keyed by device_kind: a known kind answers with its
    source, the CPU keeps its nominal row, and an accelerator the table
    does not know raises instead of borrowing somebody else's peak."""
    from types import SimpleNamespace as Dev

    v5e = obs_profile.device_peaks(Dev(device_kind="TPU v5 lite",
                                       platform="tpu"))
    assert v5e["peak_source"] == "kind:v5" and v5e["flops_per_s"] == 197e12
    cpu = obs_profile.device_peaks(Dev(device_kind="cpu", platform="cpu"))
    assert cpu["peak_source"] == "platform:cpu"
    for kind, platform in (("TPU v9x", "tpu"), ("Mystery GPU", "gpu")):
        with pytest.raises(ValueError, match="no peak"):
            obs_profile.device_peaks(Dev(device_kind=kind,
                                         platform=platform))


def _tiny_wc():
    from mapreduce_tpu.engine import DeviceWordCount
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.parallel import make_mesh

    return DeviceWordCount(
        make_mesh(), chunk_len=2048,
        config=EngineConfig(local_capacity=2048, exchange_capacity=1024,
                            out_capacity=2048, tile=512, tile_records=64))


def test_engine_records_flops_and_mfu():
    """A device run must publish flops/bytes counters and derive MFU —
    and fold the same numbers into its timings dict so the stats doc
    and /statusz per-task stats carry them."""
    wc = _tiny_wc()
    f0 = REGISTRY.sum("mrtpu_device_flops_total")
    h0 = REGISTRY.value("mrtpu_device_wave_seconds", stage="compute")
    t = {}
    counts = wc.count_bytes(b"alpha beta beta gamma " * 300, timings=t)
    assert counts[b"beta"] == 600
    assert t["flops"] > 0
    assert t["cost_source"] in ("measured", "analytic")
    assert t.get("mfu", 0.0) >= 0.0
    assert REGISTRY.sum("mrtpu_device_flops_total") > f0
    # per-wave stage histogram observed on DEVICE_BUCKETS
    assert REGISTRY.value("mrtpu_device_wave_seconds",
                          stage="compute") > h0
    snap = obs_profile.device_snapshot()
    assert snap["flops_total"] > 0
    assert snap["waves"] >= 1


def test_cost_model_analytic_fallback(monkeypatch):
    """Backends without cost_analysis (the satellite's CPU-tier concern)
    must still produce nonzero flops via the analytic estimate."""
    from mapreduce_tpu.engine import device_engine as de

    monkeypatch.setattr(de._profile, "program_costs",
                        lambda compiled: None)
    a0 = REGISTRY.sum("mrtpu_device_flops_total", source="analytic")
    wc = _tiny_wc()
    t = {}
    wc.count_bytes(b"fall back to analytic " * 200, timings=t)
    assert t["cost_source"] == "analytic"
    assert t["flops"] > 0
    assert REGISTRY.sum("mrtpu_device_flops_total",
                        source="analytic") > a0


# -- wave-span nesting (acceptance) ------------------------------------------


def _contains(outer, inner, slack=0.5):
    """Time containment with half-a-microsecond slack: ts/dur are
    INDEPENDENTLY rounded to 0.1µs on a monotonic base that can sit at
    ~1e12µs (where float64 itself only resolves ~0.25µs), so an inner
    span closed at the same instant as its parent — the wave span and
    its overflow readback share one clock read — can round to an end up
    to two quanta past the parent's."""
    return (outer["ts"] <= inner["ts"] + slack
            and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + slack)


def test_wave_spans_nest_under_job_span(tmp_path):
    """The tentpole's trace criterion: a device-plane run produces
    claim -> run -> device_run -> wave ⊃ {upload, compute, readback}
    under ONE job trace, with correct parent ids and time containment
    (what Perfetto renders as nesting)."""
    from mapreduce_tpu.server import Server

    files = []
    for i in range(3):
        p = tmp_path / f"t{i}.txt"
        p.write_text(f"wave spans nest under the job span t{i}\n" * 4)
        files.append(str(p))
    TRACER.reset()
    m = "mapreduce_tpu.examples.wordcount"
    params = {r: m for r in ("taskfn", "mapfn", "partitionfn",
                             "reducefn", "finalfn")}
    params["combinerfn"] = m
    params["storage"] = f"mem:{uuid.uuid4().hex}"
    params["init_args"] = {"files": files, "num_reducers": 3,
                           "device_chunk_len": 2048}
    params["device"] = True
    server = Server(f"mem://{uuid.uuid4().hex}", "pw")
    server.configure(params)
    stats = server.loop()
    assert stats["map"]["failed"] == 0

    ev = TRACER.events()
    jobs = [e for e in ev if e["name"] == "job"
            and e["args"].get("phase") == "device"]
    assert len(jobs) == 1
    job = jobs[0]
    assert job["args"]["outcome"] == "written"
    fam = [e for e in ev
           if e["args"].get("trace_id") == job["args"]["trace_id"]]
    names = {e["name"] for e in fam}
    assert {"claim", "run", "write", "device_run", "wave",
            "upload", "compute", "readback"} <= names, sorted(names)

    by_name = {}
    for e in fam:
        by_name.setdefault(e["name"], []).append(e)
    (run,) = by_name["run"]
    assert run["args"]["parent_id"] == job["args"]["span_id"]
    dr_ids = set()
    for dr in by_name["device_run"]:
        assert dr["args"]["parent_id"] == run["args"]["span_id"]
        assert _contains(run, dr)
        dr_ids.add(dr["args"]["span_id"])
    waves = by_name["wave"]
    assert waves, "no wave spans recorded"
    for wv in waves:
        assert wv["args"]["parent_id"] in dr_ids
        kids = [e for e in fam
                if e["args"].get("parent_id") == wv["args"]["span_id"]]
        kid_names = {e["name"] for e in kids}
        assert {"upload", "compute", "readback"} <= kid_names, (
            f"wave {wv['args'].get('wave')} children: {sorted(kid_names)}")
        for k in kids:
            assert _contains(wv, k), (
                f"{k['name']} not inside its wave span")
    # the whole thing is a loadable Chrome trace
    doc = TRACER.chrome_trace()
    obs_profile.validate_trace(doc)
    json.dumps(doc)


# -- statusz / status CLI device section -------------------------------------


def test_statusz_and_render_device_section():
    from mapreduce_tpu.cli import render_status
    from mapreduce_tpu.coord.docstore import MemoryDocStore
    from mapreduce_tpu.obs.statusz import cluster_status

    obs_profile.record_run({"flops": 1e9, "bytes": 5e8,
                            "source": "analytic"},
                           waves=2, compute_s=0.5, n_dev=1)
    snap = cluster_status(MemoryDocStore())
    dev = snap["device"]
    assert dev["flops_total"] > 0
    assert dev["mfu"] > 0
    assert 0 < dev["roofline_frac"] <= 1.0 or dev["roofline_frac"] > 0
    out = render_status(snap)
    assert "device plane" in out
    assert "MFU" in out


# -- profile bundles ---------------------------------------------------------


def test_bundle_roundtrip(tmp_path):
    """write_bundle -> load_bundle: the metrics snapshot survives the
    strict Prometheus parser, the trace validates structurally, and the
    statusz carries the device section."""
    with TRACER.span("bundle-span", probe=1):
        pass
    out = obs_profile.write_bundle(str(tmp_path / "bundle"))
    loaded = obs_profile.load_bundle(out)
    assert loaded["manifest"]["kind"] == "mrtpu-profile-bundle"
    assert loaded["manifest"]["trace_events"] == len(
        loaded["trace"]["traceEvents"])
    assert any(name == "mrtpu_trace_spans_total"
               for name, _ in loaded["metrics"])
    assert "device" in loaded["statusz"]
    # a corrupted trace must fail the re-validation loudly
    with open(tmp_path / "bundle" / "trace.json", "w") as f:
        json.dump({"traceEvents": [{"name": "x"}]}, f)
    with pytest.raises(ValueError):
        obs_profile.load_bundle(out)


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        obs_profile.validate_trace({"no": "events"})
    with pytest.raises(ValueError):
        obs_profile.validate_trace(
            {"traceEvents": [{"name": "a", "ph": "B", "ts": 0,
                              "dur": 0, "pid": 1, "tid": 1}]})
    obs_profile.validate_trace(
        {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.5,
                          "dur": 2.0, "pid": 1, "tid": 1}]})


def test_tracez_endpoint_and_profile_cli(tmp_path):
    """/tracez serves the span ring (auth-gated) and the profile CLI
    captures a loadable bundle from a live docserver."""
    from mapreduce_tpu.cli import cmd_profile
    from mapreduce_tpu.coord.docserver import DocServer, HttpDocStore

    board = DocServer().start_background()
    try:
        store = HttpDocStore(f"{board.host}:{board.port}")
        store.ping()  # records an rpc span server-side
        doc = store.tracez()
        assert any(e["name"] == "rpc:ping" for e in doc["traceEvents"])
        store.close()
        out = str(tmp_path / "bundle")
        rc = cmd_profile([f"http://{board.host}:{board.port}",
                          "--out", out])
        assert rc == 0
        loaded = obs_profile.load_bundle(out)
        assert any(e["name"] == "rpc:ping"
                   for e in loaded["trace"]["traceEvents"])
    finally:
        board.shutdown()

    sec = DocServer(auth_token="sekrit").start_background()
    try:
        nosy = HttpDocStore(f"{sec.host}:{sec.port}")
        with pytest.raises(PermissionError):
            nosy.tracez()
        nosy.close()
    finally:
        sec.shutdown()


# -- regression gate ---------------------------------------------------------

_SPECS = [
    benchgate.MetricSpec("value", rel_tol=0.25, required=True),
    benchgate.MetricSpec("timings.compute_s", rel_tol=0.25),
    benchgate.MetricSpec("tput", rel_tol=0.25, direction="higher"),
]

_HISTORY = [
    {"value": 2.8, "timings": {"compute_s": 2.0}, "tput": 100.0},
    {"value": 2.9, "timings": {"compute_s": 2.1}, "tput": 110.0},
    {"value": 3.0, "timings": {"compute_s": 1.9}, "tput": 90.0},
]


def test_gate_fails_on_2x_slowdown_passes_in_tolerance():
    slow = {"value": 5.8, "timings": {"compute_s": 4.0}, "tput": 100.0}
    problems = benchgate.gate(slow, _HISTORY, _SPECS)
    assert len(problems) == 2, problems
    noisy = {"value": 3.0, "timings": {"compute_s": 2.15}, "tput": 95.0}
    assert benchgate.gate(noisy, _HISTORY, _SPECS) == []
    # higher-is-better direction: collapsed throughput is flagged
    slow_tput = {"value": 2.8, "timings": {"compute_s": 2.0},
                 "tput": 40.0}
    problems = benchgate.gate(slow_tput, _HISTORY, _SPECS)
    assert problems and "tput" in problems[0]


def test_gate_missing_metrics_semantics():
    # missing optional metric in current: skipped; missing required: fail
    cur = {"timings": {"compute_s": 2.0}, "tput": 100.0}
    problems = benchgate.gate(cur, _HISTORY, _SPECS)
    assert len(problems) == 1 and "value" in problems[0]
    # metric absent from ALL history entries: nothing to gate
    specs = _SPECS + [benchgate.MetricSpec("brand_new_metric", 0.25)]
    cur = {"value": 2.8, "timings": {"compute_s": 2.0}, "tput": 100.0,
           "brand_new_metric": 999.0}
    assert benchgate.gate(cur, _HISTORY, specs) == []


def test_gate_synthetic_entries_and_history_file(tmp_path):
    synth = benchgate.synthetic_entry(_HISTORY, _SPECS)
    assert synth["value"] == 2.9  # median
    assert synth["timings"]["compute_s"] == 2.0
    assert benchgate.gate(synth, _HISTORY, _SPECS) == []
    doubled = benchgate.synthetic_entry(_HISTORY, _SPECS, scale=2.0)
    assert benchgate.gate(doubled, _HISTORY, _SPECS)  # value+compute fail

    path = str(tmp_path / "HIST.json")
    # first run seeds (nothing to compare), second gates against it
    assert benchgate.check_and_append(path, _HISTORY[0], _SPECS) == []
    assert benchgate.check_and_append(path, _HISTORY[1], _SPECS) == []
    data, history = benchgate.load_history(path)
    assert len(history) == 2
    assert all("recorded_time" in h for h in history)
    bad = {"value": 9.9, "timings": {"compute_s": 2.0}, "tput": 100.0}
    problems = benchgate.check_and_append(path, bad, _SPECS)
    assert problems, "2x+ regression accepted into history"
    _, history = benchgate.load_history(path)
    assert len(history) == 2, "regressed run must NOT be appended"


def test_bench_check_smoke_is_tier1_safe():
    """The CI/tooling satellite: bench.py --check --smoke runs against
    the committed BENCH.json history with synthetic/registry-based
    assertions only — exercised here so the gate itself is tested on
    every tier-1 run."""
    import bench

    assert bench.check_smoke() == 0
