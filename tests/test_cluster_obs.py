"""Cluster telemetry plane unit coverage (PR 6): clock alignment,
push loss-tolerance, the merged /clusterz timeline, per-task roll-ups,
diagnosis (stragglers / skew / hotspots), build info, and the
flight recorder."""

import json
import os
import signal
import subprocess
import sys
import time
import uuid

import pytest

from mapreduce_tpu import spec
from mapreduce_tpu.coord.docserver import DocServer, HttpDocStore
from mapreduce_tpu.obs import analysis
from mapreduce_tpu.obs.collector import (
    PROC_ID, Collector, TelemetryPusher)
from mapreduce_tpu.obs.metrics import REGISTRY, parse_prometheus
from mapreduce_tpu.obs.profile import validate_trace
from mapreduce_tpu.obs.trace import TRACER, Tracer
from mapreduce_tpu.server import Server
from mapreduce_tpu.worker import spawn_worker_threads
from tests.cli_workers import child_env, cli_workers


@pytest.fixture(autouse=True)
def fresh_modules():
    spec.clear_caches()
    yield
    spec.clear_caches()


# -- clock alignment ---------------------------------------------------------

def test_clock_alignment_converges_under_cross_process_offsets():
    """Simulated processes whose monotonic clocks differ by minutes must
    land on the collector's timebase within 10ms (the min-delta estimate
    keeps the luckiest push's one-way delay as its only error)."""
    col = Collector()
    true_offset = 123.456  # collector mono - sender mono, seconds
    base = 5000.0          # the sender's monotonic clock
    # pushes arrive with varying network delays; the smallest (4ms)
    # bounds the alignment error
    for i, delay in enumerate((0.050, 0.004, 0.020)):
        t_send = base + i
        col.push({"proc": "simproc", "role": "worker:sim",
                  "t_mono": t_send,
                  "spans": [{"name": "job", "ph": "X",
                             "ts": round(t_send * 1e6, 1), "dur": 1000.0,
                             "pid": 7, "tid": 1,
                             "args": {"worker": "sim"}}],
                  "metrics": ""},
                 received_mono=t_send + true_offset + delay)
    # empty local tracer: the process-global ring holds earlier tests'
    # job spans, and this assertion filters by span name
    doc = col.cluster_doc(tracer=Tracer())
    validate_trace(doc)
    est = doc["mrtpuCluster"]["procs"]["simproc"]["offset_s"]
    assert abs(est - true_offset) < 0.010, est
    # the merged span landed on the collector timebase: its aligned ts
    # equals its sender-clock ts + the estimated offset
    jobs = [e for e in doc["traceEvents"] if e.get("name") == "job"]
    assert jobs
    for e in jobs:
        sender_ts_s = e["ts"] / 1e6 - est
        assert base - 0.001 <= sender_ts_s <= base + 3.0


def test_clock_alignment_survives_wall_clock_step(monkeypatch):
    """The NTP-survival pattern of tests/test_stats.py: alignment is
    monotonic-only, so stepping the WALL clock between pushes must not
    move the estimate at all."""
    from mapreduce_tpu.coord import docstore

    col = Collector()
    true_offset = -42.0
    col.push({"proc": "p", "role": "w", "t_mono": 100.0, "spans": [],
              "metrics": ""}, received_mono=100.0 + true_offset + 0.002)
    before = col.cluster_doc()["mrtpuCluster"]["procs"]["p"]["offset_s"]

    step = {"offset": 0.0}
    base_now = docstore.now

    def stepped_now():
        return base_now() + step["offset"]

    monkeypatch.setattr(docstore, "now", stepped_now)
    step["offset"] = -3600.0  # the wall clock jumps back an hour
    col.push({"proc": "p", "role": "w", "t_mono": 101.0, "spans": [],
              "metrics": ""}, received_mono=101.0 + true_offset + 0.005)
    after = col.cluster_doc()["mrtpuCluster"]["procs"]["p"]["offset_s"]
    assert after == before
    assert abs(after - true_offset) < 0.010


# -- pusher ------------------------------------------------------------------

def test_pusher_delivers_and_self_push_never_duplicates():
    """A flush lands local spans at the collector; the merged timeline
    shows spans from the local ring AND a remote proc, and a process
    pushing to its OWN collector appears exactly once."""
    srv = DocServer().start_background()
    marker = f"clusobs-{uuid.uuid4().hex[:8]}"
    try:
        with TRACER.span(marker):
            pass
        pusher = TelemetryPusher(f"{srv.host}:{srv.port}",
                                 role="self", interval=60)
        assert pusher.flush()
        pusher.stop(flush=False)
        # a genuinely remote proc
        srv.collector.push({"proc": "remote-1", "role": "worker:r1",
                            "t_mono": time.monotonic(),
                            "spans": [{"name": "remote-span", "ph": "X",
                                       "ts": 1.0, "dur": 2.0, "pid": 9,
                                       "tid": 1, "args": {}}],
                            "metrics": ""})
        store = HttpDocStore(f"{srv.host}:{srv.port}")
        try:
            doc = store.clusterz()
        finally:
            store.close()
        validate_trace(doc)
        names = [e.get("name") for e in doc["traceEvents"]]
        assert names.count(marker) == 1  # self-push did not duplicate
        assert "remote-span" in names
        procs = doc["mrtpuCluster"]["procs"]
        assert PROC_ID in procs and "remote-1" in procs
        # distinct Perfetto tracks
        assert (procs[PROC_ID]["track_pid"]
                != procs["remote-1"]["track_pid"])
    finally:
        srv.shutdown()


def test_pusher_loss_is_counted_never_raised():
    """A dead collector: flush returns False (no exception), the bounded
    backlog overflow and the shutdown leftovers are counted in
    mrtpu_telemetry_dropped_total."""
    d0 = REGISTRY.sum("mrtpu_telemetry_dropped_total")
    # 127.0.0.1:1 refuses instantly; tiny backlog forces overflow
    pusher = TelemetryPusher("127.0.0.1:1", role="lossy", interval=60,
                             max_backlog=5)
    for i in range(12):
        with TRACER.span(f"lossy-span-{i}"):
            pass
    assert pusher.flush() is False
    assert pusher.flush() is False  # breaker may be open now: still False
    assert (REGISTRY.value("mrtpu_telemetry_dropped_total",
                           reason="backlog") > 0)
    pusher.stop()  # final flush fails too -> leftovers counted
    assert (REGISTRY.value("mrtpu_telemetry_dropped_total",
                           reason="shutdown") > 0)
    assert REGISTRY.sum("mrtpu_telemetry_dropped_total") - d0 >= 12 - 5


def test_collector_ingest_is_idempotent_across_resends():
    """A batch whose ack was lost is re-sent byte-identical (transport
    retry) and again by the next interval's flush (backlog kept): the
    seq-stamped ingest must not duplicate spans, and the cumulative
    'missed' report must not double-count."""
    col = Collector()

    def batch(seqs, missed):
        return {"proc": "p", "role": "w", "t_mono": 1.0,
                "spans": [{"name": f"s{s}", "ph": "X", "ts": 1.0,
                           "dur": 1.0, "pid": 1, "tid": 1}
                          for s in seqs],
                "span_seqs": list(seqs), "missed": missed,
                "metrics": ""}

    col.push(batch([1, 2, 3], missed=4), received_mono=2.0)
    col.push(batch([1, 2, 3], missed=4), received_mono=2.1)  # re-send
    # next interval: backlog grew by one span, still carrying the old
    col.push(batch([1, 2, 3, 4], missed=4), received_mono=2.2)
    doc = col.cluster_doc(tracer=Tracer())
    names = [e["name"] for e in doc["traceEvents"]
             if e.get("ph") == "X"]
    assert sorted(names) == ["s1", "s2", "s3", "s4"]
    assert doc["mrtpuCluster"]["procs"]["p"]["missed"] == 4


def test_acquire_pusher_is_shared_per_process():
    """N workers in one process lease ONE pusher per collector address
    (a pusher per worker would deliver the shared span ring N times);
    the last release stops it."""
    from mapreduce_tpu.obs.collector import (
        acquire_pusher, release_pusher)

    srv = DocServer().start_background()
    addr = f"{srv.host}:{srv.port}"
    try:
        a = acquire_pusher(addr, None, role="worker:w0", interval=60)
        b = acquire_pusher(addr, None, role="worker:w1", interval=60)
        assert a is not None and b is a  # one lease, refcounted
        assert a.pusher is b.pusher
        release_pusher(b)
        assert a.pusher._thread is not None  # still running
        release_pusher(a)
        assert a.pusher._thread is None      # last release stopped it
        # disabled / unreachable configs yield None, never raise
        assert acquire_pusher(addr, None, role="x", interval=0) is None
        assert acquire_pusher(None, None, role="x", interval=1) is None
    finally:
        srv.shutdown()


def test_collector_tolerates_garbage_payloads():
    """Partial garbage degrades, never raises: bad metrics keep the
    previous snapshot, non-dict spans are skipped, and the HTTP sink
    answers 400 to non-JSON without killing the handler."""
    col = Collector()
    col.push({"proc": "g", "role": "w", "t_mono": 1.0,
              "spans": [{"name": "ok", "ph": "X", "ts": 1.0, "dur": 1.0,
                         "pid": 1, "tid": 1}],
              "metrics": "mrtpu_task_records_total{task=\"t\"} 5\n"})
    col.push({"proc": "g", "role": "w", "t_mono": "NaNsense",
              "spans": ["not-a-dict", 42],
              "metrics": "¡¡not prometheus at all"})
    doc = col.cluster_doc()
    validate_trace(doc)
    assert doc["mrtpuCluster"]["tasks"]["t"]["records"] == 5

    srv = DocServer().start_background()
    try:
        from mapreduce_tpu.utils.httpclient import KeepAliveClient

        c = KeepAliveClient(srv.host, srv.port)
        status, _ = c.request("POST", "/telemetry", body=b"}{not json")
        assert status == 400
        status, _ = c.request("POST", "/telemetry", body=b"[1,2,3]")
        assert status == 400
        c.close()
    finally:
        srv.shutdown()


def test_clusterz_is_auth_gated():
    token = uuid.uuid4().hex
    srv = DocServer(auth_token=token).start_background()
    try:
        bad = HttpDocStore(f"{srv.host}:{srv.port}", auth_token="wrong")
        with pytest.raises(PermissionError):
            bad.clusterz()
        bad.close()
        good = HttpDocStore(f"{srv.host}:{srv.port}", auth_token=token)
        assert "traceEvents" in good.clusterz()
        good.close()
    finally:
        srv.shutdown()


# -- per-task roll-ups / statusz --------------------------------------------

def test_per_task_rollups_reach_statusz(tmp_path):
    files = []
    for i in range(3):
        p = tmp_path / f"f{i}.txt"
        p.write_text("alpha beta gamma alpha\n" * 5)
        files.append(str(p))
    srv = DocServer().start_background()
    connstr = f"http://{srv.host}:{srv.port}"
    try:
        m = "mapreduce_tpu.examples.wordcount"
        params = {r: m for r in ("taskfn", "mapfn", "partitionfn",
                                 "reducefn", "finalfn")}
        params["storage"] = f"mem:{uuid.uuid4().hex}"
        params["init_args"] = {"files": files, "num_reducers": 3}
        threads = spawn_worker_threads(connstr, "rollup", 2)
        server = Server(connstr, "rollup")
        server.configure(params)
        server.loop()
        for t in threads:
            t.join(timeout=30)
        store = HttpDocStore(f"{srv.host}:{srv.port}")
        try:
            snap = store.statusz()
        finally:
            store.close()
        # build identity rendered on every snapshot
        assert snap["build"]["version"]
        assert snap["build"]["python"]
        # the collector's per-task accounting section
        tasks = snap["telemetry"]["tasks"]
        assert tasks["rollup"]["records"] > 0
        assert tasks["rollup"]["bytes"] > 0
        # worker metrics carry the task label
        assert REGISTRY.sum("mrtpu_worker_jobs_total", task="rollup",
                            outcome="written") > 0
        assert REGISTRY.sum("mrtpu_task_records_total", task="rollup",
                            phase="map") > 0
        assert REGISTRY.sum("mrtpu_partition_records_total",
                            task="rollup") > 0
    finally:
        srv.shutdown()


def test_build_info_gauge_renders():
    from mapreduce_tpu.obs.buildinfo import build_info

    info = build_info(refresh=True)
    assert info["version"] and info["python"]
    assert "jax" in info and "backend" in info
    parsed = parse_prometheus(REGISTRY.render())
    rows = [(lk, v) for (name, lk), v in parsed.items()
            if name == "mrtpu_build_info"]
    assert len(rows) == 1 and rows[0][1] == 1.0
    labels = dict(rows[0][0])
    assert labels["version"] == info["version"]


# -- diagnosis ---------------------------------------------------------------

def _job_event(worker, dur_s, ts_s=1.0):
    return {"name": "job", "ph": "X", "ts": round(ts_s * 1e6, 1),
            "dur": round(dur_s * 1e6, 1), "pid": 1, "tid": 1,
            "args": {"worker": worker, "phase": "map"}}


def _synthetic_doc():
    events = [_job_event("w_fast", 0.02, ts_s=1.0 + i) for i in range(6)]
    events += [_job_event("w_slow", 0.40, ts_s=8.0 + i) for i in range(3)]
    events.append({"name": "claim", "ph": "X", "ts": 1e6, "dur": 5e3,
                   "pid": 1, "tid": 1, "args": {"worker": "w_fast"}})
    events.append({"name": "write", "ph": "X", "ts": 2e6, "dur": 8e3,
                   "pid": 1, "tid": 1, "args": {"worker": "w_fast"}})
    metrics = [
        ["mrtpu_partition_records_total",
         {"task": "t", "phase": "map", "partition": "P00000"}, 900],
        ["mrtpu_partition_records_total",
         {"task": "t", "phase": "map", "partition": "P00001"}, 60],
        ["mrtpu_partition_records_total",
         {"task": "t", "phase": "map", "partition": "P00002"}, 40],
        ["mrtpu_http_retries_total", {"endpoint": "h:1"}, 7],
        ["mrtpu_worker_jobs_total",
         {"worker": "w_fast", "outcome": "broken"}, 2],
    ]
    return {"traceEvents": events,
            "mrtpuCluster": {"aligned_to": "self", "procs": {},
                             "tasks": {}, "metrics": metrics}}


def test_diagnose_names_straggler_and_skewed_partition():
    rep = analysis.diagnose(_synthetic_doc())
    assert [s["worker"] for s in rep["stragglers"]] == ["w_slow"]
    assert rep["stragglers"][0]["ratio"] > 5
    skew = rep["skew"]
    assert [s["partition"] for s in skew] == ["P00000"]
    assert skew[0]["share"] == 0.9
    hot = {(h["metric"], tuple(sorted(h["labels"].items())))
           for h in rep["hotspots"]}
    assert ("mrtpu_http_retries_total", (("endpoint", "h:1"),)) in hot
    assert rep["phases"]["claim_s"] > 0
    assert rep["phases"]["run_s"] == 0.0
    text = analysis.render_diagnosis(rep)
    assert "w_slow" in text and "P00000" in text
    assert "w_fast" in text  # per-worker stats still listed


def test_diagnose_clean_run_flags_nothing():
    doc = {"traceEvents": [_job_event("a", 0.02 + 0.001 * i)
                           for i in range(4)]
           + [_job_event("b", 0.021 + 0.001 * i) for i in range(4)],
           "mrtpuCluster": {"procs": {}, "tasks": {}, "metrics": [
               ["mrtpu_partition_records_total",
                {"task": "t", "partition": "P00000"}, 50],
               ["mrtpu_partition_records_total",
                {"task": "t", "partition": "P00001"}, 55]]}}
    rep = analysis.diagnose(doc)
    assert rep["stragglers"] == []
    assert rep["skew"] == []
    assert rep["hotspots"] == []


def test_diagnose_falls_back_to_job_seconds_metrics():
    """Job spans lost to telemetry drops: the straggler test runs on the
    aggregated job-seconds histogram instead, and says so."""
    doc = {"traceEvents": [],
           "mrtpuCluster": {"procs": {}, "tasks": {}, "metrics": [
               ["mrtpu_worker_job_seconds_sum", {"worker": "a"}, 0.10],
               ["mrtpu_worker_job_seconds_count", {"worker": "a"}, 5],
               ["mrtpu_worker_job_seconds_sum", {"worker": "b"}, 4.0],
               ["mrtpu_worker_job_seconds_count", {"worker": "b"}, 5]]}}
    rep = analysis.diagnose(doc)
    assert rep["latency_source"] == "metrics"
    assert [s["worker"] for s in rep["stragglers"]] == ["b"]
    assert any("lost" in n for n in rep["notes"])


# -- flight recorder ---------------------------------------------------------

def test_flight_recorder_dumps_on_sigterm(tmp_path):
    """A SIGTERM'd worker must leave its telemetry behind: the flight
    trace parses as a Chrome trace, the metrics snapshot parses as
    Prometheus text, and the exit code is the conventional 143."""
    trace_out = tmp_path / "w.trace.json"
    # the worker logs its start at INFO before entering the poll loop;
    # SIGTERM before that could beat the handler install
    with cli_workers(f"dir://{tmp_path}/board", "flightdb", 1,
                     args=[("--trace-out", str(trace_out))]) as workers:
        time.sleep(0.2)
        workers.procs[0].send_signal(signal.SIGTERM)
    assert workers.rcs == [143], workers.tails()
    flight_trace = str(trace_out) + ".flight.trace.json"
    flight_metrics = str(trace_out) + ".flight.metrics.prom"
    assert os.path.exists(flight_trace), "flight trace missing"
    assert os.path.exists(flight_metrics), "flight metrics missing"
    with open(flight_trace, encoding="utf-8") as f:
        validate_trace(json.load(f))
    with open(flight_metrics, encoding="utf-8") as f:
        text = f.read()
    parse_prometheus(text)  # the snapshot is valid exposition text
    # the worker's instruments were registered (an idle worker may have
    # no samples yet, but the family headers prove whose registry it is)
    assert "mrtpu_worker_claims_total" in text


def test_flight_recorder_silent_on_normal_exit(tmp_path):
    """A normal exit exports --trace-out and DISARMS the recorder: the
    flight files' absence is what makes their presence a signal."""
    trace_out = tmp_path / "n.trace.json"
    proc = subprocess.run(
        [sys.executable, "-m", "mapreduce_tpu.cli", "worker",
         f"dir://{tmp_path}/board", "flightdb",
         "--max-iter", "1", "--trace-out", str(trace_out)],
        env=child_env(), capture_output=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-500:]
    assert os.path.exists(trace_out)
    assert not os.path.exists(str(trace_out) + ".flight.trace.json")
    assert not os.path.exists(str(trace_out) + ".flight.metrics.prom")
