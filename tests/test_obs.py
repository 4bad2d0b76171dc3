"""Observability-plane unit tests: registry semantics, Prometheus
render/parse round-trip, tracer nesting + header propagation, /metrics
and /statusz exposition over a live DocServer, and the status CLI
renderer — plus the acceptance check that a full wordcount run's trace
nests claim -> run -> write under one per-job trace for every completed
job."""

import json
import threading
import uuid

import pytest

from mapreduce_tpu import spec
from mapreduce_tpu.coord.docserver import DocServer, HttpDocStore
from mapreduce_tpu.obs.metrics import (
    LATENCY_BUCKETS, REGISTRY, Registry, parse_prometheus)
from mapreduce_tpu.obs.trace import TRACE_HEADER, TRACER, Tracer
from mapreduce_tpu.obs.statusz import cluster_status


@pytest.fixture(autouse=True)
def fresh_modules():
    spec.clear_caches()
    yield
    spec.clear_caches()


# -- registry ---------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = Registry()
    c = reg.counter("t_requests_total", "requests")
    c.inc(endpoint="a:1")
    c.inc(2, endpoint="a:1")
    c.inc(endpoint="b:2")
    assert c.value(endpoint="a:1") == 3
    assert c.value(endpoint="b:2") == 1
    assert c.value(endpoint="never") == 0
    assert c.sum() == 4
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("t_depth", "queue depth")
    g.set(7, phase="map")
    g.inc(phase="map")
    assert g.value(phase="map") == 8

    h = reg.histogram("t_latency_seconds", "latency")
    for v in (0.003, 0.03, 0.3, 3.0):
        h.observe(v, op="x")
    assert h.value(op="x") == 4  # scalar read-back = observation count

    # kind mismatch on an existing name must raise, not silently alias
    with pytest.raises(TypeError):
        reg.gauge("t_requests_total")


def test_registry_reset_keeps_families_alive():
    """reset() zeroes series but keeps metric handles registered: a
    module-level instrument created at import time must keep landing in
    render() after a test reset."""
    reg = Registry()
    c = reg.counter("t_keep_total", "kept")
    c.inc()
    reg.reset()
    assert c.value() == 0
    c.inc(5)  # the SAME handle object keeps working...
    assert reg.value("t_keep_total") == 5  # ...and the registry sees it
    assert "t_keep_total" in reg.render()


def test_render_parse_roundtrip():
    reg = Registry()
    c = reg.counter("t_rt_total", "with labels")
    c.inc(3, plane='bl"ob\\x', status="503")
    g = reg.gauge("t_rt_gauge", "a gauge")
    g.set(2.5, k="v")
    h = reg.histogram("t_rt_seconds", "hist", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    # literal backslash followed by 'n' must survive the round trip
    # (single-pass unescape; sequential replaces would decode a newline)
    c.inc(1, plane="a\\nb", status="0")
    text = reg.render()
    parsed = parse_prometheus(text)
    assert parsed[("t_rt_total",
                   (("plane", 'bl"ob\\x'), ("status", "503")))] == 3
    assert parsed[("t_rt_total",
                   (("plane", "a\\nb"), ("status", "0")))] == 1
    assert parsed[("t_rt_gauge", (("k", "v"),))] == 2.5
    # histogram: cumulative buckets + sum + count, +Inf bucket == count
    assert parsed[("t_rt_seconds_bucket", (("le", "0.1"),))] == 1
    assert parsed[("t_rt_seconds_bucket", (("le", "+Inf"),))] == 2
    assert parsed[("t_rt_seconds_count", ())] == 2
    assert parsed[("t_rt_seconds_sum", ())] == pytest.approx(5.05)
    # HELP/TYPE lines present for each family
    for fam in ("t_rt_total", "t_rt_gauge", "t_rt_seconds"):
        assert f"# TYPE {fam}" in text


def test_parse_prometheus_rejects_garbage():
    with pytest.raises(ValueError):
        parse_prometheus("this is not exposition format")


def test_latency_buckets_preset_ends_in_inf():
    assert LATENCY_BUCKETS[-1] == float("inf")
    assert list(LATENCY_BUCKETS) == sorted(LATENCY_BUCKETS)


def test_thread_safety_under_contention():
    reg = Registry()
    c = reg.counter("t_contended_total", "hammered")

    def hammer():
        for _ in range(1000):
            c.inc(worker="w")

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert c.value(worker="w") == 8000


# -- tracer -----------------------------------------------------------------


def test_span_nesting_and_ids():
    tr = Tracer()
    with tr.span("outer", k="v") as outer:
        with tr.span("inner"):
            pass
        outer.args["outcome"] = "late-stamp"
    ev = {e["name"]: e for e in tr.events()}
    assert ev["inner"]["args"]["trace_id"] == ev["outer"]["args"]["trace_id"]
    assert ev["inner"]["args"]["parent_id"] == ev["outer"]["args"]["span_id"]
    assert ev["outer"]["args"]["parent_id"] is None
    assert ev["outer"]["args"]["outcome"] == "late-stamp"
    # time containment (Perfetto nests by ts/dur on one tid)
    o, i = ev["outer"], ev["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6


def test_adopt_parents_remote_context():
    tr = Tracer()
    with tr.adopt("deadbeefdeadbeef:cafecafecafecafe"):
        with tr.span("server-side"):
            pass
    (e,) = tr.events()
    assert e["args"]["trace_id"] == "deadbeefdeadbeef"
    assert e["args"]["parent_id"] == "cafecafecafecafe"
    # bad header is a no-op, not an error
    with tr.adopt("garbage"):
        with tr.span("orphan"):
            pass
    orphan = tr.events()[-1]
    assert orphan["args"]["parent_id"] is None


def test_chrome_trace_shape_and_buffer_bound():
    tr = Tracer(max_events=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    doc = tr.chrome_trace()
    assert len(doc["traceEvents"]) == 3  # bounded, drops the excess
    for e in doc["traceEvents"]:
        assert e["ph"] == "X"
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(e)
    json.dumps(doc)  # must be JSON-serializable as-is


def test_trace_header_injected_and_adopted_over_http():
    """A client span's context crosses the board plane: the rpc span the
    server records carries the caller's trace id."""
    board = DocServer().start_background()
    try:
        store = HttpDocStore(f"{board.host}:{board.port}")
        TRACER.reset()
        with TRACER.span("caller") as sp:
            store.ping()
            caller_trace = sp.trace_id
        rpc = [e for e in TRACER.events() if e["name"] == "rpc:ping"]
        assert rpc, "server side recorded no rpc span"
        assert rpc[-1]["args"]["trace_id"] == caller_trace
        store.close()
    finally:
        board.shutdown()


# -- exposition over a live server ------------------------------------------


def test_metrics_and_statusz_endpoints():
    board = DocServer().start_background()
    try:
        store = HttpDocStore(f"{board.host}:{board.port}")
        board.store.insert("db1.task", {"_id": "unique", "status": "MAP",
                                        "iteration": 2})
        board.store.insert("db1.map_jobs", {"_id": "0", "status": 0})
        store.ping()
        text = store.metrics_text()
        parsed = parse_prometheus(text)  # valid exposition
        assert any(name == "mrtpu_docserver_requests_total"
                   for name, _ in parsed)
        # scrape-time board depth gauge
        assert parsed[("mrtpu_board_jobs",
                       (("db", "db1"), ("phase", "map"),
                        ("status", "WAITING")))] == 1
        snap = store.statusz()
        assert snap["tasks"]["db1"]["status"] == "MAP"
        assert snap["tasks"]["db1"]["iteration"] == 2
        assert snap["tasks"]["db1"]["phases"]["map"] == {"WAITING": 1}
        store.close()
    finally:
        board.shutdown()


def test_exposition_respects_auth():
    board = DocServer(auth_token="sekrit").start_background()
    try:
        bad = HttpDocStore(f"{board.host}:{board.port}")
        with pytest.raises(PermissionError):
            bad.metrics_text()
        with pytest.raises(PermissionError):
            bad.statusz()
        bad.close()
        good = HttpDocStore(f"{board.host}:{board.port}",
                            auth_token="sekrit")
        assert "mrtpu" in good.metrics_text()
        good.close()
    finally:
        board.shutdown()


def test_statusz_worker_liveness(monkeypatch):
    from mapreduce_tpu.coord.docstore import MemoryDocStore
    from mapreduce_tpu.utils.constants import STATUS

    store = MemoryDocStore()
    store.insert("db.task", {"_id": "unique", "status": "MAP",
                             "iteration": 1})
    now = 1000.0
    store.insert("db.map_jobs", {"_id": "a", "worker": "w-live",
                                 "status": int(STATUS.RUNNING),
                                 "lease_expires": now + 10})
    store.insert("db.map_jobs", {"_id": "b", "worker": "w-dead",
                                 "status": int(STATUS.RUNNING),
                                 "lease_expires": now - 5})
    store.insert("db.map_jobs", {"_id": "c", "worker": "w-done",
                                 "status": int(STATUS.WRITTEN),
                                 "lease_expires": now - 60})
    snap = cluster_status(store, now=now)
    ws = snap["tasks"]["db"]["workers"]
    assert ws["w-live"]["alive"] is True
    assert ws["w-dead"]["alive"] is False
    assert ws["w-done"]["running"] == 0


# -- status CLI -------------------------------------------------------------


def test_status_cli_renders_snapshot(capsys):
    from mapreduce_tpu.cli import cmd_status

    board = DocServer().start_background()
    try:
        board.store.insert("wc.task", {"_id": "unique", "status": "REDUCE",
                                       "iteration": 3})
        board.store.insert("wc.red_jobs", {"_id": "P0", "status": 4})
        rc = cmd_status([f"http://{board.host}:{board.port}"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "[wc]" in out and "REDUCE" in out and "iteration=3" in out
        assert "WRITTEN=1" in out
        rc = cmd_status([f"http://{board.host}:{board.port}", "--json"])
        snap = json.loads(capsys.readouterr().out)
        assert snap["tasks"]["wc"]["iteration"] == 3
    finally:
        board.shutdown()


def test_render_status_empty_board():
    from mapreduce_tpu.cli import render_status

    assert "no tasks" in render_status({"tasks": {}})


# -- acceptance: trace nesting over a real run ------------------------------


def _span_contains(outer, inner):
    return (outer["ts"] <= inner["ts"] + 1e-6
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
            + 1e-6)


def test_full_run_trace_nests_claim_run_write(tmp_path):
    """Every completed job's trace must nest claim -> run -> write under
    one per-job root span (the PR's acceptance criterion), and the
    export must be valid Chrome trace JSON."""
    from mapreduce_tpu.server import Server
    from mapreduce_tpu.worker import spawn_worker_threads

    files = []
    for i in range(3):
        p = tmp_path / f"t{i}.txt"
        p.write_text(f"spans nest claim run write t{i}\n" * 3)
        files.append(str(p))
    TRACER.reset()
    connstr = f"mem://{uuid.uuid4().hex}"
    m = "mapreduce_tpu.examples.wordcount"
    params = {r: m for r in ("taskfn", "mapfn", "partitionfn",
                             "reducefn", "finalfn")}
    params["storage"] = f"mem:{uuid.uuid4().hex}"
    params["init_args"] = {"files": files, "num_reducers": 3}
    threads = spawn_worker_threads(connstr, "tr", 2)
    server = Server(connstr, "tr")
    server.configure(params)
    stats = server.loop()
    for t in threads:
        t.join(timeout=30)
    assert stats["map"]["failed"] == 0

    doc = TRACER.chrome_trace()
    json.loads(json.dumps(doc))  # valid JSON end to end
    ev = doc["traceEvents"]
    jobs = [e for e in ev if e["name"] == "job"
            and e["args"].get("outcome") == "written"]
    # every map + reduce job completed exactly once in this trace
    assert len(jobs) == stats["map"]["count"] + stats["reduce"]["count"]
    by_trace = {}
    for e in ev:
        by_trace.setdefault(e["args"].get("trace_id"), []).append(e)
    for job in jobs:
        fam = {e["name"]: e for e in by_trace[job["args"]["trace_id"]]}
        assert {"claim", "run", "write"} <= set(fam), (
            f"job {job['args']['job']} trace missing spans: "
            f"{sorted(fam)}")
        for child in ("claim", "run", "write"):
            assert _span_contains(job, fam[child]), (
                f"{child} not nested inside job span")
        assert fam["claim"]["ts"] <= fam["run"]["ts"] <= fam["write"]["ts"]
        # run/write parent back to this job's root
        assert fam["run"]["args"]["parent_id"] == job["args"]["span_id"]
