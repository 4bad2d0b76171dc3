"""PR-6 acceptance: a THREE-PROCESS cluster (this process drives the
server + hosts the docserver/collector; two worker OS processes join
over http) must produce ONE merged Perfetto timeline via /clusterz with
spans from all three processes on an aligned timebase — and ``cli
diagnose`` over it must name the injected straggler (one worker
launched with a per-job sleep) and the injected key skew (every hot*
word routed to partition P00000 by tests/skew_mods.py)."""

import json
import time
import uuid

import pytest

from mapreduce_tpu import spec, storage
from mapreduce_tpu.coord.docserver import DocServer, HttpDocStore
from mapreduce_tpu.obs import analysis
from mapreduce_tpu.obs.profile import validate_trace
from mapreduce_tpu.server import Server
from mapreduce_tpu.storage import BlobServer
from tests.cli_workers import child_env, cli_workers

N_SPLITS = 8
N_REDUCERS = 4
STRAGGLE_S = 0.35


@pytest.fixture(autouse=True)
def fresh_modules():
    spec.clear_caches()
    # the driver pushes its process-global trace ring to the collector,
    # so job spans left over from earlier in-process tests (other worker
    # names, other latency profiles) would land in THIS clusterz doc and
    # dilute the straggler baseline until wslow no longer stands out
    from mapreduce_tpu.obs.trace import TRACER
    TRACER.reset()
    yield
    spec.clear_caches()


# claim-batch 1 + no claim-ahead keep each job span a clean per-job
# claim->write interval: a batch's later jobs backdate to the batch
# claim, and a prefetched claim backdates to BEFORE the previous job
# finished — both are queueing, not execution, and both inflate the fast
# worker's median enough to mask the injected straggler under the ratio
# test
WORKER_ARGS = ("--claim-batch", "1", "--no-claim-ahead",
               "--telemetry-interval", "0.1")


def test_three_process_timeline_and_diagnosis(tmp_path, capsys):
    docsrv = DocServer().start_background()
    blobsrv = BlobServer(str(tmp_path / "blobs")).start_background()
    connstr = f"http://127.0.0.1:{docsrv.port}"
    storage_dsl = f"http:127.0.0.1:{blobsrv.port}"

    # stage skewed inputs as blobs: 40 hot* uniques (all -> P00000 by
    # skew_mods.partitionfn) + 3 cold uniques per split
    st = storage.router(storage_dsl)
    hot = " ".join(f"hot{i}" for i in range(40))
    blobs = []
    expected_uniques = set()
    for i in range(N_SPLITS):
        text = f"{hot} cold{i}a cold{i}b cold{i}c\n"
        expected_uniques.update(text.split())
        name = f"in/f{i}"
        st.write(name, text)
        blobs.append(name)

    with cli_workers(
            connstr, "skw", 2,
            args=[("--name", "wfast", *WORKER_ARGS),
                  ("--name", "wslow", *WORKER_ARGS)],
            envs=[child_env(),
                  child_env(MRTPU_SKEW_DELAY=str(STRAGGLE_S))]) as workers:
        m = "tests.skew_mods"
        params = {r: m for r in ("taskfn", "mapfn", "partitionfn",
                                 "reducefn", "finalfn")}
        params["storage"] = storage_dsl
        params["init_args"] = {"blobs": blobs,
                               "num_reducers": N_REDUCERS,
                               "storage": storage_dsl}
        server = Server(connstr, "skw")
        server.configure(params)
        t_loop0 = time.monotonic()
        stats = server.loop()
        t_loop1 = time.monotonic()
    assert workers.rcs == [0, 0], workers.tails()
    assert stats["map"]["failed"] == 0
    from tests.skew_mods import RESULT
    assert set(RESULT) == expected_uniques
    assert RESULT["hot0"] == N_SPLITS  # exactly-once, telemetry or not

    store = HttpDocStore(f"127.0.0.1:{docsrv.port}")
    try:
        doc = store.clusterz()
        snap = store.statusz()
    finally:
        store.close()
        blobsrv.shutdown()
        docsrv.shutdown()

    # -- ONE merged Perfetto file with all three processes ----------------
    validate_trace(doc)
    procs = doc["mrtpuCluster"]["procs"]
    roles = sorted(p["role"] for p in procs.values())
    assert len(procs) >= 3, roles
    assert any(r == "worker:wfast" for r in roles), roles
    assert any(r == "worker:wslow" for r in roles), roles
    # spans actually present from >= 3 distinct process tracks
    span_pids = {e["pid"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
    assert len(span_pids) >= 3, span_pids
    # metadata names every track
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert len(meta) == len(procs)

    # -- aligned timebase: worker job spans sit inside the driver's loop
    #    window measured on the DRIVER's monotonic clock (same-host
    #    monotonic bases agree, so the estimated offsets must be small
    #    and the shifted spans must land in the window)
    worker_jobs = [e for e in doc["traceEvents"]
                   if e.get("name") == "job"
                   and (e.get("args") or {}).get("worker")
                   in ("wfast", "wslow")]
    assert worker_jobs, "no worker job spans reached the collector"
    for e in worker_jobs:
        ts = e["ts"] / 1e6
        assert t_loop0 - 1.0 <= ts <= t_loop1 + 1.0, (
            e["args"], ts, (t_loop0, t_loop1))
    for p in procs.values():
        if p["offset_s"] is not None:
            assert abs(p["offset_s"]) < 1.0, p

    # -- per-task roll-ups crossed the process boundary -------------------
    tasks = snap["telemetry"]["tasks"]
    assert tasks["skw"]["records"] > 0
    assert tasks["skw"]["bytes"] > 0

    # -- diagnosis: the injected straggler and the injected skew ----------
    rep = analysis.diagnose(doc)
    assert [s["worker"] for s in rep["stragglers"]] == ["wslow"], (
        rep["stragglers"], rep["workers"])
    assert rep["stragglers"][0]["median_s"] >= STRAGGLE_S * 0.8
    skew_parts = {(s["plane"], s["partition"]) for s in rep["skew"]}
    assert ("host", "P00000") in skew_parts, rep["skew"]
    top = rep["skew"][0]
    assert top["partition"] == "P00000" and top["share"] > 0.5, top

    # -- the CLI renders the same verdicts --------------------------------
    from mapreduce_tpu import cli

    out_file = str(tmp_path / "cluster_trace.json")
    with open(out_file, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    assert cli.main(["diagnose", out_file]) == 0
    text = capsys.readouterr().out
    assert "wslow" in text and "P00000" in text
