"""The kill-the-board-mid-stream acceptance scenario (ISSUE 13 /
ROADMAP item 3): two REAL docserver OS processes over one shared HA
dir; a wordcount task runs through the multi-endpoint connstr with a
worker pinned INSIDE a job (the chaos_mods HOLD key) and a resident
EngineSession feeding on the device plane while the primary is
SIGKILLed.  Asserts:

* the standby takes over within one lease period (plus bounded
  detection/replay slack),
* the exactly-once witness holds across the failover — every map job
  STARTED exactly once and COMPLETED exactly once, no duplicate
  applies from the replayed mutation log,
* the session's post-failover snapshot is bit-identical to an
  uninterrupted run over the same records (the device plane never
  hiccups while the control plane fails over).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from mapreduce_tpu.server import Server
from mapreduce_tpu.utils.httpclient import RetryPolicy
from mapreduce_tpu.worker import spawn_worker_threads
from tests import chaos_mods

pytestmark = [pytest.mark.chaos]

LEASE = 1.0
CHAOS_RETRY = RetryPolicy(max_attempts=10, base_delay=0.02,
                          max_delay=0.3, deadline=25.0,
                          breaker_threshold=0)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _healthz(port: int, timeout: float = 0.5):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz",
                timeout=timeout) as r:
            return json.loads(r.read())
    except Exception:
        return None


def _spawn_docserver(port: int, ha_dir: str,
                     extra=()) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-m", "mapreduce_tpu.cli", "docserver",
         "--host", "127.0.0.1", "--port", str(port),
         "--ha-dir", ha_dir, "--ha-lease", str(LEASE)] + list(extra),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _wait(pred, timeout: float, what: str):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(0.05)
    raise TimeoutError(what)


def test_sigkill_primary_mid_stream(tmp_path):
    ha_dir = str(tmp_path / "ha")
    p1, p2 = _free_port(), _free_port()
    procs = [_spawn_docserver(p1, ha_dir), _spawn_docserver(p2, ha_dir)]
    threads = []
    feeder = {}
    try:
        for port in (p1, p2):
            _wait(lambda port=port: _healthz(port) is not None, 30,
                  f"docserver on {port} never served /healthz")
        roles = _wait(
            lambda: ({p: (_healthz(p) or {}).get("primary")
                      for p in (p1, p2)}
                     if any((_healthz(p) or {}).get("primary")
                            for p in (p1, p2)) else None),
            30, "no replica ever took the board lease")
        prim_port = p1 if roles[p1] else p2
        stby_port = p2 if prim_port == p1 else p1
        prim = procs[0] if prim_port == p1 else procs[1]
        connstr = f"http://127.0.0.1:{p1},127.0.0.1:{p2}"

        # -- the host plane: a wordcount task with a pinned worker ------
        files = []
        for i in range(6):
            f = tmp_path / f"part{i}.txt"
            f.write_text(f"alpha beta part{i} gamma alpha\n" * 5)
            files.append(str(f))
        chaos_mods.reset(files, hold_key=2)
        params = {r: "tests.chaos_mods"
                  for r in ("taskfn", "mapfn", "partitionfn",
                            "reducefn", "finalfn")}
        params["storage"] = "mem:hakill"
        threads = spawn_worker_threads(connstr, "hakill", 2,
                                       retry=CHAOS_RETRY)
        server = Server(connstr, "hakill", retry=CHAOS_RETRY)
        server.configure(params)
        import threading as _threading

        stats_box = {}

        def drive():
            stats_box["stats"] = server.loop()

        driver = _threading.Thread(target=drive, daemon=True)
        driver.start()

        # -- the device plane: a resident session feeding mid-kill ------
        # (the shared synthetic record stream at test_session's
        # config/shape: its wave program is warm from earlier suites,
        # so this test pays failover wall, not a tokenizer compile)
        from mapreduce_tpu.engine.device_engine import EngineConfig
        from mapreduce_tpu.engine.session import EngineSession
        from mapreduce_tpu.parallel import make_mesh
        from tests.test_fused_engine import _chunks as _rec_chunks
        from tests.test_fused_engine import _records_map_fn

        cfg = EngineConfig(local_capacity=256, exchange_capacity=128,
                           out_capacity=256, tile=64, tile_records=64,
                           reduce_op="sum")
        chunks = _rec_chunks(np.random.default_rng(13), 48)
        mesh = make_mesh()
        sess = EngineSession(mesh, _records_map_fn, cfg,
                             task="live", k=1)
        parts = np.array_split(np.arange(len(chunks)), 6)

        def feed_loop():
            for idx in parts:
                sess.feed(chunks[idx[0]:idx[-1] + 1])
                time.sleep(0.2)
            feeder["done"] = True

        feed_thread = _threading.Thread(target=feed_loop, daemon=True)

        # wait until the held map job pins a worker mid-stream, then
        # open fire: feeds running, worker traffic in flight, SIGKILL
        _wait(lambda: chaos_mods.STARTED.get(2, 0) >= 1, 60,
              "the held map job was never claimed")
        feed_thread.start()
        t_kill = time.monotonic()
        os.kill(prim.pid, signal.SIGKILL)
        prim.wait(timeout=10)

        promoted = _wait(
            lambda: ((_healthz(stby_port) or {}).get("primary")
                     and time.monotonic()), 30,
            "standby never took over after SIGKILL")
        takeover_s = promoted - t_kill
        # one lease period + bounded detection/replay slack (the
        # standby claims as soon as the persisted expiry passes)
        assert takeover_s <= LEASE + 2.0, (
            f"standby takeover took {takeover_s:.2f}s "
            f"(lease {LEASE}s)")

        # release the pinned job only now: its heartbeat/claim traffic
        # provably spanned the failover
        chaos_mods.HOLD.set()
        driver.join(timeout=60)
        assert "stats" in stats_box, "server.loop did not finish"
        _wait(lambda: feeder.get("done"), 60,
              "session feed loop did not finish")

        # exactly-once witness across the failover: every job STARTED
        # exactly once and COMPLETED exactly once — the replayed board
        # (claims, heartbeats, WRITTEN marks, dedupe) let nothing run
        # twice and lost nothing
        assert dict(chaos_mods.STARTED) == {i: 1 for i in range(6)}, \
            dict(chaos_mods.STARTED)
        assert dict(chaos_mods.COMPLETED) == {i: 1 for i in range(6)}, \
            dict(chaos_mods.COMPLETED)
        assert stats_box["stats"]["map"]["failed"] == 0
        assert stats_box["stats"]["reduce"]["failed"] == 0
        assert chaos_mods.RESULT["alpha"] == 6 * 5 * 2

        # the device plane never hiccupped: post-failover snapshot is
        # bit-identical to an uninterrupted run over the same records
        got = sess.snapshot("live")
        ref_sess = EngineSession(mesh, _records_map_fn, cfg,
                                 task="ref", k=1)
        for idx in parts:
            ref_sess.feed(chunks[idx[0]:idx[-1] + 1])
        ref = ref_sess.snapshot("ref")
        for field in ("keys", "values", "payload", "valid"):
            assert np.array_equal(np.asarray(getattr(got, field)),
                                  np.asarray(getattr(ref, field))), field
        sess.close()
        ref_sess.close()
    finally:
        chaos_mods.HOLD.set()
        for t in threads:
            t.join(timeout=30)
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)

def test_history_survives_board_failover(tmp_path):
    """The durable history plane across a SIGKILL failover: pushes land
    on the primary's history segments (under the shared HA dir), the
    promoted standby serves /queryz over the SAME segments, and a probe
    counter's total increase matches this process's registry exactly —
    no gap from the failover, no double count from re-sent batches.
    The promoted server's trend summary must also carry at least one
    regression finding (the failover burst: this pusher's
    client-failover/retry counters fire from zero)."""
    from mapreduce_tpu.coord.docserver import HttpDocStore
    from mapreduce_tpu.obs import analysis
    from mapreduce_tpu.obs.collector import TelemetryPusher
    from mapreduce_tpu.obs.metrics import REGISTRY, counter

    ha_dir = str(tmp_path / "ha")
    p1, p2 = _free_port(), _free_port()
    procs = [_spawn_docserver(p1, ha_dir), _spawn_docserver(p2, ha_dir)]
    probe = counter("mrtpu_hachaos_probe_total",
                    "failover-spanning history probe")
    pusher = TelemetryPusher(f"127.0.0.1:{p1},127.0.0.1:{p2}",
                             role="hachaos", interval=60.0)
    try:
        for port in (p1, p2):
            _wait(lambda port=port: _healthz(port) is not None, 30,
                  f"docserver on {port} never served /healthz")
        roles = _wait(
            lambda: ({p: (_healthz(p) or {}).get("primary")
                      for p in (p1, p2)}
                     if any((_healthz(p) or {}).get("primary")
                            for p in (p1, p2)) else None),
            30, "no replica ever took the board lease")
        prim_port = p1 if roles[p1] else p2
        stby_port = p2 if prim_port == p1 else p1
        prim = procs[0] if prim_port == p1 else procs[1]

        # pre-kill: a few delivered increments land in the primary's
        # history segments
        for _ in range(3):
            probe.inc()
            _wait(pusher.flush, 30, "pre-kill telemetry push failed")
            time.sleep(0.05)

        os.kill(prim.pid, signal.SIGKILL)
        prim.wait(timeout=10)
        # increments DURING the outage: flushes may fail, the backlog
        # holds them — the cumulative value rides the next success
        for _ in range(2):
            probe.inc()
            pusher.flush()
            time.sleep(0.05)
        _wait(lambda: (_healthz(stby_port) or {}).get("primary"), 30,
              "standby never took over after SIGKILL")
        probe.inc()
        _wait(pusher.flush, 30,
              "no telemetry push succeeded after promotion")

        want = REGISTRY.sum("mrtpu_hachaos_probe_total")
        assert want == 6.0
        client = HttpDocStore(f"127.0.0.1:{stby_port}")
        try:
            res = client.queryz({"metric": "mrtpu_hachaos_probe_total",
                                 "fn": "increase", "start": -3600})
            got = sum(v for s in res["series"]
                      for _t, v in s["points"])
            # bit-exact across the failover: no gap (the standby tails
            # the dead primary's segments), no double count (delta
            # encoding + seq idempotency eat the re-sent batches)
            assert got == want, (got, want)
            row = client.statusz().get("history") or {}
            assert row.get("entries", 0) >= 2, row
            doc = client.clusterz()
        finally:
            client.close()

        # trend-aware diagnosis over PERSISTED windows on the promoted
        # server: the failover burst (client failovers / retries from
        # zero) must surface as at least one regression finding
        report = analysis.diagnose(doc)
        findings = (report.get("trends") or {}).get("findings") or []
        assert findings, report.get("trends")
    finally:
        pusher.stop(flush=False)
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)


def test_alert_fires_exactly_once_across_failover(tmp_path):
    """The alerting-plane chaos acceptance (ISSUE 19): a threshold rule
    goes pending on the primary, the primary is SIGKILLed mid-window,
    and the promoted standby replays the shared alert log, RESUMES the
    pending timer (it does not restart), and fires EXACTLY once — the
    webhook witness sees one firing delivery across the kill.  When
    the condition clears, resolved is delivered too, and `cli alerts`
    against the standby shows the whole lifecycle."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from mapreduce_tpu.obs.collector import TelemetryPusher
    from mapreduce_tpu.obs.metrics import counter

    hits = []

    class _Hook(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            hits.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    hook = ThreadingHTTPServer(("127.0.0.1", 0), _Hook)
    threading.Thread(target=hook.serve_forever, daemon=True).start()

    def delivered(to):
        return sum(1 for d in hits if d.get("to") == to)

    def alertz(port):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/alertz",
                    timeout=0.5) as r:
                return json.loads(r.read())
        except Exception:
            return None

    ha_dir = str(tmp_path / "ha")
    p1, p2 = _free_port(), _free_port()
    alert_args = [
        "--alert",
        "probe:increase(mrtpu_alertchaos_probe_total[6]):>:4:2",
        "--alert-webhook", f"pager=127.0.0.1:{hook.server_address[1]}",
        "--alert-interval", "0.25", "--alert-damp", "0.5"]
    procs = [_spawn_docserver(p1, ha_dir, alert_args),
             _spawn_docserver(p2, ha_dir, alert_args)]
    probe = counter("mrtpu_alertchaos_probe_total",
                    "failover-spanning alert probe")
    pusher = TelemetryPusher(f"127.0.0.1:{p1},127.0.0.1:{p2}",
                             role="alertchaos", interval=60.0)
    try:
        for port in (p1, p2):
            _wait(lambda port=port: _healthz(port) is not None, 30,
                  f"docserver on {port} never served /healthz")
        roles = _wait(
            lambda: ({p: (_healthz(p) or {}).get("primary")
                      for p in (p1, p2)}
                     if any((_healthz(p) or {}).get("primary")
                            for p in (p1, p2)) else None),
            30, "no replica ever took the board lease")
        prim_port = p1 if roles[p1] else p2
        stby_port = p2 if prim_port == p1 else p1
        prim = procs[0] if prim_port == p1 else procs[1]

        # breach the threshold (increase 9 > 4 in the 6s window) and
        # wait for the PRIMARY's evaluator to append the pending
        # transition to the shared alert log
        probe.inc(9)
        _wait(pusher.flush, 30, "telemetry push never succeeded")
        _wait(lambda: (((alertz(prim_port) or {}).get("snapshot") or {})
                       .get("counts") or {}).get("pending"),
              20, "the rule never went pending on the primary")

        # open fire mid-window: pending logged, NOT yet firing
        os.kill(prim.pid, signal.SIGKILL)
        prim.wait(timeout=10)
        assert delivered("firing") == 0, hits
        _wait(lambda: (_healthz(stby_port) or {}).get("primary"), 30,
              "standby never took over after SIGKILL")

        # the promoted standby resumes the pending timer and fires —
        # the webhook hears it exactly once
        _wait(lambda: delivered("firing") >= 1, 30,
              "promoted standby never fired the alert")
        (firing,) = [d for d in hits if d["to"] == "firing"]
        assert firing["rule"] == "probe" and firing["seq"] >= 1

        # nothing pushes any more: the window drains, the damped
        # instance resolves, resolved is delivered
        _wait(lambda: delivered("resolved") >= 1, 40,
              "resolved was never delivered after the window drained")
        assert delivered("firing") == 1, hits
        assert delivered("resolved") == 1, hits

        # `cli alerts` against the STANDBY shows the lifecycle (it
        # serves the tailed log), and the promotion fence bumped the
        # log generation
        out = subprocess.run(
            [sys.executable, "-m", "mapreduce_tpu.cli", "alerts",
             f"http://127.0.0.1:{stby_port}"],
            stdout=subprocess.PIPE, timeout=30,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))).stdout.decode()
        assert "alerts: 1 rule(s)" in out and "resolved=1" in out, out
        snap = (alertz(stby_port) or {}).get("snapshot") or {}
        assert snap["log"]["generation"] >= 2
        assert snap["counts"] == {"resolved": 1}
    finally:
        pusher.stop(flush=False)
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
        hook.shutdown()
        hook.server_close()
