"""The kill-the-ENGINE-HOST acceptance scenario (ISSUE 16; the
device-plane sibling of tests/test_ha_chaos' kill-the-board test): a
REAL engine-host OS process joins the fleet over a shared dir://
board, feeds a resident stream with a spill after every feed, and is
SIGKILLed mid-stream.  Asserts:

* the scheduler's failed-host recovery sweep notices the expired host
  lease and re-homes its stream to the live spare within one
  host-lease period (plus bounded detection slack),
* the re-homed stream is SERVABLE immediately: a fresh session on the
  destination answers a snapshot from the last committed spill, and
  that snapshot is bit-identical to an uninterrupted stream over
  exactly the chunks the spill covers,
* the exactly-once witness holds — the recovered aggregate equals the
  host-side oracle over those chunks (each record folded once: the
  kill landing mid-feed/mid-spill lost the uncommitted tail, never
  double-folded the committed one),
* the recovery is auditable: the migration counter and the control
  ledger's ``fleet`` decision both name the move.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from mapreduce_tpu.coord import docstore
from mapreduce_tpu.coord.fleet import FleetMember, FleetRegistry
from mapreduce_tpu.engine.autotune import AdmissionAdvisor
from mapreduce_tpu.engine.device_engine import EngineConfig
from mapreduce_tpu.engine.session import EngineSession
from mapreduce_tpu.engine.spill import SessionSpillStore
from mapreduce_tpu.obs import control as _control
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.parallel import make_mesh
from mapreduce_tpu.sched.scheduler import Scheduler
from mapreduce_tpu.storage.localdir import LocalDirStorage
from tests.test_fused_engine import _chunks as _rec_chunks
from tests.test_fused_engine import _dict_oracle, _records_map_fn, \
    _result_dict

pytestmark = [pytest.mark.chaos]

#: the failed-host detection window under test (seconds)
LEASE = 1.0

CFG = EngineConfig(local_capacity=256, exchange_capacity=128,
                   out_capacity=256, tile=64, tile_records=64,
                   reduce_op="sum")


def _wait(pred, timeout: float, what: str):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(0.05)
    raise TimeoutError(what)


def test_sigkill_engine_host_streams_rehomed(tmp_path):
    board_dir = tmp_path / "board"
    spill_dir = tmp_path / "spill"
    board_dir.mkdir(), spill_dir.mkdir()
    connstr = f"dir://{board_dir}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = subprocess.Popen(
        [sys.executable, "-m", "tests.fleet_chaos_child", connstr,
         str(spill_dir), "victim", str(LEASE)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        cwd=repo, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    spare = None
    try:
        store = docstore.connect(connstr)
        reg = FleetRegistry(store)
        # the spare joins BEFORE the kill: recovery latency measured
        # below is detection + sweep, not spare startup
        spare = FleetMember(store, host_id="spare", lease=30.0)
        spare.join(timeout=10.0, warm_programs=[], hbm_frac=0.1)

        _wait(lambda: (store.find_one("__chaos__.progress",
                                      {"_id": "victim"}) or {}
                       ).get("spilled_chunks", 0) >= 4,
              90, "the victim never spilled 4 chunks (jax startup "
                   "or board join failed in the child)")
        t_kill = time.monotonic()
        os.kill(child.pid, signal.SIGKILL)   # mid-feed by design
        child.wait(timeout=10)

        # one sweeping scheduler (the admission owner's role): poll it
        # the way ticks would — moves appear once the lease expires
        sched = Scheduler(store, use_lease=False,
                          advisor=AdmissionAdvisor(), fleet=reg)
        m0 = REGISTRY.sum("mrtpu_session_migrations_total",
                          task="live", reason="recovery")
        moves = _wait(lambda: sched.recovery_sweep() or None,
                      LEASE + 5.0,
                      "recovery sweep never re-homed the stream")
        recovered_s = time.monotonic() - t_kill
        assert moves == [("live", "spare")]
        assert recovered_s <= LEASE + 2.0, (
            f"re-home took {recovered_s:.2f}s (host lease {LEASE}s)")
        assert reg.route("live")["host"] == "spare"
        doc = next(d for d in reg.hosts() if d["_id"] == "victim")
        assert doc.get("holder") is None        # reaped under guard

        # the stream is SERVABLE now: lazy restore from the last
        # committed spill on the destination, one session construction
        # away — bit-identical to an uninterrupted stream over exactly
        # the chunks that spill covers, and value-exact vs the oracle
        chunks = _rec_chunks(np.random.default_rng(13), 48)
        mesh = make_mesh()
        dst = EngineSession(
            mesh, _records_map_fn, CFG, task="live", k=1,
            spill=SessionSpillStore(LocalDirStorage(str(spill_dir))))
        got = dst.snapshot("live")
        pos = dst.stats("live")["chunks"]
        assert pos >= 4                        # the durable prefix
        ref_s = EngineSession(mesh, _records_map_fn, CFG, task="ref",
                              k=1)
        for i in range(pos):                   # the child's feed steps
            ref_s.feed(chunks[i:i + 1])
        ref = ref_s.snapshot("ref")
        for field in ("keys", "values", "payload", "valid"):
            assert np.array_equal(np.asarray(getattr(got, field)),
                                  np.asarray(getattr(ref, field))), \
                field
        # exactly-once: the aggregate equals each committed record
        # folded once — no double-fold from the killed feed
        assert _result_dict(got) == _dict_oracle(chunks[:pos], "sum")
        dst.close(drop_spill=False), ref_s.close()

        # auditability: the move is counted and ledgered
        assert REGISTRY.sum("mrtpu_session_migrations_total",
                            task="live", reason="recovery") - m0 == 1
        assert REGISTRY.sum("mrtpu_fleet_recoveries_total",
                            host="victim") >= 1
        decs = _control.LEDGER.decisions(controller="fleet",
                                         task="live")
        assert any("victim to spare" in (d.get("note") or "")
                   for d in decs), [d.get("note") for d in decs]
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=10)
        if spare is not None:
            try:
                spare.leave()
            except OSError:
                pass
