"""Fenced trainer failover under network chaos: a partition outlasting
the trainer lease mid-epoch fences the original trainer, a successor
acquires the lease, restores the latest COMPLETE checkpoint and reaches
the same final state as an uninterrupted run — with exactly-once
optimizer-step accounting proven from the checkpoint lineage, not
eyeballed from a plausible loss curve.  Plus the lease/release
fast-handoff semantics (no reap wait) and the SIGTERM flight-recorder
arming on the trainer CLI path."""

import os
import signal
import subprocess
import sys
import threading
import time
import uuid

import numpy as np
import pytest

from mapreduce_tpu.coord import Connection, TrainerFencedError, TrainerLease
from mapreduce_tpu.coord.docserver import DocServer
from mapreduce_tpu.models import DistributedTrainer, MLPConfig, TrainConfig
from mapreduce_tpu.models.checkpoint import CheckpointManager
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.parallel import make_mesh
from mapreduce_tpu.storage.memory import MemoryStorage
from mapreduce_tpu.testing.faults import FaultProxy
from mapreduce_tpu.utils.constants import STATUS
from mapreduce_tpu.utils.httpclient import RetryPolicy

pytestmark = [pytest.mark.chaos, pytest.mark.telemetry]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: tight policy: a partitioned heartbeat must resolve (fail) in well
#: under a lease period so the fence gate keeps polling
TIGHT = RetryPolicy(max_attempts=2, base_delay=0.02, deadline=0.4,
                    breaker_threshold=0)


def _data(n=64, dim=16, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    y = (np.arange(n) % classes).astype(np.int32)
    return x, y


def _trainer(max_epochs):
    # tiny on purpose: three trainer instances compile in this test
    return DistributedTrainer(
        make_mesh(), MLPConfig(sizes=(16, 8, 4)),
        TrainConfig(bunch_size=8, max_epochs=max_epochs, min_epochs=1,
                    patience=100, learning_rate=0.1, momentum=0.9))


def _assert_state_equal(a, b):
    import jax

    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# -- the tentpole chaos scenario ---------------------------------------------


def test_partition_outlasting_lease_failover_exactly_once():
    """Mid-epoch partition outlasts trainer A's lease: A fences at its
    next step boundary (committing NOTHING past the fence), successor B
    waits out the lease, restores A's last complete checkpoint and
    finishes the run.  The final state is bit-identical to an
    uninterrupted reference at the same epoch count, every epoch was
    committed exactly once (manifest lineage: generations partition the
    step range), and B's step-recovery time landed in the gauge."""
    E, k = 6, 3  # total epochs; A is fenced after committing epoch k
    x, y = _data()
    board = DocServer().start_background()
    proxy = FaultProxy(board.host, board.port).start()
    direct = f"http://{board.host}:{board.port}"
    storage = MemoryStorage()
    mgr = CheckpointManager(storage, keep_n=20)

    lease_a = TrainerLease(
        Connection(f"http://{proxy.address}", "ft", retry=TIGHT),
        holder="A", lease=0.8)
    a_done_k = threading.Event()
    a_resume = threading.Event()
    a_out = {}

    def on_epoch_a(rec):
        if rec["epoch"] == k:
            a_done_k.set()
            a_resume.wait(timeout=30)  # held mid-run (epoch k committed)

    def run_a():
        try:
            a_out["out"] = _trainer(E).fit(
                x, y, x, y, manager=mgr, lease=lease_a,
                on_epoch=on_epoch_a)
        except TrainerFencedError as exc:
            a_out["fenced"] = str(exc)

    try:
        assert lease_a.try_acquire()
        gen_a = lease_a.generation
        ta = threading.Thread(target=run_a, daemon=True)
        ta.start()
        assert a_done_k.wait(timeout=60), "A never reached epoch k"

        proxy.partition()   # A's board RPCs now go into the void
        a_resume.set()      # A proceeds into epoch k+1's fence gate

        # successor: waits out A's lease on the DIRECT path, restores,
        # finishes.  The partition outlasts the lease by construction —
        # it stays up until after B completes.
        lease_b = TrainerLease(Connection(direct, "ft"), holder="B",
                               lease=5.0)
        t0 = time.monotonic()
        lease_b.acquire(timeout=30)
        waited = time.monotonic() - t0
        assert lease_b.generation > gen_a  # the fencing token advanced
        b_out = _trainer(E).fit(x, y, x, y, manager=mgr, lease=lease_b)
        proxy.heal()  # A's pending beat now gets a definitive answer
        ta.join(timeout=60)
        assert not ta.is_alive(), "trainer A wedged"
    finally:
        a_resume.set()
        proxy.stop()
        board.shutdown()

    # A fenced without applying (or committing) anything past epoch k
    assert "fenced" in a_out, a_out
    assert "out" not in a_out
    assert waited >= 0.3, f"B acquired in {waited:.2f}s — no lease wait?"

    # B restored A's last complete checkpoint and ran k+1..E
    assert b_out["restored"] and b_out["start_epoch"] == k + 1
    assert b_out["epochs_run"] == E - k

    # exactly-once optimizer-step accounting from the manifest lineage:
    # every epoch 1..E committed once; generation gen_a wrote 1..k,
    # generation gen_b wrote k+1..E, and no step has two writers
    assert mgr.steps() == list(range(1, E + 1))
    from mapreduce_tpu.models import checkpoint as ckpt

    gens = {step: ckpt.load_manifest(storage, "", step)["meta"]
            ["generation"] for step in mgr.steps()}
    assert all(gens[s] == gen_a for s in range(1, k + 1)), gens
    assert all(gens[s] == lease_b.generation
               for s in range(k + 1, E + 1)), gens

    # value-identity: B's lineage equals an uninterrupted run at the
    # same epoch count — params AND optimizer state, bit for bit
    ref = _trainer(E).fit(x, y, x, y)
    assert ref["epochs_run"] == E
    _assert_state_equal(b_out["params"], ref["params"])
    _assert_state_equal(b_out["opt_state"], ref["opt_state"])

    # the successor's step-recovery time was recorded for the bench gate
    assert REGISTRY.value("mrtpu_trainer_recovery_seconds") > 0
    assert REGISTRY.sum("mrtpu_trainer_lease_fences_total") >= 1


def test_fenced_trainer_commits_nothing_after_losing_lease():
    """The commit gate specifically: a trainer whose lease is stolen
    between epochs raises at the NEXT boundary and the checkpoint
    stream gains nothing from it — the successor's view of 'latest
    complete' can never be a fenced straggler's write."""
    x, y = _data()
    board = f"mem://{uuid.uuid4().hex}"
    storage = MemoryStorage()
    mgr = CheckpointManager(storage, keep_n=20)
    lease_a = TrainerLease(Connection(board, "ft2"), holder="A",
                           lease=30.0)
    assert lease_a.try_acquire()

    stolen = {}

    def on_epoch(rec):
        if rec["epoch"] == 2 and not stolen:
            # simulate the successor appearing: takeover by release +
            # reacquire under another holder (generation advances)
            b = TrainerLease(Connection(board, "ft2"), holder="B",
                             lease=30.0)
            lease_a.release()
            assert b.try_acquire()
            stolen["gen"] = b.generation

    with pytest.raises(TrainerFencedError):
        _trainer(6).fit(x, y, x, y, manager=mgr, lease=lease_a,
                        on_epoch=on_epoch)
    assert mgr.steps() == [1, 2]  # epochs 1..2 committed, nothing after


# -- release semantics: no reap wait -----------------------------------------


def test_released_lease_and_released_jobs_hand_off_immediately():
    """The no-reap-wait pair: a cleanly released trainer lease is
    claimable by the successor IMMEDIATELY (well under a lease period),
    and Task.release_jobs hands an exiting worker's claimed-but-unrun
    jobs straight back to WAITING so the successor's claim round trip
    gets them with no lease expiry in between."""
    from mapreduce_tpu.coord.task import Task, make_job
    from mapreduce_tpu.utils.constants import TASK_STATUS

    connstr = f"mem://{uuid.uuid4().hex}"
    LEASE = 30.0  # long on purpose: any reap wait would blow the budget

    # trainer lease: release -> immediate successor claim
    a = TrainerLease(Connection(connstr, "rel"), holder="A", lease=LEASE)
    b = TrainerLease(Connection(connstr, "rel"), holder="B", lease=LEASE)
    assert a.try_acquire()
    assert not b.try_acquire()  # held: successor must wait...
    t0 = time.monotonic()
    assert a.release()
    assert b.try_acquire(), "released lease not immediately claimable"
    assert time.monotonic() - t0 < LEASE / 10
    # the released holder is fenced, not racing
    assert not a.heartbeat()
    with pytest.raises(TrainerFencedError):
        a.ensure_owned(max_wait=0.2)

    # job claims: release_jobs -> immediately re-claimable, no BROKEN
    # transition, no repetitions charge
    cnn = Connection(connstr, "rel")
    task = Task(cnn, job_lease=LEASE)
    task.create_collection(
        TASK_STATUS.MAP,
        {"taskfn": "m", "mapfn": "m", "partitionfn": "m",
         "reducefn": "m", "finalfn": "m", "storage": "mem:x",
         "path": "p"}, 1)
    coll = task.map_jobs_ns()
    task.insert_jobs(coll, [make_job(i, i) for i in range(3)])
    w1 = Task(cnn, job_lease=LEASE)
    got, _ = w1.take_next_jobs("w1", "tmp1", 3)
    assert len(got) == 3
    t0 = time.monotonic()
    assert w1.release_jobs(coll, got) == 3
    w2 = Task(cnn, job_lease=LEASE)
    got2, _ = w2.take_next_jobs("w2", "tmp2", 3)
    assert len(got2) == 3, "released jobs not immediately claimable"
    assert time.monotonic() - t0 < LEASE / 10
    assert all(j["repetitions"] == 0 for j in got2)
    assert all(j["status"] == int(STATUS.RUNNING) for j in got2)


def test_lease_lost_during_shard_upload_aborts_before_manifest():
    """The commit fence runs at the MANIFEST write, not just before the
    upload: a lease stolen while shards are uploading (slow blob plane,
    GC pause) must abort the save with no manifest published — the
    stale trainer cannot commit a checkpoint over a live successor's
    lineage."""
    from mapreduce_tpu.models import checkpoint as ckpt

    board = f"mem://{uuid.uuid4().hex}"
    a = TrainerLease(Connection(board, "t"), holder="A", lease=0.2)
    assert a.try_acquire()
    st = MemoryStorage()
    tree = {"w": np.arange(8, dtype=np.float32)}

    class StealMidUpload(MemoryStorage):
        def __init__(self, inner):
            super().__init__()
            self._blobs = inner._blobs  # share the blob dict
            self._lock = inner._lock

        def write_bytes(self, name, data):
            super().write_bytes(name, data)
            # successor grabs the lease right after this shard lands
            time.sleep(0.25)  # let A's lease expire
            b = TrainerLease(Connection(board, "t"), holder="B",
                             lease=30.0)
            assert b.try_acquire()

    with pytest.raises(TrainerFencedError):
        ckpt.save(StealMidUpload(st), 5, tree,
                  precommit=a.ensure_owned)
    # shards may exist, but the checkpoint does NOT (manifest-last)
    assert ckpt.list_steps(st) == []


def test_crashed_trainer_cli_releases_lease(tmp_path, monkeypatch):
    """A NON-fence crash inside fit (storage error, Ctrl-C) must hand
    the lease back on the way out: the standby acquires immediately —
    a crash-restart loop must not pay a full lease expiry per cycle."""
    from mapreduce_tpu import cli

    board = f"mem://{uuid.uuid4().hex}"

    def boom(self, *a, **k):
        raise RuntimeError("storage exploded")

    monkeypatch.setattr(DistributedTrainer, "fit", boom)
    with pytest.raises(RuntimeError, match="storage exploded"):
        cli.cmd_train([board, "tdb",
                       "--storage", f"shared:{tmp_path}/ck",
                       "--epochs", "1", "--lease", "30"])
    # the 30s lease would dwarf the test timeout if it leaked: a single
    # immediate claim attempt must succeed
    suc = TrainerLease(Connection(board, "tdb"), holder="suc", lease=30.0)
    assert suc.try_acquire(), "crashed CLI leaked its trainer lease"
    assert suc.generation == 2  # the crashed run's tenure was gen 1


def test_acquire_poll_seeds_once():
    """The singleton seed upsert happens ONCE per handle, not on every
    poll of a blocked acquire() — a standby waiting out a live holder
    pays one board round-trip per poll, not two."""
    board = f"mem://{uuid.uuid4().hex}"
    holder = TrainerLease(Connection(board, "tdb"), holder="A", lease=30.0)
    assert holder.try_acquire()

    standby = TrainerLease(Connection(board, "tdb"), holder="B", lease=30.0)
    seeds = []
    orig = TrainerLease._seed
    standby._seed = lambda: seeds.append(1) or orig(standby)
    for _ in range(5):
        assert not standby.try_acquire()  # busy: A holds it
    assert len(seeds) == 1
    holder.release()
    assert standby.try_acquire()  # and the memoized seed doesn't block


# -- the bench gate: trainer_recovery_s --------------------------------------


def test_bench_train_recovery_gate(tmp_path):
    """``bench_train.py --check`` gates ``trainer_recovery_s``: a real
    measured smoke recovery (lease acquire -> restore -> first epoch)
    passes against its own history, a synthetic 6x regression fails,
    and a run missing the metric fails because the spec requires it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_train_under_test", os.path.join(REPO, "bench_train.py"))
    bt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bt)

    row = bt.bench_recovery(make_mesh())
    assert row["metric"] == "trainer_recovery_s" and row["value"] > 0
    path = str(tmp_path / "hist.json")
    assert bt.run_check([row], path=path) == []  # first run seeds
    assert bt.run_check([row], path=path) == []  # same run: in band
    bad = dict(row, value=row["value"] * 6)
    problems = bt.run_check([bad], path=path)
    assert problems and "trainer_recovery_s" in problems[0]
    problems = bt.run_check([], path=path)
    assert any("required" in p for p in problems)

    # cross-platform history must not pollute the baseline: a huge
    # other-platform recovery entry (e.g. TPU paying a jit compile)
    # neither rescues the 6x regression nor trips a good run
    from mapreduce_tpu.obs import benchgate

    benchgate.append_history(
        path, {"trainer_recovery_s": row["value"] * 100,
               "platform": "otherplat"})
    assert bt.run_check([bad], path=path), \
        "other-platform entry rescued a real regression"
    assert bt.run_check([row], path=path) == []


# -- flight recorder on the trainer CLI path ---------------------------------


def _child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _wait_for_line(stream, needle, timeout=60.0):
    found = threading.Event()

    def reader():
        for raw in stream:
            if needle in raw:
                found.set()
                return

    threading.Thread(target=reader, daemon=True).start()
    assert found.wait(timeout), f"never saw {needle!r} in child stderr"


def test_sigterm_trainer_dumps_flight_telemetry(tmp_path):
    """A preempted (SIGTERM'd) trainer CLI run exits 143 and leaves its
    flight telemetry AND a resumable checkpoint stream behind — the
    abnormal-exit signal the failover story is built on."""
    trace_out = tmp_path / "t.trace.json"
    ckpt_dir = tmp_path / "ckpt"
    cmd = [sys.executable, "-m", "mapreduce_tpu.cli", "train",
           f"mem://{uuid.uuid4().hex}", "ftcli",
           "--storage", f"shared:{ckpt_dir}",
           "--epochs", "500", "--patience", "1000", "--bunch", "16",
           "--trace-out", str(trace_out)]
    proc = subprocess.Popen(cmd, env=_child_env(),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    try:
        # epoch logs prove the loop (and the SIGTERM handler) is up —
        # and that at least one checkpoint committed
        _wait_for_line(proc.stderr, "epoch 1:")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
    assert rc == 143, rc
    assert os.path.exists(str(trace_out) + ".flight.trace.json")
    assert os.path.exists(str(trace_out) + ".flight.metrics.prom")
    with open(str(trace_out) + ".flight.metrics.prom",
              encoding="utf-8") as f:
        text = f.read()
    assert "mrtpu_ckpt_saves_total" in text
    # the preempted run left a complete, resumable checkpoint stream
    from mapreduce_tpu.models import checkpoint as ckpt
    from mapreduce_tpu.storage.localdir import LocalDirStorage

    steps = ckpt.list_steps(LocalDirStorage(str(ckpt_dir)))
    assert steps, "no committed checkpoint from the preempted trainer"
    man = ckpt.load_manifest(LocalDirStorage(str(ckpt_dir)), "",
                             steps[-1])
    assert man["meta"]["generation"] == 1  # first holder's tenure
