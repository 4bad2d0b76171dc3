"""Control-plane unit tests: docstore semantics, connection errors channel
and batched inserts, persistent_table optimistic concurrency + locks, task
claiming atomicity and lease reaping.

Mirrors the reference's embedded utests for cnn.lua:119-161,
persistent_table.lua:256-264, task.lua:365-367 — but against the in-proc /
dir backends, needing no live service (the improvement SURVEY.md §4 asks
for).
"""

import threading
import uuid

import pytest

from mapreduce_tpu.coord import docstore
from mapreduce_tpu.coord.connection import Connection
from mapreduce_tpu.coord.docserver import DocServer
from mapreduce_tpu.coord.persistent_table import PersistentTable
from mapreduce_tpu.coord.task import Task, make_job
from mapreduce_tpu.utils.constants import STATUS, TASK_STATUS


@pytest.fixture(params=["mem", "dir", "http"])
def store(request, tmp_path):
    if request.param == "mem":
        yield docstore.MemoryDocStore()
    elif request.param == "dir":
        s = docstore.DirDocStore(str(tmp_path / "store"))
        yield s
        s.close()
    else:
        srv = DocServer().start_background()
        s = docstore.connect(srv.connstr)
        yield s
        s.close()
        srv.shutdown()


def test_insert_find_update_remove(store):
    store.insert("c", {"_id": "a", "x": 1})
    store.insert("c", {"_id": "b", "x": 2, "tag": "t"})
    assert store.count("c") == 2
    assert store.find_one("c", {"x": 2})["_id"] == "b"
    assert store.find_one("c", {"x": {"$gte": 2}})["_id"] == "b"
    assert store.find_one("c", {"x": {"$in": [5, 1]}})["_id"] == "a"
    assert store.find_one("c", {"tag": {"$exists": False}})["_id"] == "a"
    n = store.update("c", {"x": {"$lt": 10}}, {"$inc": {"x": 10}}, multi=True)
    assert n == 2
    assert sorted(d["x"] for d in store.find("c")) == [11, 12]
    store.update("c", {"_id": "zz"}, {"$set": {"x": 1}}, upsert=True)
    assert store.count("c") == 3
    assert store.remove("c", {"_id": "zz"}) == 1
    store.drop_collection("c")
    assert store.count("c") == 0


def test_replace_semantics(store):
    store.insert("c", {"_id": "a", "x": 1, "y": 2})
    store.update("c", {"_id": "a"}, {"x": 9})
    doc = store.find_one("c", {"_id": "a"})
    assert doc["x"] == 9 and "y" not in doc and doc["_id"] == "a"


def test_find_and_modify_atomic_claim(store):
    """Concurrent claimers never double-claim one doc."""
    for i in range(20):
        store.insert("jobs", {"_id": f"j{i}", "status": 0})
    claimed = []
    lock = threading.Lock()

    def claim_all(name):
        while True:
            got = store.find_and_modify(
                "jobs", {"status": 0}, {"$set": {"status": 1, "who": name}})
            if got is None:
                return
            with lock:
                claimed.append(got["_id"])

    threads = [threading.Thread(target=claim_all, args=(f"w{i}",))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert sorted(claimed) == sorted(f"j{i}" for i in range(20))
    assert len(set(claimed)) == 20


def test_or_queries(store):
    store.insert("c", {"_id": "a", "s": 0})
    store.insert("c", {"_id": "b", "s": 2})
    docs = store.find("c", {"$or": [{"s": 0}, {"s": 2}]})
    assert len(docs) == 2


def test_connection_errors_channel():
    cnn = Connection(f"mem://{uuid.uuid4().hex}", "db")
    cnn.insert_error("w1", "boom")
    try:
        raise ValueError("exploded")
    except ValueError as e:
        cnn.insert_exception("w2", e)
    errs = cnn.get_errors()
    assert len(errs) == 2
    assert any("exploded" in e["msg"] for e in errs)
    cnn.remove_errors([e["_id"] for e in errs])
    assert cnn.get_errors() == []


def test_connection_batched_inserts():
    """cnn.lua:119-161: annotate_insert buffers; flush writes and fires
    callbacks."""
    cnn = Connection(f"mem://{uuid.uuid4().hex}", "db")
    fired = []
    for i in range(10):
        cnn.annotate_insert("db.jobs", {"i": i}, lambda: fired.append(1))
    assert cnn.connect().count("db.jobs") == 0  # still pending
    cnn.flush_pending_inserts(0)
    assert cnn.connect().count("db.jobs") == 10
    assert len(fired) == 10


def test_persistent_table_roundtrip_and_conflict():
    name = uuid.uuid4().hex
    cnn = Connection(f"mem://{name}", "db")
    t1 = PersistentTable("conf", cnn)
    t1.set("lr", 0.01)
    t1.update()
    t2 = PersistentTable("conf", Connection(f"mem://{name}", "db"))
    assert t2.get("lr") == 0.01
    # two-client consistency (persistent_table.lua:256-264)
    t2.set("epoch", 3)
    t2.update()
    t1.update()
    assert t1.get("epoch") == 3
    # read_only refuses writes
    t3 = PersistentTable("conf", cnn, read_only=True)
    with pytest.raises(RuntimeError):
        t3.set("x", 1)


def test_persistent_table_lock():
    cnn = Connection(f"mem://{uuid.uuid4().hex}", "db")
    t = PersistentTable("conf", cnn)
    t.lock()
    with pytest.raises(TimeoutError):
        PersistentTable("conf", cnn).lock(timeout=0.05, poll=0.01)
    t.unlock()
    PersistentTable("conf", cnn).lock(timeout=1.0)


@pytest.fixture(params=["mem", "http"])
def connstr(request):
    """The task fault suite (claim atomicity, lease reap, heartbeat) must
    hold over the networked board too — VERDICT r3 item 1."""
    if request.param == "mem":
        yield f"mem://{uuid.uuid4().hex}"
    else:
        srv = DocServer().start_background()
        yield srv.connstr
        srv.shutdown()


def _mk_task(connstr, status=TASK_STATUS.MAP, lease=30.0):
    cnn = Connection(connstr, "db")
    task = Task(cnn, job_lease=lease)
    task.create_collection(status, {
        "taskfn": "m", "mapfn": "m", "partitionfn": "m", "reducefn": "m",
        "finalfn": "m", "storage": "mem:x", "path": "x",
    }, iteration=1)
    return cnn, task


def test_task_claim_and_status(connstr):
    cnn, task = _mk_task(connstr)
    task.insert_jobs(task.map_jobs_ns(),
                     [make_job(0, "f0"), make_job(1, "f1")])
    job, st = task.take_next_job("w1", "tmp1")
    assert st == TASK_STATUS.MAP and job is not None
    assert job["status"] == int(STATUS.RUNNING)
    assert job["worker"] == "w1"
    assert "lease_expires" in job
    job2, _ = task.take_next_job("w2", "tmp2")
    assert job2["_id"] != job["_id"]
    job3, _ = task.take_next_job("w3", "tmp3")
    assert job3 is None  # board empty
    # WAIT and FINISHED claim nothing
    task.set_task_status(TASK_STATUS.FINISHED)
    job4, st4 = task.take_next_job("w4", "t")
    assert job4 is None and st4 == TASK_STATUS.FINISHED


def test_task_lease_reaping(connstr):
    cnn, task = _mk_task(connstr, lease=0.0)  # leases expire immediately
    task.insert_jobs(task.map_jobs_ns(), [make_job(0, "f0")])
    job, _ = task.take_next_job("w1", "t")
    assert job is not None
    n = task.reap_expired(task.map_jobs_ns())
    assert n == 1
    doc = cnn.connect().find_one(task.map_jobs_ns(), {"_id": job["_id"]})
    assert doc["status"] == int(STATUS.BROKEN)
    assert doc["repetitions"] == 1
    # reclaimable after reaping
    job2, _ = task.take_next_job("w2", "t")
    assert job2 is not None and job2["_id"] == job["_id"]


def test_task_heartbeat_extends_lease(connstr):
    cnn, task = _mk_task(connstr, lease=0.05)
    task.insert_jobs(task.map_jobs_ns(), [make_job(0, "f0")])
    job, _ = task.take_next_job("w1", "t")
    old = job["lease_expires"]
    task.job_lease = 60.0
    task.heartbeat(job)
    doc = cnn.connect().find_one(task.map_jobs_ns(), {"_id": job["_id"]})
    assert doc["lease_expires"] > old
    assert task.reap_expired(task.map_jobs_ns()) == 0
