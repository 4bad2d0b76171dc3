"""Fault-injection tests: transient failures retry, permanent failures hit
the FAILED cap without hanging the phase, dead workers' leases are reaped,
and a crashed server resumes mid-task.  (The reference has retry/BROKEN/
FAILED logic and crash-restore but zero automated tests for any of it —
SURVEY.md §4 item 4; these close that gap.)"""

import uuid

import pytest

from mapreduce_tpu import spec
from mapreduce_tpu.examples import naive
from mapreduce_tpu.server import Server
from mapreduce_tpu.worker import spawn_worker_threads
from mapreduce_tpu.utils.constants import (
    MAX_JOB_RETRIES, STATUS, TASK_STATUS)
from tests import faulty_mods

M = "tests.faulty_mods"


@pytest.fixture(autouse=True)
def fresh_modules():
    spec.clear_caches()
    yield
    spec.clear_caches()


@pytest.fixture
def corpus(tmp_path):
    files = []
    for i in range(4):
        p = tmp_path / f"f{i}.txt"
        p.write_text(f"alpha beta f{i} gamma alpha\n" * 5)
        files.append(str(p))
    return files


def _params(corpus):
    params = {r: M for r in ("taskfn", "mapfn", "partitionfn", "reducefn",
                             "finalfn")}
    params["storage"] = f"mem:{uuid.uuid4().hex}"
    return params


def test_transient_failures_are_retried(corpus):
    """A mapfn that fails its first two attempts must still produce the
    exact result: BROKEN -> reclaim -> success (worker.lua:112-138 path)."""
    faulty_mods.reset(corpus, fail_times=2)
    connstr = f"mem://{uuid.uuid4().hex}"
    threads = spawn_worker_threads(connstr, "ft1", 2)
    server = Server(connstr, "ft1")
    server.configure(_params(corpus))
    stats = server.loop()
    for t in threads:
        t.join(timeout=30)
    assert faulty_mods.RESULT == naive.wordcount(corpus)
    assert stats["map"]["failed"] == 0
    # errors were reported through the channel and drained by the server
    assert server.cnn.get_errors() == []


def test_permanent_failure_becomes_FAILED_and_phase_completes(corpus):
    """One job that always fails: after MAX_JOB_RETRIES it is FAILED,
    completion counts it done (server.lua:192-213), and the final result
    simply misses that split's words."""
    faulty_mods.reset(corpus, always_fail_key=2)
    connstr = f"mem://{uuid.uuid4().hex}"
    threads = spawn_worker_threads(connstr, "ft2", 3)
    server = Server(connstr, "ft2")
    server.configure(_params(corpus))
    stats = server.loop()
    for t in threads:
        t.join(timeout=30)
    assert stats["map"]["failed"] == 1
    oracle = naive.wordcount([f for i, f in enumerate(corpus) if i != 2])
    assert faulty_mods.RESULT == oracle
    assert f"f2" not in faulty_mods.RESULT


def test_a_job_that_always_fails_does_not_end_the_whole_pool(corpus):
    """The server polls slower than the workers retry, as on a loaded
    machine: the job at its retry cap waits for the server to fail it, it
    is not handed out again.  Handed out on (to 9 repetitions), it took
    every worker's MAX_WORKER_RETRIES between two polls and loop() never
    returned: the case above hung the tier-1 run that way."""
    import threading

    faulty_mods.reset(corpus, always_fail_key=2)
    connstr = f"mem://{uuid.uuid4().hex}"
    threads = spawn_worker_threads(connstr, "ft2s", 3)
    server = Server(connstr, "ft2s")
    server.configure(_params(corpus))
    server.poll_sleep = 0.5
    stats = {}
    t = threading.Thread(target=lambda: stats.update(server.loop()),
                         daemon=True)
    t.start()
    t.join(timeout=30)
    docs = {d["_id"]: d for d in
            server.cnn.connect().find(server.task.map_jobs_ns())}
    assert not t.is_alive(), (
        "loop() still waits", [th.is_alive() for th in threads], docs)
    assert docs["2"]["status"] == int(STATUS.FAILED)
    assert docs["2"]["repetitions"] == MAX_JOB_RETRIES
    assert stats["map"]["failed"] == 1
    assert "f2" not in faulty_mods.RESULT
    for th in threads:
        th.join(timeout=30)


def test_dead_worker_lease_reaped_end_to_end(corpus):
    """A zombie claims a job and never runs it; the server's lease reaper
    puts it back and a live worker finishes — no reference equivalent
    (missing dead-worker reaping, SURVEY.md §5)."""
    from mapreduce_tpu.coord.connection import Connection
    from mapreduce_tpu.coord.task import Task

    faulty_mods.reset(corpus)
    connstr = f"mem://{uuid.uuid4().hex}"
    server = Server(connstr, "ft3", job_lease=0.3)
    server.configure(_params(corpus))
    # plan the map phase, then let a zombie grab a job pre-workers
    server.task.create_collection(TASK_STATUS.WAIT, server.params, 1)
    server._prepare_map()
    zombie_task = Task(Connection(connstr, "ft3"), job_lease=0.3)
    job, _ = zombie_task.take_next_job("zombie", "t")
    assert job is not None
    threads = spawn_worker_threads(connstr, "ft3", 2)
    server._poll_phase(server.task.map_jobs_ns(), "map")
    server._prepare_reduce()
    server._poll_phase(server.task.red_jobs_ns(), "reduce")
    stats = server._compute_stats()
    server._final()
    for t in threads:
        t.join(timeout=30)
    assert faulty_mods.RESULT == naive.wordcount(corpus)
    assert stats["map"]["failed"] == 0
    # the zombie's job really did go through BROKEN (repetitions > 0)
    docs = server.cnn.connect().find(server.task.map_jobs_ns(),
                                     {"_id": job["_id"]})
    assert docs[0]["repetitions"] >= 1
    assert docs[0]["status"] == int(STATUS.WRITTEN)


def test_interleaved_transient_failures_dont_kill_worker(corpus):
    """Regression: the worker's give-up counter must track CONSECUTIVE
    failures, not lifetime ones.  Every one of the 4 map jobs fails its
    first attempt and succeeds on retry — 4 lifetime failures but never
    more than 1 in a row.  A lifetime counter hits MAX_WORKER_RETRIES=3
    and the single worker abandons the task mid-phase; the consecutive
    counter never trips and the task completes exactly."""
    import threading

    faulty_mods.reset(corpus, fail_first_per_key=True)
    connstr = f"mem://{uuid.uuid4().hex}"
    params = _params(corpus)
    server = Server(connstr, "ft7")
    server.configure(params)
    threads = spawn_worker_threads(connstr, "ft7", 1,
                                   conf={"max_iter": 200})
    stats = {}
    done = threading.Event()

    def drive():
        stats.update(server.loop())
        done.set()

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    # bounded wait so a reintroduced lifetime counter fails loudly here
    # instead of hanging the suite on the server's poll loop
    assert done.wait(timeout=60), (
        "task did not finish: worker likely gave up on interleaved "
        "transient failures (lifetime-failure counting regression)")
    for th in threads:
        th.join(timeout=30)
    assert faulty_mods.RESULT == naive.wordcount(corpus)
    assert stats["map"]["failed"] == 0


def test_server_crash_resume_at_reduce(corpus):
    """Kill the server after map completed and reduce was planned; a new
    server must resume at REDUCE (skip map) and finish correctly
    (server.lua:468-491 restore path)."""
    faulty_mods.reset(corpus)
    connstr = f"mem://{uuid.uuid4().hex}"
    params = _params(corpus)
    threads = spawn_worker_threads(connstr, "ft4", 2,
                                   conf={"max_iter": 200})
    s1 = Server(connstr, "ft4")
    s1.configure(params)
    s1.task.create_collection(TASK_STATUS.WAIT, s1.params, 1)
    s1._prepare_map()
    s1._poll_phase(s1.task.map_jobs_ns(), "map")
    s1._prepare_reduce()
    del s1  # server "crashes" here; task doc says REDUCE

    s2 = Server(connstr, "ft4")
    s2.configure(params)
    stats = s2.loop()
    for t in threads:
        t.join(timeout=30)
    assert faulty_mods.RESULT == naive.wordcount(corpus)
    assert s2.task.finished()
    assert stats["reduce"]["failed"] == 0


def test_server_crash_resume_at_map(corpus):
    """Crash mid-MAP: a restarted server must not recreate WRITTEN jobs
    (their output files already exist) and must finish correctly."""
    faulty_mods.reset(corpus)
    connstr = f"mem://{uuid.uuid4().hex}"
    params = _params(corpus)
    s1 = Server(connstr, "ft5")
    s1.configure(params)
    s1.task.create_collection(TASK_STATUS.WAIT, s1.params, 1)
    s1._prepare_map()
    # one worker drains the whole map board, then the server dies before
    # reduce planning
    threads = spawn_worker_threads(connstr, "ft5", 1)
    s1._poll_phase(s1.task.map_jobs_ns(), "map")
    n_written = s1.cnn.connect().count(
        s1.task.map_jobs_ns(), {"status": int(STATUS.WRITTEN)})
    assert n_written == 4
    del s1

    s2 = Server(connstr, "ft5")
    s2.configure(params)
    threads += spawn_worker_threads(connstr, "ft5", 1)
    s2.loop()
    for t in threads:
        t.join(timeout=30)
    assert faulty_mods.RESULT == naive.wordcount(corpus)
    # no duplicated map work: still exactly 4 map jobs, all WRITTEN
    docs = s2.cnn.connect().find(s2.task.map_jobs_ns())
    assert len(docs) == 4
    assert all(d["status"] == int(STATUS.WRITTEN) for d in docs)


def test_worker_death_between_finished_and_written_is_reaped(corpus):
    """A worker dying AFTER mark_as_finished but BEFORE mark_as_written
    leaves the job in FINISHED — non-terminal.  The lease reaper must treat
    FINISHED like RUNNING (advisor finding r1) or the server's poll loop
    would hang forever waiting on an unreapable job."""
    from mapreduce_tpu.coord.connection import Connection
    from mapreduce_tpu.coord.task import Task

    faulty_mods.reset(corpus)
    connstr = f"mem://{uuid.uuid4().hex}"
    server = Server(connstr, "ft6", job_lease=0.3)
    server.configure(_params(corpus))
    server.task.create_collection(TASK_STATUS.WAIT, server.params, 1)
    server._prepare_map()
    # zombie claims a job and "dies" right after the FINISHED transition
    zombie_task = Task(Connection(connstr, "ft6"), job_lease=0.3)
    job, _ = zombie_task.take_next_job("zombie", "t")
    assert job is not None
    server.cnn.connect().update(
        server.task.map_jobs_ns(), {"_id": job["_id"]},
        {"$set": {"status": int(STATUS.FINISHED)}})
    threads = spawn_worker_threads(connstr, "ft6", 2)
    server._poll_phase(server.task.map_jobs_ns(), "map")
    server._prepare_reduce()
    server._poll_phase(server.task.red_jobs_ns(), "reduce")
    server._compute_stats()
    server._final()
    for t in threads:
        t.join(timeout=30)
    assert faulty_mods.RESULT == naive.wordcount(corpus)
    docs = server.cnn.connect().find(server.task.map_jobs_ns(),
                                     {"_id": job["_id"]})
    assert docs[0]["repetitions"] >= 1
    assert docs[0]["status"] == int(STATUS.WRITTEN)
