"""Chaos suite for the multi-tenant service layer: admission +
fairness under worker churn and a FaultProxy partition between the
scheduler's board and the worker pool, with exactly-once PER TENANT
proven by the PR-1 execution-count witness pattern (tests/sched_mods),
and the cancelled-tenant guarantee (queued jobs never run) checked
under the same faults."""

import time
import uuid

import pytest

from mapreduce_tpu.coord.docserver import DocServer
from mapreduce_tpu.sched.scheduler import (
    ADMITTED, CANCELLED, DONE, RUNNING, Scheduler, SchedulerConfig)
from mapreduce_tpu.sched.service import (
    ScheduledWorker, TaskRunner, wait_for_state)
from mapreduce_tpu.testing.faults import FaultProxy
from mapreduce_tpu.utils.httpclient import RetryPolicy
from tests import sched_mods

CHAOS_RETRY = RetryPolicy(max_attempts=8, base_delay=0.02, max_delay=0.3,
                          deadline=20.0, breaker_threshold=0)

pytestmark = [pytest.mark.chaos, pytest.mark.telemetry]


def _tenant_params(name, tmp_path, n_files):
    files = []
    for i in range(n_files):
        p = tmp_path / f"{name}{i}.txt"
        p.write_text(f"alpha beta {name}{i} gamma alpha\n" * 4)
        files.append(str(p))
    sched_mods.reset(name, files)
    m = f"tests.sched_mod_{name}"
    params = {r: m for r in ("taskfn", "mapfn", "partitionfn",
                             "reducefn", "finalfn")}
    params["storage"] = f"mem:{uuid.uuid4().hex}"
    return params


def test_exactly_once_per_tenant_under_partition_and_churn(tmp_path):
    """Two tenants served by one cross-tenant pool THROUGH a fault
    proxy; mid-run the proxy partitions (shorter than the job lease:
    claims/heartbeats retry through with their request ids, nothing is
    re-issued) and one worker is killed and replaced (its unrun claims
    release back).  Both tenants finish with every job executed
    exactly once; a third tenant cancelled while QUEUED never runs a
    single map call."""
    board = DocServer().start_background()
    proxy = FaultProxy(board.host, board.port).start()
    runner = None
    workers = []
    try:
        direct = f"http://{board.host}:{board.port}"
        proxied = f"http://{proxy.address}"
        # max_inflight=2: a and b occupy the budget, c stays QUEUED —
        # admission control is what makes the cancel-a-queued-tenant
        # scenario real
        sch = Scheduler(board.store,
                        config=SchedulerConfig(max_inflight=2))
        runner = TaskRunner(direct, sch).start()
        workers = [ScheduledWorker(proxied, retry=CHAOS_RETRY,
                                   name=f"cw{i}").start()
                   for i in range(2)]
        da = sch.submit("alice", db="cha",
                        params=_tenant_params("a", tmp_path, 4),
                        est_jobs=4)
        db = sch.submit("bob", db="chb",
                        params=_tenant_params("b", tmp_path, 3),
                        est_jobs=3)
        dc = sch.submit("carol", db="chc",
                        params=_tenant_params("c", tmp_path, 2),
                        est_jobs=2)
        # admission order under the budget: a and b in, c queued
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            states = {d["_id"]: d["state"] for d in sch.list_tasks()}
            if (states[da["_id"]] in (ADMITTED, RUNNING, DONE)
                    and states[db["_id"]] in (ADMITTED, RUNNING, DONE)):
                break
            time.sleep(0.02)
        assert sch.get(dc["_id"])["state"] == "QUEUED"
        # cancel the queued tenant NOW — its jobs must never run
        assert sch.cancel(dc["_id"])["state"] == CANCELLED

        # worker churn: kill one worker mid-service, spawn a successor
        workers[0].stop(timeout=20)
        workers.append(ScheduledWorker(proxied, retry=CHAOS_RETRY,
                                       name="cw2").start())
        # partition the board<->worker path briefly (well under the
        # 60s job lease): claims and heartbeats retry through, rid
        # dedupe keeps every retried mutation exactly-once
        proxy.partition(duration=0.5)

        wait_for_state(sch, da["_id"], DONE, timeout=90)
        wait_for_state(sch, db["_id"], DONE, timeout=90)

        for name, n in (("a", 4), ("b", 3)):
            st = sched_mods.state(name)
            assert dict(st.STARTED) == {i: 1 for i in range(n)}, (
                name, dict(st.STARTED))
            assert dict(st.COMPLETED) == {i: 1 for i in range(n)}, (
                name, dict(st.COMPLETED))
            assert st.RESULT["alpha"] == n * 8
        # the cancelled tenant: zero executions, never admitted, and
        # its board carries nothing claimable
        assert dict(sched_mods.state("c").STARTED) == {}
        cdoc = sch.get(dc["_id"])
        assert cdoc["state"] == CANCELLED
        assert "admitted_time" not in cdoc
        assert board.store.count("chc.map_jobs") == 0
        # fairness accounting survived the faults: both served tenants
        # were charged, the cancelled one was not
        snap = sch.snapshot()
        assert snap["tenants"]["alice"]["served_cost"] == 4.0
        assert snap["tenants"]["bob"]["served_cost"] == 3.0
        assert snap["tenants"]["carol"]["served_cost"] == 0.0
    finally:
        if runner:
            runner.stop()
        for w in workers:
            w.stop(timeout=20)
        proxy.stop()
        board.shutdown()


def test_admission_keeps_weighted_fairness_under_churn(tmp_path):
    """Weighted-fair dequeue holds while workers churn: with
    max_inflight=1 and tenants at weight 1 vs 3, the admission
    SEQUENCE (recorded from the scheduler's own transitions) stays the
    deterministic 3:1 interleave whatever the worker pool is doing."""
    board = DocServer().start_background()
    runner = None
    workers = []
    try:
        direct = f"http://{board.host}:{board.port}"
        sch = Scheduler(board.store,
                        config=SchedulerConfig(max_inflight=1))
        # tiny single-file tasks so turnover is quick
        subs = []
        for i in range(2):
            subs.append(sch.submit(
                "small", params=_tenant_params("a", tmp_path, 1),
                weight=1.0, est_jobs=1))
        for i in range(6):
            subs.append(sch.submit(
                "big", params=_tenant_params("b", tmp_path, 1),
                weight=3.0, est_jobs=1))
        runner = TaskRunner(direct, sch).start()
        workers = [ScheduledWorker(direct, name="fw0").start()]
        # churn the pool while the queue drains
        give_up = time.monotonic() + 90
        churned = 0
        while time.monotonic() < give_up:
            done = [d for d in sch.list_tasks(state=DONE)]
            if len(done) == len(subs):
                break
            if churned < 3:
                workers.append(ScheduledWorker(
                    direct, name=f"fw{len(workers)}").start())
                workers[churned].stop(timeout=10)
                churned += 1
            time.sleep(0.2)
        done = sch.list_tasks(state=DONE)
        assert len(done) == len(subs), [d["state"] for d in
                                        sch.list_tasks()]
        order = [d["tenant"] for d in
                 sorted(done, key=lambda d: d["admitted_time"])]
        # both start at cost 0 (tie -> alphabetical: "big"), then the
        # served/weight ratios interleave big 3:1 over small
        assert order == ["big", "small", "big", "big", "big", "small",
                         "big", "big"], order
    finally:
        if runner:
            runner.stop()
        for w in workers:
            w.stop(timeout=20)
        board.shutdown()
