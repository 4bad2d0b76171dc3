"""Multi-process coordination: workers in separate OS processes talking to
the server through the job board and the blob plane — the reference's real
deployment topology (N worker processes + one mongod, test.sh:10 launches
workers under screen)."""

import collections
import contextlib
import os
import time

import pytest

from mapreduce_tpu import spec, storage
from mapreduce_tpu.server import Server
from tests.cli_workers import EXIT_S, REPO, child_env, cli_workers


@pytest.fixture(autouse=True)
def fresh_modules():
    spec.clear_caches()
    yield
    spec.clear_caches()


@contextlib.contextmanager
def _dir_planes(tmp_path):
    """dir:// docstore + shared-dir storage: one filesystem."""
    yield f"dir://{tmp_path}/ctrl", f"shared:{tmp_path}/blobs"


@contextlib.contextmanager
def _http_planes(tmp_path):
    """The networked control plane (VERDICT r3 item 1): task claims over a
    DocServer (``http://`` connstr), and every byte — inputs, intermediate
    map files, results — through a BlobServer (``http:`` storage).  The
    only things server and workers share are two TCP sockets; the
    reference needed exactly this from mongod (cnn.lua:34-39,
    worker.lua:20-27)."""
    from mapreduce_tpu.coord.docserver import DocServer
    from mapreduce_tpu.storage import BlobServer

    docsrv = DocServer().start_background()
    blobsrv = BlobServer(str(tmp_path / "blobroot")).start_background()
    try:
        yield (f"http://127.0.0.1:{docsrv.port}",
               f"http:127.0.0.1:{blobsrv.port}")
    finally:
        docsrv.shutdown()
        blobsrv.shutdown()


def _wordcount(storage_dsl, blobs):
    """Server params: tests/netwc_mod.py over *blobs* of *storage_dsl*."""
    m = "tests.netwc_mod"
    params = {r: m for r in ("taskfn", "mapfn", "partitionfn", "reducefn",
                             "finalfn", "combinerfn")}
    params["storage"] = storage_dsl
    params["init_args"] = {"blobs": blobs, "num_reducers": 5,
                           "storage": storage_dsl}
    return params


@pytest.mark.parametrize("planes", [_dir_planes, _http_planes],
                         ids=["dir", "http"])
def test_worker_processes(tmp_path, planes):
    with planes(tmp_path) as (connstr, storage_dsl):
        # stage the inputs as blobs: workers never read this test's files
        st = storage.router(storage_dsl)
        expected = collections.Counter()
        blobs = []
        for i in range(4):
            text = f"alpha beta p{i} gamma alpha delta\n" * 10
            expected.update(text.split())
            name = f"input/f{i}.txt"
            st.write(name, text)
            blobs.append(name)

        with cli_workers(connstr, "wcmp", 2, threads=2) as workers:
            server = Server(connstr, "wcmp")
            server.configure(_wordcount(storage_dsl, blobs))
            stats = server.loop()
            from tests.netwc_mod import RESULT
            assert RESULT == dict(expected)
            assert stats["map"]["failed"] == 0
            # the map work really happened in the child processes: this
            # process never imported the job executor for those jobs —
            # check via worker names recorded in the job docs
            docs = server.cnn.connect().find(server.task.map_jobs_ns())
            assert docs and all(d.get("worker") for d in docs)
    # every worker process exited cleanly: after the task, or after it
    # found nothing left to do
    assert workers.rcs == [0, 0], workers.tails()


def test_cli_workers_yields_only_once_a_late_child_is_up(tmp_path):
    """The race the helper closes: one child spends three seconds before
    it reaches the worker, the other runs a task of a fifth of a second
    alone.  Started by hand, the late one found the task FINISHED and
    idled out its ``--max-iter``; here the work starts when both poll."""
    late_s = 3.0
    (tmp_path / "sitecustomize.py").write_text(
        "import os, time\ntime.sleep(float(os.environ['LATE_S']))\n")
    path = os.pathsep.join((str(tmp_path), REPO))
    st = storage.router(f"shared:{tmp_path}/blobs")
    st.write("in", "alpha beta alpha\n")
    t0 = time.monotonic()
    with cli_workers(f"dir://{tmp_path}/ctrl", "late", 2,
                     envs=[child_env(PYTHONPATH=path, LATE_S="0"),
                           child_env(PYTHONPATH=path, LATE_S=str(late_s))]
                     ) as workers:
        assert time.monotonic() - t0 >= late_s
        server = Server(f"dir://{tmp_path}/ctrl", "late")
        server.configure(_wordcount(f"shared:{tmp_path}/blobs", ["in"]))
        server.loop()
        t_done = time.monotonic()
    assert workers.rcs == [0, 0], workers.tails()
    assert time.monotonic() - t_done <= EXIT_S
    assert all(p.poll() is not None for p in workers.procs)
