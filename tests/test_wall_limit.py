"""The wall limit of tests/conftest.py: one constant, every test, and no
wait inside a test that is longer than it."""

import faulthandler
import glob
import os
import re
import signal
import subprocess
import sys
import threading
import time

import pytest

from tests.conftest import TEST_LIMIT_S, wall_limit

HERE = os.path.dirname(os.path.abspath(__file__))


def _sleeps(box):
    time.sleep(5)


def _waits_for_a_child(box):
    box["child"] = subprocess.Popen(["sleep", "5"])
    try:
        box["child"].wait(timeout=5)
    finally:
        # what every test that starts a child does: kill, then wait
        box["child"].kill()
        box["child"].wait(timeout=5)


def _waits_for_an_event(box):
    threading.Event().wait(timeout=5)


@pytest.mark.parametrize(
    "body", [_sleeps, _waits_for_a_child, _waits_for_an_event])
def test_a_wait_past_the_limit_fails_with_the_stacks(body):
    box = {}
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as exc:
        with wall_limit(0.2, "tests/x.py::the_case"):
            body(box)
    assert time.monotonic() - t0 < 1.0
    msg = str(exc.value)
    assert "tests/x.py::the_case: wall limit of 0.2 s exceeded" in msg
    # every thread's stack, the waiting frame of this one among them
    assert "Current thread" in msg and f"in {body.__name__}" in msg
    if "child" in box:
        assert box["child"].poll() is not None


@pytest.fixture
def calls(monkeypatch):
    """What is asked of the process's one dump_traceback_later slot."""
    calls = []
    monkeypatch.setattr(faulthandler, "dump_traceback_later",
                        lambda t, exit, file: calls.append(("arm", t, exit)))
    monkeypatch.setattr(faulthandler, "cancel_dump_traceback_later",
                        lambda: calls.append(("cancel",)))
    return calls


def test_a_body_that_ends_in_time_leaves_the_outer_limit_as_it_was(calls):
    outer = signal.getsignal(signal.SIGALRM)
    left = signal.getitimer(signal.ITIMER_REAL)[0]
    with wall_limit(0.2, "inner"):
        assert signal.getsignal(signal.SIGALRM) is not outer
        assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 0.2
    # the hook's handler and what was left of its timer are back
    assert signal.getsignal(signal.SIGALRM) is outer
    assert left - 1.0 < signal.getitimer(signal.ITIMER_REAL)[0] <= left
    # the one dump_traceback_later slot: the inner's last resort at twice
    # its limit, cancelled at its exit, the hook's armed again
    assert [c[0] for c in calls] == ["cancel", "arm", "cancel", "arm"]
    assert calls[1][1:] == (pytest.approx(0.4, abs=0.05), True)
    assert TEST_LIMIT_S < calls[3][1] <= 2 * TEST_LIMIT_S and calls[3][2]
    time.sleep(0.3)  # the inner's timer is gone: nothing fires


def test_with_no_limit_open_nothing_is_left_armed(calls, monkeypatch):
    # what the hook leaves behind when the last test has run
    import tests.conftest as conftest
    monkeypatch.setattr(conftest, "_LAST_RESORTS", [])
    outer = signal.signal(signal.SIGALRM, signal.SIG_DFL)
    left = signal.setitimer(signal.ITIMER_REAL, 0)[0]
    try:
        with wall_limit(0.2, "only"):
            pass
        assert signal.getsignal(signal.SIGALRM) is signal.SIG_DFL
        assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
        assert [c[0] for c in calls] == ["cancel", "arm", "cancel"]
    finally:
        signal.signal(signal.SIGALRM, outer)
        signal.setitimer(signal.ITIMER_REAL, left)


def test_the_hook_holds_every_test_to_the_one_limit(request):
    assert 3 * 57 <= TEST_LIMIT_S <= 180  # the slowest case: conftest.py
    assert callable(signal.getsignal(signal.SIGALRM))
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= TEST_LIMIT_S
    assert threading.current_thread() is threading.main_thread()
    # pytest's faulthandler_timeout uses the process's one
    # dump_traceback_later slot from a wrapper inside the hook's: set, it
    # would replace the last resort and cancel it when the test ends
    assert not float(request.config.getini("faulthandler_timeout") or 0)


def test_the_last_resort_ends_a_process_the_handler_cannot_reach():
    # SIGALRM masked, as where the main thread never returns to bytecode
    code = (
        "import signal, time\n"
        "from tests.conftest import wall_limit\n"
        "signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})\n"
        "with wall_limit(0.3, 'stuck'):\n"
        "    time.sleep(30)\n")
    proc = subprocess.run([sys.executable, "-c", code], text=True,
                          cwd=os.path.dirname(HERE), capture_output=True,
                          timeout=60)
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "Timeout (0:00:00" in proc.stderr, proc.stderr[-2000:]
    assert "<module>" in proc.stderr


def test_no_wait_in_a_test_is_unbounded_or_longer_than_the_limit():
    offenders = []
    for path in sorted(glob.glob(os.path.join(HERE, "*.py"))):
        with open(path, encoding="utf-8") as f:
            for n, line in enumerate(f, 1):
                where = f"{os.path.basename(path)}:{n}"
                for value in re.findall(r"timeout=([0-9][0-9.]*)", line):
                    if float(value) > TEST_LIMIT_S:
                        offenders.append(f"{where} timeout={value}")
                if re.search(r"\.(join|wait|communicate)\(\)", line):
                    offenders.append(f"{where} {line.strip()}")
    assert not offenders, offenders


def test_a_dead_workers_files_go_back_without_what_is_done():
    # the queue as xdist 3.8.0's loadscope scheduler leaves it when a worker
    # dies: every file the worker had, nodeid -> done
    import types

    from tests.conftest import pytest_handlecrashitem
    sched = types.SimpleNamespace(workqueue={
        "tests/a.py": {"tests/a.py::one": True, "tests/a.py::two": True},
        "tests/b.py": {"tests/b.py::one": True, "tests/b.py::hung": False,
                       "tests/b.py::three": False},
        "tests/c.py": {"tests/c.py::hung": False}})
    pytest_handlecrashitem("tests/b.py::hung", None, sched)
    assert sched.workqueue == {"tests/b.py": {
        "tests/b.py::one": True, "tests/b.py::hung": True,
        "tests/b.py::three": False}, "tests/c.py": {"tests/c.py::hung": False}}
    pytest_handlecrashitem("x", None, types.SimpleNamespace())  # --dist load
