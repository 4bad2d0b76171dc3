"""Test bootstrap: force an 8-device virtual CPU mesh before JAX loads.

Multi-chip hardware is not available in CI; all sharding/collective tests run
over ``--xla_force_host_platform_device_count=8`` CPU devices (the rebuild's
answer to the reference's "fake cluster = N local processes + localhost ssh",
SURVEY.md §4).
"""

import os
import sys

# force, not setdefault: tests run on the CPU, whatever accelerator the
# machine has.  MAPREDUCE_TPU_TESTS=1 opts OUT of the pin for the
# hardware-gated tests (the compiled-Mosaic cases): run
#   MAPREDUCE_TPU_TESTS=1 pytest tests/test_flash_attention.py -k tpu
# on a machine with a chip.
_USE_TPU = os.environ.get("MAPREDUCE_TPU_TESTS") == "1"
if not _USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

if not _USE_TPU:
    jax.config.update("jax_platforms", "cpu")


# -- the wall limit --------------------------------------------------------
# How long a test may take, and what happens when it takes longer, is
# decided here and nowhere else: no marker, option or environment
# variable lifts it, and no wait inside a test may be longer.  A test
# that cannot fit is `slow` in the sense of pytest.ini.

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import hashlib  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

# The two slowest cases of a whole run under the driver's command take 38
# to 57 s (test_profile.py's bench.py --check --smoke, test_transformer.py's
# chunked ring attention); 89 s was seen with other work on the same cores
# (PR 30).
TEST_LIMIT_S = 180.0

# deadlines (time.monotonic) of the last resorts armed by the
# wall_limit()s now open, outermost first: the process has ONE
# dump_traceback_later slot, so an inner limit's exit re-arms the outer's
_LAST_RESORTS = []


# where the last resort writes: inside a test fd 2 is pytest's capture
# file, and what goes there is lost with the process
_stderr_fd = 2


def pytest_configure(config):
    global _stderr_fd
    _stderr_fd = os.dup(2)  # capture is off here: the real one


def _arm_last_resort():
    faulthandler.cancel_dump_traceback_later()
    if _LAST_RESORTS:
        faulthandler.dump_traceback_later(
            max(_LAST_RESORTS[-1] - time.monotonic(), 0.01), exit=True,
            file=_stderr_fd)


@contextlib.contextmanager
def wall_limit(seconds, what):
    """Fail *what* alone once it has run for *seconds*.

    At *seconds* a SIGALRM handler on the main thread writes every
    thread's stack and raises ``pytest.fail``: sleeps, ``Popen.wait``,
    ``Event.wait``, socket reads and ``Thread.join`` are interrupted, the
    test fails with its own evidence and the run goes on.  At twice
    *seconds* the last resort, for the hang no handler reaches (the main
    thread inside a C call that never returns to bytecode, or the signal
    masked): faulthandler's watchdog thread writes the stacks and exits
    the process, which xdist reports as a crash of the case running.
    Main thread only; limits nest, and the exit restores the outer one.
    """
    def on_alarm(signum, frame):
        with tempfile.TemporaryFile() as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read().decode("utf-8", "replace")
        pytest.fail(f"{what}: wall limit of {seconds:g} s exceeded\n"
                    f"{stacks}", pytrace=False)

    t0 = time.monotonic()
    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    old_delay, _ = signal.setitimer(signal.ITIMER_REAL, seconds)
    _LAST_RESORTS.append(t0 + 2 * seconds)
    _arm_last_resort()
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)
        if old_delay:
            signal.setitimer(
                signal.ITIMER_REAL,
                max(old_delay - (time.monotonic() - t0), 0.01))
        _LAST_RESORTS.pop()
        _arm_last_resort()


# What xdist 3.8.0 does under --dist loadfile when a worker dies (the last
# resort, or a crash) needs two repairs, both shown on PR 30's planted
# hangs.  Its scheduler puts ALL of the dead worker's files back on its
# queue, those already done and the case that ended the worker too, and
# hands a worker one file at a time, the next when a case of the last one
# ends.  So (1) a file with nothing left to run starves the worker that
# gets it and the session never ends, and (2) the case that ended one
# worker is run again by the next, until --max-worker-restart is spent.

@pytest.hookimpl(optionalhook=True)  # xdist's; absent under -p no:xdist
def pytest_handlecrashitem(crashitem, report, sched):
    # (1), on the controller: leave on the queue only what is left to run,
    # less the case that ended the worker
    queue = getattr(sched, "workqueue", None)  # the loadscope family's
    for scope in list(queue or ()):
        if crashitem in queue[scope]:
            queue[scope][crashitem] = True
        if all(queue[scope].values()):
            del queue[scope]


_ENDED_A_WORKER = pytest.StashKey[bool]()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    # (2), where the scheduler had already sent the file on to a worker
    # that was still up: a file says which case a worker is running, and
    # the worker that is handed a case whose file is there does not run it
    run = os.environ.get("PYTEST_XDIST_TESTRUNUID")  # one a run, every worker
    running = run and os.path.join(
        tempfile.gettempdir(), "mrtpu-running-%s-%s" % (
            run, hashlib.sha1(item.nodeid.encode()).hexdigest()))
    item.stash[_ENDED_A_WORKER] = bool(running) and os.path.exists(running)
    if running:
        open(running, "w").close()
    try:
        # set-up, call and tear-down: fixtures hang too
        with wall_limit(TEST_LIMIT_S, item.nodeid):
            yield
    finally:
        if running:
            os.unlink(running)


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    if item.stash[_ENDED_A_WORKER]:
        pytest.fail(f"{item.nodeid} was running when an earlier worker of "
                    f"this run ended (its last resort at {2 * TEST_LIMIT_S:g}"
                    " s, or a crash): not run again", pytrace=False)


@pytest.hookimpl(trylast=True)
def pytest_exception_interact():
    # pytest's faulthandler plugin cancels the pending dump at every
    # failure (for pdb's sake); the tear-down still has to end
    _arm_last_resort()


# -- failure telemetry artifacts (@pytest.mark.telemetry) -------------------
# A failing chaos test is a distributed-systems flake by construction;
# a bare assertion message is useless without the run's telemetry.  On
# failure of any test marked `telemetry`, dump the process's /metrics
# exposition and Chrome trace to MRTPU_TEST_ARTIFACTS (default:
# .test-artifacts/ next to the repo root) and name the paths in the
# report, so the flake arrives with its own evidence attached.

import re  # noqa: E402

ARTIFACT_ROOT = os.environ.get(
    "MRTPU_TEST_ARTIFACTS",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".test-artifacts"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.when != "call" or not rep.failed:
        return
    if item.get_closest_marker("telemetry") is None:
        return
    try:
        from mapreduce_tpu.obs.metrics import REGISTRY
        from mapreduce_tpu.obs.trace import TRACER

        slug = re.sub(r"[^A-Za-z0-9_.-]+", "_", item.nodeid)[-120:]
        outdir = os.path.join(ARTIFACT_ROOT, slug)
        os.makedirs(outdir, exist_ok=True)
        metrics_path = os.path.join(outdir, "metrics.prom")
        with open(metrics_path, "w", encoding="utf-8") as f:
            f.write(REGISTRY.render())
        trace_path = TRACER.export(os.path.join(outdir, "trace.json"))
        rep.sections.append(
            ("telemetry artifacts",
             f"metrics: {metrics_path}\ntrace:   {trace_path}"))
    except Exception as exc:
        # artifact capture must never mask the real failure
        rep.sections.append(
            ("telemetry artifacts", f"capture failed: {exc!r}"))
