"""``cli worker`` children for a test that drives a Server beside them.

A worker can miss a short task in two ways, both the program behaving as
the reference does (worker.lua:97-138): it comes up after the task has
FINISHED, or it is up and sleeps through it on a backed-off poll.  Either
way it then idles for ``--max-iter`` polls.  So a test that starts a child
waits until the child is up before it starts the work, and the children
poll on a short leash: one with nothing to do says "no task appeared,
exiting" and exits 0 inside EXIT_S.
"""

import collections
import contextlib
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

UP_S = 45.0     # for every child to say it is up
EXIT_S = 45.0   # for every child to exit by itself, once the body is done
# a worker's polls stay short enough to catch a task that lives a fifth of
# a second, and its idle budget is 35 s from its first poll to "no task
# appeared": under EXIT_S whenever the work started at once
POLL = ("--max-iter", "350", "--max-sleep", "0.1")


def child_env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(extra)
    return env


class _Child:
    """One worker process and the thread that drains its stderr."""

    def __init__(self, argv, env, threads):
        self.proc = subprocess.Popen(argv, env=env, text=True,
                                     stdout=subprocess.DEVNULL,
                                     stderr=subprocess.PIPE)
        self.tail = collections.deque(maxlen=40)
        self.up = threading.Event()  # set at EOF too: check .started
        self.started = 0
        self._threads = threads
        self._reader = threading.Thread(target=self._drain, daemon=True)
        self._reader.start()

    def _drain(self):
        for line in self.proc.stderr:
            self.tail.append(line)
            # Worker.execute logs this before its first poll
            if " starting" in line and "worker " in line:
                self.started += 1
                if self.started == self._threads:
                    self.up.set()
        self.up.set()

    def stderr_tail(self):
        return "".join(self.tail)[-1500:]


class Workers:
    """What ``cli_workers`` yields; ``rcs`` is filled when it exits."""

    def __init__(self, children):
        self._children = children
        self.procs = [c.proc for c in children]
        self.rcs = None

    def tails(self):
        return [c.stderr_tail() for c in self._children]


@contextlib.contextmanager
def cli_workers(connstr, dbname, n, *, args=None, envs=None, threads=1):
    """Start *n* ``cli worker`` processes, yield once each has said it is
    up, and leave none alive.

    Child *i* gets the further arguments ``args[i]`` and the environment
    ``envs[i]``; *threads* is ``--workers``.  On exit the
    children get EXIT_S to exit by themselves; what is left is killed and
    reported in ``rcs`` as "killed".  The caller asserts on ``rcs`` with
    ``tails()`` as the message.
    """
    children = []
    try:
        for i in range(n):
            argv = [sys.executable, "-m", "mapreduce_tpu.cli", "worker",
                    connstr, dbname, "--workers", str(threads), *POLL,
                    *(args[i] if args else ())]
            children.append(
                _Child(argv, envs[i] if envs else child_env(), threads))
        ws = Workers(children)
        deadline = time.monotonic() + UP_S
        for c in children:
            c.up.wait(max(deadline - time.monotonic(), 0))
            assert c.started == threads and c.proc.poll() is None, (
                f"worker child not up in {UP_S:g} s "
                f"(exit code {c.proc.poll()}):\n{c.stderr_tail()}")
        yield ws
        deadline = time.monotonic() + EXIT_S
        ws.rcs = []
        for c in children:
            try:
                ws.rcs.append(c.proc.wait(max(deadline - time.monotonic(), 0)))
            except subprocess.TimeoutExpired:
                ws.rcs.append("killed")
    finally:
        for c in children:
            if c.proc.poll() is None:
                c.proc.kill()
            c.proc.wait(timeout=10)
            c._reader.join(timeout=10)
        assert all(c.proc.poll() is not None for c in children)

