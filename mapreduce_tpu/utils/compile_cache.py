"""Persistent XLA compilation cache setup, shared by every entry point
(the CLI commands, bench.py, bench_train.py, chip_smoke.py).

ONE rule places the cache.  Where ``$JAX_COMPILATION_CACHE_DIR`` is set,
the cache lives there and this module sets no other: the variable is
never rewritten and ``jax.config`` is never pointed elsewhere, so
whoever launched the process decides (a chip harness that keeps one
directory warm across runs, a test's throwaway directory).  Where it is
not set, an explicit *path* argument applies (``warmup --cache-dir``),
else the checkout-adjacent ``.jax_cache`` (:data:`DEFAULT_DIR`).  The
shape-bucket registry (obs/compile) lives inside whichever directory is
in force.

Why a cache at all: the device engine's wave program is dominated by
the ``lax.sort`` comparator's compile, and the engine's auto wave split
is corpus-size-independent, so one warm entry serves every corpus on
the machine.  (Compile seconds on current hardware: see PERF.md.)
"""

from __future__ import annotations

import os
from typing import Optional

#: JAX's own variable: where it is set, it alone places the cache
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: default cache location: alongside the repo/package installation
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: fallback for read-only installs (site-packages): a user cache dir —
#: warmup must not silently fail to persist the compile it exists to
#: avoid
USER_DIR = os.path.join(
    os.environ.get("XDG_CACHE_HOME",
                   os.path.join(os.path.expanduser("~"), ".cache")),
    "mapreduce_tpu", "jax_cache")


def writable_dir(path: str) -> bool:
    """True when *path* exists (or can be created) and accepts writes —
    the check ``cmd_warmup`` HARD-FAILS on, because a warmup that
    persists nothing silently re-pays the cold compile forever."""
    try:
        os.makedirs(path, exist_ok=True)
        # pid-suffixed: concurrent probers (bench_host's worker fleet)
        # must not race on one name and wrongly divert to USER_DIR
        probe = os.path.join(path, f".write_probe.{os.getpid()}")
        with open(probe, "w"):
            pass
        try:
            os.remove(probe)
        except FileNotFoundError:
            pass
        return True
    except OSError:
        return False


def enable_persistent_cache(path: Optional[str] = None) -> str:
    """Turn XLA's persistent compilation cache on at the directory the
    module rule picks (``$JAX_COMPILATION_CACHE_DIR``, else *path*,
    else the checkout-adjacent ``.jax_cache``, else — when that is not
    writable — the user cache dir).  Idempotent; returns the path."""
    import jax

    path = _resolve_dir(path)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path


def _resolve_dir(path: Optional[str] = None) -> str:
    path = os.environ.get(ENV_VAR) or path
    if not path:
        for cand in (DEFAULT_DIR, USER_DIR):
            if writable_dir(cand):
                path = cand
                break
        else:  # nothing writable: persist nowhere, but SAY so
            path = USER_DIR
            import logging

            logging.getLogger("mapreduce_tpu.compile_cache").warning(
                "no writable compile-cache dir (tried %s, %s): every "
                "process will re-pay the cold compile; set $%s to a "
                "writable path", DEFAULT_DIR, USER_DIR, ENV_VAR)
    return path


def enable_persistent_cache_lazy(path: Optional[str] = None) -> str:
    """The production-entrypoint form of :func:`enable_persistent_cache`:
    place the cache WITHOUT forcing a jax import.

    The worker/docserver processes are deliberately jax-free
    (obs/buildinfo keeps them that way); importing jax just to set a
    config knob would cost them seconds of startup and megabytes of
    memory for nothing.  When jax is not yet imported and the variable
    is unset, the chosen dir travels in ``$JAX_COMPILATION_CACHE_DIR``
    (jax reads it at import time — and XLA initialises the persistent
    cache lazily at the FIRST compile, so the variable governs any jax
    the process loads later); a variable that IS set already says
    everything and is left alone.  When jax is already imported
    (embedders, the server's device path), fall through to the
    config-update form — which must still run before the process's
    first compile, or XLA has already latched the cache off."""
    import sys

    if "jax" in sys.modules:
        return enable_persistent_cache(path)
    path = _resolve_dir(path)
    if not os.environ.get(ENV_VAR):
        os.environ[ENV_VAR] = path
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1.0")
    return path
