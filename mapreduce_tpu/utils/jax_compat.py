"""Shared JAX call-site helpers for the device plane.

Written for the installed JAX (0.9.0): call sites spell ``jax.shard_map``
and ``jax.lax.pcast`` directly.  What stays here is
:func:`quiet_unusable_donation` — the shared scoped filter for the
expected "donated buffers were not usable" warning at the places that
donate inputs purely to free them (engine wave inputs, trainer epoch
batches).
"""

from __future__ import annotations

import contextlib
import warnings


@contextlib.contextmanager
def quiet_unusable_donation():
    """Scoped suppression of jax's "Some donated buffers were not
    usable" warning — the ONE shared helper for code that donates
    buffers purely for their free-on-consumption semantics (the
    engine's wave inputs, the trainer's stacked epoch batches), where
    no output aliases them and the warning is expected once per
    lowering.  Always a call-site context, never a process-wide filter
    install, so a genuine donation failure anywhere else keeps its
    diagnostic.  (``warnings.catch_warnings`` mutates global filter
    state, so callers keep the scope to their own compile/dispatch
    sites and enter it once per loop, not once per call, to minimise
    the cross-thread window.)"""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message=r"Some donated buffers were not usable")
        yield
