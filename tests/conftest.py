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


# -- failure telemetry artifacts (@pytest.mark.telemetry) -------------------
# A failing chaos test is a distributed-systems flake by construction;
# a bare assertion message is useless without the run's telemetry.  On
# failure of any test marked `telemetry`, dump the process's /metrics
# exposition and Chrome trace to MRTPU_TEST_ARTIFACTS (default:
# .test-artifacts/ next to the repo root) and name the paths in the
# report, so the flake arrives with its own evidence attached.

import re  # noqa: E402

import pytest  # noqa: E402

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
