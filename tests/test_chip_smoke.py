"""chip_smoke.py on the CPU: the "run it here first" step kept as a test.

The smoke itself only means something on the TPU (it exits non-zero
anywhere else, before any phase).  What tier-1 can hold it to: importing
it touches no device, ``main()`` refuses the CPU, and the phase
functions — the code the chip runs — pass on a CPU mesh at tiny sizes,
kernels under the interpreter (control flow and correctness; no speed).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def cache_config_restored():
    """chip_smoke.main and the CLI it drives legitimately place the
    PROCESS-WIDE compile cache; the shared test process gets it back."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_import_touches_no_device():
    """A fresh interpreter that imports chip_smoke has not loaded jax —
    so it cannot have initialised a backend or claimed a chip."""
    code = ("import sys, chip_smoke; "
            "assert 'jax' not in sys.modules, 'import pulled in jax'")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=90)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_main_refuses_the_cpu(capsys, monkeypatch):
    """On the CPU main() exits non-zero BEFORE any phase, names the
    device it found, and prints no result."""
    def no_phase(*a, **kw):
        raise AssertionError("a phase ran on the CPU")

    for phase in ("engine_phase", "trainer_phase", "loop_trainer_phase",
                  "cache_check"):
        monkeypatch.setattr(chip_smoke, phase, no_phase)
    import jax

    assert chip_smoke.main() != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out == [f"platform=cpu device_kind={jax.devices()[0].device_kind}"
                   f" devices={len(jax.devices())}"]


def test_report_ends_on_the_exact_verdict(capsys):
    """The last stdout line is the verdict the driver parses: exactly
    ``ok`` and ``device``{platform, kind, count}; everything else rides
    the summary line above it, which closes on ``"claim": null``."""
    chip_smoke.report({"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
                      {"engine": {"waves": 3}}, 12.3)
    summary, verdict = capsys.readouterr().out.strip().splitlines()
    assert json.loads(verdict) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert summary.endswith('"claim": null}')
    assert json.loads(summary)["phases"] == {"engine": {"waves": 3}}


def test_fails_without_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it fails, and prints no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=90,
                          env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.fixture
def tiny_waves(monkeypatch):
    """Shrink the engine's wave to 4 chunks per device, so a kilobyte
    corpus spans 3 waves (9000 bytes rounds to 4 rows at both row
    lengths in play: DeviceWordCount's 2560 and the wordcount module's
    2048)."""
    from mapreduce_tpu.engine.device_engine import DeviceEngine

    monkeypatch.setattr(DeviceEngine, "WAVE_BYTES", 9000)
    return 2048


def test_engine_phase_on_a_cpu_mesh(tiny_waves):
    """Both entry points against the oracle, the exchange matrix against
    its host recompute on 2 devices, kernel builds counted (interpret
    here), served fold == all-lax fold — over 3 waves."""
    from mapreduce_tpu.engine.device_engine import EngineConfig
    from mapreduce_tpu.parallel import make_mesh

    cfg = EngineConfig(local_capacity=4096, exchange_capacity=2048,
                       out_capacity=4096, tile=512, tile_records=104,
                       combine_in_scan=True, combine_capacity=1024,
                       segment_impl="pallas", tokenize_impl="pallas")
    out = chip_smoke.engine_phase(make_mesh(n_data=2), cfg,
                                  chunk_len=tiny_waves)
    assert out["waves"] == 3
    assert out["kernels"] == ["segreduce", "tokenize"]
    assert out["exchange_records"] > 0


def test_trainer_phases_on_a_cpu_mesh():
    """Five falling steps on a 2-way sequence-parallel mesh, then the
    "loop" trainer through cli train."""
    from mapreduce_tpu.models.transformer import TransformerConfig
    from mapreduce_tpu.parallel import make_mesh

    out = chip_smoke.trainer_phase(
        make_mesh(n_data=2),
        TransformerConfig(vocab=64, embed=32, n_layers=1, n_heads=2,
                          head_dim=16, ffn=64),
        batch=2, seq_per_device=16)
    assert len(out["losses"]) == 5 and out["seq_len"] == 32
    assert chip_smoke.loop_trainer_phase(epochs=2)["epochs_run"] == 2


def test_cache_check_holds_the_one_directory(tmp_path, monkeypatch):
    """cache_check passes when the run's programs landed in the
    directory in force, and fails when another cache directory was
    written behind it."""
    from mapreduce_tpu.obs.compile import LEDGER, REGISTRY_BASENAME
    from mapreduce_tpu.utils import compile_cache

    cache = str(tmp_path / "cache")
    other = str(tmp_path / "other")
    os.makedirs(cache)
    monkeypatch.setenv(compile_cache.ENV_VAR, cache)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", other)
    monkeypatch.setattr(compile_cache, "USER_DIR", str(tmp_path / "user"))
    monkeypatch.setattr(LEDGER, "snapshot", lambda: {"programs": {
        p: {"compiled": 1, "persistent_hit": 0, "compile_s": 1.0}
        for p in ("wave", "tf_step")}})
    before = {d: chip_smoke._listing(d)
              for d in (cache, other, str(tmp_path / "user"))}
    assert compile_cache.enable_persistent_cache() == cache
    for name in (REGISTRY_BASENAME, "jit_wave-abc"):
        open(os.path.join(cache, name), "w").close()
    out = chip_smoke.cache_check(cache, before)
    assert out["new_entries"] == 1
    json.dumps(out)
    os.makedirs(other)
    open(os.path.join(other, "stray"), "w").close()
    with pytest.raises(AssertionError, match="second cache"):
        chip_smoke.cache_check(cache, before)
