"""Chip smoke: the standing proof that the system still starts on the TPU.

Drives the two device paths ONCE, in this one process, through the entry
points a user calls, on every device ``jax.devices()`` shows (one chip
or the four-chip host), and checks what comes out by the repo's own
means:

* the wave engine at the program shape ``bench.py`` times — a seeded
  ``bench.make_corpus`` text spanning two full waves and a ragged third,
  once through ``Server.loop()`` with ``device=True`` (the path
  ``python -m mapreduce_tpu.cli wordcount --device`` takes) and once
  through ``DeviceWordCount.count_bytes``; counts EQUAL an independent
  host oracle, no capacity retry, one dispatch per wave, XLA's own cost
  model, a peak looked up by device kind, the exchange matrix equal to
  its host recompute, every device holding bytes while waves fly;
* the Pallas kernels the served config names compiled by Mosaic, none
  interpreted, and the served fold bit-identical to the all-lax fold;
* five ``TransformerTrainer.step()`` calls at the flagship width
  (167.8M parameters; ``bench_train.py``) with finite, falling loss and
  the flash kernels compiled — called directly on one device, per ring
  step on several — then the reference-parity ``"loop"`` trainer through
  ``cli train`` (the looped configuration, ``loop_steps`` > 1 through
  ``TransformerTrainer.step_opt``, is not driven here: its standing
  proof on the chip is its benchmark cell, ``python3 benchmark/run.py
  --workload train-ouro-4k``, which checks step 0 against
  ``benchmark/reference_looplm.py`` at the published widths);
* the compile cache where the environment placed it, and nowhere else.

It claims no speed.  It exits non-zero before doing any work when the
platform is not ``tpu``; every phase raises on failure and nothing is
caught and carried past, so exit code 0 means every assertion held.
Stdout ends with two JSON lines: the per-phase summary (closing on
``"claim": null``), then — the LAST line, the one the driver parses —
exactly ``{"ok": true, "device": {"platform", "kind", "count"}}`` with
the device as JAX reports it.  It reads and appends to no benchmark
record.

    python chip_smoke.py

``tests/test_chip_smoke.py`` runs the same phase functions on the CPU at
tiny sizes (kernels under the interpreter): control flow and
correctness only.
"""

from __future__ import annotations

import contextlib
import io
import json
import math
import os
import sys
import tempfile
import time
import uuid

#: fraction of one wave the corpus spans: two full waves and a ragged
#: third, so the wave fold, the streaming feeder and the padded final
#: wave all run
CORPUS_WAVES = 2.3


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def _kernel_builds(kernel: str, mode: str) -> float:
    from mapreduce_tpu.obs.metrics import REGISTRY

    return REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                        kernel=kernel, mode=mode)


def _assert_kernels_compiled(kernels, on_tpu: bool) -> None:
    """Every kernel in *kernels* was built for this platform's mode —
    Mosaic on the TPU, the interpreter elsewhere — and none in the
    other."""
    mode, other = (("mosaic", "interpret") if on_tpu
                   else ("interpret", "mosaic"))
    for kernel in kernels:
        assert _kernel_builds(kernel, mode) > 0, (
            f"kernel {kernel!r} was never built in {mode} mode")
        assert _kernel_builds(kernel, other) == 0, (
            f"kernel {kernel!r} was built in {other} mode")


def smoke_corpus(wc) -> bytes:
    """Seeded ``bench.make_corpus`` text cut to ``CORPUS_WAVES`` waves of
    *wc*'s engine on its mesh."""
    import bench

    eng = wc._engine_for(wc._row_len())
    wave_bytes = eng._rows_per_wave(wc._row_len()) * eng.n_dev * wc.chunk_len
    target = int(CORPUS_WAVES * wave_bytes)
    n_words = target // 6 + 1024        # ~6.25 bytes per word: overshoots
    text = bench.make_corpus(n_words, max(n_words // 25, 1))
    assert len(text) >= target, (len(text), target)
    return text[:text.rindex(b" ", 0, target) + 1]


def host_oracle(text: bytes) -> dict:
    """{word: count} by a code path that shares nothing with the device
    engine: the in-tree C++ tokenizer where it builds, else Python."""
    from mapreduce_tpu import native

    if native.native_available():
        return native.wordcount_bytes(text)
    from collections import Counter

    return dict(Counter(text.split()))


def _check_run(tm: dict, dispatched: float, on_tpu: bool,
               what: str) -> None:
    """The per-run assertions both engine entry points share."""
    assert tm["retries"] == 0, (what, tm)
    assert tm["waves"] >= math.ceil(CORPUS_WAVES), (what, tm)
    assert dispatched == tm["waves"], (
        f"{what}: {dispatched} wave dispatches for {tm['waves']} waves")
    assert tm["cost_source"] == "measured", (what, tm)
    if on_tpu:
        assert tm["peak_source"].startswith("kind:"), (what, tm)


def _wave_dispatches() -> float:
    from mapreduce_tpu.obs.metrics import REGISTRY

    return REGISTRY.sum("mrtpu_device_dispatches_total", program="wave")


def _server_wordcount(mesh, path: str, chunk_len: int, config) -> tuple:
    """The corpus file through ``Server.loop()`` with ``device=True`` —
    what ``cli wordcount --device`` runs.  The wordcount module takes
    the capacities and formulations of *config* through its init_args
    (its tile_records and combiner are its own)."""
    from mapreduce_tpu import spec
    from mapreduce_tpu.examples import wordcount as module
    from mapreduce_tpu.server import Server

    spec.clear_caches()
    name = module.__name__
    params = {r: name for r in ("taskfn", "mapfn", "partitionfn",
                                "reducefn", "finalfn")}
    params.update({
        "device": True,
        "mesh": mesh,
        "storage": f"mem:{uuid.uuid4().hex}",
        "init_args": {
            "files": [path],
            "device_chunk_len": chunk_len,
            "device_local_capacity": config.local_capacity,
            "device_exchange_capacity": config.exchange_capacity,
            "device_out_capacity": config.out_capacity,
            "device_sort_impl": config.sort_impl,
            "device_segment_impl": config.segment_impl,
            "device_tokenize_impl": config.tokenize_impl},
    })
    server = Server(f"mem://{uuid.uuid4().hex}", "chip_smoke")
    server.configure(params)
    stats = server.loop()
    return dict(module.RESULT), stats["device"]


def engine_phase(mesh, config, chunk_len: int) -> dict:
    """The wave engine under *config* on *mesh*, through both entry
    points, against the host oracle; then the same corpus under the
    all-lax formulations, bit for bit."""
    from dataclasses import replace

    import numpy as np

    from mapreduce_tpu.engine import DeviceWordCount, materialize_counts
    from mapreduce_tpu.obs.memory import memory_snapshot

    on_tpu = mesh.devices.flat[0].platform == "tpu"
    n_dev = mesh.shape["data"]
    wc = DeviceWordCount(mesh, chunk_len=chunk_len, config=config)
    t0 = time.monotonic()
    text = smoke_corpus(wc)
    oracle = host_oracle(text)
    log(f"corpus {len(text) / 1e6:.1f} MB, {len(oracle)} unique words, "
        f"{sum(oracle.values())} words ({time.monotonic() - t0:.1f}s)")

    # 1. Server.loop(device=True)
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        path = os.path.join(tmp, "corpus.txt")
        with open(path, "wb") as f:
            f.write(text)
        d0 = _wave_dispatches()
        served, tm_srv = _server_wordcount(mesh, path, chunk_len, config)
        _check_run(tm_srv, _wave_dispatches() - d0, on_tpu, "Server.loop")
    assert served == {w.decode(): c for w, c in oracle.items()}, (
        "Server.loop(device=True) counts differ from the host oracle")
    log(f"Server.loop(device=True): {tm_srv['waves']} waves, counts == "
        f"oracle ({time.monotonic() - t0:.1f}s)")

    # 2. DeviceWordCount.count_bytes
    t0 = time.monotonic()
    tm: dict = {}
    d0 = _wave_dispatches()
    counts = wc.count_bytes(text, timings=tm)
    _check_run(tm, _wave_dispatches() - d0, on_tpu, "count_bytes")
    assert counts == oracle, (
        "DeviceWordCount.count_bytes differs from the host oracle")
    matrix = np.asarray(tm["exchange"]["matrix"], dtype=np.int64)
    assert np.array_equal(matrix, wc.host_exchange_matrix(text)), (
        f"exchange matrix differs from its host recompute:\n{matrix}")
    assert matrix.sum(axis=0).all() and matrix.sum(axis=1).all(), (
        f"a device sent or received nothing:\n{matrix}")
    if on_tpu:
        # sampled by the engine at each wave's readback, later waves
        # still in flight: work that has never run on several devices
        # may put everything on the first
        mem = memory_snapshot()
        assert mem["device_source"] == "measured", mem
        for dev in mesh.devices.flat:
            held = mem["devices"][str(dev.id)]["bytes_in_use"]
            assert held > 0, f"device {dev.id} held no bytes mid-run"
    log(f"count_bytes: {tm['waves']} waves on {n_dev} device(s), counts "
        f"== oracle, exchange matrix == host recompute "
        f"({time.monotonic() - t0:.1f}s)")

    # 3. kernels compiled, not interpreted; served fold == all-lax fold
    kernels = [k for k, impl in (("segreduce", config.segment_impl),
                                 ("tokenize", config.tokenize_impl))
               if impl == "pallas"]
    _assert_kernels_compiled(kernels, on_tpu)
    t0 = time.monotonic()
    chunks, row_len = wc._to_chunks(text)
    fold = wc._engine_for(row_len).run(chunks)
    lax = DeviceWordCount(mesh, chunk_len=chunk_len, config=replace(
        config, segment_impl="lax", tokenize_impl="lax",
        sort_impl="variadic"))
    fold_lax = lax._engine_for(row_len).run(chunks)
    for field in fold._fields:
        assert np.array_equal(getattr(fold, field),
                              getattr(fold_lax, field)), (
            f"served fold differs from the all-lax fold in {field!r}")
    assert materialize_counts(chunks, fold_lax) == oracle
    log(f"served fold ({'+'.join(kernels) or 'no'} kernels) bit-identical "
        f"to the all-lax fold ({time.monotonic() - t0:.1f}s)")
    return {"corpus_bytes": len(text), "unique_words": len(oracle),
            "waves": tm["waves"], "kernels": kernels,
            "exchange_records": int(matrix.sum())}


def trainer_phase(mesh, cfg, batch: int, seq_per_device: int,
                  steps: int = 5) -> dict:
    """*steps* ``TransformerTrainer.step()`` calls on one fixed seeded
    batch: every loss finite, the last below the first; on the TPU the
    flash forward, dQ and dKV kernels each compiled by Mosaic."""
    import jax
    import numpy as np

    from mapreduce_tpu.models.transformer import TransformerTrainer

    on_tpu = mesh.devices.flat[0].platform == "tpu"
    n_data = mesh.shape["data"]
    t0 = time.monotonic()
    trainer = TransformerTrainer(mesh, cfg, learning_rate=1e-3)
    params = trainer.init_params()
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab, size=(batch, seq_per_device * n_data + 1)
    ).astype(np.int32)
    losses = []
    for _ in range(steps):
        params, loss = trainer.step(params, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    if on_tpu:
        if n_data == 1:
            assert trainer.cfg.flash is True, trainer.cfg
        # several devices: the ring path calls the same kernels once
        # per ring step (parallel/ring.py)
        _assert_kernels_compiled(("flash_fwd", "flash_dq", "flash_dkv"),
                                 on_tpu)
    log(f"trainer: {n_params / 1e6:.1f}M params, sp{n_data}, losses "
        f"{[round(x, 4) for x in losses]} ({time.monotonic() - t0:.1f}s)")
    return {"params_m": round(n_params / 1e6, 1), "losses": losses,
            "seq_len": seq_per_device * n_data}


def loop_trainer_phase(epochs: int = 2) -> dict:
    """The reference-parity ``"loop"`` trainer (the digits MLP) through
    its CLI, in this process."""
    from mapreduce_tpu import cli

    t0 = time.monotonic()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli.main(["train", f"mem://{uuid.uuid4().hex}", "smoke",
                       "--epochs", str(epochs), "--no-lease", "--fresh",
                       "--storage", f"mem:{uuid.uuid4().hex}"])
    assert rc == 0, f"cli train exited {rc}"
    doc = json.loads(out.getvalue().strip().splitlines()[-1])
    assert doc["epochs_run"] == epochs, doc
    assert 0.0 < doc["best_val_loss"] < float("inf"), doc
    log(f"cli train: {epochs} epochs, best val loss "
        f"{doc['best_val_loss']:.4f} ({time.monotonic() - t0:.1f}s)")
    return {"epochs_run": doc["epochs_run"],
            "best_val_loss": doc["best_val_loss"]}


def _listing(path: str) -> set:
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def cache_check(cache_dir: str, before: dict) -> dict:
    """The compile cache sits where the environment placed it: this
    run's programs are in *cache_dir* (fresh entries for whatever it
    compiled), and no other cache directory was created or written."""
    import jax

    from mapreduce_tpu.obs.compile import LEDGER, REGISTRY_BASENAME
    from mapreduce_tpu.utils import compile_cache

    assert jax.config.jax_compilation_cache_dir == cache_dir, (
        jax.config.jax_compilation_cache_dir, cache_dir)
    env = os.environ.get(compile_cache.ENV_VAR)
    assert env in (None, "", cache_dir), (env, cache_dir)
    for other in (compile_cache.DEFAULT_DIR, compile_cache.USER_DIR):
        if os.path.realpath(other) != os.path.realpath(cache_dir):
            assert _listing(other) == before[other], (
                f"a second cache directory was written: {other}")
    now = _listing(cache_dir)
    assert REGISTRY_BASENAME in now, (cache_dir, sorted(now)[:8])
    programs = LEDGER.snapshot()["programs"]
    outcomes = {p: {k: programs[p][k]
                    for k in ("compiled", "persistent_hit", "compile_s")}
                for p in ("wave", "tf_step")}
    fresh = sum(o["compiled"] for o in outcomes.values())
    new = now - before[cache_dir] - {REGISTRY_BASENAME}
    assert new or not fresh, (
        f"{fresh} fresh compiles left no new entry in {cache_dir}")
    log(f"compile cache at {cache_dir}: {len(new)} new entries; "
        f"{outcomes}")
    return {"dir": cache_dir, "new_entries": len(new),
            "programs": outcomes}


def report(device: dict, phases: dict, wall_s: float) -> None:
    """The run's two stdout lines: the per-phase summary, then the
    verdict.  The verdict is LAST and carries exactly ``ok`` and
    ``device`` (``platform``, ``kind``, ``count``) — the driver refuses
    any other shape, so detail belongs in the summary line above it."""
    print(json.dumps({"phases": phases, "wall_s": wall_s, "claim": None}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)


def main() -> int:
    t_start = time.monotonic()
    from mapreduce_tpu.utils import compile_cache

    before = {d: _listing(d) for d in (compile_cache.DEFAULT_DIR,
                                       compile_cache.USER_DIR)}
    cache_dir = compile_cache.enable_persistent_cache()
    before.setdefault(cache_dir, _listing(cache_dir))

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"devices={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU — this proves nothing on "
              f"{device['platform']!r}; run it through the chip tool",
              file=sys.stderr)
        return 1

    from mapreduce_tpu.engine.wordcount import bench_engine_config
    from mapreduce_tpu.models.transformer import TransformerConfig
    from mapreduce_tpu.parallel import make_mesh

    mesh = make_mesh()
    phases = {}
    phases["engine"] = engine_phase(mesh, bench_engine_config(),
                                    chunk_len=1 << 22)
    # the flagship configuration (README "Using it", bench_train.py)
    phases["trainer"] = trainer_phase(
        mesh, TransformerConfig(vocab=32768, embed=1024, n_layers=8,
                                n_heads=8, head_dim=128, ffn=4096),
        batch=4, seq_per_device=2048)
    assert phases["trainer"]["params_m"] == 167.8, phases["trainer"]
    phases["loop_trainer"] = loop_trainer_phase()
    phases["compile_cache"] = cache_check(cache_dir, before)
    report(device, phases, round(time.monotonic() - t_start, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
