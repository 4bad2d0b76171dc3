"""Training-path benchmark: steps/sec/chip and MFU on real hardware.

BASELINE.md requires measured training throughput ("steps/sec/chip",
"MFU") — the quantitative form of the rebuild's north star that no CPU
worker sits in the training loop (the reference moves the whole serialized
model through GridFS every minibatch, SURVEY.md §3.5, and publishes no
training numbers at all, init.lua:19-20).

Prints one JSON line per model family:

  {"metric": "mlp_train_steps_per_s", "value": ..., "unit": "steps/s", ...}
  {"metric": "transformer_train_tokens_per_s", "value": ..., "unit":
   "tok/s", "mfu": ...}

MFU = achieved training FLOP/s over the chip's peak bf16 FLOP/s
(obs/profile's table, keyed by device_kind).  The MLP is the
reference-parity model (256-128-10, APRIL-ANN init.lua:12) — tiny by
design, so its MFU is reported but meaningless; the transformer is the
beyond-parity long-context family and is the real MXU utilisation story.

Elastic-training gate: every run also measures ``trainer_recovery_s``
(successor lease acquire -> restore of the latest sharded checkpoint ->
first epoch committed; README "Preemption-tolerant training").
``--check`` gates this run against BENCH_TRAIN.json's ``history``
(obs/benchgate.py medians + tolerances) and appends on pass;
``--check --smoke`` measures and gates ONLY the recovery key (CI-safe
on a CPU box — the throughput specs are not ``required``).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

STEPS = 20
WARMUP = 3

HISTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_TRAIN.json")


def train_specs():
    """Per-metric tolerances for ``--check`` (obs/benchgate.py): the
    throughput keys get a wide band; the
    recovery key is the elastic-training gate — step-recovery time
    (successor lease acquire -> restore -> first epoch committed) must
    not silently regress.  Throughput keys are not ``required`` so a
    CPU smoke check (which measures only recovery) still gates."""
    from mapreduce_tpu.obs.benchgate import MetricSpec

    return [
        MetricSpec("mlp_train_steps_per_s", rel_tol=0.50,
                   direction="higher"),
        MetricSpec("transformer_train_tokens_per_s", rel_tol=0.35,
                   direction="higher"),
        MetricSpec("trainer_recovery_s", rel_tol=1.50,
                   direction="lower", required=True),
    ]


def bench_recovery(mesh):
    """``trainer_recovery_s``: fenced-failover step-recovery time.

    A predecessor trains 3 epochs with sharded checkpoints + a trainer
    lease on a board, then releases (the clean-preemption form; the
    expiry form is tests/test_train_failover.py's chaos scenario).  The
    timed region is everything a successor pays before it is making
    progress again: lease acquire -> restore of the latest complete
    checkpoint (digest-verified, resharded onto its mesh) -> first
    epoch applied AND committed.  Includes the successor's jit compile
    — a real failover pays it too."""
    import tempfile
    import uuid

    from mapreduce_tpu.coord import Connection, TrainerLease
    from mapreduce_tpu.models import (
        DistributedTrainer, MLPConfig, TrainConfig, make_digits)
    from mapreduce_tpu.models.checkpoint import CheckpointManager
    from mapreduce_tpu.storage.localdir import LocalDirStorage

    root = tempfile.mkdtemp(prefix="mrtpu_recovery_")
    board = f"mem://{uuid.uuid4().hex}"
    x_tr, y_tr, x_va, y_va = make_digits()

    def make_trainer(max_epochs):
        return DistributedTrainer(
            mesh, MLPConfig(sizes=(256, 64, 10)),
            TrainConfig(bunch_size=32, max_epochs=max_epochs,
                        min_epochs=1, patience=100))

    mgr = CheckpointManager(LocalDirStorage(root), keep_n=2)
    pre = TrainerLease(Connection(board, "train"), holder="pre",
                       lease=30.0)
    pre.acquire(timeout=10)
    out = make_trainer(3).fit(x_tr, y_tr, x_va, y_va, manager=mgr,
                              lease=pre)
    assert out["epochs_run"] == 3, out
    pre.release()

    suc = TrainerLease(Connection(board, "train"), holder="suc",
                       lease=30.0)
    t0 = time.monotonic()
    suc.acquire(timeout=10)
    out = make_trainer(4).fit(x_tr, y_tr, x_va, y_va, manager=mgr,
                              lease=suc)
    sec = time.monotonic() - t0
    assert out["restored"] and out["start_epoch"] == 4, out
    suc.release()
    return {"metric": "trainer_recovery_s", "value": round(sec, 3),
            "unit": "s", "restored_step": 3,
            "n_devices": len(mesh.devices.flat)}


def run_check(rows, path=HISTORY_PATH, append=True):
    """Gate this run's rows against the file's ``history`` and append
    on pass; returns the regression list (empty = accepted)."""
    import jax

    from mapreduce_tpu.obs import benchgate

    entry = {r["metric"]: r["value"] for r in rows}
    plat = jax.devices()[0].platform
    entry["platform"] = plat
    # baseline on same-platform entries only (an entry without the
    # platform stamp predates it and counts): a TPU recovery includes
    # a multi-second jit compile a CPU run never pays — cross-platform
    # medians would false-fail one direction and mask the other
    return benchgate.check_and_append(
        path, entry, train_specs(), key="history", append=append,
        match=lambda h: h.get("platform", plat) == plat)


def _peak_flops(mesh):
    """Peak bf16 FLOP/s of one of *mesh*'s devices, from the one table
    (obs/profile, keyed by device_kind — an accelerator it does not
    know is an error).  None on the CPU: an MFU is a device metric and
    a CPU run reports none."""
    from mapreduce_tpu.obs.profile import device_peaks

    dev = mesh.devices.flat[0]
    if dev.platform == "cpu":
        return None
    return device_peaks(dev)["flops_per_s"]


def _timeit(step_fn, n=None):
    n = STEPS if n is None else n
    # force completion with a VALUE readback: np.asarray must wait for
    # the data.  The final loss depends on every prior step's params,
    # so one readback drains the whole chain.
    # Durations ride time.monotonic() like everywhere else — an NTP step
    # mid-measurement must not corrupt a published steps/s number.
    for _ in range(WARMUP):
        out = step_fn()
    np.asarray(out)
    t0 = time.monotonic()
    for _ in range(n):
        out = step_fn()
    np.asarray(out)
    return (time.monotonic() - t0) / n


def bench_mlp(mesh):
    import jax
    from mapreduce_tpu.models import (
        DistributedTrainer, MLPConfig, TrainConfig)

    mlp_cfg = MLPConfig(sizes=(256, 128, 10))  # reference init.lua:12
    cfg = TrainConfig(bunch_size=128)
    tr = DistributedTrainer(mesh, mlp_cfg, cfg)
    params, opt_state = tr.init_state()
    n_data = mesh.shape["data"]
    batch = cfg.bunch_size * n_data
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 256)).astype(np.float32)
    y = (np.arange(batch) % 10).astype(np.int32)
    xd, yd = tr.place_batch(x, y)

    state = {"params": params, "opt": opt_state}

    def step():
        state["params"], state["opt"], loss = tr._train_step(
            state["params"], state["opt"], xd, yd)
        return loss

    sec = _timeit(step)

    # fused path: a whole scanned epoch per dispatch (what fit() runs).
    # _train_epoch DONATES the stacked batches, so each timed call gets
    # a fresh device-side copy of the master stacks — an on-device copy,
    # not a host re-upload, mirroring fit()'s fresh device_put per epoch
    # without putting the slow link inside the timed region.
    S = 100
    xs = jax.device_put(np.broadcast_to(x, (S,) + x.shape).copy(),
                        tr.epoch_sharding)
    ys = jax.device_put(np.broadcast_to(y, (S,) + y.shape).copy(),
                        tr.epoch_sharding)
    copy2 = jax.jit(lambda a, b: (a + 0, b + 0),
                    out_shardings=(tr.epoch_sharding, tr.epoch_sharding))

    def epoch():
        xs_c, ys_c = copy2(xs, ys)
        state["params"], state["opt"], losses = tr._train_epoch(
            state["params"], state["opt"], xs_c, ys_c)
        return losses

    sec_fused = _timeit(epoch, n=3) / S

    # training FLOPs ~= 6 * params * batch (2 fwd + 4 bwd per weight)
    n_params = sum(int(np.prod(np.shape(p)))
                   for p in jax.tree.leaves(state["params"]))
    flops = 6.0 * n_params * batch
    n_chips = len(mesh.devices.flat)
    peak = _peak_flops(mesh)
    out = {
        "metric": "mlp_train_steps_per_s",
        "value": round(1.0 / sec, 2),
        "unit": "steps/s",
        "per_chip_steps_per_s": round(1.0 / sec / n_chips, 2),
        "fused_steps_per_s": round(1.0 / sec_fused, 2),
        "global_batch": batch,
        "flops_per_step": flops,
    }
    if peak:
        out["mfu"] = round(flops / sec / (peak * n_chips), 6)
        out["fused_mfu"] = round(flops / sec_fused / (peak * n_chips), 6)
    return out


def _transformer_rate(mesh, cfg, B, T, n_steps=None):
    """Shared harness: one trainer, timed steps; returns (sec/step,
    n_params)."""
    import jax
    from mapreduce_tpu.models.transformer import TransformerTrainer

    tr = TransformerTrainer(mesh, cfg, learning_rate=1e-3)
    params = tr.init_params()
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, size=(B, T + 1)).astype(np.int32)
    x, y = tr.place_batch(toks)
    state = {"params": params}

    def step():
        state["params"], loss = tr._train_step(state["params"], x, y)
        return loss

    sec = _timeit(step, n=n_steps)
    n_params = sum(int(np.prod(np.shape(p)))
                   for p in jax.tree.leaves(state["params"]))
    return sec, n_params


def _train_flops(cfg, n_params, B, T):
    """6ND for the dense matmuls + attention: fwd QK^T and AV are
    2*B*H*T^2*D FLOPs each; x3 for training."""
    attn = 3 * 2 * 2 * B * cfg.n_heads * T * T * cfg.head_dim
    return 6.0 * n_params * (B * T) + attn


def bench_transformer(mesh):
    from mapreduce_tpu.models.transformer import TransformerConfig

    n_data = mesh.shape["data"]
    # head_dim=128 (H=8): same embed/params/FLOPs as 16x64, but shaped
    # for the 128-wide MXU contraction and 128-lane registers (the
    # kernel's rate at head_dim 64 is not measured on current
    # hardware); every production TPU transformer picks head_dim 128
    # for this reason
    cfg = TransformerConfig(
        vocab=32768, embed=1024, n_layers=8,
        n_heads=8, head_dim=128, ffn=4096)
    B = 4
    T = 2048 * n_data  # sequence-parallel: T/n_data per device
    sec, n_params = _transformer_rate(mesh, cfg, B, T)
    tokens = B * T
    flops = _train_flops(cfg, n_params, B, T)
    n_chips = len(mesh.devices.flat)
    peak = _peak_flops(mesh)
    out = {
        "metric": "transformer_train_tokens_per_s",
        "value": round(tokens / sec, 1),
        "unit": "tok/s",
        "steps_per_s": round(1.0 / sec, 3),
        "seq_len": T,
        "global_batch": B,
        "params_m": round(n_params / 1e6, 1),
        "flops_per_step": flops,
    }
    if peak:
        out["mfu"] = round(flops / sec / (peak * n_chips), 4)
    return out


def bench_longctx(mesh):
    """A fixed 32,768-token context SHARDED over the mesh (the Pallas
    flash kernel's O(block²) score memory + sequence-chunked loss;
    README's long-context story as a runnable number — same context
    length whatever the mesh, so the metric compares across machines).
    No rematerialisation: with the kernel, activations fit at 32K
    (9.2 GB of a v5e's 16, PERF.md section 4).  remat=True keeps a
    layer's input and the kernel's output and row statistics and runs
    the rest of the layer's forward again in the backward pass; what
    that costs at this shape, and whether it reaches 65K/128K on one
    chip: not measured on current hardware."""
    from mapreduce_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab=32768, embed=1024, n_layers=8, n_heads=8, head_dim=128,
        ffn=4096, loss_block=2048)
    T = 32768
    sec, n_params = _transformer_rate(mesh, cfg, 1, T, n_steps=3)
    flops = _train_flops(cfg, n_params, 1, T)
    n_chips = len(mesh.devices.flat)
    peak = _peak_flops(mesh)
    out = {
        "metric": "transformer_32k_ctx_tokens_per_s",
        "value": round(T / sec, 1),
        "unit": "tok/s",
        "seq_len": T,
        "steps_per_s": round(1.0 / sec, 3),
    }
    if peak:
        out["mfu"] = round(flops / sec / (peak * n_chips), 4)
    return out


def main() -> None:
    from mapreduce_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax

    from mapreduce_tpu.parallel import make_mesh

    platform = jax.devices()[0].platform
    mesh = make_mesh()
    smoke = "--smoke" in sys.argv
    check = "--check" in sys.argv
    if smoke:
        global STEPS
        STEPS = 3

    rows = []
    if not (check and smoke):
        # --check --smoke is the recovery-only gate (CI-safe: no
        # transformer bench on a CPU box); everything else runs the
        # full throughput families first
        print(f"# platform={platform} devices={len(mesh.devices.flat)}; "
              "mlp ...", file=sys.stderr, flush=True)
        rows.append(bench_mlp(mesh))
        print(json.dumps(rows[-1]), flush=True)
        print("# transformer ...", file=sys.stderr, flush=True)
        rows.append(bench_transformer(mesh))
        print(json.dumps(rows[-1]), flush=True)
        if not smoke and platform == "tpu":
            print("# 32k context ...", file=sys.stderr, flush=True)
            rows.append(bench_longctx(mesh))
            print(json.dumps(rows[-1]), flush=True)

    print("# recovery ...", file=sys.stderr, flush=True)
    rows.append(bench_recovery(mesh))
    print(json.dumps(rows[-1]), flush=True)

    # driver-visible artifact: the training numbers land in a committed
    # file each round the way the wordcount bench's land in BENCH_r*.json
    if platform == "tpu" and not smoke:
        with open(HISTORY_PATH) as f:
            doc = json.load(f)
        doc["platform"] = platform
        doc["metrics"] = rows
        with open(HISTORY_PATH, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {HISTORY_PATH}", file=sys.stderr)

    if check:
        problems = run_check(rows)
        if problems:
            print("# REGRESSION GATE FAILED:", file=sys.stderr)
            for pr in problems:
                print(f"#   {pr}", file=sys.stderr)
            raise SystemExit(1)
        print(f"# regression gate passed; run appended to "
              f"{HISTORY_PATH}", file=sys.stderr)


if __name__ == "__main__":
    main()
