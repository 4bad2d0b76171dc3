"""The fused distributed trainer: weights in HBM, one jit per train step.

This is the BASELINE.json north star made concrete — the reference's
distributed SGD moves the *entire serialized model* through GridFS for
every minibatch gradient and every optimizer step (SURVEY.md §3.5); here
the parameters never leave device memory:

  * **data parallelism**: the global batch is sharded over the mesh's
    ``data`` axis; the batch-mean loss makes XLA insert the gradient
    all-reduce (psum over ICI) — the compiled equivalent of the
    reference's map=grads / reduce=sum cycle (common.lua:85-137);
  * **tensor parallelism**: weight matrices are sharded over the
    ``model`` axis Megatron-style (even layers column-split, odd layers
    row-split), declared ONCE as regex partition rules
    (:data:`TRAINER_PARTITION_RULES`, parallel/partition.py) that apply
    uniformly to params and optimizer state;
  * SGD + momentum + weight decay (the reference's optimizer knobs,
    examples/APRIL-ANN/init.lua:14-17), optional ``1/sqrt(N)`` gradient
    smoothing (common.lua:163-166), holdout early stopping
    (common.lua:172-189);
  * **elastic, preemption-tolerant training**: per-epoch sharded
    checkpoints through the blob planes (models/checkpoint.py,
    manifest-committed, retention keep-N + best), resume-on-start, and
    an optional trainer lease (coord/lease.py) so a preempted or
    partitioned trainer FENCES at its next step boundary while a
    successor restores the latest complete checkpoint and continues —
    the per-epoch RNG is derived from ``seed + epoch`` so the
    successor's lineage is bit-identical to an uninterrupted run.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..utils.jax_compat import quiet_unusable_donation

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..coord.lease import TrainerFencedError, TrainerLease
from ..obs import compile as _compile_obs
from ..obs import memory as _memory_obs
from ..obs import metrics as _metrics
from ..parallel.partition import match_partition_rules, shard_tree
from ..storage.localdir import LocalDirStorage
from .checkpoint import CheckpointError, CheckpointManager
from .mlp import MLPConfig, init_params, nll_loss, loss_and_accuracy

Params = Dict[str, jax.Array]

#: Megatron-alternating layout as ONE regex table (replaces the old
#: hand-threaded ``param_spec`` function): even layers column-split,
#: odd layers row-split, so consecutive matmuls need only one
#: collective between them.  Anchored on the TRAILING leaf name, the
#: same table resolves optimizer mirrors (``…/trace/w0``) identically —
#: scalar leaves pass through replicated before any rule is consulted
#: (parallel/partition.py).
TRAINER_PARTITION_RULES: Tuple[Tuple[str, P], ...] = (
    (r"w\d*[02468]$", P(None, "model")),
    (r"w\d*[13579]$", P("model", None)),
    (r"b\d*[02468]$", P("model")),
    (r"b\d*[13579]$", P()),
)

_RECOVERY_S = _metrics.gauge(
    "mrtpu_trainer_recovery_seconds",
    "seconds from fit() entry to the end of the first epoch after "
    "restoring a checkpoint (the successor's step-recovery time)")
_EPOCHS = _metrics.counter(
    "mrtpu_trainer_epochs_total",
    "optimizer epochs applied by this process "
    "(labels: outcome=applied|fenced)")


@dataclass(frozen=True)
class TrainConfig:
    """Reference hyperparameters (examples/APRIL-ANN/init.lua:10-20) as
    defaults: lr .01, momentum .02, weight decay 1e-4, bunch (per-shard
    batch) 128, 20-40 epochs."""

    learning_rate: float = 0.01
    momentum: float = 0.02
    weight_decay: float = 1e-4
    bunch_size: int = 128
    max_epochs: int = 40
    min_epochs: int = 5
    patience: int = 8           # epochs without val improvement -> stop
    smoothing: bool = False     # grads *= 1/sqrt(n_data) (common.lua:163-166)
    seed: int = 1234
    keep_checkpoints: int = 3   # retention: newest N (+ the marked best)


#: the TrainConfig fields that determine the training LINEAGE — the
#: bit-identical successor contract (and the precommit residual-race
#:  defense built on it) holds only if a resume runs the same values.
#: Mesh-dependent quantities (global batch = bunch * n_data) are NOT
#: attested: resuming on a different mesh is the reshard feature, and
#: its lineage divergence is inherent, not a config mistake.
LINEAGE_FIELDS: Tuple[str, ...] = (
    "seed", "learning_rate", "momentum", "weight_decay",
    "bunch_size", "smoothing", "min_epochs", "patience")


def lineage_config(cfg: TrainConfig) -> Dict[str, Any]:
    """The manifest-stamped attestation of *cfg*'s lineage fields."""
    return {f: getattr(cfg, f) for f in LINEAGE_FIELDS}


class DistributedTrainer:
    """Train the MLP family over a ``(model, data)`` mesh."""

    def __init__(self, mesh: Mesh, mlp_cfg: MLPConfig = MLPConfig(),
                 cfg: TrainConfig = TrainConfig()) -> None:
        self.mesh = mesh
        self.mlp_cfg = mlp_cfg
        self.cfg = cfg
        self.n_data = mesh.shape["data"]
        self.opt = optax.chain(
            optax.add_decayed_weights(cfg.weight_decay),
            optax.sgd(cfg.learning_rate, momentum=cfg.momentum),
        )
        self.batch_sharding = NamedSharding(mesh, P("data"))
        self.replicated = NamedSharding(mesh, P())

        grad_scale = (1.0 / np.sqrt(self.n_data)) if cfg.smoothing else 1.0

        def train_step(params, opt_state, x, y):
            loss, grads = jax.value_and_grad(
                lambda p: nll_loss(p, x, y, self.mlp_cfg))(params)
            if grad_scale != 1.0:
                grads = jax.tree.map(lambda g: g * grad_scale, grads)
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        # ledgered jits (obs/compile): first-call compiles emit spans +
        # per-program compile-seconds and land in the shape-bucket
        # registry; no cross-instance key — the closures bake in live
        # hyperparameters (lr/momentum), so instances must not alias
        self._train_step = _compile_obs.wrap_jit(
            train_step, program="mlp_step", donate_argnums=(0, 1))

        def train_epoch(params, opt_state, xs, ys):
            """lax.scan of train_step over stacked minibatches
            ([S, batch, ...]): ONE dispatch per epoch instead of one per
            step.  A tiny model's per-step path is dispatch-latency-
            bound (not measured on current hardware) — its whole epoch
            should ride a single XLA program, the same inversion the
            engine applies to the data plane."""
            def body(carry, xy):
                p, o = carry
                p, o, loss = train_step(p, o, *xy)
                return (p, o), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), (xs, ys))
            return params, opt_state, losses

        # donate the stacked epoch batches too (args 2, 3): a full
        # epoch's xs/ys HBM is marked reusable while the scan runs (the
        # lowered module tags them jax.buffer_donor) and the caller-side
        # arrays are consumed — fit() device_puts fresh stacks each
        # epoch anyway, so nothing legitimate reads them back
        self._train_epoch = _compile_obs.wrap_jit(
            train_epoch, program="mlp_epoch",
            donate_argnums=(0, 1, 2, 3))
        self.epoch_sharding = NamedSharding(mesh, P(None, "data"))
        self._eval = _compile_obs.wrap_jit(
            lambda p, x, y: loss_and_accuracy(p, x, y, self.mlp_cfg),
            program="mlp_eval")
        self._devices = list(mesh.devices.flat)

    # -- state placement ---------------------------------------------------

    def abstract_state(self) -> Dict[str, Any]:
        """The full training-state tree as shapes/dtypes only (no device
        work) — the restore template and the input to the rule table."""
        return jax.eval_shape(
            lambda: (lambda p: {"params": p, "opt": self.opt.init(p)})(
                init_params(jax.random.key(0), self.mlp_cfg)))

    def init_state(self) -> Tuple[Params, Any]:
        key = jax.random.key(self.cfg.seed)
        # one placement path for the whole state: the regex rules lay
        # out params AND the optimizer mirrors (momentum trace) — no
        # jit-inheritance magic deciding half the layout
        params = shard_tree({"params": init_params(key, self.mlp_cfg)},
                            TRAINER_PARTITION_RULES, self.mesh)["params"]
        # the moments are BORN sharded: opt.init runs under jit with
        # out_shardings resolved from the SAME rule table, never
        # materializing the trace replicated on one device first — at
        # the scale the rules exist for, the state only fits sharded,
        # init included
        opt_specs = match_partition_rules(
            TRAINER_PARTITION_RULES, self.abstract_state())["opt"]
        opt_state = _compile_obs.wrap_jit(
            self.opt.init, program="opt_init",
            out_shardings=jax.tree.map(
                lambda ps: NamedSharding(self.mesh, ps), opt_specs,
                is_leaf=lambda x: isinstance(x, P)))(params)
        return params, opt_state

    def place_batch(self, x: np.ndarray, y: np.ndarray):
        return (jax.device_put(x, self.batch_sharding),
                jax.device_put(y, self.batch_sharding))

    # -- the training loop (reference server_final loop, compiled) ---------

    def fit(self, x_tr: np.ndarray, y_tr: np.ndarray,
            x_va: np.ndarray, y_va: np.ndarray,
            checkpoint_dir: Optional[str] = None,
            log: Optional[Callable[[str], None]] = None,
            manager: Optional[CheckpointManager] = None,
            lease: Optional[TrainerLease] = None,
            resume: bool = True,
            on_epoch: Optional[Callable[[Dict[str, Any]], None]] = None,
            ) -> Dict[str, Any]:
        """Run epochs until the holdout stops improving (the reference's
        stopping criterion role, common.lua:193-201).  Returns history +
        final params.

        Elastic contract:

        * *manager* (or the *checkpoint_dir* convenience, which opens a
          retention-managed :class:`CheckpointManager` over that
          directory) commits a sharded checkpoint EVERY epoch and tags
          the best-holdout one; with *resume* (default) fit first
          restores the latest complete checkpoint — on THIS trainer's
          mesh, whatever mesh wrote it — and continues from the next
          epoch with identical early-stopping state;
        * *lease* fences: each epoch starts (and each checkpoint
          commits) only after an affirmative heartbeat;
          :class:`~..coord.lease.TrainerFencedError` propagates to the
          caller with nothing committed for the fenced epoch;
        * determinism: the epoch's batch permutation is seeded
          ``seed + epoch``, so a successor's lineage is bit-identical
          to an uninterrupted run at the same epoch count.
        """
        cfg = self.cfg
        t_start = time.monotonic()
        if manager is None and checkpoint_dir:
            manager = CheckpointManager(LocalDirStorage(checkpoint_dir),
                                        keep_n=cfg.keep_checkpoints)
        global_batch = cfg.bunch_size * self.n_data
        n = x_tr.shape[0]
        steps = max(n // global_batch, 1)
        x_va_d, y_va_d = self.place_batch(x_va, y_va)

        best_val = np.inf
        best_epoch = 0
        start_epoch = 1
        restored = False
        params = opt_state = None
        if manager is not None and resume:
            # restore into the ABSTRACT template (shapes/dtypes only):
            # the recovery path — the very thing trainer_recovery_s
            # times — must not pay a random init + device placement it
            # would immediately overwrite
            got = manager.restore_latest(
                self.abstract_state(),
                mesh=self.mesh, rules=TRAINER_PARTITION_RULES)
            if got is not None:
                state, manifest = got
                params, opt_state = state["params"], state["opt"]
                meta = manifest.get("meta") or {}
                stamped = meta.get("train_config")
                if stamped:
                    # a resume under different hyperparameters would
                    # silently continue a FOREIGN lineage — the typed
                    # config gate, like validate_manifest_against but
                    # for the values the shapes can't see
                    ours = lineage_config(cfg)
                    bad = [f for f in LINEAGE_FIELDS if f in stamped
                           and stamped[f] != ours[f]]
                    if bad:
                        raise CheckpointError(
                            "resume config mismatch vs checkpoint step "
                            f"{manifest['step']}: " + ", ".join(
                                f"{f}={ours[f]!r} (checkpoint has "
                                f"{stamped[f]!r})" for f in bad))
                start_epoch = int(manifest["step"]) + 1
                best_val = float(meta.get("best_val", np.inf))
                best_epoch = int(meta.get("best_epoch", 0))
                restored = True
                if log:
                    log(f"restored checkpoint step {manifest['step']} "
                        f"(best_val {best_val:.4f} @ {best_epoch})")
        if params is None:
            params, opt_state = self.init_state()

        history: List[Dict[str, float]] = []
        last_epoch = cfg.max_epochs
        if restored and (start_epoch - 1 >= cfg.min_epochs
                         and (start_epoch - 1) - best_epoch
                         >= cfg.patience):
            # the restored lineage had already hit the stopping
            # criterion: resuming must not train past it, or every
            # preempt-and-resume cycle would advance one epoch beyond
            # where an uninterrupted run stopped
            last_epoch = start_epoch - 1
        for epoch in range(start_epoch, last_epoch + 1):
            if lease is not None:
                # fence gate: an expired/superseded lease must stop us
                # BEFORE this epoch's optimizer step is applied
                try:
                    lease.ensure_owned()
                except TrainerFencedError:
                    _EPOCHS.inc(outcome="fenced")
                    raise
            rng = np.random.default_rng(cfg.seed + epoch)
            perm = rng.permutation(n)
            need = steps * global_batch
            if need > n:  # static shapes: wrap around (dataset may be
                # smaller than even one global batch)
                perm = np.tile(perm, -(-need // n))
            sel = perm[:need]
            xs = jax.device_put(
                x_tr[sel].reshape(steps, global_batch, x_tr.shape[1]),
                self.epoch_sharding)
            ys = jax.device_put(y_tr[sel].reshape(steps, global_batch),
                                self.epoch_sharding)
            # scoped: the stacked-batch donation is expected to be
            # unaliasable (outputs are params/opt leaves and losses)
            with quiet_unusable_donation():
                params, opt_state, losses = self._train_epoch(
                    params, opt_state, xs, ys)
            val_loss, val_acc = self._eval(params, x_va_d, y_va_d)
            val_loss = float(val_loss)
            # per-epoch HBM gauges (obs/memory): device memory_stats
            # where the backend has them, else the state+batch bytes
            # this trainer holds, labelled analytic
            _memory_obs.sample_device_memory(
                self._devices,
                analytic_bytes_in_use=sum(
                    int(a.nbytes)
                    for a in jax.tree_util.tree_leaves(
                        (params, opt_state, xs, ys))
                    if hasattr(a, "nbytes")))
            rec = {"epoch": epoch,
                   "train_loss": float(np.asarray(losses).mean()),
                   "val_loss": val_loss,
                   "val_acc": float(val_acc)}
            history.append(rec)
            improved = val_loss < best_val - 1e-6
            if improved:
                best_val, best_epoch = val_loss, epoch
            if manager is not None:
                # commit gates: never publish a checkpoint a live
                # successor could already have superseded.  Checked
                # BEFORE the shard upload (don't ship state fenced) and
                # again as the save's precommit hook — immediately
                # before the manifest publish, after the long upload —
                # so the stale-writer race narrows to one blob write.
                # A fence at either gate discards the epoch (nothing
                # committed): it counts as fenced, not applied.
                try:
                    if lease is not None:
                        lease.ensure_owned()
                    manager.save(
                        epoch, {"params": params, "opt": opt_state},
                        rules=TRAINER_PARTITION_RULES,
                        meta={"epoch": epoch, "val_loss": val_loss,
                              "best_val": float(best_val),
                              "best_epoch": best_epoch,
                              "train_config": lineage_config(cfg),
                              "generation": (lease.generation
                                             if lease is not None
                                             else None)},
                        precommit=(lease.ensure_owned
                                   if lease is not None else None))
                except TrainerFencedError:
                    _EPOCHS.inc(outcome="fenced")
                    raise
                if improved:
                    manager.mark_best(epoch)
            _EPOCHS.inc(outcome="applied")
            if restored and epoch == start_epoch:
                # step-recovery time: fit entry (acquire happened just
                # before) -> restored -> first epoch applied + committed
                _RECOVERY_S.set(time.monotonic() - t_start)
            if log:
                log(f"epoch {epoch}: train {rec['train_loss']:.4f} "
                    f"val {val_loss:.4f} acc {rec['val_acc']:.3f}")
            if on_epoch:
                on_epoch(rec)
            if (epoch >= cfg.min_epochs
                    and epoch - best_epoch >= cfg.patience):
                break
        return {"params": params, "opt_state": opt_state,
                "history": history,
                "best_val_loss": best_val, "best_epoch": best_epoch,
                "epochs_run": len(history), "start_epoch": start_epoch,
                "restored": restored}
