"""Pipeline parallelism: GPipe-style microbatch pipelining over the mesh.

The last member of the parallelism portfolio (dp/tp/sp/ep elsewhere;
the reference has none of these, SURVEY.md §2.10): layers are sharded
one-per-rank over the ``model`` axis, and M microbatches flow through the
S stages on a ``lax.scan`` over M+S-1 ticks, activations hopping
stage-to-stage with ``lax.ppermute`` each tick.  Written functionally —
the backward pass IS ``jax.grad`` of the scan: autodiff transposes the
ppermute into the reverse hop and replays the schedule backwards, so the
1F1B-ish bubble structure falls out of the program instead of being
hand-scheduled.

Model shape: an input projection (replicated, applied by stage 0), S
identical ``[H, H]`` tanh blocks (stage s owns block s — the stacked
weights are sharded ``P('model')`` on the stage axis), and a replicated
classifier head applied after the last stage.  That uniform-stage shape
is what pipelining wants on TPU: every tick is the same compiled matmul
on every rank.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import compile as _compile_obs

Params = Dict[str, jax.Array]


@dataclass(frozen=True)
class PipelineConfig:
    n_in: int = 64
    hidden: int = 64
    n_classes: int = 10
    microbatch: int = 8     # rows per microbatch
    dtype: Any = jnp.bfloat16


def init_pipeline_params(key: jax.Array, cfg: PipelineConfig,
                         n_stages: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    H = cfg.hidden
    scale = lambda n: 1.0 / np.sqrt(n)
    return {
        "w_in": jax.random.normal(k1, (cfg.n_in, H), jnp.float32)
        * scale(cfg.n_in),
        # stage axis leads: sharded P("model") so rank s owns block s
        "w_stage": jax.random.normal(k2, (n_stages, H, H), jnp.float32)
        * scale(H),
        "b_stage": jnp.zeros((n_stages, H), jnp.float32),
        "w_out": jax.random.normal(k3, (H, cfg.n_classes), jnp.float32)
        * scale(H),
    }


def pipeline_param_spec(name: str) -> P:
    if name in ("w_stage", "b_stage"):
        return P("model")
    return P()


def _stage_block(h, w, b, dtype):
    return jnp.tanh(h.astype(dtype) @ w.astype(dtype)
                    + b.astype(dtype)).astype(jnp.float32)


def pipeline_forward_local(params: Params, x: jax.Array,
                           cfg: PipelineConfig,
                           model_axis: str = "model") -> jax.Array:
    """Inside shard_map over *model_axis*: ``x`` [N, n_in] (replicated),
    returns [N, n_classes] log-probabilities (replicated).

    N must be a multiple of ``cfg.microbatch``; M = N/microbatch
    microbatches stream through S stages in M+S-1 ticks."""
    S = jax.lax.psum(1, model_axis)
    stage = jax.lax.axis_index(model_axis)
    Bm = cfg.microbatch
    N = x.shape[0]
    if N % Bm != 0:
        raise ValueError(f"batch {N} not a multiple of microbatch {Bm}")
    M = N // Bm
    H = cfg.hidden

    # my stage's block (w_stage arrives sharded: leading dim 1 per rank)
    w = params["w_stage"][0]
    b = params["b_stage"][0]
    # stage 0's injected stream: input projection of each microbatch
    inj = (x.astype(cfg.dtype) @ params["w_in"].astype(cfg.dtype)
           ).astype(jnp.float32).reshape(M, Bm, H)

    T = M + S - 1
    fwd = [(i, i + 1) for i in range(S - 1)]  # stage s -> s+1 (no wrap)

    def tick(carry, t):
        buf, outs = carry  # buf [Bm, H]: activation arriving this tick
        mb = jnp.clip(t, 0, M - 1)
        h_in = jnp.where(stage == 0, inj[mb], buf)
        y = _stage_block(h_in, w, b, cfg.dtype)
        # the LAST stage's output for microbatch t-(S-1) is ready now
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        is_out = (t >= S - 1) & (stage == S - 1)
        outs = outs.at[out_idx].add(
            jnp.where(is_out, y, jnp.zeros_like(y)))
        buf = jax.lax.ppermute(y, model_axis, fwd)
        return (buf, outs), None

    # the scan carry must enter with the device-varying type the body
    # produces: varying over the pipeline axis (the body mixes in
    # axis_index) AND over whatever axes shard the batch — zeros derived
    # from inj inherit the latter, pcast adds the former
    varying = lambda a: jax.lax.pcast(a, model_axis, to="varying")
    outs0 = varying(inj * 0.0)
    buf0 = varying(inj[0] * 0.0)
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                jnp.arange(T, dtype=jnp.int32))
    # only the last stage holds real outputs: one psum replicates them
    outs = jax.lax.psum(outs, model_axis)
    logits = (outs.reshape(N, H).astype(cfg.dtype)
              @ params["w_out"].astype(cfg.dtype)).astype(jnp.float32)
    return jax.nn.log_softmax(logits, axis=-1)


class PipelinedTrainer:
    """SGD over the pipelined classifier on a ``(model, data)`` mesh:
    pipeline stages over ``model``, batch data-parallel over ``data``."""

    def __init__(self, mesh: Mesh, cfg: PipelineConfig = PipelineConfig(),
                 learning_rate: float = 1e-2, seed: int = 0) -> None:
        self.mesh, self.cfg, self.seed = mesh, cfg, seed
        self.n_stages = mesh.shape["model"]
        pspecs = {n: pipeline_param_spec(n)
                  for n in init_pipeline_params(jax.random.key(0), cfg,
                                                self.n_stages)}
        self._pspecs = pspecs

        def local_loss(params, x, y):
            logp = pipeline_forward_local(params, x, cfg)
            nll = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
            return jax.lax.pmean(nll, "data")

        loss_fn = jax.shard_map(
            local_loss, mesh=mesh,
            in_specs=(pspecs, P("data"), P("data")), out_specs=P())

        def train_step(params, x, y):
            loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
            params = jax.tree.map(lambda p, g: p - learning_rate * g,
                                  params, grads)
            return params, loss

        # ledgered jits (obs/compile): compile spans + seconds + shape
        # buckets; per-instance (the closure bakes in the lr)
        self._train_step = _compile_obs.wrap_jit(
            train_step, program="pipe_step", donate_argnums=(0,))
        self._loss = _compile_obs.wrap_jit(loss_fn, program="pipe_loss")

    def init_params(self) -> Params:
        params = init_pipeline_params(jax.random.key(self.seed), self.cfg,
                                      self.n_stages)
        return {n: jax.device_put(
                    a, NamedSharding(self.mesh, self._pspecs[n]))
                for n, a in params.items()}

    def place_batch(self, x: np.ndarray, y: np.ndarray):
        sh = NamedSharding(self.mesh, P("data"))
        return jax.device_put(x, sh), jax.device_put(y, sh)

    def step(self, params: Params, x: np.ndarray, y: np.ndarray):
        xd, yd = self.place_batch(x, y)
        return self._train_step(params, xd, yd)


def pipeline_reference(params: Params, x: np.ndarray,
                       cfg: PipelineConfig) -> np.ndarray:
    """Unpipelined oracle: apply the stage blocks sequentially (same
    dtype discipline as the pipelined path — bf16 matmuls, f32 carry)."""
    h = (jnp.asarray(x).astype(cfg.dtype)
         @ jnp.asarray(params["w_in"]).astype(cfg.dtype))
    h = h.astype(jnp.float32)
    for s in range(params["w_stage"].shape[0]):
        h = _stage_block(h, jnp.asarray(params["w_stage"])[s],
                         jnp.asarray(params["b_stage"])[s], cfg.dtype)
    logits = (h.astype(cfg.dtype)
              @ jnp.asarray(params["w_out"]).astype(cfg.dtype)
              ).astype(jnp.float32)
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))
