"""What a looped language model adds to ``models/transformer.py``'s
block: rotary positions, and the objective over the passes.

A looped model (``TransformerConfig.loop_steps`` R > 1; Ouro-2.6B's
``total_ut_steps``) runs one stack of layers R times over every token
with the same parameters — that loop is ``transformer.forward_local``'s —
and has a head, one shared exit gate and a loss after every pass: the
objective is the expected next-token loss under the learned exit
distribution less an entropy term (:func:`looped_loss`).  Everything
here runs INSIDE the trainer's ``shard_map``; ``cfg`` is the
``TransformerConfig``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def yarn_ramp(cfg) -> np.ndarray:
    """YaRN's blend of each rotary pair ``i = 0 .. D/2 - 1``, float32 in
    [0, 1]: 0 keeps the pair's plain frequency, 1 divides it by
    ``cfg.yarn_factor``.  A pair that turns ``r`` times over the
    ``cfg.yarn_original_positions`` the model was trained on has index
    ``c(r) = D ln(original / (2 pi r)) / (2 ln theta)``; the ramp rises
    linearly from ``floor(c(beta_fast))`` to ``ceil(c(beta_slow))``,
    clipped to the pairs there are (pairs 18 to 35 of 64 at theta 5e5,
    8,192 positions, betas 32 and 1)."""
    D = cfg.head_dim

    def pair_of(turns):
        return (D * math.log(cfg.yarn_original_positions
                             / (2 * math.pi * turns))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(pair_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(pair_of(cfg.yarn_beta_slow)), D - 1)
    if low == high:
        high += 0.001           # no division by zero: a step, not a ramp
    return np.clip((np.arange(D // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)


def rope_tables(cfg, t_local: int, data_axis: str, yarn: bool = False,
                dim=None):
    """``(cos, sin)``, each [T_local, D/2] float32, of the rotary angles
    ``position * inv_freq_i`` at this shard's GLOBAL positions; ``D`` is
    *dim*, the dimensions the embedding turns (None = all of
    ``cfg.head_dim``).  Plain: ``inv_freq_i = theta**(-2i/D)``.  With *yarn* (``cfg.yarn_factor`` s
    over ``cfg.yarn_original_positions``): ``inv_freq_i = (plain_i / s)
    ramp_i + plain_i (1 - ramp_i)`` with :func:`yarn_ramp`'s blend, and
    cos and sin both times the attention factor
    (``cfg.yarn_attention_factor``, else ``0.1 ln s + 1``), which so
    multiplies q and k alike and the scores by its square."""
    pos = (jax.lax.axis_index(data_axis) * t_local
           + jnp.arange(t_local)).astype(jnp.float32)
    dim = dim or cfg.head_dim
    inv_freq = 1.0 / (jnp.float32(cfg.rope_theta) ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if yarn:
        ramp = jnp.asarray(yarn_ramp(cfg))
        inv_freq = (inv_freq / jnp.float32(cfg.yarn_factor)) * ramp \
            + inv_freq * (1.0 - ramp)
    angle = pos[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if not yarn:
        return cos, sin
    factor = cfg.yarn_attention_factor
    if factor is None:
        factor = 0.1 * math.log(cfg.yarn_factor) + 1.0
    return cos * jnp.float32(factor), sin * jnp.float32(factor)


def apply_rope(x: jax.Array, tables, seq_axis: int) -> jax.Array:
    """Rotate-half rotary embedding of ``x [..., D]`` whose sequence lies
    on *seq_axis*: ``x * cos + rotate_half(x) * sin`` with the halves
    written out, in float32, back in ``x``'s type; *tables* is
    :func:`rope_tables`'s."""
    shape = [1] * x.ndim
    shape[seq_axis], shape[-1] = tables[0].shape
    cos, sin = (t.reshape(shape) for t in tables)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def looped_loss(hs, targets, params, chunk_nll, cfg, data_axis: str):
    """The looped objective of :func:`transformer.loss_local` from the R hidden
    states ``hs`` [R, B, T, E].  One ``loss_block`` scan covers the R
    passes of every chunk, a (pass, chunk) pair a trip, so that one
    [B, Tc, V] block of logits is live at a time, as in the dense loss;
    each trip also takes its rows' gate logit.  What mixes the passes is
    elementwise on [R, B, T] afterwards."""
    R, B, T, E = hs.shape
    Tc = cfg.loss_block or T
    if T % Tc != 0:
        raise ValueError(f"loss_block {Tc} must divide T_local {T}")
    C = T // Tc

    # checkpointed, so that a trip keeps its bfloat16 rows for the
    # gate's backward pass and not their float32 copy; the loss keeps
    # what its own rule says (transformer.chunk_nll)
    @jax.checkpoint
    def gate_logit(x_c):
        with jax.named_scope("tf.exit_gate"):
            return jnp.einsum("bte,e->bt", x_c.astype(jnp.float32),
                              params["exit_w"],
                              precision=jax.lax.Precision.HIGHEST
                              ) + params["exit_b"][0]

    def trip(_, xt):
        x_c, t_c = xt
        return None, (chunk_nll(x_c, t_c), gate_logit(x_c))

    with jax.named_scope("tf.loss"):
        xs = jnp.moveaxis(hs.reshape(R, B, C, Tc, E), 2, 1)
        ts = jnp.broadcast_to(
            jnp.moveaxis(targets.reshape(B, C, Tc), 1, 0)[None],
            (R, C, B, Tc))
        _, (nll, gate) = jax.lax.scan(
            trip, None,
            (xs.reshape(R * C, B, Tc, E), ts.reshape(R * C, B, Tc)))
        nll, gate = (jnp.moveaxis(a.reshape(R, C, B, Tc), 1, 2)
                     .reshape(R, B, T) for a in (nll, gate))
        with jax.named_scope("tf.exit_gate"):
            # p_t = lam_t prod_{j<t}(1 - lam_j), the last pass takes the
            # rest: with 1 - lam taken by subtraction the R masses of a
            # position sum to 1 to float32 rounding
            lam = jax.nn.sigmoid(gate)
            left, masses = jnp.ones_like(lam[0]), []
            for t in range(R - 1):
                masses.append(lam[t] * left)
                left = left * (1.0 - lam[t])
            p = jnp.stack(masses + [left])
            # p log p is 0 at p = 0 (a gate saturated in float32), value
            # and gradient
            some = p > 0
            neg_entropy = jnp.where(
                some, p * jnp.log(jnp.where(some, p, 1.0)), 0.0).sum(axis=0)
        expected = (p * nll).sum(axis=0)
        total = (expected.mean()
                 + jnp.float32(cfg.exit_entropy_weight) * neg_entropy.mean())
        stats = jnp.stack([nll.mean(axis=(1, 2)), p.mean(axis=(1, 2))])
    return (jax.lax.pmean(total, data_axis),
            jax.lax.pmean(stats, data_axis))
