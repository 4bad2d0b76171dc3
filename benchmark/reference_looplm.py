"""Plain reference for the ``looped_trainer`` kind: a looped language
model's forward pass and its training objective in float32 ``jax.numpy``
at ``jax.default_matmul_precision("highest")`` — no kernel, no
``shard_map``, no bfloat16 — and what the first AdamW step makes of its
gradient (:func:`reference_gradients`, :func:`adamw_first_step`).
Written from the
description of Ouro-2.6B (``huggingface.co/ByteDance/Ouro-2.6B``
``config.json``; the Ouro paper's Stage-I objective), not from
``mapreduce_tpu/models/transformer.py``, which it does not import:

    x = embed[tokens]
    for t = 1..R:                               (the same weights at every t)
      for l = 1..L:
        h = rmsnorm(x; ln1_l);  q, k, v = h Wq_l, h Wk_l, h Wv_l
        q, k = rope(q), rope(k)                 (rotate-half over all of D)
        a = softmax(q k^T / sqrt(D), causal) v
        x = x + rmsnorm(a Wo_l; ln1_out_l)      (a norm on the output too)
        h = rmsnorm(x; ln2_l)
        x = x + rmsnorm((silu(h Wg_l) * (h Wu_l)) Wd_l; ln2_out_l)
      x = rmsnorm(x; final)                     (closes each pass)
      h_t = x;  z_t = h_t W_head;  lam_t = sigmoid(h_t . exit_w + exit_b)
    p_t = lam_t prod_{j<t}(1 - lam_j)  for t < R;   p_R = prod_{j<R}(1 - lam_j)
    objective = mean over positions of
                [ sum_t p_t CE(z_t, target) + beta sum_t p_t log p_t ]

RMSNorm is ``x / sqrt(mean(x^2) + eps) * scale``; everything is
bias-free but the gate.  Departures from the published model, each also
in the configuration file's ``assumed``:

* the weights are the trainer's flat dictionary, so the three attention
  projections are the slices ``wqkv[:, 0|1|2]`` of one ``[E, 3, H*D]``
  tensor, ``Wg, Wu, Wd`` are ``w_gate, w_in, w_out``, and the head is
  ``unembed``: a naming, not a change of the mathematics;
* that the final norm's output, and not the un-normed stream, is what
  the next pass starts from is read from the model's public modelling
  code from memory;
* ``beta`` is not in ``config.json``; the caller passes it;
* ``early_exit_threshold`` is an inference setting and takes no part.

Attention runs in blocks of query rows against the whole context with a
causal mask, and logits and loss in blocks of rows, so that nothing of
size ``T x T`` or ``T x vocab`` is ever whole.

*operand_dtype* is how the benchmark reads what a LOWER precision would
give: every matrix product's two operands are rounded to that type first
(the product itself stays float32 at ``highest``; the backward pass
multiplies by the rounded operands and rounds nothing more).  ``None``,
the default, rounds nothing and is the reference.

*remat* changes no number: each layer, each block of attention rows and
each block of logits is computed again in the backward pass and not
kept, so that the gradient at a cell's sizes fits one chip beside the
weights (32 layer applications of 8,192 positions at width 2048).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """Rotary embedding of ``x [T, H, D]`` at positions ``0..T-1``:
    ``x * cos + rotate_half(x) * sin``, the angle of dimension pair ``i``
    being ``position * theta**(-2i/D)`` and ``rotate_half(x)`` the halves
    of the last axis swapped, the second negated."""
    T, _, D = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]  # [T, 1, D]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def _attention(q, k, v, block, mm, keep):
    """Causal attention, [T, H, D] each, *block* query rows at a time;
    *keep* wraps what the backward pass may compute again."""
    T, H, D = q.shape
    kt = jnp.transpose(k, (1, 2, 0))            # [H, D, T]
    vt = jnp.transpose(v, (1, 0, 2))            # [H, T, D]
    cols = jnp.arange(T)

    def rows(args):
        qb, row0 = args                         # [block, H, D], scalar
        s = mm("bhd,hdt->hbt", qb, kt) / jnp.sqrt(jnp.float32(D))
        mask = cols[None, :] <= (row0 + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return mm("hbt,htd->bhd", p, vt)

    out = jax.lax.map(keep(rows), (q.reshape(T // block, block, H, D),
                                   jnp.arange(0, T, block)))
    return out.reshape(T, H, D)


def reference_outputs(params, tokens, targets, *, n_layers: int,
                      n_heads: int, head_dim: int, loop_steps: int,
                      rope_theta: float, beta: float, eps: float = 1e-6,
                      block: int = 512, operand_dtype=None,
                      remat: bool = False):
    """``(objective [], pass_losses [R], exit_masses [R])`` of *tokens*
    ``[B, T]`` against *targets* ``[B, T]`` under *params*: the training
    objective, the mean next-token loss of each pass's head, and the mean
    exit probability of each pass.  *block* must divide ``T``."""
    L, H, D, R = n_layers, n_heads, head_dim, loop_steps
    keep = jax.checkpoint if remat else (lambda f: f)

    @jax.custom_jvp
    def rounded(o):
        return o.astype(operand_dtype).astype(jnp.float32)

    # the rounding passes derivatives through: the backward pass sees
    # the rounded operands, and its own are not rounded again (a
    # cotangent of 1e-6 would be flushed to zero in 8 bits)
    rounded.defjvp(lambda o, do: (rounded(*o), do[0]))

    def mm(spec, a, b):
        if operand_dtype is not None:
            a, b = rounded(a), rounded(b)
        return jnp.einsum(spec, a, b)

    with jax.default_matmul_precision("highest"):
        p = {n: a.astype(jnp.float32) for n, a in params.items()}

        def layer(x, i):
            T = x.shape[0]
            h = _rmsnorm(x, p[f"L{i}.ln1_scale"], eps)
            q, k, v = (mm("te,ef->tf", h, p[f"L{i}.wqkv"][:, j])
                       .reshape(T, H, D) for j in range(3))
            a = _attention(_rope(q, rope_theta), _rope(k, rope_theta),
                           v, block, mm, keep).reshape(T, H * D)
            x = x + _rmsnorm(mm("tf,fe->te", a, p[f"L{i}.wo"]),
                             p[f"L{i}.ln1_out_scale"], eps)
            h = _rmsnorm(x, p[f"L{i}.ln2_scale"], eps)
            u = (jax.nn.silu(mm("te,ef->tf", h, p[f"L{i}.w_gate"]))
                 * mm("te,ef->tf", h, p[f"L{i}.w_in"]))
            return x + _rmsnorm(mm("tf,fe->te", u, p[f"L{i}.w_out"]),
                                p[f"L{i}.ln2_out_scale"], eps)

        def one_pass(x):
            for i in range(L):
                x = keep(functools.partial(layer, i=i))(x)
            return _rmsnorm(x, p["final_scale"], eps)

        def head(h, tgt):
            """[T, E] -> per-position loss [T] and gate lam [T]."""
            T = h.shape[0]

            def rows(args):
                hb, tb = args
                logp = jax.nn.log_softmax(
                    mm("te,ev->tv", hb, p["unembed"]), axis=-1)
                return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

            ce = jax.lax.map(keep(rows), (h.reshape(T // block, block, -1),
                                          tgt.reshape(T // block, block)))
            lam = jax.nn.sigmoid(mm("te,e->t", h, p["exit_w"])
                                 + p["exit_b"][0])
            return ce.reshape(T), lam

        def one(tok, tgt):
            x = p["embed"][tok]

            def a_pass(x, _):                   # the same weights at every t
                x = one_pass(x)
                return x, head(x, tgt)

            _, (ce, lam) = jax.lax.scan(a_pass, x, None, length=R)
            left = jnp.ones_like(lam[0])        # prod_{j<t} (1 - lam_j)
            probs = []
            for t in range(R - 1):
                probs.append(lam[t] * left)
                left = left * (1.0 - lam[t])
            probs.append(left)
            return ce, jnp.stack(probs)                     # [R, T] each

        ce, probs = jax.vmap(one, out_axes=1)(tokens, targets)  # [R, B, T]
        # p log p is 0 where p is: a gate saturated in float32 is no NaN
        plogp = jnp.where(probs > 0, probs * jnp.log(
            jnp.where(probs > 0, probs, 1.0)), 0.0)
        objective = jnp.mean((probs * ce).sum(axis=0)
                             + beta * plogp.sum(axis=0))
        return objective, ce.mean(axis=(1, 2)), probs.mean(axis=(1, 2))


def reference_gradients(params, tokens, targets, **kw):
    """``((objective, pass_losses, exit_masses), gradients)``: the outputs
    of :func:`reference_outputs` (same keywords) and the gradient of the
    objective for every parameter — a layer's weight is used in every
    pass, so its gradient is the sum over the passes."""
    def objective(p):
        out = reference_outputs(p, tokens, targets, remat=True, **kw)
        return out[0], out

    (_, out), grads = jax.value_and_grad(objective, has_aux=True)(params)
    return out, grads


def adamw_first_step(p, g, *, learning_rate: float, b1: float, b2: float,
                     eps: float, weight_decay: float):
    """What AdamW's FIRST step adds to a parameter *p* whose gradient is
    *g*, written out (Loshchilov & Hutter; both moments start at zero and
    are bias-corrected, so the first step's are ``g`` and ``g*g``)."""
    m = (1.0 - b1) * g
    v = (1.0 - b2) * g * g
    m_hat, v_hat = m / (1.0 - b1), v / (1.0 - b2)
    return -learning_rate * (m_hat / (jnp.sqrt(v_hat) + eps)
                             + weight_decay * p)
