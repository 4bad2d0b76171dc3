"""Plain reference for the ``trainer`` kind: the dense decoder's forward
pass and mean next-token loss in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")`` — no kernel, no
``shard_map``, no bf16.  The equations are those of
``mapreduce_tpu/models/transformer.py`` (``_layer_local``,
``forward_local``, ``loss_local``), written out again so that a change
there cannot move this yardstick:

    x   = embed[tokens]                       (no position encoding)
    per layer:
      h = rmsnorm(x) * ln1_scale              (eps 1e-6)
      q, k, v = h @ wqkv[:, 0|1|2]            ([E, 3, H*D], heads split)
      a = softmax(q k^T / sqrt(D), causal) v
      x = x + a @ wo
      h = rmsnorm(x) * ln2_scale
      x = x + gelu(h @ w_in) @ w_out          (tanh-approximate GELU)
    logits = x @ unembed                      (no final norm, untied)
    loss   = mean over positions of -log softmax(logits)[target]

Attention runs in blocks of query rows against the whole context with a
causal mask, and logits and loss in blocks of rows, so that nothing of
size ``T x T`` or ``T x vocab`` is ever whole (at T=32768 and vocab
32768 the float32 logits alone would be 4.3 GB).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * scale


def _attention(q, k, v, block: int):
    """Causal attention, [T, H, D] each, *block* query rows at a time."""
    T, H, D = q.shape
    kt = jnp.transpose(k, (1, 2, 0))            # [H, D, T]
    vt = jnp.transpose(v, (1, 0, 2))            # [H, T, D]
    cols = jnp.arange(T)

    def rows(args):
        qb, row0 = args                         # [block, H, D], scalar
        s = jnp.einsum("bhd,hdt->hbt", qb, kt) / jnp.sqrt(jnp.float32(D))
        mask = cols[None, :] <= (row0 + jnp.arange(block))[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hbt,htd->bhd", p, vt)

    qs = q.reshape(T // block, block, H, D)
    out = jax.lax.map(rows, (qs, jnp.arange(0, T, block)))
    return out.reshape(T, H, D)


def reference_loss(params, tokens, targets, *, n_layers: int, n_heads: int,
                   head_dim: int, block: int = 512):
    """Mean next-token negative log-likelihood of *tokens* ``[B, T]``
    against *targets* ``[B, T]`` under *params* (the trainer's flat
    dict), all in float32.  *block* must divide ``T``."""
    H, D = n_heads, head_dim
    with jax.default_matmul_precision("highest"):
        p = {n: a.astype(jnp.float32) for n, a in params.items()}

        def one(tok, tgt):
            T = tok.shape[0]
            x = p["embed"][tok]
            for i in range(n_layers):
                h = _rmsnorm(x, p[f"L{i}.ln1_scale"])
                qkv = jnp.einsum("te,ecf->ctf", h, p[f"L{i}.wqkv"])
                q, k, v = (qkv[j].reshape(T, H, D) for j in range(3))
                a = _attention(q, k, v, block).reshape(T, H * D)
                x = x + a @ p[f"L{i}.wo"]
                h = _rmsnorm(x, p[f"L{i}.ln2_scale"])
                x = x + jax.nn.gelu(h @ p[f"L{i}.w_in"]) @ p[f"L{i}.w_out"]

            def nll(args):
                xb, tb = args
                logp = jax.nn.log_softmax(xb @ p["unembed"], axis=-1)
                return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

            blocks = (x.reshape(T // block, block, -1),
                      tgt.reshape(T // block, block))
            return jax.lax.map(nll, blocks).reshape(T)

        return jnp.mean(jax.vmap(one)(tokens, targets))
