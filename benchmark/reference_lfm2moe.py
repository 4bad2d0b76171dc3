"""Plain reference for the ``moe_trainer`` kind: LFM2-24B-A2B's forward
pass and its training loss in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")`` — no kernel, no ``shard_map``,
no bfloat16, no grouping of the experts' products — with its gradient
(:func:`reference_gradients`); AdamW's first step written out is
``reference_looplm.adamw_first_step``.  Written from the model's public
``config.json`` (``huggingface.co/LiquidAI/LFM2-24B-A2B``, ``model_type``
``lfm2_moe``) and the family's public modelling code as recalled, not
from ``mapreduce_tpu/models``, which it does not import:

    x = embed[tokens]
    for layer i:
      x = x + op_i(rms(x; ln1_i));   x = x + ffn_i(rms(x; ln2_i))
    logits = rms(x; final) embed^T                     (tied embeddings)
    loss = mean next-token cross-entropy

    op, "conv":   b, c, u = split3(h W_in);  v = b * u
                  y_t = sum_{j<K} w[:, j] * v_{t-(K-1)+j}   (v = 0 before 0)
                  out = (c * y) W_out
    op, "attn":   q = h W_q (H heads), k, v = h W_k, h W_v (Hkv heads)
                  q, k = rms over each head's D dims (q_norm, k_norm)
                  q, k = rope(q), rope(k)      (rotate-half, all of D)
                  out = softmax(q k^T / sqrt(D), causal) v W_o
                  (key/value head j serves query heads j H/Hkv ... )
    ffn, "dense": W2 (silu(W1 h) * W3 h)
    ffn, "moe":   s = sigmoid(h W_g);  S = top-k of (s + b)
                  g_e = s_e / (sum_{e' in S} s_e' + 1e-6)
                  out = sum_{e in S, lo <= e < lo + n} g_e W2_e(silu(W1_e h) * W3_e h)

RMSNorm is ``x / sqrt(mean(x^2) + eps) * scale``; nothing has a bias but
the router's selection.  ``held = (lo, n)`` is the share of the experts
the chip under test holds: the sum runs over those alone while ``g`` is
normalised over all of ``S``; ``held=None`` is the uncut layer (then
``moe_w_*`` hold every expert).  The experts are a loop with a mask over
ALL tokens, an expert at a time.

Departures and assumptions, each also in the configuration file's
``assumed``: the weights are the trainer's flat dictionary (``W_in`` is
``conv_in [E, 3, E]`` in the order b, c, u; ``W_q`` is ``wq``, ``W_k,
W_v`` are ``wkv[:, 0|1]``; ``W1, W3, W2`` are ``w_gate, w_in, w_out`` and
``moe_w_gate, moe_w_in, moe_w_out [n, ...]`` for the held experts; the
norms ``ln1_scale, ln2_scale, final_scale, q_norm_scale,
k_norm_scale``); the split's order, the tied head, the router product in
float32 and the bias as a fixed buffer are read from memory of the
public code.

*given* ``[n_moe, B, T, k]`` puts another's choices in the place of
``S`` (the system's own, so that a comparison of gradients is one of
arithmetic and not of which near-tied expert a token took): the weights,
the output and the gradients are then of THOSE experts, while the
choices and loads returned stay the reference's own ``S`` of the same
layer input, for the comparison of the routing itself.

*rule* is for the controls and the tests: a WRONG routing rule, to show
that the comparison tells it from the published one — ``"biased_weights"``
weights by ``s + b``, ``"held_norm"`` normalises over the chosen experts
held here only, ``"softmax"`` scores by a softmax over the experts.

*operand_dtype*, *remat*, *block*: as in ``reference_looplm.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference_looplm import _attention, _rmsnorm, _rope


def _gated(h, w1, w3, w2, mm):
    return mm("tf,fe->te", jax.nn.silu(mm("te,ef->tf", h, w1))
              * mm("te,ef->tf", h, w3), w2)


def routed_layer(h, w_router, bias, w_gate, w_in, w_out, *, top_k: int,
                 held=None, rule: str = "published", given=None,
                 mm=jnp.einsum, by_rows=lambda f, x: f(x)):
    """The routed expert layer of the module's equations on ``h [T, E]``:
    ``(out [T, E], (chosen [T, k], g [T, k], loads [n]))``.  ``w_gate,
    w_in, w_out [n, ...]`` are the experts ``lo .. lo + n - 1`` of ``held
    = (lo, n)`` (``None``: all of them, from 0).  With *given* ``[T, k]``
    ``g`` and ``out`` are of those experts; ``chosen`` and ``loads`` are
    the layer's own choice either way."""
    logits = mm("te,ex->tx", h, w_router)
    s = (jax.nn.softmax(logits, axis=-1) if rule == "softmax"
         else jax.nn.sigmoid(logits))
    bias = 0.0 if bias is None else bias
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(s) + bias, top_k)
    used = chosen if given is None else given
    lo, n = held if held is not None else (0, w_in.shape[0])
    scores = s + bias if rule == "biased_weights" else s
    picked = jnp.take_along_axis(scores, used, axis=1)              # [T, k]
    here = (used >= lo) & (used < lo + n)
    total = (jnp.where(here, picked, 0.0) if rule == "held_norm"
             else picked).sum(axis=-1, keepdims=True)
    g = picked / (total + 1e-6)
    out, loads = jnp.zeros_like(h), []
    for e in range(n):                      # one expert at a time, masked
        g_e = jnp.where(used == lo + e, g, 0.0).sum(axis=-1)        # [T]
        y = by_rows(lambda hb, e=e: _gated(hb, w_gate[e], w_in[e],
                                           w_out[e], mm), h)
        out = out + g_e[:, None] * y
        loads.append((chosen == lo + e).sum())
    return out, (chosen, g, jnp.stack(loads))


def reference_outputs(params, tokens, targets, *, layer_ops, layer_ffns,
                      n_heads: int, n_kv_heads: int, head_dim: int,
                      rope_theta: float, eps: float, top_k: int,
                      held=None, block: int = 512, operand_dtype=None,
                      remat: bool = False, rule: str = "published",
                      given=None):
    """``(loss [], chosen [n_moe, B, T, k] int32, weights [n_moe, B, T,
    k], loads [n_moe, n])`` of *tokens* ``[B, T]`` against *targets*
    ``[B, T]``: the mean loss, the experts each token chose in each
    expert layer with their weights ``g``, and the (token, expert) pairs
    each held expert took.  *block* must divide ``T``."""
    H, Hkv, D = n_heads, n_kv_heads, head_dim
    keep = jax.checkpoint if remat else (lambda f: f)

    @jax.custom_jvp
    def rounded(o):
        return o.astype(operand_dtype).astype(jnp.float32)

    # the backward pass sees the rounded operands and rounds nothing more
    rounded.defjvp(lambda o, do: (rounded(*o), do[0]))

    def mm(spec, a, b):
        if operand_dtype is not None:
            a, b = rounded(a), rounded(b)
        return jnp.einsum(spec, a, b)

    def by_rows(f, x):
        """``f`` over *block* rows of ``x [T, ...]`` at a time."""
        T = x.shape[0]
        out = jax.lax.map(keep(f), x.reshape(T // block, block,
                                             *x.shape[1:]))
        return out.reshape(T, *out.shape[2:])

    with jax.default_matmul_precision("highest"):
        p = {n: a.astype(jnp.float32) for n, a in params.items()}

        def conv_op(h, i):
            T = h.shape[0]
            w_in, w = p[f"L{i}.conv_in"], p[f"L{i}.conv_w"]
            b, c, u = (mm("te,ef->tf", h, w_in[:, j]) for j in range(3))
            K = w.shape[1]
            v = jnp.concatenate([jnp.zeros((K - 1, b.shape[1]), h.dtype),
                                 b * u])
            y = sum(w[:, j] * v[j:j + T] for j in range(K))
            return mm("tf,fe->te", c * y, p[f"L{i}.conv_out"])

        def attn_op(h, i):
            T = h.shape[0]
            q = mm("te,ef->tf", h, p[f"L{i}.wq"]).reshape(T, H, D)
            k, v = (mm("te,ef->tf", h, p[f"L{i}.wkv"][:, j])
                    .reshape(T, Hkv, D) for j in range(2))
            q = _rmsnorm(q, p[f"L{i}.q_norm_scale"], eps)
            k = _rmsnorm(k, p[f"L{i}.k_norm_scale"], eps)
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
            a = _attention(q, k, v, block, mm, keep).reshape(T, H * D)
            return mm("tf,fe->te", a, p[f"L{i}.wo"])

        def dense_ffn(h, i):
            return by_rows(lambda hb: _gated(
                hb, p[f"L{i}.w_gate"], p[f"L{i}.w_in"], p[f"L{i}.w_out"],
                mm), h)

        def moe_ffn(h, i, given):
            return routed_layer(
                h, p[f"L{i}.w_router"], p.get(f"L{i}.router_bias"),
                p[f"L{i}.moe_w_gate"], p[f"L{i}.moe_w_in"],
                p[f"L{i}.moe_w_out"], top_k=top_k, held=held, rule=rule,
                given=given, mm=mm, by_rows=by_rows)

        def layer(x, given, i):
            h = _rmsnorm(x, p[f"L{i}.ln1_scale"], eps)
            x = x + (conv_op if layer_ops[i] == "conv" else attn_op)(h, i)
            h = _rmsnorm(x, p[f"L{i}.ln2_scale"], eps)
            if layer_ffns[i] == "moe":
                m, routing = moe_ffn(h, i, given)
                return x + m, routing
            return x + dense_ffn(h, i), None

        def one(tok, tgt, given):
            x = p["embed"][tok]
            routings = []
            for i in range(len(layer_ops)):
                x, routing = keep(functools.partial(layer, i=i))(
                    x, None if given is None or layer_ffns[i] != "moe"
                    else given[len(routings)])
                if routing is not None:
                    routings.append(routing)
            x = _rmsnorm(x, p["final_scale"], eps)

            def rows(args):
                hb, tb = args
                logp = jax.nn.log_softmax(
                    mm("te,ve->tv", hb, p["embed"]), axis=-1)
                return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

            T = x.shape[0]
            ce = jax.lax.map(keep(rows), (x.reshape(T // block, block, -1),
                                          tgt.reshape(T // block, block)))
            chosen, g, loads = (jnp.stack(r) for r in zip(*routings))
            return ce.reshape(T), chosen, g, loads   # [n_moe, T, k] x 2

        # a sequence at a time, each computed again in the backward
        # pass: a block of attention rows of ONE sequence is live
        ce, chosen, g, loads = jax.lax.map(
            keep(lambda a: one(*a)),
            (tokens, targets,
             None if given is None else jnp.swapaxes(given, 0, 1)))
        return (ce.mean(), jnp.swapaxes(chosen, 0, 1), jnp.swapaxes(g, 0, 1),
                loads.sum(axis=0))


def reference_gradients(params, tokens, targets, **kw):
    """``((loss, chosen, weights, loads), gradients)``:
    :func:`reference_outputs`
    (same keywords) and the loss's gradient for every parameter; the
    router's bias has none (it only selects)."""
    def loss(p):
        out = reference_outputs(p, tokens, targets, remat=True, **kw)
        return out[0], out

    (_, out), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return out, grads
