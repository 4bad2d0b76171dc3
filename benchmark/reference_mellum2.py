"""Plain reference for the ``mellum_trainer`` kind: Mellum2-12B-A2.5B's
forward pass and its training loss in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")`` — no kernel, no ``shard_map``,
no bfloat16, no grouping of the experts' products, no table of tiles —
with its gradient, a layer at a time (:func:`gradient_programs`); AdamW's
first step written out is ``reference_looplm.adamw_first_step``.  Written
from the model's public ``config.json``
(``huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct``, ``model_type``
``mellum``) and the way ``transformers`` computes YaRN's frequencies, not
from ``mapreduce_tpu/models``, which it does not import:

    x = embed[tokens]
    for layer i:
      h = rms(x; ln1_i)
      q = h W_q (H heads), k, v = h W_k, h W_v (Hkv heads), no bias
      q, k = rms over each head's D dims (q_norm, k_norm)
      q, k = rope_i(q), rope_i(k)          (rotate-half over all of D)
      a = softmax(q k^T / sqrt(D) + mask_i) v     (key/value head j
                                  serves query heads j H/Hkv ...)
      x = x + a W_o
      h = rms(x; ln2_i);  p = softmax(h W_r)      (all X experts, float32)
      S = the top_k experts with the largest p
      g_e = p_e / sum_{e' in S} p_e'              (norm_topk_prob)
      x = x + sum_{e in S, lo <= e < lo + n} g_e W2_e(silu(W1_e h) * W3_e h)
    logits = rms(x; final) W_head                 (untied)
    loss = mean next-token cross-entropy

    layer_types[i] "full_attention":  mask allows 0 <= q - k
                                      rope: YaRN (below)
    layer_types[i] "sliding_attention": mask allows 0 <= q - k < window
                                      rope: inv_freq_j = theta^(-2j/D)

YaRN, pair ``j = 0 .. D/2 - 1``: ``extra_j = theta^(-2j/D)``, ``inter_j =
extra_j / factor``; ``c(r) = D ln(original / (2 pi r)) / (2 ln theta)``,
``low = max(floor(c(beta_fast)), 0)``, ``high = min(ceil(c(beta_slow)),
D - 1)``, ``ramp_j = clip((j - low) / (high - low), 0, 1)``; ``inv_freq_j
= inter_j ramp_j + extra_j (1 - ramp_j)``; cos and sin both times
``attention_factor``.  RMSNorm is ``x / sqrt(mean(x^2) + eps) * scale``.

``held = (lo, n)`` is the share of the experts the chip under test
holds: the sum runs over those alone while ``g`` is normalised over all
of ``S``; ``held=None`` is the uncut layer (then ``moe_w_*`` hold every
expert).  The experts are a loop with a mask over ALL tokens, an expert
at a time; attention is a masked softmax over the whole context, *block*
query rows at a time, so that nothing of size ``T x T`` or ``T x vocab``
is ever whole.

Departures and assumptions, each also in the configuration file's
``assumed``: the weights are the trainer's flat dictionary (``W_q`` is
``wq``, ``W_k, W_v`` are ``wkv[:, 0|1]``, ``W_r`` is ``w_router``, ``W1,
W3, W2`` are ``moe_w_gate, moe_w_in, moe_w_out [n, ...]`` for the held
experts, ``W_head`` is ``unembed``; the norms ``ln1_scale, ln2_scale,
final_scale, q_norm_scale, k_norm_scale``); the per-head norm of q and k
is the Qwen3-MoE family's convention, whose keys this config carries.

*given* ``[n_layers, B, T, k]`` puts another's choices in the place of
``S`` (the system's own, so that a comparison of gradients is one of
arithmetic and not of which near-tied expert a token took): the weights,
the output and the gradients are then of THOSE experts, while the
choices and loads returned stay the reference's own ``S`` of the same
layer input.

The keywords that describe the model are the caller's, so that a control
can describe a WRONG one and show that the comparison tells it from the
published: ``window=None`` on every layer (the window left out),
``yarn=None`` (plain rotary on the full layers too), ``score="sigmoid"``
(each expert's sigmoid in the softmax's place).

*operand_dtype*, *remat*, *block*: as in ``reference_looplm.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference_looplm import _rmsnorm


def inverse_frequencies(head_dim: int, theta: float, yarn=None):
    """``(inv_freq [D/2] float32, factor)`` of the module's rotary rules:
    plain without *yarn*, else YaRN's with *yarn* = ``{"factor",
    "original_max_position_embeddings", "beta_fast", "beta_slow",
    "attention_factor"}``."""
    D = head_dim
    j = jnp.arange(D // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * j / D)
    if yarn is None:
        return extra, 1.0

    def c(r):
        return (D * math.log(yarn["original_max_position_embeddings"]
                             / (2 * math.pi * r)) / (2 * math.log(theta)))

    low = max(math.floor(c(yarn["beta_fast"])), 0)
    high = min(math.ceil(c(yarn["beta_slow"])), D - 1)
    ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    inter = extra / yarn["factor"]
    return inter * ramp + extra * (1.0 - ramp), yarn["attention_factor"]


def _rope(x, inv_freq, factor):
    """``x [T, H, D]`` at positions ``0..T-1``: ``x cos + rotate_half(x)
    sin``, cos and sin of ``position * inv_freq`` (each pair's angle on
    both halves) times *factor*."""
    T, _, D = x.shape
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]  # [T, 1, D]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return (x * jnp.cos(angle) + half * jnp.sin(angle)) * factor


def _attention(q, k, v, window, block, mm, keep):
    """Attention of ``q [T, H, D]`` to ``k, v [T, Hkv, D]`` over ``0 <=
    q - k`` (and ``< window`` where one is given), key/value head ``j``
    serving the query heads ``j H/Hkv ...`` (the ``g`` of a group), *block*
    query rows at a time against the whole context; *keep* wraps what
    the backward pass may compute again."""
    T, H, D = q.shape
    Hkv = k.shape[1]
    kt = jnp.transpose(k, (1, 2, 0))            # [Hkv, D, T]
    vt = jnp.transpose(v, (1, 0, 2))            # [Hkv, T, D]
    cols = jnp.arange(T)

    def rows(args):
        qb, row0 = args                         # [block, Hkv, G, D], scalar
        s = mm("bhgd,hdt->hgbt", qb, kt) / jnp.sqrt(jnp.float32(D))
        back = (row0 + jnp.arange(block))[:, None] - cols[None, :]
        mask = back >= 0
        if window is not None:
            mask = mask & (back < window)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        return mm("hgbt,htd->bhgd", p, vt)

    out = jax.lax.map(keep(rows), (
        q.reshape(T // block, block, Hkv, H // Hkv, D),
        jnp.arange(0, T, block)))
    return out.reshape(T, H, D)


def layer_params(params, i: int) -> dict:
    """Layer *i*'s tensors of the flat dictionary, by their bare names
    (``"L3.wq"`` is ``"wq"``)."""
    prefix = f"L{i}."
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def attention_sublayer(x, lp, *, n_heads, n_kv_heads, head_dim, eps,
                       rope, window, block, mm=jnp.einsum,
                       keep=lambda f: f):
    """``x + a W_o`` of the layer whose tensors are *lp*
    (:func:`layer_params`) on ``x [T, E]``; *rope* is
    :func:`inverse_frequencies`'s pair for this layer, *window* its
    window (None: all earlier positions)."""
    T, H, Hkv, D = x.shape[0], n_heads, n_kv_heads, head_dim
    h = _rmsnorm(x, lp["ln1_scale"], eps)
    q = mm("te,ef->tf", h, lp["wq"]).reshape(T, H, D)
    k, v = (mm("te,ef->tf", h, lp["wkv"][:, j]).reshape(T, Hkv, D)
            for j in range(2))
    q = _rope(_rmsnorm(q, lp["q_norm_scale"], eps), *rope)
    k = _rope(_rmsnorm(k, lp["k_norm_scale"], eps), *rope)
    a = _attention(q, k, v, window, block, mm, keep).reshape(T, H * D)
    return x + mm("tf,fe->te", a, lp["wo"])


def router_scores(h, w_router, score: str = "softmax", mm=jnp.einsum):
    """``p [T, X]``: the router's score of every expert for ``h [T, E]``."""
    logits = mm("te,ex->tx", h, w_router)
    return (jax.nn.softmax(logits, axis=-1) if score == "softmax"
            else jax.nn.sigmoid(logits))


def _gated(h, w1, w3, w2, mm):
    return mm("tf,fe->te", jax.nn.silu(mm("te,ef->tf", h, w1))
              * mm("te,ef->tf", h, w3), w2)


def routed_layer(h, w_router, w_gate, w_in, w_out, *, top_k: int,
                 held=None, score: str = "softmax", given=None,
                 mm=jnp.einsum, by_rows=lambda f, rows: f(rows)):
    """The routed expert layer of the module's equations on ``h [T, E]``:
    ``(out [T, E], (chosen [T, k], g [T, k], loads [n]))``.  ``w_gate,
    w_in, w_out [n, ...]`` are the experts ``lo .. lo + n - 1`` of ``held
    = (lo, n)`` (``None``: all of them, from 0).  With *given* ``[T, k]``
    ``g`` and ``out`` are of those experts (an entry under 0 stands for
    the layer's own choice of that slot); ``chosen`` and ``loads`` are
    the layer's own choice either way."""
    p = router_scores(h, w_router, score, mm)
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(p), top_k)
    used = chosen if given is None else jnp.where(given < 0, chosen, given)
    lo, n = held if held is not None else (0, w_in.shape[0])
    picked = jnp.take_along_axis(p, used, axis=1)                   # [T, k]
    g = picked / picked.sum(axis=-1, keepdims=True)
    out, loads = jnp.zeros_like(h), []
    for e in range(n):                      # one expert at a time, masked
        g_e = jnp.where(used == lo + e, g, 0.0).sum(axis=-1)        # [T]
        out = out + by_rows(
            lambda rows, e=e: rows[1][:, None] * _gated(
                rows[0], w_gate[e], w_in[e], w_out[e], mm), (h, g_e))
        loads.append((chosen == lo + e).sum())
    return out, (chosen, g, jnp.stack(loads))


class Model:
    """The model as the caller describes it (the module's keywords), and
    its pieces on one sequence: a layer, and the head with its loss.
    *layer_types* names each layer ``"full_attention"`` or
    ``"sliding_attention"``; *yarn* is the full layers' rotary scaling
    (:func:`inverse_frequencies`), *window* the sliding layers' width;
    *block* must divide ``T``.  *remat* wraps in ``jax.checkpoint`` what
    a backward pass may compute again: a block of attention rows, of an
    expert's rows, of the head's."""

    def __init__(self, *, layer_types, n_heads: int, n_kv_heads: int,
                 head_dim: int, rope_theta: float, yarn, window, eps: float,
                 top_k: int, held=None, score: str = "softmax",
                 block: int = 512, operand_dtype=None, remat: bool = False):
        self.layer_types, self.block, self.eps = tuple(layer_types), block, eps
        self.attention = dict(n_heads=n_heads, n_kv_heads=n_kv_heads,
                              head_dim=head_dim, eps=eps, block=block)
        self.routed = dict(top_k=top_k, held=held, score=score)
        self.ropes = {"full_attention": inverse_frequencies(
                          head_dim, rope_theta, yarn),
                      "sliding_attention": inverse_frequencies(
                          head_dim, rope_theta)}
        self.windows = {"full_attention": None, "sliding_attention": window}
        self.keep = jax.checkpoint if remat else (lambda f: f)

        @jax.custom_jvp
        def rounded(o):
            return o.astype(operand_dtype).astype(jnp.float32)

        # the backward pass sees the rounded operands and rounds nothing
        # more
        rounded.defjvp(lambda o, do: (rounded(*o), do[0]))

        def mm(spec, a, b):
            if operand_dtype is not None:
                a, b = rounded(a), rounded(b)
            return jnp.einsum(spec, a, b)

        self.mm = mm

    def by_rows(self, f, rows):
        """``f`` over *block* rows at a time of the arrays *rows* ``[T,
        ...]``."""
        T = jax.tree.leaves(rows)[0].shape[0]
        out = jax.lax.map(self.keep(f), jax.tree.map(
            lambda a: a.reshape(T // self.block, self.block, *a.shape[1:]),
            rows))
        return out.reshape(T, *out.shape[2:])

    def kind(self, kind: str, T: int) -> tuple:
        """What tells a layer of *kind* from another, as data: ``(inv_freq
        [D/2], factor, window)``, the window of a layer without one ``T``
        (every earlier position is under it).  One compiled layer then
        serves both kinds."""
        window = self.windows[kind]
        return (*self.ropes[kind], T if window is None else window)

    def attend(self, lp, x, kind: tuple):
        """``x + a W_o``: the attention sublayer with the tensors *lp*
        (:func:`layer_params`) on ``x [T, E]``; *kind* is :meth:`kind`'s."""
        *rope, window = kind
        return attention_sublayer(x, lp, rope=rope, window=window,
                                  mm=self.mm, keep=self.keep,
                                  **self.attention)

    def route(self, lp, x, given=None):
        """``(x + the experts' sum, (chosen, g, loads))``: the routed
        sublayer with the tensors *lp* on ``x [T, E]``."""
        m, routing = routed_layer(
            _rmsnorm(x, lp["ln2_scale"], self.eps), lp["w_router"],
            lp["moe_w_gate"], lp["moe_w_in"], lp["moe_w_out"], given=given,
            mm=self.mm, by_rows=self.by_rows, **self.routed)
        return x + m, routing

    def layer(self, lp, x, kind: tuple, given=None):
        """``(x', (chosen, g, loads))`` of a whole layer."""
        lp = {n: a.astype(jnp.float32) for n, a in lp.items()}
        return self.route(lp, self.attend(lp, x, kind), given)

    def losses(self, hp, x, targets):
        """Every position's cross-entropy ``[T]`` of the last layer's
        ``x [T, E]`` under the head's tensors *hp* (``final_scale``,
        ``unembed``)."""
        hp = {n: a.astype(jnp.float32) for n, a in hp.items()}
        x = _rmsnorm(x, hp["final_scale"], self.eps)

        def rows(args):
            hb, tb = args
            logp = jax.nn.log_softmax(
                self.mm("te,ev->tv", hb, hp["unembed"]), axis=-1)
            return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

        return self.by_rows(rows, (x, targets))


def gradient_programs(**model):
    """``f(params, tokens, targets, given=None) -> ((loss [], chosen [L,
    B, T, k] int32, weights [L, B, T, k], loads [L, n]), gradients)`` of
    the model *model* describes (:class:`Model`'s keywords) on *tokens*
    ``[B, T]`` against *targets* ``[B, T]``: the mean loss, the experts
    each token chose in each layer with their weights ``g``, the (token,
    expert) pairs each held expert took, and the loss's gradient for
    every parameter, **a layer at a time**: the forward pass keeps every
    layer's float32 input; the head gives the loss and its gradient for
    the last layer's output; then each layer's own backward pass from
    its kept input, the last layer first.  The three pieces are three
    compiled programs (a layer's kind is data, :meth:`Model.kind`), so
    the most that is ever live is one layer's backward pass beside the
    kept inputs and the gradients, whatever the depth: the whole model's
    gradient as ONE program reserved 11.5 GB at the benchmark's sizes,
    in the one instruction order that fitted the chip at all."""
    m = Model(remat=True, **model)

    def precise(f):
        def g(*args):
            with jax.default_matmul_precision("highest"):
                return f(*args)
        return g

    forward = jax.jit(precise(m.layer))

    @jax.jit
    @precise
    def backward(lp, x, kind, given, dy):
        _, vjp = jax.vjp(lambda lp, x: m.layer(lp, x, kind, given)[0], lp, x)
        return vjp(dy)

    @jax.jit
    @precise
    def head(hp, x, targets, scale):
        ce, vjp = jax.vjp(lambda hp, x: m.losses(hp, x, targets), hp, x)
        return (ce.sum(), *vjp(jnp.full_like(ce, scale)))

    def gradients(params, tokens, targets, given=None):
        B, T = tokens.shape
        L = len(m.layer_types)
        if given is None:                          # every choice its own
            given = jnp.full((L, B, T, m.routed["top_k"]), -1, jnp.int32)
        kinds = [m.kind(kind, T) for kind in m.layer_types]
        grads = {n: jnp.zeros(a.shape, jnp.float32)
                 for n, a in params.items()}

        def add(prefix, part):
            for n, g in part.items():
                grads[prefix + n] = grads[prefix + n] + g

        loss, routings = 0.0, []
        for b in range(B):                         # a sequence at a time
            xs = [params["embed"].astype(jnp.float32)[tokens[b]]]
            routings.append([])
            for i in range(L):
                x, routing = forward(layer_params(params, i), xs[-1],
                                     kinds[i], given[i, b])
                xs.append(x)
                routings[-1].append(routing)
            ce, dhead, dx = head(
                {n: params[n] for n in ("final_scale", "unembed")},
                xs.pop(), targets[b], 1.0 / (B * T))
            loss = loss + ce / (B * T)
            add("", dhead)
            for i in reversed(range(L)):
                dlp, dx = backward(layer_params(params, i), xs.pop(),
                                   kinds[i], given[i, b], dx)
                add(f"L{i}.", dlp)
            grads["embed"] = grads["embed"].at[tokens[b]].add(dx)
        # [B][L] of (chosen, g, loads) to three arrays, layers first
        by_layer = [[jnp.stack(part) for part in zip(*seq)]
                    for seq in routings]
        chosen, g, loads = (jnp.stack(part, axis=1)
                            for part in zip(*by_layer))
        return (loss, chosen, g, loads.sum(axis=1)), grads

    return gradients


def reference_gradients(params, tokens, targets, *, given=None, **model):
    """:func:`gradient_programs` made and called once."""
    return gradient_programs(**model)(params, tokens, targets, given)
