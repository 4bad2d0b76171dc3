"""Plain reference for the ``glm_trainer`` kind: GLM-4.7-Flash's forward
pass, its two training losses, every gradient and the move of its
selection bias in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")`` — no kernel, no ``shard_map``,
no bfloat16, no grouping of the experts' products — the gradient a layer
at a time (:func:`gradient_programs`); AdamW's first step written out is
``reference_looplm.adamw_first_step``.  Written from the model's public
``config.json`` (``huggingface.co/zai-org/GLM-4.7-Flash``, ``model_type``
``glm4_moe_lite``) and the equations of DeepSeek-V2 (latent attention)
and DeepSeek-V3 (the router, the prediction block), whose keys that file
carries, not from ``mapreduce_tpu/models``, which it does not import:

    x = embed[tokens]
    for layer i:
      h = rms(x; ln1_i)
      c_q = rms(h W_qa; q_a_norm);   [q_nope | q_rope] = c_q W_qb   (H heads)
      [c_kv | k_r] = h W_kva;        c_kv = rms(c_kv; kv_a_norm)
      [k_nope | v] = c_kv W_kvb                                  (H heads)
      q = [q_nope | rope(q_rope)];   k = [k_nope | rope(k_r)]    (k_r: ONE
                              rotary key, the same for all H heads)
      x = x + softmax(q k^T / sqrt(d_nope + d_rope), causal) v W_o
      h = rms(x; ln2_i)
      layer 0 (first_k_dense_replace):  x = x + W2 (silu(W1 h) * W3 h)
      later layers:  s = sigmoid(h W_r)             (all X experts)
        S = the top_k experts with the largest s + b   (b selects only)
        g_e = c s_e / (sum_{e' in S} s_e' + 1e-6)      (c: routed_scaling)
        x = x + sum_{e in S, lo <= e < lo + n} g_e Expert_e(h) + Shared(h)
    L_main = mean CE(rms(x_L; final) W_head, next token)
    u = [rms(embed[next token]; enorm) ; rms(x_L; hnorm)] W_eh
    y = one more layer of the later kind on u, positions as above
    L_mtp = mean CE(rms(y; final') W_head, token after next)   over the
            T - 1 positions that have one
    L = L_main + lambda L_mtp
    after the step:  b_e += gamma sign(mean load - load_e)   over ALL X
            experts of each expert layer, from the step's own S

``rope`` is rotate-half over the ``d_rope`` dimensions (``inv_freq_j =
theta^(-2j/d_rope)``); RMSNorm is ``x / sqrt(mean(x^2) + eps) * scale``;
``Expert_e`` and ``Shared`` are gated FFNs ``W2 (silu(W1 h) * W3 h)``.
``held = (lo, n)`` is the share of the experts the chip under test
holds: the sum runs over those alone while ``g`` is normalised over all
of ``S``; ``held=None`` is the uncut layer (then ``moe_w_*`` hold every
expert).  The shared expert is whole on every chip and counted once.
The experts are a loop with a mask over ALL tokens, an expert at a time;
attention is a masked softmax over the whole context, *block* query rows
at a time, so that nothing of size ``T x T`` or ``T x vocab`` is ever
whole.

Departures and assumptions, each also in the configuration file's
``assumed``: the weights are the trainer's flat dictionary (``W_qa,
W_qb, W_kva, W_kvb, W_o`` are ``wq_a, wq_b, wkv_a, wkv_b, wo``, a head's
columns together, ``nope`` before ``rope`` in ``wq_b``, the latent
before the rotary key in ``wkv_a``, ``k_nope`` before ``v`` in
``wkv_b``; ``W1, W3, W2`` are ``w_gate, w_in, w_out``, ``moe_w_gate,
moe_w_in, moe_w_out [n, ...]`` for the held experts and ``shared_w_gate,
shared_w_in, shared_w_out``; ``W_r``, ``b`` are ``w_router``,
``router_bias``; the prediction block is layer ``L`` with ``enorm_scale,
hnorm_scale, w_eh, final_scale`` beside its layer's tensors); the block
reads ``x_L`` before the main model's final norm; it runs all ``T``
positions, the last one's loss masked (causal: it changes nothing for
the others; its four choices count in the block's loads); ``lambda`` and
``gamma`` are not in the config.

*given* ``[expert layers, B, T, k]`` (the main model's expert layers in
order, then the block's) puts another's choices in the place of ``S``
(the system's own, so that a comparison of gradients is one of
arithmetic and not of which near-tied expert a token took): the weights,
the output, the gradients and the bias's move are then of THOSE experts
(the rule reads "the step's own choices"; after set-up has evened the
loads to a few pairs around the mean, the sign of ``mean load - load_e``
is a coin's for any second routing, however close), while the choices
and the held experts' loads returned stay the reference's own ``S`` of
the same layer input.

The keywords that describe the model are the caller's, so that a control
can describe a WRONG one and show that the comparison tells it from the
published: ``rope=False`` (no rotary embedding), ``shared_key=False``
(head ``j`` reads the rotary key rolled ``j`` places: a key a head where
all share one), ``latent_norms=False``, ``shared=False`` (no shared
expert), ``routed_scale=1.0``, ``mtp_weight=0.0``.

*operand_dtype*, *remat*, *block*: as in ``reference_looplm.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference_looplm import _attention, _rmsnorm, _rope

#: the prediction block's tensors beside its layer's
JOIN = ("enorm_scale", "hnorm_scale", "w_eh", "final_scale")


def layer_params(params, i: int) -> dict:
    """Layer *i*'s tensors of the flat dictionary, by their bare names
    (``"L3.wq_a"`` is ``"wq_a"``)."""
    prefix = f"L{i}."
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def _gated(h, w1, w3, w2, mm):
    return mm("tf,fe->te", jax.nn.silu(mm("te,ef->tf", h, w1))
              * mm("te,ef->tf", h, w3), w2)


def latent_qkv(h, lp, *, n_heads: int, rope_dim: int, theta: float,
               eps: float, rope: bool = True, shared_key: bool = True,
               latent_norms: bool = True, mm=jnp.einsum):
    """``(q, k, v)``, each ``[T, H, .]``, of the module's latent
    attention for ``h [T, E]``; the widths come from the tensors'
    shapes."""
    T, H = h.shape[0], n_heads
    rank = lp["kv_a_norm_scale"].shape[0]
    c_q = mm("te,er->tr", h, lp["wq_a"])
    kv_a = mm("te,er->tr", h, lp["wkv_a"])
    c_kv, k_r = kv_a[:, :rank], kv_a[:, rank:]
    if latent_norms:
        c_q = _rmsnorm(c_q, lp["q_a_norm_scale"], eps)
        c_kv = _rmsnorm(c_kv, lp["kv_a_norm_scale"], eps)
    q = mm("tr,rf->tf", c_q, lp["wq_b"]).reshape(T, H, -1)
    kv = mm("tr,rf->tf", c_kv, lp["wkv_b"]).reshape(T, H, -1)
    nope = q.shape[-1] - rope_dim
    q_nope, q_r = q[..., :nope], q[..., nope:]
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if shared_key:
        k_r = jnp.broadcast_to(k_r[:, None, :], (T, H, rope_dim))
    else:                           # the control's: a key a head
        k_r = jnp.stack([jnp.roll(k_r, j, axis=-1) for j in range(H)],
                        axis=1)
    if rope:
        q_r, k_r = _rope(q_r, theta), _rope(k_r, theta)
    return (jnp.concatenate([q_nope, q_r], axis=-1),
            jnp.concatenate([k_nope, k_r], axis=-1), v)


def routed_layer(h, lp, *, top_k: int, held=None, routed_scale: float = 1.0,
                 shared: bool = True, given=None, mm=jnp.einsum,
                 by_rows=lambda f, rows: f(rows)):
    """The expert layer of the module's equations on ``h [T, E]``: ``(out
    [T, E], (chosen [T, k], g [T, k], loads [n], all_loads [X]))``.
    ``lp["moe_w_*"] [n, ...]`` are the experts ``lo .. lo + n - 1`` of
    ``held = (lo, n)`` (``None``: all of them, from 0).  With *given*
    ``[T, k]`` ``g`` and ``out`` are of those experts (an entry under 0
    stands for the layer's own choice of that slot); ``chosen`` and
    ``loads`` are the layer's own choice either way.  ``all_loads`` counts
    every expert of the router in the choices the output was made from,
    *given*'s where there are any: what the bias's rule reads."""
    s = jax.nn.sigmoid(mm("te,ex->tx", h, lp["w_router"]))
    bias = lp.get("router_bias", 0.0)
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(s) + bias, top_k)
    used = chosen if given is None else jnp.where(given < 0, chosen, given)
    w_gate, w_in, w_out = (lp[n] for n in ("moe_w_gate", "moe_w_in",
                                           "moe_w_out"))
    lo, n = held if held is not None else (0, w_in.shape[0])
    picked = jnp.take_along_axis(s, used, axis=1)                   # [T, k]
    g = routed_scale * picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)

    def expert(out, e_and_weights):     # one expert at a time, masked
        e, w1, w3, w2 = e_and_weights
        g_e = jnp.where(used == lo + e, g, 0.0).sum(axis=-1)        # [T]
        out = out + by_rows(
            lambda rows: rows[1][:, None] * _gated(rows[0], w1, w3, w2, mm),
            (h, g_e))
        return out, (chosen == lo + e).sum()

    out, loads = jax.lax.scan(expert, jnp.zeros_like(h),
                              (jnp.arange(n), w_gate, w_in, w_out))
    if shared and "shared_w_in" in lp:
        out = out + by_rows(
            lambda rows: _gated(rows, lp["shared_w_gate"], lp["shared_w_in"],
                                lp["shared_w_out"], mm), h)
    all_loads = (used[..., None] == jnp.arange(s.shape[1])).sum(axis=(0, 1))
    return out, (chosen, g, loads, all_loads)


class Model:
    """The model as the caller describes it (the module's keywords), and
    its pieces on one sequence: a layer, the joining of the prediction
    block, and the head with its loss.  *block* must divide ``T``.
    *remat* wraps in ``jax.checkpoint`` what a backward pass may compute
    again: a block of attention rows, of an expert's rows, of the
    head's."""

    def __init__(self, *, n_layers: int, n_heads: int, rope_dim: int,
                 rope_theta: float, eps: float, top_k: int, held=None,
                 routed_scale: float = 1.0, mtp_weight: float = 0.3,
                 bias_rate: float = 0.0, rope: bool = True,
                 shared_key: bool = True, latent_norms: bool = True,
                 shared: bool = True, block: int = 512, operand_dtype=None,
                 remat: bool = False):
        self.n_layers, self.block, self.eps = n_layers, block, eps
        self.top_k, self.mtp_weight, self.bias_rate = (top_k, mtp_weight,
                                                       bias_rate)
        self.latent = dict(n_heads=n_heads, rope_dim=rope_dim,
                           theta=rope_theta, eps=eps, rope=rope,
                           shared_key=shared_key, latent_norms=latent_norms)
        self.routed = dict(top_k=top_k, held=held, routed_scale=routed_scale,
                           shared=shared)
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

    def attend(self, lp, x):
        """``x + a W_o``: the attention sublayer with the tensors *lp*
        (:func:`layer_params`) on ``x [T, E]``."""
        q, k, v = latent_qkv(_rmsnorm(x, lp["ln1_scale"], self.eps), lp,
                             mm=self.mm, **self.latent)
        a = _attention(q, k, v, self.block, self.mm, self.keep)
        return x + self.mm("tf,fe->te", a.reshape(x.shape[0], -1), lp["wo"])

    def layer(self, lp, x, given=None):
        """``(x', routing)`` of a whole layer: ``routing`` is
        :func:`routed_layer`'s of an expert layer, None of the dense
        one (told apart by their tensors)."""
        lp = {n: a.astype(jnp.float32) for n, a in lp.items()
              if n not in JOIN}
        x = self.attend(lp, x)
        h = _rmsnorm(x, lp["ln2_scale"], self.eps)
        if "w_router" not in lp:
            return x + self.by_rows(
                lambda rows: _gated(rows, lp["w_gate"], lp["w_in"],
                                    lp["w_out"], self.mm), h), None
        m, routing = routed_layer(h, lp, given=given, mm=self.mm,
                                  by_rows=self.by_rows, **self.routed)
        return x + m, routing

    def join(self, jp, after, x):
        """``u [T, E]``: the prediction block's input from the embeddings
        *after* ``[T, E]`` of each position's next token and the last
        layer's output ``x [T, E]``, with the block's joining tensors
        *jp*."""
        jp = {n: a.astype(jnp.float32) for n, a in jp.items()}
        both = jnp.concatenate([
            _rmsnorm(after, jp["enorm_scale"], self.eps),
            _rmsnorm(x, jp["hnorm_scale"], self.eps)], axis=-1)
        return self.mm("tf,fe->te", both, jp["w_eh"])

    def losses(self, hp, x, targets):
        """Every position's cross-entropy ``[T]`` of a last hidden state
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

    # -- what the pieces are of, on a batch ------------------------------

    def expert_layers(self, params) -> list:
        """The layers with a router, the prediction block's last."""
        return [i for i in range(self.n_layers + 1)
                if f"L{i}.w_router" in params]

    def after_next(self, targets):
        """``(token after next [T], weight [T])`` of one sequence's next
        tokens: the block's targets and 1.0 where there is one."""
        T = targets.shape[0]
        return (jnp.concatenate([targets[1:], targets[:1]]),
                (jnp.arange(T) < T - 1).astype(jnp.float32))

    def moved_bias(self, params, all_loads) -> dict:
        """Each expert layer's selection bias after the rule's move,
        from *all_loads* ``[expert layers, X]`` of the whole batch."""
        out = {}
        for i, loads in zip(self.expert_layers(params), all_loads):
            mean = loads.sum() / loads.shape[0]
            out[f"L{i}.router_bias"] = params[f"L{i}.router_bias"] \
                + self.bias_rate * jnp.sign(mean - loads)
        return out

    def objective(self, params, tokens, targets, given=None):
        """``(L, (L_main, L_mtp, chosen, g, loads, all_loads))`` of the
        whole model on *tokens*, *targets* ``[B, T]`` as ONE function
        (what :func:`gradient_programs` takes a layer at a time)."""
        B, T = tokens.shape
        L = self.n_layers
        rows = {i: r for r, i in enumerate(self.expert_layers(params))}
        head = lambda scale: {"final_scale": params[scale],
                              "unembed": params["unembed"]}
        embed = params["embed"].astype(jnp.float32)
        main = block = 0.0
        routings = []
        for b in range(B):
            x, routings_b = embed[tokens[b]], []
            for i in range(L + 1):
                if i == L:
                    last, x = x, self.join(
                        {n: params[f"L{L}.{n}"] for n in JOIN},
                        embed[targets[b]], x)
                x, routing = self.layer(
                    layer_params(params, i), x,
                    None if given is None or i not in rows
                    else given[rows[i], b])
                if routing is not None:
                    routings_b.append(routing)
            after, has = self.after_next(targets[b])
            main = main + self.losses(head("final_scale"), last,
                                      targets[b]).sum() / (B * T)
            block = block + (self.losses(head(f"L{L}.final_scale"), x, after)
                             * has).sum() / (B * (T - 1))
            routings.append(routings_b)
        chosen, g, loads, all_loads = _stack_routings(routings)
        return main + self.mtp_weight * block, (main, block, chosen, g,
                                                loads, all_loads)


def _stack_routings(routings) -> tuple:
    """``[B][expert layers]`` of ``(chosen, g, loads, all_loads)`` to
    ``(chosen, g [layers, B, T, k], loads [layers, n], all_loads
    [layers, X])``, the loads summed over the batch."""
    by_layer = [[jnp.stack(part) for part in zip(*seq)] for seq in routings]
    chosen, g, loads, all_loads = (jnp.stack(part, axis=1)
                                   for part in zip(*by_layer))
    return chosen, g, loads.sum(axis=1), all_loads.sum(axis=1)


def gradient_programs(**model):
    """``f(params, tokens, targets, given=None) -> ((L_main [], chosen
    [layers, B, T, k] int32, g [layers, B, T, k], loads [layers, n]),
    gradients, {"mtp_loss", "objective", "bias"})`` of the model *model*
    describes (:class:`Model`'s keywords) on *tokens* ``[B, T]`` against
    *targets* ``[B, T]``: the main loss, the experts each token chose in
    each expert layer (the prediction block's last) with their weights,
    the pairs each held expert took, the OBJECTIVE's gradient for every
    parameter (zero for the selection bias), the block's loss, the
    objective, and every selection bias after its move.

    **A layer at a time**, as ``reference_mellum2.gradient_programs``:
    the forward pass keeps every layer's float32 input; the two heads
    give the losses and their gradients for the two last hidden states;
    then the block's layer, its joining, and each layer's own backward
    pass from its kept input, the last layer first.  The most that is
    ever live is one layer's backward pass beside the kept inputs and
    the gradients, whatever the depth."""
    m = Model(remat=True, **model)
    L = m.n_layers

    def precise(f):
        def g(*args):
            with jax.default_matmul_precision("highest"):
                return f(*args)
        return g

    forward = jax.jit(precise(m.layer))
    join = jax.jit(precise(m.join))

    @jax.jit
    @precise
    def backward(lp, x, given, dy):
        _, vjp = jax.vjp(lambda lp, x: m.layer(lp, x, given)[0], lp, x)
        return vjp(dy)

    @jax.jit
    @precise
    def join_backward(jp, after, x, du):
        return jax.vjp(m.join, jp, after, x)[1](du)

    @jax.jit
    @precise
    def head(hp, x, targets, counted, scale):
        """The sum of the *counted* positions' losses, and the gradient
        of *scale* times it."""
        ce, vjp = jax.vjp(lambda hp, x: m.losses(hp, x, targets), hp, x)
        return ((ce * counted).sum(), *vjp(counted * scale))

    def gradients(params, tokens, targets, given=None):
        B, T = tokens.shape
        rows = {i: r for r, i in enumerate(m.expert_layers(params))}
        if given is None:                          # every choice its own
            given = jnp.full((len(rows), B, T, m.top_k), -1, jnp.int32)
        grads = {n: jnp.zeros(a.shape, jnp.float32)
                 for n, a in params.items()}

        def add(prefix, part, rename=None):
            for n, g in part.items():
                n = prefix + (rename or {}).get(n, n)
                grads[n] = grads[n] + g

        def layer_of(i, b):
            """Layer *i*'s tensors and its given choices of sequence *b*
            (a dense layer reads none)."""
            lp = {n: a for n, a in layer_params(params, i).items()
                  if n not in JOIN}
            return lp, (given[rows[i], b] if i in rows
                        else jnp.zeros((T, m.top_k), jnp.int32))

        main = block = 0.0
        routings = []
        embed = params["embed"].astype(jnp.float32)
        jp = {n: params[f"L{L}.{n}"] for n in JOIN}
        for b in range(B):                         # a sequence at a time
            xs, routings_b = [embed[tokens[b]]], []
            for i in range(L + 1):
                if i == L:                         # the block's input
                    xs.append(join(jp, embed[targets[b]], xs[-1]))
                lp, given_b = layer_of(i, b)
                x, routing = forward(lp, xs[-1], given_b)
                xs.append(x)
                if routing is not None:
                    routings_b.append(routing)
            routings.append(routings_b)
            after, has = m.after_next(targets[b])
            # the block: its head, its layer, its joining
            ce, dhead, dx = head(
                {"final_scale": jp["final_scale"],
                 "unembed": params["unembed"]}, xs.pop(), after, has,
                m.mtp_weight / (B * (T - 1)))
            block = block + ce / (B * (T - 1))
            add("", dhead, {"final_scale": f"L{L}.final_scale"})
            lp, given_b = layer_of(L, b)
            dlp, du = backward(lp, xs.pop(), given_b, dx)
            add(f"L{L}.", dlp)
            djp, dafter, dlast = join_backward(jp, embed[targets[b]],
                                               xs[-1], du)
            add(f"L{L}.", {n: g for n, g in djp.items()
                           if n != "final_scale"})
            grads["embed"] = grads["embed"].at[targets[b]].add(dafter)
            # the main head, then the layers, the last first
            ce, dhead, dx = head(
                {n: params[n] for n in ("final_scale", "unembed")},
                xs.pop(), targets[b], jnp.ones((T,)), 1.0 / (B * T))
            main = main + ce / (B * T)
            add("", dhead)
            dx = dx + dlast
            for i in reversed(range(L)):
                lp, given_b = layer_of(i, b)
                dlp, dx = backward(lp, xs.pop(), given_b, dx)
                add(f"L{i}.", dlp)
            grads["embed"] = grads["embed"].at[tokens[b]].add(dx)
        chosen, g, loads, all_loads = _stack_routings(routings)
        return ((main, chosen, g, loads), grads,
                {"mtp_loss": block,
                 "objective": main + m.mtp_weight * block,
                 "bias": m.moved_bias(params, all_loads)})

    return gradients


def reference_gradients(params, tokens, targets, *, given=None, **model):
    """:func:`gradient_programs` made and called once."""
    return gradient_programs(**model)(params, tokens, targets, given)
