"""Token-mixing operators beside the block's fused multi-head attention:
a gated short convolution, the projections of grouped-query attention
with a norm on every head of q and k, and those of latent attention
(MLA).

A model may give each layer its own operator
(``TransformerConfig.layer_ops``); ``transformer._layer_local`` calls
these for what its own body does not do.  Everything here runs INSIDE
the trainer's ``shard_map``: ``h`` is the layer's normed input ``[B,
T_local, E]`` in ``cfg.dtype``, ``lp`` the layer's parameters without
their ``L<i>.`` prefix, already sliced over the ``model`` axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .looplm import apply_rope


def rmsnorm(x, scale, eps):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, the
    mean in float32, the result in ``x``'s type."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def short_conv(h: jax.Array, lp, cfg, data_axis: str) -> jax.Array:
    """The gated short convolution, up to its output projection's sum
    over the ``model`` axis (the caller's):

        b, c, u = split3(h W_in);  v = b * u
        y_t = sum_j w[:, j] * v_{t - (K-1) + j}     (v = 0 before position 0)
        out = (c * y) W_out

    a depthwise causal convolution of ``cfg.conv_taps`` = K taps a
    channel.  Channels are split over the ``model`` axis (``conv_in [E, 3,
    E/n]`` by columns, ``conv_w [E/n, K]``, ``conv_out [E/n, E]`` by
    rows); over the ``data`` axis a shard's first K-1 positions read the
    previous shard's last K-1 of ``v``.  The gates and the taps multiply
    in float32: three shifted multiply-adds that fuse with them."""
    K = cfg.conv_taps
    T = h.shape[1]
    bcu = jnp.einsum("bte,ecf->btcf", h, lp["conv_in"].astype(cfg.dtype))
    b, c, u = (bcu[:, :, j].astype(jnp.float32) for j in range(3))
    v = b * u
    halo = jnp.zeros_like(v[:, :K - 1])
    n_data = jax.lax.psum(1, data_axis)
    if n_data > 1:
        # shard i's tail goes to shard i + 1; shard 0 receives zeros
        halo = jax.lax.ppermute(
            v[:, T - (K - 1):], data_axis,
            [(i, i + 1) for i in range(n_data - 1)])
    vp = jnp.concatenate([halo, v], axis=1)          # [B, K-1+T, E/n]
    w = lp["conv_w"]                                 # [E/n, K] float32
    y = sum(w[:, j] * vp[:, j:j + T] for j in range(K))
    return jnp.einsum("btf,fe->bte", (c * y).astype(cfg.dtype),
                      lp["conv_out"].astype(cfg.dtype))


def grouped_qkv(h: jax.Array, lp, cfg, n_model: int, rope):
    """q, k and v of grouped-query attention, ``H`` query heads over
    ``cfg.kv_heads`` key/value heads (``wq [E, H D]``, ``wkv [E, 2, Hkv
    D]``), each key/value head serving ``H / Hkv`` consecutive query
    heads: with ``cfg.qk_norm`` an RMSNorm with a learned ``[D]`` scale
    over each head of q and of k, then the rotary embedding, then K and V
    repeated to ``H`` heads — the attention that follows (the flash
    kernels, the ring) takes equal head counts, and the repeat's
    transpose sums dK and dV over a group.  In the kernel's ``[B, H, T,
    D]`` layout with ``cfg.flash``, else the ring's ``[B, T, H, D]``."""
    D, E = cfg.head_dim, h.shape[-1]
    H, Hkv = cfg.n_heads // n_model, cfg.kv_heads // n_model
    wq = lp["wq"].astype(cfg.dtype).reshape(E, H, D)
    wkv = lp["wkv"].astype(cfg.dtype).reshape(E, 2, Hkv, D)
    if cfg.flash:
        q = jnp.einsum("bte,ehd->bhtd", h, wq)
        kv = jnp.einsum("bte,echd->cbhtd", h, wkv)
        heads, seq = 1, 2
    else:
        q = jnp.einsum("bte,ehd->bthd", h, wq)
        kv = jnp.einsum("bte,echd->cbthd", h, wkv)
        heads, seq = 2, 1
    k, v = kv[0], kv[1]
    if cfg.qk_norm:
        with jax.named_scope("tf.qk_norm"):
            q = rmsnorm(q, lp["q_norm_scale"].astype(cfg.dtype),
                        cfg.norm_eps)
            k = rmsnorm(k, lp["k_norm_scale"].astype(cfg.dtype),
                        cfg.norm_eps)
    if rope is not None:
        with jax.named_scope("tf.rope"):
            q, k = apply_rope(q, rope, seq), apply_rope(k, rope, seq)
    k, v = (jnp.repeat(a, H // Hkv, axis=heads) for a in (k, v))
    return q, k, v


def latent_qkv(h: jax.Array, lp, cfg, n_model: int, rope):
    """q, k and v of latent attention (MLA), ``H`` heads:

        c_q = rms(h W_qa);  [q_nope | q_rope] = c_q W_qb     (a head:
                                          qk_nope_dim + qk_rope_dim)
        [c_kv | k_r] = h W_kva;  c_kv = rms(c_kv)
        [k_nope | v] = c_kv W_kvb     (a head: qk_nope_dim + v_head_dim)
        q = [q_nope | rope(q_rope)],  k = [k_nope | rope(k_r)]

    ``k_r`` (``qk_rope_dim`` wide) is ONE key for all the heads: rotated
    once, then broadcast to them.  *rope* is ``looplm.rope_tables`` over
    ``qk_rope_dim`` dimensions; the rotary embedding (rotate-half inside
    those) leaves the ``qk_nope_dim`` others as they are.  ``wq_b [Rq, H
    (nope + rope)]`` and ``wkv_b [Rkv, H (nope + v)]`` hold a head's
    columns together and split by heads over the ``model`` axis; the
    down-projections and the latent norms are whole on every rank.  In
    the kernel's ``[B, H, T, D]`` layout with ``cfg.flash``, else the
    ring's ``[B, T, H, D]``; the attention that follows takes q, k of
    ``qk_nope_dim + qk_rope_dim`` and v of ``v_head_dim``, which
    ``cfg.validate`` holds equal."""
    H = cfg.n_heads // n_model
    Rkv, Dn, Dr, Dv = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                       cfg.v_head_dim)
    dt, eps = cfg.dtype, cfg.norm_eps
    with jax.named_scope("tf.mla_down"):
        c_q = rmsnorm(jnp.einsum("bte,er->btr", h, lp["wq_a"].astype(dt)),
                      lp["q_a_norm_scale"].astype(dt), eps)
        kv_a = jnp.einsum("bte,er->btr", h, lp["wkv_a"].astype(dt))
        c_kv = rmsnorm(kv_a[..., :Rkv], lp["kv_a_norm_scale"].astype(dt),
                       eps)
        k_r = kv_a[..., Rkv:]                                # [B, T, Dr]
    out = "bhtd" if cfg.flash else "bthd"
    heads, seq = (1, 2) if cfg.flash else (2, 1)
    with jax.named_scope("tf.mla_up"):
        q = jnp.einsum(f"btr,rhd->{out}", c_q,
                       lp["wq_b"].astype(dt).reshape(-1, H, Dn + Dr))
        kv = jnp.einsum(f"btr,rhd->{out}", c_kv,
                        lp["wkv_b"].astype(dt).reshape(-1, H, Dn + Dv))
        k_r = jnp.expand_dims(k_r, heads)            # one head: all share
    with jax.named_scope("tf.rope"):
        q_r = apply_rope(q[..., Dn:], rope, seq)
        k_r = apply_rope(k_r, rope, seq)
    with jax.named_scope("tf.mla_up"):
        shape = list(q_r.shape)
        q = jnp.concatenate([q[..., :Dn], q_r], axis=-1)
        k = jnp.concatenate([kv[..., :Dn], jnp.broadcast_to(k_r, shape)],
                            axis=-1)
    return q, k, kv[..., Dn:]
