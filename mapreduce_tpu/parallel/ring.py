"""Ring attention: sequence/context parallelism over the mesh ring.

The reference has NO long-context machinery (SURVEY.md §5 "Long-context /
sequence parallelism: absent") — its only notion of length is streaming
file splits.  A TPU-native framework must scale sequence length across
devices (brief requirement), and the idiomatic construct is ring
attention: shard the sequence over the ``data`` axis, keep Q resident,
and rotate K/V blocks around the ICI ring with ``lax.ppermute`` while
accumulating attention in the numerically-stable online-softmax form
(flash-attention accumulation).  Peak memory per device is O(T_local²)
instead of O(T_global²), and the K/V transfer overlaps compute around the
ring.

Layout: inputs are the LOCAL sequence block ``[batch, t_local, heads,
head_dim]`` inside ``shard_map`` over *axis_name*; the global sequence is
the concatenation over mesh positions, in axis order.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _block_attn(q, k, v, mask, scale):
    """One (Q-block, KV-block) partial attention in online-softmax form.

    Returns ``(block_max [B,H,Tq], exp-weights sum [B,H,Tq],
    weighted V [B,Tq,H,D])`` — un-normalised pieces for the accumulator.

    Mixed precision: the two matmuls run in the INPUT dtype (bf16 keeps
    them on the MXU fast path) with f32 accumulation
    (preferred_element_type); softmax statistics are always f32.
    """
    # [B, H, Tq, Tk] — f32 accumulation regardless of operand dtype
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, -jnp.inf)
    m = scores.max(axis=-1)  # [B, H, Tq]
    # guard fully-masked rows (all -inf): exp(-inf - -inf) would be NaN
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(mask, p, 0.0)
    den = p.sum(axis=-1)  # [B, H, Tq]
    num = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return safe_m, den, num


def _combine(m, den, num, bm, bden, bnum):
    """Fold one partial-attention block into the online-softmax
    accumulator (associative, so ring steps and local chunks share it)."""
    new_m = jnp.maximum(m, bm)
    corr_old = jnp.exp(m - new_m)
    corr_new = jnp.exp(bm - new_m)
    den = den * corr_old + bden * corr_new
    # broadcast the [B,H,T] corrections over the [B,T,H,D] accumulator
    num = (num * jnp.moveaxis(corr_old, 1, 2)[..., None]
           + bnum * jnp.moveaxis(corr_new, 1, 2)[..., None])
    return new_m, den, num


def _ring_attention_flash(q, k, v, axis_name, causal, scale,
                          interpret=None):
    """Ring attention with the Pallas kernel as each step's local
    compute (ops/flash_attention.py).  A ring step sees KV from rank
    ``src = rank - s``: blocks BEFORE mine are fully unmasked (plain
    attention), my own block is standard causal, blocks AFTER mine are
    fully masked — so the three cases dispatch to the existing kernel
    via ``lax.cond`` (causal=False / causal=True / skip) and no
    offset-masking kernel variant is needed.  Per-step partials combine
    in (out, lse) log-sum-exp form; the kernel's custom vjp carries the
    lse cotangent, so the whole ring differentiates.

    Layout: converts to the kernel's [B, H, T, D] at the boundary and
    rotates K/V in that layout (same bytes over ICI)."""
    from ..ops.flash_attention import NEG_INF, flash_attention_lse

    P = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    qk = jnp.swapaxes(q, 1, 2)  # [B, H, T, D]
    kk = jnp.swapaxes(k, 1, 2)
    vk = jnp.swapaxes(v, 1, 2)
    B, H, T, D = qk.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    def attend(kv_causal, k_blk, v_blk):
        o, l = flash_attention_lse(qk, k_blk, v_blk, causal=kv_causal,
                                   scale=scale, interpret=interpret)
        return o.astype(jnp.float32), l

    def step(carry, s):
        k_blk, v_blk, out, lse = carry
        src = (rank - s) % P
        if causal:
            o_s, l_s = jax.lax.cond(
                src == rank,
                lambda: attend(True, k_blk, v_blk),
                lambda: jax.lax.cond(
                    src < rank,
                    lambda: attend(False, k_blk, v_blk),
                    # fully-masked step: contributes nothing
                    lambda: (jnp.zeros_like(out),
                             jnp.full_like(lse, NEG_INF))))
        else:
            o_s, l_s = attend(False, k_blk, v_blk)
        new_lse = jnp.logaddexp(lse, l_s)
        w_old = jnp.exp(lse - new_lse)
        w_new = jnp.exp(l_s - new_lse)
        out = out * w_old + o_s * w_new
        perm = [(i, (i + 1) % P) for i in range(P)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, out, new_lse), None

    # accumulators derived from q inherit its vma (same trick as the jnp
    # path); lse at NEG_INF with out zeros combines to zeros, no NaN
    out0 = qk.astype(jnp.float32) * 0.0
    lse0 = (qk[..., :1].astype(jnp.float32) * 0.0) + NEG_INF
    (k_f, v_f, out, lse), _ = jax.lax.scan(
        step, (kk, vk, out0, lse0), jnp.arange(P))
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = True,
                   scale: Optional[float] = None,
                   block_size: Optional[int] = None,
                   use_flash: Optional[bool] = None,
                   window: Optional[int] = None) -> jax.Array:
    """Exact multi-head attention over a sequence sharded on *axis_name*.

    ``q/k/v``: [B, T_local, H, D] local blocks (must run inside
    ``shard_map``).  Returns [B, T_local, H, D].

    ``block_size`` additionally chunks each ring step's LOCAL attention
    (flash-attention style) over BOTH the query and key/value axes:
    scores materialise as [B, H, block, block] instead of
    [B, H, T_local, T_local], with each tile rematerialised in the
    backward pass — O(block²) attention memory regardless of T_local,
    the single-device half of the long-context story (the ring supplies
    the cross-device half).  Must divide T_local; None = one chunk
    (exact same math either way: the online-softmax combine is
    associative).

    ``use_flash`` (None = auto: on TPU) runs each ring step's local
    attention through the Pallas kernel instead of the jnp path — the
    kernel already tiles, so ``block_size`` is ignored there.

    ``window=W`` (with ``causal``) is sliding-window attention over
    GLOBAL positions, ``0 <= q - k < W``: the jnp path masks it as it
    masks the diagonal, on any number of shards.  The kernel ring
    refuses it: a ring step's block lies ``s`` shards back and the
    kernel's window counts from position 0 of what it is handed.
    """
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"window={window!r} needs causal=True and a "
                         "width of at least 1")
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash:
        if window is not None:
            raise ValueError(
                "a sliding window over a sequence sharded on the ring has "
                "no kernel path: run it unsharded (the flash kernels' "
                "window) or with use_flash=False")
        return _ring_attention_flash(q, k, v, axis_name, causal, scale)
    P = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    block = block_size or T
    if T % block != 0:
        raise ValueError(f"block_size {block} must divide T_local {T}")
    C = T // block

    q_pos = rank * T + jnp.arange(T)  # global positions of my queries

    def tile_step(carry, xs, q_c, qp_c):
        """Fold one KV tile into one Q chunk's accumulator."""
        m_c, den_c, num_c = carry
        kb, vb, kp = xs  # [B, block, H, D] x2, [block]
        if causal:
            mask = kp[None, :] <= qp_c[:, None]  # [Tq_c, Tk_c]
            if window is not None:
                mask &= qp_c[:, None] - kp[None, :] < window
        else:
            mask = jnp.ones((qp_c.shape[0], kp.shape[0]), bool)
        bm, bden, bnum = _block_attn(q_c, kb, vb, mask[None, None], scale)
        return _combine(m_c, den_c, num_c, bm, bden, bnum), None

    def step(carry, s):
        k_blk, v_blk, m, den, num = carry
        # the block currently held arrived from rank - s (ring order)
        src = (rank - s) % P
        kv_pos = src * T + jnp.arange(T)
        if C == 1:
            (m, den, num), _ = tile_step((m, den, num),
                                         (k_blk, v_blk, kv_pos),
                                         q, q_pos)
        else:
            # flash tiling: outer scan over Q chunks (each with its own
            # accumulator slice), inner scan over KV tiles; each tile
            # recomputed in the backward pass (jax.checkpoint) so only
            # one [B, H, block, block] score tile ever exists
            kc = jnp.moveaxis(k_blk.reshape(B, C, block, H, D), 1, 0)
            vc = jnp.moveaxis(v_blk.reshape(B, C, block, H, D), 1, 0)
            kp_c = kv_pos.reshape(C, block)

            def q_step(_, xs):
                q_c, qp_c, m_c, den_c, num_c = xs
                inner = jax.checkpoint(
                    lambda cry, ys: tile_step(cry, ys, q_c, qp_c))
                (m_c, den_c, num_c), _ = jax.lax.scan(
                    inner, (m_c, den_c, num_c), (kc, vc, kp_c))
                return None, (m_c, den_c, num_c)

            qc = jnp.moveaxis(q.reshape(B, C, block, H, D), 1, 0)
            qp = q_pos.reshape(C, block)
            mc = jnp.moveaxis(m.reshape(B, H, C, block), 2, 0)
            denc = jnp.moveaxis(den.reshape(B, H, C, block), 2, 0)
            numc = jnp.moveaxis(num.reshape(B, C, block, H, D), 1, 0)
            _, (mc, denc, numc) = jax.lax.scan(
                q_step, None, (qc, qp, mc, denc, numc))
            m = jnp.moveaxis(mc, 0, 2).reshape(B, H, T)
            den = jnp.moveaxis(denc, 0, 2).reshape(B, H, T)
            num = jnp.moveaxis(numc, 0, 1).reshape(B, T, H, D)
        # rotate K/V to the next device; after P-1 rotations every device
        # has seen every block
        perm = [(i, (i + 1) % P) for i in range(P)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m, den, num), None

    # the scan carry must enter with the same device-varying type the body
    # produces; deriving the zero accumulators from q inherits q's vma
    # regardless of how many mesh axes enclose us (sp alone, or sp x tp).
    # Accumulators are f32 even for bf16 inputs (online-softmax stats and
    # the weighted-V sum must not round per ring step).
    stat0 = jnp.moveaxis(q[..., 0].astype(jnp.float32) * 0.0, 1, 2)
    m0 = stat0 - jnp.inf      # [B, H, T]
    den0 = stat0
    num0 = q.astype(jnp.float32) * 0.0
    (k_f, v_f, m, den, num), _ = jax.lax.scan(
        step, (k, v, m0, den0, num0), jnp.arange(P))

    den = jnp.moveaxis(den, 1, 2)[..., None]  # [B, T, H, 1]
    return (num / jnp.maximum(den, 1e-20)).astype(q.dtype)


def full_attention_reference(q, k, v, causal: bool = True,
                             scale: Optional[float] = None) -> jax.Array:
    """Unsharded oracle: plain softmax attention over the GLOBAL sequence
    ([B, T, H, D]); tests diff ring_attention against this."""
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)
