"""In-tree Pallas flash attention: the transformer's single-chip hot op.

Why a hand-written kernel (the first Pallas use in this repo): the
unchunked jnp attention materialises the [B, H, T, T] f32 score tensor
in HBM — at B4 H16 T2048 that is 1.07GB *per layer*, re-read across
softmax passes; the lax.scan + jax.checkpoint flash tiling
(parallel/ring.py block path) keeps memory bounded but pays scan
overhead + full recompute.  A Pallas kernel holds each score tile in
VMEM, never touching HBM with scores at all.  (The three formulations'
rates are not measured on current hardware.)

Kernel layout is ``[B, H, T, D]`` (Mosaic tiling wants the sequence and
head_dim in the last two block dims); the wrapper accepts the model's
native ``[B, T, H, D]`` too and transposes, but the transformer feeds
the kernel layout directly so no transpose is ever materialised.  The
grid is ``(B, H, T/block_q, T/block_kv)`` — KV innermost, so the
(m, den, acc) online-softmax state for one Q tile lives in VMEM scratch
across KV steps while Pallas double-buffers the KV tile DMAs against the
MXU.  Causal Q tiles skip above-diagonal KV tiles entirely — the index
map redirects the skipped DMA to the next tile that will be needed (the
shipped-kernel trick), so neither FLOPs nor bytes are wasted.  Score
memory is O(block_q x block_kv) whatever T is, so the same kernel serves
the 2048-token bench and the 32K long-context config.

Backward is the standard two-pass flash recomputation (dQ pass over KV
tiles, dKV pass over Q tiles) wired through ``jax.custom_vjp`` with
(q, k, v, out, lse) residuals — activation memory O(B T H D), never
O(T²).  lse/delta ride as ``[B, H, T, 1]`` so their tiles obey lane
tiling without 128x replication.

The reference has no analogue (its only notion of long inputs is
streaming file iterators, utils.lua:133-200); this is the beyond-parity
long-context family's hot op (SURVEY.md §7 "pallas kernels for the hot
ops").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_compat import default_interpret, pallas_call, pick_block, sds

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/max NaN-free

#: shared plumbing lives in ops/pallas_compat (ONE spelling of the
#: CPU-fallback policy across every kernel module); the old private
#: names stay as aliases for in-tree callers of the kernel internals
_pick_block = pick_block
_sds = sds


def _on_diag(iq, j, block_q, block_kv):
    """Does KV tile j intersect or precede Q tile iq's causal row range?"""
    return j * block_kv <= iq * block_q + block_q - 1


# -- forward -----------------------------------------------------------------


def _crosses_diag(iq, j, block_q, block_kv):
    """Does KV tile j contain any masked (above-diagonal) element for Q
    tile iq?  False for tiles strictly below the diagonal — those run
    the unmasked body, skipping the iota/compare/select VPU passes that
    dominate a VPU-bound kernel (the MXU work per tile is ~4us; 31/32 of
    a 32K causal grid's needed tiles never cross the diagonal)."""
    return j * block_kv + block_kv - 1 > iq * block_q


def _dispatch_tile(accum, needed, causal, iq, j, block_q, block_kv):
    """Run *accum(mask)* under the masked/full split all three kernels
    share: diagonal-crossing tiles take the masked body, strictly-below
    tiles the unmasked one, non-causal always unmasked."""
    if not causal:
        accum(False)
        return
    diag = _crosses_diag(iq, j, block_q, block_kv)

    @pl.when(needed & diag)
    def _tile_masked():
        accum(True)

    @pl.when(needed & jnp.logical_not(diag))
    def _tile_full():
        accum(False)


def _fwd_kernel(pids, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, den_scr, acc_scr,
                *, causal, block_q, block_kv, n_kv):
    # q arrives PRE-SCALED by 1/sqrt(D) (see _fwd_call): one elementwise
    # pass over [B,H,T,D] outside replaces a [block_q,block_kv] scale
    # pass in every tile
    iq, j = pids[2:]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = j * block_kv
    needed = _on_diag(iq, j, block_q, block_kv) if causal else True

    def _accum(mask):
        q = q_ref[0, 0]  # [block_q, D]
        k = k_ref[0, 0]  # [block_kv, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if mask:
            qp = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kp = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kp <= qp, s, NEG_INF)
        m_prev = m_scr[:, 0:1]                      # [block_q, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                      # masked cols -> 0
        corr = jnp.exp(m_prev - m_new)
        den = den_scr[:, 0:1] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [block_q, D]
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[:, 0:1] = m_new
        den_scr[:, 0:1] = den

    _dispatch_tile(_accum, needed, causal, iq, j, block_q, block_kv)

    # emit once, on the final KV step (the j-loop keeps (m, den, acc) in
    # VMEM scratch; dividing every step cost a [block_q, D] divide + log
    # per tile for values that never left VMEM)
    @pl.when(j == n_kv - 1)
    def _emit():
        den = jnp.maximum(den_scr[:, 0:1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / den).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, 0:1] + jnp.log(den)


# -- backward: dQ pass -------------------------------------------------------


def _dq_kernel(pids, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, block_q, block_kv, n_kv):
    # q is pre-scaled (q^ = q/sqrt(D)); the kernel accumulates dq^ = ds.k
    # and the one final emission multiplies by scale (chain rule through
    # q^ = scale*q), replacing a per-tile [block_q, D] scale pass
    iq, j = pids[2:]

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = iq * block_q
    k_start = j * block_kv
    needed = _on_diag(iq, j, block_q, block_kv) if causal else True

    def _accum(mask):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]             # [block_q, 1]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if mask:
            qp = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kp = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kp <= qp, s, NEG_INF)
        p = jnp.exp(s - lse)            # recomputed softmax tile
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)           # [block_q, block_kv] f32
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_tile(_accum, needed, causal, iq, j, block_q, block_kv)

    @pl.when(j == n_kv - 1)
    def _emit():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


# -- backward: dK/dV pass ----------------------------------------------------


def _dkv_kernel(pids, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, causal, block_q, block_kv, n_q):
    # q is pre-scaled, so dK = dS^T . q^ needs NO scale factor at all
    # (dk = dS^T . scale*q exactly)
    jk, i = pids[2:]

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = i * block_q
    k_start = jk * block_kv
    needed = (q_start + block_q - 1 >= k_start) if causal else True

    def _accum(mask):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if mask:
            qp = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kp = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kp <= qp, s, NEG_INF)
        p = jnp.exp(s - lse)            # [block_q, block_kv]
        # dV += P^T . dO   (contract over the q axis)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # dK += dS^T . Q^
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_tile(_accum, needed, causal, i, jk, block_q, block_kv)

    @pl.when(i == n_q - 1)
    def _emit():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# -- pallas_call wrappers ----------------------------------------------------


def _q_index(b, h, i, j):
    return (b, h, i, 0)


def _make_kv_index(causal, block_q, block_kv, n_kv):
    def kv_index(b, h, i, j):
        if not causal:
            return (b, h, j, 0)
        # skipped (above-diagonal) tiles redirect their DMA to tile 0 —
        # the first tile the NEXT Q block will need — so no bytes stream
        # for tiles the kernel won't touch
        return (b, h, jax.lax.select(
            _on_diag(i, j, block_q, block_kv), j, 0), 0)
    return kv_index


def _fwd_call(q, k, v, cfgt):
    causal, scale, block_q, block_kv, interpret = cfgt
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    n_q, n_kv = Tq // block_q, Tk // block_kv
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)  # q^ = q/sqrt(D)
    kv_index = _make_kv_index(causal, block_q, block_kv, n_kv)
    q_spec = pl.BlockSpec((1, 1, block_q, D), _q_index)
    kv_spec = pl.BlockSpec((1, 1, block_kv, D), kv_index)
    row_spec = pl.BlockSpec((1, 1, block_q, 1), _q_index)
    kernel = functools.partial(
        _fwd_kernel, causal=causal,
        block_q=block_q, block_kv=block_kv, n_kv=n_kv)
    out, lse = pallas_call(
        kernel,
        name="flash_fwd",
        grid=(B, H, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[_sds(q.shape, q.dtype, q),
                   _sds((B, H, Tq, 1), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v)
    return out, lse


def _bwd_call(q, k, v, out, lse, do, cfgt, dlse=None):
    causal, scale, block_q, block_kv, interpret = cfgt
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    n_q, n_kv = Tq // block_q, Tk // block_kv
    # the kernels recompute s from the PRE-SCALED q^ (matching _fwd_call's
    # lse); dq picks scale back up at emission, dk needs none (dk=dS^T.q^)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    # delta[b,h,t] = sum_d dO * O — a tiny elementwise pass, jnp is fine
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B, H, Tq, 1]
    if dlse is not None:
        # lse cotangent: ds += p * dlse == running the same kernels with
        # delta - dlse (see _flash_lse_bwd)
        delta = delta - dlse.astype(jnp.float32)

    kv_index = _make_kv_index(causal, block_q, block_kv, n_kv)
    q_spec = pl.BlockSpec((1, 1, block_q, D), _q_index)
    kv_spec = pl.BlockSpec((1, 1, block_kv, D), kv_index)
    row_spec = pl.BlockSpec((1, 1, block_q, 1), _q_index)

    dq = pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, n_kv=n_kv),
        name="flash_dq",
        grid=(B, H, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=_sds(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dKV grid: KV tiles outer, Q tiles inner; causal skips tiles fully
    # BELOW the needed range, redirecting to the last Q tile (always
    # needed: it is on/after every diagonal)
    def q_index2(b, h, j, i):
        if not causal:
            return (b, h, i, 0)
        return (b, h, jax.lax.select(
            i * block_q + block_q - 1 >= j * block_kv, i, n_q - 1), 0)

    def kv_index2(b, h, j, i):
        return (b, h, j, 0)

    q_spec2 = pl.BlockSpec((1, 1, block_q, D), q_index2)
    kv_spec2 = pl.BlockSpec((1, 1, block_kv, D), kv_index2)
    row_spec2 = pl.BlockSpec((1, 1, block_q, 1), q_index2)
    dk, dv = pallas_call(
        functools.partial(_dkv_kernel, causal=causal,
                          block_q=block_q, block_kv=block_kv, n_q=n_q),
        name="flash_dkv",
        grid=(B, H, n_kv, n_q),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                  row_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[_sds(k.shape, k.dtype, k),
                   _sds(v.shape, v.dtype, v)],
        scratch_shapes=[pltpu.VMEM((block_kv, D), jnp.float32),
                        pltpu.VMEM((block_kv, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_lse(q, k, v, cfgt):
    return _fwd_call(q, k, v, cfgt)


def _flash_lse_fwd(q, k, v, cfgt):
    out, lse = _fwd_call(q, k, v, cfgt)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(cfgt, res, cots):
    """Backward with BOTH cotangents: the lse cotangent folds into the
    delta term — d(lse)/ds is the softmax row p, so ds picks up p*dlse,
    i.e. the kernels run unchanged with delta' = delta - dlse.  (dv has
    no lse term: lse is independent of V.)  flash_attention discards
    lse, so its dlse arrives as zeros and the fold is a no-op there."""
    q, k, v, out, lse = res
    do, dlse = cots
    return _bwd_call(q, k, v, out, lse, do, cfgt, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _make_cfgt(q, k, causal, scale, block_q, block_kv, interpret):
    D = q.shape[3]
    if scale is None:
        scale = D ** -0.5
    block_q = pick_block(q.shape[2], block_q)
    block_kv = pick_block(k.shape[2], block_kv)
    return (bool(causal), float(scale), int(block_q), int(block_kv),
            default_interpret(interpret))


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = 1024, block_kv: int = 1024,
                        interpret: Optional[bool] = None):
    """Kernel-layout (``[B, H, T, D]``) attention returning
    ``(out, lse [B, H, T, 1] f32)`` — the partial-softmax form ring
    attention needs to combine per-ring-step results across devices
    (parallel/ring.py); fully differentiable including through uses of
    lse.  Same tiling/auto-shrink rules as :func:`flash_attention`."""
    cfgt = _make_cfgt(q, k, causal, scale, block_q, block_kv, interpret)
    return _flash_lse(q, k, v, cfgt)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 1024, block_kv: int = 1024,
                    layout: str = "bhtd",
                    interpret: Optional[bool] = None) -> jax.Array:
    """Tiled attention, differentiable; O(block²) score memory.

    ``layout="bhtd"`` (kernel-native) or ``"bthd"`` (the ring path's
    convention; transposed in and out).  ``interpret=None`` auto-selects
    the Pallas interpreter off-TPU (the CPU test mesh) and the compiled
    Mosaic kernel on TPU.  Block sizes shrink to T when T is smaller;
    T must divide by the (shrunk) blocks.
    """
    if layout == "bthd":
        q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    elif layout != "bhtd":
        raise ValueError(f"unknown layout {layout!r}")
    cfgt = _make_cfgt(q, k, causal, scale, block_q, block_kv, interpret)
    out, _ = _flash_lse(q, k, v, cfgt)
    if layout == "bthd":
        out = jnp.swapaxes(out, 1, 2)
    return out
