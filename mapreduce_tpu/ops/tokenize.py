"""On-device byte-stream tokenizer + word hasher.

The reference's map hot loop is a Lua ``gmatch("[^%s]+")`` per line with a
table-insert per token (examples/WordCount/mapfn.lua:4-7, job.lua:77-97).
The TPU-native version never materialises tokens: the raw UTF-8 bytes go to
the device as one ``[L] uint8`` array and a data-parallel pass computes,
per byte position,

  * whether a word ends there, and
  * the rolling 64-bit hash (two independent 32-bit polynomial lanes) of
    the word ending there, plus where its bytes start,

using an associative scan over affine maps — the standard trick for
sequential recurrences on parallel hardware: the rolling-hash step
``h_i = a*h_{i-1} + (b_i+1)`` is the affine map ``h -> m*h + c`` with
``(m, c) = (a, b_i+1)`` on word bytes and ``(0, 0)`` on separators (which
also performs the reset).  ``lax.associative_scan`` composes the maps in
O(log L) depth; the composed ``c`` lane at each position IS the hash of
the word-prefix ending there.

Note: FNV-1a itself (utils/hashing.py, the partition-hash parity fn) is
*not* scan-decomposable (xor-then-multiply is non-affine), so the device
path uses polynomial hashing.  Device and host paths agree because the
host twin here (`word_hashes_host`) implements the identical polynomial.

Hash equality stands in for string equality (64 bits: collision odds for a
1M-word vocabulary are ~3e-8); the final strings are materialised on the
host by slicing the original bytes at one representative (start, length)
per unique hash — the "hash on device, dictionary on host" answer to
string keys on a numeric accelerator (SURVEY.md §7 hard part (b)).

Whitespace = ASCII {space, \\t, \\n, \\r, \\f, \\v}, matching Python's
``str.split()`` on ASCII text (the reference's Lua ``%s`` class,
mapfn.lua:4-7); multi-byte UTF-8 sequences are treated as word bytes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_compat

# jax.experimental.pallas is imported lazily inside the kernel/wrapper
# functions: this module rides every engine import, and processes that
# never select tokenize impl='pallas' should not pay the pallas import

#: polynomial multipliers for the two 32-bit hash lanes (odd constants:
#: FNV prime and a Murmur3 finalizer constant)
HASH_A1 = 16777619
HASH_A2 = 0x85EBCA6B
#: third, independent lane used only by collision-verify mode
HASH_A3 = 0xCC9E2D51
WORD_HASH_LANES = 2

_WS = (32, 9, 10, 13, 12, 11)


class TokenStream(NamedTuple):
    """Per-byte-position token info (fixed shape [L])."""

    is_end: jax.Array   # [L] bool — a word's last byte is here
    keys: jax.Array     # [L, 2] uint32 — hash lanes of the word ending here
    start: jax.Array    # [L] int32 — byte offset where that word starts
    length: jax.Array   # [L] int32 — word length in bytes


def _is_space(b: jax.Array) -> jax.Array:
    m = b == _WS[0]
    for w in _WS[1:]:
        m = m | (b == w)
    return m


def _affine_combine(left, right):
    ml, cl = left
    mr, cr = right
    return ml * mr, cl * mr + cr


#: inner tile width for the two-level scans.  A flat scan over millions of
#: elements costs log2(L) full-array passes; scanning [L/W, W] tiles along
#: the short axis + a small cross-tile prefix pass cuts the full-width
#: passes to log2(W) and keeps every intermediate a clean 2-D array.
SCAN_TILE = 512


def _shifted(x: jax.Array, d: int, fill) -> jax.Array:
    """x shifted right by d along its LAST axis, filling with *fill*."""
    pad = jnp.full(x.shape[:-1] + (d,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-d]], axis=-1)


def _hillis_affine(m: jax.Array, c: jax.Array):
    """Inclusive scan of affine maps h->m*h+c along the last axis, as an
    UNROLLED Hillis-Steele ladder of log2(L) static shift+multiply-add
    passes.  jax.lax.associative_scan's recursive odd/even slicing
    compiles pathologically on TPU at multi-million-element widths
    (>10 min at L=4M, measured — the round-1 bench killer); this emits
    only pad/slice/mul/add HLO with static shapes, which XLA compiles in
    seconds and runs at HBM bandwidth."""
    L = m.shape[-1]
    d = 1
    while d < L:
        ml = _shifted(m, d, 1)
        cl = _shifted(c, d, 0)
        # compose right∘left BEFORE overwriting m: (m*ml, m*cl + c)
        m, c = m * ml, m * cl + c
        d *= 2
    return m, c


def _hillis_max(x: jax.Array) -> jax.Array:
    """Inclusive running max along the last axis (same ladder)."""
    L = x.shape[-1]
    lowest = (jnp.iinfo(x.dtype).min
              if jnp.issubdtype(x.dtype, jnp.integer) else -jnp.inf)
    d = 1
    while d < L:
        x = jnp.maximum(x, _shifted(x, d, lowest))
        d *= 2
    return x


def _affine_scan(m: jax.Array, c: jax.Array) -> jax.Array:
    """Inclusive scan of affine maps h->m*h+c; returns the composed c lane
    (== h at each position, with h before the sequence = 0).

    Two-level (tiled) formulation: within-tile inclusive scan vectorized
    over tiles, then an exclusive cross-tile prefix of the tile totals,
    composed back in — ``T_tile_i ∘ T_prefix_b = (Mi*Mp, Cp*Mi + Ci)``.
    """
    L = m.shape[0]
    W = SCAN_TILE
    if L % W != 0 or L <= W:
        _, c_out = _hillis_affine(m, c)
        return c_out
    mb = m.reshape(L // W, W)
    cb = c.reshape(L // W, W)
    Mi, Ci = _hillis_affine(mb, cb)
    # exclusive prefix of per-tile totals (last column), shifted by one
    Mt, Ct = Mi[:, -1], Ci[:, -1]
    Mp, Cp = _hillis_affine(Mt, Ct)
    one = jnp.ones((1,), m.dtype)
    zero = jnp.zeros((1,), c.dtype)
    Mp = jnp.concatenate([one, Mp[:-1]])
    Cp = jnp.concatenate([zero, Cp[:-1]])
    h = Cp[:, None] * Mi + Ci
    return h.reshape(L)


def _cummax_scan(x: jax.Array) -> jax.Array:
    """Tiled inclusive running max (same rationale as _affine_scan)."""
    L = x.shape[0]
    W = SCAN_TILE
    if L % W != 0 or L <= W:
        return _hillis_max(x)
    xb = x.reshape(L // W, W)
    inner = _hillis_max(xb)
    totals = inner[:, -1]
    prefix = _hillis_max(totals)
    lowest = jnp.full((1,), jnp.iinfo(x.dtype).min
                      if jnp.issubdtype(x.dtype, jnp.integer) else -jnp.inf,
                      x.dtype)
    prefix = jnp.concatenate([lowest, prefix[:-1]])
    return jnp.maximum(inner, prefix[:, None]).reshape(L)


# -- the fused Pallas tokenizing map-scan (tokenize_impl='pallas') -----------
#
# tokenize_hash's lax formulation pays, per hash lane, a log2-pass
# Hillis-Steele affine ladder over the full chunk, plus the boundary
# cummax ladder — each pass a full HBM read+write of the chunk-sized
# intermediates.  The kernel fuses byte classify + ALL affine-hash lanes
# + the word-boundary cummax into ONE blocked pass: per [R, 128] VMEM
# tile it composes the affine maps within-tile (two-level: lanes then
# rows) and threads the cross-block state — previous byte's space-ness,
# each hash lane's running value, the running word-start max — through
# kernel scratch across the sequential grid.  uint32 affine composition
# and int32 max are associative in machine arithmetic, so the result is
# BIT-identical to the ladder formulation (the golden suite pins it
# against the host oracle and the lax twin, including non-tile-multiple
# chunk lengths).

#: lane width of the tokenize kernel's 2-D layout
_TOK_LANES = 128
#: default bytes per kernel block (EngineConfig.tokenize_block
#: overrides and fingerprints it)
TOKENIZE_BLOCK = 4096
_INT32_MIN = -(2 ** 31)


def _tokenize_kernel(pids, b_ref, nb_ref, *refs,
                     multipliers: Tuple[int, ...]):
    """One grid step = one [R, _TOK_LANES] block of the byte chunk.
    refs: per-multiplier hash out-refs (int32 bit patterns), then
    end/start/length out-refs (int32), then scratch, each a
    [1, _TOK_LANES] int32 VMEM row: the previous block's last row of
    space-ness, the per-lane running hash ([n_lanes, _TOK_LANES]) and
    the running word-start max (both held in every lane)."""
    from jax.experimental import pallas as pl

    shift_l, shift_r = pallas_compat.shift_lanes, pallas_compat.shift_rows
    last_lane = pallas_compat.last_lane
    n_lanes = len(multipliers)
    h_refs = refs[:n_lanes]
    end_ref, start_ref, len_ref = refs[n_lanes:n_lanes + 3]
    cps_ref, ch_ref, cs_ref = refs[n_lanes + 3:]
    blk = pids[0]

    @pl.when(blk == 0)
    def _init():
        # "the byte before the chunk is a separator": position 0 can
        # start a word
        cps_ref[...] = jnp.ones_like(cps_ref)
        ch_ref[...] = jnp.zeros_like(ch_ref)
        cs_ref[...] = jnp.full_like(cs_ref, _INT32_MIN)

    b32 = b_ref[...].astype(jnp.int32)      # [R, L]
    R, L = b32.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, L), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, L), 0)
    space = _is_space(b32)
    word = jnp.logical_not(space)
    is_end = word & _is_space(nb_ref[...].astype(jnp.int32))
    sp32 = space.astype(jnp.int32)
    is_start = word & (pallas_compat.shift1_flat(
        sp32, cps_ref[...], lane, row) > 0)

    def affine(m, c, shift, idx, span):
        """Inclusive scan of the maps h -> m*h + c along one block axis
        (the lax path's _hillis_affine with roll shifts; int32
        arithmetic wraps to the same bits as uint32)."""
        d = 1
        while d < span:
            m, c = m * shift(m, d, 1, idx), m * shift(c, d, 0, idx) + c
            d *= 2
        return m, c

    for i, a in enumerate(multipliers):
        a32 = int(np.uint32(a).astype(np.int32))
        mw, cw = affine(jnp.where(word, a32, 0),
                        jnp.where(word, b32 + 1, 0), shift_l, lane, L)
        mi, ci = affine(last_lane(mw, lane), last_lane(cw, lane),
                        shift_r, row, R)
        hc = jnp.broadcast_to(ch_ref[i:i + 1], (R, L))
        comb = hc * mi + ci             # carry ∘ rows 0..r, value lane
        h_refs[i][...] = shift_r(comb, 1, hc, row) * mw + cw
        ch_ref[i:i + 1] = comb[R - 1:R]

    cummax = functools.partial(pallas_compat.ladder_scan, op=jnp.maximum,
                               identity=_INT32_MIN)
    pos = blk * (R * L) + row * L + lane
    mw = cummax(jnp.where(is_start, pos, -1), shift=shift_l, idx=lane,
                span=L)
    cmax = jnp.broadcast_to(cs_ref[...], (R, L))
    rinc = jnp.maximum(cmax, cummax(last_lane(mw, lane), shift=shift_r,
                                    idx=row, span=R))
    start = jnp.maximum(mw, shift_r(rinc, 1, cmax, row))
    start_ref[...] = start
    len_ref[...] = pos - start + 1
    end_ref[...] = is_end.astype(jnp.int32)
    cps_ref[...] = sp32[R - 1:R]
    cs_ref[...] = rinc[R - 1:R]


def _tokenize_pallas(chunk: jax.Array, multipliers: Tuple[int, ...],
                     block: int, interpret: Optional[bool]) -> TokenStream:
    """The fused kernel path behind :func:`tokenize_hash`
    (``impl='pallas'``) — identical TokenStream, one blocked pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N = chunk.shape[0]
    L = _TOK_LANES
    R = pallas_compat.block_rows(block, L, 32, interpret)  # uint8 tiles
    block = R * L
    npad = -(-N // block) * block
    pad = npad - N
    cp = (jnp.concatenate([chunk, jnp.full((pad,), ord(" "), jnp.uint8)])
          if pad else chunk)
    # next byte, space-filled at the end (matching the lax path's
    # next_space=True closure of the final word)
    nb = jnp.concatenate([cp[1:], jnp.full((1,), ord(" "), jnp.uint8)])
    rows = npad // L
    shape2 = (rows, L)
    spec = pl.BlockSpec((R, L), lambda i: (i, 0))
    n_lanes = len(multipliers)
    outs = pallas_compat.pallas_call(
        functools.partial(_tokenize_kernel,
                          multipliers=tuple(int(a) for a in multipliers)),
        name="tokenize",
        interpret=interpret,
        grid=(npad // block,),
        in_specs=[spec, spec],
        out_specs=[spec] * (n_lanes + 3),
        out_shape=[pallas_compat.sds(shape2, jnp.int32, chunk)]
        * (n_lanes + 3),
        scratch_shapes=[pltpu.VMEM((1, L), jnp.int32),
                        pltpu.VMEM((n_lanes, L), jnp.int32),
                        pltpu.VMEM((1, L), jnp.int32)],
    )(cp.reshape(shape2), nb.reshape(shape2))
    keys = jnp.stack(
        [jax.lax.bitcast_convert_type(o, jnp.uint32).reshape(-1)[:N]
         for o in outs[:n_lanes]], axis=-1)
    end, start, length = (o.reshape(-1)[:N] for o in outs[n_lanes:])
    return TokenStream(is_end=end.astype(bool), keys=keys,
                       start=start, length=length)


def tokenize_hash(chunk: jax.Array,
                  multipliers=(HASH_A1, HASH_A2),
                  impl: str = "lax",
                  block: int = TOKENIZE_BLOCK,
                  interpret: Optional[bool] = None) -> TokenStream:
    """Tokenize one padded byte chunk ``[L] uint8`` entirely on-device.

    *multipliers* selects the polynomial hash lanes (one affine scan
    each); collision-verify mode passes a third lane.  ``impl`` picks
    the formulation: ``"lax"`` (the tiled Hillis-Steele ladders below)
    or ``"pallas"`` (ONE fused blocked kernel pass — classify + all
    hash lanes + boundary cummax together; bit-identical, pinned by the
    golden suite).  *block*/*interpret* configure the kernel only."""
    if impl not in ("lax", "pallas"):
        raise ValueError(f"tokenize impl must be 'lax' or 'pallas', "
                         f"got {impl!r}")
    if impl == "pallas":
        return _tokenize_pallas(chunk, tuple(multipliers), block,
                                interpret)
    L = chunk.shape[0]
    b32 = chunk.astype(jnp.uint32)
    space = _is_space(chunk)
    word = ~space

    # word ends: word byte whose successor is a separator (or the chunk end)
    next_space = jnp.concatenate([space[1:], jnp.ones((1,), bool)])
    is_end = word & next_space
    # word starts: word byte whose predecessor is a separator (or position 0)
    prev_space = jnp.concatenate([jnp.ones((1,), bool), space[:-1]])
    is_start = word & prev_space

    # independent polynomial hash lanes via one affine scan each
    keys = []
    for a in multipliers:
        m = jnp.where(word, jnp.uint32(a), jnp.uint32(0))
        c = jnp.where(word, b32 + jnp.uint32(1), jnp.uint32(0))
        keys.append(_affine_scan(m, c))
    keys = jnp.stack(keys, axis=-1)

    # start offset: running max of (position where a word starts, else -1),
    # reset implicitly because separators never read it
    pos = jnp.arange(L, dtype=jnp.int32)
    start_marks = jnp.where(is_start, pos, jnp.int32(-1))
    start = _cummax_scan(start_marks)
    length = pos - start + 1
    return TokenStream(is_end=is_end, keys=keys, start=start, length=length)


# --- host twin (oracle + final key materialisation) ------------------------

def word_hashes_host(text: bytes) -> dict:
    """Pure-Python twin of :func:`tokenize_hash`: {word_bytes: (h1, h2)}.
    Used by tests as the oracle and available for host-side fallback."""
    out = {}
    for w in text.split():
        h1 = h2 = 0
        for byte in w:
            h1 = (h1 * HASH_A1 + byte + 1) & 0xFFFFFFFF
            h2 = (h2 * HASH_A2 + byte + 1) & 0xFFFFFFFF
        out[w] = (h1, h2)
    return out


def shard_text(data: bytes, num_shards: int,
               pad_multiple: int = 128, return_offsets: bool = False,
               pad_to: int = None):
    """Host prep: split a text blob into ``num_shards`` roughly equal byte
    chunks on whitespace boundaries, space-padded to one common static
    length (multiple of *pad_multiple* for TPU lane alignment).

    ``pad_to`` fixes the padded length L to a caller-chosen value
    (still rounded to *pad_multiple*; raised if a span genuinely
    exceeds it): callers that compile shape-specialised programs pass a
    corpus-INDEPENDENT target so every corpus hits one compiled program
    / one persistent-cache entry, instead of a data-dependent max-span
    length that recompiles per corpus size.

    Returns ``(chunks [S, L] uint8, L)`` — or, with *return_offsets*,
    ``(chunks, L, starts [S] int64)`` where ``starts[i]`` is chunk *i*'s
    byte offset in *data* (so a padded-space offset ``c*L + j`` maps back
    to original offset ``starts[c] + j``).  Splitting only at whitespace
    keeps every word intact inside exactly one shard — the same invariant
    the reference gets from line-aligned input splits (README.md:43-45).
    """
    n = len(data)
    flat = np.frombuffer(data, dtype=np.uint8)  # zero-copy
    bounds = [0]
    for s in range(1, num_shards):
        cut = min(n, s * n // num_shards)
        while cut < n and data[cut:cut + 1] not in (b" ", b"\t", b"\n",
                                                    b"\r", b"\x0b", b"\x0c"):
            cut += 1
        bounds.append(cut)
    bounds.append(n)
    L = max(1, max(bounds[i + 1] - bounds[i] for i in range(num_shards)))
    if pad_to is not None:
        L = max(L, pad_to)
    L = ((L + pad_multiple - 1) // pad_multiple) * pad_multiple
    arr = np.full((num_shards, L), ord(" "), dtype=np.uint8)
    for i in range(num_shards):
        lo, hi = bounds[i], bounds[i + 1]
        arr[i, :hi - lo] = flat[lo:hi]  # single memcpy per shard
    if return_offsets:
        return arr, L, np.asarray(bounds[:-1], dtype=np.int64)
    return arr, L
