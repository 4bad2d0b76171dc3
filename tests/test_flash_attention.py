"""Pallas flash attention vs the unsharded oracle (interpret mode on the
CPU test mesh; the compiled Mosaic path is what bench_train measures on
hardware, and what the MAPREDUCE_TPU_TESTS=1 cases below check there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mapreduce_tpu.models.transformer import (TransformerConfig,
                                              TransformerTrainer)
from mapreduce_tpu.ops import flash_attention as fa
from mapreduce_tpu.ops.flash_attention import (flash_attention,
                                               flash_attention_lse)
from mapreduce_tpu.parallel import make_mesh
from mapreduce_tpu.parallel.ring import full_attention_reference
from tests.kernel_calls import eqns, kernel_calls


def _qkv(B=2, T=256, H=3, D=16, dtype=jnp.float32):
    return tuple(
        jax.random.normal(jax.random.key(i), (B, T, H, D), dtype)
        for i in range(3))


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_oracle(causal):
    q, k, v = _qkv()
    # full f32 dots: the CPU backend's DEFAULT matmul precision is
    # bf16-grade (measured 6e-2 on a plain f32 dot), which would swamp
    # the comparison
    with jax.default_matmul_precision("float32"):
        out = flash_attention(q, k, v, causal=causal, layout="bthd",
                              block_q=128, block_kv=64)
        ref = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_oracle(causal):
    q, k, v = _qkv()

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       layout="bthd", block_q=128,
                                       block_kv=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention_reference(q, k, v,
                                                causal=causal) ** 2)

    with jax.default_matmul_precision("float32"):
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_kernel_native_layout():
    q, k, v = _qkv()
    with jax.default_matmul_precision("float32"):
        a = flash_attention(q, k, v, layout="bthd", block_q=64,
                            block_kv=64)
        b = flash_attention(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)),
                            layout="bhtd", block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(a),
                               np.asarray(jnp.swapaxes(b, 1, 2)),
                               atol=1e-6)


def test_awkward_lengths_auto_shrink_blocks():
    """T not divisible by the requested block must NOT raise (a config
    that trained on the jnp path keeps working): blocks auto-shrink to a
    valid divisor and the result still matches the oracle."""
    from mapreduce_tpu.ops.flash_attention import _pick_block

    assert _pick_block(96, 64) == 48       # divides, multiple of 8
    assert _pick_block(640, 512) == 320
    assert _pick_block(256, 512) == 256    # T smaller than request
    assert 250 % _pick_block(250, 64) == 0  # always a divisor

    q, k, v = _qkv(T=96)
    with jax.default_matmul_precision("float32"):
        out = flash_attention(q, k, v, layout="bthd", block_q=64,
                              block_kv=64)
        ref = full_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_transformer_flash_path_matches_ring():
    """The model-level wiring: cfg.flash=True (interpreted kernel) must
    reproduce the ring path's loss and one SGD step bit-near-exactly."""
    mesh = make_mesh(n_data=1, n_model=1)
    # one layer: the flash/ring equivalence is a per-layer property and
    # the interpreted kernel's trace time scales with layer count
    # (suite-budget right-sizing, PR 12); layer STACKING is covered by
    # the transformer suite's multi-layer trains
    kw = dict(vocab=64, embed=32, n_layers=1, n_heads=2, head_dim=16,
              ffn=64)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, size=(2, 129)).astype(np.int32)

    tr_ring = TransformerTrainer(mesh, TransformerConfig(flash=False,
                                                         **kw))
    tr_flash = TransformerTrainer(mesh, TransformerConfig(flash=True,
                                                          **kw))
    p = tr_ring.init_params()
    copy = lambda: jax.tree.map(jnp.copy, p)
    x, y = tr_ring.place_batch(toks)
    l_ring = float(tr_ring._loss(p, x, y))
    l_flash = float(tr_flash._loss(p, x, y))
    # the CPU backend's default matmul precision is bf16-grade, and the
    # two paths round differently tile by tile
    assert abs(l_ring - l_flash) < 1e-3

    p1, _ = tr_ring._train_step(copy(), x, y)
    p2, _ = tr_flash._train_step(copy(), x, y)
    for name in p1:
        np.testing.assert_allclose(np.asarray(p1[name]),
                                   np.asarray(p2[name]), atol=1e-4,
                                   err_msg=name)


def test_train_steps_scan_path():
    """_train_steps: S steps in one dispatch == S sequential steps."""
    mesh = make_mesh(n_data=1, n_model=1)
    cfg = TransformerConfig(vocab=64, embed=32, n_layers=1, n_heads=2,
                            head_dim=16, ffn=64, flash=False)
    tr = TransformerTrainer(mesh, cfg, learning_rate=1e-2)
    p = tr.init_params()
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 64, size=(3, 2, 129)).astype(np.int32)

    xs, ys = tr.place_batch(toks)
    p_scan, losses = tr._train_steps(jax.tree.map(jnp.copy, p), xs, ys)

    p_seq = jax.tree.map(jnp.copy, p)
    seq_losses = []
    for s in range(3):
        x, y = tr.place_batch(toks[s])
        p_seq, loss = tr._train_step(p_seq, x, y)
        seq_losses.append(float(loss))
    np.testing.assert_allclose(np.asarray(losses), np.asarray(seq_losses),
                               rtol=1e-5)
    for name in p_scan:
        np.testing.assert_allclose(np.asarray(p_scan[name]),
                                   np.asarray(p_seq[name]), atol=1e-5,
                                   err_msg=name)


def test_flash_rejected_on_sharded_sequence():
    cfg = TransformerConfig(vocab=64, embed=32, n_layers=1, n_heads=8,
                            head_dim=16, ffn=64, flash=True)
    with pytest.raises(ValueError, match="ring"):
        TransformerTrainer(make_mesh(), cfg)


def test_ring_flash_matches_oracle():
    """The kernel-backed ring path (use_flash=True, interpreted on CPU):
    full attention over a sequence sharded on 4 devices must match the
    unsharded oracle, forward and gradients."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mapreduce_tpu.parallel.ring import ring_attention

    mesh = make_mesh()  # data=8
    B, T, H, D = 2, 256, 2, 16
    q, k, v = _qkv(B=B, T=T, H=H, D=D)

    def run(use_flash):
        def local(q, k, v):
            return ring_attention(q, k, v, "data", causal=True,
                                  use_flash=use_flash)
        # check_vma=False: the pallas HLO *interpreter* (CPU test mode)
        # emits unvarying internal dynamic_slice operands that trip
        # shard_map's vma checker; the compiled Mosaic path carries vma
        # correctly (the TPU transformer runs with checking on)
        sm = jax.shard_map(local, mesh=mesh,
                          in_specs=(P(None, "data"),) * 3,
                          out_specs=P(None, "data"), check_vma=False)

        def loss(q, k, v):
            return jnp.sum(sm(q, k, v) ** 2)

        with jax.default_matmul_precision("float32"):
            out = sm(q, k, v)
            grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return out, grads

    out_f, g_f = run(True)
    with jax.default_matmul_precision("float32"):
        ref = full_attention_reference(q, k, v, causal=True)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            full_attention_reference(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    for name, a, b in zip("qkv", g_f, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3,
                                   err_msg=f"d{name}")


# -- the one-pass backward: grid shapes that can break an accumulator --------
# flash_dkv keeps dK/dV of a KV tile in scratch across the inner Q loop and
# the float32 dQ of every Q tile in scratch across the whole (kv, q) loop;
# a block index that repeats on consecutive grid steps is neither
# re-fetched nor written back, and the grid's block indices come from
# tables.  Each case below is a grid on which one of those can go wrong.


def _oracle_lse(q, k, v, causal):
    """Plain attention in the kernel layout ([B, H, T, D]; Tq may differ
    from Tk), float32 throughout: ``(out, lse [B, H, Tq, 1])``."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        keep = (jnp.arange(k.shape[2])[None, :]
                <= jnp.arange(q.shape[2])[:, None])
        s = jnp.where(keep, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse), v), lse


#: (Tq, Tk, block_q, block_kv): what the grid comes to
_BWD_GRIDS = {
    "one_tile": (64, 64, 64, 64),             # n_q = n_kv = 1
    "one_q_tile": (128, 128, 128, 32),        # n_q = 1 < n_kv = 4
    "one_kv_tile": (128, 128, 32, 128),       # n_kv = 1 < n_q = 4
    "q_finer": (192, 192, 32, 64),            # block_q < block_kv, 6 x 3
    "kv_finer": (192, 192, 64, 32),           # block_q > block_kv, 3 x 6
    "tq_ne_tk": (96, 192, 32, 64),            # non-causal only
}

_BWD_CASES = (
    [(g, causal, jnp.float32, False, None)
     for g in ("one_tile", "one_q_tile", "one_kv_tile", "q_finer",
               "kv_finer") for causal in (True, False)]
    + [("tq_ne_tk", False, jnp.float32, False, None),
       # a loss that uses lse: the non-zero dlse folds into delta
       ("q_finer", True, jnp.float32, True, None),
       ("kv_finer", False, jnp.float32, True, None),
       ("q_finer", True, jnp.bfloat16, False, None),
       ("one_q_tile", False, jnp.bfloat16, True, None),
       # an accumulator budget of two Q tiles: three passes over Q-row
       # ranges, dK/dV summed from float32 partials
       ("q_finer", True, jnp.float32, False, 2),
       ("kv_finer", False, jnp.float32, True, 1)])


@pytest.mark.parametrize(
    "grid,causal,dtype,use_lse,acc_tiles", _BWD_CASES,
    ids=[f"{g}-{'causal' if c else 'full'}-{jnp.dtype(d).name}"
         f"{'-lse' if u else ''}{f'-acc{a}' if a else ''}"
         for g, c, d, u, a in _BWD_CASES])
def test_backward_grid_shapes(grid, causal, dtype, use_lse, acc_tiles,
                              monkeypatch):
    Tq, Tk, block_q, block_kv = _BWD_GRIDS[grid]
    B, H, D = 1, 2, 16
    if acc_tiles:
        monkeypatch.setattr(fa, "_DQ_ACC_BYTES", acc_tiles * block_q * D * 4)
    q, k, v = (jax.random.normal(jax.random.key(i), (B, H, T, D), dtype)
               for i, T in enumerate((Tq, Tk, Tk)))

    def loss_of(attend):
        def loss(q, k, v):
            out, lse = attend(q, k, v)
            out = out.astype(jnp.float32)
            if use_lse:
                return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
            return jnp.sum(out ** 2)
        return loss

    flash = loss_of(lambda q, k, v: flash_attention_lse(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv))
    oracle = loss_of(lambda q, k, v: _oracle_lse(q, k, v, causal))
    with jax.default_matmul_precision("float32"):
        gf = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    # the tolerances of test_gradients_match_oracle (float32) and of
    # test_tpu_compiled_kernel_matches_oracle (bfloat16)
    tol = dict(atol=5e-5, rtol=1e-4) if dtype == jnp.float32 \
        else dict(atol=5e-2, rtol=5e-2)
    for name, a, b in zip("qkv", gf, gr):
        assert a.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol,
            err_msg=f"d{name} mismatch ({grid}, causal={causal})")


def test_backward_visits_each_tile_once():
    """Structure, not numbers: a traced backward holds three kernel
    programs, and the products sit where the one-pass design puts them —
    2 in flash_fwd (QK^T, PV), 5 in flash_dkv (QK^T, P^T dO, dO V^T,
    dS^T Q, dS K), none in flash_dq (scale and cast).  Non-causal, so a
    kernel holds its tile body once (causal holds it masked and full)."""
    from mapreduce_tpu.obs.metrics import REGISTRY

    def builds(kernel):
        return REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                            kernel=kernel)

    q, k, v = (jnp.swapaxes(a, 1, 2) for a in _qkv(B=1, T=128, H=1))
    before = {n: builds(n) for n in ("flash_fwd", "flash_dkv", "flash_dq")}
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=False, block_q=64, block_kv=64)),
        argnums=(0, 1, 2)))(q, k, v)
    kernels = [e for e, _ in eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    dots = {e.params["name"]: sum(
        inner.primitive.name == "dot_general"
        for inner, _ in eqns(e.params["jaxpr"])) for e in kernels}
    assert len(kernels) == 3, [e.params["name"] for e in kernels]
    assert dots == {"flash_fwd": 2, "flash_dkv": 5, "flash_dq": 0}
    for name, was in before.items():
        assert builds(name) == was + 1, name


# -- the grid of needed tiles (PR 32) ----------------------------------------
# Both tile loops run on a grid whose innermost axis counts the needed
# (Q tile, KV tile) pairs; which pair a step is, and what it opens, closes
# or completes, stands in int32 tables made at trace time.  The tables are
# held here to what they must say, apart from any kernel: Pallas keeps an
# output block in VMEM while its index stays and writes it back when the
# index moves, whatever the body wrote, so a block whose index comes back
# later, or moves before the body's write, is silently wrong on the chip
# and right under the interpreter.


def _tile_counts(grid):
    Tq, Tk, block_q, block_kv = _BWD_GRIDS[grid]
    return Tq // block_q, Tk // block_kv, block_q, block_kv


def _allowed(iq, j, block_q, block_kv, causal, window=None):
    """Does the tile pair hold an allowed (q, k)?  Position by position,
    apart from the module's own tile arithmetic."""
    if not causal:
        return True
    back = ((iq * block_q + np.arange(block_q))[:, None]
            - (j * block_kv + np.arange(block_kv))[None, :])
    return bool(((back >= 0) & ((back < window) if window else True)).any())


def _written_back(blocks, writes, adds_to):
    """Replay what the pipeline does with one output over the grid's
    steps.  *blocks*: the block index a step holds; *writes*: whether
    the body writes the held block there; *adds_to*: the block a step's
    products add to, None for a step without products.  Every run of one
    index must hold exactly one write, and no step may add to a block
    after that block's write.  Returns ``{block: runs}``: a block that
    is written back once has one run."""
    runs, wrote_at, start = {}, {}, 0
    for t in range(1, len(blocks) + 1):
        if t < len(blocks) and blocks[t] == blocks[start]:
            continue
        written = [u for u in range(start, t) if writes[u]]
        assert len(written) == 1, (blocks[start], start, t, written)
        runs[blocks[start]] = runs.get(blocks[start], 0) + 1
        wrote_at[blocks[start]] = written[0]
        start = t
    for t, block in enumerate(adds_to):
        assert block is None or wrote_at[block] >= t, (t, block)
    return runs


#: windows (positions) the tables are also held to: a tile's width, a
#: multiple of it, neither, narrower than a tile, one position, wider
#: than the sequence (then the tables are the causal ones)
_TABLE_CASES = ([(g, c, None, None) for g in _BWD_GRIDS
                 for c in (True, False)]
                + [("q_finer", True, 2, None), ("kv_finer", False, 1, None),
                   ("kv_finer", True, 1, None), ("tq_ne_tk", True, 1, None),
                   ("one_kv_tile", True, 2, None)]
                + [(g, True, None, w)
                   for g in ("q_finer", "kv_finer", "one_tile", "tq_ne_tk")
                   for w in (64, 80, 20, 1, 500)]
                + [("q_finer", True, 2, 80), ("kv_finer", True, 1, 64),
                   ("tq_ne_tk", True, 1, 40)])


@pytest.mark.parametrize(
    "grid,causal,acc_tiles,window", _TABLE_CASES,
    ids=[f"{g}-{'causal' if c else 'full'}{f'-acc{a}' if a else ''}"
         f"{f'-win{w}' if w else ''}" for g, c, a, w in _TABLE_CASES])
def test_needed_tile_tables(grid, causal, acc_tiles, window):
    n_q, n_kv, block_q, block_kv = _tile_counts(grid)
    flag = lambda flags, bit: [bool(f & bit) for f in flags]

    # forward: Q-major, KV ascending; o and lse leave once a Q tile
    (qt, kt, flags), _ = fa._fwd_tables(n_q, n_kv, block_q, block_kv, causal,
                                        window)
    want = [(iq, j) for iq in range(n_q) for j in range(n_kv)
            if _allowed(iq, j, block_q, block_kv, causal, window)]
    assert list(zip(qt.tolist(), kt.tolist())) == want
    assert all(flag(flags, fa._WORK))
    # the softmax state opens at each row's first pair and nowhere else
    assert flag(flags, fa._OPENS) == [
        t == 0 or qt[t - 1] != qt[t] for t in range(len(qt))]
    assert _written_back(qt.tolist(), flag(flags, fa._CLOSES),
                         qt.tolist()) == {iq: 1 for iq in range(n_q)}

    # backward: pass by pass, KV-major, Q ascending from the first
    # needed Q tile; a row nobody needs keeps one step without _WORK
    q_tiles = acc_tiles or n_q
    assert n_q % q_tiles == 0
    (qt, kt, pt, dqt, flags), _ = fa._bwd_tables(
        n_q, n_kv, block_q, block_kv, causal, q_tiles, window)
    want, rows = [], []
    for c in range(n_q // q_tiles):
        for j in range(n_kv):
            row = [(iq, j, True) for iq in range(c * q_tiles,
                                                 (c + 1) * q_tiles)
                   if _allowed(iq, j, block_q, block_kv, causal, window)]
            row = row or [((c + 1) * q_tiles - 1, j, False)]
            want += row
            rows += [(c, j)] * len(row)
    work = flag(flags, fa._WORK)
    assert list(zip(qt.tolist(), kt.tolist(), work)) == want
    assert list(zip(pt.tolist(), kt.tolist())) == rows
    assert (pt == qt // q_tiles).all()
    assert flag(flags, fa._OPENS) == [
        t == 0 or rows[t - 1] != rows[t] for t in range(len(rows))]
    # dK, dV: one block a (pass, KV tile), written at the row's end
    assert _written_back(
        rows, flag(flags, fa._CLOSES),
        [r if w else None for r, w in zip(rows, work)]) == {
            (c, j): 1 for c in range(n_q // q_tiles) for j in range(n_kv)}
    # dQ: one block a Q tile, held for one run of steps and written at
    # the tile's last contribution
    done = flag(flags, fa._DQ_DONE)
    for iq in range(n_q):
        mine = [t for t in range(len(qt)) if qt[t] == iq and work[t]]
        assert [t for t in mine if done[t]] == [mine[-1]]
        assert dqt[mine[-1]] == iq
    assert _written_back(
        dqt.tolist(), done,
        [q if w else None for q, w in zip(qt.tolist(), work)]) == {
            iq: 1 for iq in range(n_q)}
    opens = flag(flags, fa._DQ_OPENS)
    if window is None:
        # the accumulator is zeroed on KV tile 0's row: every Q tile is
        # there, and no table says so
        assert not any(opens)
        for c in range(n_q // q_tiles):
            assert [q for q, r, w in zip(qt.tolist(), rows, work)
                    if r == (c, 0) and w] == list(range(c * q_tiles,
                                                        (c + 1) * q_tiles))
    else:
        # under a window the tables say where: a Q tile's first needed
        # pair, once, before anything adds to it
        for iq in range(n_q):
            mine = [t for t in range(len(qt)) if qt[t] == iq and work[t]]
            assert [t for t in mine if opens[t]] == [mine[0]]
        assert not any(o and not w for o, w in zip(opens, work))


def test_no_window_builds_the_parents_tables():
    """``window=None`` is the causal table as it always was: the needed
    pairs Q tile by Q tile, the flag words of PR 32 (no ``_DQ_OPENS``),
    and 528 / 528 at the dense cell's 32 tiles, 10 / 10 at the looped
    cell's 4, on the causal kernels' gauge."""
    from mapreduce_tpu.obs.metrics import REGISTRY

    read = lambda kernel, kind: REGISTRY.value(
        "mrtpu_flash_grid_steps", kernel=kernel, kind=kind)
    for n, needed in ((32, 528), (4, 10)):
        for args in ((n, n, 1024, 1024, True), (n, n, 1024, 1024, True,
                                                None)):
            (qt, kt, flags), _ = fa._fwd_tables(*args)
            assert list(zip(qt.tolist(), kt.tolist())) == [
                (iq, j) for iq in range(n) for j in range(iq + 1)]
            assert flags.tolist() == [
                fa._WORK | (fa._OPENS if j == 0 else 0)
                | (fa._CLOSES if j == iq else 0)
                for iq in range(n) for j in range(iq + 1)]
        (qt, kt, pt, dqt, flags), _ = fa._bwd_tables(n, n, 1024, 1024, True,
                                                     n)
        assert list(zip(kt.tolist(), qt.tolist())) == [
            (j, iq) for j in range(n) for iq in range(j, n)]
        # a Q tile is done at its diagonal step, which opens row j:
        # the block held after it is the next row's
        assert dqt.tolist() == [
            j if iq == j else min(j + 1, n - 1)
            for j in range(n) for iq in range(j, n)]
        assert not pt.any()
        assert flags.tolist() == [
            fa._WORK | (fa._OPENS | fa._DQ_DONE if iq == j else 0)
            | (fa._CLOSES if iq == n - 1 else 0)
            for j in range(n) for iq in range(j, n)]
        q = jnp.zeros((1, 1, n * 1024, 128), jnp.bfloat16)
        jax.make_jaxpr(jax.grad(lambda q: flash_attention(
            q, q, q).astype(jnp.float32).sum()))(q)
        for kernel in ("flash_fwd", "flash_dkv"):
            assert (read(kernel, "steps"), read(kernel, "needed")) \
                == (needed, needed)


def test_window_tables_at_the_cells_lengths():
    """A 1024-position window on 1024 x 1024 tiles needs the diagonal
    tile and the one before it: 63 of a 32K head's 528 pairs, 47 of a
    24K head's 300, 31 of a 16K head's 136."""
    for n, full, windowed in ((32, 528, 63), (24, 300, 47), (16, 136, 31)):
        assert len(fa._fwd_tables(n, n, 1024, 1024, True)[0][0]) == full
        (qt, kt, _), _ = fa._fwd_tables(n, n, 1024, 1024, True, 1024)
        assert list(zip(qt.tolist(), kt.tolist())) == [
            (iq, j) for iq in range(n) for j in (iq - 1, iq) if j >= 0]
        assert len(qt) == windowed
        assert len(fa._bwd_tables(n, n, 1024, 1024, True, n,
                                  1024)[0][0]) == windowed


_GRID_CASES = [("q_finer", True, None), ("q_finer", False, None),
               ("kv_finer", True, None), ("tq_ne_tk", False, None),
               ("tq_ne_tk", True, None), ("one_tile", True, None),
               ("q_finer", True, 64), ("kv_finer", True, 80),
               ("tq_ne_tk", True, 40), ("q_finer", True, 500)]


@pytest.mark.parametrize("grid,causal,window", _GRID_CASES,
                         ids=[f"{g}-{'causal' if c else 'full'}"
                              f"{f'-win{w}' if w else ''}"
                              for g, c, w in _GRID_CASES])
def test_built_kernels_run_the_needed_steps_alone(grid, causal, window):
    """The kernels as built: the innermost grid axis of flash_fwd and of
    flash_dkv is as long as there are needed tiles (plus, backward, one
    step a KV tile that no Q tile needs), causal or not, windowed or
    not, and the gauge says the same; the windowed programs carry their
    own names, there and on the gauge."""
    from mapreduce_tpu.obs.metrics import REGISTRY

    Tq, Tk, block_q, block_kv = _BWD_GRIDS[grid]
    n_q, n_kv = Tq // block_q, Tk // block_kv
    B, H, D = 1, 2, 16
    q, k, v = (jnp.zeros((B, H, T, D), jnp.float32) for T in (Tq, Tk, Tk))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention_lse(
            q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
            window=window)[0].sum(), argnums=(0, 1, 2)))(q, k, v)
    grids = {e.params["name"]: tuple(e.params["grid_mapping"].grid)
             for e, _ in eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"}
    needed = sum(_allowed(iq, j, block_q, block_kv, causal, window)
                 for iq in range(n_q) for j in range(n_kv))
    unseen = sum(not any(_allowed(iq, j, block_q, block_kv, causal, window)
                         for iq in range(n_q)) for j in range(n_kv))
    fwd, dkv, dq = (name + ("_win" if window else "")
                    for name in ("flash_fwd", "flash_dkv", "flash_dq"))
    assert grids == {fwd: (B, H, needed), dkv: (B, H, needed + unseen),
                     dq: (B, H, n_q)}
    read = lambda kernel, kind: REGISTRY.value(
        "mrtpu_flash_grid_steps", kernel=kernel, kind=kind)
    assert (read(fwd, "steps"), read(fwd, "needed")) == (needed, needed)
    assert (read(dkv, "steps"), read(dkv, "needed")) \
        == (needed + unseen, needed)
    if not causal:
        assert needed == n_q * n_kv and unseen == 0
    if window == 500:                    # wider than the sequence: causal
        assert needed == sum(_allowed(iq, j, block_q, block_kv, True)
                             for iq in range(n_q) for j in range(n_kv))


# -- a sliding window (PR 35) -------------------------------------------------


def _window_oracle(q, k, v, window):
    """Masked jnp softmax over ``0 <= q - k < window``, [B, H, T, D]."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    back = jnp.arange(q.shape[2])[:, None] - jnp.arange(k.shape[2])[None, :]
    s = jnp.where((back >= 0) & (back < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


#: (T, block_q, block_kv, window): a multiple of the tile; the tile
#: itself (every tile of the layer is then masked, half by the diagonal
#: and half by the edge); not a multiple; narrower than a tile (one tile
#: crosses both); one position; as wide as the sequence and wider
#: (equals causal); Q and KV tiles of unequal count, both ways
_WINDOW_CASES = [(256, 64, 64, 128), (256, 64, 64, 64), (256, 64, 64, 100),
                 (256, 64, 64, 40), (256, 64, 64, 1), (256, 64, 64, 256),
                 (256, 64, 64, 300), (192, 32, 64, 48), (192, 64, 32, 80),
                 (192, 32, 64, 64)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T,block_q,block_kv,window", _WINDOW_CASES,
                         ids=[f"T{t}-q{bq}-kv{bk}-win{w}"
                              for t, bq, bk, w in _WINDOW_CASES])
def test_window_matches_a_masked_softmax(T, block_q, block_kv, window,
                                         dtype):
    """Forward and all three gradients of the windowed kernels against
    a masked jnp softmax."""
    B, H, D = 1, 2, 16
    q, k, v, w = (jax.random.normal(jax.random.key(i), (B, H, T, D), dtype)
                  for i in range(4))
    weigh = lambda out: jnp.sum(out.astype(jnp.float32)
                                * w.astype(jnp.float32))
    flash = lambda q, k, v: flash_attention(
        q, k, v, window=window, block_q=block_q, block_kv=block_kv)
    with jax.default_matmul_precision("float32"):
        out, want = flash(q, k, v), _window_oracle(q, k, v, window)
        got = jax.grad(lambda *a: weigh(flash(*a)), argnums=(0, 1, 2))(
            q, k, v)
        ref = jax.grad(lambda *a: weigh(_window_oracle(*a, window)),
                       argnums=(0, 1, 2))(q, k, v)
    tol = dict(atol=5e-5, rtol=1e-4) if dtype == jnp.float32 \
        else dict(atol=5e-2, rtol=5e-2)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), **tol)
    for name, a, b in zip("qkv", got, ref):
        assert a.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol,
            err_msg=f"d{name} mismatch (window {window})")
    if window >= T and dtype == jnp.float32:
        causal = flash_attention(q, k, v, block_q=block_q,
                                 block_kv=block_kv)
        assert np.array_equal(np.asarray(out), np.asarray(causal))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("part", ["forward", "dq", "dk", "dv"])
def test_head_width_256_matches_a_masked_softmax(part, dtype):
    """Latent attention's width (GLM-4.7-Flash: 192 + 64 for q and k, 256
    for v): the forward and each of the three gradients at D = 256
    against a masked jnp softmax, several tiles a head, scale 256^-1/2."""
    B, H, T, D = 1, 2, 128, 256
    q, k, v, w = (jax.random.normal(jax.random.key(i), (B, H, T, D), dtype)
                  for i in range(4))
    weigh = lambda out: jnp.sum(out.astype(jnp.float32)
                                * w.astype(jnp.float32))
    flash = lambda q, k, v: flash_attention(q, k, v, block_q=32,
                                            block_kv=64)
    tol = dict(atol=5e-5, rtol=1e-4) if dtype == jnp.float32 \
        else dict(atol=5e-2, rtol=5e-2)
    with jax.default_matmul_precision("float32"):
        if part == "forward":
            got, want = flash(q, k, v), _window_oracle(q, k, v, T)
            assert got.dtype == dtype
        else:
            i = "qkv".index(part[1])
            got = jax.grad(lambda *a: weigh(flash(*a)), argnums=i)(q, k, v)
            want = jax.grad(lambda *a: weigh(_window_oracle(*a, T)),
                            argnums=i)(q, k, v)
            assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_backward_tile_reserve_follows_the_head_width():
    """40 MiB at 128 and under, as every program built before the
    reserve followed D asked for (the admitted cells' steps keep their
    text); the operand, output and scratch tiles' 8 MiB double at 256."""
    assert fa._bwd_tile_bytes(64) == fa._bwd_tile_bytes(128) == 40 << 20
    assert fa._bwd_tile_bytes(256) == 48 << 20
    assert fa._bwd_tile_bytes(512) == 64 << 20
    # with the dQ of 8 Q tiles of 1024 x 256 resident: under a v5e
    # core's 128 MiB
    assert 8 * 1024 * 256 * 4 + fa._bwd_tile_bytes(256) == 56 << 20


def test_window_with_several_backward_passes(monkeypatch):
    """An accumulator budget of two Q tiles: three passes over Q-row
    ranges, each Q tile's accumulator zeroed at its first windowed
    pair."""
    T, block, window = 192, 32, 80
    monkeypatch.setattr(fa, "_DQ_ACC_BYTES", 2 * block * 16 * 4)
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 2, T, 16))
               for i in range(3))
    with jax.default_matmul_precision("float32"):
        got = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, window=window, block_q=block, block_kv=block) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(lambda *a: jnp.sum(_window_oracle(*a, window) ** 2),
                       argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-4)


def test_window_is_refused_without_causal():
    q = jnp.zeros((1, 1, 64, 16))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=16)
    with pytest.raises(ValueError, match="at least 1"):
        flash_attention(q, q, q, window=0)


@pytest.mark.parametrize("acc_tiles", [None, 1], ids=["one_pass", "acc1"])
def test_causal_keys_past_the_queries_get_zero_gradients(acc_tiles,
                                                         monkeypatch):
    """Causal with more keys than queries (Tk > Tq): no query sees the
    KV tiles past the last query row, their dK and dV blocks are still
    written, as zeros, and everything else matches the oracle."""
    Tq, Tk, block_q, block_kv = _BWD_GRIDS["tq_ne_tk"]
    B, H, D = 1, 2, 16
    if acc_tiles:
        monkeypatch.setattr(fa, "_DQ_ACC_BYTES", acc_tiles * block_q * D * 4)
    q, k, v = (jax.random.normal(jax.random.key(i), (B, H, T, D))
               for i, T in enumerate((Tq, Tk, Tk)))

    def loss_of(attend):
        def loss(q, k, v):
            out, lse = attend(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
        return loss

    flash = loss_of(lambda q, k, v: flash_attention_lse(
        q, k, v, causal=True, block_q=block_q, block_kv=block_kv))
    oracle = loss_of(lambda q, k, v: _oracle_lse(q, k, v, True))
    with jax.default_matmul_precision("float32"):
        gf = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    seen = -(-Tq // block_kv) * block_kv        # whole KV tiles a query sees
    assert seen < Tk
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-4, err_msg=f"d{name}")
    for name, g in zip("kv", gf[1:]):
        assert not np.asarray(g[:, :, seen:]).any(), f"d{name}"
        assert np.asarray(g[:, :, :seen]).any(), f"d{name}"


# -- what a checkpoint policy can keep of the kernel (PR 29) -----------------

#: entry point -> (calls the kernel, names the caller's policy lists,
#: flash_fwd calls in the gradient under that policy).  Both entry
#: points name the same two residuals; what is kept is the policy's
_NAMING = {
    # local attention, as the trainer wraps it with the kernel on: the
    # output and the row statistics are kept, and the recomputation
    # runs no forward kernel
    "local": (lambda q, k, v: flash_attention(q, k, v, interpret=True),
              fa.KEPT_NAMES, 1),
    # the ring's entry point, as the trainer wraps the ring path: it
    # makes n_data partial outputs a layer, keeping those multiplies
    # the bytes, so the policy lists no name.  Nothing is kept, as under
    # the bare checkpoint before PR 29, and the recomputation runs the
    # kernel again
    "ring-lse": (lambda q, k, v: flash_attention_lse(
        q, k, v, interpret=True)[0], (), 2),
}


def _under_policy(entry):
    call, listed, _ = _NAMING[entry]
    return jax.checkpoint(
        lambda q, k, v: call(q, k, v).astype(jnp.float32).sum(),
        policy=jax.checkpoint_policies.save_only_these_names(*listed))


@pytest.mark.parametrize("entry", sorted(_NAMING))
def test_residual_names_by_entry_point(entry):
    """Which of the kernel's residuals a naming policy keeps: the two
    named ones in their dense shapes where it lists them, and nothing
    where it lists none."""
    from jax._src.ad_checkpoint import saved_residuals

    B, H, T, D = 1, 2, 64, 16
    q, k, v = (a.swapaxes(1, 2) for a in _qkv(B=B, T=T, H=H, D=D))
    kept, names = [], set()
    for aval, why in saved_residuals(_under_policy(entry), q, k, v):
        if "from the argument" in why:
            continue                                    # q, k, v
        kept.append(aval.shape)
        if "named" in why:
            names.add(why.split("'")[1])
    listed = set(_NAMING[entry][1])
    if not listed:
        assert kept == [] and names == set()
        return
    # the row statistics are kept dense, without the kernel's unit minor
    # dimension (padded to 128 lanes in a TPU's memory).  The output is
    # also the checkpointed function's result here, and such a residual
    # is listed as the no-op jax puts behind it, not by its name
    assert sorted(kept) == [(B, H, T), (B, H, T, D)]
    assert {fa.KEPT_NAMES[1]} <= names <= listed


@pytest.mark.parametrize("entry", sorted(_NAMING))
def test_forward_kernel_calls_in_a_checkpointed_gradient(entry):
    """The gradient of a checkpointed call holds ONE forward kernel when
    its residuals are named and kept, two when they are not (the
    recomputation makes them again); the backward kernels run once."""
    q, k, v = (a.swapaxes(1, 2) for a in _qkv(B=1, T=64, H=2, D=16))
    jaxpr = jax.make_jaxpr(jax.grad(_under_policy(entry), argnums=(0, 1, 2))
                           )(q, k, v)
    calls = kernel_calls(jaxpr.jaxpr)
    assert calls == {"flash_fwd": _NAMING[entry][2], "flash_dkv": 1,
                     "flash_dq": 1}


@pytest.mark.parametrize("wrapping", ["policy", "bare"])
@pytest.mark.parametrize("causal", [True, False])
def test_named_residuals_leave_the_gradient_bit_identical(causal, wrapping):
    """Naming changes which values are kept, not one bit of a result:
    under the keeping policy and under a bare checkpoint (which
    recomputes them), against the gradient with no checkpoint."""
    q, k, v = (a.swapaxes(1, 2) for a in _qkv(B=1, T=128, H=2, D=16))
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)

    def loss(q, k, v):
        return (flash_attention(q, k, v, causal=causal, interpret=True,
                                block_q=64, block_kv=64) * w).sum()

    f = {"policy": jax.checkpoint(
             loss, policy=jax.checkpoint_policies.save_only_these_names(
                 *fa.KEPT_NAMES)),
         "bare": jax.checkpoint(loss)}[wrapping]
    want = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    got = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- hardware-gated: the compiled Mosaic path -------------------------------
# Run with MAPREDUCE_TPU_TESTS=1 on a machine with a real chip (conftest
# then skips the cpu pin); silently skipped in the virtual-CPU CI.  These
# close the interpret-only gap: tiling and the shard_map vma plumbing are
# exercised compiled, with check_vma ON.

needs_tpu = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="compiled-Mosaic test: needs a real TPU "
           "(MAPREDUCE_TPU_TESTS=1)")


@needs_tpu
@pytest.mark.parametrize("causal", [True, False])
def test_tpu_compiled_kernel_matches_oracle(causal):
    q, k, v = _qkv(B=1, T=512, H=2, D=64, dtype=jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       layout="bthd").astype(jnp.float32))

    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, layout="bthd"))(q, k, v)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    ref = full_attention_reference(q, k, v, causal=causal)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(full_attention_reference(
        q, k, v, causal=causal).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)
    for name, a, b in zip("qkv", grads, g_ref):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2,
                                   err_msg=f"d{name}")


@needs_tpu
def test_tpu_compiled_backward_at_the_cell_tile():
    """The benchmark cell's tile on the chip: 1024 x 1024 blocks, head
    width 128, causal, 4 x 4 tiles a head — 10 needed, 6 skipped, so the
    resident dQ accumulator, the redirected block indices and the
    masked/full split all run compiled (the case above is one tile)."""
    q, k, v = _qkv(B=1, T=4096, H=2, D=128, dtype=jnp.bfloat16)

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) ** 2)

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, layout="bthd", block_q=1024, block_kv=1024))
    grads = jax.jit(jax.grad(flash, argnums=(0, 1, 2)))(q, k, v)
    # the oracle in float32 on the same values: over 4096 keys a
    # bfloat16 oracle is itself 0.27 from it, the kernel 0.04
    with jax.default_matmul_precision("float32"):
        g_ref = jax.jit(jax.grad(
            loss(lambda q, k, v: full_attention_reference(
                q, k, v, causal=True)),
            argnums=(0, 1, 2)))(*(a.astype(jnp.float32) for a in (q, k, v)))
    for name, a, b in zip("qkv", grads, g_ref):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2,
                                   err_msg=f"d{name}")


@needs_tpu
def test_tpu_ring_flash_compiled_vma_checked():
    """The production composition: kernel-backed ring inside shard_map
    with vma checking ON, compiled (the CPU suite must disable checking
    for the interpreter's unvarying internal operands)."""
    from jax.sharding import PartitionSpec as P

    from mapreduce_tpu.parallel.ring import ring_attention

    n = len(jax.devices())
    mesh = make_mesh(n_data=n, n_model=1)
    q, k, v = _qkv(B=1, T=256 * n, H=2, D=64, dtype=jnp.bfloat16)

    def local(q, k, v):
        return ring_attention(q, k, v, "data", causal=True, use_flash=True)

    sm = jax.shard_map(local, mesh=mesh, in_specs=(P(None, "data"),) * 3,
                       out_specs=P(None, "data"))  # check_vma defaults ON
    out = jax.jit(sm)(q, k, v)
    ref = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)

    def loss(q, k, v):
        return jnp.sum(sm(q, k, v).astype(jnp.float32) ** 2)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
