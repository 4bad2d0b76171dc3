"""Ring attention + transformer tests on the virtual 8-device mesh: the
sharded computation must match unsharded oracles to float tolerance, and
the sp x tp training step must actually learn."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from mapreduce_tpu.models.transformer import (
    TransformerConfig, TransformerTrainer, init_transformer)
from mapreduce_tpu.parallel import make_mesh
from mapreduce_tpu.parallel.ring import (
    full_attention_reference, ring_attention)


def _qkv(B=2, T=32, H=4, D=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.normal(size=(B, T, H, D)).astype(np.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    mesh = make_mesh()  # data=8
    q, k, v = _qkv()
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "data", causal=causal),
        mesh=mesh,
        in_specs=(PS(None, "data"),) * 3, out_specs=PS(None, "data")))
    got = np.asarray(fn(q, k, v))
    want = np.asarray(full_attention_reference(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ring_attention_single_device_degenerates():
    mesh = make_mesh(n_data=1, n_model=1)
    q, k, v = _qkv(T=16)
    fn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "data"),
        mesh=mesh, in_specs=(PS(None, "data"),) * 3,
        out_specs=PS(None, "data"))
    got = np.asarray(fn(q, k, v))
    want = np.asarray(full_attention_reference(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _batch(rng, cfg, B, T):
    """Learnable synthetic language: tok[t+1] = (tok[t] + 1) % K with
    occasional resets — a next-token task a tiny LM must crack."""
    K = cfg.vocab
    toks = np.zeros((B, T + 1), dtype=np.int32)
    toks[:, 0] = rng.integers(0, K, size=B)
    for t in range(T):
        toks[:, t + 1] = (toks[:, t] + 1) % K
    return toks


def test_transformer_sp_tp_trains():
    mesh = make_mesh(n_model=2)  # model=2 x data=4: tp x sp
    cfg = TransformerConfig(vocab=32, embed=64, n_layers=2, n_heads=4,
                            head_dim=16, ffn=128)
    trainer = TransformerTrainer(mesh, cfg, learning_rate=3e-2)
    params = trainer.init_params()
    rng = np.random.default_rng(0)
    losses = []
    for it in range(80):
        toks = _batch(rng, cfg, B=8, T=32)
        params, loss = trainer.step(params, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.35, (losses[0], losses[-1])
    assert losses[-1] < 1.2, losses[-20:]


def test_transformer_loss_matches_unsharded():
    """The sharded vocab/sequence cross-entropy must equal a plain
    unsharded computation of the same model."""
    mesh = make_mesh(n_model=2)
    cfg = TransformerConfig(vocab=32, embed=32, n_layers=1, n_heads=4,
                            head_dim=8, ffn=64, dtype=jnp.float32)
    trainer = TransformerTrainer(mesh, cfg)
    params_host = init_transformer(jax.random.key(trainer.seed), cfg)
    params = trainer.init_params()
    rng = np.random.default_rng(1)
    toks = _batch(rng, cfg, B=2, T=16)
    x, y = trainer.place_batch(toks)
    got = float(trainer._loss(params, x, y))

    # unsharded oracle: same math with n_model=1 axes absent
    from mapreduce_tpu.models.transformer import loss_local
    one = make_mesh(n_data=1, n_model=1)
    oracle = jax.shard_map(
        lambda p, a, b: loss_local(p, a, b, cfg, 1),
        mesh=one,
        in_specs=({n: PS() for n in params_host}, PS(None, "data"),
                  PS(None, "data")),
        out_specs=PS())
    want = float(oracle(params_host, toks[:, :-1], toks[:, 1:]))
    assert abs(got - want) < 1e-3, (got, want)


def test_transformer_remat_matches_no_remat():
    """jax.checkpoint rematerialisation must not change the math — same
    params, same tokens, identical loss and identical one-step update."""
    from dataclasses import replace

    mesh = make_mesh(n_model=2)
    cfg = TransformerConfig(vocab=32, embed=32, n_layers=2, n_heads=4,
                            head_dim=8, ffn=64, dtype=jnp.float32)
    rng = np.random.default_rng(2)
    toks = _batch(rng, cfg, B=2, T=16)

    losses = {}
    for remat in (False, True):
        c = replace(cfg, remat=remat)
        trainer = TransformerTrainer(mesh, c, learning_rate=1e-2)
        params = trainer.init_params()
        params, loss0 = trainer.step(params, toks)
        _, loss1 = trainer.step(params, toks)
        losses[remat] = (float(loss0), float(loss1))
    assert np.allclose(losses[False], losses[True], rtol=1e-6), losses


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [1, 2])
def test_ring_attention_chunked_matches_full(causal, block):
    """Flash-style local chunking (block_size) must be bit-for-math
    identical to the unchunked path: the online-softmax combine is
    associative, so chunk boundaries cannot change the result."""
    mesh = make_mesh()  # data=8 -> T_local = 4
    q, k, v = _qkv()
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "data", causal=causal,
                                       block_size=block),
        mesh=mesh,
        in_specs=(PS(None, "data"),) * 3, out_specs=PS(None, "data")))
    got = np.asarray(fn(q, k, v))
    want = np.asarray(full_attention_reference(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ring_attention_chunked_gradients_match():
    """The jax.checkpoint'd chunk scan must give the same gradients as
    the unchunked path (backward rematerialisation changes memory, not
    math)."""
    mesh = make_mesh()
    q, k, v = _qkv(T=32)

    def loss(block):
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "data",
                                           block_size=block),
            mesh=mesh,
            in_specs=(PS(None, "data"),) * 3,
            out_specs=PS(None, "data"))
        return lambda q, k, v: (f(q, k, v) ** 2).sum()

    g_full = jax.grad(loss(None), argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g_blk = jax.grad(loss(2), argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for a, b in zip(g_full, g_blk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_transformer_attn_block_trains():
    mesh = make_mesh(n_model=2)
    cfg = TransformerConfig(vocab=32, embed=64, n_layers=1, n_heads=4,
                            head_dim=16, ffn=128, remat=True,
                            attn_block=4)
    trainer = TransformerTrainer(mesh, cfg, learning_rate=3e-2)
    params = trainer.init_params()
    rng = np.random.default_rng(0)
    losses = []
    for it in range(40):
        toks = _batch(rng, cfg, B=8, T=32)
        params, loss = trainer.step(params, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])


def test_transformer_loss_block_matches_unchunked():
    """Sequence-chunked cross-entropy must equal the unchunked loss (and
    its gradients): logits chunks recompute in backward, math unchanged."""
    from dataclasses import replace

    mesh = make_mesh(n_model=2)
    cfg = TransformerConfig(vocab=32, embed=32, n_layers=1, n_heads=4,
                            head_dim=8, ffn=64, dtype=jnp.float32)
    rng = np.random.default_rng(3)
    # T=32 over data=4 -> T_local=8; loss_block=2 -> C=4 chunks, so the
    # multi-chunk scan/reassembly path genuinely runs
    toks = _batch(rng, cfg, B=2, T=32)

    results = {}
    for tc in (None, 2):
        c = replace(cfg, loss_block=tc)
        trainer = TransformerTrainer(mesh, c, learning_rate=1e-2)
        params = trainer.init_params()
        params, loss0 = trainer.step(params, toks)
        _, loss1 = trainer.step(params, toks)
        results[tc] = (float(loss0), float(loss1))
    assert np.allclose(results[None], results[2], rtol=1e-6), results


def test_checkpoint_roundtrip_and_reshard(tmp_path):
    """Transformer checkpoints: save mid-training, reload, continue —
    losses must continue the saved trajectory exactly; and a checkpoint
    saved on one mesh layout must restore onto a DIFFERENT tp x sp
    layout (resharding via device_put with the new NamedSharding)."""
    import numpy as np

    from mapreduce_tpu.parallel import make_mesh

    cfg = TransformerConfig(vocab=64, embed=32, n_layers=2, n_heads=4,
                            head_dim=8, ffn=64)
    mesh = make_mesh(n_data=4, n_model=2)
    tr = TransformerTrainer(mesh, cfg, learning_rate=1e-2)
    params = tr.init_params()
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, size=(2, 33)).astype(np.int32)

    params, _ = tr.step(params, toks)
    tr.save(str(tmp_path / "ckpt"), params, step=1)
    ref_losses = []
    for _ in range(3):
        params, loss = tr.step(params, toks)
        ref_losses.append(float(loss))

    # resume on the SAME layout
    p2, step = tr.load(str(tmp_path / "ckpt"))
    assert step == 1
    got = []
    for _ in range(3):
        p2, loss = tr.step(p2, toks)
        got.append(float(loss))
    np.testing.assert_allclose(got, ref_losses, rtol=1e-6)

    # restore onto a different mesh layout (tp 4 x sp 2)
    mesh2 = make_mesh(n_data=2, n_model=4)
    tr2 = TransformerTrainer(mesh2, cfg, learning_rate=1e-2)
    p3, _ = tr2.load(str(tmp_path / "ckpt"))
    got2 = []
    for _ in range(3):
        p3, loss = tr2.step(p3, toks)
        got2.append(float(loss))
    # looser than the same-layout check: a different tp width changes
    # psum reduction ORDER, so f32 rounding drifts ~1e-4/step
    np.testing.assert_allclose(got2, ref_losses, rtol=3e-3)

    # config mismatch is a clean error, not silent garbage
    other = TransformerTrainer(
        mesh, TransformerConfig(vocab=64, embed=32, n_layers=3,
                                n_heads=4, head_dim=8, ffn=64))
    with pytest.raises(ValueError, match="do not match"):
        other.load(str(tmp_path / "ckpt"))


def test_checkpoint_retention_and_corrupt_fallback(tmp_path):
    """save() keeps only the newest *keep* checkpoints (the old npz
    overwrote in place — the sharded layout must stay bounded too), and
    load() falls back past a corrupt shard to the previous complete
    checkpoint instead of aborting, counted in ``mrtpu_ckpt_*`` (the
    restore policy of models/checkpoint.py)."""
    import numpy as np

    from mapreduce_tpu.models import checkpoint as ckpt
    from mapreduce_tpu.obs.metrics import REGISTRY
    from mapreduce_tpu.parallel import make_mesh
    from mapreduce_tpu.storage.localdir import LocalDirStorage

    cfg = TransformerConfig(vocab=64, embed=32, n_layers=2, n_heads=4,
                            head_dim=8, ffn=64)
    mesh = make_mesh(n_data=4, n_model=2)
    tr = TransformerTrainer(mesh, cfg, learning_rate=1e-2)
    params = tr.init_params()
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, size=(2, 33)).astype(np.int32)
    d = tmp_path / "r"
    saved = {}
    for step in range(1, 5):
        params, _ = tr.step(params, toks)
        tr.save(str(d), params, step=step, keep=2)
        saved[step] = {k: np.asarray(v) for k, v in params.items()}
    st = LocalDirStorage(str(d))
    assert ckpt.list_steps(st) == [3, 4]

    # garble one shard of the newest checkpoint: load() must fall back
    # to step 3 value-identically and count the event
    shard = st.list(r"ckpt-00000004/.*\.npy")[0]
    st.write_bytes(shard, b"\x00" * 8)
    before = REGISTRY.sum("mrtpu_ckpt_fallbacks_total")
    p2, step = tr.load(str(d))
    assert step == 3
    for k in p2:
        np.testing.assert_array_equal(np.asarray(p2[k]), saved[3][k])
    assert REGISTRY.sum("mrtpu_ckpt_fallbacks_total") == before + 1


def test_adamw_optimizer_path_and_state_checkpoint(tmp_path):
    """The optax path: adamw trains under the tp x sp mesh, and
    save/load_state restores BOTH params and moments — the resumed
    trajectory must equal the uninterrupted one exactly (fresh moments
    would diverge on the very next step)."""
    import numpy as np
    import optax

    from mapreduce_tpu.parallel import make_mesh

    cfg = TransformerConfig(vocab=64, embed=32, n_layers=2, n_heads=4,
                            head_dim=8, ffn=64)
    mesh = make_mesh(n_data=4, n_model=2)
    tr = TransformerTrainer(mesh, cfg, optimizer=optax.adamw(1e-3))
    params, opt = tr.init_state()
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, size=(2, 33)).astype(np.int32)

    losses = []
    for _ in range(4):
        params, opt, loss = tr.step_opt(params, opt, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # adamw actually optimizes

    tr.save(str(tmp_path / "s"), params, step=4, opt_state=opt)
    cont = []
    for _ in range(3):
        params, opt, loss = tr.step_opt(params, opt, toks)
        cont.append(float(loss))

    p2, o2, step = tr.load_state(str(tmp_path / "s"))
    assert step == 4
    resumed = []
    for _ in range(3):
        p2, o2, loss = tr.step_opt(p2, o2, toks)
        resumed.append(float(loss))
    np.testing.assert_allclose(resumed, cont, rtol=1e-6)

    # a params-only checkpoint resumes with fresh moments, not a crash
    tr.save(str(tmp_path / "p"), p2, step=7)
    p3, o3, step = tr.load_state(str(tmp_path / "p"))
    assert step == 7
    p3, o3, loss = tr.step_opt(p3, o3, toks)
    assert np.isfinite(float(loss))

    # the string shorthand builds the same kind of trainer
    tr2 = TransformerTrainer(mesh, cfg, learning_rate=1e-3,
                             optimizer="adamw")
    pp, oo = tr2.init_state()
    pp, oo, loss = tr2.step_opt(pp, oo, toks)
    assert np.isfinite(float(loss))
