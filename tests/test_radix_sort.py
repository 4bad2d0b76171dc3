"""Golden-equivalence suite for the Pallas radix sort + fused exchange.

The tentpole contract (ops/radix_sort): the LSD radix formulation is
BIT-identical to ``jax.lax.sort`` — a hard array-equality pin, never a
tolerance — across the whole golden matrix: stability on duplicate hash
keys (the iota permutation lane ties to input order), the full uint32
key range including the sign-bit edge values and the engine sentinel,
every record arity through ``sorted_unique_reduce``'s rank-sort gather
transport, capacity-retry convergence, and the fused partition plan's
exchange traffic-matrix row bit-equal to the host recompute.  Off-TPU
the kernels run under the Pallas interpreter (ops/pallas_compat's ONE
CPU-fallback policy), so these tests execute the real kernel logic:
grid sequencing, the ladder prefix offsets, the in-kernel scatter.

Plus the machinery satellites: the three-impl tier dispatcher serving
cold on argsort and hot-swapping to the radix program (with the
generalized ``tier=`` metric label, so radix-served dispatches are
distinguishable from the classic 0/1 taxonomy), session stats
reporting a non-default ``sort_impl``, CLI/device-hook passthrough,
and the analytic cost model's radix terms (fixed digit passes, no
comparator ``n·log n``).
"""

from collections import Counter
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mapreduce_tpu.engine import DeviceWordCount, tiering
from mapreduce_tpu.engine.device_engine import DeviceEngine, EngineConfig
from mapreduce_tpu.engine.session import EngineSession
from mapreduce_tpu.obs import profile as obs_profile
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.ops.radix_sort import (
    RADIX_PASSES, radix_partition_plan, radix_sort_pairs)
from mapreduce_tpu.ops.segscan import sorted_unique_reduce
from mapreduce_tpu.parallel import make_mesh

from tests.test_fused_engine import (
    _chunks, _dict_oracle, _records_map_fn, _result_dict)
from tests.test_tiering import _StubSpec, _tier_disp

#: one small block so every ops-level case runs a multi-tile grid (the
#: cross-tile prefix ladder and the full-array scatter revisits)
BLOCK = 512

#: the uint32 edge values the bit-order argument must survive: zero,
#: the signed-positive max, the sign bit, and the sentinel
_EDGES = np.array([0, 1, 0x7FFFFFFF, 0x80000000, 0xFFFFFFFE, 0xFFFFFFFF],
                  dtype=np.uint32)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


# -- ops-level: radix == lax.sort over the golden matrix ---------------------


def _pin_sorted(k1, k2, ctx):
    n = int(k1.shape[0])
    iota = jnp.arange(n, dtype=jnp.int32)
    want = jax.lax.sort((jnp.asarray(k1), jnp.asarray(k2), iota),
                        num_keys=2)
    got = radix_sort_pairs(jnp.asarray(k1), jnp.asarray(k2), block=BLOCK)
    for g, w, lane in zip(got, want, ("k1", "k2", "perm")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (ctx, lane)


def test_radix_sort_bit_identical_duplicates_and_stability():
    """Heavy duplicate mass in BOTH key lanes: equal (k1, k2) pairs
    must keep input order (the permutation lane is the witness — any
    unstable pass would permute it differently from lax.sort)."""
    rng = np.random.default_rng(5)
    for n in (1, 37, BLOCK, BLOCK + 1, 3 * BLOCK + 99):
        k1 = rng.integers(0, 7, n).astype(np.uint32)
        k2 = rng.integers(0, 3, n).astype(np.uint32)
        _pin_sorted(k1, k2, ("dup", n))


def test_radix_sort_full_uint32_range_and_sign_bit_edges():
    """Unsigned bit order == unsigned numeric order: full-range random
    keys plus a dense injection of the sign-bit edge values and the
    sentinel sort identically to the comparator."""
    rng = np.random.default_rng(11)
    n = 2 * BLOCK + 17
    k1 = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    k2 = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    k1[: n // 2] = rng.choice(_EDGES, n // 2)
    k2[n // 3:] = rng.choice(_EDGES, n - n // 3)
    _pin_sorted(k1, k2, "edges")
    # and the all-edge-values corner outright
    k = rng.choice(_EDGES, n).astype(np.uint32)
    _pin_sorted(k, k[::-1].copy(), "all-edges")


def test_radix_kernel_builds_are_counted():
    """The kernel programs land on the shared build counter under
    their own names (the bench gate's registry witness)."""
    h0 = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                      kernel="radix_hist")
    s0 = REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                      kernel="radix_scatter")
    rng = np.random.default_rng(13)
    k = rng.integers(0, 1 << 16, 700).astype(np.uint32)
    _pin_sorted(k, k, "counted")
    assert REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                        kernel="radix_hist") > h0
    assert REGISTRY.sum("mrtpu_pallas_kernel_builds_total",
                        kernel="radix_scatter") > s0


def test_partition_plan_bit_equal_to_onehot_plan():
    """The fused-exchange primitive: ranks of valid rows and the
    counts row both equal the classic one-hot cumsum plan it deletes
    (invalid rows — dest == P — are dropped by the downstream scatter
    either way, so only valid ranks are pinned)."""
    rng = np.random.default_rng(17)
    for n, P in ((1, 2), (300, 4), (2 * BLOCK + 31, 8)):
        dest = jnp.asarray(rng.integers(0, P + 1, n).astype(np.int32))
        rank, counts = radix_partition_plan(dest, P, block=BLOCK)
        onehot = (dest[:, None] == jnp.arange(P)[None, :]).astype(
            jnp.int32)
        want_rank = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - 1,
            jnp.clip(dest, 0, P - 1)[:, None], axis=1)[:, 0]
        valid = np.asarray(dest) < P
        assert np.array_equal(np.asarray(rank)[valid],
                              np.asarray(want_rank)[valid]), (n, P)
        assert np.array_equal(np.asarray(counts),
                              np.asarray(onehot.sum(axis=0))), (n, P)


@pytest.mark.parametrize("n, P", [(7, 2), (300, 4), (2 * BLOCK + 31, 8)])
def test_onehot_plan_reads_a_row_out_of_range_at_the_column_it_clips_to(
        n, P):
    """``routing_plan``'s ``"lax"`` formulation picks a row's rank out
    of the one-hot cumsum by a compare and a sum, no longer by
    ``take_along_axis``: every entry equals the gathered one, those of
    rows that go nowhere (``dest`` of ``P``, past it, or negative)
    included — their rank "means nothing" and stays what it was."""
    from mapreduce_tpu.parallel.shuffle import routing_plan

    rng = np.random.default_rng(31 + n)
    dest = rng.integers(-1, P + 3, n).astype(np.int32)
    dest[[0, n // 2, -1]] = [P, P + 2, -1]
    rank, counts = jax.jit(lambda d: routing_plan(d, P))(jnp.asarray(dest))
    onehot = (dest[:, None] == np.arange(P)[None, :]).astype(np.int32)
    want = np.take_along_axis(
        np.cumsum(onehot, axis=0) - 1,
        np.clip(dest, 0, P - 1)[:, None], axis=1)[:, 0]
    assert np.array_equal(np.asarray(rank), want)
    assert np.array_equal(np.asarray(counts), onehot.sum(axis=0))
    assert rank.dtype == counts.dtype == jnp.int32


def test_sorted_unique_reduce_radix_all_arities():
    """Every record arity rides the rank-sort gather transport:
    unit values, scalar values, two-lane values, and a three-lane
    payload — each bit-identical to the variadic comparator path,
    for sum/min/max."""
    rng = np.random.default_rng(19)
    n = 384
    keys = rng.integers(0, 40, size=(n, 2)).astype(np.uint32)
    valid = rng.random(n) < 0.8
    cases = [
        ("unit", np.zeros(n, np.int32),
         np.arange(n, dtype=np.int32)[:, None], True, ("sum",)),
        ("scalar", rng.integers(-50, 100, n).astype(np.int32),
         np.arange(n, dtype=np.int32)[:, None], False,
         ("sum", "min", "max")),
        ("two-lane", rng.integers(0, 100, (n, 2)).astype(np.int32),
         np.arange(n, dtype=np.int32)[:, None], False, ("sum",)),
        ("payload-q3", rng.integers(-50, 100, n).astype(np.int32),
         rng.integers(0, 1 << 20, (n, 3)).astype(np.int32), False,
         ("sum",)),
    ]
    for name, vals, pay, unit, ops in cases:
        for op in ops:
            args = (jnp.asarray(keys), jnp.asarray(vals),
                    jnp.asarray(pay), jnp.asarray(valid), 128, op)
            want = sorted_unique_reduce(*args, unit_values=unit,
                                        sort_impl="variadic")
            got = sorted_unique_reduce(*args, unit_values=unit,
                                       sort_impl="radix")
            for f in want._fields:
                assert np.array_equal(np.asarray(getattr(want, f)),
                                      np.asarray(getattr(got, f))), (
                    name, op, f)


def test_sorted_unique_reduce_rejects_unknown_sort_impl():
    z = jnp.zeros((8, 2), jnp.uint32)
    with pytest.raises(ValueError, match="sort_impl"):
        sorted_unique_reduce(z, jnp.zeros(8, jnp.int32),
                             jnp.zeros((8, 1), jnp.int32),
                             jnp.ones(8, bool), 8, "sum",
                             sort_impl="bitonic")


# -- engine-level: fold bit-identity, fused exchange, retry ------------------
#
# Suite-budget note: every distinct EngineConfig is a wave-program
# compile and the interpreter pays 32 kernel evaluations per radix
# sort site, so the engines here keep k=1 wave shapes and one shared
# config family.


def _wc(mesh, sort_impl="variadic", out_capacity=1024):
    return DeviceWordCount(
        mesh, chunk_len=2048,
        config=EngineConfig(local_capacity=1024, exchange_capacity=256,
                            out_capacity=out_capacity, tile=512,
                            tile_records=128, combine_in_scan=True,
                            sort_impl=sort_impl))


def test_engine_fold_bit_identical_radix_multiwave(mesh):
    """The full fused wave program under sort_impl='radix' — radix
    sort at every stage plus the fused exchange plan — equals the
    variadic fold across 3 waves, with one dispatch per wave and no
    separate count-pass dispatch."""
    corpus = b"the quick brown fox jumps over the lazy dog " * 400
    d0 = REGISTRY.sum("mrtpu_device_dispatches_total", program="wave")
    tm_v = {}
    counts_v = _wc(mesh).count_bytes(corpus, timings=tm_v, waves=3)
    d1 = REGISTRY.sum("mrtpu_device_dispatches_total", program="wave")
    tm_r = {}
    counts_r = _wc(mesh, "radix").count_bytes(corpus, timings=tm_r,
                                              waves=3)
    d2 = REGISTRY.sum("mrtpu_device_dispatches_total", program="wave")
    assert counts_r == counts_v
    assert counts_r[b"the"] == 800
    assert tm_v["waves"] == tm_r["waves"] >= 2
    assert tm_v["retries"] == tm_r["retries"] == 0
    assert d1 - d0 == tm_v["waves"]
    assert d2 - d1 == tm_r["waves"]


def test_exchange_matrix_bit_equal_under_radix(mesh):
    """PR 9 matrix semantics under the fused plan: the on-device
    traffic matrix (the histogram row the radix plan donates) equals
    the host recompute bit-for-bit."""
    data = (b"alpha beta gamma delta epsilon zeta hotword hotword "
            * 300)
    wc = _wc(mesh, "radix")
    tm = {}
    wc.count_bytes(data, timings=tm, waves=3)
    want = wc.host_exchange_matrix(data, waves=3)
    assert np.array_equal(np.asarray(tm["exchange"]["matrix"]), want)


def test_radix_capacity_retry_convergence(mesh):
    """A deliberately under-sized out_capacity overflows, right-sizes,
    and converges to ground truth — with the retry's matrix still
    bit-equal to the untruncated host recompute."""
    words = [f"w{i:03d}".encode() for i in range(97)]
    corpus = (b" ".join(words) + b" ") * 30
    wc = _wc(mesh, "radix", out_capacity=8)
    tm = {}
    counts = wc.count_bytes(corpus, timings=tm, waves=2)
    assert tm["retries"] >= 1
    truth = {bytes(w): c for w, c in Counter(corpus.split()).items()}
    assert counts == truth
    assert np.array_equal(np.asarray(tm["exchange"]["matrix"]),
                          wc.host_exchange_matrix(corpus, waves=2))


# -- the three-impl tier dispatcher ------------------------------------------


def test_tiered_radix_swaps_and_labels_impl_name(mesh):
    """'tiered-radix' serves cold on argsort tier-0 and hot-swaps to
    the radix program at a wave boundary, exactly like the classic
    policy — and the steady-tier dispatches land under tier='radix'
    (the generalized label), leaving the classic '1' series untouched
    so existing gate keys keep their meaning."""
    rng = np.random.default_rng(23)
    chunks = _chunks(rng, 4 * mesh.shape["data"])
    cfg = EngineConfig(local_capacity=256, exchange_capacity=64,
                       out_capacity=256, reduce_op="sum",
                       sort_impl="tiered-radix")
    eng = DeviceEngine(mesh, _records_map_fn, cfg)
    eng._tier_spec = _StubSpec(after=2)  # steady tier lands at poll 2
    t0, t1 = _tier_disp("0"), _tier_disp("1")
    tr = _tier_disp("radix")
    tm = {}
    with tiering.force_cold():
        res = eng.run(chunks, timings=tm, waves=4, max_retries=0)
    assert res.overflow == 0
    assert tm["tier_swaps"] == 1 and tm["tier_cold_start"]
    assert tm["serving_tier"] == 1
    assert _tier_disp("0") - t0 == 2
    assert _tier_disp("radix") - tr == 2
    assert _tier_disp("1") == t1  # the classic label never moves
    assert _result_dict(res) == _dict_oracle(chunks, "sum")


def test_dispatcher_rejects_untied_policy(mesh):
    from mapreduce_tpu.engine.tiering import TieredWaveDispatcher

    with pytest.raises(ValueError, match="tiered"):
        TieredWaveDispatcher(object(), EngineConfig(sort_impl="radix"))


# -- session stats / config / CLI passthrough --------------------------------


def test_session_stats_report_non_default_sort_impl(mesh):
    cfg = EngineConfig(local_capacity=256, exchange_capacity=128,
                       out_capacity=256, tile=64, tile_records=64,
                       reduce_op="sum")
    rng = np.random.default_rng(29)
    chunks = _chunks(rng, mesh.shape["data"])
    sess = EngineSession(mesh, _records_map_fn,
                         replace(cfg, sort_impl="radix"), k=1)
    sess.feed(chunks, task="t")
    stats = sess.stats("t")
    assert stats["sort_impl"] == "radix"
    assert _result_dict(sess.snapshot("t")) == _dict_oracle(chunks,
                                                            "sum")
    # a default variadic session keeps the pre-radix key set exactly
    sess_v = EngineSession(mesh, _records_map_fn, cfg, k=1)
    sess_v.feed(chunks, task="t")
    assert "sort_impl" not in sess_v.stats("t")


def test_engine_config_rejects_unknown_sort_impl(mesh):
    with pytest.raises(ValueError, match="sort_impl"):
        DeviceEngine(mesh, lambda c, i, f: None,
                     EngineConfig(sort_impl="bitonic"))


def test_device_hooks_and_cli_flags_pass_sort_impl():
    """`cli wordcount --device --sort-impl radix` lands in init_args as
    device_sort_impl, which the wordcount module's device_config reads
    (cheap: no engine is built)."""
    from mapreduce_tpu.examples.wordcount import _conf, device_config

    saved = dict(_conf)
    try:
        for impl in ("radix", "tiered-radix"):
            _conf["device_sort_impl"] = impl
            assert device_config().sort_impl == impl
        _conf.pop("device_sort_impl")
        assert device_config().sort_impl == "variadic"
    finally:
        _conf.clear()
        _conf.update(saved)
    from mapreduce_tpu import cli as cli_mod

    with pytest.raises(SystemExit):
        cli_mod.cmd_wordcount(["f", "--sort-impl", "bitonic"])


# -- cost model: the radix formulation reaches the roofline ------------------


def test_analytic_costs_radix_terms():
    """The radix terms replace the comparator n·log2(n): fixed digit
    passes (linear in n — doubling n doubles the sort flops exactly),
    trading MORE histogram/scatter ALU for FEWER bytes over memory
    (the kernel moves 12-byte sort lanes per pass, not whole records),
    and absent when sort_impl is unset (back-compat: the comparator
    model)."""
    assert RADIX_PASSES == 16
    base = obs_profile.analytic_costs(1 << 20, 1 << 16, 16,
                                      fold_records=256)
    radix = obs_profile.analytic_costs(1 << 20, 1 << 16, 16,
                                       fold_records=256,
                                       sort_impl="radix")
    assert radix["flops"] != base["flops"]
    assert radix["bytes"] < base["bytes"]
    assert radix["flops"] > 0 and radix["bytes"] > (1 << 20)
    # record-count independence of the pass structure: sort flops are
    # linear in n (no log factor), so (2n flops - seg/fold terms)
    # scales exactly 2x
    a = obs_profile.analytic_costs(0, 1 << 14, 16, sort_impl="radix")
    b = obs_profile.analytic_costs(0, 1 << 15, 16, sort_impl="radix")
    assert b["flops"] == 2 * a["flops"] and b["bytes"] == 2 * a["bytes"]
    # explicit variadic/None both mean the comparator model
    assert obs_profile.analytic_costs(
        1 << 20, 1 << 16, 16, fold_records=256,
        sort_impl="variadic") == base
