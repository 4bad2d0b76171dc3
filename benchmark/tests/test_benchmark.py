"""The benchmark's own tests: CPU only, tiny sizes, some seconds in all.

    python -m pytest benchmark/tests -q

They check the shape of every manifest and data file (what stopped PR
22), that the command refuses anything but the cell's TPU chips, that
each kind's loop yields the contract's object, and the yardstick's
arithmetic: the corpus reference, the float32 language-model reference,
the FLOPs function and the trace reduction.
"""

import glob
import json
import os
import re
import subprocess
import sys
import time
from collections import Counter

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFESTS = ["BENCHMARK.json"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(BENCH, "parked", "*.json")))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def line_ok(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


# -- (a) names, units, moves, chips -----------------------------------------


@pytest.mark.parametrize("path", MANIFESTS)
def test_manifest_shape(path):
    m = load(ROOT, path)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert m["paths"] == ["benchmark"]
    assert all(line_ok(w) for w in m["command"]) and len(m["command"]) <= 32

    configs = {c["name"]: c for c in m["configs"]}
    assert len(configs) == len(m["configs"]) <= 24
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        on_disk = load(ROOT, c["file"])
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])

    cells = {w["name"]: w for w in m["workloads"]}
    assert 1 <= len(cells) == len(m["workloads"]) <= 24
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) \
        == len(cells)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line_ok(w["why"])
        on_disk = load(BENCH, "workloads", f"{w['name']}.json")
        assert (on_disk["config"], on_disk["chips"], on_disk["why"],
                on_disk["traffic"]["name"]) == (
            w["config"], w["chips"], w["why"], w["traffic"])
    assert {w["config"] for w in m["workloads"]} == set(configs)
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)

    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) == len(m["end_to_end"]) <= 16
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
        assert set(e.get("workloads", cells)) <= set(cells)

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    layers = {p["name"]: p for p in m["per_layer"]}
    assert 1 <= len(layers) == len(m["per_layer"]) <= 128
    assert not set(layers) & set(e2e)
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(p["name"]) and UNIT.match(p["unit"])
        assert NAME.match(p["layer"]), p["layer"]
        assert p["better"] in ("lower", "higher")
        assert p["source"] in SOURCES
        assert reported_in(p) <= set(cells) and reported_in(p)
        # every cell that reports the metric reports what it moves
        assert p["moves"] in e2e and p["moves"] != "setup_s"
        assert reported_in(p) <= reported_in(e2e[p["moves"]])
        on_disk = load(BENCH, "layer_metrics", f"{p['name']}.json")
        assert {k: on_disk[k] for k in p} == p
    for name in cells:       # setup_s, one more, and one per-layer metric
        assert sum(name in reported_in(e) for e in m["end_to_end"]) >= 2
        assert any(name in reported_in(p) for p in m["per_layer"])
    assert len(json.dumps(m)) < 64 * 1024


def test_data_files_are_name_shaped():
    """Every data file, listed in a manifest or waiting to be."""
    for path in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json")):
        d = load(path)
        assert os.path.basename(path) == d["name"] + ".json"
        assert NAME.match(d["name"]) and NAME.match(d["layer"])
        assert NAME.match(d["moves"]) and UNIT.match(d["unit"])
        assert all(NAME.match(w) for w in d["workloads"])
        assert d["source"] in SOURCES and isinstance(d["read"], dict)
    for path in glob.glob(os.path.join(BENCH, "workloads", "*.json")):
        d = load(path)
        assert os.path.basename(path) == d["name"] + ".json"
        assert NAME.match(d["name"]) and NAME.match(d["config"])
        assert NAME.match(d["traffic"]["name"]) and line_ok(d["why"])
        assert os.path.exists(os.path.join(
            BENCH, "configs", d["config"] + ".json"))
    for path in glob.glob(os.path.join(BENCH, "configs", "*.json")):
        d = load(path)
        assert os.path.basename(path) == d["name"] + ".json"
        assert NAME.match(d["name"]) and line_ok(d["source"])
        assert os.path.exists(os.path.join(BENCH, "kinds",
                                           d["kind"] + ".py"))
        assert d["guarantees"] if d["kind"] == "wordcount" else True


# -- (b) the command refuses the CPU ----------------------------------------


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "train-dense-32k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr


# -- (c) each kind's loop, tiny, on the CPU ---------------------------------


def tiny(kind):
    """A tiny configuration and cell of *kind*, with the manifest that
    names their metrics."""
    m = load(BENCH, "parked", "with-wordcount.json")
    if kind == "wordcount":
        config = load(BENCH, "configs", "wordcount-europarl.json")
        config["corpus"].update(n_words=60_000, n_lines=2_400)
        config["program"]["chunk_len"] = 1 << 14
        cell = load(BENCH, "workloads", "wc-europarl-1x.json")
    else:
        config = load(BENCH, "configs", "dense-168m-32k.json")
        config["model"].update(vocab=256, embed=64, n_layers=2, n_heads=2,
                               head_dim=32, ffn=128, loss_block=64)
        config["train"].update(seq_len=128, reference_block=32)
        cell = load(BENCH, "workloads", "train-dense-32k.json")
    return m, cell, config


@pytest.mark.parametrize("kind,n_dev,traced", [
    ("wordcount", 1, False), ("wordcount", 4, True),
    ("trainer", 1, False), ("trainer", 1, True)])
def test_kind_loop_yields_the_contracts_object(kind, n_dev, traced):
    import jax

    from benchmark import run

    m, cell, config = tiny(kind)
    result = run.measure(m, cell, config, seed=2**31 + 11, seconds=1.0,
                         traced=traced, devices=jax.devices()[:n_dev],
                         t_start=time.monotonic())
    # no TPU plane in a CPU trace: no breakdown, and the trace's metrics
    # are left out of the line
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == n_dev
    names = set(result["metrics"])
    if not traced:
        want = {"wordcount": {"wc_job_s", "wc_rate", "setup_s"},
                "trainer": {"train_tok_rate", "setup_s"}}[kind]
        assert names == want
    elif kind == "wordcount":
        assert names == {"wc.materialize_s", "wc.upload_s",
                         "wc.readback_s", "wc.compute_s"}
    else:
        assert names == {"train.step_ms"}     # no peak for a CPU: no mfu
    for v in result["metrics"].values():
        assert v["value"] > 0 and UNIT.match(v["unit"])
    json.dumps(result)


# -- (d) the corpus and its reference ---------------------------------------


def test_corpus_reference_equals_counter():
    from benchmark import corpus

    params = dict(load(BENCH, "configs", "wordcount-europarl.json")["corpus"],
                  n_words=150_000, n_lines=6_000)
    text, reference = corpus.make_corpus(params, seed=2**31 + 3)
    assert reference == dict(Counter(text.split()))
    assert sum(reference.values()) == 150_000
    assert max(len(w) for w in reference) > 128          # the tail words
    assert text.count(b"\n") >= 6_000
    # another seed: the same words at the same rates, in another order
    text2, reference2 = corpus.make_corpus(params, seed=7)
    assert text2 != text and reference2 == dict(Counter(text2.split()))
    assert abs(len(text2) - len(text)) < 0.01 * len(text)
    # the same seed: the same bytes
    assert corpus.make_corpus(params, seed=7)[0] == text2


# -- (e) the float32 reference against the trainer --------------------------


def test_reference_loss_equals_the_trainers():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_lm
    from benchmark.kinds.trainer import LOSS_TOLERANCE
    from mapreduce_tpu.models.transformer import (TransformerConfig,
                                                  TransformerTrainer)
    from mapreduce_tpu.parallel import make_mesh

    model = dict(vocab=512, embed=64, n_layers=2, n_heads=4, head_dim=16,
                 ffn=256, loss_block=32)
    tokens = np.random.default_rng(0).integers(
        0, model["vocab"], size=(2, 129), dtype=np.int32)
    want = None
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, LOSS_TOLERANCE)):
        trainer = TransformerTrainer(
            make_mesh(devices=jax.devices()[:1]),
            TransformerConfig(dtype=dtype, **model), seed=3)
        params = trainer.init_params()
        if want is None:
            want = float(reference_lm.reference_loss(
                params, tokens[:, :-1], tokens[:, 1:], n_layers=2,
                n_heads=4, head_dim=16, block=32))
        _, got = trainer.step(params, tokens)
        assert abs(float(got) - want) / want <= tol, (dtype, got, want)


# -- (f) the FLOPs function -------------------------------------------------


def test_required_flops_at_the_cells_sizes():
    from benchmark import flops

    config = load(BENCH, "configs", "dense-168m-32k.json")
    assert flops.matmul_params(config["model"]) == 134_217_728
    got = flops.train_step_flops(config["model"], config["train"]["batch"],
                                 config["train"]["seq_len"])
    assert got == pytest.approx(7.92e13, rel=1e-3)
    assert got == 6 * 134_217_728 * 32768 + 3 * 2 * 2 * 8 * 32768**2 * 128 * 8 / 2
    assert load(BENCH, "peaks.json")["by_device_kind"]["TPU v5 lite"][
        "flops_bf16"] == 197e12


# -- the trace reduction, on a hand-made event list -------------------------


def test_trace_reduction_by_hand():
    from benchmark import trace

    d = load(HERE, "trace_events.json")
    ops = [tuple(e) for e in d["ops"]]
    spans = [tuple(e) for e in d["spans"]]
    want = d["expect"]
    window = (100, 1100)
    inside = trace.clip(ops, window)
    assert trace.merge([(0, 5), (3, 9), (9, 12), (20, 21)]) == \
        [(0, 12), (20, 21)]
    assert trace.busy_ns(inside) == want["busy_ns"]
    by_name = trace.self_time_by_name(inside)
    assert by_name == want["self"]
    assert sum(by_name.values()) == trace.busy_ns(inside)
    assert trace.matching_ns(by_name, ["k"]) == want["k_ns"]
    assert trace.matching_ns(by_name, ["whil"]) == 0      # whole names only
    idle = trace.gaps(ops, window)
    assert [list(g) for g in idle] == want["gaps"]
    assert trace.label_gaps(idle, spans) == want["by_span"]
    assert trace.op_name("%flash_fwd.8 = bf16[1,8]{1,0} custom-call(%x)") \
        == "flash_fwd.8"
    assert trace.top_ops(by_name, ["k"], n=3) == \
        [["k", 2e-07], ["g", 2e-07], ["f", 1.5e-07]]

    s = trace.summarize({"/device:TPU:0": ops,
                         "/device:TPU:1": ops + [("h", 600, 300)]},
                        spans, "job")
    assert s["window_s"] == pytest.approx(1e-6)
    assert s["busy_s"] == pytest.approx((600 + 900) / 2 / 1e9)
    assert s["idle_share"] == pytest.approx(0.4)         # the idler chip
    assert s["idle_gaps"] == [["job", 3e-07], ["check", 1e-07]]
    assert trace.summarize({}, spans, "job") == {}
