"""The trainer's step record (PR 37): ``TransformerTrainer.step_log()``,
the spans ``step_in_flight`` and ``step_wait``, the counters made from the
record, and ``benchmark/step_report.py``.  CPU, toy sizes: no time here
is a device number.
"""

import gc
import json
import os
import statistics
import time

import numpy as np
import pytest

from mapreduce_tpu.models import transformer as tf
from mapreduce_tpu.models.moe import STAT_DROPPED, block_rows, tiles_for
from mapreduce_tpu.models.transformer import (TransformerConfig,
                                              TransformerTrainer, slow_cause)
from mapreduce_tpu.obs.metrics import REGISTRY, counter
from mapreduce_tpu.obs.trace import TRACER
from mapreduce_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DENSE = dict(vocab=64, embed=32, n_layers=2, n_heads=2, head_dim=16, ffn=64,
             loss_block=16, flash=False)
LOOPED = dict(DENSE, loop_steps=2, rope_theta=1e4, ffn_gated=True,
              sandwich_norm=True, final_norm=True)
ROUTED = dict(vocab=64, embed=32, n_layers=2, n_heads=2, head_dim=16, ffn=64,
              loss_block=32, flash=False, layer_ffns=("dense", "moe"),
              moe_experts=8, moe_top_k=2, moe_ffn=16, moe_held=4,
              moe_held_offset=2)
TOKENS = np.random.default_rng(0).integers(0, 64, size=(2, 65),
                                           dtype=np.int32)
#: what every closed record holds; ``turnaround_s`` comes with the next
#: dispatch, ``overlap_s`` and ``wait_s`` with an observer, ``mem`` where
#: a device reports, ``slow`` with a cause
ALWAYS = {"step", "program", "tokens", "t_enter", "place_s", "dispatch_s",
          "compiled", "gc_s", "step_s", "closed_by"}


def one_device():
    import jax

    return make_mesh(devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def dense():
    return TransformerTrainer(one_device(), TransformerConfig(**DENSE))


@pytest.fixture(scope="module")
def looped():
    return TransformerTrainer(one_device(), TransformerConfig(**LOOPED),
                              optimizer="adamw")


@pytest.fixture(scope="module")
def routed():
    return TransformerTrainer(one_device(), TransformerConfig(**ROUTED),
                              optimizer="adamw")


def settle(trainer):
    """Close whatever an earlier test of the module left in flight."""
    if trainer._flight is not None:
        trainer._close(time.monotonic(), "next_step")
    return len(trainer.step_log())


# -- the record's fields ------------------------------------------------------


def test_step_and_observe_loss_leave_a_record(dense):
    n = settle(dense)
    tokens0 = REGISTRY.sum("mrtpu_train_tokens_total", program="tf_step")
    steps0 = REGISTRY.value("mrtpu_train_step_seconds", program="tf_step")
    waits0 = REGISTRY.value("mrtpu_train_host_wait_seconds",
                            program="tf_step")
    params = dense.init_params()
    for _ in range(3):
        params, loss = dense.step(params, TOKENS)
        got = dense.observe_loss(loss)
        assert isinstance(got, float) and got == float(loss)
    log = dense.step_log()[n:]
    assert [r["step"] for r in log] == [log[0]["step"] + i for i in range(3)]
    for r in log:
        assert ALWAYS | {"overlap_s", "wait_s"} <= set(r)
        assert r["program"] == "tf_step" and r["tokens"] == 2 * 64
        assert r["closed_by"] == "observe" and "pairs_held" not in r
        assert min(r["place_s"], r["dispatch_s"], r["overlap_s"],
                   r["wait_s"], r["gc_s"]) >= 0
        assert r["step_s"] >= r["wait_s"]
        json.dumps(r)
    # proven done to proven done: the steps tile the clock
    assert log[1]["step_s"] == pytest.approx(
        log[1]["t_enter"] - log[0]["t_enter"] - log[0]["place_s"]
        - log[0]["dispatch_s"] - log[0]["overlap_s"] - log[0]["wait_s"]
        + log[1]["place_s"] + log[1]["dispatch_s"] + log[1]["overlap_s"]
        + log[1]["wait_s"], abs=2e-4)
    # the counters follow the record, the last step's at step_log()
    assert REGISTRY.sum("mrtpu_train_tokens_total", program="tf_step") \
        == tokens0 + 3 * 128
    assert REGISTRY.value("mrtpu_train_step_seconds", program="tf_step") \
        == steps0 + 3
    assert REGISTRY.value("mrtpu_train_host_wait_seconds",
                          program="tf_step") == waits0 + 3
    assert dense.step_log()[n:] == log           # and only once


def test_step_opt_and_observe_passes_leave_a_record(looped):
    n = settle(looped)
    params, opt_state = looped.init_state()
    params, opt_state, loss, stats = looped.step_opt(params, opt_state,
                                                     TOKENS)
    got = looped.observe_passes(stats)
    assert got.shape == (2, 2)
    (r,) = looped.step_log()[n:]
    assert ALWAYS | {"overlap_s", "wait_s"} <= set(r)
    assert r["program"] == "tf_step_opt" and r["closed_by"] == "observe"
    assert "pairs_held" not in r and "tiles_in_use" not in r
    # whoever reads the loss next finds no step in flight
    assert looped.observe_loss(loss) == float(loss)
    assert len(looped.step_log()) == n + 1


def test_step_opt_and_observe_experts_leave_a_record(routed):
    n = settle(routed)
    *_, stats = routed.step_opt(*routed.init_state(), TOKENS)
    loads = routed.observe_experts(stats)
    (r,) = routed.step_log()[n:]
    assert ALWAYS | {"overlap_s", "wait_s", "pairs_held", "tiles_in_use",
                     "load_max_over_mean"} <= set(r)
    held = loads[:, :STAT_DROPPED]
    assert r["pairs_held"] == held.sum() and r["closed_by"] == "observe"
    assert len(r["tiles_in_use"]) == len(r["load_max_over_mean"]) == 1
    assert r["load_max_over_mean"][0] == pytest.approx(
        held[0].max() / held[0].mean())
    assert REGISTRY.value("mrtpu_moe_tiles_in_use", layer=1) \
        == r["tiles_in_use"][0]
    (flight,) = [e for e in TRACER.events()
                 if e["name"] == "step_in_flight"
                 and e["args"].get("pairs_held") is not None][-1:]
    assert flight["args"]["pairs_held"] == r["pairs_held"]
    assert flight["args"]["step"] == r["step"]


def test_a_step_nobody_observes_is_closed_by_the_next(dense):
    n = settle(dense)
    params = dense.init_params()
    for _ in range(3):
        params, loss = dense.step(params, TOKENS)
        float(loss)                 # the caller's own read
    log = dense.step_log()[n:]
    assert len(log) == 2            # the third is still in flight
    for r in log:
        assert r["closed_by"] == "next_step"
        assert "wait_s" not in r and "overlap_s" not in r
    # enter to enter
    assert log[1]["step_s"] == pytest.approx(
        dense._flight[0]["t_enter"] - log[1]["t_enter"], abs=1e-9)


def test_turnaround_is_filled_by_the_following_step(dense):
    n = settle(dense)
    params = dense.init_params()
    params, loss = dense.step(params, TOKENS)
    dense.observe_loss(loss)
    (first,) = dense.step_log()[n:]
    assert "turnaround_s" not in first
    time.sleep(0.02)                # the caller's Python between steps
    params, loss = dense.step(params, TOKENS)
    dense.observe_loss(loss)
    first, second = dense.step_log()[n:]
    assert first["turnaround_s"] >= 0.02 and "turnaround_s" not in second
    # proven done to the next dispatch's return lies inside the next step
    assert second["step_s"] >= first["turnaround_s"]


def test_compiled_and_gc_are_booked_to_their_step():
    trainer = TransformerTrainer(one_device(), TransformerConfig(
        **dict(DENSE, ffn=96)))          # a shape no other test compiled
    params = trainer.init_params()
    for i in range(4):
        if i == 3:
            gc.collect()
        params, loss = trainer.step(params, TOKENS)
        trainer.observe_loss(loss)
    log = trainer.step_log()
    # the ledger acquires the step for the fresh parameters' placement
    # and once more for the step's own outputs', then no more
    assert log[0]["compiled"] == 1 and log[1]["compiled"] <= 1
    assert log[2]["compiled"] == log[3]["compiled"] == 0
    assert log[2]["gc_s"] == 0 < log[3]["gc_s"] < log[3]["step_s"]


# -- spans --------------------------------------------------------------------


def test_the_spans_of_a_step_share_its_trace(dense):
    settle(dense)
    seen = len(TRACER.events())
    params, loss = dense.step(dense.init_params(), TOKENS)
    dense.observe_loss(loss)
    spans = {e["name"]: e["args"] for e in TRACER.events()[seen:]}
    assert set(spans) >= {"train_step", "place_batch", "dispatch",
                          "step_in_flight", "step_wait"}
    assert len({spans[n]["trace_id"] for n in (
        "train_step", "place_batch", "dispatch", "step_in_flight",
        "step_wait")}) == 1
    assert spans["step_in_flight"]["parent_id"] \
        == spans["train_step"]["span_id"]
    assert spans["step_wait"]["parent_id"] \
        == spans["step_in_flight"]["span_id"]
    assert spans["step_wait"]["step"] == spans["step_in_flight"]["step"] \
        == dense.step_log()[-1]["step"]


def test_both_spans_are_in_the_profilers_trace(dense, tmp_path):
    import jax

    from benchmark import stages
    from benchmark import trace as trace_reader
    from tests.test_stage_trace import host_spans

    settle(dense)
    params, loss = dense.step(dense.init_params(), TOKENS)   # warm
    dense.observe_loss(loss)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        params, loss = dense.step(params, TOKENS)
        dense.observe_loss(loss)
    finally:
        jax.profiler.stop_trace()
    xplane = trace_reader.find_xplane(str(tmp_path))
    spans = {s["name"]: s for s in host_spans(xplane)}
    flight, wait, step = (spans[n] for n in (
        "step_in_flight", "step_wait", "train_step"))
    assert flight["span_id"] and wait["span_id"]
    assert flight["parent_id"] == step["span_id"]
    assert wait["parent_id"] == flight["span_id"]
    assert step["t1"] <= flight["t0"] <= wait["t0"]
    assert wait["t1"] <= flight["t1"]
    # as the new metric files read them: seconds by name, and idle time
    # under the shortest span over it
    _devices, program_spans = stages.read_xplane(xplane, {})
    program = stages.reduce({}, program_spans, [])
    for name in ("step_wait", "step_in_flight"):
        metric = json.load(open(os.path.join(
            ROOT, "benchmark", "layer_metrics",
            {"step_wait": "step.wait_ms",
             "step_in_flight": "step.in_flight_ms"}[name] + ".json")))
        assert stages.read_layer_metric(metric["read"], program) \
            == pytest.approx((spans[name]["t1"] - spans[name]["t0"]) / 1e6)
    idle = trace_reader.label_gaps(
        [(flight["t0"], flight["t1"])],
        [(s["name"], s["t0"], s["t1"] - s["t0"]) for s in spans.values()])
    assert idle["step_wait"] == wait["t1"] - wait["t0"]
    assert idle["step_in_flight"] \
        == (flight["t1"] - flight["t0"]) - idle["step_wait"]


@pytest.mark.parametrize("name,cells", [
    ("step.wait_ms", 3), ("step.in_flight_ms", 3),
    ("step.idle_wait_share", 3), ("step.idle_launch_share", 3),
    ("step.idle_place_share", 4), ("step.idle_dispatch_share", 4)])
def test_the_metric_files_read_with_the_groups_that_exist(name, cells):
    from benchmark import stages

    metric = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json")))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert any(group in metric["read"] for group in stages.READ_GROUPS)
    assert len(metric["workloads"]) == cells
    assert set(metric["workloads"]) <= {w["name"]
                                        for w in manifest["workloads"]}
    assert ("train-dense-32k" in metric["workloads"]) == (cells == 4)
    assert metric["moves"] == "train_tok_rate"
    assert name not in {m["name"] for m in manifest["per_layer"]}
    # a program without the span (the parent) gives nothing to read
    empty = stages.reduce({}, [], [])
    assert stages.read_layer_metric(metric["read"], empty) is None


# -- routed models ------------------------------------------------------------


def test_tiles_in_use_is_the_rows_in_use_arithmetic(routed):
    """256 pairs a step on 4 held experts: tiles of 16 rows, 16 + 4 of
    them in the buffers; every expert owns at least one."""
    pairs = 2 * 64 * 2
    by_hand = [0, 16, 17, 100]                  # 1 + 1 + 2 + 7 tiles
    *_, stats = routed.step_opt(*routed.init_state(), TOKENS)
    stats = {"loads": np.array([by_hand + [0, pairs]])}
    routed.observe_experts(stats)               # closes the step with them
    r = routed.step_log()[-1]
    assert r["tiles_in_use"] == [11] and r["pairs_held"] == 133
    assert block_rows(pairs) == 16 and tiles_for(pairs, 4, 16) == 20
    assert REGISTRY.value("mrtpu_moe_tiles_in_use", layer=1) == 11
    assert REGISTRY.value("mrtpu_moe_rows_in_use_share", layer=1) \
        == pytest.approx(11 / 20)
    assert r["load_max_over_mean"] == [pytest.approx(100 / 33.25)]


def test_observe_experts_with_no_step_in_flight_records_nothing(routed):
    n = settle(routed)
    held0 = REGISTRY.sum("mrtpu_moe_pairs_held_total")
    waits0 = counter("mrtpu_trace_spans_total").value(name="step_wait")
    routed.observe_experts({"loads": np.array([[32, 32, 32, 32, 0, 256]])})
    assert len(routed.step_log()) == n and routed._flight is None
    assert counter("mrtpu_trace_spans_total").value(name="step_wait") \
        == waits0
    assert REGISTRY.sum("mrtpu_moe_pairs_held_total") == held0 + 128
    assert REGISTRY.value("mrtpu_moe_tiles_in_use", layer=1) == 8
    assert REGISTRY.value("mrtpu_moe_rows_in_use_share", layer=1) \
        == pytest.approx(8 / 20)
    assert REGISTRY.value("mrtpu_moe_expert_load_max_over_mean",
                          layer=1) == pytest.approx(1.0)


# -- the slow rule ------------------------------------------------------------


def record(step_s=0.5, **fields):
    return dict({"step_s": step_s, "compiled": 0, "gc_s": 0.0,
                 "wait_s": 0.4, "turnaround_s": 0.005}, **fields)


@pytest.mark.parametrize("cause,rec,before", [
    # 0.9 s against a median of 0.5: 0.4 over
    ("compiled", record(0.9, compiled=1, gc_s=0.3, wait_s=0.0), {}),
    ("gc", record(0.9, gc_s=0.21, wait_s=0.0), {"turnaround_s": 0.3}),
    ("host_turnaround", record(0.9, gc_s=0.19, wait_s=0.0),
     {"turnaround_s": 0.21}),
    ("host_overlap", record(0.9, wait_s=0.0009), {"turnaround_s": 0.19}),
    ("device", record(0.9, wait_s=0.8), {}),
    # a step nobody observed has no wait to read
    ("device", {k: v for k, v in record(0.9).items() if k != "wait_s"}, {}),
    (None, record(0.75, compiled=1), {}),         # at 1.5 times: not over
    (None, record(0.4), {})])
def test_slow_cause(cause, rec, before):
    earlier = [record() for _ in range(tf.SLOW_WINDOW)]
    earlier[-1].update(before)
    assert slow_cause(rec, earlier) == cause
    # only the SLOW_WINDOW records before it count ...
    assert slow_cause(rec, [record(9.0)] * 100 + earlier) == cause
    # ... and fewer than SLOW_MIN say nothing
    assert slow_cause(rec, earlier[-(tf.SLOW_MIN - 1):]) is None


def test_a_slow_step_is_booked_once_from_injected_stamps(dense):
    """Stamps handed to the record's own entry points: 40 even steps,
    then one whose wait found the device long done."""
    settle(dense)
    dense._steps.clear()
    booked0 = REGISTRY.sum("mrtpu_train_slow_steps_total",
                           cause="host_overlap")
    t = 1000.0
    dense._last = ({}, t)
    for i in range(41):
        late = i == 40
        rec = {"step": i, "program": "tf_step", "tokens": 128, "t_enter": t,
               "place_s": 0.001, "dispatch_s": 0.001, "compiled": 0,
               "overlap_s": 0.9 if late else 0.1,
               "wait_s": 0.0002 if late else 0.4}
        dense._flight = (rec, TRACER.begin("step_in_flight", step=i),
                         t + 0.002)
        t += 0.002 + rec["overlap_s"] + rec["wait_s"]
        dense._close(t, "observe")
        dense._finish()
    log = dense.step_log()
    assert [r["step"] for r in log if "slow" in r] == [40]
    assert log[40]["slow"] == "host_overlap"
    assert log[40]["step_s"] == pytest.approx(0.9022)
    assert REGISTRY.sum("mrtpu_train_slow_steps_total",
                        cause="host_overlap") == booked0 + 1
    dense._steps.clear()
    dense._last = None


# -- what it costs, what it keeps ---------------------------------------------


def test_the_ring_stays_at_its_bound(dense, monkeypatch):
    assert tf.STEP_LOG_SIZE == dense._steps.maxlen == 4096
    monkeypatch.setattr(tf, "STEP_LOG_SIZE", 8)
    trainer = TransformerTrainer(one_device(), TransformerConfig(**DENSE))
    params = trainer.init_params()
    for _ in range(12):
        params, loss = trainer.step(params, TOKENS)
        trainer.observe_loss(loss)
    assert [r["step"] for r in trainer.step_log()] == list(range(4, 12))


def test_the_gap_side_work_of_a_record_is_under_50_us(dense):
    """Between a step proven done and the next dispatch's return the
    device sits idle: what the record does there is the wait's closing
    stamps and span, ending ``step_in_flight``, and one append.  Timed
    with the wait's opening too (an upper bound), at the median of
    1,000."""
    settle(dense)
    kept = list(dense._steps)
    loss = np.float32(1.0)
    took = []
    for i in range(1000):
        rec = {"step": i, "program": "tf_step", "tokens": 128,
               "t_enter": time.monotonic(), "compiled": 0}
        dense._flight = (rec, TRACER.begin("step_in_flight", step=i),
                         time.monotonic())
        t0 = time.perf_counter()
        dense.observe_loss(loss)
        took.append(time.perf_counter() - t0)
        dense._finish()                  # deferred: not the gap's
    dense._steps.clear()
    dense._steps.extend(kept)
    assert statistics.median(took) < 50e-6, statistics.median(took)


def test_count_step_computes_its_constants_once_a_shape(monkeypatch):
    calls = []
    kept = tf.remat_kept_bytes
    monkeypatch.setattr(tf, "remat_kept_bytes", lambda *a: (
        calls.append(a[2:]), kept(*a))[1])
    trainer = TransformerTrainer(one_device(), TransformerConfig(**DENSE))
    apps0 = REGISTRY.sum("mrtpu_train_layer_applications_total")
    attn0 = REGISTRY.sum("mrtpu_train_operator_applications_total",
                         operator="attn")
    params = trainer.init_params()
    short = TOKENS[:, :33]
    for tokens in (TOKENS, TOKENS, short, short, TOKENS):
        params, loss = trainer.step(params, tokens)
        trainer.observe_loss(loss)
    assert calls == [(2, 64), (2, 32), (2, 64)]
    assert REGISTRY.value("mrtpu_train_loss_kept_bytes",
                          program="tf_step") == 2 * 64 * 4
    assert REGISTRY.sum("mrtpu_train_layer_applications_total") \
        == apps0 + 5 * 2
    assert REGISTRY.sum("mrtpu_train_operator_applications_total",
                        operator="attn") == attn0 + 5 * 2
    assert [r["tokens"] for r in trainer.step_log()] \
        == [128, 128, 64, 64, 128]


# -- memory -------------------------------------------------------------------


class FakeDevice:
    def __init__(self, id, **stats):
        self.id, self.platform, self.stats = id, "fake", stats

    def memory_stats(self):
        return self.stats


def test_mem_is_the_fullest_devices_sample_in_flight(looped):
    settle(looped)
    looped._devices = [
        FakeDevice(0, bytes_in_use=900, peak_bytes_in_use=950,
                   bytes_reserved=0, peak_bytes_reserved=10,
                   bytes_limit=4000),
        FakeDevice(1, bytes_in_use=500, peak_bytes_in_use=600,
                   bytes_reserved=800, peak_bytes_reserved=850,
                   bytes_limit=4000)]
    try:
        params, opt_state = looped.init_state()
        assert looped.state_mem == {
            "phase": "state", "device": "1", "bytes_in_use": 500,
            "peak_bytes_in_use": 600, "bytes_reserved": 800,
            "peak_bytes_reserved": 850}
        params, opt_state, _, stats = looped.step_opt(params, opt_state,
                                                      TOKENS)
        looped.observe_passes(stats)
    finally:
        looped._devices = list(looped.mesh.local_devices)
    mem = looped.step_log()[-1]["mem"]
    assert mem == dict(looped.state_mem, phase="in_flight")
    assert REGISTRY.value("mrtpu_device_memory_bytes", device="1",
                          stat="peak_bytes_reserved",
                          source="measured") == 850
    # the CPU reports nothing: no mem
    *_, stats = looped.step_opt(params, opt_state, TOKENS)
    looped.observe_passes(stats)
    assert "mem" not in looped.step_log()[-1]


def test_a_state_made_under_jit_samples_nothing(looped):
    import jax

    looped.state_mem = "untouched"
    jax.jit(looped.init_opt_state)(looped.init_params())
    assert looped.state_mem == "untouched"
    looped.state_mem = None


# -- benchmark/step_report.py -------------------------------------------------


def test_step_report_on_a_tiny_cell():
    import jax

    from benchmark import step_report
    from benchmark.run import load_json

    bench = os.path.join(ROOT, "benchmark")
    config = load_json(bench, "configs", "dense-168m-32k.json")
    config["model"].update(vocab=256, embed=64, n_layers=2, n_heads=2,
                           head_dim=32, ffn=128, loss_block=64)
    config["train"].update(seq_len=128, reference_block=32)
    cell = load_json(bench, "workloads", "train-dense-32k.json")
    out = step_report.report(cell, config, seed=2**31 + 37, seconds=0.5,
                             devices=jax.devices()[:1])
    json.dumps(out)
    steps, summary = out["steps"], out["summary"]
    warm = int(cell["traffic"]["warm_units"])
    # the dense kind reads the loss itself: the next step closes a record,
    # so the window's last step is still in flight
    assert len(steps) == warm + summary["steps"] >= warm + 1
    assert [r["step"] for r in steps] == list(range(len(steps)))
    assert {r["closed_by"] for r in steps} == {"next_step"}
    assert summary["failed"] == 0 and not summary["profiled"]
    # steps of a millisecond on a shared CPU: some may well read slow
    assert summary["compiled"] == 0 and "compiled" not in summary["slow"]
    assert steps[0]["compiled"] >= 1          # warm-up compiled the step
    assert summary["train_tok_rate"] > 0
    assert summary["tokens_over_step_s"] > 0
    assert set(summary["step_s"]) == {"median", "p99"}
    assert summary["step_s"]["median"] <= summary["step_s"]["p99"]
    assert summary["wait_s"] == {}            # nobody observed
    assert set(summary["mem"]) == {"cell_made", "warmed", "window_done",
                                   "in_flight"}
    with open(os.path.join(bench, "step_report.py")) as f:
        assert len(f.readlines()) < 100
