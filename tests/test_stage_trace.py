"""Program spans and stage scopes on the profiler's clock (PR 25).

(a) the tracer's bridge into ``jax.profiler``; (b)/(c) the span trees of
a tiny ``count_bytes`` and a tiny ``TransformerTrainer.step`` in the
``.xplane.pb``; (d) every stage scope in the ledger's stage map, one case
per scope; (e) ``benchmark/stages.py`` on a hand-made event list; (f)
``benchmark/kernel_work.py`` at the admitted cell's sizes; (g) the
trainer kind's traced run through ``run.measure``; (h) the join of a real
CPU trace with the stage map.  CPU, tiny sizes: no time here is a device
number.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from mapreduce_tpu.obs.compile import LEDGER, hlo_op_paths
from mapreduce_tpu.obs.trace import TRACER, Tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

WAVE_SCOPES = ["wave.map", "wave.combine", "wave.append", "wave.local",
               "wave.exchange", "wave.fold", "sur.sort", "sur.segreduce",
               "sur.compact"]
TF_SCOPES = ["tf.embed", "tf.attn_proj", "tf.flash", "tf.ffn", "tf.loss",
             "tf.update"]
#: scopes only a looped configuration's program has (PR 28;
#: tests/test_looplm.py finds each in that program's stage map)
LOOP_SCOPES = ["tf.rope", "tf.exit_gate", "tf.pass_loop",
               "tf.final_norm"]
#: scopes only a program with layers of several kinds and routed experts
#: has (PR 33; tests/test_lfm2moe.py finds each in its stage map)
MOE_SCOPES = ["tf.conv_op", "tf.qk_norm", "tf.moe_route", "tf.moe_dispatch",
              "tf.moe_experts", "tf.moe_combine"]
#: scopes only a program with latent attention, a shared expert, a
#: prediction block and a bias that follows the loads has (PR 39;
#: tests/test_glm47flash.py finds each in its stage map)
GLM_SCOPES = ["tf.mla_down", "tf.mla_up", "tf.shared_expert", "tf.mtp",
              "tf.bias_update"]


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def host_spans(xplane):
    """The host plane's events that carry a ``span_id`` stat, as dicts."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "span_id" in stats:
                    out.append(dict(stats, name=e.name, t0=e.start_ns,
                                    t1=e.start_ns + e.duration_ns))
    return out


def children(spans, parent):
    return [s for s in spans if s.get("parent_id") == parent["span_id"]]


def inside(outer, inner):
    return outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """ONE profiler session over: bridge spans of a private tracer, a
    tiny ``count_bytes`` (three waves), tiny trainer steps (flash under
    the Pallas interpreter, the ring path, the optax path)."""
    import jax

    from benchmark import trace as trace_reader
    from mapreduce_tpu.engine import DeviceWordCount
    from mapreduce_tpu.models.transformer import (TransformerConfig,
                                                  TransformerTrainer)
    from mapreduce_tpu.parallel import make_mesh

    LEDGER.reset()       # the stage maps below are these programs' alone
    mesh = make_mesh(devices=jax.devices()[:1])
    model = dict(vocab=64, embed=32, n_layers=2, n_heads=2, head_dim=16,
                 ffn=64, loss_block=16)
    tokens = np.random.default_rng(0).integers(0, 64, size=(1, 65),
                                               dtype=np.int32)
    flash = TransformerTrainer(mesh, TransformerConfig(**model, flash=True))
    ring = TransformerTrainer(mesh, TransformerConfig(**model, flash=False))
    adam = TransformerTrainer(mesh, TransformerConfig(**model, flash=False),
                              optimizer="adamw")
    wc = DeviceWordCount(mesh, chunk_len=2048)
    text = b"the quick brown fox jumps over the lazy dog " * 300
    # warm every program: the traced calls below compile nothing
    params = flash.step(flash.init_params(), tokens)[0]
    ring.step(ring.init_params(), tokens)
    adam.step_opt(*adam.init_state(), tokens)
    wc.count_bytes(text, waves=3)

    tracer = Tracer()
    tdir = str(tmp_path_factory.mktemp("stage_trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with tracer.span("lexical", n=3, note="x", skipped=[1]) as lex:
            wave0 = tracer.begin("detached", wave=0)
            wave1 = tracer.begin("detached", wave=1)   # overlaps wave0
            late = tracer.begin("backdated", start=time.monotonic() - 1.0)
            tracer.end(wave0)
            tracer.end(wave1)
            tracer.end(late)
        tracer.record("elapsed", time.monotonic() - 1.0, time.monotonic())
        counts = wc.count_bytes(text, waves=3)
        params, loss = flash.step(params, tokens)
        float(loss)
    finally:
        jax.profiler.stop_trace()
    xplane = trace_reader.find_xplane(tdir)
    return {"xplane": xplane, "spans": host_spans(xplane),
            "tracer": tracer, "lexical": lex, "counts": counts,
            "wave": LEDGER.stage_map("wave"),
            "tf_step": LEDGER.stage_map("tf_step"),
            "tf_step_opt": LEDGER.stage_map("tf_step_opt")}


# -- (a) the bridge ----------------------------------------------------------


def test_bridge_writes_spans_into_the_profilers_trace(traced):
    by_name = {}
    for s in traced["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    (lex,) = by_name["lexical"]
    assert lex["span_id"] == traced["lexical"].span_id
    assert "parent_id" not in lex                 # a root span
    # scalar args ride along as stats; a list does not
    assert lex["n"] == 3 and lex["note"] == "x" and "skipped" not in lex
    waves = sorted(by_name["detached"], key=lambda s: s["wave"])
    assert [w["wave"] for w in waves] == [0, 1]
    for w in waves:
        assert w["parent_id"] == lex["span_id"] and inside(lex, w)
    # entered in one place, left in another, overlapping a sibling:
    # both recorded whole
    assert waves[1]["t0"] < waves[0]["t1"] <= waves[1]["t1"]
    # a backdated start cannot be bridged: ring only
    assert "backdated" not in by_name and "elapsed" not in by_name
    ring = [e["name"] for e in traced["tracer"].events()]
    assert sorted(ring) == ["backdated", "detached", "detached", "elapsed",
                            "lexical"]


def test_ring_is_what_it_was_with_no_trace_running():
    tr = Tracer()
    with tr.span("outer", k=1) as outer:
        child = tr.begin("child", wave=2)
        assert child._annotation is not None    # jax is loaded here
        tr.end(child, outcome="ok")
        assert child._annotation is None
    events = tr.events()
    assert [e["name"] for e in events] == ["child", "outer"]
    for e in events:
        assert set(e) == {"name", "ph", "ts", "dur", "pid", "tid", "cat",
                          "args"}
        assert e["ph"] == "X" and e["cat"] == "mapreduce_tpu"
    assert events[0]["args"] == {
        "trace_id": outer.trace_id, "span_id": child.span_id,
        "parent_id": outer.span_id, "wave": 2, "outcome": "ok"}
    assert events[1]["args"] == {
        "trace_id": outer.trace_id, "span_id": outer.span_id,
        "parent_id": None, "k": 1}


# -- (b), (c) the span trees -------------------------------------------------


def test_count_bytes_span_tree_in_the_xplane(traced):
    spans = traced["spans"]
    assert traced["counts"][b"fox"] == 300
    (job,) = [s for s in spans if s["name"] == "wordcount"]
    kids = children(spans, job)
    assert sorted(k["name"] for k in kids) == [
        "device_run", "materialize", "readback", "split"]
    assert all(inside(job, k) for k in kids)
    by_name = {k["name"]: k for k in kids}
    assert by_name["readback"]["stage"] == "result"
    assert by_name["split"]["t1"] <= by_name["device_run"]["t0"]
    assert by_name["readback"]["t1"] <= by_name["materialize"]["t0"]
    waves = children(spans, by_name["device_run"])
    assert sorted(w["wave"] for w in waves) == [0, 1, 2]
    for w in waves:
        assert w["name"] == "wave" and inside(by_name["device_run"], w)
        stages_ = children(spans, w)
        assert sorted(s["name"] for s in stages_) == [
            "compute", "readback", "upload"]
        assert all(inside(w, s) for s in stages_)
        (rb,) = [s for s in stages_ if s["name"] == "readback"]
        assert rb["kind"] == "overflow"


def test_trainer_step_span_tree_in_the_xplane(traced):
    spans = traced["spans"]
    (step,) = [s for s in spans if s["name"] == "train_step"]
    kids = sorted(children(spans, step), key=lambda s: s["t0"])
    assert [k["name"] for k in kids] == ["place_batch", "dispatch"]
    assert all(inside(step, k) for k in kids)
    assert kids[0]["t1"] <= kids[1]["t0"]
    ring = [e["name"] for e in TRACER.events()]
    assert {"train_step", "place_batch", "dispatch", "wordcount", "split",
            "materialize"} <= set(ring)


# -- (d) every scope of the table, by name -----------------------------------


@pytest.mark.parametrize("program,scope", [
    *[("wave", s) for s in WAVE_SCOPES],
    *[("tf_step", s) for s in TF_SCOPES],
    ("tf_step", "tf.ring"), ("tf_step_opt", "tf.update")])
def test_stage_map_holds_the_scope(traced, program, scope):
    from benchmark import stages

    (paths,) = traced[program].values()          # one HLO module a program
    assert any(scope in stages.stage_chain(p) for p in paths.values()), (
        f"no instruction of {program} carries {scope}")


def test_stage_map_nests_and_survives_the_backward_pass(traced):
    from benchmark import stages

    chains = {stages.stage_chain(p)
              for p in traced["wave"]["jit_per_device"].values()}
    assert ("wave.local", "sur.compact") in chains
    assert ("wave.combine", "sur.sort") in chains
    paths = traced["tf_step"]["jit_train_step"].values()
    assert any("transpose(" in p and stages.stage_chain(p) == ("tf.ffn",)
               for p in paths)
    assert LEDGER.stage_map("no_such_program") == {}


def test_an_instruction_without_a_path_takes_its_callers():
    text = """HloModule jit_f, is_scheduled=true

%body.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]) parameter(0)
  %fusion.2 = f32[8] fusion(%arg), kind=kLoop, calls=%fused.3
  ROOT %tuple.4 = (s32[], f32[8]) tuple(%arg, %fusion.2), metadata={op_name="jit(f)/tf.ffn/mul"}
}

%cond.5 (arg.1: (s32[], f32[8])) -> pred[] {
  ROOT %lt = pred[] constant(true)
}

ENTRY %main.6 (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0)
  %copy.7 = f32[8] copy(%x)
  %while.8 = (s32[], f32[8]) while(%copy.7), condition=%cond.5, body=%body.1, metadata={op_name="jit(f)/transpose(jvp(tf.loss))/while" source_file="a.py"}
  ROOT %gte = f32[8] get-tuple-element(%while.8), index=1, metadata={op_name="jit(f)/tf.update/sub"}
}
"""
    paths = hlo_op_paths(text)
    assert paths["while.8"] == "jit(f)/transpose(jvp(tf.loss))/while"
    assert paths["fusion.2"] == paths["while.8"]     # compiler-made, in the loop
    assert paths["lt"] == paths["while.8"]
    assert paths["tuple.4"] == "jit(f)/tf.ffn/mul"    # its own path wins
    assert "copy.7" not in paths and "x" not in paths  # entry: no caller


# -- (e) the reducer on a hand-made event list -------------------------------


def test_stage_reduction_by_hand():
    from benchmark import stages

    t = load(BENCH, "tests", "stage_events.json")
    ops = [((name, tuple(chain)), s, d) for name, chain, s, d in t["ops"]]
    spans = [tuple(s) for s in t["program_spans"]]
    program = stages.reduce({"/device:TPU:0": ops}, spans,
                            [tuple(m) for m in t["marks"]])
    want = t["expect"]
    assert program["window_ns"] == want["window_ns"]
    assert program["busy_ns"] == want["busy_ns"]
    by_stage = stages.by_stage(program["by_key"])
    assert by_stage == want["by_stage"]            # innermost scope wins
    assert sum(by_stage.values()) == program["busy_ns"]
    for stage, ns in want["stage_ns"].items():
        assert stages.stage_ns(program["by_key"], stage) == ns, stage
    assert stages.stage_ns(program["by_key"], "tf.flash",
                           ["flash_fwd"]) == want["glue_ns"]
    for instr, n in want["calls"].items():
        assert program["calls"][instr] == n
    assert "before" not in program["calls"]
    assert program["idle_by_span"] == want["idle_by_span"]

    read = stages.read_layer_metric
    assert read({"stage": "tf.loss", "over": "busy"}, program) == \
        pytest.approx(100 * 200 / 600)
    assert read({"stage": "(unscoped)", "over": "busy"}, program) == \
        pytest.approx(100 * 100 / 600)
    assert read({"idle_under": "split", "over": "window"}, program) == \
        pytest.approx(10.0)
    assert read({"idle_under": "upload", "over": "window"}, program) is None
    assert read({"program_span": "train_step", "scale": 1000},
                program) == pytest.approx(want["train_step_ms"])
    assert read({"program_span": "dispatch"}, program) is None
    lines = stages.table(program)
    assert any("tf.loss" in ln and "33.33%" in ln for ln in lines)
    assert "copy.2" in lines[-1]

    # the busiest of two chips waits for the other: the one with LEAST
    # busy time is read, as trace.summarize does
    two = stages.reduce({"/device:TPU:0": ops, "/device:TPU:1": ops[:2]},
                        spans, [tuple(m) for m in t["marks"]])
    assert two["busy_ns"] == 400

    # a program that names no stage and writes no span: nothing to read
    bare = stages.reduce(
        {"/device:TPU:0": [((name, ()), s, d) for (name, _), s, d in ops]},
        [], [tuple(m) for m in t["marks"]])
    for group in ({"stage": "tf.loss", "over": "busy"},
                  {"stage": "(unscoped)", "over": "busy"},
                  {"kernel_roofline": "flash_fwd"},
                  {"idle_under": "split", "over": "window"},
                  {"program_span": "train_step"}):
        assert read(group, bare) is None
    assert stages.reduce({}, spans, [])["busy_ns"] == 0


def test_kernel_roofline_from_the_hand_made_trace(capsys):
    from benchmark import kernel_work, stages

    t = load(BENCH, "tests", "stage_events.json")
    ops = [((name, tuple(chain)), s, d) for name, chain, s, d in t["ops"]]
    program = stages.reduce({"/device:TPU:0": ops}, [],
                            [tuple(m) for m in t["marks"]])
    config = load(BENCH, "configs", "dense-168m-32k.json")
    program.update(config=config, device_kind="TPU v5 lite")
    work = kernel_work.kernel_call_work("flash_fwd", config["model"], 1,
                                        32768)
    least = work["flops"] / 197e12               # one call, 100 ns traced
    got = stages.read_layer_metric({"kernel_roofline": "flash_fwd"}, program)
    assert got == pytest.approx(100.0 * least / 100e-9)
    assert "compute-bound" in capsys.readouterr().err
    # no such kernel in the trace, no rule for the kernel, no peak: nothing
    assert stages.read_layer_metric({"kernel_roofline": "flash_dq"},
                                    program) is None
    assert stages.read_layer_metric({"kernel_roofline": "while"},
                                    program) is None
    program["device_kind"] = "cpu"
    assert stages.read_layer_metric({"kernel_roofline": "flash_fwd"},
                                    program) is None


# -- (f) required work of the kernels ----------------------------------------


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_kernel_work_at_the_cells_sizes(kernel):
    from benchmark import flops, kernel_work

    config = load(BENCH, "configs", "dense-168m-32k.json")
    model, train = config["model"], config["train"]
    B, T = train["batch"], train["seq_len"]
    work = kernel_work.kernel_call_work(kernel, model, B, T)
    per_step = work["flops"] * model["n_layers"]
    assert per_step == pytest.approx(1.76e13, rel=0.005)
    # the three kernels are the attention term of the step's FLOPs
    attention = (flops.train_step_flops(model, B, T)
                 - 6.0 * flops.matmul_params(model) * B * T)
    assert 3 * per_step == attention
    # at 32K the kernels are compute-bound by two orders
    peak = kernel_work.peaks("TPU v5 lite")
    seconds, bound = kernel_work.least_seconds(work, peak)
    assert bound == "compute" and seconds == work["flops"] / 197e12
    assert work["bytes"] / peak["hbm_bytes_per_s"] < seconds / 10
    assert kernel_work.least_seconds(
        {"flops": 1.0, "bytes": 1e9}, peak)[1] == "memory"
    assert kernel_work.kernel_call_work("tokenize", model, B, T) is None
    assert kernel_work.peaks("cpu") is None


# -- (g) the stage report: traced trainer run on the CPU ----------------------


def tiny_trainer_cell():
    config = load(BENCH, "configs", "dense-168m-32k.json")
    config["model"].update(vocab=256, embed=64, n_layers=2, n_heads=2,
                           head_dim=32, ffn=128, loss_block=64)
    config["train"].update(seq_len=128, reference_block=32)
    return load(BENCH, "workloads", "train-dense-32k.json"), config


def test_stage_report_prints_dispatch_ms_and_no_trace_metric(capsys):
    import jax

    from benchmark import stage_report

    cell, config = tiny_trainer_cell()
    result = stage_report.report(cell, config, seed=2**31 + 25,
                                 devices=jax.devices()[:1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == cell["traffic"]["trace_units"]
    # no TPU plane: the host-clock and program-span metrics, nothing that
    # reads the device trace, and no breakdown
    assert set(result["metrics"]) == {"train.step_ms", "train.dispatch_ms"}
    assert result["metrics"]["train.dispatch_ms"]["unit"] == "ms"
    assert 0 < result["metrics"]["train.dispatch_ms"]["value"] \
        <= result["metrics"]["train.step_ms"]["value"]
    assert "breakdown" not in result
    assert "stage table" not in capsys.readouterr().err


def test_harness_traced_run_is_what_it_was_with_the_spans_on():
    """``run.py`` is not this PR's to edit: its traced run of the admitted
    cell reads the manifest's metrics from a program that now writes its
    spans into the same trace, and none of the waiting ones."""
    import jax

    from benchmark import run

    cell, config = tiny_trainer_cell()
    result = run.measure(load(ROOT, "BENCHMARK.json"), cell, config,
                         seed=2**31 + 25, seconds=0.5, traced=True,
                         devices=jax.devices()[:1],
                         t_start=time.monotonic())
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train.step_ms"}


def test_stage_report_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "stage_report.py"),
         "--workload", "train-dense-32k", "--seed", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=90)
    assert proc.returncode == 1
    assert proc.stdout == "" and "needs 1 TPU chip" in proc.stderr


RUN_PY_READS = ("timings", "unit_seconds", "derived", "trace",
                "trace_events")


def metric_file_names():
    return sorted(os.listdir(os.path.join(BENCH, "layer_metrics")))


@pytest.mark.parametrize("name", metric_file_names())
def test_metric_file_has_a_reader_and_waits_if_run_py_has_none(name):
    """A file ``run.py`` cannot read is in no manifest (its traced run
    would raise), names a scope the programs have, and is found by the
    stage report through its own ``workloads``."""
    from benchmark import stage_report, stages

    listed = {m["name"] for path in (
        [os.path.join(ROOT, "BENCHMARK.json")]
        + [os.path.join(BENCH, "parked", p)
           for p in os.listdir(os.path.join(BENCH, "parked"))])
        for m in load(path)["per_layer"]}
    d = load(BENCH, "layer_metrics", name)
    mine = [g for g in stages.READ_GROUPS if g in d["read"]]
    if not mine:
        assert any(g in d["read"] for g in RUN_PY_READS), d["read"]
        return
    assert d["name"] not in listed
    assert d["source"] == ("program_span" if mine == ["program_span"]
                           else "device_trace")
    if "stage" in d["read"] and d["read"]["stage"] != stages.UNSCOPED:
        assert d["read"]["stage"] in (WAVE_SCOPES + TF_SCOPES + LOOP_SCOPES
                                      + MOE_SCOPES + GLM_SCOPES)
    for cell in d["workloads"]:
        assert d in stage_report.metric_files(cell)


def test_the_waiting_metric_files_are_the_issues():
    from benchmark import stages

    waiting = {n[:-5] for n in metric_file_names() if any(
        g in load(BENCH, "layer_metrics", n)["read"]
        for g in stages.READ_GROUPS)}
    assert {n for n in waiting if n.startswith("train.")} == {
        "train.flash_fwd_roofline", "train.flash_dq_roofline",
        "train.flash_dkv_roofline", "train.flash_glue_share",
        "train.attn_proj_share", "train.ffn_share", "train.loss_share",
        "train.update_share", "train.unscoped_share", "train.dispatch_ms"}
    assert {n for n in waiting if n.startswith("wc.")} == {
        "wc.map_share", "wc.combine_share", "wc.local_share",
        "wc.exchange_share", "wc.fold_share", "wc.sort_share",
        "wc.segreduce_share", "wc.compact_share", "wc.unscoped_share",
        "wc.idle_split_share", "wc.idle_materialize_share"}
    assert {n for n in waiting if n.startswith("moe.")} == {
        "moe.route_share", "moe.dispatch_share", "moe.experts_share",
        "moe.combine_share", "moe.conv_op_share", "moe.qk_norm_share",
        "moe.attn_proj_share", "moe.ffn_share", "moe.rope_share",
        "moe.loss_share", "moe.update_share", "moe.unscoped_share",
        "moe.dispatch_ms", "moe.gmm_roofline"}
    assert {n for n in waiting if n.startswith("glm.")} == {
        "glm.mla_down_share", "glm.mla_up_share", "glm.shared_expert_share",
        "glm.mtp_share", "glm.bias_update_share", "glm.flash_fwd_roofline",
        "glm.flash_dkv_roofline"}


# -- (h) the join, end to end, on the CPU's own trace ------------------------


def test_real_trace_joins_with_the_stage_map(traced):
    from benchmark import stages

    devices, spans = stages.read_xplane(
        traced["xplane"], stages.ledger_paths(), host_ops=True)
    assert {s[0] for s in spans} >= {"wordcount", "split", "device_run",
                                     "wave", "materialize", "train_step"}
    (ops,) = devices.values()
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    program = stages.reduce(devices, spans, [(lo, hi)])
    by_stage = stages.by_stage(program["by_key"])
    assert sum(by_stage.values()) == program["busy_ns"] > 0
    for stage in ("tf.ffn", "tf.loss", "tf.flash", "sur.compact",
                  "sur.sort", "wave.map"):
        assert by_stage.get(stage, 0) > 0, (stage, by_stage)
    assert stages.stage_ns(program["by_key"], "wave.local") > 0
    assert stages.stage_ns(program["by_key"], "wave.local") >= sum(
        ns for (_, chain), ns in program["by_key"].items()
        if chain[:1] == ("wave.local",) and chain[-1] == "sur.compact")
    # without host_ops a CPU trace has no device: the harness reads none
    assert stages.read_xplane(traced["xplane"], {})[0] == {}
