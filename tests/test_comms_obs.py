"""Exchange & dataflow observability (obs/comms) test suite.

The contract under test, end to end:

* the DEVICE traffic matrix is bit-equal to a host recompute from the
  wave's input records — row sums = records each device sent, column
  sums = records each partition received — across multi-wave runs,
  capacity-retry runs, and the wordcount plane (where the host twin
  re-derives per-device-per-wave unique words and routes them by the
  host hash);
* on a collision-free workload the column sums equal the final
  ``n_live`` per device (nothing deduped across sources/waves);
* the topology model classifies links and honours env bandwidth
  overrides; the modeled exchange seconds stay labelled analytic;
* ``cli diagnose`` names the hot destination device from the matrix,
  falls back to matrix recv totals for the skew check when partition
  gauges are absent (and says so), and reports the upload/compute
  overlap + critical path from the merged timeline's spans;
* the comms snapshot reaches /statusz and rides profile bundles as a
  strictly-validated ``comms.json`` (corrupt docs are refused on load).
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from mapreduce_tpu.engine import DeviceEngine, DeviceWordCount, EngineConfig
from mapreduce_tpu.obs import comms as comms_mod
from mapreduce_tpu.obs.analysis import diagnose, render_diagnosis
from mapreduce_tpu.obs.metrics import REGISTRY
from mapreduce_tpu.parallel import make_mesh
from mapreduce_tpu.parallel.mesh import (
    LINK_CLASSES, device_link_matrix, link_class, link_peaks)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


# -- pure interval arithmetic ------------------------------------------------


def test_overlap_fraction_pure_math():
    # upload [0,2] vs busy [1,3]: 1s of the 2s upload overlapped
    assert comms_mod.overlap_fraction([(0, 2)], [(1, 3)]) == 0.5
    # fully hidden
    assert comms_mod.overlap_fraction([(1, 2)], [(0, 3)]) == 1.0
    # disjoint
    assert comms_mod.overlap_fraction([(0, 1)], [(2, 3)]) == 0.0
    # no upload at all = the feeder hid everything
    assert comms_mod.overlap_fraction([], [(0, 1)]) == 1.0
    # overlapping upload intervals must not double-count (union, not sum)
    assert comms_mod.overlap_fraction([(0, 2), (1, 2)], [(0, 2)]) == 1.0


def test_matrix_stats_rollups():
    st = comms_mod.matrix_stats([[1, 0], [1, 6]])
    assert st["records"] == 8
    assert st["row_sums"] == [1, 7] and st["col_sums"] == [2, 6]
    assert st["hot_dst"] == 1 and st["hot_dst_share"] == 0.75
    assert st["imbalance_recv"] == pytest.approx(6 / 4.0)
    assert st["imbalance_send"] == pytest.approx(7 / 4.0)
    # empty matrix degrades to balanced, not a crash
    assert comms_mod.matrix_stats([[0]])["imbalance_recv"] == 1.0


# -- topology model ----------------------------------------------------------


class _FakeDev:
    def __init__(self, id, platform="tpu", slice_index=None):
        self.id = id
        self.platform = platform
        if slice_index is not None:
            self.slice_index = slice_index


def test_link_class_taxonomy():
    a = _FakeDev(0, slice_index=0)
    b = _FakeDev(1, slice_index=0)
    c = _FakeDev(2, slice_index=1)
    cpu0, cpu1 = _FakeDev(3, platform="cpu"), _FakeDev(4, platform="cpu")
    assert link_class(a, a) == "self"
    assert link_class(a, b) == "ici"
    assert link_class(a, c) == "dcn"
    assert link_class(cpu0, cpu1) == "host"
    m = device_link_matrix([a, b, c])
    assert [row[i] for i, row in enumerate(m)] == ["self"] * 3
    assert m[0][2] == "dcn" and m[0][1] == "ici"


def test_link_peaks_env_override(monkeypatch):
    base = link_peaks()
    assert base["peak_source"] == "datasheet"
    assert set(LINK_CLASSES) <= set(base)
    monkeypatch.setenv("MAPREDUCE_TPU_PEAK_ICI_BYTES_PER_S", "1e6")
    over = link_peaks()
    assert over["ici"] == 1e6
    assert over["peak_source"] == "env:ici"
    assert over["dcn"] == base["dcn"]  # only the named class moves


def test_modeled_exchange_seconds_analytic(monkeypatch):
    monkeypatch.setenv("MAPREDUCE_TPU_PEAK_ICI_BYTES_PER_S", "1e6")
    model = comms_mod.modeled_exchange_seconds(
        {"ici": 2_000_000, "self": 10}, n_dev=2)
    # 2MB over 2 devices x 1MB/s = 1s, and ici is the bottleneck
    assert model["seconds_by_link"]["ici"] == pytest.approx(1.0)
    assert model["bottleneck_link"] == "ici"
    assert model["modeled_exchange_s"] == pytest.approx(1.0)
    assert model["source"] == "analytic"


# -- the device matrix vs host recompute -------------------------------------


def _records_map_fn(chunk, chunk_index, cfg):
    k1 = (chunk % 23).astype(jnp.uint32)
    k2 = (chunk % 5).astype(jnp.uint32)
    keys = jnp.stack([k1, k2], axis=-1)
    vals = (chunk % 101).astype(jnp.int32) + 1
    pay = (k1 * 7 + k2).astype(jnp.int32)[:, None]
    valid = (chunk % 7) != 0
    return keys, vals, pay, valid, jnp.int32(0)


def _host_records_matrix(chunks, n_dev, waves):
    """Host twin of the engine's matrix for _records_map_fn: per wave,
    per device, dedupe the block's valid (k1, k2) keys — the local
    reduce — and route each unique by k1 % P."""
    S = chunks.shape[0]
    k = -(-S // (waves * n_dev))
    rpw = k * n_dev
    m = np.zeros((n_dev, n_dev), dtype=np.int64)
    for w in range(-(-S // rpw)):
        for d in range(n_dev):
            rows = chunks[w * rpw + d * k:
                          min(w * rpw + (d + 1) * k, S)].reshape(-1)
            uniq = {(int(r % 23), int(r % 5)) for r in rows
                    if r % 7 != 0}
            for k1, _k2 in uniq:
                m[d, k1 % n_dev] += 1
    return m


def test_matrix_bit_equal_to_host_recompute_multiwave(mesh):
    n_dev = mesh.shape["data"]
    rng = np.random.default_rng(3)
    chunks = rng.integers(0, 1 << 14, size=(3 * n_dev * 2, 32)) \
        .astype(np.int32)
    cfg = EngineConfig(local_capacity=256, exchange_capacity=64,
                       out_capacity=256, reduce_op="sum")
    tm = {}
    res = DeviceEngine(mesh, _records_map_fn, cfg).run(
        chunks, timings=tm, waves=3, max_retries=0)
    assert res.overflow == 0
    got = np.asarray(tm["exchange"]["matrix"])
    want = _host_records_matrix(chunks, n_dev, waves=3)
    assert np.array_equal(got, want)
    assert tm["exchange_records"] == int(want.sum())
    assert (got.sum(axis=1) == np.asarray(
        tm["exchange"]["row_sums"])).all()
    assert (got.sum(axis=0) == np.asarray(
        tm["exchange"]["col_sums"])).all()


def _unique_keys_map_fn(chunk, chunk_index, cfg):
    """Globally-unique keys (the chunk VALUES are globally unique row
    ids): nothing ever dedupes across sources or waves, so received
    records per partition == final n_live per partition."""
    k1 = chunk.astype(jnp.uint32)
    keys = jnp.stack([k1, k1 + 1], axis=-1)
    vals = jnp.ones_like(chunk, dtype=jnp.int32)
    pay = chunk.astype(jnp.int32)[:, None]
    valid = jnp.ones(chunk.shape[0], dtype=bool)
    return keys, vals, pay, valid, jnp.int32(0)


def test_matrix_col_sums_equal_n_live_collision_free(mesh):
    n_dev = mesh.shape["data"]
    S, R = 2 * n_dev * 2, 16
    chunks = np.arange(S * R, dtype=np.int32).reshape(S, R)
    cfg = EngineConfig(local_capacity=1 << 10, exchange_capacity=1 << 8,
                       out_capacity=1 << 10, reduce_op="sum")
    tm = {}
    res = DeviceEngine(mesh, _unique_keys_map_fn, cfg).run(
        chunks, timings=tm, waves=2, max_retries=0)
    assert res.overflow == 0
    got = np.asarray(tm["exchange"]["matrix"])
    n_live = res.valid.sum(axis=1)
    # every record is globally unique: received == surviving uniques
    assert (got.sum(axis=0) == n_live).all(), (got.sum(axis=0), n_live)
    # and every record was sent exactly once: row sums == emitted rows
    assert got.sum() == S * R


def test_wordcount_matrix_host_recompute_and_retry(mesh):
    data = (b"alpha beta gamma delta epsilon zeta hotword hotword " * 300)
    wc = DeviceWordCount(
        mesh, chunk_len=1024,
        config=EngineConfig(local_capacity=1 << 12,
                            exchange_capacity=1 << 10,
                            out_capacity=1 << 12, combine_in_scan=True))
    tm = {}
    counts = wc.count_bytes(data, timings=tm, waves=3)
    want = wc.host_exchange_matrix(data, waves=3)
    assert np.array_equal(np.asarray(tm["exchange"]["matrix"]), want)

    # capacity-retry run: absurd capacities overflow, converge, and the
    # final attempt's matrix equals the SAME untruncated host recompute
    tiny = DeviceWordCount(
        mesh, chunk_len=1024,
        config=EngineConfig(local_capacity=4, exchange_capacity=2,
                            out_capacity=4, combine_in_scan=True))
    tm2 = {}
    counts2 = tiny.count_bytes(data, timings=tm2, waves=3)
    assert counts2 == counts
    assert tm2["retries"] >= 1
    assert np.array_equal(np.asarray(tm2["exchange"]["matrix"]),
                          tiny.host_exchange_matrix(data, waves=3))


def test_matrix_rides_registry_and_statusz(mesh):
    rng = np.random.default_rng(11)
    chunks = rng.integers(0, 1 << 14, size=(2 * mesh.shape["data"], 32)) \
        .astype(np.int32)
    cfg = EngineConfig(local_capacity=256, exchange_capacity=64,
                       out_capacity=256, reduce_op="sum")
    e0 = REGISTRY.sum("mrtpu_exchange_records_total")
    tm = {}
    DeviceEngine(mesh, _records_map_fn, cfg, task="commstest").run(
        chunks, timings=tm, waves=2, max_retries=0)
    delta = REGISTRY.sum("mrtpu_exchange_records_total") - e0
    assert delta == tm["exchange_records"] > 0
    # task-labelled: the collector can roll it up per tenant
    assert REGISTRY.sum("mrtpu_exchange_records_total",
                        task="commstest") >= tm["exchange_records"]
    # imbalance gauges landed for both sides
    assert REGISTRY.value("mrtpu_exchange_imbalance", side="recv",
                          task="commstest") >= 1.0
    assert REGISTRY.value("mrtpu_exchange_imbalance", side="send",
                          task="commstest") >= 1.0
    # and the snapshot mirror feeds the /statusz comms section
    from mapreduce_tpu.obs.statusz import comms_snapshot_section

    sec = comms_snapshot_section()
    assert sec["exchange"]["records"] == tm["exchange_records"]
    assert 0.0 <= sec["upload_overlap_frac"] <= 1.0
    from mapreduce_tpu.cli import _render_comms

    text = "\n".join(_render_comms(sec))
    assert "exchange" in text and "imbalance" in text


# -- diagnose: matrix-driven skew, hot destination, overlap ------------------


def _doc_with_metrics(rows, events=()):
    return {"traceEvents": list(events),
            "mrtpuCluster": {"aligned_to": "t", "procs": {},
                             "metrics": [list(r) for r in rows]}}


def test_diagnose_names_hot_destination_from_matrix():
    # 8 devices; device 5 receives 41% of records (imbalance 3.28x)
    rows = []
    for s in range(8):
        rows.append(["mrtpu_exchange_records_total",
                     {"src": f"D{s:03d}", "dst": "D005", "task": "wc"},
                     41.0])
        for d in range(8):
            if d == 5:
                continue
            rows.append(["mrtpu_exchange_records_total",
                         {"src": f"D{s:03d}", "dst": f"D{d:03d}",
                          "task": "wc"}, 59.0 / 7.0])
    report = diagnose(_doc_with_metrics(rows))
    ex = report["comms"]["exchange"]["wc"]
    assert ex["hot_dst"] == "D005"
    assert ex["hot_dst_share"] == pytest.approx(0.41, abs=0.001)
    assert ex["imbalance_recv"] == pytest.approx(3.28, abs=0.01)
    assert any("device 5 receives 41% of records" in n
               for n in report["notes"]), report["notes"]
    rendered = render_diagnosis(report)
    assert "exchange traffic:" in rendered


def test_diagnose_skew_falls_back_to_matrix_and_says_so():
    # NO partition gauges in the doc — only the matrix
    rows = [["mrtpu_exchange_records_total",
             {"src": "D000", "dst": "D000", "task": "wc"}, 90.0],
            ["mrtpu_exchange_records_total",
             {"src": "D000", "dst": "D001", "task": "wc"}, 5.0],
            ["mrtpu_exchange_records_total",
             {"src": "D001", "dst": "D002", "task": "wc"}, 5.0]]
    report = diagnose(_doc_with_metrics(rows))
    dev_skew = [s for s in report["skew"] if s["plane"] == "device"]
    assert dev_skew and dev_skew[0]["partition"] == "P00000"
    assert dev_skew[0]["source"] == "exchange_matrix"
    assert any("exchange traffic matrix" in n for n in report["notes"])
    assert "[via exchange matrix]" in render_diagnosis(report)


def test_diagnose_skew_prefers_partition_gauges():
    rows = [["mrtpu_device_partition_records",
             {"task": "wc", "partition": "P00000"}, 90.0],
            ["mrtpu_device_partition_records",
             {"task": "wc", "partition": "P00001"}, 10.0],
            ["mrtpu_device_partition_records",
             {"task": "wc", "partition": "P00002"}, 5.0],
            ["mrtpu_exchange_records_total",
             {"src": "D000", "dst": "D001", "task": "wc"}, 1000.0]]
    report = diagnose(_doc_with_metrics(rows))
    dev_skew = [s for s in report["skew"] if s["plane"] == "device"]
    assert dev_skew and dev_skew[0]["source"] == "partition_gauges"
    assert not any("partition gauges were absent" in n
                   for n in report["notes"])


def _span(name, ts, dur, span_id=None, parent_id=None, pid=1, **args):
    a = {"span_id": span_id or f"{name}-{ts}", "parent_id": parent_id}
    a.update(args)
    return {"name": name, "ph": "X", "ts": ts * 1e6, "dur": dur * 1e6,
            "pid": pid, "tid": 1, "args": a}


def test_diagnose_overlap_and_critical_path_from_spans():
    # wave w1: dispatch at t=1, done at t=10; upload of the NEXT wave
    # at [2, 4] fully hidden; a second upload [12, 20] NOT hidden
    events = [
        _span("device_run", 0, 22, span_id="run"),
        _span("wave", 0, 10, span_id="w1", parent_id="run"),
        _span("compute", 1, 0.1, parent_id="w1"),
        _span("upload", 2, 2, parent_id="w2"),
        _span("wave", 11, 10, span_id="w2", parent_id="run"),
        _span("compute", 12, 0.1, parent_id="w2"),
        _span("upload", 12, 8, parent_id="w2"),
    ]
    report = diagnose(_doc_with_metrics([], events))
    cp = report["critical_path"]
    # uploads total 10s; [2,4] (2s) + [12,20] (8s) all inside busy
    # intervals [1,10] and [12,21] except... [2,4] ⊂ [1,10] ✓ and
    # [12,20] ⊂ [12,21] ✓ -> fully overlapped
    assert cp["upload_overlap_frac"] == pytest.approx(1.0)
    assert cp["bound"] in ("compute", "upload")
    assert cp["stages"]["compute"] > 0

    # now a feeder-bound shape: uploads mostly OUTSIDE device busy time
    events2 = [
        _span("device_run", 0, 30, span_id="run"),
        _span("wave", 10, 2, span_id="w1", parent_id="run"),
        _span("compute", 10, 0.1, parent_id="w1"),
        _span("upload", 0, 10, parent_id="w1"),
        _span("upload", 13, 10, parent_id="w1"),
    ]
    report2 = diagnose(_doc_with_metrics([], events2))
    cp2 = report2["critical_path"]
    assert cp2["upload_overlap_frac"] < 0.5
    assert cp2["feeder_bound"] is True
    assert cp2["bound"] == "upload"
    assert any("feeder-bound" in n for n in report2["notes"])


def test_overlap_is_per_process_worst_case():
    """One process's busy device must not hide another process's
    feeder-bound run: the overlap fraction is computed per track and
    the WORST fraction reported (the span-plane twin of the
    collector's MIN-merge rule for the overlap gauge)."""
    healthy = [
        _span("wave", 0, 20, span_id="h-w", parent_id="h-r", pid=1),
        _span("compute", 0.5, 0.1, parent_id="h-w", pid=1),
        _span("upload", 1, 2, parent_id="h-w", pid=1),   # fully hidden
    ]
    feeder_bound = [
        _span("wave", 50, 1, span_id="f-w", parent_id="f-r", pid=2),
        _span("compute", 50, 0.1, parent_id="f-w", pid=2),
        _span("upload", 40, 10, parent_id="f-w", pid=2),  # all waiting
    ]
    report = diagnose(_doc_with_metrics([], healthy + feeder_bound))
    cp = report["critical_path"]
    # pooled intervals would report ~1.0 (proc 2's waits fall inside
    # proc 1's busy window); per-proc must surface proc 2's ~0
    assert cp["upload_overlap_frac"] < 0.2, cp
    assert cp["upload_overlap_frac_by_proc"]["1"] == pytest.approx(1.0)
    assert cp["upload_overlap_frac_by_proc"]["2"] < 0.2
    assert cp["feeder_bound"] is True


def test_record_exchange_publish_false_skips_registry():
    """publish=False (non-zero process index on a multi-controller
    mesh) must compute the derived dict but touch NO counters — the
    collector sums counter families across processes, so a replicated
    matrix published N times would read as N x the traffic."""
    e0 = REGISTRY.sum("mrtpu_exchange_records_total")
    derived = comms_mod.record_exchange(
        [[3, 1], [2, 4]], row_bytes=16, task="mp", publish=False)
    assert derived["exchange_records"] == 10
    assert derived["exchange"]["row_sums"] == [4, 6]
    assert REGISTRY.sum("mrtpu_exchange_records_total") == e0
    assert REGISTRY.sum("mrtpu_exchange_records_total", task="mp") == 0
    # the snapshot mirror (per-process /statusz) still updates
    assert comms_mod.comms_snapshot()["exchange"]["records"] == 10


def test_diagnose_end_to_end_from_live_engine_run(mesh, tmp_path,
                                                  capsys):
    """The acceptance path: a skewed device workload (the device-plane
    twin of tests/skew_mods.py's hot-key routing) -> collector doc ->
    `cli diagnose` names the hot destination device, with the matrix
    and the timeline coming from the real engine run."""
    from mapreduce_tpu.cli import cmd_diagnose
    from mapreduce_tpu.obs.collector import Collector

    def hot_map_fn(chunk, chunk_index, cfg):
        # ~3/4 of records get key_hi = 0 (-> partition 0), the rest
        # spread by value: the device-plane twin of tests/skew_mods.py's
        # hot*->P00000 routing.  key_lo stays the raw value so distinct
        # records stay distinct through the local reduce.
        hot = (chunk % 4) < 3
        k1 = jnp.where(hot, jnp.uint32(0), chunk.astype(jnp.uint32))
        keys = jnp.stack([k1, chunk.astype(jnp.uint32)], axis=-1)
        vals = jnp.ones_like(chunk, dtype=jnp.int32)
        pay = chunk.astype(jnp.int32)[:, None]
        valid = jnp.ones(chunk.shape[0], dtype=bool)
        return keys, vals, pay, valid, jnp.int32(0)

    # a label no other test's run carries: diagnose reads this process's
    # whole span ring, and a repartition decision another file recorded
    # on a task of the same name (the bench smoke's "skewed", when
    # tests/test_profile.py ran on this worker first) reads as "already
    # acted on"
    task = "comms-obs-skewed"
    n_dev = mesh.shape["data"]
    rng = np.random.default_rng(5)
    chunks = rng.integers(0, 1 << 10, size=(2 * n_dev, 16)) \
        .astype(np.int32)
    cfg = EngineConfig(local_capacity=256, exchange_capacity=64,
                       out_capacity=256, reduce_op="sum")
    tm = {}
    DeviceEngine(mesh, hot_map_fn, cfg, task=task).run(
        chunks, timings=tm, waves=2, max_retries=0)
    assert tm["exchange_hot_dst"] == 0
    assert tm["exchange_imbalance"] > 2.0

    collector = Collector()
    collector.push({"proc": "engineproc", "role": "server",
                    "spans": [], "metrics": REGISTRY.render(),
                    "t_mono": 0.0})
    doc = collector.cluster_doc()
    report = diagnose(doc)
    ex = report["comms"]["exchange"][task]
    assert ex["hot_dst"] == "D000"
    assert ex["imbalance_recv"] > 2.0
    assert any("exchange imbalance" in n and "device 0" in n
               for n in report["notes"]), report["notes"]

    # the actual CLI entry point, offline on the saved timeline
    path = tmp_path / "cluster.json"
    path.write_text(json.dumps(doc, default=float))
    assert cmd_diagnose([str(path)]) == 0
    out = capsys.readouterr().out
    assert "exchange imbalance" in out and "device 0 receives" in out
    assert cmd_diagnose([str(path), "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["comms"]["exchange"][task]["hot_dst"] == "D000"


# -- bundles -----------------------------------------------------------------


def test_comms_json_bundle_round_trip(tmp_path, mesh):
    from mapreduce_tpu.obs.profile import load_bundle, write_bundle

    rng = np.random.default_rng(17)
    chunks = rng.integers(0, 1 << 14, size=(2 * mesh.shape["data"], 32)) \
        .astype(np.int32)
    cfg = EngineConfig(local_capacity=256, exchange_capacity=64,
                       out_capacity=256, reduce_op="sum")
    tm = {}
    DeviceEngine(mesh, _records_map_fn, cfg).run(chunks, timings=tm,
                                                 waves=2, max_retries=0)
    out = str(tmp_path / "bundle")
    write_bundle(out)
    loaded = load_bundle(out)
    assert loaded["comms"]["kind"] == "mrtpu-comms"
    snap = loaded["comms"]["snapshot"]
    assert snap["exchange"]["records"] == tm["exchange_records"]
    assert loaded["manifest"]["files"].count("comms.json") == 1
    assert loaded["statusz"]["comms"]["exchange"]["records"] \
        == tm["exchange_records"]

    # corrupt comms.json must be refused on reload (strict validator)
    with open(f"{out}/comms.json", "r", encoding="utf-8") as f:
        doc = json.load(f)
    doc["snapshot"]["exchange"]["row_sums"] = [1]  # disagrees w/ matrix
    with open(f"{out}/comms.json", "w", encoding="utf-8") as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match="row sums"):
        load_bundle(out)


def test_validate_comms_shapes():
    good = {"kind": "mrtpu-comms", "version": 1, "snapshot": {
        "exchange": {"records": 3, "imbalance_send": 1.0,
                     "imbalance_recv": 1.5, "row_sums": [1, 2],
                     "col_sums": [3, 0], "matrix": [[1, 0], [2, 0]]},
        "upload_overlap_frac": 0.5}}
    comms_mod.validate_comms(good)
    for mutate, match in (
            (lambda d: d.update(kind="nope"), "not a mrtpu-comms"),
            (lambda d: d["snapshot"]["exchange"].pop("records"),
             "numeric 'records'"),
            (lambda d: d["snapshot"].update(upload_overlap_frac=1.5),
             "upload_overlap_frac"),
            (lambda d: d["snapshot"]["exchange"].update(
                matrix=[[1, 0]]), "square")):
        bad = json.loads(json.dumps(good))
        mutate(bad)
        with pytest.raises(ValueError, match=match):
            comms_mod.validate_comms(bad)
