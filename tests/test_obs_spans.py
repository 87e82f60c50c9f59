"""The one span recorder (obs/trace.py), the scopes of the device program
and the program's own reduction of a capture.

- a span records start <= end, the span that caused it, its thread, and
  ``failed`` when the body raised; the ring is bounded;
- spans record under ``observability=none`` and nothing is exported there;
- a ``jax.profiler`` capture on the CPU holds the ``lgbm.*`` annotations;
- the exact grower's train block, lowered at a toy shape, names every phase
  of the iteration, and its jaxpr is the one it has with the scopes patched
  out;
- ``reduce_phases`` on a hand-made event list: nested ``while``, an op with
  no scope, a gap under a host span; no ``lgbm.`` scope at all reads as
  nothing;
- the compile hook records ``jax.trace`` / ``jax.lower`` /
  ``jax.backend_compile`` with ``fun_name`` and the enclosing span;
- an armed flight recorder is fed the closed spans.
"""
import contextlib
import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import profiling
from lightgbm_tpu.obs import MetricsRegistry, TrainingObs, trace
from lightgbm_tpu.obs.distributed import FlightRecorder

SCOPES = ("lgbm.gradients", "lgbm.root_hist", "lgbm.row_gather",
          "lgbm.route_rows",
          "lgbm.hist_tile", "lgbm.hist_subtract", "lgbm.partition_scatter",
          "lgbm.split_search",
          "lgbm.leaf_ids", "lgbm.score_update", "lgbm.tree_pack")


def spans_named(*names, since=0):
    return [s for s in trace.recorded_spans()
            if s["name"] in names and s["id"] > since]


def last_id():
    # the ring is in order of closing: an outer span closes after the spans
    # it holds and has the smaller id
    return max((s["id"] for s in trace.recorded_spans()), default=0)


# ------------------------------------------------------------ the record
def test_span_records_interval_parent_thread_and_failure():
    mark = last_id()
    with trace.recorder.span("t.outer", rows=7) as outer:
        with trace.recorder.span("t.inner") as inner:
            inner.counts["bytes"] = 12
        with pytest.raises(ValueError):
            with trace.recorder.span("t.fails"):
                raise ValueError("boom")
    got = {s["name"]: s for s in spans_named("t.outer", "t.inner", "t.fails",
                                             since=mark)}
    assert set(got) == {"t.outer", "t.inner", "t.fails"}
    for s in got.values():
        assert s["start_ns"] <= s["end_ns"]
        assert s["thread"] == threading.get_ident()
    assert got["t.outer"]["parent"] is None
    assert got["t.inner"]["parent"] == got["t.outer"]["id"] == outer.id
    assert got["t.fails"]["parent"] == got["t.outer"]["id"]
    assert got["t.outer"]["start_ns"] <= got["t.inner"]["start_ns"]
    assert got["t.inner"]["end_ns"] <= got["t.outer"]["end_ns"]
    assert got["t.outer"]["counts"] == {"rows": 7}
    assert got["t.inner"]["counts"] == {"bytes": 12}
    assert [got[n]["failed"] for n in ("t.outer", "t.inner", "t.fails")] \
        == [False, False, True]
    # one wall-clock anchor for the process puts a span on the wall clock
    wall_ns = trace.WALL_ANCHOR[0] + (got["t.outer"]["start_ns"]
                                      - trace.WALL_ANCHOR[1])
    assert abs(wall_ns / 1e9 - time.time()) < 60


def test_parent_is_per_thread():
    mark = last_id()
    started, release = threading.Event(), threading.Event()

    def other():
        with trace.recorder.span("t.other_thread"):
            started.set()
            assert release.wait(10)

    th = threading.Thread(target=other)
    th.start()
    assert started.wait(10)
    with trace.recorder.span("t.main_thread"):
        pass
    release.set()
    th.join(10)
    assert not th.is_alive()
    got = {s["name"]: s for s in spans_named("t.other_thread",
                                             "t.main_thread", since=mark)}
    # the span open on ANOTHER thread is not this one's cause
    assert got["t.main_thread"]["parent"] is None
    assert got["t.other_thread"]["thread"] != got["t.main_thread"]["thread"]


def test_ring_is_bounded():
    for _ in range(trace.RING_SIZE + 50):
        with trace.recorder.span("t.fill"):
            pass
    spans = trace.recorded_spans()
    assert len(spans) == trace.RING_SIZE
    assert spans[-1]["name"] == "t.fill"


# ------------------------------------------------------------ export
def test_spans_record_under_observability_none_and_export_nothing(tmp_path):
    reg_before = {m.name for m in lgb.obs.get_registry().metrics()
                  if m.name == "lgbm_train_span_seconds"}
    mark = last_id()
    X = np.random.RandomState(0).randn(400, 5)
    y = (X[:, 0] > 0).astype(float)
    events = tmp_path / "events.jsonl"
    bst = lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1,
                     "observability": "none",
                     "obs_event_file": str(events)},
                    lgb.Dataset(X, y), num_boost_round=2)
    assert bst._impl.obs.level == 0
    names = [s["name"] for s in trace.recorded_spans() if s["id"] > mark]
    for want in ("ingest.construct", "ingest.to_float64", "ingest.find_bins",
                 "ingest.bundle", "ingest.bin_columns", "ingest.stack",
                 "train.setup", "train.device_put_bins",
                 "train.objective_init", "train.make_block_fn",
                 "train.block", "train.block_prepare",
                 "train.block_dispatch"):
        assert want in names, want
    # no barrier was added: the wait span exists only where the code
    # already waited (observability=basic|full)
    assert "train.block_wait" not in names
    assert not events.exists()
    assert {m.name for m in lgb.obs.get_registry().metrics()
            if m.name == "lgbm_train_span_seconds"} == reg_before


@pytest.mark.parametrize("sparse,sample_cnt", [
    (False, 250),     # dense and larger than the sample: the sampled rows
    (False, 1000),    # the table is its own sample: every value
    (True, 250),      # sparse: the stored entries
], ids=["dense_sampled", "dense_own_sample", "sparse_stored"])
def test_find_bins_span_counts_the_values_its_zero_test_read(sparse,
                                                             sample_cnt):
    sparse_mod = pytest.importorskip("scipy.sparse")
    rng = np.random.RandomState(37)
    n, f = 1000, 6
    X = rng.randn(n, f)
    X[rng.rand(n, f) < 0.3] = 0.0
    X[rng.rand(n, f) < 0.1] = np.nan
    data = sparse_mod.csr_matrix(X) if sparse else X
    mark = last_id()
    lgb.Dataset(data, (X[:, 0] > 0).astype(float),
                params={"verbose": -1,
                        "bin_construct_sample_cnt": sample_cnt}).construct()
    (span,) = spans_named("ingest.find_bins", since=mark)
    counts = span["counts"]
    assert counts["columns"] == f and counts["sample_rows"] == sample_cnt
    assert counts["values_scanned"] == (data.nnz if sparse
                                        else sample_cnt * f)
    # of the sampled rows' values, as before the zero test moved to them
    rows = np.arange(n) if sample_cnt == n else np.sort(
        np.random.RandomState(1).choice(n, sample_cnt, replace=False))
    assert counts["nan_values"] == int(np.isnan(X[rows]).sum())
    assert counts["zero_values"] == int((X[rows] == 0).sum())
    assert counts["nonzero_scan_s"] >= 0 and counts["find_bin_s"] > 0


# want: (partition_window_placement, leaf_ids_gather_free,
#        hist_smaller_child)
@pytest.mark.parametrize("extra,want", [
    ({}, (0, 1, 1)),                             # the CPU's element scatter
    ({"tpu_hist_impl": "pallas_interpret"}, (1, 1, 1)),  # the chip's loop
    ({"tpu_hist_impl": "pallas_interpret", "objective": "multiclass",
      "num_class": 3}, (0, 1, 1)),               # vmapped class batching
    ({"tpu_hist_impl": "pallas_interpret", "tree_growth": "frontier"},
     (0, 0, 0)),                                 # another grower
    ({"tree_growth": "batched"}, (0, 0, 0)),
    ({"cegb_penalty_feature_lazy": [0.1] * 4}, (0, 0, 1)),  # ids by split
], ids=["cpu_default", "pallas", "pallas_vmapped", "pallas_frontier",
        "batched", "cegb_lazy"])
def test_setup_span_says_how_the_block_places_and_maps_rows(extra, want):
    mark = last_id()
    X = np.random.RandomState(0).randn(300, 4)
    y = (X[:, 0] > 0).astype(float) + (X[:, 1] > 0)
    if "num_class" not in extra:
        y = (y > 0).astype(float)
    lgb.Booster(dict({"objective": "binary", "num_leaves": 4, "verbose": -1},
                     **extra), lgb.Dataset(X, y))
    (setup,) = spans_named("train.setup", since=mark)
    assert (setup["counts"]["partition_window_placement"],
            setup["counts"]["leaf_ids_gather_free"],
            setup["counts"]["hist_smaller_child"]) == want
    assert setup["counts"]["rows"] == 300


def test_enabled_tracer_exports(tmp_path):
    reg = MetricsRegistry()
    path = tmp_path / "ev.jsonl"
    ev = trace.EventStream(str(path))
    tr = trace.Tracer(enabled=True, registry=reg, events=ev,
                      metric="lgbm_span_seconds")
    with tr.span("t.exported", iteration=4):
        pass
    ev.close()
    rec = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(rec) == 1 and rec[0]["event"] == "span"
    assert rec[0]["span"] == "t.exported" and rec[0]["iteration"] == 4
    assert rec[0]["failed"] is False and rec[0]["dur_s"] >= 0
    assert rec[0]["parent"] is None and rec[0]["span_id"] > 0
    assert reg.summary("lgbm_span_seconds", "",
                       labels={"span": "t.exported"}).count == 1


def test_armed_flight_recorder_is_fed_closed_spans(tmp_path):
    fr = FlightRecorder(str(tmp_path / "e.jsonl"), size=16)
    fr.install()
    try:
        with trace.recorder.span("t.in_flight", rows=3):
            pass
        # a span an event stream already mirrored is not fed twice
        ev = trace.EventStream(str(tmp_path / "e.jsonl"), ring=fr)
        tr = trace.Tracer(enabled=True, registry=MetricsRegistry(),
                          events=ev)
        with tr.span("t.mirrored"):
            pass
        ev.close()
    finally:
        fr.uninstall()
    with trace.recorder.span("t.after_uninstall"):
        pass
    dump = fr.dump("test")
    rec = [json.loads(line) for line in open(dump)][1:]
    names = [r.get("span") for r in rec if r["event"] == "span"]
    assert names == ["t.in_flight", "t.mirrored"]
    assert rec[0]["rows"] == 3 and "dur_s" in rec[0]


# ------------------------------------------------------------ profiler
def test_cpu_profiler_capture_holds_the_span_annotations(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.recorder.span("t.captured"):
            with trace.recorder.span("t.captured_child"):
                jnp.arange(8.0).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    host = trace.load_capture(str(tmp_path))["host"]
    got = {name: (start, dur) for name, start, dur in host}
    assert {"t.captured", "t.captured_child"} <= set(got)
    o, c = got["t.captured"], got["t.captured_child"]
    assert o[0] <= c[0] and c[0] + c[1] <= o[0] + o[1]
    # a CPU capture has no device plane: nothing to read, not a table of 0
    assert trace.capture_phases(str(tmp_path)) is None


# ------------------------------------------------------------ scopes
def _toy_block():
    X = np.random.RandomState(3).randn(600, 6)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    g = lgb.train({"objective": "binary", "num_leaves": 7, "max_bin": 15,
                   "min_data_in_leaf": 5, "verbose": -1},
                  lgb.Dataset(X, y), num_boost_round=1)._impl
    return g._build_run_block(), g.train_block_sds(1)


def test_train_block_names_every_phase_and_keeps_its_jaxpr(monkeypatch):
    run_block, sds = _toy_block()
    # the lowered module, not a compiled one: the compile cache's key does
    # not see metadata, so a cached executable may predate the scopes
    text = jax.jit(run_block).lower(*sds).as_text(debug_info=True)
    found = set(re.findall(r"lgbm\.[a-z_]+", text))
    assert found == set(SCOPES)
    scoped = str(jax.make_jaxpr(run_block)(*sds))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    run_block, sds = _toy_block()
    bare_text = jax.jit(run_block).lower(*sds).as_text(debug_info=True)
    assert not re.findall(r"lgbm\.[a-z_]+", bare_text)
    assert str(jax.make_jaxpr(run_block)(*sds)) == scoped


# ------------------------------------------------------------ reduction
def test_reduce_phases_on_a_synthetic_event_list():
    dev = [
        # a while loop spans the ops of its body: control flow, not busy
        ("jit(run_block)/while", 0, 1000),
        ("jit(run_block)/while/body/lgbm.row_gather/gather", 0, 100),
        ("jit(run_block)/while/body/lgbm.hist_tile/pallas_call", 100, 300),
        # nested scopes: the LAST lgbm. component is the op's scope
        ("jit(run_block)/while/body/lgbm.split_search/lgbm.exchange/psum",
         400, 50),
        ("jit(run_block)/while/body/vmap(lgbm.split_search)/reduce_max",
         450, 50),
        ("jit(run_block)/while/body/lgbm.partition_scatter/scatter", 500, 400),
        ("fusion.7", 900, 100),                    # an op with no scope
        # a gap of 500 under train.block_dispatch, then one more op
        ("jit(run_block)/lgbm.score_update/add", 1500, 500),
    ]
    host = [("train.block", 0, 5000), ("train.block_dispatch", 900, 800)]
    got = trace.reduce_phases({"devices": [dev], "host": host})
    assert got["busy_s"] == pytest.approx(1500e-9)
    assert got["by_scope"] == pytest.approx({
        "lgbm.score_update": 500e-9, "lgbm.partition_scatter": 400e-9,
        "lgbm.hist_tile": 300e-9, "lgbm.row_gather": 100e-9,
        "lgbm.split_search": 50e-9, "lgbm.exchange": 50e-9})
    assert list(got["by_scope"])[:2] == ["lgbm.score_update",
                                         "lgbm.partition_scatter"]
    assert got["unscoped_s"] == pytest.approx(100e-9)
    assert got["idle_by_span"] == pytest.approx(
        {"train.block_dispatch": 500e-9})
    # two chips: the average over chips, as bench/trace_reduce.py has it
    two = trace.reduce_phases({"devices": [dev, dev], "host": host})
    assert two["busy_s"] == pytest.approx(got["busy_s"])
    assert two["by_scope"] == pytest.approx(got["by_scope"])


# what the parent of PR 38 reduced REMAINDER_EVENTS to, key for key: the
# reduction gained three tables and the four it had read as they did
REMAINDER_BEFORE = (
    '{"busy_s": 1.63e-06, "by_scope": {"lgbm.score_update": 5e-07, '
    '"lgbm.partition_scatter": 4e-07, "lgbm.hist_tile": 3e-07, '
    '"lgbm.row_gather": 1e-07, "lgbm.leaf_ids": 7e-08, '
    '"lgbm.exchange": 5e-08, "lgbm.split_search": 5e-08}, '
    '"unscoped_s": 1.6e-07, "idle_by_span": {"train.block_dispatch": 5e-07, '
    '"train.block": 7e-08}}')
REMAINDER_EVENTS = {"devices": [[
    ("jit(run_block)/while", 0, 1000),
    ("jit(run_block)/while/body/lgbm.row_gather/gather", 0, 100),
    ("jit(run_block)/while/body/lgbm.hist_tile/pallas_call", 100, 300),
    ("jit(run_block)/while/body/lgbm.split_search/lgbm.exchange/psum",
     400, 50),
    ("jit(run_block)/while/body/vmap(lgbm.split_search)/reduce_max", 450, 50),
    ("jit(run_block)/while/body/lgbm.partition_scatter/scatter", 500, 400),
    ("fusion.7", 900, 100),               # no scope, and the module has none
    # a gap of 500 after it, then an op that leaves a gap of 10 behind
    ("jit(run_block)/lgbm.score_update/add", 1500, 500),
    # no scope, as load_capture names it: the instruction and where it sits
    ("copy.478 jit(run_block)/while/body/copy", 2010, 30),
    ("copy.478 jit(run_block)/while/body/copy", 2100, 30),
    ("jit(run_block)/lgbm.leaf_ids/scatter", 2130, 70)]],
    "host": [("train.block", 0, 5000), ("train.block_dispatch", 900, 800)]}


def test_reduce_phases_says_what_the_remainder_is():
    got = trace.reduce_phases(REMAINDER_EVENTS)
    before = json.loads(REMAINDER_BEFORE)
    assert json.dumps({k: got[k] for k in before}) == REMAINDER_BEFORE
    assert sorted(set(got) - set(before)) == [
        "events_by_scope", "idle_after_scope", "unscoped_ops"]
    # leaf events, so seconds divide into calls; in by_scope's order
    assert got["events_by_scope"] == {s: 1 for s in got["by_scope"]}
    assert list(got["events_by_scope"]) == list(got["by_scope"])
    # a gap goes to the scope of the op that ended before it
    assert got["idle_after_scope"] == pytest.approx(
        {"unscoped": 560e-9, "lgbm.score_update": 10e-9})
    assert sum(got["idle_after_scope"].values()) == pytest.approx(
        sum(got["idle_by_span"].values()))
    assert got["unscoped_ops"] == [
        ["fusion.7", pytest.approx(100e-9), 1],
        ["copy.478 jit(run_block)/while/body/copy", pytest.approx(60e-9), 2]]
    # two chips: seconds and events are a chip's average
    two = trace.reduce_phases(dict(REMAINDER_EVENTS,
                                   devices=REMAINDER_EVENTS["devices"] * 2))
    assert two["events_by_scope"] == got["events_by_scope"]
    assert two["idle_after_scope"] == pytest.approx(got["idle_after_scope"])
    assert two["unscoped_ops"][1][1:] == [pytest.approx(60e-9), 2]
    # only the ten largest unscoped ops are listed
    many = [("fusion.%d" % i, 100 * i, 10 + i) for i in range(40)] \
        + [("jit(f)/lgbm.leaf_ids/scatter", 9000, 5)]
    top = trace.reduce_phases({"devices": [many], "host": []})["unscoped_ops"]
    assert [name for name, _, _ in top] == [
        "fusion.%d" % i for i in range(39, 29, -1)]


def test_trace_phases_tool_prints_the_three_new_tables():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_phases.py")
    spec = importlib.util.spec_from_file_location("trace_phases_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    text = tool.table(trace.reduce_phases(REMAINDER_EVENTS))
    assert "idle after an op of scope" in text
    assert "copy.478 jit(run_block)/while/body/copy" in text
    row = [ln for ln in text.splitlines() if ln.startswith("lgbm.hist_tile")]
    assert row and row[0].split()[-1] == "1"       # its events


def test_reduce_phases_reads_no_scope_at_all_as_nothing():
    bare = [("fusion.243", 0, 100), ("fusion.236", 100, 50)]
    assert trace.reduce_phases({"devices": [bare], "host": []}) is None
    assert trace.reduce_phases({"devices": [], "host": []}) is None
    assert trace.reduce_phases({"devices": [[]], "host": []}) is None
    assert trace.scope_of("fusion.243") is None
    assert trace.scope_of("jit(f)/lgbm.leaf_ids/scatter") == "lgbm.leaf_ids"


def _msg(*fields) -> bytes:
    """A protobuf message from (number, value) pairs: ints as varints,
    bytes/str length-delimited. The test's own encoder, so the reader in
    obs/trace.py is checked against the wire format and not itself."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_hlo_op_names_reads_the_modules_a_capture_carries():
    def instr(name, op_name=None):
        md = [(7, _msg((1, "gather"), (2, op_name)))] if op_name else []
        return _msg((1, name), (2, "fusion"), (35, 300), *md)

    module = _msg((1, "jit_run_block"), (3, _msg(
        (1, "body"),
        (2, instr("fusion.243",
                  "jit(run_block)/while/body/lgbm.partition_scatter/scatter")),
        (2, instr("copy.215")))))
    meta = _msg((1, 7), (2, "jit_run_block(123)"),
                (5, _msg((1, 9), (6, _msg((1, module))))))
    space = _msg(
        (1, _msg((2, "/device:TPU:0"), (4, _msg((1, 1), (2, _msg((2, "x"))))))),
        (1, _msg((1, 2), (2, "/host:metadata"),
                 (4, _msg((1, 7), (2, meta))),
                 (5, _msg((1, 9), (2, _msg((1, 9), (2, "Hlo Proto"))))))))
    assert trace.hlo_op_names(space) == {"jit_run_block(123)": {
        "fusion.243":
            "jit(run_block)/while/body/lgbm.partition_scatter/scatter"}}
    assert trace.hlo_op_names(b"") == {}


# ------------------------------------------------------------ compile hook
def test_compile_hook_records_trace_lower_compile_with_name_and_span():
    profiling.install_compile_hook()
    before = profiling.compile_cache_stats()
    mark = last_id()

    def span_hook_probe(x):
        return jnp.sin(jnp.cumsum(x)) * 3.0     # nested jits inside a trace

    x = jnp.arange(37.0)
    with trace.recorder.span("t.compiling") as outer:
        jax.jit(span_hook_probe)(x).block_until_ready()
    got = [s for s in spans_named("jax.trace", "jax.lower",
                                  "jax.backend_compile", since=mark)
           if "span_hook_probe" in s["counts"]["fun_name"]]
    assert sorted(s["name"] for s in got) == [
        "jax.backend_compile", "jax.lower", "jax.trace"]
    for s in got:
        assert s["parent"] == outer.id
        assert 0 <= s["start_ns"] <= s["end_ns"]
    # the traces of cumsum and sin INSIDE the outer trace are part of its
    # duration and not spans of their own
    assert [s["counts"]["fun_name"]
            for s in spans_named("jax.trace", since=mark)
            if s["parent"] == outer.id] == ["span_hook_probe"]
    after = profiling.compile_cache_stats()
    assert after["trace_seconds"] > before["trace_seconds"]
    assert after["lower_seconds"] > before["lower_seconds"]
    assert after["backend_compiles"] > before["backend_compiles"]
