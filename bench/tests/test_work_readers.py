"""The readers PR 38 brought, on hand-made ``result`` dicts and hand-made
span lists, and every metric they serve resolved through BENCHMARK.json:
the work counts of the traced block (traced_block), a phase's cost a tile
or a row (phase_unit_cost), the remainder's two totals (phases_total), the
set-up spans (setup_spans) and what of ``setup_s`` nothing accounts for
(setup_remainder). Each reads NOTHING, never 0, on what the parent's
program hands over: no count on the span, no table in ``phases``, no span.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_work_readers.py -q
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run
from bench.readers import (phase_unit_cost, phases_total, program_spans,
                           setup_remainder, setup_spans, traced_block)
from bench.tests.test_program_spans import span

ALL = ["criteo_share_train", "criteo_clicklog_train",
       "criteo_clicklog_goss_train", "criteo_categorical_train"]
WITH_PHASES = ALL[2:]
# metric -> the cells that report it
NEW_METRICS = dict(
    {m: ALL for m in (
        "partition_tiles_per_iter", "hist_tiles_per_iter",
        "split_rows_per_iter", "hist_rows_traced_per_iter",
        "hist_ns_per_row", "startup_before_program_s", "program_import_s",
        "engine_train_s", "setup_unattributed_s")},
    **{m: WITH_PHASES for m in (
        "row_gather_ms", "hist_tile_ms", "partition_scatter_ms",
        "root_hist_ms", "leaf_ids_ms", "score_update_ms",
        "row_gather_us_per_tile", "unscoped_ms", "idle_in_program_ms")})

# a process that imported the program 9 s after it started, trained a first
# block (iteration 0) inside engine.train, then two more; the second
# block's counts are the traced one's
SPANS = [
    span(1, "runtime.before_import", 0, 9000),
    span(2, "import.basic", 9000, 2500),
    span(3, "import.engine", 11500, 100),
    span(4, "train.engine", 20000, 18000),
    span(5, "train.block", 21000, 16000, parent=4, start_iter=0, count=1,
         splits=254, split_rows=800, partition_tiles=52, hist_rows=300,
         hist_tiles=22),
    span(6, "train.block", 40000, 5000, start_iter=1, count=1,
         splits=254, split_rows=1000, partition_tiles=60, hist_rows=400,
         hist_tiles=20),
    span(7, "train.block", 45000, 5000, start_iter=2, count=1,
         splits=254, split_rows=1200, partition_tiles=70, hist_rows=500,
         hist_tiles=30),
    span(8, "train.engine", 60000, 10),               # a later, second call
]
# the parent's spans: blocks that count nothing of the device's work
OLD_SPANS = [span(5, "train.block", 21000, 16000, start_iter=0, count=1),
             span(6, "train.block", 40000, 5000, start_iter=1, count=1)]
TRACE = {"iters": 1, "first_iter": 1, "busy_s": 5.0, "window_s": 5.2,
         "op_s": {"build_histogram_pallas_vals.3": 3e-6,
                  "build_histogram_pallas_vals.9": 1e-6, "fusion.2": 4.0}}
PHASES = {"busy_s": 5.0, "unscoped_s": 0.05,
          "by_scope": {"lgbm.row_gather": 2.8e-3, "lgbm.hist_tile": 1e-3},
          "idle_by_span": {"train.block": 0.2},
          "events_by_scope": {"lgbm.row_gather": 160, "lgbm.hist_tile": 40},
          "idle_after_scope": {"lgbm.partition_scatter": 0.15,
                               "unscoped": 0.05},
          "unscoped_ops": [["copy.478", 0.03, 254]]}
# what the parent's capture_phases hands on: the four tables it had
OLD_PHASES = {k: PHASES[k] for k in ("busy_s", "unscoped_s", "by_scope",
                                     "idle_by_span")}


@pytest.fixture
def recorded(monkeypatch):
    """The program's recorder replaced by a hand-made span list."""
    def put(spans):
        monkeypatch.setattr(program_spans, "recorded", lambda: spans)
    put(SPANS)
    return put


def spec_of(name):
    return bench_run.load_json(bench_run.HERE, "metrics", name + ".json")


def read(name, result):
    spec = spec_of(name)
    return bench_run.load_module("readers", spec["reader"]).read(spec, result)


@pytest.mark.parametrize("name, want", [
    ("partition_tiles_per_iter", 60), ("hist_tiles_per_iter", 20),
    ("split_rows_per_iter", 1000), ("hist_rows_traced_per_iter", 400),
    # 4e-6 s of histogram kernels over 400 rows; 2.8e-3 s over 80 tiles
    ("hist_ns_per_row", 10.0), ("row_gather_us_per_tile", 35.0),
    ("unscoped_ms", 50.0), ("idle_in_program_ms", 150.0),
    ("startup_before_program_s", 9.0), ("program_import_s", 2.6),
    ("engine_train_s", 18.0),
    # 60 s of set-up less 9 before the import less 12 + 20 + 16 of clocks
    ("setup_unattributed_s", 3.0)])
def test_a_new_metric_on_a_hand_made_result(recorded, name, want):
    result = {"trace": TRACE, "phases": PHASES,
              "end_to_end": {"setup_s": 60.0},
              "clocks": {"data_s": 12.0, "binning_s": 20.0,
                         "first_block_s": 16.0, "compile_s": 4.0}}
    assert read(name, result) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_reads_nothing_on_the_parents_shapes(recorded, name):
    recorded(OLD_SPANS)
    result = {"trace": TRACE, "phases": OLD_PHASES,
              "end_to_end": {"setup_s": 60.0},
              "clocks": {"data_s": 12.0, "binning_s": 20.0}}
    got = read(name, result)
    if spec_of(name)["reader"] == "trace_phases" or name == "unscoped_ms":
        # what the parent's table had already (its scopes, ``unscoped_s``)
        # reads there as here: no such entry, or a number, never 0
        assert got is None or got > 0
    else:
        assert got is None
    # no capture at all, and a program without the recorder
    assert read(name, {"trace": None, "phases": None, "clocks": {},
                       "end_to_end": {}}) is None
    recorded(None)
    if spec_of(name)["reader"] not in ("trace_phases", "phases_total"):
        assert read(name, result) is None


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_lists_its_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench_run.find(bench["per_layer"], name, "metric")
    assert entry["workloads"] == NEW_METRICS[name]
    assert entry["better"] == "lower"
    assert entry["moves"] == ("setup_s" if entry["layer"] == "entry"
                              else "train_s_per_iter")
    # none of them rides the reader test_program_spans.py holds to 8
    assert spec_of(name)["reader"] != "program_spans"
    # and they stand at the end of the list, behind what was there
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(name) >= names.index("construct_bin_categorical_s")


def test_the_traced_block_is_the_newest_span_of_that_iteration(recorded):
    later = SPANS + [span(9, "train.block", 70000, 5, start_iter=1, count=1,
                          hist_rows=7)]
    recorded(later)
    assert traced_block.traced_counts({"trace": TRACE})["hist_rows"] == 7
    # a block of another length is another block
    recorded([span(9, "train.block", 0, 5, start_iter=1, count=2,
                   hist_rows=7)])
    assert traced_block.traced_counts({"trace": TRACE}) is None
    recorded(SPANS)
    with pytest.raises(ValueError):
        traced_block.read({"count": "hist_rows", "what": "sum"},
                          {"trace": dict(TRACE, first_iter=2)})


def test_a_unit_cost_needs_its_seconds_and_its_count(recorded):
    spec = spec_of("row_gather_us_per_tile")
    result = {"trace": TRACE, "phases": PHASES}
    # a grower with no tile: the span has no such count
    recorded([span(6, "train.block", 0, 5, start_iter=1, count=1,
                   splits=254, split_rows=1000)])
    assert phase_unit_cost.read(spec, result) is None
    # a count of 0 divides nothing
    recorded([span(6, "train.block", 0, 5, start_iter=1, count=1,
                   partition_tiles=0, hist_tiles=0)])
    assert phase_unit_cost.read(spec, result) is None
    # no op under the scope, no op of the family
    recorded(SPANS)
    assert phase_unit_cost.read(
        dict(spec, scopes=["lgbm.no_such_scope"]), result) is None
    assert phase_unit_cost.read(
        dict(spec_of("hist_ns_per_row"), kernel="no_such_kernel"),
        result) is None


def test_the_set_up_readers_take_program_spans_words(recorded):
    assert setup_spans.read({"prefix": "import.", "which": "first",
                             "what": "sum_s"}, {}) == pytest.approx(2.5)
    assert setup_spans.read({"prefix": "export.", "what": "sum_s"},
                            {}) is None
    assert setup_spans.read({"span": "train.block", "which": "all",
                             "what": "hist_tiles"}, {}) == 72
    # the remainder leaves out the clocks a job does not take
    spec = spec_of("setup_unattributed_s")
    got = setup_remainder.read(spec, {
        "end_to_end": {"setup_s": 110.0},
        "clocks": {"data_s": 12.0, "binning_s": 22.0, "first_block_s": 15.0,
                   "unsampled_rest_s": 44.0, "sampled_block_ready_s": 4.0,
                   "goss_warmup_s": 58.0}})
    assert got == pytest.approx(110.0 - 9.0 - 97.0)
    assert phases_total.read({"key": "busy_s", "what": "ms_per_iter"},
                             {"phases": PHASES, "trace": TRACE}) == 5000.0
    # a capture with no gap after an op of the scope
    assert phases_total.read(
        dict(spec_of("idle_in_program_ms"), scope="lgbm.leaf_ids"),
        {"phases": PHASES, "trace": TRACE}) is None
    with pytest.raises(ValueError):
        phases_total.read({"key": "busy_s", "what": "s"},
                          {"phases": PHASES, "trace": TRACE})
