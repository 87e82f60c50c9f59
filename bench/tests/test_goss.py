"""The GOSS cell at its rehearsal shape on the CPU: the bfloat16 control
and the planted faults of its own against its limits, the job's refusal,
and every metric the cell brings resolved through its reader. (Its
``--rehearsal`` run end to end, and the three faults every training cell
is held to, are test_run.py's, which runs them on every cell of
BENCHMARK.json; its bfloat16 test is for train_window's cells and
conftest.py leaves this one out of it.)

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q -p no:cacheprovider
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run
from bench.jobs import goss_window
from bench.readers import hist_roofline_bag, trace_phases
from bench.tests import readings_goss
from bench.tests.test_yardstick import THREE_LEAVES

CELL = "criteo_clicklog_goss_train"
NEW_METRICS = ("goss_sample_ms", "hist_rows_per_iter", "hist_roofline_bag",
               "goss_warmup_s")
# the number each planted fault has to fail
FAULTS = {"control": "leaf_value_gap", "no_multiplier": "leaf_value_gap",
          "counts_all_rows": "count_mismatch",
          "bag_reused": "bag_uniformity", "others_in_order": "bag_uniformity",
          "top_swapped": "bag_top_missed", "bernoulli_rest": "bag_count_gap",
          "oob_not_scored": "score_gap"}


def drive(monkeypatch, seed=2147483703):
    # a window long enough for the three sampled blocks the check judges
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", CELL, "--seed", str(seed), "--seconds",
        "5", "--trace", "1", "--rehearsal"])
    return bench_run.main()


@pytest.fixture(scope="module")
def a_run():
    """One sound rehearsal run, what its check was handed, and every
    variant read from that."""
    seen = {}
    real = goss_window.judge

    def keep(ctx, X, y, model_text, scores, bags):
        seen.update(ctx=ctx, X=X, y=y, model_text=model_text, scores=scores,
                    bags=bags)
        return real(ctx, X, y, model_text, scores, bags)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(goss_window, "judge", keep)
        line = drive(m)
    got = readings_goss.variants(seen["ctx"], seen["X"], seen["y"],
                                 seen["model_text"], seen["scores"],
                                 seen["bags"])
    return line, seen, got


def test_a_sound_run_is_correct_and_judges_the_first_sampled_trees(a_run):
    line, seen, got = a_run
    assert line["correct"] is True, line["compared"]
    assert set(NEW_METRICS) - {"goss_sample_ms", "hist_roofline_bag"} \
        <= set(line["would_report"])      # the other two need a device trace
    assert got["fails"]["program"] == []
    # ten unsampled iterations, then the window's: the judged trees are
    # the first sampled ones, each on a bag of 30%
    assert got["shape"]["judged"][0] == 10
    trees = goss_window.reference_goss.parse_trees(seen["model_text"])
    n = len(seen["y"])
    assert [int(t["internal_count"][0]) for t in trees[9:11]] == \
        [n, int(n * 0.2) + int(n * 0.1)]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_fails_the_number_named_for_it(a_run, fault):
    _, _, got = a_run
    assert FAULTS[fault] in got["fails"][fault], got[fault]
    if fault == "control":    # not by the rows it routes
        assert "count_mismatch" not in got["fails"][fault]


def test_the_job_refuses_a_program_whose_goss_is_a_multiplier(monkeypatch):
    """The parent of the PR that brought the bag: no bag to hand over."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    real = GBDT._setup_train

    def mask_form(self, ds):
        real(self, ds)
        self._goss_bag = False
    monkeypatch.setattr(GBDT, "_setup_train", mask_form)
    with pytest.raises(SystemExit) as e:
        drive(monkeypatch)
    assert "multiplier on the gradients" in str(e.value)


def recorded():
    return bench_run.load_json(bench_run.HERE, "tests",
                               "readings_%s.json" % CELL)["numbers"]


@pytest.mark.parametrize("number", sorted(recorded()))
def test_a_limit_lies_between_its_two_readings(number):
    """The cell's limits at its own size against the readings they were
    set from (chip runs and the planted faults read from what those runs
    handed their check; readings_criteo_clicklog_goss_train.json)."""
    limits = bench_run.load_json(bench_run.HERE, "workloads",
                                 CELL + ".json")["check"]["limits"]
    assert sorted(limits) == sorted(recorded())
    read = recorded()[number]
    limit = {number: limits[number]}
    assert goss_window.hold({number: read["lower"]}, limit)[1], read
    assert not goss_window.hold({number: read["upper"]}, limit)[1], read
    if limits[number]:   # room on both sides: twice at the least
        assert 2 * read["lower"] <= limits[number] <= read["upper"] / 2


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_lists_this_cell_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = bench_run.find(json.load(f)["per_layer"], name, "metric")
    assert entry["workloads"] == [CELL]
    spec = bench_run.load_json(bench_run.HERE, "metrics", name + ".json")
    assert bench_run.load_module("readers", spec["reader"]).read(
        spec, {"clocks": {}, "trace": None, "phases": None}) is None


def test_the_scope_reader_on_a_recorded_table():
    spec = bench_run.load_json(bench_run.HERE, "metrics",
                               "goss_sample_ms.json")
    phases = {"by_scope": {"lgbm.goss_sample": 0.018, "lgbm.bag_compact":
                           0.073, "lgbm.row_gather": 2.0}}
    tr = {"iters": 1}
    assert abs(trace_phases.read(spec, {"phases": phases, "trace": tr})
               - 91.0) < 1e-9
    # a program without the scopes (the parent): nothing, never 0
    only = {"by_scope": {"lgbm.row_gather": 2.0}}
    assert trace_phases.read(spec, {"phases": only, "trace": tr}) is None
    assert trace_phases.read(spec, {"phases": None, "trace": tr}) is None


def test_hist_roofline_bag_prices_the_bag_and_not_the_table():
    # test_yardstick's three-leaf tree, read as grown on a bag of 1000:
    # the bag for the root, then 1000 and 600 in-bag rows for the splits
    tr = {"op_s": {"build_histogram_x.1": 2e-3}, "iters": 1, "first_iter": 0}
    result = {"trace": tr, "model_text": THREE_LEAVES,
              "config": {"data": {"rows": 10 ** 9, "cols": 4},
                         "params": {"max_bin": 16}},
              "peaks": {"hbm_bytes_per_s": 1e9}}
    got = hist_roofline_bag.read({"kernel": "histogram",
                                  "bound": "hbm_bytes_per_s"}, result)
    want = 100.0 * ((2600 * 12 + 5 * 4 * 16 * 3 * 4) / 1e9) / 2e-3
    assert abs(got - want) < 1e-9


def test_the_job_hands_on_the_rows_the_kernel_saw(a_run):
    """hist_rows_per_iter is the traced (first sampled) block's count, and
    it is the bag plus the smaller child's in-bag rows of every split."""
    _, seen, _ = a_run
    t = goss_window.reference_goss.parse_trees(seen["model_text"])[10]
    count = lambda c: t["leaf_count"][-c - 1] if c < 0 \
        else t["internal_count"][c]
    smaller = sum(min(count(int(l)), count(int(r)))
                  for l, r in zip(t["left_child"], t["right_child"]))
    assert goss_window.traced_hist_rows(1) == t["internal_count"][0] + smaller
