"""The click-log cell at its rehearsal shape on the CPU: the bfloat16
control and the two planted faults of its own against its limits, the
job's two refusals, and every metric the cell brings resolved through its
reader. (Its ``--rehearsal`` run end to end, and the three faults every
training cell is held to, are test_run.py's, which runs them on every
cell of BENCHMARK.json.)

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
from bench.jobs import clicklog_window
from bench.tests import readings_clicklog

CELL = "criteo_clicklog_train"
NEW_METRICS = ("setup_cache_misses", "construct_nan_values",
               "construct_zero_values", "features_with_missing")


def drive(monkeypatch, seed=2147483702):
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", CELL, "--seed", str(seed), "--seconds",
        "0.5", "--trace", "1", "--rehearsal"])
    return bench_run.main()


def test_the_control_and_the_flipped_model_are_not_correct(monkeypatch):
    seen = {}
    real = clicklog_window.judge

    def keep(ctx, X, y, model_text, scores):
        seen.update(ctx=ctx, X=X, y=y, model_text=model_text)
        return real(ctx, X, y, model_text, scores)
    monkeypatch.setattr(clicklog_window, "judge", keep)
    line = drive(monkeypatch)
    assert line["correct"] is True, line["compared"]
    # the traced rehearsal names what a traced chip run would report
    assert set(NEW_METRICS) <= set(line["would_report"])
    got = readings_clicklog.variants(seen["ctx"], seen["X"], seen["y"],
                                     seen["model_text"])
    assert got["fails"]["program"] == []
    # the precision below the one stated fails a limit, and not by the
    # rows it routes
    assert "count_mismatch" not in got["fails"]["control"], got["control"]
    assert {"leaf_value_gap", "leaf_value_gap_median", "split_gain_gap",
            "split_gain_gap_median"} <= set(got["fails"]["control"]), \
        got["control"]
    # NaN rows sent down the other side are counted
    assert got["shape"]["nodes_flipped"] > 0
    assert "count_mismatch" in got["fails"]["flipped"], got["flipped"]


def test_a_program_that_ignores_missing_values_is_not_correct(monkeypatch):
    """use_missing=false underneath the stated use_missing=true: a NaN is
    binned with the zeros and no missing direction is priced."""
    import lightgbm_tpu as lgb
    dataset, train = lgb.Dataset, lgb.train
    monkeypatch.setattr(lgb, "Dataset", lambda X, y, params: dataset(
        X, y, params=dict(params, use_missing=False)))
    monkeypatch.setattr(lgb, "train", lambda params, ds, **kw: train(
        dict(params, use_missing=False), ds, **kw))
    line = drive(monkeypatch)
    assert line["correct"] is False
    failed = [k for k, c in line["compared"].items() if not c["ok"]]
    assert set(failed) & {"node_regret", "count_mismatch"}, line["compared"]


def test_the_job_refuses_a_program_that_bakes_the_data_in(monkeypatch):
    from lightgbm_tpu.boosting.gbdt import GBDT
    real = GBDT._make_train_iter_fn

    def closes_over_metadata(self):
        fn = real(self)
        self._iter_capture = self._iter_capture[:3]   # as before PR 29
        return fn
    monkeypatch.setattr(GBDT, "_make_train_iter_fn", closes_over_metadata)
    with pytest.raises(SystemExit) as e:
        drive(monkeypatch)
    assert "closes its train block over" in str(e.value)


def test_the_job_goes_on_where_it_cannot_ask(monkeypatch):
    """A program that keeps its block's arguments under another name is
    not refused for it."""
    monkeypatch.setattr(clicklog_window, "block_takes_metadata",
                        lambda lgb, params: None)
    assert drive(monkeypatch)["correct"] is True


def test_the_job_holds_the_stored_layout(monkeypatch):
    monkeypatch.setattr(clicklog_window, "stored_columns", lambda: 66)
    with pytest.raises(SystemExit) as e:
        drive(monkeypatch)
    assert "66 stored columns" in str(e.value)


def recorded():
    return bench_run.load_json(bench_run.HERE, "tests",
                               "readings_%s.json" % CELL)["numbers"]


@pytest.mark.parametrize("number", sorted(recorded()))
def test_a_limit_lies_between_its_two_readings(number):
    """The cell's limits at its own size against the readings they were
    set from (chip runs and host readings of their models, recorded in
    readings_criteo_clicklog_train.json): the largest a sound run read
    passes, the smallest its control or planted fault read fails."""
    limits = bench_run.load_json(bench_run.HERE, "workloads",
                                 CELL + ".json")["check"]["limits"]
    assert sorted(limits) == sorted(recorded())
    read = recorded()[number]
    limit = {number: limits[number]}
    assert clicklog_window.hold({number: read["lower"]}, limit)[1], read
    assert not clicklog_window.hold({number: read["upper"]}, limit)[1], read
    if limits[number]:   # room on both sides: twice at the least
        assert 2 * read["lower"] <= limits[number] <= read["upper"] / 2


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_resolves_through_its_reader(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = bench_run.find(json.load(f)["per_layer"], name, "metric")
    assert entry["workloads"] == [CELL]
    spec = bench_run.load_json(bench_run.HERE, "metrics", name + ".json")
    reader = bench_run.load_module("readers", spec["reader"])
    # a count of 0 is a reading, and bench/run.py reports it; a job that
    # was handed no such count (the program lacks it) reads as nothing
    assert reader.read(spec, {"clocks": {spec["key"]: 0}}) == 0
    assert reader.read(spec, {"clocks": {}}) is None


def test_the_job_hands_on_what_the_program_counted(monkeypatch):
    """The three counts of the table come from the program's spans, of the
    timed table and not of the 64-row one the job asks its question of."""
    seen = {}
    real = clicklog_window.judge

    def keep(ctx, X, y, model_text, scores):
        seen["X"] = X
        return real(ctx, X, y, model_text, scores)
    monkeypatch.setattr(clicklog_window, "judge", keep)
    drive(monkeypatch)
    X = seen["X"]      # 6,000 rows: every row is sampled
    find_bins = clicklog_window.span_counts("ingest.find_bins")
    assert find_bins["nan_values"] == int(np.isnan(X).sum())
    assert find_bins["zero_values"] == int((X == 0).sum())
    with_missing = clicklog_window.span_counts("train.setup")[
        "features_with_missing"]
    assert with_missing == int(np.isnan(X).any(axis=0).sum())
