"""The categorical cell at its rehearsal shape on the CPU: the bfloat16
control and the planted faults of its own against its limits, the job's
refusal, and every metric the cell brings resolved through its reader.
(Its ``--rehearsal`` run end to end, and the three faults every training
cell is held to, are test_run.py's, which runs them on every cell of
BENCHMARK.json; its bfloat16 test is for train_window's cells and
conftest.py leaves this one out of it.)

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q -p no:cacheprovider
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run
from bench.jobs import categorical_window
from bench.readers import trace_phases
from bench.tests import readings_categorical

CELL = "criteo_categorical_train"
NEW_METRICS = ("split_search_cat_ms", "route_rows_ms", "cat_splits_per_iter",
               "construct_bin_categorical_s")
# the number each planted fault has to fail
FAULTS = {"control": "leaf_value_gap", "no_cat_l2": "leaf_value_gap",
          "bitset_inverted": "count_mismatch",
          "unkept_left": "count_mismatch"}


def drive(monkeypatch, seed=2147483735, trace=1):
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", CELL, "--seed", str(seed), "--seconds",
        "2", "--trace", str(trace), "--rehearsal"])
    return bench_run.main()


def kept_run(train_with=None):
    """One rehearsal run and what its check was handed; ``train_with``
    changes the program's parameters and not what it is judged against."""
    with pytest.MonkeyPatch.context() as m, \
            readings_categorical.kept(train_with or {}) as seen:
        line = drive(m)
    return line, seen


@pytest.fixture(scope="module")
def a_run():
    line, seen = kept_run()
    got = readings_categorical.variants(
        seen["ctx"], seen["X"], seen["y"], seen["model_text"], seen["scores"])
    return line, seen, got


def test_a_sound_run_is_correct_and_splits_on_categories(a_run):
    line, seen, got = a_run
    assert line["correct"] is True, line["compared"]
    assert set(NEW_METRICS) - {"split_search_cat_ms", "route_rows_ms"} \
        <= set(line["would_report"])      # the other two need a device trace
    assert got["fails"]["program"] == []
    shape = got["shape"]
    # every tree splits on categories, of both kinds (a quarter of the
    # 254 splits at the cell's own size; at 6,000 rows and 14 splits the
    # integer columns' terms take most)
    assert all(shape["cat_splits"]), shape
    assert sum(shape["onehot_splits"]) > 0
    assert sum(shape["cat_splits"][:3]) > sum(shape["onehot_splits"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_fails_the_number_named_for_it(a_run, fault):
    _, _, got = a_run
    assert FAULTS[fault] in got["fails"][fault], got[fault]
    if fault in ("control", "no_cat_l2"):    # not by the rows they route
        assert "count_mismatch" not in got["fails"][fault]


def test_a_finder_held_to_one_category_fails_node_regret():
    """The program trained with max_cat_threshold=1, judged against
    upstream's 32: it routes as its own model says and fails the regret."""
    line, seen = kept_run({"max_cat_threshold": 1})
    assert line["correct"] is False
    compared = line["compared"]
    assert not compared["node_regret"]["ok"], compared
    assert compared["count_mismatch"]["ok"] and compared["score_gap"]["ok"]


def test_the_job_refuses_a_program_that_gathers_the_set(monkeypatch):
    """The parent of the PR that brought the gather-free test publishes no
    such count: the job ends before any data is made."""
    real = categorical_window.span_counts

    def without(name):
        return {k: v for k, v in real(name).items()
                if k != "cat_route_gather_free"}
    monkeypatch.setattr(categorical_window, "span_counts", without)
    made = []
    real_load = bench_run.load_module

    def load(kind, name):
        mod = real_load(kind, name)
        if kind == "generators":
            monkeypatch.setattr(mod, "generate",
                                lambda *a, **k: made.append(1))
        return mod
    monkeypatch.setattr(bench_run, "load_module", load)
    with pytest.raises(SystemExit) as e:
        drive(monkeypatch, trace=0)
    assert "through a gather" in str(e.value) and not made


def recorded():
    return bench_run.load_json(bench_run.HERE, "tests",
                               "readings_%s.json" % CELL)["numbers"]


@pytest.mark.parametrize("number", sorted(recorded()))
def test_a_limit_lies_between_its_two_readings(number):
    """The cell's limits at its own size against the readings they were
    set from (chip runs and the planted faults read from what those runs
    handed their check; readings_criteo_categorical_train.json)."""
    limits = bench_run.load_json(bench_run.HERE, "workloads",
                                 CELL + ".json")["check"]["limits"]
    assert sorted(limits) == sorted(recorded())
    read = recorded()[number]
    limit = {number: limits[number]}
    assert categorical_window.hold({number: read["lower"]}, limit)[1], read
    assert not categorical_window.hold({number: read["upper"]}, limit)[1], read
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


def test_the_scope_readers_on_a_recorded_table():
    phases = {"by_scope": {"lgbm.route_rows": 0.076, "lgbm.split_search": 0.2,
                           "lgbm.split_search_cat": 0.15}}
    tr = {"iters": 1}
    for name, want in (("route_rows_ms", 76.0),
                       ("split_search_cat_ms", 150.0)):
        spec = bench_run.load_json(bench_run.HERE, "metrics", name + ".json")
        got = trace_phases.read(spec, {"phases": phases, "trace": tr})
        assert abs(got - want) < 1e-9
    # a program without the finder's scope (the parent): nothing, never 0
    spec = bench_run.load_json(bench_run.HERE, "metrics",
                               "split_search_cat_ms.json")
    only = {"by_scope": {"lgbm.route_rows": 0.076}}
    assert trace_phases.read(spec, {"phases": only, "trace": tr}) is None


def test_the_job_hands_on_the_traced_blocks_categorical_splits(a_run):
    """cat_splits_per_iter is the window's first block's count: the
    splits of the second tree (the first is set-up's) on a categorical
    column."""
    _, seen, got = a_run
    spans = categorical_window.block_spans()
    counted = [s["counts"]["cat_splits"] for s in spans
               if "cat_splits" in s["counts"]]
    assert got["shape"]["cat_splits"][1] in counted
