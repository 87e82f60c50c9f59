"""bench/run.py end to end on the CPU at the configuration's rehearsal
shape: the labelled dry run, the refusal to measure without a chip, and
``correct`` coming out false when the timed path is broken underneath or
the reference is computed in bfloat16 in the program's place.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q -p no:cacheprovider
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import reference_gbdt as ref
from bench import run as bench_run
from bench.jobs import train_window
from bench.tests.readings_on_chip import in_programs_place, level_by_level

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cli(*args):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                          env=ENV, capture_output=True, text=True,
                          timeout=900)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_end_to_end_and_reports_no_metric(cell):
    p = run_cli("--workload", cell, "--seed", "2147483700", "--seconds", "1",
                "--trace", "1", "--rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "REHEARSAL" in p.stdout
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["no_chip_run"]
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert list(line)[-1] == "compared"
    # each number compared stands beside its limit at the end of stderr
    tail = p.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and "limit" in t for t in tail)


@pytest.mark.parametrize("cell", CELLS[:1])
def test_without_a_chip_there_is_no_run_and_no_number(cell):
    p = run_cli("--workload", cell, "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def drive(monkeypatch, cell, seed=2147483701):
    """The rest of a run after the look for a chip: bench/run.py's main
    on the rehearsal shape, in this process."""
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", cell, "--seed", str(seed), "--seconds",
        "0.5", "--trace", "0", "--rehearsal"])
    return bench_run.main()


def freeze_scores(monkeypatch):
    """A step that returns its state unchanged: the tree is kept, the
    scores are not moved."""
    import jax.numpy as jnp
    from lightgbm_tpu.boosting.gbdt import GBDT
    real = GBDT.train_many

    def frozen(self, n):
        if not self.boost_from_average_done:
            return real(self, n)   # let the init score in, once
        before = jnp.array(self.scores, copy=True)
        out = real(self, n)
        self.scores = before
        return out
    monkeypatch.setattr(GBDT, "train_many", frozen)


def half_the_batch(monkeypatch):
    """Half of the rows left out, the leaf means taken over the rest."""
    import lightgbm_tpu as lgb
    real = lgb.Dataset
    monkeypatch.setattr(lgb, "Dataset", lambda X, y, **kw: real(
        X[:len(y) // 2], y[:len(y) // 2], **kw))


def alter_an_answer(monkeypatch):
    """One leaf value of every tree moved by 1% where the tree is made."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    real = GBDT._extract_host_tree

    def altered(self, t):
        ht = real(self, t)
        ht.leaf_value[0] *= 1.01
        return ht
    monkeypatch.setattr(GBDT, "_extract_host_tree", altered)


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct_and_each_fault_is_not(cell, monkeypatch):
    assert drive(monkeypatch, cell)["correct"] is True
    for fault, caught_by in ((freeze_scores, "leaf_value_gap"),
                             (half_the_batch, "count_mismatch"),
                             (alter_an_answer, "score_gap")):
        with monkeypatch.context() as m:
            fault(m)
            line = drive(m, cell)
        assert line["correct"] is False, fault.__name__
        assert line["compared"][caught_by]["ok"] is False, fault.__name__


@pytest.mark.parametrize("cell", CELLS)
def test_the_bfloat16_control_is_not_correct(cell, monkeypatch):
    """The reference in the program's place, gradients and hessians
    rounded to bfloat16 before they are summed, fails the cell's limits."""
    seen = {}
    real = train_window.judge

    def keep(ctx, X, y, model_text, scores):
        seen.update(ctx=ctx, X=X, y=y, model_text=model_text)
        return real(ctx, X, y, model_text, scores)
    monkeypatch.setattr(train_window, "judge", keep)
    assert drive(monkeypatch, cell)["correct"] is True
    ctx, X, y = seen["ctx"], seen["X"], seen["y"]
    check, params = ctx["workload"]["check"], ctx["config"]["params"]
    judged = ref.parse_trees(seen["model_text"])[:check["follow_trees"]]
    nodes = ref.draw_nodes(ctx["seed"], judged, check["regret_nodes"])
    exact, low = (ref.follow(X, y, judged, params["learning_rate"], 0.0,
                             nodes, train_window.search_of(ctx),
                             grad_cast=cast)
                  for cast in (None, ref.bfloat16_round))
    got = ref.readings(in_programs_place(judged, low), exact)
    got["score_gap"] = 0.0   # its scores are the sums of its own leaves
    compared, correct = train_window.hold(got, check["limits"])
    assert correct is False, compared
    assert compared["count_mismatch"]["ok"], compared
