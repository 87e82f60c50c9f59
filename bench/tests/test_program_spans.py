"""bench/readers/program_spans.py on a hand-made list of recorded spans,
and every ``program_spans`` metric of BENCHMARK.json against the spans the
program records in a toy run on the CPU (a count of what is read, never a
time under a device metric's name).

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_program_spans.py -q
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.readers import program_spans

MS = 1_000_000


def span(i, name, start_ms, dur_ms, parent=None, **counts):
    return {"id": i, "name": name, "start_ns": start_ms * MS,
            "end_ns": (start_ms + dur_ms) * MS, "parent": parent,
            "thread": 1, "failed": False, "counts": counts}


SPANS = [
    span(1, "ingest.construct", 0, 100, rows=50),
    span(2, "ingest.to_float64", 0, 10, parent=1, bytes_copied=0),
    span(3, "ingest.to_float64", 10, 20, parent=1, bytes_copied=400),
    span(4, "ingest.find_bins", 30, 40, parent=1),
    # the first block: it compiles
    span(10, "train.block", 200, 1000, count=1),
    span(11, "train.block_prepare", 200, 50, parent=10),
    span(12, "jax.trace", 210, 5, parent=11, fun_name="add"),
    span(13, "train.block_dispatch", 250, 900, parent=10),
    span(14, "jax.trace", 260, 300, parent=13, fun_name="run_block"),
    span(15, "jax.lower", 560, 100, parent=13, fun_name="jit(run_block)"),
    span(16, "jax.backend_compile", 660, 400, parent=13,
         fun_name="jit(run_block)"),
    # two more blocks, and a trace outside any block
    span(20, "train.block", 2000, 10, count=1),
    span(21, "train.block_prepare", 2000, 2, parent=20),
    span(22, "train.block_dispatch", 2002, 6, parent=20),
    span(30, "train.block", 3000, 10, count=1),
    span(31, "train.block_prepare", 3000, 4, parent=30),
    span(32, "train.block_dispatch", 3004, 2, parent=30),
    span(40, "jax.trace", 4000, 7, fun_name="later"),
]


@pytest.mark.parametrize("spec, want", [
    ({"span": "ingest.to_float64", "what": "sum_s"}, 0.030),
    ({"span": "ingest.to_float64", "which": "first", "what": "sum_s"}, 0.010),
    ({"span": "ingest.to_float64", "what": "bytes_copied"}, 400),
    ({"span": "ingest.construct", "what": "rows"}, 50),
    ({"span": ["jax.trace", "jax.lower"], "under": "train.block",
      "which": "first", "what": "sum_s"}, 0.405),
    ({"span": "train.block_prepare", "under": "train.block",
      "which": "after_first", "what": "mean_ms"}, 3.0),
    ({"span": "train.block_dispatch", "under": "train.block",
      "which": "after_first", "what": "mean_ms"}, 4.0),
    ({"span": "train.block_dispatch", "which": "all", "what": "mean_ms"},
     908 / 3),
    # nothing to read is nothing, never 0
    ({"span": "ingest.stack", "what": "sum_s"}, None),
    ({"span": "ingest.find_bins", "what": "bytes_copied"}, None),
    ({"span": "jax.trace", "under": "ingest.construct", "what": "sum_s"},
     None),
    ({"span": "train.block", "which": "after_first", "under": "train.setup",
      "what": "sum_s"}, None),
])
def test_reduce_on_a_hand_made_span_list(spec, want):
    got = program_spans.reduce(SPANS, dict({"reader": "program_spans"},
                                           **spec))
    assert got is None if want is None else got == pytest.approx(want)


def test_unknown_which_is_an_error():
    with pytest.raises(ValueError):
        program_spans.reduce(SPANS, {"span": "train.block", "which": "last",
                                     "what": "sum_s"})


def test_a_program_without_the_recorder_reads_as_nothing(monkeypatch):
    from lightgbm_tpu.obs import trace
    monkeypatch.delattr(trace, "recorded_spans")
    assert program_spans.read({"span": "train.block", "what": "sum_s"},
                              {}) is None


def test_every_program_span_metric_reads_a_toy_run():
    import lightgbm_tpu as lgb
    X = np.random.RandomState(5).randn(500, 6)
    y = (X[:, 0] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 5, "max_bin": 31,
              "verbose": -1}
    ds = lgb.Dataset(X, y, params=dict(params)).construct()
    gbdt = lgb.train(params, ds, num_boost_round=1)._impl
    for _ in range(3):
        gbdt.train_many(1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    read = 0
    for m in bench["per_layer"]:
        with open(os.path.join(ROOT, "bench", "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] != "program_spans":
            continue
        assert m["source"] == "program_span"
        value = program_spans.read(spec, {})
        assert value is not None and value > 0, m["name"]
        read += 1
    assert read == 8
