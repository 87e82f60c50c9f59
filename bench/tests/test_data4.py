"""The data-parallel cell (criteo_clicklog_data4_train) on the CPU: its
rehearsal on four virtual devices, the bfloat16 control against its limits,
the job's refusals, the sharded generator, and the four metrics it brings
read by their readers from a hand-made traced run. (Its ``--rehearsal`` run
on the devices a process has, and the three faults every training cell is
held to, are test_run.py's.)

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

from bench import run as bench_run
from bench import reference_data_parallel, work_exchange
from bench.generators import clicklog, clicklog_shards
from bench.jobs import data4_window
from bench.readers import (exchange_roofline, mesh_busy_spread,
                           phase_unit_cost, program_spans, trace_phases)
from bench.tests import readings_data4

CELL = "criteo_clicklog_data4_train"
SIBLING = "criteo_clicklog_train"
NEW_METRICS = ("exchange_ms", "exchange_us_per_call", "exchange_roofline",
               "mesh_busy_spread_pct")


def drive(monkeypatch, seed=2147483703, trace=1):
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", CELL, "--seed", str(seed), "--seconds",
        "0.5", "--trace", str(trace), "--rehearsal"])
    return bench_run.main()


def test_the_rehearsal_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELL,
                        "--seed", "2147483710", "--seconds", "1", "--trace",
                        "1", "--rehearsal"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "mesh 4 chips" in p.stdout
    # the window's blocks load the program set-up compiled
    assert "0 compiles in it" in p.stdout, p.stdout
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
    assert line["compared"]["count_mismatch"]["value"] == 0
    # what the program's own spans give already off the chip
    assert set(SPAN_METRICS) <= set(line["would_report"]), line
    assert not set(PINNED) & set(line["would_report"]), line


# the cell's metrics that read the program's spans, not a device trace
SPAN_METRICS = ("block_prepare_ms", "block_dispatch_ms",
                "first_block_trace_lower_s")
# the work-count, phase and entry metrics whose cells test_work_readers.py
# lists: the cell does not report them
PINNED = ("partition_tiles_per_iter", "hist_tiles_per_iter",
          "split_rows_per_iter", "hist_rows_traced_per_iter",
          "hist_ns_per_row", "row_gather_us_per_tile", "row_gather_ms",
          "startup_before_program_s", "program_import_s", "engine_train_s",
          "setup_unattributed_s")


def test_the_control_is_not_correct(monkeypatch):
    """bench/tests/readings_data4.py's variants of a rehearsal: the program
    passes, the bfloat16 control fails the gaps, the flipped model the
    counts."""
    monkeypatch.setattr(reference_data_parallel, "readings",
                        reference_data_parallel.readings)
    seen = readings_data4.keep_the_follow()
    line = drive(monkeypatch)
    assert line["correct"] is True, line["compared"]
    got = readings_data4.variants(
        seen, {k: c["value"] for k, c in line["compared"].items()}, True)
    assert got["fails"]["program"] == []
    assert "count_mismatch" not in got["fails"]["control"], got["control"]
    assert {"leaf_value_gap", "split_gain_gap"} <= set(
        got["fails"]["control"]), got["control"]
    assert "count_mismatch" in got["fails"]["flipped"], got["flipped"]


def test_the_job_refuses_a_program_that_counts_a_mesh_in_float32(
        monkeypatch):
    """The parent's program records no ``mesh_exact_counts``."""
    monkeypatch.setattr(data4_window, "span_counts", lambda name: {})
    with pytest.raises(SystemExit) as e:
        drive(monkeypatch, trace=0)
    assert "does not count the leaves of a mesh in integers" in str(e.value)


def test_a_chip_run_needs_the_cells_chips():
    import jax
    cfg = bench_run.load_json(ROOT, "bench", "configs",
                              "criteo-1of18-clicklog-data4.json")
    want = len(jax.devices()) + 1
    ctx = {"rehearsal": False, "workload": {"block_iters": 1},
           "config": dict(cfg, params=dict(cfg["params"],
                                           mesh_shape=[want]))}
    with pytest.raises(SystemExit) as e:
        data4_window.run(ctx)
    assert "needs %d chips" % want in str(e.value)


def test_block_zero_is_the_one_chip_cells_table():
    data = dict(bench_run.load_json(ROOT, "bench", "configs",
                                    "criteo-1of18-clicklog-data4.json")
                ["rehearsal"]["data"])
    X, y = clicklog_shards.generate(5, **data)
    per = data["rows"] // data["shards"]
    one = {k: v for k, v in data.items() if k not in ("rows", "shards")}
    x0, y0 = clicklog.generate(5, per, **one)
    assert X.shape == (data["rows"], data["cols"]) and X.flags.f_contiguous
    np.testing.assert_array_equal(X[:per], x0)
    np.testing.assert_array_equal(y[:per], y0)
    # the other blocks are other tables of the same roles
    assert not np.array_equal(X[per:2 * per], x0, equal_nan=True)
    assert abs(y[per:].mean() - data["positive_share"]) < 0.02
    with pytest.raises(ValueError):
        clicklog_shards.generate(5, **dict(data, rows=data["rows"] + 1))


def test_the_check_sizes_are_the_siblings():
    mine = bench_run.load_json(bench_run.HERE, "workloads",
                               CELL + ".json")["check"]
    theirs = bench_run.load_json(bench_run.HERE, "workloads",
                                 SIBLING + ".json")["check"]
    assert sorted(mine["limits"]) == sorted(theirs["limits"])
    for key in ("follow_trees", "sample_rows", "regret_nodes", "grid_cells",
                "search_rows"):
        assert mine[key] == theirs[key], key


def recorded():
    return bench_run.load_json(bench_run.HERE, "tests",
                               "readings_%s.json" % CELL)["numbers"]


@pytest.mark.parametrize("number", sorted(recorded()))
def test_a_limit_lies_between_its_two_readings(number):
    """The cell's limits against the readings they were set from
    (readings_criteo_clicklog_data4_train.json): the largest a sound run
    read passes, the smallest its control or planted fault read fails,
    with room on both sides."""
    limits = bench_run.load_json(bench_run.HERE, "workloads",
                                 CELL + ".json")["check"]["limits"]
    assert sorted(limits) == sorted(recorded())
    read = recorded()[number]
    limit = {number: limits[number]}
    assert data4_window.hold({number: read["lower"]}, limit)[1], read
    assert not data4_window.hold({number: read["upper"]}, limit)[1], read
    if limits[number]:   # room on both sides: twice at the least
        assert 2 * read["lower"] <= limits[number] <= read["upper"] / 2


# a traced iteration of a four-chip run, as the job hands it on: the
# exchange's device seconds (averaged over the chips), each chip's busy
# time, and the spans the program recorded
TRACE = {"iters": 1, "first_iter": 1, "window_s": 5.0, "busy_s": 4.7425,
         "busy_s_per_chip": [4.8, 4.7, 4.75, 4.72]}
PHASES = {"by_scope": {"lgbm.exchange": 0.02, "lgbm.row_gather": 2.4}}
SPANS = [{"id": 1, "name": "train.setup", "start_ns": 0, "end_ns": 1,
          "parent": None, "counts": {"mesh_chips": 4,
                                     "mesh_exact_counts": 1}},
         {"id": 2, "name": "train.block", "start_ns": 2, "end_ns": 3,
          "parent": None, "counts": {"start_iter": 1, "count": 1,
                                     "exchanges": 512,
                                     "exchange_bytes": 52_000_000}}]
RESULT = {"trace": TRACE, "phases": PHASES, "device_kind": "TPU v5 lite"}


def read(name, result):
    spec = bench_run.load_json(bench_run.HERE, "metrics", name + ".json")
    return bench_run.load_module("readers", spec["reader"]).read(spec, result)


@pytest.mark.parametrize("name,want", [
    ("exchange_ms", 20.0),                       # 0.02 s, one iteration
    ("exchange_us_per_call", 0.02 / 512 * 1e6),
    # 2 x 3 / 4 x 52 MB over 200 GB/s = 0.39 ms of the 20 ms
    ("exchange_roofline", 100 * 78e6 / 2e11 / 0.02),
    ("mesh_busy_spread_pct", 100 * 0.1 / 5.0)])
def test_a_new_metric_on_a_hand_made_traced_run(monkeypatch, name, want):
    monkeypatch.setattr(program_spans, "recorded", lambda: SPANS)
    assert abs(read(name, RESULT) - want) < 1e-9 * max(1.0, want)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_nothing_where_there_is_nothing(monkeypatch,
                                                           name):
    monkeypatch.setattr(program_spans, "recorded", lambda: SPANS)
    assert read(name, dict(RESULT, trace=None)) is None
    # the parent's program: no exchange counts, no mesh count, no scope
    old = [dict(s, counts={k: v for k, v in s["counts"].items()
                           if not k.startswith(("exchange", "mesh"))})
           for s in SPANS]
    monkeypatch.setattr(program_spans, "recorded", lambda: old)
    bare = dict(RESULT, phases={"by_scope": {"lgbm.row_gather": 2.4}},
                trace=dict(TRACE, busy_s_per_chip=[4.8]))
    assert read(name, bare) is None


def test_the_job_reads_each_chip_of_a_capture(monkeypatch):
    """Two chips' leaf ops, one busier: the reduced trace averages them
    and keeps each chip's busy time, the scope table comes over the chips
    and for each."""
    from lightgbm_tpu.obs import trace as obs_trace
    chips = [[("fusion.1", 0, 100), ("all-gather.2", 100, 50)],
             [("fusion.1", 0, 60), ("all-gather.2", 100, 50)]]
    scoped = [[("fusion.1 jit(b)/lgbm.row_gather/gather", s, d)
               if n == "fusion.1" else
               ("all-gather.2 jit(b)/lgbm.exchange/all_gather", s, d)
               for n, s, d in chip] for chip in chips]
    monkeypatch.setattr(data4_window.trace_reduce, "load_events",
                        lambda d: {"devices": chips, "host": []})
    monkeypatch.setattr(obs_trace, "load_capture",
                        lambda d: {"devices": scoped, "host": []})
    trace, phases, per_chip = data4_window.read_trace(
        "unused", {"wall_s": 1e-6, "iters": 1, "first_iter": 1})
    assert trace["busy_s_per_chip"] == [150e-9, 110e-9]
    assert abs(trace["busy_s"] - 130e-9) < 1e-18
    assert abs(phases["by_scope"]["lgbm.exchange"] - 50e-9) < 1e-18
    assert [p["by_scope"]["lgbm.row_gather"] for p in per_chip] == \
        [100e-9, 60e-9]
    result = {"trace": trace}
    assert abs(mesh_busy_spread.read({}, result) - 100 * 40e-9 / 1e-6) \
        < 1e-9


def test_the_exchange_bound_and_its_peak():
    assert work_exchange.allreduce_bytes_sent(100, 4) == 150.0
    assert work_exchange.allreduce_bytes_sent(100, 1) == 0.0
    assert exchange_roofline.ici_peak("TPU v5 lite") == 2.0e11
    with pytest.raises(ValueError):
        exchange_roofline.ici_peak("cpu")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_lists_the_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = bench_run.find(json.load(f)["per_layer"], name, "metric")
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "collectives"
    assert entry["moves"] == "train_s_per_iter"
    spec = bench_run.load_json(bench_run.HERE, "metrics", name + ".json")
    assert bench_run.load_module("readers", spec["reader"]) in (
        trace_phases, phase_unit_cost, exchange_roofline, mesh_busy_spread)
