"""The training plan (``boosting/plan.py`` ``resolve_plan``) of each
benchmark cell at its true size, on a described TPU v5e, resolved on the
CPU with no device touched; the row-space fallback where the copy does not
fit; and each refused combination's message.
"""
import glob
import json
import os
import re

import jax
import pytest

from lightgbm_tpu.boosting.plan import (DATA, EXACT, SERIAL, TrainFacts,
                                        resolve_plan)
from lightgbm_tpu.config import Config
from lightgbm_tpu.core import partition
from lightgbm_tpu.core.histogram import hist_kernel
from lightgbm_tpu.log import LightGBMError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a TPU v5e's memory_stats()["bytes_limit"]: 15.75 GiB (PERF.md section 4)
V5E_BYTES_LIMIT = 16_911_433_728


def _cell(name):
    """A benchmark configuration (read only) as (Config, TrainFacts) on
    the chip: its rows and columns, its categorical columns, a TPU backend
    with the v5e's limit, and its mesh."""
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        conf = json.load(f)
    params = conf["params"]
    categorical = len([c for c in str(params.get(
        "categorical_feature", "")).split(",") if c])
    mesh = tuple(("data", int(s)) for s in params.get("mesh_shape", ()))
    return Config(dict(params)), TrainFacts(
        rows=conf["data"]["rows"], cols=conf["data"]["cols"],
        col_num_bin=(params["max_bin"],) * conf["data"]["cols"],
        categorical=categorical, single_columns=categorical == 0,
        mesh=mesh, backend="tpu", bytes_limit=V5E_BYTES_LIMIT,
        boosting=params.get("boosting", "gbdt"))


CELLS = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(ROOT, "bench", "configs", "*.json")))


@pytest.fixture
def no_device(monkeypatch):
    """Any device call while a plan resolves fails the test."""
    def touched(*a, **k):
        raise AssertionError("resolve_plan touched a device")
    for name in ("devices", "local_devices", "default_backend",
                 "device_put"):
        monkeypatch.setattr(jax, name, touched)


def test_every_cell_has_a_case():
    assert CELLS == sorted(EXPECT)


EXPECT = {
    # the exact grower over all rows on one chip, the Pallas kernels, leaf
    # ids in row space (the copy fits beside the table), no bag
    "criteo-1of64": dict(grower=EXACT, learner=SERIAL, hist_impl="pallas",
                         row_space_ids=True, copy=True, goss_bag=False,
                         windows=True, row_chunk=4096),
    "criteo-1of64-clicklog": dict(grower=EXACT, learner=SERIAL,
                                  hist_impl="pallas", row_space_ids=True,
                                  goss_bag=False, replay_kernel=True),
    "criteo-1of48-categorical": dict(grower=EXACT, learner=SERIAL,
                                     hist_impl="pallas", row_space_ids=True,
                                     goss_bag=False, replay_kernel=False),
    # the partition starts from the bag; the unsampled iterations keep
    # boosting=gbdt's ids from the partition
    "criteo-1of64-clicklog-goss": dict(grower=EXACT, goss_bag=True,
                                       copy=True, row_space_ids=False,
                                       all_rows_in_bag=True),
    "criteo-1of64-clicklog-dart": dict(grower=EXACT, copy=True,
                                       row_space_ids=True,
                                       replay_kernel=True, fuse_blocks=True),
    # four chips: the data learner's row partition, its ids from the
    # partition, the counts the chips' integers
    "criteo-1of18-clicklog-data4": dict(grower=EXACT, learner=DATA,
                                        hist_impl="pallas", copy=False,
                                        row_space_ids=False,
                                        mesh_exact_counts=True,
                                        row_multiple=4),
}


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_plans_its_path_at_its_true_size(cell, no_device):
    cfg, facts = _cell(cell)
    plan = resolve_plan(cfg, facts)
    got = {k: getattr(plan, k) for k in EXPECT[cell]}
    assert got == EXPECT[cell], cell
    assert hist_kernel(plan.hist_impl) == (True, False)   # Pallas, compiled
    assert plan.cat_route_gather_free == (facts.categorical > 0)


def test_a_copy_that_does_not_fit_keeps_the_partitions_ids(no_device):
    cfg, facts = _cell("criteo-1of64-clicklog")
    need = partition.row_space_bytes(facts.rows, facts.cols)
    assert resolve_plan(cfg, TrainFacts(**dict(
        facts.__dict__, bytes_limit=need + need // 9 + 1))).row_space_ids
    plan = resolve_plan(cfg, TrainFacts(**dict(facts.__dict__,
                                               bytes_limit=need)))
    assert not plan.row_space_ids and not plan.copy
    assert not plan.replay_kernel and plan.grower == EXACT
    # DART holds the copy for its replay whatever the limit says
    dart, dart_facts = _cell("criteo-1of64-clicklog-dart")
    assert resolve_plan(dart, TrainFacts(**dict(
        dart_facts.__dict__, bytes_limit=need))).row_space_ids


GATES = {
    "stream_needs_frontier": (
        {}, dict(streamed=True),
        "streamed training requires tree_growth=frontier"),
    "stream_needs_f32": (
        {"tree_growth": "frontier", "gpu_use_dp": True}, dict(streamed=True),
        "streamed training accumulates f32 wave histograms"),
    "stream_mesh_data_only": (
        {"tree_growth": "frontier", "data_stream_chunk_rows": 256},
        dict(streamed=True, mesh=(("feature", 4),)),
        "streamed mesh training is data-parallel only"),
    "stream_no_feature_axis": (
        {"tree_growth": "frontier", "data_stream_chunk_rows": 256},
        dict(streamed=True, mesh=(("data", 2), ("feature", 2))),
        "streamed training cannot shard the feature axis"),
    "stream_mesh_chunk_rows": (
        {"tree_growth": "frontier"},
        dict(streamed=True, mesh=(("data", 4),)),
        "needs an explicit data_stream_chunk_rows"),
    "stream_shard_without_mesh": (
        {"tree_growth": "frontier"},
        dict(streamed=True, shard_world=2, shard_rank=1),
        "ingested as shard 1/2 but no mesh is configured; "
        "set mesh_shape=[2]"),
    "mesh_efb": (
        {"tree_learner": "data"}, dict(mesh=(("data", 4),), bundled=True),
        "EFB bundles / nbit-packed columns are not yet supported"),
    "voting_forced": (
        {"tree_learner": "voting"}, dict(mesh=(("data", 4),), num_forced=1),
        "not supported with the voting-parallel tree learner"),
    "waves_cegb": (
        {"tree_growth": "batched", "cegb_penalty_split": 1e-4}, {},
        "tree_growth=batched requires exact split ordering"),
    "waves_feature_learner": (
        {"tree_growth": "frontier", "tree_learner": "feature"},
        dict(mesh=(("feature", 4),)),
        "tree_growth=frontier does not support tree_learner=feature"),
}


@pytest.mark.parametrize("gate", sorted(GATES))
def test_each_refused_combination_raises_its_message(gate, no_device):
    params, facts, message = GATES[gate]
    with pytest.raises(LightGBMError, match=re.escape(message)):
        resolve_plan(Config(params),
                     TrainFacts(rows=1000, cols=8, **facts))


@pytest.mark.parametrize("growth", ["batched", "frontier"])
def test_f64_histograms_fall_back_to_the_exact_grower_and_say_so(growth):
    plan = resolve_plan(Config({"tree_growth": growth, "gpu_use_dp": True,
                                "tpu_hist_impl": "pallas"}),
                        TrainFacts(rows=1000, cols=8, backend="tpu"))
    assert plan.grower == EXACT and plan.hist_dtype == "f64"
    assert plan.hist_impl == "matmul"
    assert [level for level, _ in plan.log] == ["warning", "warning"]
    assert "falling back to exact growth" in plan.log[0][1]


def test_a_table_of_no_stored_column_plans_no_copy():
    """Every feature constant: no stored column, so no feature-major copy,
    no row-space ids and no replay kernel; the booster still builds."""
    import numpy as np
    import lightgbm_tpu as lgb
    X = np.ones((200, 3))
    y = (np.arange(200) % 2).astype(float)
    g = lgb.Booster({"objective": "binary", "verbose": -1},
                    lgb.Dataset(X, y))._impl
    plan = g.plan
    assert plan.facts.cols == 0 and plan.grower == EXACT
    assert not (plan.copy or plan.row_space_ids or plan.replay_kernel)
    assert g._bins_by_col is None
