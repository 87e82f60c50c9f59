"""Capability matrix for the fast-path feature combinations: every combination of (learner) x (growth mode) x (forced/CEGB/
plain) x (pool cap) x (classes) must either train on its EXPECTED path —
asserted via the engagement flags, so a refactor cannot silently land a
config on the O(N x leaves) masked fallback — or refuse loudly with
LightGBMError. No silent third option.
"""
import json
import os
import tempfile

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.log import LightGBMError, Log
from lightgbm_tpu.obs.trace import recorded_spans

from conftest import make_binary, make_multiclass


def _data(multiclass=False, n=1200, f=6):
    if multiclass:
        X, y = make_multiclass(n=n, f=f, k=3)
        return X, y.astype(int)
    return make_binary(n=n, f=f)


def _forced_file():
    f = tempfile.NamedTemporaryFile("w", suffix=".json", delete=False)
    json.dump({"feature": 0, "threshold": 0.0}, f)
    f.close()
    return f.name


# rows: (case id, params overrides, expectation)
# expectation: "raise" | dict of engagement flags to assert
#   part_mesh -> _partition_on_mesh, fp -> _explicit_fp,
#   use_part -> grow_params.use_partition, pool -> grow_params.pool_slots>0,
#   vmapped -> grow_params.vmapped_classes,
#   batch -> grow_params.batch_splits>0,
#   frontier -> grow_params.frontier_mode,
#   frontier_rs -> grow_params.frontier_rs
#   bag -> _goss_bag (GOSS grows its sampled trees on a bag partition)
#   cat_free -> train.setup's cat_route_gather_free (a categorical split
#     routes its rows without a gather over them: the exact grower's
#     one-split form; the wave growers test per-row sets through
#     take_along_axis). "CATEGORICAL" makes the last column one
# "WARNS" in the overrides: a warning holding that text must be logged
_F64_WARNING = "does not support f64 histograms yet; falling back to exact"
MATRIX = [
    ("serial-plain", {}, dict(use_part=True, part_mesh=False, fp=False,
                              cat_free=False)),
    ("serial-categorical", {"CATEGORICAL": True},
     dict(use_part=True, cat_free=True)),
    ("serial-categorical-goss", {"CATEGORICAL": True, "boosting": "goss"},
     dict(use_part=True, bag=True, cat_free=True)),
    ("data-categorical", {"CATEGORICAL": True, "tree_learner": "data",
                          "mesh_shape": [8]},
     dict(part_mesh=True, cat_free=True)),
    ("batched-categorical", {"CATEGORICAL": True, "tree_growth": "batched"},
     dict(batch=True, cat_free=False)),
    ("frontier-categorical", {"CATEGORICAL": True,
                              "tree_growth": "frontier"},
     dict(frontier=True, cat_free=False)),
    ("serial-forced", {"FORCED": True}, dict(use_part=True)),
    ("serial-cegb", {"cegb_tradeoff": 0.5,
                     "cegb_penalty_split": 1e-4}, dict(use_part=True)),
    ("serial-pool", {"histogram_pool_size": 1e-4},
     dict(use_part=True, pool=True)),
    ("serial-batched", {"tree_growth": "batched"},
     dict(batch=True, use_part=True)),
    ("data-plain", {"tree_learner": "data", "mesh_shape": [8]},
     dict(part_mesh=True, use_part=True, fp=False)),
    ("data-forced", {"tree_learner": "data", "mesh_shape": [8],
                     "FORCED": True},
     dict(part_mesh=True, use_part=True)),   # straight-line psum rebuild
    ("data-cegb", {"tree_learner": "data", "mesh_shape": [8],
                   "cegb_tradeoff": 0.5, "cegb_penalty_split": 1e-4},
     dict(part_mesh=True, use_part=True)),   # CEGB rides the shard_map
    ("data-batched", {"tree_learner": "data", "mesh_shape": [8],
                      "tree_growth": "batched"},
     dict(part_mesh=True, batch=True)),
    ("data-pool", {"tree_learner": "data", "mesh_shape": [8],
                   "histogram_pool_size": 1e-4},
     dict(part_mesh=True, pool=False)),          # cap off on meshes
    ("feature-plain", {"tree_learner": "feature", "mesh_shape": [8]},
     dict(fp=True)),
    ("feature-forced", {"tree_learner": "feature", "mesh_shape": [8],
                        "FORCED": True}, dict(fp=False)),
    ("feature-cegb", {"tree_learner": "feature", "mesh_shape": [8],
                      "cegb_tradeoff": 0.5, "cegb_penalty_split": 1e-4},
     dict(fp=False)),
    ("feature-batched", {"tree_learner": "feature", "mesh_shape": [8],
                         "tree_growth": "batched"}, "raise"),
    ("voting-plain", {"tree_learner": "voting", "mesh_shape": [8],
                      "top_k": 3}, dict(part_mesh=False, fp=False)),
    ("voting-forced", {"tree_learner": "voting", "mesh_shape": [8],
                       "FORCED": True}, "raise"),
    ("voting-cegb", {"tree_learner": "voting", "mesh_shape": [8],
                     "cegb_tradeoff": 0.5, "cegb_penalty_split": 1e-4},
     "raise"),
    ("voting-batched", {"tree_learner": "voting", "mesh_shape": [8],
                        "tree_growth": "batched"}, "raise"),
    ("batched-forced", {"tree_growth": "batched", "FORCED": True},
     "raise"),
    ("batched-cegb", {"tree_growth": "batched", "cegb_tradeoff": 0.5,
                      "cegb_penalty_split": 1e-4}, "raise"),
    ("mc-vmap", {"MULTICLASS": True}, dict(vmapped=True)),
    ("mc-pool-seq", {"MULTICLASS": True, "histogram_pool_size": 1e-4},
     dict(vmapped=False, pool=True)),
    # GOSS: the row partition starts from the bag where the exact grower
    # runs over it on one device; everywhere else the sampler is a mask
    ("goss-serial", {"boosting": "goss", "learning_rate": 0.5},
     dict(bag=True, use_part=True, batch=False, frontier=False)),
    ("goss-forced", {"boosting": "goss", "learning_rate": 0.5,
                     "FORCED": True}, dict(bag=True, use_part=True)),
    ("goss-pool", {"boosting": "goss", "learning_rate": 0.5,
                   "histogram_pool_size": 1e-4}, dict(bag=True, pool=True)),
    ("goss-cegb", {"boosting": "goss", "learning_rate": 0.5,
                   "cegb_tradeoff": 0.5, "cegb_penalty_split": 1e-4},
     dict(bag=False, use_part=True)),
    ("goss-mc-vmap", {"boosting": "goss", "learning_rate": 0.5,
                      "MULTICLASS": True}, dict(bag=False, vmapped=True)),
    ("goss-data", {"boosting": "goss", "learning_rate": 0.5,
                   "tree_learner": "data", "mesh_shape": [8]},
     dict(bag=False, part_mesh=True)),
    ("goss-stream", {"boosting": "goss", "learning_rate": 0.5,
                     "tree_growth": "frontier",
                     "data_stream_chunk_rows": 256},
     dict(bag=False, frontier=True)),
    ("goss-batched", {"boosting": "goss", "tree_growth": "batched"},
     dict(batch=True, bag=False)),
    ("dart-batched", {"boosting": "dart", "tree_growth": "batched"},
     dict(batch=True)),
    ("rf-batched", {"boosting": "rf", "tree_growth": "batched",
                    "bagging_freq": 1, "bagging_fraction": 0.8},
     dict(batch=True)),
    ("mc-batched", {"MULTICLASS": True, "tree_growth": "batched"},
     dict(batch=True, vmapped=True)),
    ("batched-bagging", {"tree_growth": "batched", "bagging_freq": 1,
                         "bagging_fraction": 0.6},
     dict(batch=True, frontier=False)),
    ("batched-f64", {"tree_growth": "batched", "gpu_use_dp": True,
                     "WARNS": _F64_WARNING},
     dict(batch=False, frontier=False, use_part=True)),  # exact grower
    ("serial-frontier", {"tree_growth": "frontier"},
     dict(frontier=True, frontier_rs=False, batch=False, use_part=True,
          part_mesh=False)),
    ("data-frontier", {"tree_learner": "data", "mesh_shape": [8],
                       "tree_growth": "frontier"},
     dict(part_mesh=True, frontier=True, frontier_rs=True)),
    ("data-frontier-psum", {"tree_learner": "data", "mesh_shape": [8],
                            "tree_growth": "frontier",
                            "tpu_frontier_rs": False},
     dict(part_mesh=True, frontier=True, frontier_rs=False)),
    ("voting-frontier", {"tree_learner": "voting", "mesh_shape": [8],
                         "top_k": 3, "tree_growth": "frontier"},
     dict(part_mesh=False, frontier=True, frontier_rs=False)),
    ("feature-frontier", {"tree_learner": "feature", "mesh_shape": [8],
                          "tree_growth": "frontier"}, "raise"),
    ("frontier-forced", {"tree_growth": "frontier", "FORCED": True},
     "raise"),
    ("frontier-cegb", {"tree_growth": "frontier", "cegb_tradeoff": 0.5,
                       "cegb_penalty_split": 1e-4}, "raise"),
    ("mc-frontier", {"MULTICLASS": True, "tree_growth": "frontier"},
     dict(vmapped=True, frontier=True)),
    ("goss-frontier", {"boosting": "goss", "tree_growth": "frontier"},
     dict(frontier=True, batch=False, bag=False)),
    ("rf-frontier", {"boosting": "rf", "tree_growth": "frontier",
                     "bagging_freq": 1, "bagging_fraction": 0.8},
     dict(frontier=True, batch=False)),
    ("frontier-f64", {"tree_growth": "frontier", "gpu_use_dp": True,
                      "WARNS": _F64_WARNING},
     dict(frontier=False, batch=False, use_part=True)),  # exact grower
]


@pytest.mark.parametrize("case,overrides,expect",
                         MATRIX, ids=[m[0] for m in MATRIX])
def test_capability_matrix(case, overrides, expect):
    overrides = dict(overrides)
    multiclass = overrides.pop("MULTICLASS", False)
    forced = overrides.pop("FORCED", False)
    warns = overrides.pop("WARNS", None)
    X, y = _data(multiclass=multiclass)
    if overrides.pop("CATEGORICAL", False):
        X = X.copy()
        X[:, -1] = np.digitize(X[:, -1], [-1.0, -0.3, 0.2, 0.9, 1.5])
        overrides["categorical_feature"] = str(X.shape[1] - 1)
    params = {"objective": "multiclass" if multiclass else "binary",
              "num_leaves": 15, "verbosity": 0 if warns else -1,
              "min_data_in_leaf": 5,
              **({"num_class": 3} if multiclass else {}),
              **overrides}
    path = None
    if forced:
        path = _forced_file()
        params["forcedsplits_filename"] = path
    logged = []
    Log.reset_callback(logged.append)
    try:
        if expect == "raise":
            with pytest.raises(LightGBMError):
                lgb.train(params, lgb.Dataset(X, y), num_boost_round=2)
            return
        bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=3)
        impl = bst._impl
        flags = dict(
            part_mesh=impl._partition_on_mesh,
            fp=getattr(impl, "_explicit_fp", False),
            use_part=impl.grow_params.use_partition,
            pool=impl.grow_params.pool_slots > 0,
            vmapped=impl.grow_params.vmapped_classes,
            batch=impl.grow_params.batch_splits > 0,
            frontier=impl.grow_params.frontier_mode,
            frontier_rs=impl.grow_params.frontier_rs,
            bag=impl._goss_bag,
            cat_free=bool([s for s in recorded_spans()
                           if s["name"] == "train.setup"][-1]["counts"]
                          .get("cat_route_gather_free")))
        for key, want in expect.items():
            assert flags[key] == want, (case, key, flags)
        if warns:
            assert any("Warning" in m and warns in m for m in logged), logged
        # and the model actually learned (no silently-dead path)
        pred = bst.predict(X, raw_score=not multiclass)
        if multiclass:
            acc = (np.argmax(pred, axis=1) == y).mean()
            assert acc > 0.7, (case, acc)
        else:
            from sklearn.metrics import roc_auc_score
            auc = roc_auc_score(y, pred)
            assert auc > 0.8, (case, auc)
    finally:
        Log.reset_callback(None)
        # gpu_use_dp turns x64 on for the process: not for the next test
        jax.config.update("jax_enable_x64", False)
        if path:
            os.unlink(path)
