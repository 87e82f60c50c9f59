#!/usr/bin/env python3
"""Generate docs/Parameters.md from the config table.

The reference generates src/io/config_auto.cpp FROM docs/Parameters.rst
(doc-is-source-of-truth); here the direction is inverted — config.py's
typed table is the source of truth and the doc is derived, so the two can
never drift. Run: python tools/gen_params_doc.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from lightgbm_tpu.config import _PARAMS  # noqa: E402

HEADER = """# Parameters

Generated from `lightgbm_tpu/config.py` by `tools/gen_params_doc.py` —
do not edit by hand. Keys and aliases follow the reference's parameter
table (include/LightGBM/config.h); values are parsed from Python dicts,
CLI `key=value` pairs, and `#`-commented config files alike.

| Parameter | Type | Default | Aliases |
|---|---|---|---|
"""


NOTES = """
## Notes

**`boosting=goss`** (`top_rate`, `other_rate`; `boosting/goss.py`). As
upstream's `goss.hpp`: the first `int(1 / learning_rate)` iterations train
on all rows; every later one on a bag of the `int(N * top_rate)` rows of
largest |gradient x hessian| at weight 1 (ties at the threshold go to the
lower row ids) and EXACTLY `int(N * other_rate)` of the rest, drawn
uniformly without replacement from the `bagging_seed` chain, at weight
`(N - top_cnt) / other_cnt`. Where the exact grower runs over the row
partition on one device (`tree_learner=serial`, `tree_growth=exact`, no
CEGB; multiclass where the classes grow in sequence, which is the TPU's
way) the bag IS the partition: the rows out of it cost no histogram pass,
a sampled tree's `leaf_count` / `internal_count` are integer counts of
in-bag rows, and every row still takes every tree's score (all rows are
routed in row space). The unsampled and the sampled iterations are two
device programs there, the first being `boosting=gbdt`'s;
`GBDT.compile_block(n)` readies the next one without running it, and
`GBDT.last_bag` holds the newest bag on the device. Everywhere else
(vmapped multiclass, `tree_growth=batched|frontier`, streaming, every mesh
learner, CEGB) the sampler stays a multiplier on gradient, hessian and
sample mask inside one program: a `lax.top_k` threshold and a Bernoulli
draw of the rest at `other_cnt / (N - top_cnt)`, every row in every pass,
counts summed in float32. No option selects between the two.

**`categorical_feature`** (`max_cat_threshold`, `cat_l2`, `cat_smooth`,
`max_cat_to_onehot`, `min_data_per_group`; `core/split.py`
`per_feature_split_categorical`). Upstream's candidates, order and
tie-breaks: one-vs-rest where a column has at most `max_cat_to_onehot`
bins, else the categories with at least `cat_smooth` rows sorted by
`sum_gradient / (sum_hessian + cat_smooth)` and searched from both ends;
the children of a sorted-subset split are valued under
`lambda_l2 + cat_l2`. A column keeps its `max_bin - 1` most frequent
categories of the sampled rows (99% coverage); bin 0 is the catch-all of
every other id, NaN and negatives, and always goes right. The finder runs
over the categorical columns alone (their number is static a data set,
which they are is data). On the chip path (`tree_growth=exact` over the
row partition: serial, a GOSS bag, `tree_learner=data`) a split's category
set is tested by selects on its eight words, no gather over the routed
rows; `tree_growth=batched|frontier` test per-row sets through a
`take_along_axis`, and `tree_learner=feature` sends a device's whole slice
of the columns through the finder. A node's raw-value set in the model
text is as wide as the largest id going left: label-encode sparse ids.
"""


def main() -> None:
    rows = []
    for name, typ, default, aliases in _PARAMS:
        tname = getattr(typ, "__name__", str(typ))
        dflt = repr(default) if default != "" else "`\"\"`"
        rows.append("| `%s` | %s | %s | %s |" % (
            name, tname, dflt,
            ", ".join("`%s`" % a for a in aliases) if aliases else "—"))
    out = HEADER + "\n".join(rows) + "\n" + NOTES
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "Parameters.md")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write(out)
    print("wrote %s (%d parameters)" % (os.path.normpath(path), len(rows)))


if __name__ == "__main__":
    main()
