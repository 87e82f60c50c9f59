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
