"""Subprocess worker for the kill-and-resume checkpoint test.

Three modes driven by argv: ``golden`` trains the full run uninterrupted,
``victim`` raises SIGTERM in itself mid-train (the checkpoint callback must
snapshot at the iteration boundary and re-raise, so the process dies with
the real signal exit status), ``resume`` continues the victim's directory to
the full round count and writes the final model text for byte comparison.
"""
import os
import signal
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu import callback, engine
from lightgbm_tpu.profiling import enable_compile_cache

enable_compile_cache()

NUM_ROUNDS = 8
KILL_AT = 3


class _KillAt:
    """Raises SIGTERM in our own process right before iteration ``k``."""
    before_iteration = True
    order = 0

    def __init__(self, k):
        self.k = k

    def __call__(self, env):
        if env.iteration - env.begin_iteration == self.k:
            os.kill(os.getpid(), signal.SIGTERM)


def main():
    ckpt_dir, mode = sys.argv[1], sys.argv[2]
    r = np.random.RandomState(7)
    X = r.randn(150, 5)
    y = (X[:, 0] + 0.3 * r.randn(150) > 0).astype(np.float64)
    params = dict(objective="binary", num_leaves=4, verbosity=0,
                  bagging_fraction=0.7, bagging_freq=1)
    ds = lgb.Dataset(X, label=y, params=dict(params))
    cbs = [callback.checkpoint(ckpt_dir, period=1)]
    if mode == "victim":
        cbs.append(_KillAt(KILL_AT))
    bst = engine.train(dict(params), ds, num_boost_round=NUM_ROUNDS,
                       callbacks=cbs,
                       resume_from=(ckpt_dir if mode == "resume" else None),
                       verbose_eval=False)
    if mode in ("golden", "resume"):
        with open(os.path.join(ckpt_dir, "final_model.txt"), "w") as f:
            f.write(bst.model_to_string())


if __name__ == "__main__":
    main()
