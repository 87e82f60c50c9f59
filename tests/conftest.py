"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's test stance (tests are end-to-end through the Python
API, SURVEY.md §4) plus what the reference lacks: multi-device collectives are
exercised on a virtual CPU mesh (xla_force_host_platform_device_count) so the
data/feature/voting-parallel code paths run in CI without a TPU pod.
"""
import os

# XLA_FLAGS is read when the CPU client is created, which is still ahead of
# us even if jax was already imported (e.g. by a pytest plugin).
os.environ["JAX_PLATFORMS"] = "cpu"   # for any subprocesses we spawn
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# jax may have been imported before this conftest (pytest plugins), in which
# case it latched JAX_PLATFORMS from the original environment; config.update
# still wins as long as no backend exists yet.
jax.config.update("jax_platforms", "cpu")

# persistent compilation cache: the tree-growth graph is expensive to compile
# on the CPU backend; cache hits make repeat test runs fast. Placed by the
# package's own helper: JAX_COMPILATION_CACHE_DIR when set, else
# <checkout>/.jax_cache. Everything is cached, small programs too: a warm
# tier-1 run takes ~460 s against ~730 s with only the slow compiles kept,
# and a cold one is no slower for the writes (972 s against 1007 s).
from lightgbm_tpu.profiling import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests (long training runs, multi-device meshes, "
        "fuzz sweeps) excluded from the tier-1 fast suite so it fits the "
        "870s budget; run the full suite with -m '' or just -m slow")


@pytest.fixture
def rng():
    return np.random.RandomState(42)


def make_binary(n=2000, f=10, seed=7):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    logit = X[:, 0] + 2.0 * X[:, 1] * (X[:, 2] > 0) - X[:, 3] ** 2 + \
        0.5 * r.randn(n)
    y = (logit > 0).astype(np.float64)
    return X, y


def make_regression(n=2000, f=10, seed=11):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    y = X[:, 0] * 3 + np.abs(X[:, 1]) + np.sin(X[:, 2] * 2) + 0.1 * r.randn(n)
    return X, y


def make_multiclass(n=2000, f=10, k=4, seed=13):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    centers = r.randn(k, f) * 2
    d = ((X[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
    y = np.argmin(d, axis=1).astype(np.float64)
    return X, y


def make_ranking(num_queries=100, per_query=20, f=8, seed=17):
    r = np.random.RandomState(seed)
    n = num_queries * per_query
    X = r.randn(n, f)
    rel = X[:, 0] + 0.5 * X[:, 1] + 0.3 * r.randn(n)
    y = np.zeros(n)
    for q in range(num_queries):
        s = slice(q * per_query, (q + 1) * per_query)
        ranks = np.argsort(np.argsort(-rel[s]))
        y[s] = np.where(ranks < 2, 3, np.where(ranks < 5, 1, 0))
    group = np.full(num_queries, per_query, dtype=np.int64)
    return X, y, group
