"""lightgbm_tpu.obs — telemetry spans, metrics registry, health monitors.

Contracts pinned here (ISSUE 5):
- NaN injected into grad/hess is flagged within ONE iteration, in both
  warn mode (report recorded, training continues) and raise mode
  (LightGBMError before the next iteration trains);
- spans under observability=none are near-free and export nothing;
- Prometheus text exposition is byte-stable (golden string) so scrape
  configs can rely on it;
- the process-wide registry survives concurrent writers (serving
  micro-batch queue hammered from many threads while being scraped) with
  exact counts;
- turning the frontier grower's health accumulator on adds ZERO per-wave
  collectives — the psum count in the sharded jaxpr is identical with
  obs_health on and off (the "one extra scalar piggy-backed" guarantee).
"""
import os
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import callback, engine
from lightgbm_tpu.config import Config
from lightgbm_tpu.log import LightGBMError
from lightgbm_tpu.obs import (HEALTH_NONFINITE, HEALTH_WAVES, HealthMonitor,
                              MetricsRegistry, TrainingObs, health_vec)
from lightgbm_tpu.obs.registry import get_registry

from conftest import make_binary

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


# ------------------------------------------------------------ NaN injection
def _nan_fobj(bad_iters, calls):
    """Custom objective: logistic-ish grads, poisoned with NaN on the
    iterations listed in ``bad_iters``. Appends each call's index to
    ``calls`` so tests can pin exactly how far training got."""
    def fobj(preds, dataset):
        it = len(calls)
        calls.append(it)
        y = dataset.get_label()
        p = 1.0 / (1.0 + np.exp(-preds))
        grad = (p - y).astype(np.float32)
        hess = np.maximum(p * (1 - p), 1e-3).astype(np.float32)
        if it in bad_iters:
            grad[::7] = np.nan
        return grad, hess
    return fobj


def test_nan_injection_flagged_within_one_iteration_warn():
    """warn mode: the poisoned iteration is reported (at its exact index),
    training continues to completion, the anomaly counter advances."""
    X, y = make_binary(n=400, f=4)
    calls = []
    bst = engine.train({"objective": "binary", "verbosity": -1,
                        "num_leaves": 7},
                       lgb.Dataset(X, label=y), num_boost_round=4,
                       fobj=_nan_fobj({1}, calls),
                       callbacks=[callback.health_monitor("warn")])
    mon = bst._impl.obs.monitor
    assert mon is not None and mon.action == "warn"
    bad = [r for r in mon.reports if r.kind == "nonfinite_gradient"]
    # flagged at the injection iteration (NaN then persists in the scores,
    # so later iterations legitimately re-flag)
    assert bad and bad[0].iteration == 1
    assert mon.anomaly_count() >= 1
    assert len(calls) == 4                        # warn does not stop training
    # (the poisoned tree grows no split, so the device-side convergence
    # stop trims the model — warn only guarantees the loop isn't aborted)
    assert bst.current_iteration >= 1


def test_nan_injection_raise_stops_before_next_iteration():
    """raise mode (config-driven wiring): LightGBMError surfaces from the
    poisoned iteration's dispatch — the next iteration never trains."""
    X, y = make_binary(n=400, f=4)
    calls = []
    with pytest.raises(LightGBMError, match="health monitor"):
        engine.train({"objective": "binary", "verbosity": -1,
                      "num_leaves": 7, "observability": "basic",
                      "health_monitor": "raise"},
                     lgb.Dataset(X, label=y), num_boost_round=6,
                     fobj=_nan_fobj({1}, calls))
    # iteration 0 trained clean, iteration 1 raised, iteration 2 never ran
    assert calls == [0, 1]


def test_health_vec_device_semantics():
    """The device flag vector: NaN anywhere in grad/hess poisons the sum
    (NaN * 0 == NaN survives masking), stump mirrors ~any_split."""
    import jax.numpy as jnp
    g = jnp.ones((16,), jnp.float32)
    h = jnp.ones((16,), jnp.float32)
    ok = np.asarray(health_vec(g, h, jnp.bool_(True)))
    assert ok[HEALTH_NONFINITE] == 0.0 and ok.shape == (4,)
    bad = np.asarray(health_vec(g.at[3].set(jnp.nan), h, jnp.bool_(True)))
    assert bad[HEALTH_NONFINITE] == 1.0
    gh = np.asarray(health_vec(
        g, h, jnp.bool_(False),
        grower_health=jnp.asarray([[5.0, 0.0], [3.0, 1.0]])))
    assert gh[HEALTH_WAVES] == 8.0 and gh[1] == 1.0 and gh[2] == 1.0


def test_health_monitor_stump_never_escalates():
    """Zero-positive-gain waves are counted but never abort/raise — a
    converged model legitimately stops splitting."""
    reg = MetricsRegistry()
    mon = HealthMonitor(action="raise", registry=reg)
    rows = np.asarray([[0.0, 1.0, 0.0, 2.0]])    # stump only
    reports = mon.check(rows, start_iter=7)
    assert [r.kind for r in reports] == ["zero_gain_wave"]
    assert mon.anomaly_count() == 0               # no anomaly, no raise


# ------------------------------------------------------------ span overhead
def test_disabled_spans_are_near_free():
    """observability=none: spans are recorded in memory all the same, so
    10k of them must stay well under a millisecond each, export nothing,
    and leave the ring no longer than its bound."""
    from lightgbm_tpu.obs import trace
    obs = TrainingObs.disabled()
    exported = obs.registry.summary(
        "lgbm_train_span_seconds", "Wall-clock span durations.",
        labels={"span": "train_block"}).count
    t0 = time.perf_counter()
    for _ in range(10000):
        with obs.span("train_block"):
            pass
    assert time.perf_counter() - t0 < 0.5
    assert obs.registry.summary(
        "lgbm_train_span_seconds", "Wall-clock span durations.",
        labels={"span": "train_block"}).count == exported
    assert obs.events is None
    spans = trace.recorded_spans()
    assert len(spans) == trace.RING_SIZE
    assert spans[-1]["name"] == "train_block"


def test_enabled_spans_record_summaries():
    reg = MetricsRegistry()
    from lightgbm_tpu.obs.trace import Tracer
    tr = Tracer(enabled=True, registry=reg, metric="lgbm_span_seconds")
    with tr.span("hist_build"):
        pass
    with tr.span("hist_build"):
        pass
    s = reg.summary("lgbm_span_seconds", "Span wall time.",
                    labels={"span": "hist_build"})
    assert s.count == 2 and len(s.values()) == 2


# ----------------------------------------------------- Prometheus exposition
def test_prometheus_exposition_golden():
    """Byte-exact exposition-format (0.0.4) output: families sorted by
    name, HELP/TYPE headers, summary quantile series + _sum/_count."""
    reg = MetricsRegistry()
    c = reg.counter("lgbm_test_requests_total", "Requests served.")
    g = reg.gauge("lgbm_up", "Up gauge.")
    s = reg.summary("lgbm_latency_seconds", "Latency.")
    c.inc(); c.inc(2)
    g.set(1)
    for v in (0.1, 0.2, 0.3):
        s.observe(v)
    assert reg.prometheus_text() == (
        '# HELP lgbm_latency_seconds Latency.\n'
        '# TYPE lgbm_latency_seconds summary\n'
        'lgbm_latency_seconds{quantile="0.5"} 0.2\n'
        'lgbm_latency_seconds{quantile="0.9"} 0.3\n'
        'lgbm_latency_seconds{quantile="0.99"} 0.3\n'
        'lgbm_latency_seconds_sum 0.6000000000000001\n'
        'lgbm_latency_seconds_count 3\n'
        '# HELP lgbm_test_requests_total Requests served.\n'
        '# TYPE lgbm_test_requests_total counter\n'
        'lgbm_test_requests_total 3\n'
        '# HELP lgbm_up Up gauge.\n'
        '# TYPE lgbm_up gauge\n'
        'lgbm_up 1\n')


def test_registry_labels_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("lgbm_x_total", "X.", labels={"sink": "a"})
    b = reg.counter("lgbm_x_total", "X.", labels={"sink": "b"})
    assert a is not b
    assert reg.counter("lgbm_x_total", "X.", labels={"sink": "a"}) is a
    with pytest.raises(ValueError):
        reg.gauge("lgbm_x_total", "X.", labels={"sink": "a"})
    a.inc()
    text = reg.prometheus_text()
    assert 'lgbm_x_total{sink="a"} 1' in text
    assert 'lgbm_x_total{sink="b"} 0' in text


# ------------------------------------------------------------ thread safety
def test_registry_thread_safety_under_micro_batch_queue():
    """Hammer the serving micro-batch queue from many threads while a
    scraper thread reads the process registry; per-request accounting must
    come out exact and every scrape must parse."""
    from lightgbm_tpu.serving import MicroBatchQueue, ServingEngine
    eng = ServingEngine(max_batch=64)
    eng.registry.load_file("m", os.path.join(GOLDEN, "model_ref.txt"))
    nf = eng.registry.get("m").num_features
    q = MicroBatchQueue(eng, deadline_ms=5).start()
    stop = threading.Event()
    scrape_errors = []

    def scraper():
        reg = get_registry()
        while not stop.is_set():
            try:
                text = reg.prometheus_text()
                assert "lgbm_serving_requests_total" in text
                snap = reg.snapshot()
                assert "metrics" in snap
            except Exception as e:       # surfaced after join
                scrape_errors.append(e)
                return

    def client(seed):
        rng = np.random.RandomState(seed)
        futs = [q.submit("m", rng.rand(k, nf).astype(np.float32))
                for k in rng.randint(1, 9, size=10)]
        for f in futs:
            f.result(timeout=120)

    scr = threading.Thread(target=scraper); scr.start()
    threads = [threading.Thread(target=client, args=(i,)) for i in range(6)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        stop.set(); scr.join(); q.stop()
    assert not scrape_errors
    assert eng.metrics.requests == 60    # exact under concurrency
    assert eng.metrics.queue_depth == 0


# ------------------------------------------------------- psum invariance
def test_frontier_health_adds_no_collectives():
    """Acceptance: the per-wave psum count is UNCHANGED with the health
    accumulator on — health rides values the wave already reduced.
    Entry construction and equation walk are the shared
    analysis/jaxpr_audit.py implementation (the one the audit baseline
    and perf gate also consume), not a hand-rolled jaxpr scan."""
    import jax
    from lightgbm_tpu.analysis import jaxpr_audit
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")

    def psum_count(obs_health):
        fn, args, _ = jaxpr_audit.sharded_frontier_fn(
            param_overrides={"obs_health": obs_health})
        counts = jaxpr_audit.count_collectives(jax.make_jaxpr(fn)(*args))
        return counts.get("psum", 0)

    n_off = psum_count(False)
    n_on = psum_count(True)
    assert n_off > 0                     # the wave reduction is really there
    assert n_on == n_off
