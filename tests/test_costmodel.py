"""XLA cost-model extraction, roofline attribution and the perf gate
(lightgbm_tpu/obs/costmodel.py, obs/perfgate.py, ISSUE 6 acceptance):

- extracted costs per ladder bucket exactly match a direct AOT
  ``lower().compile().cost_analysis()`` of the same entry point;
- extraction adds ZERO backend compiles to warmed training/serving
  programs and leaves the grower's compiled program unchanged (jaxpr +
  psum count pinned, extending tests/test_obs.py's invariance pattern);
- ``observability=none`` training does no costmodel work at all;
- the perf gate's comparison units: exact + relative tolerances, drift
  failure with a readable diff, missing counters;
- the stats server's EADDRINUSE fallback and ``/roofline`` route;
- the registry Histogram type's cumulative bucket exposition.
"""
import json
import threading
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.log import LightGBMError
from lightgbm_tpu.obs.costmodel import (CHIP_PEAKS, CostModel,
                                        costs_from_compiled, detect_peaks,
                                        get_cost_model,
                                        normalize_device_kind, roofline_row,
                                        roofline_table)
from lightgbm_tpu.obs.registry import MetricsRegistry
from lightgbm_tpu.profiling import (backend_compile_count,
                                    install_compile_hook)


def _train(rows=2048, feats=8, leaves=15, depth=4, iters=3, **params):
    rng = np.random.RandomState(0)
    X = rng.randn(rows, feats).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    p = {"objective": "binary", "verbosity": -1, "num_leaves": leaves,
         "max_depth": depth, "tree_growth": "frontier"}
    p.update(params)
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=iters)


# ------------------------------------------------------------ extraction
def test_ladder_bucket_costs_match_direct_aot_exactly():
    """Golden acceptance: for every wave-width ladder bucket, the cost
    model's numbers equal a direct AOT compile + cost_analysis of the
    same entry point — the extraction layer adds no interpretation."""
    import jax
    from lightgbm_tpu import bucketing
    from lightgbm_tpu.core.grow_frontier import wave_hist_entry

    bst = _train(rows=256, feats=4, leaves=15, depth=4, iters=1)
    b = bst._impl
    b.models
    out = b.extract_cost_model(force=True)
    params = b.grow_params
    ladder = bucketing.wave_width_ladder(params.num_leaves,
                                         params.max_depth)
    assert ladder == [1, 2, 4, 8]
    n, ncols = b.xb.shape
    prev_bytes = 0.0
    for w in ladder:
        name = "frontier_hist_w%d" % w
        assert name in out
        fn, args, kwargs = wave_hist_entry(n, ncols, b.xb.dtype, params, w)
        direct = costs_from_compiled(fn.lower(*args, **kwargs).compile())
        for key in ("flops", "bytes_accessed", "peak_bytes", "temp_bytes",
                    "output_bytes"):
            if key in direct or key in out[name]:
                assert out[name].get(key) == direct.get(key), (name, key)
        # wider waves sweep more slots: bytes strictly grow, and are
        # positive — a zeroed counter would mean extraction broke
        assert out[name]["bytes_accessed"] > prev_bytes
        prev_bytes = out[name]["bytes_accessed"]
    assert out["train_block"]["flops"] > 0
    assert out["train_block"]["bytes_accessed"] > 0


def test_extraction_adds_no_compiles_and_leaves_program_unchanged():
    """Acceptance: after warmup, (a) repeated extraction compiles
    nothing, (b) training after extraction compiles nothing, (c) the
    grower's STRUCTURAL FINGERPRINT (analysis/jaxpr_audit.py — primitive
    sequence + avals, collectives included) is identical before and
    after extraction.  Same invariant the audit baseline gates; one
    shared jaxpr walk instead of a bespoke string compare."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.analysis import jaxpr_audit
    from lightgbm_tpu.core.grow_frontier import grow_tree_frontier

    install_compile_hook()
    bst = _train()
    b = bst._impl
    b.models

    def grower_invariants():
        n = b.num_data
        f = b.xb.shape[1]
        jx = jax.make_jaxpr(
            lambda xb, g, h, m: grow_tree_frontier(
                xb, g, h, m, b.feature_meta, jnp.ones((f,), bool),
                b.grow_params))(
            b.xb, jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.ones((n,), jnp.float32))
        return (jaxpr_audit.structural_fingerprint(jx),
                jaxpr_audit.count_collectives(jx).get("psum", 0))

    before_fp, before_psum = grower_invariants()
    assert b.extract_cost_model(force=True)      # first: may compile
    c0 = backend_compile_count()
    out2 = b.extract_cost_model(force=True)      # repeat: pure cache
    assert out2 and backend_compile_count() == c0
    c1 = backend_compile_count()
    b.train_many(3)                              # same block length
    assert backend_compile_count() == c1
    after_fp, after_psum = grower_invariants()
    assert after_fp == before_fp
    assert after_psum == before_psum


def test_observability_none_emits_no_costmodel_work():
    """Acceptance: an observability=none run does zero costmodel work —
    the extraction counter does not move during training, and the
    non-forced call returns {}."""
    reg_counter = get_cost_model()._c_extract
    v0 = reg_counter.value
    bst = _train(observability="none")
    b = bst._impl
    b.models
    assert reg_counter.value == v0
    assert b.extract_cost_model() == {}
    assert reg_counter.value == v0


def test_costmodel_disk_cache_roundtrip(tmp_path):
    """A second CostModel over the same cache dir serves the entry from
    disk: same numbers, zero AOT compiles."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda a: (a * 2.0).sum())
    sds = jax.ShapeDtypeStruct((128, 4), jnp.float32)
    cm1 = CostModel(registry=MetricsRegistry(), cache_dir=str(tmp_path))
    first = cm1.analyze("double_sum", fn, sds)
    assert (tmp_path / CostModel.DISK_CACHE_NAME).exists()
    cm2 = CostModel(registry=MetricsRegistry(), cache_dir=str(tmp_path))
    c0 = backend_compile_count()
    again = cm2.analyze("double_sum", fn, sds)
    assert again == first
    assert backend_compile_count() == c0
    assert int(cm2._c_compiles.value) == 0


# ------------------------------------------------------------ roofline
def test_detect_peaks_table():
    assert detect_peaks("TPU v4") == CHIP_PEAKS["v4"]
    assert detect_peaks("TPU v5 lite") == CHIP_PEAKS["v5e"]
    assert detect_peaks("tpu_v6_lite") == CHIP_PEAKS["v6e"]
    assert normalize_device_kind("TPU v5 lite") == "tpuv5e"
    # CPU / unknown hosts: achieved rates only, never a borrowed peak
    assert detect_peaks("cpu") is None
    assert detect_peaks("Some Weird Host") is None
    # unknown TPU generation: an error, never another chip's peak
    with pytest.raises(LightGBMError, match="CHIP_PEAKS"):
        detect_peaks("TPU v9")


def test_roofline_row_math_and_bound():
    costs = {"flops": 2e9, "bytes_accessed": 1e8, "peak_bytes": 5e6}
    peaks = dict(CHIP_PEAKS["v5e"])
    row = roofline_row("x", costs, seconds=2.0, calls=4.0, peaks=peaks)
    assert row["flops_per_s"] == pytest.approx(4e9)
    assert row["bytes_per_s"] == pytest.approx(2e8)
    assert row["arithmetic_intensity"] == pytest.approx(20.0)
    # rows round utilization ratios to 8 decimals
    assert row["mfu"] == pytest.approx(
        4e9 / peaks["flops_per_s"], abs=5e-9)
    assert row["membw_util"] == pytest.approx(
        2e8 / peaks["hbm_bytes_per_s"], abs=5e-9)
    # intensity 20 < v5e ridge (~240): memory bound
    assert row["bound"] == "memory"
    # no peaks (CPU): achieved rates only
    cpu_row = roofline_row("x", costs, 2.0, 4.0, peaks=None)
    assert "mfu" not in cpu_row and "bound" not in cpu_row
    # no timing: static costs only
    static = roofline_row("x", costs, 0.0, 0.0, peaks=peaks)
    assert "flops_per_s" not in static


def test_roofline_table_joins_wall_times():
    reg = MetricsRegistry()
    cm = CostModel(registry=reg)
    cm.record("phase_a", {"flops": 1e6, "bytes_accessed": 1e6})
    cm.record("phase_b", {"flops": 2e6, "bytes_accessed": 4e6})
    rows = roofline_table({"phase_a": (0.5, 2.0)}, cost_model=cm)
    by_name = {r["phase"]: r for r in rows}
    assert by_name["phase_a"]["flops_per_s"] == pytest.approx(4e6)
    assert "flops_per_s" not in by_name["phase_b"]   # static only
    rows2 = roofline_table({}, cost_model=cm, include_static_only=False)
    assert rows2 == []


# ------------------------------------------------------------ perf gate
def test_perfgate_compare_units():
    from lightgbm_tpu.obs import perfgate
    counters = {"slot_sweeps_per_tree": 15.0, "frontier_ladder": [1, 2, 4],
                "costmodel_flops_x": 1000.0}
    base = perfgate.make_baseline(counters, {"rows": 1})
    # identical measurement passes
    v, table = perfgate.compare(base, dict(counters))
    assert v == [] and "slot_sweeps_per_tree" in table
    # exact counter drift fails, naming the counter and both values
    bad = dict(counters, slot_sweeps_per_tree=30.0)
    v, table = perfgate.compare(base, bad)
    assert len(v) == 1 and v[0]["counter"] == "slot_sweeps_per_tree"
    assert v[0]["baseline"] == 15.0 and v[0]["measured"] == 30.0
    assert "FAIL" in table
    # ladder is compared exactly as a list
    v, _ = perfgate.compare(base, dict(counters, frontier_ladder=[1, 2, 8]))
    assert len(v) == 1 and v[0]["counter"] == "frontier_ladder"
    # rel tolerance: inside passes, outside fails
    v, _ = perfgate.compare(base, dict(counters, costmodel_flops_x=1200.0))
    assert v == []                                    # 20% < 25% tol
    v, _ = perfgate.compare(base, dict(counters, costmodel_flops_x=1500.0))
    assert len(v) == 1 and "tol" in v[0]["reason"]
    # a counter the baseline declares must be measured
    missing = dict(counters)
    missing.pop("costmodel_flops_x")
    v, table = perfgate.compare(base, missing)
    assert len(v) == 1 and "MISSING" in table
    # a NEW measured counter is informational, not a failure
    v, table = perfgate.compare(base, dict(counters, brand_new=1.0))
    assert v == [] and "not in baseline" in table


def test_perfgate_spec_policy():
    from lightgbm_tpu.obs import perfgate
    assert perfgate.default_spec("waves_per_tree") == {"mode": "exact",
                                                      "tol": 0}
    assert perfgate.default_spec("costmodel_flops_train_block")["mode"] \
        == "rel"
    assert perfgate.default_spec("costmodel_bytes_train_block")["tol"] \
        == pytest.approx(0.5)


@pytest.mark.slow
def test_perfgate_measure_deterministic():
    """Two measurements on the same code produce identical counters."""
    from lightgbm_tpu.obs import perfgate
    wl = {"rows": 512, "features": 4, "num_leaves": 7, "max_depth": 3,
          "iters": 2}
    c1, _ = perfgate.measure(wl)
    c2, _ = perfgate.measure(wl)
    assert c1 == c2
    assert c1["compiles_after_warmup"] == 0.0
    assert c1["health_vec_width"] == 4.0


def test_committed_baseline_is_wellformed():
    """PERF_COUNTERS.json stays parseable with the declared schema and
    one spec per counter (the gate CLI revalidates values in CI)."""
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "PERF_COUNTERS.json")
    with open(path) as fh:
        base = json.load(fh)
    assert base["schema"] == 1
    assert base["workload"]["rows"] > 0
    assert len(base["counters"]) >= 10
    for name, spec in base["counters"].items():
        assert spec["mode"] in ("exact", "rel", "min"), name
        assert "value" in spec and "tol" in spec, name
        if spec["mode"] == "min":
            assert spec["floor"] > 0, name
    # the structural invariants the gate exists to protect
    assert base["counters"]["compiles_after_warmup"]["value"] == 0
    assert base["counters"]["health_vec_width"]["value"] == 4


# ------------------------------------------------------------ serving
def test_serving_warmup_extract_costs():
    from lightgbm_tpu.serving.predictor import ServingEngine
    from lightgbm_tpu.serving.registry import ModelRegistry
    bst = _train(rows=256, feats=4, leaves=7, depth=3, iters=2)
    reg = ModelRegistry()
    reg.register_booster("m", bst)
    eng = ServingEngine(registry=reg, max_batch=64, min_bucket=32)
    eng.warmup(extract_costs=True)
    ents = get_cost_model().entries()
    for bucket in (32, 64):
        name = "predict_b%d" % bucket
        assert name in ents
        assert ents[name]["flops"] > 0
    # larger buckets do strictly more work
    assert ents["predict_b64"]["flops"] > ents["predict_b32"]["flops"]
    # extraction ran before the floor was marked: serving stays clean
    eng.predict("m", np.zeros((40, 4), np.float32))
    assert eng.metrics.recompiles_after_warmup() == 0


# ------------------------------------------------------------ server
def _get(port, path):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=5) as r:
        return r.status, json.loads(r.read().decode())


def test_stats_server_port_conflict_falls_back_to_ephemeral():
    """Regression (satellite 2): two servers on the same port must both
    come up — the second lands on an OS-assigned port instead of dying
    with EADDRINUSE — and both serve /healthz."""
    from lightgbm_tpu.obs.server import StatsServer
    s1 = StatsServer(0, registry=MetricsRegistry()).start()
    try:
        s2 = StatsServer(s1.port, registry=MetricsRegistry()).start()
        try:
            assert s2.port != s1.port
            for port in (s1.port, s2.port):
                status, body = _get(port, "/healthz")
                assert status == 200 and body["status"] == "ok"
        finally:
            s2.stop()
    finally:
        s1.stop()


def test_stats_server_roofline_route():
    from lightgbm_tpu.obs.server import StatsServer
    reg = MetricsRegistry()
    get_cost_model().record("route_probe", {"flops": 7.0,
                                            "bytes_accessed": 11.0})
    s = StatsServer(0, registry=reg).start()
    try:
        status, body = _get(s.port, "/roofline")
        assert status == 200
        assert body["peaks"] is None          # CPU test host
        names = [r["phase"] for r in body["rows"]]
        assert "route_probe" in names
    finally:
        s.stop()


# ------------------------------------------------------------ histogram
def test_histogram_cumulative_exposition():
    """Prometheus histogram semantics: cumulative inclusive-le buckets,
    trailing +Inf, lifetime _sum/_count."""
    reg = MetricsRegistry()
    h = reg.histogram("t_lat_ms", "help", buckets=(1.0, 5.0, 10.0))
    for v in (0.5, 1.0, 3.0, 7.0, 100.0):
        h.observe(v)
    text = reg.prometheus_text()
    assert '# TYPE t_lat_ms histogram' in text
    assert 't_lat_ms_bucket{le="1"} 2' in text      # 0.5, 1.0 (inclusive)
    assert 't_lat_ms_bucket{le="5"} 3' in text
    assert 't_lat_ms_bucket{le="10"} 4' in text
    assert 't_lat_ms_bucket{le="+Inf"} 5' in text
    assert 't_lat_ms_count 5' in text
    assert 't_lat_ms_sum 111.5' in text
    assert h.count == 5 and h.total == pytest.approx(111.5)
    # get-or-create idempotence + kind collision guard
    assert reg.histogram("t_lat_ms") is h
    with pytest.raises(ValueError):
        reg.counter("t_lat_ms")
    with pytest.raises(ValueError):
        reg.histogram("empty", buckets=())


def test_serving_metrics_latency_histogram():
    """Satellite 1: request latency rides the registry Histogram while
    the JSON snapshot keeps its p50/p90/p99 schema."""
    from lightgbm_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    for ms in (1.0, 2.0, 50.0):
        m.record_request(rows=10, latency_s=ms / 1000.0)
    assert m._h_latency.kind == "histogram"
    assert m._h_latency.count == 3
    snap = m.snapshot()
    assert snap["latency_ms"]["count"] == 3
    assert snap["latency_ms"]["p50_ms"] == pytest.approx(2.0)
    text = m._h_latency.samples()
    names = {s[0] for s in text}
    assert "lgbm_serving_request_latency_ms_bucket" in names
