"""The static-analysis subsystem itself (lightgbm_tpu/analysis/,
ISSUE 7): lint rules fire exactly where the golden corpus says, the
suppression channel works, the jaxpr/HLO audit primitives detect what
they claim to detect, seeded invariant violations fail the comparison
naming entry + invariant, and the committed ANALYSIS_BASELINE.json
stays well-formed.
"""
import glob
import json
import os
import re

import numpy as np
import pytest

from lightgbm_tpu.analysis import astlint, auditor, hlo_audit, jaxpr_audit
from lightgbm_tpu.analysis.astlint import lint_paths, lint_source
from lightgbm_tpu.obs.registry import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
CORPUS = sorted(glob.glob(os.path.join(HERE, "lint_corpus", "*.py")))


# ------------------------------------------------------------ lint corpus
def _expected_markers(path):
    out = set()
    with open(path, encoding="utf-8") as fh:
        for i, line in enumerate(fh, start=1):
            m = re.search(r"# EXPECT=(LGL\d+)", line)
            if m:
                out.add((m.group(1), i))
    return out


@pytest.mark.parametrize("path", CORPUS,
                         ids=[os.path.basename(p) for p in CORPUS])
def test_corpus_rules_fire_exactly_where_marked(path):
    """Golden corpus: every `# EXPECT=RULE` line produces exactly that
    finding, nothing else fires, and suppressed lines stay silent."""
    assert CORPUS, "lint corpus missing"
    got = {(f.rule, f.line) for f in lint_paths([path])}
    assert got == _expected_markers(path)


def test_corpus_covers_every_rule():
    """One seeded violation per catalog rule — a rule nothing exercises
    is a rule that silently broke."""
    fired = {f.rule for f in lint_paths(CORPUS)}
    assert fired == set(astlint.LINT_RULES)


def test_package_lints_clean():
    """The satellite-1 contract: the repo's own source has no
    unsuppressed findings."""
    findings = astlint.lint_package()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_rule_catalog_wellformed():
    for rule, (sev, summary) in astlint.LINT_RULES.items():
        assert re.fullmatch(r"LGL\d{3}", rule)
        assert sev in ("error", "warning")
        assert summary


# ------------------------------------------------------------ suppression
def test_suppression_parsing():
    src = (
        "import jax\n"
        "def f(x):\n"
        "    jax.block_until_ready(x)  "
        "# lgbm-lint: disable=LGL103,LGL101 reason text here\n"
        "\n"
        "    jax.block_until_ready(x)\n"
    )
    findings = lint_source(src, resolve_params=False)
    # line 3 suppressed (multi-rule list parses); a suppression also
    # covers the line directly below it, so the control call sits on 5
    assert [f.line for f in findings] == [5]
    assert findings[0].rule == "LGL103"


def test_file_level_suppression_window():
    """disable-file only counts in the first ten lines — a buried one
    cannot silently turn a rule off for a long file."""
    head = "# lgbm-lint: disable-file=LGL103\nimport jax\n" \
           "def f(x):\n    jax.block_until_ready(x)\n"
    assert lint_source(head, resolve_params=False) == []
    buried = "import jax\n" + "\n" * 12 + \
        "# lgbm-lint: disable-file=LGL103\n" \
        "def f(x):\n    jax.block_until_ready(x)\n"
    assert len(lint_source(buried, resolve_params=False)) == 1


def test_unknown_config_param_detection():
    src = "def f(cfg):\n    return cfg.not_a_real_param\n"
    findings = lint_source(src, known_params={"learning_rate"})
    assert [f.rule for f in findings] == ["LGL107"]
    ok = "def f(cfg):\n    return cfg.learning_rate\n"
    assert lint_source(ok, known_params={"learning_rate"}) == []


# ------------------------------------------------------------ jaxpr audit
def test_structural_fingerprint_stable_and_discriminating():
    import jax
    import jax.numpy as jnp
    fn = lambda x: jnp.sin(x) + 1.0                       # noqa: E731
    sds = jax.ShapeDtypeStruct((8,), jnp.float32)
    fp1 = jaxpr_audit.structural_fingerprint(jax.make_jaxpr(fn)(sds))
    fp2 = jaxpr_audit.structural_fingerprint(jax.make_jaxpr(fn)(sds))
    assert fp1 == fp2
    other = jaxpr_audit.structural_fingerprint(
        jax.make_jaxpr(lambda x: jnp.cos(x) + 1.0)(sds))
    assert other != fp1
    # shape change is a different program too
    wider = jaxpr_audit.structural_fingerprint(
        jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((16,), jnp.float32)))
    assert wider != fp1


def test_iter_eqns_recurses_into_scan():
    import jax
    import jax.numpy as jnp

    def fn(xs):
        return jax.lax.scan(lambda c, x: (c + jnp.sin(x), c), 0.0, xs)

    jx = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4,), jnp.float32))
    prims = jaxpr_audit.primitive_sequence(jx)
    assert "scan" in prims
    assert "sin" in prims          # only reachable through the sub-jaxpr


def test_collective_schedule_and_counts():
    import jax
    import jax.numpy as jnp

    def fn(x):
        return jax.lax.psum(x, "i"), jax.lax.all_gather(x, "i")

    jx = jax.make_jaxpr(fn, axis_env=[("i", 2)])(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    sched = jaxpr_audit.collective_schedule(jx)
    assert [s["primitive"] for s in sched] == ["psum", "all_gather"]
    assert sched[0]["operands"] == ["float32[4]"]
    counts = jaxpr_audit.count_collectives(jx)
    assert counts == {"psum": 1, "all_gather": 1}
    audit = jaxpr_audit.audit_jaxpr(jx)
    assert audit["psums"] == 1 and audit["collectives"] == 2
    assert audit["f64_eqns"] == 0 and audit["host_callbacks"] == []


def test_f64_equations_detected():
    import jax
    import jax.numpy as jnp
    with jax.enable_x64():
        jx = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2.0)(
            jax.ShapeDtypeStruct((4,), jnp.float32))
    assert jaxpr_audit.count_f64_eqns(jx) > 0
    clean = jax.make_jaxpr(lambda x: x * 2.0)(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    assert jaxpr_audit.count_f64_eqns(clean) == 0


def test_host_callbacks_detected():
    import jax
    import jax.numpy as jnp

    def fn(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct((4,), np.float32), x)

    jx = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4,), jnp.float32))
    assert jaxpr_audit.host_callback_primitives(jx)


def test_sharded_frontier_entry_matches_perfgate_counter():
    """The shared entry IS the perf-gate program: same per-wave psum
    normalization as the committed psum_per_wave_branch counter."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    from lightgbm_tpu.obs.perfgate import _psum_per_wave, bucketing_ladder
    fn, args, params = jaxpr_audit.sharded_frontier_fn()
    psums = jaxpr_audit.count_collectives(
        jax.make_jaxpr(fn)(*args)).get("psum", 0)
    ladder = bucketing_ladder(params.num_leaves, params.max_depth)
    assert psums / len(ladder) == _psum_per_wave()


# ------------------------------------------------------------ hlo audit
def test_input_output_alias_parsing():
    text = ("HloModule jit_f, input_output_alias={ {0}: (3, {}, "
            "may-alias), {1}: (10, {}, must-alias) }, "
            "entry_computation_layout={(f32[8])->f32[8]}")
    aliases = hlo_audit.input_output_aliases(text)
    assert aliases == [
        {"output_index": [0], "param_number": 3, "kind": "may-alias"},
        {"output_index": [1], "param_number": 10, "kind": "must-alias"},
    ]
    assert hlo_audit.input_output_aliases("HloModule jit_f") == []


def test_audit_donation_effective_and_dropped():
    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct((64,), jnp.float32)
    # same-shape output: XLA records the alias
    ok = hlo_audit.audit_donation(lambda x: x + 1.0, (sds,), (0,))
    assert ok["ok"] and ok["donated_params"] == [0]
    assert 0 in ok["aliased_params"]
    # scalar output cannot reuse the donated [64] buffer: alias dropped,
    # and the audit must SAY so rather than silently passing
    dropped = hlo_audit.audit_donation(lambda x: x.sum(), (sds,), (0,))
    assert not dropped["ok"] and dropped["missing"] == [0]


def test_flat_param_ranges_spans_pytrees():
    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct((4,), jnp.float32)
    ranges = hlo_audit.flat_param_ranges(((sds, sds), None, sds))
    assert ranges == [(0, 2), (2, 2), (2, 3)]


# ------------------------------------------------------------ comparison
def _fake_measured():
    entry = {"fingerprint": "abc", "num_eqns": 10, "psums": 1,
             "all_gathers": 0, "collectives": 1,
             "collective_schedule": [{"primitive": "psum",
                                      "operands": ["float32[4]"]}],
             "f64_eqns": 0, "host_callbacks": []}
    return {"schema": auditor.SCHEMA, "jax": "x", "backend": "cpu",
            "workload": {}, "entries": {"wave": dict(entry)},
            "donation": {"train_block": {
                "donate_argnums": [3, 8], "donated_params": [5, 10],
                "aliased_params": [5, 10], "missing": [], "ok": True}}}


def test_compare_audit_passes_on_identity():
    m = _fake_measured()
    violations, report = auditor.compare_audit(m, m)
    assert violations == []
    assert "wave" in report


def test_seeded_second_psum_fails_naming_entry_and_invariant():
    """The acceptance demo in unit form: one extra psum in a wave entry
    must fail the gate with a violation naming both."""
    base, meas = _fake_measured(), _fake_measured()
    meas["entries"]["wave"]["psums"] = 2
    meas["entries"]["wave"]["collectives"] = 2
    meas["entries"]["wave"]["collective_schedule"].append(
        {"primitive": "psum", "operands": ["float32[4]"]})
    violations, _ = auditor.compare_audit(base, meas)
    assert {v["invariant"] for v in violations} == {
        "psums", "collectives", "collective_schedule"}
    assert all(v["entry"] == "wave" for v in violations)


def test_seeded_f64_is_a_hard_violation_even_if_baselined():
    base, meas = _fake_measured(), _fake_measured()
    base["entries"]["wave"]["f64_eqns"] = 3   # a poisoned baseline
    meas["entries"]["wave"]["f64_eqns"] = 3
    violations, _ = auditor.compare_audit(base, meas)
    assert any(v["invariant"] == "zero_f64" and v["entry"] == "wave"
               for v in violations)


def test_fingerprint_drift_and_missing_entry_fail():
    base, meas = _fake_measured(), _fake_measured()
    meas["entries"]["wave"]["fingerprint"] = "zzz"
    violations, _ = auditor.compare_audit(base, meas)
    assert any(v["invariant"] == "fingerprint" for v in violations)
    del meas["entries"]["wave"]
    violations, _ = auditor.compare_audit(base, meas)
    assert any(v["invariant"] == "present" for v in violations)


def test_dropped_donation_fails():
    base, meas = _fake_measured(), _fake_measured()
    meas["donation"]["train_block"].update(
        ok=False, missing=[10], aliased_params=[5])
    violations, _ = auditor.compare_audit(base, meas)
    assert any(v["invariant"] == "donation_aliased"
               and v["entry"] == "train_block" for v in violations)


def test_write_baseline_refuses_hard_invariant_breaks(tmp_path):
    bad = _fake_measured()
    bad["entries"]["wave"]["f64_eqns"] = 1
    with pytest.raises(ValueError, match="f64"):
        auditor.write_baseline(bad, str(tmp_path / "b.json"))
    bad2 = _fake_measured()
    bad2["donation"]["train_block"]["ok"] = False
    with pytest.raises(ValueError, match="donation"):
        auditor.write_baseline(bad2, str(tmp_path / "b.json"))
    good = _fake_measured()
    path = auditor.write_baseline(good, str(tmp_path / "b.json"))
    assert auditor.load_baseline(path) == good


def test_publish_gauges():
    m = _fake_measured()
    reg = MetricsRegistry()
    auditor.publish(m, [], registry=reg)
    text = reg.prometheus_text()
    assert "lgbm_analysis_entries 1" in text
    assert "lgbm_analysis_violations 0" in text
    assert "lgbm_analysis_collectives_total 1" in text


# ------------------------------------------------------------ baseline file
def test_committed_baseline_is_wellformed():
    path = os.path.join(os.path.dirname(HERE), "ANALYSIS_BASELINE.json")
    with open(path) as fh:
        base = json.load(fh)
    assert base["schema"] == auditor.SCHEMA
    entries = base["entries"]
    # the entry points the audit exists to protect
    for name in ("train_block", "grower", "grower_sharded",
                 "materialize", "frontier_hist_w1", "predict_b32"):
        assert name in entries, name
    for name, e in entries.items():
        assert e["f64_eqns"] == 0, name
        assert e["host_callbacks"] == [], name
        assert re.fullmatch(r"[0-9a-f]{64}", e["fingerprint"]), name
    # the sharded grower's collective schedule is committed exactly
    sharded = entries["grower_sharded"]
    assert sharded["psums"] > 0
    assert len(sharded["collective_schedule"]) == sharded["collectives"]
    don = base["donation"]["train_block"]
    assert don["ok"] and don["missing"] == []
    from lightgbm_tpu.boosting.gbdt import GBDT
    assert don["donate_argnums"] == list(GBDT.TRAIN_BLOCK_DONATE)


# ------------------------------------------------- donation regression
@pytest.mark.slow
def test_train_block_donation_actually_aliased():
    """Satellite 2: train_many's donated scores/bag-mask buffers are
    really input-output aliased in the compiled executable — XLA
    silently dropping them would turn every block boundary into a full
    [N, K] copy.  Audited on the exact executing signature."""
    import jax
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(256, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "num_leaves": 7, "max_depth": 3,
                     "tree_growth": "frontier"},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    b = bst._impl
    b.models
    block = int(b._last_block_len)
    assert block > 0
    args = b.train_block_sds(block)
    result = hlo_audit.audit_donation(
        b._build_run_block(), args, type(b).TRAIN_BLOCK_DONATE)
    assert result["ok"], result
    # the aliased leaves are the right buffers: scores [N, K] f32 and
    # the bagging mask [N] f32
    ranges = hlo_audit.flat_param_ranges(args)
    scores_range = ranges[type(b).TRAIN_BLOCK_DONATE[0]]
    leaves = jax.tree_util.tree_leaves(args[type(b).TRAIN_BLOCK_DONATE[0]])
    assert leaves[0].shape == (256, 1)
    assert scores_range[0] in result["aliased_params"]
