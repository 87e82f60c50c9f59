"""Cross-round bench trend: every BENCH_r*.json in one table.

Each PR up to 20 landed a ``BENCH_rNN.json`` (shape drifting as the
harness grew: early rounds nest everything under ``parsed``, later
rounds add subsystem blocks like ``streaming`` / ``distributed`` /
``packed_bins``).  This tool reads them ALL, extracts a tolerant set of
headline metrics per round, and emits:

- a markdown trend table (metric x {first seen, best ever, latest,
  delta}) with a ``REGRESSION?`` flag when the latest value is worse
  than the best-ever by more than ``--tolerance`` (relative); payload /
  collective pins use zero tolerance — those are exact invariants, any
  growth is real;
- ``--json`` with the full per-round series for dashboards.

Numbers across rounds come from DIFFERENT hosts and backends (CI is
CPU, some rounds ran accelerator probes), so the flag is a prompt to
look, not a gate — the perf gate proper is tools/perf_gate.py over
deterministic counters.  Exit 0 always unless ``--strict``, which turns
flagged regressions into exit 1.

Usage::

    python tools/bench_trend.py [--dir .] [--json trend.json]
    python tools/bench_trend.py --markdown trend.md --strict
"""
import argparse
import glob
import json
import os
import re
import sys

# (metric, candidate paths tried in order — each also retried under
# "parsed" —, direction: +1 higher-is-better / -1 lower-is-better,
# pin: exact invariant => zero tolerance)
METRICS = [
    ("train_5_iters_s", ["phase_seconds.train_5_iters"], -1, False),
    ("predict_rows_per_sec", ["predict_rows_per_sec"], +1, False),
    ("train_auc", ["train_auc"], +1, False),
    ("mfu_estimate", ["mfu_estimate"], +1, False),
    ("obs_basic_overhead_frac", ["obs_basic_overhead_frac"], -1, False),
    ("obs_trace_overhead_frac", ["obs_trace_overhead_frac"], -1, False),
    ("traversal_speedup_vs_replay",
     ["traversal_speedup_vs_replay"], +1, False),
    ("stream_overlap_efficiency",
     ["streaming.overlap_efficiency"], +1, False),
    ("stream_ingest_rows_per_sec",
     ["streaming.ingest_rows_per_sec"], +1, False),
    ("payload_frac_data_rs",
     ["distributed.payload_vs_serial.data_rs"], -1, True),
    ("payload_frac_voting",
     ["distributed.payload_vs_serial.voting"], -1, True),
    ("wave_payload_f32_data",
     ["distributed_streaming.per_wave_collectives_8dev_F16_B16"
      ".data.payload_f32_per_wave",
      "distributed.per_wave_collectives_8dev_F16_B16"
      ".data.payload_f32_per_wave"], -1, True),
    ("wave_payload_f32_voting",
     ["distributed_streaming.per_wave_collectives_8dev_F16_B16"
      ".voting.payload_f32_per_wave",
      "distributed.per_wave_collectives_8dev_F16_B16"
      ".voting.payload_f32_per_wave"], -1, True),
    ("packing_bytes_ratio_w1", ["packed_bins.w1.bytes_ratio"], +1, True),
    ("packing_bytes_ratio_w8", ["packed_bins.w8.bytes_ratio"], +1, True),
    ("serve_recompiles_after_warmup",
     ["serve_recompiles_after_warmup"], -1, True),
]


def _dig(doc, dotted):
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def extract(doc, paths):
    """First numeric hit across ``paths``, each tried at top level and
    under the legacy ``parsed`` nesting."""
    for p in paths:
        for root in (doc, doc.get("parsed") or {}):
            v = _dig(root, p)
            if isinstance(v, bool):
                continue
            if isinstance(v, (int, float)):
                return float(v)
    return None


def load_rounds(bench_dir):
    """``[(round_number, doc)]`` sorted by round."""
    rounds = []
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except ValueError:
            print("bench_trend: skipping unreadable %s" % path,
                  file=sys.stderr)
            continue
        if isinstance(doc, dict):
            rounds.append((int(m.group(1)), doc))
    return sorted(rounds)


def build_trend(rounds, tolerance):
    """Per-metric series + best/latest/flag summary."""
    out = {"rounds": [r for r, _ in rounds], "metrics": {}}
    for name, paths, direction, pin in METRICS:
        series = {}
        for rnum, doc in rounds:
            v = extract(doc, paths)
            if v is not None:
                series[rnum] = v
        if not series:
            continue
        ordered = sorted(series.items())
        latest_r, latest = ordered[-1]
        best_r, best = max(ordered, key=lambda kv: direction * kv[1])
        first_r, first = ordered[0]
        tol = 0.0 if pin else tolerance
        scale = max(abs(best), 1e-12)
        worse_frac = (best - latest) * direction / scale
        out["metrics"][name] = {
            "direction": "higher" if direction > 0 else "lower",
            "pin": pin,
            "series": {str(k): v for k, v in ordered},
            "first": {"round": first_r, "value": first},
            "best": {"round": best_r, "value": best},
            "latest": {"round": latest_r, "value": latest},
            "worse_than_best_frac": round(worse_frac, 4),
            "regression": bool(worse_frac > tol),
        }
    return out


def _fmt(v):
    if v is None:
        return "-"
    if abs(v) >= 1000:
        return "%.0f" % v
    return ("%.4f" % v).rstrip("0").rstrip(".")


def to_markdown(trend):
    lines = [
        "# Bench trend (%d rounds: r%s..r%s)"
        % (len(trend["rounds"]), min(trend["rounds"] or [0]),
           max(trend["rounds"] or [0])),
        "",
        "| metric | dir | first | best (round) | latest (round) "
        "| vs best | flag |",
        "|---|---|---|---|---|---|---|",
    ]
    for name, m in sorted(trend["metrics"].items()):
        flag = ""
        if m["regression"]:
            flag = "**REGRESSION?**" if not m["pin"] else "**PIN BROKEN**"
        lines.append(
            "| %s | %s%s | %s | %s (r%d) | %s (r%d) | %+.1f%% | %s |"
            % (name, m["direction"], " pin" if m["pin"] else "",
               _fmt(m["first"]["value"]),
               _fmt(m["best"]["value"]), m["best"]["round"],
               _fmt(m["latest"]["value"]), m["latest"]["round"],
               -100.0 * m["worse_than_best_frac"], flag))
    lines += [
        "",
        "`vs best` is the latest value relative to the best-ever "
        "(sign-adjusted; negative = worse). Cross-round numbers come "
        "from different hosts — flags prompt a look, the real gate is "
        "tools/perf_gate.py.",
    ]
    return "\n".join(lines) + "\n"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dir", default=".",
                    help="directory holding BENCH_r*.json")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="relative slack before flagging a non-pin "
                    "metric (default 0.25: CI hosts are noisy)")
    ap.add_argument("--json", default="",
                    help="write the full trend JSON here")
    ap.add_argument("--markdown", default="",
                    help="write the markdown table here (also printed)")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any metric is flagged")
    args = ap.parse_args()

    rounds = load_rounds(args.dir)
    if not rounds:
        print("bench_trend: no BENCH_r*.json under %s" % args.dir,
              file=sys.stderr)
        return 2
    trend = build_trend(rounds, args.tolerance)
    md = to_markdown(trend)
    print(md, end="")
    if args.markdown:
        with open(args.markdown, "w") as fh:
            fh.write(md)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(trend, fh, indent=2, sort_keys=True)
    flagged = [n for n, m in trend["metrics"].items() if m["regression"]]
    if flagged:
        print("bench_trend: flagged: %s" % ", ".join(sorted(flagged)),
              file=sys.stderr)
    return 1 if (args.strict and flagged) else 0


if __name__ == "__main__":
    sys.exit(main())
