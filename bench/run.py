"""One run of one benchmark cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name, and nothing here knows a cell's, a
configuration's or a metric's name:

    BENCHMARK.json workloads[name]
      -> bench/workloads/<name>.json   the traffic: job, its parameters
      -> bench/configs/<config>.json   the shape, the job's parameters,
                                       the generator and its parameters
      -> bench/generators/<generator>.py   generate(seed, **data[, dtype])
      -> bench/jobs/<job>.py               run(ctx) -> Result
    BENCHMARK.json per_layer[name]
      -> bench/metrics/<name>.json     {"reader": ..., reader's arguments}
      -> bench/readers/<reader>.py     read(spec, result) -> number | None

It is one process on the chips the cell asks for, starts no child, and has
no fallback: no TPU, too few chips or a device the peaks table does not
list ends it non-zero with no result line. ``--rehearsal`` is the one way
to run it elsewhere: a labelled CPU dry run at the configuration's
``rehearsal`` shape that reports no metric at all.
"""
import time

T_START = time.time()

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)   # "bench" and the program import from the checkout


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    return importlib.import_module("bench.%s.%s" % (kind, name))


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    sys.exit("bench/run.py: no %s named %r in BENCHMARK.json" % (what, name))


def device_or_exit(chips, rehearsal):
    """The device as JAX reports it, and its row of the peaks table."""
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    peaks = load_json(HERE, "peaks.json")
    if rehearsal:
        return info, next(iter(peaks["devices"].values()))
    if d.platform != "tpu":
        sys.exit("bench/run.py: no TPU (jax found %s): no run, no number"
                 % json.dumps(info))
    if len(devs) < chips:
        sys.exit("bench/run.py: the cell needs %d chips, jax found %d"
                 % (chips, len(devs)))
    if d.device_kind not in peaks["devices"]:
        sys.exit("bench/run.py: device kind %r is not in bench/peaks.json"
                 % d.device_kind)
    return info, peaks["devices"][d.device_kind]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    os.chdir(ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "configuration")
    workload = load_json(HERE, "workloads", cell["name"] + ".json")
    config = load_json(ROOT, config_entry["file"])

    device, peaks = device_or_exit(cell["chips"], args.rehearsal)
    if args.rehearsal:
        print("REHEARSAL: a CPU dry run at a toy shape. It proves the "
              "control flow and is no chip run; it reports no metric.",
              flush=True)
        config = dict(config, **config["rehearsal"])
        workload = dict(workload, **workload.get("rehearsal", {}))

    ctx = {"t_start": globals()["T_START"], "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "rehearsal": args.rehearsal,
           "workload": workload, "config": config, "peaks": peaks,
           "generator": load_module("generators", config["generator"])}
    result = load_module("jobs", workload["job"]).run(ctx)

    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    metrics = {}
    for m in wanted:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        if args.trace:
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            value = load_module("readers", spec["reader"]).read(spec, result)
        else:
            value = result["end_to_end"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = dict(device, memory_peak_bytes=result["memory_peak_bytes"])
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if args.trace and result.get("trace") is not None:
        device["busy_s"] = result["trace"]["busy_s"]
        device["window_s"] = result["trace"]["window_s"]
        line["breakdown"] = result["trace"]["breakdown"]
    if args.rehearsal:
        line = {"rehearsal": True, "no_chip_run": True,
                "correct": result["correct"], "attempted": result["attempted"],
                "failed": result["failed"], "metrics": {},
                "would_report": sorted(metrics),
                "device": {k: device[k] for k in ("platform", "kind", "count")}}
    line["compared"] = result["compared"]
    for name, c in result["compared"].items():
        print("compared %s = %.6g (limit %.6g) %s" % (
            name, c["value"], c["limit"], "ok" if c["ok"] else "FAILS"),
            file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
