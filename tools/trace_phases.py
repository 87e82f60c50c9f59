"""One jax.profiler capture as a table: device seconds by ``lgbm.*`` scope.

    python tools/trace_phases.py <trace_dir> [--json]

``<trace_dir>`` is what ``obs_perfetto_dir`` (or any
``jax.profiler.start_trace``) wrote: the newest ``.xplane.pb`` under it is
read by ``lightgbm_tpu.obs.trace.capture_phases``. Device busy time is the
union of the leaf events of ``XLA Ops``; each op counts under the LAST
``lgbm.`` component of its scoped name; an idle gap counts under the
innermost ``lgbm.*`` host span its middle falls in, and again under the
scope of the op that ended before it; the ten largest ops under no scope
are listed by instruction and by where they sit. A capture in which no op
carries a scope (a CPU capture, or an executable that a build without scopes
compiled and the compile cache handed back) prints that, and exits 1.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def table(phases) -> str:
    busy = phases["busy_s"]
    events = phases["events_by_scope"]
    rows = [("scope", "device_s", "% of busy", "events")]
    rows += [(k, "%.6f" % v, "%.2f" % (100.0 * v / busy), "%g" % events[k])
             for k, v in phases["by_scope"].items()]
    rows.append(("(unscoped)", "%.6f" % phases["unscoped_s"],
                 "%.2f" % (100.0 * phases["unscoped_s"] / busy), ""))
    rows.append(("busy (union)", "%.6f" % busy, "100.00", ""))
    for title, key in (("idle under host span", "idle_by_span"),
                       ("idle after an op of scope", "idle_after_scope")):
        rows += [("", "", "", ""), (title, "idle_s", "", "")]
        rows += [(k, "%.6f" % v, "", "") for k, v in phases[key].items()]
    width = [max(len(r[i]) for r in rows) for i in range(4)]
    lines = ["  ".join(c.ljust(w) for c, w in zip(r, width)).rstrip()
             for r in rows]
    # an unscoped op's name is its instruction and where it sits: long
    lines += ["", "largest ops under no scope: device_s, events, name"]
    lines += ["%.6f  %6g  %s" % (v, count, name)
              for name, v, count in phases["unscoped_ops"]]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--json", action="store_true",
                    help="print capture_phases' dict and no table")
    args = ap.parse_args(argv)
    from lightgbm_tpu.obs.trace import capture_phases
    phases = capture_phases(args.trace_dir)
    if phases is None:
        print("trace_phases: no device op under an lgbm.* scope in %s"
              % args.trace_dir, file=sys.stderr)
        return 1
    print(json.dumps(phases) if args.json else table(phases))
    return 0


if __name__ == "__main__":
    sys.exit(main())
