"""Device time of the traced iteration, from the reduced trace.

  kernel_ms_per_iter  sum of the device durations of the ops whose name
                      matches the regular expression ``kernel``
  other_ms_per_iter   device busy time minus that family's
  idle_pct            1 - union of device-op intervals over the traced wall

No trace, or no event of the family in it, reads as nothing: never as 0.
"""


import re


def kernel_seconds(tr, pattern):
    """Device seconds of the ops whose name matches, None when none does."""
    found = [s for name, s in tr["op_s"].items() if re.search(pattern, name)]
    return sum(found) if found else None


def read(spec, result):
    tr = result.get("trace")
    if tr is None or tr["busy_s"] <= 0:
        return None
    iters = tr["iters"]
    if spec["what"] == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    kernel_s = kernel_seconds(tr, spec["kernel"])
    if kernel_s is None:
        return None
    if spec["what"] == "kernel_ms_per_iter":
        return 1e3 * kernel_s / iters
    if spec["what"] == "other_ms_per_iter":
        return 1e3 * (tr["busy_s"] - kernel_s) / iters
    raise ValueError("trace_ops: unknown 'what' %r" % spec["what"])
