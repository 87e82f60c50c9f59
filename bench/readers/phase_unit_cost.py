"""Device time of a phase of the traced iteration over the work the program
counted for it: what one tile, or one row, costs.

  scopes   the ``lgbm.*`` scopes whose device seconds are summed, from the
           table the job handed on as ``phases`` (bench/readers/
           trace_phases.py); or
  kernel   a regular expression over the reduced trace's op names, as
           bench/readers/trace_ops.py takes ``hist_kernel_ms``
  counts   the counts on the traced block's ``train.block`` span that are
           summed to divide by (bench/readers/traced_block.py)
  per_s    the unit, in seconds: 1e-6 reads microseconds a count

No trace, no op under the scopes or of the family, no such count, or a
count of 0 reads as nothing: never as 0.
"""
from bench.readers.trace_ops import kernel_seconds
from bench.readers.traced_block import traced_counts


def read(spec, result):
    counts = traced_counts(result)
    if counts is None or not all(c in counts for c in spec["counts"]):
        return None
    work = sum(counts[c] for c in spec["counts"])
    if "kernel" in spec:
        seconds = kernel_seconds(result["trace"], spec["kernel"])
    else:
        by_scope = (result.get("phases") or {}).get("by_scope", {})
        found = [by_scope[s] for s in spec["scopes"] if s in by_scope]
        seconds = sum(found) if found else None
    if not seconds or not work:
        return None
    return seconds / work / spec["per_s"]
