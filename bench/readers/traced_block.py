"""A count the program recorded on the traced block's ``train.block`` span.

The device adds up a tree's work in the split loop's state (the rows and
tiles of the two tile loops, the rows the kernel saw:
``lightgbm_tpu/core/grow.py`` ``WORK_COUNTS``); the counts join their
block's span when the host fetches the trees. The traced block is the span
whose ``start_iter`` is the reduced trace's ``first_iter`` and whose
``count`` is its ``iters`` (the newest such, should a process hold two).

  count   the count to read
  what    ``per_iter``: the count over the block's iterations

No trace, a program without the recorder, no such span, or a span without
that count (the parent's program; a grower with no tile) reads as nothing:
never as 0.
"""
from bench.readers import program_spans


def traced_counts(result):
    """The counts of the traced block's span, None when there is none."""
    tr = result.get("trace")
    spans = program_spans.recorded() if tr is not None else None
    if spans is None:
        return None
    found = [s["counts"] for s in spans if s["name"] == "train.block"
             and s["counts"].get("start_iter") == tr["first_iter"]
             and s["counts"].get("count") == tr["iters"]]
    return found[-1] if found else None


def read(spec, result):
    counts = traced_counts(result)
    if counts is None or spec["count"] not in counts:
        return None
    if spec["what"] == "per_iter":
        return counts[spec["count"]] / result["trace"]["iters"]
    raise ValueError("traced_block: unknown 'what' %r" % spec["what"])
