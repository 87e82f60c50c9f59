"""The exchange's share of its roofline in the traced iteration: the least
time a chip could take to send what the iteration's all-reduces need
(bench/work_exchange.py: the ring's bytes for the payload the program
counted on the traced block's span, over the chip's ICI peak in
bench/peaks_ici.json) over the device time under the program's exchange
scope, averaged over the chips (``phases``, as bench/readers/trace_phases.py
reads it).

  scopes   the scopes of the exchange
  count    the payload count on the traced ``train.block`` span
  chips    the count on ``train.setup`` that says how many chips share it

No capture, no scope, no count (the parent's program) reads as nothing:
never as 0. A device kind the peaks table does not list is an error.
"""
import json
import os

from bench import work_exchange
from bench.readers import program_spans
from bench.readers.traced_block import traced_counts

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks_ici.json")


def ici_peak(kind):
    with open(PEAKS) as f:
        devices = json.load(f)["devices"]
    if kind not in devices:
        raise ValueError("exchange_roofline: device kind %r is not in "
                         "bench/peaks_ici.json" % kind)
    return devices[kind]["ici_bytes_per_s"]


def read(spec, result):
    phases, tr = result.get("phases"), result.get("trace")
    if phases is None or tr is None:
        return None
    found = [phases["by_scope"][s] for s in spec["scopes"]
             if s in phases["by_scope"]]
    counts = traced_counts(result)
    if not found or sum(found) <= 0 or counts is None \
            or spec["count"] not in counts:
        return None
    spans = program_spans.recorded() or []
    setup = [s["counts"] for s in spans if s["name"] == "train.setup"
             and s["counts"].get(spec["chips"], 0) > 1]
    if not setup:
        return None
    bound = work_exchange.bound_seconds(
        counts[spec["count"]], setup[-1][spec["chips"]],
        ici_peak(result["device_kind"]))
    return 100.0 * bound / sum(found)
