"""What of a run's ``setup_s`` neither the job's clocks nor the time
before the program's import account for.

  clocks   the job's host clocks that tile set-up; those a job does not
           take are left out
  span     the program's span from the process's start to its import
           (``runtime.before_import``)

The run's ``setup_s`` less the clocks that exist less that span's seconds:
the program's own imports before the first clock, the job's probe, the
raw columns freed, the prints. A program that records no such span (the
parent's) reads as nothing, never as a remainder that holds the start-up.
"""
from bench.readers import program_spans


def read(spec, result):
    setup_s = result.get("end_to_end", {}).get("setup_s")
    before = program_spans.read({"span": spec["span"], "which": "first",
                                 "what": "sum_s"}, result)
    if setup_s is None or before is None:
        return None
    clocks = result.get("clocks", {})
    return setup_s - before - sum(clocks[c] for c in spec["clocks"]
                                  if c in clocks)
