"""Set-up as the program's own spans say it, from the process's start:
bench/readers/program_spans.py's selection and reduction, for the spans a
booster's training does not record (``runtime.before_import``, the lazy
imports' ``import.<module>``, ``train.engine``); that reader's metrics are
held to read on every toy booster (bench/tests/test_program_spans.py), and
these are recorded once a process.

  span     the name to read, or a list of names; or
  prefix   every span whose name starts with it (``import.``)
  which, what, under   as in bench/readers/program_spans.py

A program without the recorder or without such a span (the parent's) reads
as nothing: never as 0.
"""
from bench.readers import program_spans


def read(spec, result):
    spans = program_spans.recorded()
    if spans is None:
        return None
    if "prefix" in spec:
        names = sorted({s["name"] for s in spans
                        if s["name"].startswith(spec["prefix"])})
        if not names:
            return None
        spec = dict(spec, span=names)
    return program_spans.reduce(spans, spec)
