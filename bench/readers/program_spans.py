"""A number from the spans the program recorded in its own memory
(``lightgbm_tpu.obs.trace.recorded_spans()``: name, start and end on the
host's monotonic clock, the span that caused it, numeric counts).

  span    the name to read, or a list of names read together
  under   optional: only spans with an ancestor of this name count
  which   ``all`` | ``first`` | ``after_first``: which of the spans named
          ``under`` (of the spans named ``span`` when there is no ``under``)
          take part, in the order they started
  what    ``sum_s``: seconds summed; ``mean_ms``: milliseconds a span;
          any other word: that count, summed over the spans that carry it

A program without the recorder (the parent of the PR that brought it), no
span of that name, or no span with that count reads as nothing: never as 0.
"""


def recorded():
    from lightgbm_tpu.obs import trace
    spans = getattr(trace, "recorded_spans", None)
    return None if spans is None else spans()


def select(spans, spec):
    """The spans ``spec`` names, from a list of recorded-span dicts."""
    names = spec["span"] if isinstance(spec["span"], list) else [spec["span"]]
    anchor = [spec["under"]] if spec.get("under") else names
    anchors = sorted((s for s in spans if s["name"] in anchor),
                     key=lambda s: s["start_ns"])
    which = spec.get("which", "all")
    if which == "first":
        anchors = anchors[:1]
    elif which == "after_first":
        anchors = anchors[1:]
    elif which != "all":
        raise ValueError("program_spans: unknown 'which' %r" % which)
    if not spec.get("under"):
        return anchors
    by_id = {s["id"]: s for s in spans}
    picked = {s["id"] for s in anchors}

    def below_picked(s):
        while s["parent"] in by_id:      # ids grow: a parent is older
            s = by_id[s["parent"]]
            if s["id"] in picked:
                return True
        return False

    return [s for s in spans if s["name"] in names and below_picked(s)]


def reduce(spans, spec):
    chosen = select(spans, spec)
    if not chosen:
        return None
    what = spec["what"]
    seconds = [(s["end_ns"] - s["start_ns"]) / 1e9 for s in chosen]
    if what == "sum_s":
        return sum(seconds)
    if what == "mean_ms":
        return 1e3 * sum(seconds) / len(seconds)
    counts = [s["counts"][what] for s in chosen if what in s["counts"]]
    return sum(counts) if counts else None


def read(spec, result):
    spans = recorded()
    return None if spans is None else reduce(spans, spec)
