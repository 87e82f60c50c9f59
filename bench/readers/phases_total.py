"""One entry of the table the job handed on as ``phases``
(``lightgbm_tpu.obs.trace.capture_phases``), in device milliseconds of the
traced iteration.

  key     ``unscoped_s``: device seconds of the ops under no ``lgbm.*``
          scope; ``idle_after_scope``: the device's gaps, each put down to
          the scope of the op that ended before it
  scope   for a table a scope: the one scope read
  what    ``ms_per_iter``

No capture, no table, or a table without that entry (the parent's program;
no gap after an op of that scope) reads as nothing: never as 0.
"""


def read(spec, result):
    phases, tr = result.get("phases"), result.get("trace")
    if phases is None or tr is None:
        return None
    value = phases.get(spec["key"])
    if "scope" in spec and value is not None:
        value = value.get(spec["scope"])
    if value is None:
        return None
    if spec["what"] == "ms_per_iter":
        return 1e3 * value / tr["iters"]
    raise ValueError("phases_total: unknown 'what' %r" % spec["what"])
