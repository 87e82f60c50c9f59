"""Device time of the traced iteration under the program's own scopes.

The job reduced the capture with ``lightgbm_tpu.obs.trace.capture_phases``
(scope joined to op through the capture's ``Hlo Proto``) before it dropped
it, and handed the table on as ``phases``: ``by_scope`` holds device
seconds under each ``lgbm.*`` scope.

  scopes   the scopes read together
  what     ``ms_per_iter``: their device milliseconds an iteration

No capture, no table (a program without scopes) or no op under any of the
scopes reads as nothing: never as 0.
"""


def read(spec, result):
    phases, tr = result.get("phases"), result.get("trace")
    if phases is None or tr is None:
        return None
    found = [phases["by_scope"][s] for s in spec["scopes"]
             if s in phases["by_scope"]]
    if not found:
        return None
    if spec["what"] == "ms_per_iter":
        return 1e3 * sum(found) / tr["iters"]
    raise ValueError("trace_phases: unknown 'what' %r" % spec["what"])
