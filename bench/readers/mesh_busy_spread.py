"""How unevenly the chips of a mesh were busy in the traced iteration: the
largest less the smallest chip's device busy time (the union of its leaf
ops, bench/trace_reduce.py, handed on by the job as
``trace["busy_s_per_chip"]``) over the traced block's wall, in percent.
Every split waits at the exchange for the slowest chip, so this is what
uneven shards cost there.

No trace, or a trace of fewer than two chips, reads as nothing: never as 0.
"""


def read(spec, result):
    tr = result.get("trace")
    busy = (tr or {}).get("busy_s_per_chip")
    if not busy or len(busy) < 2 or tr["window_s"] <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / tr["window_s"]
