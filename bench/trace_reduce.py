"""From a jax.profiler trace of one traced block to busy time, op times
and idle gaps.

What a v5e trace holds (looked at by hand, PR 26): plane ``/device:TPU:n``
has the lines ``XLA Modules`` (one event per executable), ``XLA Ops``
(every HLO op, NESTED: a ``%while`` or ``%cond`` event spans the ops of
its body) and ``Async XLA Ops`` (copy-start..copy-done windows, which
overlap compute). Busy time is therefore the union of the LEAF events of
``XLA Ops``: an event that contains a later event is control flow and is
dropped, or a while loop would read as 100% busy. Plane ``/host:CPU``,
line ``python``, holds the job's ``bench_*`` TraceAnnotations, on the same
clock; a device gap is named by the annotation its middle falls in.

An op's name is the text before `` = `` without the ``%``
(``build_histogram_pallas_vals.13``, ``fusion.251``).
"""
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_MARK = "bench_"


def short_name(name):
    return name.split(" = ", 1)[0].lstrip("%")


def load_events(trace_dir):
    """{"devices": [[(name, start_ns, dur_ns), ...] per chip],
        "host": [(name, start_ns, dur_ns), ...]} from the .xplane.pb."""
    from jax.profiler import ProfileData
    path = None
    for root, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                path = os.path.join(root, f)
    if path is None:
        return {"devices": [], "host": []}
    devices, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(short_name(e.name), e.start_ns,
                                     e.duration_ns) for e in line.events])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_MARK))
    return {"devices": devices, "host": host}


def leaf_events(events):
    """Events that contain no later event, in start order."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, dur) in enumerate(ev):
        if i + 1 < len(ev) and sum(ev[i + 1][1:]) <= start + dur:
            continue   # the next event lies inside this one: control flow
        out.append((name, start, dur))
    return out


def busy_and_gaps(leaves):
    """(union of the leaf intervals in ns, [(gap_start, gap_ns, op before)])."""
    busy, gaps = 0, []
    end, last = None, None
    for name, start, dur in leaves:
        if end is None or start >= end:
            if end is not None and start > end:
                gaps.append((end, start - end, last))
            busy += dur
            end = start + dur
        elif start + dur > end:
            busy += start + dur - end
            end = start + dur
        last = name
    return busy, gaps


def host_state(host, t):
    for name, start, dur in host:
        if start <= t < start + dur:
            return name[len(HOST_MARK):]
    return "other"


def top(pairs, n=10):
    return [[k, v] for k, v in sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


def reduce(events, traced_block):
    """The reduced trace the readers take their numbers from, or None when
    no operation ran on a device. Busy and op times are averaged over the
    chips in the trace, as the benchmark's contract defines ``busy_s``."""
    per_chip_busy, op_ns, gap_ns = [], {}, {}
    for dev in events["devices"]:
        leaves = leaf_events(dev)
        busy, gaps = busy_and_gaps(leaves)
        per_chip_busy.append(busy)
        for name, _, dur in leaves:
            op_ns[name] = op_ns.get(name, 0) + dur
        for start, dur, before in gaps:
            key = "%s, after %s" % (host_state(events["host"],
                                               start + dur // 2), before)
            gap_ns[key] = gap_ns.get(key, 0) + dur
    if not per_chip_busy or sum(per_chip_busy) <= 0:
        return None
    chips = len(per_chip_busy)
    op_s = {k: v / 1e9 / chips for k, v in op_ns.items()}
    return {"busy_s": sum(per_chip_busy) / 1e9 / chips,
            "window_s": traced_block["wall_s"],
            "iters": traced_block["iters"],
            "first_iter": traced_block["first_iter"],
            "op_s": op_s,
            "breakdown": {"device_ops": top(op_s),
                          "idle_gaps": top({k: v / 1e9 / chips
                                            for k, v in gap_ns.items()})}}
