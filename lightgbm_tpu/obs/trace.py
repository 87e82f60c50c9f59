"""Spans, event streams, Perfetto capture and its reduction by phase.

A span is host-side: ``with tracer.span("train.block")`` records name,
start and end (``perf_counter_ns``), the span that was open on the thread
when it opened, the thread, whether the body raised, and a small dict of
numeric counts. Closed spans sit in ONE bounded ring for the process
(``recorded_spans()``), and every span also enters a
``jax.profiler.TraceAnnotation("lgbm." + name)``, so a profiler session
started by anyone holds the program's spans on the device trace's clock.
Recording is always on (about 2 us a span) and never touches the device;
``observability=`` decides what a ``Tracer`` EXPORTS (registry summaries,
the event stream); a span itself never waits for the device. Spans sit at layer boundaries only: never per row, column, tile or
serving request (``obs/reqtrace.py`` keeps those).

Events are JSON-lines (one object per line, ``ts`` + ``event`` keys
always present), append-only and flushed per write so a preempted run
keeps everything it logged.

Perfetto capture rides ``jax.profiler.start_trace/stop_trace``; the
trace lands under ``<dir>/plugins/profile/...`` and loads in
ui.perfetto.dev or TensorBoard.  Capture is process-global in jax, so
the helper refuses to nest instead of crashing mid-train.
``capture_phases(dir)`` reduces such a capture to device seconds by
``lgbm.*`` scope and idle seconds by host span, and says what the rest is:
leaf events by scope, each device gap by the scope of the op that ended
before it, and the ten largest ops under no scope (``tools/trace_phases.py``
prints it).

What a ``train.block`` span counts of the DEVICE's work (``split_rows``,
``partition_tiles``, ``hist_rows``, ``hist_tiles``: core/grow.py
``WORK_COUNTS``) is added up in the split loop's state and stays on the
device; it joins the span when the host fetches the block's trees
(``materialize``), so a span still never waits. Set-up is under spans from
the process's start: ``runtime.before_import`` (recorded by
``lightgbm_tpu/__init__.py`` at the first import of ``basic`` or
``engine``, ending where the package's own import began),
``import.basic`` and ``import.engine`` for those two lazy imports, and
``train.engine`` around ``engine.train`` with
``train.booster_init`` for the booster's construction. (The backend's own
start-up has no span: ``jax.monitoring`` of jax 0.9.0 reports the three
compile phases and no backend-initialisation duration, so it lies inside
``runtime.before_import`` where a caller touched the device first, else
inside the first span that does.)
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import json
import re
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from ..log import Log
from .registry import MetricsRegistry, get_registry


# every scope the device program names and every host annotation a span
# writes starts with this, so one prefix finds both in a capture
SCOPE_PREFIX = "lgbm."


class EventStream:
    """Thread-safe JSON-lines sink (a file path or an open handle).

    Every record carries ``ts`` (wall clock) and ``seq`` — a per-stream
    monotonic counter assigned under the write lock.  ``seq`` is what
    ``tools/merge_events.py`` tie-breaks on when zipping streams from
    hosts with skewed clocks: wall time orders ACROSS streams, the
    monotonic counter orders WITHIN one.  ``static_fields`` (e.g.
    ``process``/``host`` in distributed runs) are stamped onto every
    record; ``ring`` is an optional flight recorder (anything with
    ``append``) that sees each record after it is written.
    """

    def __init__(self, path_or_fh, static_fields: Optional[Dict] = None,
                 ring=None):
        self._lock = threading.Lock()
        self._static = dict(static_fields or {})
        self._ring = ring
        self._seq = 0
        if hasattr(path_or_fh, "write"):
            self._fh = path_or_fh
            self._owns = False
        else:
            self._fh = open(path_or_fh, "a")
            self._owns = True

    def write(self, event: str, **fields) -> Dict:
        rec = {"ts": round(time.time(), 6), "event": event}
        rec.update(self._static)
        rec.update(fields)
        with self._lock:
            rec["seq"] = self._seq
            self._seq += 1
            line = json.dumps(rec, sort_keys=True, default=str) + "\n"
            self._fh.write(line)
            self._fh.flush()
        if self._ring is not None:
            self._ring.append(rec)
        return rec

    def mirrors_into(self, ring) -> bool:
        return ring is not None and self._ring is ring

    def flush(self, fsync: bool = False) -> None:
        """Push buffered lines to the OS and, with ``fsync=True``, to
        disk — called from the crash paths (HealthMonitor abort, the
        checkpoint SIGTERM latch, the flight recorder's dump) so the
        final events before a kill are never lost."""
        with self._lock:
            try:
                self._fh.flush()
                if fsync:
                    import os
                    os.fsync(self._fh.fileno())
            except (OSError, ValueError):
                pass   # closed handle / non-file sink: nothing to sync

    def close(self) -> None:
        self.flush(fsync=self._owns)
        with self._lock:
            if self._owns:
                self._fh.close()


# ------------------------------------------------------------ spans
# ONE bounded ring of closed spans for the process, whatever tracer closed
# them: recording is always on and costs a couple of microseconds a span;
# ``observability=`` only decides what a Tracer EXPORTS. ``start_ns`` and
# ``end_ns`` are ``time.perf_counter_ns()``; ``WALL_ANCHOR`` pairs one
# wall-clock reading with one perf-counter reading, so
# ``wall_ns = WALL_ANCHOR[0] + (t_ns - WALL_ANCHOR[1])``.
RING_SIZE = 4096
WALL_ANCHOR = (time.time_ns(), time.perf_counter_ns())
_ring = collections.deque(maxlen=RING_SIZE)
_ring_lock = threading.Lock()
_ids = itertools.count(1)
_open = threading.local()      # .stack: this thread's open spans, outermost first
_flight = None                 # the armed FlightRecorder, fed closed spans


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def feed_flight(recorder) -> None:
    """Arm (or, with None, disarm) the flight recorder that sees every
    closed span no event stream already mirrored into it."""
    global _flight
    _flight = recorder


def recorded_spans() -> List[Dict]:
    """The ring's closed spans, oldest first, as plain dicts: ``id``,
    ``name``, ``start_ns``, ``end_ns``, ``parent`` (the id of the span
    open on the same thread when this one opened, else None), ``thread``,
    ``failed`` and ``counts``."""
    with _ring_lock:
        spans = list(_ring)
    return [s.as_dict() for s in spans]


class _Span:
    """One span: a context manager while open, a record once closed."""

    __slots__ = ("id", "name", "start_ns", "end_ns", "parent", "thread",
                 "failed", "counts", "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, counts: Dict):
        self.id = next(_ids)
        self.name = name
        self.counts = counts
        self.start_ns = self.end_ns = 0
        self.parent = None
        self.thread = threading.get_ident()
        self.failed = False
        self._tracer = tracer
        self._annotation = None

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def as_dict(self) -> Dict:
        return {"id": self.id, "name": self.name, "start_ns": self.start_ns,
                "end_ns": self.end_ns, "parent": self.parent,
                "thread": self.thread, "failed": self.failed,
                "counts": dict(self.counts)}

    def __enter__(self):
        stack = _stack()
        if stack:
            self.parent = stack[-1].id
        stack.append(self)
        # the profiler's clock: whenever ANY jax.profiler session runs, the
        # span lies in its /host:CPU plane beside the device's ops
        self._annotation = TraceAnnotation(SCOPE_PREFIX + self.name)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        self._annotation = None
        stack = _stack()
        if self in stack:   # with what it still held, if closed out of order
            del stack[stack.index(self):]
        self.failed = exc_type is not None
        _keep(self)
        return False


def _keep(s: _Span) -> None:
    with _ring_lock:
        _ring.append(s)
    mirrored = s._tracer._export(s)
    if _flight is not None and not mirrored:
        _flight.record("span", **_span_fields(s))


def _span_fields(s: _Span) -> Dict:
    """A closed span as the fields of a ``span`` event."""
    wall_ns = WALL_ANCHOR[0] + (s.start_ns - WALL_ANCHOR[1])
    return dict(s.counts, span=s.name, span_id=s.id, parent=s.parent,
                thread=s.thread, start_ts=round(wall_ns / 1e9, 6),
                dur_s=round(s.duration_s, 6), failed=s.failed)


def record_span(name: str, duration_s: float, ended_ago_s: float = 0.0,
                **counts) -> None:
    """Record a span that has already happened and ended ``ended_ago_s``
    ago: now for the compile hook's (jax reports a duration after the
    fact). Its parent is the span open on this thread."""
    s = _Span(recorder, name, counts)
    s.end_ns = time.perf_counter_ns() - int(ended_ago_s * 1e9)
    s.start_ns = s.end_ns - int(duration_s * 1e9)
    stack = _stack()
    if stack:
        s.parent = stack[-1].id
    _keep(s)


class Tracer:
    """Span factory. Every span is recorded in the process ring; an
    ``enabled`` tracer also EXPORTS each one it closes (a registry summary,
    the event stream when it has one)."""

    def __init__(self, enabled: bool = True,
                 registry: Optional[MetricsRegistry] = None,
                 events: Optional[EventStream] = None,
                 metric: str = "lgbm_span_seconds"):
        self.enabled = enabled
        self._registry = registry if registry is not None else get_registry()
        self.events = events
        self._metric = metric

    def span(self, name: str, **counts) -> _Span:
        """Open a span. ``counts`` start the span's dict of numeric counts,
        which the body may add to through ``span.counts``."""
        return _Span(self, name, counts)

    def _export(self, s: _Span) -> bool:
        """True when the span went to an event stream that mirrors into
        the flight recorder."""
        if not self.enabled:
            return False
        self._registry.summary(
            self._metric, "Wall-clock span durations.",
            labels={"span": s.name}).observe(s.duration_s)
        if self.events is None:
            return False
        self.events.write("span", **_span_fields(s))
        return self.events.mirrors_into(_flight)


# the process's own tracer for code that has no TrainingObs at hand (ingest,
# the compile hook): records, exports nothing
recorder = Tracer(enabled=False)


# ------------------------------------------------------------ perfetto
_trace_lock = threading.Lock()
_trace_active = False


@contextlib.contextmanager
def perfetto_trace(trace_dir: Optional[str]):
    """Capture a ``jax.profiler`` trace into ``trace_dir`` for the body of
    the ``with``.  ``trace_dir`` falsy -> no-op.  Nested/concurrent
    captures degrade to a warning (jax's profiler is process-global).
    Yields True when a capture actually started."""
    global _trace_active
    if not trace_dir:
        yield False
        return
    with _trace_lock:
        if _trace_active:
            Log.warning("perfetto capture already active; skipping nested "
                        "capture into %s" % trace_dir)
            start = False
        else:
            _trace_active = True
            start = True
    if not start:
        yield False
        return
    started = False
    try:
        import jax
        try:
            jax.profiler.start_trace(trace_dir)
            started = True
        except Exception as e:  # profiler backend unavailable: degrade
            Log.warning("jax.profiler.start_trace failed (%s); continuing "
                        "without Perfetto capture" % e)
        yield started
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                Log.warning("jax.profiler.stop_trace failed: %s" % e)
        with _trace_lock:
            _trace_active = False


class PerfettoWindow:
    """Drive ``perfetto_trace`` over a [start, start+count) iteration
    window from inside the boosting loop.  ``step(lo, hi)`` is called
    before each dispatch covering iterations [lo, hi); capture starts
    when the window first overlaps and stops once ``hi`` passes the end
    (fused blocks widen the capture to block granularity)."""

    def __init__(self, trace_dir: str, start_iter: int, num_iters: int):
        self.trace_dir = trace_dir
        self.lo = int(start_iter)
        self.hi = int(start_iter) + int(num_iters)
        self._cm = None
        self.captured = False

    def step(self, lo: int, hi: int) -> None:
        if self._cm is None and lo < self.hi and hi > self.lo:
            self._cm = perfetto_trace(self.trace_dir)
            self.captured = bool(self._cm.__enter__())
        elif self._cm is not None and lo >= self.hi:
            self.close()

    def close(self) -> None:
        if self._cm is not None:
            cm, self._cm = self._cm, None
            cm.__exit__(None, None, None)


# ------------------------------------------------------------ reduction
# What a v5e capture holds (looked at by hand, PR 26 and PR 27): plane
# ``/device:TPU:n`` has the lines ``XLA Modules`` (one event per executable
# run, named ``jit_run_block(<fingerprint>)``) and ``XLA Ops`` (every HLO
# op, NESTED: a ``%while`` event spans the ops of its body), so busy time
# is the union of the LEAF events. A device event carries NO scope: its name
# is the HLO instruction's text without metadata (``%fusion.243 = ...``) and
# its stats are times. The scope sits in plane ``/host:metadata``, whose
# event metadata (one per executable, same name as the module's events)
# holds the stat ``Hlo Proto``: the compiled module, where every
# instruction has ``metadata.op_name``
# (``jit(run_block)/while/body/.../lgbm.partition_scatter/scatter``). So an
# op's scoped name is looked up by (module, instruction name).
# ``jax.profiler.ProfileData`` does not expose event metadata, hence the few
# lines of protobuf wire format below. Plane ``/host:CPU`` holds the
# TraceAnnotations, the spans' among them, on the same clock.
_DEVICE_PLANE = "/device:TPU:"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_HOST_PLANE = "/host:CPU"
_HLO_PLANE = "/host:metadata"
_SCOPE_RE = re.compile(re.escape(SCOPE_PREFIX) + r"[A-Za-z0-9_]+")

Event = Tuple[str, int, int]           # (text, start_ns, duration_ns)


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _wire_fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError("xplane: wire type %d" % kind)
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def _descend(bufs, *numbers):
    """The sub-messages reached from ``bufs`` through these field numbers."""
    for number in numbers:
        bufs = [v for buf in bufs for k, v in _wire_fields(buf)
                if k == number]
    return bufs


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def hlo_op_names(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """{module: {instruction: op_name}} from the compiled modules a capture
    carries. Field numbers: XSpace.planes=1; XPlane.name=2,
    .event_metadata=4 (map: value=2); XEventMetadata.name=2, .stats=5;
    XStat.bytes_value=6; HloProto.hlo_module=1; HloModuleProto
    .computations=3; HloComputationProto.instructions=2;
    HloInstructionProto.name=1, .metadata=7; OpMetadata.op_name=2."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _descend([memoryview(xplane)], 1):
        if [_text(v) for v in _descend([plane], 2)] != [_HLO_PLANE]:
            continue
        for meta in _descend([plane], 4, 2):
            names = out.setdefault(_text(_descend([meta], 2)[0]), {})
            for instr in _descend([meta], 5, 6, 1, 3, 2):
                for op_name in _descend([instr], 7, 2):
                    names[_text(_descend([instr], 1)[0])] = _text(op_name)
    return out


def load_capture(trace_dir: str) -> Dict[str, list]:
    """{"devices": [[(op_name, start_ns, dur_ns), ...] per chip],
    "host": [(span name, start_ns, dur_ns), ...]} from the newest
    ``.xplane.pb`` under ``trace_dir``. ``op_name`` is the scoped name the
    compiled module gives the event's instruction; where that name holds no
    ``lgbm.`` scope, the event's own short name before it (``copy.478
    jit(run_block)/while/body/copy``: the instruction and where it sits),
    and the short name alone where the module gives none."""
    import bisect
    import os
    from jax.profiler import ProfileData
    path = None
    for root, _, files in os.walk(trace_dir):
        for f in sorted(files):
            if f.endswith(".xplane.pb"):
                path = os.path.join(root, f)
    devices, host = [], []
    if path is None:
        return {"devices": devices, "host": host}
    with open(path, "rb") as fh:
        raw = fh.read()
    op_names = hlo_op_names(raw)
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith(_DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            if _OPS_LINE not in lines:
                continue
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in (lines[_MODULES_LINE].events
                          if _MODULES_LINE in lines else ()))
            starts = [m[0] for m in modules]
            ops = []
            for e in lines[_OPS_LINE].events:
                short = e.name.split(" = ", 1)[0].lstrip("%")
                at = bisect.bisect_right(starts, e.start_ns) - 1
                names = op_names.get(modules[at][2], {}) \
                    if at >= 0 and e.start_ns < modules[at][1] else {}
                full = names.get(short)
                if full is None:
                    full = short
                elif scope_of(full) is None:
                    full = "%s %s" % (short, full)
                ops.append((full, e.start_ns, e.duration_ns))
            devices.append(ops)
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name[len(SCOPE_PREFIX):], e.start_ns,
                             e.duration_ns) for e in line.events
                            if e.name.startswith(SCOPE_PREFIX))
    return {"devices": devices, "host": host}


def scope_of(op_name: str) -> Optional[str]:
    """The LAST ``lgbm.`` component of an op's name: the innermost scope."""
    found = _SCOPE_RE.findall(op_name)
    return found[-1] if found else None


def _leaf_events(events: Iterable[Event]) -> List[Event]:
    """Events that contain no later event, in start order."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(ev)
            if not (i + 1 < len(ev)
                    and ev[i + 1][1] + ev[i + 1][2] <= e[1] + e[2])]


def reduce_phases(events: Dict[str, list]) -> Optional[Dict]:
    """Device seconds by scope and idle seconds by host span, averaged
    over the chips in the capture. None when no operation ran on a device
    or NO op carries an ``lgbm.`` scope at all (an executable compiled by
    a build without scopes and loaded from the compile cache shows none):
    nothing to read is never read as 0.

    What the remainder is: ``events_by_scope`` counts the leaf events
    under each scope (seconds divide into calls); ``idle_after_scope`` puts
    each device gap down to the scope of the op that ended before it
    (``unscoped`` where it has none: a gap of the device program's own is
    named by the phase that left it, whatever number the compiler gave its
    fusion); ``unscoped_ops`` lists the ten largest ops under no scope as
    [name, seconds, events]."""
    chips = [_leaf_events(dev) for dev in events["devices"]]
    chips = [leaves for leaves in chips if leaves]
    if not chips:
        return None
    busy_ns, unscoped_ns = 0, 0
    scope_ns: Dict[str, int] = {}
    scope_events: Dict[str, int] = {}
    idle_ns: Dict[str, int] = {}
    after_ns: Dict[str, int] = {}
    bare: Dict[str, List[int]] = {}        # unscoped op -> [ns, events]
    for leaves in chips:
        end, ended = None, None            # ended: scope of the op at `end`
        for op_name, start, dur in leaves:
            scope = scope_of(op_name)
            if scope is None:
                unscoped_ns += dur
                seen = bare.setdefault(op_name, [0, 0])
                seen[0] += dur
                seen[1] += 1
            else:
                scope_ns[scope] = scope_ns.get(scope, 0) + dur
                scope_events[scope] = scope_events.get(scope, 0) + 1
            if end is None or start >= end:
                if end is not None and start > end:
                    span = _innermost_span(events["host"],
                                           end + (start - end) // 2)
                    idle_ns[span] = idle_ns.get(span, 0) + start - end
                    after = ended or "unscoped"
                    after_ns[after] = after_ns.get(after, 0) + start - end
                busy_ns += dur
                end, ended = start + dur, scope
            elif start + dur > end:
                busy_ns += start + dur - end
                end, ended = start + dur, scope
    if not scope_ns:
        return None
    n = len(chips)
    per = 1e9 * n
    by_size = lambda d: dict(sorted(((k, v / per) for k, v in d.items()),
                                    key=lambda kv: -kv[1]))
    a_chip = lambda count: count // n if count % n == 0 else count / n
    largest = sorted(bare.items(), key=lambda kv: -kv[1][0])[:10]
    return {"busy_s": busy_ns / per, "by_scope": by_size(scope_ns),
            "unscoped_s": unscoped_ns / per, "idle_by_span": by_size(idle_ns),
            "events_by_scope": {k: a_chip(scope_events[k])
                                for k in by_size(scope_ns)},
            "idle_after_scope": by_size(after_ns),
            "unscoped_ops": [[name, ns / per, a_chip(count)]
                             for name, (ns, count) in largest]}


def _innermost_span(host: Iterable[Event], t: int) -> str:
    """The shortest host span that holds instant ``t``."""
    best, best_dur = "none", None
    for name, start, dur in host:
        if start <= t < start + dur and (best_dur is None or dur < best_dur):
            best, best_dur = name, dur
    return best


def capture_phases(trace_dir: str) -> Optional[Dict]:
    """One ``jax.profiler`` capture as a table: ``busy_s``, ``by_scope``
    (device seconds under each ``lgbm.*`` scope), ``unscoped_s`` and
    ``idle_by_span`` (device gaps by the innermost ``lgbm.*`` host span
    their middle falls in); and, of what those leave over,
    ``events_by_scope``, ``idle_after_scope`` and ``unscoped_ops``
    (reduce_phases)."""
    return reduce_phases(load_capture(trace_dir))
