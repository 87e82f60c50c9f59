"""Distributed telemetry: cross-process federation, straggler skew, and
the flight recorder (ISSUE 10).

Three pieces, all strictly host-side — nothing here runs inside (or
changes) a compiled program, so the training step's jaxpr fingerprint
and per-wave psum count are byte-identical with this module on or off:

- **Federation.** Each process's ``MetricsRegistry`` grows constant
  ``process=<jax.process_index()>`` / ``host=<hostname>`` labels injected
  at exposition time (``registry.set_global_labels`` — no call-site
  changes anywhere).  Once per fused block the processes allgather their
  JSON snapshot + Prometheus text (piggy-backed on the same allgather
  that carries the block timings), and every process caches the merged
  cluster view; the StatsServer's ``/metrics/cluster`` + ``/stats/cluster``
  routes serve that cache — scrapes are pull-only and never trigger a
  collective.  With ``jax.process_count() == 1`` the cluster routes
  degenerate to exactly the local snapshot and no allgather is ever
  issued.

- **Per-wave comm/compute attribution + straggler detection.**  The
  training loop hands ``on_block`` a host/device wall-time split for each
  synced dispatch (host side: feature-mask sampling + dispatch until the
  async call returns; device side: the ``block_until_ready`` wait).  The
  allgathered walls yield ``lgbm_wave_straggler_skew`` (max/median) and a
  per-wave stall estimate: this process's device wait minus the cluster
  minimum is time spent waiting on slower peers at the wave collectives
  — the comm-vs-compute split the GBDT benchmarking literature
  (PAPERS.md 1809.04559) calls out as what separates tuned from untuned
  distributed runs.  Skew above ``obs_straggler_warn_skew`` routes a
  warn-only report through the HealthMonitor (like stumps, stragglers
  never escalate to abort — they are an infra symptom, not a training
  anomaly).

- **Flight recorder.**  A bounded ring of the most recent events/spans
  per process that dumps to ``<obs_event_file>.<process>.crash.jsonl``
  on HealthMonitor abort, SIGTERM, or an unhandled exception — the
  post-mortem for "what was rank 3 doing when the run hung".
  ``tools/merge_events.py`` zips per-host streams (and crash dumps) into
  one time-ordered timeline.

Transport: host metadata only, never inside a compiled program.  On
backends that support multiprocess computations the allgather is
``multihost_utils.process_allgather`` (``parallel.network.JaxHostComm``);
the CPU backend cannot run cross-process computations at all, so there
the coordination-service KV store carries the payload
(``parallel.network.KvHostComm``) — ``parallel.network.default_host_comm``
picks.  Calls are SPMD-lockstep by construction: every process runs the
same block cadence, so allgather N on one process pairs with allgather N
on every other.
"""
from __future__ import annotations

import collections
import json
import os
import signal
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..log import Log
from . import trace
from .registry import MetricsRegistry, get_registry


def process_env() -> Tuple[int, int, str]:
    """(process_index, process_count, hostname) — safe to call whether or
    not jax.distributed is initialized (defaults to a single process)."""
    idx, count = 0, 1
    try:
        import jax
        idx = int(jax.process_index())
        count = int(jax.process_count())
    except Exception:
        pass
    import socket
    return idx, count, socket.gethostname()


def straggler_skew(walls: Sequence[float]) -> Tuple[float, int]:
    """``(max/median, argmax)`` over per-process wall times.  The
    max/median ratio is robust to one slow outlier inflating the mean
    (the straggler itself must not drag the denominator); a degenerate
    median (all ~zero) reports 1.0, never inf/NaN."""
    vals = [max(float(w), 0.0) for w in walls]
    if not vals:
        return 1.0, -1
    s = sorted(vals)
    n = len(s)
    med = s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])
    mx = max(vals)
    arg = vals.index(mx)
    if med <= 1e-12:
        return 1.0, arg
    return mx / med, arg


def merge_prometheus_texts(texts: Sequence[str]) -> str:
    """Merge per-process Prometheus expositions into one: HELP/TYPE
    headers deduplicated (first process wins), sample lines grouped per
    family with every process's series kept — the per-process
    ``process=".."`` global labels make them distinct series, so no
    value-level merging is needed or wanted."""
    fams: Dict[str, Dict[str, List[str]]] = {}

    def fam(name: str) -> Dict[str, List[str]]:
        return fams.setdefault(name, {"help": [], "type": [], "samples": []})

    for text in texts:
        cur: Optional[str] = None
        for line in (text or "").splitlines():
            if line.startswith("# HELP "):
                cur = line.split()[2]
                f = fam(cur)
                if not f["help"]:
                    f["help"].append(line)
            elif line.startswith("# TYPE "):
                cur = line.split()[2]
                f = fam(cur)
                if not f["type"]:
                    f["type"].append(line)
            elif line.strip():
                if cur is None:        # headerless stray: key by base name
                    cur = line.split("{")[0].split(" ")[0]
                fam(cur)["samples"].append(line)
    lines: List[str] = []
    for name in sorted(fams):
        f = fams[name]
        lines += f["help"] + f["type"] + f["samples"]
    return "\n".join(lines) + ("\n" if lines else "")


class FlightRecorder:
    """Bounded in-memory ring of recent telemetry records that dumps to
    ``<base_path>.<process>.crash.jsonl`` when the run dies.

    Fed by the EventStream (every written record lands here too) and by
    direct ``record()`` calls; ``install()`` hooks SIGTERM and
    ``sys.excepthook`` so the dump happens on kills and unhandled
    exceptions, and the HealthMonitor's fatal path calls ``dump``
    explicitly.  The SIGTERM hook chains: it dumps, restores the previous
    handler, and re-delivers the signal — composing with the checkpoint
    callback's latch-then-resign protocol (checkpoint/callback.py), which
    restores THIS handler before re-raising, so a checkpointed run dumps
    after its final snapshot and still exits like a SIGTERM'd process.
    Only the first dump wins (``dump`` latches), so abort-then-SIGTERM
    never truncates an earlier, more complete dump.
    """

    def __init__(self, base_path: str, process_index: int = 0,
                 size: int = 512, on_dump=None):
        self.process_index = int(process_index)
        self.dump_path = "%s.%d.crash.jsonl" % (base_path,
                                                self.process_index)
        self._ring = collections.deque(maxlen=max(int(size), 1))
        self._lock = threading.Lock()
        self._dumped = False
        self._on_dump = on_dump
        self._installed = False
        self._prev_sigterm = None
        self._prev_hook = None

    # ------------------------------------------------------------ feed
    def append(self, rec: Dict) -> None:
        with self._lock:
            self._ring.append(dict(rec))

    def record(self, event: str, **fields) -> None:
        rec = {"ts": round(time.time(), 6), "event": event}
        rec.update(fields)
        self.append(rec)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    # ------------------------------------------------------------ dump
    def dump(self, reason: str) -> Optional[str]:
        with self._lock:
            if self._dumped:
                return self.dump_path
            self._dumped = True
            recs = list(self._ring)
        if self._on_dump is not None:
            try:
                self._on_dump(reason)
            except Exception:
                pass
        header = {"ts": round(time.time(), 6),
                  "event": "flight_recorder_dump", "reason": str(reason),
                  "process": self.process_index, "entries": len(recs)}
        try:
            with open(self.dump_path, "w") as fh:
                fh.write(json.dumps(header, sort_keys=True) + "\n")
                for rec in recs:
                    fh.write(json.dumps(rec, sort_keys=True,
                                        default=str) + "\n")
                fh.flush()
                os.fsync(fh.fileno())
        except OSError as e:
            Log.warning("obs: flight recorder dump to %s failed: %s"
                        % (self.dump_path, e))
            return None
        return self.dump_path

    # ------------------------------------------------------------ hooks
    def install(self) -> None:
        """Arm the SIGTERM + excepthook crash paths (idempotent)."""
        if self._installed:
            return
        self._installed = True
        trace.feed_flight(self)     # closed spans, as events are fed
        self._prev_hook = sys.excepthook
        sys.excepthook = self._excepthook
        if threading.current_thread() is threading.main_thread():
            try:
                self._prev_sigterm = signal.signal(signal.SIGTERM,
                                                   self._on_sigterm)
            except ValueError:
                self._prev_sigterm = None

    def uninstall(self) -> None:
        if not self._installed:
            return
        self._installed = False
        if trace._flight is self:
            trace.feed_flight(None)
        # == not `is`: attribute access mints a fresh bound method, so an
        # identity check never matches the one install() stored
        if sys.excepthook == self._excepthook:
            sys.excepthook = self._prev_hook or sys.__excepthook__
        try:
            if signal.getsignal(signal.SIGTERM) == self._on_sigterm:
                signal.signal(signal.SIGTERM,
                              self._prev_sigterm
                              if self._prev_sigterm is not None
                              else signal.SIG_DFL)
        except ValueError:
            pass

    def _on_sigterm(self, signum, frame) -> None:
        self.dump("sigterm")
        prev = self._prev_sigterm
        self._installed = False
        try:
            signal.signal(signal.SIGTERM,
                          prev if prev is not None else signal.SIG_DFL)
        except ValueError:
            pass
        if callable(prev):
            prev(signum, frame)
        else:
            signal.raise_signal(signal.SIGTERM)

    def _excepthook(self, etype, value, tb) -> None:
        try:
            self.dump("exception:%s" % getattr(etype, "__name__", etype))
        except Exception:
            pass
        (self._prev_hook or sys.__excepthook__)(etype, value, tb)


class DistributedObs:
    """Per-process distributed-telemetry driver.

    Constructed by ``TrainingObs.from_config`` when observability is on
    and more than one jax process exists (or ``obs_distributed=on``).
    The training loop calls ``on_block`` once per synced dispatch; the
    StatsServer serves ``cluster_stats``/``cluster_prometheus``.  Tests
    drive it with an injected ``comm`` (``parallel.network.LoopbackComm``)
    and explicit process identity — no cluster required.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 monitor=None, comm=None,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None,
                 hostname: Optional[str] = None,
                 warn_skew: float = 2.0,
                 set_labels: bool = True,
                 timeout_ms: int = 60000):
        env_idx, env_count, env_host = process_env()
        self.process_index = env_idx if process_index is None \
            else int(process_index)
        self.process_count = env_count if process_count is None \
            else int(process_count)
        self.hostname = env_host if hostname is None else str(hostname)
        self.registry = registry if registry is not None else get_registry()
        self.monitor = monitor
        self.warn_skew = float(warn_skew)
        self._lock = threading.Lock()
        self._cluster: Optional[Dict] = None
        self._block = 0
        self._degraded = False
        if comm is None and self.process_count > 1:
            from ..parallel.network import default_host_comm
            comm = default_host_comm(namespace="lgbm_obs",
                                     timeout_ms=timeout_ms)
        self._comm = comm
        if set_labels and self.process_count > 1:
            self.registry.set_global_labels({
                "process": str(self.process_index), "host": self.hostname})
        self._g_skew = self.registry.gauge(
            "lgbm_wave_straggler_skew",
            "Max/median of per-process block wall time over the last "
            "allgathered dispatch (1.0 = perfectly balanced).")
        self._g_straggler = self.registry.gauge(
            "lgbm_dist_straggler_process",
            "Process index with the largest wall time in the last "
            "allgathered dispatch.")
        self._g_wall = self.registry.gauge(
            "lgbm_dist_block_seconds",
            "This process's wall time for the last synced dispatch.")
        self._g_host = self.registry.gauge(
            "lgbm_dist_block_host_seconds",
            "Host-side share of the last dispatch (feature sampling + "
            "dispatch until the async call returned).")
        self._g_dev = self.registry.gauge(
            "lgbm_dist_block_device_seconds",
            "Device-side share of the last dispatch (the "
            "block_until_ready wait: compute + wave collectives).")
        self._g_wave = self.registry.gauge(
            "lgbm_dist_wave_seconds",
            "This process's wall time per frontier wave over the last "
            "dispatch.")
        self._g_stall = self.registry.gauge(
            "lgbm_dist_wave_stall_seconds",
            "Per-wave stall estimate: this process's device wait minus "
            "the cluster minimum — time spent waiting on slower peers "
            "at the wave collectives.")
        self._c_blocks = self.registry.counter(
            "lgbm_dist_blocks_total",
            "Synced dispatches accounted by distributed obs.")
        self._c_allgathers = self.registry.counter(
            "lgbm_dist_allgathers_total",
            "Host-metadata allgathers issued (one per block when more "
            "than one process participates; always 0 single-process).")
        self._c_straggler = self.registry.counter(
            "lgbm_dist_straggler_blocks_total",
            "Blocks whose wall-time skew crossed "
            "obs_straggler_warn_skew.")

    # ------------------------------------------------------------ blocks
    def on_block(self, start_iter: int, count: int, busy_s: float,
                 wait_s: float, waves: float = 0.0) -> Optional[Dict]:
        """Account one synced dispatch and (multi-process) run the
        once-per-block allgather: timings + snapshot federation,
        straggler skew, cluster cache refresh.  Returns the cluster
        stats document, or None when single-process/degraded."""
        busy_s = max(float(busy_s), 0.0)
        wait_s = max(float(wait_s), 0.0)
        wall = busy_s + wait_s
        waves = max(float(waves), 0.0)
        self._g_wall.set(wall)
        self._g_host.set(busy_s)
        self._g_dev.set(wait_s)
        if waves > 0:
            self._g_wave.set(wall / waves)
        self._c_blocks.inc()
        if self.process_count <= 1 or self._comm is None:
            self._g_skew.set(1.0)
            return None
        if self._degraded:
            return None
        rec = {"process": self.process_index, "host": self.hostname,
               "block": self._block, "start_iter": int(start_iter),
               "count": int(count), "busy_s": round(busy_s, 6),
               "wait_s": round(wait_s, 6), "wall_s": round(wall, 6),
               "waves": waves}
        payload = {"timing": rec, "stats": self.registry.snapshot(),
                   "prom": self.registry.prometheus_text()}
        try:
            gathered = self._comm.allgather(payload)
            self._c_allgathers.inc()
        except Exception as e:
            # telemetry must never kill training: one warning, then the
            # rest of the run is local-only
            self._degraded = True
            Log.warning("obs.distributed: host allgather failed (%s); "
                        "cluster federation disabled for the rest of "
                        "this run" % e)
            return None
        self._block += 1
        timings = sorted((g["timing"] for g in gathered),
                         key=lambda t: t["process"])
        skew, arg = straggler_skew([t["wall_s"] for t in timings])
        straggler = timings[arg]["process"] if 0 <= arg < len(timings) \
            else -1
        self._g_skew.set(skew)
        self._g_straggler.set(straggler)
        min_dev = min(t["wait_s"] for t in timings)
        stall = max(wait_s - min_dev, 0.0)
        self._g_stall.set(stall / waves if waves > 0 else stall)
        doc = {
            "ts": round(time.time(), 3),
            "process_count": self.process_count,
            "block": rec["block"],
            "processes": {str(g["timing"]["process"]): g["stats"]
                          for g in gathered},
            "timings": {str(t["process"]): t for t in timings},
            "straggler": {"skew": round(skew, 4), "process": straggler,
                          "threshold": self.warn_skew},
        }
        prom = merge_prometheus_texts([g["prom"] for g in gathered])
        with self._lock:
            self._cluster = {"stats": doc, "prom": prom}
        if self.warn_skew > 0 and skew >= self.warn_skew:
            self._c_straggler.inc()
            note = getattr(self.monitor, "note_straggler", None)
            if note is not None:
                note(iteration=int(start_iter), process=straggler,
                     skew=skew, threshold=self.warn_skew)
            else:
                Log.warning(
                    "obs.distributed: process %d is a straggler "
                    "(wall-time skew %.2fx >= %.2fx) at iteration %d"
                    % (straggler, skew, self.warn_skew, int(start_iter)))
        return doc

    # ------------------------------------------------------------ routes
    def cluster_stats(self) -> Dict:
        """The ``/stats/cluster`` body.  Single-process: exactly the live
        local snapshot (and no allgather is ever issued).  Multi-process:
        the cached merge from the last block's allgather; before the
        first block completes, a pending doc carrying only the local
        snapshot."""
        if self.process_count <= 1:
            return self.registry.snapshot()
        with self._lock:
            cached = self._cluster
        if cached is None:
            return {"ts": round(time.time(), 3), "pending": True,
                    "process_count": self.process_count,
                    "processes": {str(self.process_index):
                                  self.registry.snapshot()}}
        return cached["stats"]

    def cluster_prometheus(self) -> str:
        """The ``/metrics/cluster`` body (same caching rules as
        ``cluster_stats``)."""
        if self.process_count <= 1:
            return self.registry.prometheus_text()
        with self._lock:
            cached = self._cluster
        if cached is None:
            return self.registry.prometheus_text()
        return cached["prom"]
