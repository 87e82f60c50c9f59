"""Supervised training: watchdog + restart loop + peer-death detection.

Three layers, smallest blast radius first:

- :class:`Watchdog` — a deadline on the per-iteration heartbeat the
  training loop emits (the synced ``block_until_ready`` window the obs
  layer already times). The FIRST deadline is warmup-aware: the initial
  compile legitimately takes far longer than any later iteration, so the
  grace window is added until the first beat lands. On expiry it sets the
  fault-injection abort event, which wakes cooperative waits (injected
  hangs) into a :class:`~..resilience.faults.WatchdogAbort`.

- :class:`Supervisor` — the in-process restart loop behind
  ``train(supervise=True)``: on a crash or watchdog abort it records the
  flight-dump path the engine attached to the exception, sleeps a bounded
  exponential backoff, and re-runs the attempt with
  ``resume_from=checkpoint_dir`` (byte-exact resume, PR 3 contract).
  After ``max_restarts`` failed restarts it raises with the LAST
  flight-dump path in the message — the operator's entry point.

- :class:`ProcessSupervisor` — the same loop one level up: the trainer is
  a child process, so SIGKILL and genuinely-stuck dispatches (which no
  in-process watchdog can interrupt) are survivable. Hang detection rides
  a heartbeat FILE the trainer touches each iteration
  (``supervise_heartbeat_file`` / :func:`heartbeat_file_callback`);
  a stale heartbeat gets the child SIGKILLed and restarted. The chaos
  smoke drives kill-and-resume byte-identity through this class.

- :class:`KvHeartbeat` — per-rank liveness leases in the jax.distributed
  coordination-service KV store, so a multi-process rank can fail fast
  with "rank 1 is dead" instead of blocking a full KV timeout
  (``KvHostComm(peer_guard=hb.dead_peers)``).
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import Callable, List, Optional

from ..log import LightGBMError, Log
from . import faults

ATTEMPT_ENV = "LGBM_SUPERVISOR_ATTEMPT"


def _registry_counter(name: str, doc: str):
    from ..obs.registry import get_registry
    return get_registry().counter(name, doc)


class Watchdog:
    """Heartbeat deadline with a warmup-aware first window.

    ``beat()`` is called by the training loop each iteration; until the
    first beat the deadline is ``timeout_s + warmup_grace_s`` (the first
    compile is slow-but-alive), after it plain ``timeout_s``. On expiry
    ``on_fire(elapsed_s)`` runs once and the fault-injection abort event
    is set so cooperative waits unwind as WatchdogAbort.
    """

    def __init__(self, timeout_s: float, warmup_grace_s: float = 0.0,
                 on_fire: Optional[Callable[[float], None]] = None,
                 name: str = "train"):
        self.timeout_s = float(timeout_s)
        self.warmup_grace_s = max(float(warmup_grace_s), 0.0)
        self.on_fire = on_fire
        self.name = name
        self.fired = False
        self.beats = 0
        self._deadline = 0.0
        self._last = 0.0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Watchdog":
        now = time.monotonic()
        with self._lock:
            self._last = now
            self._deadline = now + self.timeout_s + self.warmup_grace_s
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="lgbm-watchdog-%s" % self.name,
            daemon=True)
        self._thread.start()
        return self

    def beat(self) -> None:
        now = time.monotonic()
        with self._lock:
            self.beats += 1
            self._last = now
            self._deadline = now + self.timeout_s

    def _loop(self) -> None:
        poll = max(min(self.timeout_s / 4.0, 0.5), 0.01)
        while not self._stop.wait(poll):
            with self._lock:
                expired = time.monotonic() > self._deadline
                elapsed = time.monotonic() - self._last
            if expired and not self.fired:
                self.fired = True
                Log.warning("watchdog %r fired: no heartbeat for %.1fs "
                            "(timeout %.1fs%s)", self.name, elapsed,
                            self.timeout_s,
                            ", warmup grace spent" if not self.beats else "")
                faults.request_abort(
                    "watchdog %r: no heartbeat for %.1fs"
                    % (self.name, elapsed))
                if self.on_fire is not None:
                    try:
                        self.on_fire(elapsed)
                    except Exception:
                        pass
                return

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def callback(self):
        """A before_iteration training callback that beats this watchdog."""
        wd = self

        class _Beat:
            before_iteration = True
            order = -100          # first: the beat must precede any work

            def __call__(self, env):
                wd.beat()

        return _Beat()


def heartbeat_file_callback(path: str):
    """A before_iteration callback touching ``path`` every iteration —
    the cross-process heartbeat a :class:`ProcessSupervisor` watches."""

    class _Touch:
        before_iteration = True
        order = -99
        heartbeat_path = path

        def __call__(self, env):
            with open(path, "w") as fh:
                fh.write("%d %.6f\n" % (env.iteration, time.time()))

    return _Touch()


class Supervisor:
    """In-process restart loop: crash / watchdog-abort -> flight dump ->
    bounded exponential backoff -> resume from the newest valid
    checkpoint -> retry, up to ``max_restarts`` restarts."""

    def __init__(self, checkpoint_dir: str, max_restarts: int = 3,
                 backoff_s: float = 1.0, backoff_max_s: float = 60.0,
                 hang_timeout_s: float = 0.0, warmup_grace_s: float = 120.0):
        if not checkpoint_dir:
            raise LightGBMError(
                "supervised training needs checkpoint_dir: auto-resume "
                "has nowhere to resume from")
        self.checkpoint_dir = checkpoint_dir
        self.max_restarts = max(int(max_restarts), 0)
        self.backoff_s = max(float(backoff_s), 0.0)
        self.backoff_max_s = max(float(backoff_max_s), self.backoff_s)
        self.hang_timeout_s = max(float(hang_timeout_s), 0.0)
        self.warmup_grace_s = max(float(warmup_grace_s), 0.0)
        self.restarts = 0
        self.last_flight_dump: Optional[str] = None
        self._c_restarts = _registry_counter(
            "lgbm_supervisor_restarts_total",
            "Supervised-training restarts (crash, watchdog, or SIGTERM).")
        self._c_fires = _registry_counter(
            "lgbm_supervisor_watchdog_fires_total",
            "Watchdog deadline expiries during supervised training.")

    def run(self, attempt: Callable):
        """``attempt(resume_from, watchdog)`` until it returns; the first
        try resumes from ``initial_resume`` (usually None), every retry
        from the supervisor's checkpoint dir."""
        delay = self.backoff_s
        resume: Optional[str] = None
        while True:
            wd: Optional[Watchdog] = None
            if self.hang_timeout_s > 0:
                wd = Watchdog(self.hang_timeout_s, self.warmup_grace_s,
                              on_fire=lambda _s: self._c_fires.inc())
                wd.start()
            try:
                result = attempt(resume, wd)
                return result
            except Exception as e:  # noqa: BLE001 - the restart seam
                dump = getattr(e, "flight_dump_path", None)
                if dump:
                    self.last_flight_dump = dump
                self.restarts += 1
                self._c_restarts.inc()
                if self.restarts > self.max_restarts:
                    suffix = (" (last flight dump: %s)" % self.last_flight_dump
                              if self.last_flight_dump else "")
                    raise LightGBMError(
                        "supervised training failed after %d restart%s: "
                        "%s: %s%s" % (self.max_restarts,
                                      "" if self.max_restarts == 1 else "s",
                                      type(e).__name__, e, suffix)) from e
                Log.warning(
                    "supervisor: attempt %d failed (%s: %s); resuming from "
                    "%s in %.1fs%s", self.restarts, type(e).__name__, e,
                    self.checkpoint_dir, delay,
                    " [flight dump %s]" % dump if dump else "")
                time.sleep(delay)
                delay = min(delay * 2.0, self.backoff_max_s)
                resume = self.checkpoint_dir
            finally:
                if wd is not None:
                    wd.stop()
                faults.clear_abort()


class ProcessSupervisor:
    """Restart loop around a trainer CHILD process — survives SIGKILL and
    non-cooperative hangs. The child is expected to resume itself (pass a
    ``resume``/``checkpoint_dir`` that makes a rerun continue); the
    supervisor's job is only death/hang detection, backoff, and the
    restart budget. Each attempt's index rides the LGBM_SUPERVISOR_ATTEMPT
    env var so chaos workers can arm faults on attempt 0 only.

    One process for each chip: an accelerator belongs to the process that
    first touched jax, and a child that needs it then fails or hangs. This
    supervisor is sound only while its own process stays off jax — no
    ``jax.devices()``, no ``Dataset`` construction (binning initialises
    the backend), nothing that imports a jitted module and calls it. Bin
    and train in the child."""

    def __init__(self, argv: List[str], max_restarts: int = 3,
                 backoff_s: float = 0.5, backoff_max_s: float = 30.0,
                 hang_timeout_s: float = 0.0, warmup_grace_s: float = 60.0,
                 heartbeat_file: Optional[str] = None,
                 env: Optional[dict] = None, cwd: Optional[str] = None,
                 poll_s: float = 0.25):
        self.argv = list(argv)
        self.max_restarts = max(int(max_restarts), 0)
        self.backoff_s = max(float(backoff_s), 0.0)
        self.backoff_max_s = max(float(backoff_max_s), self.backoff_s)
        self.hang_timeout_s = max(float(hang_timeout_s), 0.0)
        self.warmup_grace_s = max(float(warmup_grace_s), 0.0)
        self.heartbeat_file = heartbeat_file
        self.env = env
        self.cwd = cwd
        self.poll_s = max(float(poll_s), 0.05)
        self.restarts = 0
        self.hang_kills = 0
        self.attempts: List[int] = []     # exit codes, one per attempt

    def _heartbeat_age(self, started: float) -> float:
        """Seconds since the last heartbeat (file mtime), measuring from
        child start while no heartbeat exists yet."""
        if self.heartbeat_file and os.path.exists(self.heartbeat_file):
            return time.time() - os.path.getmtime(self.heartbeat_file)
        return time.time() - started

    def _run_once(self, attempt: int) -> int:
        env = dict(self.env if self.env is not None else os.environ)
        env[ATTEMPT_ENV] = str(attempt)
        started = time.time()
        warmed = False
        proc = subprocess.Popen(self.argv, env=env, cwd=self.cwd)
        try:
            while True:
                rc = proc.poll()
                if rc is not None:
                    return rc
                if self.hang_timeout_s > 0:
                    age = self._heartbeat_age(started)
                    budget = self.hang_timeout_s + (
                        0.0 if warmed else self.warmup_grace_s)
                    if self.heartbeat_file and \
                            os.path.exists(self.heartbeat_file) and \
                            os.path.getmtime(self.heartbeat_file) >= started:
                        warmed = True
                        budget = self.hang_timeout_s
                    if age > budget:
                        self.hang_kills += 1
                        Log.warning(
                            "process supervisor: heartbeat stale %.1fs "
                            "(> %.1fs); killing pid %d", age, budget,
                            proc.pid)
                        proc.kill()
                        proc.wait(timeout=30)
                        return -9
                time.sleep(self.poll_s)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)

    def run(self) -> int:
        delay = self.backoff_s
        attempt = 0
        while True:
            rc = self._run_once(attempt)
            self.attempts.append(rc)
            if rc == 0:
                return 0
            self.restarts += 1
            if self.restarts > self.max_restarts:
                raise LightGBMError(
                    "process supervisor: command failed after %d restarts "
                    "(exit codes %s): %s"
                    % (self.max_restarts, self.attempts,
                       " ".join(self.argv[:6])))
            Log.warning("process supervisor: attempt %d exited %s; "
                        "restarting in %.1fs", attempt, rc, delay)
            time.sleep(delay)
            delay = min(delay * 2.0, self.backoff_max_s)
            attempt += 1


class KvHeartbeat:
    """Per-rank liveness leases in the coordination-service KV store.

    Each rank's daemon thread rewrites ``<ns>/p<rank>`` every
    ``period_s`` with a wall-clock stamp; ``dead_peers()`` returns the
    ranks whose lease is older than ``lease_s`` (or missing after the
    initial grace). ``client`` defaults to the live jax.distributed
    client; tests inject a dict-backed stub."""

    def __init__(self, namespace: str = "lgbm_hb", period_s: float = 2.0,
                 lease_s: float = 10.0, client=None, rank: Optional[int] = None,
                 num_processes: Optional[int] = None):
        self._ns = str(namespace)
        self.period_s = max(float(period_s), 0.1)
        self.lease_s = max(float(lease_s), self.period_s)
        self._client = client
        self._rank = rank
        self._n = num_processes
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._started_at = 0.0

    def _resolve(self):
        if self._client is None:
            from jax._src import distributed as _jdist
            self._client = getattr(_jdist.global_state, "client", None)
            if self._client is None:
                raise LightGBMError(
                    "KvHeartbeat needs jax.distributed to be initialized")
        if self._rank is None or self._n is None:
            import jax
            self._rank = int(jax.process_index())
            self._n = int(jax.process_count())
        return self._client

    def _key(self, rank: int) -> str:
        return "%s/p%d" % (self._ns, rank)

    def beat_once(self) -> None:
        client = self._resolve()
        key = self._key(self._rank)
        stamp = "%.6f" % time.time()
        try:
            client.key_value_delete(key)
        except Exception:
            pass
        client.key_value_set(key, stamp)

    def start(self) -> "KvHeartbeat":
        self._resolve()
        self._started_at = time.time()
        self.beat_once()
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.period_s):
                try:
                    self.beat_once()
                except Exception as e:  # noqa: BLE001 - liveness best-effort
                    Log.debug("KvHeartbeat beat failed: %s", e)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="lgbm-kv-heartbeat")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        try:
            self._resolve().key_value_delete(self._key(self._rank))
        except Exception:
            pass

    def last_seen(self, rank: int) -> Optional[float]:
        client = self._resolve()
        try:
            raw = client.blocking_key_value_get(self._key(rank), 200)
            return float(raw)
        except Exception:
            return None

    def dead_peers(self) -> List[int]:
        """Ranks whose lease expired. A never-seen peer only counts as
        dead once our own uptime exceeds the lease (startup grace)."""
        self._resolve()
        now = time.time()
        dead = []
        for p in range(self._n):
            if p == self._rank:
                continue
            seen = self.last_seen(p)
            if seen is None:
                if self._started_at and now - self._started_at > self.lease_s:
                    dead.append(p)
            elif now - seen > self.lease_s:
                dead.append(p)
        return dead
