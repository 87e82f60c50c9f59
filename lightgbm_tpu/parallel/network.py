"""Distributed topology bootstrap.

The reference's Network/Linkers stack (src/network/: TCP mesh construction,
Bruck allgather, recursive-halving reduce-scatter — network.cpp:64-298) is
replaced wholesale by XLA collectives over the device mesh: psum/all_gather/
reduce_scatter compiled into the training step (see parallel.learners).
What remains host-side is multi-process bootstrap: the analog of
Network::Init (application.cpp:169) is ``jax.distributed.initialize``.

``init`` accepts the reference's ``machines`` ip:port list for API compat
(basic.py:1734 set_network) and maps it onto jax.distributed's
coordinator/process model.
"""
from __future__ import annotations

import os
import time
from typing import List, Optional

from ..log import Log, LightGBMError

_initialized = False
_num_machines = 1
_rank = 0


def init(machines: str = "", local_listen_port: int = 12400,
         time_out: int = 120, num_machines: int = 1) -> None:
    """Network::Init analog. With num_machines == 1 this is a no-op; with
    more, the caller must run one process per host and the machine list's
    first entry is used as the jax.distributed coordinator."""
    global _initialized, _num_machines, _rank
    if num_machines <= 1:
        _initialized = True
        return
    import jax
    # Compiled collectives on the CPU backend need a cross-process
    # implementation: jax's default leaves psum/all_gather unable to cross
    # process boundaries, which would break every learner schedule in
    # parallel/learners.py the moment the mesh spans hosts. Gloo rides the
    # same TCP fabric the coordinator already uses; TPU/GPU backends ignore
    # the flag. It only takes effect before the first backend client is
    # created — a caller who already touched jax.devices() keeps theirs.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    hosts: List[str] = [m.strip() for m in machines.split(",") if m.strip()]
    if len(hosts) != num_machines:
        raise LightGBMError(
            "machines list has %d entries but num_machines=%d"
            % (len(hosts), num_machines))
    coordinator = hosts[0]
    process_id = int(os.environ.get("LIGHTGBM_TPU_RANK",
                                    os.environ.get("JAX_PROCESS_ID", "0")))
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_machines,
                               process_id=process_id,
                               initialization_timeout=time_out)
    _initialized = True
    _num_machines = num_machines
    _rank = process_id
    Log.info("Distributed init: rank %d / %d (coordinator %s)",
             _rank, _num_machines, coordinator)


def free() -> None:
    global _initialized, _num_machines, _rank, _external_comm
    _initialized = False
    _num_machines = 1
    _rank = 0
    # drop any injected transport: the host may free its callback code
    # right after LGBM_NetworkFree
    _external_comm = None


def num_machines() -> int:
    return _num_machines


def rank() -> int:
    return _rank


class HostComm:
    """Host-side allgather seam for distributed ingest (the pluggable
    collectives idea of LGBM_NetworkInitWithFunctions, network.h:96 /
    c_api.h:958 — kept so tests can run the identical code path without a
    cluster).

    ``allgather(obj) -> list[obj]`` returns every host's object in rank
    order. The jax implementation rides jax.experimental.multihost_utils;
    LoopbackComm simulates K hosts in one process for tests.
    """

    def allgather(self, obj):
        raise NotImplementedError


class JaxHostComm(HostComm):
    """Cross-host allgather via jax.distributed (host metadata only — the
    heavy per-iteration collectives are XLA ops inside the training step).

    Arbitrary picklable objects (ragged arrays included) are supported by
    gathering pickled bytes: lengths first (fixed shape), then the padded
    byte arrays — the same serialize-then-Allgather shape as the reference's
    BinMapper sync (dataset_loader.cpp:615-640)."""

    def allgather(self, obj):
        import pickle
        import numpy as _np
        from jax.experimental import multihost_utils
        blob = _np.frombuffer(pickle.dumps(obj), dtype=_np.uint8)
        lengths = multihost_utils.process_allgather(
            _np.asarray([blob.size], _np.int64))
        lengths = _np.asarray(lengths).reshape(-1)
        maxlen = int(lengths.max())
        padded = _np.zeros(maxlen, _np.uint8)
        padded[:blob.size] = blob
        stacked = _np.asarray(multihost_utils.process_allgather(padded))
        stacked = stacked.reshape(len(lengths), maxlen)
        return [pickle.loads(stacked[i, :int(lengths[i])].tobytes())
                for i in range(len(lengths))]


class KvHostComm(HostComm):
    """Host allgather over the jax.distributed coordination-service
    key-value store — no compiled computation at all, which matters
    because the CPU backend cannot run cross-process computations
    (``process_allgather`` raises "Multiprocess computations aren't
    implemented on the CPU backend"), yet the coordination service is up
    on every backend the moment ``jax.distributed.initialize`` returns.

    Protocol: each rank sets ``<ns>/r<round>/p<rank>`` to its
    base64-pickled payload, then blocking-gets every rank's key (the
    blocking get IS the synchronization — no separate barrier).  The
    round counter namespaces keys so consecutive allgathers never read a
    stale value; calls must therefore be SPMD-lockstep across processes
    (same construction order, same call count), which is exactly how the
    distributed-obs per-block cadence drives it.  Keys from two rounds
    back are best-effort deleted to keep the coordinator's store bounded.
    """

    def __init__(self, namespace: str = "lgbm_hostcomm",
                 timeout_ms: int = 60000, retries: int = 3,
                 retry_backoff_s: float = 0.25, peer_guard=None,
                 client=None, num_processes: Optional[int] = None,
                 rank: Optional[int] = None):
        self._ns = str(namespace)
        self._timeout_ms = int(timeout_ms)
        self._retries = max(int(retries), 0)
        self._retry_backoff_s = max(float(retry_backoff_s), 0.0)
        # peer_guard() -> list of dead peer ranks (KvHeartbeat.dead_peers);
        # checked between poll slices so a dead rank fails in seconds, not
        # after the full blocking-get timeout
        self._peer_guard = peer_guard
        self._client = client              # tests inject a dict-backed stub
        self._n = num_processes
        self._rank = rank
        self._round = 0

    def _resolve(self):
        if self._client is None:
            from jax._src import distributed as _jdist
            self._client = getattr(_jdist.global_state, "client", None)
            if self._client is None:
                raise LightGBMError(
                    "KvHostComm needs jax.distributed to be initialized")
        if self._n is None or self._rank is None:
            import jax
            self._n = int(jax.process_count())
            self._rank = int(jax.process_index())
        return self._client

    @staticmethod
    def _transient(err: Exception) -> bool:
        """Coordination-service failures worth retrying; a timeout is NOT
        transient — the peer is late or dead, retrying just re-waits."""
        return "DEADLINE_EXCEEDED" not in str(err)

    def _kv_set(self, key: str, value: str, r: int) -> None:
        from ..resilience import faults
        client = self._client
        last: Optional[Exception] = None
        for attempt in range(self._retries + 1):
            try:
                faults.inject("kv_set", round=r, rank=self._rank, key=key)
                client.key_value_set(key, value)
                return
            except Exception as e:  # noqa: BLE001 - classify + retry below
                if isinstance(e, LightGBMError):
                    raise
                last = e
                if not self._transient(e) or attempt == self._retries:
                    break
                Log.warning("KvHostComm set %s failed (%s); retry %d/%d",
                            key, e, attempt + 1, self._retries)
                time.sleep(self._retry_backoff_s * (2 ** attempt))
        raise LightGBMError(
            "KvHostComm set failed: namespace=%s round=%d rank=%d key=%s "
            "after %d attempt(s): %s"
            % (self._ns, r, self._rank, key, self._retries + 1, last))

    def _kv_get(self, key: str, r: int, peer: int) -> str:
        from ..resilience import faults
        client = self._client
        deadline = time.monotonic() + self._timeout_ms / 1000.0
        start = time.monotonic()
        attempts = 0
        last: Optional[Exception] = None
        while True:
            # short poll slices so the peer guard runs every ~2s even
            # while the value is simply not there yet
            slice_ms = min(max(int((deadline - time.monotonic()) * 1000), 1),
                           2000)
            attempts += 1
            try:
                faults.inject("kv_get", round=r, rank=self._rank,
                              peer=peer, key=key)
                return client.blocking_key_value_get(key, slice_ms)
            except Exception as e:  # noqa: BLE001 - classify + retry below
                if isinstance(e, LightGBMError):
                    raise
                last = e
                elapsed_ms = (time.monotonic() - start) * 1000.0
                if self._peer_guard is not None:
                    try:
                        dead = list(self._peer_guard())
                    except Exception:
                        dead = []
                    if peer in dead:
                        raise LightGBMError(
                            "KvHostComm allgather: peer rank %d is DEAD "
                            "(heartbeat lease expired) — namespace=%s "
                            "round=%d rank=%d key=%s elapsed=%.0fms"
                            % (peer, self._ns, r, self._rank, key,
                               elapsed_ms)) from e
                timed_out = time.monotonic() >= deadline
                if not timed_out and self._transient(e) and \
                        attempts <= self._retries:
                    Log.warning("KvHostComm get %s failed (%s); retry "
                                "%d/%d", key, e, attempts, self._retries)
                    time.sleep(self._retry_backoff_s * (2 ** (attempts - 1)))
                    continue
                if not timed_out and "DEADLINE_EXCEEDED" in str(e):
                    continue     # poll slice expired; keep waiting
                raise LightGBMError(
                    "KvHostComm allgather %s: namespace=%s round=%d "
                    "rank=%d peer=%d key=%s elapsed=%.0fms attempts=%d: %s"
                    % ("timed out" if timed_out else "failed",
                       self._ns, r, self._rank, peer, key,
                       elapsed_ms, attempts, last)) from e

    def allgather(self, obj):
        import base64
        import pickle
        self._resolve()
        n, me = self._n, self._rank
        r = self._round
        self._round += 1
        keyfmt = "%s/r%d/p%%d" % (self._ns, r)
        blob = base64.b64encode(pickle.dumps(obj)).decode("ascii")
        self._kv_set(keyfmt % me, blob, r)
        out = []
        for p in range(n):
            raw = self._kv_get(keyfmt % p, r, p)
            out.append(pickle.loads(base64.b64decode(raw)))
        if r >= 2:   # GC our own key from two rounds back
            try:
                self._client.key_value_delete(
                    "%s/r%d/p%d" % (self._ns, r - 2, me))
            except Exception:
                pass
        return out


def check_model_agreement(digest: str, comm: Optional["HostComm"] = None,
                          namespace: str = "lgbm_model_agree") -> List[str]:
    """Cross-process model-agreement check: allgather each rank's model
    digest and fail loudly if any pair differs.

    Data-parallel training is replicated-by-construction — every rank
    commits the tree built from the globally reduced histograms — so a
    digest mismatch always means real divergence (non-deterministic input
    order, a rank reading different data, a collective silently local).
    Returns the rank-ordered digest list; raises LightGBMError naming the
    disagreeing ranks. Single-process runs return ``[digest]`` untouched.
    """
    if comm is None:
        comm = default_host_comm(namespace=namespace)
    if comm is None:
        return [str(digest)]
    digests = [str(d) for d in comm.allgather(str(digest))]
    if len(set(digests)) > 1:
        raise LightGBMError(
            "model disagreement across processes: "
            + ", ".join("rank %d=%s" % (i, d[:16])
                        for i, d in enumerate(digests)))
    return digests


# one KV comm per namespace, process-wide: the round counter lives on
# the instance, so handing out a FRESH KvHostComm for a namespace that
# already ran an allgather would reuse round-0 keys and fail with
# ALREADY_EXISTS. Every process acquires namespaces in lockstep (the
# callers are collective), so the cached counters stay aligned.
_KV_COMMS: dict = {}


def default_host_comm(namespace: str = "lgbm_hostcomm",
                      timeout_ms: int = 60000) -> Optional[HostComm]:
    """The right host-metadata allgather for the current topology: None
    single-process, the coordination-service KV comm on the CPU backend
    (which cannot run multiprocess computations), ``process_allgather``
    everywhere else (TPU/GPU meshes). KV comms are cached per namespace
    (first call's ``timeout_ms`` wins) so repeated acquisitions continue
    one round sequence instead of colliding on reused keys."""
    import jax
    if jax.process_count() <= 1:
        return None
    if jax.default_backend() == "cpu":
        comm = _KV_COMMS.get(namespace)
        if comm is None:
            comm = KvHostComm(namespace=namespace, timeout_ms=timeout_ms)
            _KV_COMMS[namespace] = comm
        return comm
    return JaxHostComm()


class LoopbackComm(HostComm):
    """Test double: K simulated hosts as K threads in one process, with a
    barrier-synchronized allgather — the collective semantics are real
    (rank-ordered, lockstep) without any cluster.

    A simulated host that dies between the two waits used to hang every
    other thread forever; ``abort()`` (call it from the dying rank's
    except/finally) breaks the barrier so peers get a clean LightGBMError
    instead, and ``timeout_s`` bounds the wait as a backstop."""

    def __init__(self, shared: dict, my_rank: int):
        self._shared = shared
        self._rank = my_rank

    @staticmethod
    def group(k: int, timeout_s: Optional[float] = None) -> List["LoopbackComm"]:
        import threading
        shared = {"slots": [None] * k, "barrier": threading.Barrier(k),
                  "timeout_s": timeout_s, "aborted_by": None}
        return [LoopbackComm(shared, r) for r in range(k)]

    def abort(self) -> None:
        """Mark this rank dead and break the barrier, unblocking peers."""
        if self._shared.get("aborted_by") is None:
            self._shared["aborted_by"] = self._rank
        self._shared["barrier"].abort()

    def _wait(self, phase: str) -> None:
        import threading
        try:
            self._shared["barrier"].wait(self._shared.get("timeout_s"))
        except threading.BrokenBarrierError:
            culprit = self._shared.get("aborted_by")
            raise LightGBMError(
                "LoopbackComm allgather aborted at %s barrier on rank %d%s"
                % (phase, self._rank,
                   ": rank %d crashed" % culprit if culprit is not None
                   else " (barrier broken or timed out)")) from None

    def allgather(self, obj):
        try:
            self._shared["slots"][self._rank] = obj
            self._wait("publish")
            out = list(self._shared["slots"])
            self._wait("drain")   # don't overwrite until all read
            return out
        except LightGBMError:
            raise
        except BaseException:
            # dying between the waits must not wedge the peers
            self.abort()
            raise


class ExternalComm(HostComm):
    """Injectable collectives — the LGBM_NetworkInitWithFunctions seam
    (reference c_api.h:958, network.h:96, meta.h:51-57). The caller hands
    the ABI two C function pointers:

      allgather(input, input_size, block_start, block_len, num_block,
                output, output_size)
      reduce_scatter(input, input_size, type_size, block_start, block_len,
                     num_block, output, output_size, &reducer)

    and every host-side collective (sharded ingest's bin-sample merge,
    HostComm.allgather users) dispatches through them instead of
    jax.distributed — which is exactly what makes the distributed code
    path drivable from a test without a cluster. Ragged payloads ride the
    same two-phase shape as the reference's BinMapper sync: one fixed
    8-byte length round, then the data round.
    """

    def __init__(self, num_machines: int, my_rank: int,
                 reduce_scatter_ptr: int, allgather_ptr: int):
        import ctypes
        self._k = int(num_machines)
        self._rank = int(my_rank)
        c = ctypes
        self._AGT = c.CFUNCTYPE(
            None, c.c_char_p, c.c_int32, c.POINTER(c.c_int32),
            c.POINTER(c.c_int32), c.c_int, c.c_char_p, c.c_int32)
        # last arg: const ReduceFunction& == pointer to the function pointer
        self._RST = c.CFUNCTYPE(
            None, c.c_char_p, c.c_int32, c.c_int, c.POINTER(c.c_int32),
            c.POINTER(c.c_int32), c.c_int, c.c_char_p, c.c_int32,
            c.POINTER(c.c_void_p))
        # void* not char*: ctypes converts incoming c_char_p callback
        # args to NUL-truncated bytes, corrupting binary payloads
        self._REDT = c.CFUNCTYPE(None, c.c_void_p, c.c_void_p, c.c_int,
                                 c.c_int32)
        self._ag = self._AGT(allgather_ptr) if allgather_ptr else None
        self._rs = self._RST(reduce_scatter_ptr) if reduce_scatter_ptr else None

    def _allgather_raw(self, blob: bytes, block_lens) -> bytes:
        import ctypes as c
        k = self._k
        starts = [0] * k
        for i in range(1, k):
            starts[i] = starts[i - 1] + int(block_lens[i - 1])
        total = starts[-1] + int(block_lens[-1])
        out = c.create_string_buffer(total)
        inp = c.create_string_buffer(bytes(blob), len(blob))
        self._ag(c.cast(inp, c.c_char_p), c.c_int32(len(blob)),
                 (c.c_int32 * k)(*starts), (c.c_int32 * k)(
                     *[int(b) for b in block_lens]),
                 c.c_int(k), c.cast(out, c.c_char_p), c.c_int32(total))
        return out.raw

    def allgather(self, obj):
        import pickle
        import struct
        if self._ag is None:
            raise LightGBMError("external allgather function not provided")
        blob = pickle.dumps(obj)
        lens_raw = self._allgather_raw(struct.pack("<q", len(blob)),
                                       [8] * self._k)
        lens = [struct.unpack_from("<q", lens_raw, 8 * i)[0]
                for i in range(self._k)]
        data = self._allgather_raw(blob, lens)
        out, off = [], 0
        for ln in lens:
            out.append(pickle.loads(data[off:off + ln]))
            off += ln
        return out

    def reduce_scatter_sum(self, arr):
        """Reference Network::ReduceScatter shape: each rank contributes a
        float64 array of K equal blocks; rank r receives the element-wise
        sum of every rank's block r. The sum reducer crosses the ABI as a
        ReduceFunction pointer (meta.h:51)."""
        import ctypes as c
        import numpy as np
        if self._rs is None:
            raise LightGBMError("external reduce_scatter function "
                                "not provided")
        a = np.ascontiguousarray(arr, np.float64)
        k = self._k
        if a.size % k:
            raise LightGBMError("reduce_scatter payload not divisible "
                                "into %d blocks" % k)
        blk = a.size // k
        blk_bytes = blk * 8

        def _sum(src, dst, type_size, nbytes):
            n = nbytes // 8
            s = np.frombuffer(c.string_at(src, nbytes), np.float64, n)
            buf = (c.c_double * n).from_address(dst)
            np.asarray(buf)[:] += s
        reducer = self._REDT(_sum)
        reducer_ptr = c.c_void_p(c.cast(reducer, c.c_void_p).value)
        starts = (c.c_int32 * k)(*[i * blk_bytes for i in range(k)])
        lens = (c.c_int32 * k)(*([blk_bytes] * k))
        out = c.create_string_buffer(blk_bytes)
        inp = a.tobytes()
        inbuf = c.create_string_buffer(inp, len(inp))
        self._rs(c.cast(inbuf, c.c_char_p), c.c_int32(len(inp)),
                 c.c_int(8), starts, lens, c.c_int(k),
                 c.cast(out, c.c_char_p), c.c_int32(blk_bytes),
                 c.byref(reducer_ptr))
        return np.frombuffer(out.raw, np.float64, blk).copy()


_external_comm: Optional[ExternalComm] = None


def init_with_functions(num_machines: int, rank: int,
                        reduce_scatter_ptr: int, allgather_ptr: int) -> None:
    """LGBM_NetworkInitWithFunctions analog: injectable collectives for
    hosts that bring their own transport (or tests that bring none)."""
    global _initialized, _num_machines, _rank, _external_comm
    _external_comm = ExternalComm(num_machines, rank,
                                  reduce_scatter_ptr, allgather_ptr)
    _initialized = True
    _num_machines = int(num_machines)
    _rank = int(rank)
    Log.info("Network init with external functions: rank %d / %d",
             _rank, _num_machines)


def active_comm() -> Optional[HostComm]:
    """The registered external transport, if any — HostComm consumers
    (e.g. BinnedDataset.from_sharded) use it when no comm is passed."""
    return _external_comm
