"""Double-buffered host->device chunk transfer.

``jax.device_put`` is asynchronous: it enqueues the copy and returns
immediately, so issuing the NEXT chunk's transfer before sweeping the
current chunk's histograms overlaps PCIe/ICI traffic with compute — the
staging trick of the GPU-GBDT line (arXiv 1706.08359 §4), host-driven.
The pipeline keeps ``prefetch`` transfers in flight and measures how
well the overlap works: ``wait_s`` accumulates only the time the sweep
loop actually blocks on an unfinished copy, so

    overlap_efficiency = 1 - wait_s / total_s

is 1.0 when every transfer finished under the previous sweep and 0.0
when the loop is pure transfer-bound. Those numbers surface in
``tools/stream_smoke.py``.

Chunks are repacked host-side to a UNIFORM ``chunk_rows`` row count
(last chunk zero-padded): every device buffer then has one shape
[R, C], so the jitted per-chunk kernels compile once regardless of how
many chunks the dataset has or how ragged the source's chunking was.
Row ``r`` of uniform chunk ``i`` is global row ``i*R + r``; rows past
``num_data`` are masked off by the grower's ``row_valid``.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Iterator, List, Optional, Tuple

import numpy as np

from ..log import check


def repack_uniform(chunks: List[np.ndarray], chunk_rows: int
                   ) -> Tuple[List[np.ndarray], int]:
    """Repack ragged uint8 chunks into ``chunk_rows``-row chunks.

    Returns (uniform_chunks, num_rows); every returned chunk has exactly
    ``chunk_rows`` rows (the last is zero-padded). Works chunk-by-chunk —
    never concatenates the full matrix.
    """
    check(chunk_rows > 0, "chunk_rows should be > 0, got %d" % chunk_rows)
    ncols = chunks[0].shape[1] if chunks else 0
    out: List[np.ndarray] = []
    buf = np.zeros((chunk_rows, ncols), np.uint8)
    fill = 0
    total = 0
    for c in chunks:
        c = np.asarray(c, np.uint8)
        total += c.shape[0]
        pos = 0
        while pos < c.shape[0]:
            take = min(chunk_rows - fill, c.shape[0] - pos)
            buf[fill:fill + take] = c[pos:pos + take]
            fill += take
            pos += take
            if fill == chunk_rows:
                out.append(buf)
                buf = np.zeros((chunk_rows, ncols), np.uint8)
                fill = 0
    if fill > 0:
        out.append(buf)          # trailing rows stay zero-padded
    return out, total


class ChunkPipeline:
    """Prefetching iterator over uniform device-resident bin chunks.

    ``packed=True`` stores the uniform host chunks word-packed (int32,
    4 codes per word — core/binpack.py) so every transfer lands in the
    kernel-native layout the packed histogram impls consume directly.
    The byte volume per row is unchanged by the words themselves
    (ceil(C/4)*4 vs C); the transfer halving of ``tpu_bin_packing=
    nibble`` comes from the DATASET pair coding having halved C before
    the chunks were quantized. ``num_cols`` always reports the real
    stored-column count C, not the word count.
    """

    def __init__(self, chunks: List[np.ndarray], chunk_rows: int,
                 prefetch: int = 2, device=None, packed: bool = False):
        self.chunk_rows = int(chunk_rows)
        self.prefetch = max(1, int(prefetch))
        self.device = device
        self.host_chunks, self.num_data = repack_uniform(chunks,
                                                         self.chunk_rows)
        self.num_chunks = len(self.host_chunks)
        self.num_cols = self.host_chunks[0].shape[1] if self.host_chunks \
            else 0
        self.packed = bool(packed)
        if self.packed:
            from ..core.binpack import pack_words_np
            self.host_chunks = [pack_words_np(c) for c in self.host_chunks]
        self.num_padded = self.num_chunks * self.chunk_rows
        # valid (unpadded) rows of each uniform chunk
        self.valid_rows = [
            min(self.chunk_rows, self.num_data - i * self.chunk_rows)
            for i in range(self.num_chunks)]
        # accounting, cumulative across sweeps
        self.sweeps = 0
        self.rows_transferred = 0
        self.wait_s = 0.0
        self.total_s = 0.0

    def _put(self, i: int):
        import jax
        h = self.host_chunks[i]
        return jax.device_put(h, self.device) if self.device is not None \
            else jax.device_put(h)

    @property
    def rows_per_sweep(self) -> int:
        """Rows THIS process transfers per sweep (== num_data when the
        pipeline is unsharded)."""
        return self.num_data

    def sweep(self) -> Iterator[Tuple[int, "object"]]:
        """Yield (chunk_index, device_chunk) once per chunk, in order,
        keeping up to ``prefetch`` transfers in flight ahead of the
        consumer. The consumer should finish its work on a yielded chunk
        before advancing (the buffer is dropped on the next step)."""
        t0 = time.perf_counter()
        inflight: deque = deque()
        for i in range(min(self.prefetch, self.num_chunks)):
            inflight.append((i, self._put(i)))
        while inflight:
            i, dev = inflight.popleft()
            tw = time.perf_counter()
            # the sync IS the measurement: wait_s only accumulates when a
            # transfer failed to hide under the previous chunk's sweep
            dev.block_until_ready()  # lgbm-lint: disable=LGL103 overlap probe
            self.wait_s += time.perf_counter() - tw
            nxt = i + self.prefetch
            if nxt < self.num_chunks:
                inflight.append((nxt, self._put(nxt)))
            yield i, dev
            del dev
        self.sweeps += 1
        self.rows_transferred += self.rows_per_sweep
        self.total_s += time.perf_counter() - t0

    # ------------------------------------------------------------- stats
    def overlap_efficiency(self) -> float:
        return 1.0 - self.wait_s / self.total_s if self.total_s > 0 else 1.0

    def ingest_rows_per_sec(self) -> Optional[float]:
        return self.rows_transferred / self.total_s if self.total_s > 0 \
            else None

    def stats(self) -> dict:
        return {
            "num_chunks": self.num_chunks,
            "chunk_rows": self.chunk_rows,
            "prefetch": self.prefetch,
            "sweeps": self.sweeps,
            "rows_transferred": self.rows_transferred,
            "wait_s": self.wait_s,
            "total_s": self.total_s,
            "overlap_efficiency": self.overlap_efficiency(),
            "ingest_rows_per_sec": self.ingest_rows_per_sec(),
        }


# --------------------------------------------------------- chunks x chips
def split_chunks_rows(chunks: List[np.ndarray], offsets
                      ) -> List[List[np.ndarray]]:
    """Slice an ordered chunk list into per-shard chunk lists along the
    contiguous row offsets — chunk by chunk, never concatenating the
    full matrix (the single-process analog of ``source.ShardedSource``)."""
    world = len(offsets) - 1
    out: List[List[np.ndarray]] = [[] for _ in range(world)]
    pos = 0
    for c in chunks:
        n = int(c.shape[0])
        for p in range(world):
            a = max(int(offsets[p]) - pos, 0)
            b = min(int(offsets[p + 1]) - pos, n)
            if a < b:
                out[p].append(c[a:b])
        pos += n
    check(pos >= int(offsets[-1]),
          "chunk list holds %d rows but shard offsets expect %d"
          % (pos, int(offsets[-1])))
    return out


def shard_rows_host(arr: np.ndarray, offsets, local_padded: int
                    ) -> np.ndarray:
    """Permute a host ``[n, ...]`` array into SHARD-MAJOR padded layout.

    Shard ``p`` owns original rows ``[offsets[p], offsets[p+1])`` (the
    contiguous shard-assignment contract, stream/source.py); in the
    padded layout those rows occupy ``[p*local_padded, p*local_padded +
    n_p)`` and the rest of each shard's block is zero — so a
    ``P(DATA_AXIS)`` row sharding puts every shard's rows (and only its
    rows) on its own device, with padding masked by ``row_valid``.
    Original row ``r`` of shard ``p`` lives at padded index
    ``p*local_padded + (r - offsets[p])``.
    """
    arr = np.asarray(arr)
    world = len(offsets) - 1
    out = np.zeros((world * int(local_padded),) + arr.shape[1:], arr.dtype)
    for p in range(world):
        n_p = int(offsets[p + 1]) - int(offsets[p])
        out[p * local_padded:p * local_padded + n_p] = \
            arr[int(offsets[p]):int(offsets[p + 1])]
    return out


def shard_rows_perm(offsets, local_padded: int) -> np.ndarray:
    """Inverse bookkeeping of :func:`shard_rows_host`: the ``[n]`` index
    vector such that ``padded[perm]`` recovers the original row order."""
    world = len(offsets) - 1
    parts = [np.arange(int(offsets[p + 1]) - int(offsets[p]), dtype=np.int64)
             + p * int(local_padded) for p in range(world)]
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


class ShardedChunkPipeline(ChunkPipeline):
    """Mesh-mode pipeline: ``sweep()`` yields GLOBAL ``[D*R, C]`` device
    arrays sharded ``P(DATA_AXIS)`` whose shard ``p`` is shard ``p``'s
    local uniform chunk ``i`` — so inside a ``shard_map`` kernel, chunk
    ``i`` looks exactly like the single-device pipeline's chunk ``i`` of
    that shard's rows, and the per-chunk kernels stay byte-identical.

    Every shard is padded (with all-zero chunks) to the GLOBAL maximum
    chunk count, so the host wave loop takes the same number of steps on
    every process — a collective inside the final chunk's kernel then
    lines up by construction. ``num_data``/``num_padded`` are global;
    ``local_padded = num_chunks * chunk_rows`` is one shard's padded row
    block. Word packing is intentionally unsupported here (the mesh
    learners shard the PLAIN feature axis); ``col_pad`` appends zero
    columns so the stored-column count divides the mesh axis when the
    reduce-scatter learner needs it.
    """

    def __init__(self, shard_chunks: List[List[np.ndarray]],
                 shard_row_counts: List[int], chunk_rows: int, mesh,
                 prefetch: int = 2, col_pad: int = 0):
        import jax
        from ..parallel.mesh import DATA_AXIS
        self.mesh = mesh
        self.chunk_rows = int(chunk_rows)
        self.prefetch = max(1, int(prefetch))
        self.device = None
        self.packed = False
        self.shard_row_counts = [int(n) for n in shard_row_counts]
        self.world = len(self.shard_row_counts)
        check(DATA_AXIS in mesh.axis_names,
              "sharded chunk pipeline needs a %r mesh axis" % DATA_AXIS)
        check(int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
              == self.world,
              "shard count %d != mesh size %d" % (
                  self.world,
                  int(np.prod([mesh.shape[a] for a in mesh.axis_names]))))
        # local shards are the mesh positions whose device this process
        # addresses, in mesh order; shard_chunks must line up with them
        pid = jax.process_index()
        devices = list(np.asarray(mesh.devices).reshape(-1))
        self.local_shards = [p for p, d in enumerate(devices)
                             if d.process_index == pid]
        self._local_devices = [devices[p] for p in self.local_shards]
        check(len(shard_chunks) == len(self.local_shards),
              "got chunk lists for %d shards but this process addresses "
              "%d mesh positions" % (len(shard_chunks),
                                     len(self.local_shards)))
        # uniform-repack each local shard; chunk-count padding to the
        # GLOBAL max keeps every process's wave loop in lockstep
        self.num_chunks = max(
            -(-n // self.chunk_rows) for n in self.shard_row_counts)
        R = self.chunk_rows
        self._shard_host_chunks: List[List[np.ndarray]] = []
        ncols = 0
        for li, chunks in enumerate(shard_chunks):
            uni, n = repack_uniform(chunks, R)
            p = self.local_shards[li]
            check(n == self.shard_row_counts[p],
                  "shard %d chunk rows %d != declared count %d"
                  % (p, n, self.shard_row_counts[p]))
            ncols = uni[0].shape[1] if uni else ncols
            if col_pad:
                uni = [np.concatenate(
                    [c, np.zeros((R, col_pad), c.dtype)], axis=1)
                    for c in uni]
            while len(uni) < self.num_chunks:
                uni.append(np.zeros((R, ncols + col_pad), np.uint8))
            self._shard_host_chunks.append(uni)
        self.num_cols = ncols + col_pad
        self.num_data = sum(self.shard_row_counts)
        self.local_padded = self.num_chunks * R
        self.num_padded = self.world * self.local_padded
        self.valid_rows = [
            min(R, max(self.shard_row_counts) - i * R)
            for i in range(self.num_chunks)]
        self.host_chunks = list(range(self.num_chunks))  # indices only
        self._sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(DATA_AXIS, None))
        self.sweeps = 0
        self.rows_transferred = 0
        self.wait_s = 0.0
        self.total_s = 0.0

    @property
    def rows_per_sweep(self) -> int:
        return sum(self.shard_row_counts[p] for p in self.local_shards)

    def shard_offsets(self) -> List[int]:
        """Row offsets of the rank-ordered shard blocks (original row
        space): shard ``p`` owns ``[off[p], off[p+1])``."""
        off = [0]
        for n in self.shard_row_counts:
            off.append(off[-1] + n)
        return off

    def _put(self, i: int):
        import jax
        bufs = [jax.device_put(self._shard_host_chunks[li][i], d)
                for li, d in enumerate(self._local_devices)]
        shape = (self.world * self.chunk_rows, self.num_cols)
        return jax.make_array_from_single_device_arrays(
            shape, self._sharding, bufs)
