"""Host-driven frontier growth over device-streamed chunks.

The in-memory frontier grower (core/grow_frontier.py) is one jitted
``lax.while_loop`` over the whole [N, C] bin matrix. Here the matrix
never fits on device, so the wave loop moves to the HOST and each wave's
single dataset sweep becomes a sum of per-chunk sweeps — legal because
histograms are additive over row partitions, which is the exact property
that makes the result structure-identical to single-shot growth at the
same bin boundaries (asserted in tests/test_stream.py).

Everything per-ROW except the bin matrix (scores, grad/hess, sample
mask, leaf ids) stays device-resident at full length, padded to
``num_chunks * chunk_rows``; padding rows carry ``sample_mask == 0`` so
they contribute exactly zero to every histogram channel and every
gradient sum, and their (meaningless) leaf ids are never read.

The wave is cut into fixed-shape jitted kernels built from the SAME
helpers the in-memory grower uses (wave_plan / wave_route / wave_slots /
wave_commit / root_state):

- ``_wave_begin``  — per-leaf planning + the loop condition (the ONE
  host sync per wave: a single bool decides whether to sweep);
- ``_chunk_wave``  — per non-final chunk: dynamic-slice the chunk's
  rows out of the full per-row arrays, route them, accumulate the
  smaller-child histogram partial (fixed [R, C] chunk shape ->
  compiles once, independent of how many chunks the dataset has);
- ``_chunk_wave_commit`` — the FINAL chunk's sweep fused with the
  sibling subtraction and pool/tree/best commit: chunks+1 dispatches
  per wave, and the [W, C, B, 3] wave histogram never materializes as
  a standalone dispatch output.

When the dataset is word-packed (``tpu_bin_packing``, core/binpack.py)
the chunks arrive as int32 words and both sweep kernels unpack lanes
in-register; routing gathers the split column straight from the words.

Wave width is FIXED at ``frontier_max_width`` (the bucketing ladder is
disabled when streaming): a ladder would multiply the per-chunk kernel
set by the ladder length and make the compiled-program count depend on
which widths a run happens to visit — the perf gate pins that count
invariant in chunk count instead.

CHUNKS x CHIPS (``mesh`` given): every kernel above is wrapped in ONE
``shard_map`` over the data axis, each shard seeing exactly the block
the single-device kernel would see for its own rows — per-chunk bodies
are reused verbatim, and the learner's collective schedule
(``parallel/learners.py``: psum / reduce-scatter election / top-k
voting) fires only inside ``root_commit`` and the final chunk's fused
``chunk_wave_commit``. Histograms are additive over row partitions AND
over chunks, so accumulating chunk partials locally and reducing once
per wave is exact — and the per-wave collective count/payload is the
PR 12 in-memory number, independent of chunk count. Per-shard-varying
values that must cross the host loop between dispatches (the chunk
histogram accumulators, and the pool under varying-pool learners) ride
a leading mesh-sized axis sharded on the data axis; everything else in
the carried state is replicated. The per-wave host bool sync becomes a
single psum'd int32 continue flag whose output is a fully-replicated
global array — every process reads the SAME device value, so the wave
loops stay in lockstep without any host-side channel.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..bucketing import frontier_max_width
from ..core.grow import GrowParams, TreeArrays, expand_hist
from ..core.grow_frontier import (_FrontierState, root_state, wave_commit,
                                  wave_plan, wave_route, wave_slots)
from ..core.histogram import build_histogram, build_histogram_frontier
from ..core.split import FeatureMeta, find_best_split
from ..log import check
from ..parallel.learners import make_frontier_learner
from ..parallel.mesh import DATA_AXIS
from .pipeline import ChunkPipeline


class StreamFrontierGrower:
    """Grows one tree per ``grow()`` call by sweeping a ChunkPipeline.

    Same contract as ``grow_tree_frontier`` (tree, leaf_id, aux), with
    per-row inputs at the pipeline's PADDED length. With ``mesh`` the
    pipeline must be a ``ShardedChunkPipeline`` and the per-row inputs
    are GLOBAL arrays row-sharded over the data axis in the pipeline's
    shard-major padded layout; the returned tree is fully replicated and
    ``leaf_id`` stays row-sharded.
    """

    def __init__(self, pipeline: ChunkPipeline, meta: FeatureMeta,
                 params: GrowParams, mesh=None):
        check(not params.frontier_bucketing,
              "streamed growth uses a fixed wave width; construct "
              "GrowParams with frontier_bucketing=False")
        self.pipeline = pipeline
        self.params = params
        self.mesh = mesh
        self.trees_grown = 0
        self.waves = 0
        self.wave_dispatches = 0   # jitted calls inside wave loops
        p = params
        R = pipeline.chunk_rows
        ncols = pipeline.num_cols
        l = p.num_leaves
        b = p.num_bins
        sp = p.split
        kb = frontier_max_width(l, p.max_depth)
        self.wave_width = kb
        self._hist_shape = (ncols, b, 3)
        meta_ = meta
        axis = None if mesh is None else DATA_AXIS
        # leaf_id lives at block-local length inside the kernels: the
        # whole padded length when single-device, one shard's padded
        # block under the mesh
        n_rows = pipeline.num_padded if mesh is None \
            else pipeline.local_padded
        if mesh is not None:
            check(not (p.obs_health or p.obs_modelstats),
                  "streamed mesh growth disables obs accumulators; "
                  "construct GrowParams with obs_health/obs_modelstats "
                  "False (gbdt.py does)")
            check(not p.word_packed_cols,
                  "streamed mesh growth takes plain uint8 chunks; "
                  "tpu_bin_packing=word is single-process only")

        def make_lrn(fmask):
            # the feature mask changes per tree (feature_fraction), so the
            # learner closures bind it at trace time inside each kernel
            def child_best(hist_col, sum_g, sum_h, cnt, min_c, max_c):
                return find_best_split(
                    expand_hist(hist_col, sum_g, sum_h, cnt, meta_, p,
                                ncols),
                    meta_, sp, sum_g, sum_h, cnt, fmask,
                    min_constraint=min_c, max_constraint=max_c,
                    with_categorical=p.with_categorical)

            psum = (lambda x: x) if axis is None \
                else (lambda x: lax.psum(x, axis))
            return make_frontier_learner(p, axis, meta_, fmask,
                                         psum, child_best)

        def root_sums(grad, hess, mask):
            return (jnp.sum(grad * mask), jnp.sum(hess * mask),
                    jnp.sum(mask))

        def root_chunk(xb_c, start, grad, hess, mask, acc):
            g_c = lax.dynamic_slice(grad, (start,), (R,))
            h_c = lax.dynamic_slice(hess, (start,), (R,))
            m_c = lax.dynamic_slice(mask, (start,), (R,))
            return acc + build_histogram(
                xb_c, g_c, h_c, m_c, num_bins=b,
                row_chunk=p.row_chunk, impl=p.hist_impl,
                packed_cols=p.word_packed_cols)

        def root_commit(hist_acc, root_g, root_h, root_c, fmask):
            lrn = make_lrn(fmask)
            hist_root = lrn.reduce(hist_acc)
            return root_state(hist_root, root_g, root_h, root_c,
                              n_rows, l, sp, lrn, p, fmask,
                              axis_name=axis)

        def wave_begin(best, num_leaves):
            do = (num_leaves < l) & jnp.any(best.gain > 0.0)
            plan = wave_plan(best, num_leaves, kb, l)
            return do, plan

        def chunk_wave(xb_c, start, leaf_id, grad, hess, mask, plan,
                       hist_acc):
            (gval, gleaf, valid, nvalid, node, right_leaf, cur,
             rank_of_leaf) = plan
            lid_c = lax.dynamic_slice(leaf_id, (start,), (R,))
            g_c = lax.dynamic_slice(grad, (start,), (R,))
            h_c = lax.dynamic_slice(hess, (start,), (R,))
            m_c = lax.dynamic_slice(mask, (start,), (R,))
            new_lid, active, rs, go_left = wave_route(
                xb_c, lid_c, cur, rank_of_leaf, right_leaf, meta_,
                p.with_efb, p.with_categorical,
                packed_cols=p.word_packed_cols)
            _left_small, slot = wave_slots(cur, active, go_left, rs)
            part = build_histogram_frontier(
                xb_c, slot, g_c, h_c, m_c, num_bins=b, num_slots=kb,
                row_chunk=p.row_chunk, impl=p.hist_impl,
                packed_cols=p.word_packed_cols)
            leaf_id = lax.dynamic_update_slice(leaf_id, new_lid, (start,))
            return leaf_id, hist_acc + part

        def commit_state(s: _FrontierState, plan, hist_small, leaf_id,
                         fmask):
            lrn = make_lrn(fmask)
            (gval, gleaf, valid, nvalid, node, right_leaf, cur,
             rank_of_leaf) = plan
            left_small = cur.left_count <= cur.right_count
            hs = lrn.reduce(hist_small)
            (pool, tree, leaf_min, leaf_max, best, health,
             mstats) = wave_commit(
                s, kb, l, gval, gleaf, valid, nvalid, node, right_leaf,
                cur, left_small, hs, meta_, sp, p.max_depth, lrn)
            return _FrontierState(leaf_id=leaf_id, hist_pool=pool,
                                  best=best, tree=tree, leaf_min=leaf_min,
                                  leaf_max=leaf_max, health=health,
                                  mstats=mstats)

        def chunk_wave_commit(xb_c, start, s: _FrontierState, leaf_id,
                              grad, hess, mask, plan, hist_acc, fmask):
            # LAST chunk of the wave: its sweep, the sibling subtraction
            # and the 2K-child bin-scan commit fuse into ONE dispatch, so
            # the [W, C, B, 3] wave histogram never leaves the compiled
            # region as a standalone output (chunks+2 -> chunks+1
            # dispatches per wave — the streamed analog of the in-memory
            # grower's fused wave body)
            leaf_id, hist_acc = chunk_wave(xb_c, start, leaf_id, grad,
                                           hess, mask, plan, hist_acc)
            return commit_state(s, plan, hist_acc, leaf_id, fmask)

        if mesh is None:
            self._root_sums = jax.jit(root_sums)
            self._root_chunk = jax.jit(root_chunk)
            self._root_commit = jax.jit(root_commit)
            self._wave_begin = jax.jit(wave_begin)
            self._chunk_wave = jax.jit(chunk_wave)
            self._chunk_wave_commit = jax.jit(chunk_wave_commit)
            self._zero_root_acc = None
            self._zero_wave_acc = None
            self._audit_fns = {}
            return

        # ---------------------------------------------- chunks x chips
        # Per-shard-varying tensors that must survive between host-level
        # dispatches (chunk accumulators; the pool under varying-pool
        # learners) carry a leading mesh-sized axis sharded on DATA_AXIS:
        # each shard's block is its local value, so nothing is ever
        # averaged/collapsed by an out_spec and nothing is communicated
        # between chunks — the only collectives are the learner schedule
        # inside root_commit / the final fused chunk (payload == PR 12).
        varying = bool(p.voting_top_k > 0 or p.frontier_rs)
        self._varying_pool = varying
        rows = P(DATA_AXIS)
        repl = P()
        xspec = P(DATA_AXIS, None)
        lead = P(DATA_AXIS)        # leading-axis prefix for accumulators
        state_spec = _FrontierState(
            leaf_id=rows, hist_pool=(lead if varying else repl),
            best=repl, tree=repl, leaf_min=repl, leaf_max=repl,
            health=None, mstats=None)

        def _pack(s: _FrontierState) -> _FrontierState:
            return s._replace(hist_pool=s.hist_pool[None]) if varying \
                else s

        def _unpack(s: _FrontierState) -> _FrontierState:
            return s._replace(hist_pool=s.hist_pool[0]) if varying else s

        def root_chunk_mesh(xb_c, start, grad, hess, mask, acc):
            return root_chunk(xb_c, start, grad, hess, mask, acc[0])[None]

        def root_commit_mesh(hist_acc, root_g, root_h, root_c, fmask):
            return _pack(root_commit(hist_acc[0], root_g, root_h, root_c,
                                     fmask))

        def wave_begin_mesh(best, num_leaves):
            # the ONE per-wave sync: a psum'd continue flag whose result
            # is fully replicated, so every process's host loop reads the
            # same device value (no host-side channel, no divergence)
            do, plan = wave_begin(best, num_leaves)
            return lax.psum(do.astype(jnp.int32), axis), plan

        def chunk_wave_mesh(xb_c, start, leaf_id, grad, hess, mask, plan,
                            hist_acc):
            leaf_id, h = chunk_wave(xb_c, start, leaf_id, grad, hess,
                                    mask, plan, hist_acc[0])
            return leaf_id, h[None]

        def chunk_wave_commit_mesh(xb_c, start, s, leaf_id, grad, hess,
                                   mask, plan, hist_acc, fmask):
            s = _unpack(s)
            leaf_id, h = chunk_wave(xb_c, start, leaf_id, grad, hess,
                                    mask, plan, hist_acc[0])
            return _pack(commit_state(s, plan, h, leaf_id, fmask))

        # the unjitted shard_map'd stage fns are kept for the jaxpr
        # auditor (analysis/jaxpr_audit.streamed_sharded_fn composes one
        # full wave from them): jax.make_jaxpr on these traces the exact
        # per-dispatch program without compiling or perturbing the jitted
        # executables above
        self._audit_fns = {}

        def sm(name, fn, in_specs, out_specs):
            raw = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=False)
            self._audit_fns[name] = raw
            return jax.jit(raw)

        # root sums need no explicit axis: jnp.sum over the global
        # row-sharded arrays lowers to a GSPMD all-reduce and yields
        # replicated scalars
        self._root_sums = jax.jit(root_sums)
        self._root_chunk = sm(
            "root_chunk", root_chunk_mesh,
            (xspec, repl, rows, rows, rows, lead), lead)
        self._root_commit = sm(
            "root_commit", root_commit_mesh,
            (lead, repl, repl, repl, repl), state_spec)
        self._wave_begin = sm("wave_begin", wave_begin_mesh,
                              (repl, repl), (repl, repl))
        self._chunk_wave = sm(
            "chunk_wave", chunk_wave_mesh,
            (xspec, repl, rows, rows, rows, rows, repl, lead),
            (rows, lead))
        self._chunk_wave_commit = sm(
            "chunk_wave_commit", chunk_wave_commit_mesh,
            (xspec, repl, state_spec, rows, rows, rows, rows, repl, lead,
             repl),
            state_spec)
        # zero accumulators are device-put once (host zeros are globally
        # available, so multi-process device_put is legal) and reused
        # every wave — transfers stay one chunk per dispatch
        world = pipeline.world
        shard0 = NamedSharding(mesh, P(DATA_AXIS))
        self._zero_root_acc = jax.device_put(
            np.zeros((world,) + self._hist_shape, np.float32), shard0)
        self._zero_wave_acc = jax.device_put(
            np.zeros((world, kb) + self._hist_shape, np.float32), shard0)

    # ----------------------------------------------------------------- grow
    def grow(self, grad: jnp.ndarray, hess: jnp.ndarray,
             sample_mask: jnp.ndarray, feature_mask: jnp.ndarray,
             trace_span=None
             ) -> Tuple[TreeArrays, jnp.ndarray, Optional[jnp.ndarray]]:
        """Grow one tree. ``grad``/``hess``/``sample_mask`` are full
        padded-length device arrays; ``sample_mask`` must already be 0 on
        padding rows (and on bagged-out / GOSS-dropped rows).

        ``trace_span`` (obs/reqtrace.py, optional) gets one child per
        frontier wave — chunk-transfer wait (the pipeline's ``wait_s``
        delta) vs host dispatch time, plus the fused last-chunk commit —
        mirroring the serving request span tree on the training side.
        Pure host bookkeeping: the dispatched programs are identical with
        tracing on or off."""
        pipe = self.pipeline
        R = pipe.chunk_rows
        meshed = self.mesh is not None
        sample_mask = sample_mask.astype(jnp.float32)
        tspan = trace_span if trace_span else None
        if tspan is not None:
            rspan = tspan.child("root_sweep", chunks=pipe.num_chunks)
            w_mark = pipe.wait_s
        root_g, root_h, root_c = self._root_sums(grad, hess, sample_mask)
        acc = self._zero_root_acc if meshed \
            else jnp.zeros(self._hist_shape, jnp.float32)
        for i, xb_c in pipe.sweep():
            # np scalar start: every process passes the identical value,
            # so the replicated in_spec holds by construction
            acc = self._root_chunk(xb_c, np.int32(i * R), grad, hess,
                                   sample_mask, acc)
        state = self._root_commit(acc, root_g, root_h, root_c,
                                  feature_mask)
        if tspan is not None:
            rspan.end(transfer_wait_ms=round(
                (pipe.wait_s - w_mark) * 1000.0, 3))

        last = pipe.num_chunks - 1
        while True:
            do, plan = self._wave_begin(state.best, state.tree.num_leaves)
            if not bool(do):          # the one host sync per wave
                break
            if tspan is not None:
                wspan = tspan.child("wave", wave=self.waves,
                                    chunks=pipe.num_chunks)
                w_mark = pipe.wait_s
            hist_acc = self._zero_wave_acc if meshed \
                else jnp.zeros((self.wave_width,) + self._hist_shape,
                               jnp.float32)
            leaf_id = state.leaf_id
            dispatches = 1            # wave_begin
            for i, xb_c in pipe.sweep():
                if i == last:
                    # final chunk: sweep + sibling subtraction + commit
                    # in one fused dispatch (the wave histogram stays an
                    # internal value of the compiled region)
                    state = self._chunk_wave_commit(
                        xb_c, np.int32(i * R), state, leaf_id, grad,
                        hess, sample_mask, plan, hist_acc, feature_mask)
                else:
                    leaf_id, hist_acc = self._chunk_wave(
                        xb_c, np.int32(i * R), leaf_id, grad, hess,
                        sample_mask, plan, hist_acc)
                dispatches += 1
            self.waves += 1
            self.wave_dispatches += dispatches
            if tspan is not None:
                wspan.end(dispatches=dispatches, fused_commit=True,
                          transfer_wait_ms=round(
                              (pipe.wait_s - w_mark) * 1000.0, 3))

        self.trees_grown += 1
        if self.params.obs_modelstats:
            return state.tree, state.leaf_id, (state.health, state.mstats)
        return state.tree, state.leaf_id, state.health
