"""Jaxpr-level audit primitives (layer 2a of the analyzer).

One implementation of the jaxpr walk the repo used to hand-roll per PR
(the obs psum-count test of PR 5, the costmodel jaxpr-identity test of
PR 6): recursive equation iteration, a stable STRUCTURAL FINGERPRINT of
a traced program (primitive sequence + avals, hashed), the collective
schedule (every psum / all-gather with operand shapes), f64-primitive
and host-callback counts.

Everything here consumes a ``ClosedJaxpr`` from ``jax.make_jaxpr`` —
pure tracing, no compilation — so auditing an entry point can never
recompile or perturb its executing program.  ``jax.ShapeDtypeStruct``
mirrors are accepted anywhere real arrays are, which is how the audit
prices entry points without touching training state (the
obs/costmodel.py extraction discipline).

The sharded-grower entry (``sharded_frontier_fn``) is the 8-virtual-
device construction previously duplicated between obs/perfgate.py and
tests/test_obs.py; both now import it from here.
"""
from __future__ import annotations

import hashlib
import json
from typing import Any, Dict, Iterator, List, Optional, Tuple

# primitive names that are cross-device collectives (operand shapes =
# the per-wave payload the multi-chip roadmap items care about)
COLLECTIVE_PRIMITIVES = {
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "ppermute", "pshuffle", "reduce_scatter", "psum2", "allreduce",
    "all_reduce",
}
# primitives that call back into the host from compiled code
HOST_CALLBACK_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "host_callback",
    "outside_call", "infeed", "outfeed", "python_callback",
}


def _sub_jaxprs(eqn) -> Iterator[Any]:
    """Inner jaxprs of a call/control-flow equation (pjit, scan, cond,
    while, shard_map, custom_* ...), wherever they hide in params."""
    for val in eqn.params.values():
        for item in (val if isinstance(val, (list, tuple)) else [val]):
            jaxpr = getattr(item, "jaxpr", None)
            if jaxpr is not None and hasattr(jaxpr, "eqns"):
                yield jaxpr                     # ClosedJaxpr -> Jaxpr
            elif hasattr(item, "eqns"):
                yield item                      # bare Jaxpr


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Depth-first iteration over every equation, recursing into
    sub-jaxprs (scan bodies, cond branches, shard_map shards...)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)      # accept ClosedJaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _aval_sig(var) -> str:
    aval = getattr(var, "aval", None)
    if aval is None:
        return "?"
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", "?")
    return "%s[%s]" % (dtype, ",".join(map(str, shape)))


def primitive_sequence(jaxpr) -> List[str]:
    """The flattened primitive-name sequence of a traced program — the
    raw material of the structural fingerprint."""
    return [eqn.primitive.name for eqn in iter_eqns(jaxpr)]


def structural_fingerprint(jaxpr) -> str:
    """Stable hash of a program's STRUCTURE: the depth-first primitive
    sequence plus each equation's output avals and the program's
    input/output avals.  Two programs with the same fingerprint execute
    the same primitive schedule on the same shapes — "byte-identical
    grower" as one comparison.  Parameters (branch indices, donated
    buffers, compiler options) are deliberately NOT hashed: they either
    show up as structure or are execution details."""
    h = hashlib.sha256()
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    h.update(",".join(_aval_sig(v) for v in inner.invars).encode())
    h.update(b"|")
    h.update(",".join(_aval_sig(v) for v in inner.outvars).encode())
    for eqn in iter_eqns(jaxpr):
        h.update(eqn.primitive.name.encode())
        h.update(b"(")
        h.update(",".join(_aval_sig(v) for v in eqn.outvars).encode())
        h.update(b");")
    return h.hexdigest()


def collective_schedule(jaxpr) -> List[Dict[str, Any]]:
    """Every collective equation in program order with operand shapes —
    the audit's "exactly one psum per wave, of exactly this payload"
    invariant.  Returns ``[{"primitive", "operands"}, ...]``."""
    out: List[Dict[str, Any]] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMITIVES:
            out.append({
                "primitive": eqn.primitive.name,
                "operands": [_aval_sig(v) for v in eqn.invars],
            })
    return out


def count_collectives(jaxpr) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for entry in collective_schedule(jaxpr):
        counts[entry["primitive"]] = counts.get(entry["primitive"], 0) + 1
    return counts


def count_f64_eqns(jaxpr) -> int:
    """Equations producing a float64 output — must be zero everywhere on
    the f32-only frontier path."""
    n = 0
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            dtype = getattr(getattr(v, "aval", None), "dtype", None)
            if dtype is not None and str(dtype) == "float64":
                n += 1
                break
    return n


def host_callback_primitives(jaxpr) -> List[str]:
    """Host-callback equations in the program (must be empty in hot
    paths — a callback serializes the dispatch pipeline)."""
    return [eqn.primitive.name for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in HOST_CALLBACK_PRIMITIVES
            or "callback" in eqn.primitive.name]


def audit_jaxpr(jaxpr) -> Dict[str, Any]:
    """The full invariant record of one traced entry point, as stored in
    ANALYSIS_BASELINE.json."""
    sched = collective_schedule(jaxpr)
    counts = count_collectives(jaxpr)
    return {
        "fingerprint": structural_fingerprint(jaxpr),
        "num_eqns": len(primitive_sequence(jaxpr)),
        "psums": counts.get("psum", 0),
        "all_gathers": counts.get("all_gather", 0),
        "collectives": sum(counts.values()),
        "collective_schedule": sched,
        "f64_eqns": count_f64_eqns(jaxpr),
        "host_callbacks": host_callback_primitives(jaxpr),
    }


# ------------------------------------------------------------ shared entry
def sharded_frontier_fn(num_devices: int = 8,
                        param_overrides: Optional[Dict[str, Any]] = None,
                        num_features: int = 4):
    """The canonical sharded frontier-grower entry: ``(fn, args,
    params)`` such that ``jax.make_jaxpr(fn)(*args)`` is the
    8-virtual-device shard_map program whose per-wave psum count
    obs/perfgate.py gates, the audit baseline records, and
    tests/test_obs.py pins.  One construction, three consumers.
    ``param_overrides`` lets invariance tests toggle GrowParams fields
    (``obs_health``) on the otherwise-identical program.
    ``num_features`` widens the feature axis (default 4, the historical
    shape — baselines keyed on it must not drift); the reduce-scatter
    learner needs it divisible by ``num_devices``.

    Returns None when fewer than ``num_devices`` devices exist (the
    analyze/perf-gate CLIs re-exec with a virtual-device flag to
    guarantee them)."""
    import jax
    if len(jax.devices()) < num_devices:
        return None
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from ..core.grow import GrowParams
    from ..core.grow_frontier import grow_tree_frontier
    from ..core.split import FeatureMeta, SplitParams

    r = np.random.RandomState(0)
    n, f, b = 256, int(num_features), 16
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    ones = np.ones(n, np.float32)
    meta = FeatureMeta(
        num_bin=jnp.full((f,), b, jnp.int32),
        missing_type=jnp.zeros((f,), jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool),
        penalty=jnp.ones((f,), jnp.float32),
        monotone=jnp.zeros((f,), jnp.int32))
    sp = SplitParams(lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0,
                     min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3,
                     min_gain_to_split=0.0, max_cat_threshold=32,
                     cat_smooth=10.0, cat_l2=10.0, max_cat_to_onehot=4,
                     min_data_per_group=100)
    params = GrowParams(num_leaves=7, num_bins=b, max_depth=3, split=sp,
                        row_chunk=16384, hist_impl="scatter",
                        **(param_overrides or {}))
    fmask = jnp.ones((f,), bool)
    mesh = Mesh(np.asarray(jax.devices()[:num_devices]), ("data",))

    def inner(xbj, gj, hj, mj):
        return grow_tree_frontier(xbj, gj, hj, mj, meta, fmask, params,
                                  axis_name="data")

    shapes = jax.eval_shape(
        lambda: grow_tree_frontier(jnp.asarray(xb), jnp.asarray(g),
                                   jnp.asarray(ones), jnp.asarray(ones),
                                   meta, fmask, params))
    out_specs = jax.tree.map(lambda _: P(), shapes)
    # only the per-row leaf ids stay sharded
    out_specs = (out_specs[0], P("data"), out_specs[2])
    fn = shard_map(inner, mesh=mesh, in_specs=(P("data"),) * 4,
                   out_specs=out_specs, check_vma=False)
    return fn, (xb, g, ones, ones), params


def streamed_sharded_fn(num_devices: int = 8,
                        param_overrides: Optional[Dict[str, Any]] = None,
                        num_features: int = 16):
    """The chunks-x-chips entry: ``(fn, args, params)`` such that
    ``jax.make_jaxpr(fn)(*args)`` traces ONE full growth wave of the
    mesh-mode StreamFrontierGrower — the host-dispatched sequence
    ``wave_begin`` (psum'd continue flag) -> ``chunk_wave`` (no
    collectives) -> ``chunk_wave_commit`` (the learner schedule fused
    into the last chunk).  Its collective count/payload is the per-wave
    comm contract of distributed out-of-core training that
    obs/perfgate.py gates and the audit baseline records: one int32
    psum (the flag) plus exactly the in-memory learner's schedule, so
    the f32 payload must EQUAL the ``wave_payload_f32_*`` pins.

    ``param_overrides`` picks the learner (``frontier_rs`` /
    ``voting_top_k``), as with ``sharded_frontier_fn``.  Args are
    ``ShapeDtypeStruct`` mirrors — tracing only, nothing executes.
    Returns None when fewer than ``num_devices`` devices exist."""
    import jax
    if len(jax.devices()) < num_devices:
        return None
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from ..core.grow import GrowParams
    from ..core.split import FeatureMeta, SplitParams
    from ..parallel.mesh import DATA_AXIS
    from ..stream.grow_stream import StreamFrontierGrower
    from ..stream.pipeline import ShardedChunkPipeline

    r = np.random.RandomState(0)
    world, chunk_rows, f, b = int(num_devices), 32, int(num_features), 16
    rows = 2 * chunk_rows                   # 2 uniform chunks per shard
    shard_chunks = [[r.randint(0, b, (rows, f)).astype(np.uint8)]
                    for _ in range(world)]
    mesh = Mesh(np.asarray(jax.devices()[:world]), (DATA_AXIS,))
    pipe = ShardedChunkPipeline(shard_chunks, [rows] * world, chunk_rows,
                                mesh)
    meta = FeatureMeta(
        num_bin=jnp.full((f,), b, jnp.int32),
        missing_type=jnp.zeros((f,), jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool),
        penalty=jnp.ones((f,), jnp.float32),
        monotone=jnp.zeros((f,), jnp.int32))
    sp = SplitParams(lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0,
                     min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3,
                     min_gain_to_split=0.0, max_cat_threshold=32,
                     cat_smooth=10.0, cat_l2=10.0, max_cat_to_onehot=4,
                     min_data_per_group=100)
    params = GrowParams(num_leaves=7, num_bins=b, max_depth=3, split=sp,
                        row_chunk=16384, hist_impl="scatter",
                        **(param_overrides or {}))
    grower = StreamFrontierGrower(pipe, meta, params, mesh=mesh)
    fns = grower._audit_fns

    n = pipe.num_padded
    sds = jax.ShapeDtypeStruct
    scal = sds((), jnp.float32)
    fmask = sds((f,), jnp.bool_)
    acc0 = sds((world,) + grower._hist_shape, jnp.float32)
    state = jax.eval_shape(fns["root_commit"], acc0, scal, scal, scal,
                           fmask)
    xb_c = sds((world * chunk_rows, pipe.num_cols), jnp.uint8)
    row = sds((n,), jnp.float32)
    hist_acc = sds((world, grower.wave_width) + grower._hist_shape,
                   jnp.float32)

    def one_wave(state, xb_c, grad, hess, mask, hist_acc, fmask):
        do, plan = fns["wave_begin"](state.best, state.tree.num_leaves)
        leaf_id, hist_acc = fns["chunk_wave"](
            xb_c, np.int32(0), state.leaf_id, grad, hess, mask, plan,
            hist_acc)
        state = fns["chunk_wave_commit"](
            xb_c, np.int32(chunk_rows), state, leaf_id, grad, hess, mask,
            plan, hist_acc, fmask)
        return do, state

    return one_wave, (state, xb_c, row, row, row, hist_acc, fmask), params


def schedule_signature(schedule: List[Dict[str, Any]]) -> str:
    """Canonical string form of a collective schedule (baseline diffs)."""
    return json.dumps(schedule, sort_keys=True)
