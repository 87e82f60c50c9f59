"""Row-partition growth (core/partition.py) tests.

The partition path must produce bit-identical trees to the masked full-pass
path — it is a pure cost optimization (O(N x depth) vs O(N x num_leaves)
row visits, the DataPartition data_partition.hpp:20-37 analog).
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.core.histogram import hist_kernel
from lightgbm_tpu.core.grow import GrowParams, grow_tree
from lightgbm_tpu.core.split import FeatureMeta, SplitParams


def _meta(f, b, missing=0):
    return FeatureMeta(
        num_bin=jnp.full((f,), b, jnp.int32),
        missing_type=jnp.full((f,), missing, jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool),
        penalty=jnp.ones((f,), jnp.float32),
        monotone=jnp.zeros((f,), jnp.int32),
        col=jnp.arange(f, dtype=jnp.int32),
        offset=jnp.zeros((f,), jnp.int32),
        bundled=jnp.zeros((f,), bool))


def _split_params(**kw):
    base = dict(lambda_l1=0.0, lambda_l2=0.1, max_delta_step=0.0,
                min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3,
                min_gain_to_split=0.0, max_cat_threshold=32,
                cat_smooth=10.0, cat_l2=10.0, max_cat_to_onehot=4,
                min_data_per_group=100)
    base.update(kw)
    return SplitParams(**base)


@pytest.mark.parametrize("num_leaves,chunk", [(31, 512), (63, 300)])
def test_partition_matches_masked(num_leaves, chunk):
    np.random.seed(1)
    n, f, b = 5000, 6, 33
    xb = np.random.randint(0, b, (n, f)).astype(np.uint8)
    grad = np.random.randn(n).astype(np.float32)
    hess = (np.random.rand(n) + 0.5).astype(np.float32)
    mask = (np.random.rand(n) < 0.8).astype(np.float32)
    meta = _meta(f, b)
    fm = jnp.ones((f,), bool)
    out = {}
    for mode in (False, True):
        p = GrowParams(num_leaves=num_leaves, num_bins=b, max_depth=-1,
                       split=_split_params(), row_chunk=chunk,
                       hist_impl="scatter", use_partition=mode)
        t, li = jax.jit(lambda *a: grow_tree(*a, params=p)[:2])(
            jnp.asarray(xb), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), meta, fm)
        out[mode] = (jax.tree.map(np.asarray, t), np.asarray(li))
    t0, l0 = out[False]
    t1, l1 = out[True]
    assert (l0 == l1).all()
    assert int(t0.num_leaves) == int(t1.num_leaves)
    for name in t0._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(t0, name), np.float64),
            np.asarray(getattr(t1, name), np.float64),
            rtol=1e-5, atol=1e-6, err_msg=name)


def test_partition_leaf_counts_consistent():
    """Partition bookkeeping: leaf ranges tile [0, N) and counts match the
    per-row leaf_id assignment."""
    from lightgbm_tpu.core.partition import (hist_for_leaf, init_partition,
                                             make_row_gather, partition_rows,
                                             stack_vals)

    np.random.seed(4)
    n, chunk = 1000, 128
    f, b = 3, 8
    part = init_partition(n, 8, chunk)
    leaf_id = jnp.zeros((n,), jnp.int32)
    decision_np = np.random.rand(n) < 0.3
    # route the split decision through the gathered feature bytes, the way
    # grow_tree does: column 0 holds the decision bit
    xb = np.random.randint(0, b, (n, f)).astype(np.uint8)
    xb[:, 0] = decision_np.astype(np.uint8)
    vals = stack_vals(jnp.asarray(np.random.randn(n).astype(np.float32)),
                      jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32))
    gr = make_row_gather(jnp.asarray(xb), vals)

    def split(p, l):
        p, l = partition_rows(p, l, jnp.int32(0), jnp.int32(1),
                              lambda rows: rows[:, 0] == 1,
                              jnp.asarray(True), chunk, gr,
                              maintain_leaf_id=True)
        return (p, l) + tuple(
            hist_for_leaf(p, jnp.int32(child), gr, n, f, b, chunk,
                          impl="scatter") for child in (0, 1))

    part, leaf_id, hl, hr = jax.jit(split)(part, leaf_id)
    # a child's histogram, built from its new range, covers exactly its rows
    assert int(np.asarray(hl)[0, 1, 2]) == int(decision_np.sum())
    assert int(np.asarray(hr)[0, 0, 2]) == int((~decision_np).sum())
    lid = np.asarray(leaf_id)
    order = np.asarray(part.order)[:n]
    begin = np.asarray(part.leaf_begin)
    count = np.asarray(part.leaf_count)
    assert count[0] + count[1] == n
    assert begin[1] == count[0]
    # every leaf range holds exactly its leaf's rows
    np.testing.assert_array_equal(np.sort(order), np.arange(n))
    assert (lid[order[:count[0]]] == 0).all()
    assert (lid[order[count[0]:n]] == 1).all()
    assert count[0] == int(decision_np.sum())
    # reconstruction from ranges matches the maintained assignment
    from lightgbm_tpu.core.partition import leaf_id_from_partition
    lid2 = np.asarray(jax.jit(
        lambda p: leaf_id_from_partition(p, n))(part))
    np.testing.assert_array_equal(lid, lid2)


def _ranges(n, num_leaves, ids, counts, chunk=64, garbage=(), seed=0):
    """(n, [(order, leaf_begin, leaf_count)]) of a partition whose live
    ranges lie in position order ``ids`` with sizes ``counts``; every other
    leaf is empty, its start taken in turn from ``garbage`` (else 0)."""
    assert sum(counts) == n and len(ids) == len(counts)
    rng = np.random.RandomState(seed)
    order = np.concatenate([rng.permutation(n),
                            np.full((chunk,), n)]).astype(np.int32)
    begin = np.zeros((num_leaves,), np.int32)
    count = np.zeros((num_leaves,), np.int32)
    begin[list(ids)] = np.concatenate([[0], np.cumsum(counts)[:-1]])
    count[list(ids)] = counts
    empty = [l for l in range(num_leaves) if l not in set(ids)]
    for l, g in zip(empty, garbage):
        begin[l] = g
    return n, [(order, begin, count)]


def _case_one_leaf():
    return _ranges(1000, 8, [0], [1000])


def _case_255_leaves_200_live():
    rng = np.random.RandomState(1)
    ids = rng.permutation(255)[:200]
    cuts = np.sort(rng.choice(np.arange(1, 5000), 199, replace=False))
    counts = np.diff(np.concatenate([[0], cuts, [5000]]))
    return _ranges(5000, 255, ids, counts,
                   garbage=rng.randint(0, 5000, 55))


def _case_ids_out_of_position_order():
    # leaf 6's range lies before leaf 0's, leaf 3's between them
    return _ranges(900, 8, [6, 3, 0, 5], [100, 250, 50, 500])


def _case_empty_leaves_with_garbage_starts():
    # empty leaves 1, 3, 4 start on live leaf 2's start (300), in the
    # middle of leaf 0's range, and on the last position
    return _ranges(1000, 6, [0, 2, 5], [300, 450, 250],
                   garbage=[300, 17, 999])


def _case_one_row_leaves_at_both_ends():
    return _ranges(777, 5, [4, 1, 2], [1, 775, 1], garbage=[0, 776])


def _case_ragged_n_with_tail_pad():
    # 1003 rows in chunks of 128: the pad past order[:n] holds n
    return _ranges(1003, 4, [2, 0, 3, 1], [400, 3, 500, 100], chunk=128)


def _case_vmap_two_classes():
    # two classes' partitions of the same rows, batched as vmapped
    # class-batched growth batches them
    return 900, [_case_ids_out_of_position_order()[1][0],
                 _ranges(900, 8, [7, 1], [899, 1], garbage=[5, 899, 450],
                         seed=3)[1][0]]


_LEAF_ID_CASES = {f.__name__[len("_case_"):]: f for f in (
    _case_one_leaf, _case_255_leaves_200_live,
    _case_ids_out_of_position_order, _case_empty_leaves_with_garbage_starts,
    _case_one_row_leaves_at_both_ends, _case_ragged_n_with_tail_pad,
    _case_vmap_two_classes)}


def _leaf_ids_ref(order, begin, count, n):
    lid = np.zeros((n,), np.int32)
    for l in range(len(begin)):
        lid[order[begin[l]:begin[l] + count[l]]] = l
    return lid


@pytest.mark.parametrize("case", list(_LEAF_ID_CASES))
def test_leaf_id_from_partition_equals_the_range_by_range_reference(case):
    """Element for element: ``lid[order[b:b+c]] = l`` for every leaf."""
    from lightgbm_tpu.core.partition import (RowPartition,
                                             leaf_id_from_partition)
    n, parts = _LEAF_ID_CASES[case]()

    def fn(order, begin, count):
        return leaf_id_from_partition(RowPartition(order, begin, count), n)

    if len(parts) == 1:
        got = jax.jit(fn)(*map(jnp.asarray, parts[0]))[None]
    else:
        got = jax.jit(jax.vmap(fn))(*map(jnp.asarray, map(np.stack,
                                                          zip(*parts))))
    assert got.dtype == jnp.int32
    for g, p in zip(np.asarray(got), parts):
        np.testing.assert_array_equal(g, _leaf_ids_ref(*p, n))


def test_leaf_ids_are_mapped_without_a_gather_or_a_loop_over_all_rows():
    """No ``gather`` and no loop with an operand or a result of N
    elements: on a v5e the searchsorted over the range starts (8 steps,
    each a gather of N elements from a 255-entry table, and one more for
    the leaf id) was a quarter of an iteration (PERF.md, PR 30)."""
    from lightgbm_tpu.core.partition import (init_partition,
                                             leaf_id_from_partition)
    n, nl = 10_000, 255

    def full_size(fn, *args):
        return sorted({e.primitive.name for e in _eqns_under(
            jax.make_jaxpr(fn)(*args).jaxpr, "")
            if e.primitive.name in ("gather", "while", "scan")
            and any(int(np.prod(v.aval.shape)) >= n
                    for v in list(e.invars) + list(e.outvars)
                    if hasattr(v.aval, "shape"))})

    assert full_size(lambda p: leaf_id_from_partition(p, n),
                     init_partition(n, nl, 64)) == []
    # the walk does see the search it guards against
    starts = jnp.arange(nl, dtype=jnp.int32)
    assert full_size(lambda sb: (sb + 1)[jnp.searchsorted(
        sb, jnp.arange(n, dtype=jnp.int32), side="right") - 1],
        starts) == ["gather", "scan"]


_PN, _PF, _PB = 2000, 3, 8         # the placement cases' rows, columns, bins
# (begin, count) of the split leaf in units of the chunk c, valid, threshold
# on column 0 (bins <= it go left): leaf 0 lies before it, leaf 2 after
_PLACEMENT_CASES = {
    "invalid": (lambda c: (100, c + 9), False, 3),
    "empty": (lambda c: (100, 0), True, 3),
    "under_one_tile": (lambda c: (100, c - 7), True, 3),
    "exactly_one_tile": (lambda c: (100, c), True, 3),
    "ragged_tiles": (lambda c: (37, 2 * c + c // 3), True, 3),
    "all_left": (lambda c: (37, 2 * c + c // 3), True, _PB),
    "all_right": (lambda c: (37, 2 * c + c // 3), True, -1),
    "at_the_start": (lambda c: (0, c + 5), True, 3),
    "at_the_end": (lambda c: (_PN - (c + 5), c + 5), True, 3),
}


@functools.lru_cache(maxsize=None)
def _placement_problem(windows, chunk):
    """One compiled split per (placement, chunk); the leaf, its range and
    the threshold are arguments. Integer-valued gradients: every f32 sum is
    exact, so the histograms compare with array_equal."""
    from lightgbm_tpu.core.partition import (RowPartition, hist_for_leaf,
                                             make_row_gather, partition_rows,
                                             stack_vals)
    r = np.random.RandomState(28)
    xb = r.randint(0, _PB, (_PN, _PF)).astype(np.uint8)
    vals = np.stack([r.randint(-4, 5, _PN), r.randint(1, 4, _PN),
                     r.randint(0, 2, _PN)], axis=1).astype(np.float32)
    order = np.concatenate([r.permutation(_PN),
                            np.full(chunk, _PN)]).astype(np.int32)
    gr = make_row_gather(jnp.asarray(xb), stack_vals(
        jnp.asarray(vals[:, 0]), jnp.asarray(vals[:, 1]),
        jnp.asarray(vals[:, 2])))

    @jax.jit
    def split(begin, count, valid, thr):
        part = RowPartition(jnp.asarray(order), begin, count)
        part, _ = partition_rows(
            part, jnp.zeros((_PN,), jnp.int32), jnp.int32(1), jnp.int32(3),
            lambda rows: rows[:, 0].astype(jnp.int32) <= thr, valid, chunk,
            gr, windows=windows)
        # either child's histogram from its new range, as the grower
        # builds the smaller one's (a dead split builds none)
        hl, hr = (hist_for_leaf(part, jnp.int32(child), gr, _PN, _PF, _PB,
                                chunk, valid=valid, impl="scatter")
                  for child in (1, 3))
        return part, hl, hr
    # what stack_vals feeds the histograms: (g*m, h*m, m), m in {0, 1}
    return xb, vals * vals[:, 2:], order, split


@pytest.mark.parametrize("case", sorted(_PLACEMENT_CASES))
@pytest.mark.parametrize("chunk", [64, 300, 512])
@pytest.mark.parametrize("windows", [False, True],
                         ids=["element_scatter", "windows"])
def test_placement_matches_the_rule(windows, chunk, case):
    """Either placement gives the order the rule states — the leaf's lefts
    ascending from its begin, its rights descending from its end, in the
    order the tiles met them — and leaves everything outside the leaf's
    range as it was; a clamped window would show at either end of order."""
    where, valid, thr = _PLACEMENT_CASES[case]
    beg, cnt = where(chunk)
    xb, vals, order, split = _placement_problem(windows, chunk)
    begin = np.array([0, beg, beg + cnt, 0], np.int32)
    count = np.array([beg, cnt, _PN - beg - cnt, 0], np.int32)
    part, hl, hr = split(jnp.asarray(begin), jnp.asarray(count),
                         jnp.asarray(valid), jnp.int32(thr))

    ids = order[beg:beg + cnt] if valid else order[:0]
    left = xb[ids, 0] <= thr
    want = order.copy()
    want_begin, want_count = begin.copy(), count.copy()
    want_h = np.zeros((2, _PF, _PB, 3), np.float32)
    if valid:
        want[beg:beg + cnt] = np.concatenate([ids[left], ids[~left][::-1]])
        want_begin[3] = beg + left.sum()
        want_count[1], want_count[3] = left.sum(), (~left).sum()
        for side, rows in enumerate((ids[left], ids[~left])):
            for col in range(_PF):
                np.add.at(want_h[side, col], xb[rows, col], vals[rows])
    got = np.asarray(part.order)
    # the element scatter sends a ragged tile's surplus to the last slot
    keep = len(want) if windows else len(want) - 1
    np.testing.assert_array_equal(got[:keep], want[:keep])
    np.testing.assert_array_equal(np.asarray(part.leaf_begin), want_begin)
    np.testing.assert_array_equal(np.asarray(part.leaf_count), want_count)
    np.testing.assert_array_equal(np.asarray(hl), want_h[0])
    np.testing.assert_array_equal(np.asarray(hr), want_h[1])


def test_partition_window_placement_matches_scatter_path():
    """A grown tree is the same under either placement: the Pallas impls
    take the windows (interpret mode runs them on the CPU), "scatter" the
    element scatter."""
    np.random.seed(9)
    n, f, b = 3000, 5, 64
    xb = np.random.randint(0, b, (n, f)).astype(np.uint8)
    grad = np.random.randn(n).astype(np.float32)
    hess = (np.random.rand(n) + 0.5).astype(np.float32)
    mask = np.ones(n, np.float32)
    meta = _meta(f, b)
    fm = jnp.ones((f,), bool)
    out = {}
    for impl in ("scatter", "pallas_interpret"):
        p = GrowParams(num_leaves=15, num_bins=b, max_depth=-1,
                       split=_split_params(), row_chunk=1024,
                       hist_impl=impl, use_partition=True)
        t_, li = jax.jit(lambda *a: grow_tree(*a, params=p)[:2])(
            jnp.asarray(xb), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), meta, fm)
        out[impl] = (jax.tree.map(np.asarray, t_), np.asarray(li))
    t0, l0 = out["scatter"]
    t1, l1 = out["pallas_interpret"]
    assert int(t0.num_leaves) == 15
    assert (l0 == l1).all()
    np.testing.assert_array_equal(t0.split_feature, t1.split_feature)
    np.testing.assert_allclose(t0.leaf_value, t1.leaf_value,
                               rtol=1e-4, atol=1e-5)


def _eqns_under(jaxpr, scope):
    """Every equation, sub-jaxprs included, whose name stack has ``scope``."""
    for eqn in jaxpr.eqns:
        if scope in str(eqn.source_info.name_stack):
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns_under(sub, scope)


def test_tpu_tile_loop_places_ids_without_a_scatter_into_order():
    """The tile loop as the TPU rule builds it: under
    lgbm.partition_scatter one sort and two window writes a tile, and no
    scatter at all, so none whose operand has order's length (on a v5e such
    a scatter cost 187 us a tile, half an iteration; PERF.md, PR 28)."""
    from lightgbm_tpu.core.partition import (init_partition, make_row_gather,
                                             partition_rows, stack_vals,
                                             window_placement)
    n, chunk, f, b = 1000, 128, 3, 8
    impl = "pallas_interpret"
    gr = make_row_gather(jnp.zeros((n, f), jnp.uint8), stack_vals(
        jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
        jnp.ones((n,), jnp.float32)))
    part = init_partition(n, 8, chunk)

    def names(windows):
        jaxpr = jax.make_jaxpr(lambda p: partition_rows(
            p, jnp.zeros((n,), jnp.int32), jnp.int32(0), jnp.int32(1),
            lambda rows: rows[:, 0] == 1, jnp.asarray(True), chunk, gr,
            windows=windows))(part)
        return [(e.primitive.name, e.invars[0].aval.shape)
                for e in _eqns_under(jaxpr.jaxpr, "lgbm.partition_scatter")
                if e.invars]

    pallas = hist_kernel(impl).pallas
    placed = names(window_placement(pallas, vmapped=False))
    ops = [name for name, _ in placed]
    assert ops.count("sort") == 1
    assert ops.count("dynamic_update_slice") == 2
    assert not [o for o in ops if o.startswith("scatter")]
    # the walk does see the other placement's scatter into order
    assert ("scatter", part.order.shape) in names(
        window_placement(pallas, vmapped=True))


@pytest.mark.parametrize("impl", ["scatter", "pallas_interpret"])
def test_smaller_child_plus_sibling_is_the_parent(impl):
    """The identity the exact grower leans on: the two children's
    histograms, each built from its new range, add up to the parent's built
    from the old one — within float32 rounding in the gradient and hessian
    channels, and EXACTLY in the count channel (integers in float32), so
    parent - smaller is the sibling and its counts are the partition's."""
    from lightgbm_tpu.core.partition import (hist_for_leaf, init_partition,
                                             make_row_gather, partition_rows,
                                             stack_vals, window_placement)
    r = np.random.RandomState(32)
    n, chunk, f, b = 5000, 512, 5, 64
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    gr = make_row_gather(jnp.asarray(xb), stack_vals(
        jnp.asarray(r.randn(n).astype(np.float32)),
        jnp.asarray((r.rand(n) + 0.5).astype(np.float32)),
        jnp.asarray((r.rand(n) < 0.8).astype(np.float32))))

    @jax.jit
    def split(part):
        hist = functools.partial(hist_for_leaf, gather_rows=gr, num_rows=n,
                                 num_cols=f, num_bins=b, chunk=chunk,
                                 impl=impl)
        parent = hist(part, jnp.int32(0))
        part, _ = partition_rows(
            part, jnp.zeros((n,), jnp.int32), jnp.int32(0), jnp.int32(1),
            lambda rows: rows[:, 2] < 20, jnp.asarray(True), chunk, gr,
            windows=window_placement(hist_kernel(impl).pallas,
                                     vmapped=False))
        return parent, hist(part, jnp.int32(0)), hist(part, jnp.int32(1))

    parent, left, right = map(np.asarray,
                              split(init_partition(n, 4, chunk)))
    n_left = int((xb[:, 2] < 20).sum())
    assert 0 < n_left < n - n_left                  # left is the smaller
    np.testing.assert_array_equal(left[:, :, 2] + right[:, :, 2],
                                  parent[:, :, 2])
    np.testing.assert_array_equal(parent[:, :, 2] - left[:, :, 2],
                                  right[:, :, 2])
    np.testing.assert_allclose(parent - left, right, rtol=1e-5, atol=2e-4)


def _pallas_channels(jaxpr):
    """The value-channel count K of every pallas_call under ``jaxpr``: the
    leading axis of the digit kernel's [K, N] value input."""
    return [e.invars[1].aval.shape[0] for e in _eqns_under(jaxpr, "")
            if e.primitive.name == "pallas_call"]


def test_exact_grower_calls_the_kernel_with_three_channels_only():
    """Root and tile passes alike feed the kernel (grad, hess, count) of
    ONE leaf: no call prices two children through six channels (80 us a
    4,096-row tile on a v5e against 40 at three; PERF.md, PR 32)."""
    from lightgbm_tpu.core.histogram import hist_tile_vals
    n, f, b = 600, 4, 16
    p = GrowParams(num_leaves=7, num_bins=b, max_depth=-1,
                   split=_split_params(), row_chunk=256,
                   hist_impl="pallas_interpret", use_partition=True)
    jaxpr = jax.make_jaxpr(functools.partial(grow_tree, params=p))(
        jnp.zeros((n, f), jnp.uint8), jnp.zeros((n,), jnp.float32),
        jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
        _meta(f, b), jnp.ones((f,), bool))
    got = _pallas_channels(jaxpr.jaxpr)
    assert len(got) >= 2 and set(got) == {3}, got
    # the walk does see a six-channel call
    six = jax.make_jaxpr(lambda x, v: hist_tile_vals(
        x, v, b, "pallas_interpret"))(jnp.zeros((256, f), jnp.uint8),
                                      jnp.zeros((256, 6), jnp.float32))
    assert _pallas_channels(six.jaxpr) == [6]


def test_mesh_builds_the_globally_smaller_child_on_every_device():
    """Two devices whose LOCAL smaller child of the root's split differs:
    device 0 holds few of the left child's rows, device 1 most of them,
    and over both the left child is the smaller. Each device must
    histogram the globally smaller child (the psum adds like to like) —
    the grown tree is the single-device one."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    r = np.random.RandomState(5)
    n, f, b = 4000, 4, 16
    half = n // 2
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    # column 0 decides: bins 0..7 go left; 5% of device 0's rows and 85% of
    # device 1's, 45% of all
    left = np.concatenate([r.rand(half) < 0.05, r.rand(half) < 0.85])
    xb[:, 0] = np.where(left, r.randint(0, 8, n), r.randint(8, b, n))
    g = (np.where(left, -1.0, 1.0) + 0.1 * r.randn(n)).astype(np.float32)
    h = np.ones(n, np.float32)
    meta, fm = _meta(f, b), jnp.ones((f,), bool)
    p = GrowParams(num_leaves=15, num_bins=b, max_depth=-1,
                   split=_split_params(), row_chunk=256,
                   hist_impl="scatter", use_partition=True)

    tree_ref, leaf_ref = jax.jit(lambda *a: grow_tree(
        *a, meta, fm, p)[:2])(xb, g, h, h)
    assert int(tree_ref.split_feature[0]) == 0
    assert int(tree_ref.threshold_bin[0]) == 7
    assert left[:half].sum() < half - left[:half].sum()       # device 0
    assert left[half:].sum() > half - left[half:].sum()       # device 1
    assert left.sum() < n - left.sum()                        # both

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    fn = shard_map(
        lambda *a: grow_tree(*a, meta, fm,
                             p._replace(partition_on_mesh=True),
                             axis_name="data")[:2],
        mesh=mesh, in_specs=(P("data"),) * 4,
        out_specs=(jax.tree.map(lambda _: P(), tree_ref), P("data")),
        check_vma=False)
    tree_dp, leaf_dp = jax.jit(fn)(xb, g, h, h)
    for name in ("split_feature", "threshold_bin", "leaf_count",
                 "internal_count", "split_leaf"):
        np.testing.assert_array_equal(np.asarray(getattr(tree_dp, name)),
                                      np.asarray(getattr(tree_ref, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(leaf_dp), np.asarray(leaf_ref))
    np.testing.assert_allclose(np.asarray(tree_dp.leaf_value),
                               np.asarray(tree_ref.leaf_value),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("ids_dtype", ["int32", "uint8"])
def test_row_space_ids_agree_with_the_bags_ranges_of_order(ids_dtype):
    """A bag's rows are split twice over: by partition_rows in their ranges
    of ``order`` (for the histogram passes) and, with every other row, by
    route_in_row_space. After a few splits, one of them dead, an in-bag
    row's row-space id is the leaf whose range holds it, a row out of the
    bag has the leaf its column sends it to, and the pad is never read."""
    from lightgbm_tpu.core.partition import (
        ROUTE_LANES, RowPartition, _range_owner, bag_partition,
        bins_by_column, make_row_gather, partition_rows, route_in_row_space,
        row_space_leaf_ids, stack_vals)
    rng = np.random.default_rng(34)
    n, f, chunk, nl = 2 * ROUTE_LANES + 77, 3, 128, 8
    xb = jnp.asarray(rng.integers(0, 16, (n, f)).astype(np.uint8))
    in_bag = jnp.asarray(rng.random(n) < 0.3)
    ones = jnp.ones((n,), jnp.float32)
    gr = make_row_gather(xb, stack_vals(ones, ones, ones))
    # (leaf, column, threshold, valid): leaf 1 exists once split 0 is made
    splits = [(0, 0, 7, True), (1, 1, 4, True), (0, 2, 9, False),
              (0, 2, 11, True)]

    @jax.jit
    def run(xb, in_bag):
        bag = bag_partition(in_bag, chunk)
        zeros = jnp.zeros((nl,), jnp.int32)
        part = RowPartition(bag.order, zeros,
                            zeros.at[0].set(bag.leaf_count[0]))
        cols = bins_by_column(xb)
        lid = jnp.zeros(cols.shape[1:], ids_dtype)
        for t, (leaf, col, thr, valid) in enumerate(splits):
            leaf, right, valid = jnp.int32(leaf), jnp.int32(t + 1), \
                jnp.asarray(valid)
            part, _ = partition_rows(
                part, None, leaf, right, lambda r: r[:, col] <= thr, valid,
                chunk, gr)
            lid = route_in_row_space(lid, cols, jnp.int32(col),
                                     lambda c: c <= thr, leaf, right, valid)
        return part, lid, row_space_leaf_ids(lid, n)

    part, lid, ids = jax.tree.map(np.asarray, run(xb, in_bag))
    assert lid.shape == (3, ROUTE_LANES) and lid.dtype == ids_dtype
    assert ids.shape == (n,) and ids.dtype == np.int32
    x, bag = np.asarray(xb), np.asarray(in_bag)
    want = np.zeros(n, np.int32)
    for t, (leaf, col, thr, valid) in enumerate(splits):
        if valid:
            want[(want == leaf) & (x[:, col] > thr)] = t + 1
    np.testing.assert_array_equal(ids, want)
    assert sorted(np.unique(ids)) == [0, 1, 2, 4]
    owner = np.asarray(_range_owner(
        jnp.asarray(part.order), jnp.asarray(part.leaf_begin),
        jnp.asarray(part.leaf_count), n))
    np.testing.assert_array_equal(owner[bag], ids[bag])
    assert (owner[~bag] == -1).all()       # no range holds a row out of it
    np.testing.assert_array_equal(part.leaf_count,
                                  np.bincount(ids[bag], minlength=nl))


def test_bins_by_column_holds_the_stored_columns_in_whole_lanes():
    from lightgbm_tpu.core.partition import ROUTE_LANES, bins_by_column
    rng = np.random.default_rng(35)
    for n in (5, ROUTE_LANES, 3 * ROUTE_LANES + 1):
        xb = rng.integers(0, 255, (n, 4)).astype(np.uint8)
        cols = np.asarray(jax.jit(bins_by_column)(jnp.asarray(xb)))
        assert cols.dtype == np.uint8
        assert cols.shape == (4, -(-n // ROUTE_LANES), ROUTE_LANES)
        np.testing.assert_array_equal(cols.reshape(4, -1)[:, :n], xb.T)
        assert not cols.reshape(4, -1)[:, n:].any()
