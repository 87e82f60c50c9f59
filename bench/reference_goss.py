"""Plain reference for a binary-logloss GOSS job on data with missing
values, and the numbers that decide ``correct``: the copy of
bench/reference_clicklog.py that the configuration
criteo-1of64-clicklog-goss brings. It does everything that file does, and
knows what a bag is: a tree of ``boosting=goss`` past the unsampled
iterations is grown on the ``top_cnt`` rows of largest |g*h| at weight 1
and on ``other_cnt`` of the rest at weight (N - top_cnt) / other_cnt
(LightGBM v2.2.4 src/boosting/goss.hpp), and every row, in the bag or
out of it, takes the tree's score.

It imports nothing of the program. Its inputs are the raw data the
generator made, the job's parameters, the text of the model the timed
booster wrote (the standard LightGBM model format, parsed here), the
scores that booster held when the window closed, and for each judged
tree the bag the booster grew it on, a code a row (0 out of the bag, 1
top, 2 one of the others). It follows every tree up to the last judged
one from the raw values alone, in float64: a tree without a bag (the
unsampled iterations) on all rows at weight 1, a tree with one on its
bag's rows at their weights; either way its own leaf values move its own
scores on ALL rows. Of the judged trees (the first sampled ones) it reads
the sibling's numbers, with counts over in-bag rows and sums weighted:

  count_mismatch   rows whose node the reference disagrees on, over every
                   followed tree, the unsampled ones too: every row (of a
                   sampled tree: every row of its bag) is
                   sent down the tree by ``x <= threshold`` on its
                   raw value, a missing value down the side the node's
                   ``default_left`` names, and every node's count is held
                   against the model's (binning's value -> bin map, the
                   NaN bin, the row partition's missing direction)
  leaf_value_gap   worst leaf: the model's value against -G/(H+l2) * rate
                   from the reference's own gradients of its own scores
                   (histogram sums, leaf output, score update: the
                   reference's scores follow tree by tree);
                   leaf_value_gap_median is the tree's median leaf
  split_gain_gap   worst split: the model's gain against the gain of that
                   split from the reference's sums (histogram kernel,
                   split search's arithmetic); split_gain_gap_median is
                   the tree's median split
  node_regret      at nodes drawn from the seed (the root of the last
                   judged tree and some internal nodes below the root of
                   each), how far the chosen split's gain lies below the
                   best gain over every column and every boundary of the
                   reference's own grid, each boundary priced with the
                   missing rows on the left AND on the right, both on ALL
                   rows of the node (split search's argmax and its
                   two-direction scan, at the root and below it)
  split_order_gap  leaf-wise growth splits the waiting leaf whose best
                   gain is largest: how far a split's gain lies above
                   that of a split made earlier while its leaf already
                   waited (WHICH leaf is split next)
  score_gap        on a seeded row sample, the booster's final scores
                   against the sum of its own trees' leaves over ALL
                   iterations (score update of every block in the window)

and it judges each bag itself, from its own |g*h| of its own scores and
not by the program's sampler:

  bag_count_gap    |rows coded top - top_cnt| + |rows coded other -
                   other_cnt|, summed over the judged bags
  bag_top_missed   rows on the wrong side of the reference's own
                   threshold (its top_cnt-th largest |g*h|) by more than
                   a rounding band: above it and not top, or below it and
                   top. The program's |g*h| is float32 of float32 scores,
                   so rows within the band of the threshold may fall
                   either way, and exact ties are the program's to break
  bag_uniformity   -log10 of the smallest tail probability over three
                   tests a bag: the others against a uniform draw of the
                   rest over ten value bands of |g*h| below the threshold
                   (chi-square), over 64 blocks of row position
                   (chi-square), and the share of this bag's others that
                   the next judged bag draws again (it is other_cnt /
                   (N - top_cnt) for independent draws, all of them for a
                   bag reused; two-sided normal). 999 where a probability
                   underflows

A gap is |program - reference| over the larger of the reference's value
at that leaf and its median over the tree's leaves, since some leaves sit
at nought.

Against upstream's tree.h ``NumericalDecision`` and
feature_histogram.hpp ``FindBestThreshold`` (v2.2.4), as the model text's
``decision_type`` encodes them (bit 0 categorical, bit 1 default_left,
bits 2-3 the missing type: 0 none, 1 zero, 2 NaN):

  same      a NaN under missing type NaN, and a zero (|x| <= 1e-35) under
            missing type zero, goes where ``default_left`` says; a NaN
            under any other missing type is read as 0.0; every other
            value goes left when ``x <= threshold``. A column that has
            missing values is priced with them on either side of every
            boundary and the better side kept: upstream's two scans,
            which leave the NaN bin (the zero bin) out of the running sum
            so that it falls to the far side.
  departs   candidates are the reference's own cells, not the program's
            bins: cut points at equally spaced ranks of the column's
            present values (every distinct value where there are few),
            where bench/reference_gbdt.py cuts the value range into
            equal widths: a count column's range is a few values wide
            where its rows are and millions wide where they are not.
            One more candidate than upstream's scan names outright:
            every present value left, the missing rows right (upstream
            reaches it as the NaN bin alone on the right). Like the
            sibling it holds a candidate to ``min_data_in_leaf`` on both
            sides and not to ``min_sum_hessian_in_leaf``, and it has no
            categorical decision: a model with one is refused.
"""
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8


# ------------------------------------------------------------ model text
def parse_trees(model_text):
    """Trees of a LightGBM model text as dicts of numpy arrays."""
    ints = ("split_feature", "left_child", "right_child", "leaf_count",
            "internal_count", "decision_type")
    floats = ("threshold", "leaf_value", "split_gain")
    trees = []
    for block in model_text.split("\nTree=")[1:]:
        block = block.split("\nend of trees", 1)[0]
        kv = dict(ln.split("=", 1) for ln in block.splitlines()[1:]
                  if "=" in ln)
        t = {"num_leaves": int(kv["num_leaves"])}
        for k in ints:
            t[k] = np.array(kv.get(k, "").split(), np.int64)
        for k in floats:
            t[k] = np.array(kv.get(k, "").split(), np.float64)
        trees.append(t)
    return trees


# ------------------------------------------------------------ the reference
def sigmoid(s):
    return 1.0 / (1.0 + np.exp(-s))


def init_score(y, row_weight=None):
    p = float(np.average(y.astype(np.float64), weights=row_weight))
    p = min(max(p, 1e-15), 1 - 1e-15)
    return float(np.log(p / (1.0 - p)))


class Columns:
    """Contiguous columns (views where X is column-major, copies made
    once otherwise) of the columns the trees use."""

    def __init__(self, X):
        self.X, self.cols = X, {}

    def __getitem__(self, f):
        if f not in self.cols:
            self.cols[f] = np.ascontiguousarray(self.X[:, f])
        return self.cols[f]


ZERO_THRESHOLD = 1e-35   # upstream's kZeroThreshold
MISSING_NONE, MISSING_ZERO, MISSING_NAN = 0, 1, 2


def goes_left(v, threshold, decision_type):
    """Upstream's NumericalDecision on an array of raw values."""
    if decision_type & 1:
        raise ValueError("reference_clicklog: a categorical decision")
    default_left = bool(decision_type & 2)
    missing_type = (decision_type >> 2) & 3
    left = v <= np.float64(threshold)            # a NaN compares False
    nan = np.isnan(v)
    if missing_type == MISSING_NAN:
        left[nan] = default_left
        return left
    left[nan] = 0.0 <= threshold                 # read as 0.0
    if missing_type == MISSING_ZERO:
        left[nan | (np.abs(v) <= ZERO_THRESHOLD)] = default_left
    return left


def route(cols, tree, rows=None, n=None):
    """Leaf index of each row, by raw value, threshold and the node's
    missing direction. Nodes are split in the order the model lists them,
    so a node's rows exist before it is reached; children listed as
    -(leaf+1)."""
    n = n if rows is None else len(rows)
    leaf = np.zeros(n, np.int32)
    if tree["num_leaves"] <= 1:
        return leaf
    pending = {0: np.arange(n, dtype=np.int32)}
    for k in range(tree["num_leaves"] - 1):
        idx = pending.pop(k)
        col = cols[int(tree["split_feature"][k])]
        v = col[idx] if rows is None else col[rows[idx]]
        go_left = goes_left(v, float(tree["threshold"][k]),
                            int(tree["decision_type"][k]))
        for child, part in ((int(tree["left_child"][k]), idx[go_left]),
                            (int(tree["right_child"][k]), idx[~go_left])):
            if child < 0:
                leaf[part] = -child - 1
            else:
                pending[child] = part
    return leaf


def node_sums(tree, per_leaf):
    """Sums at the internal nodes from sums at the leaves (children come
    after their parent, so one backward sweep does it)."""
    m = tree["num_leaves"] - 1
    out = np.zeros(m, np.float64)
    for k in range(m - 1, -1, -1):
        for child in (int(tree["left_child"][k]), int(tree["right_child"][k])):
            out[k] += per_leaf[-child - 1] if child < 0 else out[child]
    return out


def child_sums(child, per_leaf, per_node):
    """Each split's child's sum: a leaf's (listed as -(leaf+1)) or a
    node's."""
    return np.where(child < 0, per_leaf[np.maximum(-child - 1, 0)],
                    per_node[np.maximum(child, 0)])


def leaf_gain(g, h, l2):
    return g * g / (h + l2)


def leaves_under(tree):
    """For each internal node, the leaves of its subtree."""
    m = tree["num_leaves"] - 1
    out = [None] * m
    for k in range(m - 1, -1, -1):
        out[k] = []
        for child in (int(tree["left_child"][k]), int(tree["right_child"][k])):
            out[k] += [-child - 1] if child < 0 else out[child]
    return out


def draw_nodes(seed, trees, judged, per_tree):
    """The nodes whose choice of split is judged, {tree index: nodes}:
    ``per_tree`` internal nodes below the root of every tree of ``judged``
    (indices into ``trees``), drawn from the seed, and the root of the
    last of them (the program prices a root by a pass of its own)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xA26]))
    out = {}
    for i in judged:
        tree = trees[i]
        below = np.arange(1, tree["num_leaves"] - 1)
        picked = rng.choice(below, size=min(per_tree, len(below)),
                            replace=False)
        out[i] = ([0] if i == judged[-1] and tree["num_leaves"] > 1
                  else []) + sorted(int(k) for k in picked)
    return out


class GridSearch:
    """Greedy split search over the reference's own candidates. Every
    column is cut at up to ``cells`` - 1 points, the values found at
    equally spaced ranks among its present (not missing) values on every
    k-th row; each cut point c is the threshold ``x <= c``, and one more
    candidate puts every present value left. Sums per cell in float64. A
    column with missing rows in the node has every candidate priced twice,
    the missing rows left and right, and the better kept. At 4096 cells
    the cut points are finer than the program's 255 bins, so a sound
    argmax reads a regret of a few thousandths at most. The best split of
    a node of up to ``search_rows`` rows is found on all its rows; that of
    a larger node is found on every k-th of its rows and then priced on
    ALL of them, a gain the true best cannot lie below."""

    CUT_SAMPLE = 2000000     # values a column's cut points are taken from

    def __init__(self, cols, num_cols, cells, min_data, search_rows,
                 zero_as_missing):
        self.cols, self.cells, self.min_data = cols, cells, min_data
        self.num_cols, self.search_rows = num_cols, search_rows
        self.zero_as_missing = zero_as_missing
        with ThreadPoolExecutor(THREADS) as pool:
            self.cuts = list(pool.map(self.cut_points, range(num_cols)))

    def missing(self, x):
        nan = np.isnan(x)
        if self.zero_as_missing:
            nan |= np.abs(x) <= ZERO_THRESHOLD
        return nan

    def cut_points(self, j):
        x = self.cols[j]
        x = x[::max(1, len(x) // self.CUT_SAMPLE)]
        x = np.sort(x[~self.missing(x)])
        if not len(x):
            return x
        ranks = np.linspace(0, len(x) - 1, self.cells + 1)[1:-1]
        return np.unique(x[ranks.astype(np.intp)])

    def gains(self, j, idx, g, h, l2):
        """The gain of every candidate of column ``j`` on the rows ``idx``
        (None: every row), [2, cuts + 1]: row 0 with the missing rows on
        the left, row 1 with them on the right; -inf where a side holds
        under min_data rows."""
        x = self.cols[j] if idx is None else self.cols[j][idx]
        cuts = self.cuts[j]
        m = len(cuts) + 1
        gone = self.missing(x)
        here = ~gone
        gm, hm, cm = g[gone].sum(), h[gone].sum(), int(gone.sum())
        q = np.searchsorted(cuts, x[here], side="left")   # x <= cuts[q]
        gp, hp, cp = g.sum(), h.sum(), len(g)
        gl = np.cumsum(np.bincount(q, g[here], m))
        hl = np.cumsum(np.bincount(q, h[here], m))
        cl = np.cumsum(np.bincount(q, minlength=m))
        out = np.full((2, m), -np.inf)
        for side, (ga, ha, ca) in enumerate(((gl + gm, hl + hm, cl + cm),
                                             (gl, hl, cl))):
            ok = (ca >= self.min_data) & (cp - ca >= self.min_data)
            with np.errstate(divide="ignore", invalid="ignore"):
                gain = (leaf_gain(ga, ha, l2)
                        + leaf_gain(gp - ga, hp - ha, l2)
                        - leaf_gain(gp, hp, l2))
            out[side] = np.where(ok, gain, -np.inf)
            if not cm:
                break     # nothing is missing here: one pricing
        return out

    def best_gain(self, idx, g, h, l2):
        """``g``, ``h``: of the node's rows ``idx`` (None: every row)."""
        k = max(1, -(-len(g) // self.search_rows))   # 1 for an empty node
        if k == 1:
            rows, gs, hs = idx, g, h
        else:
            rows = np.arange(0, len(g), k) if idx is None else idx[::k]
            gs, hs = g[::k], h[::k]

        def one(j):
            gain = self.gains(j, rows, gs, hs, l2)
            return float(gain.max()), j, int(gain.argmax())

        with ThreadPoolExecutor(THREADS) as pool:
            best, j, cell = max(pool.map(one, range(self.num_cols)))
        if k > 1 and best > 0:
            best = float(self.gains(j, idx, g, h, l2).flat[cell])
        return best


def order_gap(tree, gain):
    """Leaf-wise growth: split k is the best of the leaves waiting at step
    k. Node j's leaf waits from the step after its parent's split until
    step j, so no split made in between may have a smaller gain than j's.
    The worst (gain_j - gain_k) / gain_k over such pairs, 0 where none."""
    m = tree["num_leaves"] - 1
    parent = np.full(m, -1)
    for k in range(m):
        for child in (int(tree["left_child"][k]), int(tree["right_child"][k])):
            if child >= 0:
                parent[child] = k
    worst = 0.0
    for j in range(m):
        earlier = gain[parent[j] + 1:j]
        if len(earlier):
            worst = max(worst, float(((gain[j] - earlier) / earlier).max()))
    return worst


def gaps(program, reference):
    """Per element, |program - reference| over max(|reference|,
    median |reference|)."""
    ref = np.abs(reference)
    return np.abs(program - reference) / np.maximum(ref, np.median(ref))


def gap(program, reference):
    """The worst element's gap."""
    return float(np.max(gaps(program, reference)))


OUT_OF_BAG, BAG_TOP, BAG_OTHER = 0, 1, 2
BAND = 1e-4    # relative half-width of the threshold's rounding band


def bag_counts(n, top_rate, other_rate):
    """goss.hpp's counts and the others' multiplier."""
    top_cnt = max(1, int(n * top_rate))
    other_cnt = max(1, min(int(n * other_rate), n - top_cnt))
    return top_cnt, other_cnt, (n - top_cnt) / other_cnt


def chi_square_tail(stat, df):
    """P(chi-square with ``df`` degrees > stat), Wilson and Hilferty's
    normal approximation (df >= 9 here)."""
    z = ((stat / df) ** (1.0 / 3) - (1 - 2.0 / (9 * df))) \
        / math.sqrt(2.0 / (9 * df))
    return 0.5 * math.erfc(z / math.sqrt(2.0))


def spread_tail(cell, is_rest, is_other, cells):
    """Tail probability that ``is_other`` is a uniform draw of the rows
    ``is_rest``, over their ``cell`` (0 .. cells - 1): chi-square of the
    others a cell against the cell's share of the rest. A draw without
    replacement spreads a little less than the chi-square allows."""
    rest = np.bincount(cell[is_rest], minlength=cells).astype(np.float64)
    got = np.bincount(cell[is_other], minlength=cells).astype(np.float64)
    want = got.sum() * rest / rest.sum()
    keep = want > 0
    stat = float((((got - want) ** 2)[keep] / want[keep]).sum())
    return chi_square_tail(stat, int(keep.sum()) - 1)


def judge_bag(code, gh, top_cnt, other_cnt, next_code=None):
    """One bag against the reference's own |g*h|: (count gap, rows on the
    wrong side of the threshold's band, smallest tail probability)."""
    n = len(code)
    is_top, is_other = code == BAG_TOP, code == BAG_OTHER
    count_gap = abs(int(is_top.sum()) - top_cnt) \
        + abs(int(is_other.sum()) - other_cnt)
    thr = np.partition(gh, n - top_cnt)[n - top_cnt]
    missed = int(((gh > thr * (1 + BAND)) & ~is_top).sum()
                 + ((gh < thr * (1 - BAND)) & is_top).sum())
    is_rest = ~is_top
    rest_gh = gh[is_rest]
    edges = np.quantile(rest_gh[::max(1, len(rest_gh) // 1000000)],
                        np.arange(1, 10) / 10.0)
    tails = [spread_tail(np.searchsorted(edges, gh, side="left"), is_rest,
                         is_other, 10),
             spread_tail((np.arange(n, dtype=np.int64) * 64 // n)
                         .astype(np.intp), is_rest, is_other, 64)]
    if next_code is not None:
        again = is_other & (next_code != BAG_TOP)
        m = int(again.sum())
        x = int((again & (next_code == BAG_OTHER)).sum())
        p0 = other_cnt / (n - top_cnt)
        if m and p0 < 1:    # every one of the rest drawn: nothing to test
            z = (x - m * p0) / math.sqrt(m * p0 * (1 - p0))
            tails.append(math.erfc(abs(z) / math.sqrt(2.0)))
    return count_gap, missed, min(tails)


def route_all(X, trees):
    """Every row's leaf in every tree, by raw value: follow's costly part,
    which a caller that follows the same trees again hands back to it."""
    cols = Columns(X)
    for tree in trees:     # made once, not by thirteen threads at a time
        for f in np.unique(tree["split_feature"]):
            cols[int(f)]
    with ThreadPoolExecutor(min(len(trees), os.cpu_count() or THREADS)) \
            as pool:
        return list(pool.map(lambda t: route(cols, t, n=len(X)), trees))


def follow(X, y, trees, bags, rates, learning_rate, l2, nodes, search,
           grad_cast=None, row_weight=None, leaves=None, weight_of=None):
    """Follow ``trees`` (every tree up to the last judged one) from the
    seed's data. ``bags`` maps a tree's index to its bag's codes; a tree
    without one is grown on all rows. ``rates`` = (top_rate, other_rate).
    Returns {tree index: dict} of what the reference reads: of every tree
    its node counts, and of a tree with a bag also leaf values, split
    gains, the worst regret over the tree's judged ``nodes`` ({tree index: node list},
    draw_nodes; ``search`` holds GridSearch's cells, min_data, search_rows
    and zero_as_missing), the order gap, and the bag's own three numbers
    (judge_bag). ``grad_cast`` rounds gradients and hessians before they
    are weighted and summed (the lower-precision control). ``row_weight``:
    the data's own weight a row, which multiplies gradient and hessian
    before the bag is drawn. ``leaves``: route_all's result for these
    trees. ``weight_of``: the weight of each code, where a planted fault
    wants another than (0, 1, the multiplier)."""
    n = len(y)
    cols = Columns(X)
    y64 = y.astype(np.float64)
    init = init_score(y, row_weight)
    score = np.full(n, init, np.float64)
    top_cnt, other_cnt, multiply = bag_counts(n, *rates)
    if weight_of is None:
        weight_of = np.array([0.0, 1.0, multiply])
    search = GridSearch(cols, X.shape[1], **search)
    if leaves is None:
        leaves = route_all(X, trees)
    out = {}
    # a bag is judged beside the trees that follow it: its |g*h| is of the
    # scores before its tree
    judging = ThreadPoolExecutor(max(1, len(bags)))
    for i, (tree, leaf) in enumerate(zip(trees, leaves)):
        p = sigmoid(score)
        g, h = p - y64, p * (1.0 - p)
        if row_weight is not None:
            g, h = g * row_weight, h * row_weight
        code = bags.get(i)
        if code is not None:
            later = [j for j in sorted(bags) if j > i]
            bag_read = judging.submit(
                judge_bag, code, np.abs(g * h), top_cnt, other_cnt,
                bags[later[0]] if later else None)
        if grad_cast is not None:
            g, h = grad_cast(g), grad_cast(h)
        nl = tree["num_leaves"]
        if code is None:
            rows, gw, hw, leaf_in = None, g, h, leaf
        else:
            rows = np.flatnonzero(code != OUT_OF_BAG)
            w = weight_of[code[rows]]
            gw, hw, leaf_in = g[rows] * w, h[rows] * w, leaf[rows]
        cnt = np.bincount(leaf_in, minlength=nl)
        gl = np.bincount(leaf_in, weights=gw, minlength=nl)
        hl = np.bincount(leaf_in, weights=hw, minlength=nl)
        with np.errstate(divide="ignore", invalid="ignore"):
            value = -gl / (hl + l2) * learning_rate   # NaN: an empty leaf
        ci = node_sums(tree, cnt.astype(np.float64))
        out[i] = {"leaf_count": cnt, "internal_count": ci}
        if code is not None:
            gi, hi = node_sums(tree, gl), node_sums(tree, hl)
            lc, rc = tree["left_child"], tree["right_child"]
            with np.errstate(divide="ignore", invalid="ignore"):
                gain = (leaf_gain(child_sums(lc, gl, gi),
                                  child_sums(lc, hl, hi), l2)
                        + leaf_gain(child_sums(rc, gl, gi),
                                    child_sums(rc, hl, hi), l2)
                        - leaf_gain(gi, hi, l2))
            under, regret = leaves_under(tree), 0.0
            for k in nodes.get(i, ()):
                if k == 0:
                    best = search.best_gain(rows, gw, hw, l2)
                else:
                    inside = np.zeros(nl, bool)
                    inside[under[k]] = True
                    at = np.flatnonzero(inside[leaf_in])
                    best = search.best_gain(rows[at], gw[at], hw[at], l2)
                if best > 0:   # -inf where no boundary leaves min_data rows
                    regret = max(regret, (best - gain[k]) / best)
            out[i].update(leaf_value=value + (init if i == 0 else 0.0),
                          split_gain=gain, node_regret=regret,
                          split_order_gap=order_gap(tree, gain),
                          bag=bag_read)
        # an empty leaf's NaN reaches no row: a row sits in a leaf of its
        # own tree, and a leaf no in-bag row reached is one the program
        # did not grow
        score += np.nan_to_num(value)[leaf]
    for f in out.values():
        if "bag" in f:
            f["bag"] = f["bag"].result()
    judging.shutdown()
    return out


def readings(trees, ref):
    """The numbers compared, program's model against the reference's
    follow of it ({tree index: dict}), each the worst over the judged
    trees (count_mismatch summed over every followed tree, the bags' two
    counts over the judged ones): the worst leaf
    and the worst split of a tree, and the tree's median leaf and split
    (a small leaf carved from a large parent inherits the parent's
    float32 rounding, so the worst swings with the tree's shape and the
    median does not)."""
    r = {"count_mismatch": 0.0, "leaf_value_gap": 0.0,
         "leaf_value_gap_median": 0.0, "split_gain_gap": 0.0,
         "split_gain_gap_median": 0.0, "node_regret": 0.0,
         "split_order_gap": 0.0, "bag_count_gap": 0.0,
         "bag_top_missed": 0.0, "bag_uniformity": 0.0}

    def worst(name, value):
        r[name] = max(r[name], float(value))

    for i, want in sorted(ref.items()):
        tree = trees[i]
        r["count_mismatch"] += float(
            np.abs(tree["leaf_count"] - want["leaf_count"]).sum()
            + np.abs(tree["internal_count"] - want["internal_count"]).sum())
        if "bag" not in want:
            continue    # an unsampled tree: its counts are held, no more
        count_gap, missed, tail = want["bag"]
        r["bag_count_gap"] += float(count_gap)
        r["bag_top_missed"] += float(missed)
        worst("bag_uniformity",
              999.0 if tail < 1e-300 else -math.log10(tail))
        leaf = gaps(tree["leaf_value"], want["leaf_value"])
        split = gaps(tree["split_gain"], want["split_gain"])
        worst("leaf_value_gap", leaf.max())
        worst("leaf_value_gap_median", np.median(leaf))
        worst("split_gain_gap", split.max())
        worst("split_gain_gap_median", np.median(split))
        worst("node_regret", want["node_regret"])
        worst("split_order_gap", want["split_order_gap"])
    return r


def score_gap(X, trees, scores, sample_rows):
    """The booster's scores at the sampled rows against the sum of its own
    trees' leaves there (the first tree carries the init score)."""
    if len(scores) != len(X):
        return float("inf")   # the booster did not hold a score per row
    cols = Columns(X)
    total = np.zeros(len(sample_rows), np.float64)
    for tree in trees:
        total += tree["leaf_value"][route(cols, tree, rows=sample_rows)]
    return gap(np.asarray(scores, np.float64)[sample_rows], total)


def bfloat16_round(a):
    """Round float64 to the nearest bfloat16 (8 significand bits), the
    precision below the float32 the configuration states."""
    f = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    f = (f + 0x7FFF + ((f >> 16) & 1)) & 0xFFFF0000
    return f.astype(np.uint32).view(np.float32).astype(np.float64)
