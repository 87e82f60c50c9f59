"""Plain reference for a binary-logloss GBDT job whose table has
categorical columns, and the numbers that decide ``correct``: the copy of
bench/reference_clicklog.py that the configuration criteo-1of48-categorical
brings. It does everything that file does (its docstring says what each
number reads and how a missing value is treated), and knows a categorical
node.

It imports nothing of the program. Its inputs are the raw data the
generator made, the job's parameters (which columns are categorical and
the six categorical parameters among them), the text of the model the
timed booster wrote and the scores that booster held when the window
closed. What it adds to the sibling:

  routing          a categorical node (``decision_type`` bit 0) holds a set
                   of raw category ids, the bits of its words of the
                   tree's ``cat_threshold`` between its two
                   ``cat_boundaries``. Every row is routed by its RAW
                   value: an id in the set goes left; a NaN, a negative id
                   and an id the set does not hold (too large for its
                   words, or a category the program's bin mapper did not
                   keep) go right. ``count_mismatch`` holds every node's
                   count to that.
  leaf values      -G / (H + lambda) * rate from the reference's own
                   gradients of its own scores, with lambda =
                   ``lambda_l2 + cat_l2`` where the leaf's parent split is
                   a sorted-subset categorical split and ``lambda_l2``
                   elsewhere (a numerical split, a one-vs-rest split, a
                   tree of one leaf).
  split gains      the children under the split's lambda, the parent under
                   ``lambda_l2`` (upstream's gain_shift).
  node_regret      a categorical column is searched as upstream's
                   FindBestThresholdCategorical searches it, on the node's
                   rows by raw value: over the categories the model's
                   ``feature_infos`` lists for the column (what the bin
                   mapper kept), every other id lumped on the right and
                   never a candidate; one-vs-rest where the column has at
                   most ``max_cat_to_onehot`` bins (kept categories + the
                   catch-all), else the categories with at least
                   ``cat_smooth`` rows sorted by G / (H + cat_smooth),
                   prefixes from both ends up to min(``max_cat_threshold``,
                   (used + 1) / 2) categories, a prefix priced only where
                   at least ``min_data_per_group`` rows came in since the
                   last priced one, the search of a direction ending where
                   the right side falls under ``min_data_in_leaf`` or
                   ``min_data_per_group`` rows. The numerical columns are
                   searched as the sibling searches them.

Against upstream (v2.2.4 feature_histogram.hpp:110-271, tree.h
``CategoricalDecision``):

  same      the candidates, their order and the two regularisers; an id
            outside the set, a negative id and a NaN go right.
  departs   like the sibling it holds a candidate to ``min_data_in_leaf``
            and not to ``min_sum_hessian_in_leaf``; it sorts equal
            G / (H + cat_smooth) by category id where upstream sorts them
            by bin; it reads ``x`` as int(x) only for x >= 0 (upstream
            casts first: -0.5 is category 0 there, and right here; the
            generator makes whole ids); and it prices in float64 from raw
            values where upstream prices float32 bin sums (the sums are
            what the check compares).
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8


# ------------------------------------------------------------ model text
def parse_trees(model_text):
    """Trees of a LightGBM model text as dicts of numpy arrays."""
    ints = ("split_feature", "left_child", "right_child", "leaf_count",
            "internal_count", "decision_type", "cat_boundaries",
            "cat_threshold")
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


def parse_kept_categories(model_text):
    """{column: ascending ids} of the categories the model's
    ``feature_infos`` lists (a categorical column's entry is its kept ids
    joined by ':'; a numerical one's is '[min:max]', a constant's
    'none')."""
    line = next(ln for ln in model_text.splitlines()
                if ln.startswith("feature_infos="))
    kept = {}
    for j, info in enumerate(line.split("=", 1)[1].split()):
        if info != "none" and not info.startswith("["):
            kept[j] = np.sort(np.array(info.split(":"), np.int64))
    return kept


# ------------------------------------------------------------ the reference
def sigmoid(s):
    return 1.0 / (1.0 + np.exp(-s))


def init_score(y):
    p = min(max(float(np.mean(y, dtype=np.float64)), 1e-15), 1 - 1e-15)
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


def in_set(v, words):
    """Whether each raw value is an id whose bit is set in ``words`` (a
    node's uint32 words, lowest ids first); a NaN, a negative value and an
    id past the last word are not."""
    ok = v >= 0                                  # a NaN compares False
    iv = np.where(ok, v, 0).astype(np.int64)
    ok &= iv < 32 * len(words)
    iv[~ok] = 0
    return ok & ((words[iv >> 5] >> (iv & 31)) & 1).astype(bool)


def node_words(tree, k):
    """The words of categorical node ``k``'s set."""
    at = int(tree["threshold"][k])
    b = tree["cat_boundaries"]
    return tree["cat_threshold"][int(b[at]):int(b[at + 1])]


def goes_left(v, tree, k):
    """Upstream's Decision of node ``k`` on an array of raw values."""
    decision_type = int(tree["decision_type"][k])
    if decision_type & 1:
        return in_set(v, node_words(tree, k))
    threshold = float(tree["threshold"][k])
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
        go_left = goes_left(v, tree, k)
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


def draw_nodes(seed, trees, per_tree):
    """The nodes whose choice of split is judged: ``per_tree`` internal
    nodes below the root of every tree, drawn from the seed, and the root
    of the last tree (the program prices a root by a pass of its own)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xA26]))
    out = []
    for i, tree in enumerate(trees):
        below = np.arange(1, tree["num_leaves"] - 1)
        picked = rng.choice(below, size=min(per_tree, len(below)),
                            replace=False)
        out.append(([0] if i == len(trees) - 1 and tree["num_leaves"] > 1
                    else []) + sorted(int(k) for k in picked))
    return out


class GridSearch:
    """Greedy split search over the reference's own candidates.

    A NUMERICAL column is cut at up to ``cells`` - 1 points, the values
    found at equally spaced ranks among its present (not missing) values
    on every k-th row; each cut point c is the threshold ``x <= c``, and
    one more candidate puts every present value left. Sums per cell in
    float64. A column with missing rows in the node has every candidate
    priced twice, the missing rows left and right, and the better kept.

    A CATEGORICAL column (``cat["kept"]``: column -> the ascending ids the
    model's feature_infos lists) is searched as the module's docstring
    says, upstream's candidates on the node's rows by raw value.

    The best split of a node of up to ``search_rows`` rows is found on all
    its rows; that of a larger node is found on every k-th of its rows and
    then priced on ALL of them, a gain the true best cannot lie below."""

    CUT_SAMPLE = 2000000     # values a column's cut points are taken from

    def __init__(self, cols, num_cols, cells, min_data, search_rows,
                 zero_as_missing, cat):
        self.cols, self.cells, self.min_data = cols, cells, min_data
        self.num_cols, self.search_rows = num_cols, search_rows
        self.zero_as_missing = zero_as_missing
        self.cat = cat
        with ThreadPoolExecutor(THREADS) as pool:
            self.cuts = list(pool.map(
                lambda j: None if j in cat["kept"] else self.cut_points(j),
                range(num_cols)))

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
        """The gain of every candidate of numerical column ``j`` on the
        rows ``idx`` (None: every row), [2, cuts + 1]: row 0 with the
        missing rows on the left, row 1 with them on the right; -inf where
        a side holds under min_data rows."""
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

    # ---------------------------------------------------- categorical
    def category_sums(self, j, idx, g, h):
        """(G, H, C) of each kept category of column ``j`` on the rows
        ``idx``; rows of any other value are in none of them."""
        x = self.cols[j] if idx is None else self.cols[j][idx]
        kept = self.cat["kept"][j]
        ok = x >= 0                                  # a NaN compares False
        iv = np.where(ok, x, -1).astype(np.int64)
        at = np.minimum(np.searchsorted(kept, iv), len(kept) - 1)
        q = np.where(ok & (kept[at] == iv), at, len(kept))
        m = len(kept) + 1
        return (np.bincount(q, g, m)[:-1], np.bincount(q, h, m)[:-1],
                np.bincount(q, minlength=m)[:-1])

    def price_set(self, sums, left, lam, g, h, l2):
        """The gain of sending the kept categories ``left`` (positions in
        the kept list) left, the children under ``lam``."""
        G, H, _ = sums
        gl, hl = G[left].sum(), H[left].sum()
        gp, hp = g.sum(), h.sum()
        return float(leaf_gain(gl, hl, lam) + leaf_gain(gp - gl, hp - hl, lam)
                     - leaf_gain(gp, hp, l2))

    def best_set(self, j, idx, g, h, l2):
        """(gain, positions going left, lambda) of categorical column
        ``j``'s best candidate on the rows ``idx``; gain -inf where it has
        none."""
        c = self.cat
        sums = G, H, C = self.category_sums(j, idx, g, h)
        cp = len(g)
        none = (-np.inf, None, l2)
        if len(C) + 1 <= c["max_cat_to_onehot"]:
            ok = np.flatnonzero((C >= self.min_data)
                                & (cp - C >= self.min_data))
            if not len(ok):
                return none
            gain, t = max((self.price_set(sums, [t], l2, g, h, l2), t)
                          for t in ok)
            return gain, [t], l2
        lam = l2 + c["cat_l2"]
        used = np.flatnonzero(C >= c["cat_smooth"])
        used = used[np.argsort(G[used] / (H[used] + c["cat_smooth"]),
                               kind="stable")]
        most = min(c["max_cat_threshold"], (len(used) + 1) // 2)
        best = none
        for seq in (used, used[::-1]):
            rows_left = group = 0
            for i in range(min(len(seq), most)):
                rows_left += C[seq[i]]
                group += C[seq[i]]
                if rows_left < self.min_data:
                    continue
                if cp - rows_left < max(self.min_data,
                                        c["min_data_per_group"]):
                    break
                if group < c["min_data_per_group"]:
                    continue
                group = 0
                gain = self.price_set(sums, seq[:i + 1], lam, g, h, l2)
                if gain > best[0]:
                    best = (gain, seq[:i + 1], lam)
        return best

    def best_gain(self, idx, g, h, l2):
        """``g``, ``h``: of the node's rows ``idx`` (None: every row)."""
        k = max(1, -(-len(g) // self.search_rows))   # 1 for an empty node
        if k == 1:
            rows, gs, hs = idx, g, h
        else:
            rows = np.arange(0, len(g), k) if idx is None else idx[::k]
            gs, hs = g[::k], h[::k]

        def one(j):
            if j in self.cat["kept"]:
                gain, left, lam = self.best_set(j, rows, gs, hs, l2)
                return gain, j, (left, lam)
            gain = self.gains(j, rows, gs, hs, l2)
            return float(gain.max()), j, int(gain.argmax())

        with ThreadPoolExecutor(THREADS) as pool:
            best, j, what = max(pool.map(one, range(self.num_cols)),
                                key=lambda r: r[:2])
        if k > 1 and best > 0:
            if j in self.cat["kept"]:
                left, lam = what
                best = self.price_set(self.category_sums(j, idx, g, h),
                                      left, lam, g, h, l2)
            else:
                best = float(self.gains(j, idx, g, h, l2).flat[what])
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


def split_lambdas(tree, l2, cat):
    """Each split's regulariser: ``l2 + cat_l2`` where it is a
    sorted-subset categorical split (a categorical column with more bins,
    kept categories + the catch-all, than ``max_cat_to_onehot``), ``l2``
    elsewhere."""
    m = tree["num_leaves"] - 1
    lam = np.full(m, float(l2))
    for k in range(m):
        if int(tree["decision_type"][k]) & 1:
            kept = cat["kept"].get(int(tree["split_feature"][k]), ())
            if len(kept) + 1 > cat["max_cat_to_onehot"]:
                lam[k] += cat["cat_l2"]
    return lam


def leaf_lambdas(tree, lam, l2):
    """Each leaf's regulariser: its parent split's."""
    out = np.full(tree["num_leaves"], float(l2))
    for k in range(tree["num_leaves"] - 1):
        for child in (int(tree["left_child"][k]), int(tree["right_child"][k])):
            if child < 0:
                out[-child - 1] = lam[k]
    return out


def follow(X, y, trees, learning_rate, l2, nodes, search, grad_cast=None,
           leaves=None):
    """Follow ``trees`` from the seed's data. Returns per-tree dicts of
    what the reference reads: node counts, leaf values, split gains, the
    worst regret over the tree's judged ``nodes`` (draw_nodes; ``search``
    holds GridSearch's cells, min_data, search_rows, zero_as_missing and
    ``cat``, the categorical columns' kept ids and parameters) and the
    order gap. ``grad_cast`` rounds gradients and hessians before they are
    summed (the lower-precision control); ``leaves`` are the trees' leaf
    of every row where the caller has routed them already."""
    n = len(y)
    cols = Columns(X)
    y64 = y.astype(np.float64)
    init = init_score(y)
    score = np.full(n, init, np.float64)
    cat = search["cat"]
    search = GridSearch(cols, X.shape[1], **search) if any(nodes) else None
    if leaves is None:
        leaves = route_all(X, trees)
    out = []
    for i, (tree, leaf) in enumerate(zip(trees, leaves)):
        p = sigmoid(score)
        g, h = p - y64, p * (1.0 - p)
        if grad_cast is not None:
            g, h = grad_cast(g), grad_cast(h)
        nl = tree["num_leaves"]
        lam = split_lambdas(tree, l2, cat)
        cnt = np.bincount(leaf, minlength=nl)
        gl = np.bincount(leaf, weights=g, minlength=nl)
        hl = np.bincount(leaf, weights=h, minlength=nl)
        with np.errstate(divide="ignore", invalid="ignore"):
            value = -gl / (hl + leaf_lambdas(tree, lam, l2)) * learning_rate
        gi, hi = node_sums(tree, gl), node_sums(tree, hl)
        ci = node_sums(tree, cnt.astype(np.float64))
        lc, rc = tree["left_child"], tree["right_child"]
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = (leaf_gain(child_sums(lc, gl, gi), child_sums(lc, hl, hi),
                              lam)
                    + leaf_gain(child_sums(rc, gl, gi),
                                child_sums(rc, hl, hi), lam)
                    - leaf_gain(gi, hi, l2))
        under, regret = leaves_under(tree), 0.0
        for k in nodes[i]:
            if k == 0:
                best = search.best_gain(None, g, h, l2)
            else:
                inside = np.zeros(nl, bool)
                inside[under[k]] = True
                idx = np.flatnonzero(inside[leaf])
                best = search.best_gain(idx, g[idx], h[idx], l2)
            if best > 0:   # -inf where no candidate leaves min_data rows
                regret = max(regret, (best - gain[k]) / best)
        out.append({"leaf_count": cnt, "internal_count": ci,
                    "leaf_value": value + (init if i == 0 else 0.0),
                    "split_gain": gain, "node_regret": regret,
                    "split_order_gap": order_gap(tree, gain)})
        score += value[leaf]
    return out


def route_all(X, trees):
    """Each tree's leaf of every row."""
    cols = Columns(X)
    with ThreadPoolExecutor(max(len(trees), 1)) as pool:
        return list(pool.map(lambda t: route(cols, t, n=len(X)), trees))


def readings(trees, ref):
    """The numbers compared, program's model against the reference's
    follow of it, each the worst over the judged trees: the worst leaf
    and the worst split of a tree, and the tree's median leaf and split
    (a small leaf carved from a large parent inherits the parent's
    float32 rounding, so the worst swings with the tree's shape and the
    median does not)."""
    r = {"count_mismatch": 0.0, "leaf_value_gap": 0.0,
         "leaf_value_gap_median": 0.0, "split_gain_gap": 0.0,
         "split_gain_gap_median": 0.0, "node_regret": 0.0,
         "split_order_gap": 0.0}

    def worst(name, value):
        r[name] = max(r[name], float(value))

    for tree, want in zip(trees, ref):
        r["count_mismatch"] += float(
            np.abs(tree["leaf_count"] - want["leaf_count"]).sum()
            + np.abs(tree["internal_count"] - want["internal_count"]).sum())
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
