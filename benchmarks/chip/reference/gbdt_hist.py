"""XGBoost ``hist`` boosting in plain numpy float32: the fit cells' reference.

An exact ``bincount`` gradient histogram and greedy level-wise split
finding with the same parameters the program is given (max_depth, num_bins,
eta, lambda, min_child_weight, objective).  No kernels, no bf16, no
batching; it imports nothing from ``dmlc_core_tpu``.  The objective is
found by name (``objectives/<objective>.py``): its gradient is the only
thing of it a round needs.

Split rule (XGBoost ``hist``, as the program documents it): at each level
every node scores every (feature, threshold) by
``GL^2/(HL+lam) + GR^2/(HR+lam) - GT^2/(HT+lam)``, both children need
``H >= min_child_weight``, the last bin is never a threshold, the first
maximum wins, and a node splits only if its best gain is > 0.  Rows go
right when ``bin > threshold``.  Leaves take ``-G/(H+lam) * eta``.

With ``missing=True`` (sparsity-aware split finding, XGBoost's algorithm
3) the last bin id is reserved for absent entries.  Every (feature,
threshold) is scored twice, the reserved bin's ``G, H`` on the right and
on the left, each under ``min_child_weight``; the node sends its absent
rows left only where that gain is strictly larger (right on ties); the
reserved bin is never a threshold and the last real one (``num_bins - 2``,
present against absent) is allowed; rows in the reserved bin follow the
node's direction.

A margin of ``[n, K]`` (``num_class = K > 1``) grows K trees a round from
ONE margin snapshot, tree ``k`` from column ``k`` of g and h (XGBoost
``multi:softprob``: the gradients are taken before any of the round's K
updates land); a round's tree arrays then lead with the class axis.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import objectives


def histogram(bins, node, g, h, num_nodes, num_bins):
    """Exact per-(node, feature, bin) sums of ``g`` and ``h``:
    two ``[num_nodes, F, num_bins]`` float32 arrays.  Rows whose node id is
    outside ``[0, num_nodes)`` count nowhere."""
    n, f = bins.shape
    live = (node >= 0) & (node < num_nodes)
    flat = ((node[live, None].astype(np.int64) * f + np.arange(f)) * num_bins
            + bins[live].astype(np.int64)).ravel()
    size = num_nodes * f * num_bins
    out = []
    for v in (g, h):
        w = np.repeat(v[live].astype(np.float64), f)
        out.append(np.bincount(flat, weights=w, minlength=size)
                   .reshape(num_nodes, f, num_bins).astype(np.float32))
    return out


def grad_hess(margin, label, objective, **extras):
    """The named objective's gradient and hessian, float32."""
    return objectives.load(objective).grad_hess(margin, label, **extras)


def logloss(margin, label):
    """Mean binary cross-entropy of logistic margins, float64."""
    return objectives.load("logistic").loss(margin, label)


def build_tree(bins, g, h, max_depth, num_bins, reg_lambda,
               min_child_weight, learning_rate, missing=False):
    """Grow one tree; returns ``(split_feat, split_bin, leaf_value,
    default_left, margin_delta)`` in the level-order layout (``-1`` = no
    split; ``default_left`` all False without ``missing``)."""
    n, f = bins.shape
    n_internal = 2 ** max_depth - 1
    split_feat = np.full(n_internal, -1, np.int32)
    split_bin = np.zeros(n_internal, np.int32)
    default_left = np.zeros(n_internal, bool)
    node = np.zeros(n, np.int64)
    lam = np.float32(reg_lambda)
    miss = num_bins - 1
    for depth in range(max_depth):
        n_nodes = 2 ** depth
        G, H = histogram(bins, node, g, h, n_nodes, num_bins)
        GL, HL = np.cumsum(G, -1), np.cumsum(H, -1)
        GT, HT = GL[..., -1:], HL[..., -1:]

        def score(GL, HL):
            GR, HR = GT - GL, HT - HL
            # (at the reserved bin, absent rows counted left a second time,
            # HR + lam can be 0: never valid, never a threshold)
            with np.errstate(divide="ignore", invalid="ignore"):
                gain = (GL * GL / (HL + lam) + GR * GR / (HR + lam)
                        - GT * GT / (HT + lam))
            valid = (HL >= min_child_weight) & (HR >= min_child_weight)
            return np.where(valid, gain, -np.inf)

        gain = score(GL, HL)                  # absent rows on the right
        go_left = np.zeros(gain.shape, bool)
        if missing:
            gain_left = score(GL + G[..., miss:], HL + H[..., miss:])
            go_left = gain_left > gain
            gain = np.maximum(gain, gain_left)
        gain[..., num_bins - 1] = -np.inf
        gain = gain.reshape(n_nodes, -1)
        best = np.argmax(gain, axis=1)
        best_gain = gain[np.arange(n_nodes), best]
        do_split = best_gain > 0
        sf = np.where(do_split, best // num_bins, -1).astype(np.int32)
        sb = (best % num_bins).astype(np.int32)
        dl = go_left.reshape(n_nodes, -1)[np.arange(n_nodes), best] & do_split
        lvl = n_nodes - 1 + np.arange(n_nodes)
        split_feat[lvl], split_bin[lvl], default_left[lvl] = sf, sb, dl
        nf = sf[node]
        row_bin = bins[np.arange(n), np.maximum(nf, 0)].astype(np.int64)
        go_right = (row_bin > sb[node]) & (nf >= 0)
        if missing:
            go_right &= ~((row_bin == miss) & dl[node])
        node = node * 2 + go_right
    n_leaf = 2 ** max_depth
    Gl = np.bincount(node, weights=g.astype(np.float64), minlength=n_leaf)
    Hl = np.bincount(node, weights=h.astype(np.float64), minlength=n_leaf)
    leaf = (-Gl / (Hl + reg_lambda) * learning_rate).astype(np.float32)
    return split_feat, split_bin, leaf, default_left, leaf[node]


def boost(bins, label, rounds, *, max_depth, num_bins, learning_rate,
          reg_lambda, min_child_weight, objective="logistic",
          base_score=0.0, missing=False, num_class=1, extras=None):
    """``rounds`` boosting rounds; returns ``(trees, margin)`` where trees
    is a list of ``(split_feat, split_bin, leaf_value, default_left)``, one
    entry a round.  With ``num_class = K > 1`` the margin is ``[n, K]`` and
    every array of an entry is the round's K trees stacked, ``[K, ...]``.
    ``extras``: the objective's further per-row arrays, by name."""
    gradient = objectives.load(objective).grad_hess
    n = bins.shape[0]
    margin = np.full((n,) if num_class == 1 else (n, num_class), base_score,
                     np.float32)
    label = label.astype(np.float32)

    def grow(g, h):
        return build_tree(bins, g, h, max_depth, num_bins, reg_lambda,
                          min_child_weight, learning_rate, missing)

    trees = []
    for _ in range(rounds):
        g, h = gradient(margin, label, **(extras or {}))
        if margin.ndim == 1:
            *tree, delta = grow(g, h)
        else:
            grown = [grow(g[:, k], h[:, k]) for k in range(num_class)]
            tree = [np.stack(a) for a in list(zip(*grown))[:4]]
            delta = np.stack([t[4] for t in grown], axis=1)
        trees.append(tuple(tree))
        margin = margin + delta
    return trees, margin
