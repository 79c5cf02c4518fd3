"""Score rows by walking published trees in plain numpy: the score cell's
reference.  Takes the arrays as they were published (level-order
``split_feat`` / ``split_bin`` / ``leaf_value``, quantile ``boundaries``)
and imports nothing from the code under test."""

from __future__ import annotations

import numpy as np


def bin_rows(x, boundaries):
    """``searchsorted(boundaries[f], x[:, f], side="right")`` per feature:
    the binning contract both the trainer and the server state."""
    x = np.asarray(x, np.float32)
    out = np.empty(x.shape, np.int64)
    for f in range(x.shape[1]):
        out[:, f] = np.searchsorted(boundaries[f], x[:, f], side="right")
    return out


def margins(bins, split_feat, split_bin, leaf_value, base_score=0.0,
            default_left=None, miss_id=None):
    """Sum over trees of the leaf each row reaches.  ``split_feat[t, i] ==
    -1`` means node ``i`` does not split: the row falls to child ``2i``.
    With ``default_left`` (``[T, 2**d - 1]`` bool) and ``miss_id`` a row
    whose bin at the node's feature is ``miss_id`` (absent) goes left
    where the node says so; without them it goes right like any row above
    the threshold, ``miss_id`` being the largest bin.  A stack with a
    class axis after the tree axis (``[T, K, ...]``, K trees a round) gives
    ``[n, K]``: column ``k`` is the walk of every round's tree ``k``."""
    if np.ndim(split_feat) == 3:
        return np.stack(
            [margins(bins, split_feat[:, k], split_bin[:, k],
                     leaf_value[:, k], base_score,
                     None if default_left is None else default_left[:, k],
                     miss_id)
             for k in range(np.shape(split_feat)[1])], axis=1)
    n = bins.shape[0]
    depth = int(np.log2(leaf_value.shape[1]))
    rows = np.arange(n)
    out = np.full(n, base_score, np.float64)
    for t, (sf, sb, leaf) in enumerate(zip(split_feat, split_bin,
                                           leaf_value)):
        node = np.zeros(n, np.int64)
        for d in range(depth):
            at = 2 ** d - 1 + node
            f = sf[at]
            row_bin = bins[rows, np.maximum(f, 0)]
            go_right = (row_bin > sb[at]) & (f >= 0)
            if default_left is not None and miss_id is not None:
                go_right &= ~((row_bin == miss_id) & default_left[t][at])
            node = node * 2 + go_right
        out += leaf[node].astype(np.float64)
    return out


def predict_logistic(x, boundaries, split_feat, split_bin, leaf_value,
                     base_score=0.0):
    m = margins(bin_rows(x, boundaries), split_feat, split_bin, leaf_value,
                base_score)
    return 1.0 / (1.0 + np.exp(-m))
