"""Histogram-based gradient-boosted trees, fully jit-compiled (XGBoost hist on TPU).

This is the BASELINE.json north star: the hist algorithm that XGBoost runs on
top of dmlc-core's data pipeline + Rabit allreduce, redesigned for XLA:

- features are pre-binned to int8-range ids (``ops.histogram.apply_bins``);
- a boosting round is ONE jit: for each tree level (static ``max_depth``
  python loop, unrolled by trace) compute the per-(node, feature, bin)
  gradient histogram with a single flat segment_sum, run the best-split scan
  (cumsum over bins = the "left sums"), and advance every row one level with
  pure gathers — no data-dependent control flow, no host sync;
- rounds are chained with ``lax.scan`` over stacked tree arrays so a full
  ``fit`` is one compiled program;
- under a mesh, rows shard over "data" (histograms become per-shard partials
  + ICI all-reduce, courtesy of GSPMD — the Rabit aggregation, compiled), and
  wide feature spaces can shard the histogram over "model"
  (``grad_histogram(model_axis=...)``).

Trees are stored level-order as flat arrays (a pytree — checkpointable via
bridge.checkpoint): ``split_feat``/``split_bin`` [n_internal] with -1 marking
"no split" (rows fall through to child 2*i), ``leaf_value`` [2**max_depth].
Prediction walks the static levels with gathers — O(depth) gathers per row,
batched over the whole batch.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import numpy as np

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.ops.histogram import (HistPlan, apply_bins,
                                         distributed_quantile_boundaries,
                                         hist_plan)
from dmlc_core_tpu.param import Parameter, field
from dmlc_core_tpu.utils.logging import CHECK

__all__ = ["GBDTParam", "TreeEnsemble", "GBDT"]


class GBDTParam(Parameter):
    num_boost_round = field(int, default=10, lower=1, help="number of trees")
    max_depth = field(int, default=6, lower=1, upper=14, help="tree depth")
    num_bins = field(int, default=256, lower=2, upper=1024,
                     help="feature histogram bins")
    learning_rate = field(float, default=0.3, lower=0.0, help="shrinkage eta")
    reg_lambda = field(float, default=1.0, lower=0.0, help="L2 on leaf weights")
    reg_alpha = field(float, default=0.0, lower=0.0,
                      help="L1 on leaf weights (XGBoost alpha: gradient "
                           "sums are soft-thresholded in gains and leaves)")
    scale_pos_weight = field(float, default=1.0, lower=0.0,
                             help="weight multiplier for positive rows "
                                  "(logistic class-imbalance knob)")
    min_child_weight = field(float, default=1.0, lower=0.0,
                             help="minimum hessian sum per child")
    min_split_loss = field(float, default=0.0, lower=0.0,
                           help="gamma: minimum gain to split a node")
    # XGBoost's range is (0, 1]; the inclusive field bound keeps 0 out via
    # the epsilon (subsample=0 would silently train all-empty trees)
    subsample = field(float, default=1.0, lower=1e-6, upper=1.0,
                      help="per-tree row subsampling rate")
    colsample_bytree = field(float, default=1.0, lower=1e-6, upper=1.0,
                             help="per-tree feature subsampling rate")
    colsample_bylevel = field(float, default=1.0, lower=1e-6, upper=1.0,
                              help="per-level feature subsampling rate "
                                   "(draws a fresh mask every tree depth, "
                                   "composed with colsample_bytree; a "
                                   "softmax round's K trees share the "
                                   "level draw)")
    colsample_bynode = field(float, default=1.0, lower=1e-6, upper=1.0,
                             help="per-node feature subsampling rate "
                                  "(fresh mask per (depth, node), composed "
                                  "with the tree/level draws; softmax "
                                  "rounds share it like bylevel)")
    max_delta_step = field(float, default=0.0, lower=0.0,
                           help="cap on |leaf weight| before shrinkage "
                                "(XGBoost's imbalanced-logistic stabiliser; "
                                "0 disables). Applied to leaf values AND "
                                "to split gain scoring like XGBoost; with "
                                "reg_alpha>0 AND a binding cap the gain's "
                                "alpha term is the self-consistent -2a|w| "
                                "(XGBoost's CalcGain uses +a|w| there), so "
                                "split choices can differ from XGBoost in "
                                "that corner")
    seed = field(int, default=0, help="subsampling PRNG seed")
    monotone_constraints = field(str, default="",
                                 help="per-feature monotone directions, "
                                      "XGBoost style: '(1,0,-1,...)' or "
                                      "'1,0,-1' — +1 non-decreasing, -1 "
                                      "non-increasing, 0 free; empty "
                                      "disables")
    base_score = field(float, default=0.0,
                       help="initial prediction margin (XGBoost base_score "
                            "in margin space: its default 0.5 probability "
                            "== margin 0 for logistic; for squared "
                            "objectives set e.g. the label mean). "
                            "Streaming boost_round callers must init "
                            "their margin with it themselves")
    handle_missing = field(bool, default=False,
                           help="sparsity-aware splits: NaN features take a "
                                "reserved bin and each split learns its "
                                "default direction (XGBoost semantics)")
    objective = field(str, default="logistic",
                      enum=["logistic", "squared", "softmax", "lambdarank"],
                      help="loss (lambdarank: LambdaMART over the query "
                           "groups fit_binned(group=) names)")
    lambdarank_truncation_level = field(
        int, default=30, lower=1, upper=128,
        help="objective=lambdarank: a pair counts when the better-ranked "
             "of its two rows is among its query's first this many "
             "(LightGBM lambdarank_truncation_level; at most a pair "
             "tile's rows)")
    num_class = field(int, default=1, lower=1,
                      help="classes for objective=softmax (K trees/round)")
    hist_method = field(str, default="auto",
                        enum=["auto", "pallas", "scatter"],
                        help="histogram algorithm: VMEM-resident pallas "
                             "kernel (auto on a TPU) or segment-sum scatter "
                             "(auto everywhere else)")


class TreeEnsemble(NamedTuple):
    """Stacked level-order trees: arrays lead with the tree axis [T, ...].

    Multiclass (objective=softmax) ensembles carry a class axis after the
    tree axis — [T, K, ...] — one tree per class per round (the XGBoost
    multi:softmax layout).
    """

    split_feat: Any    # [T(, K), 2**d - 1] int32, -1 = no split
    split_bin: Any     # [T(, K), 2**d - 1] int32
    leaf_value: Any    # [T(, K), 2**d] float32 (shrinkage already applied)
    default_left: Any  # [T(, K), 2**d - 1] bool: missing rows go left here
                       # (all-False without handle_missing — legacy routing)
    # split statistics for importance (XGBoost get_score analogs); None on
    # ensembles loaded from pre-stats checkpoints — routing never reads them
    split_gain: Any = None   # [T(, K), 2**d - 1] f32 gain, 0 where no split
    split_cover: Any = None  # [T(, K), 2**d - 1] f32 hessian mass at node

    @property
    def num_trees(self) -> int:
        return self.split_feat.shape[0]


def _widen_bins(bins):
    """Accept pre-binned features in the uint8/uint16 wire dtype (the
    byte-frugal device feed, ``bridge/binning.py``): widen to int32 *on
    device, inside the jit*, so the host->device transfer ships the narrow
    bytes and every downstream compare/select/gather sees exactly the
    int32 the on-device ``apply_bins`` path produces — split decisions are
    bitwise-identical by construction (tests/test_device_feed.py)."""
    import jax.numpy as jnp

    bins = jnp.asarray(bins)
    return bins if bins.dtype == jnp.int32 else bins.astype(jnp.int32)


def _feature_pick(bins_fm, feat):
    """``bins_fm[feat[r], r]`` for every row r of feature-major bins
    ``[F, rows]``, as a compare-select-sum over the leading axis (the
    widening fuses into the select).  ``feat == -1`` picks nothing: 0."""
    import jax.numpy as jnp

    fiota = jnp.arange(bins_fm.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(feat[None, :] == fiota[:, None],
                             bins_fm.astype(jnp.int32), 0), axis=0)


def _table_pick(table, node):
    """``table[node]`` for a per-node table of n entries and row ids
    ``node`` in ``[0, n)``, as n compare-selects on dense ``[rows]``
    vectors reduced over the leading axis — not the gather, which XLA
    lowers on the TPU to one-hot reductions over lanes.  Exact: one term
    of the sum is non-zero.  Ids outside ``[0, n)`` would read 0 / False
    where a gather clamps; ``_build_tree`` never makes one (padded rows
    route like any other)."""
    import jax.numpy as jnp

    hit = node[None, :] == jnp.arange(table.shape[0],
                                      dtype=node.dtype)[:, None]
    if table.dtype == jnp.bool_:
        return jnp.any(hit & table[:, None], axis=0)
    return jnp.sum(jnp.where(hit, table[:, None], 0), axis=0)


def _objective_grad(p: "GBDTParam", margin, label, rank_layout=None):
    """``(g, h)`` of ``p.objective`` at ``margin``, before the row weight:
    the ONE place every round body reaches an objective through.
    ``rank_layout`` is what ``lambdarank`` keeps of its group column
    (:func:`_rank_layout`, once a fit).  Which scope names what: the
    listwise gradient runs under ``gbdt.rank``; what a softmax BOOSTING
    round does once over its class axis under ``gbdt.softmax`` (the
    class-major ``[K, rows]`` gradient here, from ``margin[K, rows]``, and
    in :func:`_softmax_round` the margin's update and any transposition
    to or from ``[rows, K]``); the per-row gradients under
    ``gbdt.grad_hess``, which is also where every round body does its
    per-TREE work (the row weight's multiply, the tree's sampling, for a
    softmax round one class's row of the gradient)."""
    import jax
    import jax.numpy as jnp

    if p.objective == "lambdarank":
        CHECK(rank_layout is not None,
              "objective='lambdarank' takes its gradient over query "
              "groups: train it through fit_binned(group=)")
        with jax.named_scope("gbdt.rank"):
            return _lambdarank_grad_hess(margin, rank_layout,
                                         p.lambdarank_truncation_level)
    if p.objective == "softmax":
        with jax.named_scope("gbdt.softmax"):
            return _softmax_grad_hess(margin, label, p.num_class)
    with jax.named_scope("gbdt.grad_hess"):
        if p.objective == "logistic":
            pr = 1.0 / (1.0 + jnp.exp(-margin))
            return pr - label, pr * (1.0 - pr)
        return margin - label, jnp.ones_like(margin)


def _apply_pos_weight(weight, label, p):
    """scale_pos_weight: positive-class rows count spw-times harder in
    every gradient/hessian sum (XGBoost's imbalance knob; logistic only —
    other objectives have no positive class)."""
    if p.scale_pos_weight == 1.0 or p.objective != "logistic":
        return weight
    import jax.numpy as jnp

    return weight * jnp.where(label > 0.5, p.scale_pos_weight, 1.0)


def _softmax_grad_hess(margin, label, num_class: int):
    """Per-class gradients for softmax cross-entropy, class-major: margin
    ``[K, *rows]``, integer labels ``[*rows]`` -> (g, h) each
    ``[K, *rows]`` (the rows as :func:`_lane_tiles` folds them, or flat).

    Matches XGBoost's SoftmaxMultiClassObj exactly: h = max(2*p*(1-p), eps)
    — the factor 2 keeps leaf values on the same scale as the XGBoost
    baseline, and the clamp keeps -G/(H+lambda) finite at reg_lambda=0 for
    confidently-classified leaves.
    """
    import jax
    import jax.numpy as jnp

    pr = jax.nn.softmax(margin, axis=0)
    classes = jnp.arange(num_class, dtype=jnp.int32).reshape(
        (num_class,) + (1,) * label.ndim)
    onehot = (label.astype(jnp.int32)[None] == classes).astype(jnp.float32)
    return pr - onehot, jnp.maximum(2.0 * pr * (1.0 - pr), 1e-16)


# -- lambdarank: the gradient over query groups ------------------------------
#
# A row's gradient depends on every row of its query, and queries are
# skewed (1 to over a thousand rows), so nothing here is laid out by query.
# A round sorts the rows by (query, margin descending) and then works on
# that order cut into TILES of ``_RANK_TILE`` consecutive rows: a query's
# rows are consecutive, so a pair lies inside one tile, or its better-ranked
# row (one of its query's first ``k``) lies before the tile: those ``k``
# rows of the ONE query that reaches into a tile from before it are the
# tile's *carry*.  Every pair is then met once in a dense
# ``[tile rows, tile rows + k]`` block, the sums onto both ends of a pair
# are reductions of that block along one axis or the other, and what
# crosses tiles (the carry's sums, a query's totals) is a scan over the
# tiles, a few thousand entries long.  No scatter, no per-row gather.

_RANK_TILE = 128      # rows of a pair block (>= the truncation level)
_RANK_CHUNK = 512     # tiles a step of the pair loop holds at once
_RANK_SORT_BLOCK = 4096   # rows a pass of _sort_in_spans sorts together


def _rank_tiles(rows: int) -> Tuple[int, int]:
    """``(tiles, tiles a chunk)`` that hold ``rows`` rows: whole chunks."""
    tiles = -(-rows // _RANK_TILE)
    chunk = min(tiles, _RANK_CHUNK)
    return -(-tiles // chunk) * chunk, chunk


def _descending_key(x):
    """An int32 that sorts ASCENDING as float32 ``x`` sorts descending
    (0.0 and -0.0 as one): the compiler builds a sort's comparator into
    every stage of its network, and one over integers compiles several
    times faster than one over floats."""
    import jax.lax as lax
    import jax.numpy as jnp

    bits = lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, -x), jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _from_descending_key(key):
    """The float32 ``_descending_key`` was made from: the key carries it
    whole, so a sort need not move the float beside it."""
    import jax.lax as lax
    import jax.numpy as jnp

    return -lax.bitcast_convert_type(key ^ ((key >> 31) & 0x7FFFFFFF),
                                     jnp.float32)


def _sort_in_spans(keys, payload=()):
    """``keys + payload`` sorted by ``keys`` (int32, lexicographic, no two
    rows equal where their order matters), for rows that are OUT OF ORDER
    ONLY INSIDE SPANS: a query's rows stay in the query's span whatever
    their margins.  A sort of the whole array is a network of 231 stages
    at 2.3M rows, every one holding the comparator, and takes the TPU's
    compiler a minute and a half; blocks of ``_RANK_SORT_BLOCK`` rows take
    78.  So: sort every block, then the blocks shifted by half a block,
    and so on in turn until the rows are in order (checked on the device):
    a span of up to half a block lies whole inside a block of one of the
    two passes, longer spans take more passes, rows already in order (round
    0: every margin equal) none."""
    import jax.lax as lax
    import jax.numpy as jnp

    n, block = keys[0].shape[0], _RANK_SORT_BLOCK
    ops = tuple(keys) + tuple(payload)
    if n <= block:
        return lax.sort(ops, num_keys=len(keys), is_stable=False)
    blocks = -(-n // block)
    fill = blocks * block + block // 2 - n
    last = jnp.iinfo(jnp.int32).max            # the filling stays behind
    ops = tuple(jnp.pad(x, (0, fill), constant_values=last if i < len(keys)
                        else 0) for i, x in enumerate(ops))

    def out_of_order(state):
        worse = jnp.zeros((ops[0].shape[0] - 1,), bool)
        for key in reversed(state[1][:len(keys)]):
            worse = (key[:-1] > key[1:]) | ((key[:-1] == key[1:]) & worse)
        return jnp.any(worse)

    def one_pass(state):
        shifted, rows = state
        at = jnp.where(shifted, block // 2, 0)
        cut = [lax.dynamic_slice(x, (at,), (blocks * block,))
               .reshape(blocks, block) for x in rows]
        cut = lax.sort(cut, dimension=1, num_keys=len(keys), is_stable=False)
        return ~shifted, tuple(
            lax.dynamic_update_slice(x, y.reshape(-1), (at,))
            for x, y in zip(rows, cut))

    _, ops = lax.while_loop(out_of_order, one_pass, (jnp.bool_(False), ops))
    return tuple(x[:n] for x in ops)


def _linked_sums(a, link, reverse: bool = False):
    """``x[i] = a[i] + link[i] * x[i - 1]`` along axis 0 (``x[i + 1]`` with
    ``reverse``), ``link`` boolean, ``a``'s shape or its leading part: sums
    that run on while the link holds."""
    import jax.lax as lax
    import jax.numpy as jnp

    m = jnp.broadcast_to(
        link.reshape(link.shape + (1,) * (a.ndim - link.ndim)),
        a.shape).astype(a.dtype)

    def combine(first, then):
        return first[0] * then[0], then[1] + then[0] * first[1]

    return lax.associative_scan(combine, (m, a), reverse=reverse)[1]


def _query_sums(x, query):
    """The sum of ``x`` over each row's whole query, at every row.  ``x``
    and ``query`` are ``[tiles, _RANK_TILE]`` in row order, a query's rows
    consecutive.  Inside a tile rows of one query find each other by
    comparison; a query that runs over a tile's edge takes the rest from
    the tiles before and after, linked while they hold that query alone."""
    import jax.numpy as jnp

    same = query[:, :, None] == query[:, None, :]
    local = jnp.sum(jnp.where(same, x[:, None, :], 0.0), axis=-1)
    first, last = query[:, 0], query[:, -1]
    whole = first == last
    runs_on = first[1:] == last[:-1]       # tile i + 1 opens in i's last query
    none, zero = jnp.zeros((1,), bool), jnp.zeros((1,), x.dtype)
    before = _linked_sums(
        jnp.concatenate([zero, jnp.where(runs_on, local[:-1, -1], 0.0)]),
        jnp.concatenate([none, runs_on & whole[:-1]]))
    after = _linked_sums(
        jnp.concatenate([jnp.where(runs_on, local[1:, 0], 0.0), zero]),
        jnp.concatenate([runs_on & whole[1:], none]), reverse=True)
    return (local + jnp.where(query == first[:, None], before[:, None], 0.0)
            + jnp.where(query == last[:, None], after[:, None], 0.0))


def _rank_layout(label, group, k: int):
    """What ``lambdarank`` needs of the labels and the group column alone,
    once a fit: every array ``[tiles, _RANK_TILE]`` over the rows in the
    order ``_lambdarank_grad_hess`` sorts them into (a query keeps its
    span, so its start, its ranks' discounts and its ``1 / maxDCG`` stay
    where they are whatever the margins).  ``group`` holds the real rows'
    ids, a query's rows adjacent; ``label`` may be longer (the fit's row
    padding), and every row beyond ``group`` is a query of its own, as are
    the rows that fill the last tile: they form no pair."""
    import jax.lax as lax
    import jax.numpy as jnp

    CHECK(k <= _RANK_TILE, f"lambdarank_truncation_level {k} is over a "
                           f"pair tile's {_RANK_TILE} rows")
    n, B = group.shape[0], label.shape[0]
    tiles, _ = _rank_tiles(B)
    rows = tiles * _RANK_TILE
    at = jnp.arange(rows, dtype=jnp.int32)
    ids = jnp.pad(group.astype(jnp.int32), (0, rows - n))
    opens = ((ids != jnp.roll(ids, 1)) | (at >= n) | (at == 0))
    # queries numbered in row order: ascending, so a sort by (query,
    # margin) leaves every query where it is
    query = jnp.cumsum(opens.astype(jnp.int32)) - 1
    start = lax.cummax(jnp.where(opens, at, 0))
    rank = at - start
    discount = 1.0 / jnp.log2(2.0 + rank.astype(jnp.float32))
    grade = jnp.pad(label.astype(jnp.float32), (0, rows - B))
    # maxDCG: the query's grades in descending order under the same
    # discounts, the first k of them
    _, _, ideal = _sort_in_spans((query, _descending_key(grade)), (grade,))
    dcg = jnp.where(rank < k, (jnp.exp2(ideal) - 1.0) * discount, 0.0)
    shape = (tiles, _RANK_TILE)
    query = query.reshape(shape)
    max_dcg = _query_sums(dcg.reshape(shape), query)
    # the carry of tile i: the first k rows of the query its first row
    # belongs to, those of them that lie before the tile.  Where they lie
    # never changes, so what a round does to fetch them is settled here: a
    # tile hands on its own rows of rank t of its LAST query (``hands``),
    # and, where it holds that query alone, what it was handed itself
    # (``passes``); the next tile takes them if it opens in that query
    rank = rank.reshape(shape)
    hands = ((rank[:, None, :] == jnp.arange(k, dtype=jnp.int32)[:, None])
             & (query == query[:, -1:])[:, None, :])          # [tiles, k, T]
    taken = query[1:, 0] == query[:-1, -1]
    whole = query[:-1, 0] == query[:-1, -1]
    handed = jnp.any(hands, axis=-1)[:-1]
    layout = {"query": query, "grade": grade, "rank": rank,
              "discount": discount.reshape(shape),
              "inv_max_dcg": jnp.where(max_dcg > 0.0, 1.0 / max_dcg, 0.0),
              "hands": hands, "takes": taken,
              "passes": (taken & whole)[:, None] & ~handed}
    layout["carry_has"] = _rank_carry(jnp.ones(shape, jnp.float32),
                                      layout) > 0.0
    return layout


def _rank_carry(x, layout):
    """``[tiles, k]``: for every tile, ``x`` at the first ``k`` rows of the
    query that reaches into it from before, 0 where a row of that rank
    lies in the tile itself or in none (``x`` ``[tiles, _RANK_TILE]`` in
    sorted order).  A scan over the tiles, no gather: a gather of 17.7k
    slices ran as a loop of as many steps, 27 ms a round at 2.27M rows."""
    import jax.numpy as jnp

    own = jnp.sum(jnp.where(layout["hands"], x[:, None, :], 0), axis=-1)
    given = jnp.where(layout["takes"][:, None], own[:-1], 0)
    none = jnp.zeros((1,) + own.shape[1:], own.dtype)
    return _linked_sums(
        jnp.concatenate([none, given]),
        jnp.concatenate([none.astype(bool), layout["passes"]]))


def _rank_pairs(s_a, y_a, d_a, s_b, y_b, d_b, inv_b, spread_b, pair):
    """The equations of one pair, over any block of pairs ``(a, b)`` that
    broadcasts: ``a`` the better-ranked row.  Returns ``(lam as b's
    gradient takes it, w, lam)``, zero where ``pair`` is False; ``a``'s
    gradient takes the first negated."""
    import jax.numpy as jnp

    a_is_hi = y_a > y_b
    ds = jnp.where(a_is_hi, s_a - s_b, s_b - s_a)
    dn = (jnp.abs((jnp.exp2(y_a) - 1.0) - (jnp.exp2(y_b) - 1.0))
          * jnp.abs(d_a - d_b) * inv_b)
    dn = jnp.where(spread_b, dn / (0.01 + jnp.abs(ds)), dn)
    rho = 1.0 / (1.0 + jnp.exp(ds))
    lam = jnp.where(pair, rho * dn, 0.0)
    return jnp.where(a_is_hi, lam, -lam), lam * (1.0 - rho), lam


def _lambdarank_grad_hess(margin, layout, k: int):
    """LambdaMART's ``(g, h)`` as LightGBM's ``lambdarank`` computes them,
    float32, before the row weight.  For a query ``q`` with margins ``s``
    and grades ``y``: ``r`` the 0-based rank by ``s`` descending, ties by
    ascending row; ``G(y) = 2^y - 1``, ``D(r) = 1 / log2(2 + r)``; for
    every pair ``r_a < r_b``, ``r_a < k``, ``y_a != y_b``, with ``hi`` the
    larger grade: ``ds = s_hi - s_lo``, ``dN = (G_hi - G_lo) |D_hi - D_lo|
    / maxDCG_q``, divided by ``0.01 + |ds|`` where ``q``'s best and worst
    margins differ; ``rho = 1 / (1 + exp(ds))``; ``g_hi -= rho dN``,
    ``g_lo += rho dN``, both ``h += rho (1 - rho) dN``, ``S_q += 2 rho
    dN``; last every ``g`` and ``h`` of ``q`` times ``log2(1 + S_q) /
    S_q`` where ``S_q > 0``.  Every pair counts, none sampled, whatever
    the query's size (the layout: the comment above ``_RANK_TILE``)."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    B = margin.shape[0]
    query = layout["query"]
    tiles = query.shape[0]
    rows = tiles * _RANK_TILE
    _, chunk = _rank_tiles(rows)
    # rows in (query, margin descending, row ascending) order: no two
    # keys are equal, and a row moves inside its query's span alone;
    # ``row`` is what takes the sums back
    _, s, row, grade = _sort_in_spans(
        (query.reshape(-1),
         _descending_key(jnp.pad(margin, (0, rows - B))),
         jnp.arange(rows, dtype=jnp.int32)), (layout["grade"],))
    s = _from_descending_key(s)
    # a query's best and worst margins differ where any two neighbours do
    steps = (s != jnp.roll(s, 1)) & (layout["rank"].reshape(-1) > 0)
    s, grade = s.reshape(query.shape), grade.reshape(query.shape)
    spread = _query_sums(steps.reshape(query.shape).astype(jnp.float32),
                         query) > 0.0

    first = query[:, :1]
    carry_d = 1.0 / jnp.log2(2.0 + jnp.arange(k, dtype=jnp.float32))

    def block(t):
        """The pairs of ``chunk`` tiles: inside each tile ``[a, b]``, and
        its carry against its rows ``[k, b]``.  Of each of the three sums
        of :func:`_rank_pairs`: per row what it takes as the pair's ``b``
        and as its ``a``, per carry row what it takes."""
        q, r = t["query"], t["rank"]
        row_b = (t["s"][:, None, :], t["grade"][:, None, :],
                 t["discount"][:, None, :], t["inv_max_dcg"][:, None, :],
                 t["spread"][:, None, :])
        inside = ((q[:, :, None] == q[:, None, :])
                  & (r[:, :, None] < r[:, None, :]) & (r[:, :, None] < k)
                  & (t["grade"][:, :, None] != t["grade"][:, None, :]))
        own = _rank_pairs(t["s"][:, :, None], t["grade"][:, :, None],
                          t["discount"][:, :, None], *row_b, inside)
        reaches = (t["carry_has"][:, :, None]
                   & (q[:, None, :] == t["first"][:, :, None])
                   & (t["carry_grade"][:, :, None]
                      != t["grade"][:, None, :]))
        carried = _rank_pairs(t["carry_s"][:, :, None],
                              t["carry_grade"][:, :, None],
                              carry_d[None, :, None], *row_b, reaches)
        return ([jnp.sum(x, axis=1) + jnp.sum(y, axis=1)
                 for x, y in zip(own, carried)],
                [jnp.sum(x, axis=2) for x in own],
                [jnp.sum(y, axis=2) for y in carried])

    per_tile = {"query": query, "rank": layout["rank"], "s": s,
                "grade": grade, "discount": layout["discount"],
                "inv_max_dcg": layout["inv_max_dcg"], "spread": spread,
                "first": first, "carry_has": layout["carry_has"],
                "carry_s": _rank_carry(s, layout),
                "carry_grade": _rank_carry(grade, layout)}
    as_b, as_a, to_carry = jax.tree_util.tree_map(
        lambda x: x.reshape((tiles,) + x.shape[2:]),
        lax.map(block, jax.tree_util.tree_map(
            lambda x: x.reshape((tiles // chunk, chunk) + x.shape[1:]),
            per_tile)))
    # a carry row's sums, over every tile its query reaches into, go back
    # to the row itself: it lies in the tile before the first of them.
    # (Three scans, and two in _rank_carry: stacked into one each they ran
    # 1.0 ms a round SLOWER at 17,920 tiles; PERF.md section 6, PR 36.)
    runs_on = jnp.concatenate([first[1:, 0] == first[:-1, 0],
                               jnp.zeros((1,), bool)])
    opens_next = jnp.concatenate([first[1:, 0] == query[:-1, -1],
                                  jnp.zeros((1,), bool)])

    def back(x):
        x = _linked_sums(x, runs_on, reverse=True)
        x = jnp.where(opens_next[:, None], jnp.roll(x, -1, axis=0), 0.0)
        return jnp.sum(jnp.where(layout["hands"], x[:, :, None], 0.0), axis=1)

    lam_b, w_b, abs_b = as_b
    lam_a, w_a, abs_a = (x + back(y) for x, y in zip(as_a, to_carry))
    total = _query_sums(abs_a + abs_b, query)
    norm = jnp.where(total > 0.0, jnp.log2(1.0 + total) / total, 1.0)
    g, h = (lam_b - lam_a) * norm, (w_a + w_b) * norm
    _, g, h = _sort_in_spans((row,), (g.reshape(-1), h.reshape(-1)))
    return g[:B], h[:B]


def _l1_threshold(G, alpha: float):
    """XGBoost's ThresholdL1: soft-threshold the gradient sum so both the
    split gain and the leaf value see |G| shrunk by alpha (CalcWeight /
    CalcGainGivenWeight semantics).  alpha=0 is the identity."""
    if alpha == 0.0:
        return G
    import jax.numpy as jnp

    return jnp.sign(G) * jnp.maximum(jnp.abs(G) - alpha, 0.0)


def _check_softmax_labels(label, num_class: int, what: str = "labels"):
    """Class-id range check shared by every softmax entry point:
    out-of-range ids silently clamp under jit (take_along_axis / one-hot),
    so they must be rejected before tracing.  Of a label that lives on a
    device only its least and largest id cross to the host (two scalars
    of one small program), never the column; a host array is read where
    it is."""
    if np.size(label) == 0:
        return
    import jax

    if isinstance(label, jax.Array):
        lo, hi = jax.device_get(_label_range()(label))
    else:
        host = np.asarray(label)
        lo, hi = host.min(), host.max()
    CHECK(lo >= 0 and hi < num_class,
          f"softmax {what} must lie in [0, {num_class}); "
          f"got range [{lo}, {hi}]")


@functools.lru_cache(maxsize=None)
def _label_range():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda label: (jnp.min(label), jnp.max(label)))


def _parse_monotone(spec: str, num_feature: int):
    """'(1,0,-1)' / '1,0,-1' -> int32 [F] array, or None when empty/all
    zero (the zero-cost legacy path).  Empty entries are rejected — a
    dropped comma slot would silently shift every later constraint onto
    the wrong feature."""
    spec = (spec or "").strip().strip("()")
    if not spec:
        return None
    parts = spec.replace(" ", "").split(",")
    CHECK(all(v != "" for v in parts),
          f"monotone_constraints has an empty entry: {spec!r}")
    vals = [int(v) for v in parts]
    CHECK(len(vals) == num_feature,
          f"monotone_constraints has {len(vals)} entries for "
          f"{num_feature} features")
    CHECK(all(v in (-1, 0, 1) for v in vals),
          f"monotone_constraints entries must be -1/0/+1, got {vals}")
    arr = np.asarray(vals, np.int32)
    return None if not arr.any() else arr


def _build_tree(hist_bins, bins_fm, g, h, plan: HistPlan, max_depth: int,
                num_bins: int, reg_lambda: float, min_child_weight: float,
                learning_rate: float,
                min_split_loss: float = 0.0, feat_mask=None,
                missing: bool = False, reg_alpha: float = 0.0,
                monotone=None, level_mask_fn=None,
                max_delta_step: float = 0.0):
    """Grow one tree level-by-level; returns (split_feat, split_bin,
    leaf_value, default_left, split_gain, split_cover, margin_delta).
    Pure jax, shapes static in (max_depth, num_bins, F).

    ``hist_bins`` is the copy of the bins that ``plan``'s histogram reads,
    opaque here, and ``bins_fm`` the same bins as ``[F, rows]`` in their
    wire dtype (both from ``plan.layouts``): every per-row pick reduces
    over a leading axis with rows on the lanes.

    ``feat_mask`` ([F] bool, optional) disables features for this tree
    (colsample); ``min_split_loss`` is the XGBoost gamma pruning threshold.

    ``missing=True`` is sparsity-aware split finding (XGBoost's algorithm
    3): rows whose feature is missing carry the reserved bin
    ``num_bins - 1``; every candidate split is scored twice from the SAME
    cumsums — missing mass on the left vs on the right — and the better
    direction is stored per node in ``default_left``.  The histogram
    kernels are untouched: the missing bin is just the last bin.

    Below the root a level's histograms come by sibling subtraction
    (``HistPlan.level``): the rows of ONE child of every pair are summed,
    the lighter one by hessian mass at the chosen split as upstream builds
    the smaller child, and the other child is parent - built.  So the loop
    carries the level above's ``(G, H)``, and a row carries beside its node
    id the key the next histogram reads: its parent's id if it sits in the
    built child, -1 (no node slot) if in the derived one.  A node that does
    not split sends every row left: its right child is built, exactly zero,
    and its left is the parent bit for bit.

    ``monotone`` ([F] int in {-1, 0, +1}, or None) enforces monotone
    response per feature the XGBoost way: candidate splits whose child
    weights violate the direction are masked, every node carries a
    [lower, upper] weight interval, children of a constrained split split
    that interval at the clamped midpoint, and leaf weights clamp into
    their interval — together these guarantee monotonic predictions.
    (Gains are scored before the interval clamp — a mild difference from
    XGBoost's interval-aware scoring that affects split choice, never the
    monotonicity guarantee.  The ``max_delta_step`` clamp, by contrast,
    DOES enter gain scoring, via ``_score``.)

    Each phase runs under a ``jax.named_scope`` (``gbdt.hist`` /
    ``gbdt.split`` / ``gbdt.route`` / ``gbdt.leaf``; the callers add
    ``gbdt.layout`` and ``gbdt.grad_hess``), and every level's three
    phases inside a ``gbdt.level<depth>`` one, whose level the kernel's
    call is named by too (``hist_pallas.hist_kernel_name``): metadata only,
    read back per phase and per level from a ``jax.profiler`` trace's
    ``tf_op`` (docs/observability.md).
    """
    import jax
    import jax.numpy as jnp

    F, B = bins_fm.shape
    n_internal = 2 ** max_depth - 1
    split_feat = jnp.full((n_internal,), -1, dtype=jnp.int32)
    split_bin = jnp.zeros((n_internal,), dtype=jnp.int32)
    default_left = jnp.zeros((n_internal,), dtype=jnp.bool_)
    split_gain = jnp.zeros((n_internal,), dtype=jnp.float32)
    split_cover = jnp.zeros((n_internal,), dtype=jnp.float32)
    node = jnp.zeros((B,), dtype=jnp.int32)  # node id within the level
    key = node                # what the level's histogram reads of a row
    parent = built_right = None                      # the root has neither
    miss_id = num_bins - 1
    if monotone is not None:
        mono = jnp.asarray(monotone, jnp.int32)          # [F]
        # per-node weight interval, split at the midpoint on constrained
        # splits (XGBoost's bound propagation)
        node_lo = jnp.full((1,), -jnp.inf, jnp.float32)
        node_hi = jnp.full((1,), jnp.inf, jnp.float32)

    for depth in range(max_depth):
        with jax.named_scope(f"gbdt.level{depth}"):
            n_nodes = 2 ** depth
            level_off = n_nodes - 1
            with jax.named_scope("gbdt.hist"):
                # G, H: [n, F, nbins]
                G, H = plan.level(hist_bins, key, g, h, num_bins, parent,
                                  built_right, level=depth)
            with jax.named_scope("gbdt.split"):
                GL = jnp.cumsum(G, axis=-1)
                HL = jnp.cumsum(H, axis=-1)
                GT = GL[..., -1:]
                HT = HL[..., -1:]
                lam = reg_lambda

                mds = max_delta_step

                def _clamp_w(w):
                    return jnp.clip(w, -mds, mds) if mds > 0.0 else w

                def _opt_w(Gv, Hv):
                    # the (possibly mds-clamped) optimum leaf weight — the ONE
                    # definition shared by gain scoring, monotone masking, and
                    # the monotone interval midpoints, so they can never
                    # desynchronize
                    return _clamp_w(-_l1_threshold(Gv, reg_alpha) / (Hv + lam))

                def _weights(GLv, HLv):
                    return _opt_w(GLv, HLv), _opt_w(GT - GLv, HT - HLv)

                def _score(Gv, Hv):
                    # -2x the leaf objective at the (possibly clamped) optimum
                    # weight; algebraically equal to ThresholdL1(G)^2/(H+lam)
                    # when max_delta_step leaves the weight unclamped, so split
                    # choices under the cap match XGBoost's CalcWeight-clamped
                    # CalcGain rather than ignoring the cap.  Known deviation:
                    # with reg_alpha>0 AND a binding cap, the alpha term here
                    # is -2a|w| (the self-consistent -2x objective) where
                    # XGBoost's CalcGain adds +a|w| — gains, and possibly
                    # argmax splits, differ from XGBoost in that corner
                    if mds == 0.0:
                        return _l1_threshold(Gv, reg_alpha) ** 2 / (Hv + lam)
                    w = _opt_w(Gv, Hv)
                    return (-(2.0 * Gv * w + (Hv + lam) * w * w)
                            - 2.0 * reg_alpha * jnp.abs(w))

                def _gain(GLv, HLv):
                    GRv = GT - GLv
                    HRv = HT - HLv
                    gn = (_score(GLv, HLv) + _score(GRv, HRv)
                          - _score(GT, HT))                  # [n, F, nbins]
                    ok = (HLv >= min_child_weight) & (HRv >= min_child_weight)
                    if monotone is not None:
                        wl, wr = _weights(GLv, HLv)
                        c = mono[None, :, None]
                        ok = ok & ~(c * (wl - wr) > 0)   # violating splits
                    return gn, ok

                gain, valid = _gain(GL, HL)
                if missing:
                    # default-right scored above (thresholds below the missing
                    # bin exclude its mass from GL, so it lands right for free);
                    # score default-left by shifting the missing mass into the
                    # left sums
                    gain_l, valid_l = _gain(GL + G[..., miss_id:miss_id + 1],
                                            HL + H[..., miss_id:miss_id + 1])
                    gain = jnp.where(valid, gain, -jnp.inf)
                    gain_l = jnp.where(valid_l, gain_l, -jnp.inf)
                    go_left_default = gain_l > gain
                    gain = jnp.maximum(gain, gain_l)
                    valid = valid | valid_l
                # splitting on the last bin sends everything left: never valid
                # (with missing handling the last REAL threshold is num_bins -
                # 2, which separates non-missing from missing — allowed)
                valid = valid & (jnp.arange(num_bins)
                                 < num_bins - 1)[None, None, :]
                if level_mask_fn is not None:
                    # the level/node draw consumes the tree mask (nested
                    # sampling)
                    valid = valid & level_mask_fn(depth, n_nodes,
                                                  feat_mask)[:, :, None]
                elif feat_mask is not None:
                    valid = valid & feat_mask[None, :, None]
                gain = jnp.where(valid, gain, -jnp.inf)
                flat = gain.reshape(n_nodes, F * num_bins)
                best = jnp.argmax(flat, axis=-1)                 # [n]
                best_gain = jnp.take_along_axis(flat, best[:, None],
                                                axis=-1)[:, 0]
                bf = (best // num_bins).astype(jnp.int32)
                bb = (best % num_bins).astype(jnp.int32)
                do_split = best_gain > min_split_loss
                sf = jnp.where(do_split, bf, -1)
                if missing:
                    dl = jnp.take_along_axis(
                        go_left_default.reshape(n_nodes, F * num_bins),
                        best[:, None], axis=-1)[:, 0] & do_split
                else:
                    dl = jnp.zeros((n_nodes,), jnp.bool_)
                lvl = level_off + jnp.arange(n_nodes)
                split_feat = split_feat.at[lvl].set(sf)
                split_bin = split_bin.at[lvl].set(bb)
                default_left = default_left.at[lvl].set(dl)
                split_gain = split_gain.at[lvl].set(
                    jnp.where(do_split, best_gain, 0.0))
                GTn, HTn = GT[:, 0, 0], HT[:, 0, 0]
                split_cover = split_cover.at[lvl].set(
                    jnp.where(do_split, HTn, 0.0))

                def _left_at_best(sums, cums):
                    # the left child's sum at the chosen split, [n]-sized
                    # gathers: the cumsum at ``best``, plus the missing bin's
                    # mass where the node sends missing rows left
                    left = jnp.take_along_axis(
                        cums.reshape(n_nodes, F * num_bins), best[:, None],
                        axis=-1)[:, 0]
                    if missing:
                        left = left + jnp.where(dl, jnp.take_along_axis(
                            sums[..., miss_id], bf[:, None], axis=-1)[:, 0],
                            0.0)
                    return left

                # the next level builds the lighter child of every pair and
                # derives the heavier, whose error so stays at the f32 rounding
                # of sums of its own size; a node that does not split has an
                # empty right child
                HLb = _left_at_best(H, HL)
                built_right = ~do_split | (HTn - HLb < HLb)
                parent = (G, H)
                if monotone is not None:
                    # child intervals: the chosen split's child weights set the
                    # midpoint; constrained features split the node interval
                    # there
                    GLb = _left_at_best(G, GL)
                    wl = _opt_w(GLb, HLb)
                    wr = _opt_w(GTn - GLb, HTn - HLb)
                    wl = jnp.clip(wl, node_lo, node_hi)
                    wr = jnp.clip(wr, node_lo, node_hi)
                    mid = 0.5 * (wl + wr)
                    c_node = jnp.where(do_split, mono[bf], 0)    # [n]
                    # c=+1: left subtree weights <= mid <= right subtree
                    # weights
                    lo_l = node_lo
                    hi_l = jnp.where(c_node > 0, jnp.minimum(node_hi, mid),
                                     node_hi)
                    lo_r = jnp.where(c_node > 0, jnp.maximum(node_lo, mid),
                                     node_lo)
                    hi_r = node_hi
                    lo_l = jnp.where(c_node < 0, jnp.maximum(node_lo, mid),
                                     lo_l)
                    hi_r = jnp.where(c_node < 0, jnp.minimum(node_hi, mid),
                                     hi_r)
                    node_lo = jnp.stack([lo_l, lo_r], axis=1).reshape(-1)
                    node_hi = jnp.stack([hi_l, hi_r], axis=1).reshape(-1)
            with jax.named_scope("gbdt.route"):
                # advance every row one level.  Rows' split features come from
                # a per-node table of 1..2**(d-1) entries and their bin from
                # one of F columns: both are compare-select-sums over a LEADING
                # axis, rows on the lanes.  At 11M x 28 on a v5e the row-major
                # select-sum streamed the lane-padded int32 [rows, F] (512 B a
                # row) at 81% of the HBM peak, 8.5 ms a level, and the table
                # gathers took 8.5 + 11 ms a round as one-hot reductions over
                # lanes; take_along_axis was slower still (PERF.md, PR 25).
                nf = _table_pick(sf, node)                       # [B]
                row_bin = _feature_pick(bins_fm, nf)
                # one pick for the node's threshold and for which of its
                # children the next level builds (a pick of its own for the
                # flag cost 0.29 ms a level at 16.8M rows; PERF.md, PR 31)
                bin_flag = _table_pick(bb * 2 + built_right, node)
                go_right = (row_bin > (bin_flag >> 1)) & (nf >= 0)
                if missing:
                    # missing rows sit at bin num_bins-1 > any threshold, so
                    # they already go right; default-left overrides that
                    go_right = go_right & ~((row_bin == miss_id)
                                            & _table_pick(dl, node))
                key = jnp.where(go_right == ((bin_flag & 1) == 1), node, -1)
                node = node * 2 + go_right.astype(jnp.int32)

    with jax.named_scope("gbdt.leaf"):
        Gl, Hl = plan.leaf_sums(node, g, h, 2 ** max_depth)
        leaf_w = -_l1_threshold(Gl, reg_alpha) / (Hl + reg_lambda)
        if max_delta_step > 0.0:
            leaf_w = jnp.clip(leaf_w, -max_delta_step, max_delta_step)
        if monotone is not None:
            leaf_w = jnp.clip(leaf_w, node_lo, node_hi)
        leaf_value = leaf_w * learning_rate
        margin_delta = _table_pick(leaf_value, node)
    return (split_feat, split_bin, leaf_value, default_left, split_gain,
            split_cover, margin_delta)


def _tree_sampling(p: "GBDTParam", rnd, B: int, F: int, class_index=0):
    """Per-tree (row_weight, feature_mask) for subsample/colsample; both
    None at the default rates so the bench path traces unchanged.  ``rnd``
    is the (traced) round index; sampling is deterministic in
    (seed, rnd, class_index) — each of a softmax round's K trees draws its
    own subset, as XGBoost samples per tree, not per round.
    """
    import jax
    import jax.numpy as jnp

    row_w = None
    fmask = None
    if p.subsample < 1.0 or p.colsample_bytree < 1.0:
        key = jax.random.fold_in(jax.random.PRNGKey(p.seed),
                                 jnp.asarray(rnd, jnp.uint32))
        if not (isinstance(class_index, int) and class_index == 0):
            # (a softmax round's index is traced.)  Class 0 keeps the
            # round's key, as the per-row objectives' one tree does
            key = jnp.where(class_index == 0, key,
                            jax.random.fold_in(key, class_index))
        if p.subsample < 1.0:
            row_w = (jax.random.uniform(jax.random.fold_in(key, 0), (B,))
                     < p.subsample).astype(jnp.float32)
        if p.colsample_bytree < 1.0:
            u = jax.random.uniform(jax.random.fold_in(key, 1), (F,))
            fmask = u < p.colsample_bytree
            # never mask every feature: the cheapest column always stays
            fmask = fmask.at[jnp.argmin(u)].set(True)
    return row_w, fmask


def _level_mask_fn(p, rnd, F: int):
    """colsample_bylevel / colsample_bynode: fresh feature masks per tree
    depth (and per node for bynode), seeded by (seed, rnd, depth) —
    deterministic, trace-safe, never empty (each node's cheapest column
    always stays).  Returns ``mask(depth, n_nodes) -> [n_nodes, F]`` bool,
    or None when both rates are 1.0.  A softmax round's K trees share the
    draw (the grow closure has no class identity)."""
    if p.colsample_bylevel >= 1.0 and p.colsample_bynode >= 1.0:
        return None
    import jax
    import jax.numpy as jnp

    base = jax.random.fold_in(jax.random.PRNGKey(p.seed),
                              jnp.asarray(rnd, jnp.uint32))
    base = jax.random.fold_in(base, 7)   # domain-separate from row/col draws

    def mask(depth: int, n_nodes: int, tree_mask=None):
        # NESTED draws (XGBoost semantics): bylevel samples from the
        # bytree survivors, bynode from the bylevel survivors — independent
        # draws could intersect to an empty per-node feature set, silently
        # truncating the node into a leaf
        key = jax.random.fold_in(base, depth)
        allowed = (tree_mask if tree_mask is not None
                   else jnp.ones((F,), bool))
        if p.colsample_bylevel < 1.0:
            u = jnp.where(allowed, jax.random.uniform(key, (F,)), jnp.inf)
            allowed = ((u < p.colsample_bylevel) & allowed
                       ).at[jnp.argmin(u)].set(True)
        m = jnp.broadcast_to(allowed[None, :], (n_nodes, F))
        if p.colsample_bynode < 1.0:
            un = jnp.where(allowed[None, :],
                           jax.random.uniform(jax.random.fold_in(key, 1),
                                              (n_nodes, F)), jnp.inf)
            m = ((un < p.colsample_bynode) & m
                 ).at[jnp.arange(n_nodes), jnp.argmin(un, axis=1)].set(True)
        return m

    return mask


def _row_sampling(p, rnd, n_rows: int, B: int, F: int, class_index=0):
    """Per-tree sampling drawn over the UNPADDED row count, then padded to
    the working batch: the subsample draw must not depend on kernel row
    padding, or padded and unpadded entry points (fit_binned vs
    boost_round) would select different row subsets for the same data.
    Padding rows carry weight 0 regardless; the pad is shape-only."""
    import jax.numpy as jnp

    row_w, fmask = _tree_sampling(p, rnd, n_rows, F,
                                  class_index=class_index)
    if row_w is not None and B != n_rows:
        row_w = jnp.pad(row_w, (0, B - n_rows))
    return row_w, fmask


_LANE_TILE = 8 * 128     # elements of one (8 sublanes, 128 lanes) tile


def _lane_tiles(x):
    """``[..., B]`` -> ``[..., R, 128]``: the last axis padded with zeros
    to whole ``(8, 128)`` tiles and folded onto the lanes.  The chip tiles
    an array's two minor axes, so in ``[K, B]`` a tile holds 8 classes'
    rows and reading or writing ONE class's row moves all 8 (0.53 ms a
    tree for the margin's row at 23 x 4,898,816, where a contiguous row
    is 0.07: my chip run, PR 40); in ``[K, R, 128]`` a class's rows are
    tiles of their own."""
    import jax.numpy as jnp

    pad = -x.shape[-1] % _LANE_TILE
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (-1, 128))


def _class_major(margin):
    """The API's ``[rows, K]`` margins as a softmax round works on them:
    class-major and lane-tiled, ``[K, R, 128]`` (:func:`_lane_tiles`)."""
    import jax

    with jax.named_scope("gbdt.softmax"):
        return _lane_tiles(margin.T)


def _rows_by_classes(margin, n_rows: int):
    """:func:`_class_major`'s inverse: the first ``n_rows`` rows of
    ``[K, R, 128]`` margins as the API's ``[n_rows, K]``."""
    import jax

    with jax.named_scope("gbdt.softmax"):
        return margin.reshape(margin.shape[0], -1)[:, :n_rows].T


def _softmax_round(p, bins, margin, label, weight, rnd, grow,
                   num_feature: int, n_rows=None):
    """One multiclass boosting round over class-major margins
    ``[K, R, 128]`` (:func:`_class_major`): K trees from one margin
    snapshot (XGBoost multi:softmax — gradients evaluated before any of
    the round's K updates land), each tree drawing its own row/feature
    subset.  ``grow`` is the caller's _build_tree closure, ``bins`` what it
    reads, ``label`` and ``weight`` the ``[B]`` rows the trees see.

    A class's tree is ONE traced body, scanned over the class axis: the
    compiled round holds one tree's kernels whatever K is.  The carry is
    the margin, whose class k tree k's leaves land in; nothing reads it
    before the round is over (``g_all`` / ``h_all`` are the snapshot's).
    Returns the margin and the round's trees stacked ``[K, ...]``."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    K, B = margin.shape[0], label.shape[0]
    n_rows = B if n_rows is None else n_rows
    g_all, h_all = _objective_grad(p, margin, _lane_tiles(label))

    def tiles(x, k):
        return lax.dynamic_index_in_dim(x, k, 0, keepdims=False)

    def rows(x, k):
        return tiles(x, k).reshape(-1)[:B]

    def class_tree(margin, k):
        with jax.named_scope("gbdt.grad_hess"):
            row_w, fmask = _row_sampling(p, rnd, n_rows, B, num_feature,
                                         class_index=k)
            w = weight if row_w is None else weight * row_w
            gk, hk = rows(g_all, k) * w, rows(h_all, k) * w
        *tree, delta = grow(bins, gk, hk, rnd, fmask)
        with jax.named_scope("gbdt.softmax"):
            margin = lax.dynamic_update_index_in_dim(
                margin, tiles(margin, k) + _lane_tiles(delta), k, 0)
        return margin, tuple(tree)

    return lax.scan(class_tree, margin, jnp.arange(K, dtype=jnp.int32))


def _route_tree(split_feat, split_bin, default_left, bins,
                max_depth: int, miss_id: int = -1):
    """Leaf slot of every row in one tree (static-depth gathers).

    ``miss_id`` >= 0 enables sparsity-aware routing: rows whose split
    feature carries that bin follow the node's learned default direction
    instead of the threshold compare.
    """
    import jax.numpy as jnp

    B, F = bins.shape
    node = jnp.zeros((B,), dtype=jnp.int32)
    fiota = jnp.arange(F, dtype=jnp.int32)
    for depth in range(max_depth):
        level_off = 2 ** depth - 1
        sf = split_feat[level_off + node]
        sb = split_bin[level_off + node]
        # select-sum, not take_along_axis (the gather lowering is slower on
        # the TPU); still row-major, unlike _build_tree's picks: no measured
        # path runs this at more than a serving batch (PERF.md section 7)
        row_bin = jnp.sum(jnp.where(sf[:, None] == fiota[None, :], bins, 0),
                          axis=1)
        go_right = (row_bin > sb) & (sf >= 0)
        if miss_id >= 0:
            dl = default_left[level_off + node]
            go_right = go_right & ~((row_bin == miss_id) & dl)
        node = node * 2 + go_right.astype(jnp.int32)
    return node


def _predict_tree(split_feat, split_bin, leaf_value, default_left, bins,
                  max_depth: int, miss_id: int = -1):
    """Route every row down one tree and read its leaf value."""
    return leaf_value[_route_tree(split_feat, split_bin, default_left, bins,
                                  max_depth, miss_id)]


def _per_tree(fn, arrays, multiclass: bool):
    """Apply a per-tree function over one round's arrays, stacking the K
    class trees on axis 1 for softmax ensembles — the single definition of
    the multiclass tree layout used by predict / staged losses / leaves."""
    import jax.numpy as jnp

    if multiclass:
        K = arrays[0].shape[0]
        return jnp.stack([fn(*(a[k] for a in arrays)) for k in range(K)],
                         axis=1)
    return fn(*arrays)


class GBDT:
    """Histogram gradient-boosted trees over binned dense features."""

    def __init__(self, param: GBDTParam, num_feature: int,
                 model_axis: Optional[str] = None):
        CHECK(param.objective != "softmax" or param.num_class >= 2,
              "objective=softmax needs num_class >= 2")
        CHECK(param.scale_pos_weight == 1.0 or param.objective == "logistic",
              f"scale_pos_weight={param.scale_pos_weight} only applies to "
              f"objective=logistic (got {param.objective!r}); it would "
              f"silently do nothing here")
        self._monotone = _parse_monotone(param.monotone_constraints,
                                         num_feature)
        self.param = param
        self.num_feature = num_feature
        self.model_axis = model_axis
        self.boundaries: Optional[np.ndarray] = None  # [F, num_bins-1]

    # -- binning --------------------------------------------------------------
    def make_bins(self, sample: np.ndarray, comm=None,
                  count: Optional[int] = None) -> np.ndarray:
        """Fit quantile boundaries from a host sample; returns them.

        ``comm`` (rabit-shaped, e.g. ``dmlc_core_tpu.collective``) makes the
        boundaries consistent across data-parallel workers via the merged
        quantile summary (:func:`..ops.histogram.distributed_quantile_
        boundaries`) — every rank must call with its own shard's sample.
        Without it, each worker bins on its local sample only, which forks
        split semantics across shards.  When ``sample`` is a capped
        subsample of the shard, pass the shard's true row count as
        ``count`` so imbalanced shards merge with their real mass.
        """
        CHECK(sample.shape[1] == self.num_feature, "sample feature dim mismatch")
        # sparsity-aware mode reserves the last bin id for missing values:
        # finite values quantile-bin into [0, num_bins - 2]
        eff_bins = (self.param.num_bins - 1 if self.param.handle_missing
                    else self.param.num_bins)
        # safe publication, not a race: the continuous trainer fits edges
        # once on its ingest thread and only then publishes the ensemble
        # under its lock; the publish clock cannot reach a boundaries read
        # until it observes that ensemble under the same lock
        # dmlclint: disable=race-unlocked-shared-write
        self.boundaries = distributed_quantile_boundaries(
            sample, eff_bins, comm=comm, count=count)
        return self.boundaries

    def set_boundaries(self, boundaries: np.ndarray) -> None:
        """Install externally computed quantile boundaries — e.g. a
        streaming :class:`~dmlc_core_tpu.bridge.binning.HostBinner`'s
        (``model.set_boundaries(binner.boundaries)``) — instead of
        :meth:`make_bins`' sample fit.  The shape contract is the same:
        ``[num_feature, eff_bins - 1]`` where the sparsity-aware mode
        reserves the last bin id for missing values."""
        boundaries = np.asarray(boundaries, dtype=np.float32)
        eff_bins = (self.param.num_bins - 1 if self.param.handle_missing
                    else self.param.num_bins)
        CHECK(boundaries.shape == (self.num_feature, eff_bins - 1),
              f"boundaries shape {boundaries.shape} != "
              f"{(self.num_feature, eff_bins - 1)} (num_bins="
              f"{self.param.num_bins}, handle_missing="
              f"{self.param.handle_missing})")
        self.boundaries = boundaries

    def bin_features(self, x):
        CHECK(self.boundaries is not None, "call make_bins first")
        miss = (self.param.num_bins - 1 if self.param.handle_missing
                else None)
        return apply_bins(x, self.boundaries, missing_bin=miss)

    # -- compiled round/predict ----------------------------------------------
    def _plan(self, method: str, *arrays, rows: Optional[int] = None,
              pads: bool = False) -> HistPlan:
        """The histogram plan (``ops.histogram.hist_plan``) of this model by
        ``method`` under the ambient mesh; ``arrays`` decide ``auto``,
        ``rows`` is the row count the histogram sees where nothing pads,
        or the count a caller that ``pads`` starts from."""
        p = self.param
        return hist_plan(method, self.model_axis, self.num_feature,
                         p.max_depth, p.num_bins, rows=rows, arrays=arrays,
                         pads=pads)

    def _method(self, *arrays) -> str:
        return self._plan(self.param.hist_method, *arrays).method

    def _fit_plan(self, bins) -> HistPlan:
        """The plan of a compiled fit over ``bins``: the fit pads rows to
        the plan's multiple before the histogram sees them."""
        return self._plan(self.param.hist_method, bins, rows=bins.shape[0],
                          pads=True)

    def _fit_method(self, bins) -> str:
        """The hist method a compiled fit over ``bins`` runs."""
        return self._fit_plan(bins).method

    def _hist_blocks(self, method: str) -> dict:
        """The kernel shape a fit by ``method`` runs, as the
        ``gbdt.fit.dispatch`` span records it (``HistPlan.blocks``)."""
        return self._plan(method).blocks()

    @functools.lru_cache(maxsize=None)
    def _round_fn(self, plan: HistPlan):
        import jax

        p = self.param

        def one_round(margin, bins, label, weight, rnd):
            B, F = bins.shape
            bins, bins_fm = plan.layouts(bins)

            def grow(bins_, g, h, rnd_, fmask):
                return _build_tree(
                    bins_, bins_fm, g, h, plan, p.max_depth, p.num_bins,
                    p.reg_lambda, p.min_child_weight, p.learning_rate,
                    min_split_loss=p.min_split_loss, feat_mask=fmask,
                    missing=p.handle_missing, reg_alpha=p.reg_alpha,
                    monotone=self._monotone,
                    level_mask_fn=_level_mask_fn(p, rnd_, F),
                    max_delta_step=p.max_delta_step)

            if p.objective == "softmax":
                margin, trees = _softmax_round(
                    p, bins, _class_major(margin), label, weight, rnd,
                    grow, F)
                return _rows_by_classes(margin, B), trees
            g, h = _objective_grad(p, margin, label)
            with jax.named_scope("gbdt.grad_hess"):
                row_w, fmask = _tree_sampling(p, rnd, B, F)
                if row_w is not None:
                    weight = weight * row_w
                g, h = g * weight, h * weight
            sf, sb, lv, dl, sg, sc, delta = grow(bins, g, h, rnd, fmask)
            with jax.named_scope("gbdt.grad_hess"):
                margin = margin + delta
            return margin, (sf, sb, lv, dl, sg, sc)

        return jax.jit(one_round)

    def _fit_fn(self, num_rounds: int, method: str = "scatter"):
        """The compiled fit by a NAMED method, planned here under the
        ambient mesh: how a caller with no rows in hand (a test, a
        benchmark's ``.lower``) reaches the program ``fit_binned`` runs."""
        return self._build_fit(num_rounds, self._plan(method),
                               with_eval=False)

    @functools.lru_cache(maxsize=None)
    def _build_fit(self, num_rounds: int, plan: HistPlan, with_eval: bool,
                   eval_metric: str = "loss"):
        """One jitted scan-fit builder serving both entry points — the
        training body (padding, sampling, grow) must never fork between
        the plain and eval-tracked fits.  ``with_eval`` adds per-round
        eval-margin accumulation and train/eval losses: the whole
        eval-tracked fit is ONE compiled program (the round-by-round host
        loop costs ~a round-trip per round; early stopping becomes a host
        post-pass over the losses)."""
        import jax
        import jax.lax as lax

        p = self.param
        d = p.max_depth
        miss_id = p.num_bins - 1 if p.handle_missing else -1

        def fit(bins, label, weight, ev_bins=None, ev_label=None,
                group=None):
            import jax.numpy as jnp

            n_rows, F = bins.shape
            # pad rows to the plan's multiple (the kernel's tile) ONCE per
            # fit: padded rows carry weight 0, so they vanish from every
            # histogram, and per-call padding inside the kernel then no-ops
            pad = -n_rows % plan.row_multiple
            with jax.named_scope("gbdt.layout"):
                if ev_bins is not None:
                    ev_bins = _widen_bins(ev_bins)
                if pad:
                    label = jnp.pad(label, (0, pad))
                    weight = jnp.pad(weight, (0, pad))
                # (the padded rows are queries of their own: no pair)
                rank_layout = (None if group is None else _rank_layout(
                    label, group, p.lambdarank_truncation_level))
            bins, bins_fm = plan.layouts(bins, pad)
            B = n_rows + pad
            weight = _apply_pos_weight(weight, label, p)
            K = p.num_class if p.objective == "softmax" else 1

            def grow(bins_, g, h, rnd, fmask):
                return _build_tree(
                    bins_, bins_fm, g, h, plan, p.max_depth, p.num_bins,
                    p.reg_lambda, p.min_child_weight, p.learning_rate,
                    min_split_loss=p.min_split_loss, feat_mask=fmask,
                    missing=p.handle_missing, reg_alpha=p.reg_alpha,
                    monotone=self._monotone,
                    level_mask_fn=_level_mask_fn(p, rnd, F),
                    max_delta_step=p.max_delta_step)

            def round_step(margin, rnd):
                if K == 1:
                    g, h = _objective_grad(p, margin, label, rank_layout)
                    with jax.named_scope("gbdt.grad_hess"):
                        row_w, fmask = _row_sampling(p, rnd, n_rows, B, F)
                        w = weight if row_w is None else weight * row_w
                        g, h = g * w, h * w
                    sf, sb, lv, dl, sg, sc, delta = grow(bins, g, h, rnd,
                                                         fmask)
                    with jax.named_scope("gbdt.grad_hess"):
                        margin = margin + delta
                    return margin, (sf, sb, lv, dl, sg, sc)
                return _softmax_round(p, bins, margin, label, weight, rnd,
                                      grow, F, n_rows=n_rows)

            # a softmax fit's margin is class-major (_class_major) until
            # it leaves the program as the API's [n_rows, K]
            margin0 = jnp.full((B,), p.base_score, jnp.float32)
            if K > 1:
                margin0 = _lane_tiles(jnp.broadcast_to(margin0, (K, B)))
            rounds = jnp.arange(num_rounds, dtype=jnp.uint32)

            def real_rows(margin):
                return (margin[:n_rows] if K == 1
                        else _rows_by_classes(margin, n_rows))

            if not with_eval:
                margin, trees = lax.scan(round_step, margin0, rounds)
                return TreeEnsemble(*trees), real_rows(margin)

            def eval_body(carry, rnd):
                margin, ev_margin = carry
                margin, trees = round_step(margin, rnd)
                sf, sb, lv, dl = trees[:4]
                if K == 1:
                    ev_delta = _predict_tree(sf, sb, lv, dl, ev_bins, d,
                                             miss_id)
                else:
                    ev_delta = jnp.stack(
                        [_predict_tree(sf[k], sb[k], lv[k], dl[k], ev_bins,
                                       d, miss_id) for k in range(K)],
                        axis=1)
                ev_margin = ev_margin + ev_delta
                # losses on the REAL rows (padded rows carry weight 0 but
                # _logloss is unweighted)
                tr_loss = _logloss(real_rows(margin), label[:n_rows],
                                   p.objective)
                ev_loss = _eval_metric_fn(eval_metric,
                                          p.objective)(ev_margin, ev_label)
                return (margin, ev_margin), (trees, tr_loss, ev_loss)

            ev0 = jnp.full((ev_bins.shape[0],) if K == 1
                           else (ev_bins.shape[0], K), p.base_score,
                           jnp.float32)
            (margin, _), (trees, trl, evl) = lax.scan(
                eval_body, (margin0, ev0), rounds)
            return TreeEnsemble(*trees), real_rows(margin), trl, evl

        return jax.jit(fit)

    @functools.lru_cache(maxsize=None)
    def _predict_fn(self):
        import jax
        import jax.lax as lax
        import jax.numpy as jnp

        d = self.param.max_depth
        miss_id = (self.param.num_bins - 1 if self.param.handle_missing
                   else -1)

        def predict(ensemble: TreeEnsemble, bins):
            bins = _widen_bins(bins)
            B = bins.shape[0]
            multiclass = ensemble.split_feat.ndim == 3

            def body(acc, tree):
                delta = _per_tree(
                    lambda sf, sb, lv, dl: _predict_tree(sf, sb, lv, dl,
                                                         bins, d, miss_id),
                    tree, multiclass)
                return acc + delta, None

            shape = ((B, ensemble.split_feat.shape[1]) if multiclass
                     else (B,))
            out, _ = lax.scan(body,
                              jnp.full(shape, self.param.base_score,
                                       jnp.float32),
                              (ensemble.split_feat, ensemble.split_bin,
                               ensemble.leaf_value, ensemble.default_left))
            return out

        return jax.jit(predict)

    # -- public API ------------------------------------------------------------
    def fit_binned(self, bins, label, weight=None, group=None
                   ) -> Tuple[TreeEnsemble, Any]:
        """Train on pre-binned features; returns (ensemble, final margin).

        ``group`` (``objective="lambdarank"`` only, and required there) is
        a per-row int32 query id, the rows of a query adjacent as a
        ``qid:`` file has them; the device reads it, the host never does."""
        import jax.numpy as jnp

        p = self.param
        # the host's part of a fit: argument staging and the asynchronous
        # dispatch of the compiled program (it does not wait for the device,
        # but for the two scalars of a softmax fit's label check)
        K = p.num_class if p.objective == "softmax" else 1
        with telemetry.span("gbdt.fit.dispatch", rounds=p.num_boost_round,
                            objective=p.objective, num_class=p.num_class,
                            trees_per_round=K) as sp:
            CHECK((group is not None) == (p.objective == "lambdarank"),
                  f"fit_binned(group=) is objective='lambdarank''s per-row "
                  f"query id: it needs one, and no other objective takes "
                  f"one (objective={p.objective!r}, group "
                  f"{'given' if group is not None else 'missing'})")
            if K > 1:
                _check_softmax_labels(label, K)
            weight = (jnp.ones(bins.shape[0], jnp.float32)
                      if weight is None else jnp.asarray(weight))
            bins = jnp.asarray(bins)
            plan = self._fit_plan(bins)
            sp.set(method=plan.method, **plan.blocks())
            more = {}
            if group is not None:
                CHECK(group.shape == (bins.shape[0],),
                      f"group has shape {group.shape}, the rows are "
                      f"{bins.shape[0]}")
                sp.set(truncation_level=p.lambdarank_truncation_level)
                more["group"] = jnp.asarray(group, jnp.int32)
            return self._build_fit(p.num_boost_round, plan,
                                   with_eval=False)(
                bins, jnp.asarray(label, jnp.float32), weight, **more)

    def _refuse_lambdarank(self, entry: str) -> None:
        CHECK(self.param.objective != "lambdarank",
              f"{entry} takes no group column: objective='lambdarank' "
              f"trains through fit_binned(group=) alone")

    def boost_round(self, margin, bins, label, weight,
                    round_index: Optional[int] = None):
        """One boosting round (the unit train step for streaming/bench).

        ``round_index`` seeds the per-tree subsample/colsample draw (traced
        scalar: varying it does not recompile).  It is REQUIRED when
        sampling is enabled — otherwise every streamed round would silently
        draw the identical row/feature subset.

        Takes no ``group``: ``objective="lambdarank"`` is refused here by
        name (its once-a-fit query layout lives in ``fit_binned``).
        """
        import jax.numpy as jnp

        self._refuse_lambdarank("boost_round")
        if round_index is None:
            CHECK(self.param.subsample >= 1.0
                  and self.param.colsample_bytree >= 1.0
                  and self.param.colsample_bylevel >= 1.0
                  and self.param.colsample_bynode >= 1.0,
                  "boost_round needs round_index= when subsample/"
                  "colsample_by* are enabled (each tree must draw fresh "
                  "subsets)")
            round_index = 0
        weight = _apply_pos_weight(jnp.asarray(weight),
                                   jnp.asarray(label), self.param)
        # no padding here: the histogram sees these rows as they are
        plan = self._plan(self.param.hist_method, bins, margin,
                          rows=bins.shape[0])
        return self._round_fn(plan)(
            margin, bins, label, weight,
            jnp.asarray(round_index, jnp.uint32))

    def append_rounds(self, ensemble: Optional[TreeEnsemble], bins, label,
                      weight=None, *, num_rounds: int = 1,
                      margin=None, start_round: Optional[int] = None
                      ) -> Tuple[TreeEnsemble, Any]:
        """Append ``num_rounds`` boosting rounds trained on fresh (binned)
        data — the warm-start step of the continuous training ring
        (docs/training.md).  Returns ``(extended ensemble, final margin)``.

        The margin is seeded from the existing ensemble's own predictions
        on ``bins`` (pass ``margin`` to chain calls over the same batch
        without re-predicting).  The bin boundaries are NOT refit: the
        restored edges stay frozen, so the serving-side uint8 wire stays
        bitwise identical across refreshes.  ``start_round`` seeds the
        per-tree subsample/colsample draw — it defaults to
        ``ensemble.num_trees`` so appended trees continue the fresh-fit
        draw sequence instead of repeating it.

        ``ensemble=None`` starts a new ensemble from the base margin (the
        trainer's cold start: same sequence a fresh streaming fit runs).

        Takes no ``group``: ``objective="lambdarank"`` is refused by name.
        """
        import jax.numpy as jnp

        self._refuse_lambdarank("append_rounds")
        CHECK(num_rounds >= 1, "append_rounds needs num_rounds >= 1")
        bins = jnp.asarray(bins)
        label = jnp.asarray(label, jnp.float32)
        weight = (jnp.ones(bins.shape[0], jnp.float32)
                  if weight is None else jnp.asarray(weight))
        K = (self.param.num_class if self.param.objective == "softmax"
             else 1)
        if margin is None:
            if ensemble is None:
                shape = (bins.shape[0], K) if K > 1 else (bins.shape[0],)
                margin = jnp.full(shape, self.param.base_score, jnp.float32)
            else:
                margin = self.predict_margin(ensemble, bins)
        if start_round is None:
            start_round = 0 if ensemble is None else ensemble.num_trees
        new = []
        for r in range(num_rounds):
            margin, tree = self.boost_round(margin, bins, label, weight,
                                            round_index=start_round + r)
            new.append(tree)

        def stack(i):
            return np.stack([np.asarray(t[i]) for t in new], axis=0)

        def cat(old, i, dtype=None):
            fresh = stack(i)
            if dtype is not None:
                fresh = fresh.astype(dtype)
            if old is None:      # ensemble=None: the fresh trees ARE it
                return fresh
            old = np.asarray(old)
            return np.concatenate([old, fresh.astype(old.dtype)], axis=0)

        if ensemble is None:
            ensemble = TreeEnsemble(None, None, None, None, None, None)
        # pre-stats ensembles (old checkpoints) carry split_gain/cover =
        # None: keep them None — mixing absent and present stats would
        # fork the checkpoint schema mid-stream
        has_stats = (ensemble.split_feat is None
                     or ensemble.split_gain is not None)
        return TreeEnsemble(
            cat(ensemble.split_feat, 0),
            cat(ensemble.split_bin, 1),
            cat(ensemble.leaf_value, 2),
            cat(ensemble.default_left, 3, dtype=bool),
            cat(ensemble.split_gain, 4) if has_stats else None,
            cat(ensemble.split_cover, 5) if has_stats else None,
        ), margin

    def predict_margin(self, ensemble: TreeEnsemble, bins):
        return self._predict_fn()(ensemble, bins)

    def predict(self, ensemble: TreeEnsemble, bins):
        import jax
        import jax.numpy as jnp

        margin = self.predict_margin(ensemble, bins)
        if self.param.objective == "logistic":
            return 1.0 / (1.0 + jnp.exp(-margin))
        if self.param.objective == "softmax":
            return jax.nn.softmax(margin, axis=1)     # [B, K] probabilities
        return margin

    def predict_class(self, ensemble: TreeEnsemble, bins):
        """Hard class labels: argmax over classes (softmax) or the 0.5
        threshold (logistic); int32 [B]."""
        import jax.numpy as jnp

        CHECK(self.param.objective in ("logistic", "softmax"),
              "predict_class needs a classification objective")
        margin = self.predict_margin(ensemble, bins)
        if self.param.objective == "softmax":
            return jnp.argmax(margin, axis=1).astype(jnp.int32)
        return (margin > 0).astype(jnp.int32)

    # -- training with eval / early stopping ----------------------------------
    @functools.lru_cache(maxsize=None)
    def _tree_margin_fn(self):
        import jax

        d = self.param.max_depth
        miss_id = (self.param.num_bins - 1 if self.param.handle_missing
                   else -1)

        def one_tree(sf, sb, lv, dl, bins):
            return _predict_tree(sf, sb, lv, dl, _widen_bins(bins), d,
                                 miss_id)

        return jax.jit(one_tree)

    def fit_with_eval(self, bins, label, eval_bins=None, eval_label=None,
                      weight=None, early_stopping_rounds: int = 0,
                      compiled: bool = True, eval_metric: str = "loss"):
        """Boosting with validation loss tracking and early stopping.

        Returns (ensemble, history) where history is a list of per-round dicts
        (train margin loss and, when an eval set is given, eval loss).  With
        ``early_stopping_rounds`` > 0, stops when eval loss hasn't improved
        for that many rounds and truncates the ensemble to the best round.

        ``compiled=True`` (default, needs an eval set) runs the WHOLE
        eval-tracked fit as one jit — per-round losses come back as arrays
        and the sequential stopping rule is applied on the host afterwards,
        giving bit-identical results to the round-by-round loop at scan-fit
        speed (rounds past the stopping point are computed then discarded:
        on accelerators the flops are cheaper than per-round host syncs).
        ``compiled=False`` keeps the host-driven loop (debugging, or when
        per-round side effects are wanted).

        Takes no ``group`` and has no ranking metric:
        ``objective="lambdarank"`` is refused by name.
        """
        import jax.numpy as jnp

        self._refuse_lambdarank("fit_with_eval")
        K = (self.param.num_class if self.param.objective == "softmax"
             else 1)
        if K > 1:
            _check_softmax_labels(label, K)
            if eval_label is not None:
                _check_softmax_labels(eval_label, K, what="eval labels")
        weight = (jnp.ones(bins.shape[0], jnp.float32)
                  if weight is None else jnp.asarray(weight))
        bins = jnp.asarray(bins)
        label = jnp.asarray(label, jnp.float32)
        if compiled and eval_bins is not None:
            return self._fit_with_eval_compiled(
                bins, label, jnp.asarray(eval_bins),
                jnp.asarray(eval_label, jnp.float32), weight,
                early_stopping_rounds, eval_metric)
        mshape = (bins.shape[0],) if K == 1 else (bins.shape[0], K)
        margin = jnp.full(mshape, self.param.base_score, jnp.float32)
        eval_margin = None
        if eval_bins is not None:
            eval_bins = jnp.asarray(eval_bins)
            eval_label = jnp.asarray(eval_label, jnp.float32)
            eshape = ((eval_bins.shape[0],) if K == 1
                      else (eval_bins.shape[0], K))
            eval_margin = jnp.full(eshape, self.param.base_score,
                                   jnp.float32)
        trees = []
        history = []
        stopper = _EarlyStop(early_stopping_rounds)
        metric_fn = _eval_metric_fn(eval_metric, self.param.objective)
        tree_margin = self._tree_margin_fn()
        for r in range(self.param.num_boost_round):
            margin, (sf, sb, lv, dl, sg, sc) = self.boost_round(
                margin, bins, label, weight, round_index=r)
            trees.append((sf, sb, lv, dl, sg, sc))
            entry = {"round": r,
                     "train_loss": float(_logloss(margin, label,
                                                  self.param.objective))}
            if eval_margin is not None:
                if K == 1:
                    delta = tree_margin(sf, sb, lv, dl, eval_bins)
                else:
                    # softmax rounds carry K trees: [K, ...] arrays
                    delta = jnp.stack(
                        [tree_margin(sf[k], sb[k], lv[k], dl[k], eval_bins)
                         for k in range(K)], axis=1)
                eval_margin = eval_margin + delta
                eval_loss = float(metric_fn(eval_margin, eval_label))
                entry["eval_loss"] = eval_loss
                if stopper.update(r, eval_loss):
                    trees = trees[:stopper.best_round + 1]
                    history.append(entry)
                    break
            history.append(entry)
        stacked = [jnp.stack([t[i] for t in trees]) for i in range(6)]
        return TreeEnsemble(*stacked), history

    def _fit_with_eval_compiled(self, bins, label, eval_bins, eval_label,
                                weight, early_stopping_rounds: int,
                                eval_metric: str = "loss"):
        """One-jit eval-tracked fit + host-side sequential stopping rule
        (see :meth:`fit_with_eval`); returns identical (ensemble, history)
        to the round-by-round loop."""
        R = self.param.num_boost_round
        ens, _, trl, evl = self._build_fit(
            R, self._fit_plan(bins), with_eval=True,
            eval_metric=eval_metric)(
            bins, label, weight, eval_bins, eval_label)
        trl = np.asarray(trl)
        evl = np.asarray(evl)
        history = []
        stopper = _EarlyStop(early_stopping_rounds)
        stop_after = R
        for r in range(R):
            history.append({"round": r, "train_loss": float(trl[r]),
                            "eval_loss": float(evl[r])})
            if stopper.update(r, float(evl[r])):
                stop_after = stopper.best_round + 1
                break
        if stop_after < R:
            ens = TreeEnsemble(*(None if a is None
                                 else np.asarray(a)[:stop_after]
                                 for a in ens))
        return ens, history

    @functools.lru_cache(maxsize=None)
    def _staged_losses_fn(self, metric: str = "loss"):
        import jax
        import jax.lax as lax
        import jax.numpy as jnp

        p = self.param
        d = p.max_depth
        miss_id = p.num_bins - 1 if p.handle_missing else -1
        K = p.num_class if p.objective == "softmax" else 1

        def staged(ensemble, bins, label):
            bins = _widen_bins(bins)
            B = bins.shape[0]

            def body(margin, tree):
                delta = _per_tree(
                    lambda sf, sb, lv, dl: _predict_tree(sf, sb, lv, dl,
                                                         bins, d, miss_id),
                    tree, K > 1)
                margin = margin + delta
                return margin, _eval_metric_fn(metric, p.objective)(margin,
                                                                    label)

            margin0 = jnp.full((B,) if K == 1 else (B, K), p.base_score,
                               jnp.float32)
            _, losses = lax.scan(body, margin0,
                                 (ensemble.split_feat, ensemble.split_bin,
                                  ensemble.leaf_value,
                                  ensemble.default_left))
            return losses

        return jax.jit(staged)

    @functools.lru_cache(maxsize=None)
    def _predict_leaf_fn(self):
        import jax
        import jax.lax as lax
        import jax.numpy as jnp

        d = self.param.max_depth
        miss_id = (self.param.num_bins - 1 if self.param.handle_missing
                   else -1)

        def leaves(ensemble, bins):
            bins = _widen_bins(bins)
            multiclass = ensemble.split_feat.ndim == 3

            def body(_, tree):
                out = _per_tree(
                    lambda sf, sb, dl: _route_tree(sf, sb, dl, bins, d,
                                                   miss_id),
                    tree, multiclass)
                return 0, out

            _, ids = lax.scan(body, 0,
                              (ensemble.split_feat, ensemble.split_bin,
                               ensemble.default_left))
            # scan stacks on axis 0 ([T, B(, K)]); XGBoost's pred_leaf is
            # row-major [B, T(, K)]
            return jnp.moveaxis(ids, 0, 1)

        return jax.jit(leaves)

    def predict_leaf(self, ensemble: TreeEnsemble, bins) -> np.ndarray:
        """Leaf index of every row in every tree (XGBoost pred_leaf):
        int32 [B, T] (or [B, T, K] for softmax), ids in [0, 2**max_depth).
        The standard input for leaf-embedding feature engineering."""
        import jax.numpy as jnp

        return np.asarray(self._predict_leaf_fn()(ensemble,
                                                  jnp.asarray(bins)))

    def staged_losses(self, ensemble: TreeEnsemble, bins, label,
                      metric: str = "loss") -> np.ndarray:
        """Per-round cumulative metric of the ensemble on any dataset —
        the learning curve, post-hoc, as one compiled scan over the tree
        axis.  ``metric``: loss (objective's own) | error | rmse | mae.
        [num_trees] f32."""
        import jax.numpy as jnp

        if self.param.objective == "softmax":
            _check_softmax_labels(label, self.param.num_class)
        return np.asarray(self._staged_losses_fn(metric)(
            ensemble, jnp.asarray(bins), jnp.asarray(label, jnp.float32)))

    # -- introspection / persistence ------------------------------------------
    def feature_importance(self, ensemble: TreeEnsemble,
                           kind: str = "weight") -> np.ndarray:
        """Per-feature importance (the XGBoost importance_type set):
        'weight' = split count, 'gain'/'total_gain' = mean/summed split
        gain, 'cover'/'total_cover' = mean/summed hessian mass at splits.
        Gain/cover need the split statistics recorded at fit time (absent
        on ensembles loaded from pre-stats checkpoints)."""
        kinds = ("weight", "gain", "total_gain", "cover", "total_cover")
        CHECK(kind in kinds, f"importance kind {kind!r} not in {kinds}")
        sf = np.asarray(ensemble.split_feat).reshape(-1)
        mask = sf >= 0
        counts = np.bincount(sf[mask], minlength=self.num_feature)
        if kind == "weight":
            return counts.astype(np.float64)
        stat = (ensemble.split_gain if "gain" in kind
                else ensemble.split_cover)
        CHECK(stat is not None,
              f"{kind} importance needs split statistics; this ensemble "
              f"was loaded from a checkpoint without them — refit to get "
              f"them")
        stat = np.asarray(stat).reshape(-1)
        totals = np.bincount(sf[mask], weights=stat[mask],
                             minlength=self.num_feature).astype(np.float64)
        if kind.startswith("total_"):
            return totals
        return np.divide(totals, counts, out=np.zeros_like(totals),
                         where=counts > 0)

    def dump_trees(self, ensemble: TreeEnsemble,
                   feature_names=None) -> str:
        """Human-readable text dump of every tree (XGBoost get_dump
        style): internal nodes show the split feature, the REAL threshold
        value (bin id mapped back through the binning boundaries; routing
        is strict — rows with value < threshold go left, ties go right,
        matching apply_bins' side='right' searchsorted), the
        missing-row default direction, and the recorded gain/cover; leaves
        show their values.  No-split nodes collapse into their left
        subtree, matching the routing semantics."""
        CHECK(self.boundaries is not None,
              "dump_trees needs the binning boundaries; call make_bins or "
              "load_model first")
        sf_all = np.asarray(ensemble.split_feat)
        sb_all = np.asarray(ensemble.split_bin)
        lv_all = np.asarray(ensemble.leaf_value)
        dl_all = np.asarray(ensemble.default_left)
        sg_all = (None if ensemble.split_gain is None
                  else np.asarray(ensemble.split_gain))
        sc_all = (None if ensemble.split_cover is None
                  else np.asarray(ensemble.split_cover))
        multiclass = sf_all.ndim == 3
        lines = []

        def one_tree(sf, sb, lv, dl, sg, sc, title):
            lines.append(f"booster[{title}]:")
            d = self.param.max_depth

            def walk(node, depth, indent):
                if depth < d:
                    i = 2 ** depth - 1 + node    # flat level-order id
                    if sf[i] >= 0:
                        f = int(sf[i])
                        b = int(sb[i])
                        bounds = self.boundaries[f]
                        thr = (float(bounds[b]) if b < len(bounds)
                               else float("inf"))
                        name = (feature_names[f]
                                if feature_names is not None else f"f{f}")
                        miss = "yes" if (dl is not None and dl[i]) else "no"
                        extra = ""
                        if sg is not None:
                            extra = (f",gain={sg[i]:.6g}"
                                     f",cover={sc[i]:.6g}")
                        lines.append(f"{indent}{i}:[{name}<{thr:.6g}] "
                                     f"missing_left={miss}{extra}")
                        walk(node * 2, depth + 1, indent + "  ")
                        walk(node * 2 + 1, depth + 1, indent + "  ")
                        return
                # leaf or collapsed no-split subtree: rows fall through
                # left to the leaf slot
                leaf = node
                for _ in range(depth, d):
                    leaf = leaf * 2
                lines.append(f"{indent}leaf={lv[leaf]:.6g}")

            walk(0, 0, "  ")

        for t in range(ensemble.num_trees):
            if multiclass:
                for k in range(sf_all.shape[1]):
                    one_tree(sf_all[t, k], sb_all[t, k], lv_all[t, k],
                             dl_all[t, k],
                             None if sg_all is None else sg_all[t, k],
                             None if sc_all is None else sc_all[t, k],
                             f"{t}.class{k}")
            else:
                one_tree(sf_all[t], sb_all[t], lv_all[t], dl_all[t],
                         None if sg_all is None else sg_all[t],
                         None if sc_all is None else sc_all[t], str(t))
        return "\n".join(lines) + "\n"

    def save_model(self, uri: str, ensemble: TreeEnsemble,
                   extra: Optional[dict] = None) -> None:
        """Persist the model + binning boundaries to any URI.

        ``extra`` adds caller-owned numpy leaves to the payload (e.g. the
        sklearn facade's class labels); keys must not clash with the core
        schema.
        """
        from dmlc_core_tpu.bridge.checkpoint import save_checkpoint

        save_checkpoint(uri, self._model_payload(ensemble, extra))

    def _model_payload(self, ensemble: TreeEnsemble,
                       extra: Optional[dict] = None) -> dict:
        """The checkpoint pytree ``save_model`` writes (trees + binning
        boundaries + routing contract), as a dict — the single schema both
        the URI writer and :meth:`serving_state` build from."""
        CHECK(self.boundaries is not None, "model has no bin boundaries")
        payload = {
            "split_feat": np.asarray(ensemble.split_feat),
            "split_bin": np.asarray(ensemble.split_bin),
            "leaf_value": np.asarray(ensemble.leaf_value),
            "default_left": np.asarray(ensemble.default_left),
            "boundaries": np.asarray(self.boundaries),
            # binning contract: loading into a param with a different
            # missing-mode would silently mis-bin NaNs and ignore the
            # learned default directions — record it so load can refuse
            "handle_missing": np.array([int(self.param.handle_missing)]),
            # predict-time contract: _predict_fn adds the loader's
            # base_score, so a mismatch silently shifts every margin
            "base_score": np.array([self.param.base_score], np.float32),
        }
        # omit absent stats (ensembles loaded from pre-stats checkpoints):
        # np.asarray(None) would write an object-dtype leaf that can never
        # be loaded back
        if ensemble.split_gain is not None:
            payload["split_gain"] = np.asarray(ensemble.split_gain)
        if ensemble.split_cover is not None:
            payload["split_cover"] = np.asarray(ensemble.split_cover)
        for k, v in (extra or {}).items():
            CHECK(k not in payload, f"extra key {k!r} clashes with the "
                                    f"model schema")
            arr = np.asarray(v)
            # object arrays serialize as raw pointers and can never load
            # back (e.g. pandas .to_numpy() labels); reject at save time
            CHECK(arr.dtype != object,
                  f"extra key {k!r} has object dtype; convert to a "
                  f"numeric or fixed-width string array first")
            payload[k] = arr
        return payload

    def load_model(self, uri: str) -> TreeEnsemble:
        from dmlc_core_tpu.bridge.checkpoint import load_checkpoint

        return self.load_model_dict(load_checkpoint(uri))

    def load_model_dict(self, flat: dict) -> TreeEnsemble:
        """Restore from an already-loaded checkpoint dict — callers that
        read extra payload keys themselves (the sklearn facade) avoid a
        second full fetch of the URI (and the old/new-mix race a re-read
        of a concurrently replaced remote object would open)."""
        # keys are jax.tree_util.keystr paths; save_model writes a flat dict,
        # so each key is exactly "['<name>']" — match it exactly (a substring
        # match would alias e.g. 'split_feat' with any future key containing
        # that text).  default=... marks keys older checkpoints lack.
        _REQUIRED = object()

        def get(name, default=_REQUIRED):
            key = f"['{name}']"
            if key not in flat:
                CHECK(default is not _REQUIRED,
                      f"checkpoint is missing required key {name!r}")
                return default
            return flat[key]

        self.boundaries = np.asarray(get("boundaries"), dtype=np.float32)
        sf = get("split_feat")
        # models saved before sparsity-aware splits have no default_left /
        # handle_missing keys: all-False + non-missing reproduces their
        # exact routing
        dl = get("default_left", default=None)
        dl = (np.asarray(dl).astype(bool) if dl is not None
              else np.zeros(np.asarray(sf).shape, dtype=bool))
        hm = get("handle_missing", default=None)
        saved_hm = bool(hm[0]) if hm is not None else False
        CHECK(saved_hm == self.param.handle_missing,
              f"model was saved with handle_missing={saved_hm} but this "
              f"GBDT has handle_missing={self.param.handle_missing}; the "
              f"binning and routing contracts differ — construct the "
              f"loader with the matching GBDTParam")
        bs = get("base_score", default=None)
        saved_bs = float(bs[0]) if bs is not None else 0.0
        CHECK(abs(saved_bs - self.param.base_score) < 1e-9,
              f"model was saved with base_score={saved_bs} but this GBDT "
              f"has base_score={self.param.base_score}; predictions would "
              f"silently shift — construct the loader with the matching "
              f"GBDTParam")
        sg = get("split_gain", default=None)
        sc = get("split_cover", default=None)
        return TreeEnsemble(sf, get("split_bin"), get("leaf_value"), dl,
                            None if sg is None else np.asarray(sg),
                            None if sc is None else np.asarray(sc))

    def serving_state(self, ensemble: TreeEnsemble,
                      extra: Optional[dict] = None) -> dict:
        """Self-describing checkpoint pytree for the model-lifecycle path
        (docs/serving.md): the :meth:`save_model` payload plus a
        ``serve_meta`` leaf recording everything a loader needs to rebuild
        this GBDT *without* knowing its params up front — num_feature,
        num_bins, max_depth, objective, num_class.  The binner edges
        (``set_boundaries`` contract) ride the same blob, so a swapped-in
        model always serves through the exact bins it trained on.

        Feed this to :class:`~dmlc_core_tpu.bridge.checkpoint.
        CheckpointManager`.save and restore with :meth:`from_serving_state`.
        ``extra`` adds caller-owned leaves on top (the continuous trainer's
        ingest cursor rides the same atomic blob as the trees it trained);
        unknown keys are ignored by every loader.
        """
        merged = {
            _SERVE_META_KEY: np.array(
                [_SERVE_SCHEMA, self.num_feature, self.param.num_bins,
                 self.param.max_depth,
                 _OBJECTIVE_CODES[self.param.objective],
                 self.param.num_class],
                np.int64)}
        for k, v in (extra or {}).items():
            CHECK(k != _SERVE_META_KEY, "extra must not override serve_meta")
            merged[k] = v
        return self._model_payload(ensemble, merged)

    @classmethod
    def from_serving_state(cls, flat: dict) -> Tuple["GBDT", TreeEnsemble]:
        """Rebuild (GBDT, ensemble) from a flat :func:`~dmlc_core_tpu.
        bridge.checkpoint.load_checkpoint` dict written by
        :meth:`serving_state` — boundaries installed, predictions
        bitwise-equal to the saver's (round-trip asserted in
        tests/test_lifecycle.py)."""
        meta = flat.get(f"['{_SERVE_META_KEY}']")
        CHECK(meta is not None,
              "checkpoint has no serve_meta leaf — not a serving_state "
              "blob (train-side save_model checkpoints need their "
              "GBDTParam known to the loader)")
        meta = np.asarray(meta).reshape(-1)
        CHECK(meta.shape[0] == 6 and int(meta[0]) == _SERVE_SCHEMA,
              f"unsupported serve_meta schema {meta!r}")
        _, num_feature, num_bins, max_depth, obj_code, num_class = (
            int(v) for v in meta)
        CHECK(obj_code in _OBJECTIVE_FROM_CODE,
              f"serve_meta names unknown objective code {obj_code}")
        hm = flat.get("['handle_missing']")
        bs = flat.get("['base_score']")
        split_feat = flat.get("['split_feat']")
        CHECK(split_feat is not None, "checkpoint is missing split_feat")
        param = GBDTParam(
            objective=_OBJECTIVE_FROM_CODE[obj_code],
            num_bins=num_bins, max_depth=max_depth, num_class=num_class,
            num_boost_round=max(1, int(np.asarray(split_feat).shape[0])),
            handle_missing=bool(hm[0]) if hm is not None else False,
            base_score=float(bs[0]) if bs is not None else 0.0)
        gbdt = cls(param, num_feature)
        return gbdt, gbdt.load_model_dict(flat)

    @classmethod
    def resume(cls, flat: dict,
               param: Optional[GBDTParam] = None
               ) -> Tuple["GBDT", TreeEnsemble]:
        """Warm-start restore for continuous training: rebuild
        ``(GBDT, ensemble)`` from a :meth:`serving_state` checkpoint with
        the binner edges frozen from the restored state, ready for
        :meth:`append_rounds` against fresh data.

        ``serve_meta`` records only the structural contract (bins, depth,
        objective, classes) — not training hyperparameters like
        learning_rate or regularisation.  Pass ``param`` to supply those
        for the appended rounds; its structural fields must match the
        checkpoint (they define the routing + binning contract the uint8
        serving wire depends on — the whole point of resume over refit is
        that the wire stays bitwise skew-free).
        """
        gbdt, ensemble = cls.from_serving_state(flat)
        if param is None:
            return gbdt, ensemble
        for f in ("objective", "num_bins", "max_depth", "num_class"):
            CHECK(getattr(param, f) == getattr(gbdt.param, f),
                  f"resume param {f}={getattr(param, f)!r} != checkpoint "
                  f"{f}={getattr(gbdt.param, f)!r}; the structural "
                  f"contract is frozen by the serving checkpoint")
        # handle_missing/base_score mismatches are refused inside
        # load_model_dict (the binning/margin contracts)
        out = cls(param, gbdt.num_feature)
        return out, out.load_model_dict(flat)


# serving_state schema: bump when the serve_meta layout changes
_SERVE_SCHEMA = 1
_SERVE_META_KEY = "serve_meta"
_OBJECTIVE_CODES = {"logistic": 0, "squared": 1, "softmax": 2,
                    "lambdarank": 3}
_OBJECTIVE_FROM_CODE = {v: k for k, v in _OBJECTIVE_CODES.items()}


class _EarlyStop:
    """The sequential stopping rule shared by the host loop and the
    compiled post-pass: improvement = loss drop > 1e-9; stop once
    ``patience`` rounds pass without one.  One implementation — the
    compiled path's bit-identical-history guarantee depends on it."""

    def __init__(self, patience: int):
        self.patience = patience
        self.best_round = -1
        self.best_loss = float("inf")

    def update(self, r: int, loss: float) -> bool:
        """Record round r's eval loss; True = stop after this round."""
        if loss < self.best_loss - 1e-9:
            self.best_loss, self.best_round = loss, r
            return False
        return bool(self.patience) and r - self.best_round >= self.patience


def _eval_metric_fn(metric: str, objective: str):
    """In-graph eval metric for fit_with_eval: 'loss' = the objective's
    own loss (logloss/mlogloss/MSE), 'error' = classification error rate
    (0.5 threshold / argmax), 'rmse' / 'mae' = regression errors.  All
    are minimized by early stopping."""
    import jax.numpy as jnp

    CHECK(objective != "lambdarank",
          "objective='lambdarank' has no in-graph metric (a ranking "
          "metric needs the group column)")
    if metric == "loss":
        return lambda m, y: _logloss(m, y, objective)
    if metric == "error":
        CHECK(objective in ("logistic", "softmax"),
              f"eval_metric='error' needs a classification objective, "
              f"got {objective!r}")
        if objective == "softmax":
            return lambda m, y: jnp.mean(
                (jnp.argmax(m, axis=1) != y.astype(jnp.int32)).astype(
                    jnp.float32))
        return lambda m, y: jnp.mean(((m > 0) != (y > 0.5)).astype(
            jnp.float32))
    if metric in ("rmse", "mae"):
        CHECK(objective == "squared",
              f"eval_metric={metric!r} compares margins to targets "
              f"directly — only meaningful for objective='squared', got "
              f"{objective!r} (classification margins are log-odds)")
        if metric == "rmse":
            return lambda m, y: jnp.sqrt(jnp.mean((m - y) ** 2))
        return lambda m, y: jnp.mean(jnp.abs(m - y))
    CHECK(False, f"unknown eval_metric {metric!r}; "
                 f"use loss|error|rmse|mae")


def _logloss(margin, label, objective: str):
    import jax
    import jax.numpy as jnp

    if objective == "logistic":
        return jnp.mean(jnp.logaddexp(0.0, margin) - label * margin)
    if objective == "softmax":
        # mlogloss: mean cross-entropy of the true class
        logp = jax.nn.log_softmax(margin, axis=1)
        ids = label.astype(jnp.int32)
        return -jnp.mean(jnp.take_along_axis(logp, ids[:, None],
                                             axis=1)[:, 0])
    return jnp.mean((margin - label) ** 2)
