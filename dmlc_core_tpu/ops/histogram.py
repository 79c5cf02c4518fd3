"""Quantile binning + gradient histograms (the XGBoost-hist core on TPU).

Design notes (TPU-first):
- binning is a one-time ``searchsorted`` per feature (vmapped, compiled once);
  bins are uint8/int32 — HBM-friendly, 4x smaller than raw floats at 256 bins;
- TWO histogram methods, chosen from the platform (``auto``, the default of
  ``GBDTParam.hist_method``; :func:`resolve_hist_method`):

  * ``"pallas"`` (a TPU): the histogram is a **matmul on the MXU** whose two
    one-hot operands are built tile by tile in VMEM and never written to HBM
    (:mod:`.hist_pallas`).  TPU scatter-adds serialise (measured: the flat
    segment_sum below is >1000x slower than the matmul on a v5e);
  * ``"scatter"`` (anywhere else): one flat ``segment_sum`` over
    ``node*F*nbins + f*nbins + bin`` ids — cache-friendly scalar scatter,
    the fastest CPU formulation, and the exact-f32 reference in tests.

  Nothing falls back from the kernel: a mesh it cannot be shard_mapped over
  raises (``hist_pallas.hist_kernel_plan``), a kernel Mosaic rejects raises.
- ONE decision per fit: :func:`hist_plan` settles the method, the mesh, the
  row padding and the kernel's blocking before anything is traced, and the
  :class:`HistPlan` it returns owns everything that depends on the method —
  the layout of the bins the histogram reads (:meth:`HistPlan.layouts`,
  made once per fit: ``[F, rows]`` int32 for the kernel, ``[rows, F]`` int32
  for ``scatter``), the per-level histogram and the leaf sums.  A caller
  (``models/gbdt.py``) hands the histogram's copy back as an opaque operand;
- a level of n nodes below the root builds n / 2 of them by summation, one
  child of every pair, and takes the siblings as parent - built
  (:meth:`HistPlan.level`, both methods): 32 node-products a depth-6 round
  (:func:`hist_built_nodes`).  :func:`grad_histogram`, the one-shot entry,
  builds every node it is asked for;
- everything is static-shape: ``num_bins``, ``num_features``, and the level's
  node count are compile-time constants, so XLA tiles the matmul/scatter
  efficiently and the whole boosting round stays inside one jit.

Under a sharded batch (rows split over the "data" mesh axis) the histogram
becomes per-shard partials + an all-reduce over ICI — exactly the
distributed-hist aggregation XGBoost does over Rabit (SURVEY.md §2.9):
GSPMD partitions the ``scatter`` segment-sum, and the kernel runs under
``shard_map`` with a ``psum`` over the data axis.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import numpy as np

from dmlc_core_tpu.utils.logging import CHECK

__all__ = ["quantile_boundaries", "apply_bins", "grad_histogram",
           "HistPlan", "hist_plan", "hist_built_nodes",
           "resolve_hist_method",
           "local_quantile_summary",
           "merged_quantile_boundaries", "distributed_quantile_boundaries"]


def resolve_hist_method(method: str, *arrays) -> str:
    """Resolve ``"auto"`` to a concrete histogram method.

    Prefers the committed platform of any input jax.Array, falling back to
    ``jax.default_backend()``: the VMEM-resident Pallas kernel on a TPU,
    scatter segment-sums (which run anywhere) on every other platform.
    Nothing is probed: on a TPU ``auto`` *means* ``pallas``, and a kernel
    Mosaic rejects raises with the compiler's message.
    """
    if method != "auto":
        return method
    import jax

    platform = None
    for a in arrays:
        devs = getattr(a, "devices", None)
        if callable(devs):
            try:
                platform = next(iter(a.devices())).platform
                break
            except Exception:
                continue
    if platform is None:
        platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "scatter"


def hist_built_nodes(max_depth: int):
    """Node slots each level of a fit builds by summation, root first: the
    root, then ONE child of every pair (:meth:`HistPlan.level`) — 1, 1, 2,
    4, 8, 16 at depth 6."""
    return [max(1, 2 ** depth // 2) for depth in range(max_depth)]


class HistPlan(NamedTuple):
    """What one fit settled about its histograms before tracing
    (:func:`hist_plan`).  Immutable and hashable: static under ``jit`` and
    part of the model's compiled-function cache keys.  Everything that
    depends on the method is a method of the plan, so a caller never asks
    which one it is."""

    method: str                       # "pallas" | "scatter"
    model_axis: Optional[str] = None  # the histogram's feature dim splits here
    mesh: Any = None                  # shard_map the kernel over it, or None
    row_multiple: int = 1             # rows a fit pads to, once
    # the kernel's shape as ``gbdt.fit.dispatch`` records it; zeros and
    # empty strings for a method that is no kernel
    # (``hist_pallas.hist_kernel_plan``)
    row_tile: int = 0
    level_node_blocks: str = ""
    feature_blocks: int = 0
    block_features: int = 0
    bin_split: str = ""
    # :func:`hist_built_nodes` of the fit, whatever the method
    built_nodes: str = ""
    # what each level's kernel call is named in the compiled program, root
    # first (``hist_pallas.hist_kernel_name``); "" for no kernel
    level_kernels: str = ""

    def blocks(self) -> dict:
        """The kernel's shape, the node slots each level builds and the
        names its calls carry in a profile, as the ``gbdt.fit.dispatch``
        span records them beside the method."""
        return {"level_node_blocks": self.level_node_blocks,
                "feature_blocks": self.feature_blocks,
                "block_features": self.block_features,
                "row_tile": self.row_tile,
                "bin_split": self.bin_split,
                "built_nodes": self.built_nodes,
                "level_kernels": self.level_kernels}

    def layouts(self, bins, pad: int = 0):
        """The two device layouts a fit keeps of one ``[rows, F]`` binned
        batch, rows padded by ``pad``: the histogram's own copy (opaque to
        the caller, handed back to :meth:`histogram`) and ``[F, rows]`` in
        the wire dtype for the per-row feature pick — rows on the minor
        (lane) axis, so a pass over it streams rows x F narrow bytes
        instead of a row-major array whose F lanes pad to 128.  The
        histogram's copy is widened int32 (v5e Mosaic lowers no sub-32-bit
        compare): ``[F, rows]`` for the kernel, a quarter or less of the
        lane-padded row-major one, and ``[rows, F]`` for ``scatter``.
        Made once per fit (once per streamed round) under ``gbdt.layout``."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("gbdt.layout"):
            bins = jnp.asarray(bins)
            if pad:
                bins = jnp.pad(bins, ((0, pad), (0, 0)))
            return self._operand(bins), bins.T

    def _operand(self, bins):
        """The histogram's copy of row-major ``bins`` (see :meth:`layouts`);
        narrow dtypes are widened so no id arithmetic wraps."""
        import jax.numpy as jnp

        if self.method == "pallas":
            bins = bins.T
        return bins if bins.dtype == jnp.int32 else bins.astype(jnp.int32)

    def histogram(self, hist_bins, node_ids, grad, hess, num_nodes: int,
                  num_bins: int, *, level=None):
        """``(G, H)`` of ``num_nodes`` node slots built by summation, each
        ``[num_nodes, F, num_bins]`` f32, from the histogram's copy of the
        bins (:meth:`layouts`); otherwise the contract of
        :func:`grad_histogram`.  A row whose id lies outside
        ``[0, num_nodes)`` adds nothing, under either method.  ``level`` is
        the tree level a fit builds here, a label the kernel's call is
        named by (``hist_pallas.hist_kernel_name``) and nothing else."""
        import jax
        import jax.numpy as jnp

        if self.method == "pallas":
            # looked up at call time: tests put a stand-in on the module
            from dmlc_core_tpu.ops import hist_pallas

            if self.mesh is not None:
                G, H = hist_pallas.grad_hist_pallas_sharded(
                    hist_bins, node_ids, grad, hess, num_nodes, num_bins,
                    self.mesh, self.model_axis, level=level)
            else:
                G, H = hist_pallas.grad_hist_pallas(
                    hist_bins, node_ids, grad, hess, num_nodes, num_bins,
                    level=level)
        else:
            B, F = hist_bins.shape
            ids = (node_ids[:, None] * (F * num_bins)
                   + jnp.arange(F, dtype=jnp.int32)[None, :] * num_bins
                   + hist_bins)                               # [B, F]
            nseg = num_nodes * F * num_bins
            # a row of no node slot goes past the last segment, which drops
            # it (a negative id would wrap)
            inside = (node_ids >= 0) & (node_ids < num_nodes)
            flat_ids = jnp.where(inside[:, None], ids, nseg).reshape(-1)
            g_flat = jnp.broadcast_to(grad[:, None], (B, F)).reshape(-1)
            h_flat = jnp.broadcast_to(hess[:, None], (B, F)).reshape(-1)
            G = jax.ops.segment_sum(g_flat, flat_ids, num_segments=nseg)
            H = jax.ops.segment_sum(h_flat, flat_ids, num_segments=nseg)
            G = G.reshape(num_nodes, F, num_bins)
            H = H.reshape(num_nodes, F, num_bins)
        return self._constrain(G), self._constrain(H)

    def _constrain(self, hist):
        """``hist [n, F, bins]`` with its feature dim split over the model
        axis, where the plan has one."""
        if self.model_axis is None:
            return hist
        import jax
        from jax.sharding import PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            hist, P(None, self.model_axis, None))

    def level(self, hist_bins, keys, grad, hess, num_bins: int,
              parent=None, built_right=None, *, level=None):
        """One level's ``(G, H)``, each ``[n, F, num_bins]`` f32 in node
        order, by sibling subtraction: ONE child of every pair is built by
        summation (:meth:`histogram`, ``n / 2`` node slots) and the other
        is ``parent - built`` — what the ``hist`` algorithm is, upstream
        and here, under both methods.

        ``parent`` is the ``(G, H)`` of the level above, each
        ``[n / 2, F, num_bins]``, and ``built_right [n / 2]`` says which
        child of each pair the rows were keyed for: ``keys [B]`` holds the
        PARENT's id of a row that sits in that child and any id outside
        ``[0, n / 2)`` (-1) of a row that sits in its sibling.  The root
        (``parent=None``) has no sibling: ``keys`` are its rows' node ids,
        all 0.  ``level`` is the tree level, the label of :meth:`histogram`.

        The subtraction and the interleave are elementwise on the built
        half's transposed result (on a v5e they add a quarter to the
        transposition's time at 2,000 features, PERF.md, PR 31); under
        ``shard_map`` the ``psum`` has carried the built half only.  A
        derived child sums the same terms as a built one in another order
        of f32 additions; a pair whose built child has no rows keeps its
        parent bit for bit."""
        import jax.numpy as jnp

        if parent is None:
            return self.histogram(hist_bins, keys, grad, hess, 1, num_bins,
                                  level=level)
        half = parent[0].shape[0]
        built = self.histogram(hist_bins, keys, grad, hess, half, num_bins,
                               level=level)
        right = built_right[:, None, None]

        def pair(above, summed):
            other = above - summed
            both = jnp.stack([jnp.where(right, other, summed),
                              jnp.where(right, summed, other)], axis=1)
            return self._constrain(both.reshape(2 * half, *summed.shape[1:]))

        return pair(parent[0], built[0]), pair(parent[1], built[1])

    def leaf_sums(self, node, g, h, num_leaves: int):
        """``(sum g, sum h)`` of every leaf, each ``[num_leaves]`` f32, for
        row leaf ids ``node``: segment-sums, or where those serialise (a
        TPU's scatter-adds) a small f32 matmul against the leaf one-hot."""
        import jax
        import jax.numpy as jnp

        if self.method != "pallas":
            return (jax.ops.segment_sum(g, node, num_segments=num_leaves),
                    jax.ops.segment_sum(h, node, num_segments=num_leaves))
        leafhot = (node[:, None] == jnp.arange(num_leaves, dtype=node.dtype)
                   ).astype(jnp.float32)                     # [B, n_leaf]
        gh = jnp.stack([g, h], axis=1)                       # [B, 2]
        sums = jax.lax.dot_general(leafhot, gh, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        return sums[:, 0], sums[:, 1]


def hist_plan(method: str, model_axis: Optional[str], num_feature: int,
              max_depth: int, num_bins: int, rows: Optional[int] = None,
              arrays=(), pads: bool = False) -> HistPlan:
    """Settle one fit's histograms from what is known before tracing:
    ``auto`` from the platform of ``arrays``
    (:func:`resolve_hist_method`), and for the kernel the ambient mesh, the
    row tile, the row padding and the blocking
    (``hist_pallas.hist_kernel_plan``, which raises where the mesh forbids
    the kernel).  ``rows`` is the row count the histogram will see, or with
    ``pads`` the count a fit has and pads to the plan's ``row_multiple``
    itself; None where it is not known yet."""
    method = resolve_hist_method(method, *arrays)
    if method != "pallas":
        return HistPlan(method, model_axis, built_nodes=",".join(
            map(str, hist_built_nodes(max_depth))))
    from dmlc_core_tpu.ops.hist_pallas import hist_kernel_plan

    return HistPlan(method, model_axis, **hist_kernel_plan(
        model_axis, num_feature, max_depth, num_bins, batch=rows,
        pads=pads))


def _strictly_increasing(bounds: np.ndarray) -> np.ndarray:
    """Make per-feature boundaries strictly increasing so searchsorted is
    stable on ties (repeated quantiles from heavy-tailed or constant
    features collapse otherwise).

    The nudge is magnitude-relative: an absolute epsilon is absorbed by
    float32 once |bound| exceeds ~1e1 (ulp(1e7) ≈ 1), which would let
    duplicate boundaries survive on large-valued features.
    """
    eps = np.float32(1e-6)
    scale = np.maximum(np.abs(bounds), np.float32(1.0))
    return np.maximum.accumulate(
        bounds + eps * scale * np.arange(bounds.shape[1], dtype=np.float32),
        axis=1)


def quantile_boundaries(sample: np.ndarray, num_bins: int) -> np.ndarray:
    """Per-feature quantile split points from a host-side sample.

    Returns boundaries [F, num_bins-1]; feature value v lands in bin
    ``searchsorted(boundaries[f], v)`` in [0, num_bins).  (The reference
    ecosystem's quantile sketch; a host numpy quantile is exact for the
    sampled rows and runs once per training job.)
    """
    sample = np.asarray(sample, dtype=np.float32)
    qs = np.linspace(0, 1, num_bins + 1)[1:-1]
    bounds = _nan_aware_quantile(sample, qs)             # [F, nb-1]
    return _strictly_increasing(bounds)


def _nan_aware_quantile(sample: np.ndarray, qs: np.ndarray) -> np.ndarray:
    """Per-feature quantiles, transposed to [F, len(qs)]; NaNs (missing
    values under GBDTParam.handle_missing) are excluded from the ranks.
    All-NaN features get zero boundaries (no real value to separate)."""
    if np.isnan(sample).any():
        import warnings

        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message="All-NaN slice")
            out = np.nanquantile(sample, qs, axis=0).T.astype(np.float32)
        return np.nan_to_num(out, nan=0.0)
    return np.quantile(sample, qs, axis=0).T.astype(np.float32)


def local_quantile_summary(sample: np.ndarray, num_points: int):
    """Fixed-size mergeable quantile summary of one data shard.

    Returns ``(points [F, num_points] float32, counts [F] float32)``: the
    shard's per-feature equi-rank quantiles plus its per-feature FINITE
    value counts.  Every point of feature f carries mass
    ``counts[f] / num_points``, which is all
    :func:`merged_quantile_boundaries` needs to take weighted quantiles of
    a union of shards — the fixed shape makes the summary allgather-able
    (every rank contributes the same [F, K] block regardless of shard
    size).

    Counts are per-feature because NaNs (missing values) carry no rank
    mass: a feature that is entirely missing on this shard contributes
    zero mass (its zero-filled points vanish in the merge) instead of K
    fabricated zeros at full shard weight.  An empty shard likewise
    returns zero points with zero counts and still participates in the
    collective without skewing the result.
    """
    sample = np.asarray(sample, dtype=np.float32)
    n, F = sample.shape
    if n == 0:
        return (np.zeros((F, num_points), np.float32),
                np.zeros((F,), np.float32))
    qs = np.linspace(0, 1, num_points)
    points = _nan_aware_quantile(sample, qs)
    counts = np.sum(np.isfinite(sample), axis=0).astype(np.float32)
    return points, counts


def merged_quantile_boundaries(points: np.ndarray, counts,
                               num_bins: int) -> np.ndarray:
    """Merge per-shard quantile summaries into one set of bin boundaries.

    Args:
      points: [W, F, K] stacked :func:`local_quantile_summary` points from
        all W shards (e.g. straight from ``collective.allgather``).
      counts: [W, F] per-shard per-feature finite counts (or [W] uniform
        per-shard row counts when no values are missing).
      num_bins: target bin count.

    Returns boundaries [F, num_bins-1], bit-identical on every rank that
    sees the same (points, counts) — which allgather guarantees — so
    data-parallel workers bin consistently without shipping raw rows.  This
    is the distributed-quantile-sketch step of XGBoost-hist (reference:
    SURVEY.md §2.9 — the hist aggregation consumer of rabit allreduce),
    done as one fixed-size allgather + a deterministic host merge: each
    point of shard w's feature f carries mass ``counts[w, f] / K`` and the
    merged boundary_j is the pooled weighted ``(j+1)/num_bins`` quantile
    per feature (inverted-CDF rule).  A feature with zero total mass (all
    shards all-missing) gets zero boundaries — there are no real values to
    separate.
    """
    points = np.asarray(points, dtype=np.float32)
    CHECK(points.ndim == 3, f"points must be [W, F, K], got {points.shape}")
    W, F, K = points.shape
    counts = np.asarray(counts, dtype=np.float64)
    if counts.ndim == 1:
        counts = np.broadcast_to(counts[:, None], (W, F))
    CHECK(counts.shape == (W, F),
          f"counts must be [W]={W} or [W, F]={(W, F)}, got {counts.shape}")
    CHECK(counts.sum() > 0, "merged_quantile_boundaries: all shards empty")
    # pooled points [F, W*K], per-point mass [F, W*K] (per-feature shard mass)
    pooled = np.swapaxes(points, 0, 1).reshape(F, W * K)
    mass = np.repeat(counts.T, K, axis=1) / K            # [F, W*K]
    order = np.argsort(pooled, axis=1, kind="stable")
    v_sorted = np.take_along_axis(pooled, order, axis=1)
    cum = np.cumsum(np.take_along_axis(mass, order, axis=1), axis=1)
    total = counts.sum(axis=0)                           # [F]
    out = np.empty((F, num_bins - 1), np.float32)
    for j in range(num_bins - 1):
        target = total * (j + 1) / num_bins              # [F]
        idx = np.minimum((cum < target[:, None]).sum(axis=1), W * K - 1)
        out[:, j] = v_sorted[np.arange(F), idx]
    out[total == 0] = 0.0
    return _strictly_increasing(out)


def distributed_quantile_boundaries(sample: np.ndarray, num_bins: int,
                                    comm=None,
                                    num_points: Optional[int] = None,
                                    count: Optional[int] = None
                                    ) -> np.ndarray:
    """Quantile bin boundaries consistent across data-parallel workers.

    Each worker summarises its local ``sample`` (:func:`local_quantile_
    summary`), allgathers the fixed-size summaries through ``comm`` (any
    object with rabit-shaped ``allgather`` — e.g. ``dmlc_core_tpu.
    collective``), and merges deterministically: all ranks return identical
    boundaries.  With ``comm=None`` (single process) this degrades to the
    plain :func:`quantile_boundaries`.

    ``num_points`` controls summary resolution (default ``8 * num_bins``,
    min 64): per-shard rank error is O(1/num_points), far below bin width.

    ``count`` overrides the shard mass this rank contributes to the merge.
    Pass the TRUE shard row count when ``sample`` is a capped subsample —
    otherwise imbalanced shards are mis-weighted (a 10M-row shard sampled
    to 100k would count the same as a full 100k shard).
    """
    if comm is None:
        return quantile_boundaries(sample, num_bins)
    K = num_points or max(64, 8 * num_bins)
    points, fc = local_quantile_summary(sample, K)       # fc: [F] finite
    n = np.asarray(sample).shape[0]
    if count is not None:
        CHECK(count >= 0, f"count must be non-negative, got {count}")
        CHECK(n > 0 or count == 0,
              f"count={count} with an empty sample contributes unsampled "
              f"mass; pass the shard's rows (or a subsample) too")
        if n > 0:
            # scale per-feature finite mass from the subsample up to the
            # shard's true size (assumes missingness rates survive sampling)
            fc = fc * (count / n)
    all_points = comm.allgather(points.astype(np.float32))   # [W, F, K]
    all_counts = comm.allgather(fc.astype(np.float32))       # [W, F]
    return merged_quantile_boundaries(all_points, all_counts, num_bins)


def apply_bins(x, boundaries, missing_bin: Optional[int] = None):
    """Bin dense features: x [B, F] float -> bins [B, F] int32 in [0, num_bins).

    jit-safe; vmapped searchsorted over the feature axis.  With
    ``missing_bin`` set, NaN entries take that reserved id (sparsity-aware
    GBDT: boundaries then cover one fewer bin, ``[F, num_bins - 2]``).
    """
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x)
    boundaries = jnp.asarray(boundaries)

    def one_feature(col, bounds):
        return jnp.searchsorted(bounds, col, side="right").astype(jnp.int32)

    ids = jax.vmap(one_feature, in_axes=(1, 0), out_axes=1)(x, boundaries)
    if missing_bin is not None:
        ids = jnp.where(jnp.isnan(x), jnp.int32(missing_bin), ids)
    return ids


def grad_histogram(bins, node_ids, grad, hess, num_nodes: int, num_bins: int,
                   model_axis: Optional[str] = None, method: str = "scatter"):
    """Per-(node, feature, bin) gradient/hessian sums of one level, planned
    and laid out on the spot (a fit plans once and keeps the layout:
    :func:`hist_plan`).

    Args:
      bins:     [B, F] binned features, row-major, int32 or a narrower wire
        dtype.
      node_ids: [B] int32 current tree-node of each row (in [0, num_nodes)).
      grad/hess: [B] float32 (pre-multiplied by instance weight; padding rows
        must carry 0 weight so they vanish from every bin).
      num_nodes, num_bins: static.
      model_axis: optional mesh axis name — when set, the histogram output is
        sharding-constrained to split the feature dim over that axis
        (tensor-parallel hist for very wide feature spaces).
      method: "scatter" (default: segment_sum, exact f32 — the reference
        formulation and the fast CPU one) | "pallas" (the VMEM kernel: bf16
        g and h on the MXU, the fast TPU one) | "auto" (resolve by
        platform).  The exact path stays the default so existing callers
        keep f32 semantics.

    Returns (G, H): each [num_nodes, F, num_bins] float32.
    """
    import jax.numpy as jnp

    bins = jnp.asarray(bins)
    B, F = bins.shape
    plan = hist_plan(method, model_axis, F, (num_nodes - 1).bit_length() + 1,
                     num_bins, rows=B, arrays=(bins, grad))
    return plan.histogram(plan._operand(bins), node_ids, grad, hess,
                          num_nodes, num_bins)
