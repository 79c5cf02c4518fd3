"""Quantile binning + gradient histograms (the XGBoost-hist core on TPU).

Design notes (TPU-first):
- binning is a one-time ``searchsorted`` per feature (vmapped, compiled once);
  bins are uint8/int32 — HBM-friendly, 4x smaller than raw floats at 256 bins;
- TWO histogram algorithms, chosen per backend:

  * ``"onehot"`` (TPU): the histogram is a **matmul on the MXU**.
    ``G[n,f,b] = sum_i nodehot[i,n] * g_i * binhot[i,f,b]`` — contract the
    row axis with ``dot_general``:  ``[2n, B] @ [B, F*nbins]``.  The bin
    one-hot depends only on the (static) binned features, so a full ``fit``
    materialises it ONCE in bf16 and every level of every round is a pure
    matmul read — systolic-array work instead of scatter.  TPU scatter-adds
    serialise (measured: the flat segment_sum below is >1000x slower than
    this on v5e); the one-hot matmul is the idiomatic recast.
  * ``"scatter"`` (CPU): one flat ``segment_sum`` over
    ``node*F*nbins + f*nbins + bin`` ids — cache-friendly scalar scatter,
    the fastest CPU formulation (and the exact-f32 reference in tests).

- everything is static-shape: ``num_bins``, ``num_features``, and the level's
  node count are compile-time constants, so XLA tiles the matmul/scatter
  efficiently and the whole boosting round stays inside one jit.

Under a sharded batch (rows split over the "data" mesh axis) GSPMD turns
either formulation into per-shard partial histograms + an all-reduce over
ICI — exactly the distributed-hist aggregation XGBoost does over Rabit
(SURVEY.md §2.9), but compiler-scheduled (the contracted row axis of the
dot_general is the sharded one, so the psum falls out of SPMD partitioning).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from dmlc_core_tpu.utils.logging import CHECK

__all__ = ["quantile_boundaries", "apply_bins", "grad_histogram",
           "bin_onehot", "resolve_hist_method", "local_quantile_summary",
           "merged_quantile_boundaries", "distributed_quantile_boundaries"]


def resolve_hist_method(method: str, *arrays) -> str:
    """Resolve ``"auto"`` to a concrete histogram algorithm.

    Prefers the committed platform of any input jax.Array, falling back to
    ``jax.default_backend()``: the VMEM-resident Pallas kernel on TPU,
    scatter segment-sums on CPU, the plain one-hot matmul anywhere else.
    Nothing is probed: on a TPU ``auto`` *means* ``pallas``, and a kernel
    Mosaic rejects raises with the compiler's message instead of quietly
    training through the HBM-bound ``onehot`` path.
    """
    if method == "pallas_fused":
        # the name of a retired variant (W built in the kernel, which the one
        # kernel now does at every level): still accepted, runs ``pallas``
        return "pallas"
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
    return {"cpu": "scatter", "tpu": "pallas"}.get(platform, "onehot")


def bin_onehot(bins, num_bins: int, dtype=None):
    """One-hot encode binned features: [B, F] int -> [B, F*num_bins].

    This is the matmul RHS of the one-hot histogram.  It depends only on the
    binned features, so callers training many rounds materialise it once
    (bf16: 0/1 exactly representable) and amortise across every level/round.
    """
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.bfloat16
    bins = jnp.asarray(bins).astype(jnp.int32)  # narrow dtypes must not wrap
    B, F = bins.shape
    iota = jnp.arange(num_bins, dtype=jnp.int32)
    return (bins[:, :, None] == iota).astype(dtype).reshape(B, F * num_bins)


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
                   model_axis: Optional[str] = None, method: str = "scatter",
                   onehot=None, feature_major: bool = False):
    """Per-(node, feature, bin) gradient/hessian sums.

    Args:
      bins:     [B, F] int32 binned features.
      node_ids: [B] int32 current tree-node of each row (in [0, num_nodes)).
      grad/hess: [B] float32 (pre-multiplied by instance weight; padding rows
        must carry 0 weight so they vanish from every bin).
      num_nodes, num_bins: static.
      model_axis: optional mesh axis name — when set, the histogram output is
        sharding-constrained to split the feature dim over that axis
        (tensor-parallel hist for very wide feature spaces).
      method: "scatter" (default: segment_sum, exact f32 — the reference
        formulation and the fast CPU one) | "onehot" (bf16 MXU matmul, the
        fast TPU one) | "auto" (resolve by platform).  The exact path stays
        the default so existing callers keep f32 semantics.
      onehot: optional precomputed :func:`bin_onehot` (amortised across
        levels/rounds by callers; only used by the onehot method).
      feature_major: ``bins`` is handed over as ``[F, B]`` int32, the layout
        the ``pallas`` kernel reads (a fit keeps it once, ``gbdt.layout``);
        row-major bins are transposed and widened here for the kernel.

    Returns (G, H): each [num_nodes, F, num_bins] float32.
    """
    import jax
    import jax.numpy as jnp

    bins = jnp.asarray(bins)
    F, B = bins.shape if feature_major else bins.shape[::-1]
    method = resolve_hist_method(method, bins, grad)
    sharded_mesh = None
    if method == "pallas":
        from dmlc_core_tpu.ops.hist_pallas import hist_kernel_plan

        method, sharded_mesh = hist_kernel_plan(method, model_axis, F,
                                                num_nodes, num_bins, batch=B)
    if feature_major != (method == "pallas"):
        bins = bins.T
    if method == "pallas":
        bins = bins.astype(jnp.int32)

    if sharded_mesh is not None:
        from dmlc_core_tpu.ops.hist_pallas import grad_hist_pallas_sharded

        G, H = grad_hist_pallas_sharded(
            bins, node_ids, grad, hess, num_nodes, num_bins, sharded_mesh,
            model_axis)
    elif method == "pallas":
        from dmlc_core_tpu.ops.hist_pallas import grad_hist_pallas

        G, H = grad_hist_pallas(bins, node_ids, grad, hess, num_nodes,
                                num_bins)
    elif method == "onehot":
        if onehot is None:
            onehot = bin_onehot(bins, num_bins)
        dt = onehot.dtype
        nodehot = (node_ids.astype(jnp.int32)[:, None]
                   == jnp.arange(num_nodes, dtype=jnp.int32)).astype(dt)
        # [B, 2n]: per-row node one-hot weighted by g (first n cols) and h
        W = jnp.concatenate([nodehot * grad[:, None].astype(dt),
                             nodehot * hess[:, None].astype(dt)], axis=1)
        GH = jax.lax.dot_general(
            W, onehot, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [2n, F*nbins] f32 acc
        GH = GH.reshape(2, num_nodes, F, num_bins)
        G, H = GH[0], GH[1]
    else:
        ids = (node_ids[:, None] * (F * num_bins)
               + jnp.arange(F, dtype=jnp.int32)[None, :] * num_bins
               + bins)                                    # [B, F]
        flat_ids = ids.reshape(-1)
        nseg = num_nodes * F * num_bins
        g_flat = jnp.broadcast_to(grad[:, None], (B, F)).reshape(-1)
        h_flat = jnp.broadcast_to(hess[:, None], (B, F)).reshape(-1)
        G = jax.ops.segment_sum(g_flat, flat_ids, num_segments=nseg)
        H = jax.ops.segment_sum(h_flat, flat_ids, num_segments=nseg)
        G = G.reshape(num_nodes, F, num_bins)
        H = H.reshape(num_nodes, F, num_bins)
    if model_axis is not None:
        from jax.sharding import PartitionSpec as P

        constraint = P(None, model_axis, None)
        G = jax.lax.with_sharding_constraint(G, constraint)
        H = jax.lax.with_sharding_constraint(H, constraint)
    return G, H
