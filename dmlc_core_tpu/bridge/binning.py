"""Host-side quantile binning: compute edges once, ship uint8 over the wire.

The device-feed cost: a 2M x 28 float32 hist-training feed moves ~670 MB
host<->device (x f32 up, bins i32 back, bins up again) over PCIe and
through HBM, while the hist algorithm only ever reads the 256-bin ids —
the same 8-bit representation LightGBM/XGBoost histogram training
computes on.  This module moves the binning to the host so the wire (and
the device-resident copy every tree level re-reads) carries the **uint8
bins** instead:

- :func:`fit_binner` streams quantile bin edges over any row source — a
  raw ``[n, F]`` array, an iterable of arrays, a parser / RowBlock
  iterator, or :class:`~dmlc_core_tpu.data.page_cache.PageCacheReader`'s
  zero-copy mmap'd views — using the same mergeable per-chunk summaries
  as the distributed sketch (:mod:`dmlc_core_tpu.ops.histogram`), so the
  edges are computed in one pass without materialising the dataset;
- :class:`HostBinner` applies those edges with numpy ``searchsorted``
  exactly as the on-device :func:`~dmlc_core_tpu.ops.histogram.apply_bins`
  does (``side="right"``, same NaN handling), emitting the narrowest wire
  dtype that holds ``num_bins`` ids (uint8 through 256 bins) — split
  decisions are bitwise-identical to the float path by construction
  (asserted in ``tests/test_device_feed.py``);
- :class:`BinnedBatch` + :func:`binned_batches` adapt the existing dense
  batch pipeline to the binned wire format for the device-feed loader.

Wire-format size math (the reason this module exists): ``n x F`` rows cost
``n*F`` bytes binned-uint8 vs ``3 * n*F * 4`` on the old
device-side-binning path — a 12x wire reduction (2M x 28: 56 MB vs
~670 MB), plus ``8n`` bytes of labels+weights either way.
"""

from __future__ import annotations

from typing import Any, Iterable, Iterator, NamedTuple, Optional

import numpy as np

from dmlc_core_tpu.bridge.batching import (DenseBatch, _register_batch_pytree,
                                           dense_batches)
from dmlc_core_tpu.data.row_block import RowBlock
from dmlc_core_tpu.ops.histogram import (local_quantile_summary,
                                         merged_quantile_boundaries)
from dmlc_core_tpu.utils.logging import CHECK

__all__ = ["HostBinner", "BinnedBatch", "fit_binner",
           "fit_binner_from_summaries", "default_summary_points",
           "binned_batches", "wire_dtype"]


def default_summary_points(num_bins: int) -> int:
    """Per-chunk summary resolution K for ``num_bins`` target bins — the
    single formula both :func:`fit_binner` and any external summary
    producer (the fleet-ingest workers) must share for their summaries to
    merge into identical edges."""
    return max(64, 8 * num_bins)


def wire_dtype(num_bins: int) -> np.dtype:
    """The narrowest unsigned dtype that holds ``num_bins`` bin ids."""
    CHECK(num_bins >= 2, f"num_bins must be >= 2, got {num_bins}")
    if num_bins <= 256:
        return np.dtype(np.uint8)
    if num_bins <= 65536:
        return np.dtype(np.uint16)
    return np.dtype(np.int32)


class BinnedBatch(NamedTuple):
    """A :class:`~dmlc_core_tpu.bridge.batching.DenseBatch` whose features
    are pre-binned ids in the wire dtype — what the device feed ships.

    Same padding/masking contract as DenseBatch: padding rows carry
    ``weight == 0`` and the true row count rides in ``num_rows`` (static
    aux data, host-side)."""

    bins: np.ndarray     # [B, F] wire dtype (uint8 for <=256 bins)
    label: np.ndarray    # [B] float32
    weight: np.ndarray   # [B] float32 (0.0 marks padding)
    num_rows: Optional[int] = None


_register_batch_pytree(BinnedBatch, ("bins", "label", "weight"))


class HostBinner:
    """Apply fixed quantile edges on the host; emit wire-dtype bin ids.

    ``boundaries`` is ``[F, eff_bins - 1]`` float32 exactly as
    :meth:`GBDT.make_bins` / :func:`fit_binner` produce it, where
    ``eff_bins = num_bins - 1`` when ``handle_missing`` reserves the last
    id for NaNs (the GBDT sparsity-aware contract), else ``num_bins``.

    :meth:`transform` is the host twin of the on-device
    :func:`~dmlc_core_tpu.ops.histogram.apply_bins`: identical ids for
    identical float32 inputs (both are ``searchsorted(side="right")`` over
    the same edges), so a model trained on these bins makes bitwise-equal
    split decisions to one that binned on device.
    """

    def __init__(self, boundaries: np.ndarray, num_bins: int,
                 handle_missing: bool = False):
        boundaries = np.asarray(boundaries, dtype=np.float32)
        CHECK(boundaries.ndim == 2,
              f"boundaries must be [F, bins-1], got {boundaries.shape}")
        eff = num_bins - 1 if handle_missing else num_bins
        CHECK(boundaries.shape[1] == eff - 1,
              f"boundaries have {boundaries.shape[1] + 1} bins; expected "
              f"{eff} (num_bins={num_bins}, handle_missing={handle_missing})")
        self.boundaries = boundaries
        self.num_bins = num_bins
        self.handle_missing = handle_missing
        self.dtype = wire_dtype(num_bins)

    @property
    def num_feature(self) -> int:
        return self.boundaries.shape[0]

    def transform(self, x: np.ndarray) -> np.ndarray:
        """Bin ``x [B, F]`` float -> ``[B, F]`` wire-dtype ids.

        NaNs take the reserved missing id under ``handle_missing``;
        without it they land in the last bin (numpy and jax searchsorted
        agree: NaN compares false against every edge probe, so the binary
        search walks right) — both match ``apply_bins`` exactly.
        """
        x = np.asarray(x)
        CHECK(x.ndim == 2 and x.shape[1] == self.num_feature,
              f"x must be [B, {self.num_feature}], got {x.shape}")
        x32 = np.ascontiguousarray(x, dtype=np.float32)
        out = np.empty(x32.shape, dtype=self.dtype)
        for f in range(self.num_feature):
            out[:, f] = np.searchsorted(self.boundaries[f], x32[:, f],
                                        side="right")
        if self.handle_missing:
            out[np.isnan(x32)] = self.num_bins - 1
        return out

    def transform_batch(self, batch: DenseBatch) -> BinnedBatch:
        """Bin one dense batch into the wire format (labels/weights/count
        pass through untouched)."""
        return BinnedBatch(self.transform(batch.x), batch.label,
                           batch.weight, num_rows=batch.num_rows)

    def wire_nbytes(self, n_rows: int) -> int:
        """Bytes one ``[n_rows, F]`` binned feed ships (features only)."""
        return n_rows * self.num_feature * self.dtype.itemsize


def _dense_chunks(source: Any, num_feature: Optional[int],
                  handle_missing: bool) -> Iterator[np.ndarray]:
    """Normalize any supported row source into ``[n, F]`` float chunks.

    RowBlock sources densify chunk-by-chunk (absent features become NaN
    under ``handle_missing`` — the XGBoost sparse-means-missing
    semantics — else 0.0, matching ``block_to_dense``); ndarray sources
    stream through untouched, so page-cache views and parser output both
    feed the same summary math.
    """
    from dmlc_core_tpu.bridge.batching import block_to_dense

    fill = np.nan if handle_missing else 0.0

    def one(item):
        if isinstance(item, RowBlock):
            CHECK(num_feature is not None,
                  "RowBlock sources need num_feature= to densify")
            return block_to_dense(item, num_feature, fill_value=fill).x
        arr = np.asarray(item)
        CHECK(arr.ndim == 2, f"chunks must be [n, F], got {arr.shape}")
        return arr

    if isinstance(source, np.ndarray):
        yield one(source)
        return
    if isinstance(source, RowBlock):
        yield one(source)
        return
    for item in source:
        chunk = one(item)
        if chunk.shape[0]:
            yield chunk


def _resummarize(points: np.ndarray, counts: np.ndarray,
                 num_points: int) -> np.ndarray:
    """Collapse pooled per-chunk summaries to one fixed [F, num_points]
    summary (weighted quantiles of the pooled points) so a streamed fit
    can still allgather a fixed-size block per rank."""
    return merged_quantile_boundaries(points, counts, num_points + 1)


def fit_binner(source: Any, num_bins: int,
               num_feature: Optional[int] = None,
               handle_missing: bool = False, comm=None,
               num_points: Optional[int] = None) -> HostBinner:
    """Stream quantile bin edges over ``source``; return a ready binner.

    ``source`` may be a ``[n, F]`` array, an iterable of arrays, a
    parser / RowBlock iterable (``num_feature`` required to densify), or
    a :class:`~dmlc_core_tpu.data.page_cache.PageCacheReader` (pass
    ``reader.blocks``) — the mmap'd views are read in place, never
    copied whole.  Each chunk contributes a fixed-size mergeable summary
    (:func:`~dmlc_core_tpu.ops.histogram.local_quantile_summary`) and the
    deterministic weighted merge produces the edges in one pass: memory
    is O(chunks x F x num_points), not O(rows).

    ``comm`` (rabit-shaped allgather, e.g. ``dmlc_core_tpu.collective``)
    makes edges consistent across data-parallel workers: the local stream
    is re-summarised to one fixed block per rank and merged globally, so
    every rank returns identical boundaries — same discipline as
    :func:`~dmlc_core_tpu.ops.histogram.distributed_quantile_boundaries`.

    ``handle_missing`` reserves the last bin id for NaN (GBDT
    sparsity-aware contract): edges then cover ``num_bins - 1`` real bins.
    """
    K = num_points or default_summary_points(num_bins)
    all_points, all_counts = [], []
    n_feat = None
    for chunk in _dense_chunks(source, num_feature, handle_missing):
        if n_feat is None:
            n_feat = chunk.shape[1]
        CHECK(chunk.shape[1] == n_feat,
              f"chunk feature dim {chunk.shape[1]} != {n_feat}")
        pts, cnt = local_quantile_summary(chunk, K)
        all_points.append(pts)
        all_counts.append(cnt)
    CHECK(all_points, "fit_binner: empty source (no rows to summarise)")
    return fit_binner_from_summaries(
        np.stack(all_points), np.stack(all_counts), num_bins,
        handle_missing=handle_missing, comm=comm, num_points=K)


def fit_binner_from_summaries(points: np.ndarray, counts: np.ndarray,
                              num_bins: int, *,
                              handle_missing: bool = False, comm=None,
                              num_points: Optional[int] = None) -> HostBinner:
    """The allgather-merge tail of :func:`fit_binner`, callable on
    pre-accumulated ``local_quantile_summary`` stacks.

    ``points [C, F, K]`` / ``counts [C, F]`` are this rank's per-chunk
    summaries (K must be :func:`default_summary_points` of ``num_bins``
    unless ``num_points`` overrides it, and every participating rank must
    use the same K).  With ``comm`` the local stack is re-summarised to one
    fixed ``[F, K]`` block, allgathered, and merged globally — every rank
    returns bitwise-identical boundaries.  This is how the fleet-ingest
    workers (:mod:`dmlc_core_tpu.parallel.fleet_ingest`) fit one
    cross-rank-consistent binner over dynamically-assigned unit sets:
    summaries accumulate per unit during ingest, and the rank's final
    merge goes through exactly this path.
    """
    eff_bins = num_bins - 1 if handle_missing else num_bins
    K = num_points or default_summary_points(num_bins)
    points = np.asarray(points, dtype=np.float32)
    counts = np.asarray(counts, dtype=np.float32)
    if comm is not None:
        local = _resummarize(points, counts, K)          # [F, K]
        local_mass = counts.sum(axis=0).astype(np.float32)
        points = comm.allgather(local.astype(np.float32))    # [W, F, K]
        counts = comm.allgather(local_mass)                  # [W, F]
    boundaries = merged_quantile_boundaries(points, counts, eff_bins)
    return HostBinner(boundaries, num_bins, handle_missing=handle_missing)


def binned_batches(parser, binner: HostBinner, batch_size: int,
                   drop_remainder: bool = False) -> Iterable[BinnedBatch]:
    """Fixed-size :class:`BinnedBatch` stream from a parser: the dense
    batch pipeline with host binning fused in, so downstream transfers
    ship wire-dtype ids instead of float32 features.

    Under ``binner.handle_missing`` absent features densify to NaN and
    bin to the reserved missing id (padding rows stay zero-binned with
    ``weight == 0``, exactly like the float pipeline's contract).
    """
    fill = np.nan if binner.handle_missing else 0.0
    for batch in dense_batches(parser, batch_size, binner.num_feature,
                               drop_remainder=drop_remainder,
                               fill_value=fill):
        yield binner.transform_batch(batch)
