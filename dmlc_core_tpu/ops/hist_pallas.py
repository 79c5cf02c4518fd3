"""Pallas TPU kernel: gradient histograms without materialising the one-hot.

The ``"onehot"`` method in :mod:`.histogram` casts the XGBoost-hist kernel
(reference workload: src/data + Rabit hist aggregation consumers) as an MXU
matmul ``W[M, B] @ onehot[B, F*nbins]``.  That is compute-shaped right, but
HBM-bound: the materialised one-hot is ``F*nbins/8`` times larger than the
binned features (28 feat x 256 bins -> 14 KB/row in bf16 vs 112 B/row of
int32 bins), and every tree level of every boosting round re-reads all of it.

This kernel keeps the matmul but builds the one-hot **tile-by-tile in VMEM**:

- grid = (feature blocks, row tiles), both sequential on TPU, rows inner;
- an ``[M, F_blk*nbins]`` f32 accumulator lives in one VMEM output block
  indexed by the feature block alone, so it persists across a block's row
  tiles (zeroed at the first);
- per step: DMA ``W`` tile ``[M, TB]`` (bf16) + bins tile ``[TB, F_blk]``
  (int32), then for each feature compare-to-iota -> ``[TB, nbins]`` one-hot
  in VMEM and issue one MXU dot, accumulating in f32;
- a table whose ``[M, F*nbins]`` accumulator fits the VMEM budget is one
  feature block; a wider one is cut by :func:`hist_block_plan` into blocks
  of 128 features, ``W`` re-read once per block.  Every feature's one-hot
  is still built once, so a level stays ONE ``hist_level`` call whose cost
  follows rows x features.

HBM traffic per level falls from ``B*F*nbins*2`` bytes to
``B*(4F + 2M + 12)`` — ~100x for the flagship shapes — turning the histogram
from bandwidth- to compute-bound.  Numerics match the ``"onehot"`` method
exactly (same bf16 one-hot / bf16 W / f32 accumulate).

Used automatically on TPU via ``resolve_hist_method("auto")``: the plan
blocks features, then nodes, until an accumulator block fits VMEM, so width
and depth never send a fit to the plain one-hot matmul (only a mesh the
kernel cannot be shard_mapped over does — :func:`hist_kernel_plan`).
On a TPU backend a kernel Mosaic rejects raises with the compiler's message
— nothing here probes-and-swallows on behalf of ``auto``.
"""

from __future__ import annotations

import functools

import numpy as np

from dmlc_core_tpu.utils.logging import log_warning

__all__ = ["hist_matmul_pallas", "grad_hist_pallas",
           "grad_hist_pallas_fused", "grad_hist_pallas_sharded",
           "ambient_mesh", "hist_kernel_plan", "fit_row_multiple",
           "interpret_mode",
           "pallas_fused_supported", "pallas_i8_supported", "hist_fits_vmem",
           "hist_block_plan", "hist_block_counts",
           "BLOCK_ROWS", "DATA_AXIS"]

# interpreter mode: runs the kernels on CPU for tests/debugging (flipped by
# tests, or set DMLC_TPU_PALLAS_INTERPRET=1 to debug without a chip).
# Read through interpret_mode(), which refuses it on a TPU backend.
import os as _os

_INTERPRET = _os.environ.get("DMLC_TPU_PALLAS_INTERPRET",
                             "").strip().lower() in ("1", "true", "yes")


def interpret_mode() -> bool:
    """Whether kernels run in the Pallas interpreter (tests, CPU debugging).

    On a TPU backend the answer is never yes: an interpreted kernel there
    would pass every check while the Mosaic kernel — the thing the chip is
    for — never compiled, so the request is refused instead of honoured.
    """
    if not _INTERPRET:
        return False
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "Pallas interpret mode (DMLC_TPU_PALLAS_INTERPRET / "
            "hist_pallas._INTERPRET) is refused on a TPU backend: the "
            "kernels must compile through Mosaic there")
    return True

# row-tile size: callers that want the wrapper's internal padding to no-op
# (e.g. GBDT's fit-level padding) must pad to a multiple of this.
# DMLC_TPU_HIST_BLOCK_ROWS overrides for on-chip tuning sweeps; 1024 is the
# measured-best default on v5e (see BASELINE.md round-3 block_rows sweep).
try:
    BLOCK_ROWS = int(_os.environ.get("DMLC_TPU_HIST_BLOCK_ROWS", "") or 1024)
except ValueError:
    raise ValueError(
        "DMLC_TPU_HIST_BLOCK_ROWS must be an integer multiple of the 128 "
        f"lane width, got {_os.environ['DMLC_TPU_HIST_BLOCK_ROWS']!r}"
    ) from None
if BLOCK_ROWS < 128 or BLOCK_ROWS % 128:
    raise ValueError(
        f"DMLC_TPU_HIST_BLOCK_ROWS must be a positive multiple of the 128 "
        f"lane width, got {BLOCK_ROWS}")


def _bins_compare_dtype(num_bins: int):
    """dtype bins are compared in inside the kernel: int8 when the bin ids
    fit (<=256 with wraparound) AND the backend lowers it, else int32."""
    import jax.numpy as jnp

    if num_bins <= 256 and pallas_i8_supported():
        return jnp.int8
    return jnp.int32

# VMEM budget for the resident accumulator block (bytes): what
# hist_block_plan cuts a level's [2n, F*nbins] histogram down to.
_ACC_BYTES_LIMIT = 8 * 1024 * 1024

# a bins tile's minor (feature) extent is whole or a multiple of the lanes
_LANES = 128


def _pad_nodes(num_nodes: int) -> int:
    """Node-slot padding so M = 2*n_pad is a multiple of the bf16 tile (16)."""
    return -(-max(8, num_nodes) // 8) * 8


def hist_fits_vmem(num_nodes: int, num_feature: int, num_bins: int) -> bool:
    """Whether the resident [2*n_pad, F*nbins] f32 accumulator fits VMEM."""
    return 2 * _pad_nodes(num_nodes) * num_feature * num_bins * 4 \
        <= _ACC_BYTES_LIMIT


def hist_block_plan(num_nodes: int, num_feature: int, num_bins: int):
    """``(nodes per kernel call, features per accumulator block)`` of one
    level, or None when even 8 node slots of the narrowest feature block
    overflow VMEM.  The one place the budget is applied.

    Features are blocked first: a feature block is a grid step of the SAME
    kernel call (every feature's one-hot is still built once; only ``W``
    is re-read), while a node block is another call that re-reads the bins
    and re-builds every one-hot — kernel cost is VPU-bound and
    m-independent (measured — BASELINE.md r3 profile), so #node sweeps
    scales the cost.  So: the most nodes for which the narrowest legal
    feature block (128 bins columns, or all F of a narrower table) fits,
    and beside them all F features in one block where those fit,
    else blocks of 128.  Not wider where the budget would allow it: the
    tile body is unrolled over the block's features, and at 512 of them
    Mosaic's register allocator spilled 173 MB for a v5e (PR 27), while
    ``W``'s re-reads are small beside the bins at any node count.
    """
    narrow = min(num_feature, _LANES)
    nodes = num_nodes
    if not hist_fits_vmem(nodes, narrow, num_bins):
        nodes = 1 << (num_nodes - 1).bit_length()
        while nodes >= 8 and not hist_fits_vmem(nodes, narrow, num_bins):
            nodes //= 2
        if nodes < 8:
            return None
    if hist_fits_vmem(nodes, num_feature, num_bins):
        return nodes, num_feature
    return nodes, _LANES


def hist_block_counts(model_axis, num_feature: int, num_nodes: int,
                      num_bins: int):
    """``(node blocks, feature blocks)`` one chip's kernel runs a level of
    ``num_nodes`` nodes in: kernel calls, and grid steps over features
    inside each.  For a fit :func:`hist_kernel_plan` settled on the kernel;
    what ``gbdt.fit.dispatch`` records beside the method."""
    if model_axis is not None:
        num_feature //= ambient_mesh().shape[model_axis]
    nodes, feats = hist_block_plan(num_nodes, num_feature, num_bins)
    return -(-num_nodes // nodes), -(-num_feature // feats)


def _accumulate_tile(w, bins_ref, out_ref, num_feature: int, num_bins: int,
                     row_axis: int = 0):
    """Shared tile body: zero-init at the first step of the grid's row axis,
    then per-feature one-hot dots of ``w`` [M, TB] accumulated into the
    resident ``out_ref``.

    The iota matches the bins dtype: callers may pass bins as int8 (the
    profiled v5e bottleneck is this in-VMEM one-hot build, not the MXU dots
    — kernel time is m-independent — and int8 compares run 4 lanes/cycle
    wider on the VPU).  num_bins=256 still fits: both sides wrap through
    int8 identically, so equality is preserved.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(row_axis) == 0)
    def _zero():
        out_ref[:] = jnp.zeros_like(out_ref)

    iota = jax.lax.broadcasted_iota(jnp.int32, (1, num_bins), 1)
    iota = iota.astype(bins_ref.dtype)
    for f in range(num_feature):
        onehot = (bins_ref[:, f:f + 1] == iota).astype(w.dtype)  # [TB, nbins]
        out_ref[:, f * num_bins:(f + 1) * num_bins] += jax.lax.dot_general(
            w, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _split_gh(out, n_pad: int, num_nodes: int, num_feature: int,
              num_bins: int):
    """Shared epilogue: [2*n_pad, F*nbins] -> (G, H) trimmed to num_nodes."""
    out = out.reshape(2, n_pad, num_feature, num_bins)
    return out[0, :num_nodes], out[1, :num_nodes]


def _kernel(w_ref, bins_ref, out_ref, *, num_feature: int, num_bins: int,
            row_axis: int = 0):
    _accumulate_tile(w_ref[:], bins_ref, out_ref, num_feature, num_bins,
                     row_axis)


def hist_matmul_pallas(w, bins, num_bins: int, block_rows: int = BLOCK_ROWS,
                       block_features=None):
    """``out[m, f*nbins + b] = sum_i w[m, i] * (bins[i, f] == b)``.

    Args:
      w: [M, B] bf16 per-row weights (M multiple of 16; rows beyond the live
        node count must be zero).
      bins: [B, F] int32 binned features in [0, num_bins).
      num_bins: static bin count.
      block_rows: row-tile size (B is padded up to a multiple internally).
      block_features: features per accumulator block (128, or a multiple);
        None or >= F keeps all F in one block.

    Returns [M, F*num_bins] float32.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, b = w.shape
    bf = bins.shape[1]
    bins = bins.astype(_bins_compare_dtype(num_bins))
    if b % block_rows:
        pad = block_rows - b % block_rows
        w = jnp.pad(w, ((0, 0), (0, pad)))         # zero W => zero contribution
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        b += pad
    tiles = b // block_rows
    if block_features is None or block_features >= bf:
        block_features = bf
    # feature blocks on the OUTER axis, row tiles inside: the accumulator
    # block moves only when a feature block's rows are all in, and W's tile
    # is the same for every block.  F need not divide: the last block's
    # columns beyond F read unspecified bins whose histogram columns lie
    # beyond the output and are never written back.  One buffer for an
    # output block whose index moves — Pallas would keep two, and the
    # budget is for one.
    blocks = pl.cdiv(bf, block_features)
    out_buffering = {"pipeline_mode": pl.Buffered(1)} if blocks > 1 else {}
    kernel = functools.partial(_kernel, num_feature=block_features,
                               num_bins=num_bins, row_axis=1)
    return pl.pallas_call(
        kernel,
        grid=(blocks, tiles),
        in_specs=[
            pl.BlockSpec((m, block_rows), lambda j, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, block_features), lambda j, i: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m, block_features * num_bins),
                               lambda j, i: (0, j),
                               memory_space=pltpu.VMEM, **out_buffering),
        out_shape=jax.ShapeDtypeStruct((m, bf * num_bins), jnp.float32),
        interpret=interpret_mode(),
        name="hist_level",
    )(w, bins)


def grad_hist_pallas(bins, node_ids, grad, hess, num_nodes: int,
                     num_bins: int):
    """Per-(node, feature, bin) gradient/hessian sums via the VMEM kernel.

    Same contract as :func:`.histogram.grad_histogram`; returns (G, H) each
    [num_nodes, F, num_bins] float32.  Rows with out-of-range (e.g. negative)
    node ids contribute nothing.

    Levels too wide or deep for one resident accumulator run blocked (see
    :func:`hist_block_plan`): feature blocks are grid steps of one kernel
    call; node blocks are calls of their own, and shifting node ids by the
    block base makes the kernel's own out-of-range drop do the partitioning.
    """
    import jax.numpy as jnp

    plan = hist_block_plan(num_nodes, bins.shape[1], num_bins)
    assert plan is not None, "caller must gate on hist_block_plan"
    block, block_features = plan
    if block < num_nodes:
        node_ids = node_ids.astype(jnp.int32)
        parts = [
            _grad_hist_pallas_block(bins, node_ids - b0, grad, hess,
                                    min(block, num_nodes - b0), num_bins,
                                    block_features)
            for b0 in range(0, num_nodes, block)
        ]
        return (jnp.concatenate([p[0] for p in parts]),
                jnp.concatenate([p[1] for p in parts]))
    return _grad_hist_pallas_block(bins, node_ids, grad, hess, num_nodes,
                                   num_bins, block_features)


def _grad_hist_pallas_block(bins, node_ids, grad, hess, num_nodes: int,
                            num_bins: int, block_features=None):
    import jax.numpy as jnp

    bins = jnp.asarray(bins).astype(jnp.int32)
    bf = bins.shape[1]
    n_pad = _pad_nodes(num_nodes)
    iota_n = jnp.arange(n_pad, dtype=jnp.int32)
    nodehot = node_ids.astype(jnp.int32)[None, :] == iota_n[:, None]  # [n, B]
    w = jnp.concatenate([
        jnp.where(nodehot, grad[None, :], 0.0),
        jnp.where(nodehot, hess[None, :], 0.0),
    ], axis=0).astype(jnp.bfloat16)                # [2*n_pad, B]
    out = hist_matmul_pallas(w, bins, num_bins,
                             block_features=block_features)
    return _split_gh(out, n_pad, num_nodes, bf, num_bins)


def _fused_kernel(node_ref, g_ref, h_ref, bins_ref, out_ref, *,
                  n_pad: int, num_feature: int, num_bins: int):
    import jax
    import jax.numpy as jnp

    # W tile [2*n_pad, TB] built in VMEM from node/g/h (12 B/row of HBM
    # traffic instead of 4*n_pad B/row for a materialised W)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (n_pad, 1), 0)
    nodehot = (iota_n == node_ref[:]).astype(jnp.bfloat16)   # [n_pad, TB]
    w = jnp.concatenate([nodehot * g_ref[:].astype(jnp.bfloat16),
                         nodehot * h_ref[:].astype(jnp.bfloat16)], axis=0)
    _accumulate_tile(w, bins_ref, out_ref, num_feature, num_bins)


def grad_hist_pallas_fused(bins, node_ids, grad, hess, num_nodes: int,
                           num_bins: int, block_rows: int = BLOCK_ROWS):
    """Like :func:`grad_hist_pallas`, with the weight matrix built in-kernel.

    Skips the XLA-side [2n, B] W materialisation entirely: the kernel reads
    node/g/h row tiles and bins, and builds both one-hots in VMEM.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bins = jnp.asarray(bins).astype(_bins_compare_dtype(num_bins))
    b, bf = bins.shape
    n_pad = _pad_nodes(num_nodes)
    node = node_ids.astype(jnp.int32).reshape(1, b)
    g = grad.astype(jnp.float32).reshape(1, b)
    h = hess.astype(jnp.float32).reshape(1, b)
    if b % block_rows:
        pad = block_rows - b % block_rows
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        node = jnp.pad(node, ((0, 0), (0, pad)), constant_values=-1)
        g = jnp.pad(g, ((0, 0), (0, pad)))
        h = jnp.pad(h, ((0, 0), (0, pad)))
        b += pad
    m = 2 * n_pad
    kernel = functools.partial(_fused_kernel, n_pad=n_pad, num_feature=bf,
                               num_bins=num_bins)
    row_spec = pl.BlockSpec((1, block_rows), lambda i: (0, i),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid=(b // block_rows,),
        in_specs=[row_spec, row_spec, row_spec,
                  pl.BlockSpec((block_rows, bf), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((m, bf * num_bins), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, bf * num_bins), jnp.float32),
        interpret=interpret_mode(),
        name="hist_level_fused",
    )(node, g, h, bins)
    return _split_gh(out, n_pad, num_nodes, bf, num_bins)


# mesh axis name the whole package shards batch rows over (parallel/mesh.py
# data_sharding default); the sharded hist uses it for its psum axis
DATA_AXIS = "data"


def ambient_mesh():
    """The Mesh of an enclosing ``with mesh:`` block, or None outside one.

    grad_histogram reads this at trace time to shard_map the kernel for
    sharded runs; callers opt in simply by tracing under their mesh (the
    convention every sharded path in this package already follows).  One
    accessor, the one the installed jax keeps the ``with mesh:`` stack in;
    if an upgrade moves it this raises (and tests/test_hist_pallas.py pins
    it) rather than quietly un-sharding the kernel.
    """
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def _data_parallelism(mesh) -> int:
    return 1 if mesh is None else mesh.shape.get(DATA_AXIS, 1)


def fit_row_multiple() -> int:
    """Row count a fit pads to ONCE so no kernel call pads again: the tile
    size times the ambient data axis (each data shard must itself be a
    whole number of tiles once the kernel runs under shard_map)."""
    return BLOCK_ROWS * _data_parallelism(ambient_mesh())


def hist_kernel_plan(method: str, model_axis, num_feature: int,
                     num_nodes: int, num_bins: int, batch=None):
    """Settle a ``pallas``/``pallas_fused`` request against the shapes and
    the ambient mesh.  Returns ``(method, mesh)``: the method that will
    actually run and the mesh to shard_map the kernel over (None = one
    plain kernel call).

    Single source of truth for ``GBDT._method`` (decides once per fit, for
    the deepest level, so an ``onehot`` outcome still amortises its matmul
    RHS across rounds) and ``grad_histogram`` (per level) — the two cannot
    drift.

    - A Mosaic kernel has no GSPMD partitioning rule; on a TPU, jit refuses
      one over sharded operands ("Mosaic kernels cannot be automatically
      partitioned").  So under a mesh that actually shards — a
      ``model_axis``, or a data axis wider than one device — the kernel
      runs inside shard_map: rows over data, features over model.
    - ``onehot`` (a GSPMD-partitionable matmul) takes over only where the
      mesh forbids the kernel: features do not divide the model axis, rows
      do not divide the data axis (``batch=None`` skips that check for
      callers that pad rows later), or a ``model_axis`` is named with no
      mesh to find it in.  Width and depth never do: the per-shard ``F/mp``
      slice is blocked by :func:`hist_block_plan` (whose None — 8 node
      slots of 128 features over the budget, bins in the tens of
      thousands — is the one shape left to ``onehot``).
    - Blocked levels (nodes or features) have no fused variant.
    """
    mesh = ambient_mesh()
    dp = _data_parallelism(mesh)
    mp = 1
    if model_axis is not None:
        mp = None if mesh is None else mesh.shape.get(model_axis)
        if mp is None:
            return "onehot", None
    sharded = model_axis is not None or dp > 1
    if sharded and (num_feature % mp != 0
                    or (batch is not None and batch % dp != 0)):
        return "onehot", None
    plan = hist_block_plan(num_nodes, num_feature // mp, num_bins)
    if plan is None:
        return "onehot", None
    if plan != (num_nodes, num_feature // mp) and method == "pallas_fused":
        method = "pallas"
    return method, (mesh if sharded else None)


def grad_hist_pallas_sharded(bins, node_ids, grad, hess, num_nodes: int,
                             num_bins: int, mesh, model_axis=None,
                             data_axis: str = DATA_AXIS,
                             fused: bool = False):
    """shard_map-wrapped VMEM hist: rows dp-sharded, features model-sharded.

    The only way the Pallas kernel runs on more than one device: each shard
    runs the VMEM kernel on its local rows and partial histograms are
    psummed over the data axis — the distributed-hist aggregation XGBoost
    does over Rabit.  With a ``model_axis`` each model shard also slices its
    own ``F/mp`` feature columns (bins arrive feature-replicated) and the
    output is ``P(None, model_axis, None)`` — exactly the constraint the
    GSPMD path advertises, so split-finding code downstream is unchanged;
    without one the output is replicated.

    Requires ``F % mesh.shape[model_axis] == 0``; callers check this (and the
    per-shard VMEM fit) through :func:`hist_kernel_plan` first.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    row_axis = data_axis if data_axis in mesh.shape else None
    inner = grad_hist_pallas_fused if fused else grad_hist_pallas
    f_local = (bins.shape[1] if model_axis is None
               else bins.shape[1] // mesh.shape[model_axis])

    def local_hist(b, n, g, h):
        if model_axis is not None:
            idx = jax.lax.axis_index(model_axis)
            b = jax.lax.dynamic_slice_in_dim(b, idx * f_local, f_local,
                                             axis=1)
        G, H = inner(b, n.astype(jnp.int32), g, h, num_nodes, num_bins)
        if row_axis is not None:
            G = jax.lax.psum(G, row_axis)
            H = jax.lax.psum(H, row_axis)
        return G, H

    out_spec = P(None, model_axis, None)
    # check_vma off: pallas_call's out_shape carries no vma annotation; the
    # psum above already makes the outputs data-axis-invariant
    return jax.shard_map(
        local_hist, mesh=mesh,
        in_specs=(P(row_axis, None), P(row_axis), P(row_axis), P(row_axis)),
        out_specs=(out_spec, out_spec), check_vma=False,
    )(bins, node_ids, grad, hess)


def _probe_failed(what: str, exc: Exception) -> bool:
    """A variant probe is allowed to say no — never silently: the variant
    changes which kernel ``auto`` runs, so the compiler's reason is logged."""
    reason = (str(exc).strip().splitlines() or [""])[0]
    log_warning(f"hist_pallas: {what} rejected by the compiler, variant "
                f"off ({type(exc).__name__}: {reason})")
    return False


@functools.lru_cache(maxsize=None)
def pallas_i8_supported() -> bool:
    """Probe whether int8 bins compare+select lowers in the kernel.

    Probed with a direct pallas_call (not through the wrappers, which would
    recurse into this gate): an int8 bins tile against the shared tile body.
    Falls back to int32 bins — with a logged reason — when Mosaic rejects
    the int8 vector ops (the v5e does: "Target does not support this
    comparison"), and is disabled outright by DMLC_TPU_HIST_I8=0.
    """
    if _os.environ.get("DMLC_TPU_HIST_I8", "").strip() == "0":
        return False
    import jax

    interpret = interpret_mode()
    if jax.default_backend() == "cpu" and not interpret:
        return False
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_kernel, num_feature=2, num_bins=8)
    probe = jax.jit(lambda w, b: pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((16, 128), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((128, 2), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((16, 16), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((16, 16), jnp.float32),
        interpret=interpret,
    )(w, b))
    # the gate is first consulted while a kernel wrapper is being traced:
    # run the probe eagerly there, not as part of the caller's program
    with jax.core.eval_context():
        w = jnp.zeros((16, 128), jnp.bfloat16).at[0, 0].set(1.0)
        bins = jnp.zeros((128, 2), jnp.int8)
        try:
            out = np.asarray(probe(w, bins))
        except Exception as exc:  # noqa: BLE001 — logged, never silent
            return _probe_failed("int8 bin compares", exc)
    return bool(out[0, 0] == 1.0)


@functools.lru_cache(maxsize=None)
def pallas_fused_supported() -> bool:
    """Probe the fused-W kernel separately from the plain one.

    The fused kernel's in-VMEM bf16 concat at the n_pad=8 boundary (below the
    16-sublane tile) can fail to lower on real Mosaic even when
    :func:`hist_matmul_pallas` compiles.  ``auto`` never selects the fused
    kernel; a user-selected ``pallas_fused`` that does not lower falls back
    to ``pallas`` with the compiler's reason logged.
    """
    import jax

    if jax.default_backend() == "cpu" and not interpret_mode():
        return False
    import jax.numpy as jnp

    probe = jax.jit(lambda b, n, g, h: grad_hist_pallas_fused(
        b, n, g, h, num_nodes=4, num_bins=8, block_rows=128))
    with jax.core.eval_context():
        bins = jnp.zeros((128, 2), jnp.int32)
        node = jnp.zeros((128,), jnp.int32)
        one = jnp.ones((128,), jnp.float32)
        try:
            G = np.asarray(probe(bins, node, one, one)[0])
        except Exception as exc:  # noqa: BLE001 — logged, never silent
            return _probe_failed("the fused-W kernel", exc)
    return bool(G[0, 0, 0] == 128.0)
