"""Pallas TPU kernel: a level's gradient histogram as an MXU matmul whose
operands never leave VMEM.

A gradient histogram (the XGBoost-hist kernel; reference workload: src/data
+ Rabit hist aggregation consumers) is the product of two one-hots over the
rows, ``W[2n, B] @ binhot[B, F*nbins]``.  Written to HBM the bin one-hot is
``F*nbins/8`` times the binned features (28 feat x 256 bins -> 14 KB/row in
bf16 vs 112 B/row of int32 bins) and every level of every round would
re-read it, so it is never written.

This kernel builds both operands of the matmul **tile-by-tile in VMEM**,
rows on the lanes, and splits the bin index between them
(``bin = hi * L + lo``, :func:`hist_split_plan`)::

    key_i            = node_i * H + hi_i      (node outside [0, n): no key)
    A [(n, hi, c), i] = (key_i == n * H + hi) ? (g_i if c == 0 else h_i) : 0
    LO[lo, i]         = (lo_i == lo)
    acc_f[lo, (n, hi, c)] += LO . A^T         (contract the tile's rows; f32)

A call that builds n nodes needs only ``2n * num_bins`` buckets a feature,
so ``H`` and ``L`` are chosen per call to give the product ``2nH * L`` just
that many (or the next whole tiles above), shared out so that neither side
of the MXU waits for the other.  On a v5e the dot costs ``max(2nH, 2L)``
cycles a 1,024-row tile and feature, per 128 of ``2nH`` (measured at the
powers of two, PERF.md PR 28, and at every whole-tile neighbour, PR 34: 24
splits at two shapes on one line, 0.2005 ms a cycle at HIGGS's size), and
``L`` is any multiple of the bf16 tile's 16 sublanes.  At 256 bins::

    built nodes   1      2      4      8      16     32     64     128
    (H, L)        16x16  8x32   8x32   6x48   4x64   2x128  2x128  1x256
    cycles        32     64     64     96     128    256    512    1024

(6, 48) covers 288 pairs for 256 bins: ``2nH = 2L = 96`` where (4, 64) and
(8, 32) pay 128 for one side.  From 16 nodes up the dot is full 128 x 128
tiles and runs at the MXU's peak for the task's product; a call of fewer
nodes costs a quarter to three quarters of the 16-node call, not all of
it.  From 128 nodes ``H = 1``: the plain one-hot matmul, transposed.  A
fit's level of n nodes builds n / 2 of them here, one child of every pair,
and takes the siblings as parent - built (``histogram.HistPlan.level``): 1,
1, 2, 4, 8, 16 node slots at depth 6, whose rows are keyed by their
parent's id and all other rows by -1, which the body drops; the one-shot
``grad_histogram`` builds every node it is asked for.

- grid = (node blocks, feature blocks, row tiles), all sequential on TPU,
  rows innermost.  A step costs 0.13-0.18 us beyond its dot whatever it
  holds, so the row tile ``TB`` follows the block's features
  (:func:`hist_row_tile`): a step carries about the row-features of a
  128-feature block's step of ``BLOCK_ROWS`` rows, 8,192 rows at 28
  features, 16,384 at 13;
- the ``[F_blk, L, 2nH]`` f32 accumulator lives in one VMEM output block
  indexed by the node and feature block alone, so it persists across a
  block's row tiles (zeroed at the first);
- per step: DMA the node / g / h row tiles ``[1, TB]`` and the bins tile
  ``[F_blk, TB]`` (feature-major int32: v5e Mosaic lowers no sub-32-bit
  compare), then for each feature the bin's quotient and remainder by the
  static ``L`` (a shift and a mask, or a multiply and a shift where ``L``
  is no power of two), two compare-selects against a sublane iota and one
  MXU dot.  Both operands are built as int32 words that hold
  TWO bf16 rows each (g and h of one key; ones of two neighbouring ``lo``)
  and are bitcast to bf16: half the compares, and no convert;
- a table whose accumulator fits the VMEM budget is one block; a wider
  one is cut by :func:`hist_block_plan` into feature blocks, and a level
  of more nodes than a block of 128 features leaves room for (64 at 256
  bins) into node blocks.  Both are steps of the grid, so a level is ONE
  ``hist_level`` call whatever its blocking; a feature block builds its
  features' one-hots once, a node block builds every feature's again with
  its own base taken off the node ids.  The body is unrolled over all of
  a block's features, so a call's time follows the SLOTS of its grid and
  the blocks divide the table (:func:`hist_feature_block`: 968 features
  are 11 blocks of 88 and 2,000 are 25 of 80, where blocks of 128 paid
  for 56 and 48 columns no row has);
- the call says which level it is: ``hist_level_L<level>_n<node slots>``
  (:func:`hist_kernel_name`; ``hist_level_L4_n8``), metadata of the
  compiled program that a profile's reader finds each level by;
- the result leaves the kernel as ``[node blocks, F, L, 2nH]``; one XLA
  transpose a level puts it back to ``(G, H)[n, F, num_bins]``.

HBM traffic per level is ``B*(4F + 12)`` bytes — no weight matrix is ever
written.  Every term is a bf16-rounded g or h (or 0) times a bf16 0/1,
accumulated in f32: against the exact ``scatter`` histogram only the bf16
rounding of g and h and the order of the f32 additions differ.

``auto`` means this kernel on a TPU (``histogram.resolve_hist_method``) and
nothing falls back from it.  :func:`hist_kernel_plan` settles a fit's
kernel once, before tracing: width and depth are blocked (features, then
nodes) until an accumulator block fits VMEM, and a mesh the kernel cannot
be shard_mapped over raises a ``ValueError`` that names what to change.  On
a TPU backend a kernel Mosaic rejects raises with the compiler's message.
"""

from __future__ import annotations

import functools

__all__ = ["hist_matmul_pallas", "grad_hist_pallas",
           "grad_hist_pallas_sharded",
           "ambient_mesh", "hist_kernel_plan",
           "interpret_mode", "hist_fits_vmem",
           "hist_block_plan", "hist_feature_block", "hist_split_plan",
           "hist_row_tile",
           "hist_kernel_name", "BLOCK_ROWS", "DATA_AXIS"]

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

# the base row tile, a multiple of the 128 lanes: the rows of a grid step
# over a 128-feature block, and of any table too small to fill wider
# tiles; hist_row_tile widens it for narrower blocks.  At 4096 the widest
# blocked level leaves Mosaic's default VMEM (PERF.md, PR 28).
BLOCK_ROWS = 2048

# the widest row tile: at 32,768 rows a 28-feature step's calls of 8 and 16
# nodes took 3 ms longer than at 16,384 (the sweep, PERF.md, PR 37)
_ROW_TILE_CAP = 16384

# whole tiles a chip's rows must fill before a tile is widened: a fit pads
# to the tile (under 1.6% of such rows), and a small table keeps the base
_ROW_TILES_FILLED = 64


# VMEM budget for the resident accumulator block (bytes): what
# hist_block_plan cuts a level's [2n, F*nbins] histogram down to.
_ACC_BYTES_LIMIT = 8 * 1024 * 1024

# features of a blocked accumulator's widest block (and the MXU's lanes)
_LANES = 128

# a blocked accumulator's narrowest block: the least width whose step
# keeps BLOCK_ROWS rows (hist_row_tile doubles the tile at 64 features, and
# a tile of other rows sums in another order); and what one more feature
# block costs a round's calls, in feature slots (PERF.md, PR 39)
_NARROWEST_BLOCK = 72
_BLOCK_CHARGE = 2

# VMEM a call may count on unasked: Mosaic's default on a v5e is 16 MiB (of
# 128), less a quarter for what the compiler keeps there itself
_VMEM_UNASKED = 12 * 1024 * 1024


def _pad_nodes(num_nodes: int) -> int:
    """Node slots the byte rule counts: a multiple of 8, and 8 at least."""
    return -(-max(8, num_nodes) // 8) * 8


def hist_fits_vmem(num_nodes: int, num_feature: int, num_bins: int) -> bool:
    """Whether a level's resident f32 accumulator fits the VMEM budget,
    counted as ``[2*n_pad, F*nbins]``: the kernel's ``[F, L, 2nH]`` block
    wherever ``H * L`` is ``num_bins`` and ``2nH`` fills its lanes; what
    Mosaic's tiles add to that, ``hist_matmul_pallas`` asks for."""
    return 2 * _pad_nodes(num_nodes) * num_feature * num_bins * 4 \
        <= _ACC_BYTES_LIMIT


def hist_block_plan(num_nodes: int, num_feature: int, num_bins: int):
    """``(node slots, features)`` of one accumulator block of a level, or
    None when even 8 node slots of the narrowest feature block overflow
    VMEM.  The one place the budget is applied.

    Features are blocked first: both kinds of block are grid steps of the
    SAME kernel call, but under feature blocks every feature's one-hots
    are still built once (only the 12 B a row of node, g and h are
    re-read), while a node block re-reads the bins and re-builds every
    one-hot.  So: the most nodes for which a block of ``_LANES`` features
    (or all F of a narrower table) fits, and beside them all F features in
    one block where those fit, else blocks of :func:`hist_feature_block`
    features, the width that divides the table with the fewest slots left
    over.  Never wider than ``_LANES`` where the budget would allow it: at
    512 features a block Mosaic spilled 173 MB for a v5e (PR 27, the body
    then unrolled over the block's features).
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
    return nodes, hist_feature_block(num_feature)


def hist_feature_block(num_feature: int) -> int:
    """Features of one block of a table too wide for one accumulator: of
    the multiples of 8 (the int32 sublane tile of the ``[F_blk, rows]``
    bins block) from ``_NARROWEST_BLOCK`` to ``_LANES``, the width ``w``
    that minimises ``ceil(F / w) * (w + _BLOCK_CHARGE)``, ties to the wider
    block.  A pure function of the static ``num_feature``.

    The body is unrolled over ALL of a block's features, so a call's time
    follows the slots of its grid, not the table's columns: under blocks
    of 128, 968 features paid for 1,024 (8 blocks, the last 72 wide) and
    2,000 for 2,048.  So the blocks divide the table: 968 -> 11 blocks of
    88 and 2,000 -> 25 of 80, no slot empty; 260 -> 3 of 88 (264 slots for
    384); 136 -> 2 of 72 (144 for 256); 256, 1,024, 2,048 keep 128.
    Measured on a v5e, the kernel alone, ms a call at 1, 4, 16 built nodes
    (and 64 in two node blocks) for every width (PERF.md, PR 39)::

        width   1,185,792 x 968 (slots)            401,408 x 2,000 (slots)
        72      26.38  51.42 101.37 (1,008)   17.87 34.82 68.74 272.54 (2,016)
        80      27.10  52.92 104.44 (1,040)   17.54 34.35 68.01 270.00 (2,000)
        88      24.97  48.99  96.93   (968)   17.76 34.76 68.80 273.19 (2,024)
        96      27.28  53.48 105.77 (1,056)   17.62 34.55 68.44 271.95 (2,016)
        104     26.75  52.54 104.03 (1,040)   18.12 35.58 70.54 280.44 (2,080)
        112     25.85  50.85 100.75 (1,008)   17.51 34.43 68.32 271.71 (2,016)
        120     27.66  54.43 107.89 (1,080)   17.68 34.80 69.08 274.84 (2,040)
        128     26.14  51.52 102.20 (1,024)   17.70 34.89 69.30 275.81 (2,048)

    ``ms = slots x a(nodes) + steps x 0.17-0.21 us`` to 0.1 ms a call, and
    a width that divides F reads 0.06-0.10 ms under it.  A block more is a
    row of grid steps more: 4.2, 2.3, 1.2 feature slots' worth at 1, 4, 16
    built nodes, 2.1 over the six calls of a depth-6 round, which
    ``_BLOCK_CHARGE`` is.  Blocks of 128 whose last skipped its empty
    tail under one ``pl.when`` read 0.09-0.38 ms a call OVER the dividing
    width (the branch costs every block 2% a slot) and did not ship.
    """
    widths = range(_NARROWEST_BLOCK, _LANES + 1, 8)
    return min(widths, key=lambda w: (-(-num_feature // w)
                                      * (w + _BLOCK_CHARGE), -w))


def hist_row_tile(block_features: int, rows=None) -> int:
    """Rows of one grid step of a call whose feature block holds
    ``block_features`` features, over ``rows`` rows a chip.  A pure
    function of the two static shapes; what ``gbdt.fit.dispatch`` records
    as ``row_tile``, what a fit pads its rows to, and what
    :func:`hist_matmul_pallas` cuts them by.

    A grid step costs 0.13-0.18 us beyond its dot, whatever it holds (on a
    v5e, at 13 and 28 features a step, 1 to 16 built nodes; 0.16 at a
    128-feature block; PERF.md, PR 37): 0.73-0.98 ms of a 7-27 ms call at
    HIGGS's 5,372 steps of ``BLOCK_ROWS`` rows.  So a step carries the
    row-features a 128-feature block's step does, ``128 * BLOCK_ROWS``: the
    largest ``BLOCK_ROWS * 2^k`` rows that stay under them, and under
    ``_ROW_TILE_CAP``.  28 features: 8,192; 13: 16,384; 65 and more, and
    so every blocked table: ``BLOCK_ROWS``.  Measured, ms a call at 1, 2,
    4, 8, 16 built nodes::

        tile      11,010,048 x 28                 16,777,216 x 13
        2,048      7.22 13.58 13.73 20.22 26.67    5.83 10.20 10.44 15.08 19.67
        4,096      6.85 13.21 13.29 19.74 26.19    5.19  9.65  9.77 14.35 18.94
        8,192      6.67 13.03 13.07 19.50 25.94    4.92  9.37  9.43 13.99 18.56
        16,384     6.58 12.94 12.96 19.39 25.82    4.78  9.23  9.26 13.81 18.37
        32,768     6.54 12.90 12.91 22.36 28.89    4.72  9.16  9.18 13.72 18.27

    Never more than the rows fill: a table whose rows, padded to
    ``BLOCK_ROWS``, are fewer than ``_ROW_TILES_FILLED`` such tiles keeps
    ``BLOCK_ROWS``, as do rows not yet known (``rows=None``).  Rows padded
    to the tile this gives are given the same tile again.  A call of so
    many nodes that its step would leave Mosaic's default VMEM at this tile
    runs at a half or a quarter of it (:func:`hist_matmul_pallas`: from 64
    built nodes at 256 bins and 13 or 28 features; no level of a depth-6
    fit).
    """
    if rows is None:
        return BLOCK_ROWS
    tile = BLOCK_ROWS
    while (tile < _ROW_TILE_CAP
           and block_features * 2 * tile <= _LANES * BLOCK_ROWS):
        tile *= 2
    filled = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    return tile if filled >= _ROW_TILES_FILLED * tile else BLOCK_ROWS


def _require_block_plan(num_nodes: int, num_feature: int, num_bins: int):
    """:func:`hist_block_plan`, or a ``ValueError`` where no block fits."""
    plan = hist_block_plan(num_nodes, num_feature, num_bins)
    if plan is None:
        raise ValueError(
            f"hist_method='pallas': no accumulator block fits VMEM at "
            f"num_bins={num_bins} (8 node slots x "
            f"{min(num_feature, _LANES)} features x {num_bins} bins of f32 "
            f"g and h exceed {_ACC_BYTES_LIMIT} bytes); use fewer bins, or "
            f"hist_method='scatter'")
    return plan


def hist_split_plan(num_nodes: int, num_bins: int):
    """``(H, L)``: how one kernel call of ``num_nodes`` nodes splits the bin
    index, ``bin = hi * L + lo`` with ``hi`` in ``[0, H)`` on the node's side
    of the product and ``lo`` in ``[0, L)`` on the other.  A pure function
    of the two static shapes; what ``gbdt.fit.dispatch`` records per level.

    Of every whole-tile ``L`` (the multiples of 16, the bf16 tile's
    sublanes, up to the power of two at or above ``num_bins``) with ``H =
    ceil(num_bins / L)``, the one whose dot is cheapest by
    :func:`_dot_cycles`; ties go to the fewest one-hot words built a lane
    (``K + L / 2``, ``K`` the node side's :func:`_key_rows`), then to the
    smaller ``L``.  At 256 bins: (16, 16) at one node, (8, 32) at 2 and 4,
    (6, 48) at 8, (4, 64) at 16, (2, 128) at 32 and 64, (1, 256) from 128.  A
    table of 16 bins or fewer is not split.  ``H * L >= num_bins``; pairs
    past ``num_bins`` (bins 256-287 at (6, 48); 255, 257 bins) are columns
    no row matches.
    """
    most = 1 << max(4, (num_bins - 1).bit_length())

    def cost(lo):
        keys = _key_rows(num_nodes, -(-num_bins // lo))
        return _dot_cycles(keys, lo), keys + lo // 2, lo

    lo = min(range(16, most + 1, 16), key=cost)
    return -(-num_bins // lo), lo


def _key_rows(num_nodes: int, hi: int) -> int:
    """int32 sublanes of the node-side operand: one per (node, hi) key,
    padded to the int32 tile (8) with keys no row carries."""
    return -(-num_nodes * hi // 8) * 8


def _dot_cycles(keys: int, lo: int) -> int:
    """MXU cycles of one feature's dot a 1,024-row tile, ``LO[L, rows] .
    A[2K, rows]^T``, as measured on a v5e: ``max(2K, 2L)`` for every 128 of
    the node side's ``2K`` bf16 rows (PERF.md, PRs 28 and 34)."""
    return -(-2 * keys // _LANES) * max(min(2 * keys, _LANES), 2 * lo)


def _bin_split(hi: int, lo: int):
    """The body's ``bin -> (hi_i, lo_i)`` by a static ``L``, on an int32
    array: a shift and a mask where ``L`` is a power of two; otherwise, ``L``
    being ``16 * m``, the quotient of ``bin >> 4`` by ``m`` as a multiply and
    a shift, ``(t * mul) >> s == t // m`` on every ``t`` of ``[0, H * m)``
    (``mul = ceil(2^s / m)``, the least ``s`` whose error stays under one
    step there; inside int32 for any bin count whose accumulator fits
    VMEM), and the remainder from it."""
    if lo & (lo - 1) == 0:
        shift = lo.bit_length() - 1
        return lambda b: (b >> shift, b & (lo - 1))
    m, top = lo // 16, hi * lo // 16 - 1
    shift = next(s for s in range(m.bit_length(), 31)
                 if (-(1 << s) % m) * top < 1 << s)
    mul = -(-(1 << shift) // m)

    def split(b):
        high = ((b >> 4) * mul) >> shift
        return high, b - high * lo

    return split


def hist_kernel_name(num_nodes: int, level=None) -> str:
    """What one kernel call is named in the compiled program and so in a
    profile (``%hist_level_L4_n8.52 = ... custom-call``): the node slots it
    builds, and the tree level where the caller says one (a fit's
    ``_build_tree``; the one-shot ``grad_histogram`` has none:
    ``hist_level_n32``).  The one rule a trace's reader rests on, which it
    takes from ``gbdt.fit.dispatch``'s ``level_kernels`` and never
    rebuilds: the name starts with ``hist_level``, is static, and does not
    end in ``.<digits>``, the one suffix a reader strips to group an
    instruction's copies."""
    at = "" if level is None else f"_L{level}"
    return f"hist_level{at}_n{num_nodes}"


# bf16 1.0 in the low / high half of an int32 word
_ONE_LOW, _ONE_HIGH = 0x3F80, 0x3F800000

# features of a block the tile body is unrolled over, a whole blocked block:
# straight-line code is what lets the scheduler build one feature's operands
# under the previous feature's dot (a loop of single features took 1.75x as
# long, groups of 16 6-25% longer; PERF.md, PR 28).  A wider block (a table
# of few bins that fits unblocked) loops over groups of these.
_UNROLL = _LANES


def _kernel(node_ref, g_ref, h_ref, bins_ref, out_ref, *, num_nodes: int,
            hi: int, lo: int, num_feature: int, node_blocks: int):
    """One (node block, feature block, row tile) step: zero the resident
    accumulator at the block's first tile, then per feature build ``A`` and
    ``LO`` from the tile's row vectors and accumulate ``LO . A^T``.
    ``num_nodes`` is a node block's slots; with more than one block the
    step's block base is taken off the node ids, so the rows of every other
    block fall outside ``[0, num_nodes)`` and drop out below.

    Both operands are built two bf16 rows to an int32 word, the pair that
    ``pltpu.bitcast`` unfolds along the sublanes (low half first): a key's
    g and h, and the ones of ``lo`` = 2k and 2k + 1.  So ``A`` costs one
    compare and one select per KEY and tile lane, ``LO`` one per two bins.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    split = _bin_split(hi, lo)
    node = node_ref[:]                                       # [1, TB]
    if node_blocks > 1:
        node = node - pl.program_id(0) * num_nodes
    # a row whose node is outside [0, n) gets a key no iota value equals
    base = jnp.where((node >= 0) & (node < num_nodes), node * hi, -(1 << 30))

    def bf16_bits(x):
        return pltpu.bitcast(x.astype(jnp.bfloat16).astype(jnp.float32),
                             jnp.int32)

    gh = jax.lax.shift_right_logical(bf16_bits(g_ref[:]), 16) \
        | bf16_bits(h_ref[:])                                # g low, h high
    key_iota = jax.lax.broadcasted_iota(
        jnp.int32, (_key_rows(num_nodes, hi), 1), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (lo // 2, 1), 0)

    def one_feature(f):
        b = bins_ref[pl.ds(f, 1), :]                         # [1, TB]
        high, low = split(b)
        a = jnp.where(key_iota == base + high, gh, 0)
        one = jnp.where((low & 1) == 1, _ONE_HIGH, _ONE_LOW)
        lo_hot = jnp.where(lo_iota == (low >> 1), one, 0)
        out_ref[f] += jax.lax.dot_general(
            pltpu.bitcast(lo_hot, jnp.bfloat16),             # [L, TB]
            pltpu.bitcast(a, jnp.bfloat16),                  # [2 keys, TB]
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def group(k, carry):
        for u in range(_UNROLL):
            one_feature(k * _UNROLL + u)
        return carry

    # a block of at most _UNROLL features is straight-line code throughout
    groups = num_feature // _UNROLL if num_feature > _UNROLL else 0
    if groups:
        jax.lax.fori_loop(0, groups, group, 0)
    for f in range(groups * _UNROLL, num_feature):
        one_feature(f)


def hist_matmul_pallas(rows, bins, num_bins: int, *, num_nodes: int,
                       block_rows=None, block_features=None,
                       block_nodes=None, level=None):
    """``out[c*n + k, f*nbins + b] = sum_i [node_i == k] * (g_i, h_i)[c] *
    (bins[f, i] == b)``: the kernel's entry, one ``hist_level`` call.

    Args:
      rows: the per-row ``(node, g, h)``, each ``[B]``: int32 node ids (a row
        whose id is outside ``[0, num_nodes)`` adds nothing) and f32 g, h.
      bins: [F, B] int32 binned features in [0, num_bins), feature-major.
      num_bins, num_nodes: static.
      block_rows: row-tile size (B is padded up to a multiple internally);
        None takes :func:`hist_row_tile` of the feature block and B, halved
        while a deep level's step would outgrow Mosaic's default VMEM.
      block_features: features per accumulator block (a multiple of 8);
        None or >= F keeps all F in one block.  The body runs all of a
        block's feature slots, those of a short last block too: the plan's
        width (:func:`hist_feature_block`) leaves the fewest empty.
      block_nodes: node slots per accumulator block; None or >= num_nodes
        keeps all of them in one block.  The bin index is split for a
        block's slots (:func:`hist_split_plan`); a last block that is
        short holds slots no row carries.
      level: the tree level this call builds, a label for the call's name
        alone (:func:`hist_kernel_name`); None where the caller has none.

    Returns [2*num_nodes, F*num_bins] float32, the kernel's
    ``[node blocks, F, L, 2nH]`` transposed back.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    node, g, h = (jnp.asarray(r)[None, :] for r in rows)
    bf, b = bins.shape
    if block_features is None or block_features >= bf:
        block_features = bf
    if block_nodes is None or block_nodes >= num_nodes:
        block_nodes = num_nodes
    hi, lo = hist_split_plan(block_nodes, num_bins)
    cols = 2 * _key_rows(block_nodes, hi)
    # node blocks on the OUTERMOST axis, feature blocks inside them, row
    # tiles innermost: the accumulator block moves only when a (node,
    # feature) block's rows are all in, and the row vectors' tiles are the
    # same for every block.  A node block re-reads the bins and re-builds
    # every one-hot; a feature block re-reads only the row vectors.  F need
    # not divide: the last block's features beyond F read unspecified bins
    # whose histograms lie beyond the output and are never written back.
    # One buffer for an output block whose index moves — Pallas would keep
    # two, and the budget is for one.
    node_blocks = pl.cdiv(num_nodes, block_nodes)
    blocks = pl.cdiv(bf, block_features)
    moves = node_blocks * blocks > 1
    out_buffering = {"pipeline_mode": pl.Buffered(1)} if moves else {}

    def held(tile):
        """VMEM the call holds at a row tile, as Mosaic tiles it: the
        accumulator (its minor extent padded to the lanes: few bins or few
        nodes leave a tile mostly empty, which the byte rule of
        hist_block_plan does not count), the bins tile twice, the two
        operands.  Within Mosaic's default for every blocked shape; an
        unblocked table of many narrow features asks for what it needs."""
        return ((1 if moves else 2) * block_features * lo
                * -(-cols // _LANES) * _LANES * 4
                + 2 * block_features * tile * 4 + (cols + lo) * tile * 2)

    if block_rows is None:
        # the operands grow with a level's key rows: a widened tile is kept
        # only while the call stays within what Mosaic gives unasked.  Past
        # it a call took 6-12% longer (64 built nodes at 28 features x
        # 8,192 rows or 13 x 16,384; 128 at half those; PERF.md, PR 37); a
        # half or a quarter of the fit's tile still divides its rows
        block_rows = hist_row_tile(block_features, b)
        while block_rows > BLOCK_ROWS and held(block_rows) > _VMEM_UNASKED:
            block_rows //= 2
    if b % block_rows:
        pad = ((0, 0), (0, block_rows - b % block_rows))
        node = jnp.pad(node, pad, constant_values=-1)        # no key
        g, h, bins = jnp.pad(g, pad), jnp.pad(h, pad), jnp.pad(bins, pad)
        b += pad[1][1]
    vmem = held(block_rows)
    params = {}
    if vmem > _VMEM_UNASKED:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem + _VMEM_UNASKED // 3)
    row_spec = pl.BlockSpec((1, block_rows), lambda n, j, i: (0, i),
                            memory_space=pltpu.VMEM)
    kernel = functools.partial(_kernel, num_nodes=block_nodes, hi=hi, lo=lo,
                               num_feature=block_features,
                               node_blocks=node_blocks)
    out = pl.pallas_call(
        kernel,
        grid=(node_blocks, blocks, b // block_rows),
        in_specs=[row_spec, row_spec, row_spec,
                  pl.BlockSpec((block_features, block_rows),
                               lambda n, j, i: (j, i),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((None, block_features, lo, cols),
                               lambda n, j, i: (n, j, 0, 0),
                               memory_space=pltpu.VMEM, **out_buffering),
        out_shape=jax.ShapeDtypeStruct((node_blocks, bf, lo, cols),
                                       jnp.float32),
        interpret=interpret_mode(),
        name=hist_kernel_name(num_nodes, level),
        **params,
    )(node.astype(jnp.int32), g.astype(jnp.float32), h.astype(jnp.float32),
      bins)
    # [block, F, lo, (node, hi, c)] -> [(c, block, node), (F, hi, lo)]; the
    # short last block's slots past num_nodes and the bins past num_bins
    # (no row has either) dropped
    out = out[..., :2 * block_nodes * hi].reshape(
        node_blocks, bf, lo, block_nodes, hi, 2)
    out = out.transpose(5, 0, 3, 1, 4, 2).reshape(
        2, node_blocks * block_nodes, bf, hi * lo)
    return out[:, :num_nodes, :, :num_bins].reshape(2 * num_nodes,
                                                    bf * num_bins)


def grad_hist_pallas(bins, node_ids, grad, hess, num_nodes: int,
                     num_bins: int, level=None):
    """Per-(node, feature, bin) gradient/hessian sums via the VMEM kernel.

    ``bins`` is FEATURE-MAJOR, ``[F, B]`` int32 — the layout the kernel
    reads (``HistPlan.layouts`` makes it); otherwise the contract of
    :func:`.histogram.grad_histogram`: returns (G, H) each
    [num_nodes, F, num_bins] float32.  Rows with out-of-range (e.g.
    negative) node ids contribute nothing.

    ONE ``hist_level`` call for any width and depth: a level too wide or
    deep for one resident accumulator runs blocked (:func:`hist_block_plan`)
    and node blocks, like feature blocks, are steps of that call's grid;
    ``level`` is the label of its name (:func:`hist_kernel_name`).
    """
    import jax.numpy as jnp

    bf = bins.shape[0]
    block_nodes, block_features = _require_block_plan(num_nodes, bf,
                                                      num_bins)
    out = hist_matmul_pallas(
        (node_ids.astype(jnp.int32), grad, hess), bins, num_bins,
        num_nodes=num_nodes, block_features=block_features,
        block_nodes=block_nodes, level=level).reshape(2, num_nodes, bf,
                                                      num_bins)
    return out[0], out[1]


# mesh axis name the whole package shards batch rows over (parallel/mesh.py
# data_sharding default); the sharded hist uses it for its psum axis
DATA_AXIS = "data"


def ambient_mesh():
    """The Mesh of an enclosing ``with mesh:`` block, or None outside one.

    hist_kernel_plan reads this once per fit to shard_map the kernel for
    sharded runs; callers opt in simply by fitting under their mesh (the
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


def hist_kernel_plan(model_axis, num_feature: int, max_depth: int,
                     num_bins: int, batch=None, pads: bool = False) -> dict:
    """Settle the kernel of one fit against the ambient mesh, ONCE, before
    tracing: none of it depends on the level.  Returns the kernel's share of
    a :class:`~dmlc_core_tpu.ops.histogram.HistPlan`:

    - ``mesh``: the mesh to shard_map the kernel over, None for one plain
      call.  A Mosaic kernel has no GSPMD partitioning rule; on a TPU, jit
      refuses one over sharded operands ("Mosaic kernels cannot be
      automatically partitioned").  So under a mesh that actually shards —
      a ``model_axis``, or a data axis wider than one device — the kernel
      runs inside shard_map: rows over data, features over model;
    - ``row_tile``: the rows of a grid step, :func:`hist_row_tile` of one
      chip's feature block and its share of ``batch`` rows (one tile a fit:
      a block of 64 features or fewer is the whole table's at any node
      count; a level of 64 built nodes or more may run at a half or a
      quarter of it, :func:`hist_matmul_pallas`);
    - ``row_multiple``: rows a fit pads to once so no kernel call pads
      again, the tile times the data axis (each data shard must itself be
      a whole number of tiles under shard_map);
    - what ``gbdt.fit.dispatch`` records: ``built_nodes``, the node slots
      each level's call builds from the root (one child of every pair
      below it, ``histogram.hist_built_nodes``), ``level_node_blocks``
      (the grid steps over nodes of each level's one call),
      ``feature_blocks`` (grid steps over features inside each node
      block) and ``block_features`` (the features of one such step,
      :func:`hist_feature_block`: 25 and 80 at 2,000 features, 11 and 88
      at 968, 1 and all F of a table that is one block) of one chip's
      ``F/mp`` slice,
      ``bin_split``, the :func:`hist_split_plan` ``HxL`` of every level's
      call, that of a node block's slots where the level has several, and
      ``level_kernels``, every level's :func:`hist_kernel_name`.

    A mesh the kernel cannot be shard_mapped over raises a ``ValueError``
    that names the condition and the remedy — nothing falls back: a
    ``model_axis`` that is no axis of an enclosing ``with mesh:``, features
    that do not divide the model axis, ``batch`` rows that do not divide the
    data axis (not checked for a caller that ``pads`` them to
    ``row_multiple`` itself, a compiled fit, nor where ``batch`` is None:
    rows not known yet, which get the base tile).  Width and depth never
    raise, :func:`hist_block_plan` blocks them; only bins in the tens of
    thousands leave no block that fits.
    """
    mesh = ambient_mesh()
    dp = _data_parallelism(mesh)
    mp = 1
    if model_axis is not None:
        mp = None if mesh is None else mesh.shape.get(model_axis)
        if mp is None:
            raise ValueError(
                f"hist_method='pallas': model_axis={model_axis!r} is not an "
                f"axis of an enclosing `with mesh:` block ("
                f"{'no mesh' if mesh is None else tuple(mesh.shape)}); "
                f"trace the fit under the mesh that has it, or drop "
                f"model_axis")
    if num_feature % mp:
        raise ValueError(
            f"hist_method='pallas': num_feature={num_feature} does not "
            f"divide over the {mp} shards of model axis {model_axis!r}; pad "
            f"the features to a multiple of {mp}, or choose a model axis "
            f"that divides them")
    if batch is not None and not pads and batch % dp:
        raise ValueError(
            f"hist_method='pallas': {batch} rows do not divide over the "
            f"{dp} shards of the {DATA_AXIS!r} mesh axis; pad rows as "
            f"`fit_binned` does (weight-0 rows, to a multiple of {dp})")
    from dmlc_core_tpu.ops.histogram import hist_built_nodes

    local = num_feature // mp
    built = hist_built_nodes(max_depth)
    # a level's block: all its slots, or a power of two under them
    plans = [_require_block_plan(n, local, num_bins) for n in built]
    feats = plans[-1][1]
    splits = (hist_split_plan(slots, num_bins) for slots, _ in plans)
    steps = [-(-n // slots) for n, (slots, _) in zip(built, plans)]
    sharded = model_axis is not None or dp > 1
    tile = hist_row_tile(feats, None if batch is None else -(-batch // dp))
    return {"mesh": mesh if sharded else None,
            "row_tile": tile,
            "row_multiple": tile * dp,
            "level_node_blocks": ",".join(map(str, steps)),
            "feature_blocks": -(-local // feats),
            "block_features": feats,
            "bin_split": ",".join(f"{hi}x{lo}" for hi, lo in splits),
            "built_nodes": ",".join(map(str, built)),
            "level_kernels": ",".join(hist_kernel_name(n, level)
                                      for level, n in enumerate(built))}


def grad_hist_pallas_sharded(bins, node_ids, grad, hess, num_nodes: int,
                             num_bins: int, mesh, model_axis=None,
                             level=None):
    """shard_map-wrapped VMEM hist: rows dp-sharded, features model-sharded.

    The only way the Pallas kernel runs on more than one device: each shard
    runs the VMEM kernel on its local rows and partial histograms are
    psummed over the data axis — the distributed-hist aggregation XGBoost
    does over Rabit.  ``bins`` is feature-major ``[F, B]`` as the kernel
    reads it.  With a ``model_axis`` each model shard also slices its
    own ``F/mp`` feature rows (bins arrive feature-replicated) and the
    output is ``P(None, model_axis, None)`` — exactly the constraint the
    GSPMD path advertises, so split-finding code downstream is unchanged;
    without one the output is replicated.  ``level`` is the label of the
    kernel's name (:func:`hist_kernel_name`).

    Requires ``F % mesh.shape[model_axis] == 0`` and rows that divide the
    data axis: what :func:`hist_kernel_plan` checks before it hands out the
    mesh.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    row_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
    f_local = (bins.shape[0] if model_axis is None
               else bins.shape[0] // mesh.shape[model_axis])

    def local_hist(b, n, g, h):
        if model_axis is not None:
            idx = jax.lax.axis_index(model_axis)
            b = jax.lax.dynamic_slice_in_dim(b, idx * f_local, f_local,
                                             axis=0)
        G, H = grad_hist_pallas(b, n.astype(jnp.int32), g, h, num_nodes,
                                num_bins, level)
        if row_axis is not None:
            G = jax.lax.psum(G, row_axis)
            H = jax.lax.psum(H, row_axis)
        return G, H

    out_spec = P(None, model_axis, None)
    # check_vma off: pallas_call's out_shape carries no vma annotation; the
    # psum above already makes the outputs data-axis-invariant
    return jax.shard_map(
        local_hist, mesh=mesh,
        in_specs=(P(None, row_axis), P(row_axis), P(row_axis), P(row_axis)),
        out_specs=(out_spec, out_spec), check_vma=False,
    )(bins, node_ids, grad, hess)
