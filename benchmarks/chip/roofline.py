"""Operations and bytes a kernel needs, from its shapes, and the least time
the chip could take by the peaks table.  Kept with the benchmark so that a
PR that changes a kernel cannot change what it is measured against."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has: "
                       f"{[k for k in table if not k.startswith('_')]})")
    return table[device_kind]


def hist_level_work(rows: int, num_feature: int, num_bins: int,
                    num_nodes: int):
    """``(flops, bytes)`` of ONE product that builds ``num_nodes`` nodes'
    gradient histograms by summation, in the one-hot matmul formulation:
    ``[2n, rows] @ [rows, F*bins]`` with the LIVE node count n (rows the
    kernel pads up to its bf16 tile do no useful work and are not
    counted).  Bytes are what the call must move through HBM at least
    once: a bf16 weight matrix ``[2n, rows]``, the int32 bins (``rows x
    F``, whichever way round the kernel is handed them), and the f32
    result.  How many nodes a level must build is
    :func:`hist_built_nodes`'s to say, not this function's."""
    m = 2 * num_nodes
    flops = 2.0 * m * rows * num_feature * num_bins
    nbytes = rows * (2.0 * m + 4.0 * num_feature) \
        + 4.0 * m * num_feature * num_bins
    return flops, nbytes


def hist_built_nodes(max_depth: int):
    """Nodes each level of one tree must build BY SUMMATION: 1 at the root
    and ``2 ** (depth - 1)`` below it, one child of every pair.  Depth 6:
    1, 1, 2, 4, 8, 16 = 32 node-products (63 if every node were built)."""
    return [1 if depth == 0 else 2 ** (depth - 1)
            for depth in range(max_depth)]


def hist_round_work(rows: int, num_feature: int, num_bins: int,
                    max_depth: int):
    """``[(flops, bytes), ...]``, one entry a level: the LEAST work one
    boosting round's histograms need, whatever implements them.

    Where the count comes from: every configuration's ``source`` names
    XGBoost's ``tree_method=hist``, and in that algorithm a level below the
    root sums the rows of one child of each pair and takes the sibling as
    the parent's histogram minus it (XGBoost's ``hist`` updater and
    LightGBM both build one child and subtract).  So it is the task's
    work, not an optimisation's: a kernel that builds every node does 63
    node-products a depth-6 round where the task needs 32, and reads at
    most half its roofline at the deep levels; one that builds a child of
    each pair at the bf16 peak reads 100%, and none can pass it without
    leaving work out.  A level below the root also moves the parents' f32
    histograms in and the derived siblings out (``[n, 2, F, bins]`` each
    for n built nodes): bytes and no product."""
    work = []
    for depth, built in enumerate(hist_built_nodes(max_depth)):
        flops, nbytes = hist_level_work(rows, num_feature, num_bins, built)
        if depth:
            nbytes += 2 * 4.0 * 2 * built * num_feature * num_bins
        work.append((flops, nbytes))
    return work


def least_seconds(flops: float, nbytes: float, device_kind: str):
    """``(seconds, which)``: the larger of compute and memory time at the
    published peaks, and which of the two bounds."""
    p = peaks(device_kind)
    compute = flops / p["bf16_flops_per_s"]
    memory = nbytes / p["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
