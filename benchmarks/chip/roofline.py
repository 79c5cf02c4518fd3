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
    """``(flops, bytes)`` of one level's gradient histogram in the one-hot
    matmul formulation the kernel implements: ``[2n, rows] @ [rows,
    F*bins]`` with the LIVE node count n (rows the kernel pads up to its
    bf16 tile do no useful work and are not counted).  Bytes are what the
    call must move through HBM at least once: the bf16 weight matrix
    ``[2n, rows]``, the int32 bins ``[rows, F]`` as the kernel is handed
    them, and the f32 result."""
    m = 2 * num_nodes
    flops = 2.0 * m * rows * num_feature * num_bins
    nbytes = rows * (2.0 * m + 4.0 * num_feature) \
        + 4.0 * m * num_feature * num_bins
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, device_kind: str):
    """``(seconds, which)``: the larger of compute and memory time at the
    published peaks, and which of the two bounds."""
    p = peaks(device_kind)
    compute = flops / p["bf16_flops_per_s"]
    memory = nbytes / p["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
