#!/usr/bin/env python
"""Allreduce bandwidth sweep (BASELINE.json metric "Rabit->ICI allreduce
GB/s"): effective algorithm bandwidth vs message size over a mesh axis.

    python benchmarks/bench_collective.py [axis_size] [sizes_mb...]

On a real pod the axis spans ICI; on a dev host set
XLA_FLAGS=--xla_force_host_platform_device_count=N for a virtual mesh
(correctness/shape validation — the GB/s is then host-memory bandwidth, not
ICI). Prints one JSON line per message size.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    from dmlc_core_tpu.collective.mesh_collectives import (
        allreduce_bandwidth_gbps)
    from dmlc_core_tpu.device import init_device
    from dmlc_core_tpu.parallel.mesh import make_mesh

    init_device()
    args = sys.argv[1:]
    ndev = len(jax.devices())
    axis = int(args[0]) if args else ndev
    sizes_mb = [float(s) for s in args[1:]] or [1, 4, 16, 64]
    mesh = make_mesh({"data": axis}, devices=jax.devices()[:axis])
    backend = jax.devices()[0].platform
    for mb in sizes_mb:
        gbps = allreduce_bandwidth_gbps(mesh, "data", nbytes=int(mb * 2**20))
        print(json.dumps({
            "metric": "allreduce_algbw_gbps",
            "value": round(gbps, 3),
            "unit": f"GB/s ({mb} MB message, {axis}-way, {backend})",
        }))


if __name__ == "__main__":
    main()
