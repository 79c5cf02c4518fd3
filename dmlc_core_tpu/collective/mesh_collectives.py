"""In-program collectives over a named mesh axis (the ICI data plane).

These are the building blocks a training step uses *inside* jit/shard_map —
replacing Rabit's tree allreduce with XLA collectives that ride ICI within a
slice and DCN across slices (the design center of SURVEY.md §5.8).

:class:`MeshCollective` compiles allreduce/allgather/reducescatter/broadcast
for a given mesh axis once and reuses the executable (jit caching), plus a
benchmark helper reporting effective allreduce GB/s — the BASELINE.json
"Rabit→ICI allreduce GB/s" metric.

:func:`ring_allreduce` is an explicit ``lax.ppermute`` ring
(reduce-scatter + all-gather), provided both as a reference for custom
overlap patterns (the scaling-book recipe) and as a cross-check that XLA's
built-in ``psum`` beats a hand-rolled ring — it should, and bench.py verifies.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from dmlc_core_tpu.utils.logging import CHECK
from dmlc_core_tpu.utils.timer import get_time

__all__ = ["MeshCollective", "ring_allreduce", "allreduce_bandwidth_gbps"]


class MeshCollective:
    """Compiled collectives over one axis of a Mesh."""

    def __init__(self, mesh, axis: str = "data"):
        CHECK(axis in mesh.axis_names, f"axis {axis!r} not in mesh {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self.axis_size = mesh.shape[axis]
        # compiled-fn cache lives on the instance (NOT functools.lru_cache on
        # bound methods, which pins self/mesh in a global cache forever — a
        # leak in long-lived jobs that build many meshes)
        self._fn_cache: dict = {}

    def _cached(self, key, builder):
        fn = self._fn_cache.get(key)
        if fn is None:
            fn = self._fn_cache[key] = builder()
        return fn

    def _shard_map(self, fn, in_spec, out_spec):
        import jax

        return jax.jit(jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_spec, out_specs=out_spec))

    def _allreduce_fn(self, op: str):
        return self._cached(("allreduce", op),
                            lambda: self._build_allreduce(op))

    def _build_allreduce(self, op: str):
        import jax
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        reducers = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin}
        CHECK(op in reducers, f"unknown op {op!r}")
        red = reducers[op]
        axis = self.axis

        def kernel(x):
            return red(x, axis)

        # input sharded over the axis on dim 0, output likewise (allreduce of
        # per-shard partials -> every shard holds the same reduced value, so
        # the logical output is the reduction replicated along the axis)
        return self._shard_map(kernel, P(axis), P(axis))

    def allreduce(self, x, op: str = "sum"):
        """Reduce per-shard partials along the axis; every shard of the output
        holds the reduced value.  Input dim 0 must equal the axis size."""
        return self._allreduce_fn(op)(x)

    def _psum_scalar_fn(self):
        return self._cached("psum", self._build_psum)

    def _build_psum(self):
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        axis = self.axis

        def kernel(x):
            # caller contract: x.shape[0] == axis_size, so the local shard's
            # dim 0 is 1; drop it so the logical result is x.shape[1:]
            return lax.psum(x[0], axis)

        return self._shard_map(kernel, P(axis), P())

    def psum(self, x):
        """Sum shards along the axis, returning the unreplicated result
        (shape = x.shape[1:])."""
        import jax.numpy as jnp  # noqa: F401

        return self._psum_scalar_fn()(x)

    def _allgather_fn(self):
        return self._cached("allgather", self._build_allgather)

    def _build_allgather(self):
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        axis = self.axis

        def kernel(x):
            return lax.all_gather(x, axis, tiled=True)

        return self._shard_map(kernel, P(axis), P(axis))

    def allgather(self, x):
        """All-gather shards: output dim0 = axis_size * x.dim0 per shard."""
        return self._allgather_fn()(x)

    def _reduce_scatter_fn(self):
        return self._cached("reduce_scatter", self._build_reduce_scatter)

    def _build_reduce_scatter(self):
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        axis = self.axis

        def kernel(x):
            # caller contract: x is [axis_size, elems]; each shard contributes
            # its partial vector x[0] and receives its 1/axis_size slice of
            # the sum.
            return lax.psum_scatter(x[0], axis, scatter_dimension=0, tiled=True)

        return self._shard_map(kernel, P(axis), P(axis))

    def reduce_scatter(self, x):
        """Reduce [axis_size, elems] partials; shard i of the [elems] output
        holds slice i of the sum (elems must divide by axis_size)."""
        return self._reduce_scatter_fn()(x)

    def _broadcast_fn(self, root: int):
        return self._cached(("broadcast", root),
                            lambda: self._build_broadcast(root))

    def _build_broadcast(self, root: int):
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        size = self.axis_size

        def kernel(x):
            # select the root shard everywhere via a masked psum
            idx = lax.axis_index(axis)
            mask = (idx == root).astype(x.dtype)
            return lax.psum(x * mask, axis)

        return self._shard_map(kernel, P(axis), P(axis))

    def broadcast(self, x, root: int = 0):
        """Every output shard holds the root shard's value."""
        return self._broadcast_fn(root)(x)


# compiled ring kernels keyed by (mesh, axis): rebuilding the jit wrapper
# per ring_allreduce call emptied its compile cache every time, so every
# call paid a full retrace (jax.Mesh is hashable and meshes are few and
# long-lived, so a plain dict is the right cache)
_RING_FNS: dict = {}


def ring_allreduce(mesh, axis: str, x):
    """Explicit bidirectional-free ppermute ring allreduce
    (reduce-scatter phase + all-gather phase), shard_map'd over ``axis``.

    The per-shard input must be divisible into ``axis_size`` equal segments on
    dim 0."""
    fn = _RING_FNS.get((mesh, axis))
    if fn is None:
        fn = _RING_FNS[(mesh, axis)] = _build_ring_allreduce(mesh, axis)
    return fn(x)


def _build_ring_allreduce(mesh, axis: str):
    import jax
    import jax.lax as lax
    import jax.numpy as jnp  # noqa: F401
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]

    def kernel(x):
        segs = x.reshape((n, -1) + x.shape[1:])
        my = lax.axis_index(axis)

        # reduce-scatter: after n-1 steps, shard i holds the full sum of
        # segment (i+1) mod n
        def rs_step(k, acc_segs):
            send_idx = (my - k) % n
            chunk = acc_segs[send_idx]
            received = lax.ppermute(chunk, axis, perm_fwd)
            recv_idx = (my - k - 1) % n
            return acc_segs.at[recv_idx].add(received)

        segs = lax.fori_loop(0, n - 1, rs_step, segs)

        # all-gather: circulate each completed segment around the ring
        def ag_step(k, acc_segs):
            send_idx = (my - k + 1) % n
            chunk = acc_segs[send_idx]
            received = lax.ppermute(chunk, axis, perm_fwd)
            recv_idx = (my - k) % n
            return acc_segs.at[recv_idx].set(received)

        segs = lax.fori_loop(0, n - 1, ag_step, segs)
        return segs.reshape((-1,) + x.shape[1:])

    return jax.jit(jax.shard_map(kernel, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(axis)))


def allreduce_bandwidth_gbps(mesh, axis: str, nbytes: int = 64 << 20,
                             iters: int = 10, dtype=np.float32) -> float:
    """Measure effective allreduce bandwidth over the axis (the BASELINE.json
    'Rabit→ICI allreduce GB/s' metric): algbw = 2*(n-1)/n * bytes / time."""
    import jax
    import jax.numpy as jnp

    n = mesh.shape[axis]
    coll = MeshCollective(mesh, axis)
    elems_per_shard = max(1, nbytes // np.dtype(dtype).itemsize // max(n, 1))
    x = jnp.ones((n, elems_per_shard), dtype=dtype)
    fn = coll._psum_scalar_fn()
    jax.block_until_ready(fn(x))  # compile
    start = get_time()
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    elapsed = (get_time() - start) / iters
    payload = elems_per_shard * np.dtype(dtype).itemsize * n
    algbw = 2 * (n - 1) / max(n, 1) * payload / max(elapsed, 1e-12)
    return algbw / 1e9
