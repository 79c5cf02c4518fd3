"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference predates LLMs (SURVEY.md §5.7): its only "long input" machinery
is streamed chunked reads.  The TPU-native framework makes long-context
first-class with the two standard sequence-parallel schemes, built on XLA
collectives over ICI:

- :func:`ring_attention` — blockwise attention with the KV shard rotating
  around the mesh-axis ring via ``lax.ppermute``, combined with the online
  (flash-style) softmax accumulator, so sequences scale with the number of
  devices while each device only ever holds its own Q shard and one KV block.
  Communication overlaps compute under XLA's scheduler (ppermute is async).
- :func:`ulysses_attention` — all-to-all resharding: sequence-sharded inputs
  are transposed to head-sharded via ``lax.all_to_all``, attention runs
  locally over full sequence length per head group, and the output transposes
  back.  Right when heads >= devices and full-sequence kernels are preferred.

Both are exact (match full attention to float tolerance) and jit-compiled via
shard_map over a named mesh axis.  Both are differentiable — jax autodiff
composes through the ppermute scan / all_to_all, and the gradients match
full-attention gradients (tests/test_sequence.py) — so long-context
TRAINING, not just inference, rides these paths.
"""

from __future__ import annotations

import functools
from typing import Optional

from dmlc_core_tpu.utils.logging import CHECK

__all__ = ["ring_attention", "ulysses_attention", "reference_attention"]


def reference_attention(q, k, v, causal: bool = False, sm_scale: Optional[float] = None):
    """Plain full attention (the correctness oracle). Shapes [B, L, H, D]."""
    import jax.numpy as jnp

    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        L, Lk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(Lk)[None, :] > jnp.arange(L)[:, None]
        s = jnp.where(mask[None, None], -jnp.inf, s)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _ring_attention_local(q, k, v, axis: str, axis_size: int, causal: bool,
                          sm_scale: Optional[float]):
    """Per-shard kernel: local Q stays put, KV blocks rotate the ring."""
    import jax.lax as lax
    import jax.numpy as jnp

    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    my = lax.axis_index(axis)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    neg_inf = jnp.finfo(jnp.float32).min

    def step(carry, t):
        o, m, l, k_cur, v_cur = carry
        # the block we hold at step t originated at rank (my - t) mod n
        src = (my - t) % axis_size
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = my * Lq + jnp.arange(Lq)
            k_pos = src * Lk + jnp.arange(Lk)
            mask = k_pos[None, :] > q_pos[:, None]
            s = jnp.where(mask[None, None], neg_inf, s)
        m_new = jnp.maximum(m, s.max(-1))
        # rows with no visible keys yet keep m at -inf; guard the exp
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        if causal:
            p = jnp.where(mask[None, None], 0.0, p)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + p.sum(-1)
        o_new = (o * alpha.transpose(0, 2, 1)[..., None]
                 + jnp.einsum("bhqk,bkhd->bqhd", p, v_cur,
                              preferred_element_type=jnp.float32))
        k_next = lax.ppermute(k_cur, axis, perm)
        v_next = lax.ppermute(v_cur, axis, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    o0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Lq), neg_inf, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    # mark the fresh accumulators as device-varying over the ring axis so the
    # scan carry type matches the per-shard outputs (jax >= 0.6 vma).  pcast
    # is the current spelling; pvary its deprecated predecessor (probe pcast
    # FIRST — jax 0.9 fires the DeprecationWarning even on hasattr(pvary)).
    if hasattr(lax, "pcast"):
        o0, m0, l0 = (lax.pcast(x, (axis,), to="varying")
                      for x in (o0, m0, l0))
    elif hasattr(lax, "pvary"):
        o0, m0, l0 = (lax.pvary(x, (axis,)) for x in (o0, m0, l0))
    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v),
                                  jnp.arange(axis_size))
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _ring_fn(mesh, axis: str, causal: bool, sm_scale):
    import jax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    spec = P(None, axis, None, None)

    def kernel(q, k, v):
        return _ring_attention_local(q, k, v, axis, n, causal, sm_scale)

    return jax.jit(jax.shard_map(kernel, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec))


def ring_attention(q, k, v, mesh, axis: str = "data", causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Exact attention over sequence-sharded [B, L, H, D] inputs.

    L must divide by the axis size; each device holds L/n of Q, K, V and peak
    memory is O(L/n * L/n) per step instead of O(L^2).
    """
    CHECK(q.shape[1] % mesh.shape[axis] == 0,
          "sequence length must divide the mesh axis size")
    return _ring_fn(mesh, axis, causal, sm_scale)(q, k, v)


@functools.lru_cache(maxsize=None)
def _ulysses_fn(mesh, axis: str, causal: bool, sm_scale):
    import jax
    import jax.lax as lax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    spec = P(None, axis, None, None)

    def kernel(q, k, v):
        # [B, L/n, H, D] -> [B, L, H/n, D]: split heads, gather sequence
        def to_heads(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        def to_seq(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
        oh = reference_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale)
        return to_seq(oh)

    return jax.jit(jax.shard_map(kernel, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec))


def ulysses_attention(q, k, v, mesh, axis: str = "data", causal: bool = False,
                      sm_scale: Optional[float] = None):
    """Exact attention via all-to-all head/sequence resharding.

    Requires H % axis_size == 0 and L % axis_size == 0.
    """
    n = mesh.shape[axis]
    CHECK(q.shape[2] % n == 0, "num heads must divide the mesh axis size")
    CHECK(q.shape[1] % n == 0, "sequence length must divide the mesh axis size")
    return _ulysses_fn(mesh, axis, causal, sm_scale)(q, k, v)
