"""Seeded data for every cell: the same ``--seed`` gives the same inputs.

A configuration's ``data`` block describes its columns (``cardinality``:
0 = a standard-normal float column, k > 0 = integer values uniform in
``[0, k)``) and its teacher (a seeded linear model on the standardised
columns plus Gaussian noise, as ``bench.make_higgs_like`` and
``chip_smoke.data_phase`` draw it; those two are listed in PERF.md for
deletion).  What a label is, given the teacher's margin, is the
configuration's objective's to say (``objectives/<objective>.py``:
``latents`` teachers, ``label``); the host generators below stay binary
logistic.  With ``missing_share`` (``[F]`` floats) entry ``(row, f)`` is
absent (NaN) with that column's probability, independently, and an absent
entry adds ``missing_effect x a[f]`` to the teacher's margin where a
present one adds ``z x w[f]`` (:func:`absent_teacher`): absence carries
signal, so the default direction a split learns matters to the loss.
Two generators read that block:

- :func:`device_binned` makes the rows ON THE DEVICE in one jitted call
  (``jax.random``), bins them with the model's own boundaries and returns
  the uint8 wire form the fit consumes — nothing crosses the host;
- :func:`host_rows` / :func:`write_libsvm` make rows on the host in
  independent seeded chunks, for the text file the ingest cell parses and
  the request bodies the score cell sends.  The writer assembles the
  file's bytes as numpy arrays (fixed-width ``%.4f`` tokens), never row by
  row in Python.  They hold no absent entries: a configuration with
  ``missing_share`` is refused there.

This module imports numpy only; JAX is imported inside the device
functions, so the load generator's child process can use the host half.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 65_536        # host rows are seeded per chunk of this size
_WRITER_THREADS = 4        # numpy releases the GIL inside the big ufuncs
_EDGE_BLOCK = 16           # bin boundaries compared per pass over the rows


def columns(config):
    """``(cardinality[F] int64, mean[F], std[F])`` of a config's columns."""
    card = np.asarray(config["data"]["cardinality"], np.int64)
    if card.shape != (config["num_feature"],):
        raise ValueError(f"data.cardinality has {card.shape[0]} columns, "
                         f"num_feature is {config['num_feature']}")
    k = card.astype(np.float64)
    mean = np.where(card > 0, (k - 1) / 2, 0.0)
    std = np.where(card > 0, np.sqrt(np.maximum(k * k - 1, 1) / 12), 1.0)
    return card, mean.astype(np.float32), std.astype(np.float32)


def _latent_rng(seed, stream, latent):
    """Teacher ``latent``'s generator: the first is seeded as the only one
    always was, so a one-teacher objective's data never changes."""
    return np.random.default_rng([int(seed), stream, latent] if latent
                                 else [int(seed), stream])


def teacher(config, seed, latent=0):
    """The seeded linear teacher's weights, ``[F]`` float32 (teacher
    ``latent`` of an objective that draws several)."""
    rng = _latent_rng(seed, 0x7EAC, latent)
    return rng.standard_normal(config["num_feature"]).astype(np.float32)


def missing(config):
    """``(share[F] float32, effect)`` of a configuration whose ``data``
    block has ``missing_share``, else ``None``."""
    data = config["data"]
    if "missing_share" not in data:
        return None
    share = np.asarray(data["missing_share"], np.float32)
    if share.shape != (config["num_feature"],):
        raise ValueError(f"data.missing_share has {share.shape[0]} columns, "
                         f"num_feature is {config['num_feature']}")
    if not ((share >= 0) & (share < 1)).all():
        raise ValueError("data.missing_share lies in [0, 1)")
    return share, float(data.get("missing_effect", 0.0))


def reserved_bin(config):
    """The bin id the configuration's model keeps for absent entries
    (``model.handle_missing``: the last of ``num_bins``), or ``None``."""
    if (config.get("model") or {}).get("handle_missing"):
        return config["num_bins"] - 1
    return None


def absent_teacher(config, seed, latent=0):
    """``(add[F] float32, intercept)``: what an absent entry of column
    ``f`` adds to the teacher's margin (``missing_effect x a[f]``, ``a``
    seeded like ``w``), and the constant that takes the mean of those
    additions out again, so that the labels stay balanced whatever the
    seed."""
    share, effect = missing(config)
    rng = _latent_rng(seed, 0xAB5E, latent)
    a = (effect * rng.standard_normal(config["num_feature"])).astype(
        np.float32)
    return a, np.float32(-(share.astype(np.float64) * a).sum())


def _chunk_rng(seed, chunk):
    return np.random.default_rng([int(seed), 0xDA7A, int(chunk)])


def _host_chunk(config, seed, chunk, n):
    if missing(config) is not None:
        raise NotImplementedError(
            "the host generators write dense rows: a configuration with "
            "data.missing_share has no ingest or score cell yet")
    card, mean, std = columns(config)
    rng = _chunk_rng(seed, chunk)
    x = rng.standard_normal((n, card.shape[0]), dtype=np.float32)
    if (card > 0).any():
        u = rng.random((n, card.shape[0]), dtype=np.float32)
        x = np.where(card > 0, np.floor(u * card), x).astype(np.float32)
    noise = rng.standard_normal(n, dtype=np.float32)
    z = (x - mean) / std
    y = (z @ teacher(config, seed)
         + np.float32(config["data"]["label_noise"]) * noise > 0)
    return x, y.astype(np.float32)


def host_rows(config, seed, n):
    """``(x[n, F] float32, y[n] float32)`` made chunk by chunk from the
    seed; chunk ``c`` holds the same rows whatever ``n`` is."""
    xs, ys = [], []
    for c in range(-(-n // CHUNK_ROWS)):
        rows = min(CHUNK_ROWS, n - c * CHUNK_ROWS)
        x, y = _host_chunk(config, seed, c, rows)
        xs.append(x)
        ys.append(y)
    return np.concatenate(xs), np.concatenate(ys)


# -- libsvm text, assembled as bytes -----------------------------------------

def _libsvm_bytes(x, y):
    """One chunk of ``label j:v ...`` lines, ``%.4f`` values, as one bytes
    object.  Every token is 4 + len(str(j)) + 6 bytes wide: a positive value
    takes a second leading space where a negative one has its sign, so the
    whole chunk is a fixed-shape uint8 array filled by column."""
    n, f = x.shape
    if np.abs(x).max() >= 9.99995:
        raise ValueError("the fixed-width libsvm writer holds |v| < 10 "
                         "(standard-normal columns); got a larger value")
    q = np.rint(x.astype(np.float64) * 1e4).astype(np.int32)
    neg = q < 0
    mag = np.abs(q)
    digits = [(mag // 10 ** p % 10 + 48).astype(np.uint8)
              for p in (4, 3, 2, 1, 0)]
    parts = [np.where(y > 0.5, 49, 48).astype(np.uint8)[:, None]]
    for j in range(f):
        idx = np.frombuffer(str(j).encode(), np.uint8)
        width = 2 + idx.size + 1 + 6            # "  j:" or " j:-" + d.dddd
        tok = np.empty((n, width), np.uint8)
        lead_neg = np.concatenate([[32], idx, [58, 45]]).astype(np.uint8)
        lead_pos = np.concatenate([[32, 32], idx, [58]]).astype(np.uint8)
        tok[:, :lead_neg.size] = np.where(neg[:, j:j + 1], lead_neg,
                                          lead_pos)
        o = lead_neg.size
        tok[:, o] = digits[0][:, j]
        tok[:, o + 1] = 46
        for d in range(1, 5):
            tok[:, o + 1 + d] = digits[d][:, j]
        parts.append(tok)
    parts.append(np.full((n, 1), 10, np.uint8))
    return np.concatenate(parts, axis=1).tobytes()


def printed_values(x):
    """The float32 a parser reads back from :func:`_libsvm_bytes`' text."""
    return (np.rint(x.astype(np.float64) * 1e4) / 1e4).astype(np.float32)


def write_libsvm(path, config, seed, rows):
    """Write ``rows`` seeded rows as libsvm text to ``path`` (atomically:
    a run that dies mid-write leaves no file that looks whole)."""
    chunks = -(-rows // CHUNK_ROWS)

    def one(c):
        n = min(CHUNK_ROWS, rows - c * CHUNK_ROWS)
        return _libsvm_bytes(*_host_chunk(config, seed, c, n))

    tmp = f"{path}.partial"
    with open(tmp, "wb") as out, \
            ThreadPoolExecutor(_WRITER_THREADS) as pool:
        for blob in pool.map(one, range(chunks)):
            out.write(blob)
    os.replace(tmp, path)


# -- on the device -----------------------------------------------------------
#
# Device arrays are made feature-major, [F, n]: with the rows in the lane
# dimension every vector op is full, where [n, 28] would use 28 lanes of
# 128 and a reduce over the 255 boundaries would cross lanes.

def _device_key(seed, stream):
    """A key of the ``rbg`` generator (the chip's own random-bit
    generator): JAX's default threefry is computed in vector integer ops
    and is far slower for the hundreds of millions of values a data set
    takes.  The same seed gives the same values on the same devices."""
    import jax

    return jax.random.fold_in(jax.random.key(int(seed), impl="rbg"), stream)


def _draw_xt(key, n, card):
    """``[F, n]`` float32 columns as the configuration describes them."""
    import jax
    import jax.numpy as jnp

    kn, ku = jax.random.split(key)
    f = card.shape[0]
    x = jax.random.normal(kn, (f, n), jnp.float32)
    if (card > 0).any():
        u = jax.random.uniform(ku, (f, n), jnp.float32)
        k = card.astype(np.float32)[:, None]
        x = jnp.where(k > 0, jnp.floor(u * k), x)
    return x


def _draw_absent(key, n, share):
    """``[F, n]`` bool: entry absent, column ``f`` with probability
    ``share[f]``, every entry on its own."""
    import jax
    import jax.numpy as jnp

    u = jax.random.uniform(jax.random.fold_in(key, 3), (share.shape[0], n),
                           jnp.float32)
    return u < share[:, None]


def device_sample(config, seed, n):
    """A host float32 ``[n, F]`` sample of the device distribution, for
    ``GBDT.make_bins`` (drawn on the device, one transfer back); absent
    entries are NaN, which ``make_bins`` leaves out of the ranks."""
    import jax
    import jax.numpy as jnp

    card, _, _ = columns(config)
    absent = missing(config)

    def draw(k):
        xt = _draw_xt(k, n, card)
        if absent is not None:
            xt = jnp.where(_draw_absent(k, n, absent[0]), jnp.nan, xt)
        return xt.T

    return np.asarray(jax.jit(draw)(_device_key(seed, 1)))


def bin_on_device(xt, boundaries, missing_bin=None):
    """``searchsorted(boundaries[f], x[:, f], side="right")`` for feature-
    major ``xt[F, n]``, as a count of the boundaries at or below each
    value: the ids ``ops.histogram.apply_bins`` gives, without its
    per-element binary search, whose program took 39 s to compile and run
    for 11M x 28 on a v5e (my chip run, PR 22; the two not separated).
    The boundaries are walked in blocks of ``_EDGE_BLOCK``: one fused
    elementwise pass over ``xt`` per block, so nothing of shape ``[bins, F,
    n]`` is ever laid out.  With ``missing_bin`` a NaN takes that id, as
    ``apply_bins(..., missing_bin=)`` gives it (no boundary is ``<=`` NaN,
    so without it a NaN would count 0).  Returns ``[F, n]`` int32."""
    import jax
    import jax.numpy as jnp

    edges = jnp.asarray(boundaries, jnp.float32).T      # [bins - 1, F]
    pad = -edges.shape[0] % _EDGE_BLOCK                 # +inf: never <= x
    edges = jnp.pad(edges, ((0, pad), (0, 0)), constant_values=jnp.inf)
    blocks = edges.reshape(-1, _EDGE_BLOCK, edges.shape[1])

    def add_block(i, count):
        block = blocks[i]
        for j in range(_EDGE_BLOCK):
            count = count + (xt >= block[j][:, None]).astype(jnp.int32)
        return count

    ids = jax.lax.fori_loop(0, blocks.shape[0], add_block,
                            jnp.zeros(xt.shape, jnp.int32))
    if missing_bin is not None:
        ids = jnp.where(jnp.isnan(xt), jnp.int32(missing_bin), ids)
    return ids


def device_binned(config, seed, n, boundaries, wire_dtype, sharding=None):
    """``(bins[n, F] wire dtype, label[n] f32, weight[n] f32, extras)``
    generated, binned with the model's ``boundaries`` and cast on the
    device in ONE jitted call; with ``sharding`` (dim 0 over the mesh's data
    axis) every chip makes only its own rows.  The configuration's
    objective says how many teachers are drawn and what their margins make
    of a row: its label, and in ``extras`` whatever further per-row arrays
    it needs (``{}`` where it needs none).  Absent entries take the id the
    configuration's model reserves (:func:`reserved_bin`).  Whatever
    depends on the seed (the key, the teachers, the boundaries) is an
    ARGUMENT of the program, never a constant inside it: every seed then
    runs the same cached program."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import objectives

    objective = objectives.load(config["objective"])
    card, mean, std = columns(config)
    noise = float(config["data"]["label_noise"])
    absent, missing_bin = missing(config), reserved_bin(config)
    if absent is not None and missing_bin is None:
        raise ValueError("data.missing_share makes NaN entries: the "
                         "configuration's model block needs handle_missing")

    def make(key, teachers, edges, absent_teachers):
        kx, ke = jax.random.split(key)
        xt = _draw_xt(kx, n, card)
        z = (xt - mean[:, None]) / std[:, None]
        if absent is not None:
            gone = _draw_absent(kx, n, absent[0])
            xt = jnp.where(gone, jnp.nan, xt)
        latent = []
        for k, w in enumerate(teachers):        # (the first teacher's noise
            # comes from ``ke`` itself, as the only teacher's always did)
            terms = z * w[:, None]
            shift = noise * jax.random.normal(
                jax.random.fold_in(ke, k) if k else ke, (n,), jnp.float32)
            if absent is not None:
                add, intercept = absent_teachers[k]
                terms = jnp.where(gone, add[:, None], terms)
                shift = shift + intercept
            latent.append(jnp.sum(terms, axis=0) + shift)
        label, extras = objective.label(jnp.stack(latent),
                                        jax.random.fold_in(key, 4), config)
        bins = bin_on_device(xt, edges, missing_bin).astype(wire_dtype).T
        return bins, label, jnp.ones((n,), jnp.float32), extras

    out_shardings = None
    if sharding is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        rows2d = NamedSharding(sharding.mesh, P(*sharding.spec, None))
        # (every extra is per row: the one sharding covers the whole dict)
        out_shardings = (rows2d, sharding, sharding, sharding)
    latents = range(objective.latents(config))
    return jax.jit(make, out_shardings=out_shardings)(
        _device_key(seed, 2), [teacher(config, seed, k) for k in latents],
        np.asarray(boundaries, np.float32),
        [] if absent is None else [absent_teacher(config, seed, k)
                                   for k in latents])
