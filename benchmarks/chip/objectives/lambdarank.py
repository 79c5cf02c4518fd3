"""LambdaMART over query groups, as LightGBM's ``lambdarank`` computes it
(``src/objective/rank_objective.hpp``,
``LambdarankNDCG::GetGradientsForOneQuery``; XGBoost >= 2.0's ``rank:ndcg``
with ``lambdarank_pair_method=topk`` builds the same pairs).

One teacher.  A row's grade ``y`` in 0..4 is its latent margin,
standardised over the rows, cut at fixed normal quantiles (``GRADE_SHARES``:
the same shares of every grade for every seed).  Rows come in queries: the
further per-row array ``group``, ascending ids, a query's rows adjacent,
``data.queries`` seeded sizes (:func:`query_sizes`).  A configuration
whose ``mesh`` has a ``data`` axis of ``d`` chips has its queries whole on
one chip, as a query-aligned ingest gives them and LightGBM's distributed
``lambdarank`` asks of its input: the rows a chip holds, ``rows / d`` of
them, are ``queries / d`` whole queries (:func:`label`).

For a query ``q`` of ``n_q`` rows with margins ``s`` and grades ``y``::

    r_i    = 0-based rank of row i by s descending, ties by ascending row
    G(y)   = 2^y - 1            D(r) = 1 / log2(2 + r)     k = TRUNCATION_LEVEL
    maxDCG = sum over r < min(k, n_q) of G(y sorted descending)[r] D(r)
    inv    = 1 / maxDCG, 0 where maxDCG is 0
    for every pair (a, b) of q with r_a < r_b, r_a < k, y_a != y_b:
        hi, lo = the one with the larger grade, the other
        ds   = s_hi - s_lo
        dN   = (G(y_hi) - G(y_lo)) |D(r_hi) - D(r_lo)| inv
        if the best and the worst margin of q differ:  dN /= 0.01 + |ds|
        rho  = 1 / (1 + exp(ds))
        lam  = rho dN              w = rho (1 - rho) dN
        g_hi -= lam    g_lo += lam    h_hi += w    h_lo += w    S += 2 lam
    if S > 0:  every g and h of q is multiplied by log2(1 + S) / S

float32 throughout.  A fit that learned nothing leaves every margin equal:
ranks are the row order.

``LOSS`` is ``1 - NDCG@10``: one minus the mean over queries of
``DCG@10 / maxDCG@10`` with the gains and discounts above and ties by row,
a query with no relevant row counting 1.
"""

import numpy as np

LOSS = "1-ndcg@10"
TRUNCATION_LEVEL = 30      # the reference is handed no configuration: a test
#                            holds this to the configuration's and GBDTParam's
NDCG_AT = 10
# the share of the rows in grades 0..4 (MSLR-WEB30K's, as remembered)
GRADE_SHARES = (0.514, 0.325, 0.134, 0.019, 0.008)


def latents(config):
    return 1


def _grade_cuts():
    """The standard-normal quantiles at the cumulative ``GRADE_SHARES``."""
    from statistics import NormalDist

    return [NormalDist().inv_cdf(float(c))
            for c in np.cumsum(GRADE_SHARES)[:-1]]


def query_sizes(key, config, rows, queries=None):
    """``queries`` (``data.queries`` unless given) seeded sizes,
    ``[queries]`` int32 inside a jitted program: ``round(exp(mu + sigma
    z))`` clipped to ``[least, largest]`` (log-normal: most queries hold
    about a hundred rows, a few the largest), with ``mu`` found by
    bisection so that they sum to ``rows``, what float32 leaves over added
    one row a query from the first on."""
    import jax
    import jax.numpy as jnp

    spec = config["data"]["query_size"]
    if queries is None:
        queries = int(config["data"]["queries"])
    least, largest = int(spec["least"]), int(spec["largest"])
    if not queries * least <= rows <= queries * largest:
        raise ValueError(f"{queries} queries of {least}..{largest} rows "
                         f"cannot hold {rows} rows")
    z = float(spec["sigma"]) * jax.random.normal(key, (queries,), jnp.float32)

    def sizes(mu):
        return jnp.clip(jnp.round(jnp.exp(mu + z)), least,
                        largest).astype(jnp.int32)

    def halve(_, span):
        lo, hi = span
        mid = 0.5 * (lo + hi)
        under = jnp.sum(sizes(mid)) <= rows
        return jnp.where(under, mid, lo), jnp.where(under, hi, mid)

    reach = jnp.max(jnp.abs(z)) + jnp.log(float(largest)) + 1.0
    lo, _ = jax.lax.fori_loop(0, 48, halve, (-reach, reach))
    n = sizes(lo)
    left = rows - jnp.sum(n)
    room = n < largest
    return n + (room & (jnp.cumsum(room) <= left)).astype(jnp.int32)


def data_shards(config, rows):
    """``d``, the configuration's ``mesh`` ``data`` axis (1 without a
    mesh), once ``rows`` and ``data.queries`` are known to divide by it."""
    d = int((config.get("mesh") or {}).get("data", 1))
    queries = int(config["data"]["queries"])
    if rows % d or queries % d:
        raise ValueError(
            f"{rows} rows in {queries} queries cannot lie whole on the "
            f"{d} chips of the mesh's data axis: a chip holds rows / {d} "
            f"rows in queries / {d} whole queries, so give rows and "
            f"data.queries multiples of {d}")
    return d


def label(latent, key, config):
    """The grades, and the group column: under a ``data`` axis of ``d``
    chips each chip's ``rows / d`` rows hold ``queries / d`` whole
    queries, their sizes drawn from the shard's own key; the ids ascend
    over the whole column.  Without a mesh one draw, from ``key`` itself,
    covers every row."""
    import jax
    import jax.numpy as jnp

    m = latent[0]
    z = (m - jnp.mean(m)) / jnp.std(m)
    grade = sum((z > cut).astype(jnp.float32) for cut in _grade_cuts())
    rows = m.shape[0]
    d = data_shards(config, rows)
    per, queries = rows // d, int(config["data"]["queries"]) // d
    first = []                          # every query's first row
    for shard in range(d):
        n = query_sizes(key if d == 1 else jax.random.fold_in(key, shard),
                        config, per, queries)
        first.append(shard * per + jnp.cumsum(n) - n)
    # the column's first query opens at row 0, where the ids start
    opens = jnp.zeros((rows,), jnp.int32).at[
        jnp.concatenate(first)[1:]].add(1)
    return grade, {"group": jnp.cumsum(opens)}


def _queries(group):
    """``(start, stop)`` of every run of equal ids, host side."""
    group = np.asarray(group)
    edges = np.flatnonzero(np.diff(group)) + 1
    return zip(np.r_[0, edges], np.r_[edges, group.shape[0]])


def grad_hess(margin, label, group):
    """A loop over the queries; a query's pairs ``(a, b)``, ``a`` the
    better-ranked, are the ``[min(k, n_q), n_q]`` block of the equations
    above (``tests/test_mslr_rank.py`` holds it to the loop over pairs)."""
    f = np.float32
    k = TRUNCATION_LEVEL
    g, h = np.zeros(margin.shape, f), np.zeros(margin.shape, f)
    for start, stop in _queries(group):
        n = stop - start
        order = np.argsort(-margin[start:stop].astype(f), kind="stable")
        s = margin[start:stop].astype(f)[order]       # by rank from here on
        y = label[start:stop].astype(f)[order]
        gain = np.exp2(y) - f(1)
        disc = f(1) / np.log2(f(2) + np.arange(n, dtype=f))
        top = min(k, n)
        max_dcg = np.sum(np.sort(gain)[::-1][:top] * disc[:top], dtype=f)
        inv = f(1) / max_dcg if max_dcg > 0 else f(0)
        r = np.arange(n)
        pair = (r[:top, None] < r[None, :]) & (y[:top, None] != y[None, :])
        a_is_hi = y[:top, None] > y[None, :]
        ds = np.where(a_is_hi, 1, -1).astype(f) * (s[:top, None] - s[None, :])
        dn = (np.abs(gain[:top, None] - gain[None, :])
              * np.abs(disc[:top, None] - disc[None, :]) * inv)
        if s[0] != s[-1]:
            dn = dn / (f(0.01) + np.abs(ds))
        with np.errstate(over="ignore"):
            rho = f(1) / (f(1) + np.exp(ds))
        lam = np.where(pair, rho * dn, f(0))
        w = np.where(pair, rho * (f(1) - rho) * dn, f(0))
        to_lo = np.where(a_is_hi, lam, -lam)     # b's part of g; a's is minus
        gq, hq = to_lo.sum(axis=0, dtype=f), w.sum(axis=0, dtype=f)
        gq[:top] -= to_lo.sum(axis=1, dtype=f)
        hq[:top] += w.sum(axis=1, dtype=f)
        total = f(2) * lam.sum(dtype=f)
        if total > 0:
            norm = np.log2(f(1) + total) / total
            gq, hq = gq * norm, hq * norm
        g[start + order], h[start + order] = gq, hq
    return g, h


def _dcg_at(by, gain, group, first):
    """Per query, the DCG of its first ``NDCG_AT`` rows in the order ``by``
    descending (ties by row)."""
    rows = np.arange(group.shape[0])
    order = np.lexsort((rows, -by, group))
    rank = rows - first[group]            # (group is unchanged by the sort)
    worth = np.where(rank < NDCG_AT, gain[order] / np.log2(2.0 + rank), 0.0)
    return np.bincount(group, weights=worth, minlength=first.shape[0])


def loss(margin, label, group):
    """Computed on the host for a device margin too, from its float32
    values as they are: a ranking loss is a sort, and a sort of 2.27M rows
    by three keys costs the TPU's compiler minutes for every new row count
    (the check's sample differs in size from seed to seed), where numpy
    takes a second.  Sums in float64 either way."""
    m = np.asarray(margin, np.float64)
    group = np.asarray(group) - np.asarray(group[:1])
    first = np.searchsorted(group, np.arange(int(group[-1]) + 1))
    gain = np.exp2(np.asarray(label, np.float64)) - 1.0
    best = _dcg_at(gain, gain, group, first)
    got = _dcg_at(m, gain, group, first)
    return float(1.0 - np.mean(np.where(best > 0, got / np.where(
        best > 0, best, 1.0), 1.0)))


def learned_nothing(label, config, group):
    return loss(np.zeros(np.shape(label)), np.asarray(label),
                np.asarray(group))


def sample(m, group):
    """The largest prefix ``<= m`` that ends where a query ends."""
    if group.shape[0] <= m:
        return int(group.shape[0])
    head = np.asarray(group[:m + 1])
    return int(np.flatnonzero(head[:-1] != head[-1])[-1]) + 1


def fit_args(group):
    return {"group": group}
