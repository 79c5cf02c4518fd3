"""Softmax over ``K = model.num_class`` classes (XGBoost
``multi:softprob``).

``K`` teachers; the label is the class whose margin is largest, as a
float32 id in ``[0, K)`` (every class is drawn, in shares that follow the
seeded teachers' norms: balanced only over seeds)::

    y = argmax_k latent[k]
    p = softmax(m) over the K columns of m[n, K]
    g[:, k] = p[:, k] - [y = k],   h[:, k] = max(2 p[:, k] (1 - p[:, k]), 1e-16)
    loss = -mean(log p[i, y[i]])              (mean cross-entropy)

(the factor 2 and the floor are XGBoost's ``SoftmaxMultiClassObj``).  A
round grows K trees from one margin snapshot, tree ``k`` from column ``k``
of ``g`` and ``h`` (``reference/gbdt_hist.py:boost``).  A fit that learned
nothing stays at ``base_score`` 0 in every column: ``loss = ln K``,
whatever the labels.  Rows stand alone; ``fit_binned`` takes nothing more.
"""

import numpy as np

from benchmarks.chip import objectives

LOSS = "mlogloss"


def latents(config):
    return int(config["model"]["num_class"])


def label(latent, key, config):
    import jax.numpy as jnp

    return jnp.argmax(latent, axis=0).astype(jnp.float32), {}


def _log_softmax(xp, m):
    z = m - m.max(axis=1, keepdims=True)
    return z - xp.log(xp.exp(z).sum(axis=1, keepdims=True))


def grad_hess(margin, label):
    p = np.exp(_log_softmax(np, margin))
    hit = label.astype(np.int32)[:, None] == np.arange(margin.shape[1])
    return ((p - hit).astype(np.float32),
            np.maximum(2.0 * p * (1.0 - p), 1e-16).astype(np.float32))


def loss(margin, label):
    xp, m = objectives.namespace(margin)
    own = xp.take_along_axis(_log_softmax(xp, m),
                             label.astype(np.int32)[:, None], axis=1)
    return float(-xp.mean(own))


def learned_nothing(label, config):
    return float(np.log(latents(config)))


def sample(m):
    return m


def fit_args():
    return {}
