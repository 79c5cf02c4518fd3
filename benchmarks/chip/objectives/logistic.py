"""Binary logistic (XGBoost ``binary:logistic``).

One teacher; the label is the sign of its margin::

    y = [latent > 0]
    p = 1 / (1 + exp(-m)),   g = p - y,   h = p (1 - p)
    loss = mean(log(1 + exp(m)) - y m)        (mean binary cross-entropy)

A fit that learned nothing stays at ``base_score`` 0: ``loss = ln 2``,
whatever the labels.  Rows stand alone; ``fit_binned`` takes nothing more.
"""

import numpy as np

from benchmarks.chip import objectives

LOSS = "logloss"


def latents(config):
    return 1


def label(latent, key, config):
    import jax.numpy as jnp

    return (latent[0] > 0).astype(jnp.float32), {}


def grad_hess(margin, label):
    p = 1.0 / (1.0 + np.exp(-margin))
    return (p - label).astype(np.float32), (p * (1 - p)).astype(
        np.float32)


def loss(margin, label):
    xp, m = objectives.namespace(margin)
    return float(xp.mean(xp.logaddexp(0.0, m) - label * m))


def learned_nothing(label, config):
    return float(np.log(2.0))


def sample(m):
    return m


def fit_args():
    return {}
