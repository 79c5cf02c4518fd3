"""Squared error (XGBoost ``reg:squarederror``).

One teacher; the label is its margin, noise included, a real number::

    y = latent
    g = m - y,   h = 1
    loss = mean((m - y)^2)                    (mean squared error)

A fit that learned nothing stays at ``base_score`` 0: ``loss = mean(y^2)``,
the labels' second moment.  Rows stand alone; ``fit_binned`` takes nothing
more.
"""

import numpy as np

from benchmarks.chip import objectives

LOSS = "mse"


def latents(config):
    return 1


def label(latent, key, config):
    return latent[0], {}


def grad_hess(margin, label):
    return (margin - label).astype(np.float32), np.ones_like(margin)


def loss(margin, label):
    xp, m = objectives.namespace(margin)
    return float(xp.mean((m - label) ** 2))


def learned_nothing(label, config):
    return loss(np.zeros(label.shape, np.float32), label)


def sample(m):
    return m


def fit_args():
    return {}
