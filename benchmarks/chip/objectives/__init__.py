"""What a fit configuration's ``objective`` means to the yardstick.

A configuration names its objective (``"objective": "logistic"``), and the
``fit`` kind finds ``objectives/<objective>.py`` by that name, as traffic
kinds and per-layer readers are found: a later PR brings a deployment with
another objective as a new file here, and edits nothing.  Whatever depends
on the objective lives in its module, which states its equations:

- ``LOSS``: the loss's name in the check's lines (``"logloss"``);
- ``latents(config)``: how many seeded teachers ``datagen`` draws;
- ``label(latent, key, config)``: inside ``datagen.device_binned``'s one
  jitted program, ``(label[n] float32, extras)`` from the latent margins
  ``latent[latents, n]``; ``extras`` is a dict of FURTHER PER-ROW ARRAYS
  (dim 0 = rows) the objective needs beside ``label`` and ``weight``, such
  as a group column; ``key`` is a ``jax.random`` key of its own;
- ``grad_hess(margin, label, **extras)``: the plain reference's gradient
  and hessian, numpy float32, nothing from the program;
- ``loss(margin, label, **extras)``: the ONE loss the program's and the
  reference's margins are compared by, a float: numpy margins (the
  reference's, on the host) in float64, device margins (the program's) on
  the device as they are (:func:`namespace`);
- ``learned_nothing(label, config, **extras)``: the loss of the fit's
  starting margin, what ``controls.py`` holds a sample's loss against;
- ``sample(m, **extras)``: the largest row prefix ``<= m`` that the check
  may cut (``m`` where every row stands alone);
- ``fit_args(**extras)``: the keyword arguments ``GBDT.fit_binned`` takes
  beyond ``bins, label, weight``.
"""

from __future__ import annotations

import importlib
import pkgutil

import numpy as np


def names() -> list:
    """Every ``objectives/<name>.py``, by file name."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def load(name: str):
    if name not in names():
        raise ValueError(f"objective {name!r} has no module "
                         f"benchmarks/chip/objectives/{name}.py "
                         f"(the folder has: {names()})")
    return importlib.import_module(f"{__name__}.{name}")


def namespace(margin):
    """``(xp, margin)`` a loss is computed in: numpy and float64 for a host
    array, ``jax.numpy`` and the array as it is for a device one."""
    if isinstance(margin, np.ndarray):
        return np, margin.astype(np.float64)
    import jax.numpy as jnp

    return jnp, margin
