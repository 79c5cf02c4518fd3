"""A later PR adds a configuration, a cell, a traffic kind, a per-layer
metric and an objective as NEW FILES plus manifest entries; nothing that
exists is edited.  Shown here by building such an addition in a scratch
tree and running it through the unchanged harness."""

import json
import shutil
import textwrap

import jax

from benchmarks.chip import harness, layer_metrics, tracereduce, traffic
from benchmarks.chip.tests import rehearsal

KIND = '''
def setup(ctx):
    return {"ticks": ctx.config["ticks"]}

def window(ctx, state, t_start):
    return {"setup_s": 0.25, "attempted": state["ticks"], "failed": 0}

def check(ctx, state, window):
    yield True, "a dummy is always right"

def end_to_end(ctx, state, window):
    return {"setup_s": window["setup_s"], "ticks_per_s": 4.0}
'''

METRIC = '''
NAME, UNIT, LAYER, MOVES = "dummy_ticks", "ticks", "dummy layer", "ticks_per_s"
KINDS = ("dummy",)

def reduce(evidence):
    return float(evidence["window"]["attempted"])
'''


def test_a_cell_a_config_a_kind_and_a_metric_arrive_as_files(
        tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    here = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p.relative_to(here): p.read_bytes()
              for p in here.rglob("*") if p.is_file()}
    # the addition: four new files ...
    (here / "configs" / "dummy.json").write_text(json.dumps(
        {"name": "dummy", "ticks": 3}))
    (here / "workloads" / "dummy.tick.json").write_text(json.dumps(
        {"kind": "dummy", "config": "dummy", "chips": 1}))
    (here / "traffic" / "dummy.py").write_text(textwrap.dedent(KIND))
    (here / "layer_metrics" / "dummy_ticks.py").write_text(
        textwrap.dedent(METRIC))
    # ... and entries appended to the manifest
    manifest = harness.load_manifest()
    manifest["configs"].append({"name": "dummy", "source": "none",
                                "file": "benchmarks/chip/configs/dummy.json",
                                "reduced": [], "why": "a test"})
    manifest["workloads"].append({"name": "dummy.tick", "config": "dummy",
                                  "traffic": "tick", "chips": 1,
                                  "why": "a test"})
    manifest["end_to_end"].append({"name": "ticks_per_s", "unit": "ticks/s",
                                   "workloads": ["dummy.tick"]})
    manifest["per_layer"].append({"name": "dummy_ticks", "unit": "ticks",
                                  "workloads": ["dummy.tick"]})
    assert all(p.read_bytes() == old for p, old in
               ((here / rel, old) for rel, old in before.items()))

    # the unchanged harness finds all four by name
    monkeypatch.setattr(traffic, "__path__",
                        traffic.__path__ + [str(here / "traffic")])
    monkeypatch.setattr(layer_metrics, "__path__",
                        layer_metrics.__path__
                        + [str(here / "layer_metrics")])

    class NoDeviceTrace:
        busy_s, window_s, chips = 0.5, 1.0, []

        def breakdown(self):
            return {"device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(tracereduce, "load", lambda *a: NoDeviceTrace())
    cell, config = harness.load_cell(manifest, "dummy.tick", str(root))
    assert (cell["kind"], config["ticks"]) == ("dummy", 3)
    for trace, want in ((False, {"ticks_per_s", "setup_s"}),
                        (True, {"dummy_ticks"})):
        ctx, _ = rehearsal.context(
            dict(cell, trace_after_s=0, trace_seconds=0), config, tmp_path,
            jax.devices()[:1], trace=trace)
        result = harness.run_cell(ctx, manifest, 0.0)
        assert result["correct"] and set(result["metrics"]) == want
    assert result["metrics"]["dummy_ticks"] == {"value": 3.0,
                                                "unit": "ticks"}


OBJECTIVE = '''
"""Squared error whose rows come in groups of ``group_rows``; the loss is
the mean over groups of each group's mean error::

    loss = mean_q(mean_{i in q}((m_i - y_i)^2))
"""
import numpy as np

LOSS = "gmse"
SEEN = []

def latents(config):
    return 1

def label(latent, key, config):
    import jax.numpy as jnp
    rows = jnp.arange(latent.shape[1], dtype=jnp.int32)
    return latent[0], {"group": rows // config["group_rows"]}

def grad_hess(margin, label, group):
    SEEN.append(group)
    return (margin - label).astype(np.float32), np.ones_like(margin)

def loss(margin, label, group):
    err = (np.asarray(margin, np.float64) - np.asarray(label)) ** 2
    group = np.asarray(group)
    return float(np.mean(np.bincount(group, err) / np.bincount(group)))

def learned_nothing(label, config, group):
    return loss(np.zeros(label.shape), label, group)

def sample(m, group):
    group = np.asarray(group[:m + 1])
    return m if len(group) <= m else int(np.flatnonzero(
        group[:-1] != group[-1])[-1]) + 1

def fit_args(group):
    return {"group": group}
'''


def test_an_objective_arrives_as_a_file(tmp_path, monkeypatch):
    """``objectives/<name>.py`` is found by the configuration's
    ``objective``, and its further per-row array travels: made inside
    ``device_binned``'s one program (sharded by rows like the label), cut
    on a boundary of its own by ``sample``, handed to the reference's
    gradient by ``boost`` and to ``fit_binned`` by the timed call."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip import datagen, objectives
    from benchmarks.chip.reference import gbdt_hist
    from benchmarks.chip.traffic import fit
    from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

    (tmp_path / "grouped.py").write_text(OBJECTIVE)
    monkeypatch.setattr(objectives, "__path__",
                        objectives.__path__ + [str(tmp_path)])
    assert set(objectives.names()) == {"grouped", *rehearsal.OBJECTIVES}
    grouped = objectives.load("grouped")
    cfg = rehearsal.config(objective="grouped", group_rows=100)
    model = fit.make_model(rehearsal.config(), 2)
    fit.fit_bins(cfg, 5, model)
    sharding = data_sharding(make_mesh({"data": 4}, devices=jax.devices()[:4]))
    bins, label, weight, extras = datagen.device_binned(
        cfg, 5, 4096, model.boundaries, jnp.uint8, sharding)
    assert list(extras) == ["group"]
    assert extras["group"].sharding == label.sharding == sharding
    assert np.array_equal(np.asarray(extras["group"]),
                          np.arange(4096) // 100)
    # the check's sample ends where a group ends
    m = grouped.sample(2048, **extras)
    assert m == 2000 and grouped.sample(4096, **extras) == 4096
    sx = {"group": np.asarray(extras["group"][:m])}
    sb, sl = np.asarray(bins[:m]), np.asarray(label[:m])
    _, margin = gbdt_hist.boost(sb, sl, 2, extras=sx,
                                **fit.reference_params(cfg))
    assert len(grouped.SEEN) == 2 and grouped.SEEN[0] is sx["group"]
    assert grouped.loss(margin, sl, **sx) < grouped.learned_nothing(
        sl, cfg, **sx)

    # the timed call hands fit_binned what fit_args makes of the extras
    class Program:
        def fit_binned(self, *data, **more):
            self.got = (data, more)
            return None, jnp.zeros(1)

    state = {"model": Program(), "data": (bins, label, weight),
             "fit_args": grouped.fit_args(**extras)}
    fit._fit(state)
    assert state["model"].got == ((bins, label, weight),
                                  {"group": extras["group"]})


def test_memory_peak_is_read_before_the_check(tmp_path, monkeypatch):
    """``memory_peak_bytes`` is what the timed path held: the check's
    reference and eager calls come after the reading."""
    import types

    order = []
    kind = types.SimpleNamespace(
        setup=lambda ctx: {},
        window=lambda ctx, state, t0: {"setup_s": 0.1, "attempted": 1,
                                       "failed": 0},
        check=lambda ctx, state, window: order.append("check") or
        [(True, "a dummy is always right")],
        end_to_end=lambda ctx, state, window: {"setup_s": 0.1})
    monkeypatch.setattr(harness, "load_kind", lambda name: kind)
    monkeypatch.setattr(harness, "peak_memory_bytes",
                        lambda devices: order.append("peak") or 7)
    ctx, _ = rehearsal.context({"name": "x", "kind": "dummy"}, {}, tmp_path,
                               jax.devices()[:1])
    result = harness.run_cell(ctx, {"end_to_end": [
        {"name": "setup_s", "unit": "s"}], "per_layer": []}, 0.0)
    assert order == ["peak", "check"]
    assert result["device"]["memory_peak_bytes"] == 7
    assert list(result)[-1] == "compared"
    assert result["compared"][0] == "ok: a dummy is always right"
