"""A configuration's objective arrives as ``objectives/<objective>.py``:
the three that ship run through the unchanged ``fit`` kind on the CPU
(Pallas kernel in interpret mode), each check fails what it should, and
``logistic`` is to the bit what the harness did before objectives were
modules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import datagen, objectives
from benchmarks.chip.reference import gbdt_hist, tree_walk
from benchmarks.chip.tests import rehearsal
from benchmarks.chip.traffic import fit
from dmlc_core_tpu.ops import hist_pallas

CELL = {"name": "r.fit", "kind": "fit", "chips": 1, "rounds_per_fit": 2}
CONFIGS = {"logistic": {},
           "squared": {"objective": "squared"},
           "softmax": {"objective": "softmax", "model": {"num_class": 3}}}
# a reference fitted under another objective: squared error's labels under
# the logistic gradient, softmax's classes as one-hot columns under squared
# error's
OTHER = {"squared": "logistic", "softmax": "squared"}


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)


def _config(name):
    return rehearsal.config(**CONFIGS[name])


# -- the contract -----------------------------------------------------------

def test_the_folder_holds_the_three_objectives_of_the_program():
    from dmlc_core_tpu.models.gbdt import GBDTParam

    assert objectives.names() == ["logistic", "softmax", "squared"]
    for name in objectives.names():
        GBDTParam(objective=name, num_class=3)      # the program knows it
        mod = objectives.load(name)
        assert mod.__doc__ and "loss = " in mod.__doc__
        for given in ("LOSS", "latents", "label", "grad_hess", "loss",
                      "learned_nothing", "sample", "fit_args"):
            assert hasattr(mod, given), (name, given)
        assert mod.sample(77) == 77 and mod.fit_args() == {}


def test_an_unknown_objective_raises_and_names_the_folder():
    says = (r"objective 'hinge' has no module "
            r"benchmarks/chip/objectives/hinge\.py \(the folder has: "
            r"\['logistic', 'softmax', 'squared'\]\)")
    with pytest.raises(ValueError, match=says):
        objectives.load("hinge")
    with pytest.raises(ValueError, match=says):
        datagen.device_binned(rehearsal.config(objective="hinge"), 1, 64,
                              np.zeros((5, 15), np.float32), jnp.uint8)
    with pytest.raises(ValueError, match=says):
        gbdt_hist.grad_hess(np.zeros(4, np.float32),
                            np.zeros(4, np.float32), "hinge")


@pytest.mark.parametrize("name, nothing", [
    ("logistic", float(np.log(2.0))), ("softmax", float(np.log(3.0))),
    ("squared", None)])
def test_learned_nothing_is_the_loss_of_the_starting_margin(name, nothing):
    mod, cfg = objectives.load(name), _config(name)
    rng = np.random.default_rng(5)
    label = {"logistic": (rng.random(999) > 0.3).astype(np.float32),
             "softmax": rng.integers(0, 3, 999).astype(np.float32),
             "squared": rng.standard_normal(999).astype(np.float32)}[name]
    start = np.zeros((999, 3) if name == "softmax" else 999, np.float32)
    got = mod.learned_nothing(label, cfg)
    assert got == pytest.approx(mod.loss(start, label), rel=1e-12)
    if nothing is None:
        nothing = float(np.mean(label.astype(np.float64) ** 2))
    assert got == pytest.approx(nothing, rel=1e-12)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_one_loss_on_the_host_and_on_the_device(name):
    """The same formula both ways: float64 for a numpy margin, the device's
    float32 for a device one."""
    mod = objectives.load(name)
    rng = np.random.default_rng(6)
    shape = (4096, 3) if name == "softmax" else (4096,)
    margin = rng.standard_normal(shape).astype(np.float32)
    label = (rng.integers(0, 3, 4096) if name == "softmax"
             else rng.integers(0, 2, 4096)).astype(np.float32)
    host = mod.loss(margin, label)
    device = mod.loss(jnp.asarray(margin), jnp.asarray(label))
    assert host == pytest.approx(device, rel=1e-5) and host != device


def test_softmax_draws_a_teacher_a_class_and_labels_of_every_class():
    cfg = _config("softmax")
    assert objectives.load("softmax").latents(cfg) == 3
    assert np.array_equal(datagen.teacher(cfg, 9, 0), datagen.teacher(cfg, 9))
    assert not np.array_equal(datagen.teacher(cfg, 9, 1),
                              datagen.teacher(cfg, 9))
    model = fit.make_model(cfg, 1)
    fit.fit_bins(cfg, 9, model)
    _, label, _, extras = datagen.device_binned(cfg, 9, 30_000,
                                                model.boundaries, jnp.uint8)
    share = np.bincount(np.asarray(label).astype(int), minlength=3) / 30_000
    assert extras == {} and share.min() > 0.1 and share.sum() == 1


def test_squared_labels_are_the_teachers_margin():
    cfg = _config("squared")
    model = fit.make_model(cfg, 1)
    fit.fit_bins(cfg, 9, model)
    _, real, _, _ = datagen.device_binned(cfg, 9, 30_000, model.boundaries,
                                          jnp.uint8)
    _, sign, _, _ = datagen.device_binned(_config("logistic"), 9, 30_000,
                                          model.boundaries, jnp.uint8)
    real = np.asarray(real)
    assert np.array_equal(real > 0, np.asarray(sign) > 0.5)
    w = datagen.teacher(cfg, 9)
    assert real.std() == pytest.approx(np.sqrt((w * w).sum() + 0.09),
                                       rel=0.03)


# -- the reference: K trees a round from one margin snapshot -----------------

def test_reference_grows_k_trees_a_round_like_the_exact_program():
    """``boost`` under ``softmax`` against the program's exact (scatter)
    fit: the same splits in all K trees of both rounds, and the stack of
    ``[T, K, ...]`` arrays walks to the reference's own ``[n, K]`` margin."""
    cfg = rehearsal.config(hist_method="scatter", **CONFIGS["softmax"])
    model = fit.make_model(cfg, 2)
    fit.fit_bins(cfg, 4, model)
    bins, label, _, _ = datagen.device_binned(cfg, 4, 3000, model.boundaries,
                                              jnp.uint8)
    bins, label = np.asarray(bins), np.asarray(label)
    trees, margin = gbdt_hist.boost(bins, label, 2,
                                    **fit.reference_params(cfg))
    assert margin.shape == (3000, 3) and trees[0][0].shape == (3, 7)
    ensemble, fitted = model.fit_binned(bins, label)
    stack = [np.stack([t[i] for t in trees]) for i in range(4)]
    assert np.array_equal(np.asarray(ensemble.split_feat), stack[0])
    assert np.array_equal(np.asarray(ensemble.split_bin), stack[1])
    np.testing.assert_allclose(np.asarray(fitted), margin, atol=1e-5)
    walked = tree_walk.margins(bins.astype(np.int64), *stack[:3])
    np.testing.assert_allclose(walked, margin, atol=1e-6)
    # one snapshot a round: tree 1 of round 0 is grown from the starting
    # margin's gradient, not from a margin that tree 0 has already moved
    g, h = gbdt_hist.grad_hess(np.zeros((3000, 3), np.float32), label,
                               "softmax")
    alone = gbdt_hist.build_tree(bins, g[:, 1], h[:, 1], 3, 16, 1.0, 1.0, 0.3)
    assert np.array_equal(alone[0], trees[0][0][1])
    assert np.array_equal(alone[2], trees[0][2][1])


# -- through the harness ------------------------------------------------------

@pytest.mark.parametrize("name", ["squared", "softmax"])
def test_objective_reads_correct_through_run_cell(interpret, tmp_path, name):
    result, lines = rehearsal.run(CELL, _config(name), tmp_path,
                                  jax.devices()[:1])
    assert result["correct"], lines
    loss = objectives.load(name).LOSS
    assert [f"train {loss}" in line for line in result["compared"]] == [
        False, False, False, True, False, True, False]
    assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}


def test_softmax_on_four_virtual_devices(interpret, tmp_path):
    cell = {**CELL, "name": "r.fit.dp4", "chips": 4}
    cfg = rehearsal.config(mesh={"data": 4}, rows=8192, **CONFIGS["softmax"])
    result, lines = rehearsal.run(cell, cfg, tmp_path, jax.devices()[:4])
    assert result["correct"], lines


@pytest.mark.parametrize("name", ["squared", "softmax"])
def test_the_loss_line_fails_what_it_should(interpret, tmp_path, monkeypatch,
                                            name):
    """The objective's loss line holds a sound run, fails a reference
    fitted under another objective, and fails a program one level short."""
    cfg = _config(name)
    ctx, _ = rehearsal.context(CELL, cfg, tmp_path, jax.devices()[:1],
                               seconds=0.0)
    state = fit.setup(ctx)
    window = fit.window(ctx, state, 0.0)
    line = f"train {objectives.load(name).LOSS} after 2 rounds"

    def loss_line(state, window):
        (ok,) = [ok for ok, what in fit.check(ctx, state, window)
                 if line in what]
        return ok

    assert loss_line(state, window)
    plain = gbdt_hist.boost

    def under_another(bins, label, rounds, **kw):
        if kw["num_class"] > 1:
            label = (label[:, None] == np.arange(kw["num_class"]))
        return plain(bins, label, rounds, **{**kw, "objective": OTHER[name]})

    with monkeypatch.context() as patched:
        patched.setattr(gbdt_hist, "boost", under_another)
        assert not loss_line(state, window)

    shallow = fit.make_model({**cfg, "max_depth": cfg["max_depth"] - 1}, 2)
    shallow.set_boundaries(state["model"].boundaries)
    short = {**state, "model": shallow}
    short["warm"] = fit._fit(short)
    assert not loss_line(short, fit.window(ctx, short, 0.0))


# -- logistic is what the harness always did ---------------------------------

def _parent_device_binned(config, seed, n, boundaries, wire_dtype):
    """``datagen.device_binned`` as it stood before objectives were
    modules (PR 34), label expression and all."""
    card, mean, std = datagen.columns(config)
    noise = float(config["data"]["label_noise"])
    absent = datagen.missing(config)
    missing_bin = datagen.reserved_bin(config)

    def make(key, w, edges, *absent_args):
        kx, ke = jax.random.split(key)
        xt = datagen._draw_xt(kx, n, card)
        terms = (xt - mean[:, None]) / std[:, None] * w[:, None]
        shift = noise * jax.random.normal(ke, (n,), jnp.float32)
        if absent is not None:
            add, intercept = absent_args
            gone = datagen._draw_absent(kx, n, absent[0])
            terms = jnp.where(gone, add[:, None], terms)
            xt = jnp.where(gone, jnp.nan, xt)
            shift = shift + intercept
        margin = jnp.sum(terms, axis=0) + shift
        bins = datagen.bin_on_device(xt, edges,
                                     missing_bin).astype(wire_dtype).T
        return (bins, (margin > 0).astype(jnp.float32),
                jnp.ones((n,), jnp.float32))

    absent_args = (() if absent is None
                   else datagen.absent_teacher(config, seed))
    return jax.jit(make)(datagen._device_key(seed, 2),
                         datagen.teacher(config, seed),
                         np.asarray(boundaries, np.float32), *absent_args)


@pytest.mark.parametrize("cfg", [rehearsal.config(),
                                 rehearsal.missing_config()],
                         ids=["dense", "absent"])
def test_logistic_data_is_the_parents_bit_for_bit(cfg):
    seed = 3500000007
    model = fit.make_model(cfg, 1)
    fit.fit_bins(cfg, seed, model)
    args = (cfg, seed, 50_000, model.boundaries, jnp.uint8)
    *new, extras = datagen.device_binned(*args)
    old = _parent_device_binned(*args)
    assert extras == {}
    for a, b in zip(old, new):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))


def test_logistic_gradient_and_lines_are_the_parents(interpret, tmp_path):
    """The reference's gradient, the loss line and the band line, each
    against the expression the harness held before (PR 34)."""
    rng = np.random.default_rng(8)
    margin = rng.standard_normal(5000).astype(np.float32) * 3
    label = (rng.random(5000) > 0.5).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-margin))
    g, h = gbdt_hist.grad_hess(margin, label, "logistic")
    assert np.array_equal(g, (p - label).astype(np.float32))
    assert np.array_equal(h, (p * (1 - p)).astype(np.float32))

    def host(margin, label):
        m = margin.astype(np.float64)
        return float(np.mean(np.logaddexp(0.0, m) - label * m))

    def device(margin, label):
        return float(jnp.mean(jnp.logaddexp(0.0, margin) - label * margin))

    assert gbdt_hist.logloss(margin, label) == host(margin, label)
    cfg = rehearsal.config()
    ctx, _ = rehearsal.context(CELL, cfg, tmp_path, jax.devices()[:1],
                               seconds=0.0, seed=3500000007)
    state = fit.setup(ctx)
    window = fit.window(ctx, state, 0.0)
    lines = [what for _, what in fit.check(ctx, state, window)]
    bins, label, _ = state["data"]
    m, tol, band = 2048, 0.01, 0.05
    sb, sl = np.asarray(bins[:m]), np.asarray(label[:m])
    _, ref_margin = gbdt_hist.boost(sb, sl, 2, **fit.reference_params(cfg))
    ref_loss = host(ref_margin, sl)
    sub_loss = device(state["model"].fit_binned(sb, sl)[1], jnp.asarray(sl))
    full_loss = device(window["last"][1], label)
    assert lines[3] == (
        f"train logloss after 2 rounds on {m} sampled "
        f"rows: program {sub_loss:.5f} vs reference {ref_loss:.5f}, "
        f"{abs(sub_loss - ref_loss):.2e} apart (tolerance {tol})")
    assert lines[5] == (
        f"train logloss of the whole 4096-row fit "
        f"{full_loss:.5f}: {abs(full_loss - sub_loss):.5f} from the "
        f"sample's (band {band})")
