"""``mslr-web30k-2.27m-x136-lambdarank`` and its cell ``mslr30k.rank.fit``
arrive as files: the objective's module with its plain reference, the
configuration, the cell, one per-layer reader.  The reference's gradient is
held to a loop over pairs written from the equations of its docstring; the
data recipe to the sizes the configuration states; the reader to traces
with and without the program's ``gbdt.rank`` scope; the fit at its real
size to the TPU's compiler (no chip attached: a compile is not a run).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import datagen, harness, objectives
from benchmarks.chip.layer_metrics import rank_grad_ms_per_round
from benchmarks.chip.objectives import lambdarank
from benchmarks.chip.tests import rehearsal
from benchmarks.chip.tests import test_scopes as made
from benchmarks.chip.traffic import fit
from dmlc_core_tpu.ops import hist_pallas

CELL, CONFIG = "mslr30k.rank.fit", "mslr-web30k-2.27m-x136-lambdarank"


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def config(manifest):
    return harness.load_cell(manifest, CELL)[1]


# -- the manifest names the files -----------------------------------------------

def test_the_manifest_names_the_files(manifest, config):
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert entry["file"] == f"benchmarks/chip/configs/{CONFIG}.json"
    assert entry["reduced"] == [] and config["reduced_reason"] == {}
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "fit",
                                                                1)
    body, _ = harness.load_cell(manifest, CELL)
    assert (body["kind"], body["rounds_per_fit"]) == ("fit", 5)
    assert manifest["workloads"][-1] == cell       # appended, nothing moved
    assert manifest["configs"][-1] == entry
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == [
        "airline115m.fit.dp4"]
    assert "lambdarank" in objectives.names()
    assert objectives.load(config["objective"]) is lambdarank


def test_the_cell_reports_the_one_chip_readers_and_its_own(manifest):
    e2e = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                   "end_to_end")}
    assert e2e == {"train_rows_per_s", "setup_s"}
    mine = [m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                    "per_layer")]
    higgs = [m["name"] for m in harness.cell_metrics(manifest, "higgs11m.fit",
                                                     "per_layer")]
    assert len(higgs) == 11 and mine == higgs + ["rank_grad_ms_per_round"]
    assert manifest["per_layer"][-1] == {
        "name": "rank_grad_ms_per_round", "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": "models: lambdarank gradient over query groups",
        "moves": "train_rows_per_s", "workloads": [CELL]}


def test_the_configuration_is_at_the_sources_sizes(config):
    assert (config["rows"], config["num_feature"]) == (2_270_296, 136)
    assert config["data"]["queries"] == 18_919
    size = config["data"]["query_size"]
    assert (size["least"], size["largest"]) == (1, 1_251)
    assert (config["max_depth"], config["num_bins"], config["learning_rate"],
            config["reg_lambda"]) == (6, 256, 0.3, 1.0)
    assert config["objective"] == "lambdarank" and config["mesh"] is None
    assert config["hist_method"] == "auto"
    assert config["expect_hist_method"] == "pallas"
    assert len(lambdarank.GRADE_SHARES) == 5
    assert sum(lambdarank.GRADE_SHARES) == pytest.approx(1.0)
    for key in ("features", "grades", "query_size", "truncation_level",
                "parameters", "min_child_weight"):
        assert config["assumed"][key], key
    # every limit of the check with the chip's readings it was set from
    for key, reason in (("hist_atol", "hist_tolerance_reason"),
                        ("logloss_tolerance", "logloss_tolerance_reason"),
                        ("full_vs_sample_band", "full_vs_sample_band_reason"),
                        ("margin_atol", "margin_atol_reason")):
        assert config["check"][key] > 0
        assert "my chip run" in config["check"][reason], reason


def test_one_truncation_level_in_the_module_the_file_and_the_program(config):
    from dmlc_core_tpu.models.gbdt import GBDTParam

    assert (lambdarank.TRUNCATION_LEVEL
            == config["model"]["lambdarank_truncation_level"]
            == GBDTParam().lambdarank_truncation_level == 30)
    assert fit.make_model(config, 5).param.lambdarank_truncation_level == 30


# -- the reference's gradient, against the equations ---------------------------

def by_the_equations(margin, label, group, k):
    """``objectives/lambdarank.py``'s docstring, pair by pair, in Python
    floats."""
    g, h = np.zeros(len(margin)), np.zeros(len(margin))
    for start, stop in lambdarank._queries(group):
        s = [float(v) for v in margin[start:stop]]
        y = [float(v) for v in label[start:stop]]
        n = stop - start
        order = sorted(range(n), key=lambda i: (-s[i], i))
        rank = {row: r for r, row in enumerate(order)}

        def gain(v):
            return 2.0 ** v - 1.0

        def discount(r):
            return 1.0 / np.log2(2.0 + r)

        best = sorted(y, reverse=True)
        max_dcg = sum(gain(best[r]) * discount(r) for r in range(min(k, n)))
        inv = 1.0 / max_dcg if max_dcg > 0 else 0.0
        spread = s[order[0]] != s[order[-1]]
        total = 0.0
        for a in range(n):
            for b in range(n):
                if not (rank[a] < rank[b] and rank[a] < k and y[a] != y[b]):
                    continue
                hi, lo = (a, b) if y[a] > y[b] else (b, a)
                ds = s[hi] - s[lo]
                dn = ((gain(y[hi]) - gain(y[lo]))
                      * abs(discount(rank[hi]) - discount(rank[lo])) * inv)
                if spread:
                    dn /= 0.01 + abs(ds)
                rho = 1.0 / (1.0 + np.exp(ds))
                lam, w = rho * dn, rho * (1.0 - rho) * dn
                g[start + hi] -= lam
                g[start + lo] += lam
                h[start + hi] += w
                h[start + lo] += w
                total += 2.0 * lam
        if total > 0:
            g[start:stop] *= np.log2(1.0 + total) / total
            h[start:stop] *= np.log2(1.0 + total) / total
    return g, h


@pytest.mark.parametrize("case", ["sizes_about_k", "tied_margins",
                                  "all_margins_equal", "one_grade"])
def test_the_references_gradient_is_the_equations(case):
    rng = np.random.default_rng(len(case))
    sizes = [1, 2, 29, 30, 31, 64, 7]
    group = np.repeat(np.arange(len(sizes)), sizes).astype(np.int32)
    n = len(group)
    label = rng.integers(0, 5, n).astype(np.float32)
    margin = rng.standard_normal(n).astype(np.float32)
    if case == "tied_margins":
        margin = (rng.integers(0, 3, n) / 2).astype(np.float32)
    if case == "all_margins_equal":
        margin[:] = 0.25
    if case == "one_grade":
        label[:] = 3.0
    want_g, want_h = by_the_equations(margin, label, group,
                                      lambdarank.TRUNCATION_LEVEL)
    g, h = lambdarank.grad_hess(margin, label, group)
    assert g.dtype == h.dtype == np.float32
    np.testing.assert_allclose(g, want_g, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=1e-7)
    assert (case == "one_grade") == (not g.any())


def test_the_loss_is_one_minus_ndcg_at_10_on_the_host_and_the_device():
    rng = np.random.default_rng(3)
    sizes = [1, 4, 12, 40, 9]
    group = np.repeat(np.arange(len(sizes)), sizes).astype(np.int32) + 17
    label = rng.integers(0, 5, len(group)).astype(np.float32)
    label[group == 18] = 0.0               # no relevant row: counts 1
    margin = rng.standard_normal(len(group)).astype(np.float32)
    margin[20:30] = 0.5                     # ties, by ascending row
    ndcg = []
    for q in np.unique(group):
        s, y = margin[group == q], label[group == q]
        order = sorted(range(len(s)), key=lambda i: (-s[i], i))[:10]
        dcg = sum((2.0 ** y[i] - 1) / np.log2(2 + r)
                  for r, i in enumerate(order))
        ideal = sum((2.0 ** v - 1) / np.log2(2 + r)
                    for r, v in enumerate(sorted(y, reverse=True)[:10]))
        ndcg.append(dcg / ideal if ideal > 0 else 1.0)
    host = lambdarank.loss(margin, label, group)
    assert host == pytest.approx(1.0 - np.mean(ndcg), rel=1e-12)
    # a device margin is read on the host, from its float32 values
    device = lambdarank.loss(jnp.asarray(margin), jnp.asarray(label),
                             jnp.asarray(group))
    assert device == host
    assert lambdarank.learned_nothing(label, {}, group) == pytest.approx(
        lambdarank.loss(np.zeros(len(group), np.float32), label, group))
    assert lambdarank.loss(label, label, group) == pytest.approx(0.0,
                                                                 abs=1e-12)


# -- the data recipe ------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3_600_000_003])
def test_grades_and_query_sizes_hold_for_every_seed(config, seed):
    """At a fortieth of the rows and queries (the recipe is per row and per
    query): the five grades' shares, and sizes that are ``data.queries``
    many, from the least to the largest the file states, and sum to the
    rows."""
    rows, queries = 56_757, 473
    cfg = dict(config, rows=rows,
               data=dict(config["data"], queries=queries))
    model = fit.make_model(cfg, 1)
    fit.fit_bins(cfg, seed, model)
    _, label, weight, extras = datagen.device_binned(
        cfg, seed, rows, model.boundaries, jnp.uint8)
    assert list(extras) == ["group"] and extras["group"].dtype == jnp.int32
    share = np.bincount(np.asarray(label).astype(int), minlength=5) / rows
    # (fixed normal quantiles of a margin that is normal: the shares swing
    # with the sample, 0.002 at this size and 0.0003 at the cell's)
    np.testing.assert_allclose(share, lambdarank.GRADE_SHARES, atol=0.008)
    assert set(np.unique(np.asarray(label))) == {0.0, 1.0, 2.0, 3.0, 4.0}
    group = np.asarray(extras["group"])
    assert group[0] == 0 and group[-1] == queries - 1
    assert set(np.diff(group)) == {0, 1}
    sizes = np.bincount(group)
    assert sizes.sum() == rows and len(sizes) == queries
    assert 1 <= sizes.min() and sizes.max() <= 1_251
    # heavy-tailed: the mean well over the median, some queries at the cap
    assert np.mean(sizes) == pytest.approx(120, abs=0.01)
    assert np.median(sizes) < 0.8 * np.mean(sizes)
    assert sizes.max() > 600
    # the check's sample ends where a query ends
    m = lambdarank.sample(8_192, **extras)
    assert 8_192 - 1_251 < m <= 8_192 and group[m - 1] != group[m]
    assert lambdarank.sample(rows, **extras) == rows
    assert lambdarank.fit_args(**extras) == {"group": extras["group"]}


def test_the_cell_runs_through_the_unchanged_fit_kind(tmp_path, monkeypatch):
    """The whole path on the CPU at a rehearsal's size (Pallas kernel in
    interpret mode): set-up makes the group column on the device, the timed
    call hands it to ``fit_binned``, the check's reference takes it through
    ``boost(extras=)``, and every line of the check holds."""
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    cfg = rehearsal.config(objective="lambdarank", rows=6_000,
                           model={"lambdarank_truncation_level": 30})
    cfg["data"].update(queries=50, query_size={
        "distribution": "lognormal", "sigma": 0.9, "least": 1,
        "largest": 1_251})
    cell = {"name": "r.fit", "kind": "fit", "chips": 1, "rounds_per_fit": 3}
    result, lines = rehearsal.run(cell, cfg, tmp_path, jax.devices()[:1])
    assert result["correct"], result["compared"]
    assert any("train 1-ndcg@10 after 3 rounds" in line
               for line in result["compared"])
    assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}


# -- the reader -------------------------------------------------------------------

RANK = "%sort.9 = (s32[8], s32[8], s32[8]) sort(%p)"


def test_the_reader_reads_the_gradients_scope(tmp_path, monkeypatch):
    monkeypatch.setitem(made.TF_OPS, RANK,
                        "jit(fit)/while/body/gbdt.rank/sort:")
    round_ = [(RANK, 3.0)] + made.ROUND
    said = []
    evidence = dict(made._evidence(tmp_path, [made._chip(0, round_ + round_)]),
                    say=said.append)
    assert rank_grad_ms_per_round.reduce(evidence) == pytest.approx(3.0)
    assert not said
    # the gradient is no part of the per-row objectives' reader
    from benchmarks.chip.layer_metrics import leaf_grad_ms_per_round

    assert leaf_grad_ms_per_round.reduce(evidence) == pytest.approx(2.0)


def test_the_reader_returns_nothing_without_the_scope_and_says_why(tmp_path):
    said = []
    evidence = dict(made._evidence(tmp_path, [made._chip(0, made.ROUND)]),
                    say=said.append)
    assert rank_grad_ms_per_round.reduce(evidence) is None
    assert said == ["rank_grad_ms_per_round: no op of the trace ran under "
                    "gbdt.rank"]
    # the program of another objective, recorded on the chip (PR 24)
    from benchmarks.chip import tracereduce

    trace = tracereduce.from_profile(tracereduce.read_profile(made.SCOPED))
    assert rank_grad_ms_per_round.reduce(
        {"trace": trace, "xplane": made.SCOPED, "config": {"max_depth": 6},
         "say": said.append}) is None
    assert len(said) == 2
    # and no trace at all
    assert rank_grad_ms_per_round.reduce(
        {"trace": trace, "xplane": None, "cell": {}, "state": {},
         "say": said.append}) is None


# -- the real size, for the TPU's compiler --------------------------------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return described


def test_mslr30k_rank_fit_compiles_for_one_described_chip(topo, manifest):
    """2,270,296 x 136 with the group column: the three sorts, the pair
    blocks and six ``hist_level`` calls (one block of all 136 features)
    compile for a v5e, inside a chip and over the bound from shapes."""
    from jax.sharding import SingleDeviceSharding

    from benchmarks.chip.tests.test_compile_rehearsal import (
        HBM_BYTES, resident_bytes, total_bytes)

    one = SingleDeviceSharding(topo.devices[0])
    cell, config = harness.load_cell(manifest, CELL)
    rows, rounds = config["rows"], cell["rounds_per_fit"]

    def given(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    model = fit.make_model(config, rounds)
    compiled = model._fit_fn(rounds, "pallas").lower(
        given((rows, config["num_feature"]), jnp.uint8),
        given((rows,), jnp.float32), given((rows,), jnp.float32),
        group=given((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    assert "gbdt.rank" in text and text.count(" sort(") >= 3
    assert resident_bytes(config) < total_bytes(compiled) < HBM_BYTES
