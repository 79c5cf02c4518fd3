"""A listwise configuration on a mesh arrives as files: the ``fit`` kind
runs ``lambdarank`` rows sharded over ``data`` on four virtual devices
(set-up, window, check; the check lowers the timed fit with the
objective's own arguments), and the group column lies in whole queries on
every chip.  Without a mesh the group column is the one it always was."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import datagen, harness
from benchmarks.chip.objectives import lambdarank
from benchmarks.chip.tests import rehearsal
from benchmarks.chip.traffic import fit
from dmlc_core_tpu.ops import hist_pallas
from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

CELL = {"name": "r.fit.dp4", "kind": "fit", "chips": 4, "rounds_per_fit": 2}


def rank_config(mesh, rows=8192, queries=64):
    """The rehearsal table as a ranking set: ``queries`` log-normal
    queries, LambdaMART truncated at 30, rows over ``mesh``."""
    cfg = rehearsal.config(objective="lambdarank", rows=rows, mesh=mesh,
                           model={"lambdarank_truncation_level": 30})
    cfg["data"].update(queries=queries, query_size={
        "distribution": "lognormal", "sigma": 0.9, "least": 1,
        "largest": 1_251})
    return cfg


def shape_of(line):
    """A check line with its numbers blanked: what it compares."""
    return re.sub(r"\d[\d.e+-]*", "#", line)


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)


def test_a_ranking_fit_on_four_devices_passes_every_check_line(interpret,
                                                               tmp_path):
    """8,192 rows x 5 features in 64 queries over ``{data: 4}``: every
    line of the check holds, the optimized-HLO line of the mesh among
    them; on one device without a mesh the same lines hold but that one."""
    for side in ("four", "one"):
        (tmp_path / side).mkdir()
    result, _ = rehearsal.run(CELL, rank_config({"data": 4}),
                              tmp_path / "four", jax.devices()[:4])
    assert result["correct"], result["compared"]
    assert result["device"]["count"] == 4
    (hlo,) = [line for line in result["compared"]
              if "optimized HLO" in line]
    assert hlo.startswith("ok: ") and "every chip holds 2048 rows" in hlo
    assert any("train 1-ndcg@10 after 2 rounds" in line
               for line in result["compared"])
    one, _ = rehearsal.run({**CELL, "name": "r.fit", "chips": 1},
                           rank_config(None), tmp_path / "one",
                           jax.devices()[:1])
    assert one["correct"], one["compared"]
    assert [shape_of(line) for line in one["compared"]] == [
        shape_of(line) for line in result["compared"] if line != hlo]


def _group(cfg, seed, rows, devices):
    model = fit.make_model(cfg, 1)
    fit.fit_bins(cfg, seed, model)
    sharding = None
    if cfg["mesh"]:
        sharding = data_sharding(make_mesh(dict(cfg["mesh"]),
                                           devices=devices))
    *_, extras = datagen.device_binned(cfg, seed, rows, model.boundaries,
                                       jnp.uint8, sharding)
    return extras["group"]


@pytest.mark.parametrize("seed", [5, 4_200_000_017])
def test_every_shard_holds_whole_queries(seed):
    """Under ``{data: 4}`` a chip's first row opens a query and its last
    closes one, each chip holds a quarter of the queries, and the ids
    ascend over the whole column."""
    rows, queries = 8192, 64
    group = _group(rank_config({"data": 4}, rows, queries), seed, rows,
                   jax.devices()[:4])
    ids = np.asarray(group)
    assert ids[0] == 0 and ids[-1] == queries - 1
    assert set(np.diff(ids)) == {0, 1}
    assert len(group.addressable_shards) == 4
    for shard in group.addressable_shards:
        start = shard.index[0].start or 0
        mine = np.asarray(shard.data)
        assert mine.shape == (rows // 4,)
        assert np.array_equal(mine, ids[start:start + rows // 4])
        assert start == 0 or ids[start - 1] != mine[0]       # opens a query
        stop = start + rows // 4
        assert stop == rows or ids[stop] != mine[-1]         # closes one
        assert len(np.unique(mine)) == queries // 4
    sizes = np.bincount(ids)
    assert sizes.min() >= 1 and sizes.max() <= 1_251


@pytest.mark.parametrize("rows, queries", [(8190, 64), (8192, 66)])
def test_a_mesh_the_queries_cannot_divide_is_refused(rows, queries):
    cfg = rank_config({"data": 4}, rows, queries)
    with pytest.raises(ValueError, match="give rows and data.queries "
                                         "multiples of 4"):
        lambdarank.data_shards(cfg, rows)
    with pytest.raises(ValueError, match="cannot lie whole on the 4 chips"):
        _group(cfg, 5, rows, jax.devices()[:4])


# sha256 of the little-endian int32 group column that the configuration
# without a mesh made before its queries were drawn a shard at a time
MSLR_GROUP_SHA256 = {
    3_600_000_003:
        "200e3bbe6dc4b549a4b1286ee99098e91eb72d303ee257e7536697e85bfd08e6",
    4_200_000_001:
        "19865855720ce0a6af1e52b6b054d6ddbbc30069db924040e9a726e3c1a2dca0",
}


@pytest.mark.parametrize("seed", sorted(MSLR_GROUP_SHA256))
def test_without_a_mesh_the_group_column_is_unchanged(seed):
    """The MSLR configuration (no mesh) at 65,536 rows: the one draw over
    every row, to the bit."""
    _, config = harness.load_cell(harness.load_manifest(),
                                  "mslr30k.rank.fit")
    assert config["mesh"] is None
    ids = np.asarray(_group(config, seed, 65_536, None))
    assert ids.dtype == np.int32
    digest = hashlib.sha256(ids.astype("<i4").tobytes()).hexdigest()
    assert digest == MSLR_GROUP_SHA256[seed]
