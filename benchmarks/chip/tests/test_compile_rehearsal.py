"""Compile both fit cells at their real size for a DESCRIBED v5e (no chip
attached): the TPU's compiler refuses here what it would refuse there — a
kernel Mosaic cannot lower, a program that does not fit a chip's 15.75 GB.
A compile that passes is not a chip run and gives no time.

All in this one file, the topology described inside a fixture, so that
only the worker that runs the file loads the TPU's library.
"""

import os

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.traffic import fit

HBM_BYTES = 15.75e9      # what the compiler gives a v5e program


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile can be written to the persistent cache
    # but never read back: keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return described


def compiled_fit(cell_name, shardings):
    import jax
    import jax.numpy as jnp

    manifest = harness.load_manifest()
    cell, config = harness.load_cell(manifest, cell_name)
    rows, rounds = config["rows"], cell["rounds_per_fit"]
    model = fit.make_model(config, rounds)
    rows2d, rows1d = shardings
    args = (jax.ShapeDtypeStruct((rows, config["num_feature"]), jnp.uint8,
                                 sharding=rows2d),
            jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=rows1d),
            jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=rows1d))
    # code that asks jax.default_backend() sees the CPU here, so the test
    # names the method the chip resolves ``auto`` to
    return model._fit_fn(rounds, "pallas").lower(*args).compile(), config


def total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def resident_bytes(config, chips=1):
    """The least a fit that holds its chip's rows can compile to: the uint8
    bins it is handed and the int32 ``[F, rows]`` copy the kernel reads, 5
    bytes a row-feature.  What this lower bound guards is that the set is
    resident (a program that streamed or dropped rows would fall under
    it).  It is NOT the driver's admission floor of a quarter of the
    chip: that is read from a run's ``memory_peak_bytes`` (3.51 / 7.20 GB
    a chip, PERF.md section 4), which holds what else the process keeps
    beside this one program (2.64 / 1.95 GB since PR 28)."""
    return config["rows"] // chips * config["num_feature"] * 5


def test_higgs11m_fit_compiles_for_one_described_chip(topo):
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    compiled, config = compiled_fit("higgs11m.fit", (one, one))
    assert "tpu_custom_call" in compiled.as_text()
    assert resident_bytes(config) < total_bytes(compiled) < HBM_BYTES


def test_airline_dp4_fit_compiles_for_the_described_2x2(topo):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    with mesh:
        compiled, config = compiled_fit(
            "airline115m.fit.dp4",
            (NamedSharding(mesh, P("data", None)),
             NamedSharding(mesh, P("data"))))
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-reduce" in hlo
    assert f"[{config['rows']},{config['num_feature']}]" not in hlo
    # per chip, with room left for what else the process keeps there
    assert (resident_bytes(config, chips=4) < total_bytes(compiled)
            < HBM_BYTES - 1.5e9)


def test_bosch1m_fit_compiles_for_one_described_chip(topo):
    """The fourth shape, 1,183,747 x 968 with ``handle_missing``: two gains
    a candidate in split scoring and the default-left pick in routing
    compile for a v5e, the kernel one call a level over 8 feature blocks,
    inside a chip and over the bound from shapes."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    compiled, config = compiled_fit("bosch1m.fit", (one, one))
    assert fit.make_model(config, 1).param.handle_missing
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == config["max_depth"]
    assert resident_bytes(config) < total_bytes(compiled) < HBM_BYTES
