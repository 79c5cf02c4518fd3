"""chip_smoke.py on the CPU: the same phase functions the chip run drives,
at tiny size, with interpret mode switched on EXPLICITLY by the test —
never by the script discovering it has no chip — plus the refusal contract
of the script itself."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_under_test", os.path.join(REPO, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


smoke = _load_smoke()

TINY = smoke.Config(rows=3000, num_feature=4, num_bins=16, max_depth=3,
                    rounds=3, batch_rows=1024, max_batch=4,
                    request_sizes=(1, 3), hist_method="pallas",
                    acc_floor=0.8)


@pytest.fixture
def interpreted(monkeypatch):
    from dmlc_core_tpu.ops import hist_pallas

    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)


@pytest.fixture(scope="module")
def libsvm(tmp_path_factory):
    return smoke.data_phase(TINY, str(tmp_path_factory.mktemp("smoke")))


def test_flagship_is_the_full_width_model():
    cfg = smoke.FLAGSHIP
    assert (cfg.num_feature, cfg.num_bins, cfg.max_depth, cfg.rounds) \
        == (28, 256, 6, 10)
    assert cfg.rows >= 200_000 and cfg.hist_method == "auto"
    assert len(cfg.request_sizes) >= 2 and max(cfg.request_sizes) \
        <= cfg.max_batch


def test_native_phase_builds_and_loads_the_core():
    smoke.native_phase()


def test_train_phase_runs_the_kernel_path_and_checks_hold(libsvm,
                                                          interpreted):
    times = {}
    trained = smoke.train_phase(TINY, libsvm, times)
    assert trained["method"] == "pallas"
    assert str(trained["bins"].dtype) == "uint8"          # the wire dtype
    assert trained["bins"].shape == (3072, 4)             # 3 x 1024, padded
    assert int(np.asarray(trained["weight"]).sum()) == TINY.rows
    smoke.check_train(TINY, trained, expect_method="pallas")
    assert {"parse_bin_feed_s", "fit_compile_and_first_run_s",
            "fit_second_run_s"} <= set(times)
    # and the checks can fail: a floor above the fit, a method mismatch
    with pytest.raises(AssertionError, match="train accuracy"):
        smoke.check_train(TINY._replace(acc_floor=1.0), trained, "pallas")
    with pytest.raises(AssertionError, match="resolved to"):
        smoke.check_train(TINY, trained, expect_method="scatter")


def test_kernel_vs_scatter_check_catches_a_wrong_histogram(libsvm,
                                                           interpreted,
                                                           monkeypatch):
    from dmlc_core_tpu.ops import hist_pallas

    trained = smoke.train_phase(TINY, libsvm, {})
    real = hist_pallas.grad_hist_pallas

    def skewed(*args, **kwargs):
        G, H = real(*args, **kwargs)
        return G * 1.5, H

    monkeypatch.setattr(hist_pallas, "grad_hist_pallas", skewed)
    with pytest.raises(AssertionError, match="G histogram vs scatter"):
        smoke.check_train(TINY, trained, expect_method="pallas")


def test_serve_phase_publishes_reloads_and_serves(libsvm, interpreted,
                                                  tmp_path):
    times = {}
    trained = smoke.train_phase(TINY, libsvm, times)
    smoke.serve_phase(TINY, trained, str(tmp_path), times)
    assert os.path.isdir(tmp_path / "ckpt")
    assert {"serve_warmup_s", "requests_s"} <= set(times)


def test_mesh_phase_shards_rows_and_keeps_the_kernel(libsvm, interpreted,
                                                     monkeypatch):
    """The multi-chip phase on the 8-device virtual mesh.  The optimized-HLO
    assertion is a hardware statement (interpret mode lowers the kernel to
    ordinary ops), so only its CPU-visible half is driven here: every
    device holds 1/N of the rows and the fit resolves to the kernel."""
    import jax

    from dmlc_core_tpu.device import DeviceInfo

    trained = smoke.train_phase(TINY, libsvm, {})
    n = len(jax.devices())
    assert n == 8
    seen = []
    real_check = smoke.check

    def check(cond, what):
        seen.append(what)
        if "Mosaic kernel on per-chip row shards" in what:
            return                      # hardware-only: see docstring
        real_check(cond, what)

    monkeypatch.setattr(smoke, "check", check)
    smoke.mesh_phase(TINY, libsvm, trained,
                     DeviceInfo("cpu", "cpu", n, ""), {})
    assert any("every chip holds 1/8 of the rows (384 of 3072)" in s
               for s in seen), seen
    assert any(s.startswith("data=4 x model=2: method 'pallas'")
               for s in seen), seen


def _run_script(cwd, script, **env_overrides):
    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)
    env.update(env_overrides)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_the_cpu_and_names_the_reason():
    """``python chip_smoke.py`` under JAX_PLATFORMS=cpu: non-zero exit, the
    reason on stderr, and no result line on stdout."""
    proc = _run_script(REPO, "chip_smoke.py", JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "platform is 'tpu' (JAX reports 'cpu')" in proc.stderr
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert lines and not lines[-1].startswith("{")
    for line in lines:
        if line.startswith("{"):
            assert "ok" not in json.loads(line)


def test_script_alone_in_a_directory_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env_path = os.pathsep.join(
        p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
        if p and os.path.abspath(p) != REPO)
    proc = _run_script(str(tmp_path), "chip_smoke.py",
                       JAX_PLATFORMS="cpu", PYTHONPATH=env_path)
    assert proc.returncode != 0
    assert "No module named 'dmlc_core_tpu'" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())
