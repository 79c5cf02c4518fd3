"""``run.py`` refuses to measure without the TPU and the cell's chips,
and cannot run where the program is not."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip import harness, run

ARGS = ["--seed", "1", "--seconds", "1", "--trace", "0"]


def no_result_line(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_refuses_any_platform_but_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--workload", "higgs11m.fit", *ARGS],
        capture_output=True, text=True, env=env, cwd=harness.ROOT,
        timeout=300)
    assert done.returncode == run.EXIT_NO_DEVICE, done.stderr[-2000:]
    assert no_result_line(done.stdout)
    assert "refusing to measure" in done.stderr


def test_refuses_fewer_chips_than_the_cell_asks_for(monkeypatch, capsys):
    from dmlc_core_tpu import device

    one_chip = device.DeviceInfo("tpu", "TPU v5 lite", 1, "/nowhere")
    monkeypatch.setattr(device, "init_device", lambda: one_chip)
    rc = run.main(["--workload", "airline115m.fit.dp4", *ARGS])
    out = capsys.readouterr()
    assert rc == run.EXIT_NO_DEVICE
    assert no_result_line(out.out) and "needs 4 tpu chip(s)" in out.err


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell(harness.load_manifest(), "no.such.cell")


def test_fails_where_only_the_benchmark_is(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure: non-zero, no result."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "higgs11m.fit", *ARGS], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=300)
    assert done.returncode != 0
    assert no_result_line(done.stdout)


def test_prints_what_was_compared_last_on_both_streams(monkeypatch, capfd):
    """Every number compared beside its limit: the last lines of stderr,
    and the last key of the result's line."""
    from dmlc_core_tpu import device

    one_chip = device.DeviceInfo("tpu", "TPU v5 lite", 1, "/nowhere")
    monkeypatch.setattr(device, "init_device", lambda: one_chip)
    compared = ["ok: a number 0.01 (limit 0.02)", "FAILED: another 3 (limit 2)"]
    monkeypatch.setattr(
        harness, "run_cell", lambda ctx, manifest, t0: {
            "correct": False, "attempted": 1, "failed": 0, "metrics": {},
            "device": {}, "compared": compared})
    assert run.main(["--workload", "higgs11m.fit", *ARGS]) == 0
    out = capfd.readouterr()
    assert out.err.splitlines()[-2:] == compared
    line = json.loads(out.out.splitlines()[-1])
    assert list(line)[-1] == "compared" and line["compared"] == compared
