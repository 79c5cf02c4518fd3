"""Smoke tests running the example entry points end-to-end (CPU)."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(script, args, env_extra=None):
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, script] + args, env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.slow
def test_train_logreg_example(tmp_path):
    rng = np.random.RandomState(0)
    lines = []
    for i in range(400):
        x = rng.randn(8)
        y = int(x[0] + x[1] > 0)
        feats = " ".join(f"{j}:{x[j]:.4f}" for j in range(8))
        lines.append(f"{y} {feats}")
    data = tmp_path / "train.libsvm"
    data.write_text("\n".join(lines) + "\n")
    proc = run_example(os.path.join(REPO, "examples", "train_logreg.py"),
                       ["--data", str(data), "--num-feature", "8",
                        "--batch-size", "64", "--epochs", "1"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "loss=" in proc.stderr or "loss=" in proc.stdout


@pytest.mark.slow
def test_train_gbdt_example(tmp_path):
    rng = np.random.RandomState(1)
    rows = []
    for i in range(600):
        x = rng.randn(4)
        y = int(x[0] * x[1] > 0)
        rows.append(",".join([str(y)] + [f"{v:.4f}" for v in x]))
    data = tmp_path / "train.csv"
    data.write_text("\n".join(rows) + "\n")
    ckpt = tmp_path / "model.bin"
    proc = run_example(os.path.join(REPO, "examples", "train_gbdt.py"),
                       ["--data", f"{data}?format=csv&label_column=0",
                        "--num-feature", "4", "--rounds", "5",
                        "--max-depth", "3", "--num-bins", "16",
                        "--checkpoint", str(ckpt)])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "rows/sec" in proc.stdout
    assert ckpt.exists()


def test_bench_pipeline_infeed_roundtrip(tmp_path, capsys):
    """genrec -> infeed harness: every record lands on the device batches."""
    import sys

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import bench_pipeline
    finally:
        sys.path.pop(0)

    rec = str(tmp_path / "t.rec")
    bench_pipeline.genrec(rec, records=1000, nbytes=64)
    bench_pipeline.bench_infeed(rec, record_bytes=64, batch=128)
    out = capsys.readouterr().out
    assert "1000 records" in out


@pytest.mark.slow
def test_train_mlp_example(tmp_path):
    rng = np.random.RandomState(1)
    lines = []
    for i in range(300):
        x = rng.randn(6)
        y = int(x[0] - x[1] > 0)
        feats = " ".join(f"{j}:{x[j]:.4f}" for j in range(6))
        lines.append(f"{y} {feats}")
    data = tmp_path / "train.libsvm"
    data.write_text("\n".join(lines) + "\n")
    proc = run_example(os.path.join(REPO, "examples", "train_mlp.py"),
                       ["--data", str(data), "--num-feature", "6",
                        "--hidden", "16", "--batch-size", "64",
                        "--epochs", "1"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "epoch 0: loss=" in proc.stderr + proc.stdout


@pytest.mark.slow
def test_train_gbdt_example_with_eval(tmp_path):
    rng = np.random.RandomState(9)
    for name, n in (("tr", 900), ("ev", 300)):
        lines = []
        for i in range(n):
            x = rng.randn(4)
            y = int(x[0] + x[1] > 0)
            feats = " ".join(f"{j}:{x[j]:.4f}" for j in range(4))
            lines.append(f"{y} {feats}")
        (tmp_path / f"{name}.libsvm").write_text("\n".join(lines) + "\n")
    proc = run_example(os.path.join(REPO, "examples", "train_gbdt.py"),
                       ["--data", str(tmp_path / "tr.libsvm"),
                        "--eval-data", str(tmp_path / "ev.libsvm"),
                        "--num-feature", "4", "--rounds", "20",
                        "--max-depth", "3", "--num-bins", "16",
                        "--early-stopping-rounds", "3"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "eval: first" in proc.stdout
    assert "trees kept" in proc.stdout


@pytest.mark.slow
def test_train_gbdt_resumable_checkpoints(tmp_path):
    """--checkpoint-dir: a fresh run writes step checkpoints; a rerun with
    more rounds resumes from the latest instead of starting over."""
    rng = np.random.RandomState(3)
    lines = []
    for i in range(600):
        x = rng.randn(6)
        y = int(x[0] - x[2] > 0)
        feats = " ".join(f"{j}:{x[j]:.4f}" for j in range(6))
        lines.append(f"{y} {feats}")
    data = tmp_path / "train.libsvm"
    data.write_text("\n".join(lines) + "\n")
    ckpt = tmp_path / "ckpts"
    script = os.path.join(REPO, "examples", "train_gbdt.py")
    base_args = ["--data", str(data), "--num-feature", "6",
                 "--max-depth", "3", "--hist-method", "scatter",
                 "--checkpoint-dir", str(ckpt), "--checkpoint-every", "2"]
    proc = run_example(script, base_args + ["--rounds", "4"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert (ckpt / "ckpt-00000002").exists()
    proc = run_example(script, base_args + ["--rounds", "6"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "resuming from checkpoint step 2" in proc.stdout
    # throughput honesty: the resumed run reports only the rounds IT trained
    assert "trained 4 rounds" in proc.stdout


@pytest.mark.slow
def test_train_mlp_resumable_checkpoints(tmp_path):
    """--checkpoint-dir on the MLP example: params + optimizer state
    round-trip through CheckpointManager's template restore; a rerun with
    more epochs resumes rather than restarting."""
    rng = np.random.RandomState(5)
    lines = []
    for i in range(512):
        x = rng.randn(8)
        y = int(x[0] + x[3] > 0)
        feats = " ".join(f"{j}:{x[j]:.4f}" for j in range(8))
        lines.append(f"{y} {feats}")
    data = tmp_path / "train.libsvm"
    data.write_text("\n".join(lines) + "\n")
    ckpt = tmp_path / "ckpts"
    script = os.path.join(REPO, "examples", "train_mlp.py")
    base = ["--data", str(data), "--num-feature", "8", "--batch-size",
            "128", "--checkpoint-dir", str(ckpt)]
    proc = run_example(script, base + ["--epochs", "2"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert (ckpt / "ckpt-00000001").exists()
    proc = run_example(script, base + ["--epochs", "3"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout + proc.stderr
    assert "resuming from checkpoint epoch 1" in out


@pytest.mark.slow
def test_train_gbdt_distributed_cli(tmp_path):
    """Under a multi-worker launch the GBDT CLI trains ONE global
    data-parallel model (not N per-shard models) and reports the global
    row count; rank 0 writes the final checkpoint."""
    rng = np.random.RandomState(11)
    lines = []
    for i in range(1000):
        x = rng.randn(6)
        y = int(x[0] + x[1] > 0)
        feats = " ".join(f"{j}:{x[j]:.4f}" for j in range(6))
        lines.append(f"{y} {feats}")
    data = tmp_path / "train.libsvm"
    data.write_text("\n".join(lines) + "\n")
    ckpt = tmp_path / "model.bin"
    from tests.conftest import run_tracker_workers

    proc = run_tracker_workers(
        tmp_path, None, 2,
        script_path=os.path.join(REPO, "examples", "train_gbdt.py"),
        script_args=["--data", str(data), "--num-feature", "6", "--rounds",
                     "4", "--max-depth", "3", "--num-bins", "16",
                     "--hist-method", "scatter", "--checkpoint", str(ckpt)])
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = proc.stdout + proc.stderr
    # both ranks print the SAME global summary (one SPMD program)
    assert out.count("over 2 workers") == 2, out[-2000:]
    assert "on 1000 rows" in out
    assert ckpt.exists()
