"""Test configuration: force an 8-device virtual CPU mesh before jax import.

Multi-chip hardware is not available in CI; sharding tests run on
``--xla_force_host_platform_device_count=8`` as the SURVEY.md §4 test strategy
prescribes (the "fake cluster" the reference never had).
"""

import os

# Force CPU even when the environment points at a real accelerator: the test
# suite validates sharding semantics on a virtual mesh, not device perf.
# The variable is set before jax is imported anywhere, and children inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")


def run_tracker_workers(tmp_path, script_text, nworkers, env_extra=None,
                        timeout=600, script_path=None, script_args=()):
    """Shared multi-process launch recipe: write a worker script (or use an
    existing one via ``script_path`` + ``script_args``), run it under
    `dmlc-submit --cluster local`, return the CompletedProcess.

    Used by the tracker/collective/distributed-model e2e tests so the env
    hygiene (CPU forcing, PYTHONPATH, XLA_FLAGS scrubbing, RESULT_DIR)
    lives in exactly one place.
    """
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if script_path is None:
        script_path = tmp_path / "worker.py"
        script_path.write_text(script_text)
    env = os.environ.copy()
    env["RESULT_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    cmd = [sys.executable, "-m", "dmlc_core_tpu.tracker.submit",
           "--cluster", "local", "--num-workers", str(nworkers), "--",
           sys.executable, str(script_path), *map(str, script_args)]
    return subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                          text=True, timeout=timeout)
