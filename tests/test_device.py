"""dmlc_core_tpu.device.init_device and the "no hidden device" rules around it.

The contract (docs: dmlc_core_tpu/device.py): the compile cache goes where
JAX_COMPILATION_CACHE_DIR says or to one fixed in-checkout path; the device
statement never changes the platform and never falls back; interpret mode,
mesh reshapes and child environments cannot quietly move work off the chip.
"""

import os
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REPORT = ("import jax, json; from dmlc_core_tpu.device import init_device; "
           "info = init_device(); print(json.dumps({"
           "'cache': jax.config.jax_compilation_cache_dir, "
           "'used': jax.config.jax_enable_compilation_cache, "
           "'info': list(info)}))")


def _init_in_subprocess(cwd, **env_overrides):
    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for key, value in env_overrides.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run([sys.executable, "-c", _REPORT], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout(tmp_path):
    """Two processes started from different directories land on the same
    cache directory: <repo>/.jax_cache, derived from the package location."""
    import json

    other = tmp_path / "elsewhere"
    other.mkdir()
    seen = []
    for cwd in (REPO, str(other)):
        proc = _init_in_subprocess(cwd)
        assert proc.returncode == 0, proc.stderr[-2000:]
        seen.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    assert seen[0]["cache"] == seen[1]["cache"] \
        == os.path.join(REPO, ".jax_cache")
    assert seen[0]["info"] == ["cpu", "cpu", 1, seen[0]["cache"]]
    # placed, but not used on the CPU: XLA:CPU entries are machine code for
    # the CPU that compiled them and a checkout travels between machines
    assert seen[0]["used"] is False
    # the statement is logged, on stderr, for whoever reads the run
    assert "device: platform=cpu" in proc.stderr


def test_cache_dir_env_set_means_code_sets_nothing(tmp_path):
    import json

    wanted = str(tmp_path / "given-cache")
    proc = _init_in_subprocess(REPO, JAX_COMPILATION_CACHE_DIR=wanted)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["cache"] == wanted            # jax read the env var itself
    assert got["info"][3] == wanted
    assert got["used"] is True               # ...and nothing else was touched


# -- names are part of what is cached -----------------------------------------
#
# A program run as ``python <checkout>/prog.py <plain|scoped> [options]``:
# it calls init_device(), compiles one function — with a
# ``jax.named_scope("gbdt.route")`` inside it or without — and reports
# whether the compiled text names the scope and what the cache holds.
_CACHE_PROG = """
import json, os, sys
import jax, jax.numpy as jnp
from dmlc_core_tpu.device import init_device

info = init_device()
for option in sys.argv[2:]:
    name, value = option.split("=")
    jax.config.update(name, {"False": False, "None": None}[value])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

def f(x):
    if sys.argv[1] == "scoped":
        with jax.named_scope("gbdt.route"):
            return jnp.sum(x * 2.0)
    return jnp.sum(x * 2.0)

text = jax.jit(f).lower(jnp.ones((8, 128))).compile().as_text()
print(json.dumps({
    "scope": "gbdt.route" in text,
    "entries": sorted(os.listdir(info.cache_dir)),
    "metadata_in_key":
        jax.config.jax_compilation_cache_include_metadata_in_key,
    "regex": jax.config.jax_hlo_source_file_canonicalization_regex}))
"""


def _checkout_copy(where):
    """A stand-in for a second copy of the tree at another path: the
    package is linked, not copied, and ``init_device`` derives the checkout
    root from where the package was imported from."""
    where.mkdir()
    os.symlink(os.path.join(REPO, "dmlc_core_tpu"),
               where / "dmlc_core_tpu", target_is_directory=True)
    (where / "prog.py").write_text(_CACHE_PROG)
    return where


def _run_cache_prog(checkout, cache, *argv):
    import json

    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(checkout)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    proc = subprocess.run([sys.executable, str(checkout / "prog.py"), *argv],
                          cwd=str(checkout), env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_init_device_puts_names_into_the_cache_key(tmp_path):
    import re

    checkout = _checkout_copy(tmp_path / "tree")
    got = _run_cache_prog(checkout, tmp_path / "cache", "plain")
    assert got["metadata_in_key"] is True
    # ...and takes the checkout's own path out of it again
    assert re.sub(got["regex"], "", str(checkout / "prog.py")) == "prog.py"


@pytest.mark.parametrize("metadata_in_key", [True, False])
def test_a_scope_added_since_the_cached_compile_is_not_lost(
        tmp_path, metadata_in_key):
    """One cache, the same function compiled plain and then with a
    ``named_scope`` added.  With JAX's default key (names left out) the
    second compile loads the first one's executable and the scope is gone
    from its text and from any profile; ``init_device`` keys on the names,
    so the scoped program is compiled and cached beside the plain one."""
    checkout = _checkout_copy(tmp_path / "tree")
    cache = tmp_path / "cache"
    options = [] if metadata_in_key else [
        "jax_compilation_cache_include_metadata_in_key=False"]
    plain = _run_cache_prog(checkout, cache, "plain", *options)
    assert plain["scope"] is False and plain["entries"]
    scoped = _run_cache_prog(checkout, cache, "scoped", *options)
    if metadata_in_key:
        assert scoped["scope"] is True
        assert len(scoped["entries"]) > len(plain["entries"])
    else:       # the staleness init_device exists to prevent
        assert scoped["scope"] is False
        assert scoped["entries"] == plain["entries"]


@pytest.mark.parametrize("canonical", [True, False])
def test_a_second_copy_of_the_tree_hits_the_first_copys_cache(
        tmp_path, canonical):
    """With names in the key the key also holds source file names; the
    checkout root is stripped from them, so a copy of the tree at another
    path compiles nothing anew (without the regex it would)."""
    cache = tmp_path / "cache"
    options = [] if canonical else [
        "jax_hlo_source_file_canonicalization_regex=None"]
    first = _run_cache_prog(_checkout_copy(tmp_path / "one"), cache,
                            "scoped", *options)
    second = _run_cache_prog(_checkout_copy(tmp_path / "two"), cache,
                             "scoped", *options)
    assert first["entries"] and first["scope"] and second["scope"]
    if canonical:
        assert second["entries"] == first["entries"]
    else:
        assert len(second["entries"]) > len(first["entries"])


def test_cpu_without_an_explicit_request_is_an_error():
    """JAX's silent no-accelerator fallback is refused: the CPU is used
    when, and only when, the environment says JAX_PLATFORMS=cpu."""
    proc = _init_in_subprocess(REPO, JAX_PLATFORMS=None)
    assert proc.returncode != 0
    assert "JAX found no accelerator" in proc.stderr
    assert "JAX_PLATFORMS=cpu" in proc.stderr


def test_interpret_mode_is_refused_on_a_tpu_backend(monkeypatch):
    import jax

    from dmlc_core_tpu.ops import hist_pallas

    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    assert hist_pallas.interpret_mode() is True          # CPU: honoured
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="refused on a TPU backend"):
        hist_pallas.interpret_mode()
    monkeypatch.setattr(hist_pallas, "_INTERPRET", False)
    assert hist_pallas.interpret_mode() is False         # off stays off


def test_interpret_env_var_is_what_sets_the_flag():
    code = ("from dmlc_core_tpu.ops import hist_pallas; "
            "print(hist_pallas._INTERPRET, hist_pallas.interpret_mode())")
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DMLC_TPU_PALLAS_INTERPRET"] = "1"
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["True", "True"]


def test_auto_on_tpu_means_pallas_with_nothing_probed(monkeypatch):
    import jax

    from dmlc_core_tpu.ops.histogram import resolve_hist_method

    assert resolve_hist_method("auto") == "scatter"      # this CPU host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_hist_method("auto") == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert resolve_hist_method("auto") == "scatter"      # runs anywhere
    assert resolve_hist_method("scatter") == "scatter"   # explicit wins


def test_a_kernel_the_compiler_rejects_raises_its_message(monkeypatch):
    """No probe stands between a request for the kernel and the compiler:
    what Mosaic says about the one kernel body reaches the caller."""
    import numpy as np

    from dmlc_core_tpu.ops import hist_pallas
    from dmlc_core_tpu.ops.histogram import grad_histogram

    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)

    def rejected(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel: nope\nmore")

    monkeypatch.setattr(hist_pallas, "_kernel", rejected)
    rows = np.zeros(128, np.float32)
    with pytest.raises(RuntimeError, match="Mosaic failed to compile TPU "
                                           "kernel: nope"):
        grad_histogram(np.zeros((128, 2), np.int32), rows.astype(np.int32),
                       rows, rows, 4, 8, method="pallas")


def _fake_devices(platform, n=4):
    return [types.SimpleNamespace(platform=platform, id=i, process_index=0)
            for i in range(n)]


def test_make_mesh_reshapes_only_cpu_devices(monkeypatch):
    import jax
    from jax.experimental import mesh_utils

    from dmlc_core_tpu.parallel.mesh import make_mesh

    def boom(*args, **kwargs):
        raise RuntimeError("topology-aware assignment failed")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", boom)
    # CPU devices: plain id-order reshape, the builder is never consulted
    mesh = make_mesh({"data": 4, "model": 2}, devices=jax.devices()[:8])
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    assert [d.id for d in np.asarray(mesh.devices).ravel()] == list(range(8))
    # hardware: the builder's error is the caller's error — no reshape
    with pytest.raises(RuntimeError, match="topology-aware assignment"):
        make_mesh({"data": 2, "model": 2}, devices=_fake_devices("tpu"))


def _capture_fleet_child_env(monkeypatch, **fleet_kwargs):
    from dmlc_core_tpu.serve import fleet as fleet_mod

    seen = {}

    class FakeProc:
        pid = 4242

        def poll(self):
            return None

    def fake_popen(argv, env=None, **kwargs):
        seen["env"] = env
        return FakeProc()

    monkeypatch.setattr(fleet_mod.subprocess, "Popen", fake_popen)
    fleet = fleet_mod.ReplicaFleet(1, ports=[1], **fleet_kwargs)
    fleet._launch(0)
    return seen["env"]


@pytest.mark.parametrize("parent", ["tpu", "tpu,cpu", None])
def test_fleet_children_inherit_jax_platforms_unchanged(monkeypatch, parent):
    """No child is defaulted to the CPU: a replica runs on whatever the
    parent's environment names (including nothing at all)."""
    if parent is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent)
    env = _capture_fleet_child_env(monkeypatch)
    assert env.get("JAX_PLATFORMS") == parent
    assert env["PYTHONPATH"].split(os.pathsep)[0] == REPO
    # an explicit per-fleet override still wins (the CPU drills use it)
    env = _capture_fleet_child_env(monkeypatch,
                                   extra_env={"JAX_PLATFORMS": "cpu"})
    assert env["JAX_PLATFORMS"] == "cpu"


def test_fleet_start_fails_at_once_with_the_replicas_own_error(tmp_path,
                                                               monkeypatch):
    """A replica that cannot start (on a one-chip host: the second one,
    at backend init) fails the fleet promptly and by name — not after the
    90 s readiness deadline, with no reason."""
    import time

    from dmlc_core_tpu.serve.fleet import ReplicaFleet

    fleet = ReplicaFleet(2, log_dir=str(tmp_path), auto_restart=False)
    monkeypatch.setattr(
        fleet, "_argv",
        lambda i: [sys.executable, "-c",
                   "import sys, time; "
                   + ("sys.stderr.write('Unable to initialize backend "
                      "tpu: chip is held'); sys.exit(3)" if i == 1
                      else "time.sleep(600)")])
    start = time.monotonic()
    with pytest.raises(RuntimeError) as err:
        fleet.start(timeout_s=90.0)
    assert time.monotonic() - start < 30
    assert "replica 1" in str(err.value) and "rc=3" in str(err.value)
    assert "Unable to initialize backend tpu: chip is held" in str(err.value)
    # a failed start leaves no orphan behind: replica 0 was reaped
    assert all(code is not None for code in fleet.poll())


def test_native_core_always_comes_through_make(monkeypatch):
    """An existing .so is not loaded as-is: the load goes through
    ``make -C native`` first, so a stale git-ignored library copied along
    with the tree can never stand in for the committed sources."""
    from dmlc_core_tpu import native_bridge

    assert os.path.exists(native_bridge._SO_PATH) or \
        native_bridge.available()
    calls = []
    monkeypatch.setattr(native_bridge, "_lib", None)
    monkeypatch.setattr(native_bridge, "_tried", False)
    monkeypatch.setattr(native_bridge, "_build",
                        lambda: calls.append(1) or False)
    assert native_bridge._load() is None     # make said no -> no dlopen
    assert calls == [1]
