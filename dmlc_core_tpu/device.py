"""The one place an entry point meets the accelerator.

Every program that touches JAX (``examples/*``, ``python -m
dmlc_core_tpu.serve``, ``python -m dmlc_core_tpu.train``, ``bench.py``,
``chip_smoke.py``, ``__graft_entry__``) calls :func:`init_device` first.
It does three things and nothing else:

- **compile cache**: when ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
  itself and no code sets another directory; when it is not, the cache
  goes to one fixed path inside the checkout (``<root>/.jax_cache``,
  git-ignored).  The path is part of the cache key, so it is derived from
  the package location — never from a temp dir, a pid or a clock — and
  child processes (fleet replicas, tracker workers) that call this
  function land on the same directory as their parent.  On the CPU that
  default directory is placed but not used: an XLA:CPU entry is machine
  code for the CPU that compiled it (the loader warns of SIGILL on any
  feature mismatch, and does so even on the compiling machine), a
  checkout travels between machines, and CPU compiles are cheap.
- **names are part of what is cached**: this system reads its own
  profiles (the ``gbdt.*`` scopes of ``models/gbdt.py``, found again in a
  ``jax.profiler`` trace's ``tf_op``), and JAX's cache key leaves the name
  stack out by default — a change that only adds or renames a
  ``jax.named_scope`` then loads its parent's executable and its trace
  shows the parent's names: a wrong measurement with nothing wrong in the
  code.  So ``jax_compilation_cache_include_metadata_in_key`` is set.  With
  it the key also holds source file names, and a checkout is not always
  unpacked at one path, so ``jax_hlo_source_file_canonicalization_regex``
  strips the checkout root (from the package location, like the default
  cache directory): two copies of one tree share their cache entries.
- **device statement**: returns and logs what JAX found.  It never changes
  the platform and never falls back: the CPU is used when, and only when,
  the caller's environment says ``JAX_PLATFORMS=cpu``.  A run that asked
  for nothing and got the CPU (JAX's silent no-accelerator fallback) is an
  error here, not a slower success.
"""

from __future__ import annotations

import os
import re
from typing import NamedTuple

from dmlc_core_tpu.utils.logging import log_info

__all__ = ["DeviceInfo", "init_device", "CACHE_ENV", "DEFAULT_CACHE_DIR"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT_ROOT, ".jax_cache")


class DeviceInfo(NamedTuple):
    """What JAX reports for this process, plus where compiles are cached."""

    platform: str      # jax.devices()[0].platform
    device_kind: str   # jax.devices()[0].device_kind
    count: int         # len(jax.devices())
    cache_dir: str

    def as_dict(self) -> dict:
        """The ``device`` object benchmark and smoke JSON lines carry."""
        return {"platform": self.platform, "kind": self.device_kind,
                "count": self.count}


def init_device() -> DeviceInfo:
    """State the device this process runs on and place its compile cache.

    Raises ``RuntimeError`` when JAX landed on the CPU without
    ``JAX_PLATFORMS=cpu`` having asked for it.
    """
    import jax

    devices = jax.devices()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(_CHECKOUT_ROOT + os.sep))
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        if devices[0].platform == "cpu":
            jax.config.update("jax_enable_compilation_cache", False)
    info = DeviceInfo(devices[0].platform, devices[0].device_kind,
                      len(devices), jax.config.jax_compilation_cache_dir)
    requested = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if info.platform == "cpu" and requested != "cpu":
        raise RuntimeError(
            f"JAX found no accelerator (JAX_PLATFORMS={requested or 'unset'}"
            f", devices={devices}) and would run on the CPU; set "
            f"JAX_PLATFORMS=cpu to run on the CPU on purpose")
    log_info(f"device: platform={info.platform} kind={info.device_kind} "
             f"count={info.count} compile_cache={info.cache_dir}")
    return info
