"""The persistent XLA compilation cache: ONE owner of where it lives.

A cold process compiles every executable it dispatches; a server restart, a
respawned fleet replica, the second phase of `chip_smoke.py` and every test
module after `jax.clear_caches()` would all pay that again. JAX can keep
compiled executables on disk — this module decides where:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads that variable itself, so the
  program uses that directory and sets NO directory in code. Child processes
  inherit the variable.
- unset: one fixed path inside the checkout, `<repo>/.jax_cache` (git- and
  chiprun-ignored). Fixed matters: the path takes part in the cache key, so
  a directory that moves per run (temp name, pid, timestamp) never hits.

Stdlib-only at import: launchers that must stay off JAX can still ask
`cache_dir()`.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
  """The directory the compile cache lives in for this process."""
  return os.environ.get(ENV) or str(REPO_CACHE)


_enabled_dir: Optional[str] = None


def enable(min_compile_secs: float = 0.0) -> str:
  """Turn the persistent cache on for this process and return its directory.
  The FIRST call in a process decides (later calls only return the
  directory), so the test suite's threshold survives the engines it builds;
  call before the first compile. `min_compile_secs` is the smallest compile
  worth persisting — 0 keeps even the small eager-op executables a cold
  server start replays by the dozen."""
  global _enabled_dir
  if _enabled_dir is None:
    import jax
    if not os.environ.get(ENV):
      REPO_CACHE.mkdir(parents=True, exist_ok=True)
      jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", float(min_compile_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled_dir = cache_dir()
  return _enabled_dir
