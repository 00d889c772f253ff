"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax imports.

Multi-chip sharding logic (tp/pp/dp/sp) is validated on a virtual CPU mesh
exactly as the driver's dryrun does; what only the chip's compiler can say is
asked of it in tests/test_chip_compile.py (a described v5e, no chip attached),
and real-TPU runs come from chip_smoke.py.
"""
import asyncio
import inspect
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
  os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("XOT_SKIP_JAX_PROBE", "1")
# The suite is a CPU suite: it must never claim a chip another process holds.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

# Persistent compilation cache (XLA compiles dominate the suite): repeat runs,
# and every module after the per-module jax.clear_caches() below, load
# executables from disk instead of recompiling. clear_caches bounds
# IN-PROCESS state (the XLA:CPU segfault); the disk cache makes the
# recompiles it forces cheap. Only compiles worth 0.2 s are kept.
from xotorch_tpu.utils import compile_cache

compile_cache.enable(min_compile_secs=0.2)

import pytest


def pytest_configure(config):
  config.addinivalue_line("markers", "asyncio: run the test inside a fresh asyncio event loop")
  config.addinivalue_line(
    "markers", "faults: fault-injection suite (runs as a dedicated CI step; "
               "knobs are monkeypatch-scoped so the injector never leaks into the plain run)")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
  """Bound in-process XLA state: after ~100 accumulated CPU executables the
  NEXT pjit-over-a-mesh compile segfaults inside XLA:CPU
  (backend_compile_and_load, reproducible at the first test_multichip test
  in a full-suite run; every affected file passes in isolation). Dropping
  compiled executables between modules keeps the process under the
  threshold at the cost of a few recompiles per file."""
  yield
  jax.clear_caches()


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
  """Run coroutine tests with asyncio.run (no pytest-asyncio in this image)."""
  fn = pyfuncitem.obj
  if inspect.iscoroutinefunction(fn):
    kwargs = {name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames}
    asyncio.run(fn(**kwargs))
    return True
  return None
