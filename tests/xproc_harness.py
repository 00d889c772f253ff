"""Shared harness for spawning real `xot` node processes in tests and
measurement scripts (tests/test_cross_process.py, tests/test_checkpoint_drill.py,
scripts/xproc_ring_bench.py). ONE copy of the child-environment contract —
the spawn env block drifted between copies once already (ADVISOR r5)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def node_env(**overrides) -> dict:
  """The canonical environment for a CPU-pinned node child process.

  - JAX_PLATFORMS=cpu with ONE host device: a test child never claims an
    accelerator its parent (or a sibling) may hold — one process per chip.
  - The compile cache needs nothing here: a child inherits
    JAX_COMPILATION_CACHE_DIR when it is set and otherwise resolves the same
    fixed in-checkout path the suite uses (xotorch_tpu/utils/compile_cache),
    so first forwards load instead of recompiling.
  - PYTHONFAULTHANDLER + PYTHONUNBUFFERED make hangs diagnosable from the
    log (SIGABRT dumps thread stacks; prints land as they happen).
  """
  env = {
    **os.environ,
    "PYTHONPATH": str(REPO),
    "JAX_PLATFORMS": "cpu",
    "XOT_SKIP_JAX_PROBE": "1",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    "PYTHONFAULTHANDLER": "1",
    "PYTHONUNBUFFERED": "1",
  }
  env.update({k: str(v) for k, v in overrides.items()})
  return env


def spawn_node(node_id: str, api_port: int, listen: int, broadcast: int,
               grpc_port: int, logfile, *, model: str = "synthetic-tiny",
               discovery_timeout: int = 15, response_timeout: int = 120,
               extra_args=(), extra_env=None) -> subprocess.Popen:
  env = node_env(**(extra_env or {}))
  return subprocess.Popen(
    [sys.executable, "-m", "xotorch_tpu.main",
     "--node-id", node_id, "--disable-tui",
     "--inference-engine", "jax", "--default-model", model,
     "--chatgpt-api-port", str(api_port),
     "--listen-port", str(listen), "--broadcast-port", str(broadcast),
     "--node-port", str(grpc_port),
     "--discovery-timeout", str(discovery_timeout),
     "--chatgpt-api-response-timeout", str(response_timeout),
     *extra_args],
    env=env, stdout=logfile, stderr=subprocess.STDOUT, cwd=str(REPO),
  )


def http_get(port: int, path: str, timeout: float = 5.0):
  with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
    return json.loads(r.read())


def _log_tail(log_path, n_chars: int = 3000) -> str:
  if not log_path:
    return ""
  try:
    return "\n--- log tail ---\n" + Path(log_path).read_text()[-n_chars:]
  except OSError:
    return f"\n(log {log_path} unreadable)"


def wait_for(predicate, deadline_s: float, what: str, log_path=None,
             proc: subprocess.Popen | None = None) -> None:
  """Poll `predicate` until true; on timeout (or child death, when `proc`
  is given) raise with the child's log tail so failures are diagnosable."""
  t0 = time.monotonic()
  while time.monotonic() - t0 < deadline_s:
    if proc is not None and proc.poll() is not None:
      raise AssertionError(
        f"{what}: child exited rc={proc.returncode}{_log_tail(log_path)}")
    try:
      if predicate():
        return
    except (urllib.error.URLError, OSError, json.JSONDecodeError, KeyError):
      pass
    time.sleep(1.0)
  raise TimeoutError(f"{what} (after {deadline_s:.0f}s){_log_tail(log_path)}")


def teardown_nodes(procs, logs) -> None:
  """Uniform child teardown: terminate all, wait-or-kill all, close logs.
  Shared by every multi-process test so a teardown fix lands once."""
  for p in procs.values():
    if p.poll() is None:
      p.terminate()
  for p in procs.values():
    try:
      p.wait(timeout=10)
    except subprocess.TimeoutExpired:
      p.kill()
  for f in logs.values():
    try:
      f.close()
    except Exception:
      pass
