"""Elastic replica fleet: the controller half that OWNS the replica set.

Eight PRs of front-door machinery observe and steer a STATIC set of
replicas: a SIGKILLed replica is gone forever, a sustained surge can only
shed 429s, and the router process itself is a single point of failure.
This package closes the loop the metrics already make possible:

- **`FleetLease`** (here): a file-based TTL lease that gates controller
  ACTUATION (spawn/retire/respawn) so N stateless-identical routers can
  all route (rendezvous hashing already guarantees they agree on
  placement) while exactly one acts. No coordination service: the lease
  is a JSON file on the shared host, renewed by atomic replace; a failed
  holder simply stops renewing and the TTL hands actuation over.
- **`FleetSpawner`** (spawner.py): the slot template — every replica the
  fleet may ever run, active or latent, with its argv/env/log — and the
  process management to start and stop them. Pids persist to a sidecar
  file so a NEW lease holder can retire processes a dead holder spawned.
- **`FleetController`** (controller.py): one tick per router poll. Dead
  detection (the unreachable/scrape-failure streak), crash respawn down
  the warm cold-start path (the persistent XLA compile cache of
  utils/compile_cache + PRESERVE-style prefix pre-announce before the
  replica enters rotation), scale-up on sustained admission-queue
  pressure, and scale-down of controller-added spares through the
  existing drain lifecycle so no in-flight request dies.

Following the replica-sharding analysis of arXiv 2004.13336, replicas
share nothing at runtime; the controller only ever touches them through
their public HTTP surface plus POSIX process management.
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Any, Dict, List, Optional


def load_template(path: str) -> List[Dict[str, Any]]:
  """Parse a fleet template file: `{"slots": [{name, url, active, argv,
  env, log}, ...]}`. The slot list is the fleet's whole possible world —
  `active` slots are expected to be running already (spawned by the
  operator or harness); latent ones are what scale-up has to offer.
  Validation is strict: a malformed template must fail at boot, not at
  the first 3 a.m. respawn."""
  with open(path) as f:
    doc = json.load(f)
  slots = doc.get("slots")
  if not isinstance(slots, list) or not slots:
    raise ValueError(f"fleet template {path}: 'slots' must be a non-empty list")
  seen = set()
  for s in slots:
    if not isinstance(s, dict) or not s.get("name") or not s.get("url"):
      raise ValueError(f"fleet template {path}: every slot needs name + url")
    if s["name"] in seen:
      raise ValueError(f"fleet template {path}: duplicate slot {s['name']!r}")
    seen.add(s["name"])
    if not isinstance(s.get("argv"), list) or not s["argv"]:
      raise ValueError(f"fleet template {path}: slot {s['name']!r} needs argv")
  return slots


class FleetLease:
  """TTL'd actuation lease over a shared file. `try_acquire()` is the only
  verb: it acquires when the lease is free or expired, renews when we
  already hold it, and reports False while another holder's lease is
  live. Writes go through temp-file + os.replace (atomic on POSIX) and
  are confirmed by read-back, so of two routers racing an expired lease
  at most one can see its own id in the file. The read-back window still
  admits one overlapping tick under a perfectly symmetric race — the
  actuations behind it are idempotent (a double-spawned slot loses the
  port bind and exits), and the very next renewal resolves ownership.

  `path=None` is solo mode: a single router with no HA peers always holds
  the lease and pays zero file I/O."""

  def __init__(self, path: Optional[str], holder: str, ttl_s: float):
    self.path = path
    self.holder = holder
    self.ttl_s = max(0.5, float(ttl_s))
    self.held = path is None
    self.acquired_total = 0
    self.lost_total = 0

  def _read(self) -> Optional[dict]:
    try:
      with open(self.path) as f:
        doc = json.loads(f.read())
      return doc if isinstance(doc, dict) else None
    except (OSError, json.JSONDecodeError):
      return None

  def _write(self, doc: dict) -> bool:
    try:
      d = os.path.dirname(self.path) or "."
      os.makedirs(d, exist_ok=True)
      fd, tmp = tempfile.mkstemp(dir=d, prefix=".lease.")
      with os.fdopen(fd, "w") as f:
        f.write(json.dumps(doc))
      os.replace(tmp, self.path)
      return True
    except OSError:
      return False

  def peek(self) -> Optional[dict]:
    """The current lease row (holder, expires) without touching it."""
    return None if self.path is None else self._read()

  def try_acquire(self, now: Optional[float] = None) -> bool:
    """One tick of the lease protocol. Returns whether we hold actuation
    AFTER this call; the caller diffs against its previous view to emit
    lease.acquired / lease.lost transitions."""
    if self.path is None:
      return True
    now = time.time() if now is None else now
    was = self.held
    cur = self._read()
    free = (cur is None or cur.get("holder") == self.holder
            or float(cur.get("expires") or 0.0) <= now)
    if free and self._write({"holder": self.holder,
                             "expires": now + self.ttl_s, "at": now}):
      back = self._read()
      self.held = bool(back and back.get("holder") == self.holder)
    else:
      self.held = False
    if self.held and not was:
      self.acquired_total += 1
    elif was and not self.held:
      self.lost_total += 1
    return self.held

  def release(self) -> None:
    """Drop the lease on clean shutdown so a peer takes over NOW instead
    of after a full TTL. Best-effort — a crash skips this by definition."""
    if self.path is None or not self.held:
      return
    cur = self._read()
    if cur and cur.get("holder") == self.holder:
      self._write({"holder": "", "expires": 0.0, "at": time.time()})
    self.held = False

  def status(self) -> dict:
    return {
      "mode": "solo" if self.path is None else "file",
      "path": self.path, "holder_id": self.holder, "held": self.held,
      "ttl_s": self.ttl_s, "lease": self.peek(),
      "acquired_total": self.acquired_total, "lost_total": self.lost_total,
    }
