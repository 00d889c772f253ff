"""Soak orchestrator: a real N-process ring + load + faults + scrapes.

Spawns `xotorch_tpu.main` node processes over localhost gRPC/UDP exactly
like the cross-process test suite (tests/xproc_harness owns the child
environment contract), drives tools/soak/loadgen against node 0's OpenAI
API, executes a wall-clock fault schedule (SIGKILL a node process, or
install drop/delay injector rules in a child via its /v1/debug/faults
endpoint), and continuously scrapes every node's /metrics and
/v1/debug/flight plus node 0's /v1/cluster/metrics and /v1/perf. The
verdict math lives in tools/soak/__init__ — this module only collects.
"""
from __future__ import annotations

import asyncio
import json
import os
import re
import signal
import sys
import time
import urllib.request
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

REPO = Path(__file__).resolve().parent.parent.parent
if str(REPO) not in sys.path:
  sys.path.insert(0, str(REPO))

from tools import soak as verdicts
from tools.soak.loadgen import LoadPlan, run_load
from xotorch_tpu.utils import knobs

_PROM_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{[^}]*\})?\s+([0-9eE+.\-]+|NaN|Inf)\s*$")


def parse_prom(text: str) -> Dict[str, float]:
  """Flat {metric_name: value} view of a /metrics exposition (labels
  dropped, same-name series summed — one node per process here)."""
  out: Dict[str, float] = {}
  for line in text.splitlines():
    if line.startswith("#"):
      continue
    m = _PROM_LINE.match(line.strip())
    if not m:
      continue
    try:
      v = float(m.group(2))
    except ValueError:
      continue
    out[m.group(1)] = out.get(m.group(1), 0.0) + v
  return out


# Alert knobs for soak children: CI-timescale windows so a smoke's single
# mid-run kill provably drives pending -> firing -> resolved INSIDE the run
# (the production defaults' 2/10-minute windows and 14.4x/6x thresholds are
# sized for real traffic and would outlive the whole smoke). The error
# budget is loose enough that only an actual failure burst burns it, and
# the latency targets stay at their (CPU-safe) defaults.
SOAK_ALERT_ENV = {
  "XOT_ALERT_EVAL_S": "1",
  "XOT_ALERT_FAST_S": "15",
  "XOT_ALERT_SLOW_S": "45",
  "XOT_ALERT_BURN_FAST": "2",
  "XOT_ALERT_BURN_SLOW": "1",
  "XOT_ALERT_PENDING_S": "1",
  "XOT_ALERT_RESOLVE_S": "5",
  "XOT_SLO_ERROR_RATE": "0.05",
  # With burn thresholds this low, keep the latency budget WIDE (10% of
  # requests may miss the CPU-safe latency targets) so a loaded CI runner
  # can't fire a latency rule outside the fault window — the kill detector
  # here is the error-rate rule.
  "XOT_SLO_TARGET": "0.9",
  # CI-timescale history: 2 s samples so a one-minute smoke still records
  # a meaningful downsampled series for the report's history section. The
  # node-side drift sentinel is effectively OFF (pending hold longer than
  # any smoke): its peer-median arm needs only XOT_DRIFT_MIN_SAMPLES in
  # the current window — no chronic baseline — so a loaded CI runner's
  # hop-RTT jitter between ring nodes could otherwise fire perf_drift
  # outside any fault window, a zero-tolerance red. Chronic detection is
  # proven by its own unit/e2e tests, not smuggled into the smoke.
  "XOT_HISTORY_SAMPLE_S": "2",
  "XOT_DRIFT_PENDING_S": "600",
}


@dataclass
class FaultPhase:
  kind: str                      # "kill" | "rules" | "kill_router" (fleet holder)
  node: int                      # ring index (0 = API node; unused for kill_router)
  at_s: float                    # seconds from load start
  grace_s: float = 45.0          # how long after the fault aborts are excused
  until_s: Optional[float] = None  # rules: uninstall time (default at_s+grace)
  rules: Optional[list] = None   # rules: /v1/debug/faults payload




# Child env for ROUTER-mode replicas: bounded admission on (the gate the
# overload phase exercises) and CPU-safe latency SLO targets tight enough
# that the injected gray-failure delay (>= 2x the target) provably fires a
# burn-rate rule while healthy CI traffic stays far under them.
ROUTER_REPLICA_ENV = {
  "XOT_MAX_INFLIGHT": "1",
  "XOT_ADMIT_QUEUE_DEPTH": "2",
  "XOT_SLO_TTFT_S": "6",
  "XOT_SLO_E2E_S": "6",
  # Short trailing window for the history compact: the injected gray
  # delay pollutes the slow replica's trailing means, and a 120 s default
  # window would keep the router's differential-drift comparison naming it
  # long after the fault cleared — blocking the readmission the smoke
  # asserts. 30 s lets the gauges forget the fault on the smoke's clock.
  "XOT_DRIFT_WINDOW_S": "30",
  # Node-side drift sentinel effectively off for the smoke: the gray
  # phase is an ACUTE fault the burn rules own (and provably fire on);
  # letting the chronic sentinel also fire during it adds nothing but a
  # 60 s resolve hysteresis that outlives the run. The router-side
  # differential naming (the actuator) still runs and is what the
  # report's drift section records.
  "XOT_DRIFT_PENDING_S": "600",
}

# Extra child env for FABRIC-mode replicas (layered on the router env):
# a low prefix floor so every loadgen prompt bucket — the 8-word head
# included — clears the prefill-export / host-import minimum. The smoke
# must chain and import on its REAL traffic mix, not only the long-prompt
# tail; the host tier itself rides its default byte budget.
FABRIC_REPLICA_ENV = {
  "XOT_PREFIX_CACHE_MIN": "8",
}

# Router process env: CI-timescale cadences (1 s polls, 5 s minimum
# out-time, 2 canaries) so drain -> probe -> readmit completes inside a
# short smoke window.
ROUTER_ENV = {
  "XOT_ROUTER_POLL_S": "1",
  "XOT_ROUTER_MIN_OUT_S": "5",
  "XOT_ROUTER_PROBES": "2",
  "XOT_ROUTER_SPILL_DEPTH": "1",
  "XOT_ROUTER_PROBE_TOKENS": "2",
}

# Extra router env for FLEET mode (layered on ROUTER_ENV): CI-timescale
# elastic-controller cadences — a dead replica is declared after 3 s of
# unclean polls, queue pressure must hold 3 ticks before a scale-up, the
# actuation lease hands over 5 s after its holder dies, and spares are
# never idle-retired inside a smoke (the retire path has its own unit
# coverage; retiring mid-smoke would just shrink the fleet the hedge
# phase needs). Hedging is fully open (pct=100) with a 1.5 s floor and a
# 1x p99 factor so the injected 4 s ProcessPrompt stall provably out-waits
# the hedge delay while healthy sub-second requests never reach it.
FLEET_ROUTER_ENV = {
  "XOT_FLEET_DEAD_POLLS": "3",
  "XOT_FLEET_UP_QUEUE": "1",
  "XOT_FLEET_UP_POLLS": "3",
  "XOT_FLEET_IDLE_POLLS": "600",
  "XOT_FLEET_COOLDOWN_S": "5",
  "XOT_FLEET_LEASE_TTL_S": "5",
  "XOT_FLEET_BOOT_TIMEOUT_S": "150",
  "XOT_ROUTER_HEDGE_PCT": "100",
  "XOT_ROUTER_HEDGE_FACTOR": "1",
  "XOT_ROUTER_HEDGE_MIN_S": "1.5",
}


@dataclass
class SoakConfig:
  # Knob-backed fields read the XOT_SOAK_* registry at construction so a
  # programmatic SoakConfig() and the CLI agree on (and honor) the same
  # defaults — utils/knobs.py is the single source of truth.
  procs: int = field(default_factory=lambda: knobs.get_int("XOT_SOAK_PROCS"))
  seconds: float = field(default_factory=lambda: knobs.get_float("XOT_SOAK_SECONDS"))
  rate_rps: float = field(default_factory=lambda: knobs.get_float("XOT_SOAK_RPS"))
  arrival: str = "poisson"
  stream_fraction: float = field(
    default_factory=lambda: knobs.get_float("XOT_SOAK_STREAM_FRACTION"))
  session_reuse: float = field(
    default_factory=lambda: knobs.get_float("XOT_SOAK_SESSION_REUSE"))
  max_tokens: int = 16
  model: str = "synthetic-tiny"
  seed: int = field(default_factory=lambda: knobs.get_int("XOT_SOAK_SEED"))
  recon_tol_s: float = field(
    default_factory=lambda: knobs.get_float("XOT_SOAK_RECON_TOL_S"))
  faults: List[FaultPhase] = field(default_factory=list)
  out: Optional[str] = None
  tag: str = "run"
  api_base: int = 53510
  udp_port: int = 53530
  grpc_base: int = 53550
  log_dir: Optional[str] = None
  scrape_interval_s: float = 2.0
  drain_timeout_s: float = 120.0
  restarts: int = 1              # XOT_REQUEST_RESTARTS for the children
  alert_env: Dict[str, str] = field(default_factory=lambda: dict(SOAK_ALERT_ENV))
  # --- router mode (the replicated-rings front door) ---
  # router=True spawns `replicas` INDEPENDENT single-node rings (disjoint
  # discovery ports) plus a `python -m xotorch_tpu.router` process, and the
  # load targets the router. `overload` layers an above-capacity arrival
  # window on the base load ({"at_s", "seconds", "rate_rps"}); `gray`
  # installs a ProcessPrompt delay on one replica for a timed phase
  # ({"node", "at_s", "hold_s", "delay_s"}) — the delayed-but-health-green
  # failure the router must drain and later readmit.
  router: bool = False
  replicas: int = 2
  # fabric=True (implies router): disaggregated prefill/decode roles —
  # replica 0 boots XOT_FABRIC_ROLE=prefill (out of rotation, serves
  # kv.handles), the rest decode, peers cross-wired; the report gains a
  # `fabric` section (cross-replica import deltas + router chain counters)
  # with its own green bar (>= 1 real import, zero dropped transfers).
  fabric: bool = False
  overload: Optional[dict] = None
  gray: Optional[dict] = None
  router_port: int = 53590
  replica_env: Dict[str, str] = field(default_factory=lambda: dict(ROUTER_REPLICA_ENV))
  router_env: Dict[str, str] = field(default_factory=lambda: dict(ROUTER_ENV))
  # fleet=True (implies router): the elastic-fleet smoke. The replicas
  # spawn from a generated fleet TEMPLATE (plus `fleet_latent` latent
  # spare slots) under TWO router processes sharing one actuation lease —
  # routerA boots first and provably holds the lease, routerB carries the
  # client load. `fleet_kill_router_at_s` SIGKILLs the holder mid-load so
  # the survivor must take over actuation; the report gains a `fleet`
  # section (respawns / scale-ups / lease holders / hedge outcomes) with
  # its own green bar, including ZERO client errors total.
  fleet: bool = False
  fleet_latent: int = 1
  fleet_kill_router_at_s: Optional[float] = None
  fleet_env: Dict[str, str] = field(default_factory=lambda: dict(FLEET_ROUTER_ENV))


class SoakRing:
  """Child processes + the last-good scrape of each (a killed node's final
  truth is its last successful scrape)."""

  def __init__(self, cfg: SoakConfig):
    self.cfg = cfg
    self.procs: Dict[str, object] = {}
    self.logs: Dict[str, object] = {}
    self.ports: Dict[str, int] = {}
    # Router mode: N independent single-node rings, named rep<i>; the node
    # id doubles as the replica id everywhere (metrics, cluster views).
    self.names: List[str] = ([f"rep{i}" for i in range(cfg.replicas)] if cfg.router
                             else [f"soak-{i}" for i in range(cfg.procs)])
    # Fleet mode: latent template slots the controller may scale into.
    # They are not harness children — everything that must also cover
    # controller-spawned processes (scrapes, drain, leak check, teardown)
    # iterates all_names and resolves liveness via the pid sidecar.
    self.latent_names: List[str] = (
      [f"rep{cfg.replicas + i}" for i in range(cfg.fleet_latent)]
      if cfg.fleet else [])
    self.all_names: List[str] = self.names + self.latent_names
    self.router_proc = None
    self.router_log = None
    self.last_router: Optional[dict] = None
    # Fleet mode: the second (lease-holding) router process, the last-good
    # /v1/router body PER router id (a dead holder's final counters must
    # survive its death), and every lease holder_id ever observed.
    self.fleet_router_proc = None
    self.fleet_router_log = None
    self.fleet_template: Optional[Path] = None
    self.fleet_status: Dict[str, dict] = {}
    self.fleet_holders: set = set()
    # Out-of-rotation routing tracker, per EPISODE: while the router
    # reports a replica draining/probing, its routed_total is baselined at
    # the episode's first scrape and any growth accumulates into `accum`
    # when the episode closes (replica healthy again). Episode-scoped so
    # requests legitimately routed BETWEEN two drains (replica healthy)
    # never count as routed-while-out. accum + the live episode's delta
    # > 0 means traffic landed on a drained replica — the failover red.
    self.router_track: Dict[str, Dict[str, Optional[int]]] = {}
    self.last_metrics: Dict[str, Dict[str, float]] = {}
    self.last_flight: Dict[str, dict] = {}
    self.last_cluster: Optional[dict] = None
    self.last_perf: Optional[dict] = None
    self.last_alerts: Optional[dict] = None
    self.last_anatomy: Optional[dict] = None
    # Latest /v1/history body per head node: the chronic-memory record the
    # report's history section summarizes and CI uploads as an artifact.
    self.last_history: Dict[str, dict] = {}
    # Where children spool their flight ring on SIGTERM (teardown): a
    # terminated node's evidence survives the process instead of relying
    # only on its last-good scrape. Set by spawn().
    self.dump_dir: Optional[Path] = None
    # Firing rows accumulated across every /v1/alerts scrape, keyed by
    # alert identity: peer eviction PRUNES a dead node's compact from
    # later scrapes, so the settle scrape alone could lose a firing that
    # happened on it — the verdict classifies this superset instead.
    self.alert_rows: Dict[tuple, dict] = {}
    self.killed: set = set()

  def spawn(self, log_dir: Path) -> None:
    import subprocess
    import sys as _sys
    from tests.xproc_harness import node_env, spawn_node
    self.dump_dir = log_dir / "flight_dumps"
    self.dump_dir.mkdir(parents=True, exist_ok=True)
    for i, name in enumerate(self.names):
      self.ports[name] = self.cfg.api_base + i
      self.logs[name] = open(log_dir / f"{name}.log", "w")
      # Router mode gives every replica a DISJOINT discovery port pair so
      # the "replicas" stay independent rings instead of gossiping into one.
      udp = self.cfg.udp_port + (2 * i if self.cfg.router else 0)
      extra = {"XOT_REQUEST_RESTARTS": str(self.cfg.restarts),
               "XOT_FLIGHT_DUMP_DIR": str(self.dump_dir),
               **self.cfg.alert_env}
      if self.cfg.router:
        extra.update(self.cfg.replica_env)
      if self.cfg.fabric:
        # Disaggregated roles: replica 0 prefills and offers, the rest
        # decode. Peers are cross-wired so an entry fetch resolves by URL
        # even when the offer path is not what found it.
        peers = ",".join(f"http://127.0.0.1:{self.cfg.api_base + j}"
                         for j in range(len(self.names)) if j != i)
        extra.update({"XOT_FABRIC_ROLE": "prefill" if i == 0 else "decode",
                      "XOT_FABRIC_PEERS": peers,
                      **FABRIC_REPLICA_ENV})
      self.procs[name] = spawn_node(
        name, self.cfg.api_base + i, udp, udp,
        self.cfg.grpc_base + i, self.logs[name], model=self.cfg.model,
        response_timeout=180, extra_env=extra,
      )
    if self.cfg.fleet:
      for j, name in enumerate(self.latent_names):
        self.ports[name] = self.cfg.api_base + len(self.names) + j
      self._write_fleet_template(log_dir, extra)
      self._spawn_fleet_routers(log_dir)
    elif self.cfg.router:
      self.router_log = open(log_dir / "router.log", "w")
      replica_flags = []
      for name in self.names:
        replica_flags += ["--replica", f"http://127.0.0.1:{self.ports[name]}"]
      self.router_proc = subprocess.Popen(
        [_sys.executable, "-m", "xotorch_tpu.router",
         "--port", str(self.cfg.router_port), *replica_flags],
        env=node_env(**self.cfg.router_env), stdout=self.router_log,
        stderr=subprocess.STDOUT)

  def _node_argv(self, name: str, i: int) -> List[str]:
    """The exact argv spawn_node would use for slot i — a controller
    respawn must reproduce the harness spawn bit-for-bit (same ports, same
    discovery isolation) or the 'respawned' replica is a different ring."""
    udp = self.cfg.udp_port + 2 * i
    return [sys.executable, "-m", "xotorch_tpu.main",
            "--node-id", name, "--disable-tui",
            "--inference-engine", "jax",
            "--default-model", self.cfg.model,
            "--chatgpt-api-port", str(self.cfg.api_base + i),
            "--listen-port", str(udp), "--broadcast-port", str(udp),
            "--node-port", str(self.cfg.grpc_base + i),
            "--discovery-timeout", "15",
            "--chatgpt-api-response-timeout", "180"]

  def _write_fleet_template(self, log_dir: Path, node_extra: Dict[str, str]) -> None:
    """The slot universe both routers load: harness replicas as active
    slots, spares as latent ones. Slot env is the FULL node environment
    (not a delta) so a spawn from inside a router process cannot inherit
    router-only knobs. The pid sidecar is pre-seeded with the harness
    children's pids — that is how the controller SIGKILLs a half-dead
    replica before respawning and how teardown finds controller spawns."""
    from tests.xproc_harness import node_env
    active = set(self.names)
    slots = []
    for i, name in enumerate(self.all_names):
      slots.append({
        "name": name,
        "url": f"http://127.0.0.1:{self.ports[name]}",
        "active": name in active,
        "argv": self._node_argv(name, i),
        "env": node_env(**node_extra),
        "log": str(log_dir / f"{name}.log"),
      })
    self.fleet_template = log_dir / "fleet_template.json"
    self.fleet_template.write_text(json.dumps({"slots": slots}, indent=1) + "\n")
    Path(str(self.fleet_template) + ".pids").write_text(
      json.dumps({name: self.procs[name].pid for name in self.names}) + "\n")

  def _spawn_fleet_routers(self, log_dir: Path) -> None:
    """routerA first, and it must HOLD the lease before routerB even
    boots: the holder-kill phase then provably hands actuation over
    instead of flaking on whichever router won the boot race."""
    import subprocess
    from tests.xproc_harness import node_env, wait_for
    renv = node_env(**{**self.cfg.router_env, **self.cfg.fleet_env,
                       "XOT_FLEET_LEASE_PATH": str(log_dir / "fleet.lease")})

    def router(rid: str, port: int, log):
      return subprocess.Popen(
        [sys.executable, "-m", "xotorch_tpu.router",
         "--port", str(port), "--fleet-template", str(self.fleet_template),
         "--router-id", rid],
        env=renv, stdout=log, stderr=subprocess.STDOUT)

    self.fleet_router_log = open(log_dir / "routerA.log", "w")
    self.fleet_router_proc = router(
      "routerA", self.cfg.router_port + 1, self.fleet_router_log)

    def a_holds() -> bool:
      st = self.get_json_port(self.cfg.router_port + 1, "/v1/router")
      lease = ((st or {}).get("fleet") or {}).get("lease") or {}
      return bool(lease.get("held"))

    wait_for(a_holds, 60, "routerA holds the fleet lease",
             proc=self.fleet_router_proc,
             log_path=getattr(self.fleet_router_log, "name", None))
    self.router_log = open(log_dir / "routerB.log", "w")
    self.router_proc = router("routerB", self.cfg.router_port, self.router_log)

  def _fleet_pids(self) -> Dict[str, int]:
    if not self.fleet_template:
      return {}
    try:
      doc = json.loads(Path(str(self.fleet_template) + ".pids").read_text())
    except (OSError, ValueError):
      return {}
    if not isinstance(doc, dict):
      return {}
    out = {}
    for name, pid in doc.items():
      try:
        out[str(name)] = int(pid)
      except (TypeError, ValueError):
        continue
    return out

  def wait_ready(self) -> None:
    from tests.xproc_harness import http_get, wait_for
    for name in self.names:
      port = self.ports[name]
      wait_for(lambda p=port: http_get(p, "/healthcheck").get("status") == "ok",
               180, f"{name} API health", proc=self.procs[name],
               log_path=self._log_path(name))
    # Router mode: each replica is its own 1-node ring; plain mode: every
    # node must see the full ring.
    n = 1 if self.cfg.router else len(self.names)
    for name in self.names:
      port = self.ports[name]
      wait_for(lambda p=port: len(http_get(p, "/v1/topology").get("nodes", {})) == n,
               120, f"{name} sees {n}-node ring", proc=self.procs[name],
               log_path=self._log_path(name))
    if self.cfg.router:
      # Fabric mode deliberately keeps the prefill replica OUT of rotation,
      # so the router advertises one fewer routable replica — and the chain
      # path needs it discovered AS prefill before any load arrives.
      want = len(self.names) - (1 if self.cfg.fabric else 0)
      wait_for(lambda: http_get(self.cfg.router_port, "/healthcheck")
               .get("routable") == want,
               60, f"router routes {want} of {len(self.names)} replicas",
               proc=self.router_proc,
               log_path=getattr(self.router_log, "name", None))
      if self.cfg.fabric:
        wait_for(lambda: len(http_get(self.cfg.router_port, "/v1/router")
                             .get("prefill_replicas") or []) >= 1,
                 60, "router discovers the prefill replica",
                 proc=self.router_proc,
                 log_path=getattr(self.router_log, "name", None))
      if self.cfg.fleet:
        # The holder router is warmed too (its recent-body ring feeds the
        # respawn pre-announce), so it must also route everything first.
        wait_for(lambda: http_get(self.cfg.router_port + 1, "/healthcheck")
                 .get("routable") == want,
                 60, f"routerA routes {want} of {len(self.names)} replicas",
                 proc=self.fleet_router_proc,
                 log_path=getattr(self.fleet_router_log, "name", None))

  def _log_path(self, name: str):
    f = self.logs.get(name)
    return getattr(f, "name", None)

  def alive(self, name: str) -> bool:
    proc = self.procs.get(name)
    if proc is not None and proc.poll() is None and name not in self.killed:
      return True
    # Fleet mode: a respawned or scaled-up replica is the ROUTER's child,
    # not ours — the spawner's pid sidecar is the only liveness truth. The
    # poll() above has already reaped our own SIGKILLed child, so a stale
    # sidecar pid answers ESRCH here rather than lingering as a zombie.
    if self.cfg.fleet:
      pid = self._fleet_pids().get(name)
      if pid:
        try:
          os.kill(pid, 0)
          return True
        except OSError:
          return False
    return False

  def get_json(self, name: str, path: str, timeout: float = 5.0) -> Optional[dict]:
    return self.get_json_port(self.ports[name], path, timeout)

  def get_json_port(self, port: int, path: str, timeout: float = 5.0) -> Optional[dict]:
    try:
      with urllib.request.urlopen(
          f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return json.loads(r.read())
    except Exception:
      return None

  def get_text(self, name: str, path: str, timeout: float = 5.0) -> Optional[str]:
    try:
      with urllib.request.urlopen(
          f"http://127.0.0.1:{self.ports[name]}{path}", timeout=timeout) as r:
        return r.read().decode("utf-8", "replace")
    except Exception:
      return None

  def scrape_once(self) -> None:
    for name in self.all_names:
      if not self.alive(name):
        continue
      text = self.get_text(name, "/metrics")
      if text is not None:
        self.last_metrics[name] = parse_prom(text)
      flight = self.get_json(name, "/v1/debug/flight")
      if flight is not None:
        self.last_flight[name] = flight
    # Cluster/alert rollups. A plain ring's node 0 sees every peer via the
    # status bus; router-mode replicas are DISJOINT rings, so each head is
    # scraped and the node rows merged into one cluster/alert view (node
    # ids are unique across replicas by construction).
    heads = [n for n in (self.all_names if self.cfg.router else self.names[:1])
             if self.alive(n)]
    merged_cluster: Dict[str, dict] = {}
    merged_alert_nodes: Dict[str, dict] = {}
    for head in heads:
      cluster = self.get_json(head, "/v1/cluster/metrics")
      if cluster is not None:
        merged_cluster.update(cluster.get("nodes") or {})
      alerts = self.get_json(head, "/v1/alerts")
      if alerts is not None:
        merged_alert_nodes.update(alerts.get("nodes") or {})
      # ?window=0: the stats/trailing head of the record without its rows
      # — the continuous scrape only feeds the report's summary, so
      # shipping every retained row each tick would be discarded I/O. The
      # full body is fetched ONCE at settle (scrape_history_full) for the
      # history_settle.json artifact.
      history = self.get_json(head, "/v1/history?window=0")
      if history is not None:
        self.last_history[head] = history
    if merged_cluster:
      self.last_cluster = {"nodes": merged_cluster, "count": len(merged_cluster)}
    if merged_alert_nodes:
      self.last_alerts = {
        "nodes": merged_alert_nodes,
        "cluster": {"firing": sum(int(a.get("firing") or 0)
                                  for a in merged_alert_nodes.values())},
      }
      for row in verdicts.alert_rows_of(self.last_alerts):
        key = verdicts.alert_row_key(row)
        prev = self.alert_rows.get(key)
        if prev is None or (row.get("resolved_at") is not None
                            and prev.get("resolved_at") is None):
          self.alert_rows[key] = row
    if heads:
      perf = self.get_json(heads[0], "/v1/perf")
      if perf is not None:
        self.last_perf = perf
      # The origin's latency-anatomy rollup: stage-contribution
      # percentiles over its reservoir of skew-corrected breakdowns.
      anatomy = self.get_json(heads[0], "/v1/anatomy")
      if anatomy is not None:
        self.last_anatomy = anatomy
    if self.cfg.router and self.router_proc is not None and self.router_proc.poll() is None:
      status = self.get_json_port(self.cfg.router_port, "/v1/router")
      if status is not None:
        self.last_router = status
        self._note_fleet(status)
        for name, row in (status.get("replicas") or {}).items():
          # Fleet boot/retire phases are out-of-rotation too: routing to a
          # replica the controller is still warming (or tearing down) is
          # the same red as routing to a drained one.
          state = ("retiring" if row.get("retiring")
                   else "warming" if row.get("warming")
                   else str(row.get("state") or ""))
          self.note_router_row(name, state, int(row.get("routed_total") or 0))
    if (self.cfg.fleet and self.fleet_router_proc is not None
        and self.fleet_router_proc.poll() is None):
      status = self.get_json_port(self.cfg.router_port + 1, "/v1/router")
      if status is not None:
        self._note_fleet(status)

  def _note_fleet(self, status: dict) -> None:
    """Last-good /v1/router per router id + the holder set. Keyed by the
    router's own id so the holder's final pre-death counters (its respawn
    actuations) keep contributing after it is SIGKILLed."""
    if not isinstance(status.get("fleet"), dict):
      return
    self.fleet_status[str(status.get("router_id") or "?")] = status
    lease = (status.get("fleet") or {}).get("lease") or {}
    if lease.get("held") and lease.get("holder_id"):
      self.fleet_holders.add(str(lease["holder_id"]))

  def scrape_history_full(self) -> None:
    """One full /v1/history fetch per reachable head (every retained row)
    — the settle-time artifact the CI step uploads; the continuous scrape
    deliberately fetches only the row-less summary."""
    heads = [n for n in (self.all_names if self.cfg.router else self.names[:1])
             if self.alive(n)]
    for head in heads:
      history = self.get_json(head, "/v1/history", timeout=10.0)
      if history is not None:
        self.last_history[head] = history

  def note_router_row(self, name: str, state: str, routed: int) -> None:
    """One router-scrape observation into the out-of-rotation tracker."""
    track = self.router_track.setdefault(
      name, {"accum": 0, "episode_start": None, "episode_last": None})
    if state in ("draining", "probing", "warming", "retiring"):
      if track["episode_start"] is None:
        track["episode_start"] = routed
      track["episode_last"] = routed
    elif track["episode_start"] is not None:
      # Episode closed (readmitted): bank its delta, reset the baseline.
      track["accum"] += max(
        0, int(track["episode_last"] or track["episode_start"])
        - int(track["episode_start"]))
      track["episode_start"] = track["episode_last"] = None

  def kill(self, index: int) -> None:
    name = self.names[index]
    proc = self.procs.get(name)
    if proc is not None and proc.poll() is None:
      proc.send_signal(signal.SIGKILL)
    self.killed.add(name)

  def kill_fleet_router(self) -> None:
    """SIGKILL the holder router (routerA — spawn() serialized its lease
    acquisition) so the surviving load router must take over actuation."""
    if self.fleet_router_proc is not None and self.fleet_router_proc.poll() is None:
      self.fleet_router_proc.send_signal(signal.SIGKILL)

  def teardown(self) -> None:
    from tests.xproc_harness import teardown_nodes
    procs = dict(self.procs)
    logs = dict(self.logs)
    if self.router_proc is not None:
      procs["router"] = self.router_proc
      if self.router_log is not None:
        logs["router"] = self.router_log
    if self.fleet_router_proc is not None:
      procs["routerA"] = self.fleet_router_proc
      if self.fleet_router_log is not None:
        logs["routerA"] = self.fleet_router_log
    teardown_nodes(procs, logs)
    self._teardown_fleet_pids()

  def _teardown_fleet_pids(self) -> None:
    """Controller-spawned replicas (respawns, scale-ups) are children of a
    ROUTER process, not ours; the routers are already down, so the pid
    sidecar the spawner maintains is the handover. SIGTERM first so they
    spool their flight rings (XOT_FLIGHT_DUMP_DIR is in the slot env),
    SIGKILL whatever ignores it. Idempotent: dead pids answer ESRCH."""
    ours = {proc.pid for proc in self.procs.values()}
    pids = [pid for pid in self._fleet_pids().values() if pid not in ours]
    for pid in pids:
      try:
        os.kill(pid, signal.SIGTERM)
      except OSError:
        pass
    deadline = time.monotonic() + 8.0
    while time.monotonic() < deadline:
      if not any(_pid_alive(pid) for pid in pids):
        return
      time.sleep(0.2)
    for pid in pids:
      try:
        os.kill(pid, signal.SIGKILL)
      except OSError:
        pass

  def collect_flight_dumps(self) -> Dict[str, dict]:
    """Parse the post-mortem spool: {node_id: dump} from every
    `flight_*.json` a SIGTERM'd child wrote to the dump dir. Children dump
    at teardown (and on any external SIGTERM); a SIGKILLed node can write
    nothing — its last-good scrape stays its only record."""
    return collect_flight_dumps(self.dump_dir)


def _pid_alive(pid: int) -> bool:
  try:
    os.kill(pid, 0)
    return True
  except OSError:
    return False


def collect_flight_dumps(dump_dir: Optional[Path]) -> Dict[str, dict]:
  out: Dict[str, dict] = {}
  if not dump_dir:
    return out
  for path in sorted(Path(dump_dir).glob("flight_*.json")):
    try:
      dump = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
      continue
    node_id = dump.get("node_id")
    if node_id:
      out[str(node_id)] = dump
  return out


def _sum_counter(metrics_by_node: Dict[str, Dict[str, float]], name: str) -> float:
  return sum(float(m.get(name, 0.0)) for m in metrics_by_node.values())


def _abort_events(flight_by_node: Dict[str, dict]) -> List[dict]:
  """Watchdog/deadline abort evidence from each node's frozen snapshots:
  one event per snapshot whose timeline contains a watchdog.fired or
  deadline.expired transition, stamped with the snapshot freeze time."""
  events = []
  for node_id, flight in flight_by_node.items():
    for snap in flight.get("snapshots") or []:
      names = {e.get("event") for e in snap.get("events") or []}
      if "watchdog.fired" in names or "deadline.expired" in names:
        events.append({"node_id": node_id, "ts": snap.get("frozen_at"),
                       "request_id": snap.get("request_id"),
                       "reason": snap.get("reason")})
  return events


async def _chat_once(port: int, model: str, timeout_s: float = 300.0) -> None:
  """One sequential warmup completion (pays the cold-jit compiles before
  the measured window opens)."""
  import aiohttp
  body = {"model": model, "messages": [{"role": "user", "content": "soak warmup"}],
          "max_tokens": 8, "temperature": 0}
  async with aiohttp.ClientSession(
      timeout=aiohttp.ClientTimeout(total=timeout_s)) as session:
    async with session.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                            json=body) as resp:
      text = await resp.text()
      if resp.status != 200:
        raise RuntimeError(f"warmup failed ({resp.status}): {text[:300]}")


async def _scraper(ring: SoakRing, stop: asyncio.Event) -> None:
  loop = asyncio.get_running_loop()
  while not stop.is_set():
    await loop.run_in_executor(None, ring.scrape_once)
    try:
      await asyncio.wait_for(stop.wait(), timeout=ring.cfg.scrape_interval_s)
    except asyncio.TimeoutError:
      pass


async def _fault_driver(ring: SoakRing, t_load_start: float,
                        windows: List[dict]) -> None:
  """Execute the wall-clock fault schedule; records each phase's excuse
  window (unix seconds) for the verdict's abort classification."""
  phases = sorted(ring.cfg.faults, key=lambda p: p.at_s)
  loop = asyncio.get_running_loop()
  for phase in phases:
    delay = t_load_start + phase.at_s - time.monotonic()
    if delay > 0:
      await asyncio.sleep(delay)
    now = time.time()
    try:
      if phase.kind == "kill":
        ring.kill(phase.node)
        windows.append({"kind": "kill", "node": ring.names[phase.node],
                        "t0": now - 1.0, "t1": now + phase.grace_s})
      elif phase.kind == "kill_router":
        # HA handover: no client impact is EXPECTED (the load router
        # survives), so the short grace window exists only to make the
        # phase visible in the report's fault timeline.
        ring.kill_fleet_router()
        windows.append({"kind": "kill_router", "node": "routerA",
                        "t0": now - 1.0, "t1": now + phase.grace_s})
      elif phase.kind == "rules":
        name = ring.names[phase.node]
        until = phase.until_s if phase.until_s is not None else phase.at_s + phase.grace_s
        body = json.dumps({"rules": phase.rules or []}).encode()

        def post(payload=body, port=ring.ports[name]):
          req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/debug/faults", data=payload,
            headers={"Content-Type": "application/json"})
          with urllib.request.urlopen(req, timeout=5.0):
            pass

        def delete(port=ring.ports[name]):
          req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/debug/faults", method="DELETE")
          with urllib.request.urlopen(req, timeout=5.0):
            pass

        try:
          await loop.run_in_executor(None, post)
          windows.append({"kind": "rules", "node": name,
                          "t0": now - 1.0, "t1": time.time() + (until - phase.at_s) + phase.grace_s})
          hold = t_load_start + until - time.monotonic()
          if hold > 0:
            await asyncio.sleep(hold)
        finally:
          # Synchronous on purpose: this must also run when the driver is
          # CANCELLED mid-hold (teardown after an early load failure), and
          # a cancelled coroutine cannot await the executor. Localhost with
          # a 5 s timeout; a killed/unreachable node has no injector left
          # to remove.
          try:
            delete()
          except Exception:
            pass
    except asyncio.CancelledError:
      raise
    except Exception as e:
      # One unreachable/late node must not lose the whole soak (the run's
      # collected data and verdict): record the failed phase, keep going.
      print(f"soak: fault phase {phase.kind}@{phase.at_s:g} (node {phase.node}) "
            f"failed: {e!r}", file=sys.stderr)


async def _drain(ring: SoakRing, timeout_s: float) -> bool:
  """Wait until every reachable node reports zero in-flight requests."""
  deadline = time.monotonic() + timeout_s
  loop = asyncio.get_running_loop()
  while time.monotonic() < deadline:
    await loop.run_in_executor(None, ring.scrape_once)
    busy = [n for n in ring.all_names if ring.alive(n)
            and float(ring.last_metrics.get(n, {}).get("xot_active_requests", 0.0)) > 0]
    if not busy:
      return True
    await asyncio.sleep(1.0)
  return False


async def run_soak(cfg: SoakConfig) -> dict:
  """The whole arc: spawn -> warm -> baseline -> load + faults + scrapes ->
  drain -> settle scrapes -> verdict report (returned AND written to
  cfg.out when set)."""
  import tempfile
  log_dir = Path(cfg.log_dir) if cfg.log_dir else Path(tempfile.mkdtemp(prefix="xot_soak_"))
  log_dir.mkdir(parents=True, exist_ok=True)
  if cfg.fabric:
    # Disaggregated roles only make sense behind the front door: the
    # router is what chains prefill -> offer -> decode per request.
    cfg.router = True
  if cfg.fleet:
    # The elastic fleet lives behind routers by construction.
    cfg.router = True
    if cfg.fleet_kill_router_at_s is not None:
      cfg.faults.append(FaultPhase(
        kind="kill_router", node=0,
        at_s=float(cfg.fleet_kill_router_at_s), grace_s=10.0))
  if cfg.gray is not None:
    # The gray-failure drain phase: a timed ProcessPrompt delay on one
    # replica — requests there get slower (visible to ITS burn-rate rules
    # and to clients) while /healthcheck stays green. Rides the existing
    # rules-phase machinery, so its window excuses the resulting alert
    # firings exactly like any injected fault.
    g = cfg.gray
    cfg.faults.append(FaultPhase(
      kind="rules", node=int(g.get("node", cfg.replicas - 1)),
      at_s=float(g["at_s"]), until_s=float(g["at_s"]) + float(g.get("hold_s", 20.0)),
      grace_s=float(g.get("grace_s", 60.0)),
      rules=[{"rpc": "ProcessPrompt", "action": "delay", "nth": 1,
              "times": 1000000, "delay_s": float(g.get("delay_s", 12.0))}]))
  ring = SoakRing(cfg)
  t_wall_start = time.time()
  loop = asyncio.get_running_loop()
  try:
    await loop.run_in_executor(None, ring.spawn, log_dir)
    await loop.run_in_executor(None, ring.wait_ready)
    if cfg.router:
      # Pay every replica's cold jit directly, then prove the router path.
      for name in ring.names:
        await _chat_once(ring.ports[name], cfg.model)
      if cfg.fleet:
        # Warm the holder router too: its recent-body ring is what feeds
        # a respawned replica's warm pre-announce.
        await _chat_once(cfg.router_port + 1, cfg.model)
      api_port = cfg.router_port
    else:
      api_port = ring.ports[ring.names[0]]
    await _chat_once(api_port, cfg.model)
    # Let the warmup's metric summaries ride one topology tick so the
    # baseline cluster scrape includes every node's post-warmup counters.
    await asyncio.sleep(5.0)
    await loop.run_in_executor(None, ring.scrape_once)
    base_cluster = (ring.last_cluster or {}).get("nodes", {})
    base_metrics = {n: dict(m) for n, m in ring.last_metrics.items()}
    # Router baseline at load start: boot-time/warmup drains (cold-jit
    # alerts, a poll racing a replica's bind) resolved before the measured
    # window must not satisfy the gray-failure drain/readmit expectation —
    # and the routed-while-out tracker starts fresh for the same reason.
    base_router = dict(ring.last_router) if ring.last_router else None
    ring.router_track.clear()
    # Fleet baselines at load start, same reasoning: boot-time lease churn
    # and warmup-era actuations (none expected, but races exist) must not
    # satisfy the measured window's respawn/scale-up/holder expectations.
    base_fleet = {rid: st for rid, st in ring.fleet_status.items()}
    ring.fleet_holders.clear()

    plan = LoadPlan(seconds=cfg.seconds, rate_rps=cfg.rate_rps, arrival=cfg.arrival,
                    stream_fraction=cfg.stream_fraction, session_reuse=cfg.session_reuse,
                    max_tokens=cfg.max_tokens, model=cfg.model, seed=cfg.seed,
                    extra_phases=[dict(cfg.overload)] if cfg.overload else [])
    stop_scraper = asyncio.Event()
    scraper = asyncio.ensure_future(_scraper(ring, stop_scraper))
    windows: List[dict] = []
    t_load_start = time.monotonic()
    t_wall_load_start = time.time()
    fault_task = asyncio.ensure_future(_fault_driver(ring, t_load_start, windows))
    try:
      records = await run_load(api_port, plan)
    finally:
      # Cancel rather than await: in the normal arc every phase fires
      # within the load window so this is a no-op, but a load that died
      # early must not block teardown for the rest of a long wall-clock
      # fault schedule. The driver's own cleanup (rules uninstall) is
      # cancel-safe.
      if not fault_task.done():
        fault_task.cancel()
      await asyncio.gather(fault_task, return_exceptions=True)
      drained = await _drain(ring, cfg.drain_timeout_s)
      # Two topology ticks so surviving peers' final summaries reach node 0.
      await asyncio.sleep(5.0)
      stop_scraper.set()
      await scraper
    await loop.run_in_executor(None, ring.scrape_once)
    settle_a = {n: dict(m) for n, m in ring.last_metrics.items() if ring.alive(n)}
    await asyncio.sleep(3.0)
    await loop.run_in_executor(None, ring.scrape_once)
    settle_b = {n: dict(m) for n, m in ring.last_metrics.items() if ring.alive(n)}
    # Settle-time /v1/alerts scrape: the firing->resolved evidence the CI
    # step uploads as an artifact (and the report's alert section reads).
    try:
      (log_dir / "alerts_settle.json").write_text(
        json.dumps(ring.last_alerts or {}, indent=1) + "\n")
    except OSError as e:
      print(f"soak: writing alerts_settle.json failed: {e!r}", file=sys.stderr)
    # The history record next to the alerts scrape: the same CI step
    # uploads both, so a chronic-rot investigation has the full
    # downsampled time-series, not just the report's trailing means.
    try:
      await loop.run_in_executor(None, ring.scrape_history_full)
      (log_dir / "history_settle.json").write_text(
        json.dumps(ring.last_history or {}, indent=1) + "\n")
    except OSError as e:
      print(f"soak: writing history_settle.json failed: {e!r}", file=sys.stderr)

    # Tear the ring down BEFORE assembling the report: children spool
    # their flight rings on SIGTERM (XOT_FLIGHT_DUMP_DIR), and the dumps
    # are post-mortem evidence the report merges with the last-good
    # scrapes. The finally-teardown below is then an idempotent no-op.
    await loop.run_in_executor(None, ring.teardown)
    dumps = ring.collect_flight_dumps()

    report = _build_report(cfg, ring, records, windows, base_cluster, base_metrics,
                           settle_a, settle_b, drained, t_wall_start, dumps=dumps,
                           t_wall_load_start=t_wall_load_start,
                           base_router=base_router, base_fleet=base_fleet)
    verdicts.evaluate(report)
    if cfg.out:
      verdicts.write_report(report, cfg.out)
    return report
  finally:
    await loop.run_in_executor(None, ring.teardown)


def _build_report(cfg: SoakConfig, ring: SoakRing, records, windows,
                  base_cluster, base_metrics, settle_a, settle_b,
                  drained: bool, t_wall_start: float,
                  dumps: Optional[Dict[str, dict]] = None,
                  t_wall_load_start: Optional[float] = None,
                  base_router: Optional[dict] = None,
                  base_fleet: Optional[Dict[str, dict]] = None) -> dict:
  ok_recs = [r for r in records if r.ok]
  rejected_recs = [r for r in records if getattr(r, "rejected", False)]
  # 429s are deliberate admission sheds, not failures: they never reached
  # the ring, so they belong to neither the error count nor the e2e
  # reconciliation sample (the server only times requests it ADMITTED).
  err_recs = [r for r in records if not r.ok and not getattr(r, "rejected", False)]
  # The server's request_seconds family records "any outcome" (finish OR
  # abort), so the client e2e sample it reconciles against must count
  # errored requests too — excluding them would compare a survivors-only
  # distribution against an everyone distribution.
  e2e_all = [r.e2e_s for r in records
             if r.e2e_s is not None and not getattr(r, "rejected", False)]

  def in_window(rec) -> bool:
    t_fail = rec.t_submit + (rec.e2e_s or 0.0)
    return any(w["t0"] <= t_fail <= w["t1"] for w in windows)

  errors_outside = [r for r in err_recs if not in_window(r)]
  elapsed = max(1e-9, time.time() - t_wall_start)
  client = {
    "submitted": len(records),
    "ok": len(ok_recs),
    "rejected": len(rejected_recs),
    "errors": len(err_recs),
    "errors_in_fault_windows": len(err_recs) - len(errors_outside),
    "errors_outside_fault_windows": len(errors_outside),
    "streamed": sum(1 for r in records if r.streamed),
    "session_reuse": sum(1 for r in records if r.session is not None),
    "rps_target": cfg.rate_rps,
    "rps_achieved": round(len(records) / cfg.seconds, 4) if cfg.seconds else None,
    "ttft_s": verdicts.latency_summary([r.ttft_s for r in ok_recs if r.ttft_s is not None]),
    # Raw per-gap samples, not per-request means: the server's
    # token_seconds family is per-token, so the client sample must be too.
    "tpot_s": verdicts.latency_summary(
      [g for r in ok_recs for g in (getattr(r, "tpot_gaps", None) or [])]),
    "tpot_request_mean_s": verdicts.latency_summary(
      [r.tpot_s for r in ok_recs if r.tpot_s is not None]),
    "e2e_s": verdicts.latency_summary(e2e_all),
    "e2e_ok_s": verdicts.latency_summary([r.e2e_s for r in ok_recs if r.e2e_s is not None]),
    "error_samples": [r.error for r in err_recs[:5]],
  }

  nodes_final = (ring.last_cluster or {}).get("nodes", {})
  # Node ids == spawn names; names[0] runs the API. Router runs have one
  # origin PER replica (each head node's first touch ≈ HTTP arrival there).
  origin = set(ring.all_names) if cfg.router else ring.names[0]
  server = {}
  for family, _client_key, mode in verdicts.RECONCILE_FAMILIES:
    # Two-sided families compare like with like: only the ORIGIN node's
    # histogram (its first touch ≈ HTTP arrival) — the ring-merged family
    # is a mixture of per-node views of the same request. One-sided
    # families merge ring-wide (the invariant holds for every view).
    only = origin if mode == "two_sided" else None
    server[family] = verdicts.server_percentiles(
      nodes_final, base_cluster, family, only_node=only)
  for counter, prom in (
      ("watchdog_aborts", "xot_watchdog_aborts_total"),
      ("request_restarts", "xot_request_restarts_total"),
      ("peer_evictions", "xot_peer_evictions_total"),
      ("dedup_drops", "xot_dedup_drops_total"),
      ("hop_retries", "xot_hop_retries_total"),
      ("admission_rejections", "xot_admission_rejections_total"),
      ("requests", "xot_requests_total"),
      ("tokens", "xot_tokens_total"),
  ):
    server[counter] = (_sum_counter(ring.last_metrics, prom)
                       - _sum_counter(base_metrics, prom))
  if ring.last_perf is not None:
    server["perf"] = {k: ring.last_perf.get(k) for k in ("gauges", "dispatch") if k in ring.last_perf}

  # Abort evidence: last-good scrapes MERGED with the post-mortem dumps —
  # a terminated node's frozen snapshots survive teardown even when its
  # final scrape was missed (killed nodes still rely on last-good).
  flight_evidence = {n: dict(f) for n, f in ring.last_flight.items()}
  for node_id, dump in (dumps or {}).items():
    row = flight_evidence.setdefault(node_id, {})
    have = {(s.get("request_id"), s.get("reason"), s.get("frozen_at"))
            for s in row.get("snapshots") or []}
    merged = list(row.get("snapshots") or [])
    for snap in dump.get("snapshots") or []:
      key = (snap.get("request_id"), snap.get("reason"), snap.get("frozen_at"))
      if key not in have:
        merged.append(snap)
    row["snapshots"] = merged
  events = _abort_events(flight_evidence)
  aborts = verdicts.classify_aborts(events, windows)
  aborts["unattributed"] = max(0, int(server["watchdog_aborts"]) - len(events))
  # Classify the accumulated superset, not just the settle scrape: a
  # firing on a since-evicted peer survives here even though its compact
  # no longer rides the final /v1/alerts response. SLO burns and
  # perf_drift firings split into their own sections — different green
  # bars, different benchdiff zero-tolerance keys.
  all_rows = list(ring.alert_rows.values())
  alerts = verdicts.classify_alert_firings(
    [r for r in all_rows if not verdicts.is_drift_row(r)], windows,
    since=t_wall_load_start)
  drift = verdicts.summarize_drift(
    [r for r in all_rows if verdicts.is_drift_row(r)], windows,
    since=t_wall_load_start, router_status=ring.last_router)

  report = {
    "schema": verdicts.SCHEMA,
    "tag": cfg.tag,
    "recorded": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_wall_start)),
    "elapsed_s": round(time.time() - t_wall_start, 1),
    "config": {
      "procs": cfg.procs, "seconds": cfg.seconds, "rate_rps": cfg.rate_rps,
      "arrival": cfg.arrival, "stream_fraction": cfg.stream_fraction,
      "session_reuse": cfg.session_reuse, "max_tokens": cfg.max_tokens,
      "model": cfg.model, "seed": cfg.seed, "recon_tol_s": cfg.recon_tol_s,
      "restarts": cfg.restarts,
      "router": cfg.router, "replicas": cfg.replicas if cfg.router else None,
      "fabric": cfg.fabric, "overload": cfg.overload, "gray": cfg.gray,
      "fleet": cfg.fleet,
      "fleet_latent": cfg.fleet_latent if cfg.fleet else None,
      "fleet_kill_router_at_s": cfg.fleet_kill_router_at_s,
      "faults": [{"kind": p.kind, "node": p.node, "at_s": p.at_s,
                  "grace_s": p.grace_s} for p in cfg.faults],
    },
    "fault_windows": windows,
    "client": client,
    "server": server,
    # Runs with injected DELAY rules restrict TTFT reconciliation to the
    # median: the delay lands in the server's TTFT histogram for every
    # request, but the client TTFT sample covers only streamed ones — a
    # delay hitting non-streamed requests puts the slow observations on
    # exactly one side, making the tails structurally incomparable (the
    # token_seconds median-only precedent, applied per run). Keyed on the
    # rules' ACTIONS: error/drop/kill rules phases keep the full check.
    "reconciliation": verdicts.reconcile(
      client, server, cfg.recon_tol_s,
      quantile_overrides=({"ttft_seconds": (0.5,)} if any(
        p.kind == "rules" and any(str(r.get("action")) == "delay"
                                  for r in (p.rules or []))
        for p in cfg.faults) else None)),
    "aborts": aborts,
    "alerts": alerts,
    "drift": drift,
    "history": verdicts.summarize_history(ring.last_history),
    "anatomy": verdicts.summarize_anatomy(ring.last_anatomy),
    "flight_dumps": {
      node_id: {"reason": d.get("reason"), "events": len(d.get("events") or ()),
                "snapshots": len(d.get("snapshots") or ())}
      for node_id, d in (dumps or {}).items()
    },
    "leaks": verdicts.leak_check(settle_a, settle_b),
    "drained": drained,
  }
  if cfg.overload and t_wall_load_start is not None:
    # Abort evidence gets a 45 s tail past the burst: a queue built during
    # the window would shed as "stalled" aborts up to a stall timeout later
    # — exactly the failure the gate must have prevented.
    t0 = t_wall_load_start + float(cfg.overload["at_s"]) - 1.0
    t1 = (t_wall_load_start + float(cfg.overload["at_s"])
          + float(cfg.overload.get("seconds", 0.0)) + 45.0)
    report["overload"] = verdicts.summarize_overload(
      records, events, [{"t0": t0, "t1": t1}],
      server.get("admission_rejections", 0.0))
  if cfg.router:
    report["router"] = verdicts.summarize_router(
      ring.last_router, ring.router_track, expect_drain=cfg.gray is not None,
      baseline=base_router)
  if cfg.fabric:
    # Load-window deltas of the cross-replica KV fabric counters (summed
    # over replicas — only the decode side imports, but the sum stays
    # correct if roles ever mix) plus the router's chain bookkeeping.
    rt, base_rt = (ring.last_router or {}), (base_router or {})

    def fabric_delta(prom: str) -> float:
      return (_sum_counter(ring.last_metrics, prom)
              - _sum_counter(base_metrics, prom))

    report["fabric"] = {
      "hits": fabric_delta("xot_kv_fabric_hits_total"),
      "misses": fabric_delta("xot_kv_fabric_misses_total"),
      "errors": fabric_delta("xot_kv_fabric_errors_total"),
      "bytes": fabric_delta("xot_kv_fabric_bytes_total"),
      "router_chained": max(0, int(rt.get("fabric_chained_total") or 0)
                            - int(base_rt.get("fabric_chained_total") or 0)),
      "router_chain_failures": max(
        0, int(rt.get("fabric_chain_failures_total") or 0)
        - int(base_rt.get("fabric_chain_failures_total") or 0)),
      # The smoke's whole point: a disaggregated ring that never imports
      # KV is just a slow router, so the verdict requires a real hit.
      "expect_hit": True,
    }
  if cfg.fleet:
    report["fleet"] = verdicts.summarize_fleet(
      ring.fleet_status, base_fleet, ring.last_router, base_router,
      holders=sorted(h for h in ring.fleet_holders if h),
      expect={
        # Each expectation is keyed on whether the run actually staged the
        # fault that produces it — a custom fault schedule only has to
        # clear the bars for what it injected.
        "respawn": any(p.kind == "kill" for p in cfg.faults),
        "scale_up": cfg.overload is not None,
        "hedge_win": any(
          p.kind == "rules" and any(str(r.get("action")) == "delay"
                                    for r in (p.rules or []))
          for p in cfg.faults),
        "holder_change": any(p.kind == "kill_router" for p in cfg.faults),
      })
  if not drained:
    leaked = report["leaks"]
    leaked["ok"] = False
    leaked.setdefault("active_requests", {})["<drain-timeout>"] = 1.0
  return report
