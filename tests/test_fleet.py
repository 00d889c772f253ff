"""Elastic fleet controller tests: the template validator, the file TTL
actuation lease (acquire/renew/expire/takeover/release), the controller's
plan logic against a fake router (dead -> respawn, scale-up debounce +
cooldown, adoption after a lease handover, idle-spare retire through the
drain discipline), the hedge-delay derivation, the fabric probe retry, the
engine compile-cache wiring, the idempotent admission queue high-water
mark, and the new trailing gauges the controller and hedger consume. The
full multi-process arc (SIGKILL under load -> warm respawn, surge ->
scale-up, router kill -> lease handover, slow replica -> won hedge) runs as
`python -m tools.soak --fleet-smoke` and its committed SOAK_fleet.json is
gated by tools/benchdiff."""
import asyncio
import json

import pytest

from xotorch_tpu.fleet import FleetLease, load_template
from xotorch_tpu.fleet.controller import FleetController
from xotorch_tpu.orchestration.flight import FlightRecorder
from xotorch_tpu.router import hedge_delay_s
from xotorch_tpu.router.app import _Replica


# ------------------------------------------------------------- fleet template

def _template(tmp_path, slots):
  path = tmp_path / "fleet.json"
  path.write_text(json.dumps({"slots": slots}))
  return str(path)


def _slot(name, active=False, url=None):
  return {"name": name, "url": url or f"http://127.0.0.1:1{name[1:]}",
          "active": active, "argv": ["/bin/true"]}


def test_load_template_validates(tmp_path):
  path = _template(tmp_path, [_slot("r0", active=True), _slot("r1")])
  slots = load_template(path)
  assert [s["name"] for s in slots] == ["r0", "r1"]
  assert slots[0]["active"] and not slots[1]["active"]
  for bad in ([],                                        # empty
              [{"name": "r0"}],                          # no url
              [_slot("r0"), _slot("r0")],                # duplicate
              [{"name": "r0", "url": "http://x"}]):      # no argv
    with pytest.raises(ValueError):
      load_template(_template(tmp_path, bad))


# ------------------------------------------------------------ actuation lease

def test_lease_acquire_renew_expire_takeover_release(tmp_path):
  path = str(tmp_path / "lease.json")
  a = FleetLease(path, "router-a", ttl_s=10.0)
  b = FleetLease(path, "router-b", ttl_s=10.0)
  assert a.try_acquire(now=0.0) is True and a.acquired_total == 1
  assert b.try_acquire(now=1.0) is False and b.held is False
  # Renewal by the holder extends the TTL.
  assert a.try_acquire(now=8.0) is True
  assert b.try_acquire(now=12.0) is False  # renewed at 8: live until 18
  # The holder stops renewing (crashed): the TTL hands actuation over.
  assert b.try_acquire(now=18.5) is True and b.acquired_total == 1
  # The old holder's next tick observes the loss — no split brain.
  assert a.try_acquire(now=19.0) is False and a.lost_total == 1
  # Clean shutdown releases NOW: no TTL wait for the peer.
  b.release()
  assert b.held is False
  assert a.try_acquire(now=19.5) is True


def test_lease_solo_mode_always_held():
  lease = FleetLease(None, "router", ttl_s=5.0)
  assert lease.held is True
  assert lease.try_acquire() is True
  assert lease.peek() is None and lease.status()["mode"] == "solo"
  lease.release()  # no-op in solo mode
  assert lease.try_acquire() is True


# --------------------------------------------------------- controller planning

class _FakeRouter:
  """The controller's view of a router: a replica table, a flight
  recorder, and the warm-announce hook — no HTTP anywhere."""

  def __init__(self, names):
    self.replicas = {n: _Replica(n, f"http://127.0.0.1:1{n[1:]}") for n in names}
    self.flight = FlightRecorder(node_id="fake-router")
    self.warm_calls = []

  def routable(self):
    return [r for r in self.replicas.values()
            if r.lifecycle.routable and r.reachable
            and not r.warming and not r.retiring]

  def spawn_warm_announce(self, rep, n):
    self.warm_calls.append((rep.name, n))
    rep.warming = False


def _controller(tmp_path, monkeypatch, slots, router=None, **env):
  defaults = {"XOT_FLEET_UP_POLLS": "2", "XOT_FLEET_UP_QUEUE": "1",
              "XOT_FLEET_IDLE_POLLS": "2", "XOT_FLEET_DEAD_POLLS": "3",
              "XOT_FLEET_COOLDOWN_S": "0", "XOT_FLEET_BOOT_TIMEOUT_S": "30"}
  defaults.update(env)
  for k, v in defaults.items():
    monkeypatch.setenv(k, str(v))
  path = _template(tmp_path, slots)
  router = router or _FakeRouter([s["name"] for s in slots])
  ctl = FleetController(router, path, "router-test")
  # Plan logic only: never exec a real process.
  ctl.spawner.spawn = lambda name: 40000 + int(name[1:])
  ctl.spawner.terminate = lambda name, sig=None: True
  ctl.spawner.reap = lambda name, timeout_s=0: None
  return ctl, router


def _alive(rep, queued=0, hwm=None, active=0):
  rep.lifecycle.note_status(0.0, reachable=True)
  rep.reachable = True
  rep.queue = {"queued": queued, "queued_hwm": hwm if hwm is not None else queued,
               "est_wait_s": 0.0}
  rep.active_requests = active


def test_controller_respawns_dead_replica_into_warm_path(tmp_path, monkeypatch):
  ctl, router = _controller(tmp_path, monkeypatch,
                            [_slot("r0", active=True), _slot("r1", active=True)])
  r0, r1 = router.replicas["r0"], router.replicas["r1"]
  _alive(r0)
  _alive(r1)
  # r1 goes dark: unreachable (or unscrapable — same streak) for 3 polls.
  r1.reachable = False
  r1.down_streak = 2
  ctl.tick(10.0)
  assert ctl.deaths_total == 0  # below the streak threshold: not dead yet
  r1.down_streak = 3
  ctl.tick(11.0)
  assert ctl.deaths_total == 1 and ctl.respawns_total == 1
  # The respawned slot is warming: out of rotation until the pre-announce.
  assert r1.warming is True and "r1" in ctl._warm_deadline
  assert r1.down_streak == 0  # the streak now judges the NEW process
  events = [e["event"] for e in router.flight.tail(0)]
  assert "fleet.dead" in events and "fleet.respawn" in events
  # Booted: the warm pre-announce fires, then the slot re-enters rotation.
  r1.reachable = True
  ctl.tick(12.0)
  assert router.warm_calls == [("r1", ctl.warm_prefixes)]
  assert "r1" not in ctl._warm_deadline and r1.warming is False
  # Respawns are never double-fired while the boot deadline is pending.
  assert ctl.respawns_total == 1


def test_controller_scale_up_debounce_and_revert_on_boot_timeout(tmp_path, monkeypatch):
  ctl, router = _controller(tmp_path, monkeypatch,
                            [_slot("r0", active=True), _slot("r1")],
                            XOT_FLEET_BOOT_TIMEOUT_S="5")
  r0, r1 = router.replicas["r0"], router.replicas["r1"]
  _alive(r0, queued=2, hwm=2)
  ctl.tick(1.0)
  assert ctl.scale_ups_total == 0  # debounce: 1 of 2 pressed polls
  ctl.tick(2.0)
  assert ctl.scale_ups_total == 1 and ctl.desired["r1"] and "r1" in ctl.scaled
  assert r1.warming is True
  events = [e["event"] for e in router.flight.tail(0)]
  assert "fleet.spawn" in events and "fleet.respawn" not in events
  # The spare never comes up: past the boot deadline the slot is given
  # back (a counted failure) so the next surge can retry it.
  ctl.tick(8.0)
  assert ctl.respawn_failures_total == 1
  assert ctl.desired["r1"] is False and "r1" not in ctl.scaled
  assert r1.warming is False


def test_controller_scale_up_needs_fleet_wide_pressure(tmp_path, monkeypatch):
  ctl, router = _controller(tmp_path, monkeypatch,
                            [_slot("r0", active=True), _slot("r1", active=True),
                             _slot("r2")])
  _alive(router.replicas["r0"], queued=5, hwm=5)
  _alive(router.replicas["r1"], queued=0, hwm=0)  # one idle replica: spill's job
  for now in (1.0, 2.0, 3.0):
    ctl.tick(now)
  assert ctl.scale_ups_total == 0 and ctl._up_ticks == 0


def test_controller_adopts_running_slot_after_handover(tmp_path, monkeypatch):
  """A reachable slot the controller believes latent was spawned by a
  previous lease holder: adopt it as a controller-scaled spare."""
  ctl, router = _controller(tmp_path, monkeypatch,
                            [_slot("r0", active=True), _slot("r1")])
  _alive(router.replicas["r0"])
  _alive(router.replicas["r1"])
  ctl.tick(1.0)
  assert ctl.adopted_total == 1
  assert ctl.desired["r1"] is True and "r1" in ctl.scaled


def test_controller_retires_idle_spare_through_drain(tmp_path, monkeypatch):
  ctl, router = _controller(tmp_path, monkeypatch,
                            [_slot("r0", active=True), _slot("r1")])
  r0, r1 = router.replicas["r0"], router.replicas["r1"]
  _alive(r0)
  _alive(r1)
  ctl.tick(1.0)  # adopts r1 as a scaled spare
  assert "r1" in ctl.scaled
  r1.active_requests = 1
  ctl.tick(2.0)
  assert ctl.retires_total == 0  # busy: the idle debounce never starts
  r1.active_requests = 0
  ctl.tick(3.0)
  ctl.tick(4.0)
  assert ctl.retires_total == 1 and r1.retiring is True
  # Retiring holds the slot out of rotation while in-flight work drains.
  assert r1 not in router.routable()
  lc_before = r1.lifecycle
  ctl.tick(5.0)
  assert ctl.scale_downs_total == 1 and ctl.desired["r1"] is False
  # A planned exit resets the lifecycle to latent-boot semantics: the
  # process being gone must not register as an unreachable drain.
  assert r1.lifecycle is not lc_before and r1.lifecycle.drains_total == 0
  assert r1.reachable is False and r1.retiring is False
  events = [e["event"] for e in router.flight.tail(0)]
  assert "fleet.retire" in events


def test_controller_non_holder_observes_but_never_actuates(tmp_path, monkeypatch):
  lease_path = tmp_path / "lease.json"
  FleetLease(str(lease_path), "other-router", ttl_s=3600.0).try_acquire(now=None)
  monkeypatch.setenv("XOT_FLEET_LEASE_PATH", str(lease_path))
  ctl, router = _controller(tmp_path, monkeypatch,
                            [_slot("r0", active=True), _slot("r1")])
  r0 = router.replicas["r0"]
  _alive(r0, queued=5, hwm=5)
  r0.reachable = False
  r0.down_streak = 99  # screaming dead — but actuation is not ours
  for now in (1.0, 2.0, 3.0, 4.0):
    ctl.tick(now)
  assert ctl.lease.held is False
  assert ctl.deaths_total == 0 and ctl.respawns_total == 0
  assert ctl.scale_ups_total == 0 and ctl._up_ticks == 0
  st = ctl.status()
  assert st["lease"]["held"] is False
  assert st["lease"]["lease"]["holder"] == "other-router"


def test_controller_tick_never_raises(tmp_path, monkeypatch):
  ctl, router = _controller(tmp_path, monkeypatch, [_slot("r0", active=True)])
  ctl._adopt = None  # force a TypeError inside the tick
  ctl.tick(1.0)  # absorbed: the hosting poll loop must survive anything


# ---------------------------------------------------------------- hedge delay

def test_hedge_delay_from_fleet_trailing_p99():
  compacts = [{"trailing": {"request_p99_s": 2.0}},
              {"trailing": {"request_p99_s": 4.0}},
              {"trailing": {"request_p99_s": 100.0}}]  # the slow one: outvoted
  assert hedge_delay_s(compacts, factor=2.0, min_s=0.5) == pytest.approx(8.0)
  # No p99 yet (thin traffic): fall back to the p50 median.
  assert hedge_delay_s([{"trailing": {"request_p50_s": 1.0}}], 3.0, 0.5) \
      == pytest.approx(3.0)
  # Cold fleet: the bare floor — hedging never waits on absent data.
  assert hedge_delay_s([], 2.0, 0.5) == pytest.approx(0.5)
  assert hedge_delay_s([{"trailing": {"request_p99_s": 0.01}}], 2.0, 0.5) \
      == pytest.approx(0.5)  # floored


# --------------------------------------------------------- fabric probe retry

def test_fabric_probe_retry_absorbs_one_failure():
  from xotorch_tpu.fabric.client import FabricClient, FetchResult
  client = FabricClient(["http://peer"])
  calls = []

  def flaky(url, obj):
    calls.append(url)
    if len(calls) == 1:
      raise OSError("connection reset")
    return {"key": "k", "common": 7}

  client._post_json = flaky
  result = FetchResult()
  resp = client._probe_peer("http://peer", {"toks": [1]}, result)
  # One dropped connection is absorbed: no counted error, no backoff.
  assert resp == {"key": "k", "common": 7} and len(calls) == 2
  assert result.errors == 0 and client._peer_usable("http://peer")


def test_fabric_probe_retry_exhaustion_counts_one_error():
  from xotorch_tpu.fabric.client import FabricClient, FetchResult
  client = FabricClient(["http://peer"])
  calls = []

  def dead(url, obj):
    calls.append(url)
    raise OSError("refused")

  client._post_json = dead
  result = FetchResult()
  assert client._probe_peer("http://peer", {"toks": [1]}, result) is None
  # A dead peer is still ONE counted error (not one per attempt), and it
  # enters the down backoff so the next consult skips it.
  assert len(calls) == 2 and result.errors == 1
  assert not client._peer_usable("http://peer")


# ------------------------------------------------------- compile-cache wiring

def test_engine_wires_persistent_compile_cache_once(monkeypatch):
  """The engine's jax accessor turns the persistent cache on through the one
  helper (utils/compile_cache) — and the helper applies its config once per
  process, so an engine built inside the suite never resets the suite's
  own threshold."""
  jax = pytest.importorskip("jax")
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.utils import compile_cache
  engine = JAXShardInferenceEngine.__new__(JAXShardInferenceEngine)
  monkeypatch.setattr(compile_cache, "_enabled_dir", None)
  updates = []
  monkeypatch.setattr(jax.config, "update", lambda opt, val: updates.append(opt))
  assert engine._jax() is jax
  assert compile_cache._enabled_dir == compile_cache.cache_dir()
  assert "jax_persistent_cache_min_compile_time_secs" in updates
  # Idempotent: the second call never re-applies the config.
  del updates[:]
  assert engine._jax() is jax
  assert updates == []


# --------------------------------------------------- admission queue high-water

async def test_admission_queued_hwm_is_windowed_and_idempotent(monkeypatch):
  monkeypatch.setenv("XOT_MAX_INFLIGHT", "1")
  monkeypatch.setenv("XOT_ADMIT_QUEUE_DEPTH", "4")
  from xotorch_tpu.orchestration.admission import AdmissionGate
  from xotorch_tpu.inference.dummy import DummyInferenceEngine
  from tests.test_orchestration import _make_node
  node = await _make_node("hwm-node", DummyInferenceEngine())
  gate = AdmissionGate(node)
  gate.admit("a")
  gate.admit("b")
  gate.admit("c")
  assert len(gate._queue) == 2
  import time as _time
  t0 = _time.monotonic()
  # The burst drains completely...
  gate.release()
  gate.release()
  gate.release()
  assert gate.inflight == 0 and len(gate._queue) == 0
  # ...but the trailing high-water mark survives the drain, and EVERY
  # reader sees it (time-windowed, never reset-on-read: the status-bus
  # rollup and the router poll both read compact()).
  assert gate.queued_hwm(now=t0 + 1.0) == 2
  assert gate.queued_hwm(now=t0 + 1.0) == 2
  assert gate.compact()["queued_hwm"] == 2
  # Past the window the burst is forgotten; the live depth still floors it.
  assert gate.queued_hwm(now=t0 + gate.hwm_window_s + 1.0) == 0


# --------------------------------------------------------- new trailing gauges

async def test_history_p99_and_admit_wait_gauges(monkeypatch):
  from tests.test_history import _hist_env
  from tests.test_alerts import _summary
  from tests.test_orchestration import _make_node
  from xotorch_tpu.inference.dummy import DummyInferenceEngine
  _hist_env(monkeypatch)
  monkeypatch.setenv("XOT_MAX_INFLIGHT", "2")
  node = await _make_node("h-p99", DummyInferenceEngine())
  h = node.history
  h.observe(now=0.0, summary=_summary(requests=10, e2e=[0.2] * 10))
  s = h.observe(now=1.0, summary=_summary(requests=30,
                                          e2e=[0.2] * 10 + [0.9] * 20))
  g = s["gauges"]
  # The window's 20 new observations all sit in (0.5, 1.0]: both the p50
  # and the p99 (what the router's hedge delay is derived from) land there.
  assert 0.5 < g["request_p50_s"] <= 1.0
  assert 0.5 < g["request_p99_s"] <= 1.0
  # The gate is enabled and idle: a live zero-wait estimate, present (not
  # omitted) so the controller's trend window sees the calm too.
  assert g["admit_wait_s"] == pytest.approx(0.0)
  await node.stop()


async def test_history_gauges_omit_admit_wait_when_gate_disabled(monkeypatch):
  from tests.test_history import _hist_env
  from tests.test_alerts import _summary
  from tests.test_orchestration import _make_node
  from xotorch_tpu.inference.dummy import DummyInferenceEngine
  _hist_env(monkeypatch)
  node = await _make_node("h-nogate", DummyInferenceEngine())
  h = node.history
  h.observe(now=0.0, summary=_summary(requests=5, e2e=[0.1] * 5))
  s = h.observe(now=1.0, summary=_summary(requests=6, e2e=[0.1] * 6))
  assert "admit_wait_s" not in s["gauges"]  # defaults-off adds no gauge
  await node.stop()
