"""Node: the masterless peer orchestrating the token ring.

Parity: /root/reference/xotorch/orchestration/node.py:22-620 — same public
surface (start/stop, process_prompt/process_tensor, enqueue_example/
process_example, coordinate_save, collect_topology, on_token,
on_opaque_status) and the same deterministic-ring design:

- every peer derives the identical partition table from the gossiped topology
  (RingMemoryWeightedPartitioningStrategy), so routing needs no coordination;
- the token ring: the last-layer peer samples, broadcasts the token list to
  all peers, and feeds the token back to partition 0; everyone else forwards
  hidden state to the next partition (bf16 on the wire here — the reference
  upcast to fp32 every hop);
- peers reconcile membership every `topology_interval` seconds and re-gossip
  the topology with a visited-set BFS capped at max_depth.

Training rides the same ring: forward activations down, gradients chained
back (process_example), with the engine-leaf train/evaluate implemented for
real in the JAX engine (the reference's engines never implemented them).
"""
from __future__ import annotations

import asyncio
import json
import time
import uuid
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from xotorch_tpu.inference.engine import (
  CacheExhausted, InferenceEngine, RequestStateLost, inference_engine_classes,
)
from xotorch_tpu.inference.shard import Shard
from xotorch_tpu.models.registry import get_supported_models
from xotorch_tpu.networking.discovery import Discovery
from xotorch_tpu.networking.peer_handle import PeerHandle
from xotorch_tpu.networking.server import Server
from xotorch_tpu.topology.device_capabilities import UNKNOWN_DEVICE_CAPABILITIES, device_capabilities
from xotorch_tpu.topology.partitioning import PartitioningStrategy, map_partitions_to_shards
from xotorch_tpu.orchestration.tracing import TRACEPARENT_KEY, TraceContext, Tracer
from xotorch_tpu.orchestration.admission import AdmissionGate
from xotorch_tpu.orchestration.alerts import AlertEngine
from xotorch_tpu.orchestration.anatomy import (
  AnatomyStore, ClockSkew, extract_breakdown, ring_offsets,
)
from xotorch_tpu.orchestration.metrics import NodeMetrics, aggregate_histograms
from xotorch_tpu.orchestration.flight import FlightRecorder
from xotorch_tpu.topology.topology import Topology
from xotorch_tpu.utils import knobs
from xotorch_tpu.utils.helpers import DEBUG, AsyncCallbackSystem, spawn_detached

# inference_state side-channel key carrying the per-request completion cap to
# the last-layer peer (companion to tracing.TRACEPARENT_KEY).
MAX_TOKENS_KEY = "xot_max_tokens"
# Same side-channel for the per-request sampling temperature (OpenAI
# `temperature`): whichever peer samples must use the REQUEST's temperature,
# not its own node default.
TEMP_KEY = "xot_temperature"
# And for OpenAI `top_p` (nucleus sampling). Values snap to a 0.05 grid at
# the API so the (top_k, top_p)-specialised executables stay bounded.
TOP_P_KEY = "xot_top_p"
# And for the OpenAI sampling extras the reference parsed-and-dropped
# (chatgpt_api.py): one JSON-safe dict {seed, logit_bias,
# presence_penalty, frequency_penalty} applied on device by the sampler.
SAMPLING_KEY = "xot_sampling"
# Prompt token ids for prompt-lookup speculation on multi-partition rings:
# mid-ring hops carry hidden states, so the SAMPLER peer (which drafts)
# never sees the prompt tokens unless the first-layer owner sends them once
# on the first hop. Only attached when XOT_SPECULATE > 0.
PROMPT_TOKENS_KEY = "xot_prompt_tokens"
# Request-scoped partition map ("routing epoch"): [[node_id, start, end],
# ...] in ring order, pinned ONCE by the node that originates a request and
# carried on the prompt hop and every tensor hop. Every peer routes THIS
# request by the map, not by its own live topology view — a peer that joined
# moments ago (whose gossip/partition view still lags) would otherwise
# recompute a DIFFERENT shard for the same request and serve the wrong layer
# range: the observed failure was a just-joined peer prefilling the full
# model into one engine context while the ring decoded through another,
# silently diverging the stream. Membership changes mid-request still abort
# via hop errors (the map names a peer that no longer answers).
RING_MAP_KEY = "xot_ring_map"
# Remaining end-to-end deadline budget (seconds at send time), riding the
# inference_state side-channel like the traceparent: every peer that touches
# the request derives its own absolute deadline from it, so the watchdog can
# abort a blown request ANYWHERE on the ring (monotonic clocks don't compare
# across hosts — the absolute value never crosses the wire).
DEADLINE_KEY = "xot_deadline_s"


_DRAFT_SCAN_WINDOW = knobs.get_int("XOT_SPECULATE_WINDOW")

# A busy local engine defers a stall-watchdog abort (an in-flight cold-jit
# compile is active work, not a distributed stall) for at most this many
# stall-timeout multiples: one compile fits comfortably, while an engine kept
# permanently busy by OTHER requests cannot shield a dead-peer hang forever.
_STALL_DEFER_CAP = 4


def _lookup_draft(context: List[int], k: int) -> List[int]:
  """Prompt-lookup drafting (model-free speculative decoding): propose the
  continuation of the most recent EARLIER occurrence of the current tail
  n-gram in prompt+output. Summarisation/extraction/code workloads repeat
  long prompt spans verbatim, so drafts verify at high acceptance; on text
  with no repeats this returns [] and decode proceeds normally."""
  if k < 2 or len(context) < 4:
    return []
  # Bound the backward scan: long-context prompts would otherwise pay an
  # O(prompt) Python scan per decode round on the event loop.
  context = context[-_DRAFT_SCAN_WINDOW:]
  for n in (3, 2):
    if len(context) <= n:
      continue
    tail = context[-n:]
    best: List[int] = []
    # Newest occurrence preferred, but keep scanning older ones when the
    # continuation is short — self-repetition's newest match sits right at
    # the tail with almost nothing after it, while older ones run long.
    for i in range(len(context) - n - 1, -1, -1):
      if context[i:i + n] == tail:
        cont = context[i + n:i + n + k]
        if len(cont) == k:
          return cont
        if len(cont) > len(best):
          best = cont
    if len(best) >= 2:
      return best
  return []


class Node:
  def __init__(
    self,
    _id: str,
    server: Server,
    inference_engine: InferenceEngine,
    discovery: Discovery,
    shard_downloader,
    partitioning_strategy: PartitioningStrategy,
    max_generate_tokens: int = 1024,
    default_sample_temp: float = 0.6,
    default_sample_top_k: int = 35,
    topology_viz=None,
    decode_chunk_size: Optional[int] = None,
  ):
    self.id = _id
    self.server = server
    self.inference_engine = inference_engine
    self.discovery = discovery
    self.shard_downloader = shard_downloader
    self.partitioning_strategy = partitioning_strategy
    self.max_generate_tokens = max_generate_tokens
    self.default_sample_temp = default_sample_temp
    self.default_sample_top_k = default_sample_top_k
    self.topology_viz = topology_viz
    # Tokens per fused decode dispatch when one partition owns the whole
    # model; 1 disables (pure per-token ring). Bounds both streaming latency
    # and the EOS overshoot (tokens computed past EOS are discarded).
    self.decode_chunk_size = (
      decode_chunk_size if decode_chunk_size is not None
      else knobs.get_int("XOT_DECODE_CHUNK")
    )
    # Adaptive growth ceiling: each fused dispatch doubles the chunk up to
    # this cap, so long generations amortise the per-dispatch host sync
    # while the FIRST chunk stays small for
    # streaming latency and short replies never overshoot far past EOS.
    # Power-of-two ladder => bounded executable count per (B, size) pair.
    self.max_decode_chunk_size = max(
      self.decode_chunk_size, knobs.get_int("XOT_DECODE_CHUNK_MAX")
    )

    self.peers: List[PeerHandle] = []
    self.topology = Topology()
    self.device_capabilities = UNKNOWN_DEVICE_CAPABILITIES
    self.buffered_token_output: Dict[str, Tuple[List[int], bool]] = {}
    self.checkpoints: Dict[str, Dict[str, int]] = {}
    self.topology_inference_engines_pool: List[List[str]] = []
    self.node_download_progress: Dict[str, Any] = {}

    self.on_token: AsyncCallbackSystem = AsyncCallbackSystem()
    self.on_opaque_status: AsyncCallbackSystem = AsyncCallbackSystem()
    self.on_opaque_status.register("node_status").on_next(self.on_node_status)

    self._topology_task: Optional[asyncio.Task] = None
    self.outstanding_requests: Dict[str, str] = {}

    # Observability: real spans + real prometheus metrics for the intents the
    # reference declared but never wired (SURVEY §0, §5), plus the always-on
    # flight recorder whose frozen snapshots turn watchdog aborts into
    # replayable timelines (/v1/debug/flight).
    self.tracer = Tracer(node_id=self.id)
    self.metrics = NodeMetrics(node_id=self.id)
    self.flight = FlightRecorder(node_id=self.id)
    self._request_trace_ctx: Dict[str, Any] = {}
    self._last_token_time: Dict[str, float] = {}
    # First-touch monotonic timestamp per request — feeds the TTFT and
    # whole-request SLO histograms (each node observes its own view).
    self._request_started: Dict[str, float] = {}
    # Latest metric summaries received from peers over the status bus
    # (type "node_metrics"); served by /v1/cluster/metrics so one scrape
    # sees the whole ring. Bounded by cluster size in practice; the LRU
    # guard protects against id churn. Each ingest is stamped (monotonic)
    # so a dead node's last-good summary reads STALE past 3x the topology
    # cadence instead of polluting the cluster aggregate forever, and
    # eviction prunes the row outright.
    self.peer_metrics: "OrderedDict[str, dict]" = OrderedDict()
    self._peer_metrics_at: Dict[str, float] = {}
    # Topology-reconcile cadence (start() overwrites with the real value):
    # the staleness horizon for peer_metrics rows is 3x this.
    self.topology_interval = 2.0
    # Engine-depth observability: hand the engine this node's recorder,
    # metrics registry, tracer, and a trace-context resolver so batcher
    # queue waits, prefill slices, pool pressure, host-tier traffic, and
    # first-compile events surface as spans/histograms/flight events.
    # Duck-typed (base-class attrs default None): every engine accepts the
    # hooks, engines that never call them pay nothing.
    for hook, value in (("metrics", self.metrics), ("flight", self.flight),
                        ("tracer", self.tracer),
                        ("trace_ctx", self._request_trace_ctx.get)):
      try:
        setattr(inference_engine, hook, value)
      except Exception as e:
        if DEBUG >= 2:
          print(f"engine observability hook {hook} not attached: {e!r}")
    # Per-request completion caps (OpenAI max_tokens); rides the
    # inference_state side-channel to whichever peer owns the last layer.
    self._request_max_tokens: Dict[str, int] = {}
    # Per-request sampling temperature (OpenAI temperature); same channel.
    self._request_temp: Dict[str, float] = {}
    # Per-request nucleus sampling (OpenAI top_p); same channel.
    self._request_top_p: Dict[str, float] = {}
    # Per-request sampling extras (OpenAI seed / logit_bias / penalties);
    # same channel (SAMPLING_KEY).
    self._request_sampling: Dict[str, dict] = {}
    # Does engine.infer_sample_tensor accept the `sampling` kwarg? Resolved
    # by signature inspection on first extras request (None = not yet).
    self._engine_accepts_sampling: Optional[bool] = None
    # Why a request aborted (bounded LRU; API pops entries when reporting).
    self.request_errors: "OrderedDict[str, str]" = OrderedDict()
    # Request ids whose finish broadcast was applied here (bounded): shields
    # against out-of-order straggler deltas resurrecting finished requests.
    self._finished_results: "OrderedDict[str, None]" = OrderedDict()
    # Per-request EOS id cache: constant over a request's lifetime; avoids a
    # ring-partition recompute per sampled token on the per-token path.
    self._request_eos: Dict[str, Tuple[int, ...]] = {}
    # Prompt token ids per request (sampler peer only): the draft source for
    # prompt-lookup speculative decoding (XOT_SPECULATE).
    self._request_prompt_tokens: Dict[str, List[int]] = {}
    # Per-request partition map (RING_MAP_KEY): ring-ordered
    # [node_id, start_layer, end_layer] rows, pinned at request origin.
    self._request_ring_map: "OrderedDict[str, list]" = OrderedDict()
    # Serializes peer-set reconciliation (periodic loop + hop-time heals).
    self._update_peers_lock = asyncio.Lock()
    # Client-cancelled requests (cancel_request): the decode loops stop at
    # the next token/chunk boundary instead of running to EOS/cap. Bounded
    # LRU rather than per-request cleanup: the flag must outlive
    # finish_request_state so a still-running loop (possibly on a REMOTE
    # sampler peer, marked via the finished broadcast) reliably observes it.
    self._cancelled: "OrderedDict[str, None]" = OrderedDict()
    # Draft-MODEL speculation (XOT_DRAFT_MODEL): a small resident model
    # proposes every round (engine.draft_tokens) where prompt-lookup only
    # fires on n-gram repeats. Setting a draft model implies speculation on
    # (default 8 draft tokens; XOT_SPECULATE still overrides the depth).
    self.draft_model = knobs.get_str("XOT_DRAFT_MODEL", "")
    self.speculate_tokens = knobs.get_int("XOT_SPECULATE", 8 if self.draft_model else 0)
    # Strong refs to detached tasks (hops, fused loops, broadcasts): the
    # event loop holds tasks only weakly — a GC'd generation-driving task
    # would silently stall its request with no error.
    self._detached_tasks: set = set()

    # ---- request survivability (deadlines, watchdog, eviction) ----
    # End-to-end request deadline (0 disables); remaining budget rides the
    # hops (DEADLINE_KEY / send_prompt's deadline field).
    self.request_deadline_s = knobs.get_float("XOT_REQUEST_DEADLINE_S")
    # Stall watchdog: abort any request whose last observed progress (hop
    # received / token sampled / broadcast delta applied) is older than
    # this (0 disables) — a peer that dies AFTER acking a tensor otherwise
    # stalls the request forever with no error anywhere.
    self.stall_timeout_s = knobs.get_float("XOT_STALL_TIMEOUT_S")
    # Periodic peer health monitor (0 disables): a peer failing
    # XOT_HEALTH_FAILS consecutive checks is evicted and the topology
    # repartitioned; eviction holds for XOT_EVICT_COOLDOWN_S so discovery
    # can't immediately re-admit a corpse.
    self.health_interval_s = knobs.get_float("XOT_HEALTH_INTERVAL_S")
    self.health_fail_threshold = max(1, knobs.get_int("XOT_HEALTH_FAILS"))
    self.evict_cooldown_s = knobs.get_float("XOT_EVICT_COOLDOWN_S")
    self._request_deadline: Dict[str, float] = {}
    self._last_progress: Dict[str, float] = {}
    # Requests whose stall abort was deferred because the local engine was
    # mid-dispatch (compile included): tracked so the flight recorder logs
    # ONE `watchdog.deferred` per stall episode, not one per sweep tick.
    self._stall_deferred: set = set()
    # Receiver-side hop dedup: per-request bounded seen-sets of hop seq ids
    # (note_hop_delivery) — what makes retried deliveries idempotent.
    self._hop_seen: "OrderedDict[str, OrderedDict]" = OrderedDict()
    self._health_fails: Dict[str, int] = {}
    self._evicted_until: Dict[str, float] = {}
    self._watchdog_task: Optional[asyncio.Task] = None
    self._health_task: Optional[asyncio.Task] = None
    # Metrics history (XOT_HISTORY, default on): a bounded downsampling
    # time-series of this node's own windowed gauge deltas, optionally
    # spooled to XOT_HISTORY_DIR so restarts keep the record. Served at
    # /v1/history; its trailing compact rides metrics_summary() so ring
    # peers (and the router) can run peer-median drift comparisons.
    # Constructed BEFORE the alert engine: the engine's DriftSentinel
    # reads it on every evaluate tick.
    from xotorch_tpu.orchestration.history import MetricsHistory
    self.history = MetricsHistory(self)
    self._history_task: Optional[asyncio.Task] = None
    # SLO burn-rate alerts + gray-failure localization (XOT_ALERT, default
    # on): evaluated on a background cadence over windowed deltas of this
    # node's own metric summaries; served at /v1/alerts and rolled over the
    # status bus via metrics_summary().
    self.alerts = AlertEngine(self)
    self._alert_task: Optional[asyncio.Task] = None
    # Bounded admission gate (XOT_MAX_INFLIGHT, default 0 = off): the API
    # acquires a slot before process_prompt, so overload is shed as 429s at
    # the door instead of watchdog "stalled" aborts inside the ring.
    # Exposed at /v1/queue; the compact rides metrics_summary() while
    # enabled so the router (and peers) place by live load.
    self.admission = AdmissionGate(self)
    # Anticipatory-prefetch dedupe (bounded LRU of (shard, prompt-hash) ->
    # monotonic ts): the router's /v1/prefetch pre-announce and the
    # admission gate's on_queued hook fire for the SAME queued request, and
    # the duplicate would re-run tokenizer encode + host-store match on a
    # node that is by definition saturated.
    self._prefetch_recent: "OrderedDict[tuple, float]" = OrderedDict()
    # Critical-path latency anatomy (XOT_ANATOMY, default on): per-peer
    # clock-skew estimation fed by hop clock stamps (receive side:
    # note via `self.clock`; send side: peer handles adopt `self.clock` at
    # peer-set assignment, like `flight`), plus a bounded reservoir of
    # skew-corrected per-request stage breakdowns assembled at the ORIGIN
    # once the ring's trace shards arrive. Served at /v1/anatomy.
    self.clock = ClockSkew(self.id)
    # Spans stamp through the same (possibly skew-injected) wall clock as
    # the hop stamps, so XOT_ANATOMY_SKEW_NS simulates a skewed host end
    # to end — spans drift exactly as far as the stamps that correct them.
    self.tracer.now_ns = self.clock.wall_ns
    self.anatomy = AnatomyStore()
    self._anatomy_delay_s = max(0.0, knobs.get_float("XOT_ANATOMY_DELAY_S"))
    # Requests THIS node originated (bounded LRU): only the origin holds
    # the rolled-up trace, so only it assembles the breakdown.
    self._anatomy_origin: "OrderedDict[str, None]" = OrderedDict()

  def _spawn(self, coro) -> "asyncio.Task":
    return spawn_detached(coro, self._detached_tasks)

  # ------------------------------------------------------------- lifecycle

  async def start(self, wait_for_peers: int = 0, topology_interval: float = 2.0) -> None:
    self.device_capabilities = await device_capabilities()
    self.topology_interval = topology_interval
    await self.server.start()
    await self.discovery.start()
    await self.update_peers(wait_for_peers)
    await self.collect_topology(set())
    self._topology_task = self._spawn(self.periodic_topology_collection(topology_interval))
    self.start_watchdog()
    self.start_health_monitor()
    self.start_alerts()
    self.start_history()
    if DEBUG >= 1:
      print(f"Node {self.id} started; topology: {self.topology}")

  async def stop(self) -> None:
    for attr in ("_topology_task", "_watchdog_task", "_health_task", "_alert_task",
                 "_history_task"):
      task = getattr(self, attr)
      if task is not None:
        task.cancel()
        try:
          await task
        except asyncio.CancelledError:
          pass
        setattr(self, attr, None)
    await self.discovery.stop()
    await self.server.stop()
    # Detached graceful channel drains (peer replacement mid-request) must
    # not outlive the node: settle them with a short grace, cancel the rest.
    try:
      from xotorch_tpu.networking.grpc.peer_handle import drain_graceful_closes
      await drain_graceful_closes()
    except ImportError:
      pass  # grpc-less deployments (in-process ring) have none

  # ------------------------------------------------------- survivability

  def start_watchdog(self, request_id: Optional[str] = None) -> None:
    """Arm the deadline/stall watchdog (no-op when nothing needs it).
    Also called lazily from _note_progress / deadline adoption so Nodes
    driven without start() — the test harness pattern — still get
    coverage, and a peer whose OWN knobs are off still enforces a deadline
    that arrived via hop metadata (the origin may be the node that died).
    `request_id` is the request whose progress/deadline triggered the lazy
    arming — recorded so a flight snapshot shows the arming→firing pair."""
    if self._watchdog_task is None and (
        self.stall_timeout_s > 0 or self.request_deadline_s > 0 or self._request_deadline):
      self._watchdog_task = self._spawn(self._watchdog_loop())
      self.flight.record("watchdog.armed", request_id,
                         stall_s=self.stall_timeout_s, deadline_s=self.request_deadline_s)

  def start_health_monitor(self) -> None:
    if self._health_task is None and self.health_interval_s > 0:
      self._health_task = self._spawn(self._health_monitor_loop())

  def start_alerts(self) -> None:
    if self._alert_task is None and self.alerts.enabled:
      self._alert_task = self._spawn(self._alert_loop())

  def start_history(self) -> None:
    if self._history_task is None and self.history.enabled:
      self._history_task = self._spawn(self._history_loop())

  async def _history_loop(self) -> None:
    """Metrics-history sampling cadence: one windowed gauge sample per
    tick. Host-side reads only (metric cells, engine counters, EWMAs) —
    this loop can never add a device sync."""
    while True:
      await asyncio.sleep(self.history.sample_s)
      try:
        self.history.observe()
      except Exception as e:
        if DEBUG >= 1:
          print(f"history sampling error: {e!r}")

  async def _alert_loop(self) -> None:
    """SLO rule evaluation cadence: snapshot the node's own metric summary,
    difference it at the burn windows, step each rule's state machine.
    Host-side reads only — this loop can never add a device sync."""
    while True:
      await asyncio.sleep(self.alerts.eval_interval_s)
      try:
        self.alerts.evaluate()
      except Exception as e:
        if DEBUG >= 1:
          print(f"alert evaluation error: {e!r}")

  def _note_progress(self, request_id: str) -> None:
    self._last_progress[request_id] = time.monotonic()
    self._stall_deferred.discard(request_id)
    self.start_watchdog(request_id)

  def note_hop_delivery(self, request_id: Optional[str], hop_seq: Optional[str]) -> bool:
    """Receiver-side dedup for retried hops: True admits the delivery, False
    means this (request, seq) was already delivered — the sender's ack got
    lost and its retry redelivered; processing it again would double-decode
    a position. Bounded per-request seen-sets (retries land close in time,
    so a small window suffices); rows age out of the bounded LRU rather
    than dying at finish, so a retry landing after the request completed is
    still dropped instead of resurrecting state for a dead request."""
    if hop_seq is None:
      return True
    key = request_id or ""
    seen = self._hop_seen.get(key)
    if seen is None:
      seen = self._hop_seen[key] = OrderedDict()
      while len(self._hop_seen) > 256:
        self._hop_seen.popitem(last=False)
    self._hop_seen.move_to_end(key)
    if hop_seq in seen:
      self.metrics.dedup_drops_total.inc()
      self.flight.record("hop.dedup_drop", request_id, seq=hop_seq)
      if DEBUG >= 2:
        print(f"[{request_id}] duplicate hop delivery {hop_seq} dropped")
      return False
    seen[hop_seq] = None
    while len(seen) > 128:
      seen.popitem(last=False)
    return True

  async def _watchdog_loop(self) -> None:
    """Abort requests that blew their end-to-end deadline or stopped making
    progress. Today's alternative is a silent forever-hang: a peer that
    dies after acking a tensor raises no error anywhere. Aborting rides the
    existing _abort_request path, so the finish broadcast cleans up
    bookkeeping and KV on every surviving peer too."""
    bounds = [t for t in (self.stall_timeout_s, self.request_deadline_s) if t > 0]
    tick = min(1.0, max(0.02, min(bounds) / 4)) if bounds else 1.0
    while True:
      await asyncio.sleep(tick)
      now = time.monotonic()
      try:
        for rid, dl in list(self._request_deadline.items()):
          if now <= dl:
            continue
          if rid in self.outstanding_requests or rid in self.buffered_token_output:
            self.metrics.watchdog_aborts_total.inc()
            self.flight.record("deadline.expired", rid, overdue_s=round(now - dl, 3))
            self.flight.record("watchdog.fired", rid, kind="deadline")
            await self._abort_request(rid, f"deadline_exceeded: request blew its deadline on {self.id}")
          else:
            self._request_deadline.pop(rid, None)  # finished elsewhere; GC the row
        if self.stall_timeout_s > 0:
          # Sweep every request with a progress row, not just locally
          # outstanding ones: the ORIGIN of a forwarded prompt returns
          # right after the forward (it is never "outstanding" here), yet a
          # silently lost prompt chain must still end at its deadline
          # instead of riding the API timeout. Rows die at finish, so a
          # completed request can't false-abort.
          busy_fn = getattr(self.inference_engine, "dispatch_inflight", None)
          for rid in set(self.outstanding_requests) | set(self._last_progress):
            last = self._last_progress.get(rid)
            if last is None:
              self._last_progress[rid] = now
            elif now - last > self.stall_timeout_s:
              if (busy_fn is not None and busy_fn()
                  and now - last <= self.stall_timeout_s * _STALL_DEFER_CAP):
                # The local engine is mid-dispatch (a cold-jit compile of a
                # first request can exceed any sane stall bound): this is
                # active work, not the silent distributed stall the watchdog
                # exists for. Defer — the stall clock keeps running, so the
                # abort fires at the first sweep that finds the engine idle.
                # BOUNDED: on a busy ring the engine is mid-dispatch at
                # almost every sweep serving OTHER requests, which must not
                # shield a dead-peer hang forever — past the cap the abort
                # fires regardless. A hung DEVICE call is the request
                # deadline's job.
                if rid not in self._stall_deferred:
                  self._stall_deferred.add(rid)
                  self.flight.record("watchdog.deferred", rid,
                                     idle_s=round(now - last, 3))
                continue
              self._stall_deferred.discard(rid)
              self.metrics.watchdog_aborts_total.inc()
              self.flight.record("watchdog.fired", rid, kind="stall",
                                 idle_s=round(now - last, 3))
              await self._abort_request(
                rid, f"stalled: no progress for {now - last:.2f}s on {self.id} "
                     f"(stall timeout {self.stall_timeout_s:g}s)")
      except Exception as e:
        if DEBUG >= 1:
          print(f"watchdog error: {e!r}")

  async def _health_monitor_loop(self) -> None:
    """Periodic wiring for the (previously never-called) peer health_check:
    evict peers that fail repeatedly and repartition, so the NEXT request
    pins a ring of live peers instead of routing into a corpse."""
    while True:
      await asyncio.sleep(self.health_interval_s)
      try:
        await self._health_sweep(self.health_fail_threshold)
      except Exception as e:
        if DEBUG >= 1:
          print(f"health monitor error: {e!r}")

  async def _health_sweep(self, evict_after: int) -> None:
    for peer in list(self.peers):
      try:
        ok = await peer.health_check()
      except Exception:
        ok = False
      if ok:
        self._health_fails.pop(peer.id(), None)
        continue
      from xotorch_tpu.networking import faults
      faults.bump("health_check_failures")
      fails = self._health_fails.get(peer.id(), 0) + 1
      self._health_fails[peer.id()] = fails
      self.flight.record("health.check_failed", None, peer=peer.id(), fails=fails)
      if fails >= evict_after:
        await self._evict_peer(peer)

  async def _evict_peer(self, peer) -> None:
    if DEBUG >= 1:
      print(f"Evicting unhealthy peer {peer.id()}@{peer.addr()}")
    self.peers = [p for p in self.peers if p.id() != peer.id()]
    self._evicted_until[peer.id()] = time.monotonic() + self.evict_cooldown_s
    self._health_fails.pop(peer.id(), None)
    # A dead peer's last-good metric summary must not keep feeding the
    # cluster aggregate (it would freeze the ring's percentiles at the
    # moment of death).
    self.peer_metrics.pop(peer.id(), None)
    self._peer_metrics_at.pop(peer.id(), None)
    self.metrics.peer_evictions_total.inc()
    self.metrics.peers.set(len(self.peers))
    self.flight.record("peer.evicted", None, peer=peer.id(),
                       cooldown_s=self.evict_cooldown_s)
    # An eviction is a terminal anomaly for whatever was riding that peer:
    # freeze a node-scope snapshot now (in-flight requests usually follow
    # with their own watchdog/hop-error freeze via _abort_request).
    self.flight.freeze(None, reason=f"peer_evicted:{peer.id()}")
    try:
      await peer.disconnect()
    except Exception as e:
      if DEBUG >= 1:
        print(f"evicted peer {peer.id()} disconnect failed (already dead?): {e!r}")
    try:
      # Repartition NOW: the dead peer must leave the partition table before
      # any new (or restarted) request pins its ring map.
      await self.collect_topology(set())
    except Exception as e:
      if DEBUG >= 1:
        print(f"post-eviction repartition failed (next periodic sweep retries): {e!r}")

  def _is_evicted(self, peer_id: str) -> bool:
    until = self._evicted_until.get(peer_id)
    if until is None:
      return False
    if time.monotonic() >= until:
      self._evicted_until.pop(peer_id, None)
      return False
    return True

  async def heal_ring(self) -> None:
    """Aggressive one-shot heal for the API's request-restart path: a
    request just died, so a single failed check is enough to evict; then
    re-derive the partition table so the restarted request pins a live
    ring. Peers that pass stay — an engine-side failure must not cost a
    healthy peer its seat."""
    await self._health_sweep(evict_after=1)
    try:
      await self.collect_topology(set())
    except Exception as e:
      if DEBUG >= 1:
        print(f"heal_ring repartition failed (restart will pin the stale map): {e!r}")

  # ----------------------------------------------------------- status bus

  def on_node_status(self, request_id, opaque_status) -> None:
    """Ingest cluster-wide opaque status (parity node.py:73-98): track which
    node is actively serving, download progress, engine pools — feeds viz."""
    try:
      status = json.loads(opaque_status)
      status_type = status.get("type", "")
      if status_type == "supported_inference_engines":
        self.topology_inference_engines_pool.append(status.get("engines", []))
      elif status_type == "download_progress":
        self.node_download_progress[status.get("node_id")] = status.get("progress")
      elif status_type == "trace_spans":
        # Cluster trace rollup (receiver side): adopt a peer's finished
        # spans so a single /v1/traces call on ANY node returns the whole
        # ring's trace for a request. Own broadcasts echo locally — skip.
        if status.get("node_id") != self.id:
          self.tracer.ingest(status.get("spans") or [])
      elif status_type == "node_metrics":
        nid = status.get("node_id")
        if nid and nid != self.id:
          self.ingest_peer_metrics(nid, status.get("metrics") or {})
      elif status_type == "resume_checkpoint":
        # Cluster-wide resume: each peer loads ITS layer range from the
        # shared checkpoint directory, so a multi-partition training ring
        # never restarts as a chimera of resumed + fresh shards.
        if status.get("node_id") != self.id:
          base = Shard.from_dict(status.get("base_shard", {}))
          path = status.get("path", "")
          self._spawn(self._resume_local(base, path))
      elif status_type == "node_status":
        if status.get("status", "").startswith("start_"):
          self.topology.active_node_id = status.get("node_id")
          base = status.get("base_shard") or {}
          if self.topology_viz is not None and base.get("n_layers"):
            # The active model's REAL depth drives the displayed layer
            # ranges (VERDICT r3 weak #5: a hardcoded 32 was wrong for
            # every other model).
            self.topology_viz.update_model(base.get("model_id"), base.get("n_layers"))
          # Adopt the origin's trace context before any tensor hop arrives so
          # even peers that only observe the request join its trace.
          rid = status.get("request_id")
          tp = status.get("traceparent")
          if rid and tp and rid not in self._request_trace_ctx:
            ctx = TraceContext.from_traceparent(tp)
            if ctx is not None:
              self._request_trace_ctx[rid] = ctx
        elif status.get("status", "").startswith("end_"):
          if status.get("node_id") == self.topology.active_node_id:
            self.topology.active_node_id = None
      if self.topology_viz is not None:
        self.topology_viz.update_visualization(self.topology, self.partitioning_strategy.partition(self.topology), self.id)
    except Exception as e:
      if DEBUG >= 2:
        print(f"on_node_status error: {e!r}")

  # ------------------------------------------------------------ inference

  async def process_prompt(self, base_shard: Shard, prompt: str, request_id: Optional[str] = None,
                           traceparent: Optional[str] = None, max_tokens: Optional[int] = None,
                           images: Optional[List[np.ndarray]] = None,
                           temperature: Optional[float] = None,
                           top_p: Optional[float] = None,
                           sampling: Optional[dict] = None,
                           ring_map: Optional[list] = None,
                           deadline: Optional[float] = None) -> None:
    if request_id is None:
      request_id = str(uuid.uuid4())
    if request_id not in self._request_deadline:
      # A forwarded prompt carries the origin's REMAINING budget; an origin
      # request starts a fresh one from the node knob.
      if deadline is not None:
        self._request_deadline[request_id] = time.monotonic() + max(0.0, float(deadline))
      elif self.request_deadline_s > 0:
        self._request_deadline[request_id] = time.monotonic() + self.request_deadline_s
    self._request_started.setdefault(request_id, time.monotonic())
    self.flight.record("request.admitted", request_id, model=base_shard.model_id,
                       origin=traceparent is None)
    self._note_progress(request_id)
    if traceparent is None:
      # Test/soak-only latency tap: injector rules matching rpc
      # "ProcessPrompt" apply at the ORIGIN, after the request's first-touch
      # clock is stamped — the gray-failure shape for a SINGLE-node replica
      # where no peer hop exists to delay. A delay here lands in this node's
      # own TTFT/e2e SLO histograms (so its burn-rate alerts fire exactly
      # like a real slowdown) while /healthcheck stays green — the PR 9
      # delayed-but-health-green scenario the router must act on. With no
      # injector installed this costs one function call per origin request.
      # Gated on a rule that EXPLICITLY names this rpc: wildcard (rpc-less)
      # rules keep their historical peer-handle-boundary semantics and
      # never have their nth/times budget consumed at the origin. (A spec
      # mixing an explicit ProcessPrompt rule with wildcard rules shares
      # one injector, so the wildcard rules' counters do advance on origin
      # taps — name the rpc on both when that matters.)
      from xotorch_tpu.networking import faults
      inj = faults.active()
      if inj is not None and any(r.rpc == "ProcessPrompt" for r in inj.rules):
        try:
          await inj.apply("ProcessPrompt", None)
        except faults.TransientHopError as e:
          await self._abort_request(request_id, f"injected fault on {self.id}: {e}")
          return
    if ring_map:
      # Forwarded prompt: route by the SENDER's pinned map, not our own
      # (possibly lagging) partition view — see RING_MAP_KEY.
      if request_id not in self._request_ring_map:
        self._set_ring_map(request_id, ring_map)
    else:
      self._pin_ring_map(base_shard, request_id)
    shard = self.get_current_shard(base_shard, request_id=request_id)
    if max_tokens is not None:
      # Per-request completion cap (OpenAI max_tokens); the node-wide
      # max_generate_tokens stays the hard ceiling.
      self._request_max_tokens[request_id] = self._clamp_max_tokens(max_tokens)
    if temperature is not None:
      # Per-request sampling temperature (OpenAI temperature); the node
      # default applies only when the request doesn't specify one.
      self._request_temp[request_id] = max(0.0, float(temperature))
    if top_p is not None:
      self._request_top_p[request_id] = min(1.0, max(0.0, float(top_p)))
    if sampling:
      # OpenAI extras (seed / logit_bias / penalties), validated at the API.
      self._request_sampling[request_id] = dict(sampling)
    start_ns = time.perf_counter_ns()
    if traceparent is None:
      # Count only origin requests: a forwarded prompt re-enters process_prompt
      # on the partition-0 owner and would double the cluster-wide sum.
      self.metrics.requests_total.inc()
      if self.anatomy.enabled:
        # Only the origin assembles anatomy: it holds the rolled-up trace.
        self._anatomy_origin[request_id] = None
        self._anatomy_origin.move_to_end(request_id)
        while len(self._anatomy_origin) > 512:
          self._anatomy_origin.popitem(last=False)
    # A forwarded prompt carries the origin node's trace context; joining it
    # keeps one trace per request across the ring (reference tracing.py:36-70).
    parent_ctx = TraceContext.from_traceparent(traceparent)
    with self.tracer.start_span(
      "process_prompt" if parent_ctx is None else "process_prompt.forwarded",
      parent=parent_ctx,
      attributes={"request.id": request_id, "model.id": base_shard.model_id},
    ) as span:
      # The request's root span context rides the status bus + tensor hops so
      # every peer's hop spans join the same trace (reference tracing.py:36-70).
      self._request_trace_ctx[request_id] = span.context()
      self._spawn(self.broadcast_opaque_status(request_id, json.dumps({
        "type": "node_status", "node_id": self.id, "status": "start_process_prompt",
        "base_shard": base_shard.to_dict(), "shard": shard.to_dict(),
        "prompt": prompt, "request_id": request_id,
        "traceparent": span.context().traceparent(),
      })))
      try:
        await self._process_prompt(base_shard, prompt, request_id, images)
      except CacheExhausted as e:
        # Prefill overflow: the prompt itself doesn't fit the KV budget. If
        # any tokens were already produced, end as a normal truncated
        # completion (the decode side's path); a pure-prefill overflow is a
        # client error the API answers with 400 context_length_exceeded —
        # never a 500 (ADVICE r1 (d); ref chatgpt_api.py:357-438 semantics).
        tokens, _ = self.buffered_token_output.get(request_id, ([], False))
        if tokens:
          await self._finish_as_length(request_id)
        else:
          if DEBUG >= 1:
            print(f"[{request_id}] prompt exceeds cache: {e}")
          await self._abort_request(request_id, f"context_length_exceeded: {e}")
      except Exception as e:
        print(f"Error processing prompt [{request_id}]: {e!r}")
        if DEBUG >= 2:
          import traceback
          traceback.print_exc()
        await self._abort_request(request_id, f"prompt processing failed on {self.id}: {e!r}")
    self._spawn(self.broadcast_opaque_status(request_id, json.dumps({
      "type": "node_status", "node_id": self.id, "status": "end_process_prompt",
      "request_id": request_id, "elapsed_time_ns": time.perf_counter_ns() - start_ns,
    })))

  async def _process_prompt(self, base_shard: Shard, prompt: str, request_id: str,
                            images: Optional[List[np.ndarray]] = None) -> None:
    shard = self.get_current_shard(base_shard, request_id=request_id)
    if not shard.is_first_layer:
      # Not our turn: hand the prompt to the partition-0 owner and stop.
      await self.forward_prompt(base_shard, prompt, request_id, 0, images)
      return
    # In a multi-partition ring the EOS/max decision is made by the
    # last-layer peer; forward_prompt carries the cap there (see below).
    self.outstanding_requests[request_id] = "processing prompt"
    self.metrics.active_requests.set(len(self.outstanding_requests))
    sampler = getattr(self.inference_engine, "infer_sample_tensor", None)
    if shard.is_last_layer and sampler is not None and not images:
      # Single-partition text prompt: prefill + on-device sampling in one
      # engine call — the host never sees the prompt's logits.
      tokens = await self.inference_engine.encode(shard, prompt)
      if self.speculate_tokens > 0:
        self._request_prompt_tokens[request_id] = [int(t) for t in np.asarray(tokens).reshape(-1)]
      token, _ = await sampler(
        request_id, shard, np.asarray(tokens).reshape(1, -1),
        temp=self._temp_for(request_id), top_k=self.default_sample_top_k,
        top_p=self._top_p_for(request_id),
        **self._sampling_kwargs(request_id),
      )
      await self.process_sampled_token(base_shard, int(token), request_id, None)
      return
    result, inference_state = await self.inference_engine.infer_prompt(
      request_id, shard, prompt, images=images,
      **self._keep_on_device_kwargs(shard, request_id),
    )
    if (self.speculate_tokens > 0 and not shard.is_last_layer and not images
        and self._inprocess_chain(base_shard, request_id) is not None):
      # Ship the prompt ids to the sampler peer once (first hop's state):
      # prompt-lookup drafting needs tokens, and mid-ring hops are hidden
      # states only. Only for co-located chains — the fused ring (the only
      # consumer of ring speculation) requires them, and a network ring
      # would pay the wire bytes for nothing. The extra tokenize is the
      # price of keeping engine.infer_prompt's one-call contract.
      try:
        toks = await self.inference_engine.encode(shard, prompt)
        inference_state = {**(inference_state or {}),
                           PROMPT_TOKENS_KEY: [int(t) for t in np.asarray(toks).reshape(-1)]}
      except Exception as e:
        # Speculation degrades to output-only drafting; the request itself
        # is unaffected, but log why draft acceptance just dropped.
        if DEBUG >= 1:
          print(f"[{request_id}] prompt tokenize for speculation failed: {e!r}")
    await self.process_inference_result(base_shard, result, request_id, inference_state)

  async def process_tensor(self, base_shard: Shard, tensor: np.ndarray, request_id: Optional[str] = None,
                           inference_state: Optional[dict] = None) -> None:
    if request_id is None:
      request_id = str(uuid.uuid4())
    if inference_state and request_id not in self._request_ring_map:
      m = inference_state.get(RING_MAP_KEY)
      if m:
        self._set_ring_map(request_id, m)
    shard = self.get_current_shard(base_shard, request_id=request_id)
    start_ns = time.perf_counter_ns()
    self.outstanding_requests[request_id] = "processing tensor"
    self.metrics.active_requests.set(len(self.outstanding_requests))
    self.metrics.tensor_hops_total.inc()
    self._request_started.setdefault(request_id, time.monotonic())
    self.flight.record("hop.recv", request_id,
                       layers=f"{shard.start_layer}-{shard.end_layer}")
    self._note_progress(request_id)
    if inference_state and request_id not in self._request_deadline:
      d = inference_state.get(DEADLINE_KEY)
      if d is not None:
        self._request_deadline[request_id] = time.monotonic() + max(0.0, float(d))
        self.start_watchdog()  # a hop-carried deadline must be enforced HERE too
    # Join the request's trace: the traceparent rides the inference_state
    # side-channel across peers (W3C propagation, reference tracing.py:36-70).
    ctx = self._request_trace_ctx.get(request_id)
    if ctx is None and inference_state:
      ctx = TraceContext.from_traceparent(inference_state.get(TRACEPARENT_KEY))
      if ctx is not None:
        self._request_trace_ctx[request_id] = ctx
    if inference_state and request_id not in self._request_max_tokens:
      cap = inference_state.get(MAX_TOKENS_KEY)
      if cap is not None:
        self._request_max_tokens[request_id] = self._clamp_max_tokens(cap)
    if inference_state and request_id not in self._request_temp:
      t = inference_state.get(TEMP_KEY)
      if t is not None:
        self._request_temp[request_id] = max(0.0, float(t))
    if inference_state and request_id not in self._request_top_p:
      p = inference_state.get(TOP_P_KEY)
      if p is not None:
        self._request_top_p[request_id] = min(1.0, max(0.0, float(p)))
    if inference_state and request_id not in self._request_sampling:
      s = inference_state.get(SAMPLING_KEY)
      if s:
        self._request_sampling[request_id] = dict(s)
    if inference_state and request_id not in self._request_prompt_tokens:
      # Only the SAMPLER (last-layer peer) consumes the prompt ids — a
      # mid-ring node on a 3+-partition ring must forward them untouched or
      # the drafting peer never sees them.
      if shard.is_last_layer:
        pt = inference_state.pop(PROMPT_TOKENS_KEY, None)  # consume: no more hops need it
        if pt:
          self._request_prompt_tokens[request_id] = [int(t) for t in pt]
    try:
      sampler = getattr(self.inference_engine, "infer_sample_tensor", None)
      fuse_sample = shard.is_last_layer and sampler is not None
      with self.tracer.start_span(
        "process_tensor", parent=ctx,
        attributes={"request.id": request_id, "shard.start": shard.start_layer, "shard.end": shard.end_layer},
      ):
        if fuse_sample:
          # Last-layer hop: forward + on-device sampling in one dispatch —
          # only the sampled token int crosses to the host, not the
          # [1, 1, vocab] fp32 logits (VERDICT r1 weak #3).
          token, inference_state = await sampler(
            request_id, shard, tensor, temp=self._temp_for(request_id),
            top_k=self.default_sample_top_k, inference_state=inference_state,
            top_p=self._top_p_for(request_id),
            **self._sampling_kwargs(request_id),
          )
        else:
          result, inference_state = await self.inference_engine.infer_tensor(
            request_id, shard, tensor, inference_state,
            **self._keep_on_device_kwargs(shard, request_id),
          )
      self.metrics.hop_latency.observe((time.perf_counter_ns() - start_ns) / 1e9)
      if fuse_sample:
        await self.process_sampled_token(base_shard, int(token), request_id, inference_state)
      else:
        await self.process_inference_result(base_shard, result, request_id, inference_state)
    except CacheExhausted as e:
      # The KV cache is full: the tokens so far are a valid, truncated
      # completion — end as a normal "length" finish, not an error.
      if DEBUG >= 1:
        print(f"[{request_id}] cache exhausted, finishing as length: {e}")
      await self._finish_as_length(request_id)
    except Exception as e:
      print(f"Error processing tensor for shard {shard}: {e!r}")
      if DEBUG >= 2:
        import traceback
        traceback.print_exc()
      await self._abort_request(request_id, f"tensor hop failed on {self.id} ({shard}): {e!r}")
    finally:
      if DEBUG >= 3:
        print(f"process_tensor elapsed {(time.perf_counter_ns()-start_ns)/1e6:.1f}ms")

  async def _abort_request(self, request_id: str, error: str) -> None:
    """Terminate a request after a hop error: release local state AND tell
    every peer it finished, so mid-ring nodes (which only learn request
    lifecycles from the finished-result broadcast) don't leak bookkeeping or
    KV caches for a request that will never complete. The reference simply
    loses in-flight requests on failure (SURVEY §5); broadcasting a finish
    also unblocks any API client waiting on the token stream. The error
    string rides the broadcast so API nodes surface a real error instead of
    an empty successful completion."""
    self.record_request_error(request_id, error)
    self.metrics.requests_failed_total.inc()
    # Freeze the request's flight timeline BEFORE cleanup churns the ring:
    # watchdog aborts, blown deadlines, and hop errors each become a
    # replayable /v1/debug/flight snapshot instead of one log line.
    self.flight.record("request.aborted", request_id, error=error[:200])
    self.flight.freeze(request_id, reason=error[:200])
    # Watchdog/deadline aborts can fire while the request's driving task is
    # still alive (a hung engine call, a loop awaiting a dead peer): the
    # cancel flag makes any late-completing local work stop at its next
    # boundary instead of resurrecting popped state.
    self._mark_cancelled(request_id)
    tokens, _ = self.buffered_token_output.get(request_id, ([], False))
    self.trigger_on_token_callbacks(request_id, tokens, True)
    try:
      await self.broadcast_result(request_id, tokens, True, error=error)
    except Exception as e:
      # Abort-path broadcast: peers that answered are cleaned up, the dead
      # one is why we're here — local finish below must still run.
      if DEBUG >= 1:
        print(f"[{request_id}] abort broadcast partially failed: {e!r}")
    await self._finish_generation(request_id)

  async def cancel_request(self, request_id: str) -> None:
    """Client-initiated graceful stop (OpenAI stop sequences, disconnects):
    end the request with the tokens produced so far — no error. Takes effect
    between fused chunks / sampled tokens on THIS node (the sampler in
    single-partition serving); a multi-partition ring's other peers finish
    via the resulting broadcast."""
    if request_id not in self.outstanding_requests and request_id not in self.buffered_token_output:
      return  # already finished (or never seen here) — idempotent
    self._mark_cancelled(request_id)
    tokens, _ = self.buffered_token_output.get(request_id, ([], False))
    self.buffered_token_output[request_id] = (tokens, True)
    self.trigger_on_token_callbacks(request_id, tokens, True)
    self._spawn(self.broadcast_result(request_id, [], True, total_len=len(tokens), full_ref=tokens))
    # Final cleanup happens when the driving loop observes the flag at its
    # next boundary (or when the ring's finished broadcast arrives); the
    # flag itself ages out of the bounded LRU, so no cleanup races it.

  def _mark_cancelled(self, request_id: str) -> None:
    self._cancelled[request_id] = None
    self._cancelled.move_to_end(request_id)
    while len(self._cancelled) > 256:
      self._cancelled.popitem(last=False)

  async def _finish_as_length(self, request_id: str) -> None:
    """End a request gracefully with whatever tokens it produced (used when
    the KV cache fills before EOS/cap — the OpenAI 'length' outcome)."""
    tokens, _ = self.buffered_token_output.get(request_id, ([], False))
    self.buffered_token_output[request_id] = (tokens, True)
    self.trigger_on_token_callbacks(request_id, tokens, True)
    try:
      await self.broadcast_result(request_id, tokens, True)
    except Exception as e:
      if DEBUG >= 1:
        print(f"[{request_id}] length-finish broadcast partially failed: {e!r}")
    await self._finish_generation(request_id)

  def record_request_error(self, request_id: str, error: str) -> None:
    """Remember why a request died (bounded; consumed by the API when it
    reports the failure to the client)."""
    self.request_errors[request_id] = error
    while len(self.request_errors) > 256:
      self.request_errors.popitem(last=False)

  async def process_inference_result(self, base_shard: Shard, result: np.ndarray, request_id: str,
                                     inference_state: Optional[dict] = None) -> None:
    """The token-ring decode driver (parity node.py:109-147)."""
    shard = self.get_current_shard(base_shard, request_id=request_id)
    if not shard.is_last_layer:
      # Mid-ring: forward the hidden state (bf16 numpy) to the next partition.
      self.outstanding_requests[request_id] = "waiting"
      await self.forward_tensor(base_shard, result, request_id,
                                self.get_partition_index(offset=1, request_id=request_id),
                                inference_state)
      return

    # Last layer: sample, then continue via the shared token path. Engines
    # with the extras-aware host sampler get the request's sampling config
    # (seed/bias/min_p/logprob recording) — the vision first-token path and
    # fused decode then agree on sampling rules AND logprob entry counts.
    sample_kwargs = {}
    if self._host_sample_accepts_extras():
      n_sampled = len(self.buffered_token_output.get(request_id, ((), 0))[0])
      sample_kwargs = {"request_id": request_id,
                       "sampling": self._request_sampling.get(request_id),
                       "sample_index": n_sampled}
    token = await self.inference_engine.sample(
      result, temp=self._temp_for(request_id), top_k=self.default_sample_top_k,
      top_p=self._top_p_for(request_id), **sample_kwargs,
    )
    await self.process_sampled_token(
      base_shard, int(np.asarray(token).reshape(-1)[0]), request_id, inference_state
    )

  async def process_sampled_token(self, base_shard: Shard, token_int: int, request_id: str,
                                  inference_state: Optional[dict] = None) -> None:
    """Buffer/broadcast a freshly sampled token and either stop (EOS/cap) or
    keep the ring turning. Shared by the sample-on-host path
    (process_inference_result) and the fused on-device sampler."""
    shard = self.get_current_shard(base_shard, request_id=request_id)
    if request_id not in self.buffered_token_output:
      self.buffered_token_output[request_id] = ([], False)
    buffered, _ = self.buffered_token_output[request_id]

    if DEBUG >= 2:
      print(f"[{request_id}] token {token_int} ({len(buffered)+1} so far)")
    if self._ingest_sampled_tokens(request_id, [token_int], buffered, base_shard):
      await self._finish_generation(request_id)
      return

    # Fused fast path: when this single partition owns the whole model, decode
    # K tokens per device dispatch (forward + on-device sampling under one
    # lax.scan, models/generate.py) instead of paying a host round-trip per
    # token. Runs DETACHED so the awaited process_prompt chain returns after
    # the first token and API streaming starts immediately (the per-token
    # path gets the same property from forward_tensor's create_task).
    if self.decode_chunk_size > 1:
      if shard.is_first_layer:
        gen = getattr(self.inference_engine, "generate_chunk", None)
        if gen is not None:
          self._spawn(
            self._fused_decode_loop(base_shard, shard, request_id, buffered, inference_state, gen)
          )
          return
      elif shard.is_last_layer:
        # Multi-partition ring whose every partition is co-located in THIS
        # process: fold the whole chain into one fused executable per chunk
        # (engine.generate_chunk_ring) instead of one hop per partition per
        # token — the ring decodes at the fused rate. The sampler peer (last
        # layer) drives, same as it drives the per-token ring.
        ring = self._ring_fused_gen(base_shard, request_id)
        if ring is not None:
          ring_gen, ring_verify = ring
          self._spawn(
            self._fused_decode_loop(base_shard, shard, request_id, buffered, inference_state,
                                    ring_gen, allow_speculation=False,
                                    ring_verify=ring_verify)
          )
          return

    await self._forward_next_token(base_shard, request_id, buffered, inference_state)

  def _ring_fused_gen(self, base_shard: Shard, request_id: str):
    """A generate_chunk-shaped callable that decodes the WHOLE multi-partition
    ring in fused chunks, or None when the ring doesn't qualify: every
    partition must be served by a ring-fusion-capable engine living in this
    process (self or an in-process peer — the same co-location the
    device-resident hop path keys off), and the request must be a plain one
    (sampling extras keep the per-token path, whose last-layer sampler
    applies them). The chain binds the CURRENT partition table; if membership
    changes mid-generation the engine fails loudly (RequestStateLost) rather
    than decode against remapped shards."""
    if self._request_sampling.get(request_id):
      return None
    ring = getattr(self.inference_engine, "generate_chunk_ring", None)
    if ring is None:
      return None
    chain = self._inprocess_chain(base_shard, request_id)
    if chain is None:
      return None

    async def gen(rid, _shard, prev_token, num_tokens, temp, top_k, top_p=0.0, next_size=None):
      return await ring(rid, chain, prev_token, num_tokens, temp=temp, top_k=top_k,
                        top_p=top_p, next_size=next_size)

    ring_verify_impl = getattr(self.inference_engine, "verify_draft_ring", None)
    verify = None
    if ring_verify_impl is not None:
      async def verify(rid, _shard, prev_token, draft, _impl=ring_verify_impl):
        return await _impl(rid, chain, prev_token, draft)

    return gen, verify

  def _inprocess_chain(self, base_shard: Shard, request_id: Optional[str] = None):
    """The ring-ordered [(engine, shard)] chain when EVERY partition is
    served by a ring-fusion-capable engine in THIS process (self or an
    in-process peer), else None. Shared by the fused-ring dispatch and the
    prompt-token side-channel gating. Ring-mapped requests bind THEIR
    pinned partition table, not the live view."""
    entries = self._ring_entries(request_id)
    if entries is not None:
      node_ids = [n for n, _, _ in entries]
    else:
      try:
        node_ids = [p.node_id for p in self.partitioning_strategy.partition(self.topology)]
      except Exception:
        return None
    if len(node_ids) < 2:
      return None
    chain = []
    for i, node_id in enumerate(node_ids):
      if node_id == self.id:
        eng = self.inference_engine
      else:
        peer = next((p for p in self.peers if p.id() == node_id), None)
        node = getattr(peer, "node", None)  # InProcessPeerHandle only
        eng = getattr(node, "inference_engine", None) if node is not None else None
      if eng is None or not getattr(eng, "supports_ring_fusion", False):
        return None
      chain.append((eng, self.get_current_shard(base_shard, i, request_id=request_id)))
    return chain

  async def _fused_decode_loop(self, base_shard: Shard, shard: Shard, request_id: str,
                               buffered: List[int], inference_state: Optional[dict], gen,
                               allow_speculation: bool = True, ring_verify=None) -> None:
    """Chunked decode until EOS/cap; EOS/max checks happen between chunks and
    surplus tokens after EOS inside a chunk are discarded.
    allow_speculation=False + ring_verify for the fused-RING path: the
    single-shard verify_draft executable must not interleave with
    multi-segment lockstep state, but the ring has its own composite
    verifier (engine.verify_draft_ring) with the same contract."""
    s = self._request_sampling.get(request_id)
    if s and ring_verify is None:
      # A prefill that sampled on the host (multimodal) never bound the
      # request's extras to its decode state — bind them now so the fused
      # chunks apply bias/seed and record logprobs like any text request.
      attach = getattr(self.inference_engine, "attach_sampling", None)
      if attach is not None:
        try:
          await attach(shard, request_id, s, sampled_tokens=tuple(buffered))
        except Exception as e:
          if DEBUG >= 1:
            print(f"[{request_id}] attach_sampling failed: {e!r}")
    # Speculation verifies drafts by plain greedy argmax — requests whose
    # extras RESHAPE the distribution (penalties/bias change even greedy
    # argmax) must not speculate or the verified tokens would ignore them;
    # logprobs requests must not either (the verify path samples nothing,
    # so it would record no logprob entries for accepted drafts). A seed
    # alone is irrelevant at temp==0 (greedy is already deterministic), so
    # seed-only requests keep the speculation fast path.
    # min_p is exempt like seed: speculation only runs at temp==0, where
    # the argmax always satisfies the floor (p_max >= min_p * p_max) — the
    # mask provably cannot change greedy output.
    reshaping = set(self._request_sampling.get(request_id, ())) & {
      "presence_penalty", "frequency_penalty", "logit_bias", "logprobs"}
    spec_wanted = (self.speculate_tokens > 0 and self._temp_for(request_id) == 0
                   and not reshaping)
    if not spec_wanted:
      verify = None
    elif ring_verify is not None:
      verify = ring_verify
    elif allow_speculation:
      verify = getattr(self.inference_engine, "verify_draft", None)
    else:
      verify = None
    # Persistent draft context: prompt + generated tokens, appended as they
    # arrive (never rebuilt — a 32k prompt must not be re-copied per round).
    spec_context = (list(self._request_prompt_tokens.get(request_id, ())) + list(buffered)
                    if verify is not None else [])
    spec_strikes = 0
    try:
      self.outstanding_requests[request_id] = "generating"
      size = self.decode_chunk_size
      while True:
        if request_id in self._cancelled:
          await self._finish_generation(request_id)
          return
        # Never compute far past the request cap: shrink the last chunk to
        # the next power of two covering what the cap still allows.
        limit = self._request_max_tokens.get(request_id, self.max_generate_tokens)
        remaining = max(1, limit - len(buffered))
        if verify is not None:
          # Speculation drafting (greedy only): a draft MODEL when
          # configured (engine.draft_tokens — proposes every round), else
          # prompt-lookup (the continuation of the last n-gram's previous
          # occurrence in prompt+output — model-free, repeat-heavy text
          # only). Either way ONE verify forward yields up to draft+1
          # tokens, each exactly what sequential greedy decode would
          # produce (engine.verify_draft).
          k = min(self.speculate_tokens, remaining)
          drafter = (getattr(self.inference_engine, "draft_tokens", None)
                     if self.draft_model else None)
          if drafter is not None and len(self.outstanding_requests) > 1:
            # Under concurrent load the batcher's shared weight read already
            # amortizes decode; per-request draft forwards would serialize
            # EXTRA executor dispatches — the same measured principle that
            # disables batch-chunk speculation (PERF.md r3: 279 vs 357).
            # Prompt-lookup below stays (its draft is host-side and free).
            drafter = None
          draft = list(await drafter(request_id, spec_context, k)) if drafter else []
          if not draft:
            # Prompt-lookup stays the fallback: the draft model may be
            # unavailable (failed load self-disables it engine-side) or out
            # of cache capacity — n-gram speculation still applies.
            draft = _lookup_draft(spec_context, k)
          if len(draft) >= 2:
            accepted = await verify(request_id, shard, buffered[-1], draft)
            if accepted:
              # Back-off: repeated full rejections (bonus-only returns) mean
              # the text repeats n-grams with divergent continuations — each
              # round would pay a whole verify forward for ONE token, far
              # below the fused-chunk baseline. Stop speculating for this
              # request after two straight misses.
              if len(accepted) == 1:
                spec_strikes += 1
                if spec_strikes >= 2:
                  verify = None
              else:
                spec_strikes = 0
              spec_context.extend(accepted)
              if self._ingest_sampled_tokens(request_id, accepted, buffered, base_shard):
                await self._finish_generation(request_id)
                return
              continue
        this_size = min(size, 1 << (remaining - 1).bit_length())
        # Next-chunk size hint for the engine's speculative dispatch: what
        # THIS loop will ask for next if no EOS lands in this chunk — the
        # ladder's next rung clipped to the cap that will remain. The engine
        # overlaps that chunk with our EOS scan; a misprediction (EOS, cap)
        # is a free rollback on its side.
        rem_after = remaining - this_size
        next_hint = (min(min(size * 2, self.max_decode_chunk_size),
                         1 << (rem_after - 1).bit_length())
                     if rem_after >= 1 else None)
        chunk = await gen(
          request_id, shard, buffered[-1], this_size,
          temp=self._temp_for(request_id), top_k=self.default_sample_top_k,
          top_p=self._top_p_for(request_id), next_size=next_hint,
        )
        if chunk is None:
          # Fast path unavailable (cache nearly full, shard changed): fall
          # back to the per-token ring.
          await self._forward_next_token(base_shard, request_id, buffered, inference_state)
          return
        new_tokens = chunk.reshape(-1).tolist()
        if verify is not None:
          spec_context.extend(int(t) for t in new_tokens)
        if self._ingest_sampled_tokens(request_id, new_tokens, buffered, base_shard):
          await self._finish_generation(request_id)
          return
        size = min(size * 2, self.max_decode_chunk_size)
    except CacheExhausted as e:
      if DEBUG >= 1:
        print(f"[{request_id}] cache exhausted, finishing as length: {e}")
      await self._finish_as_length(request_id)
    except Exception as e:
      print(f"Error in fused decode for [{request_id}]: {e!r}")
      if DEBUG >= 2:
        import traceback
        traceback.print_exc()
      await self._abort_request(request_id, f"fused decode failed on {self.id}: {e!r}")

  async def _forward_next_token(self, base_shard: Shard, request_id: str,
                                buffered: List[int], inference_state: Optional[dict]) -> None:
    # Feed the sampled token back to partition 0 for the next decode step.
    self.outstanding_requests[request_id] = "waiting"
    await self.forward_tensor(
      base_shard, np.asarray([[buffered[-1]]], dtype=np.int64), request_id,
      self.get_partition_index_of_first_layer(), inference_state,
    )

  def _ingest_sampled_tokens(self, request_id: str, new_tokens: List[int], buffered: List[int],
                             base_shard: Optional[Shard] = None) -> bool:
    """Shared per-token accounting for the per-token ring and the fused chunk
    path: append to the request buffer (stopping at EOS or the request cap),
    update metrics/trace, fire callbacks, and broadcast. Returns finished."""
    if request_id in self._cancelled:
      # Tokens computed after a client cancel are discarded; report finished
      # so the driving loop stops at this boundary.
      return True
    eos = self._request_eos.get(request_id)
    if eos is None:
      eos = self._eos_token_ids(base_shard, request_id)
      if eos:
        # Only cache a RESOLVED set: an empty result may mean the tokenizer
        # wasn't ready yet, and freezing that for the request's lifetime
        # would disable EOS detection entirely.
        self._request_eos[request_id] = eos
    limit = self._request_max_tokens.get(request_id, self.max_generate_tokens)
    trace_ctx = self._request_trace_ctx.get(request_id)
    now = time.monotonic()
    self._note_progress(request_id)
    last = self._last_token_time.get(request_id)
    appended = 0
    finished = False
    for t in new_tokens:
      buffered.append(int(t))
      appended += 1
      self.metrics.tokens_total.inc()
      self.tracer.record_token(request_id, trace_ctx)
      if int(t) in eos or len(buffered) >= limit:
        finished = True
        break
    if last is None and appended:
      # First sampled token on this node: the TTFT SLO observation, measured
      # from this node's first touch of the request (prompt/hop arrival).
      started = self._request_started.get(request_id)
      if started is not None:
        self.metrics.ttft.observe(now - started)
    if last is not None and appended:
      self.metrics.token_latency.observe((now - last) / appended)
    self._last_token_time[request_id] = now
    self.buffered_token_output[request_id] = (buffered, finished)
    self.trigger_on_token_callbacks(request_id, buffered, finished)
    # Delta broadcast: only the newly appended tokens ride the wire —
    # O(1) bytes/token instead of the reference's full-list-every-token
    # O(T^2) fan-out (node.py:580-591; SURVEY §2.5 "known-inefficient
    # design to replace"). total_len lets receivers detect gaps and ask for
    # a one-shot full reconciliation (broadcast_result handles the resend).
    delta = buffered[len(buffered) - appended:] if appended else []
    # full_ref is the LIVE buffer object: by the time a gapped peer asks for
    # reconciliation, buffered_token_output may already be popped by
    # _finish_generation — the list object itself stays complete.
    self._spawn(
      self.broadcast_result(request_id, delta, finished, total_len=len(buffered),
                            full_ref=buffered)
    )
    return finished

  async def _finish_generation(self, request_id: str) -> None:
    self.finish_request_state(request_id)
    self.buffered_token_output.pop(request_id, None)  # callbacks/broadcast hold the list
    clear = getattr(self.inference_engine, "clear_request", None)
    if clear is not None:
      await clear(request_id)

  def _temp_for(self, request_id: str) -> float:
    """The request's sampling temperature, falling back to the node default
    (read at SAMPLE time, so a temp that arrived via the tensor
    side-channel after the prompt hop still applies)."""
    return self._request_temp.get(request_id, self.default_sample_temp)

  def _top_p_for(self, request_id: str) -> float:
    """The request's nucleus-sampling threshold; 0.0 (and the OpenAI
    default 1.0, normalised at the API) means disabled."""
    return self._request_top_p.get(request_id, 0.0)

  def _sampling_kwargs(self, request_id: str) -> dict:
    """Extra kwargs for engines whose fused sampler supports the OpenAI
    extras (seed/logit_bias/penalties). Empty for plain requests AND for
    engines whose infer_sample_tensor signature never learned the `sampling`
    kwarg — real signature inspection (cached), so an extras request against
    an older engine degrades to plain sampling instead of TypeError-aborting."""
    s = self._request_sampling.get(request_id)
    if not s:
      return {}
    if self._engine_accepts_sampling is None:
      import inspect
      sampler = getattr(self.inference_engine, "infer_sample_tensor", None)
      try:
        params = inspect.signature(sampler).parameters
        self._engine_accepts_sampling = (
          "sampling" in params
          or any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()))
      except (TypeError, ValueError):
        self._engine_accepts_sampling = False
    return {"sampling": s} if self._engine_accepts_sampling else {}

  def _host_sample_accepts_extras(self) -> bool:
    """Does engine.sample accept request_id/sampling? Same cached signature
    inspection as _sampling_kwargs, for the host sampling path."""
    if getattr(self, "_host_sample_extras", None) is None:
      import inspect
      try:
        params = inspect.signature(self.inference_engine.sample).parameters
        self._host_sample_extras = (
          "sampling" in params
          or any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()))
      except (TypeError, ValueError):
        self._host_sample_extras = False
    return self._host_sample_extras

  def pop_request_logprobs(self, request_id: str, n: Optional[int] = None) -> Optional[list]:
    """Drain the engine's recorded logprob entries for a request (OpenAI
    `logprobs`). None when the local engine recorded none — plain requests,
    engines without the feature, or rings where a REMOTE node samples (the
    token broadcast carries ids only; logprob reporting requires the API
    node to host the sampling shard)."""
    pop = getattr(self.inference_engine, "pop_logprobs", None)
    return pop(request_id, n) if pop is not None else None

  def _clamp_max_tokens(self, cap: Any) -> int:
    return max(1, min(int(cap), self.max_generate_tokens))

  def _eos_token_ids(self, base_shard: Optional[Shard] = None,
                     request_id: Optional[str] = None) -> Tuple[int, ...]:
    """EOS ids for the REQUEST's model. With per-model engine contexts, the
    engine's active tokenizer/cfg may belong to a different in-flight model —
    resolve per shard when the engine supports it, never from whichever
    model happens to be active. Ring-mapped requests resolve their PINNED
    shard (the engine context key), not the live view's."""
    per_shard = getattr(self.inference_engine, "eos_token_ids_for", None)
    if base_shard is not None and per_shard is not None:
      try:
        ids = per_shard(self.get_current_shard(base_shard, request_id=request_id))
        # Empty means "context not resident / tokenizer unresolved", not
        # "this model has no EOS" — fall through to the engine-level lookup
        # rather than silently disabling EOS detection.
        if ids:
          return ids
      except Exception as e:
        # Fall through to the engine-level tokenizer lookup below.
        if DEBUG >= 2:
          print(f"per-shard EOS lookup failed ({e!r}); using engine tokenizer")
    tokenizer = getattr(self.inference_engine, "tokenizer", None)
    eos = getattr(tokenizer, "eos_token_id", None) if tokenizer else None
    cfg = getattr(self.inference_engine, "cfg", None)
    from_cfg = tuple(getattr(cfg, "eos_token_ids", ()) or ()) if cfg else ()
    return tuple(e for e in ((eos,) if eos is not None else ()) + from_cfg)

  # -------------------------------------------------------------- routing

  def _set_ring_map(self, request_id: str, ring_map) -> None:
    """Record a request's pinned partition map (bounded LRU — an abandoned
    request must not leak its row forever; finish_request_state pops it on
    the normal path)."""
    rows = [(str(n), int(s), int(e)) for n, s, e in ring_map]
    self._request_ring_map[request_id] = rows
    self._request_ring_map.move_to_end(request_id)
    while len(self._request_ring_map) > 512:
      self._request_ring_map.popitem(last=False)

  def _ring_entries(self, request_id: Optional[str]):
    """The request's pinned [node_id, start, end] rows, or None when the
    request predates the map (old peer on the wire) / isn't ring-routed.
    Reads refresh the LRU: a long-lived streaming request must not lose its
    map to 512 newer requests and silently fall back to live-view routing."""
    if not request_id:
      return None
    rows = self._request_ring_map.get(request_id)
    if rows is not None:
      self._request_ring_map.move_to_end(request_id)
    return rows

  def _pin_ring_map(self, base_shard: Shard, request_id: str) -> None:
    """Originate a request's routing epoch from THIS node's current view.
    Called exactly once, by the node that first accepts the request."""
    if request_id in self._request_ring_map or not self.partitioning_strategy:
      return
    partitions = self.partitioning_strategy.partition(self.topology)
    shards = map_partitions_to_shards(partitions, base_shard.n_layers, base_shard.model_id)
    self._set_ring_map(request_id, [
      (p.node_id, s.start_layer, s.end_layer) for p, s in zip(partitions, shards)
    ])

  def get_partition_index(self, offset: int = 0, request_id: Optional[str] = None) -> int:
    entries = self._ring_entries(request_id)
    if entries is not None:
      current = next((i for i, (n, _, _) in enumerate(entries) if n == self.id), None)
      if current is None:
        raise ValueError(f"Node {self.id} is not in request {request_id}'s ring map")
      return (current + offset) % len(entries)
    if not self.partitioning_strategy:
      return 0
    partitions = self.partitioning_strategy.partition(self.topology)
    current = next((i for i, p in enumerate(partitions) if p.node_id == self.id), None)
    if current is None:
      raise ValueError(f"No partition found for node {self.id}")
    return (current + offset) % len(partitions)

  def get_partition_index_of_first_layer(self) -> int:
    # map_partitions_to_shards assigns layer 0 to partitions[0] by
    # construction, so the first-layer owner is always ring index 0 — in the
    # live view AND in any pinned ring map (rows preserve partition order).
    return 0

  def get_current_shard(self, base_shard: Shard, index: Optional[int] = None,
                        request_id: Optional[str] = None) -> Shard:
    entries = self._ring_entries(request_id)
    if entries is not None:
      if index is None:
        index = self.get_partition_index(request_id=request_id)
      _, start, end = entries[index]
      return Shard(base_shard.model_id, start, end, base_shard.n_layers)
    if index is None:
      index = self.get_partition_index()
    partitions = self.partitioning_strategy.partition(self.topology)
    shards = map_partitions_to_shards(partitions, base_shard.n_layers, base_shard.model_id)
    return shards[index]

  async def _peer_by_id(self, target_id: str):
    """Resolve a hop's peer handle, healing transient peer-set lag: the
    peer set is reconciled on a background cadence, and a hop can race a
    window where discovery knows the peer but self.peers briefly doesn't
    (a replaced handle whose connect timed out once, an admission that
    finished after the last reconcile). One on-demand reconcile turns that
    race into a served request instead of an abort; a peer that is GONE
    still fails (update_peers can't resurrect it) and keeps the abort
    semantics."""
    peer = next((p for p in self.peers if p.id() == target_id), None)
    if peer is not None:
      return peer
    try:
      await self.update_peers()
    except Exception as e:
      if DEBUG >= 2:
        print(f"on-demand peer reconcile failed: {e!r}")
    return next((p for p in self.peers if p.id() == target_id), None)

  def _ring_target_id(self, target_index: int, request_id: Optional[str]) -> str:
    entries = self._ring_entries(request_id)
    if entries is not None:
      return entries[target_index][0]
    return self.partitioning_strategy.partition(self.topology)[target_index].node_id

  async def forward_prompt(self, base_shard: Shard, prompt: str, request_id: str, target_index: int,
                           images: Optional[List[np.ndarray]] = None) -> None:
    if DEBUG >= 1:
      print(f"Forwarding prompt [{request_id}] to partition {target_index}")
    target_id = self._ring_target_id(target_index, request_id)
    next_shard = self.get_current_shard(base_shard, target_index, request_id=request_id)
    if target_id == self.id:
      await self._process_prompt(base_shard, prompt, request_id, images)
      return
    peer = await self._peer_by_id(target_id)
    if peer is None:
      raise ValueError(f"Peer for {target_index} ({target_id}) not found")
    ctx = self._request_trace_ctx.get(request_id)
    dl = self._request_deadline.get(request_id)
    await peer.send_prompt(next_shard, prompt, request_id,
                           traceparent=ctx.traceparent() if ctx else None,
                           max_tokens=self._request_max_tokens.get(request_id),
                           images=images,
                           temperature=self._request_temp.get(request_id),
                           top_p=self._request_top_p.get(request_id),
                           ring_map=self._ring_entries(request_id),
                           deadline=max(0.0, dl - time.monotonic()) if dl is not None else None)

  def _keep_on_device_kwargs(self, shard: Shard, request_id: Optional[str] = None) -> dict:
    """Engine kwargs for a mid-ring hop: request device-resident output when
    the engine supports it AND the next partition is co-located (self or an
    in-process peer — the fast path that keeps hidden states in HBM across
    the hop, VERDICT r2 #3). One partition computation, not three: this sits
    on the per-token hot path it exists to optimize."""
    if shard.is_last_layer or not getattr(self.inference_engine, "supports_device_io", False):
      return {}
    try:
      target_id = self._ring_target_id(
        self.get_partition_index(offset=1, request_id=request_id), request_id)
    except Exception:
      return {}
    if target_id == self.id:
      return {"keep_on_device": True}
    peer = next((p for p in self.peers if p.id() == target_id), None)
    if peer is not None and getattr(peer, "accepts_device_arrays", False):
      return {"keep_on_device": True}
    return {}

  async def forward_tensor(self, base_shard: Shard, tensor, request_id: str, target_index: int,
                           inference_state: Optional[dict] = None) -> None:
    target_id = self._ring_target_id(target_index, request_id)
    next_shard = self.get_current_shard(base_shard, target_index, request_id=request_id)
    # Inject the trace context so the receiving peer's hop span joins this
    # request's trace (rides the existing inference_state side-channel).
    ctx = self._request_trace_ctx.get(request_id)
    if ctx is not None:
      inference_state = {**(inference_state or {}), TRACEPARENT_KEY: ctx.traceparent()}
    ring_rows = self._ring_entries(request_id)
    if ring_rows is not None:
      inference_state = {**(inference_state or {}), RING_MAP_KEY: ring_rows}
    cap = self._request_max_tokens.get(request_id)
    if cap is not None:
      inference_state = {**(inference_state or {}), MAX_TOKENS_KEY: cap}
    t = self._request_temp.get(request_id)
    if t is not None:
      inference_state = {**(inference_state or {}), TEMP_KEY: t}
    p = self._request_top_p.get(request_id)
    if p is not None:
      inference_state = {**(inference_state or {}), TOP_P_KEY: p}
    s = self._request_sampling.get(request_id)
    if s is not None:
      inference_state = {**(inference_state or {}), SAMPLING_KEY: s}
    dl = self._request_deadline.get(request_id)
    if dl is not None:
      inference_state = {**(inference_state or {}), DEADLINE_KEY: max(0.0, dl - time.monotonic())}
    if target_id == self.id:
      # Schedule rather than await: a direct call would grow one coroutine
      # chain per token and blow the recursion limit on long generations.
      self._spawn(self.process_tensor(base_shard, tensor, request_id, inference_state))
      return
    peer = await self._peer_by_id(target_id)
    if peer is None:
      raise ValueError(f"Peer for {target_index} ({target_id}) not found")
    if not getattr(peer, "accepts_device_arrays", False) and not isinstance(tensor, np.ndarray):
      # Cross-host hop: the device array materialises to numpy HERE and only
      # here — the wire/codec path stays numpy-typed.
      tensor = np.asarray(tensor)
    await peer.send_tensor(next_shard, tensor, request_id, inference_state)

  # ------------------------------------------------------------- training

  async def enqueue_example(self, base_shard: Shard, example: np.ndarray, target: np.ndarray,
                            length: np.ndarray, train: bool = False,
                            request_id: Optional[str] = None) -> Tuple[float, Optional[np.ndarray]]:
    """Route an example to the partition-0 owner (parity node.py:210-228).
    Pins the example's ring map (RING_MAP_KEY) like a serving request: every
    peer must run the layer range THIS node's view assigns, or a peer whose
    gossip lags processes the example against the wrong partitioning — the
    observed failure was a peer running the FULL model for an example the
    origin had pipelined, silently applying its optimizer update to an
    orphaned context."""
    if request_id is None:
      request_id = str(uuid.uuid4())
    self._pin_ring_map(base_shard, request_id)
    shard = self.get_current_shard(base_shard, request_id=request_id)
    if shard.is_first_layer:
      return await self.process_example(base_shard, example, target, length, train, request_id)
    index = self.get_partition_index_of_first_layer()
    target_id = self._ring_target_id(index, request_id)
    peer = await self._peer_by_id(target_id)
    if peer is None:
      raise ValueError(f"No peer for first-layer partition {index}")
    try:
      result = await peer.send_example(
        self.get_current_shard(base_shard, index, request_id=request_id),
        example, target, length, train, request_id,
        ring_map=self._ring_entries(request_id))
    finally:
      # Training is strictly request/response: the pinned row is dead once
      # the example returns, and leaving it would churn the bounded LRU
      # under long training loops (evicting live SERVING requests' maps).
      self._request_ring_map.pop(request_id, None)
    if result is None:
      raise RuntimeError(f"Peer {target_id} returned no loss for example {request_id}")
    return result

  async def process_example(self, base_shard: Shard, example: np.ndarray, target: np.ndarray,
                            length: np.ndarray, train: bool = False,
                            request_id: Optional[str] = None,
                            ring_map: Optional[list] = None) -> Tuple[float, Optional[np.ndarray]]:
    """Run this shard's slice of a training/eval example; recurse down the
    ring and chain gradients back up (parity node.py:254-345)."""
    if request_id is None:
      request_id = str(uuid.uuid4())
    if ring_map and request_id not in self._request_ring_map:
      self._set_ring_map(request_id, ring_map)
    shard = self.get_current_shard(base_shard, request_id=request_id)
    start_ns = time.perf_counter_ns()
    status_kind = "train_example" if train else "eval_example"
    self._spawn(self.broadcast_opaque_status(request_id, json.dumps({
      "type": "node_status", "node_id": self.id, "status": f"start_{status_kind}",
      "request_id": request_id,
    })))
    try:
      if train:
        loss, grads = await self.inference_engine.train_example(
          request_id, shard, example, target, length,
          forward_fn=self._forward_example_fn(base_shard, request_id),
        )
        return loss, grads
      else:
        loss = await self.inference_engine.evaluate_example(
          request_id, shard, example, target, length,
          forward_fn=self._forward_example_fn(base_shard, request_id),
        )
        return loss, None
    finally:
      self._request_ring_map.pop(request_id, None)  # request/response: row is dead
      self._spawn(self.broadcast_opaque_status(request_id, json.dumps({
        "type": "node_status", "node_id": self.id, "status": f"end_{status_kind}",
        "request_id": request_id, "elapsed_time_ns": time.perf_counter_ns() - start_ns,
      })))

  def _forward_example_fn(self, base_shard: Shard, request_id: str):
    """Downstream hop for pipelined training: ships activations to the next
    partition, returns (loss, grad_wrt_activations)."""
    async def forward(activations: np.ndarray, target: np.ndarray, length: np.ndarray, train: bool):
      next_index = self.get_partition_index(offset=1, request_id=request_id)
      target_id = self._ring_target_id(next_index, request_id)
      next_shard = self.get_current_shard(base_shard, next_index, request_id=request_id)
      if target_id == self.id:
        return await self.process_example(base_shard, activations, target, length, train, request_id)
      peer = await self._peer_by_id(target_id)
      if peer is None:
        raise ValueError(f"No peer for partition {next_index}")
      result = await peer.send_example(next_shard, activations, target, length, train, request_id,
                                       ring_map=self._ring_entries(request_id))
      if result is None:
        raise RuntimeError(f"Peer {target_id} returned no loss for example {request_id}")
      return result
    return forward

  async def _resume_local(self, base_shard: Shard, path: str) -> None:
    try:
      shard = self.get_current_shard(base_shard)
      await self.inference_engine.load_checkpoint(shard, path)
      if DEBUG >= 1:
        print(f"Resumed {shard} from {path}")
    except Exception as e:
      print(f"Resume of {base_shard.model_id} from {path} failed on {self.id}: {e!r}")

  async def coordinate_resume(self, base_shard: Shard, path: str) -> None:
    """Restore a checkpoint across the WHOLE ring: load the local layer range
    and broadcast a resume_checkpoint status so every peer loads its own
    (the per-shard save files share one directory — coordinate_save naming).
    Completes the reference's parsed-but-dead --resume-checkpoint flag
    (ref main.py:82; engine leaf was a no-op, inference_engine.py:31-35)."""
    await self._resume_local(base_shard, path)
    await self.broadcast_opaque_status("", json.dumps({
      "type": "resume_checkpoint", "node_id": self.id,
      "base_shard": base_shard.to_dict(), "path": path,
    }))

  async def coordinate_save(self, base_shard: Shard, iteration: int, destination: str) -> None:
    """Ask every peer('s engine) to save its shard (parity node.py:230-252)."""
    shard = self.get_current_shard(base_shard)
    model = base_shard.model_id
    sid = f"{shard.start_layer}-{shard.end_layer}"
    self.checkpoints.setdefault(model, {})
    if self.checkpoints[model].get(sid) == iteration:
      return
    self.checkpoints[model][sid] = iteration
    path = f"{destination}/{model}/{sid}-{iteration}.safetensors"
    await self.inference_engine.save_checkpoint(shard, path)
    if DEBUG >= 1:
      print(f"Saved checkpoint {path}")

  # ------------------------------------------------------------- topology

  async def update_peers(self, wait_for_peers: int = 0) -> bool:
    """Reconcile the peer set against discovery (parity node.py:462-511).
    Serialized: the read-modify-write of self.peers spans awaits (connects/
    disconnects), and callers now include on-demand hop-time reconciles
    (_peer_by_id) racing the periodic loop — unsynchronized runs would
    clobber each other's peer-set assignment."""
    async with self._update_peers_lock:
      return await self._update_peers_locked(wait_for_peers)

  async def _update_peers_locked(self, wait_for_peers: int = 0) -> bool:
    next_peers = await self.discovery.discover_peers(wait_for_peers)
    # Health-evicted peers stay out for their cooldown even when discovery
    # still lists them (its liveness view can lag a death by many seconds).
    next_peers = [p for p in next_peers if not self._is_evicted(p.id())]
    current_ids = {p.id() for p in self.peers}
    next_ids = {p.id() for p in next_peers}
    peers_added = [p for p in next_peers if p.id() not in current_ids]
    peers_removed = [p for p in self.peers if p.id() not in next_ids]
    # Keep known peers, but ADOPT discovery's replacement handle when the
    # peer's address changed (re-admitted via a better NIC): the old handle
    # was gracefully disconnected by discovery and reconnecting it would
    # dial the address that just lost. Adopted handles lazy-connect on
    # first call.
    by_id = {p.id(): p for p in next_peers}
    peers_kept = []
    for p in self.peers:
      if p.id() not in next_ids:
        continue
      replacement = by_id[p.id()]
      if replacement is not p and replacement.addr() != p.addr():
        if DEBUG >= 1:
          print(f"Peer {p.id()} address changed {p.addr()} -> {replacement.addr()}; adopting new handle")
        peers_kept.append(replacement)
      else:
        peers_kept.append(p)

    async def _connect(peer):
      try:
        await asyncio.wait_for(peer.connect(), timeout=5.0)
        return True
      except Exception as e:
        if DEBUG >= 1:
          print(f"Failed to connect {peer.id()}: {e!r}")
        return False

    async def _disconnect(peer):
      try:
        # Graceful: eviction can race an in-flight RPC on a peer that is
        # flapping rather than dead; cancelling it mid-call would abort a
        # healthy request. Returns immediately (the drain runs detached),
        # so no timeout is needed here.
        await peer.disconnect(grace=600.0)
      except Exception as e:
        if DEBUG >= 2:
          print(f"Failed to disconnect {peer.id()}: {e!r}")

    connected = await asyncio.gather(*(_connect(p) for p in peers_added))
    await asyncio.gather(*(_disconnect(p) for p in peers_removed))
    # Re-filter at assignment: an eviction can land during the awaits above
    # (the health monitor doesn't hold this lock) and must not be undone by
    # this read-modify-write completing with its stale snapshot.
    self.peers = [p for p in peers_kept + [p for p, ok in zip(peers_added, connected) if ok]
                  if not self._is_evicted(p.id())]
    for p in self.peers:
      # Hand each peer handle this node's flight recorder so hop.send events
      # (with their dedup seq ids) land in the SENDER's timeline, and the
      # clock collector so hop sends carry this node's wall stamp.
      p.flight = self.flight
      p.clock = self.clock
    self.metrics.peers.set(len(self.peers))
    return bool(peers_added or peers_removed)

  async def periodic_topology_collection(self, interval: float) -> None:
    while True:
      await asyncio.sleep(interval)
      try:
        changed = await self.update_peers()
        if changed:
          await self.collect_topology(set())
          await self.select_best_inference_engine()
        if self.peers:
          # Piggyback the cluster metrics rollup on the topology cadence:
          # a compact summary per tick keeps every peer's
          # /v1/cluster/metrics view fresh without a new RPC surface.
          await self.broadcast_opaque_status("", json.dumps({
            "type": "node_metrics", "node_id": self.id,
            "metrics": self.metrics_summary(),
          }))
      except Exception as e:
        if DEBUG >= 1:
          print(f"Topology collection error: {e!r}")

  async def collect_topology(self, visited: set, max_depth: int = 4) -> Topology:
    """Visited-set BFS gossip crawl (parity node.py:533-566)."""
    prev_visited = set(visited)
    next_topology = Topology()
    next_topology.update_node(self.id, self.device_capabilities)
    visited.add(self.id)
    visited.update(p.id() for p in self.peers)

    for peer in self.peers:
      next_topology.update_node(peer.id(), peer.device_capabilities())
      next_topology.add_edge(self.id, peer.id(), peer.description())
      if peer.id() in prev_visited or max_depth <= 0:
        continue  # someone up the crawl already asked this peer
      try:
        other = await asyncio.wait_for(peer.collect_topology(set(visited), max_depth - 1), timeout=5.0)
        visited.update(other.nodes.keys())
        # Origin-filtered merge takes only the peer's OWN edges/caps (a stale
        # or malicious peer cannot rewrite the rest of the graph); transitive
        # nodes it learned about are added if we don't know them yet.
        next_topology.merge(peer.id(), other)
        for node_id, caps in other.nodes.items():
          if node_id not in next_topology.nodes:
            next_topology.update_node(node_id, caps)
      except Exception as e:
        if DEBUG >= 2:
          print(f"collect_topology from {peer.id()} failed: {e!r}")

    next_topology.active_node_id = self.topology.active_node_id
    self.topology = next_topology
    if self.topology_viz is not None:
      try:
        self.topology_viz.update_visualization(self.topology, self.partitioning_strategy.partition(self.topology), self.id)
      except Exception as e:
        # Viz is cosmetic; a TUI paint error must never break topology
        # collection — but don't hide it from whoever is debugging the TUI.
        if DEBUG >= 2:
          print(f"topology viz update failed: {e!r}")
    return next_topology

  async def select_best_inference_engine(self) -> None:
    """Broadcast which engines this node supports so the cluster can settle
    on an intersection (parity node.py:513-518)."""
    supported = [type(self.inference_engine).__name__]
    await self.broadcast_opaque_status("", json.dumps({
      "type": "supported_inference_engines", "node_id": self.id, "engines": supported,
    }))

  def get_supported_models_for_cluster(self) -> List[str]:
    pools = self.topology_inference_engines_pool or [[type(self.inference_engine).__name__]]
    return get_supported_models(pools)

  # ------------------------------------------------------------ broadcast

  def finish_request_state(self, request_id: str) -> None:
    """Release all per-request bookkeeping (idempotent). Runs on the sampler
    when a request finishes or errors, and on every other peer when the
    finished-result broadcast arrives — so mid-ring nodes don't leak
    outstanding/trace state for requests whose end they never see locally."""
    self.outstanding_requests.pop(request_id, None)
    self.metrics.active_requests.set(len(self.outstanding_requests))
    self.tracer.finish_request(request_id)
    started = self._request_started.pop(request_id, None)
    if started is not None:
      elapsed = time.monotonic() - started
      self.metrics.request_latency.observe(elapsed)
      self.flight.record("request.finished", request_id, secs=round(elapsed, 4))
    ctx = self._request_trace_ctx.pop(request_id, None)
    if ctx is not None and ctx.sampled and self.tracer.enabled and self.peers:
      # Cluster trace rollup: flush THIS node's shard of the request's
      # spans over the status bus, so any node's /v1/traces returns the
      # whole ring's trace. The ctx pop above makes this once-per-request
      # (finish_request_state is idempotent). Spawn guarded: harness code
      # calls this without a running loop — rollup is best-effort there.
      try:
        self._spawn(self._flush_trace_spans(request_id, ctx.trace_id))
      except RuntimeError:
        pass  # no running event loop (sync harness/test call): skip rollup
    was_origin = request_id in self._anatomy_origin
    self._anatomy_origin.pop(request_id, None)
    if ctx is not None and was_origin and self.anatomy.enabled and self.tracer.enabled:
      # Origin-only, once per request (the ctx pop above + the origin-set
      # pop here gate it). Delayed so remote span shards land first.
      try:
        self._spawn(self._assemble_anatomy(request_id, ctx.trace_id))
      except RuntimeError:
        pass  # no running event loop: anatomy is best-effort in harnesses
    self._last_token_time.pop(request_id, None)
    self._request_max_tokens.pop(request_id, None)
    self._request_temp.pop(request_id, None)
    self._request_top_p.pop(request_id, None)
    self._request_sampling.pop(request_id, None)
    self._request_eos.pop(request_id, None)
    self._request_prompt_tokens.pop(request_id, None)
    self._request_ring_map.pop(request_id, None)
    self._request_deadline.pop(request_id, None)
    self._last_progress.pop(request_id, None)
    self._stall_deferred.discard(request_id)
    # _hop_seen rows deliberately OUTLIVE the request (they age out of the
    # bounded LRU instead): a slow retry can land after the request
    # finished, and admitting it as fresh would resurrect per-request state
    # for a dead request.

  def trigger_on_token_callbacks(self, request_id: str, tokens: List[int], is_finished: bool) -> None:
    self.on_token.trigger_all(request_id, tokens, is_finished)

  async def broadcast_result(self, request_id: str, result: List[int], is_finished: bool,
                             error: Optional[str] = None, total_len: Optional[int] = None,
                             full_ref: Optional[List[int]] = None) -> None:
    """Fan the (delta) token payload out to every peer. A peer whose ack
    reports a gap (it missed an earlier broadcast — joined late, dropped an
    RPC) gets a full-list reconciliation send (retried once: for a finished
    request this second RPC is the peer's only chance to learn the end);
    steady state stays O(1) bytes per token. `full_ref` is the sender's live
    token buffer — read at reconciliation time, NOT via buffered_token_output
    (the sampler pops that entry the moment the request finishes)."""
    async def send(peer):
      try:
        ack = await asyncio.wait_for(
          peer.send_result(request_id, result, is_finished, error=error, total_len=total_len),
          timeout=15.0,
        )
        if total_len is not None and isinstance(ack, dict) and ack.get("applied") is False:
          full = list(full_ref) if full_ref is not None else (
            self.buffered_token_output.get(request_id, (list(result), is_finished))[0]
          )
          for attempt in (1, 2):
            try:
              await asyncio.wait_for(
                peer.send_result(request_id, full, is_finished, error=error,
                                 total_len=len(full)),
                timeout=15.0,
              )
              break
            except Exception:
              if attempt == 2:
                raise
      except Exception as e:
        if DEBUG >= 2:
          print(f"broadcast_result to {peer.id()} failed: {e!r}")
    await asyncio.gather(*(send(p) for p in self.peers), return_exceptions=True)

  async def ingest_remote_result(self, request_id: str, tokens: List[int],
                                 total_len: Optional[int], is_finished: bool,
                                 error: Optional[str] = None) -> Tuple[bool, int]:
    """Receiver side of the delta token broadcast: reconcile the delta into
    this peer's buffer. Returns (applied, have) for the sender's ack — a gap
    (missed broadcast) reports applied=False so the sender re-sends the full
    list. total_len=None means `tokens` IS the full list (legacy/abort
    sends).

    Ordering robustness (each broadcast is an independent task, so unary
    RPCs to the same peer can land out of order): a send whose total_len is
    not ahead of what we hold is STALE and ignored (monotonic guard — a
    delayed early delta must never truncate newer state), and anything
    arriving after the finish was applied is dropped outright (a straggler
    must not resurrect per-request state or fire post-finish callbacks)."""
    if request_id in self._finished_results:
      return True, 0  # straggler after finish: drop
    buffered, _ = self.buffered_token_output.get(request_id, ([], False))
    have = len(buffered)
    if is_finished and not tokens:
      # A mid-ring abort/exhaustion broadcast carries no token payload (only
      # the sampler buffers tokens); fall back to whatever this peer knows so
      # listeners aren't handed an empty completion.
      merged = buffered
    elif total_len is not None and total_len <= have and not is_finished and not error:
      return True, have  # stale reorder: newer state already held
    elif total_len is None or total_len == len(tokens):
      merged = list(tokens)  # full list (legacy send or reconciliation)
    else:
      start = total_len - len(tokens)
      if have >= start:
        merged = buffered[:start] + list(tokens)  # contiguous (or finish replay)
      else:
        # Gap: we never saw tokens [have, start). Don't hand listeners a
        # sequence with a hole — ask for reconciliation. Record the error
        # NOW though: its delivery must not depend on the second RPC.
        if error:
          self.record_request_error(request_id, error)
        return False, have
    if error:
      # Record before triggering so API consumers see the cause when the
      # finished callback lands.
      self.record_request_error(request_id, error)
    # Applied deltas are progress for THIS peer's stall watchdog: mid-ring
    # nodes see no hops during a healthy generation — the sampler's token
    # broadcasts are their only heartbeat.
    self._note_progress(request_id)
    self.buffered_token_output[request_id] = (merged, is_finished)
    self.trigger_on_token_callbacks(request_id, merged, is_finished)
    if is_finished:
      # The finished broadcast is how non-sampler peers learn a request
      # ended; run the same cleanup the sampler runs (bookkeeping + the
      # engine's resident KV cache). Remember the id (bounded) so delayed
      # stragglers can't resurrect the request. Mark cancelled too: if THIS
      # peer is the sampler with a decode loop still running (an API peer
      # cancelled on a stop sequence), the loop must stop at its next
      # boundary, not run to the cap re-creating popped request state.
      self._mark_cancelled(request_id)
      self._finished_results[request_id] = None
      while len(self._finished_results) > 512:
        self._finished_results.popitem(last=False)
      await self._finish_generation(request_id)
    return True, len(merged)

  async def _flush_trace_spans(self, request_id: str, trace_id: str) -> None:
    """Cluster trace rollup (sender side): ship this node's finished spans
    for one trace over the opaque-status bus. Export filters by node.id, so
    spans previously ingested FROM peers are never re-broadcast (no echo
    amplification); receivers dedup by span id anyway. The short sleep lets
    the spans enclosing the finish (hop span, prompt root) close first."""
    await asyncio.sleep(0.05)
    spans = self.tracer.export(trace_id=trace_id, node_id=self.id)
    if not spans:
      return
    await self.broadcast_opaque_status(request_id, json.dumps({
      "type": "trace_spans", "node_id": self.id, "request_id": request_id,
      "trace_id": trace_id, "spans": spans,
    }))

  def _peer_hop_rtts(self) -> Dict[str, float]:
    """This node's hop-RTT EWMA seconds per peer (sender-side view) — the
    transit bound the skew estimator's one-way edges need."""
    out: Dict[str, float] = {}
    for p in self.peers:
      ewma = getattr(p, "hop_rtt", None)
      v = ewma.value() if ewma is not None else None
      if v is not None:
        out[p.id()] = round(v, 6)
    return out

  def ring_offsets_view(self) -> Dict[str, dict]:
    """Every reachable node's clock offset relative to THIS node, from the
    local skew estimator plus each peer's `clock` summary off the status
    bus (orchestration/anatomy.ring_offsets)."""
    clocks: Dict[str, dict] = {self.id: self.clock.deltas()}
    rtts: Dict[str, Dict[str, float]] = {self.id: self._peer_hop_rtts()}
    for nid, summary in self.peer_metrics.items():
      if self.peer_metrics_stale(nid):
        # Same rule as the cluster metrics aggregate: a dead/wedged peer's
        # last clock window is history, not signal — solving offsets from
        # it would silently freeze the correction at the moment it died.
        continue
      clk = summary.get("clock") if isinstance(summary, dict) else None
      if isinstance(clk, dict):
        clocks[nid] = clk.get("deltas") or {}
        if isinstance(clk.get("hop_rtt_s"), dict):
          rtts[nid] = clk["hop_rtt_s"]
    return ring_offsets(self.id, clocks, rtts)

  async def _assemble_anatomy(self, request_id: str, trace_id: str) -> None:
    """Origin-side breakdown assembly for one finished request: wait a beat
    for remote span shards to arrive over the status bus, re-base the
    assembled trace onto this clock, and reservoir the stage breakdown."""
    await asyncio.sleep(self._anatomy_delay_s)
    try:
      spans = self.tracer.export(trace_id=trace_id)
      if not spans:
        return
      offsets = self.ring_offsets_view()
      # Off the event loop: a long generation's trace holds thousands of
      # spans and the sweep is quadratic-ish in them — blocking decode for
      # every in-flight request at each finish is not acceptable.
      breakdown = await asyncio.get_running_loop().run_in_executor(
        None, extract_breakdown, spans, offsets, request_id, trace_id)
      if breakdown is None:
        return
      self.anatomy.add(breakdown)
      self.flight.record(
        "anatomy.breakdown", request_id, e2e_s=breakdown["e2e_s"],
        stages=len(breakdown["stages"]),
        unattributed_s=breakdown["stages"]["unattributed"]["secs"])
    except Exception as e:
      if DEBUG >= 1:
        print(f"[{request_id}] anatomy assembly failed: {e!r}")

  def spool_flight(self, reason: str = "") -> Optional[str]:
    """Post-mortem spool: dump the flight ring + frozen snapshots to
    XOT_FLIGHT_DUMP_DIR (no-op when unset) so a SIGTERM'd node's evidence
    survives the process. Called from the main-loop signal handler."""
    dump_dir = knobs.get_str("XOT_FLIGHT_DUMP_DIR")
    if not dump_dir:
      return None
    return self.flight.dump_to(dump_dir, reason=reason)

  def metrics_summary(self) -> dict:
    """This node's compact metric summary (counters + histogram sum/count)
    for the cluster rollup — what rides the status bus and what
    /v1/cluster/metrics serves per node."""
    summary = self.metrics.summary()
    summary["node_id"] = self.id
    summary["ts"] = time.time()
    if self.clock.enabled:
      # Clock-skew compact: this node's received one-way deltas per sender
      # plus its sender-side hop RTTs — what lets the ORIGIN solve the
      # whole ring's offsets (anatomy.ring_offsets) from one rollup.
      summary["clock"] = {"deltas": self.clock.deltas(),
                          "hop_rtt_s": self._peer_hop_rtts()}
    # Roofline-attribution compact (engines that expose one): rides the
    # same status-bus broadcast, so /v1/perf on any node rolls up the ring.
    perf_fn = getattr(self.inference_engine, "perf_compact", None)
    perf = perf_fn() if callable(perf_fn) else None
    if perf is not None:
      summary["perf"] = perf
    # Alert compact (active + recent + degraded peers): rides the same
    # broadcast so ONE /v1/alerts scrape on any node sees the whole ring's
    # firing alerts with their localization verdicts.
    if self.alerts.enabled:
      summary["alerts"] = self.alerts.compact()
    # Admission compact (inflight/queued/est-wait): only while the gate is
    # enabled — defaults-off must add no keys to the wire.
    if self.admission.enabled:
      summary["admission"] = self.admission.compact()
    # History compact (trailing gauge means): what ring peers' drift
    # sentinels median against. Only while enabled — XOT_HISTORY=0 must
    # add no keys to the wire.
    if self.history.enabled:
      summary["history"] = self.history.compact()
    return summary

  async def prefetch_prompt(self, base_shard: Shard, prompt: str) -> bool:
    """PRESERVE-style anticipatory KV prefetch (arXiv 2501.08192): start the
    engine's host-to-HBM prefix restore for a prompt that is QUEUED (at the
    admission gate, or pre-announced by the router) so by the time it is
    admitted its warm prefix is already resident and it prefills only the
    suffix. Best-effort and side-effect-free on miss: engines without the
    hook (or without a host tier) report False and nothing changes."""
    hook = getattr(self.inference_engine, "prefetch_host_prefix", None)
    if hook is None:
      return False
    try:
      shard = self.get_current_shard(base_shard)
      # Dedupe the router pre-announce against the gate's own on_queued
      # hook: one restore per (shard, prompt) per window is all the host
      # tier can use.
      key = (shard, hash(prompt))
      now = time.monotonic()
      last = self._prefetch_recent.get(key)
      if last is not None and now - last < 30.0:
        return False
      self._prefetch_recent[key] = now
      self._prefetch_recent.move_to_end(key)
      while len(self._prefetch_recent) > 128:
        self._prefetch_recent.popitem(last=False)
      return bool(await hook(shard, prompt))
    except Exception as e:
      if DEBUG >= 1:
        print(f"anticipatory prefix prefetch failed (cold prefill instead): {e!r}")
      return False

  def ingest_peer_metrics(self, node_id: str, summary: dict) -> None:
    self.peer_metrics[node_id] = summary
    self.peer_metrics.move_to_end(node_id)
    self._peer_metrics_at[node_id] = time.monotonic()
    while len(self.peer_metrics) > 64:
      evicted_id, _ = self.peer_metrics.popitem(last=False)
      self._peer_metrics_at.pop(evicted_id, None)

  def peer_metrics_stale(self, node_id: str) -> bool:
    """True when a peer's last summary is older than 3x the topology cadence
    (summaries ride every topology tick, so three missed ticks means a dead
    or wedged peer — its row is history, not signal)."""
    at = self._peer_metrics_at.get(node_id)
    if at is None:
      return True  # pre-stamp row (old peer, direct dict write): treat as stale
    return time.monotonic() - at > 3.0 * max(0.1, self.topology_interval)

  def cluster_metrics_view(self) -> Tuple[Dict[str, dict], Dict[str, dict]]:
    """(nodes, aggregate) for /v1/cluster/metrics: this node's summary plus
    each peer's latest, with stale rows MARKED (`stale: true`) and excluded
    from the ring-wide percentile aggregate — a node that died mid-soak must
    not freeze the cluster's p95 at its last-good histogram forever."""
    nodes: Dict[str, dict] = {self.id: self.metrics_summary()}
    for node_id, summary in self.peer_metrics.items():
      if node_id in nodes:
        continue
      if self.peer_metrics_stale(node_id):
        summary = {**summary, "stale": True}
      nodes[node_id] = summary
    aggregate = aggregate_histograms(
      [s for s in nodes.values() if not s.get("stale")])
    return nodes, aggregate

  async def broadcast_opaque_status(self, request_id: str, status: str) -> None:
    async def send(peer):
      try:
        await asyncio.wait_for(peer.send_opaque_status(request_id, status), timeout=15.0)
      except Exception as e:
        if DEBUG >= 2:
          print(f"broadcast_status to {peer.id()} failed: {e!r}")
    await asyncio.gather(*(send(p) for p in self.peers), return_exceptions=True)
    # Local delivery too (parity: the reference triggers locally as well).
    self.on_opaque_status.trigger_all(request_id, status)

  @property
  def current_topology(self) -> Topology:
    return self.topology
