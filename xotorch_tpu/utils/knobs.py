"""Central registry of every `XOT_*` environment knob.

Single source of truth for the knob surface: name, type, default (in env-var
string form), and one doc line per knob. Three consumers:

- runtime code reads knobs through the typed accessors (`get_int`,
  `get_float`, `get_bool`, `get_str`, `raw`) — a typo'd name raises
  `UnknownKnobError` at the read site instead of silently returning the
  default forever;
- `tools/xotlint` loads this module standalone (it imports only the stdlib,
  never the package) and fails CI on any `XOT_*` env read whose name is not
  registered here;
- the README "Environment knob reference" table is GENERATED from this
  registry (`python -m tools.xotlint --knob-docs`) and drift between the
  two is a lint failure.

Keep `_DEFS` declarative: one `Knob(...)` literal per knob, string-literal
arguments only, so the linter can read it without importing the package.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple


class UnknownKnobError(KeyError):
  """An env read referenced an `XOT_*` name that is not registered."""


@dataclass(frozen=True)
class Knob:
  name: str
  kind: str  # "int" | "float" | "bool" | "str" | "json" | "path"
  default: Optional[str]  # env-string form; None = unset (auto/disabled)
  doc: str
  section: str = "General"


# NOTE for editors: keep every field a plain literal (no computed defaults,
# no conditionals) — the registry doubles as documentation and the linter's
# ground truth, so a value a reader can't see at a glance defeats both.
_DEFS: Tuple[Knob, ...] = (
  # ----------------------------------------------------------- engine core
  Knob("XOT_DTYPE", "str", "bfloat16", "Model compute/weight dtype for the JAX engine.", "Engine"),
  Knob("XOT_QUANTIZE", "str", None, "Weight quantization mode (`int8` or `int4`); unset serves full precision.", "Engine"),
  Knob("XOT_KV_QUANT", "str", None, "KV-cache quantization mode (`int8`); unset keeps KV in compute dtype.", "Engine"),
  Knob("XOT_SEED", "int", None, "Sampling PRNG seed; unset derives one from wall-clock time.", "Engine"),
  Knob("XOT_CACHE_LEN", "int", "2048", "Initial per-request KV-cache length (tokens); grows geometrically when exceeded.", "Engine"),
  Knob("XOT_MAX_CACHE_LEN", "int", "32768", "Hard ceiling for per-request KV-cache growth (tokens).", "Engine"),
  Knob("XOT_MAX_RESIDENT_REQUESTS", "int", "8", "Max request states resident per shard context before LRU eviction.", "Engine"),
  Knob("XOT_MAX_RESIDENT_MODELS", "int", "2", "Max model shard contexts resident before LRU eviction of whole models.", "Engine"),
  Knob("XOT_PREFILL_CHUNK", "int", "4096", "Prefill chunk length (tokens): prompts longer than this prefill in chunks.", "Engine"),
  Knob("XOT_SCAN_PREFILL", "bool", "1", "Use the lax.scan prefill over equal chunks (one compile for any chunk count).", "Engine"),
  Knob("XOT_DECODE_BATCH", "int", "8", "Max concurrent requests fused into one batched decode dispatch.", "Engine"),
  Knob("XOT_BATCH_WINDOW_MS", "float", "0", "Batching window (ms) the decode batcher waits to coalesce submitters; 0 = one event-loop tick.", "Engine"),
  Knob("XOT_DECODE_CHUNK", "int", "8", "Tokens per fused decode dispatch on a single-partition ring; 1 = per-token ring.", "Engine"),
  Knob("XOT_DECODE_CHUNK_MAX", "int", "64", "Adaptive fused-decode chunk ceiling (doubles per dispatch up to this).", "Engine"),
  Knob("XOT_OVERLAP_CHUNKS", "bool", "1", "Overlap fused-decode chunk N+1 dispatch with chunk N host readback.", "Engine"),
  Knob("XOT_OVERLAP_BATCH", "bool", "0", "Overlap batched-decode dispatch with readback (two in-flight batches).", "Engine"),
  # ------------------------------------------------------------- paged KV
  Knob("XOT_PAGED_KV", "bool", "0", "Serve decode from the shared paged KV pool instead of contiguous per-request caches.", "Paged KV"),
  Knob("XOT_KV_PAGE", "int", "128", "Page size (tokens) of the paged KV pool.", "Paged KV"),
  Knob("XOT_KV_POOL_TOKENS", "int", "0", "Total paged-pool capacity in tokens; 0 sizes it automatically.", "Paged KV"),
  Knob("XOT_PAGED_KERNEL", "bool", None, "Force the Pallas ragged paged-attention kernel on/off; unset auto-selects by backend.", "Paged KV"),
  Knob("XOT_PAGED_PREFILL", "bool", "1", "Prefill straight into pool pages under XOT_PAGED_KV (no contiguous commit copy).", "Paged KV"),
  Knob("XOT_RAGGED_PREFILL", "bool", "1", "Kernel-path T>1 segments read pages natively via the ragged kernel (no gathered view); 0 restores the legacy gather+cached-kernel read.", "Paged KV"),
  Knob("XOT_PAGED_SPEC", "bool", "1", "Draft verification runs native to the page arena (ragged query over the request's page table); 0 restores unpage-then-verify.", "Paged KV"),
  Knob("XOT_KV_DEFRAG", "bool", "1", "Background page-pool defragmentation in batcher-idle slots: migrate high pages into low free holes and rewrite only the virtual maps.", "Paged KV"),
  Knob("XOT_KV_DEFRAG_MAX_MOVES", "int", "8", "Max page migrations per idle defrag pass (bounds the donated-copy burst).", "Paged KV"),
  Knob("XOT_PREFILL_COSCHED", "bool", "1", "Co-schedule chunked prefill slices through the decode batcher's drain cycle.", "Paged KV"),
  Knob("XOT_PREFILL_CHUNK_BUDGET", "int", "1", "Prefill segments admitted per decode drain cycle under co-scheduling.", "Paged KV"),
  Knob("XOT_KV_HOST_BYTES", "int", "268435456", "Host-RAM budget (bytes) for the spilled warm-prefix KV tier; 0 disables.", "Paged KV"),
  # --------------------------------------------------------- prefix cache
  Knob("XOT_PREFIX_CACHE", "int", "2", "Prefix-cache entries kept per context (LRU); 0 disables prefix caching.", "Prefix cache"),
  Knob("XOT_PREFIX_CACHE_MIN", "int", "32", "Minimum matched prefix length (tokens) worth reusing from the cache.", "Prefix cache"),
  # ---------------------------------------------------- attention kernels
  Knob("XOT_FLASH_ATTENTION", "bool", None, "Force the Pallas flash-attention prefill kernel on/off; unset auto-selects by backend.", "Kernels"),
  Knob("XOT_FLASH_BLOCK_Q", "int", "128", "Flash-attention query block size.", "Kernels"),
  Knob("XOT_FLASH_BLOCK_K", "int", "128", "Flash-attention key/value block size.", "Kernels"),
  Knob("XOT_FLASH_DECODE", "bool", None, "Force the Pallas flash-decode kernel on/off; unset auto-selects by backend and length.", "Kernels"),
  Knob("XOT_FLASH_DECODE_MIN", "int", "4096", "Minimum KV length (tokens) before flash-decode engages.", "Kernels"),
  Knob("XOT_FD_BLOCK_Q", "int", "128", "Flash-decode query-head block size.", "Kernels"),
  Knob("XOT_FD_BLOCK_K", "int", "256", "Flash-decode key/value block size.", "Kernels"),
  Knob("XOT_INT4_KERNEL", "str", "1", "Fused int4 matmul kernel: `1` on real TPU, `0` off, `force` even off-TPU.", "Kernels"),
  Knob("XOT_INT8_KERNEL", "str", "0", "Fused int8 matmul kernel: `1` on real TPU, `0` off, `force` even off-TPU.", "Kernels"),
  # ----------------------------------------------------------- speculative
  Knob("XOT_SPECULATE", "int", "0", "Speculative draft depth (tokens per round); 0 disables (8 implied by XOT_DRAFT_MODEL).", "Speculative"),
  Knob("XOT_SPECULATE_WINDOW", "int", "2048", "Backward scan window (tokens) for prompt-lookup draft matching.", "Speculative"),
  Knob("XOT_DRAFT_MODEL", "str", None, "Resident draft model id for model-based speculative decoding.", "Speculative"),
  Knob("XOT_DRAFT_RETRY_S", "float", "300", "Cooldown (s) before retrying a draft model that failed to load.", "Speculative"),
  Knob("XOT_SPEC_EWMA_S", "float", "60", "Time constant (s) of the xot_spec_accept_rate EWMA gauge.", "Speculative"),
  # ------------------------------------------------------------- sharding
  Knob("XOT_TP", "int", None, "Tensor-parallel width of each ring partition's serving mesh (primary knob; overrides XOT_SERVE_TP). 0 forces single-device; unset defers to XOT_SERVE_TP.", "Sharding"),
  Knob("XOT_SERVE_TP", "int", None, "Tensor-parallel degree for serving; unset auto-selects from local devices.", "Sharding"),
  Knob("XOT_SERVE_SP", "int", "0", "Sequence-parallel degree for long-prompt serving prefill.", "Sharding"),
  Knob("XOT_SERVE_EP", "int", "0", "Expert-parallel degree for MoE serving.", "Sharding"),
  Knob("XOT_MAX_SEQ_LEN", "int", None, "Override the model's maximum sequence length (RoPE/table sizing).", "Sharding"),
  # ------------------------------------------------------- training / LoRA
  Knob("XOT_LORA_RANK", "int", "0", "LoRA adapter rank for training; 0 trains/serves without LoRA.", "Training"),
  Knob("XOT_LORA_TARGETS", "str", None, "LoRA target set; `all` extends adapters to MLP slots (default attention-only).", "Training"),
  Knob("XOT_ADAPTERS", "str", None, "Comma-separated `name=path` list of LoRA adapters to serve (multi-LoRA).", "Training"),
  Knob("XOT_LR", "float", "1e-5", "Training learning rate.", "Training"),
  Knob("XOT_SAVE_OPT_STATE", "bool", "1", "Persist/restore optimizer state across training checkpoints.", "Training"),
  # ------------------------------------------------- ring / survivability
  Knob("XOT_HOP_RETRIES", "int", "2", "Retries per ring hop on transient transport failures; 0 = fail-fast.", "Survivability"),
  Knob("XOT_HOP_BACKOFF_S", "float", "0.05", "Base backoff (s) for hop retries (exponential + jitter).", "Survivability"),
  Knob("XOT_REQUEST_DEADLINE_S", "float", "0", "End-to-end request deadline (s); remaining budget rides the hops. 0 disables.", "Survivability"),
  Knob("XOT_STALL_TIMEOUT_S", "float", "30", "Per-node stall watchdog: abort a request with no progress for this long. A mid-dispatch local engine (compiles included) defers the abort, bounded at 4x. 0 disables.", "Survivability"),
  Knob("XOT_HEALTH_INTERVAL_S", "float", "5", "Peer health-check cadence (s); 0 disables the health monitor.", "Survivability"),
  Knob("XOT_HEALTH_FAILS", "int", "2", "Consecutive failed health checks before a peer is evicted.", "Survivability"),
  Knob("XOT_EVICT_COOLDOWN_S", "float", "30", "Seconds an evicted peer stays barred from re-admission by discovery.", "Survivability"),
  Knob("XOT_REQUEST_RESTARTS", "int", "0", "One-shot transparent API restarts after a ring failure (streaming qualifies until its first content chunk).", "Survivability"),
  Knob("XOT_FAULT_SPEC", "json", None, "Test-only: JSON fault-injection rules applied at the peer-handle boundary.", "Survivability"),
  # --------------------------------------------- admission / front door
  Knob("XOT_MAX_INFLIGHT", "int", "0", "Bounded admission: max requests admitted into the ring concurrently by the origin node's API; 0 disables the gate (today's behavior).", "Front door"),
  Knob("XOT_ADMIT_QUEUE_DEPTH", "int", "32", "Bounded admission queue: over-limit requests wait here (FIFO); beyond it they are rejected with HTTP 429 + Retry-After.", "Front door"),
  Knob("XOT_ROUTER_POLL_S", "float", "2", "Router: cadence (s) for polling each replica's /v1/alerts, /v1/queue, and /healthcheck.", "Front door"),
  Knob("XOT_ROUTER_PROBE_TOKENS", "int", "2", "Router: max_tokens of the synthetic canary completion sent to a probing replica.", "Front door"),
  Knob("XOT_ROUTER_PROBES", "int", "2", "Router: consecutive successful canaries required before a drained replica is readmitted.", "Front door"),
  Knob("XOT_ROUTER_MIN_OUT_S", "float", "10", "Router: minimum seconds a drained replica stays out before readmission; doubles (bounded 8x) when the replica flaps.", "Front door"),
  Knob("XOT_ROUTER_FLAP_S", "float", "60", "Router: a re-drain within this many seconds of a readmission counts as flapping (escalates the out-time hysteresis).", "Front door"),
  Knob("XOT_ROUTER_SPILL_DEPTH", "int", "2", "Router: spill a request to the least-loaded healthy replica when its affinity replica's admission queue is at least this deep.", "Front door"),
  Knob("XOT_ROUTER_TIMEOUT_S", "float", "300", "Router: total proxy timeout (s) for one forwarded request.", "Front door"),
  Knob("XOT_ROUTER_DRIFT", "bool", "1", "Router: compare each replica's /v1/history trailing gauges against the fleet median and treat a chronic drifter as a drain-eligible perf_drift suspect.", "Front door"),
  Knob("XOT_ROUTER_DRIFT_POLLS", "int", "3", "Router: consecutive poll ticks a replica must deviate from the fleet median before it is named perf_drift.", "Front door"),
  Knob("XOT_ROUTER_HEDGE_PCT", "float", "0", "Router: request-hedging budget as a percentage of proxied requests (a still-unstarted request is duplicated to the least-loaded other replica, first byte wins); 0 disables hedging.", "Front door"),
  Knob("XOT_ROUTER_HEDGE_FACTOR", "float", "2", "Router: hedge delay as a multiple of the fleet's trailing request p99 (median of routable replicas' /v1/history compacts).", "Front door"),
  Knob("XOT_ROUTER_HEDGE_MIN_S", "float", "0.5", "Router: hedge-delay floor (s); also the delay used while the fleet has no trailing p99 history yet.", "Front door"),
  # ------------------------------------------------------------ elastic fleet
  Knob("XOT_FLEET_MIN", "int", "1", "Fleet controller: minimum replica slots kept spawned (the template's initially-active set).", "Fleet"),
  Knob("XOT_FLEET_MAX", "int", "0", "Fleet controller: maximum concurrently active replica slots; 0 means every slot in the template.", "Fleet"),
  Knob("XOT_FLEET_UP_QUEUE", "int", "1", "Fleet controller: scale up when the fleet-wide admission-queue high-water mark is at least this deep for XOT_FLEET_UP_POLLS consecutive ticks.", "Fleet"),
  Knob("XOT_FLEET_UP_POLLS", "int", "3", "Fleet controller: consecutive controller ticks the queue-depth signal must hold before a scale-up actuates.", "Fleet"),
  Knob("XOT_FLEET_IDLE_POLLS", "int", "60", "Fleet controller: consecutive idle ticks (no queue, no inflight fleet-wide) before a controller-scaled spare replica is retired via the drain path.", "Fleet"),
  Knob("XOT_FLEET_DEAD_POLLS", "int", "3", "Fleet controller: consecutive unreachable-or-scrape-failed polls before an ever-reachable replica is declared dead and respawned.", "Fleet"),
  Knob("XOT_FLEET_COOLDOWN_S", "float", "20", "Fleet controller: minimum seconds between scaling actuations (respawns of dead replicas are exempt).", "Fleet"),
  Knob("XOT_FLEET_BOOT_TIMEOUT_S", "float", "180", "Fleet controller: seconds a freshly spawned replica gets to answer its healthcheck before the spawn counts as a respawn failure.", "Fleet"),
  Knob("XOT_FLEET_LEASE_TTL_S", "float", "15", "Fleet controller: TTL (s) of the actuation lease; a dead lease holder's lease expires and actuation hands over to a surviving router.", "Fleet"),
  Knob("XOT_FLEET_LEASE_PATH", "path", None, "Fleet controller: path of the shared TTL'd lease file gating actuation to one router; unset runs the controller solo (always holds).", "Fleet"),
  Knob("XOT_FLEET_WARM_PREFIXES", "int", "4", "Fleet controller: recent request prefixes pre-announced (/v1/prefetch) at a fresh spawn before it enters rotation (PRESERVE-style warm cold-start).", "Fleet"),
  # ------------------------------------------------------------ KV fabric
  Knob("XOT_FABRIC_PEERS", "str", "", "Fleet-wide KV fabric: comma-separated sibling replica base URLs to probe on a host-tier prefix miss; empty disables static peer probing (router offers still work).", "KV fabric"),
  Knob("XOT_FABRIC_ROLE", "str", "mixed", "Disaggregated serving role: `prefill` (compute KV, offer it, return a handle instead of streaming), `decode` (import offered KV, serve decode), or `mixed` (default: serve everything).", "KV fabric"),
  Knob("XOT_FABRIC_TIMEOUT_S", "float", "2", "KV fabric: per-request transport timeout (s) for peer match probes and entry fetches; a timed-out fetch degrades to a cold prefill.", "KV fabric"),
  Knob("XOT_FABRIC_OFFER_TTL_S", "float", "120", "KV fabric: seconds an announced peer offer stays usable in the local directory before it expires.", "KV fabric"),
  # ------------------------------------------------------------- topology
  Knob("XOT_COORDINATOR", "str", None, "JAX multi-host coordinator address (`host:port`); setting it implies multi-host.", "Topology"),
  Knob("XOT_MULTIHOST", "bool", "0", "Force JAX multi-host initialization.", "Topology"),
  Knob("XOT_NUM_PROCESSES", "int", None, "Process count for JAX multi-host init (required with XOT_COORDINATOR).", "Topology"),
  Knob("XOT_PROCESS_ID", "int", None, "This process's index for JAX multi-host init (required with XOT_COORDINATOR).", "Topology"),
  Knob("XOT_PROBE_TIMEOUT", "float", "120", "Timeout (s) for the device-capability accelerator probe subprocess.", "Topology"),
  Knob("XOT_SKIP_JAX_PROBE", "bool", "0", "Skip the JAX accelerator probe (report CPU-only capabilities).", "Topology"),
  # ------------------------------------------------------ paths / identity
  Knob("XOT_HOME", "path", None, "Root directory for downloads and state; unset uses `~/.xot_tpu`.", "Paths"),
  Knob("XOT_MODEL_DIR", "path", None, "Local directory of model checkpoints (offline serving).", "Paths"),
  Knob("XOT_UUID", "str", None, "Override the persistent per-machine node id.", "Paths"),
  # ------------------------------------------------------- native sidecar
  Knob("XOT_SIDECAR_BIN", "path", None, "Path to a prebuilt native sidecar binary (skips the make step).", "Sidecar"),
  Knob("XOT_SIDECAR_QUANT", "str", None, "Native sidecar weight quantization (`int8`); read by the C++ engine.", "Sidecar"),
  # ------------------------------------------------------------ observability
  Knob("XOT_TRACING", "bool", "1", "Record request/hop spans in the in-process tracer (served at /v1/traces).", "Observability"),
  Knob("XOT_FLIGHT", "bool", "1", "Record runtime events in the per-node flight recorder (served at /v1/debug/flight).", "Observability"),
  Knob("XOT_FLIGHT_EVENTS", "int", "4096", "Flight-recorder ring capacity (events).", "Observability"),
  Knob("XOT_FLIGHT_SNAPSHOTS", "int", "16", "Frozen flight-recorder snapshots kept per node (LRU).", "Observability"),
  Knob("XOT_FLIGHT_DUMP_DIR", "path", None, "Post-mortem spool: on SIGTERM/SIGINT the node dumps its flight ring + frozen snapshots here as JSON; unset disables.", "Observability"),
  Knob("XOT_ANATOMY", "bool", "1", "Critical-path latency anatomy: hop clock stamps, skew-corrected per-request stage breakdowns (served at /v1/anatomy). 0 removes the clock field from the wire entirely.", "Observability"),
  Knob("XOT_ANATOMY_RESERVOIR", "int", "256", "Recent stage breakdowns kept per node for /v1/anatomy percentiles and diffs.", "Observability"),
  Knob("XOT_ANATOMY_CLOCK_WINDOW", "int", "64", "Per-peer window of one-way clock-delta samples the skew estimator min-filters.", "Observability"),
  Knob("XOT_ANATOMY_DELAY_S", "float", "0.35", "Seconds after a request finishes before the origin assembles its breakdown (lets remote span shards arrive over the status bus).", "Observability"),
  Knob("XOT_ANATOMY_SKEW_NS", "int", "0", "Test-only: artificial offset (ns) added to this node's anatomy wall clock — the skew-injection point for offset-recovery proofs.", "Observability"),
  Knob("XOT_PERF_ATTR", "bool", "1", "Live roofline attribution: per-dispatch time/bytes/FLOPs accounting served at /v1/perf.", "Observability"),
  Knob("XOT_PERF_EWMA_S", "float", "30", "Time constant (s) of the EWMA throughput/utilization gauges (xot_decode_tok_s and friends).", "Observability"),
  Knob("XOT_DEVICE_TRACE_MAX_S", "float", "120", "Auto-stop a /v1/trace/device/start jax.profiler session after this many seconds; 0 disables the cap.", "Observability"),
  # ------------------------------------------------------ alerting / SLOs
  Knob("XOT_ALERT", "bool", "1", "Evaluate SLO burn-rate alert rules on a background cadence (served at /v1/alerts).", "Alerting"),
  Knob("XOT_ALERT_EVAL_S", "float", "5", "Alert-rule evaluation cadence (seconds).", "Alerting"),
  Knob("XOT_ALERT_FAST_S", "float", "120", "Fast burn-rate window (seconds) of the multi-window SLO rules.", "Alerting"),
  Knob("XOT_ALERT_SLOW_S", "float", "600", "Slow burn-rate window (seconds) of the multi-window SLO rules.", "Alerting"),
  Knob("XOT_ALERT_BURN_FAST", "float", "14.4", "Fast-window burn-rate threshold (error-budget multiples) a rule must exceed to fire.", "Alerting"),
  Knob("XOT_ALERT_BURN_SLOW", "float", "6", "Slow-window burn-rate threshold (error-budget multiples) a rule must exceed to fire.", "Alerting"),
  Knob("XOT_ALERT_PENDING_S", "float", "10", "Seconds the burn condition must hold before a pending alert transitions to firing.", "Alerting"),
  Knob("XOT_ALERT_RESOLVE_S", "float", "60", "Hysteresis: seconds the burn condition must stay clear before a firing alert resolves.", "Alerting"),
  Knob("XOT_ALERT_SNAPSHOTS", "int", "256", "Bounded ring of timestamped metric snapshots the burn windows are computed over.", "Alerting"),
  Knob("XOT_ALERT_HISTORY", "int", "64", "Recent resolved alerts kept for /v1/alerts (bounded).", "Alerting"),
  Knob("XOT_ALERT_DEVICE_TRACE", "bool", "0", "Capture-on-anomaly: a firing alert starts the bounded device trace (auto-stops after XOT_DEVICE_TRACE_MAX_S).", "Alerting"),
  Knob("XOT_ALERT_RTT_TAU_S", "float", "30", "Time constant (s) of the per-peer hop send RTT EWMAs (xot_peer_hop_seconds).", "Alerting"),
  Knob("XOT_ALERT_HOP_DEGRADED_S", "float", "0.2", "Absolute hop-RTT floor (s) below which a peer is never scored degraded.", "Alerting"),
  Knob("XOT_ALERT_DEGRADED_FACTOR", "float", "3", "A peer whose hop RTT or per-dispatch compute exceeds this multiple of the ring median is scored degraded.", "Alerting"),
  Knob("XOT_SLO_TTFT_S", "float", "10", "TTFT SLO target (s) the XOT_SLO_TARGET fraction of requests must beat.", "Alerting"),
  Knob("XOT_SLO_E2E_S", "float", "60", "End-to-end request latency SLO target (s).", "Alerting"),
  Knob("XOT_SLO_TARGET", "float", "0.99", "Fraction of requests that must meet each latency SLO target (error budget = 1 - target; must leave budget * XOT_ALERT_BURN_FAST below 1 or the rule can never fire).", "Alerting"),
  Knob("XOT_SLO_ERROR_RATE", "float", "0.01", "Failed-request budget: the fraction of requests that may abort before the error-rate rule burns.", "Alerting"),
  # --------------------------------------------------- metrics history / drift
  Knob("XOT_HISTORY", "bool", "1", "Metrics history: sample windowed deltas of the node's own gauges on a background cadence (served at /v1/history); 0 disables the sampler entirely — no task, no wire keys, byte-identical serving.", "History"),
  Knob("XOT_HISTORY_SAMPLE_S", "float", "10", "History sampling cadence (seconds): one windowed-delta gauge sample per tick.", "History"),
  Knob("XOT_HISTORY_SAMPLES", "int", "360", "Fine-tier samples kept before the oldest are merged into the next-coarser tier (at the default 10 s cadence: one hour at full resolution).", "History"),
  Knob("XOT_HISTORY_MERGE", "int", "8", "Samples merged into one duration-weighted bucket when a history tier overflows into the next-coarser tier.", "History"),
  Knob("XOT_HISTORY_COARSE", "int", "336", "Buckets kept in each of the two coarser history tiers (mid keeps merge-fold buckets, old keeps merge^2-fold).", "History"),
  Knob("XOT_HISTORY_DIR", "path", None, "JSONL spool directory for history samples: restarts and soak teardowns keep the record (restored rows are marked as a restart boundary); unset keeps history in memory only.", "History"),
  Knob("XOT_DRIFT", "bool", "1", "Evaluate chronic perf-drift rules over the metrics history inside the alert loop (requires XOT_HISTORY and XOT_ALERT); fires the perf_drift alert class.", "History"),
  Knob("XOT_DRIFT_WINDOW_S", "float", "120", "Recent window (s) a drift rule averages over — also the trailing-mean window of the history compact the router and ring peers compare.", "History"),
  Knob("XOT_DRIFT_BASELINE_S", "float", "600", "Trailing baseline window (s) a drift rule compares its recent window against; the baseline ends where the recent window begins.", "History"),
  Knob("XOT_DRIFT_RATIO", "float", "0.25", "Relative worsening vs the gauge's own trailing baseline before a drift rule's condition holds (direction-aware: tok/s down, rtt up).", "History"),
  Knob("XOT_DRIFT_PEER_RATIO", "float", "0.5", "Relative worsening vs the median of peer nodes' trailing gauges before a drift rule's condition holds.", "History"),
  Knob("XOT_DRIFT_MIN_SAMPLES", "int", "3", "Minimum samples carrying the gauge in each compared window before a drift rule may evaluate (thin evidence never pages).", "History"),
  Knob("XOT_DRIFT_PENDING_S", "float", "30", "Seconds a drift condition must hold before the pending perf_drift alert transitions to firing.", "History"),
  Knob("XOT_DRIFT_RESOLVE_S", "float", "60", "Hysteresis: seconds a drift condition must stay clear before a firing perf_drift alert resolves.", "History"),
  # ------------------------------------------------------- soak / load gen
  Knob("XOT_SOAK_SECONDS", "float", "60", "Soak load duration (s) for `python -m tools.soak` when --seconds is not given.", "Soak"),
  Knob("XOT_SOAK_RPS", "float", "1.5", "Mean open-loop arrival rate (requests/s) for the soak load generator.", "Soak"),
  Knob("XOT_SOAK_PROCS", "int", "2", "Ring size (node processes) the soak orchestrator spawns.", "Soak"),
  Knob("XOT_SOAK_STREAM_FRACTION", "float", "0.5", "Fraction of soak requests issued as SSE streaming completions.", "Soak"),
  Knob("XOT_SOAK_SESSION_REUSE", "float", "0.3", "Probability a soak request reuses a session prefix (prefix-cache exercise).", "Soak"),
  Knob("XOT_SOAK_RECON_TOL_S", "float", "2.5", "Absolute slack (s) allowed between client- and server-observed latency percentiles in the soak verdict.", "Soak"),
  Knob("XOT_SOAK_SEED", "int", "1234", "PRNG seed for the soak load generator (arrivals, lengths, mixes).", "Soak"),
)

REGISTRY: Dict[str, Knob] = {k.name: k for k in _DEFS}

_UNSET = object()
_FALSE_STRINGS = frozenset(("", "0", "false", "no", "off"))


def _lookup(name: str) -> Knob:
  try:
    return REGISTRY[name]
  except KeyError:
    raise UnknownKnobError(
      f"{name} is not a registered knob — add it to xotorch_tpu/utils/knobs.py"
    ) from None


def raw(name: str, default=_UNSET) -> Optional[str]:
  """The env value as a string, or the registered default (which may be
  None = unset) — the exact-substitute for `os.getenv` that still fails
  loudly on typo'd knob names. A set-but-EMPTY value is returned verbatim:
  tri-state call sites distinguish `XOT_X=` (set: forces the non-"1"
  branch, e.g. kernel off) from `XOT_X` absent (auto-select); the numeric
  accessors below map empty to the default instead (the historical
  `... or 0` idiom)."""
  knob = _lookup(name)
  value = os.environ.get(name)
  if value is None:
    return knob.default if default is _UNSET else default
  return value


def get_str(name: str, default=_UNSET) -> Optional[str]:
  return raw(name, default)


def _required(name: str):
  raise RuntimeError(f"knob {name} has no default and is not set in the environment")


def _numeric(name: str, default, cast):
  value = raw(name, default)
  if isinstance(value, str) and value.strip() == "":
    # Empty value == unset for numbers (`XOT_X= prog` must not crash).
    knob = _lookup(name)
    value = knob.default if default is _UNSET else default
  if value is None:
    return None if default is not _UNSET else _required(name)
  return cast(value)


def get_int(name: str, default=_UNSET) -> Optional[int]:
  return _numeric(name, default, int)


def get_float(name: str, default=_UNSET) -> Optional[float]:
  return _numeric(name, default, float)


def get_bool(name: str, default=_UNSET) -> Optional[bool]:
  """Truthiness matching the historical call sites: "0"/"false"/"no"/"off"
  (any case) and set-but-empty are False, any other set value is True."""
  value = raw(name, default)
  if value is None:
    return None if default is not _UNSET else _required(name)
  if isinstance(value, bool):
    return value
  return str(value).strip().lower() not in _FALSE_STRINGS


def knob_table_markdown() -> str:
  """The README "Environment knob reference" section body — generated so
  docs can never drift from the registry (xotlint's doc-drift checker
  compares this rendering against the committed README)."""
  lines = []
  section = None
  for knob in _DEFS:
    if knob.section != section:
      section = knob.section
      lines.append(f"\n**{section}**\n")
      lines.append("| Knob | Type | Default | Description |")
      lines.append("| --- | --- | --- | --- |")
    default = "_unset_" if knob.default is None else f"`{knob.default}`"
    lines.append(f"| `{knob.name}` | {knob.kind} | {default} | {knob.doc} |")
  return "\n".join(lines).strip() + "\n"
