"""JAXShardInferenceEngine — the flagship TPU compute backend.

TPU-native replacement for the reference's TorchDynamicShardInferenceEngine
(sharded_inference_engine.py:37-424), redesigned around XLA's compilation
model instead of eager dispatch:

- Each layer-range Shard compiles to a small, fixed set of XLA executables:
  one per prefill length bucket (powers of two) + ONE decode step. Static
  shapes everywhere — no per-request cache/mask re-sizing (the reference
  re-allocates both per request, :144-147), so there are no recompilation
  storms and decode always hits the same executable.
- The KV cache is a static [L, B, S, Hkv, D] bf16 buffer donated back to the
  compiled step each token — it stays resident in HBM for the life of the
  request; the host only ever sees the (hidden, pos) pair that crosses shard
  boundaries. This kills the reference's biggest wire sin (fp32 upcast +
  tokens/mask/input_pos JSON re-sent every hop, llm_utils.py:617-623).
- Per-REQUEST state (cache, position) replaces the reference's per-engine
  singleton state, fixing the documented interleaving race
  (sharded_inference_engine.py:42,135; SURVEY §5) and allowing concurrent
  requests; an LRU bound caps HBM.
- Per-MODEL `_ShardContext` replaces the reference's whole-world reload on
  model switch (ensure_shard drops everything, :372-421; VERDICT r2 weak
  #2): params/executables/tokenizer/request-states are kept per (model,
  layer-range) in an LRU of resident contexts, every compute path binds its
  context at call time, and alternating models through the API never
  corrupt each other's in-flight requests.
- All device work funnels through a single-worker executor (same structural
  concurrency model as the reference, :46) so the asyncio loop never blocks
  on XLA, and JAX tracing is never entered from two threads.
"""
from __future__ import annotations

import asyncio
import os
import re
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import numpy as np

from xotorch_tpu.download.shard_download import NoopShardDownloader, ShardDownloader
from xotorch_tpu.inference.engine import CacheExhausted, InferenceEngine, RequestStateLost
from xotorch_tpu.inference.jax_engine import vkv
from xotorch_tpu.inference.jax_engine.vkv import VirtualKV
from xotorch_tpu.inference.shard import Shard
from xotorch_tpu.inference.tokenizers import DummyTokenizer, resolve_tokenizer
from xotorch_tpu.models.config import ModelConfig, config_from_hf_dict, load_model_config
from xotorch_tpu.models.registry import get_model_card
from xotorch_tpu.utils import knobs
from xotorch_tpu.utils.helpers import DEBUG, spawn_detached

from xotorch_tpu.ops.sampling import DEFAULT_TEMP, DEFAULT_TOP_K

MAX_RESIDENT_REQUESTS = knobs.get_int("XOT_MAX_RESIDENT_REQUESTS")
# How many (model, layer-range) contexts stay resident in HBM at once.
MAX_RESIDENT_MODELS = knobs.get_int("XOT_MAX_RESIDENT_MODELS")

# coordinate_save file naming: {start}-{end}-{iteration}.safetensors (stem).
# The single source of truth for every "is this a shard save?" decision
# (defined beside the save/validate code; engine and API must agree).
from xotorch_tpu.train.lora import SHARD_SAVE_RE  # noqa: E402


def _bucket(n: int, minimum: int = 16) -> int:
  b = minimum
  while b < n:
    b *= 2
  return b


@dataclass
class _RequestState:
  cache: Any  # device pytree {"k","v"}; None once committed to the page pool
  pos: int  # tokens already resident in this shard's cache
  last_used: float
  # Paged KV (XOT_PAGED_KV): vkv.VirtualKV — the request's ordered LOGICAL
  # page handle over the context's PagePool arena (cache is then None).
  # Slots the sliding window has released are zeroed in place, so
  # len(pages) stays == pages_for(pos); physical ids resolve per dispatch
  # via vkv.resolve_page_table. See _commit_state_to_pages / vkv.py.
  pages: Optional[Any] = None
  paged_seed: Optional[list] = None
  # OpenAI sampling extras (seed / logit_bias / presence+frequency penalties):
  # {"seed": int|None, "bias": [1,V] device array|None, "counts": [1,V] int32
  #  device array|None, "presence": float, "frequency": float}. None = plain
  # request — extras requests decode in their own fused chunk (never batched),
  # so the common path's executables and batcher grouping are untouched.
  extras: Optional[Dict[str, Any]] = None


@dataclass
class _ShardContext:
  """Everything one (model, layer-range) needs to serve: weights,
  executables, tokenizer, and the per-request device states. Compute paths
  bind their context at call time, so a model switch can never swap the
  params out from under an in-flight request."""
  shard: Shard
  cfg: ModelConfig
  params: Any
  mesh: Any
  forward_jit: Any
  forward_flash_jit: Any
  forward_decode_flash_jit: Any
  fill_jits: Optional[Dict[str, Any]]
  forward_hidden_jit: Any
  forward_hidden_flash_jit: Any
  vision: Any
  model_dir: Optional[Path]
  synthetic: bool
  cache_len: int
  max_cache_len: int
  tokenizer: Any = None
  states: "OrderedDict[str, _RequestState]" = field(default_factory=OrderedDict)
  opt_state: Any = None
  optimizer: Any = None
  batcher: Any = None  # lazy _DecodeBatcher (continuous batching)
  # In-flight speculative BATCH chunk (decode overlap for a stable
  # multi-request batch): {"rids", "n", "toks", "prev", "pos", "temps",
  # "top_k", "top_p", "states"} — see _decode_batch_sync.
  batch_spec: Any = None
  # Automatic prefix cache: completed prefills' KV snapshots keyed by token
  # hash — a new prompt sharing a long common prefix (system prompt,
  # multi-turn history) seeds its cache from the snapshot and prefills only
  # the suffix. LRU bounded by XOT_PREFIX_CACHE entries (device HBM!).
  # Under XOT_PAGED_KV entries are {"pages": [...], "len": n} markers that
  # SHARE the pool's pages (incref) instead of holding a snapshot copy.
  prefix_cache: "OrderedDict[int, Tuple[np.ndarray, Any]]" = field(default_factory=OrderedDict)
  # Paged KV-cache pool (XOT_PAGED_KV=1): lazy paged_cache.PagePool — one
  # shared K/V arena + free-list/refcount metadata for every resident
  # request of this context.
  page_pool: Any = None
  # Analytic roofline model (costmodel.CostModel) bound at load time from
  # the shard's config + quantization — predicts the HBM bytes/FLOPs each
  # dispatch must move for the live attribution pipeline (/v1/perf).
  costmodel: Any = None


class _DecodeBatcher:
  """Continuous batching at chunk granularity (VERDICT r2 #9, the
  'beating' half of the bar — no reference counterpart).

  Concurrent requests each drive their own fused-decode loop; this collector
  coalesces their generate_chunk calls into ONE batched device dispatch per
  window. Decode at batch 1 is HBM-bound — the whole parameter set streams
  from HBM per step regardless of batch — so B concurrent requests batched
  together cost ~1x the weight traffic instead of Bx: aggregate throughput
  scales nearly linearly until the MXU becomes the limit.

  Coalescing comes from a DRAIN LOOP, not a timer: while one batch computes
  on the engine executor (a whole chunk's worth of device time), every
  request that becomes ready queues into `pending`; the next drain iteration
  takes them ALL. Batch width therefore adapts to load automatically — an
  idle server runs batches of one with zero added latency, a loaded one
  converges to full-width batches. Rows share one sampling key per chunk
  (per-step splits inside the scan); greedy decoding is unaffected and
  sampled streams stay independent via their distinct logits.

  The drain cycle also CO-SCHEDULES prefill: `pending_prefill` holds
  bounded prompt slices (engine _prefill_and_sample splits a long prompt
  into XOT_PREFILL_CHUNK_BUDGET-segment thunks) and each cycle runs the
  decode dispatches first, then admits ONE slice — so a 16 k prompt's
  prefill interleaves with decode instead of monopolising the single-worker
  executor, and resident streams stall at most one slice per cycle."""

  def __init__(self, engine: "JAXShardInferenceEngine", ctx: "Optional[_ShardContext]",
               dispatch=None):
    self.engine = engine
    self.ctx = ctx
    # Optional async dispatch override (the fused-RING batcher reuses this
    # collector with a different sync body; items' `state` slot then carries
    # the request's seg list — opaque to the drain loop either way).
    self.dispatch = dispatch
    self.pending: list = []
    self.pending_prefill: list = []  # (sync thunk, future) prompt slices
    self._draining = False
    self._drain_task = None  # strong ref: the loop only weakly holds tasks

  async def submit_prefill(self, fn, tokens: int = 0, key: Optional[tuple] = None,
                           start: int = 0) -> Any:
    """Admit one bounded prefill slice into the drain-cycle rotation. FIFO
    across requests; a single request's slices stay ordered because its
    driver awaits each before submitting the next. With an idle decode side
    the loop degenerates to back-to-back slices (one event-loop tick of
    overhead per slice — noise next to segment compute). `tokens`/`key`/
    `start` carry the slice's perf-attribution facts (position count,
    executable identity, already-resident offset — later slices attend over
    the KV earlier ones wrote) to the drain loop's _observe_dispatch;
    key=None (the prologue: prefix reuse / state alloc, not a prefill
    executable) stays unobserved."""
    fut = asyncio.get_running_loop().create_future()
    self.pending_prefill.append((fn, fut, time.monotonic(), tokens, key, start))
    if not self._draining:
      self._draining = True
      self._drain_task = spawn_detached(self._drain())
    return await fut

  async def submit(self, request_id: str, state: "_RequestState", prev_token: int,
                   num_tokens: int, temp: float, top_k: int, top_p: float = 0.0,
                   next_size: Optional[int] = None) -> np.ndarray:
    fut = asyncio.get_running_loop().create_future()
    # Enqueue timestamp rides the item (index 8, always just before fut) so
    # the drain loop can observe true queue wait per lane — the
    # xot_queue_wait_seconds SLO signal admission control keys off.
    self.pending.append((request_id, state, prev_token, num_tokens, temp, top_k, top_p,
                         next_size, time.monotonic(), fut))
    if not self._draining:
      self._draining = True
      self._drain_task = spawn_detached(self._drain())
    return await fut

  async def _drain(self) -> None:
    try:
      # One event-loop yield before the first take: concurrent loops woken in
      # the same pass (e.g. all prefills just finished) coalesce immediately.
      try:
        window = knobs.get_float("XOT_BATCH_WINDOW_MS") / 1000.0
      except ValueError:
        window = 0.0
      await asyncio.sleep(window)
      batch: list = []
      while self.pending or self.pending_prefill:
        batch, self.pending = self.pending, []
        m = self.engine.metrics
        if m is not None and batch:
          take_t = time.monotonic()
          for it in batch:
            m.queue_wait_decode.observe(take_t - it[8])
        # Only (top_k, top_p) are compile-time sampling constants:
        # temperature is TRACED per row (ops/sampling.sample_logits), so
        # requests at different temperatures — and different points of the
        # adaptive chunk ladder (min size wins; bigger requesters loop
        # again) — still share ONE dispatch and one weight read, which is
        # the whole win.
        groups: Dict[Tuple[int, float], list] = {}
        for item in batch:
          groups.setdefault((item[5], item[6]), []).append(item)
        cap = self.engine._decode_batch_max()
        # The context holds ONE speculative batch slot: speculating is only
        # profitable when this drain cycle is a single dispatch (one
        # sampling group, within cap). Multiple groups/slices would evict
        # each other's in-flight batch every cycle — pure wasted device
        # work at exactly the high-concurrency regime.
        single_dispatch = (len(groups) == 1
                           and all(len(g) <= cap for g in groups.values()))
        for (top_k, top_p), items in groups.items():
          # Stable row order: speculative batch chunks match on the ordered
          # request tuple, and asyncio wake-up order is not deterministic.
          items.sort(key=lambda it: it[0])
          num_tokens = min(item[3] for item in items)
          for off in range(0, len(items), cap):
            chunk_items = items[off:off + cap]
            try:
              t0 = time.monotonic()
              kernels: list = []
              if self.dispatch is not None:
                results = await self.dispatch(chunk_items, num_tokens, top_k, top_p,
                                              single_dispatch, kernels)
              else:
                results = await self.engine._run(
                  self.engine._decode_batch_sync, self.ctx, chunk_items, num_tokens, top_k, top_p,
                  single_dispatch, kernels=kernels,
                )
              secs = time.monotonic() - t0
              fl = self.engine.flight
              if fl is not None:
                # Node-scoped (request_id=None) so the event survives into
                # EVERY co-batched request's frozen snapshot — a stalled
                # member's postmortem must show the dispatches that ran
                # while it was resident, whichever request led the chunk.
                fl.record("batcher.dispatch", None,
                          lead=chunk_items[0][0], batch=len(chunk_items),
                          tokens=num_tokens, secs=round(secs, 6))
              # First-compile classification: a new (padded batch width,
              # chunk size, sampling constants) tuple means a fresh
              # executable — the compile stall the watchdog soak needs to
              # see. The width is padded to the same power-of-two bucket
              # the decode paths compile for (B_pad), so a batch of 3
              # riding the padded-4 executable counts as the cache hit it
              # is.
              self.engine._observe_dispatch(
                "decode", ("decode", self.dispatch is not None,
                           _bucket(len(chunk_items), 1),
                           num_tokens, int(top_k), float(top_p), tuple(kernels)),
                secs, batch=len(chunk_items), tokens=num_tokens,
                ctx=self.ctx, items=chunk_items)
              for (*_, fut), toks in zip(chunk_items, results):
                if not fut.done():
                  fut.set_result(toks)
            except Exception as e:
              for *_, fut in chunk_items:
                if not fut.done():
                  fut.set_exception(e)
        # Co-scheduling: decode dispatched first, now admit ONE prefill
        # slice — the decode stall this cycle is bounded by that slice
        # (XOT_PREFILL_CHUNK_BUDGET segments), never a whole prompt. Slice
        # errors (pool exhaustion, capacity) land on the slice's own future
        # and fail only its request; the drain loop keeps serving.
        if self.pending_prefill:
          fn, fut, enq_t, p_tokens, p_key, p_start = self.pending_prefill.pop(0)
          if m is not None:
            m.queue_wait_prefill.observe(time.monotonic() - enq_t)
          try:
            t0 = time.monotonic()
            kernels = []
            res = await self.engine._run(fn, kernels=kernels)
            secs = time.monotonic() - t0
            fl = self.engine.flight
            if fl is not None:
              fl.record("batcher.prefill_slice", None, secs=round(secs, 6))
            if p_key is not None:
              self.engine._observe_dispatch("prefill", p_key + (tuple(kernels),), secs,
                                            tokens=p_tokens, ctx=self.ctx,
                                            start=p_start)
            if not fut.done():
              fut.set_result(res)
          except Exception as e:
            if not fut.done():
              fut.set_exception(e)
        # Let the resolved requests' loops ingest tokens and re-submit before
        # the next take, so steady-state batches stay wide.
        await asyncio.sleep(0)
      # Queues drained — the batcher is idle. Spend the slot on page-pool
      # compaction: a bounded defrag pass (XOT_KV_DEFRAG) rewrites only the
      # virtual maps on the executor thread, so it is invisible to requests
      # and never delays a dispatch that has work queued.
      if (self.ctx is not None and self.ctx.page_pool is not None
          and self.engine._defrag_on()
          and self.ctx.page_pool.fragmentation() > 0):
        try:
          await self.engine._run(self.engine._defrag_sync, self.ctx)
        except Exception as e:
          if DEBUG >= 1:
            print(f"idle defrag pass failed (ignored): {e!r}")
    except Exception as e:
      # A failure OUTSIDE the per-group dispatch (whose errors already land
      # on their futures) must fail every affected submitter loudly — both
      # the not-yet-taken `pending` AND the taken-but-undispatched remainder
      # of `batch`, and any queued prefill slices. A hanging `await fut`
      # with no error would freeze the whole server. set_exception is
      # idempotent via the done() check.
      failed, self.pending = self.pending, []
      failed_prefill, self.pending_prefill = self.pending_prefill, []
      for *_, fut in batch + failed:
        if not fut.done():
          fut.set_exception(e)
      for _, fut, *_meta in failed_prefill:
        if not fut.done():
          fut.set_exception(e)
    finally:
      self._draining = False
      if self.pending or self.pending_prefill:
        # A submit slipped in between the empty-check and here; it saw
        # _draining=True and didn't start a drain — do it for them.
        self._draining = True
        self._drain_task = spawn_detached(self._drain())


class JAXShardInferenceEngine(InferenceEngine):
  def __init__(self, shard_downloader: Optional[ShardDownloader] = None, dtype: Optional[str] = None,
               quantize: Optional[str] = None, kv_quant: Optional[str] = None):
    self.shard_downloader = shard_downloader or NoopShardDownloader()
    self.session: Dict[str, Any] = {}
    self._contexts: "OrderedDict[Shard, _ShardContext]" = OrderedDict()
    self._active: Optional[_ShardContext] = None
    self.executor = ThreadPoolExecutor(max_workers=1, thread_name_prefix="jax-engine")
    self._dtype_name = dtype or knobs.get_str("XOT_DTYPE")
    # Weight-only quantization (models/quantize.py): "int8" halves the HBM
    # bytes per decoded token — the binding resource at batch 1. CLI
    # --quantize / env XOT_QUANTIZE.
    self._quantize = (quantize or knobs.get_str("XOT_QUANTIZE", "")).lower() or None
    if self._quantize is not None:
      from xotorch_tpu.models.quantize import QUANT_DTYPES
      if self._quantize not in QUANT_DTYPES:
        # Fail at construction, not at first shard load minutes later.
        raise ValueError(f"Unsupported quantization {self._quantize!r}; have {sorted(QUANT_DTYPES)}")
    # int8 KV cache (models/transformer.init_kv_cache kv_quant): halves
    # cache bandwidth + HBM per resident token — the binding resource for
    # LONG contexts. CLI --kv-quantize / env XOT_KV_QUANT.
    self._kv_quant = (kv_quant or knobs.get_str("XOT_KV_QUANT", "")).lower() or None
    if self._kv_quant not in (None, "int8"):
      raise ValueError(f"Unsupported KV quantization {self._kv_quant!r}; have ['int8']")
    # cache_len is the INITIAL per-request KV allocation; caches grow by
    # doubling (bounded executables: one decode program per power-of-two
    # size) up to max_cache_len = min(XOT_MAX_CACHE_LEN, cfg.max_seq_len).
    self._configured_cache_len = knobs.get_int("XOT_CACHE_LEN")
    self._configured_max_cache_len = knobs.get_int("XOT_MAX_CACHE_LEN")
    self._shard_lock = asyncio.Lock()
    self._seed = knobs.get_int("XOT_SEED", int(time.time()))
    self._sample_calls = 0
    self._oom_count = 0
    # Contiguous-cache grow-copies (each a full device-side copy of a
    # request's KV). The paged path (XOT_PAGED_KV) appends into pool pages
    # instead — its tests assert this stays ZERO across decode.
    self._grow_copies = 0
    # Device bytes copied moving prefilled contiguous KV into pool pages
    # (_commit_state_to_pages). Paged-NATIVE prefill (XOT_PAGED_PREFILL)
    # scatters segments straight into pages, so a plain paged request keeps
    # this at ZERO end to end — the tests' acceptance bar.
    self._commit_copy_bytes = 0
    # Prefix-cache observability (tests + /metrics): hits and tokens whose
    # prefill was skipped entirely, plus entries evicted (LRU bound, pool
    # pressure, OOM recovery — the events the host tier exists to absorb).
    self._prefix_hits = 0
    self._prefix_tokens_saved = 0
    self._prefix_evictions = 0
    # Host-tier KV offload (kv_offload.HostKVStore, XOT_KV_HOST_BYTES):
    # evicted prefix entries spill D2H instead of being destroyed, and a
    # prefix lookup that misses HBM but hits the host tier streams the KV
    # back into fresh pool pages before prefilling only the suffix. Lazy —
    # engines that never evict a prefix never allocate the store.
    self._host_kv = None
    self._host_kv_hits = 0
    self._host_spill_bytes = 0
    self._host_fetch_bytes = 0
    # Host hits split by the entry's origin tier ("local" spill vs "fabric"
    # cross-replica import) — exported as labeled xot_kv_host_hits_total
    # series next to the bare total.
    self._host_hits_by_source: Dict[str, int] = {}
    # Fleet-wide KV fabric (xotorch_tpu/fabric, XOT_FABRIC_PEERS): a prefix
    # that misses HBM *and* the local host tier consults sibling replicas
    # and imports the longest covering entry into the host store, then takes
    # the ordinary _host_promote restore path. Lazy like the host store —
    # engines with no peers and no offers never build a client.
    self._fabric = None
    self._fabric_hits = 0
    self._fabric_misses = 0
    self._fabric_errors = 0
    self._fabric_bytes = 0
    # Speculative-decode observability: drafted vs model-confirmed tokens,
    # plus a live efficiency gauge — paired EWMAs of the proposed/accepted
    # token rates whose ratio is xot_spec_accept_rate (both decay with the
    # same time constant, so the ratio stays meaningful across idle gaps).
    # Lazy: engines that never verify a draft never allocate the pair.
    self._spec_proposed = 0
    self._spec_accepted = 0
    self._spec_ewma: Optional[Tuple[Any, Any]] = None
    # Paged→contiguous gathers (_unpage_state invocations). Paged-native
    # speculation keeps draft verification on the page table, so a plain
    # paged request — speculating or not — finishes with this at ZERO
    # (counter-asserted in tests, exported as xot_kv_unpage_total).
    self._unpage_calls = 0
    # Background defrag (XOT_KV_DEFRAG): pages migrated by idle compaction
    # passes. Each move is one page's device copy + a host-side rewrite of
    # every virtual map naming it — exported as xot_kv_defrag_moves_total.
    self._defrag_moves = 0
    # Requests whose device state was dropped by OOM recovery (bounded LRU):
    # their next touch raises RequestStateLost instead of silently starting
    # over from an empty cache.
    self._states_lost_to_oom: "OrderedDict[str, None]" = OrderedDict()
    # OpenAI logprob reports per request (bounded LRU of lists of per-token
    # entries). Kept OUTSIDE _RequestState: the API drains them when it
    # formats the response, which can happen after the node already cleared
    # the request's device state. Locked: the recorder runs on the engine
    # executor thread while the API pops from the event-loop thread.
    self._logprob_store: "OrderedDict[str, list]" = OrderedDict()
    self._logprob_lock = threading.Lock()
    # Speculatively dispatched next decode chunks (request_id -> record):
    # while the host ingests chunk N's tokens (EOS scan, broadcast), chunk
    # N+1 already runs on device — its input (chunk N's last token) is a
    # DEVICE array, so no host value is needed to start it. Mispredictions
    # (EOS stopped the request, the node shrank the next chunk, a verify
    # step interleaved) just roll back state.pos; the cache slots written
    # past pos are invisible to the validity mask and get overwritten, the
    # same free-rollback design as verify_draft.
    self._spec_next: Dict[str, dict] = {}
    # Same overlap records for fused RING chunks (generate_chunk_ring):
    # request_id -> {"toks","n","pos","temp","top_k","top_p","prev","states"}.
    # Held on the DRIVING engine (the last shard's); the listed states may
    # belong to peer engines' contexts — the ring loop is the request's sole
    # driver, so only this engine's executor ever resolves/rolls them back.
    self._ring_spec: Dict[str, dict] = {}
    # Continuous-batching collectors for fused RING chunks, keyed by the
    # co-located chain identity (one per served multi-partition model).
    self._ring_batchers: Dict[tuple, Any] = {}
    self._overlap_hits = 0
    self._overlap_misses = 0
    self._overlap_batch_hits = 0
    self._overlap_batch_misses = 0
    # First-compile observability: executable identity keys already
    # dispatched once. The FIRST dispatch of a new key pays XLA compilation
    # (the stall that can false-trip the PR 4 watchdog on compile-heavy
    # first requests); later dispatches hit the jit cache. Split counters
    # export via /metrics, and each miss records an `engine.compile` flight
    # event carrying the observed wall time.
    self._exec_seen: set = set()
    # Pallas kernels the gates selected for the dispatch now on the executor
    # (_selected / _run): executor-thread state, reset per dispatch.
    self._kernel_tags: set = set()
    self._jit_first_dispatches = 0
    self._jit_cached_dispatches = 0
    # Device computations currently on the executor (event-loop-thread
    # increments around _run): the stall watchdog's "actively computing,
    # not stalled" signal — a cold-jit compile shows up here for its whole
    # wall time.
    self._dispatches_inflight = 0
    # Live roofline attribution (XOT_PERF_ATTR, default on): cumulative
    # per-executable time/bytes plus EWMA throughput/utilization gauges,
    # fed ONLY from the _observe_dispatch boundaries below — the wall
    # timestamps the batcher already takes, so the decode hot path gains
    # zero device syncs. Served at /v1/perf and as /metrics gauges.
    self.perf = None
    if knobs.get_bool("XOT_PERF_ATTR"):
      from xotorch_tpu.inference.jax_engine.costmodel import PerfAttribution
      self.perf = PerfAttribution(knobs.get_float("XOT_PERF_EWMA_S"))
    self._chip_peaks: Optional[Tuple[Optional[float], Optional[float]]] = None

  # ------------------------------------- active-context delegation (compat)

  @property
  def shard(self) -> Optional[Shard]:
    return self._active.shard if self._active else None

  @property
  def cfg(self) -> Optional[ModelConfig]:
    return self._active.cfg if self._active else None

  @property
  def params(self) -> Any:
    return self._active.params if self._active else None

  @property
  def states(self) -> "OrderedDict[str, _RequestState]":
    return self._active.states if self._active else OrderedDict()

  @property
  def tokenizer(self):
    return self._active.tokenizer if self._active else None

  @tokenizer.setter
  def tokenizer(self, value):
    if self._active is not None:
      self._active.tokenizer = value

  @property
  def _mesh(self):
    return self._active.mesh if self._active else None

  @property
  def cache_len(self) -> int:
    return self._active.cache_len if self._active else self._configured_cache_len

  @property
  def max_cache_len(self) -> int:
    return self._active.max_cache_len if self._active else self._configured_max_cache_len

  # ---------------------------------------------------------------- helpers

  def _jax(self):
    """jax, with the persistent compilation cache on (utils/compile_cache):
    a restarted server or respawned fleet replica loads its executables
    from disk instead of paying the cold-jit stall. Wired here, lazily, so
    import order can't matter."""
    import jax
    from xotorch_tpu.utils import compile_cache
    compile_cache.enable()
    return jax

  def _dtype(self):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[self._dtype_name]

  def _pallas_kernels_ok(self, cfg: ModelConfig) -> bool:
    """Every family takes the Pallas fast path: the flash kernels implement
    the sliding-window lower bound (traced per-layer scalar; out-of-window
    blocks' DMAs elided) and the gemma2 tanh soft-cap / query_pre_attn
    scale as compile-time constants (ops/flash_attention.py,
    ops/flash_decode.py). Kept as a seam for future configs the kernels
    can't serve."""
    return True

  def _selected(self, kernel: str, on: bool) -> bool:
    """A kernel gate's verdict, noted for the dispatch now on the executor:
    `_run` hands the names of the Pallas kernels its dispatch selected back
    to the observer, which folds them into the executable-identity key — so
    the `engine.compile` flight event (/v1/debug/flight) and /v1/perf say
    which kernels each executable was built with. The gates keep "XLA path
    off-TPU" for tests; on the chip a selected kernel that fails to compile
    fails its request — nothing retries on a reference path."""
    if on:
      self._kernel_tags.add(kernel)
    return on

  def _flash_enabled(self) -> bool:
    """XOT_FLASH_ATTENTION: 1 = force on (interpret mode off-TPU), 0 = off,
    unset = on when running on real TPU."""
    env = knobs.raw("XOT_FLASH_ATTENTION")
    on = env == "1" if env is not None else self._jax().default_backend() == "tpu"
    return self._selected("flash_prefill", on)

  def _flash_decode_on(self, cache_s: int) -> bool:
    """Occupancy-aware Pallas cached-attention kernel selection (decode
    steps and pos>0 prefill segments). XOT_FLASH_DECODE: 1 = force on
    (interpret mode off-TPU), 0 = off, unset = on real TPU when the
    resident cache is at least XOT_FLASH_DECODE_MIN (default 4096 — below
    that the fused XLA path is already bandwidth-optimal and the
    kernel-launch overhead isn't worth it). int8 caches qualify too: the
    kernel takes their raw buffers + scales and dequantizes per tile
    (ops/flash_decode._scores), keeping the int8 bandwidth AND the
    occupancy DMA elision the XLA path lacks."""
    env = knobs.raw("XOT_FLASH_DECODE")
    if env == "0":
      return False
    on = env == "1" or self._jax().default_backend() == "tpu"
    return self._selected(
      "flash_cached", on and cache_s >= knobs.get_int("XOT_FLASH_DECODE_MIN"))

  @staticmethod
  def _moe_routed_for(ctx: "_ShardContext") -> bool:
    """Static flag for the decode executables: the top-k gather path reads
    only the chosen experts' weights — but a gather across an E axis that is
    SHARDED over 'ep' would make XLA all-gather the expert tensors, so ep
    meshes keep the dense-combine form (each device computes its resident
    experts, the combine einsum implies the psum)."""
    mesh = ctx.mesh
    return not (mesh is not None and "ep" in mesh.axis_names and mesh.shape["ep"] > 1)

  def _serving_mesh(self, cfg: ModelConfig, shard: Optional[Shard] = None):
    """Multi-chip serving mesh (VERDICT r1 #2 / SURVEY §7.2 stage 7, the ICI
    fast path): a peer that owns several local chips serves its layer-range
    shard SPMD over a local mesh instead of leaving all but one chip idle.

    Axes: 'tp' (Megatron tensor parallel — XOT_TP, falling back to
    XOT_SERVE_TP: 0 = off, N = force, unset = all local devices on real
    TPU) and optionally 'sp' (XOT_SERVE_SP=N): sequence-parallel PREFILL,
    where a long prompt's positions shard over sp chips and attention runs
    as ring attention over ICI (ops/ring_attention) — the serving-side twin
    of the training sp axis. Requested sizes reduce to the largest feasible
    divisors so placements stay even."""
    env = knobs.raw("XOT_TP")
    if env is None:
      env = knobs.raw("XOT_SERVE_TP")
    sp_env = knobs.get_int("XOT_SERVE_SP")
    # 'ep' (XOT_SERVE_EP=N, MoE models only): expert tensors distribute over
    # N local chips' HBM (parallel/mesh.spec_for_param 'we_*' rules) — each
    # chip computes its RESIDENT experts and the combine einsum's psum rides
    # ICI. Fixes the reference's dead-stub MoE gap properly
    # (llm_utils.py:502-590) and round 3's dense-everywhere serving
    # (VERDICT r3 #6).
    ep_env = knobs.get_int("XOT_SERVE_EP")
    if not cfg.is_moe:
      ep_env = 0
    # The ring executables need a whole-model shard (token input, from-zero
    # context): a pipeline mid-shard must not reserve sp devices it can
    # never use — they would hold replicated copies of the tp work.
    if shard is not None and not (shard.is_first_layer and shard.is_last_layer):
      sp_env = 0
    jax = self._jax()
    n_local = len(jax.local_devices())
    if env is not None:
      t = int(env)
      t = min(max(t, 1), n_local)
    elif jax.default_backend() == "tpu" and n_local > 1:
      # Auto-tp takes the local chips — but leaves room for explicitly
      # requested sp/ep axes (otherwise XOT_SERVE_SP/EP alone would silently
      # reduce to 1 after tp claimed every device).
      t = n_local
      if sp_env > 1:
        t //= sp_env
      if ep_env > 1:
        t //= max(ep_env, 1)
      t = max(t, 1)
    else:
      t = 1
    dims = [cfg.num_kv_heads, cfg.num_heads, cfg.hidden_size,
            cfg.num_heads * cfg.head_dim, cfg.intermediate_size, cfg.vocab_size]
    if cfg.is_moe and cfg.moe_intermediate_size:
      dims.append(cfg.moe_intermediate_size)
    while t > 1 and any(d % t for d in dims):
      t -= 1
    ep = min(ep_env, n_local // max(t, 1)) if ep_env > 1 else 1
    # ep must divide the expert count or the placement would be ragged.
    while ep > 1 and cfg.num_experts % ep:
      ep -= 1
    sp = min(sp_env, n_local // (max(t, 1) * max(ep, 1))) if sp_env > 1 else 1
    # Prefill segments are padded to power-of-two buckets; a non-po2 sp
    # would never divide them and the ring jits would sit unused while the
    # axis held replicated copies — clamp to the largest power of two.
    while sp > 1 and sp & (sp - 1):
      sp -= 1
    if t <= 1 and sp <= 1 and ep <= 1:
      return None
    from xotorch_tpu.parallel.mesh import make_mesh
    axes = {}
    if ep > 1:
      axes["ep"] = ep
    if sp > 1:
      axes["sp"] = sp
    axes["tp"] = max(t, 1)
    return make_mesh(axes, jax.local_devices())

  def _engine_span(self, name: str, request_id: Optional[str],
                   attributes: Optional[dict] = None):
    """A child span of the request's trace for an engine-depth phase, or a
    no-op context when tracing is off / no trace context exists (an orphan
    engine span without a request parent would pollute the buffer with
    single-span traces)."""
    from contextlib import nullcontext
    tr = self.tracer
    if tr is None or not tr.enabled or self.trace_ctx is None or not request_id:
      return nullcontext()
    ctx = self.trace_ctx(request_id)
    if ctx is None:
      return nullcontext()
    return tr.start_span(name, parent=ctx,
                         attributes={"request.id": request_id, **(attributes or {})})

  def _observe_dispatch(self, kind: str, key: tuple, seconds: float,
                        batch: int = 1, tokens: int = 0,
                        ctx: "Optional[_ShardContext]" = None,
                        items: Optional[list] = None,
                        start: int = 0, emitted: Optional[int] = None) -> None:
    """Classify one device dispatch as jit-cache miss (first sighting of
    this executable identity key) or hit, and record the miss — with its
    wall time, which includes the compile — as a flight event. The key is a
    static-shape proxy for the executable (batch width, chunk/bucket size,
    sampling constants): exactly the tuple a recompile keys off.

    The same boundary feeds the roofline attribution: `seconds` is a wall
    interval the caller already measured, and the cost model turns the
    dispatch's static facts (batch rows' depths/layouts, token count) into
    predicted HBM bytes and FLOPs — all host metadata, zero device syncs."""
    if key not in self._exec_seen:
      self._exec_seen.add(key)
      self._jit_first_dispatches += 1
      if self.flight is not None:
        self.flight.record("engine.compile", None, kind=kind, batch=batch,
                           tokens=tokens, secs=round(seconds, 4), key=key)
    else:
      self._jit_cached_dispatches += 1
    perf = self.perf
    if perf is None:
      return
    cm = ctx.costmodel if ctx is not None else None
    hbm_bytes = flops = 0
    total_tokens = tokens
    if cm is not None:
      if kind == "decode":
        rows = self._perf_rows(items) if items else [(0, False, None)] * max(batch, 1)
        hbm_bytes, flops = cm.decode_dispatch_cost(
          tokens, rows, page=knobs.get_int("XOT_KV_PAGE"))
        total_tokens = tokens * max(batch, 1)
      elif kind == "verify":
        # One K-token draft-verify forward: a single weight stream (the
        # whole speculation win) + KV read at the layout the request is
        # actually served from — items carries its (depth, paged, alloc)
        # row. The lane's token count is the ACCEPTED output (`emitted`),
        # so /v1/perf's verify lane reads as accepted tok/s directly.
        depth, paged, alloc = (items[0] if items else (0, False, None))
        hbm_bytes, flops = cm.verify_dispatch_cost(
          tokens, depth, paged=paged, alloc_tokens=alloc,
          page=knobs.get_int("XOT_KV_PAGE"))
      else:
        hbm_bytes, flops = cm.prefill_dispatch_cost(tokens, self._prefill_chunk(),
                                                    start=start)
    if emitted is not None:
      total_tokens = emitted
    perf.observe(key, kind, seconds, tokens=total_tokens, batch=batch,
                 hbm_bytes=hbm_bytes, flops=flops)

  @staticmethod
  def _perf_rows(items: list) -> list:
    """(depth, paged, alloc_tokens) per batcher item, for the cost model's
    KV-read prediction. Reads only host metadata (`state.pos` ints, cache
    SHAPES); items whose state slot is not a _RequestState (the fused-ring
    batcher carries seg lists there) contribute a depth-0 row."""
    rows = []
    for it in items:
      st = it[1]
      pos = getattr(st, "pos", None)
      if pos is None:
        rows.append((0, False, None))
        continue
      cache = getattr(st, "cache", None)
      paged = cache is None and getattr(st, "pages", None) is not None
      alloc = None
      if cache is not None:
        try:
          alloc = int(cache["k"].shape[2])
        except (KeyError, TypeError, IndexError):
          alloc = None
      rows.append((int(pos), bool(paged), alloc))
    return rows

  def _chip_peak_specs(self) -> Tuple[Optional[float], Optional[float]]:
    """(peak bf16 TFLOP/s, peak HBM GB/s) of the local chip, or (None, None)
    off-TPU — the denominators of the utilization gauges, from the one
    device_kind-keyed table (topology.device_capabilities). A TPU kind the
    table does not know RAISES: a borrowed denominator would mis-state every
    utilisation without a trace. Cached: reading device kind strings is
    cheap but this runs on every /metrics scrape."""
    if self._chip_peaks is None:
      if not self._contexts:
        # No shard loaded yet: jax.devices() here would initialize the
        # backend (seconds on real TPU) on the EVENT-LOOP thread just to
        # serve a scrape, stalling every handler. Report unknown, uncached,
        # so the first post-load scrape picks the real peaks up.
        return (None, None)
      d0 = self._jax().devices()[0]
      if d0.platform == "tpu":
        from xotorch_tpu.topology.device_capabilities import tpu_chip_peaks
        self._chip_peaks = tpu_chip_peaks(d0.device_kind)
      else:
        self._chip_peaks = (None, None)
    return self._chip_peaks

  def perf_stats(self) -> Optional[Dict[str, float]]:
    """EWMA gauge values for /metrics (xot_decode_tok_s and friends), or
    None when attribution is off (XOT_PERF_ATTR=0)."""
    if self.perf is None:
      return None
    peak_tflops, peak_gbps = self._chip_peak_specs()
    return self.perf.gauges(peak_gbps, peak_tflops)

  def perf_compact(self) -> Optional[Dict[str, Any]]:
    """Small perf summary for the status-bus rollup (rides node_metrics on
    the topology cadence, so /v1/perf on any node shows the whole ring)."""
    if self.perf is None:
      return None
    out = self.perf.compact()
    gauges = self.perf_stats() or {}
    out["hbm_util_pct"] = gauges.get("hbm_util_pct", 0.0)
    out["mfu_pct"] = gauges.get("mfu_pct", 0.0)
    spec = self.spec_stats()
    if spec is not None:
      out["spec_accept_rate"] = spec["accept_rate"]
      out["spec_proposed"] = self._spec_proposed
      out["spec_accepted"] = self._spec_accepted
    return out

  def history_gauges(self) -> Optional[Dict[str, Any]]:
    """Host-side gauge snapshot for the metrics-history sampler
    (orchestration/history.py): live EWMA throughput/utilization plus the
    cumulative counters the sampler differences per tick (jit dispatch
    classification, host-tier fetch bytes — CUMULATIVE_ENGINE_KEYS). Reads
    attribute ints and EWMA cells only; never touches the device. None
    when attribution is off (XOT_PERF_ATTR=0) — the sampler then records
    the node-level gauges alone."""
    if self.perf is None:
      return None
    out: Dict[str, Any] = dict(self.perf_stats() or {})
    spec = self.spec_stats()
    if spec is not None:
      out["spec_accept_rate"] = spec["accept_rate"]
    out["jit_first_dispatches"] = self._jit_first_dispatches
    out["jit_cached_dispatches"] = self._jit_cached_dispatches
    out["host_fetch_bytes"] = self._host_fetch_bytes
    return out

  def _observe_spec(self, proposed: int, accepted: int) -> None:
    """Feed one verify round into the paired accept-rate EWMAs (every
    verify path calls this right after bumping the cumulative counters)."""
    from xotorch_tpu.inference.jax_engine.costmodel import _Ewma
    if self._spec_ewma is None:
      tau = knobs.get_float("XOT_SPEC_EWMA_S")
      self._spec_ewma = (_Ewma(tau), _Ewma(tau))
    now = time.monotonic()
    self._spec_ewma[0].observe(float(proposed), 1e-3, now)
    self._spec_ewma[1].observe(float(accepted), 1e-3, now)

  def spec_stats(self) -> Optional[Dict[str, float]]:
    """Live speculation-efficiency gauge (xot_spec_accept_rate): EWMA
    accepted-token rate over EWMA proposed-token rate. None until a draft
    has been verified — the gauge only exists once speculation ran, the
    same presence rule as the other engine-feature gauges."""
    if self._spec_ewma is None:
      return None
    now = time.monotonic()
    prop = self._spec_ewma[0].peek(now)
    acc = self._spec_ewma[1].peek(now)
    return {"accept_rate": round(acc / prop, 4) if prop > 1e-12 else 0.0}

  def perf_report(self) -> Optional[Dict[str, Any]]:
    """The full /v1/perf attribution report: the loaded model's analytic
    roofline (bf16/int8/int4 ceilings), predicted vs actual resident weight
    bytes, achieved EWMA throughput/utilization, per-lane cumulative totals,
    the heaviest executables, jit dispatch classification, and pool +
    host-tier byte flows. Host metadata only — safe on the serving path."""
    if self.perf is None:
      return None
    peak_tflops, peak_gbps = self._chip_peak_specs()
    report: Dict[str, Any] = {
      "gauges": self.perf.gauges(peak_gbps, peak_tflops),
      "lanes": self.perf.lanes(),
      "executables": self.perf.executables(),
      "dispatch": {
        "jit_first_dispatches": self._jit_first_dispatches,
        "jit_cached_dispatches": self._jit_cached_dispatches,
      },
      "byte_flows": {
        "host_spill_bytes": self._host_spill_bytes,
        "host_fetch_bytes": self._host_fetch_bytes,
        "commit_copy_bytes": self._commit_copy_bytes,
        "unpage_gathers": self._unpage_calls,
        "pool": self.page_pool_stats(),
        "host_tier": self.host_kv_stats(),
      },
      # Drafted-vs-accepted next to the verify lane's accepted tok/s, so
      # acceptance-adjusted throughput can be gated from one endpoint.
      "speculation": {
        "proposed": self._spec_proposed,
        "accepted": self._spec_accepted,
        "accept_rate_ewma": (self.spec_stats() or {}).get("accept_rate"),
      },
      "model": None,
      "ceilings": None,
    }
    ctx = self._active
    if ctx is not None and ctx.costmodel is not None:
      from xotorch_tpu.models.quantize import quantized_bytes
      from xotorch_tpu.parallel.mesh import device_bytes
      cm = ctx.costmodel
      report["model"] = {
        "model_id": ctx.shard.model_id,
        "layers": [ctx.shard.start_layer, ctx.shard.end_layer],
        "dtype": self._dtype_name,
        "quantize": self._quantize,
        "kv_quant": self._kv_quant,
        "tp": cm.tp,
        "n_params": cm.n_params(),
        "weight_bytes_predicted": cm.weight_bytes(),
        # Metadata-only walk over the resident pytree (size × itemsize) —
        # the live cross-check that the analytic layout math is honest.
        "weight_bytes_actual": quantized_bytes(ctx.params),
        # Mesh twin of the same cross-check: per-device predicted vs the
        # pytree's actual per-leaf shard sizes (sharding.shard_shape).
        "weight_bytes_per_device_predicted": cm.weight_bytes_per_device(),
        "weight_bytes_per_device_actual": device_bytes(ctx.params),
        "kv_write_bytes_per_token": cm.kv_write_bytes_per_token(),
        "kv_read_bytes_per_token_at_cache_len": cm.kv_read_bytes_per_token(
          ctx.cache_len, alloc_tokens=ctx.cache_len),
        "kv_read_bytes_per_token_at_cache_len_per_device":
          cm.kv_read_bytes_per_token_per_device(
            ctx.cache_len, alloc_tokens=ctx.cache_len),
        "collective_bytes_per_token": cm.collective_bytes_per_token(),
      }
      report["ceilings"] = cm.ceilings(peak_gbps)
    return report

  async def _run(self, fn, *args, oom_as_cache_exhausted: bool = True,
                 kernels: Optional[list] = None):
    """Every device computation funnels through the single-worker executor.
    HBM exhaustion is caught HERE: the engine frees what it can (prefix
    snapshots, resident request states, idle model contexts) so SUBSEQUENT
    requests find a healthy engine. Serving computations surface the OOM as
    CacheExhausted (the graceful length/400 path); load/train callers pass
    oom_as_cache_exhausted=False and get a RuntimeError instead — a model
    that does not FIT is a capacity problem, not the client's prompt
    length. `kernels` (a list the caller owns) receives the names of the
    Pallas kernels the dispatch's gates selected, for the observer's
    executable-identity key. TPU-native analogue of the reference's CUDA-OOM clear_model
    recovery (sharded_inference_engine.py:85-106, 330-334).

    The in-flight counter brackets the executor call so the stall watchdog
    (Node._watchdog_loop via `dispatch_inflight`) can tell "the engine is
    actively computing — a cold-jit compile included" apart from a silent
    distributed stall: a compile-heavy first request must never be aborted
    as stalled while its own prefill is still on the worker thread."""
    def call():
      # Executor thread, one dispatch at a time: the gates' verdicts between
      # these two lines belong to THIS dispatch (see _selected).
      self._kernel_tags.clear()
      out = fn(*args)
      if kernels is not None:
        kernels.extend(sorted(self._kernel_tags))
      return out

    self._dispatches_inflight += 1
    try:
      return await asyncio.get_running_loop().run_in_executor(self.executor, call)
    except Exception as e:
      if "RESOURCE_EXHAUSTED" in str(e) or "Out of memory" in str(e):
        try:
          # Runs ON the event loop, no awaits: cooperative scheduling makes
          # the dict mutations atomic w.r.t. every other coroutine, and the
          # single executor worker is idle (its task just failed).
          freed = self._free_device_memory()
        except Exception as free_err:  # recovery must never mask the OOM
          freed = f"recovery itself failed: {free_err!r}"
        msg = f"device memory exhausted (recovery #{self._oom_count}: freed {freed}); original: {e}"
        if oom_as_cache_exhausted:
          raise CacheExhausted(msg) from e
        raise RuntimeError(msg) from e
      raise
    finally:
      self._dispatches_inflight -= 1

  def dispatch_inflight(self) -> bool:
    """True while the executor worker is running a device computation
    (forward, prefill slice, compile). Consumed by the Node stall watchdog:
    time spent here is active local work, not a distributed stall."""
    return self._dispatches_inflight > 0

  def _free_device_memory(self) -> str:
    """Aggressive, reference-style recovery: drop every prefix-cache
    snapshot, every resident request state, and all but the active model
    context. Cleared requests are remembered (bounded) so their next touch
    fails loudly with RequestStateLost instead of silently restarting from
    an empty cache.

    SPILL-THEN-DROP: before a prefix entry is destroyed its KV is copied
    D2H into the host tier (kv_offload.HostKVStore), so recovery frees the
    same HBM as before but the warm set survives — the next request sharing
    a spilled prefix restores it into fresh pool pages instead of paying a
    cold 16 k prefill. Best-effort per entry: the device is mid-OOM, so a
    spill whose own gather fails is simply skipped (recovery must free
    memory above all else)."""
    # Counted HERE (not at _run's catch site) so forced/direct invocations
    # — bench's kvhost stage, tests — are visible in
    # xot_oom_recoveries_total exactly as the metric's help text promises.
    self._oom_count += 1
    n_snap = n_state = n_ctx = n_spill = 0
    # In-flight speculative chunks hold device token arrays and reference
    # the states being dropped — release them too (their requests are lost
    # to OOM anyway, and a stale record must never resolve against a
    # recreated state).
    self._spec_next.clear()
    self._ring_spec.clear()
    for ctx in self._contexts.values():
      ctx.batch_spec = None
      for _, (toks, entry) in ctx.prefix_cache.items():
        if self._spill_prefix_entry(ctx, toks, entry):
          n_spill += 1
      n_snap += len(ctx.prefix_cache)
      self._prefix_evictions += len(ctx.prefix_cache)
      ctx.prefix_cache.clear()
      for rid in ctx.states:
        self._states_lost_to_oom[rid] = None
      n_state += len(ctx.states)
      ctx.states.clear()
      # Paged KV: the arena and its refcount metadata go wholesale — every
      # referencing state/prefix entry was just dropped above, and the next
      # paged request rebuilds a fresh (empty) pool.
      ctx.page_pool = None
    while len(self._states_lost_to_oom) > 512:
      self._states_lost_to_oom.popitem(last=False)
    for shard in [s for s, c in self._contexts.items() if c is not self._active]:
      self._contexts.pop(shard)
      n_ctx += 1
    import jax
    jax.clear_caches()  # drop compiled executables' scratch allocations too
    # clear_caches also wiped the jit cache: every executable identity is
    # about to compile again — reset the first-dispatch classifier so the
    # recompiles are counted as misses, not silently misread as hits.
    self._exec_seen.clear()
    freed = (f"{n_snap} prefix snapshots ({n_spill} spilled to host tier), "
             f"{n_state} request states, {n_ctx} model contexts")
    if self.flight is not None:
      self.flight.record("engine.oom_recovery", None, recovery=self._oom_count,
                         freed=freed)
      # OOM recovery is a terminal anomaly for every resident request:
      # freeze the whole ring so the postmortem shows what led up to it.
      self.flight.freeze(None, reason=f"oom_recovery:{self._oom_count}")
    return freed

  # ------------------------------------------------------------- public API

  async def encode(self, shard: Shard, prompt: str) -> np.ndarray:
    ctx = await self._ensure_ctx(shard)
    tokenizer = await self._ensure_tokenizer(ctx)
    return np.asarray(tokenizer.encode(prompt), dtype=np.int64)

  async def decode(self, shard: Shard, tokens: np.ndarray) -> str:
    ctx = await self._ensure_ctx(shard)
    tokenizer = await self._ensure_tokenizer(ctx)
    return tokenizer.decode(np.asarray(tokens).reshape(-1).tolist())

  async def sample(self, x: np.ndarray, temp: float = DEFAULT_TEMP, top_k: int = DEFAULT_TOP_K,
                   top_p: float = 0.0, request_id: Optional[str] = None,
                   sampling: Optional[dict] = None,
                   sample_index: Optional[int] = None) -> np.ndarray:
    """Host-path sampling. On THIS engine it runs exactly once per request —
    the first token of a multimodal prefill (ring decode hops sample via the
    fused infer_sample_tensor, which owns penalties/counts). It honors the
    per-request extras the fused sampler supports at token 1 — seed,
    logit_bias, min_p, and logprob recording — so a vision request's first
    token follows the request's sampling rules and its logprob entries
    align 1:1 with its tokens in the API's zip. presence/frequency count
    previously SAMPLED tokens, so they are no-ops at token 1 by definition;
    attach_sampling() then seeds the decode-state counts WITH this token so
    later fused chunks penalize it like the text path does.

    `sample_index` (the number of tokens sampled before this one) makes a
    seeded request reproducible: the key derives from (seed, sample_index),
    never from the engine-global call counter, which depends on unrelated
    concurrent traffic."""
    def _sample() -> np.ndarray:
      import jax
      import jax.numpy as jnp
      from xotorch_tpu.ops.sampling import sample_logits, sample_logits_logprobs
      logits = np.asarray(x)
      if logits.ndim == 3:
        logits = logits[:, -1, :]
      elif logits.ndim == 1:
        logits = logits[None, :]
      self._sample_calls += 1
      s = sampling or {}
      seed = s.get("seed")
      if seed is not None:
        key = jax.random.fold_in(jax.random.PRNGKey(int(seed)),
                                 sample_index if sample_index is not None else 0)
      else:
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed), self._sample_calls)
      bias = None
      lb = s.get("logit_bias")
      if lb:
        V = logits.shape[-1]
        pairs = [(int(t), float(v)) for t, v in lb.items() if 0 <= int(t) < V]
        if pairs:
          dense = np.zeros((1, V), np.float32)
          dense[0, [p[0] for p in pairs]] = [p[1] for p in pairs]
          bias = jnp.asarray(dense)
      min_p = float(s["min_p"]) if s.get("min_p") else None
      want_lp = s.get("logprobs")
      jl = jnp.asarray(logits)
      if want_lp is not None and request_id is not None:
        tok, lp, top_ids, top_lps = sample_logits_logprobs(
          jl, key, temp=temp, top_k=top_k, top_p=top_p, bias=bias,
          min_p=min_p, top_lp=int(want_lp))
        self._record_logprobs(request_id, np.asarray(lp), np.asarray(top_ids),
                              np.asarray(top_lps))
        out = tok
      else:
        out = sample_logits(jl, key, temp=temp, top_k=top_k, top_p=top_p,
                            bias=bias, min_p=min_p)
      return np.asarray(out).astype(np.int64)

    return await self._run(_sample)

  # Capability flag for Node: this engine can consume jax device arrays as
  # input and hand its output back device-resident (the co-located-partition
  # fast path, VERDICT r2 #3 — no host round-trip between in-process hops).
  supports_device_io = True

  async def infer_tensor(
    self, request_id: str, shard: Shard, input_data, inference_state: Optional[dict] = None,
    keep_on_device: bool = False,
  ) -> Tuple[Any, Optional[dict]]:
    ctx = await self._ensure_ctx(shard)
    start = time.perf_counter_ns()
    out = await self._run(self._infer_sync, ctx, request_id, input_data, keep_on_device)
    if DEBUG >= 4:
      print(f"infer_tensor[{request_id}] {input_data.shape} -> {out.shape} in {(time.perf_counter_ns()-start)/1e6:.2f}ms")
    return out, inference_state

  # ----------------------------------------------------------- device path

  def _to_device_input(self, input_data):
    import jax
    import jax.numpy as jnp
    if isinstance(input_data, jax.Array):
      # Device-resident hop from a co-located partition: no host copy.
      if input_data.ndim == 2:
        return input_data.astype(jnp.int32)
      if input_data.ndim == 3:
        return input_data.astype(self._dtype())
      raise ValueError(f"infer_tensor expects 2-D tokens or 3-D hidden state, got ndim={input_data.ndim}")
    if input_data.ndim == 2:
      return jnp.asarray(input_data.astype(np.int32))
    if input_data.ndim == 3:
      return jnp.asarray(input_data).astype(self._dtype())
    raise ValueError(f"infer_tensor expects 2-D tokens or 3-D hidden state, got ndim={input_data.ndim}")

  def _prefill_chunk(self) -> int:
    return knobs.get_int("XOT_PREFILL_CHUNK")

  def _segment_setup(self, ctx: _ShardContext, request_id: str, input_data: np.ndarray):
    """Shared per-segment prep for the forward and fused-sample paths:
    device transfer, bucket padding, state/capacity, and the
    flash-vs-cached-vs-baseline executable choice (one place, no drift).

    Executable selection: fresh-request prefill takes the in-segment Pallas
    flash kernel; decode steps and pos>0 segments over a long resident cache
    take the occupancy-aware cached kernel; everything else uses the
    XLA-fused baseline over the resident cache."""
    import jax.numpy as jnp
    x = self._to_device_input(input_data)
    true_t = x.shape[1]
    bucket = 1 if true_t == 1 else _bucket(true_t)
    state = self._prep_state(ctx, request_id, bucket)
    if bucket != true_t:
      pad = [(0, 0), (0, bucket - true_t)] + [(0, 0)] * (x.ndim - 2)
      x = jnp.pad(x, pad)
    kernels_ok = self._pallas_kernels_ok(ctx.cfg)
    use_flash = true_t > 1 and state.pos == 0 and kernels_ok and self._flash_enabled()
    use_fd = (not use_flash) and kernels_ok and self._flash_decode_on(state.cache["k"].shape[2])
    return x, true_t, state, use_flash, use_fd

  def _forward_segment(self, ctx: _ShardContext, request_id: str, input_data: np.ndarray,
                       fill: bool = False):
    """Single-segment device forward. Returns (device output, true_t) —
    the output stays on device so callers that don't need it (cache-fill
    segments, the fused sample path) never pay the host copy. `fill` selects
    the hidden-only executables on a last-layer shard (cache update without
    the unembedding)."""
    import jax.numpy as jnp
    st = ctx.states.get(request_id)
    if (self._paged_on() and self._paged_spec_on() and st is not None
        and st.cache is None and st.pages is not None
        and getattr(input_data, "ndim", 0) == 2 and input_data.shape[0] == 1
        and ctx.shard.is_first_layer and ctx.shard.is_last_layer):
      # Page-backed request on the per-token/segment path (extras decode,
      # per-token bucket fallback, node-driven rings): forward NATIVE to
      # the arena instead of gathering pages back to a contiguous buffer.
      # XOT_PAGED_SPEC=0 restores the legacy unpage-then-contiguous route
      # (_prep_state below).
      return self._forward_segment_paged(ctx, request_id, input_data)
    x, true_t, state, use_flash, use_fd = self._segment_setup(ctx, request_id, input_data)
    ring_ok = (ctx.fill_jits is not None and "ring" in ctx.fill_jits
               and state.pos == 0 and x.ndim == 2 and true_t > 1
               and x.shape[1] % ctx.mesh.shape["sp"] == 0)
    if fill and ring_ok:
      # Sequence-parallel prefill-from-zero (serving-side sp): the
      # segment's positions shard over the sp chips and attention rings
      # the KV chunks over ICI. Applies to the first (from-zero) segment;
      # later segments attend the resident cache and use the cached path.
      forward = ctx.fill_jits["ring"]
    elif ring_ok:
      forward = ctx.fill_jits["ring_full"]
    elif fill and ctx.fill_jits is not None:
      forward = ctx.fill_jits["flash" if use_flash else ("cached" if use_fd else "base")]
    elif use_flash:
      forward = ctx.forward_flash_jit
    elif use_fd:
      forward = ctx.forward_decode_flash_jit
    else:
      forward = ctx.forward_jit
    out, new_cache = forward(ctx.params, x, state.cache, jnp.int32(state.pos))
    state.cache = new_cache
    state.pos += true_t
    state.last_used = time.monotonic()
    return out, true_t

  def _forward_segment_paged(self, ctx: _ShardContext, request_id: str, input_data):
    """Single-segment forward NATIVE to the page arena (models/
    generate.forward_paged): the page-backed twin of _forward_segment for
    the per-token and bucket-fallback paths, so requests that leave the
    fused chunk ladder (sampling extras stepping per token, odd tails)
    never gather back to a contiguous buffer — _unpage_calls stays 0.
    Returns (device logits, true_t), same contract as _forward_segment."""
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import forward_paged
    x = self._to_device_input(input_data)
    true_t = int(x.shape[1])
    bucket = 1 if true_t == 1 else _bucket(true_t)
    state = self._prep_state_paged(ctx, request_id, bucket)
    pool = ctx.page_pool
    if bucket != true_t:
      x = jnp.pad(x, [(0, 0), (0, bucket - true_t)])
    table = self._paged_table_for(ctx, state)
    out, pool.arena = forward_paged(
      ctx.params, x, pool.arena, table, jnp.int32(state.pos), ctx.cfg,
      use_kernel=self._paged_kernel_on(), moe_routed=self._moe_routed_for(ctx),
      ragged=self._ragged_prefill_on(), start_layer=ctx.shard.start_layer,
      tp_mesh=ctx.mesh)
    state.pos += true_t
    # Bucket-overshoot pages hold only padding garbage and are exclusively
    # ours — back to the pool, then release what the window slid past.
    freed = state.pages.trim_to(pool.pages_for(state.pos))
    if freed:
      pool.decref(freed)
    self._vkv_window_release(ctx, state)
    state.last_used = time.monotonic()
    return out, true_t

  def _scan_prefill(self, ctx: _ShardContext, request_id: str, input_data,
                    chunk: int, want_hidden: bool = False):
    """Run a long prompt's leading FULL segments through the fused
    scan-prefill executable (models/generate.prefill_scan): the segment
    loop runs device-side under one `lax.scan`, so the dispatch + H2D bill
    is one per power-of-two segment GROUP (log2 of the segment count)
    instead of one of each per segment.

    Returns the [B, total, H] last-layer hidden states (device array) when
    `want_hidden` (mid-shard ring forwarding), else True for a cache-only
    fill; None/False when the path doesn't apply (Pallas decode kernel
    gated off, or an sp ring prefill outranks it — int8 KV caches qualify,
    the cached kernel dequantizes per tile) so the caller falls back to the
    per-segment loop. `input_data` length must be a multiple of `chunk`."""
    import jax
    import jax.numpy as jnp
    total = input_data.shape[1]
    # Below 2 segments the per-segment loop already pays a single dispatch
    # (and keeps the in-segment flash kernel for the from-zero case).
    if not knobs.get_bool("XOT_SCAN_PREFILL") or total % chunk or total < 2 * chunk:
      return None
    st = ctx.states.get(request_id)
    pos0 = st.pos if st is not None else 0
    if not (self._pallas_kernels_ok(ctx.cfg) and self._flash_decode_on(pos0 + total)):
      return None
    # Sequence-parallel prefill-from-zero shards the positions over chips —
    # it outranks the single-chip scan (mirrors _forward_segment's ring_ok).
    if (ctx.fill_jits is not None and "ring" in ctx.fill_jits and pos0 == 0
        and input_data.ndim == 2 and total % ctx.mesh.shape["sp"] == 0):
      return None
    from xotorch_tpu.models.generate import prefill_scan, scan_groups
    state = self._prep_state(ctx, request_id, total)
    x = self._to_device_input(input_data)
    outs = []
    for off, g in scan_groups(total // chunk):
      h, state.cache = prefill_scan(
        ctx.params, x[:, off * chunk:(off + g) * chunk], state.cache, jnp.int32(state.pos),
        ctx.cfg, g, is_first=(x.ndim == 2), start_layer=ctx.shard.start_layer,
        moe_routed=self._moe_routed_for(ctx), tp_mesh=ctx.mesh)
      if want_hidden:
        outs.append(h)
      state.pos += g * chunk
    state.last_used = time.monotonic()
    if not want_hidden:
      return True
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

  def _infer_sync(self, ctx: _ShardContext, request_id: str, input_data,
                  keep_on_device: bool = False):
    # Long prompts prefill in fixed segments: bounds the prefill-bucket
    # executable set and (with the cached Pallas kernel) keeps attention
    # memory at VMEM-tile scale instead of [T, S] — a 32 k prompt never
    # materialises a 32 k × 32 k score tensor anywhere.
    import jax.numpy as jnp
    true_t = input_data.shape[1]
    chunk = self._prefill_chunk()
    if true_t > chunk:
      # Mid-shard ring prefill (hidden outputs, no unembedding anywhere):
      # the fused scan path covers the leading full segments in O(log)
      # dispatches; the tail and any fallback take the per-segment loop.
      outs = []
      off0 = 0
      if not ctx.shard.is_last_layer:
        split = ((true_t - 1) // chunk) * chunk
        h = self._scan_prefill(ctx, request_id, input_data[:, :split], chunk,
                               want_hidden=True)
        if h is not None:
          outs.append(h if keep_on_device else np.asarray(h))
          off0 = split
      for off in range(off0, true_t, chunk):
        out, t = self._forward_segment(ctx, request_id, input_data[:, off:off + chunk])
        # Padded tail positions carry garbage activations — slice them off.
        outs.append(out[:, :t] if keep_on_device else np.asarray(out[:, :t]))
      return jnp.concatenate(outs, axis=1) if keep_on_device else np.concatenate(outs, axis=1)
    out, t = self._forward_segment(ctx, request_id, input_data)
    # keep_on_device: the next hop is co-located — hand back the device
    # array; the tensor never touches the host (VERDICT r2 #3).
    return out[:, :t] if keep_on_device else np.asarray(out[:, :t])

  async def infer_sample_tensor(
    self, request_id: str, shard: Shard, input_data: np.ndarray,
    temp: float = DEFAULT_TEMP, top_k: int = DEFAULT_TOP_K,
    inference_state: Optional[dict] = None, top_p: float = 0.0,
    sampling: Optional[dict] = None,
  ) -> Tuple[int, Optional[dict]]:
    """Last-shard forward + ON-DEVICE sampling (models/generate.forward_sample):
    the host receives one int, not [B, T, vocab] fp32 logits. This is the
    ring's last-layer hot path (VERDICT r1 weak #3 — the reference pulls
    ~0.5 MB of logits to the host per token, node.py:109-147).

    `sampling`: OpenAI extras {seed, logit_bias, presence_penalty,
    frequency_penalty} — applied on device (sampling.py); penalty counts
    start at zero and accumulate per SAMPLED token (OpenAI's formula —
    prompt tokens carry no penalty)."""
    ctx = await self._ensure_ctx(shard)
    if not shard.is_last_layer:
      raise ValueError(f"infer_sample_tensor requires the last-layer shard, got {shard}")
    tok = await self._prefill_and_sample(ctx, request_id, input_data, float(temp),
                                         int(top_k), float(top_p), sampling)
    return tok, inference_state

  def _cosched_on(self) -> bool:
    """XOT_PREFILL_COSCHED: admit a long prompt's prefill slices into the
    decode batcher's drain cycles (default on) so resident decode streams
    keep producing while the prompt prefills — per-cycle decode stall is
    bounded by ONE slice (XOT_PREFILL_CHUNK_BUDGET segments), not one
    prompt. 0 restores the monolithic one-executor-call prefill."""
    return knobs.get_bool("XOT_PREFILL_COSCHED")

  def _prefill_chunk_budget(self) -> int:
    """Prefill segments admitted per batcher drain cycle (co-scheduling
    slice size). 1 = finest interleaving (one XOT_PREFILL_CHUNK segment of
    decode stall per cycle); larger trades decode latency for prefill
    dispatch amortisation (slices use the fused scan executables)."""
    return max(1, knobs.get_int("XOT_PREFILL_CHUNK_BUDGET"))

  async def _prefill_and_sample(self, ctx: _ShardContext, request_id: str, input_data,
                                temp: float, top_k: int, top_p: float,
                                sampling: Optional[dict]) -> int:
    """Prefill + first-token sampling driver. Short prompts (and every
    non-co-scheduled configuration) run the whole thing as ONE executor
    call, exactly as before. A multi-segment prompt with co-scheduling on
    instead splits into bounded slices awaited through the decode batcher's
    prefill lane: the engine executor alternates decode dispatches and
    prefill slices, so a 16 k prompt no longer head-of-line-blocks every
    co-resident decode stream for its whole prefill."""
    chunk = self._prefill_chunk()
    # Co-scheduling engages only when there is concurrent activity to
    # protect (the same others-active heuristic as chunk overlap): an idle
    # engine keeps the monolithic path — one executor call, fused scan
    # grouping intact. Under load, the sliced path trades that amortisation
    # for bounded decode stall — exactly the serving-side deal.
    now = time.monotonic()
    # list() snapshot: this runs on the EVENT-LOOP thread while the executor
    # thread inserts/evicts states — iterating the live dict could raise
    # "dictionary changed size during iteration" under exactly the
    # concurrent load this path exists for (list(d.items()) is atomic in
    # CPython; the generator over it is not exposed to mutation).
    others_active = (
      (ctx.batcher is not None and bool(ctx.batcher.pending or ctx.batcher.pending_prefill))
      or any(now - st.last_used < 1.0
             for rid, st in list(ctx.states.items()) if rid != request_id))
    cosched = (self._cosched_on() and self._decode_batch_max() > 1 and others_active
               and getattr(input_data, "ndim", 0) == 2 and input_data.shape[0] == 1
               and input_data.shape[1] > chunk)
    tokens_in = int(input_data.shape[1]) if getattr(input_data, "ndim", 0) == 2 else 0
    if not cosched:
      # T==1 is a per-token decode step riding this entry point, not a
      # prefill — a span per token would swamp the trace buffer.
      if tokens_in > 1:
        with self._engine_span("engine.prefill", request_id,
                               {"tokens": tokens_in, "cosched": False}):
          kernels: list = []
          tok, consumed, fill_secs = await self._run(
            self._infer_sample_sync, ctx, request_id, input_data,
            temp, top_k, top_p, sampling, kernels=kernels)
        # Attribute only the suffix that actually ran: a warm request whose
        # prompt mostly hit the prefix cache must not book the full prompt's
        # bytes/FLOPs over a millisecond window (utilization would read far
        # above 100% — the exact lying-backend signal the gauges catch).
        suffix_t = tokens_in - consumed
        if suffix_t > 0:
          self._observe_dispatch("prefill",
                                 ("prefill", _bucket(suffix_t), int(top_k),
                                  float(top_p), tuple(kernels)),
                                 fill_secs, tokens=suffix_t, ctx=ctx,
                                 start=consumed)
        return tok
      tok, _consumed, _secs = await self._run(self._infer_sample_sync, ctx, request_id,
                                              input_data, temp, top_k, top_p, sampling)
      return tok
    if ctx.batcher is None:
      ctx.batcher = _DecodeBatcher(self, ctx)
    batcher = ctx.batcher
    paged_native = self._paged_prefill_ok(ctx, request_id, input_data, sampling)
    is_fresh = request_id not in ctx.states
    with self._engine_span("engine.prefill", request_id,
                           {"tokens": tokens_in, "cosched": True}):
      # The prologue rides the prefill lane too: prefix reuse may restore a
      # spilled prefix from the HOST tier (H2D stream into fresh pool pages,
      # _host_promote) — admitted as one bounded drain-cycle unit, decode
      # dispatches first, so co-resident streams never stall on the copy.
      full_prompt, consumed = await batcher.submit_prefill(
        partial(self._prefill_begin_sync, ctx, request_id, input_data, paged_native))
      if consumed:
        input_data = input_data[:, consumed:]
      try:
        true_t = input_data.shape[1]
        split = ((true_t - 1) // chunk) * chunk if true_t > chunk else 0
        step = self._prefill_chunk_budget() * chunk
        for off in range(0, split, step):
          sl = input_data[:, off:min(off + step, split)]
          # expected_pos guards slice continuity: only the very first slice of
          # an unseeded request may create the state; every later slice must
          # find it exactly where the previous slice left it (LRU churn
          # between slices otherwise silently restarts at pos 0). The first
          # slice reserves capacity for the WHOLE remaining prompt so the
          # contiguous path allocates once instead of grow-copying per slice.
          expected = consumed + off if (consumed or off) else None
          fill_t = int(sl.shape[1])
          await batcher.submit_prefill(
            partial(self._prefill_fill_sync, ctx, request_id, sl, paged_native,
                    expected, true_t if off == 0 else None),
            tokens=fill_t,
            key=("prefill", _bucket(fill_t), bool(paged_native), "fill"),
            start=consumed + off)
        tail_t = int(true_t - split)
        return await batcher.submit_prefill(
          partial(self._prefill_sample_sync, ctx, request_id, input_data[:, split:],
                  temp, top_k, top_p, sampling, paged_native, full_prompt,
                  consumed + split if (consumed or split) else None),
          tokens=tail_t,
          key=("prefill", _bucket(tail_t), bool(paged_native),
               int(top_k), float(top_p)),
          start=consumed + split)
      except CacheExhausted:
        # Pool/capacity exhaustion mid-prefill kills only THIS request: its
        # partial pages return to the pool at once, so the co-scheduled
        # decode streams it was interleaving with never feel the pressure.
        if paged_native and is_fresh:
          await self._run(self._abort_paged_prefill, ctx, request_id)
        raise

  def _build_extras(self, ctx: _ShardContext, sampling: dict) -> Dict[str, Any]:
    """Materialise a request's sampling extras on device: a dense [1, V]
    bias vector from the sparse logit_bias dict, and (when penalties are
    set) a [1, V] count vector starting at ZERO — OpenAI's published
    penalty formula counts how often a token was SAMPLED prior to the
    current position, so prompt tokens carry no penalty (vLLM/TGI
    implement the same rule; repetition-penalty-style prompt inclusion is
    a different knob)."""
    import jax.numpy as jnp
    V = ctx.cfg.vocab_size
    extras: Dict[str, Any] = {
      "seed": sampling.get("seed"),
      "presence": float(sampling.get("presence_penalty") or 0.0),
      "frequency": float(sampling.get("frequency_penalty") or 0.0),
      # min-p: None keeps every existing executable untouched (static
      # presence in ops/sampling); the value itself is traced. Riding the
      # extras lane is a DELIBERATE conservative choice: min_p requests
      # decode in their own fused chunk (no continuous batching) — a [B]
      # per-row vector through the batched executables would lift that, at
      # the cost of an always-on softmax in every user's decode step.
      "min_p": float(sampling["min_p"]) if sampling.get("min_p") else None,
      "bias": None, "counts": None,
    }
    lb = sampling.get("logit_bias")
    if lb:
      # Ids past the model's vocab are DROPPED (never wrapped — a modulo
      # would silently bias an unrelated token); the API already rejected
      # negatives and non-integers.
      pairs = [(int(t), float(v)) for t, v in lb.items() if 0 <= int(t) < V]
      if pairs:
        ids = np.asarray([p[0] for p in pairs], np.int32)
        vals = np.asarray([p[1] for p in pairs], np.float32)
        extras["bias"] = jnp.zeros((1, V), jnp.float32).at[0, ids].add(vals)
    if extras["presence"] or extras["frequency"]:
      extras["counts"] = jnp.zeros((1, V), jnp.int32)
    # OpenAI logprobs: None = off; K in 0..20 = report the sampled token's
    # logprob plus the top-K alternatives per step.
    extras["logprobs"] = sampling.get("logprobs")
    return extras

  def _record_logprobs(self, request_id: str, lp, top_ids, top_lps) -> None:
    """Append per-token logprob entries ([T] lp, [T, K] ids/lps host arrays)
    for the API to drain via pop_logprobs. Bounded LRU — an abandoned
    request's entries age out instead of leaking."""
    entries = [{
      "logprob": float(lp[i]),
      "top": [(int(t), float(p)) for t, p in zip(top_ids[i], top_lps[i])],
    } for i in range(len(lp))]
    with self._logprob_lock:
      self._logprob_store.setdefault(request_id, []).extend(entries)
      self._logprob_store.move_to_end(request_id)
      while len(self._logprob_store) > 512:
        self._logprob_store.popitem(last=False)

  def pop_logprobs(self, request_id: str, n: Optional[int] = None) -> Optional[list]:
    """Drain up to `n` (default: all) recorded logprob entries for a
    request, in sampling order. None when the request never recorded any
    (plain requests; requests sampled on a remote ring node)."""
    with self._logprob_lock:
      store = self._logprob_store.get(request_id)
      if store is None:
        return None
      if n is None or n >= len(store):
        self._logprob_store.pop(request_id, None)
        return store
      out, self._logprob_store[request_id] = store[:n], store[n:]
      return out

  def _extras_key(self, state: "_RequestState", extras: Optional[Dict[str, Any]],
                  request_id: str = "", sample_pos: Optional[int] = None):
    """Seeded requests derive their PRNG stream from (seed, position, choice
    index) so the same request replayed reproduces its tokens (OpenAI `seed`
    best-effort determinism) while the n>1 sibling sub-requests ("rid#0",
    "rid#1", ... — chatgpt_api request fan-out) still draw DISTINCT streams
    instead of n identical completions; unseeded requests keep the
    engine-global stream.

    `sample_pos` is the ABSOLUTE position of the token being sampled — NOT
    chunk-start state.pos, which a prefix-cache hit shifts (a warm replay
    prefills only the uncached suffix, so folding chunk-start pos would give
    the cold and warm runs different streams for the same seed)."""
    import jax
    if extras and extras.get("seed") is not None:
      choice = 0
      if "#" in request_id:
        tail = request_id.rsplit("#", 1)[1]
        # crc32, not hash(): PYTHONHASHSEED randomises hash() per process,
        # which would break cross-run seed reproducibility for caller-chosen
        # ids with a non-numeric '#'-suffix.
        import zlib
        choice = int(tail) if tail.isdigit() else zlib.crc32(tail.encode())
      pos = state.pos if sample_pos is None else sample_pos
      key = jax.random.fold_in(jax.random.PRNGKey(int(extras["seed"])), pos)
      return jax.random.fold_in(key, choice)
    self._sample_calls += 1
    return jax.random.fold_in(jax.random.PRNGKey(self._seed), self._sample_calls)

  def _prefill_begin_sync(self, ctx: _ShardContext, request_id: str, input_data,
                          paged_native: bool) -> Tuple[Optional[np.ndarray], int]:
    """Prefill prologue (executor-side): automatic prefix-cache reuse for a
    fresh token prefill sharing a long common prefix with a stored entry —
    full-model text path only (mid-shards see hidden states, not tokens, so
    they cannot key a prefix). Returns (full prompt for the later
    _prefix_store, positions consumed by reuse)."""
    is_prefill = (getattr(input_data, "ndim", 0) == 2 and input_data.shape[1] > 1
                  and input_data.shape[0] == 1  # snapshots are keyed batch-1
                  and ctx.shard.is_first_layer and request_id not in ctx.states)
    if not is_prefill:
      return None, 0
    full_prompt = np.asarray(input_data)
    return full_prompt, self._prefix_reuse(ctx, request_id, full_prompt,
                                           paged_native=paged_native)

  def _check_prefill_continuity(self, ctx: _ShardContext, request_id: str,
                                expected_pos: Optional[int]) -> None:
    """Between co-scheduled slices the engine serves other requests, so a
    burst of new states can LRU-evict a mid-prefill request. A later slice
    must NOT silently recreate it at pos 0 and scatter its segment there —
    fail loudly instead (the node aborts the request, same contract as
    mid-generation eviction). `expected_pos` is None for the slice allowed
    to create the state (the first, with no prefix reuse)."""
    if expected_pos is None:
      return
    st = ctx.states.get(request_id)
    if st is None or st.pos != expected_pos:
      raise RequestStateLost(
        f"request {request_id}: prefill state evicted mid-co-scheduled prefill "
        f"(expected pos {expected_pos}, found {st.pos if st else 'no state'})")

  def _prefill_fill_sync(self, ctx: _ShardContext, request_id: str, input_data,
                         paged_native: bool, expected_pos: Optional[int] = None,
                         reserve: Optional[int] = None) -> None:
    """Cache-fill forward of a prompt slice whose length is a multiple of
    the prefill chunk — hidden-only executables, outputs dropped on device,
    never copied to host. The unit of work the co-scheduling lane admits
    between decode dispatches (_DecodeBatcher.submit_prefill). `reserve`
    (first slice of a co-scheduled CONTIGUOUS prefill) pre-sizes the cache
    for the whole remaining prompt, exactly as the monolithic path's
    one-shot prep does — without it every later slice would trigger a
    _grow_cache full-buffer copy (the paged side appends pages, no copy,
    and needs no reservation)."""
    self._check_prefill_continuity(ctx, request_id, expected_pos)
    if paged_native:
      self._paged_fill_sync(ctx, request_id, input_data)
      return
    if reserve and reserve > input_data.shape[1]:
      self._prep_state(ctx, request_id, reserve)
    chunk = self._prefill_chunk()
    if not self._scan_prefill(ctx, request_id, input_data, chunk):
      for off in range(0, input_data.shape[1], chunk):
        self._forward_segment(ctx, request_id, input_data[:, off:off + chunk], fill=True)

  def _abort_paged_prefill(self, ctx: _ShardContext, request_id: str) -> None:
    """Release a paged-native prefill that died on pool exhaustion: the
    request can never produce a token, so its partially-filled pages go
    back to the pool IMMEDIATELY — co-resident decode streams must not
    starve on capacity a dead request is holding. (A fresh prefill only;
    a page-backed state that already streamed tokens keeps its pages and
    fails through the normal length path.)"""
    st = ctx.states.get(request_id)
    if st is not None and st.cache is None:
      ctx.states.pop(request_id, None)
      self._release_state_pages(ctx, st)

  def _infer_sample_sync(self, ctx: _ShardContext, request_id: str, input_data: np.ndarray,
                         temp: float, top_k: int, top_p: float = 0.0,
                         sampling: Optional[dict] = None) -> Tuple[int, int, float]:
    """Returns (token, consumed, fill_secs): `consumed` is the prefix-cache
    hit the prologue took off the prompt and `fill_secs` the wall time of
    the actual prefill executables AFTER the prologue — so the caller's
    perf attribution covers the suffix that really ran, not the full prompt
    over a window that also includes prefix reuse / host-tier restores."""
    paged_native = self._paged_prefill_ok(ctx, request_id, input_data, sampling)
    is_fresh = request_id not in ctx.states
    full_prompt, consumed = self._prefill_begin_sync(ctx, request_id, input_data, paged_native)
    if consumed:
      input_data = input_data[:, consumed:]

    t0 = time.monotonic()
    try:
      true_t = input_data.shape[1]
      chunk = self._prefill_chunk()
      if true_t > chunk:
        split = ((true_t - 1) // chunk) * chunk
        self._prefill_fill_sync(ctx, request_id, input_data[:, :split], paged_native)
        input_data = input_data[:, split:]
      tok = self._prefill_sample_sync(ctx, request_id, input_data, temp, top_k, top_p,
                                      sampling, paged_native, full_prompt)
      return tok, consumed, time.monotonic() - t0
    except CacheExhausted:
      if paged_native and is_fresh:
        self._abort_paged_prefill(ctx, request_id)
      raise

  def _prefill_sample_sync(self, ctx: _ShardContext, request_id: str, input_data,
                           temp: float, top_k: int, top_p: float,
                           sampling: Optional[dict], paged_native: bool,
                           full_prompt: Optional[np.ndarray],
                           expected_pos: Optional[int] = None) -> int:
    """Final prefill segment: forward + ON-DEVICE sampling of the first
    token (the epilogue of infer_sample_tensor, shared by the one-shot and
    co-scheduled drivers)."""
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import forward_sample

    self._check_prefill_continuity(ctx, request_id, expected_pos)
    if paged_native:
      return self._paged_sample_sync(ctx, request_id, input_data, temp, top_k, top_p,
                                     full_prompt, sampling)
    x, seg_t, state, use_flash, use_fd = self._segment_setup(ctx, request_id, input_data)
    if sampling and state.extras is None:
      state.extras = self._build_extras(ctx, sampling)
    extras = state.extras
    key = self._extras_key(state, extras, request_id=request_id,
                           sample_pos=state.pos + seg_t - 1)
    e = extras or {}
    want_lp = e.get("logprobs")
    out, state.cache = forward_sample(
      ctx.params, x, state.cache, jnp.int32(state.pos), jnp.int32(seg_t - 1), key,
      ctx.cfg, x.ndim == 2, temp, top_k, top_p, use_flash=use_flash, use_flash_decode=use_fd,
      start_layer=ctx.shard.start_layer, moe_routed=self._moe_routed_for(ctx),
      bias=e.get("bias"), counts=e.get("counts"),
      presence=e.get("presence", 0.0), frequency=e.get("frequency", 0.0),
      min_p=e.get("min_p"),
      top_lp=-1 if want_lp is None else int(want_lp),
      tp_mesh=ctx.mesh,
    )
    if want_lp is not None:
      tok, lp, top_ids, top_lps = out
      self._record_logprobs(request_id, np.asarray(lp), np.asarray(top_ids),
                            np.asarray(top_lps))
    else:
      tok = out
    state.pos += seg_t
    state.last_used = time.monotonic()
    if full_prompt is not None:
      self._prefix_store(ctx, request_id, full_prompt)
    tok_int = int(np.asarray(tok).reshape(-1)[0])
    if extras and extras.get("counts") is not None:
      extras["counts"] = extras["counts"].at[0, tok_int % ctx.cfg.vocab_size].add(1)
    return tok_int

  # ---------------------------------------------------- speculative decode

  async def verify_draft(self, request_id: str, shard: Shard, prev_token: int,
                         draft: list) -> Optional[list]:
    """Greedy draft verification (prompt-lookup speculative decoding): run
    ONE forward over [prev_token] + draft, accept the longest prefix of the
    draft that matches the model's own argmax stream, and take the model's
    next token after the accepted prefix as a bonus. Returns 1..len(draft)+1
    tokens — every one exactly what sequential greedy decode would have
    produced — or None when the fast path does not apply.

    KV rollback is free by design: rejected positions' cache slots sit past
    the rolled-back `pos`, invisible to the validity mask
    (transformer.forward_shard kv_valid_len) and overwritten by the next
    write at the same offsets.

    A page-backed request (XOT_PAGED_KV + XOT_PAGED_SPEC) verifies NATIVE
    to the arena — a T>1 ragged query over its existing page table
    (_verify_draft_paged_sync), with the same free rollback plus a
    page-granular decref of the rejected tail; everything else takes the
    contiguous forward below.
    """
    if not (shard.is_first_layer and shard.is_last_layer) or not draft:
      return None
    ctx = self._contexts.get(shard)
    if ctx is None:
      raise RequestStateLost(
        f"request {request_id}: model context {shard.model_id} evicted mid-generation")
    state = ctx.states.get(request_id)
    if state is None:
      raise RequestStateLost(f"request {request_id}: device state evicted mid-generation")
    # Room check uses the PADDED bucket (what _prep_state will actually
    # demand), not the raw draft length — near the cache end a raw-length
    # guard would pass and then _prep_state would raise CacheExhausted,
    # ending the request early where plain decode drains to the last slot.
    # COMMITTED position: an in-flight speculative chunk inflates state.pos
    # by its size (and will be rolled back by _prep_state) — judging room by
    # the inflated pos would disable speculation one chunk early.
    committed_pos = self._committed_pos(ctx, request_id, state)
    if committed_pos + _bucket(1 + len(draft)) > ctx.max_cache_len:
      return None  # no room to verify: caller falls back to plain decode
    # Refresh LRU at BOTH levels (same reasoning as generate_chunk): a
    # request decoding purely through accepted drafts must not have its
    # model context evicted out from under it.
    self._contexts.move_to_end(shard)
    ctx.states.move_to_end(request_id)
    return await self._run(self._verify_draft_sync, ctx, request_id, int(prev_token),
                           [int(t) for t in draft])

  def _verify_draft_sync(self, ctx: _ShardContext, request_id: str, prev_token: int,
                         draft: list):
    import jax.numpy as jnp
    state = ctx.states[request_id]
    if self._paged_spec_ok(ctx, state):
      # Paged-native verification: the forward runs as a T>1 ragged query
      # over the request's EXISTING page table — no gather-back, no
      # re-commit, no contiguous buffer at any point.
      return self._verify_draft_paged_sync(ctx, request_id, prev_token, draft)
    # Discard in-flight speculation BEFORE capturing pos: _prep_state (via
    # _forward_segment) would roll state.pos back underneath us, and a
    # pos_before read from the inflated value would land the post-verify
    # position past the real sequence — pulling stale cache slots inside
    # the valid attention window for every later token.
    self._discard_spec(request_id, state)
    self._discard_batch_spec_for(ctx, request_id)
    pos_before = state.pos
    x = np.asarray([[prev_token] + draft], dtype=np.int64)
    t0 = time.monotonic()
    out, true_t = self._forward_segment(ctx, request_id, x)
    # preds[i] = model's greedy choice AFTER consuming x[:, : i + 1].
    preds = np.asarray(jnp.argmax(out[0, :true_t], axis=-1)).astype(np.int64)
    secs = time.monotonic() - t0
    n_acc = 0
    while n_acc < len(draft) and int(preds[n_acc]) == draft[n_acc]:
      n_acc += 1
    # preds has len(draft)+1 entries, so preds[n_acc] is the bonus token in
    # BOTH the partial- and full-acceptance cases.
    accepted = draft[:n_acc] + [int(preds[n_acc])]
    # Roll back: only prev_token + the accepted draft wrote VALID cache
    # slots; the rest are masked out and re-written by the next dispatch.
    state.pos = pos_before + 1 + n_acc
    self._spec_proposed += len(draft)
    self._spec_accepted += n_acc
    self._observe_spec(len(draft), n_acc)
    alloc = state.cache["k"].shape[2] if state.cache is not None else None
    self._observe_dispatch(
      "verify", ("verify", _bucket(true_t), False, tuple(sorted(self._kernel_tags))), secs,
      tokens=_bucket(true_t), ctx=ctx, items=[(pos_before, False, alloc)],
      emitted=len(accepted))
    if self.flight is not None:
      self.flight.record("spec.verify", request_id, drafted=len(draft),
                         accepted=n_acc, paged=False)
    return accepted

  def _paged_spec_ok(self, ctx: _ShardContext, state: "_RequestState") -> bool:
    """Qualification rule for paged-native draft verification: the request
    must already live on the page table (cache committed/native) with
    XOT_PAGED_SPEC on. The only remaining fallback is the knob itself —
    XOT_PAGED_SPEC=0 restores the contiguous verify (which un-pages a
    page-backed state via _prep_state — the pre-ragged behavior)."""
    return (self._paged_on() and self._paged_spec_on()
            and state.cache is None and state.pages is not None)

  def _verify_draft_paged_sync(self, ctx: _ShardContext, request_id: str,
                               prev_token: int, draft: list):
    """Greedy draft verification NATIVE to the page arena: one
    forward_argmax_paged dispatch runs [prev_token] + draft as a T>1 ragged
    query through the request's existing page table, scattering the draft's
    K/V into the request's own pages (partial tail page + fresh
    allocations covering the padded bucket). Rollback is page-granular and
    free: pos rewinds to the accepted prefix and the tail pages past
    pages_for(pos) — bucket overshoot AND rejected-draft pages, all
    fresh-allocated this round — decref straight back to the pool. The
    request never leaves the arena, so _unpage_state and
    _commit_state_to_pages stay untouched (the counters tests assert)."""
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import forward_argmax_paged
    state = ctx.states[request_id]
    self._discard_spec(request_id, state)
    self._discard_batch_spec_for(ctx, request_id)
    pos_before = state.pos
    T = 1 + len(draft)
    bucket = _bucket(T)
    try:
      # Extends the table to cover the padded bucket (pages for the draft
      # positions — a draft straddling a page boundary allocates its fresh
      # pages HERE, before any device work).
      self._prep_state_paged(ctx, request_id, bucket)
    except CacheExhausted:
      # Pool pressure: fall back to plain decode (one page per chunk beats
      # a bucket-wide verify claim) — same "fast path does not apply"
      # contract as the room check in verify_draft.
      return None
    pool = ctx.page_pool
    x = np.zeros((1, bucket), dtype=np.int64)
    x[0, :T] = [prev_token] + draft
    table = self._paged_table_for(ctx, state)
    t0 = time.monotonic()
    preds_dev, pool.arena = forward_argmax_paged(
      ctx.params, jnp.asarray(x, jnp.int32), pool.arena, table,
      jnp.int32(pos_before), ctx.cfg, use_kernel=self._paged_kernel_on(),
      moe_routed=self._moe_routed_for(ctx), ragged=self._ragged_prefill_on(),
      start_layer=ctx.shard.start_layer, tp_mesh=ctx.mesh)
    preds = np.asarray(preds_dev[0, :T]).astype(np.int64)
    secs = time.monotonic() - t0
    n_acc = 0
    while n_acc < len(draft) and int(preds[n_acc]) == draft[n_acc]:
      n_acc += 1
    accepted = draft[:n_acc] + [int(preds[n_acc])]
    state.pos = pos_before + 1 + n_acc
    # Page-granular rollback: everything past pages_for(pos) was allocated
    # for this verify (the pre-verify invariant is len(pages) ==
    # pages_for(pos), restored here) — shared prefix pages are full pages
    # below pos_before and can never sit in the trimmed tail.
    freed = state.pages.trim_to(pool.pages_for(state.pos))
    if freed:
      pool.decref(freed)
    self._vkv_window_release(ctx, state)
    state.last_used = time.monotonic()
    self._spec_proposed += len(draft)
    self._spec_accepted += n_acc
    self._observe_spec(len(draft), n_acc)
    self._observe_dispatch(
      "verify", ("verify", bucket, True, tuple(sorted(self._kernel_tags))), secs,
      tokens=bucket, ctx=ctx, items=[(pos_before, True, None)],
      emitted=len(accepted))
    if self.flight is not None:
      self.flight.record("spec.verify", request_id, drafted=len(draft),
                         accepted=n_acc, paged=True)
    return accepted

  # ----------------------------------------------- draft-model speculation

  @staticmethod
  def _draft_rid(request_id: str) -> str:
    """Draft-model cache states live in the DRAFT model's context under a
    derived key: sharing the raw request_id would collide with the target
    request's engine-global speculation records (_spec_next) — _prep_state
    on the draft state would pop and mis-apply the target's in-flight
    speculative-chunk rollback."""
    return request_id + "#draft"

  async def draft_tokens(self, request_id: str, context_tokens, k: int) -> list:
    """Model-based speculative drafting (XOT_DRAFT_MODEL): greedy-generate
    `k` candidate tokens from a small resident draft model, to be verified
    by the target model's verify_draft / verify_draft_ring in ONE forward.

    Where prompt-lookup drafting (orchestration/node._lookup_draft) only
    fires when the text repeats an earlier n-gram, a draft model proposes on
    EVERY round: decode is weight-HBM-bound, so a ~10x smaller draft's k
    steps + one target verify forward stream far fewer weight bytes per
    accepted token than k target steps. The reference has no speculation of
    any kind (its decode loop is strictly per-token, node.py:109-147).

    `context_tokens` is the full accepted sequence (prompt + generated).
    The draft keeps its own per-request KV cache in the draft model's
    context; only the yet-unseen suffix is fed each round (state.pos IS the
    seen count), and rejected draft positions roll back for free exactly
    like verify_draft — slots past the committed pos are invisible and get
    overwritten. The draft model must share the target's tokenizer (the
    standard speculative-decoding contract; e.g. llama-3.2-1b drafting for
    llama-3.1-70b). Returns [] when drafting is off, capacity is exhausted,
    or the draft model cannot load — callers fall back to plain decode."""
    mid = knobs.get_str("XOT_DRAFT_MODEL", "")
    if not mid or k < 2 or time.monotonic() < getattr(self, "_draft_retry_at", 0.0):
      return []
    from xotorch_tpu.models.registry import build_full_shard
    shard = build_full_shard(mid, self.__class__.__name__)
    if shard is None:
      return []
    try:
      ctx = await self._ensure_ctx(shard)
    except Exception as e:
      cooldown = knobs.get_float("XOT_DRAFT_RETRY_S")
      if DEBUG >= 1:
        print(f"draft model {mid} failed to load, pausing drafting {cooldown:.0f}s: {e!r}")
      # Per-engine cooldown, NOT os.environ: clearing the env var would turn
      # drafting off for every engine in the process (bench ring2, tests)
      # and erase the operator's configured value; a permanent flag would
      # never recover from a transient failure (OOM pressure, download
      # hiccup). Generation proceeds undrafted meanwhile.
      self._draft_retry_at = time.monotonic() + cooldown
      return []
    return await self._run(self._draft_sync, ctx, self._draft_rid(request_id),
                           list(context_tokens), k)

  def _draft_sync(self, ctx: _ShardContext, rid: str, context: list, k: int) -> list:
    import jax
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import decode_chunk
    st = ctx.states.get(rid)
    seen = st.pos if st is not None else 0
    suffix = context[seen:]
    if not suffix:
      # The draft state is AHEAD of the accepted sequence (only possible
      # after an LRU resurrection mismatch) — resync from scratch.
      ctx.states.pop(rid, None)
      seen, suffix = 0, list(context)
    try:
      # The whole body guards CacheExhausted, not just this first check: the
      # fill segments below re-enter _prep_state with PADDED buckets, which
      # can exhaust where the unpadded total fits (verify_draft's padded
      # guard exists for the same reason). Escaping here would let the
      # node's decode loop finish the TARGET request as length-capped
      # because the DRAFT model's cache filled. A partial ingest before the
      # raise is harmless — state.pos records exactly what landed.
      state = self._prep_state(ctx, rid, len(suffix) + k)
      # Ingest accepted-but-unseen tokens (all but the last) as cache fill:
      # scan-prefill for the leading full segments, per-segment for the tail.
      fill = np.asarray([suffix[:-1]], dtype=np.int64)
      chunk = self._prefill_chunk()
      done = 0
      n_fill = fill.shape[1]
      if n_fill:
        split = (n_fill // chunk) * chunk
        if split and self._scan_prefill(ctx, rid, fill[:, :split], chunk):
          done = split
        for off in range(done, n_fill, chunk):
          self._forward_segment(ctx, rid, fill[:, off:off + chunk], fill=True)
      # Fused greedy draft: ONE dispatch scans k forward+argmax steps.
      pos = state.pos
      use_fd = self._pallas_kernels_ok(ctx.cfg) and self._flash_decode_on(state.cache["k"].shape[2])
      toks, state.cache = decode_chunk(
        ctx.params, jnp.asarray([[suffix[-1]]], jnp.int32), state.cache, jnp.int32(pos),
        jax.random.PRNGKey(0), ctx.cfg, k, 0.0, 0,
        use_flash_decode=use_fd, moe_routed=self._moe_routed_for(ctx),
        tp_mesh=ctx.mesh)
    except CacheExhausted:
      return []
    draft = [int(t) for t in np.asarray(toks)[0]]
    # Commit ONLY the real token's slot: the k drafted slots are scratch —
    # the next round's fill overwrites whatever verification rejected.
    state.pos = pos + 1
    state.last_used = time.monotonic()
    return draft

  # ----------------------------------------------------------- prefix cache

  def _prefix_cache_max(self) -> int:
    """Snapshot entries kept per model context (0 disables). Each entry
    holds a device KV copy of its prompt — HBM cost scales with model size
    and prompt length, so the default is small."""
    return knobs.get_int("XOT_PREFIX_CACHE")

  def _prefix_cache_min(self) -> int:
    return knobs.get_int("XOT_PREFIX_CACHE_MIN")

  @staticmethod
  def _best_hbm_prefix(ctx: _ShardContext, toks: np.ndarray,
                       limit: int) -> Tuple[Optional[int], int]:
    """(entry key, common length) of the resident HBM prefix entry with the
    longest common token prefix for `toks` — the single scan shared by
    _prefix_reuse (pick the entry to seed from) and _host_promote (only
    promote a host entry that beats every resident one). Matching rule
    itself lives in kv_offload.common_prefix_len, shared with the host
    tier's own match."""
    from xotorch_tpu.inference.jax_engine.kv_offload import common_prefix_len
    best_key, best_len = None, 0
    for key, (ptoks, _) in ctx.prefix_cache.items():
      common = common_prefix_len(ptoks, toks, limit)
      if common > best_len:
        best_key, best_len = key, common
    return best_key, best_len

  def _prefix_reuse(self, ctx: _ShardContext, request_id: str, tokens_2d: np.ndarray,
                    paged_native: bool = False) -> int:
    """Seed a fresh request's cache from the stored snapshot with the
    longest common token prefix (causality makes positions < common valid
    regardless of what follows). Returns positions consumed (0 = no hit).
    With `paged_native` (paged-native prefill will serve this request) a
    paged entry is reused with ZERO copies: the matched full pages are
    incref'd in place as the request's page-table head."""
    if self._prefix_cache_max() <= 0:
      return 0
    toks = np.asarray(tokens_2d).reshape(-1).astype(np.int64)
    # Host-tier consult: a prefix that was spilled (pool pressure, OOM
    # recovery) restores into the HBM cache here — after which the scan
    # below serves it exactly like a native warm hit (same incref/seed
    # paths, same accounting). A local miss consults the fleet-wide KV
    # fabric inside the promote, so a sibling's warm prefix serves here
    # too — byte-identical, via the same restore.
    self._host_promote(ctx, toks, request_id=request_id)
    if not ctx.prefix_cache:
      return 0
    limit = toks.shape[0] - 1  # at least one token must still be forwarded
    best_key, best_len = self._best_hbm_prefix(ctx, toks, limit)
    if best_key is None or best_len < self._prefix_cache_min():
      return 0
    import jax
    _, snap = ctx.prefix_cache[best_key]
    ctx.prefix_cache.move_to_end(best_key)
    if isinstance(snap, dict) and "pages" in snap:
      # Paged entry: gather the shared pages into the fresh prefill buffer
      # (the same copy the snapshot path pays) and HOLD them (incref) so
      # commit can put them at the head of this request's page table
      # instead of re-copying — N warm requests share one arena copy of
      # the prefix. Reuse is rounded DOWN to whole pages: the suffix
      # prefill and later appends then only ever write pages past the
      # shared ones.
      pool = ctx.page_pool
      page = pool.page_size
      consumed = (min(best_len, snap["len"]) // page) * page
      if consumed < self._prefix_cache_min():
        return 0
      ids = list(snap["pages"][:consumed // page])
      if paged_native:
        # Zero-gather, zero-commit warm start: the matched full pages become
        # this request's page-table head IN PLACE (incref'd — read-only by
        # construction, decode/suffix writes land past them in fresh pages).
        # N warm requests share one arena copy of a hot prefix and never
        # touch a contiguous buffer at all.
        state = self._get_or_create_paged_state(ctx, request_id)
        pool.incref(ids)
        state.pages = VirtualKV(ids)
        state.pos = consumed
        self._prefix_hits += 1
        self._prefix_tokens_saved += consumed
        if DEBUG >= 2:
          print(f"[{request_id}] prefix cache hit: {consumed} tokens reused in place "
                f"({len(ids)} shared pages, zero copy)")
        return consumed
      from xotorch_tpu.inference.jax_engine.paged_cache import gather_pages
      state = self._get_or_create_state(ctx, request_id, min_len=toks.shape[0])
      gathered = gather_pages(pool.arena, np.asarray(ids, np.int32))
      state.cache = {
        name: jax.lax.dynamic_update_slice(
          state.cache[name], gathered[name][:, :, :consumed].astype(state.cache[name].dtype),
          (0,) * state.cache[name].ndim)
        for name in state.cache
      }
      pool.incref(ids)
      state.paged_seed = ids
      state.pos = consumed
      self._prefix_hits += 1
      self._prefix_tokens_saved += consumed
      if DEBUG >= 2:
        print(f"[{request_id}] prefix cache hit: {consumed} tokens reused ({len(ids)} shared pages)")
      return consumed
    state = self._get_or_create_state(ctx, request_id, min_len=toks.shape[0])
    state.cache = {
      # Rank-generic: int8-KV scale leaves are rank 4 ([L, B, S, Hkv]),
      # K/V rank 5 — start indices must match each leaf's own rank.
      name: jax.lax.dynamic_update_slice(
        state.cache[name], snap[name][:, :, :best_len].astype(state.cache[name].dtype),
        (0,) * state.cache[name].ndim,
      )
      for name in state.cache
    }
    state.pos = best_len
    self._prefix_hits += 1
    self._prefix_tokens_saved += best_len
    if DEBUG >= 2:
      print(f"[{request_id}] prefix cache hit: {best_len} tokens reused")
    return best_len

  def _prefix_store(self, ctx: _ShardContext, request_id: str, tokens_2d: np.ndarray) -> None:
    """Snapshot a completed prefill's KV for future prefix reuse. The slice
    is a fresh device buffer — never aliased with the (donated) live cache."""
    if self._prefix_cache_max() <= 0:
      return
    toks = np.asarray(tokens_2d).reshape(-1).astype(np.int64)
    T = toks.shape[0]
    if T < self._prefix_cache_min():
      return
    state = ctx.states.get(request_id)
    if state is None or state.pos < T:
      return
    key = hash(toks.tobytes())
    if key in ctx.prefix_cache:
      ctx.prefix_cache.move_to_end(key)
      return
    if self._paged_on():
      # Paged mode: SHARE the prefill's full pages (incref) instead of
      # snapshotting a whole cache copy — the arena holds one copy of a hot
      # system prompt no matter how many requests and entries reference it.
      # Shared pages are read-only by construction: decode appends always
      # land at page index pos // page_size, past every full prefix page,
      # so divergence after the shared prefix is copy-on-write with the
      # "copy" limited to the partial tail page each request already owns.
      try:
        pool = self._ensure_page_pool(ctx)
        if state.pages is None:
          self._commit_state_to_pages(ctx, state)
      except CacheExhausted:
        # Caching is best-effort: a full pool must never fail a request
        # whose prefill already succeeded. The decode path re-attempts the
        # commit and surfaces capacity errors where the contiguous path does.
        return
      n_full = T // pool.page_size
      if n_full <= 0:
        return
      ids = vkv.as_handle(state.pages).prefix_ids(n_full)
      if ids is None:
        # A windowed request already released prefix pages back to the pool
        # — the hole-y virtual map isn't a shareable physical prefix.
        return
      pool.incref(ids)
      ctx.prefix_cache[key] = (toks, {"pages": ids, "len": n_full * pool.page_size})
    else:
      import jax.numpy as jnp

      def snap(buf):
        # A FULL slice (T == buffer length, e.g. a prompt landing exactly on
        # its power-of-two bucket) returns the SAME array object in JAX — and
        # the live cache is donated into the next decode dispatch, which would
        # delete the "snapshot" out from under future reuse. Force a copy in
        # exactly that case.
        s = buf[:, :, :T]
        return jnp.copy(s) if s is buf else s

      ctx.prefix_cache[key] = (toks, {name: snap(buf) for name, buf in state.cache.items()})
    while len(ctx.prefix_cache) > self._prefix_cache_max():
      _, (etoks, evicted) = ctx.prefix_cache.popitem(last=False)
      # LRU overflow is an eviction like any other: spill the entry D2H so
      # the warm set outlives the HBM bound, THEN release the device copy.
      self._spill_prefix_entry(ctx, etoks, evicted)
      self._prefix_evictions += 1
      if ctx.page_pool is not None and isinstance(evicted, dict) and "pages" in evicted:
        ctx.page_pool.decref(evicted["pages"])

  # ------------------------------------------------- host-tier KV offload
  #
  # A second KV tier under the HBM prefix cache (kv_offload.HostKVStore,
  # bounded by XOT_KV_HOST_BYTES, LRU by prefix key). Every prefix-entry
  # eviction — LRU overflow in _prefix_store, pool-pressure reclaim in
  # _pool_alloc, OOM recovery in _free_device_memory — spills the entry's
  # KV D2H before the device copy is released (spill-then-drop), and
  # _prefix_reuse consults the tier whenever the HBM cache misses (or
  # matches shorter): a host hit allocates fresh pool pages, streams the KV
  # back H2D, and re-creates the HBM entry IN PLACE, so the request then
  # takes the exact native warm path (incref'd shared pages / snapshot
  # seed) and prefills only its suffix. Entries live in one canonical
  # contiguous layout, so spills and restores compose across both cache
  # layouts and across page-size changes. Degrade-safe by construction:
  # any validation or capacity failure during restore falls back to a cold
  # prefill — never a wrong token, never a client-visible error.

  def _host_kv_max_bytes(self) -> int:
    """XOT_KV_HOST_BYTES: host-RAM budget for spilled prefix KV (0
    disables the tier). Default 256 MiB — enough for tens of long warm
    prefixes of a 1B-class model, noise next to the host RAM that backs a
    TPU VM."""
    try:
      return knobs.get_int("XOT_KV_HOST_BYTES")
    except ValueError:
      return 0

  def _host_kv_store(self):
    """The engine-wide host tier, or None when disabled. One store for all
    contexts (entries are namespaced by Shard), sized once at first use."""
    max_bytes = self._host_kv_max_bytes()
    if max_bytes <= 0:
      return None
    if self._host_kv is None:
      from xotorch_tpu.inference.jax_engine.kv_offload import HostKVStore
      self._host_kv = HostKVStore(max_bytes)
      self._host_kv.observer = self._host_evict_event
    return self._host_kv

  def _host_evict_event(self, entries: int, nbytes: int) -> None:
    """HostKVStore budget-eviction callback: the tier silently dropping warm
    prefixes to fit its budget is exactly the kind of invisible decision the
    flight recorder exists to capture."""
    if self.flight is not None:
      self.flight.record("host.evict", None, entries=entries, bytes=nbytes)

  def host_kv_stats(self) -> Optional[Dict[str, int]]:
    """Occupancy of the host tier for /metrics gauges, or None while no
    store exists (disabled, or nothing ever spilled)."""
    store = self._host_kv
    if store is None:
      return None
    return {"bytes": store.total_bytes, "entries": len(store)}

  # ------------------------------------------------- fleet-wide KV fabric
  #
  # Cross-replica prefix transfer (xotorch_tpu/fabric): a prefix that
  # misses HBM *and* the local host tier consults sibling replicas — the
  # offer directory first (router chaining and spill pre-announce land
  # offers there), then static XOT_FABRIC_PEERS probes — and imports the
  # longest covering entry into the local HostKVStore with its content
  # digest verified. The import then takes the EXISTING _host_promote
  # restore path (fresh pool pages, H2D scatter), so a remote hit is
  # byte-identical to a local host-warm hit and unpage/commit-copy stay 0.
  # Every failure mode — unreachable peer, torn transfer, digest mismatch
  # — degrades to a cold prefill, never an error.

  def _fabric_client(self, create: bool = False):
    """The fabric pull client, or None while the fabric is idle. Built
    lazily when XOT_FABRIC_PEERS names siblings, or on the first incoming
    offer (`create=True`) — a single-replica deployment never pays for it."""
    if self._fabric is None:
      peers = [p.strip() for p in knobs.get_str("XOT_FABRIC_PEERS").split(",")
               if p.strip()]
      if not peers and not create:
        return None
      from xotorch_tpu.fabric.client import FabricClient
      self._fabric = FabricClient(
        peers, timeout_s=knobs.get_float("XOT_FABRIC_TIMEOUT_S"),
        offer_ttl_s=knobs.get_float("XOT_FABRIC_OFFER_TTL_S"))
    return self._fabric

  def fabric_offer(self, shard: Shard, toks, length: int, nbytes: int,
                   url: str) -> bool:
    """Record a sibling's announce (`POST /v1/kv/offer`): peer `url` holds
    a host-tier entry covering `toks`. The offer carries the full token
    ids, so the next local miss resolves coverage with zero round-trips.
    Returns False when the host tier is disabled (nowhere to import)."""
    if self._host_kv_max_bytes() <= 0:
      return False
    client = self._fabric_client(create=True)
    key = client.offers.record(shard, toks, length, nbytes, url)
    if self.flight is not None:
      self.flight.record("fabric.offer", None, key=key[:16], tokens=int(length),
                         bytes=int(nbytes), peer=url)
    return True

  async def prefetch_fabric_offer(self, shard: Shard, toks) -> bool:
    """Anticipatory pull for a just-offered prefix (PRESERVE discipline,
    same contract as prefetch_host_prefix but keyed on token ids): start
    the fabric fetch + host-to-HBM promote while the chained request is
    still in flight to us. Resident contexts only; best-effort."""
    ctx = self._contexts.get(shard)
    if ctx is None or ctx.params is None:
      return False
    toks = np.asarray(toks, dtype=np.int64).reshape(-1)
    if toks.shape[0] < 2:
      return False
    fetched_before = self._fabric_bytes
    promote = partial(self._host_promote, ctx, toks)
    if ctx.batcher is not None:
      await ctx.batcher.submit_prefill(promote)
    else:
      await self._run(promote)
    return self._fabric_bytes > fetched_before

  def _fabric_consult(self, ctx: _ShardContext, toks: np.ndarray, limit: int,
                      have: int, request_id: Optional[str] = None) -> bool:
    """Fetch the best sibling entry covering `toks` past `have` (what the
    local tiers already cover) and import it into the host store. Runs on
    the engine executor inside _host_promote; the transfer is attributed
    to the request's TTFT anatomy as its own stage (engine.fabric_fetch).
    Returns True when an entry landed — the caller then re-matches."""
    client = self._fabric_client()
    if client is None:
      return False
    store = self._host_kv_store()
    if store is None:
      return False
    t0 = time.monotonic()
    with self._engine_span("engine.fabric_fetch", request_id):
      res = client.fetch(ctx.shard, toks, limit, better_than=have)
    if res.errors:
      self._fabric_errors += res.errors
    if res.payload is None:
      self._fabric_misses += 1
      return False
    n = store.import_entry(ctx.shard, res.payload, source="fabric")
    if n <= 0:
      # Digest mismatch or over-budget payload: dropped exactly like a
      # torn local host entry — cold prefill, never a wrong token.
      self._fabric_errors += 1
      self._fabric_misses += 1
      if DEBUG >= 1:
        print(f"fabric import rejected (torn/over-budget transfer from {res.url})")
      return False
    self._fabric_hits += 1
    self._fabric_bytes += n
    if self.flight is not None:
      self.flight.record("fabric.fetch", request_id,
                         tokens=int(res.payload["length"]), bytes=n, peer=res.url,
                         secs=round(time.monotonic() - t0, 4))
    if DEBUG >= 2:
      print(f"fabric fetch: {res.payload['length']}-token prefix imported "
            f"from {res.url} ({n} bytes)")
    return True

  async def prefill_export(self, shard: Shard, prompt: str) -> Optional[dict]:
    """Disaggregated prefill (XOT_FABRIC_ROLE=prefill): run the prompt's
    prefill on this replica, copy the resulting prefix entry into the host
    tier (non-destructive copy-out), and return a transfer handle — the
    router offers it at a decode replica, which imports the KV over the
    fabric instead of paying the cold prefill. None when the prompt is too
    short to cache or the host tier/prefix cache is off (the router then
    degrades to plain forwarding)."""
    if self._host_kv_max_bytes() <= 0 or self._prefix_cache_max() <= 0:
      return None
    import uuid
    ctx = await self._ensure_ctx(shard)
    tokenizer = await self._ensure_tokenizer(ctx)
    toks = np.asarray(tokenizer.encode(prompt), dtype=np.int64).reshape(-1)
    if toks.shape[0] < max(2, self._prefix_cache_min()):
      return None
    rid = f"fabric-prefill-{uuid.uuid4().hex[:12]}"
    try:
      await self.infer_sample_tensor(rid, shard, toks.reshape(1, -1), temp=0.0)
      return await self._run(self._export_prefix_sync, ctx, toks)
    finally:
      await self.clear_request(rid)

  def _export_prefix_sync(self, ctx: _ShardContext, toks: np.ndarray) -> Optional[dict]:
    """Host-tier copy-out + handle for a just-prefilled prompt: spill the
    HBM prefix entry (pure copy — live refs untouched) and describe the
    resulting host entry for a fabric offer."""
    store = self._host_kv_store()
    if store is None:
      return None
    key = hash(np.ascontiguousarray(toks).tobytes())
    hbm = ctx.prefix_cache.get(key)
    if hbm is not None:
      etoks, snap = hbm
      self._spill_prefix_entry(ctx, etoks, snap)
    entry, common = store.match(ctx.shard, toks, toks.shape[0])
    if entry is None or entry.length <= 0:
      return None
    from xotorch_tpu.fabric import entry_key
    return {"key": entry_key(ctx.shard, entry.toks), "length": int(entry.length),
            "nbytes": int(entry.nbytes), "covered": int(min(common, entry.length)),
            "tokens": [int(t) for t in entry.toks]}

  def _cache_leaf_names(self) -> set:
    """Leaf names a restored snapshot must carry to seed the CURRENT cache
    config (transformer.init_kv_cache): plain bf16/f32 K/V, or K/V + their
    scale leaves under int8 KV. A host entry spilled under a different
    config fails this check and is treated as a miss."""
    names = {"k", "v"}
    if self._kv_quant is not None:
      names |= {"k_scale", "v_scale"}
    return names

  def _spill_prefix_entry(self, ctx: _ShardContext, toks, entry) -> bool:
    """Copy one evicted prefix entry D2H into the host tier (best-effort:
    spilling is pure copy-out — live requests sharing the entry's pages
    keep their own refs and are never touched; a failed spill only means
    the entry dies the way it always used to). Paged entries gather their
    full pages into the canonical contiguous layout; snapshot entries copy
    leaf-for-leaf."""
    store = self._host_kv_store()
    if store is None:
      return False
    try:
      t0 = time.monotonic()
      toks = np.asarray(toks).reshape(-1).astype(np.int64)
      if isinstance(entry, dict) and "pages" in entry:
        pool = ctx.page_pool
        if pool is None:
          return False
        from xotorch_tpu.inference.jax_engine.paged_cache import gather_pages
        g = gather_pages(pool.arena, np.asarray(entry["pages"], np.int32))
        data = {name: np.asarray(buf) for name, buf in g.items()}
        length = int(entry["len"])
      else:
        data = {name: np.asarray(buf) for name, buf in entry.items()}
        length = int(data["k"].shape[2])
      n = store.put(ctx.shard, toks, data, length)
      if n > 0:
        self._host_spill_bytes += n
        if self.flight is not None:
          self.flight.record("host.spill", None, tokens=length, bytes=n,
                             secs=round(time.monotonic() - t0, 4))
        if DEBUG >= 2:
          print(f"prefix entry spilled to host tier: {length} tokens, {n} bytes")
      return n > 0
    except Exception as e:
      # The spill path runs inside eviction and OOM recovery — it must
      # never turn a cleanup into a failure.
      if DEBUG >= 1:
        print(f"host KV spill failed (entry dropped): {e!r}")
      return False

  def _host_promote(self, ctx: _ShardContext, toks: np.ndarray,
                    request_id: Optional[str] = None) -> None:
    """If the host tier holds a strictly longer usable prefix for `toks`
    than any resident HBM entry, stream it back and re-create the HBM
    entry: fresh pool pages + H2D scatter under XOT_PAGED_KV (the entry
    then shares pages with the request exactly like a native hit), or a
    device_put snapshot on the contiguous path. A local miss (or a shorter
    local match) consults the fleet-wide fabric first — an imported
    sibling entry lands in the host store and is restored by the very same
    code below. Runs on the engine executor; under co-scheduling the
    caller rides the _DecodeBatcher prefill lane, so co-resident decode
    dispatches first and never stalls on the copy. Every failure mode
    degrades to a cold prefill."""
    store = self._host_kv_store()
    if store is None:
      return
    limit = toks.shape[0] - 1
    if limit <= 0:
      return
    _, hbm_best = self._best_hbm_prefix(ctx, toks, limit)
    entry, common = store.match(ctx.shard, toks, limit) if len(store) else (None, 0)
    local_usable = min(common, entry.length) if entry is not None else 0
    if local_usable < limit and self._fabric_consult(
        ctx, toks, limit, max(local_usable, hbm_best), request_id=request_id):
      entry, common = store.match(ctx.shard, toks, limit)
    if entry is None:
      return
    t0 = time.monotonic()
    usable = min(common, entry.length)
    want_paged = (self._paged_on()
                  and set(entry.data) == self._cache_leaf_names())
    try:
      if set(entry.data) != self._cache_leaf_names() and not want_paged:
        # Spilled under an incompatible cache config (e.g. int8-KV scales
        # missing/extra): unusable here, and keeping it would shadow
        # fresher compatible entries.
        store.drop(ctx.shard, entry.toks)
        return
      if want_paged:
        pool = self._ensure_page_pool(ctx)
        page = pool.page_size
        if (usable // page) * page <= max(hbm_best, self._prefix_cache_min() - 1):
          return  # whatever we restored, the scan below would not use it
        n_full = entry.length // page
        leaf = entry.data["k"]
        if (n_full <= 0 or leaf.ndim != 5 or leaf.shape[2] < n_full * page
            or leaf.shape[0] != pool.arena["k"].shape[0]
            or leaf.shape[3:] != pool.arena["k"].shape[3:]):
          store.drop(ctx.shard, entry.toks)  # torn or config-mismatched
          return
        sc = entry.data.get("k_scale")
        if sc is not None and (sc.shape[0] != pool.arena["k_scale"].shape[0]
                               or sc.shape[2] < n_full * page
                               or sc.shape[3:] != pool.arena["k_scale"].shape[3:]):
          store.drop(ctx.shard, entry.toks)  # scale leaves torn/mismatched
          return
        from xotorch_tpu.inference.jax_engine.paged_cache import scatter_pages
        ids = self._pool_alloc(ctx, pool, n_full)
        try:
          pool.arena = scatter_pages(pool.arena, entry.data, np.asarray(ids, np.int32))
        except Exception:
          pool.decref(ids)
          raise
        restored = (entry.toks, {"pages": ids, "len": n_full * page})
      else:
        if usable <= max(hbm_best, self._prefix_cache_min() - 1):
          return
        leaf = entry.data["k"]
        if leaf.ndim != 5 or leaf.shape[2] < entry.length:
          store.drop(ctx.shard, entry.toks)
          return
        import jax.numpy as jnp
        # Truncate toks to the KV the entry actually COVERS: a paged spill
        # keeps the full prompt toks but only whole pages of KV
        # (entry.length < len(toks)), and a snapshot entry keyed on the
        # longer toks would let _prefix_reuse mark the uncovered tail as
        # cached — zero KV served as valid positions, silently wrong
        # tokens. (The paged restore branch caps via its "len" field.)
        restored = (np.ascontiguousarray(entry.toks[:entry.length]),
                    {name: jnp.asarray(arr[:, :, :entry.length])
                     for name, arr in entry.data.items()})
    except CacheExhausted:
      # Restore raced pool pressure (live requests hold every page): the
      # entry stays in the host tier for a calmer moment; this request
      # prefills cold.
      return
    except Exception as e:
      if DEBUG >= 1:
        print(f"host KV restore failed (entry dropped, cold prefill): {e!r}")
      store.drop(ctx.shard, entry.toks)
      return
    key = hash(np.ascontiguousarray(restored[0]).tobytes())
    old = ctx.prefix_cache.pop(key, None)
    if old is not None and ctx.page_pool is not None \
       and isinstance(old[1], dict) and "pages" in old[1]:
      ctx.page_pool.decref(old[1]["pages"])
    ctx.prefix_cache[key] = restored
    while len(ctx.prefix_cache) > self._prefix_cache_max():
      _, (etoks, evicted) = ctx.prefix_cache.popitem(last=False)
      self._spill_prefix_entry(ctx, etoks, evicted)
      self._prefix_evictions += 1
      if ctx.page_pool is not None and isinstance(evicted, dict) and "pages" in evicted:
        ctx.page_pool.decref(evicted["pages"])
    self._host_kv_hits += 1
    src = getattr(entry, "source", "local")
    self._host_hits_by_source[src] = self._host_hits_by_source.get(src, 0) + 1
    self._host_fetch_bytes += entry.nbytes
    if self.flight is not None:
      self.flight.record("host.restore", None, tokens=entry.length,
                         bytes=entry.nbytes, source=src,
                         secs=round(time.monotonic() - t0, 4))
    if DEBUG >= 2:
      print(f"host KV tier hit: {entry.length}-token prefix restored "
            f"({entry.nbytes} bytes H2D)")

  async def prefetch_host_prefix(self, shard: Shard, prompt: str) -> bool:
    """PRESERVE-style anticipatory restore (arXiv 2501.08192): run the
    host-to-HBM prefix promote for a prompt that is still QUEUED (admission
    gate / router pre-announce), so by admission its warm prefix is already
    resident and the request takes the native warm path immediately.
    Strictly best-effort and load-shaped: resident contexts only (a
    prefetch must never trigger a model load), and when a batcher is live
    the promote rides the co-scheduled prefill lane so resident decode
    never stalls on the H2D copy. Returns True when bytes were restored."""
    store = self._host_kv
    if (store is None or len(store) == 0) and self._fabric_client() is None:
      return False
    if self._host_kv_store() is None:
      return False  # tier disabled: a fabric import would have nowhere to land
    ctx = self._contexts.get(shard)
    if ctx is None or ctx.params is None:
      return False
    try:
      tokenizer = await self._ensure_tokenizer(ctx)
      toks = np.asarray(tokenizer.encode(prompt), dtype=np.int64).reshape(-1)
    except Exception:
      return False  # unresolvable tokenizer: the real request will report it
    if toks.shape[0] < 2:
      return False
    fetched_before = self._host_fetch_bytes
    promote = partial(self._host_promote, ctx, toks)
    if ctx.batcher is not None:
      await ctx.batcher.submit_prefill(promote)
    else:
      await self._run(promote)
    return self._host_fetch_bytes > fetched_before

  async def infer_prompt(
    self, request_id: str, shard: Shard, prompt: str, inference_state: Optional[dict] = None,
    images: Optional[list] = None, keep_on_device: bool = False,
  ) -> Tuple[Any, Optional[dict]]:
    ctx = await self._ensure_ctx(shard)
    if not images:
      return await super().infer_prompt(request_id, shard, prompt, inference_state,
                                        keep_on_device=keep_on_device)
    if not ctx.cfg.is_multimodal:
      # Defense in depth (the API rejects this earlier): never silently answer
      # about an image the model cannot see.
      raise ValueError(f"model {shard.model_id} does not support image input")
    tokens = await self.encode(shard, prompt)
    out = await self._run(self._infer_multimodal_sync, ctx, request_id, tokens.reshape(-1), images)
    return out, inference_state

  def _infer_multimodal_sync(self, ctx: _ShardContext, request_id: str, token_ids: np.ndarray,
                             images: list) -> np.ndarray:
    """Multimodal prefill: vision tower -> projector -> splice patch features
    at <image> placeholder positions -> run the text stack on the merged
    embedding sequence (is_first=False jit). LLaVA-1.5 semantics, verified
    against transformers in tests/test_vision_llava.py."""
    import jax.numpy as jnp
    from xotorch_tpu.models.vision import encode_images, merge_image_features, preprocess_images, project_features

    if ctx.vision is None:
      raise RuntimeError("vision weights unavailable for multimodal request")
    vparams, pparams = ctx.vision
    cfg = ctx.cfg
    pixels = preprocess_images(images, cfg.vision.image_size)
    feats = encode_images(vparams, jnp.asarray(pixels), cfg.vision,
                          feature_layer=cfg.vision_feature_layer,
                          select=cfg.vision_feature_select)
    feats = project_features(pparams, feats, act=cfg.projector_hidden_act)
    token_embeds = ctx.params["embed"]["embedding"][jnp.asarray(token_ids.astype(np.int32))]
    merged = merge_image_features(token_embeds, token_ids, feats, cfg.image_token_index)

    true_t = merged.shape[0]
    bucket = 1 if true_t == 1 else _bucket(true_t)
    state = self._prep_state(ctx, request_id, bucket)
    x = merged[None]
    if bucket != true_t:
      x = jnp.pad(x, [(0, 0), (0, bucket - true_t), (0, 0)])
    forward = ctx.forward_hidden_jit
    if (true_t > 1 and state.pos == 0 and self._pallas_kernels_ok(ctx.cfg)
        and self._flash_enabled()):
      forward = ctx.forward_hidden_flash_jit
    out, state.cache = forward(ctx.params, x.astype(self._dtype()), state.cache, jnp.int32(state.pos))
    state.pos += true_t
    state.last_used = time.monotonic()
    return np.asarray(out[:, :true_t])

  async def attach_sampling(self, shard: Shard, request_id: str, sampling: dict,
                            sampled_tokens=()) -> None:
    """Bind a request's sampling extras (seed/bias/penalties/logprobs) to
    its decode state when the PREFILL path couldn't — the multimodal prefill
    samples its first token on the host (engine.sample), so state.extras was
    never built and the fused decode chunks would otherwise run extras-free
    (no bias, no logprob recording) for the rest of the stream.
    `sampled_tokens` are tokens already sampled outside the extras state
    (the host-sampled first token): they seed the penalty counts so
    presence/frequency treat them exactly as the text path does (which
    counts its prefill-sampled token before decode). Idempotent; no-op when
    the state is unknown or extras already exist."""
    ctx = self._contexts.get(shard)
    if ctx is None:
      return
    state = ctx.states.get(request_id)
    if state is None or state.extras is not None:
      return

    def _attach() -> None:
      if state.extras is not None:
        return
      extras = self._build_extras(ctx, sampling)
      counts = extras.get("counts")
      if counts is not None:
        for t in sampled_tokens:
          counts = counts.at[0, int(t) % ctx.cfg.vocab_size].add(1)
        extras["counts"] = counts
      state.extras = extras

    await self._run(_attach)

  async def generate_chunk(
    self, request_id: str, shard: Shard, prev_token: int, num_tokens: int,
    temp: float = DEFAULT_TEMP, top_k: int = DEFAULT_TOP_K, top_p: float = 0.0,
    next_size: Optional[int] = None,
  ) -> Optional[np.ndarray]:
    """Fused multi-token decode (models/generate.py): one device dispatch
    produces UP TO `num_tokens` sampled tokens, with sampling on-device under
    the same `lax.scan` as the forward steps. A coalesced batch runs at the
    minimum size requested across its rows (the batcher's grouping note), so
    callers must treat the returned length as authoritative and loop. Only
    valid when this shard spans the whole model (single-partition ring) and
    the request already has a prefilled cache. Returns None when the fast
    path does not apply so the caller (Node.process_inference_result) falls
    back to the per-token ring.
    """
    if not (shard.is_first_layer and shard.is_last_layer) or num_tokens < 1:
      return None
    ctx = self._contexts.get(shard)
    if ctx is None:
      # A full-model shard with no resident context means the context (and
      # the request's KV cache with it) was LRU-evicted mid-generation: the
      # prefill that preceded this call must have created it. Returning None
      # would silently fall back to the per-token ring, which would reload
      # the model with EMPTY states and restart from pos 0 — fail loudly.
      raise RequestStateLost(
        f"request {request_id}: model context {shard.model_id} evicted mid-generation"
      )
    state = ctx.states.get(request_id)
    if state is None:
      # The caller guaranteed a prefill happened, so the state was LRU-evicted
      # under concurrency. Falling back would silently restart from an empty
      # cache — fail loudly instead.
      raise RequestStateLost(f"request {request_id}: device state evicted mid-generation")
    # Refresh LRU recency at BOTH levels: a request decoding purely through
    # the fused path must not have its request state — or its whole model
    # context — evicted mid-generation by newer requests.
    self._contexts.move_to_end(shard)
    ctx.states.move_to_end(request_id)
    # The chunk advances the cache by num_tokens starting at pos (the slot of
    # prev_token's forward step is pos, the last sampled token's is pos+K-1).
    # Capacity math MUST use the COMMITTED position: with a speculative
    # chunk in flight state.pos is optimistically advanced by its size, and
    # judging capacity by the inflated pos would raise CacheExhausted one
    # chunk early — dropping a final chunk the device already computed.
    committed_pos = self._committed_pos(ctx, request_id, state)
    if committed_pos + num_tokens > ctx.max_cache_len:
      if committed_pos + 1 > ctx.max_cache_len:
        raise CacheExhausted(
          f"request {request_id}: cache full at {committed_pos}/{ctx.max_cache_len}")
      # Shrink to the cache tail and keep the FUSED path to the very end —
      # with the adaptive growth ladder (node.py) the tail can be up to
      # max_decode_chunk_size-1 tokens, far too many to hand to the
      # per-token ring at one host round-trip each. Largest power of two
      # <= tail stays on the compiled-size ladder (at most log2 extra
      # dispatches to drain the tail); the check above guaranteed tail >= 1.
      tail = ctx.max_cache_len - committed_pos
      num_tokens = min(num_tokens, 1 << (tail.bit_length() - 1))

    if self._decode_batch_max() > 1 and state.extras is None:
      # Continuous batching: coalesce with other requests' concurrent chunks
      # (a lone request flows through as a batch of one, same executable).
      # Requests with sampling extras (seed/bias/penalties) skip the batcher
      # and decode in their own fused chunk — correctness first, and the
      # common path's executables stay free of [B, V] extras operands.
      if ctx.batcher is None:
        ctx.batcher = _DecodeBatcher(self, ctx)
      return await ctx.batcher.submit(request_id, state, prev_token, num_tokens,
                                      float(temp), int(top_k), float(top_p),
                                      next_size=next_size)

    def _chunk() -> np.ndarray:
      return self._decode_batch_sync(
        ctx, [(request_id, state, prev_token, num_tokens, float(temp), top_k, float(top_p),
               next_size, None)],
        num_tokens, int(top_k), float(top_p),
      )[0]

    return await self._run(_chunk)

  # Node's ring-fusion detection keys off this flag: when every partition of
  # a ring is served by an engine with it (co-located, one process/device),
  # multi-partition decode folds into ONE fused executable per chunk instead
  # of one hop per partition per token.
  supports_ring_fusion = True

  async def generate_chunk_ring(
    self, request_id: str, chain, prev_token: int, num_tokens: int,
    temp: float = DEFAULT_TEMP, top_k: int = DEFAULT_TOP_K, top_p: float = 0.0,
    next_size: Optional[int] = None,
  ) -> Optional[np.ndarray]:
    """Fused decode across a CO-LOCATED multi-partition ring: `chain` is the
    ring-ordered list of (engine, shard) pairs covering layers 0..N-1, every
    engine a ring-fusion-capable instance in THIS process. One dispatch runs
    all partitions' layer stacks + sampling for up to `num_tokens` tokens
    (models/generate.decode_chunk_ring), so the multi-partition ring decodes
    at the single-shard fused rate instead of per-token hop latency — the
    reference's ring is per-token by construction (node.py:109-147).

    Each partition's params and KV cache stay exactly where the per-token
    ring keeps them (its engine's context/state) — entering or leaving the
    fused path needs no migration, and the per-token ring remains the
    fallback (returns None when the chain doesn't qualify). Called on the
    LAST shard's engine (the sampler peer drives generation)."""
    if num_tokens < 1:
      return None
    segs = self._resolve_ring_segs(request_id, chain)
    if segs is None:
      return None

    if self._decode_batch_max() > 1:
      # Continuous batching for ring chunks: concurrent requests on the SAME
      # co-located chain coalesce into one batched multi-segment dispatch
      # (decode_chunk_ring_batched) — B rows ride one weight read per
      # segment, the same aggregate-throughput win as the single-shard
      # batcher. The `state` slot of the shared collector carries the segs.
      chain_key = tuple((id(eng), sh) for eng, sh in chain)
      batcher = self._ring_batchers.get(chain_key)
      if batcher is None:
        async def dispatch(items, n, tk, tp, single, kernels, _self=self):
          return await _self._run(_self._ring_batch_sync, items, n, tk, tp,
                                  kernels=kernels)

        batcher = _DecodeBatcher(self, None, dispatch=dispatch)
        self._ring_batchers[chain_key] = batcher
      return await batcher.submit(request_id, segs, prev_token, num_tokens,
                                  float(temp), int(top_k), float(top_p),
                                  next_size=next_size)

    def _chunk() -> np.ndarray:
      return self._ring_chunk_sync(segs, request_id, int(prev_token), int(num_tokens),
                                   float(temp), int(top_k), float(top_p),
                                   int(next_size) if next_size else None)

    return await self._run(_chunk)

  def _resolve_ring_segs(self, request_id: str, chain) -> Optional[list]:
    """Validate a co-located chain and resolve its [(engine, ctx, state)]
    segments — ONE qualification rule shared by the fused-ring decode,
    batch, and draft-verify paths. Returns None when the chain doesn't
    qualify (caller falls back); raises RequestStateLost when a segment's
    context/state was evicted mid-generation (same loud contract as
    generate_chunk)."""
    if len(chain) < 2:
      return None
    shards = [s for _, s in chain]
    if not (shards[0].is_first_layer and shards[-1].is_last_layer):
      return None
    if any(b.start_layer != a.end_layer + 1 for a, b in zip(shards, shards[1:])):
      return None  # non-contiguous coverage: not a whole-model chain
    segs = []
    for eng, sh in chain:
      if not getattr(eng, "supports_ring_fusion", False) or not isinstance(eng, JAXShardInferenceEngine):
        return None
      ctx = eng._contexts.get(sh)
      if ctx is None:
        # Prefill created this context; its loss mid-generation means the KV
        # cache is gone too — fail loudly.
        raise RequestStateLost(
          f"request {request_id}: model context {sh.model_id} [{sh.start_layer}-{sh.end_layer}] "
          f"evicted mid-generation on {eng!r}")
      state = ctx.states.get(request_id)
      if state is None:
        raise RequestStateLost(
          f"request {request_id}: device state for layers [{sh.start_layer}-{sh.end_layer}] "
          f"evicted mid-generation")
      if state.extras is not None:
        return None  # sampling extras decode per-token (host-side bookkeeping)
      eng._contexts.move_to_end(sh)
      ctx.states.move_to_end(request_id)
      segs.append((eng, ctx, state))
    return segs

  async def verify_draft_ring(self, request_id: str, chain, prev_token: int,
                              draft: list) -> Optional[list]:
    """Greedy draft verification across a CO-LOCATED multi-partition ring:
    one composite forward (models/generate.forward_argmax_ring) runs
    [prev_token] + draft through every partition's layers and accepts the
    longest matching prefix + bonus — prompt-lookup speculation works on
    multi-partition rings exactly as on a single shard. Returns the accepted
    tokens, or None when the fast path does not apply (caller decodes
    normally)."""
    if not draft:
      return None
    segs = self._resolve_ring_segs(request_id, chain)
    if segs is None:
      return None

    def _verify():
      return self._ring_verify_sync(segs, request_id, int(prev_token),
                                    [int(t) for t in draft])

    return await self._run(_verify)

  def _ring_verify_sync(self, segs, request_id: str, prev_token: int,
                        draft: list) -> Optional[list]:
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import forward_argmax_ring

    states = [st for _, _, st in segs]
    T = 1 + len(draft)
    T_pad = _bucket(T)
    max_len = min(ctx.max_cache_len for _, ctx, _ in segs)
    # Room check against the COMMITTED position BEFORE touching the spec
    # record: near the cache tail every iteration finds a draft and bails —
    # popping first would throw away (and force recomputing) the in-flight
    # speculative chunk each time, killing the overlap for the request's
    # remainder (same ordering rule as verify_draft's _committed_pos check).
    spec = self._ring_spec.get(request_id)
    committed = (spec["pos"]
                 if spec is not None and all(st.pos == spec["pos"] + spec["n"]
                                             for st in spec["states"])
                 else states[0].pos)
    if committed + T_pad > max_len:
      return None  # no room to verify: caller decodes normally, spec intact
    # The verify supersedes any in-flight ring speculation: roll it back so
    # pos below is the committed one.
    spec = self._ring_spec.pop(request_id, None)
    if spec is not None:
      self._overlap_misses += 1
      for st in spec["states"]:
        if st.pos == spec["pos"] + spec["n"]:
          st.pos = spec["pos"]
    pos = states[0].pos
    if any(st.pos != pos for st in states):
      return None  # lockstep broken: plain decode path recovers
    for eng, ctx, st in segs:
      if st.cache["k"].shape[2] < pos + T_pad:
        eng._grow_cache(ctx, st, pos + T_pad)
    x = np.zeros((1, T_pad), dtype=np.int64)
    x[0, :T] = [prev_token] + draft
    S = states[0].cache["k"].shape[2]
    use_fd = self._pallas_kernels_ok(segs[0][1].cfg) and self._flash_decode_on(S)
    preds_dev, new_caches = forward_argmax_ring(
      tuple(ctx.params for _, ctx, _ in segs), jnp.asarray(x, jnp.int32),
      tuple(st.cache for st in states), jnp.int32(pos), segs[-1][1].cfg,
      use_flash_decode=use_fd,
      start_layers=tuple(ctx.shard.start_layer for _, ctx, _ in segs),
      moe_routed=all(self._moe_routed_for(c) for _, c, _ in segs),
      tp_mesh=segs[0][1].mesh,
    )
    preds = np.asarray(preds_dev[0, :T]).astype(np.int64)
    n_acc = 0
    while n_acc < len(draft) and int(preds[n_acc]) == draft[n_acc]:
      n_acc += 1
    accepted = draft[:n_acc] + [int(preds[n_acc])]
    now = time.monotonic()
    for st, c in zip(states, new_caches):
      st.cache = c
      st.pos = pos + 1 + n_acc
      st.last_used = now
    self._spec_proposed += len(draft)
    self._spec_accepted += n_acc
    self._observe_spec(len(draft), n_acc)
    if self.flight is not None:
      self.flight.record("spec.verify", request_id, drafted=len(draft),
                         accepted=n_acc, paged=False)
    return accepted

  def _ring_batch_sync(self, items: list, num_tokens: int, top_k: int,
                       top_p: float) -> list:
    """Executor body for a coalesced ring batch. A batch of one delegates to
    _ring_chunk_sync (keeping its speculative-overlap machinery); B > 1
    stacks every segment's member caches and runs ONE
    decode_chunk_ring_batched dispatch. Members whose segments lost pos
    lockstep resolve to None (their node loops fall back per-token)."""
    import jax
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import decode_chunk_ring_batched

    if len(items) == 1:
      rid, segs, prev_token, n, temp, *_rest = items[0]
      next_size = items[0][7] if len(items[0]) > 8 else None
      return [self._ring_chunk_sync(segs, rid, int(prev_token), int(n), float(temp),
                                    int(top_k), float(top_p),
                                    int(next_size) if next_size else None)]

    # Batch membership supersedes any solo ring speculation: roll back.
    members = []
    results: list = [None] * len(items)
    for i, it in enumerate(items):
      rid, segs = it[0], it[1]
      states = [st for _, _, st in segs]
      spec = self._ring_spec.pop(rid, None)
      if spec is not None:
        self._overlap_misses += 1
        for st in spec["states"]:
          if st.pos == spec["pos"] + spec["n"]:
            st.pos = spec["pos"]
      if any(st.pos != states[0].pos for st in states):
        continue  # lockstep broken: this member falls back (None result)
      # Capacity guard (mirrors _ring_chunk_sync): a member whose cache
      # can't hold the group's chunk is EXCLUDED — its node loop falls back
      # to the per-token ring, which drains the cache tail and surfaces
      # CacheExhausted gracefully. Without this, _grow_cache clamps at
      # max_cache_len and dynamic_update_slice clamps the write start,
      # silently overwriting earlier KV slots for every batch member.
      max_len_i = min(c.max_cache_len for _, c, _ in segs)
      if states[0].pos + num_tokens > max_len_i:
        continue
      members.append((i, it))
    if not members:
      return results

    segs0 = members[0][1][1]
    n_seg = len(segs0)
    # Per segment: grow every member's cache to a common power-of-two length
    # (one executable per (B, n, S...) tuple; same policy as the single-shard
    # batched path).
    for s in range(n_seg):
      seg_states = [it[1][s][2] for _, it in members]
      eng, ctx = segs0[s][0], segs0[s][1]
      target = max(max(st.pos + num_tokens for st in seg_states),
                   max(st.cache["k"].shape[2] for st in seg_states))
      for _, it in members:
        e_i, c_i, st_i = it[1][s]
        if st_i.cache["k"].shape[2] < target:
          e_i._grow_cache(c_i, st_i, target)

    cfg = segs0[-1][1].cfg
    S = members[0][1][1][0][2].cache["k"].shape[2]
    use_fd = self._pallas_kernels_ok(cfg) and self._flash_decode_on(S)
    B = len(members)
    B_pad = _bucket(B, 1)
    pos_vec = jnp.asarray([it[1][0][2].pos for _, it in members], jnp.int32)
    temps = jnp.asarray([float(it[4]) for _, it in members], jnp.float32)
    toks = jnp.asarray([[int(it[2])] for _, it in members], jnp.int32)
    self._sample_calls += 1
    key = jax.random.fold_in(jax.random.PRNGKey(self._seed), self._sample_calls)
    seg_caches = tuple(
      tuple(it[1][s][2].cache for _, it in members) for s in range(n_seg)
    )
    out, new_seg_caches = decode_chunk_ring_batched(
      tuple(ctx.params for _, ctx, _ in segs0), seg_caches, toks, pos_vec, key,
      cfg, num_tokens, temps, top_k, top_p, use_flash_decode=use_fd,
      start_layers=tuple(ctx.shard.start_layer for _, ctx, _ in segs0),
      moe_routed=all(self._moe_routed_for(c) for _, c, _ in segs0),
      pad_rows=B_pad - B, tp_mesh=segs0[0][1].mesh,
    )
    out_np = np.asarray(out)
    now = time.monotonic()
    for b, (i, it) in enumerate(members):
      for s in range(n_seg):
        st = it[1][s][2]
        st.cache = new_seg_caches[s][b]
        st.pos = int(pos_vec[b]) + num_tokens
        st.last_used = now
      results[i] = out_np[b].astype(np.int64)
    return results

  def _ring_chunk_sync(self, segs, request_id: str, prev_token: int, num_tokens: int,
                       temp: float, top_k: int, top_p: float,
                       next_size: Optional[int]) -> Optional[np.ndarray]:
    """Executor-side body of generate_chunk_ring: capacity checks, the fused
    multi-segment dispatch, speculative next-chunk overlap, and the write-back
    of every segment's cache/position. Runs on the DRIVING engine's executor;
    peer segments' states are touched only here for the request's lifetime
    (the ring loop is the request's sole driver), so cross-engine mutation is
    race-free by construction."""
    import jax
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import decode_chunk_ring

    states = [st for _, _, st in segs]

    # Resolve an in-flight speculative ring chunk (same free-rollback design
    # as the single-shard path): a hit means the device already computed this
    # very chunk; a miss rolls every segment's optimistic advance back.
    spec = self._ring_spec.pop(request_id, None)
    spec_hit = (
      spec is not None
      # IDENTITY comparison per state: == would fall into _RequestState's
      # dataclass equality and try to compare jax-array cache pytrees.
      and len(spec["states"]) == len(states)
      and all(a is b for a, b in zip(spec["states"], states))
      and spec["prev"] == prev_token and spec["n"] == num_tokens
      and spec["temp"] == temp and spec["top_k"] == top_k and spec["top_p"] == top_p
      and all(st.pos == spec["pos"] + spec["n"] for st in states)
    )
    if spec is not None:
      self._overlap_hits += spec_hit
      self._overlap_misses += not spec_hit
      if not spec_hit:
        # Roll back the states the speculation ADVANCED (the recorded ones —
        # a replaced state object for the same request must keep its own pos).
        for st in spec["states"]:
          if st.pos == spec["pos"] + spec["n"]:
            st.pos = spec["pos"]

    max_len = min(ctx.max_cache_len for _, ctx, _ in segs)

    def dispatch(tok_dev, n: int):
      """One fused ring chunk from `tok_dev` ([1,1] int32). Grows every
      segment's cache to a common power-of-two length first (one executable
      per (n, S) pair) and advances every segment's position in lockstep."""
      pos_now = states[0].pos
      target = max(pos_now + n, max(st.cache["k"].shape[2] for st in states))
      for (eng, ctx, st) in segs:
        if st.cache["k"].shape[2] < target:
          eng._grow_cache(ctx, st, target)
      S = states[0].cache["k"].shape[2]
      use_fd = (self._pallas_kernels_ok(segs[0][1].cfg) and self._flash_decode_on(S))
      self._sample_calls += 1
      key = jax.random.fold_in(jax.random.PRNGKey(self._seed), self._sample_calls)
      toks, new_caches = decode_chunk_ring(
        tuple(ctx.params for _, ctx, _ in segs), tok_dev,
        tuple(st.cache for st in states), jnp.int32(pos_now), key,
        segs[-1][1].cfg, n, temp, top_k, top_p, use_flash_decode=use_fd,
        start_layers=tuple(ctx.shard.start_layer for _, ctx, _ in segs),
        moe_routed=all(self._moe_routed_for(c) for _, c, _ in segs),
        tp_mesh=segs[0][1].mesh,
      )
      for st, c in zip(states, new_caches):
        st.cache = c
        st.pos = pos_now + n
      return toks

    if spec_hit:
      # The speculated chunk IS this chunk (capacity was validated when it
      # was dispatched); positions already sit past it.
      toks = spec["toks"]
    else:
      pos = states[0].pos
      if any(st.pos != pos for st in states):
        # Lockstep broken (a segment restarted, partial prefill): the fused
        # path would corrupt caches — make the node fall back to the ring.
        return None
      if pos + num_tokens > max_len:
        if pos + 1 > max_len:
          raise CacheExhausted(f"request {request_id}: cache full at {pos}/{max_len}")
        tail = max_len - pos
        num_tokens = min(num_tokens, 1 << (tail.bit_length() - 1))
      toks = dispatch(jnp.asarray([[prev_token]], dtype=jnp.int32), num_tokens)

    # Speculative NEXT ring chunk: dispatch it from this chunk's device-side
    # last token BEFORE fetching — the device crunches chunk N+1 while the
    # host ingests chunk N (EOS scan + broadcast), hiding the chunk-boundary
    # round-trip exactly like the single-shard overlap path. Solo requests
    # only: under concurrency the next chunk coalesces into a ring BATCH
    # (different executable/membership), so the solo speculation would miss
    # every time — same measured rationale as the single-shard default.
    now0 = time.monotonic()
    last_ctx, last_state = segs[-1][1], states[-1]
    others_active = any(st is not last_state and now0 - st.last_used < 1.0
                        for st in last_ctx.states.values())
    spec_rec = None
    if (next_size and self._overlap_on() and not others_active
        and states[0].pos + next_size <= max_len):
      pos_before = states[0].pos
      ntoks = dispatch(toks[:, -1:].astype(jnp.int32), next_size)
      spec_rec = {"toks": ntoks, "n": next_size, "pos": pos_before, "temp": temp,
                  "top_k": top_k, "top_p": top_p, "states": list(states)}

    host = np.asarray(toks[0])  # fetch chunk N; the speculative chunk keeps computing
    if spec_rec is not None:
      spec_rec["prev"] = int(host[-1])
      self._ring_spec[request_id] = spec_rec
    now = time.monotonic()
    for st in states:
      st.last_used = now
    return host.astype(np.int64)

  def _decode_batch_max(self) -> int:
    return knobs.get_int("XOT_DECODE_BATCH")

  def _overlap_on(self) -> bool:
    """XOT_OVERLAP_CHUNKS: speculative next-chunk dispatch (default on)."""
    return knobs.get_bool("XOT_OVERLAP_CHUNKS")

  def _batch_overlap_on(self) -> bool:
    """XOT_OVERLAP_BATCH: speculative next-BATCH dispatch (default off).
    Measured on the bench TPU, concurrent batch membership jitters cycle to
    cycle (requests sit at different ladder rungs and caps), so most
    speculative batches missed and their wasted chunks cost more than the
    overlap saved (279 vs 357 tok/s aggregate). The fused
    stack/decode/split executable carries the batched win instead; flip
    this on for workloads with genuinely stable membership (fixed-width
    lockstep batch serving)."""
    return knobs.get_bool("XOT_OVERLAP_BATCH")

  def _discard_spec(self, request_id: str, state: Optional["_RequestState"] = None) -> None:
    """Drop a request's in-flight speculative chunk and roll back the
    optimistic position advance. Called whenever any OTHER operation is
    about to touch the request's device state (segment forwards, draft
    verification, cleanup) — their view of pos must be the committed one."""
    spec = self._spec_next.pop(request_id, None)
    if spec is not None and state is not None and state.pos == spec["pos"] + spec["n"]:
      state.pos = spec["pos"]

  def _discard_batch_spec(self, ctx: "_ShardContext") -> None:
    """Drop an in-flight speculative BATCH chunk: roll every member's
    optimistic position advance back to its committed value. Cache contents
    past the committed positions are invisible and get overwritten — same
    free-rollback property as the single-request path."""
    spec, ctx.batch_spec = ctx.batch_spec, None
    if spec is None:
      return
    for st, p in zip(spec["states"], spec["pos"]):
      if st.pos == p + spec["n"]:
        st.pos = p

  def _discard_batch_spec_for(self, ctx: "_ShardContext", request_id: str) -> None:
    """Discard the context's speculative batch IF this request is a member —
    the single guard every path that supersedes batch speculation must run
    (segment forwards, draft verify, membership shrink, cleanup)."""
    if ctx.batch_spec is not None and request_id in ctx.batch_spec["rids"]:
      self._discard_batch_spec(ctx)

  def _committed_pos(self, ctx: "_ShardContext", request_id: str,
                     state: "_RequestState") -> int:
    """The request's position EXCLUDING any in-flight speculative chunk —
    what capacity/room checks must judge by (the optimistic advance rolls
    back for free; treating it as real would end requests a chunk early)."""
    spec = self._spec_next.get(request_id)
    if spec is not None and state.pos == spec["pos"] + spec["n"]:
      return spec["pos"]
    b = ctx.batch_spec
    if b is not None and request_id in b["rids"]:
      i = b["rids"].index(request_id)
      if state.pos == b["pos"][i] + b["n"]:
        return b["pos"][i]
    return state.pos

  def _decode_batch_sync(self, ctx: _ShardContext, items: list, num_tokens: int,
                         top_k: int, top_p: float = 0.0,
                         allow_batch_spec: bool = True) -> list:
    """Run one fused decode chunk for 1..B requests in a single dispatch.

    B == 1 keeps the existing single-request executable (cache donated in
    place). B > 1 first GROWS every member's resident cache to a common
    power-of-two length (uniform shapes -> one compiled stack/decode/split
    executable per batch width; the cost is that a short request batched
    with a long one keeps the long buffer until it finishes — bounded by
    max_cache_len, and OOM recovery can still evict), then decodes with
    PER-ROW positions (transformer.forward_shard vector start_pos) inside
    models/generate.decode_chunk_batched — stack, scan, and split are ONE
    compiled program, not dozens of eager dispatches, since
    decode at batch 1 is HBM-bandwidth-bound on the weights."""
    import jax
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import decode_chunk

    if self._use_paged(ctx, items):
      # Paged KV (XOT_PAGED_KV): chunks index the shared page arena through
      # per-request page tables — one dispatch, no stack/split/growth.
      return self._decode_batch_paged_sync(ctx, items, num_tokens, top_k, float(top_p))

    states = [it[1] for it in items]
    for state in states:
      if state.cache is None and state.pages is not None:
        # A previously-paged request fell back to the contiguous path (env
        # change, late-attached extras): gather its pages back first.
        self._unpage_state(ctx, state, min_len=state.pos + num_tokens)

    if len(items) == 1:
      rid, state = items[0][0], states[0]
      prev_token, temp = int(items[0][2]), float(items[0][4])
      next_size = items[0][7] if len(items[0]) > 8 else None
      extras = state.extras
      # Membership shrank to one: the speculative batch can't resolve
      # through this path — commit the rolled-back positions.
      self._discard_batch_spec_for(ctx, rid)

      # Speculative-chunk resolution: if the LAST call dispatched this very
      # chunk ahead of time (same input token / size / sampling), its device
      # result is (likely) already computed — skip the dispatch entirely.
      # Any mismatch rolls pos back and decodes normally; the mispredicted
      # cache writes sit past pos, invisible and overwritten.
      spec = self._spec_next.pop(rid, None)
      spec_hit = (
        spec is not None and extras is None
        and spec["prev"] == prev_token and spec["n"] == num_tokens
        and spec["temp"] == temp and spec["top_k"] == top_k and spec["top_p"] == top_p
        and state.pos == spec["pos"] + spec["n"]
      )
      if spec is not None:
        self._overlap_hits += spec_hit
        self._overlap_misses += not spec_hit
      if spec is not None and not spec_hit and state.pos == spec["pos"] + spec["n"]:
        state.pos = spec["pos"]

      if spec_hit:
        toks = spec["toks"]
      else:
        if state.pos + num_tokens > state.cache["k"].shape[2]:
          self._grow_cache(ctx, state, state.pos + num_tokens)
        use_fd = (self._pallas_kernels_ok(ctx.cfg)
                  and self._flash_decode_on(state.cache["k"].shape[2]))
        key = self._extras_key(state, extras, request_id=rid)
        e = extras or {}
        want_lp = e.get("logprobs")
        tok = jnp.asarray([[prev_token]], dtype=jnp.int32)
        out = decode_chunk(
          ctx.params, tok, state.cache, jnp.int32(state.pos), key,
          ctx.cfg, num_tokens, temp, top_k, top_p, use_flash_decode=use_fd,
          moe_routed=self._moe_routed_for(ctx),
          bias=e.get("bias"), counts=e.get("counts"),
          presence=e.get("presence", 0.0), frequency=e.get("frequency", 0.0),
          min_p=e.get("min_p"),
          top_lp=-1 if want_lp is None else int(want_lp),
          tp_mesh=ctx.mesh,
        )
        out = list(out)
        if want_lp is not None:
          lp, top_ids, top_lps = out.pop()  # [B, T], [B, T, K] — batch row 0
          self._record_logprobs(rid, np.asarray(lp[0]), np.asarray(top_ids[0]),
                                np.asarray(top_lps[0]))
        if e.get("counts") is not None:
          toks, state.cache, extras["counts"] = out
        else:
          toks, state.cache = out
        state.pos += num_tokens

      # Dispatch the NEXT chunk before fetching this one's tokens: its
      # input is this chunk's last token — a device array — so the device
      # crunches chunk N+1 while the host runs the EOS scan and broadcast
      # for chunk N. This hides the host round-trip that otherwise
      # serializes every chunk boundary. Plain requests only:
      # extras carry host-side state (counts/logprobs) per chunk. And only
      # when NO other request is actively decoding — under concurrency this
      # request's next chunk will coalesce into a BATCH (different
      # executable, different membership), so the solo speculation would
      # miss every time and its wasted chunks cost more than they save
      # (measured: 324 vs 357 tok/s aggregate at 8 streams).
      now = time.monotonic()
      others_active = any(st is not state and now - st.last_used < 1.0
                          for st in ctx.states.values())
      spec_rec = None
      if (extras is None and next_size and self._overlap_on() and not others_active
          and state.pos + int(next_size) <= ctx.max_cache_len):
        if state.pos + int(next_size) > state.cache["k"].shape[2]:
          self._grow_cache(ctx, state, state.pos + int(next_size))
        use_fd2 = (self._pallas_kernels_ok(ctx.cfg)
                   and self._flash_decode_on(state.cache["k"].shape[2]))
        self._sample_calls += 1
        key2 = jax.random.fold_in(jax.random.PRNGKey(self._seed), self._sample_calls)
        pos_before = state.pos
        ntoks, state.cache = decode_chunk(
          ctx.params, toks[:, -1:].astype(jnp.int32), state.cache, jnp.int32(pos_before),
          key2, ctx.cfg, int(next_size), temp, top_k, top_p, use_flash_decode=use_fd2,
          moe_routed=self._moe_routed_for(ctx), tp_mesh=ctx.mesh,
        )
        state.pos += int(next_size)
        spec_rec = {"toks": ntoks, "n": int(next_size), "pos": pos_before,
                    "temp": temp, "top_k": top_k, "top_p": top_p}

      host = np.asarray(toks[0])  # fetch chunk N; chunk N+1 keeps computing
      if spec_rec is not None:
        spec_rec["prev"] = int(host[-1])
        self._spec_next[rid] = spec_rec
      state.last_used = time.monotonic()
      return [host.astype(np.int64)]

    # Multi-request batch: any SINGLE-request speculation is superseded —
    # commit those rolled-back positions first.
    for it in items:
      self._discard_spec(it[0], it[1])

    def dispatch_batch(row_tokens_dev, n_toks: int, temps):
      """One batched fused chunk over the CURRENT states, fully inside ONE
      compiled program (models/generate.decode_chunk_batched): stack the
      caches, decode, split back — eager per-leaf concat/slice ops here
      used to cost dozens of dispatches per cycle, which dominated the
      batched path end to end. Members first grow to a COMMON power-of-two
      cache length so the executable specializes on one shape tuple.
      `row_tokens_dev` is [B, 1]. Returns the [B, n_toks] device tokens."""
      from xotorch_tpu.models.generate import decode_chunk_batched
      target = max(max(s.pos + n_toks for s in states),
                   max(s.cache["k"].shape[2] for s in states))
      for state in states:
        if state.cache["k"].shape[2] < target:
          self._grow_cache(ctx, state, target)
      S_uniform = states[0].cache["k"].shape[2]
      use_fd = (self._pallas_kernels_ok(ctx.cfg) and self._flash_decode_on(S_uniform))
      pos_vec = jnp.asarray([s.pos for s in states], dtype=jnp.int32)
      # Per-ROW temperatures (traced): mixed-temperature requests share the
      # dispatch; dummy pad rows are built inside the executable.
      temp_vec = jnp.asarray(list(temps), jnp.float32)
      self._sample_calls += 1
      key = jax.random.fold_in(jax.random.PRNGKey(self._seed), self._sample_calls)
      out, new_caches = decode_chunk_batched(
        ctx.params, tuple(s.cache for s in states), row_tokens_dev, pos_vec, key,
        ctx.cfg, n_toks, temp_vec, top_k, top_p, use_flash_decode=use_fd,
        pad_rows=B_pad - B, moe_routed=self._moe_routed_for(ctx),
        tp_mesh=ctx.mesh,
      )
      for state, c in zip(states, new_caches):
        state.cache = c
        state.pos += n_toks
      return out

    # Pad the batch width to a power of two (dummy rows replicate row 0 and
    # are discarded): bounds the decode executables to log2(B_max) widths
    # instead of one compile per distinct concurrency level mid-serving.
    B = len(states)
    B_pad = _bucket(B, 1)
    rids = tuple(it[0] for it in items)
    temps = tuple(float(it[4]) for it in items)
    prevs = [int(it[2]) for it in items]

    # Resolve an in-flight speculative batch: same ordered membership, same
    # size/temps/sampling constants, each row's input token matching — its
    # device result IS this batch's answer, no dispatch needed.
    bspec = ctx.batch_spec
    bhit = (
      bspec is not None
      and bspec["rids"] == rids and bspec["n"] == num_tokens
      and bspec["temps"] == temps and bspec["top_k"] == top_k and bspec["top_p"] == top_p
      and bspec["prev"] == prevs
      and all(st.pos == p + num_tokens for st, p in zip(bspec["states"], bspec["pos"]))
    )
    if bspec is not None:
      self._overlap_batch_hits += bhit
      self._overlap_batch_misses += not bhit
    if bhit:
      ctx.batch_spec = None
      out = bspec["toks"]  # caches were split and positions advanced at dispatch
    else:
      self._discard_batch_spec(ctx)
      out = dispatch_batch(jnp.asarray([[t] for t in prevs], jnp.int32), num_tokens, temps)

    # Speculative NEXT batch: dispatch it from this batch's device-side last
    # tokens before fetching this batch's results — the device crunches
    # chunk N+1 while every member's loop ingests chunk N (the same overlap
    # as the single-request path, multiplied by the batch width).
    next_sizes = [it[7] if len(it) > 8 else None for it in items]
    spec_rec = None
    if (allow_batch_spec and self._batch_overlap_on() and all(ns for ns in next_sizes)
        and all(s.extras is None for s in states)):
      n2 = min(int(ns) for ns in next_sizes)
      if all(s.pos + n2 <= ctx.max_cache_len for s in states):
        pos2 = [s.pos for s in states]
        toks2 = dispatch_batch(out[:, -1:].astype(jnp.int32), n2, temps)
        spec_rec = {"rids": rids, "n": n2, "toks": toks2, "prev": None, "pos": pos2,
                    "temps": temps, "top_k": top_k, "top_p": top_p,
                    "states": list(states)}

    out_np = np.asarray(out)  # fetch chunk N; the speculative batch keeps computing
    if spec_rec is not None:
      spec_rec["prev"] = [int(out_np[i, -1]) for i in range(len(states))]
      ctx.batch_spec = spec_rec
    now = time.monotonic()
    for state in states:
      state.last_used = now
    return [out_np[i].astype(np.int64) for i in range(len(states))]

  # -------------------------------------------------------------- paged KV
  #
  # XOT_PAGED_KV=1: requests' KV lives as fixed-size pages in ONE shared
  # arena per context (paged_cache.PagePool) instead of per-request
  # contiguous buffers. The page arena is the request's home for its WHOLE
  # lifetime: paged-NATIVE prefill (XOT_PAGED_PREFILL, default on) scatters
  # every segment's K/V straight into pool pages (prefill_scan /
  # forward_sample with a page table), so there is no contiguous prefill
  # buffer, no commit copy, and no double-residency window — and a warm
  # prefix hit increfs the matched full pages in place instead of gathering
  # them back. Decode chunks index the arena through per-request page
  # tables (models/generate.decode_chunk_paged): batch membership is
  # metadata, appends allocate pages instead of grow-copying, and attention
  # reads only each row's occupied pages. _commit_state_to_pages remains
  # for requests that still prefill contiguous (hidden input,
  # XOT_PAGED_PREFILL=0) and counts its copied bytes (_commit_copy_bytes —
  # zero for the native path). Contiguous remains the default until on-chip
  # A/B numbers land (bench.py `paged` / `vkv` stages).
  #
  # VIRTUAL ADDRESSING (vkv.py): requests hold VirtualKV handles — logical
  # page slots naming physical ids, resolved once per dispatch by the
  # jit-free vkv.resolve_page_table mapper. Every paged family rides it:
  # sliding-window configs release out-of-window pages back to the pool as
  # decode advances (_vkv_window_release; the kernels' windowed _kv_map
  # clamp bounds the DMA to live pages), int8-KV pairs K/V pages with
  # per-(position, head) scale pages from the same arena, and idle-slot
  # defrag (_defrag_sync) migrates pages under live requests by rewriting
  # only the virtual maps. There is no family gate list anymore.

  def _paged_on(self) -> bool:
    return knobs.get_bool("XOT_PAGED_KV")

  def _paged_kernel_on(self) -> bool:
    """XOT_PAGED_KERNEL: 1 = force the Pallas ragged kernel (interpret mode
    off-TPU), 0 = force the jnp.take XLA fallback, unset = kernel on real
    TPU only."""
    env = knobs.raw("XOT_PAGED_KERNEL")
    on = env == "1" if env is not None else self._jax().default_backend() == "tpu"
    return self._selected("paged", on)

  def _ragged_prefill_on(self) -> bool:
    """XOT_RAGGED_PREFILL: under the kernel path, T>1 segments read pages
    NATIVELY through the ragged paged-attention kernel (page-table-
    indirected kv BlockSpecs — no gathered-view materialisation on the
    prefill/verify hot path). 0 restores the legacy gather + cached-kernel
    read for on-chip A/B."""
    return knobs.get_bool("XOT_RAGGED_PREFILL")

  def _paged_spec_on(self) -> bool:
    """XOT_PAGED_SPEC: draft verification runs native to the page arena
    (T>1 ragged query over the request's page table). 0 restores the
    unpage-then-verify-contiguous fallback."""
    return knobs.get_bool("XOT_PAGED_SPEC")

  def _ensure_page_pool(self, ctx: _ShardContext):
    if ctx.page_pool is None:
      from xotorch_tpu.inference.jax_engine.paged_cache import PagePool
      page = knobs.get_int("XOT_KV_PAGE")
      tokens = knobs.get_int("XOT_KV_POOL_TOKENS")
      if tokens <= 0:
        # Room for one max-length context plus a typical batch of
        # initial-allocation-sized requests; ceil'd to whole pages.
        tokens = ctx.max_cache_len + MAX_RESIDENT_REQUESTS * ctx.cache_len
      num_pages = -(-tokens // page) + 1  # +1: reserved scratch page 0
      ctx.page_pool = PagePool(ctx.cfg, ctx.shard.get_layer_count(), num_pages,
                               page, self._dtype(), mesh=ctx.mesh,
                               kv_quant=self._kv_quant is not None)
      if DEBUG >= 1:
        print(f"KV page pool ready: {num_pages - 1} pages x {page} tokens")
    return ctx.page_pool

  def _pool_alloc(self, ctx: _ShardContext, pool, n: int) -> list:
    """pool.alloc with reclaim: prefix entries are CACHES — under pool
    pressure they must yield to live requests, not pin pages until clients
    see 'pool exhausted' errors the contiguous path never produces. Evict
    oldest-first (decref) and retry; entries whose pages are still shared
    with live requests free nothing (ref > 1) and the loop keeps going.
    Only when no entry is left to evict does the exhaustion surface.
    Evicted entries SPILL to the host tier first (spill-then-drop): pool
    pressure demotes the warm set one level instead of destroying it."""
    while True:
      try:
        ids = pool.alloc(n)
        if self.flight is not None and n > 0:
          self.flight.record("pool.alloc", None, pages=n, free=pool.free_pages)
        return ids
      except CacheExhausted:
        if self.flight is not None:
          self.flight.record("pool.pressure", None, need=n, free=pool.free_pages,
                             in_use=pool.pages_in_use)
        evicted = False
        while ctx.prefix_cache and not evicted:
          _, (etoks, entry) = ctx.prefix_cache.popitem(last=False)
          self._spill_prefix_entry(ctx, etoks, entry)
          self._prefix_evictions += 1
          if isinstance(entry, dict) and "pages" in entry:
            pool.decref(entry["pages"])
            evicted = True
        if not evicted:
          raise

  def _commit_state_to_pages(self, ctx: _ShardContext, state: _RequestState) -> None:
    """Move a prefilled request's contiguous KV into pool pages and free the
    buffer. Prefix-shared pages held in `paged_seed` (already incref'd, page
    -aligned below pos by construction) become the table's head; only the
    suffix is copied. From here on the request decodes via the paged path;
    contiguous code paths that touch it later un-page it (_unpage_state)."""
    from xotorch_tpu.inference.jax_engine.paged_cache import commit_pages
    pool = self._ensure_page_pool(ctx)
    n = pool.pages_for(state.pos)
    seed = list(state.paged_seed or [])
    fresh = self._pool_alloc(ctx, pool, n - len(seed))
    if fresh:
      pool.arena = commit_pages(pool.arena, state.cache, np.asarray(fresh, np.int32),
                                start_page=len(seed))
      leaf = pool.arena["k"]  # [L, P, page, Hkv, D]
      self._commit_copy_bytes += (2 * len(fresh) * leaf.shape[0] * leaf.shape[2]
                                  * leaf.shape[3] * leaf.shape[4] * leaf.dtype.itemsize)
      sc = pool.arena.get("k_scale")  # int8 arena: scale pages ride the copy
      if sc is not None:
        self._commit_copy_bytes += (2 * len(fresh) * sc.shape[0] * sc.shape[2]
                                    * sc.shape[3] * sc.dtype.itemsize)
    state.pages = VirtualKV(seed + fresh)
    state.paged_seed = None
    state.cache = None

  def _unpage_state(self, ctx: _ShardContext, state: _RequestState,
                    min_len: int = 0) -> None:
    """Gather a paged request back into a contiguous buffer (the reverse of
    commit). Since virtual KV addressing this is a LEGACY path: segment
    forwards, per-token decode, and extras all stay on pages
    (_forward_segment_paged / decode_chunk_paged extras), so only
    XOT_PAGED_SPEC=0 — the explicit restore-the-old-fallbacks knob — can
    reach it (xot_kv_unpage_total counts every invocation; the paged tests
    assert it stays 0 suite-wide)."""
    import jax
    from xotorch_tpu.inference.jax_engine.paged_cache import gather_pages
    self._unpage_calls += 1
    pool = ctx.page_pool
    need = min(max(min_len, state.pos, 1), ctx.max_cache_len)
    length = ctx.cache_len
    while length < need and length < ctx.max_cache_len:
      length *= 2
    length = min(length, ctx.max_cache_len)
    cache = self._new_cache(ctx, length)
    if not state.pages:
      # A page-backed state that never wrote anything (pos 0): nothing to
      # gather — hand back a fresh buffer.
      state.cache = cache
      state.pages = None
      return
    # Released (windowed) slots resolve to the scratch page: its zeros
    # gather into dead positions no query can see (the legacy path only
    # serves non-windowed configs anyway).
    gathered = gather_pages(pool.arena, np.asarray(list(state.pages), np.int32))
    cut = min(len(state.pages) * pool.page_size, length)
    state.cache = {
      name: jax.lax.dynamic_update_slice(
        cache[name], gathered[name][:, :, :cut].astype(cache[name].dtype),
        (0,) * cache[name].ndim)
      for name in cache
    }
    pool.decref(vkv.as_handle(state.pages).live())
    state.pages = None

  # ------------------------------------------------- paged-NATIVE prefill

  def _paged_prefill_on(self) -> bool:
    """XOT_PAGED_PREFILL: prefill segments scatter straight into pool pages
    (default on under XOT_PAGED_KV — no contiguous buffer, no commit copy,
    no double-residency window). 0 restores prefill-then-commit."""
    return knobs.get_bool("XOT_PAGED_PREFILL")

  def _paged_prefill_ok(self, ctx: _ShardContext, request_id: str, input_data,
                        sampling: Optional[dict]) -> bool:
    """Qualification rule for paged-native prefill: token input on a
    full-model shard (mid-ring shards see hidden states), batch 1, no sp
    ring prefill (which shards positions over chips and outranks), and a
    state that is either fresh or already page-backed (a contiguous state
    keeps its path). Sampling extras qualify — forward_sample threads them
    alongside the page table, and the request then decodes paged too
    (decode_chunk_paged extras), so it never leaves the arena."""
    if not (self._paged_on() and self._paged_prefill_on()
            and ctx.shard.is_first_layer and ctx.shard.is_last_layer
            and getattr(input_data, "ndim", 0) == 2 and input_data.shape[0] == 1
            and not (ctx.fill_jits is not None and "ring" in ctx.fill_jits)):
      return False
    st = ctx.states.get(request_id)
    return st is None or (st.cache is None and st.pages is not None)

  def _get_or_create_paged_state(self, ctx: _ShardContext, request_id: str) -> _RequestState:
    """Page-backed twin of _get_or_create_state: the state NEVER owns a
    contiguous buffer — its KV lives in pool pages from the first prefill
    segment on (cache=None, pages=[])."""
    state = ctx.states.get(request_id)
    if state is None:
      if request_id in self._states_lost_to_oom:
        raise RequestStateLost(
          f"request {request_id}: device state dropped by OOM recovery")
      state = _RequestState(cache=None, pos=0, last_used=time.monotonic(),
                            pages=VirtualKV())
      ctx.states[request_id] = state
      while len(ctx.states) > MAX_RESIDENT_REQUESTS:
        evicted, est = ctx.states.popitem(last=False)
        self._release_state_pages(ctx, est)
        if DEBUG >= 2:
          print(f"Evicted request state {evicted}")
    ctx.states.move_to_end(request_id)
    return state

  def _prep_state_paged(self, ctx: _ShardContext, request_id: str, bucket: int) -> _RequestState:
    """Page-backed twin of _prep_state: capacity for `bucket` more tokens is
    PAGES, not a buffer grow. The table must cover the padded bucket — its
    tail-padding garbage writes land in pages this request owns (masked by
    per-row length, overwritten by later writes at the same positions);
    _paged_sample_sync trims the overshoot back to pages_for(pos) after the
    prompt lands. Pool exhaustion raises CacheExhausted BEFORE any device
    work, for the incoming request only — co-resident decode streams' pages
    are untouched."""
    pool = self._ensure_page_pool(ctx)
    state = self._get_or_create_paged_state(ctx, request_id)
    if state.pages is None:
      raise AssertionError(f"request {request_id}: paged prefill on a contiguous state")
    self._discard_spec(request_id, state)
    self._discard_batch_spec_for(ctx, request_id)
    needed = state.pos + bucket
    if needed > ctx.max_cache_len:
      raise CacheExhausted(
        f"Request {request_id}: {bucket} new tokens at pos {state.pos} "
        f"exceed max cache length {ctx.max_cache_len}")
    need_pages = pool.pages_for(needed)
    if need_pages > len(state.pages):
      state.pages.extend(self._pool_alloc(ctx, pool, need_pages - len(state.pages)))
    return state

  def _device_table(self, ctx: _ShardContext, table: np.ndarray):
    """Place a host-built page table on the device(s). Under a serving
    mesh the table is committed REPLICATED explicitly: every paged
    executable then sees mesh-consistent input shardings (arena Hkv-
    sharded per cache_spec, table/positions replicated) instead of leaving
    GSPMD to re-infer a layout per executable — page ids index the arena's
    unsharded page axis, so every tp shard needs the whole table. The put
    is an async host→device copy of a few KB of metadata, not a sync."""
    import jax.numpy as jnp
    if ctx.mesh is None:
      return jnp.asarray(table)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(table, NamedSharding(ctx.mesh, PartitionSpec()))

  def _paged_table_for(self, ctx: _ShardContext, state: _RequestState):
    """The request's [1, maxp] device page table, width bucketed to a power
    of two (0-padded — the scratch page, masked) so the prefill executables
    stay logarithmic in context length. Physical resolution of the virtual
    handle happens HERE, once per dispatch (vkv.resolve_page_table):
    window-released slots resolve to scratch and the kernels' windowed
    clamp never reads them."""
    maxp = _bucket(max(len(state.pages), 1), 1)
    return self._device_table(ctx, vkv.resolve_page_table([state.pages], maxp))

  def _paged_fill_sync(self, ctx: _ShardContext, request_id: str, input_data) -> None:
    """Fill-only paged-native prefill of `input_data` (length a multiple of
    the prefill chunk): segments scatter straight into pool pages under the
    fused scan executable — the paged twin of _scan_prefill, with the same
    power-of-two group decomposition (log dispatches, bounded executables).
    No contiguous buffer exists at any point."""
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import prefill_scan, scan_groups
    chunk = self._prefill_chunk()
    total = int(input_data.shape[1])
    state = self._prep_state_paged(ctx, request_id, total)
    pool = ctx.page_pool
    x = self._to_device_input(input_data)
    table = self._paged_table_for(ctx, state)
    use_kernel = self._paged_kernel_on()
    for off, g in scan_groups(total // chunk):
      _, pool.arena = prefill_scan(
        ctx.params, x[:, off * chunk:(off + g) * chunk], pool.arena, jnp.int32(state.pos),
        ctx.cfg, g, is_first=True, start_layer=ctx.shard.start_layer,
        moe_routed=self._moe_routed_for(ctx),
        page_table=table, paged_kernel=use_kernel,
        ragged_prefill=self._ragged_prefill_on(), tp_mesh=ctx.mesh)
      state.pos += g * chunk
    # Long windowed prompts free their dead head DURING prefill: later
    # segments' queries sit at >= pos, so pages the window slid past are
    # already invisible to every remaining read.
    self._vkv_window_release(ctx, state)
    state.last_used = time.monotonic()

  def _paged_sample_sync(self, ctx: _ShardContext, request_id: str, input_data,
                         temp: float, top_k: int, top_p: float,
                         full_prompt: Optional[np.ndarray],
                         sampling: Optional[dict] = None) -> int:
    """Final paged-native prefill segment + ON-DEVICE first-token sampling:
    forward_sample over the page arena. After the prompt lands the request
    is ALREADY page-resident — its first decode chunk is pure metadata
    (no _commit_state_to_pages copy, no freed buffer). Sampling extras
    (bias/penalties/min-p/logprobs) thread through the same executable the
    contiguous epilogue uses — extras requests stay paged end to end."""
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import forward_sample
    true_t = int(input_data.shape[1])
    bucket = 1 if true_t == 1 else _bucket(true_t)
    state = self._prep_state_paged(ctx, request_id, bucket)
    pool = ctx.page_pool
    x = self._to_device_input(input_data)
    if bucket != true_t:
      x = jnp.pad(x, [(0, 0), (0, bucket - true_t)])
    table = self._paged_table_for(ctx, state)
    if sampling and state.extras is None:
      state.extras = self._build_extras(ctx, sampling)
    extras = state.extras
    key = self._extras_key(state, extras, request_id=request_id,
                           sample_pos=state.pos + true_t - 1)
    e = extras or {}
    want_lp = e.get("logprobs")
    out, pool.arena = forward_sample(
      ctx.params, x, pool.arena, jnp.int32(state.pos), jnp.int32(true_t - 1), key,
      ctx.cfg, True, temp, top_k, top_p,
      start_layer=ctx.shard.start_layer, moe_routed=self._moe_routed_for(ctx),
      bias=e.get("bias"), counts=e.get("counts"),
      presence=e.get("presence", 0.0), frequency=e.get("frequency", 0.0),
      min_p=e.get("min_p"),
      top_lp=-1 if want_lp is None else int(want_lp),
      page_table=table, paged_kernel=self._paged_kernel_on(),
      ragged_prefill=self._ragged_prefill_on(), tp_mesh=ctx.mesh)
    if want_lp is not None:
      tok, lp, top_ids, top_lps = out
      self._record_logprobs(request_id, np.asarray(lp), np.asarray(top_ids),
                            np.asarray(top_lps))
    else:
      tok = out
    state.pos += true_t
    # Trim the padded bucket's overshoot: pages past pages_for(pos) hold
    # only padding garbage and are exclusively ours (fresh-allocated; the
    # shared prefix sits below pos) — return them to the pool. Then release
    # whatever the window already slid past.
    freed = state.pages.trim_to(pool.pages_for(state.pos))
    if freed:
      pool.decref(freed)
    self._vkv_window_release(ctx, state)
    state.last_used = time.monotonic()
    if full_prompt is not None:
      self._prefix_store(ctx, request_id, full_prompt)
    tok_int = int(np.asarray(tok).reshape(-1)[0])
    if extras and extras.get("counts") is not None:
      extras["counts"] = extras["counts"].at[0, tok_int % ctx.cfg.vocab_size].add(1)
    return tok_int

  def page_pool_stats(self) -> Optional[Dict[str, int]]:
    """Aggregate page-pool occupancy across resident contexts, or None when
    no pool exists (the /metrics gauges appear only under XOT_PAGED_KV)."""
    pools = [c.page_pool for c in self._contexts.values() if c.page_pool is not None]
    if not pools:
      return None
    return {"pages_in_use": sum(p.pages_in_use for p in pools),
            "free_pages": sum(p.free_pages for p in pools),
            "peak_pages_in_use": sum(p.peak_pages_in_use for p in pools),
            "fragmentation": sum(p.fragmentation() for p in pools),
            "defrag_moves": self._defrag_moves}

  def _release_state_pages(self, ctx: _ShardContext, state: _RequestState) -> None:
    """Drop a finished/evicted request's page references (committed table
    AND any not-yet-committed prefix-seed holds). Pages shared with the
    prefix cache or other requests survive via their own refs."""
    pool = ctx.page_pool
    if pool is None:
      return
    if state.pages is not None:
      pool.decref(vkv.as_handle(state.pages).live())
      state.pages = None
    if state.paged_seed:
      pool.decref(state.paged_seed)
      state.paged_seed = None

  def _vkv_window_release(self, ctx: _ShardContext, state: _RequestState) -> None:
    """Sliding-window page reclamation: once EVERY layer this shard serves
    is windowed, pages wholly behind the widest window can never be read
    again (queries only advance) — zero their virtual slots and return the
    physical pages to the pool while the request keeps decoding. The
    virtual map keeps its length (the len(pages) == pages_for(pos)
    arithmetic everywhere is untouched); released slots resolve to the
    scratch page, which the kernels' windowed clamp never DMAs. One
    global-attention layer in the shard (gemma2-style alternation) disables
    freeing entirely — its reads reach back to position 0."""
    pool = ctx.page_pool
    if pool is None or not isinstance(state.pages, VirtualKV):
      return
    w = vkv.freeable_window(ctx.cfg, ctx.shard.start_layer,
                            ctx.shard.get_layer_count())
    if w <= 0:
      return
    freed = state.pages.release_below(
      vkv.dead_page_count(state.pos, w, pool.page_size))
    if freed:
      pool.decref(freed)
      if self.flight is not None:
        self.flight.record("vkv.window_free", None, pages=len(freed),
                           pos=state.pos, window=w)

  def _defrag_on(self) -> bool:
    """XOT_KV_DEFRAG: compact the page pool in batcher-idle slots (window
    release and request churn strand free holes below the high-water mark;
    compaction keeps long-lived arenas dense without touching requests)."""
    return knobs.get_bool("XOT_KV_DEFRAG")

  def _defrag_max_moves(self) -> int:
    try:
      return max(1, knobs.get_int("XOT_KV_DEFRAG_MAX_MOVES"))
    except ValueError:
      return 8

  def _defrag_sync(self, ctx: _ShardContext, max_moves: Optional[int] = None) -> int:
    """One bounded compaction pass (executor thread, batcher-idle slots):
    migrate the highest used pages into the lowest free holes with ONE
    donated gather-scatter, then rewrite only the VIRTUAL maps — every
    holder of a physical id (request handles, uncommitted prefix seeds,
    prefix-cache entries) renames src -> dst; no request state, position,
    or cache byte changes meaning. Returns pages moved. Requests in flight
    are safe by construction: tables are resolved fresh from the handles at
    every dispatch, and the executor serializes this pass against them."""
    pool = ctx.page_pool
    if pool is None:
      return 0
    plan = pool.defrag_plan(max_moves if max_moves is not None
                            else self._defrag_max_moves())
    if not plan:
      return 0
    from xotorch_tpu.inference.jax_engine.paged_cache import migrate_pages
    srcs = [s for s, _ in plan]
    dsts = [d for _, d in plan]
    pool.arena = migrate_pages(pool.arena, srcs, dsts)
    mapping = {s: d for s, d in plan}
    for st in ctx.states.values():
      if isinstance(st.pages, VirtualKV):
        st.pages.remap(mapping)
      elif st.pages is not None:
        st.pages = VirtualKV(vkv.remap_ids(st.pages, mapping))
      if st.paged_seed:
        st.paged_seed = vkv.remap_ids(st.paged_seed, mapping)
    for _, entry in ctx.prefix_cache.values():
      if isinstance(entry, dict) and "pages" in entry:
        entry["pages"] = vkv.remap_ids(entry["pages"], mapping)
    pool.apply_moves(plan)
    self._defrag_moves += len(plan)
    if self.flight is not None:
      self.flight.record("vkv.defrag", None, moves=len(plan),
                         fragmentation=pool.fragmentation())
    return len(plan)

  def _clear_prefix_cache(self, ctx: _ShardContext) -> None:
    """Drop every prefix entry, returning paged entries' page references to
    the pool (a bare .clear() would leak their refcounts). Every caller
    clears because the entries became INVALID (weight swap, adapter churn)
    — so the host tier's entries for this context are dropped too, never
    spilled: serving a stale prefix under new weights would be silently
    wrong tokens, the one failure mode the tier must never have."""
    pool = ctx.page_pool
    for _, entry in ctx.prefix_cache.values():
      if pool is not None and isinstance(entry, dict) and "pages" in entry:
        pool.decref(entry["pages"])
    ctx.prefix_cache.clear()
    if self._host_kv is not None:
      self._host_kv.drop_ctx(ctx.shard)

  def _use_paged(self, ctx: _ShardContext, items: list) -> bool:
    """One qualification rule for routing a decode dispatch to the paged
    path: XOT_PAGED_KV decides, full stop — every family (sliding window,
    int8 KV, sampling extras) is paged-servable under virtual addressing.
    Extras members run as their own single-row dispatches inside
    _decode_batch_paged_sync (their bias/counts plumbing is per-request),
    but they never leave the arena."""
    return self._paged_on()

  def _decode_batch_paged_sync(self, ctx: _ShardContext, items: list, num_tokens: int,
                               top_k: int, top_p: float = 0.0) -> list:
    """Paged twin of the batched fused chunk: commit any member still on its
    prefill buffer, append pages to cover the chunk, and run ONE
    decode_chunk_paged dispatch indexing the shared arena — no cache
    stack/split, no common-length growth, no grow-copies. The page-table
    width is bucketed to a power of two so executables stay logarithmic in
    the longest resident context. Sampling extras thread through the same
    executable when the dispatch is a single row (their bias/counts are
    per-request [1, V] state) — a mixed batch splits extras members into
    their own rows first, so NOBODY leaves the arena."""
    import jax.numpy as jnp
    from xotorch_tpu.models.generate import decode_chunk_paged
    pool = self._ensure_page_pool(ctx)
    states = [it[1] for it in items]
    if len(items) > 1 and any(s.extras is not None for s in states):
      by_rid: Dict[str, Any] = {}
      plain = [it for it in items if it[1].extras is None]
      if plain:
        for it, r in zip(plain, self._decode_batch_paged_sync(
            ctx, plain, num_tokens, top_k, top_p)):
          by_rid[it[0]] = r
      for it in items:
        if it[1].extras is not None:
          by_rid[it[0]] = self._decode_batch_paged_sync(
            ctx, [it], num_tokens, top_k, top_p)[0]
      return [by_rid[it[0]] for it in items]
    for it in items:
      # Any leftover speculation records belong to the contiguous path —
      # supersede them before touching positions.
      self._discard_spec(it[0], it[1])
      self._discard_batch_spec_for(ctx, it[0])
    # max_cache_len backstop (generate_chunk already guards per request
    # before submitting): positions past the model's max context would get
    # out-of-range RoPE AND drain the SHARED pool — shrink to the tightest
    # member's tail (largest po2, same ladder as generate_chunk), and fail
    # loudly if a member has no room at all.
    for it in items:
      if it[1].pos + 1 > ctx.max_cache_len:
        raise CacheExhausted(
          f"request {it[0]}: cache full at {it[1].pos}/{ctx.max_cache_len}")
    tail = min(ctx.max_cache_len - s.pos for s in states)
    if num_tokens > tail:
      num_tokens = 1 << (tail.bit_length() - 1)
    for state in states:
      if state.pages is None:
        self._commit_state_to_pages(ctx, state)
      need = pool.pages_for(state.pos + num_tokens)
      if need > len(state.pages):
        state.pages.extend(self._pool_alloc(ctx, pool, need - len(state.pages)))
    B = len(states)
    maxp = _bucket(max(len(s.pages) for s in states), 1)
    # The once-per-dispatch physical resolution of every member's virtual
    # handle (0-padded: the scratch page, masked / window-clamped).
    table = vkv.resolve_page_table([s.pages for s in states], maxp)
    B_pad = _bucket(B, 1)
    pos_vec = jnp.asarray([s.pos for s in states], jnp.int32)
    temps = jnp.asarray([float(it[4]) for it in items], jnp.float32)
    toks = jnp.asarray([[int(it[2])] for it in items], jnp.int32)
    extras = states[0].extras if B == 1 else None
    e = extras or {}
    want_lp = e.get("logprobs")
    key = self._extras_key(states[0], extras, request_id=items[0][0])
    res = list(decode_chunk_paged(
      ctx.params, pool.arena, self._device_table(ctx, table), toks, pos_vec, key, ctx.cfg,
      num_tokens, temps, top_k, top_p, use_kernel=self._paged_kernel_on(),
      pad_rows=B_pad - B, moe_routed=self._moe_routed_for(ctx),
      bias=e.get("bias"), counts=e.get("counts"),
      presence=e.get("presence", 0.0), frequency=e.get("frequency", 0.0),
      top_lp=-1 if want_lp is None else int(want_lp),
      min_p=e.get("min_p"),
      tp_mesh=ctx.mesh))
    out, pool.arena = res[0], res[1]
    idx = 2
    if e.get("counts") is not None:
      extras["counts"] = res[idx]
      idx += 1
    if want_lp is not None:
      lp, top_ids, top_lps = res[idx]
      self._record_logprobs(items[0][0], np.asarray(lp[0]), np.asarray(top_ids[0]),
                            np.asarray(top_lps[0]))
    out_np = np.asarray(out)
    now = time.monotonic()
    for state in states:
      state.pos += num_tokens
      self._vkv_window_release(ctx, state)
      state.last_used = now
    return [out_np[i].astype(np.int64) for i in range(B)]

  def _prep_state(self, ctx: _ShardContext, request_id: str, bucket: int) -> _RequestState:
    """State + capacity for `bucket` more tokens. Checks are against the
    padded bucket, not true_t: dynamic_update_slice CLAMPS out-of-range
    starts, which would silently overwrite earlier cache slots. Runs on the
    engine executor (it may touch the device to grow the cache)."""
    state = self._get_or_create_state(ctx, request_id, min_len=bucket)
    if state.cache is None and state.pages is not None:
      # A contiguous code path (segment forward, draft verify, per-token
      # decode) is touching a paged request: gather it back first.
      self._unpage_state(ctx, state, min_len=state.pos + bucket)
    # A segment forward (prefill, per-token ring, draft verify) supersedes
    # any speculatively dispatched chunk: commit the rolled-back position
    # before capacity math.
    self._discard_spec(request_id, state)
    self._discard_batch_spec_for(ctx, request_id)
    needed = state.pos + bucket
    if needed > ctx.max_cache_len:
      raise CacheExhausted(
        f"Request {request_id}: {bucket} new tokens at pos {state.pos} "
        f"exceed max cache length {ctx.max_cache_len}"
      )
    if needed > state.cache["k"].shape[2]:
      self._grow_cache(ctx, state, needed)
    return state

  def _grow_cache(self, ctx: _ShardContext, state: _RequestState, needed: int) -> None:
    """Double the request's KV buffer until it fits `needed` (caller bounds
    against max_cache_len). Power-of-two sizes keep the executable count
    logarithmic; contents are preserved, tail slots zero-padded."""
    import jax
    import jax.numpy as jnp
    self._grow_copies += 1
    S = state.cache["k"].shape[2]
    new_len = S
    while new_len < needed:
      new_len *= 2
    new_len = min(new_len, ctx.max_cache_len)

    def _pad(x):
      pad = [(0, 0)] * x.ndim
      pad[2] = (0, new_len - S)
      return jnp.pad(x, pad)

    state.cache = jax.tree.map(_pad, state.cache)
    if ctx.mesh is not None:
      from xotorch_tpu.parallel.mesh import shard_cache
      state.cache = shard_cache(state.cache, ctx.mesh)
    if DEBUG >= 2:
      print(f"KV cache grown {S} -> {new_len}")

  def _get_or_create_state(self, ctx: _ShardContext, request_id: str, min_len: int = 0) -> _RequestState:
    """Per-request device state with LRU residency (shared by the text,
    multimodal, and fused-decode paths — one lifecycle, no drift). A fresh
    state is allocated at the bucket size covering min_len so a long prompt
    doesn't allocate-then-immediately-regrow."""
    state = ctx.states.get(request_id)
    if state is None:
      if request_id in self._states_lost_to_oom:
        # The plain infer path would otherwise silently recreate a pos=0
        # state and decode with no context after an OOM recovery dropped
        # it. The entry stays (LRU-bounded): retries of a dead request must
        # keep failing loudly, and request ids are never reused (uuids).
        raise RequestStateLost(
          f"request {request_id}: device state dropped by OOM recovery")
      length = ctx.cache_len
      while length < min_len and length < ctx.max_cache_len:
        length *= 2
      # The doubling can overshoot a non-power-of-two max; never allocate
      # beyond the configured bound (callers raise CacheExhausted when even
      # max_cache_len can't fit the request).
      length = min(length, ctx.max_cache_len)
      state = _RequestState(cache=self._new_cache(ctx, length), pos=0, last_used=time.monotonic())
      ctx.states[request_id] = state
      while len(ctx.states) > MAX_RESIDENT_REQUESTS:
        evicted, est = ctx.states.popitem(last=False)
        self._release_state_pages(ctx, est)
        if DEBUG >= 2:
          print(f"Evicted request state {evicted}")
    # True LRU: refresh recency on every touch, not just creation.
    ctx.states.move_to_end(request_id)
    return state

  def _new_cache(self, ctx: _ShardContext, length: Optional[int] = None):
    from xotorch_tpu.models.transformer import init_kv_cache
    cache = init_kv_cache(ctx.cfg, ctx.shard.get_layer_count(), 1, length or ctx.cache_len,
                          self._dtype(), kv_quant=self._kv_quant is not None)
    if ctx.mesh is not None:
      # KV heads shard over tp alongside the attention weights, so the cache
      # stays distributed across the local chips' HBM for the request's life.
      from xotorch_tpu.parallel.mesh import shard_cache
      cache = shard_cache(cache, ctx.mesh)
    return cache

  # ------------------------------------------------------------ shard setup

  async def ensure_shard(self, shard: Shard) -> None:
    await self._ensure_ctx(shard)

  async def _ensure_ctx(self, shard: Shard) -> _ShardContext:
    """Resolve the context for `shard`, loading it if absent. Resident
    contexts are an LRU bounded by XOT_MAX_RESIDENT_MODELS: switching models
    keeps the previous model's params/executables/request-states warm
    (VERDICT r2 weak #2 — the old engine dropped every in-flight request's
    KV cache on any model switch), and compute paths hold their own ctx
    reference so eviction can never corrupt a running computation (its
    params stay alive through the reference; only NEW requests miss)."""
    ctx = self._contexts.get(shard)
    if ctx is not None:
      self._contexts.move_to_end(shard)
      self._active = ctx
      return ctx
    async with self._shard_lock:
      ctx = self._contexts.get(shard)  # another task loaded it while we waited
      if ctx is not None:
        self._contexts.move_to_end(shard)
        self._active = ctx
        return ctx
      ctx = await self._load_shard(shard)
      self._contexts[shard] = ctx
      self._contexts.move_to_end(shard)
      self._active = ctx
      while len(self._contexts) > MAX_RESIDENT_MODELS:
        # Prefer evicting a context with no in-flight request states; only
        # when every candidate is busy does the oldest go (its requests then
        # fail loudly via RequestStateLost rather than silently restarting).
        victim = next(
          (s for s, c in self._contexts.items() if s != shard and not c.states),
          next(s for s in self._contexts if s != shard),
        )
        evicted = self._contexts.pop(victim)
        if DEBUG >= 1:
          print(f"Evicted model context {victim} "
                f"({len(evicted.states)} resident request states)")
      return ctx

  async def _load_shard(self, shard: Shard) -> _ShardContext:
    from xotorch_tpu.models.registry import adapter_path, split_adapter
    card = get_model_card(shard.model_id) or {}
    synthetic_cfg = card.get("synthetic_config")
    # Multi-LoRA serving: "base@name" ids address a registered adapter set
    # (XOT_ADAPTERS) served over the base model — a distinct context whose
    # BASE tensors are shared with any resident sibling (same HBM buffers).
    base_id, adapter_name = split_adapter(shard.model_id)
    adapter_ckpt = None
    if adapter_name is not None:
      ap = adapter_path(adapter_name)
      if ap is None:
        raise ValueError(
          f"adapter {adapter_name!r} is not registered — set XOT_ADAPTERS="
          f"'{adapter_name}=/path/to/adapter'")
      p = Path(ap)
      if not p.exists():
        raise FileNotFoundError(f"adapter {adapter_name!r} path does not exist: {ap}")
      adapter_ckpt = self._latest_shard_saves(p) if p.is_dir() else p
      if not adapter_ckpt:
        raise FileNotFoundError(f"no adapter checkpoint files under {ap}")

    def _donor_ctx():
      """A resident context over the same base + layer range whose params
      (quantized, mesh-placed) this adapter context can alias."""
      for s, c in self._contexts.items():
        if (split_adapter(s.model_id)[0] == base_id
            and (s.start_layer, s.end_layer) == (shard.start_layer, shard.end_layer)):
          return c
      return None

    donor = _donor_ctx() if adapter_name is not None else None
    if donor is not None:
      # Tokenizer/vision resolution needs the BASE model dir even when the
      # weights are aliased (a None here would silently hand the adapter
      # context a DummyTokenizer).
      model_dir = donor.model_dir
    elif synthetic_cfg is not None:
      model_dir = None
    else:
      model_dir = await self.shard_downloader.ensure_shard(shard, self.__class__.__name__)

    def _load():
      jax = self._jax()  # wires the persistent compile cache before the first compile
      import jax.numpy as jnp
      from xotorch_tpu.models.transformer import forward_shard, init_random_params
      from xotorch_tpu.models.weights import load_shard_params

      if donor is not None:
        # Alias the donor's base tensors — one resident base serves every
        # adapter; only the rank-r adapter leaves differ per context.
        # Quantization and mesh placement are already applied to them.
        cfg = donor.cfg
        params = {**donor.params,
                  "layers": {k: v for k, v in donor.params["layers"].items()
                             if not k.startswith("lora_")}}
        mesh = donor.mesh
      else:
        if synthetic_cfg is not None:
          cfg = config_from_hf_dict(synthetic_cfg)
          # Per-layer key folding makes this shard's weights bit-identical to
          # the same layer range of a full-model init — ring peers agree on
          # synthetic weights while allocating only shard-sized HBM.
          params = init_random_params(
            cfg, shard.get_layer_count(), shard.is_first_layer, shard.is_last_layer,
            jax.random.PRNGKey(0), dtype=self._dtype(), start_layer=shard.start_layer,
          )
        else:
          cfg = load_model_config(model_dir)
          params = load_shard_params(model_dir, cfg, shard, dtype=self._dtype())

        if self._quantize:
          from xotorch_tpu.models.quantize import quantize_params
          params = quantize_params(params, self._quantize, scale_dtype=self._dtype())

        mesh = self._serving_mesh(cfg, shard)
        if mesh is not None:
          # Place params per the Megatron partition rules; inside jit, XLA
          # derives the tp all-reduces (over ICI) from these placements —
          # computation follows data, no explicit collectives in model code.
          from xotorch_tpu.parallel.mesh import shard_params
          params = shard_params(params, mesh)
          if DEBUG >= 1:
            print(f"Serving shard over local tp={mesh.shape['tp']} mesh")

      if adapter_ckpt is not None:
        # Merge the registered adapter set over the (possibly aliased) base.
        from xotorch_tpu.train import lora as lora_mod
        params = lora_mod.load_lora_checkpoint(params, shard, adapter_ckpt)
        if DEBUG >= 1:
          print(f"LoRA adapter {adapter_name!r} attached over {base_id}")

      # LoRA fine-tuning (XOT_LORA_RANK / CLI --lora-rank): adapter tensors
      # join the stacked layers pytree (replicated under a tp mesh — they are
      # rank-r slivers), the base stays frozen via the masked optimizer.
      # A registered adapter checkpoint already carries its trained lora
      # leaves — attaching fresh random-A/zero-B ones here would overwrite
      # them and silently serve plain base outputs.
      lora_rank = knobs.get_int("XOT_LORA_RANK")
      if lora_rank > 0 and adapter_ckpt is None:
        from xotorch_tpu.train.lora import ATTN_SLOTS, MLP_SLOTS, add_lora_params
        targets = ATTN_SLOTS + (MLP_SLOTS if knobs.get_str("XOT_LORA_TARGETS", "") == "all" else ())
        params = add_lora_params(params, lora_rank, jax.random.PRNGKey(self._seed), targets)
        if DEBUG >= 1:
          print(f"LoRA adapters attached: rank={lora_rank}, targets={targets}")

      # The serving mesh rides into every executable as a STATIC kwarg (Mesh
      # is hashable — same pattern as the ring_mesh closure below): the
      # forward pins tp activation layouts (transformer._tp_constraint), the
      # attention Pallas kernels run per device over head-sliced operands
      # (parallel.mesh.per_shard_kernel) and the quantized matvec kernels
      # stand down (transformer._linear) — all from observing the mesh.
      fwd = partial(
        forward_shard, cfg=cfg, is_first=shard.is_first_layer, is_last=shard.is_last_layer,
        start_layer=shard.start_layer, tp_mesh=mesh,
      )
      forward_jit = jax.jit(fwd, donate_argnums=(2,))
      forward_flash_jit = jax.jit(partial(fwd, use_flash=True), donate_argnums=(2,))
      # Occupancy-aware Pallas decode executable (long-context serving); jit
      # construction is lazy so this costs nothing until first selected.
      forward_decode_flash_jit = jax.jit(partial(fwd, use_flash_decode=True), donate_argnums=(2,))
      # Cache-fill executables for the fused-sample path: hidden-only
      # (is_last=False) so non-final chunked-prefill segments never pay the
      # [T, vocab] unembedding nobody reads. jit construction is lazy —
      # these cost nothing unless a long prompt actually uses them.
      fill_jits = None
      if shard.is_last_layer:
        fill_fwd = partial(forward_shard, cfg=cfg, is_first=shard.is_first_layer, is_last=False,
                           start_layer=shard.start_layer, tp_mesh=mesh)
        fill_jits = {
          "base": jax.jit(fill_fwd, donate_argnums=(2,)),
          "flash": jax.jit(partial(fill_fwd, use_flash=True), donate_argnums=(2,)),
          "cached": jax.jit(partial(fill_fwd, use_flash_decode=True), donate_argnums=(2,)),
        }
        if (mesh is not None and "sp" in mesh.axis_names and mesh.shape["sp"] > 1
            and shard.is_first_layer
            and not (cfg.uses_sliding_window or cfg.attn_logit_softcap
                     or cfg.query_pre_attn_scalar)):
          # Sequence-parallel prefill-from-zero: the prompt's positions
          # shard over the sp axis and attention runs as RING attention
          # over ICI (ops/ring_attention; the serving twin of the training
          # sp axis). KV writes land in the replicated cache via the
          # GSPMD-inserted gathers. Windowed/soft-capped families are
          # excluded (ring attention implements neither). "ring" is the
          # hidden-only fill variant (fused-sample path); "ring_full" the
          # logits variant (_infer_sync's segment loop).
          fill_jits["ring"] = jax.jit(partial(fill_fwd, ring_mesh=mesh), donate_argnums=(2,))
          fill_jits["ring_full"] = jax.jit(partial(fwd, ring_mesh=mesh), donate_argnums=(2,))
      # Multimodal prefill injects merged (text+image) embeddings as hidden
      # state, bypassing the token-embedding lookup: an is_first=False jit.
      forward_hidden_jit = None
      forward_hidden_flash_jit = None
      vision = None
      if cfg.is_multimodal and shard.is_first_layer:
        hidden_fwd = partial(forward_shard, cfg=cfg, is_first=False, is_last=shard.is_last_layer,
                             start_layer=shard.start_layer, tp_mesh=mesh)
        forward_hidden_jit = jax.jit(hidden_fwd, donate_argnums=(2,))
        # Image prompts are the longest fresh-context prefills (576 patches
        # per image on llava-1.5) — they deserve the Pallas flash path too.
        forward_hidden_flash_jit = jax.jit(partial(hidden_fwd, use_flash=True), donate_argnums=(2,))
        if donor is not None:
          vision = donor.vision  # alias — LoRA never touches the tower
        elif model_dir is not None:
          from xotorch_tpu.models.weights import load_vision_tower
          vision = load_vision_tower(model_dir, cfg, dtype=self._dtype())
      return (cfg, params, mesh, forward_jit, forward_flash_jit, forward_decode_flash_jit,
              fill_jits, forward_hidden_jit, forward_hidden_flash_jit, vision)

    (cfg, params, mesh, forward_jit, forward_flash_jit, forward_decode_flash_jit,
     fill_jits, forward_hidden_jit, forward_hidden_flash_jit, vision) = await self._run(
       _load, oom_as_cache_exhausted=False)
    cache_len = min(self._configured_cache_len, cfg.max_seq_len)
    max_cache_len = max(cache_len, min(self._configured_max_cache_len, cfg.max_seq_len))
    ctx = _ShardContext(
      shard=shard, cfg=cfg, params=params, mesh=mesh,
      forward_jit=forward_jit, forward_flash_jit=forward_flash_jit,
      forward_decode_flash_jit=forward_decode_flash_jit, fill_jits=fill_jits,
      forward_hidden_jit=forward_hidden_jit, forward_hidden_flash_jit=forward_hidden_flash_jit,
      vision=vision, model_dir=model_dir, synthetic=synthetic_cfg is not None,
      cache_len=cache_len, max_cache_len=max_cache_len,
    )
    from xotorch_tpu.inference.jax_engine.costmodel import CostModel, dtype_width
    ctx.costmodel = CostModel(
      cfg=cfg, n_layers=shard.get_layer_count(),
      is_first=shard.is_first_layer, is_last=shard.is_last_layer,
      quantize=self._quantize, dtype_bytes=dtype_width(self._dtype_name),
      kv_quant=self._kv_quant, start_layer=shard.start_layer,
      # Mesh-aware roofline: per-device byte/FLOP math divides by the tp
      # width the params/caches were actually placed with.
      tp=(int(mesh.shape["tp"])
          if mesh is not None and "tp" in mesh.axis_names else 1),
    )
    if DEBUG >= 1:
      print(f"JAX engine ready for {shard} (dtype={self._dtype_name}, cache_len={cache_len})")
    return ctx

  def eos_token_ids_for(self, shard: Shard) -> Tuple[int, ...]:
    """EOS ids for a SPECIFIC resident model — the Node's per-request EOS
    check must not read whichever context happens to be active (two models
    in flight would check each other's EOS ids). Unresolved tokenizer falls
    back to the checkpoint config's eos list."""
    ctx = self._contexts.get(shard)
    if ctx is None:
      return ()
    eos = getattr(ctx.tokenizer, "eos_token_id", None) if ctx.tokenizer else None
    from_cfg = tuple(ctx.cfg.eos_token_ids or ())
    return tuple(e for e in ((eos,) if eos is not None else ()) + from_cfg)

  async def _ensure_tokenizer(self, ctx: Optional[_ShardContext] = None):
    ctx = ctx or self._active
    if ctx.tokenizer is not None:
      return ctx.tokenizer
    if ctx.synthetic or ctx.shard.model_id == "dummy":
      ctx.tokenizer = DummyTokenizer()
      if ctx.cfg.eos_token_ids:
        ctx.tokenizer.eos_token_id = ctx.cfg.eos_token_ids[0]
      return ctx.tokenizer
    try:
      ctx.tokenizer = await resolve_tokenizer(ctx.model_dir)
    except Exception as e:
      if DEBUG >= 1:
        print(f"Tokenizer resolution failed for {ctx.model_dir}: {e!r}; using dummy tokenizer")
      ctx.tokenizer = DummyTokenizer()
      if ctx.cfg.eos_token_ids:
        ctx.tokenizer.eos_token_id = ctx.cfg.eos_token_ids[0]
    return ctx.tokenizer

  # ------------------------------------------------------------ checkpoints

  def _checkpoint_file_for(self, path: Path, shard: Shard) -> Optional[Path]:
    """Resolve a concrete safetensors file for this shard: a file path is
    taken as-is; a directory prefers this shard's own `{start}-{end}-*`
    saves (latest iteration), falling back to any safetensors present."""
    if path.is_file():
      return path
    if not path.is_dir():
      return None
    sid = f"{shard.start_layer}-{shard.end_layer}"
    mine = sorted(
      (p for p in path.glob(f"{sid}-*.safetensors") if not p.stem.endswith("-opt")),
      key=lambda p: int(p.stem.rsplit("-", 1)[-1]) if p.stem.rsplit("-", 1)[-1].isdigit() else -1,
    )
    if mine:
      return mine[-1]
    # Never fall back to ANOTHER shard's save (a `{start}-{end}-{iter}` file
    # for a different layer range would load garbage or KeyError) or to an
    # optimizer-moments file ('*-opt.safetensors', train/optstate.py — its
    # opt.{i} keys are not weights); only non-shard-patterned weight files
    # qualify as a generic fallback.
    rest = sorted(p for p in path.glob("*.safetensors")
                  if not SHARD_SAVE_RE.fullmatch(p.stem) and not p.stem.endswith("-opt"))
    return rest[0] if rest else None

  @staticmethod
  def _latest_shard_saves(path: Path) -> list:
    """All `{start}-{end}-{iter}` saves in a directory, latest iteration per
    layer range — the file set a re-partitioned ring merges adapters from.
    Delegates to train.lora so the API's listing validation resolves
    directories with the SAME rule the load path uses."""
    from xotorch_tpu.train.lora import adapter_checkpoint_files
    return adapter_checkpoint_files(path)

  async def load_checkpoint(self, shard: Shard, path: str) -> None:
    ctx = await self._ensure_ctx(shard)

    # The moments file a resume may restore — set ONLY by the branches that
    # load a trained save as-is (single adapter file, explicit shard save):
    # a base reload or a multi-piece re-partition merge lands at a different
    # parameter point than any one save's moments.
    resume = {"opt": None}

    def _load():
      import jax
      from xotorch_tpu.train import lora as lora_mod
      from xotorch_tpu.models.weights import load_shard_params
      p = Path(path)
      ckpt = self._checkpoint_file_for(p, ctx.shard)
      if ckpt is not None and lora_mod.is_lora_checkpoint(ckpt):
        # Adapter-only checkpoint: merge into the (already loaded) base.
        resume["opt"] = self._opt_state_file(ckpt, ctx.shard)
        return lora_mod.load_lora_checkpoint(ctx.params, ctx.shard, ckpt)
      if p.is_dir():
        # Re-partitioned resume: no save matches this exact layer range, but
        # the union of other shards' ADAPTER saves may cover it (absolute
        # layer indexing exists for exactly this; lora.py naming note).
        # Checked regardless of what _checkpoint_file_for fell back to — a
        # base model.safetensors sitting in the same dir must not shadow the
        # trained adapter set.
        pieces = self._latest_shard_saves(p)
        if pieces and all(lora_mod.is_lora_checkpoint(f) for f in pieces):
          return lora_mod.load_lora_checkpoint(ctx.params, ctx.shard, pieces)
      model_dir = p if p.is_dir() else p.parent
      # Priority: an explicitly named file, or a shard-patterned save, beats
      # an HF index sitting in the same directory — the trained checkpoint
      # must never lose to the pristine base weights next to it.
      explicit = ckpt is not None and (p.is_file() or SHARD_SAVE_RE.fullmatch(ckpt.stem))
      if explicit:
        params = load_shard_params(model_dir, ctx.cfg, ctx.shard, dtype=self._dtype(),
                                   checkpoint_file=ckpt)
        resume["opt"] = self._opt_state_file(ckpt, ctx.shard)
      elif (model_dir / "model.safetensors.index.json").exists() or (model_dir / "model.safetensors").exists():
        params = load_shard_params(model_dir, ctx.cfg, ctx.shard, dtype=self._dtype())
      elif ckpt is not None:
        params = load_shard_params(model_dir, ctx.cfg, ctx.shard, dtype=self._dtype(),
                                   checkpoint_file=ckpt)
      else:
        raise FileNotFoundError(f"no checkpoint for shard {ctx.shard} at {path}")
      if self._quantize:
        # A quantized engine stays quantized across full-weight reloads
        # (checkpoints are stored in compute dtype — save_checkpoint
        # dequantizes — so requantize on the way back in).
        from xotorch_tpu.models.quantize import quantize_params
        params = quantize_params(params, self._quantize, scale_dtype=self._dtype())
      # An engine running with LoRA must stay a LoRA engine after a full/base
      # checkpoint load: re-attach FRESH adapters (same rank/targets as the
      # current ones) so has_lora stays true and the optimizer keeps the base
      # frozen — otherwise a base reload silently converts --lora-rank
      # training into a full fine-tune.
      lora_a_keys = sorted(k for k in ctx.params["layers"] if k.startswith("lora_") and k.endswith("_a"))
      if lora_a_keys:
        rank = int(ctx.params["layers"][lora_a_keys[0]].shape[-1])
        targets = tuple(k[len("lora_"):-len("_a")] for k in lora_a_keys)
        params = lora_mod.add_lora_params(params, rank, jax.random.PRNGKey(self._seed), targets)
        # FRESH random adapters: any saved moments belong to a different
        # parameter point — shapes would match, values would mislead.
        resume["opt"] = None
      return params

    def _load_and_restore():
      # Params swap, optimizer reset, AND moments restore in ONE executor
      # task: a second await window between them would let an interleaved
      # train_example advance the fresh params before the checkpoint's
      # moments land — params one step past the checkpoint with moments AT
      # it. Every pos/params/opt mutation is serialized on this executor.
      ctx.params = _load()
      ctx.opt_state = None  # optimizer state is invalid for reloaded weights
      self._clear_prefix_cache(ctx)  # snapshots were computed under the old weights

      # Training resume: restore the moments saved WITH the checkpoint that
      # was just loaded (the file name ties them — rolling back to
      # iteration 2 never picks up iteration 4's moments). Any failure
      # keeps the cold state: a truncated/mismatched moments file must
      # never block loading perfectly valid weights.
      opt_file = resume["opt"]
      if (opt_file is not None and opt_file.exists()
          and knobs.get_bool("XOT_SAVE_OPT_STATE")):
        from xotorch_tpu.train.optstate import load_opt_state
        self._ensure_optimizer(ctx)
        try:
          ctx.opt_state = load_opt_state(ctx.opt_state, opt_file)
        except Exception as e:
          print(f"optimizer state not restored ({e!r}); training resumes cold")
          ctx.opt_state = None

    await self._run(_load_and_restore, oom_as_cache_exhausted=False)

  async def save_checkpoint(self, shard: Shard, path: str) -> None:
    ctx = await self._ensure_ctx(shard)

    def _save():
      from xotorch_tpu.train import lora as lora_mod
      if lora_mod.has_lora(ctx.params):
        # Parameter-efficient save: adapters only (MBs, not the base model).
        lora_mod.save_lora_checkpoint(ctx.params, ctx.shard, Path(path))
        return
      from xotorch_tpu.models.quantize import dequantize_params, is_quantized
      from xotorch_tpu.models.weights import save_shard_params
      params = ctx.params
      if is_quantized(params):
        # Checkpoints stay HF-layout compute-dtype safetensors — loadable by
        # stock tooling, never a private int8 format.
        params = dequantize_params(params, self._dtype())
      save_shard_params(params, ctx.cfg, ctx.shard, Path(path))

    await self._run(_save, oom_as_cache_exhausted=False)

    # Optimizer moments ride alongside (training resume without them
    # restarts AdamW cold — the first steps after every restart regress).
    # XOT_SAVE_OPT_STATE=0 opts out for inference-only checkpoints — and
    # then any stale paired moments file is REMOVED: overwriting the
    # weights while leaving an older save's moments next to them would
    # pair moments from a different parameter point on the next resume.
    opt_file = self._opt_state_file(Path(path), ctx.shard)

    def _save_opt():
      if ctx.opt_state is not None and knobs.get_bool("XOT_SAVE_OPT_STATE"):
        from xotorch_tpu.train.optstate import save_opt_state
        save_opt_state(ctx.opt_state, opt_file)
      elif opt_file.exists():
        opt_file.unlink()

    await self._run(_save_opt, oom_as_cache_exhausted=False)

  @staticmethod
  def _opt_state_file(path: Path, shard: Shard) -> Path:
    """Moments ride NEXT TO the specific checkpoint they belong to
    ('0-3-4.safetensors' -> '0-3-4-opt.safetensors'): a rollback to an
    earlier save must never restore a later save's moments. Checkpoint
    paths are concrete .safetensors files on both the save and load sides
    (save_file requires one; load resolves via _checkpoint_file_for)."""
    if path.suffix != ".safetensors":
      raise ValueError(f"checkpoint path must be a .safetensors file, got {path}")
    return path.with_name(path.stem + "-opt.safetensors")

  # -------------------------------------------------------------- training

  def _ensure_optimizer(self, ctx: _ShardContext):
    """Optimizer state is tied to the context's param tree; _load_shard and
    load_checkpoint reset it (stale Adam moments must never be applied to a
    different tree)."""
    if ctx.optimizer is None or ctx.opt_state is None:
      import optax
      from xotorch_tpu.train.lora import has_lora, masked_optimizer
      from xotorch_tpu.train.step import trainable_subtree
      lr = knobs.get_float("XOT_LR")
      base = optax.adamw(lr)
      # With adapters attached, the base model is FROZEN: optax.masked zeroes
      # non-adapter updates and never allocates Adam moments for them.
      # Optimizer state lives over trainable_subtree(params) (train/step.py)
      # — an int8-quantized base is invisible to the optimizer entirely.
      ctx.optimizer = masked_optimizer(base, ctx.params) if has_lora(ctx.params) else base
      ctx.opt_state = ctx.optimizer.init(trainable_subtree(ctx.params))
    return ctx.optimizer

  async def train_example(self, request_id: str, shard: Shard, example: np.ndarray, target: np.ndarray,
                          lengths: np.ndarray, forward_fn=None):
    """Pipelined training over the ring: forward my slice (keeping the vjp
    residuals), chain downstream through forward_fn, pull the gradient back
    through the saved vjp, apply AdamW locally, hand the input-gradient
    upstream. Completes node.py:299-345's missing engine leaf. Every device
    op (including host<->device transfers) runs on the single executor."""
    ctx = await self._ensure_ctx(shard)
    if not shard.is_last_layer and forward_fn is None:
      raise ValueError("Non-last shard requires forward_fn to chain the ring")
    from xotorch_tpu.models.quantize import is_quantized
    from xotorch_tpu.train.lora import has_lora
    if is_quantized(ctx.params) and not has_lora(ctx.params):
      raise ValueError(
        "Full-parameter training on an int8-quantized base is not supported; "
        "attach adapters (--lora-rank / XOT_LORA_RANK) for QLoRA fine-tuning"
      )
    optimizer = self._ensure_optimizer(ctx)

    if shard.is_last_layer:
      def _last():
        import jax.numpy as jnp
        import optax
        from xotorch_tpu.train.step import merge_trees, shard_loss_and_grads, split_float
        x = jnp.asarray(example.astype(np.int32) if example.ndim == 2 else example)
        tgt = jnp.asarray(np.asarray(target).astype(np.int32))
        lens = jnp.asarray(np.asarray(lengths).reshape(-1).astype(np.int32))
        loss, x_grad, param_grads = shard_loss_and_grads(
          ctx.params, ctx.cfg, x, tgt, lens, shard.is_first_layer, True,
          start_layer=shard.start_layer,
        )
        # Updates apply to the float subtree only; a quantized base rides
        # through untouched (never copied, never zero-filled).
        fl, nf = split_float(ctx.params)
        updates, ctx.opt_state = optimizer.update(param_grads, ctx.opt_state, fl)
        ctx.params = merge_trees(optax.apply_updates(fl, updates), nf)
        self._clear_prefix_cache(ctx)  # prefill snapshots are stale under new weights
        return float(loss), np.asarray(x_grad)
      return await self._run(_last, oom_as_cache_exhausted=False)

    # Mid/first shard: one forward with saved residuals, then backward later.
    def _fwd_vjp():
      import jax
      import jax.numpy as jnp
      from xotorch_tpu.models.transformer import forward_shard, init_kv_cache
      from xotorch_tpu.train.step import merge_trees, split_float
      x = jnp.asarray(example.astype(np.int32) if example.ndim == 2 else example)
      B, T = x.shape[0], x.shape[1]
      cache = init_kv_cache(ctx.cfg, shard.get_layer_count(), B, T, jnp.float32)
      # vjp over the float subtree only: an int8-quantized base is frozen and
      # non-differentiable (train/step.split_float).
      fl, nf = split_float(ctx.params)

      def fwd(p_fl, xin):
        return forward_shard(merge_trees(p_fl, nf), xin, cache, jnp.int32(0), ctx.cfg,
                             shard.is_first_layer, False, start_layer=shard.start_layer)[0]

      if shard.is_first_layer:
        out, vjp_fn = jax.vjp(lambda p: fwd(p, x), fl)
      else:
        out, vjp_fn = jax.vjp(fwd, fl, x)
      return np.asarray(out), vjp_fn, out.dtype

    activations, vjp_fn, out_dtype = await self._run(_fwd_vjp, oom_as_cache_exhausted=False)
    loss, down_grad = await forward_fn(activations, np.asarray(target), np.asarray(lengths), True)
    if down_grad is None:
      raise RuntimeError(f"Downstream shard returned no gradient for {request_id}")

    def _bwd_apply():
      import jax.numpy as jnp
      import optax
      from xotorch_tpu.train.step import merge_trees, split_float
      down = jnp.asarray(np.asarray(down_grad)).astype(out_dtype)
      if shard.is_first_layer:
        (float_grads,) = vjp_fn(down)
        x_grad = np.zeros((1,), np.float32)  # token inputs are not differentiable
      else:
        float_grads, xg = vjp_fn(down)
        x_grad = np.asarray(xg)
      # Float-subtree update: the frozen int8 base is never copied.
      fl, nf = split_float(ctx.params)
      updates, ctx.opt_state = optimizer.update(float_grads, ctx.opt_state, fl)
      ctx.params = merge_trees(optax.apply_updates(fl, updates), nf)
      self._clear_prefix_cache(ctx)  # prefill snapshots are stale under new weights
      return x_grad

    x_grad = await self._run(_bwd_apply, oom_as_cache_exhausted=False)
    return float(loss), x_grad

  async def evaluate_example(self, request_id: str, shard: Shard, example: np.ndarray, target: np.ndarray,
                             lengths: np.ndarray, forward_fn=None) -> float:
    ctx = await self._ensure_ctx(shard)
    if not shard.is_last_layer and forward_fn is None:
      raise ValueError("Non-last shard requires forward_fn to chain the ring")

    def _fwd():
      import jax.numpy as jnp
      from xotorch_tpu.models.transformer import forward_shard, init_kv_cache
      x = jnp.asarray(example.astype(np.int32) if example.ndim == 2 else example)
      B, T = x.shape[0], x.shape[1]
      cache = init_kv_cache(ctx.cfg, shard.get_layer_count(), B, T, jnp.float32)
      out = forward_shard(ctx.params, x, cache, jnp.int32(0), ctx.cfg,
                          shard.is_first_layer, shard.is_last_layer,
                          start_layer=shard.start_layer)[0]
      if shard.is_last_layer:
        from xotorch_tpu.train.step import masked_ce_loss
        tgt = jnp.asarray(np.asarray(target).astype(np.int32))
        lens = jnp.asarray(np.asarray(lengths).reshape(-1).astype(np.int32))
        return float(masked_ce_loss(out, tgt, lens))
      return np.asarray(out)

    out = await self._run(_fwd, oom_as_cache_exhausted=False)
    if shard.is_last_layer:
      return out
    loss, _ = await forward_fn(out, np.asarray(target), np.asarray(lengths), False)
    return loss

  async def clear_request(self, request_id: str) -> None:
    # Runs ON THE EXECUTOR: discarding a batch spec rolls back OTHER live
    # requests' positions, which must never race a dispatch that is reading
    # them on the executor thread (every pos mutation is serialized there).
    def _clear():
      self._spec_next.pop(request_id, None)
      self._ring_spec.pop(request_id, None)
      for ctx in self._contexts.values():
        # A member finished: the batch's membership changes, so the
        # speculative batch can never resolve — roll the others back.
        self._discard_batch_spec_for(ctx, request_id)
        for rid in (request_id, self._draft_rid(request_id)):
          st = ctx.states.pop(rid, None)
          if st is not None:
            # Return the request's page references to the pool; pages shared
            # with the prefix cache or other requests survive via their refs.
            self._release_state_pages(ctx, st)

    await self._run(_clear, oom_as_cache_exhausted=False)
