"""Fused multi-token decode: forward + sampling under one `lax.scan`.

The reference's decode loop pays a full host round-trip per token — logits
come back to python, sampling runs there, and the next token is re-dispatched
(sharded_inference_engine.py:208-228 + node.py:109-147). That cost is
structural on GPU+gRPC; on TPU it is pure overhead whenever a single
partition owns the whole model (the common single-host case and the bench
config). Here the whole decode chunk is ONE XLA computation: `lax.scan` over
K steps, each step = forward_shard (cache-resident) + on-device Gumbel-max
sampling, so the host sees K tokens per dispatch instead of per-token
latency. EOS is checked between chunks on the host; tokens past EOS inside a
chunk are discarded by the caller (bounded overshoot, amortised to nothing).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from xotorch_tpu.models.config import ModelConfig
from xotorch_tpu.models.transformer import forward_shard, unembed
from xotorch_tpu.ops.sampling import sample_logits, sample_logits_logprobs


@partial(
  jax.jit,
  static_argnames=("cfg", "is_first", "top_k", "top_p", "use_flash", "use_flash_decode",
                   "start_layer", "top_lp", "moe_routed", "paged_kernel", "ragged_prefill",
                   "tp_mesh"),
  donate_argnames=("cache",),
)
def forward_sample(
  params,
  x: jnp.ndarray,  # [B, T] int32 tokens (is_first) or [B, T, H] hidden
  cache,
  start_pos: jnp.ndarray,  # scalar int32
  last_index: jnp.ndarray,  # scalar int32 — index of the LAST REAL position in x (pre-padding)
  key: jax.Array,
  cfg: ModelConfig,
  is_first: bool,
  temp: float,
  top_k: int,
  top_p: float = 0.0,
  use_flash: bool = False,
  use_flash_decode: bool = False,
  start_layer: int = 0,  # absolute first-layer index (sliding-window families)
  bias: jnp.ndarray = None,  # [B, V] OpenAI logit_bias (presence static)
  counts: jnp.ndarray = None,  # [B, V] token counts for penalties
  presence: float = 0.0,
  frequency: float = 0.0,
  top_lp: int = -1,  # static: -1 = no logprob reporting; >=0 = report
  moe_routed: bool = True,  # static: False when experts shard over 'ep'
  min_p=None,  # min-p cutoff (traced; None = off) — ops/sampling
  page_table: jnp.ndarray = None,  # [1, max_pages]: paged-NATIVE prefill — `cache` is the arena
  paged_kernel: bool = False,
  ragged_prefill: bool = True,  # static: kernel prefill reads pages natively
  tp_mesh=None,  # static Mesh: tensor-parallel activation constraints
):
  """Last-shard forward + ON-DEVICE sampling in one dispatch: returns
  ([B] int32 sampled token, updated cache) — with `top_lp >= 0`, instead
  ((tok, lp, top_ids, top_lps), cache) per ops/sampling.sample_logits_logprobs.
  With `page_table`, `cache` is the shared page ARENA and the segment's K/V
  scatter straight into pool pages (transformer.forward_shard paged prefill);
  the donated/returned cache is then the updated arena.

  Two wins over infer_tensor-then-sample (VERDICT r1 weak #3):
  - the host never sees the [B, T, vocab] fp32 logits (~0.5 MB/token for a
    128 k vocab) — only the sampled token crosses to the host;
  - the unembedding matmul runs on ONE position (`last_index` — the real
    last token, not the bucket-padding tail) instead of the whole segment,
    which for a 4 k prefill bucket on a 128 k vocab skips ~1 TFLOP of
    logits nobody reads.
  """
  h, cache = forward_shard(params, x, cache, start_pos, cfg=cfg, is_first=is_first,
                           is_last=False, use_flash=use_flash, use_flash_decode=use_flash_decode,
                           start_layer=start_layer, moe_routed=moe_routed,
                           page_table=page_table, paged_kernel=paged_kernel,
                           ragged_prefill=ragged_prefill, tp_mesh=tp_mesh)
  h_last = jax.lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)  # [B, 1, H]
  logits = unembed(params, h_last, cfg)
  if top_lp >= 0:
    out = sample_logits_logprobs(logits[:, -1, :], key, temp=temp, top_k=top_k, top_p=top_p,
                                 bias=bias, counts=counts, presence=presence,
                                 frequency=frequency, top_lp=top_lp, min_p=min_p)
    return out, cache
  tok = sample_logits(logits[:, -1, :], key, temp=temp, top_k=top_k, top_p=top_p,
                      bias=bias, counts=counts, presence=presence, frequency=frequency,
                      min_p=min_p)
  return tok, cache


@partial(
  jax.jit,
  static_argnames=("cfg", "num_tokens", "top_k", "top_p", "use_flash_decode", "top_lp",
                   "moe_routed", "tp_mesh"),
  donate_argnames=("cache",),
)
def decode_chunk(
  params,
  tok: jnp.ndarray,  # [B, 1] int32 — last sampled token
  cache: Dict[str, jnp.ndarray],
  start_pos: jnp.ndarray,  # scalar int32 — absolute position of `tok`
  key: jax.Array,
  cfg: ModelConfig,
  num_tokens: int,
  temp: float,
  top_k: int,
  top_p: float = 0.0,
  use_flash_decode: bool = False,
  bias: jnp.ndarray = None,  # [B, V] OpenAI logit_bias
  counts: jnp.ndarray = None,  # [B, V] token counts; updated INSIDE the scan
  presence: float = 0.0,
  frequency: float = 0.0,
  top_lp: int = -1,  # static: -1 = no logprob reporting; >=0 = report
  moe_routed: bool = True,  # static: False when experts shard over 'ep'
  min_p=None,  # min-p cutoff (traced; None = off) — ops/sampling
  tp_mesh=None,  # static Mesh: tensor-parallel activation constraints
):
  """Generate `num_tokens` tokens in one device program.

  Requires the shard to span the whole model (is_first and is_last). Returns
  ([B, num_tokens] int32 sampled tokens, updated cache) — plus the updated
  counts when `counts` is passed (penalty requests), plus a logprob triple
  (lp [B, T], top_ids [B, T, top_lp], top_lps [B, T, top_lp]) as the final
  element when `top_lp >= 0` (the scan stacks per-step reports). The
  incoming `tok` is consumed (its forward step is the first scan iteration);
  the returned tokens start at position start_pos + 1. `temp` is traced — a
  scalar or a per-ROW [B] array (ops/sampling.sample_logits), so batched
  rows may carry different request temperatures in one dispatch. Counts ride
  the scan carry: token i+1 inside the chunk sees token i's penalty — the
  within-chunk feedback a host-side implementation would lose.
  """
  track_counts = counts is not None
  want_lp = top_lp >= 0

  def step(carry, _):
    tok, cache, pos, key, counts = carry
    logits, cache = forward_shard(params, tok, cache, pos, cfg=cfg, is_first=True, is_last=True,
                                  use_flash_decode=use_flash_decode, moe_routed=moe_routed,
                                  tp_mesh=tp_mesh)
    key, sub = jax.random.split(key)
    # counts=None (not the 0-d carry placeholder) when penalties are off:
    # the None/array split is what keeps the [B, V] penalty subtractions out
    # of the plain fused-decode executable entirely.
    step_counts = counts if track_counts else None
    if want_lp:
      nxt, lp, top_ids, top_lps = sample_logits_logprobs(
        logits[:, -1, :], sub, temp=temp, top_k=top_k, top_p=top_p,
        bias=bias, counts=step_counts, presence=presence, frequency=frequency,
        top_lp=top_lp, min_p=min_p)
      ys = (nxt, lp, top_ids, top_lps)
    else:
      nxt = sample_logits(logits[:, -1, :], sub, temp=temp, top_k=top_k, top_p=top_p,
                          bias=bias, counts=step_counts,
                          presence=presence, frequency=frequency, min_p=min_p)
      ys = nxt
    if track_counts:
      rows = jnp.arange(counts.shape[0], dtype=jnp.int32)
      counts = counts.at[rows, nxt].add(1)
    return (nxt[:, None], cache, pos + 1, key, counts), ys

  init = (tok.astype(jnp.int32), cache, start_pos.astype(jnp.int32), key,
          counts if track_counts else jnp.zeros((), jnp.int32))
  (_, cache, _, _, counts_out), ys = jax.lax.scan(step, init, None, length=num_tokens)
  if want_lp:
    toks, lp, top_ids, top_lps = ys
    aux = (lp.T, top_ids.transpose(1, 0, 2), top_lps.transpose(1, 0, 2))
  else:
    toks, aux = ys, None
  out = [toks.T, cache]  # [B, num_tokens]
  if track_counts:
    out.append(counts_out)
  if want_lp:
    out.append(aux)
  return tuple(out)


def scan_groups(n_segs: int):
  """Power-of-two decomposition of a segment count: yields (offset, size)
  groups, largest first (7 -> (0, 4), (4, 2), (6, 1)). Shared by
  engine._scan_prefill and the bench's long stage so both dispatch the SAME
  prefill_scan executables — the executable count stays logarithmic in the
  max segment count and the bench measures exactly the serving pattern."""
  off = 0
  while n_segs > 0:
    g = 1 << (n_segs.bit_length() - 1)
    yield off, g
    off += g
    n_segs -= g


@partial(
  jax.jit,
  static_argnames=("cfg", "n_segs", "is_first", "start_layer", "moe_routed", "paged_kernel",
                   "ragged_prefill", "tp_mesh"),
  donate_argnames=("cache",),
)
def prefill_scan(
  params,
  x: jnp.ndarray,  # [B, T] int32 tokens (is_first) or [B, T, H] hidden; T = n_segs * seg
  cache: Dict[str, jnp.ndarray],
  start_pos: jnp.ndarray,  # scalar int32 — absolute position of x[:, 0]
  cfg: ModelConfig,
  n_segs: int,
  is_first: bool = True,
  start_layer: int = 0,
  moe_routed: bool = True,
  page_table: jnp.ndarray = None,  # [1, max_pages]: paged-NATIVE prefill — `cache` is the arena
  paged_kernel: bool = False,
  ragged_prefill: bool = True,  # static: kernel prefill reads pages natively
  tp_mesh=None,  # static Mesh: tensor-parallel activation constraints
):
  """Chunked long-prompt prefill as ONE device program: `lax.scan` over the
  prompt's fixed-size segments, each step = forward_shard over the
  occupancy-aware cached-attention kernel (ops/flash_decode.py — in-segment
  causality is by absolute position, so the same kernel serves the from-zero
  segment and every later one).

  The host-side segment loop (engine._infer_sync, and round 3's bench long
  stage) pays one dispatch + one H2D transfer per segment (16 k prefill = 8
  of each). Here the prompt crosses to the device once and the segment
  loop runs entirely device-side — XLA overlaps the next segment's compute
  with the cache writes of the last, and the dispatch bill is 1 regardless
  of T. No unembedding happens anywhere in the loop: callers take the
  returned hidden states (the decode/sample executable unembeds its one
  real position), so the [T, vocab] logits the reference materialises per
  segment (torch sharded_inference_engine.py:208-228) are never computed.

  Returns ([B, T, H] hidden states of the LAST transformer layer for every
  position, updated cache). The hidden stack costs T*H*2 bytes of HBM
  (≈67 MB at 16 k / H=2048) — noise next to the attention reads — and keeps
  the output shape identical to the per-segment path, so ring forwarding
  (non-last shards hand hidden states to the next partition) and the
  fused-sample tail both consume it unchanged.

  With `page_table`, `cache` is the shared page ARENA: every segment's K/V
  scatter straight into pool pages (paged-NATIVE prefill — the table must
  already cover start_pos + T), and the donated/returned cache is the
  updated arena. The table is closed over by the scan body (no L axis).
  """
  B, T = x.shape[0], x.shape[1]
  seg = T // n_segs
  xs = jnp.moveaxis(x.reshape((B, n_segs, seg) + x.shape[2:]), 1, 0)

  def step(carry, x_seg):
    cache, pos = carry
    h, cache = forward_shard(params, x_seg, cache, pos, cfg=cfg, is_first=is_first,
                             is_last=False, use_flash_decode=True,
                             start_layer=start_layer, moe_routed=moe_routed,
                             page_table=page_table, paged_kernel=paged_kernel,
                             ragged_prefill=ragged_prefill, tp_mesh=tp_mesh)
    return (cache, pos + seg), h

  (cache, _), hs = jax.lax.scan(step, (cache, start_pos.astype(jnp.int32)), xs)
  return jnp.moveaxis(hs, 0, 1).reshape(B, T, -1), cache


@partial(
  jax.jit,
  static_argnames=("cfg", "num_tokens", "top_k", "top_p", "use_flash_decode", "start_layers",
                   "moe_routed", "tp_mesh"),
  donate_argnames=("caches",),
)
def decode_chunk_ring(
  params_segs,  # tuple of per-partition param pytrees, ring order (first..last)
  tok: jnp.ndarray,  # [B, 1] int32 — last sampled token
  caches,  # tuple of per-partition cache dicts (each [L_i, B, S, Hkv, D])
  start_pos: jnp.ndarray,  # scalar int32 — absolute position of `tok`
  key: jax.Array,
  cfg: ModelConfig,
  num_tokens: int,
  temp,
  top_k: int,
  top_p: float = 0.0,
  use_flash_decode: bool = False,
  start_layers: Tuple[int, ...] = (0,),
  moe_routed: bool = True,
  tp_mesh=None,  # static Mesh the co-located partitions all serve over
):
  """Fused multi-PARTITION decode: the whole ring's layer stacks run inside
  ONE device program, K tokens per dispatch.

  The reference's multi-partition decode is per-token by construction — one
  hop per partition per token (node.py:109-147), each a host round-trip even
  when every partition lives on the same chip. When the partitions are
  co-located (one process, one device — the engine's ring-fusion path
  detects this), nothing about pipeline partitioning requires that: the
  per-token step is just segment_0(embed+layers) -> segment_1(layers) -> ...
  -> unembed+sample, all device-resident. Scanning that composite step K
  times gives the multi-partition ring the SAME dispatch amortisation as the
  single-shard fused path.

  Each partition keeps its own params pytree and its own KV cache — HBM
  layout is identical to the per-token ring, so entering/leaving the fused
  path needs no cache migration; positions advance in lockstep.
  `start_layers` (static) carries each segment's absolute first-layer index
  for sliding-window families. Returns ([B, num_tokens] int32 tokens, tuple
  of updated caches in ring order).
  """
  def step(carry, _):
    tok, caches, pos, key = carry
    h = tok
    new_caches = []
    for i, params in enumerate(params_segs):
      h, c = forward_shard(params, h, caches[i], pos, cfg=cfg, is_first=(i == 0),
                           is_last=False, use_flash_decode=use_flash_decode,
                           start_layer=start_layers[i], moe_routed=moe_routed,
                           tp_mesh=tp_mesh)
      new_caches.append(c)
    logits = unembed(params_segs[-1], h, cfg)
    key, sub = jax.random.split(key)
    nxt = sample_logits(logits[:, -1, :], sub, temp=temp, top_k=top_k, top_p=top_p)
    return (nxt[:, None], tuple(new_caches), pos + 1, key), nxt

  init = (tok.astype(jnp.int32), tuple(caches), start_pos.astype(jnp.int32), key)
  (_, caches, _, _), toks = jax.lax.scan(step, init, None, length=num_tokens)
  return toks.T, caches


@partial(
  jax.jit,
  static_argnames=("cfg", "use_flash_decode", "start_layers", "moe_routed", "tp_mesh"),
  donate_argnames=("caches",),
)
def forward_argmax_ring(
  params_segs,  # tuple of per-partition param pytrees, ring order
  x: jnp.ndarray,  # [1, T_pad] int32 — [prev_token] + draft, zero-padded
  caches,  # tuple of per-partition cache dicts
  start_pos: jnp.ndarray,  # scalar int32
  cfg: ModelConfig,
  use_flash_decode: bool = False,
  start_layers: Tuple[int, ...] = (0,),
  moe_routed: bool = True,
  tp_mesh=None,  # static Mesh the co-located partitions all serve over
):
  """One forward through EVERY co-located partition + per-position greedy
  argmax: the ring twin of the draft-verification forward (engine
  verify_draft) — a whole prompt-lookup draft verifies in ONE dispatch even
  when the model spans partitions. Returns ([1, T_pad] int32 argmax,
  updated caches); positions past the true draft length are padding (their
  cache writes sit past the validity mask and get overwritten)."""
  h = x
  new_caches = []
  for i, params in enumerate(params_segs):
    h, c = forward_shard(params, h, caches[i], start_pos, cfg=cfg, is_first=(i == 0),
                         is_last=False, use_flash_decode=use_flash_decode,
                         start_layer=start_layers[i], moe_routed=moe_routed,
                         tp_mesh=tp_mesh)
    new_caches.append(c)
  logits = unembed(params_segs[-1], h, cfg)
  return jnp.argmax(logits, axis=-1).astype(jnp.int32), tuple(new_caches)


@partial(
  jax.jit,
  static_argnames=("cfg", "use_kernel", "moe_routed", "ragged", "start_layer", "tp_mesh"),
  donate_argnames=("arena",),
)
def forward_argmax_paged(
  params,
  x: jnp.ndarray,  # [1, T_pad] int32 — [prev_token] + draft, zero-padded to a po2 bucket
  arena: Dict[str, jnp.ndarray],  # shared page arena: [L, P, page, Hkv, D] leaves
  page_table: jnp.ndarray,  # [1, max_pages] int32 physical page ids (0-padded)
  start_pos: jnp.ndarray,  # scalar int32 — the request's committed position
  cfg: ModelConfig,
  use_kernel: bool = False,  # static: Pallas ragged kernel vs XLA gather
  moe_routed: bool = True,
  ragged: bool = True,  # static: kernel path reads pages natively (no gather)
  start_layer: int = 0,
  tp_mesh=None,  # static Mesh: tensor-parallel activation constraints
):
  """Draft verification over the PAGED arena: one forward of
  [prev_token] + draft as a T>1 ragged query through the request's existing
  page table + per-position greedy argmax — the paged twin of the
  contiguous verify forward (engine._verify_draft_sync) and of
  forward_argmax_ring. Draft K/V scatter straight into the request's pages
  (the engine pre-extends the table to cover the padded bucket); rejected
  positions' slots sit past the rolled-back pos, invisible to the validity
  mask, and the rejected tail's FRESH pages decref back to the pool host-
  side. T_pad is the caller's po2 bucket, so the executable count is
  logarithmic in the draft depth, never one per K. Returns
  ([1, T_pad] int32 argmax, updated arena)."""
  h, arena = forward_shard(params, x, arena, start_pos, cfg=cfg, is_first=True,
                           is_last=False, moe_routed=moe_routed,
                           start_layer=start_layer,
                           page_table=page_table, paged_kernel=use_kernel,
                           ragged_prefill=ragged, tp_mesh=tp_mesh)
  logits = unembed(params, h, cfg)
  return jnp.argmax(logits, axis=-1).astype(jnp.int32), arena


@partial(
  jax.jit,
  static_argnames=("cfg", "num_tokens", "top_k", "top_p", "use_flash_decode", "start_layers",
                   "moe_routed", "pad_rows", "tp_mesh"),
  donate_argnames=("seg_caches",),
)
def decode_chunk_ring_batched(
  params_segs,  # tuple of per-partition param pytrees, ring order
  seg_caches,  # tuple over segments of tuples over B requests of cache dicts
  toks: jnp.ndarray,  # [B, 1] int32 — each request's last sampled token
  pos_vec: jnp.ndarray,  # [B] int32 per-request positions
  key: jax.Array,
  cfg: ModelConfig,
  num_tokens: int,
  temps: jnp.ndarray,  # [B] per-request temperatures (traced)
  top_k: int,
  top_p: float = 0.0,
  use_flash_decode: bool = False,
  start_layers: Tuple[int, ...] = (0,),
  moe_routed: bool = True,
  pad_rows: int = 0,  # static: dummy rows padding B to a power of two
  tp_mesh=None,  # static Mesh the co-located partitions all serve over
):
  """Continuous batching for the fused multi-partition ring: B concurrent
  requests' chunks share ONE dispatch through every partition's layer stack
  (same win as decode_chunk_batched — decode is weight-HBM-bound, so B rows
  ride one weight read per segment instead of B). Stack each segment's
  per-request caches along batch, scan the composite per-token step with
  PER-ROW positions, split every segment's caches back — all inside one
  compiled program. Returns ([B_real, num_tokens] tokens, tuple over
  segments of tuples of B_real updated caches)."""
  B = len(seg_caches[0])
  stacked = []
  for caches in seg_caches:
    stacked.append({
      name: jnp.concatenate([c[name] for c in caches]
                            + [jnp.zeros_like(caches[0][name])] * pad_rows, axis=1)
      for name in caches[0]
    })
  if pad_rows:
    toks = jnp.concatenate([toks, jnp.broadcast_to(toks[:1], (pad_rows, 1))], axis=0)
    pos_vec = jnp.concatenate([pos_vec, jnp.broadcast_to(pos_vec[:1], (pad_rows,))])
    temps = jnp.concatenate([temps, jnp.broadcast_to(temps[:1], (pad_rows,))])

  def step(carry, _):
    tok, caches, pos, key = carry
    h = tok
    new_caches = []
    for i, params in enumerate(params_segs):
      h, c = forward_shard(params, h, caches[i], pos, cfg=cfg, is_first=(i == 0),
                           is_last=False, use_flash_decode=use_flash_decode,
                           start_layer=start_layers[i], moe_routed=moe_routed,
                           tp_mesh=tp_mesh)
      new_caches.append(c)
    logits = unembed(params_segs[-1], h, cfg)
    key, sub = jax.random.split(key)
    nxt = sample_logits(logits[:, -1, :], sub, temp=temps, top_k=top_k, top_p=top_p)
    return (nxt[:, None], tuple(new_caches), pos + 1, key), nxt

  init = (toks.astype(jnp.int32), tuple(stacked), pos_vec.astype(jnp.int32), key)
  (_, stacked, _, _), out = jax.lax.scan(step, init, None, length=num_tokens)
  split = tuple(
    tuple({name: seg[name][:, i:i + 1] for name in seg} for i in range(B))
    for seg in stacked
  )
  return out.T[:B], split


@partial(
  jax.jit,
  static_argnames=("cfg", "use_kernel", "moe_routed", "ragged", "start_layer", "tp_mesh"),
  donate_argnames=("arena",),
)
def forward_paged(
  params,
  x: jnp.ndarray,  # [B, T] int32 tokens (T == 1 per-token decode, T > 1 segment)
  arena: Dict[str, jnp.ndarray],  # shared page arena: [L, P, page, Hkv, D] leaves
  page_table: jnp.ndarray,  # [B, max_pages] int32 physical page ids (0-padded)
  start_pos: jnp.ndarray,  # scalar (or [B]) int32 position of x[:, 0]
  cfg: ModelConfig,
  use_kernel: bool = False,  # static: Pallas ragged kernel vs XLA gather
  moe_routed: bool = True,
  ragged: bool = True,  # static: kernel path reads pages natively (no gather)
  start_layer: int = 0,
  tp_mesh=None,  # static Mesh: tensor-parallel activation constraints
):
  """Full-logits forward over the PAGED arena — the vkv-backed per-token
  step. The contiguous per-token fallbacks (sampling extras mid-stream,
  non-bucket chunk tails) used to un-page the whole cache just to run
  forward_jit; this is the same forward with the K/V scattering into the
  request's pages instead, so those paths stay paged (zero
  xot_kv_unpage_total). Returns ([B, T, vocab] fp32 logits, updated
  arena)."""
  return forward_shard(params, x, arena, start_pos, cfg=cfg, is_first=True,
                       is_last=True, moe_routed=moe_routed,
                       start_layer=start_layer, page_table=page_table,
                       paged_kernel=use_kernel, ragged_prefill=ragged,
                       tp_mesh=tp_mesh)


@partial(
  jax.jit,
  static_argnames=("cfg", "num_tokens", "top_k", "top_p", "use_kernel", "pad_rows",
                   "moe_routed", "top_lp", "tp_mesh"),
  donate_argnames=("arena",),
)
def decode_chunk_paged(
  params,
  arena: Dict[str, jnp.ndarray],  # shared page arena: [L, P, page, Hkv, D] leaves
  page_table: jnp.ndarray,  # [B, max_pages] int32 physical page ids (0-padded)
  toks: jnp.ndarray,  # [B, 1] int32 — each request's last sampled token
  pos_vec: jnp.ndarray,  # [B] int32 per-request positions
  key: jax.Array,
  cfg: ModelConfig,
  num_tokens: int,
  temps: jnp.ndarray,  # [B] per-request temperatures (traced)
  top_k: int,
  top_p: float = 0.0,
  use_kernel: bool = False,  # static: Pallas ragged kernel vs XLA gather
  pad_rows: int = 0,  # static: dummy rows padding B to a power of two
  moe_routed: bool = True,
  bias: jnp.ndarray = None,  # [B, V] OpenAI logit_bias
  counts: jnp.ndarray = None,  # [B, V] token counts; updated INSIDE the scan
  presence: float = 0.0,
  frequency: float = 0.0,
  top_lp: int = -1,  # static: -1 = no logprob reporting; >=0 = report
  min_p=None,  # min-p cutoff (traced; None = off) — ops/sampling
  tp_mesh=None,  # static Mesh: tensor-parallel activation constraints
):
  """Batched fused decode over the PAGED KV pool, ONE executable end to end.

  Where decode_chunk_batched must first grow every member to a common
  contiguous length, then stack B caches and split them back per chunk,
  here batch membership is pure metadata: rows index the ONE shared arena
  through their page tables, writes scatter into each row's current page,
  and reads stop at each row's own occupied pages (ops/paged_attention) —
  no per-chunk stack/split, no common-length growth, no grow-copies.

  Sampling extras (logit bias, presence/frequency penalties with counts
  riding the scan carry, min-p, logprob reporting) mirror decode_chunk's
  contract exactly — they're what used to force an extras-bearing request
  OFF its pages. All default off, so the plain executables are unchanged.

  Dummy pad rows carry an all-zero page table: their writes land in the
  pool's reserved scratch page 0 (never allocated to a request) and their
  outputs are discarded — same log2(max batch) executable bounding as the
  contiguous batched path, without donating a real buffer twice. Returns
  ([B_real, num_tokens] int32 tokens, updated arena) — plus the updated
  counts when `counts` is passed, plus the logprob triple when
  `top_lp >= 0` (decode_chunk's ordering)."""
  B = toks.shape[0]
  track_counts = counts is not None
  want_lp = top_lp >= 0
  if pad_rows:
    page_table = jnp.concatenate(
      [page_table, jnp.zeros((pad_rows, page_table.shape[1]), page_table.dtype)], axis=0)
    toks = jnp.concatenate([toks, jnp.broadcast_to(toks[:1], (pad_rows, 1))], axis=0)
    pos_vec = jnp.concatenate([pos_vec, jnp.zeros((pad_rows,), pos_vec.dtype)])
    temps = jnp.concatenate([temps, jnp.broadcast_to(temps[:1], (pad_rows,))])
    if bias is not None:
      bias = jnp.concatenate([bias, jnp.zeros((pad_rows, bias.shape[1]), bias.dtype)], axis=0)
    if track_counts:
      counts = jnp.concatenate(
        [counts, jnp.zeros((pad_rows, counts.shape[1]), counts.dtype)], axis=0)

  def step(carry, _):
    tok, arena, pos, key, counts = carry
    logits, arena = forward_shard(params, tok, arena, pos, cfg=cfg, is_first=True,
                                  is_last=True, moe_routed=moe_routed,
                                  page_table=page_table, paged_kernel=use_kernel,
                                  tp_mesh=tp_mesh)
    key, sub = jax.random.split(key)
    step_counts = counts if track_counts else None
    if want_lp:
      nxt, lp, top_ids, top_lps = sample_logits_logprobs(
        logits[:, -1, :], sub, temp=temps, top_k=top_k, top_p=top_p,
        bias=bias, counts=step_counts, presence=presence, frequency=frequency,
        top_lp=top_lp, min_p=min_p)
      ys = (nxt, lp, top_ids, top_lps)
    else:
      nxt = sample_logits(logits[:, -1, :], sub, temp=temps, top_k=top_k, top_p=top_p,
                          bias=bias, counts=step_counts,
                          presence=presence, frequency=frequency, min_p=min_p)
      ys = nxt
    if track_counts:
      rows = jnp.arange(counts.shape[0], dtype=jnp.int32)
      counts = counts.at[rows, nxt].add(1)
    return (nxt[:, None], arena, pos + 1, key, counts), ys

  init = (toks.astype(jnp.int32), arena, pos_vec.astype(jnp.int32), key,
          counts if track_counts else jnp.zeros((), jnp.int32))
  (_, arena, _, _, counts_out), ys = jax.lax.scan(step, init, None, length=num_tokens)
  if want_lp:
    toks_out, lp, top_ids, top_lps = ys
    aux = (lp.T[:B], top_ids.transpose(1, 0, 2)[:B], top_lps.transpose(1, 0, 2)[:B])
  else:
    toks_out, aux = ys, None
  out = [toks_out.T[:B], arena]
  if track_counts:
    out.append(counts_out[:B])
  if want_lp:
    out.append(aux)
  return tuple(out)


@partial(
  jax.jit,
  static_argnames=("cfg", "num_tokens", "top_k", "top_p", "use_flash_decode", "pad_rows",
                   "moe_routed", "tp_mesh"),
  donate_argnames=("caches",),
)
def decode_chunk_batched(
  params,
  caches: Tuple[Dict[str, jnp.ndarray], ...],  # B per-request caches, UNIFORM shapes
  toks: jnp.ndarray,  # [B, 1] int32 — each request's last sampled token
  pos_vec: jnp.ndarray,  # [B] int32 per-request positions
  key: jax.Array,
  cfg: ModelConfig,
  num_tokens: int,
  temps: jnp.ndarray,  # [B] per-request temperatures (traced)
  top_k: int,
  top_p: float = 0.0,
  use_flash_decode: bool = False,
  pad_rows: int = 0,  # static: dummy rows padding B to a power of two
  moe_routed: bool = True,  # static: False when experts shard over 'ep'
  tp_mesh=None,  # static Mesh: tensor-parallel activation constraints
):
  """Batched fused decode for continuous batching, ONE executable end to
  end: stack the requests' caches along the batch axis, run the decode
  scan, split the updated caches back per request. Fusing the stack/split
  into the compiled program matters twice — XLA schedules the copies next
  to the compute instead of as dozens of EAGER ops (each a separate
  dispatch), and donation lets it reuse the input cache buffers.

  Dummy pad rows (static count) are zeros built inside the program — pads
  keep the executable count at log2(max batch) widths without donating the
  same real buffer twice. Returns ([B_real, num_tokens] tokens, tuple of
  B_real updated caches). Requires every cache to share one shape (the
  engine grows members to a common length before calling).
  """
  B = len(caches)
  cache_b = {
    name: jnp.concatenate(
      [c[name] for c in caches]
      + [jnp.zeros_like(caches[0][name])] * pad_rows, axis=1)
    for name in caches[0]
  }
  if pad_rows:
    toks = jnp.concatenate([toks, jnp.broadcast_to(toks[:1], (pad_rows, 1))], axis=0)
    pos_vec = jnp.concatenate([pos_vec, jnp.broadcast_to(pos_vec[:1], (pad_rows,))])
    temps = jnp.concatenate([temps, jnp.broadcast_to(temps[:1], (pad_rows,))])
  out, cache_b = decode_chunk(
    params, toks, cache_b, pos_vec, key, cfg, num_tokens, temps, top_k, top_p,
    use_flash_decode=use_flash_decode, moe_routed=moe_routed, tp_mesh=tp_mesh,
  )
  split = tuple({name: cache_b[name][:, i:i + 1] for name in cache_b} for i in range(B))
  return out[:B], split
