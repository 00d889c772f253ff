"""Benchmark: greedy/sampled decode throughput on the flagship model.

Measures the reference's own two native metrics (BASELINE.md): aggregate
output tokens/sec at the sampler (the chat-TUI method, chat_tui.py:121-128)
and per-token latency, plus TTFT for the prefill path and MFU / HBM-bandwidth
utilisation against the chip's public peak (TPU_CHIP_SPECS, keyed by
`device_kind`).

Chip or fail: ONE process, on the TPU. A run that finds no TPU, a device_kind
the peak table does not know, or a stage that raises exits non-zero with the
error — there is no CPU fallback, no retry loop and no partial-result
salvage, so no CPU timing can ever be filed under a device metric's name.
Every result names `platform` / `device_kind` / `n_devices` as JAX reports
them. (The process holds the chip while it runs: start nothing else that
needs it.)

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tok/s", "vs_baseline": N, ...}
vs_baseline compares against BENCH_BASELINE.json, keyed per (model, platform,
method).
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).parent
QUANT_PREFIXES = {"int8", "int4"}


def log(msg: str) -> None:
  print(msg, file=sys.stderr, flush=True)


def _record(stage: str, **kw) -> None:
  """Progress line on stderr (the one JSON result goes to stdout)."""
  log(f"[bench:{stage}] {kw if kw else ''}")


def _tpu_peaks(devices):
  """(peak bf16 TFLOP/s, peak HBM GB/s) of one chip, from the one
  device_kind-keyed table; a kind it does not know raises."""
  from xotorch_tpu.topology.device_capabilities import tpu_chip_peaks
  return tpu_chip_peaks(devices[0].device_kind)


def _calibrate_sync() -> dict:
  """Probe whether block_until_ready actually barriers on this backend.

  Times a known-FLOP matmul two ways: (a) block_until_ready only, (b) a
  device->host fetch of one element (which cannot return fake data). If (a)
  implies a FLOP rate far above the chip's physical peak while (b) doesn't,
  the async timing path is lying (JAX returns before the device finishes —
  a timing without a real barrier measures the enqueue) and every
  measurement must use host-fetch control timings.
  """
  import jax
  import jax.numpy as jnp
  import numpy as np

  on_tpu = jax.devices()[0].platform == "tpu"
  n = 4096 if on_tpu else 1024
  reps = 8 if on_tpu else 2
  flops = 2 * n * n * n  # 137.4 GFLOP at n=4096
  a = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)
  b = jax.random.normal(jax.random.PRNGKey(2), (n, n), jnp.bfloat16)
  mm = jax.jit(lambda a, b: a @ b)
  np.asarray(mm(a, b))[0, 0]  # compile + full sync
  t0 = time.time()
  for _ in range(reps):
    c = mm(a, b)
  c.block_until_ready()
  block_secs = (time.time() - t0) / reps

  t0 = time.time()
  for _ in range(reps):
    c = mm(a, b)
    _ = np.asarray(c[0, 0])  # D2H fetch: cannot complete before the matmul
  fetch_secs = (time.time() - t0) / reps

  peak_tflops, _ = _tpu_peaks(jax.devices())
  block_tflops = flops / block_secs / 1e12
  fetch_tflops = flops / fetch_secs / 1e12
  # block_until_ready is broken if it reports a rate over the physical peak
  # (with 2x headroom for spec slop) while the fetch timing is sane.
  sync_ok = peak_tflops is None or block_tflops <= 2 * peak_tflops
  out = {
    "matmul_gflop": round(flops / 1e9, 1),
    "block_ms": round(block_secs * 1000, 3),
    "fetch_ms": round(fetch_secs * 1000, 3),
    "block_tflops": round(block_tflops, 2),
    "fetch_tflops": round(fetch_tflops, 2),
    "peak_tflops": peak_tflops,
    "block_until_ready_ok": sync_ok,
  }
  _record("sync_calibration", **out)
  return out


def _run_config(model_id: str, prefill_len: int, decode_tokens: int, chunk: int,
                cache_len: int, stage_prefix: str,
                measure_async: bool = False, quantize: str = "",
                long_stage: bool = False) -> dict:
  """Measure one model config end to end. Returns the result dict.

  `measure_async`: also time block_until_ready-only variants of both decode
  paths (doubles the workload) — only worth it when the sync calibration
  found block_until_ready broken, or BENCH_ASYNC=1 forces the diagnostic.
  `quantize`: "int8" measures the weight-only-quantized model
  (models/quantize.py) — roofline math then uses the ACTUAL resident bytes
  (int8 halves them), not 2 bytes/param."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  from functools import partial
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.registry import model_cards
  from xotorch_tpu.models.transformer import forward_shard, init_kv_cache, init_random_params
  from xotorch_tpu.models.generate import decode_chunk
  from xotorch_tpu.models.quantize import quantize_params, quantized_bytes

  cfg = config_from_hf_dict(model_cards[model_id]["synthetic_config"])
  n = cfg.num_layers

  t0 = time.time()
  params = init_random_params(cfg, n, True, True, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
  n_params = sum(int(x.size) for x in jax.tree.leaves(params))
  if quantize:
    params = quantize_params(params, quantize)
  params = jax.block_until_ready(params)
  param_bytes = quantized_bytes(params)
  # Analytic cost model (costmodel.CostModel): the same math the serving
  # attribution layer uses, recorded NEXT TO the measured timings so every
  # harvest carries its own predicted bytes/FLOPs — and cross-checked here
  # against the real pytree (a layout drift shows up as a mismatch flag in
  # the JSON, and as a ground-truth test failure in CI).
  from xotorch_tpu.inference.jax_engine.costmodel import CostModel
  cm = CostModel(cfg=cfg, n_layers=n, is_first=True, is_last=True,
                 quantize=quantize or None, dtype_bytes=2)
  predicted_weight_bytes = cm.weight_bytes()
  # Fused decode streams the weights once per token and reads the whole
  # ALLOCATED contiguous cache per step (the XLA path's real traffic).
  predicted_decode_bytes_per_tok = (predicted_weight_bytes
                                    + cm.kv_read_bytes_per_token(prefill_len, alloc_tokens=cache_len)
                                    + cm.kv_write_bytes_per_token())
  predicted_flops_per_tok = cm.decode_flops_per_token(prefill_len)
  _record(f"{stage_prefix}:params", model=model_id,
          n_params=n_params, gb=round(param_bytes / 1e9, 2),
          predicted_gb=round(predicted_weight_bytes / 1e9, 2),
          predicted_match=predicted_weight_bytes == param_bytes,
          secs=round(time.time() - t0, 1))

  fwd = jax.jit(partial(forward_shard, cfg=cfg, is_first=True, is_last=True), donate_argnums=(2,))
  cache = init_kv_cache(cfg, n, 1, cache_len, jnp.bfloat16)
  prompt = jnp.asarray(np.random.randint(0, cfg.vocab_size, (1, prefill_len)), jnp.int32)

  # --- prefill (TTFT) ---
  t0 = time.time()
  logits, cache = fwd(params, prompt, cache, jnp.int32(0))
  np.asarray(logits[:, -1, :1])  # host fetch: true barrier even if b_u_r lies
  _record(f"{stage_prefix}:prefill_compile", secs=round(time.time() - t0, 1))

  # warm decode compile
  tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
  t0 = time.time()
  logits, cache = fwd(params, tok, cache, jnp.int32(prefill_len))
  np.asarray(logits[:, -1, :1])
  _record(f"{stage_prefix}:decode_compile", secs=round(time.time() - t0, 1))

  # steady-state TTFT (cached executable), host-fetch timed with the SAME
  # fetch expression the warm-up used — a new slice/argmax shape here would
  # put a one-time XLA compile inside the timed window.
  cache2 = init_kv_cache(cfg, n, 1, cache_len, jnp.bfloat16)
  t0 = time.time()
  lg, cache2 = fwd(params, prompt, cache2, jnp.int32(0))
  np.asarray(lg[:, -1, :1])
  ttft = time.time() - t0
  del cache2, lg

  # --- per-token decode loop (the ring-hop path: one dispatch per token).
  # Control timing fetches each sampled token to the host — that D2H is part
  # of the real serving loop (the Node broadcasts every token) AND it is a
  # sync the backend cannot fake, unlike block_until_ready (VERDICT r2 #1).
  pos = prefill_len + 1
  tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
  first_tok = int(np.asarray(tok)[0, 0])  # t1: produced by the warm decode step
  loop_tokens = [first_tok]
  t0 = time.time()
  last_beat = t0
  for i in range(decode_tokens):
    logits, cache = fwd(params, tok, cache, jnp.int32(pos + i))
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    loop_tokens.append(int(np.asarray(tok)[0, 0]))
    if time.time() - last_beat > 60:  # keep the parent's stall watchdog fed
      last_beat = time.time()
      _record(f"{stage_prefix}:per_token_progress", i=i + 1, of=decode_tokens)
  elapsed = time.time() - t0
  hop_toks_per_sec = decode_tokens / elapsed
  _record(f"{stage_prefix}:per_token", tok_s=round(hop_toks_per_sec, 1))

  # Async variant (block_until_ready only) — diagnostic for sync breakage.
  # Mirrors the control loop exactly (prefill + warm decode step filling
  # position prefill_len, then decode_tokens steps from pos), and drains all
  # pre-loop device work before the timer so only the decode loop is timed.
  async_hop_toks_per_sec = None
  if measure_async:
    cache_a = init_kv_cache(cfg, n, 1, cache_len, jnp.bfloat16)
    lg_a, cache_a = fwd(params, prompt, cache_a, jnp.int32(0))
    tok_a = jnp.argmax(lg_a[:, -1:], axis=-1).astype(jnp.int32)
    lg_a, cache_a = fwd(params, tok_a, cache_a, jnp.int32(prefill_len))
    tok_a = jnp.argmax(lg_a[:, -1:], axis=-1).astype(jnp.int32)
    np.asarray(lg_a[:, -1, :1])  # true barrier: prefill+warm work must not leak into the timer
    t0 = time.time()
    for i in range(decode_tokens):
      lg_a, cache_a = fwd(params, tok_a, cache_a, jnp.int32(pos + i))
      tok_a = jnp.argmax(lg_a[:, -1:], axis=-1).astype(jnp.int32)
    tok_a.block_until_ready()
    async_hop_toks_per_sec = decode_tokens / (time.time() - t0)
    del cache_a, lg_a, tok_a

  # --- fused decode (the serving fast path: forward + sampling under one
  # lax.scan, models/generate.py; Node uses it whenever one partition owns
  # the whole model). Control timing fetches each chunk's tokens — serving
  # does that anyway (EOS check between chunks).
  cache3 = init_kv_cache(cfg, n, 1, cache_len, jnp.bfloat16)
  logits3, cache3 = fwd(params, prompt, cache3, jnp.int32(0))
  tok3 = jnp.argmax(logits3[:, -1:], axis=-1).astype(jnp.int32)
  key = jax.random.PRNGKey(0)
  t0 = time.time()
  toks, cache3 = decode_chunk(params, tok3, cache3, jnp.int32(prefill_len), key, cfg, chunk, 0.0, 0)
  np.asarray(toks)
  _record(f"{stage_prefix}:fused_compile", secs=round(time.time() - t0, 1))

  # Sequential control: fetch chunk N's tokens BEFORE dispatching N+1 (the
  # pre-overlap serving loop). Kept as a transparency datum next to the
  # overlapped headline below.
  fused_tokens = [int(v) for v in np.asarray(toks)[0]]
  produced = chunk
  t0 = time.time()
  last_beat = t0
  while produced < decode_tokens + chunk:  # match the per-token loop's length
    tok3 = toks[:, -1:].astype(jnp.int32)
    toks, cache3 = decode_chunk(params, tok3, cache3, jnp.int32(prefill_len + produced), key, cfg, chunk, 0.0, 0)
    fused_tokens.extend(int(v) for v in np.asarray(toks)[0])  # host fetch per chunk = control sync
    produced += chunk
    if time.time() - last_beat > 60:
      last_beat = time.time()
      _record(f"{stage_prefix}:fused_progress", produced=produced)
  seq_elapsed = time.time() - t0
  seq_n = produced - chunk
  seq_toks_per_sec = seq_n / seq_elapsed

  # Overlapped fused decode — THE serving loop (engine._decode_batch_sync
  # speculative next-chunk dispatch, default on): chunk N+1 is dispatched
  # from chunk N's last token (a device array) BEFORE N's tokens are
  # fetched, so the device never idles during the host's EOS scan. Every
  # chunk's tokens are still fetched (same per-chunk host sync as serving);
  # only the ORDER of fetch vs dispatch changes. Greedy tokens are
  # cross-checked against the per-token loop below, unchanged.
  ov_cache = init_kv_cache(cfg, n, 1, cache_len, jnp.bfloat16)
  lg_o, ov_cache = fwd(params, prompt, ov_cache, jnp.int32(0))
  tok_o = jnp.argmax(lg_o[:, -1:], axis=-1).astype(jnp.int32)
  toks_o, ov_cache = decode_chunk(params, tok_o, ov_cache, jnp.int32(prefill_len), key, cfg, chunk, 0.0, 0)
  np.asarray(toks_o)  # warm (executables already compiled above)
  del lg_o
  ov_tokens: list = []
  produced_o = chunk
  t0 = time.time()
  last_beat = t0
  while produced_o < decode_tokens + chunk:
    nxt, ov_cache = decode_chunk(params, toks_o[:, -1:].astype(jnp.int32), ov_cache,
                                 jnp.int32(prefill_len + produced_o), key, cfg, chunk, 0.0, 0)
    ov_tokens.extend(int(v) for v in np.asarray(toks_o)[0])  # fetch N while N+1 computes
    toks_o = nxt
    produced_o += chunk
    if time.time() - last_beat > 60:
      last_beat = time.time()
      _record(f"{stage_prefix}:fused_overlap_progress", produced=produced_o)
  ov_tokens.extend(int(v) for v in np.asarray(toks_o)[0])  # drain the in-flight chunk
  fused_elapsed = time.time() - t0
  fused_n = produced_o - chunk  # chunks COMPUTED inside the window (warm chunk excluded)
  toks_per_sec = fused_n / fused_elapsed
  per_token_ms = 1000 * fused_elapsed / fused_n
  # The overlap must be a pure reordering of fetch vs dispatch — byte-equal
  # greedy streams, or the headline is invalid.
  overlap_tokens_match = ov_tokens == fused_tokens
  del ov_cache

  # Core numbers on the log BEFORE the long-context stage starts.
  _record(
    f"{stage_prefix}_core_result",
    model_id=model_id, platform=jax.devices()[0].platform,
    n_devices=len(jax.devices()),
    device_kind=str(getattr(jax.devices()[0], "device_kind", "")),
    n_params=n_params, quantize=quantize or None, param_bytes=param_bytes,
    tok_s=round(toks_per_sec, 2), per_token_ms=round(per_token_ms, 3),
    ttft_ms=round(ttft * 1000, 1), per_token_path_tok_s=round(hop_toks_per_sec, 2),
    fused_seq_tok_s=round(seq_toks_per_sec, 2), overlap_tokens_match=overlap_tokens_match,
  )

  # --- long-context decode (auto on TPU; BENCH_LONG=0 disables, =N sets
  # the depth). Prefill runs in chunked segments (the serving path's design
  # — no [T, S] score blowup; 2048 tokens by default, BENCH_LONG_SEG
  # overrides), then decode at depth measures the resident-cache read cost
  # the short config can't see.
  on_tpu_now = jax.devices()[0].platform == "tpu"
  long_ctx = int(os.getenv("BENCH_LONG", "16384" if on_tpu_now else "0") or 0) if long_stage else 0
  long_result = {}
  if long_ctx >= 2048:
    # Segment size: 2048 keeps r3 comparability; BENCH_LONG_SEG=4096 matches
    # the engine's serving default (XOT_PREFILL_CHUNK) — fewer, larger
    # dispatches with better MXU tiling per segment. Validated: rounded to
    # a multiple of 256 (the flash kernel requires T % block == 0) and
    # clamped to the depth (a seg > long_ctx would zero the whole stage).
    seg = max(256, int(os.getenv("BENCH_LONG_SEG", "2048") or 2048) // 256 * 256)
    seg = min(seg, long_ctx // 256 * 256)
    long_ctx -= long_ctx % seg  # whole segments: ONE executable serves all
    # BENCH_KV_QUANT=int8: the long stage runs on an int8 KV cache — decode
    # at depth is cache-bandwidth-bound, so the halved bytes/token (plus the
    # cached kernel's in-tile dequant, ops/flash_decode._scores) is the
    # measurable win. Serving-shaped: the kernel path serves int8 caches.
    kvq = os.getenv("BENCH_KV_QUANT", "") == "int8"
    cache_shape_len = long_ctx + 4 * chunk + 64  # covers warm-up + all timed chunks
    lprompt = np.random.randint(0, cfg.vocab_size, (1, long_ctx))
    # Engine-shaped executables (engine._segment_setup's selection): the
    # from-zero segment takes the Pallas flash prefill kernel, later
    # segments the occupancy-aware cached-attention kernel — the XLA
    # baseline attention reads the FULL allocated cache per segment and
    # materialises [T, S] scores, which is what capped round 3's long
    # prefill at ~7% MFU (VERDICT r3 weak #3). Off-TPU both stay baseline.
    # The scan path serves quantized weights too (the kernels only touch
    # q/k/v after the projections, so weight quantization is orthogonal) —
    # matching engine._scan_prefill, which gates on the cache format only.
    use_scan = ((on_tpu_now or os.getenv("XOT_SCAN_PREFILL_FORCE") == "1")
                and long_ctx >= 2 * seg
                and os.getenv("XOT_SCAN_PREFILL", "1") == "1")
    if on_tpu_now:
      fwd_seg0 = jax.jit(partial(forward_shard, cfg=cfg, is_first=True, is_last=True,
                                 use_flash=True), donate_argnums=(2,))
      fwd_segN = jax.jit(partial(forward_shard, cfg=cfg, is_first=True, is_last=True,
                                 use_flash_decode=True), donate_argnums=(2,))
    else:
      fwd_seg0 = fwd_segN = fwd

    def _prefill_long(lcache):
      """The serving-shaped long prefill (engine._scan_prefill): leading
      full segments fold into fused scan-prefill executables (one dispatch
      per power-of-two segment group, where the host-side per-segment loop
      pays one dispatch + one H2D transfer per segment), then the FINAL
      segment runs through the
      logits executable for the next-token distribution."""
      if not use_scan:
        for off in range(0, long_ctx, seg):
          x = jnp.asarray(lprompt[:, off:off + seg], jnp.int32)
          lg, lcache = (fwd_seg0 if off == 0 else fwd_segN)(params, x, lcache, jnp.int32(off))
        return lg, lcache
      from xotorch_tpu.models.generate import prefill_scan, scan_groups
      split = long_ctx - seg
      xdev = jnp.asarray(lprompt[:, :split], jnp.int32)  # ONE H2D for the scanned part
      for off, g in scan_groups(split // seg):
        _, lcache = prefill_scan(params, xdev[:, off * seg:(off + g) * seg], lcache,
                                 jnp.int32(off * seg), cfg, g)
      lg, lcache = fwd_segN(params, jnp.asarray(lprompt[:, split:], jnp.int32),
                            lcache, jnp.int32(split))
      return lg, lcache

    # Compile warm-up OUTSIDE the timed window (the long cache shape is new,
    # so the first segment call would otherwise bill XLA compile time as
    # prefill throughput — every other metric here excludes compiles). The
    # scan path needs a full untimed pass (each power-of-two group is its
    # own executable); the per-segment path warms with two segments as
    # before (seg0 + one pos>0 segment cover both executables).
    lcache = init_kv_cache(cfg, n, 1, cache_shape_len, jnp.bfloat16, kv_quant=kvq)
    if use_scan:
      lg, lcache = _prefill_long(lcache)
    else:
      lg, lcache = fwd_seg0(params, jnp.asarray(lprompt[:, :seg], jnp.int32), lcache, jnp.int32(0))
      if long_ctx > seg:
        lg, lcache = fwd_segN(params, jnp.asarray(lprompt[:, seg:2 * seg], jnp.int32),
                              lcache, jnp.int32(seg))
    np.asarray(lg[:, -1, :1])
    del lcache
    lcache = init_kv_cache(cfg, n, 1, cache_shape_len, jnp.bfloat16, kv_quant=kvq)
    t0 = time.time()
    lg, lcache = _prefill_long(lcache)
    np.asarray(lg[:, -1, :1])  # host fetch: true barrier
    long_prefill_s = time.time() - t0
    ltok = jnp.argmax(lg[:, -1:], axis=-1).astype(jnp.int32)
    use_fd_l = kvq and on_tpu_now  # int8 cache decode rides the Pallas cached kernel
    ltoks, lcache = decode_chunk(params, ltok, lcache, jnp.int32(long_ctx), key, cfg, chunk, 0.0, 0,
                                 use_flash_decode=use_fd_l)
    np.asarray(ltoks)  # decode compile + first chunk
    t0 = time.time()
    produced_l = 0
    # Several dispatches, not one: a single chunk's wall time is too noisy
    # to be the long-context headline. Overlapped like the short config —
    # dispatch N+1 from the device-side last token, then fetch N.
    while produced_l < max(32, 3 * chunk):
      ltok = ltoks[:, -1:].astype(jnp.int32)
      nxt_l, lcache = decode_chunk(params, ltok, lcache, jnp.int32(long_ctx + chunk + produced_l),
                                   key, cfg, chunk, 0.0, 0, use_flash_decode=use_fd_l)
      np.asarray(ltoks)
      ltoks = nxt_l
      produced_l += chunk
    np.asarray(ltoks)  # drain the in-flight chunk (its compute is in-window)
    # Prefill MFU (VERDICT r3 #5): dense matmul FLOPs (2 per param per
    # token) + causal attention FLOPs (QK^T and AV, each 2*H FLOPs per
    # (query, visible-key) pair, ~T^2/2 pairs per layer) against the chip's
    # bf16 peak. The plausibility gate below marks >100% implausible.
    peak_tflops_l, _ = _tpu_peaks(jax.devices())
    H_attn = cfg.num_heads * cfg.head_dim
    prefill_flops = 2 * n_params * long_ctx + 2 * cfg.num_layers * long_ctx * long_ctx * H_attn
    prefill_mfu = (round(100 * prefill_flops / (long_prefill_s * peak_tflops_l * 1e12), 2)
                   if peak_tflops_l else None)
    long_result = {
      "long_ctx": long_ctx,
      "long_prefill_s": round(long_prefill_s, 2),
      "long_prefill_tok_s": round(long_ctx / long_prefill_s, 1),
      "prefill_mfu_pct": prefill_mfu,
      "prefill_mode": "scan" if use_scan else "segmented",
      "long_tok_s": round(produced_l / (time.time() - t0), 2),
      **({"long_kv_quant": "int8"} if kvq else {}),
    }
    del lcache, lg, ltok, ltoks
    _record(f"{stage_prefix}:long_context", **long_result)

  # Async fused variant (block_until_ready only) — diagnostic.
  async_toks_per_sec = None
  if measure_async:
    cache4 = init_kv_cache(cfg, n, 1, cache_len, jnp.bfloat16)
    lg4, cache4 = fwd(params, prompt, cache4, jnp.int32(0))
    tok4 = jnp.argmax(lg4[:, -1:], axis=-1).astype(jnp.int32)
    toks4, cache4 = decode_chunk(params, tok4, cache4, jnp.int32(prefill_len), key, cfg, chunk, 0.0, 0)
    toks4.block_until_ready()
    produced4 = chunk
    t0 = time.time()
    while produced4 < decode_tokens + chunk:
      tok4 = toks4[:, -1:].astype(jnp.int32)
      toks4, cache4 = decode_chunk(params, tok4, cache4, jnp.int32(prefill_len + produced4), key, cfg, chunk, 0.0, 0)
      produced4 += chunk
    toks4.block_until_ready()
    async_toks_per_sec = (produced4 - chunk) / (time.time() - t0)
    del cache4, lg4, tok4, toks4

  # --- greedy token cross-check: the fused scan and the per-token loop run
  # the same model from the same prefill state, so their argmax streams must
  # agree on a LONG COMMON PREFIX. Bit-exact full-stream equality is too
  # strict in bf16: the two executables reduce in different orders, and one
  # near-tie argmax flip legitimately forks the sequence — everything after
  # the first divergence is conditioned on different context and proves
  # nothing. A lying backend (returning uncomputed garbage) diverges within
  # the first token or two; a healthy one agrees for many. This is the
  # measurement-integrity gate VERDICT r2 asked for.
  n_cmp = min(len(loop_tokens), len(fused_tokens))
  agree = next((i for i in range(n_cmp) if loop_tokens[i] != fused_tokens[i]), n_cmp)
  min_prefix = min(16, n_cmp)
  tokens_verified = bool(n_cmp > 0 and agree >= min_prefix)
  if agree < n_cmp:
    _record(f"{stage_prefix}:token_divergence", at=agree, of=n_cmp,
            loop=loop_tokens[max(0, agree - 2):agree + 3],
            fused=fused_tokens[max(0, agree - 2):agree + 3])

  # If async and control timings diverge, the async path is not syncing;
  # the control number is the truth (it already is what we report).
  async_divergence = (round(async_toks_per_sec / toks_per_sec, 2)
                      if (async_toks_per_sec and toks_per_sec) else None)

  # Roofline context: decode does ~2·P MACs/token and must stream the full
  # resident param bytes from HBM each token (2/param at bf16, ~1 at int8) —
  # MFU for the compute view, BW% for the (binding, at batch 1) memory view.
  # hbm_bw_pct/mfu_pct keep their historical weights-only definitions (every
  # committed harvest is comparable through benchdiff); the predicted_* pair
  # below additionally counts the KV traffic the cost model attributes.
  devices = jax.devices()
  peak_tflops, peak_gbps = _tpu_peaks(devices)
  mfu_pct = round(100 * 2 * n_params * toks_per_sec / (peak_tflops * 1e12), 2) if peak_tflops else None
  hbm_pct = round(100 * param_bytes * toks_per_sec / (peak_gbps * 1e9), 2) if peak_gbps else None
  ceiling = round(peak_gbps * 1e9 / param_bytes, 1) if peak_gbps else None
  predicted_hbm_util_pct = (round(100 * predicted_decode_bytes_per_tok * toks_per_sec
                                  / (peak_gbps * 1e9), 2) if peak_gbps else None)
  predicted_mfu_pct = (round(100 * predicted_flops_per_tok * toks_per_sec
                             / (peak_tflops * 1e12), 2) if peak_tflops else None)

  result = {
    "model_id": model_id,
    "platform": devices[0].platform,
    "n_devices": len(devices),
    "device_kind": str(getattr(devices[0], "device_kind", "")),
    "n_params": n_params,
    "quantize": quantize or None,
    "param_bytes": param_bytes,
    "tok_s": round(toks_per_sec, 2),
    "per_token_ms": round(per_token_ms, 3),
    "ttft_ms": round(ttft * 1000, 1),
    "per_token_path_tok_s": round(hop_toks_per_sec, 2),
    "fused_speedup": round(toks_per_sec / hop_toks_per_sec, 2),
    # Sequential control (fetch-then-dispatch): the pre-overlap loop; the
    # headline is the overlapped loop serving actually runs.
    "fused_seq_tok_s": round(seq_toks_per_sec, 2),
    "overlap_tokens_match": overlap_tokens_match,
    "async_tok_s": round(async_toks_per_sec, 2) if async_toks_per_sec else None,
    "async_per_token_path_tok_s": round(async_hop_toks_per_sec, 2) if async_hop_toks_per_sec else None,
    "async_divergence": async_divergence,
    "tokens_verified": tokens_verified,
    "tokens_agree_prefix": agree,
    "mfu_pct": mfu_pct,
    "hbm_bw_pct": hbm_pct,
    "roofline_tok_s": ceiling,
    "predicted_weight_bytes": predicted_weight_bytes,
    "predicted_weight_match": predicted_weight_bytes == param_bytes,
    "predicted_decode_bytes_per_tok": predicted_decode_bytes_per_tok,
    "predicted_flops_per_tok": predicted_flops_per_tok,
    "predicted_hbm_util_pct": predicted_hbm_util_pct,
    "predicted_mfu_pct": predicted_mfu_pct,
    "prefill_len": prefill_len,
    "decode_tokens": decode_tokens,
    **long_result,
  }
  prefill_mfu_val = result.get("prefill_mfu_pct")
  # Implausibility gate: measured throughput against the COST MODEL's
  # predicted bytes/FLOPs per token (which include the KV traffic), not the
  # inline weights-only constants — a backend reporting more bytes/s or
  # FLOP/s than the chip can physically move is lying about its timings.
  # The 10% margin absorbs spec slop, exactly as before.
  gate_hbm = predicted_hbm_util_pct if predicted_hbm_util_pct is not None else hbm_pct
  gate_mfu = predicted_mfu_pct if predicted_mfu_pct is not None else mfu_pct
  result["implausible"] = bool(
    (gate_hbm is not None and gate_hbm > 110)
    or (gate_mfu is not None and gate_mfu > 100)
    or (prefill_mfu_val is not None and prefill_mfu_val > 100)
    or not tokens_verified
    or not overlap_tokens_match
  )
  if result["implausible"]:
    reasons = []
    if gate_hbm is not None and gate_hbm > 110:
      reasons.append(f"predicted HBM utilization {gate_hbm} exceeds physical ceiling")
    if gate_mfu is not None and gate_mfu > 100:
      reasons.append(f"predicted MFU {gate_mfu} exceeds 100")
    if prefill_mfu_val is not None and prefill_mfu_val > 100:
      reasons.append(f"prefill_mfu_pct={prefill_mfu_val} exceeds 100")
    if not tokens_verified:
      reasons.append("fused/per-token greedy token streams disagree")
    if not overlap_tokens_match:
      reasons.append("overlapped fused stream differs from sequential control")
    result["diagnosis"] = "; ".join(reasons)
  return result


class _NullServer:
  async def start(self):
    pass

  async def stop(self):
    pass


class _NoDiscovery:
  async def start(self):
    pass

  async def stop(self):
    pass

  async def discover_peers(self, wait_for_peers: int = 0):
    return []


def _bench_caps():
  from xotorch_tpu.topology.device_capabilities import DeviceCapabilities, DeviceFlops
  return DeviceCapabilities("bench", "chip", 1024, DeviceFlops(1.0, 2.0, 4.0))


async def _timed_generate(nodes, shard, prompt: str, request_id: str,
                          timeout: float = 1800) -> dict:
  """One greedy request through the Node serving loop, measured with the
  chat-TUI method (ref chat_tui.py:121-128): a timestamp at every token
  callback; steady tok/s drops the first token (prefill + compiles).
  `nodes` — every ring member (the token broadcast may surface on any
  peer). The ONE measurement body every Node-based runner shares
  (_run_ring2, _run_spec, _run_real_model). Returns
  {ttft_s, tok_s, n_tokens, tokens}."""
  import asyncio

  done = asyncio.Event()
  stamps = []
  final = {"tokens": []}

  def on_token(rid, tokens, is_finished):
    if rid != request_id:
      return  # a straggler broadcast from a previous run must not leak in
    stamps.append((time.time(), len(tokens)))
    final["tokens"] = list(tokens)
    if is_finished:
      done.set()

  for node in nodes:
    node.on_token.register(f"cb-{request_id}-{node.id}").on_next(on_token)
  t0 = time.time()
  await nodes[0].process_prompt(shard, prompt, request_id)
  await asyncio.wait_for(done.wait(), timeout=timeout)
  for node in nodes:
    node.on_token.deregister(f"cb-{request_id}-{node.id}")
  n_toks = max(n for _, n in stamps)
  after_first = [t for t, n in stamps if n > 1]
  steady = (n_toks - 1) / (after_first[-1] - stamps[0][0]) if len(after_first) > 1 else 0.0
  return {"ttft_s": stamps[0][0] - t0, "tok_s": steady, "n_tokens": n_toks,
          "tokens": final["tokens"]}


def _run_ring2(model_id: str, prefill_len: int, decode_tokens: int,
               pertoken_tokens: int = 16) -> dict:
  """2-partition same-process ring throughput: two engines in one process
  joined by InProcessPeerHandle, each owning HALF the layers.

  TWO modes, both measured with the chat-TUI method (tokens/elapsed at the
  token callback, ref chat_tui.py:121-128):
  - FUSED (the serving default, VERDICT r3 #1): the sampler peer folds the
    whole chain into one executable per chunk (engine.generate_chunk_ring) —
    ring2_tok_s, the driver's ring-sharded metric.
  - per-token (decode_chunk_size=1): one hop per partition per token, the
    reference's structural design — ring2_pertoken_tok_s, kept as the
    transparency datum the fused number is judged against.
  The two modes' greedy streams must agree on their common prefix
  (ring2_tokens_verified) — same self-validation as the single-shard bench."""
  import asyncio

  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.registry import model_cards
  from xotorch_tpu.networking.inprocess import InProcessPeerHandle
  from xotorch_tpu.orchestration.node import Node
  from xotorch_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy

  n_layers = config_from_hf_dict(model_cards[model_id]["synthetic_config"]).num_layers

  async def run_mode(tag: str, chunk: int, n_tokens: int) -> dict:
    from xotorch_tpu.inference.shard import Shard

    nodes = []
    for name in (f"ring2-{tag}-a", f"ring2-{tag}-b"):
      node = Node(name, _NullServer(), JAXShardInferenceEngine(), _NoDiscovery(), None,
                  RingMemoryWeightedPartitioningStrategy(),
                  max_generate_tokens=n_tokens, default_sample_temp=0.0,
                  decode_chunk_size=chunk)
      node.device_capabilities = _bench_caps()
      nodes.append(node)
    for node in nodes:
      for other in nodes:
        node.topology.update_node(other.id, _bench_caps())
      node.peers = [InProcessPeerHandle(o) for o in nodes if o is not node]

    shard = Shard(model_id, 0, n_layers - 1, n_layers)
    prompt = " ".join(["w"] * prefill_len)  # DummyTokenizer: 1 token/word

    async def generate(run_tag: str) -> dict:
      return await _timed_generate(nodes, shard, prompt, f"bench-{run_tag}")

    warm = await generate(f"{tag}-warmup")  # compiles both shards' executables
    _record(f"ring2:{tag}:warmup",
            **{k: round(v, 3) for k, v in warm.items() if k != "tokens"})
    timed = await generate(f"{tag}-timed")
    _record(f"ring2:{tag}", tok_s=round(timed["tok_s"], 2),
            n_tokens=timed["n_tokens"])
    return timed

  async def run() -> dict:
    fused = await run_mode("fused", int(os.getenv("XOT_DECODE_CHUNK", "8")), decode_tokens)
    pertoken = await run_mode("pertoken", 1, min(decode_tokens, pertoken_tokens))
    n_cmp = min(len(fused["tokens"]), len(pertoken["tokens"]))
    agree = next((i for i in range(n_cmp)
                  if fused["tokens"][i] != pertoken["tokens"][i]), n_cmp)
    return {
      "ring2_tok_s": round(fused["tok_s"], 2),
      "ring2_per_token_ms": round(1000.0 / fused["tok_s"], 3) if fused["tok_s"] else None,
      "ring2_ttft_ms": round(fused["ttft_s"] * 1000, 1),
      "ring2_n_tokens": fused["n_tokens"],
      "ring2_pertoken_tok_s": round(pertoken["tok_s"], 2),
      "ring2_fused_speedup": (round(fused["tok_s"] / pertoken["tok_s"], 2)
                              if pertoken["tok_s"] else None),
      # Same-prefix self-validation as the single-shard token cross-check.
      "ring2_tokens_verified": bool(n_cmp > 0 and agree >= min(8, n_cmp)),
    }

  return asyncio.run(run())


def _run_spec(model_id: str, prefill_len: int, decode_tokens: int) -> dict:
  """Prompt-lookup speculative decoding throughput (XOT_SPECULATE) through
  the real Node serving loop, on a repeat-heavy prompt (the
  summarisation/extraction workload shape prompt-lookup exists for).

  Measures the same request with speculation ON vs OFF — chat-TUI method at
  the token callback — plus the engine's draft accounting. The two greedy
  streams must be IDENTICAL (spec_tokens_verified): speculation may never
  change output, only its rate. Acceptance is data-dependent; whatever the
  synthetic model's greedy text yields is reported honestly.

  BENCH_SPEC_PAGED=1 adds the PAGED A/B (the `specpaged` stage): the
  same on/off pair under XOT_PAGED_KV=1, where verification runs as a T>1
  ragged query over the request's page table (engine XOT_PAGED_SPEC). All
  four greedy streams must be byte-identical, and the paged spec-on run
  must finish with ZERO unpage gathers and ZERO commit-copy bytes — the
  native-verify acceptance bar, asserted here exactly as in the tests."""
  import asyncio

  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.inference.shard import Shard
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.registry import model_cards
  from xotorch_tpu.orchestration.node import Node
  from xotorch_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy

  n_layers = config_from_hf_dict(model_cards[model_id]["synthetic_config"]).num_layers
  words = ("alpha", "beta", "gamma", "delta")
  prompt = " ".join(words[i % len(words)] for i in range(prefill_len))

  async def run_mode(spec: int, tag: str, paged: bool = False) -> dict:
    # Restore user-set values after: the paged A/B flips XOT_PAGED_KV per
    # mode, so the contiguous pair is honest even when the stage env sets it.
    prior = {k: os.environ.get(k) for k in ("XOT_SPECULATE", "XOT_PAGED_KV")}
    os.environ["XOT_SPECULATE"] = str(spec)
    os.environ["XOT_PAGED_KV"] = "1" if paged else "0"
    try:
      eng = JAXShardInferenceEngine()
      node = Node(f"spec-{tag}", _NullServer(), eng, _NoDiscovery(), None,
                  RingMemoryWeightedPartitioningStrategy(),
                  max_generate_tokens=decode_tokens, default_sample_temp=0.0,
                  decode_chunk_size=int(os.getenv("XOT_DECODE_CHUNK", "8")))
      node.device_capabilities = _bench_caps()
      node.topology.update_node(node.id, _bench_caps())
      shard = Shard(model_id, 0, n_layers - 1, n_layers)

      warm = await _timed_generate([node], shard, prompt, f"bench-spec-{tag}-warmup")
      _record(f"spec:{tag}:warmup", tok_s=round(warm["tok_s"], 2))
      # Draft accounting as DELTAS over the timed run only — the engine's
      # counters are cumulative and include the warmup.
      p0, a0 = getattr(eng, "_spec_proposed", 0), getattr(eng, "_spec_accepted", 0)
      timed = await _timed_generate([node], shard, prompt, f"bench-spec-{tag}-timed")
      timed["proposed"] = getattr(eng, "_spec_proposed", 0) - p0
      timed["accepted"] = getattr(eng, "_spec_accepted", 0) - a0
      # Native-verify acceptance counters (cumulative over warmup + timed —
      # the bar is ZERO, so the window doesn't matter).
      timed["unpage_calls"] = getattr(eng, "_unpage_calls", 0)
      timed["commit_copy_bytes"] = getattr(eng, "_commit_copy_bytes", 0)
      _record(f"spec:{tag}", tok_s=round(timed["tok_s"], 2),
              proposed=timed["proposed"], accepted=timed["accepted"])
      return timed
    finally:
      for k, v in prior.items():
        if v is None:
          os.environ.pop(k, None)
        else:
          os.environ[k] = v

  async def run() -> dict:
    on = await run_mode(8, "on")
    off = await run_mode(0, "off")
    out = {
      "spec_tok_s": round(on["tok_s"], 2),
      "spec_off_tok_s": round(off["tok_s"], 2),
      "spec_speedup": round(on["tok_s"] / off["tok_s"], 2) if off["tok_s"] else None,
      "spec_proposed": on["proposed"],
      "spec_accepted": on["accepted"],
      "spec_accept_rate": (round(on["accepted"] / on["proposed"], 3)
                           if on["proposed"] else None),
      # IDENTITY, not common-prefix: speculation may never change output.
      "spec_tokens_verified": bool(on["tokens"] and on["tokens"] == off["tokens"]),
    }
    if os.getenv("BENCH_SPEC_PAGED", "0") == "1":
      pon = await run_mode(8, "paged-on", paged=True)
      poff = await run_mode(0, "paged-off", paged=True)
      out.update({
        # spec_tok_s counts only ACCEPTED tokens (rejected drafts never
        # reach the stream), so specpaged_tok_s IS the acceptance-adjusted
        # headline the roofline comparison uses.
        "specpaged_tok_s": round(pon["tok_s"], 2),
        "specpaged_off_tok_s": round(poff["tok_s"], 2),
        "specpaged_speedup": (round(pon["tok_s"] / poff["tok_s"], 2)
                              if poff["tok_s"] else None),
        "specpaged_proposed": pon["proposed"],
        "specpaged_accepted": pon["accepted"],
        "specpaged_accept_rate": (round(pon["accepted"] / pon["proposed"], 3)
                                  if pon["proposed"] else None),
        # The native-verify bar: zero gather-backs, zero commit copies.
        "specpaged_unpage_calls": pon["unpage_calls"],
        "specpaged_commit_copy_bytes": pon["commit_copy_bytes"],
        # All four streams identical: paged spec == paged plain == contiguous.
        "specpaged_tokens_verified": bool(
          pon["tokens"] and pon["tokens"] == poff["tokens"]
          and pon["tokens"] == on["tokens"]),
      })
    return out

  return asyncio.run(run())


def _run_mesh(model_id: str, prefill_len: int, decode_tokens: int) -> dict:
  """Tensor-parallel serving mesh throughput (the `mesh` stage): the
  same greedy request through the Node loop with the ring stage tp-sharded
  (XOT_TP=N — weights per spec_for_param, KV on Hkv, activations pinned,
  paged kernels per-tp-shard) vs single-device (XOT_TP=0).

  The two greedy streams must be IDENTICAL (mesh_tokens_verified): a mesh
  may never change output, only who holds the bytes. The collective tax is
  reported from the cost model (two row-parallel psums per layer) so the
  speedup can be read against the per-device roofline honestly — on real
  chips ICI carries it, on the forced host mesh it is memcpy. BENCH_MESH_TP
  sets the requested width (default 2; the engine clamps to feasibility)."""
  import asyncio

  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.inference.shard import Shard
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.registry import model_cards
  from xotorch_tpu.orchestration.node import Node
  from xotorch_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy

  n_layers = config_from_hf_dict(model_cards[model_id]["synthetic_config"]).num_layers
  tp_req = int(os.getenv("BENCH_MESH_TP", "2"))
  words = ("alpha", "beta", "gamma", "delta")
  prompt = " ".join(words[i % len(words)] for i in range(prefill_len))

  async def run_mode(tp: int, tag: str) -> dict:
    prior = os.environ.get("XOT_TP")
    os.environ["XOT_TP"] = str(tp)
    try:
      eng = JAXShardInferenceEngine()
      node = Node(f"mesh-{tag}", _NullServer(), eng, _NoDiscovery(), None,
                  RingMemoryWeightedPartitioningStrategy(),
                  max_generate_tokens=decode_tokens, default_sample_temp=0.0,
                  decode_chunk_size=int(os.getenv("XOT_DECODE_CHUNK", "8")))
      node.device_capabilities = _bench_caps()
      node.topology.update_node(node.id, _bench_caps())
      shard = Shard(model_id, 0, n_layers - 1, n_layers)

      warm = await _timed_generate([node], shard, prompt, f"bench-mesh-{tag}-warmup")
      _record(f"mesh:{tag}:warmup", tok_s=round(warm["tok_s"], 2))
      timed = await _timed_generate([node], shard, prompt, f"bench-mesh-{tag}-timed")
      mesh = getattr(eng, "_mesh", None)
      timed["tp"] = int(mesh.shape["tp"]) if mesh is not None and "tp" in mesh.shape else 1
      model = (eng.perf_report() or {}).get("model") or {}
      timed["collective_bytes"] = model.get("collective_bytes_per_token", 0)
      timed["weight_bytes_per_device"] = model.get("weight_bytes_per_device_actual")
      _record(f"mesh:{tag}", tok_s=round(timed["tok_s"], 2),
              tp=timed["tp"])
      return timed
    finally:
      if prior is None:
        os.environ.pop("XOT_TP", None)
      else:
        os.environ["XOT_TP"] = prior

  async def run() -> dict:
    on = await run_mode(tp_req, "on")
    off = await run_mode(0, "off")
    return {
      "mesh_tok_s": round(on["tok_s"], 2),
      "mesh_off_tok_s": round(off["tok_s"], 2),
      "mesh_speedup": round(on["tok_s"] / off["tok_s"], 2) if off["tok_s"] else None,
      "mesh_ttft_ms": round(on["ttft_s"] * 1000, 1),
      "mesh_tp": on["tp"],
      # Per-device byte story behind the headline: the cost-model ICI term
      # and the ground-truth-checked per-device weight stream.
      "mesh_collective_bytes": on["collective_bytes"],
      "mesh_weight_bytes_per_device": on["weight_bytes_per_device"],
      # IDENTITY, not allclose: sharding may never change the stream.
      "mesh_tokens_verified": bool(on["tokens"] and on["tokens"] == off["tokens"]),
    }

  return asyncio.run(run())


def _run_vkv(model_id: str, prefill_len: int, decode_tokens: int) -> dict:
  """Virtual-KV A/B (the `vkv` stage): the same greedy request through
  the Node loop on three cache layouts — paged int8-KV (the headline: scale
  pages halve paged KV read bytes, judged against the 662 tok/s int8
  ceiling), contiguous int8-KV (the `rest` stage's layout — isolates what
  the page indirection costs/buys at equal arithmetic), and paged bf16 (the
  `paged` stage's layout — isolates what int8 KV buys at equal addressing).

  Paged int8 vs contiguous int8 must be byte-IDENTICAL
  (vkv_tokens_verified): virtual addressing may never change output, only
  where the bytes live. The bf16 arm legitimately differs (different cache
  numerics) and is only a throughput reference. Both paged arms must finish
  with ZERO unpage gathers and ZERO commit-copy bytes — the gate-list
  retirement bar, asserted here exactly as in tests/test_vkv.py — and the
  paged pool's defrag/fragmentation counters ride along for the record."""
  import asyncio

  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.inference.shard import Shard
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.registry import model_cards
  from xotorch_tpu.orchestration.node import Node
  from xotorch_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy

  n_layers = config_from_hf_dict(model_cards[model_id]["synthetic_config"]).num_layers
  words = ("alpha", "beta", "gamma", "delta")
  prompt = " ".join(words[i % len(words)] for i in range(prefill_len))

  async def run_mode(tag: str, paged: bool, kv_quant: str) -> dict:
    prior = {k: os.environ.get(k) for k in ("XOT_PAGED_KV", "XOT_KV_QUANT")}
    os.environ["XOT_PAGED_KV"] = "1" if paged else "0"
    os.environ["XOT_KV_QUANT"] = kv_quant
    try:
      eng = JAXShardInferenceEngine()
      node = Node(f"vkv-{tag}", _NullServer(), eng, _NoDiscovery(), None,
                  RingMemoryWeightedPartitioningStrategy(),
                  max_generate_tokens=decode_tokens, default_sample_temp=0.0,
                  decode_chunk_size=int(os.getenv("XOT_DECODE_CHUNK", "8")))
      node.device_capabilities = _bench_caps()
      node.topology.update_node(node.id, _bench_caps())
      shard = Shard(model_id, 0, n_layers - 1, n_layers)

      warm = await _timed_generate([node], shard, prompt, f"bench-vkv-{tag}-warmup")
      _record(f"vkv:{tag}:warmup", tok_s=round(warm["tok_s"], 2))
      timed = await _timed_generate([node], shard, prompt, f"bench-vkv-{tag}-timed")
      # Zero bars are cumulative over warmup + timed on purpose: one gather
      # anywhere means the layout lied about being native.
      timed["unpage_calls"] = int(getattr(eng, "_unpage_calls", 0))
      timed["commit_copy_bytes"] = int(getattr(eng, "_commit_copy_bytes", 0))
      stats = eng.page_pool_stats() if paged else None
      timed["pool"] = stats or {}
      _record(f"vkv:{tag}", tok_s=round(timed["tok_s"], 2),
              unpage_calls=timed["unpage_calls"])
      return timed
    finally:
      for k, v in prior.items():
        if v is None:
          os.environ.pop(k, None)
        else:
          os.environ[k] = v

  async def run() -> dict:
    pon = await run_mode("int8-paged", paged=True, kv_quant="int8")
    coff = await run_mode("int8-contig", paged=False, kv_quant="int8")
    bf16 = await run_mode("bf16-paged", paged=True, kv_quant="")
    return {
      "vkv_int8_tok_s": round(pon["tok_s"], 2),
      "vkv_int8_contig_tok_s": round(coff["tok_s"], 2),
      "vkv_bf16_tok_s": round(bf16["tok_s"], 2),
      # What the page indirection costs/buys at equal arithmetic, and what
      # int8 KV buys at equal addressing.
      "vkv_paged_speedup": (round(pon["tok_s"] / coff["tok_s"], 2)
                            if coff["tok_s"] else None),
      "vkv_int8_speedup": (round(pon["tok_s"] / bf16["tok_s"], 2)
                           if bf16["tok_s"] else None),
      "vkv_ttft_ms": round(pon["ttft_s"] * 1000, 1),
      # The gate-list retirement bar, summed over BOTH paged arms.
      "vkv_unpage_calls": pon["unpage_calls"] + bf16["unpage_calls"],
      "vkv_commit_copy_bytes": pon["commit_copy_bytes"] + bf16["commit_copy_bytes"],
      # Arena health for the record (headline arm): idle-slot defrag
      # activity and the live-hole gauge it acts on.
      "vkv_defrag_moves": int(pon["pool"].get("defrag_moves", 0)),
      "vkv_fragmentation_pages": int(pon["pool"].get("fragmentation", 0)),
      "vkv_peak_pages_in_use": int(pon["pool"].get("peak_pages_in_use", 0)),
      # IDENTITY, not allclose: the int8 arms share numerics, so virtual
      # addressing may not change a single token. bf16 is excluded — its
      # cache numerics differ by construction.
      "vkv_tokens_verified": bool(pon["tokens"] and pon["tokens"] == coff["tokens"]),
    }

  return asyncio.run(run())


def _run_concurrent(model_id: str, prefill_len: int, decode_tokens: int, n_conc: int) -> dict:
  """Aggregate throughput of N concurrent requests through one Node with
  continuous batching (VERDICT r2 #9: the target is >= 4x single-request
  tok/s at 8 concurrent — decode is HBM-bound at batch 1, so batched rows
  ride the same weight reads)."""
  import asyncio

  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.inference.shard import Shard
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.registry import model_cards
  from xotorch_tpu.orchestration.node import Node
  from xotorch_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy

  n_layers = config_from_hf_dict(model_cards[model_id]["synthetic_config"]).num_layers

  async def run() -> dict:
    engine = JAXShardInferenceEngine()
    widths = []
    inner = engine._decode_batch_sync

    def recording(ctx, items, *a):
      widths.append(len(items))
      return inner(ctx, items, *a)

    engine._decode_batch_sync = recording
    node = Node("bench-conc", _NullServer(), engine, _NoDiscovery(), None,
                RingMemoryWeightedPartitioningStrategy(),
                max_generate_tokens=decode_tokens, default_sample_temp=0.0,
                decode_chunk_size=32)
    node.device_capabilities = _bench_caps()
    node.topology.update_node(node.id, node.device_capabilities)
    shard = Shard(model_id, 0, n_layers - 1, n_layers)

    async def generate(rid: str, n_words: int) -> int:
      done = asyncio.Event()
      count = {"n": 0}

      def on_token(request_id, tokens, is_finished):
        if request_id != rid:
          return
        count["n"] = len(tokens)
        if is_finished:
          done.set()

      node.on_token.register(f"cb-{rid}").on_next(on_token)
      await node.process_prompt(shard, " ".join(["w"] * n_words), rid)
      await asyncio.wait_for(done.wait(), timeout=1800)
      node.on_token.deregister(f"cb-{rid}")
      return count["n"]

    # Warmup: compiles prefill + every power-of-two batch width.
    await asyncio.gather(*(generate(f"warm-{i}", prefill_len) for i in range(n_conc)))

    t0 = time.time()
    n1 = await generate("single", prefill_len)
    single_tok_s = n1 / (time.time() - t0)
    _record("concurrent:single", tok_s=round(single_tok_s, 2))

    widths.clear()
    t0 = time.time()
    counts = await asyncio.gather(*(generate(f"conc-{i}", prefill_len) for i in range(n_conc)))
    agg_tok_s = sum(counts) / (time.time() - t0)
    max_width = max(widths) if widths else 0
    _record("concurrent:aggregate", n=n_conc, tok_s=round(agg_tok_s, 2),
            dispatches=len(widths), max_batch_width=max_width)
    out = {
      "concurrent_n": n_conc,
      "concurrent_tok_s": round(agg_tok_s, 2),
      "single_stream_tok_s": round(single_tok_s, 2),
      "concurrency_speedup": round(agg_tok_s / single_tok_s, 2) if single_tok_s else None,
      "concurrent_max_batch_width": max_width,
    }
    out.update(_kv_pool_metrics(engine))
    return out

  return asyncio.run(run())


def _kv_pool_metrics(engine) -> dict:
  """Paged-KV observability snapshot for bench records (mirrors the /metrics
  gauges/counters): pool occupancy + the commit/grow copy counters the
  paged-native path must keep at zero. Empty when no pool exists (XOT_PAGED_KV
  off)."""
  stats = engine.page_pool_stats() if hasattr(engine, "page_pool_stats") else None
  if stats is None:
    return {}
  return {
    "kv_pool_pages_in_use": stats["pages_in_use"],
    "kv_pool_free_pages": stats["free_pages"],
    "kv_commit_copy_bytes": int(getattr(engine, "_commit_copy_bytes", 0)),
    "kv_grow_copies": int(getattr(engine, "_grow_copies", 0)),
  }


def _run_prefill_interference(model_id: str, prefill_len: int, decode_tokens: int,
                              n_conc: int) -> dict:
  """Mixed 16 k-prefill-under-N-stream-decode A/B (ISSUE 2 `pagedfill`):
  the serving pattern every prior PERF number ignored — PERF's 8-stream
  aggregate was measured with no prefill interference, so real mixed
  traffic was strictly worse than anything recorded. N short-prompt decode
  streams run; mid-decode, one long prompt arrives. Records the long
  prompt's TTFT and the decode streams' stall (inter-chunk gap p50/max
  during the prefill window), co-scheduled (XOT_PREFILL_COSCHED=1) vs
  monolithic (=0), and cross-checks the long prompt's greedy token stream
  between the two runs — byte inequality feeds the implausibility gate
  (co-scheduling must reorder work, never change it)."""
  import asyncio
  import statistics

  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.inference.shard import Shard
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.registry import model_cards
  from xotorch_tpu.orchestration.node import Node
  from xotorch_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy

  n_layers = config_from_hf_dict(model_cards[model_id]["synthetic_config"]).num_layers

  async def run_once(tag: str) -> dict:
    engine = JAXShardInferenceEngine()
    node = Node(f"bench-pagedfill-{tag}", _NullServer(), engine, _NoDiscovery(), None,
                RingMemoryWeightedPartitioningStrategy(),
                max_generate_tokens=decode_tokens, default_sample_temp=0.0,
                decode_chunk_size=16)
    node.device_capabilities = _bench_caps()
    node.topology.update_node(node.id, node.device_capabilities)
    shard = Shard(model_id, 0, n_layers - 1, n_layers)

    stamps: dict = {}  # rid -> [monotonic time per token callback]
    tokens: dict = {}  # rid -> final token list

    async def generate(rid: str, n_words: int):
      done = asyncio.Event()

      def on_token(request_id, toks, is_finished):
        if request_id != rid:
          return
        stamps.setdefault(rid, []).append(time.monotonic())
        tokens[rid] = [int(t) for t in toks]
        if is_finished:
          done.set()

      node.on_token.register(f"cb-{rid}").on_next(on_token)
      await node.process_prompt(shard, " ".join(["w"] * n_words), rid)
      await asyncio.wait_for(done.wait(), timeout=3600)
      node.on_token.deregister(f"cb-{rid}")

    async def mixed(round_tag: str) -> dict:
      """One mixed round: n_conc decode streams; once every stream has its
      first token, the long prompt fires. Returns TTFT + stall stats."""
      stamps.clear()
      tokens.clear()
      dec = [f"{round_tag}-dec-{i}" for i in range(n_conc)]
      long_rid = f"{round_tag}-long"

      async def long_after_decode_starts():
        # Fire the long prompt only once every decode stream has produced
        # its first token — the interference being measured is prefill vs
        # STEADY-STATE decode.
        while len([r for r in stamps if r in dec]) < n_conc:
          await asyncio.sleep(0.01)
        t0 = time.monotonic()
        await generate(long_rid, prefill_len)
        return t0

      t_start = time.monotonic()
      results = await asyncio.gather(
        *(generate(r, 48) for r in dec), long_after_decode_starts())
      t_long_start = results[-1]
      t_first_long = stamps[long_rid][0]

      # Decode stall: inter-callback gaps of the decode streams inside the
      # long prompt's prefill window (start -> first long token).
      gaps = []
      for rid in dec:
        ts = stamps.get(rid, [])
        prior = [t for t in ts if t <= t_long_start]
        window = ([prior[-1]] if prior else []) + \
                 [t for t in ts if t_long_start < t <= t_first_long]
        gaps.extend(b - a for a, b in zip(window, window[1:]))
      return {
        "ttft_s": round(t_first_long - t_long_start, 3),
        "stall_p50_ms": round(1000 * statistics.median(gaps), 1) if gaps else None,
        "stall_max_ms": round(1000 * max(gaps), 1) if gaps else None,
        "decode_chunks_during_prefill": sum(
          1 for rid in dec for t in stamps.get(rid, [])
          if t_long_start < t <= t_first_long),
        "long_tokens": list(tokens.get(long_rid, [])),
        "elapsed_s": round(time.monotonic() - t_start, 1),
      }

    # Warmup round compiles everything the measured round dispatches —
    # including the co-scheduled slice executables, which only exist under
    # live decode interference (a solo long prompt would warm the
    # monolithic path instead).
    await mixed("warm")
    out = await mixed("meas")
    out.update(_kv_pool_metrics(engine))
    _record(f"pagedfill:{tag}",
            **{k: v for k, v in out.items() if k != "long_tokens"})
    return out

  # The warm round uses byte-identical prompts, so the prefix cache (2
  # entries by default) would collapse the MEASURED round's prefill to a
  # warm-prefix hit — TTFT/stall would record a no-op and the A/B would be
  # vacuous. This stage measures prefill interference, not prefix reuse:
  # disable the cache for both runs.
  prev = {k: os.environ.get(k) for k in ("XOT_PREFILL_COSCHED", "XOT_PREFIX_CACHE")}
  try:
    os.environ["XOT_PREFIX_CACHE"] = "0"
    os.environ["XOT_PREFILL_COSCHED"] = "1"
    cos = asyncio.run(run_once("cosched"))
    os.environ["XOT_PREFILL_COSCHED"] = "0"
    mono = asyncio.run(run_once("monolithic"))
  finally:
    for k, v in prev.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v

  # Greedy streams must be byte-equal: co-scheduling reorders executor work
  # between requests, never the tokens of any one request.
  n_cmp = min(len(cos["long_tokens"]), len(mono["long_tokens"]), 32)
  verified = bool(n_cmp > 0 and cos["long_tokens"][:n_cmp] == mono["long_tokens"][:n_cmp])
  return {
    "pagedfill_prefill_len": prefill_len,
    "pagedfill_n_streams": n_conc,
    "pagedfill_ttft_s": cos["ttft_s"],
    "pagedfill_stall_p50_ms": cos["stall_p50_ms"],
    "pagedfill_stall_max_ms": cos["stall_max_ms"],
    "pagedfill_decode_chunks_during_prefill": cos["decode_chunks_during_prefill"],
    "pagedfill_nocosched_ttft_s": mono["ttft_s"],
    "pagedfill_nocosched_stall_p50_ms": mono["stall_p50_ms"],
    "pagedfill_nocosched_stall_max_ms": mono["stall_max_ms"],
    "pagedfill_nocosched_decode_chunks_during_prefill": mono["decode_chunks_during_prefill"],
    "pagedfill_tokens_verified": verified,
    **{f"pagedfill_{k}": v for k, v in cos.items() if k.startswith("kv_")},
  }


def _run_kv_host(model_id: str, prefill_len: int, decode_tokens: int) -> dict:
  """Cold vs HBM-warm vs host-warm TTFT A/B (ISSUE 3 `kvhost`): the same
  prompt served three ways — cold prefill, HBM prefix-cache hit, and a
  host-tier restore after a forced OOM recovery (_free_device_memory
  spill-then-drop). The host-warm number is the whole point of the tier:
  strictly better than cold (the prefix streams back over PCIe instead of
  re-prefilling) while strictly worse than an HBM hit (the H2D copy is not
  free). All three greedy streams must be byte-identical — a tier that
  changes tokens is corruption, and the inequality feeds the bench's
  implausibility gate exactly like the fused/per-token cross-check."""
  import asyncio

  import numpy as np

  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.inference.shard import Shard
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.registry import model_cards

  n_layers = config_from_hf_dict(model_cards[model_id]["synthetic_config"]).num_layers

  # TOKEN-level prompts, engine-direct: the synthetic models' dummy
  # tokenizer maps every word to the same id, so word-varied Node prompts
  # would all share one token stream and the warmup would silently warm the
  # "cold" run (the pagedfill stage sidesteps the same trap by disabling
  # the prefix cache — here the cache IS the measurand). Distinct modular
  # patterns diverge at token 0, so warmups never seed a measured prefix.
  def pattern(seed: int) -> np.ndarray:
    return ((np.arange(prefill_len) * (seed * 2 + 3) + seed) % 200 + 3)[None, :].astype(np.int64)

  async def run() -> dict:
    engine = JAXShardInferenceEngine()
    shard = Shard(model_id, 0, n_layers - 1, n_layers)

    async def generate(rid: str, prompt: np.ndarray):
      """One greedy request: TTFT is the prefill-to-first-sampled-token
      wall time (infer_sample_tensor), then a few fused chunks for the
      cross-checkable stream."""
      t0 = time.monotonic()
      tok, _ = await engine.infer_sample_tensor(rid, shard, prompt, temp=0.0)
      ttft = time.monotonic() - t0
      toks = [int(tok)]
      for _ in range(max(1, decode_tokens // 16)):
        out = await engine.generate_chunk(rid, shard, toks[-1], 16, temp=0.0)
        toks.extend(int(t) for t in out)
      await engine.clear_request(rid)
      return round(ttft, 3), toks

    # Compile warmups on a DISTINCT prefix: run it twice so BOTH the cold
    # path and the warm path (prefix hit + suffix-only prefill — different
    # executable shapes) are compiled before anything is measured.
    await generate("kvhost-warmexe", pattern(1))
    await generate("kvhost-warmexe2", pattern(1))
    cold_ttft, cold_toks = await generate("kvhost-cold", pattern(0))
    _record("kvhost:cold", ttft_s=cold_ttft)
    hbm_ttft, hbm_toks = await generate("kvhost-hbm", pattern(0))
    _record("kvhost:hbm", ttft_s=hbm_ttft)

    # Forced OOM recovery: every HBM prefix entry spills to the host tier,
    # then drops (spill-then-drop). jax.clear_caches() inside recovery also
    # drops compiled executables — re-warm on a fresh distinct prefix so
    # the host-warm TTFT measures the H2D restore, not recompilation.
    engine._free_device_memory()
    host_stats = engine.host_kv_stats() or {"bytes": 0, "entries": 0}
    # jax.clear_caches() inside recovery dropped every compiled executable:
    # re-warm on the WARMUP prefix — which is itself in the host tier now,
    # so this run exercises the full restore machinery (scatter jit, warm
    # suffix prefill, decode) and the measured run below pays only the
    # actual H2D restore, not recompilation.
    await generate("kvhost-rewarm", pattern(1))
    hits0, fetch0 = engine._host_kv_hits, engine._host_fetch_bytes
    host_ttft, host_toks = await generate("kvhost-host", pattern(0))
    _record("kvhost:host", ttft_s=host_ttft,
            host_entries=host_stats["entries"], host_hits=engine._host_kv_hits)

    n_cmp = min(len(cold_toks), len(hbm_toks), len(host_toks), 32)
    verified = bool(n_cmp > 0 and cold_toks[:n_cmp] == hbm_toks[:n_cmp] == host_toks[:n_cmp])
    return {
      "kvhost_prefill_len": prefill_len,
      "kvhost_cold_ttft_s": cold_ttft,
      "kvhost_hbm_ttft_s": hbm_ttft,
      "kvhost_host_ttft_s": host_ttft,
      # The acceptance shape: HBM-warm <= host-warm <= cold. Recorded, not
      # gated: host-clock TTFTs of one request each are too noisy to fail on.
      "kvhost_ordering_ok": bool(hbm_ttft <= host_ttft <= cold_ttft),
      "kvhost_tokens_verified": verified,
      "kvhost_host_entries_after_free": host_stats["entries"],
      "kvhost_host_bytes_after_free": host_stats["bytes"],
      # Measured-run deltas: exactly one host hit whose fetched bytes are
      # the restored prefix entry — the e2e observability the /metrics
      # counters expose in production.
      "kvhost_host_hits": int(engine._host_kv_hits - hits0),
      "kvhost_fetch_bytes": int(engine._host_fetch_bytes - fetch0),
      "kvhost_spill_bytes": int(engine._host_spill_bytes),
      "kvhost_oom_recoveries": int(engine._oom_count),
    }

  # The tier must be ON for this stage regardless of ambient env; prefix
  # caching likewise (it is the thing being spilled/restored).
  prev = {k: os.environ.get(k) for k in ("XOT_KV_HOST_BYTES", "XOT_PREFIX_CACHE")}
  try:
    if int(os.environ.get("XOT_KV_HOST_BYTES") or 0) <= 0:
      os.environ["XOT_KV_HOST_BYTES"] = str(1 << 30)
    if int(os.environ.get("XOT_PREFIX_CACHE") or 2) <= 0:
      os.environ["XOT_PREFIX_CACHE"] = "2"
    return asyncio.run(run())
  finally:
    for k, v in prev.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v


def _run_fabric(model_id: str, prefill_len: int, decode_tokens: int) -> dict:
  """Cold vs fabric-warm TTFT A/B (the `fabric` stage): TWO
  engines in one process stand in for two replicas — engine A prefills a
  prompt and spills it to its host tier; engine B, whose fabric client is
  wired straight to A's store through the REAL pack/serve/unpack/digest
  path (no sockets — the serialize + verify + import + H2D restore cost is
  what's measured; the wire itself is the soak's job), serves the same
  prompt after an offer lands. The fabric-warm TTFT must beat B's cold
  TTFT on an equal-length prompt, B's greedy stream must be byte-identical
  to A's (a fabric that changes tokens is corrupting caches), and the
  paged zero bars hold (the import rides the normal host-restore path)."""
  import asyncio

  import numpy as np

  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.inference.shard import Shard
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.registry import model_cards

  n_layers = config_from_hf_dict(model_cards[model_id]["synthetic_config"]).num_layers

  # Token-level prompts for the same reason as the kvhost stage: the
  # synthetic tokenizer collapses word-varied prompts onto one id stream.
  def pattern(seed: int) -> np.ndarray:
    return ((np.arange(prefill_len) * (seed * 2 + 3) + seed) % 200 + 3)[None, :].astype(np.int64)

  def wire(eng_b, eng_a) -> None:
    """B's fabric transport -> A's host store, through the real server
    surface (fabric_server.match_response / serve_entry)."""
    import json as _json

    from xotorch_tpu.fabric import server as fabric_server
    client = eng_b._fabric_client(create=True)

    def post_json(url: str, body: dict) -> dict:
      resp = fabric_server.match_response(
        eng_a._host_kv, Shard(model_id, 0, n_layers - 1, n_layers),
        np.asarray(body["toks"], dtype=np.int64), int(body["limit"]))
      return _json.loads(_json.dumps(resp))

    def get_bytes(url: str) -> bytes:
      blob = fabric_server.serve_entry(eng_a._host_kv, url.rsplit("/", 1)[-1].split("?")[0])
      if blob is None:
        raise OSError(f"no entry for {url}")
      return blob

    client._post_json = post_json
    client._get_bytes = get_bytes

  async def run() -> dict:
    shard = Shard(model_id, 0, n_layers - 1, n_layers)
    eng_a = JAXShardInferenceEngine()
    eng_b = JAXShardInferenceEngine()

    async def generate(engine, rid: str, prompt: np.ndarray):
      t0 = time.monotonic()
      tok, _ = await engine.infer_sample_tensor(rid, shard, prompt, temp=0.0)
      ttft = time.monotonic() - t0
      toks = [int(tok)]
      for _ in range(max(1, decode_tokens // 16)):
        out = await engine.generate_chunk(rid, shard, toks[-1], 16, temp=0.0)
        toks.extend(int(t) for t in out)
      await engine.clear_request(rid)
      return round(ttft, 3), toks

    # Replica A: prefill the measured prompt, spill it to A's host tier.
    _, a_toks = await generate(eng_a, "fabric-src", pattern(0))
    eng_a._free_device_memory()
    src_stats = eng_a.host_kv_stats() or {"bytes": 0, "entries": 0}
    _record("fabric:spilled", **src_stats)

    # Replica B: compile both shapes (cold prefill + prefix-hit suffix
    # prefill) on a distinct prefix, then measure cold on ANOTHER distinct
    # equal-length prompt — B must never have seen pattern(0) cold, or the
    # warm run below would hit B's own prefix cache instead of the fabric.
    await generate(eng_b, "fabric-warmexe", pattern(1))
    await generate(eng_b, "fabric-warmexe2", pattern(1))
    cold_ttft, _ = await generate(eng_b, "fabric-cold", pattern(2))
    _record("fabric:cold", ttft_s=cold_ttft)

    # The offer lands (router-chain shape), transport wired to A's store.
    wire(eng_b, eng_a)
    toks0 = [int(t) for t in pattern(0)[0]]
    assert eng_b.fabric_offer(shard, toks0, len(toks0),
                              int(src_stats["bytes"]), "http://bench-peer-a")
    hits0, bytes0 = eng_b._fabric_hits, eng_b._fabric_bytes
    warm_ttft, warm_toks = await generate(eng_b, "fabric-warm", pattern(0))
    _record("fabric:warm", ttft_s=warm_ttft,
            hits=eng_b._fabric_hits - hits0)

    n_cmp = min(len(a_toks), len(warm_toks), 32)
    verified = bool(n_cmp > 0 and a_toks[:n_cmp] == warm_toks[:n_cmp])
    return {
      "fabric_prefill_len": prefill_len,
      "fabric_cold_ttft_s": cold_ttft,
      "fabric_warm_ttft_s": warm_ttft,
      # Recorded, not gated (single-request host-clock noise), same as
      # kvhost_ordering.
      "fabric_ordering_ok": bool(warm_ttft <= cold_ttft),
      "fabric_speedup": round(cold_ttft / warm_ttft, 3) if warm_ttft else None,
      "fabric_tokens_verified": verified,
      "fabric_hits": int(eng_b._fabric_hits - hits0),
      "fabric_fetch_bytes": int(eng_b._fabric_bytes - bytes0),
      "fabric_errors": int(eng_b._fabric_errors),
      "fabric_src_entries": int(src_stats["entries"]),
    }

  prev = {k: os.environ.get(k) for k in ("XOT_KV_HOST_BYTES", "XOT_PREFIX_CACHE")}
  try:
    if int(os.environ.get("XOT_KV_HOST_BYTES") or 0) <= 0:
      os.environ["XOT_KV_HOST_BYTES"] = str(1 << 30)
    if int(os.environ.get("XOT_PREFIX_CACHE") or 2) <= 0:
      os.environ["XOT_PREFIX_CACHE"] = "2"
    return asyncio.run(run())
  finally:
    for k, v in prev.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v


def _find_real_model() -> "tuple[str, str] | None":
  """(model_id, dir) of a REAL downloaded checkpoint, if one exists on disk.

  Looked up from XOT_REAL_MODEL_DIR (+ XOT_REAL_MODEL_ID, default
  llama-3.2-1b), then $XOT_MODEL_DIR/<id> and the downloader's default
  XOT_HOME layout. Zero-egress containers without weights simply skip the
  stage; the moment weights are present it runs with no flag flips
  (VERDICT r3 #3)."""
  candidates = []
  model_id = os.getenv("XOT_REAL_MODEL_ID", "llama-3.2-1b")
  explicit = os.getenv("XOT_REAL_MODEL_DIR")
  if explicit:
    candidates.append((model_id, Path(explicit)))
  root = os.getenv("XOT_MODEL_DIR")
  if root:
    candidates.append((model_id, Path(root) / model_id))
  home = Path(os.getenv("XOT_HOME", Path.home() / ".xotorch")) / "models"
  if home.is_dir():
    for d in sorted(home.iterdir()):
      candidates.append((d.name, d))
  for mid, d in candidates:
    try:
      if d.is_dir() and any(d.glob("*.safetensors")) and (d / "config.json").exists():
        return mid, str(d)
    except OSError:
      continue
  return None


def _run_real_model(decode_tokens: int = 64) -> dict:
  """Serve a REAL checkpoint end to end (weights.py HF remap + real
  tokenizer + engine + Node) and report tok/s plus a text sanity signal.
  Runs only when _find_real_model found weights on disk."""
  import asyncio

  found = _find_real_model()
  if found is None:
    return {}
  model_id, model_dir = found
  _record("real_model:found", model_id=model_id, dir=model_dir)

  from xotorch_tpu.download.shard_download import LocalShardDownloader
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.inference.shard import Shard
  from xotorch_tpu.orchestration.node import Node
  from xotorch_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy

  async def run() -> dict:
    engine = JAXShardInferenceEngine(LocalShardDownloader({model_id: model_dir}))
    node = Node("bench-real", _NullServer(), engine, _NoDiscovery(), None,
                RingMemoryWeightedPartitioningStrategy(),
                max_generate_tokens=decode_tokens, default_sample_temp=0.0)
    node.device_capabilities = _bench_caps()
    node.topology.update_node(node.id, node.device_capabilities)
    import json as _json
    n_layers = _json.loads((Path(model_dir) / "config.json").read_text()).get("num_hidden_layers")
    shard = Shard(model_id, 0, n_layers - 1, n_layers)
    prompt = "The capital of France is"

    async def generate(tag: str) -> dict:
      return await _timed_generate([node], shard, prompt, tag)

    warm = await generate("real-warm")
    _record("real_model:warmup", tok_s=round(warm["tok_s"], 2))
    timed = await generate("real-timed")
    text = await engine.decode(shard, __import__("numpy").asarray(timed["tokens"]))
    printable = sum(c.isprintable() or c.isspace() for c in text) / max(1, len(text))
    distinct = len(set(timed["tokens"])) / max(1, len(timed["tokens"]))
    return {
      "real_model_id": model_id,
      "real_model_tok_s": round(timed["tok_s"], 2),
      "real_model_ttft_ms": round(timed["ttft_s"] * 1000, 1),
      "real_model_n_tokens": len(timed["tokens"]),
      "real_model_text": text[:160],
      # Text sanity: a real checkpoint produces printable, non-degenerate
      # text; random/broken weights produce byte salad or one repeated id.
      "real_model_text_plausible": bool(printable > 0.9 and distinct > 0.15),
    }

  return asyncio.run(run())


def main() -> None:
  prefill_len = int(os.getenv("BENCH_PREFILL", "128"))
  decode_tokens = int(os.getenv("BENCH_DECODE", "128"))
  # 64 = the serving ladder's steady-state cap (node.max_decode_chunk_size
  # default): the bench chunk mirrors what a long generation actually runs.
  chunk = int(os.getenv("BENCH_CHUNK", "64"))
  cache_len = int(os.getenv("BENCH_CACHE_LEN", "1024"))
  model_id = os.getenv("BENCH_MODEL", "synthetic-llama-1b")

  t0 = time.time()
  import jax
  devices = jax.devices()  # backend init; a failure raises and ends the run
  d0 = devices[0]
  if d0.platform != "tpu":
    sys.exit(f"bench.py measures on a TPU and JAX found platform={d0.platform!r} "
             f"(JAX_PLATFORMS={os.getenv('JAX_PLATFORMS', '')!r}): no chip, no number")
  _tpu_peaks(devices)  # a device_kind the peak table does not know ends the run here
  from xotorch_tpu.utils import compile_cache
  _record("init", platform=d0.platform, n_devices=len(devices), device_kind=d0.device_kind,
          compile_cache=compile_cache.enable(), secs=round(time.time() - t0, 1))

  calib = _calibrate_sync()
  # The async (block_until_ready-only) timing variants double the workload;
  # they are only informative when calibration showed b_u_r is broken.
  measure_async = (not calib["block_until_ready_ok"]) or os.getenv("BENCH_ASYNC", "0") == "1"

  res = _run_config(model_id, prefill_len, decode_tokens, chunk, cache_len,
                    "flagship", measure_async, long_stage=True)
  res["block_until_ready_ok"] = calib["block_until_ready_ok"]
  # int8 weight-only flagship (decode is HBM-bound at batch 1, so halving
  # resident bytes ~doubles the roofline). BENCH_QUANT="" disables.
  quant = os.getenv("BENCH_QUANT", "int8")
  if quant:
    res["quant_fmt"] = quant  # _emit keys the field pass-through off this
    qres = _run_config(model_id, prefill_len, decode_tokens, chunk, cache_len,
                       "flagship-int8", measure_async, quantize=quant, long_stage=True)
    res.update({
      f"{quant}_tok_s": qres["tok_s"],
      f"{quant}_per_token_ms": qres["per_token_ms"],
      f"{quant}_ttft_ms": qres["ttft_ms"],
      f"{quant}_hbm_bw_pct": qres["hbm_bw_pct"],
      f"{quant}_roofline_tok_s": qres["roofline_tok_s"],
      f"{quant}_tokens_verified": qres["tokens_verified"],
      f"{quant}_speedup": round(qres["tok_s"] / res["tok_s"], 2) if res.get("tok_s") else None,
      f"{quant}_implausible": qres["implausible"],
      f"{quant}_long_tok_s": qres.get("long_tok_s"),
      f"{quant}_long_prefill_s": qres.get("long_prefill_s"),
    })
    if qres.get("diagnosis"):
      res[f"{quant}_diagnosis"] = qres["diagnosis"]
  # Every stage below is a stage the run asked for: one that raises ends the
  # run non-zero with its error instead of riding along as an `*_error` field.
  if os.getenv("BENCH_RING", "2") == "2":
    res.update(_run_ring2(model_id, prefill_len, min(decode_tokens, 32)))
  n_conc = int(os.getenv("BENCH_CONCURRENT", "8") or 0)
  if n_conc > 1:
    res.update(_run_concurrent(model_id, min(prefill_len, 64), decode_tokens, n_conc))
  # Prefill-interference stage (opt-in: BENCH_PAGEDFILL=1): long-prompt
  # prefill under N decode streams, TTFT + decode-stall p50, co-scheduled vs
  # monolithic, streams cross-checked.
  if os.getenv("BENCH_PAGEDFILL", "0") == "1":
    pf_prefill = int(os.getenv("BENCH_PAGEDFILL_PREFILL", "16384"))
    pf_decode = int(os.getenv("BENCH_PAGEDFILL_DECODE", "256"))
    res.update(_run_prefill_interference(model_id, pf_prefill, pf_decode, max(2, n_conc or 8)))
    # The paged-prefill/co-scheduling token stream feeds the same
    # measurement-integrity gate as the fused/per-token cross-check: a
    # scheduler that changes tokens is lying about its numbers.
    if res.get("pagedfill_tokens_verified") is False:
      _flag_implausible(res, "co-scheduled vs monolithic prefill token streams disagree")
  # Host-tier KV offload stage (opt-in: BENCH_KVHOST=1): cold vs HBM-warm vs
  # host-warm TTFT for one prompt, the host-warm run restored from a forced
  # _free_device_memory spill.
  if os.getenv("BENCH_KVHOST", "0") == "1":
    kh_prefill = int(os.getenv("BENCH_KVHOST_PREFILL", "2048"))
    res.update(_run_kv_host(model_id, kh_prefill, min(decode_tokens, 64)))
    # A KV tier that changes greedy tokens is corrupting caches, and its
    # timings are meaningless.
    if res.get("kvhost_tokens_verified") is False:
      _flag_implausible(res, "cold vs HBM-warm vs host-warm token streams disagree")
  # Cross-replica KV fabric stage (opt-in: BENCH_FABRIC=1): cold vs
  # fabric-warm TTFT with two engines standing in for two replicas, the warm
  # run importing the prefix through the real pack/digest/import path.
  if os.getenv("BENCH_FABRIC", "0") == "1":
    fb_prefill = int(os.getenv("BENCH_FABRIC_PREFILL", "2048"))
    res.update(_run_fabric(model_id, fb_prefill, min(decode_tokens, 64)))
    if res.get("fabric_tokens_verified") is False:
      _flag_implausible(res, "source vs fabric-warm greedy token streams disagree")
  # Speculative-decoding stage (opt-in: a repeat-heavy prompt through the
  # Node loop with XOT_SPECULATE on vs off, streams cross-checked).
  if os.getenv("BENCH_SPEC", "0") == "1":
    res.update(_run_spec(model_id, min(prefill_len, 128), decode_tokens))
  # Mesh (tensor-parallel serving) stage (opt-in: BENCH_MESH=1): XOT_TP on vs
  # off through the Node loop, greedy streams cross-checked byte for byte.
  if os.getenv("BENCH_MESH", "0") == "1":
    res.update(_run_mesh(model_id, min(prefill_len, 128), decode_tokens))
    if res.get("mesh_tokens_verified") is False:
      _flag_implausible(res, "tp-mesh vs single-device greedy token streams disagree")
  # Virtual-KV A/B stage (opt-in: BENCH_VKV=1): paged int8-KV vs contiguous
  # int8-KV vs paged bf16, int8 streams byte-identical, both paged arms at
  # zero unpage/commit-copy.
  if os.getenv("BENCH_VKV", "0") == "1":
    res.update(_run_vkv(model_id, min(prefill_len, 128), decode_tokens))
    if res.get("vkv_tokens_verified") is False:
      _flag_implausible(res, "paged int8 vs contiguous int8 greedy token streams disagree")
    # The zero bar is measurement integrity too: a "paged" number that
    # secretly gathered the cache back measured the contiguous path.
    if res.get("vkv_unpage_calls", 0) or res.get("vkv_commit_copy_bytes", 0):
      _flag_implausible(res, "paged vkv arms gathered pages back (nonzero unpage/commit-copy)")
  # Real-checkpoint stage: runs whenever actual downloaded weights are on
  # disk (zero-egress containers without them skip).
  res.update(_run_real_model())
  _emit(_apply_baseline(res))


def _flag_implausible(res: dict, why: str) -> None:
  res["implausible"] = True
  res["diagnosis"] = "; ".join(filter(None, [res.get("diagnosis"), why]))


def _apply_baseline(result: dict) -> dict:
  """vs_baseline per (model, platform, method); first PLAUSIBLE run records
  the bar. An implausible result (over-roofline throughput or failed token
  cross-check) never becomes the baseline — that is how round 2's 147x-over-
  physics number poisoned BENCH_BASELINE.json (ADVICE r2 high)."""
  baseline_file = REPO / "BENCH_BASELINE.json"
  baselines = {}
  if baseline_file.exists():
    try:
      baselines = json.loads(baseline_file.read_text())
    except json.JSONDecodeError:
      baselines = {}
  key = f"{result['model_id']}:{result['platform']}:fused"
  baseline = baselines.get(key, {}).get("tok_s")
  if result.get("implausible"):
    result["vs_baseline"] = round(result["tok_s"] / baseline, 3) if baseline else 0.0
    return result
  if os.getenv("BENCH_NO_BASELINE", "0") == "1":
    # Ad-hoc runs must not write throwaway configs in as the bar.
    result["vs_baseline"] = round(result["tok_s"] / baseline, 3) if baseline else 1.0
    return result
  if baseline is None:
    baseline = result["tok_s"]
    baselines[key] = {
      "tok_s": result["tok_s"], "per_token_ms": result["per_token_ms"],
      "ttft_ms": result["ttft_ms"], "recorded": time.strftime("%Y-%m-%d"),
    }
    try:
      baseline_file.write_text(json.dumps(baselines, indent=2))
    except OSError:
      pass
  result["vs_baseline"] = round(result["tok_s"] / baseline, 3) if baseline else 1.0
  return result


def _emit(result: dict) -> None:
  model_id = result.get("model_id", "unknown")
  out = {
    "metric": f"decode_tok_s_{model_id.replace('-', '_')}_bf16_1chip",
    "value": result.get("tok_s", 0.0),
    "unit": "tok/s",
    "vs_baseline": result.get("vs_baseline", 0.0),
  }
  for k in ("per_token_ms", "ttft_ms", "per_token_path_tok_s", "fused_speedup",
            "fused_seq_tok_s", "overlap_tokens_match",
            "long_ctx", "long_prefill_s", "long_tok_s",
            "async_tok_s", "async_divergence", "tokens_verified", "tokens_agree_prefix",
            "implausible", "diagnosis", "block_until_ready_ok", "roofline_tok_s",
            "ring2_tok_s", "ring2_per_token_ms", "ring2_ttft_ms",
            "ring2_pertoken_tok_s", "ring2_fused_speedup", "ring2_tokens_verified",
            "ring2_n_tokens", "long_prefill_tok_s", "prefill_mfu_pct", "prefill_mode",
            "spec_tok_s", "spec_off_tok_s", "spec_speedup", "spec_proposed",
            "spec_accepted", "spec_accept_rate", "spec_tokens_verified",
            "real_model_id", "real_model_tok_s", "real_model_ttft_ms",
            "real_model_n_tokens", "real_model_text", "real_model_text_plausible",
            "concurrent_n", "concurrent_tok_s", "single_stream_tok_s",
            "concurrency_speedup", "concurrent_max_batch_width",
            "mfu_pct", "hbm_bw_pct", "platform", "n_devices", "device_kind",
            "n_params", "param_bytes", "stage",
            "predicted_weight_bytes", "predicted_weight_match",
            "predicted_decode_bytes_per_tok", "predicted_flops_per_tok",
            "predicted_hbm_util_pct", "predicted_mfu_pct"):
    if result.get(k) is not None:
      out[k] = result[k]
  # Quantized-flagship fields (int8_tok_s, int8_speedup, ...) pass through
  # as a family keyed off the measured format. The
  # pagedfill_* (prefill-interference A/B), kv_* (page-pool observability)
  # and specpaged_* (paged speculative-decode A/B) families ride the same
  # mechanism.
  prefixes = set(QUANT_PREFIXES) | {"pagedfill", "kv", "specpaged"}
  if result.get("quant_fmt"):
    out["quant_fmt"] = result["quant_fmt"]
    prefixes.add(result["quant_fmt"])
  for k, v in result.items():
    if k.split("_", 1)[0] in prefixes and v is not None:
      out[k] = v
  print(json.dumps(out), flush=True)


if __name__ == "__main__":
  main()
