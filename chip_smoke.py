#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts on the chip.

    python3 chip_smoke.py            one TPU chip (how the driver runs it)
    python3 chip_smoke.py --chips 4  the four-chip tp mesh against one device, nothing else
    python3 chip_smoke.py --rehearse tiny sizes on whatever JAX finds (control flow only;
                                     never prints the ok line, exits 3)

Drives `xot` CLI -> HTTP API -> Node -> _DecodeBatcher -> JAXShardInferenceEngine ->
forward_shard/decode_chunk -> Pallas kernels once, at the full width and depth of
`synthetic-llama-1b` (seeded random weights, no download), and checks what comes out.

One process per chip: this parent never imports jax. Each phase is a child that holds
the chip alone and has exited before the next starts; device facts come from the
`device` child's report and from the server's own endpoints.

  device   platform / device_kind / count, jax / jaxlib / libtpu versions, compile-cache dir
  kernels  every selectable Pallas kernel, compiled (interpret=False) at the model's widths,
           against its plain jax.numpy counterpart: max abs error printed, tolerance enforced
  logits   engine-path prefill logits vs the same weights through forward_shard with XLA
           attention in float32; the compiled executables contain the kernels; the plain
           greedy path is deterministic
  serve    `python -m xotorch_tpu.main` over HTTP: non-streaming, streaming, the same greedy
           request twice, 8 concurrent, one ~6 k-token prompt; then the server's own report
           (/v1/topology, /metrics, /v1/perf, /v1/debug/flight) must say TPU, the selected
           kernels, a first-dispatch counter that stopped growing, 0 failures

The LAST stdout line is `{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`
only if every phase passed on a TPU; any failure (or no accelerator) exits non-zero with no
such line. Needs no network, calls no git, stops every process it starts.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
RESULT_TAG = "PHASE_RESULT "
PORTS = {"api": 52615, "node": 52616, "listen": 52617, "broadcast": 52618}


class Sizes:
  """What a run is sized to. The real run is the published width and depth of
  synthetic-llama-1b; --rehearse shrinks everything so the control flow can be
  walked on CPU with the kernels interpreted."""

  def __init__(self, rehearse: bool):
    self.rehearse = rehearse
    self.model = "synthetic-tiny" if rehearse else "synthetic-llama-1b"
    self.prompt = 24 if rehearse else 126  # words; the chat template adds 2 -> ~128 tokens
    self.long_prompt = 300 if rehearse else 6000
    self.new_tokens = 16 if rehearse else 64
    self.fingerprint_tokens = 16
    # Server-side knobs only the rehearsal sets (a user's server runs the defaults):
    # thresholds scaled down with the prompt, kernels forced on in interpret mode.
    self.server_env = ({"XOT_PREFILL_CHUNK": "128", "XOT_FLASH_DECODE_MIN": "128",
                        "XOT_CACHE_LEN": "64", "XOT_FLASH_ATTENTION": "1",
                        "XOT_FLASH_DECODE": "1"} if rehearse else {})


def log(msg: str) -> None:
  print(msg, flush=True)


# ----------------------------------------------------------------- parent side


def child_env(extra: dict | None = None) -> dict:
  env = dict(os.environ)
  env["PYTHONPATH"] = str(HERE) + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
  env["PYTHONUNBUFFERED"] = "1"
  env.setdefault("TPU_LOG_DIR", "disabled")
  env.update(extra or {})
  return env


def kill_group(proc: subprocess.Popen) -> None:
  """SIGTERM, then SIGKILL, the child's whole process group."""
  if proc.poll() is not None:
    return
  for sig, wait in ((signal.SIGTERM, 15), (signal.SIGKILL, 10)):
    try:
      os.killpg(proc.pid, sig)
    except ProcessLookupError:
      return
    try:
      proc.wait(timeout=wait)
      return
    except subprocess.TimeoutExpired:
      continue


def run_phase(name: str, flags: list, timeout: float) -> dict:
  """Run one phase as a child that owns the chip; echo its lines; return its
  PHASE_RESULT. Raises on a non-zero exit, a timeout or a missing result."""
  log(f"\n===== phase {name} =====")
  t0 = time.time()
  proc = subprocess.Popen(
    [sys.executable, str(HERE / "chip_smoke.py"), "--phase", name, *flags],
    stdout=subprocess.PIPE, text=True, cwd=str(HERE), env=child_env(), start_new_session=True)
  timer = threading.Timer(timeout, kill_group, args=(proc,))
  timer.start()
  result = None
  try:
    for line in proc.stdout:
      line = line.rstrip("\n")
      if line.startswith(RESULT_TAG):
        result = json.loads(line[len(RESULT_TAG):])
      else:
        log(f"  {line}")
    rc = proc.wait()
  finally:
    timer.cancel()
    kill_group(proc)
  secs = time.time() - t0
  if rc != 0 or result is None or not result.get("ok"):
    raise RuntimeError(f"phase {name} failed (exit code {rc}, {secs:.0f}s"
                       f"{', no result line' if result is None else ''})")
  log(f"  phase {name}: ok in {secs:.1f}s")
  return result


def http_json(path: str, body: dict | None = None, timeout: float = 900.0):
  url = f"http://127.0.0.1:{PORTS['api']}{path}"
  data = json.dumps(body).encode() if body is not None else None
  req = urllib.request.Request(url, data=data, headers={"Content-Type": "application/json"})
  with urllib.request.urlopen(req, timeout=timeout) as r:
    return json.loads(r.read())


def http_text(path: str, timeout: float = 30.0) -> str:
  with urllib.request.urlopen(f"http://127.0.0.1:{PORTS['api']}{path}", timeout=timeout) as r:
    return r.read().decode()


def metric(text: str, name: str) -> float:
  """A counter/gauge from Prometheus text, summed over its label sets; 0 if absent."""
  total = 0.0
  for line in text.splitlines():
    if line.startswith(name) and line[len(name):len(name) + 1] in (" ", "{"):
      total += float(line.rsplit(" ", 1)[1])
  return total


def chat_body(sz: Sizes, words: int, stream: bool = False, fingerprint: bool = False) -> dict:
  body = {"model": sz.model, "temperature": 0, "max_tokens": sz.new_tokens, "stream": stream,
          "messages": [{"role": "user", "content": " ".join(["smoke"] * words)}]}
  if fingerprint:
    # Token ids are not on the wire and the dummy tokenizer decodes every id to the
    # same word, so a greedy stream is fingerprinted by its per-token logprobs. Such
    # requests decode through their own (logprob-reporting) executables — each costs
    # a compile, so the pair stays short.
    body.update(logprobs=True, max_tokens=sz.fingerprint_tokens)
  return body


def complete(sz: Sizes, words: int, fingerprint: bool = False) -> dict:
  """One non-streaming completion -> {secs, completion_tokens, prompt_tokens, fingerprint}."""
  t0 = time.time()
  out = http_json("/v1/chat/completions", chat_body(sz, words, fingerprint=fingerprint))
  secs = time.time() - t0
  if "error" in out:
    raise RuntimeError(f"completion failed: {out['error']}")
  choice = out["choices"][0]
  fp = None
  if fingerprint:
    fp = [round(t["logprob"], 6) for t in choice["logprobs"]["content"]]
  return {"secs": secs, "completion_tokens": out["usage"]["completion_tokens"],
          "prompt_tokens": out["usage"]["prompt_tokens"], "finish": choice["finish_reason"],
          "fingerprint": fp}


def stream_complete(sz: Sizes, words: int) -> dict:
  """One SSE completion -> time to first content chunk, chunks, total seconds."""
  url = f"http://127.0.0.1:{PORTS['api']}/v1/chat/completions"
  req = urllib.request.Request(url, data=json.dumps(chat_body(sz, words, stream=True)).encode(),
                               headers={"Content-Type": "application/json"})
  t0 = time.time()
  ttft = None
  pieces, finish, done = 0, None, False
  with urllib.request.urlopen(req, timeout=900.0) as r:
    for raw in r:
      line = raw.decode().strip()
      if not line.startswith("data:"):
        continue
      payload = line[5:].strip()
      if payload == "[DONE]":
        done = True
        break
      chunk = json.loads(payload)
      if "error" in chunk:
        raise RuntimeError(f"stream failed: {chunk['error']}")
      choice = chunk["choices"][0]
      if (choice.get("delta") or {}).get("content"):
        pieces += len(choice["delta"]["content"].split())
        if ttft is None:
          ttft = time.time() - t0
      finish = choice.get("finish_reason") or finish
  return {"ttft": ttft, "words": pieces, "secs": time.time() - t0, "finish": finish, "done": done}


def check(cond: bool, what: str) -> None:
  if not cond:
    raise AssertionError(what)
  log(f"  ok: {what}")


def cache_census() -> tuple:
  """(files, bytes) in the persistent compile cache the children use."""
  d = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or HERE / ".jax_cache")
  files = [p for p in d.rglob("*") if p.is_file()] if d.is_dir() else []
  return len(files), sum(p.stat().st_size for p in files)


def serve_phase(sz: Sizes, device: dict) -> None:
  """Start the server the way a user does and drive it over HTTP."""
  log("\n===== phase serve =====")
  label = f"[{device['platform']} {device['kind']} x{device['count']}]"
  out_dir = HERE / "chiprun_out"
  out_dir.mkdir(exist_ok=True)
  log_path = out_dir / "chip_smoke_server.log"
  cmd = [sys.executable, "-m", "xotorch_tpu.main", "--inference-engine", "jax",
         "--default-model", sz.model, "--disable-tui",
         "--chatgpt-api-port", str(PORTS["api"]), "--node-port", str(PORTS["node"]),
         "--listen-port", str(PORTS["listen"]), "--broadcast-port", str(PORTS["broadcast"]),
         "--chatgpt-api-response-timeout", "900"]
  log(f"  $ {' '.join(cmd[1:])}")
  t_start = time.time()
  with open(log_path, "w") as logf:
    server = subprocess.Popen(cmd, stdout=logf, stderr=subprocess.STDOUT, cwd=str(HERE),
                              env=child_env(sz.server_env), start_new_session=True)
  try:
    while True:
      if server.poll() is not None:
        raise RuntimeError(f"server exited with code {server.returncode} before it answered")
      try:
        if http_json("/healthcheck", timeout=2.0).get("status") == "ok":
          break
      except (urllib.error.URLError, OSError, json.JSONDecodeError):
        pass
      if time.time() - t_start > 300:
        raise RuntimeError("server did not answer /healthcheck within 300 s")
      time.sleep(0.5)
    log(f"  server up in {time.time() - t_start:.1f}s")

    # -- the node says which device it serves from
    topo = http_json("/v1/topology")
    caps = list(topo["nodes"].values())
    check(len(caps) == 1, f"one node in /v1/topology ({[c['model'] for c in caps]})")
    if not sz.rehearse:
      check("TPU" in caps[0]["chip"] and caps[0]["num_devices"] == device["count"],
            f"node capabilities name the TPU, not the host CPU: {caps[0]['model']!r}, "
            f"{caps[0]['memory']} MB")

    # -- warm-up: the first request loads the model and pays the compiles
    cold = complete(sz, sz.prompt)
    check(cold["completion_tokens"] == sz.new_tokens and cold["finish"] == "length",
          f"non-streaming completion: {cold['prompt_tokens']} prompt tokens -> "
          f"{cold['completion_tokens']} new tokens ({cold['secs']:.1f}s cold, compiles included)")
    s_warm = stream_complete(sz, sz.prompt)
    check(s_warm["done"] and s_warm["words"] == sz.new_tokens and s_warm["ttft"] is not None,
          f"streaming completion: {s_warm['words']} tokens over SSE, [DONE] seen")

    # -- the same greedy request twice: identical tokens
    a = complete(sz, sz.prompt, fingerprint=True)
    b = complete(sz, sz.prompt, fingerprint=True)
    check(a["fingerprint"] is not None and len(a["fingerprint"]) == sz.fingerprint_tokens
          and a["fingerprint"] == b["fingerprint"],
          f"same greedy request twice: {len(a['fingerprint'])} identical token logprobs")

    # -- 8 concurrent requests: the batcher forms a real batch
    results, errors = [], []

    def one():
      try:
        results.append(complete(sz, sz.prompt))
      except Exception as e:  # collected; the check below fails the phase
        errors.append(repr(e))

    t0 = time.time()
    threads = [threading.Thread(target=one) for _ in range(8)]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    batch_secs = time.time() - t0
    check(not errors and len(results) == 8
          and all(r["completion_tokens"] == sz.new_tokens for r in results),
          f"8 concurrent requests all completed {sz.new_tokens} tokens {errors or ''}")
    flight = http_json("/v1/debug/flight?live=all")["events"]
    widths = [e["batch"] for e in flight if e["event"] == "batcher.dispatch"]
    check(max(widths) >= 2, f"_DecodeBatcher formed a real batch (widest dispatch: {max(widths)} rows)")

    # -- one long prompt: chunked prefill, cache growth, cached-prefill + flash-decode kernels
    long_ = complete(sz, sz.long_prompt)
    check(long_["completion_tokens"] == sz.new_tokens,
          f"long prompt: {long_['prompt_tokens']} prompt tokens -> {long_['completion_tokens']} "
          f"new tokens in {long_['secs']:.1f}s (compiles included)")

    # -- warmed shapes repeat without a new executable
    m0 = http_text("/metrics")
    first0 = metric(m0, "xot_jit_first_dispatch_total")
    seq = complete(sz, sz.prompt)
    s_run = stream_complete(sz, sz.prompt)
    m1 = http_text("/metrics")
    first1 = metric(m1, "xot_jit_first_dispatch_total")
    check(first1 == first0 and first0 > 0,
          f"xot_jit_first_dispatch_total stopped growing after warm-up ({first0:.0f} -> {first1:.0f}; "
          f"{metric(m1, 'xot_jit_cached_dispatch_total'):.0f} cached dispatches)")

    # -- the server's own report
    failed = metric(m1, "xot_requests_failed_total")
    aborts = metric(m1, "xot_watchdog_aborts_total")
    total = metric(m1, "xot_requests_total")
    check(failed == 0 and aborts == 0 and total >= 14,
          f"server counted {total:.0f} requests, {failed:.0f} failed, {aborts:.0f} watchdog aborts")
    flight = http_json("/v1/debug/flight?live=all")["events"]
    compiles = [e for e in flight if e["event"] == "engine.compile"]
    log("  executables first dispatched (engine.compile flight events; seconds include the compile):")
    for c in compiles:
      log(f"    {c['kind']:8s} {c['secs']:8.2f}s  key={c['key']}")
    kernels_of = lambda kind: {k for c in compiles if c["kind"] == kind for k in c["key"][-1]}
    check("flash_prefill" in kernels_of("prefill"),
          "a prefill executable was built with the flash prefill kernel (from-zero segment)")
    check("flash_cached" in kernels_of("prefill"),
          "a prefill executable was built with the cached-attention kernel (segment over a resident cache)")
    check("flash_cached" in kernels_of("decode"),
          "a decode executable was built with the cached-attention (flash decode) kernel")
    perf = http_json("/v1/perf")
    check(perf["dispatch"]["jit_first_dispatches"] == first1
          and perf["model"]["model_id"] == sz.model
          and perf["model"]["weight_bytes_actual"] == perf["model"]["weight_bytes_predicted"],
          f"/v1/perf: {perf['model']['n_params'] / 1e9:.3f} B params, "
          f"{perf['model']['weight_bytes_actual'] / 2**30:.2f} GiB of weights resident as predicted")
    if not sz.rehearse:
      check(perf["ceilings"] is not None and perf["gauges"].get("hbm_util_pct", 0) >= 0,
            "/v1/perf carries roofline ceilings from the device_kind peak table")

    # -- information, not a claim
    log(f"  {label} time to first token ({cold['prompt_tokens']}-token prompt, warm): "
        f"{s_run['ttft'] * 1000:.0f} ms")
    log(f"  {label} single stream: {sz.new_tokens / seq['secs']:.1f} tokens/s end to end over HTTP "
        f"({seq['secs']:.2f}s for {sz.new_tokens} tokens, prefill included)")
    log(f"  {label} 8 concurrent: {8 * sz.new_tokens / batch_secs:.1f} tokens/s aggregate "
        f"({batch_secs:.2f}s, first batch: compiles included)")
    log(f"  {label} first-dispatch (compile-bearing) seconds, all executables: "
        f"{sum(c['secs'] for c in compiles):.1f}s over {len(compiles)} executables")
  except Exception:
    log(f"  --- server log tail ({log_path}) ---")
    for line in log_path.read_text(errors="replace").splitlines()[-60:]:
      log(f"  | {line}")
    raise
  finally:
    kill_group(server)
  log(f"  phase serve: ok in {time.time() - t_start:.1f}s (server stopped)")


def parent(args) -> int:
  if not (HERE / "xotorch_tpu").is_dir():
    print("chip_smoke.py: no xotorch_tpu/ next to this script — it proves a checkout, "
          "it is not a program on its own", file=sys.stderr)
    return 2
  sz = Sizes(args.rehearse)
  flags = ["--seed", str(args.seed)] + (["--rehearse"] if args.rehearse else [])
  t0 = time.time()
  files0, bytes0 = cache_census()
  try:
    device = run_phase("device", flags + ["--chips", str(args.chips)], 300)
    if args.chips == 4:
      run_phase("tp4", flags, 1500)
    else:
      run_phase("kernels", flags, 600)
      run_phase("logits", flags, 600)
      serve_phase(sz, device)
  except Exception as e:
    print(f"chip_smoke: FAILED after {time.time() - t0:.0f}s: {e}", file=sys.stderr)
    return 1
  files1, bytes1 = cache_census()
  log(f"\ncompile cache: {files1} files / {bytes1 / 2**20:.1f} MiB now; this run added "
      f"{files1 - files0} files / {(bytes1 - bytes0) / 2**20:.1f} MiB "
      f"(a warm run adds ~0)")
  log(f"all phases passed in {time.time() - t0:.0f}s")
  if args.rehearse:
    log("rehearsal only: not a chip run, no result line")
    return 3
  print(json.dumps({"ok": True, "device": {"platform": device["platform"],
                                           "kind": device["kind"], "count": device["count"]}}),
        flush=True)
  return 0


# ------------------------------------------------------------------ child side


def emit(result: dict) -> None:
  print(RESULT_TAG + json.dumps(result), flush=True)


def phase_device(args) -> None:
  import jax
  import jaxlib
  from xotorch_tpu.utils import compile_cache
  try:
    import libtpu
    libtpu_version = getattr(libtpu, "__version__", "?")
  except ImportError:
    libtpu_version = "not installed"
  devices = jax.devices()
  d0 = devices[0]
  log(f"platform={d0.platform} device_kind={d0.device_kind!r} count={len(devices)}")
  log(f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu_version} "
      f"python={sys.version.split()[0]}")
  log(f"compile cache: {compile_cache.cache_dir()} "
      f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get(compile_cache.ENV) else 'fixed path in the checkout'})")
  if not args.rehearse:
    if d0.platform != "tpu":
      raise SystemExit(f"JAX found platform={d0.platform!r}: no accelerator, no result")
    if len(devices) != args.chips:
      raise SystemExit(f"this run wants {args.chips} chip(s) and JAX found {len(devices)}"
                       + (" — the four-chip path is behind --chips 4" if len(devices) == 4 else ""))
    from xotorch_tpu.topology.device_capabilities import tpu_chip_spec
    spec = tpu_chip_spec(d0.device_kind)  # an unknown kind ends the run here
    stats = d0.memory_stats() or {}
    log(f"peak table row: {spec} ; device reports {stats.get('bytes_limit', 0) / 2**30:.2f} GiB HBM")
  emit({"ok": True, "platform": d0.platform, "kind": d0.device_kind, "count": len(devices)})


def _err(got, ref) -> tuple:
  import numpy as np
  g, r = np.asarray(got, np.float32), np.asarray(ref, np.float32)
  return float(np.max(np.abs(g - r))), float(np.linalg.norm(g - r) / max(np.linalg.norm(r), 1e-30))


def phase_kernels(args) -> None:
  """Every selectable Pallas kernel at the model's widths, compiled, vs plain jax.numpy."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.quantize import (dequantize_tensor_grouped, quantize_tensor,
                                           quantize_tensor_grouped)
  from xotorch_tpu.models.registry import model_cards
  from xotorch_tpu.ops.attention import gqa_attention
  from xotorch_tpu.ops.flash_attention import flash_attention
  from xotorch_tpu.ops.flash_decode import flash_cached_attention
  from xotorch_tpu.ops.int4_matmul import int4_grouped_matmul
  from xotorch_tpu.ops.int8_matmul import int8_rowquant_matmul
  from xotorch_tpu.ops.paged_attention import (_paged_attention_xla, paged_decode_attention,
                                               paged_prefill_attention)
  from xotorch_tpu.utils import compile_cache
  compile_cache.enable()

  sz = Sizes(args.rehearse)
  cfg = config_from_hf_dict(model_cards[sz.model]["synthetic_config"])
  Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
  H, I, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
  if args.rehearse:  # interpret=None: interpreted off-TPU, compiled on one
    interp, dt, S, T_SHORT, T_SEG, T_LONG, PAGE = None, jnp.float32, 256, 32, 32, 64, 16
  else:
    interp, dt, S, T_SHORT, T_SEG, T_LONG, PAGE = False, jnp.bfloat16, 8192, 128, 512, 4096, 128
  ATT_TOL, MM_TOL = 3e-2, 1.5e-2
  key = jax.random.PRNGKey(args.seed)
  failures = []

  def rnd(i, *shape, dtype=dt):
    return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32).astype(dtype)

  def case(name, kernel, reference, *operands, tol=ATT_TOL, rel=False):
    """Run the kernel and its reference (each one jitted program) on `operands`."""
    got = jax.block_until_ready(jax.jit(kernel)(*operands))
    mx, l2 = _err(got, jax.jit(reference)(*operands))
    finite = bool(np.isfinite(np.asarray(got, np.float32)).all())
    bad = (not finite) or (l2 if rel else mx) > tol
    log(f"{'FAIL' if bad else 'ok  '} {name:58s} max_abs_err={mx:.4g} rel_l2={l2:.4g} "
        f"(tol {'rel_l2' if rel else 'max_abs'} {tol:g})")
    if bad:
      failures.append(name)

  def sliced(fn, T):
    """A reference evaluated T_SEG query positions at a time: the plain [T, S] score
    tensor of a 4096-token segment is gigabytes."""
    return lambda *ops: jnp.concatenate([fn(o, *ops) for o in range(0, T, T_SEG)], axis=1)

  w = jnp.int32(T_SEG // 2)

  # -- flash prefill (from-zero segment), global and windowed
  for T in (T_SHORT, T_LONG):
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    case(f"flash_attention global T={T}",
         lambda q, k, v: flash_attention(q, k, v, interpret=interp),
         sliced(lambda o, q, k, v: gqa_attention(q[:, o:o + T_SEG], k, v, pos[:, o:o + T_SEG]), T),
         rnd(1, 1, T, Hq, D), rnd(2, 1, T, Hkv, D), rnd(3, 1, T, Hkv, D))
  pos = jnp.arange(T_SEG, dtype=jnp.int32)[None]
  case(f"flash_attention windowed T={T_SEG} w={int(w)}",
       lambda q, k, v, w: flash_attention(q, k, v, window=w, interpret=interp),
       lambda q, k, v, w: gqa_attention(q, k, v, pos, window=w),
       rnd(4, 1, T_SEG, Hq, D), rnd(5, 1, T_SEG, Hkv, D), rnd(6, 1, T_SEG, Hkv, D), w)

  # -- cached attention over a resident cache: decode steps and pos>0 segments.
  # The int8-KV reference is transformer._cache_read's math (dequantise, then attend).
  kc, vc = rnd(7, 8, S, Hkv, D), rnd(8, 8, S, Hkv, D)
  kq, ks = quantize_tensor(kc, axis=-1, scale_dtype=dt)
  vq, vs = quantize_tensor(vc, axis=-1, scale_dtype=dt)
  deq = lambda x, s: x.astype(dt) * s.astype(dt)[..., None]
  for B in (1, 8):
    starts = jnp.asarray([S - 1 - i * (S // 16) for i in range(B)], jnp.int32)
    q = rnd(9 + B, B, 1, Hq, D)
    case(f"flash_cached decode B={B} S={S}",
         lambda q, k, v, st: flash_cached_attention(q, k, v, st, interpret=interp),
         lambda q, k, v, st: gqa_attention(q, k, v, st[:, None], kv_valid_len=st + 1),
         q, kc[:B], vc[:B], starts)
    case(f"flash_cached decode int8-KV B={B} S={S}",
         lambda q, k, v, st, ks, vs: flash_cached_attention(q, k, v, st, k_scale=ks, v_scale=vs,
                                                            interpret=interp),
         lambda q, k, v, st, ks, vs: gqa_attention(q, deq(k, ks), deq(v, vs), st[:, None],
                                                   kv_valid_len=st + 1),
         q, kq[:B], vq[:B], starts, ks[:B], vs[:B])
  start = jnp.asarray([S // 2], jnp.int32)
  q = rnd(20, 1, T_SEG, Hq, D)
  seg_pos = lambda st: st[:, None] + jnp.arange(T_SEG, dtype=jnp.int32)[None]
  case(f"flash_cached segment T={T_SEG} at pos {S // 2}",
       lambda q, k, v, st: flash_cached_attention(q, k, v, st, interpret=interp),
       lambda q, k, v, st: gqa_attention(q, k, v, seg_pos(st), kv_valid_len=st + T_SEG),
       q, kc[:1], vc[:1], start)
  case(f"flash_cached segment int8-KV T={T_SEG} at pos {S // 2}",
       lambda q, k, v, st, ks, vs: flash_cached_attention(q, k, v, st, k_scale=ks, v_scale=vs,
                                                          interpret=interp),
       lambda q, k, v, st, ks, vs: gqa_attention(q, deq(k, ks), deq(v, vs), seg_pos(st),
                                                 kv_valid_len=st + T_SEG),
       q, kq[:1], vq[:1], start, ks[:1], vs[:1])
  case(f"flash_cached segment windowed T={T_SEG} w={int(w)}",
       lambda q, k, v, st, w: flash_cached_attention(q, k, v, st, window=w, interpret=interp),
       lambda q, k, v, st, w: gqa_attention(q, k, v, seg_pos(st), kv_valid_len=st + T_SEG, window=w),
       q, kc[:1], vc[:1], start, w)

  # -- paged pool: decode over each row's pages, ragged T>1 segments
  maxp = S // PAGE
  n_pages = 8 * maxp + 1
  kp, vp = rnd(21, n_pages, PAGE, Hkv, D), rnd(22, n_pages, PAGE, Hkv, D)
  kpq, kps = quantize_tensor(kp, axis=-1, scale_dtype=dt)
  vpq, vps = quantize_tensor(vp, axis=-1, scale_dtype=dt)
  table = jnp.asarray(np.random.default_rng(args.seed).permutation(n_pages - 1)[:8 * maxp]
                      .reshape(8, maxp) + 1, jnp.int32)
  lens = jnp.asarray([S - i * (S // 10) for i in range(8)], jnp.int32)
  q = rnd(23, 8, 1, Hq, D)
  scale = D ** -0.5
  case(f"paged decode B=8 ({maxp} pages of {PAGE})",
       lambda q, kp, vp, pt, ln: paged_decode_attention(q, kp, vp, pt, ln, use_kernel=True,
                                                        interpret=interp),
       lambda q, kp, vp, pt, ln: _paged_attention_xla(q, kp, vp, pt, ln, scale, 0.0),
       q, kp, vp, table, lens)
  case("paged decode int8-KV B=8",
       lambda q, kp, vp, pt, ln, ks, vs: paged_decode_attention(
         q, kp, vp, pt, ln, use_kernel=True, interpret=interp, k_scale_pages=ks, v_scale_pages=vs),
       lambda q, kp, vp, pt, ln, ks, vs: _paged_attention_xla(
         q, kp, vp, pt, ln, scale, 0.0, k_scale_pages=ks, v_scale_pages=vs),
       q, kpq, vpq, table, lens, kps, vps)
  case(f"paged decode windowed B=8 w={int(w)}",
       lambda q, kp, vp, pt, ln, w: paged_decode_attention(q, kp, vp, pt, ln, use_kernel=True,
                                                           interpret=interp, window=w),
       lambda q, kp, vp, pt, ln, w: _paged_attention_xla(q, kp, vp, pt, ln, scale, 0.0, window=w),
       q, kp, vp, table, lens, w)
  for T in (T_SEG, T_LONG):  # T_LONG runs as position slices through the same kernel
    valid = jnp.asarray([min(S, T + S // 4)], jnp.int32)
    qpos = (valid - T)[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    case(f"ragged paged prefill T={T}",
         lambda q, kp, vp, pt: paged_prefill_attention(q, kp, vp, pt, qpos, valid, use_kernel=True,
                                                       interpret=interp),
         sliced(lambda o, q, kp, vp, pt: paged_prefill_attention(
           q[:, o:o + T_SEG], kp, vp, pt, qpos[:, o:o + T_SEG], valid - T + o + T_SEG), T),
         rnd(24 + T, 1, T, Hq, D), kp, vp, table[:1])
  valid = jnp.asarray([min(S, T_SEG + S // 4)], jnp.int32)
  qpos = (valid - T_SEG)[:, None] + jnp.arange(T_SEG, dtype=jnp.int32)[None]
  case(f"ragged paged prefill int8-KV T={T_SEG}",
       lambda q, kp, vp, pt, ks, vs: paged_prefill_attention(
         q, kp, vp, pt, qpos, valid, use_kernel=True, interpret=interp,
         k_scale_pages=ks, v_scale_pages=vs),
       lambda q, kp, vp, pt, ks, vs: paged_prefill_attention(
         q, kp, vp, pt, qpos, valid, k_scale_pages=ks, v_scale_pages=vs),
       rnd(30, 1, T_SEG, Hq, D), kpq, vpq, table[:1], kps, vps)

  # -- quantized decode matvecs vs a dequantised jnp.dot (the reference at full precision)
  hi = jax.lax.Precision.HIGHEST
  for d_in, d_out, rows in ((H, I, 8), (I, H, 1), (H, V, 1)):
    wq, wscale = quantize_tensor(rnd(40 + rows, d_in, d_out, dtype=jnp.float32) * 0.02,
                                 axis=0, scale_dtype=jnp.float32)
    case(f"int8_rowquant_matmul {d_in}x{d_out} rows={rows}",
         lambda h, wq, ws: int8_rowquant_matmul(h, wq, ws, interpret=interp),
         lambda h, wq, ws: jnp.matmul(h.astype(jnp.float32), wq.astype(jnp.float32) * ws, precision=hi),
         rnd(41, rows, d_in), wq, wscale.reshape(-1), tol=MM_TOL, rel=True)
  for d_in, d_out, rows in ((H, I, 8), (I, H, 1)):
    wq4, gscale = quantize_tensor_grouped(rnd(50 + rows, 1, d_in, d_out, dtype=jnp.float32) * 0.02,
                                          scale_dtype=jnp.float32)
    case(f"int4_grouped_matmul {d_in}x{d_out} rows={rows}",
         lambda h, wq, gs: int4_grouped_matmul(h, wq[0], gs[0], interpret=interp),
         lambda h, wq, gs: jnp.matmul(h.astype(jnp.float32),
                                      dequantize_tensor_grouped(wq, gs, jnp.float32)[0], precision=hi),
         rnd(51, rows, d_in), wq4, gscale, tol=MM_TOL, rel=True)

  if failures:
    raise SystemExit(f"{len(failures)} kernel(s) outside tolerance: {failures}")
  emit({"ok": True})


def _prompt_tokens(seed: int, n: int, vocab: int):
  import numpy as np
  return np.random.default_rng(seed).integers(3, vocab - 1, (1, n)).astype(np.int64)


async def _next_logits(engine, shard, rid: str, context):
  """float32 logits for the token after `context` ([1, n] ids): the fused prefill
  entry point for all but the last token, then ONE per-token step — the host never
  receives more than a single [vocab] row."""
  import numpy as np
  await engine.infer_sample_tensor(rid, shard, context[:, :-1], temp=0.0, top_k=0)
  logits, _ = await engine.infer_tensor(rid, shard, context[:, -1:])
  await engine.clear_request(rid)
  return np.asarray(logits)[0, -1].astype(np.float32)


async def _greedy(engine, shard, rid: str, prompt, n_new: int) -> list:
  """Prefill + fused greedy decode through the engine's serving entry points."""
  import numpy as np
  tok, _ = await engine.infer_sample_tensor(rid, shard, prompt, temp=0.0, top_k=0)
  toks = [int(tok)]
  while len(toks) < n_new:
    chunk = await engine.generate_chunk(rid, shard, toks[-1], min(n_new - len(toks), 64),
                                        temp=0.0, top_k=0)
    toks.extend(int(t) for t in np.asarray(chunk).reshape(-1))
  await engine.clear_request(rid)
  return toks[:n_new]


def _engine(args):
  """An in-process engine the way the server builds one (bfloat16 default); the
  rehearsal's is float32 so its comparisons are exact on the CPU."""
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  os.environ.update(Sizes(args.rehearse).server_env)
  os.environ["XOT_PREFIX_CACHE"] = "0"  # every in-process request takes the cold path
  return JAXShardInferenceEngine(dtype="float32" if args.rehearse else None)


def phase_logits(args) -> None:
  import asyncio

  import jax
  import jax.numpy as jnp
  import numpy as np
  sz = Sizes(args.rehearse)
  from xotorch_tpu.models.registry import build_full_shard
  from xotorch_tpu.models.transformer import forward_shard, init_kv_cache

  async def run():
    engine = _engine(args)
    shard = build_full_shard(sz.model, "JAXShardInferenceEngine")
    t0 = time.time()
    ctx = await engine._ensure_ctx(shard)
    cfg = ctx.cfg
    log(f"engine loaded {sz.model}: {cfg.num_layers} layers, hidden {cfg.hidden_size}, "
        f"{cfg.num_heads}q/{cfg.num_kv_heads}kv heads, vocab {cfg.vocab_size} in {time.time() - t0:.1f}s")
    T = 32 if args.rehearse else 128
    prompt = _prompt_tokens(args.seed, T, cfg.vocab_size)

    # engine path: from-zero prefill through the flash prefill executable
    t0 = time.time()
    logits, _ = await engine.infer_tensor("logits", shard, prompt)
    got = np.asarray(logits)[0, -1].astype(np.float32)
    log(f"engine prefill logits {np.asarray(logits).shape} in {time.time() - t0:.1f}s (compile included)")
    await engine.clear_request("logits")

    # reference: the same weights in float32 through forward_shard, XLA attention
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), ctx.params)
    cache32 = init_kv_cache(cfg, cfg.num_layers, 1, T, jnp.float32)
    with jax.default_matmul_precision("highest"):
      ref_all, _ = jax.jit(lambda p, x, c: forward_shard(
        p, x, c, jnp.int32(0), cfg=cfg, is_first=True, is_last=True))(
          params32, jnp.asarray(prompt, jnp.int32), cache32)
    ref = np.asarray(ref_all)[0, -1].astype(np.float32)
    del params32, cache32, ref_all
    mx, l2 = _err(got, ref)
    tol_l2, tol_abs = (1e-3, 1e-3) if args.rehearse else (0.05, 0.25)
    margin = float(ref.max() - ref[int(got.argmax())])
    log(f"last-position logits, engine ({engine._dtype_name}, Pallas) vs float32 XLA reference: "
        f"max_abs_err={mx:.4g} rel_l2={l2:.4g} ref_std={ref.std():.3g} "
        f"engine argmax {int(got.argmax())} ref argmax {int(ref.argmax())} (ref margin {margin:.3g})")
    if not (np.isfinite(got).all() and got.shape == (cfg.vocab_size,)):
      raise SystemExit("engine logits not finite / wrong shape")
    if l2 > tol_l2 or mx > tol_abs or margin > 2 * tol_abs:
      raise SystemExit(f"engine logits outside tolerance (rel_l2 <= {tol_l2}, max_abs <= {tol_abs})")

    # the compiled executables contain the kernels their gates select
    if not args.rehearse:
      x = jnp.asarray(prompt, jnp.int32)
      cache = engine._new_cache(ctx, 2048)
      hlo = ctx.forward_flash_jit.lower(ctx.params, x, cache, jnp.int32(0)).compile().as_text()
      n_flash = hlo.count("tpu_custom_call")
      cache = engine._new_cache(ctx, 8192)
      hlo = ctx.forward_decode_flash_jit.lower(ctx.params, x[:, :1], cache,
                                               jnp.int32(5000)).compile().as_text()
      n_fd = hlo.count("tpu_custom_call")
      log(f"compiled prefill executable: {n_flash} tpu_custom_call; "
          f"compiled long-context decode executable: {n_fd} tpu_custom_call")
      if not (n_flash and n_fd):
        raise SystemExit("a compiled executable is missing the Pallas kernel its gate selected")

    # the plain greedy path (prefill + fused decode chunks) is deterministic
    a = await _greedy(engine, shard, "det-a", prompt, sz.new_tokens)
    b = await _greedy(engine, shard, "det-b", prompt, sz.new_tokens)
    log(f"greedy {sz.new_tokens} tokens twice: {a[:8]}... identical={a == b}")
    if a != b or len(a) != sz.new_tokens:
      raise SystemExit("the same greedy request produced different tokens")

  asyncio.run(run())
  emit({"ok": True})


def phase_tp4(args) -> None:
  """synthetic-llama-1b over the engine's default four-chip tp mesh, against one
  device of the same host."""
  import asyncio

  import jax
  import jax.numpy as jnp
  import numpy as np
  sz = Sizes(args.rehearse)
  from xotorch_tpu.models.registry import build_full_shard

  devices = jax.devices()
  if args.rehearse:
    os.environ["XOT_TP"] = str(min(4, len(devices)))
  shard = build_full_shard(sz.model, "JAXShardInferenceEngine")
  short_t = 32 if args.rehearse else 128
  long_t = 300 if args.rehearse else 6000

  def in_use():
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices]

  async def drive(engine, tag: str) -> dict:
    ctx = await engine._ensure_ctx(shard)
    vocab = ctx.cfg.vocab_size
    short, long_ = _prompt_tokens(args.seed, short_t, vocab), _prompt_tokens(args.seed + 1, long_t, vocab)
    out = {"ctx": ctx}
    t0 = time.time()
    logits, _ = await engine.infer_tensor(f"{tag}-l", shard, short)
    out["logits"] = np.asarray(logits)[0, -1].astype(np.float32)
    await engine.clear_request(f"{tag}-l")
    out["short"] = await _greedy(engine, shard, f"{tag}-s", short, sz.new_tokens)
    # long prompt: chunked prefill + cache growth, then ONE step's logits at depth
    # (cached-attention kernel), then the fused greedy decode
    out["deep_logits"] = await _next_logits(engine, shard, f"{tag}-d", long_)
    out["long"] = await _greedy(engine, shard, f"{tag}-g", long_, sz.new_tokens)
    out["prompts"] = {"short": short, "long": long_}
    log(f"{tag}: short prompt {short_t} + long prompt {long_t} tokens served in "
        f"{time.time() - t0:.1f}s (compiles included); kernels seen: "
        f"{sorted({k for key in engine._exec_seen for k in key[-1]})}")
    return out

  async def run():
    base = in_use()
    mesh_engine = _engine(args)
    tp = await drive(mesh_engine, "tp4")
    ctx = tp["ctx"]
    mesh = ctx.mesh
    if mesh is None or (int(mesh.shape["tp"]) != 4 and not args.rehearse):
      raise SystemExit(f"expected the engine's default on this host to be a tp=4 serving mesh, got {mesh}")
    log(f"serving mesh: {dict(mesh.shape)} over {[d.id for d in mesh.devices.flat]}")

    # parameters are spread: sharded leaves hold 1/tp of their bytes on each device
    n_dev = int(mesh.shape["tp"])
    sharded = whole = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(ctx.params)[0]:
      shards = leaf.addressable_shards
      per = {s.device.id: s.data.nbytes for s in shards}
      if len(per) != n_dev:
        raise SystemExit(f"{jax.tree_util.keystr(path)} lives on {len(per)} devices, not {n_dev}")
      if all(b * n_dev == leaf.nbytes for b in per.values()):
        sharded += leaf.nbytes
      elif all(b == leaf.nbytes for b in per.values()):
        whole += leaf.nbytes  # replicated (norms, int8 scales): small by construction
      else:
        raise SystemExit(f"{jax.tree_util.keystr(path)} is unevenly placed: {per}")
    log(f"parameters: {sharded / 2**30:.3f} GiB in leaves split {n_dev} ways, "
        f"{whole / 2**20:.3f} MiB replicated")
    if whole > 0.01 * sharded:
      raise SystemExit("more than 1% of parameter bytes are replicated instead of sharded")
    used = [u - b for u, b in zip(in_use(), base)]
    if any(used):
      log(f"per-device bytes in use after serving: {[f'{u / 2**20:.0f} MiB' for u in used]}")
      # every device holds about its quarter; a leaf (or the whole model) left behind
      # on device 0 would put it at several times the others
      if max(used) > 1.5 * min(used) or used[0] > 0.5 * (sharded + whole):
        raise SystemExit("device memory is uneven across the mesh (something landed whole on one device)")
    elif not args.rehearse:
      raise SystemExit("devices report no memory_stats")

    # the compiled steps contain the all-reduces and the kernels
    if not args.rehearse:
      x = jnp.zeros((1, 128), jnp.int32)
      hlo = ctx.forward_flash_jit.lower(ctx.params, x, mesh_engine._new_cache(ctx, 2048),
                                        jnp.int32(0)).compile().as_text()
      hlo_d = ctx.forward_decode_flash_jit.lower(ctx.params, x[:, :1], mesh_engine._new_cache(ctx, 8192),
                                                 jnp.int32(5000)).compile().as_text()
      for name, text in (("prefill", hlo), ("long-context decode", hlo_d)):
        n_ar, n_k = text.count("all-reduce("), text.count("tpu_custom_call")
        log(f"compiled tp {name} step: {n_ar} all-reduce, {n_k} tpu_custom_call")
        if not (n_ar and n_k):
          raise SystemExit(f"tp {name} step lacks its all-reduces or its kernel")

    # the same requests on ONE device of the same host
    os.environ["XOT_TP"] = "0"
    one_dev = _engine(args)
    one = {**await drive(one_dev, "one-device"), "engine": one_dev}
    if one["ctx"].mesh is not None:
      raise SystemExit("the comparison engine was meant to serve from one device")
    # Two bfloat16 evaluations of the same model: each sits within the `logits` phase's
    # tolerance of the float32 reference (measured there: max_abs 0.093, rel_l2 0.023),
    # and tp changes the reduction order, so they may differ from EACH OTHER by about as
    # much. A broken shard, collective or kernel shows as rel_l2 ~ 1.
    tol_abs, tol_l2 = (1e-3, 1e-3) if args.rehearse else (0.25, 0.05)
    rms = 0.0
    for name in ("logits", "deep_logits"):
      mx, l2 = _err(tp[name], one[name])
      rms = max(rms, l2 * float(one[name].std()))
      log(f"{name}: tp vs one device max_abs_err={mx:.4g} rel_l2={l2:.4g} "
          f"(tol max_abs {tol_abs}, rel_l2 {tol_l2})")
      if not np.isfinite(tp[name]).all() or mx > tol_abs or l2 > tol_l2:
        raise SystemExit(f"{name} differ between the tp mesh and one device")
    tie = max(4 * rms, 1e-4)  # a flip is a near-tie within ~4x the measured rms difference
    one_engine = one["engine"]
    for name in ("short", "long"):
      a, b = tp[name], one[name]
      agree = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), len(a))
      log(f"{name} prompt greedy tokens: {agree}/{len(a)} agree between tp and one device")
      if agree < len(a):
        # Greedy streams may part only at a near-tie: the reference's own logits for
        # the shared context must hold the two candidates within the logits tolerance.
        context = np.concatenate([one["prompts"][name], np.asarray([a[:agree]], np.int64)], axis=1)
        ref = await _next_logits(one_engine, shard, "tie", context)
        gap = abs(float(ref[a[agree]]) - float(ref[b[agree]]))
        log(f"  token {agree}: tp chose {a[agree]} ({ref[a[agree]]:.4f}), one device chose "
            f"{b[agree]} ({ref[b[agree]]:.4f}); reference gap {gap:.4g} (near-tie if <= {tie:.3g})")
        if gap > tie:
          raise SystemExit(f"{name} prompt: greedy streams diverge at token {agree} without a near-tie")

  asyncio.run(run())
  emit({"ok": True})


def main() -> int:
  ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
  ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                  help="4: run ONLY the four-chip tp path and its one-device comparison")
  ap.add_argument("--seed", type=int, default=0, help="seed for prompts and kernel inputs")
  ap.add_argument("--rehearse", action="store_true",
                  help="tiny sizes on any platform: walks the control flow, prints no result line")
  ap.add_argument("--phase", choices=("device", "kernels", "logits", "tp4"),
                  help=argparse.SUPPRESS)  # child entry: one phase in a process of its own
  args = ap.parse_args()
  if args.phase is None:
    return parent(args)
  {"device": phase_device, "kernels": phase_kernels, "logits": phase_logits,
   "tp4": phase_tp4}[args.phase](args)
  return 0


if __name__ == "__main__":
  sys.exit(main())
