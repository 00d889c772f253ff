"""int8 KV cache (transformer.init_kv_cache kv_quant + engine XOT_KV_QUANT).

K/V store as int8 with one scale per (position, head): half the cache
bandwidth and HBM per resident token — the binding resource for long
contexts. Quantization happens at WRITE (per fresh segment), dequantization
fuses into the attention read. No reference counterpart (the reference keeps
fp16/bf16 torch caches, sharded_inference_engine.py:71-82).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xotorch_tpu.inference.shard import Shard
from xotorch_tpu.models.config import config_from_hf_dict
from xotorch_tpu.models.registry import model_cards
from xotorch_tpu.models.transformer import (
  _quantize_kv, forward_shard, init_kv_cache, init_random_params,
)


def _tiny():
  cfg = config_from_hf_dict(model_cards["synthetic-tiny"]["synthetic_config"])
  params = init_random_params(cfg, cfg.num_layers, True, True, jax.random.PRNGKey(0), dtype=jnp.float32)
  return cfg, params


def test_quantize_kv_roundtrip_bound():
  x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3, 16), jnp.float32)
  q, scale = _quantize_kv(x, jnp.float32)
  assert q.dtype == jnp.int8 and scale.shape == (2, 5, 3)
  back = q.astype(jnp.float32) * scale[..., None]
  err = np.abs(np.asarray(back) - np.asarray(x))
  assert (err <= np.asarray(scale)[..., None] * 0.5 + 1e-6).all()


def test_forward_with_int8_cache_close_to_bf16_cache():
  cfg, params = _tiny()
  x = jnp.asarray([[3, 7, 11, 250, 1, 42]], jnp.int32)
  cache_f = init_kv_cache(cfg, cfg.num_layers, 1, 32, jnp.float32)
  cache_q = init_kv_cache(cfg, cfg.num_layers, 1, 32, jnp.float32, kv_quant=True)
  assert cache_q["k"].dtype == jnp.int8 and cache_q["k_scale"].shape == (cfg.num_layers, 1, 32, cfg.num_kv_heads)

  out_f, cache_f = forward_shard(params, x, cache_f, jnp.int32(0), cfg, True, True)
  out_q, cache_q = forward_shard(params, x, cache_q, jnp.int32(0), cfg, True, True)
  f, q = np.asarray(out_f), np.asarray(out_q)
  rel_l2 = np.linalg.norm(q - f) / np.linalg.norm(f)
  assert rel_l2 < 0.05, f"int8 KV deviates {rel_l2:.3f}"
  assert int(q[0, -1].argmax()) == int(f[0, -1].argmax())

  # Decode continuation over the quantized resident cache stays close.
  tok_f = jnp.argmax(out_f[:, -1:], axis=-1).astype(jnp.int32)
  for step in range(4):
    out_f, cache_f = forward_shard(params, tok_f, cache_f, jnp.int32(6 + step), cfg, True, True)
    out_q, cache_q = forward_shard(params, tok_f, cache_q, jnp.int32(6 + step), cfg, True, True)
    assert int(np.asarray(out_q)[0, -1].argmax()) == int(np.asarray(out_f)[0, -1].argmax())
    tok_f = jnp.argmax(out_f[:, -1:], axis=-1).astype(jnp.int32)


def test_int8_cache_bytes_halved():
  cfg, _ = _tiny()
  bf16 = init_kv_cache(cfg, cfg.num_layers, 1, 1024, jnp.bfloat16)
  q8 = init_kv_cache(cfg, cfg.num_layers, 1, 1024, jnp.bfloat16, kv_quant=True)
  bytes_bf16 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(bf16))
  bytes_q8 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(q8))
  # int8 K/V + bf16 per-(pos,head) scales: ~0.5x + 1/D overhead.
  assert bytes_q8 < 0.6 * bytes_bf16


async def test_engine_kv_quant_serving(tmp_path):
  from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint
  from xotorch_tpu.download.shard_download import LocalShardDownloader
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine

  model_dir = make_hf_checkpoint(tmp_path, TINY_LLAMA_CFG, seed=3)
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  shard = Shard("m", 0, n - 1, n)
  prompt = np.array([[1, 5, 9, 200, 17, 3, 42]], dtype=np.int64)

  async def generate(kv_quant):
    eng = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32",
                                  kv_quant=kv_quant)
    tok, _ = await eng.infer_sample_tensor("r", shard, prompt, temp=0.0)
    toks = [int(tok)]
    for _ in range(8):
      tok, _ = await eng.infer_sample_tensor("r", shard, np.asarray([[toks[-1]]]), temp=0.0)
      toks.append(int(tok))
    # Fused chunks over the same quantized cache (growth + batcher path).
    chunk = await eng.generate_chunk("r", shard, toks[-1], 4, temp=0.0)
    toks.extend(int(t) for t in chunk)
    return toks, eng

  ref, _ = await generate(None)
  got, eng = await generate("int8")
  state = eng._contexts[shard].states["r"]
  assert state.cache["k"].dtype == jnp.int8 and "k_scale" in state.cache
  # Tiny-model greedy streams agree for a long prefix under KV int8.
  agree = next((i for i in range(min(len(ref), len(got))) if ref[i] != got[i]), len(ref))
  assert agree >= 8, f"KV-int8 stream diverged at {agree}: {got} vs {ref}"


async def test_kv_quant_with_prefix_cache(tmp_path, monkeypatch):
  """Prefix-cache snapshots of an int8 cache (extra rank-4 scale leaves)
  store and reuse without rank mismatches, and the reused stream matches a
  cold engine's."""
  from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint
  from xotorch_tpu.download.shard_download import LocalShardDownloader
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine

  monkeypatch.setenv("XOT_PREFIX_CACHE_MIN", "8")
  model_dir = make_hf_checkpoint(tmp_path, TINY_LLAMA_CFG, seed=3)
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  shard = Shard("m", 0, n - 1, n)
  prompt = (np.arange(24, dtype=np.int64)[None, :] % 250) + 1

  async def generate(eng, rid):
    tok, _ = await eng.infer_sample_tensor(rid, shard, prompt, temp=0.0)
    toks = [int(tok)]
    for _ in range(4):
      tok, _ = await eng.infer_sample_tensor(rid, shard, np.asarray([[toks[-1]]]), temp=0.0)
      toks.append(int(tok))
    return toks

  eng = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32",
                                kv_quant="int8")
  first = await generate(eng, "r1")
  second = await generate(eng, "r2")
  assert eng._prefix_hits == 1
  assert first == second


async def test_kv_quant_flash_decode_matches_xla_path(tmp_path, monkeypatch):
  """int8 KV caches now TAKE the Pallas cached kernel (in-kernel per-tile
  dequant, ops/flash_decode._scores): the engine must select it and the
  logits must match the XLA dense path on the SAME quantized cache — the
  dequant math is identical, only the attention implementation differs."""
  import numpy as np
  from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint
  from xotorch_tpu.download.shard_download import LocalShardDownloader
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.inference.shard import Shard

  model_dir = make_hf_checkpoint(tmp_path, TINY_LLAMA_CFG, seed=3)
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  shard = Shard("m", 0, n - 1, n)
  prompt = np.array([np.arange(90) % 250], dtype=np.int64)

  monkeypatch.setenv("XOT_PREFILL_CHUNK", "32")
  monkeypatch.setenv("XOT_FLASH_DECODE", "0")
  dense = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32",
                                  kv_quant="int8")
  ld, _ = await dense.infer_tensor("r", shard, prompt)

  monkeypatch.setenv("XOT_FLASH_DECODE", "1")
  monkeypatch.setenv("XOT_FLASH_DECODE_MIN", "0")
  flash = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32",
                                  kv_quant="int8")
  assert flash._flash_decode_on(10_000) is True
  lf, _ = await flash.infer_tensor("r", shard, prompt)
  np.testing.assert_allclose(lf, ld, atol=1e-4, rtol=1e-3)

  # Decode steps over the quantized resident cache agree too. The engine
  # reads XOT_FLASH_DECODE at CALL time, so the dense engine's step must run
  # with it off — otherwise this would compare the flash path to itself.
  tok = np.array([[int(np.argmax(ld[0, -1]))]], dtype=np.int64)
  monkeypatch.setenv("XOT_FLASH_DECODE", "0")
  dd, _ = await dense.infer_tensor("r", shard, tok)
  monkeypatch.setenv("XOT_FLASH_DECODE", "1")
  df, _ = await flash.infer_tensor("r", shard, tok)
  np.testing.assert_allclose(df, dd, atol=1e-4, rtol=1e-3)


async def test_flash_prefill_composes_with_int8_cache(tmp_path, monkeypatch):
  """Pallas flash prefill (interpret mode on CPU) WRITES the quantized cache
  while attending over fresh K/V; the subsequent decode reads the int8
  cache — the exact composition real-TPU serving uses. Streams must agree
  with the no-flash int8-cache engine."""
  from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint
  from xotorch_tpu.download.shard_download import LocalShardDownloader
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine

  model_dir = make_hf_checkpoint(tmp_path, TINY_LLAMA_CFG, seed=5)
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  shard = Shard("m", 0, n - 1, n)
  prompt = np.array([[1, 5, 9, 200, 17, 33, 2, 8]], dtype=np.int64)

  async def decode_steps(eng, k=4):
    tok, _ = await eng.infer_sample_tensor("r", shard, prompt, temp=0.0)
    toks = [int(tok)]
    for _ in range(k):
      tok, _ = await eng.infer_sample_tensor("r", shard, np.asarray([[toks[-1]]]), temp=0.0)
      toks.append(int(tok))
    return toks

  monkeypatch.setenv("XOT_FLASH_ATTENTION", "0")
  base = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32",
                                 kv_quant="int8")
  want = await decode_steps(base)

  monkeypatch.setenv("XOT_FLASH_ATTENTION", "1")  # interpret mode off-TPU
  flash = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32",
                                  kv_quant="int8")
  assert flash._flash_enabled()
  got = await decode_steps(flash)
  assert got == want, f"flash+int8KV stream {got} != baseline {want}"
