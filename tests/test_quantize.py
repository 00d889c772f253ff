"""int8 weight-only quantization (models/quantize.py).

The wiring invariant is tight: forward over a QUANTIZED pytree must equal
forward over its DEQUANTIZED float reconstruction (same rounded weights, so
only float reassociation separates them). Quality vs the ORIGINAL weights is
a separate, looser check (int8 rounding error is real but small). Parity
note: no reference counterpart — the reference serves torch fp16/bf16 only
(sharded_inference_engine.py:58-65); this is beyond-parity capability.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xotorch_tpu.inference.shard import Shard
from xotorch_tpu.models.config import config_from_hf_dict
from xotorch_tpu.models.quantize import (
  dequantize_params, dequantize_tensor, is_quantized, quantize_params,
  quantize_tensor, quantized_bytes,
)
from xotorch_tpu.models.registry import model_cards
from xotorch_tpu.models.transformer import forward_shard, init_kv_cache, init_random_params


def _tiny(model_id="synthetic-tiny", dtype=jnp.float32):
  cfg = config_from_hf_dict(model_cards[model_id]["synthetic_config"])
  params = init_random_params(cfg, cfg.num_layers, True, True, jax.random.PRNGKey(0), dtype=dtype)
  return cfg, params


def test_quantize_tensor_roundtrip_error_bound():
  w = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 48), jnp.float32)
  q, scale = quantize_tensor(w, axis=1, scale_dtype=jnp.float32)
  assert q.dtype == jnp.int8 and scale.shape == (4, 48)
  back = dequantize_tensor(q, scale, axis=1, dtype=jnp.float32)
  # Symmetric rounding: error per element <= scale/2 for its channel.
  err = np.abs(np.asarray(back) - np.asarray(w))
  bound = np.asarray(scale)[:, None, :] * 0.5 + 1e-6
  assert (err <= bound).all()


def test_quantized_forward_matches_dequantized_reconstruction():
  cfg, params = _tiny()
  qparams = quantize_params(params, scale_dtype=jnp.float32)
  assert is_quantized(qparams) and not is_quantized(params)
  # int8 leaves plus float scales must be ~half the bf16 bytes (f32 here: ~1/4).
  assert quantized_bytes(qparams) < 0.35 * quantized_bytes(params)
  ref = dequantize_params(qparams, jnp.float32)

  x = jnp.asarray([[3, 7, 11, 250, 1, 42]], jnp.int32)
  cache_q = init_kv_cache(cfg, cfg.num_layers, 1, 32, jnp.float32)
  cache_r = init_kv_cache(cfg, cfg.num_layers, 1, 32, jnp.float32)
  out_q, _ = forward_shard(qparams, x, cache_q, jnp.int32(0), cfg, True, True)
  out_r, _ = forward_shard(ref, x, cache_r, jnp.int32(0), cfg, True, True)
  np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_r), atol=2e-3, rtol=1e-3)


def test_quantized_forward_close_to_original():
  cfg, params = _tiny()
  qparams = quantize_params(params, scale_dtype=jnp.float32)
  x = jnp.asarray([[3, 7, 11, 250, 1, 42]], jnp.int32)
  cache_q = init_kv_cache(cfg, cfg.num_layers, 1, 32, jnp.float32)
  cache_f = init_kv_cache(cfg, cfg.num_layers, 1, 32, jnp.float32)
  out_q, _ = forward_shard(qparams, x, cache_q, jnp.int32(0), cfg, True, True)
  out_f, _ = forward_shard(params, x, cache_f, jnp.int32(0), cfg, True, True)
  q, f = np.asarray(out_q), np.asarray(out_f)
  rel_l2 = np.linalg.norm(q - f) / np.linalg.norm(f)
  assert rel_l2 < 0.05, f"int8 deviates {rel_l2:.3f} rel L2 from float"
  # Greedy next-token agreement on the last position.
  assert int(q[0, -1].argmax()) == int(f[0, -1].argmax())


def test_quantized_moe_forward():
  cfg, params = _tiny("synthetic-tiny-moe")
  qparams = quantize_params(params, scale_dtype=jnp.float32)
  for slot in ("we_gate", "we_up", "we_down"):
    assert qparams["layers"][slot].dtype == jnp.int8
    assert slot + "_scale" in qparams["layers"]
  ref = dequantize_params(qparams, jnp.float32)
  x = jnp.asarray([[3, 7, 11, 250]], jnp.int32)
  cache_q = init_kv_cache(cfg, cfg.num_layers, 1, 16, jnp.float32)
  cache_r = init_kv_cache(cfg, cfg.num_layers, 1, 16, jnp.float32)
  out_q, _ = forward_shard(qparams, x, cache_q, jnp.int32(0), cfg, True, True)
  out_r, _ = forward_shard(ref, x, cache_r, jnp.int32(0), cfg, True, True)
  np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_r), atol=5e-3, rtol=1e-2)


def test_quantized_tied_embedding_unembed():
  import dataclasses
  cfg, params = _tiny()
  # Tied variant: drop lm_head so unembed rides the (quantized) embedding.
  cfg2 = dataclasses.replace(cfg, tie_word_embeddings=True)
  params = {k: v for k, v in params.items() if k != "lm_head"}
  qparams = quantize_params(params, scale_dtype=jnp.float32)
  assert qparams["embed"]["embedding"].dtype == jnp.int8
  ref = dequantize_params(qparams, jnp.float32)
  x = jnp.asarray([[5, 9, 2]], jnp.int32)
  cache_q = init_kv_cache(cfg2, cfg2.num_layers, 1, 16, jnp.float32)
  cache_r = init_kv_cache(cfg2, cfg2.num_layers, 1, 16, jnp.float32)
  out_q, _ = forward_shard(qparams, x, cache_q, jnp.int32(0), cfg2, True, True)
  out_r, _ = forward_shard(ref, x, cache_r, jnp.int32(0), cfg2, True, True)
  np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_r), atol=2e-3, rtol=1e-3)


def test_quantized_decode_chunk_matches_dequantized():
  from xotorch_tpu.models.generate import decode_chunk
  cfg, params = _tiny()
  qparams = quantize_params(params, scale_dtype=jnp.float32)
  ref = dequantize_params(qparams, jnp.float32)

  prompt = jnp.asarray([[3, 7, 11, 250, 1]], jnp.int32)

  def run(p):
    cache = init_kv_cache(cfg, cfg.num_layers, 1, 64, jnp.float32)
    logits, cache = forward_shard(p, prompt, cache, jnp.int32(0), cfg, True, True)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    toks, _ = decode_chunk(p, tok, cache, jnp.int32(prompt.shape[1]), jax.random.PRNGKey(0),
                           cfg, 16, 0.0, 0)
    return np.asarray(toks)[0].tolist()

  assert run(qparams) == run(ref)


def test_quantized_params_shard_over_tp_mesh():
  from xotorch_tpu.parallel.mesh import make_mesh, param_specs_like, shard_params
  cfg, params = _tiny()
  qparams = quantize_params(params, scale_dtype=jnp.float32)
  mesh = make_mesh({"tp": 2})
  specs = param_specs_like(qparams, mesh)
  assert specs["layers"]["wq_scale"] is not None
  placed = shard_params(qparams, mesh)
  x = jnp.asarray([[3, 7, 11, 250]], jnp.int32)
  cache = init_kv_cache(cfg, cfg.num_layers, 1, 16, jnp.float32)
  out, _ = jax.jit(forward_shard, static_argnames=("cfg", "is_first", "is_last"))(
    placed, x, cache, jnp.int32(0), cfg=cfg, is_first=True, is_last=True)
  ref_cache = init_kv_cache(cfg, cfg.num_layers, 1, 16, jnp.float32)
  ref_out, _ = forward_shard(qparams, x, ref_cache, jnp.int32(0), cfg, True, True)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-3, rtol=1e-3)


def test_int4_grouped_roundtrip_and_forward():
  from xotorch_tpu.models.quantize import quantize_tensor_grouped, dequantize_tensor_grouped
  w = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 48), jnp.float32)
  q, gscale = quantize_tensor_grouped(w, scale_dtype=jnp.float32, group_size=16)
  # PACKED uint8 container: two nibbles per byte along the group axis (a
  # native S4 array crossing a jit boundary breaks some backends' transfer
  # paths).
  assert q.shape == (2, 4, 8, 48) and gscale.shape == (2, 4, 48)
  assert q.dtype == jnp.uint8
  back = dequantize_tensor_grouped(q, gscale, jnp.float32)
  err = np.abs(np.asarray(back) - np.asarray(w))
  bound = np.repeat(np.asarray(gscale), 16, axis=1) * 0.5 + 1e-6
  assert (err <= bound).all()

  cfg, params = _tiny()
  qparams = quantize_params(params, "int4", scale_dtype=jnp.float32)
  assert qparams["layers"]["wq"].dtype == jnp.uint8
  assert "wq_gscale" in qparams["layers"]
  assert qparams["embed"]["embedding"].dtype == jnp.int8  # embeddings stay int8
  # int4 layer slots + int8 embeddings: well under half the f32 bytes.
  assert quantized_bytes(qparams) < 0.3 * quantized_bytes(params)
  ref = dequantize_params(qparams, jnp.float32)
  assert ref["layers"]["wq"].shape == params["layers"]["wq"].shape

  x = jnp.asarray([[3, 7, 11, 250, 1, 42]], jnp.int32)
  cache_q = init_kv_cache(cfg, cfg.num_layers, 1, 32, jnp.float32)
  cache_r = init_kv_cache(cfg, cfg.num_layers, 1, 32, jnp.float32)
  out_q, _ = forward_shard(qparams, x, cache_q, jnp.int32(0), cfg, True, True)
  out_r, _ = forward_shard(ref, x, cache_r, jnp.int32(0), cfg, True, True)
  np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_r), atol=5e-3, rtol=1e-2)

  # Quality vs the original float model: looser than int8 but bounded.
  cache_f = init_kv_cache(cfg, cfg.num_layers, 1, 32, jnp.float32)
  out_f, _ = forward_shard(params, x, cache_f, jnp.int32(0), cfg, True, True)
  rel_l2 = np.linalg.norm(np.asarray(out_q) - np.asarray(out_f)) / np.linalg.norm(np.asarray(out_f))
  # The tiny model is int4's WORST case: H=64 degrades to a single 64-wide
  # group (real models get 128-wide groups over 2k+ dims) and random-normal
  # weights compound rounding error through 4 layers. Observed ~0.19; the
  # bound guards against regressions (a broken path lands near 1.0+), not
  # production quality — the decode_chunk equality test below pins the
  # wiring exactly.
  assert rel_l2 < 0.3, f"int4 deviates {rel_l2:.3f} rel L2 from float"


def test_int4_decode_chunk_and_mesh():
  from xotorch_tpu.models.generate import decode_chunk
  from xotorch_tpu.parallel.mesh import make_mesh, shard_params
  cfg, params = _tiny()
  qparams = quantize_params(params, "int4", scale_dtype=jnp.float32)
  ref = dequantize_params(qparams, jnp.float32)

  prompt = jnp.asarray([[3, 7, 11, 250, 1]], jnp.int32)

  def run(p):
    cache = init_kv_cache(cfg, cfg.num_layers, 1, 64, jnp.float32)
    logits, cache = forward_shard(p, prompt, cache, jnp.int32(0), cfg, True, True)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    toks, _ = decode_chunk(p, tok, cache, jnp.int32(prompt.shape[1]), jax.random.PRNGKey(0),
                           cfg, 8, 0.0, 0)
    return np.asarray(toks)[0].tolist()

  assert run(qparams) == run(ref)

  # tp mesh placement: the tiny model degrades to G=1 groups, which cannot
  # shard over tp=2 — the divisibility guard must replicate, not fail.
  mesh = make_mesh({"tp": 2})
  placed = shard_params(qparams, mesh)
  x = jnp.asarray([[3, 7]], jnp.int32)
  cache = init_kv_cache(cfg, cfg.num_layers, 1, 16, jnp.float32)
  out, _ = jax.jit(forward_shard, static_argnames=("cfg", "is_first", "is_last"))(
    placed, x, cache, jnp.int32(0), cfg=cfg, is_first=True, is_last=True)
  assert np.isfinite(np.asarray(out)).all()


def test_qlora_over_int4_base():
  from xotorch_tpu.train.lora import add_lora_params
  cfg, params = _tiny()
  qparams = quantize_params(params, "int4", scale_dtype=jnp.float32)
  qparams = add_lora_params(qparams, rank=4, key=jax.random.PRNGKey(7))
  # Adapter shapes follow the LOGICAL in/out dims of the grouped base.
  H = cfg.hidden_size
  assert qparams["layers"]["lora_wq_a"].shape == (cfg.num_layers, H, 4)
  assert qparams["layers"]["lora_wq_b"].shape[-1] == qparams["layers"]["wq"].shape[-1]
  assert qparams["layers"]["lora_wq_a"].dtype == jnp.float32
  x = jnp.asarray([[3, 7, 11]], jnp.int32)
  cache = init_kv_cache(cfg, cfg.num_layers, 1, 16, jnp.float32)
  out, _ = forward_shard(qparams, x, cache, jnp.int32(0), cfg, True, True)
  assert np.isfinite(np.asarray(out)).all()


def test_qlora_train_step_updates_adapters_only():
  import optax
  from xotorch_tpu.train.lora import add_lora_params, lora_param_counts, masked_optimizer
  from xotorch_tpu.train.step import make_train_step, trainable_subtree
  cfg, params = _tiny()
  qparams = quantize_params(params, scale_dtype=jnp.float32)

  # A quantized base without adapters must be rejected (scales/norms would
  # train against immutable int8 weights).
  bare_step = make_train_step(cfg, optax.adamw(1e-2))
  with pytest.raises(ValueError, match="LoRA"):
    bare_step(qparams, optax.adamw(1e-2).init(trainable_subtree(qparams)), {
      "inputs": jnp.zeros((1, 4), jnp.int32), "targets": jnp.zeros((1, 4), jnp.int32),
      "lengths": jnp.asarray([4], jnp.int32),
    })

  qparams = add_lora_params(qparams, rank=4, key=jax.random.PRNGKey(7))
  assert qparams["layers"]["lora_wq_a"].dtype == jnp.float32  # NOT int8
  adapter, total = lora_param_counts(qparams)
  assert adapter < total * 0.2

  optimizer = masked_optimizer(optax.adamw(1e-2), qparams)
  step = make_train_step(cfg, optimizer)
  # opt_state lives over the float subtree: the int8 base is invisible to it.
  opt_state = optimizer.init(trainable_subtree(qparams))
  batch = {
    "inputs": jnp.asarray(np.random.RandomState(0).randint(0, 255, (2, 8)), jnp.int32),
    "targets": jnp.asarray(np.random.RandomState(1).randint(0, 255, (2, 8)), jnp.int32),
    "lengths": jnp.asarray([8, 8], jnp.int32),
  }
  p, opt_state, loss0 = step(qparams, opt_state, batch)
  losses = [float(loss0)]
  for _ in range(8):
    p, opt_state, loss = step(p, opt_state, batch)
    losses.append(float(loss))
  assert losses[-1] < losses[0], f"QLoRA loss did not decrease: {losses}"
  # The int8 base is bit-identical; only adapters moved.
  np.testing.assert_array_equal(np.asarray(p["layers"]["wq"]), np.asarray(qparams["layers"]["wq"]))
  assert not np.array_equal(np.asarray(p["layers"]["lora_wq_a"]),
                            np.asarray(qparams["layers"]["lora_wq_a"]))


async def test_engine_quantized_serving(tmp_path, monkeypatch):
  from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint
  from xotorch_tpu.download.shard_download import LocalShardDownloader
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine

  model_dir = make_hf_checkpoint(tmp_path, TINY_LLAMA_CFG, seed=3)
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  shard = Shard("m", 0, n - 1, n)
  tokens = np.array([[1, 5, 9, 200, 17]], dtype=np.int64)

  full = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32")
  out_f, _ = await full.infer_tensor("r", shard, tokens)

  quant = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32",
                                  quantize="int8")
  out_q, _ = await quant.infer_tensor("r", shard, tokens)
  assert out_q.shape == out_f.shape
  assert int(np.argmax(out_q[0, -1])) == int(np.argmax(out_f[0, -1]))

  # save_checkpoint of a quantized engine writes float safetensors (HF-layout,
  # loadable by stock tooling).
  ckpt = tmp_path / "ck" / "model.safetensors"
  await quant.save_checkpoint(shard, str(ckpt))
  from safetensors import safe_open
  with safe_open(str(ckpt), framework="np") as f:
    name = next(n for n in f.keys() if n.endswith("q_proj.weight"))
    assert f.get_tensor(name).dtype == np.float32


async def test_engine_quantized_full_train_rejected(tmp_path):
  from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint
  from xotorch_tpu.download.shard_download import LocalShardDownloader
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine

  model_dir = make_hf_checkpoint(tmp_path, TINY_LLAMA_CFG, seed=3)
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  shard = Shard("m", 0, n - 1, n)
  eng = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32",
                                quantize="int8")
  x = np.random.RandomState(0).randint(0, 255, (1, 8))
  with pytest.raises(ValueError, match="LoRA"):
    await eng.train_example("t", shard, x, x, np.array([8]))


@pytest.mark.parametrize("group_size", [64, 128])
@pytest.mark.parametrize("rows", [1, 3, 8])
def test_int4_pallas_matvec_matches_dequant(rows, group_size):
  """The decode-path Pallas int4 kernel (in-register nibble unpack,
  ops/int4_matmul.py) must match the full dequantize-then-matmul oracle
  exactly for 1..8 rows and non-trivial group counts."""
  from xotorch_tpu.models.quantize import dequantize_tensor_grouped, quantize_tensor_grouped
  from xotorch_tpu.ops.int4_matmul import int4_grouped_matmul

  w = jax.random.normal(jax.random.PRNGKey(5), (1, 256, 384), jnp.float32)
  q, gscale = quantize_tensor_grouped(w, scale_dtype=jnp.float32, group_size=group_size)
  ref_w = dequantize_tensor_grouped(q, gscale, jnp.float32)[0]  # [256, 384]
  with jax.default_matmul_precision("highest"):
    h = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(6), rows),
                          (rows, 256), jnp.float32)
    got = int4_grouped_matmul(h, q[0], gscale[0], block_out=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(h @ ref_w), atol=1e-4, rtol=1e-4)


def test_int8_rowquant_matvec_close_to_dequant():
  """The W8A8 decode kernel (ops/int8_matmul.py): int8 x int8 MXU dot with
  row-quantized activations must track the exact fused-dequant path to
  ~1% relative L2 (the A8 rounding budget) for 1..8 rows."""
  from xotorch_tpu.models.quantize import quantize_tensor
  from xotorch_tpu.ops.int8_matmul import int8_rowquant_matmul

  w = jax.random.normal(jax.random.PRNGKey(15), (256, 384), jnp.float32)
  q, scale = quantize_tensor(w, axis=0, scale_dtype=jnp.float32)
  ref_w = q.astype(jnp.float32) * scale  # exact dequant
  with jax.default_matmul_precision("highest"):
    for rows in (1, 3, 8):
      h = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(16), rows),
                            (rows, 256), jnp.float32)
      got = np.asarray(int8_rowquant_matmul(h, q, scale.reshape(-1), block_out=128))
      ref = np.asarray(h @ ref_w)
      err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
      assert err < 0.01, f"rows={rows}: rel L2 {err:.4f} exceeds the A8 budget"


async def _kernel_engine_stream(tmp_path, monkeypatch, quantize, env, value, steps=5):
  """Shared scaffold for the Pallas-kernel-vs-fallback engine stream tests:
  tiny checkpoint, greedy prefill + `steps` decode tokens through
  infer_sample_tensor under `env`=`value`."""
  from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint
  from xotorch_tpu.download.shard_download import LocalShardDownloader
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine

  model_dir = make_hf_checkpoint(tmp_path, TINY_LLAMA_CFG, seed=3)
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  shard = Shard("m", 0, n - 1, n)
  prompt = np.array([[1, 5, 9, 200, 17]], dtype=np.int64)
  monkeypatch.setenv(env, value)
  eng = JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}),
                                dtype="float32", quantize=quantize)
  tok, _ = await eng.infer_sample_tensor("r", shard, prompt, temp=0.0)
  toks = [int(tok)]
  for _ in range(steps):
    tok, _ = await eng.infer_sample_tensor("r", shard, np.asarray([[toks[-1]]]), temp=0.0)
    toks.append(int(tok))
  return toks


async def test_int8_kernel_engine_decode(tmp_path, monkeypatch):
  """XOT_INT8_KERNEL=force (W8A8, interpret off-TPU) through the engine:
  greedy stream identical to the fused-dequant path on the tiny model (A8
  rounding is far inside its argmax margins)."""
  off = await _kernel_engine_stream(tmp_path, monkeypatch, "int8", "XOT_INT8_KERNEL", "0")
  on = await _kernel_engine_stream(tmp_path, monkeypatch, "int8", "XOT_INT8_KERNEL", "force")
  assert on == off, f"int8 kernel stream {on} != fused-dequant {off}"


async def test_int4_kernel_engine_decode(tmp_path, monkeypatch):
  """XOT_INT4_KERNEL=force engages the Pallas int4 decode matvec off-TPU
  (interpret): the engine's greedy stream equals the einsum fallback's."""
  off = await _kernel_engine_stream(tmp_path, monkeypatch, "int4", "XOT_INT4_KERNEL", "0")
  on = await _kernel_engine_stream(tmp_path, monkeypatch, "int4", "XOT_INT4_KERNEL", "force")
  assert on == off, f"int4 kernel stream {on} != einsum {off}"
