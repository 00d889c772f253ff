"""Weight-only int8 quantization: 2x the batch-1 decode roofline.

Single-stream decode must stream every weight byte from HBM once per token,
so at bf16 a 1.24B-param model caps at ~330 tok/s on a v5e (819 GB/s / 2.47
GB — the VERDICT r2 roofline math). Storing weights as per-output-channel
symmetric int8 halves the bytes per token; XLA fuses the int8->bf16 convert
and the channel-scale multiply into the matmul's operand read, so HBM traffic
really is int8 and the MXU still sees bf16 operands.

Design:
- A quantized projection is two sibling leaves in the same pytree slot the
  bf16 tensor occupied: `<slot>` becomes int8 with the SAME shape, and
  `<slot>_scale` holds the per-output-channel scale (compute dtype). The
  forward helpers in models/transformer.py dispatch on the presence of the
  scale leaf — a static pytree property, so the choice is baked into the
  traced graph with zero runtime branching.
- Scales reduce over the INPUT axis (the contraction axis), one scale per
  output channel: `y = (x @ q) * scale` is exact in the scale and rounds only
  the weights, the standard weight-only scheme.
- The embedding table quantizes per ROW (per vocab entry): a row lookup
  rescales by its own scale, and for tied-embedding models the same row scale
  column-scales the unembedding logits — one table serves both directions.
- Norms, biases, the MoE router, and LoRA adapters stay in compute dtype:
  they are O(hidden) bytes (nothing vs the matmuls) and carry outsized
  numerical leverage.

No reference counterpart: the reference serves torch fp16/bf16 only
(/root/reference/xotorch/inference/torch/sharded_inference_engine.py:58-65);
this is capability beyond parity, aimed at the "or beats" half of the bar.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# Stacked-layer matmul slots ([L, in, out] / [L, E, in, out]) that carry the
# model's bytes. Keys absent from a layer dict are skipped, so one list
# covers dense, MoE, biased (qwen2) and qk-norm variants.
LAYER_SLOTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "we_gate", "we_up", "we_down")

# int4's STORED dtype is uint8 (two nibbles per byte, pack_int4) -- native
# S4 arrays crossing jit boundaries are unsupported on some backends.
QUANT_DTYPES = {"int8": jnp.int8, "int4": jnp.uint8}

# int4 quantizes GROUP-WISE along the contraction axis (per-channel is too
# coarse at 4 bits): weight [.., in, out] reshapes to [.., G, gs, out] with
# one scale per (group, out-channel). 128 matches the MXU contraction tile.
INT4_GROUP_SIZE = 128

# int4 keeps these at int8: embedding/lm_head rows carry outsized numerical
# leverage, and the MoE expert einsum doesn't need a third layout variant.
_INT4_LAYER_SLOTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_tensor(w: jnp.ndarray, axis: int, dtype=jnp.int8,
                    scale_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """Symmetric per-channel quantization reducing over `axis` (the matmul
  contraction axis). Returns (q, scale) with scale squeezed over `axis`."""
  qmax = float(jnp.iinfo(dtype).max)
  w32 = w.astype(jnp.float32)
  scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / qmax
  scale = jnp.maximum(scale, 1e-12)  # all-zero channels quantize to zeros
  q = jnp.clip(jnp.round(w32 / scale), -qmax, qmax).astype(dtype)
  return q, jnp.squeeze(scale, axis=axis).astype(scale_dtype)


def dequantize_tensor(q: jnp.ndarray, scale: jnp.ndarray, axis: int,
                      dtype=jnp.bfloat16) -> jnp.ndarray:
  """Inverse of quantize_tensor (tests and checkpoint save-back)."""
  return (q.astype(jnp.float32) * jnp.expand_dims(scale.astype(jnp.float32), axis)).astype(dtype)


def _group_size(d_in: int, group_size: int = INT4_GROUP_SIZE) -> int:
  """Largest usable group: `group_size` when it divides the contraction dim,
  else the whole dim (degrades to per-channel — tiny test models)."""
  return group_size if d_in % group_size == 0 else d_in


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
  """Pack int4 values (int32 in [-8, 7], [..., gs, out]) into uint8 nibble
  pairs along the group axis -> [..., gs // 2, out]: element 2i rides the
  LOW nibble, 2i+1 the high. uint8 is the STORED dtype everywhere — a
  native int4 (S4) array crossing a jit boundary is unsupported on some
  backends, while uint8 is universal and streams the same 0.5 bytes/param
  from HBM."""
  *lead, gs, d_out = q.shape
  pairs = q.reshape(*lead, gs // 2, 2, d_out)
  lo = pairs[..., 0, :] & 0xF
  hi = pairs[..., 1, :] & 0xF
  return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
  """Inverse of pack_int4: [..., gs // 2, out] uint8 -> [..., gs, out] int8
  in [-8, 7]. Runs INSIDE compiled graphs (transformer._linear): XLA fuses
  the shift/mask/sign-extend into the dot's operand read, so HBM streams
  the packed bytes and the MXU sees bf16."""
  lo = (packed & 0xF).astype(jnp.int8)
  hi = (packed >> 4).astype(jnp.int8)
  lo = jnp.where(lo > 7, lo - 16, lo)
  hi = jnp.where(hi > 7, hi - 16, hi)
  *lead, gs_half, d_out = packed.shape
  return jnp.stack([lo, hi], axis=-2).reshape(*lead, gs_half * 2, d_out)


def quantize_tensor_grouped(w: jnp.ndarray, scale_dtype=jnp.bfloat16,
                            group_size: int = INT4_GROUP_SIZE) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """Group-wise symmetric int4 quantization of a stacked weight
  [L, in, out] -> (packed uint8 [L, G, gs // 2, out], scale [L, G, out]).
  The contraction axis splits into groups; each (group, out-channel) gets
  its own scale; values pack two-per-byte (pack_int4)."""
  L, d_in, d_out = w.shape
  gs = _group_size(d_in, group_size)
  qmax = 7.0
  wg = w.astype(jnp.float32).reshape(L, d_in // gs, gs, d_out)
  scale = jnp.max(jnp.abs(wg), axis=2, keepdims=True) / qmax
  scale = jnp.maximum(scale, 1e-12)
  q = jnp.clip(jnp.round(wg / scale), -qmax, qmax).astype(jnp.int32)
  return pack_int4(q), jnp.squeeze(scale, axis=2).astype(scale_dtype)


def dequantize_tensor_grouped(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
  """Inverse of quantize_tensor_grouped: packed [L, G, gs // 2, out] ->
  [L, in, out]."""
  unpacked = unpack_int4(q)
  L, G, gs, d_out = unpacked.shape
  w = unpacked.astype(jnp.float32) * scale.astype(jnp.float32)[:, :, None, :]
  return w.reshape(L, G * gs, d_out).astype(dtype)


def _contraction_axis(slot: str, ndim: int) -> int:
  """Input (contraction) axis of a stacked weight: [L, in, out] -> 1,
  MoE [L, E, in, out] -> 2, except *_down whose input axis is the expert
  intermediate — same position, so position is uniform: ndim - 2."""
  return ndim - 2


def quantize_params(params: Dict[str, Any], fmt: str = "int8",
                    scale_dtype=jnp.bfloat16) -> Dict[str, Any]:
  """Quantize a shard pytree in place of its bf16 matmul weights.

  Embedding/lm_head are included: for a 1B-class model the 128k-vocab
  embedding is ~20% of all bytes. Returns a NEW pytree (leaves shared where
  unquantized). Idempotent: already-int8 leaves are left alone.
  """
  if fmt not in QUANT_DTYPES:
    raise ValueError(f"Unsupported quantization format {fmt!r}; have {sorted(QUANT_DTYPES)}")
  int4 = fmt == "int4"

  out: Dict[str, Any] = dict(params)
  layers = dict(params["layers"])
  for slot in LAYER_SLOTS:
    w = layers.get(slot)
    # uint8 = the packed-int4 container; gscale presence marks it even if a
    # caller passes a rebuilt tree.
    if (w is None or w.dtype in (jnp.int8, jnp.uint8)
        or slot + "_gscale" in layers):
      continue
    if (int4 and slot in _INT4_LAYER_SLOTS
        and _group_size(w.shape[-2]) % 2 == 0):  # nibble pairs need even groups
      q, gscale = quantize_tensor_grouped(w, scale_dtype)
      layers[slot] = q
      layers[slot + "_gscale"] = gscale
    else:
      # int8 per-channel — also the int4 format's fallback for MoE experts.
      q, scale = quantize_tensor(w, _contraction_axis(slot, w.ndim), jnp.int8, scale_dtype)
      layers[slot] = q
      layers[slot + "_scale"] = scale
  out["layers"] = layers

  embed = params.get("embed")
  if embed is not None and embed["embedding"].dtype != jnp.int8:
    w = embed["embedding"]  # [vocab, H]: per-row scale serves take AND tied unembed
    q, scale = quantize_tensor(w, 1, jnp.int8, scale_dtype)
    out["embed"] = {"embedding": q, "embedding_scale": scale}

  head = params.get("lm_head")
  if head is not None and head.dtype != jnp.int8:
    q, scale = quantize_tensor(head, 0, jnp.int8, scale_dtype)  # [H, vocab] -> scale [vocab]
    out["lm_head"] = q
    out["lm_head_scale"] = scale
  return out


def dequantize_params(params: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
  """Rebuild a compute-dtype pytree from a quantized one (checkpoint
  save-back: save_shard_params writes HF-layout tensors, which must stay
  loadable by stock tooling, not carry a private int8 format)."""
  out: Dict[str, Any] = dict(params)
  layers = dict(params["layers"])
  for slot in LAYER_SLOTS:
    gscale = layers.pop(slot + "_gscale", None)
    if gscale is not None:
      layers[slot] = dequantize_tensor_grouped(layers[slot], gscale, dtype)
      continue
    scale = layers.pop(slot + "_scale", None)
    if scale is None:
      continue
    w = layers[slot]
    layers[slot] = dequantize_tensor(w, scale, _contraction_axis(slot, w.ndim), dtype)
  out["layers"] = layers
  embed = params.get("embed")
  if embed is not None and "embedding_scale" in embed:
    out["embed"] = {"embedding": dequantize_tensor(embed["embedding"], embed["embedding_scale"], 1, dtype)}
  scale = out.pop("lm_head_scale", None)
  if scale is not None:
    out["lm_head"] = dequantize_tensor(params["lm_head"], scale, 0, dtype)
  return out


def is_quantized(params: Dict[str, Any]) -> bool:
  return (any(k.endswith("_scale") or k.endswith("_gscale") for k in params.get("layers", {}))
          or "lm_head_scale" in params)


def quantized_bytes(params: Dict[str, Any]) -> int:
  """Actual HBM bytes of a param pytree (roofline math for quantized benches
  — n_params * 2 overstates an int8 model by ~2x). int4 counts as packed
  half-bytes (int4 slots are packed uint8, two values
  per byte, so plain itemsize accounting is exact)."""
  total = 0
  for x in jax.tree.leaves(params):
    total += x.size * x.dtype.itemsize
  return total
