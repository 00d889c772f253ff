"""The shard transformer: a pure function over a stacked-layer pytree.

TPU-first redesign of the reference's per-layer python module loop
(ShardTransformerDecoder, llm_utils.py:416-489; GeneralMHA,
general_mha.py:72-122):

- A shard's layers are STACKED along a leading axis and traversed with
  `lax.scan`, so XLA compiles ONE layer body regardless of shard depth —
  compile time is O(1) in layers and the whole shard is a single fused
  computation (no python in the hot loop).
- The KV cache is a static-shape [L, B, S, Hkv, D] buffer carried through the
  scan and kept resident in HBM by the engine; positions are integers and the
  causal mask is computed on device (nothing resized per request).
- First/last-shard special cases (embedding, final norm + lm_head) mirror the
  reference's `(hidden, None) | (None, logits)` contract
  (general_mha.py:246-249) as `is_first/is_last` static flags.

Dense and MoE blocks share the attention path; MoE is implemented for real
(the reference's MoE was dead stubs that mis-loaded through a dense builder,
llm_utils.py:502-590).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from xotorch_tpu.models.config import ModelConfig
from xotorch_tpu.ops.attention import gqa_attention
from xotorch_tpu.utils import knobs
from xotorch_tpu.ops.rope import apply_rope, rope_frequencies

Params = Dict[str, Any]


LORA_SCALE = 2.0  # alpha / r with alpha = 2r (train/lora.py builds the tensors)


def _maybe_lora(layer: Params, slot: str, h: jnp.ndarray, base_out: jnp.ndarray) -> jnp.ndarray:
  """base_out + scale * (h @ A) @ B when `slot` carries LoRA tensors. The
  presence check is static under jit — adapters change the traced graph, not
  a runtime branch, so un-adapted serving pays nothing."""
  a = layer.get(f"lora_{slot}_a")
  if a is None:
    return base_out
  delta = (h @ a) @ layer[f"lora_{slot}_b"]
  return base_out + delta.astype(base_out.dtype) * LORA_SCALE


def _linear(layer: Params, slot: str, h: jnp.ndarray, tp_mesh=None) -> jnp.ndarray:
  """h @ layer[slot], transparently dequantizing weight-only-quantized slots
  (models/quantize.py): presence of `<slot>_scale` (int8, per-out-channel)
  or `<slot>_gscale` (int4, group-wise) is a static pytree property, so the
  quantized graph is baked at trace time. XLA fuses the narrow->bf16 convert
  + scale into the dot's operand read — HBM streams int8/int4, the MXU
  computes bf16.

  `tp_mesh` (static): under a serving mesh the decode matvec Pallas kernels
  stand down — GSPMD has no partitioning rule for the custom call (the
  chip's compiler refuses it inside a multi-device jit), where the einsum
  forms below partition into per-shard partial dots."""
  w = layer[slot]
  gscale = layer.get(slot + "_gscale")
  if gscale is not None:
    # int4 group-wise: w is PACKED uint8 [G, gs/2, out] (two nibbles per
    # byte — models/quantize.pack_int4), gscale [G, out].
    B, T, _ = h.shape
    k4 = knobs.get_str("XOT_INT4_KERNEL")
    if (B * T <= 8 and tp_mesh is None
        and (k4 == "force" or (k4 != "0" and jax.default_backend() == "tpu"))):
      # Decode hot path ON REAL TPU: Pallas kernel (ops/int4_matmul.py)
      # unpacks the nibbles IN REGISTERS between the packed-tile read and
      # the MXU dot, so HBM streams the promised 0.5 bytes/param — XLA's
      # lowering of the unpack graph materializes the unpacked tensor,
      # erasing the format's bandwidth win (measured 230 -> 275 tok/s).
      # Off-TPU the kernel would run in interpret mode (far slower than
      # the einsum below).
      from xotorch_tpu.ops.int4_matmul import int4_grouped_matmul
      out = int4_grouped_matmul(h.reshape(B * T, h.shape[-1]), w, gscale)
      return out.reshape(B, T, -1).astype(h.dtype)
    # Prefill / wide batches: compute-bound, one materialized unpack
    # amortizes over the whole segment — per-group partial dots (K = gs =
    # 128, one MXU contraction tile) scaled then summed.
    from xotorch_tpu.models.quantize import unpack_int4
    w4 = unpack_int4(w)  # [G, gs, out] int8
    G, gs, _ = w4.shape
    hg = h.reshape(B, T, G, gs)
    partial = jnp.einsum("btgi,gio->btgo", hg, w4.astype(h.dtype))
    return jnp.einsum("btgo,go->bto", partial, gscale.astype(h.dtype))
  scale = layer.get(slot + "_scale")
  if scale is None:
    return h @ w
  B, T, _ = h.shape
  k8 = knobs.get_str("XOT_INT8_KERNEL")
  if (B * T <= 8 and tp_mesh is None
      and (k8 == "force" or (k8 == "1" and jax.default_backend() == "tpu"))):
    # Opt-in W8A8 decode path (ops/int8_matmul.py): the MXU consumes int8
    # weights directly (int32 accumulate) instead of the VPU running
    # convert+scale passes over every element first. Activations
    # row-quantize to int8 — approximate (~1/255), so the fused-dequant
    # path below stays the default; A/B'd on-chip via XOT_INT8_KERNEL.
    from xotorch_tpu.ops.int8_matmul import int8_rowquant_matmul
    out = int8_rowquant_matmul(h.reshape(B * T, h.shape[-1]), w, scale)
    return out.reshape(B, T, -1).astype(h.dtype)
  return (h @ w.astype(h.dtype)) * scale.astype(h.dtype)


def _tp_constraint(x: jnp.ndarray, tp_mesh, axis: int) -> jnp.ndarray:
  """Pin a tensor-parallel layout on an activation: `axis` sharded over the
  mesh's 'tp' axis, everything else replicated. Placed at the Megatron
  column→row boundaries (q/k/v heads after the projections, ffn columns
  after gate/up) so GSPMD's propagation keeps partial activations + ONE
  psum per block instead of resolving an unconstrained fixpoint to
  all-gather-the-columns-then-compute-replicated. Static no-op off-mesh or
  when the axis doesn't divide (degenerate tiny-model heads)."""
  if tp_mesh is None or "tp" not in tp_mesh.axis_names:
    return x
  tp = int(tp_mesh.shape["tp"])
  if tp <= 1 or x.shape[axis] % tp != 0:
    return x
  from jax.sharding import NamedSharding, PartitionSpec
  spec = [None] * x.ndim
  spec[axis % x.ndim] = "tp"
  return jax.lax.with_sharding_constraint(
    x, NamedSharding(tp_mesh, PartitionSpec(*spec)))


def _moe_einsum(layer: Params, slot: str, eq: str, h: jnp.ndarray) -> jnp.ndarray:
  """Expert einsum with the same static int8 dispatch; per-(expert, out)
  scales broadcast over the leading E axis of the 'e...' output."""
  w = layer[slot]
  scale = layer.get(slot + "_scale")
  if scale is None:
    return jnp.einsum(eq, h, w)
  out = jnp.einsum(eq, h, w.astype(h.dtype))
  return out * scale.astype(h.dtype)[:, None, None, :]


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float,
             offset: bool = False) -> jnp.ndarray:
  """offset=True is the gemma convention: weights are stored zero-centred
  and the norm multiplies by (1 + w), all in fp32 (HF GemmaRMSNorm)."""
  x32 = x.astype(jnp.float32)
  norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
  w32 = weight.astype(jnp.float32)
  if offset:
    w32 = 1.0 + w32
  return (norm * w32).astype(x.dtype)


def _mlp_act(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
  if cfg.hidden_act == "gelu_pytorch_tanh":
    return jax.nn.gelu(x, approximate=True)
  return jax.nn.silu(x)


def init_kv_cache(cfg: ModelConfig, num_layers: int, batch: int, max_seq: int, dtype=jnp.bfloat16,
                  kv_quant: bool = False) -> Dict[str, jnp.ndarray]:
  """KV buffers [L, B, S, Hkv, D]. kv_quant stores K/V as int8 with one
  scale per (position, head) — half the cache bandwidth and HBM per token;
  presence of the scale leaves is the static marker the forward dispatches
  on (same pattern as weight quantization)."""
  shape = (num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
  if not kv_quant:
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
  return {
    "k": jnp.zeros(shape, jnp.int8), "v": jnp.zeros(shape, jnp.int8),
    "k_scale": jnp.zeros(shape[:-1], dtype), "v_scale": jnp.zeros(shape[:-1], dtype),
  }


def _quantize_kv(x: jnp.ndarray, scale_dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """Per-(position, head) symmetric int8 over the head dim: [B,T,H,D] ->
  (int8 [B,T,H,D], scale [B,T,H]). Same math as the weight path — one
  quantizer, two tensor families."""
  from xotorch_tpu.models.quantize import quantize_tensor
  return quantize_tensor(x, axis=-1, scale_dtype=scale_dtype)


def _cache_write(layer_cache: Dict[str, jnp.ndarray], k: jnp.ndarray, v: jnp.ndarray,
                 start_pos: jnp.ndarray) -> Dict[str, jnp.ndarray]:
  """Insert fresh K/V at start_pos (scalar, or [B] per-row for continuous
  batching), quantizing on the way in when the cache is int8."""
  quant = "k_scale" in layer_cache
  new = {}
  entries = [("k", k), ("v", v)]
  if quant:
    qk, sk = _quantize_kv(k, layer_cache["k_scale"].dtype)
    qv, sv = _quantize_kv(v, layer_cache["v_scale"].dtype)
    entries = [("k", qk), ("v", qv), ("k_scale", sk), ("v_scale", sv)]
  for name, val in entries:
    buf = layer_cache[name]
    val = val.astype(buf.dtype)
    if jnp.ndim(start_pos) == 0:
      zeros = (0,) * (buf.ndim - 2)
      new[name] = jax.lax.dynamic_update_slice(buf, val, (0, start_pos) + zeros)
    else:
      row = jax.vmap(lambda c, x, sp: jax.lax.dynamic_update_slice(
        c, x, (sp,) + (0,) * (c.ndim - 1)))
      new[name] = row(buf, val, start_pos)
  return new


def _cache_read(layer_cache: Dict[str, jnp.ndarray], dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """(K, V) in compute dtype; int8 caches dequantize on read — XLA fuses the
  convert + scale into the attention operand stream, so HBM traffic stays
  int8."""
  k = layer_cache["k"].astype(dtype)
  v = layer_cache["v"].astype(dtype)
  if "k_scale" in layer_cache:
    k = k * layer_cache["k_scale"].astype(dtype)[..., None]
    v = v * layer_cache["v_scale"].astype(dtype)[..., None]
  return k, v


def _attention_block(
  layer: Params, x: jnp.ndarray, layer_cache: Dict[str, jnp.ndarray],
  positions: jnp.ndarray, kv_valid_len: jnp.ndarray, start_pos: jnp.ndarray,
  cfg: ModelConfig, inv_freq: jnp.ndarray, use_flash: bool = False,
  ring_mesh=None, use_flash_decode: bool = False,
  window: Optional[jnp.ndarray] = None,  # per-layer scalar, 0 = global
  page_table: Optional[jnp.ndarray] = None,  # [B, max_pages]: paged-KV decode
  paged_kernel: bool = False,
  ragged_prefill: bool = True,  # static: kernel prefill reads pages natively
  tp_mesh=None,  # static Mesh: activation constraints for tensor parallelism
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
  B, T, H = x.shape
  h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps, cfg.norm_offset)
  q = _maybe_lora(layer, "wq", h, _linear(layer, "wq", h, tp_mesh))
  k = _maybe_lora(layer, "wk", h, _linear(layer, "wk", h, tp_mesh))
  v = _maybe_lora(layer, "wv", h, _linear(layer, "wv", h, tp_mesh))
  if "bq" in layer:
    q = q + layer["bq"]
    k = k + layer["bk"]
    v = v + layer["bv"]
  q = _tp_constraint(q.reshape(B, T, cfg.num_heads, cfg.head_dim), tp_mesh, 2)
  k = _tp_constraint(k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim), tp_mesh, 2)
  v = _tp_constraint(v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim), tp_mesh, 2)
  if cfg.qk_norm:
    q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps, cfg.norm_offset)
  q = apply_rope(q, positions, inv_freq)
  k = apply_rope(k, positions, inv_freq)
  if page_table is not None:
    # Paged KV (engine XOT_PAGED_KV): layer_cache leaves are one layer's
    # slice of the shared page arena ([P, page, Hkv, D]); this request
    # batch reaches its tokens through `page_table`. The fresh K/V scatter
    # straight into pool pages — position p lands at table[p // page] slot
    # p % page — so decode appends AND prefill segments are page-native
    # (no contiguous buffer, no commit copy). Reads go through
    # ops/paged_attention, which stops at each ROW's occupied pages instead
    # of the batch maximum.
    from xotorch_tpu.ops.paged_attention import paged_decode_attention, paged_prefill_attention
    page = layer_cache["k"].shape[1]
    attn_scale_p = cfg.query_pre_attn_scalar ** -0.5 if cfg.query_pre_attn_scalar else None
    kv_quant_p = "k_scale" in layer_cache
    if kv_quant_p:
      # int8 arena: quantize the fresh K/V on the way in; payload AND
      # per-(position, head) scales scatter into the SAME (page, slot) —
      # scale pages are just one more arena leaf riding the scan.
      qk, sk = _quantize_kv(k, layer_cache["k_scale"].dtype)
      qv, sv = _quantize_kv(v, layer_cache["v_scale"].dtype)
      k, v = qk, qv
    if T == 1:
      # Decode step: [B] per-row positions (scalar normalised — a 1-token
      # paged prefill is the same write).
      sp = (jnp.full((B,), start_pos, jnp.int32) if jnp.ndim(start_pos) == 0
            else start_pos.astype(jnp.int32))
      # mode="clip": dummy pad rows (all-zero table, pos from 0) can step
      # their page index past the table width inside a chunk — clamping
      # keeps them on a real table slot, which for them is always the
      # scratch page.
      pidx = jnp.take_along_axis(page_table, (sp // page)[:, None], axis=1,
                                 mode="clip")[:, 0]
      off = sp % page
      new_cache = {
        "k": layer_cache["k"].at[pidx, off].set(k[:, 0].astype(layer_cache["k"].dtype)),
        "v": layer_cache["v"].at[pidx, off].set(v[:, 0].astype(layer_cache["v"].dtype)),
      }
      if kv_quant_p:
        new_cache["k_scale"] = layer_cache["k_scale"].at[pidx, off].set(
          sk[:, 0].astype(layer_cache["k_scale"].dtype))
        new_cache["v_scale"] = layer_cache["v_scale"].at[pidx, off].set(
          sv[:, 0].astype(layer_cache["v_scale"].dtype))
      layer_cache = new_cache
      attn = paged_decode_attention(
        q, layer_cache["k"], layer_cache["v"], page_table, kv_valid_len,
        softcap=cfg.attn_logit_softcap or 0.0, scale=attn_scale_p,
        use_kernel=paged_kernel, tp_mesh=tp_mesh, window=window,
        k_scale_pages=layer_cache.get("k_scale"),
        v_scale_pages=layer_cache.get("v_scale"))
    else:
      # Paged-native T>1 segment (prefill slice or draft-verify forward):
      # every position scatters into its own (page, slot). B == 1 by
      # contract (per-request prefill); the engine allocates the table to
      # cover the PADDED segment, so bucket-padding garbage lands in pages
      # this request owns (masked by kv_valid_len, overwritten by later
      # writes at the same positions).
      if B != 1:
        raise ValueError(f"paged prefill serves per-request segments (B == 1), got B={B}")
      pos_vec = positions[0].astype(jnp.int32)  # [T] absolute positions
      pidx = jnp.take(page_table[0], pos_vec // page, mode="clip")
      off = pos_vec % page
      new_cache = {
        "k": layer_cache["k"].at[pidx, off].set(k[0].astype(layer_cache["k"].dtype)),
        "v": layer_cache["v"].at[pidx, off].set(v[0].astype(layer_cache["v"].dtype)),
      }
      if kv_quant_p:
        new_cache["k_scale"] = layer_cache["k_scale"].at[pidx, off].set(
          sk[0].astype(layer_cache["k_scale"].dtype))
        new_cache["v_scale"] = layer_cache["v_scale"].at[pidx, off].set(
          sv[0].astype(layer_cache["v_scale"].dtype))
      layer_cache = new_cache
      attn = paged_prefill_attention(
        q, layer_cache["k"], layer_cache["v"], page_table, positions, kv_valid_len,
        softcap=cfg.attn_logit_softcap or 0.0, scale=attn_scale_p,
        use_kernel=paged_kernel, ragged=ragged_prefill, tp_mesh=tp_mesh,
        window=window,
        k_scale_pages=layer_cache.get("k_scale"),
        v_scale_pages=layer_cache.get("v_scale"))
    attn2d = _tp_constraint(
      attn.reshape(B, T, cfg.num_heads * cfg.head_dim), tp_mesh, 2)
    out = _maybe_lora(layer, "wo", attn2d, _linear(layer, "wo", attn2d, tp_mesh))
    if cfg.sandwich_norms:
      out = rms_norm(out, layer["post_attn_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    return out, layer_cache
  layer_cache = _cache_write(layer_cache, k, v, start_pos)
  kv_quant = "k_scale" in layer_cache
  if (window is not None or cfg.attn_logit_softcap or cfg.query_pre_attn_scalar) \
      and ring_mesh is not None:
    raise ValueError(
      "ring attention (sequence parallelism) does not support sliding-window "
      "/ attn-softcap / query_pre_attn_scalar configs (gemma2, windowed "
      "mistral) — it hardcodes the 1/sqrt(head_dim) score scale")
  # Static gemma-family score adjustments; None/0.0 for every other family,
  # so their compiled kernels are unchanged.
  attn_scale = cfg.query_pre_attn_scalar ** -0.5 if cfg.query_pre_attn_scalar else None
  if use_flash:
    # Prefill-from-zero fast path (engine guarantees start_pos == 0): the
    # fresh segment IS the whole visible context, and relative == absolute
    # positions, so the Pallas kernel's in-segment causal mask is exact.
    # Attends over the FRESH k/v (never reads the cache), so it composes
    # with an int8 cache unchanged. The per-layer window rides in as a
    # traced scalar (0 = global) — sliding and global layers share one
    # kernel, and out-of-window kv blocks are never DMA'd.
    from xotorch_tpu.ops.flash_attention import flash_attention
    attn = flash_attention(q, k, v, window=window, softcap=cfg.attn_logit_softcap,
                           scale=attn_scale, tp_mesh=tp_mesh)
  elif use_flash_decode:
    # Decode steps and chunked-prefill segments over a long resident cache:
    # Pallas kernel whose cost is proportional to the OCCUPIED prefix
    # (blocks past the causally visible region are never DMA'd) and whose
    # scores never leave VMEM — no [T, S] materialisation
    # (ops/flash_decode.py). q_start is already per-row. An int8 cache
    # passes its raw buffers + per-(position, head) scales and dequantizes
    # IN-KERNEL per tile — HBM streams int8 bytes AND keeps the
    # occupancy/window DMA elision (the XLA path fused the dequant but read
    # the entire static buffer). With a sliding window the visible range
    # shrinks to min(window, occupied): blocks below the window re-map too.
    from xotorch_tpu.ops.flash_decode import flash_cached_attention
    q_start = (jnp.full((B,), start_pos, dtype=jnp.int32) if jnp.ndim(start_pos) == 0
               else start_pos.astype(jnp.int32))
    if kv_quant:
      kb, vb = layer_cache["k"], layer_cache["v"]  # raw int8
    else:
      kb, vb = layer_cache["k"].astype(q.dtype), layer_cache["v"].astype(q.dtype)
    attn = flash_cached_attention(q, kb, vb, q_start,
                                  window=window, softcap=cfg.attn_logit_softcap,
                                  scale=attn_scale,
                                  k_scale=layer_cache.get("k_scale"),
                                  v_scale=layer_cache.get("v_scale"), tp_mesh=tp_mesh)
  elif ring_mesh is not None:
    # Sequence-parallel training path (start_pos == 0, T sharded over 'sp'):
    # ring attention rotates KV chunks over ICI instead of materialising the
    # full sequence on every device.
    from xotorch_tpu.ops.ring_attention import ring_attention_sharded
    attn = ring_attention_sharded(q, k, v, ring_mesh)
  else:
    k_all, v_all = _cache_read(layer_cache, q.dtype)
    attn = gqa_attention(q, k_all, v_all, positions, kv_valid_len,
                         scale=attn_scale, softcap=cfg.attn_logit_softcap, window=window)
  attn2d = _tp_constraint(
    attn.reshape(B, T, cfg.num_heads * cfg.head_dim), tp_mesh, 2)
  out = _maybe_lora(layer, "wo", attn2d, _linear(layer, "wo", attn2d, tp_mesh))
  if cfg.sandwich_norms:
    out = rms_norm(out, layer["post_attn_norm"], cfg.rms_norm_eps, cfg.norm_offset)
  return out, layer_cache


def _dense_mlp(layer: Params, h: jnp.ndarray, cfg: ModelConfig,
               tp_mesh=None) -> jnp.ndarray:
  gate = _mlp_act(cfg, _tp_constraint(
    _maybe_lora(layer, "w_gate", h, _linear(layer, "w_gate", h, tp_mesh)), tp_mesh, -1))
  up = gate * _tp_constraint(
    _maybe_lora(layer, "w_up", h, _linear(layer, "w_up", h, tp_mesh)), tp_mesh, -1)
  return _maybe_lora(layer, "w_down", up, _linear(layer, "w_down", up, tp_mesh))


def _moe_take(layer: Params, slot: str, idx: jnp.ndarray, eq: str, x: jnp.ndarray) -> jnp.ndarray:
  """Routed expert einsum: gather ONLY the chosen experts' weight slices
  (`idx` [N, k] expert ids) and contract. int8 experts dequantize via their
  gathered per-(expert, out) scales — HBM streams just the selected experts'
  bytes, which is the whole point of the routed path."""
  w = jnp.take(layer[slot], idx, axis=0)  # [N, k, ...]
  scale = layer.get(slot + "_scale")
  if scale is None:
    return jnp.einsum(eq, x, w)
  out = jnp.einsum(eq, x, w.astype(x.dtype))
  return out * jnp.take(scale, idx, axis=0).astype(x.dtype)


def _moe_mlp_routed(layer: Params, h: jnp.ndarray, cfg: ModelConfig,
                    top_vals: jnp.ndarray, top_idx: jnp.ndarray) -> jnp.ndarray:
  """Top-k ROUTED expert compute for decode-sized inputs: gather the k chosen
  experts' weights per token and run only those, so a decode step streams
  k experts' bytes from HBM instead of all E (qwen3-30b-a3b: 8 of 128 —
  ~16x fewer expert bytes/FLOPs per token than the dense-combine form the
  round-3 serving path used everywhere, VERDICT r3 #6). Same math as the
  dense combine (the E-k dropped terms are exactly zero there), so greedy
  streams agree."""
  B, T, H = h.shape
  N, k = B * T, top_idx.shape[-1]
  x = h.reshape(N, H)
  idx = top_idx.reshape(N, k)
  vals = top_vals.reshape(N, k).astype(h.dtype)
  gate = jax.nn.silu(_moe_take(layer, "we_gate", idx, "nh,nkhi->nki", x))
  up = _moe_take(layer, "we_up", idx, "nh,nkhi->nki", x)
  down = _moe_take(layer, "we_down", idx, "nki,nkih->nkh", gate * up)
  return jnp.einsum("nkh,nk->nh", down, vals).reshape(B, T, H)


# Decode-sized inputs (B*T at or under this) take the routed gather path;
# prefill segments are always bucketed to >= 16 tokens and stay dense.
_MOE_ROUTED_MAX_TOKENS = 8


def _moe_mlp(layer: Params, h: jnp.ndarray, cfg: ModelConfig,
             moe_routed: bool = True) -> jnp.ndarray:
  """Correct top-k MoE (qwen3-moe style), two regimes:

  - decode (B*T <= 8, `moe_routed`): gather-and-compute ONLY the top-k
    experts (_moe_mlp_routed) — bytes/token drop from E experts to k.
  - prefill / `moe_routed=False`: dense-combine — every expert computed,
    non-selected terms zeroed by the combine weights. Exact, and the form
    GSPMD partitions cleanly over an 'ep' mesh axis (each device computes
    its RESIDENT experts, the combine einsum implies the psum): the engine
    passes moe_routed=False when serving over an ep mesh, where a gather
    across the sharded E axis would make XLA all-gather the expert weights.
  """
  B, T, H = h.shape
  router_logits = (h.astype(jnp.float32) @ layer["router"].astype(jnp.float32))  # [B,T,E]
  probs = jax.nn.softmax(router_logits, axis=-1)
  top_vals, top_idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
  if cfg.norm_topk_prob:
    top_vals = top_vals / top_vals.sum(axis=-1, keepdims=True)
  if moe_routed and B * T <= _MOE_ROUTED_MAX_TOKENS:
    return _moe_mlp_routed(layer, h, cfg, top_vals, top_idx)
  combine = jnp.zeros_like(probs)
  combine = jnp.put_along_axis(combine, top_idx, top_vals, axis=-1, inplace=False)  # [B,T,E]
  gate = jax.nn.silu(_moe_einsum(layer, "we_gate", "bth,ehi->ebti", h))
  up = _moe_einsum(layer, "we_up", "bth,ehi->ebti", h)
  expert_out = _moe_einsum(layer, "we_down", "ebti,eih->ebth", gate * up)
  return jnp.einsum("ebth,bte->bth", expert_out, combine.astype(h.dtype))


def forward_shard(
  params: Params,
  x: jnp.ndarray,  # [B, T] int32 tokens (first shard) or [B, T, H] hidden
  cache: Dict[str, jnp.ndarray],
  start_pos: jnp.ndarray,  # scalar int32: absolute position of x[:, 0]
  cfg: ModelConfig,
  is_first: bool,
  is_last: bool,
  use_flash: bool = False,
  ring_mesh=None,
  use_flash_decode: bool = False,
  start_layer: int = 0,
  moe_routed: bool = True,
  page_table: Optional[jnp.ndarray] = None,  # [B, max_pages]: paged-KV decode
  paged_kernel: bool = False,
  ragged_prefill: bool = True,  # static: kernel prefill reads pages natively
  tp_mesh=None,  # static Mesh: activation constraints for tensor parallelism
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
  """Run one shard. Returns (hidden or fp32 logits, updated cache).

  With `page_table`, `cache` is the shared page ARENA (leaves
  [L, num_pages, page_size, Hkv, D] — paged_cache.PagePool). Decode steps
  (T == 1, [B] per-row start_pos) write into each row's current page and
  attend only its occupied pages; prefill segments (T > 1, B == 1, scalar
  start_pos) scatter every position straight into its page — paged-NATIVE
  prefill, no contiguous buffer and no commit copy (ops/paged_attention).
  The page table is closed over rather than scanned (it has no L axis).

  moe_routed (static): decode-sized MoE inputs take the top-k gather path;
  the engine passes False when expert weights are sharded over an 'ep' mesh
  axis (see _moe_mlp).

  tp_mesh (static, hashable — same pattern as ring_mesh): the serving mesh
  when this executable runs SPMD over more than one device. Activations get
  explicit with_sharding_constraint pins at the Megatron column→row
  boundaries (_tp_constraint; a no-op without a tp axis wider than 1) so
  GSPMD keeps heads/ffn columns sharded instead of all-gathering; every
  attention Pallas kernel runs per device via shard_map over head-sliced
  operands (parallel.mesh.per_shard_kernel), and the quantized matvec
  kernels stand down (_linear). Ignored on ring (sequence-parallel)
  executables, whose activations shard over 'sp'.

  cfg/is_first/is_last/use_flash/use_flash_decode must be static under jit;
  start_pos is traced so one executable serves every decode step. use_flash
  selects the Pallas prefill kernel (ops/flash_attention.py) and is only
  valid when start_pos == 0; use_flash_decode selects the occupancy-aware
  Pallas cached-attention kernel (ops/flash_decode.py), valid for decode
  steps (T == 1) and pos>0 chunked-prefill segments (T > 1) — the engine
  picks the right executable per call.

  start_layer (static): ABSOLUTE index of this shard's first layer — only
  consulted by sliding-window families, where which layers slide is a
  property of the absolute layer index (gemma2 alternates), so a mid-ring
  shard must know where it sits.
  """
  if ring_mesh is not None:
    # Ring (sequence-parallel) executables shard activations over 'sp' along
    # T; pinning a tp-only layout on them would force an sp all-gather.
    tp_mesh = None
  if is_first:
    emb = params["embed"]["embedding"]
    row_scale = params["embed"].get("embedding_scale")
    if row_scale is None:
      h = jnp.take(emb, x, axis=0)
    else:
      # int8 table: each looked-up row rescales by its own per-row scale
      # (models/quantize.py) — compute dtype comes from the scale.
      h = (jnp.take(emb, x, axis=0).astype(row_scale.dtype)
           * jnp.take(row_scale, x, axis=0)[..., None])
    if cfg.scale_embedding:
      # Gemma normalises embeddings by sqrt(hidden); HF rounds the
      # normaliser to the compute dtype first — match that exactly.
      h = h * jnp.asarray(cfg.hidden_size ** 0.5, h.dtype)
  else:
    h = x
  B, T = h.shape[0], h.shape[1]
  if jnp.ndim(start_pos) == 0:
    positions = (start_pos + jnp.arange(T, dtype=jnp.int32))[None, :].repeat(B, axis=0)
    kv_valid_len = jnp.full((B,), start_pos + T, dtype=jnp.int32)
  else:
    # [B] start positions: each batch row is an independent request at its
    # own depth (continuous batching of concurrent decodes).
    positions = start_pos.astype(jnp.int32)[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    kv_valid_len = start_pos.astype(jnp.int32) + T
  inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)

  # Per-layer sliding windows ride the scan as one more xs leaf ([L] int32,
  # 0 = global) — the scan still compiles ONE layer body; the window is a
  # traced scalar inside it, so alternating gemma2 layers share the graph.
  L = jax.tree.leaves(params["layers"])[0].shape[0]
  windows = None
  if cfg.uses_sliding_window:
    import numpy as _np
    windows = jnp.asarray(
      _np.array([cfg.layer_window(start_layer + i) for i in range(L)], _np.int32))
  def layer_body(h, xs):
    if windows is None:
      layer, layer_cache = xs
      window = None
    else:
      layer, layer_cache, window = xs
    attn_out, layer_cache = _attention_block(
      layer, h, layer_cache, positions, kv_valid_len, start_pos, cfg, inv_freq, use_flash,
      ring_mesh, use_flash_decode, window=window,
      page_table=page_table, paged_kernel=paged_kernel, ragged_prefill=ragged_prefill,
      tp_mesh=tp_mesh,
    )
    h = h + attn_out
    mlp_in = rms_norm(h, layer["mlp_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    mlp_out = (_moe_mlp(layer, mlp_in, cfg, moe_routed=moe_routed) if cfg.is_moe
               else _dense_mlp(layer, mlp_in, cfg, tp_mesh=tp_mesh))
    if cfg.sandwich_norms:
      mlp_out = rms_norm(mlp_out, layer["post_mlp_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    return h + mlp_out, layer_cache

  # The cache dict rides the scan as a pytree: each leaf's leading L axis is
  # sliced per layer, so int8 caches (extra scale leaves) need no special
  # casing anywhere downstream.
  xs = (params["layers"], cache) if windows is None else (params["layers"], cache, windows)
  h, new_cache = jax.lax.scan(layer_body, h, xs)

  if not is_last:
    return h, new_cache
  return unembed(params, h, cfg), new_cache


def unembed(params: Params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
  """Final norm + (tied-embedding or lm_head) unembedding -> fp32 logits.
  The single source of truth shared by forward_shard and the fused sampling
  path (models/generate.forward_sample)."""
  h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps, cfg.norm_offset)
  if cfg.tie_word_embeddings and "lm_head" not in params:
    emb = params["embed"]["embedding"]
    row_scale = params["embed"].get("embedding_scale")
    if row_scale is None:
      logits = h @ emb.T
    else:
      # Tied int8 table: the per-row scale becomes a per-vocab-column scale.
      logits = (h @ emb.astype(h.dtype).T) * row_scale.astype(h.dtype)[None, None, :]
  else:
    head_scale = params.get("lm_head_scale")
    if head_scale is None:
      logits = h @ params["lm_head"]
    else:
      logits = (h @ params["lm_head"].astype(h.dtype)) * head_scale.astype(h.dtype)[None, None, :]
  logits = logits.astype(jnp.float32)
  if cfg.final_logit_softcap:
    cap = jnp.float32(cfg.final_logit_softcap)
    logits = jnp.tanh(logits / cap) * cap
  return logits


def init_random_params(
  cfg: ModelConfig, num_local_layers: int, is_first: bool, is_last: bool,
  key: jax.Array, dtype=jnp.float32, scale: float = 0.02, start_layer: int = 0,
) -> Params:
  """Random-initialised shard params in the stacked layout (tests, benches,
  and training-from-scratch).

  Per-tensor keys are folded from (absolute layer index, tensor slot), so a
  shard generating layers [a, b] gets bit-identical weights to the same
  layers of a full-model init — ring peers agree on synthetic weights without
  ever materialising the whole model (HBM stays shard-sized).
  """
  H, D = cfg.hidden_size, cfg.head_dim
  I = cfg.intermediate_size
  E, MI = cfg.num_experts, cfg.moe_intermediate_size or I

  def rnd(k, *shape):
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

  def layer_params(abs_idx: int) -> Params:
    def lk(slot: int):
      return jax.random.fold_in(jax.random.fold_in(key, abs_idx), slot)
    norm_init = jnp.zeros if cfg.norm_offset else jnp.ones
    p: Params = {
      "attn_norm": norm_init((H,), dtype),
      "mlp_norm": norm_init((H,), dtype),
      "wq": rnd(lk(0), H, cfg.num_heads * D),
      "wk": rnd(lk(1), H, cfg.num_kv_heads * D),
      "wv": rnd(lk(2), H, cfg.num_kv_heads * D),
      "wo": rnd(lk(3), cfg.num_heads * D, H),
    }
    if cfg.sandwich_norms:
      p["post_attn_norm"] = norm_init((H,), dtype)
      p["post_mlp_norm"] = norm_init((H,), dtype)
    if cfg.attention_bias:
      p["bq"] = jnp.zeros((cfg.num_heads * D,), dtype)
      p["bk"] = jnp.zeros((cfg.num_kv_heads * D,), dtype)
      p["bv"] = jnp.zeros((cfg.num_kv_heads * D,), dtype)
    if cfg.qk_norm:
      p["q_norm"] = jnp.ones((D,), dtype)
      p["k_norm"] = jnp.ones((D,), dtype)
    if cfg.is_moe:
      p["router"] = rnd(lk(4), H, E)
      p["we_gate"] = rnd(lk(5), E, H, MI)
      p["we_up"] = rnd(lk(6), E, H, MI)
      p["we_down"] = rnd(lk(7), E, MI, H)
    else:
      p["w_gate"] = rnd(lk(4), H, I)
      p["w_up"] = rnd(lk(5), H, I)
      p["w_down"] = rnd(lk(6), I, H)
    return p

  per_layer = [layer_params(start_layer + i) for i in range(num_local_layers)]
  layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)

  params: Params = {"layers": layers}
  embed_key = jax.random.fold_in(key, 1_000_000)
  if is_first or cfg.tie_word_embeddings:
    params["embed"] = {"embedding": rnd(embed_key, cfg.vocab_size, H)}
  if is_last:
    params["final_norm"] = (jnp.zeros if cfg.norm_offset else jnp.ones)((H,), dtype)
    if not cfg.tie_word_embeddings:
      params["lm_head"] = rnd(jax.random.fold_in(key, 1_000_001), H, cfg.vocab_size)
  return params
