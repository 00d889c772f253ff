"""Pallas TPU flash attention (causal, GQA) for the prefill hot path.

The reference materialises a full [T, S] boolean mask on the host and runs
torch SDPA over it per shard (sharded_inference_engine.py:144-186); here the
prefill attention is a single Pallas kernel: tiled over (batch, q-head,
q-block, kv-block) with the online-softmax recurrence, scores never leave
VMEM, and fully-masked kv blocks above the causal diagonal are skipped.

Sliding windows (gemma2 alternating layers, windowed mistral) are supported
with the window size as a SCALAR-PREFETCH operand: the per-layer window is a
traced value inside the layer scan, so one compiled kernel serves sliding
and global layers alike (window 0 = global), and kv blocks fully below the
window re-map in the BlockSpec index — Pallas elides the DMA, so
out-of-window cache is never fetched, not just masked. Gemma2's tanh score
soft-cap and query_pre_attn_scalar score scale are compile-time constants.

Scope: self-attention over the freshly projected K/V of the prefill segment
(positions [0, T)), which is exactly the engine's prefill call — decode steps
(T == 1) and any resumed-from-nonzero-position path use the cached-attention
kernel in ops/flash_decode.py or the XLA baseline in ops/attention.py
(engine._infer_sync picks per call).

On CPU (tests, dev laptops) the kernel runs in Pallas interpret mode so the
same code path is exercised without a TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _softcap(s, cap: float):
  if cap:
    s = jnp.tanh(s * (1.0 / cap)) * cap
  return s




def _mxu_operand(x):
  """MXU-ready operand dtype: bf16/f32 stay native (full-rate MXU, f32
  accumulate via preferred_element_type); float16 — which Mosaic's matmul
  lowering does not reliably support on all TPU generations — upcasts."""
  return x.astype(jnp.float32) if x.dtype == jnp.float16 else x


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, block_q, block_k,
                  scale, softcap):
  """Grid = (B, Hq, nQ, nK); nK innermost so the scratch accumulators carry
  the online-softmax state across kv blocks of one (b, h, i) triple."""
  i = pl.program_id(2)
  j = pl.program_id(3)
  n_k = pl.num_programs(3)

  @pl.when(j == 0)
  def _init():
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

  # Causal block skip: kv block j is visible to q block i iff its first key
  # position <= the last query position of block i.
  q_last = (i + 1) * block_q - 1

  @pl.when(j * block_k <= q_last)
  def _compute():
    # NATIVE-dtype operands with f32 accumulation: casting bf16 q/k/v up to
    # f32 before the dot halves the MXU rate for zero accuracy gain (the
    # accumulator is f32 either way) — on prefill, attention FLOPs are the
    # MFU bill. Stats (max/exp/l/acc) stay f32.
    q = _mxu_operand(q_ref[0, 0])  # [block_q, D]
    k = _mxu_operand(k_ref[0, 0])  # [block_k, D]
    v = _mxu_operand(v_ref[0, 0])  # [block_k, D]

    s = jax.lax.dot_general(
      q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [block_q, block_k] f32
    s = _softcap(s, softcap)

    # Elementwise causal mask (only the diagonal blocks actually cut).
    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[:, :1]  # [block_q, 1] (lane-replicated scratch, col 0)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)

    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    # P in v's dtype for the second MXU dot (standard flash practice:
    # probabilities are in [0, 1] where bf16 is dense; accumulate is f32).
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
      p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

  @pl.when(j == n_k - 1)
  def _finalize():
    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows cannot occur under causality; belt+braces
    o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _flash_kernel_windowed(win_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                           *, block_q, block_k, scale, softcap):
  """Sliding-window variant: win_ref is the scalar-prefetch window ([1]
  int32, 0 = global — the per-LAYER value, traced, so gemma2's alternating
  layers run this one kernel). Adds the window lower bound to the causal
  mask and skips kv blocks entirely below it."""
  i = pl.program_id(2)
  j = pl.program_id(3)
  n_k = pl.num_programs(3)
  w = win_ref[0]

  @pl.when(j == 0)
  def _init():
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

  q_last = (i + 1) * block_q - 1
  # Lowest position any query in this block can see: q_first - (w - 1).
  block_visible = jnp.logical_and(
    j * block_k <= q_last,
    jnp.logical_or(w <= 0, (j + 1) * block_k - 1 >= i * block_q - w + 1),
  )

  @pl.when(block_visible)
  def _compute():
    q = _mxu_operand(q_ref[0, 0])  # full-rate MXU, f32 accumulate (see above)
    k = _mxu_operand(k_ref[0, 0])
    v = _mxu_operand(v_ref[0, 0])

    s = jax.lax.dot_general(
      q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    s = _softcap(s, softcap)

    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    visible = k_pos <= q_pos
    visible = jnp.logical_and(visible, jnp.logical_or(w <= 0, k_pos > q_pos - w))
    s = jnp.where(visible, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)

    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
      p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

  @pl.when(j == n_k - 1)
  def _finalize():
    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)  # window >= 1: every real row sees itself
    o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret", "softcap", "scale",
                                    "tp_mesh"))
def flash_attention(
  q: jnp.ndarray,  # [B, T, Hq, D]
  k: jnp.ndarray,  # [B, T, Hkv, D]
  v: jnp.ndarray,  # [B, T, Hkv, D]
  block_q: int | None = None,  # default env XOT_FLASH_BLOCK_Q, else 128
  block_k: int | None = None,  # default env XOT_FLASH_BLOCK_K, else 128
  interpret: bool | None = None,
  window: jnp.ndarray | None = None,  # traced scalar int32; None = global-only kernel
  softcap: float = 0.0,  # static tanh score cap (gemma2); 0 = off
  scale: float | None = None,  # static score scale; None = D**-0.5
  tp_mesh=None,  # static Mesh: the kernel runs per device, heads sliced over 'tp'
) -> jnp.ndarray:
  """Causal grouped-query flash attention over one contiguous segment.

  Query position t attends keys [max(0, t - window + 1), t] (window 0 or
  None = all of [0, t]). Returns [B, T, Hq, D] in q.dtype. T must be a
  multiple of the (possibly clamped) block sizes — the engine's
  power-of-two prefill buckets guarantee this. `window=None` (static)
  compiles the original non-prefetch kernel, so non-windowed families'
  executables are byte-identical to before.

  Block sizes default from XOT_FLASH_BLOCK_Q/XOT_FLASH_BLOCK_K (else
  128x128) — the prefill-MFU tuning knob (VERDICT r3 #5); read at trace
  time, so set them before the engine compiles its executables.
  """
  from xotorch_tpu.utils import knobs
  if block_q is None:
    block_q = max(1, knobs.get_int("XOT_FLASH_BLOCK_Q"))
  if block_k is None:
    block_k = max(1, knobs.get_int("XOT_FLASH_BLOCK_K"))
  B, T, Hq, D = q.shape
  Hkv = k.shape[2]
  groups = Hq // Hkv
  block_q = min(block_q, T)
  block_k = min(block_k, T)
  if T % block_q or T % block_k:
    raise ValueError(f"T={T} must be a multiple of block_q={block_q}, block_k={block_k}")
  if interpret is None:
    interpret = jax.default_backend() != "tpu"
  if tp_mesh is not None:
    # Under a serving mesh the Mosaic call must be manual on every device
    # (parallel.mesh.per_shard_kernel); q/k/v arrive head-sharded from the
    # projections' tp constraint, and attention never crosses heads.
    from jax.sharding import PartitionSpec as P
    from xotorch_tpu.parallel.mesh import head_axis, per_shard_kernel
    heads = P(None, None, head_axis(tp_mesh, Hq, Hkv), None)
    local = functools.partial(flash_attention, block_q=block_q, block_k=block_k,
                              interpret=interpret, softcap=softcap, scale=scale)
    return per_shard_kernel(
      local, tp_mesh, (q, k, v), (heads, heads, heads), heads,
      {"window": None if window is None else jnp.asarray(window, jnp.int32)}, {"window": P()})

  scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
  # [B, H, T, D] layout: the kernel tiles the last two dims.
  qt = q.transpose(0, 2, 1, 3)
  kt = k.transpose(0, 2, 1, 3)
  vt = v.transpose(0, 2, 1, 3)

  grid = (B, Hq, T // block_q, T // block_k)

  if window is None:
    out = pl.pallas_call(
      functools.partial(_flash_kernel, block_q=block_q, block_k=block_k, scale=scale,
                        softcap=float(softcap)),
      grid=grid,
      in_specs=[
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // groups, j, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // groups, j, 0)),
      ],
      out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
      out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
      scratch_shapes=[
        pltpu.VMEM((block_q, D), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
      ],
      interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)

  win = jnp.asarray(window, jnp.int32).reshape(1)

  def kv_index(b, h, i, j, win_ref):
    # Clamp j into this q block's visible kv range: blocks past the causal
    # diagonal re-map down, blocks below the window re-map up — either way
    # the grid index stops changing and Pallas elides the DMA.
    last = ((i + 1) * block_q - 1) // block_k
    w = win_ref[0]
    lo = jnp.where(w > 0, jnp.maximum(i * block_q - w + 1, 0) // block_k, 0)
    return (b, h // groups, jnp.clip(j, lo, last), 0)

  grid_spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=1,
    grid=grid,
    in_specs=[
      pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j, win_ref: (b, h, i, 0)),
      pl.BlockSpec((1, 1, block_k, D), kv_index),
      pl.BlockSpec((1, 1, block_k, D), kv_index),
    ],
    out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j, win_ref: (b, h, i, 0)),
    scratch_shapes=[
      pltpu.VMEM((block_q, D), jnp.float32),
      pltpu.VMEM((block_q, 128), jnp.float32),
      pltpu.VMEM((block_q, 128), jnp.float32),
    ],
  )
  out = pl.pallas_call(
    functools.partial(_flash_kernel_windowed, block_q=block_q, block_k=block_k, scale=scale,
                      softcap=float(softcap)),
    grid_spec=grid_spec,
    out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
    interpret=interpret,
  )(win, qt, kt, vt)
  return out.transpose(0, 2, 1, 3)
