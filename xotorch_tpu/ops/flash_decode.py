"""Pallas TPU cached-attention kernels: queries at an offset over a long,
HBM-resident KV cache (decode steps and chunked long-prompt prefill).

Attention over the resident cache is HBM-bound: it must stream the occupied
cache past the MXU. The XLA baseline (ops/attention.py) materialises
[T, S] scores over the ENTIRE static buffer regardless of occupancy — cheap
at 2 k, the dominant cost (and at long T an OOM) at 32 k (VERDICT r1 weak
#7 / missing #3). This kernel makes the cost proportional to the OCCUPIED,
CAUSALLY-VISIBLE prefix:

- `q_start` (the segment's absolute position) is a scalar-prefetch operand,
  so the BlockSpec index maps can depend on it: kv blocks past the last
  visible block re-map to the last visible block index. Pallas skips the
  DMA when consecutive grid steps map to the same block — unneeded cache is
  never fetched from HBM, not just masked.
- Queries of all `groups` q-heads sharing one kv head are batched into the
  sublane dim together with `block_q` positions (GQA packing: row r of a
  tile is position r // groups, head r % groups), with the online-softmax
  recurrence carried across kv blocks in VMEM scratch.
- Scores never leave VMEM — no [T, S] materialisation, so a 2048-token
  segment attending a 32 k cache costs VMEM tiles, not gigabytes.

T == 1 is the decode step; T > 1 at q_start > 0 is a chunked-prefill
segment (the engine splits prompts longer than XOT_PREFILL_CHUNK). Prefill
from zero uses the in-segment kernel in ops/flash_attention.py. On CPU the
kernel runs in interpret mode so tests exercise the same code path.

Reference context: the torch engine re-ran SDPA over a host-built dense mask
every step (sharded_inference_engine.py:144-186); there is no reference
long-context path to mirror (SURVEY §5 "Long-context" — greenfield).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xotorch_tpu.ops.flash_attention import _mxu_operand, _softcap

NEG_INF = -1e30


def _kv_tiles(k_ref, v_ref, dt):
  """One kv tile pair as MXU operands. An int8 cache's tiles convert to `dt`
  (the query's MXU dtype) UNSCALED — small integers are exact in bf16 — and
  `_scores` / `_weighted_values` apply the per-(position, head) scales on
  the score side."""
  if k_ref.dtype == jnp.int8:
    return k_ref[0, 0].astype(dt), v_ref[0, 0].astype(dt)
  return _mxu_operand(k_ref[0, 0]), _mxu_operand(v_ref[0, 0])


def _scores(q, k, ks_ref, scale: float, softcap: float):
  """[rows, block_k] f32 scores. int8 K dequantizes HERE: the scale of key
  position j multiplies column j of q @ k_int8^T — the [1, block_k] scale
  tile broadcasts along sublanes in its natural (lane-major) layout, where a
  [block_k] -> [block_k, 1] column to scale K's rows is a relayout Mosaic
  refuses (`infer-vector-layout: unsupported shape cast`, libtpu 0.0.34).
  Same product as transformer._cache_read's k * scale, rounded once in f32
  instead of once in bf16."""
  s = jax.lax.dot_general(
    q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
  if ks_ref is not None:
    s = s * ks_ref[0, 0].astype(jnp.float32)
  return _softcap(s * scale, softcap)


def _weighted_values(p, v, vs_ref):
  """p @ dequant(v): int8 V's per-position scale folds into the matching
  COLUMN of p (p @ (v * s[:, None]) == (p * s[None, :]) @ v) — again a
  lane-major [1, block_k] broadcast. The softmax denominator keeps the
  unscaled p."""
  if vs_ref is not None:
    p = p * vs_ref[0, 0].astype(jnp.float32)
  return jax.lax.dot_general(
    p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _cached_kernel(start_ref, *refs, block_q: int, block_k: int, groups: int, scale: float,
                   softcap: float = 0.0, quant: bool = False):
  """Grid = (B, Hkv, nQ, nK); nK innermost so scratch carries the
  online-softmax state across kv blocks of one (batch, kv-head, q-block).
  `quant` (static) threads the int8 cache's per-(position, head) scale
  tiles in as two extra operands."""
  if quant:
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
  else:
    (q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref), ks_ref, vs_ref = refs, None, None
  b = pl.program_id(0)
  i = pl.program_id(2)
  j = pl.program_id(3)
  n_k = pl.num_programs(3)
  q_start = start_ref[b]
  # Last absolute position covered by this q block (incl. bucket padding).
  q_last = q_start + (i + 1) * block_q - 1

  @pl.when(j == 0)
  def _init():
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

  @pl.when(j * block_k <= q_last)
  def _compute():
    # Native-dtype MXU operands, f32 accumulate (pre-cast to f32 would
    # halve the MXU rate — this kernel also serves pos>0 chunked-prefill
    # segments, which are compute-bound).
    q = _mxu_operand(q_ref[0, 0])  # [block_q * groups, D]
    k, v = _kv_tiles(k_ref, v_ref, q.dtype)
    s = _scores(q, k, ks_ref, scale, softcap)  # [block_q * groups, block_k]

    # Row r is query position q_start + i*block_q + r // groups.
    row_pos = q_start + i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // groups
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos <= row_pos, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)

    l_ref[:] = jnp.broadcast_to(alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
    acc_ref[:] = acc_ref[:] * alpha + _weighted_values(p, v, vs_ref)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

  @pl.when(j == n_k - 1)
  def _finalize():
    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _cached_kernel_windowed(start_ref, win_ref, *refs, block_q: int, block_k: int, groups: int,
                            scale: float, softcap: float, quant: bool = False):
  """Sliding-window variant: win_ref ([1] int32, 0 = global) is the
  per-LAYER window as a traced scalar-prefetch operand — one compiled
  kernel serves gemma2's alternating sliding/global layers. Cache blocks
  entirely below the window are skipped (and their DMAs elided via the
  BlockSpec re-map), so decode cost is proportional to min(window,
  occupied prefix) instead of the occupied prefix."""
  if quant:
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
  else:
    (q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref), ks_ref, vs_ref = refs, None, None
  b = pl.program_id(0)
  i = pl.program_id(2)
  j = pl.program_id(3)
  n_k = pl.num_programs(3)
  q_start = start_ref[b]
  w = win_ref[0]
  q_last = q_start + (i + 1) * block_q - 1
  # Lowest position any query row of this block can see (first row has the
  # block's minimum position q_start + i*block_q).
  lowest_visible = q_start + i * block_q - w + 1

  @pl.when(j == 0)
  def _init():
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

  block_visible = jnp.logical_and(
    j * block_k <= q_last,
    jnp.logical_or(w <= 0, (j + 1) * block_k - 1 >= lowest_visible),
  )

  @pl.when(block_visible)
  def _compute():
    # Native-dtype MXU operands, f32 accumulate (pre-cast to f32 would
    # halve the MXU rate — this kernel also serves pos>0 chunked-prefill
    # segments, which are compute-bound).
    q = _mxu_operand(q_ref[0, 0])  # [block_q * groups, D]
    k, v = _kv_tiles(k_ref, v_ref, q.dtype)
    s = _scores(q, k, ks_ref, scale, softcap)

    row_pos = q_start + i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // groups
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    visible = k_pos <= row_pos
    visible = jnp.logical_and(visible, jnp.logical_or(w <= 0, k_pos > row_pos - w))
    s = jnp.where(visible, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)

    l_ref[:] = jnp.broadcast_to(alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
    acc_ref[:] = acc_ref[:] * alpha + _weighted_values(p, v, vs_ref)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

  @pl.when(j == n_k - 1)
  def _finalize():
    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)  # window >= 1: every real row sees itself
    o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret", "softcap",
                                             "scale", "tp_mesh"))
def flash_cached_attention(
  q: jnp.ndarray,  # [B, T, Hq, D] — queries at absolute positions q_start + [0, T)
  k: jnp.ndarray,  # [B, S, Hkv, D] — full static cache buffer (segment already written)
  v: jnp.ndarray,  # [B, S, Hkv, D]
  q_start: jnp.ndarray,  # [B] int32 — absolute position of q[:, 0]
  block_q: int | None = None,  # default env XOT_FD_BLOCK_Q, else 128
  block_k: int | None = None,  # default env XOT_FD_BLOCK_K, else 256
  interpret: bool | None = None,
  window: jnp.ndarray | None = None,  # traced scalar int32; None = global-only kernel
  softcap: float = 0.0,  # static tanh score cap (gemma2); 0 = off
  scale: float | None = None,  # static score scale; None = D**-0.5
  k_scale: jnp.ndarray | None = None,  # [B, S, Hkv] — int8 cache's per-(pos, head) scales
  v_scale: jnp.ndarray | None = None,
  tp_mesh=None,  # static Mesh: the kernel runs per device, heads sliced over 'tp'
) -> jnp.ndarray:
  """Causal GQA attention of a query segment over the occupied cache prefix.

  Query t attends cache positions [max(0, q_start + t - window + 1),
  q_start + t] (window None/0 = the whole prefix). Returns [B, T, Hq, D].
  `window=None` (static) compiles the original kernel, so non-windowed
  families' executables are unchanged. With `k_scale`/`v_scale` the cache
  buffers are raw int8 and dequantize IN-KERNEL per tile (`_scores` /
  `_weighted_values`: transformer._cache_read's product, scales applied on
  the score side) — int8-KV long-context serving keeps both the halved
  cache bandwidth and the occupancy/window DMA elision.
  """
  B, T, Hq, D = q.shape
  S, Hkv = k.shape[1], k.shape[2]
  groups = Hq // Hkv
  quant = k_scale is not None
  from xotorch_tpu.utils import knobs
  if block_q is None:
    block_q = max(1, knobs.get_int("XOT_FD_BLOCK_Q"))
  if block_k is None:
    block_k = max(1, knobs.get_int("XOT_FD_BLOCK_K"))
  # Halve block sizes until they divide the actual T/S: cache lengths are
  # usually powers of two, but XOT_MAX_CACHE_LEN / cfg.max_seq_len clamps can
  # produce odd sizes — degrade block size instead of crashing the hot path.
  block_q = min(block_q, T)
  while T % block_q:
    block_q //= 2
  block_k = min(block_k, S)
  while S % block_k:
    block_k //= 2
  if interpret is None:
    interpret = jax.default_backend() != "tpu"
  if tp_mesh is not None:
    # Under a serving mesh the Mosaic call must be manual on every device
    # (parallel.mesh.per_shard_kernel). The cache is Hkv-sharded
    # (parallel.mesh.cache_spec) and q head-sharded; positions and the
    # window are replicated, and nothing crosses shards.
    from jax.sharding import PartitionSpec as P
    from xotorch_tpu.parallel.mesh import head_axis, per_shard_kernel
    ax = head_axis(tp_mesh, Hq, Hkv)
    heads, scales = P(None, None, ax, None), P(None, None, ax)
    local = functools.partial(flash_cached_attention, block_q=block_q, block_k=block_k,
                              interpret=interpret, softcap=softcap, scale=scale)
    return per_shard_kernel(
      local, tp_mesh, (q, k, v, q_start), (heads, heads, heads, P()), heads,
      {"window": None if window is None else jnp.asarray(window, jnp.int32),
       "k_scale": k_scale, "v_scale": v_scale},
      {"window": P(), "k_scale": scales, "v_scale": scales})

  scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
  # GQA packing: [B, Hkv, T * groups, D], row = position * groups + group.
  qt = q.reshape(B, T, Hkv, groups, D).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, T * groups, D)
  kt = k.transpose(0, 2, 1, 3)  # [B, Hkv, S, D]
  vt = v.transpose(0, 2, 1, 3)
  start = q_start.astype(jnp.int32)
  if quant:
    # [B, Hkv, 1, S]: the singleton sublane axis keeps the scale block's
    # trailing dims inside the Mosaic layout rule (same trick as the int4
    # kernel's group scales).
    kst = k_scale.transpose(0, 2, 1).reshape(B, Hkv, 1, S)
    vst = v_scale.transpose(0, 2, 1).reshape(B, Hkv, 1, S)

  rows = block_q * groups
  n_q = T // block_q
  n_k = S // block_k

  scratch = [
    pltpu.VMEM((rows, D), jnp.float32),
    pltpu.VMEM((rows, 128), jnp.float32),
    pltpu.VMEM((rows, 128), jnp.float32),
  ]
  q_block = pl.BlockSpec((1, 1, rows, D), lambda b, h, i, j, *_: (b, h, i, 0))

  if window is None:
    def _kv_j(b, i, j, start_ref):
      # Blocks past this q block's last visible position re-map to the last
      # visible block: the grid index stops changing, so Pallas elides the
      # DMA.
      last = (start_ref[b] + (i + 1) * block_q - 1) // block_k
      return jnp.minimum(j, last)

    prefetch, operands = 1, (start, qt, kt, vt)
  else:
    win = jnp.asarray(window, jnp.int32).reshape(1)

    def _kv_j(b, i, j, start_ref, win_ref):
      # Clamp into the visible range: above the causal diagonal re-map down,
      # below the sliding window re-map up — the repeated block index elides
      # the DMA either way, so decode streams min(window, occupied) bytes.
      last = (start_ref[b] + (i + 1) * block_q - 1) // block_k
      w = win_ref[0]
      lo = jnp.where(w > 0,
                     jnp.maximum(start_ref[b] + i * block_q - w + 1, 0) // block_k, 0)
      return jnp.clip(j, lo, last)

    prefetch, operands = 2, (start, win, qt, kt, vt)

  kv_block = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, i, j, *pf: (b, h, _kv_j(b, i, j, *pf), 0))
  in_specs = [q_block, kv_block, kv_block]
  if quant:
    operands = operands + (kst, vst)
    sc_block = pl.BlockSpec((1, 1, 1, block_k),
                            lambda b, h, i, j, *pf: (b, h, 0, _kv_j(b, i, j, *pf)))
    in_specs += [sc_block, sc_block]

  kernel = (functools.partial(_cached_kernel, block_q=block_q, block_k=block_k,
                              groups=groups, scale=scale, softcap=float(softcap), quant=quant)
            if window is None else
            functools.partial(_cached_kernel_windowed, block_q=block_q, block_k=block_k,
                              groups=groups, scale=scale, softcap=float(softcap), quant=quant))
  grid_spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=prefetch,
    grid=(B, Hkv, n_q, n_k),
    in_specs=in_specs,
    out_specs=q_block,
    scratch_shapes=scratch,
  )
  out = pl.pallas_call(
    kernel, grid_spec=grid_spec,
    out_shape=jax.ShapeDtypeStruct((B, Hkv, T * groups, D), q.dtype),
    interpret=interpret,
  )(*operands)
  return out.reshape(B, Hkv, T, groups, D).transpose(0, 2, 1, 3, 4).reshape(B, T, Hq, D)


def flash_decode_attention(
  q: jnp.ndarray,  # [B, 1, Hq, D]
  k: jnp.ndarray,  # [B, S, Hkv, D]
  v: jnp.ndarray,  # [B, S, Hkv, D]
  kv_valid: jnp.ndarray,  # [B] int32 — occupied prefix length (incl. this step)
  block_k: int = 256,
  interpret: bool | None = None,
  window: jnp.ndarray | None = None,
  softcap: float = 0.0,
  scale: float | None = None,
) -> jnp.ndarray:
  """Single-token decode attention (T == 1 specialisation)."""
  return flash_cached_attention(q, k, v, kv_valid.astype(jnp.int32) - 1,
                                block_q=1, block_k=block_k, interpret=interpret,
                                window=window, softcap=softcap, scale=scale)
