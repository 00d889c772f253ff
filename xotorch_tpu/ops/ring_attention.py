"""Ring attention: causal sequence/context parallelism over a mesh axis.

Long-context capability the reference lacks entirely (SURVEY §5 — sequence
length there is bounded by single-device max_seq_len). Here the sequence is
sharded over the `sp` mesh axis; each device holds one contiguous Q/K/V chunk
and the KV chunks rotate around the ring with `jax.lax.ppermute` while every
device folds each visiting chunk into a blockwise online-softmax accumulator
(the Liu et al. ring-attention / Milakov-Gimelshein recurrence).

Collectives ride ICI on a real pod slice; the same code runs on the virtual
8-device CPU mesh in tests. Pure jnp + ppermute, so jax autodiff gives the
backward pass (ring'd again by XLA) for sequence-parallel training.

Layout contract: chunk i on mesh position i holds global positions
[i*Tl, (i+1)*Tl) — exactly what PartitionSpec(None, 'sp', ...) produces.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _fold_chunk(q, k, v, acc, m, l, q_pos, k_pos, scale):
  """One online-softmax update of (acc, m, l) with a visiting KV chunk.

  q [B,Tq,Hkv,g,D]; k,v [B,Tk,Hkv,D]; q_pos [Tq], k_pos [Tk] absolute;
  acc [B,Tq,Hkv,g,D] f32; m,l [B,Tq,Hkv,g] f32.
  """
  # Native-dtype operands, f32 accumulate: a pre-cast to f32 would halve
  # the MXU rate on bf16 inputs (same rule as the flash kernels).
  from xotorch_tpu.ops.flash_attention import _mxu_operand
  q, k, v = _mxu_operand(q), _mxu_operand(k), _mxu_operand(v)
  s = jnp.einsum("btkgd,bskd->btkgs", q, k, preferred_element_type=jnp.float32) * scale
  visible = (k_pos[None, :] <= q_pos[:, None])[None, :, None, None, :]  # [1,Tq,1,1,Tk]
  s = jnp.where(visible, s, NEG_INF)

  m_cur = jnp.max(s, axis=-1)
  m_new = jnp.maximum(m, m_cur)
  # Rows with no visible key yet keep m = NEG_INF; exp(s - NEG_INF) would be
  # exp(+inf) — guard by clamping the shift.
  shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
  p = jnp.exp(s - shift[..., None])
  p = jnp.where(visible, p, 0.0)
  alpha = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - shift))
  l_new = alpha * l + jnp.sum(p, axis=-1)
  acc_new = acc * alpha[..., None] + jnp.einsum(
    "btkgs,bskd->btkgd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
  return acc_new, m_new, l_new


def ring_attention(
  q: jnp.ndarray,  # [B, Tl, Hq, D] local query chunk
  k: jnp.ndarray,  # [B, Tl, Hkv, D] local key chunk
  v: jnp.ndarray,  # [B, Tl, Hkv, D] local value chunk
  axis_name: str = "sp",
) -> jnp.ndarray:
  """Causal GQA ring attention. Call INSIDE shard_map over `axis_name`.

  Device i computes its queries' attention over all kv chunks j <= i; chunks
  j > i are skipped entirely (no FLOPs — half the ring steps do no work on
  the devices the causal mask excludes, matching the striped/blockwise
  formulation's lower bound for contiguous layout).
  """
  P = jax.lax.psum(1, axis_name)
  idx = jax.lax.axis_index(axis_name)
  B, Tl, Hq, D = q.shape
  Hkv = k.shape[2]
  g = Hq // Hkv
  scale = 1.0 / (D ** 0.5)

  qg = q.reshape(B, Tl, Hkv, g, D)
  q_pos = idx * Tl + jnp.arange(Tl, dtype=jnp.int32)

  acc0 = jnp.zeros((B, Tl, Hkv, g, D), jnp.float32)
  m0 = jnp.full((B, Tl, Hkv, g), NEG_INF, jnp.float32)
  l0 = jnp.zeros((B, Tl, Hkv, g), jnp.float32)

  perm = [(i, (i + 1) % P) for i in range(P)]

  def step(s, carry):
    acc, m, l, k_cur, v_cur = carry
    src = (idx - s) % P  # chunk currently resident originated on device src
    k_pos = src * Tl + jnp.arange(Tl, dtype=jnp.int32)

    def fold(args):
      acc, m, l = args
      return _fold_chunk(qg, k_cur, v_cur, acc, m, l, q_pos, k_pos, scale)

    acc, m, l = jax.lax.cond(src <= idx, fold, lambda a: a, (acc, m, l))
    # Rotate after the fold; the last rotation is wasted but keeps the loop
    # shape uniform (XLA overlaps the ppermute with the next fold).
    k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
    v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
    return acc, m, l, k_nxt, v_nxt

  acc, m, l, _, _ = jax.lax.fori_loop(0, P, step, (acc0, m0, l0, k, v))
  l = jnp.where(l == 0.0, 1.0, l)  # cannot happen under causality (diagonal always folds)
  out = acc / l[..., None]
  return out.reshape(B, Tl, Hq, D).astype(q.dtype)


def ring_attention_sharded(
  q: jnp.ndarray,  # [B, T, Hq, D] global
  k: jnp.ndarray,
  v: jnp.ndarray,
  mesh,
  axis_name: str = "sp",
) -> jnp.ndarray:
  """Convenience wrapper: shard global arrays over `axis_name` along T and
  run ring_attention under shard_map.

  Composes with the other mesh axes when present: batch stays dp-sharded and
  heads stay tp-sharded straight through the shard_map (the ring only ever
  communicates over `axis_name`), so tp+sp+dp all hold without resharding.
  """
  from jax.sharding import PartitionSpec as P

  names = set(mesh.axis_names)
  b_ax = "dp" if "dp" in names else None
  h_ax = "tp" if "tp" in names else None
  spec = P(b_ax, axis_name, h_ax, None)
  fn = jax.shard_map(
    functools.partial(ring_attention, axis_name=axis_name),
    mesh=mesh,
    in_specs=(spec, spec, spec),
    out_specs=spec,
    check_vma=False,
  )
  return fn(q, k, v)
