"""Pallas TPU kernel: group-wise int4 (packed uint8) matvec for decode.

The portable int4 path stores weights as uint8 nibble pairs
(models/quantize.pack_int4) and XLA's lowering of the unpack→dot graph
MATERIALIZES the unpacked int8 tensor, so HBM streams ~1.5 bytes/param and
int4 decode measures no faster than bf16 (observed 230 vs 236 tok/s). This
kernel unpacks nibbles IN REGISTERS between the packed-tile read and the
MXU dot: HBM traffic is the 0.5 bytes/param the format promises, plus the
[G, out] scales.

The contraction never re-interleaves the nibbles — a sum is order-free, so
packed row p's low nibble (logical element 2p) contracts against
h_even[p] and the high nibble (2p+1) against h_odd[p]:

    h @ W  ==  h_even @ unpack_lo(Wp) + h_odd @ unpack_hi(Wp)

h_even/h_odd are strided slices of the (tiny) activation built outside the
kernel; the weight tile needs only mask/shift/sign-extend + a contiguous
reshape, which Mosaic lowers cleanly (the interleaving stack/reshape
variant failed to compile).

Scope: the decode hot path — a few query rows (B <= 8 fused-decode rows)
against a [in, out] projection. Prefill keeps the XLA einsum formulation
(compute-bound; one materialized unpack amortizes over the whole segment).
One grid step per out-block with the FULL contraction in-kernel: a
(out-block, group) grid measured 2.5x slower than XLA from sheer per-step
overhead at matvec sizes. On CPU the kernel runs in interpret mode so
tests exercise the same path.

ONE kernel body: the scale-after-dot, int8-shift-unpack and W4A8 variants
that were once selectable by environment variable all reshape the
activation to a per-group batched operand in-kernel, which Mosaic
(libtpu 0.0.34) refuses for a v5e — `infer-vector-layout: unsupported
shape cast` — so they were deleted with their selector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _signext4(x: jnp.ndarray) -> jnp.ndarray:
  # Branch-free sign extension of a 4-bit value sitting in an int32 lane:
  # (x ^ 8) - 8 maps 0..7 -> 0..7 and 8..15 -> -8..-1 in two cheap integer
  # ops (the compare+select formulation costs three and a mask register).
  return (x ^ 8) - 8


def _int4_matvec_kernel(he_ref, ho_ref, w_ref, gs_ref, o_ref):
  # f32 in-kernel math: measured FASTER than bf16 compute (275 vs 242
  # tok/s end to end — the extra converts cost more than the halved
  # elementwise bytes save on the VPU).
  packed = w_ref[...].astype(jnp.int32)  # [G, gs//2, block_out]
  lo = _signext4(packed & 0xF)
  hi = _signext4(packed >> 4)
  scale = gs_ref[...].astype(jnp.float32)  # [G, 1, block_out]
  G, gs_half, block_out = packed.shape
  lo_f = (lo.astype(jnp.float32) * scale).reshape(G * gs_half, block_out)
  hi_f = (hi.astype(jnp.float32) * scale).reshape(G * gs_half, block_out)

  he = he_ref[...].astype(jnp.float32)  # [rows, G * gs//2]
  ho = ho_ref[...].astype(jnp.float32)
  acc = jax.lax.dot_general(he, lo_f, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
  acc = acc + jax.lax.dot_general(ho, hi_f, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
  o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_out", "interpret"))
def int4_grouped_matmul(
  h: jnp.ndarray,  # [rows, in] (rows small — decode)
  w_packed: jnp.ndarray,  # [G, gs // 2, out] uint8 (models/quantize.pack_int4)
  gscale: jnp.ndarray,  # [G, out]
  block_out: int = 1024,
  interpret: bool | None = None,
) -> jnp.ndarray:
  """h @ dequant(w) with the nibble unpack fused into the kernel.

  Returns [rows, out] in h.dtype. Exact (weight-only quantization; f32
  in-kernel math)."""
  rows, d_in = h.shape
  G, gs_half, d_out = w_packed.shape
  gs = gs_half * 2
  if G * gs != d_in:
    raise ValueError(f"packed weight {w_packed.shape} does not cover in={d_in}")
  block_out = min(block_out, d_out)
  while d_out % block_out:
    block_out //= 2
  # VMEM bound: the kernel holds lo_f + hi_f at [d_in/2, block_out] f32 (8
  # bytes per packed element). Cap the footprint at ~8 MB or the Mosaic
  # compile blows VMEM on wide contractions (w_down: in=8192).
  while block_out > 128 and (d_in // 2) * block_out * 8 > 8_000_000:
    block_out //= 2
  if interpret is None:
    interpret = jax.default_backend() != "tpu"

  hg = h.reshape(rows, G, gs)
  h_even = hg[:, :, 0::2].reshape(rows, G * gs_half)  # pairs with the LOW nibbles
  h_odd = hg[:, :, 1::2].reshape(rows, G * gs_half)  # ... the HIGH nibbles
  # [G, 1, out]: a singleton sublane axis keeps the block's trailing dims
  # within the Pallas TPU layout rule (second-to-last must divide 8 or
  # equal the array's dimension).
  gs3 = gscale.reshape(G, 1, d_out)

  act_block = pl.BlockSpec((rows, G * gs_half), lambda j: (0, 0))
  return pl.pallas_call(
    _int4_matvec_kernel,
    grid=(d_out // block_out,),
    in_specs=[
      act_block, act_block,
      pl.BlockSpec((G, gs_half, block_out), lambda j: (0, 0, j)),
      pl.BlockSpec((G, 1, block_out), lambda j: (0, 0, j)),
    ],
    out_specs=pl.BlockSpec((rows, block_out), lambda j: (0, j)),
    out_shape=jax.ShapeDtypeStruct((rows, d_out), h.dtype),
    interpret=interpret,
  )(h_even, h_odd, w_packed, gs3)
