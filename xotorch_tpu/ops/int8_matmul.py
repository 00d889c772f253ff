"""Pallas TPU kernel: W8A8 int8-MXU matvec for the int8-weight decode path.

The default int8 path dequantizes in the dot's operand read — XLA fuses the
int8→bf16 convert + per-channel scale, so HBM streams int8, but the VPU
still runs two elementwise passes (convert, multiply) over EVERY weight
element per token before the bf16 MXU dot. Round 3 measured that path at
56% of the int8 roofline (373 of 662 tok/s). Here the weights go to the
MXU AS int8 (its native doubled-rate format, int32 accumulation) and the
activations row-quantize to int8 once per call — per-weight-element work
drops to zero, and the scales compose after the dot:

    out[r, o] = acc_i32[r, o] * a_scale[r] * w_scale[o]

APPROXIMATE: activation rounding adds ~1/255 relative error per dot (the
default fused-dequant path is exact in bf16). Opt-in via XOT_INT8_KERNEL=1
(models/transformer._linear, decode-sized inputs on real TPU only), A/B'd
on-chip. Same scope rule as the int4 kernel: GSPMD cannot partition the
custom call, so `_linear` takes the fused-dequant path under a serving mesh.

No reference counterpart: the reference has no quantization at all
(SURVEY §5 — torch fp32/fp16 end to end).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def rowquant_int8(a: jnp.ndarray):
  """Symmetric per-row int8 activation quantization: (int8 values,
  [rows, 1] f32 scales)."""
  a = a.astype(jnp.float32)
  s = jnp.max(jnp.abs(a), axis=1, keepdims=True) / 127.0
  s = jnp.where(s == 0.0, 1.0, s)
  return jnp.round(a / s).astype(jnp.int8), s


def _int8_matvec_kernel(h8_ref, hs_ref, w_ref, ws_ref, o_ref):
  # precision pinned: an int8 MXU dot has no higher-precision form, and under a
  # process-wide jax_default_matmul_precision("highest") Mosaic refuses the
  # inherited fp32 contract precision ("Bad lhs type", seen on a v5e).
  acc = jax.lax.dot_general(h8_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=jnp.int32)  # [rows, block_out]
  o_ref[...] = (acc.astype(jnp.float32) * hs_ref[...].astype(jnp.float32)
                * ws_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_out", "interpret"))
def int8_rowquant_matmul(
  h: jnp.ndarray,  # [rows, in] float (rows small — decode)
  w: jnp.ndarray,  # [in, out] int8 (models/quantize per-out-channel layout)
  w_scale: jnp.ndarray,  # [out]
  block_out: int = 2048,
  interpret: bool | None = None,
) -> jnp.ndarray:
  """h @ (w * w_scale) with h row-quantized to int8 and the dot on the int8
  MXU. Returns [rows, out] in h.dtype."""
  rows, d_in = h.shape
  d_out = w.shape[1]
  # Block choice: the whole output when it fits the request and the VMEM cap
  # (the int8 weight tile is d_in * block_out bytes; ~8 MB), else the largest
  # divisor of d_out that is a MULTIPLE OF 128 — the Pallas TPU lowering
  # takes a block's lane dimension only as a multiple of 128 or the full
  # axis ("largest divisor" alone landed on 2004 for the 128256-wide
  # unembedding and was refused). Trace-time only.
  vmem_cap = max(128, 8_000_000 // max(d_in, 1))
  target = min(block_out, vmem_cap)
  if d_out <= target:
    block_out = d_out
  else:
    lanes = [d for d in range(128, target + 1, 128) if d_out % d == 0]
    if not lanes:
      raise ValueError(f"int8_rowquant_matmul: out width {d_out} has no multiple-of-128 "
                       f"divisor <= {target}; pad the projection or use the fused-dequant path")
    block_out = lanes[-1]
  if interpret is None:
    interpret = jax.default_backend() != "tpu"

  h8, a_scale = rowquant_int8(h)
  ws2 = w_scale.reshape(1, d_out)

  out = pl.pallas_call(
    _int8_matvec_kernel,
    grid=(d_out // block_out,),
    in_specs=[
      pl.BlockSpec((rows, d_in), lambda j: (0, 0)),
      pl.BlockSpec((rows, 1), lambda j: (0, 0)),
      pl.BlockSpec((d_in, block_out), lambda j: (0, j)),
      pl.BlockSpec((1, block_out), lambda j: (0, j)),
    ],
    out_specs=pl.BlockSpec((rows, block_out), lambda j: (0, j)),
    out_shape=jax.ShapeDtypeStruct((rows, d_out), h.dtype),
    interpret=interpret,
  )(h8, a_scale, w, ws2)
  return out
