"""Ragged paged-attention decode: queries over a shared KV page arena.

The paged KV pool (inference/jax_engine/paged_cache.py) stores every
resident request's cache as fixed-size pages in ONE arena per layer; each
batch row reaches its tokens through a page table. Decode attention then has
two jobs the contiguous kernels don't: indirect the KV reads through the
table, and stop at each ROW's own occupied page count instead of the batch
maximum — a 16 k-context row co-batched with 512-token rows must not make
the short rows stream (or even DMA) 16 k of cache.

Two implementations, one contract:

- `_paged_attention_xla`: pure-XLA `jnp.take` gather of each row's pages +
  the shared gqa_attention mask math (ops/attention.py). Runs anywhere,
  reference for correctness tests, and the CPU-serving fallback.
- `_paged_attention_kernel`: Pallas TPU kernel following the
  flash_decode.py occupancy-DMA pattern. Grid = (B, Hkv, max_pages); the
  page table and per-row lengths are scalar-prefetch operands so the kv
  BlockSpec index map can resolve LOGICAL page j to its PHYSICAL arena page
  — and clamp j past the row's last occupied page to that last page
  (`_logical_page_index`): the repeated block index makes Pallas elide the
  DMA, so each row streams ceil(len_b / page) pages from HBM, not
  max_pages. Unallocated/padded table slots are never touched.

Both kernels take two OPTIONAL operand families, threaded the same way
flash_decode grew them (static flags select the executable; absent operands
leave the original kernels byte-identical):

- `window` ([1] int32 scalar-prefetch, one per-LAYER sliding window, 0 =
  global): the kv index map clamps the page range to [lo, last] where lo is
  the first page holding an in-window position, so out-of-window pages are
  never DMA'd — the same bound the engine's VirtualKV handles use to decref
  window-expired pages back to the pool (vkv.py). Dead table slots hold the
  scratch page and sit below lo by construction.
- `k_scale_pages`/`v_scale_pages` ([P, page, Hkv] per-layer SCALE pages,
  int8-KV arenas): dequantized in-kernel on the score side, exactly
  `flash_decode._scores` / `_weighted_values` — HBM streams int8 bytes,
  halving paged KV bandwidth. A page id indexes payload and scale pages alike, so
  the same `_kv_map` serves both BlockSpecs.

`paged_decode_attention` is T == 1 only (the decode step).
`paged_prefill_attention` serves T > 1 RAGGED segments — chunked-prefill
slices and the draft-verify forward ([prev_token] + draft) — whose K/V were
scattered straight into pool pages (transformer._attention_block's paged
write-through). Three read paths, one contract:

- XLA reference (use_kernel=False): `jnp.take` gather of each row's pages +
  the shared gqa_attention mask math. Runs anywhere, correctness reference.
- Ragged Pallas kernel (use_kernel=True, ragged=True — the default kernel
  path): the T>1 generalisation of the decode kernel below. The kv
  BlockSpec indirects through the page table directly (`_kv_map`), per-row
  page saturation elides DMAs past each row's occupied pages, and the
  causal mask offsets every query row by its resident position
  (q_start = kv_valid_len - T) — NO gathered-view materialisation
  anywhere, the Ragged Paged Attention design (arXiv 2604.15464).
- Legacy gathered view (use_kernel=True, ragged=False): gather + the
  occupancy-aware cached kernel (ops/flash_decode.py) — the pre-ragged
  shape, kept for on-chip A/B (XOT_RAGGED_PREFILL=0).

On CPU the kernels run in interpret mode so tests exercise the same code
paths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xotorch_tpu.ops.flash_attention import _mxu_operand
from xotorch_tpu.ops.flash_decode import _kv_tiles, _scores, _weighted_values

NEG_INF = -1e30


def _run_paged_kernel(kernel, tp_mesh, q, k_pages, v_pages, page_table, rows,
                      win, k_scale_pages, v_scale_pages):
  """Call a paged Pallas kernel — once per device of the serving mesh when
  there is one (parallel.mesh.per_shard_kernel: the chip's compiler refuses
  an unwrapped Mosaic call inside a multi-device jit). q and the page arena
  are sliced on their head axes ([B,T,Hq,D] / [P,page,Hkv,D], heads at
  index 2; scale pages [P,page,Hkv], heads at index 2 — matching
  parallel.mesh.cache_spec) when 'tp' divides both head counts, so each
  shard's kernel sees Hq/tp query heads over Hkv/tp arena heads: same GQA
  group size, same grid shape, no cross-shard traffic (the softmax is per
  head). The table / row metadata / window are replicated; window / scale
  pages ride along when present."""
  if tp_mesh is None:
    return kernel(q, k_pages, v_pages, page_table, rows, win,
                  k_scale_pages, v_scale_pages)
  from jax.sharding import PartitionSpec as P
  from xotorch_tpu.parallel.mesh import head_axis, per_shard_kernel
  ax = head_axis(tp_mesh, q.shape[2], k_pages.shape[2])
  heads, scales = P(None, None, ax, None), P(None, None, ax)
  return per_shard_kernel(
    lambda q_, kp, vp, pt, rows_, window=None, k_scale=None, v_scale=None:
      kernel(q_, kp, vp, pt, rows_, window, k_scale, v_scale),
    tp_mesh, (q, k_pages, v_pages, page_table, rows), (heads, heads, heads, P(), P()), heads,
    {"window": win, "k_scale": k_scale_pages, "v_scale": v_scale_pages},
    {"window": P(), "k_scale": scales, "v_scale": scales})


def _logical_page_index(j, length, page_size: int, window=None):
  """Logical kv-page index a grid step `j` should read for a row holding
  `length` tokens: j itself while occupied, else saturating at the row's
  LAST occupied page — and, with a sliding `window`, at the FIRST page
  holding an in-window position. The saturation is the ragged skip —
  consecutive grid steps mapping to the same page make Pallas elide the
  DMA, so a row's HBM reads stop at the occupied (and in-window) pages
  regardless of the batch maximum. Exposed for tests (per-row-read
  assertion without a TPU)."""
  last = jnp.maximum(length - 1, 0) // page_size
  jj = jnp.minimum(j, last)
  if window is not None:
    lo = jnp.where(window > 0,
                   jnp.maximum(length - window, 0) // page_size, 0)
    jj = jnp.maximum(jj, lo)
  return jj


def _paged_kernel(*refs, page: int, groups: int, scale: float, softcap: float,
                  windowed: bool = False, quant: bool = False):
  """Grid = (B, Hkv, n_pages); the page axis innermost so VMEM scratch
  carries the online-softmax state across one (batch, kv-head)'s pages.
  Rows of a tile are the `groups` query heads sharing this kv head (the
  T == 1 specialisation of flash_decode's GQA packing). `windowed` threads
  the per-layer sliding window in as one more scalar-prefetch operand;
  `quant` threads int8 scale-page tiles in as two more kv operands — both
  static, so configs without them compile the original kernel."""
  n_sp = 3 if windowed else 2
  pt_ref, len_ref = refs[0], refs[1]
  win_ref = refs[2] if windowed else None
  rest = refs[n_sp:]
  if quant:
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
  else:
    (q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref), ks_ref, vs_ref = rest, None, None
  b = pl.program_id(0)
  j = pl.program_id(2)
  n_j = pl.num_programs(2)
  length = len_ref[b]

  @pl.when(j == 0)
  def _init():
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

  if windowed:
    w = win_ref[0]
    # First in-window position is length - w; pages wholly below it are
    # clamped away by _kv_map, and the grid gate skips their compute too.
    low = jnp.where(w > 0, jnp.maximum(length - w, 0), 0)
    gate = jnp.logical_and(j * page < length, (j + 1) * page > low)
  else:
    gate = j * page < length

  @pl.when(gate)
  def _compute():
    q = _mxu_operand(q_ref[0, 0])  # [groups, D]
    # int8 pages: the per-(position, head) scale tiles apply on the score
    # side (flash_decode._scores / _weighted_values).
    k, v = _kv_tiles(k_ref, v_ref, q.dtype)  # [page, D]
    s = _scores(q, k, ks_ref, scale, softcap)  # [groups, page]
    # The decode query sits at position length - 1: every occupied position
    # is causally visible, so the mask is occupancy (plus the window).
    k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    visible = k_pos < length
    if windowed:
      visible = jnp.logical_and(
        visible, jnp.logical_or(w <= 0, k_pos >= length - w))
    s = jnp.where(visible, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[:] = jnp.broadcast_to(
      alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
    acc_ref[:] = acc_ref[:] * alpha + _weighted_values(p, v, vs_ref)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

  @pl.when(j == n_j - 1)
  def _finalize():
    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _paged_attention_kernel(q, k_pages, v_pages, page_table, lengths,
                            window=None, k_scale_pages=None,
                            v_scale_pages=None, *, scale: float,
                            softcap: float,
                            interpret: bool | None) -> jnp.ndarray:
  B, T, Hq, D = q.shape
  P_, page, Hkv, _ = k_pages.shape
  groups = Hq // Hkv
  maxp = page_table.shape[1]
  windowed = window is not None
  quant = k_scale_pages is not None
  if interpret is None:
    interpret = jax.default_backend() != "tpu"

  qt = q[:, 0].reshape(B, Hkv, groups, D)  # head h_q = kv * groups + g
  kt = k_pages.transpose(2, 0, 1, 3)  # [Hkv, P, page, D]
  vt = v_pages.transpose(2, 0, 1, 3)
  pt = page_table.astype(jnp.int32)
  lens = lengths.astype(jnp.int32)

  def _kv_map(b, h, j, pt_ref, len_ref, *rest):
    # The window-rotated logical view: pages below the window clamp to the
    # first in-window page (their DMA elides), pages past the last occupied
    # one clamp to it. `rest[0]` is the window scalar-prefetch ref when the
    # executable is windowed.
    win = rest[0][0] if windowed else None
    jj = _logical_page_index(j, len_ref[b], page, window=win)
    return (h, pt_ref[b, jj], 0, 0)

  q_block = pl.BlockSpec((1, 1, groups, D), lambda b, h, j, *_: (b, h, 0, 0))
  kv_block = pl.BlockSpec((1, 1, page, D), _kv_map)
  in_specs = [q_block, kv_block, kv_block]
  operands = [qt, kt, vt]
  prefetch = [pt, lens]
  if windowed:
    prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))
  if quant:
    # [P, page, Hkv] -> [Hkv, P, 1, page]: trailing (sublane=1, lane=page)
    # keeps the scale block inside the Mosaic layout rule (flash_decode's
    # transpose trick); the SAME _kv_map resolves its physical page.
    kst = k_scale_pages.transpose(2, 0, 1).reshape(Hkv, P_, 1, page)
    vst = v_scale_pages.transpose(2, 0, 1).reshape(Hkv, P_, 1, page)
    sc_block = pl.BlockSpec((1, 1, 1, page), _kv_map)
    in_specs += [sc_block, sc_block]
    operands += [kst, vst]
  grid_spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=len(prefetch),
    grid=(B, Hkv, maxp),
    in_specs=in_specs,
    out_specs=q_block,
    scratch_shapes=[
      pltpu.VMEM((groups, D), jnp.float32),
      pltpu.VMEM((groups, 128), jnp.float32),
      pltpu.VMEM((groups, 128), jnp.float32),
    ],
  )
  out = pl.pallas_call(
    functools.partial(_paged_kernel, page=page, groups=groups,
                      scale=scale, softcap=float(softcap),
                      windowed=windowed, quant=quant),
    grid_spec=grid_spec,
    out_shape=jax.ShapeDtypeStruct((B, Hkv, groups, D), q.dtype),
    interpret=interpret,
  )(*prefetch, *operands)
  return out.reshape(B, 1, Hq, D)


# Query rows (groups x positions) one ragged-kernel tile may hold in VMEM.
_RAGGED_MAX_ROWS = 2048


def _paged_ragged_kernel(*refs, page: int, groups: int, T: int, scale: float,
                         softcap: float, windowed: bool = False,
                         quant: bool = False):
  """T > 1 generalisation of `_paged_kernel`: grid = (B, Hkv, n_pages), the
  page axis innermost so VMEM scratch carries the online-softmax state of
  ALL of one (batch, kv-head)'s query rows across its pages. A tile packs
  the `groups` query heads sharing this kv head times the T segment
  positions as rows (row r = g*T + t), so one MXU dot scores a whole page
  against every query at once. Causality is per ROW: query t sits at
  absolute position q_start[b] + t and sees exactly the occupied positions
  at or before it (and, windowed, above its own position - window) — the
  ragged mask that lets one kernel serve chunked prefill slices and
  draft-verify forwards over a resident cache."""
  n_sp = 4 if windowed else 3
  pt_ref, qstart_ref, len_ref = refs[0], refs[1], refs[2]
  win_ref = refs[3] if windowed else None
  rest = refs[n_sp:]
  if quant:
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
  else:
    (q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref), ks_ref, vs_ref = rest, None, None
  b = pl.program_id(0)
  j = pl.program_id(2)
  n_j = pl.num_programs(2)
  length = len_ref[b]
  q_start = qstart_ref[b]

  @pl.when(j == 0)
  def _init():
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

  if windowed:
    w = win_ref[0]
    # Lowest position any query row of this batch can see: the EARLIEST
    # row sits at q_start and sees k_pos > q_start - w.
    low = jnp.where(w > 0, jnp.maximum(q_start - w + 1, 0), 0)
    gate = jnp.logical_and(j * page < length, (j + 1) * page > low)
  else:
    gate = j * page < length

  @pl.when(gate)
  def _compute():
    q = _mxu_operand(q_ref[0, 0])  # [groups*T, D]
    k, v = _kv_tiles(k_ref, v_ref, q.dtype)  # [page, D]
    s = _scores(q, k, ks_ref, scale, softcap)  # [groups*T, page]
    # Row r is query offset t = r % T at absolute position q_start + t; it
    # attends key positions <= its own. Position 0 is visible to every row,
    # so m/l leave NEG_INF on the very first page — later fully-masked
    # pages then renormalise against a finite running max (exp(-inf - m)
    # underflows to 0, never NaN). Windowed rows whose window starts past
    # the first computed page accumulate garbage under an all-NEG_INF max
    # the same way — and the first REAL score wipes it (alpha underflows
    # to 0), so the invariant holds per row.
    k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % T
    visible = k_pos <= q_pos
    if windowed:
      visible = jnp.logical_and(
        visible, jnp.logical_or(w <= 0, k_pos > q_pos - w))
    s = jnp.where(visible, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[:] = jnp.broadcast_to(
      alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
    acc_ref[:] = acc_ref[:] * alpha + _weighted_values(p, v, vs_ref)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

  @pl.when(j == n_j - 1)
  def _finalize():
    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _ragged_attention_kernel(q, k_pages, v_pages, page_table, kv_valid_len,
                             window=None, k_scale_pages=None,
                             v_scale_pages=None, *, scale: float,
                             softcap: float,
                             interpret: bool | None) -> jnp.ndarray:
  """Pallas dispatch for the T>1 ragged kernel: queries [B, T, Hq, D] over
  page-table-indirected K/V. Query row t of batch b sits at absolute
  position kv_valid_len[b] - T + t (the engine's prefill/verify contract:
  contiguous positions ending at the last occupied one)."""
  B, T, Hq, D = q.shape
  P_, page, Hkv, _ = k_pages.shape
  groups = Hq // Hkv
  # One tile holds every query row of a (batch, kv-head): q, the f32
  # accumulator and the two lane-replicated softmax stats all scale with
  # groups*T, and a 4096-position segment (the default XOT_PREFILL_CHUNK)
  # overflows VMEM on a v5e (refused at compile time: "Ran out of memory in
  # memory space vmem"). Longer segments run as consecutive position slices
  # through the SAME kernel — slice i's queries end at kv position
  # kv_valid_len - T + (i+1)*block_t, which is all the kernel's masks, gates
  # and page clamps key off.
  block_t = 1 << max((_RAGGED_MAX_ROWS // groups).bit_length() - 1, 0)
  if T > block_t and T % block_t == 0:
    outs = []
    for i in range(T // block_t):
      outs.append(_ragged_attention_kernel(
        q[:, i * block_t:(i + 1) * block_t], k_pages, v_pages, page_table,
        kv_valid_len - (T - (i + 1) * block_t), window, k_scale_pages,
        v_scale_pages, scale=scale, softcap=softcap, interpret=interpret))
    return jnp.concatenate(outs, axis=1)
  maxp = page_table.shape[1]
  windowed = window is not None
  quant = k_scale_pages is not None
  if interpret is None:
    interpret = jax.default_backend() != "tpu"

  lens = kv_valid_len.astype(jnp.int32)
  q_start = lens - T
  # Head h_q = kv * groups + g packs to tile row r = g*T + t.
  qt = q.transpose(0, 2, 1, 3).reshape(B, Hkv, groups * T, D)
  kt = k_pages.transpose(2, 0, 1, 3)  # [Hkv, P, page, D]
  vt = v_pages.transpose(2, 0, 1, 3)
  pt = page_table.astype(jnp.int32)

  def _kv_map(b, h, j, pt_ref, qstart_ref, len_ref, *rest):
    win = None
    if windowed:
      # The earliest query row bounds the visible range from below.
      w = rest[0][0]
      lo = jnp.where(w > 0,
                     jnp.maximum(qstart_ref[b] - w + 1, 0) // page, 0)
    jj = _logical_page_index(j, len_ref[b], page)
    if windowed:
      jj = jnp.maximum(jj, lo)
    return (h, pt_ref[b, jj], 0, 0)

  q_block = pl.BlockSpec((1, 1, groups * T, D), lambda b, h, j, *_: (b, h, 0, 0))
  kv_block = pl.BlockSpec((1, 1, page, D), _kv_map)
  in_specs = [q_block, kv_block, kv_block]
  operands = [qt, kt, vt]
  prefetch = [pt, q_start, lens]
  if windowed:
    prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))
  if quant:
    kst = k_scale_pages.transpose(2, 0, 1).reshape(Hkv, P_, 1, page)
    vst = v_scale_pages.transpose(2, 0, 1).reshape(Hkv, P_, 1, page)
    sc_block = pl.BlockSpec((1, 1, 1, page), _kv_map)
    in_specs += [sc_block, sc_block]
    operands += [kst, vst]
  grid_spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=len(prefetch),
    grid=(B, Hkv, maxp),
    in_specs=in_specs,
    out_specs=q_block,
    scratch_shapes=[
      pltpu.VMEM((groups * T, D), jnp.float32),
      pltpu.VMEM((groups * T, 128), jnp.float32),
      pltpu.VMEM((groups * T, 128), jnp.float32),
    ],
  )
  out = pl.pallas_call(
    functools.partial(_paged_ragged_kernel, page=page, groups=groups, T=T,
                      scale=scale, softcap=float(softcap),
                      windowed=windowed, quant=quant),
    grid_spec=grid_spec,
    out_shape=jax.ShapeDtypeStruct((B, Hkv, groups * T, D), q.dtype),
    interpret=interpret,
  )(*prefetch, *operands)
  return (out.reshape(B, Hkv, groups, T, D)
          .transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, D))


def _gather_paged_view(q, k_pages, v_pages, page_table,
                       k_scale_pages=None, v_scale_pages=None):
  """`jnp.take` each row's pages into a contiguous [B, maxp*page, ...] view.
  int8 arenas dequantize here (same math as transformer._cache_read) so the
  caller sees compute-dtype K/V; scratch-page slots gather zeros and mask
  out downstream."""
  B = q.shape[0]
  maxp, page = page_table.shape[1], k_pages.shape[1]
  k = jnp.take(k_pages, page_table, axis=0)  # [B, maxp, page, Hkv, D]
  v = jnp.take(v_pages, page_table, axis=0)
  k = k.reshape(B, maxp * page, *k.shape[3:])
  v = v.reshape(B, maxp * page, *v.shape[3:])
  if k_scale_pages is not None:
    ks = jnp.take(k_scale_pages, page_table, axis=0).reshape(B, maxp * page, -1)
    vs = jnp.take(v_scale_pages, page_table, axis=0).reshape(B, maxp * page, -1)
    k = k.astype(q.dtype) * ks.astype(q.dtype)[..., None]
    v = v.astype(q.dtype) * vs.astype(q.dtype)[..., None]
  return k, v


def _paged_attention_xla(q, k_pages, v_pages, page_table, lengths,
                         scale: float, softcap: float, window=None,
                         k_scale_pages=None, v_scale_pages=None) -> jnp.ndarray:
  """`jnp.take`-based fallback: gather each row's pages into a per-row
  contiguous view, then run the shared masked-softmax math. Padded table
  slots gather the scratch page; their positions sit at or past the row's
  length and mask out (released window slots likewise sit below the window
  mask)."""
  from xotorch_tpu.ops.attention import gqa_attention
  k, v = _gather_paged_view(q, k_pages, v_pages, page_table,
                            k_scale_pages, v_scale_pages)
  q_positions = (lengths.astype(jnp.int32) - 1)[:, None]  # [B, 1]
  return gqa_attention(q, k, v, q_positions, kv_valid_len=lengths.astype(jnp.int32),
                       scale=scale, softcap=softcap, window=window)


def paged_prefill_attention(
  q: jnp.ndarray,  # [B, T, Hq, D] — a prefill segment's queries (B == 1)
  k_pages: jnp.ndarray,  # [P, page, Hkv, D] — one layer's K arena
  v_pages: jnp.ndarray,  # [P, page, Hkv, D]
  page_table: jnp.ndarray,  # [B, max_pages] int32 physical page ids (0-padded)
  q_positions: jnp.ndarray,  # [B, T] int32 absolute positions of the queries
  kv_valid_len: jnp.ndarray,  # [B] int32 — occupied positions incl. this segment
  softcap: float = 0.0,  # static tanh score cap (gemma2); 0 = off
  scale: float | None = None,  # static score scale; None = D**-0.5
  use_kernel: bool = False,
  ragged: bool = True,  # static: kernel path reads pages NATIVELY (no gather)
  interpret: bool | None = None,
  tp_mesh=None,  # static Mesh: kernel runs per-tp-shard over sliced heads
  window=None,  # traced per-layer sliding window scalar; None = global layer
  k_scale_pages=None,  # [P, page, Hkv] int8-KV scale pages; None = bf16 arena
  v_scale_pages=None,
) -> jnp.ndarray:
  """Causal GQA attention of a T>1 ragged segment over its row's occupied
  pages: chunked-prefill slices and draft-verify forwards share this op.

  Query t (absolute position q_positions[:, t] == kv_valid_len - T + t)
  attends every occupied position <= it, reached through `page_table`.
  `use_kernel` (static) selects the Pallas path; with `ragged` (the
  default) that is the TRUE ragged kernel — the kv BlockSpec indirects
  through the page table, each row's DMA stops at its own occupied pages,
  and no gathered view is ever materialised on the hot path. ragged=False
  keeps the legacy shape (gather the pages contiguous, run the
  occupancy-aware flash_cached kernel over the view) for on-chip A/B.
  The default XLA gather path is the correctness reference and the off-TPU
  fallback. Padded table slots hold the scratch page; their positions sit
  at or past kv_valid_len and mask out. Returns [B, T, Hq, D].
  """
  T = q.shape[1]
  win = None if window is None else jnp.asarray(window, jnp.int32).reshape(1)
  if use_kernel and ragged:
    D = q.shape[-1]
    k_scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    kernel = functools.partial(_ragged_attention_kernel, scale=k_scale,
                               softcap=float(softcap), interpret=interpret)
    return _run_paged_kernel(kernel, tp_mesh, q, k_pages, v_pages, page_table,
                             kv_valid_len, win, k_scale_pages, v_scale_pages)
  if use_kernel:
    # Legacy gathered view: int8 arenas hand the RAW pages + gathered
    # scales to flash_cached, which dequantizes in-kernel over the view.
    from xotorch_tpu.ops.flash_decode import flash_cached_attention
    B = q.shape[0]
    maxp, page = page_table.shape[1], k_pages.shape[1]
    k = jnp.take(k_pages, page_table, axis=0).reshape(B, maxp * page, *k_pages.shape[2:])
    v = jnp.take(v_pages, page_table, axis=0).reshape(B, maxp * page, *v_pages.shape[2:])
    ks = vs = None
    if k_scale_pages is not None:
      ks = jnp.take(k_scale_pages, page_table, axis=0).reshape(B, maxp * page, -1)
      vs = jnp.take(v_scale_pages, page_table, axis=0).reshape(B, maxp * page, -1)
    q_start = kv_valid_len.astype(jnp.int32) - T
    return flash_cached_attention(q, k, v, q_start, window=window,
                                  softcap=softcap, scale=scale,
                                  k_scale=ks, v_scale=vs, interpret=interpret,
                                  tp_mesh=tp_mesh)
  from xotorch_tpu.ops.attention import gqa_attention
  k, v = _gather_paged_view(q, k_pages, v_pages, page_table,
                            k_scale_pages, v_scale_pages)
  return gqa_attention(q, k, v, q_positions.astype(jnp.int32),
                       kv_valid_len=kv_valid_len.astype(jnp.int32),
                       scale=scale, softcap=softcap, window=window)


def paged_decode_attention(
  q: jnp.ndarray,  # [B, 1, Hq, D] — each row's decode query
  k_pages: jnp.ndarray,  # [P, page, Hkv, D] — one layer's K arena
  v_pages: jnp.ndarray,  # [P, page, Hkv, D]
  page_table: jnp.ndarray,  # [B, max_pages] int32 physical page ids (0-padded)
  lengths: jnp.ndarray,  # [B] int32 — occupied positions incl. this step
  softcap: float = 0.0,  # static tanh score cap (gemma2); 0 = off
  scale: float | None = None,  # static score scale; None = D**-0.5
  use_kernel: bool = False,
  interpret: bool | None = None,
  tp_mesh=None,  # static Mesh: kernel runs per-tp-shard over sliced heads
  window=None,  # traced per-layer sliding window scalar; None = global layer
  k_scale_pages=None,  # [P, page, Hkv] int8-KV scale pages; None = bf16 arena
  v_scale_pages=None,
) -> jnp.ndarray:
  """Causal GQA decode attention over each row's occupied pages.

  Row b's query (at absolute position lengths[b] - 1) attends positions
  [0, lengths[b]) reached through page_table[b] — windowed layers only the
  last `window` of them, and the kernel's page range clamps to match (the
  VirtualKV contract: released head slots are never DMA'd). Returns
  [B, 1, Hq, D]. `use_kernel` (static) selects the Pallas path; the
  default XLA gather path is the correctness reference and the off-TPU
  fallback.
  """
  D = q.shape[-1]
  scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
  if use_kernel:
    win = None if window is None else jnp.asarray(window, jnp.int32).reshape(1)
    kernel = functools.partial(_paged_attention_kernel, scale=scale,
                               softcap=float(softcap), interpret=interpret)
    return _run_paged_kernel(kernel, tp_mesh, q, k_pages, v_pages, page_table,
                             lengths, win, k_scale_pages, v_scale_pages)
  return _paged_attention_xla(q, k_pages, v_pages, page_table, lengths,
                              scale, float(softcap), window=window,
                              k_scale_pages=k_scale_pages,
                              v_scale_pages=v_scale_pages)
