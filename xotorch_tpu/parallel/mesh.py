"""Device mesh + sharding rules: the intra-peer parallelism layer.

The reference has NO collectives at all (SURVEY §2.9/§5 — gRPC unicast ring
only); this module is the TPU-native depth the north-star asks for. A peer
that owns several TPU chips runs its layer-range shard SPMD over a local
`jax.sharding.Mesh`; XLA inserts the all-reduces (over ICI) implied by the
parameter shardings below — the scaling-book recipe: pick a mesh, annotate
shardings, let the compiler place collectives.

Axes:
  dp — data parallel (batch)
  tp — tensor parallel (attention heads / ffn columns, Megatron-style)
  sp — sequence parallel (ring attention over the KV sequence; ops/ring_attention)
  ep — expert parallel (MoE experts)

Pipeline parallelism stays at the Node/ring layer (topology partitioning),
exactly as in the reference design; within a pipeline stage these axes give
the second dimension of scaling the reference lacks.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

def _int4_dense_slots():
  """Single source of truth for which dense slots can carry the int4
  grouped rank-4 layout (models/quantize.py owns the list)."""
  from xotorch_tpu.models.quantize import _INT4_LAYER_SLOTS
  return _INT4_LAYER_SLOTS


def head_axis(mesh, *head_counts: int) -> Optional[str]:
  """The mesh axis a kernel's HEAD dimension splits over: 'tp' when the mesh
  has a tp axis wider than 1 that divides every given head count (GQA group
  size is then preserved per shard), else None (heads replicated)."""
  if mesh is None or "tp" not in mesh.axis_names:
    return None
  tp = int(mesh.shape["tp"])
  return "tp" if tp > 1 and all(h % tp == 0 for h in head_counts) else None


def per_shard_kernel(kernel, mesh, operands, specs, out_spec, optional=None,
                     optional_specs=None):
  """Run a Pallas kernel once PER DEVICE of the serving mesh:
  `kernel(*operands, **present_optionals)` with each operand sliced per its
  PartitionSpec. `optional` maps keyword -> operand-or-None (a window, int8
  scale tiles): the None ones are dropped together with their
  `optional_specs`, so one call site serves every variant of a kernel.

  A Mosaic custom call has no partitioning rule: inside a jit that spans
  more than one device the TPU lowering refuses it outright ("Mosaic kernels
  cannot be automatically partitioned. Please wrap the call in a
  shard_map") — on the virtual CPU mesh the kernels run interpreted as
  plain XLA ops, so only the chip's compiler ever said so. `jax.shard_map`
  over EVERY mesh axis makes the call manual: operands whose spec names
  'tp' arrive sliced on that axis (heads — attention is per head, so no
  cross-shard traffic), everything else replicated; axes the specs never
  name (sp/ep) just see replicated operands. check_vma is off: the kernel
  body is opaque to the replication checker."""
  import jax
  present = {n: a for n, a in (optional or {}).items() if a is not None}
  per_shard = jax.shard_map(
    lambda ops, opt: kernel(*ops, **opt), mesh=mesh,
    in_specs=(tuple(specs), {n: optional_specs[n] for n in present}),
    out_specs=out_spec, check_vma=False)
  return per_shard(tuple(operands), present)


def make_mesh(axis_sizes: Dict[str, int], devices: Optional[Sequence] = None):
  """Build a Mesh with named axes from {axis: size}. Axes of size 1 are kept
  (harmless, simplifies downstream specs)."""
  import jax
  from jax.sharding import Mesh

  devices = list(devices if devices is not None else jax.devices())
  names = tuple(axis_sizes.keys())
  sizes = tuple(axis_sizes.values())
  total = int(np.prod(sizes))
  if total > len(devices):
    raise ValueError(f"Mesh {axis_sizes} needs {total} devices, have {len(devices)}")
  mesh_devices = np.asarray(devices[:total]).reshape(sizes)
  return Mesh(mesh_devices, names)


def spec_for_param(name: str, ndim: Optional[int] = None):
  """PartitionSpec for a single named parameter in the stacked layout
  (transformer.py). Megatron layout: qkv/gate/up column-parallel over tp,
  o/down row-parallel (their matmul output implies an XLA all-reduce over
  tp); norms replicated; MoE experts shard over ep.

  `ndim` disambiguates the int4 grouped layout (models/quantize.py): a DENSE
  matmul slot at rank 4 is [L, G, gs, out] — the out axis moves to -1 and
  row-parallel slots shard the GROUP axis (in = G*gs)."""
  from jax.sharding import PartitionSpec as P

  if ndim == 4 and name in _int4_dense_slots():
    col = name in ("wq", "wk", "wv", "w_gate", "w_up")
    return P(None, None, None, "tp") if col else P(None, "tp", None, None)
  if name.endswith("_gscale"):
    base = name[: -len("_gscale")]
    col = base in ("wq", "wk", "wv", "w_gate", "w_up")
    return P(None, None, "tp") if col else P(None, "tp", None)

  rules = {
    "attn_norm": P(None, None), "mlp_norm": P(None, None),
    "post_attn_norm": P(None, None), "post_mlp_norm": P(None, None),
    "wq": P(None, None, "tp"), "wk": P(None, None, "tp"), "wv": P(None, None, "tp"),
    "wo": P(None, "tp", None),
    "w_gate": P(None, None, "tp"), "w_up": P(None, None, "tp"), "w_down": P(None, "tp", None),
    "bq": P(None, "tp"), "bk": P(None, "tp"), "bv": P(None, "tp"),
    "q_norm": P(None, None), "k_norm": P(None, None),
    "router": P(None, None, None),
    "we_gate": P(None, "ep", None, "tp"),
    "we_up": P(None, "ep", None, "tp"),
    "we_down": P(None, "ep", "tp", None),
    "embedding": P(None, "tp"),
    "final_norm": P(None),
    "lm_head": P(None, "tp"),
    # int8 weight-only scales (models/quantize.py): one scale per OUTPUT
    # channel, so each follows its base tensor's out-axis sharding with the
    # contraction axis dropped.
    "wq_scale": P(None, "tp"), "wk_scale": P(None, "tp"), "wv_scale": P(None, "tp"),
    "wo_scale": P(None, None),
    "w_gate_scale": P(None, "tp"), "w_up_scale": P(None, "tp"), "w_down_scale": P(None, None),
    "we_gate_scale": P(None, "ep", "tp"), "we_up_scale": P(None, "ep", "tp"),
    "we_down_scale": P(None, "ep", None),
    # Per-vocab-row embedding scale: replicated (the int8 table itself still
    # shards over tp along hidden).
    "embedding_scale": P(None),
    "lm_head_scale": P("tp"),
  }
  return rules.get(name)


def _int4_shape_guard(name: str, leaf):
  """Shape to divisibility-check, ONLY for the int4 grouped layouts: their
  group axis legitimately degrades (G=1 on tiny models) and should fall back
  to replication. Every other parameter keeps the LOUD device_put failure on
  a non-dividing mesh axis — silently replicating a misconfigured tp run
  would hide the config error and blow HBM on large models."""
  is_int4_dense = getattr(leaf, "ndim", None) == 4 and name in _int4_dense_slots()
  if is_int4_dense or name.endswith("_gscale"):
    return getattr(leaf, "shape", None)
  return None


def _restrict_spec(spec, mesh, shape: Optional[Tuple[int, ...]] = None):
  """Drop axis names the mesh doesn't have (e.g. tp rules on a dp×ep mesh):
  an absent axis simply means replicated there. With `shape` (int4 grouped
  layouts only — _int4_shape_guard), also drop a mesh axis the tensor
  dimension doesn't divide evenly (G=1 degenerate groups replicate rather
  than fail)."""
  from jax.sharding import PartitionSpec as P

  if spec is None:
    return P()
  names = set(mesh.axis_names)
  out = []
  for i, ax in enumerate(spec):
    if ax not in names:
      out.append(None)
    elif shape is not None and i < len(shape) and shape[i] % mesh.shape[ax] != 0:
      out.append(None)
    else:
      out.append(ax)
  return P(*out)


def param_specs_like(params: Dict[str, Any], mesh=None) -> Dict[str, Any]:
  """A spec pytree mirroring the param tree exactly (path-keyed). Pass the
  mesh to drop rule axes it doesn't have (same semantics as shard_params)."""
  import jax
  from jax.sharding import PartitionSpec as P

  def spec(path, leaf):
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    s = spec_for_param(name, getattr(leaf, "ndim", None))
    if mesh is not None:
      # Same shape guard as shard_params: the returned specs must agree
      # with actual placement or in_shardings consumers get mismatches.
      return _restrict_spec(s, mesh, _int4_shape_guard(name, leaf))
    return s if s is not None else P()

  return jax.tree_util.tree_map_with_path(spec, params)


def shard_params(params: Dict[str, Any], mesh) -> Dict[str, Any]:
  """Place a param pytree onto the mesh per the partition rules. XLA derives
  the matching collectives inside jit from these placements."""
  import jax
  from jax.sharding import NamedSharding

  def place(path, leaf):
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    spec = spec_for_param(name, getattr(leaf, "ndim", None))
    placement = _restrict_spec(spec, mesh, _int4_shape_guard(name, leaf))
    return jax.device_put(leaf, NamedSharding(mesh, placement))

  return jax.tree_util.tree_map_with_path(place, params)


def device_bytes(tree) -> int:
  """Per-device resident bytes of a (possibly sharded) pytree: each leaf
  counts its LOCAL shard shape (`sharding.shard_shape`) × itemsize, so a
  tp-sharded param tree reports what one chip actually holds. Metadata-only
  (no device sync) — the ground truth the mesh-aware cost model's
  weight_bytes_per_device is tested against."""
  import math

  import jax

  total = 0
  for leaf in jax.tree_util.tree_leaves(tree):
    shape = getattr(leaf, "shape", None)
    if shape is None:
      continue
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None and hasattr(sharding, "shard_shape"):
      shape = sharding.shard_shape(tuple(shape))
    total += math.prod(shape) * leaf.dtype.itemsize
  return int(total)


def batch_spec(rank: int = 2):
  """Batch leaves shard along dp on their leading axis and (rank >= 2) the
  sequence axis over sp when those axes exist in the mesh."""
  from jax.sharding import PartitionSpec as P
  if rank >= 2:
    return P("dp", "sp", *([None] * (rank - 2)))
  return P("dp")


def shard_batch(batch, mesh):
  import jax
  from jax.sharding import NamedSharding
  return jax.tree.map(
    lambda x: jax.device_put(x, NamedSharding(mesh, _restrict_spec(batch_spec(x.ndim), mesh))), batch
  )


def cache_spec(rank: int = 5):
  # [L, B, S, Hkv, D]: batch over dp, kv heads over tp. int8-KV scale
  # leaves are rank 4 ([L, B, S, Hkv]) — same placement minus the head dim.
  from jax.sharding import PartitionSpec as P
  if rank == 4:
    return P(None, "dp", None, "tp")
  return P(None, "dp", None, "tp", None)


def shard_cache(cache, mesh):
  import jax
  from jax.sharding import NamedSharding
  def _place(x):
    # One-time arena placement at pool creation, not steady-state decode work.
    spec = _restrict_spec(cache_spec(x.ndim), mesh)
    return jax.device_put(x, NamedSharding(mesh, spec))  # xotlint: disable=hotpath-sync (pool creation)

  return jax.tree.map(_place, cache)
