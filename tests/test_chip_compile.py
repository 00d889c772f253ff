"""What only the chip's compiler can say, asked of it without a chip.

The TPU compiler is installed next to the CPU backend and compiles for a chip
that is DESCRIBED, not attached (`jax.experimental.topologies`). Interpret-mode
tests lower the Pallas kernels to ordinary XLA ops, so they never saw what
Mosaic and the TPU partitioner refuse: a lane->sublane relayout of the int8-KV
scale tile, a block width that is not a multiple of 128, a 4096-position ragged
tile that overflows VMEM, a kernel inside a multi-device jit without a
shard_map. Each kernel the engine can select is compiled here at
`synthetic-llama-1b` widths for one described v5e chip, and the tp=4 prefill /
decode steps for a described `v5e:2x2` mesh. A compile that passes is not a
chip run; `chip_smoke.py` is.

Also here: the device_kind-keyed peak table, the compile-cache helper and the
capability probe's refusal to downgrade — the chip-or-fail contracts.
"""
import asyncio
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_tpu.models.config import config_from_hf_dict
from xotorch_tpu.models.registry import model_cards

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # the compiler otherwise logs under /tmp
# libtpu lets one process at a time load it (/tmp/libtpu_lockfile) — right for a chip,
# wrong for describing one: parallel test workers would all but one skip this file.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

CFG = config_from_hf_dict(model_cards["synthetic-llama-1b"]["synthetic_config"])
HQ, HKV, D = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
H, I, V, PAGE = CFG.hidden_size, CFG.intermediate_size, CFG.vocab_size, 128
BF, I8, I32 = jnp.bfloat16, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def v5e():
  """A described (not attached) v5e 2x2 slice; skip where it cannot be described."""
  from jax.experimental import topologies
  try:
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
  except Exception as e:  # no libtpu in this environment
    pytest.skip(f"cannot describe a v5e topology here: {e!r}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
  """A compile for a described chip is written to the persistent cache but cannot
  be read back without the chip (the next one warns and recompiles): keep these
  compiles out of it."""
  from jax.experimental.compilation_cache import compilation_cache as cc
  jax.config.update("jax_enable_compilation_cache", False)
  cc.reset_cache()
  yield
  jax.config.update("jax_enable_compilation_cache", True)
  cc.reset_cache()


def _compile(fn, *abstract) -> str:
  return jax.jit(fn).lower(*abstract).compile().as_text()


# ------------------------------------------------------------ one-chip kernels


def _flash(window=False, T=128, d=D):
  from xotorch_tpu.ops.flash_attention import flash_attention
  shapes = [((1, T, HQ, d), BF), ((1, T, HKV, d), BF), ((1, T, HKV, d), BF)]
  if window:
    return (lambda q, k, v, w: flash_attention(q, k, v, window=w, interpret=False),
            shapes + [((), I32)])
  return (lambda q, k, v: flash_attention(q, k, v, interpret=False), shapes)


def _cached(B=1, T=1, S=8192, window=False, kvq=False):
  from xotorch_tpu.ops.flash_decode import flash_cached_attention
  kv = I8 if kvq else BF
  shapes = [((B, T, HQ, D), BF), ((B, S, HKV, D), kv), ((B, S, HKV, D), kv), ((B,), I32)]
  names = []
  if window:
    shapes.append(((), I32))
    names.append("window")
  if kvq:
    shapes += [((B, S, HKV), BF)] * 2
    names += ["k_scale", "v_scale"]
  return (lambda q, k, v, s, *opt: flash_cached_attention(
    q, k, v, s, interpret=False, **dict(zip(names, opt))), shapes)


def _paged(T=1, B=1, window=False, kvq=False, pages=256, maxp=64):
  from xotorch_tpu.ops.paged_attention import paged_decode_attention, paged_prefill_attention
  kv = I8 if kvq else BF
  shapes = [((B, T, HQ, D), BF), ((pages, PAGE, HKV, D), kv), ((pages, PAGE, HKV, D), kv),
            ((B, maxp), I32), ((B,), I32)]
  names = []
  if window:
    shapes.append(((), I32))
    names.append("window")
  if kvq:
    shapes += [((pages, PAGE, HKV), BF)] * 2
    names += ["k_scale_pages", "v_scale_pages"]
  if T == 1:
    return (lambda q, kp, vp, pt, ln, *opt: paged_decode_attention(
      q, kp, vp, pt, ln, use_kernel=True, interpret=False, **dict(zip(names, opt))), shapes)
  return (lambda q, kp, vp, pt, ln, *opt: paged_prefill_attention(
    q, kp, vp, pt, jnp.zeros((B, T), I32), ln, use_kernel=True, interpret=False,
    **dict(zip(names, opt))), shapes)


def _int8(d_in, d_out, rows):
  from xotorch_tpu.ops.int8_matmul import int8_rowquant_matmul
  return (lambda h, w, s: int8_rowquant_matmul(h, w, s, interpret=False),
          [((rows, d_in), BF), ((d_in, d_out), I8), ((d_out,), BF)])


def _int4(d_in, d_out, rows):
  from xotorch_tpu.ops.int4_matmul import int4_grouped_matmul
  return (lambda h, w, s: int4_grouped_matmul(h, w, s, interpret=False),
          [((rows, d_in), BF), ((d_in // 128, 64, d_out), jnp.uint8), ((d_in // 128, d_out), BF)])


KERNELS = {
  # the default serving path: flash prefill from zero, cached attention over the
  # resident cache for decode steps and pos>0 segments
  "flash-prefill-T128": lambda: _flash(),
  "flash-prefill-T4096": lambda: _flash(T=4096),
  "flash-prefill-windowed": lambda: _flash(window=True, T=4096),
  "flash-prefill-D128": lambda: _flash(T=4096, d=128),
  "cached-decode-B1": lambda: _cached(),
  "cached-decode-B8": lambda: _cached(B=8),
  "cached-segment-T128": lambda: _cached(T=128),
  "cached-segment-T4096": lambda: _cached(T=4096),
  "cached-decode-windowed": lambda: _cached(window=True),
  # int8 KV cache (--kv-quantize int8): the scale tile's relayout was refused
  "cached-decode-int8kv": lambda: _cached(kvq=True),
  "cached-decode-int8kv-B8-windowed": lambda: _cached(B=8, window=True, kvq=True),
  "cached-segment-int8kv": lambda: _cached(T=4096, kvq=True),
  # paged pool (XOT_PAGED_KV=1)
  "paged-decode": lambda: _paged(B=8),
  "paged-decode-windowed": lambda: _paged(B=8, window=True),
  "paged-decode-int8kv": lambda: _paged(B=8, kvq=True),
  "ragged-prefill-T16": lambda: _paged(T=16),
  "ragged-prefill-T1024": lambda: _paged(T=1024),
  # the default prefill chunk: one 4096-position tile overflowed VMEM
  "ragged-prefill-T4096": lambda: _paged(T=4096),
  "ragged-prefill-T4096-windowed": lambda: _paged(T=4096, window=True),
  "ragged-prefill-int8kv": lambda: _paged(T=4096, kvq=True),
  # quantized decode matvecs (--quantize int8 + XOT_INT8_KERNEL, --quantize int4)
  "int8-matvec-up": lambda: _int8(H, I, 8),
  "int8-matvec-down": lambda: _int8(I, H, 1),
  # the unembedding width: "largest divisor" chose a 2004-wide block
  "int8-matvec-vocab": lambda: _int8(H, V, 1),
  "int4-matvec-up": lambda: _int4(H, I, 8),
  "int4-matvec-down": lambda: _int4(I, H, 1),
  "int4-matvec-kv": lambda: _int4(H, HKV * D, 1),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(v5e, name):
  """Every kernel the engine can select compiles (never interpret=True) for one
  described v5e chip at synthetic-llama-1b widths, as a Mosaic custom call."""
  from jax.sharding import SingleDeviceSharding
  fn, shapes = KERNELS[name]()
  one_chip = SingleDeviceSharding(v5e.devices[0])
  abstract = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
  assert "tpu_custom_call" in _compile(fn, *abstract)


# ------------------------------------------------------- the four-chip tp steps


@pytest.fixture
def tp4(v5e, monkeypatch):
  """Abstract synthetic-llama-1b params/caches placed over a described tp=4 mesh,
  with the kernel wrappers steered to compile (they ask jax.default_backend(), which
  still says cpu here — the steering lives in the test, not in the program)."""
  from jax.sharding import NamedSharding, PartitionSpec as P
  from xotorch_tpu.models.transformer import init_kv_cache, init_random_params
  from xotorch_tpu.parallel.mesh import _restrict_spec, cache_spec, make_mesh, param_specs_like
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  mesh = make_mesh({"tp": 4}, v5e.devices)
  shapes = jax.eval_shape(lambda: init_random_params(
    CFG, CFG.num_layers, True, True, jax.random.PRNGKey(0), dtype=BF))
  params = jax.tree.map(
    lambda leaf, spec: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)),
    shapes, param_specs_like(shapes, mesh))

  def cache(S, kvq=False):
    tree = jax.eval_shape(lambda: init_kv_cache(CFG, CFG.num_layers, 1, S, BF, kv_quant=kvq))
    return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
      leaf.shape, leaf.dtype,
      sharding=NamedSharding(mesh, _restrict_spec(cache_spec(leaf.ndim), mesh))), tree)

  rep = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, P()))
  param_bytes = sum(np.prod(leaf.shape) * leaf.dtype.itemsize for leaf in jax.tree.leaves(shapes))
  return {"mesh": mesh, "params": params, "cache": cache, "rep": rep, "param_bytes": int(param_bytes)}


@pytest.mark.parametrize("step", ["prefill-flash", "prefill-cached-int8kv", "decode-flash", "decode-xla"])
def test_tp4_step_compiles_for_v5e_2x2(tp4, step):
  """The engine's default on a four-chip host — one model over a tp mesh of all
  local chips, flash kernels on — compiles: the kernels run per shard
  (parallel.mesh.per_shard_kernel) where an unwrapped Mosaic call is refused, the
  row-parallel projections all-reduce, and each chip holds a quarter of the weights."""
  from xotorch_tpu.models.generate import decode_chunk, forward_sample
  mesh, params, cache, rep = tp4["mesh"], tp4["params"], tp4["cache"], tp4["rep"]
  key = rep((2,), jnp.uint32)
  if step == "prefill-flash":
    lowered = forward_sample.lower(params, rep((1, 128), I32), cache(2048), rep((), I32), rep((), I32),
                                   key, CFG, True, 0.0, 0, use_flash=True, tp_mesh=mesh)
  elif step == "prefill-cached-int8kv":
    lowered = forward_sample.lower(params, rep((1, 4096), I32), cache(8192, kvq=True), rep((), I32),
                                   rep((), I32), key, CFG, True, 0.0, 0, use_flash_decode=True,
                                   tp_mesh=mesh)
  else:
    flash = step == "decode-flash"
    lowered = decode_chunk.lower(params, rep((1, 1), I32), cache(8192 if flash else 2048), rep((), I32),
                                 key, CFG, 8, 0.0, 0, use_flash_decode=flash, tp_mesh=mesh)
  compiled = lowered.compile()
  text = compiled.as_text()
  assert ("tpu_custom_call" in text) == (step != "decode-xla")
  assert "all-reduce(" in text
  per_device = compiled.memory_analysis().argument_size_in_bytes
  assert per_device < 0.35 * tp4["param_bytes"], (per_device, tp4["param_bytes"])


def test_unwrapped_kernel_is_refused_under_a_mesh(tp4):
  """The refusal the wrapping answers: the same kernel with head-sharded operands
  and no shard_map does not compile for four chips."""
  from jax.sharding import NamedSharding, PartitionSpec as P
  from xotorch_tpu.ops.flash_attention import flash_attention
  heads = NamedSharding(tp4["mesh"], P(None, None, "tp", None))
  q = jax.ShapeDtypeStruct((1, 128, HQ, D), BF, sharding=heads)
  kv = jax.ShapeDtypeStruct((1, 128, HKV, D), BF, sharding=heads)
  with pytest.raises(NotImplementedError, match="shard_map"):
    _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False), q, kv, kv)
  assert "tpu_custom_call" in _compile(
    lambda q, k, v: flash_attention(q, k, v, interpret=False, tp_mesh=tp4["mesh"]), q, kv, kv)


# --------------------------------------- repairs, checked for results on the CPU


@pytest.mark.parametrize("kvq", [False, True], ids=["bf16", "int8kv"])
def test_ragged_prefill_position_slices_match_one_tile(monkeypatch, kvq):
  """A segment too long for one VMEM tile runs as position slices through the same
  ragged kernel: same output as the single tile and as the XLA reference."""
  from xotorch_tpu.models.quantize import quantize_tensor
  from xotorch_tpu.ops import paged_attention as pa
  key = jax.random.PRNGKey(3)
  T, page, maxp, hq, hkv, d = 64, 16, 8, 4, 2, 16
  q = jax.random.normal(key, (1, T, hq, d), jnp.float32)
  kp = jax.random.normal(jax.random.fold_in(key, 1), (maxp + 1, page, hkv, d), jnp.float32)
  vp = jax.random.normal(jax.random.fold_in(key, 2), (maxp + 1, page, hkv, d), jnp.float32)
  scales = {}
  if kvq:
    kp, ks = quantize_tensor(kp, axis=-1, scale_dtype=jnp.float32)
    vp, vs = quantize_tensor(vp, axis=-1, scale_dtype=jnp.float32)
    scales = {"k_scale_pages": ks, "v_scale_pages": vs}
  table = jnp.arange(1, maxp + 1, dtype=I32)[None]
  valid = jnp.asarray([100], I32)
  qpos = (valid - T)[:, None] + jnp.arange(T, dtype=I32)[None]
  run = lambda **kw: np.asarray(pa.paged_prefill_attention(q, kp, vp, table, qpos, valid, **scales, **kw))
  one_tile = run(use_kernel=True)
  monkeypatch.setattr(pa, "_RAGGED_MAX_ROWS", 32)  # 2 groups -> 16-position slices
  sliced = run(use_kernel=True)
  np.testing.assert_allclose(sliced, one_tile, atol=1e-5, rtol=1e-5)
  np.testing.assert_allclose(sliced, run(), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("d_in,d_out,block", [
  (2048, 128256, 768),  # the unembedding: largest multiple-of-128 divisor within the VMEM cap
  (2048, 8192, 2048), (8192, 2048, 512), (256, 384, 384), (64, 96, 96)])
def test_int8_matvec_block_is_lane_aligned_or_whole(monkeypatch, d_in, d_out, block):
  """The block the wrapper picks is a multiple of 128 dividing the output, or the
  whole output — the only widths the TPU lowering takes — and the result is right."""
  from xotorch_tpu.models.quantize import quantize_tensor
  from xotorch_tpu.ops import int8_matmul
  seen = []
  real = int8_matmul.pl.pallas_call
  monkeypatch.setattr(int8_matmul.pl, "pallas_call", lambda kernel, **kw: (
    seen.append(kw["out_specs"].block_shape[1]) or real(kernel, **kw)))
  w = jax.random.normal(jax.random.PRNGKey(1), (d_in, d_out), jnp.float32) * 0.02
  wq, ws = quantize_tensor(w, axis=0, scale_dtype=jnp.float32)
  h = jax.random.normal(jax.random.PRNGKey(2), (1, d_in), jnp.float32)
  jax.eval_shape(int8_matmul.int8_rowquant_matmul, h, wq, ws)  # traces: the choice is trace-time
  assert seen == [block] and d_out % block == 0 and (block % 128 == 0 or block == d_out)
  if d_out <= 8192:  # interpreting 167 grid steps of the vocab case proves nothing more
    got = np.asarray(int8_matmul.int8_rowquant_matmul(h, wq, ws))
    ref = np.asarray(h @ (wq.astype(jnp.float32) * ws))
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 0.015


def test_int8_matvec_refuses_width_it_cannot_tile():
  from xotorch_tpu.ops.int8_matmul import int8_rowquant_matmul
  with pytest.raises(ValueError, match="multiple-of-128"):
    int8_rowquant_matmul(jnp.zeros((1, 64)), jnp.zeros((64, 200), I8), jnp.ones((200,)), block_out=128)


def test_quantized_matvec_kernels_stand_down_under_a_mesh(monkeypatch):
  """What used to be an os.environ write at load time is now observed per
  executable: with a serving mesh `_linear` takes the einsum forms GSPMD can
  partition, without one (and the kernel forced) it takes the Pallas matvec."""
  from xotorch_tpu.models import transformer
  from xotorch_tpu.models.quantize import quantize_tensor_grouped
  from xotorch_tpu.ops import int4_matmul
  from xotorch_tpu.parallel.mesh import make_mesh
  calls = []
  real = int4_matmul.int4_grouped_matmul
  monkeypatch.setattr(int4_matmul, "int4_grouped_matmul", lambda *a, **k: calls.append(1) or real(*a, **k))
  monkeypatch.setenv("XOT_INT4_KERNEL", "force")
  w, gs = quantize_tensor_grouped(jax.random.normal(jax.random.PRNGKey(0), (1, 128, 64)),
                                  scale_dtype=jnp.float32)
  layer = {"wq": w[0], "wq_gscale": gs[0]}
  h = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 128))
  off_mesh = transformer._linear(layer, "wq", h)
  assert calls == [1]
  on_mesh = transformer._linear(layer, "wq", h, make_mesh({"tp": 2}, jax.devices()[:2]))
  assert calls == [1]  # no second kernel call
  np.testing.assert_allclose(np.asarray(on_mesh), np.asarray(off_mesh), atol=1e-4, rtol=1e-4)


# --------------------------------------------------------- the device_kind table


@pytest.mark.parametrize("kind,name,bf16,gbps", [
  ("TPU v5 lite", "v5e", 197.0, 819.0), ("TPU v5", "v5p", 459.0, 2765.0),
  ("TPU v6 lite", "v6e", 918.0, 1638.0), ("TPU v4", "v4", 275.0, 1228.0),
  ("TPU v3", "v3", 61.5, 450.0), ("TPU v2", "v2", 22.5, 350.0)])
def test_device_kind_resolves_by_table(kind, name, bf16, gbps):
  from xotorch_tpu.topology.device_capabilities import tpu_chip_peaks, tpu_chip_spec
  assert tpu_chip_spec(kind)["name"] == name
  assert tpu_chip_peaks(kind) == (bf16, gbps)


@pytest.mark.parametrize("kind", ["TPU v5e", "v5litepod", "TPU v7x", "cpu", "", "tpu v5 lite"])
def test_unknown_device_kind_raises(kind):
  """No substring guessing and no default chip: an unknown kind is an error."""
  from xotorch_tpu.topology.device_capabilities import UnknownDeviceError, tpu_chip_peaks
  with pytest.raises(UnknownDeviceError, match="TPU_CHIP_SPECS"):
    tpu_chip_peaks(kind)


@pytest.mark.parametrize("topology", ["v2:2x2", "v3:2x2", "v4:2x2x1", "v5e:2x2", "v5p:2x2x1", "v6e:2x2"])
def test_table_is_keyed_by_what_the_runtime_reports(v5e, topology):
  """The keys are the device_kind strings the installed runtime reports."""
  from jax.experimental import topologies
  from xotorch_tpu.topology.device_capabilities import tpu_chip_spec
  kind = topologies.get_topology_desc(platform="tpu", topology_name=topology).devices[0].device_kind
  assert tpu_chip_spec(kind)["name"] == topology.split(":")[0]


def test_engine_peaks_raise_on_unknown_tpu(monkeypatch):
  """The engine reads the same table and no longer swallows the error."""
  from types import SimpleNamespace
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from xotorch_tpu.topology.device_capabilities import UnknownDeviceError
  engine = JAXShardInferenceEngine()
  engine._contexts["loaded"] = object()
  fake = SimpleNamespace(devices=lambda: [SimpleNamespace(platform="tpu", device_kind="TPU v9 nano")])
  monkeypatch.setattr(engine, "_jax", lambda: fake)
  with pytest.raises(UnknownDeviceError):
    engine._chip_peak_specs()
  fake.devices = lambda: [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
  assert engine._chip_peak_specs() == (197.0, 819.0)


# ------------------------------------------------------------ the capability probe


def _fresh_probe(monkeypatch):
  import importlib
  dc = importlib.import_module("xotorch_tpu.topology.device_capabilities")  # the package re-exports the function
  monkeypatch.setattr(dc, "_cached_capabilities", None)
  monkeypatch.setattr(dc, "_probe_future", None)
  monkeypatch.setenv("XOT_SKIP_JAX_PROBE", "0")
  return dc


def test_probe_timeout_is_an_error_not_the_host_cpu(monkeypatch):
  import time
  dc = _fresh_probe(monkeypatch)
  monkeypatch.setenv("XOT_PROBE_TIMEOUT", "0.05")
  monkeypatch.setattr(dc, "device_capabilities_sync", lambda: time.sleep(0.5) or dc._probe_host_sync())
  with pytest.raises(RuntimeError, match="XOT_PROBE_TIMEOUT"):
    asyncio.run(dc.device_capabilities())


def test_probe_init_failure_propagates(monkeypatch):
  dc = _fresh_probe(monkeypatch)

  def boom():
    raise RuntimeError("Unable to initialize backend 'tpu'")

  monkeypatch.setattr(dc, "_probe_jax_sync", boom)
  with pytest.raises(RuntimeError, match="Unable to initialize backend"):
    asyncio.run(dc.device_capabilities())


@pytest.mark.parametrize("n,ici", [(1, [1, 1, 1]), (4, [2, 2, 1])])
def test_probe_reports_the_tpu_as_the_runtime_describes_it(monkeypatch, n, ici):
  """Capabilities from devices shaped like the installed runtime's: device_kind
  'TPU v5 lite', coords as LISTS (the first real chip run died hashing them)."""
  from types import SimpleNamespace
  dc = _fresh_probe(monkeypatch)
  devices = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", coords=[i % 2, i // 2, 0],
                             memory_stats=lambda: {"bytes_limit": 16 * 2**30}) for i in range(n)]
  monkeypatch.setattr(jax, "local_devices", lambda: devices)
  caps = asyncio.run(dc.device_capabilities())
  assert caps.chip == "TPU v5e" and caps.model == f"Google TPU v5e x{n}"
  assert caps.num_devices == n and caps.ici_topology == ici
  assert caps.memory == n * 16 * 1024 and caps.flops.fp16 == 197.0 * n


def test_probe_unknown_tpu_kind_is_an_error(monkeypatch):
  from types import SimpleNamespace
  dc = _fresh_probe(monkeypatch)
  monkeypatch.setattr(jax, "local_devices", lambda: [SimpleNamespace(
    platform="tpu", device_kind="TPU v9 nano", coords=[0, 0, 0], memory_stats=lambda: {})])
  with pytest.raises(dc.UnknownDeviceError):
    asyncio.run(dc.device_capabilities())


@pytest.mark.parametrize("skip", ["0", "1"])
def test_probe_clean_cpu_jax_takes_the_host_probe(monkeypatch, skip):
  """A CPU-only JAX that initialises cleanly still reports the host, with or
  without XOT_SKIP_JAX_PROBE."""
  dc = _fresh_probe(monkeypatch)
  monkeypatch.setenv("XOT_SKIP_JAX_PROBE", skip)
  caps = asyncio.run(dc.device_capabilities())
  assert "TPU" not in caps.model and caps.flops.fp16 > 0


# ------------------------------------------------------------- the compile cache


@pytest.mark.parametrize("env_dir", ["/some/shared/cache", None], ids=["env-set", "env-unset"])
def test_compile_cache_dir_comes_from_env_or_one_fixed_path(monkeypatch, env_dir):
  """JAX_COMPILATION_CACHE_DIR set: that directory, and no directory set in code.
  Unset: one fixed path inside the checkout — no temp name, pid or timestamp."""
  from pathlib import Path
  from xotorch_tpu.utils import compile_cache
  if env_dir:
    monkeypatch.setenv(compile_cache.ENV, env_dir)
  else:
    monkeypatch.delenv(compile_cache.ENV, raising=False)
  monkeypatch.setattr(compile_cache, "_enabled_dir", None)
  updates = {}
  monkeypatch.setattr(jax.config, "update", lambda opt, val: updates.__setitem__(opt, val))
  got = compile_cache.enable(min_compile_secs=0.2)
  repo = Path(__file__).resolve().parent.parent
  if env_dir:
    assert got == env_dir and "jax_compilation_cache_dir" not in updates
  else:
    assert got == str(repo / ".jax_cache") == updates["jax_compilation_cache_dir"]
  assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.2
  assert compile_cache.enable() == got and compile_cache.cache_dir() == got
