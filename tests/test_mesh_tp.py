"""Mesh-sharded ring stages (ISSUE 16): TP serving equality + mesh rooflines.

Each ring partition is a true tensor-parallel mesh stage: partition weights
shard per parallel/mesh.spec_for_param, the paged arena and contiguous
caches shard their Hkv axis (cache_spec), activations pin the Megatron
layout (transformer._tp_constraint), and the paged Pallas kernels run
per-tp-shard (parallel/mesh.per_shard_kernel). The acceptance bars
tested here, on the virtual 8-device CPU mesh from conftest:

- greedy streams under XOT_TP=2 (and an infeasible request clamped down)
  are byte-identical to XOT_TP=1 on the contiguous, paged (gather AND
  kernel read), and speculative-verify paths;
- the paged path keeps its zero-copy story on the SHARDED arena: zero
  unpage gathers, zero commit-copy bytes, pool invariants intact;
- XOT_TP is the primary knob — it overrides XOT_SERVE_TP both ways;
- CostModel.weight_bytes_per_device is ground-truth-equal to the sharded
  pytree's per-leaf `sharding.shard_shape` bytes (bf16/fp32, int8, int4),
  and perf_report/ceilings expose the tp-divided mesh terms exactly.
"""
import numpy as np
import pytest

from xotorch_tpu.download.shard_download import LocalShardDownloader
from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
from xotorch_tpu.inference.shard import Shard

from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint


@pytest.fixture(scope="module")
def tiny_model_dir(tmp_path_factory):
  return make_hf_checkpoint(tmp_path_factory.mktemp("meshtp"), TINY_LLAMA_CFG, seed=3)


def _env(monkeypatch, tp, **extra):
  monkeypatch.setenv("XOT_SEED", "7")
  monkeypatch.setenv("XOT_CACHE_LEN", "32")
  monkeypatch.setenv("XOT_KV_PAGE", "8")
  monkeypatch.setenv("XOT_KV_POOL_TOKENS", "512")
  monkeypatch.setenv("XOT_TP", str(tp))
  for k, v in extra.items():
    monkeypatch.setenv(k, str(v))


def _engine(model_dir):
  return JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32")


def _full_shard():
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  return Shard("m", 0, n - 1, n)


_PROMPT = np.array([[1, 5, 9, 200, 17, 3, 42]], dtype=np.int64)


async def _greedy_stream(eng, rid: str, n_tokens: int):
  """Prefill + one fused greedy chunk — the serving-shaped drive both sides
  of every equality test share, so tp on/off compare identical programs."""
  shard = _full_shard()
  tok, _ = await eng.infer_sample_tensor(rid, shard, _PROMPT, temp=0.0)
  seq = [int(tok)]
  out = await eng.generate_chunk(rid, shard, seq[-1], n_tokens - 1, temp=0.0)
  seq.extend(int(t) for t in np.asarray(out).reshape(-1))
  return seq


async def _greedy_reference(model_dir, n_tokens: int):
  """Sequential per-token greedy continuation of _PROMPT on a solo engine."""
  eng = _engine(model_dir)
  shard = _full_shard()
  tok, _ = await eng.infer_sample_tensor("ref", shard, _PROMPT, temp=0.0)
  seq = [int(tok)]
  for _ in range(n_tokens - 1):
    tok, _ = await eng.infer_sample_tensor("ref", shard, np.asarray([[seq[-1]]]), temp=0.0)
    seq.append(int(tok))
  return seq


def _spec_axes(x):
  """Flattened PartitionSpec entries of a device array's sharding."""
  return tuple(x.sharding.spec)


# ----------------------------------------------------------- knob precedence


async def test_xot_tp_overrides_serve_tp(tiny_model_dir, monkeypatch):
  """XOT_TP is the primary knob: 0 forces the mesh OFF even when
  XOT_SERVE_TP asks for one; N forces it ON even when XOT_SERVE_TP says 0;
  unset defers to XOT_SERVE_TP; an infeasible request clamps down to the
  largest divisor of every dense dim (2 kv heads bound the tiny model)."""
  shard = _full_shard()

  monkeypatch.setenv("XOT_TP", "0")
  monkeypatch.setenv("XOT_SERVE_TP", "2")
  eng = _engine(tiny_model_dir)
  await eng.ensure_shard(shard)
  assert eng._mesh is None

  monkeypatch.setenv("XOT_TP", "2")
  monkeypatch.setenv("XOT_SERVE_TP", "0")
  eng = _engine(tiny_model_dir)
  await eng.ensure_shard(shard)
  assert eng._mesh is not None and eng._mesh.shape["tp"] == 2

  monkeypatch.delenv("XOT_TP", raising=False)
  monkeypatch.setenv("XOT_SERVE_TP", "2")
  eng = _engine(tiny_model_dir)
  await eng.ensure_shard(shard)
  assert eng._mesh is not None and eng._mesh.shape["tp"] == 2

  monkeypatch.setenv("XOT_TP", "8")
  monkeypatch.delenv("XOT_SERVE_TP", raising=False)
  eng = _engine(tiny_model_dir)
  await eng.ensure_shard(shard)
  assert eng._mesh is not None and eng._mesh.shape["tp"] == 2  # 8 -> 2


# ------------------------------------------------------------ stream equality


async def test_tp_contiguous_stream_byte_identical(tiny_model_dir, monkeypatch):
  """Contiguous path: the tp=2 greedy stream equals the tp-off stream token
  for token, and the resident cache actually shards Hkv over the mesh."""
  _env(monkeypatch, 0)
  off = await _greedy_stream(_engine(tiny_model_dir), "r", 12)

  _env(monkeypatch, 2)
  eng = _engine(tiny_model_dir)
  got = await _greedy_stream(eng, "r", 12)
  assert eng._mesh is not None and eng._mesh.shape["tp"] == 2
  assert got == off, f"{got} != {off}"

  state = eng._contexts[_full_shard()].states["r"]
  # [L, B, S, Hkv, D] with Hkv sharded (parallel/mesh.cache_spec).
  assert "tp" in _spec_axes(state.cache["k"])
  assert "tp" in _spec_axes(state.cache["v"])


@pytest.mark.parametrize("kernel", ["0", "1"])
async def test_tp_paged_stream_byte_identical(tiny_model_dir, monkeypatch, kernel):
  """Paged path through BOTH reads (XLA gather and the per-tp-shard Pallas
  kernel): tp=2 equals tp-off byte for byte, the request stays page-native
  on the SHARDED arena (zero unpage gathers, zero commit-copy bytes), and
  the pool invariants hold."""
  _env(monkeypatch, 0, XOT_PAGED_KV="1", XOT_PAGED_KERNEL=kernel)
  off = await _greedy_stream(_engine(tiny_model_dir), "r", 12)

  _env(monkeypatch, 2, XOT_PAGED_KV="1", XOT_PAGED_KERNEL=kernel)
  eng = _engine(tiny_model_dir)
  got = await _greedy_stream(eng, "r", 12)
  assert eng._mesh is not None and eng._mesh.shape["tp"] == 2
  assert got == off, f"{got} != {off}"

  ctx = eng._contexts[_full_shard()]
  state, pool = ctx.states["r"], ctx.page_pool
  assert state.cache is None and state.pages, "stream must stay page-native"
  assert len(state.pages) == pool.pages_for(state.pos)
  assert all(pool.refcount(p) >= 1 for p in state.pages)
  # Arena leaves are [L, P, page, Hkv, D]: Hkv sharded over tp.
  assert "tp" in _spec_axes(pool.arena["k"])
  assert "tp" in _spec_axes(pool.arena["v"])
  assert eng._unpage_calls == 0, "tp paged decode must never gather back"
  assert eng._commit_copy_bytes == 0, "tp paged decode must never commit-copy"


@pytest.mark.parametrize("kernel", ["0", "1"])
async def test_tp_paged_verify_byte_identical(tiny_model_dir, monkeypatch, kernel):
  """Speculative verify on the tp mesh: perfect, wrong-tail, and fully-wrong
  drafts against a page-backed state reproduce the sequential greedy stream
  exactly, with the zero-copy counters and pages invariant intact."""
  ref = await _greedy_reference(tiny_model_dir, 8)

  _env(monkeypatch, 2, XOT_PAGED_KV="1", XOT_PAGED_KERNEL=kernel)
  eng = _engine(tiny_model_dir)
  shard = _full_shard()
  tok, _ = await eng.infer_sample_tensor("spec", shard, _PROMPT, temp=0.0)
  assert eng._mesh is not None and eng._mesh.shape["tp"] == 2
  got = [int(tok)]
  assert got[0] == ref[0]

  accepted = await eng.verify_draft("spec", shard, got[-1], ref[1:4])
  assert accepted == ref[1:5], f"{accepted} != {ref[1:5]}"
  got.extend(accepted)
  wrong = [ref[5], (ref[6] + 1) % 250, (ref[6] + 2) % 250]
  accepted = await eng.verify_draft("spec", shard, got[-1], wrong)
  assert accepted[:2] == ref[5:7] and len(accepted) == 2
  got.extend(accepted)
  bad = [(ref[7] + 9) % 250, 1, 2]
  accepted = await eng.verify_draft("spec", shard, got[-1], bad)
  assert accepted == [ref[7]]
  got.extend(accepted)
  assert got == ref[: len(got)]

  ctx = eng._contexts[shard]
  state, pool = ctx.states["spec"], ctx.page_pool
  assert state.cache is None and state.pages
  assert len(state.pages) == pool.pages_for(state.pos)
  assert eng._unpage_calls == 0 and eng._commit_copy_bytes == 0


# --------------------------------------------------- roofline ground truth


@pytest.mark.parametrize("fmt", [None, "int8", "int4"])
def test_weight_bytes_per_device_matches_sharded_pytree(fmt):
  """CostModel.weight_bytes_per_device vs the real thing: shard a random
  param pytree over a {'tp': 2} mesh with the production placement rules
  and compare against per-leaf `sharding.shard_shape` byte counts — the
  same ground-truth style weight_bytes already passes against
  quantized_bytes. Covers the int8 scale placement (row scales replicate)
  and the int4 grouped fallback (groups=1 on the tiny dims -> replicated
  row payloads)."""
  import jax
  import jax.numpy as jnp

  from xotorch_tpu.inference.jax_engine.costmodel import CostModel
  from xotorch_tpu.models.config import config_from_hf_dict
  from xotorch_tpu.models.quantize import quantize_params, quantized_bytes
  from xotorch_tpu.models.transformer import init_random_params
  from xotorch_tpu.parallel.mesh import device_bytes, make_mesh, shard_params

  cfg = config_from_hf_dict(TINY_LLAMA_CFG)
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  params = init_random_params(cfg, n, True, True, jax.random.PRNGKey(0),
                              dtype=jnp.float32)
  if fmt:
    params = quantize_params(params, fmt, scale_dtype=jnp.float32)

  cm = CostModel(cfg, n, True, True, quantize=fmt, dtype_bytes=4, tp=2)
  # Global prediction stays honest on the quantized tree...
  assert cm.weight_bytes(fmt) == quantized_bytes(params)
  # ...and the per-device prediction equals what one mesh device holds.
  sharded = shard_params(params, make_mesh({"tp": 2}))
  assert cm.weight_bytes_per_device(fmt) == device_bytes(sharded)

  # tp=1 degenerates every per-device method to its global twin.
  cm1 = CostModel(cfg, n, True, True, quantize=fmt, dtype_bytes=4, tp=1)
  assert cm1.weight_bytes_per_device(fmt) == cm1.weight_bytes(fmt)
  assert cm1.collective_bytes_per_token() == 0


async def test_perf_report_mesh_attribution(monkeypatch):
  """/v1/perf under XOT_TP=2 (synthetic model): the report carries the
  tp-divided mesh terms, the per-device prediction is ground-truth-equal to
  the sharded resident pytree, and the collective term matches the analytic
  two-psums-per-layer formula exactly."""
  from tests.test_perf_attr import TINY_SHARD, _drive_engine

  monkeypatch.setenv("XOT_TP", "2")
  engine = JAXShardInferenceEngine()
  await _drive_engine(engine, "mesh-r1", n_chunks=1)
  assert engine._mesh is not None and engine._mesh.shape["tp"] == 2

  report = engine.perf_report()
  model = report["model"]
  assert model["tp"] == 2
  # Per-device prediction == per-leaf shard_shape bytes of the live pytree.
  assert model["weight_bytes_per_device_predicted"] == \
    model["weight_bytes_per_device_actual"]
  assert model["weight_bytes_per_device_predicted"] < model["weight_bytes_predicted"]
  # KV arena shards Hkv (2 kv heads / tp=2): per-device reads halve.
  assert model["kv_read_bytes_per_token_at_cache_len"] == \
    2 * model["kv_read_bytes_per_token_at_cache_len_per_device"]
  # Two row-parallel psums per layer, 2*(tp-1)/tp of hidden each.
  dtype_bytes = {"float32": 4, "bfloat16": 2}[model["dtype"]]
  n_layers, hidden = 4, 64
  want = n_layers * 2 * (2 * (2 - 1) * hidden * dtype_bytes // 2)
  assert model["collective_bytes_per_token"] == want

  ceil = report["ceilings"]
  assert ceil["tp"] == 2
  assert ceil["collective_bytes_per_token"] == want
  for label in ("bf16", "int8", "int4"):
    assert ceil[f"{label}_weight_bytes_per_device"] < ceil[f"{label}_weight_bytes"]


async def test_perf_report_off_mesh_degenerates(monkeypatch):
  """tp off: per-device terms equal their global twins, the ceilings table
  carries no mesh keys, and the collective term is zero."""
  from tests.test_perf_attr import _drive_engine

  monkeypatch.setenv("XOT_TP", "0")
  engine = JAXShardInferenceEngine()
  await _drive_engine(engine, "mesh-r0", n_chunks=1)
  assert engine._mesh is None

  report = engine.perf_report()
  model = report["model"]
  assert model["tp"] == 1
  assert model["weight_bytes_per_device_predicted"] == model["weight_bytes_predicted"]
  assert model["weight_bytes_per_device_actual"] == model["weight_bytes_actual"]
  assert model["collective_bytes_per_token"] == 0
  ceil = report["ceilings"]
  assert ceil["tp"] == 1
  assert "collective_bytes_per_token" not in ceil
  assert "bf16_weight_bytes_per_device" not in ceil
