"""Fused scan-prefill (models/generate.prefill_scan + engine._scan_prefill).

Round 3 measured 16 k prefill at ~7% MFU; a large share was structural —
the host-side segment loop pays one dispatch + one H2D round-trip per
segment (engine._infer_sync / the bench's long stage). prefill_scan folds the
whole segment loop into ONE `lax.scan` executable over the occupancy-aware
cached-attention kernel (in-segment causality is by absolute position, so
the same kernel serves the from-zero segment and every later one).

These tests prove, on the CPU interpret path:
- prefill_scan's hidden states and cache match the sequential per-segment
  forward over the XLA baseline attention (cross-implementation equality);
- the engine's serving path (infer_sample_tensor) produces the same token
  stream with the scan path on as with it off, and the scan path actually
  engaged (the per-segment fill executables are never called);
- the power-of-two grouping covers non-power-of-two segment counts;
- mid-shard ring prefill (_infer_sync hidden outputs) matches per-segment.
"""
import numpy as np
import pytest

from xotorch_tpu.download.shard_download import LocalShardDownloader
from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
from xotorch_tpu.inference.shard import Shard

from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint


SCAN_CFG = dict(TINY_LLAMA_CFG, max_position_embeddings=2048)


@pytest.fixture()
def tiny_model_dir(tmp_path):
  return make_hf_checkpoint(tmp_path, SCAN_CFG, seed=11)


def _engine(model_dir, monkeypatch, scan: bool, chunk: int = 32, **env):
  monkeypatch.setenv("XOT_CACHE_LEN", "64")
  monkeypatch.setenv("XOT_MAX_CACHE_LEN", "1024")
  monkeypatch.setenv("XOT_PREFILL_CHUNK", str(chunk))
  monkeypatch.setenv("XOT_FLASH_DECODE", "1")
  monkeypatch.setenv("XOT_FLASH_DECODE_MIN", "0")
  monkeypatch.setenv("XOT_SCAN_PREFILL", "1" if scan else "0")
  for k, v in env.items():
    monkeypatch.setenv(k, str(v))
  return JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32")


def test_prefill_scan_matches_sequential_baseline():
  """prefill_scan (cached Pallas kernel, interpret mode) == the sequential
  per-segment forward over the XLA baseline attention: same hidden states
  for every position, same KV cache contents."""
  import jax.numpy as jnp
  from xotorch_tpu.models.config import ModelConfig
  from xotorch_tpu.models.generate import prefill_scan
  from xotorch_tpu.models.transformer import forward_shard, init_kv_cache, init_random_params
  import jax

  cfg = ModelConfig(model_family="llama", vocab_size=128, hidden_size=32,
                    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                    intermediate_size=64, max_seq_len=512)
  params = init_random_params(cfg, cfg.num_layers, True, True, jax.random.PRNGKey(0),
                              dtype=jnp.float32)
  seg, n_segs = 16, 4
  T = seg * n_segs
  toks = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size, (1, T)), jnp.int32)

  cache_a = init_kv_cache(cfg, cfg.num_layers, 1, 128, jnp.float32)
  hs_seq = []
  pos = 0
  for off in range(0, T, seg):
    h, cache_a = forward_shard(params, toks[:, off:off + seg], cache_a, jnp.int32(pos),
                               cfg=cfg, is_first=True, is_last=False)
    hs_seq.append(h)
    pos += seg
  h_seq = jnp.concatenate(hs_seq, axis=1)

  cache_b = init_kv_cache(cfg, cfg.num_layers, 1, 128, jnp.float32)
  h_scan, cache_b = prefill_scan(params, toks, cache_b, jnp.int32(0), cfg, n_segs)

  np.testing.assert_allclose(np.asarray(h_scan), np.asarray(h_seq), atol=1e-4, rtol=1e-3)
  for name in ("k", "v"):
    np.testing.assert_allclose(np.asarray(cache_b[name][:, :, :T]),
                               np.asarray(cache_a[name][:, :, :T]), atol=1e-5, rtol=1e-4)


async def test_engine_scan_prefill_token_equality(tiny_model_dir, monkeypatch):
  """Serving path: a long prompt through infer_sample_tensor with the scan
  path ON yields the same greedy token as with it OFF — and the ON engine
  never calls the per-segment fill executables (the scan actually ran)."""
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  shard = Shard("m", 0, n - 1, n)
  # 7 full segments + tail: exercises the 4+2+1 power-of-two grouping.
  prompt = np.array([np.arange(7 * 32 + 9) % 250], dtype=np.int64)

  off_eng = _engine(tiny_model_dir, monkeypatch, scan=False)
  tok_off, _ = await off_eng.infer_sample_tensor("r", shard, prompt, temp=0.0)

  on_eng = _engine(tiny_model_dir, monkeypatch, scan=True)
  await on_eng.ensure_shard(shard)
  ctx = on_eng._contexts[shard]
  fill_calls = {"n": 0}
  real_fill = dict(ctx.fill_jits)

  def spy(name):
    inner = real_fill[name]

    def wrapped(*a, **k):
      fill_calls["n"] += 1
      return inner(*a, **k)
    return wrapped

  for name in ("base", "flash", "cached"):
    ctx.fill_jits[name] = spy(name)
  tok_on, _ = await on_eng.infer_sample_tensor("r", shard, prompt, temp=0.0)

  assert tok_on == tok_off
  assert fill_calls["n"] == 0, "scan path did not engage — per-segment fill ran"

  # The caches agree too: the next decode steps stay identical.
  t_on, t_off = tok_on, tok_off
  for _ in range(4):
    t_on, _ = await on_eng.infer_sample_tensor("r", shard,
                                               np.array([[t_on]], dtype=np.int64), temp=0.0)
    t_off, _ = await off_eng.infer_sample_tensor("r", shard,
                                                np.array([[t_off]], dtype=np.int64), temp=0.0)
    assert t_on == t_off


async def test_midshard_scan_prefill_hidden_equality(tiny_model_dir, monkeypatch):
  """Mid-shard ring prefill (_infer_sync hidden outputs, no unembedding):
  the scan path's hidden states match the per-segment loop's."""
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  first = Shard("m", 0, 0, n)  # first-but-not-last: hidden outputs
  prompt = np.array([np.arange(5 * 32) % 250], dtype=np.int64)  # 5 segs: 4+1

  off_eng = _engine(tiny_model_dir, monkeypatch, scan=False)
  h_off, _ = await off_eng.infer_tensor("r", first, prompt)

  on_eng = _engine(tiny_model_dir, monkeypatch, scan=True)
  h_on, _ = await on_eng.infer_tensor("r", first, prompt)

  np.testing.assert_allclose(h_on, h_off, atol=1e-4, rtol=1e-3)


async def test_scan_prefill_composes_with_prefix_cache(tiny_model_dir, monkeypatch):
  """A prefix-cache hit seeds the cache at pos>0; the scan path must fill
  the remaining FULL segments from that offset (prefill_scan at arbitrary
  q_start) and produce the same greedy token as the scan-off engine."""
  import numpy as np

  common = list(np.arange(4 * 32) % 250)  # 4 full segments of shared prefix
  p1 = np.array([common + [7, 9, 11]], dtype=np.int64)
  p2 = np.array([common + list(np.arange(3 * 32) % 199) + [5]], dtype=np.int64)

  async def run(scan: bool):
    eng = _engine(tiny_model_dir, monkeypatch, scan=scan,
                  XOT_PREFIX_CACHE="2", XOT_PREFIX_CACHE_MIN="8")
    n = TINY_LLAMA_CFG["num_hidden_layers"]
    shard = Shard("m", 0, n - 1, n)
    t1, _ = await eng.infer_sample_tensor("ra", shard, p1, temp=0.0)
    # Second request shares the 128-token prefix: seeds from the snapshot,
    # then prefills its 97-token suffix (3 full segments + tail) at pos>0.
    t2, _ = await eng.infer_sample_tensor("rb", shard, p2, temp=0.0)
    return int(t1), int(t2)

  on = await run(True)
  off = await run(False)
  assert on == off, f"prefix-cache + scan-prefill diverged: {on} != {off}"
