"""Paged KV-cache pool + ragged paged-attention decode (XOT_PAGED_KV=1).

Correctness bars, all against the contiguous default path:
- pool allocation/free/refcount invariants (paged_cache.PagePool);
- page-table gather == contiguous cache content at mixed lengths, and the
  paged attention op (XLA fallback AND interpret-mode Pallas kernel) ==
  the dense masked reference;
- per-row (not max-row) page reads: the kernel's kv index map SATURATES at
  each row's last occupied page, so DMA stops at ceil(len/page) pages;
- an engine-level mixed-length concurrent batch decodes streams BYTE-EQUAL
  to the contiguous path with ZERO cache grow-copies (the contiguous run
  of the same workload grows) and per-request page counts proportional to
  each request's own length;
- prefix-cache page sharing: a warm request's table HEADS with the entry's
  shared pages (one arena copy of the prefix), shared pages are never
  mutated while streams diverge past the prefix (copy-on-write by
  construction), and refcounts drain to zero.

The 16k-member mixed batch of the acceptance criterion runs on-chip via the
bench `paged` stage (bench.py); here the same invariants run at
CPU-sized lengths (page 16, prompts 40/3/4 growing past their po2 buckets).
"""
import asyncio

import numpy as np
import pytest

from xotorch_tpu.download.shard_download import LocalShardDownloader
from xotorch_tpu.inference.engine import CacheExhausted
from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
from xotorch_tpu.inference.shard import Shard

from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint


@pytest.fixture(scope="module")
def tiny_model_dir(tmp_path_factory):
  # Module-scoped: the torch-built checkpoint is identical across tests and
  # this file already builds several engines per test.
  return make_hf_checkpoint(tmp_path_factory.mktemp("paged"), TINY_LLAMA_CFG, seed=3)


def _full_shard():
  n = TINY_LLAMA_CFG["num_hidden_layers"]
  return Shard("m", 0, n - 1, n)


def _paged_env(monkeypatch, **extra):
  monkeypatch.setenv("XOT_SEED", "7")
  monkeypatch.setenv("XOT_CACHE_LEN", "16")
  monkeypatch.setenv("XOT_PAGED_KV", "1")
  monkeypatch.setenv("XOT_KV_PAGE", "16")
  monkeypatch.setenv("XOT_KV_POOL_TOKENS", "512")
  for k, v in extra.items():
    monkeypatch.setenv(k, v)


def _engine(model_dir):
  return JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32")


def _tiny_cfg_obj():
  from xotorch_tpu.models.config import config_from_hf_dict
  return config_from_hf_dict(TINY_LLAMA_CFG)


async def _decode_loop(eng, rid, prompt, chunks=4, chunk_size=8):
  shard = _full_shard()
  logits, _ = await eng.infer_tensor(rid, shard, prompt)
  tok = int((await eng.sample(logits, temp=0.0))[0])
  toks = [tok]
  for _ in range(chunks):
    out = await eng.generate_chunk(rid, shard, toks[-1], chunk_size, temp=0.0)
    toks.extend(int(t) for t in out)
  return toks


_PROMPTS = {
  "long": np.array([np.arange(40) % 250 + 1], dtype=np.int64),
  "s1": np.array([[7, 3, 11]], dtype=np.int64),
  "s2": np.array([[42, 17, 5, 9]], dtype=np.int64),
}


# ------------------------------------------------------------- pool basics


def test_page_pool_alloc_free_refcount_invariants():
  import jax.numpy as jnp
  from xotorch_tpu.inference.jax_engine.paged_cache import PagePool
  pool = PagePool(_tiny_cfg_obj(), 2, num_pages=8, page_size=16, dtype=jnp.float32)
  assert pool.free_pages == 7  # page 0 reserved scratch
  assert pool.pages_in_use == 0

  a = pool.alloc(3)
  assert len(a) == 3 and len(set(a)) == 3 and 0 not in a
  assert pool.pages_in_use == 3
  assert all(pool.refcount(p) == 1 for p in a)

  pool.incref(a[:2])
  assert [pool.refcount(p) for p in a] == [2, 2, 1]
  pool.decref(a)  # drops one ref each: only the last page frees
  assert pool.pages_in_use == 2 and pool.free_pages == 5
  pool.decref(a[:2])
  assert pool.pages_in_use == 0 and pool.free_pages == 7

  b = pool.alloc(7)  # everything usable
  with pytest.raises(CacheExhausted):
    pool.alloc(1)
  pool.decref(b)

  with pytest.raises(AssertionError):
    pool.decref([0])  # the scratch page is untouchable
  with pytest.raises(AssertionError):
    pool.decref([b[0]])  # double free
  assert pool.pages_for(1) == 1 and pool.pages_for(16) == 1 and pool.pages_for(17) == 2


def test_commit_gather_roundtrip_and_attention_equality():
  """Page-table gather reproduces the contiguous cache at mixed lengths,
  and both paged-attention implementations match the dense reference."""
  import jax
  import jax.numpy as jnp
  from xotorch_tpu.inference.jax_engine.paged_cache import PagePool, commit_pages, gather_pages
  from xotorch_tpu.ops.attention import gqa_attention
  from xotorch_tpu.ops.paged_attention import paged_decode_attention

  cfg = _tiny_cfg_obj()
  L, page, P = 2, 8, 16
  rng = np.random.default_rng(0)
  pool = PagePool(cfg, L, P, page, jnp.float32)
  lengths = [11, 5]
  pt = np.zeros((2, 2), np.int32)
  dense_k = np.zeros((2, 16, cfg.num_kv_heads, cfg.head_dim), np.float32)
  dense_v = np.zeros_like(dense_k)
  for b, n_tok in enumerate(lengths):
    cache = {
      "k": jnp.asarray(rng.standard_normal((L, 1, 16, cfg.num_kv_heads, cfg.head_dim)),
                       jnp.float32),
      "v": jnp.asarray(rng.standard_normal((L, 1, 16, cfg.num_kv_heads, cfg.head_dim)),
                       jnp.float32),
    }
    n = pool.pages_for(n_tok)
    ids = pool.alloc(n)
    pt[b, :n] = ids
    pool.arena = commit_pages(pool.arena, cache, np.asarray(ids, np.int32), 0)
    # Round-trip: gathered pages == the contiguous source (up to n*page).
    back = gather_pages(pool.arena, np.asarray(ids, np.int32))
    np.testing.assert_array_equal(np.asarray(back["k"]),
                                  np.asarray(cache["k"][:, :, :n * page]))
    dense_k[b] = np.asarray(cache["k"][0, 0, :16])
    dense_v[b] = np.asarray(cache["v"][0, 0, :16])

  q = rng.standard_normal((2, 1, cfg.num_heads, cfg.head_dim)).astype(np.float32)
  lens = jnp.asarray(lengths, jnp.int32)
  ref = gqa_attention(jnp.asarray(q), jnp.asarray(dense_k), jnp.asarray(dense_v),
                      (lens - 1)[:, None], kv_valid_len=lens)
  layer0 = {"k": pool.arena["k"][0], "v": pool.arena["v"][0]}
  got_xla = paged_decode_attention(jnp.asarray(q), layer0["k"], layer0["v"],
                                   jnp.asarray(pt), lens)
  got_kernel = paged_decode_attention(jnp.asarray(q), layer0["k"], layer0["v"],
                                      jnp.asarray(pt), lens, use_kernel=True,
                                      interpret=True)
  np.testing.assert_allclose(np.asarray(got_xla), np.asarray(ref), atol=1e-5)
  np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(ref), atol=1e-5)


def test_kernel_reads_per_row_pages_not_max():
  """The ragged kernel's kv index map saturates at each ROW's last occupied
  page: past it, consecutive grid steps return the SAME page (Pallas elides
  the DMA), so a short row co-batched with a long one streams exactly
  ceil(len/page) distinct pages — per-row reads, not max-row reads."""
  import jax.numpy as jnp
  from xotorch_tpu.ops.paged_attention import _logical_page_index

  page = 16
  maxp = 64  # a 1024-token neighbour forces a 64-wide table
  for n_tok, want_pages in ((33, 3), (16, 1), (1, 1), (1024, 64)):
    seen = [int(_logical_page_index(j, jnp.int32(n_tok), page)) for j in range(maxp)]
    assert len(set(seen)) == want_pages, (n_tok, seen)
    # Saturation: after the last occupied page the index STOPS changing.
    last = -(-n_tok // page) - 1
    assert all(s == last for s in seen[last:])
    assert seen[:last + 1] == list(range(last + 1))


# --------------------------------------------------------- engine-level e2e


async def test_mixed_length_batch_stream_equal_zero_grow_copies(tiny_model_dir, monkeypatch):
  """Mixed-length concurrent batch under XOT_PAGED_KV=1: token streams
  byte-equal to the contiguous path, zero cache grow-copies (the SAME
  workload on the contiguous path grows), per-request page counts track
  each request's own length, and the pool drains on clear_request."""
  monkeypatch.setenv("XOT_SEED", "7")
  monkeypatch.setenv("XOT_CACHE_LEN", "16")

  # Contiguous solo references — these GROW (each crosses its po2 bucket).
  want, contiguous_grows = {}, 0
  for rid, prompt in _PROMPTS.items():
    eng = _engine(tiny_model_dir)
    want[rid] = await _decode_loop(eng, rid, prompt)
    contiguous_grows += eng._grow_copies
  assert contiguous_grows > 0, "workload must exercise contiguous growth to prove the contrast"

  _paged_env(monkeypatch)
  eng = _engine(tiny_model_dir)
  results = await asyncio.gather(*(
    _decode_loop(eng, rid, prompt) for rid, prompt in _PROMPTS.items()
  ))
  got = dict(zip(_PROMPTS.keys(), results))
  for rid in want:
    assert got[rid] == want[rid], f"{rid}: paged {got[rid]} != contiguous {want[rid]}"
  assert eng._grow_copies == 0, "paged decode must never grow-copy"

  shard = _full_shard()
  ctx = eng._contexts[shard]
  pool = ctx.page_pool
  states = ctx.states
  for rid in _PROMPTS:
    st = states[rid]
    assert st.cache is None, "committed request must have freed its contiguous buffer"
    # Per-request page counts proportional to each request's OWN length —
    # the long member never forces the short members to its size.
    assert len(st.pages) == pool.pages_for(st.pos), (rid, st.pos, st.pages)
  assert len(states["long"].pages) > len(states["s1"].pages)

  for rid in _PROMPTS:
    await eng.clear_request(rid)
  assert pool.pages_in_use == 0, "pool must drain when requests clear"


async def test_paged_kernel_engine_stream_equal(tiny_model_dir, monkeypatch):
  """XOT_PAGED_KERNEL=1 (interpret off-TPU) swaps the XLA gather fallback
  for the Pallas ragged kernel — streams must stay byte-equal."""
  monkeypatch.setenv("XOT_SEED", "7")
  monkeypatch.setenv("XOT_CACHE_LEN", "16")
  prompt = _PROMPTS["long"]
  eng = _engine(tiny_model_dir)
  want = await _decode_loop(eng, "r", prompt, chunks=2)

  _paged_env(monkeypatch, XOT_PAGED_KERNEL="1")
  eng2 = _engine(tiny_model_dir)
  got = await _decode_loop(eng2, "r", prompt, chunks=2)
  assert got == want


async def test_prefix_cache_shares_pages_copy_on_write(tiny_model_dir, monkeypatch):
  """Under XOT_PAGED_KV the prefix cache SHARES the prefill's full pages
  (incref) instead of snapshotting a cache copy: a warm request's page
  table heads with the shared ids, the shared pages' contents never change
  while the two streams diverge past the prefix, and every reference
  (requests + entries) must drain before the pages free."""
  _paged_env(monkeypatch, XOT_PREFIX_CACHE_MIN="16")
  shard = _full_shard()
  prompt_a = np.array([np.arange(44) % 250 + 1], dtype=np.int64)
  prompt_b = np.concatenate([prompt_a, np.array([[99, 98, 97, 96]])], axis=1)

  async def generate(eng, rid, prompt):
    tok, _ = await eng.infer_sample_tensor(rid, shard, prompt, temp=0.0)
    toks = [int(tok)]
    for _ in range(2):
      out = await eng.generate_chunk(rid, shard, toks[-1], 8, temp=0.0)
      toks.extend(int(t) for t in out)
    return toks

  # Cold contiguous reference for the warm request's stream.
  monkeypatch.setenv("XOT_PAGED_KV", "0")
  want_b = await generate(_engine(tiny_model_dir), "cold", prompt_b)
  monkeypatch.setenv("XOT_PAGED_KV", "1")

  eng = _engine(tiny_model_dir)
  await generate(eng, "ra", prompt_a)
  ctx = eng._contexts[shard]
  pool = ctx.page_pool
  (_, (_, entry)), = ctx.prefix_cache.items()
  shared = list(entry["pages"])
  assert entry["len"] == 32 and len(shared) == 2  # 44 tokens -> 2 full 16-pages
  assert [pool.refcount(p) for p in shared] == [2, 2]  # ra + entry
  shared_before = np.asarray(pool.arena["k"][:, np.asarray(shared)])

  got_b = await generate(eng, "rb", prompt_b)
  assert eng._prefix_hits == 1
  assert eng._prefix_tokens_saved == 32  # whole pages only
  assert got_b == want_b, f"warm paged stream {got_b} != cold contiguous {want_b}"
  # The warm request's table HEADS with the shared pages — one arena copy
  # of the prefix serves both requests and the entry.
  assert ctx.states["rb"].pages[:2] == shared
  # Copy-on-write divergence: both requests appended past the prefix into
  # their OWN pages; the shared pages were never written.
  shared_after = np.asarray(pool.arena["k"][:, np.asarray(shared)])
  np.testing.assert_array_equal(shared_before, shared_after)

  await eng.clear_request("ra")
  await eng.clear_request("rb")
  # Both prefix entries (ra's and rb's prompts both stored) still hold refs.
  assert all(pool.refcount(p) >= 1 for p in shared)
  assert pool.pages_in_use > 0
  eng._clear_prefix_cache(ctx)
  assert pool.pages_in_use == 0


async def test_pool_pressure_evicts_prefix_entries_not_requests(tiny_model_dir, monkeypatch):
  """Prefix entries are caches: when the pool can't satisfy a live request,
  the oldest entries are evicted (their pages decref'd) and the allocation
  retried — clients never see 'pool exhausted' for capacity that is merely
  pinned by reusable snapshots."""
  # 5 usable pages of 16 tokens: request A (44-token prompt + decode) takes
  # 4 and its prefix entry pins 2 of them; after A clears, request B needs
  # 4 of its own — impossible without reclaiming A's entry mid-decode.
  _paged_env(monkeypatch, XOT_KV_POOL_TOKENS="80", XOT_PREFIX_CACHE_MIN="16")
  shard = _full_shard()
  prompt_a = np.array([np.arange(44) % 250 + 1], dtype=np.int64)
  prompt_b = np.array([np.arange(44) % 250 + 101], dtype=np.int64)  # no shared prefix

  async def generate(eng, rid, prompt):
    tok, _ = await eng.infer_sample_tensor(rid, shard, prompt, temp=0.0)
    out = await eng.generate_chunk(rid, shard, int(tok), 8, temp=0.0)
    return [int(tok)] + [int(t) for t in out]

  eng = _engine(tiny_model_dir)
  await generate(eng, "ra", prompt_a)
  ctx = eng._contexts[shard]
  assert len(ctx.prefix_cache) == 1  # A's entry pins 2 full pages
  await eng.clear_request("ra")
  # B's prefill+decode needs more pages than remain unpinned; A's entry
  # must yield instead of the request failing.
  await generate(eng, "rb", prompt_b)
  pool = ctx.page_pool
  # A's entry was reclaimed; only B's own entry (over B's pages) survives.
  assert len(ctx.prefix_cache) == 1
  # Spill-then-drop: the reclaim demoted A's warm prefix to the host tier
  # (kv_offload) instead of destroying it, and counted the eviction.
  assert eng._prefix_evictions >= 1
  assert eng._host_kv is not None and eng._host_spill_bytes > 0
  host_entry, common = eng._host_kv.match(ctx.shard, prompt_a.reshape(-1), 43)
  assert host_entry is not None and common == 43
  (_, (_, entry)), = ctx.prefix_cache.items()
  assert set(entry["pages"]) <= set(ctx.states["rb"].pages)
  await eng.clear_request("rb")
  eng._clear_prefix_cache(ctx)
  assert pool.pages_in_use == 0


async def test_per_token_decode_stays_paged(tiny_model_dir, monkeypatch):
  """Per-token fused-sample steps on a committed request run NATIVE to the
  page arena (virtual KV addressing — no gather back to a contiguous
  buffer): the stream must continue exactly as the all-contiguous
  engine's, with the unpage counter still at zero."""
  monkeypatch.setenv("XOT_SEED", "7")
  monkeypatch.setenv("XOT_CACHE_LEN", "16")
  shard = _full_shard()
  prompt = _PROMPTS["long"]

  async def mixed(eng, rid):
    # chunked decode (paged when enabled) ...
    logits, _ = await eng.infer_tensor(rid, shard, prompt)
    tok = int((await eng.sample(logits, temp=0.0))[0])
    toks = [tok]
    out = await eng.generate_chunk(rid, shard, toks[-1], 8, temp=0.0)
    toks.extend(int(t) for t in out)
    # ... then per-token fused-sample steps (paged-native bucket fallback)
    for _ in range(3):
      tok, _ = await eng.infer_sample_tensor(
        rid, shard, np.asarray([[toks[-1]]], dtype=np.int64), temp=0.0)
      toks.append(int(tok))
    # ... and back to a chunk
    out = await eng.generate_chunk(rid, shard, toks[-1], 8, temp=0.0)
    toks.extend(int(t) for t in out)
    return toks

  want = await mixed(_engine(tiny_model_dir), "r")
  _paged_env(monkeypatch)
  eng = _engine(tiny_model_dir)
  got = await mixed(eng, "r")
  assert got == want
  assert eng._unpage_calls == 0, "per-token steps must not gather pages back"
