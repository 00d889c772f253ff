"""Speculative next-chunk dispatch (decode overlap): while the host ingests
chunk N's tokens (EOS scan, broadcast), chunk N+1 is already running on
device — its input is chunk N's last token, a device array. Mispredictions
roll back state.pos; cache writes past pos are invisible and overwritten
(the verify_draft free-rollback design). This hides the per-chunk host
round-trip.

No reference counterpart — the reference pays a full host round-trip per
TOKEN (node.py:109-147); this is the "beating" half of the bar.
"""
import numpy as np
import pytest

from xotorch_tpu.download.shard_download import LocalShardDownloader
from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
from xotorch_tpu.inference.shard import Shard

from tests.test_model_equivalence import TINY_LLAMA_CFG, make_hf_checkpoint

N = TINY_LLAMA_CFG["num_hidden_layers"]
FULL = Shard("m", 0, N - 1, N)
PROMPT = np.array([[1, 5, 9, 200, 17, 33, 2, 8]], dtype=np.int64)


@pytest.fixture()
def tiny_model_dir(tmp_path):
  return make_hf_checkpoint(tmp_path, TINY_LLAMA_CFG, seed=3)


def _engine(model_dir):
  return JAXShardInferenceEngine(LocalShardDownloader({"m": model_dir}), dtype="float32")


async def _ladder_decode(eng, rid, n_total, size=4, cap=16, temp=0.0):
  """Drive generate_chunk the way the node's fused loop does: ladder growth
  with a next-size hint, EOS ignored (synthetic model)."""
  logits, _ = await eng.infer_tensor(rid, FULL, PROMPT)
  toks = [int(np.argmax(logits[0, -1]))]
  remaining = n_total
  while remaining > 0:
    # Node semantics (node._fused_decode_loop): request the power-of-two
    # ladder size COVERING remaining and discard surplus — never clamp the
    # request to remaining (that would desync the engine's size prediction).
    this = min(size, 1 << (remaining - 1).bit_length())
    rem_after = remaining - this
    hint = (min(min(size * 2, cap), 1 << (rem_after - 1).bit_length())
            if rem_after >= 1 else None)
    out = await eng.generate_chunk(rid, FULL, toks[-1], this, temp=temp, top_k=0,
                                   next_size=hint)
    got = [int(t) for t in out][:remaining]
    toks.extend(got)
    remaining -= len(out)
    size = min(size * 2, cap)
  return toks


async def test_overlap_matches_sequential_greedy(tiny_model_dir, monkeypatch):
  """Token-exact equivalence across the ladder: overlapped decode must equal
  the same loop with speculation disabled — and the speculative path must
  actually have engaged (hit counter), or the test is vacuous."""
  on = _engine(tiny_model_dir)
  got = await _ladder_decode(on, "r", 40)
  assert on._overlap_hits >= 2, "speculative chunks never resolved"

  monkeypatch.setenv("XOT_OVERLAP_CHUNKS", "0")
  off = _engine(tiny_model_dir)
  ref = await _ladder_decode(off, "r", 40)
  assert off._overlap_hits == 0
  assert got == ref


async def test_mispredicted_size_rolls_back(tiny_model_dir):
  """Feed a WRONG next-size hint, then request a different size: the engine
  must discard the speculative chunk, roll pos back, and still produce the
  sequential-greedy stream."""
  eng = _engine(tiny_model_dir)
  logits, _ = await eng.infer_tensor("r", FULL, PROMPT)
  toks = [int(np.argmax(logits[0, -1]))]
  out = await eng.generate_chunk("r", FULL, toks[-1], 4, temp=0.0, top_k=0, next_size=8)
  toks += [int(t) for t in out]
  # Ask for 2, not the hinted 8 -> miss.
  out = await eng.generate_chunk("r", FULL, toks[-1], 2, temp=0.0, top_k=0)
  toks += [int(t) for t in out]
  assert eng._overlap_misses >= 1

  ref_eng = _engine(tiny_model_dir)
  logits, _ = await ref_eng.infer_tensor("o", FULL, PROMPT)
  ref = [int(np.argmax(logits[0, -1]))]
  for size in (4, 2):
    out = await ref_eng.generate_chunk("o", FULL, ref[-1], size, temp=0.0, top_k=0)
    ref += [int(t) for t in out]
  assert toks == ref


async def test_interleaved_segment_forward_discards_spec(tiny_model_dir):
  """A per-token forward between chunks (the ring path / draft verify uses
  the same seam) must supersede the in-flight speculative chunk: the logits
  it returns must equal a never-speculated engine's at the same position."""
  eng = _engine(tiny_model_dir)
  logits, _ = await eng.infer_tensor("r", FULL, PROMPT)
  tok0 = int(np.argmax(logits[0, -1]))
  out = await eng.generate_chunk("r", FULL, tok0, 4, temp=0.0, top_k=0, next_size=8)
  chunk = [int(t) for t in out]
  assert "r" in eng._spec_next  # speculation in flight
  lg, _ = await eng.infer_tensor("r", FULL, np.array([[chunk[-1]]], dtype=np.int64))
  assert "r" not in eng._spec_next  # superseded

  ref_eng = _engine(tiny_model_dir)
  logits, _ = await ref_eng.infer_tensor("o", FULL, PROMPT)
  out = await ref_eng.generate_chunk("o", FULL, int(np.argmax(logits[0, -1])), 4,
                                     temp=0.0, top_k=0)
  ref_chunk = [int(t) for t in out]
  assert chunk == ref_chunk
  ref_lg, _ = await ref_eng.infer_tensor("o", FULL, np.array([[ref_chunk[-1]]], dtype=np.int64))
  np.testing.assert_allclose(lg, ref_lg, atol=1e-5, rtol=1e-5)


async def test_overlap_sampled_stream_reproduces(tiny_model_dir, monkeypatch):
  """temp>0: the speculative dispatch draws from the SAME engine-global PRNG
  stream in the same order as sequential dispatch (one draw per chunk), so
  an all-hits run is stream-identical to the overlap-off run."""
  monkeypatch.setenv("XOT_SEED", "1234")
  on = _engine(tiny_model_dir)
  got = await _ladder_decode(on, "r", 24, temp=0.8)
  assert on._overlap_hits >= 1
  monkeypatch.setenv("XOT_OVERLAP_CHUNKS", "0")
  off = _engine(tiny_model_dir)
  ref = await _ladder_decode(off, "r", 24, temp=0.8)
  assert got == ref


async def test_cache_tail_uses_committed_pos(tiny_model_dir, monkeypatch):
  """Near the cache cap, capacity math must use the COMMITTED position, not
  the speculatively inflated one: overlap-on must drain exactly as many
  tokens as overlap-off before CacheExhausted — the review repro had it
  dropping a whole final chunk the device had already computed."""
  from xotorch_tpu.inference.engine import CacheExhausted

  monkeypatch.setenv("XOT_CACHE_LEN", "16")
  monkeypatch.setenv("XOT_MAX_CACHE_LEN", "32")

  async def drain(eng, rid):
    logits, _ = await eng.infer_tensor(rid, FULL, PROMPT)  # 8-token prefill
    toks = [int(np.argmax(logits[0, -1]))]
    try:
      while True:
        out = await eng.generate_chunk(rid, FULL, toks[-1], 8, temp=0.0, top_k=0,
                                       next_size=8)
        toks.extend(int(t) for t in out)
    except CacheExhausted:
      return toks

  on = await drain(_engine(tiny_model_dir), "r")
  monkeypatch.setenv("XOT_OVERLAP_CHUNKS", "0")
  off = await drain(_engine(tiny_model_dir), "r")
  assert on == off, f"overlap drained {len(on)} tokens, sequential {len(off)}"


async def _batched_ladder(eng, rid, prompt, n_total, size=4, cap=8, temp=0.0):
  """Concurrent-request driver through the BATCHER (default XOT_DECODE_BATCH):
  same ladder + hint math as the node's fused loop."""
  import numpy as _np
  logits, _ = await eng.infer_tensor(rid, FULL, prompt)
  toks = [int(_np.argmax(logits[0, -1]))]
  remaining = n_total
  while remaining > 0:
    this = min(size, 1 << (remaining - 1).bit_length())
    rem_after = remaining - this
    hint = (min(min(size * 2, cap), 1 << (rem_after - 1).bit_length())
            if rem_after >= 1 else None)
    out = await eng.generate_chunk(rid, FULL, toks[-1], this, temp=temp, top_k=0,
                                   next_size=hint)
    toks.extend(int(t) for t in out)
    remaining -= len(out)
    size = min(size * 2, cap)
  return toks


async def test_batch_overlap_matches_solo_streams(tiny_model_dir, monkeypatch):
  """Batch-level overlap (XOT_OVERLAP_BATCH=1 opt-in — default off because
  jittery membership makes it thrash, engine._batch_overlap_on): three
  concurrent requests coalesce in the batcher and the NEXT batch is
  speculatively dispatched from the current batch's device-side last
  tokens. Every stream must equal its solo run, and the speculative batch
  must actually have resolved at least once."""
  import asyncio
  monkeypatch.setenv("XOT_OVERLAP_BATCH", "1")
  prompts = {
    "a": np.array([[1, 5, 9, 2]], dtype=np.int64),
    "b": np.array([[7, 3, 11]], dtype=np.int64),
    "c": np.array([[42, 17, 5, 9, 100, 3]], dtype=np.int64),
  }
  want = {}
  for rid, p in prompts.items():
    solo = _engine(tiny_model_dir)
    want[rid] = await _ladder_decode_prompt(solo, rid, p, 24)

  eng = _engine(tiny_model_dir)
  results = await asyncio.gather(*(
    _batched_ladder(eng, rid, p, 24) for rid, p in prompts.items()))
  got = dict(zip(prompts.keys(), results))
  assert eng._overlap_batch_hits >= 1, "speculative batch never resolved"
  for rid in want:
    assert got[rid] == want[rid], rid


async def _ladder_decode_prompt(eng, rid, prompt, n_total, size=4, cap=8):
  import numpy as _np
  logits, _ = await eng.infer_tensor(rid, FULL, prompt)
  toks = [int(_np.argmax(logits[0, -1]))]
  remaining = n_total
  while remaining > 0:
    this = min(size, 1 << (remaining - 1).bit_length())
    out = await eng.generate_chunk(rid, FULL, toks[-1], this, temp=0.0, top_k=0)
    toks.extend(int(t) for t in out)
    remaining -= len(out)
    size = min(size * 2, cap)
  return toks


async def test_batch_overlap_membership_change_rolls_back(tiny_model_dir, monkeypatch):
  """One member finishes while a speculative batch is in flight: the others
  must keep producing their exact solo streams through the re-formed
  batches (misprediction rollback across the whole batch)."""
  import asyncio
  monkeypatch.setenv("XOT_OVERLAP_BATCH", "1")
  pa = np.array([[1, 5, 9, 2]], dtype=np.int64)
  pb = np.array([[7, 3, 11]], dtype=np.int64)

  solo_a = await _ladder_decode_prompt(_engine(tiny_model_dir), "a", pa, 40)
  solo_b = await _ladder_decode_prompt(_engine(tiny_model_dir), "b", pb, 12)

  eng = _engine(tiny_model_dir)
  res_a, res_b = await asyncio.gather(
    _batched_ladder(eng, "a", pa, 40),  # long: keeps decoding after b ends
    _batched_ladder(eng, "b", pb, 12),
  )
  await eng.clear_request("b")
  assert res_a == solo_a
  assert res_b == solo_b


async def test_verify_draft_with_spec_in_flight(tiny_model_dir):
  """Prompt-lookup verification while a speculative chunk is in flight:
  verify must read the COMMITTED position (the review repro had it reading
  the inflated pos, landing post-verify state past the real sequence and
  pulling stale cache slots into the attention window). The combined
  stream must equal plain greedy decode."""
  solo = await _ladder_decode(_engine(tiny_model_dir), "s", 20, size=4, cap=4)

  eng = _engine(tiny_model_dir)
  logits, _ = await eng.infer_tensor("r", FULL, PROMPT)
  toks = [int(np.argmax(logits[0, -1]))]
  out = await eng.generate_chunk("r", FULL, toks[-1], 4, temp=0.0, top_k=0, next_size=4)
  toks += [int(t) for t in out]
  assert "r" in eng._spec_next  # speculation in flight
  # Draft = the TRUE greedy continuation (from the solo run), so verify
  # accepts everything and appends its bonus token.
  draft = solo[len(toks):len(toks) + 3]
  accepted = await eng.verify_draft("r", FULL, toks[-1], draft)
  assert accepted is not None and list(accepted)[:3] == draft
  toks += [int(t) for t in accepted]
  # Continue fused decoding to the end; every token must match solo greedy.
  while len(toks) < len(solo):
    out = await eng.generate_chunk("r", FULL, toks[-1], 4, temp=0.0, top_k=0, next_size=4)
    toks += [int(t) for t in out]
  assert toks[:len(solo)] == solo


async def test_clear_request_drops_spec(tiny_model_dir):
  eng = _engine(tiny_model_dir)
  logits, _ = await eng.infer_tensor("r", FULL, PROMPT)
  await eng.generate_chunk("r", FULL, int(np.argmax(logits[0, -1])), 4,
                           temp=0.0, top_k=0, next_size=8)
  assert "r" in eng._spec_next
  await eng.clear_request("r")
  assert "r" not in eng._spec_next


async def test_oom_recovery_drops_inflight_spec(tiny_model_dir):
  """HBM-exhaustion recovery while a speculative chunk is in flight: the
  spec record must be released with the states (a stale record must never
  resolve against a recreated state), and the victim fails loudly with
  RequestStateLost rather than silently restarting."""
  from xotorch_tpu.inference.engine import RequestStateLost

  eng = _engine(tiny_model_dir)
  logits, _ = await eng.infer_tensor("r", FULL, PROMPT)
  await eng.generate_chunk("r", FULL, int(np.argmax(logits[0, -1])), 4,
                           temp=0.0, top_k=0, next_size=4)
  assert "r" in eng._spec_next
  eng._free_device_memory()
  assert eng._spec_next == {}
  with pytest.raises(RequestStateLost):
    await eng.generate_chunk("r", FULL, 1, 4, temp=0.0, top_k=0)
