"""Real-checkpoint readiness drill (VERDICT r4 next #6).

Real weights can't enter this zero-egress container, so this drill
synthesizes a checkpoint laid out EXACTLY like a real HF repo — multi-file
sharded safetensors with `model.safetensors.index.json`, `config.json`, and
a real fast-tokenizer file set (tokenizer.json + tokenizer_config.json with
a chat template + special_tokens_map.json) — for a REGISTRY model id
(llama-3.2-1b, 16 layers), then drives the full user path with zero code
edits:

    seed dir -> `xot run` CLI -> seed_models -> HFShardDownloader.ensure_shard
    (local-complete fast path, no network) -> load_shard_params (weight-map
    index resolution) -> AutoTokenizer chat template -> generate -> decoded
    text on stdout.

What a real deployment would hit that synthetic-model tests don't: weight-map
multi-file resolution, HF tensor naming end to end, AutoTokenizer loading
from disk, chat-template application, and the downloader's local-complete
decision. Parity: /root/reference/xotorch/download/new_shard_download.py:181-194.
"""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from tests.xproc_harness import node_env

REPO = Path(__file__).resolve().parent.parent

MODEL_ID = "llama-3.2-1b"          # registry card: 16 layers, repo unsloth/Llama-3.2-1B-Instruct
REPO_DIRNAME = "unsloth--Llama-3.2-1B-Instruct"
N_LAYERS, HIDDEN, HEADS, KV_HEADS, INTER, VOCAB = 16, 64, 4, 2, 128, 128


def _write_tokenizer(d: Path) -> None:
  """A real fast tokenizer (WordLevel), loadable by AutoTokenizer, with the
  special tokens and chat template a llama checkpoint ships."""
  from tokenizers import Tokenizer, models, pre_tokenizers

  words = ["hello", "world", "ring", "check", "the", "a", "ok", "yes", "no",
           "user", "assistant", "system", ":", ",", ".", "!", "?"]
  vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
  for i, w in enumerate(words):
    vocab[w] = 3 + i
  for i in range(VOCAB - len(vocab)):
    vocab[f"w{i}"] = len(vocab)
  tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
  tok.pre_tokenizer = pre_tokenizers.Whitespace()
  tok.save(str(d / "tokenizer.json"))
  (d / "tokenizer_config.json").write_text(json.dumps({
    "tokenizer_class": "PreTrainedTokenizerFast",
    "bos_token": "<s>", "eos_token": "</s>", "unk_token": "<unk>",
    "chat_template": (
      "{% for message in messages %}{{ message['role'] }} : {{ message['content'] }} "
      "{% endfor %}{% if add_generation_prompt %}assistant : {% endif %}"
    ),
  }))
  (d / "special_tokens_map.json").write_text(json.dumps(
    {"bos_token": "<s>", "eos_token": "</s>", "unk_token": "<unk>"}))


def _make_checkpoint(d: Path) -> None:
  """HF-llama-named tensors sharded over THREE safetensors files with a
  weight-map index, like a real multi-file repo."""
  from safetensors.numpy import save_file

  rng = np.random.default_rng(11)
  head_dim = HIDDEN // HEADS

  def w(*shape):
    return (rng.standard_normal(shape) * 0.02).astype(np.float32)

  tensors = {"model.embed_tokens.weight": w(VOCAB, HIDDEN),
             "model.norm.weight": np.ones((HIDDEN,), np.float32),
             "lm_head.weight": w(VOCAB, HIDDEN)}
  for i in range(N_LAYERS):
    p = f"model.layers.{i}."
    tensors[p + "self_attn.q_proj.weight"] = w(HEADS * head_dim, HIDDEN)
    tensors[p + "self_attn.k_proj.weight"] = w(KV_HEADS * head_dim, HIDDEN)
    tensors[p + "self_attn.v_proj.weight"] = w(KV_HEADS * head_dim, HIDDEN)
    tensors[p + "self_attn.o_proj.weight"] = w(HIDDEN, HEADS * head_dim)
    tensors[p + "mlp.gate_proj.weight"] = w(INTER, HIDDEN)
    tensors[p + "mlp.up_proj.weight"] = w(INTER, HIDDEN)
    tensors[p + "mlp.down_proj.weight"] = w(HIDDEN, INTER)
    tensors[p + "input_layernorm.weight"] = np.ones((HIDDEN,), np.float32)
    tensors[p + "post_attention_layernorm.weight"] = np.ones((HIDDEN,), np.float32)

  # Three files, split by layer range (real repos split by size; the index
  # contract is identical) — embed in the first, head/norm in the last.
  files = {"model-00001-of-00003.safetensors": {},
           "model-00002-of-00003.safetensors": {},
           "model-00003-of-00003.safetensors": {}}
  weight_map = {}
  for name, arr in tensors.items():
    if name.startswith("model.layers."):
      layer = int(name.split(".")[2])
      f = (f"model-0000{min(layer // 6 + 1, 3)}-of-00003.safetensors")
    elif "embed" in name:
      f = "model-00001-of-00003.safetensors"
    else:
      f = "model-00003-of-00003.safetensors"
    files[f][name] = arr
    weight_map[name] = f
  for fname, group in files.items():
    save_file(group, str(d / fname))
  total = sum(a.nbytes for a in tensors.values())
  (d / "model.safetensors.index.json").write_text(json.dumps(
    {"metadata": {"total_size": total}, "weight_map": weight_map}))

  (d / "config.json").write_text(json.dumps({
    "architectures": ["LlamaForCausalLM"], "model_type": "llama",
    "hidden_size": HIDDEN, "intermediate_size": INTER,
    "num_attention_heads": HEADS, "num_key_value_heads": KV_HEADS,
    "num_hidden_layers": N_LAYERS, "vocab_size": VOCAB,
    "max_position_embeddings": 2048, "rope_theta": 500000.0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "bos_token_id": 1, "eos_token_id": 2, "torch_dtype": "float32",
  }))
  _write_tokenizer(d)


def test_xot_run_from_seeded_checkpoint(tmp_path):
  seed = tmp_path / "seed" / REPO_DIRNAME
  seed.mkdir(parents=True)
  _make_checkpoint(seed)

  home = tmp_path / "xot_home"
  env = node_env(XOT_HOME=home)
  r = subprocess.run(
    [sys.executable, "-m", "xotorch_tpu.main", "run", MODEL_ID,
     "--prompt", "hello world ring check",
     "--models-seed-dir", str(tmp_path / "seed"),
     "--disable-tui", "--max-generate-tokens", "8",
     "--listen-port", "52488", "--broadcast-port", "52489",
     "--node-port", "52498", "--chatgpt-api-port", "52478"],
    env=env, capture_output=True, text=True, timeout=420, cwd=str(REPO),
  )
  assert r.returncode == 0, f"xot run failed:\n{r.stdout[-3000:]}\n{r.stderr[-2000:]}"
  # seed_models moved the dir into XOT_HOME and generation produced text.
  assert (home / "models" / REPO_DIRNAME / "model.safetensors.index.json").exists()
  assert not (tmp_path / "seed" / REPO_DIRNAME).exists(), "seed dir should have been MOVED"
  assert "Generated" in r.stdout or "tok/s" in r.stdout or len(r.stdout.strip()) > 0, r.stdout


@pytest.mark.asyncio
async def test_ensure_shard_local_complete_no_network(tmp_path, monkeypatch):
  """ensure_shard on a complete seeded dir returns WITHOUT any network I/O
  (fetch_file_list would raise in this zero-egress container)."""
  from xotorch_tpu.download.hf_shard_download import HFShardDownloader
  from xotorch_tpu.inference.shard import Shard

  target = tmp_path / "models" / REPO_DIRNAME
  target.mkdir(parents=True)
  _make_checkpoint(target)
  monkeypatch.setenv("XOT_HOME", str(tmp_path))

  dl = HFShardDownloader()
  path = await dl.ensure_shard(Shard(MODEL_ID, 0, N_LAYERS - 1, N_LAYERS),
                               "JAXShardInferenceEngine")
  assert path == target

  # A missing weight file flips the decision back to the network path.
  (target / "model-00002-of-00003.safetensors").unlink()
  dl2 = HFShardDownloader()
  with pytest.raises(Exception):
    await dl2.ensure_shard(Shard(MODEL_ID, 0, N_LAYERS - 1, N_LAYERS),
                           "JAXShardInferenceEngine")


@pytest.mark.asyncio
async def test_shard_slice_local_complete(tmp_path, monkeypatch):
  """A shard needing only layers 0-7 is satisfied by the files its
  allow-patterns name even when a LATER shard file is missing."""
  from xotorch_tpu.download.hf_shard_download import HFShardDownloader
  from xotorch_tpu.inference.shard import Shard

  target = tmp_path / "models" / REPO_DIRNAME
  target.mkdir(parents=True)
  _make_checkpoint(target)
  (target / "model-00003-of-00003.safetensors").unlink()  # layers 12+, head
  monkeypatch.setenv("XOT_HOME", str(tmp_path))

  dl = HFShardDownloader()
  # Layers 0-5 live entirely in file 1 (+ embed); file 3's absence is fine.
  path = await dl.ensure_shard(Shard(MODEL_ID, 0, 5, N_LAYERS), "JAXShardInferenceEngine")
  assert path == target
  # The LAST shard needs file 3 -> not locally complete -> network path raises.
  with pytest.raises(Exception):
    await dl.ensure_shard(Shard(MODEL_ID, 12, N_LAYERS - 1, N_LAYERS), "JAXShardInferenceEngine")


# ---------------------------------------------------------------- llava drill
# VERDICT r4 missing #4: a real llava-layout checkpoint + AutoProcessor file
# set had never been loaded. This drill saves a REAL (tiny) llava repo via
# transformers save_pretrained — authentic tensor naming
# (language_model.model.layers..., vision_tower..., multi_modal_projector...)
# sharded over multiple safetensors files with an index — plus the full
# processor file set (CLIPImageProcessor preprocessor_config + tokenizer +
# processor_config with chat template), and drives an image chat request
# through the serving stack: AutoProcessor resolution (tokenizers.py
# processor patching), <image> placeholder tokenization, patch-feature
# merge, generation.

LLAVA_MODEL_ID = "llava-1.5-7b-hf"          # registry card: 32 layers, vision
LLAVA_DIRNAME = "llava-hf--llava-1.5-7b-hf"
IMAGE_TOKEN_ID = 120


def _make_llava_checkpoint(d: Path) -> None:
  from transformers import CLIPImageProcessor

  from tests.test_vision_llava import save_tiny_llava, tiny_llava_cfg

  # Shared tiny-llava shape; the drill uses the registry card's 32 layers
  # and this checkpoint's small vocab. max_shard_size in save_tiny_llava
  # forces the REAL multi-file + index layout big repos have.
  cfg = tiny_llava_cfg(n_text_layers=32, vocab=VOCAB,
                       image_token_index=IMAGE_TOKEN_ID,
                       max_position_embeddings=2048)
  save_tiny_llava(d, cfg, seed=3)

  # Processor file set: image preprocessor + tokenizer + processor config.
  CLIPImageProcessor(size={"shortest_edge": 28}, crop_size={"height": 28, "width": 28},
                     do_center_crop=True, do_resize=True).save_pretrained(d)
  _write_tokenizer(d)
  # "<image>" must tokenize to ONE token (the merge expands it into patch
  # features): register it as a special token with id IMAGE_TOKEN_ID.
  from tokenizers import Tokenizer
  tok = Tokenizer.from_file(str(d / "tokenizer.json"))
  tok.add_special_tokens(["<image>"])
  # rewrite the vocab entry so the special token lands on IMAGE_TOKEN_ID
  tcfg = json.loads((d / "tokenizer_config.json").read_text())
  tok_json = json.loads(tok.to_str())
  for added in tok_json.get("added_tokens", []):
    if added["content"] == "<image>":
      added["id"] = IMAGE_TOKEN_ID
  # drop the vocab word that occupied the id, then bind <image> to it
  vocab = tok_json["model"]["vocab"]
  for k, v in list(vocab.items()):
    if v == IMAGE_TOKEN_ID:
      del vocab[k]
  vocab["<image>"] = IMAGE_TOKEN_ID
  (d / "tokenizer.json").write_text(json.dumps(tok_json))
  tcfg["processor_class"] = "LlavaProcessor"
  (d / "tokenizer_config.json").write_text(json.dumps(tcfg))
  (d / "processor_config.json").write_text(json.dumps({
    "processor_class": "LlavaProcessor", "image_token": "<image>",
    "patch_size": 14, "vision_feature_select_strategy": "default",
  }))


def _png_data_uri() -> str:
  import base64
  import io
  from PIL import Image
  img = Image.new("RGB", (28, 28), (120, 30, 200))
  buf = io.BytesIO()
  img.save(buf, format="PNG")
  return "data:image/png;base64," + base64.b64encode(buf.getvalue()).decode()


@pytest.mark.asyncio
async def test_llava_processor_resolution_offline(tmp_path, monkeypatch):
  """AutoProcessor loads from the seeded dir with zero network, gets the
  plain-tokenizer surface patched on (parity: reference tokenizers.py:26-63),
  and '<image>' tokenizes to the single configured image token id."""
  target = tmp_path / "models" / LLAVA_DIRNAME
  target.mkdir(parents=True)
  _make_llava_checkpoint(target)
  monkeypatch.setenv("XOT_HOME", str(tmp_path))

  from xotorch_tpu.inference.tokenizers import resolve_tokenizer
  proc = await resolve_tokenizer("llava-hf/llava-1.5-7b-hf")
  assert hasattr(proc, "image_processor"), "expected an AutoProcessor, not a bare tokenizer"
  assert proc.eos_token_id == 2
  ids = proc.encode("hello <image> world")
  assert list(ids).count(IMAGE_TOKEN_ID) == 1, ids


def test_xot_serves_image_chat_from_seeded_llava(tmp_path):
  """Full vision serving drill: seeded real-layout llava repo ->
  ensure_shard offline -> AutoProcessor chat template with an <image>
  placeholder -> patch-feature merge -> generation, through the HTTP API."""
  import threading
  import time as _time

  seed = tmp_path / "seed" / LLAVA_DIRNAME
  seed.mkdir(parents=True)
  _make_llava_checkpoint(seed)

  home = tmp_path / "xot_home"
  env = node_env(XOT_HOME=home)
  proc = subprocess.Popen(
    [sys.executable, "-m", "xotorch_tpu.main",
     "--default-model", LLAVA_MODEL_ID,
     "--models-seed-dir", str(tmp_path / "seed"),
     "--disable-tui", "--inference-engine", "jax",
     "--listen-port", "52482", "--broadcast-port", "52483",
     "--node-port", "52492", "--chatgpt-api-port", "52472"],
    env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=str(REPO),
  )
  tail = []
  t = threading.Thread(target=lambda: [tail.append(ln) for ln in proc.stdout], daemon=True)
  t.start()
  try:
    import json as j
    import urllib.request
    deadline = _time.monotonic() + 120
    while _time.monotonic() < deadline:
      if proc.poll() is not None:  # crash fast, don't burn the window
        raise AssertionError(
          f"server exited rc={proc.returncode} during startup:\n" + "".join(tail[-40:]))
      try:
        with urllib.request.urlopen("http://127.0.0.1:52472/healthcheck", timeout=2):
          break
      except Exception:
        _time.sleep(1)
    else:
      raise AssertionError("server never healthy:\n" + "".join(tail[-40:]))

    def content_for(messages):
      body = j.dumps({"model": LLAVA_MODEL_ID, "messages": messages,
                      "max_tokens": 6, "temperature": 0}).encode()
      req = urllib.request.Request("http://127.0.0.1:52472/v1/chat/completions",
                                   data=body, headers={"Content-Type": "application/json"})
      with urllib.request.urlopen(req, timeout=300) as r:
        out = j.loads(r.read())
      content = out["choices"][0]["message"]["content"]
      assert isinstance(content, str) and len(content) > 0, out
      return content

    with_image = content_for([{"role": "user", "content": [
      {"type": "text", "text": "what is this"},
      {"type": "image_url", "image_url": {"url": _png_data_uri()}},
    ]}])
    # Same TOKEN sequence without pixels: a literal "<image>" in the text
    # tokenizes to the same placeholder id, but no image rides the request,
    # so the engine takes the text path. The drill tokenizer decodes ids to
    # DISTINCT words, so if the serving stack silently dropped the pixels
    # both greedy streams would decode to the same string; the
    # patch-feature merge must change the output.
    text_only = content_for([{"role": "user",
                              "content": "what is this\n<image>"}])
    assert with_image != text_only, (
      f"vision path had no effect on the output: {with_image!r}")
  finally:
    proc.terminate()
    try:
      proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
      proc.kill()
