"""The `xot` CLI: construct the object graph and run a peer.

Parity: /root/reference/xotorch/main.py:73-402 — subcommands run|eval|train,
discovery module selection (udp|manual), node/API wiring, event plumbing
(preemptive shard load on remote prompt-start, throttled download-progress
broadcast), signal handling, one-shot run/train/eval flows.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys
import time
import uuid
from functools import partial
from pathlib import Path

from xotorch_tpu import VERSION
from xotorch_tpu.api.chatgpt_api import ChatGPTAPI
from xotorch_tpu.inference.engine import get_inference_engine, inference_engine_classes
from xotorch_tpu.inference.shard import Shard
from xotorch_tpu.inference.tokenizers import resolve_tokenizer
from xotorch_tpu.models.registry import build_base_shard, get_repo, model_cards
from xotorch_tpu.networking.grpc.peer_handle import GRPCPeerHandle
from xotorch_tpu.networking.grpc.server import GRPCServer
from xotorch_tpu.orchestration.node import Node
from xotorch_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
from xotorch_tpu.utils.helpers import (
  DEBUG,
  find_available_port,
  get_all_ip_addresses_and_interfaces,
  get_or_create_node_id,
  shutdown,
  spawn_detached,
)


def build_parser() -> argparse.ArgumentParser:
  parser = argparse.ArgumentParser(prog="xot", description="xotorch_tpu: TPU-native distributed LLM runtime")
  parser.add_argument("command", nargs="?", choices=["run", "eval", "train"], help="one-shot command")
  parser.add_argument("model_name", nargs="?", help="model id (see models registry)")
  parser.add_argument("--version", action="version", version=f"xot {VERSION}")
  parser.add_argument("--node-id", type=str, default=None)
  parser.add_argument("--node-host", type=str, default="0.0.0.0")
  parser.add_argument("--node-port", type=int, default=None)
  parser.add_argument("--listen-port", type=int, default=5678, help="UDP discovery listen port")
  parser.add_argument("--broadcast-port", type=int, default=5678)
  parser.add_argument("--discovery-module", type=str, choices=["udp", "manual"], default="udp")
  parser.add_argument("--discovery-timeout", type=int, default=30)
  parser.add_argument("--discovery-config-path", type=str, default=None)
  parser.add_argument("--wait-for-peers", type=int, default=0)
  parser.add_argument("--inference-engine", type=str, default="jax", help="jax | dummy")
  parser.add_argument("--chatgpt-api-port", type=int, default=52415)
  parser.add_argument("--chatgpt-api-response-timeout", type=int, default=90)
  parser.add_argument("--max-generate-tokens", type=int, default=1024)
  parser.add_argument("--default-temp", type=float, default=0.6)
  parser.add_argument("--default-top-k", type=int, default=35)
  parser.add_argument("--system-prompt", type=str, default=None)
  parser.add_argument("--default-model", type=str, default=None)
  parser.add_argument("--disable-tui", action="store_true")
  parser.add_argument("--chat-tui", action="store_true",
                      help="terminal chat mode with live tok/s (parity ref main.py:100,380-381)")
  parser.add_argument("--prompt", type=str, default="Who are you?")
  parser.add_argument("--run-gc", action="store_true", help="run garbage collection after each request")
  parser.add_argument("--models-seed-dir", type=str, default=None)
  # train flags (parity main.py:78-82)
  parser.add_argument("--data", type=str, default="xotorch_tpu/train/data/lora")
  parser.add_argument("--iters", type=int, default=100)
  parser.add_argument("--batch-size", type=int, default=1)
  parser.add_argument("--sequence-length", type=int, default=512)
  parser.add_argument("--save-every", type=int, default=5)
  parser.add_argument("--save-checkpoint-dir", type=str, default="checkpoints")
  parser.add_argument("--resume-checkpoint", type=str, default=None)
  parser.add_argument("--lora-rank", type=int, default=0,
                      help="attach rank-r LoRA adapters; train updates only them (<1%% of params)")
  parser.add_argument("--quantize", type=str, default=None, choices=["int8", "int4"],
                      help="weight-only quantization: int8 halves HBM bytes/token (~2x decode); "
                           "int4 quarters them (group-wise, embeddings/experts stay int8)")
  parser.add_argument("--kv-quantize", type=str, default=None, choices=["int8"],
                      help="int8 KV cache: half the cache bandwidth + HBM per resident token "
                           "(long-context serving)")
  parser.add_argument("--serve-tp", type=int, default=None,
                      help="tensor-parallel width over this peer's local chips "
                           "(default: all local chips on real TPU; 0/1 disables)")
  parser.add_argument("--serve-sp", type=int, default=None,
                      help="sequence-parallel width for long-prompt prefill: the from-zero "
                           "segment ring-attends over this many local chips (composes with "
                           "--serve-tp; power of two)")
  parser.add_argument("--serve-ep", type=int, default=None,
                      help="expert-parallel width for MoE models: expert weights distribute "
                           "over this many local chips' HBM, each computing its resident "
                           "experts (composes with --serve-tp; must divide the expert count)")
  parser.add_argument("--draft-model", type=str, default=None,
                      help="model id to greedy-draft speculative tokens with (must share the "
                           "target's tokenizer, e.g. llama-3.2-1b for llama-3.1-70b); the "
                           "target verifies each draft in one forward. Implies speculation "
                           "on (depth XOT_SPECULATE, default 8)")
  parser.add_argument("--adapters", type=str, default=None,
                      help="multi-LoRA serving registry: 'name=/path/to/adapter,name2=/dir'. "
                           "Requests select an adapter via the model id 'base@name'; all "
                           "adapters share one resident base (adapter-only checkpoints from "
                           "--lora-rank training)")
  return parser


def build_node(args) -> tuple:
  node_id = args.node_id or get_or_create_node_id()
  node_port = args.node_port or find_available_port()
  if getattr(args, "lora_rank", 0):
    # The engine reads this at shard-load time (every peer must agree, so the
    # train CLI's value rides the env into locally spawned engines; remote
    # peers set their own flag).
    os.environ["XOT_LORA_RANK"] = str(args.lora_rank)
  if getattr(args, "quantize", None):
    os.environ["XOT_QUANTIZE"] = args.quantize
  if getattr(args, "kv_quantize", None):
    os.environ["XOT_KV_QUANT"] = args.kv_quantize
  if getattr(args, "draft_model", None):
    os.environ["XOT_DRAFT_MODEL"] = args.draft_model
  if getattr(args, "adapters", None):
    os.environ["XOT_ADAPTERS"] = args.adapters
  if getattr(args, "serve_tp", None) is not None:
    os.environ["XOT_SERVE_TP"] = str(args.serve_tp)
  if getattr(args, "serve_sp", None) is not None:
    os.environ["XOT_SERVE_SP"] = str(args.serve_sp)
  if getattr(args, "serve_ep", None) is not None:
    os.environ["XOT_SERVE_EP"] = str(args.serve_ep)

  # Multi-host slice seam (SURVEY §2.9 north-star: no gRPC intra-slice):
  # when the launcher provides slice membership (XOT_COORDINATOR/XOT_MULTIHOST),
  # the co-hosted processes join one JAX distributed runtime BEFORE any
  # device use, so every serving/training mesh spans the whole slice and its
  # collectives ride ICI. The gRPC ring then connects only slice leaders.
  from xotorch_tpu.parallel.multihost import init_multihost, multihost_requested
  if multihost_requested():
    n_proc, rank = init_multihost()
    print(f"multi-host slice: process {rank}/{n_proc}")

  from xotorch_tpu.download import NoopShardDownloader
  from xotorch_tpu.download.hf_shard_download import HFShardDownloader

  engine_name = args.inference_engine
  if engine_name == "dummy":
    downloader = NoopShardDownloader()
    # A dummy peer has no use for accelerator capabilities; skip the JAX
    # probe (backend init takes seconds) so CLI dry runs start instantly.
    os.environ.setdefault("XOT_SKIP_JAX_PROBE", "1")
  else:
    downloader = HFShardDownloader()
  engine = get_inference_engine(engine_name, downloader)
  engine_classname = type(engine).__name__

  def create_peer_handle(peer_id, addr, desc, caps):
    return GRPCPeerHandle(peer_id, addr, desc, caps)

  if args.discovery_module == "udp":
    from xotorch_tpu.networking.udp.discovery import UDPDiscovery
    discovery = UDPDiscovery(
      node_id, node_port, args.listen_port, args.broadcast_port,
      create_peer_handle, discovery_timeout=args.discovery_timeout,
    )
  else:
    from xotorch_tpu.networking.manual.discovery import ManualDiscovery
    if not args.discovery_config_path:
      raise SystemExit("--discovery-config-path is required with --discovery-module manual")
    discovery = ManualDiscovery(args.discovery_config_path, node_id, create_peer_handle)

  # The chat TUI owns the terminal — never run the Live topology layout under
  # it (same exclusion as the reference, main.py:158).
  topology_viz = None
  if not args.disable_tui and not args.chat_tui:
    from xotorch_tpu.viz.topology_viz import TopologyViz
    api_endpoints = [f"http://{ip}:{args.chatgpt_api_port}/v1/chat/completions"
                     for ip, _ in get_all_ip_addresses_and_interfaces()][:2]
    web_urls = [f"http://{ip}:{args.chatgpt_api_port}" for ip, _ in get_all_ip_addresses_and_interfaces()][:2]
    topology_viz = TopologyViz(chatgpt_api_endpoints=api_endpoints, web_chat_urls=web_urls)

  node = Node(
    node_id, None, engine, discovery, downloader,
    RingMemoryWeightedPartitioningStrategy(),
    max_generate_tokens=args.max_generate_tokens,
    default_sample_temp=args.default_temp,
    default_sample_top_k=args.default_top_k,
    topology_viz=topology_viz,
  )
  node.server = GRPCServer(node, args.node_host, node_port)

  api = ChatGPTAPI(
    node, engine_classname,
    response_timeout=args.chatgpt_api_response_timeout,
    default_model=args.default_model,
    system_prompt=args.system_prompt,
  )
  if topology_viz is not None:
    api.on_chat_completion_request = lambda req_id, _req, prompt: topology_viz.update_prompt(req_id, prompt)

  _wire_events(node, engine, engine_classname, topology_viz, downloader)
  return node, engine, engine_classname, api, topology_viz


def _wire_events(node: Node, engine, engine_classname: str, topology_viz, downloader) -> None:
  """Event plumbing (parity main.py:180-224)."""
  # Preemptive shard load: when a remote peer starts a prompt, every peer
  # warms its own layer range immediately (parity main.py:201-212).
  def on_opaque_status(request_id: str, status: str) -> None:
    try:
      data = json.loads(status)
      if data.get("type") == "node_status" and data.get("status") == "start_process_prompt":
        base_shard = Shard.from_dict(data.get("base_shard", {}))
        if data.get("node_id") != node.id:
          current = node.get_current_shard(base_shard)
          node._spawn(engine.ensure_shard(current))
    except Exception as e:
      if DEBUG >= 2:
        print(f"preemptive load error: {e!r}")

  node.on_opaque_status.register("main-preemptive-load").on_next(on_opaque_status)

  # Throttled download-progress broadcast at <= 5 Hz (parity main.py:214-224).
  last_broadcast = {"t": 0.0}

  def on_progress(shard, event):
    now = time.monotonic()
    if now - last_broadcast["t"] < 0.2 and not getattr(event, "is_complete", False):
      return
    last_broadcast["t"] = now
    payload = event.to_dict() if hasattr(event, "to_dict") else dict(event)
    node._spawn(node.broadcast_opaque_status("", json.dumps({
      "type": "download_progress", "node_id": node.id, "progress": payload,
    })))

  if downloader is not None:
    downloader.on_progress.register("main-progress").on_next(on_progress)


async def _resolve_cli_tokenizer(model_name: str, engine_classname: str):
  """Tokenizer for the one-shot CLI flows (synthetic/dummy cards never touch
  the network)."""
  if model_name.startswith("synthetic") or model_name == "dummy":
    from xotorch_tpu.inference.tokenizers import DummyTokenizer
    return DummyTokenizer()
  return await resolve_tokenizer(get_repo(model_name, engine_classname))


async def run_model_cli(node: Node, engine_classname: str, model_name: str, prompt: str) -> None:
  """One-shot generate (parity main.py:226-256)."""
  shard = build_base_shard(model_name, engine_classname)
  if shard is None:
    print(f"Error: unsupported model '{model_name}' for engine {engine_classname}")
    return
  tokenizer = await _resolve_cli_tokenizer(model_name, engine_classname)
  if model_name.startswith("synthetic") or model_name == "dummy":
    final_prompt = prompt
  else:
    final_prompt = tokenizer.apply_chat_template(
      [{"role": "user", "content": prompt}], tokenize=False, add_generation_prompt=True
    )
  request_id = str(uuid.uuid4())
  done = asyncio.Event()
  out = {}

  def on_token(req_id, tokens, is_finished):
    if req_id != request_id:
      return
    out["tokens"] = list(tokens)
    if is_finished:
      done.set()

  node.on_token.register("cli-wait-response").on_next(on_token)
  started = time.monotonic()
  await node.process_prompt(shard, final_prompt, request_id)
  try:
    await asyncio.wait_for(done.wait(), timeout=300)
  except asyncio.TimeoutError:
    print("Generation timed out")
    return
  elapsed = time.monotonic() - started
  tokens = out.get("tokens", [])
  eos = getattr(tokenizer, "eos_token_id", None)
  text = tokenizer.decode([t for t in tokens if t != eos])
  print(text)
  print(f"\n[{len(tokens)} tokens in {elapsed:.1f}s = {len(tokens)/max(elapsed,1e-9):.1f} tok/s]", file=sys.stderr)


async def train_model_cli(node: Node, engine_classname: str, model_name: str, args) -> None:
  """Distributed train loop (parity main.py:272-315) — engine leaves exist
  here, unlike the reference."""
  from xotorch_tpu.train.dataset import iterate_batches, load_dataset
  shard = build_base_shard(model_name, engine_classname)
  if shard is None:
    print(f"Error: unsupported model '{model_name}'")
    return
  train_set, valid_set, test_set = load_dataset(args.data)
  tokenizer = await _resolve_cli_tokenizer(model_name, engine_classname)
  if args.resume_checkpoint:
    # Ring-wide: every peer loads its own layer range from the checkpoint
    # directory before the first step (the flag was parsed-but-dead in round
    # 1 — VERDICT weak #5; the reference's engine load_checkpoint was a
    # no-op, inference_engine.py:31-35).
    await node.coordinate_resume(shard, args.resume_checkpoint)
  losses = []
  for it, batch in enumerate(iterate_batches(train_set, tokenizer, args.batch_size, args.sequence_length)):
    if it >= args.iters:
      break
    inputs, targets, lengths = batch
    loss, _ = await node.enqueue_example(shard, inputs, targets, lengths, train=True)
    losses.append(loss)
    print(f"iter {it}: loss={loss:.4f}")
    if args.save_every > 0 and (it + 1) % args.save_every == 0:
      await node.coordinate_save(shard, it + 1, args.save_checkpoint_dir)


async def eval_model_cli(node: Node, engine_classname: str, model_name: str, args) -> None:
  from xotorch_tpu.train.dataset import iterate_batches, load_dataset
  shard = build_base_shard(model_name, engine_classname)
  _, _, test_set = load_dataset(args.data)
  tokenizer = await _resolve_cli_tokenizer(model_name, engine_classname)
  losses = []
  for batch in iterate_batches(test_set, tokenizer, args.batch_size, args.sequence_length):
    inputs, targets, lengths = batch
    loss, _ = await node.enqueue_example(shard, inputs, targets, lengths, train=False)
    losses.append(loss)
  if losses:
    print(f"eval loss: {sum(losses)/len(losses):.4f} over {len(losses)} batches")


async def async_main(args) -> None:
  if args.models_seed_dir:
    # Move pre-seeded checkpoint dirs into XOT_HOME before anything resolves
    # models, so ensure_shard's local-complete fast path and tokenizer
    # resolution find them (parity reference main.py:251-255).
    from xotorch_tpu.download.hf_shard_download import seed_models
    await seed_models(args.models_seed_dir)
  node, engine, engine_classname, api, topology_viz = build_node(args)
  loop = asyncio.get_running_loop()
  def _on_exit_signal(s):
    # Post-mortem spool BEFORE teardown churns state: with
    # XOT_FLIGHT_DUMP_DIR set, the flight ring + frozen snapshots land on
    # disk so a terminated node's evidence survives the process (the soak
    # orchestrator collects these instead of relying on last-good scrapes).
    try:
      node.spool_flight(reason=f"signal:{getattr(s, 'name', s)}")
    except Exception as e:
      if DEBUG >= 1:
        print(f"flight spool on {s} failed: {e!r}")
    spawn_detached(shutdown(s, loop, node.server))

  for sig in (signal.SIGINT, signal.SIGTERM):
    try:
      loop.add_signal_handler(sig, lambda s=sig: _on_exit_signal(s))
    except NotImplementedError:
      pass

  await node.start(wait_for_peers=args.wait_for_peers)
  if topology_viz is not None:
    topology_viz.start()

  if args.chat_tui:
    from xotorch_tpu.viz.chat_tui import run_chat_tui
    model = args.model_name or args.default_model or "llama-3.2-1b"
    tokenizer = await _resolve_cli_tokenizer(model, engine_classname)
    await run_chat_tui(node, engine_classname, model, tokenizer)
    await node.stop()
    return

  if args.command == "run":
    model = args.model_name or args.default_model or "llama-3.2-1b"
    await run_model_cli(node, engine_classname, model, args.prompt)
    await node.stop()
    return
  if args.command == "train":
    model = args.model_name or "synthetic-tiny"
    await train_model_cli(node, engine_classname, model, args)
    await node.stop()
    return
  if args.command == "eval":
    model = args.model_name or "synthetic-tiny"
    await eval_model_cli(node, engine_classname, model, args)
    await node.stop()
    return

  runner = await api.run(port=args.chatgpt_api_port)
  try:
    await asyncio.Event().wait()
  finally:
    await runner.cleanup()
    await node.stop()


def run() -> None:
  args = build_parser().parse_args()
  try:
    asyncio.run(async_main(args))
  except KeyboardInterrupt:
    pass


if __name__ == "__main__":
  run()
