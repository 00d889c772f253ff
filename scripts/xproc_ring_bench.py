"""Cross-process gRPC ring timing on localhost.

Times the per-token wire cost of the ring with the device out of the picture
(CPU-pinned nodes; the numbers are host/loopback timings, never device
metrics): two `xot` processes on
localhost, UDP discovery, per-token ring decode over actual gRPC + XOT1
codec framing, vs the same build serving solo.

With a tiny model the compute term is negligible, so

    wire_ms_per_token ≈ 1000/ring_tok_s − 1000/solo_tok_s

is the per-token cost of one full ring lap (2 gRPC hops + codec + the
node decode loop) — the number a real 2-host deployment adds on top of
per-partition compute when partitions are NOT co-located (co-located rings
take the fused in-process path instead, see models/generate.decode_chunk_ring).

Writes XPROC_RING_r05.json. Usage: python scripts/xproc_ring_bench.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
from tests.xproc_harness import http_get, spawn_node, wait_for  # noqa: E402
API_A, API_B = 52474, 52475
UDP_A, UDP_B = 52484, 52485
GRPC_A, GRPC_B = 52494, 52495
MODEL = "synthetic-tiny"
DECODE_TOKENS = int(os.getenv("XPROC_DECODE", "64"))


def _spawn(node_id, api, listen, bcast, grpc, logfile):
  # Per-token ring is the DELIBERATE subject: disable chunked decode so
  # every token pays the wire (the co-located fused path would hide it).
  return spawn_node(node_id, api, listen, bcast, grpc, logfile,
                    model=MODEL, discovery_timeout=8, response_timeout=600,
                    extra_env={"XOT_DECODE_CHUNK": "1"})


def _get(port, path, timeout=5.0):
  return http_get(port, path, timeout)


def _wait(predicate, deadline_s, what, log_path=None, proc=None):
  wait_for(predicate, deadline_s, what, log_path=log_path, proc=proc)


def _decode_tok_s(port, n_tokens) -> float:
  body = json.dumps({
    "model": MODEL, "messages": [{"role": "user", "content": "wire timing"}],
    "max_tokens": n_tokens, "temperature": 0,
  }).encode()
  req = urllib.request.Request(f"http://127.0.0.1:{port}/v1/chat/completions",
                               data=body, headers={"Content-Type": "application/json"})
  # Warmup (compile both partitions), then measure.
  with urllib.request.urlopen(req, timeout=600) as r:
    json.loads(r.read())
  t0 = time.monotonic()
  with urllib.request.urlopen(req, timeout=600) as r:
    out = json.loads(r.read())
  dt = time.monotonic() - t0
  usage = out.get("usage", {})
  n = usage.get("completion_tokens") or n_tokens
  return n / dt


def main() -> None:
  logs = {}
  procs = []
  result = {"model": MODEL, "decode_tokens": DECODE_TOKENS, "platform": "cpu",
            "network": "localhost loopback"}
  try:
    logs["a"] = open("/tmp/xpb_a.log", "w")
    a = _spawn("xpb-a", API_A, UDP_A, UDP_B, GRPC_A, logs["a"])
    procs.append(a)
    _wait(lambda: _get(API_A, "/healthcheck").get("status") == "ok", 90, "A health",
          log_path="/tmp/xpb_a.log", proc=a)
    _wait(lambda: len(_get(API_A, "/v1/topology")["nodes"]) == 1, 30, "A solo topo")
    solo = _decode_tok_s(API_A, DECODE_TOKENS)
    result["solo_tok_s"] = round(solo, 2)
    print(f"solo (1 process, per-token): {solo:.1f} tok/s", flush=True)

    logs["b"] = open("/tmp/xpb_b.log", "w")
    b = _spawn("xpb-b", API_B, UDP_B, UDP_A, GRPC_B, logs["b"])
    procs.append(b)
    _wait(lambda: _get(API_B, "/healthcheck").get("status") == "ok", 90, "B health",
          log_path="/tmp/xpb_b.log", proc=b)
    _wait(lambda: len(_get(API_A, "/v1/topology")["nodes"]) == 2
          and len(_get(API_B, "/v1/topology")["nodes"]) == 2, 60, "2-node ring",
          log_path="/tmp/xpb_b.log", proc=b)
    ring = _decode_tok_s(API_A, DECODE_TOKENS)
    result["ring2_xproc_tok_s"] = round(ring, 2)
    wire_ms = 1000.0 / ring - 1000.0 / solo
    result["ring_lap_overhead_ms_per_token"] = round(wire_ms, 2)
    print(f"2-process gRPC ring (per-token): {ring:.1f} tok/s", flush=True)
    print(f"ring lap overhead: {wire_ms:.2f} ms/token (2 hops + codec + loop)", flush=True)
  finally:
    for p in procs:
      p.terminate()
    for p in procs:
      try:
        p.wait(timeout=10)
      except subprocess.TimeoutExpired:
        p.kill()
    for f in logs.values():
      f.close()
  out = REPO / "XPROC_RING_r05.json"
  out.write_text(json.dumps(result, indent=2))
  print(json.dumps(result))


if __name__ == "__main__":
  main()
