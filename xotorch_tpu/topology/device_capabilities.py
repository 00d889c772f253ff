"""Device capability probing — TPU-first.

Parity: /root/reference/xotorch/topology/device_capabilities.py:22-164, which
carries a static TFLOPS table for ~80 GPU/Apple chips and probes via
system_profiler/pynvml. This build inverts the priority: the primary probe is
the JAX runtime (`jax.devices()`) reporting TPU generation, per-chip HBM and
ICI coordinates; CUDA-through-torch and psutil CPU probes are the fallbacks so
mixed TPU+CPU dev rings still partition sensibly (SURVEY §7.4.7).

Memory is reported in MB of *accelerator* memory (HBM on TPU) because the ring
partitioning strategy weights by it — the TPU analogue of the reference's
RAM weighting.
"""
from __future__ import annotations

import asyncio
import os
import re
from dataclasses import dataclass, field, asdict
from typing import Any, Dict, List, Optional

from xotorch_tpu.utils import knobs
from xotorch_tpu.utils.helpers import DEBUG

TFLOPS = 1.00


@dataclass(frozen=True)
class DeviceFlops:
  # units of TFLOPS
  fp32: float
  fp16: float  # bf16 on TPU
  int8: float

  def to_dict(self) -> Dict[str, float]:
    return asdict(self)


@dataclass
class DeviceCapabilities:
  model: str
  chip: str
  memory: int  # MB of accelerator (HBM) or host memory
  flops: DeviceFlops
  num_devices: int = 1
  ici_topology: Optional[List[int]] = None  # e.g. [2, 2] mesh shape within slice

  def __str__(self) -> str:
    return (
      f"Model: {self.model}. Chip: {self.chip}. Memory: {self.memory}MB. "
      f"Flops: fp32 {self.flops.fp32:.2f} TFLOPS, fp16/bf16 {self.flops.fp16:.2f} TFLOPS, int8 {self.flops.int8:.2f} TFLOPS"
    )

  def model_dump(self) -> Dict[str, Any]:
    d = asdict(self)
    d["flops"] = self.flops.to_dict()
    return d

  def to_dict(self) -> Dict[str, Any]:
    return self.model_dump()

  @classmethod
  def from_dict(cls, data: Dict[str, Any]) -> "DeviceCapabilities":
    flops = data.get("flops", {})
    return cls(
      model=data.get("model", "Unknown Model"),
      chip=data.get("chip", "Unknown Chip"),
      memory=int(data.get("memory", 0)),
      flops=DeviceFlops(
        fp32=float(flops.get("fp32", 0)), fp16=float(flops.get("fp16", 0)), int8=float(flops.get("int8", 0))
      ),
      num_devices=int(data.get("num_devices", 1)),
      ici_topology=data.get("ici_topology"),
    )


UNKNOWN_DEVICE_CAPABILITIES = DeviceCapabilities(
  model="Unknown Model", chip="Unknown Chip", memory=0, flops=DeviceFlops(fp32=0, fp16=0, int8=0)
)

# Public PER-DEVICE peak numbers (bf16 dense TFLOP/s, HBM GB, HBM GB/s),
# keyed by the `device_kind` string the installed runtime (jax 0.9.0 /
# libtpu 0.0.34) reports for that generation — read off
# `jax.experimental.topologies.get_topology_desc` for each. "device" is what
# jax reports: a CORE on v2/v3 (two devices per chip), a CHIP on v4+
# (megacore). All three columns use the same denominator so MFU and HBM-BW%
# are mutually consistent. fp32 on TPU ≈ bf16/2 via the MXU's
# fp32-accumulate path; int8 2× bf16 where supported. hbm_gbps feeds the
# bandwidth-utilisation metric: batch-1 decode is HBM-bound, so BW% is the
# honest "how close to roofline" number (MFU alone undersells decode).
# Source: Google Cloud TPU documentation, per-generation system
# architecture pages ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s —
# the on-chip-measurement guide §4 quotes the same). This is the ONE peak
# table: bench.py and the engine's perf attribution read it through
# `tpu_chip_peaks`, and a device_kind that is not in it is an error, never a
# default — a wrong denominator silently mis-states every utilisation.
TPU_CHIP_SPECS: Dict[str, Dict[str, Any]] = {
  "TPU v2": {"name": "v2", "bf16": 22.5, "hbm_gb": 8, "hbm_gbps": 350.0},  # per core (half chip)
  "TPU v3": {"name": "v3", "bf16": 61.5, "hbm_gb": 16, "hbm_gbps": 450.0},  # per core (half chip)
  "TPU v4": {"name": "v4", "bf16": 275.0, "hbm_gb": 32, "hbm_gbps": 1228.0},  # per chip (megacore)
  "TPU v5 lite": {"name": "v5e", "bf16": 197.0, "hbm_gb": 16, "hbm_gbps": 819.0},
  "TPU v5": {"name": "v5p", "bf16": 459.0, "hbm_gb": 95.0, "hbm_gbps": 2765.0},
  "TPU v6 lite": {"name": "v6e", "bf16": 918.0, "hbm_gb": 32, "hbm_gbps": 1638.0},
}

# Heterogeneous static TFLOPS table (VERDICT r3 #10): a TPU framework still
# meets mixed dev rings (a Mac laptop + a CUDA workstation + a TPU VM in one
# UDP discovery domain), and the RAM/HBM-weighted partitioner needs non-zero
# planning numbers for the non-TPU peers. Values are from public vendor
# specs (dense, no sparsity); fp16 means the chip's preferred half-precision
# (bf16 where native). This is the same ROLE as the reference's ~80-chip
# CHIP_FLOPS table (device_capabilities.py:54-164), rebuilt from public data
# rather than ported. Matching is case-insensitive substring both ways
# (lookup_chip_flops), so "NVIDIA GeForce RTX 4090" hits "RTX 4090".
GPU_CHIP_FLOPS: Dict[str, DeviceFlops] = {
  # datacenter
  "NVIDIA B200": DeviceFlops(fp32=80.0 * TFLOPS, fp16=2250.0 * TFLOPS, int8=4500.0 * TFLOPS),
  "NVIDIA H200": DeviceFlops(fp32=67.0 * TFLOPS, fp16=989.0 * TFLOPS, int8=1979.0 * TFLOPS),
  "NVIDIA H100": DeviceFlops(fp32=67.0 * TFLOPS, fp16=989.0 * TFLOPS, int8=1979.0 * TFLOPS),
  "NVIDIA A100": DeviceFlops(fp32=19.5 * TFLOPS, fp16=312.0 * TFLOPS, int8=624.0 * TFLOPS),
  "NVIDIA A10": DeviceFlops(fp32=31.2 * TFLOPS, fp16=125.0 * TFLOPS, int8=250.0 * TFLOPS),
  "NVIDIA L40S": DeviceFlops(fp32=91.6 * TFLOPS, fp16=366.0 * TFLOPS, int8=733.0 * TFLOPS),
  "NVIDIA L4": DeviceFlops(fp32=30.3 * TFLOPS, fp16=121.0 * TFLOPS, int8=242.0 * TFLOPS),
  "NVIDIA V100": DeviceFlops(fp32=15.7 * TFLOPS, fp16=125.0 * TFLOPS, int8=62.8 * TFLOPS),
  "NVIDIA T4": DeviceFlops(fp32=8.1 * TFLOPS, fp16=65.0 * TFLOPS, int8=130.0 * TFLOPS),
  "NVIDIA P100": DeviceFlops(fp32=9.3 * TFLOPS, fp16=18.7 * TFLOPS, int8=9.3 * TFLOPS),
  "RTX A6000": DeviceFlops(fp32=38.7 * TFLOPS, fp16=155.0 * TFLOPS, int8=310.0 * TFLOPS),
  # consumer
  "RTX 5090": DeviceFlops(fp32=104.8 * TFLOPS, fp16=209.6 * TFLOPS, int8=838.0 * TFLOPS),
  "RTX 4090": DeviceFlops(fp32=82.6 * TFLOPS, fp16=165.2 * TFLOPS, int8=660.6 * TFLOPS),
  "RTX 4080": DeviceFlops(fp32=48.7 * TFLOPS, fp16=97.5 * TFLOPS, int8=390.0 * TFLOPS),
  "RTX 4070": DeviceFlops(fp32=29.2 * TFLOPS, fp16=58.3 * TFLOPS, int8=233.0 * TFLOPS),
  "RTX 3090": DeviceFlops(fp32=35.6 * TFLOPS, fp16=71.2 * TFLOPS, int8=284.0 * TFLOPS),
  "RTX 3080": DeviceFlops(fp32=29.8 * TFLOPS, fp16=59.5 * TFLOPS, int8=238.0 * TFLOPS),
  "RTX 3070": DeviceFlops(fp32=20.3 * TFLOPS, fp16=40.6 * TFLOPS, int8=162.6 * TFLOPS),
  "RTX 3060": DeviceFlops(fp32=12.7 * TFLOPS, fp16=25.5 * TFLOPS, int8=102.0 * TFLOPS),
  "GTX 1080": DeviceFlops(fp32=8.9 * TFLOPS, fp16=0.14 * TFLOPS, int8=35.6 * TFLOPS),
  "T1000": DeviceFlops(fp32=2.5 * TFLOPS, fp16=5.0 * TFLOPS, int8=10.0 * TFLOPS),
  "Quadro M2000": DeviceFlops(fp32=1.8 * TFLOPS, fp16=0.03 * TFLOPS, int8=1.8 * TFLOPS),
  "Quadro P400": DeviceFlops(fp32=0.6 * TFLOPS, fp16=0.01 * TFLOPS, int8=0.6 * TFLOPS),
  # AMD (drivers report "AMD Instinct MI300X" — keys are the minimal
  # distinctive substring so both torch and rocm-smi name forms hit)
  "MI300X": DeviceFlops(fp32=163.4 * TFLOPS, fp16=1307.0 * TFLOPS, int8=2614.0 * TFLOPS),
  "MI250X": DeviceFlops(fp32=47.9 * TFLOPS, fp16=383.0 * TFLOPS, int8=383.0 * TFLOPS),
  "Radeon RX 7900": DeviceFlops(fp32=61.4 * TFLOPS, fp16=122.8 * TFLOPS, int8=122.8 * TFLOPS),
  # Jetson (edge)
  "Jetson AGX Orin": DeviceFlops(fp32=5.3 * TFLOPS, fp16=10.6 * TFLOPS, int8=105.0 * TFLOPS),
  "Jetson Orin Nano": DeviceFlops(fp32=1.3 * TFLOPS, fp16=2.6 * TFLOPS, int8=20.0 * TFLOPS),
  "Jetson Xavier": DeviceFlops(fp32=1.4 * TFLOPS, fp16=2.8 * TFLOPS, int8=22.0 * TFLOPS),
}

# Apple silicon (GPU fp32; fp16 = 2x via the GPU's half-rate path; int8
# planning number 2x fp16). Unified memory means the partitioner can weight
# these peers by system RAM directly.
APPLE_CHIP_FLOPS: Dict[str, DeviceFlops] = {
  "Apple M1 Ultra": DeviceFlops(fp32=21.2 * TFLOPS, fp16=42.4 * TFLOPS, int8=84.8 * TFLOPS),
  "Apple M1 Max": DeviceFlops(fp32=10.6 * TFLOPS, fp16=21.2 * TFLOPS, int8=42.4 * TFLOPS),
  "Apple M1 Pro": DeviceFlops(fp32=5.3 * TFLOPS, fp16=10.6 * TFLOPS, int8=21.2 * TFLOPS),
  "Apple M1": DeviceFlops(fp32=2.6 * TFLOPS, fp16=5.2 * TFLOPS, int8=10.4 * TFLOPS),
  "Apple M2 Ultra": DeviceFlops(fp32=27.2 * TFLOPS, fp16=54.4 * TFLOPS, int8=108.8 * TFLOPS),
  "Apple M2 Max": DeviceFlops(fp32=13.6 * TFLOPS, fp16=27.2 * TFLOPS, int8=54.4 * TFLOPS),
  "Apple M2 Pro": DeviceFlops(fp32=6.8 * TFLOPS, fp16=13.6 * TFLOPS, int8=27.2 * TFLOPS),
  "Apple M2": DeviceFlops(fp32=3.6 * TFLOPS, fp16=7.2 * TFLOPS, int8=14.4 * TFLOPS),
  "Apple M3 Ultra": DeviceFlops(fp32=28.4 * TFLOPS, fp16=56.8 * TFLOPS, int8=113.6 * TFLOPS),
  "Apple M3 Max": DeviceFlops(fp32=14.2 * TFLOPS, fp16=28.4 * TFLOPS, int8=56.8 * TFLOPS),
  "Apple M3 Pro": DeviceFlops(fp32=7.1 * TFLOPS, fp16=14.2 * TFLOPS, int8=28.4 * TFLOPS),
  "Apple M3": DeviceFlops(fp32=4.1 * TFLOPS, fp16=8.2 * TFLOPS, int8=16.4 * TFLOPS),
  "Apple M4 Max": DeviceFlops(fp32=18.4 * TFLOPS, fp16=36.8 * TFLOPS, int8=73.6 * TFLOPS),
  "Apple M4 Pro": DeviceFlops(fp32=9.2 * TFLOPS, fp16=18.4 * TFLOPS, int8=36.8 * TFLOPS),
  "Apple M4": DeviceFlops(fp32=4.6 * TFLOPS, fp16=9.2 * TFLOPS, int8=18.4 * TFLOPS),
}


def lookup_chip_flops(name: str) -> Optional[DeviceFlops]:
  """Case-insensitive match against the GPU and Apple tables.

  Primary direction: the longest table KEY that is a substring of the
  reported name — 'NVIDIA A100-SXM4-80GB' hits 'NVIDIA A100', and a plain
  'Apple M1'/'NVIDIA A10' hits its own entry, never a longer sibling
  ('M1 Ultra', 'A100'). Only when nothing hits does the reverse direction
  run (a truncated reported name inside a longer key)."""
  if not name:
    return None
  low = name.lower()
  for contains_key in (True, False):
    best = None
    for table in (GPU_CHIP_FLOPS, APPLE_CHIP_FLOPS):
      for key, flops in table.items():
        kl = key.lower()
        hit = (kl in low) if contains_key else (low in kl)
        if hit and (best is None or len(kl) > best[0]):
          best = (len(kl), flops)
    if best is not None:
      return best[1]
  return None


class UnknownDeviceError(LookupError):
  """A TPU `device_kind` the peak table has no row for."""


def tpu_chip_spec(device_kind: str) -> Dict[str, Any]:
  """The TPU_CHIP_SPECS row for a `device_kind` string exactly as
  `jax.devices()[0].device_kind` reports it. Unknown kinds raise: add the
  row (with its source) rather than borrow another chip's peaks."""
  try:
    return TPU_CHIP_SPECS[str(device_kind)]
  except KeyError:
    raise UnknownDeviceError(
      f"TPU device_kind {device_kind!r} is not in TPU_CHIP_SPECS "
      f"({sorted(TPU_CHIP_SPECS)}) — add its published peaks to "
      "xotorch_tpu/topology/device_capabilities.py") from None


def tpu_chip_peaks(device_kind: str) -> "tuple[float, float]":
  """(peak bf16 TFLOP/s, peak HBM GB/s) for a TPU `device_kind` string —
  the roofline denominators. One lookup for bench.py and the engine's perf
  attribution; an unknown kind raises UnknownDeviceError."""
  spec = tpu_chip_spec(device_kind)
  return spec["bf16"], spec["hbm_gbps"]


def _probe_jax_sync() -> Optional[DeviceCapabilities]:
  """Probe the local JAX runtime. Returns None when JAX initialised cleanly
  with no accelerator (CPU-only — the host probe has better memory numbers).
  A backend init that RAISES propagates, and so does an unknown TPU kind: a
  node that was meant to serve from a chip must not join the ring advertising
  its host CPU instead."""
  import jax
  devices = jax.local_devices()
  d0 = devices[0]
  platform = d0.platform
  if platform == "tpu":
    spec = tpu_chip_spec(d0.device_kind)
    key = spec["name"]
    per_chip_hbm_mb = int(spec["hbm_gb"] * 1024)
    stats = d0.memory_stats()
    if stats and "bytes_limit" in stats:
      per_chip_hbm_mb = int(stats["bytes_limit"] / (1024 * 1024))
    n = len(devices)
    # Chip coordinates within the slice (a list per device on the installed
    # runtime): the extent along each axis is the local ICI mesh shape.
    coords = [tuple(d.coords) for d in devices]
    ici = [len({c[i] for c in coords}) for i in range(len(coords[0]))]
    bf16 = spec["bf16"]
    return DeviceCapabilities(
      model=f"Google TPU {key} x{n}",
      chip=f"TPU {key}",
      memory=per_chip_hbm_mb * n,
      flops=DeviceFlops(fp32=bf16 / 2 * n, fp16=bf16 * n, int8=bf16 * 2 * n),
      num_devices=n,
      ici_topology=ici,
    )
  if platform == "gpu":
    name = str(getattr(d0, "device_kind", "Unknown GPU"))
    flops = lookup_chip_flops(name) or DeviceFlops(fp32=10.0, fp16=20.0, int8=40.0)
    mem_mb = 8 * 1024
    try:
      stats = d0.memory_stats()
      if stats and "bytes_limit" in stats:
        mem_mb = int(stats["bytes_limit"] / (1024 * 1024))
    except Exception:
      pass
    n = len(devices)
    return DeviceCapabilities(
      model=f"{name} x{n}", chip=name, memory=mem_mb * n,
      flops=DeviceFlops(fp32=flops.fp32 * n, fp16=flops.fp16 * n, int8=flops.int8 * n),
      num_devices=n,
    )
  return None  # cpu platform -> use the host probe for better memory numbers


MEMINFO_PATH = "/proc/meminfo"  # module constant so tests can point elsewhere


def _jetson_total_mem_mb() -> Optional[int]:
  """Jetson boards have UNIFIED memory: the CUDA device property reports a
  carve-out, not what the model planner can actually use — /proc/meminfo
  MemTotal is the honest number (parity: reference
  device_capabilities.py:182-205 get_jetson_device_meminfo)."""
  try:
    with open(MEMINFO_PATH) as fp:
      first = fp.readline()
    m = re.search(r"\d+", first)
    return int(m.group()) // 1024 if m else None  # kB -> MB
  except OSError:
    return None


DEVICE_TREE_MODEL_PATH = "/proc/device-tree/model"


def _jetson_flops(cuda_name: str, mem_mb: int) -> DeviceFlops:
  """Resolve a Jetson board's FLOPS. CUDA reports the bare SoC name ('Orin')
  for the whole family, which spans a ~4x perf range — the device-tree
  model string names the actual board; failing that, unified-memory size
  separates AGX (32/64 GB) from Nano-class (4-8 GB) boards."""
  try:
    with open(DEVICE_TREE_MODEL_PATH) as fp:
      board = fp.read().strip("\x00 \n")
    hit = lookup_chip_flops(board)
    if hit is not None:
      return hit
  except OSError:
    pass
  hit = lookup_chip_flops(cuda_name)
  if hit is not None:
    return hit
  if "xavier" in cuda_name.lower():
    return GPU_CHIP_FLOPS["Jetson Xavier"]
  key = "Jetson AGX Orin" if mem_mb >= 24 * 1024 else "Jetson Orin Nano"
  return GPU_CHIP_FLOPS[key]


def _probe_torch_cuda_sync() -> Optional[DeviceCapabilities]:
  """torch-CUDA fallback for peers whose JAX is CPU-only but that carry a
  CUDA GPU (the reference's primary probe path, device_capabilities.py:207-328
  — here a fallback, since TPU peers probe through JAX first). Jetson
  (Orin/Xavier) devices take their memory from /proc/meminfo — unified
  memory — and resolve their FLOPS by family name."""
  try:
    import torch
    if not torch.cuda.is_available():
      return None
    n = torch.cuda.device_count()
    name = torch.cuda.get_device_name(0)
    mem_mb = torch.cuda.get_device_properties(0).total_memory // (1024 * 1024)
  except Exception:
    return None
  if any(k in name.lower() for k in ("orin", "xavier", "jetson")):
    unified = _jetson_total_mem_mb()
    if unified:
      mem_mb = unified
    flops = _jetson_flops(name, int(mem_mb))
    return DeviceCapabilities(
      model=f"Jetson ({name})", chip=name, memory=int(mem_mb),
      flops=flops, num_devices=n,
    )
  flops = lookup_chip_flops(name) or DeviceFlops(fp32=10.0, fp16=20.0, int8=40.0)
  return DeviceCapabilities(
    model=f"{name} x{n}", chip=name, memory=int(mem_mb) * n,
    flops=DeviceFlops(fp32=flops.fp32 * n, fp16=flops.fp16 * n, int8=flops.int8 * n),
    num_devices=n,
  )


def _probe_amd_sync() -> Optional[DeviceCapabilities]:
  """AMD GPU probe: pyamdgpuinfo when installed (parity: reference
  device_capabilities.py:330-348), else `rocm-smi --json`. Returns None on
  hosts without AMD tooling — the chain falls through to the host probe."""
  try:
    import pyamdgpuinfo  # optional dep, present on AMD hosts that set it up
    # detect_gpus() must run BEFORE get_gpu() — the library builds its
    # device list there (same order the reference relies on).
    n = max(int(pyamdgpuinfo.detect_gpus()), 1)
    gpu = pyamdgpuinfo.get_gpu(0)
    name = gpu.name
    mem_mb = int(gpu.memory_info["vram_size"]) // (1024 * 1024)
  except Exception:
    name = mem_mb = None
    n = 1
  if name is None:
    try:
      import json as _json
      import subprocess
      out = subprocess.run(
        ["rocm-smi", "--showproductname", "--showmeminfo", "vram", "--json"],
        capture_output=True, text=True, timeout=10)
      data = _json.loads(out.stdout)
      cards = [v for k, v in sorted(data.items()) if k.lower().startswith("card")]
      if not cards:
        return None
      c0 = cards[0]
      name = (c0.get("Card series") or c0.get("Card SKU")
              or c0.get("Card model") or "AMD GPU")
      vram = c0.get("VRAM Total Memory (B)") or c0.get("vram Total Memory (B)")
      mem_mb = int(vram) // (1024 * 1024) if vram else None
      n = len(cards)
    except Exception:
      return None
  if mem_mb is None:
    return None
  flops = lookup_chip_flops(str(name)) or DeviceFlops(fp32=10.0, fp16=20.0, int8=40.0)
  return DeviceCapabilities(
    model=f"{name} x{n}" if n > 1 else str(name), chip=str(name), memory=int(mem_mb) * n,
    flops=DeviceFlops(fp32=flops.fp32 * n, fp16=flops.fp16 * n, int8=flops.int8 * n),
    num_devices=n,
  )


def _apple_chip_name() -> Optional[str]:
  """The marketing chip name ('Apple M2 Max') on macOS, or None."""
  import platform as _platform
  if _platform.system() != "Darwin":
    return None
  try:
    import subprocess
    out = subprocess.run(["sysctl", "-n", "machdep.cpu.brand_string"],
                         capture_output=True, text=True, timeout=5).stdout.strip()
    return out or None
  except Exception:
    return None


def _probe_mac_sync(quick: bool = False) -> Optional[DeviceCapabilities]:
  """macOS probe (parity: reference device_capabilities.py:350-378
  get_mac_system_info): model identifier ('Mac15,6'), chip name and
  physical memory from `system_profiler SPHardwareDataType -json`, with the
  sysctl brand string as the fallback chip source. Returns None off macOS.

  quick=True skips the system_profiler subprocess (seconds) and resolves
  from sysctl + psutil only — the instant-start path goes through here so
  ONE implementation owns the Apple-silicon mapping."""
  import platform as _platform
  if _platform.system() != "Darwin":
    return None
  model_id, chip, mem_mb = None, None, None
  if not quick:
    try:
      import json as _json
      import subprocess
      out = subprocess.run(["system_profiler", "SPHardwareDataType", "-json"],
                           capture_output=True, text=True, timeout=15)
      hw = _json.loads(out.stdout)["SPHardwareDataType"][0]
      model_id = hw.get("machine_model")
      chip = hw.get("chip_type")  # e.g. "Apple M2 Max"
      phys = hw.get("physical_memory", "")  # e.g. "32 GB"
      m = re.search(r"(\d+)\s*GB", str(phys))
      if m:
        mem_mb = int(m.group(1)) * 1024
    except Exception:
      pass
  chip = chip or _apple_chip_name()
  if chip is None:
    return None
  if mem_mb is None:
    try:
      import psutil
      mem_mb = psutil.virtual_memory().total // (1024 * 1024)
    except Exception:
      mem_mb = 16 * 1024
  flops = lookup_chip_flops(chip) or DeviceFlops(fp32=2.0, fp16=4.0, int8=8.0)
  return DeviceCapabilities(
    model=model_id or f"Mac ({chip})", chip=chip, memory=int(mem_mb),
    flops=flops, num_devices=1,
  )


def _probe_host_sync() -> DeviceCapabilities:
  import platform as _platform
  try:
    import psutil
    mem_mb = psutil.virtual_memory().total // (1024 * 1024)
    cores = psutil.cpu_count(logical=False) or os.cpu_count() or 1
  except Exception:
    mem_mb, cores = 8 * 1024, os.cpu_count() or 1
  # Apple silicon: unified memory + a real GPU — the static table gives the
  # partitioner honest planning numbers for a Mac peer in a mixed ring.
  # quick=True: no subprocess; this path must return instantly.
  mac = _probe_mac_sync(quick=True)
  if mac is not None and mac.flops.fp16 > 0:
    return mac
  # ~50 GFLOPS fp32/core is a serviceable planning number for modern x86/arm.
  per_core = 0.05
  return DeviceCapabilities(
    model=f"{_platform.system()} CPU ({_platform.machine()})",
    chip=_platform.processor() or _platform.machine() or "CPU",
    memory=int(mem_mb),
    flops=DeviceFlops(fp32=per_core * cores, fp16=per_core * cores * 2, int8=per_core * cores * 4),
    num_devices=1,
  )


_cached_capabilities: Optional[DeviceCapabilities] = None
_probe_future: Optional["asyncio.Future"] = None


async def device_capabilities() -> DeviceCapabilities:
  """Async probe with caching and a timeout.

  The JAX backend init runs on a worker thread so the event loop stays
  live. If it raises, or runs past XOT_PROBE_TIMEOUT (default 120 s), that
  is an ERROR the caller sees (Node.start lets it end the process): a node
  that silently downgraded to host-CPU capabilities would join the ring,
  take a CPU-sized layer share and serve from the wrong device.
  XOT_SKIP_JAX_PROBE (dummy engine, CPU tests) never touches JAX at all.
  """
  global _cached_capabilities, _probe_future
  if _cached_capabilities is not None:
    return _cached_capabilities
  timeout = knobs.get_float("XOT_PROBE_TIMEOUT")
  loop = asyncio.get_running_loop()
  # A local reference: a probe that fails fast clears the global from its thread.
  probe = _probe_future
  if probe is None:
    # Single in-flight probe on a DAEMON thread: JAX backend init is not
    # thread-safe (so repeat callers share the future), and a daemon thread
    # never blocks the process exit a timed-out probe leads to.
    import threading

    probe = _probe_future = loop.create_future()

    def _worker(fut, target_loop) -> None:
      global _cached_capabilities, _probe_future
      try:
        caps = device_capabilities_sync()
      except Exception as e:
        _probe_future = None  # let a later caller re-probe
        try:
          # err=e: the name `e` is unbound once this except block ends, long
          # before the loop runs the callback.
          target_loop.call_soon_threadsafe(
            lambda err=e: fut.set_exception(err) if not fut.done() else None)
        except RuntimeError:
          pass  # loop already closed
        return
      # Plain assignment is thread-safe; record the result even if the loop
      # that started the probe has exited (a later asyncio.run sees the cache).
      _cached_capabilities = caps
      try:
        target_loop.call_soon_threadsafe(lambda: fut.set_result(caps) if not fut.done() else None)
      except RuntimeError:
        _probe_future = None

    threading.Thread(target=_worker, args=(probe, loop), daemon=True, name="xot-probe").start()
  try:
    return await asyncio.wait_for(asyncio.shield(probe), timeout)
  except asyncio.TimeoutError:
    raise RuntimeError(
      f"device probe (JAX backend init) exceeded XOT_PROBE_TIMEOUT={timeout:g}s — "
      "refusing to report host capabilities in the accelerator's place") from None


def device_capabilities_sync() -> DeviceCapabilities:
  """Probe priority (jax-first — the inversion this framework exists for),
  then the reference's per-OS chain (device_capabilities.py:167-396):
  torch-CUDA (incl. Jetson unified memory) -> AMD (pyamdgpuinfo/rocm-smi)
  -> macOS system_profiler -> generic host. Windows follows the same chain
  as the reference's windows_device_capabilities (cuda -> amd -> cpu); the
  host probe names the OS. The chain past JAX runs only when JAX
  initialised CLEANLY without an accelerator — a failed init raises out of
  _probe_jax_sync instead of walking down to the host CPU."""
  caps = None
  skip_accel = knobs.get_bool("XOT_SKIP_JAX_PROBE")
  if not skip_accel:
    caps = _probe_jax_sync()
    if caps is None:
      # torch is a heavyweight import: only pay it when it is installed AND
      # the caller didn't ask for the instant-start path.
      import importlib.util
      if importlib.util.find_spec("torch") is not None:
        caps = _probe_torch_cuda_sync()
    if caps is None:
      caps = _probe_amd_sync()
    if caps is None:
      # Full macOS probe (runs a subprocess — never on the instant-start
      # path; skip_accel runs fall through to the host probe's quick
      # sysctl-based Apple branch instead).
      caps = _probe_mac_sync()
  if caps is None:
    caps = _probe_host_sync()
  if DEBUG >= 1:
    print(f"Device capabilities: {caps}")
  return caps
