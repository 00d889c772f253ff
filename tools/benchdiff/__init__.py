"""benchdiff: make bench harvests comparable.

Every TPU harvest lands a `BENCH_*.json` in the repo root, and until now the
only way to answer "did this round regress?" was a human reading two JSON
blobs next to PERF.md. This tool owns that comparison:

- `diff_records` / `render_markdown`: per-metric deltas between any two
  bench records (or a record vs the `BENCH_BASELINE.json` bar), with
  per-metric noise thresholds and direction awareness (tok/s up = better,
  latency down = better) so a 1% wiggle reads as noise, not a headline.
- `check_repo`: the CI gate — every committed bench file must parse, carry
  a throughput number, and respect the same physical-plausibility rules the
  bench harness enforces at measurement time (HBM% within the ceiling,
  MFU <= 100, token cross-checks honored) — a hand-edited or corrupted
  harvest file fails CI instead of silently becoming the record.
- `perf_md_section` / `check_perf_md` / `write_perf_md`: PERF.md's
  measured-results table is GENERATED from the committed JSONs between
  BEGIN/END markers and drift-checked in CI, exactly like the README knob
  table — the markdown can no longer disagree with the data files.

Stdlib-only on purpose: CI runs it before any heavyweight import, and the
bench parent process can call it without touching jax.
"""
from __future__ import annotations

import json
import math
import re
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

BEGIN_MARK = "<!-- BEGIN BENCH RESULTS (generated: python -m tools.benchdiff --write-perf-md) -->"
END_MARK = "<!-- END BENCH RESULTS -->"

# Fields that describe the CONFIG of a run, not its performance — identical
# configs are a precondition of a meaningful diff, not a delta to report.
CONFIG_KEYS = frozenset({
  "n_params", "param_bytes", "prefill_len", "decode_tokens", "long_ctx",
  "n_devices", "concurrent_n", "elapsed_s", "t", "recorded", "n", "rc",
  "predicted_weight_bytes", "predicted_decode_bytes_per_tok",
  "predicted_flops_per_tok", "roofline_tok_s", "int8_roofline_tok_s",
  "int4_roofline_tok_s",
})

# Per-metric relative noise floors (fraction): within this band the verdict
# is "within noise" regardless of sign. Unlisted metrics take DEFAULT_NOISE.
NOISE = {
  "tok_s": 0.05,
  "value": 0.05,
  "ttft_ms": 0.15,  # one request's TTFT on the host clock jitters hard run to run
  "per_token_ms": 0.05,
  "long_tok_s": 0.07,
  "long_prefill_s": 0.10,
  "concurrent_tok_s": 0.07,
  # Speculation throughput is acceptance-dependent (data-dependent draft
  # hits), so both spec stages — and their off-arms, measured in the same
  # noisy window — get the wider concurrent-style floor.
  "spec_tok_s": 0.07,
  "spec_off_tok_s": 0.07,
  "specpaged_tok_s": 0.07,
  "specpaged_off_tok_s": 0.07,
  # Mesh on/off arms share one process and compile twice; collective
  # placement jitters the small-model window like the concurrent stage.
  "mesh_tok_s": 0.07,
  "mesh_off_tok_s": 0.07,
  "mesh_speedup": 0.07,
  "mesh_ttft_ms": 0.15,
  # The vkv stage's three arms compile three engines in one window; the
  # arm ratios inherit both arms' jitter, so they ride the wide floor too.
  # The zero bars (vkv_unpage_calls, vkv_commit_copy_bytes) are direction
  # rules, not noise entries — any move off 0 is REGRESSED.
  "vkv_int8_tok_s": 0.07,
  "vkv_int8_contig_tok_s": 0.07,
  "vkv_bf16_tok_s": 0.07,
  "vkv_paged_speedup": 0.07,
  "vkv_int8_speedup": 0.07,
  "vkv_ttft_ms": 0.15,
  # The fabric stage's TTFT pair compiles two engines in one window and the
  # warm arm's cost is dominated by a host-tier restore — both arms (and
  # their ratio) ride the wide TTFT-style floors.
  "fabric_cold_ttft_s": 0.15,
  "fabric_warm_ttft_s": 0.15,
  "fabric_speedup": 0.07,
}
DEFAULT_NOISE = 0.05
# Soak latency percentiles ride a loaded CPU ring in CI: run-to-run jitter
# is far above bench-grade noise, so soak-to-soak drift gates at a wider
# floor. Zero-tolerance counters (false aborts, leaks) are NOT noise-floored
# — their direction rule flags any increase from 0 as REGRESSED.
SOAK_LATENCY_NOISE = 0.30

SOAK_SCHEMA = "xot-soak-v1"


def is_soak_file(record: Dict[str, Any]) -> bool:
  """A `SOAK_*.json` verdict report written by `python -m tools.soak`."""
  return isinstance(record, dict) and record.get("schema") == SOAK_SCHEMA


def soak_metrics_of(record: Dict[str, Any]) -> Dict[str, float]:
  """The flat metric dict tools/soak stamps into every report
  (`flatten_metrics`): latency percentiles, rates, abort/leak counters."""
  out = {}
  for k, v in (record.get("metrics") or {}).items():
    if _is_number(v):
      out[k] = float(v)
  return out


def _is_soak_latency(name: str) -> bool:
  return ((name.startswith("client_") or name.startswith("server_"))
          and name.endswith("_s"))


def _is_number(v: Any) -> bool:
  return isinstance(v, (int, float)) and not isinstance(v, bool) and math.isfinite(v)


def load_bench(path: Path) -> Optional[Dict[str, Any]]:
  """A bench file as a flat {field: value} record, or None when the file
  holds no extractable record. Three committed shapes are understood: the
  flat result line (`BENCH_TPU_*.json`), the driver roundfile whose `tail`
  embeds the result line (`BENCH_r0*.json`), and `BENCH_BASELINE.json`'s
  keyed form (returned as-is — `is_baseline_file` distinguishes it)."""
  try:
    data = json.loads(Path(path).read_text())
  except (OSError, json.JSONDecodeError):
    return None
  if not isinstance(data, dict):
    return None
  if "tail" in data and "metric" not in data:
    # Driver roundfile: the result line is the last parseable JSON object
    # in the captured tail.
    for line in reversed(str(data.get("tail", "")).splitlines()):
      line = line.strip()
      if line.startswith("{"):
        try:
          rec = json.loads(line)
        except json.JSONDecodeError:
          continue
        if isinstance(rec, dict) and ("metric" in rec or "tok_s" in rec):
          return rec
    return None
  return data


def is_baseline_file(record: Dict[str, Any]) -> bool:
  """BENCH_BASELINE.json shape: every value is a dict keyed
  `model:platform:method` with its own tok_s."""
  return bool(record) and all(
    isinstance(v, dict) and "tok_s" in v for v in record.values())


def record_model_platform(record: Dict[str, Any]) -> Tuple[str, str]:
  """(model_id, platform) of a flat record; the model falls out of the
  `metric` name (`decode_tok_s_<model-with-underscores>_bf16_1chip`) when
  no explicit model_id survived `_emit`'s field pass-through."""
  model = record.get("model_id")
  if not model:
    m = re.match(r"decode_tok_s_(.+)_bf16_1chip$", str(record.get("metric", "")))
    model = m.group(1).replace("_", "-") if m else "unknown"
  return str(model), str(record.get("platform", "unknown"))


def metrics_of(record: Dict[str, Any]) -> Dict[str, float]:
  """The record's numeric performance metrics. `value` (the emit alias of
  the fused-decode headline) folds into `tok_s` so flat records and
  baseline entries diff under one name."""
  out: Dict[str, float] = {}
  for k, v in record.items():
    if k in CONFIG_KEYS or not _is_number(v):
      continue
    out[k] = float(v)
  if "tok_s" not in out and _is_number(record.get("value")):
    out["tok_s"] = float(record["value"])
  out.pop("value", None)
  return out


def baseline_metrics_for(baseline: Dict[str, Any],
                         record: Dict[str, Any]) -> Tuple[Optional[str], Dict[str, float]]:
  """The baseline bar matching a flat record: keyed per
  (model, platform, method) so a CPU smoke run never diffs against the TPU
  bar. Returns (key or None, metrics)."""
  model, platform = record_model_platform(record)
  key = f"{model}:{platform}:fused"
  entry = baseline.get(key)
  if not isinstance(entry, dict):
    return None, {}
  return key, {k: float(v) for k, v in entry.items() if _is_number(v)}


# Soak counters whose every increase is bad vs. informational counters whose
# magnitude depends on the injected fault schedule. Zero-tolerance is
# reserved for the counters a green VERDICT already guarantees are zero
# (false aborts, leaks): a drift gate on them can never flag a green run.
# Raw watchdog aborts and client errors are legitimately nonzero when a kill
# lands awkwardly (in-window, excused by the verdict) — gating those would
# make CI flake on fault-timing luck, so they report as info.
_SOAK_DOWN = frozenset({
  "false_aborts", "leaked_requests", "pool_page_leaks",
  # An SLO alert firing with no injected fault to blame is the alerting
  # twin of a false abort: the rules paged on healthy traffic. A green
  # verdict guarantees zero, so the drift gate can never flag a green run.
  "alert_firings_outside_fault_windows",
  # A watchdog abort INSIDE the overload window means above-capacity load
  # was shed as "stalled" aborts instead of admission-gate 429s — the exact
  # PR 8 failure mode the front door exists to close. A green verdict
  # guarantees zero, so the gate can never flag a green run.
  "overload_watchdog_aborts",
  # Traffic routed to a replica while it was out of rotation: the router
  # kept placing load on a drained/probing replica — failover is broken.
  "router_routed_while_out",
  # A perf_drift firing with no injected fault to blame: the chronic
  # sentinel named rot on healthy traffic — the drift twin of a false
  # abort. A green verdict guarantees zero, so the gate can never flag a
  # green run.
  "drift_firings_outside_fault_windows",
  # A KV-fabric transfer dropped mid-smoke (peer error, torn blob, digest
  # mismatch) between two healthy localhost processes: the transport is
  # broken, not degraded. A green verdict guarantees zero (tools/soak
  # evaluate reds on any), so the gate can never flag a green run.
  "fabric_transfer_failures",
  # A fleet respawn that never came back healthy is the outage the elastic
  # controller exists to prevent; a hedged request streaming tokens from
  # BOTH legs is a double-billed response (the loser was not cancelled).
  # A green verdict guarantees both are zero, so the gate can never flag a
  # green run.
  "fleet_respawn_failures",
  "hedge_both_streamed",
})
_SOAK_INFO = frozenset({
  "requests_submitted", "requests_ok", "request_errors",
  "request_restarts_total", "peer_evictions_total", "hop_retries_total",
  "dedup_drops_total", "watchdog_aborts_total",
  # Admission/router magnitudes depend on the injected overload/gray
  # schedule (an overload burst is SUPPOSED to shed, a gray failure is
  # supposed to drain), so their drift is informational; the zero bars
  # above are what a green verdict actually guarantees.
  "requests_rejected", "admission_rejections_total", "overload_client_rejected",
  "router_drains_total", "router_readmits_total", "router_prefetch_announced",
  # Raw firing counts depend on the fault schedule (a kill is SUPPOSED to
  # fire the error-rate rule), so magnitude drift is informational.
  "alert_firings_total", "alerts_fired_and_resolved",
  # Drift magnitudes depend on the injected schedule too (a gray phase is
  # SUPPOSED to deviate from the fleet median); the zero bar above is what
  # a green verdict guarantees. History volumes scale with run length.
  "drift_firings_total", "router_drift_named",
  "history_samples_total", "history_restarts_total",
  # Latency-anatomy shape: reservoir depth varies with load; the
  # unattributed share is gated ABSOLUTELY below (_ANATOMY_MAX_UNATTRIBUTED)
  # rather than by drift, so both report as info in diffs.
  "anatomy_breakdowns", "anatomy_unattributed_share",
  # Fabric chain/import magnitudes scale with the prompt mix (session
  # reuse satisfies locally, only fresh prompts chain), and a chain
  # FAILURE's documented degradation is a plain cold forward — the soak
  # verdict owns the >= 1 hit bar; drift here is informational.
  "kv_fabric_misses", "fabric_chained", "fabric_chain_failures",
  # Fleet actuation and hedge magnitudes are dictated by the injected
  # fault schedule (a SIGKILL is SUPPOSED to respawn, a surge is SUPPOSED
  # to scale up, a stall is SUPPOSED to hedge); the verdict owns the >= 1
  # expectations and the zero bars above own the failure counters.
  "fleet_respawns", "fleet_deaths", "fleet_scale_ups", "fleet_scale_downs",
  "fleet_spawn_failures", "hedges_fired", "hedges_won", "hedge_cancelled",
})

# A committed green soak whose stage breakdowns leave more than this
# fraction of e2e unattributed is not evidence — the anatomy can't say
# where the time went, so it must not sit in the tree as the record.
_ANATOMY_MAX_UNATTRIBUTED = 0.5


def _direction(name: str) -> str:
  """'up' = higher is better, 'down' = lower is better, 'info' = report the
  delta but render no verdict (utilization, counts, ratios whose sign has
  no universal meaning)."""
  if name in _SOAK_DOWN:
    return "down"
  if name in _SOAK_INFO:
    return "info"
  if (name.endswith("tok_s") or name.endswith("speedup") or name.endswith("_rps")
      or name.endswith("_accept_rate") or name == "vs_baseline"):
    return "up"
  # Cross-replica KV reuse is the fabric's whole point: more imported
  # warm-prefix hits/bytes at the same workload = less cold prefill.
  if name.startswith("kv_fabric_hits") or name.startswith("kv_fabric_bytes"):
    return "up"
  # Paged-native zero-bars: any unpage gather or commit copy on a paged
  # path is a structural regression, not noise (zero baseline means any
  # increase reads REGRESSED with no floor to hide behind).
  if name.endswith("_unpage_calls") or name.endswith("_commit_copy_bytes"):
    return "down"
  # Defrag copies at an identical workload are pure overhead (each move is
  # a page of HBM traffic the arena paid to stay compact) — fewer is
  # better; the fragmentation gauge itself stays info below (a snapshot of
  # workload shape, not a cost).
  if name.endswith("_defrag_moves"):
    return "down"
  if name.endswith("_ms") or name.endswith("_s"):
    return "down"
  return "info"


def diff_records(current: Dict[str, float], baseline: Dict[str, float],
                 noise: Optional[Dict[str, float]] = None) -> List[Dict[str, Any]]:
  """Per-metric delta rows, baseline-ordered then current-only extras. A
  metric missing from the baseline is reported as `new` (never a failure:
  bench stages accrete round over round); one missing from the current run
  is `missing` — that IS worth a look, a stage stopped reporting."""
  noise = {**NOISE, **(noise or {})}
  rows: List[Dict[str, Any]] = []
  for name in list(baseline) + [m for m in current if m not in baseline]:
    base = baseline.get(name)
    cur = current.get(name)
    row: Dict[str, Any] = {"metric": name, "baseline": base, "current": cur}
    if base is None:
      row.update(delta=None, pct=None, verdict="new")
    elif cur is None:
      row.update(delta=None, pct=None, verdict="missing")
    else:
      delta = cur - base
      pct = (delta / abs(base) * 100.0) if base else None
      row.update(delta=round(delta, 4), pct=round(pct, 2) if pct is not None else None)
      direction = _direction(name)
      if name in noise:
        floor = noise[name] * 100.0
      elif name in _SOAK_DOWN:
        floor = 0.0  # zero-tolerance: any new abort/leak/error is a regression
      elif _is_soak_latency(name):
        floor = SOAK_LATENCY_NOISE * 100.0
      else:
        floor = DEFAULT_NOISE * 100.0
      if direction == "info":
        row["verdict"] = "info"
      elif pct is None:
        # Zero baseline: percent is undefined but the sign still is —
        # a counter moving 0 -> N must not hide behind "within noise".
        row["verdict"] = ("within noise" if delta == 0 else
                          "improved" if (delta > 0) == (direction == "up") else "REGRESSED")
      elif abs(pct) <= floor:
        row["verdict"] = "within noise"
      else:
        better = (pct > 0) == (direction == "up")
        row["verdict"] = "improved" if better else "REGRESSED"
    rows.append(row)
  return rows


def render_markdown(rows: List[Dict[str, Any]], title: str = "") -> str:
  def fmt(v):
    if v is None:
      return "—"
    if isinstance(v, float) and v == int(v) and abs(v) < 1e15:
      return str(int(v))
    return f"{v:g}" if isinstance(v, float) else str(v)

  lines = []
  if title:
    lines.append(f"### {title}\n")
  lines.append("| Metric | Baseline | Current | Δ | Δ% | Verdict |")
  lines.append("| --- | --- | --- | --- | --- | --- |")
  for r in rows:
    pct = f"{r['pct']:+.2f}%" if r.get("pct") is not None else "—"
    lines.append(f"| {r['metric']} | {fmt(r['baseline'])} | {fmt(r['current'])} "
                 f"| {fmt(r['delta'])} | {pct} | {r['verdict']} |")
  return "\n".join(lines) + "\n"


# ----------------------------------------------------------------- CI gate



def _plausibility_findings(name: str, rec: Dict[str, Any]) -> List[str]:
  """The measurement-integrity rules bench.py enforces live, re-applied to
  the committed file — a hand-edited or bit-rotted harvest cannot sit in
  the tree claiming over-roofline physics without its `implausible` flag."""
  findings = []
  if "implausible" not in rec:
    # Every emit includes the field; a record without it is a finding on its
    # own, and the physics checks below still run against it (flagged=False).
    findings.append(f"{name}: record carries no `implausible` verdict")
  flagged = bool(rec.get("implausible"))
  checks = (
    ("hbm_bw_pct", 110.0, "exceeds the physical HBM ceiling"),
    ("mfu_pct", 100.0, "exceeds 100% MFU"),
    ("prefill_mfu_pct", 100.0, "exceeds 100% prefill MFU"),
    # The cost-model fields bench.py's live gate keys on since PR 7 —
    # absent from pre-PR-7 harvests, required-plausible in every new one.
    ("predicted_hbm_util_pct", 110.0,
     "exceeds the physical HBM ceiling (cost-model prediction)"),
    ("predicted_mfu_pct", 100.0, "exceeds 100% MFU (cost-model prediction)"),
  )
  for field_name, limit, why in checks:
    v = rec.get(field_name)
    if _is_number(v) and v > limit and not flagged:
      findings.append(f"{name}: {field_name}={v} {why} but `implausible` is not set")
  for field_name in ("tokens_verified", "overlap_tokens_match"):
    if rec.get(field_name) is False and not flagged:
      findings.append(f"{name}: {field_name} is false but `implausible` is not set")
  roof = rec.get("roofline_tok_s")
  tok_s = rec.get("tok_s", rec.get("value"))
  if _is_number(roof) and _is_number(tok_s) and tok_s > 1.1 * roof and not flagged:
    findings.append(f"{name}: tok_s={tok_s} exceeds roofline_tok_s={roof} "
                    "but `implausible` is not set")
  return findings


def bench_files(root: Path) -> List[Path]:
  return sorted(Path(root).glob("BENCH_*.json"))


def soak_files(root: Path) -> List[Path]:
  return sorted(Path(root).glob("SOAK_*.json"))


def _soak_findings(name: str, rec: Dict[str, Any]) -> List[str]:
  """Gate one committed soak report: a red (or schema-less, or internally
  inconsistent) verdict must not sit in the tree as if it were the record."""
  findings = []
  if not is_soak_file(rec):
    return [f"{name}: not a recognized soak report (schema != {SOAK_SCHEMA!r})"]
  verdict = rec.get("verdict")
  if verdict != "green":
    findings.append(f"{name}: soak verdict is {verdict!r} — only green soaks may be committed "
                    f"(reasons: {'; '.join(map(str, rec.get('reasons') or ())) or 'none recorded'})")
  metrics = rec.get("metrics")
  if not isinstance(metrics, dict) or not any(_is_number(v) for v in metrics.values()):
    findings.append(f"{name}: soak report carries no flat `metrics` dict to diff")
  else:
    # Driven by _SOAK_DOWN so the drift gate and the green-contradiction
    # gate can never disagree about what zero-tolerance means.
    for zero_key in sorted(_SOAK_DOWN):
      v = metrics.get(zero_key)
      if _is_number(v) and v > 0 and verdict == "green":
        findings.append(f"{name}: metrics[{zero_key}]={v} contradicts the green verdict")
    # Stage-breakdown honesty: a green file carrying an anatomy section
    # must ATTRIBUTE the time it reports (absolute bound, not drift).
    share = metrics.get("anatomy_unattributed_share")
    if _is_number(share) and share > _ANATOMY_MAX_UNATTRIBUTED and verdict == "green":
      findings.append(
        f"{name}: metrics[anatomy_unattributed_share]={share} exceeds the "
        f"{_ANATOMY_MAX_UNATTRIBUTED:g} bound — the stage breakdown cannot say "
        "where the time went")
  return findings


def check_repo(root: Path) -> List[str]:
  """Schema + implausibility gate over every committed bench file, plus the
  PERF.md generated-section drift check. Returns human-readable findings
  (empty = gate passes)."""
  root = Path(root)
  findings: List[str] = []
  for path in bench_files(root):
    rec = load_bench(path)
    if rec is None:
      # A driver roundfile whose round FAILED (rc != 0) legitimately holds
      # no record — the failure is its record. Anything else is corrupt.
      try:
        raw = json.loads(path.read_text())
      except (OSError, json.JSONDecodeError):
        raw = None
      if not (isinstance(raw, dict) and "tail" in raw and raw.get("rc", 0) != 0):
        findings.append(f"{path.name}: no parseable bench record")
      continue
    if is_baseline_file(rec):
      for key, entry in sorted(rec.items()):
        if not _is_number(entry.get("tok_s")):
          findings.append(f"{path.name}: baseline entry {key!r} has no numeric tok_s")
      continue
    if not _is_number(rec.get("tok_s", rec.get("value"))):
      findings.append(f"{path.name}: record carries no numeric tok_s/value")
      continue
    findings.extend(_plausibility_findings(path.name, rec))
  for path in soak_files(root):
    try:
      rec = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
      findings.append(f"{path.name}: no parseable soak report")
      continue
    findings.extend(_soak_findings(path.name, rec))
  findings.extend(check_perf_md(root))
  return findings


# ------------------------------------------------- PERF.md generated table


def perf_md_section(root: Path) -> str:
  """The PERF.md measured-results table, generated from the committed
  on-chip harvest files (BENCH_TPU_*.json) against BENCH_BASELINE.json.
  Deterministic: sorted by filename, values straight from the JSONs."""
  root = Path(root)
  baseline_rec = load_bench(root / "BENCH_BASELINE.json") or {}
  lines = [
    BEGIN_MARK,
    "",
    "| File | tok/s | vs baseline | TTFT ms | HBM % | int8 tok/s | int4 tok/s | verified | implausible |",
    "| --- | --- | --- | --- | --- | --- | --- | --- | --- |",
  ]

  def cell(v):
    return str(v) if _is_number(v) else "—"

  for path in sorted(root.glob("BENCH_TPU_*.json")):
    rec = load_bench(path)
    if rec is None or is_baseline_file(rec):
      continue
    cur = metrics_of(rec)
    _, base = baseline_metrics_for(baseline_rec, rec)
    vs = (round(cur["tok_s"] / base["tok_s"], 3)
          if _is_number(cur.get("tok_s")) and _is_number(base.get("tok_s")) and base["tok_s"]
          else None)
    lines.append(
      f"| `{path.name}` | {cell(cur.get('tok_s'))} | {cell(vs)} "
      f"| {cell(cur.get('ttft_ms'))} | {cell(cur.get('hbm_bw_pct'))} "
      f"| {cell(cur.get('int8_tok_s'))} | {cell(cur.get('int4_tok_s'))} "
      f"| {str(bool(rec.get('tokens_verified', False))).lower()} "
      f"| {str(bool(rec.get('implausible', False))).lower()} |")
  if baseline_rec:
    lines.append("")
    lines.append("Baseline bars (`BENCH_BASELINE.json`): "
                 + ", ".join(f"`{k}` = {v.get('tok_s')} tok/s"
                             for k, v in sorted(baseline_rec.items())))
  lines += ["", END_MARK]
  return "\n".join(lines)


def _committed_section(text: str) -> Optional[str]:
  start = text.find(BEGIN_MARK)
  end = text.find(END_MARK)
  if start == -1 or end == -1 or end < start:
    return None
  return text[start:end + len(END_MARK)]


def check_perf_md(root: Path, perf_md: str = "PERF.md") -> List[str]:
  path = Path(root) / perf_md
  try:
    text = path.read_text()
  except OSError:
    return [f"{perf_md}: missing"]
  committed = _committed_section(text)
  if committed is None:
    return [f"{perf_md}: no `{BEGIN_MARK}` ... `{END_MARK}` block — "
            "add one and run `python -m tools.benchdiff --write-perf-md`"]
  if committed.strip() != perf_md_section(root).strip():
    return [f"{perf_md}: generated measured-results section is stale — "
            "run `python -m tools.benchdiff --write-perf-md`"]
  return []


def write_perf_md(root: Path, perf_md: str = "PERF.md") -> bool:
  """Regenerate the PERF.md section in place (True when the file changed).
  Appends the block at the end when no markers exist yet."""
  path = Path(root) / perf_md
  text = path.read_text()
  section = perf_md_section(root)
  committed = _committed_section(text)
  if committed is None:
    new_text = text.rstrip() + "\n\n## Measured results (generated)\n\n" + section + "\n"
  else:
    new_text = text.replace(committed, section)
  if new_text != text:
    path.write_text(new_text)
    return True
  return False
