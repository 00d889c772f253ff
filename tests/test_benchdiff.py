"""tools/benchdiff tests, fixtured on the COMMITTED bench harvests.

The committed `BENCH_*.json` files are the real data the tool exists for:
the r04-vs-baseline delta PERF.md reports (165.9 -> 203.7 tok/s) must fall
out of the tool, the roundfile `tail` embedding must parse, and the PERF.md
generated section must be current — the same assertions CI's benchdiff gate
makes, pinned here so a refactor can't quietly change the math.
"""
import json
from pathlib import Path

import pytest

from tools.benchdiff import (
  BEGIN_MARK, END_MARK, baseline_metrics_for, check_perf_md, check_repo,
  diff_records, is_baseline_file, is_soak_file, load_bench, metrics_of,
  perf_md_section, render_markdown, soak_metrics_of, write_perf_md,
)
from tools.benchdiff.__main__ import main as benchdiff_main

REPO = Path(__file__).resolve().parent.parent


def _rows_by_metric(rows):
  return {r["metric"]: r for r in rows}


def test_r04_vs_baseline_reproduces_perf_md_delta():
  """The acceptance delta: BENCH_TPU_r04_main.json against the committed
  baseline bar must show exactly the 165.9 -> 203.74 tok/s improvement."""
  current = load_bench(REPO / "BENCH_TPU_r04_main.json")
  baseline = load_bench(REPO / "BENCH_BASELINE.json")
  assert is_baseline_file(baseline) and not is_baseline_file(current)
  key, base_metrics = baseline_metrics_for(baseline, current)
  assert key == "synthetic-llama-1b:tpu:fused"
  rows = _rows_by_metric(diff_records(metrics_of(current), base_metrics))
  tok = rows["tok_s"]
  assert tok["baseline"] == 165.9 and tok["current"] == 203.74
  assert tok["pct"] == pytest.approx(22.81, abs=0.01)
  assert tok["verdict"] == "improved"
  # TTFT is lower-is-better: 152.9 -> 82.5 is an improvement, not a regression.
  assert rows["ttft_ms"]["verdict"] == "improved"


def test_noise_thresholds_and_direction():
  base = {"tok_s": 100.0, "ttft_ms": 100.0, "per_token_ms": 10.0, "hbm_bw_pct": 50.0}
  cur = {"tok_s": 102.0, "ttft_ms": 130.0, "per_token_ms": 11.0, "hbm_bw_pct": 60.0}
  rows = _rows_by_metric(diff_records(cur, base))
  assert rows["tok_s"]["verdict"] == "within noise"  # +2% < 5% floor
  assert rows["ttft_ms"]["verdict"] == "REGRESSED"  # +30% latency > 15% floor
  assert rows["per_token_ms"]["verdict"] == "REGRESSED"  # +10% > 5% floor
  assert rows["hbm_bw_pct"]["verdict"] == "info"  # utilization: delta only
  rows = _rows_by_metric(diff_records({"tok_s": 90.0}, {"tok_s": 100.0}))
  assert rows["tok_s"]["verdict"] == "REGRESSED"
  rows = _rows_by_metric(diff_records({"tok_s": 120.0}, {"tok_s": 100.0}))
  assert rows["tok_s"]["verdict"] == "improved"


def test_baseline_missing_and_current_missing_metrics():
  rows = _rows_by_metric(diff_records(
    {"tok_s": 100.0, "int8_tok_s": 200.0}, {"tok_s": 100.0, "ttft_ms": 50.0}))
  assert rows["int8_tok_s"]["verdict"] == "new"  # accreting stages: no failure
  assert rows["ttft_ms"]["verdict"] == "missing"  # a stage stopped reporting
  assert rows["int8_tok_s"]["delta"] is None and rows["ttft_ms"]["delta"] is None


def test_roundfile_tail_embedding_parses():
  rec = load_bench(REPO / "BENCH_r03.json")
  assert rec is not None
  assert metrics_of(rec).get("tok_s") is not None


def test_value_aliases_tok_s():
  rec = {"metric": "decode_tok_s_synthetic_tiny_bf16_1chip", "value": 42.5, "platform": "cpu"}
  m = metrics_of(rec)
  assert m["tok_s"] == 42.5 and "value" not in m


def test_markdown_output_stable():
  current = load_bench(REPO / "BENCH_TPU_r04_main.json")
  baseline = load_bench(REPO / "BENCH_BASELINE.json")
  _, base_metrics = baseline_metrics_for(baseline, current)
  rows = diff_records(metrics_of(current), base_metrics)
  md1 = render_markdown(rows, title="t")
  md2 = render_markdown(diff_records(metrics_of(current), base_metrics), title="t")
  assert md1 == md2
  assert "| tok_s | 165.9 | 203.74 |" in md1
  assert md1.splitlines()[2].startswith("| Metric |")


def test_committed_repo_passes_gate_and_perf_md_current():
  assert check_repo(REPO) == []
  assert check_perf_md(REPO) == []
  # Generation is deterministic.
  assert perf_md_section(REPO) == perf_md_section(REPO)
  assert BEGIN_MARK in (REPO / "PERF.md").read_text()


def test_gate_flags_bad_files(tmp_path):
  (tmp_path / "BENCH_broken.json").write_text("{not json")
  (tmp_path / "BENCH_liar.json").write_text(json.dumps({
    "metric": "decode_tok_s_x_bf16_1chip", "tok_s": 50000.0, "platform": "tpu",
    "hbm_bw_pct": 14000.0, "implausible": False,
  }))
  (tmp_path / "BENCH_flagged.json").write_text(json.dumps({
    "metric": "decode_tok_s_x_bf16_1chip", "tok_s": 50000.0, "platform": "tpu",
    "hbm_bw_pct": 14000.0, "implausible": True,  # honestly flagged: no finding
  }))
  (tmp_path / "PERF.md").write_text(f"{BEGIN_MARK}\nstale\n{END_MARK}\n")
  findings = check_repo(tmp_path)
  assert any("BENCH_broken.json" in f for f in findings)
  assert any("BENCH_liar.json" in f and "implausible" in f for f in findings)
  assert not any("BENCH_flagged.json" in f for f in findings)
  assert any("PERF.md" in f and "stale" in f for f in findings)


def test_gate_rejects_modern_record_missing_implausible(tmp_path):
  """Omitting the `implausible` key entirely must not bypass the physics
  checks: no committed record may omit it."""
  (tmp_path / "BENCH_TPU_r99.json").write_text(json.dumps({
    "metric": "decode_tok_s_x_bf16_1chip", "tok_s": 50000.0, "platform": "tpu",
    "hbm_bw_pct": 14000.0,  # over-roofline, and no `implausible` key at all
  }))
  (tmp_path / "PERF.md").write_text(perf_md_section(tmp_path) + "\n")
  findings = check_repo(tmp_path)
  assert any("no `implausible` verdict" in f for f in findings)
  assert any("hbm_bw_pct" in f for f in findings)  # physics checks still ran


def test_failed_roundfile_is_not_a_gate_finding(tmp_path):
  (tmp_path / "BENCH_r99.json").write_text(json.dumps(
    {"n": 99, "cmd": "python bench.py", "rc": 1, "tail": "Traceback ..."}))
  (tmp_path / "PERF.md").write_text(perf_md_section(tmp_path) + "\n")
  assert check_repo(tmp_path) == []


def test_write_perf_md_round_trips(tmp_path):
  for name in ("BENCH_TPU_r04_main.json", "BENCH_BASELINE.json"):
    (tmp_path / name).write_text((REPO / name).read_text())
  (tmp_path / "PERF.md").write_text("# perf\n\nnarrative\n")
  assert write_perf_md(tmp_path) is True
  assert check_perf_md(tmp_path) == []
  assert write_perf_md(tmp_path) is False  # idempotent
  text = (tmp_path / "PERF.md").read_text()
  assert text.startswith("# perf") and "BENCH_TPU_r04_main.json" in text


def test_cli_exit_codes(tmp_path, capsys):
  # Happy diff: r04 improved over the baseline -> exit 0, table on stdout.
  rc = benchdiff_main(["BENCH_TPU_r04_main.json", "--baseline", "BENCH_BASELINE.json",
                       "--root", str(REPO)])
  out = capsys.readouterr().out
  assert rc == 0 and "| tok_s | 165.9 | 203.74 |" in out
  # Regression beyond noise -> exit 1; --no-gate suppresses.
  bad = tmp_path / "BENCH_regressed.json"
  bad.write_text(json.dumps({
    "metric": "decode_tok_s_synthetic_llama_1b_bf16_1chip", "tok_s": 100.0,
    "platform": "tpu", "implausible": False}))
  args = [str(bad), "--baseline", str(REPO / "BENCH_BASELINE.json")]
  assert benchdiff_main(args) == 1
  capsys.readouterr()
  assert benchdiff_main(args + ["--no-gate"]) == 0
  capsys.readouterr()
  # The CI gate on the committed repo passes.
  assert benchdiff_main(["--check", "--root", str(REPO)]) == 0
  capsys.readouterr()


def test_cli_report_out_file(tmp_path, capsys):
  out_file = tmp_path / "report.md"
  rc = benchdiff_main(["BENCH_TPU_r04_main.json", "--baseline", "BENCH_BASELINE.json",
                       "--root", str(REPO), "--out", str(out_file)])
  capsys.readouterr()
  assert rc == 0
  assert "| tok_s | 165.9 | 203.74 |" in out_file.read_text()


# ------------------------------------------------------- soak verdict shape


def _soak_record(**metrics):
  """A minimal SOAK_*.json-shaped record (schema + verdict + flat metrics —
  the committed fixture SOAK_smoke.json is the full real one)."""
  base = {
    "client_ttft_p95_s": 0.5, "client_e2e_p95_s": 1.2, "server_ttft_p95_s": 0.4,
    "achieved_rps": 0.25, "requests_submitted": 15.0, "requests_ok": 15.0,
    "request_errors": 0.0, "false_aborts": 0.0, "leaked_requests": 0.0,
    "pool_page_leaks": 0.0, "watchdog_aborts_total": 0.0,
    "request_restarts_total": 1.0,
  }
  base.update(metrics)
  return {"schema": "xot-soak-v1", "verdict": "green", "reasons": [],
          "metrics": base}


def test_committed_soak_fixture_is_real_and_green():
  """SOAK_smoke.json is the committed evidence behind the survivability
  defaults flip: a real 2-process smoke run — green verdict, an actually
  injected kill, and the flat metrics benchdiff diffs."""
  rec = json.loads((REPO / "SOAK_smoke.json").read_text())
  assert is_soak_file(rec) and rec["verdict"] == "green"
  assert rec["config"]["faults"], "the smoke must have injected a fault"
  m = soak_metrics_of(rec)
  assert m["false_aborts"] == 0 and m["leaked_requests"] == 0
  assert m["requests_submitted"] > 0 and "client_e2e_p95_s" in m


def test_soak_diff_direction_awareness():
  """Latency drift within the wide soak noise floor is quiet; a new abort
  or leak on a zero baseline is REGRESSED at any magnitude; rate counters
  are informational."""
  rows = _rows_by_metric(diff_records(
    soak_metrics_of(_soak_record(client_e2e_p95_s=1.4, false_aborts=1.0,
                                 leaked_requests=2.0, requests_ok=14.0)),
    soak_metrics_of(_soak_record())))
  assert rows["client_e2e_p95_s"]["verdict"] == "within noise"  # +17% < 30% floor
  assert rows["false_aborts"]["verdict"] == "REGRESSED"   # 0 -> 1, no pct defined
  assert rows["leaked_requests"]["verdict"] == "REGRESSED"
  assert rows["requests_ok"]["verdict"] == "info"
  worse = _rows_by_metric(diff_records(
    soak_metrics_of(_soak_record(client_e2e_p95_s=2.0)),
    soak_metrics_of(_soak_record())))
  assert worse["client_e2e_p95_s"]["verdict"] == "REGRESSED"  # +67% > 30% floor
  better = _rows_by_metric(diff_records(
    soak_metrics_of(_soak_record(achieved_rps=0.4)),
    soak_metrics_of(_soak_record())))
  assert better["achieved_rps"]["verdict"] == "improved"  # _rps is higher-better


def test_soak_gate_rejects_red_and_inconsistent_reports(tmp_path):
  (tmp_path / "PERF.md").write_text(perf_md_section(tmp_path) + "\n")
  red = _soak_record()
  red["verdict"] = "red"
  red["reasons"] = ["false abort: n1"]
  (tmp_path / "SOAK_red.json").write_text(json.dumps(red))
  findings = check_repo(tmp_path)
  assert any("SOAK_red.json" in f and "red" in f for f in findings)
  # A green verdict contradicted by nonzero abort metrics is also flagged.
  lying = _soak_record(false_aborts=3.0)
  (tmp_path / "SOAK_lying.json").write_text(json.dumps(lying))
  findings = check_repo(tmp_path)
  assert any("SOAK_lying.json" in f and "false_aborts" in f for f in findings)
  # And a clean green one passes.
  (tmp_path / "SOAK_red.json").unlink()
  (tmp_path / "SOAK_lying.json").unlink()
  (tmp_path / "SOAK_ok.json").write_text(json.dumps(_soak_record()))
  assert check_repo(tmp_path) == []


def test_soak_alert_keys_gate_and_direction(tmp_path):
  """Out-of-fault-window alert firings are zero-tolerance: REGRESSED even
  from a zero baseline, and a committed green report carrying one is
  flagged by --check; raw firing counts stay informational (a kill is
  SUPPOSED to fire the error-rate rule)."""
  rows = _rows_by_metric(diff_records(
    soak_metrics_of(_soak_record(alert_firings_outside_fault_windows=1.0,
                                 alert_firings_total=3.0)),
    soak_metrics_of(_soak_record(alert_firings_outside_fault_windows=0.0,
                                 alert_firings_total=1.0))))
  assert rows["alert_firings_outside_fault_windows"]["verdict"] == "REGRESSED"
  assert rows["alert_firings_total"]["verdict"] == "info"
  (tmp_path / "PERF.md").write_text(perf_md_section(tmp_path) + "\n")
  lying = _soak_record(alert_firings_outside_fault_windows=2.0)
  (tmp_path / "SOAK_alerts.json").write_text(json.dumps(lying))
  findings = check_repo(tmp_path)
  assert any("SOAK_alerts.json" in f and "alert_firings_outside_fault_windows" in f
             for f in findings)


def test_soak_anatomy_gate_and_direction(tmp_path):
  """The stage-breakdown honesty gate: a committed green soak whose
  anatomy leaves more than the declared fraction unattributed is flagged
  by --check (absolute bound); reservoir depth and the share itself stay
  informational in soak-to-soak diffs."""
  rows = _rows_by_metric(diff_records(
    soak_metrics_of(_soak_record(anatomy_breakdowns=12.0,
                                 anatomy_unattributed_share=0.2)),
    soak_metrics_of(_soak_record(anatomy_breakdowns=8.0,
                                 anatomy_unattributed_share=0.1))))
  assert rows["anatomy_breakdowns"]["verdict"] == "info"
  assert rows["anatomy_unattributed_share"]["verdict"] == "info"
  (tmp_path / "PERF.md").write_text(perf_md_section(tmp_path) + "\n")
  lying = _soak_record(anatomy_unattributed_share=0.8)
  (tmp_path / "SOAK_anatomy.json").write_text(json.dumps(lying))
  findings = check_repo(tmp_path)
  assert any("SOAK_anatomy.json" in f and "anatomy_unattributed_share" in f
             for f in findings)
  # Under the bound: passes.
  (tmp_path / "SOAK_anatomy.json").write_text(json.dumps(
    _soak_record(anatomy_unattributed_share=0.3)))
  assert check_repo(tmp_path) == []


def test_soak_cli_diff_and_mixed_shapes(tmp_path, capsys):
  cur = tmp_path / "SOAK_now.json"
  base = tmp_path / "SOAK_then.json"
  cur.write_text(json.dumps(_soak_record(client_e2e_p95_s=1.3)))
  base.write_text(json.dumps(_soak_record()))
  rc = benchdiff_main([str(cur), "--baseline", str(base)])
  out = capsys.readouterr().out
  assert rc == 0 and "[soak]" in out and "client_e2e_p95_s" in out
  # A regression gates the CLI exactly like bench files.
  cur.write_text(json.dumps(_soak_record(false_aborts=1.0)))
  assert benchdiff_main([str(cur), "--baseline", str(base)]) == 1
  capsys.readouterr()
  # Soak-vs-bench cross diffs are a usage error, both ways.
  assert benchdiff_main([str(cur), "--baseline",
                         str(REPO / "BENCH_BASELINE.json")]) == 2
  assert benchdiff_main([str(REPO / "BENCH_TPU_r04_main.json"),
                         "--baseline", str(cur)]) == 2
  capsys.readouterr()
