"""xotlint self-tests: per-checker true/false-positive fixtures + the
real-tree gate (a fresh run over the repository must have no finding
outside the committed baseline, which is what CI enforces).

Fixture trees mirror the real layout (xotorch_tpu/utils/knobs.py,
orchestration/metrics.py, api/chatgpt_api.py, README.md) inside tmp_path so
every checker runs exactly the code path it runs in CI.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
  sys.path.insert(0, str(ROOT))

from tools.xotlint import CHECKERS, run_checkers
from tools.xotlint import __main__ as xotlint_main
from tools.xotlint import callgraph, doc_drift, metrics_consistency
from tools.xotlint.core import Repo, load_baseline

# A minimal but faithful knob registry for fixture trees: same REGISTRY /
# knob_table_markdown surface the checkers load standalone.
FIXTURE_KNOBS = '''
from dataclasses import dataclass
from typing import Optional

@dataclass(frozen=True)
class Knob:
  name: str
  kind: str
  default: Optional[str]
  doc: str
  section: str = "General"

_DEFS = (
  Knob("XOT_GOOD", "int", "1", "A registered knob."),
  Knob("XOT_TRISTATE", "bool", None, "Unset means auto."),
)
REGISTRY = {k.name: k for k in _DEFS}

def knob_table_markdown():
  lines = ["**General**", "", "| Knob | Type | Default | Description |",
           "| --- | --- | --- | --- |"]
  for k in _DEFS:
    default = "_unset_" if k.default is None else "`%s`" % k.default
    lines.append("| `%s` | %s | %s | %s |" % (k.name, k.kind, default, k.doc))
  return "\\n".join(lines).strip() + "\\n"
'''

FIXTURE_METRICS = '''
class NodeMetrics:
  def __init__(self, node_id=""):
    from prometheus_client import CollectorRegistry, Counter, Gauge
    self.registry = CollectorRegistry()
    labels = {"node_id": node_id}
    self.requests_total = Counter(
      "xot_requests_total", "Requests", ["node_id"], registry=self.registry
    ).labels(**labels)
    self.peers = Gauge(
      "xot_peers", "Peers", ["node_id"], registry=self.registry
    ).labels(**labels)

  def exposition(self):
    from prometheus_client import generate_latest
    body = generate_latest(self.registry)
    extra = []
    for key, name, help_text in (
      ("hop_retries", "xot_hop_retries_total", "Retried hops"),
    ):
      extra.append(f"# HELP {name} {help_text}\\n# TYPE {name} counter\\n{name} 0\\n")
    return body + "".join(extra).encode()
'''

FIXTURE_API = '''
class API:
  async def handle_get_metrics(self, request):
    eng = self.engine
    extra = []
    for attr, name, help_text in (
      ("_prefix_hits", "xot_prefix_cache_hits_total", "Prefix hits"),
    ):
      val = getattr(eng, attr, None)
      if val is not None:
        extra.append(f"# HELP {name} {help_text}\\n# TYPE {name} counter\\n{name} {val}\\n")
    return extra
'''

FIXTURE_ENGINE = '''
class Engine:
  def __init__(self):
    self._prefix_hits = 0

  def hit(self):
    self._prefix_hits += 1
'''


def make_tree(tmp_path, files):
  """Write a fixture tree with the standard well-known modules, plus the
  test's own files; returns a Repo rooted there."""
  defaults = {
    "xotorch_tpu/__init__.py": "",
    "xotorch_tpu/utils/__init__.py": "",
    "xotorch_tpu/utils/knobs.py": FIXTURE_KNOBS,
    "xotorch_tpu/orchestration/__init__.py": "",
    "xotorch_tpu/orchestration/metrics.py": FIXTURE_METRICS,
    "xotorch_tpu/api/__init__.py": "",
    "xotorch_tpu/api/chatgpt_api.py": FIXTURE_API,
    "xotorch_tpu/inference/__init__.py": "",
    "xotorch_tpu/inference/engine.py": FIXTURE_ENGINE,
  }
  merged = {**defaults, **files}
  for rel, content in merged.items():
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(content)
  repo = Repo(str(tmp_path))
  if "README.md" not in merged:
    (tmp_path / "README.md").write_text(
      "# fixture\n\n" + doc_drift.generated_section(repo) + "\n")
  return repo


def findings_by(repo, checker, code=None):
  found = run_checkers(repo, only=[checker])
  if code is not None:
    found = [f for f in found if f.code == code]
  return found


# ------------------------------------------------------------ async-safety

def test_async_safety_flags_blocking_calls(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "import time, subprocess, asyncio\n"
    "async def hop():\n"
    "  time.sleep(1)\n"
    "  subprocess.run(['x'])\n"
    "  out.block_until_ready()\n"
  )})
  codes = [f.key for f in findings_by(repo, "async-safety", "blocking-call")]
  assert codes == ["hop:time.sleep", "hop:subprocess.run", "hop:block_until_ready"]


def test_async_safety_ignores_sync_and_async_equivalents(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "import time, asyncio\n"
    "def sync_helper():\n"
    "  time.sleep(1)\n"          # sync scope: fine
    "async def hop():\n"
    "  await asyncio.sleep(1)\n"  # async equivalent: fine
    "  def inner():\n"
    "    time.sleep(1)\n"         # nested sync def: out of scope
  )})
  assert findings_by(repo, "async-safety", "blocking-call") == []


def test_async_safety_flags_raw_create_task_except_wrapper(tmp_path):
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/node.py": (
      "import asyncio\n"
      "def start():\n"
      "  asyncio.create_task(work())\n"
    ),
    # The wrapper module itself is the one sanctioned call site.
    "xotorch_tpu/utils/helpers.py": (
      "import asyncio\n"
      "def spawn_detached(coro):\n"
      "  return asyncio.create_task(coro)\n"
    ),
  })
  found = findings_by(repo, "async-safety", "raw-create-task")
  assert [f.path for f in found] == ["xotorch_tpu/orchestration/node.py"]


def test_async_safety_flags_lock_across_await(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "async def locked():\n"
    "  with self._lock:\n"
    "    await thing()\n"
    "async def fine():\n"
    "  with self._lock:\n"
    "    x = 1\n"
    "  await thing()\n"
  )})
  found = findings_by(repo, "async-safety", "lock-across-await")
  assert [f.key for f in found] == ["locked"]


def test_async_safety_inline_suppression(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "import time\n"
    "async def hop():\n"
    "  time.sleep(1)  # xotlint: disable=async-safety (fixture reason)\n"
  )})
  assert findings_by(repo, "async-safety") == []


# ----------------------------------------------------------- knob-registry

def test_knob_registry_flags_unregistered_and_direct_reads(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "import os\n"
    "from xotorch_tpu.utils import knobs\n"
    "a = os.getenv('XOT_TYPO')\n"          # unregistered + direct
    "b = os.getenv('XOT_GOOD', '1')\n"     # registered but direct
    "c = os.environ['XOT_GOOD']\n"         # registered but direct
    "d = knobs.get_int('XOT_TYPO2')\n"     # typo through the accessor
  )})
  unreg = {f.key for f in findings_by(repo, "knob-registry", "unregistered-knob")}
  direct = {f.key for f in findings_by(repo, "knob-registry", "direct-env-read")}
  assert unreg == {"XOT_TYPO", "XOT_TYPO2"}
  assert direct == {"XOT_GOOD"}


def test_knob_registry_accepts_accessors_and_writes(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "import os\n"
    "from xotorch_tpu.utils import knobs\n"
    "a = knobs.get_int('XOT_GOOD')\n"
    "b = knobs.raw('XOT_TRISTATE')\n"
    "os.environ['XOT_GOOD'] = '2'\n"  # a write, not a read
  )})
  assert findings_by(repo, "knob-registry") == []


# --------------------------------------------------------------- doc-drift

def test_doc_drift_clean_when_generated(tmp_path):
  repo = make_tree(tmp_path, {})  # README generated by make_tree
  assert findings_by(repo, "doc-drift") == []


def test_doc_drift_flags_missing_stale_and_unknown(tmp_path):
  repo = make_tree(tmp_path, {})
  readme = tmp_path / "README.md"
  text = readme.read_text()
  # Stale default for one knob, drop the other, add a phantom row.
  text = text.replace("| `XOT_GOOD` | int | `1` |", "| `XOT_GOOD` | int | `7` |")
  text = "\n".join(l for l in text.splitlines() if "XOT_TRISTATE" not in l)
  text = text.replace("<!-- END XOT KNOBS -->",
                      "| `XOT_PHANTOM` | int | `0` | Not registered. |\n<!-- END XOT KNOBS -->")
  readme.write_text(text)
  found = {(f.code, f.key) for f in findings_by(Repo(str(tmp_path)), "doc-drift")}
  assert found == {
    ("stale-doc", "XOT_GOOD"),
    ("undocumented-knob", "XOT_TRISTATE"),
    ("unknown-documented-knob", "XOT_PHANTOM"),
  }


def test_doc_drift_flags_missing_section(tmp_path):
  repo = make_tree(tmp_path, {"README.md": "# no markers here\n"})
  assert [f.code for f in findings_by(repo, "doc-drift")] == ["missing-section"]


# ----------------------------------------------------- metrics-consistency

def test_metrics_clean_fixture(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "from xotorch_tpu.networking.faults import bump\n"
    "class Node:\n"
    "  def hop(self):\n"
    "    self.metrics.requests_total.inc()\n"
    "    self.metrics.peers.set(2)\n"
    "    bump('hop_retries')\n"
  )})
  assert findings_by(repo, "metrics-consistency") == []


def test_metrics_flags_unknown_attr_and_unexported_bump(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "class Node:\n"
    "  def hop(self):\n"
    "    self.metrics.requests_typo_total.inc()\n"
    "    bump('never_exported')\n"
  )})
  codes = {(f.code, f.key) for f in findings_by(repo, "metrics-consistency")}
  assert codes == {
    ("unknown-metric-attr", "requests_typo_total.inc"),
    ("unexported-counter", "never_exported"),
  }


def test_metrics_flags_counter_name_convention(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/metrics.py": (
    FIXTURE_METRICS
    .replace("xot_requests_total", "xot_requests")  # counter w/o _total
    .replace('"xot_peers"', '"xot_peers_total"')    # gauge WITH _total
  )})
  keys = {f.key for f in findings_by(repo, "metrics-consistency",
                                     "counter-name-convention")}
  assert keys == {"xot_requests", "xot_peers_total"}


def test_metrics_flags_dead_exported_engine_counter(tmp_path):
  repo = make_tree(tmp_path, {
    # Engine no longer increments the attr the API still exports.
    "xotorch_tpu/inference/engine.py": "class Engine:\n  pass\n",
  })
  found = findings_by(repo, "metrics-consistency", "dead-exported-counter")
  assert [f.key for f in found] == ["xot_prefix_cache_hits_total"]


def test_metrics_init_assignment_is_not_an_increment(tmp_path):
  """`self._attr = 0` in __init__ must not count as incrementing: an
  exported counter whose only remaining reference is its zero-init is
  exactly the stale-exposition drift this check exists for."""
  repo = make_tree(tmp_path, {
    "xotorch_tpu/inference/engine.py": (
      "class Engine:\n"
      "  def __init__(self):\n"
      "    self._prefix_hits = 0\n"
    ),
  })
  found = findings_by(repo, "metrics-consistency", "dead-exported-counter")
  assert [f.key for f in found] == ["xot_prefix_cache_hits_total"]
  # Self-referential assignment IS an increment.
  repo = make_tree(tmp_path / "b", {
    "xotorch_tpu/inference/engine.py": (
      "class Engine:\n"
      "  def hit(self):\n"
      "    self._prefix_hits = self._prefix_hits + 1\n"
    ),
  })
  assert findings_by(repo, "metrics-consistency", "dead-exported-counter") == []


def test_metrics_flags_dead_exported_gauge(tmp_path):
  """An exposition row keyed on a STATS-DICT key (pool/host/perf gauge
  tables) must resolve to a key some engine code actually produces."""
  api = (
    "class API:\n"
    "  async def handle_get_metrics(self, request):\n"
    "    eng = self.engine\n"
    "    extra = []\n"
    "    stats = eng.perf_stats()\n"
    "    for key, name, help_text in (\n"
    "      ('decode_tok_s', 'xot_decode_tok_s', 'EWMA decode tok/s'),\n"
    "      ('ghost_rate', 'xot_ghost_rate', 'Never produced anywhere'),\n"
    "    ):\n"
    "      extra.append(f\"# HELP {name} {help_text}\\n# TYPE {name} gauge\\n{name} {stats[key]}\\n\")\n"
    "    return extra\n"
  )
  engine = (
    "class Engine:\n"
    "  def __init__(self):\n"
    "    self._prefix_hits = 0\n"
    "  def hit(self):\n"
    "    self._prefix_hits += 1\n"
    "  def perf_stats(self):\n"
    "    return {'decode_tok_s': 1.0}\n"
  )
  repo = make_tree(tmp_path, {
    "xotorch_tpu/api/chatgpt_api.py": FIXTURE_API.rstrip() + "\n" + api,
    "xotorch_tpu/inference/engine.py": engine,
  })
  found = findings_by(repo, "metrics-consistency", "dead-exported-gauge")
  assert [f.key for f in found] == ["xot_ghost_rate"]


# ----------------------------------------------- flight-event consistency

FIXTURE_FLIGHT = '''
EVENTS = (
  "request.admitted",
  "watchdog.fired",
)
_EVENT_SET = frozenset(EVENTS)

class FlightRecorder:
  def record(self, event, request_id=None, **attrs):
    pass
'''


def test_flight_events_clean_fixture(tmp_path):
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/flight.py": FIXTURE_FLIGHT,
    "xotorch_tpu/orchestration/node.py": (
      "class Node:\n"
      "  def admit(self):\n"
      "    self.flight.record('request.admitted', 'r1')\n"
      "    self.flight.record('watchdog.fired', 'r1', kind='stall')\n"
      # Non-`a.b` record() calls (an unrelated recorder API) are not flight
      # sites and must not be matched against the vocabulary.
      "    self.audio.record('wav')\n"
    ),
  })
  assert findings_by(repo, "metrics-consistency") == []


def test_flight_events_flags_typo_and_dead(tmp_path):
  """A typo'd event literal raises at runtime on the serving path — it must
  fail lint instead; and the event the typo orphaned is now dead (declared
  but never recorded), which is the same drift seen from the other side."""
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/flight.py": FIXTURE_FLIGHT,
    "xotorch_tpu/orchestration/node.py": (
      "class Node:\n"
      "  def admit(self):\n"
      "    self.flight.record('request.admited', 'r1')\n"  # typo
      "    self.flight.record('watchdog.fired', 'r1')\n"
    ),
  })
  found = {(f.code, f.key) for f in findings_by(repo, "metrics-consistency")}
  assert found == {
    ("unknown-flight-event", "request.admited"),
    ("dead-flight-event", "request.admitted"),
  }


def test_flight_events_absent_module_skips_checks(tmp_path):
  """Trees without orchestration/flight.py (every other fixture here) have
  no vocabulary to check against: `.record("a.b")` calls pass silently
  instead of all being flagged unknown."""
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "class Node:\n"
    "  def f(self):\n"
    "    self.flight.record('any.thing')\n"
  )})
  assert findings_by(repo, "metrics-consistency") == []


def _metrics_with_ttft_hist():
  return FIXTURE_METRICS.replace(
    "from prometheus_client import CollectorRegistry, Counter, Gauge",
    "from prometheus_client import CollectorRegistry, Counter, Gauge, Histogram",
  ).replace(
    "  def exposition(self):",
    '    self.ttft = Histogram(\n'
    '      "xot_ttft_seconds", "TTFT", ["node_id"], registry=self.registry\n'
    '    ).labels(**labels)\n\n'
    "  def exposition(self):",
  )


def test_alert_rule_refs_clean_fixture(tmp_path):
  """AlertRule references that resolve against the extracted surface —
  family to an exported histogram, bad/total to exported counters — are
  clean (the FP guard for unknown-alert-metric)."""
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/metrics.py": _metrics_with_ttft_hist(),
    "xotorch_tpu/orchestration/alerts.py": (
      "class AlertRule:\n"
      "  def __init__(self, **kw): pass\n"
      "RULES = (\n"
      "  AlertRule(name='lat', kind='latency', family='ttft_seconds'),\n"
      "  AlertRule(name='err', kind='errors', bad='requests', total='requests'),\n"
      ")\n"
    ),
  })
  assert findings_by(repo, "metrics-consistency", "unknown-alert-metric") == []


def test_alert_rule_refs_flag_unresolvable_metrics(tmp_path):
  """A typo'd rule reference means the alert silently evaluates to 'no
  data' forever — the TP case: an unknown family, an unexported counter,
  and a family resolving to the WRONG type (a gauge is not a latency
  distribution) all fail."""
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/metrics.py": _metrics_with_ttft_hist(),
    "xotorch_tpu/orchestration/alerts.py": (
      "class AlertRule:\n"
      "  def __init__(self, **kw): pass\n"
      "RULES = (\n"
      "  AlertRule(name='a', kind='latency', family='nope_seconds'),\n"
      "  AlertRule(name='b', kind='errors', bad='ghost', total='requests'),\n"
      "  AlertRule(name='c', kind='latency', family='peers'),\n"  # gauge, not hist
      ")\n"
    ),
  })
  keys = {f.key for f in findings_by(repo, "metrics-consistency",
                                     "unknown-alert-metric")}
  assert keys == {"family:nope_seconds", "bad:ghost", "family:peers"}


def test_alert_rule_refs_absent_module_skips(tmp_path):
  """Fixture trees without orchestration/alerts.py simply have no rules to
  check (every pre-existing fixture in this file)."""
  repo = make_tree(tmp_path, {})
  assert findings_by(repo, "metrics-consistency", "unknown-alert-metric") == []


def test_metrics_registry_resolves_labeled_histogram_family(tmp_path):
  """The shared-parent registry shape — one Histogram local, several
  `self.attr = var.labels(...)` — must register every attr, or the
  queue-wait lanes would read as unknown-metric-attr at their observe()
  sites."""
  metrics = FIXTURE_METRICS.replace(
    "from prometheus_client import CollectorRegistry, Counter, Gauge",
    "from prometheus_client import CollectorRegistry, Counter, Gauge, Histogram",
  ).replace(
    "  def exposition(self):",
    '    qw = Histogram(\n'
    '      "xot_queue_wait_seconds", "Waits", ["node_id", "lane"],\n'
    '      registry=self.registry)\n'
    '    self.queue_wait_decode = qw.labels(node_id=node_id, lane="decode")\n'
    '    self.queue_wait_prefill = qw.labels(node_id=node_id, lane="prefill")\n\n'
    "  def exposition(self):",
  )
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/metrics.py": metrics,
    "xotorch_tpu/orchestration/node.py": (
      "class Node:\n"
      "  def f(self):\n"
      "    self.metrics.queue_wait_decode.observe(0.1)\n"
      "    self.metrics.queue_wait_prefill.observe(0.2)\n"
    ),
  })
  assert findings_by(repo, "metrics-consistency") == []
  reg = metrics_consistency.registry_metrics(repo)
  assert reg["queue_wait_decode"] == ("xot_queue_wait_seconds", "histogram")
  assert reg["queue_wait_prefill"] == ("xot_queue_wait_seconds", "histogram")


# -------------------------------------------------------- exception-hygiene

def test_exception_hygiene_flags_silent_pass_in_scope(tmp_path):
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/node.py": (
      "def f():\n"
      "  try:\n    x()\n  except Exception:\n    pass\n"
    ),
    # Same pattern outside the serving-path scopes: not flagged.
    "xotorch_tpu/models/__init__.py": "",
    "xotorch_tpu/models/helpers.py": (
      "def f():\n"
      "  try:\n    x()\n  except Exception:\n    pass\n"
    ),
  })
  found = findings_by(repo, "exception-hygiene")
  assert [f.path for f in found] == ["xotorch_tpu/orchestration/node.py"]


def test_exception_hygiene_accepts_logged_or_narrow_or_suppressed(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "def f():\n"
    "  try:\n    x()\n"
    "  except Exception as e:\n    print(e)\n"       # logged
    "def g():\n"
    "  try:\n    x()\n  except OSError:\n    pass\n"  # narrow type
    "def h():\n"
    "  try:\n    x()\n"
    "  except Exception:  # xotlint: disable=exception-hygiene (fixture)\n"
    "    pass\n"
  )})
  assert findings_by(repo, "exception-hygiene") == []


# ------------------------------------------------------------ CLI contract

def test_cli_exit_codes_clean_and_violating(tmp_path, capsys):
  make_tree(tmp_path, {})
  assert xotlint_main.main(["--root", str(tmp_path), "--no-baseline"]) == 0
  (tmp_path / "xotorch_tpu/orchestration/node.py").write_text(
    "import time\nasync def f():\n  time.sleep(1)\n")
  assert xotlint_main.main(["--root", str(tmp_path), "--no-baseline"]) == 1
  capsys.readouterr()


def test_cli_rejects_unknown_checker(tmp_path, capsys):
  """A typo'd --checker name must be a usage error (exit 2), never a silent
  zero-checker run that reads as clean."""
  make_tree(tmp_path, {})
  assert xotlint_main.main(["--root", str(tmp_path), "--checker", "async-safty"]) == 2
  assert xotlint_main.main(["--root", str(tmp_path), "--checker", "async-safety"]) == 0
  capsys.readouterr()


def test_exception_hygiene_identity_stable_across_unrelated_edits(tmp_path):
  """Finding identity is scoped to the enclosing def, so adding a silent
  handler in ANOTHER function does not renumber (un-grandfather) an
  existing finding."""
  body = "def old():\n  try:\n    x()\n  except Exception:\n    pass\n"
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": body})
  before = {f.identity for f in findings_by(repo, "exception-hygiene")}
  grown = ("def earlier():\n  try:\n    y()\n  except Exception:\n    pass\n" + body)
  repo2 = make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": grown})
  after = {f.identity for f in findings_by(repo2, "exception-hygiene")}
  assert before <= after, (before, after)


def test_cli_baseline_grandfathers_then_fails_fresh(tmp_path, capsys):
  make_tree(tmp_path, {"xotorch_tpu/orchestration/node.py": (
    "import time\nasync def old():\n  time.sleep(1)\n")})
  assert xotlint_main.main(["--root", str(tmp_path), "--write-baseline"]) == 0
  assert xotlint_main.main(["--root", str(tmp_path)]) == 0  # baselined
  (tmp_path / "xotorch_tpu/orchestration/node.py").write_text(
    "import time\nasync def old():\n  time.sleep(1)\n"
    "async def fresh():\n  time.sleep(1)\n")
  assert xotlint_main.main(["--root", str(tmp_path)]) == 1  # new finding
  capsys.readouterr()


# --------------------------------------------------------------- real tree

def test_real_tree_matches_committed_baseline():
  """The CI gate, as a test: a fresh run over the repository has no finding
  outside tools/xotlint/baseline.json, and no baseline entry is stale."""
  repo = Repo(str(ROOT))
  findings = run_checkers(repo)
  baseline = set(load_baseline(str(ROOT / "tools/xotlint/baseline.json")))
  identities = {f.identity for f in findings}
  fresh = [f.render() for f in findings if f.identity not in baseline]
  assert fresh == [], "non-baselined xotlint findings:\n" + "\n".join(fresh)
  stale = baseline - identities
  assert stale == set(), f"stale baseline entries (fixed — remove them): {stale}"


def test_real_tree_every_checker_ran():
  assert set(CHECKERS) == {
    "async-safety", "knob-registry", "doc-drift",
    "metrics-consistency", "exception-hygiene",
    "hotpath-sync", "retrace-hazard", "donation-safety", "lock-discipline",
    "endpoint-contract", "wire-schema", "bus-vocabulary",
    "http-client-hygiene",
  }


def test_real_tree_baseline_ships_empty():
  """Policy (PR 5, reaffirmed here): findings get FIXED or suppressed with
  a reason in the same PR — the committed baseline is always empty."""
  assert load_baseline(str(ROOT / "tools/xotlint/baseline.json")) == []


def test_real_registry_covers_every_xot_read():
  """Belt-and-braces for the registry: every XOT_* string literal passed to
  an env read or knob accessor anywhere in the package is registered."""
  repo = Repo(str(ROOT))
  assert [f.render() for f in run_checkers(repo, only=["knob-registry"])] == []


def test_synthetic_violation_per_checker(tmp_path):
  """Acceptance sweep: seeding one synthetic violation of EACH checker into
  an otherwise-clean tree makes the CLI exit non-zero."""
  violations = {
    "async-safety": {"xotorch_tpu/orchestration/bad_async.py":
                     "import time\nasync def f():\n  time.sleep(1)\n"},
    "knob-registry": {"xotorch_tpu/orchestration/bad_knob.py":
                      "import os\nx = os.getenv('XOT_NOT_A_KNOB')\n"},
    "doc-drift": {"README.md": "# markers removed\n"},
    "metrics-consistency": {"xotorch_tpu/orchestration/bad_metric.py":
                            "def f(self):\n  self.metrics.bogus_total.inc()\n"},
    "exception-hygiene": {"xotorch_tpu/orchestration/bad_except.py":
                          "def f():\n  try:\n    x()\n  except Exception:\n    pass\n"},
    "hotpath-sync": {"xotorch_tpu/inference/jax_engine/engine.py": FIXTURE_HOT_ENGINE},
    "retrace-hazard": {"xotorch_tpu/ops/bad_jit.py": (
      "import functools, jax\n"
      "@functools.partial(jax.jit, static_argnames=('start_pos',))\n"
      "def f(x, start_pos):\n  return x\n")},
    "donation-safety": {"xotorch_tpu/ops/bad_donor.py": (
      FIXTURE_DONOR_JIT +
      "def use_after(state):\n"
      "  out = write(state.buf, 1)\n"
      "  return state.buf\n")},
    "lock-discipline": {"xotorch_tpu/orchestration/bad_lock.py": (
      "import threading\n"
      "class S:\n"
      "  def __init__(self):\n"
      "    self._lock = threading.Lock()\n"
      "    self.observer = None\n"
      "  def f(self):\n"
      "    with self._lock:\n"
      "      self.observer(1)\n")},
    "endpoint-contract": {"xotorch_tpu/orchestration/bad_endpoint.py": (
      "async def poll(session, base):\n"
      "  try:\n"
      "    async with session.get(f'{base}/v1/not/registered', timeout=5.0) as r:\n"
      "      return await r.json()\n"
      "  except Exception:\n"
      "    return None\n")},
    "wire-schema": {"xotorch_tpu/orchestration/bad_wire.py": (
      "import json\n"
      "import urllib.request\n"
      "def read(url):\n"
      "  try:\n"
      "    with urllib.request.urlopen(url, timeout=2.0) as r:\n"
      "      d = json.loads(r.read())\n"
      "    return d.get('definitely_not_a_produced_key')\n"
      "  except Exception:\n"
      "    return None\n")},
    "bus-vocabulary": {"xotorch_tpu/orchestration/bad_bus.py": (
      "import json\n"
      "class Node:\n"
      "  def __init__(self, server):\n"
      "    self.server = server\n"
      "    self.on_opaque_status.register('node_status').on_next(self.on_node_status)\n"
      "  async def announce(self):\n"
      "    await self.server.broadcast_opaque_status('', json.dumps({'type': 'ghost_status'}))\n"
      "  def on_node_status(self, rid, status):\n"
      "    t = status.get('type', '')\n"
      "    if t == 'ghost_status':\n"
      "      return 1\n"
      "    if t == 'phantom_thing':\n"
      "      return 2\n")},
    "http-client-hygiene": {"xotorch_tpu/orchestration/bad_http.py": (
      "import urllib.request\n"
      "def f(url):\n"
      "  try:\n"
      "    with urllib.request.urlopen(url) as r:\n"
      "      return r.read()\n"
      "  except Exception:\n"
      "    return None\n")},
  }
  for checker, files in violations.items():
    root = tmp_path / checker.replace("-", "_")
    root.mkdir()
    make_tree(root, files)
    rc = xotlint_main.main(["--root", str(root), "--no-baseline"])
    assert rc == 1, f"synthetic {checker} violation did not fail the CLI"
    found = findings_by(Repo(str(root)), checker)
    assert found, f"synthetic {checker} violation not caught by its own checker"


# ------------------------------------------------------------ callgraph core

def test_callgraph_method_and_attr_type_resolution(tmp_path):
  """The drain-loop seam: `self.engine` typed by the __init__ annotation,
  self-method edges, and function REFERENCES passed as call arguments
  (executor indirection) all resolve."""
  repo = make_tree(tmp_path, {"xotorch_tpu/inference/jax_engine/engine.py": (
    "class JAXShardInferenceEngine:\n"
    "  def _run(self, fn):\n    return fn()\n"
    "  def _decode_batch_sync(self):\n    self._helper()\n"
    "  def _helper(self):\n    pass\n"
    "  def _unreached(self):\n    pass\n"
    "class _DecodeBatcher:\n"
    "  def __init__(self, engine: \"JAXShardInferenceEngine\"):\n"
    "    self.engine = engine\n"
    "  async def _drain(self):\n"
    "    await self.engine._run(self.engine._decode_batch_sync)\n"
  )})
  prog = callgraph.program(repo)
  reach = prog.reachable(("engine.py::_DecodeBatcher._drain",))
  names = {q.rsplit("::", 1)[1] for q in reach}
  assert "JAXShardInferenceEngine._run" in names            # typed-attr method call
  assert "JAXShardInferenceEngine._decode_batch_sync" in names  # reference edge
  assert "JAXShardInferenceEngine._helper" in names         # self-method edge
  assert "JAXShardInferenceEngine._unreached" not in names


def test_callgraph_cycle_tolerance_and_imports(tmp_path):
  repo = make_tree(tmp_path, {
    "xotorch_tpu/inference/a.py": (
      "from xotorch_tpu.inference.b import pong\n"
      "def ping():\n  pong()\n"),
    "xotorch_tpu/inference/b.py": (
      "from xotorch_tpu.inference import a\n"
      "def pong():\n  a.ping()\n"),
  })
  prog = callgraph.program(repo)
  reach = prog.reachable(("a.py::ping",))  # must terminate
  names = {q.rsplit("::", 1)[1] for q in reach}
  assert names >= {"ping", "pong"}


def test_callgraph_unknown_callee_conservatism(tmp_path):
  """Unresolvable callees (stdlib, dynamic attributes, called parameters)
  are recorded but never expand the frontier — no phantom reachability."""
  repo = make_tree(tmp_path, {"xotorch_tpu/inference/c.py": (
    "import os\n"
    "def lonely(cb):\n"
    "  os.getpid()\n"
    "  cb()\n"
    "  mystery.attr()\n"
    "def other():\n  pass\n"
  )})
  prog = callgraph.program(repo)
  reach = prog.reachable(("c.py::lonely",))
  assert {q.rsplit("::", 1)[1] for q in reach} == {"lonely"}
  info = prog.funcs[[q for q in prog.funcs if q.endswith("c.py::lonely")][0]]
  assert "os.getpid" in info.unresolved and "mystery.attr" in info.unresolved


# -------------------------------------------------------------- hotpath-sync

FIXTURE_HOT_ENGINE = '''
import numpy as np
import jax
import jax.numpy as jnp

class JAXShardInferenceEngine:
  def _decode_batch_sync(self, items):
    toks = jnp.zeros((1, 4))
    self._helper(toks)
    return np.asarray(toks[0])   # sanctioned seam: sampling readback

  def _helper(self, x):
    out = jnp.zeros((1,))
    host = np.asarray(out)       # TP: device fetch off the sanctioned seam
    n = int(out[0])              # TP: hidden transfer
    meta = np.asarray([1, 2])    # FP guard: host metadata, no device taint
    rows = float(out.ndim)       # FP guard: .ndim is a free metadata read
    width = int(out.shape[0])    # FP guard: .shape too
    count = int(len(out))        # FP guard: len() too
    return host, n, meta, rows, width, count

  def _cold_path(self):
    out = jnp.zeros((1,))
    return np.asarray(out)       # FP guard: not reachable from entry points
'''


def test_hotpath_sync_flags_reachable_syncs_not_sanctioned_or_cold(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/inference/jax_engine/engine.py":
                              FIXTURE_HOT_ENGINE})
  keys = {f.key for f in findings_by(repo, "hotpath-sync")}
  assert keys == {"_helper:np.asarray", "_helper:int"}


def test_hotpath_sync_block_until_ready_and_suppression(tmp_path):
  body = FIXTURE_HOT_ENGINE.replace(
    "host = np.asarray(out)       # TP: device fetch off the sanctioned seam",
    "host = np.asarray(out)  # xotlint: disable=hotpath-sync (fixture reason)\n"
    "    out.block_until_ready()")
  repo = make_tree(tmp_path, {"xotorch_tpu/inference/jax_engine/engine.py": body})
  keys = {f.key for f in findings_by(repo, "hotpath-sync")}
  assert keys == {"_helper:block_until_ready", "_helper:int"}


def test_hotpath_sync_sanctioned_list_matches_real_tree_exactly():
  """No dead sanctioning: clearing SANCTIONED makes the checker fire on the
  real tree EXACTLY the identities the list names — every entry is
  load-bearing, and nothing outside it relies on sanctioning."""
  from tools.xotlint import hotpath_sync
  repo = Repo(str(ROOT))
  orig = dict(hotpath_sync.SANCTIONED)
  try:
    hotpath_sync.SANCTIONED.clear()
    found = hotpath_sync.check(repo)
  finally:
    hotpath_sync.SANCTIONED.update(orig)
  fired = {tuple(f.key.split(":", 1)) for f in found}
  sanctioned = {(suffix.rsplit(".", 1)[-1], op)
                for suffix, op in hotpath_sync.SANCTIONED}
  assert fired == sanctioned, (fired, sanctioned)


async def test_dynamic_sync_callers_agree_with_sanctioned_list(monkeypatch):
  """THE dynamic-static cross-check: drive a real engine decode with the
  same monkeypatch instrumentation the PR 7-9 sync tests use, capture the
  CALLER of every host fetch, and assert every caller that sits on the
  statically-declared hot path is in the checker's SANCTIONED list. One
  source of truth, checked from both sides."""
  import sys
  import jax
  import numpy as np
  from tests.test_perf_attr import _drive_engine
  from xotorch_tpu.inference.jax_engine.engine import JAXShardInferenceEngine
  from tools.xotlint import hotpath_sync

  callers = set()
  real_asarray, real_bur = np.asarray, jax.block_until_ready

  def _record(kind):
    f = sys._getframe(2)
    if f.f_code.co_filename.endswith("jax_engine/engine.py"):
      # The bare function name: co_qualname carries the class ("Engine.method",
      # "outer.<locals>.inner"), and the static sets below are compared by
      # their final component.
      callers.add((f.f_code.co_name, kind))

  def counting_asarray(*a, **kw):
    _record("np.asarray")
    return real_asarray(*a, **kw)

  def counting_bur(x):
    _record("block_until_ready")
    return real_bur(x)

  monkeypatch.setenv("XOT_SEED", "7")
  engine = JAXShardInferenceEngine()
  monkeypatch.setattr(np, "asarray", counting_asarray)
  monkeypatch.setattr(jax, "block_until_ready", counting_bur)
  try:
    await _drive_engine(engine, "xlint-xcheck")
  finally:
    monkeypatch.setattr(np, "asarray", real_asarray)
    monkeypatch.setattr(jax, "block_until_ready", real_bur)

  # co_name is the bare function name (co_qualname needs 3.11+), so the
  # static sets are compared by their final component too.
  prog = callgraph.program(Repo(str(ROOT)))
  hot_scopes = {q.rsplit("::", 1)[1].rsplit(".", 1)[-1]
                for q in prog.reachable(hotpath_sync.ENTRY_POINTS)}
  sanctioned_scopes = {suffix.rsplit(".", 1)[-1]
                       for suffix, _op in hotpath_sync.SANCTIONED}
  on_path = {(qn, kind) for qn, kind in callers if qn in hot_scopes}
  assert on_path, "the drive never touched the static hot path — dead cross-check"
  off_list = {(qn, kind) for qn, kind in on_path if qn not in sanctioned_scopes}
  assert off_list == set(), (
    f"dynamically observed sync callers on the static hot path that the "
    f"sanctioned-boundary list does not name: {off_list}")


# ------------------------------------------------------------ retrace-hazard

def test_retrace_hazard_unbounded_static_and_allowlist(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/ops/bad_jit.py": (
    "import functools, jax\n"
    "@functools.partial(jax.jit, static_argnames=('start_pos', 'num_tokens', 'top_k'))\n"
    "def f(x, start_pos, num_tokens, top_k):\n"
    "  return x\n"
  )})
  keys = {f.key for f in findings_by(repo, "retrace-hazard", "unbounded-static")}
  assert keys == {"f:start_pos"}  # num_tokens/top_k: bounded by design


def test_retrace_hazard_traced_branch_and_static_idioms(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/ops/branchy.py": (
    "import functools, jax\n"
    "@functools.partial(jax.jit, static_argnames=('flag',))\n"
    "def f(x, y, flag):\n"
    "  if x > 0:\n"                                      # TP
    "    return x\n"
    "  if y is None:\n"                                  # FP: None presence
    "    return x\n"
    "  if isinstance(y, (int, float)) and y == 0.0:\n"   # FP: guarded idiom
    "    return x\n"
    "  if flag:\n"                                       # FP: static param
    "    return x\n"
    "  if x.shape[0] > 1:\n"                             # FP: shape metadata
    "    return x\n"
    "  return x\n"
  )})
  found = findings_by(repo, "retrace-hazard", "traced-branch")
  assert [f.line for f in found] == [4]


def test_retrace_hazard_mutable_capture(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/ops/capt.py": (
    "import jax\n"
    "_TABLE = {'a': 1}\n"
    "_FROZEN = ('a',)\n"
    "@jax.jit\n"
    "def f(y):\n"
    "  return y + _TABLE['a'] + len(_FROZEN)\n"
  )})
  keys = {f.key for f in findings_by(repo, "retrace-hazard", "mutable-capture")}
  assert keys == {"f:_TABLE"}  # tuple capture is immutable: clean


# ----------------------------------------------------------- donation-safety

FIXTURE_DONOR_JIT = (
  "import functools, jax\n"
  "@functools.partial(jax.jit, donate_argnames=('buf',))\n"
  "def write(buf, x):\n"
  "  return buf.at[0].set(x)\n"
)


def test_donation_safety_use_after_and_rebind(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/ops/donor.py": (
    FIXTURE_DONOR_JIT +
    "def use_after(state):\n"
    "  out = write(state.buf, 1)\n"
    "  return state.buf\n"          # TP: donated buffer read
    "def rebind(state):\n"
    "  state.buf = write(state.buf, 1)\n"
    "  return state.buf\n"          # FP guard: rebound from the result
    "def rebind_later(state):\n"
    "  out = write(state.buf, 1)\n"
    "  state.buf = out\n"
    "  return state.buf\n"          # FP guard: rebound before the read
  )})
  found = findings_by(repo, "donation-safety", "use-after-donate")
  assert [f.key for f in found] == ["use_after:state.buf"]


def test_donation_safety_discard_and_branches(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/ops/donor2.py": (
    FIXTURE_DONOR_JIT +
    "def discard(state):\n"
    "  write(state.buf, 1)\n"       # TP: result dropped, buffer gone
    "def branches(state, flag):\n"
    "  if flag:\n"
    "    state.buf = write(state.buf, 1)\n"
    "  else:\n"
    "    y = state.buf\n"           # FP guard: sibling branch never runs after
    "  return None\n"
  )})
  found = findings_by(repo, "donation-safety")
  assert [(f.code, f.key) for f in found] == [("donated-result-discarded",
                                               "discard:state.buf")]


def test_donation_safety_factory_and_wrapper_transitivity(tmp_path):
  """The lazy-jit factory idiom (`_commit_jit()(arena, ...)`) and the
  wrapper that donates its own parameter both propagate to callers."""
  repo = make_tree(tmp_path, {"xotorch_tpu/inference/pool.py": (
    "import jax\n"
    "_JITS = {}\n"
    "def _commit_jit():\n"
    "  fn = _JITS.get('commit')\n"
    "  if fn is None:\n"
    "    def commit(arena, seg):\n"
    "      return arena\n"
    "    fn = _JITS['commit'] = jax.jit(commit, donate_argnames=('arena',))\n"
    "  return fn\n"
    "def commit_pages(arena, seg):\n"
    "  return _commit_jit()(arena, seg)\n"   # clean: returned
    "def caller(pool):\n"
    "  commit_pages(pool.arena, 1)\n"        # TP via wrapper transitivity
  )})
  found = findings_by(repo, "donation-safety")
  assert [(f.code, f.key) for f in found] == [("donated-result-discarded",
                                               "caller:pool.arena")]


# ----------------------------------------------------------- lock-discipline

FIXTURE_LOCKS = '''
import threading
import time
import jax.numpy as jnp

class Store:
  def __init__(self):
    self._lock = threading.Lock()
    self._aux_lock = threading.Lock()
    self.observer = None

  def bad_put(self):
    with self._lock:
      if self.observer is not None:
        self.observer(1, 2)
      time.sleep(0.1)
      x = jnp.zeros((1,))

  def good_put(self):
    with self._lock:
      snap = 1
    if self.observer is not None:
      self.observer(snap, 2)
'''


def test_lock_discipline_events_and_fp_guard(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/store.py": FIXTURE_LOCKS})
  found = findings_by(repo, "lock-discipline")
  codes = {(f.code, f.key) for f in found}
  assert codes == {
    ("callback-under-lock", "Store.bad_put:Store._lock:observer"),
    ("blocking-under-lock", "Store.bad_put:Store._lock:time.sleep"),
    ("device-op-under-lock", "Store.bad_put:Store._lock:jnp.zeros"),
  }


def test_lock_discipline_asyncio_lock_is_not_a_threading_lock(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/alock.py": (
    "import asyncio\n"
    "class T:\n"
    "  def __init__(self):\n"
    "    self._lock = asyncio.Lock()\n"
    "  async def fine(self):\n"
    "    async with self._lock:\n"
    "      await asyncio.sleep(0)\n"
  )})
  assert findings_by(repo, "lock-discipline") == []


def test_lock_discipline_interprocedural_lock_order(tmp_path):
  """A->B by direct nesting in one function, B->A through a CALL made while
  holding B (callgraph closure) — the inconsistent pair is one finding."""
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/order.py": (
    "import threading\n"
    "class S:\n"
    "  def __init__(self):\n"
    "    self._lock = threading.Lock()\n"
    "    self._aux_lock = threading.Lock()\n"
    "  def ab(self):\n"
    "    with self._lock:\n"
    "      with self._aux_lock:\n"
    "        pass\n"
    "  def ba(self):\n"
    "    with self._aux_lock:\n"
    "      self._take_main()\n"
    "  def _take_main(self):\n"
    "    with self._lock:\n"
    "      pass\n"
  )})
  found = findings_by(repo, "lock-discipline", "lock-order")
  assert [f.key for f in found] == ["S._aux_lock<->S._lock"]


def test_lock_discipline_consistent_order_is_clean(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/order2.py": (
    "import threading\n"
    "class S:\n"
    "  def __init__(self):\n"
    "    self._lock = threading.Lock()\n"
    "    self._aux_lock = threading.Lock()\n"
    "  def ab(self):\n"
    "    with self._lock:\n"
    "      with self._aux_lock:\n"
    "        pass\n"
    "  def ab2(self):\n"
    "    with self._lock:\n"
    "      with self._aux_lock:\n"
    "        pass\n"
  )})
  assert findings_by(repo, "lock-discipline", "lock-order") == []


# --------------------------------------------------------- suppression audit

def test_suppression_audit_stale_missing_reason_unknown(tmp_path):
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/supp.py": (
    "import time\n"
    "async def hop():\n"
    "  time.sleep(1)  # xotlint: disable=async-safety (fixture reason)\n"
    "def quiet():\n"
    "  x = 1  # xotlint: disable=async-safety\n"
    "  y = 2  # xotlint: disable=async-safty (typo'd checker)\n"
  )})
  found = [(f.code, f.line) for f in run_checkers(repo)
           if f.checker == "suppression-audit"]
  assert ("stale-suppression", 5) in found
  assert ("missing-reason", 5) in found
  assert ("unknown-checker", 6) in found
  # The EARNED suppression on line 3 is not stale.
  assert not any(line == 3 for _, line in found)


def test_suppression_audit_catches_stale_on_checker_queried_lines(tmp_path):
  """Regression: checkers must consult suppressed() only once a violation
  is ESTABLISHED — a stale disable comment on a CLEAN line a checker
  inspects (a resolvable metrics attr, a registered knob accessor read)
  must still surface as stale, not be marked 'earned' by the query."""
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/clean.py": (
    "from xotorch_tpu.utils import knobs\n"
    "class Node:\n"
    "  def hop(self):\n"
    "    self.metrics.requests_total.inc()  # xotlint: disable=metrics-consistency (stale)\n"
    "    k = knobs.get_int('XOT_GOOD')  # xotlint: disable=knob-registry (stale)\n"
  )})
  stale = {(f.line, f.code) for f in run_checkers(repo)
           if f.checker == "suppression-audit"}
  assert (4, "stale-suppression") in stale
  assert (5, "stale-suppression") in stale


def test_suppression_audit_skipped_on_partial_runs(tmp_path):
  """A --checker subset run has incomplete hit data: no audit findings."""
  repo = make_tree(tmp_path, {"xotorch_tpu/orchestration/supp.py": (
    "def quiet():\n"
    "  x = 1  # xotlint: disable=async-safety\n"
  )})
  assert [f for f in run_checkers(repo, only=["async-safety"])
          if f.checker == "suppression-audit"] == []
  assert [f for f in run_checkers(repo)
          if f.checker == "suppression-audit"] != []


# ------------------------------------------------------------ wire contracts

FIXTURE_WIRE_SERVER = '''
from aiohttp import web

class WireAPI:
  def __init__(self, node):
    self.node = node

  async def handle_queue(self, request):
    return web.json_response({"inflight": 1, "queued": 2, "est_wait_s": 0.5})

  async def handle_kv(self, request):
    return web.json_response({"payload": "x"})

  def attach(self, app):
    app.router.add_get("/v1/queue", self.handle_queue)
    app.router.add_get("/v1/kv/{key}", self.handle_kv)
    app.router.add_post("/v1/dead", self.handle_queue)
'''

FIXTURE_WIRE_CLIENT = '''
import json
import urllib.request

async def poll(session, base):
  try:
    async with session.get(f"{base}/v1/queue", timeout=5.0) as resp:
      q = await resp.json()
    return q.get("queued")
  except Exception:
    return None

def fetch_kv(base_url, key):
  try:
    with urllib.request.urlopen(f"{base_url}/v1/kv/{key}?payload=1", timeout=2.0) as r:
      return json.loads(r.read()).get("payload")
  except Exception:
    return None
'''


def test_endpoint_contract_unknown_and_dead_routes(tmp_path):
  repo = make_tree(tmp_path, {
    "xotorch_tpu/api/wire_server.py": FIXTURE_WIRE_SERVER,
    "xotorch_tpu/router/wire_client.py": FIXTURE_WIRE_CLIENT + (
      "async def typo(session, base):\n"
      "  try:\n"
      "    async with session.get(f'{base}/v1/quue', timeout=5.0) as r:\n"
      "      return await r.json()\n"
      "  except Exception:\n"
      "    return None\n"
      "async def wrong_verb(session, base):\n"
      "  try:\n"
      "    async with session.post(f'{base}/v1/queue', timeout=5.0) as r:\n"
      "      return await r.json()\n"
      "  except Exception:\n"
      "    return None\n"),
  })
  found = {(f.code, f.key) for f in findings_by(repo, "endpoint-contract")}
  assert ("unknown-route", "GET /v1/quue") in found
  assert ("unknown-route", "POST /v1/queue") in found       # verb mismatch
  assert ("dead-route", "POST /v1/dead") in found           # zero consumers
  # Consumed routes and {param} templates do NOT fire: /v1/queue is polled,
  # /v1/kv/{key} is fetched with a different placeholder name.
  keys = {k for _, k in found}
  assert not any("/v1/kv" in k for k in keys)
  assert ("unknown-route", "GET /v1/queue") not in found


def test_endpoint_contract_ignores_external_urls(tmp_path):
  repo = make_tree(tmp_path, {
    "xotorch_tpu/download/ext.py": (
      "async def dl(session):\n"
      "  try:\n"
      "    async with session.get('https://huggingface.co/repo/resolve/main/f',\n"
      "                           timeout=5.0) as r:\n"
      "      return await r.read()\n"
      "  except Exception:\n"
      "    return None\n"),
  })
  assert [f for f in findings_by(repo, "endpoint-contract")
          if f.code == "unknown-route"] == []


def test_endpoint_allowlist_matches_real_tree_exactly():
  """No dead allowlisting, same standard as hotpath-sync's SANCTIONED:
  clearing ALLOWLIST makes the checker fire on the real tree EXACTLY the
  identities the list names — every entry is load-bearing, and no
  unlisted route is dead."""
  from tools.xotlint import endpoint_contract
  repo = Repo(str(ROOT))
  orig = dict(endpoint_contract.ALLOWLIST)
  try:
    endpoint_contract.ALLOWLIST.clear()
    found = [f for f in endpoint_contract.check(repo) if f.code == "dead-route"]
  finally:
    endpoint_contract.ALLOWLIST.update(orig)
  fired = {tuple(f.key.split(" ", 1)) for f in found}
  assert fired == set(endpoint_contract.ALLOWLIST), (
    fired ^ set(endpoint_contract.ALLOWLIST))


def test_endpoint_docs_generated_and_drift(tmp_path):
  from tools.xotlint import endpoint_contract as ec
  repo = make_tree(tmp_path, {
    "xotorch_tpu/api/wire_server.py": FIXTURE_WIRE_SERVER,
    "xotorch_tpu/router/wire_client.py": FIXTURE_WIRE_CLIENT,
  })
  readme = tmp_path / "README.md"
  # A tree WITH routes but no API section in the README:
  assert any(f.code == "missing-api-section"
             for f in findings_by(repo, "endpoint-contract"))
  # Regenerating the section makes it clean...
  section = ec.generated_section(repo)
  assert "| `GET` | `/v1/queue` |" in section and "handle_queue" in section
  readme.write_text(readme.read_text() + "\n" + section + "\n")
  doc_codes = {"missing-api-section", "undocumented-route", "stale-api-doc",
               "phantom-route-doc"}
  clean = [f for f in findings_by(Repo(str(tmp_path)), "endpoint-contract")
           if f.code in doc_codes]
  assert clean == [], [f.render() for f in clean]
  # ...and each drift direction fires its own per-route code.
  lines = readme.read_text().splitlines()
  mutated = []
  for line in lines:
    if "| `POST` | `/v1/dead` |" in line:
      continue  # drop a documented row -> undocumented-route
    if "| `/v1/queue` |" in line:
      line = line.replace("handle_queue", "handle_renamed")  # -> stale-api-doc
    if line.strip() == ec.END_MARK:  # phantom row INSIDE the marked section
      mutated.append("| `GET` | `/v1/ghost` | `xotorch_tpu/api/wire_server.py` | `gone` |")
    mutated.append(line)
  readme.write_text("\n".join(mutated) + "\n")
  found = {(f.code, f.key)
           for f in findings_by(Repo(str(tmp_path)), "endpoint-contract")}
  assert ("undocumented-route", "POST /v1/dead") in found
  assert ("stale-api-doc", "GET /v1/queue") in found
  assert ("phantom-route-doc", "GET /v1/ghost") in found


def test_wire_schema_unproduced_key_and_suppression(tmp_path):
  bad = (
    "import json\n"
    "import urllib.request\n"
    "def read(url):\n"
    "  try:\n"
    "    with urllib.request.urlopen(url, timeout=2.0) as r:\n"
    "      d = json.loads(r.read())\n"
    "    return d.get('activ_requests')\n"
    "  except Exception:\n"
    "    return None\n")
  repo = make_tree(tmp_path, {
    "xotorch_tpu/api/wire_server.py": FIXTURE_WIRE_SERVER,
    "xotorch_tpu/router/wire_client.py": FIXTURE_WIRE_CLIENT,
    "xotorch_tpu/fleet/bad_reader.py": bad,
  })
  found = findings_by(repo, "wire-schema")
  assert [(f.code, f.key) for f in found] == \
      [("unproduced-key", "read:activ_requests")]
  # The same read with the key produced somewhere is clean; a suppression
  # with a reason silences the finding.
  repo2 = make_tree(tmp_path / "b", {
    "xotorch_tpu/api/wire_server.py": FIXTURE_WIRE_SERVER,
    "xotorch_tpu/fleet/bad_reader.py": bad.replace(
      "return d.get('activ_requests')",
      "return d.get('activ_requests')  "
      "# xotlint: disable=wire-schema (peer ships it in v2)"),
  })
  assert findings_by(repo2, "wire-schema") == []


def test_wire_schema_taint_through_wrapper_and_attr(tmp_path):
  """Taint follows a local fetch wrapper's return value AND an attribute
  store across files (the router -> fleet-controller seam)."""
  repo = make_tree(tmp_path, {
    "xotorch_tpu/api/wire_server.py": FIXTURE_WIRE_SERVER,
    "xotorch_tpu/router/probe.py": (
      "import json\n"
      "import urllib.request\n"
      "def get_json(url):\n"
      "  try:\n"
      "    with urllib.request.urlopen(url, timeout=2.0) as r:\n"
      "      return json.loads(r.read())\n"
      "  except Exception:\n"
      "    return None\n"
      "class Router:\n"
      "  def probe(self, rep):\n"
      "    q = get_json(rep.url + '/v1/queue') or {}\n"
      "    rep.queue_snapshot = q.get('inflight')\n"),
    "xotorch_tpu/fleet/reader.py": (
      "def plan(rep):\n"
      "  return rep.queue_snapshot.get('no_such_wire_key')\n"),
  })
  found = findings_by(repo, "wire-schema")
  assert [(f.code, f.key) for f in found] == \
      [("unproduced-key", "plan:no_such_wire_key")]


def test_wire_schema_untainted_dict_reads_are_ignored(tmp_path):
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/localcfg.py": (
      "def pick(cfg):\n"
      "  return cfg.get('no_such_key_but_local')\n"),
  })
  assert findings_by(repo, "wire-schema") == []


def test_bus_vocabulary_unheard_and_phantom(tmp_path):
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/busnode.py": (
      "import json\n"
      "class Node:\n"
      "  def __init__(self, server):\n"
      "    self.server = server\n"
      "    self.on_opaque_status.register('node_status').on_next(self.on_node_status)\n"
      "  async def announce(self):\n"
      "    await self.server.broadcast_opaque_status('', json.dumps(\n"
      "      {'type': 'node_metrics', 'v': 1}))\n"
      "    await self.server.broadcast_opaque_status('', json.dumps(\n"
      "      {'type': 'ghost_status'}))\n"
      "  def on_node_status(self, rid, status):\n"
      "    t = status.get('type', '')\n"
      "    if t == 'node_metrics':\n"
      "      return 1\n"
      "    if t == 'phantom_thing':\n"
      "      return 2\n"),
  })
  found = {(f.code, f.key) for f in findings_by(repo, "bus-vocabulary")}
  assert found == {("unheard-type", "ghost_status"),
                   ("phantom-arm", "phantom_thing")}


def test_bus_vocabulary_ignores_unregistered_dispatch(tmp_path):
  """A `.get("type")` dispatch table NOT wired to the bus (UDP discovery)
  contributes no arms, and a tree without a bus has no findings."""
  repo = make_tree(tmp_path, {
    "xotorch_tpu/orchestration/discovery.py": (
      "def on_packet(msg):\n"
      "  t = msg.get('type', '')\n"
      "  if t == 'discovery':\n"
      "    return 1\n"),
  })
  assert findings_by(repo, "bus-vocabulary") == []


def test_http_client_hygiene_timeout_and_containment(tmp_path):
  repo = make_tree(tmp_path, {
    "xotorch_tpu/router/clients.py": (
      "import urllib.request\n"
      "def no_timeout(url):\n"
      "  try:\n"
      "    with urllib.request.urlopen(url) as r:\n"
      "      return r.read()\n"
      "  except Exception:\n"
      "    return None\n"
      "def no_try(url):\n"
      "  with urllib.request.urlopen(url, timeout=2.0) as r:\n"
      "    return r.read()\n"),
  })
  found = {(f.code, f.key) for f in findings_by(repo, "http-client-hygiene")}
  assert found == {("missing-timeout", "no_timeout:dynamic-url"),
                   ("uncontained-call", "no_try:dynamic-url")}


def test_http_client_hygiene_containment_through_callers(tmp_path):
  """A bare transport wrapper is fine when EVERY call site is wrapped —
  including references handed to an executor — and flagged when any one
  is not."""
  wrapper = (
    "import urllib.request\n"
    "def fetch(url):\n"
    "  with urllib.request.urlopen(url, timeout=2.0) as r:\n"
    "    return r.read()\n")
  repo = make_tree(tmp_path, {
    "xotorch_tpu/router/wrapped.py": wrapper + (
      "def a(url):\n"
      "  try:\n"
      "    return fetch(url)\n"
      "  except Exception:\n"
      "    return None\n"
      "async def b(loop, url):\n"
      "  try:\n"
      "    return await loop.run_in_executor(None, fetch)\n"
      "  except Exception:\n"
      "    return None\n"),
  })
  assert findings_by(repo, "http-client-hygiene") == []
  repo2 = make_tree(tmp_path / "b", {
    "xotorch_tpu/router/leaky.py": wrapper + (
      "def a(url):\n"
      "  return fetch(url)\n"),  # one naked call site -> flagged
  })
  found = {(f.code, f.key) for f in findings_by(repo2, "http-client-hygiene")}
  assert found == {("uncontained-call", "fetch:dynamic-url")}


def test_http_client_hygiene_session_level_timeout_exempts(tmp_path):
  body = (
    "import aiohttp\n"
    "def mk():\n"
    "  return aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=5))\n"
    "async def call(session, base):\n"
    "  try:\n"
    "    async with session.get(f'{base}/v1/queue') as r:\n"
    "      return await r.json()\n"
    "  except Exception:\n"
    "    return None\n")
  repo = make_tree(tmp_path, {
    "xotorch_tpu/api/wire_server.py": FIXTURE_WIRE_SERVER,
    "xotorch_tpu/router/sess.py": body,
  })
  assert [f for f in findings_by(repo, "http-client-hygiene")
          if f.code == "missing-timeout"] == []
  # Without the session-level timeout the same per-call-less get fires.
  repo2 = make_tree(tmp_path / "b", {
    "xotorch_tpu/api/wire_server.py": FIXTURE_WIRE_SERVER,
    "xotorch_tpu/router/sess.py": body.replace(
      "aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=5))",
      "aiohttp.ClientSession()"),
  })
  found = {(f.code, f.key)
           for f in findings_by(repo2, "http-client-hygiene")}
  assert ("missing-timeout", "call:/v1/queue") in found


def test_suppression_audit_covers_wire_checkers_and_tool_files(tmp_path):
  """A stale wire-schema suppression is flagged even in the CLI tool trees
  (tools/anatomy etc.), which only the wire model loads — the audit runs
  over every LOADED file, not just the package walk."""
  repo = make_tree(tmp_path, {
    "tools/anatomy/probe.py": (
      "def quiet(d):\n"
      "  return d.get('k')  # xotlint: disable=wire-schema (stale claim)\n"),
  })
  found = [f for f in run_checkers(repo) if f.checker == "suppression-audit"]
  assert [(f.code, f.path) for f in found] == \
      [("stale-suppression", "tools/anatomy/probe.py")]


def test_cli_endpoint_docs_and_wire_info(tmp_path, capsys):
  make_tree(tmp_path, {"xotorch_tpu/api/wire_server.py": FIXTURE_WIRE_SERVER})
  assert xotlint_main.main(["--root", str(tmp_path), "--endpoint-docs"]) == 0
  out = capsys.readouterr().out
  assert out.startswith("<!-- BEGIN XOT HTTP API")
  assert "| `GET` | `/v1/queue` |" in out
  assert xotlint_main.main(["--root", str(tmp_path), "--wire-info"]) == 0
  capsys.readouterr()


async def test_dynamic_wire_keys_subset_of_static_closure():
  """THE dynamic-static cross-check for the wire extractor: scrape
  /v1/queue and /v1/alerts from a LIVE in-process app (aiohttp test
  utils over a real node + dummy engine) and assert every key observed
  on the real wire — top level plus the nested admission block — is in
  the statically extracted produced-key closure of those routes'
  registered handlers. An extractor that silently stopped seeing the
  handlers' dict literals fails here, not in production."""
  from tests.test_api import _api_client
  from tools.xotlint.wire import wire_model
  client, node, _ = await _api_client()
  try:
    resp = await client.get("/v1/queue")
    assert resp.status == 200
    q = await resp.json()
    resp = await client.get("/v1/alerts")
    assert resp.status == 200
    a = await resp.json()
  finally:
    await client.close()
  observed = set(q) | set(a)
  if isinstance(q.get("admission"), dict):
    observed |= set(q["admission"])
  assert len(observed) >= 15, f"scrape looks degenerate: {sorted(observed)}"

  wm = wire_model(Repo(str(ROOT)))
  closure = set()
  for route in wm.routes:
    if route.path in ("/v1/queue", "/v1/alerts") and route.handler_qual:
      closure |= wm.produced_closure(route.handler_qual)
  assert closure, "no /v1/queue //v1/alerts handler closures resolved"
  missing = sorted(k for k in observed if k not in closure)
  assert missing == [], (
    f"keys observed on the live wire that the static wire model cannot "
    f"see being produced by the handlers: {missing}")


# ------------------------------------------------------------- stats / perf

def test_stats_cover_all_checkers_and_cli_writes_file(tmp_path, capsys):
  make_tree(tmp_path, {})
  stats = {}
  run_checkers(Repo(str(tmp_path)), stats=stats)
  assert set(stats) == set(CHECKERS) | {"suppression-audit"}
  assert all("secs" in row and "findings" in row for row in stats.values())
  out = tmp_path / "stats.json"
  assert xotlint_main.main(["--root", str(tmp_path), "--no-baseline",
                            "--stats", "--stats-file", str(out)]) == 0
  payload = json.loads(out.read_text())
  assert set(payload["checkers"]) == set(CHECKERS) | {"suppression-audit"}
  assert payload["total_secs"] >= 0
  capsys.readouterr()


def test_real_tree_lint_completes_under_60s():
  """Tier-1 guard for the shared-AST-cache performance: the full
  thirteen-checker run over the real tree (callgraph + wire model each
  built once, memoized on the Repo) stays an order of magnitude inside
  the CI budget. A regression to per-checker re-parsing/re-walking would
  blow well past this."""
  import time as _time
  t0 = _time.monotonic()
  repo = Repo(str(ROOT))
  run_checkers(repo)
  assert _time.monotonic() - t0 < 60.0
