"""Analyzer tests (ISSUE 8): every rule must fire on a seeded violation
(a checker that cannot fail is waiving the policy silently), and the
self-run over THIS repo must be clean — that second half is the actual
invariant gate tier-1 runs.

Fixture repos are tiny synthetic trees in tmp_path; rules are exercised
through the same ``run()`` entry the CLI uses.
"""

import json
import subprocess
import sys
from pathlib import Path

from gridllm_tpu.analysis import run
from gridllm_tpu.analysis.rules.dashboard_drift import (
    expand_braces,
    readme_table_metrics,
)

REPO_ROOT = Path(__file__).resolve().parents[1]

# a README configuration table covering every registered env var, so
# fixture repos only trip the violations they seed (generated, not typed)
def _full_env_table() -> str:
    from gridllm_tpu.utils.config import ENV_VARS

    rows = ["## Configuration", "",
            "| Variable | Default | Description |", "|---|---|---|"]
    rows += [f"| `{v.name}` | `{v.default}` | {v.description} |"
             for v in ENV_VARS.values()]
    return "\n".join(rows)


def make_repo(tmp_path: Path, files: dict[str, str]) -> Path:
    defaults = {
        "README.md": _full_env_table() + "\n",
        "gridllm_tpu/__init__.py": "",
        "deploy/grafana-dashboard.json": "{}",
        "deploy/prometheus-alerts.yml": "groups: []",
    }
    for rel, text in {**defaults, **files}.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return tmp_path


def findings_for(root: Path, rule: str):
    return [f for f in run(root, [rule]) if f.rule == rule]


# -- per-rule seeded violations --------------------------------------------

def test_config_discipline_fires_on_direct_read(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/mod.py": (
        "import os\n"
        "LEVEL = os.environ.get('GRIDLLM_LOG_LEVEL', 'info')\n"
    )})
    msgs = [f.message for f in findings_for(root, "config-discipline")]
    assert any("direct os.environ read of GRIDLLM_LOG_LEVEL" in m
               for m in msgs), msgs


def test_config_discipline_fires_on_unregistered_var(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/mod.py": (
        "from gridllm_tpu.utils.config import env_str\n"
        "X = env_str('GRIDLLM_NO_SUCH_KNOB')\n"
    )})
    msgs = [f.message for f in findings_for(root, "config-discipline")]
    assert any("GRIDLLM_NO_SUCH_KNOB" in m and "ENV_VARS" in m
               for m in msgs), msgs


def test_config_discipline_fires_on_readme_drift(tmp_path):
    # README documents a var the registry does not know
    root = make_repo(tmp_path, {"README.md": _full_env_table() + (
        "\n| `GRIDLLM_GHOST_KNOB` | `1` | not registered anywhere |\n")})
    msgs = [f.message for f in findings_for(root, "config-discipline")]
    assert any("GRIDLLM_GHOST_KNOB" in m and "not registered" in m
               for m in msgs), msgs


def test_config_discipline_fires_on_default_drift(tmp_path):
    # README documents a default that disagrees with the registry
    table = _full_env_table().replace(
        "| `GRIDLLM_MAX_BATCH_SLOTS` | `8` |",
        "| `GRIDLLM_MAX_BATCH_SLOTS` | `16` |")
    assert "| `16` |" in table, "fixture assumes the registry default is 8"
    root = make_repo(tmp_path, {"README.md": table + "\n"})
    msgs = [f.message for f in findings_for(root, "config-discipline")]
    assert any("GRIDLLM_MAX_BATCH_SLOTS" in m and "default" in m
               for m in msgs), msgs


def test_lock_discipline_fires_on_unguarded_mutation(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/engine_like.py": (
        "class E:\n"
        "    def bad(self, slot):\n"
        "        self.alloc.free(slot)\n"
        "    def good(self, slot):\n"
        "        with self._alloc_lock:\n"
        "            self.alloc.free(slot)\n"
    )})
    fs = findings_for(root, "lock-discipline")
    assert len(fs) == 1 and fs[0].line == 3, fs


def test_lock_discipline_fires_on_order_inversion(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/engine_like.py": (
        "class E:\n"
        "    def inverted(self):\n"
        "        with self.dispatch_lock:\n"
        "            with self._alloc_lock:\n"
        "                pass\n"
        "    def single_stmt_inverted(self):\n"
        "        with self.dispatch_lock, self._alloc_lock:\n"
        "            pass\n"
        "    def correct(self):\n"
        "        with self._alloc_lock, self.dispatch_lock:\n"
        "            pass\n"
        "    def also_correct(self):\n"
        "        with self._alloc_lock:\n"
        "            with self.dispatch_lock:\n"
        "                pass\n"
    )})
    fs = findings_for(root, "lock-discipline")
    assert sorted(f.line for f in fs) == [4, 7], fs


def test_dashboard_drift_fires_on_phantom_panel_metric(tmp_path):
    root = make_repo(tmp_path, {
        "gridllm_tpu/m.py": (
            "from gridllm_tpu.obs import default_registry\n"
            "C = default_registry().counter(\n"
            "    'gridllm_real_total', 'Real.', ('model',))\n"
        ),
        "deploy/grafana-dashboard.json":
            '{"expr": "rate(gridllm_phantom_total[5m])"}',
        "README.md": _full_env_table() +
            "\n| `gridllm_real_total` (model) | real |\n",
    })
    msgs = [f.message for f in findings_for(root, "dashboard-drift")]
    assert any("gridllm_phantom_total" in m and "no code registers" in m
               for m in msgs), msgs


def test_dashboard_drift_fires_on_undocumented_metric(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/m.py": (
        "from gridllm_tpu.obs import default_registry\n"
        "C = default_registry().counter(\n"
        "    'gridllm_undocumented_total', 'Help.', ('model',))\n"
    )})
    msgs = [f.message for f in findings_for(root, "dashboard-drift")]
    assert any("gridllm_undocumented_total" in m
               and "README metrics table" in m for m in msgs), msgs


def test_dashboard_drift_fires_on_wrong_suffix(tmp_path):
    # a counter referenced with a histogram-only series suffix
    root = make_repo(tmp_path, {
        "gridllm_tpu/m.py": (
            "from gridllm_tpu.obs import default_registry\n"
            "C = default_registry().counter(\n"
            "    'gridllm_real_total', 'Real.', ('model',))\n"
        ),
        "deploy/prometheus-alerts.yml":
            "expr: gridllm_real_total_bucket > 0",
        "README.md": _full_env_table() +
            "\n| `gridllm_real_total` (model) | real |\n",
    })
    msgs = [f.message for f in findings_for(root, "dashboard-drift")]
    assert any("gridllm_real_total_bucket" in m for m in msgs), msgs


def test_dashboard_drift_fires_on_bare_histogram_family_in_query(tmp_path):
    # a Grafana QUERY naming the family references a series that never
    # exists (only _bucket/_sum/_count are exported) — flat-panel drift.
    # The same family name in prose (title) stays legal.
    root = make_repo(tmp_path, {
        "gridllm_tpu/m.py": (
            "from gridllm_tpu.obs import default_registry\n"
            "H = default_registry().histogram(\n"
            "    'gridllm_lat_seconds', 'Latency.')\n"
        ),
        "deploy/grafana-dashboard.json": (
            '{"title": "gridllm_lat_seconds p95",\n'
            ' "expr": "histogram_quantile(0.95, rate(gridllm_lat_seconds[5m]))"}'
        ),
        "README.md": _full_env_table() +
            "\n| `gridllm_lat_seconds` | latency |\n",
    })
    fs = [f for f in findings_for(root, "dashboard-drift")
          if "histogram family" in f.message]
    assert len(fs) == 1 and fs[0].line == 2, fs


def test_jit_discipline_fires_on_unwrapped_and_dirty_bodies(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/engine/engine.py": (
        "import jax\n"
        "from functools import partial\n"
        "class InferenceEngine:\n"
        "    def _build_fns(self):\n"
        "        @partial(jax.jit, static_argnames=('k',))\n"
        "        def unwrapped_fn(params, toks, k):\n"
        "            if k:\n"                      # static: fine
        "                n = toks.sum().item()\n"  # .item() inside jit
        "            if toks > 0:\n"               # traced branch
        "                pass\n"
        "            if params is None:\n"         # structure check: fine
        "                pass\n"
        "            return toks\n"
        "        self._fn = jax.jit(lambda p: p)\n"  # inline, unwrapped
        "        @partial(jax.jit)\n"
        "        def wrapped_fn(x):\n"
        "            return x\n"
        "        self._ok = self.perf.wrap('ok', wrapped_fn)\n"
    )})
    msgs = [f.message for f in findings_for(root, "jit-discipline")]
    assert any("unwrapped_fn" in m and "perf.wrap" in m for m in msgs), msgs
    assert any(".item()" in m for m in msgs), msgs
    assert any("traced value" in m and "toks" in m for m in msgs), msgs
    assert any("inline jax.jit" in m for m in msgs), msgs
    assert not any(m.startswith("jitted function wrapped_fn(")
                   for m in msgs), msgs
    assert not any("params" in m and "traced" in m for m in msgs), msgs


def test_span_pairing_fires_on_leaky_span(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/svc.py": (
        "class S:\n"
        "    def leaky(self, rid):\n"
        "        span = self.tracer.begin(rid, 'x')\n"
        "        self.work()\n"
        "        self.tracer.end(span)\n"        # not in a finally
        "    def dropped(self, rid):\n"
        "        self.tracer.begin(rid, 'x')\n"  # discarded outright
        "    def safe(self, rid):\n"
        "        span = self.tracer.begin(rid, 'x')\n"
        "        try:\n"
        "            self.work()\n"
        "        finally:\n"
        "            self.tracer.end(span)\n"
        "    def handoff(self, rid):\n"
        "        self._spans[rid] = self.tracer.begin(rid, 'x')\n"
    )})
    fs = findings_for(root, "span-pairing")
    assert sorted(f.line for f in fs) == [3, 7], fs


def test_span_pairing_fires_when_try_does_not_cover_begin(tmp_path):
    # an end()-in-finally elsewhere in the function must not count when a
    # statement between begin() and the try can raise with the span open
    root = make_repo(tmp_path, {"gridllm_tpu/svc.py": (
        "class S:\n"
        "    def gap(self, rid):\n"
        "        span = self.tracer.begin(rid, 'x')\n"
        "        self.prep()\n"              # raises -> span leaks
        "        try:\n"
        "            self.work()\n"
        "        finally:\n"
        "            self.tracer.end(span)\n"
        "    def begin_inside_try(self, rid):\n"
        "        try:\n"
        "            span = self.tracer.begin(rid, 'x')\n"
        "            self.work()\n"
        "        finally:\n"
        "            self.tracer.end(span)\n"
    )})
    fs = findings_for(root, "span-pairing")
    assert sorted(f.line for f in fs) == [3], fs


def test_config_discipline_other_tables_do_not_satisfy_doc_check(tmp_path):
    # drop one var's Configuration-table row but mention it in another
    # markdown table: the doc check must still fire
    table = _full_env_table()
    lines = [l for l in table.splitlines() if "GRIDLLM_PALLAS" not in l]
    readme = "\n".join(lines) + (
        "\n\n## Metrics\n"
        "| `gridllm_kernel_dispatch_total` | per GRIDLLM_PALLAS policy |\n")
    root = make_repo(tmp_path, {"README.md": readme})
    msgs = [f.message for f in findings_for(root, "config-discipline")]
    assert any("GRIDLLM_PALLAS" in m and "missing from the README" in m
               for m in msgs), msgs


def test_metric_hygiene_audits_keyword_labelnames(tmp_path):
    root = make_repo(tmp_path, {
        "gridllm_tpu/m.py": (
            "from gridllm_tpu.obs import default_registry\n"
            "A = default_registry().counter(\n"
            "    'gridllm_kw_total', 'Kw.', labelnames=('request_id',))\n"
            "B = default_registry().counter(\n"
            "    'gridllm_splat_total', 'Splat.', **extra)\n"
        ),
        "README.md": _full_env_table() +
            "\n| `gridllm_kw_total` `gridllm_splat_total` | seeded |\n",
    })
    msgs = [f.message for f in findings_for(root, "metric-hygiene")]
    assert any("gridllm_kw_total" in m and "request_id" in m
               for m in msgs), msgs
    assert any("gridllm_splat_total" in m and "audited" in m
               for m in msgs), msgs


def test_metric_hygiene_fires_on_bad_name_label_help(tmp_path):
    root = make_repo(tmp_path, {
        "gridllm_tpu/m.py": (
            "from gridllm_tpu.obs import default_registry\n"
            "A = default_registry().counter(\n"
            "    'BadName_total', 'Bad name.')\n"
            "B = default_registry().counter(\n"
            "    'gridllm_leaky_total', 'Bad label.', ('job_id',))\n"
            "C = default_registry().counter(\n"
            "    'gridllm_helpless_total', '')\n"
        ),
        "README.md": _full_env_table() +
            "\n| `BadName_total` `gridllm_leaky_total` "
            "`gridllm_helpless_total` | seeded |\n",
    })
    msgs = [f.message for f in findings_for(root, "metric-hygiene")]
    assert any("BadName_total" in m and "naming" in m for m in msgs), msgs
    assert any("job_id" in m for m in msgs), msgs
    assert any("gridllm_helpless_total" in m and "help" in m
               for m in msgs), msgs


def test_metric_hygiene_confines_tenant_labels_to_usage_ledger(tmp_path):
    # ISSUE 16: a `tenant` label is legal only in obs/usage.py (where the
    # TenantLRU bounds its cardinality); the identical registration in any
    # other module must fire
    root = make_repo(tmp_path, {
        "gridllm_tpu/rogue.py": (
            "from gridllm_tpu.obs import default_registry\n"
            "A = default_registry().counter(\n"
            "    'gridllm_rogue_total', 'Rogue.', ('tenant', 'model'))\n"
        ),
        "gridllm_tpu/obs/usage.py": (
            "from gridllm_tpu.obs import default_registry\n"
            "B = default_registry().counter(\n"
            "    'gridllm_ledger_total', 'Ledger.', ('tenant', 'model'))\n"
        ),
        "README.md": _full_env_table() +
            "\n| `gridllm_rogue_total` `gridllm_ledger_total` | seeded |\n",
    })
    msgs = [f.message for f in findings_for(root, "metric-hygiene")]
    assert any("gridllm_rogue_total" in m and "tenant" in m
               for m in msgs), msgs
    assert not any("gridllm_ledger_total" in m for m in msgs), msgs


# -- channel-discipline (ISSUE 13) ------------------------------------------

# a minimal bus/base.py channel registry for fixture repos: two families
# (one fixed, one parameterized), registry-derived durable_channel
_FIXTURE_BUS = """\
CHANNELS = {}


def register_channel(family, **kw):
    CHANNELS[family] = kw


CH_PING = "svc:ping"


def box_channel(box_id):
    return f"svc:box:{box_id}"


def durable_channel(channel):
    return channel in CHANNELS


register_channel(
    "svc:ping", pattern="svc:ping", payload="keys", keys=("a", "b"),
    durable=False, publishers=("gridllm_tpu/pub.py",),
    subscribers=("gridllm_tpu/sub.py",), helper="CH_PING",
    description="fixture fixed channel")
register_channel(
    "svc:box", pattern="svc:box:{box_id}", payload="keys", keys=("x",),
    durable=True, publishers=("gridllm_tpu/pub.py",),
    subscribers=("gridllm_tpu/sub.py",), helper="box_channel",
    description="fixture parameterized channel")
"""

_FIXTURE_CHANNEL_TABLE = (
    "\n## Bus channels\n\n"
    "| Channel | Durable | Payload | Who |\n|---|---|---|---|\n"
    "| `svc:ping` | no | `keys` | pub → sub |\n"
    "| `svc:box:{box_id}` | yes | `keys` | pub → sub |\n")


def _channel_repo(tmp_path, **overrides):
    files = {
        "gridllm_tpu/bus/base.py": _FIXTURE_BUS,
        "gridllm_tpu/pub.py": (
            "import json\n"
            "from gridllm_tpu.bus.base import CH_PING, box_channel\n"
            "async def go(bus):\n"
            "    await bus.publish(CH_PING, json.dumps({'a': 1, 'b': 2}))\n"
            "    await bus.publish(box_channel('1'), json.dumps({'x': 1}))\n"
        ),
        "gridllm_tpu/sub.py": (
            "from gridllm_tpu.bus.base import CH_PING, box_channel\n"
            "async def listen(bus, h):\n"
            "    await bus.subscribe(CH_PING, h)\n"
            "    await bus.subscribe(box_channel('1'), h)\n"
        ),
        "README.md": _full_env_table() + _FIXTURE_CHANNEL_TABLE,
    }
    files.update(overrides)
    return make_repo(tmp_path, files)


def test_channel_discipline_clean_fixture(tmp_path):
    root = _channel_repo(tmp_path)
    assert findings_for(root, "channel-discipline") == []


def test_channel_discipline_fires_on_raw_literal_and_fstring(tmp_path):
    root = _channel_repo(tmp_path, **{"gridllm_tpu/pub.py": (
        "import json\n"
        "from gridllm_tpu.bus.base import CH_PING, box_channel\n"
        "async def go(bus, rid):\n"
        "    await bus.publish(CH_PING, json.dumps({'a': 1, 'b': 2}))\n"
        "    await bus.publish(box_channel('1'), json.dumps({'x': 1}))\n"
        "    await bus.publish('svc:ping', '{}')\n"
        "    await bus.subscribe(f'svc:box:{rid}', go)\n"
    )})
    msgs = [f.message for f in findings_for(root, "channel-discipline")]
    assert any("raw channel literal 'svc:ping'" in m for m in msgs), msgs
    assert any("f-string channel name" in m for m in msgs), msgs


def test_channel_discipline_fires_on_payload_key_drift(tmp_path):
    # publishes an undeclared key 'c' and never sends declared key 'b'
    root = _channel_repo(tmp_path, **{"gridllm_tpu/pub.py": (
        "import json\n"
        "from gridllm_tpu.bus.base import CH_PING, box_channel\n"
        "async def go(bus):\n"
        "    await bus.publish(CH_PING, json.dumps({'a': 1, 'c': 2}))\n"
        "    await bus.publish(box_channel('1'), json.dumps({'x': 1}))\n"
    )})
    msgs = [f.message for f in findings_for(root, "channel-discipline")]
    assert any("payload key 'c'" in m and "not declared" in m
               for m in msgs), msgs
    assert any("declares payload key 'b'" in m
               and "no publisher ever sends" in m for m in msgs), msgs


def test_channel_discipline_fires_on_undeclared_direction(tmp_path):
    # sub.py publishes on a family it is only declared to subscribe to
    root = _channel_repo(tmp_path, **{"gridllm_tpu/sub.py": (
        "import json\n"
        "from gridllm_tpu.bus.base import CH_PING, box_channel\n"
        "async def listen(bus, h):\n"
        "    await bus.subscribe(CH_PING, h)\n"
        "    await bus.subscribe(box_channel('1'), h)\n"
        "    await bus.publish(CH_PING, json.dumps({'a': 1, 'b': 2}))\n"
    )})
    msgs = [f.message for f in findings_for(root, "channel-discipline")]
    assert any("not a declared publisher" in m for m in msgs), msgs


def test_channel_discipline_fires_on_hardcoded_durability(tmp_path):
    bus = _FIXTURE_BUS.replace(
        "def durable_channel(channel):\n    return channel in CHANNELS",
        "def durable_channel(channel):\n"
        "    return channel in ('svc:box',)")
    root = _channel_repo(tmp_path, **{"gridllm_tpu/bus/base.py": bus})
    msgs = [f.message for f in findings_for(root, "channel-discipline")]
    assert any("hardcodes channel name" in m and "derive" in m
               for m in msgs), msgs


def test_channel_discipline_fires_on_readme_table_drift(tmp_path):
    table = _FIXTURE_CHANNEL_TABLE.replace(
        "| `svc:box:{box_id}` | yes |", "| `svc:box:{box_id}` | no |")
    root = _channel_repo(
        tmp_path, **{"README.md": _full_env_table() + table})
    msgs = [f.message for f in findings_for(root, "channel-discipline")]
    assert any("durability" in m and "'no'" in m and "'yes'" in m
               for m in msgs), msgs
    # and a missing row is drift too
    root2 = _channel_repo(tmp_path / "r2", **{
        "README.md": _full_env_table() + _FIXTURE_CHANNEL_TABLE.replace(
            "| `svc:ping` | no | `keys` | pub → sub |\n", "")})
    msgs2 = [f.message for f in findings_for(root2, "channel-discipline")]
    assert any("'svc:ping'" in m and "missing from the README" in m
               for m in msgs2), msgs2
    # and so is the Publishers → subscribers column
    root3 = _channel_repo(tmp_path / "r3", **{
        "README.md": _full_env_table() + _FIXTURE_CHANNEL_TABLE.replace(
            "| `svc:ping` | no | `keys` | pub → sub |",
            "| `svc:ping` | no | `keys` | sub → pub |")})
    msgs3 = [f.message for f in findings_for(root3, "channel-discipline")]
    assert any("direction" in m and "sub → pub" in m for m in msgs3), msgs3


def test_channel_discipline_fires_on_helper_pattern_drift(tmp_path):
    bus = _FIXTURE_BUS.replace(
        'def box_channel(box_id):\n    return f"svc:box:{box_id}"',
        'def box_channel(box_id):\n    return f"svc:crate:{box_id}"')
    root = _channel_repo(tmp_path, **{"gridllm_tpu/bus/base.py": bus})
    msgs = [f.message for f in findings_for(root, "channel-discipline")]
    assert any("box_channel()" in m and "svc:crate" in m
               for m in msgs), msgs


# -- event-discipline (ISSUE 17) --------------------------------------------

# a minimal obs/timeline.py EVENTS registry for fixture repos
_FIXTURE_EVENTS = """\
EVENTS = {}


def register_event(name, **kw):
    EVENTS[name] = kw


register_event("svc.started", keys=("worker",),
               modules=("gridllm_tpu/svc.py",))
register_event("svc.stopped", keys=("reason", "worker"),
               modules=("gridllm_tpu/svc.py",))
"""

_FIXTURE_SVC = """\
class Svc:
    def __init__(self, flightrec, worker_id):
        self.flightrec = flightrec
        self.worker_id = worker_id

    def start(self):
        self.flightrec.record("svc", "started", worker=self.worker_id)

    def stop(self, reason):
        self.flightrec.record("svc", "stopped", worker=self.worker_id,
                              reason=reason)
"""

_FIXTURE_EVENT_TABLE = (
    "\n## Timeline events\n\n"
    "| Event | Payload keys | Emitted from |\n|---|---|---|\n"
    "| `svc.started` | `worker` | svc |\n"
    "| `svc.stopped` | `reason, worker` | svc |\n")


def _event_repo(tmp_path, **overrides):
    files = {
        "gridllm_tpu/obs/timeline.py": _FIXTURE_EVENTS,
        "gridllm_tpu/svc.py": _FIXTURE_SVC,
        "README.md": _full_env_table() + _FIXTURE_EVENT_TABLE,
    }
    files.update(overrides)
    return make_repo(tmp_path, files)


def test_event_discipline_clean_fixture(tmp_path):
    root = _event_repo(tmp_path)
    assert findings_for(root, "event-discipline") == []


def test_event_discipline_fires_on_undeclared_event_and_key(tmp_path):
    root = _event_repo(tmp_path, **{"gridllm_tpu/svc.py": _FIXTURE_SVC + (
        "\n"
        "    def crash(self):\n"
        "        self.flightrec.record('svc', 'crashed', worker='w')\n"
        "        self.flightrec.record('svc', 'started', worker='w',\n"
        "                              extra=1)\n"
    )})
    msgs = [f.message for f in findings_for(root, "event-discipline")]
    assert any("'svc.crashed'" in m and "not declared" in m
               for m in msgs), msgs
    assert any("payload key 'extra'" in m for m in msgs), msgs


def test_event_discipline_fires_on_unresolvable_and_splat(tmp_path):
    root = _event_repo(tmp_path, **{"gridllm_tpu/svc.py": _FIXTURE_SVC + (
        "\n"
        "    def weird(self, ev, fields):\n"
        "        self.flightrec.record('svc', ev)\n"
        "        self.flightrec.record('svc', 'started', **fields)\n"
    )})
    msgs = [f.message for f in findings_for(root, "event-discipline")]
    assert any("statically unresolvable" in m for m in msgs), msgs
    assert any("dynamic **fields" in m and "open_keys" in m
               for m in msgs), msgs


def test_event_discipline_fires_on_dead_declaration(tmp_path):
    events = _FIXTURE_EVENTS + (
        'register_event("svc.ghost", keys=("worker",),\n'
        '               modules=("gridllm_tpu/svc.py",))\n')
    table = _FIXTURE_EVENT_TABLE.replace(
        "| `svc.stopped`",
        "| `svc.ghost` | `worker` | svc |\n| `svc.stopped`")
    root = _event_repo(tmp_path, **{
        "gridllm_tpu/obs/timeline.py": events,
        "README.md": _full_env_table() + table})
    msgs = [f.message for f in findings_for(root, "event-discipline")]
    assert any("'svc.ghost'" in m and "no module ever emits" in m
               for m in msgs), msgs


def test_event_discipline_fires_on_readme_table_drift(tmp_path):
    table = _FIXTURE_EVENT_TABLE.replace(
        "| `svc.started` | `worker` |", "| `svc.started` | `job` |")
    root = _event_repo(
        tmp_path, **{"README.md": _full_env_table() + table})
    msgs = [f.message for f in findings_for(root, "event-discipline")]
    assert any("'svc.started'" in m and "keys" in m for m in msgs), msgs
    # a missing row is drift too
    root2 = _event_repo(tmp_path / "r2", **{
        "README.md": _full_env_table() + _FIXTURE_EVENT_TABLE.replace(
            "| `svc.started` | `worker` | svc |\n", "")})
    msgs2 = [f.message for f in findings_for(root2, "event-discipline")]
    assert any("'svc.started'" in m and "missing from the README" in m
               for m in msgs2), msgs2


def test_event_discipline_resolves_emit_event_envelope(tmp_path):
    # emit_event envelope attrs (member/request_id/stamp) are not payload
    # keys; a payload kwarg outside the registry still fires
    events = _FIXTURE_EVENTS + (
        'register_event("svc.edge", keys=("channel",),\n'
        '               modules=("gridllm_tpu/edge.py",))\n')
    table = _FIXTURE_EVENT_TABLE + "| `svc.edge` | `channel` | edge |\n"
    root = _event_repo(tmp_path, **{
        "gridllm_tpu/obs/timeline.py": events,
        "gridllm_tpu/edge.py": (
            "from gridllm_tpu.obs.timeline import emit_event\n"
            "def send(rid, stamp):\n"
            "    emit_event('svc.edge', member='m', request_id=rid,\n"
            "               stamp=stamp, channel='c')\n"),
        "README.md": _full_env_table() + table})
    assert findings_for(root, "event-discipline") == []
    root2 = _event_repo(tmp_path / "r2", **{
        "gridllm_tpu/obs/timeline.py": events,
        "gridllm_tpu/edge.py": (
            "from gridllm_tpu.obs.timeline import emit_event\n"
            "def send(rid):\n"
            "    emit_event('svc.edge', request_id=rid, channel='c',\n"
            "               shard=3)\n"),
        "README.md": _full_env_table() + table})
    msgs = [f.message for f in findings_for(root2, "event-discipline")]
    assert any("payload key 'shard'" in m for m in msgs), msgs


# -- async-discipline (ISSUE 13) --------------------------------------------

def test_async_discipline_fires_on_blocking_calls(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/gateway/svc.py": (
        "import time, subprocess, asyncio\n"
        "async def bad(my_lock, path):\n"
        "    time.sleep(1)\n"                        # 3
        "    subprocess.run(['x'])\n"                # 4
        "    open('f').read()\n"                     # 5
        "    path.read_text()\n"                     # 6
        "    my_lock.acquire()\n"                    # 7
        "    my_lock.acquire(True)\n"                # 8: still unbounded
        "    time.sleep(0)  # async-ok\n"            # waived
        "    my_lock.acquire(timeout=1)\n"           # bounded: fine
        "    my_lock.acquire(False)\n"               # non-blocking: fine
        "    my_lock.acquire(blocking=False)\n"      # non-blocking: fine
        "    await asyncio.to_thread(time.sleep, 1)\n"  # routed: fine
        "def sync_helper():\n"
        "    time.sleep(1)\n"                        # sync def: fine
        "async def uses_closure():\n"
        "    def thread_target():\n"
        "        time.sleep(1)\n"                    # nested sync: fine
        "    return thread_target\n"
    )})
    fs = findings_for(root, "async-discipline")
    assert sorted(f.line for f in fs) == [3, 4, 5, 6, 7, 8], fs
    msgs = [f.message for f in fs]
    assert any("asyncio.sleep" in m for m in msgs), msgs
    assert any("lock.acquire" in m for m in msgs), msgs


def test_async_discipline_ignores_other_subsystems(tmp_path):
    # models/ops code is sync-world; the rule scopes to the async planes
    root = make_repo(tmp_path, {"gridllm_tpu/ops/helper.py": (
        "import time\n"
        "async def odd_but_out_of_scope():\n"
        "    time.sleep(1)\n"
    )})
    assert findings_for(root, "async-discipline") == []


# -- fault-coverage (ISSUE 13) ----------------------------------------------

_FIXTURE_FAULTS = (
    'SITES = (\n    "svc.alive",\n    "svc.dead",\n)\n'
    "def check(site):\n    return False\n"
    "def inject(site):\n    check(site)\n"
)

_FIXTURE_FAULT_TABLE = (
    "\n## Faults\n\n| site | effect |\n|---|---|\n"
    "| `svc.alive` | fixture |\n| `svc.dead` | fixture |\n")


def test_fault_coverage_fires_on_dead_and_unregistered_sites(tmp_path):
    root = make_repo(tmp_path, {
        "gridllm_tpu/faults.py": _FIXTURE_FAULTS,
        "gridllm_tpu/bus/mod.py": (
            "from gridllm_tpu import faults\n"
            "def f():\n"
            "    faults.check('svc.alive')\n"
            "    faults.inject('svc.ghost')\n"
        ),
        "README.md": _full_env_table() + _FIXTURE_FAULT_TABLE,
    })
    msgs = [f.message for f in findings_for(root, "fault-coverage")]
    assert any("'svc.dead'" in m and "no live inject()/check()" in m
               for m in msgs), msgs
    assert any("'svc.ghost'" in m and "not registered" in m
               for m in msgs), msgs


def test_fault_coverage_fires_on_nonliteral_site_and_readme_drift(tmp_path):
    root = make_repo(tmp_path, {
        "gridllm_tpu/faults.py": _FIXTURE_FAULTS,
        "gridllm_tpu/bus/mod.py": (
            "from gridllm_tpu import faults\n"
            "def f(site):\n"
            "    faults.check(site)\n"
            "    faults.check('svc.alive')\n"
            "    faults.check('svc.dead')\n"
        ),
        # table documents a ghost site and misses svc.dead
        "README.md": _full_env_table() +
            "\n## Faults\n\n| site | effect |\n|---|---|\n"
            "| `svc.alive` | fixture |\n| `svc.ghost` | fixture |\n",
    })
    msgs = [f.message for f in findings_for(root, "fault-coverage")]
    assert any("literal site name" in m for m in msgs), msgs
    assert any("'svc.ghost'" in m and "not registered" in m
               for m in msgs), msgs
    assert any("'svc.dead'" in m and "missing from the README" in m
               for m in msgs), msgs


def test_fault_coverage_fires_on_uncovered_critical_subsystem(tmp_path):
    # a bus/ directory exists but carries no live site
    root = make_repo(tmp_path, {
        "gridllm_tpu/faults.py": _FIXTURE_FAULTS,
        "gridllm_tpu/bus/mod.py": "def quiet():\n    pass\n",
        "gridllm_tpu/other.py": (
            "from gridllm_tpu import faults\n"
            "def f():\n"
            "    faults.check('svc.alive')\n"
            "    faults.check('svc.dead')\n"
        ),
        "README.md": _full_env_table() + _FIXTURE_FAULT_TABLE,
    })
    msgs = [f.message for f in findings_for(root, "fault-coverage")]
    assert any("critical subsystem 'bus'" in m for m in msgs), msgs


def test_new_rules_cli_rule_filtering(tmp_path):
    """--rule runs exactly the selected new rules (ISSUE 13 satellite):
    one seeded violation each, reported under the right rule name."""
    root = make_repo(tmp_path, {
        "gridllm_tpu/faults.py": _FIXTURE_FAULTS,
        "gridllm_tpu/gateway/svc.py": (
            "import time\n"
            "async def bad(bus):\n"
            "    time.sleep(1)\n"
            "    await bus.publish('raw:chan', '{}')\n"
        ),
        "gridllm_tpu/bus/mod.py": (
            "from gridllm_tpu import faults\n"
            "def f():\n    faults.check('svc.alive')\n"
        ),
        "README.md": _full_env_table() + _FIXTURE_FAULT_TABLE,
    })
    proc = subprocess.run(
        [sys.executable, "-m", "gridllm_tpu.analysis", "--json",
         "--rule", "channel-discipline", "--rule", "async-discipline",
         "--rule", "fault-coverage", "--root", str(root)],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    fired = {f["rule"] for f in payload["findings"]}
    assert fired == {"channel-discipline", "async-discipline",
                     "fault-coverage"}, payload["findings"]


# -- kernel-parity (gridcheck v3) -------------------------------------------

# a self-consistent fixture kernel surface: registry + kernel module +
# reference + test + README table; individual tests then break one leg
_FIXTURE_KERNEL_REGISTRY = (
    "KERNELS = (\n"
    "    KernelSpec(\n"
    "        name='my_kernel', reference='attention:my_ref',\n"
    "        dispatch='my_op', rtol=1e-2, atol=1e-2,\n"
    "        test='tests/test_my.py::test_my_kernel_matches_ref',\n"
    "        description='fixture'),\n"
    ")\n"
    "EXTRA_DISPATCH_LABELS = {}\n"
)
_FIXTURE_KERNEL_FILES = {
    "gridllm_tpu/ops/kernels.py": _FIXTURE_KERNEL_REGISTRY,
    "gridllm_tpu/ops/pallas_kernels.py": (
        "from jax.experimental import pallas as pl\n"
        "def my_kernel(x):\n"
        "    return pl.pallas_call(None)(x)\n"
    ),
    "gridllm_tpu/ops/attention.py": (
        "from gridllm_tpu.ops.kvcache import record_kernel_path\n"
        "def my_ref(x):\n"
        "    return x\n"
        "def dispatch(x):\n"
        "    record_kernel_path('my_op', True)\n"
        "    return x\n"
    ),
    "tests/test_my.py": (
        "def test_my_kernel_matches_ref():\n"
        "    pass\n"
    ),
}
_FIXTURE_KERNEL_README = (
    "\n## Kernels\n\n"
    "| Kernel | Reference | Dispatch | Tolerance | Test |\n"
    "|---|---|---|---|---|\n"
    "| `my_kernel` | `my_ref` | `my_op` | `1e-2 / 1e-2` | "
    "`tests/test_my.py::test_my_kernel_matches_ref` |\n"
)


def _kernel_repo(tmp_path, **overrides):
    files = {**_FIXTURE_KERNEL_FILES,
             "README.md": _full_env_table() + _FIXTURE_KERNEL_README}
    files.update(overrides)
    return make_repo(tmp_path, files)


def test_kernel_parity_clean_fixture(tmp_path):
    root = _kernel_repo(tmp_path)
    assert findings_for(root, "kernel-parity") == []


def test_kernel_parity_fires_on_unregistered_pallas_call(tmp_path):
    # fallback direction (no fixture registry): the imported KERNELS is
    # the source of truth and the stray pallas_call is flagged
    root = make_repo(tmp_path, {"gridllm_tpu/ops/rogue.py": (
        "from jax.experimental import pallas as pl\n"
        "def rogue_kernel(x):\n"
        "    return pl.pallas_call(None)(x)\n"
    )})
    msgs = [f.message for f in findings_for(root, "kernel-parity")]
    assert any("rogue_kernel" in m and "not a registered kernel" in m
               for m in msgs), msgs


def test_kernel_parity_fires_on_unregistered_call_with_registry(tmp_path):
    root = _kernel_repo(tmp_path, **{
        "gridllm_tpu/ops/pallas_kernels.py":
            _FIXTURE_KERNEL_FILES["gridllm_tpu/ops/pallas_kernels.py"] + (
                "def stray(x):\n"
                "    return pl.pallas_call(None)(x)\n"),
    })
    msgs = [f.message for f in findings_for(root, "kernel-parity")]
    assert any("stray" in m and "not a registered kernel" in m
               for m in msgs), msgs


def test_kernel_parity_fires_on_stale_registry_row(tmp_path):
    # registered kernel whose entry fn lost its pallas_call (and one
    # that does not exist at all)
    root = _kernel_repo(tmp_path, **{
        "gridllm_tpu/ops/pallas_kernels.py": (
            "def my_kernel(x):\n"
            "    return x\n"),
    })
    msgs = [f.message for f in findings_for(root, "kernel-parity")]
    assert any("no pl.pallas_call" in m for m in msgs), msgs


def test_kernel_parity_fires_on_missing_reference_and_test(tmp_path):
    root = _kernel_repo(tmp_path, **{
        "gridllm_tpu/ops/attention.py": (
            "from gridllm_tpu.ops.kvcache import record_kernel_path\n"
            "def dispatch(x):\n"
            "    record_kernel_path('my_op', True)\n"
            "    return x\n"),
        "tests/test_my.py": "def test_something_else():\n    pass\n",
    })
    msgs = [f.message for f in findings_for(root, "kernel-parity")]
    assert any("does not resolve to a function" in m for m in msgs), msgs
    assert any("not found in tests/test_my.py" in m for m in msgs), msgs


def test_kernel_parity_fires_on_dispatch_label_drift_both_ways(tmp_path):
    # recorded label the registry doesn't know + declared label nobody
    # records
    root = _kernel_repo(tmp_path, **{
        "gridllm_tpu/ops/attention.py": (
            "from gridllm_tpu.ops.kvcache import record_kernel_path\n"
            "def my_ref(x):\n"
            "    return x\n"
            "def dispatch(x):\n"
            "    record_kernel_path('mystery_op', True)\n"
            "    return x\n"),
    })
    msgs = [f.message for f in findings_for(root, "kernel-parity")]
    assert any("'mystery_op' is not declared" in m for m in msgs), msgs
    assert any("'my_op' is never recorded" in m for m in msgs), msgs


def test_kernel_parity_fires_on_readme_drift_both_ways(tmp_path):
    phantom = (
        "\n## Kernels\n\n"
        "| Kernel | Reference | Dispatch | Tolerance | Test |\n"
        "|---|---|---|---|---|\n"
        "| `ghost_kernel` | `x` | `y` | `1 / 1` | `t` |\n"
    )
    root = _kernel_repo(tmp_path,
                        **{"README.md": _full_env_table() + phantom})
    msgs = [f.message for f in findings_for(root, "kernel-parity")]
    assert any("ghost_kernel" in m and "not registered" in m
               for m in msgs), msgs
    assert any("'my_kernel' missing from the README" in m
               for m in msgs), msgs


def test_kernel_parity_fires_on_readme_cell_drift(tmp_path):
    wrong_tol = _FIXTURE_KERNEL_README.replace("`1e-2 / 1e-2`",
                                               "`5e-1 / 5e-1`")
    root = _kernel_repo(tmp_path,
                        **{"README.md": _full_env_table() + wrong_tol})
    msgs = [f.message for f in findings_for(root, "kernel-parity")]
    assert any("tolerance cell" in m for m in msgs), msgs
    # the Differential-test column is part of the contract too
    wrong_test = _FIXTURE_KERNEL_README.replace(
        "`tests/test_my.py::test_my_kernel_matches_ref`",
        "`tests/test_my.py::test_totally_wrong_name`")
    root2 = _kernel_repo(tmp_path / "t2",
                         **{"README.md": _full_env_table() + wrong_test})
    msgs2 = [f.message for f in findings_for(root2, "kernel-parity")]
    assert any("column 5" in m and "test_totally_wrong_name" in m
               for m in msgs2), msgs2


# -- dtype-discipline (gridcheck v3) ----------------------------------------

def test_dtype_discipline_fires_on_dtype_less_construction(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/ops/mod.py": (
        "import jax.numpy as jnp\n"
        "X = jnp.asarray([1, 2])\n"
        "Y = jnp.array([1.0])\n"
        "Z = jnp.asarray([3], jnp.int32)\n"
    )})
    msgs = [f.message for f in findings_for(root, "dtype-discipline")]
    assert sum("dtype-less" in m for m in msgs) == 2, msgs


def test_dtype_discipline_fires_on_unpinned_accumulation(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/ops/mod.py": (
        "import jax\nimport jax.numpy as jnp\n"
        "def f(a, b):\n"
        "    x = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))\n"
        "    y = jnp.einsum('ij,jk->ik', a, b)\n"
        "    return x + y\n"
    )})
    msgs = [f.message for f in findings_for(root, "dtype-discipline")]
    assert any("dot_general without preferred_element_type" in m
               for m in msgs), msgs
    assert any("einsum without precision" in m for m in msgs), msgs


def test_dtype_discipline_fires_on_unanchored_softmax(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/ops/mod.py": (
        "import jax.numpy as jnp\n"
        "def bad(x):\n"
        "    return jnp.exp(x - x.max())\n"
        "def good(x):\n"
        "    return jnp.exp(x.astype(jnp.float32))\n"
    )})
    msgs = [f.message for f in findings_for(root, "dtype-discipline")]
    assert any("bad() computes exp/softmax" in m for m in msgs), msgs
    assert not any("good()" in m for m in msgs), msgs


def test_dtype_discipline_fires_on_inline_sentinel(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/ops/mod.py": (
        "import jax.numpy as jnp\n"
        "NEG = -1e30\n"
        "ANN: float = -1e30\n"  # annotated module constant: also allowed
        "def f(x, mask):\n"
        "    return jnp.where(mask, x, -1e30)\n"
    )})
    findings = findings_for(root, "dtype-discipline")
    assert len(findings) == 1 and "inline mask sentinel" in \
        findings[0].message, findings
    assert findings[0].line == 5


def test_dtype_discipline_fires_on_unpaired_quantpages_data(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/ops/mod.py": (
        "from gridllm_tpu.ops.kvcache import QuantPages\n"
        "def bad(p):\n"
        "    if isinstance(p, QuantPages):\n"
        "        return p.data\n"
        "    return p\n"
        "def good(p):\n"
        "    if isinstance(p, QuantPages):\n"
        "        return p.data, p.scale\n"
        "    return p\n"
    )})
    msgs = [f.message for f in findings_for(root, "dtype-discipline")]
    assert any("bad() consumes QuantPages p.data" in m for m in msgs), msgs
    assert not any("good()" in m for m in msgs), msgs


def test_dtype_discipline_waiver(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/ops/mod.py": (
        "import jax.numpy as jnp\n"
        "X = jnp.asarray([1, 2])  # dtype-ok\n"
    )})
    assert findings_for(root, "dtype-discipline") == []


# -- host-sync-discipline (gridcheck v3) ------------------------------------

_FIXTURE_ENGINE_LOOPS = (
    "import numpy as np\n"
    "import jax\n"
    "class Engine:\n"
    "    def _ingest_block(self, out):\n"
    "        raw = np.asarray(jax.device_get(out))\n"
    "        return raw\n"
    "    def _dispatch_block(self, k):\n"
    "        return int(self.tokens[0])\n"
    "    def _fetch_oldest(self):\n"
    "        return np.asarray(self.x)  # sync-ok\n"
    "    def helper(self):\n"
    "        return self.y.item()\n"
)


def test_host_sync_fires_inside_loop_functions(tmp_path):
    root = make_repo(tmp_path,
                     {"gridllm_tpu/engine/engine.py": _FIXTURE_ENGINE_LOOPS})
    findings = findings_for(root, "host-sync-discipline")
    msgs = [f.message for f in findings]
    assert any("_ingest_block" in m and "np.asarray" in m for m in msgs), msgs
    assert any("_ingest_block" in m and "device_get" in m for m in msgs), msgs
    assert any("_dispatch_block" in m and "int()" in m for m in msgs), msgs
    # the declared sync point and the out-of-scope helper are exempt
    assert not any("inside _fetch_oldest()" in m for m in msgs), msgs
    assert not any("helper" in m for m in msgs), msgs


def test_host_sync_flags_stale_waiver(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/engine/engine.py": (
        "class Engine:\n"
        "    def _ingest_block(self, out):\n"
        "        x = 1  # sync-ok\n"
        "        return x\n"
    )})
    msgs = [f.message for f in findings_for(root, "host-sync-discipline")]
    assert any("stale waiver" in m for m in msgs), msgs


def test_host_sync_item_and_block_until_ready(tmp_path):
    root = make_repo(tmp_path, {"gridllm_tpu/engine/engine.py": (
        "class Engine:\n"
        "    def step(self):\n"
        "        v = self.out.item()\n"
        "        self.out.block_until_ready()\n"
        "        return v\n"
    )})
    msgs = [f.message for f in findings_for(root, "host-sync-discipline")]
    assert any(".item()" in m for m in msgs), msgs
    assert any("block_until_ready" in m for m in msgs), msgs


_FIXTURE_EAGER_PROGRAMS = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "import numpy as np\n"
    "from functools import partial\n"
    "class Engine:\n"
    "    def _build_fns(self):\n"
    "        self.x = jnp.int32(0)\n"
    "    def _dispatch_prefill(self, slot, ids, row_list):\n"
    "        @partial(jax.jit, donate_argnums=(0,))\n"
    "        def row_fn(sp, slot):\n"
    "            one = jnp.asarray(1)\n"
    "            return self.sampling.step.at[slot].set(one)\n"
    "        self.sampling = self.sampling.step.at[slot].set(0)\n"
    "        row = jnp.asarray(row_list, jnp.int32)\n"
    "        buf = np.zeros((4,), np.int32)\n"
    "        return self.fn(buf, row, jnp.int32(slot), jnp.bool_(True),\n"
    "                       np.int32(slot))\n"
    "    def _finish(self, slot):\n"
    "        self.active = self.active.at[slot].set(False)\n"
    "    def apply_plan_op(self, rec):\n"
    "        tok = jax.numpy.array(rec['tok'])\n"
    "        self.local = self.scratch.at[0].set(1)\n"
    "        return tok\n"
)


def test_host_sync_flags_eager_device_programs(tmp_path):
    """ISSUE 25: a jnp constructor on a host value or an .at[] write of
    device state in the loop functions is one program (or several) per
    call; the same code inside a nested jitted def is traced, not run."""
    root = make_repo(tmp_path, {
        "gridllm_tpu/engine/engine.py": _FIXTURE_EAGER_PROGRAMS})
    found = [(f.line, f.message)
             for f in findings_for(root, "host-sync-discipline")]
    lines = _FIXTURE_EAGER_PROGRAMS.splitlines()

    def hit(fn: str, what: str, source: str) -> bool:
        return any(fn in m and what in m and source in lines[ln - 1]
                   for ln, m in found)

    assert hit("_dispatch_prefill()", ".at[]", "step.at[slot].set(0)")
    assert hit("_dispatch_prefill()", "jnp.asarray()", "row_list")
    assert hit("_dispatch_prefill()", "jnp.int32()", "jnp.int32(slot)")
    assert hit("_dispatch_prefill()", "jnp.bool_()", "jnp.bool_(True)")
    assert hit("_finish()", ".at[]", "self.active.at[slot]")
    assert hit("apply_plan_op()", "jax.numpy.array()", "rec['tok']")
    # the nested jitted def's body, numpy buffers, a local array's .at[]
    # and functions outside the loops are all silent
    assert len(found) == 6, found


def test_host_sync_real_engine_runs_no_eager_programs():
    """The engine as committed: admission, finish and plan replay build
    their arguments on the host and write device rows in jitted programs."""
    assert findings_for(REPO_ROOT, "host-sync-discipline") == []


# -- helpers ----------------------------------------------------------------

def test_expand_braces():
    assert expand_braces("gridllm_a_total") == ["gridllm_a_total"]
    assert expand_braces("gridllm_kv_{used,free}") == [
        "gridllm_kv_used", "gridllm_kv_free"]
    assert expand_braces("gridllm_{a,b}_x_{c,d}") == [
        "gridllm_a_x_c", "gridllm_a_x_d", "gridllm_b_x_c", "gridllm_b_x_d"]


def test_readme_table_metrics_parses_rows_only():
    doc = ("prose gridllm_not_in_table\n"
           "| `gridllm_engine_kv_pages_{used,free}` (model) | pressure |\n")
    names = readme_table_metrics(doc)
    assert set(names) == {"gridllm_engine_kv_pages_used",
                          "gridllm_engine_kv_pages_free"}


# -- the actual gate --------------------------------------------------------

def test_self_run_is_clean():
    """Zero findings from exactly 13 registered rules over this repo:
    the invariant set the analyzer encodes HOLDS, and stays held — any
    regression fails here (and in the tier-1 static-analysis CI job)
    with a file:line reason. The rule-count pin makes a silently
    dropped rule module a failure too, not a quieter analyzer."""
    from gridllm_tpu.analysis import RULES, load_rules

    findings = run(REPO_ROOT)
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)
    load_rules()
    assert len(RULES) == 13, sorted(RULES)


def test_live_tree_has_seven_kernels_and_no_attention_switch():
    """The KERNELS registry names exactly the public functions of
    ops/pallas_kernels.py that launch a pl.pallas_call — seven since
    PR 42 (the delta rule's two), eight since PR 53 (the routed experts'
    grouped product), nine since PR 58 (its sorted regime), eleven since
    PR 61 (the state-space scan's two), with one
    paged-attention kernel among them — and no
    knob selects a second paged-attention path."""
    import ast

    from gridllm_tpu.ops.kernels import dispatch_labels, kernel_names
    from gridllm_tpu.utils.config import ENV_VARS

    tree = ast.parse(
        (REPO_ROOT / "gridllm_tpu/ops/pallas_kernels.py").read_text())
    launchers = {
        fn.name for fn in tree.body
        if isinstance(fn, ast.FunctionDef) and not fn.name.startswith("_")
        and any(isinstance(n, ast.Attribute) and n.attr == "pallas_call"
                for n in ast.walk(fn))
    }
    assert launchers == set(kernel_names()) == {
        "flash_prefill", "flash_prefill_streamed", "ragged_attention",
        "paged_write_decode", "paged_write_chunk", "gdn_chunk", "gdn_step",
        "grouped_experts", "grouped_experts_sorted", "ssd_chunk", "ssd_step",
    }
    assert {lb for lb in dispatch_labels() if lb.startswith("attention_")} \
        == {"attention_prefill", "attention_ragged"}
    # the switch that used to pick the dispatcher is gone, and no knob
    # names attention at all
    assert [n for n in ENV_VARS if "ATTN" in n or "ATTENTION" in n] == []


def test_cli_exit_codes_and_json(tmp_path):
    env_table = _full_env_table()
    bad = make_repo(tmp_path / "bad", {"gridllm_tpu/mod.py": (
        "import os\nX = os.environ.get('GRIDLLM_PALLAS')\n")})
    proc = subprocess.run(
        [sys.executable, "-m", "gridllm_tpu.analysis", "--strict", "--json",
         "--root", str(bad)],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["version"] == "gridllm-analysis/v1"
    assert any(f["rule"] == "config-discipline"
               for f in payload["findings"])

    clean = make_repo(tmp_path / "clean", {
        "README.md": env_table +
            "\n| `gridllm_ok_total` (model) | fixture metric |\n",
        "gridllm_tpu/engine/engine.py": (
            "from gridllm_tpu.obs import default_registry\n"
            "C = default_registry().counter(\n"
            "    'gridllm_ok_total', 'Fixture.', ('model',))\n"
        ),
    })
    proc = subprocess.run(
        [sys.executable, "-m", "gridllm_tpu.analysis", "--strict",
         "--root", str(clean)],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
