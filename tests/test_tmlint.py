"""tmlint: golden bad-example snippets (one per rule, each must fire
exactly its rule), the suppression grammar, and the repo-wide clean
run that is the acceptance gate — the whole tree must lint clean in
tier-1 forever (docs/static-analysis.md)."""

import os

import pytest

from tendermint_tpu.analysis import (
    FileContext,
    Project,
    all_rules,
    rule_names,
    run_lint,
)
from tendermint_tpu.analysis.rules_exposition import MetricsExposition

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SNIPPET = "tendermint_tpu/_tmlint_snippet.py"

# real files some rules resolve against (config fields, fault sites)
_CONFIG_REL = "tendermint_tpu/config/config.py"


def _ctx(rel, code):
    return FileContext(os.path.join(REPO, rel), rel, code)


def lint_snippet(code, rel=SNIPPET, extra=None):
    """Violations reported IN the snippet file (project-level noise a
    tiny synthetic project would produce — e.g. fault-site coverage —
    anchors elsewhere and is filtered by targets, exactly like
    --changed mode)."""
    files = {rel: code}
    files.update(extra or {})
    project = Project(REPO, [_ctx(r, c) for r, c in files.items()])
    return run_lint(project, targets={rel})


def assert_only(violations, rule, count=None):
    fired = sorted({v.rule for v in violations})
    assert fired == [rule], f"want exactly [{rule}], got {fired}: {violations}"
    if count is not None:
        assert len(violations) == count, violations


# -- golden bad examples, one per rule --------------------------------------


def test_golden_fault_site_coherence():
    code = (
        "from tendermint_tpu.utils import faultinject as faults\n"
        "def f(data):\n"
        "    faults.maybe('not.a.site')\n"
        "    faults.tear('pipeline.exec', data)\n"  # known site, not a TEAR_SITE
    )
    v = lint_snippet(code)
    assert_only(v, "fault-site-coherence", 2)
    assert "KNOWN_SITES" in v[0].message
    assert "TEAR_SITES" in v[1].message


def test_fault_site_tear_check_survives_import_alias():
    # `from ... import tear as t` must not dodge the TEAR_SITES check
    code = (
        "from tendermint_tpu.utils.faultinject import tear as t\n"
        "def f(data):\n"
        "    return t('pipeline.exec', data)\n"
    )
    v = lint_snippet(code)
    assert_only(v, "fault-site-coherence", 1)
    assert "TEAR_SITES" in v[0].message


def test_golden_fault_site_coverage_is_cross_file():
    # a project whose faultinject.py registers a site nobody calls:
    # the PROJECT-level check fires, anchored at the registry file
    registry_rel = "tendermint_tpu/utils/faultinject.py"
    real = open(os.path.join(REPO, registry_rel)).read()
    project = Project(REPO, [_ctx(registry_rel, real)])
    v = [x for x in run_lint(project) if x.rule == "fault-site-coherence"]
    # every KNOWN_SITES entry is uncovered in this one-file project
    assert len(v) >= 18 and all(x.path == registry_rel for x in v)


def test_golden_bound_method_truthiness():
    code = (
        "class Beacon:\n"
        "    def state(self):\n"
        "        return 'closed'\n"
        "def f():\n"
        "    b = Beacon()\n"
        "    if b.state != 'closed':\n"  # the PR7 round-8 bug, verbatim
        "        return 1\n"
        "    return 0\n"
    )
    v = lint_snippet(code)
    assert_only(v, "bound-method-truthiness", 1)
    assert "b.state()" in v[0].message


def test_truthiness_needs_type_evidence():
    # same shape on an UNKNOWN receiver type must not flag (the v1 FSM
    # compares a plain data attribute named `state` all day)
    code = (
        "def f(fsm):\n"
        "    if fsm.state != 'closed':\n"
        "        return 1\n"
        "    return 0\n"
    )
    assert lint_snippet(code) == []


def test_golden_task_retention():
    code = (
        "import asyncio\n"
        "async def f(coro):\n"
        "    asyncio.create_task(coro)\n"
    )
    v = lint_snippet(code)
    assert_only(v, "task-retention", 1)


def test_task_retention_bound_is_fine():
    code = (
        "import asyncio\n"
        "async def f(coro, bag):\n"
        "    t = asyncio.create_task(coro)\n"
        "    bag.add(t)\n"
        "    t.add_done_callback(bag.discard)\n"
        "    return t\n"
    )
    assert lint_snippet(code) == []


def test_golden_async_hygiene():
    code = (
        "import time\n"
        "import subprocess\n"
        "async def f(fut, in_queue):\n"
        "    time.sleep(1)\n"
        "    subprocess.run(['true'])\n"
        "    x = fut.result()\n"
        "    y = in_queue.get()\n"
        "    return x, y\n"
    )
    v = lint_snippet(code)
    assert_only(v, "async-hygiene", 4)


def test_async_hygiene_wrapped_queue_get_is_fine():
    # the pubsub select idiom: asyncio.Queue.get() handed to
    # ensure_future is a coroutine factory, not a blocking call
    code = (
        "import asyncio\n"
        "async def f(in_queue, bag):\n"
        "    t = asyncio.ensure_future(in_queue.get())\n"
        "    bag.add(t)\n"
        "    t.add_done_callback(bag.discard)\n"
        "    return await t\n"
    )
    assert lint_snippet(code) == []


def test_golden_no_permanent_latch():
    code = (
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.device_failed = False\n"
        "    def crash(self):\n"
        "        self.device_failed = True\n"
    )
    v = lint_snippet(code)
    assert_only(v, "no-permanent-latch", 1)


def test_latch_allowed_in_breaker_bearing_class():
    code = (
        "from tendermint_tpu.utils.watchdog import CircuitBreaker\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.breaker = CircuitBreaker('engine')\n"
        "        self.failed = False\n"
        "    def crash(self):\n"
        "        self.failed = True\n"
        "        self.breaker.record_failure()\n"
    )
    assert lint_snippet(code) == []


def test_golden_metrics_coherence():
    code = (
        "from tendermint_tpu.utils.metrics import Counter, Registry\n"
        "class BogusMetrics:\n"
        "    def __init__(self, registry=None, namespace='tendermint'):\n"
        "        r = registry or Registry()\n"
        "        sub = 'bogus'\n"
        "        self.x = r.register(Counter('things_total', 'X.', namespace, sub))\n"
        "        self.x.inc(-1)\n"
    )
    v = lint_snippet(code)
    assert_only(v, "metrics-coherence", 2)
    assert any("bogus_things_total" in x.message for x in v)  # undocumented family
    assert any("negative" in x.message for x in v)  # counter decrement


def test_golden_trace_coherence():
    code = (
        "from tendermint_tpu.utils import trace\n"
        "def f(h):\n"
        "    with trace.span('bogus.stage', height=h):\n"
        "        trace.instant('another.bogus_marker')\n"
    )
    v = lint_snippet(code)
    assert_only(v, "trace-coherence", 2)
    assert any("bogus.stage" in x.message for x in v)


def test_trace_coherence_documented_and_dynamic_names_pass():
    # a documented name passes; a dynamically-built name ("consensus."
    # + step) is out of static reach and is skipped; a tracer-OBJECT
    # receiver with a span-shaped literal is still checked; an
    # unrelated .span() call (re.Match.span) never fires
    code = (
        "from tendermint_tpu.utils import trace\n"
        "import re\n"
        "def f(t, step, m: 're.Match'):\n"
        "    with trace.span('merkle.root', leaves=2):\n"
        "        pass\n"
        "    with trace.span('consensus.' + step):\n"
        "        pass\n"
        "    t.instant('pipeline.fallback_serial')\n"
        "    return m.span(0)\n"
    )
    assert lint_snippet(code) == []
    # same tracer-object receiver, undocumented name: fires
    bad = (
        "def f(t):\n"
        "    t.instant('pipeline.some_new_marker')\n"
    )
    v = lint_snippet(bad)
    assert_only(v, "trace-coherence", 1)


@pytest.mark.parametrize(
    "imports, call",
    [
        ("from tendermint_tpu.utils.trace import span", "span"),
        ("from tendermint_tpu.utils.trace import span as sp", "sp"),
        ("from .utils.trace import instant", "instant"),
    ],
)
def test_trace_coherence_sees_the_bare_function_import(imports, call):
    """``from ...utils.trace import span`` then a bare ``span(...)``: an
    undocumented name fires whatever its shape, a documented one passes."""
    code = (
        f"{imports}\n"
        "def f():\n"
        f"    {call}('launch.stage')\n"
        f"    {call}('undocumented')\n"
    )
    v = lint_snippet(code)
    assert_only(v, "trace-coherence", 1)
    assert "undocumented" in v[0].message


def test_golden_flightrec_coherence():
    code = (
        "def f(self, h, r):\n"
        "    self.flightrec.record('bogus.event_kind', h, r)\n"
        "    self.flightrec.record('NotDotted', h, r)\n"
    )
    v = lint_snippet(code)
    assert_only(v, "flightrec-coherence", 2)
    assert any("bogus.event_kind" in x.message for x in v)  # undocumented
    assert any("NotDotted" in x.message for x in v)         # bad grammar


def test_flightrec_coherence_documented_and_other_receivers_pass():
    # a documented kind passes; a dynamically-built kind is out of
    # static reach; record() on NON-flightrec receivers (metrics
    # recorders, csv writers) never fires regardless of argument
    code = (
        "def f(self, cs, w, kind):\n"
        "    self.flightrec.record('vote.in', 1, 0, (1, 2, 'peer'))\n"
        "    cs.flightrec.record('height.commit', 5, 0, 3)\n"
        "    self.flightrec.record('breaker.' + kind, 1, 0)\n"
        "    w.record('totally.unknown_kind')\n"
    )
    assert lint_snippet(code) == []


def test_golden_jit_purity():
    code = (
        "import time\n"
        "import jax\n"
        "def kernel(x):\n"
        "    return x * time.time()\n"
        "compiled = jax.jit(kernel)\n"
    )
    v = lint_snippet(code)
    assert_only(v, "jit-purity", 1)
    assert "time.time()" in v[0].message


def test_jit_purity_resolves_across_modules():
    helper_rel = "tendermint_tpu/ops/_tmlint_kernels.py"
    helper = (
        "import random\n"
        "def kernel(x):\n"
        "    return x + random.random()\n"
    )
    code = (
        "import jax\n"
        "from tendermint_tpu.ops import _tmlint_kernels as ops_k\n"
        "compiled = jax.jit(ops_k.kernel)\n"
    )
    files = {SNIPPET: code, helper_rel: helper}
    project = Project(REPO, [_ctx(r, c) for r, c in files.items()])
    v = [x for x in run_lint(project, targets=set(files)) if x.rule == "jit-purity"]
    assert len(v) == 1 and v[0].path == helper_rel, v


def test_golden_config_coherence():
    config_src = open(os.path.join(REPO, _CONFIG_REL)).read()
    code = (
        "import os\n"
        "def f(config):\n"
        "    a = config.base.no_such_knob\n"
        "    b = os.environ.get('TM_DEFINITELY_NOT_DOCUMENTED')\n"
        "    return a, b\n"
    )
    v = lint_snippet(code, extra={_CONFIG_REL: config_src})
    assert_only(v, "config-coherence", 2)
    assert any("no_such_knob" in x.message for x in v)
    assert any("TM_DEFINITELY_NOT_DOCUMENTED" in x.message for x in v)


def test_config_coherence_real_reads_pass():
    config_src = open(os.path.join(REPO, _CONFIG_REL)).read()
    code = (
        "def f(config):\n"
        "    return config.base.crypto_pipeline_depth, config.mempool.size\n"
    )
    assert lint_snippet(code, extra={_CONFIG_REL: config_src}) == []


def test_golden_unused_import():
    code = "import os\nimport sys\nprint(sys.argv)\n"
    v = lint_snippet(code)
    assert_only(v, "unused-import", 1)
    assert "`os`" in v[0].message


def test_golden_unreachable_code():
    code = (
        "def f():\n"
        "    return 1\n"
        "    x = 2\n"
        "    return x\n"
    )
    v = lint_snippet(code)
    assert_only(v, "unreachable-code", 1)
    assert v[0].line == 3


def test_golden_slow_marker():
    code = (
        "from tests.cs_harness import start_network\n"
        "def test_net():\n"
        "    nodes = start_network(3)\n"
        "    return nodes\n"
    )
    v = lint_snippet(code, rel="tests/test_tmlint_snippet.py")
    assert_only(v, "slow-marker", 1)


def test_slow_marker_satisfied_by_decorator_and_pytestmark():
    marked = (
        "import pytest\n"
        "from tests.cs_harness import start_network\n"
        "@pytest.mark.slow\n"
        "def test_net():\n"
        "    return start_network(3)\n"
    )
    assert lint_snippet(marked, rel="tests/test_tmlint_snippet.py") == []
    module_marked = (
        "import pytest\n"
        "from tests.cs_harness import start_network\n"
        "pytestmark = pytest.mark.slow\n"
        "def test_net():\n"
        "    return start_network(3)\n"
    )
    assert lint_snippet(module_marked, rel="tests/test_tmlint_snippet.py") == []


def test_golden_metrics_exposition():
    v = MetricsExposition().check_text("m_no_type 1\n", source="<inline>")
    assert len(v) == 1 and v[0].rule == "metrics-exposition"
    assert "no preceding TYPE" in v[0].message
    assert MetricsExposition().check_text(
        "# HELP m h\n# TYPE m gauge\nm 1\n"
    ) == []


# -- suppression grammar ----------------------------------------------------


BAD_IMPORT = "import os\nimport sys\nprint(sys.argv)\n"


def test_suppression_trailing_with_justification():
    code = "import os  # tmlint: disable=unused-import -- golden test fixture\n"
    assert lint_snippet(code) == []


def test_suppression_standalone_covers_next_line():
    code = (
        "# tmlint: disable=unused-import -- golden test fixture\n"
        "import os\n"
    )
    assert lint_snippet(code) == []


def test_suppression_file_level():
    code = (
        "# tmlint: disable-file=unused-import -- golden test fixture\n"
        "import os\n"
        "import sys\n"
    )
    assert lint_snippet(code) == []


def test_suppression_without_justification_is_itself_a_violation():
    code = "import os  # tmlint: disable=unused-import\n"
    v = lint_snippet(code)
    rules = {x.rule for x in v}
    # the suppression works (no unused-import) but the bare form flags
    assert rules == {"suppression-format"}, v
    assert "justification" in v[0].message


def test_suppression_unknown_rule_is_flagged():
    code = "import os  # tmlint: disable=no-such-rule -- why\n"
    v = lint_snippet(code)
    assert {x.rule for x in v} == {"unused-import", "suppression-format"}, v


def test_suppression_format_cannot_be_suppressed():
    code = (
        "# tmlint: disable-file=suppression-format -- try me\n"
        "import os  # tmlint: disable=unused-import\n"
    )
    v = lint_snippet(code)
    assert any(x.rule == "suppression-format" for x in v), v


def test_suppressions_only_match_real_comments():
    # the directive inside a string literal is data, not a suppression
    code = 'import os\nX = "# tmlint: disable-file=unused-import -- nope"\n'
    v = lint_snippet(code)
    assert {x.rule for x in v} == {"unused-import"}, v


# -- scenario-coherence ------------------------------------------------------


def _scenario_project(tmp_path, doc_text, scenarios=("real.scn",)):
    """A synthetic repo root: docs/claims.md + a scenarios dir; the
    rule reads both from the project root, so golden cases never touch
    the real corpus."""
    from tendermint_tpu.analysis.rules_scenario import ScenarioCoherence

    root = tmp_path / "repo"
    (root / "docs").mkdir(parents=True)
    scen = root / "tendermint_tpu" / "sim" / "scenarios"
    scen.mkdir(parents=True)
    for name in scenarios:
        (scen / name).write_text("nodes = 4\nheights = 2\nexpect = safety\n")
    (root / "docs" / "claims.md").write_text(doc_text)
    project = Project(str(root), [])
    return run_lint(project, rules=[ScenarioCoherence()])


def test_golden_scenario_coherence_missing_scenario(tmp_path):
    v = _scenario_project(
        tmp_path,
        "Safety holds. [claim:safety scenario=missing.scn]\n",
    )
    assert_only(v, "scenario-coherence", 1)
    assert "missing.scn" in v[0].message and "does not exist" in v[0].message
    assert v[0].path == "docs/claims.md" and v[0].line == 1


def test_golden_scenario_coherence_malformed_marker(tmp_path):
    v = _scenario_project(
        tmp_path,
        "ok line\n"
        "[claim:vibes scenario=real.scn]\n"          # unknown kind
        "[claim:safety]\n"                            # missing scenario=
        "[claim:liveness scenario=no_suffix]\n",      # not a .scn name
    )
    assert_only(v, "scenario-coherence", 3)
    assert all("malformed claim marker" in x.message for x in v)
    assert [x.line for x in v] == [2, 3, 4]


def test_scenario_coherence_clean_and_boundaries(tmp_path):
    # valid markers against existing scenarios lint clean; prose that
    # merely mentions claims (no [claim: token) is never matched
    v = _scenario_project(
        tmp_path,
        "A claim: safety always holds (untagged prose, not a marker).\n"
        "[claim:safety scenario=real.scn] and again "
        "[claim:liveness scenario=real.scn]\n",
    )
    assert v == [], v


def test_repo_scenario_claims_are_tagged():
    """The backfill is real: the live docs tree carries at least one
    tagged claim per corpus scenario, and the full-repo lint (below)
    holds them coherent."""
    import re

    docs_dir = os.path.join(REPO, "docs")
    text = "\n".join(
        open(os.path.join(docs_dir, f), encoding="utf-8").read()
        for f in sorted(os.listdir(docs_dir))
        if f.endswith(".md")
    )
    tagged = set(re.findall(r"\[claim:(?:safety|liveness) scenario=([a-z0-9_]+\.scn)\]", text))
    from tendermint_tpu.sim.scenario import list_scenarios

    assert set(list_scenarios()) <= tagged, (
        f"corpus scenarios without a tagged docs claim: "
        f"{set(list_scenarios()) - tagged}"
    )


# -- registry / CLI surface -------------------------------------------------

EXPECTED_RULES = {
    "fault-site-coherence",
    "bound-method-truthiness",
    "task-retention",
    "async-hygiene",
    "no-permanent-latch",
    "metrics-coherence",
    "jit-purity",
    "config-coherence",
    "metrics-exposition",
    "unused-import",
    "unreachable-code",
    "slow-marker",
    "trace-coherence",
    "flightrec-coherence",
    "scenario-coherence",
}


def test_registry_has_all_rules():
    names = set(rule_names())
    assert EXPECTED_RULES <= names, EXPECTED_RULES - names
    for r in all_rules():
        assert r.name and r.summary


def test_cli_list_rules_and_disable():
    import importlib.util

    path = os.path.join(REPO, "scripts", "tmlint.py")
    spec = importlib.util.spec_from_file_location("tmlint_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["tmlint", "--list-rules"]) == 0
    assert mod.main(["tmlint", "--disable", "definitely-not-a-rule"]) == 2
    # a path matching no files must NOT read as clean — that would
    # silently disable a CI gate pinned to a since-moved path
    assert mod.main(["tmlint", "tendermint_tpu/no_such_dir"]) == 2


def test_parse_error_is_reported():
    v = lint_snippet("def broken(:\n")
    assert_only(v, "parse-error", 1)


# -- the acceptance gate ----------------------------------------------------


def test_repo_lints_clean():
    """`python scripts/tmlint.py tendermint_tpu tests scripts` exits 0:
    zero unsuppressed violations across the tree, every suppression
    justified. Every new bug class a future review finds should land
    here as a rule — this test is what keeps it fixed forever."""
    from tendermint_tpu.analysis import load_project

    project = load_project(REPO, ("tendermint_tpu", "tests", "scripts"))
    violations = run_lint(project)
    assert violations == [], "\n" + "\n".join(v.format() for v in violations)
