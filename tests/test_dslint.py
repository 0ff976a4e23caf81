"""dslint: fixture-driven rule tests + the tier-1 zero-findings gate.

The package gate (`test_package_gate_zero_findings`) IS the enforcement
point: it runs the full rule set over `deeperspeed_tpu/` and
`tests/perf/` and fails on any non-baselined finding. It runs in
tier-1 by default (no marker) — a parse of ~150 files, well under a
second. The `dslint`-marked variants (paired with `slow`) are the
whole-repo self-scans.
"""

import json
import os
import shutil
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.dslint import (DEFAULT_PATHS, REGISTRY, RULESET_VERSION,  # noqa: E402
                          run_lint)
from tools.dslint.baseline import (load_baseline, split_by_baseline,  # noqa: E402
                                   write_baseline)
from tools.dslint.cli import main as cli_main  # noqa: E402
from tools.dslint.core import SourceFile  # noqa: E402

FIXTURES = os.path.join(REPO_ROOT, "tests", "dslint_fixtures")

# rule -> (bad fixture, expected finding count, ok fixture). Every bad
# fixture also carries exactly one `# dslint: disable=<rule>` suppressed
# occurrence, pinned by test_rule_suppression.
RULE_FIXTURES = {
    "trace-host-call": ("trace_host_call_bad.py", 6,
                        "trace_host_call_ok.py"),
    "wall-clock": ("wall_clock_bad.py", 2, "wall_clock_ok.py"),
    "strong-ref-hook": ("strong_ref_hook_bad.py", 3,
                        "strong_ref_hook_ok.py"),
    "non-atomic-commit": ("non_atomic_commit_bad.py", 2,
                          "non_atomic_commit_ok.py"),
    "barrier-no-deadline": ("barrier_no_deadline_bad.py", 2,
                            "barrier_no_deadline_ok.py"),
    "swallowed-thread-exc": ("swallowed_thread_exc_bad.py", 2,
                             "swallowed_thread_exc_ok.py"),
    "timed-pallas-no-interpret": ("timed_pallas_no_interpret_bad.py", 1,
                                  "timed_pallas_no_interpret_ok.py"),
    "multislice-collective-outside-schedule": (
        "multislice_collective_bad.py", 2, "multislice_collective_ok.py"),
}


def lint_fixture(filename, rule):
    result = run_lint(paths=[filename], root=FIXTURES, select=[rule],
                      use_baseline=False)
    assert not result.errors, result.errors
    return result.findings


# ---------------------------------------------------------------------------
# rule unit tests: true positive / true negative / suppression
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_true_positives(rule):
    bad, expected, _ = RULE_FIXTURES[rule]
    findings = lint_fixture(bad, rule)
    assert len(findings) == expected, \
        f"{rule}: expected {expected}, got " \
        f"{[(f.line, f.snippet) for f in findings]}"
    for f in findings:
        assert f.rule == rule
        assert f.message and f.snippet and f.line > 0
        assert f.path.endswith(bad)
        assert len(f.fingerprint) == 16


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_true_negatives(rule):
    _, _, ok = RULE_FIXTURES[rule]
    assert lint_fixture(ok, rule) == []


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_suppression(rule):
    """Each bad fixture carries one `# dslint: disable=<rule>` site:
    no finding may land on a directive-bearing line (or the line after
    a standalone directive comment)."""
    bad, _, _ = RULE_FIXTURES[rule]
    with open(os.path.join(FIXTURES, bad)) as f:
        lines = f.read().splitlines()
    directive_lines = set()
    for i, text in enumerate(lines, 1):
        if "dslint: disable" in text:
            directive_lines.add(i)
            if text.lstrip().startswith("#"):
                directive_lines.add(i + 1)
    assert directive_lines, f"{bad} must exercise the suppression path"
    hit = directive_lines & {f.line for f in lint_fixture(bad, rule)}
    assert not hit, f"suppression ignored on line(s) {sorted(hit)}"


def test_strong_ref_hook_module_vs_object_from_import(tmp_path):
    """`from pkg import module` attributes are module functions (fine);
    `from pkg import OBJECT` attributes are bound methods (flagged) —
    pins the module-resolution distinction, not import spelling."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helpers.py").write_text("def cleanup():\n    pass\n\nOBJ = 1\n")
    (pkg / "uses_module.py").write_text(
        "import atexit\n\nfrom . import helpers\n\n\n"
        "def install():\n    atexit.register(helpers.cleanup)\n")
    (pkg / "uses_object.py").write_text(
        "import atexit\n\nfrom .helpers import OBJ\n\n\n"
        "def install():\n    atexit.register(OBJ.close)\n")
    result = run_lint(paths=["pkg"], root=str(tmp_path),
                      select=["strong-ref-hook"], use_baseline=False)
    assert [f.path for f in result.findings] == ["pkg/uses_object.py"]


def test_explicit_missing_path_fails_loudly(tmp_path):
    """A typo'd explicit path must fail the run, not report clean over
    0 files (a pre-commit hook would silently stop gating)."""
    result = run_lint(paths=["no_such_dir"], root=str(tmp_path))
    assert not result.ok
    assert result.errors == [("no_such_dir", "path does not exist")]
    assert cli_main(["no_such_dir", "--root", str(tmp_path)]) == 1


def test_file_level_suppression(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text("# dslint: disable-file=wall-clock\n"
                   "import time\n\n\n"
                   "def f():\n    return time.time()\n")
    result = run_lint(paths=["mod.py"], root=str(tmp_path),
                      select=["wall-clock"], use_baseline=False)
    assert result.findings == []


def test_syntax_error_is_reported_not_skipped(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    result = run_lint(paths=["broken.py"], root=str(tmp_path),
                      use_baseline=False)
    assert not result.ok
    assert result.errors and result.errors[0][0] == "broken.py"


# ---------------------------------------------------------------------------
# the config-key consumption pass
# ---------------------------------------------------------------------------

def test_parse_only_key_flags_synthetic_key():
    result = run_lint(paths=["cfgpkg"], root=FIXTURES,
                      select=["parse-only-key"], use_baseline=False)
    assert not result.errors
    keys = sorted(f.message.split("'")[1] for f in result.findings)
    # phantom_knob: parsed, never read -> flagged. alpha_knob: subscript
    # consumer -> clean. launcher_knob: consumed-by-launcher escape.
    assert keys == ["phantom_knob"]
    (finding,) = result.findings
    assert finding.path.endswith("cfgpkg/config.py")


def test_parse_only_key_accepts_consumed_key_until_consumer_removed(
        tmp_path):
    """Removing a key's only consumer turns it into a finding — pins
    that consumption detection is what clears a key, not luck."""
    pkg = tmp_path / "cfgpkg"
    shutil.copytree(os.path.join(FIXTURES, "cfgpkg"), pkg)
    result = run_lint(paths=["cfgpkg"], root=str(tmp_path),
                      select=["parse-only-key"], use_baseline=False)
    assert sorted(f.message.split("'")[1] for f in result.findings) == \
        ["phantom_knob"]
    (pkg / "consumer.py").write_text(
        '"""Consumer removed."""\n\nfrom . import constants as c\n')
    result = run_lint(paths=["cfgpkg"], root=str(tmp_path),
                      select=["parse-only-key"], use_baseline=False)
    assert sorted(f.message.split("'")[1] for f in result.findings) == \
        ["alpha_knob", "phantom_knob"]


def test_parse_only_key_kwarg_and_param_consumption(tmp_path):
    """The **parsed_block pattern: a call keyword or a function
    parameter named like the key counts as consumption."""
    pkg = tmp_path / "cfgpkg"
    shutil.copytree(os.path.join(FIXTURES, "cfgpkg"), pkg)
    (pkg / "consumer.py").write_text(
        "from . import constants as c\n\n\n"
        "def build(block):\n"
        "    return Thing(**block)\n\n\n"
        "def make_thing(alpha_knob=1, phantom_knob=2):\n"
        "    return (alpha_knob, phantom_knob)\n")
    result = run_lint(paths=["cfgpkg"], root=str(tmp_path),
                      select=["parse-only-key"], use_baseline=False)
    assert result.findings == []


def test_parse_only_key_harvests_serving_blocks():
    """The real-repo harvest must see the `inference.prefix_cache` and
    `inference.speculative` sub-block keys — pins that the rule's
    enforcement covers the serving config blocks (renaming a parser's
    known-set variable would silently drop them from the gate)."""
    from tools.dslint.config_keys import (_constants_aliases,
                                          _constants_tables,
                                          _known_set_assignments,
                                          _resolve_key)
    sources = []
    for rel in (os.path.join("deeperspeed_tpu", "runtime", "config.py"),
                os.path.join("deeperspeed_tpu", "runtime",
                             "constants.py")):
        ap = os.path.join(REPO_ROOT, rel)
        with open(ap) as f:
            sources.append(SourceFile(ap, rel, f.read()))
    tables = _constants_tables(sources)
    harvested = set()
    for src in sources:
        aliases = _constants_aliases(src, tables)
        for assign in _known_set_assignments(src):
            for elt in assign.value.elts:
                key = _resolve_key(elt, aliases)
                if key is not None:
                    harvested.add(key)
    assert {"prefix_cache", "speculative", "max_pages",
            "num_draft_tokens", "draft_weight_quant"} <= harvested


def test_parse_only_key_harvests_planner_block():
    """Same drill for the schedule planner's `planner` block: its keys
    are declared through `c.PLANNER_*` constants, so the harvest must
    resolve them via the constants table — and the rule then demands a
    real consumer for each (planner/apply.py reads plan_file and
    strict_device_match; enabled gates the overlay)."""
    from tools.dslint.config_keys import (_constants_aliases,
                                          _constants_tables,
                                          _known_set_assignments,
                                          _resolve_key)
    sources = []
    for rel in (os.path.join("deeperspeed_tpu", "runtime", "config.py"),
                os.path.join("deeperspeed_tpu", "runtime",
                             "constants.py")):
        ap = os.path.join(REPO_ROOT, rel)
        with open(ap) as f:
            sources.append(SourceFile(ap, rel, f.read()))
    tables = _constants_tables(sources)
    harvested = set()
    for src in sources:
        aliases = _constants_aliases(src, tables)
        for assign in _known_set_assignments(src):
            for elt in assign.value.elts:
                key = _resolve_key(elt, aliases)
                if key is not None:
                    harvested.add(key)
    assert {"enabled", "plan_file", "strict_device_match"} <= harvested


def test_parse_only_key_harvests_disagg_blocks():
    """Same drill for the disaggregated-serving sub-blocks: the
    `inference.disaggregation` and `inference.router` keys are declared
    through `c.INFERENCE_DISAGG_*` / `c.INFERENCE_ROUTER_*` constants,
    so the harvest must resolve them via the constants table — a typo'd
    role or router weight then fails the parse-only-key gate instead of
    silently running on defaults."""
    from tools.dslint.config_keys import (_constants_aliases,
                                          _constants_tables,
                                          _known_set_assignments,
                                          _resolve_key)
    sources = []
    for rel in (os.path.join("deeperspeed_tpu", "runtime", "config.py"),
                os.path.join("deeperspeed_tpu", "runtime",
                             "constants.py")):
        ap = os.path.join(REPO_ROOT, rel)
        with open(ap) as f:
            sources.append(SourceFile(ap, rel, f.read()))
    tables = _constants_tables(sources)
    harvested = set()
    for src in sources:
        aliases = _constants_aliases(src, tables)
        for assign in _known_set_assignments(src):
            for elt in assign.value.elts:
                key = _resolve_key(elt, aliases)
                if key is not None:
                    harvested.add(key)
    assert {"disaggregation", "role", "pool_id", "handoff_timeout_s",
            "router", "queue_depth_weight", "pool_util_weight",
            "ttft_weight", "scale_up_util"} <= harvested


def test_parse_only_key_harvests_rl_block():
    """Same drill for the online-RL driver's `rl` block: parse_rl_block
    declares its known set through `c.RL_*` constants, so the harvest
    must resolve every key via the constants table — the rule then
    demands a real consumer for each (rl/driver.py and rl/losses.py
    subscript the parsed dict; the engine hook reads `loss`)."""
    from tools.dslint.config_keys import (_constants_aliases,
                                          _constants_tables,
                                          _known_set_assignments,
                                          _resolve_key)
    sources = []
    for rel in (os.path.join("deeperspeed_tpu", "runtime", "config.py"),
                os.path.join("deeperspeed_tpu", "runtime",
                             "constants.py")):
        ap = os.path.join(REPO_ROOT, rel)
        with open(ap) as f:
            sources.append(SourceFile(ap, rel, f.read()))
    tables = _constants_tables(sources)
    harvested = set()
    for src in sources:
        aliases = _constants_aliases(src, tables)
        for assign in _known_set_assignments(src):
            for elt in assign.value.elts:
                key = _resolve_key(elt, aliases)
                if key is not None:
                    harvested.add(key)
    assert {"loss", "rollouts_per_iteration", "group_size",
            "max_new_tokens", "sequence_length", "clip_ratio",
            "kl_coef", "beta", "checkpoint_interval"} <= harvested


# ---------------------------------------------------------------------------
# seeding: each fixture bug class injected into a copy of runtime code
# is caught (the acceptance-criteria drill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_seeded_bug_class_detected_in_runtime_copy(rule, tmp_path):
    bad, expected, _ = RULE_FIXTURES[rule]
    victim = os.path.join(REPO_ROOT, "deeperspeed_tpu", "runtime",
                          "utils.py")
    with open(victim) as f:
        clean = f.read()
    with open(os.path.join(FIXTURES, bad)) as f:
        seed = f.read()
    scratch = tmp_path / "runtime_copy.py"
    scratch.write_text(clean + "\n\n" + seed)
    result = run_lint(paths=["runtime_copy.py"], root=str(tmp_path),
                      select=[rule], use_baseline=False)
    assert not result.errors
    assert len(result.findings) == expected, \
        f"seeded {rule} not detected in runtime copy"


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_matching(tmp_path):
    bad, expected, _ = RULE_FIXTURES["wall-clock"]
    findings = lint_fixture(bad, "wall-clock")
    bpath = tmp_path / "baseline.json"
    write_baseline(findings, str(bpath), RULESET_VERSION)
    result = run_lint(paths=[bad], root=FIXTURES, select=["wall-clock"],
                      baseline_path=str(bpath))
    assert result.ok
    assert len(result.baselined) == expected
    assert result.findings == []


def test_baseline_is_count_aware(tmp_path):
    src = tmp_path / "mod.py"
    # two IDENTICAL offending lines -> one fingerprint, count 2
    src.write_text("import time\n\n\ndef f():\n"
                   "    t = time.time()\n    t = time.time()\n"
                   "    return t\n")
    findings = run_lint(paths=["mod.py"], root=str(tmp_path),
                        select=["wall-clock"], use_baseline=False).findings
    assert len(findings) == 2
    assert findings[0].fingerprint == findings[1].fingerprint
    baseline = {(findings[0].rule, findings[0].path,
                 findings[0].fingerprint): 1}
    new, old = split_by_baseline(findings, baseline)
    assert len(new) == 1 and len(old) == 1


def test_fingerprint_survives_line_drift(tmp_path):
    src = tmp_path / "mod.py"
    body = "import time\n\n\ndef f():\n    return time.time()\n"
    src.write_text(body)
    (f1,) = run_lint(paths=["mod.py"], root=str(tmp_path),
                     select=["wall-clock"], use_baseline=False).findings
    src.write_text("# a comment pushing everything down\n\n\n" + body)
    (f2,) = run_lint(paths=["mod.py"], root=str(tmp_path),
                     select=["wall-clock"], use_baseline=False).findings
    assert f1.line != f2.line
    assert f1.fingerprint == f2.fingerprint


def test_committed_baseline_is_empty():
    """The PR-exit criterion: everything dslint found was fixed or
    per-line justified — nothing is grandfathered."""
    committed = load_baseline(os.path.join(
        REPO_ROOT, "tools", "dslint", "baseline.json"))
    assert committed == {}


# ---------------------------------------------------------------------------
# CLI (mirrors ds_report): --json, --baseline-update, exit codes
# ---------------------------------------------------------------------------

def test_cli_json_output_and_exit_code(capsys):
    bad, expected, _ = RULE_FIXTURES["wall-clock"]
    rc = cli_main([bad, "--root", FIXTURES, "--select", "wall-clock",
                   "--no-baseline", "--json"])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["ruleset"] == RULESET_VERSION
    assert payload["ok"] is False
    assert len(payload["findings"]) == expected
    for f in payload["findings"]:
        assert {"rule", "path", "line", "col", "message", "snippet",
                "fingerprint"} <= set(f)


def test_cli_baseline_update_then_clean(tmp_path, capsys):
    bad, _, _ = RULE_FIXTURES["wall-clock"]
    bpath = str(tmp_path / "baseline.json")
    rc = cli_main([bad, "--root", FIXTURES, "--select", "wall-clock",
                   "--baseline", bpath, "--baseline-update"])
    assert rc == 0
    assert os.path.exists(bpath)
    rc = cli_main([bad, "--root", FIXTURES, "--select", "wall-clock",
                   "--baseline", bpath])
    assert rc == 0
    out = capsys.readouterr().out
    assert "clean" in out


def test_cli_clean_run_exits_zero(capsys):
    _, _, ok = RULE_FIXTURES["wall-clock"]
    rc = cli_main([ok, "--root", FIXTURES, "--no-baseline"])
    assert rc == 0
    assert "clean" in capsys.readouterr().out


def test_cli_unknown_rule_rejected(capsys):
    rc = cli_main(["--select", "no-such-rule"])
    assert rc == 2
    assert "unknown rule" in capsys.readouterr().err


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in REGISTRY:
        assert name in out


# ---------------------------------------------------------------------------
# directive parsing details
# ---------------------------------------------------------------------------

def test_directive_parsing_same_line_next_line_and_annotation():
    src = SourceFile(
        "x", "x.py",
        "import time\n"
        "t = time.time()  # dslint: disable=wall-clock\n"
        "# dslint: disable=wall-clock\n"
        "u = time.time()\n"
        "v = 1  # dslint: consumed-by-launcher\n")
    assert src.suppressed("wall-clock", 2)
    assert src.suppressed("wall-clock", 4)   # standalone applies below
    assert not src.suppressed("wall-clock", 5)
    assert src.annotated("consumed-by-launcher", 5)
    assert not src.annotated("consumed-by-launcher", 2)


# ---------------------------------------------------------------------------
# ds_report integration
# ---------------------------------------------------------------------------

def test_ds_report_json_includes_ruleset_version():
    from deeperspeed_tpu.env_report import json_report
    payload = json_report()
    assert payload["env"]["dslint_ruleset"] == RULESET_VERSION


# ---------------------------------------------------------------------------
# THE TIER-1 GATE: zero non-baselined findings over the package
# ---------------------------------------------------------------------------

def test_package_gate_zero_findings():
    """The enforcement point. If this fails: fix the finding, add a
    justified per-line suppression, or (new-rule burn-down only)
    regenerate the baseline with `bin/ds_lint --baseline-update` — in
    that order of preference. See docs/static-analysis.md."""
    result = run_lint()   # DEFAULT_PATHS against the repo root
    assert result.files_checked > 100
    report = "\n".join(f.render() for f in result.findings)
    assert not result.errors, result.errors
    assert result.findings == [], f"new dslint findings:\n{report}"


def test_gate_runs_all_rules():
    result = run_lint(paths=["wall_clock_ok.py"], root=FIXTURES,
                      use_baseline=False)
    assert set(result.rules_run) == set(REGISTRY)
    assert set(RULE_FIXTURES) | {"parse-only-key"} == set(REGISTRY)
    assert len(REGISTRY) == 9
    assert DEFAULT_PATHS == ("deeperspeed_tpu", "tests/perf")


# ---------------------------------------------------------------------------
# slow whole-repo self-scans (the only dslint-marked variants)
# ---------------------------------------------------------------------------

@pytest.mark.dslint
@pytest.mark.slow
def test_self_scan_tools_tree():
    """dslint over its own implementation: must parse everything and
    produce no findings (the linter holds itself to its rules)."""
    result = run_lint(paths=["tools"], use_baseline=False)
    assert not result.errors
    assert result.findings == [], \
        "\n".join(f.render() for f in result.findings)


@pytest.mark.dslint
@pytest.mark.slow
def test_self_scan_whole_test_tree():
    """The full tests/ tree parses under every rule (fixtures excluded:
    they exist to contain findings). Findings in test code are
    informational — the scan pins only that the engine completes and
    reports structurally sound results."""
    result = run_lint(paths=["tests"], use_baseline=False)
    fixture_free = [e for e in result.errors
                    if "dslint_fixtures" not in e[0]]
    assert not fixture_free, fixture_free
    for f in result.findings:
        assert f.rule in REGISTRY and f.line > 0
