"""Lint driver: load sources, run every registered rule, apply the
baseline, return a structured result. The pytest tier-1 gate and the
``ds_lint`` CLI are both thin wrappers over :func:`run_lint`."""

import dataclasses
import os

from .baseline import (DEFAULT_BASELINE_PATH, load_baseline,
                       split_by_baseline)
from .core import LintContext, iter_source_files
from .rules import REGISTRY

# What the tier-1 gate lints. `tests/perf/` rides along for the
# wall-clock audit (step timing on a wall clock is the same NTP-jump
# hazard PR 6 fixed in utils/timer.py) — and gets the full rule set
# since it exercises the same engine surfaces.
DEFAULT_PATHS = ("deeperspeed_tpu", "tests/perf")


@dataclasses.dataclass
class LintResult:
    findings: list          # new (non-baselined) findings
    baselined: list         # findings covered by the committed baseline
    errors: list            # (path, message) unparseable files
    files_checked: int
    rules_run: list

    @property
    def ok(self):
        return not self.findings and not self.errors

    def to_dict(self, ruleset_version):
        return {
            "ruleset": ruleset_version,
            "ok": self.ok,
            "files_checked": self.files_checked,
            "rules": self.rules_run,
            "findings": [f.to_dict() for f in self.findings],
            "baselined": [f.to_dict() for f in self.baselined],
            "errors": [{"path": p, "message": m} for p, m in self.errors],
        }


def run_lint(paths=None, root=None, select=None, baseline_path=None,
             use_baseline=True):
    """Run the rule set over ``paths`` (default: the tier-1 path set)
    relative to ``root`` (default: the repo root containing tools/).

    ``select``: optional iterable of rule names to run (others skipped).
    ``baseline_path``: None uses the committed tools/dslint/baseline.json;
    ``use_baseline=False`` reports every finding as new.
    """
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))

    ctx = LintContext(root=root)
    if paths:
        # an EXPLICIT path that doesn't exist must fail the run, not
        # silently lint 0 files with exit 0 (a typo'd pre-commit hook
        # would stop gating without anyone noticing)
        paths = list(paths)
        for p in paths:
            ap = p if os.path.isabs(p) else os.path.join(root, p)
            if not os.path.exists(ap):
                ctx.errors.append((p, "path does not exist"))
    else:
        # default set: absent members are tolerated (an installed
        # package without tests/ still lints)
        paths = [p for p in DEFAULT_PATHS
                 if os.path.exists(os.path.join(root, p))]
    ctx.sources = list(iter_source_files(paths, root, errors=ctx.errors))

    rules = [r for name, r in sorted(REGISTRY.items())
             if select is None or name in set(select)]

    findings = []
    for rule in rules:
        if rule.scope == "project":
            findings.extend(rule.check_project(ctx))
        else:
            for src in ctx.sources:
                findings.extend(rule.check_file(src, ctx))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))

    if use_baseline:
        bpath = baseline_path or DEFAULT_BASELINE_PATH
        new, old = split_by_baseline(findings, load_baseline(bpath))
    else:
        new, old = findings, []
    return LintResult(findings=new, baselined=old, errors=list(ctx.errors),
                      files_checked=len(ctx.sources),
                      rules_run=[r.name for r in rules])
