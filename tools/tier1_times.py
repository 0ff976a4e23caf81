"""Tier-1's time by test file, from the junit file the driver's command writes.

    python tools/tier1_times.py [/tmp/_t1.xml]

Prints seconds and cases by test file, the 20 longest cases, the sum, the
sum over six workers and the longest file. Exits 1 if a file is over
FILE_LIMIT_S or a case over CASE_LIMIT_S (`pyproject.toml`, `slow`).
"""

import sys
import xml.etree.ElementTree as ET
from collections import defaultdict

FILE_LIMIT_S = 300.0
CASE_LIMIT_S = 60.0
WORKERS = 6


def read(junit_text):
    """[(file, case, seconds)] of a junit document."""
    cases = []
    for case in ET.fromstring(junit_text).iter("testcase"):
        # classname is tests.test_x or tests.test_x.TestClass[.Inner]
        parts = case.get("classname", "").split(".")
        at = next((i for i, p in enumerate(parts) if p.startswith("test_")), 0)
        name = "::".join(parts[at + 1:] + [case.get("name", "")])
        cases.append((parts[at] + ".py", name, float(case.get("time", 0.0))))
    return cases


def report(cases, out=sys.stdout):
    """Print the tables; return the lines that break the rule."""
    by_file = defaultdict(lambda: [0.0, 0])
    for f, _, s in cases:
        by_file[f][0] += s
        by_file[f][1] += 1
    files = sorted(by_file.items(), key=lambda kv: -kv[1][0])
    print(f"{'file':40s} {'s':>8s} {'cases':>6s}", file=out)
    for f, (s, n) in files:
        print(f"{f:40s} {s:8.1f} {n:6d}", file=out)
    print("\nthe 20 longest cases", file=out)
    for f, name, s in sorted(cases, key=lambda c: -c[2])[:20]:
        print(f"{s:8.1f}  {f}::{name}", file=out)
    total = sum(s for _, _, s in cases)
    print(f"\n{len(cases)} cases, {total:.0f} test-seconds, "
          f"{total / WORKERS:.0f} s over {WORKERS} workers, longest file "
          f"{files[0][0]} {files[0][1][0]:.0f} s", file=out)
    over = [f"FILE over {FILE_LIMIT_S:.0f} s: {f} {s:.1f}"
            for f, (s, _) in files if s > FILE_LIMIT_S]
    over += [f"CASE over {CASE_LIMIT_S:.0f} s: {f}::{name} {s:.1f}"
             for f, name, s in cases if s > CASE_LIMIT_S]
    print(*over, sep="\n", file=out)
    return over


if __name__ == "__main__":
    path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/_t1.xml"
    with open(path, encoding="utf-8") as fh:
        sys.exit(1 if report(read(fh.read())) else 0)
