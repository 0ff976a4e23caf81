"""`tools/tier1_times.py`: tier-1's budget read from a junit file."""

import io

import pytest

from tools import tier1_times

CASE = '<testcase classname="tests.{cls}" name="{name}" time="{s}" />'


def junit(times):
    """`times` of test_a.py's cases (the second in a class), and one case
    of 1.5 s in test_b.py."""
    rows = [CASE.format(cls="test_a.TestIt" if i == 1 else "test_a",
                        name=f"test_{i}[x]", s=s)
            for i, s in enumerate(times)]
    rows.append(CASE.format(cls="test_b", name="test_three", s=1.5))
    return ('<?xml version="1.0" encoding="utf-8"?><testsuites><testsuite '
            f'name="pytest" errors="0" failures="0" tests="{len(rows)}">'
            + "".join(rows) + "</testsuite></testsuites>")


@pytest.mark.parametrize("times,over", [
    ([40.0, 59.0], []),
    ([59.0] * 5 + [6.5], ["FILE over 300 s: test_a.py 301.5"]),
    ([40.0, 60.5], ["CASE over 60 s: test_a.py::TestIt::test_1[x] 60.5"]),
], ids=["inside the budget", "over a file's", "over a case's"])
def test_report_names_what_breaks_the_rule(times, over):
    cases = tier1_times.read(junit(times))
    assert cases[:2] == [("test_a.py", "test_0[x]", times[0]),
                         ("test_a.py", "TestIt::test_1[x]", times[1])]
    assert cases[-1] == ("test_b.py", "test_three", 1.5)
    out = io.StringIO()
    assert tier1_times.report(cases, out) == over
    text = out.getvalue()
    total = sum(times) + 1.5
    assert f"{'test_a.py':40s} {sum(times):8.1f} {len(times):6d}" in text
    assert (f"{len(cases)} cases, {total:.0f} test-seconds, "
            f"{total / 6:.0f} s over 6 workers, longest file test_a.py "
            f"{sum(times):.0f} s") in text
    # the longest case heads its list, and what breaks the rule is printed
    longest = text[text.index("the 20 longest cases"):].splitlines()[1]
    assert longest.startswith(f"{max(times):8.1f}  test_a.py::")
    assert all(line in text for line in over)
