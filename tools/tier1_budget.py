"""Tier-1 wall-budget guard (ROADMAP tier-1 verify runs under a hard
``timeout -k 10 870``; PR 6 measured ~863 s of that budget already
consumed, and a suite that creeps past the timeout is KILLED mid-run —
every test after the cut silently stops counting).

This tool turns that cliff into an explicit, rankable report:

    # during the tier-1 run, record per-test durations (tests/conftest.py)
    LGBMV1_T1_DURATIONS=/tmp/t1_durations.jsonl \
        python -m pytest tests/ -q -m 'not slow' ...

    # then project the wall against the budget (exit 1 over the bar)
    python tools/tier1_budget.py /tmp/t1_durations.jsonl

It also accepts a plain pytest log (the ``tee /tmp/_t1.log`` file the
verify command writes): the trailing ``in NNN.NNs`` wall is used, plus
any ``--durations`` section lines for offender ranking.

Exit status: 0 when projected wall <= ``frac * budget`` (default 95% of
870 s), 1 otherwise — wire it after the tier-1 run so budget creep fails
loudly BEFORE the driver's timeout starts eating tests.  The fix for a
failing guard is the PR-6 discipline: mark the listed offenders ``slow``
(they still run in the full suite / bench / driver captures) or shrink
documented-arbitrary scales at constant structure.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict

DEFAULT_BUDGET_S = 870.0     # ROADMAP tier-1 verify: timeout -k 10 870
DEFAULT_FRAC = 0.95

# pytest summary tail: "=== 337 passed, 3 failed, ... in 862.95s ... ==="
_WALL_RE = re.compile(r"\bin (\d+(?:\.\d+)?)s\b")
# pytest --durations section: "12.34s call     tests/test_x.py::test_y"
_DUR_LINE_RE = re.compile(
    r"^\s*(\d+(?:\.\d+)?)s\s+(call|setup|teardown)\s+(\S+)")


def parse_durations_jsonl(lines):
    """Per-test totals + wall projection from the conftest JSONL records.
    Returns ``(per_test dict, projected_wall_s)`` — the projection is the
    sum of every recorded phase (collection/import overhead rides inside
    the first tests' setup phases, so the sum tracks the measured wall
    within a few percent)."""
    per_test = defaultdict(float)
    total = 0.0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        rec = json.loads(line)
        d = float(rec.get("duration", 0.0))
        per_test[rec["nodeid"]] += d
        total += d
    return dict(per_test), total


def parse_pytest_log(lines):
    """``(per_test dict, wall_s or None)`` from a pytest console log."""
    per_test = defaultdict(float)
    wall = None
    for line in lines:
        m = _DUR_LINE_RE.match(line)
        if m:
            per_test[m.group(3)] += float(m.group(1))
        m = _WALL_RE.search(line)
        if m:
            wall = float(m.group(1))   # keep the LAST (summary) match
    return dict(per_test), wall


def load(path):
    with open(path) as fh:
        first = fh.readline()
        rest = fh.readlines()
    lines = [first] + rest
    try:
        json.loads(first)
        is_jsonl = True
    except (ValueError, TypeError):
        is_jsonl = False
    if is_jsonl:
        return parse_durations_jsonl(lines)
    return parse_pytest_log(lines)


def report(per_test, wall, budget=DEFAULT_BUDGET_S, frac=DEFAULT_FRAC,
           top=15, out=print):
    """Render the budget report; returns True when within budget."""
    bar = frac * budget
    ok = wall is not None and wall <= bar
    out(f"tier-1 projected wall: "
        + (f"{wall:.1f} s" if wall is not None else "UNKNOWN")
        + f" of {budget:.0f} s budget (bar = {frac:.0%} = {bar:.1f} s)"
        + f" -> {'OK' if ok else 'OVER BUDGET'}")
    if per_test:
        worst = sorted(per_test.items(), key=lambda kv: -kv[1])[:top]
        out(f"worst {len(worst)} offenders (candidates for the `slow` "
            "mark — still run by the full suite and driver captures):")
        for nodeid, d in worst:
            out(f"  {d:8.2f}s  {nodeid}")
    if not ok and wall is not None:
        out(f"over by {wall - bar:.1f} s: mark offenders `slow` or shrink "
            "documented-arbitrary test scales at constant structure")
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?", default="/tmp/_t1.log",
                    help="durations JSONL (tests/conftest.py) or pytest log")
    ap.add_argument("--budget", type=float, default=DEFAULT_BUDGET_S)
    ap.add_argument("--frac", type=float, default=DEFAULT_FRAC)
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)
    per_test, wall = load(args.path)
    ok = report(per_test, wall, budget=args.budget, frac=args.frac,
                top=args.top)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
