#!/usr/bin/env python
"""Lint: emitted flight-recorder event kinds ↔ docs registry, both ways.

Every event kind passed to ``event(`` (the flight recorder,
``observability/trace.py``) anywhere in ``kfac_pytorch_tpu/`` or
``examples/`` must be a string LITERAL (policy — keeps this lint sound) and must appear in the registry table between the
``trace-event-registry:start``/``end`` markers of docs/OBSERVABILITY.md;
conversely every registry row must be emitted somewhere. ``scripts/`` and
``tests/`` are deliberately out of scan scope: merge_timeline.py and the
tests consume kinds, they don't emit them.

Exit 0 clean, 1 with a report otherwise. Run from the repo root (tier-1
wraps it in a test).
"""

from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOC = ROOT / "docs" / "OBSERVABILITY.md"
SCAN = ["kfac_pytorch_tpu", "examples"]

# Lowercase `event(` only — matches `tr.event("kind", ...)` /
# `get_trace().event("kind", ...)`, not `threading.Event(`.
CALL_RE = re.compile(r"\bevent\(\s*['\"]([^'\"]+)['\"]")
ROW_RE = re.compile(r"^\|\s*`([^`]+)`\s*\|")


def emitted_kinds() -> dict:
    """kind -> sorted list of files emitting it (literal call sites only)."""
    kinds = {}
    files = []
    for target in SCAN:
        p = ROOT / target
        files.extend(sorted(p.rglob("*.py")) if p.is_dir() else [p])
    for f in files:
        for m in CALL_RE.finditer(f.read_text()):
            kinds.setdefault(m.group(1), set()).add(str(f.relative_to(ROOT)))
    return {k: sorted(v) for k, v in kinds.items()}


def registry_kinds() -> set:
    text = DOC.read_text()
    m = re.search(
        r"<!-- trace-event-registry:start -->(.*?)"
        r"<!-- trace-event-registry:end -->",
        text,
        re.S,
    )
    if not m:
        sys.exit(f"{DOC}: trace-event-registry markers not found")
    kinds = set()
    for line in m.group(1).splitlines():
        row = ROW_RE.match(line.strip())
        if row and row.group(1) != "kind":
            kinds.add(row.group(1))
    return kinds


def main() -> int:
    emitted = emitted_kinds()
    registry = registry_kinds()

    problems = []
    for kind in sorted(set(emitted) - registry):
        problems.append(
            f"emitted but not in registry: {kind!r} "
            f"(from {', '.join(emitted[kind])})"
        )
    for kind in sorted(registry - set(emitted)):
        problems.append(f"in registry but never emitted: {kind!r}")

    if problems:
        print(
            f"check_trace_events: {len(problems)} problem(s)", file=sys.stderr
        )
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 1
    print(f"check_trace_events: OK — {len(registry)} event kinds in sync")
    return 0


if __name__ == "__main__":
    sys.exit(main())
