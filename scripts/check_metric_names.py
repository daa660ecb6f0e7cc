#!/usr/bin/env python
"""Lint: emitted telemetry names ↔ docs/OBSERVABILITY.md registry, both ways.

Every metric name passed to ``span(``/``inc(``/``set_gauge(``/``observe(``
(or as the span of ``phase(<phase>, <span>)``, observability/phases.py)
anywhere in ``kfac_pytorch_tpu/`` or ``examples/`` must be a
string LITERAL (policy — keeps this lint sound) and must appear in the
registry table between the ``metric-registry:start``/``end`` markers of
docs/OBSERVABILITY.md; conversely every registry row must be emitted
somewhere. Registry names containing ``<`` are dynamic families
(``compile/cache_size/<fn>``) and exempt from the emitted-side match.

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

CALL_RE = re.compile(
    r"\b(?:(?:span|inc|set_gauge|observe)\(|phase\(\s*['\"][^'\"]+['\"]\s*,)"
    r"\s*['\"]([^'\"]+)['\"]"
)
ROW_RE = re.compile(r"^\|\s*`([^`]+)`\s*\|")


def emitted_names() -> dict:
    """name -> sorted list of files emitting it (literal call sites only)."""
    names = {}
    files = []
    for target in SCAN:
        p = ROOT / target
        files.extend(sorted(p.rglob("*.py")) if p.is_dir() else [p])
    for f in files:
        for m in CALL_RE.finditer(f.read_text()):
            names.setdefault(m.group(1), set()).add(str(f.relative_to(ROOT)))
    return {k: sorted(v) for k, v in names.items()}


def registry_names() -> set:
    text = DOC.read_text()
    m = re.search(
        r"<!-- metric-registry:start -->(.*?)<!-- metric-registry:end -->",
        text,
        re.S,
    )
    if not m:
        sys.exit(f"{DOC}: metric-registry markers not found")
    names = set()
    for line in m.group(1).splitlines():
        row = ROW_RE.match(line.strip())
        if row and row.group(1) != "name":
            names.add(row.group(1))
    return names


def main() -> int:
    emitted = emitted_names()
    registry = registry_names()
    static_registry = {n for n in registry if "<" not in n}

    problems = []
    for name in sorted(set(emitted) - static_registry):
        problems.append(
            f"emitted but not in registry: {name!r} "
            f"(from {', '.join(emitted[name])})"
        )
    for name in sorted(static_registry - set(emitted)):
        problems.append(f"in registry but never emitted: {name!r}")

    if problems:
        print(f"check_metric_names: {len(problems)} problem(s)", file=sys.stderr)
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 1
    dyn = len(registry) - len(static_registry)
    print(
        f"check_metric_names: OK — {len(static_registry)} static names in "
        f"sync, {dyn} dynamic famil{'y' if dyn == 1 else 'ies'} exempt"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
