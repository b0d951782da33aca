"""Read Prometheus text as the server's ``/metrics`` prints it (stdlib only)."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})? (\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    """``{(name, frozenset(labels.items())): float}``; comment lines dropped."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        labels = frozenset(_LABEL.findall(m.group(2) or ""))
        out[(m.group(1), labels)] = value
    return out


def value(parsed: dict, name: str, labels: dict | None = None):
    """Sum of the series of that name whose labels include ``labels``; None
    when there is none (a reader then returns nothing)."""
    want = set((labels or {}).items())
    hits = [v for (n, ls), v in parsed.items() if n == name and want <= set(ls)]
    return sum(hits) if hits else None


def delta(before: dict, after: dict, name: str, labels: dict | None = None):
    """after - before of a series; a series absent before counts as 0."""
    b, a = value(before, name, labels), value(after, name, labels)
    return None if a is None else a - (b or 0.0)


def labels_of(parsed: dict, name: str) -> dict:
    for (n, ls), _ in parsed.items():
        if n == name:
            return dict(ls)
    return {}
