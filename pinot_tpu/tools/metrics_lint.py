"""Metric-name lint: every name used in the codebase must be cataloged.

The per-role catalogs in ``utils/metrics.py`` (``BROKER_METRIC_CATALOG``
etc.) are the single source of truth for series names.  This lint scans
the ``pinot_tpu`` package source for ``.meter("...")`` / ``.timer(...)``
/ ``.gauge(...)`` call sites and fails on any name that does not match
a catalog entry — so a typo'd metric name cannot silently fork a new
series that dashboards and alerts never see.

Dynamic names are declared in the catalogs with ``*`` wildcards
(``phase.*``, ``*.segmentCount``); an f-string call site is normalized
by replacing each ``{...}`` part with ``*`` before matching.  A literal
call site needs a literal entry: a wildcard (``phase.*``) stands for
the names code composes, not for whatever a new call site spells.

Run standalone (``python -m pinot_tpu.tools.metrics_lint``) or as the
tier-1 test ``tests/test_observability.py::test_metrics_lint``.
"""
from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Tuple

# .meter("name") / .timer(f"phase.{x}") / .gauge('...') call sites
_CALL_RE = re.compile(
    r"""\.(?:meter|timer|gauge)\(\s*(f?)(['"])((?:(?!\2).)+)\2""",
)
# {expr} parts of an f-string (no nested-brace support needed here)
_FSTRING_EXPR_RE = re.compile(r"\{[^{}]*\}")


def _normalize(fprefix: str, name: str) -> str:
    """Call-site literal -> match pattern ('phase.{n}' -> 'phase.*')."""
    if fprefix:
        return _FSTRING_EXPR_RE.sub("*", name)
    return name


_CANON_RE = re.compile(r"\*+")


def _matches(used: str, entry: str) -> bool:
    """A literal use matches a literal entry exactly; an f-string use
    (normalized to ``*``) matches an entry with the same fixed skeleton,
    or any literal entry the pattern covers (``heal.*`` is satisfied by
    ``heal.deviceFailures``)."""
    import fnmatch

    if "*" in used:
        if _CANON_RE.sub("*", used) == _CANON_RE.sub("*", entry):
            return True
        return "*" not in entry and fnmatch.fnmatchcase(entry, used)
    return used == entry


def collect_usages(package_dir: str) -> List[Tuple[str, int, str]]:
    """[(relpath, lineno, normalized name)] for every metric call site
    in the package source (tests and tools/ probes are out of scope —
    they may use throwaway registries)."""
    out: List[Tuple[str, int, str]] = []
    for root, dirs, files in os.walk(package_dir):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == os.path.join("tools", "metrics_lint.py"):
                continue  # this file's docstring/regex would self-match
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    for m in _CALL_RE.finditer(line):
                        out.append((rel, lineno, _normalize(m.group(1), m.group(3))))
    return out


def run_lint(package_dir: str = None) -> List[str]:
    """Returns a list of problem strings; empty means clean."""
    from pinot_tpu.utils import metrics as metrics_mod

    if package_dir is None:
        import pinot_tpu

        package_dir = os.path.dirname(os.path.abspath(pinot_tpu.__file__))
    catalog: Dict[str, str] = {}
    for role_catalog in metrics_mod.METRIC_CATALOGS.values():
        catalog.update(role_catalog)
    problems: List[str] = []
    for rel, lineno, name in collect_usages(package_dir):
        if not any(_matches(name, entry) for entry in catalog):
            problems.append(
                f"{rel}:{lineno}: metric name {name!r} is not in any "
                f"per-role catalog (utils/metrics.py) — add it there or "
                f"fix the typo"
            )
    return problems


def main(argv=None) -> int:
    problems = run_lint()
    for p in problems:
        print(p, file=sys.stderr)
    if problems:
        print(f"metrics lint: {len(problems)} problem(s)", file=sys.stderr)
        return 1
    print("metrics lint: clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
