#!/usr/bin/env python
"""CI lint: the metric inventory table in ARCHITECTURE.md must match
the registry exactly.

Scans ``cranesched_tpu/`` (AST, no imports) for every
``REGISTRY.counter/gauge/histogram("crane_...", ...)`` registration and
compares the set against the ``| `crane_...` |`` rows of the
"Metric inventory" table in ARCHITECTURE.md, both directions:

* a registered metric missing from the table fails (undocumented);
* a table row with no registration fails (stale docs).

Run from anywhere:  python tools/check_metrics_docs.py
Wired into the tier-1 lane (``make tier1-lint``).
"""

from __future__ import annotations

import ast
import os
import re
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
PKG = os.path.join(ROOT, "cranesched_tpu")
DOC = os.path.join(ROOT, "ARCHITECTURE.md")

# registered outside the production tree on purpose (none today)
ALLOW_UNDOCUMENTED: set[str] = set()

_FACTORIES = {"counter", "gauge", "histogram"}


def registered_metrics() -> dict[str, list[str]]:
    """name -> [file:line, ...] for every literal crane_* registration."""
    out: dict[str, list[str]] = {}
    for dirpath, _dirnames, filenames in os.walk(PKG):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as fh:
                try:
                    tree = ast.parse(fh.read(), filename=path)
                except SyntaxError as e:  # the lint must not mask it
                    raise SystemExit(f"syntax error in {path}: {e}")
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in _FACTORIES
                        and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)
                        and node.args[0].value.startswith("crane_")):
                    continue
                rel = os.path.relpath(path, ROOT)
                out.setdefault(node.args[0].value, []).append(
                    f"{rel}:{node.lineno}")
    return out


def documented_metrics() -> set[str]:
    """Names from the ARCHITECTURE.md metric-inventory table rows."""
    names = set()
    with open(DOC, encoding="utf-8") as fh:
        for line in fh:
            m = re.match(r"\|\s*`(crane_[a-z0-9_]+)`", line)
            if m:
                names.add(m.group(1))
    return names


def main() -> int:
    reg = registered_metrics()
    doc = documented_metrics()
    if not doc:
        print("check_metrics_docs: no `crane_*` table rows found in "
              "ARCHITECTURE.md (is the metric inventory table gone?)",
              file=sys.stderr)
        return 1
    failures = []
    for name in sorted(set(reg) - doc - ALLOW_UNDOCUMENTED):
        failures.append(
            f"UNDOCUMENTED {name} (registered at {reg[name][0]}) — add "
            f"a row to the ARCHITECTURE.md metric inventory table")
    for name in sorted(doc - set(reg)):
        failures.append(
            f"STALE DOC ROW {name} — documented in ARCHITECTURE.md but "
            f"no registration in cranesched_tpu/")
    if failures:
        print("\n".join(failures), file=sys.stderr)
        return 1
    print(f"METRICS_DOCS_OK registered={len(reg)} documented={len(doc)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
