#!/usr/bin/env python
"""Generate METRICS.md from the tree's metric registration sites.

The metric surface is ~40 families across obs/, compiled/, io/, zset/ and
parallel/ — README's hand-written tables have already gone stale once.
This tool makes the catalog DERIVED: it AST-walks every
``registry.counter/gauge/histogram/summary("name", "help", labels=...)``
registration under ``dbsp_tpu/`` and renders one sorted table with name,
type, labels, help, and the registration site. Families registered in
more than one place (get-or-create is idiomatic) list every site; the
help text of the first site wins (sites are visited in sorted file
order, so the rendering is deterministic).

Usage:
    python tools/gen_metrics_doc.py             # (re)write METRICS.md
    python tools/gen_metrics_doc.py --check     # exit 1 on drift

The ``--check`` form is a lint front in ``tools/lint_all.py`` (tier-1 via
tests/test_opprofile.py): adding or renaming a metric without
regenerating METRICS.md is a red build, so the committed catalog cannot
rot the way the README tables did.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

_REGISTER_METHODS = {"counter", "gauge", "histogram", "summary"}

HEADER = """\
# METRICS — the exported metric catalog (generated)

Generated by `python tools/gen_metrics_doc.py` from every registration
site under `dbsp_tpu/` — do not edit by hand; `tools/lint_all.py`
(`gen_metrics_doc --check`) fails on drift. Naming and label rules:
`tools/check_metrics.py` (names `dbsp_tpu_<subsystem>_<name>_<unit>`,
label names from the closed `obs.registry.ALLOWED_LABEL_NAMES`
allowlist, per-node families only via the `obs/opprofile.py` gate).

Scrape surfaces: every pipeline's `GET /metrics`
(`dbsp_tpu/io/server.py`, canonical exposition + the legacy
`dbsp_steps`-era names) and the manager's fleet-wide `GET /metrics`
(adds a `pipeline` label). See README §Observability for the subsystem
tour.

Numbers: `MULTICHIP_r01–r06.json` are CPU timesharing (virtual devices on
two cores); what four chips give is the benchmark cell
`nexmark-q4-4w.saturated`'s to say (PERF.md), and its `exchange_*` metrics
read the `dbsp_tpu_exchange_*` families below from the same counters.
"""


def _const_str(node) -> str:
    return node.value if isinstance(node, ast.Constant) and \
        isinstance(node.value, str) else ""


def _labels_of(call: ast.Call) -> List[str]:
    node = None
    for kw in call.keywords:
        if kw.arg == "labels":
            node = kw.value
    if node is None and len(call.args) >= 3:
        node = call.args[2]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def _help_of(call: ast.Call) -> str:
    for kw in call.keywords:
        if kw.arg == "help":
            return _const_str(kw.value)
    if len(call.args) >= 2:
        return _const_str(call.args[1])
    return ""


def collect(pkg_root: str) -> Dict[str, dict]:
    """name -> {kind, labels, help, sites} over every registration call."""
    out: Dict[str, dict] = {}
    for dirpath, _, files in os.walk(pkg_root):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, os.path.dirname(pkg_root))
            with open(path) as f:
                try:
                    tree = ast.parse(f.read())
                except SyntaxError:  # pragma: no cover — tree is importable
                    continue
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call) and
                        isinstance(node.func, ast.Attribute) and
                        node.func.attr in _REGISTER_METHODS and node.args):
                    continue
                name = _const_str(node.args[0])
                if not name.startswith("dbsp_tpu_"):
                    continue
                # file-level sites (no line numbers): the doc should not
                # churn every time an unrelated edit shifts a line
                ent = out.setdefault(name, {
                    "kind": node.func.attr, "labels": _labels_of(node),
                    "help": _help_of(node), "sites": []})
                ent["sites"].append(rel)
                if not ent["help"]:
                    ent["help"] = _help_of(node)
                if not ent["labels"]:
                    ent["labels"] = _labels_of(node)
    return out


def render(families: Dict[str, dict]) -> str:
    lines = [HEADER]
    lines.append(f"{len(families)} families.\n")
    lines.append("| name | type | labels | help | registered at |")
    lines.append("|---|---|---|---|---|")
    for name in sorted(families):
        ent = families[name]
        help_text = " ".join(ent["help"].split())
        lines.append("| `{}` | {} | {} | {} | {} |".format(
            name, ent["kind"],
            ", ".join(f"`{ln}`" for ln in ent["labels"]) or "—",
            help_text or "—",
            "; ".join(f"`{s}`" for s in sorted(set(ent["sites"])))))
    lines.append("")
    return "\n".join(lines)


def generate() -> str:
    return render(collect(os.path.join(_ROOT, "dbsp_tpu")))


def check_drift() -> List[str]:
    """Violation strings when the committed METRICS.md is stale (the
    lint_all front)."""
    path = os.path.join(_ROOT, "METRICS.md")
    want = generate()
    try:
        with open(path) as f:
            have = f.read()
    except OSError:
        return ["METRICS.md missing — run `python tools/gen_metrics_doc.py`"]
    if have != want:
        return ["METRICS.md is stale (metric registrations changed) — "
                "run `python tools/gen_metrics_doc.py`"]
    return []


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--check" in argv:
        violations = check_drift()
        for v in violations:
            print(v)
        print("gen_metrics_doc: " +
              ("ok" if not violations else "stale"))
        return 1 if violations else 0
    with open(os.path.join(_ROOT, "METRICS.md"), "w") as f:
        f.write(generate())
    print("wrote METRICS.md")
    return 0


if __name__ == "__main__":
    sys.exit(main())
