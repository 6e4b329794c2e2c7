#!/usr/bin/env python
"""Hot-path lint: no host round-trips in eval bodies; no load-bearing asserts.

Two AST checks over ``dbsp_tpu/`` (wired into the suite as a tier-1 test,
tests/test_analysis.py, and bundled into tools/lint_all.py):

1. **No host round-trips on the hot path.** ``.item()``, ``float(...)``,
   ``np.asarray``/``np.array``, and ``jax.device_get`` each force a
   device->host transfer (~us locally, far more on an accelerator — see
   compiled/compiler.py's rationale). They are banned inside:

     * operator hot-path methods: ``eval`` / ``eval_strict`` /
       ``get_output`` / ``import_value`` defined in any class, and
     * jitted functions: defs decorated with ``jax.jit`` (directly or via
       ``partial(jax.jit, ...)``) or passed to a ``jax.jit(...)`` call
       anywhere in the same module.

   Deliberate synchronization points (the grow-on-demand capacity checks)
   live in driver helpers outside eval bodies; a line that must sync
   inside one carries a ``# hotpath: ok`` waiver comment stating why.

2. **No ``assert`` for user-input validation.** In ``dbsp_tpu/circuit/``
   and ``dbsp_tpu/io/`` — the layers that validate user-built graphs and
   external data — ``assert`` is banned outright: it vanishes under
   ``python -O``, turning validation into undefined behavior. Raise typed
   exceptions (CircuitError / ValueError) instead.

3. **No stray syncs in the compiled per-tick step loop.** In
   ``dbsp_tpu/compiled/``, the methods that form the tick pipeline
   (``step``/``_dispatch``/``_run_pipelined``/``step_scanned``/
   ``run_ticks``/``maintain``/``snapshot``/``restore``) must not call
   ``block_until_ready`` or ``jax.device_get`` directly: the async tick
   pipeline exists precisely because every such sync serializes host and
   device (BENCH r05: ~70% of q3's elapsed was between-tick host work).
   Synchronization belongs in the designated sync points — ``validate()``
   (the one device->host fetch per interval) and ``block()`` — which the
   loop calls at interval boundaries. A deliberate in-loop barrier (the
   depth-1 pipeline wait on tick t-1) carries a ``# hotpath: ok`` waiver
   stating why.

Related hot-path discipline this lint does NOT need to police:
``Batch.consolidate()`` on an already-consolidated batch is free BY
CONSTRUCTION since the sorted-run metadata landed (zset/batch.py — a
1-run batch returns ``self``, counted as ``path="skipped"`` in
``dbsp_tpu_zset_consolidate_total``), so defensive consolidate calls on
canonical batches cost nothing and need no waiver or caller-side guard.

Usage: ``python tools/check_hotpath.py [root]`` — prints violations and
exits 1 when any are found.
"""

from __future__ import annotations

import ast
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

from tools.schema_walk import stale_waivers  # noqa: E402

#: method names whose bodies are operator hot paths (circuit/operator.py)
HOT_METHODS = ("eval", "eval_strict", "get_output", "import_value")

#: directories (relative to the package root) where assert is banned
NO_ASSERT_DIRS = ("circuit", "io")

#: rule 3 — the compiled engine's per-tick step loop: no direct syncs here
STEP_LOOP_DIR = "compiled"
STEP_LOOP_METHODS = ("step", "_dispatch", "_run_pipelined", "step_scanned",
                     "run_ticks", "maintain", "snapshot", "restore")

WAIVER = "# hotpath: ok"


def _iter_py(root: str):
    for dirpath, _, files in os.walk(root):
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def _dotted(node: ast.AST) -> str:
    """'jax.device_get' for Attribute chains, 'float' for Names, else ''."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_jit_expr(node: ast.AST) -> bool:
    """jax.jit / jit, or partial(jax.jit, ...), or a call of either."""
    if isinstance(node, ast.Call):
        if _dotted(node.func) in ("functools.partial", "partial") and \
                node.args and _is_jit_expr(node.args[0]):
            return True
        return _is_jit_expr(node.func)
    return _dotted(node) in ("jax.jit", "jit")


def _jitted_names(tree: ast.AST) -> set:
    """Function names passed to jax.jit(...) anywhere in the module."""
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_expr(node.func) and \
                node.args and isinstance(node.args[0], ast.Name):
            names.add(node.args[0].id)
    return names


def _forbidden_call(node: ast.Call) -> str | None:
    """The rule-1 label if this call is a host round-trip, else None."""
    dotted = _dotted(node.func)
    if isinstance(node.func, ast.Attribute) and node.func.attr == "item" \
            and not node.args:
        return ".item()"
    if dotted == "float":
        return "float()"
    if dotted in ("np.asarray", "numpy.asarray", "np.array", "numpy.array"):
        return dotted + "()"
    if dotted in ("jax.device_get", "device_get"):
        return dotted + "()"
    return None


def _forbidden_sync(node: ast.Call) -> str | None:
    """The rule-3 label if this call synchronizes host and device, else
    None: any .block_until_ready() (method or jax.block_until_ready) or
    jax.device_get inside the compiled step loop."""
    dotted = _dotted(node.func)
    if isinstance(node.func, ast.Attribute) and \
            node.func.attr == "block_until_ready":
        return ".block_until_ready()"
    if dotted in ("jax.block_until_ready", "block_until_ready"):
        return "jax.block_until_ready()"
    if dotted in ("jax.device_get", "device_get"):
        return dotted + "()"
    return None


def _check_sync_body(fn: ast.AST, kind: str, rel: str, lines,
                     violations, used) -> None:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        label = _forbidden_sync(node)
        if label is None:
            continue
        line = lines[node.lineno - 1] if node.lineno - 1 < len(lines) else ""
        if WAIVER in line:
            used.add(node.lineno)
            continue
        violations.append(
            f"{rel}:{node.lineno}: host/device sync {label} inside the "
            f"per-tick step loop ({kind}) — sync only at the designated "
            f"points (validate/block), or waive with '{WAIVER} <reason>'")


def _check_body(fn: ast.AST, kind: str, rel: str, lines, violations,
                used) -> None:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        label = _forbidden_call(node)
        if label is None:
            continue
        line = lines[node.lineno - 1] if node.lineno - 1 < len(lines) else ""
        if WAIVER in line:
            used.add(node.lineno)
            continue
        violations.append(
            f"{rel}:{node.lineno}: host round-trip {label} inside {kind} "
            f"— hoist it off the hot path (or waive with '{WAIVER} "
            "<reason>')")


def check_tree(pkg_root: str) -> list:
    """Return a list of "path:line: message" violation strings."""
    violations = []
    for path in _iter_py(pkg_root):
        with open(path) as f:
            src = f.read()
        rel = os.path.relpath(path, os.path.dirname(pkg_root))
        rel_pkg = os.path.relpath(path, pkg_root)
        try:
            tree = ast.parse(src)
        except SyntaxError as e:  # pragma: no cover — tree is importable
            violations.append(f"{rel}:{e.lineno}: unparsable: {e.msg}")
            continue
        lines = src.splitlines()
        jitted = _jitted_names(tree)
        used: set = set()  # waiver lines that suppressed a finding (W001)

        # rule 1a: operator hot-path methods
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) and \
                            item.name in HOT_METHODS:
                        _check_body(
                            item, f"{node.name}.{item.name}", rel, lines,
                            violations, used)
        # rule 1b: jitted functions (decorated or wrapped)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                is_jit = node.name in jitted or \
                    any(_is_jit_expr(d) for d in node.decorator_list)
                if is_jit:
                    _check_body(node, f"jitted function {node.name}", rel,
                                lines, violations, used)
        # rule 3: no stray syncs in the compiled per-tick step loop
        if rel_pkg.split(os.sep)[0] == STEP_LOOP_DIR:
            for node in ast.walk(tree):
                if isinstance(node, ast.ClassDef):
                    for item in node.body:
                        if isinstance(item, (ast.FunctionDef,
                                             ast.AsyncFunctionDef)) and \
                                item.name in STEP_LOOP_METHODS:
                            _check_sync_body(
                                item, f"{node.name}.{item.name}", rel,
                                lines, violations, used)
        # rule 2: no asserts in circuit/ and io/
        if rel_pkg.split(os.sep)[0] in NO_ASSERT_DIRS:
            for node in ast.walk(tree):
                if isinstance(node, ast.Assert):
                    line = lines[node.lineno - 1] \
                        if node.lineno - 1 < len(lines) else ""
                    if WAIVER in line:
                        used.add(node.lineno)
                        continue
                    violations.append(
                        f"{rel}:{node.lineno}: assert used for validation "
                        "in circuit/ or io/ — stripped under 'python -O'; "
                        "raise a typed exception (CircuitError/ValueError)")
        # W001: waivers that no longer suppress anything (shared audit)
        violations.extend(stale_waivers(src, rel, WAIVER, used))
    return violations


def main(argv=None) -> int:
    root = (argv or sys.argv[1:] or [os.path.join(_ROOT, "dbsp_tpu")])[0]
    violations = check_tree(os.path.abspath(root))
    for v in violations:
        print(v)
    if violations:
        print(f"check_hotpath: {len(violations)} violation(s)")
        return 1
    print("check_hotpath: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
