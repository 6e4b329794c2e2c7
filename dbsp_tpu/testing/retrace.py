"""Runtime compilation-stability sentinel: compile counting + transfer
guard over the steady-state tick.

The runtime half of the retrace sanitizer (static half: ``tools/
check_retrace.py``; registry: ``dbsp_tpu.retrace``), the way
``testing/tsan.py`` is the runtime half of the concurrency sanitizer.
Inside a :func:`session` — or process-wide under
``DBSP_TPU_RETRACE_SENTINEL=1`` — every watched
:class:`~dbsp_tpu.compiled.compiler.CompiledHandle` is instrumented:

* a ``logging.Handler`` on JAX's compile logger records every program
  XLA compiles BY NAME (the ``Compiling jit(<fn>)`` debug record carries
  the jitted function's ``__name__`` — exactly the name
  ``retrace.RETRACE_SCHEMA`` keys on — wrapped in the name of the
  transformation that compiled it);
* the handle's program builders (``_make_step`` / ``_make_scan``) and
  cause annotations (``_note_cause``) are wrapped so every DECLARED
  compile opportunity is ledgered: a construction permits one compile of
  its program, a ``residency`` cause note permits one more (tier flips
  recompile through the structure-keyed jit cache without a new
  construction);
* ``handle._steady_guard`` is armed to ``"disallow"``: the jitted step /
  scan call runs under ``jax.transfer_guard("disallow")``, so an
  IMPLICIT device<->host transfer in the steady tick — the class
  ``tools/check_hotpath.py``'s syntactic pass cannot see — raises at the
  dispatch site with a stack. Explicit ``jax.device_put`` /
  ``jax.device_get`` (the tick-cursor re-upload on a discontinuity, the
  validation fetch) remain legal.

:func:`check` raises :class:`~dbsp_tpu.retrace.RetraceError` when any
program in ``retrace.SENTINEL_PROGRAMS`` compiled more times than the
ledger allows — an undeclared recompile (~12ms trace+compile on this
CPU, seconds to minutes on a TPU, PER OCCURRENCE in the steady state).
Violations are NOT waivable at runtime: fix the retrace or declare the
cause in the schema (``# retrace: ok`` only waives static findings).

Typical test shape::

    from dbsp_tpu.testing import retrace as sentinel

    with sentinel.session(ch) as report:
        ch.run_ticks(t0, n, ...)        # steady state, post-warmup
    assert report.undeclared() == []    # or sentinel.check() to raise

Counts for programs OUTSIDE the sentinel set (drains, copies, lifted
SPMD callables) are informational — bench.py's ``retrace`` detail block
reports them per declared cause so perf claims can state "zero
undeclared recompiles" as recorded evidence. Names that collide with
eagerly-dispatched jnp primitives (``maximum``) over-count there; the
hard gate only reads the distinctive step-path names.
"""

from __future__ import annotations

import logging
import os
import threading
from collections import Counter
from typing import Dict, List, Optional

from dbsp_tpu.retrace import (CAUSES, RETRACE_SCHEMA, RetraceError,
                              SENTINEL_PROGRAMS, validate_schema)

__all__ = [
    "enable", "disable", "enabled", "watch", "unwatch", "maybe_watch",
    "note_construction", "reset", "compile_counts", "session", "Report",
    "check", "dryrun",
]

#: the logger that emits the ``Compiling jit(<fn>)`` debug record
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla",)

_state_lock = threading.RLock()
_ACTIVE = os.environ.get("DBSP_TPU_RETRACE_SENTINEL", "0") not in ("", "0")
_COMPILES: Counter = Counter()        # program name -> observed compiles
_CONSTRUCTIONS: Counter = Counter()   # program name -> builder calls
_CAUSE_NOTES: Counter = Counter()     # flight cause -> notes on watched
_WATCHED: List = []                   # handles instrumented this session
_HANDLER: Optional[logging.Handler] = None
_SAVED_LEVELS: Dict[str, int] = {}
_SAVED_PROPAGATE: Dict[str, bool] = {}

#: every program name any schema entry declares (log filter)
_SCHEMA_NAMES = frozenset(p.split(".", 1)[1] for p in RETRACE_SCHEMA)


def _program_name(module_name: str) -> str:
    """``jit(step_fn)`` -> ``step_fn``: the compile record names the XLA
    module, which is the function's name inside the transformation's."""
    head, sep, rest = module_name.partition("(")
    return rest[:-1] if sep and rest.endswith(")") else module_name


class _CompileLogHandler(logging.Handler):
    """Counts ``Compiling jit(<fn>)`` records for schema'd program names."""

    def emit(self, record: logging.LogRecord) -> None:
        try:
            if isinstance(record.msg, str) and \
                    record.msg.startswith("Compiling") and record.args:
                name = _program_name(str(record.args[0]))
                if name in _SCHEMA_NAMES:
                    with _state_lock:
                        _COMPILES[name] += 1
        except Exception:  # noqa: BLE001 — a log hook must never throw
            pass


def _hook_logs() -> None:
    global _HANDLER
    if _HANDLER is not None:
        return
    _HANDLER = _CompileLogHandler(level=logging.DEBUG)
    for lname in _COMPILE_LOGGERS:
        logger = logging.getLogger(lname)
        _SAVED_LEVELS[lname] = logger.level
        _SAVED_PROPAGATE[lname] = logger.propagate
        logger.setLevel(logging.DEBUG)
        # our handler is attached DIRECTLY; stop the debug flood from
        # also reaching ancestor handlers (stderr) while hooked
        logger.propagate = False
        logger.addHandler(_HANDLER)


def _unhook_logs() -> None:
    global _HANDLER
    if _HANDLER is None:
        return
    for lname in _COMPILE_LOGGERS:
        logger = logging.getLogger(lname)
        logger.removeHandler(_HANDLER)
        logger.setLevel(_SAVED_LEVELS.get(lname, logging.NOTSET))
        logger.propagate = _SAVED_PROPAGATE.get(lname, True)
    _SAVED_LEVELS.clear()
    _SAVED_PROPAGATE.clear()
    _HANDLER = None


def note_construction(name: str) -> None:
    """Ledger one declared compile opportunity for program ``name`` (the
    wrapped builders call this; tests seed synthetic ledgers with it)."""
    with _state_lock:
        _CONSTRUCTIONS[name] += 1


def watch(handle) -> None:
    """Instrument one CompiledHandle: wrap its program builders and cause
    notes into the ledger, arm the steady-state transfer guard.
    Idempotent."""
    if any(h is handle for h in _WATCHED):
        return
    validate_schema()
    _hook_logs()
    orig_step, orig_scan = handle._make_step, handle._make_scan
    orig_note = handle._note_cause
    scan_name = "_scan_body" if handle.mesh is None else "scan_fn"

    def make_step():
        note_construction("step_fn")
        return orig_step()

    def make_scan(n):
        note_construction(scan_name)
        return orig_scan(n)

    def note_cause(cause):
        with _state_lock:
            _CAUSE_NOTES[cause] += 1
        orig_note(cause)

    handle._make_step = make_step
    handle._make_scan = make_scan
    handle._note_cause = note_cause
    handle._steady_guard = "disallow"
    with _state_lock:
        _WATCHED.append(handle)


def unwatch(handle) -> None:
    """Remove the instrumentation ``watch`` installed (instance-attribute
    shadows) and disarm the transfer guard."""
    for attr in ("_make_step", "_make_scan", "_note_cause"):
        handle.__dict__.pop(attr, None)
    handle._steady_guard = None
    with _state_lock:
        for i, h in enumerate(_WATCHED):
            if h is handle:
                del _WATCHED[i]
                break


def maybe_watch(handle) -> None:
    """Construction hook ``compile_circuit`` calls: a no-op (one flag
    check) unless the sentinel is on."""
    if _ACTIVE:
        watch(handle)


def enable() -> None:
    global _ACTIVE
    _ACTIVE = True


def disable() -> None:
    global _ACTIVE
    _ACTIVE = False


def enabled() -> bool:
    return _ACTIVE


def reset() -> None:
    with _state_lock:
        _COMPILES.clear()
        _CONSTRUCTIONS.clear()
        _CAUSE_NOTES.clear()


def compile_counts() -> Dict[str, int]:
    """Observed compiles per schema'd program name (all programs, not
    just the hard-gated sentinel set)."""
    with _state_lock:
        return dict(_COMPILES)


class Report:
    """Point-in-time view of the ledger; :meth:`undeclared` is the gate."""

    def __init__(self):
        self.refresh()

    def refresh(self) -> "Report":
        with _state_lock:
            self.compiles = dict(_COMPILES)
            self.constructions = dict(_CONSTRUCTIONS)
            self.causes = dict(_CAUSE_NOTES)
        return self

    def allowance(self, name: str) -> int:
        """Declared compile opportunities for a sentinel program: one per
        builder call, plus one per ``residency`` cause note (tier flips
        re-specialize through the structure-keyed cache without a new
        construction)."""
        return self.constructions.get(name, 0) + \
            self.causes.get("residency", 0)

    def undeclared(self) -> List[str]:
        out = []
        for name in SENTINEL_PROGRAMS:
            seen = self.compiles.get(name, 0)
            allowed = self.allowance(name)
            if seen > allowed:
                out.append(
                    f"{name}: {seen} compile(s) observed, "
                    f"{allowed} declared (constructions="
                    f"{self.constructions.get(name, 0)}, residency notes="
                    f"{self.causes.get('residency', 0)}) — an undeclared "
                    "retrace in the steady state; causes noted: "
                    f"{sorted(self.causes)} (vocabulary: "
                    f"{sorted(CAUSES)})")
        return out

    def summary(self) -> dict:
        """The bench-detail block: per-program compile counts joined with
        their declared causes, plus the guard status."""
        self.refresh()
        programs = {}
        for prog, causes in sorted(RETRACE_SCHEMA.items()):
            name = prog.split(".", 1)[1]
            n = self.compiles.get(name, 0)
            if n or name in SENTINEL_PROGRAMS:
                programs[prog] = {"compiles": n,
                                  "declared_causes": sorted(causes)}
        return {
            "programs": programs,
            "cause_notes": dict(sorted(self.causes.items())),
            "undeclared": self.undeclared(),
            "transfer_guard": "disallow",
        }


def check() -> None:
    """Raise :class:`RetraceError` on any undeclared sentinel-program
    compile. NOT waivable: fix the retrace or declare the cause."""
    bad = Report().undeclared()
    if bad:
        raise RetraceError(
            f"{len(bad)} undeclared recompile(s):\n  " + "\n  ".join(bad))


class session:
    """``with retrace.session(ch, ...) as report:`` — hook the compile
    log, reset the ledger, instrument the given handles (guard armed) for
    the block; ``report`` reflects the ledger at exit. Handles compiled
    INSIDE the block are auto-watched (``maybe_watch`` runs at the end of
    ``compile_circuit``)."""

    def __init__(self, *handles):
        self.handles = list(handles)
        self.report = Report()
        self._was_active = False

    def __enter__(self) -> Report:
        self._was_active = _ACTIVE
        reset()
        enable()
        _hook_logs()
        for h in self.handles:
            watch(h)
        return self.report

    def __exit__(self, *exc):
        self.report.refresh()
        with _state_lock:
            watched = list(_WATCHED)
        for h in watched:
            unwatch(h)
        if not self._was_active:
            disable()
            _unhook_logs()
        return False


# ---------------------------------------------------------------------------
# smoke dryrun (tools/lint_all.py `retrace` front)
# ---------------------------------------------------------------------------


def dryrun(ticks: int = 8) -> dict:
    """Sentinel smoke: a small compiled pipeline's steady state must come
    out with zero undeclared recompiles under an armed transfer guard,
    and a seeded per-value retrace (python-valued tick burned in as a
    static) must be CAUGHT. Raises on either failing; returns a summary.

    NO global jax.config mutation here (tier-1 runs this in-process);
    the CPU pin comes from the caller's environment."""
    import jax
    import jax.numpy as jnp

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import compile_circuit
    from dbsp_tpu.operators import add_input_zset

    def build(c):
        s, h = add_input_zset(c, [jnp.int64], [jnp.int64])
        return h, s.integrate().output()

    handle, (h, out) = Runtime.init_circuit(1, build)

    def gen_fn(tick):
        from dbsp_tpu.zset.batch import Batch
        keys = (jnp.reshape(tick % 7, (1,)).astype(jnp.int64),)
        vals = (jnp.ones((1,), jnp.int64),)
        w = jnp.ones((1,), jnp.int64)
        return {h: Batch(keys, vals, w, runs=(1,))}

    ch = compile_circuit(handle, gen_fn=gen_fn)
    with session(ch) as report:
        ch.run_ticks(0, ticks, validate_every=4)
        ch.validate()
    clean = report.undeclared()
    if clean:
        raise RetraceError("dryrun steady state not clean:\n  " +
                           "\n  ".join(clean))
    if report.compiles.get("step_fn", 0) == 0:
        raise AssertionError(
            "retrace dryrun: no step_fn compile observed — the compile-"
            "log hook has rotted (the clean result would be vacuous)")

    # non-vacuity: a seeded per-value retrace MUST be caught. tick rides
    # as a STATIC here — the python-branch anti-pattern R001/R002 exist
    # for: every distinct value is a fresh cache key, a compile per tick.
    def step_fn(state, tick):
        if tick % 2 == 0:          # python branch on the static tick
            return state + 1
        return state - 1

    seeded = jax.jit(step_fn, static_argnums=(1,))
    with session() as report2:
        note_construction("step_fn")   # ONE declared compile
        st = jnp.zeros((), jnp.int64)
        for t in range(3):             # three distinct static values
            st = seeded(st, t)
    caught = report2.undeclared()
    if not caught:
        raise AssertionError(
            "retrace dryrun: the seeded per-value retrace was NOT "
            "caught — the sentinel has rotted")
    summary = {"steady_undeclared": 0,
               "steady_step_compiles": report.compiles.get("step_fn", 0),
               "seeded_defect_caught": True}
    print(f"retrace dryrun: ok {summary}")
    return summary


if __name__ == "__main__":
    # standalone CLI: pin the platform via env BEFORE jax imports (own
    # process only — in-process callers inherit their host's config)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    dryrun()
