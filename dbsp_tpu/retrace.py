"""Compilation-stability registry: every legal recompile, every donation.

The compiled engine's whole value proposition is that the steady-state
tick is ONE cached XLA program — BENCH r06 measured a ~12x throughput
decay from an oscillating-layout retrace-per-interval before it was
hand-fixed, and the donated-buffer aliasing class (a ``jnp.asarray``
zero-copy view riding into a ``donate_argnums`` pytree -> XLA frees the
memory under the view -> garbage int64s / SIGSEGV one tick later) has
been fixed by hand and re-documented in prose twice (checkpoint decoder,
residency tier movers). This module makes both disciplines declared data,
the way ``checkpoint.STATE_SCHEMA`` declares persistence and
``concurrency.CONCURRENCY_SCHEMA`` declares guards:

* :data:`RETRACE_SCHEMA` — every jitted program dispatched on the step /
  maintenance path, with the closed set of CAUSES under which it may
  legally (re)compile. A compile outside the declared set is a defect:
  on this CPU it costs ~12ms of trace+compile per occurrence; on a
  TPU it costs seconds to minutes.
* :data:`DONATION_SCHEMA` — every ``donate_argnums`` boundary, with the
  positions donated and the in-module names the donating callable is
  bound to (for the read-after-donation walk).
* :data:`DONATION_PRODUCERS` — every function whose results are allowed
  to feed a donated pytree, each with the owning-copy invariant it must
  uphold (the D001 escape walk starts from these).

Checked in both directions by ``tools/check_retrace.py`` (an undeclared
jit site in a registered module AND a stale schema entry are both
findings), enforced at runtime by ``dbsp_tpu/testing/retrace.py`` (jit
cache hooked per schema'd program; ``jax.transfer_guard`` armed over the
steady-state tick region), and gated at zero in tier-1 by
``tests/test_retrace.py``.

Deliberately NOT schema'd:

* operator / zset kernels (``zset/kernels.py``, ``operators/``,
  ``timeseries/``): on the compiled path they are traced INLINE into the
  step program and never dispatch as top-level programs — their
  static-config recompiles are the step program's, already declared
  here. The host engine dispatches them eagerly, but its per-dispatch
  overhead is the reason the compiled engine exists; retrace discipline
  for the host path would gate a cost model we do not claim.
* ``obs/flight.py`` / serving-plane modules: no jit sites; anything
  added there lands in a registered module or trips R005 when one of
  these modules grows a jit.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

#: waiver comment for the static pass (``tools/check_retrace.py``) — same
#: idiom as ``# hotpath: ok`` / ``# concurrency: ok``: suppresses the
#: finding on its line, MUST state the invariant that makes it safe, and
#: is itself audited (a waiver that no longer suppresses anything is a
#: W001 finding; see tools/schema_walk.py). Runtime sentinel violations
#: are NOT waivable.
WAIVER = "# retrace: ok"

#: the closed vocabulary of legal (re)compile causes. ``flight`` names
#: the flight-recorder cause annotation that accompanies the recompile on
#: the live path (dbsp_tpu/obs/flight.py event kinds), so the runtime
#: sentinel can join observed compiles against declared causes.
CAUSES: Dict[str, str] = {
    "first": "first dispatch after construction traces and compiles the "
             "program (flight cause 'retrace' — _dispatch notes it)",
    "retrace": "a capacity change invalidated the program: maintain tail "
               "growth, grow() after CompiledOverflow, presize() — each "
               "drops _step_jit/_scan_jits and notes flight cause "
               "'retrace'",
    "residency": "a tier transition changed the INPUT STRUCTURE (device "
                 "leaf -> numpy operand or back); jax.jit caches per "
                 "structure so the old program stays cached — flight "
                 "cause 'residency'",
    "chunk": "scanned dispatch compiles one program per chunk length n "
             "(_scan_jits is keyed by n); a growth run with a stable "
             "validation interval compiles exactly one",
    "grow": "a static-capacity operand (bucketed cap) changed — "
            "maintenance drains compile per (cap, structure) cache key",
    "structure": "the state pytree's structure changed (new levels after "
                 "a grow, cold levels interleaved) — snapshot copies and "
                 "requirement maxes re-specialize",
    "profile": "EXPLAIN ANALYZE segments AOT-compile per profile_ticks "
               "invocation and are discarded with it (obs/opprofile.py)",
    "config": "compiled once per static configuration key (mesh, kernel "
              "factory, static args) through a bounded lru_cache",
}

#: modules whose jit sites must ALL be declared below — tools/
#: check_retrace.py R005 fires on an undeclared ``jax.jit`` in any of
#: these, R006 on a schema entry whose site vanished. Paths relative to
#: the repo root.
RETRACE_MODULES: Tuple[str, ...] = (
    "dbsp_tpu/compiled/compiler.py",
    "dbsp_tpu/compiled/driver.py",
    "dbsp_tpu/residency.py",
    "dbsp_tpu/checkpoint.py",
    "dbsp_tpu/obs/opprofile.py",
    "dbsp_tpu/parallel/lift.py",
    "dbsp_tpu/parallel/exchange.py",
)

#: program -> {cause: why it applies to THIS program}. Keys are
#: ``<module basename>.<program name>`` where the program name is what
#: XLA's compile log reports: the function passed to ``jax.jit`` (its
#: ``__name__``) — for non-function jit operands, the enclosing def.
#: Causes must come from :data:`CAUSES`.
RETRACE_SCHEMA: Dict[str, Dict[str, str]] = {
    # -- the step path (hard-gated at zero undeclared by the sentinel) --
    "compiler.step_fn": {
        "first": "built lazily by _dispatch when _step_jit is None",
        "retrace": "maintain/grow/presize drop _step_jit; the overflow "
                   "replay in run_ticks notes the cause before replaying",
        "residency": "_enforce_residency changes hot/cold splits — "
                     "structure-keyed recompile, old program kept",
    },
    "compiler._scan_body": {
        "first": "built by step_scanned on the first chunk of length n",
        "chunk": "_scan_jits caches one program per chunk length",
        "retrace": "same invalidations as step_fn (caches cleared "
                   "together)",
        "residency": "structure-keyed like step_fn",
    },
    "compiler.scan_fn": {
        "first": "SPMD variant of _scan_body (mesh is not None)",
        "chunk": "same per-length cache",
        "retrace": "same invalidations as step_fn",
    },
    # -- maintenance / bookkeeping programs (counted, reported in bench
    #    detail; not hard-gated — their caches key on declared statics) --
    "compiler._copy_tree": {
        "first": "snapshot()/restore()/prewarm copy the state pytree",
        "structure": "one compile per state-pytree structure (levels "
                     "appear on grow, cold levels leave the hot tree)",
    },
    "compiler._drain_pair": {
        "first": "maintenance drain, full-source variant",
        "grow": "static cap operand — one compile per receiver bucket",
        "structure": "level layouts differ across (key dtypes, widths)",
    },
    "compiler._drain_slice": {
        "first": "maintenance drain, budgeted-slice variant",
        "grow": "static cap operand like _drain_pair",
        "structure": "level layouts differ across (key dtypes, widths)",
    },
    "compiler.maximum": {
        "first": "requirement running-max (jax.jit(jnp.maximum))",
        "structure": "re-specializes when the requirement vector length "
                     "changes (checks added on grow)",
    },
    # -- off-path programs --
    "opprofile.fn": {
        "profile": "per-node segments and the generator harness are "
                   "lowered+compiled per profile run, then dropped",
    },
    "lift._lifted_jit": {
        "config": "one SPMD callable per (mesh, factory, statics) via "
                  "lru_cache(1024); worker_scalar exists so VALUES ride "
                  "as operands instead of forcing per-value recompiles",
    },
    "exchange._shard_kernel": {
        "config": "static nworkers — one compile per worker count",
    },
    "exchange._sharded_consolidate": {
        "config": "one compile per mesh via lru_cache",
    },
}

#: the step-path subset the runtime sentinel hard-gates: in a
#: steady-state run EVERY compile of these must be attributable to a
#: declared cause noted on the handle; an unattributed compile is a
#: violation (NOT waivable at runtime).
SENTINEL_PROGRAMS: Tuple[str, ...] = (
    "step_fn", "_scan_body", "scan_fn")


class DonationSite(NamedTuple):
    """One ``donate_argnums`` boundary."""

    #: repo-relative file declaring the jit
    file: str
    #: donated argument positions, as written at the jit site
    argnums: Tuple[int, ...]
    #: in-module names the donating callable is bound to at call sites
    #: (the D002 read-after-donation walk tracks calls through these)
    call_names: Tuple[str, ...]
    #: the invariant making the donation safe
    why: str


#: program -> donation boundary. Every ``donate_argnums=`` occurrence in
#: a registered module must be declared here (D003 otherwise; stale
#: entries are D004).
DONATION_SCHEMA: Dict[str, DonationSite] = {
    "compiler.step_fn": DonationSite(
        "dbsp_tpu/compiled/compiler.py", (0,), ("_step_jit",),
        "donating the state pytree lets XLA alias untouched trace levels "
        "input->output instead of copying ~tens of MB per tick; cold "
        "(numpy) levels ride OUTSIDE the donated tree as per-call "
        "operands (_split_states), snapshots are real copies"),
    "compiler._scan_body": DonationSite(
        "dbsp_tpu/compiled/compiler.py", (0,), ("fn",),
        "same state donation as step_fn, per scanned chunk"),
    "compiler.scan_fn": DonationSite(
        "dbsp_tpu/compiled/compiler.py", (0,), ("fn",),
        "same state donation as step_fn, SPMD scanned chunk"),
    "compiler._drain_pair": DonationSite(
        "dbsp_tpu/compiled/compiler.py", (0, 1), ("_drain_pair",),
        "receiver and source levels are consumed; maintain() always "
        "feeds _copy_tree copies so handle state is never donated here"),
    "compiler._drain_slice": DonationSite(
        "dbsp_tpu/compiled/compiler.py", (0, 1), ("_drain_slice",),
        "same copy-in contract as _drain_pair"),
}

#: (file, qualname) -> the owning-copy invariant. These are the functions
#: whose RESULTS reach a donated pytree (trace state); the D001 escape
#: walk flags any return value produced by ``jnp.asarray`` /
#: ``np.frombuffer`` / another zero-copy view that is not wrapped in an
#: owning copy before it escapes. ``*.name`` matches the method in every
#: class of the file.
DONATION_PRODUCERS: Dict[Tuple[str, str], str] = {
    ("dbsp_tpu/checkpoint.py", "_Decoder._arr"):
        "restore decodes blob bytes into trace state the step program "
        "donates — jnp.array (a COPY) or XLA frees the decoder's buffer "
        "under it (observed: garbage int64 state one tick after restore, "
        "flaky SIGSEGV)",
    ("dbsp_tpu/residency.py", "to_device"):
        "a promoted level rejoins the donated hot pytree — jnp.array (a "
        "COPY), never asarray, or the donation frees host memory the "
        "residency bookkeeping still reads",
    ("dbsp_tpu/residency.py", "to_host"):
        "the demoted level must own its bytes: np.array (a COPY) — "
        "asarray could zero-copy-wrap the device buffer a later donation "
        "frees (the same hazard in reverse)",
    ("dbsp_tpu/compiled/compiler.py", "_copy_tree"):
        "jnp.copy per leaf: snapshots/restores must produce buffers the "
        "next donating dispatch can consume without invalidating the "
        "snapshot",
    ("dbsp_tpu/compiled/cnodes.py", "*.init_state"):
        "initial states are freshly materialized device buffers "
        "(jnp.zeros/full) — nothing upstream owns them",
}


class RetraceError(RuntimeError):
    """Schema violation raised by the runtime sentinel's ``check()``."""


def program_module(program: str) -> str:
    """'compiler.step_fn' -> 'compiler' (schema-key module basename)."""
    return program.split(".", 1)[0]


def module_basename(rel: str) -> str:
    """'dbsp_tpu/compiled/compiler.py' -> 'compiler'."""
    base = rel.replace("\\", "/").rsplit("/", 1)[-1]
    return base[:-3] if base.endswith(".py") else base


def schema_for_module(rel: str) -> Dict[str, Dict[str, str]]:
    """The RETRACE_SCHEMA entries declared against one module file."""
    base = module_basename(rel)
    return {prog: causes for prog, causes in RETRACE_SCHEMA.items()
            if program_module(prog) == base}


def validate_schema() -> None:
    """Internal consistency: every declared cause is in the closed
    vocabulary; every donation entry names a registered module. Raises
    ``ValueError`` — called by the static pass and the sentinel."""
    for prog, causes in RETRACE_SCHEMA.items():
        if not causes:
            raise ValueError(f"RETRACE_SCHEMA[{prog!r}] declares no cause")
        for cause in causes:
            if cause not in CAUSES:
                raise ValueError(
                    f"RETRACE_SCHEMA[{prog!r}] uses undeclared cause "
                    f"{cause!r} (closed vocabulary: {sorted(CAUSES)})")
    for prog, site in DONATION_SCHEMA.items():
        if site.file not in RETRACE_MODULES:
            raise ValueError(
                f"DONATION_SCHEMA[{prog!r}] points at {site.file!r}, "
                "which is not in RETRACE_MODULES")
        if prog not in RETRACE_SCHEMA:
            raise ValueError(
                f"DONATION_SCHEMA[{prog!r}] has no RETRACE_SCHEMA entry "
                "— a donating program is always a compiled program")
    for prog in SENTINEL_PROGRAMS:
        if not any(p.split(".", 1)[1] == prog for p in RETRACE_SCHEMA):
            raise ValueError(
                f"SENTINEL_PROGRAMS names {prog!r} with no schema entry")
