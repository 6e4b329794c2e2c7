"""Device time a tick a chip in the exchange's collectives, ms: the
``all-to-all`` and ``all-gather`` operations of the traced window (the
trace's ``XLA Ops`` line, by opcode; the start/done halves of an
asynchronous collective count under the same opcode), summed over the
chips, divided by the chips and by the traced ticks. The bucketize before
and the consolidation after a collective are not in it: they are ordinary
fusions and loops. None where the window held no such operation, as on one
chip.
Layer: exchange (parallel/exchange.py: exchange_local, gather_local)."""

OPCODES = ("all-to-all", "all-gather")


def collective_seconds(ops) -> float:
    """Seconds of the ``[name, seconds]`` rows whose opcode (the second
    word of ``trace_reduce.short_op_name``) is a collective's."""
    total = 0.0
    for name, seconds in ops:
        words = name.split()
        if len(words) > 1 and words[1].startswith(OPCODES):
            total += seconds
    return total


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("ops") or not trace.get("devices"):
        return None
    ticks = min(ctx["traffic"]["trace_ticks"],
                len(ctx["measures"].window_ticks(ctx["run"])))
    seconds = collective_seconds(trace["ops"])
    if not ticks or not seconds:
        return None
    return 1e3 * seconds / trace["devices"] / ticks
