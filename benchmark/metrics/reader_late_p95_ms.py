"""How late the reader sent each /view read against its schedule, 95th
percentile, ms: a starved load generator must not be read as a fast
server. Layer: load generator (benchmark/)."""


def read(ctx):
    return ctx["measures"].percentile(
        ctx["measures"].reader_lateness_ms(ctx["run"]), 95)
