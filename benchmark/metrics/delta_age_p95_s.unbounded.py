"""The end-to-end ``delta_age_p95_s`` arithmetic, reported per layer in the
cells where it cannot be held to a bound: q3 (one slow tick among 0.15 s
ticks moves the p95 by tens of percent) and q4-4w (no sets of runs on four
chips yet); PERF.md, section 2.
Layer: tick (io/controller.py, compiled/driver.py)."""


def read(ctx):
    ages = ctx["measures"].delta_ages(ctx["run"])
    return None if not ages else ctx["measures"].percentile(ages, 95)
