"""Median age of the window's events at first visibility, s — the
steadier companion of the end-to-end ``delta_age_p95_s``.
Layer: tick (io/controller.py, compiled/driver.py)."""


def read(ctx):
    ages = ctx["measures"].delta_ages(ctx["run"])
    return None if not ages else ctx["measures"].percentile(ages, 50)
