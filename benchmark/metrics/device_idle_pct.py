"""Share of the traced window in which no operation ran on the device, %:
1 - union of the device-operation intervals over the window.
Layer: device."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["window_s"] or trace["busy_s"] is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
