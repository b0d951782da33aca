"""1 minus the union of the device's op intervals over the traced window."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
