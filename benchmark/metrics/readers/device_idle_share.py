"""Idle share of the traced window on the fullest device."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("devices"):
        return None
    dev = trace["fullest"]
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
