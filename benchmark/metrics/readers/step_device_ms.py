"""Device-busy milliseconds per traced step on the fullest device."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("devices"):
        return None
    dev = trace["fullest"]
    return 1e3 * dev["busy_s"] / dev["steps"]
