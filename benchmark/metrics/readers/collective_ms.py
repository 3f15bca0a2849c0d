"""Collective milliseconds per traced step on the fullest device; with
``exposed`` the part during which no other op runs there. A trace with no
collective op has nothing to read."""


def read(ctx, exposed):
    trace = ctx["trace"]
    if not trace or not trace.get("devices"):
        return None
    dev = trace["fullest"]
    if not dev["collective_ops"]:
        return None
    key = "collective_exposed_s" if exposed else "collective_s"
    return 1e3 * dev[key] / dev["steps"]
