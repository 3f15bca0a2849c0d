"""The step's least time on one chip (benchmark/work.py) over its
device-busy time that is not an exposed collective."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("devices"):
        return None
    dev = trace["fullest"]
    compute_s = (dev["busy_s"] - dev["collective_exposed_s"]) / dev["steps"]
    if compute_s <= 0.0:
        return None
    return 100.0 * ctx["least_step_s"] / compute_s
