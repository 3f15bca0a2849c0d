"""The whole step's share of the chips' peak, from the traced steps."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("devices"):
        return None
    dev = trace["fullest"]
    flops = ctx["flops_per_item"] * ctx["items_per_step"] * dev["steps"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (dev["window_s"] * peak)
