"""Peak device memory after the window, fullest device, in GiB."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return None if not peak else peak / 2.0 ** 30
