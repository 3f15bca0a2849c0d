"""A kernel's least time over the time the device spent in it, from the
ranked device operations of the traced steps (the trace itself is gone by
the time readers run). ``kernels`` are the prefixes of the kernel's
operation names; ``work`` names the module, beside `run.py`, whose
``function(config, length, rows, peaks)`` gives the kernel's least seconds
a step from the configuration's shapes. None where no such operation is
among the ranked ones (a program without the kernel, or a kernel too short
to rank), and where the trace holds no device plane."""

import importlib


def read(ctx, kernels, work, function):
    trace = ctx["trace"]
    if not trace or not trace.get("devices"):
        return None
    dev = trace["fullest"]
    seconds = sum(sec for name, sec in dev["device_ops"]
                  if name.startswith(tuple(kernels)))
    if seconds <= 0.0:
        return None
    length = ctx["items_per_step"] // ctx["global_batch"]
    least = getattr(importlib.import_module(work), function)(
        ctx["config"], length, ctx["batch_per_chip"], ctx["peaks"])
    return 100.0 * least * dev["steps"] / seconds
