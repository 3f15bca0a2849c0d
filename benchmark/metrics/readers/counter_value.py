"""The sum of some of the program's counters or gauges, from its
process-wide registry (`tpu_dp.obs.counters`). None where the program
publishes none of them (a program older than the counters, or a phase it
does not time)."""


def read(ctx, names, scale=1.0):
    from tpu_dp.obs.counters import counters

    counts = counters.snapshot()
    present = [counts[n] for n in names if n in counts]
    if not present:
        return None
    return scale * sum(present)
