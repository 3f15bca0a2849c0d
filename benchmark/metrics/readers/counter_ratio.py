"""One of the program's counters over another, from its process-wide
registry (`tpu_dp.obs.counters`). The registry counts from the trainer's
construction, so the warm-up epoch is in both. None where the program
publishes neither, or the denominator stands at nought."""


def read(ctx, numerator, denominator, scale=1.0):
    from tpu_dp.obs.counters import counters

    counts = counters.snapshot()
    if numerator not in counts or not counts.get(denominator):
        return None
    return scale * counts[numerator] / counts[denominator]
