"""A statistic of one of the trainer's host spans over the window's steps."""


def percentile(values, q):
    """Linear-interpolated percentile (q in [0, 100])."""
    ordered = sorted(values)
    rank = (q / 100.0) * (len(ordered) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def read(ctx, span, stat):
    values = [rec[span] for rec in ctx["spans"] if span in rec]
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "p95":
        return percentile(values, 95.0)
    raise ValueError(f"unknown statistic {stat!r}")
