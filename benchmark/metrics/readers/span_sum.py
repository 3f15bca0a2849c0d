"""A statistic, over the window's steps, of the sum of several of the
trainer's host spans: what the steps that have them all spent in them."""

import statistics

STATS = {"mean": statistics.fmean, "p50": statistics.median}


def read(ctx, spans, stat):
    if stat not in STATS:
        raise ValueError(f"unknown statistic {stat!r}")
    values = [sum(rec[s] for s in spans) for rec in ctx["spans"]
              if all(s in rec for s in spans)]
    return STATS[stat](values) if values else None
