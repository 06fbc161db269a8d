"""Readers of what the harness's own clock and loop recorded (`host_clock`).

Each takes the run's `ctx` and its declaration's `params`, and returns a
number or None (nothing to read: the metric is left out of the line)."""

from perfbench import traffic as tg


def percentile(ctx, series, q, missing=None):
    """Nearest-rank percentile of a sample series; `missing` names a count
    of samples that are worse than any recorded (failed requests)."""
    values = ctx.samples.get(series)
    if not values:
        return None
    return tg.percentile(values, q, int(ctx.counters.get(missing, 0))
                         if missing else 0)


def mean(ctx, series):
    values = ctx.samples.get(series)
    return sum(values) / len(values) if values else None


def counter(ctx, name, scale=1.0):
    value = ctx.counters.get(name)
    return None if value is None else value * scale


def share(ctx, part, whole):
    """100 * counter `part` / counter `whole`."""
    whole_v = ctx.counters.get(whole)
    if not whole_v or ctx.counters.get(part) is None:
        return None
    return 100.0 * ctx.counters[part] / whole_v
