"""Readers of the device trace (`device_trace`): the reduction lives in
`perfbench/trace.py`; these pick one number out of it. No trace (a run
without `--trace 1`, a rehearsal on a CPU), no number."""

from perfbench import trace as tm


def _ready(ctx):
    return ctx.trace is not None and ctx.trace["devices"] and ctx.trace_window


def idle_share(ctx):
    """100 * (1 - busy / window), busy averaged over the chips used."""
    if not _ready(ctx):
        return None
    w = ctx.trace_window
    return 100.0 * (1.0 - tm.busy_seconds(ctx.trace, w) / ((w[1] - w[0]) / 1e9))


def gap_share(ctx, annotation):
    """Device idle time that falls inside the harness's `annotation` spans,
    as a share of the traced window (first device)."""
    if not _ready(ctx):
        return None
    w = ctx.trace_window
    gaps = tm.idle_gaps(tm.first_device(ctx.trace)["ops"], w)
    inside = tm.attribute_gaps(gaps, ctx.trace["host"]).get(
        tm.HOST_PREFIX + annotation, 0.0)
    return 100.0 * inside / ((w[1] - w[0]) / 1e9)


def op_ms_per(ctx, pattern, per):
    """Self time of the ops matching `pattern`, in ms per unit of the
    counter `per` (rounds, steps) inside the traced window, first device."""
    if not _ready(ctx) or not ctx.counters.get(per):
        return None
    secs = tm.seconds_matching(tm.first_device(ctx.trace)["ops"], pattern,
                               ctx.trace_window)
    return 1e3 * secs / ctx.counters[per]


def op_share_of_busy(ctx, pattern):
    if not _ready(ctx):
        return None
    w = ctx.trace_window
    ops = tm.first_device(ctx.trace)["ops"]
    busy = tm.length(tm.union(tm.spans_of(ops, w))) / 1e9
    return 100.0 * tm.seconds_matching(ops, pattern, w) / busy if busy else None


def exposed_collective_share(ctx):
    """Time in collective ops during which no compute op ran on that device,
    over the window; averaged over the devices."""
    if not _ready(ctx):
        return None
    w = ctx.trace_window
    per = [tm.exposed_collective_seconds(d["ops"], w)
           for d in ctx.trace["devices"].values() if d["ops"]]
    return 100.0 * (sum(per) / len(per)) / ((w[1] - w[0]) / 1e9)
