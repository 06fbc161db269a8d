"""A kernel's share of its memory roofline where the least bytes depend on
the CONTEXT a decode step reads, which `roofline:hbm_share` cannot pass: the
context is taken from the cell's traffic file (the prompt's mean length and
half the new tokens: the mean over a batch's decode steps), never from the
program. No trace, no peak, or a `counts` without the function: no number,
and the metric is left out of the line."""

from perfbench import flops
from perfbench import trace as tm


def mean_context(traffic):
    """Positions a sequence holds at the mean decode step of a batch."""
    values = traffic["prompt"]["values"]
    return sum(values) / len(values) + traffic["new_tokens"] / 2.0


def hbm_share_at_context(ctx, pattern, per, bytes):
    """100 x `counts.<bytes>(sizes, batch, mean context)` x the counter
    `per` / (published HBM bytes/s x the measured self time of the ops
    matching `pattern`), first device."""
    if ctx.trace is None or not ctx.trace["devices"] or not ctx.trace_window \
            or ctx.peaks is None or not ctx.counters.get(per):
        return None
    count = getattr(flops.family_counts(ctx.sizes, ctx.manifest), bytes, None)
    secs = tm.seconds_matching(tm.first_device(ctx.trace)["ops"], pattern,
                               ctx.trace_window)
    if count is None or not secs:
        return None
    least = count(ctx.sizes, ctx.traffic["batch"], mean_context(ctx.traffic)) \
        * ctx.counters[per]
    return 100.0 * least / (ctx.peaks["hbm_gbps"] * 1e9 * secs)
