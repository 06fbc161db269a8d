"""A decode kernel's share of its roofline BY CALL: the least time the
mathematics allows one call over the mean device time of the calls the trace
HOLDS. `sparse_roofline:share_at_context` multiplies the least time of a step
by a counter of steps the program ran and divides by the kernel's time in the
trace, which is a share only while the trace holds every call: a profiler
that loses the op events of part of a long decode loop (one traced run of
five did, PERF.md, PR 58: half the steps' ops came back as their `while`'s
own time) then reads the share at twice what it is, past 100%. Here both
sides come from the same events, so a trace that lost some reads what a whole
one reads. Both bounds as there: the LARGER of (least bytes / published HBM
bytes/s) and (least operations / published bf16 peak), from the
configuration's own `counts` module, never from the program. No trace, no
peak, no call in the window, or a `counts` without a function that was asked
for: no number, and the metric is left out of the line."""

import re

from perfbench import trace as tm
from perfbench.flops import family_counts
from perfbench.readers.context import mean_context


def share_per_call(ctx, pattern, calls, bytes=None, flops=None):
    """100 x max(`counts.<bytes>` / HBM bytes/s, `counts.<flops>` / bf16
    FLOP/s) / `sizes[calls]` x the calls in the window / their device time,
    first device: each count is called `(sizes, batch, mean decode context)`
    and is a STEP's, of which a layer's call is one of `sizes[calls]`
    (`num_hidden_layers`: every layer calls the kernel once a step). A call
    is an op whose name matches `pattern`, whole inside the traced window."""
    if ctx.trace is None or not ctx.trace["devices"] or not ctx.trace_window \
            or ctx.peaks is None or not ctx.sizes.get(calls):
        return None
    own = family_counts(ctx.sizes, ctx.manifest)
    counts = [(getattr(own, name, None), peak) for name, peak in (
        (bytes, ctx.peaks["hbm_gbps"] * 1e9),
        (flops, ctx.peaks["bf16_tflops"] * 1e12)) if name]
    rx, (w0, w1) = re.compile(pattern), ctx.trace_window
    times = [dur for raw, start, dur in tm.first_device(ctx.trace)["ops"]
             if start >= w0 and start + dur <= w1
             and rx.search(tm.op_name(raw))]
    if not counts or any(fn is None for fn, _ in counts) or not sum(times):
        return None
    step = max(fn(ctx.sizes, ctx.traffic["batch"], mean_context(ctx.traffic))
               / peak for fn, peak in counts)
    return 100.0 * step / ctx.sizes[calls] * len(times) / (sum(times) / 1e9)
