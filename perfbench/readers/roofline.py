"""Readers of shares of the chip's published peaks (`perfbench/peaks.json`):
a kernel's share of its memory roofline from the device trace, and a decode
cell's share of the bf16 peak. The bytes and operations come from the
configuration's own `counts` module (`perfbench/flops.py` finds it), never
from the program. No trace, no peak, or a `counts` without the function: no
number, and the metric is left out of the line."""

from perfbench import flops
from perfbench import trace as tm


def hbm_share(ctx, pattern, per, bytes):
    """100 x (the least bytes the ops matching `pattern` must move per unit
    of the counter `per`, `counts.<bytes>(sizes, batch)`) / (published HBM
    bytes/s x their measured self time per unit), first device."""
    if ctx.trace is None or not ctx.trace["devices"] or not ctx.trace_window \
            or ctx.peaks is None or not ctx.counters.get(per):
        return None
    count = getattr(flops.family_counts(ctx.sizes, ctx.manifest), bytes, None)
    secs = tm.seconds_matching(tm.first_device(ctx.trace)["ops"], pattern,
                               ctx.trace_window)
    if count is None or not secs:
        return None
    least = count(ctx.sizes, ctx.traffic["batch"]) * ctx.counters[per]
    return 100.0 * least / (ctx.peaks["hbm_gbps"] * 1e9 * secs)


def decode_mfu(ctx, rate):
    """100 x counter `rate` (output tokens/s) x 2 operations per ACTIVE
    matmul weight a token / (chips x the published bf16 peak)."""
    if ctx.peaks is None or ctx.counters.get(rate) is None:
        return None
    ops = 2.0 * flops.matmul_params(ctx.sizes, manifest=ctx.manifest)
    return 100.0 * ctx.counters[rate] * ops / (
        ctx.chips * ctx.peaks["bf16_tflops"] * 1e12)
