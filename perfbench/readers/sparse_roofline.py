"""A kernel's share of its roofline where BOTH bounds depend on the context
a step reads and neither alone is the roofline: the least time the
mathematics allows is the LARGER of (least bytes / published HBM bytes/s) and
(least operations / published bf16 peak). `context:hbm_share_at_context` is
the bytes' half alone, which at 128 absorbed heads over shared latent rows is
half a roofline (the two bounds meet there). The bytes and operations come
from the configuration's own `counts` module, never from the program. No
trace, no peak, or a `counts` that lacks a function that was asked for: no
number, and the metric is left out of the line."""

from perfbench import trace as tm
from perfbench.flops import family_counts
from perfbench.readers.context import mean_context


def share_at_context(ctx, pattern, per, bytes=None, flops=None,
                     context="mean"):
    """100 x max(`counts.<bytes>` / HBM bytes/s, `counts.<flops>` / bf16
    FLOP/s) x the counter `per` / the measured self time of the ops matching
    `pattern`, first device; each count is called `(sizes, batch, context)`
    with the traffic file's mean decode context, or its mean prompt for
    `context` "prompt". One of `bytes`, `flops` may be left out."""
    if ctx.trace is None or not ctx.trace["devices"] or not ctx.trace_window \
            or ctx.peaks is None or not ctx.counters.get(per):
        return None
    own = family_counts(ctx.sizes, ctx.manifest)
    asked = [(name, peak) for name, peak in (
        (bytes, ctx.peaks["hbm_gbps"] * 1e9),
        (flops, ctx.peaks["bf16_tflops"] * 1e12)) if name]
    counts = [(getattr(own, name, None), peak) for name, peak in asked]
    secs = tm.seconds_matching(tm.first_device(ctx.trace)["ops"], pattern,
                               ctx.trace_window)
    if not counts or any(fn is None for fn, _ in counts) or not secs:
        return None
    values = ctx.traffic["prompt"]["values"]
    at = sum(values) / len(values) if context == "prompt" \
        else mean_context(ctx.traffic)
    least = max(fn(ctx.sizes, ctx.traffic["batch"], at) / peak
                for fn, peak in counts)
    return 100.0 * least * ctx.counters[per] / secs

