"""Readers of what the device reports about itself."""


def peak_hbm_gb(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None


def mfu(ctx, rate, flops_counter="flops_per_token"):
    """Model FLOP/s utilization: counter `rate` (tokens/s) times the
    operations a token needs, over chips times the chip's published bf16
    peak. The runner counts them through `flops.train_flops_per_token`,
    which asks the configuration's own `counts` first (active parameters
    for sparse experts); recompute is not counted. No peak, no number."""
    if ctx.peaks is None or ctx.counters.get(rate) is None:
        return None
    return 100.0 * ctx.counters[rate] * ctx.counters[flops_counter] / (
        ctx.chips * ctx.peaks["bf16_tflops"] * 1e12)
