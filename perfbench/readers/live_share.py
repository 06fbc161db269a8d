"""Readers of two pairs of counts the program keeps on a hot path and no
metric read (`program_counter`): what a decode kernel WALKED against what
the cache's shape would have it fetch. Reached through the process-globals
of `deepspeed_tpu.telemetry`, as `readers/program.py` and
`readers/serving.py` do; a program without them gives None."""

from perfbench.readers import program


def span_field_share(ctx, part, whole):
    """100 x sum of the span field `part` / sum of `whole`, over the spans
    of the judged window's rounds that carry both (v2's `decode`,
    `decode_wave` and `chunk` spans: `kv_blocks_live` of
    `kv_blocks_table`)."""
    rounds = program.window_rounds(ctx)
    if rounds is None:
        return None
    got = total = 0
    for r in rounds:
        for s in r:
            if part in s["fields"] and whole in s["fields"]:
                got += s["fields"][part]
                total += s["fields"][whole]
    return 100.0 * got / total if total else None


def gauge_share(ctx, part, whole):
    """100 x hub gauge `part` / hub gauge `whole` (the v1 engine's last
    `generate`: `serving_v1/dense_kv_slots_live` of
    `serving_v1/dense_kv_slots_fetched`)."""
    get_hub = program._telemetry("get_hub")
    gauges = {} if get_hub is None else getattr(get_hub(), "gauges", {})
    if not gauges.get(whole) or gauges.get(part) is None:
        return None
    return 100.0 * gauges[part] / gauges[whole]
