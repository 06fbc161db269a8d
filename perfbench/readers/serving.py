"""Readers of what the v1 engine records of its `generate` calls
(`program_counter`): the gauges and counters of the process-global telemetry
hub, which update whether or not the hub writes its JSONL stream (the
`serving` event carries the same numbers: `docs/telemetry.md`). A program
that has no such gauge or counter (any commit before it was added) gives
None, and the metric is left out of the line."""


def _hub():
    try:
        from deepspeed_tpu.telemetry import get_hub
    except ImportError:
        return None
    return get_hub()


def gauge(ctx, name, scale=1.0):
    hub = _hub()
    value = None if hub is None else getattr(hub, "gauges", {}).get(name)
    return None if value is None else value * scale


def counter_share(ctx, part, whole):
    """100 x counter `part` / counter `whole`."""
    hub = _hub()
    counters = {} if hub is None else getattr(hub, "counters", {})
    if not counters.get(whole) or counters.get(part) is None:
        return None
    return 100.0 * counters[part] / counters[whole]
