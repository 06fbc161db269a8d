"""Readers of the device trace BY SCOPE (`device_trace`): the program keeps,
for every program it compiled, a table of the optimised module's
instructions with the scope each was traced under (`jax.named_scope`, flax
module path, `jvp(` / `transpose(`) and what a fusion holds
(`deepspeed_tpu.telemetry.program_map`), and joins a device's op events to
it by module and instruction name (`telemetry.by_scope`). These hand it the
first device of `ctx.trace` and the traced window, and pick one number out
of the join; the predicates are `telemetry.row_matches`'s (docs/telemetry.md,
"Program map and scopes").

The runners hand no engine to a reader, so the program is reached through
the process-globals of `deepspeed_tpu.telemetry`, as `readers/program.py`
does. A tree without the map (any commit before it was added), a run without
a trace, a rehearsal on a CPU whose profile has no device line, a program
the engine kept nothing of: None, and the metric is left out of the line.
The map is built here, after the traced window has closed, once a run.
"""

import bisect
import json
import os

from perfbench import trace as tm


def _telemetry(attr):
    try:
        from deepspeed_tpu import telemetry
    except ImportError:
        return None
    return getattr(telemetry, attr, None)


def joined(ctx):
    """`telemetry.by_scope` of the first device over the traced window,
    made once a run; None where there is nothing to join."""
    if hasattr(ctx, "_by_scope"):
        return ctx._by_scope
    ctx._by_scope = None
    by_scope, program_map = _telemetry("by_scope"), _telemetry("program_map")
    if by_scope is None or program_map is None or ctx.trace is None \
            or not ctx.trace["devices"] or not ctx.trace_window:
        return None
    maps = program_map()
    if not any(doc["rows"] for doc in maps.values()):
        return None
    dev = tm.first_device(ctx.trace)
    ctx._by_scope = by_scope(dev["ops"], dev["modules"], ctx.trace_window,
                             maps=maps)
    _dump(ctx, maps)
    return ctx._by_scope


def _dump(ctx, maps):
    """With PERFBENCH_DUMP set (as for the harness's own dumps): the
    seconds by scope, by `holds` and by phase, the unmatched ops, and what
    each program's map cost; and a small PAIR to keep with the tests, 1,500
    op events of the window (its start, its middle, its end) with the
    modules over them and the rows of the map they name. To read by hand;
    never read back."""
    dump, tables = os.environ.get("PERFBENCH_DUMP"), _telemetry("scope_tables")
    if not dump or tables is None:
        return
    os.makedirs(dump, exist_ok=True)
    doc = {"busy_s": ctx._by_scope["busy_s"],
           "unmatched_s": sum(ctx._by_scope["unmatched"].values()),
           "programs": {m: {k: v for k, v in d.items() if k != "rows"}
                        | {"rows": len(d["rows"])} for m, d in maps.items()},
           **tables(ctx._by_scope)}
    name = os.path.join(dump, ctx.workload["name"])
    with open(name + ".by_scope.json", "w") as f:
        json.dump(doc, f, indent=1)
    dev, (w0, w1) = tm.first_device(ctx.trace), ctx.trace_window
    ops = sorted((e for e in dev["ops"] if w0 <= e[1] and e[1] + e[2] <= w1),
                 key=lambda e: e[1])
    mid = max(0, len(ops) // 2 - 250)
    kept = ops[:500] + ops[mid:mid + 500] + ops[-500:] \
        if len(ops) > 1500 else ops
    named = {e[0].split(" = ", 1)[0].strip().lstrip("%") for e in kept}
    mods = sorted(dev["modules"], key=lambda e: e[1])
    starts = [e[1] for e in mods]
    over = sorted({bisect.bisect_right(starts, k[1]) - 1 for k in kept})
    pair = {"trace": {"devices": {"0": {
                "ops": kept, "modules": [mods[i] for i in over if i >= 0]}},
                "host": [[tm.HOST_PREFIX + "traced", w0, w1 - w0]]},
            "counters": {k: v for k, v in ctx.counters.items()
                         if k.startswith("traced_")},
            "maps": {m: {**{k: v for k, v in d.items() if k != "rows"},
                         "rows": [r for r in d["rows"] if r["instr"] in named]}
                     for m, d in maps.items()}}
    with open(name + ".scope_pair.json", "w") as f:
        json.dump(pair, f)


def seconds(ctx, **predicate):
    got = joined(ctx)
    return None if got is None else \
        _telemetry("seconds_where")(got, **predicate)


def ms_per(ctx, per, **predicate):
    """Self time of the device ops whose row matches `predicate`, in ms per
    unit of the counter `per` (steps, rounds, decode steps) of the traced
    window, first device."""
    secs = seconds(ctx, **predicate)
    if secs is None or not ctx.counters.get(per):
        return None
    return 1e3 * secs / ctx.counters[per]


def share_of_busy(ctx, **predicate):
    """The same seconds as a share of the device's busy time in the traced
    window (the sum of every op's self time, matched or not)."""
    secs = seconds(ctx, **predicate)
    if secs is None or not joined(ctx)["busy_s"]:
        return None
    return 100.0 * secs / joined(ctx)["busy_s"]


def unmatched_share(ctx):
    """Busy time in device ops that no row of the map names, as a share of
    busy time: this reading's own `unattributed`."""
    got = joined(ctx)
    if got is None or not got["busy_s"]:
        return None
    return 100.0 * sum(got["unmatched"].values()) / got["busy_s"]
