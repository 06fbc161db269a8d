"""The program map: what each compiled program's instructions are, and the
join that gives every device op of a profile its place in the program.

A profile's device line names an op by its HLO instruction (`fusion.123`)
and the `XLA Modules` line names the program that was running
(`jit_ds_v1_generate_b8_s128_n64`). Neither says which layer, which pass or
which scope the op came from, and `fusion` is most of every program. The
compiled module's own text does: each instruction carries the `op_name` it
was traced under (`jax.named_scope`, flax module path, `jvp(` /
`transpose(`), and a fusion names the computation it calls.

- `keep` (the engines, inside the `compile_span` of a program's first
  dispatch): what is needed to get that text LATER. It is the tracing the
  dispatch itself uses (`jitted.trace(*args)`: shapes, dtypes, shardings and
  the live leaves' layouts, no array, no function, no engine), in a bounded
  registry by module name. No `lower`, no `compile`, no `as_text` happens
  in set-up or in a measured window.
- `program_map` (an operator, a traced run's reader, `trace_capture` when
  it closes): lowers and compiles from what was kept, which the process's
  own caches answer with the executable that ran, reads its text through
  the one HLO parser (`tools/tpucomms/hlo.py`) and keeps the rows.
- `by_scope`: device events in the neutral form (`[name, start_ns,
  dur_ns]`, names with their numbers) and the module events of the same
  device -> SELF seconds by row. What no row names comes back under
  `unmatched`, never dropped.

`python -m deepspeed_tpu.telemetry --by-scope <logdir>` prints seconds by
scope and by `holds` from a directory `trace_capture` wrote.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import json
import os
import re
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, \
    Tuple

from deepspeed_tpu.tools.tpucomms import hlo

_CAP = 64   # programs kept, the oldest fall out (as the span store's spans)
MAP_FILE = "program_map.json"
Interval = Tuple[float, float]
# JAX's own names for control flow in an `op_name` path
_PLUMBING = ("while", "body", "cond", "closed_call", "checkpoint",
             "branch_0_fun", "branch_1_fun")

# module name -> what `keep` was given, and the rows once they were asked for
_KEPT: "collections.OrderedDict[str, Dict[str, Any]]" = \
    collections.OrderedDict()


def jit_name(program: str) -> str:
    """The name a program's jitted function takes, from the name its
    `compile` span carries: `v1:generate:b8_s128_n64@model2` ->
    `ds_v1_generate_b8_s128_n64_model2`. The device trace's `XLA Modules`
    line then reads `jit_` + that, one name a live program."""
    return "ds_" + re.sub(r"[^A-Za-z0-9]+", "_", program).strip("_")


def keep(program: str, traced, mesh=None, detector: Optional[str] = None,
         under_mesh: bool = False) -> None:
    """At a program's first dispatch: keep `traced` (`jitted.trace(*args)`
    on the arguments of the dispatch) under the module name its compile
    will carry. `mesh` gives a collective's replica groups their axes;
    `under_mesh` says the dispatch runs inside `with mesh:` (the train
    engine's), so that the map's lowering does too and JAX's caches know
    it for the same program; `detector` is the name the engine's
    RecompileDetector knows the program by, where it differs from
    `program`."""
    module = "jit_" + str(traced.fun_name)
    _KEPT.pop(module, None)
    _KEPT[module] = {"program": program, "module": module,
                     "detector": detector or program, "traced": traced,
                     "mesh": dict(mesh.shape) if mesh is not None else None,
                     "context": mesh if under_mesh else None,
                     "first_cache": None, "built": None}
    while len(_KEPT) > _CAP:
        _KEPT.popitem(last=False)


def note_first_dispatch(program: str, cache: str) -> None:
    """What the persistent cache said of `program`'s first dispatch
    (`compile_span` calls this as it closes)."""
    for entry in reversed(_KEPT.values()):
        if entry["program"] == program and entry["first_cache"] is None:
            entry["first_cache"] = cache
            return


def forget_programs() -> None:
    _KEPT.clear()


def _cache_said(records: Sequence[Dict[str, Any]]) -> str:
    """Of the backend compiles a map's build caused: `memory` where there
    was none (JAX's in-process caches handed back the executable that
    ran), else the worst of what the persistent cache said."""
    from deepspeed_tpu.telemetry.tracing import _worst_cache
    return _worst_cache(records) if records else "memory"


def _build(entry: Dict[str, Any]) -> Dict[str, Any]:
    from deepspeed_tpu.telemetry.tracing import compile_records
    built = {k: entry[k] for k in ("program", "module", "detector", "mesh",
                                   "first_cache")}
    t0 = time.perf_counter()
    try:
        with entry["context"] or contextlib.nullcontext():
            text = entry["traced"].lower().compile().as_text()
        t1 = time.perf_counter()
        built["module"], rows = hlo.instruction_rows(text, entry["mesh"])
    except Exception as e:   # a map is an observer: it never ends a run
        return {**built, "cache": None, "rows": [],
                "error": f"{type(e).__name__}: {str(e)[:300]}"}
    cache = _cache_said([r for r in compile_records() if r["t"] >= t0])
    built.update(cache=cache, compile_s=round(t1 - t0, 6),
                 parse_s=round(time.perf_counter() - t1, 6))
    # a program the persistent cache kept at its first dispatch and does
    # not know now was compiled from something else: its text is another
    # program's, and no row of it is given
    if cache == "miss" and entry["first_cache"] in ("hit", "miss"):
        built["stale"] = True
        rows = []
    built["rows"] = rows
    return built


def program_map(program: Optional[str] = None) -> Dict[str, Dict[str, Any]]:
    """{module name: {`program`, `module`, `detector`, `mesh`,
    `first_cache`, `cache`, `compile_s`, `parse_s`, `rows`}} of every
    program kept (or of `program` alone, by its span's or its module's
    name). `rows` is `hlo.instruction_rows` of the optimised module. Built
    on the first call and kept. A program whose text could not be had
    carries `error` and no rows; one whose text is another program's,
    `stale` and no rows."""
    out = {}
    for module, entry in list(_KEPT.items()):
        if program is not None and program not in (entry["program"], module):
            continue
        if entry["built"] is None:
            entry["built"] = _build(entry)
            # the rows are kept, the jaxpr may go
            entry["traced"] = entry["context"] = None
        out[entry["built"]["module"]] = entry["built"]
    return out


def write_program_map(logdir: str) -> Optional[str]:
    """`program_map.json` of every program kept, into `logdir` (beside the
    trace `trace_capture` closes). None where nothing was kept."""
    maps = program_map()
    if not maps:
        return None
    path = os.path.join(logdir, MAP_FILE)
    with open(path, "w") as f:
        json.dump(maps, f)
    return path


# ------------------------------------------------------------------- the join


def _bare(name: str) -> str:
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _module_of(name: str) -> str:
    """`jit_step(1234567)` -> `jit_step`."""
    return name.split("(", 1)[0].strip()


def by_scope(ops: Sequence[Sequence], modules: Sequence[Sequence],
             window: Optional[Interval] = None,
             maps: Optional[Dict[str, Dict[str, Any]]] = None
             ) -> Dict[str, Any]:
    """SELF seconds of one device's op events by row of the program map.

    `ops` and `modules` are `[name, start_ns, dur_ns]` events of ONE device
    (op names with their numbers, `%` or not). Each op goes to the module
    event that encloses its start, `(module, instr)` is looked up in
    `maps` (`program_map()` when not given), and its self time (its own
    time less that of the ops nested in it, cut to `window`) is added to
    that row. Returns

        {"rows": [(row, seconds), ...],     # rows that ran, most first
         "unmatched": {name: seconds},      # `module/instr` no row names
         "busy_s": seconds}                 # the sum of both

    so that a caller sums over any predicate on a row's `scope`, `phase`,
    `opcode`, `holds`, `loop`."""
    if maps is None:
        maps = program_map()
    index = {(m, r["instr"]): r for m, doc in maps.items()
             for r in doc["rows"]}
    mods = sorted(([_module_of(n), s, s + d] for n, s, d in modules),
                  key=lambda e: e[1])
    starts = [m[1] for m in mods]

    def module_at(t: float) -> Optional[str]:
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0:            # the latest module that encloses t
            if mods[i][2] > t:
                return mods[i][0]
            i -= 1
        return None

    seconds: Dict[Tuple[Optional[str], str], float] = {}
    stack: List[List[Any]] = []   # [key, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            key, _, own = stack.pop()
            seconds[key] = seconds.get(key, 0.0) + own / 1e9

    for raw, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        a, b = start, start + dur
        if window:
            a, b = max(a, window[0]), min(b, window[1])
            if b <= a:
                continue
        close(a)
        if stack:
            stack[-1][2] -= (min(b, stack[-1][1]) - a)
        stack.append([(module_at(start), _bare(raw)), b, b - a])
    close(float("inf"))

    rows: Dict[int, List[Any]] = {}
    unmatched: Dict[str, float] = {}
    for (module, instr), secs in seconds.items():
        row = index.get((module, instr))
        if row is None:
            name = f"{module or '(no module)'}/{instr}"
            unmatched[name] = unmatched.get(name, 0.0) + secs
        else:
            rows.setdefault(id(row), [row, 0.0])[1] += secs
    ranked = sorted(((r, s) for r, s in rows.values()), key=lambda x: -x[1])
    return {"rows": ranked, "unmatched": unmatched,
            "busy_s": sum(s for _, s in ranked) + sum(unmatched.values())}


def row_matches(row: Dict[str, Any], scope=None, any_scope=None,
                not_scope=None, phase=None, opcode=None, instr=None,
                not_instr=None, holds=None, axes=None, not_holds=None
                ) -> bool:
    """One predicate over a row, for `seconds_where`: `scope` (a name or a
    list: ALL of them among the scope's names), `any_scope` (ANY of them),
    `not_scope` (none of them), `phase` (`fwd` / `bwd` / `none`), `opcode`,
    `instr` / `not_instr` (regexes searched in the instruction name),
    `holds` (a regex searched in each entry of `holds`), `axes` (mesh axes
    that must ALL be among those of the `holds` entry that matched) and
    `not_holds` (a regex NO entry of `holds` may match: an asynchronous
    collective's wrapper holds `custom-call:AsyncCollectiveStart`)."""
    names = set(hlo.scope_names(row["scope"]))

    def listed(x):
        return [x] if isinstance(x, str) else list(x)

    if scope is not None and not all(s in names for s in listed(scope)):
        return False
    if any_scope is not None and not any(s in names
                                         for s in listed(any_scope)):
        return False
    if not_scope is not None and any(s in names for s in listed(not_scope)):
        return False
    if phase is not None and (row["phase"] or "none") != phase:
        return False
    if opcode is not None and row["opcode"] != opcode:
        return False
    if instr is not None and not re.search(instr, row["instr"]):
        return False
    if not_instr is not None and re.search(not_instr, row["instr"]):
        return False
    if not_holds is not None and any(re.search(not_holds, h)
                                     for h in row["holds"]):
        return False
    if holds is not None or axes is not None:
        rx = re.compile(holds or "")
        want = set(listed(axes)) if axes is not None else set()
        for h in row["holds"]:
            if not rx.search(h):
                continue
            got = set(h[h.index("[") + 1:-1].split(",")) if "[" in h else set()
            if want <= got:
                break
        else:
            return False
    return True


def seconds_where(joined: Dict[str, Any], **predicate) -> float:
    """Self seconds of `by_scope`'s rows that `row_matches(**predicate)`."""
    return sum(s for r, s in joined["rows"] if row_matches(r, **predicate))


# ---------------------------------------------------------- the operator's form


def read_device_events(logdir: str) -> Tuple[List[List[Any]], List[List[Any]]]:
    """(ops, modules) of the first device in the newest `.xplane.pb` under
    `logdir`, in the neutral form: the events' names, as the repo's
    benchmark reads them. Needs JAX's profile reader; no backend."""
    import jax
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(max(files,
                                                  key=os.path.getmtime))
    devices: Dict[int, Dict[str, List[List[Any]]]] = {}
    for plane in data.planes:
        dev = re.match(r"^/device:\w+:(\d+)$", plane.name)
        if not dev:
            continue
        for line in plane.lines:
            if line.name in ("XLA Ops", "XLA Modules"):
                devices.setdefault(int(dev.group(1)), {}).setdefault(
                    line.name, []).extend(
                    [_bare(e.name), float(e.start_ns), float(e.duration_ns)]
                    for e in line.events)
    if not devices:
        return [], []
    first = devices[min(devices)]
    return first.get("XLA Ops", []), first.get("XLA Modules", [])


def scope_label(row: Dict[str, Any]) -> str:
    """A row's scope less the primitive and JAX's loop plumbing: what the
    report groups by."""
    parts = [p for p in hlo.split_path(row["scope"]) if p not in _PLUMBING]
    if not (row.get("inferred") or row["opcode"] == "while"):
        parts = parts[:-1]   # the primitive
    return "/".join(parts) or "(no scope)"


def join_logdir(logdir: str) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(the maps, `by_scope` of the first device) of a directory
    `trace_capture` wrote: its `program_map.json` and its newest trace."""
    with open(os.path.join(logdir, MAP_FILE)) as f:
        maps = json.load(f)
    ops, modules = read_device_events(logdir)
    return maps, by_scope(ops, modules, maps=maps)


def scope_tables(joined: Dict[str, Any], top: int = 40
                 ) -> Dict[str, List[Tuple[str, float]]]:
    """`by_scope`'s seconds summed three ways, most first: by scope
    (`scope_label`), by what an instruction is or holds, by phase; and the
    unmatched ops."""
    def by(key: Callable[[Dict[str, Any]], Iterable[str]]):
        out: Dict[str, float] = {}
        for row, secs in joined["rows"]:
            for k in key(row):
                out[k] = out.get(k, 0.0) + secs
        return sorted(out.items(), key=lambda kv: -kv[1])[:top]

    return {"scope": by(lambda r: [scope_label(r)]),
            "holds": by(lambda r: r["holds"] or ["(none of the listed)"]),
            "phase": by(lambda r: [r["phase"] or "none"]),
            "unmatched": sorted(joined["unmatched"].items(),
                                key=lambda kv: -kv[1])[:top]}


def report(logdir: str, top: int = 40) -> str:
    """Seconds by scope and by `holds` of the newest trace in `logdir`,
    joined to the `program_map.json` beside it."""
    maps, joined = join_logdir(logdir)
    busy = joined["busy_s"] or 1.0
    tables = scope_tables(joined, top)
    lines = [f"by scope — {logdir}",
             "programs: " + ", ".join(
                 f"{d['program']} ({m}, {len(d['rows'])} rows"
                 + (", STALE" if d.get("stale") else "") + ")"
                 for m, d in maps.items()),
             f"busy {joined['busy_s']:.6f} s (self time, first device)"]
    for title, key in (("seconds by scope:", "scope"),
                       ("seconds by what an instruction is or holds:",
                        "holds"), ("seconds by phase:", "phase")):
        lines.append(title)
        lines += [f"  {secs:12.6f} s {100 * secs / busy:6.2f}%  {name}"
                  for name, secs in tables[key]]
    lost = sum(joined["unmatched"].values())
    lines.append(f"unmatched: {lost:.6f} s ({100 * lost / busy:.2f}%) in ops "
                 "no row of the map names")
    lines += [f"  {secs:12.6f} s  {name}"
              for name, secs in tables["unmatched"][:10]]
    return "\n".join(lines)
