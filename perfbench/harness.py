"""One run of one cell: what every kind of cell shares.

`run.py` parses the command line and calls `main` here. The cell's runner
(`runners/<kind>.py`, named by its traffic file) builds the system under
test, warms up, measures a window and, with `--trace 1`, a further traced
segment; this module owns the clock, the device check, the compile cache,
the profiler, the count of compilations, the readers and the result line.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, Iterator, List, Optional

from perfbench import trace as trace_mod
from perfbench.flops import peaks_for
from perfbench.manifest import CHECKOUT, Manifest, config_problems

CACHE_DIR = os.path.join(CHECKOUT, ".perfbench_cache")   # fixed: part of the cache key
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Ctx:
    """What a runner and the readers see of one run."""

    def __init__(self, manifest: Manifest, workload: Dict[str, Any], seed: int,
                 seconds: float, traced: bool, rehearsal: bool, t_start: float):
        self.manifest, self.workload = manifest, workload
        self.seed, self.seconds = int(seed), float(seconds)
        self.traced, self.rehearsal, self.t_start = traced, rehearsal, t_start
        self.chips = int(workload["chips"])
        sizes = manifest.config(workload["config"])
        traffic = manifest.traffic(workload["traffic"])
        if rehearsal:   # toy sizes, same control flow; never a measurement
            sizes = {**sizes, **sizes.get("rehearsal", {})}
            traffic = _merge(traffic, traffic.get("rehearsal", {}))
        self.sizes, self.traffic = sizes, traffic
        self.adapter = manifest.module("configs", sizes["adapter"])
        self.reference = manifest.module("configs", sizes["reference"])
        # filled by the runner
        self.samples: Dict[str, List[float]] = {}
        self.counters: Dict[str, float] = {}
        self.spans: Dict[str, Any] = {}
        self.trace: Optional[Dict[str, Any]] = None
        self.trace_window: Optional[trace_mod.Interval] = None
        self.compiles = 0
        self.device: Dict[str, Any] = {}
        self.peaks: Optional[Dict[str, float]] = None

    # ---------------------------------------------------------------- time
    def clock(self) -> float:
        return time.perf_counter()

    @contextlib.contextmanager
    def annotate(self, name: str) -> Iterator[None]:
        """A host span in the profiler's own trace (free when none runs)."""
        import jax
        with jax.profiler.TraceAnnotation(trace_mod.HOST_PREFIX + name):
            yield

    # ------------------------------------------------------------ profiler
    @contextlib.contextmanager
    def profile(self) -> Iterator[None]:
        """Trace the enclosed segment and keep its neutral form. The Python
        tracer is off: it would slow the host loop that is being measured."""
        import jax
        logdir = os.path.join(CACHE_DIR, "trace")
        _rmtree(logdir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        inventory: Dict[str, Any] = {}
        self.trace = trace_mod.read_xplane(trace_mod.newest_xplane(logdir),
                                           inventory)
        dump = os.environ.get("PERFBENCH_DUMP")
        if dump:   # to read a trace by hand; never read back
            os.makedirs(dump, exist_ok=True)
            name = os.path.join(dump, self.workload["name"])
            with open(name + ".inventory.json", "w") as f:
                json.dump(inventory, f, indent=1)
            with open(name + ".trace.json", "w") as f:
                json.dump(trace_mod.excerpt(self.trace), f)
        _rmtree(logdir)
        if self.trace["devices"]:
            self.trace_window = trace_mod.trace_window(self.trace)

    @contextlib.contextmanager
    def counting_compiles(self) -> Iterator[None]:
        """Count programs compiled (or fetched from the cache) while the
        body runs: inside a measured window there should be none."""
        self._counting = True
        try:
            yield
        finally:
            self._counting = False

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT and getattr(self, "_counting", False):
            self.compiles += 1


def _merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and \
            isinstance(base.get(k), dict) else v
    return out


def _rmtree(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def set_path(doc: Dict[str, Any], dotted: str, value: Any) -> None:
    """`arrivals.rate=8` for the sweep: set one key of a traffic dict."""
    keys = dotted.split(".")
    for k in keys[:-1]:
        doc = doc[k]
    doc[keys[-1]] = value


# ------------------------------------------------------------------ device


def claim_devices(ctx: Ctx) -> List[Any]:
    """The devices this cell runs on, or an exit: a measuring run needs an
    accelerator whose peaks are in the table, and as many chips as the cell
    asks for. A rehearsal takes what there is and says so."""
    import jax
    devs = jax.devices()
    ctx.device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": ctx.chips}
    if ctx.rehearsal:
        ctx.device["count"] = min(ctx.chips, len(devs))
        return devs[:ctx.device["count"]]
    if devs[0].platform == "cpu":
        raise SystemExit("perfbench: JAX found no accelerator "
                         f"(platform {devs[0].platform!r}); --rehearsal runs "
                         "the control flow at toy sizes")
    if len(devs) < ctx.chips:
        raise SystemExit(f"perfbench: the cell asks for {ctx.chips} chips, "
                         f"JAX sees {len(devs)}")
    try:
        ctx.peaks = peaks_for(devs[0].device_kind)
    except KeyError as e:
        raise SystemExit(f"perfbench: {e.args[0]}")
    return devs[:ctx.chips]


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, whatever
    the environment says: the driver's two sides must share nothing, and
    the path is part of the key. 0.5 s threshold as the program's own entry
    scripts keep it (PERF.md, PR 21 finding 14)."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CACHE_DIR, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


# ------------------------------------------------------------------ result


def read_metrics(ctx: Ctx, group: str) -> Dict[str, Dict[str, Any]]:
    """Every metric of `group` this cell reports, through its own reader.
    A reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for entry in ctx.manifest.metrics_for(ctx.workload["name"], group):
        decl = ctx.manifest.metric(entry["name"])
        reader = ctx.manifest.reader(decl["reader"])
        value = reader(ctx, **decl.get("params", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def prepare(args, t_start: float, traced: bool):
    """The run's context and its devices: manifest, `--set` overrides, the
    compile cache, the device check, the compile counter. Shared by `run.py`
    and `sweep.py`."""
    manifest = Manifest(getattr(args, "manifest", None))
    workload = manifest.workload(args.workload)
    bad = config_problems(manifest, workload["config"])
    if bad:   # what the benchmark's own test would refuse, no run measures
        raise SystemExit("perfbench: " + "; ".join(bad))
    ctx = Ctx(manifest, workload, args.seed,
              args.seconds, traced, args.rehearsal, t_start)
    for item in args.set or []:
        key, _, val = item.partition("=")
        set_path(ctx.traffic, key, json.loads(val))
    import jax
    if not ctx.rehearsal:   # a rehearsal's CPU programs are not worth keeping
        enable_compile_cache()
    devices = claim_devices(ctx)
    jax.monitoring.register_event_duration_secs_listener(ctx._on_event)
    return ctx, devices


def main(args, t_start: float) -> int:
    ctx, devices = prepare(args, t_start, bool(args.trace))
    manifest, workload = ctx.manifest, ctx.workload
    runner = manifest.module("runners", ctx.traffic["kind"])
    outcome = runner.run(ctx, devices)   # {"correct", "attempted", "failed", ...}

    ctx.device["memory_peak_bytes"] = memory_peak_bytes(devices)
    group = "per_layer" if ctx.traced else "end_to_end"
    line: Dict[str, Any] = {
        "correct": bool(outcome["correct"]),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": read_metrics(ctx, group), "device": ctx.device,
        "workload": workload["name"], "seed": ctx.seed,
        "notes": outcome.get("notes", {})}
    if ctx.traced and not ctx.rehearsal and not (ctx.trace or {}).get("devices"):
        raise SystemExit("perfbench: the traced segment shows no operation "
                         "on the device")
    if ctx.traced and ctx.trace is not None and ctx.trace["devices"]:
        w = ctx.trace_window
        ctx.device["busy_s"] = trace_mod.busy_seconds(ctx.trace, w)
        ctx.device["window_s"] = (w[1] - w[0]) / 1e9
        line["breakdown"] = trace_mod.breakdown(ctx.trace, w)
    if ctx.rehearsal:
        # a CPU's clock says nothing about the chip: what was read is
        # shown under another key, and `metrics` stays empty
        line["rehearsal"] = True
        line["rehearsal_metrics"], line["metrics"] = line["metrics"], {}
    print(json.dumps(line), flush=True)
    return 0
