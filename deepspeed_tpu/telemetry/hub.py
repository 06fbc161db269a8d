"""TelemetryHub — the host-side telemetry bus.

One sink for everything the stack can observe: the in-step ``MetricsState``
(fetched WITH the loss — one transfer per flush), host timers,
accelerator ``memory_stats()``,
``CommsLogger`` trace-time volume, NVMe aio counters and serving/recompile
events. Emits structured JSONL (schema: docs/telemetry.md) plus a
Prometheus-style text exposition file.

Design constraints this encodes:
- a device fetch syncs the host with the device → device values are
  DEFERRED and fetched in one batched ``jax.device_get`` at flush time
  (``flush_every`` steps, or manually with ``flush_every: 0``, which
  keeps a timed loop fully async);
- step time is stamped dispatch-to-dispatch (host clock between successive
  step events), not via block_until_ready, which would serialize host and
  device every step.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from deepspeed_tpu.telemetry.spans import Histogram

# Module-level emit listeners (not per-hub: `set_hub` swaps instances but
# subscribers — the RequestTracer's instant mirror — must keep seeing the
# stream). Callbacks receive each emitted record dict; errors are dropped.
_LISTENERS: List[Any] = []


def add_listener(cb) -> None:
    if cb not in _LISTENERS:
        _LISTENERS.append(cb)


def remove_listener(cb) -> None:
    try:
        _LISTENERS.remove(cb)
    except ValueError:
        pass


def _json_default(o):
    import numpy as np
    if isinstance(o, np.ndarray):
        return o.tolist()
    if isinstance(o, np.generic):
        return o.item()
    return repr(o)


class TelemetryHub:
    def __init__(self, enabled: bool = False,
                 jsonl_path: Optional[str] = None,
                 prometheus_path: Optional[str] = None,
                 flush_every: int = 1,
                 trace_dir: Optional[str] = None,
                 rank0_only: bool = True):
        if enabled and rank0_only:
            try:
                import jax
                enabled = jax.process_index() == 0
            except Exception:
                pass
        self.enabled = bool(enabled)
        self.jsonl_path = jsonl_path or "telemetry.jsonl"
        self.prometheus_path = prometheus_path
        self.flush_every = int(flush_every)
        self.trace_dir = trace_dir
        self._file = None
        self._deferred: List[Dict[str, Any]] = []
        self._last_step_ts: Optional[float] = None
        # counters/gauges/histograms update even when disabled (they're
        # cheap and the recompile detector's tests read them without a file)
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.histograms: Dict[str, Histogram] = {}

    @classmethod
    def from_config(cls, config) -> "TelemetryHub":
        """Build from a DeepSpeedConfig's ``telemetry`` block; an enabled
        hub also installs itself as the process-global hub so serving
        engines and the NVMe path report into the same file."""
        tcfg = getattr(config, "telemetry", None)
        if tcfg is None:
            return cls(enabled=False)
        hub = cls(enabled=tcfg.enabled, jsonl_path=tcfg.jsonl_path,
                  prometheus_path=tcfg.prometheus_path,
                  flush_every=tcfg.flush_every,
                  trace_dir=tcfg.trace_dir)
        if hub.enabled:
            set_hub(hub)
        return hub

    # ------------------------------------------------------------- raw emit
    def emit(self, kind: str, step: Optional[int] = None, **fields) -> None:
        """Write one JSONL event: {"ts", "kind", "step", **fields}."""
        if not self.enabled:
            return
        rec = {"ts": round(time.time(), 6), "kind": kind, "step": step}
        rec.update(fields)
        if self._file is None:
            d = os.path.dirname(self.jsonl_path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._file = open(self.jsonl_path, "a")
        self._file.write(json.dumps(rec, default=_json_default) + "\n")
        self._file.flush()
        for cb in list(_LISTENERS):
            try:
                cb(rec)
            except Exception:
                pass

    def counter(self, name: str, inc: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + inc

    def gauge(self, name: str, value) -> None:
        try:
            self.gauges[name] = float(value)
        except (TypeError, ValueError):
            pass

    def observe_hist(self, name: str, value) -> None:
        """Stream one observation into a fixed-bucket log histogram
        (telemetry/spans.py) — counter semantics: updates even when the
        hub is disabled; None/non-finite values are dropped."""
        if value is None:
            return
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram()
        h.observe(value)

    def histogram_event(self, name: str) -> None:
        """Emit one `histogram` snapshot event for a named histogram (a
        no-op when the hub is disabled or nothing was observed)."""
        h = self.histograms.get(name)
        if self.enabled and h is not None and h.n:
            self.emit("histogram", name=name, unit="s", **h.summary())

    # ----------------------------------------------------------- train path
    def step_event(self, step: int, loss, metrics=None,
                   samples: Optional[int] = None) -> None:
        """Defer a train step's (loss, MetricsState) DEVICE references for a
        batched fetch. No device sync happens here — the hot loop stays
        async; ``flush()`` fetches every deferred record in ONE
        ``jax.device_get`` call."""
        if not self.enabled:
            return
        self._deferred.append({"step": step, "loss": loss,
                               "metrics": metrics, "samples": samples,
                               "ts": time.perf_counter()})
        if self.flush_every and len(self._deferred) >= self.flush_every:
            self.flush()

    def flush(self) -> None:
        """Fetch all deferred device values (one transfer), emit their
        train_step events, snapshot memory/comms, refresh Prometheus."""
        if not self.enabled:
            return
        recs, self._deferred = self._deferred, []
        if recs:
            import jax
            from deepspeed_tpu.telemetry.metrics import host_metrics
            from deepspeed_tpu.telemetry.tracing import annotate
            with annotate("ds:fetch"):
                fetched = jax.device_get(
                    [(r["loss"], r["metrics"]) for r in recs])
            prev = self._last_step_ts
            for r, (loss, m) in zip(recs, fetched):
                fields: Dict[str, Any] = {}
                if loss is not None:
                    fields["loss"] = float(loss)
                if prev is not None:
                    fields["step_time_s"] = round(r["ts"] - prev, 6)
                prev = r["ts"]
                if r.get("samples") is not None:
                    fields["samples"] = r["samples"]
                fields.update(host_metrics(m))
                self.emit("train_step", step=r["step"], **fields)
                self.counter("steps_total")
                for k in ("loss", "grad_norm", "param_norm", "loss_scale",
                          "step_time_s", "lr"):
                    if k in fields:
                        self.gauge(k, fields[k])
            self._last_step_ts = prev
        self.memory_event()
        self.comms_event()
        self.write_prometheus()

    # ------------------------------------------------------------ snapshots
    def memory_event(self) -> Dict[str, Any]:
        """Accelerator memory_stats() snapshot (per-step window peaks; a
        backend that reports none — the CPU — leaves the fields absent)."""
        if not self.enabled:
            return {}
        try:
            from deepspeed_tpu.accelerator import get_accelerator
            stats = get_accelerator().memory_stats() or {}
        except Exception:
            stats = {}
        fields = {}
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                    "largest_alloc_size"):
            if key in stats:
                fields[key] = int(stats[key])
                self.gauge(key, stats[key])
        if "peak_bytes_in_use" in fields:
            fields["peak_hbm_gb"] = round(
                fields["peak_bytes_in_use"] / (1 << 30), 3)
        if fields:
            self.emit("memory", **fields)
        return fields

    def comms_event(self) -> None:
        """Trace-time collective volume from the CommsLogger (one event per
        flush; a no-op when comms logging is off or empty)."""
        if not self.enabled:
            return
        try:
            from deepspeed_tpu.comm.comms_logging import get_comms_logger
            clog = get_comms_logger()
            if not clog.enabled or not clog.comms_dict:
                return
            self.emit("comms", ops=clog.totals())
        except Exception:
            pass

    def nvme_event(self, stats: Dict[str, Any],
                   step: Optional[int] = None) -> None:
        if self.enabled and stats:
            self.emit("nvme", step=step, **stats)

    # ----------------------------------------------------------- prometheus
    def prometheus_text(self) -> str:
        """Prometheus text exposition of the hub's counters and gauges."""
        lines = []
        for name in sorted(self.counters):
            metric = f"deepspeed_tpu_{name}"
            lines.append(f"# TYPE {metric} counter")
            lines.append(f"{metric} {self.counters[name]:g}")
        for name in sorted(self.gauges):
            metric = f"deepspeed_tpu_{name}"
            lines.append(f"# TYPE {metric} gauge")
            lines.append(f"{metric} {self.gauges[name]:g}")
        return "\n".join(lines) + ("\n" if lines else "")

    def write_prometheus(self) -> None:
        if not self.enabled or not self.prometheus_path:
            return
        d = os.path.dirname(self.prometheus_path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.prometheus_path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.prometheus_text())
        os.replace(tmp, self.prometheus_path)

    def close(self) -> None:
        try:
            self.flush()
        except Exception:
            pass
        if self._file is not None:
            self._file.close()
            self._file = None


_HUB: Optional[TelemetryHub] = None


def get_hub() -> TelemetryHub:
    """The process-global hub. Disabled by default; enabled by an engine
    config's telemetry block (``TelemetryHub.from_config``) or the
    ``DS_TPU_TELEMETRY_JSONL`` env var (serving / bench without a train
    config)."""
    global _HUB
    if _HUB is None:
        env = os.environ.get("DS_TPU_TELEMETRY_JSONL")
        _HUB = TelemetryHub(enabled=bool(env), jsonl_path=env,
                            prometheus_path=os.environ.get(
                                "DS_TPU_TELEMETRY_PROM"))
    return _HUB


def set_hub(hub: TelemetryHub) -> TelemetryHub:
    global _HUB
    _HUB = hub
    return hub
