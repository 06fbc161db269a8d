"""Unified telemetry: in-step metrics, host event bus, recompile detection,
trace capture.

The reference stack's three observability pillars (`MonitorMaster` sinks,
`CommsLogger`, the FLOPS profiler) observe a host-driven training loop.
Here the loop is one compiled program, so observability splits into:

- ``MetricsState`` (metrics.py): metrics computed INSIDE the compiled step,
  delivered with the loss in one host fetch;
- ``TelemetryHub`` (hub.py): the host bus merging MetricsState with timers,
  memory stats, comms volume and NVMe counters into JSONL + a Prometheus
  text file;
- ``RecompileDetector`` (recompile.py): dispatch-time fingerprinting that
  turns silent ~3.5 s serving recompiles into warnings;
- ``RequestTracer``/``Histogram``/``export_chrome_trace`` (spans.py):
  per-request span records for the serving engines — wall-time
  decomposition with an ``unattributed`` residual invariant, streaming
  TTFT/TPOT/e2e histograms, and Chrome-trace export;
- ``trace_capture``/``annotate`` (tracing.py): perfetto trace hooks;
  ``compile_span``/``compile_records``: set-up time by program (tracing,
  lowering, backend compiles and what the persistent cache said of them),
  from the ``jax.monitoring`` listeners installed when this package is
  imported; ``init_span``/``init_phase``: an engine's construction by part;
- ``program_map``/``by_scope`` (program_map.py): every compiled program's
  instructions by the scope they were traced under (``jax.named_scope``,
  flax path, ``jvp(`` / ``transpose(``) and what a fusion holds, built
  when asked from what ``keep_program`` kept at the first dispatch, and
  the join of a profile's device ops to it by instruction name;
- ``get_span_store`` (spans.py): the closed spans of the last rounds, kept
  after their requests, process-global like ``get_hub()``;
- ``MemoryPlane`` (memory.py): the tiered residency ledger every placement
  path registers into — per-tier/per-component byte accounting, watermarks,
  and formula reconciliation (docs/memory.md).

CLI: ``python -m deepspeed_tpu.telemetry --summarize run.jsonl``,
``python -m deepspeed_tpu.telemetry --by-scope <trace logdir>``.
"""

from deepspeed_tpu.telemetry.hub import TelemetryHub, get_hub, set_hub  # noqa: F401
from deepspeed_tpu.telemetry.memory import (  # noqa: F401
    MemoryPlane, get_plane, scratch_plane, set_plane)
from deepspeed_tpu.telemetry.metrics import MetricsState, host_metrics  # noqa: F401
from deepspeed_tpu.telemetry.program_map import (  # noqa: F401
    by_scope, forget_programs, jit_name, program_map, row_matches,
    scope_tables, seconds_where, write_program_map)
from deepspeed_tpu.telemetry.program_map import keep as keep_program  # noqa: F401
from deepspeed_tpu.telemetry.recompile import RecompileDetector  # noqa: F401
from deepspeed_tpu.telemetry.spans import (  # noqa: F401
    Histogram, RequestTracer, SpanStore, export_chrome_trace, get_span_store)
from deepspeed_tpu.telemetry.tracing import (  # noqa: F401
    annotate, compile_records, compile_span, compile_totals, device_busy,
    init_phase, init_span, install_compile_listener, note_import,
    trace_capture, union_seconds)

install_compile_listener()
