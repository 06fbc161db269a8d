"""Readers of the PROGRAM's request spans (`program_span`): the summaries
`telemetry/spans.py` keeps per finished request (`queue_s`, `ttft_s`, ...),
recorded in memory in the traced run only."""

from perfbench import traffic as tg


def request_percentile(ctx, field, q, scale=1.0):
    values = [r[field] * scale for r in ctx.spans.get("requests", [])
              if r.get(field) is not None]
    return tg.percentile(values, q) if values else None
