"""lfkt-obs — zero-dependency tracing + metrics for the serving stack.

The observability layer the ROADMAP's production-scale north star needs
on top of PR 2's watchdog/deadline machinery: per-request span trees
(:mod:`.trace` → ``/debug/traces``, ``/debug/requests``), the declarative
metric catalog behind the labeled/histogram ``/metrics`` registry
(:mod:`.catalog` + utils/metrics.py), and request-id-stamped structured
logging (:mod:`.logctx`).  Stdlib only; nothing here is importable from a
jit trace, and everything is strictly zero-cost for sampled-out requests
(LFKT_TRACE_SAMPLE=0 → ``Tracer.start`` returns None before any lock).

Span classes, metric catalog, sampling and the debug endpoints:
docs/OBSERVABILITY.md.  Slow-request triage flow (tools/trace_report.py
waterfalls): docs/RUNBOOK.md "Triaging a slow request".
"""

from .catalog import METRICS, Metric, lookup, markdown_table  # noqa: F401
from .logctx import (  # noqa: F401
    JsonFormatter,
    RequestIdFilter,
    access_logger,
    bind_request_id,
    current_request_id,
    setup_json_logging,
)
from .trace import TRACER, Span, Trace, Tracer, parse_traceparent  # noqa: F401

__all__ = [
    "METRICS", "Metric", "lookup", "markdown_table",
    "JsonFormatter", "RequestIdFilter", "access_logger", "bind_request_id",
    "current_request_id", "setup_json_logging",
    "TRACER", "Span", "Trace", "Tracer", "parse_traceparent",
]
