"""Runtime observability: run journals, span tracing and the
contract-drift alarm.

Everything in this package runs host-side on materialized results —
attaching a journal or tracer never changes a compiled program (pinned
bit-exact per engine in ``tests/test_obs.py``).  See ``obs.journal``
for the schema, ``obs.report`` for the CLI, and the README's
"Observability" section for the cookbook.
"""

from .journal import (Journal, SCHEMA_VERSION, hlo_header, make_header,
                      read_journal, result_round_records, result_summary,
                      validate_journal, write_run_journal)
from .metrics import byte_budget_for, check_byte_drift
from .trace import (SpanRecord, Tracer, current_tracer, jax_profiler,
                    pop_tracer, push_tracer, span, tracing)

__all__ = [
    "Journal", "SCHEMA_VERSION", "hlo_header", "make_header",
    "read_journal", "result_round_records", "result_summary",
    "validate_journal", "write_run_journal",
    "byte_budget_for", "check_byte_drift",
    "SpanRecord", "Tracer", "current_tracer", "jax_profiler",
    "pop_tracer", "push_tracer", "span", "tracing",
]
