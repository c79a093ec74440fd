"""Span-based tracing: where a run's wall-clock actually goes.

``span(name, **meta)`` is the program's one tracing call.  It marks a
host-side phase — the init phase, the round loop's dispatch, lowering,
compilation, checkpointing — with two sinks:

* the profiler: every span enters ``jax.profiler.TraceAnnotation(name,
  **meta)``, so a profile taken with ``jax.profiler.trace`` (or
  :func:`jax_profiler`) holds it as a host event named exactly ``name``,
  ``meta`` as its stats, on the same clock as the device's operations
  and with JAX's own host spans (lowering, backend compile) nested
  under it;
* the active :class:`Tracer`, when one is pushed (``with tracing() as
  tr:`` or ``push_tracer``), which feeds the run journal and the
  Chrome-trace export.

With no profiler collecting and no tracer pushed, a span costs one
inactive TraceMe check.  Spans never touch traced values — they wrap
host-side phases only, so the compiled program is bit-identical with
tracing on (the journal/trace acceptance rail).

Program spans are named ``ranl.<layer>[.<phase>]``; none starts with
``bench.``, the prefix the benchmark's own spans use to open its
measured window.

Exports:

* ``Tracer.chrome_trace()`` / ``Tracer.write_chrome(path)`` — the
  Chrome-trace ("Perfetto"/``chrome://tracing``) JSON event form;
* ``Tracer.span_records()`` — the journal form (``kind="span"``
  records, appended by ``obs.journal.write_run_journal``);
* ``jax_profiler(log_dir)`` — passthrough to ``jax.profiler.trace``:
  the device timeline with these spans on it.
"""

from __future__ import annotations

import json
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

__all__ = ["SpanRecord", "Tracer", "tracing", "span", "current_tracer",
           "push_tracer", "pop_tracer", "jax_profiler"]


@dataclass(frozen=True)
class SpanRecord:
    """One closed span: ``t0``/``dur`` are host ``perf_counter`` seconds
    (``t0`` relative to the tracer's epoch)."""
    name: str
    t0: float
    dur: float
    meta: tuple[tuple[str, object], ...] = ()


@dataclass
class Tracer:
    """Collects :class:`SpanRecord` entries; reentrant and nestable."""
    epoch: float = field(default_factory=time.perf_counter)
    spans: list[SpanRecord] = field(default_factory=list)

    @contextmanager
    def span(self, name: str, **meta):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            dur = time.perf_counter() - t0
            self.spans.append(SpanRecord(
                name=str(name), t0=t0 - self.epoch, dur=dur,
                meta=tuple(sorted(meta.items()))))

    def totals(self) -> dict[str, float]:
        """Total seconds per span name (the report's span breakdown)."""
        out: dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.dur
        return out

    def span_records(self) -> list[dict]:
        """Journal-form records (``kind="span"``), in close order."""
        return [{"kind": "span", "name": s.name,
                 "t0_s": round(s.t0, 9), "dur_s": round(s.dur, 9),
                 **({"meta": dict(s.meta)} if s.meta else {})}
                for s in self.spans]

    def chrome_trace(self) -> dict:
        """Chrome-trace JSON object (open with Perfetto or
        ``chrome://tracing``): complete ("X") events in microseconds."""
        return {"traceEvents": [
            {"name": s.name, "ph": "X", "pid": 0, "tid": 0,
             "ts": s.t0 * 1e6, "dur": s.dur * 1e6,
             "args": dict(s.meta)} for s in self.spans]}

    def write_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)
            f.write("\n")
        return path


# -- module-level tracer stack (plain list: spans are host-side and the
# -- repo is single-threaded at the phase level being traced) -----------
_STACK: list[Tracer] = []


def current_tracer() -> Tracer | None:
    return _STACK[-1] if _STACK else None


def push_tracer(tracer: Tracer | None = None) -> Tracer:
    tracer = tracer or Tracer()
    _STACK.append(tracer)
    return tracer


def pop_tracer() -> Tracer:
    return _STACK.pop()


@contextmanager
def tracing(tracer: Tracer | None = None):
    """Activate a tracer for the block: every ``span(...)`` inside
    (including the hooks inside ``repro.run``/``repro.lower``) records
    into it.  Yields the :class:`Tracer`."""
    t = push_tracer(tracer)
    try:
        yield t
    finally:
        pop_tracer()


@contextmanager
def span(name: str, **meta):
    """Mark a host-side phase: a ``TraceAnnotation`` for the profiler,
    and a record on the active tracer when one is pushed.  Yields that
    tracer, or None."""
    t = current_tracer()
    with jax.profiler.TraceAnnotation(name, **meta):
        if t is None:
            yield None
            return
        with t.span(name, **meta):
            yield t


@contextmanager
def jax_profiler(log_dir: str):
    """Passthrough to ``jax.profiler.trace(log_dir)``: a profile of the
    device's operations with this module's spans on the same clock."""
    with jax.profiler.trace(log_dir):
        yield log_dir
