"""Per job, the host time of JAX's lowering and backend-compile spans
inside the program's ``ranl.run`` span, overlaps counted once (ms)."""

from bench.program_spans import COMPILE, LOWER, named, nested, runs
from bench.trace_reduce import measure, union


def read(ctx):
    jobs = runs(ctx)
    if jobs is None:
        return None
    spans = nested(named(ctx["trace"], COMPILE + LOWER), jobs)
    return measure(union(spans)) / 1e6 / len(jobs)
