"""Per job, the device-idle time inside the program's ``ranl.init`` span,
mean over the cell's chips (ms)."""

from bench.program_spans import INIT, idle_s, named, nested, runs


def read(ctx):
    jobs = runs(ctx)
    if jobs is None:
        return None
    init = nested(named(ctx["trace"], (INIT,)), jobs)
    return 1e3 * idle_s(ctx["trace"], init) / len(jobs)
