"""Per job, the host time of the program's ``ranl.init`` span: the init
phase from its first dispatch to its return (ms)."""

from bench.program_spans import INIT, named, nested, runs


def read(ctx):
    jobs = runs(ctx)
    if jobs is None:
        return None
    init = nested(named(ctx["trace"], (INIT,)), jobs)
    return sum(e - s for s, e in init) / 1e6 / len(jobs)
