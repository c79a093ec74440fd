"""Per job, JAX's backend compiles inside the program's ``ranl.run`` span:
the outermost ``backend_compile_and_load``/``backend_compile`` host spans
(a persistent-cache hit compiles nothing and is not counted)."""

from bench.program_spans import COMPILE, named, nested, outermost, runs


def read(ctx):
    jobs = runs(ctx)
    if jobs is None:
        return None
    return len(outermost(nested(named(ctx["trace"], COMPILE), jobs))) \
        / len(jobs)
