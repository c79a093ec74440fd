"""The program's own host spans, read against the device's busy time.

``repro.obs.span`` marks each phase of a convex job as a host event on
the profiler's clock: ``ranl.run`` around the job, ``ranl.init`` with its
``.grad``, ``.hessian``, ``.project`` and ``.factor`` phases, then
``ranl.rounds`` (the round loop's dispatch, and its lowering and compile
on a cache miss) and ``ranl.result`` (the host reads that wait for the
round loop).  JAX's own host spans of lowering and backend compilation
nest under them.  Everything here is computed from ``Trace.host`` and
``Trace.busy_intervals`` alone, so it can be checked on synthetic events.

The readers in ``bench/metrics/`` return None where ``runs`` finds no
``ranl.run`` span: a program without these spans.

    python bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

runs one traced window of a convex cell, with no reference check, and
prints one JSON object: the mean duration of each ``ranl.*`` span per
job, the device-idle time per job under each innermost ``ranl.*`` span,
and the in-window compiles and lowerings under each.
"""

from __future__ import annotations

import bisect
import os
import sys
from collections import defaultdict

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.trace_reduce import Trace, intersect, measure, union  # noqa: E402

RUN = "ranl.run"
INIT = "ranl.init"
PROGRAM_PREFIX = "ranl."
JOB = "bench.job"
COMPILE = ("backend_compile_and_load", "backend_compile")
LOWER = ("lower_sharding_computation",)
NO_SPAN = "no ranl span"


def named(trace: Trace, names) -> list:
    """(start, end) of the host spans whose name is in ``names``."""
    return [(s, e) for n, s, e in trace.host if n in names]


def nested(inner, outer) -> list:
    """The intervals of ``inner`` that lie inside one of ``outer``."""
    return [(s, e) for s, e in inner
            if any(os_ <= s and e <= oe for os_, oe in outer)]


def outermost(intervals) -> list:
    """Drop the intervals that lie inside another of the list."""
    return [(s, e) for s, e in intervals
            if not any((os_, oe) != (s, e) and os_ <= s and e <= oe
                       for os_, oe in intervals)]


def runs(ctx):
    """The window's ``ranl.run`` intervals, one per job, or None where
    the trace holds none.  Their count must equal the jobs the face
    counted: every per-job number divides by it."""
    out = named(ctx["trace"], (RUN,))
    if not out:
        return None
    if len(out) != ctx["counts"]["jobs"]:
        raise ValueError(f"{len(out)} {RUN} spans in the window for "
                         f"{ctx['counts']['jobs']} jobs")
    return out


def idle_s(trace: Trace, intervals) -> float:
    """Device-idle time inside the union of ``intervals``, mean over the
    trace's devices (s)."""
    u = union(intervals)
    devs = trace.devices()
    tot = sum(measure(u) - measure(intersect(u, trace.busy_intervals(d)))
              for d in devs)
    return tot / max(len(devs), 1) / 1e9


def innermost(trace: Trace, times) -> list:
    """The innermost ``ranl.*`` span covering each of ``times``."""
    spans = [h for h in trace.host if h[0].startswith(PROGRAM_PREFIX)]
    names = Trace(host=spans).innermost_host(times)
    return [NO_SPAN if n == "no host span" else n for n in names]


def idle_by_phase(trace: Trace, within) -> dict:
    """Device-idle time inside the union of ``within``, by the innermost
    ``ranl.*`` span over it, mean over devices (s).  Idle intervals are
    cut at every ``ranl.*`` span boundary, so each piece has one
    innermost span."""
    cuts = sorted({t for n, s, e in trace.host
                   if n.startswith(PROGRAM_PREFIX) for t in (s, e)})
    u = union(within)
    pieces = []
    for d in trace.devices():
        for s, e in u:
            busy = intersect([(s, e)], trace.busy_intervals(d))
            cur = s
            for bs, be in busy + [(e, e)]:
                if bs > cur:
                    bounds = [cur] + cuts[bisect.bisect_right(cuts, cur):
                                          bisect.bisect_left(cuts, bs)] \
                        + [bs]
                    pieces += list(zip(bounds, bounds[1:]))
                cur = max(cur, be)
    per = defaultdict(float)
    names = innermost(trace, [(s + e) / 2 for s, e in pieces])
    for (s, e), name in zip(pieces, names):
        per[name] += (e - s) / 1e9
    n_dev = max(len(trace.devices()), 1)
    return {k: v / n_dev for k, v in per.items()}


def by_phase(trace: Trace, names) -> dict:
    """The outermost host spans named in ``names`` (backend compiles, or
    lowerings), by the innermost ``ranl.*`` span over each:
    {phase: [count, host seconds]}."""
    spans = outermost(named(trace, names))
    out = defaultdict(lambda: [0, 0.0])
    for (s, e), name in zip(spans, innermost(
            trace, [(s + e) / 2 for s, e in spans])):
        out[name][0] += 1
        out[name][1] += (e - s) / 1e9
    return dict(out)


def summary(trace: Trace, jobs: int) -> dict:
    """Per job: mean duration of each ``ranl.*`` span, device-idle time
    by innermost ``ranl.*`` span inside the ``bench.job`` spans, with the
    share of it that ``ranl.*`` spans cover; and, for the window, the
    backend compiles and lowerings by phase."""
    dur = defaultdict(float)
    for n, s, e in trace.host:
        if n.startswith(PROGRAM_PREFIX):
            dur[n] += (e - s) / 1e9
    idle = idle_by_phase(trace, named(trace, (JOB,)))
    total = sum(idle.values())
    return {
        "jobs": jobs,
        "ranl_runs": len(named(trace, (RUN,))),
        "span_ms_per_job": {k: 1e3 * v / jobs
                            for k, v in sorted(dur.items())},
        "idle_ms_per_job": {k: 1e3 * v / jobs for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "idle_in_jobs_ms_per_job": 1e3 * total / jobs,
        "ranl_idle_cover": (1 - idle.get(NO_SPAN, 0.0) / total
                            if total else None),
        "compiles_by_phase": {k: [c, 1e3 * t] for k, (c, t) in
                              by_phase(trace, COMPILE).items()},
        "lowerings_by_phase": {k: [c, 1e3 * t] for k, (c, t) in
                               by_phase(trace, LOWER).items()},
        "window_s": trace.window_s(),
        "busy_s": trace.busy_s(),
    }


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import tempfile

    from bench import run, trace_reduce
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec, cell, config, traffic = run.resolve_cell(args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import jax
    run.use_compile_cache()
    devices = run.require_devices(
        cell["chips"], run.load_json(os.path.join("bench", "peaks.json")))
    face = run.make_face(config, traffic, devices, args.seed, cell["name"])
    face.setup({})
    counter = run.CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="program_spans_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        counts = face.window(args.seconds)
    trace = trace_reduce.load(trace_dir, len(devices))
    shutil.rmtree(trace_dir, ignore_errors=True)
    out = summary(trace, counts["jobs"])
    out["compiles_in_window"] = counter.compiles
    out["cache_hits_in_window"] = counter.hits
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
