"""Chip benchmark of RANL: runs one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell's configuration file
(``configs`` in ``BENCHMARK.json``), its traffic mix
(``bench/traffic/<traffic>.json``), the driver of its face
(``bench/faces/<face>.py``, named by the configuration file) and, with
``--trace 1``, one reader per per-layer metric
(``bench/metrics/<metric>.py``).

A run: set-up (TPU start, weights or data from ``--seed``, compile or
cache load, warm-up and the checked first steps), a measured window of
``--seconds``, then the comparison with the plain reference that decides
``correct``.  The last line of standard output is one JSON object.  With
no TPU, fewer chips than the cell asks for, or a device kind missing from
``bench/peaks.json``, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(Exception):
    """A run that cannot produce a result (exit code 3)."""


def load_json(rel: str):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def resolve_cell(name: str):
    """-> (spec, cell, config, traffic) for the workload ``name``."""
    spec = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(entry["file"])
    traffic = load_json(os.path.join("bench", "traffic",
                                     cell["traffic"] + ".json"))
    return spec, cell, config, traffic


def use_compile_cache() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` where set (JAX reads it itself),
    else ``<checkout>/.jax_cache``: a fixed path, because the path is part
    of the cache's key.  The persistent cache's thresholds stay as the
    program leaves them, so a compile that users pay on every call is paid
    here too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_devices(chips: int, peaks: dict):
    """The cell's TPU devices; raises where there is no TPU, too few chips
    or a device kind without peaks."""
    import jax
    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        raise BenchError(f"no TPU: {e}") from e
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return devices[:chips]


class CompileCounter:
    """Backend compilations and persistent-cache hits, by phase."""

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1

    def _on_event(self, event, **_kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1


def load_reader(name: str):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_per_layer(spec, cell):
    """Per-layer metrics this cell reports: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in spec["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    return [m for m in spec["per_layer"]
            if cell["name"] in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in reported)]


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def make_face(config, traffic, devices, seed, workload):
    face_mod = importlib.import_module(f"bench.faces.{config['face']}")
    return face_mod.Face(config, traffic, devices, seed, workload)


def run_cell(args, *, spec, cell, config, traffic, devices, peaks,
             face=None):
    """Set-up, window, reference check.  Returns the result dict."""
    chips = cell["chips"]
    split = {"import_init_s": time.perf_counter() - T_START}
    counter = CompileCounter()
    if face is None:
        face = make_face(config, traffic, devices, args.seed, cell["name"])
    face.setup(split)
    setup_s = time.perf_counter() - T_START
    c_setup, h_setup = counter.compiles, counter.hits

    if args.trace:
        import jax
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # host spans and runtime events, no Python frames: the frame
        # tracer slows the host path under test and swells the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        with jax.profiler.trace(trace_dir, profiler_options=opts):
            counts = face.window(args.seconds)
    else:
        counts = face.window(args.seconds)
    c_window = counter.compiles - c_setup
    mem_peak = memory_peak_bytes(devices)
    print(f"setup split: {json.dumps({k: round(v, 6) for k, v in split.items()})} "
          f"compiles_in_setup={c_setup} cache_hits_in_setup={h_setup} "
          f"compiles_in_window={c_window} setup_s={setup_s:.6f}",
          file=sys.stderr, flush=True)

    result_metrics = {}
    breakdown = None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": mem_peak}
    if args.trace:
        from bench import trace_reduce
        trace = trace_reduce.load(trace_dir, len(devices))
        ctx = dict(trace=trace, counts=counts, config=config,
                   traffic=traffic, chips=chips,
                   peak=peaks["devices"][devices[0].device_kind],
                   memory_peak_bytes=mem_peak)
        for m in cell_per_layer(spec, cell):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s()
        breakdown = {"device_ops": trace.top_ops(10),
                     "idle_gaps": trace.idle_gaps(10)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = face.end_to_end(counts)
        e2e["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            if m["name"] in e2e and cell["name"] in m.get(
                    "workloads", [cell["name"]]):
                result_metrics[m["name"]] = {"value": e2e[m["name"]],
                                             "unit": m["unit"]}

    face.release()
    t_ref = time.perf_counter()
    checks = face.verify()
    print(f"reference check took {time.perf_counter() - t_ref:.3f} s",
          file=sys.stderr)
    correct = all(c["value"] <= c["limit"] for c in checks)
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
    out = {"correct": bool(correct), "attempted": counts["attempted"],
           "failed": counts["failed"], "metrics": result_metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec, cell, config, traffic = resolve_cell(args.workload)
        peaks = load_json(os.path.join("bench", "peaks.json"))
        sys.path.insert(0, os.path.join(ROOT, "src"))
        sys.path.insert(0, ROOT)
        import repro  # noqa: F401  (the system under test must be here)
        use_compile_cache()
        devices = require_devices(cell["chips"], peaks)
        out = run_cell(args, spec=spec, cell=cell, config=config,
                       traffic=traffic, devices=devices, peaks=peaks)
    except (BenchError, FileNotFoundError, ImportError, KeyError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
