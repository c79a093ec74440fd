"""The readers of the program's ``ranl.*`` spans, on synthetic events and
on a small traced convex run on the CPU."""

import types

import jax
import pytest

from bench import program_spans as ps
from bench import run
from bench import trace_reduce as tr

MS = 1_000_000  # ns
READERS = ["init_span_ms.convex", "init_idle_ms.convex",
           "compiles_per_job.convex", "compile_ms.convex"]


def events():
    """One job, two devices, a 100 ms window opened by ``bench.job``.

    host: bench.job 0-100 holding ranl.run 5-95, which holds ranl.init
    10-50 (ranl.init.hessian 20-40 in it; a lowering 22-25 and a compile
    25-30, with a nested backend_compile 26-29), ranl.rounds 55-70 (a
    compile 60-65) and ranl.result 70-90; one more compile 96-99 outside
    ranl.run.
    device 0: ops 0-15, 30-45, 60-80.  device 1: op 10-20.
    """
    h, d0, d1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
    host = [("bench.job", 0, 100), ("ranl.run", 5, 95),
            ("ranl.init", 10, 50), ("ranl.init.hessian", 20, 40),
            ("lower_sharding_computation", 22, 25),
            ("backend_compile_and_load", 25, 30),
            ("backend_compile", 26, 29),
            ("ranl.rounds", 55, 70), ("backend_compile_and_load", 60, 65),
            ("ranl.result", 70, 90), ("backend_compile_and_load", 96, 99)]
    ops = [(d0, 0, 15), (d0, 30, 45), (d0, 60, 80), (d1, 10, 20)]
    return ([(h, "python", n, s * MS, (e - s) * MS) for n, s, e in host]
            + [(d, "XLA Ops", "fusion.1", s * MS, (e - s) * MS)
               for d, s, e in ops])


def ctx(evs, jobs=1):
    return {"trace": tr.from_events(evs, num_devices=2),
            "counts": {"jobs": jobs}}


def read(name, c):
    return run.load_reader(name)(c)


def test_idle_clipped_to_init_across_two_devices():
    # ranl.init 10-50: device 0 busy 10-15 and 30-45 (idle 20 ms),
    # device 1 busy 10-20 (idle 30 ms); mean over the two
    assert read("init_idle_ms.convex", ctx(events())) == pytest.approx(25.0)
    assert read("init_span_ms.convex", ctx(events())) == pytest.approx(40.0)


def test_compiles_counted_only_inside_run():
    c = ctx(events())
    # 25-30 (its nested backend_compile once) and 60-65; not 96-99
    assert read("compiles_per_job.convex", c) == pytest.approx(2.0)
    # lowering 22-25 and compiles 25-30, 60-65, overlaps once
    assert read("compile_ms.convex", c) == pytest.approx(13.0)


def test_per_job_and_count_check():
    shifted = [(p, ln, n, s + 100 * MS, d) for p, ln, n, s, d in events()]
    c = ctx(events() + shifted, jobs=2)
    assert read("init_idle_ms.convex", c) == pytest.approx(25.0)
    assert read("compiles_per_job.convex", c) == pytest.approx(2.0)
    with pytest.raises(ValueError):
        read("init_span_ms.convex", ctx(events(), jobs=2))


def test_every_reader_is_none_without_run_span():
    bare = [e for e in events() if not e[2].startswith("ranl.")]
    for name in READERS:
        assert read(name, ctx(bare)) is None


def test_idle_by_innermost_phase():
    t = tr.from_events(events(), num_devices=2)
    got = ps.idle_by_phase(t, ps.named(t, (ps.JOB,)))
    want = {"ranl.init": 10.0, "ranl.init.hessian": 15.0, "ranl.run": 12.5,
            "ranl.rounds": 10.0, "ranl.result": 15.0, ps.NO_SPAN: 7.5}
    assert {k: v * 1e3 for k, v in got.items()} == pytest.approx(want)
    comp = ps.by_phase(t, ps.COMPILE)
    assert {k: c for k, (c, _) in comp.items()} == {
        "ranl.init.hessian": 1, "ranl.rounds": 1, ps.NO_SPAN: 1}
    assert ps.by_phase(t, ps.LOWER) == {
        "ranl.init.hessian": [1, pytest.approx(0.003)]}


def test_traced_convex_run_reports_span_metrics():
    from bench.tests.test_faults import SEED, tiny
    spec, cell, config, traffic = tiny("convex.epsilon.dense")
    devices = jax.devices()[:1]
    peaks = {"devices": {devices[0].device_kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}}
    args = types.SimpleNamespace(seed=SEED, seconds=1.0, trace=1)
    out = run.run_cell(args, spec=spec, cell=cell, config=config,
                       traffic=traffic, devices=devices, peaks=peaks)
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(READERS) <= set(got)
    assert got["init_span_ms.convex"] > 0
    assert got["compiles_per_job.convex"] >= 0
