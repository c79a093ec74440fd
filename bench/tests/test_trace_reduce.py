"""The reduction from trace events to device metrics, on a small trace."""

import pytest

from bench import trace_reduce as tr

MS = 1_000_000  # ns


def events():
    """Two devices, a 100 ms window opened by a host span.

    device 0: fusion 0-30, all-reduce 25-45 (5 ms under the fusion, 15 ms
    exposed) inside a loop 20-50, fusion 60-70, all in module
    jit_step(1) 0-70, and
    jit_bigram_batch(2) 80-90 holding one op 80-90.
    device 1: one fusion 10-20.
    host: bench.step 0-100, bench.wait 45-60.
    """
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    return [
        ("/host:CPU", "python", "bench.step", 0, 100 * MS),
        ("/host:CPU", "python", "bench.wait", 45 * MS, 15 * MS),
        (d0, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0,
         30 * MS),
        (d0, "XLA Ops", "%all-reduce.3 = f32[8]{0} all-reduce(%x)",
         25 * MS, 20 * MS),
        # a loop spanning the collective hides none of it
        (d0, "XLA Ops", "%while.9 = (s32[]) while(%t)", 20 * MS, 30 * MS),
        (d0, "XLA Ops", "fusion.2", 60 * MS, 10 * MS),
        (d0, "XLA Ops", "copy.4", 80 * MS, 10 * MS),
        (d0, "XLA Modules", "jit_step(1)", 0, 70 * MS),
        (d0, "XLA Modules", "jit_bigram_batch(2)", 80 * MS, 10 * MS),
        (d1, "XLA Ops", "fusion.1", 10 * MS, 10 * MS),
        ("/device:TPU:2", "XLA Ops", "fusion.1", 0, 100 * MS),
    ]


def test_busy_union_and_idle_share():
    t = tr.from_events(events(), num_devices=2)
    assert t.window_s() == pytest.approx(0.1)
    # device 0 busy 0-50 (the loop runs to 50), 60-70, 80-90 = 70 ms;
    # device 1 10 ms
    assert t.busy_s() == pytest.approx((0.070 + 0.010) / 2)
    assert t.idle_share() == pytest.approx(1 - 0.040 / 0.1)


def test_module_time_by_name():
    t = tr.from_events(events(), num_devices=2)
    assert t.module_s("bigram_batch") == pytest.approx(0.010 / 2)
    assert t.module_count("jit_step") == 1
    assert t.module_count("nothing") == 0


def test_exposed_collective_time():
    t = tr.from_events(events(), num_devices=2)
    # 15 ms on device 0, none on device 1, averaged over the two
    assert t.collective_exposed_s() == pytest.approx(0.015 / 2)
    no_coll = [e for e in events() if "all-reduce" not in e[2]]
    assert tr.from_events(no_coll, 2).collective_exposed_s() is None


def test_idle_gaps_named_by_host_span_and_top_ops():
    t = tr.from_events(events(), num_devices=2)
    gaps = dict(t.idle_gaps(10))
    assert gaps["bench.wait"] == pytest.approx(0.010)
    assert gaps["bench.step"] == pytest.approx(0.010 + 0.010)
    top = t.top_ops(2)
    assert top[0][0] == "fusion.1"
    assert "while.9" not in dict(t.top_ops(10))
    assert top[0][1] == pytest.approx((0.030 + 0.010) / 2)


def test_trace_without_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.from_events([e for e in events() if e[0] != "/host:CPU"], 2)
