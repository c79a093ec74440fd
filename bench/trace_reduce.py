"""Reduction of a profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote, with
``jax.profiler.ProfileData``, into a ``Trace`` of plain events: device
operations and module executions per device, and host spans.  Every
number below is computed from those events alone, so it can be checked
on a small recorded trace without a chip.

* busy: the union of a device's operation intervals inside the window;
  idle share = 1 - busy / window.
* module time: the summed device durations of a module's executions,
  found by its name.
* exposed collective time: the part of a device's collective operations
  during which no other operation runs on that device (control-flow ops,
  which span their bodies, do not count as running).
* idle gaps: the holes in device 0's busy union, each named after the
  innermost host span that covers its midpoint.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|^send|^recv")
# control-flow ops span the operations of their bodies
CONTAINER = re.compile(r"^(while|conditional|call)\b")


def op_name(text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


@dataclass
class Trace:
    # device -> list of (name, start_ns, end_ns)
    ops: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    # host spans: (name, start_ns, end_ns)
    host: list = field(default_factory=list)
    window: tuple = (0.0, 0.0)

    # ------------------------------------------------------------- window
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def devices(self):
        return sorted(self.ops)

    # --------------------------------------------------------------- busy
    def busy_intervals(self, dev):
        w0, w1 = self.window
        return union((max(s, w0), min(e, w1)) for _, s, e in self.ops[dev]
                     if e > w0 and s < w1)

    def busy_s(self) -> float:
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(measure(self.busy_intervals(d)) for d in devs) \
            / len(devs) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # ------------------------------------------------------------ modules
    def module_s(self, name_part: str) -> float:
        """Mean over devices of the summed durations of the module
        executions whose name contains ``name_part``."""
        devs = self.devices()
        tot = sum(e - s for d in devs for n, s, e in self.modules.get(d, [])
                  if name_part in n)
        return tot / max(len(devs), 1) / 1e9

    def module_count(self, name_part: str) -> int:
        devs = self.devices()
        return sum(1 for n, _, _ in self.modules.get(devs[0], [])
                   if name_part in n) if devs else 0

    # -------------------------------------------------------- collectives
    def collective_exposed_s(self) -> float | None:
        """Mean over devices of collective time not overlapped by any
        other operation; None where no collective ran."""
        devs = self.devices()
        found, tot = False, 0.0
        for d in devs:
            coll = union((s, e) for n, s, e in self.ops[d]
                         if COLLECTIVE.search(n))
            if coll:
                found = True
            comp = union((s, e) for n, s, e in self.ops[d]
                         if not COLLECTIVE.search(n)
                         and not CONTAINER.search(n))
            tot += measure(coll) - measure(intersect(coll, comp))
        return tot / len(devs) / 1e9 if found else None

    # ---------------------------------------------------------- breakdown
    def top_ops(self, k: int):
        devs = self.devices()
        per = defaultdict(float)
        for d in devs:
            for n, s, e in self.ops[d]:
                if not CONTAINER.search(n):
                    per[n] += (e - s) / 1e9
        top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / len(devs)] for n, v in top]

    def idle_gaps(self, k: int):
        devs = self.devices()
        if not devs:
            return []
        w0, w1 = self.window
        busy = self.busy_intervals(devs[0])
        gaps, cur = [], w0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < w1:
            gaps.append((cur, w1))
        per = defaultdict(float)
        names = self.innermost_host([(s + e) / 2 for s, e in gaps])
        for (s, e), name in zip(gaps, names):
            per[name] += (e - s) / 1e9
        top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v] for n, v in top]

    def innermost_host(self, times):
        """The shortest host span covering each of ``times``: one sweep
        over the spans in start order with a heap keyed by duration."""
        spans = sorted(self.host, key=lambda x: x[1])
        out = ["no host span"] * len(times)
        heap, i = [], 0
        for q in sorted(range(len(times)), key=times.__getitem__):
            t = times[q]
            while i < len(spans) and spans[i][1] <= t:
                n, s, e = spans[i]
                heapq.heappush(heap, (e - s, e, n))
                i += 1
            while heap and heap[0][1] < t:
                heapq.heappop(heap)
            if heap:
                out[q] = heap[0][2]
        return out


# ------------------------------------------------------------ intervals
def union(intervals):
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def measure(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def intersect(a, b):
    """Intersection of two sorted disjoint interval lists."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


# -------------------------------------------------------------- loading
def device_index(plane_name: str):
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def from_events(events, num_devices: int) -> Trace:
    """``events``: iterable of (plane, line, name, start_ns, duration_ns).
    The window runs from the first host span named ``bench.*`` to the
    last end of a ``bench.*`` span or of a device operation after it."""
    tr = Trace()
    bench = []
    for plane, line, name, start, dur in events:
        s, e = float(start), float(start) + float(dur)
        dev = device_index(plane)
        if dev is not None:
            if dev >= num_devices:
                continue
            if line == OPS_LINE:
                tr.ops.setdefault(dev, []).append((op_name(name), s, e))
            elif line == MODULES_LINE:
                tr.modules.setdefault(dev, []).append((name, s, e))
        elif plane.startswith("/host"):
            tr.host.append((name, s, e))
            if name.startswith(WINDOW_SPAN_PREFIX):
                bench.append((s, e))
    for d in range(num_devices):
        tr.ops.setdefault(d, [])
    if not bench:
        raise ValueError("the trace holds no bench.* host span")
    w0 = min(s for s, _ in bench)
    w1 = max(e for _, e in bench)
    last_op = max((e for d in tr.ops for _, _, e in tr.ops[d]),
                  default=w1)
    tr.window = (w0, max(w1, last_op))
    return tr


def xplane_events(path: str):
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                yield (plane.name, line.name, ev.name, ev.start_ns,
                       ev.duration_ns)


def load(trace_dir: str, num_devices: int) -> Trace:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_events(xplane_events(paths[0]), num_devices)
