"""The grouped matmuls' share of their roofline (%): the least time of one
device's expert work per step (``bench.moe.expert_least_s``, from the
routing counters) over their device time per step (``expert_ms.moe``)."""

import importlib.util
import os

from bench.moe import expert_least_s

_spec = importlib.util.spec_from_file_location(
    "bench_metric_expert_ms_moe",
    os.path.join(os.path.dirname(__file__), "expert_ms.moe.py"))
_expert_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_expert_ms)


def read(ctx):
    c = ctx["counts"]
    s = _expert_ms.expert_s(ctx)
    if s is None or "held_rows" not in c:
        return None
    least = expert_least_s(ctx["config"], c["held_rows"] / c["steps"],
                           ctx["chips"], c["model_shards"], ctx["peak"])
    return 100.0 * least / s
