"""Device time per step of the expert layer's grouped matmuls, forward and
backward (ms, mean over devices).  The chip trace names them
``ragged-dot`` (XLA's ragged dot, ``grouped_matmul`` in
``models/moe.py``)."""

OPS = ("ragged-dot",)


def expert_s(ctx):
    """Grouped-matmul device seconds per step, or None where none ran."""
    tr, c = ctx["trace"], ctx["counts"]
    devs = tr.devices()
    total = sum(e - s for d in devs for n, s, e in tr.ops[d]
                if n.startswith(OPS))
    if not devs or total == 0:
        return None
    return total / len(devs) / 1e9 / c["steps"]


def read(ctx):
    s = expert_s(ctx)
    return None if s is None else 1e3 * s
