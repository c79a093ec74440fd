"""Collective time per step during which nothing else runs on the device
(ms, mean over devices): the model-axis reductions and RANL's
cross-worker all-reduce where they are not hidden."""


def read(ctx):
    s = ctx["trace"].collective_exposed_s()
    return None if s is None else 1e3 * s / ctx["counts"]["steps"]
