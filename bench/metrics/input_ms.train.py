"""Device time per step of the batch generator's program (ms)."""


def read(ctx):
    tr, c = ctx["trace"], ctx["counts"]
    if tr.module_count(c["batch_fn"]) == 0:
        return None
    return 1e3 * tr.module_s(c["batch_fn"]) / c["steps"]
