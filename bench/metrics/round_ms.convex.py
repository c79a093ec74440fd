"""Device time of the round-loop program per round (ms)."""


def read(ctx):
    tr, c = ctx["trace"], ctx["counts"]
    if tr.module_count(c["round_module"]) == 0:
        return None
    return 1e3 * tr.module_s(c["round_module"]) / c["rounds"]
