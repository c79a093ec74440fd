"""The round loop's share of its bandwidth roofline (%): the least time
its required traffic (one pass over the kept columns of X per round) takes
at the HBM peak, over the round-loop program's device time."""

from bench.flops import round_bytes


def read(ctx):
    tr, c = ctx["trace"], ctx["counts"]
    dev_s = tr.module_s(c["round_module"])
    if dev_s <= 0:
        return None
    need_s = round_bytes(c["comm_floats"], c["rows_per_worker"]) \
        / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * need_s / dev_s
