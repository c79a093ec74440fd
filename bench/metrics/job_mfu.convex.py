"""Whole jobs' share of the chip's peak (%): the least time their required
work takes (the init Hessian's FLOPs at the bf16 peak, plus one pass over
the kept columns of X per round at the HBM peak), over the jobs' wall
time."""

from bench.flops import init_flops, round_bytes


def read(ctx):
    c, pk = ctx["counts"], ctx["peak"]
    need_s = (c["jobs"] * init_flops(c["num_workers"], c["rows_per_worker"],
                                     c["dim"]) / pk["bf16_flops_per_s"]
              + round_bytes(c["comm_floats"], c["rows_per_worker"])
              / pk["hbm_bytes_per_s"])
    return 100.0 * need_s / sum(c["job_walls_s"])
