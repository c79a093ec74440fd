"""Model FLOPs of the window's steps over its time, over chips x the bf16
peak (%)."""

from bench.flops import train_step_flops


def read(ctx):
    c, t = ctx["counts"], ctx["traffic"]
    flops = train_step_flops(ctx["config"]["model"], t["batch"], t["seq"])
    rate = flops * c["steps"] / c["elapsed_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
