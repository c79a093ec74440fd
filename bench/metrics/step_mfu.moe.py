"""Model FLOPs of the window's steps (the held experts' rows as the step
counted them) over its time, over chips x the bf16 peak (%)."""

from bench.moe import train_step_flops


def read(ctx):
    c, t = ctx["counts"], ctx["traffic"]
    if "held_rows" not in c:
        return None
    flops = (c["steps"] * train_step_flops(ctx["config"], t["batch"],
                                           t["seq"], 0.0)
             + train_step_flops(ctx["config"], 0, 0, c["held_rows"]))
    rate = flops / c["elapsed_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
