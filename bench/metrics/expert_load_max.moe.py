"""The window's mean of the step's load_max_ratio: the busiest held
expert's routed rows over the mean (layers x held experts)."""


def read(ctx):
    return ctx["counts"].get("load_max_ratio")
