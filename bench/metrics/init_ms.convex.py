"""Per job, host wall time of the job minus the round loop's device time:
the init phase, its dispatch and whatever compiles per call (ms)."""


def read(ctx):
    tr, c = ctx["trace"], ctx["counts"]
    if tr.module_count(c["round_module"]) == 0:
        return None
    return 1e3 * (sum(c["job_walls_s"]) - tr.module_s(c["round_module"])) \
        / c["jobs"]
