"""Step body: device-busy microseconds per macro-step of the job's
slowest lane, from the profiler trace of one job (busy time averaged
over the traced chips)."""


def read(ctx):
    if ctx.trace is None:
        return None
    steps = int(ctx.traced_job["steps"].max())
    if steps <= 0:
        return None
    return 1e6 * ctx.trace.busy_s / steps
