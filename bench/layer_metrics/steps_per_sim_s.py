"""Time engine: macro-steps of the job's slowest lane per simulated
second (an exact count from the lanes' final ``steps`` and ``t``)."""


def read(ctx):
    job = ctx.jobs[-1]
    slow = int(job["steps"].argmax())
    t = float(job["t"][slow])
    if t <= 0:
        return None
    return float(job["steps"][slow]) / t
