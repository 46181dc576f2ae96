"""Kernels: the share of device-busy time spent in the three substrate
ops' scopes (``kernel:batched_evict``, ``kernel:fifo_grant``,
``kernel:wake_solve``), from the profiler trace of one job."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.scope_seconds or tr.busy_s <= 0:
        return None
    return 100.0 * sum(tr.scope_seconds.values()) / (tr.busy_s * tr.devices)
