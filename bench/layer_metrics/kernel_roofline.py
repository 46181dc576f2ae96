"""Kernels: the three substrate ops' share of their roofline.  For each
op, its calls in the trace times the least time of one call at the
chip's peaks (``bench.kernel_work``, from the lanes and pages a call
serves), summed, over the device time of the ops' scopes."""

from bench import kernel_work


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    least = spent = 0.0
    for op in kernel_work.OPS:
        scope = f"kernel:{op}"
        calls = tr.scope_calls.get(scope, 0)
        if calls == 0:
            continue
        least += calls * kernel_work.least_seconds(
            op, ctx.lanes_per_shard, ctx.pages, ctx.peak)
        spent += tr.scope_seconds[scope]
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
