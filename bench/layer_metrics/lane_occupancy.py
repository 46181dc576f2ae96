"""Time engine (vmap lockstep): the share of lockstep lane-steps that
advance a lane with steps of its own left.  Each shard steps all its
lanes until its slowest lane is done, so the share is the lanes' steps
over lanes times each shard's largest step count (an exact count)."""


def read(ctx):
    steps = ctx.jobs[-1]["steps"].reshape(ctx.shards, ctx.lanes_per_shard)
    lockstep = float((steps.max(axis=1) * ctx.lanes_per_shard).sum())
    if lockstep <= 0:
        return None
    return 100.0 * float(steps.sum()) / lockstep
