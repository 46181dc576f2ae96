"""One job of a cell, and the window of jobs.

A job is one call of the program's compiled runner
(``repro.core.array_sim.make_runner(spec, stepper="horizon")``, vmapped over
the cell's lanes, or under a one-axis lane mesh where the cell spreads its
lanes over several chips) on the Pallas backend: every lane steps from a
cold pool until its streams finish or its clock passes the horizon.  The
runner is compiled ahead of time, so a call can never compile.

The runner is built inside the compiled function from a spec whose query
plan (the per-stream arrays, the only part of the spec a seed changes)
is an argument rather than a constant.  Every seed then lowers to the
same program, which the persistent compile cache keeps after a
checkout's first run.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List

import numpy as np

#: the leaves of a lane's final state that the comparison reads
SUMMARY = ("t", "steps", "consumed", "qidx", "pos", "stream_done_t",
           "io_bytes", "loads", "resident")


def summary(state) -> Dict[str, object]:
    """The compared leaves of a (batched) final state, still on device."""
    return {k: getattr(state, k) for k in SUMMARY}


def to_host(summ: Dict[str, object]) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in summ.items()}


#: the spec's per-stream arrays: what dealing the streams to slots changes
PLAN = ("q_start", "q_len", "q_rate", "q_cols", "n_q", "q_table")


def split_plan(spec):
    """``(base, plan)``: the spec with its per-stream arrays left to be
    given, and those arrays.  ``base._replace(**plan)`` is the spec again;
    with traced arrays in ``plan`` it is a spec whose plan is an argument.
    The fastest query rate, which the runner reads as a number while it
    is built, stays a number: it does not depend on the streams' order."""
    rate = float(spec.max_rate)

    class _Spec(type(spec)):
        __slots__ = ()
        max_rate = rate

    plan = {k: getattr(spec, k) for k in PLAN if getattr(spec, k) is not None}
    return _Spec(*spec), plan


@dataclass
class Program:
    """The compiled timed path of a cell."""

    call: Callable[[], object]       # one job: the final states
    warm: Callable[[], object]       # the same executable, to one slice
    traces: Callable[[], int]        # times the runner was traced
    compiled: object                 # the AOT executable (HLO text)
    devices: List[object]
    lanes_per_shard: int
    compile_s: float


def build_program(spec, wl, cell, devices, backend: str = "pallas") -> Program:
    """Compile the program's runner for the cell's lanes on ``devices``,
    with the kernels of ``backend`` (``interpret`` runs the Pallas kernel
    bodies on the CPU, for the tests)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core.array_sim import make_config, make_runner, stack_configs
    from repro.kernels import ops

    tr, cf = cell.traffic, cell.config
    ops.set_backend(backend)
    shards = tr["lane_shards"]
    mesh = None
    if shards > 1:
        mesh = Mesh(np.array(devices[:shards]), ("lanes",))
    base, plan = split_plan(spec)
    traced = []

    def timed(plan, cfgs):
        traced.append(1)
        runner = make_runner(base._replace(**plan),
                             bandwidth_ref=tr["bandwidth_bps"],
                             time_slice=cf["time_slice_s"], stepper="horizon",
                             mesh=mesh)
        return runner(cfgs) if mesh is not None else jax.vmap(runner)(cfgs)

    def configs(max_time):
        cfgs = stack_configs([
            make_config(spec, cap, tr["bandwidth_bps"], pol,
                        max_time=max_time)
            for pol, _, cap in wl.lanes])
        if mesh is None:
            return jax.device_put(cfgs, devices[0])
        return jax.device_put(cfgs, NamedSharding(mesh, PartitionSpec("lanes")))

    if mesh is None:
        plan = jax.device_put(plan, devices[0])
    else:
        plan = jax.device_put(plan, NamedSharding(mesh, PartitionSpec()))
    cfgs = configs(tr["horizon_s"])
    warm_cfgs = configs(cf["time_slice_s"])
    t0 = time.perf_counter()
    compiled = jax.jit(timed).lower(plan, cfgs).compile()
    compile_s = time.perf_counter() - t0
    return Program(
        call=lambda: compiled(plan, cfgs),
        warm=lambda: compiled(plan, warm_cfgs),
        traces=lambda: len(traced),
        compiled=compiled,
        devices=list(devices[:shards]),
        lanes_per_shard=len(wl.lanes) // shards,
        compile_s=compile_s,
    )


def run_job(call: Callable[[], object]) -> Dict[str, object]:
    """One job to its end; the compared leaves of its final states."""
    import jax

    state = jax.block_until_ready(call())
    return summary(state)


def run_window(call: Callable[[], object], seconds: float,
               clock=time.perf_counter):
    """Jobs back to back, one caller, until ``seconds`` have passed; the
    job in flight then completes.  Returns the jobs' summaries and the
    window's length to the end of the last job."""
    jobs = []
    t0 = clock()
    while True:
        jobs.append(run_job(call))
        if clock() - t0 >= seconds:
            return jobs, clock() - t0
