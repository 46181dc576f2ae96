"""How ``correct`` is decided: the timed jobs' lane states against the
regression copy (:mod:`bench.reference`) at the same seed and sizes, and
against the configuration's guarantees (:mod:`bench.invariants`).

The regression copy is a frozen copy of the simulator as the benchmark
was first written, on plain jnp forms of the three substrate kernels.  It
builds its own database, streams and spec with its own classes, runs
every lane of the cell to the same horizon, and hands back the same
leaves as the timed path.  Being a copy, it holds the timed path to the
results the simulator gave then, not to an independent reading of the
semantics; that is the guarantees' part.  Each job of the window is
compared, lane by lane and stream by stream, on four numbers:

* ``progress_gap``: tuples each stream consumed (``consumed``) and the
  query it is in (``qidx``), as the largest relative gap;
* ``done_gap``: the completion time of each finished stream, in seconds
  (a stream finished on one side only counts its whole clock);
* ``io_gap``: bytes and pages loaded, as the largest relative gap;
* ``clock_gap``: each lane's final simulated clock, relative.

Each number has its limit in :data:`LIMITS` (those of the guarantees in
:data:`bench.invariants.LIMITS`); the run is correct when every job's
every number is at or under its limit.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import invariants, workgen, workload
from .job import split_plan, summary, to_host

#: the largest gap allowed for each number compared
LIMITS: Dict[str, float] = {
    "progress_gap": 0.0,
    "done_gap": 0.0,
    "io_gap": 0.0,
    "clock_gap": 0.0,
}


def _rel(a: np.ndarray, b: np.ndarray) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))


def gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
         ) -> Dict[str, float]:
    """The four numbers compared, for one job's lanes."""
    done_p = np.asarray(prog["stream_done_t"], np.float64)
    done_r = np.asarray(ref["stream_done_t"], np.float64)
    if done_p.shape != done_r.shape:
        done = float("inf")
    else:
        clock = np.broadcast_to(np.asarray(ref["t"], np.float64)[:, None],
                                done_r.shape)
        both = (done_p >= 0) & (done_r >= 0)
        one = (done_p >= 0) != (done_r >= 0)
        diff = np.where(both, np.abs(done_p - done_r),
                        np.where(one, clock, 0.0))
        done = float(np.max(diff)) if diff.size else 0.0
    return {
        "progress_gap": max(_rel(prog["consumed"], ref["consumed"]),
                            _rel(prog["qidx"], ref["qidx"])),
        "done_gap": done,
        "io_gap": max(_rel(prog["io_bytes"], ref["io_bytes"]),
                      _rel(prog["loads"], ref["loads"])),
        "clock_gap": _rel(prog["t"], ref["t"]),
    }


def is_correct(numbers: Dict[str, float],
               limits: Dict[str, float] = LIMITS) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def guarantees(cell, seed: int) -> dict:
    """What :mod:`bench.invariants` needs of a cell at a seed: the plan of
    pages and queries, each lane's pool, the bandwidth and the horizon."""
    cf, tr = cell.config, cell.traffic
    wl = workload.build(cf, tr, seed, side=workgen.REFERENCE)
    tuples = {t: workgen.table_tuples(cf, t) for t in cf["tables"]}
    return {"plan": invariants.Plan(cf, wl.streams, tuples),
            "capacities": [cap for _, _, cap in wl.lanes],
            "bandwidth": tr["bandwidth_bps"], "horizon": tr["horizon_s"]}


def judge(jobs: List[Dict[str, np.ndarray]], ref: Dict[str, np.ndarray],
          guar: dict):
    """Every number compared, each the worst over the jobs, and the count
    of lane-jobs that breach a limit on their own numbers."""
    limits = dict(LIMITS, **invariants.LIMITS)
    out = dict.fromkeys(limits, -np.inf if jobs else np.inf)
    failed = 0
    caps = guar["capacities"]
    for job in jobs:
        for i in range(len(ref["t"])):
            lane = {k: v[i:i + 1] for k, v in job.items()}
            nums = gaps(lane, {k: v[i:i + 1] for k, v in ref.items()})
            nums.update(invariants.numbers(
                lane, guar["plan"], caps[i:i + 1], guar["bandwidth"],
                guar["horizon"]))
            for k, v in nums.items():
                out[k] = max(out[k], v)
            failed += not is_correct(nums, limits)
    return out, limits, failed


def reference_summary(cell, seed: int, device=None,
                      kernels=None) -> Dict[str, np.ndarray]:
    """Every lane of ``cell`` at ``seed`` through the reference, vmapped
    on ``device``; ``kernels`` replaces its plain ops (the control)."""
    import jax

    from .reference import compiler, sim

    tr, cf = cell.traffic, cell.config
    wl = workload.build(cf, tr, seed, side=workgen.REFERENCE)
    spec = compiler.compile_workload(wl.db, wl.streams)
    base, plan = split_plan(spec)

    def lanes(plan, cfgs):
        runner = sim.make_runner(base._replace(**plan),
                                 bandwidth_ref=tr["bandwidth_bps"],
                                 time_slice=cf["time_slice_s"],
                                 stepper="horizon", kernels=kernels)
        return jax.vmap(runner)(cfgs)

    cfgs = sim.stack_configs([
        sim.make_config(spec, cap, tr["bandwidth_bps"], pol,
                        max_time=tr["horizon_s"])
        for pol, _, cap in wl.lanes])
    if device is not None:
        cfgs, plan = jax.device_put((cfgs, plan), device)
    state = jax.block_until_ready(jax.jit(lanes)(plan, cfgs))
    return to_host(summary(state))
