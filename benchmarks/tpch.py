"""TPC-H-like throughput run — paper Figures 14, 15, 16.

8 tables / 61 columns, 22 query templates per stream (qgen-style rotated
permutations), ~7.5GB accessed with 8 streams.  Defaults match the paper's
operating point: 600 MB/s I/O, buffer = 30% of accessed volume.

``--backend=array`` lowers the multi-table workload through
``repro.core.array_sim.compiler`` and runs the FULL paper policy set
(lru / cscan / pbm / opt) on the vmap-able array substrate: every
(policy x sweep-point) lane of a sweep executes as ONE batched
computation — by default on the event-horizon stepper
(``--stepper fixed`` for the classic cadence) and lane-sharded across
every visible device (``--mesh off`` to stay on one; array runs expose
one XLA host device per CPU core up to 8).  ``--smoke`` restricts to
the buffer sweep at a quick scale — the CI configuration (same flag
semantics as ``benchmarks/microbench.py``).

Policy lists come from ``repro.core.policy_registry`` — one source of
truth for both backends; unknown names fail there with the known-name
list.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional

from repro.core import EngineConfig, run_workload, simulate_belady
from repro.core.policy_registry import names as policy_names
from repro.core.workload import make_tpch_db, tpch_accessed_bytes, tpch_streams

POLICIES = policy_names(backend="event", paper_only=True)
ARRAY_POLICIES = policy_names(backend="array")

DEFAULTS = dict(n_streams=8, bandwidth=600e6, buffer_frac=0.3, seed=7)
#: --smoke scale per backend.  The array smoke runs at 0.05 (the batched
#: step's CPU cost bounds CI); the EVENT smoke stays at CI's historical
#: 0.25 — at 0.05 the 10%-buffer point drops the pool (~75 pages) below
#: streams x columns x prefetch wanted pages and the dict engine's churn
#: spiral turns a smoke run into tens of minutes.  The array step handles
#: that regime (it finishes the 0.1 lane in ~20s of sim time), which is
#: exactly the asymmetry the batched substrate exists for.
SMOKE_SCALE = 0.05
EVENT_SMOKE_SCALE = 0.25

SWEEP_POINTS = {
    "buffer": [0.1, 0.2, 0.3, 0.45, 0.6, 0.8],
    "bandwidth": [200e6, 400e6, 600e6, 900e6, 1200e6, 1600e6],
    "streams": [1, 2, 4, 8, 16, 24],
}


def one_point(db, policies, *, n_streams, bandwidth, buffer_frac, seed,
              time_slice=0.1) -> List[Dict]:
    streams = tpch_streams(db, n_streams=n_streams, seed=seed)
    ws = tpch_accessed_bytes(db, streams)
    # ONE capacity for the pool and the Belady replay: computing it twice
    # (as the seed did) invites silent divergence between the run and its
    # OPT reference when either expression drifts
    cap = max(1 << 22, int(buffer_frac * ws))
    rows = []
    pbm_trace = None
    for pol in policies:
        cfg = EngineConfig(
            bandwidth=bandwidth,
            buffer_bytes=cap,
            sample_interval=5.0,
            record_trace=(pol == "pbm"),
            pbm_time_slice=time_slice,
        )
        t0 = time.time()
        r = run_workload(db, streams, pol, cfg)
        rows.append({
            "policy": pol,
            "avg_stream_time_s": round(r.avg_stream_time, 3),
            "io_gb": round(r.io_gb, 3),
            "wall_s": round(time.time() - t0, 2),
        })
        if pol == "pbm":
            pbm_trace = (r.trace, r.page_sizes)
    if pbm_trace is not None and "opt" in policies:
        trace, sizes = pbm_trace
        _, missed = simulate_belady(
            trace, page_sizes=sizes, capacity_bytes=cap,
        )
        for row in rows:
            if row["policy"] == "opt":
                row["io_gb_belady_trace"] = round(missed / 1e9, 3)
    return rows


def _point_label(which: str, p) -> str:
    return f"{p:.0%}" if which == "buffer" else (
        f"{p/1e6:.0f}MB/s" if which == "bandwidth" else f"{int(p)} streams")


def sweep(which: str, policies: List[str], scale: float = 1.0, seed: int = 7):
    db = make_tpch_db(scale=scale)
    out = []
    for p in SWEEP_POINTS[which]:
        kw = dict(DEFAULTS)
        kw["seed"] = seed
        if which == "buffer":
            kw["buffer_frac"] = p
        elif which == "bandwidth":
            kw["bandwidth"] = p
        else:
            kw["n_streams"] = int(p)
        # PBM bucket resolution scales with the (scaled) workload duration
        # — the microbench convention (EngineConfig.pbm_time_slice: "scale
        # it down together with the workload").  The seed ran scaled TPC-H
        # sweeps at the fixed 0.1s slice, so scaled-run PBM rows (CI smoke
        # included) shift once against pre-PR-3 trend baselines.
        rows = one_point(db, policies, time_slice=0.1 * scale, **kw)
        for r in rows:
            r["sweep"] = f"tpch_{which}"
            r["point"] = p
        out.extend(rows)
        summary = " ".join(
            f"{r['policy']}={r['avg_stream_time_s']:.1f}s/{r['io_gb']:.1f}GB"
            for r in rows
        )
        print(f"  tpch/{which} @ {_point_label(which, p):10s} {summary}",
              flush=True)
    return out


def lane_mesh(n_lanes: int):
    """One-axis device mesh for lane-sharded execution, or ``None`` when
    only one device is visible.  Uses the largest device count that
    divides the lane count evenly (``shard_map`` needs equal shards);
    the host device count comes from ``XLA_FLAGS
    --xla_force_host_platform_device_count`` (set by :func:`main` for
    array runs before JAX initialises)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs)
    while n > 1 and n_lanes % n != 0:
        n -= 1
    if n <= 1:
        return None
    return Mesh(np.array(devs[:n]), ("lanes",))


def sweep_array(which: str, policies=None, scale: float = 1.0, seed: int = 7,
                step_pages: float = 1.0, stepper: str = "horizon",
                mesh: bool = True):
    """Array-backend TPC-H sweep: same row schema as :func:`sweep` for
    every registered array policy (the paper's full four-way comparison).

    For the buffer and bandwidth axes the workload shape is constant, so
    the compiled spec is lowered once and EVERY (policy x point) lane runs
    in one batched call — the runner is compiled over the whole policy
    set and treats policy, capacity and bandwidth as traced config
    scalars; with ``mesh`` (default) the lanes additionally spread across
    every visible device via ``shard_map``.  The streams axis changes the
    spec shape per point and falls back to per-point batched-policy runs.
    ``step_pages=2.0`` is the coarse fast mode the batched races use
    (~2x fewer steps for a few % fidelity) — the CI smoke runs the
    24-lane sweep with it to stay inside the job budget; validation
    always runs full fidelity (``validate.py``).  ``stepper`` picks the
    time engine — the event-horizon stepper is the default benchmark
    lane (validated against the same bars as the fixed cadence).
    """
    import jax

    from repro.core.array_sim import (
        compile_workload, make_config, make_runner, result_from_state,
        stack_configs,
    )

    policies = policies or ARRAY_POLICIES
    db = make_tpch_db(scale=scale)
    time_slice = 0.1 * scale
    points = SWEEP_POINTS[which]
    out: List[Dict] = []

    def rows_from(states, lanes, batch_wall, dt_ref, man=None):
        # wall_s is the batch wall amortised per lane — the lanes run
        # LOCKSTEP inside one vmapped call, so no per-lane wall exists
        # (unlike the sequential micro array rows); batch_wall_s/
        # batch_lanes carry the real measurement
        rows = []
        for i, (p, pol) in enumerate(lanes):
            r = result_from_state(
                jax.tree.map(lambda x, i=i: x[i], states), pol,
                dt_ref=dt_ref)
            rows.append({
                "policy": pol,
                "avg_stream_time_s": round(r.avg_stream_time, 3),
                "io_gb": round(r.io_gb, 3),
                "wall_s": round(batch_wall / max(1, len(lanes)), 2),
                "batch_wall_s": round(batch_wall, 2),
                "batch_lanes": len(lanes),
                "sweep": f"tpch_{which}",
                "point": p,
                "backend": "array",
                "stepper": stepper,
                "macro_steps": r.extras.get("macro_steps", r.steps),
                "skipped_time": r.extras.get("skipped_time", 0.0),
                "truncated": r.extras.get("truncated", False),
                "manifest": man,
            })
        return rows

    def run_lanes(spec, cfgs):
        m = lane_mesh(len(cfgs)) if mesh else None
        runner = make_runner(spec, bandwidth_ref=DEFAULTS["bandwidth"],
                             time_slice=time_slice, policies=policies,
                             step_pages=step_pages, stepper=stepper,
                             mesh=m)
        batched = runner if m is not None else jax.jit(jax.vmap(runner))
        t0 = time.time()
        states = jax.block_until_ready(batched(stack_configs(cfgs)))
        wall = time.time() - t0
        from repro.obs import manifest as _m
        man = _m.collect(spec=spec, runner=runner, backend="array")
        return states, wall, runner.dt_ref, man

    if which in ("buffer", "bandwidth"):
        streams = tpch_streams(db, n_streams=DEFAULTS["n_streams"], seed=seed)
        ws = tpch_accessed_bytes(db, streams)
        spec = compile_workload(db, streams)
        lanes, cfgs = [], []
        for p in points:
            frac = p if which == "buffer" else DEFAULTS["buffer_frac"]
            bw = p if which == "bandwidth" else DEFAULTS["bandwidth"]
            cap = max(1 << 22, int(frac * ws))
            for pol in policies:
                lanes.append((p, pol))
                cfgs.append(make_config(spec, cap, bw, pol))
        states, wall, dt_ref, man = run_lanes(spec, cfgs)
        out = rows_from(states, lanes, wall, dt_ref, man)
    else:
        for p in points:
            n_s = int(p)
            streams = tpch_streams(db, n_streams=n_s, seed=seed)
            ws = tpch_accessed_bytes(db, streams)
            spec = compile_workload(db, streams)
            cap = max(1 << 22, int(DEFAULTS["buffer_frac"] * ws))
            lanes = [(p, pol) for pol in policies]
            cfgs = [make_config(spec, cap, DEFAULTS["bandwidth"], pol)
                    for pol in policies]
            states, wall, dt_ref, man = run_lanes(spec, cfgs)
            out.extend(rows_from(states, lanes, wall, dt_ref, man))

    truncated = [(r["point"], r["policy"]) for r in out if r["truncated"]]
    if truncated:
        print(f"  tpch[array] WARNING: truncated lanes (livelock guard): "
              f"{truncated}", flush=True)
    for p in points:
        rows = [r for r in out if r["point"] == p]
        summary = " ".join(
            f"{r['policy']}={r['avg_stream_time_s']:.1f}s/{r['io_gb']:.1f}GB"
            for r in rows
        )
        print(f"  tpch[array]/{which} @ {_point_label(which, p):10s} "
              f"{summary}", flush=True)
    return out


def scaling_curve(scale: float = SMOKE_SCALE, seed: int = 7,
                  policy: str = "pbm", fracs=None):
    """Wall-clock vs mesh shape for the batched buffer sweep — the
    sharding scaling curve behind ``--scaling``.

    Runs the same 4-lane (buffer-frac) batched sweep on the horizon
    stepper under every usable mesh shape: plain vmap (1 device),
    lane-sharded one-axis meshes over 2/4 host devices, and the two-axis
    ``('lanes', 'page')`` meshes that page-shard the per-step candidate
    scans.  Every timed wall is compile-separated (cold run first, then
    the timed warm run) and trace-guarded: ``runner.trace_count()`` must
    be exactly 1 afterwards or the row is marked re-traced and its wall
    is not trustworthy.  Writes rows ``trend.py`` diffs run-over-run
    (>20% warm-wall growth per mesh shape flags a regression).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.array_sim import (
        compile_workload, make_config, make_runner, result_from_state,
        stack_configs,
    )

    db = make_tpch_db(scale=scale)
    streams = tpch_streams(db, n_streams=DEFAULTS["n_streams"], seed=seed)
    ws = tpch_accessed_bytes(db, streams)
    spec = compile_workload(db, streams)
    fracs = list(fracs) if fracs is not None else [0.1, 0.2, 0.3, 0.45]
    cfgs = stack_configs([
        make_config(spec, max(1 << 22, int(f * ws)), DEFAULTS["bandwidth"],
                    policy)
        for f in fracs
    ])
    devs = jax.devices()
    P = int(spec.page_size.shape[0])
    n_lanes = len(fracs)

    shapes = [(1, 1)]
    for k in (2, 4, 8):
        if k <= len(devs) and n_lanes % k == 0:
            shapes.append((k, 1))
    for lanes, pages in ((2, 2), (4, 2)):
        if lanes * pages <= len(devs) and n_lanes % lanes == 0 \
                and P % pages == 0:
            shapes.append((lanes, pages))

    rows = []
    for lanes, pages in shapes:
        n_dev = lanes * pages
        if n_dev == 1:
            mesh, label = None, "vmap"
        elif pages == 1:
            mesh = Mesh(np.array(devs[:n_dev]), ("lanes",))
            label = f"({lanes},) lanes"
        else:
            mesh = Mesh(np.array(devs[:n_dev]).reshape(lanes, pages),
                        ("lanes", "page"))
            label = f"({lanes}, {pages}) lanes x page"
        runner = make_runner(spec, bandwidth_ref=DEFAULTS["bandwidth"],
                             time_slice=0.1 * scale, policies=(policy,),
                             step_pages=2.0, stepper="horizon", mesh=mesh)
        vrun = runner if mesh is not None else jax.jit(jax.vmap(runner))
        t0 = time.time()
        states = jax.block_until_ready(vrun(cfgs))
        cold = time.time() - t0
        t0 = time.time()
        states = jax.block_until_ready(vrun(cfgs))
        warm = time.time() - t0
        traces = runner.trace_count()
        results = [
            result_from_state(jax.tree.map(lambda x, i=i: x[i], states),
                              policy, dt_ref=runner.dt_ref)
            for i in range(n_lanes)
        ]
        rows.append({
            "mesh": label,
            "devices": n_dev,
            "lane_shards": lanes,
            "page_shards": pages,
            "wall_s": round(warm, 3),
            "cold_wall_s": round(cold, 3),
            "trace_count": traces,
            "retraced": traces != 1,
            "macro_steps": [r.extras.get("macro_steps", r.steps)
                            for r in results],
            "avg_stream_time_s": [round(r.avg_stream_time, 3)
                                  for r in results],
        })
        print(f"  tpch scaling [{label:22s} {n_dev} device(s)]: "
              f"warm {warm:6.2f}s cold {cold:6.2f}s traces={traces}",
              flush=True)
    base = rows[0]["wall_s"]
    for r in rows:
        r["speedup_vs_vmap"] = round(base / max(r["wall_s"], 1e-9), 3)
    # stream times must not depend on the mesh shape — the sharded
    # candidate construction is bitwise-identical by design, so any
    # disagreement is a sharding bug, not noise
    for r in rows[1:]:
        if r["avg_stream_time_s"] != rows[0]["avg_stream_time_s"]:
            print(f"  tpch scaling WARNING: {r['mesh']} results diverge "
                  f"from vmap — page/lane sharding is not reduction-safe",
                  flush=True)
            r["diverged"] = True
    from repro.obs import manifest as _m
    return {
        "workload": "tpch",
        "policy": policy,
        "scale": scale,
        "fracs": fracs,
        "stepper": "horizon",
        "rows": rows,
        "manifest": _m.collect(spec=spec, backend="scaling",
                               workload="tpch"),
    }


def batched_tpch_race(scale: float = 1.0, seed: int = 7, fracs=None,
                      policy: str = "pbm"):
    """The batched TPC-H policy x buffer sweep vs the same points run
    sequentially on the event engine — the multi-table analogue of
    ``microbench.batched_buffer_race``, tracked as a CI trend metric.

    Races BOTH time engines: the ``fixed`` row is the PR-4 configuration
    (fixed-dt, one vmapped call on one device — the historical baseline
    the per-stepper ``speedup_ratio`` is measured against), the
    ``horizon`` row is the new default batched lane (event-horizon
    macro-stepping, lane-sharded across every visible device).  Returns
    the summary dict that lands in ``tpch_race.json``; the legacy
    top-level keys mirror the default (horizon) lane.
    """
    import jax

    from repro.core.array_sim import (
        compile_workload, make_config, make_runner, result_from_state,
        stack_configs,
    )

    db = make_tpch_db(scale=scale)
    streams = tpch_streams(db, n_streams=DEFAULTS["n_streams"], seed=seed)
    ws = tpch_accessed_bytes(db, streams)
    time_slice = 0.1 * scale
    spec = compile_workload(db, streams)
    fracs = list(fracs) if fracs is not None else [0.1, 0.2, 0.3, 0.45]
    caps = [max(1 << 22, int(f * ws)) for f in fracs]

    t0 = time.time()
    ev_rows = []
    for cap in caps:
        cfg = EngineConfig(bandwidth=DEFAULTS["bandwidth"], buffer_bytes=cap,
                           sample_interval=5.0, pbm_time_slice=time_slice)
        ev_rows.append(run_workload(db, streams, policy, cfg))
    event_wall = time.time() - t0

    cfgs = stack_configs([
        make_config(spec, cap, DEFAULTS["bandwidth"], policy) for cap in caps
    ])
    steppers: Dict[str, Dict] = {}
    for stepper in ("fixed", "horizon"):
        mesh = lane_mesh(len(fracs)) if stepper == "horizon" else None
        runner = make_runner(spec, bandwidth_ref=DEFAULTS["bandwidth"],
                             time_slice=time_slice, policies=(policy,),
                             step_pages=2.0, stepper=stepper, mesh=mesh)
        vrun = runner if mesh is not None else jax.jit(jax.vmap(runner))
        # compile-separated timing: the cold call pays the trace+compile,
        # the warm call is the measured wall.  trace_count() guards the
        # separation — a second trace on the warm call means the timed
        # number silently includes compilation and the race is invalid.
        t0 = time.time()
        states = jax.block_until_ready(vrun(cfgs))
        cold = time.time() - t0
        t0 = time.time()
        states = jax.block_until_ready(vrun(cfgs))
        wall = time.time() - t0
        traces = runner.trace_count()
        if traces != 1:
            print(f"  tpch batched sweep WARNING: {traces} jit traces "
                  f"for the {stepper} runner — warm wall is "
                  "compile-contaminated, race is invalid", flush=True)
        results = [
            result_from_state(jax.tree.map(lambda x, i=i: x[i], states),
                              policy, dt_ref=runner.dt_ref)
            for i in range(len(fracs))
        ]
        truncated = [f for f, r in zip(fracs, results)
                     if r.extras.get("truncated")]
        if truncated:
            print(f"  tpch batched sweep WARNING: truncated lanes "
                  f"(livelock guard) at buffer fracs {truncated} "
                  f"[{stepper}] — race is invalid", flush=True)
        steppers[stepper] = {
            "wall_s": round(wall, 3),
            "cold_wall_s": round(cold, 3),
            "trace_count": traces,
            "mesh_devices": 1 if mesh is None else mesh.size,
            "speedup_vs_event": round(event_wall / max(wall, 1e-9), 3),
            "avg_stream_time_s": [round(r.avg_stream_time, 3)
                                  for r in results],
            "macro_steps": [r.extras.get("macro_steps", r.steps)
                            for r in results],
            "skipped_time_s": [r.extras.get("skipped_time", 0.0)
                               for r in results],
            "truncated_fracs": truncated,
        }
        print(
            f"  tpch batched sweep [{policy}, {len(fracs)} buffer points, "
            f"{stepper}, {steppers[stepper]['mesh_devices']} device(s)]: "
            f"array = {wall:.2f}s (cold {cold:.2f}s incl. compile) vs "
            f"sequential event engine = {event_wall:.2f}s -> "
            f"{'array WINS' if wall < event_wall else 'event wins'} "
            f"({event_wall / max(wall, 1e-9):.2f}x)",
            flush=True,
        )

    # telemetry pass on the default (horizon) lane: a separate static
    # telemetry=True runner so neither timed lane above carries counters;
    # plain vmap (no mesh) — one extra compile, the numbers not the wall
    # matter here
    from repro.obs import counters as obs_counters
    from repro.obs import manifest as _m
    runner_t = make_runner(spec, bandwidth_ref=DEFAULTS["bandwidth"],
                           time_slice=time_slice, policies=(policy,),
                           step_pages=2.0, stepper="horizon", telemetry=True)
    states_t, tele = jax.block_until_ready(jax.jit(jax.vmap(runner_t))(cfgs))
    tele_rows = []
    for i in range(len(fracs)):
        r_t = result_from_state(
            jax.tree.map(lambda x, i=i: x[i], states_t), policy,
            dt_ref=runner_t.dt_ref)
        tele_rows.append(obs_counters.summarize(
            obs_counters.lane_slice(tele, i),
            policies=runner_t.policy_names, steps=r_t.steps))
    steppers["horizon"]["hit_rate"] = [t["hit_rate"] for t in tele_rows]
    steppers["horizon"]["array_evictions"] = [t["evictions"]
                                              for t in tele_rows]
    steppers["horizon"]["telemetry"] = tele_rows

    fixed, hor = steppers["fixed"], steppers["horizon"]
    ratio = {
        # per-backend/stepper wall-clock ratios vs the sequential event
        # engine, plus the headline tentpole ratio: the new default lane
        # against the PR-4 fixed-dt configuration
        "event": 1.0,
        "array_fixed": fixed["speedup_vs_event"],
        "array_horizon": hor["speedup_vs_event"],
        "horizon_vs_pr4_fixed": round(
            fixed["wall_s"] / max(hor["wall_s"], 1e-9), 3),
    }
    print(f"  tpch race speedup_ratio: {ratio}", flush=True)
    return {
        "workload": "tpch",
        "policy": policy,
        "fracs": list(fracs),
        "steppers": steppers,
        "speedup_ratio": ratio,
        # legacy headline keys = the default batched lane (horizon)
        "array_vmapped_wall_s": hor["wall_s"],
        "array_cold_wall_s": hor["cold_wall_s"],
        "event_sequential_wall_s": round(event_wall, 3),
        "speedup": hor["speedup_vs_event"],
        "truncated_fracs": hor["truncated_fracs"],
        "array_avg_stream_time_s": hor["avg_stream_time_s"],
        "event_avg_stream_time_s": [round(r.avg_stream_time, 3)
                                    for r in ev_rows],
        "macro_steps": hor["macro_steps"],
        "skipped_time_s": hor["skipped_time_s"],
        "hit_rate": hor["hit_rate"],
        "array_evictions": hor["array_evictions"],
        "event_evictions": [r.total_evictions for r in ev_rows],
        "manifest": _m.collect(spec=spec, runner=runner_t,
                               backend="race", workload="tpch"),
    }


def setup_lane_devices(n: Optional[int] = None) -> None:
    """Expose several XLA host devices for lane-sharded CPU execution.

    Must run before JAX initialises (the flag is read once at backend
    creation); a no-op when the flag is already set or when JAX is already
    imported.  The flag shapes only XLA's CPU host platform: on an
    accelerator it is still set but changes nothing there, and the mesh
    spans the accelerator's own devices.

    Deliberately exposes MORE devices than cores (8 by default): one
    lane per device lets short lanes finish and hand their cores to the
    long ones — with one device per core, the slowest lane shares its
    device with another lane for its whole life, which on a 2-core box
    costs ~2x on the race (the OS scheduler beats a static lane
    partition)."""
    import sys

    if "jax" in sys.modules:
        return  # too late — keep whatever the session initialised
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    if n is None:
        n = 8
    if n > 1:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", choices=["buffer", "bandwidth", "streams", "all"],
                    default="all")
    ap.add_argument("--scale", type=float, default=None,
                    help=f"table-size scale (default 1.0; under --smoke: "
                         f"{SMOKE_SCALE} array / {EVENT_SMOKE_SCALE} event)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: quick scale, buffer sweep only (same "
                         "semantics as microbench.py --smoke)")
    ap.add_argument("--backend", choices=["event", "array"], default="event")
    ap.add_argument("--stepper", choices=["fixed", "horizon"],
                    default="horizon",
                    help="array time engine for the sweep rows (the race "
                         "always measures both)")
    ap.add_argument("--mesh", choices=["auto", "off"], default="auto",
                    help="lane-sharded execution: spread batched lanes "
                         "across host devices via shard_map (auto), or "
                         "run the whole batch on one device (off)")
    ap.add_argument("--scaling", action="store_true",
                    help="run the sharding scaling curve (batched buffer "
                         "sweep wall vs mesh shape, incl. page-axis "
                         "meshes) and write "
                         "experiments/results/scaling_curve.json")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.scaling or (args.backend == "array" and args.mesh == "auto"):
        setup_lane_devices()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.scaling:
        scale = args.scale if args.scale is not None else SMOKE_SCALE
        curve = scaling_curve(scale=scale)
        out = args.out or "experiments/results/scaling_curve.json"
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(curve, f, indent=2)
        print(f"  tpch scaling curve -> {out}")
        return
    smoke_scale = SMOKE_SCALE if args.backend == "array" \
        else EVENT_SMOKE_SCALE
    scale = args.scale if args.scale is not None else (
        smoke_scale if args.smoke else 1.0)
    if args.smoke:
        sweeps = ["buffer"]
    else:
        sweeps = (["buffer", "bandwidth", "streams"]
                  if args.sweep == "all" else [args.sweep])
    rows = []
    for s in sweeps:
        if args.backend == "array":
            rows.extend(sweep_array(s, ARRAY_POLICIES, scale=scale,
                                    step_pages=2.0 if args.smoke else 1.0,
                                    stepper=args.stepper,
                                    mesh=args.mesh == "auto"))
        else:
            rows.extend(sweep(s, POLICIES, scale=scale))
    if args.backend == "array":
        race = batched_tpch_race(scale=scale)
        print(f"  tpch batched race speedup: {race['speedup']}x "
              f"(horizon vs PR-4 fixed: "
              f"{race['speedup_ratio']['horizon_vs_pr4_fixed']}x)",
              flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=2)


if __name__ == "__main__":
    main()
