"""Chip smoke run: the batched TPC-H buffer-manager sweep on a TPU.

Drives the array simulator's main path the way
``benchmarks/tpch.py::sweep_array`` does (``compile_workload`` +
``make_runner`` + ``jax.jit(jax.vmap(runner))``) at SF30 tuple counts
(``make_tpch_db(scale=1.0)``: 8,832 pages), 8 streams, seed 7, 600 MB/s,
on the event-horizon stepper, over every array policy x buffer point.

    python chip_smoke.py              # one chip, streams cut to a prefix
    python chip_smoke.py --full       # one chip, the whole streams
    python chip_smoke.py --chips 4    # four chips: meshes vs one-chip vmap

One chip: the sweep runs on the compiled Pallas kernels, cold and warm,
then once more on the plain jnp oracles (``kernels/ref.py``); every lane
must agree within 1% on stream time and I/O.  By default each stream
runs only its first ``SCANS`` scans, so that the run fits a smoke
check's time; ``--full`` runs the whole 53-scan streams (about half an
hour on one v5e), with a single Pallas pass in place of cold and warm.
Four chips: the prefix lanes under a four-chip lane mesh and a (2, 2)
``('lanes', 'page')`` mesh must be bitwise equal to the one-chip vmap on
device 0.

Fails (non-zero exit, no result line) when JAX finds no TPU.  The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_here = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(_here, "src"), _here]

#: the operating point of benchmarks/tpch.py (DEFAULTS) at SF30
SCALE = 1.0
N_STREAMS = 8
SEED = 7
BANDWIDTH = 600e6
TIME_SLICE = 0.1 * SCALE
#: largest relative stream-time / I/O difference allowed between the
#: Pallas lanes and the jnp-oracle lanes
REL_TOL = 0.01
#: the buffer points run, out of benchmarks.tpch SWEEP_POINTS["buffer"]:
#: the smallest pool (the most macro-steps) and the paper's default
BUFFER_POINTS = (0.1, 0.3)
#: without --full, each stream runs the first SCANS of its qgen-ordered
#: scans; the pools keep the full streams' sizes
SCANS = 3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices():
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found: {e}")
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX's default platform is "
             f"{devs[0].platform!r}; this script does not fall back")
    return devs


def build_sweep(buffer_points, scans):
    """The spec, lane labels and stacked configs of the sweep: every array
    policy at each buffer point, each stream cut to its first ``scans``
    scans (``None``: whole).  Pools are sized from the whole streams'
    accessed bytes, as ``benchmarks/tpch.py`` sizes them."""
    from repro.core.array_sim import compile_workload, make_config, \
        stack_configs
    from repro.core.policy_registry import names as policy_names
    from repro.core.workload import make_tpch_db, tpch_accessed_bytes, \
        tpch_streams

    db = make_tpch_db(scale=SCALE)
    streams = tpch_streams(db, n_streams=N_STREAMS, seed=SEED)
    ws = tpch_accessed_bytes(db, streams)
    run_streams = [s[:scans] for s in streams]
    ws_run = tpch_accessed_bytes(db, run_streams)
    spec = compile_workload(db, run_streams)
    log(f"streams: {[len(s) for s in run_streams]} of "
        f"{[len(s) for s in streams]} scans; accessed {ws_run / 1e9:.3f} "
        f"of {ws / 1e9:.3f} GB")
    lanes, cfgs = [], []
    for frac in buffer_points:
        cap = max(1 << 22, int(frac * ws))
        log(f"buffer point {frac}: pool {cap} bytes, "
            f"{cap / ws_run:.4f} of the bytes this run accesses")
        for pol in policy_names(backend="array"):
            lanes.append((frac, pol))
            cfgs.append(make_config(spec, cap, BANDWIDTH, pol))
    return spec, lanes, stack_configs(cfgs)


def compile_runner(spec, cfgs, backend: str, mesh=None):
    """A fresh runner traced under ``backend`` and compiled for ``cfgs``;
    returns it with the configs placed as the program takes them.

    The kernel backend is read while tracing, so it is set before the
    runner is lowered.  The Pallas program must hold the Mosaic kernels
    and the oracle program none."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.array_sim import make_runner
    from repro.kernels import ops

    ops.set_backend(backend)
    runner = make_runner(spec, bandwidth_ref=BANDWIDTH,
                         time_slice=TIME_SLICE, stepper="horizon",
                         mesh=mesh)
    if mesh is None:
        batched = jax.jit(jax.vmap(runner))
    else:
        batched = runner
        cfgs = jax.device_put(cfgs, NamedSharding(
            mesh, PartitionSpec(mesh.axis_names[0])))
    t0 = time.time()
    compiled = batched.lower(cfgs).compile()
    compile_s = time.time() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if has_kernel != (backend == "pallas"):
        fail(f"{backend} runner: tpu_custom_call "
             f"{'present' if has_kernel else 'absent'} in the compiled text")
    return runner, compiled, compile_s, cfgs


def run(compiled, cfgs):
    import jax

    t0 = time.time()
    states = jax.block_until_ready(compiled(cfgs))
    return states, time.time() - t0


def lane_results(states, lanes, runner):
    import jax

    from repro.core.array_sim import result_from_state

    out = []
    for i, (_, pol) in enumerate(lanes):
        out.append(result_from_state(
            jax.tree.map(lambda x, i=i: x[i], states), pol,
            dt_ref=runner.dt_ref))
    return out


def lanes_equal(a, b, n_lanes: int) -> list:
    """Per lane: is every leaf of the final state bitwise equal?"""
    import jax
    import numpy as np

    la = [np.asarray(x) for x in jax.tree.leaves(a)]
    lb = [np.asarray(x) for x in jax.tree.leaves(b)]
    return [all(np.array_equal(x[i], y[i]) for x, y in zip(la, lb))
            for i in range(n_lanes)]


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def check_placement(states, mesh, n_lanes: int, label: str) -> None:
    """Each lane block must sit on the devices of its lane coordinate,
    and the shards together must cover every device of the mesh."""
    import numpy as np

    grid = np.asarray(mesh.devices).reshape(mesh.devices.shape[0], -1)
    per = n_lanes // grid.shape[0]
    shards = states.io_bytes.addressable_shards
    if {s.device for s in shards} != set(grid.flat):
        fail(f"{label}: shards cover {sorted(s.device.id for s in shards)}, "
             f"the mesh holds {sorted(d.id for d in grid.flat)}")
    for s in shards:
        sl = s.index[0]
        start = sl.start or 0
        stop = n_lanes if sl.stop is None else sl.stop
        if stop - start != per or s.device not in set(grid[start // per]):
            fail(f"{label}: lanes {start}:{stop} on device {s.device.id}")
    log(f"{label}: placement ok ({grid.shape[0]} lane shards of {per} "
        f"lanes over devices {[d.id for d in grid.flat]})")


def describe(spec, lanes, scans) -> None:
    from benchmarks.tpch import SWEEP_POINTS

    log(f"sweep: scale {SCALE} (P = {int(spec.page_size.shape[0])} pages), "
        f"{N_STREAMS} streams, seed {SEED}, {BANDWIDTH / 1e6:.0f} MB/s, "
        f"time_slice {TIME_SLICE:g} s, horizon stepper, {len(lanes)} lanes "
        f"= 4 policies x buffer points {list(BUFFER_POINTS)}")
    cut = ("whole streams" if scans is None else
           f"each stream runs its first {scans} scans, not the full sweep "
           f"(--full runs it)")
    log(f"reduced: buffer points {list(BUFFER_POINTS)} of "
        f"{SWEEP_POINTS['buffer']}; {cut}")


def one_chip(dev, scans) -> None:
    spec, lanes, cfgs = build_sweep(BUFFER_POINTS, scans)
    describe(spec, lanes, scans)

    runner, compiled, compile_s, cfgs = compile_runner(spec, cfgs, "pallas")
    log(f"pallas: compiled in {compile_s:.2f} s, tpu_custom_call present")
    states, cold = run(compiled, cfgs)
    log(f"pallas: cold wall {compile_s + cold:.3f} s "
        f"(compile {compile_s:.3f} s + first run {cold:.3f} s)")
    if scans is None:
        log("pallas: warm run skipped on the whole streams")
    else:
        states, warm = run(compiled, cfgs)
        log(f"pallas: warm wall {warm:.3f} s")
    if runner.trace_count() != 1:
        fail(f"pallas runner traced {runner.trace_count()} times")
    res = lane_results(states, lanes, runner)
    for (frac, pol), r in zip(lanes, res):
        log(f"  lane buffer={frac:<4} {pol:5s} "
            f"avg_stream_time_s={r.avg_stream_time:.6f} "
            f"io_gb={r.io_gb:.6f} macro_steps={r.extras['macro_steps']} "
            f"truncated={r.extras['truncated']}")
    cut = [lane for lane, r in zip(lanes, res) if r.extras["truncated"]]
    if cut:
        fail(f"truncated lanes: {cut}")
    stats = dev.memory_stats() or {}
    log(f"pallas: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")

    runner_r, compiled_r, compile_r, _ = compile_runner(
        spec, cfgs, "reference")
    states_r, wall_r = run(compiled_r, cfgs)
    log(f"reference: compiled in {compile_r:.3f} s, wall {wall_r:.3f} s")
    res_r = lane_results(states_r, lanes, runner_r)
    same = lanes_equal(states, states_r, len(lanes))
    d_time = max(rel(a.avg_stream_time, b.avg_stream_time)
                 for a, b in zip(res, res_r))
    d_io = max(rel(a.io_gb, b.io_gb) for a, b in zip(res, res_r))
    log(f"pallas vs reference: {sum(same)}/{len(lanes)} lanes bitwise "
        f"equal; max rel diff avg_stream_time {d_time:.3e}, io {d_io:.3e}")
    for (frac, pol), a, b, eq in zip(lanes, res, res_r, same):
        if not eq:
            log(f"  differs: buffer={frac} {pol}: stream time "
                f"{a.avg_stream_time:.6f} vs {b.avg_stream_time:.6f}, "
                f"io {a.io_gb:.6f} vs {b.io_gb:.6f} GB")
    if d_time > REL_TOL or d_io > REL_TOL:
        fail(f"a Pallas lane differs from the oracle by more than "
             f"{REL_TOL:.0%}")


def four_chips(devs) -> None:
    import numpy as np
    from jax.sharding import Mesh

    if len(devs) < 4:
        fail(f"--chips 4 needs four devices, JAX sees {len(devs)}")
    spec, lanes, cfgs = build_sweep(BUFFER_POINTS, SCANS)
    describe(spec, lanes, SCANS)
    n = len(lanes)
    meshes = [
        ("lane mesh (4,)", Mesh(np.array(devs[:4]), ("lanes",))),
        ("lanes x page mesh (2, 2)",
         Mesh(np.array(devs[:4]).reshape(2, 2), ("lanes", "page"))),
    ]
    _, compiled, compile_s, cfgs_1 = compile_runner(spec, cfgs, "pallas")
    base, wall = run(compiled, cfgs_1)
    log(f"one-chip vmap on device {devs[0].id}: compile {compile_s:.3f} s, "
        f"wall {wall:.3f} s")
    for label, mesh in meshes:
        _, compiled_m, compile_m, cfgs_m = compile_runner(
            spec, cfgs, "pallas", mesh=mesh)
        states, wall_m = run(compiled_m, cfgs_m)
        log(f"{label}: compile {compile_m:.3f} s, wall {wall_m:.3f} s")
        check_placement(states, mesh, n, label)
        same = lanes_equal(states, base, n)
        log(f"{label} vs one-chip vmap: {sum(same)}/{n} lanes bitwise equal")
        if not all(same):
            fail(f"{label} is not bitwise equal to the one-chip vmap")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: Pallas sweep vs the jnp oracles on one chip; "
                         "4: lane and lanes x page meshes vs one-chip vmap")
    ap.add_argument("--full", action="store_true",
                    help="one chip: run the whole streams, not their "
                         f"first {SCANS} scans")
    args = ap.parse_args()
    if args.full and args.chips != 1:
        ap.error("--full runs on one chip only")

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devs = tpu_devices()
    dev = devs[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
        f"count {len(devs)}; compile cache {cache}")
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip(dev, None if args.full else SCANS)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
