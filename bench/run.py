"""Run one cell of the benchmark on the chips of this machine.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's spec from the seed, compiles the program's runner
(from the persistent compile cache at ``<checkout>/.jax_cache`` where
``JAX_COMPILATION_CACHE_DIR`` is not set) and warms it with one call to one
time slice.  The window then runs jobs back to back for ``--seconds``.
With ``--trace 1`` one more job runs under the profiler.  Once the window
has closed and the memory peak is read, the regression copy runs every
lane at the same seed, and each job is compared with it and held to the
configuration's guarantees (:mod:`bench.check`).

The last line of standard output is the result, one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the result's last key.  Exits 2, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from bench import cells  # noqa: E402

sys.path.insert(0, os.path.join(cells.ROOT, "src"))


CACHE_DIR = os.path.join(cells.ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set (JAX reads it itself), else a fixed directory inside the
    checkout, so a later run of the same checkout finds its programs."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def tpu_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's default platform is {devs[0].platform!r}, "
                     "not a TPU; the benchmark does not fall back")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             backend: str = "pallas", t_start: float = None) -> dict:
    """Set-up, window, optional traced job, memory, reference, comparison.
    Returns the result object (without printing it)."""
    import jax

    from bench import check, job, metrics, peaks, workload
    from bench import trace as tracing
    from repro.core.array_sim import compile_workload

    t_start = time.perf_counter() if t_start is None else t_start
    t_build = time.perf_counter()
    wl = workload.build(cell.config, cell.traffic, seed)
    spec = compile_workload(wl.db, wl.streams)
    t_compile = time.perf_counter()
    prog = job.build_program(spec, wl, cell, devices, backend=backend)
    t_warm = time.perf_counter()
    jax.block_until_ready(prog.warm())
    t_end = time.perf_counter()
    setup_s = t_end - t_start
    setup_parts = {"start_s": t_build - t_start,
                   "build_s": t_compile - t_build,
                   "compile_s": t_warm - t_compile, "warm_s": t_end - t_warm}

    jobs, window_s = job.run_window(prog.call, seconds)

    reduced = traced_job = None
    if trace:
        def one_job():
            with jax.profiler.TraceAnnotation("bench:job"):
                with jax.profiler.TraceAnnotation("bench:dispatch"):
                    out = prog.call()
                with jax.profiler.TraceAnnotation("bench:wait"):
                    return job.summary(jax.block_until_ready(out))

        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            traced, traced_s = tracing.capture(one_job, logdir)
            pd = tracing.load(logdir)
            if pd is not None:
                reduced = tracing.reduce_profile(
                    pd, tracing.hlo_op_names(prog.compiled.as_text()),
                    traced_s)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        traced_job = job.to_host(traced)
        jobs.append(traced)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in prog.devices)
    traces = prog.traces()
    host_jobs = [job.to_host(j) for j in jobs]
    lanes_per_shard, shards = prog.lanes_per_shard, len(prog.devices)
    dev0 = prog.devices[0]
    del prog, jobs

    t_ref = time.perf_counter()
    ref = check.reference_summary(cell, seed, dev0)
    ref_s = time.perf_counter() - t_ref
    numbers, limits, failed = check.judge(host_jobs, ref,
                                          check.guarantees(cell, seed))
    numbers["runner_traces"] = float(traces)
    limits["runner_traces"] = 1.0
    correct = check.is_correct(numbers, limits)

    if trace:
        ctx = metrics.Context(
            jobs=host_jobs, traced_job=traced_job,
            lanes_per_shard=lanes_per_shard, shards=shards,
            pages=int(spec.n_pages), trace=reduced,
            peak=None if reduced is None else peaks.peaks(dev0.device_kind))
        values = metrics.per_layer(cell.per_layer, ctx)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {
            "sim_s_per_s": {"value": metrics.sim_s_per_s(host_jobs, window_s),
                            "unit": units["sim_s_per_s"]},
            "setup_s": {"value": setup_s, "unit": units["setup_s"]},
        }
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": shards, "memory_peak_bytes": int(peak)}
    lane_jobs = len(host_jobs) * len(ref["t"])
    result = {
        "correct": bool(correct),
        "attempted": lane_jobs,
        # a runner traced more than once may have compiled in the window
        "failed": failed if traces == 1 else lane_jobs,
        "metrics": values,
        "device": device,
    }
    if trace and reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        top = sorted(reduced.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps[:10]],
        }
    result["jobs"] = len(host_jobs)
    result["window_s"] = window_s
    result["setup_parts"] = setup_parts
    result["reference_s"] = ref_s
    result["lane_steps"] = host_jobs[-1]["steps"].tolist()
    result["compared"] = {k: {"value": v, "limit": limits[k]}
                          for k, v in numbers.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.find_cell(args.workload)
    enable_compile_cache()
    try:
        devices = tpu_devices(cell.chips)
    except NoChip as e:
        print(f"bench.run: {e}", file=sys.stderr, flush=True)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, t_start=T_START)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
