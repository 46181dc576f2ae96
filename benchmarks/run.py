"""Benchmark driver: one section per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (spec format): for the policy
benchmarks us_per_call is the simulated avg stream time in microseconds and
``derived`` is total I/O GB; for roofline rows us_per_call is the binding
roofline term per step and derived the roofline fraction.

  PYTHONPATH=src:. python -m benchmarks.run            # quick (scaled) pass
  PYTHONPATH=src:. python -m benchmarks.run --full     # paper-scale sweeps
  PYTHONPATH=src:. python -m benchmarks.run --backend=array   # array-native
  PYTHONPATH=src:. python -m benchmarks.run --smoke    # CI smoke (tiny scale)

``--backend=array`` runs the microbenchmark AND the compiled TPC-H
multi-table sweeps on the vmap-able array substrate
(``repro.core.array_sim``) for EVERY registered array policy — the
paper's full four-way comparison (lru / cscan / pbm / opt), policy lists
derived from ``repro.core.policy_registry`` — with the same CSV/JSON row
schema, and measures batched (vmapped) buffer sweeps against sequential
event-engine runs of the same points (micro + TPC-H races).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_here = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(_here, "..", "src"))
sys.path.insert(0, os.path.join(_here, ".."))

RESULTS_DIR = os.path.join(_here, "..", "experiments", "results")


def _csv(name: str, us: float, derived) -> None:
    print(f"{name},{us:.1f},{derived}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (minutes); default is a scaled "
                         "quick pass")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: quick scale, buffer sweep only — every "
                         "buffer point runs on both backends (the array "
                         "step's plan-trigger semantics need no envelope "
                         "skips)")
    ap.add_argument("--backend", choices=["event", "array"], default="event",
                    help="microbenchmark backend: dict/heapq event engine "
                         "or the vmap-able array substrate")
    ap.add_argument("--stepper", choices=["fixed", "horizon"],
                    default="horizon",
                    help="array time engine for the sweep rows (the races "
                         "measure both; horizon is the default lane)")
    ap.add_argument("--mesh", choices=["auto", "off"], default="auto",
                    help="lane-sharded execution for array sweeps/races: "
                         "expose up to 8 XLA host devices and shard_map "
                         "batched lanes across them (auto), or keep the "
                         "pre-PR-5 one-device batch (off)")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()
    os.makedirs(RESULTS_DIR, exist_ok=True)
    scale = 1.0 if args.full else 0.25
    sweeps = ("buffer",) if args.smoke else ("buffer", "bandwidth", "streams")
    if args.backend == "array" and args.mesh == "auto":
        # lane-sharded execution: expose up to 8 XLA host devices BEFORE
        # jax initialises, so batched sweeps/races can spread lanes
        # across them via shard_map (more devices than cores on small
        # boxes — short lanes free their cores to the long ones)
        from benchmarks.tpch import setup_lane_devices
        setup_lane_devices()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import microbench, tpch, sharing, serving_bench, data_bench

    # run-level provenance: one manifest for the whole artifact directory
    # (each row also carries its own — this one records the driver flags)
    from repro.obs import manifest as run_manifest
    with open(os.path.join(RESULTS_DIR, "run_manifest.json"), "w") as f:
        json.dump(run_manifest.collect(
            backend=args.backend, stepper=args.stepper, scale=scale,
            smoke=args.smoke, sweeps=list(sweeps)), f, indent=2)

    print("# === microbenchmark (paper Figs 11-13) ===", file=sys.stderr)
    rows = []
    if args.backend == "array":
        print("# backend=array: all four paper policies "
              f"({', '.join(microbench.ARRAY_POLICIES)}) on "
              "repro.core.array_sim", file=sys.stderr)
        for s in sweeps:
            rows.extend(microbench.sweep_array(
                s, microbench.ARRAY_POLICIES, scale=scale,
                stepper=args.stepper))
    else:
        for s in sweeps:
            rows.extend(microbench.sweep(s, microbench.POLICIES, scale=scale))
    # per-backend filename: CI runs both backends back to back and uploads
    # everything, so neither run may clobber the other's rows
    micro_name = "micro_array.json" if args.backend == "array" else "micro.json"
    with open(os.path.join(RESULTS_DIR, micro_name), "w") as f:
        json.dump(rows, f, indent=2)
    for r in rows:
        _csv(
            f"micro_{r['sweep']}_{r['point']}_{r['policy']}",
            r["avg_stream_time_s"] * 1e6,
            r["io_gb"],
        )
    if args.backend == "array":
        print("# === batched (vmapped) sweep vs sequential event engine ===",
              file=sys.stderr)
        race = microbench.batched_buffer_race(scale=scale)
        with open(os.path.join(RESULTS_DIR, "batched_race.json"), "w") as f:
            json.dump(race, f, indent=2)
        _csv("micro_batched_sweep_pbm",
             race["array_vmapped_wall_s"] * 1e6, race["speedup"])

    print("# === TPC-H throughput (paper Figs 14-16) ===", file=sys.stderr)
    rows = []
    if args.backend == "array":
        # the compiled multi-table workload on the vmap-able substrate:
        # every (policy x point) lane of a sweep is ONE batched call.
        # TPC-H array rows run at the tpch smoke scale under --smoke (the
        # event engine handles 0.25 in CI; the batched step's CPU cost
        # does not yet) — trend.py compares like against like across runs.
        tpch_scale = tpch.SMOKE_SCALE if args.smoke else scale
        for s in sweeps:
            # --smoke uses the coarse 2-page step (the races' fast mode):
            # the four-policy 24-lane vmapped sweep stays in the CI budget
            rows.extend(tpch.sweep_array(
                s, tpch.ARRAY_POLICIES, scale=tpch_scale,
                step_pages=2.0 if args.smoke else 1.0,
                stepper=args.stepper, mesh=args.mesh == "auto"))
        tpch_name = "tpch_array.json"
    else:
        for s in sweeps:
            rows.extend(tpch.sweep(s, tpch.POLICIES, scale=scale))
        tpch_name = "tpch.json"
    with open(os.path.join(RESULTS_DIR, tpch_name), "w") as f:
        json.dump(rows, f, indent=2)
    for r in rows:
        _csv(
            f"{r['sweep']}_{r['point']}_{r['policy']}",
            r["avg_stream_time_s"] * 1e6,
            r["io_gb"],
        )
    if args.backend == "array":
        print("# === TPC-H batched (vmapped) sweep vs event engine ===",
              file=sys.stderr)
        race = tpch.batched_tpch_race(scale=tpch_scale)
        with open(os.path.join(RESULTS_DIR, "tpch_race.json"), "w") as f:
            json.dump(race, f, indent=2)
        _csv("tpch_batched_sweep_pbm",
             race["array_vmapped_wall_s"] * 1e6, race["speedup"])

    print("# === sharing potential (paper Figs 17-18) ===", file=sys.stderr)
    srows = [sharing.analyse("micro", scale), sharing.analyse("tpch", scale)]
    with open(os.path.join(RESULTS_DIR, "sharing.json"), "w") as f:
        json.dump(srows, f, indent=2)
    for r in srows:
        _csv(f"sharing_{r['workload']}", 0.0, r["reusable_fraction"])

    print("# === serving KV-tier policies (registry) ===", file=sys.stderr)
    # concurrent-load harness, policy list from the registry's serving
    # capability; --smoke keeps the pool_pages axis only (the CI lane)
    vrows = serving_bench.sweep(smoke=args.smoke)
    with open(os.path.join(RESULTS_DIR, "serving_bench.json"), "w") as f:
        json.dump(vrows, f, indent=2)
    for r in vrows:
        _csv(f"serve_{r['sweep']}_{r['point']}_{r['policy']}",
             r["p95_token_gap"] * 1e6, r["swap_gb"])

    print("# === data-pipeline cache (framework) ===", file=sys.stderr)
    drows = [data_bench.run_policy(p) for p in ("lru", "pbm", "opt")]
    with open(os.path.join(RESULTS_DIR, "data.json"), "w") as f:
        json.dump(drows, f, indent=2)
    for r in drows:
        _csv(f"datacache_{r['policy']}", r["miss_pages"] * 1e6, r["hit_rate"])

    if not args.skip_roofline:
        print("# === roofline (from dry-run artifacts) ===", file=sys.stderr)
        from benchmarks import roofline

        rrows = roofline.run()
        with open(os.path.join(RESULTS_DIR, "roofline.json"), "w") as f:
            json.dump(rrows, f, indent=2)
        for r in rrows:
            if r.get("dominant") == "SKIPPED":
                continue
            _csv(
                f"roofline_{r['arch']}_{r['shape']}",
                r["bound_s"] * 1e6,
                f"{r['roofline_frac']:.4f}",
            )


if __name__ == "__main__":
    main()
