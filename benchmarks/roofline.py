"""Roofline analysis per (arch x shape) on the single-pod mesh.

Reads the dry-run artifacts (JSON + gzipped optimized HLO) and derives the
three roofline terms per chip per step:

  compute    = HLO_FLOPs  / peak FLOP/s
  memory     = HLO_bytes  / peak HBM bytes/s
  collective = coll_bytes / peak ICI bytes/s per link

with the peaks of the chip the numbers are for (``PEAKS``, keyed by
``jax.Device.device_kind``; the dry-run describes a TPU v5e pod).

HLO_FLOPs/bytes come from benchmarks.hlo_analysis (trip-count aware — XLA's
cost_analysis counts scan bodies once); the compiled module is already
SPMD-partitioned, so all numbers are per-chip.

Also reported: MODEL_FLOPS (6*N*D train / 2*N*D forward; N_active for MoE),
the useful-compute ratio MODEL_FLOPS/HLO_FLOPS (catches remat/redundant
compute), the dominant term, and the roofline fraction

  frac = (MODEL_FLOPS/chips / PEAK) / max(term)

i.e. model-flops utilisation assuming the step runs at the binding term —
the number §Perf hillclimbs.

``--kernels`` switches to the substrate's own Pallas ops
(``fifo_grant`` / ``batched_evict`` / ``wake_solve``): each is lowered at
a representative
queue shape, costed with XLA's compiled ``cost_analysis()``, and executed
once under a ``jax.profiler.TraceAnnotation`` span matching the
``jax.named_scope`` in ``kernels/ops.py`` — so a Perfetto capture of any
run shows the same ``kernel:*`` names this table prices.  It prices
against the peaks of the device it runs on, and fails on a device kind
``PEAKS`` does not list (a CPU among them).

Usage: PYTHONPATH=src:. python -m benchmarks.roofline [--json out.json]
       PYTHONPATH=src:. python -m benchmarks.roofline --kernels --json roofline.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

from .hlo_analysis import analyse_file

#: per-chip peaks by ``jax.Device.device_kind``.  TPU v5e (reported as
#: "TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
#: bf16, 819 GB/s HBM, 1,600 Gbit/s of interconnect over 4 links
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
#: the chip the dry-run's production meshes describe
DRYRUN_KIND = "TPU v5 lite"
DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")

SHAPE_TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32_768 * 32,
    "decode_32k": 128,       # one token per sequence per step
    "long_500k": 1,
}


def model_flops(rec: Dict) -> float:
    n = rec["active_param_count"]
    tokens = SHAPE_TOKENS[rec["shape"]]
    if rec["shape"] == "train_4k":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


def peaks(device_kind: str) -> Dict[str, float]:
    """Roofline peaks of one chip of ``device_kind``; a kind the table
    does not list is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}") from None


def decode_ideal_seconds(rec: Dict, hbm_bw: float) -> Optional[float]:
    """Bandwidth roofline for decode: one step must read the (TP-sharded)
    active params once per chip plus this chip's share of the KV/state
    cache — that HBM traffic, not FLOPs, is the decode roofline."""
    if rec["shape"] not in ("decode_32k", "long_500k"):
        return None
    try:
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
        from repro.configs import SHAPES, get_config
        from repro.models import build_model, tree_paths
        import math as _m

        cfg = get_config(rec["arch"])
        model = build_model(cfg)
        shape = SHAPES[rec["shape"]]
        cache = model.cache_specs(shape.global_batch, shape.seq_len)
        import jax

        cache_bytes = sum(
            _m.prod(l.shape) * l.dtype.itemsize for l in jax.tree.leaves(cache)
        )
        tp = 16
        param_bytes_per_chip = 2.0 * rec["active_param_count"] / tp
        cache_per_chip = cache_bytes / rec["chips"]
        return (param_bytes_per_chip + cache_per_chip) / hbm_bw
    except Exception:
        return None


def analyse_cell(rec: Dict, hlo_path: str) -> Optional[Dict]:
    if rec["status"] != "ok" or not os.path.exists(hlo_path):
        return None
    pk = peaks(DRYRUN_KIND)
    tot = analyse_file(hlo_path)
    chips = rec["chips"]
    compute = tot["flops"] / pk["flops"]
    memory = tot["bytes"] / pk["hbm_bw"]
    coll = tot["collective_total"] / pk["ici_bw"]
    bound = max(compute, memory, coll, 1e-12)
    mf = model_flops(rec)
    ideal = mf / chips / pk["flops"]
    d_ideal = decode_ideal_seconds(rec, pk["hbm_bw"])
    if d_ideal is not None:
        ideal = max(ideal, d_ideal)
    dominant = (
        "compute" if bound == compute else "memory" if bound == memory
        else "collective"
    )
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "chips": chips,
        "flops_per_chip": tot["flops"],
        "bytes_per_chip": tot["bytes"],
        "coll_bytes_per_chip": tot["collective_total"],
        "coll_by_kind": tot["collective_bytes"],
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": coll,
        "bound_s": bound,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / chips / max(tot["flops"], 1.0),
        "roofline_frac": ideal / bound,
    }


def kernel_rows(n_pages: int = 4096) -> List[Dict]:
    """Roofline rows for the substrate's own ops at a representative
    shape (``n_pages`` ~ the batched sim's page-table width)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    dev = jax.devices()[0]
    pk = peaks(dev.device_kind)
    key_f = (jnp.arange(n_pages, dtype=jnp.float32) * 37.0) % 1009.0
    key_i = (jnp.arange(n_pages, dtype=jnp.int32) * 37) % 1009
    sizes = jnp.full((n_pages,), 512.0 * 1024.0, jnp.float32)
    evictable = (jnp.arange(n_pages) % 3) != 0
    cases = [
        ("fifo_grant", ops.fifo_grant,
         (key_i, sizes, jnp.float32(64 << 20), jnp.int32(16))),
        ("batched_evict", ops.batched_evict,
         (key_f, sizes, evictable, jnp.float32(32 << 20))),
        ("wake_solve", ops.wake_solve,
         (key_i, sizes, jnp.float32(4 << 20), jnp.float32(1 << 20),
          jnp.int32(6))),
    ]
    rows = []
    for name, fn, fnargs in cases:
        jfn = jax.jit(fn)
        compiled = jfn.lower(*fnargs).compile()
        c = compiled.cost_analysis()
        flops = float(c.get("flops", 0.0))
        nbytes = float(c.get("bytes accessed", 0.0))
        compute = flops / pk["flops"]
        memory = nbytes / pk["hbm_bw"]
        # exercise the span: the TraceAnnotation nests around the op's own
        # jax.named_scope, so profiler captures carry both labels
        with jax.profiler.TraceAnnotation(f"kernel:{name}"):
            jax.block_until_ready(jfn(*fnargs))
        rows.append({
            "kernel": name,
            "backend": ops.get_backend(),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "n_pages": n_pages,
            "flops": flops,
            "bytes": nbytes,
            "transcendentals": float(c.get("transcendentals", 0.0)),
            "compute_s": compute,
            "memory_s": memory,
            "dominant": "compute" if compute >= memory else "memory",
        })
    return rows


def fmt_kernel_table(rows: List[Dict]) -> str:
    hdr = (f"{'kernel':16s} {'P':>6s} {'flops':>12s} {'bytes':>12s} "
           f"{'comp_us':>9s} {'mem_us':>9s} {'bound':>8s}")
    out = [hdr, "-" * len(hdr)]
    for r in rows:
        out.append(
            f"{r['kernel']:16s} {r['n_pages']:6d} {r['flops']:12.3e} "
            f"{r['bytes']:12.3e} {r['compute_s']*1e6:9.3f} "
            f"{r['memory_s']*1e6:9.3f} {r['dominant']:>8s}"
        )
    return "\n".join(out)


def run(dryrun_dir: str = DRYRUN_DIR) -> List[Dict]:
    rows = []
    for jf in sorted(glob.glob(os.path.join(dryrun_dir, "*__pod.json"))):
        with open(jf) as fh:
            rec = json.load(fh)
        if rec["status"] == "skipped":
            rows.append({
                "arch": rec["arch"], "shape": rec["shape"],
                "dominant": "SKIPPED", "note": rec["reason"],
            })
            continue
        hlo = jf.replace(".json", ".hlo.gz")
        r = analyse_cell(rec, hlo)
        if r:
            rows.append(r)
    return rows


def fmt_table(rows: List[Dict]) -> str:
    hdr = (f"{'arch':26s} {'shape':12s} {'comp_ms':>9s} {'mem_ms':>9s} "
           f"{'coll_ms':>9s} {'bound':>10s} {'useful':>7s} {'RLfrac':>7s}")
    out = [hdr, "-" * len(hdr)]
    for r in rows:
        if r.get("dominant") == "SKIPPED":
            out.append(f"{r['arch']:26s} {r['shape']:12s} {'— skipped: ' + r['note']}")
            continue
        out.append(
            f"{r['arch']:26s} {r['shape']:12s} "
            f"{r['compute_s']*1e3:9.3f} {r['memory_s']*1e3:9.3f} "
            f"{r['collective_s']*1e3:9.3f} {r['dominant']:>10s} "
            f"{r['useful_ratio']:7.3f} {r['roofline_frac']:7.3f}"
        )
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default=DRYRUN_DIR)
    ap.add_argument("--json", default=None)
    ap.add_argument("--kernels", action="store_true",
                    help="cost the substrate's fifo_grant/batched_evict "
                         "ops instead of the dry-run artifacts")
    ap.add_argument("--pages", type=int, default=4096,
                    help="--kernels queue width")
    args = ap.parse_args()
    if args.kernels:
        rows = kernel_rows(args.pages)
        print(fmt_kernel_table(rows))
        payload: object = rows
        try:
            from repro.obs import manifest as _manifest
            payload = {"manifest": _manifest.collect(), "kernels": rows}
        except Exception:
            pass
    else:
        rows = run(args.dryrun_dir)
        print(fmt_table(rows))
        payload = rows
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)


if __name__ == "__main__":
    main()
