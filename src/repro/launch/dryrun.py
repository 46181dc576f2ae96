import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST run before any jax import/init: jax locks the device count on first
#   use.  The dry-run (and only the dry-run) builds 512 placeholder host
#   devices so the production meshes are real Mesh objects.

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

For each cell this builds the *real* jitted step (train_step with AdamW for
train shapes; serve_step against a full-length KV/state cache for decode
shapes; prefill forward for prefill shapes) from abstract inputs only —
no parameter or cache is ever allocated — and records:

* compiled.memory_analysis()  -> bytes/device (proves the cell fits/placement)
* compiled.cost_analysis()    -> HLO FLOPs & bytes for the roofline terms
* collective byte counts parsed from the optimized HLO (all-gather,
  all-reduce, reduce-scatter, all-to-all, collective-permute)

Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>.json (read by
benchmarks/roofline.py and EXPERIMENTS.md).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek_67b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, cell_is_skipped, get_config
from repro.models import abstract_params, build_model
from repro.models.transformer import Model
from repro.launch.mesh import make_production_mesh, mesh_chip_count
from repro.launch.inputs import (
    batch_shardings,
    cache_shardings,
    cell_mode,
    cell_shardings,
    input_specs,
)
from repro.train.optimizer import (
    OptimizerConfig,
    abstract_opt_state,
    opt_state_shardings,
)
from repro.train.train_step import make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# HLO collective ops we account under the "collective" roofline term
_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*(\S+)\s+(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(sig: str) -> int:
    m = _SHAPE_RE.match(sig.strip().lstrip("("))
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op in the HLO, by kind."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        _, sig, kind = m.groups()
        nbytes = 0
        if sig.startswith("("):
            for part in re.findall(r"[a-z0-9]+\[[0-9,]*\]", sig):
                nbytes += _shape_bytes(part)
        else:
            nbytes = _shape_bytes(sig)
        out[kind] = out.get(kind, 0) + nbytes
    return out


def _scan_trip_counts(hlo_text: str):
    """Best-effort: while-loop trip counts so per-iteration collectives can
    be scaled to full-step volumes (XLA reports the loop body once)."""
    counts = []
    for m in re.finditer(r"trip_count=(\d+)", hlo_text):
        counts.append(int(m.group(1)))
    return counts


def probe_configs(cfg):
    """Two reduced-depth clones of ``cfg`` (same family constraints).

    XLA's cost_analysis counts a scan body ONCE regardless of trip count, so
    per-step FLOPs/bytes/collectives are recovered by compiling the same cell
    at depths L1 < L2 and extrapolating linearly to the real depth
    (benchmarks/roofline.py does the fit).
    """
    import dataclasses as dc

    if cfg.local_global_ratio > 0:
        base = cfg.local_global_ratio + 1
    elif cfg.family == "hybrid":
        base = max(1, cfg.attn_every)
    elif cfg.family == "ssm":
        base = max(2, cfg.xlstm_slstm_every)
    else:
        base = 2
    out = []
    for L in (base, 2 * base):
        kw = {"n_layers": L}
        if cfg.is_encdec:
            kw["encoder_layers"] = L
        out.append((dc.replace(cfg, **kw), L))
    return out


def _lower_cell(cfg, shape, mesh):
    """Build + lower the jitted step for one cell. Returns lowered."""
    from repro.configs.base import mesh_rules
    from repro.models import shardctx

    mode = cell_mode(cfg, shape)
    rules = mesh_rules(mode, mesh.axis_names)
    shardctx.set_batch_axes(rules["batch"])
    model = build_model(cfg)
    params_abs = abstract_params(model.param_specs, jnp.bfloat16)
    batch_abs = input_specs(cfg, shape)

    def named(tree, specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    if shape.kind == "train":
        p_specs, b_specs, _ = cell_shardings(cfg, shape, model, mesh)
        opt_abs = abstract_opt_state(params_abs)
        o_specs = opt_state_shardings(p_specs)
        step = make_train_step(model, OptimizerConfig())
        jitted = jax.jit(
            step,
            in_shardings=(named(params_abs, p_specs), named(opt_abs, o_specs),
                          named(batch_abs, b_specs)),
            donate_argnums=(0, 1),
        )
        with mesh:
            return jitted.lower(params_abs, opt_abs, batch_abs)
    if shape.kind == "prefill":
        p_specs, b_specs, _ = cell_shardings(cfg, shape, model, mesh)
        jitted = jax.jit(
            model.prefill_logits,
            in_shardings=(named(params_abs, p_specs), named(batch_abs, b_specs)),
        )
        with mesh:
            return jitted.lower(params_abs, batch_abs)
    cache_abs = model.cache_specs(shape.global_batch, shape.seq_len)
    p_specs, b_specs, c_specs = cell_shardings(
        cfg, shape, model, mesh, cache_tree=cache_abs
    )
    jitted = jax.jit(
        model.serve_step,
        in_shardings=(named(params_abs, p_specs), named(cache_abs, c_specs),
                      named(batch_abs, b_specs)),
        donate_argnums=(1,),
    )
    with mesh:
        return jitted.lower(params_abs, cache_abs, batch_abs)


def _analyse(compiled) -> Dict[str, Any]:
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    return {
        "flops": cost.get("flops", 0.0),
        "hbm_bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": collective_bytes(hlo),
        "scan_trip_counts": _scan_trip_counts(hlo)[:16],
    }


def build_cell(
    arch: str, shape_name: str, mesh, probes: bool = False
) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_is_skipped(cfg, shape)
    if skip:
        return {"status": "skipped", "reason": skip}
    mode = cell_mode(cfg, shape)
    t0 = time.time()
    lowered = _lower_cell(cfg, shape, mesh)
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    result = {
        "status": "ok",
        "mode": mode,
        "chips": mesh_chip_count(mesh),
        "n_layers": cfg.n_layers,
        "compile_s": round(compile_s, 2),
        **_analyse(compiled),
        "memory": {
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    if probes:
        result["probes"] = []
        for pcfg, L in probe_configs(cfg):
            pc = _lower_cell(pcfg, shape, mesh).compile()
            result["probes"].append({"n_layers": L, **_analyse(pc)})
    return result, compiled


def save_hlo(compiled, path: str) -> None:
    """Persist the optimized HLO (gzip) for trip-count-aware accounting
    (benchmarks/hlo_analysis.py): cost_analysis counts while bodies ONCE,
    so the roofline reads the HLO itself."""
    import gzip

    with gzip.open(path, "wt") as f:
        f.write(compiled.as_text())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--probes", action="store_true",
                    help="extra reduced-depth compiles (legacy extrapolation)")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
        for arch in archs:
            for shape_name in shapes:
                tag = f"{arch}__{shape_name}__{mesh_name}"
                path = os.path.join(args.out, tag + ".json")
                hlo_path = os.path.join(args.out, tag + ".hlo.gz")
                if (
                    os.path.exists(path)
                    and not args.force
                    and (mesh_name != "pod" or os.path.exists(hlo_path))
                ):
                    print(f"[{tag}] cached")
                    continue
                try:
                    res = build_cell(arch, shape_name, mesh, probes=args.probes)
                    if isinstance(res, tuple):
                        res, compiled = res
                        if mesh_name == "pod":  # roofline is single-pod only
                            save_hlo(compiled, hlo_path)
                except Exception as e:  # noqa: BLE001 — record and continue
                    res = {
                        "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-4000:],
                    }
                    failures += 1
                res["arch"] = arch
                res["shape"] = shape_name
                res["mesh"] = mesh_name
                with open(path, "w") as f:
                    json.dump(res, f, indent=2)
                print(
                    f"[{tag}] {res['status']}"
                    + (f" compile={res.get('compile_s')}s flops={res.get('flops'):.3e}"
                       if res["status"] == "ok" else
                       (" " + res.get("reason", res.get("error", ""))[:120]))
                )
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
