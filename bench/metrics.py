"""The metrics of a run: the end-to-end metrics from the host's clock,
and the per-layer metrics from the readers in ``bench/layer_metrics``."""

from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from .cells import BENCH_DIR


@dataclass
class Context:
    """What a per-layer reader may read."""

    jobs: List[Dict[str, np.ndarray]]     # every job of the window
    traced_job: Optional[Dict[str, np.ndarray]]
    lanes_per_shard: int
    shards: int
    pages: int
    trace: object                         # bench.trace.Reduced or None
    peak: Dict[str, float]


def sim_s_per_s(jobs: List[Dict[str, np.ndarray]], window_s: float) -> float:
    """Simulated lane-seconds completed in the window over its wall
    seconds: every lane of every job, each counted to its final clock."""
    return float(sum(float(np.sum(j["t"], dtype=np.float64)) for j in jobs)) \
        / window_s


def reader(name: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, "layer_metrics", f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: "
                                f"{path}")
    spec = importlib.util.spec_from_file_location(
        f"bench.layer_metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(metrics: List[dict], ctx: Context,
              bench_dir: str = BENCH_DIR) -> Dict[str, dict]:
    """Each per-layer metric the readers find something for."""
    out = {}
    for m in metrics:
        value = reader(m["name"], bench_dir)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
