"""Helpers of the benchmark's tests: the cells at a size the CPU runs in
seconds."""

import json
import os

from bench import cells

ONE_CHIP = ["micro_sf30.buf40", "micro_sf30.buf10"]
SEED = 2**31 + 7


def tiny_bench_dir(root):
    """The benchmark's configs and traffic at 1% of the tuples, to 0.3 s."""
    for sub in ("configs", "traffic"):
        (root / sub).mkdir()
        for name in os.listdir(os.path.join(cells.BENCH_DIR, sub)):
            with open(os.path.join(cells.BENCH_DIR, sub, name)) as f:
                d = json.load(f)
            if sub == "configs":
                d["scale"] = 0.01
            else:
                d["horizon_s"] = 0.3
                if "queries_per_stream" in d:
                    d["queries_per_stream"] = 4
            (root / sub / name).write_text(json.dumps(d))
    (root / "layer_metrics").symlink_to(
        os.path.join(cells.BENCH_DIR, "layer_metrics"))
    return str(root)


def run(cell, devices, trace=False):
    from bench.run import run_cell

    return run_cell(cell, SEED, 0.5, trace, devices, backend="interpret")
