"""Helpers of the benchmark's tests: the cells at a size the CPU runs in
seconds, and a multi-table cell of the tests' own."""

import json
import os
import shutil

from bench import cells

#: the one-chip cells of ``BENCHMARK.json``: each gets a tiny job
ONE_CHIP = [w["name"] for w in cells.load_benchmark()["workloads"]
            if w["chips"] == 1]
SEED = 2**31 + 7
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: a cell that only the tests run: three tables under four query
#: templates (``data/three_tables.json``, already at a tiny size) at the
#: tiny ``buf10`` point
MULTI_TABLE = {"name": "three_tables.buf10", "config": "three_tables",
               "traffic": "buf10", "chips": 1,
               "why": "the query_templates generator: streams that change "
                      "table, chunks shared across tables"}


def tiny_bench_dir(root):
    """The benchmark's configs and traffic at 1% of the tuples, to 0.3 s,
    and the tests' multi-table configuration."""
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
    shutil.copy(os.path.join(DATA, "three_tables.json"), root / "configs")
    (root / "layer_metrics").symlink_to(
        os.path.join(cells.BENCH_DIR, "layer_metrics"))
    return str(root)


def find_cell(name, bench_dir):
    """A cell of ``BENCHMARK.json`` or :data:`MULTI_TABLE`, from the
    files under ``bench_dir``."""
    bench = cells.load_benchmark()
    bench["workloads"].append(MULTI_TABLE)
    return cells.find_cell(name, bench, bench_dir)


def run(cell, devices, trace=False):
    from bench.run import run_cell

    return run_cell(cell, SEED, 0.5, trace, devices, backend="interpret")
