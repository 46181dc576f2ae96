"""A tiny job of each one-chip cell, and of the tests' multi-table cell,
through the harness on the CPU (Pallas kernels in interpret mode), the
comparison's numbers, and the command's refusal of a CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import cells, check
from benchtiny import MULTI_TABLE, ONE_CHIP, find_cell, run


@pytest.mark.parametrize("name", ONE_CHIP + [MULTI_TABLE["name"]])
def test_tiny_job_runs_correct(name, tiny, cpu):
    cell = find_cell(name, tiny)
    res = run(cell, cpu)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"sim_s_per_s", "setup_s"}
    assert res["metrics"]["sim_s_per_s"]["value"] > 0
    assert list(res)[-1] == "compared"
    assert all(res["compared"][k]["value"] == 0 for k in check.LIMITS)
    assert res["compared"]["runner_traces"]["value"] == 1.0
    json.dumps(res)


def test_the_multi_table_cell_changes_table_inside_a_stream(tiny):
    """The tests' cell works what the micro cells do not: a global page
    axis over three tables, and streams whose scans change table."""
    from bench import workload
    from repro.core.array_sim import compile_workload

    cell = find_cell(MULTI_TABLE["name"], tiny)
    wl = workload.build(cell.config, cell.traffic, 5)
    assert {s.table for st in wl.streams for s in st} == set(
        cell.config["tables"])
    assert all(len({s.table for s in st}) == 3 for st in wl.streams)
    spec = compile_workload(wl.db, wl.streams)
    assert len(set(spec.q_table.ravel().tolist())) == 3


def test_trace_run_reports_the_counted_layers(tiny, cpu):
    """On the CPU there is no TPU plane: the trace readers find nothing
    and leave their metrics out; the exact counts stay."""
    cell = cells.find_cell("micro_sf30.buf10", bench_dir=tiny)
    res = run(cell, cpu, trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"steps_per_sim_s", "lane_occupancy"}
    assert 0 < res["metrics"]["lane_occupancy"]["value"] <= 100


def test_gaps_read_each_kind_of_difference():
    ref = {"t": np.array([1.6, 1.6]), "steps": np.array([10, 12]),
           "consumed": np.array([[100.0, 50.0], [80.0, 0.0]]),
           "qidx": np.array([[1, 0], [0, 0]]),
           "stream_done_t": np.array([[0.5, -1.0], [-1.0, -1.0]]),
           "io_bytes": np.array([1e9, 2e9]), "loads": np.array([10, 20])}
    assert check.gaps(ref, ref) == {"progress_gap": 0.0, "done_gap": 0.0,
                                    "io_gap": 0.0, "clock_gap": 0.0}
    other = {k: v.copy() for k, v in ref.items()}
    other["stream_done_t"][0, 1] = 1.2
    other["consumed"][1, 0] = 88.0
    g = check.gaps(other, ref)
    assert g["done_gap"] == pytest.approx(1.6)
    assert g["progress_gap"] == pytest.approx(0.1)
    other["steps"][0] = 3        # a job may take fewer steps to the same state
    assert check.gaps(other, ref)["io_gap"] == 0.0


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "micro_sf30.buf10",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr
