"""The lane-mesh path (a traffic mix of 24 lanes as 4 lane shards, the
paper's buffer sweep, written for the test) on four host devices, for
the micro configuration and the tests' multi-table one: a tiny job under
the mesh is correct, and one with the lanes of the other chips never
gathered is not.  Host devices must exist before JAX starts, so the run
is a child process on the CPU."""

import json
import os
import subprocess
import sys

from bench import cells, check
from benchtiny import MULTI_TABLE, tiny_bench_dir

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[1] + "/tests/bench"]
import jax, jax.numpy as jnp
from bench import cells, job
from benchtiny import run

bench = cells.load_benchmark()
bench["workloads"].append({"name": "sweep24.mesh4", "config": sys.argv[3],
                           "traffic": "sweep24.mesh4",
                           "chips": 4, "why": "lane mesh"})
cell = cells.find_cell("sweep24.mesh4", bench, sys.argv[2])
devs = jax.devices("cpu")
assert len(devs) == 4, devs
out = {"sound": run(cell, devs)}

orig = job.build_program
def build(*a, **k):
    prog = orig(*a, **k)
    call = prog.call
    def lost(st):
        # the lanes of chips 1-3 never reach the gathered result
        keep = prog.lanes_per_shard
        return jax.tree.map(
            lambda x: jnp.concatenate([x[:keep], jnp.zeros_like(x[keep:])]), st)
    prog.call = lambda: lost(jax.block_until_ready(call()))
    return prog
job.build_program = build
out["no_exchange"] = run(cell, devs)
print(json.dumps({k: {"correct": v["correct"], "failed": v["failed"],
                      "attempted": v["attempted"], "count": v["device"]["count"],
                      "compared": v["compared"]}
                  for k, v in out.items()}))
"""


def _mesh_jobs(tmp_path, config):
    """The sound and the ungathered job of ``config`` under the mesh."""
    tiny = tiny_bench_dir(tmp_path)
    tr = json.loads((tmp_path / "traffic" / "buf10.json").read_text())
    tr.update(name="sweep24.mesh4", lane_shards=4,
              pool_fractions=[0.1, 0.2, 0.4, 0.6, 0.8, 1.0])
    (tmp_path / "traffic" / "sweep24.mesh4.json").write_text(json.dumps(tr))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    out = subprocess.run([sys.executable, "-c", CHILD, cells.ROOT, tiny,
                          config],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _breached(run):
    return {k: c["value"] for k, c in run["compared"].items()
            if c["value"] > c["limit"]}


def test_mesh_cell_tiny_job_and_lost_exchange(tmp_path):
    res = _mesh_jobs(tmp_path, "micro_lineitem_sf30")
    sound = {k: v for k, v in res["sound"].items() if k != "compared"}
    assert sound == {"correct": True, "failed": 0, "attempted":
                     res["sound"]["attempted"], "count": 4}
    assert res["sound"]["attempted"] % 24 == 0
    assert res["no_exchange"]["correct"] is False
    assert res["no_exchange"]["failed"] >= 18


def test_multi_table_mesh_cell_tiny_job_and_lost_exchange(tmp_path):
    """The mesh gathers every lane: the sound job matches the regression
    copy (one device, vmapped) exactly, and ungathered shards fail.  The
    sound job breaches one guarantee, which is the program's: its CScan
    lane at pool 1.0 finishes a scan over a page it never loaded (the
    only page of ``orders.o_orderdate``, owned by the first chunk of
    ``orders``, outside the scan's range), so ``unloaded_pages`` reads 1.
    The copy shares the rule; PERF.md §7 item 1 has the finding.  Once
    the program loads such a page, the sound job is to be ``correct``."""
    res = _mesh_jobs(tmp_path, MULTI_TABLE["config"])
    sound = res["sound"]
    assert sound["count"] == 4 and sound["attempted"] % 24 == 0
    assert all(sound["compared"][k]["value"] == 0 for k in check.LIMITS)
    assert sound["compared"]["runner_traces"]["value"] == 1.0
    assert _breached(sound) == {"unloaded_pages": 1.0}
    assert sound["correct"] is False and sound["failed"] == 1
    assert res["no_exchange"]["correct"] is False
    assert res["no_exchange"]["failed"] >= 18
