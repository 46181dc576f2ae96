"""End-to-end behaviour tests: the paper's claims at test scale + the
dry-run machinery on a small in-process mesh."""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import EngineConfig, run_workload, simulate_belady
from repro.core.stats import sharing_potential
from repro.core.workload import (
    make_lineitem_db, make_tpch_db,
    micro_accessed_bytes, micro_streams,
    tpch_accessed_bytes, tpch_streams,
)


@pytest.fixture(scope="module")
def micro():
    db = make_lineitem_db(scale_tuples=6_000_000, page_bytes=16 << 10)
    return db, micro_accessed_bytes(db)


def test_claim_c1_pbm_close_to_cscan_beats_lru(micro):
    """Paper C1: PBM ~= CScans, both >> LRU (medium buffer)."""
    db, ws = micro
    streams = micro_streams(db, n_streams=8, queries_per_stream=8, seed=3)
    res = {}
    for pol in ("lru", "pbm", "cscan"):
        cfg = EngineConfig(bandwidth=700e6, buffer_bytes=int(0.4 * ws),
                           pbm_time_slice=0.01)
        res[pol] = run_workload(db, streams, pol, cfg)
    assert res["pbm"].total_io_bytes < 0.8 * res["lru"].total_io_bytes
    assert res["cscan"].total_io_bytes < 0.8 * res["lru"].total_io_bytes


def test_claim_c4_io_volume_constant_vs_bandwidth(micro):
    """Paper C4: total I/O volume ~constant across bandwidths."""
    db, ws = micro
    streams = micro_streams(db, n_streams=4, queries_per_stream=6, seed=5)
    vols = []
    for bw in (300e6, 700e6, 1500e6):
        cfg = EngineConfig(bandwidth=bw, buffer_bytes=int(0.4 * ws))
        vols.append(run_workload(db, streams, "pbm", cfg).total_io_bytes)
    lo, hi = min(vols), max(vols)
    assert hi <= 1.3 * lo, vols


def test_claim_c6_sharing_micro_exceeds_tpch():
    """Paper C6/Figs 17-18: microbenchmark has more sharing potential.

    At test scale the contrast needs the paper's own operating point for
    Fig 17 — long scans (50-100%) over one table; full-scale numbers live in
    the benchmark suite / EXPERIMENTS.md."""
    db_m = make_lineitem_db(scale_tuples=6_000_000, page_bytes=16 << 10)
    ws_m = micro_accessed_bytes(db_m)
    s_m = micro_streams(db_m, n_streams=8, queries_per_stream=4, seed=3,
                        fraction=1.0)
    r_m = run_workload(db_m, s_m, "pbm", EngineConfig(
        bandwidth=700e6, buffer_bytes=int(0.4 * ws_m), sample_interval=0.2))
    db_t = make_tpch_db(scale=0.03, page_bytes=16 << 10)
    s_t = tpch_streams(db_t, n_streams=8, seed=7)
    ws_t = tpch_accessed_bytes(db_t, s_t)
    r_t = run_workload(db_t, s_t, "pbm", EngineConfig(
        bandwidth=600e6, buffer_bytes=int(0.3 * ws_t), sample_interval=0.2))
    assert (sharing_potential(r_m).reusable_fraction
            > sharing_potential(r_t).reusable_fraction)


def test_belady_on_trace_bounds_inorder_policies(micro):
    """OPT replay (paper methodology) never exceeds PBM's miss volume."""
    db, ws = micro
    streams = micro_streams(db, n_streams=4, queries_per_stream=4, seed=8)
    cfg = EngineConfig(bandwidth=700e6, buffer_bytes=int(0.3 * ws),
                       record_trace=True)
    r = run_workload(db, streams, "pbm", cfg)
    _, opt_bytes = simulate_belady(
        r.trace, page_sizes=r.page_sizes, capacity_bytes=int(0.3 * ws)
    )
    assert opt_bytes <= r.total_io_bytes


# ------------------------------------------------- dry-run on a tiny mesh --

@pytest.mark.parametrize("arch", ["qwen2_1_5b", "granite_moe_1b_a400m",
                                  "zamba2_2_7b", "xlstm_350m"])
def test_smoke_dryrun_lowering_small_mesh(arch):
    """lower+compile the real step pipeline on a 1x1 in-process mesh using
    the SMOKE config (the 512-device run is launch/dryrun.py)."""
    from jax.sharding import NamedSharding
    from repro.configs import SHAPES, get_config
    from repro.launch.inputs import cell_shardings, input_specs
    from repro.models import abstract_params, build_model
    from repro.train.optimizer import abstract_opt_state, opt_state_shardings
    from repro.train.train_step import make_train_step
    from repro.train.optimizer import OptimizerConfig
    import dataclasses as dc

    cfg = get_config(arch, smoke=True)
    shape = dc.replace(SHAPES["train_4k"], seq_len=64, global_batch=4)
    model = build_model(cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params_abs = abstract_params(model.param_specs, jnp.float32)
    p_specs, b_specs, _ = cell_shardings(cfg, shape, model, mesh)
    opt_abs = abstract_opt_state(params_abs)
    o_specs = opt_state_shardings(p_specs)
    batch_abs = input_specs(cfg, shape)
    named = lambda specs: jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    step = make_train_step(model, OptimizerConfig())
    with mesh:
        lowered = jax.jit(
            step,
            in_shardings=(named(p_specs), named(o_specs), named(b_specs)),
        ).lower(params_abs, opt_abs, batch_abs)
        compiled = lowered.compile()
    assert compiled.cost_analysis().get("flops", 0) > 0


def test_dryrun_artifacts_exist_and_pass():
    """The committed 512-device dry-run results: every cell ok or documented
    skip, both meshes."""
    import glob, os

    d = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")
    files = glob.glob(os.path.join(d, "*.json"))
    if not files:
        pytest.skip("dry-run artifacts not generated yet")
    by_mesh = {"pod": [], "multipod": []}
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        by_mesh[rec["mesh"]].append(rec)
    for mesh, recs in by_mesh.items():
        assert len(recs) == 40, (mesh, len(recs))
        bad = [r for r in recs if r["status"] not in ("ok", "skipped")]
        assert not bad, [(r["arch"], r["shape"]) for r in bad]
