"""Multi-table semantics of the array backend (workload compiler + step).

Pins the behaviours the TPC-H throughput figures depend on:

* two tables with different pages-per-column simulate correctly (exact
  cold I/O over the union of both tables' accessed pages);
* a stream whose consecutive queries switch tables agrees with the event
  engine under a constrained pool;
* a batched (policy x buffer) sweep over the compiled TPC-H spec agrees
  with the event engine within the validated TPC-H error bars, lane for
  lane, in ONE batched call: on the program's TPC-H and on the
  benchmark's ``tpch_sf30`` configuration.
"""

import jax
import numpy as np
import pytest

from repro.core import EngineConfig, run_workload
from repro.core.pages import Database
from repro.core.scans import ScanSpec
from repro.core.workload import make_tpch_db, tpch_accessed_bytes, tpch_streams
from repro.core.array_sim import (
    compile_workload,
    make_config,
    make_runner,
    result_from_state,
    run_workload_array,
    stack_configs,
)
from repro.core.array_sim.validate import TPCH_DEFAULTS, TPCH_ERROR_BARS


def _two_table_db(page_bytes=128 << 10):
    db = Database()
    # deliberately different page grids: a.x 16 pages, a.y 4, b.u 10
    db.add_table("a", 1_000_000, {"x": 2.0, "y": 0.5}, page_bytes=page_bytes)
    db.add_table("b", 300_000, {"u": 4.0}, page_bytes=page_bytes)
    return db


# ------------------------------------------------ cold exactness ----------

def test_two_table_cold_scan_io_is_exact():
    """A cold pass over two tables with room for everything must load
    exactly the union of accessed page bytes — per-table offsets cannot
    leak I/O across tables."""
    db = _two_table_db()
    st = [[ScanSpec("a", ("x", "y"), ((0, 1_000_000),), tuple_rate=50e6),
           ScanSpec("b", ("u",), ((0, 300_000),), tuple_rate=50e6)]]
    expected = (db.tables["a"].scan_bytes(("x", "y"), 0, 1_000_000)
                + db.tables["b"].scan_bytes(("u",), 0, 300_000))
    r = run_workload_array(db, st, "lru", capacity_bytes=64 << 20,
                           bandwidth=700e6, time_slice=0.002)
    assert r.total_io_bytes == pytest.approx(expected, rel=1e-6)
    assert not r.extras["truncated"]


# ------------------------------------------ table-switching streams -------

def test_stream_switching_tables_matches_event_engine():
    """Streams that alternate tables between consecutive queries (the
    interleaving the rotated TPC-H permutations produce), under a pool a
    third of the joint working set: array LRU/PBM must stay close to the
    event engine on both paper metrics.  Built on the TPC-H table
    geometry — the fluid step's fidelity was calibrated at realistic page
    grids and rates, not at toy scans a few steps long."""
    import random

    db = make_tpch_db(scale=0.05)
    rng = random.Random(5)

    def q(tname, s):
        t = db.tables[tname]
        cols = tuple(sorted(t.columns)[:4])
        ln = max(1, int(t.n_tuples * 0.5))
        a = rng.randrange(0, max(1, t.n_tuples - ln + 1))
        return ScanSpec(tname, cols, ((a, a + ln),), tuple_rate=80e6,
                        stream=s)

    # stream s alternates lineitem/orders starting in anti-phase with s+1,
    # so consecutive queries ALWAYS switch tables and streams overlap on
    # both tables at staggered times
    streams = [
        [q(("lineitem", "orders")[(i + s) % 2], s) for i in range(8)]
        for s in range(4)
    ]
    seen, ws = set(), 0
    for stream in streams:
        for sp in stream:
            t = db.tables[sp.table]
            for c in sp.columns:
                for p in t.columns[c].pages_for_range(*sp.ranges[0]):
                    if p.pid not in seen:
                        seen.add(p.pid)
                        ws += p.size_bytes
    cap = max(1 << 22, int(0.3 * ws))
    for pol in ("lru", "pbm"):
        cfg = EngineConfig(bandwidth=600e6, buffer_bytes=cap,
                           sample_interval=5.0, pbm_time_slice=0.005)
        ev = run_workload(db, streams, pol, cfg)
        ar = run_workload_array(db, streams, pol, capacity_bytes=cap,
                                bandwidth=600e6, time_slice=0.005)
        assert not ar.extras["truncated"]
        dt = ar.avg_stream_time / ev.avg_stream_time - 1
        dio = ar.io_gb / ev.io_gb - 1
        assert abs(dt) <= 0.15, (pol, dt, dio)
        assert abs(dio) <= 0.15, (pol, dt, dio)


# ----------------------------- vmapped TPC-H sweep vs event engine --------

def _program_tpch(scale, n_streams, seed):
    """The program's TPC-H (``_TPCH_TABLES``, ``_TPCH_QUERIES``)."""
    db = make_tpch_db(scale=scale)
    streams = tpch_streams(db, n_streams=n_streams, seed=seed)
    return db, streams, tpch_accessed_bytes(db, streams)


def _tpch_sf30(scale, n_streams, seed):
    """The tables and templates of the benchmark's ``tpch_sf30``
    configuration: the specification's columns, each stream one
    permutation of the 22 templates."""
    from bench import cells, workgen

    cfg = dict(cells.load_config("tpch_sf30"), scale=scale)
    lib = workgen.lib_for(workgen.PROGRAM)
    db = workgen.make_db(lib, cfg)
    streams = workgen.template_streams(lib, db, cfg, n_streams,
                                       len(cfg["templates"]), seed)
    return db, streams, workgen.touched_bytes(db, cfg, streams)


def _vmapped(runner, cfgs):
    return jax.jit(jax.vmap(runner))(cfgs)


def _mapped(runner, cfgs):
    """One call that runs each lane to its own end: a vmap would hold
    every lane to the slowest one's steps."""
    return jax.jit(lambda c: jax.lax.map(runner, c))(cfgs)


#: (workload, time engine, batching, buffer points where CScan must beat
#: OPT).  On ``tpch_sf30`` the event engine itself puts CScan behind OPT
#: from pool 0.3 up (3.987 s against 3.924 s at 0.3, 3.041 against 2.995
#: at 0.5), so CScan's lead is held to at 0.15 only.
TPCH_CASES = {
    "program": (_program_tpch, "fixed", _vmapped, (0.15, 0.3, 0.5)),
    "tpch_sf30": (_tpch_sf30, "horizon", _mapped, (0.15,)),
}


@pytest.mark.parametrize("case", sorted(TPCH_CASES))
def test_vmapped_tpch_four_policy_buffer_sweep_within_validation_bars(case):
    """The acceptance shape of the ArrayPolicy tentpole: the FULL paper
    comparison — all four policies (lru / cscan / pbm / opt) x every
    validated buffer point — over the compiled TPC-H spec runs as ONE
    batched computation, and every lane agrees with the event engine
    within the validated TPC-H bars (<= 15% for the array-CScan /
    array-OPT ports).  Uses the quick-pass TPC-H point the bars were
    fit at, on the program's TPC-H (the fixed stepper) and on the
    benchmark's ``tpch_sf30`` tables and templates (the horizon stepper,
    which the benchmark's cell times)."""
    from repro.core.policy_registry import names as policy_names

    build, stepper, batch, cscan_ahead = TPCH_CASES[case]
    policies = policy_names(backend="array")
    assert set(policies) == {"lru", "cscan", "pbm", "opt"}
    scale = TPCH_DEFAULTS["scale"]
    bw = TPCH_DEFAULTS["bandwidth"]
    db, streams, ws = build(scale, TPCH_DEFAULTS["n_streams"],
                            TPCH_DEFAULTS["seed"])
    spec = compile_workload(db, streams)
    assert spec.n_tables >= 6          # the TPC-H fact + dimension tables
    assert spec.n_cols >= 50
    time_slice = 0.1 * scale
    # one runner over the whole registry: the policy axis itself is a
    # traced config scalar (the default policies=None means "all")
    runner = make_runner(spec, bandwidth_ref=bw, time_slice=time_slice,
                         stepper=stepper)
    fracs = sorted({f for (f, _p) in TPCH_ERROR_BARS})
    lanes = [(f, pol) for f in fracs for pol in policies]
    cfgs = stack_configs([
        make_config(spec, max(1 << 22, int(f * ws)), bw, pol)
        for f, pol in lanes
    ])
    states = jax.block_until_ready(batch(runner, cfgs))
    ios, times = {}, {}
    for i, (f, pol) in enumerate(lanes):
        ar = result_from_state(jax.tree.map(lambda x, i=i: x[i], states), pol)
        assert not ar.extras["truncated"], (f, pol)
        cap = max(1 << 22, int(f * ws))
        cfg = EngineConfig(bandwidth=bw, buffer_bytes=cap,
                           sample_interval=5.0, pbm_time_slice=time_slice)
        ev = run_workload(db, streams, pol, cfg)
        bar = TPCH_ERROR_BARS[(f, pol)]
        dt = ar.avg_stream_time / ev.avg_stream_time - 1
        dio = ar.io_gb / ev.io_gb - 1
        assert abs(dt) <= bar, (f, pol, dt, dio)
        assert abs(dio) <= bar, (f, pol, dt, dio)
        ios[(f, pol)] = ar.total_io_bytes
        times[(f, pol)] = ar.avg_stream_time
    # more buffer -> no more I/O per policy (weak monotonicity, 5% slack)
    for pol in policies:
        seq = [ios[(f, pol)] for f in fracs]
        for a, b in zip(seq, seq[1:]):
            assert b <= a * 1.05, (pol, seq)
    # the paper's policy ordering holds on the array backend too: the
    # cooperative scans beat every order-preserving policy where the
    # case holds them to it, and OPT bounds LRU at every validated
    # buffer point (Figs 14-16)
    for f in fracs:
        if f in cscan_ahead:
            assert times[(f, "cscan")] < times[(f, "opt")], (f, times)
        assert times[(f, "opt")] < times[(f, "lru")], (f, times)


def test_multitable_batched_lane_matches_solo_run():
    """A lane of the vmapped TPC-H batch must equal the same config run
    solo — batching cannot change multi-table semantics."""
    db = make_tpch_db(scale=0.02)
    streams = tpch_streams(db, n_streams=2, seed=7)
    ws = tpch_accessed_bytes(db, streams)
    spec = compile_workload(db, streams)
    runner = make_runner(spec, bandwidth_ref=600e6, time_slice=0.002,
                         policies=("pbm",))
    cfgs = stack_configs([
        make_config(spec, max(1 << 22, int(f * ws)), 600e6, "pbm")
        for f in (0.2, 0.4)
    ])
    states = jax.block_until_ready(jax.jit(jax.vmap(runner))(cfgs))
    solo = jax.block_until_ready(runner(jax.tree.map(lambda x: x[0], cfgs)))
    np.testing.assert_allclose(
        float(solo.io_bytes), float(states.io_bytes[0]), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(solo.stream_done_t), np.asarray(states.stream_done_t[0]),
        rtol=1e-5)
