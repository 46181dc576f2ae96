"""Event-horizon time engine: variable-dt macro-stepping, lane sharding,
and the ``slices_done`` livelock-guard rename.

The batched substrate now models time two ways (``make_runner(stepper=)``):
the classic fixed-dt cadence and the event-horizon stepper, which jumps
each lane to its next interesting time (trigger arrival, chunk
completion, io-credit horizon, stream completion, slice refresh).  These
tests pin the contracts the refactor introduced:

* dt-invariance — coarse (``step_pages=2``) vs fine fixed-dt vs the
  horizon stepper agree within the documented array-vs-array bars on the
  micro and TPC-H smoke workloads;
* frozen-lane invariance — a finished lane of a batched run is bit-equal
  to the same config run solo (its state freezes while slow lanes
  continue);
* ``shard_map`` lane mode — a single-device mesh is bit-equal to plain
  ``vmap``;
* the horizon's work is observable (``steps`` / ``macro_steps`` /
  ``skipped_time`` extras), not inferred;
* ``SimState.time_passed`` (a slice count that was never a time) is
  gone — the field is ``slices_done``, the old name no longer reads —
  and truncated runs still raise in ``cross_validate``;
* the budgeted FIFO-grant kernel matches its jnp oracle exactly.
"""

import functools
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.scans import ScanSpec
from repro.core.workload import (
    Q6_COLUMNS,
    make_lineitem_db,
    make_tpch_db,
    micro_accessed_bytes,
    micro_streams,
    tpch_accessed_bytes,
    tpch_streams,
)
from repro.core.array_sim import (
    ArrayCScan,
    ArrayPolicy,
    HorizonView,
    SimState,
    build_spec,
    compile_workload,
    make_config,
    make_runner,
    result_from_state,
    run_workload_array,
    stack_configs,
)

#: array-vs-array agreement bar between time discretisations (the
#: cross-backend bars live in validate.{ERROR_BARS,TPCH_ERROR_BARS};
#: between two array discretisations of the SAME machine we hold the
#: coarse/fine/horizon triangle to the same 12% envelope the validated
#: points use)
DT_INVARIANCE_BAR = 0.12


def _micro_shared():
    db = make_lineitem_db(scale_tuples=int(180_000_000 * 0.1))
    ws = micro_accessed_bytes(db)
    streams = micro_streams(db, n_streams=4, queries_per_stream=4, seed=3)
    return db, ws, streams


# ------------------------------------------------------ dt invariance -----

def test_dt_invariance_micro_fixed_coarse_horizon():
    """Coarse fixed (2-page steps), fine fixed, and the horizon stepper
    are three discretisations of one machine: both paper metrics must
    agree within the documented bar for LRU and PBM on the micro shape."""
    db, ws, streams = _micro_shared()
    spec = build_spec(db, streams)
    for pol in ("lru", "pbm"):
        runs = {}
        for tag, kw in (
            ("fine", dict(step_pages=1.0)),
            ("coarse", dict(step_pages=2.0)),
            ("horizon", dict(step_pages=1.0, stepper="horizon")),
        ):
            runner = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                                 policies=(pol,), **kw)
            runs[tag] = run_workload_array(
                db, streams, pol, capacity_bytes=int(0.3 * ws),
                bandwidth=700e6, time_slice=0.01, spec=spec, runner=runner,
            )
        ref = runs["fine"]
        assert not ref.extras["truncated"]
        for tag in ("coarse", "horizon"):
            r = runs[tag]
            assert not r.extras["truncated"], (pol, tag)
            dt_st = r.avg_stream_time / ref.avg_stream_time - 1
            dt_io = r.total_io_bytes / ref.total_io_bytes - 1
            assert abs(dt_st) <= DT_INVARIANCE_BAR, (pol, tag, dt_st)
            assert abs(dt_io) <= DT_INVARIANCE_BAR, (pol, tag, dt_io)


def test_dt_invariance_tpch_smoke():
    """Fixed vs horizon on the compiled multi-table TPC-H smoke workload
    (all four registered policies ride the same spec)."""
    db = make_tpch_db(scale=0.02)
    streams = tpch_streams(db, n_streams=3, seed=7)
    ws = tpch_accessed_bytes(db, streams)
    spec = compile_workload(db, streams)
    for pol in ("pbm", "cscan"):
        rs = {}
        for stepper in ("fixed", "horizon"):
            runner = make_runner(spec, bandwidth_ref=600e6,
                                 time_slice=0.002, policies=(pol,),
                                 stepper=stepper)
            rs[stepper] = run_workload_array(
                db, streams, pol, capacity_bytes=max(1 << 22, int(0.3 * ws)),
                bandwidth=600e6, time_slice=0.002, spec=spec, runner=runner,
            )
        dt_st = rs["horizon"].avg_stream_time / rs["fixed"].avg_stream_time - 1
        dt_io = rs["horizon"].total_io_bytes / rs["fixed"].total_io_bytes - 1
        assert abs(dt_st) <= DT_INVARIANCE_BAR, (pol, dt_st)
        assert abs(dt_io) <= DT_INVARIANCE_BAR, (pol, dt_io)


# ------------------------------------------------ frozen-lane freeze ------

def test_frozen_lane_is_bit_stable_while_slow_lanes_continue():
    """In a batched horizon run, a lane that finishes early freezes: its
    final state must be BIT-equal to the same config run solo, however
    long the slowest lane keeps stepping."""
    db, ws, streams = _micro_shared()
    spec = build_spec(db, streams)
    runner = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                         policies=("pbm",), stepper="horizon")
    fast = make_config(spec, int(1.0 * ws), 700e6, "pbm")   # roomy: finishes
    slow = make_config(spec, int(0.15 * ws), 700e6, "pbm")  # thrash: slow
    states = jax.block_until_ready(
        jax.jit(jax.vmap(runner))(stack_configs([fast, slow])))
    solo = jax.block_until_ready(runner(fast))
    fast_lane = jax.tree.map(lambda x: x[0], states)
    assert float(fast_lane.t) > 0
    # the slow lane really did keep going after the fast lane finished
    assert int(states.steps[1]) > int(states.steps[0])
    for name in ("t", "steps", "slices_done", "io_bytes", "loads",
                 "churn", "stream_done_t", "pos", "consumed"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fast_lane, name)),
            np.asarray(getattr(solo, name)), err_msg=name)


# ------------------------------------------------ shard_map lane mode -----

def test_mesh_single_device_equivalence():
    """``make_runner(mesh=...)`` over a one-device mesh must be bit-equal
    to the plain vmapped runner — for both steppers (the acceptance
    equivalence test of the shard_map lane mode)."""
    from jax.sharding import Mesh

    db, ws, streams = _micro_shared()
    spec = build_spec(db, streams)
    cfgs = stack_configs([
        make_config(spec, int(f * ws), 700e6, "pbm") for f in (0.3, 0.6)
    ])
    mesh = Mesh(np.array(jax.devices()[:1]), ("lanes",))
    for stepper in ("fixed", "horizon"):
        plain = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                            policies=("pbm",), stepper=stepper)
        sharded = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                              policies=("pbm",), stepper=stepper, mesh=mesh)
        a = jax.block_until_ready(jax.jit(jax.vmap(plain))(cfgs))
        b = jax.block_until_ready(sharded(cfgs))
        np.testing.assert_array_equal(np.asarray(a.io_bytes),
                                      np.asarray(b.io_bytes))
        np.testing.assert_array_equal(np.asarray(a.stream_done_t),
                                      np.asarray(b.stream_done_t))
        np.testing.assert_array_equal(np.asarray(a.steps),
                                      np.asarray(b.steps))


def test_mesh_rejects_three_axes():
    """One lane axis or a two-axis ('lane', 'page') mesh are the only
    accepted shapes; a third axis has no meaning here."""
    from jax.sharding import Mesh

    db, ws, streams = _micro_shared()
    spec = build_spec(db, streams)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1), ("a", "b", "c"))
    with pytest.raises(ValueError, match="one-axis"):
        make_runner(spec, policies=("pbm",), mesh=mesh)


def test_mesh_page_axis_equivalence():
    """A two-axis ('lanes', 'page') mesh page-shards the candidate scans
    inside each step; the construction is reduction-safe, so the run
    must stay BIT-equal to the plain vmapped runner."""
    from jax.sharding import Mesh

    db, ws, streams = _micro_shared()
    spec = build_spec(db, streams)
    cfgs = stack_configs([
        make_config(spec, int(f * ws), 700e6, "pbm") for f in (0.15, 0.3)
    ])
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("lanes", "page"))
    plain = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                        policies=("pbm",), stepper="horizon")
    sharded = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                          policies=("pbm",), stepper="horizon", mesh=mesh)
    assert sharded.page_axis == "page"
    a = jax.block_until_ready(jax.jit(jax.vmap(plain))(cfgs))
    b = jax.block_until_ready(sharded(cfgs))
    for name in ("io_bytes", "loads", "churn", "stream_done_t", "steps"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=name)


def test_page_sharded_ops_match_unsharded_on_multi_device_mesh():
    """The page-sharded candidate construction must be bitwise-identical
    to the unsharded oracles with REAL page shards (P split across >1
    devices).  Extra host devices must exist before JAX initialises, so
    this runs op-level checks in a subprocess with
    ``--xla_force_host_platform_device_count=4``."""
    import subprocess
    import sys

    code = """
import numpy as np, jax, jax.numpy as jnp
from functools import partial
from jax.sharding import Mesh, PartitionSpec as P
from repro.kernels import ops, ref

mesh = Mesh(np.array(jax.devices()[:4]), ("page",))
rng = np.random.default_rng(5)
Pn = 1024
for trial in range(4):
    key = jnp.asarray(rng.integers(-1, 1 << 28, Pn), jnp.int32)
    sizes = jnp.asarray(rng.choice([524288.0, 4096.0], Pn), jnp.float32)
    budget = jnp.float32(4e6)
    pops = jnp.int32(9)
    need = jnp.float32(3e6)
    ev = jnp.asarray(rng.random(Pn) < 0.7)
    fkey = jnp.asarray(rng.random(Pn), jnp.float32)

    g = jax.shard_map(
        partial(ops.fifo_grant, vmax=16, page_axis="page"),
        mesh=mesh, in_specs=(P(), P(), P(), P()),
        out_specs=(P(), P(), P()), check_vma=False,
    )(key, sizes, budget, pops)
    gr = ref.fifo_grant_ref(key, sizes, budget, pops, vmax=16)
    assert (np.asarray(g[0]) == np.asarray(gr[0])).all(), trial
    assert float(g[1]) == float(gr[1]) and int(g[2]) == int(gr[2]), trial

    e = jax.shard_map(
        partial(ops.batched_evict, vmax=64, page_axis="page"),
        mesh=mesh, in_specs=(P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(fkey, sizes, ev, need)
    er = ref.batched_evict_ref(fkey, sizes, ev, need, vmax=64)
    assert (np.asarray(e) == np.asarray(er)).all(), trial
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(sys.path)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ------------------------------------------- observability + rename -------

def test_horizon_reports_macro_steps_and_skipped_time():
    """Speedups are observable, not inferred: extras carry the executed
    step count and the simulated time the horizon jumped past."""
    db, ws, streams = _micro_shared()
    r_fix = run_workload_array(db, streams, "pbm",
                               capacity_bytes=int(0.5 * ws),
                               bandwidth=700e6, time_slice=0.01)
    r_hor = run_workload_array(db, streams, "pbm",
                               capacity_bytes=int(0.5 * ws),
                               bandwidth=700e6, time_slice=0.01,
                               stepper="horizon")
    for r in (r_fix, r_hor):
        assert r.extras["steps"] == r.steps
        assert r.extras["macro_steps"] == r.steps
        assert "skipped_time" in r.extras
        assert r.extras["slices_done"] > 0
    # the fixed cadence covers ~one fine step per step; the horizon
    # stepper must actually have jumped on this roomy pool
    assert r_fix.extras["skipped_time"] == pytest.approx(0.0, abs=1e-3)
    assert r_hor.extras["skipped_time"] > 0.0
    assert r_hor.steps < r_fix.steps


def test_time_passed_alias_is_gone():
    """``SimState.time_passed`` counted PBM slices, never time; the field
    is ``slices_done`` and the deprecated alias was removed — reading the
    old name is an AttributeError, not a warning."""
    assert "slices_done" in SimState._fields
    assert "time_passed" not in SimState._fields
    db, ws, streams = _micro_shared()
    spec = build_spec(db, streams)
    from repro.core.array_sim.sim import init_state
    st = init_state(spec, ())
    assert int(st.slices_done) == 0
    with pytest.raises(AttributeError):
        st.time_passed


def test_truncated_runs_still_raise_in_cross_validate(monkeypatch):
    """The livelock guard compares ``slices_done`` (né ``time_passed``)
    against ``max_slices``; a truncated array run must still abort
    cross-validation instead of comparing a lower bound."""
    from repro.core.array_sim import validate as v

    real = v.run_workload_array

    def forced_truncation(*args, **kw):
        kw["max_time"] = 1e-3
        return real(*args, **kw)

    monkeypatch.setattr(v, "run_workload_array", forced_truncation)
    with pytest.raises(RuntimeError, match="truncated by the livelock"):
        v.cross_validate(scale=0.02, n_streams=2, queries_per_stream=2,
                         buffer_frac=0.4, policies=("lru",))


def test_max_slices_guard_truncates_on_slices_done():
    """A tiny ``max_slices`` trips the guard via the renamed counter on
    BOTH steppers."""
    db = make_lineitem_db(scale_tuples=2_000_000)
    spec_q = ScanSpec("lineitem", Q6_COLUMNS, ((0, 2_000_000),),
                      tuple_rate=240e6)
    spec = build_spec(db, [[spec_q]])
    for stepper in ("fixed", "horizon"):
        runner = make_runner(spec, bandwidth_ref=700e6, time_slice=0.005,
                             policies=("lru",), max_slices=2,
                             stepper=stepper)
        st = jax.block_until_ready(
            runner(make_config(spec, 64 << 20, 700e6, "lru")))
        r = result_from_state(st, "lru")
        assert r.extras["truncated"], stepper
        assert int(st.slices_done) <= 2


# ------------------------------------------- horizon-provider protocol ----

def test_scan_horizon_protocol():
    """Policies are horizon providers: the default is unconstrained
    (``None``); the cooperative CScan reports per-stream chunk horizons."""
    db = make_tpch_db(scale=0.02)
    streams = tpch_streams(db, n_streams=3, seed=7)
    spec = compile_workload(db, streams)
    assert ArrayPolicy().scan_horizon((), None) is None
    cs = ArrayCScan()
    pstate = cs.init_state(spec)
    hz = HorizonView(
        spec=spec,
        active=jnp.ones(spec.n_streams, bool),
        start=jnp.zeros(spec.n_streams, jnp.float32),
        end=jnp.full(spec.n_streams, 1e6, jnp.float32),
        rate=jnp.full(spec.n_streams, 1e6, jnp.float32),
        dt_ref=jnp.float32(1e-3),
    )
    t = cs.scan_horizon(pstate, hz)
    assert t.shape == (spec.n_streams,)
    # idle active scans need a fine step to run the pick loop
    np.testing.assert_allclose(np.asarray(t), 1e-3)


# ------------------------------------------------ wake-exact stepper ------

def test_wake_exact_supersaturated_contract():
    """The supersaturated regime (capacity below one round of every
    stream's in-flight pages) used to pin the horizon stepper to the
    fine cadence; the wake-exact queue model replaces that never-jump
    rule.  Three contracts on a saturated deep-thrash point:

    * ``wake_exact=False`` preserves the PR-9 rule — results bit-equal
      to the fixed stepper;
    * ``wake_exact=True`` (the default) strictly reduces macro steps;
    * the fluid drift it introduces stays inside the documented
      array-vs-array bar (the queue model is exact; the residual drift
      is the stochastic per-step sampling collapsing onto macro steps).
    """
    db, ws, streams = _micro_shared()
    spec = build_spec(db, streams)
    cap = int(0.1 * ws)
    # the point must actually be supersaturated (pool below the scans'
    # aggregate plan-window bytes) or the contract is vacuous
    assert cap < spec.n_streams * 8 * float(np.max(spec.page_size))
    runs = {}
    for tag, kw in (
        ("fixed", dict(stepper="fixed")),
        ("off", dict(stepper="horizon", wake_exact=False)),
        ("on", dict(stepper="horizon", wake_exact=True)),
    ):
        runner = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                             policies=("pbm",), **kw)
        runs[tag] = jax.block_until_ready(
            runner(make_config(spec, cap, 700e6, "pbm")))
    # results are bit-equal; the internal clock `t` is excluded — the two
    # cadences partition the same span into different float additions
    for name in ("io_bytes", "loads", "churn", "stream_done_t"):
        np.testing.assert_array_equal(
            np.asarray(getattr(runs["fixed"], name)),
            np.asarray(getattr(runs["off"], name)), err_msg=name)
    # wake-exact macro-jumps: strictly fewer steps, bounded drift
    assert int(runs["on"].steps) < int(runs["off"].steps)
    assert int(runs["on"].steps) <= int(0.9 * int(runs["off"].steps))
    t_on = float(jnp.max(runs["on"].stream_done_t))
    t_fix = float(jnp.max(runs["fixed"].stream_done_t))
    assert abs(t_on / t_fix - 1) <= DT_INVARIANCE_BAR


def test_wake_exact_supersaturated_contract_tpch():
    """The wake-exact contract on the compiled multi-table TPC-H
    workload at a saturated buffer point — the race's 8-stream shape,
    scaled to test size (supersaturation is a per-stream in-flight
    bound, so it needs the full stream count; fewer/deeper-thrashed
    streams livelock or leave the validated regime entirely).
    ``wake_exact=False`` stays bit-equal to ``fixed`` on the result
    fields, ``wake_exact=True`` strictly cuts macro steps with drift
    inside the documented invariance bar."""
    db = make_tpch_db(scale=0.02)
    streams = tpch_streams(db, n_streams=8, seed=7)
    ws = tpch_accessed_bytes(db, streams)
    spec = compile_workload(db, streams)
    cap = max(1 << 22, int(0.3 * ws))
    assert cap < spec.n_streams * 8 * float(np.max(spec.page_size))
    runs = {}
    for tag, kw in (
        ("fixed", dict(stepper="fixed")),
        ("off", dict(stepper="horizon", wake_exact=False)),
        ("on", dict(stepper="horizon", wake_exact=True)),
    ):
        runner = make_runner(spec, bandwidth_ref=600e6, time_slice=0.002,
                             policies=("pbm",), **kw)
        runs[tag] = jax.block_until_ready(
            runner(make_config(spec, cap, 600e6, "pbm")))
    for name in ("io_bytes", "loads", "churn", "stream_done_t"):
        np.testing.assert_array_equal(
            np.asarray(getattr(runs["fixed"], name)),
            np.asarray(getattr(runs["off"], name)), err_msg=name)
    assert int(runs["on"].steps) < int(runs["off"].steps)
    t_on = float(jnp.max(runs["on"].stream_done_t))
    t_fix = float(jnp.max(runs["fixed"].stream_done_t))
    assert abs(t_on / t_fix - 1) <= DT_INVARIANCE_BAR


def test_wake_exact_no_effect_outside_saturation():
    """Non-saturated lanes never take the wake path: ``wake_exact`` on
    vs off must be BIT-identical at a buffer point above the
    supersaturation threshold."""
    db, ws, streams = _micro_shared()
    spec = build_spec(db, streams)
    cap = int(0.2 * ws)
    assert cap >= spec.n_streams * 8 * float(np.max(spec.page_size))
    runs = {}
    for tag, on in (("off", False), ("on", True)):
        runner = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                             policies=("pbm",), stepper="horizon",
                             wake_exact=on)
        runs[tag] = jax.block_until_ready(
            runner(make_config(spec, cap, 700e6, "pbm")))
    for name in ("t", "steps", "io_bytes", "loads", "churn",
                 "stream_done_t"):
        np.testing.assert_array_equal(
            np.asarray(getattr(runs["off"], name)),
            np.asarray(getattr(runs["on"], name)), err_msg=name)


def test_wake_solve_pops_only_on_lanes_that_read_it():
    """Only a supersaturated in-order lane reads the wake solve, so only
    such a lane asks it to pop.  Four policy lanes at a supersaturated
    and at a non-saturated pool, one ``vmap``: the interpret-mode Pallas
    kernels and the jnp oracles give bit-identical final states and
    counters, and ``wake_solves`` counts steps on the saturated lru, pbm
    and opt lanes only — never on cscan, never at the roomy pool."""
    from repro.kernels import ops

    db, ws, streams = _micro_shared()
    spec = build_spec(db, streams)
    sat_bytes = spec.n_streams * 8 * float(np.max(spec.page_size))
    pools = (int(0.1 * ws), int(0.2 * ws))
    assert pools[0] < sat_bytes <= pools[1]
    pols = ("lru", "cscan", "pbm", "opt")
    cfgs = stack_configs([make_config(spec, cap, 700e6, p, max_time=0.2)
                          for cap in pools for p in pols])
    runs = {}
    try:
        for backend in ("reference", "interpret"):
            ops.set_backend(backend)
            runner = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                                 policies=pols, stepper="horizon",
                                 telemetry=True)
            runs[backend] = jax.block_until_ready(
                jax.jit(jax.vmap(runner))(cfgs))
    finally:
        ops.set_backend("auto")
    for a, b in zip(jax.tree.leaves(runs["reference"]),
                    jax.tree.leaves(runs["interpret"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    state, tele = runs["interpret"]
    solves = np.asarray(tele.wake_solves)
    in_order = np.array([p != "cscan" for p in pols])
    assert (solves[:4][in_order] > 0).all()
    assert solves[:4][~in_order].sum() == 0
    assert solves[4:].sum() == 0
    # the horizon stepper asks once per macro-step of a reading lane
    np.testing.assert_array_equal(solves[:4][in_order],
                                  np.asarray(state.steps)[:4][in_order])


# ------------------------------------------------ fifo-grant kernel -------

def test_fifo_grant_kernel_matches_reference_interpret():
    """The budgeted FIFO-grant kernel (the horizon step's macro I/O pop)
    must agree exactly with the top_k oracle: strict head-of-line
    admission, pops cap, ties by page index, empty queues."""
    from repro.kernels.pbm_timeline import fifo_grant_kernel
    from repro.kernels.ref import fifo_grant_ref

    rng = np.random.default_rng(11)
    P = 128
    for i in range(10):
        if i % 3 == 0:
            # stamp-FIFO shaped keys with a -1 tail — full 30-bit range:
            # stamp_age*32768 + tie goes far past 2^24, where an f32
            # cast would silently round the tie bits away
            key = rng.integers(-1, (32767 << 15) + 32767, P)
        elif i % 3 == 1:  # dense ties on old stamps (tie bits past 2^24)
            key = (1 << 26) + rng.integers(-2, 4, P) * 3
        else:             # nothing wanted
            key = np.full(P, -1)
        key = jnp.asarray(key, jnp.int32)
        sizes = jnp.asarray(
            rng.choice([524288.0, 262144.0, 4096.0], P), jnp.float32)
        budget = jnp.float32(rng.choice([0.0, 5e5, 2e6, 1e7]))
        pops = jnp.int32(rng.integers(0, 14))
        mr, br, nr_ = fifo_grant_ref(key, sizes, budget, pops, vmax=12)
        mk, bk, nk = fifo_grant_kernel(key, sizes, budget, pops, vmax=12,
                                       interpret=True)
        np.testing.assert_array_equal(np.asarray(mr), np.asarray(mk))
        assert float(br) == float(bk)
        assert int(nr_) == int(nk)


def _wake_case(name, rng):
    """Inputs ``(key, sizes, credit0, inc, pops)`` of one named wake-solve
    case; leading axis of four lanes for ``vmap_mixed_pops``."""
    def fifo_keys(P, wanted=1.0):
        key = rng.integers(0, (32767 << 15) + 32767, P)
        return np.where(rng.random(P) < wanted, key, -1)

    def page_sizes(P):
        return rng.choice([524288.0, 262144.0, 4096.0], P)

    f32, i32 = np.float32, np.int32
    if name == "zero_pops":       # a lane that discards the solve
        P = 600
        return fifo_keys(P), page_sizes(P), f32(1e9), f32(1e7), i32(0)
    if name == "deeper_than_cap":  # credit for all; h_cap * pops stops it
        P = 700
        return fifo_keys(P), page_sizes(P), f32(1e12), f32(1e9), i32(3)
    if name == "credit_bound":    # the bytes stop it before h_cap * pops
        P = 700
        return fifo_keys(P), page_sizes(P), f32(2e5), f32(3e5), i32(40)
    if name in ("ragged_1061", "ragged_3712"):
        P = 1024 + 37 if name == "ragged_1061" else 3712
        return (fifo_keys(P, 0.3), page_sizes(P), f32(1e6), f32(8e5),
                i32(6))
    if name == "dense_ties":      # equal keys past 2^24: the page index rules
        P = 500
        key = (1 << 26) + rng.integers(-2, 4, P) * 3
        return key, page_sizes(P), f32(3e5), f32(6e5), i32(6)
    assert name == "vmap_mixed_pops"
    P = 900
    key = np.stack([fifo_keys(P, 0.4) for _ in range(4)])
    sizes = np.stack([page_sizes(P) for _ in range(4)])
    return (key, sizes, np.full(4, 1e6, f32), np.full(4, 6e5, f32),
            np.array([0, 6, 0, 6], i32))


@pytest.mark.parametrize("case", [
    "seeded", "zero_pops", "deeper_than_cap", "credit_bound", "ragged_1061",
    "ragged_3712", "dense_ties", "vmap_mixed_pops"])
def test_wake_solve_kernel_matches_reference_interpret(case):
    """The wake-solve kernel (per-page grant step of the frozen serial
    I/O server) must agree exactly with the jnp oracle: on seeded queues
    at P = 128 and 549 (the not-granted sentinel ``h_cap + 1`` on an
    empty queue), with zero pops (every page the sentinel), a queue
    deeper than ``h_cap * pops``, a credit bound that stops the pops
    first, pools that leave a ragged last row, dense key ties past 2^24,
    and four ``jax.vmap``-ed lanes with mixed pops."""
    from repro.kernels.pbm_timeline import wake_solve_kernel
    from repro.kernels.ref import wake_solve_ref

    rng = np.random.default_rng(23)
    if case != "seeded":
        args = [jnp.asarray(a) for a in _wake_case(case, rng)]
        kern = functools.partial(wake_solve_kernel, h_cap=12, interpret=True)
        oracle = functools.partial(wake_solve_ref, h_cap=12)
        if case == "vmap_mixed_pops":
            kern, oracle = jax.vmap(kern), jax.vmap(oracle)
        wr, wk = np.asarray(oracle(*args)), np.asarray(kern(*args))
        np.testing.assert_array_equal(wr, wk)
        granted = wk <= 12
        if case == "zero_pops":
            assert not granted.any()
        elif case == "deeper_than_cap":
            assert granted.sum() == 12 * 3 < np.sum(np.asarray(args[0]) >= 0)
        elif case == "credit_bound":
            assert 0 < granted.sum() < 12 * 40
        elif case == "vmap_mixed_pops":
            assert not granted[0::2].any()
            assert granted[1::2].any(axis=1).all()
        return
    for trial, P in enumerate((128, 512 + 37)):
        for i in range(4):
            if i == 3:  # nothing queued: every page gets the sentinel
                key = np.full(P, -1)
            else:
                key = rng.integers(-1, (32767 << 15) + 32767, P)
            key = jnp.asarray(key, jnp.int32)
            sizes = jnp.asarray(
                rng.choice([524288.0, 262144.0, 4096.0], P), jnp.float32)
            credit0 = jnp.float32(rng.choice([0.0, 3e5, 2e6]))
            inc = jnp.float32(rng.choice([2e5, 6e5]))
            pops = jnp.int32(rng.integers(1, 8))
            wr = wake_solve_ref(key, sizes, credit0, inc, pops, h_cap=12)
            wk = wake_solve_kernel(key, sizes, credit0, inc, pops,
                                   h_cap=12, interpret=True)
            np.testing.assert_array_equal(
                np.asarray(wr), np.asarray(wk), err_msg=f"P={P} i={i}")
            if i == 3:
                assert int(np.min(np.asarray(wk))) == 13
