"""Telemetry tier (PR 8): counters, flight recorder, provenance, lint.

The contracts under test, in acceptance order:

* ``make_runner(telemetry=False)`` output is BIT-EQUAL to the
  pre-telemetry program for every registry policy on both steppers (the
  static knob compiles to nothing when off);
* ``telemetry=True`` adds zero jit traces — the counters are ordinary
  carry leaves, so the one-trace-per-runner contract holds unchanged;
* the flight recorder (``TraceSession``) reconstructs, from per-step
  residency diffs, the SAME eviction count the carried counter reports
  (exactly) and the event engine reports (within the validation bars);
* a ``jax.debug.print`` seeded into a policy hook is caught by the
  ``jit-host-callback`` lint rule, and ``# analysis: obs`` escapes it;
* every RunManifest carries the attribution fields trend.py needs;
* the named phases reach the compiled ``op_name`` metadata, tally their
  own host seconds while JAX traces them, and the set-up spans record
  in order;
* ``ServingEngine`` structured events agree with ``EngineStats``.
"""

import textwrap
import time

import numpy as np
import pytest

from repro.analysis import lint_source
from repro.core import EngineConfig, run_workload
from repro.core.array_sim import (
    build_spec,
    make_config,
    make_runner,
    run_workload_array,
)
from repro.core.workload import (
    make_lineitem_db,
    micro_accessed_bytes,
    micro_streams,
)
from repro.obs import (
    Record,
    collect_manifest,
    counters,
    phase,
    phase_of,
    scope,
    span,
    spec_hash,
)
from repro.obs.trace import TraceSession, serving_events_to_chrome

TRACED_REL = "repro/core/array_sim/policies.py"


def _lint(src: str, rel: str = TRACED_REL):
    return [f.rule for f in lint_source(textwrap.dedent(src), rel)]


def _tiny_point():
    db = make_lineitem_db(scale_tuples=2_000_000)
    streams = micro_streams(db, n_streams=2, queries_per_stream=1, seed=3)
    return db, streams, build_spec(db, streams), 16 << 20


def _micro_point(scale=0.1, frac=0.4):
    """The trace CLI's default point (repro.obs.trace main())."""
    db = make_lineitem_db(scale_tuples=int(6_001_215 * scale))
    streams = micro_streams(db, n_streams=4, queries_per_stream=4, seed=3)
    spec = build_spec(db, streams)
    cap = max(1 << 22, int(frac * micro_accessed_bytes(db)))
    return db, streams, spec, cap


# ------------------------------------------------ tier 1: carried counters --

@pytest.mark.parametrize("stepper", ["fixed", "horizon"])
def test_telemetry_off_bit_equal_and_on_adds_no_trace(stepper):
    """The static-knob contract, all four registry policies x both
    steppers: the off path's SimState is bit-equal to the on path's, and
    each runner still traces exactly once across the whole policy sweep."""
    from repro.core import policy_registry

    _, _, spec, cap = _tiny_point()
    base = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                       stepper=stepper)
    teler = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                        stepper=stepper, telemetry=True)
    assert teler.telemetry is True and base.telemetry is False
    for pol in policy_registry.names(backend="array"):
        cfg = make_config(spec, cap, 700e6, pol)
        st0 = base(cfg)
        st1, tele = teler(cfg)
        for name in st0._fields:
            if name == "pstate":
                continue  # nested per-policy tuple, compared below
            np.testing.assert_array_equal(
                np.asarray(getattr(st0, name)), np.asarray(getattr(st1, name)),
                err_msg=f"{stepper}/{pol}/{name}")
        import jax
        for a, b in zip(jax.tree.leaves(st0.pstate),
                        jax.tree.leaves(st1.pstate)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the counters themselves agree with the state's own ground truth
        assert int(tele.loads) == int(st1.loads), (stepper, pol)
        assert int(tele.hits) + int(tele.misses) > 0, (stepper, pol)
    assert base.trace_count() == 1, stepper
    assert teler.trace_count() == 1, stepper


def test_workload_result_carries_telemetry_summary():
    db, streams, spec, cap = _tiny_point()
    res = run_workload_array(db, streams, "pbm", capacity_bytes=cap,
                             time_slice=0.01, spec=spec, stepper="horizon",
                             telemetry=True)
    t = res.extras["telemetry"]
    assert 0.0 <= t["hit_rate"] <= 1.0
    assert t["loads"] >= t["misses"] >= 0
    assert len(t["jump_hist"]) == counters.N_BINS
    # the horizon stepper must have jumped at least once somewhere past
    # bin 0 OR done everything in single fine steps — either way the
    # histogram mass equals the macro-step count
    assert sum(t["jump_hist"]) == res.extras.get("macro_steps", res.steps)
    assert "pbm" in t.get("policy_obs", {}) or t["hits"] == 0


# --------------------------------------------- tier 2: the flight recorder --

def test_trace_reconstructs_eviction_counts():
    """Acceptance: the exported Perfetto trace for the default micro
    point reconstructs the eviction count (a) exactly equal to the
    carried counter, and (b) equal to the event engine's
    ``total_evictions`` within the existing validation bars."""
    from repro.core.array_sim.validate import ERROR_BARS

    db, streams, spec, cap = _micro_point()
    sess = TraceSession(spec, policies=("pbm",))
    state = sess.run(make_config(spec, cap, 700e6, "pbm"))
    te = sess.eviction_total()
    assert te > 0, "micro point must induce evictions to test anything"

    # (a) exact agreement with the carried counter: same compiled step,
    # host-looped vs device-looped
    runner = make_runner(spec, bandwidth_ref=700e6, time_slice=0.1,
                         policies=("pbm",), stepper="horizon",
                         telemetry=True)
    st, tele = runner(make_config(spec, cap, 700e6, "pbm"))
    assert int(tele.evictions) == te
    assert float(st.t) == float(state.t)

    # (b) event engine within the validated envelope
    ev = run_workload(db, streams, "pbm", EngineConfig(
        bandwidth=700e6, buffer_bytes=cap, sample_interval=2.0,
        pbm_time_slice=0.1))
    bar = ERROR_BARS[(0.4, "pbm")]
    assert abs(te - ev.total_evictions) <= max(2, bar * ev.total_evictions), (
        f"trace={te} event={ev.total_evictions}")

    # the chrome export carries the same per-step numbers it was built from
    chrome = sess.to_chrome()
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert sum(e["args"]["evicted"] for e in xs) == te
    assert all(e["dur"] > 0 for e in xs)
    assert any(e["ph"] == "C" for e in chrome["traceEvents"])


def test_trace_session_fixed_stepper_runs():
    _, _, spec, cap = _tiny_point()
    sess = TraceSession(spec, policies=("lru",), stepper="fixed",
                        time_slice=0.01)
    sess.run(make_config(spec, cap, 700e6, "lru"))
    assert sess.events
    assert all(e["kind"] in ("fine", "refresh") for e in sess.events)


# ------------------------------------------------------- tier 3: manifest --

def test_manifest_fields_and_spec_hash():
    _, _, spec, cap = _tiny_point()
    runner = make_runner(spec, bandwidth_ref=700e6, time_slice=0.01,
                         stepper="horizon")
    runner(make_config(spec, cap, 700e6, "pbm"))
    man = collect_manifest(spec=spec, runner=runner, extra_key="x")
    for key in ("git_sha", "python", "jax", "jaxlib", "platform"):
        assert man[key], key
    assert man["spec_hash"] == spec_hash(spec)
    assert len(man["spec_hash"]) == 12
    assert man["stepper"] == "horizon"
    assert man["sanitize"] is False
    assert man["trace_count"] == 1
    assert man["extra_key"] == "x"
    # content hash: a different workload hashes differently
    _, _, spec2, _ = _micro_point(scale=0.02)
    assert spec_hash(spec2) != man["spec_hash"]


# ------------------------------------------- the jit-host-callback lint rule --

def test_debug_print_in_policy_hook_is_flagged():
    rules = _lint("""
        import jax
        class P:
            def score_victims(self, pstate, ctx):
                jax.debug.print("score={x}", x=pstate)
                return pstate
    """)
    assert "jit-host-callback" in rules


def test_obs_pragma_escapes_callback_ban_only():
    rules = _lint("""
        import jax
        # analysis: obs
        def key_of(pstate, ctx):
            jax.debug.print("k={x}", x=pstate)
            return pstate
    """)
    assert "jit-host-callback" not in rules
    # the escape is scoped: purity rules still apply under the pragma
    rules = _lint("""
        import jax
        # analysis: obs
        def key_of(pstate, ctx):
            jax.debug.print("k={x}", x=pstate)
            return float(pstate)
    """)
    assert "jit-coercion" in rules


def test_callback_spellings_are_all_caught():
    rules = _lint("""
        import jax
        from jax import debug
        from jax.experimental import io_callback, host_callback

        def key_of(pstate, ctx):
            debug.print("{x}", x=pstate)
            jax.pure_callback(lambda x: x, pstate, pstate)
            io_callback(lambda x: x, pstate, pstate)
            host_callback.id_print(pstate)
            return pstate
    """)
    assert rules.count("jit-host-callback") == 4


def test_obs_counters_module_is_a_traced_region():
    rules = _lint("""
        import jax
        def count(c, event):
            jax.debug.print("{c}", c=c)
            return c
    """, rel="repro/obs/counters.py")
    assert "jit-host-callback" in rules


# ------------------------------------------------------ phases and spans --

def test_phase_scopes_reach_op_names_and_tally_trace_seconds():
    import jax
    import jax.numpy as jnp

    def f(x):
        with phase("io"):
            y = x + 1.0
            with scope("kernel:fifo_grant"):
                time.sleep(0.02)
                y = y * 2.0
        return y

    jitted = jax.jit(f)
    x = jnp.ones(3)
    with Record() as rec:
        text = jitted.lower(x).compile().as_text()
        jitted(x)                      # a cached call traces nothing
    assert "step:io/kernel:fifo_grant" in text
    assert set(rec.trace_seconds) == {"step:io", "kernel:fifo_grant"}
    # each scope keeps its own seconds, not those of the scopes inside it
    assert rec.trace_seconds["kernel:fifo_grant"] >= 0.02
    assert rec.trace_seconds["step:io"] < 0.02
    with Record() as later:
        jitted(x)
    assert later.trace_seconds == {}


def test_phase_vocabulary():
    with pytest.raises(ValueError, match="unknown step phase"):
        phase("kernel")
    assert phase_of("jit(timed)/step:loop/while/body/step:io/"
                    "kernel:fifo_grant/pallas_call") == "step:io"
    assert phase_of("jit(timed)/vmap(jit(counted_run))") is None


def test_spans_record_in_order_while_a_record_is_open():
    t0 = time.perf_counter()
    with Record() as rec:
        with span("setup:start", start=t0):
            pass
        with span("setup:warm"):
            time.sleep(0.01)
    with span("setup:load"):
        pass
    (n0, s0, e0), (n1, s1, e1) = rec.spans
    assert (n0, n1) == ("setup:start", "setup:warm")
    assert s0 == t0 <= e0 <= s1 and e1 - s1 >= 0.01


# ------------------------------------------------- serving structured events --

def test_serving_events_agree_with_stats():
    from benchmarks.serving_bench import DEFAULT_POINT, run_policy

    events = []
    row = run_policy("pbm", record_events=True, events_out=events,
                     **DEFAULT_POINT)
    assert events, "oversubscribed default point must preempt"
    kinds = {}
    for e in events:
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
        assert e["policy"] == "pbm"
        assert "step" in e and "rid" in e
    assert kinds.get("preempt", 0) == row["preemptions"]
    assert kinds.get("resume", 0) == row["resumes"]
    prefetched = sum(1 for e in events
                     if e["kind"] == "resume" and e.get("prefetched"))
    assert prefetched == row["prefetched_resumes"]
    chrome = serving_events_to_chrome(events, label="test")
    assert (sum(1 for e in chrome["traceEvents"] if e["ph"] == "i")
            == len(events))
    assert row["manifest"]["git_sha"]


def test_serving_events_off_by_default():
    from repro.serving import PagePool, ServingEngine

    pool = PagePool(n_pages=8, page_size=4, page_bytes=1024)
    eng = ServingEngine(pool, lambda reqs: [0] * len(reqs), policy="lru")
    assert eng.record_events is False
    eng._emit("admit")
    assert eng.events == []
