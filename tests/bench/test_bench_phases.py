"""The step's phases and the set-up's spans (``bench.phases``): the split
of a hand-made trace and of the recorded excerpt, and a tiny cell set up
on the CPU (Pallas kernels interpreted)."""

import os
import re
import subprocess
import sys
import time

import pytest

from bench import cells, job, phases, trace
from benchtiny import SEED
from repro.obs import phase_of
from test_bench_trace import EXCERPT, HAND, profile

SPANS = ["setup:start", "setup:build", "setup:build", "setup:trace",
         "setup:lower", "setup:load", "setup:warm"]
KERNEL_PHASE = {"kernel:fifo_grant": "step:io",
                "kernel:batched_evict": "step:evict",
                "kernel:wake_solve": "step:horizon"}


def nested(op_names):
    """The op names with every kernel scope inside the ``io`` phase."""
    return {k: v.replace("kernel:", "step:io/kernel:")
            for k, v in op_names.items()}


def test_hand_made_trace_splits_by_innermost_phase():
    names = {"fusion.7": "jit(run)/step:loop/while/body/step:demand/x",
             "fusion.9": "jit(run)/step:loop/step:evict/kernel:batched_evict/x",
             "kernel_fifo_grant.3": "jit(run)/step:loop/step:io/"
                                    "kernel:fifo_grant/pallas_call"}
    ops = phases.op_seconds(profile(HAND), names)
    assert ops == pytest.approx({
        "step:io/kernel:fifo_grant/kernel_fifo_grant.3": 2e-6,
        "step:demand/fusion.7": 5e-7,
        "step:evict/kernel:batched_evict/fusion.9": 2e-6,
        "unphased/kernel:wake_solve/kernel_wake_solve.4": 1e-6})
    assert phases.phase_seconds(ops) == pytest.approx({
        "step:io": 2e-6, "step:demand": 5e-7, "step:evict": 2e-6,
        "unphased": 1e-6})
    # the kernel scopes read the same inside a phase as alone
    flat = {k: v.replace("step:loop/", "").replace("step:evict/", "")
            for k, v in names.items()}
    a = trace.reduce_profile(profile(HAND), names, 1e-5)
    b = trace.reduce_profile(profile(HAND), flat, 1e-5)
    assert a.scope_seconds == b.scope_seconds
    assert a.scope_calls == b.scope_calls
    assert a.op_seconds == b.op_seconds


def test_excerpt_reads_the_same_with_kernels_nested_in_phases():
    import json

    with open(EXCERPT) as f:
        fx = json.load(f)
    pd = profile(fx["planes"])
    flat = trace.reduce_profile(pd, fx["op_names"], fx["window_s"])
    deep = trace.reduce_profile(pd, nested(fx["op_names"]), fx["window_s"])
    assert deep.busy_s == flat.busy_s == pytest.approx(1.17203e-3, rel=1e-6)
    assert deep.scope_seconds == flat.scope_seconds
    assert deep.scope_calls == flat.scope_calls == {
        "kernel:fifo_grant": 1, "kernel:batched_evict": 1}
    assert deep.op_seconds == flat.op_seconds
    split = phases.phase_seconds(phases.op_seconds(pd, nested(fx["op_names"])))
    assert split["step:io"] == pytest.approx(sum(flat.scope_seconds.values()))
    assert sum(split.values()) == pytest.approx(sum(flat.op_seconds.values()))


HLO = """HloModule m, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%inner.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %select.3 = f32[4]{0} select(%p, %p, %p), metadata={op_name="jit(t)/step:loop/step:io/select_n"}
}

%outer.2 (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  ROOT %fusion.5 = f32[4]{0} fusion(%q), kind=kLoop, calls=%inner.1
}

%bare.3 (r: f32[4]) -> f32[4] {
  %r = f32[4]{0} parameter(0)
  ROOT %scatter.9 = f32[4]{0} scatter(%r, %r, %r)
}

ENTRY %main.4 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%a), kind=kCustom, calls=%outer.2
  %fusion.8 = f32[4]{0} fusion(%a), kind=kCustom, calls=%bare.3
  ROOT %add.1 = f32[4]{0} add(%fusion.7, %fusion.8), metadata={op_name="jit(t)/step:loop/add"}
}
"""


def test_a_fusion_without_op_name_takes_the_first_inside_it():
    names = phases.fused_op_names(HLO)
    io = "jit(t)/step:loop/step:io/select_n"
    assert names == {"select.3": io, "fusion.5": io, "fusion.7": io,
                     "add.1": "jit(t)/step:loop/add"}
    assert trace.hlo_op_names(HLO) == {"select.3": io,
                                       "add.1": "jit(t)/step:loop/add"}


@pytest.fixture(scope="module")
def built(tiny, cpu):
    cell = cells.find_cell("micro_sf30.buf10", bench_dir=tiny)
    t0 = time.perf_counter()
    prog, spec, rec = phases.set_up(cell, SEED, cpu, backend="interpret",
                                    t_start=t0)
    return cell, prog, spec, rec, time.perf_counter() - t0


def test_setup_spans_tile_the_setup(built):
    _, _, _, rec, setup_s = built
    assert [n for n, _, _ in rec.spans] == SPANS
    for (_, s0, e0), (_, s1, e1) in zip(rec.spans, rec.spans[1:]):
        assert s0 <= e0 <= s1 <= e1
    total = sum(e - s for _, s, e in rec.spans)
    assert total == pytest.approx(setup_s, rel=0.01)


def test_trace_seconds_name_every_phase_of_the_plain_runner(built):
    _, _, _, rec, _ = built
    ts = rec.trace_seconds
    assert set(ts) == {"step:cpu", "step:demand", "step:io", "step:evict",
                       "step:horizon", "step:loop", *KERNEL_PHASE}
    assert all(v > 0 for v in ts.values())
    (trace_s,) = [e - s for n, s, e in rec.spans if n == "setup:trace"]
    assert sum(ts.values()) <= trace_s


def test_kernel_scopes_lie_inside_their_phases(built):
    _, prog, _, _, _ = built
    names = trace.hlo_op_names(prog.compiled.as_text())
    for kernel, ph in KERNEL_PHASE.items():
        assert {phase_of(v) for v in names.values() if kernel in v} == {ph}
    assert {phase_of(v) for v in names.values()} >= {
        "step:cpu", "step:demand", "step:io", "step:evict", "step:horizon",
        "step:loop"}
    assert prog.traces() == 1


def _instructions(text):
    """The compiled program's instructions, without their metadata (which
    names the source lines that built them)."""
    return [re.sub(r", metadata=\{[^}]*\}", "", ln)
            for ln in text.splitlines() if " = " in ln]


def test_the_harness_compiles_the_same_program(built, tiny, cpu):
    from bench import workload
    from repro.core.array_sim import compile_workload

    cell, prog, _, _, _ = built
    wl = workload.build(cell.config, cell.traffic, SEED)
    spec = compile_workload(wl.db, wl.streams)
    harness = job.build_program(spec, wl, cell, cpu, backend="interpret")
    assert _instructions(harness.compiled.as_text()) == \
        _instructions(prog.compiled.as_text())


def test_a_job_splits_into_phases_only_on_a_chip(built):
    cell, prog, spec, _, _ = built
    out = phases.split_job(cell, prog, spec)
    assert set(out) == {"job_s", "lane_steps"}
    assert out["job_s"] > 0 and len(out["lane_steps"]) == 4


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "bench.phases", "--workload",
         "micro_sf30.buf10", "--seed", "1"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr
