"""Where one cell's set-up and its step's device time go, by name.

    python3 -m bench.phases --workload <cell> --seed <n>

Builds the cell's program as ``bench.run`` does, with the set-up cut into
the spans of ``repro.obs.span``:

* ``setup:start``: process start to the chips ready;
* ``setup:build``: the workload, its spec, and the query plan and lane
  configurations placed on the chips;
* ``setup:trace``: JAX tracing the runner (``jax.jit(timed).trace``);
* ``setup:lower``: lowering it, the Pallas kernels' Mosaic included;
* ``setup:load``: ``compile()``, a load from the compile cache, or a
  compile on a checkout's first run;
* ``setup:warm``: the warm-up call to one time slice.

Then it runs one job under the profiler, as a ``--trace 1`` run does,
and splits the job's device time by the step phase (``repro.obs.phases``)
that each op's ``op_name`` lies in: the innermost ``step:`` segment, or
``unphased``.  A fusion takes the ``op_name`` of its root, so an op XLA
fused across two phases counts toward one of them; a fusion XLA wrapped
around other fusions, with no ``op_name`` of its own, takes the first
one inside it (:func:`fused_op_names`); ops XLA's passes made with no
``op_name`` anywhere stay unphased.  The last line of standard output is
one JSON object.  Exits 2 where JAX finds no TPU.

``bench.run`` compiles the same program in one ``lower(...).compile()``
(``bench.job.build_program``); the tests hold the two to the same HLO.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import numpy as np  # noqa: E402

from bench import cells, job, trace  # noqa: E402
from bench.run import NoChip, enable_compile_cache, tpu_devices  # noqa: E402

UNPHASED = "unphased"
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def fused_op_names(hlo_text: str) -> Dict[str, str]:
    """``bench.trace.hlo_op_names``, and for each instruction whose own
    line carries no ``op_name`` but that calls a computation (a fusion
    XLA wrapped around other fusions), the ``op_name`` found first in
    that computation: its root, then its other instructions, through
    nested calls."""
    named = trace.hlo_op_names(hlo_text)
    body: Dict[str, list] = {}
    calls: Dict[str, str] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = body.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        if m.group(1):
            cur.insert(0, m.group(2))
        else:
            cur.append(m.group(2))
        c = _CALLS.search(line)
        if c:
            calls[m.group(2)] = c.group(1)

    def find(instr: str, depth: int) -> Optional[str]:
        if instr in named:
            return named[instr]
        if depth == 0 or instr not in calls:
            return None
        for inner in body.get(calls[instr], ()):
            got = find(inner, depth - 1)
            if got:
                return got
        return None

    out = dict(named)
    for instr in calls:
        if instr not in named:
            got = find(instr, 8)
            if got:
                out[instr] = got
    return out


def op_seconds(pd, op_names: Dict[str, str]) -> Dict[str, float]:
    """Device seconds of each op of the traced devices (summed over them),
    labelled ``<phase>/<label>``: the op's step phase, or ``unphased``,
    before the label ``bench.trace.reduce_profile`` gives it.  Control-flow
    ops, whose events span the ops they run, are left out."""
    from repro.obs import phase_of

    out: Dict[str, float] = {}
    for plane in pd.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for e in line.events:
                instr, opcode = trace.parse_event(e.name)
                if opcode in trace.CONTAINERS:
                    continue
                op_name = op_names.get(instr, "")
                scope = trace.scope_of(op_name, instr)
                label = f"{phase_of(op_name) or UNPHASED}/" + (
                    f"{scope}/{instr}" if scope else instr)
                out[label] = out.get(label, 0.0) + e.duration_ns * 1e-9
    return out


def phase_seconds(ops: Dict[str, float]) -> Dict[str, float]:
    """The labelled ops' seconds summed by phase."""
    out: Dict[str, float] = {}
    for label, s in ops.items():
        ph = label.split("/", 1)[0]
        out[ph] = out.get(ph, 0.0) + s
    return out


def _stage(spec, wl, cell, devices, backend: str):
    """Everything ``bench.job.build_program`` does before it compiles:
    the runner's function, the query plan and the lane configurations
    placed on the chips."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core.array_sim import make_config, make_runner, stack_configs
    from repro.kernels import ops

    tr, cf = cell.traffic, cell.config
    ops.set_backend(backend)
    shards = tr["lane_shards"]
    mesh = None
    if shards > 1:
        mesh = Mesh(np.array(devices[:shards]), ("lanes",))
    base, plan = job.split_plan(spec)
    traced = []

    def timed(plan, cfgs):
        traced.append(1)
        runner = make_runner(base._replace(**plan),
                             bandwidth_ref=tr["bandwidth_bps"],
                             time_slice=cf["time_slice_s"], stepper="horizon",
                             mesh=mesh)
        return runner(cfgs) if mesh is not None else jax.vmap(runner)(cfgs)

    def configs(max_time):
        cfgs = stack_configs([
            make_config(spec, cap, tr["bandwidth_bps"], pol,
                        max_time=max_time)
            for pol, _, cap in wl.lanes])
        if mesh is None:
            return jax.device_put(cfgs, devices[0])
        return jax.device_put(cfgs, NamedSharding(mesh, PartitionSpec("lanes")))

    if mesh is None:
        plan = jax.device_put(plan, devices[0])
    else:
        plan = jax.device_put(plan, NamedSharding(mesh, PartitionSpec()))
    return (timed, traced, plan, configs(tr["horizon_s"]),
            configs(cf["time_slice_s"]), shards)


def build_program(spec, wl, cell, devices, backend: str = "pallas"):
    """``bench.job.build_program`` in the spans ``setup:build`` (up to the
    compile), ``setup:trace``, ``setup:lower`` and ``setup:load``."""
    import jax

    from repro.obs import span

    with span("setup:build"):
        timed, traced, plan, cfgs, warm_cfgs, shards = _stage(
            spec, wl, cell, devices, backend)
    t0 = time.perf_counter()
    with span("setup:trace"):
        traced_fn = jax.jit(timed).trace(plan, cfgs)
    with span("setup:lower"):
        lowered = traced_fn.lower()
    with span("setup:load"):
        compiled = lowered.compile()
    return job.Program(
        call=lambda: compiled(plan, cfgs),
        warm=lambda: compiled(plan, warm_cfgs),
        traces=lambda: len(traced),
        compiled=compiled,
        devices=list(devices[:shards]),
        lanes_per_shard=len(wl.lanes) // shards,
        compile_s=time.perf_counter() - t0,
    )


def set_up(cell, seed: int, devices, backend: str = "pallas",
           t_start: Optional[float] = None):
    """The cell's program, built and warmed as ``bench.run`` sets it up;
    returns it, its spec and the :class:`repro.obs.Record` of the set-up
    spans (``setup:start`` from ``t_start`` to this call) and of the
    host seconds inside each scope while JAX traced the runner."""
    import jax

    from bench import workload
    from repro.core.array_sim import compile_workload
    from repro.obs import Record, span

    t_start = time.perf_counter() if t_start is None else t_start
    with Record() as rec:
        with span("setup:start", start=t_start):
            pass
        with span("setup:build"):
            wl = workload.build(cell.config, cell.traffic, seed)
            spec = compile_workload(wl.db, wl.streams)
        prog = build_program(spec, wl, cell, devices, backend=backend)
        with span("setup:warm"):
            jax.block_until_ready(prog.warm())
    return prog, spec, rec


def split_job(cell, prog, spec) -> dict:
    """One job under the profiler, its device time split by phase; only
    its lanes' steps and host seconds where the trace holds no TPU."""
    import jax

    from bench import metrics, peaks

    def one_job():
        with jax.profiler.TraceAnnotation("bench:job"):
            with jax.profiler.TraceAnnotation("bench:dispatch"):
                out = prog.call()
            with jax.profiler.TraceAnnotation("bench:wait"):
                return job.summary(jax.block_until_ready(out))

    logdir = tempfile.mkdtemp(prefix="bench_phases_")
    try:
        summ, job_s = trace.capture(one_job, logdir)
        pd = trace.load(logdir)
        text = prog.compiled.as_text()
        reduced = None if pd is None else trace.reduce_profile(
            pd, trace.hlo_op_names(text), job_s)
        ops = {} if reduced is None else op_seconds(
            pd, fused_op_names(text))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    host_job = job.to_host(summ)
    out = {"job_s": job_s, "lane_steps": host_job["steps"].tolist()}
    if reduced is None:
        return out
    ctx = metrics.Context(
        jobs=[host_job], traced_job=host_job,
        lanes_per_shard=prog.lanes_per_shard, shards=len(prog.devices),
        pages=int(spec.n_pages), trace=reduced,
        peak=peaks.peaks(prog.devices[0].device_kind))
    per_step = 1e6 / (reduced.devices * int(host_job["steps"].max()))
    phases = phase_seconds(ops)
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    out.update({
        "metrics": {k: v["value"] for k, v in
                    metrics.per_layer(cell.per_layer, ctx).items()},
        "busy_s": reduced.busy_s,
        "phase_us": {ph: s * per_step for ph, s in sorted(phases.items())},
        "unphased_share": 100.0 * phases.get(UNPHASED, 0.0)
        / (reduced.busy_s * reduced.devices),
        "scope_calls": reduced.scope_calls,
        "device_ops": [[n, s] for n, s in top[:15]],
        "unphased_ops": [[n, s] for n, s in top
                         if n.startswith(UNPHASED + "/")][:10],
    })
    return out


def run_phases(cell, seed: int, devices, backend: str = "pallas",
               t_start: Optional[float] = None) -> dict:
    """Set-up in spans, then one traced job split by phase."""
    prog, spec, rec = set_up(cell, seed, devices, backend, t_start)
    setup: Dict[str, float] = {}
    for name, start, end in rec.spans:
        setup[name] = setup.get(name, 0.0) + end - start
    dev0 = prog.devices[0]
    return {
        "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                   "count": len(prog.devices)},
        "setup_s": rec.spans[-1][2] - rec.spans[0][1],
        "setup": setup,
        "trace_seconds": rec.trace_seconds,
        "runner_traces": prog.traces(),
        **split_job(cell, prog, spec),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    cell = cells.find_cell(args.workload)
    enable_compile_cache()
    try:
        devices = tpu_devices(cell.chips)
    except NoChip as e:
        print(f"bench.phases: {e}", file=sys.stderr, flush=True)
        return 2
    result = run_phases(cell, args.seed, devices, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
