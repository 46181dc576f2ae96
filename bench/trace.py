"""Capture one job under the JAX profiler and reduce the trace to numbers.

The reduction reads the ``.xplane.pb`` file with ``jax.profiler.ProfileData``:

* device planes are those named ``/device:TPU:<n>``; their ``XLA Ops``
  line holds one event per HLO operation executed, named by the
  instruction's text (``%name = <shape> <opcode>(...)``);
* a device's busy time is the union of its op intervals, leaving out the
  control-flow ops (``while``, ``conditional``, ``call``) whose events
  span the ops they run; the traced window is the host's clock around
  the job;
* an op belongs to a kernel scope (``kernel:batched_evict`` and the
  others that ``repro.kernels.ops`` opens) when the ``op_name`` metadata
  the compiled program gives that HLO instruction lies inside the scope,
  or when the instruction is the op's kernel (``kernel_<op>``, the name
  of its ``pallas_call``);
* the gaps between device ops, and those between the host's ``bench:job``
  span and the device's first and last op, are named by the host span
  that covers them (the benchmark's own ``bench:*`` annotations, or the
  innermost host event).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SCOPES = ("kernel:batched_evict", "kernel:fifo_grant", "kernel:wake_solve")
#: the host span the benchmark opens around the traced job
JOB_SPAN = "bench:job"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata (the JAX scope
    path), from a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


CONTAINERS = ("while", "conditional", "call")
_EVENT = re.compile(r"^%?([\w.\-]+)(?:\s*=\s*(.*))?$", re.S)
_OPCODE = re.compile(r"(?:^|[\s)}])([a-z][a-z0-9\-]*)\(")


def parse_event(name: str) -> Tuple[str, str]:
    """``(instruction, opcode)`` of a device op event's name; the opcode
    is empty where the name carries no instruction text."""
    m = _EVENT.match(name.strip())
    if not m:
        return name, ""
    instr, rest = m.group(1), m.group(2) or ""
    op = _OPCODE.search(rest)
    return instr, op.group(1) if op else ""


def scope_of(op_name: str, instr: str = "") -> Optional[str]:
    for s in SCOPES:
        if s in op_name or instr.startswith(s.replace(":", "_")):
            return s
    return None


@dataclass
class Reduced:
    """What one traced window says, per device averaged where noted."""

    window_s: float
    busy_s: float                         # mean over the traced devices
    devices: int
    op_seconds: Dict[str, float] = field(default_factory=dict)   # all devs
    scope_seconds: Dict[str, float] = field(default_factory=dict)
    scope_calls: Dict[str, int] = field(default_factory=dict)  # all devs
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)


def _union(intervals: List[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Total covered length and the gaps between covered stretches."""
    total, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def _host_spans(pd) -> List[Tuple[int, int, str]]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return spans


def _name_gap(gap: Tuple[int, int], spans) -> str:
    """The benchmark's annotation covering the gap's middle, else the
    shortest host span that covers it."""
    mid = (gap[0] + gap[1]) // 2
    covering = [(e - s, n) for s, e, n in spans if s <= mid <= e]
    ours = [c for c in covering if c[1].startswith("bench:")]
    pick = min(ours or covering, default=None)
    return pick[1] if pick else "host: no span"


def reduce_profile(pd, op_names: Dict[str, str], window_s: float,
                   n_gaps: int = 10) -> Optional[Reduced]:
    """Reduce a loaded profile; ``None`` where it holds no device op."""
    spans = _host_spans(pd)
    jobs = [(s, e) for s, e, n in spans if n == JOB_SPAN]
    job = jobs[0] if len(jobs) == 1 else None
    per_dev_busy, gaps = [], []
    op_s: Dict[str, float] = {}
    scope_s: Dict[str, float] = {}
    instr_n: Dict[str, int] = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                instr, opcode = parse_event(e.name)
                if opcode in CONTAINERS:
                    continue
                intervals.append((e.start_ns, e.start_ns + e.duration_ns))
                scope = scope_of(op_names.get(instr, ""), instr)
                label = f"{scope}/{instr}" if scope else instr
                op_s[label] = op_s.get(label, 0.0) + e.duration_ns * 1e-9
                if scope:
                    scope_s[scope] = scope_s.get(scope, 0.0) + e.duration_ns * 1e-9
                    instr_n[label] = instr_n.get(label, 0) + 1
        if intervals:
            busy, g = _union(intervals)
            per_dev_busy.append(busy * 1e-9)
            gaps.extend(g)
            if job is not None:
                # before the device's first op and after its last
                first = min(s for s, _ in intervals)
                last = max(e for _, e in intervals)
                gaps.extend(x for x in ((job[0], first), (last, job[1]))
                            if x[1] > x[0])
    if not per_dev_busy:
        return None
    # every instruction of an op's scope runs once per call of the op
    scope_n: Dict[str, int] = {}
    for label, n in instr_n.items():
        scope = label.split("/", 1)[0]
        scope_n[scope] = max(scope_n.get(scope, 0), n)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_name_gap(g, spans), (g[1] - g[0]) * 1e-9) for g in gaps[:n_gaps]]
    return Reduced(
        window_s=window_s,
        busy_s=sum(per_dev_busy) / len(per_dev_busy),
        devices=len(per_dev_busy),
        op_seconds=op_s,
        scope_seconds=scope_s,
        scope_calls=scope_n,
        idle_gaps=named,
    )


def load(logdir: str):
    """The profile the last capture into ``logdir`` wrote."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    return ProfileData.from_file(paths[-1])


def capture(fn, logdir: str):
    """Run ``fn`` once under the profiler; returns its result and the
    host seconds it took."""
    import time

    import jax

    with jax.profiler.trace(logdir):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
    return out, dt
