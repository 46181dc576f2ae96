"""The trace reduction on a hand-made trace with known numbers, and on a
short excerpt recorded on one TPU v5e (the start of the warm-up call of
the ``micro_sf30.buf40`` cell), checked against a plain recount and the
numbers it gave when recorded."""

import json
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import trace

EXCERPT = os.path.join(os.path.dirname(__file__), "data", "trace_excerpt.json")


def profile(planes):
    return NS(planes=[NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[NS(name=n, start_ns=s, duration_ns=d)
                                    for n, s, d in ln["events"]])
        for ln in p["lines"]]) for p in planes])


HAND = [
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench:job", 0, 10_000], ["bench:dispatch", 0, 1_000],
        ["bench:wait", 1_000, 9_000]]}]},
    {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["%while.1 = (s32[]) while(%t), condition=%c, body=%b", 1_500, 8_000],
        ["%kernel_fifo_grant.3 = f32[4,1,9216]{2,1,0} custom-call(%a)", 1_500, 1_000],
        ["%fusion.7 = f32[40]{0} fusion(%x), kind=kLoop", 2_500, 500],
        ["%kernel_fifo_grant.3 = f32[4,1,9216]{2,1,0} custom-call(%a)", 4_000, 1_000],
        ["%fusion.9 = f32[40]{0} fusion(%x), kind=kLoop", 5_000, 2_000],
        ["%kernel_wake_solve.4 = s32[4,1,9216]{2,1,0} custom-call(%a)", 7_500, 1_000],
    ]}, {"name": "Steps", "events": [["step", 0, 10_000]]}]},
    {"name": "/device:TPU_NON_CORE:0", "lines": [{"name": "XLA Ops", "events": [
        ["%fusion.1 = f32[] fusion()", 0, 10_000]]}]},
]


def test_hand_made_trace():
    red = trace.reduce_profile(profile(HAND), {"fusion.9": "jit(run)/kernel:batched_evict/x"},
                               window_s=1e-5)
    # busy: [1.5,3.0] + [4.0,7.0] + [7.5,8.5] us; the while op spans them
    assert red.busy_s == pytest.approx(5.5e-6)
    assert red.idle_share == pytest.approx(0.45)
    assert red.devices == 1
    assert red.scope_seconds == pytest.approx({"kernel:fifo_grant": 2e-6,
                                               "kernel:wake_solve": 1e-6,
                                               "kernel:batched_evict": 2e-6})
    assert red.scope_calls == {"kernel:fifo_grant": 2, "kernel:wake_solve": 1,
                               "kernel:batched_evict": 1}
    # gaps: 1.5 us before the first op (its middle in the dispatch), 1.5
    # after the last (host waiting), 1.0 between fusion.7 and the second
    # grant, 0.5 before the solve
    assert [n for n, _ in red.idle_gaps] == [
        "bench:dispatch", "bench:wait", "bench:wait", "bench:wait"]
    assert [g for _, g in red.idle_gaps] == pytest.approx(
        [1.5e-6, 1.5e-6, 1e-6, 5e-7])
    assert "kernel:fifo_grant/kernel_fifo_grant.3" in red.op_seconds


def test_no_device_plane_reads_nothing():
    assert trace.reduce_profile(profile(HAND[:1]), {}, 1.0) is None


def test_hlo_op_names():
    text = ('  %kernel_batched_evict.26 = f32[4,1,9216]{2,1,0} custom-call('
            'f32[4,1,1]{2,1,0} %a), custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(run)/while/body/kernel:batched_evict/'
            'pallas_call" source_file="x.py" source_line=3}\n'
            '  ROOT %t = (f32[]) tuple(%a)\n')
    assert trace.hlo_op_names(text) == {
        "kernel_batched_evict.26":
            "jit(run)/while/body/kernel:batched_evict/pallas_call"}


def test_recorded_tpu_excerpt_matches_a_plain_recount():
    """The first 600 op events of the device's ``XLA Ops`` line, with op
    names cut to 300 characters when recorded (the two ``while`` ops keep
    their opcode), and the benchmark's host spans."""
    with open(EXCERPT) as f:
        fx = json.load(f)
    red = trace.reduce_profile(profile(fx["planes"]), fx["op_names"],
                               fx["window_s"])
    ops = [(round(s), round(s + d), name) for plane in fx["planes"]
           if plane["name"].startswith("/device:TPU:")
           for line in plane["lines"] if line["name"] == "XLA Ops"
           for name, s, d in line["events"] if " while(" not in name]
    t0 = min(s for s, _, _ in ops)
    busy = np.zeros(max(e for _, e, _ in ops) - t0, bool)
    kernel_ns = 0
    for s, e, name in ops:
        busy[s - t0:e - t0] = True
        if name.lstrip("%").startswith("kernel_"):
            kernel_ns += e - s
    assert red.busy_s == pytest.approx(busy.sum() * 1e-9, rel=1e-6)
    assert sum(red.scope_seconds.values()) >= kernel_ns * 1e-9 * (1 - 1e-6)
    assert red.busy_s == pytest.approx(1.17203e-3, rel=1e-6)
    assert red.scope_calls == {"kernel:fifo_grant": 1, "kernel:batched_evict": 1}
    assert red.scope_seconds["kernel:fifo_grant"] == pytest.approx(6.1372e-4)
