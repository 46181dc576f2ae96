"""HLO trip-count accounting (benchmarks.hlo_analysis) validation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.hlo_analysis import HloModule


def _totals(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    mod = HloModule(c.as_text())
    return mod.totals(), c.cost_analysis()


A = jax.ShapeDtypeStruct((256, 256), jnp.float32)
FL = 2 * 256 ** 3


def test_matches_xla_on_loop_free():
    t, ca = _totals(lambda a: a @ a, A)
    assert abs(t["flops"] - ca["flops"]) / ca["flops"] < 1e-6


def test_scan_scaled_by_trip_count():
    def f(a):
        return jax.lax.scan(lambda c, _: (c @ a, None), a, None, length=7)[0]
    t, ca = _totals(f, A)
    assert abs(t["flops"] - 7 * FL) / (7 * FL) < 0.01
    assert ca["flops"] < t["flops"]  # XLA counts the body once


def test_nested_scan_compose():
    def f(a):
        def outer(c, _):
            d = jax.lax.scan(lambda x, _: (x @ a, None), c, None, length=5)[0]
            return d, None
        return jax.lax.scan(outer, a, None, length=3)[0]
    t, _ = _totals(f, A)
    assert abs(t["flops"] - 15 * FL) / (15 * FL) < 0.01


def test_grad_scan_counts_fwd_and_bwd():
    def loss(a):
        out = jax.lax.scan(lambda c, _: (jnp.tanh(c @ a), None), a, None,
                           length=4)[0]
        return out.sum()
    t, _ = _totals(jax.grad(loss), A)
    # 1 fwd dot + 2 bwd dots per layer = 12 dots
    assert abs(t["flops"] - 12 * FL) / (12 * FL) < 0.02


def test_collectives_counted_with_trips():
    mesh = jax.make_mesh((1,), ("x",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(a):
        def body(c, _):
            s = jax.lax.psum(c, "x")
            return s @ a, None
        return jax.lax.scan(body, a, None, length=3)[0]

    fn = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    c = jax.jit(fn).lower(jnp.ones((64, 64))).compile()
    mod = HloModule(c.as_text())
    t = mod.totals()
    # 3 iterations x all-reduce of a 64x64 f32 (single device still emits it
    # or folds it; accept either zero or 3x shape bytes)
    if t["collective_total"]:
        assert t["collective_total"] in (3 * 64 * 64 * 4, 64 * 64 * 4 * 3)


def test_bytes_nonzero_and_scale_with_trips():
    def f1(a):
        return jax.lax.scan(lambda c, _: (c @ a, None), a, None, length=2)[0]
    def f2(a):
        return jax.lax.scan(lambda c, _: (c @ a, None), a, None, length=8)[0]
    t1, _ = _totals(f1, A)
    t2, _ = _totals(f2, A)
    assert t2["bytes"] > 2.5 * t1["bytes"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_roofline_peaks_refuse_unknown_device_kind(kind):
    """Peaks are keyed by device kind; an unlisted kind is an error, not
    a silent v5e default."""
    from benchmarks.roofline import PEAKS, peaks

    assert peaks("TPU v5 lite") is PEAKS["TPU v5 lite"]
    with pytest.raises(ValueError, match="no roofline peaks"):
        peaks(kind)
