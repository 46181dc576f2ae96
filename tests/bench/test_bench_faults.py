"""The faults that must turn ``correct`` false, each planted under the
harness in the timed path of a tiny one-chip cell on the CPU (a micro
cell and the tests' multi-table cell), and the control: the regression
copy with the kernels' float inputs in bfloat16 in the program's
place."""

import pytest

from bench import cells, check

from benchtiny import MULTI_TABLE, ONE_CHIP, SEED, find_cell, run


def _broken(monkeypatch, fault):
    """Break the timed path underneath the harness: ``fault`` rewrites
    the final states each job returns."""
    import jax

    from bench import job

    orig = job.build_program

    def build(*a, **k):
        prog = orig(*a, **k)
        call = prog.call
        prog.call = lambda: fault(jax.block_until_ready(call()))
        return prog

    monkeypatch.setattr(job, "build_program", build)


def _unchanged(st):
    """A step that returns its state unchanged: the initial state."""
    return st._replace(t=st.t * 0, consumed=st.consumed * 0,
                       qidx=st.qidx * 0, io_bytes=st.io_bytes * 0,
                       loads=st.loads * 0,
                       stream_done_t=st.stream_done_t * 0 - 1)


def _half_batch(st):
    """Half of the lanes left out: their slots hold the other half's."""
    import jax
    import jax.numpy as jnp

    def half(x):
        h = x.shape[0] // 2
        return jnp.concatenate([x[:h], x[:h]])
    return jax.tree.map(half, st)


def _altered(st):
    """One answer altered where it is produced: a page more I/O."""
    return st._replace(io_bytes=st.io_bytes.at[0].add(524288.0))


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch,
          "answer_altered": _altered}


@pytest.mark.parametrize("name,fault", [
    pytest.param(name, fault, id=fid if name == "micro_sf30.buf10"
                 else f"{name}-{fid}")
    for name in ("micro_sf30.buf10", MULTI_TABLE["name"])
    for fid, fault in FAULTS.items()])
def test_a_broken_timed_path_is_not_correct(name, fault, tiny, cpu,
                                            monkeypatch):
    _broken(monkeypatch, fault)
    cell = find_cell(name, tiny)
    res = run(cell, cpu)
    assert res["correct"] is False
    assert res["failed"] > 0


@pytest.mark.parametrize("name", ONE_CHIP)
def test_the_control_is_not_correct(name, tiny):
    """The regression copy with the kernels' float inputs (page sizes,
    byte budgets, eviction keys) rounded to bfloat16 in the program's
    place fails the comparison."""
    import jax.numpy as jnp

    from bench.reference.kernels import Oracles

    cell = cells.find_cell(name, bench_dir=tiny)
    ref = check.reference_summary(cell, SEED)
    ctl = check.reference_summary(cell, SEED, kernels=Oracles(jnp.bfloat16))
    numbers, limits, _ = check.judge([ctl], ref, check.guarantees(cell, SEED))
    assert not check.is_correct(numbers, limits)
