"""Kernel contract verifier tests (DESIGN.md §9).

Three layers of assurance, mirroring the verifier's own structure:

* the shipped tree is clean — ``verify_kernels()`` returns no findings
  (this is the CI gate's kernel half);
* every rule class fires on a seeded violation: the AST rules on
  virtual kernel sources, the abstract-interpretation rules on toy
  ``pl.pallas_call`` wrappers built to violate exactly one contract
  each (BlockSpec coverage, index bounds, write races, VMEM budget);
* the kernels the verifier guards actually match their oracles:
  a numpy-seeded differential fuzz asserts EXACT agreement between the
  interpret-mode Pallas kernels and the ``ref.py`` oracles for
  ``batched_evict`` / ``fifo_grant`` across random shapes (including
  P not a multiple of 128), ``vmax`` smaller than the victim count,
  zero budgets and all-ineligible pools — plus the 2^24 integer-key
  regression the ``kernel-float-mantissa-cast`` rule pins.

The fuzz layer is deterministic (seeded ``numpy.random.Generator``) so
CI failures reproduce; when ``hypothesis`` is installed an extra
property-based pass widens the shape coverage.
"""

from __future__ import annotations

import functools
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from repro.analysis import lint_source, verify_kernels  # noqa: E402
from repro.analysis.absint import capture_calls, check_call  # noqa: E402
from repro.analysis.kernels import (  # noqa: E402
    KernelContract,
    check_contracts,
    kernel_lint_source,
)
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.pbm_timeline import (  # noqa: E402
    batched_evict_kernel,
    fifo_grant_kernel,
    wake_solve_kernel,
)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # hypothesis is optional in the test image
    HAVE_HYPOTHESIS = False


def rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# the gate: shipped tree is clean
# ---------------------------------------------------------------------------

def test_shipped_kernels_are_clean():
    findings = verify_kernels()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_stale_contract_is_a_finding():
    # a contract whose wrapper never reaches pl.pallas_call is itself a
    # finding — the table must not rot as kernels change
    def build():
        return (lambda x: x + 1, (jnp.ones(4),), {})

    fs = check_contracts([KernelContract("stale", build)])
    assert rules(fs) == ["kernel-contract-error"]
    assert "no pallas_call" in fs[0].message


def test_crashing_wrapper_is_a_finding():
    def build():
        def wrapper():
            raise RuntimeError("boom")
        return (wrapper, (), {})

    fs = check_contracts([KernelContract("crash", build)])
    assert rules(fs) == ["kernel-contract-error"]
    assert "boom" in fs[0].message


# ---------------------------------------------------------------------------
# layer 1: AST rules on seeded virtual sources
# ---------------------------------------------------------------------------

def test_blockspec_without_memory_space_flagged():
    src = textwrap.dedent("""
        def _body(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def toy_kernel(x):
            return pl.pallas_call(
                _body,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec(
                    (8, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
                grid=(4,),
            )(x)
    """)
    fs = kernel_lint_source(src, "repro/kernels/toy.py", {"toy_ref"})
    assert rules(fs) == ["kernel-memory-space"]
    assert len(fs) == 1  # only the undeclared in_spec, not the out_spec


def test_mxu_without_preferred_element_type_flagged():
    src = textwrap.dedent("""
        def _body(x_ref, o_ref):
            a = x_ref[...]
            o_ref[...] = jnp.dot(a, a)
            o_ref[...] += jax.lax.dot_general(a, a, (((1,), (0,)), ((), ())))
    """)
    fs = kernel_lint_source(src, "repro/kernels/toy.py", None)
    assert rules(fs) == ["kernel-mxu-element-type"]
    assert len(fs) == 2


def test_mxu_with_preferred_element_type_clean():
    src = textwrap.dedent("""
        def _body(x_ref, o_ref):
            a = x_ref[...]
            o_ref[...] = jnp.dot(a, a, preferred_element_type=jnp.float32)
    """)
    assert kernel_lint_source(src, "repro/kernels/toy.py", None) == []


def test_unconditional_float_key_cast_flagged():
    # the exact bug class this PR fixed in batched_evict_kernel
    src = textwrap.dedent("""
        def toy_kernel(key, sizes):
            key_row = key.reshape(1, -1).astype(jnp.float32)
            return key_row
    """)
    fs = kernel_lint_source(src, "repro/kernels/toy.py", {"toy_ref"})
    assert rules(fs) == ["kernel-float-mantissa-cast"]
    assert "2^24" in fs[0].message


def test_dispatched_float_key_cast_clean():
    # the sanctioned pattern: dtype dispatch keeps integers on an i32 path
    src = textwrap.dedent("""
        def toy_kernel(key, sizes):
            int_key = bool(jnp.issubdtype(key.dtype, jnp.integer))
            key_row = (key.astype(jnp.int32) if int_key
                       else key.astype(jnp.float32))
            return key_row
    """)
    assert kernel_lint_source(src, "repro/kernels/toy.py", {"toy_ref"}) == []


def test_missing_oracle_flagged():
    src = "def orphan_kernel(x):\n    return x\n"
    fs = kernel_lint_source(src, "repro/kernels/toy.py", {"toy_ref"})
    assert rules(fs) == ["kernel-missing-oracle"]


def test_oracle_pragma_satisfies_pairing():
    src = ("# analysis: oracle=toy_ref\n"
           "def orphan_kernel(x):\n    return x\n")
    assert kernel_lint_source(src, "repro/kernels/toy.py", {"toy_ref"}) == []


def test_oracle_pragma_naming_missing_ref_flagged():
    src = ("# analysis: oracle=ghost_ref\n"
           "def orphan_kernel(x):\n    return x\n")
    fs = kernel_lint_source(src, "repro/kernels/toy.py", {"toy_ref"})
    assert rules(fs) == ["kernel-missing-oracle"]
    assert "ghost_ref" in fs[0].message


def test_unknown_analysis_pragma_flagged():
    src = ("def helper(x):  # analysis: hosted\n"
           "    return x\n")
    fs = lint_source(src, "repro/obs/toy.py")
    assert "unknown-analysis-pragma" in rules(fs)


def test_known_pragmas_not_flagged():
    src = ("# analysis: host\n"
           "def helper(x):\n"
           "    return x  # analysis: revisit is mentioned fine elsewhere\n")
    fs = lint_source(src, "repro/obs/toy.py")
    assert "unknown-analysis-pragma" not in rules(fs)


# ---------------------------------------------------------------------------
# layer 2: abstract interpretation on seeded toy wrappers
# ---------------------------------------------------------------------------

def _copy_body(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _captured(wrapper, *operands):
    calls = []
    with capture_calls(calls):
        wrapper(*operands)
    assert calls, "toy wrapper made no pallas_call"
    return calls


def _toy_call(in_shape, out_shape, grid, in_spec, out_spec, kernel=None):
    x = jnp.zeros(in_shape, jnp.float32)

    def wrapper(x):
        return pl.pallas_call(
            kernel or _copy_body,
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
            in_specs=[in_spec],
            out_specs=out_spec,
            grid=grid,
        )(x)

    return _captured(wrapper, x)[0]


def test_block_not_dividing_operand_flagged():
    call = _toy_call(
        (100, 128), (100, 128), (2,),
        pl.BlockSpec((48, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((48, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
    )
    fs = check_call(call)
    assert "kernel-block-coverage" in rules(fs)
    assert any("does not divide" in f.message for f in fs)


def test_index_map_out_of_bounds_flagged():
    call = _toy_call(
        (4, 128), (4, 128), (4,),
        pl.BlockSpec((1, 128), lambda i: (i + 1, 0),  # last point OOB
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
    )
    fs = check_call(call)
    assert "kernel-index-oob" in rules(fs)


def test_output_block_never_written_flagged():
    call = _toy_call(
        (4, 128), (4, 128), (2,),
        pl.BlockSpec((1, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 128), lambda i: (i, 0),  # blocks 2, 3 unwritten
                     memory_space=pltpu.VMEM),
    )
    fs = check_call(call)
    assert any(f.rule == "kernel-block-coverage"
               and "never written" in f.message for f in fs)


def test_unguarded_output_revisit_flagged():
    call = _toy_call(
        (4, 128), (1, 128), (4,),
        pl.BlockSpec((1, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 128), lambda i: (0, 0),  # every point, same block
                     memory_space=pltpu.VMEM),
    )
    fs = check_call(call)
    assert "kernel-write-race" in rules(fs)


def test_when_guarded_revisit_sanctioned():
    def guarded(x_ref, o_ref):
        i = pl.program_id(0)

        @pl.when(i == 3)
        def commit():
            o_ref[...] = x_ref[...]

    call = _toy_call(
        (4, 128), (1, 128), (4,),
        pl.BlockSpec((1, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 128), lambda i: (0, 0), memory_space=pltpu.VMEM),
        kernel=guarded,
    )
    assert "kernel-write-race" not in rules(check_call(call))


def test_revisit_pragma_sanctions():
    def blessed(x_ref, o_ref):  # analysis: revisit
        o_ref[...] = x_ref[...]

    call = _toy_call(
        (4, 128), (1, 128), (4,),
        pl.BlockSpec((1, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 128), lambda i: (0, 0), memory_space=pltpu.VMEM),
        kernel=blessed,
    )
    assert "kernel-write-race" not in rules(check_call(call))


def test_vmem_budget_exceeded_flagged():
    call = _toy_call(
        (1024, 1024), (1024, 1024), (2,),
        pl.BlockSpec((512, 1024), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((512, 1024), lambda i: (i, 0), memory_space=pltpu.VMEM),
    )
    # two f32 (512, 1024) blocks, double-buffered = 8 MiB: fine at the
    # default 16 MiB budget, over a 4 MiB one
    assert "kernel-vmem-budget" not in rules(check_call(call))
    fs = check_call(call, vmem_budget=4 * 1024 * 1024)
    assert "kernel-vmem-budget" in rules(fs)


def test_scalar_block_on_vmem_flagged():
    x = jnp.zeros((1, 1), jnp.float32)

    def wrapper(x):
        return pl.pallas_call(
            _copy_body,
            out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],  # scalar!
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        )(x)

    fs = check_call(_captured(wrapper, x)[0])
    assert "kernel-memory-space" in rules(fs)
    assert any("SMEM" in f.message for f in fs)


def test_dense_block_on_smem_flagged():
    x = jnp.zeros((1, 256), jnp.float32)

    def wrapper(x):
        return pl.pallas_call(
            _copy_body,
            out_shape=jax.ShapeDtypeStruct((1, 256), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],  # dense row!
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        )(x)

    fs = check_call(_captured(wrapper, x)[0])
    assert "kernel-memory-space" in rules(fs)
    assert any("VMEM" in f.message for f in fs)


# ---------------------------------------------------------------------------
# the registry UX satellite: set_backend validates at set time
# ---------------------------------------------------------------------------

def test_set_backend_rejects_unknown_name():
    with pytest.raises(ValueError, match="valid backends"):
        ops.set_backend("mosaic")
    assert ops.get_backend() == "auto"  # the bad set did not stick


def test_set_backend_accepts_known_names():
    try:
        for name in ops.BACKENDS:
            ops.set_backend(name)
            assert ops.get_backend() == name
    finally:
        ops.set_backend("auto")


# ---------------------------------------------------------------------------
# differential fuzz: interpret-mode kernels == oracles, EXACTLY
# ---------------------------------------------------------------------------
# Sizes are integer-valued f32 and keys stay within [-2^30, 2^30), so
# every sum the kernels take (MXU prefix bytes, the grant's running sum,
# the oracle's cumsum) is exact in f32 — any mismatch is a real
# semantics bug, not rounding.

def _evict_case(rng, P, *, int_keys, all_ineligible=False, zero_need=False,
                vmax=None):
    if int_keys:
        key = jnp.asarray(
            rng.integers(-2**30, 2**30, P, dtype=np.int64), jnp.int32)
    else:
        # integer-valued floats with deliberate ties (tie-break by index)
        key = jnp.asarray(rng.integers(-50, 50, P), jnp.float32)
    sizes = jnp.asarray(rng.integers(1, 9, P), jnp.float32)
    if all_ineligible:
        evictable = jnp.zeros(P, bool)
    else:
        evictable = jnp.asarray(rng.random(P) < 0.6)
    need = jnp.float32(0.0 if zero_need
                       else float(rng.integers(1, 5 * P // 2)))
    vmax = vmax if vmax is not None else int(rng.integers(1, P + 1))
    return key, sizes, evictable, need, vmax


def _assert_evict_agrees(key, sizes, evictable, need, vmax):
    got = batched_evict_kernel(key, sizes, evictable, need,
                               vmax=vmax, interpret=True)
    want = ref.batched_evict_ref(key, sizes, evictable, need, vmax=vmax)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batched_evict_fuzz_matches_ref_exactly():
    rng = np.random.default_rng(0)
    # P deliberately includes non-multiples of 128 (interpret mode takes
    # them; the array sim pads — the kernel must not depend on padding)
    for P in (8, 100, 128, 200, 256):
        for int_keys in (False, True):
            _assert_evict_agrees(*_evict_case(rng, P, int_keys=int_keys))


def test_batched_evict_vmax_below_victim_count():
    rng = np.random.default_rng(1)
    for trial in range(4):
        key, sizes, evictable, _, _ = _evict_case(rng, 128, int_keys=False)
        # demand more bytes than vmax candidates can ever free
        _assert_evict_agrees(key, sizes, evictable, jnp.float32(1e6), 7)


def test_batched_evict_edge_cases():
    rng = np.random.default_rng(2)
    _assert_evict_agrees(*_evict_case(rng, 64, int_keys=True,
                                      all_ineligible=True))
    _assert_evict_agrees(*_evict_case(rng, 64, int_keys=False,
                                      zero_need=True))
    _assert_evict_agrees(*_evict_case(rng, 1, int_keys=True))


def test_batched_evict_integer_keys_beyond_2_24():
    # the regression the kernel-float-mantissa-cast rule pins: under the
    # old unconditional f32 cast, 2^24 and 2^24 + 1 collapse to the same
    # float and the WRONG page wins the eviction pop
    key = jnp.asarray([2**24, 2**24 + 1, 0, 0], jnp.int32)
    sizes = jnp.ones(4, jnp.float32)
    evictable = jnp.ones(4, bool)
    got = batched_evict_kernel(key, sizes, evictable, jnp.float32(1.0),
                               vmax=4, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), [False, True, False, False])
    _assert_evict_agrees(key, sizes, evictable, jnp.float32(1.0), 4)
    # wide OPT-style distances, dense around the mantissa edge
    rng = np.random.default_rng(3)
    wide = jnp.asarray(2**24 + rng.integers(0, 64, 128), jnp.int32)
    szs = jnp.asarray(rng.integers(1, 4, 128), jnp.float32)
    ev = jnp.asarray(rng.random(128) < 0.8)
    _assert_evict_agrees(wide, szs, ev, jnp.float32(40.0), 32)


def _grant_case(rng, P, *, zero_budget=False, none_wanted=False):
    key = jnp.asarray(rng.integers(-1, 2**29, P, dtype=np.int64), jnp.int32)
    if none_wanted:
        key = jnp.full((P,), -1, jnp.int32)
    sizes = jnp.asarray(rng.integers(1, 9, P), jnp.float32)
    budget = jnp.float32(0.0 if zero_budget
                         else float(rng.integers(1, 4 * P)))
    pops = jnp.int32(int(rng.integers(1, 33)))
    vmax = int(rng.integers(1, P + 1))
    return key, sizes, budget, pops, vmax


def _assert_grant_agrees(key, sizes, budget, pops, vmax):
    g_mask, g_bytes, g_n = fifo_grant_kernel(key, sizes, budget, pops,
                                             vmax=vmax, interpret=True)
    w_mask, w_bytes, w_n = ref.fifo_grant_ref(key, sizes, budget, pops,
                                              vmax=vmax)
    np.testing.assert_array_equal(np.asarray(g_mask), np.asarray(w_mask))
    np.testing.assert_array_equal(np.asarray(g_bytes), np.asarray(w_bytes))
    np.testing.assert_array_equal(np.asarray(g_n), np.asarray(w_n))


def test_fifo_grant_fuzz_matches_ref_exactly():
    rng = np.random.default_rng(4)
    for P in (8, 100, 128, 200):
        _assert_grant_agrees(*_grant_case(rng, P))


def test_fifo_grant_edge_cases():
    rng = np.random.default_rng(5)
    _assert_grant_agrees(*_grant_case(rng, 64, zero_budget=True))
    _assert_grant_agrees(*_grant_case(rng, 64, none_wanted=True))
    _assert_grant_agrees(*_grant_case(rng, 1))


#: the cell's pool: 3,712 pages, 512 KiB apart from the columns' tail pages
CELL_P = 3712
PAGE = 512 * 1024


def _cell_pool(rng, *, distinct):
    """A queue over the cell's pool whose keys take ``distinct`` values
    (ties everywhere), and its service order: key descending, ties by
    the lower page index."""
    sizes = np.full(CELL_P, PAGE, np.int64)
    tails = rng.choice(CELL_P, 90, replace=False)
    sizes[tails] = rng.integers(1, PAGE // 4096, 90) * 4096
    values = rng.choice(2**29, distinct, replace=False)
    key = np.where(rng.random(CELL_P) < 0.2,
                   rng.choice(values, CELL_P), -1)
    wanted = np.flatnonzero(key >= 0)
    order = wanted[np.lexsort((wanted, -key[wanted]))]
    return (jnp.asarray(key, jnp.int32), jnp.asarray(sizes, jnp.float32),
            np.cumsum(sizes[order]))


@pytest.mark.parametrize("binds", ["first_head", "after_6", "never"])
@pytest.mark.parametrize("pops", [0, 6, 384])
def test_fifo_grant_cell_pool_matches_ref_exactly(pops, binds):
    """The grant pops heads of the queue until one does not fit: a budget
    that binds at the first head, after six heads, or never, against pop
    caps of none, the cell's six rounds and ``vmax``, over tie-heavy keys
    at the cell's pool."""
    rng = np.random.default_rng(21)
    key, sizes, csum = _cell_pool(rng, distinct=3)
    budget = {"first_head": csum[0] - 1, "after_6": csum[5],
              "never": 1e12}[binds]
    _assert_grant_agrees(key, sizes, jnp.float32(budget), jnp.int32(pops),
                         384)


@pytest.mark.parametrize("distinct", [3, 4])
def test_fifo_grant_ties_and_vmax_below_pops_match_ref_exactly(distinct):
    """Keys drawn from a few values: the grant takes the lower page index
    among equal keys; ``vmax`` below ``pops`` caps the pops."""
    rng = np.random.default_rng(22 + distinct)
    key, sizes, csum = _cell_pool(rng, distinct=distinct)
    for budget in (csum[40], 1e12):
        _assert_grant_agrees(key, sizes, jnp.float32(budget),
                             jnp.int32(384), 17)


def test_fifo_grant_vmapped_lanes_stop_on_their_own():
    """Under ``jax.vmap`` each lane is its own grid step: a lane's pops
    stop at its own budget and pop cap, whatever the other lanes grant."""
    rng = np.random.default_rng(26)
    pools = [_cell_pool(rng, distinct=4) for _ in range(4)]
    key = jnp.stack([p[0] for p in pools])
    sizes = jnp.stack([p[1] for p in pools])
    budget = jnp.asarray([pools[0][2][0] - 1, pools[1][2][1], 1e12, 1e12],
                         jnp.float32)
    pops = jnp.asarray([6, 6, 6, 384], jnp.int32)
    got = jax.vmap(functools.partial(fifo_grant_kernel, vmax=384,
                                     interpret=True))(key, sizes, budget,
                                                      pops)
    want = jax.vmap(functools.partial(ref.fifo_grant_ref, vmax=384))(
        key, sizes, budget, pops)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(got[2]), [0, 2, 6, 384])


def _sparse_blocks(rng, P, fill):
    """A mask that leaves page blocks 1 and 3 of a four-block pool empty:
    the kernels skip every tile those blocks touch."""
    mask = rng.random(P) < fill
    block = np.arange(P) // 512
    return mask & (block % 2 == 0)


@pytest.mark.parametrize("kernel", ["evict_f32", "evict_i32", "grant",
                                    "wake"])
def test_kernels_skip_empty_page_blocks_exactly(kernel):
    """Tiles whose row or column page block holds no candidate are
    skipped; the results must still equal the oracles exactly, on a
    ragged four-block pool where blocks 1 and 3 are empty."""
    rng = np.random.default_rng(11)
    P = 3 * 512 + 129
    sizes = jnp.asarray(rng.integers(1, 9, P) * 4096, jnp.float32)
    if kernel.startswith("evict"):
        if kernel == "evict_i32":
            key = jnp.asarray(rng.integers(0, 2**30, P), jnp.int32)
        else:
            key = jnp.asarray(rng.integers(-50, 50, P), jnp.float32)
        ev = jnp.asarray(_sparse_blocks(rng, P, 0.3))
        for need in (0.0, 40 * 4096.0, 1e9):
            _assert_evict_agrees(key, sizes, ev, jnp.float32(need), 64)
        return
    queued = _sparse_blocks(rng, P, 0.05)
    key = jnp.asarray(np.where(queued, rng.integers(0, 2**29, P), -1),
                      jnp.int32)
    if kernel == "grant":
        for budget in (0.0, 20 * 4096.0, 1e9):
            _assert_grant_agrees(key, sizes, jnp.float32(budget),
                                 jnp.int32(9), 16)
        return
    for credit0 in (0.0, 1e5, 1e7):
        got = wake_solve_kernel(key, sizes, jnp.float32(credit0),
                                jnp.float32(6e4), jnp.int32(3), h_cap=12,
                                interpret=True)
        want = ref.wake_solve_ref(key, sizes, jnp.float32(credit0),
                                  jnp.float32(6e4), jnp.int32(3), h_cap=12)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(
        P=st.integers(min_value=1, max_value=160),
        int_keys=st.booleans(),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_batched_evict_hypothesis(P, int_keys, seed):
        rng = np.random.default_rng(seed)
        _assert_evict_agrees(*_evict_case(rng, P, int_keys=int_keys))

    @settings(max_examples=25, deadline=None)
    @given(
        P=st.integers(min_value=1, max_value=160),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_fifo_grant_hypothesis(P, seed):
        rng = np.random.default_rng(seed)
        _assert_grant_agrees(*_grant_case(rng, P))
