"""Pallas kernels: batched buffer-pool ops for the array simulation.

The hot inner operations of the array-native buffer-manager simulation
(`repro.core.array_sim`): eviction-victim selection, the serial
I/O-server FIFO grant, and the wake-solve (serial-server grant
schedule) that lets the event-horizon stepper macro-jump inside the
supersaturated regime.  The *policy* is entirely encoded in the ``key``
input — the score array an
:class:`repro.core.array_sim.policies.ArrayPolicy` computed for this
step (PBM's shifted bucketed timeline, LRU's age, OPT's exact next-use
distance, CScan's keep-relevance) — so a single kernel serves every
registered policy and a vmapped sweep can mix policies per lane by
selecting between their score arrays.

Design notes
------------
* All per-page state is dense ``(1, P)`` rows; wrappers pad P up to a
  multiple of ``_BLOCK`` (the grant's to a multiple of ``_DENSE``) with
  exact sentinels (non-wanted key, zero size, non-evictable) and slice
  the padding back off, so any P works and every BlockSpec divides its
  operand.
* Victim selection and the wake solve are prefix sums over the priority
  order.  Instead of sorting (awkward on the VPU), we compute for every
  page the bytes that would be freed/served *before* it via a masked
  comparison tile contracted against page sizes on the MXU.
* That O(P^2) prefix work is **gridded over page blocks**: grid
  ``(i, j)`` walks (row-block, col-block) tiles of the comparison
  matrix with j innermost, accumulating per-row prefix bytes and ranks
  in VMEM scratch (reset at ``j == 0``, committed under
  ``pl.when(j == n_j - 1)`` — the sanctioned accumulator-revisit
  pattern).  Per-step VMEM is O(_BLOCK^2) regardless of P, so
  P >> VMEM satisfies the contract verifier's vmem-budget rule.
  The wake kernel's passes that need a *global* intermediate (per-page
  rank/prefix bytes) run as an extra leading phase axis: TPU grids are
  sequential, so phase 0 fully populates the (1, P_pad) scratch before
  phase 1 reads it.
* A tile whose row or column page block holds no candidate (queued or
  evictable page) adds exactly zero, so the kernels skip it under
  ``pl.when`` on a per-block occupancy row passed in SMEM.
* The grant needs no prefix over the whole queue: it is a prefix of the
  service order that stops at the first head that does not fit, and a
  call grants a handful of pages.  So it runs one grid step per lane
  (``jax.vmap`` adds the lane axis) over the lane's key and size rows
  held whole in VMEM, dense as ``(P_pad / 128, 128)``, and pops heads —
  a max over the keys, the lowest page index among the ties, a masked
  sum for the size — until one is not wanted, exceeds the pop cap or
  overruns the byte budget.  Its work is one vector pass per page
  granted plus one; its VMEM is O(P): 24 bytes a page, double-buffered.

Semantics are defined by the oracles in ``repro.kernels.ref``
(``batched_evict_ref`` / ``fifo_grant_ref`` / ``wake_solve_ref``);
tests assert exact agreement in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30  # plain float: a jnp scalar would be a captured constant
NEG_I32 = -(2**31) + 1  # i32 sentinel for the integer-key path

#: page-block width of the gridded kernels — each grid step touches an
#: O(_BLOCK^2) comparison tile, so VMEM stays bounded for any P
_BLOCK = 512

#: lanes of a vreg; the grant kernel lays a pool row out as (rows, 128),
#: padded to a multiple of _DENSE pages so the rows fill whole (8, 128)
#: vregs
_LANES = 128
_DENSE = 8 * _LANES


def _blocks(P: int) -> tuple[int, int]:
    p_pad = -(-P // _BLOCK) * _BLOCK
    return p_pad, p_pad // _BLOCK


def _pad_row(row: jax.Array, p_pad: int, fill) -> jax.Array:
    pad = p_pad - row.shape[-1]
    if pad == 0:
        return row
    return jnp.pad(row, ((0, 0), (0, pad)), constant_values=fill)


def _iota_f32(shape, dim: int) -> jax.Array:
    # Mosaic's iota yields integers only: build it as i32, then cast
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.float32)


def _prefix_dot(mask: jax.Array, col: jax.Array) -> jax.Array:
    # 0/1 mask contracted against f32 page bytes on the MXU.  HIGHEST keeps
    # every byte of the f32 operand: at the default precision the chip
    # rounds it to bf16, which cannot hold every page size exactly
    return jnp.dot(mask.astype(jnp.float32), col,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _order_before(key_p, key_q, i, j, block: int) -> jax.Array:
    """One (block, block) tile of the priority order: does column page q
    come before row page p?  Descending key, ties by ascending global
    page index."""
    gq = j * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    gp = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    return (key_q > key_p) | ((key_q == key_p) & (gq < gp))


def _occupied(mask_row: jax.Array, n_b: int) -> jax.Array:
    """(1, n_b) i32: does page block b hold any page of ``mask_row``?
    Tiles whose row or column block holds none add nothing to the prefix
    sums, so the kernels skip them."""
    return jnp.any(mask_row.reshape(n_b, _BLOCK), axis=1).astype(
        jnp.int32).reshape(1, n_b)


def _kernel(fscal_ref, occ_ref, key_i_ref, key_j_ref, sizes_j_ref, ev_i_ref,
            ev_j_ref, evict_out_ref, freed_acc_ref, rank_acc_ref,
            *, vmax: int, block: int, n_j: int, int_key: bool = False):
    i = pl.program_id(0)
    j = pl.program_id(1)
    need_free = fscal_ref[0, 0]

    @pl.when(j == 0)
    def _init():
        freed_acc_ref[...] = jnp.zeros_like(freed_acc_ref)
        rank_acc_ref[...] = jnp.zeros_like(rank_acc_ref)

    ev_i = ev_i_ref[:]                # (1, block) f32 0/1 — the row pages p

    # nothing is taken while nothing needs freeing, and a block without
    # evictable pages holds only sentinel keys, which precede no
    # evictable page: such tiles leave the accumulators as they are
    @pl.when((need_free > 0) & (occ_ref[0, i] > 0) & (occ_ref[0, j] > 0))
    def _acc():
        ev_j = ev_j_ref[:]            # (1, block): candidate predecessors q
        neg = NEG_I32 if int_key else NEG
        key_p = jnp.where(ev_i > 0, key_i_ref[:], neg).reshape(block, 1)
        key_q = jnp.where(ev_j > 0, key_j_ref[:], neg)
        before = _order_before(key_p, key_q, i, j, block)
        sz = (sizes_j_ref[:] * ev_j).reshape(block, 1)
        freed_acc_ref[...] = freed_acc_ref[...] + _prefix_dot(
            before, sz).reshape(1, block)    # bytes freed before page p
        rank_acc_ref[...] = rank_acc_ref[...] + jnp.sum(
            before, axis=1, dtype=jnp.float32,
        ).reshape(1, block)

    @pl.when(j == n_j - 1)
    def _commit():
        # candidate cap: page p participates only if fewer than vmax pages
        # precede it in priority order (== membership of the oracle's top_k)
        take = (
            (ev_i > 0)
            & (freed_acc_ref[...] < need_free)
            & (rank_acc_ref[...] < vmax)
            & (need_free > 0)
        )
        evict_out_ref[...] = take.astype(jnp.float32)


def _grant_kernel(iscal_ref, fscal_ref, key_ref, sizes_ref, grant_out_ref,
                  *, vmax: int):
    cap = jnp.minimum(iscal_ref[0, 0], vmax)
    budget = fscal_ref[0, 0]
    key0 = key_ref[...]               # (rows, 128) i32 keys — the FIFO keys
    sizes = sizes_ref[...]            # use up to ~30 bits (stamp*32768 +
                                      # tie), so an f32 cast would round
                                      # away the tie bits beyond 2^24
    idx = (jax.lax.broadcasted_iota(jnp.int32, key0.shape, 0) * _LANES
           + jax.lax.broadcasted_iota(jnp.int32, key0.shape, 1))
    p_pad = key0.size

    def pop(carry):
        # one head of the queue: the highest wanted key, ties by the lower
        # page index; it is granted only if it fits, else it blocks the rest
        _, n, nbytes, key = carry
        top = jnp.max(key)
        head = idx == jnp.min(jnp.where(key == top, idx, p_pad))
        size = jnp.sum(jnp.where(head, sizes, 0.0))
        fits = (top >= 0) & (n < cap) & (nbytes + size <= budget)
        return (fits, n + fits.astype(jnp.int32),
                jnp.where(fits, nbytes + size, nbytes),
                jnp.where(head & fits, -1, key))   # a granted page leaves

    start = (cap > 0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32),
             key0)
    key = jax.lax.while_loop(lambda carry: carry[0], pop, start)[3]
    grant_out_ref[...] = (key != key0).astype(jnp.int32)


def _wake_kernel(iscal_ref, fscal_ref, occ_ref, key_i_ref, key_j_ref,
                 sizes_i_ref, sizes_j_ref, wake_out_ref,
                 csum_ref, rank_ref, bytes_acc_ref, rank_acc_ref,
                 cnt_ref, nk_ref,
                 *, h_cap: int, block: int, n_j: int):
    ph = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    pops = iscal_ref[0, 0]
    credit0 = fscal_ref[0, 0]
    inc = fscal_ref[0, 1]

    wanted_p = key_i_ref[:] >= 0
    wanted_q = key_j_ref[:] >= 0

    # ---- phase 0: service rank + prefix-inclusive queue bytes per page ---
    @pl.when((ph == 0) & (j == 0))
    def _init_prefix():
        bytes_acc_ref[...] = jnp.zeros_like(bytes_acc_ref)
        rank_acc_ref[...] = jnp.zeros_like(rank_acc_ref)

    # tiles whose row or column block holds no queued page add nothing
    @pl.when((ph == 0) & (occ_ref[0, i] > 0) & (occ_ref[0, j] > 0))
    def _acc_prefix():
        before = _order_before(key_i_ref[:].reshape(block, 1), key_j_ref[:],
                               i, j, block) & wanted_q
        sz = (sizes_j_ref[:] * wanted_q).reshape(block, 1)
        bytes_acc_ref[...] = bytes_acc_ref[...] + _prefix_dot(
            before, sz).reshape(1, block)
        rank_acc_ref[...] = rank_acc_ref[...] + jnp.sum(
            before, axis=1, dtype=jnp.float32,
        ).reshape(1, block)

    @pl.when((ph == 0) & (j == n_j - 1))
    def _store_prefix():
        own = sizes_i_ref[:] * wanted_p
        csum_ref[0, pl.ds(i * block, block)] = \
            (bytes_acc_ref[...] + own).reshape(block)
        rank_ref[0, pl.ds(i * block, block)] = rank_acc_ref[...].reshape(block)

    # ---- phase 1: grants the banked credit alone allows after k steps ----
    @pl.when((ph == 1) & (i == 0) & (j == 0))
    def _init_cnt():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when((ph == 1) & (i == 0) & (occ_ref[0, j] > 0))
    def _acc_cnt():
        cs = csum_ref[0, pl.ds(j * block, block)].reshape(1, block)
        ks = _iota_f32((h_cap, block), 0) + 1.0
        ok = wanted_q & (cs <= credit0 + ks * inc)
        cnt_ref[...] = cnt_ref[...] + jnp.sum(
            ok, axis=1, dtype=jnp.float32,
        ).reshape(h_cap, 1)

    # ---- phase 2: pop-rate recursion, then per-page wake step ------------
    # n_k = min(cnt_k, n_{k-1} + pops) unrolled to
    # min(min_{1<=jj<=k}(cnt_jj + (k-jj)*pops), k*pops) — one (h_cap, h_cap)
    # min-plus tile instead of a sequential scan
    @pl.when((ph == 2) & (i == 0) & (j == 0))
    def _solve_ramp():
        popf = jnp.maximum(pops, 0).astype(jnp.float32)
        kk = _iota_f32((h_cap, h_cap), 0) + 1.0
        jj = _iota_f32((h_cap, h_cap), 1) + 1.0
        gap = kk - jj
        ramp = jnp.where(
            gap >= 0, cnt_ref[...].reshape(1, h_cap) + gap * popf, jnp.inf)
        ks = _iota_f32((h_cap, 1), 0) + 1.0
        nk_ref[...] = jnp.minimum(
            jnp.min(ramp, axis=1).reshape(h_cap, 1), ks * popf)

    @pl.when((ph == 2) & (j == n_j - 1))
    def _commit():
        rk = rank_ref[0, pl.ds(i * block, block)].reshape(1, block)
        step = 1.0 + jnp.sum(
            nk_ref[...] < (rk + 1.0), axis=0, dtype=jnp.float32,
        ).reshape(1, block)
        wake_out_ref[...] = jnp.where(
            wanted_p, step, float(h_cap + 1)).astype(jnp.int32)


def fifo_grant_kernel(
    key: jax.Array,          # (P,) i32 queue priority (-1 = not wanted)
    sizes: jax.Array,        # (P,) f32
    budget: jax.Array,       # () f32
    pops: jax.Array,         # () i32
    *,
    vmax: int = 16,
    interpret: bool = False,
):
    """Budgeted FIFO grant selection (the array sim's I/O server pop) by
    head-of-queue extraction: one grid step holds the lane's key and size
    rows whole in VMEM, laid out dense as ``(P_pad / 128, 128)``, and
    pops heads until the first that does not fit, so a call does one
    vector pass per page granted plus one.  Returns ``(grant_mask,
    granted_bytes, n_granted)``; semantics defined by
    ``ref.fifo_grant_ref`` (tests assert exact agreement in interpret
    mode)."""
    P = key.shape[0]
    p_pad = -(-P // _DENSE) * _DENSE
    rows = p_pad // _LANES
    key_2d = _pad_row(key.reshape(1, P).astype(jnp.int32), p_pad,
                      -1).reshape(rows, _LANES)
    sz_2d = _pad_row(sizes.reshape(1, P).astype(jnp.float32), p_pad,
                     0.0).reshape(rows, _LANES)
    iscal = jnp.asarray(pops, jnp.int32).reshape(1, 1)
    fscal = jnp.asarray(budget, jnp.float32).reshape(1, 1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    whole = pl.BlockSpec((rows, _LANES), lambda: (0, 0),
                         memory_space=pltpu.VMEM)
    grant = pl.pallas_call(
        functools.partial(_grant_kernel, vmax=min(vmax, P)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        in_specs=[smem, smem, whole, whole],
        out_specs=whole,
        interpret=interpret,
        name="kernel_fifo_grant",
    )(iscal, fscal, key_2d, sz_2d)
    mask = grant.reshape(p_pad)[:P] > 0
    granted = jnp.where(mask, sizes, 0.0)
    return mask, jnp.sum(granted), jnp.sum(mask)


def batched_evict_kernel(
    key: jax.Array,          # (P,) f32 OR int policy score (higher = first)
    sizes: jax.Array,        # (P,) f32
    evictable: jax.Array,    # (P,) bool
    need_free: jax.Array,    # () f32
    *,
    vmax: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Batched evict selection over a policy score array, gridded over
    (row, col) page blocks.  Returns the ``(P,) bool`` evict mask.

    Integer score arrays (array-OPT's exact next-use distances) ride an
    i32 path end to end: an unconditional f32 cast would round away key
    bits beyond 2^24 (f32 carries a 24-bit mantissa), silently merging
    distinct priorities exactly like the FIFO-tie trap documented on
    ``fifo_grant_kernel`` — the kernel verifier's
    ``kernel-float-mantissa-cast`` rule pins this dispatch."""
    P = key.shape[0]
    p_pad, n_b = _blocks(P)
    int_key = bool(jnp.issubdtype(key.dtype, jnp.integer))
    if int_key:
        key_row = _pad_row(key.reshape(1, P).astype(jnp.int32), p_pad, NEG_I32)
    else:
        key_row = _pad_row(key.reshape(1, P).astype(jnp.float32), p_pad, NEG)
    sz_row = _pad_row(sizes.reshape(1, P).astype(jnp.float32), p_pad, 0.0)
    ev_row = _pad_row(
        evictable.reshape(1, P).astype(jnp.float32), p_pad, 0.0)
    fscal = jnp.asarray(need_free, jnp.float32).reshape(1, 1)
    occ = _occupied(ev_row > 0, n_b)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    row_i = pl.BlockSpec((1, _BLOCK), lambda i, j: (0, i),
                         memory_space=pltpu.VMEM)
    row_j = pl.BlockSpec((1, _BLOCK), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM)
    evict = pl.pallas_call(
        functools.partial(_kernel, vmax=min(vmax, P), block=_BLOCK,
                          n_j=n_b, int_key=int_key),
        grid=(n_b, n_b),
        out_shape=jax.ShapeDtypeStruct((1, p_pad), jnp.float32),
        in_specs=[smem, smem, row_i, row_j, row_j, row_i, row_j],
        out_specs=pl.BlockSpec((1, _BLOCK), lambda i, j: (0, i),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((1, _BLOCK), jnp.float32),  # freed-before accumulator
            pltpu.VMEM((1, _BLOCK), jnp.float32),  # rank accumulator
        ],
        interpret=interpret,
        name="kernel_batched_evict",
    )(fscal, occ, key_row, key_row, sz_row, ev_row, ev_row)
    return evict[0, :P] > 0


def wake_solve_kernel(
    key: jax.Array,          # (P,) i32 queue priority (-1 = not wanted)
    sizes: jax.Array,        # (P,) f32
    credit0: jax.Array,      # () f32 banked io-credit
    inc: jax.Array,          # () f32 credit bytes per fine step
    pops: jax.Array,         # () i32 max pops per fine step
    *,
    h_cap: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Per-page grant step of the frozen serial I/O server (the
    event-horizon stepper's wake-exact queue model), page-blocked.

    Grid = (phase, i, j): phase 0 writes every page's service rank and
    prefix-inclusive queue bytes into global scratch, phase 1 folds them
    into per-step feasible grant counts ``cnt_k``, phase 2 solves the
    pop-rate recursion ``n_k = min(cnt_k, n_{k-1} + pops)`` as one
    min-plus tile and emits each page's first ``k`` with
    ``n_k >= rank + 1``.  Returns ``(P,) i32`` in ``1..h_cap`` with
    sentinel ``h_cap + 1``; semantics defined by ``ref.wake_solve_ref``
    (tests assert exact agreement in interpret mode)."""
    P = key.shape[0]
    p_pad, n_b = _blocks(P)
    key_row = _pad_row(key.reshape(1, P).astype(jnp.int32), p_pad, -1)
    sz_row = _pad_row(sizes.reshape(1, P).astype(jnp.float32), p_pad, 0.0)
    iscal = jnp.asarray(pops, jnp.int32).reshape(1, 1)
    fscal = jnp.stack([
        jnp.asarray(credit0, jnp.float32), jnp.asarray(inc, jnp.float32),
    ]).reshape(1, 2)
    occ = _occupied(key_row >= 0, n_b)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    row_i = pl.BlockSpec((1, _BLOCK), lambda p, i, j: (0, i),
                         memory_space=pltpu.VMEM)
    row_j = pl.BlockSpec((1, _BLOCK), lambda p, i, j: (0, j),
                         memory_space=pltpu.VMEM)
    wake = pl.pallas_call(
        functools.partial(_wake_kernel, h_cap=h_cap, block=_BLOCK, n_j=n_b),
        grid=(3, n_b, n_b),
        out_shape=jax.ShapeDtypeStruct((1, p_pad), jnp.int32),
        in_specs=[smem, smem, smem, row_i, row_j, row_i, row_j],
        out_specs=pl.BlockSpec((1, _BLOCK), lambda p, i, j: (0, i),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((1, p_pad), jnp.float32),    # prefix bytes (global)
            pltpu.VMEM((1, p_pad), jnp.float32),    # service rank (global)
            pltpu.VMEM((1, _BLOCK), jnp.float32),   # prefix-bytes accumulator
            pltpu.VMEM((1, _BLOCK), jnp.float32),   # rank accumulator
            pltpu.VMEM((h_cap, 1), jnp.float32),    # cnt_k
            pltpu.VMEM((h_cap, 1), jnp.float32),    # n_k
        ],
        interpret=interpret,
        name="kernel_wake_solve",
    )(iscal, fscal, occ, key_row, key_row, sz_row, sz_row)
    return wake[0, :P]
