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
  multiple of ``_BLOCK`` (the grant's and the wake solve's to a
  multiple of ``_DENSE``) with exact sentinels (non-wanted key, zero
  size, non-evictable) and slice the padding back off, so any P works
  and every BlockSpec divides its operand.
* Victim selection is a prefix sum over the priority order.  Instead of
  sorting (awkward on the VPU), we compute for every page the bytes
  that would be freed *before* it via a masked comparison tile
  contracted against page sizes on the MXU.
* That O(P^2) prefix work is **gridded over page blocks**: grid
  ``(i, j)`` walks (row-block, col-block) tiles of the comparison
  matrix with j innermost, accumulating per-row prefix bytes and ranks
  in VMEM scratch (reset at ``j == 0``, committed under
  ``pl.when(j == n_j - 1)`` — the sanctioned accumulator-revisit
  pattern).  Per-step VMEM is O(_BLOCK^2) regardless of P, so
  P >> VMEM satisfies the contract verifier's vmem-budget rule.
* A tile whose row or column page block holds no evictable page adds
  exactly zero, so the kernel skips it under ``pl.when`` on a per-block
  occupancy row passed in SMEM.
* The grant and the wake solve need no prefix over the whole queue:
  each reads a prefix of the service order that ends at the first head
  past a limit, and a call takes a handful of pages.  So each runs one
  grid step per lane (``jax.vmap`` adds the lane axis) over the lane's
  key and size rows held whole in VMEM, dense as ``(P_pad / 128,
  128)``, and pops heads — a max over the keys, the lowest page index
  among the ties, a masked sum for the size.  The grant stops at a head
  that is not wanted, exceeds the pop cap or overruns the byte budget;
  the wake solve at an empty queue, at rank ``h_cap * pops`` or at a
  head whose bytes no step's credit covers, so a call with ``pops = 0``
  pops nothing.  The work is one vector pass per page popped plus one;
  VMEM is O(P): 24 bytes a page, double-buffered.

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


def _dense_index(shape) -> jax.Array:
    """Page index of each slot of a dense ``(rows, 128)`` pool row."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _head(key, idx, sizes):
    """The head of the queue in service order: the highest key, ties by
    the lower page index.  Returns its key, its one-hot mask and its
    size."""
    top = jnp.max(key)
    head = idx == jnp.min(jnp.where(key == top, idx, key.size))
    return top, head, jnp.sum(jnp.where(head, sizes, 0.0))


def _grant_kernel(iscal_ref, fscal_ref, key_ref, sizes_ref, grant_out_ref,
                  *, vmax: int):
    cap = jnp.minimum(iscal_ref[0, 0], vmax)
    budget = fscal_ref[0, 0]
    key0 = key_ref[...]               # (rows, 128) i32 keys — the FIFO keys
    sizes = sizes_ref[...]            # use up to ~30 bits (stamp*32768 +
                                      # tie), so an f32 cast would round
                                      # away the tie bits beyond 2^24
    idx = _dense_index(key0.shape)

    def pop(carry):
        # the head is granted only if it is wanted and fits, else it
        # blocks the rest
        _, n, nbytes, key = carry
        top, head, size = _head(key, idx, sizes)
        fits = (top >= 0) & (n < cap) & (nbytes + size <= budget)
        return (fits, n + fits.astype(jnp.int32),
                jnp.where(fits, nbytes + size, nbytes),
                jnp.where(head & fits, -1, key))   # a granted page leaves

    start = (cap > 0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32),
             key0)
    key = jax.lax.while_loop(lambda carry: carry[0], pop, start)[3]
    grant_out_ref[...] = (key != key0).astype(jnp.int32)


def _wake_kernel(iscal_ref, fscal_ref, key_ref, sizes_ref, wake_out_ref,
                 *, h_cap: int):
    pops = jnp.maximum(iscal_ref[0, 0], 0)
    credit0 = fscal_ref[0, 0]
    inc = fscal_ref[0, 1]
    key0 = key_ref[...]               # (rows, 128) i32 FIFO keys (~30 bits)
    sizes = sizes_ref[...]
    idx = _dense_index(key0.shape)
    p_pad = key0.size
    # the credit banked after k fine steps, k = 1..h_cap; no page whose
    # prefix-inclusive bytes exceed all of them is granted within h_cap
    bounds = credit0 + (_iota_f32((1, h_cap), 1) + 1.0) * inc
    reach = jnp.max(bounds)
    # n_k <= k * pops: no page at or past rank h_cap * pops is granted
    limit = jnp.minimum(pops, p_pad) * h_cap

    def pop(carry):
        # the head takes the next rank while it is wanted and its
        # prefix-inclusive bytes stay within reach
        _, n, nbytes, key, rank, cnt = carry
        top, head, size = _head(key, idx, sizes)
        csum = nbytes + size
        take = (top >= 0) & (csum <= reach)
        return (take & (n + 1 < limit), n + take.astype(jnp.int32), csum,
                jnp.where(head & take, -1, key),
                jnp.where(head & take, n, rank),
                cnt + (take & (csum <= bounds)).astype(jnp.float32))

    # rank p_pad (past every n_k) marks a page not popped: its step is the
    # sentinel h_cap + 1, as is every page on a zero-pop call
    start = (limit > 0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32),
             key0, jnp.full(key0.shape, p_pad, jnp.int32),
             jnp.zeros((1, h_cap), jnp.float32))
    _, n, _, _, rank, cnt = jax.lax.while_loop(
        lambda carry: carry[0], pop, start)
    wake_out_ref[...] = jnp.full(key0.shape, h_cap + 1, jnp.int32)

    @pl.when(n > 0)
    def _solve():
        # pop-rate recursion n_k = min(cnt_k, n_{k-1} + pops) unrolled to
        # min(min_{1<=j<=k}(cnt_j + (k-j)*pops), k*pops) — one
        # (h_cap, h_cap) min-plus tile instead of a sequential scan
        popf = pops.astype(jnp.float32)
        gap = _iota_f32((h_cap, h_cap), 0) - _iota_f32((h_cap, h_cap), 1)
        ramp = jnp.where(gap >= 0, cnt + gap * popf, jnp.inf)
        ks = _iota_f32((h_cap, 1), 0) + 1.0
        nk = jnp.minimum(jnp.min(ramp, axis=1, keepdims=True), ks * popf)
        kcol = jax.lax.broadcasted_iota(jnp.int32, (h_cap, 1), 0)
        rank1 = rank.astype(jnp.float32) + 1.0

        def later(k, acc):
            # each step k whose n_k stays short of rank + 1 delays the page
            nk_k = jnp.sum(jnp.where(kcol == k, nk, 0.0))
            return acc + (nk_k < rank1).astype(jnp.int32)

        wake_out_ref[...] = 1 + jax.lax.fori_loop(
            0, h_cap, later, jnp.zeros(key0.shape, jnp.int32))


def _queue_call(kernel, name: str, iscal, fscal, key, sizes,
                interpret: bool) -> jax.Array:
    """One grid step of a head-of-queue kernel over a lane's (P,) key and
    size rows, padded to a multiple of ``_DENSE`` with exact sentinels
    (key -1, size 0) and held whole in VMEM, dense as ``(P_pad / 128,
    128)``; ``iscal`` (i32) and ``fscal`` (f32) ride SMEM.  Returns the
    kernel's (P,) i32 row."""
    P = key.shape[0]
    p_pad = -(-P // _DENSE) * _DENSE
    rows = p_pad // _LANES
    key_2d = _pad_row(key.reshape(1, P).astype(jnp.int32), p_pad,
                      -1).reshape(rows, _LANES)
    sz_2d = _pad_row(sizes.reshape(1, P).astype(jnp.float32), p_pad,
                     0.0).reshape(rows, _LANES)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    whole = pl.BlockSpec((rows, _LANES), lambda: (0, 0),
                         memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        in_specs=[smem, smem, whole, whole],
        out_specs=whole,
        interpret=interpret,
        name=name,
    )(iscal, fscal, key_2d, sz_2d)
    return out.reshape(p_pad)[:P]


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
    grant = _queue_call(
        functools.partial(_grant_kernel, vmax=min(vmax, key.shape[0])),
        "kernel_fifo_grant", jnp.asarray(pops, jnp.int32).reshape(1, 1),
        jnp.asarray(budget, jnp.float32).reshape(1, 1), key, sizes,
        interpret)
    mask = grant > 0
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
    event-horizon stepper's wake-exact queue model) by head-of-queue
    extraction: one grid step holds the lane's key and size rows whole
    in VMEM, dense as ``(P_pad / 128, 128)``, and pops heads in service
    order, recording each one's rank and its prefix-inclusive bytes'
    share of the per-step feasible counts ``cnt_k``.  It stops at an
    empty queue, at rank ``h_cap * pops`` or at the first head whose
    bytes exceed ``credit0 + h_cap * inc``: no page behind it is granted
    within ``h_cap`` steps.  Then it solves the pop-rate recursion
    ``n_k = min(cnt_k, n_{k-1} + pops)`` as one min-plus tile and gives
    each popped page the first ``k`` with ``n_k >= rank + 1``.  A call
    with ``pops = 0`` pops nothing.  Returns ``(P,) i32`` in
    ``1..h_cap`` with sentinel ``h_cap + 1``; semantics defined by
    ``ref.wake_solve_ref`` (tests assert exact agreement in interpret
    mode)."""
    fscal = jnp.stack([
        jnp.asarray(credit0, jnp.float32), jnp.asarray(inc, jnp.float32),
    ]).reshape(1, 2)
    return _queue_call(
        functools.partial(_wake_kernel, h_cap=h_cap), "kernel_wake_solve",
        jnp.asarray(pops, jnp.int32).reshape(1, 1), fscal, key, sizes,
        interpret)
