"""jit'd dispatch wrappers for the Pallas kernels.

Backend policy (process-global, settable):
  * "auto"      — Pallas on TPU, jnp reference elsewhere (CPU dry-run/test)
  * "pallas"    — force the compiled Pallas path (real TPU)
  * "interpret" — Pallas kernel body interpreted in Python (CPU correctness)
  * "reference" — force the jnp oracle

The model code calls these wrappers, so swapping kernels on/off never touches
model definitions — and the dry-run lowers the reference path (XLA HLO),
which is what cost_analysis reads.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import obs
from . import ref
from .flash_attention import flash_attention_kernel
from .mamba2_scan import mamba2_scan_kernel
from .mlstm import mlstm_chunked_kernel
from .paged_attention import paged_attention_kernel
from .pbm_timeline import (
    batched_evict_kernel,
    fifo_grant_kernel,
    wake_solve_kernel,
)

_BACKEND = "auto"
#: the known backend names; set_backend validates eagerly so a typo
#: fails at the call site with the valid list (the policy registry's
#: unknown-name UX), not later at dispatch inside a traced step
BACKENDS = ("auto", "pallas", "interpret", "reference")


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {name!r}; valid backends: "
            f"{sorted(BACKENDS)} (see repro.kernels.ops)"
        )
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def _use_pallas() -> Optional[bool]:
    """True = compiled pallas, False = reference, None -> interpret."""
    if _BACKEND == "pallas":
        return True
    if _BACKEND == "reference":
        return False
    if _BACKEND == "interpret":
        return None
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=())
def paged_attention(q, k_pages, v_pages, page_table, seq_lens):
    mode = _use_pallas()
    if mode is True:
        return paged_attention_kernel(q, k_pages, v_pages, page_table, seq_lens)
    if mode is None:
        return paged_attention_kernel(
            q, k_pages, v_pages, page_table, seq_lens, interpret=True
        )
    return ref.paged_attention_ref(q, k_pages, v_pages, page_table, seq_lens)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None):
    mode = _use_pallas()
    if mode is True:
        return flash_attention_kernel(q, k, v, causal=causal, window=window)
    if mode is None:
        return flash_attention_kernel(
            q, k, v, causal=causal, window=window,
            block_q=64, block_kv=64, interpret=True,
        )
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("chunk",))
def mamba2_scan(xh, a, b, c, chunk: int = 128):
    mode = _use_pallas()
    if mode is True:
        return mamba2_scan_kernel(xh, a, b, c, chunk=chunk)
    if mode is None:
        return mamba2_scan_kernel(xh, a, b, c, chunk=chunk, interpret=True)
    y, _ = ref.mamba2_scan_ref(xh, a, b, c)
    return y


# ---------------------------------------------------------------- sharding --
#
# Page-axis sharding (make_runner 2-axis mesh): per-page state is
# replicated across the page axis, but each shard only *scans* its own
# P/n slice of the pool for candidates — the O(P) candidate selection
# divides across the mesh, and the vmax-bounded prefix solve runs on the
# gathered compact candidate list.  The construction is reduction-safe
# by being bitwise-identical to the unsharded oracle: a page in the
# global top-vmax is necessarily in its own shard's local top-vmax, and
# re-ordering the gathered candidates by ascending global index before a
# stable top_k reproduces the exact (key desc, index asc) service order
# — so the f32 prefix sums visit identical values in identical order.


def _page_shard_candidates(key, aux, axis: str, vmax: int):
    """Local top-``vmax`` per page shard, gathered and re-ordered into
    the exact global service order.

    Returns ``(kv, gidx, *aux_vals)`` flattened over shards and sorted
    ascending by global index (so a stable ``top_k`` on ``kv`` resolves
    ties exactly like the unsharded oracle's)."""
    P = key.shape[0]
    aux = list(aux)
    n = int(jax.lax.psum(1, axis))
    if P % n:
        raise ValueError(
            f"page axis {axis!r} has {n} shards which do not divide the "
            f"padded pool size P={P}")
    p_loc = P // n
    start = jax.lax.axis_index(axis) * p_loc
    k_loc = jax.lax.dynamic_slice(key, (start,), (p_loc,))
    _, cand = jax.lax.top_k(k_loc, min(vmax, p_loc))
    rows = [k_loc[cand], cand + start]
    rows += [jax.lax.dynamic_slice(a, (start,), (p_loc,))[cand] for a in aux]
    gathered = [jax.lax.all_gather(r, axis).reshape(-1) for r in rows]
    order = jnp.argsort(gathered[1])
    return [g[order] for g in gathered]


def _grant_page_sharded(key, sizes, budget, pops, vmax: int, axis: str):
    kv, gidx, sz = _page_shard_candidates(key, [sizes], axis, vmax)
    take = min(vmax, kv.shape[0])
    kv_top, pos = jax.lax.top_k(kv, take)
    sz_c = sz[pos]
    csum = jnp.cumsum(sz_c)
    ok = jnp.cumprod(
        ((kv_top >= 0) & (csum <= budget)
         & (jnp.arange(take) < pops)).astype(jnp.int32)
    ).astype(bool)
    mask = jnp.zeros((key.shape[0],), bool).at[gidx[pos]].set(ok)
    return mask, jnp.sum(jnp.where(ok, sz_c, 0.0)), jnp.sum(ok)


def _evict_page_sharded(key, sizes, evictable, need_free, vmax: int,
                        axis: str):
    if jnp.issubdtype(key.dtype, jnp.integer):
        keym = jnp.where(evictable, key, jnp.iinfo(key.dtype).min)
    else:
        keym = jnp.where(evictable, key, -jnp.inf)
    kv, gidx, sz, ev = _page_shard_candidates(
        keym, [sizes, evictable], axis, vmax)
    take = min(vmax, kv.shape[0])
    _, pos = jax.lax.top_k(kv, take)
    c_ok = ev[pos]
    sz_c = jnp.where(c_ok, sz[pos], 0.0)
    csum = jnp.cumsum(sz_c)
    take_mask = c_ok & (csum - sz_c < need_free) & (need_free > 0)
    return jnp.zeros((key.shape[0],), bool).at[gidx[pos]].set(take_mask)


def fifo_grant(key, sizes, budget, pops, *, vmax: int = 16,
               page_axis: Optional[str] = None):
    """Budgeted FIFO grant over the request-queue key array (the array
    sim's serial I/O server pop, macro-step sized).

    The service order is fully encoded in ``key`` (stamp-FIFO with
    policy-provided cohort ties, -1 = not wanted); strict head-of-line
    admission against ``budget`` bytes and ``pops`` pops.  Called from
    inside the already-jitted event-horizon step, so no jit wrapper;
    backend policy picks the Mosaic kernel on TPU and the jnp oracle
    (one ``top_k`` + prefix product) elsewhere.

    With ``page_axis`` (inside a page-sharded ``shard_map`` body) each
    shard scans only its own P/n pool slice for candidates and the
    prefix solve runs on the gathered compact list — bitwise-identical
    to the unsharded path (see the sharding note above).

    The ``kernel:fifo_grant`` scope (``repro.obs.scope``) names this op
    in the compiled HLO's ``op_name`` metadata and in profiler captures,
    inside the step phase that calls it; ``bench/trace.py`` attributes
    device time to it and ``bench/kernel_work.py`` prices its calls."""
    with obs.scope("kernel:fifo_grant"):
        if page_axis is not None:
            return _grant_page_sharded(key, sizes, budget, pops, vmax,
                                       page_axis)
        mode = _use_pallas()
        if mode is not False:
            return fifo_grant_kernel(
                key, sizes, budget, pops, vmax=vmax, interpret=(mode is None),
            )
        return ref.fifo_grant_ref(key, sizes, budget, pops, vmax=vmax)


def batched_evict(key, sizes, evictable, need_free, *, vmax: int = 64,
                  page_axis: Optional[str] = None):
    """Batched evict selection over a policy score array (array-sim core).

    The eviction policy is fully encoded in ``key`` — the
    ``ArrayPolicy.score_victims`` output for this step — so this one op
    serves LRU, PBM, CScan, OPT, and any future registered policy.
    Integer score arrays (exact Belady next-use distances) are honoured
    bit-exactly: both the kernel and the oracle keep them on an integer
    path instead of an f32 cast that would collapse keys beyond 2^24.
    Called from inside the already-jitted ``array_sim`` step, so no jit
    wrapper here; backend policy picks the Mosaic kernel on TPU and the
    jnp oracle elsewhere (the oracle is itself fully vectorised).

    With ``page_axis`` (inside a page-sharded ``shard_map`` body) each
    shard scans only its own P/n pool slice for victim candidates —
    bitwise-identical to the unsharded path (see the sharding note
    above).

    Scoped as ``kernel:batched_evict`` (``repro.obs.scope``), which
    ``bench/trace.py`` and ``bench/kernel_work.py`` read.
    """
    with obs.scope("kernel:batched_evict"):
        if page_axis is not None:
            return _evict_page_sharded(key, sizes, evictable, need_free,
                                       vmax, page_axis)
        mode = _use_pallas()
        if mode is not False:
            return batched_evict_kernel(
                key, sizes, evictable, need_free,
                vmax=vmax, interpret=(mode is None),
            )
        return ref.batched_evict_ref(
            key, sizes, evictable, need_free, vmax=vmax,
        )


def wake_solve(key, sizes, credit0, inc, pops, *, h_cap: int = 64):
    """Per-page grant step of the frozen serial I/O server — the
    event-horizon stepper's wake-exact queue model (how many fine steps
    until each queued page is granted, given the io-credit cadence
    ``credit0 + k*inc`` and the per-step ``pops`` cap).

    Pages not wanted (``key < 0``) or not granted within ``h_cap`` fine
    steps carry the sentinel ``h_cap + 1``.  Called from inside the
    already-jitted event-horizon step, so no jit wrapper; backend policy
    picks the page-blocked Mosaic kernel on TPU and the jnp oracle (one
    stable argsort + the pop-rate recursion) elsewhere.  Under page
    sharding the inputs are replicated and the solve's outputs feed
    lane-global jump decisions, so it runs replicated as-is.

    Scoped as ``kernel:wake_solve`` (``repro.obs.scope``), which
    ``bench/trace.py`` and ``bench/kernel_work.py`` read."""
    with obs.scope("kernel:wake_solve"):
        mode = _use_pallas()
        if mode is not False:
            return wake_solve_kernel(
                key, sizes, credit0, inc, pops,
                h_cap=h_cap, interpret=(mode is None),
            )
        return ref.wake_solve_ref(
            key, sizes, credit0, inc, pops, h_cap=h_cap,
        )


@functools.partial(jax.jit, static_argnames=("chunk",))
def mlstm_chunked(q, k, v, a, i, chunk: int = 128):
    mode = _use_pallas()
    if mode is True:
        return mlstm_chunked_kernel(q, k, v, a, i, chunk=chunk)
    if mode is None:
        return mlstm_chunked_kernel(q, k, v, a, i, chunk=chunk, interpret=True)
    return ref.gla_ref(q, k, v, a, i)
