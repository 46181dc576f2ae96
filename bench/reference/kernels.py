"""Plain jnp forms of the simulator's three substrate ops.

Each op selects with one exact ``top_k`` (or one stable ``argsort``) and
sums page bytes with one ``cumsum``; they are what the Pallas kernels of
the program must reproduce.  ``Oracles()`` keeps every float input in
float32, as the simulator states.  ``Oracles(jnp.bfloat16)`` is the
control: the ops' float inputs (page sizes, byte budgets and the float
eviction keys) rounded to bfloat16 before the float32 sums, as kernels
that read their inputs in half the bytes would see them.  Rounding the
bytes alone changes nothing at a deployment whose pages are 512 KiB and
whose I/O credit grows by whole pages: those are exact in bfloat16.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


class Oracles:
    """``fifo_grant`` / ``batched_evict`` / ``wake_solve`` with the call
    signatures the step uses."""

    def __init__(self, dtype=jnp.float32):
        self.dtype = jnp.dtype(dtype)

    def _b(self, x):
        if self.dtype == jnp.float32:
            return x
        return jnp.asarray(x).astype(self.dtype).astype(jnp.float32)

    def batched_evict(self, key, sizes, evictable, need_free, *,
                      vmax: int = 64, page_axis: Optional[str] = None):
        """Victims in descending ``key`` order (ties: ascending page index)
        among the ``vmax`` highest keys, until ``need_free`` bytes are
        covered.  Returns the evict mask."""
        assert page_axis is None
        sizes, need_free = self._b(sizes), self._b(need_free)
        P = key.shape[0]
        if jnp.issubdtype(key.dtype, jnp.integer):
            key = jnp.where(evictable, key, jnp.iinfo(key.dtype).min)
        else:
            key = jnp.where(evictable, self._b(key), -jnp.inf)
        _, cand = jax.lax.top_k(key, min(vmax, P))
        c_ok = evictable[cand]
        sz_c = jnp.where(c_ok, sizes[cand], 0.0)
        csum = jnp.cumsum(sz_c)
        take = c_ok & (csum - sz_c < need_free) & (need_free > 0)
        return jnp.zeros((P,), bool).at[cand].set(take)

    def fifo_grant(self, key, sizes, budget, pops, *, vmax: int = 16,
                   page_axis: Optional[str] = None):
        """Strict head-of-line pop of the request queue (descending
        ``key``, ties ascending index, ``key < 0`` not wanted) within
        ``budget`` bytes and ``pops`` pops.  Returns ``(mask, bytes, n)``."""
        assert page_axis is None
        sizes, budget = self._b(sizes), self._b(budget)
        P = key.shape[0]
        kv, cand = jax.lax.top_k(key, min(vmax, P))
        sz = sizes[cand]
        csum = jnp.cumsum(sz)
        n = kv.shape[0]
        ok = jnp.cumprod(
            ((kv >= 0) & (csum <= budget)
             & (jnp.arange(n) < pops)).astype(jnp.int32)
        ).astype(bool)
        mask = jnp.zeros((P,), bool).at[cand].set(ok)
        return mask, jnp.sum(jnp.where(ok, sz, 0.0)), jnp.sum(ok)

    def wake_solve(self, key, sizes, credit0, inc, pops, *, h_cap: int = 64):
        """Fine step (``1..h_cap``) at which the frozen serial server grants
        each queued page; ``h_cap + 1`` for pages not wanted or not granted
        within ``h_cap`` steps.  The grant count follows
        ``n_k = min(bytes_ok(credit0 + k*inc), n_{k-1} + pops)``."""
        sizes, credit0, inc = self._b(sizes), self._b(credit0), self._b(inc)
        P = key.shape[0]
        order = jnp.argsort(-key)
        kv = key[order]
        w_ord = kv >= 0
        sz = jnp.where(w_ord, sizes[order], 0.0)
        csum = jnp.cumsum(sz)
        ks = jnp.arange(1, h_cap + 1, dtype=jnp.float32)
        cnt = jnp.sum(
            w_ord[None, :] & (csum[None, :] <= credit0 + ks[:, None] * inc),
            axis=1,
        ).astype(jnp.float32)
        popf = jnp.maximum(pops, 0).astype(jnp.float32)
        gap = ks[:, None] - ks[None, :]
        ramp = jnp.where(gap >= 0, cnt[None, :] + gap * popf, jnp.inf)
        n_k = jnp.minimum(jnp.min(ramp, axis=1), ks * popf)
        rank = jnp.arange(P, dtype=jnp.float32)
        step = 1 + jnp.sum(n_k[None, :] < (rank[:, None] + 1.0), axis=1)
        step = jnp.where(w_ord, step, h_cap + 1).astype(jnp.int32)
        return jnp.zeros((P,), jnp.int32).at[order].set(step)
