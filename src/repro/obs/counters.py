"""Jit-pure in-step telemetry: the carried :class:`Telemetry` pytree.

The batched substrate's step is a pure ``carry -> carry`` function under
``jax.jit``/``jax.vmap`` — the ONLY place a per-step observation can
live without breaking that contract is the carry itself.  Host
callbacks (``jax.debug.print`` and friends) are banned from traced
regions by the analysis lint (rule ``jit-host-callback``, DESIGN.md §8)
precisely because they are the tempting wrong answer: they serialise
the vmapped lanes, defeat donated buffers, and change what XLA may
fuse.  So telemetry is data: fixed-shape integer counters threaded
through the step like any other state leaf, updated with the pure
``jnp`` helpers below, and summarised on the host only after the run.

The knob is **static** (``make_runner(telemetry=True)``): with it off
the step never constructs the pytree and compiles to exactly the
pre-telemetry program (bit-equal results, asserted in
``tests/test_obs.py``); with it on the counters are ordinary carry
leaves, so the one-trace-per-runner contract holds unchanged.

Counter taxonomy (one :class:`Telemetry` per lane; vmap batches them):

===============  ==========================================================
``hits``         plan-trigger crossings of *resident* pages — consumptions
                 served from the pool (cooperative lanes: chunk pages
                 consumed)
``misses``       demand grants — loads that un-blocked a scan frontier
``loads``        every I/O grant (demand + readahead)
``evictions``    pages evicted by the batched eviction kernel
``evict_rank``   log2 histogram of each victim's rank in the policy score
                 order (rank 0 = the policy's top victim; mass in high
                 bins means the kernel digs far past the policy's
                 preference to free bytes — the deep-thrash signature)
``jump_hist``    log2 histogram of macro-step length in fine steps (the
                 horizon stepper's jump sizes; all-ones under ``fixed``)
``ioq_depth_sum``/``ioq_depth_max``  pending request-queue depth,
                 integrated over steps / peak
``chunk_picks``  cooperative chunk selections (the I/O server switching
                 to a new CScan chunk)
``wake_solves``  horizon steps whose wake solve was asked for more than
                 zero pops (a supersaturated in-order lane under
                 ``wake_exact``; every other lane discards the solve and
                 asks it for none)
``pol_obs``      per compiled policy, the row its ``observe`` hook
                 accumulates (PBM: bucket occupancy histogram; LRU:
                 resident age mass; OPT: referenced/unreferenced split;
                 see ``ArrayPolicy.observe``)
===============  ==========================================================
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: log2 histogram bins: bin b counts values in [2**b, 2**(b+1)), the
#: last bin absorbs the tail.  8 bins cover ranks/jumps up to 128+.
N_BINS = 8


class Telemetry(NamedTuple):
    """Per-lane counter pytree carried through the jitted step."""

    hits: jax.Array           # i32 resident plan-trigger crossings
    misses: jax.Array         # i32 demand (frontier-blocking) grants
    loads: jax.Array          # i32 all I/O grants
    evictions: jax.Array      # i32 pages evicted
    evict_rank: jax.Array     # (N_BINS,) i32 victim rank in score order
    jump_hist: jax.Array      # (N_BINS,) i32 macro-step length (fine steps)
    ioq_depth_sum: jax.Array  # i32 pending-request depth, step-integrated
    ioq_depth_max: jax.Array  # i32 pending-request depth, peak
    chunk_picks: jax.Array    # i32 cooperative chunk selections
    wake_solves: jax.Array    # i32 steps whose wake solve was asked to pop
    pol_obs: Tuple = ()       # per-policy observe rows (f32 vectors)


def count(c: jax.Array, event) -> jax.Array:
    """Accumulate ``event`` (a bool mask, a count, or a scalar flag)
    into counter ``c``.  Pure ``jnp`` — safe in traced regions."""
    return c + jnp.sum(event).astype(c.dtype)


def hist(h: jax.Array, bins, weights) -> jax.Array:
    """Scatter-add ``weights`` into histogram ``h`` at ``bins``."""
    return h.at[bins].add(jnp.asarray(weights).astype(h.dtype))


def log2_bin(x, n_bins: int = N_BINS) -> jax.Array:
    """Map positive values to log2 bins: 1 -> 0, 2-3 -> 1, 4-7 -> 2, ...
    clipped to ``[0, n_bins)`` (zero/negative values land in bin 0)."""
    xf = jnp.maximum(jnp.asarray(x).astype(jnp.float32), 1.0)
    return jnp.clip(jnp.floor(jnp.log2(xf)).astype(jnp.int32), 0, n_bins - 1)


def init_telemetry(policies, spec) -> Telemetry:  # analysis: host
    """Zeroed :class:`Telemetry` for one lane of a compiled policy set.

    Policies opt into a private row via ``observe_init`` (``None`` means
    no row; a zero-length placeholder keeps the pytree structure stable
    across policy sets, and the step skips accumulation on ``size == 0``
    — a static shape check, free under jit)."""
    rows = []
    for p in policies:
        proto = p.observe_init(spec)
        rows.append(jnp.zeros((0,), jnp.float32) if proto is None
                    else jnp.zeros_like(proto))
    z = jnp.int32(0)
    zh = jnp.zeros(N_BINS, jnp.int32)
    return Telemetry(
        hits=z, misses=z, loads=z, evictions=z,
        evict_rank=zh, jump_hist=zh,
        ioq_depth_sum=z, ioq_depth_max=z, chunk_picks=z, wake_solves=z,
        pol_obs=tuple(rows),
    )


def lane_slice(tele: Telemetry, i: int) -> Telemetry:  # analysis: host
    """Extract lane ``i`` of a vmapped (batched) telemetry pytree."""
    return jax.tree.map(lambda x: x[i], tele)


# analysis: host
def summarize(tele: Telemetry, policies=None, steps=None) -> dict:
    """Host-side digest of one lane's telemetry — the dict stamped into
    ``ArrayResult.extras['telemetry']`` and the RunManifest."""
    hits = int(tele.hits)
    misses = int(tele.misses)
    out = {
        "hits": hits,
        "misses": misses,
        "loads": int(tele.loads),
        "evictions": int(tele.evictions),
        "hit_rate": round(hits / max(1, hits + misses), 4),
        "evict_rank_hist": np.asarray(tele.evict_rank).tolist(),
        "jump_hist": np.asarray(tele.jump_hist).tolist(),
        "ioq_depth_max": int(tele.ioq_depth_max),
        "chunk_picks": int(tele.chunk_picks),
        "wake_solves": int(tele.wake_solves),
    }
    if steps is not None and int(steps) > 0:
        out["ioq_depth_mean"] = round(
            int(tele.ioq_depth_sum) / int(steps), 2)
    if policies is not None:
        pol = {}
        for p, row in zip(policies, tele.pol_obs):
            name = p if isinstance(p, str) else p.name
            arr = np.asarray(row)
            if arr.size and np.any(arr):   # other lanes' rows stay zero
                pol[name] = [round(float(v), 2) for v in arr.tolist()]
        if pol:
            out["policy_obs"] = pol
    return out
