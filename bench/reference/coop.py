"""Cooperative Scans substrate for the array backend (paper §2).

The ABM *inverts* buffer-management control flow: loading decisions are
global, CScan operators consume whichever chunk of their range is ready
(out-of-order, chunk-at-a-time), and eviction is relevance-driven.  None
of that is expressible as an eviction score over the in-order step — the
event CScan beats even OPT, which bounds every order-preserving policy —
so the step compiles this chunk-granular fluid model in whenever a
``cooperative`` :class:`~repro.core.array_sim.policies.ArrayPolicy`
(array-CScan) is among its policies, and blends per-lane with the
in-order model by the traced policy id.

The model mirrors ``policies/cscan.py`` at chunk granularity:

* **state** (:class:`CoopState`, the cooperative policy's pstate): per
  (stream, chunk) consumed flags for the stream's current query, the
  chunk each stream is consuming (+ fractional progress and banked CPU
  credit), and the single chunk the serial I/O server is loading;
* **CPU** (:func:`cpu_phase`): an idle scan picks the *available* chunk
  (all pages of its columns resident) the fewest other scans are
  interested in (``UseRelevance``), then consumes its tuple overlap at
  the query rate; completion leftovers bank one step of credit so chunk
  boundaries don't quantise the rate;
* **I/O** (:func:`io_phase`): when the server idles, pick the next load
  by ``QueryRelevance`` (starved scans first, then fewest chunks
  remaining) then ``LoadRelevance`` (most interested scans, lowest chunk
  id) — gated by the paper's eviction rule: a chunk is only loadable if
  enough bytes are held by chunks with strictly lower ``KeepRelevance``
  (interest count).  The selected chunk's missing pages (union of the
  interested scans' columns) drain through the step's shared byte-budget
  server; victims come from the least-interesting chunks via the same
  batched-evict kernel as every other policy, scored by
  ``ArrayCScan.score_victims``.

Chunk geometry (global chunk ids, page→chunk ownership by first tuple —
exactly ``ABM._ensure_chunk_meta``) is compiled by
``compiler.compile_workload`` into ``SimSpec``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

_NEGI = -(1 << 30)


class CoopState(NamedTuple):
    """Cooperative-substrate state: the ``ArrayCScan`` policy pytree."""

    done: jnp.ndarray        # (S, CH) bool chunks consumed (current query)
    cur_chunk: jnp.ndarray   # (S,) i32 chunk being consumed, -1 = none
    chunk_pos: jnp.ndarray   # (S,) f32 tuples consumed within cur_chunk
    credit: jnp.ndarray      # (S,) f32 banked CPU budget (completion spill)
    inflight: jnp.ndarray    # () i32 chunk the I/O server loads, -1 = idle


class CoopCpu(NamedTuple):
    """CPU-phase outputs the step blends into its per-lane state."""

    adv: jnp.ndarray             # (S,) tuples credited to ``pos`` this step
    finished: jnp.ndarray        # (S,) bool current query completed
    consumed_pages: jnp.ndarray  # (P,) bool pages consumed (completed chunks)
    pin_pages: jnp.ndarray       # (P,) bool pages of chunks being consumed
    done: jnp.ndarray            # (S, CH) post-consumption flags
    cur_chunk: jnp.ndarray       # (S,) i32
    chunk_pos: jnp.ndarray       # (S,) f32
    credit: jnp.ndarray          # (S,) f32


class CoopIo(NamedTuple):
    """I/O-phase outputs: the cooperative lane's request set + evict view."""

    load_key: jnp.ndarray    # (P,) i32 server queue key (-1 = not wanted)
    wanted: jnp.ndarray      # (P,) bool missing pages of the inflight chunk
    evictable: jnp.ndarray   # (P,) bool Keep < Load rule applied
    keep_key: jnp.ndarray    # (P,) f32 eviction priority (fewest interest)
    inflight: jnp.ndarray    # () i32 updated inflight chunk
    starved: jnp.ndarray     # (S,) bool diagnostic


class CoopConsts(NamedTuple):
    """Device constants the step closes over (from ``SimSpec``)."""

    n_chunks: int
    page_chunk: jnp.ndarray   # (P,) i32
    chunk_first: jnp.ndarray  # (CH,) f32 table-local tuple coords
    chunk_last: jnp.ndarray   # (CH,) f32
    chunk_table: jnp.ndarray  # (CH,) i32


def coop_consts(spec) -> CoopConsts:
    if spec.page_chunk is None:
        raise ValueError(
            "spec has no chunk geometry — recompile the workload with "
            "compiler.compile_workload (seed-era SimSpecs cannot run the "
            "cooperative policy)"
        )
    return CoopConsts(
        n_chunks=int(spec.n_chunks),
        page_chunk=jnp.asarray(spec.page_chunk),
        chunk_first=jnp.asarray(spec.chunk_first),
        chunk_last=jnp.asarray(spec.chunk_last),
        chunk_table=jnp.asarray(spec.chunk_table),
    )


def init_coop_state(spec) -> CoopState:
    S, CH = spec.n_streams, int(spec.n_chunks)
    if CH <= 0:
        raise ValueError(
            "spec has no chunk geometry — recompile the workload with "
            "compiler.compile_workload"
        )
    return CoopState(
        done=jnp.zeros((S, CH), bool),
        cur_chunk=jnp.full(S, -1, jnp.int32),
        chunk_pos=jnp.zeros(S, jnp.float32),
        credit=jnp.zeros(S, jnp.float32),
        inflight=jnp.int32(-1),
    )


def chunk_pick(prev_inflight, new_inflight):
    """Did the I/O server switch to loading a NEW chunk this step?  The
    obs tier's CScan chunk-pick signal (``Telemetry.chunk_picks``): both
    args are the scalar inflight chunk id, ``-1`` = idle."""
    return (new_inflight >= 0) & (new_inflight != prev_inflight)


def _interest(cc: CoopConsts, active, start, end, q_tab, done):
    """(S, CH) pending interest + per-(stream, chunk) tuple overlap: a
    scan is interested in every not-yet-consumed chunk of its table that
    overlaps its range (``ScanState.chunks_remaining``)."""
    ov_lo = jnp.maximum(cc.chunk_first[None, :], start[:, None])
    ov_hi = jnp.minimum(cc.chunk_last[None, :], end[:, None])
    overlap = jnp.maximum(ov_hi - ov_lo, 0.0)
    in_range = (
        (overlap > 0.0)
        & (cc.chunk_table[None, :] == q_tab[:, None])
        & active[:, None]
    )
    return in_range & ~done, overlap


def _chunk_missing(cc: CoopConsts, cols, resident, page_col, page_valid,
                   n_cols: int):
    """(S, CH) "some page of my columns is absent" — the complement of the
    ABM's chunk availability.  One (CH, C) scatter + a broadcast AND keeps
    it fully vectorised (no per-stream scatter loop)."""
    missing = (~resident) & page_valid
    miss_cc = jnp.zeros((cc.n_chunks, n_cols), bool).at[
        cc.page_chunk, page_col
    ].max(missing)
    return jnp.any(miss_cc[None, :, :] & cols[:, None, :], axis=2)


#: pick→consume rounds unrolled per step: a chunk's CPU time can be ~one
#: step (TPC-H chunks at small scales), so completing one chunk and
#: starting the next must happen WITHIN a step or chunk boundaries
#: quantise every scan to <= 1 chunk/step — a 30-100% CPU-time inflation
#: the continuous event engine does not have.
_PICK_ROUNDS = 2


def cpu_phase(cc: CoopConsts, cstate: CoopState, *, active, start, end,
              cols, q_tab, rate_j, dt, credit_cap, resident, page_col,
              page_valid, s_idx) -> CoopCpu:
    """One CPU step of every CScan: pick-if-idle (UseRelevance), consume,
    complete — chained for ``_PICK_ROUNDS`` rounds so the leftover budget
    of a completed chunk flows into the next one within the same step
    (the event engine consumes continuously; any residue banks as capped
    credit for the next step).  Runs on the pre-advance view, like the
    in-order burst."""
    S, CH = cols.shape[0], cc.n_chunks
    n_cols = cols.shape[1]
    interest0, overlap = _interest(cc, active, start, end, q_tab,
                                   cstate.done)
    in_range = interest0 | cstate.done   # static within the step
    miss_sc = _chunk_missing(cc, cols, resident, page_col, page_valid,
                             n_cols)
    cid = jnp.arange(CH, dtype=jnp.int32)

    done = cstate.done
    cur = cstate.cur_chunk
    chunk_pos = cstate.chunk_pos
    budget = rate_j * dt + cstate.credit
    adv = jnp.zeros(S, jnp.float32)
    consumed_any = jnp.zeros(S, bool)
    completed_cc = jnp.zeros((CH, n_cols), bool)

    for _ in range(_PICK_ROUNDS):
        interest = in_range & active[:, None] & ~done
        avail = interest & ~miss_sc
        # UseRelevance pick for idle scans: the available chunk the FEWEST
        # scans are interested in (it becomes evictable soonest), lowest
        # chunk id on ties — exactly ``ABM.get_chunk``
        count = jnp.sum(interest, axis=0).astype(jnp.int32)   # (CH,)
        pick_key = jnp.where(
            avail, count[None, :] * (CH + 2) + cid[None, :],
            jnp.int32(1 << 30),
        )
        pick = jnp.argmin(pick_key, axis=1).astype(jnp.int32)
        can_pick = jnp.any(avail, axis=1)
        idle = cur < 0
        started = idle & can_pick
        cur = jnp.where(started, pick, cur)
        chunk_pos = jnp.where(started, 0.0, chunk_pos)

        consuming = cur >= 0
        ci = jnp.clip(cur, 0, CH - 1)
        cur_ov = overlap[s_idx, ci]
        room = jnp.maximum(cur_ov - chunk_pos, 0.0)
        adv_t = jnp.where(consuming, jnp.minimum(budget, room), 0.0)
        budget = budget - adv_t
        pos_in = chunk_pos + adv_t
        completed = consuming & (
            pos_in >= cur_ov - jnp.maximum(1e-3, 1e-6 * cur_ov)
        )
        consumed_any = consumed_any | (adv_t > 0.0) | completed
        done = done.at[s_idx, ci].max(completed)
        completed_cc = completed_cc.at[ci].max(cols & completed[:, None])
        adv = adv + jnp.where(completed, cur_ov, 0.0)
        cur = jnp.where(completed, -1, cur)
        chunk_pos = jnp.where(completed, 0.0, pos_in)

    # bank the residue ONLY for scans that did work and ended the step
    # between chunks — an idle (starved) scan accumulates nothing
    credit2 = jnp.where(
        consumed_any & (cur < 0),
        jnp.minimum(budget, credit_cap), 0.0,
    )

    # query completion: every interested chunk consumed (the engine's
    # ``chunks_remaining`` empty) — robust against f32 tuple rounding
    interest_after = in_range & active[:, None] & ~done
    finished = active & ~jnp.any(interest_after, axis=1)

    # pages consumed this step (completed chunks, consuming scan's
    # columns) — feeds the LRU clock and the churn diagnostic
    consumed_pages = completed_cc[cc.page_chunk, page_col] & page_valid
    # a chunk being consumed is pinned for its scan's columns
    # (``ABM.pin_chunk``); completed chunks unpin
    pin_cc = jnp.zeros((CH, n_cols), bool).at[jnp.clip(cur, 0, CH - 1)].max(
        cols & (cur >= 0)[:, None]
    )
    pin_pages = pin_cc[cc.page_chunk, page_col] & page_valid

    return CoopCpu(adv=adv, finished=finished,
                   consumed_pages=consumed_pages, pin_pages=pin_pages,
                   done=done, cur_chunk=cur, chunk_pos=chunk_pos,
                   credit=credit2)


def io_phase(cc: CoopConsts, *, done, cur_chunk, inflight, pin_pages,
             active, start, end, cols, q_tab, resident, free, page_chunk_sizes,
             page_col, page_valid, n_streams: int) -> CoopIo:
    """ABM's next-load decision as one batched selection.

    Runs on the post-advance view (new queries register their interest
    immediately).  The Keep<Load rule is enforced twice: chunk selection
    requires enough bytes held at strictly lower interest counts
    (feasibility), and the evictable mask the eviction kernel sees is
    restricted to pages of chunks with interest below the inflight
    chunk's LoadRelevance.
    """
    CH = cc.n_chunks
    S = n_streams
    page_size = page_chunk_sizes
    interest, _ = _interest(cc, active, start, end, q_tab, done)
    miss_sc = _chunk_missing(cc, cols, resident, page_col, page_valid,
                             cols.shape[1])
    avail = interest & ~miss_sc
    count = jnp.sum(interest, axis=0).astype(jnp.int32)       # (CH,)
    n_remaining = jnp.sum(interest, axis=1).astype(jnp.int32)  # (S,)
    consuming = cur_chunk >= 0
    starved = (
        active & ~consuming & (n_remaining > 0)
        & ~jnp.any(avail, axis=1)
    )

    # union of the interested scans' columns per chunk: the ABM loads a
    # chunk once for everyone (``_union_columns``)
    ucols = jnp.any(interest[:, :, None] & cols[:, None, :], axis=0)
    ucols_p = ucols[cc.page_chunk, page_col]
    missing_p = (~resident) & page_valid & ucols_p
    mb = jnp.zeros(CH, jnp.float32).at[cc.page_chunk].add(
        jnp.where(missing_p, page_size, 0.0)
    )

    # Keep < Load feasibility: bytes resident in chunks with interest
    # count strictly below k, via a bytes-by-count histogram (counts are
    # bounded by the stream count)
    count_p = count[cc.page_chunk]
    base_ev = resident & page_valid & ~pin_pages
    bb = jnp.zeros(S + 2, jnp.float32).at[jnp.clip(count_p, 0, S + 1)].add(
        jnp.where(base_ev, page_size, 0.0)
    )
    below = jnp.concatenate([jnp.zeros(1, jnp.float32), jnp.cumsum(bb)])
    feasible = free + below[jnp.clip(count, 0, S + 1)] >= mb

    # keep (or drop) the chunk in flight: it stays until fully resident
    # for the interested union, loses its interest, or turns infeasible
    infl_c = jnp.clip(inflight, 0, CH - 1)
    still = (
        (inflight >= 0) & (mb[infl_c] > 0) & (count[infl_c] > 0)
        & feasible[infl_c]
    )
    inflight1 = jnp.where(still, inflight, -1)

    # ABM next_load: best query first (starved, then fewest chunks
    # remaining), then that query's best chunk (most interested scans,
    # lowest id).  Lexicographic argmax in three masked reductions.
    qkey_s = (jnp.where(starved, 2048, 0)
              + (1023 - jnp.clip(n_remaining, 0, 1023)))      # (S,)
    qbest = jnp.max(
        jnp.where(interest, qkey_s[:, None], _NEGI), axis=0
    )                                                          # (CH,)
    loadable = (count > 0) & (mb > 0) & feasible
    q1 = jnp.where(loadable, qbest, _NEGI)
    qm = jnp.max(q1)
    c1 = jnp.where(loadable & (qbest == qm), count, _NEGI)
    cm = jnp.max(c1)
    sel_mask = loadable & (qbest == qm) & (count == cm)
    sel = jnp.argmax(sel_mask).astype(jnp.int32)   # first True = lowest id
    has_sel = jnp.any(sel_mask)
    inflight2 = jnp.where(
        inflight1 >= 0, inflight1, jnp.where(has_sel, sel, -1)
    )

    # the server's request set: missing pages of the inflight chunk in
    # page-index order (one chunk at a time — the serial ABM server)
    infl2_c = jnp.clip(inflight2, 0, CH - 1)
    P = cc.page_chunk.shape[0]
    want_p = (cc.page_chunk == infl2_c) & (inflight2 >= 0) & missing_p
    load_key = jnp.where(
        want_p, (1 << 24) - jnp.arange(P, dtype=jnp.int32), -1
    )

    # eviction view: only chunks with KeepRelevance strictly below the
    # inflight chunk's LoadRelevance may be evicted; fewest-interest
    # chunks go first, lowest chunk id on ties (whole chunks drain
    # together since all their pages share one key)
    infl_count = jnp.where(inflight2 >= 0, count[infl2_c], 0)
    evictable = base_ev & (count_p < infl_count)
    keep_key = (
        (S + 1.0 - count_p.astype(jnp.float32))
        + 0.5 * (CH - cc.page_chunk.astype(jnp.float32)) / CH
    )

    return CoopIo(load_key=load_key, wanted=want_p, evictable=evictable,
                  keep_key=keep_key, inflight=inflight2, starved=starved)


def chunk_horizon(spec, cstate: CoopState, hz):
    """Per-stream event horizon of the cooperative model (seconds): a
    consuming scan's next interesting moment is its current chunk's
    completion (``(overlap - chunk_pos) / rate``); an idle active scan
    needs a fine step to run the pick loop; inactive streams contribute
    nothing.  The chunk — not the page trigger — is CScan's clock, which
    is why this lives with the substrate and not the in-order step."""
    CH = int(spec.n_chunks)
    chunk_first = jnp.asarray(spec.chunk_first)
    chunk_last = jnp.asarray(spec.chunk_last)
    ci = jnp.clip(cstate.cur_chunk, 0, CH - 1)
    ov = jnp.maximum(
        jnp.minimum(chunk_last[ci], hz.end)
        - jnp.maximum(chunk_first[ci], hz.start),
        0.0,
    )
    rem_c = jnp.maximum(ov - cstate.chunk_pos, 0.0)
    t = jnp.where(
        cstate.cur_chunk >= 0,
        rem_c / jnp.maximum(hz.rate, 1.0),
        hz.dt_ref,
    )
    return jnp.where(hz.active, t, jnp.float32(np.inf))


def clear_on_query_change(done, finished):
    """A finished query's chunk flags reset — the next query registers a
    fresh ``chunks_remaining`` set (new ``ScanState``)."""
    return jnp.where(finished[:, None], False, done)


def chunk_geometry(db, tnames, page_rows):  # analysis: host
    """Compiler helper: global chunk ids for the compiled tables.

    Returns ``(n_chunks, chunk_first, chunk_last, chunk_table,
    page_chunk)`` where ``page_rows`` is the compiled page list as
    ``(table_index, first_tuple)`` pairs in global page order.  Page →
    chunk ownership follows ``ABM._ensure_chunk_meta``: a page belongs to
    the chunk containing its first tuple ("one page contains data from
    multiple adjacent chunks" — unique ownership by first tuple).
    """
    chunk_first, chunk_last, chunk_table = [], [], []
    offs = []
    for ti, tname in enumerate(tnames):
        t = db.tables[tname]
        offs.append(len(chunk_first))
        for ch in range(t.n_chunks):
            lo, hi = t.chunk_range(ch)
            chunk_first.append(float(lo))
            chunk_last.append(float(hi))
            chunk_table.append(ti)
    page_chunk = np.zeros(len(page_rows), np.int32)
    for gi, (ti, first) in enumerate(page_rows):
        t = db.tables[tnames[ti]]
        local = min(int(first // t.chunk_tuples), t.n_chunks - 1)
        page_chunk[gi] = offs[ti] + local
    return (
        len(chunk_first),
        np.asarray(chunk_first, np.float32),
        np.asarray(chunk_last, np.float32),
        np.asarray(chunk_table, np.int32),
        page_chunk,
    )
