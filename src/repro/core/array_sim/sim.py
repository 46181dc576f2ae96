"""Array-native batched simulation of concurrent scans over a buffer pool.

The event engine (``repro.core.engine``) replays the paper's machine one
heapq event at a time in Python.  This module re-expresses the same system
as a **pure, fixed-shape array program**:

* per-page state (residency, LRU clock, FIFO request stamp) and
  per-stream state (query index, cursor, speed estimate) live in dense
  JAX arrays (:class:`SimState`); policy-private state (PBM's bucket
  timeline, CScan's chunk flags) rides along as pure pytrees owned by
  the compiled :class:`~repro.core.array_sim.policies.ArrayPolicy`
  objects;
* a pure ``step(state, cfg) -> state`` advances the whole machine by one
  page-transfer time ``dt`` — scans consume tuples with the engine's
  **per-page plan-trigger semantics**: each column keeps a fractional
  frontier cursor, a page is needed only at the instant the cursor crosses
  its trigger (``max(page_first, scan_start)``), and a scan blocks exactly
  at the earliest absent trigger across its columns — never on pages whose
  trigger it already crossed.  A bandwidth-budgeted I/O server pops the
  request FIFO; eviction dispatches on the **score arrays the compiled
  policies provide** (``ArrayPolicy.score_victims`` through the batched
  eviction kernel) — the step itself knows no policy by name or id.
  Because a blocked scan pins nothing and a running burst pins only its
  last ~``segment_pages`` plan entries, pools far below ``streams x
  columns`` pages stay live — the paper's small-buffer operating points
  (10-40%) run on this substrate, cross-validated against the event
  engine (see ``validate.ERROR_BARS``);
* steps come in two flavours on the paper's own cadence: *within* a PBM
  time slice the bucketed timeline is static (cheap step: consume, load,
  evict), and once per ``time_slice`` a *refresh* step recomputes every
  page's estimated next consumption — the policies see the boundary as
  the static ``refresh`` flag of their observation window
  (:class:`~repro.core.array_sim.policies.StepCtx`);
* a **cooperative** policy (array-CScan) inverts the control flow: when
  one is compiled in, the step also runs the chunk-granular ABM
  substrate (``array_sim.coop``) and blends per-lane between the
  in-order and cooperative models by the traced policy id — so a vmapped
  sweep mixes all four paper policies in ONE batched call;
* everything is ``jax.jit``- and ``jax.vmap``-compatible, so an entire
  sweep axis (buffer sizes x bandwidths x policies) runs as ONE batched
  computation instead of N serial Python event loops;
* **time itself is modelled two ways** (``make_runner(stepper=...)``):
  the ``"fixed"`` stepper grinds the classic fixed-``dt`` cadence
  (bit-compatible with the pre-horizon engine), while the ``"horizon"``
  stepper exploits the paper's own premise — long scans make the near
  future *predictable* — by computing, per lane and per step, the
  earliest **interesting** time (next plan-trigger arrival, next chunk
  completion, io-credit horizon of the pending request queue, stream
  completion, next timeline refresh) and advancing all state arrays by
  that variable ``dt`` in one jump.  Jumps never cross a PBM slice
  boundary, so the refresh cadence — the paper's semantic clock — is
  identical in both modes; finished lanes freeze (their metrics are
  bit-stable while slower lanes continue).  A ``mesh=`` on
  ``make_runner`` layers ``shard_map`` execution over the lane axis on
  top, spreading a batched sweep across devices with per-lane horizons
  intact;
* workloads may span SEVERAL tables (``compiler.compile_workload``):
  pages live in one global id space with per-column offsets, each query
  row carries its own table's tuple coordinates, and the global column
  mask restricts every per-column computation (frontier cursors, advance
  limits, consumption estimates) to the query's table — the step itself
  never branches on a table id, which is what keeps the TPC-H throughput
  run (Figs 14-16) on the same jit/vmap path as the microbenchmark.

Policy names resolve through ``repro.core.policy_registry`` — the single
table shared with the event engine; the traced ``cfg.policy`` carries the
registry's stable array id.
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import policy_registry
from ...obs import counters as obs
from ...obs import phases
from . import coop as coop_mod
from .policies import BIG_CUT, ArrayPolicy, HorizonView, StepCtx
from .spec import SimSpec

_REQ_NONE = 1 << 24   # FIFO stamp sentinel: page not currently requested
_JIT_STEPS = 6        # LRU-clock jitter amplitude in step-lengths
_LOAD_MAX = 6         # load grants per step (credit caps at ~5 pages)
_PROG_MIN = 1.0       # tuples: a slice with less progress skips the EWMA
_BURST_W = 0.75       # burst-report weight in the speed estimate: the
                      # engine's per-burst EWMA samples the CPU rate
                      # between stalls and the effective rate at stall
                      # exits, so its estimate sits between the two
_RATE_JIT = 0.08      # per-(stream, query) CPU pacing skew amplitude
_GATE_P = 0.105       # blocked-scan window-refresh rate (engine wakes
                      # re-issue the prefetch window every ~10-20ms)
_DIP_P = 0.31         # fraction of steps a stream's push speed dips to
                      # its effective rate (stall-exit EWMA crash)
_DIP_DEPTH = 0.8     # dip floor as a fraction of the effective rate
_SEG_PAGES = 2.0      # engine segment_pages: plan entries pinned per burst
_SEG_WIN = 2          # static back-window (pages/column) the pin scan walks
_MAX_ABSORB = 3       # whole slices a wake-exact refresh step may absorb
                      # beyond its own tail (bounds the multi-slice PBM
                      # timeline shift and the jump-length cap)


class ArraySimConfig(NamedTuple):
    """Traced runtime knobs: a batch of configs (one per sweep point) can
    be stacked leaf-wise and vmapped over."""

    capacity_bytes: jax.Array   # f32 buffer-pool capacity
    bandwidth: jax.Array        # f32 bytes/sec of the I/O server
    policy: jax.Array           # i32 registry array id (policy_registry)
    max_time: jax.Array         # f32 livelock guard


class SimState(NamedTuple):
    # ---- per-page (P,) ---------------------------------------------------
    resident: jax.Array       # bool
    last_used: jax.Array      # f32 LRU clock
    req_step: jax.Array       # i32 FIFO stamp: step the page was first wanted
    req_tie: jax.Array        # i32 within-cohort service rank fixed at stamp
    fresh: jax.Array          # bool: loaded but not consumed since (churn)
    # ---- per-stream (S,) -------------------------------------------------
    qidx: jax.Array           # i32 current query (== n_q when stream done)
    pos: jax.Array            # f32 tuples consumed within current query
    speed: jax.Array          # f32 EWMA tuples/sec (effective, stalls incl.)
    consumed: jax.Array       # f32 lifetime tuples consumed (speed input)
    consumed_ref: jax.Array   # f32 `consumed` at the last slice boundary
    stream_done_t: jax.Array  # f32 finish time, -1 while running
    # ---- scalars ---------------------------------------------------------
    t: jax.Array              # f32 sim clock
    steps: jax.Array          # i32 simulation steps executed (macro steps
                              #   under the horizon stepper)
    slices_done: jax.Array    # i32 PBM slices elapsed — the livelock guard
                              #   compares THIS against max_slices (the
                              #   pre-PR-5 name miscounted: it was always a
                              #   slice count, never a time)
    io_credit: jax.Array      # f32 banked I/O bytes (partial in-flight load)
    io_bytes: jax.Array       # f32 lifetime loaded bytes (paper I/O volume)
    loads: jax.Array          # i32 lifetime page loads
    loads_demand: jax.Array   # i32 loads granted for a blocking frontier
    churn: jax.Array          # i32 loads evicted before any consumption
    # ---- policy-private state (one pytree per compiled ArrayPolicy) ------
    pstate: Tuple = ()


@dataclass
class ArrayResult:
    """Mirror of ``EngineResult`` for the array backend rows."""

    policy: str
    stream_times: List[float]
    total_io_bytes: float
    total_loads: int
    sim_time: float
    steps: int
    wall_s: float = 0.0
    extras: dict = field(default_factory=dict)

    @property
    def avg_stream_time(self) -> float:
        return sum(self.stream_times) / max(1, len(self.stream_times))

    @property
    def io_gb(self) -> float:
        return self.total_io_bytes / 1e9


def resolve_policies(
    policies: Optional[Sequence] = None,
) -> Tuple[ArrayPolicy, ...]:
    """Resolve a policy list (names and/or :class:`ArrayPolicy` objects)
    through the registry; ``None`` means every registered array policy.
    At most one cooperative policy may be compiled into one step."""
    if policies is None:
        policies = policy_registry.names(backend="array")
    out = []
    for p in policies:
        out.append(policy_registry.array_policy(p) if isinstance(p, str)
                   else p)
    if sum(p.cooperative for p in out) > 1:
        raise ValueError(
            "at most one cooperative policy per compiled step, got "
            f"{[p.name for p in out if p.cooperative]}"
        )
    return tuple(out)


class _View(NamedTuple):
    """Derived per-stream view of the current query + per-column cursors.
    Carried alongside :class:`SimState` so each step computes it once (this
    step's post-advance view is the next step's pre-advance view).

    The *frontier* of a column is its first page whose trigger
    (``max(page_first, scan_start)``) the scan cursor has not crossed yet —
    the engine's ``plan_idx`` restricted to that column.  ``ftrig`` is the
    fractional per-column cursor: the absolute tuple position at which the
    column next needs a page resident."""

    active: jax.Array    # (S,) bool
    length: jax.Array    # (S,) f32
    rate: jax.Array      # (S,) f32
    cols: jax.Array      # (S, C) bool
    start: jax.Array     # (S,) f32 absolute scan start
    cur: jax.Array       # (S,) f32 absolute cursor
    end: jax.Array       # (S,) f32 absolute scan end
    eps: jax.Array       # (S,) f32 cursor tolerance (f32 rounding guard)
    frontier: jax.Array  # (S, C) i32 local index of next unconsumed page
                         #   (== col_npages when the column is exhausted)
    fpidx: jax.Array     # (S, C) i32 global page id of the frontier (clamped)
    ftrig: jax.Array     # (S, C) f32 fractional per-column cursor (trigger)
    fneed: jax.Array     # (S, C) bool frontier exists inside the scan range


def _u01(idx, t, t_mult, idx_mult=2654435761):
    """Deterministic per-(lane, time) uniform draw in [0, 1): Knuth
    multiplicative hash of a lane index against a time-like salt, top 24
    bits scaled.  Pure — the jit/vmap-safe stand-in for an RNG stream
    everywhere the step needs the event engine's timing noise.  ``t`` may
    be a scalar (sim step / slice counter) or an array shaped like
    ``idx`` (per-lane stamps); distinct ``t_mult``/``idx_mult`` pairs
    decorrelate the independent noise sources."""
    h = idx.astype(jnp.uint32) * jnp.uint32(idx_mult) + \
        jnp.asarray(t).astype(jnp.uint32) * jnp.uint32(t_mult)
    return (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0**-24)


def make_config(
    spec: SimSpec,
    capacity_bytes: float,
    bandwidth: float = 700e6,
    policy: str = "pbm",
    max_time: float = 3e5,
) -> ArraySimConfig:
    """Build one traced config.  ``policy`` is a registry name — the one
    name table in ``repro.core.policy_registry``; raw integer ids were a
    pre-registry shim and are now a hard error."""
    if not isinstance(policy, str):
        raise TypeError(
            f"make_config(policy={policy!r}): integer policy ids were "
            "removed — pass a registry name from "
            "repro.core.policy_registry.names(backend='array') "
            f"({policy_registry.names(backend='array')})"
        )
    entry = policy_registry.get(policy)
    if entry.array_id is None:
        raise KeyError(
            f"policy {policy!r} is event-engine-only; array-backend "
            f"policies: {policy_registry.names(backend='array')}"
        )
    pid = entry.array_id
    return ArraySimConfig(
        capacity_bytes=jnp.float32(capacity_bytes),
        bandwidth=jnp.float32(bandwidth),
        policy=jnp.int32(pid),
        max_time=jnp.float32(max_time),
    )


def stack_configs(cfgs: Sequence[ArraySimConfig]) -> ArraySimConfig:
    """Stack N configs leaf-wise into one batched config for vmap."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *cfgs)


def init_state(spec: SimSpec,
               policies: Sequence[ArrayPolicy] = ()) -> SimState:
    P, S = spec.n_pages, spec.n_streams
    n_q = jnp.asarray(spec.n_q)
    return SimState(
        resident=jnp.zeros(P, bool),
        last_used=jnp.full(P, -1e9, jnp.float32),
        req_step=jnp.full(P, _REQ_NONE, jnp.int32),
        req_tie=jnp.zeros(P, jnp.int32),
        fresh=jnp.zeros(P, bool),
        qidx=jnp.zeros(S, jnp.int32),
        pos=jnp.zeros(S, jnp.float32),
        speed=jnp.asarray(spec.q_rate[:, 0]),
        consumed=jnp.zeros(S, jnp.float32),
        consumed_ref=jnp.zeros(S, jnp.float32),
        stream_done_t=jnp.where(n_q > 0, -1.0, 0.0).astype(jnp.float32),
        t=jnp.float32(0.0),
        steps=jnp.int32(0),
        slices_done=jnp.int32(0),
        io_credit=jnp.float32(0.0),
        io_bytes=jnp.float32(0.0),
        loads=jnp.int32(0),
        loads_demand=jnp.int32(0),
        churn=jnp.int32(0),
        pstate=tuple(p.init_state(spec) for p in policies),
    )


def _evict_candidates(spec: SimSpec) -> int:
    """Eviction-candidate window (``vmax``) for the eviction kernel: the
    top-k priority pages considered per eviction call must cover a whole
    amortised batch (16 pages) of *maximum-size* pages even when the
    priority order is led by small column-tail / dimension-table pages —
    a multi-table pool mixes page sizes, the micro pool does not.  64 is
    the validated single-table floor; the median valid page size bounds
    how many candidates one batch can need, capped at 256 to keep the
    kernel's O(P * vmax)-ish work flat."""
    sizes = spec.page_size[spec.page_valid]
    if sizes.size == 0:
        return 64
    med = float(np.median(sizes))
    need = int(np.ceil(16 * float(np.max(sizes)) / max(med, 1.0))) + 16
    return int(min(256, max(64, need)))


def make_step(spec: SimSpec, dt: float, time_slice: float,
              prefetch_pages: int = 8, refresh: bool = False,
              policies: Sequence[ArrayPolicy] = ("lru", "pbm"),
              vmax: Optional[int] = None, stepper: str = "fixed",
              h_max: float = 8.0, h_io: float = 3.0,
              wake_exact: bool = True,
              page_axis: Optional[str] = None,
              telemetry: bool = False):
    """Build the pure ``step(carry, cfg) -> carry`` for a policy set.

    ``refresh=False`` is the cheap within-slice step; ``refresh=True`` is
    the once-per-``time_slice`` boundary step (the policies' ``StepCtx``
    carries the flag; PBM recomputes every page's next consumption and
    shifts its timeline there, and the step drops dead request-queue
    entries).  ``policies`` are the lanes this step can serve: a config's
    ``cfg.policy`` (registry array id) selects per lane between the
    policy-provided score/readahead/tie arrays — the step itself contains
    no per-policy branches.  Compiling a single policy specialises the
    step (no stacking, no unused machinery); compiling a cooperative
    policy (array-CScan) additionally builds the chunk-granular ABM
    substrate and blends the two consumption models per lane.

    ``stepper`` picks the time model:

    * ``"fixed"`` — every step advances the static ``dt``; the carry is
      ``(state, view)`` (bit-compatible with the pre-horizon engine);
    * ``"horizon"`` — the step advances a **variable** ``dt``: each step
      closes by computing the next step's event horizon (the earliest
      interesting time over the trigger-arrival / chunk-completion /
      io-credit / completion candidates, clipped to ``[dt, h_max*dt]``)
      together with the trigger window of the post-advance view, and the
      carry ``(state, view, win, rem, next_dt)`` threads both forward —
      so the window math is computed once per step in either mode.
      ``rem`` is the whole-fine-step budget left in the current PBM
      slice; the cheap step jumps ``min(next_h, rem - 1)`` fine steps
      and the refresh step absorbs the final (``<= h_max``) jump, which
      is what lets an uneventful slice collapse to ``ceil(n_inner /
      h_max)`` macro-steps — one at the smoke scales, where the slice
      fits inside ``h_max``.  ``h_io`` bounds
      the jump, in fine steps, while requests are pending — the
      wake-quantisation knob of the I/O-bound regime.

    ``wake_exact`` (STATIC, horizon only) replaces the supersaturated
    never-jump rule with the exact serial-server wake computation
    (DESIGN.md §10): with the request queue frozen at the end of a step,
    each queued page's grant step is solved in closed form
    (``kernels.ops.wake_solve``) and a supersaturated lane jumps
    straight to the first fine step that unblocks a stream — spanning
    slice boundaries when the refresh step absorbs up to ``_MAX_ABSORB``
    whole slices.  ``wake_exact=False`` restores the never-jump rule
    bit-for-bit.  Non-saturated lanes behave identically either way.

    ``page_axis`` (STATIC) is the mesh axis name of a page-sharded
    ``shard_map`` enclosure: the batched evict/grant kernels then scan
    only their own ``P / n`` pool slice for candidates and combine over
    the gathered compact lists — bitwise-identical to the unsharded
    path (see ``kernels.ops``).  The wake solve runs replicated (its
    output feeds lane-global jump decisions).

    ``telemetry`` is the STATIC obs knob (``repro.obs``, DESIGN.md §8):
    with it on, the step threads a :class:`~repro.obs.counters.Telemetry`
    pytree as the LAST carry element and accumulates jit-pure counters
    from values the step already computes; with it off (the default) the
    carry, the compiled program, and the results are exactly the
    pre-telemetry ones.

    The step's sections run under the named phases of ``repro.obs``
    (``step:cpu``, ``step:demand``, ``step:io``, ``step:evict``,
    ``step:obs``, ``step:horizon``; DESIGN.md §8): ``op_name`` metadata
    on the compiled ops, which a profiler capture attributes device
    time by, and nothing else.
    """
    from repro.kernels import ops as kops

    if stepper not in ("fixed", "horizon"):
        raise ValueError(f"unknown stepper {stepper!r}: fixed | horizon")
    horizon = stepper == "horizon"
    policies = resolve_policies(policies)
    P, S, Q, C = spec.n_pages, spec.n_streams, spec.n_queries, spec.n_cols
    vmax = _evict_candidates(spec) if vmax is None else int(vmax)
    K = int(prefetch_pages)
    # deepest per-column readahead actually reachable: the plan-entry-count
    # window spreads ~K entries over the scanned columns, so the scatter
    # only needs to walk K_LOOP+1 slots per (stream, column)
    K_LOOP = min(K, 4)
    # static per-column trigger lookahead: the most page triggers a scan
    # can cross in one step, plus one for the conservative advance cap.
    # The horizon stepper sizes it for the longest jump it can take —
    # h_max fine steps, or the whole slice when that is shorter (every
    # jump, the refresh tail included, is bounded by min(h_max, n_inner)
    # fine steps: inner_cond hands the tail to the refresh step only
    # once the clipped next_h reaches it),
    # with the compiler's per-column max-rate geometry keeping the window
    # from exploding on columns no fast scan ever touches.
    # one PBM slice is a whole number of fine steps (the fixed cadence
    # always rounded it so, and the whole validated envelope of PR 1-4
    # was fit against that rounding with the bucket math still using the
    # configured ``time_slice``).  The horizon stepper keeps BOTH: its
    # macro-steps are integer multiples of the fine step (``h`` fine
    # steps in one jump — which makes a non-jumping horizon run
    # bit-equal to the fixed stepper), and its slice budget is the same
    # ``n_inner`` fine steps.  At the deep-thrash operating points the
    # churn spiral is cliff-sensitive even to sub-ulp step-length drift
    # (a byte-credit equality at the grant boundary), which is exactly
    # why time is quantised instead of accumulated as f32 remainders.
    n_inner = max(1, int(round(time_slice / float(dt))))
    if horizon:
        h_max_i = max(1, int(round(h_max)))
        dt_long = float(dt) * min(h_max_i, n_inner)
        W = spec.trigger_window(max(float(dt), dt_long), tight=True)
        # budgeted FIFO pops per step: enough to drain an h_io-page jump
        # plus the banked credit (the fixed step's 6 cover ~2 pages + bank)
        n_rounds_io = max(_LOAD_MAX, int(round(h_io)) + 2)
        if wake_exact:
            # wake-exact supersaturated jumps span at most the slice
            # budget plus _MAX_ABSORB absorbed slices (and never more
            # than 64 fine steps — the wake solve's h_cap); the grant's
            # candidate window must cover every pop such a jump stands
            # in for.  Growing vmax alone is results-neutral: strict
            # head-of-line truncates at the pops SCALAR, which keeps the
            # PR-9 cap (n_rounds_io) on non-saturated lanes.
            wake_cap_i = min(64, max(h_max_i, (1 + _MAX_ABSORB) * n_inner))
            n_rounds = max(n_rounds_io, wake_cap_i * _LOAD_MAX)
        else:
            wake_cap_i = h_max_i
            n_rounds = n_rounds_io
    else:
        h_max_i = 1
        W = spec.trigger_window(float(dt))
        n_rounds = n_rounds_io = _LOAD_MAX
        wake_cap_i = 1
    dt_ref = jnp.float32(dt)
    h_io_f = jnp.float32(h_io)
    time_slice_f = jnp.float32(time_slice)

    page_size = jnp.asarray(spec.page_size)
    page_first = jnp.asarray(spec.page_first)
    page_last = jnp.asarray(spec.page_last)
    page_col = jnp.asarray(spec.page_col)
    page_valid = jnp.asarray(spec.page_valid)
    col_start = jnp.asarray(spec.col_start)
    col_npages = jnp.asarray(spec.col_npages)
    col_tpp = jnp.asarray(spec.col_tpp)
    q_start = jnp.asarray(spec.q_start)
    q_len = jnp.asarray(spec.q_len)
    q_rate = jnp.asarray(spec.q_rate)
    q_cols = jnp.asarray(spec.q_cols)
    n_q = jnp.asarray(spec.n_q)
    s_idx = jnp.arange(S)
    max_page = jnp.float32(float(np.max(spec.page_size)))
    INF = jnp.float32(np.inf)
    # supersaturation threshold for the horizon's io-credit candidate: the
    # aggregate plan-window bytes every stream can keep requested at once
    sat_bytes = jnp.float32(S * K * float(np.max(spec.page_size)))

    # ---- policy dispatch tables (policy-provided, id-indexed) ------------
    n_pol = len(policies)
    ids = policy_registry.array_ids()
    max_id = max(ids.values())
    lookup_np = np.zeros(max_id + 1, np.int32)
    valid_np = np.zeros(max_id + 1, bool)
    for j, p in enumerate(policies):
        lookup_np[ids[p.name]] = j
        valid_np[ids[p.name]] = True
    lookup = jnp.asarray(lookup_np)
    id_valid = jnp.asarray(valid_np)
    k_wins_np = np.asarray([p.request_window(spec, K) for p in policies],
                           np.int32)
    coop_idx = next((j for j, p in enumerate(policies) if p.cooperative),
                    None)
    has_coop = coop_idx is not None
    coop_flags = jnp.asarray([p.cooperative for p in policies])
    if has_coop:
        cc = coop_mod.coop_consts(spec)
        if spec.q_table is None:
            raise ValueError(
                "cooperative policy needs the multitable query-table map; "
                "lower the workload with compiler.compile_workload"
            )
        q_table = jnp.asarray(spec.q_table)

    def query_view(qidx, pos) -> _View:
        """Gather the per-stream view of the current query + per-column
        frontier cursors (plan-trigger granular, see :class:`_View`)."""
        qi = jnp.clip(qidx, 0, Q - 1)
        active = qidx < n_q
        start = q_start[s_idx, qi]
        length = q_len[s_idx, qi]
        rate = q_rate[s_idx, qi]
        cols = q_cols[s_idx, qi]                       # (S, C)
        cur = start + pos
        end = start + length
        # tolerance for "has the cursor crossed this trigger": one tuple
        # plus the f32 ulp of the cursor magnitude, so rounding in
        # ``cur + adv`` can never strand a trigger in limbo
        eps = 1.0 + 4e-7 * end
        local = jnp.floor(cur[:, None] / col_tpp[None, :]).astype(jnp.int32)
        local = jnp.clip(local, 0, col_npages[None, :] - 1)
        # page boundaries are exact ints but tpp is fractional: correct the
        # division so cur lands in [first, last) of its page (a cursor at a
        # boundary must map to the NEXT page or it stalls with adv_lim=0)
        pidx0 = col_start[None, :] + local
        local = local + (cur[:, None] >= page_last[pidx0]).astype(jnp.int32)
        local = local - (cur[:, None] < page_first[pidx0]).astype(jnp.int32)
        local = jnp.clip(local, 0, col_npages[None, :] - 1)
        pidx0 = col_start[None, :] + local
        # frontier: the containing page iff its trigger is still ahead of
        # (or at) the cursor, else the next page.  The trigger of the page
        # straddling the scan start is the start itself (engine plan).
        trig0 = jnp.maximum(page_first[pidx0], start[:, None])
        consumed0 = trig0 < cur[:, None] - eps[:, None]
        frontier = local + consumed0.astype(jnp.int32)  # (S, C), may == np
        fpidx = col_start[None, :] + jnp.minimum(
            frontier, col_npages[None, :] - 1
        )
        ftrig = jnp.maximum(page_first[fpidx], start[:, None])
        fneed = (
            active[:, None]
            & cols
            & (frontier < col_npages[None, :])
            & (page_first[fpidx] < end[:, None])
        )
        return _View(active, length, rate, cols, start, cur, end, eps,
                     frontier, fpidx, ftrig, fneed)

    def _sel(is_coop, coop_val, inorder_val):
        """Per-lane blend between the cooperative and in-order models.
        Specialises away when the compiled policy set is single-model."""
        if not has_coop:
            return inorder_val
        if n_pol == 1:
            return coop_val
        return jnp.where(is_coop, coop_val, inorder_val)

    # window of the next W+1 page triggers per (stream, column): entries
    # w < W gate the advance (block at the first absent trigger), entry
    # W is the conservative cap so one step never outruns the window
    wk = jnp.arange(W + 1)                                  # (W+1,)

    def window(view: _View):
        """Trigger-window geometry of a view: global page ids, triggers,
        need mask and cursor distance of the next W+1 plan triggers per
        (stream, column).  The fixed step computes it on its own view;
        the horizon step computes it once on the post-advance view and
        carries it to the next step (this step's ``view2`` window IS the
        next step's ``view`` window)."""
        w_local = view.frontier[:, :, None] + wk[None, None, :]
        w_pidx = col_start[None, :, None] + jnp.minimum(
            w_local, col_npages[None, :, None] - 1
        )
        w_trig = jnp.maximum(page_first[w_pidx], view.start[:, None, None])
        w_need = (
            view.fneed[:, :, None]
            & (w_local < col_npages[None, :, None])
            & (page_first[w_pidx] < view.end[:, None, None])
        )
        w_dist = jnp.maximum(w_trig - view.cur[:, None, None], 0.0)
        return w_pidx, w_trig, w_need, w_dist

    def adv_limit(win, resident):
        """Per-stream advance limit against a residency: distance to the
        first absent trigger, capped at the (W+1)-th trigger when every
        windowed page is resident (W is sized so the cap exceeds the
        longest step's advance for a full window)."""
        w_pidx, _w_trig, w_need, w_dist = win
        absent = w_need[:, :, :W] & ~resident[w_pidx[:, :, :W]]
        lim = jnp.min(jnp.where(absent, w_dist[:, :, :W], INF), axis=2)
        cap = jnp.where(w_need[:, :, W], w_dist[:, :, W], INF)
        return jnp.min(jnp.minimum(lim, cap), axis=1)       # (S,)

    def core(state: SimState, view: _View, win, cfg: ArraySimConfig, dt,
             h_u, adv_lim_in=None, pend_in=None, slices_u=None, tele=None):
        """One simulation step of length ``dt`` == ``h_u`` fine steps
        (``h_u`` is the static 1 under the fixed stepper, a traced i32
        under the horizon stepper — a macro-step stands in for ``h_u``
        fine steps and scales the per-fine-step processes accordingly).
        ``adv_lim_in`` is the carried advance limit the previous horizon
        step computed against this step's residency (the horizon IS that
        computation, so it is never done twice)."""
        with phases.phase("cpu"):
            # a config whose policy id is NOT in this step's compiled set must
            # not silently run as some other policy (a mislabeled lane in a
            # stacked sweep would be wrong science with no diagnostic).  A jit
            # step cannot raise, so an invalid lane trips the livelock guard
            # on its first step: the run terminates immediately with every
            # stream unfinished and ``extras["truncated"] = True`` — the flag
            # every harness already refuses to compare.
            ok_id = (
                (cfg.policy >= 0) & (cfg.policy <= max_id)
                & id_valid[jnp.clip(cfg.policy, 0, max_id)]
            )
            t2 = state.t + jnp.where(ok_id, dt, cfg.max_time + 1.0)
            pol_local = lookup[jnp.clip(cfg.policy, 0, max_id)]
            is_coop = coop_flags[pol_local] if has_coop else False
            # supersaturation of this lane (pool below the scans' aggregate
            # plan-window bytes): selects the wake-exact jump model in the
            # horizon tail and the matching pop cap at the macro grant
            sat = cfg.capacity_bytes < sat_bytes

            # ============ CPU: consume up to the first absent trigger =========
            (active, length, rate, _cols, start, cur, end, eps, frontier,
             _fpidx, _ftrig, fneed) = view
            # tie-break jitter for the LRU clock: every touch/load in one step
            # would otherwise share the exact timestamp t2, and eviction would
            # break those ties by page index — a SYSTEMATIC bias that carves
            # the pool into a stable always-evicted side and a resident elite
            # whose hit rate the event engine (with its total event-order
            # recency) never reaches.  A deterministic per-(page, step) hash
            # spanning _JIT_STEPS fine-step-lengths reproduces the engine's
            # order noise (its touch events spread over multi-step burst
            # intervals, so recency may genuinely invert across a few
            # neighbouring steps) while staying pure for jit/vmap (no RNG
            # state).  The amplitude is calibrated against the event engine at
            # the small-pool points, in units of the FINE step — a horizon
            # macro-step must not inflate it.
            jit_p = _JIT_STEPS * dt_ref * _u01(jnp.arange(P, dtype=jnp.uint32),
                                               state.steps, 40503)
            w_pidx, w_trig, w_need, w_dist = win                # (S, C, W+1)
            # per-(stream, query) CPU-rate skew: the event engine's burst
            # granularity paces each scan on its own event clock, so two scans
            # at the same position drift apart within a query; the fluid step
            # advances them in perfect lockstep, freezing phase alignments
            # that inflate sharing at tiny pools (zero-mean per-STEP noise
            # integrates away — the drift must be sustained within a query to
            # outrun a small pool's residency window, while a large pool still
            # tolerates it, exactly like the engine).  Deterministic hash of
            # (stream, query): pure, vmap-safe, zero-mean across queries.
            ur = _u01(jnp.arange(S, dtype=jnp.uint32), state.qidx, 48271)
            rate_j = rate * (1.0 + _RATE_JIT * (2.0 * ur - 1.0))
            adv_lim = adv_limit(win, state.resident) if adv_lim_in is None \
                else adv_lim_in
            runnable = active & (adv_lim > 0.0)
            remaining = length - state.pos
            adv_io = jnp.where(
                runnable,
                jnp.minimum(jnp.minimum(rate_j * dt, remaining), adv_lim),
                0.0,
            )
            adv_io = jnp.maximum(adv_io, 0.0)

            margin = jnp.maximum(0.5, 3e-5 * length)
            finished_io = runnable & (remaining - adv_io <= margin)

            # ============ cooperative CPU model (compiled on demand) ==========
            if has_coop:
                cstate: coop_mod.CoopState = state.pstate[coop_idx]
                q_tab = q_table[s_idx, jnp.clip(state.qidx, 0, Q - 1)]
                coop_cpu = coop_mod.cpu_phase(
                    cc, cstate, active=active, start=start, end=end,
                    cols=_cols, q_tab=q_tab, rate_j=rate_j, dt=dt,
                    credit_cap=rate_j * dt, resident=state.resident,
                    page_col=page_col, page_valid=page_valid, s_idx=s_idx,
                )
                adv = _sel(is_coop, coop_cpu.adv, adv_io)
                finished = _sel(is_coop, coop_cpu.finished, finished_io)
            else:
                adv, finished = adv_io, finished_io
            # invalid-lane freeze (see ok_id above): no consumption, no
            # completions — the lane must end truncated, not half-run
            adv = jnp.where(ok_id, adv, 0.0)
            finished = finished & ok_id
            cur2_pre = cur + adv_io

            qidx2 = state.qidx + finished.astype(jnp.int32)
            pos2 = jnp.where(finished, 0.0, state.pos + adv)
            newly_done = (qidx2 >= n_q) & (state.stream_done_t < 0)
            stream_done_t2 = jnp.where(newly_done, t2, state.stream_done_t)

            # speed estimation on the engine's report cadence, not per step: a
            # per-step EWMA collapses a blocked scan's estimate toward zero in
            # a few ms, which sends the very pages it waits for to far-future
            # buckets.  The engine instead measures (Δvirt_pos / Δt) between
            # consecutive bursts — stall time folded in, progressless intervals
            # skipped — so the estimate tracks the *effective* scan rate.  The
            # array analogue updates once per PBM slice from the cumulative
            # consumed-tuples counter (refresh step below).
            consumed2 = state.consumed + adv
            next_rate = q_rate[s_idx, jnp.clip(qidx2, 0, Q - 1)]
            speed1 = jnp.where(finished, next_rate, state.speed)  # fresh scan
            if refresh:
                prog = consumed2 - state.consumed_ref
                # a wake-exact refresh step may stand in for several slices:
                # the burst-report cadence is then slices_u slice lengths
                if slices_u is None:
                    inst = prog / time_slice_f
                else:
                    inst = prog / (time_slice_f
                                   * slices_u.astype(jnp.float32))
                speed2 = jnp.where(
                    active & (prog > _PROG_MIN) & ~finished,
                    _BURST_W * next_rate + (1.0 - _BURST_W) * inst,
                    speed1,
                )
                consumed_ref2 = consumed2
            else:
                speed2 = speed1
                consumed_ref2 = state.consumed_ref

            # pages consumed this step: resident windowed pages whose trigger
            # the cursor crossed (same predicate the next view's frontier uses,
            # so crossing and frontier advance can never disagree)
            crossed = (
                w_need[:, :, :W]
                & runnable[:, None, None]
                & state.resident[w_pidx[:, :, :W]]
                & (w_trig[:, :, :W] < (cur2_pre - eps)[:, None, None])
            )
            if has_coop:
                crossed = crossed & jnp.logical_not(is_coop)
            cross_pidx = w_pidx[:, :, :W]
            # engine parity: the LRU clock ticks when a page is consumed, and
            # only the pages of the running burst are pinned — a blocked scan
            # pins nothing, a mid-page scan pins nothing it already consumed
            last_used2 = state.last_used.at[cross_pidx].max(
                jnp.where(crossed, t2 + jit_p[cross_pidx], -INF)
            )
            if has_coop:
                touched_coop = is_coop & coop_cpu.consumed_pages
                last_used2 = jnp.where(touched_coop, t2 + jit_p, last_used2)

        # ================= post-advance view (I/O demand) =================
        with phases.phase("demand"):
            view2 = query_view(qidx2, pos2)
            (active2, _l2, rate2, cols2, start2, cur2, end2, eps2, frontier2,
             fpidx2, ftrig2, need2) = view2

            # request set = the engine's plan window: the blocking page (the
            # trigger the cursor sits on) plus the next ~K *plan entries* in
            # (trigger, column, page) order.  Crucially a far-trigger frontier
            # page (a sparse column whose next boundary is many dense-pages
            # ahead) is NOT demanded early: the engine only requests it once it
            # enters the plan window.  Early fetches age out of a small pool
            # and reload — churn the engine does not have.
            inv_tpp = 1.0 / col_tpp[None, :]
            dens = jnp.sum(jnp.where(need2, inv_tpp, 0.0), axis=1, keepdims=True)
            # one fused scatter over K_LOOP+1 plan-window slots per (stream,
            # column); K_LOOP bounds the per-column scatter walk
            ks = jnp.arange(K_LOOP + 1)                    # (K_LOOP+1,)
            pf_local = frontier2[:, :, None] + ks[None, None, :]
            exists = (pf_local < col_npages[None, :, None]) & need2[:, :, None]
            pf_pidx = col_start[None, :, None] + jnp.minimum(
                pf_local, col_npages[None, :, None] - 1
            )
            pf_trig = jnp.maximum(page_first[pf_pidx], start2[:, None, None])
            exists &= page_first[pf_pidx] < end2[:, None, None]
            # the engine prefetches the next K *plan entries* — an entry-COUNT
            # window over the (trigger, column, page) plan order, resident
            # entries included in the budget.  The count cut matters: it can
            # leave a same-trigger group partner just outside the window, to be
            # discovered only at the next wake (see the request gate below) —
            # the separation behind the engine's small-pool churn.
            e_trig = jnp.where(exists, pf_trig, INF)
            flat_trig = e_trig.reshape(S, C * (K_LOOP + 1))
            flat_ord = jnp.argsort(jnp.argsort(flat_trig, axis=1), axis=1)
            # argsort twice = rank in the plan order; jnp.argsort is stable, so
            # ties resolve by (column, page) flat position — the engine's plan
            # sort key (trigger, column, index)
            rank = flat_ord.reshape(S, C, K_LOOP + 1)
            # the k=0 slot (the frontier itself) is always requested once its
            # trigger reaches the cursor — the blocking demand — even with
            # prefetch disabled
            blocking = (ks[None, None, :] == 0) & (
                pf_trig <= (cur2 + eps2)[:, None, None]
            )
            # request cadence gate, engine parity: a scan issues requests only
            # while it runs (burst ends) and at the instant it blocks — a
            # blocked scan's window is FROZEN until its demand loads.  Pages
            # entering the window mid-wait are not requested until the wake,
            # which is what separates group partners into distant queue
            # positions (continuous re-wanting erased that separation and with
            # it most of the engine's small-pool churn).
            ug = _u01(jnp.arange(S, dtype=jnp.uint32), state.steps,
                      3266489917, idx_mult=2246822519)
            # the engine's refresh rate follows its wake rate, which rises
            # with I/O pressure: scale by the lifetime duty cycle (a stalled
            # scan wakes per demand load ~= often; a CPU-bound scan re-issues
            # only per burst, where the continuous window already covers it)
            duty_g = jnp.clip(
                (state.consumed / jnp.maximum(state.t, 1e-9))
                / jnp.maximum(rate, 1.0),
                0.0, 1.0,
            )
            gate_p = _GATE_P * (1.0 - duty_g)
            if horizon:
                # a macro-step stands in for h_u fine steps: the blocked-scan
                # window-refresh is a per-fine-step Bernoulli process, so the
                # macro step fires it with the compounded probability —
                # otherwise longer jumps would silently freeze blocked
                # windows.  h_u == 1 keeps gate_p exactly (bit-parity with
                # the fixed stepper; pow would round at the ulp level).
                gate_p = jnp.where(
                    h_u == 1, gate_p,
                    1.0 - (1.0 - gate_p) ** h_u.astype(jnp.float32),
                )
            gate = (
                (adv_io > 0.0) | (state.steps == 0) | finished | (ug < gate_p)
            )
            # per-policy readahead width (ArrayPolicy.request_window): e.g. the
            # array LRU widens the engine's 8-entry window at single-table
            # deep thrash — a policy-provided value, indexed by the lane's id
            if n_pol == 1:
                k_win = int(k_wins_np[0])
            else:
                k_win = jnp.asarray(k_wins_np)[pol_local]
            # the blocking demand is exempt from the gate: the engine requests
            # the page it blocks on unconditionally, and a frontier page that
            # was resident at the block transition but evicted during the wait
            # would otherwise stall for a geometric number of steps before its
            # demand is even queued
            ok = exists & (((rank <= k_win) & gate[:, None, None]) | blocking)
            kb = jnp.where(ks == 0, 31, jnp.clip(K_LOOP + 1 - ks, 1, 30))
            bonus = jnp.full(P, -1, jnp.int32).at[pf_pidx].max(
                jnp.where(ok, kb[None, None, :], -1)
            )
            in_plan_window = (bonus >= 0) & ~state.resident & page_valid
            # FIFO request queue, array-form: every page keeps the step at which
            # it was first requested, and — engine parity — the request STAYS
            # queued after the cursor's plan window moves past it: the engine
            # only drops an entry when the page loads or the requesting query
            # ends.  Those stale early fetches (served hundreds of grants after
            # they were issued, evicted before their scan arrives, re-requested)
            # are most of the engine's small-pool churn; forgetting them made
            # the array 15-25% too fast below 20% buffer.  The array clears
            # stamps on load, and at each slice refresh for pages no active
            # scan is interested in (the query-end drop, slice-quantised).
            wanted = in_plan_window | (
                (state.req_step != _REQ_NONE) & ~state.resident & page_valid
            )
            req_step2 = jnp.where(
                wanted, jnp.minimum(state.req_step, state.steps + 1), _REQ_NONE
            )
            # strict FIFO by first-wanted step (engine parity: a demand request
            # does NOT jump ahead of older readahead in the serial queue).
            # Ties within one step's cohort resolve by a hash fixed at stamp
            # time — the engine's enqueue order is equally arbitrary, but a
            # deterministic page-index order would serve the same streams first
            # every cohort and freeze fine phase alignments between overlapping
            # scans that the event engine's noise dissolves.  The bonus only
            # defines membership of the wanted set, not the service order.
            stamp_age = jnp.clip(state.steps + 1 - req_step2, 0, 32767)
            # within-cohort service order: the engine enqueues a woken scan's
            # whole window CONTIGUOUSLY (one event = adjacent queue slots), and
            # the order of scans within one array step is event-timing noise.
            # So the cohort rank is (stream hash, plan rank) — a per-stream
            # block — fixed at stamp time like the engine's queue position.
            s_ord = (512.0 * _u01(jnp.arange(S, dtype=jnp.uint32),
                                  state.steps, 40503)).astype(jnp.int32)
            slot = s_ord[:, None, None] * 64 + jnp.clip(rank, 0, 63)
            tie_now = jnp.full(P, 32767, jnp.int32).at[pf_pidx].min(
                jnp.where(ok, slot, 32767)
            )
            new_stamp = wanted & (state.req_step == _REQ_NONE)
            req_tie2 = jnp.where(new_stamp, tie_now, state.req_tie)
            tie_blk = 32767 - req_tie2
            tie_idx = 32767 - jnp.arange(P, dtype=jnp.int32)
            # per-policy cohort order (ArrayPolicy.fifo_tie): the array LRU
            # tracks the engine best with the stream-block order; estimate-
            # driven policies with the plan-deterministic index order (their
            # scores already absorb the timing noise)
            tie_tab = [tie_idx if p.fifo_tie == "plan" else tie_blk
                       for p in policies]
            if n_pol == 1:
                tie15 = tie_tab[0]
            else:
                tie15 = jnp.stack(tie_tab)[pol_local]
            load_key = jnp.where(wanted, stamp_age * 32768 + tie15, -1)

        # ================= I/O server: budgeted admission =================
        with phases.phase("io"):
            used = jnp.sum(page_size * state.resident)
            free = cfg.capacity_bytes - used
            # engine parity: a running scan pins the pages of its current CPU
            # burst — the last ~segment_pages plan entries behind the cursor —
            # for the burst's duration; a blocked scan pins nothing, so pools
            # far below streams x columns pages cannot livelock.  The array
            # analogue pins pages whose trigger lies within a segment length
            # (segment_pages plan entries ~= seg/dens tuples) behind the cursor
            # of a stream that advanced this step.
            seg_len = _SEG_PAGES / jnp.maximum(dens[:, 0], 1e-30)  # (S,) tuples
            bk = jnp.arange(_SEG_WIN)                           # (B,)
            b_local = frontier2[:, :, None] - 1 - bk[None, None, :]
            b_pidx = col_start[None, :, None] + jnp.clip(
                b_local, 0, col_npages[None, :, None] - 1
            )
            b_trig = jnp.maximum(page_first[b_pidx], start2[:, None, None])
            burst = (
                (b_local >= 0)
                & (cols2 & active2[:, None])[:, :, None]
                & runnable[:, None, None]
                & (b_trig >= (cur2 - seg_len)[:, None, None])
            )
            pin = jnp.zeros(P, jnp.int32).at[b_pidx].max(burst.astype(jnp.int32))
            evictable_io = state.resident & (pin == 0) & page_valid

            # ============ cooperative I/O model (compiled on demand) ==========
            if has_coop:
                done3 = coop_mod.clear_on_query_change(
                    coop_cpu.done, coop_cpu.finished
                )
                q_tab2 = q_table[s_idx, jnp.clip(qidx2, 0, Q - 1)]
                coop_io = coop_mod.io_phase(
                    cc, done=done3, cur_chunk=coop_cpu.cur_chunk,
                    inflight=cstate.inflight, pin_pages=coop_cpu.pin_pages,
                    active=active2, start=start2, end=end2, cols=cols2,
                    q_tab=q_tab2, resident=state.resident, free=free,
                    page_chunk_sizes=page_size, page_col=page_col,
                    page_valid=page_valid, n_streams=S,
                )
                load_key = _sel(is_coop, coop_io.load_key, load_key)
                wanted = _sel(is_coop, coop_io.wanted, wanted)
                evictable = _sel(is_coop, coop_io.evictable, evictable_io)
            else:
                evictable = evictable_io
            evictable_bytes = jnp.sum(page_size * evictable)
            headroom = free + evictable_bytes
            credit = state.io_credit + cfg.bandwidth * dt

            # an invalid lane's server grants nothing (ok_id freeze)
            budget = jnp.where(ok_id, jnp.minimum(credit, headroom), 0.0)
            if horizon:
                # serial-server causality over a macro-step: credit accrued
                # while the queue was EMPTY must not fund requests that only
                # appear at the end of the jump (the engine's idle server
                # banks about one fine step of work, no more).  Cap this
                # step's serviceable bytes at the queue content present when
                # the jump began plus one fine step's credit — which also
                # makes the cap vacuous at h_u == 1 (fixed-stepper parity).
                # The queue bytes were computed by the previous step's
                # horizon (they ARE its io-credit candidate) and carried.
                pend_bytes0 = pend_in
                if has_coop:
                    infl0 = cstate.inflight
                    pend_c0 = (
                        (cc.page_chunk == jnp.clip(infl0, 0, cc.n_chunks - 1))
                        & (infl0 >= 0) & ~state.resident & page_valid
                    )
                    pend_bytes0 = _sel(
                        is_coop, jnp.sum(page_size * pend_c0), pend_bytes0
                    )
                budget = jnp.minimum(
                    budget,
                    state.io_credit + pend_bytes0 + cfg.bandwidth * dt_ref,
                )
                # budgeted FIFO pop as ONE batched grant op — the macro grant
                # covers an h_io-fine-step jump without n_rounds serial
                # argmax passes over the page axis (Pallas head-of-queue kernel
                # on TPU, top_k + prefix-product oracle elsewhere).
                # Semantics match the fixed loop: strict head-of-line (the
                # first page that does not fit blocks the rest), ties by
                # lower page index, _LOAD_MAX pops per fine step stood in
                # for — all inside the static n_rounds top-k window, which
                # therefore also caps a multi-step grant's pop count (the
                # byte budget of an h_io-step jump fits the window at the
                # validated operating points; credit a short window leaves
                # unspent banks for the next step, like the fixed path's
                # leftover credit).
                if wake_exact:
                    # non-saturated lanes keep the PR-9 pop cap bit-for-bit;
                    # a wake-exact supersaturated jump needs every pop its
                    # fine steps would have taken
                    pop_cap = jnp.where(sat, n_rounds, n_rounds_io)
                else:
                    pop_cap = n_rounds
                pops = jnp.minimum(h_u * _LOAD_MAX, pop_cap)
                load_mask, load_bytes, n_load = kops.fifo_grant(
                    load_key, page_size, budget, pops, vmax=n_rounds,
                    page_axis=page_axis,
                )
                cand = cand_ok = None
            else:
                # the server grants at most ~credit bytes (a handful of pages)
                # per step: pop the FIFO head a few times instead of sorting
                # anything.  Head-of-line semantics: the first page that does
                # not fit blocks the rest of the queue, like the engine's
                # serial server.
                kcur = load_key
                taken = jnp.float32(0.0)
                open_ = jnp.bool_(True)
                arange_p = jnp.arange(P)
                hit = jnp.zeros(P, bool)
                cand = []
                cand_ok = []
                for _ in range(n_rounds):
                    j = jnp.argmax(kcur)
                    ok_j = open_ & (kcur[j] >= 0) & (
                        taken + page_size[j] <= budget
                    )
                    open_ = ok_j
                    is_j = arange_p == j   # arithmetic mask: fuses, scatter won't
                    hit = hit | (is_j & ok_j)
                    taken = taken + jnp.where(ok_j, page_size[j], 0.0)
                    kcur = jnp.where(is_j, -1, kcur)
                    cand.append(j)
                    cand_ok.append(ok_j)
                load_mask = hit
                cand = jnp.stack(cand)                     # (n_rounds,)
                cand_ok = jnp.stack(cand_ok)
                load_bytes = taken
                n_load = jnp.sum(cand_ok)

            # bank leftover credit instead of zeroing it whenever the request
            # queue went momentarily empty — that dropped the partially-funded
            # head-of-line load and made effective bandwidth dip below
            # cfg.bandwidth on bursty workloads.  The cap stays at 4 pages
            # while requests remain unserved (funding the next grants); with an
            # empty queue one page-time is kept, compensating the idle server's
            # ability to start a load the instant a request arrives mid-step
            # (the engine's serial server never banks more idle time than that).
            leftover = credit - load_bytes
            starved_io = jnp.sum(wanted & ~load_mask) > 0
            credit_cap = jnp.where(starved_io, 4 * max_page, max_page)
            io_credit2 = jnp.minimum(leftover, credit_cap)

        # ================= policy hooks + batched eviction ================
        with phases.phase("evict"):
            # engine speed-estimate DIPS: the dict engine's per-burst EWMA
            # crashes toward the effective rate at every stall exit, and pages
            # pushed during a dip land in far-future buckets — prime eviction
            # victims although their consumption is imminent.  That mis-push
            # churn (7% of engine loads at 40% buffer, ~20% at 10%) never
            # happens with a smooth estimate, leaving the array faster than
            # the machine it models.  Sample the dips per (stream, step).
            ud = _u01(jnp.arange(S, dtype=jnp.uint32), state.steps, 3266489917)
            eff_rate = jnp.clip(
                state.consumed / jnp.maximum(state.t, 1e-9),
                1.0, None,
            )
            dip_p = jnp.float32(_DIP_P)
            if horizon:
                # the dip is a per-FINE-step Bernoulli (calibrated against
                # the engine's stall-exit EWMA crashes): a macro-step
                # standing in for h_u fine steps fires it with the
                # compounded probability, like the request gate above —
                # h_u == 1 keeps _DIP_P exactly (fixed-stepper bit parity).
                # Without this the wake-exact path under-samples dips and
                # ran ~18% too fast at the 10% deep-thrash point.
                dip_p = jnp.where(
                    h_u == 1, dip_p,
                    1.0 - (1.0 - dip_p) ** h_u.astype(jnp.float32),
                )
            speed_push = jnp.where(
                ud < dip_p, jnp.minimum(_DIP_DEPTH * eff_rate, speed2), speed2
            )

            # pages whose consumption state changed this step (feeds the churn
            # diagnostic below and PBM's within-slice update set)
            was_crossed = jnp.zeros(P, bool).at[cross_pidx].max(crossed)
            if has_coop:
                was_crossed = _sel(is_coop, coop_cpu.consumed_pages,
                                   was_crossed)
            if horizon:
                # compacted within-slice update set: the padded (S, C, W)
                # cross window grows with the horizon's longer trigger
                # lookahead, but the pages that actually changed stay few —
                # hand the policies a dense id list instead of the padded
                # window (duplicates and the fill id carry ``upd_on`` False
                # or an identical update value, so the min-combining scatter
                # is unchanged; overflow beyond the static cap merely leaves
                # a page's bucket stale until the slice refresh).
                upd_mask = (was_crossed | load_mask) & page_valid
                upd_pages = jnp.nonzero(upd_mask, size=min(P, 512),
                                        fill_value=0)[0]
                upd_on = upd_mask[upd_pages]
            else:
                upd_pages = upd_on = None
            ctx = StepCtx(
                spec=spec, refresh=refresh, time_slice=time_slice_f, now=t2,
                steps=state.steps, slices_done=state.slices_done,
                slices_elapsed=slices_u, dt=dt,
                page_first=page_first, page_last=page_last, page_col=page_col,
                page_valid=page_valid, resident=state.resident,
                last_used=last_used2, load_mask=load_mask, load_cand=cand,
                load_ok=cand_ok, cross_pidx=cross_pidx, crossed=crossed,
                upd_pages=upd_pages, upd_on=upd_on,
                active=active2, cols=cols2, cur=cur2, end=end2, start=start2,
                eps=eps2, rate=rate2, speed_push=speed_push,
                coop=coop_io if has_coop else None,
            )
            pstate2 = []
            for p, ps in zip(policies, state.pstate):
                if p.cooperative:
                    # the cooperative substrate owns its state transitions
                    pstate2.append(coop_mod.CoopState(
                        done=done3, cur_chunk=coop_cpu.cur_chunk,
                        chunk_pos=coop_cpu.chunk_pos, credit=coop_cpu.credit,
                        inflight=coop_io.inflight,
                    ))
                else:
                    pstate2.append(p.on_consume(p.on_request(ps, ctx), ctx))
            keys = [p.score_victims(ps, ctx)
                    for p, ps in zip(policies, pstate2)]
            if n_pol == 1:
                key = keys[0]
            else:
                key = jnp.stack(keys)[pol_local]

            if refresh:
                # query-end request drop, slice-quantised: pending requests for
                # pages no active scan is interested in leave the queue
                interested = (ctx.eta_estimate() < BIG_CUT) & page_valid
                req_step2 = jnp.where(interested, req_step2, _REQ_NONE)
                slices_done2 = state.slices_done + (
                    jnp.int32(1) if slices_u is None else slices_u
                )
            else:
                slices_done2 = state.slices_done

            # engine parity: evictions are amortised in batches (>= 16 pages),
            # so a triggered eviction frees up to a whole batch, not one page.
            # The cooperative server instead evicts exactly the victims its
            # chunk needs (ABM plans evictions per load decision).
            batch = jnp.minimum(16 * max_page, cfg.capacity_bytes)
            need_io = jnp.where(
                load_bytes > free,
                jnp.minimum(jnp.maximum(load_bytes, batch) - free,
                            evictable_bytes),
                0.0,
            )
            if has_coop:
                need_coop = jnp.where(
                    load_bytes > free,
                    jnp.minimum(load_bytes - free, evictable_bytes),
                    0.0,
                )
                need_free = _sel(is_coop, need_coop, need_io)
            else:
                need_free = need_io
            evict = kops.batched_evict(key, page_size, evictable, need_free,
                                       vmax=vmax, page_axis=page_axis)

            resident2 = (state.resident & ~evict) | load_mask
            last_used3 = jnp.where(load_mask, t2 + jit_p, last_used2)
            # churn diagnostic: a page evicted while still "fresh" (loaded but
            # never consumed since) was a wasted load
            fresh2 = jnp.where(load_mask, True,
                               state.fresh & ~was_crossed & resident2)
            churn2 = state.churn + jnp.sum(state.fresh & evict & ~was_crossed)
            req_step3 = jnp.where(load_mask, _REQ_NONE, req_step2)
            demand_hit = load_mask & (bonus == 31)
            if has_coop:
                demand_hit = demand_hit & jnp.logical_not(is_coop)

            new_state = SimState(
                resident=resident2,
                last_used=last_used3,
                req_step=req_step3,
                req_tie=req_tie2,
                fresh=fresh2,
                qidx=qidx2,
                pos=pos2,
                speed=speed2,
                consumed=consumed2,
                consumed_ref=consumed_ref2,
                stream_done_t=stream_done_t2,
                t=t2,
                steps=state.steps + 1,
                slices_done=slices_done2,
                io_credit=io_credit2,
                io_bytes=state.io_bytes + load_bytes,
                loads=state.loads + n_load,
                loads_demand=state.loads_demand + jnp.sum(demand_hit),
                churn=churn2,
                pstate=tuple(pstate2),
            )

        # ============ obs tier 1: jit-pure carried counters ===============
        # (repro.obs, DESIGN.md §8).  Every source below is a value the
        # step computed anyway; every update goes through the pure
        # obs.count/obs.hist helpers — the analysis lint's host-callback
        # ban (rule jit-host-callback) keeps this the only telemetry
        # channel inside traced regions.  ``tele is None`` is static:
        # with telemetry off this whole block compiles to nothing.
        if tele is None:
            tele2 = None
        else:
            with phases.phase("obs"):
                hits_ev = jnp.sum(crossed)
                if has_coop:
                    hits_ev = hits_ev + jnp.sum(touched_coop)
                    picks = coop_mod.chunk_pick(
                        cstate.inflight, coop_io.inflight
                    ) & is_coop
                else:
                    picks = jnp.bool_(False)
                depth = jnp.sum((req_step3 != _REQ_NONE) & page_valid)
                # victim rank in the policy's score order (0 = top victim):
                # double argsort of the masked score, the rank histogram's
                # high bins = the kernel digging past the policy preference
                vrank = jnp.argsort(jnp.argsort(
                    -jnp.where(evictable, key, -INF)
                ))
                pol_rows = []
                for j, (p, ps) in enumerate(zip(policies, pstate2)):
                    row = tele.pol_obs[j]
                    if row.shape[0]:
                        o = p.observe(ps, ctx)
                        if n_pol > 1:
                            o = jnp.where(pol_local == j, o, 0.0)
                        row = row + o
                    pol_rows.append(row)
                tele2 = tele._replace(
                    hits=obs.count(tele.hits, hits_ev),
                    misses=obs.count(tele.misses, demand_hit),
                    loads=obs.count(tele.loads, n_load),
                    evictions=obs.count(tele.evictions, evict),
                    evict_rank=obs.hist(tele.evict_rank,
                                        obs.log2_bin(vrank + 1), evict),
                    jump_hist=obs.hist(tele.jump_hist, obs.log2_bin(h_u), 1),
                    ioq_depth_sum=obs.count(tele.ioq_depth_sum, depth),
                    ioq_depth_max=jnp.maximum(tele.ioq_depth_max, depth),
                    chunk_picks=obs.count(tele.chunk_picks, picks),
                    pol_obs=tuple(pol_rows),
                )

        if not horizon:
            return new_state, view2, None, tele2

        # ================= event horizon of the NEXT step =================
        with phases.phase("horizon"):
            # The earliest "interesting" time ahead, from the same machinery
            # the policies already expose: the post-advance trigger window
            # (computed here ONCE and carried — it is the next step's view
            # window), the pending request queue, the cooperative chunk state,
            # and the per-policy scan_horizon hooks.  Everything is a lower
            # bound on "nothing the discretisation cares about happens before
            # then"; overshoot is impossible because the CPU advance clamps at
            # the first absent trigger and the refresh cadence is capped by
            # the slice remainder in the runner's loop nest.
            win2 = window(view2)
            adv_lim2 = adv_limit(win2, resident2)
            runnable2 = active2 & (adv_lim2 > 0.0)
            remaining2 = jnp.maximum(_l2 - pos2, 0.0)
            # next trigger arrival / stream completion: how long each runnable
            # scan can burn CPU before it blocks, finishes, or outruns the
            # window cap (rate without the per-query jitter: an 8% overshoot
            # only means the scan blocks slightly before the jump ends)
            t_cpu = jnp.where(
                runnable2,
                jnp.minimum(adv_lim2, remaining2) / jnp.maximum(rate2, 1.0),
                INF,
            )
            # io-credit horizon: while requests are pending the server is the
            # clock.  Non-saturated lanes jump at most h_io page-transfer
            # times at the lane's own bandwidth (the wake-quantisation knob;
            # blocked scans wake at jump end instead of mid-jump).
            # SUPERSATURATED lanes — pool below the scans' aggregate plan
            # window (streams x readahead entries), the engine's churn-spiral
            # regime — used to keep the fine cadence entirely.  Under
            # ``wake_exact`` they instead jump by the EXACT serial-server
            # wake: with the queue frozen at this step's end the server's
            # future is deterministic (each fine step banks bandwidth*dt_ref
            # more credit and pops at most _LOAD_MAX fitting heads), so each
            # queued page's grant step has a closed form (kernels.ops
            # wake_solve, DESIGN.md §10) and the lane jumps straight to the
            # first fine step that unblocks a stream — the dominant residual
            # cost at deep thrash was exactly these h=1 crawl steps.
            wanted3 = (req_step3 != _REQ_NONE) & ~resident2 & page_valid
            pend_bytes2 = jnp.sum(jnp.where(wanted3, page_size, 0.0))
            pend2 = pend_bytes2 > 0.0
            t_io_base = h_io_f * dt_ref
            if wake_exact:
                # the queue key the NEXT step will serve: same stamp-FIFO
                # construction as load_key, one step older (stamps are
                # carried, ties were fixed at stamp time — uniform aging
                # keeps the service order; later arrivals rank behind every
                # frozen entry, so the predicted prefix is exact)
                stamp_age3 = jnp.clip(state.steps + 2 - req_step3, 0, 32767)
                wake_key = jnp.where(wanted3, stamp_age3 * 32768 + tie15, -1)
                # only a saturated in-order lane reads the solve (t_io_pend
                # below; cooperative lanes keep their own candidates): every
                # other lane asks it for zero pops, and it pops nothing
                wake_pops = _sel(is_coop, jnp.int32(0),
                                 jnp.where(sat, _LOAD_MAX, 0))
                wake_step = kops.wake_solve(
                    wake_key, page_size, io_credit2,
                    cfg.bandwidth * dt_ref, wake_pops,
                    h_cap=wake_cap_i,
                )
                if tele2 is not None:
                    with phases.phase("obs"):
                        tele2 = tele2._replace(wake_solves=obs.count(
                            tele2.wake_solves, wake_pops > 0))
                # a blocked stream wakes when EVERY absent page it sits on
                # (trigger at/behind the cursor, all columns) is granted:
                # per-stream max over those pages' grant steps, then the
                # lane jumps to the EARLIEST such wake
                w_pidx2, _wt2, w_need2, w_dist2 = win2
                absent2 = w_need2[:, :, :W] & ~resident2[w_pidx2[:, :, :W]]
                d0 = absent2 & (w_dist2[:, :, :W] <= 0.0)
                kp = jnp.where(
                    d0, wake_step[w_pidx2[:, :, :W]].astype(jnp.float32), 0.0
                )
                k_stream = jnp.max(kp, axis=(1, 2))
                blocked_s = active2 & ~runnable2 & jnp.any(d0, axis=(1, 2))
                k_wake = jnp.min(jnp.where(blocked_s, k_stream, INF))
                # headroom guard: the solve's credit cadence is only real
                # while the pool (free + evictable bytes) can absorb it —
                # past that the budget pins at headroom and the schedule is
                # no longer a lower bound; fall back to the fine cadence.
                # No blocked stream at all: the CPU candidates own the
                # horizon, quantised like a non-saturated pending jump.
                can_jump = jnp.isfinite(k_wake) & (
                    io_credit2 + k_wake * cfg.bandwidth * dt_ref <= headroom
                )
                t_wake = jnp.where(
                    can_jump, (k_wake + 0.25) * dt_ref,
                    jnp.where(jnp.isfinite(k_wake), dt_ref, t_io_base),
                )
                t_io_pend = jnp.where(sat, t_wake, t_io_base)
            else:
                t_io_pend = jnp.where(sat, 0.0, t_io_base)
            t_io = jnp.where(pend2, t_io_pend, INF)
            if has_coop:
                # cooperative lanes: the in-order trigger candidate is
                # meaningless (consumption is chunk-granular, out of order);
                # the chunk in flight plays the pending queue's role.  The
                # wake solve models the in-order stamp queue, not chunks —
                # cooperative lanes keep the pre-wake-exact candidates.
                t_cpu = _sel(is_coop, jnp.full(S, INF), t_cpu)
                t_io_coop = (jnp.where(sat, 0.0, t_io_base) if wake_exact
                             else t_io_pend)
                t_io = _sel(
                    is_coop,
                    jnp.where(coop_io.inflight >= 0, t_io_coop, INF),
                    t_io,
                )
            # per-policy horizon providers (ArrayPolicy.scan_horizon): e.g.
            # array-CScan reports each stream's current-chunk completion
            hz = HorizonView(spec=spec, active=active2, start=start2, end=end2,
                             rate=rate2, dt_ref=dt_ref)
            t_tab = [p.scan_horizon(ps, hz) for p, ps in zip(policies, pstate2)]
            if any(t is not None for t in t_tab):
                t_tab = [jnp.full(S, INF) if t is None else t for t in t_tab]
                t_pol = t_tab[0] if n_pol == 1 else \
                    jnp.stack(t_tab)[pol_local]
                t_pol_min = jnp.min(t_pol)
            else:
                t_pol_min = INF
            next_dt = jnp.minimum(jnp.minimum(jnp.min(t_cpu), t_io), t_pol_min)
            # quantise to whole fine steps (floor: undershooting a horizon
            # only costs an extra step; overshooting would cost fidelity).
            # Wake-exact supersaturated lanes may plan past h_max up to the
            # wake cap — the slice loop still clips each macro-step at the
            # boundary, and the refresh step absorbs whole slices from the
            # surplus (_MAX_ABSORB at most).
            if wake_exact:
                h_cap_lane = jnp.where(sat, wake_cap_i, h_max_i)
            else:
                h_cap_lane = h_max_i
            next_h = jnp.clip(
                jnp.floor(next_dt / dt_ref).astype(jnp.int32), 1, h_cap_lane
            )
        return new_state, view2, (win2, adv_lim2, pend_bytes2, next_h), tele2

    # telemetry rides at the END of every carry so the loop conditions'
    # positional reads (cond: c[0]; inner_cond: c[5], c[6]) are identical
    # with the knob on or off
    if not horizon:
        def step(carry, cfg: ArraySimConfig):
            if telemetry:
                state, view, tele = carry
            else:
                (state, view), tele = carry, None
            new_state, view2, _, tele2 = core(state, view, window(view),
                                              cfg, dt_ref, 1, tele=tele)
            if telemetry:
                return new_state, view2, tele2
            return new_state, view2
    elif refresh:
        def step(carry, cfg: ArraySimConfig):
            # slice-boundary step: absorb the slice remainder (at most
            # h_max fine steps — inner_cond only hands the tail over
            # once next_h reaches it), then re-arm the slice budget of
            # n_inner fine steps
            if telemetry:
                state, view, win, adv_lim, pend, rem_u, _next_h, tele = carry
            else:
                state, view, win, adv_lim, pend, rem_u, _next_h = carry
                tele = None
            if wake_exact:
                # a wake-exact supersaturated jump may clear whole
                # slices beyond this one's tail: absorb up to
                # _MAX_ABSORB of them into this refresh step — the PBM
                # timeline shift, the slice counter and the speed-EWMA
                # cadence all advance by the absorbed count
                # (shift_timeline takes the multi-slice k directly).
                # Non-saturated lanes absorb exactly the tail, as before.
                sat_l = cfg.capacity_bytes < sat_bytes
                extra = jnp.where(
                    sat_l,
                    jnp.clip((_next_h - rem_u) // n_inner, 0, _MAX_ABSORB),
                    0,
                )
                h_u = rem_u + extra * n_inner
                slices_u = jnp.int32(1) + extra
            else:
                h_u = rem_u
                slices_u = None
            new_state, view2, (win2, adv_lim2, pend2, next_h2), tele2 = core(
                state, view, win, cfg,
                h_u.astype(jnp.float32) * dt_ref, h_u, adv_lim, pend,
                slices_u=slices_u, tele=tele,
            )
            out = (new_state, view2, win2, adv_lim2, pend2,
                   jnp.int32(n_inner), next_h2)
            if telemetry:
                return (*out, tele2)
            return out
    else:
        def step(carry, cfg: ArraySimConfig):
            # within-slice macro-step: jump to the event horizon, keeping
            # at least one fine step of slice for the refresh to absorb
            if telemetry:
                state, view, win, adv_lim, pend, rem_u, next_h, tele = carry
            else:
                state, view, win, adv_lim, pend, rem_u, next_h = carry
                tele = None
            h = jnp.minimum(next_h, rem_u - 1)
            new_state, view2, (win2, adv_lim2, pend2, next_h2), tele2 = core(
                state, view, win, cfg,
                h.astype(jnp.float32) * dt_ref, h, adv_lim, pend,
                tele=tele,
            )
            out = (new_state, view2, win2, adv_lim2, pend2, rem_u - h,
                   next_h2)
            if telemetry:
                return (*out, tele2)
            return out

    step.adv_limit = adv_limit
    step.query_view = query_view
    step.window = window
    step.policies = policies
    step.trigger_w = W
    return step


_UNSET = object()


def make_runner(
    spec: SimSpec,
    bandwidth_ref: float = 700e6,
    time_slice: float = 0.1,
    prefetch_pages: int = 8,
    max_slices: int = 80_000,
    policies: Optional[Sequence] = None,
    step_pages: float = 1.0,
    vmax: Optional[int] = None,
    static_policy=_UNSET,
    stepper: str = "fixed",
    h_max: float = 8.0,
    h_io: float = 3.0,
    wake_exact: bool = True,
    mesh=None,
    sanitize: bool = False,
    telemetry: bool = False,
):
    """Jitted ``run(cfg) -> SimState``: steps until every stream finishes.

    The fine step length is ``step_pages`` page-transfer times at
    ``bandwidth_ref`` (other bandwidths flow through the per-step byte
    credit), and the PBM timeline refreshes structurally every
    ``time_slice`` — the refresh cadence is compiled into the loop nest
    instead of branching per step.  ``step_pages > 1`` is the coarse fast
    mode for batched sweeps: ~2x fewer steps for a few % fidelity.

    ``stepper`` picks the time engine:

    * ``"fixed"`` — every slice is ``round(time_slice/dt)`` fixed-length
      steps (bit-compatible with the pre-horizon engine);
    * ``"horizon"`` — each slice is a ``while`` of variable-length
      macro-steps: every step jumps to the event horizon the previous
      step computed (next trigger arrival / chunk completion / io-credit
      exhaustion / stream completion, capped at ``h_max`` fine steps and
      at the slice boundary), and the slice-boundary refresh step absorbs
      whatever remains — an uneventful slice is ONE step.  ``h_io``
      bounds the jump, in fine steps, while requests are pending (the
      wake-quantisation knob, calibrated against the validation bars);
      supersaturated lanes (pool below the scans' aggregate plan-window
      bytes) jump by the EXACT serial-server wake while pending
      (``wake_exact``, the default — see :func:`make_step`), or never
      jump at all with ``wake_exact=False`` (the pre-wake-exact rule,
      bit-equal to the fixed stepper at those points).  Finished lanes
      freeze at their final state while slower lanes continue.

    ``policies`` is the set of registry policies the runner's lanes may
    select (names or ``ArrayPolicy`` objects); the default is EVERY
    registered array policy, so one runner serves a whole four-policy
    sweep.  A single-name tuple specialises the compiled step for that
    policy (no stacked dispatch, no unused machinery) — the fast path for
    per-policy validation runs.  The pre-registry ``static_policy``
    spelling of that single-policy case was removed and now raises.

    vmap-ready: ``jax.vmap(make_runner(spec))`` over a stacked config runs
    a whole sweep axis in one call.  With ``mesh`` (a ``jax.sharding.Mesh``
    over the devices to use), the returned runner instead takes a STACKED
    config directly and executes it as a ``shard_map`` — a one-axis mesh
    shards the lane axis (lanes spread across the mesh devices, each
    shard running the vmapped runner with per-lane horizons intact; the
    lane count must divide the mesh size evenly), and a two-axis mesh
    ``('lane', 'page')`` additionally shards the global page axis: each
    page shard scans only its own ``P / n_page`` slice of the pool for
    evict/grant candidates, with the reductions combined over gathered
    compact candidate lists — bitwise-identical to the unsharded run
    (``repro.kernels.ops``); the page-shard count must divide the padded
    pool size ``spec.n_pages``.

    ``sanitize=True`` is the contract-checker mode (``repro.analysis``):
    the run compiles under ``jax.experimental.checkify`` NaN + OOB-index
    checks — any step primitive producing a NaN, or any gather/scatter
    index leaving its array, raises instead of propagating garbage
    through a sweep — and the runner hard-errors if it is ever traced
    more than once (a pytree leaf changing shape/dtype between calls is
    a silent 10x recompile slowdown; here it is a ``RuntimeError``).
    Every runner (sanitized or not) exposes ``runner.trace_count()``,
    the number of jit traces taken so far — the one-trace-per-sweep
    invariant is asserted in CI against the plain runners too.
    Incompatible with ``mesh`` (checkify does not compose with
    ``shard_map`` here; sanitize single lanes instead).

    ``telemetry=True`` (STATIC — a different runner, not a traced leaf)
    threads the jit-pure counter pytree of ``repro.obs`` through the
    carry: the runner then returns ``(state, telemetry)`` instead of the
    bare state.  Off (the default) compiles to the exact pre-telemetry
    program — bit-equal results; on adds carry leaves but zero extra
    traces (both are asserted in ``tests/test_obs.py``).
    """
    if static_policy is not _UNSET:
        raise TypeError(
            "make_runner(static_policy=...) was removed; pass "
            "policies=(name,) — resolved through "
            "repro.core.policy_registry (None still means every array "
            "policy)"
        )
    if sanitize and mesh is not None:
        raise ValueError(
            "make_runner(sanitize=True) does not compose with mesh= "
            "(checkify under shard_map); sanitize unsharded lanes instead"
        )
    pols = resolve_policies(policies)
    page_axis = None
    if mesh is not None:
        if len(mesh.axis_names) not in (1, 2):
            raise ValueError(
                f"make_runner(mesh=...) wants a one-axis lane mesh or a "
                f"two-axis ('lane', 'page') mesh, got axes "
                f"{mesh.axis_names}"
            )
        if len(mesh.axis_names) == 2:
            page_axis = mesh.axis_names[1]
    dt = float(step_pages) * float(np.max(spec.page_size)) / float(bandwidth_ref)
    cheap = make_step(spec, dt, time_slice, prefetch_pages, refresh=False,
                      policies=pols, vmax=vmax, stepper=stepper,
                      h_max=h_max, h_io=h_io, wake_exact=wake_exact,
                      page_axis=page_axis, telemetry=telemetry)
    full = make_step(spec, dt, time_slice, prefetch_pages, refresh=True,
                     policies=pols, vmax=vmax, stepper=stepper,
                     h_max=h_max, h_io=h_io, wake_exact=wake_exact,
                     page_axis=page_axis, telemetry=telemetry)

    if stepper == "fixed":
        n_inner = max(1, int(round(time_slice / dt)))

        def run(cfg: ArraySimConfig):
            state = init_state(spec, pols)
            carry = (state, cheap.query_view(state.qidx, state.pos))
            if telemetry:
                carry = (*carry, obs.init_telemetry(pols, spec))

            def slice_body(c):
                c = jax.lax.fori_loop(
                    0, n_inner - 1, lambda i, s: cheap(s, cfg), c
                )
                return full(c, cfg)

            def cond(c):
                st = c[0]
                return (
                    jnp.any(st.stream_done_t < 0)
                    & (st.t < cfg.max_time)
                    & (st.slices_done < max_slices)
                )

            out = jax.lax.while_loop(cond, slice_body, carry)
            if telemetry:
                return out[0], out[-1]
            return out[0]
    else:
        n_inner = max(1, int(round(time_slice / dt)))

        def run(cfg: ArraySimConfig):
            state = init_state(spec, pols)
            view0 = cheap.query_view(state.qidx, state.pos)
            win0 = cheap.window(view0)
            carry = (state, view0, win0,
                     cheap.adv_limit(win0, state.resident),
                     jnp.float32(0.0), jnp.int32(n_inner), jnp.int32(1))
            if telemetry:
                carry = (*carry, obs.init_telemetry(pols, spec))

            def inner_cond(c):
                # keep macro-stepping while the slice has more than one
                # fine step left AND the planned jump falls short of the
                # boundary — otherwise hand the tail to the refresh step
                rem_u, next_h = c[5], c[6]
                return (rem_u > 1) & (next_h < rem_u)

            def slice_body(c):
                c = jax.lax.while_loop(
                    inner_cond, lambda s: cheap(s, cfg), c
                )
                return full(c, cfg)

            def cond(c):
                st = c[0]
                return (
                    jnp.any(st.stream_done_t < 0)
                    & (st.t < cfg.max_time)
                    & (st.slices_done < max_slices)
                )

            out = jax.lax.while_loop(cond, slice_body, carry)
            if telemetry:
                return out[0], out[-1]
            return out[0]

    # one trace per (stepper x policy-set) is a substrate invariant: the
    # counter ticks inside the traced body, so it counts TRACES, not
    # calls — a leaf changing shape/dtype between configs shows up here
    trace_counter = {"n": 0}

    def counted_run(cfg: ArraySimConfig):
        trace_counter["n"] += 1
        # the loop nest's own ops (conditions, carry threading between the
        # refresh and within-slice steps) are the ``loop`` phase; the
        # step's sections open their phases inside it
        with phases.phase("loop"):
            return run(cfg)

    if mesh is not None:
        # configs shard over the lane axis only; per-page state is
        # replicated across the page axis (each page shard scans its own
        # pool slice inside the kernels — kops page_axis dispatch above)
        pspec = jax.sharding.PartitionSpec(mesh.axis_names[0])
        runner = jax.jit(jax.shard_map(
            jax.vmap(counted_run), mesh=mesh,
            in_specs=(pspec,), out_specs=pspec, check_vma=False,
        ))
    elif sanitize:
        from jax.experimental import checkify

        checked = jax.jit(checkify.checkify(
            counted_run,
            errors=checkify.nan_checks | checkify.index_checks,
        ))

        def runner(cfg: ArraySimConfig):
            err, state = checked(cfg)
            err.throw()
            if trace_counter["n"] > 1:
                raise RuntimeError(
                    f"make_runner(sanitize=True): {trace_counter['n']} jit "
                    "traces for one runner — a config leaf changed "
                    "shape/dtype between calls (stack configs with "
                    "stack_configs / keep leaves f32/i32 scalars); every "
                    "(stepper x policy-set) must compile exactly once"
                )
            return state
    else:
        runner = jax.jit(counted_run)
    runner.dt_ref = dt
    runner.stepper = stepper
    runner.lane_mesh = mesh
    runner.page_axis = page_axis
    runner.wake_exact = wake_exact
    runner.sanitize = sanitize
    runner.telemetry = telemetry
    runner.policy_names = tuple(p.name for p in pols)
    runner.trace_count = lambda: trace_counter["n"]
    return runner


def result_from_state(state: SimState, policy, sim_wall: float = 0.0,
                      dt_ref: Optional[float] = None) -> ArrayResult:
    """Convert a finished (device) state into an :class:`ArrayResult`.

    A run cut short by the ``max_time``/``max_slices`` livelock guard is
    NOT silently reported as complete: unfinished streams still contribute
    ``t_end`` to ``stream_times`` (a lower bound), but the result carries
    ``extras["truncated"] = True`` plus the unfinished-stream count so
    harnesses can refuse to compare it against a finished event run.

    ``dt_ref`` (the runner's fine-step length, ``runner.dt_ref``) makes
    the time engine's work observable instead of inferred: extras report
    ``steps``/``macro_steps`` (steps actually executed) plus
    ``skipped_time`` (simulated seconds covered beyond one fine step per
    step — 0 under the fixed stepper, the jumped time under the horizon
    stepper).
    """
    done_t = np.asarray(state.stream_done_t, np.float64)
    t_end = float(state.t)
    stream_times = [d if d >= 0 else t_end for d in done_t]
    unfinished = int(np.sum(done_t < 0))
    if isinstance(policy, str):
        name = policy
    else:
        name = policy_registry.array_name(int(policy)) or str(policy)
    steps = int(state.steps)
    extras = {
        "truncated": unfinished > 0,
        "unfinished_streams": unfinished,
        "churn_loads": int(state.churn),
        "demand_loads": int(state.loads_demand),
        "steps": steps,
        "macro_steps": steps,
        "slices_done": int(state.slices_done),
    }
    if dt_ref is not None:
        extras["skipped_time"] = round(max(0.0, t_end - steps * dt_ref), 6)
    return ArrayResult(
        policy=name,
        stream_times=stream_times,
        total_io_bytes=float(state.io_bytes),
        total_loads=int(state.loads),
        sim_time=t_end,
        steps=steps,
        wall_s=sim_wall,
        extras=extras,
    )


def run_workload_array(
    db,
    streams,
    policy_name: str,
    *,
    capacity_bytes: float,
    bandwidth: float = 700e6,
    time_slice: float = 0.1,
    prefetch_pages: int = 8,
    max_time: float = 3e5,
    spec: Optional[SimSpec] = None,
    runner=None,
    stepper: str = "fixed",
    wake_exact: bool = True,
    sanitize: bool = False,
    telemetry: bool = False,
) -> ArrayResult:
    """Array-backend counterpart of ``repro.core.run_workload`` for every
    registered array policy (lru / pbm / cscan / opt).  Accepts any
    workload the compiler can lower — multi-table streams included.
    ``stepper`` selects the time engine and ``sanitize`` the checkify
    contract-checker mode (see :func:`make_runner`) when no pre-built
    ``runner`` is passed.
    Check ``result.extras["truncated"]`` when lowering ``max_time``: a run
    cut short by the livelock guard reports lower bounds, not results."""
    from .compiler import compile_workload

    if spec is None:
        spec = compile_workload(db, streams)
    if runner is None:
        runner = make_runner(spec, bandwidth_ref=bandwidth,
                             time_slice=time_slice,
                             prefetch_pages=prefetch_pages,
                             policies=(policy_name,), stepper=stepper,
                             wake_exact=wake_exact,
                             sanitize=sanitize, telemetry=telemetry)
    cfg = make_config(spec, capacity_bytes, bandwidth, policy_name,
                      max_time=max_time)
    t0 = _time.time()
    out = jax.block_until_ready(runner(cfg))
    if getattr(runner, "telemetry", False):
        state, tele = out
    else:
        state, tele = out, None
    result = result_from_state(state, policy_name,
                               sim_wall=_time.time() - t0,
                               dt_ref=getattr(runner, "dt_ref", None))
    if tele is not None:
        result.extras["telemetry"] = obs.summarize(
            tele, policies=getattr(runner, "policy_names", None),
            steps=result.steps,
        )
    return result
