"""The ``ArrayPolicy`` surface: buffer policies as jit/vmap-safe data.

The batched step (``array_sim.sim.make_step``) no longer hardcodes any
policy: it drives a tuple of :class:`ArrayPolicy` objects — pure-pytree
state plus array-function hooks — and dispatches eviction on the score
arrays they provide.  One lane of a vmapped sweep selects its policy by
indexing the stacked per-policy arrays with the traced config id, so a
whole (policy x buffer x bandwidth) grid runs as ONE batched call.

The protocol (all hooks are traced inside the jitted step; everything
they return must be jit/vmap-safe arrays):

* :meth:`ArrayPolicy.init_state` — build the policy's private state
  pytree for a workload (``()`` for stateless policies);
* :meth:`ArrayPolicy.on_request` / :meth:`ArrayPolicy.on_consume` —
  advance that state from the step's observation window
  (:class:`StepCtx`: this step's I/O grants, crossed plan triggers,
  post-advance scan view, consumption-estimate thunks);
* :meth:`ArrayPolicy.score_victims` — the policy itself: a ``(P,)`` f32
  eviction priority (higher = evicted first) consumed by the batched
  eviction kernel (``repro.kernels.ops.batched_evict``);
* :meth:`ArrayPolicy.scan_horizon` — the policy as a **horizon
  provider** for the event-horizon time engine
  (``make_runner(stepper="horizon")``): per stream, the seconds until
  the policy's own state next needs attention.  The in-order candidates
  (trigger arrival, completion, io-credit) come from the step itself;
  a policy only overrides this when its consumption model has its own
  clock — array-CScan reports the current chunk's completion;
* static knobs: ``request_window`` (per-policy readahead width),
  ``fifo_tie`` (request-cohort service order), ``cooperative`` (the
  policy inverts control flow and schedules loads itself — CScan; the
  step then runs the chunk-granular cooperative substrate in
  ``array_sim.coop`` against this policy's state).

Policies register in ``repro.core.policy_registry`` — the single table
both the event engine and the array backend resolve names through.

This module also keeps the vectorised numeric cores the policies are
built from:

* :func:`time_to_bucket` — the O(1) ``TimeToBucketNumber`` of paper
  Fig. 10, vectorised over a whole page array.
* :func:`next_consumption` — ``PageNextConsumption`` (paper Fig. 9)
  vectorised over the whole page array instead of per-page dict walks.
* :func:`target_buckets` — where every page *would* go if (re)pushed now.
* :func:`shift_timeline` — ``RefreshRequestedBuckets``: the once-per-
  slice timeline shift with spill re-bucketing (previously fused into
  the eviction kernel; elementwise, so it lives with the policy).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

# "no interest" sentinel: a finite big value, not inf — XLA:CPU fuses
# float arithmetic far better than inf/pred-heavy broadcasts
BIG = jnp.float32(1e30)
BIG_CUT = 1e29


def time_to_bucket(eta, time_slice, n_groups, m):
    """Vectorised TimeToBucketNumber: bucket index for each eta (seconds).

    Matches ``PBMPolicy.time_to_bucket`` elementwise: group ``g`` covers
    slice offsets ``[m*(2^g - 1), m*(2^(g+1) - 1))`` with bucket width
    ``2^g`` slices.  ``eta=inf`` maps to the last bucket (callers decide
    not-requested separately).
    """
    nb = n_groups * m
    s = jnp.maximum(eta, 0.0) / time_slice
    g = jnp.floor(jnp.log2(s / m + 1.0)).astype(jnp.int32)
    g = jnp.clip(g, 0, n_groups - 1)
    glen = jnp.left_shift(jnp.int32(1), g)
    start = m * (glen - 1)
    width = glen.astype(jnp.float32)
    idx = jnp.floor((s - start.astype(jnp.float32)) / width).astype(jnp.int32)
    b = jnp.clip(g * m + idx, 0, nb - 1)
    return jnp.where(eta <= 0.0, 0, b).astype(jnp.int32)


def next_consumption(page_first, page_last, page_col, cols_cur, cur_abs,
                     scan_end, speed, active, scan_start=None, eps=None):
    """``PageNextConsumption`` over the whole page array: min over streams
    of estimated seconds until the page's consumption, :data:`BIG` where no
    registered scan wants the page.

    Consumption is **plan-trigger granular**, mirroring the event engine's
    access plan: a page is consumed the instant the scan cursor crosses its
    *trigger* ``max(page_first, scan_start)`` (the page's first tuple, or
    the scan start for the page straddling it), and from then on the scan
    no longer registers interest — even while the cursor is still inside
    the page's tuple range.  ``eps`` absorbs f32 cursor rounding so a page
    whose trigger the cursor sits exactly on still counts as pending.

    ``scan_start=None`` keeps the legacy page-overlap interest
    (``page_last > cur``): the registration-time view where nothing has
    been consumed yet, used by the parity property tests.

    Unrolled over streams (S is small and static): 1-D elementwise ops per
    stream fuse to a single fast loop on CPU, where the equivalent (S, P)
    broadcast compiles to a pathologically slow predicate fusion.
    """
    S = cur_abs.shape[0]
    colmask_sp = cols_cur[:, page_col]           # one (S, P) gather
    eta = jnp.full(page_first.shape, BIG)
    for s in range(S):
        if scan_start is None:
            trigger = page_first
            pending = page_last > cur_abs[s]
        else:
            trigger = jnp.maximum(page_first, scan_start[s])
            tol = 0.0 if eps is None else eps[s]
            pending = (trigger >= cur_abs[s] - tol) & (
                page_last > scan_start[s]
            )
        interest = (
            colmask_sp[s]                        # scan touches the column
            & pending                            # trigger not yet crossed
            & (page_first < scan_end[s])         # inside the scanned range
            & active[s]
        )
        e = jnp.maximum(trigger - cur_abs[s], 0.0) / jnp.maximum(
            speed[s], 1e-6
        )
        eta = jnp.minimum(eta, jnp.where(interest, e, BIG))
    return eta


def target_buckets(eta, time_slice, n_groups, m, page_valid):
    """Bucket every page would get if pushed now: ``time_to_bucket`` for
    requested pages, the not-requested sentinel (== nb) otherwise."""
    nb = n_groups * m
    requested = (eta < BIG_CUT) & page_valid
    b = time_to_bucket(jnp.where(requested, eta, 0.0), time_slice, n_groups, m)
    return jnp.where(requested, b, nb).astype(jnp.int32)


def shift_timeline(bucket, b_target, slices_done, k, *, nb, m):
    """``RefreshRequestedBuckets`` (paper Fig. 9/10): advance the bucketed
    timeline by ``k`` slices.  Per elapsed slice, bucket ``b`` (length
    ``2**(b//m)`` slices) moves left when the slice counter divides its
    length; a page shifted past position 0 is *spilled* and re-bucketed at
    ``b_target`` — its freshly recomputed priority, the self-correction
    step of the paper."""

    def shift_once(i, b):
        tp = slices_done + i + 1
        blen = jnp.left_shift(jnp.int32(1), jnp.clip(b, 0, nb - 1) // m)
        req = (b >= 0) & (b < nb)
        moved = req & ((tp % blen) == 0)
        b2 = jnp.where(moved, b - 1, b)
        return jnp.where(b2 < 0, b_target, b2)

    return jax.lax.fori_loop(0, jnp.maximum(k, 0), shift_once, bucket)


class StepCtx:
    """Observation window one simulation step hands to the policy hooks.

    Built fresh inside the traced step (never carried), after the CPU
    advance and the I/O grant phase, so hooks see this step's loads and
    trigger crossings plus the post-advance scan view.  The consumption
    estimates are *thunks* with per-step memoisation: however many
    policies ask for :meth:`eta_estimate` during one step, it is computed
    once — and a step compiled without a PBM-like policy never computes
    it at all.

    ``refresh`` is a static Python bool: the step is compiled separately
    for the cheap within-slice flavour and the once-per-``time_slice``
    boundary flavour, exactly like the paper's PBM cadence.
    """

    def __init__(self, *, spec, refresh: bool, time_slice, now, steps,
                 dt, page_first, page_last, page_col,
                 page_valid, resident, last_used, load_mask, load_cand,
                 load_ok, cross_pidx, crossed, active, cols, cur, end,
                 start, eps, rate, speed_push, coop=None,
                 slices_done=None, slices_elapsed=None,
                 upd_pages=None, upd_on=None):
        self.spec = spec
        self.refresh = refresh
        self.time_slice = time_slice
        self.now = now                  # f32 sim clock (end of this step)
        self.steps = steps
        self.slices_done = slices_done  # i32 PBM slices elapsed (pre-step)
        self.slices_elapsed = slices_elapsed
        # ^ i32 slices THIS refresh step stands in for (None == 1): the
        #   wake-exact horizon refresh may absorb whole slices beyond
        #   its own tail, and the timeline shift must advance by all of
        #   them (shift_timeline's k)
        self.dt = dt                    # step length: static under the fixed
                                        # stepper, traced under "horizon"
        self.page_first = page_first
        self.page_last = page_last
        self.page_col = page_col
        self.page_valid = page_valid
        self.resident = resident        # (P,) bool pre-eviction residency
        self.last_used = last_used      # (P,) f32 post-touch LRU clock
        self.load_mask = load_mask      # (P,) bool granted loads this step
        self.load_cand = load_cand      # (LOAD_MAX,) i32 candidate pages
        self.load_ok = load_ok          # (LOAD_MAX,) bool grant mask
        self.cross_pidx = cross_pidx    # (S, C, W) i32 windowed page ids
        self.crossed = crossed          # (S, C, W) bool triggers crossed
        self.upd_pages = upd_pages      # (U,) i32 compacted update set —
        self.upd_on = upd_on            #   loads + crossings deduplicated
                                        #   (horizon stepper; None = use
                                        #   the padded load/cross windows)
        self.active = active            # post-advance view ------------
        self.cols = cols                # (S, C) bool
        self.cur = cur                  # (S,) f32 absolute cursor
        self.end = end
        self.start = start
        self.eps = eps
        self.rate = rate                # (S,) f32 true current query rate
        self.speed_push = speed_push    # (S,) f32 estimator w/ engine dips
        self.coop = coop                # cooperative-substrate outputs
        self._eta_estimate = None
        self._eta_exact = None

    def eta_estimate(self):
        """PBM's estimated next consumption per page: plan-trigger
        granular, from the per-slice speed estimator with the engine's
        stall-exit dips folded in.  Memoised per step."""
        if self._eta_estimate is None:
            self._eta_estimate = next_consumption(
                self.page_first, self.page_last, self.page_col,
                self.cols, self.cur, self.end, self.speed_push,
                self.active, scan_start=self.start, eps=self.eps,
            )
        return self._eta_estimate

    def eta_estimate_at(self, pages):
        """:meth:`eta_estimate` for a small page-id subset (the within-
        slice update set: this step's loads + crossed triggers)."""
        return next_consumption(
            self.page_first[pages], self.page_last[pages],
            self.page_col[pages], self.cols, self.cur, self.end,
            self.speed_push, self.active, scan_start=self.start,
            eps=self.eps,
        )

    def eta_exact(self):
        """OPT's oracle: exact next-consumption distances from the true
        CPU rates of the *current* queries — computable because the scan
        plans are static.  Memoised per step."""
        if self._eta_exact is None:
            self._eta_exact = next_consumption(
                self.page_first, self.page_last, self.page_col,
                self.cols, self.cur, self.end, self.rate,
                self.active, scan_start=self.start, eps=self.eps,
            )
        return self._eta_exact


class HorizonView:
    """The slim observation window the event-horizon stepper hands to
    :meth:`ArrayPolicy.scan_horizon`: the post-advance per-stream scan
    view plus the fine step length.  Built at the END of a step (the
    horizon describes the NEXT step)."""

    def __init__(self, *, spec, active, start, end, rate, dt_ref):
        self.spec = spec
        self.active = active    # (S,) bool post-advance
        self.start = start      # (S,) f32 absolute scan start
        self.end = end          # (S,) f32 absolute scan end
        self.rate = rate        # (S,) f32 true current query rate
        self.dt_ref = dt_ref    # f32 fine step length (static)


class ArrayPolicy:
    """Base protocol: a buffer policy as pure-pytree state + array hooks.

    Subclasses override what they need; the defaults are a stateless
    policy that only scores victims.  Hook outputs must be jit/vmap-safe
    (no Python control flow on traced values); ``ctx.refresh`` is static
    and MAY branch Python-side.
    """

    #: registry name (also the event-engine counterpart's name)
    name: str = "?"
    #: the policy schedules loads itself (ABM); the step runs the
    #: cooperative chunk substrate against this policy's state
    cooperative: bool = False
    #: request-cohort service order: "stream" = per-stream blocks (the
    #: woken scan's window enqueues contiguously), "plan" = plan-
    #: deterministic page order (estimates absorb the timing noise)
    fifo_tie: str = "stream"

    def request_window(self, spec, prefetch_pages: int) -> int:
        """Plan-entry readahead width for this policy (static)."""
        return prefetch_pages

    def init_state(self, spec) -> Any:
        """Policy-private state pytree for a workload (device arrays)."""
        return ()

    def on_request(self, pstate, ctx: StepCtx):
        """Observe this step's request/grant activity (``ctx.load_*``)."""
        return pstate

    def on_consume(self, pstate, ctx: StepCtx):
        """Observe this step's consumption (``ctx.crossed`` and the
        post-advance view); ``ctx.refresh`` marks the slice boundary."""
        return pstate

    def score_victims(self, pstate, ctx: StepCtx) -> jax.Array:
        """``(P,) f32`` eviction priority, higher = evicted first.  The
        step masks non-evictable pages and pops the order in batch."""
        raise NotImplementedError

    def scan_horizon(self, pstate, hz: HorizonView):
        """Per-stream seconds until this policy's state next needs a step
        (``(S,) f32``), or ``None`` for no policy-specific constraint —
        the event-horizon stepper then jumps on the step's own candidates
        alone (trigger arrival, completion, io-credit).  Only policies
        whose consumption model owns a clock override this (array-CScan:
        the consuming chunk's completion)."""
        return None  # noqa: RET501  (hook contract: explicit None means no clock)

    def observe_init(self, spec):
        """Zeros prototype of this policy's telemetry row (``None`` =
        no policy-specific counters).  Only consulted when the runner
        is built with ``telemetry=True`` (``repro.obs``); the row is a
        fixed-shape f32 vector the step accumulates per step."""
        return None  # noqa: RET501  (hook contract: None means no row)

    def observe(self, pstate, ctx: StepCtx):
        """Telemetry row for this step, same shape as
        :meth:`observe_init` — pure ``jnp``, added into the carried
        ``Telemetry.pol_obs`` entry (lanes running another policy are
        masked out by the step)."""
        return None  # noqa: RET501

    def __repr__(self) -> str:  # pragma: no cover
        return f"{type(self).__name__}({self.name})"


def _lru_age(ctx: StepCtx) -> jax.Array:
    return jnp.maximum(ctx.now - ctx.last_used, 0.0)


class ArrayLRU(ArrayPolicy):
    """Least-recently-used: score = age of the last consumption touch."""

    name = "lru"
    fifo_tie = "stream"

    def request_window(self, spec, prefetch_pages: int) -> int:
        # calibrated vs the event engine: its 8-entry window underfeeds
        # the array LRU at deep thrash (its requests are colder); the +2
        # widening restores the engine's churn level.  SINGLE-TABLE
        # deep-thrash calibration (micro 0.1-0.2 buffer) — on multi-table
        # workloads the same +2 overfeeds churn at the paper's TPC-H
        # operating points, where the engine's own width tracks within
        # the validation bars.
        return prefetch_pages + 2 if spec.n_tables == 1 else prefetch_pages

    def score_victims(self, pstate, ctx: StepCtx) -> jax.Array:
        return _lru_age(ctx)

    def observe_init(self, spec):
        # [resident-page age mass (s), resident-page count] per step:
        # mean resident age = row[0] / row[1] over the run
        return jnp.zeros(2, jnp.float32)

    def observe(self, pstate, ctx: StepCtx):
        res = ctx.resident & ctx.page_valid
        age = jnp.where(res, _lru_age(ctx), 0.0)
        return jnp.stack([jnp.sum(age),
                          jnp.sum(res).astype(jnp.float32)])


class ArrayPBM(ArrayPolicy):
    """Predictive Buffer Manager: the paper's bucketed consumption
    timeline as policy state (one ``(P,)`` bucket array).

    Within a slice the timeline is static except for pages whose estimate
    just changed (this step's loads and crossed triggers — the dict impl
    re-pushes a page on every load and consume event); at the slice
    boundary every page's next consumption is recomputed, no-longer-
    requested pages demote, and the timeline shifts one slice with spill
    re-bucketing (``RefreshRequestedBuckets`` as one vector op)."""

    name = "pbm"
    fifo_tie = "plan"

    def init_state(self, spec):
        return jnp.full(spec.n_pages, spec.not_requested, jnp.int32)

    def on_consume(self, bucket, ctx: StepCtx):
        spec = ctx.spec
        NR = spec.not_requested
        m = spec.buckets_per_group
        if ctx.refresh:
            # slice boundary: full PageNextConsumption recompute (trigger-
            # granular: consumed pages drop out per column), bucket
            # transitions, and one timeline shift with spill re-bucketing
            eta = ctx.eta_estimate()
            b_target = target_buckets(eta, ctx.time_slice, spec.n_groups,
                                      m, ctx.page_valid)
            interested = (eta < BIG_CUT) & ctx.page_valid
            assign = (
                ctx.load_mask | ((bucket == NR) & interested)
                | (b_target == 0)
            )
            bucket_pre = jnp.where(
                ~interested, NR, jnp.where(assign, b_target, bucket)
            ).astype(jnp.int32)
            k = (jnp.int32(1) if ctx.slices_elapsed is None
                 else ctx.slices_elapsed)
            return shift_timeline(bucket_pre, b_target, ctx.slices_done,
                                  k, nb=spec.nb, m=m)
        # within a slice: one fused gather/scatter over the update set.
        # Combining (min) scatter with an NR+1 sentinel for off entries:
        # duplicate ON entries of one page carry identical b_u (eta is a
        # function of the page alone), so the result is deterministic
        # even when a page appears both on and off in ``upd``.  The
        # horizon stepper hands a compacted id list (its cross window is
        # sized for macro-jumps — walking it padded would cost more than
        # the whole fixed step); the fixed stepper keeps the padded
        # windows bit-for-bit.
        if ctx.upd_pages is not None:
            upd, upd_on = ctx.upd_pages, ctx.upd_on
        else:
            upd = jnp.concatenate(
                [ctx.load_cand, ctx.cross_pidx.reshape(-1)]
            )
            upd_on = jnp.concatenate([ctx.load_ok, ctx.crossed.reshape(-1)])
        eta_u = ctx.eta_estimate_at(upd)
        b_u = target_buckets(eta_u, ctx.time_slice, spec.n_groups, m,
                             jnp.ones(upd.shape[0], bool))
        new_b = jnp.full(spec.n_pages, NR + 1, jnp.int32).at[upd].min(
            jnp.where(upd_on, b_u, NR + 1)
        )
        return jnp.where(new_b <= NR, new_b, bucket)

    def score_victims(self, bucket, ctx: StepCtx) -> jax.Array:
        # composite key: bucket level dominates; not-requested (== nb) is
        # the top level with LRU order inside; requested buckets break
        # ties by a per-(page, call) hash (the dict impl's insertion
        # order is equally arbitrary, but a FIXED index order would carve
        # a stable always-kept elite out of every bucket — systematic
        # retention the dict engine's churning insertion order never
        # develops).
        P = bucket.shape[0]
        nb = ctx.spec.nb
        age = _lru_age(ctx)
        idxi = jnp.arange(P, dtype=jnp.uint32)
        seed = jax.lax.bitcast_convert_type(
            jnp.asarray(ctx.now, jnp.float32) + 1.0, jnp.uint32
        ).astype(jnp.uint32)
        h32 = idxi * jnp.uint32(2654435761) + seed * jnp.uint32(40503)
        tie = (h32 >> jnp.uint32(8)).astype(jnp.float32) \
            * jnp.float32(2.0**-24)
        tb = jnp.where(bucket == nb, age / (age + 1.0), tie)
        return bucket.astype(jnp.float32) + 0.5 * tb

    def observe_init(self, spec):
        # resident-page occupancy per timeline bucket (paper Fig. 10),
        # step-integrated; the last slot is the not-requested level
        return jnp.zeros(spec.nb + 1, jnp.float32)

    def observe(self, bucket, ctx: StepCtx):
        nb = ctx.spec.nb
        res = (ctx.resident & ctx.page_valid).astype(jnp.float32)
        return jnp.zeros(nb + 1, jnp.float32).at[
            jnp.clip(bucket, 0, nb)
        ].add(res)


class ArrayOPT(ArrayPolicy):
    """OPT / Belady on exact plan distances (paper §3, §4 "OPT simulator").

    The scan plans are static and in-order, so every page's exact next
    consumption is one :func:`next_consumption` over the TRUE current
    query rates — no estimator.  Eviction mirrors
    ``policies.opt.OraclePolicy``: unreferenced pages first in LRU order,
    then referenced pages by furthest exact next use.  Like the paper's
    OPT it bounds *order-preserving* policies only — CScans may beat it
    (the paper's "food for thought" footnote).

    The score array is recomputed once per PBM slice and held STALE in
    between (the policy state is the cached key).  This is deliberate
    engine parity, not an optimisation: the event oracle ranks victims
    from burst-quantised scan positions, so at saturation it keeps
    evicting just-arrived readahead whose scans still rank far — ~19% of
    its loads at the 10%-buffer micro point are evicted before first use.
    A continuously re-scored array oracle never makes that mistake and
    came out 12-24% *more optimal* than the machine it models; freezing
    the ranking on the slice cadence reproduces the engine's churn
    channel (fit: micro -5/-6/-10%, TPC-H -7/+1/+1% stream time at the
    validated points).
    """

    name = "opt"
    fifo_tie = "stream"

    def init_state(self, spec):
        return jnp.zeros(spec.n_pages, jnp.float32)

    def on_consume(self, key, ctx: StepCtx):
        if not ctx.refresh:
            return key
        eta = ctx.eta_exact()
        age = _lru_age(ctx)
        unreferenced = eta >= BIG_CUT
        # bands: referenced pages map to [0, 1) monotone in eta (furthest
        # next use evicted first), unreferenced to [2, 3) in LRU order —
        # always above every referenced page
        return jnp.where(
            unreferenced,
            2.0 + age / (age + 1.0),
            eta / (eta + 1.0),
        )

    def score_victims(self, key, ctx: StepCtx) -> jax.Array:
        return key

    def observe_init(self, spec):
        # [unreferenced resident pages, referenced resident pages] per
        # step (the oracle's two score bands — mass in the first slot
        # means the pool holds dead pages the plans no longer want)
        return jnp.zeros(2, jnp.float32)

    def observe(self, key, ctx: StepCtx):
        res = ctx.resident & ctx.page_valid
        unref = res & (key >= 2.0)
        return jnp.stack([
            jnp.sum(unref).astype(jnp.float32),
            jnp.sum(res & (key < 2.0)).astype(jnp.float32),
        ])


class ArrayCScan(ArrayPolicy):
    """Cooperative Scans' ABM as an array policy (paper §2).

    CScan *inverts* buffer-management control flow — ABM decides loads
    globally and delivers chunks out of order — so it cannot be expressed
    as an eviction score over the in-order substrate alone (it beats even
    OPT, which bounds every order-preserving policy).  ``cooperative``
    makes the step run the chunk-granular cooperative substrate
    (``array_sim.coop``: per-(stream, chunk) consumption state,
    availability, the choose-chunk/choose-scan relevance loop, chunk-at-
    a-time loads) against this policy's state; the policy itself
    contributes the KeepRelevance eviction score the substrate computed:
    chunks the fewest CScans are interested in go first, and the paper's
    "evict only if KeepRelevance < LoadRelevance" rule is enforced by the
    substrate's evictable mask."""

    name = "cscan"
    cooperative = True
    fifo_tie = "plan"

    def init_state(self, spec):
        from .coop import init_coop_state
        return init_coop_state(spec)

    def score_victims(self, pstate, ctx: StepCtx) -> jax.Array:
        assert ctx.coop is not None, (
            "ArrayCScan needs the cooperative substrate: compile the step "
            "with this policy in its policies tuple"
        )
        return ctx.coop.keep_key

    def scan_horizon(self, pstate, hz: HorizonView):
        # the chunk is CScan's clock: nothing interesting happens for a
        # consuming scan before its current chunk completes; an idle scan
        # needs a fine step to run the pick loop
        from .coop import chunk_horizon
        return chunk_horizon(hz.spec, pstate, hz)

    def observe_init(self, spec):
        # [chunks-done flags summed over streams, scans consuming a
        # chunk] per step (chunk picks themselves are counted by the
        # step via coop.chunk_pick — they need both inflight states)
        return jnp.zeros(2, jnp.float32)

    def observe(self, pstate, ctx: StepCtx):
        return jnp.stack([
            jnp.sum(pstate.done.astype(jnp.float32)),
            jnp.sum((pstate.cur_chunk >= 0).astype(jnp.float32)),
        ])
