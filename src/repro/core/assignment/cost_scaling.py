"""Cost-scaling assignment (max-weight perfect matching) — paper §5, on TPU.

Implements the paper's Algorithm 5.2 outer loop with the lock-free Refine of
Algorithm 5.4, adapted from CUDA atomics to synchronous Jacobi rounds
(DESIGN.md §2): every active node applies its push/relabel decision to the
pre-round state; the concurrent unit-flow updates commute (disjoint entries of
the dense matching matrix F), so one round is a legal stage-stepping trace in
the sense of the paper's Lemma 5.3.

Representation (complete bipartite, |X| = |Y| = n):
  * costs  c[x, y] = -(n+1) * w[x, y]   (minimization form, Goldberg–Kennedy
    integer scaling: optimality at ε < 1 on the scaled costs = exact optimum)
  * F[x, y] ∈ {0, 1}: the pseudoflow — dense instead of adjacency structs
  * e(x) = 1 - Σ_y F[x, y],  e(y) = Σ_x F[x, y] - 1   (supplies of [9])
  * prices p_x, p_y; part-reduced cost c'_p(x, y) = c(x, y) - p(y)

Heuristics of §5.2/§5.5:
  * arc fixing: arcs with c_p > 2nε never carry flow again — an accumulating
    +INF mask replaces the paper's "flow = -10" adjacency-list deletion,
  * price updates: the Dial-bucket Dijkstra becomes a vectorized Bellman–Ford
    over the dense bipartite graph (same distances; O(n²) per sweep on the
    VPU instead of a host priority queue).

Beyond-paper variant: ``refine="auction"`` fuses push+relabel into a top-2
bid (Bertsekas auction, equivalent ε-scaling semantics) which converges in
fewer Jacobi rounds; the paper-faithful ``refine="pushrelabel"`` is the
baseline recorded in EXPERIMENTS.md.

Batching: every function is shape-polymorphic over leading batch axes —
``w`` may be ``(n, n)`` or ``(B, n, n)``, with prices ``(..., n)``, counters
``(...,)`` and ε carried per instance. Orchestration is delegated to the
unified runtime of ``repro.core.solver_loop``: the nested ε-scaling/refine
loops are flattened into one per-instance cycle (``_ScaleState``) so an
instance that reaches a perfect matching (or finishes its ε-scaling
schedule, which depends on its own max|c|) can be frozen via a select —
masked mode — or dropped from the working set entirely — ``compact=True``,
early-exit compaction — while the rest of the batch keeps refining. Either
way batched results bit-match a loop of single-instance solves.
``solve_assignment`` accepts both ranks; the pad-and-bucket front end for
ragged batches lives in ``repro.core.batch``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.solver_loop import (LoopSpec, masked_events_active,
                                    run_compacted, run_masked)

INF = jnp.int32(2 ** 30)


class AssignmentResult(NamedTuple):
    col_of_row: jax.Array   # (..., n) int32: matched y for each x; the
    #                         sentinel n marks an UNMATCHED row (only
    #                         possible when converged is False)
    weight: jax.Array       # (...,) total matching weight (original scale)
    p_x: jax.Array
    p_y: jax.Array
    rounds: jax.Array       # (...,) total Jacobi rounds across all refines
    pushes: jax.Array       # (...,) total pushes (paper's op-count metric)
    relabels: jax.Array     # (...,) total relabel operations
    converged: jax.Array


class _RefineState(NamedTuple):
    F: jax.Array
    p_x: jax.Array
    p_y: jax.Array
    fixed: jax.Array        # accumulating arc-fixing mask (True = deleted)
    rounds: jax.Array
    pushes: jax.Array
    relabels: jax.Array


def _masked(c, fixed):
    return jnp.where(fixed, INF, c)


def _exp(eps, k: int):
    """ε with k broadcast axes appended: per-instance ε against (..., n[, n])."""
    eps = jnp.asarray(eps)
    return eps.reshape(eps.shape + (1,) * k)


def _freeze(live, new: _RefineState, old: _RefineState) -> _RefineState:
    """Keep ``old`` leaves where ``live`` is False (per-instance no-op)."""
    from repro.core.masking import freeze
    return freeze(live, new, old)


def _round_pushrelabel(c, eps, st: _RefineState, *,
                       backend: str = "xla") -> _RefineState:
    """One Jacobi round of Algorithm 5.4 over all active nodes of both sides."""
    F, p_x, p_y, fixed = st.F, st.p_x, st.p_y, st.fixed
    e1 = _exp(eps, 1)

    row_sum = jnp.sum(F, axis=-1)
    col_sum = jnp.sum(F, axis=-2)
    active_x = row_sum == 0            # e(x) = 1
    active_y = col_sum > 1             # e(y) > 0

    # ---- X side: min part-reduced cost over residual (x,y) = unmatched arcs.
    if backend == "pallas":  # the paper's hot loop as the bidding kernel
        from repro.kernels.bidding.ops import bidding_op
        op = bidding_op
        for _ in range(c.ndim - 2):  # one vmap per leading batch axis
            op = jax.vmap(op)
        min_cpx, arg_x, _ = op(c, p_y, fixed | (F == 1))
    else:
        cpx = _masked(c - p_y[..., None, :], fixed)
        cpx = jnp.where(F == 1, INF, cpx)        # residual X->Y iff F == 0
        min_cpx = jnp.min(cpx, axis=-1)
        arg_x = jnp.argmin(cpx, axis=-1)
    admis_x = min_cpx < -p_x                     # c_p(x, ỹ) < 0 (line 11)
    push_x = active_x & admis_x & (min_cpx < INF)
    relab_x = active_x & ~admis_x & (min_cpx < INF)
    p_x = jnp.where(relab_x, -(min_cpx + e1), p_x)      # line 18

    # ---- Y side: residual (y,x) iff F[x,y] == 1; c'_p(y,x) = -c(x,y) - p(x).
    cpy = jnp.where(F == 1, -c - p_x[..., :, None], INF)    # (x, y) layout
    min_cpy = jnp.min(cpy, axis=-2)
    arg_y = jnp.argmin(cpy, axis=-2)
    admis_y = min_cpy < -p_y
    push_y = active_y & admis_y & (min_cpy < INF)
    relab_y = active_y & ~admis_y & (min_cpy < INF)
    p_y = jnp.where(relab_y, -(min_cpy + e1), p_y)

    # ---- fulfillment: apply all unit pushes at once (disjoint F entries).
    n = c.shape[-1]
    add = (jax.nn.one_hot(arg_x, n, dtype=F.dtype)
           * push_x[..., :, None].astype(F.dtype))
    rem = (jnp.swapaxes(jax.nn.one_hot(arg_y, n, dtype=F.dtype), -1, -2)
           * push_y[..., None, :].astype(F.dtype))
    F = jnp.clip(F + add - rem, 0, 1)

    return _RefineState(
        F=F, p_x=p_x, p_y=p_y, fixed=fixed,
        rounds=st.rounds + 1,
        pushes=st.pushes + jnp.sum(push_x, -1) + jnp.sum(push_y, -1),
        relabels=st.relabels + jnp.sum(relab_x, -1) + jnp.sum(relab_y, -1),
    )


def _round_auction(c, eps, st: _RefineState, *,
                   backend: str = "xla") -> _RefineState:
    """Beyond-paper refine round: top-2 bidding (push+relabel fused).

    Every unmatched x computes its best and second-best part-reduced cost,
    bids its best y down to the second-best level minus ε, and each y accepts
    the single best bid, evicting the previous owner. One round performs the
    work of a push AND the price move a later relabel would do — strictly
    fewer rounds to ε-optimality, same invariants.
    """
    F, p_x, p_y, fixed = st.F, st.p_x, st.p_y, st.fixed
    n = c.shape[-1]

    row_sum = jnp.sum(F, axis=-1)
    active_x = row_sum == 0

    if backend == "pallas":  # top-2 bid via the bidding kernel
        from repro.kernels.bidding.ops import bidding_op
        op = bidding_op
        for _ in range(c.ndim - 2):  # one vmap per leading batch axis
            op = jax.vmap(op)
        min1, arg1, min2 = op(c, p_y, fixed)
    else:
        cpx = _masked(c - p_y[..., None, :], fixed)  # part-reduced costs
        min1 = jnp.min(cpx, axis=-1)
        arg1 = jnp.argmin(cpx, axis=-1)
        cpx2 = jnp.where(jax.nn.one_hot(arg1, n, dtype=bool), INF, cpx)
        min2 = jnp.min(cpx2, axis=-1)
    min2 = jnp.where(min2 >= INF, min1, min2)    # single-candidate rows

    # x is willing to lower p(ỹ)'s attractiveness gap: the winning reduced
    # cost after the bid equals (second best) + ε below nothing — i.e. the
    # new own-price of x would be -(min2 + eps). The bid strength (lower is
    # stronger) is min1 - (min2 + eps) <= -eps < 0.
    bid_strength = min1 - min2 - _exp(eps, 1)    # < 0, more negative = stronger
    bids = jnp.where(
        (jnp.arange(n) == arg1[..., :, None]) & active_x[..., :, None],
        bid_strength[..., :, None], INF)
    best_bid = jnp.min(bids, axis=-2)
    winner = jnp.argmin(bids, axis=-2)
    got_bid = best_bid < INF

    # y accepts the winner: previous owner (if any) is evicted.
    new_match = jax.nn.one_hot(winner, n, dtype=F.dtype, axis=-2) \
        * got_bid[..., None, :].astype(F.dtype)
    F = F * (~got_bid)[..., None, :].astype(F.dtype) + new_match
    # price update on won columns: p(y) absorbs the bid (Bertsekas raise,
    # expressed in Goldberg price coordinates: p_y strictly decreases by >=ε).
    p_y = jnp.where(got_bid, p_y + best_bid, p_y)
    # the winner's own price moves as the later relabel would (ε-CS witness).
    # x won iff its row of new_match holds a 1: a bid is only ever placed by
    # an active x at its own arg1, so that row is 0 except, when x's bid won,
    # at arg1[x]. A row reduction, not a gather at arg1 (serial on TPU).
    won = jnp.any(new_match != 0, axis=-1)
    p_x = jnp.where(won, -(min2 + _exp(eps, 1)), p_x)

    n_push = jnp.sum(got_bid, axis=-1)
    return _RefineState(
        F=F, p_x=p_x, p_y=p_y, fixed=fixed,
        rounds=st.rounds + 1,
        pushes=st.pushes + n_push,
        relabels=st.relabels + n_push,
    )


def _is_perfect(F):
    """Per-instance perfect-matching predicate: scalar or (B,) bool."""
    n = F.shape[-1]
    return (jnp.sum(F, axis=(-2, -1)) == n) \
        & jnp.all(jnp.sum(F, axis=-2) <= 1, axis=-1) \
        & jnp.all(jnp.sum(F, axis=-1) <= 1, axis=-1)


def price_update(c, eps, st: _RefineState, max_sweeps: int) -> _RefineState:
    """Vectorized price-update heuristic (paper Alg. 5.3, Bellman–Ford form).

    Distances (in ε units) from every deficit node (unmatched y) backwards
    along residual arcs; then p(v) -= ε·l(v). Arc length of residual (v,w) is
    max(0, floor(c_p(v,w)/ε) + 1) — identical to the Dial-bucket numbers.
    """
    F, p_x, p_y = st.F, st.p_x, st.p_y
    e1, e2 = _exp(eps, 1), _exp(eps, 2)
    INF_D = jnp.int32(2 ** 26)  # distance infinity (sums stay in int32)
    deficit_y = jnp.sum(F, axis=-2) == 0
    l_y0 = jnp.where(deficit_y, 0, INF_D)

    # Fixed arcs stay in the distance graph: the refine never pushes on
    # them, but the final prices must be ε-optimal over every arc, and a
    # fixed arc left out lets the drop ε·l(v) carry prices across it.
    cp_xy = c + p_x[..., :, None] - p_y[..., None, :]
    len_xy = jnp.minimum(jnp.maximum(0, cp_xy // e2 + 1), INF_D)  # arc X->Y
    len_xy = jnp.where(F == 0, len_xy, INF_D)
    cp_yx = -c + p_y[..., None, :] - p_x[..., :, None]
    len_yx = jnp.where(F == 1, jnp.minimum(
        jnp.maximum(0, cp_yx // e2 + 1), INF_D), INF_D)

    def body(carry):
        l_x, l_y, _, it = carry
        nl_x = jnp.min(jnp.minimum(len_xy + l_y[..., None, :], INF_D), -1)
        nl_x = jnp.minimum(l_x, nl_x)
        # y relaxes through residual (y, x) arcs using the fresh l_x
        nl_y = jnp.min(jnp.minimum(len_yx + nl_x[..., :, None], INF_D), -2)
        nl_y = jnp.minimum(jnp.minimum(l_y, nl_y), l_y0)
        changed = jnp.any(nl_x != l_x) | jnp.any(nl_y != l_y)
        return nl_x, nl_y, changed, it + 1

    def cond(carry):
        return carry[2] & (carry[3] < max_sweeps)

    l_x, l_y, _, _ = jax.lax.while_loop(
        cond, body, (jnp.full_like(p_x, INF_D), l_y0, jnp.bool_(True),
                     jnp.int32(0)))

    reach_x, reach_y = l_x < INF_D, l_y < INF_D
    last = jnp.maximum(jnp.max(jnp.where(reach_x, l_x, 0), axis=-1),
                       jnp.max(jnp.where(reach_y, l_y, 0), axis=-1))
    l_x = jnp.where(reach_x, l_x, last[..., None] + 1)
    l_y = jnp.where(reach_y, l_y, last[..., None] + 1)
    return st._replace(p_x=st.p_x - e1 * l_x, p_y=st.p_y - e1 * l_y)


class _ScaleState(NamedTuple):
    """Flattened per-instance ε-scaling carry for the solver-loop runtime.

    The paper's nested loops — Alg. 5.2's ε schedule around Alg. 5.4's
    refine — are flattened into ONE heuristic cycle so the runtime
    (``repro.core.solver_loop``) can freeze or compact instances at cycle
    granularity: each instance carries its own in-flight ε, its Jacobi-round
    count within the current refine, and its schedule-liveness flag, and the
    cycle performs refine-completion transitions (arc fixing, ε downstep,
    refine re-init) per instance the moment ITS refine finishes — not when
    the whole batch's does. Per-instance state trajectories are identical to
    the nested form (every transition is per-instance pure), which is what
    lets compacted, masked, and single-instance solves bit-match.
    """

    c: jax.Array      # (..., n, n) scaled costs (per-instance constants)
    eps: jax.Array    # (...,) ε of the refine currently in flight
    k: jax.Array      # (...,) Jacobi rounds inside the current refine
    alive: jax.Array  # (...,) bool: ε schedule not yet finished
    st: _RefineState


def _refine_init(c, eps, st: _RefineState) -> _RefineState:
    """Refine entry (Alg. 5.2 lines 3-6): strip the flow, reprice X —
    ``F <- 0; p(x) <- -min_y (c'_p(x,y) + eps)``."""
    cpx = _masked(c - st.p_y[..., None, :], st.fixed)
    return st._replace(F=jnp.zeros_like(st.F),
                       p_x=-(jnp.min(cpx, axis=-1) + _exp(eps, 1)))


def _scale_init(w, *, alpha: int) -> _ScaleState:
    """Initial flat state: per-instance ε = ceil(max|c| / alpha), first
    refine entered (Alg. 5.0 start)."""
    w_i = jnp.asarray(w, jnp.int32)
    n = w_i.shape[-1]
    batch = w_i.shape[:-2]
    with jax.named_scope("assignment/init"):
        c = -(n + 1) * w_i                               # minimization form
        C = jnp.maximum(jnp.max(jnp.abs(c), axis=(-2, -1)), 1)  # per inst
        eps0 = jnp.maximum(1, -(-C // alpha))            # eps <- ceil(C/alpha)
        st = _RefineState(
            F=jnp.zeros(batch + (n, n), jnp.int32),
            p_x=jnp.zeros(batch + (n,), jnp.int32),
            p_y=jnp.zeros(batch + (n,), jnp.int32),
            fixed=jnp.zeros(batch + (n, n), jnp.bool_),
            rounds=jnp.zeros(batch, jnp.int32),
            pushes=jnp.zeros(batch, jnp.int32),
            relabels=jnp.zeros(batch, jnp.int32),
        )
        return _ScaleState(c=c, eps=eps0, k=jnp.zeros(batch, jnp.int32),
                           alive=jnp.ones(batch, jnp.bool_),
                           st=_refine_init(c, eps0, st))


def _scale_warm(w, p_y, dmax, *, alpha: int) -> _ScaleState:
    """Warm flat state: re-enter the ε ladder at a delta-bounded rung with
    the prior column prices.

    ``_refine_init`` makes the empty flow EXACTLY ε-optimal for ANY
    ``p_y`` (it reprices every row against the given column prices), so
    warm correctness is unconditional — the ladder still ends at ε = 1,
    where 1-optimality on ``(n+1)``-scaled costs is the exact optimum.
    The prior prices only change how much work is left: a price vector
    that was 1-optimal for the base costs is ``(1 + D)``-optimal for the
    mutated costs, ``D = max |Δc|`` in scaled units, so the ladder can
    start at ``min(1 + D, ε_cold)`` instead of ``ceil(max|c|/α)`` and a
    small delta skips almost every rung.  ``dmax`` is the per-instance
    ``D`` (callers overestimate it freely; it is clamped to the cold ε).
    """
    w_i = jnp.asarray(w, jnp.int32)
    n = w_i.shape[-1]
    batch = w_i.shape[:-2]
    with jax.named_scope("assignment/init"):
        c = -(n + 1) * w_i
        C = jnp.maximum(jnp.max(jnp.abs(c), axis=(-2, -1)), 1)
        eps_cold = jnp.maximum(1, -(-C // alpha))
        eps0 = jnp.clip(1 + jnp.asarray(dmax, jnp.int32), 1, eps_cold)
        st = _RefineState(
            F=jnp.zeros(batch + (n, n), jnp.int32),
            p_x=jnp.zeros(batch + (n,), jnp.int32),
            p_y=jnp.asarray(p_y, jnp.int32),
            fixed=jnp.zeros(batch + (n, n), jnp.bool_),
            rounds=jnp.zeros(batch, jnp.int32),
            pushes=jnp.zeros(batch, jnp.int32),
            relabels=jnp.zeros(batch, jnp.int32),
        )
        return _ScaleState(c=c, eps=eps0, k=jnp.zeros(batch, jnp.int32),
                           alive=jnp.ones(batch, jnp.bool_),
                           st=_refine_init(c, eps0, st))


_scale_warm_jit = jax.jit(_scale_warm, static_argnames=("alpha",))


@functools.lru_cache(maxsize=None)
def _assignment_spec(method: str, alpha: int, max_rounds: int,
                     rounds_per_heuristic: int, use_price_update: bool,
                     use_arc_fixing: bool, backend: str) -> LoopSpec:
    """The assignment solver's registration with the solver-loop runtime.

    One cycle = ``rounds_per_heuristic`` Jacobi rounds of the refine round
    function, the price-update sweep (paper Alg. 5.3), and — for instances
    whose refine just finished (perfect matching or ``max_rounds`` hit) —
    the refine-exit transition: arc fixing at the finished ε, ε downstep,
    and re-entry into the next refine (or schedule death after the ε = 1
    pass). Cached per static-knob tuple so the runtime's jitted drivers
    cache-hit on the spec.
    """
    round_fn = functools.partial(
        {"pushrelabel": _round_pushrelabel,
         "auction": _round_auction}[method], backend=backend)

    def cycle(s: _ScaleState) -> _ScaleState:
        c, eps, k, alive, st = s
        n = c.shape[-1]

        def inner(_, t):
            with jax.named_scope("assignment/refine"):
                return round_fn(c, eps, t)

        new = jax.lax.fori_loop(0, rounds_per_heuristic, inner, st)
        if use_price_update:
            with jax.named_scope("assignment/price_update"):
                perf = _is_perfect(new.F)
                if perf.ndim == 0:  # single instance: genuinely skip it
                    new = jax.lax.cond(
                        perf, lambda t: t,
                        lambda t: price_update(c, eps, t, max_sweeps=2 * n),
                        new)
                else:
                    new = _freeze(
                        ~perf, price_update(c, eps, new, max_sweeps=2 * n),
                        new)
        with jax.named_scope("assignment/rescale"):
            k = k + rounds_per_heuristic
            done = _is_perfect(new.F) | (k >= max_rounds)
            if use_arc_fixing:
                # Arc fixing at refine exit (paper §5.2, Goldberg [8]): now
                # that f is a genuine ε-optimal FLOW w.r.t. p, any unmatched
                # arc with c_p > 2nε carries zero flow in every ε'-optimal
                # flow with ε' <= ε — freeze it for all subsequent refines.
                # (Matched arcs always satisfy |c_p| <= ε, so only F == 0
                # arcs can be fixed; the mask replaces the paper's
                # adjacency-list deletion with flow = -10 sentinels.)
                cp = c + new.p_x[..., :, None] - new.p_y[..., None, :]
                fix = new.fixed | ((cp > 2 * n * _exp(eps, 2))
                                   & (new.F == 0))
                new = new._replace(
                    fixed=jnp.where(done[..., None, None], fix, new.fixed))
            # ε schedule step for finished refines: divide down, or die
            # after the ε = 1 pass (Goldberg–Kennedy: 1-optimal on scaled
            # costs = exact optimum).
            still = alive & ~(done & (eps <= 1))
            eps_next = jnp.where(done & (eps > 1),
                                 jnp.maximum(1, -(-eps // alpha)), eps)
            new = _freeze(done & still, _refine_init(c, eps_next, new), new)
            return _ScaleState(c=c, eps=eps_next, k=jnp.where(done, 0, k),
                               alive=still, st=new)

    def live(s: _ScaleState, rounds: jax.Array) -> jax.Array:
        return s.alive

    return LoopSpec(cycle=cycle, live=live,
                    rounds_per_cycle=rounds_per_heuristic, lead_axes_fn=None)


def _assignment_finalize(w, st: _RefineState) -> AssignmentResult:
    """Matching, weight (original scale), and convergence from a final state.

    Unmatched rows (all-zero F row — possible only when ``max_rounds`` was
    hit before a perfect matching) get the sentinel ``n``, so callers can
    always detect them; matched rows get their argmax column.
    """
    w_i = jnp.asarray(w, jnp.int32)
    n = w_i.shape[-1]
    with jax.named_scope("assignment/finalize"):
        matched = jnp.sum(st.F, axis=-1) > 0
        col = jnp.where(matched, jnp.argmax(st.F, axis=-1), n)
        weight = jnp.sum(jnp.where(matched, jnp.take_along_axis(
            w_i, jnp.minimum(col, n - 1)[..., :, None], axis=-1)[..., 0], 0),
            axis=-1)
        return AssignmentResult(
            col_of_row=col, weight=weight, p_x=st.p_x, p_y=st.p_y,
            rounds=st.rounds, pushes=st.pushes, relabels=st.relabels,
            converged=_is_perfect(st.F),
        )


@functools.partial(jax.jit, static_argnames=(
    "method", "alpha", "max_rounds", "rounds_per_heuristic",
    "use_price_update", "use_arc_fixing", "backend"))
def _solve_assignment_impl(
    w: jax.Array,
    *,
    method: str,
    alpha: int,
    max_rounds: int,
    rounds_per_heuristic: int,
    use_price_update: bool,
    use_arc_fixing: bool,
    backend: str,
) -> AssignmentResult:
    """Jitted solver body, rank-polymorphic (shard_map-able on (B, n, n)).

    Orchestration lives in ``repro.core.solver_loop.run_masked``: each
    instance runs its own flattened ε-scaling schedule (``_ScaleState``) and
    is frozen via selects once its schedule finishes, while the rest of the
    batch keeps refining.
    """
    state = _scale_init(w, alpha=alpha)
    spec = _assignment_spec(method, alpha, max_rounds, rounds_per_heuristic,
                            use_price_update, use_arc_fixing, backend)
    state, _ = run_masked(spec, state, state.eps.shape)
    return _assignment_finalize(w, state.st)


_scale_init_jit = jax.jit(_scale_init, static_argnames=("alpha",))
_assignment_finalize_jit = jax.jit(_assignment_finalize)


def _solve_assignment_compact(
    w: jax.Array,
    *,
    lanes=None,
    method: str,
    alpha: int,
    max_rounds: int,
    rounds_per_heuristic: int,
    use_price_update: bool,
    use_arc_fixing: bool,
    backend: str,
) -> AssignmentResult:
    """Batched solve with early-exit compaction on the (B,) axis.

    ``run_compacted`` drives the host loop: instances whose ε schedule
    finished are dropped from the working set — still-live ones are
    gathered into dense pow2-sized sub-batches between jitted cycle
    segments — instead of being select-masked until the whole batch drains.
    Results bit-match the masked path (tests/test_compact.py).
    """
    state = _scale_init_jit(jnp.asarray(w, jnp.int32), alpha=alpha)
    spec = _assignment_spec(method, alpha, max_rounds, rounds_per_heuristic,
                            use_price_update, use_arc_fixing, backend)
    state, _ = run_compacted(spec, state, w.shape[0], lanes=lanes)
    return _assignment_finalize_jit(jnp.asarray(w, jnp.int32), state.st)


def _solve_assignment_stepped(
    w: jax.Array,
    *,
    method: str,
    alpha: int,
    max_rounds: int,
    rounds_per_heuristic: int,
    use_price_update: bool,
    use_arc_fixing: bool,
    backend: str,
) -> AssignmentResult:
    """Eager masked solve for cycle telemetry (any batch rank).

    Same init/finalize jits as the compacted path around an eager
    ``run_masked``, which host-steps the jitted cycle under the active
    ``cycle_events(masked=True)`` hook that routed here.  Bit-matches
    ``_solve_assignment_impl`` (tests/test_obs.py).
    """
    w_i = jnp.asarray(w, jnp.int32)
    state = _scale_init_jit(w_i, alpha=alpha)
    spec = _assignment_spec(method, alpha, max_rounds, rounds_per_heuristic,
                            use_price_update, use_arc_fixing, backend)
    state, _ = run_masked(spec, state, state.eps.shape)
    return _assignment_finalize_jit(w_i, state.st)


def solve_assignment(
    w: jax.Array,
    *,
    method: str = "auction",
    alpha: int = 10,
    max_rounds: int = 200_000,
    rounds_per_heuristic: int = 16,
    use_price_update: bool = True,
    use_arc_fixing: bool = True,
    backend: str = "xla",
    compact: bool = False,
    mesh=None,
    mesh_axis: str | None = None,
) -> AssignmentResult:
    """Max-weight perfect matching on a complete bipartite graph (paper §5).

    Args:
      w: integer weight matrix — ``(n, n)`` for one instance or ``(B, n, n)``
        for a batch solved in one dispatch (see
        ``repro.core.batch.solve_assignment_batch`` for the ragged
        list-of-matrices front end). Integer weights only (exactness of the
        (n+1)-scaling argument); floats should be pre-quantized by the
        caller. Requires ``n * (n+1) * max|w|`` within int32 range.
      method: ``"auction"`` (beyond-paper top-2 bidding refine, fewer
        rounds) or ``"pushrelabel"`` (paper-faithful Algorithm 5.4).
      alpha: ε-scaling divisor; 10 is the paper's factor (§5.5).
      max_rounds: per-refine Jacobi-round cap; an instance that hits it
        reports ``converged=False`` and may leave rows unmatched (their
        ``col_of_row`` entries hold the sentinel ``n``).
      rounds_per_heuristic: Jacobi rounds between price-update sweeps.
      use_price_update: run the vectorized Bellman–Ford price-update
        heuristic (paper Alg. 5.3).
      use_arc_fixing: freeze arcs with ``c_p > 2nε`` between refines
        (paper §5.2).
      backend: ``"xla"`` or ``"pallas"`` (the bidding/min stage as a TPU
        kernel).
      compact: early-exit compaction (``repro.core.solver_loop``; batched
        ``(B, n, n)`` weights only). Instances whose ε schedule finished
        are dropped from the working set between jitted cycle segments —
        still-live instances are gathered into dense pow2-sized
        sub-batches — instead of being select-masked until the whole batch
        drains. Worth it when convergence is ragged across the batch. With
        ``mesh=``, compaction stays within each shard (one host lane per
        device, no collectives).
      mesh: optional ``jax.sharding.Mesh``
        (``repro.launch.mesh.make_solver_mesh``). Requires batched ``w``
        ``(B, n, n)`` with ``B`` divisible by the shard count; the batch
        axis is then partitioned under ``shard_map`` — each device refines
        its own instances with no cross-device sync (per-instance ε
        schedules and liveness masks already make instances independent),
        and results bit-match the unsharded batched solve
        (tests/test_shard.py).
      mesh_axis: mesh axis to shard over (default: the mesh's first axis).

    Returns:
      ``AssignmentResult`` with leaves leading with the batch axes of ``w``:
      ``col_of_row (..., n)`` (sentinel ``n`` = unmatched row, only when not
      converged), ``weight (...,)`` on the original scale, prices
      ``p_x``/``p_y (..., n)``, operation counters, and ``converged``.

    Convergence contract: each instance runs its own ε-scaling schedule
    (ε starts at that instance's max|c| and divides by ``alpha`` down to 1);
    ``converged=True`` means the final 1-optimal flow is an EXACT optimal
    matching (Goldberg–Kennedy integer scaling). Instances that finish early
    are frozen by liveness masks, so batched results bit-match a loop of
    single-instance solves (tests/test_batch.py).
    """
    kw = dict(method=method, alpha=alpha, max_rounds=max_rounds,
              rounds_per_heuristic=rounds_per_heuristic,
              use_price_update=use_price_update,
              use_arc_fixing=use_arc_fixing, backend=backend)
    if compact:
        if w.ndim != 3:
            raise ValueError(
                f"compact=True needs batched (B, n, n) weights, got shape "
                f"{w.shape}; compaction drops converged instances from a "
                f"batch axis")
        lanes = None
        if mesh is not None:
            from repro.launch.mesh import compact_lanes
            lanes = compact_lanes(mesh, mesh_axis, w.shape[0])
        return _solve_assignment_compact(w, lanes=lanes, **kw)
    if mesh is None:
        if masked_events_active():
            return _solve_assignment_stepped(w, **kw)
        return _solve_assignment_impl(w, **kw)
    if w.ndim != 3:
        raise ValueError(
            f"mesh-sharded solve_assignment needs batched (B, n, n) weights, "
            f"got shape {w.shape}")
    from repro.launch.mesh import dispatch_sharded
    return dispatch_sharded(_solve_assignment_impl, (w,), w.shape[0],
                            mesh, mesh_axis, **kw)
