"""Synchronous data-parallel push-relabel max-flow on 2D grid graphs.

TPU adaptation of the paper's §4 (Hong's lock-free push-relabel, CUDA) — see
DESIGN.md §2. One Jacobi round applies the per-node decision of Algorithm 4.5
to EVERY node simultaneously:

  * each active node (e > 0) finds its lowest residual neighbour (sink at
    height 0, the four grid neighbours, source at height N),
  * if strictly lower, it pushes ``min(e, cap)`` toward it (Hong's relaxed
    rule: push whenever ``h(x) > h(ỹ)``, not only ``== h+1``),
  * otherwise it relabels to ``h(ỹ) + 1``.

Concurrent ``e(y) += δ`` updates (atomicAdd in the paper) become one shift-and-
add aggregation per round — associativity of addition replaces atomicity.
The global/gap relabeling heuristic (paper Alg. 4.4/4.8) is a vectorized
min-plus wavefront BFS from the sink run every ``rounds_per_heuristic`` rounds,
inside the same jitted while_loop (no host round-trip, unlike the CPU-GPU
hybrid of Hong & He).

Grid layout: ``cap[d, i, j]`` is the residual capacity of the edge from node
(i, j) toward its neighbour in direction d ∈ {UP, DOWN, LEFT, RIGHT}.
``cap_src``/``cap_sink`` are the residual capacities of the terminal edges
(x → s) and (x → t).

Batching: every helper here operates on the LAST two axes, so state arrays may
carry leading batch dimensions — ``e``: ``(..., H, W)``, ``cap``:
``(4, ..., H, W)`` (direction axis first so ``cap[d]`` stays a plain index).
``maxflow_grid`` solves one instance; ``maxflow_grid_batch`` solves a stack of
same-shape instances in ONE jitted dispatch, with per-instance convergence
masks so converged instances become no-ops instead of blocking the batch
(see ``repro.core.batch`` for the pad-and-bucket front end). Outer
orchestration is delegated to ``repro.core.solver_loop``: masked iteration
by default, early-exit compaction — converged instances leave the working
set between cycles — under ``compact=True``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.solver_loop import (LoopSpec, masked_events_active,
                                    run_compacted, run_masked)

UP, DOWN, LEFT, RIGHT = 0, 1, 2, 3
_OPP = (DOWN, UP, RIGHT, LEFT)
INF_H = jnp.int32(2 ** 30)


class GridProblem(NamedTuple):
    """A grid-cut instance (the Kolmogorov graph construction of [12])."""

    cap_nbr: jax.Array   # (4, H, W) neighbour capacities
    cap_src: jax.Array   # (H, W) capacity of s -> x
    cap_sink: jax.Array  # (H, W) capacity of x -> t


class GridFlowState(NamedTuple):
    e: jax.Array          # (..., H, W) excess
    h: jax.Array          # (..., H, W) heights, int32
    cap: jax.Array        # (4, ..., H, W) residual neighbour capacities
    cap_src: jax.Array    # (..., H, W) residual x -> s (returns excess)
    cap_sink: jax.Array   # (..., H, W) residual x -> t
    sink_flow: jax.Array  # (...,) total flow delivered to the sink
    src_flow: jax.Array   # (...,) total flow returned to the source
    # (...,) int32 count of global-relabel (heuristic) invocations per
    # instance, excluding the round-0 init BFS. None = untracked (states
    # built by hand, e.g. kernel unit tests); solver-built states always
    # carry it. None is an empty pytree subtree, so both forms jit.
    heur: jax.Array | None = None


class GridFlowResult(NamedTuple):
    flow: jax.Array        # (...,) max-flow value(s)
    cut: jax.Array         # (..., H, W) bool — True = sink side of the cut
    state: GridFlowState   # NOTE: maxflow_grid_batch returns cap (B, 4, H, W)
    rounds: jax.Array      # (...,) Jacobi rounds executed per instance
    converged: jax.Array   # (...,) bool
    # (...,) heuristic invocations (see GridFlowState.heur); None when the
    # state was solved by a pre-observability caller.
    heuristics: jax.Array | None = None


def _nbr_h(h: jax.Array, d: int) -> jax.Array:
    """Height of the neighbour in direction d, INF outside the grid.

    Operates on the last two (H, W) axes; leading batch axes pass through.
    """
    big = INF_H
    if d == UP:
        return jnp.concatenate(
            [jnp.full_like(h[..., :1, :], big), h[..., :-1, :]], axis=-2)
    if d == DOWN:
        return jnp.concatenate(
            [h[..., 1:, :], jnp.full_like(h[..., :1, :], big)], axis=-2)
    if d == LEFT:
        return jnp.concatenate(
            [jnp.full_like(h[..., :, :1], big), h[..., :, :-1]], axis=-1)
    return jnp.concatenate(
        [h[..., :, 1:], jnp.full_like(h[..., :, :1], big)], axis=-1)


def _move(a: jax.Array, d: int) -> jax.Array:
    """Deposit a[x] at x's neighbour in direction d (zero fill at border)."""
    z = jnp.zeros_like
    if d == UP:
        return jnp.concatenate([a[..., 1:, :], z(a[..., :1, :])], axis=-2)
    if d == DOWN:
        return jnp.concatenate([z(a[..., :1, :]), a[..., :-1, :]], axis=-2)
    if d == LEFT:
        return jnp.concatenate([a[..., :, 1:], z(a[..., :, :1])], axis=-1)
    return jnp.concatenate([z(a[..., :, :1]), a[..., :, :-1]], axis=-1)


def _gsum(a: jax.Array) -> jax.Array:
    """Per-instance grid sum: reduce the trailing (H, W) axes only."""
    return jnp.sum(a, axis=(-2, -1))


def jacobi_round(state: GridFlowState, n_nodes: jax.Array) -> GridFlowState:
    """One synchronous push/relabel round over every node (Alg. 4.5, Jacobi).

    Shape-polymorphic over leading batch axes: ``e`` may be ``(..., H, W)``
    with ``cap`` ``(4, ..., H, W)``; a converged instance (no active node) is
    an exact no-op, which is what makes the batched solver sound.
    """
    e, h, cap, cap_src, cap_sink, sink_flow, src_flow = state[:7]
    active = e > 0

    # Candidate heights: [sink, source, UP, DOWN, LEFT, RIGHT]; INF if the
    # corresponding residual edge is absent. argmin picks the first minimum,
    # so the sink (height 0) always wins when available, and ties at height N
    # prefer the source (stranded excess drains home instead of bouncing).
    cand = jnp.stack(
        [jnp.where(cap_sink > 0, 0, INF_H),
         jnp.where(cap_src > 0, n_nodes, INF_H)]
        + [jnp.where(cap[d] > 0, _nbr_h(h, d), INF_H) for d in range(4)],
        axis=0,
    )  # (6, ..., H, W)
    h_min = jnp.min(cand, axis=0)
    choice = jnp.argmin(cand, axis=0)

    do_push = active & (h > h_min)
    do_relabel = active & (h <= h_min) & (h_min < INF_H)

    # --- relabel (needs no atomicity: only x writes h(x); paper line 17) ---
    h_new = jnp.where(do_relabel, h_min + 1, h)

    # --- push (fulfillment stages aggregated by shift-adds) ---
    cap_choice = jnp.stack([cap_sink, cap_src] + [cap[d] for d in range(4)], 0)
    delta_all = jnp.where(do_push, jnp.minimum(e, jnp.take_along_axis(
        cap_choice, choice[None], axis=0)[0]), 0.0)

    d_sink = jnp.where(choice == 0, delta_all, 0.0)
    d_src = jnp.where(choice == 1, delta_all, 0.0)
    d_nbr = [jnp.where(choice == 2 + d, delta_all, 0.0) for d in range(4)]

    out = d_sink + d_src + sum(d_nbr)
    inflow = sum(_move(d_nbr[d], d) for d in range(4))

    e_new = e - out + inflow
    cap_new = jnp.stack(
        [cap[d] - d_nbr[d] + _move(d_nbr[_OPP[d]], _OPP[d]) for d in range(4)], 0
    )
    return state._replace(
        e=e_new,
        h=h_new,
        cap=cap_new,
        cap_src=cap_src - d_src,
        cap_sink=cap_sink - d_sink,
        sink_flow=sink_flow + _gsum(d_sink),
        src_flow=src_flow + _gsum(d_src),
    )


def jacobi_round_multipush(state: GridFlowState,
                           n_nodes: jax.Array) -> GridFlowState:
    """Beyond-paper round: push to EVERY strictly-lower residual neighbour.

    The paper's Algorithm 4.5 moves one unit-direction per node per round;
    saturating all admissible edges per round (priority: sink, source, then
    the grid directions) drains excess in fewer rounds at identical
    per-round cost on the VPU (every push is still admissible under Hong's
    relaxed rule against pre-round heights, so correctness is inherited).
    """
    e, h, cap, cap_src, cap_sink, sink_flow, src_flow = state[:7]
    active = e > 0

    cand_h = [jnp.where(cap_sink > 0, 0, INF_H),
              jnp.where(cap_src > 0, n_nodes, INF_H)] + \
             [jnp.where(cap[d] > 0, _nbr_h(h, d), INF_H) for d in range(4)]
    cand_cap = [cap_sink, cap_src] + [cap[d] for d in range(4)]

    remaining = jnp.where(active, e, 0.0)
    deltas = []
    pushed_any = jnp.zeros_like(active)
    for ch, cc in zip(cand_h, cand_cap):
        ok = active & (h > ch)
        d = jnp.where(ok, jnp.minimum(remaining, cc), 0.0)
        remaining = remaining - d
        pushed_any = pushed_any | (d > 0)
        deltas.append(d)
    d_sink, d_src, d_nbr = deltas[0], deltas[1], deltas[2:]

    # relabel only nodes that could not push anywhere
    h_min = jnp.minimum(jnp.minimum(cand_h[0], cand_h[1]),
                        jnp.minimum(jnp.minimum(cand_h[2], cand_h[3]),
                                    jnp.minimum(cand_h[4], cand_h[5])))
    do_relabel = active & ~pushed_any & (h <= h_min) & (h_min < INF_H)
    h_new = jnp.where(do_relabel, h_min + 1, h)

    out = d_sink + d_src + sum(d_nbr)
    inflow = sum(_move(d_nbr[d], d) for d in range(4))
    cap_new = jnp.stack(
        [cap[d] - d_nbr[d] + _move(d_nbr[_OPP[d]], _OPP[d]) for d in range(4)],
        0)
    return state._replace(
        e=e - out + inflow, h=h_new, cap=cap_new,
        cap_src=cap_src - d_src, cap_sink=cap_sink - d_sink,
        sink_flow=sink_flow + _gsum(d_sink),
        src_flow=src_flow + _gsum(d_src),
    )


def bfs_heights(cap: jax.Array, cap_sink: jax.Array, h_prev: jax.Array,
                n_nodes: jax.Array, max_iters: int) -> jax.Array:
    """Vectorized backwards BFS from the sink (paper Alg. 4.4 + gap relabel).

    Min-plus wavefront: h(x) = 1 if residual x->t, else 1 + min over residual
    out-edges (x, y) of h(y). Unreached nodes (the 'gap') get height >= N so
    the flow stranded on them returns to the source (paper §4.6). We keep
    ``max(h_prev, N)`` rather than the paper's plain ``N`` so heights already
    climbing toward the source (up to 2N-1) are never reset — resetting would
    let stranded excess oscillate between heuristic invocations.
    """
    def body(carry):
        h, _, it = carry
        relaxed = h
        for d in range(4):
            cand = jnp.where(cap[d] > 0, _nbr_h(h, d) + 1, INF_H)
            relaxed = jnp.minimum(relaxed, cand)
        relaxed = jnp.minimum(relaxed, h0)
        changed = jnp.any(relaxed != h)
        return relaxed, changed, it + 1

    def cond(carry):
        _, changed, it = carry
        return changed & (it < max_iters)

    with jax.named_scope("maxflow/relabel"):
        h0 = jnp.where(cap_sink > 0, jnp.int32(1), INF_H)
        h, _, _ = jax.lax.while_loop(cond, body,
                                     (h0, jnp.bool_(True), jnp.int32(0)))
        # gap relabel
        return jnp.where(h >= INF_H, jnp.maximum(h_prev, n_nodes), h)


def check_no_violations(state: GridFlowState) -> jax.Array:
    """True iff no residual edge (x,y) has h(x) > h(y)+1 (per instance).

    The paper's hybrid global relabel (Alg. 4.8 lines 1-6) cancels such
    violating edges, which arise under asynchronous interleaving. Our Jacobi
    schedule provably never creates them (DESIGN.md §2); this check is the
    runtime witness (asserted in tests / hypothesis properties). Returns a
    scalar for single instances, ``(B,)`` for batched states. Accepts both
    public layouts: ``maxflow_grid`` states (``cap`` ``(4, H, W)``) and
    ``maxflow_grid_batch`` results (``cap`` ``(B, 4, H, W)``).
    """
    cap = state.cap
    if state.h.ndim > 2:  # batched public layout -> internal (4, B, H, W)
        cap = jnp.moveaxis(cap, -3, 0)
    ok = jnp.ones(state.h.shape[:-2], jnp.bool_)
    for d in range(4):
        viol = (cap[d] > 0) & (state.h > _nbr_h(state.h, d) + 1)
        ok &= ~jnp.any(viol, axis=(-2, -1))
    return ok


VALID_BACKENDS = ("xla", "multipush", "pallas", "balanced")


def _round_fn(backend: str):
    """Jacobi-round implementation for a backend flag.

    Unknown strings raise (a typo'd backend silently solving with the
    default XLA round is a perf bug that looks like a perf result).
    """
    if backend == "pallas":  # the paper-optimized hot loop as a TPU kernel
        from repro.kernels.grid_push.ops import jacobi_round_pallas
        return jacobi_round_pallas
    if backend == "multipush":  # beyond-paper: saturate all lower nbrs
        return jacobi_round_multipush
    if backend == "balanced":  # active-tile scheduled kernel (drop the
        from repro.kernels.grid_push.ops import \
            jacobi_round_scheduled      # pushed-flow stall signal here)
        return lambda s, n: jacobi_round_scheduled(s, n)[0]
    if backend == "xla":
        return jacobi_round
    raise ValueError(
        f"unknown maxflow backend {backend!r}; valid backends: "
        f"{', '.join(VALID_BACKENDS)}")


@functools.lru_cache(maxsize=None)
def _grid_spec(rounds_per_heuristic: int, max_rounds: int,
               bfs_max_iters: int, backend: str,
               stall_threshold: float = 0.05) -> LoopSpec:
    """The grid solver's registration with the solver-loop runtime.

    Cached per static-knob tuple so repeated solves hand the runtime the
    SAME spec object and the compacted drivers' jitted cycles cache-hit.
    The cycle is shape-polymorphic: ``n_nodes`` and the BFS cap derive from
    the state's trailing (H, W), so one spec serves every grid size and
    every compaction sub-batch size.

    Every backend's cycle is exactly ``rounds_per_heuristic`` rounds (the
    runtime's rounds accounting assumes it). The fixed-cadence backends end
    the cycle with an unconditional global relabel; ``"balanced"`` ends it
    with a STALL-DRIVEN one — a per-instance EWMA of terminal-retired flow
    per unit remaining excess decides which instances re-run the (bidirectional)
    relabel pass, and ``lax.cond`` skips its cost entirely when no instance
    stalled. The trigger and the relabel are pure per-instance functions of
    per-instance state, so the batched == loop-of-singles bit-match
    contract survives (tests/test_balanced.py).
    """
    round_fn = _round_fn(backend)
    # The fused Pallas round writes every plane of the loop carry while it
    # still reads them, so a loop body of one round copies the whole carry
    # before each call; two rounds an iteration alternate between the carry
    # and temporaries, with no copy.
    unroll = 2 if backend == "pallas" else 1
    if backend == "balanced":
        from repro.kernels.bfs_relabel.ops import bfs_relabel_heights
        from repro.kernels.grid_push.ops import jacobi_round_scheduled

    def _count_heur(new: GridFlowState, invoked) -> GridFlowState:
        if new.heur is None:
            return new
        return new._replace(heur=new.heur + invoked.astype(jnp.int32))

    def cycle(state: GridFlowState) -> GridFlowState:
        H, W = state.e.shape[-2:]
        n_nodes = jnp.int32(H * W + 2)
        iters = bfs_max_iters or (H * W + 2)

        if backend == "balanced":
            batch = state.e.shape[:-2]

            def inner(_, carry):
                s, ewma = carry
                remaining = jnp.maximum(_gsum(s.e), 1.0)
                with jax.named_scope("maxflow/push"):
                    s, retired = jacobi_round_scheduled(s, n_nodes)
                # EWMA of per-round progress: excess RETIRED at a terminal
                # this round as a fraction of the excess still in flight
                # (inter-node moves don't count — height-plateau ping-pong
                # must read as a stall, not progress). Alpha 1/2 ≈ a
                # two-round memory — long enough to ride out single slack
                # rounds, short enough to catch a stall within a cycle.
                ewma = 0.5 * ewma + 0.5 * (retired / remaining)
                return s, ewma

            new, ewma = jax.lax.fori_loop(
                0, rounds_per_heuristic, inner,
                (state, jnp.ones(batch, jnp.float32)))
            stalled = (jnp.any(new.e > 0, axis=(-2, -1))
                       & (ewma < stall_threshold))

            def relabel(s: GridFlowState) -> jax.Array:
                with jax.named_scope("maxflow/relabel"):
                    h_bfs = bfs_relabel_heights(s.cap, s.cap_src, s.cap_sink,
                                                s.h, n_nodes, iters)
                    return jnp.where(stalled[..., None, None], h_bfs, s.h)

            h_new = jax.lax.cond(jnp.any(stalled), relabel,
                                 lambda s: s.h, new)
            return _count_heur(new._replace(h=h_new), stalled)

        def inner(_, s):
            with jax.named_scope("maxflow/push"):
                return round_fn(s, n_nodes)

        new = jax.lax.fori_loop(0, rounds_per_heuristic, inner, state,
                                unroll=unroll)
        new = new._replace(
            h=bfs_heights(new.cap, new.cap_sink, new.h, n_nodes, iters))
        return _count_heur(new, jnp.ones(state.e.shape[:-2], jnp.bool_))

    def live(state: GridFlowState, rounds: jax.Array) -> jax.Array:
        return jnp.any(state.e > 0, axis=(-2, -1)) & (rounds < max_rounds)

    def lead_axes(a, batch_ndim: int) -> int:
        # the only leaf with an axis before the batch axes is cap
        # (4, ..., H, W) — the direction axis leads
        return 1 if a.ndim - batch_ndim == 3 else 0

    return LoopSpec(cycle=cycle, live=live,
                    rounds_per_cycle=rounds_per_heuristic,
                    lead_axes_fn=lead_axes,
                    heur=lambda s: s.heur)


def _grid_init(cap0, cs0, ct0, *, bfs_max_iters: int) -> GridFlowState:
    """Paper Alg. 4.7 init: saturate s->x, heights from a round-0 BFS.

    Internal layout — ``cs0``/``ct0`` ``(..., H, W)``, ``cap0``
    ``(4, ..., H, W)``.
    """
    *b, H, W = cs0.shape
    bshape = tuple(b)
    n_nodes = jnp.int32(H * W + 2)
    bfs_iters = bfs_max_iters or (H * W + 2)
    with jax.named_scope("maxflow/init"):
        state = GridFlowState(
            e=cs0.astype(jnp.float32),
            h=jnp.zeros(bshape + (H, W), jnp.int32),
            cap=cap0.astype(jnp.float32),
            cap_src=cs0.astype(jnp.float32),   # residual x -> s (saturated)
            cap_sink=ct0.astype(jnp.float32),
            sink_flow=jnp.zeros(bshape, jnp.float32),
            src_flow=jnp.zeros(bshape, jnp.float32),
            heur=jnp.zeros(bshape, jnp.int32),  # init BFS below not counted
        )
        # Start from BFS-consistent heights (global relabel at round 0).
        return state._replace(h=bfs_heights(state.cap, state.cap_sink,
                                            state.h, n_nodes, bfs_iters))


def _grid_finalize(state: GridFlowState, rounds, *,
                   bfs_max_iters: int) -> GridFlowResult:
    """Min cut + convergence flags from a finished (internal-layout) state.

    Sink side of the cut = nodes that still reach t in the residual graph.
    """
    H, W = state.e.shape[-2:]
    n_nodes = jnp.int32(H * W + 2)
    bfs_iters = bfs_max_iters or (H * W + 2)
    with jax.named_scope("maxflow/finalize"):
        h_bfs = bfs_heights(state.cap, state.cap_sink, state.h, n_nodes,
                            bfs_iters)
        return GridFlowResult(
            flow=state.sink_flow,
            cut=h_bfs < n_nodes,
            state=state,
            rounds=rounds,
            converged=~jnp.any(state.e > 0, axis=(-2, -1)),
            heuristics=state.heur,
        )


def _solve_grid(cap0, cs0, ct0, *, rounds_per_heuristic, max_rounds,
                bfs_max_iters, backend,
                stall_threshold=0.05) -> GridFlowResult:
    """Shared masked solver loop, rank-polymorphic over leading batch axes.

    ``cs0``/``ct0`` are ``(..., H, W)`` with ``cap0`` ``(4, ..., H, W)``.
    Orchestration lives in ``repro.core.solver_loop.run_masked``: the loop
    predicate is a per-instance liveness mask (batch shape ``(...,)``,
    scalar for a single instance) and converged instances are frozen via
    selects. With no batch axes the mask is the scalar predicate of the
    original single-instance loop, so both entry points share one
    trajectory.
    """
    state = _grid_init(cap0, cs0, ct0, bfs_max_iters=bfs_max_iters)
    spec = _grid_spec(rounds_per_heuristic, max_rounds, bfs_max_iters,
                      backend, stall_threshold)
    state, rounds = run_masked(spec, state, cs0.shape[:-2])
    return _grid_finalize(state, rounds, bfs_max_iters=bfs_max_iters)


_grid_init_jit = jax.jit(_grid_init, static_argnames=("bfs_max_iters",))
_grid_finalize_jit = jax.jit(_grid_finalize,
                             static_argnames=("bfs_max_iters",))


def _grid_warm(cap0, cs0, ct0, base_cap, base_ct, prior_cap, prior_ct,
               *, bfs_max_iters: int) -> GridFlowState:
    """Warm restart (arXiv 2511.01235 §3): clamp the prior flow to the new
    capacities, repair conservation deficits, re-BFS the heights.

    Internal layout throughout (``cap*`` ``(4, ..., H, W)``, rest
    ``(..., H, W)``).  ``base_*`` are the capacities the prior solve ran
    against; the prior NET flow per grid arc is recovered from its residuals
    as ``base_cap - prior_cap`` and per sink edge as ``base_ct - prior_ct``.
    The restart invariant (Baumstark et al., arXiv 1507.01926) is that the
    height function stays a valid lower bound on residual sink distance —
    guaranteed here by recomputing exact BFS heights against the repaired
    residual graph.  (Fresh zero gap memory, not the prior heights: exact
    distances plus a uniform ``N`` on the unreachable region can never
    contain a violating edge, whereas prior heights carried across a
    capacity delta can — the no-violations witness stays unconditional.)

    Repair: clamping to shrunken capacities can leave nodes with negative
    excess (more outflow than inflow).  A Jacobi fixpoint loop lets every
    deficit node cut its own outgoing flow (sink edge first, then the grid
    directions) until conservation holds with ``e >= 0`` everywhere; flows
    only ever decrease, so the loop terminates.  Any instance still in
    deficit at the iteration cap (unreachable for integral capacities, but
    cheap to guard) falls back to its cold init, keeping warm-vs-cold
    equivalence unconditional.
    """
    *b, H, W = cs0.shape
    n_nodes = jnp.int32(H * W + 2)
    bfs_iters = bfs_max_iters or (H * W + 2)
    capn = cap0.astype(jnp.float32)
    csn = cs0.astype(jnp.float32)
    ctn = ct0.astype(jnp.float32)

    # prior positive flow per arc, clamped to the new capacities
    f = base_cap.astype(jnp.float32) - prior_cap.astype(jnp.float32)
    phi = jnp.minimum(jnp.maximum(f, 0.0), capn)
    fs = jnp.clip(base_ct.astype(jnp.float32) - prior_ct.astype(jnp.float32),
                  0.0, ctn)

    def excess(phi, fs):
        # source saturates (cold-init convention): inflow from s is csn
        inflow = sum(_move(phi[d], d) for d in range(4))
        return csn + inflow - jnp.sum(phi, axis=0) - fs

    def body(carry):
        phi, fs, e, it = carry
        deficit = jnp.maximum(-e, 0.0)
        r = jnp.minimum(deficit, fs)
        fs = fs - r
        deficit = deficit - r
        rows = []
        for d in range(4):
            r = jnp.minimum(deficit, phi[d])
            rows.append(phi[d] - r)
            deficit = deficit - r
        phi = jnp.stack(rows, 0)
        return phi, fs, excess(phi, fs), it + 1

    def cond(carry):
        _, _, e, it = carry
        return jnp.any(e < 0) & (it < jnp.int32(4 * H * W + 8))

    phi, fs, e, _ = jax.lax.while_loop(
        cond, body, (phi, fs, excess(phi, fs), jnp.int32(0)))

    resid = jnp.stack(
        [capn[d] - phi[d] + _move(phi[_OPP[d]], _OPP[d]) for d in range(4)], 0)
    cap_sink = ctn - fs
    warm = GridFlowState(
        e=jnp.maximum(e, 0.0),
        h=bfs_heights(resid, cap_sink, jnp.zeros(csn.shape, jnp.int32),
                      n_nodes, bfs_iters),
        cap=resid,
        cap_src=csn,                       # residual x -> s after saturation
        cap_sink=cap_sink,
        sink_flow=_gsum(fs),
        src_flow=jnp.zeros(tuple(b), jnp.float32),
        heur=jnp.zeros(tuple(b), jnp.int32),
    )
    bad = jnp.any(e < 0, axis=(-2, -1))    # per-instance repair failure
    cold = _grid_init(cap0, cs0, ct0, bfs_max_iters=bfs_max_iters)

    def pick(w, c):
        extra = w.ndim - bad.ndim          # trailing (H, W) / leading (4,)
        mask = bad
        if w.ndim - len(b) == 3:           # cap leaf: leading direction axis
            mask = bad[None]
            extra -= 1
        return jnp.where(mask.reshape(mask.shape + (1,) * extra), c, w)

    return jax.tree.map(pick, warm, cold)


_grid_warm_jit = jax.jit(_grid_warm, static_argnames=("bfs_max_iters",))


def _grid_batch_compact(cap0, cs0, ct0, *, rounds_per_heuristic, max_rounds,
                        bfs_max_iters, backend, stall_threshold=0.05,
                        lanes=None) -> GridFlowResult:
    """Batched solve with early-exit compaction (public (B, ...) layout).

    ``run_compacted`` drives the host loop: still-live instances are
    gathered into dense pow2-sized sub-batches between jitted cycle
    segments, so converged instances stop consuming FLOPs instead of being
    select-masked until the whole batch drains. Results bit-match the
    masked path (tests/test_compact.py).
    """
    state = _grid_init_jit(jnp.moveaxis(jnp.asarray(cap0), 1, 0),
                           jnp.asarray(cs0), jnp.asarray(ct0),
                           bfs_max_iters=bfs_max_iters)
    spec = _grid_spec(rounds_per_heuristic, max_rounds, bfs_max_iters,
                      backend, stall_threshold)
    state, rounds = run_compacted(spec, state, cs0.shape[0], lanes=lanes)
    res = _grid_finalize_jit(state, rounds, bfs_max_iters=bfs_max_iters)
    # public layout: batch axis leads everywhere, including state.cap
    return res._replace(
        state=res.state._replace(cap=jnp.moveaxis(res.state.cap, 0, 1)))


def _grid_batch_stepped(cap0, cs0, ct0, *, rounds_per_heuristic, max_rounds,
                        bfs_max_iters, backend,
                        stall_threshold=0.05) -> GridFlowResult:
    """Eager masked solve for cycle telemetry (public (B, ...) layout).

    Same init/finalize jits as the compacted path around an eager
    ``run_masked`` call, which — under the active
    ``cycle_events(masked=True)`` hook that routed here — host-steps the
    jitted cycle and emits per-cycle events.  Bit-matches
    ``_grid_batch_impl`` (the per-cycle jit granularity is what the
    compacted driver already bit-matches at; tests/test_obs.py).
    """
    state = _grid_init_jit(jnp.moveaxis(jnp.asarray(cap0), 1, 0),
                           jnp.asarray(cs0), jnp.asarray(ct0),
                           bfs_max_iters=bfs_max_iters)
    spec = _grid_spec(rounds_per_heuristic, max_rounds, bfs_max_iters,
                      backend, stall_threshold)
    state, rounds = run_masked(spec, state, cs0.shape[:1])
    res = _grid_finalize_jit(state, rounds, bfs_max_iters=bfs_max_iters)
    return res._replace(
        state=res.state._replace(cap=jnp.moveaxis(res.state.cap, 0, 1)))


@functools.partial(
    jax.jit,
    static_argnames=("rounds_per_heuristic", "max_rounds", "bfs_max_iters",
                     "backend", "stall_threshold"),
)
def maxflow_grid(
    problem: GridProblem,
    *,
    rounds_per_heuristic: int = 32,
    max_rounds: int = 100_000,
    bfs_max_iters: int = 0,
    backend: str = "xla",
    stall_threshold: float = 0.05,
) -> GridFlowResult:
    """Max-flow / min-cut of ONE grid-cut instance (paper §4 on TPU).

    Args:
      problem: ``GridProblem`` with ``cap_nbr (4, H, W)``,
        ``cap_src``/``cap_sink`` ``(H, W)``. Integer-valued capacities are
        recommended (float32 sums over them stay exact, making results
        reproducible bit-for-bit across batching/sharding layouts).
      rounds_per_heuristic: Jacobi rounds between global-relabel BFS passes —
        the paper's CYCLE constant (§4.6, CYCLE=7000 on a GTX 560 Ti; far
        smaller here because our heuristic costs one on-device fixpoint, not
        a host round-trip).
      max_rounds: hard round cap; if hit, ``converged`` is False and
        ``flow``/``cut`` describe the partial state.
      bfs_max_iters: BFS wavefront cap (0 = the H*W+2 upper bound).
      backend: ``"xla"`` (paper-faithful Jacobi round), ``"multipush"``
        (beyond-paper: saturate every lower neighbour per round),
        ``"pallas"`` (the round's decision stage as a TPU kernel), or
        ``"balanced"`` (workload-balanced: active-tile-scheduled kernel
        dispatch, bidirectional BFS relabel kernel, stall-driven heuristic
        cadence — see docs/kernels.md). Unknown strings raise ValueError.
      stall_threshold: ``"balanced"`` only — the relabel pass runs when the
        EWMA of terminal-retired flow per unit remaining excess drops below
        this (0 = never relabel after init; the solver still terminates via
        +1 relabels).

    Returns:
      ``GridFlowResult``: scalar ``flow`` (== min-cut value when
      ``converged``), ``cut (H, W)`` bool (True = sink side of a minimum
      cut), the final ``GridFlowState``, scalar ``rounds`` and
      ``converged``, plus ``heuristics`` (global-relabel invocations).

    Convergence contract: ``converged`` is True iff no node holds positive
    excess, at which point ``flow`` is the exact max-flow value (the solver
    is exact, not approximate — termination follows the paper's §4
    potential argument).
    """
    cap0, cs0, ct0 = problem
    if cs0.ndim != 2 or cap0.ndim != 3:
        # A (B, 4, H, W) stack with B == 4 would silently alias the batch
        # axis onto the direction axis — reject batches loudly instead.
        raise ValueError(
            f"maxflow_grid solves ONE instance (cap_nbr (4, H, W), got "
            f"{cap0.shape}); use maxflow_grid_batch for stacked problems")
    return _solve_grid(cap0, cs0, ct0,
                       rounds_per_heuristic=rounds_per_heuristic,
                       max_rounds=max_rounds, bfs_max_iters=bfs_max_iters,
                       backend=backend, stall_threshold=stall_threshold)


@functools.partial(
    jax.jit,
    static_argnames=("rounds_per_heuristic", "max_rounds", "bfs_max_iters",
                     "backend", "stall_threshold"),
)
def _grid_batch_impl(cap0, cs0, ct0, *, rounds_per_heuristic, max_rounds,
                     bfs_max_iters, backend,
                     stall_threshold=0.05) -> GridFlowResult:
    """Batched solve in the public (B, ...) layout (shard_map-able body)."""
    res = _solve_grid(jnp.moveaxis(cap0, 1, 0), cs0, ct0,
                      rounds_per_heuristic=rounds_per_heuristic,
                      max_rounds=max_rounds, bfs_max_iters=bfs_max_iters,
                      backend=backend, stall_threshold=stall_threshold)
    # public layout: batch axis leads everywhere, including state.cap
    return res._replace(
        state=res.state._replace(cap=jnp.moveaxis(res.state.cap, 0, 1)))


def maxflow_grid_batch(
    problem: GridProblem,
    *,
    rounds_per_heuristic: int = 32,
    max_rounds: int = 100_000,
    bfs_max_iters: int = 0,
    backend: str = "xla",
    stall_threshold: float = 0.05,
    compact: bool = False,
    mesh=None,
    mesh_axis: str | None = None,
) -> GridFlowResult:
    """Max-flow on a BATCH of same-shape grid instances in one dispatch.

    Args:
      problem: ``GridProblem`` with a leading batch axis — ``cap_nbr``
        ``(B, 4, H, W)`` (a plain stack of single-instance problems),
        ``cap_src``/``cap_sink`` ``(B, H, W)``.
      rounds_per_heuristic / max_rounds / bfs_max_iters / backend /
        stall_threshold: as in ``maxflow_grid`` (applied per instance).
      compact: early-exit compaction (``repro.core.solver_loop``). Instead
        of one jitted dispatch whose converged instances are select-masked
        until the whole batch drains, a host-driven loop gathers still-live
        instances into dense pow2-sized sub-batches between jitted cycle
        segments, so a converged instance stops consuming FLOPs. Worth it
        when convergence is ragged (stragglers dominate); the masked
        single-dispatch path wins when all instances finish together. With
        ``mesh=``, compaction stays WITHIN each shard (one host lane per
        device, no collectives — ``repro.launch.mesh.compact_lanes``).
      mesh: optional ``jax.sharding.Mesh`` (see
        ``repro.launch.mesh.make_solver_mesh``). When given, the batch axis
        is partitioned across the mesh under ``shard_map``: each device
        solves ``B // shard_count`` instances with NO cross-device
        communication (per-instance liveness masks make shards independent;
        a shard whose instances all converge finishes its dispatch early).
        ``B`` must be divisible by the shard count — the pad-and-bucket
        front end (``repro.core.batch``) pads ragged queues with inert
        instances instead of raising.
      mesh_axis: which mesh axis to shard over (default: the mesh's first
        axis, ``"batch"`` for solver meshes).

    Returns:
      ``GridFlowResult`` whose leaves lead with the batch axis:
      ``flow``/``rounds``/``converged`` are ``(B,)``, ``cut`` is
      ``(B, H, W)``, and ``state.cap`` is returned as ``(B, 4, H, W)``.

    Bit-match contract: runs the SAME shared cycle as ``maxflow_grid`` with
    batch shape ``(B,)`` — per-instance liveness masks (masked mode) or
    live-set gathers (compacted mode) advance exactly the instances still
    running, so results bit-match a loop of solo ``maxflow_grid`` runs,
    the sharded path bit-matches the unsharded one, and ``compact=True``
    bit-matches ``compact=False`` (an instance's trajectory never depends
    on its batch-mates; tests/test_batch.py, tests/test_shard.py,
    tests/test_compact.py).
    """
    cap0, cs0, ct0 = problem
    if cap0.ndim != 4 or cap0.shape[1] != 4 or cs0.ndim != 3:
        raise ValueError(
            f"maxflow_grid_batch expects cap_nbr (B, 4, H, W), got "
            f"{cap0.shape}; use maxflow_grid for a single instance")
    kw = dict(rounds_per_heuristic=rounds_per_heuristic,
              max_rounds=max_rounds, bfs_max_iters=bfs_max_iters,
              backend=backend, stall_threshold=stall_threshold)
    if compact:
        lanes = None
        if mesh is not None:
            from repro.launch.mesh import compact_lanes
            lanes = compact_lanes(mesh, mesh_axis, cs0.shape[0])
        return _grid_batch_compact(cap0, cs0, ct0, lanes=lanes, **kw)
    if mesh is None:
        if masked_events_active():
            return _grid_batch_stepped(cap0, cs0, ct0, **kw)
        return _grid_batch_impl(cap0, cs0, ct0, **kw)
    from repro.launch.mesh import dispatch_sharded
    return dispatch_sharded(_grid_batch_impl, (cap0, cs0, ct0),
                            cs0.shape[0], mesh, mesh_axis, **kw)
