"""Jit'd wrappers: full Jacobi rounds with the Pallas grid_push kernels.

``jacobi_round_pallas`` produces bit-identical state transitions to
``repro.core.maxflow.grid.jacobi_round`` (asserted in tests): one fused
``grid_push_round`` call over row strips where the grid's height allows
(``kernel.strip_rows``), else the decision kernel with the halo gather
before it and the shift-add flow deposition after it. Like the XLA round
it is shape-polymorphic over leading batch axes (``e``: ``(..., H, W)``,
``cap``: ``(4, ..., H, W)``) — the kernel grid then gains a batch
dimension.

``jacobi_round_scheduled`` is the workload-balanced variant: it builds a
per-instance ACTIVE-TILE SCHEDULE (tiles holding at least one node with
excess, compacted to the front of a tile-id permutation) and dispatches
the decision kernel over schedule positions instead of the fixed grid.
A tile with no active node is an exact no-op under one Jacobi round, so
the transition is still bit-identical to ``jacobi_round`` — the schedule
only changes which blocks do real work. It additionally returns the
per-instance RETIRED flow (excess delivered to a terminal this round),
which the balanced backend's stall detector (``repro.core.maxflow.grid``)
feeds into its relabel-trigger EWMA — neighbour-to-neighbour moves are
excluded because height-plateau ping-pong would otherwise read as
progress.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.core.maxflow.grid import (GridFlowState, _OPP, _gsum, _move,
                                     _nbr_h)
from repro.kernels import interpret_mode
from repro.kernels.grid_push.kernel import (grid_push_decide,
                                            grid_push_decide_sched,
                                            grid_push_round, STRIP_ROWS,
                                            strip_rows, tile_dims)
from repro.kernels.grid_push.ref import grid_push_decide_ref


def _deposit(state: GridFlowState, h_new, delta) -> GridFlowState:
    """Shift-add flow deposition after a decision kernel."""
    d_sink, d_src = delta[0], delta[1]
    d_nbr = [delta[2 + d] for d in range(4)]
    out = d_sink + d_src + sum(d_nbr)
    inflow = sum(_move(d_nbr[d], d) for d in range(4))
    cap_new = jnp.stack(
        [state.cap[d] - d_nbr[d] + _move(d_nbr[_OPP[d]], _OPP[d])
         for d in range(4)], 0)
    return state._replace(
        e=state.e - out + inflow, h=h_new, cap=cap_new,
        cap_src=state.cap_src - d_src, cap_sink=state.cap_sink - d_sink,
        sink_flow=state.sink_flow + _gsum(d_sink),
        src_flow=state.src_flow + _gsum(d_src),
    )


def jacobi_round_pallas(state: GridFlowState, n_nodes,
                        *, block_h: int = STRIP_ROWS, block_w: int = 256,
                        interpret: bool | None = None) -> GridFlowState:
    """One Jacobi round on the Pallas kernels, bit-identical to
    ``jacobi_round``.

    Where the grid can be cut into row strips (``kernel.strip_rows``:
    ``H`` a multiple of 8 rows), the whole round is one ``grid_push_round``
    call over strips of at most ``block_h`` rows. Otherwise the decision
    kernel runs over ``(block_h, block_w)`` tiles and XLA gathers the
    neighbour heights and deposits the flow. The fused round adds the
    terminal flows up strip by strip, in another order than
    ``jacobi_round``'s sum: the same bits wherever the sums are exact, as
    with integer capacities.
    """
    if interpret is None:
        interpret = interpret_mode()
    *batch, H, W = state.e.shape
    bh = strip_rows(H, W, block_h)
    if bh is not None:
        B = math.prod(batch)
        e, h, cap, cap_src, cap_sink, flows = grid_push_round(
            state.e.reshape(B, H, W), state.h.reshape(B, H, W),
            state.cap.reshape(4, B, H, W), state.cap_src.reshape(B, H, W),
            state.cap_sink.reshape(B, H, W), n_nodes, bh=bh,
            interpret=interpret)
        flows = jnp.sum(flows, axis=(1, 3)).reshape(tuple(batch) + (2,))
        return state._replace(
            e=e.reshape(state.e.shape), h=h.reshape(state.h.shape),
            cap=cap.reshape(state.cap.shape),
            cap_src=cap_src.reshape(state.cap_src.shape),
            cap_sink=cap_sink.reshape(state.cap_sink.shape),
            sink_flow=state.sink_flow + flows[..., 0],
            src_flow=state.src_flow + flows[..., 1])
    nbr_h = jnp.stack([_nbr_h(state.h, d) for d in range(4)], axis=0)
    h_new, delta = grid_push_decide(
        state.e, state.h, state.cap, nbr_h, state.cap_src, state.cap_sink,
        n_nodes, block_h=block_h, block_w=block_w, interpret=interpret)
    return _deposit(state, h_new, delta)


def tile_schedule(active, block_h: int, block_w: int):
    """Compacted tile schedule from a per-node activity mask.

    Args:
      active: ``(B, H, W)`` bool — which nodes hold excess this round.
      block_h / block_w: the kernel tile shape (``kernel.tile_dims``).

    Returns ``(sched, n_active)``: ``sched`` is ``(B, T)`` int32, per
    instance a permutation of the row-major tile ids with every tile
    containing an active node moved to the front (stable, so active tiles
    keep tile-id order — the schedule is a pure function of the mask,
    which preserves the per-instance determinism contract); ``n_active``
    is ``(B,)`` int32.
    """
    B, H, W = active.shape
    nth, ntw = H // block_h, W // block_w
    tile_act = active.reshape(B, nth, block_h, ntw, block_w).any(axis=(2, 4))
    tile_act = tile_act.reshape(B, nth * ntw)
    sched = jnp.argsort(~tile_act, axis=1, stable=True).astype(jnp.int32)
    return sched, jnp.sum(tile_act, axis=1).astype(jnp.int32)


def jacobi_round_scheduled(state: GridFlowState, n_nodes,
                           *, block_h: int = 64, block_w: int = 128,
                           interpret: bool | None = None):
    """One Jacobi round dispatched over active tiles only.

    Bit-identical state transition to ``jacobi_round`` /
    ``jacobi_round_pallas`` (inactive tiles are no-ops either way); the
    pallas grid just stops visiting them first. Returns
    ``(new_state, retired)`` where ``retired`` is the per-instance flow
    delivered to the sink or returned to the source this round — the
    balanced backend's stall signal (see module docstring).
    Shape-polymorphic over leading batch axes.
    """
    if interpret is None:
        interpret = interpret_mode()
    *batch, H, W = state.e.shape
    bh, bw = tile_dims(H, W, block_h, block_w)
    B = 1
    for s in batch:
        B *= s

    e = state.e.reshape(B, H, W)
    h = state.h.reshape(B, H, W)
    cap = state.cap.reshape(4, B, H, W)
    cap_src = state.cap_src.reshape(B, H, W)
    cap_sink = state.cap_sink.reshape(B, H, W)
    nbr_h = jnp.stack([_nbr_h(h, d) for d in range(4)], axis=0)
    sched, n_active = tile_schedule(e > 0, bh, bw)

    h_new, delta = grid_push_decide_sched(
        e, h, cap, nbr_h, cap_src, cap_sink, sched, n_active, n_nodes,
        block_h=bh, block_w=bw, interpret=interpret)

    h_new = h_new.reshape(state.h.shape)
    delta = delta.reshape((6,) + state.e.shape)
    retired = _gsum(delta[0] + delta[1])
    return _deposit(state, h_new, delta), retired
