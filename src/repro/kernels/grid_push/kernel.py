"""Pallas TPU kernel: the per-node decision of one push-relabel Jacobi round.

The paper's push kernel (§4.6) is the hot spot of the max-flow computation:
each node scans its residual edges, finds the lowest neighbour, and either
pushes or relabels. The CUDA version keeps heights in shared memory
(Vineet & Narayanan) — the TPU analogue is VMEM tiles chosen by BlockSpec.

The kernel computes, per grid tile: the chosen target (sink / source / one of
four neighbours), the pushed amount per target plane, and the new height. The
cross-tile flow deposition (shift-adds) is pure elementwise data movement and
stays in XLA (ops.py) where it fuses with the surrounding ops; the VMEM-
resident argmin/push math — the part the paper hand-optimizes — lives here.

VMEM per step: 12 input planes + 7 output planes of BH·BW·4B, double-
buffered. The dense kernel's 256×256 blocks ⇒ 2·19·256 KiB ≈ 9.5 MiB, and
the scheduled kernel's 64×128 blocks ⇒ 2·19·32 KiB ≈ 1.2 MiB, both under
Mosaic's 16 MiB default scoped limit (compiled for v5e in
tests/test_tpu_compile.py at 8×512²). Block widths are multiples of 128
lanes or the full width (``tile_dims``).
The halo exchange (neighbour heights) is precomputed by ops.py as 4 shifted
height planes, which on real hardware XLA lays out as cheap HBM slices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF_H = 2 ** 30  # python int: jnp scalars would be captured consts in pallas


def _decide(e, h, cap, nbr_h, cap_src, cap_sink, n_nodes):
    """The per-node decision math shared by both kernels (concrete values).

    ``cap`` / ``nbr_h`` index by direction (refs or arrays, 4 planes).
    Candidate order matches grid.jacobi_round:
    [sink, source, UP, DOWN, LEFT, RIGHT]; the push goes to the FIRST
    candidate attaining the minimum height (``jnp.argmin``'s tie rule),
    written as a min and a select chain over the six planes because
    Mosaic lowers neither an int32 argmin nor ``take_along_axis``.
    Returns ``(h_new, deltas)`` with ``deltas`` a list of six planes.
    """
    active = e > 0
    caps = [cap_sink, cap_src] + [cap[d] for d in range(4)]
    cand = [jnp.where(cap_sink > 0, 0, INF_H),
            jnp.where(cap_src > 0, n_nodes, INF_H)]
    cand += [jnp.where(caps[2 + d] > 0, nbr_h[d], INF_H) for d in range(4)]
    h_min = functools.reduce(jnp.minimum, cand)

    do_push = active & (h > h_min)
    do_relabel = active & (h <= h_min) & (h_min < INF_H)
    h_new = jnp.where(do_relabel, h_min + 1, h)

    deltas, done = [], ~do_push
    for c, k in zip(cand, caps):
        pick = ~done & (c == h_min)
        deltas.append(jnp.where(pick, jnp.minimum(e, k), 0.0))
        done = done | pick
    return h_new, deltas


def _grid_push_kernel(nn_ref, e_ref, h_ref, cap_ref, nbrh_ref, csrc_ref,
                      csink_ref, hnew_ref, delta_ref):
    # Blocks are (BH, BW) planes; in batched mode the leading batch axis is
    # squeezed out by the BlockSpecs (one grid step per instance and tile).
    cap = [cap_ref[d] for d in range(4)]        # f32 residual neighbour caps
    nbr_h = [nbrh_ref[d] for d in range(4)]     # i32 neighbour heights (halo)
    h_new, deltas = _decide(e_ref[...], h_ref[...], cap, nbr_h,
                            csrc_ref[...], csink_ref[...], nn_ref[0])
    hnew_ref[...] = h_new
    for p, d in enumerate(deltas):
        delta_ref[p] = d


def _grid_push_sched_kernel(sched_ref, nact_ref, nnodes_ref, e_ref, h_ref,
                            cap_ref, nbrh_ref, csrc_ref, csink_ref,
                            hnew_ref, delta_ref):
    """Active-tile-scheduled decision step (workload-balanced backend).

    Grid is ``(B, T)`` over SCHEDULE POSITIONS, not tile coordinates: the
    scalar-prefetched ``sched[b]`` is a permutation of instance ``b``'s
    tile ids with the active tiles compacted to the front, and this
    program's blocks are tile ``sched[b, i]`` (index maps below). Schedule
    positions past ``nact[b]`` carry tiles with NO active vertex — for
    them one Jacobi round is the identity (no node pushes or relabels), so
    the kernel skips the whole candidate/min/push stage and writes the
    identity outputs directly. The permutation covers every tile exactly
    once, so every output block is written exactly once.
    """
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i < nact_ref[b])
    def _active_tile():
        _grid_push_kernel(nnodes_ref, e_ref, h_ref, cap_ref, nbrh_ref,
                          csrc_ref, csink_ref, hnew_ref, delta_ref)

    @pl.when(i >= nact_ref[b])
    def _inactive_tile():  # identity: no active node -> no push, no relabel
        hnew_ref[...] = h_ref[...]
        delta_ref[...] = jnp.zeros_like(delta_ref)


def tile_dims(H: int, W: int, block_h: int, block_w: int):
    """The ``(bh, bw)`` block both kernels use for an ``(H, W)`` plane.

    Each requested block dim is kept when it divides the plane and meets
    the TPU's (8 sublane, 128 lane) tiling; otherwise the block spans the
    full dim, which Mosaic always accepts. Results never depend on the
    block shape, only which grid steps do the work.
    """
    def fit(block, dim, align):
        b = min(block, dim)
        return b if dim % b == 0 and (b == dim or b % align == 0) else dim
    return fit(block_h, H, 8), fit(block_w, W, 128)


@functools.partial(jax.jit, static_argnames=("block_h", "block_w",
                                             "interpret"))
def grid_push_decide(e, h, cap, nbr_h, cap_src, cap_sink, n_nodes,
                     *, block_h: int = 256, block_w: int = 256,
                     interpret: bool = False):
    """Per-node push/relabel decision for one Jacobi round.

    Returns (h_new, delta) where delta[p] is the flow pushed toward plane
    p ∈ [sink, source, UP, DOWN, LEFT, RIGHT].

    Accepts a leading batch axis: ``e`` may be ``(H, W)`` or ``(B, H, W)``
    (with ``cap``/``nbr_h`` ``(4, B, H, W)``). In batched mode the pallas
    grid gains a leading batch dimension — grid ``(B, H//bh, W//bw)`` — so
    every instance's tiles are independent kernel steps of ONE launch,
    amortizing the dispatch over the whole batch. ``n_nodes`` rides scalar
    prefetch (SMEM). ``interpret=True`` runs the kernel body on the host
    (tests off-TPU).
    """
    *batch, H, W = e.shape
    bh, bw = tile_dims(H, W, block_h, block_w)

    if not batch:
        grid = (H // bh, W // bw)
        spec2d = pl.BlockSpec((bh, bw), lambda i, j, nn: (i, j))
        spec4 = pl.BlockSpec((4, bh, bw), lambda i, j, nn: (0, i, j))
        spec6 = pl.BlockSpec((6, bh, bw), lambda i, j, nn: (0, i, j))
    else:
        (B,) = batch
        grid = (B, H // bh, W // bw)
        spec2d = pl.BlockSpec((None, bh, bw), lambda b, i, j, nn: (b, i, j))
        spec4 = pl.BlockSpec((4, None, bh, bw),
                             lambda b, i, j, nn: (0, b, i, j))
        spec6 = pl.BlockSpec((6, None, bh, bw),
                             lambda b, i, j, nn: (0, b, i, j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,   # n_nodes
        grid=grid,
        in_specs=[spec2d, spec2d, spec4, spec4, spec2d, spec2d],
        out_specs=[spec2d, spec6],
    )
    return pl.pallas_call(
        _grid_push_kernel,
        grid_spec=grid_spec,
        name="grid_push_decide",
        out_shape=[jax.ShapeDtypeStruct(e.shape, jnp.int32),
                   jax.ShapeDtypeStruct((6,) + e.shape, jnp.float32)],
        interpret=interpret,
    )(jnp.asarray([n_nodes], jnp.int32), e, h, cap, nbr_h, cap_src, cap_sink)


@functools.partial(jax.jit, static_argnames=("block_h", "block_w",
                                             "interpret"))
def grid_push_decide_sched(e, h, cap, nbr_h, cap_src, cap_sink, sched,
                           n_active, n_nodes, *, block_h: int = 64,
                           block_w: int = 128, interpret: bool = False):
    """Active-tile-scheduled push/relabel decision (balanced backend).

    Same outputs as ``grid_push_decide`` — ``(h_new, delta)`` with
    ``delta[p]`` the flow pushed toward plane p ∈ [sink, source, UP, DOWN,
    LEFT, RIGHT] — but the pallas grid runs over a COMPACTED TILE SCHEDULE
    instead of fixed (i, j) tiling:

    Args:
      e / h / cap_src / cap_sink: ``(B, H, W)`` state planes.
      cap / nbr_h: ``(4, B, H, W)``.
      sched: ``(B, T)`` int32 — per instance, a PERMUTATION of the tile
        ids ``0..T-1`` (``T = (H//bh) * (W//bw)`` for the ``tile_dims``
        block, row-major) with every tile containing an active vertex
        compacted to the front (``repro.kernels.grid_push.ops.
        tile_schedule``).
      n_active: ``(B,)`` int32 — how many leading schedule entries are
        active; programs past it take the identity fast path.
      n_nodes: scalar int32 (the paper's N).

    ``sched`` and ``n_active`` ride scalar prefetch
    (``pltpu.PrefetchScalarGridSpec``) so the BLOCK INDEX MAPS themselves
    gather the scheduled tile — the kernel's memory traffic follows the
    schedule, which is what makes the dispatch workload-balanced rather
    than grid-shaped. Inactive tiles are provably identity under one
    Jacobi round, so the result is bit-identical to ``grid_push_decide``
    on the full grid (asserted in tests/test_balanced.py).
    """
    B, H, W = e.shape
    bh, bw = tile_dims(H, W, block_h, block_w)
    ntw = W // bw
    T = (H // bh) * ntw
    assert sched.shape == (B, T), (sched.shape, B, T)

    def tile2d(b, i, sched, nact, nn):
        t = sched[b, i]
        return (b, t // ntw, t % ntw)

    def tile_planes(b, i, sched, nact, nn):
        t = sched[b, i]
        return (0, b, t // ntw, t % ntw)

    spec2d = pl.BlockSpec((None, bh, bw), tile2d)
    spec4 = pl.BlockSpec((4, None, bh, bw), tile_planes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # sched, n_active, n_nodes
        grid=(B, T),
        in_specs=[spec2d, spec2d, spec4, spec4, spec2d, spec2d],
        out_specs=[spec2d, pl.BlockSpec((6, None, bh, bw), tile_planes)],
    )
    return pl.pallas_call(
        _grid_push_sched_kernel,
        grid_spec=grid_spec,
        name="grid_push_decide_sched",
        out_shape=[jax.ShapeDtypeStruct((B, H, W), jnp.int32),
                   jax.ShapeDtypeStruct((6, B, H, W), jnp.float32)],
        interpret=interpret,
    )(sched.astype(jnp.int32), n_active.astype(jnp.int32),
      jnp.asarray([n_nodes], jnp.int32), e, h, cap, nbr_h, cap_src,
      cap_sink)
