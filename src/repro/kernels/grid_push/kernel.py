"""Pallas TPU kernels: push-relabel Jacobi rounds on a grid.

The paper's push kernel (§4.6) is the hot spot of the max-flow computation:
each node scans its residual edges, finds the lowest neighbour, and either
pushes or relabels. The CUDA version keeps heights in shared memory
(Vineet & Narayanan) — the TPU analogue is VMEM blocks chosen by BlockSpec.

``grid_push_round`` is one whole Jacobi round in one call, over row strips
of full width: each grid step reads its strip of the eight state planes
(``e``, ``h``, four ``cap``, ``cap_src``, ``cap_sink``) and the ``HALO``
rows above and below it, gathers the neighbour heights, decides, deposits
the pushed flow and writes its strip of the next state. A round reads and
writes each state plane once (8 + 8 plane passes, plus 2·``HALO``/bh of a
plane of halo rows); no neighbour-height or per-target delta plane reaches
HBM. VMEM per step: the 16 block planes and the halos double-buffered, plus
the body's temporaries over ``bh + 2·HALO`` rows: the compiler asked for
~37–42 such planes at 8×512² (6.0 MiB at bh 64, 10.4 MiB at 128, 21.5 MiB
at 256, 43 MiB at 512), so ``round_vmem_limit_bytes`` sets the limit from the shape
(``ROUND_VMEM_PLANES``), over Mosaic's 16 MiB default.

``grid_push_decide`` (the decision alone over ``(bh, bw)`` tiles, with the
four neighbour-height planes built by XLA and the deposit left to XLA) is
the round for grids whose height is not a multiple of ``HALO``, and
``grid_push_decide_sched`` is the balanced backend's tile-scheduled
decision. Their VMEM per step: 12 input planes + 7 output planes of
BH·BW·4B, double-buffered: the 256×256 blocks ⇒ 2·19·256 KiB ≈ 9.5 MiB,
the scheduled kernel's 64×128 blocks ⇒ 2·19·32 KiB ≈ 1.2 MiB, both under
the default limit. Block widths are multiples of 128 lanes or the full
width (``tile_dims``). All three compile for v5e in
tests/test_tpu_compile.py at 8×512².
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.maxflow.grid import _OPP
from repro.kernels import VMEM_CAP, VMEM_DEFAULT

INF_H = 2 ** 30  # python int: jnp scalars would be captured consts in pallas


def _decide(e, h, cap, nbr_h, cap_src, cap_sink, n_nodes):
    """The per-node decision math shared by the kernels (concrete values).

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


# Strip height the solver's round asks for (tuned on a v5e at 1–8 × 512²:
# within 5% of the fastest of 64–512 rows at each batch, and the quickest
# to compile, since Mosaic unrolls the body over the strip's vregs).
STRIP_ROWS = 64
# Rows of the halo blocks above and below a strip: one (8, 128) tile high.
# A strip's boundary rows decide with their neighbours' decisions, which
# read heights two rows out, so 8 rows hold more than the round needs.
HALO = 8
# VMEM the fused round needs, in 4-byte planes of a strip and its halos
# ((bh + 2·HALO) × W): 16 block planes (8 in, 8 out) double-buffered, plus
# the body's temporaries. The compiler asked for 37–42 at 8×512².
ROUND_VMEM_PLANES = 48
# Elementwise operations the round's body spends on one node (the
# decision ~56, neighbour heights ~6, the deposit ~20): the cost estimate's
# flops, for XLA's scheduler.
ROUND_OPS_PER_NODE = 82


def round_vmem_limit_bytes(bh: int, W: int) -> int:
    """Scoped-VMEM limit for one ``(bh, W)`` strip per grid step."""
    plane = (bh + 2 * HALO) * (-(-W // 128) * 128) * 4
    return max(VMEM_DEFAULT, ROUND_VMEM_PLANES * plane)


def strip_rows(H: int, W: int, block_h: int) -> int | None:
    """The strip height the fused round takes for an ``(H, W)`` grid.

    The largest multiple of ``HALO`` rows that divides ``H``, is at most
    ``block_h`` (or ``HALO``) and fits ``VMEM_CAP``; None when there is
    none (``H`` not a multiple of ``HALO``, or a row too wide), and the
    round then takes the tiled decide-then-deposit path.
    """
    if H % HALO:
        return None
    for bh in range(max(block_h, HALO) // HALO * HALO, 0, -HALO):
        if H % bh == 0 and round_vmem_limit_bytes(bh, W) <= VMEM_CAP:
            return bh
    return None


def _grid_push_round_kernel(nn_ref, e_ref, h_ref, cap_ref, csrc_ref,
                            csink_ref, *refs):
    """One whole Jacobi round of one row strip, halo rows included.

    The strip's planes are stacked between the ``HALO`` rows above and
    below it into ``R = bh + 2·HALO`` rows; halo rows outside the grid
    become "no node" (height ``INF_H``, no capacity, no excess), which
    reproduces ``_nbr_h``'s INF border and ``_move``'s zero fill. Rows
    ``[HALO-1, HALO+bh+1)`` decide exactly (their neighbours' heights lie
    inside the ``R`` rows), the deposit into the strip's rows reads only
    those, and the rolls' wrap-around touches only the outermost rows,
    which are never written out. The column wrap-around is masked for
    the neighbour heights; a deposit's wrapped column is always zero,
    since nothing pushes off the grid.
    """
    top, bot = refs[0:5], refs[5:10]
    e_out, h_out, cap_out, csrc_out, csink_out, flow_out = refs[10:]
    i = pl.program_id(1)
    has_top, has_bot = i > 0, i < pl.num_programs(1) - 1

    def rows(strip, above, below, fill):
        return jnp.concatenate([jnp.where(has_top, above, fill), strip,
                                jnp.where(has_bot, below, fill)], axis=0)

    e = rows(e_ref[...], top[0][...], bot[0][...], 0.0)
    h = rows(h_ref[...], top[1][...], bot[1][...], INF_H)
    cap = [rows(cap_ref[d], top[2][d], bot[2][d], 0.0) for d in range(4)]
    cap_src = rows(csrc_ref[...], top[3][...], bot[3][...], 0.0)
    cap_sink = rows(csink_ref[...], top[4][...], bot[4][...], 0.0)
    R, W = h.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
    # neighbour heights (grid._nbr_h): UP = h[r-1], DOWN = h[r+1], ...
    nbr_h = [pltpu.roll(h, 1, 0), pltpu.roll(h, R - 1, 0),
             jnp.where(col == 0, INF_H, pltpu.roll(h, 1, 1)),
             jnp.where(col == W - 1, INF_H, pltpu.roll(h, W - 1, 1))]
    h_new, deltas = _decide(e, h, cap, nbr_h, cap_src, cap_sink, nn_ref[0])
    d_sink, d_src, d_nbr = deltas[0], deltas[1], deltas[2:]

    def move(a, d):  # grid._move: deposit a[x] at x's neighbour in dir d
        return pltpu.roll(a, (R - 1, 1, W - 1, 1)[d], 0 if d < 2 else 1)

    # the deposit, in grid.jacobi_round's order of operations
    out = d_sink + d_src + sum(d_nbr)
    inflow = sum(move(d_nbr[d], d) for d in range(4))
    lo, hi = HALO, R - HALO
    e_out[...] = (e - out + inflow)[lo:hi]
    h_out[...] = h_new[lo:hi]
    for d in range(4):
        cap_out[d] = (cap[d] - d_nbr[d]
                      + move(d_nbr[_OPP[d]], _OPP[d]))[lo:hi]
    csrc_out[...] = (cap_src - d_src)[lo:hi]
    csink_out[...] = (cap_sink - d_sink)[lo:hi]
    flow_out[0:1, :] = jnp.sum(d_sink[lo:hi], axis=0, keepdims=True)
    flow_out[1:2, :] = jnp.sum(d_src[lo:hi], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def grid_push_round(e, h, cap, cap_src, cap_sink, n_nodes, *, bh: int,
                    interpret: bool = False):
    """One whole push-relabel Jacobi round over row strips (Pallas).

    Args:
      e / h / cap_src / cap_sink: ``(B, H, W)`` state planes (``h`` int32).
      cap: ``(4, B, H, W)`` residual neighbour capacities.
      n_nodes: scalar int32 (the paper's N), on scalar prefetch.
      bh: strip height, a multiple of ``HALO`` that divides ``H``
        (``strip_rows``).

    Returns ``(e, h, cap, cap_src, cap_sink, flows)``: the next state,
    bit-identical to ``grid.jacobi_round``'s, and ``flows`` ``(B, H//bh,
    2, W)``, the column sums of each strip's flow into the sink (``[..., 0,
    :]``) and back to the source (``[..., 1, :]``). The grid is ``(B,
    H//bh)``; each step reads its strip and the ``HALO`` rows above and
    below it (extra BlockSpecs on the same arrays, clamped at the grid's
    edges) and writes its strip of the next state, so the round reads and
    writes each state plane once. No output aliases an input: a strip's
    halo rows are its neighbour's interior.
    """
    B, H, W = e.shape
    if H % bh or bh % HALO:
        raise ValueError(f"grid_push_round: strips of {bh} rows do not tile "
                         f"{H} rows in blocks of {HALO} (see strip_rows)")
    n_strips, hb = H // bh, bh // HALO
    last = H // HALO - 1

    def strip(b, i, nn):
        return (b, i, 0)

    def top(b, i, nn):
        return (b, jnp.maximum(i * hb - 1, 0), 0)

    def bot(b, i, nn):
        return (b, jnp.minimum((i + 1) * hb, last), 0)

    def spec(rows, index, planes=False):
        if planes:
            return pl.BlockSpec((4, None, rows, W),
                                lambda b, i, nn: (0,) + index(b, i, nn))
        return pl.BlockSpec((None, rows, W), index)

    state_specs = [spec(bh, strip), spec(bh, strip), spec(bh, strip, True),
                   spec(bh, strip), spec(bh, strip)]
    halo_specs = [spec(HALO, index, k == 2) for index in (top, bot)
                  for k in range(5)]
    planes = (e, h, cap, cap_src, cap_sink)
    steps, row = B * n_strips, W * 4
    cost = pl.CostEstimate(   # XLA sees the blocks' traffic, not 0 bytes
        flops=ROUND_OPS_PER_NODE * steps * (bh + 2 * HALO) * W,
        transcendentals=0,
        bytes_accessed=steps * row * (8 * (bh + 2 * HALO) + 8 * bh + 2))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,   # n_nodes
        grid=(B, n_strips),
        in_specs=state_specs + halo_specs,
        out_specs=state_specs + [
            pl.BlockSpec((None, None, 2, W), lambda b, i, nn: (b, i, 0, 0))],
    )
    return pl.pallas_call(
        _grid_push_round_kernel,
        grid_spec=grid_spec,
        name="grid_push_round",
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in planes]
        + [jax.ShapeDtypeStruct((B, n_strips, 2, W), jnp.float32)],
        interpret=interpret,
        cost_estimate=cost,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=round_vmem_limit_bytes(bh, W)),
    )(jnp.asarray([n_nodes], jnp.int32), *planes, *planes, *planes)


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
