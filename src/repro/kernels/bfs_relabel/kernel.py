"""Pallas TPU kernel: bidirectional BFS wavefront sweeps for global relabel.

The paper's global relabeling heuristic (Alg. 4.4) is a backward BFS from
the sink; its gap relabel (§4.6) lifts unreached nodes to N. The XLA
implementation (``repro.core.maxflow.grid.bfs_heights``) runs ONE min-plus
relaxation sweep per ``while_loop`` iteration — every sweep is a full HBM
round trip over all five planes. This kernel is the workload-balanced
backend's replacement (cf. arXiv 2404.00270's kernel-resident global
relabel): it keeps the wavefront planes VMEM-resident and runs ``SWEEPS``
relaxation sweeps per invocation, so the fixpoint driver (ops.py) touches
HBM once per ``SWEEPS`` sweeps instead of once per sweep.

Two wavefronts relax simultaneously (both follow residual OUT-edges, so
they share one sweep loop):

* ``dt`` — height-to-sink: seeded 1 where residual x→t exists; the paper's
  Alg. 4.4 labeling.
* ``ds`` — height-via-source: seeded N+1 where residual x→s exists (a node
  at N+1 pushes to the source, whose conceptual height is N); the RETURN
  path labeling the paper leaves to slow +1 relabels. Baumstark et al.
  (arXiv 1507.01926) relabel from both terminals for exactly this reason.

The combine (``dt`` if reached, else ``max(h_prev, ds)``, else
``max(h_prev, N)``) happens in ops.py AFTER the joint fixpoint — combining
early would leak not-yet-converged ``ds`` values into the sink labeling.

Blocks are whole (H, W) planes with a batch grid dimension — wavefronts
cross the entire grid, so tiling would reintroduce a halo fixpoint per
sweep. VMEM per step: 4 cap planes + 2 seed planes + 2 in + 2 out
wavefront planes = 10 planes of H·W·4B, double-buffered across the batch
grid, plus the sweep's temporaries: the compiler asks for ~33 planes in
all, 32.97 MiB at 512², over Mosaic's 16 MiB default. ``vmem_limit_bytes``
raises the kernel's limit from its shape, up to ``VMEM_CAP``; that admits
the paper's 512² grid and square grids up to ~800², and a larger grid
raises ``ValueError`` before dispatch (it needs a tiled variant).

``sink_bfs`` (the ``pallas`` backend's relabel) runs ``grid.bfs_heights``'
one-directional labeling in one call: each grid of the batch is a step
that builds its edge masks once, sweeps between two VMEM planes until its
own fixpoint (or the sweep cap), applies the gap rule and writes once.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_CAP, VMEM_DEFAULT

INF_H = 2 ** 30  # python int: jnp scalars would be captured consts in pallas

# Relaxation sweeps per kernel invocation. Each extra sweep is pure VMEM
# work; the fixpoint driver rounds its iteration budget up to a multiple
# of this. 8 amortizes the HBM round trip without inflating the tail
# (converged planes re-relax as no-ops).
SWEEPS = 8

# VMEM the compiled kernel needs, in (H, W) int32 planes: 10 block planes,
# double-buffered across the batch grid, plus ~13 planes of sweep
# temporaries (the compiler asked for 32.97 MiB at 512², i.e. 33 planes).
VMEM_PLANES = 36


def vmem_limit_bytes(H: int, W: int, planes: int = VMEM_PLANES) -> int:
    """Scoped-VMEM limit for one ``(H, W)`` instance per grid step, for a
    kernel that holds ``planes`` such planes.

    A plane occupies whole (8, 128) tiles of 4-byte words in VMEM.
    """
    plane = -(-H // 8) * 8 * (-(-W // 128) * 128) * 4
    return max(VMEM_DEFAULT, planes * plane)


def _shift_min(a, d):
    """min-plus neighbour gather: value of a at x's neighbour in dir d.

    Mirrors ``grid._nbr_h`` (UP, DOWN, LEFT, RIGHT = 0..3) with INF fill
    outside the grid, on concrete (H, W) values inside the kernel.
    """
    big = jnp.full_like(a[:1, :], INF_H)
    bigc = jnp.full_like(a[:, :1], INF_H)
    if d == 0:    # UP
        return jnp.concatenate([big, a[:-1, :]], axis=0)
    if d == 1:    # DOWN
        return jnp.concatenate([a[1:, :], big], axis=0)
    if d == 2:    # LEFT
        return jnp.concatenate([bigc, a[:, :-1]], axis=1)
    return jnp.concatenate([a[:, 1:], bigc], axis=1)


def _bfs_relabel_kernel(cap_ref, seed_t_ref, seed_s_ref, dt_ref, ds_ref,
                        dt_out_ref, ds_out_ref):
    bh, bw = dt_ref.shape[-2:]
    cap = cap_ref[...].reshape(4, bh, bw)      # f32 residual neighbour caps
    seed_t = seed_t_ref[...].reshape(bh, bw)   # i32: 1 | INF
    seed_s = seed_s_ref[...].reshape(bh, bw)   # i32: N+1 | INF
    dt = dt_ref[...].reshape(bh, bw)
    ds = ds_ref[...].reshape(bh, bw)

    def sweep(_, carry):
        dt, ds = carry
        rt, rs = dt, ds
        for d in range(4):
            open_edge = cap[d] > 0
            rt = jnp.minimum(rt, jnp.where(open_edge,
                                           _shift_min(dt, d) + 1, INF_H))
            rs = jnp.minimum(rs, jnp.where(open_edge,
                                           _shift_min(ds, d) + 1, INF_H))
        return jnp.minimum(rt, seed_t), jnp.minimum(rs, seed_s)

    dt, ds = jax.lax.fori_loop(0, SWEEPS, sweep, (dt, ds))
    dt_out_ref[...] = dt.reshape(dt_out_ref.shape)
    ds_out_ref[...] = ds.reshape(ds_out_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bfs_relabel_sweeps(cap, seed_t, seed_s, dt, ds, *,
                       interpret: bool = False):
    """``SWEEPS`` joint relaxation sweeps of both wavefront planes.

    Args:
      cap: ``(4, B, H, W)`` residual neighbour capacities.
      seed_t / seed_s: ``(B, H, W)`` int32 seed planes (1 where residual
        x→t resp. N+1 where residual x→s; INF elsewhere).
      dt / ds: ``(B, H, W)`` int32 current wavefront planes.

    Returns the relaxed ``(dt, ds)``. Each batch instance is one kernel
    step of a ``(B,)`` pallas grid, so the whole batch rides one launch —
    the batch dimension ``maxflow_grid_batch`` dispatches over.
    """
    B, H, W = dt.shape
    vmem = vmem_limit_bytes(H, W)
    if not interpret and vmem > VMEM_CAP:
        raise ValueError(
            f"bfs_relabel_sweeps: a {H}x{W} grid needs ~{vmem >> 20} MiB of "
            f"VMEM for whole-plane blocks, over the {VMEM_CAP >> 20} MiB "
            f"this kernel may take on a TPU; grids this large need a tiled "
            f"relabel (use backend='xla' or 'pallas')")
    spec2d = pl.BlockSpec((1, H, W), lambda b: (b, 0, 0))
    spec4 = pl.BlockSpec((4, 1, H, W), lambda b: (0, b, 0, 0))
    dt, ds = pl.pallas_call(
        _bfs_relabel_kernel,
        grid=(B,),
        name="bfs_relabel_sweeps",
        in_specs=[spec4, spec2d, spec2d, spec2d, spec2d],
        out_specs=[spec2d, spec2d],
        out_shape=[jax.ShapeDtypeStruct((B, H, W), jnp.int32),
                   jax.ShapeDtypeStruct((B, H, W), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
    )(cap, seed_t, seed_s, dt, ds)
    return dt, ds


# ---------------------------------------------------------------- sink_bfs
# The fixed-cadence relabel (``grid.bfs_heights``' heights) in one call.

# Most rows of a sweep's chunk (a chunk is ``gcd(H, SINK_ROWS)`` rows, a
# multiple of the 8-row sublane tile): longer chunks give the scheduler
# more independent vregs, until they spill.
SINK_ROWS = 64
# Jacobi sweeps between two convergence tests of a grid. A sweep past the
# fixpoint is a no-op, so the heights do not depend on it.
SINK_SWEEPS = 4
# VMEM in (H, W) 4-byte planes: 6 block planes in and 1 out, double-buffered
# across the batch grid, 4 penalty planes and 2 height buffers of scratch,
# and a plane of slack for the compiler's temporaries.
SINK_VMEM_PLANES = 21
LANES = 128


def _permute_lanes(x, T: int, source):
    """Each row of ``x`` (``T`` 128-lane tiles) with its columns permuted:
    lane ``l`` of output tile ``a`` is lane ``source(a, l)[1]`` of input
    tile ``source(a, l)[0]``."""
    if T == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], LANES), 1)
    out = []
    for a in range(T):
        src_tile, src_lane = source(a, lane)
        tile = None
        for j in range(T):
            got = jnp.take_along_axis(x[:, LANES * j:LANES * (j + 1)],
                                      src_lane, axis=1,
                                      mode="promise_in_bounds")
            tile = got if tile is None else jnp.where(src_tile == j, got,
                                                      tile)
        out.append(tile)
    return jnp.concatenate(out, axis=1)


def _to_lanes(x, T: int):
    """Natural columns -> interleaved: lane ``l`` of the ``t``-th of a
    row's ``T`` 128-lane tiles holds column ``T * l + t``. Then a node's
    left and right neighbours sit at its own lane of the tiles beside it,
    except in the first and last tile, which take one lane roll each."""
    return _permute_lanes(x, T, lambda t, l: divmod(T * l + t, LANES))


def _from_lanes(y, T: int):
    """Interleaved columns (``_to_lanes``) -> natural."""
    return _permute_lanes(
        y, T, lambda j, l: ((LANES * j + l) % T, (LANES * j + l) // T))


def sink_bfs_fits(H: int, W: int) -> bool:
    """Whether ``sink_bfs`` can relabel an ``(H, W)`` grid (padded to whole
    ``(8, 128)`` tiles): every plane of one grid in VMEM under
    ``VMEM_CAP``, and every finite height (at most ``H * W + 1``) exact in
    float32."""
    return (vmem_limit_bytes(H, W, SINK_VMEM_PLANES) <= VMEM_CAP
            and H * W + 2 < 2 ** 24)


def _sink_bfs_kernel(nn_ref, cap_ref, sink_ref, hprev_ref, h_out, pen_ref,
                     buf_ref, *, max_iters: int):
    """One grid's relabel: masks, sweeps to its own fixpoint, gap rule.

    In VMEM the planes are float32 (the VPU has a float min and max, not
    an int32 one) with interleaved columns (``_to_lanes``), so a sweep
    rolls two vregs of a row's lanes and not all of them. ``pen_ref[d]``
    is 0 where the residual edge toward direction ``d`` is open and
    ``INF_H`` where it is closed or leaves the grid, so ``max(neighbour's
    height, pen)`` is what the neighbour offers and no roll's wrap-around
    reaches a node. ``buf_ref`` holds the two Jacobi planes: sweep ``k``
    reads ``buf[k % 2]`` and writes ``buf[(k + 1) % 2]``.
    """
    H, W = h_out.shape
    R = math.gcd(H, SINK_ROWS)
    S = 8   # the rows of a halo: one sublane tile
    T = W // LANES
    n_chunks = H // R
    inf = float(INF_H)

    def rows(i):
        return pl.ds(pl.multiple_of(i * R, R), R)

    def masks(i, carry):
        row = i * R + jax.lax.broadcasted_iota(jnp.int32, (R, W), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
        off = (row == 0, row == H - 1, col == 0, col == W - 1)
        bits = jnp.where(sink_ref[rows(i), :] > 0, 16, 0)
        for d in range(4):
            bits |= jnp.where((cap_ref[d, rows(i), :] > 0) & ~off[d],
                              1 << d, 0)
        bits = _to_lanes(bits, T)     # one interleave for all five masks
        for d in range(4):
            pen_ref[d, rows(i), :] = jnp.where((bits & (1 << d)) != 0, 0.0,
                                               inf)
        buf_ref[0, rows(i), :] = jnp.where((bits & 16) != 0, 1.0, inf)
        return carry

    jax.lax.fori_loop(0, n_chunks, masks, 0)

    def sweep(src, track):
        """One Jacobi sweep ``buf[src] -> buf[1 - src]``; with ``track``,
        whether it lowered any height (False at the fixpoint).

        The loop carries the last 8 rows of the chunk above. ``min(h, 1 +
        min_d max(nbr_d, pen_d))`` is ``bfs_heights``' ``min(h, min_d
        where(open_d, nbr_d + 1, INF))``: a closed edge offers at least
        ``INF``, which never undercuts ``h``.
        """
        def chunk(i, carry):
            above, drop = carry
            h = buf_ref[src, rows(i), :]
            below = buf_ref[src, pl.ds(
                pl.multiple_of(jnp.minimum((i + 1) * R, H - S), S), S), :]
            tile = [h[:, LANES * t:LANES * (t + 1)] for t in range(T)]
            nbr = (pltpu.roll(jnp.concatenate([above, h]), 1, 0)[S:],
                   pltpu.roll(jnp.concatenate([h, below]), R + S - 1,
                              0)[:R],
                   jnp.concatenate([pltpu.roll(tile[-1], 1, 1)] + tile[:-1],
                                   axis=1),
                   jnp.concatenate(tile[1:] + [pltpu.roll(tile[0], LANES - 1,
                                                          1)], axis=1))
            best = functools.reduce(jnp.minimum, [
                jnp.maximum(nbr[d], pen_ref[d, rows(i), :])
                for d in range(4)])
            new = jnp.minimum(h, best + 1.0)
            buf_ref[1 - src, rows(i), :] = new
            if track:   # fold the chunk's rows into one sublane tile
                drop = jnp.maximum(drop, jnp.max(
                    (h - new).reshape(R // S, S, W), axis=0))
            return h[R - S:], drop

        _, drop = jax.lax.fori_loop(
            0, n_chunks, chunk,
            (buf_ref[src, pl.ds(0, S), :], jnp.zeros((S, W), jnp.float32)))
        return jnp.max(drop) > 0

    def group(carry):
        it, _ = carry
        n = jnp.minimum(SINK_SWEEPS, max_iters - it)

        def untracked(k, c):
            sweep((it + k) % 2, False)
            return c

        jax.lax.fori_loop(0, n - 1, untracked, 0)
        changed = sweep((it + n - 1) % 2, True)
        return it + n, changed

    def cond(carry):
        it, changed = carry
        return changed & (it < max_iters)

    it, _ = jax.lax.while_loop(cond, group, (jnp.int32(0), jnp.bool_(True)))
    cur = it % 2
    n_nodes = nn_ref[0]

    def gap(i, carry):
        h = _from_lanes(buf_ref[cur, rows(i), :], T)
        h_out[rows(i), :] = jnp.where(
            h >= inf, jnp.maximum(hprev_ref[rows(i), :], n_nodes),
            h.astype(jnp.int32))
        return carry

    jax.lax.fori_loop(0, n_chunks, gap, 0)


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def sink_bfs(cap, cap_sink, h_prev, n_nodes, *, max_iters: int,
             interpret: bool = False):
    """Global + gap relabel heights of a batch of grids, one grid a step.

    Args:
      cap: ``(4, B, H, W)`` residual neighbour capacities.
      cap_sink: ``(B, H, W)`` residual x -> t capacities.
      h_prev: ``(B, H, W)`` int32 current heights.
      n_nodes: scalar int32 (the paper's N), on scalar prefetch.
      max_iters: the most sweeps a grid runs (``bfs_heights``' cap).

    ``H`` must be a multiple of 8 and ``W`` of 128 (``ops.
    sink_bfs_heights`` pads other grids), within ``sink_bfs_fits``.
    Returns ``(B, H, W)`` int32 heights, bit-identical to
    ``grid.bfs_heights``: each grid sweeps from its sink seeds until a
    sweep changes nothing or it has run ``max_iters`` sweeps, whatever
    the other grids of the batch do.
    """
    B, H, W = h_prev.shape
    if H % 8 or W % LANES or not sink_bfs_fits(H, W):
        raise ValueError(f"sink_bfs: a {H}x{W} grid is not whole (8, 128) "
                         f"tiles within VMEM (see sink_bfs_fits)")
    plane = pl.BlockSpec((None, H, W), lambda b, nn: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,   # n_nodes
        grid=(B,),
        in_specs=[pl.BlockSpec((4, None, H, W), lambda b, nn: (0, b, 0, 0)),
                  plane, plane],
        out_specs=plane,
        scratch_shapes=[pltpu.VMEM((4, H, W), jnp.float32),
                        pltpu.VMEM((2, H, W), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_sink_bfs_kernel, max_iters=max_iters),
        grid_spec=grid_spec,
        name="sink_bfs",
        out_shape=jax.ShapeDtypeStruct((B, H, W), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(H, W, SINK_VMEM_PLANES)),
    )(jnp.asarray([n_nodes], jnp.int32), cap, cap_sink, h_prev)
