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
"""
from __future__ import annotations

import functools

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


def vmem_limit_bytes(H: int, W: int) -> int:
    """Scoped-VMEM limit for one ``(H, W)`` instance per grid step.

    A plane occupies whole (8, 128) tiles of 4-byte words in VMEM.
    """
    plane = -(-H // 8) * 8 * (-(-W // 128) * 128) * 4
    return max(VMEM_DEFAULT, VMEM_PLANES * plane)


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
