"""Drivers of the bfs_relabel kernels: the balanced backend's
bidirectional global/gap relabel, and the ``pallas`` backend's sink-only
one (``sink_bfs_heights``, at the end of this module).

``bfs_relabel_heights`` has the same call shape as ``repro.core.maxflow.
grid.bfs_heights`` (shape-polymorphic over leading batch axes, jittable)
but differs in two deliberate ways:

* the relaxation sweeps run ``kernel.SWEEPS`` at a time VMEM-resident in
  the pallas kernel, so the XLA ``while_loop`` pays one HBM round trip per
  ``SWEEPS`` sweeps instead of per sweep (``max_iters`` still caps TOTAL
  sweeps, rounded up to a multiple of ``SWEEPS``);
* the labeling is BIDIRECTIONAL — unreached-from-sink nodes get the exact
  return gradient ``N + dist_to_source`` instead of the paper's flat
  ``N`` gap value, so stranded excess drains home in ``dist`` rounds
  rather than climbing by +1 relabels (see kernel.py / docs/kernels.md).

Both differences preserve the height invariant ``h(x) <= h(y) + 1`` on
residual edges (asserted after every invocation in tests/test_balanced.py)
and the fixpoint is schedule-independent, so the result is deterministic
per instance — which is what lets ``backend="balanced"`` keep the
batched == loop-of-singles bit-match contract.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.maxflow.grid import bfs_heights
from repro.kernels import interpret_mode
from repro.kernels.bfs_relabel.kernel import (LANES, SWEEPS,
                                              bfs_relabel_sweeps, sink_bfs,
                                              sink_bfs_fits)

# python int, not jnp.int32: this module is imported lazily, possibly
# inside a jit trace, where creating a jnp constant would leak a tracer
INF_H = 2 ** 30


def bfs_relabel_heights(cap, cap_src, cap_sink, h_prev, n_nodes,
                        max_iters: int, *, interpret: bool | None = None):
    """Bidirectional global/gap relabel heights (balanced backend).

    Args:
      cap: ``(4, ..., H, W)`` residual neighbour capacities.
      cap_src / cap_sink: ``(..., H, W)`` residual terminal capacities.
      h_prev: ``(..., H, W)`` int32 current heights (never decreased).
      n_nodes: the paper's N = H*W + 2 (the source's conceptual height).
      max_iters: sweep budget (0 would loop forever — callers pass the
        H*W + 2 upper bound like ``bfs_heights`` does).

    Returns ``(..., H, W)`` int32 heights: exact height-to-sink where the
    sink is residually reachable, else ``max(h_prev, N + dist_to_source)``
    where the source is, else ``max(h_prev, N)``.
    """
    if interpret is None:
        interpret = interpret_mode()
    *batch, H, W = h_prev.shape
    B = 1
    for s in batch:
        B *= s
    cap4 = cap.reshape(4, B, H, W)
    seed_t = jnp.where(cap_sink > 0, jnp.int32(1), INF_H).reshape(B, H, W)
    seed_s = jnp.where(cap_src > 0, n_nodes + 1, INF_H).reshape(B, H, W)

    def body(carry):
        dt, ds, _, it = carry
        nt, ns = bfs_relabel_sweeps(cap4, seed_t, seed_s, dt, ds,
                                    interpret=interpret)
        changed = jnp.any((nt != dt) | (ns != ds))
        return nt, ns, changed, it + SWEEPS

    def cond(carry):
        _, _, changed, it = carry
        return changed & (it < max_iters)

    dt, ds, _, _ = jax.lax.while_loop(
        cond, body, (seed_t, seed_s, jnp.bool_(True), jnp.int32(0)))
    dt = dt.reshape(h_prev.shape)
    ds = ds.reshape(h_prev.shape)
    return jnp.where(dt < INF_H, dt,
                     jnp.maximum(h_prev, jnp.where(ds < INF_H, ds, n_nodes)))


def sink_bfs_heights(cap, cap_sink, h_prev, n_nodes, max_iters: int, *,
                     interpret: bool | None = None):
    """``grid.bfs_heights`` with its sweeps in one ``sink_bfs`` call.

    Same arguments and the same heights, bit for bit, on every input:
    the min-plus fixpoint is unique, and each grid stops at its own
    fixpoint or after ``max_iters`` Jacobi sweeps, as ``bfs_heights``
    leaves it. A grid that is not whole ``(8, 128)`` tiles is padded with
    closed nodes, which stay unreached and change no height. Where one
    grid's planes do not fit the kernel (``kernel.sink_bfs_fits``) it is
    ``bfs_heights`` itself. Shape-polymorphic over leading batch axes,
    like ``bfs_heights``.
    """
    *batch, H, W = h_prev.shape
    if not sink_bfs_fits(H, W):
        return bfs_heights(cap, cap_sink, h_prev, n_nodes, max_iters)
    if interpret is None:
        interpret = interpret_mode()
    B = math.prod(batch)
    pad = ((0, 0), (0, -H % 8), (0, -W % LANES))
    with jax.named_scope("maxflow/relabel"):
        h = sink_bfs(jnp.pad(cap.reshape(4, B, H, W), ((0, 0),) + pad),
                     jnp.pad(cap_sink.reshape(B, H, W), pad),
                     jnp.pad(h_prev.reshape(B, H, W), pad), n_nodes,
                     max_iters=max_iters, interpret=interpret)
    return h[:, :H, :W].reshape(h_prev.shape)
