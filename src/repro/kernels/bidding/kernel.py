"""Pallas TPU kernel: per-row top-2 minimum of masked part-reduced costs.

This is the compute hot-spot of the paper's Refine (Algorithm 5.4 lines 6-10:
"select the residual edge with the lowest part-reduced cost") and of the
auction bid (top-2). On the GPU the paper scans adjacency lists per thread;
on TPU we tile the dense complete-bipartite cost matrix through VMEM and keep
a running (min1, arg1, min2) accumulator per row block.

Tiling: grid = (n_rows/BR, n_cols/BC); the column dimension is innermost so
each row-block's accumulator stays resident in its output VMEM block across
the whole column sweep (flash-attention-style streaming reduction). VMEM
working set per grid step = BR·BC·4B (costs) + BR·BC (mask) + BC·4B (prices)
+ 3·BR·4B (accumulators) — BR=256, BC=512 ⇒ ~0.7 MB ≪ 16 MB VMEM, leaving
room for double buffering of the streamed cost tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF = 2 ** 30  # python int: jnp scalars would be captured consts in pallas


def _bidding_kernel(c_ref, p_ref, m_ref, min1_ref, arg1_ref, min2_ref, *,
                    block_cols: int):
    j = pl.program_id(1)

    c = c_ref[...]                       # (BR, BC) int32 costs
    p = p_ref[...]                       # (1, BC) int32 prices
    m = m_ref[...]                       # (BR, BC) bool: True = not residual
    adj = jnp.where(m, INF, c - p)       # part-reduced cost c'_p = c - p(y)

    # local top-2 along the tile's columns
    l_min1 = jnp.min(adj, axis=1, keepdims=True)                  # (BR, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, adj.shape, 1)
    l_arg1 = jnp.min(jnp.where(adj == l_min1, cols, INF), axis=1,
                     keepdims=True)                               # first argmin
    adj2 = jnp.where(cols == l_arg1, INF, adj)
    l_min2 = jnp.min(adj2, axis=1, keepdims=True)
    l_arg1 = l_arg1 + j * block_cols                              # global col

    @pl.when(j == 0)
    def _init():
        min1_ref[...] = l_min1
        arg1_ref[...] = l_arg1
        min2_ref[...] = l_min2

    @pl.when(j > 0)
    def _merge():
        r_min1, r_arg1, r_min2 = min1_ref[...], arg1_ref[...], min2_ref[...]
        take_new = l_min1 < r_min1
        n_min1 = jnp.where(take_new, l_min1, r_min1)
        n_arg1 = jnp.where(take_new, l_arg1, r_arg1)
        # second-best among {loser of the min1 duel, both min2 candidates}
        loser = jnp.where(take_new, r_min1, l_min1)
        n_min2 = jnp.minimum(loser, jnp.minimum(l_min2, r_min2))
        min1_ref[...] = n_min1
        arg1_ref[...] = n_arg1
        min2_ref[...] = n_min2


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols",
                                             "interpret"))
def bidding(c: jax.Array, p_y: jax.Array, mask: jax.Array,
            *, block_rows: int = 256, block_cols: int = 512,
            interpret: bool = False):
    """Row-wise (min1, arg1, min2) of ``where(mask, INF, c - p_y)``.

    interpret=True executes the kernel body on the host (tests off-TPU).
    """
    n_r, n_c = c.shape
    br, bc = min(block_rows, n_r), min(block_cols, n_c)
    assert n_r % br == 0 and n_c % bc == 0, (n_r, n_c, br, bc)
    grid = (n_r // br, n_c // bc)

    out_shape = [jax.ShapeDtypeStruct((n_r, 1), jnp.int32)] * 3
    out_spec = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    min1, arg1, min2 = pl.pallas_call(
        functools.partial(_bidding_kernel, block_cols=bc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(c, p_y.reshape(1, -1), mask)
    return min1[:, 0], arg1[:, 0], min2[:, 0]
