"""Pallas TPU kernel: one BFS frontier-expansion sweep of bipartite matching.

The compute hot-spot of the lock-free alternating-BFS phase (Deveci et al.,
arXiv:1303.1379): every column scans its adjacent LABELED rows and is
claimed by the strongest candidate.  On the GPU the paper resolves the race
with atomics; here the claim rule is a deterministic keyed minimum — a
column takes the smallest root label among labeled rows reaching it over
non-matching edges, tie-broken by smallest row index — so the "winner" of
the race is a pure reduction and bit-stable across batching/sharding
layouts.

Per column ``j`` the kernel reduces, over rows ``i`` with
``adj[i, j] & (root_row[i] < INF) & (match_row[i] != j)``:

  * ``min_root[j]``  — the minimum ``root_row[i]`` (INF if no candidate),
  * ``claim_row[j]`` — the minimum ``i`` attaining that minimum root.

Tiling: grid = (n_cols/BC, n_rows/BR); the ROW dimension is innermost so
each column-block's (min_root, claim_row) accumulator stays resident in its
output VMEM block across the whole row sweep (the same streaming-reduction
shape as the bidding kernel, transposed).  Row blocks arrive in increasing
``i``, so keeping the incumbent on ties preserves the min-row tie-break.
VMEM working set per grid step = BR·BC (adj) + 2·BR·4B (row labels)
+ 2·BC·4B (accumulators) — far below the 16 MB budget at BR=256, BC=512.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF = 2 ** 30  # python int: jnp scalars would be captured consts in pallas


def _frontier_kernel(a_ref, r_ref, m_ref, root_ref, claim_ref, *,
                     block_rows: int, block_cols: int):
    j = pl.program_id(0)
    i = pl.program_id(1)

    a = a_ref[...]                       # (BR, BC) bool adjacency tile
    root = r_ref[...]                    # (BR, 1) int32 row root labels
    match = m_ref[...]                   # (BR, 1) int32 matched col (-1 free)

    cols = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) + j * block_cols
    rows = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0) + i * block_rows
    cand = jnp.where(a & (root < INF) & (match != cols), root, INF)

    # local keyed min along the tile's rows: (root, then row index)
    l_root = jnp.min(cand, axis=0, keepdims=True)                  # (1, BC)
    l_claim = jnp.min(jnp.where(cand == l_root, rows, INF), axis=0,
                      keepdims=True)

    @pl.when(i == 0)
    def _init():
        root_ref[...] = l_root
        claim_ref[...] = l_claim

    @pl.when(i > 0)
    def _merge():
        r_root, r_claim = root_ref[...], claim_ref[...]
        # strict <: on a root tie the incumbent block holds smaller rows
        take_new = l_root < r_root
        root_ref[...] = jnp.where(take_new, l_root, r_root)
        claim_ref[...] = jnp.where(take_new, l_claim, r_claim)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols",
                                             "interpret"))
def frontier(adj: jax.Array, root_row: jax.Array, match_row: jax.Array,
             *, block_rows: int = 256, block_cols: int = 512,
             interpret: bool = False):
    """Per-column ``(min_root, claim_row)`` over labeled candidate rows.

    ``adj`` is ``(n_r, n_c)`` bool; ``root_row``/``match_row`` are
    ``(n_r,)`` int32 (root INF = unlabeled, match -1 = free).  Columns with
    no candidate return ``(INF, 0)`` — callers gate on ``min_root < INF``.
    interpret=True executes the kernel body on the host (tests off-TPU).
    """
    n_r, n_c = adj.shape
    br, bc = min(block_rows, n_r), min(block_cols, n_c)
    assert n_r % br == 0 and n_c % bc == 0, (n_r, n_c, br, bc)
    grid = (n_c // bc, n_r // br)

    out_shape = [jax.ShapeDtypeStruct((1, n_c), jnp.int32)] * 2
    out_spec = pl.BlockSpec((1, bc), lambda j, i: (0, j))
    col_spec = pl.BlockSpec((br, 1), lambda j, i: (i, 0))
    min_root, claim_row = pl.pallas_call(
        functools.partial(_frontier_kernel, block_rows=br, block_cols=bc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda j, i: (i, j)),
            col_spec,
            col_spec,
        ],
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(adj, root_row.reshape(-1, 1).astype(jnp.int32),
      match_row.reshape(-1, 1).astype(jnp.int32))
    return min_root[0], claim_row[0]
