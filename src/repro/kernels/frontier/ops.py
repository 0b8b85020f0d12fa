"""Jit'd public wrapper for the frontier kernel (auto interpret on CPU)."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.frontier.kernel import frontier
from repro.kernels.frontier.ref import frontier_ref  # noqa: F401  (oracle)


def frontier_op(adj, root_row, match_row, *, block_rows: int = 256,
                block_cols: int = 512):
    return frontier(adj, root_row, match_row, block_rows=block_rows,
                    block_cols=block_cols, interpret=interpret_mode())
