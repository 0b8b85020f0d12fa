"""Jit'd public wrapper for the bidding kernel (auto interpret on CPU)."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.bidding.kernel import bidding
from repro.kernels.bidding.ref import bidding_ref  # noqa: F401  (oracle)


def bidding_op(c, p_y, mask, *, block_rows: int = 256, block_cols: int = 512):
    return bidding(c, p_y, mask, block_rows=block_rows,
                   block_cols=block_cols, interpret=interpret_mode())
