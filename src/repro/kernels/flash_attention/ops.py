"""Jit'd wrapper (auto-interpret off-TPU) for the flash fwd kernel."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import flash_attention_ref  # noqa


def flash_attention_op(q, k, v, *, causal=True, scale=None,
                       block_q=256, block_k=512):
    return flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret_mode())
