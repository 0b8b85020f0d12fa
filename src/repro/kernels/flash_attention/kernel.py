"""Pallas TPU flash-attention forward kernel (beyond-paper optimization).

The dry-run roofline shows every attention-heavy cell is MEMORY-bound on
score/prob traffic: the jnp flash implementation materializes the
(B, H, Sq, C) score block in HBM once per key chunk (f32), ~10 TB/device
per step on smollm train_4k. This kernel keeps the whole (q-block × k-block)
working set in VMEM — HBM traffic drops to the q/k/v/o tensors themselves
(napkin math in EXPERIMENTS.md §Perf: ~100x less attention traffic).

Grid: (B·H, Sq/BQ, Sk/BK), k-block innermost so the accumulator tile stays
resident. BlockSpec tiling (BQ=256, BK=512, dh<=256):
  q tile 256·dh·4B ≈ 256 KB, k/v tiles 512·dh·4B ≈ 512 KB each,
  s/p tile 256·512·4B = 512 KB, acc 256·dv·4B + stats ≈ 300 KB
  => < 2.5 MB, double-buffered well under the 16 MB VMEM budget; MXU dims
  (256, 512) × (512, dh) are 128-aligned.

GQA is handled by the index_map: the kv BlockSpec maps head h to kv-head
h // (H // KV), so no repeated K/V ever exists in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      scale: float, causal: bool, block_q: int,
                      block_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # (BQ, dh)
    k = k_ref[0]                                   # (BK, dh)
    v = v_ref[0]                                   # (BK, dv)

    run = True
    if causal:
        # skip fully-masked blocks (upper triangle)
        run = (kj * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run if causal else True)
    def _compute():
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)
        if causal:
            pos_q = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            pos_k = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(pos_q >= pos_k, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        scale: float | None = None, block_q: int = 256,
                        block_k: int = 512, interpret: bool = False):
    """q: (B, Sq, H, dh); k/v: (B, Sk, KV, dh/dv). Returns (B, Sq, H, dv).

    VMEM tiling per the module docstring; interpret=True validates on CPU.
    """
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // KV
    scale = dh ** -0.5 if scale is None else scale
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0

    # (B, S, H, d) -> (B*H, S, d) so one grid row owns one (batch, head)
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, dh)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, dv)

    grid = (B * H, Sq // bq, Sk // bk)

    def q_map(bh, qi, kj):
        return (bh, qi, 0)

    def kv_map(bh, qi, kj):
        # GQA: head bh -> kv row (batch * KV + head // G)
        return ((bh // H) * KV + (bh % H) // G, kj, 0)

    from jax.experimental.pallas import tpu as pltpu
    out = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), q_map),
            pl.BlockSpec((1, bk, dh), kv_map),
            pl.BlockSpec((1, bk, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),    # acc tile
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, Sq, dv).transpose(0, 2, 1, 3)
