"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.maxflow.grid import (GridFlowState, bfs_heights,
                                     jacobi_round)
from repro.core.maxflow.ref import random_grid_problem
from repro.kernels.bidding.kernel import bidding
from repro.kernels.bidding.ref import bidding_ref
from repro.kernels.grid_push.kernel import grid_push_decide
from repro.kernels.grid_push.ref import grid_push_decide_ref
from repro.kernels.grid_push.ops import jacobi_round_pallas


@pytest.mark.parametrize("shape,blocks", [
    ((8, 8), (8, 8)),
    ((64, 128), (16, 32)),
    ((256, 512), (128, 128)),
    ((128, 128), (128, 64)),
    ((32, 1024), (32, 256)),
])
def test_bidding_kernel_sweep(shape, blocks):
    rng = np.random.default_rng(hash(shape) % 2**31)
    n_r, n_c = shape
    c = jnp.asarray(rng.integers(-1000, 1000, (n_r, n_c)), jnp.int32)
    p = jnp.asarray(rng.integers(-500, 500, (n_c,)), jnp.int32)
    m = jnp.asarray(rng.random((n_r, n_c)) < 0.3)
    got = bidding(c, p, m, block_rows=blocks[0], block_cols=blocks[1],
                  interpret=True)
    ref = bidding_ref(c, p, m)
    for g, r, nm in zip(got, ref, ["min1", "arg1", "min2"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=nm)


def test_bidding_fully_masked_rows():
    c = jnp.zeros((8, 8), jnp.int32)
    p = jnp.zeros((8,), jnp.int32)
    m = jnp.ones((8, 8), bool)
    min1, _, min2 = bidding(c, p, m, block_rows=8, block_cols=8,
                            interpret=True)
    assert bool(jnp.all(min1 >= 2 ** 30)) and bool(jnp.all(min2 >= 2 ** 30))


@pytest.mark.parametrize("H,W,bh,bw", [(8, 8, 8, 8), (16, 32, 8, 16),
                                       (32, 32, 16, 32)])
def test_grid_push_kernel_vs_ref(H, W, bh, bw):
    rng = np.random.default_rng(0)
    cap, cs, ct = random_grid_problem(rng, H, W)
    st = GridFlowState(
        e=jnp.asarray(cs), h=jnp.zeros((H, W), jnp.int32),
        cap=jnp.asarray(cap), cap_src=jnp.asarray(cs),
        cap_sink=jnp.asarray(ct), sink_flow=jnp.float32(0),
        src_flow=jnp.float32(0))
    n = jnp.int32(H * W + 2)
    st = st._replace(h=bfs_heights(st.cap, st.cap_sink, st.h, n, H * W + 2))
    nbr_h = jnp.stack([jnp.roll(st.h, 1, 0)] * 4)  # placeholder, use ref path
    from repro.core.maxflow.grid import _nbr_h
    nbr_h = jnp.stack([_nbr_h(st.h, d) for d in range(4)], axis=0)
    h_k, d_k = grid_push_decide(st.e, st.h, st.cap, nbr_h, st.cap_src,
                                st.cap_sink, n, block_h=bh, block_w=bw,
                                interpret=True)
    h_r, d_r = grid_push_decide_ref(st.e, st.h, st.cap, nbr_h, st.cap_src,
                                    st.cap_sink, n)
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r))


def test_grid_push_kernel_batched_grid():
    """Batched mode (pallas grid gains a batch dim) == per-instance kernel."""
    rng = np.random.default_rng(7)
    B, H, W = 3, 16, 16
    probs = [random_grid_problem(rng, H, W) for _ in range(B)]
    e = jnp.asarray(np.stack([p[1] for p in probs]))
    cap = jnp.asarray(np.stack([p[0] for p in probs], axis=1))  # (4, B, H, W)
    ct = jnp.asarray(np.stack([p[2] for p in probs]))
    n = jnp.int32(H * W + 2)
    h = bfs_heights(cap, ct, jnp.zeros((B, H, W), jnp.int32), n, H * W + 2)
    from repro.core.maxflow.grid import _nbr_h
    nbr_h = jnp.stack([_nbr_h(h, d) for d in range(4)], axis=0)
    h_b, d_b = grid_push_decide(e, h, cap, nbr_h, e, ct, n,
                                block_h=8, block_w=8, interpret=True)
    for b in range(B):
        h_s, d_s = grid_push_decide(
            e[b], h[b], cap[:, b], nbr_h[:, b], e[b], ct[b], n,
            block_h=8, block_w=8, interpret=True)
        np.testing.assert_array_equal(np.asarray(h_b[b]), np.asarray(h_s))
        np.testing.assert_array_equal(np.asarray(d_b[:, b]), np.asarray(d_s))


def test_grid_push_round_bit_identical():
    """Full Jacobi rounds via the kernel == pure-jnp rounds, 5 steps."""
    rng = np.random.default_rng(1)
    H, W = 16, 16
    cap, cs, ct = random_grid_problem(rng, H, W)
    st = GridFlowState(
        e=jnp.asarray(cs), h=jnp.zeros((H, W), jnp.int32),
        cap=jnp.asarray(cap), cap_src=jnp.asarray(cs),
        cap_sink=jnp.asarray(ct), sink_flow=jnp.float32(0),
        src_flow=jnp.float32(0))
    n = jnp.int32(H * W + 2)
    st = st._replace(h=bfs_heights(st.cap, st.cap_sink, st.h, n, H * W + 2))
    for _ in range(5):
        a = jacobi_round(st, n)
        b = jacobi_round_pallas(st, n, block_h=8, block_w=8, interpret=True)
        for fa, fb, nm in zip(a, b, a._fields):
            if fa is None and fb is None:  # heur counter untracked here
                continue
            np.testing.assert_allclose(np.asarray(fa), np.asarray(fb),
                                       err_msg=nm)
        st = a


@pytest.mark.parametrize("H,W,blocks,want", [
    (512, 512, (64, 128), (64, 128)),     # aligned: kept
    (512, 512, (256, 256), (256, 256)),
    (512, 512, (64, 64), (64, 512)),      # 64 lanes < 128: full width
    (16, 16, (64, 128), (16, 16)),        # larger than the plane: full dims
    (100, 640, (64, 128), (100, 128)),    # 64 does not divide 100
    (480, 640, (12, 128), (480, 128)),    # 12 rows break the 8-row tiling
])
def test_tile_dims_are_tpu_aligned(H, W, blocks, want):
    """Kernel blocks meet the TPU tiling or span the full dim."""
    from repro.kernels.grid_push.kernel import tile_dims
    assert tile_dims(H, W, *blocks) == want
