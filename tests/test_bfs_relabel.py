"""bfs_relabel pallas kernel vs pure-jnp oracle (balanced backend relabel).

The contract: one ``bfs_relabel_sweeps`` launch == ``SWEEPS`` joint
min-plus relaxation sweeps of both wavefront planes (``ref.
bfs_relabel_sweeps_ref``), and the ops-level fixpoint driver reproduces
the eager bidirectional fixpoint + combine (``ref.
bfs_relabel_heights_ref``) bit-exactly — single and batched.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.maxflow.ref import (checkerboard_problem, long_path_problem,
                                    random_grid_problem)
from repro.kernels.bfs_relabel.kernel import INF_H, SWEEPS, bfs_relabel_sweeps
from repro.kernels.bfs_relabel.ops import bfs_relabel_heights
from repro.kernels.bfs_relabel.ref import (bfs_relabel_heights_ref,
                                           bfs_relabel_sweeps_ref)

pytestmark = pytest.mark.kernels


def _seeds(cap_src, cap_sink, n_nodes):
    seed_t = jnp.where(jnp.asarray(cap_sink) > 0, jnp.int32(1), INF_H)
    seed_s = jnp.where(jnp.asarray(cap_src) > 0, jnp.int32(n_nodes) + 1,
                       INF_H)
    return seed_t, seed_s


@pytest.mark.parametrize("H,W,seed", [(8, 8, 0), (16, 32, 1), (32, 32, 2)])
def test_sweeps_kernel_vs_ref(H, W, seed):
    rng = np.random.default_rng(seed)
    cap, cs, ct = random_grid_problem(rng, H, W)
    n = H * W + 2
    seed_t, seed_s = _seeds(cs, ct, n)
    cap = jnp.asarray(cap)
    k_dt, k_ds = bfs_relabel_sweeps(
        cap[:, None], seed_t[None], seed_s[None], seed_t[None], seed_s[None],
        interpret=True)
    r_dt, r_ds = bfs_relabel_sweeps_ref(cap, seed_t, seed_s, seed_t, seed_s,
                                        sweeps=SWEEPS)
    np.testing.assert_array_equal(np.asarray(k_dt[0]), np.asarray(r_dt))
    np.testing.assert_array_equal(np.asarray(k_ds[0]), np.asarray(r_ds))


def test_sweeps_batched_grid_matches_singles():
    """The (B,) pallas grid dim == per-instance launches, bit-exact."""
    rng = np.random.default_rng(3)
    B, H, W = 4, 12, 12
    probs = [random_grid_problem(rng, H, W) for _ in range(B)]
    n = H * W + 2
    cap = jnp.asarray(np.stack([p[0] for p in probs], axis=1))  # (4,B,H,W)
    seeds = [_seeds(p[1], p[2], n) for p in probs]
    seed_t = jnp.stack([s[0] for s in seeds])
    seed_s = jnp.stack([s[1] for s in seeds])
    b_dt, b_ds = bfs_relabel_sweeps(cap, seed_t, seed_s, seed_t, seed_s,
                                    interpret=True)
    for b in range(B):
        s_dt, s_ds = bfs_relabel_sweeps(
            cap[:, b:b + 1], seed_t[b:b + 1], seed_s[b:b + 1],
            seed_t[b:b + 1], seed_s[b:b + 1], interpret=True)
        np.testing.assert_array_equal(np.asarray(b_dt[b]), np.asarray(s_dt[0]))
        np.testing.assert_array_equal(np.asarray(b_ds[b]), np.asarray(s_ds[0]))


@pytest.mark.parametrize("maker,seed", [
    (lambda rng: random_grid_problem(rng, 16, 16), 0),
    (lambda rng: random_grid_problem(rng, 8, 24), 5),
    (lambda rng: long_path_problem(8, 8), 0),
    (lambda rng: checkerboard_problem(8, 8), 0),
])
def test_heights_driver_vs_fixpoint_ref(maker, seed):
    """ops.bfs_relabel_heights == eager fixpoint+combine oracle, with a
    non-trivial h_prev (the combine must never lower existing heights)."""
    rng = np.random.default_rng(seed)
    cap, cs, ct = maker(rng)
    H, W = cs.shape
    n = H * W + 2
    h_prev = jnp.asarray(rng.integers(0, n, (H, W)), jnp.int32)
    got = bfs_relabel_heights(jnp.asarray(cap), jnp.asarray(cs),
                              jnp.asarray(ct), h_prev, n, n, interpret=True)
    want = bfs_relabel_heights_ref(jnp.asarray(cap), jnp.asarray(cs),
                                   jnp.asarray(ct), h_prev, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_heights_batched_matches_singles():
    rng = np.random.default_rng(9)
    B, H, W = 3, 10, 10
    probs = [random_grid_problem(rng, H, W) for _ in range(B)]
    n = H * W + 2
    cap = jnp.asarray(np.stack([p[0] for p in probs], axis=1))
    cs = jnp.asarray(np.stack([p[1] for p in probs]))
    ct = jnp.asarray(np.stack([p[2] for p in probs]))
    h_prev = jnp.zeros((B, H, W), jnp.int32)
    batched = bfs_relabel_heights(cap, cs, ct, h_prev, n, n, interpret=True)
    for b in range(B):
        single = bfs_relabel_heights(cap[:, b], cs[b], ct[b], h_prev[b], n, n,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(batched[b]),
                                      np.asarray(single))


def test_bidirectional_labels_disconnected_pocket():
    """A cell cut off from the sink but residually connected to the source
    gets the exact return gradient N + dist, not the flat gap value N."""
    H, W = 4, 4
    cap = np.zeros((4, H, W), np.float32)
    cs = np.zeros((H, W), np.float32)
    ct = np.zeros((H, W), np.float32)
    cs[0, 0] = 5.0        # source feeds the top-left pocket
    ct[3, 3] = 5.0        # sink sits in the far corner, unreachable
    cap[3, 0, 0] = 1.0    # (0,0) -> (0,1): RIGHT edge only, dead ends there
    n = H * W + 2
    h = bfs_relabel_heights(jnp.asarray(cap), jnp.asarray(cs),
                            jnp.asarray(ct), jnp.zeros((H, W), jnp.int32),
                            n, n, interpret=True)
    h = np.asarray(h)
    assert h[0, 0] == n + 1                  # adjacent to the source
    assert h[3, 3] == 1                      # adjacent to the sink
    assert h[1, 1] == n                      # doubly unreached -> gap value
    # (0,1) has no residual out-edges at all (cap stores OUT capacities),
    # so neither wavefront reaches it either:
    assert h[0, 1] == n


def test_vmem_limit_admits_paper_grid_and_refuses_oversize():
    """The compiled kernel's VMEM limit comes from its shape: the paper's
    512² grid gets the ~33 planes the chip's compiler asks for, and a grid
    past ``VMEM_CAP`` raises before dispatch instead of inside Mosaic."""
    from repro.kernels.bfs_relabel.kernel import (VMEM_CAP, VMEM_DEFAULT,
                                                  vmem_limit_bytes)
    assert vmem_limit_bytes(64, 64) == VMEM_DEFAULT
    assert 33 * 2 ** 20 <= vmem_limit_bytes(512, 512) <= VMEM_CAP
    # a (480, 640) plane pads its lanes to 5 whole 128-lane tiles
    assert vmem_limit_bytes(480, 640) == vmem_limit_bytes(480, 640 - 127)
    big = jax.ShapeDtypeStruct((1, 1024, 1024), jnp.int32)
    cap = jax.ShapeDtypeStruct((4, 1, 1024, 1024), jnp.float32)
    with pytest.raises(ValueError, match="tiled relabel"):
        jax.eval_shape(lambda *a: bfs_relabel_sweeps(*a, interpret=False),
                       cap, big, big, big, big)
    # interpret mode has no VMEM: the same shape traces
    jax.eval_shape(lambda *a: bfs_relabel_sweeps(*a, interpret=True),
                   cap, big, big, big, big)
