"""bfs_relabel pallas kernels vs pure-jnp oracles.

The balanced backend's contract: one ``bfs_relabel_sweeps`` launch ==
``SWEEPS`` joint min-plus relaxation sweeps of both wavefront planes
(``ref.bfs_relabel_sweeps_ref``), and the ops-level fixpoint driver
reproduces the eager bidirectional fixpoint + combine (``ref.
bfs_relabel_heights_ref``) bit-exactly — single and batched.

The ``pallas`` backend's: ``sink_bfs_heights`` gives ``grid.bfs_heights``'
heights bit for bit (and ``ref.sink_bfs_heights_ref``'s), with its sweep
cap counted per grid, and takes the kernel by the grid's shape alone.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.maxflow.grid import bfs_heights
from repro.core.maxflow.ref import (checkerboard_problem, long_path_problem,
                                    random_grid_problem)
from repro.kernels.bfs_relabel.kernel import (INF_H, SWEEPS,
                                              bfs_relabel_sweeps,
                                              sink_bfs_fits)
from repro.kernels.bfs_relabel.ops import (bfs_relabel_heights,
                                           sink_bfs_heights)
from repro.kernels.bfs_relabel.ref import (bfs_relabel_heights_ref,
                                           bfs_relabel_sweeps_ref,
                                           sink_bfs_heights_ref)

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


# ------------------------------------------------- sink_bfs (pallas backend)

def _sink_case(batch, H, W, seed):
    """Random grids of shape ``batch + (H, W)`` in the solver's layout,
    with border edges given capacity too (the kernel's masks must close
    them), and heights ``h_prev`` drawn on ``[0, 2N)``."""
    rng = np.random.default_rng(seed)
    B = int(np.prod(batch, dtype=int))
    probs = [random_grid_problem(rng, H, W) for _ in range(B)]
    cap = np.stack([p[0] for p in probs], axis=1)
    cap[:, :, [0, -1], :] = rng.integers(0, 3, cap[:, :, [0, -1], :].shape)
    cap[:, :, :, [0, -1]] = rng.integers(0, 3, cap[:, :, :, [0, -1]].shape)
    ct = np.stack([p[2] for p in probs])
    n = H * W + 2
    h_prev = rng.integers(0, 2 * n, (B, H, W))
    return (jnp.asarray(cap.reshape((4,) + batch + (H, W))),
            jnp.asarray(ct.reshape(batch + (H, W))),
            jnp.asarray(h_prev.reshape(batch + (H, W)), jnp.int32), n)


def _pallas_names(fn, *args):
    """The names of the ``pallas_call``s ``fn`` traces to, nested ones too."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("batch,H,W,max_iters,seed", [
    ((1,), 8, 128, 0, 0),       # one grid of one sublane tile
    ((3,), 24, 136, 0, 1),      # a lane-ragged width (padded to 256)
    ((8,), 8, 128, 0, 2),       # the batch cell's batch size
    ((), 64, 64, 0, 3),         # a single grid, no batch axis
    ((2,), 12, 12, 0, 9),       # rows padded to a sublane tile
    ((2,), 16, 512, 0, 10),     # four lane tiles, interleaved
    ((2,), 8, 384, 0, 14),      # three lane tiles
    ((3,), 64, 64, 0, 4),
    ((3,), 24, 136, 1, 5),      # sweep caps that bind: one sweep,
    ((3,), 24, 136, 5, 6),      # mid-way through a convergence test,
    ((3,), 64, 64, 9, 7),       # and past one
    ((), 24, 136, 5, 8),
])
def test_sink_bfs_equals_bfs_heights(batch, H, W, max_iters, seed):
    """The kernel's heights are ``bfs_heights``' and the per-grid oracle's,
    bit for bit, with the sweep cap counted per grid."""
    cap, ct, h_prev, n = _sink_case(batch, H, W, seed)
    iters = max_iters or n
    assert _pallas_names(lambda *a: sink_bfs_heights(
        *a, n, iters, interpret=True), cap, ct, h_prev) == ["sink_bfs"]
    got = sink_bfs_heights(cap, ct, h_prev, jnp.int32(n), iters,
                           interpret=True)
    want = bfs_heights(cap, ct, h_prev, jnp.int32(n), iters)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ref = sink_bfs_heights_ref(cap, ct, h_prev, n, iters)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("sink_open", [False, True])
def test_sink_bfs_closed_grid_takes_the_gap_value(sink_open):
    """Every grid edge closed: a node beside the sink is at 1 and every
    other takes the gap value ``max(h_prev, N)``, also where ``h_prev``
    already lies above ``N``."""
    B, H, W = 2, 16, 128
    n = H * W + 2
    rng = np.random.default_rng(11)
    cap = jnp.zeros((4, B, H, W), jnp.float32)
    ct = np.zeros((B, H, W), np.float32)
    if sink_open:
        ct[:, 3, 5] = 2.0
    h_prev = jnp.asarray(rng.integers(0, 2 * n, (B, H, W)), jnp.int32)
    got = np.asarray(sink_bfs_heights(cap, jnp.asarray(ct), h_prev,
                                      jnp.int32(n), n, interpret=True))
    gap = np.maximum(np.asarray(h_prev), n)
    want = np.where(ct > 0, 1, gap)
    np.testing.assert_array_equal(got, want)
    assert (got[ct == 0] >= n).all()


def test_sink_bfs_keeps_heights_above_n():
    """``h_prev`` wholly above ``N``: the unreached nodes keep it, the
    reached ones take their distance to the sink, as in ``bfs_heights``."""
    cap, ct, h_prev, n = _sink_case((3,), 16, 128, 12)
    h_prev = h_prev + n + 1
    got = np.asarray(sink_bfs_heights(cap, ct, h_prev, jnp.int32(n), n,
                                      interpret=True))
    want = np.asarray(bfs_heights(cap, ct, h_prev, jnp.int32(n), n))
    np.testing.assert_array_equal(got, want)
    unreached = got >= n
    assert unreached.any() and (~unreached).any()
    np.testing.assert_array_equal(got[unreached],
                                  np.asarray(h_prev)[unreached])


def test_sink_bfs_batched_matches_singles():
    """A grid's heights do not depend on its batch-mates, even when the
    sweep cap binds for some grids and not for others."""
    cap, ct, h_prev, n = _sink_case((4,), 24, 136, 13)
    batched = sink_bfs_heights(cap, ct, h_prev, jnp.int32(n), 30,
                               interpret=True)
    for b in range(4):
        single = sink_bfs_heights(cap[:, b], ct[b], h_prev[b], jnp.int32(n),
                                  30, interpret=True)
        np.testing.assert_array_equal(np.asarray(batched[b]),
                                      np.asarray(single))


@pytest.mark.parametrize("H,W,kernel", [
    (512, 512, True),       # the benchmark's grids
    (8, 128, True),
    (24, 136, True),        # padded to whole (8, 128) tiles
    (12, 12, True),
    (516, 512, True),
    (1024, 1024, True),     # 21 planes of 4 MiB: within the VMEM cap
    (1536, 1536, False),    # 21 planes of 9 MiB: over it
    (2048, 1024, False),    # 21 planes of 8 MiB
])
def test_sink_bfs_shape_rule(H, W, kernel):
    """The kernel is taken by the grid's shape alone: one grid's planes,
    padded to whole ``(8, 128)`` tiles, within ``VMEM_CAP``; otherwise the
    relabel is ``bfs_heights`` (traced only, nothing compiles)."""
    from repro.kernels import VMEM_CAP
    from repro.kernels.bfs_relabel.kernel import (SINK_VMEM_PLANES,
                                                  vmem_limit_bytes)
    assert sink_bfs_fits(H, W) is kernel
    assert (vmem_limit_bytes(H, W, SINK_VMEM_PLANES) <= VMEM_CAP) is kernel
    S = jax.ShapeDtypeStruct
    names = _pallas_names(
        lambda c, t, h: sink_bfs_heights(c, t, h, jnp.int32(H * W + 2),
                                         H * W + 2, interpret=True),
        S((4, 2, H, W), jnp.float32), S((2, H, W), jnp.float32),
        S((2, H, W), jnp.int32))
    assert names == (["sink_bfs"] if kernel else [])
