"""The Pallas kernels compile for a TPU v5e, at the sizes chip_smoke.py uses.

Each test compiles one kernel for a DESCRIBED v5e chip (no chip needed:
the TPU compiler is installed with jaxlib's TPU support) with
``interpret=False``, and asserts the program holds a Mosaic kernel
(``tpu_custom_call``). This is what interpret-mode tests cannot see: the
chip's compiler refuses unaligned tiles, unsupported ops and kernels that
overrun VMEM. Sizes: the paper's 8×512² grid batch (and one 512² grid,
a served batch), 4×1024² dense assignment/matching.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test runner's workers all
import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bfs_relabel.kernel import bfs_relabel_sweeps
from repro.kernels.bidding.kernel import bidding
from repro.kernels.frontier.kernel import frontier
from repro.kernels.grid_push.kernel import (grid_push_decide,
                                            grid_push_decide_sched,
                                            grid_push_round, STRIP_ROWS,
                                            strip_rows, tile_dims)

B, H, W = 8, 512, 512          # chip_smoke.py's maxflow batch
NB, N = 4, 1024                # chip_smoke.py's assignment / matching batch
f32, i32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """``sds(shape, dtype)``: an abstract argument on one described chip.

    The persistent compilation cache stays off while these compile: an
    entry written for a described chip cannot be read back without one.
    """
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _grid_args(sds):
    return (sds((B, H, W), f32), sds((B, H, W), i32),
            sds((4, B, H, W), f32), sds((4, B, H, W), i32),
            sds((B, H, W), f32), sds((B, H, W), f32))


def test_grid_push_decide_compiles(sds):
    text = _compiled_text(
        lambda *a: grid_push_decide(*a, interpret=False),
        *_grid_args(sds), sds((), i32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [B, 1])   # the batch cell, one served grid
def test_grid_push_round_compiles(sds, batch):
    bh = strip_rows(H, W, STRIP_ROWS)
    assert bh is not None and H % bh == 0
    plane = sds((batch, H, W), f32)
    text = _compiled_text(
        lambda *a: grid_push_round(*a, bh=bh, interpret=False),
        plane, sds((batch, H, W), i32), sds((4, batch, H, W), f32), plane,
        plane, sds((), i32))
    assert "tpu_custom_call" in text


def test_grid_push_decide_sched_compiles(sds):
    bh, bw = tile_dims(H, W, 64, 128)
    assert bw % 128 == 0                       # lane-aligned tiles
    T = (H // bh) * (W // bw)
    text = _compiled_text(
        lambda *a: grid_push_decide_sched(*a, block_h=bh, block_w=bw,
                                          interpret=False),
        *_grid_args(sds), sds((B, T), i32), sds((B,), i32), sds((), i32))
    assert "tpu_custom_call" in text


def test_bfs_relabel_sweeps_compiles(sds):
    text = _compiled_text(
        lambda *a: bfs_relabel_sweeps(*a, interpret=False),
        sds((4, B, H, W), f32), *[sds((B, H, W), i32)] * 4)
    assert "tpu_custom_call" in text


def test_frontier_batched_compiles(sds):
    # vmapped, as repro.core.matching.bfs._expand calls it
    text = _compiled_text(
        jax.vmap(lambda a, r, m: frontier(a, r, m, interpret=False)),
        sds((NB, N, N), jnp.bool_), sds((NB, N), i32), sds((NB, N), i32))
    assert "tpu_custom_call" in text


def test_bidding_batched_compiles(sds):
    # vmapped, as repro.core.assignment.cost_scaling's rounds call it
    text = _compiled_text(
        jax.vmap(lambda c, p, m: bidding(c, p, m, interpret=False)),
        sds((NB, N, N), i32), sds((NB, N), i32), sds((NB, N, N), jnp.bool_))
    assert "tpu_custom_call" in text
