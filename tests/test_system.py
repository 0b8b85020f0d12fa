"""End-to-end system tests: train loop behaviour, resume-exactness,
generation, and the dry-run machinery on a tiny in-process mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs.base import get_config, smoke_variant
from repro.data.pipeline import DataConfig, host_batch
from repro.models.layers import Sharder
from repro.models.model import init_model
from repro.optim.adamw import AdamWConfig
from repro.serve.engine import greedy_generate
from repro.train.step import TrainConfig, init_train_state, make_train_step
from repro.checkpoint import store
from repro.roofline import cost_analysis_dict

SHD = Sharder()


def _train(cfg, tcfg, steps, state=None, start=0, seed=0):
    params, axes = init_model(cfg, jax.random.PRNGKey(seed))
    if state is None:
        state = init_train_state(cfg, tcfg, params)
    step_fn = jax.jit(make_train_step(cfg, axes, tcfg, SHD))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=4,
                      seed=seed, copy_prob=0.8)
    losses = []
    for s in range(start, steps):
        b = host_batch(dcfg, s, 0, 1)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    return state, losses


def test_loss_decreases_smollm():
    cfg = smoke_variant(get_config("smollm-135m"))
    tcfg = TrainConfig(optimizer=AdamWConfig(
        lr_peak=3e-3, warmup_steps=5, decay_steps=40))
    _, losses = _train(cfg, tcfg, 30)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses


def test_resume_is_exact(tmp_path):
    """Checkpoint at step 3, resume, and land bit-identically at step 6."""
    cfg = smoke_variant(get_config("smollm-135m"))
    tcfg = TrainConfig(optimizer=AdamWConfig(warmup_steps=2, decay_steps=10))
    state_a, _ = _train(cfg, tcfg, 6)

    state_b, _ = _train(cfg, tcfg, 3)
    store.save(str(tmp_path), 3, state_b)
    restored = store.restore(str(tmp_path), 3, state_b)
    state_c, _ = _train(cfg, tcfg, 6, state=restored, start=3)

    for a, c in zip(jax.tree.leaves(state_a.params),
                    jax.tree.leaves(state_c.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_microbatching_matches_full_batch():
    """grad accumulation over 2 microbatches ~= single big batch step."""
    cfg = smoke_variant(get_config("smollm-135m"))
    t1 = TrainConfig(optimizer=AdamWConfig(warmup_steps=1, decay_steps=10),
                     num_microbatches=1)
    t2 = TrainConfig(optimizer=AdamWConfig(warmup_steps=1, decay_steps=10),
                     num_microbatches=2)
    s1, l1 = _train(cfg, t1, 2)
    s2, l2 = _train(cfg, t2, 2)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


def test_greedy_generate_runs():
    cfg = smoke_variant(get_config("smollm-135m"))
    params, axes = init_model(cfg, jax.random.PRNGKey(0))
    prompts = jnp.ones((2, 8), jnp.int32)
    out = greedy_generate(cfg, params, axes, SHD, prompts, max_new=6)
    assert out.shape == (2, 6)
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab)))


def test_flow_router_trains():
    """MoE with the paper's flow router: losses stay finite and decrease."""
    cfg = smoke_variant(get_config("phi3.5-moe-42b-a6.6b"))
    assert cfg.moe.router == "flow"
    tcfg = TrainConfig(optimizer=AdamWConfig(
        lr_peak=2e-3, warmup_steps=3, decay_steps=25))
    _, losses = _train(cfg, tcfg, 15)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_dryrun_cell_on_host_mesh():
    """The cell-builder machinery lowers on an in-process 1-device mesh."""
    import dataclasses
    from repro.configs import base as cb
    from repro.launch.specs import build_cell
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
    cfg = get_config("smollm-135m")
    tiny = dataclasses.replace(smoke_variant(cfg), name=cfg.name + "-tiny")
    cb._REGISTRY[tiny.name] = tiny
    try:
        cell = build_cell(tiny.name, "train_4k", mesh)
        with mesh:
            lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                              donate_argnums=cell.donate_argnums
                              ).lower(*cell.args)
            compiled = lowered.compile()
        # normalized across jax versions (list-of-dicts vs dict); a train
        # step must report real FLOPs or the roofline numbers are garbage
        cost = cost_analysis_dict(compiled)
        assert cost.get("flops", 0.0) > 0.0, cost
    finally:
        cb._REGISTRY.pop(tiny.name, None)
