"""A run with its timed path broken underneath reads not correct.

Each fault is planted in the program's own batched entry points, below
``solve_batch`` and the serving engine, and the whole run is driven as
the command drives it, minus the look for a chip. The sharded cell
(one of ``conftest.QUEUED_CELLS``) runs in a fresh process on four
virtual CPU devices; there the exchange between chips is the gathering
of every shard's answers, and its fault returns the first shard's
answers in every shard's place.
"""
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import repro.core.assignment.cost_scaling as cost_scaling
import repro.core.batch as batch_mod
import repro.core.maxflow.grid as grid
import repro.launch.mesh as mesh_mod
from bench import harness

SEED = 2 ** 31 + 99
ENTRY = {"maxflow": "maxflow_grid_batch", "assignment": "solve_assignment"}
LOOP_MODULE = {"maxflow": grid, "assignment": cost_scaling}
REPO = pathlib.Path(__file__).resolve().parents[2]


def run(root, workload, **kw):
    cell = harness.load_cell(root, workload)
    return harness.run_cell(cell, SEED, 1.0, False, time.monotonic(),
                            log=lambda *a: None, **kw)


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()           # a planted fault must be traced anew
    yield
    jax.clear_caches()


def kind_of(workload):
    return "assignment" if workload.startswith("dense_assign") else "maxflow"


def unchanged(spec, state, batch_shape):
    """The solver loop returns its state as it found it."""
    return state, jnp.zeros(batch_shape, jnp.int32)


def half_batch(orig, problem, **kw):
    """Only the first half of the batch is solved; the rest repeat it."""
    b = jax.tree.leaves(problem)[0].shape[0]
    h = max(1, b // 2)
    res = orig(jax.tree.map(lambda a: a[:h], problem), **kw)
    idx = jnp.arange(b) % h
    return jax.tree.map(lambda a: a[idx], res)


def altered(orig, problem, **kw):
    """One answer of every batch altered where it is produced."""
    res = orig(problem, **kw)
    if hasattr(res, "flow"):
        return res._replace(flow=res.flow.at[0].add(1.0))
    col = res.col_of_row
    return res._replace(col_of_row=col.at[0, 0].set(col[0, 1])
                        .at[0, 1].set(col[0, 0]))


def one_shard(orig, impl, args, batch_size, mesh, mesh_axis, **kw):
    """The first shard's answers stand in every shard's place."""
    res = orig(impl, args, batch_size, mesh, mesh_axis, **kw)
    per = batch_size // mesh_mod.shard_count(mesh, mesh_axis)
    idx = jnp.arange(batch_size) % per
    return jax.tree.map(lambda a: a[idx], res)


def plant(setattr_, kind, fault):
    """Plant ``fault`` by name through ``setattr_``; returns the extra
    keyword arguments of the run (the control is a run setting)."""
    if fault == "control":
        return {"control": True}
    if fault == "unchanged":
        setattr_(LOOP_MODULE[kind], "run_masked", unchanged)
    elif fault == "one_shard":
        orig = mesh_mod.dispatch_sharded
        setattr_(mesh_mod, "dispatch_sharded",
                 lambda *a, **kw: one_shard(orig, *a, **kw))
    else:
        wrap = {"half_batch": half_batch, "altered": altered}[fault]
        orig = getattr(batch_mod, ENTRY[kind])
        setattr_(batch_mod, ENTRY[kind],
                 lambda problem, **kw: wrap(orig, problem, **kw))
    return {}


CELLS = ["grid_cut_512.batch", "dense_assign_1024.batch",
         "grid_cut_512.served"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_reads_not_correct(tiny_root, workload):
    res = run(tiny_root, workload, control=True)
    assert res["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_state_returned_unchanged_reads_not_correct(tiny_root, workload,
                                                    monkeypatch):
    plant(monkeypatch.setattr, kind_of(workload), "unchanged")
    assert run(tiny_root, workload)["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_half_batch_left_out_reads_not_correct(tiny_root, workload,
                                               monkeypatch):
    plant(monkeypatch.setattr, kind_of(workload), "half_batch")
    assert run(tiny_root, workload)["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_answer_altered_reads_not_correct(tiny_root, workload, monkeypatch):
    plant(monkeypatch.setattr, kind_of(workload), "altered")
    res = run(tiny_root, workload)
    assert res["correct"] is False
    gaps = {k: v["value"] for k, v in res["checks"].items()}
    assert gaps.get("flow_gap", 0) > 0 or gaps.get("weight_gap", 0) > 0 \
        or gaps.get("reported_gap", 0) > 0


MESH_RUN = """
import pathlib, sys, time
sys.path[:0] = [{repo!r}, {src!r}, {tests!r}]
from conftest import copy_benchmark
import test_faults as tf
from bench import harness
root = copy_benchmark(pathlib.Path({tmp!r}) / "c")
kw = tf.plant(setattr, "maxflow", {fault!r}) if {fault!r} else {{}}
cell = harness.load_cell(root, "grid_cut_512.batch.mesh4")
res = harness.run_cell(cell, tf.SEED, 1.0, False, time.monotonic(),
                       log=lambda *a: None, **kw)
print("RESULT", res["correct"], res["attempted"], res["device"]["count"])
"""


def run_sharded(tmp_path, fault):
    """The sharded cell's whole run on four virtual CPU devices (a fresh
    process, since the device count is fixed when JAX starts):
    ``(correct, attempted, device count)``."""
    code = MESH_RUN.format(repo=str(REPO), src=str(REPO / "src"),
                           tests=str(pathlib.Path(__file__).parent),
                           tmp=str(tmp_path), fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    [line] = [x for x in p.stdout.splitlines() if x.startswith("RESULT")]
    correct, attempted, count = line.split()[1:]
    return correct == "True", int(attempted), int(count)


def test_sharded_cell_reads_correct(tmp_path):
    correct, attempted, count = run_sharded(tmp_path, "")
    assert correct and count == 4
    assert attempted > 0 and attempted % 8 == 0   # 4 chips x the batch of 2


@pytest.mark.parametrize("fault", ["control", "unchanged", "half_batch",
                                   "one_shard", "altered"])
def test_sharded_cell_fault_reads_not_correct(tmp_path, fault):
    correct, attempted, count = run_sharded(tmp_path, fault)
    assert not correct and count == 4 and attempted > 0
