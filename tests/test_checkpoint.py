"""Checkpointing + fault tolerance: atomic roundtrip, resume-equivalence,
simulated node failure, keep-k GC."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.data import DataConfig, synthetic_batch
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.training import (
    AdamWConfig,
    FaultConfig,
    init_train_state,
    latest_step,
    make_train_step,
    restore,
    run_resumable,
    save,
    wait_pending,
)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(smoke_config(get_config("gemma-2b")),
                              compute_dtype="float32", num_layers=2,
                              layer_pattern=(0, 0))
    api = build_model(cfg, remat=False)
    step = jax.jit(make_train_step(
        api.loss_fn, AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=40)))
    dcfg = DataConfig(seq_len=16, global_batch=4)
    return cfg, api, step, dcfg


def test_roundtrip(tmp_path, setup):
    cfg, api, step, dcfg = setup
    params = api.init_params(jax.random.PRNGKey(0))
    state = init_train_state(params)
    save(str(tmp_path), 7, state)
    assert latest_step(str(tmp_path)) == 7
    like = init_train_state(api.init_params(jax.random.PRNGKey(1)))
    back = restore(str(tmp_path), 7, like)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_k_gc(tmp_path, setup):
    cfg, api, step, dcfg = setup
    state = init_train_state(api.init_params(jax.random.PRNGKey(0)))
    for s in (1, 2, 3, 4, 5):
        save(str(tmp_path), s, state, keep=2)
    dirs = sorted(os.listdir(tmp_path))
    assert dirs == ["step_00000004", "step_00000005"]


def test_resume_equals_uninterrupted(tmp_path, setup):
    """Crash + restart reproduces the uninterrupted trajectory exactly
    (deterministic data pipeline + exact state restore)."""
    cfg, api, step, dcfg = setup

    def init_state():
        return init_train_state(api.init_params(jax.random.PRNGKey(0)))

    def batch_fn(s):
        return synthetic_batch(cfg, dcfg, s)

    # uninterrupted: 10 steps
    ref_state = init_state()
    for s in range(10):
        ref_state, _ = step(ref_state, batch_fn(s))

    # interrupted at step 6 (after a checkpoint at step 5), then resumed
    fault = FaultConfig(ckpt_dir=str(tmp_path / "ft"), save_every=5, max_steps=10)
    with pytest.raises(RuntimeError, match="simulated node failure"):
        run_resumable(fault, init_state, step, batch_fn, fail_at_step=6)
    wait_pending()
    assert latest_step(fault.ckpt_dir) == 5
    state, steps_run, _ = run_resumable(fault, init_state, step, batch_fn)
    assert steps_run == 5  # resumed from 5 → 10
    for a, b in zip(jax.tree.leaves(ref_state.params), jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)


def test_atomic_no_tmp_left(tmp_path, setup):
    cfg, api, step, dcfg = setup
    state = init_train_state(api.init_params(jax.random.PRNGKey(0)))
    save(str(tmp_path), 1, state)
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_restore_respects_shardings(tmp_path, setup):
    """Elastic-rescale path: restore onto explicit (1-device) shardings."""
    cfg, api, step, dcfg = setup
    params = api.init_params(jax.random.PRNGKey(0))
    save(str(tmp_path), 3, params)
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
    back = restore(str(tmp_path), 3, params, shardings=sh)
    assert all(x.sharding == NamedSharding(mesh, P())
               for x in jax.tree.leaves(back))
