"""Compile-only guards for the TPU: the main path's learner kernel and its
chunk episode program, compiled for a described TPU v5e (``v5e:2x2``) by
the TPU compiler that ships with libtpu. Nothing runs and no chip is
needed; a kernel the chip's compiler would refuse fails here.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and pytest-xdist workers import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import DDPGConfig
from repro.core.episode import _compiled_episode, chunk_operands
from repro.core.fleet import FleetAgent
from repro.envs import LustreSimV2
from repro.kernels import ddpg_fused as fused
from repro.kernels import ops

CHUNK = 256       # FleetService's chunk width in the smoke run
UPDATES = 96      # updates per step (paper Table III)
BATCH = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("state_dim,action_dim", [(12, 2), (12, 8)])
def test_learner_kernel_compiles_for_v5e(state_dim, action_dim, one_chip,
                                         no_persistent_cache):
    """``ddpg_fused_learn`` at the paper's 2-D and the magpie8 widths, a
    chunk of 256 sessions x 96 updates x batch 16."""
    dims = fused.packed_dims(state_dim, action_dim, (64, 64))
    p, L = dims.pad, fused.NUM_LAYERS

    def shape(*s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    packed = (shape(CHUNK, 4, L, p, p), shape(CHUNK, 4, L, p),
              shape(CHUNK, 2, 2, L, p, p), shape(CHUNK, 2, 2, L, p),
              shape(CHUNK, 2, dtype=jnp.int32))
    batches = (shape(CHUNK, UPDATES, BATCH, p),
               shape(CHUNK, UPDATES, BATCH, p),
               shape(CHUNK, UPDATES, BATCH, p), shape(CHUNK, UPDATES, BATCH))
    learn = jax.jit(lambda pk, bt: fused.ddpg_fused_learn(
        pk, bt, dims=dims, gamma=0.9, tau=0.02, actor_lr=1e-3,
        critic_lr=2e-3))
    compiled = learn.lower(packed, batches).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_chunk_episode_compiles_for_v5e(one_chip, no_persistent_cache,
                                        monkeypatch):
    """The jitted chunk episode the service runs (magpie8, chunk 256), with
    the learner mode that ``auto`` picks on a TPU."""
    mode = ops.auto_mode("tpu")
    assert mode == "pallas"
    monkeypatch.setenv("REPRO_KERNELS", mode)
    env = LustreSimV2("seq_write", seed=0).to_model_env()
    cfg = DDPGConfig.for_env(env)
    agent = FleetAgent(cfg, [0], buffer_capacity=64, warmup_steps=8,
                       store="host")
    episode = _compiled_episode(env.model.step_fn, env.param_space, cfg,
                                agent._actor_tx, agent._critic_tx, True,
                                cfg.updates_per_step, fleet=True,
                                devices=None)
    shapes = jax.eval_shape(lambda: chunk_operands(env, agent, 2, CHUNK))
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    compiled = episode.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas learner
