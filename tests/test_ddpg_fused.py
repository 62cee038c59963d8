"""Fused DDPG learner: Pallas kernel (interpret mode) vs the kernels/ref.py
oracle vs the XLA ``ddpg_learn_scan`` — plus the pre-gather and empty-buffer
regression suites.

Equivalence contract: decision-relevant fields — Adam step counts, the
learner ``step``, sampled minibatch indices — are EXACT across every path.
Float fields: kernel vs oracle (same packed formulation) stays within the
PR 3 <= 4 ulp bound; kernel vs the unpadded ``ddpg_learn_scan`` (different
GEMM formulations) holds relative error at float32 resolution — see
``_assert_learner_close`` for why a raw ulp bound is the wrong metric
across formulations. Both the paper's 2-D space shape and the 8-knob shape
are covered.
"""

import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DDPGConfig, MagpieAgent
from repro.core.ddpg import (
    _ddpg_step,
    ddpg_init,
    ddpg_learn_scan,
    fleet_init,
    fleet_learn_scan,
    gather_minibatches,
    sample_minibatch_indices,
)
from repro.kernels import ddpg_fused as fused
from repro.kernels import ref

# (state_dim, action_dim): the paper's 2-D space and the 8-knob space
DIMS = [(12, 2), (12, 8)]


def _storage(rng, cap, state_dim, action_dim):
    return (rng.random((cap, state_dim)).astype(np.float32),
            rng.random((cap, action_dim)).astype(np.float32),
            rng.standard_normal(cap).astype(np.float32),
            rng.random((cap, state_dim)).astype(np.float32))


def _max_ulp(tree_a, tree_b) -> int:
    """Largest float32 ulp distance across float leaves; int leaves must be
    exactly equal (the decision-relevant part of the contract)."""
    worst = 0
    for a, b in zip(jax.tree_util.tree_leaves(tree_a),
                    jax.tree_util.tree_leaves(tree_b)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.float32:
            ai = a.view(np.int32).astype(np.int64)
            bi = b.view(np.int32).astype(np.int64)
            worst = max(worst, int(np.abs(ai - bi).max()))
        else:
            np.testing.assert_array_equal(a, b)
    return worst


def _assert_learner_close(tree_a, tree_b):
    """Cross-formulation learner tolerance: int leaves (Adam counts, step)
    exact; float leaves allclose at float32 resolution (rtol 1e-5).

    The padded kernel and the unpadded scan compute each GEMM within ~1 ulp
    of each other, but Adam's early-step denominators (sqrt(nu) + eps with
    nu near zero) amplify that to tens of ulps on weights whose magnitude is
    ~1e-4 after a handful of updates — a few e-10 absolute. The strict <= 4
    ulp bound of the PR 3 engine contract applies to same-formulation
    comparisons (kernel vs oracle below); across formulations the honest
    bound is relative error at float32 resolution."""
    for a, b in zip(jax.tree_util.tree_leaves(tree_a),
                    jax.tree_util.tree_leaves(tree_b)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.float32:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
        else:
            np.testing.assert_array_equal(a, b)


def _packed_inputs(cfg, size, seed=0, num_updates=8):
    """(packed params, packed pre-gathered batches, dims) for direct
    kernel/oracle calls — single session, no fleet axis."""
    state, _ = ddpg_init(jax.random.PRNGKey(seed), cfg)
    data = _storage(np.random.default_rng(seed + 1), 32, cfg.state_dim,
                    cfg.action_dim)
    dims = fused.packed_dims(cfg.state_dim, cfg.action_dim, cfg.hidden)
    a_adam, c_adam = state.actor_opt[0], state.critic_opt[0]
    packed = fused.pack_params(
        state.actor, state.critic, state.actor_targ, state.critic_targ,
        a_adam.mu, a_adam.nu, c_adam.mu, c_adam.nu,
        a_adam.count, c_adam.count, dims)
    idx = sample_minibatch_indices(jax.random.PRNGKey(seed + 2), num_updates,
                                   cfg.batch_size, jnp.asarray(size))
    batches = fused.pack_minibatches(gather_minibatches(data, idx), dims)
    return packed, batches, dims


# ---------------------------------------------------------------------------
# Satellite: hoisted minibatch gathers (bitwise vs the per-update path)
# ---------------------------------------------------------------------------

def test_gather_minibatches_bitwise_vs_per_update_indexing():
    rng = np.random.default_rng(0)
    data = _storage(rng, 32, 12, 2)
    idx = np.asarray(sample_minibatch_indices(jax.random.PRNGKey(1), 12, 16,
                                              jnp.asarray(20)))
    got = gather_minibatches(tuple(jnp.asarray(x) for x in data),
                             jnp.asarray(idx))
    for g, x in zip(got, data):
        want = np.stack([x[ix] for ix in idx])
        np.testing.assert_array_equal(np.asarray(g), want)


@pytest.mark.parametrize("state_dim,action_dim", DIMS)
def test_learn_scan_pregather_bitwise_vs_per_update_gather(state_dim,
                                                           action_dim,
                                                           monkeypatch):
    """The hoisted single-take learner == the old gather-per-update scan,
    bitwise: gathers are exact and the update arithmetic is untouched.
    This is the XLA path's contract — pin the default mode so the test
    means the same thing inside the REPRO_KERNELS=interpret CI lane."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    cfg = DDPGConfig(state_dim=state_dim, action_dim=action_dim)
    state, (atx, ctx) = ddpg_init(jax.random.PRNGKey(0), cfg)
    data = _storage(np.random.default_rng(0), 32, state_dim, action_dim)
    key, size, updates = jax.random.PRNGKey(42), 20, 10

    new_state, new_ms = ddpg_learn_scan(state, data, size, key, cfg, atx,
                                        ctx, updates)

    s, a, r, s2 = (jnp.asarray(x) for x in data)

    @jax.jit
    def legacy(state):
        idx = sample_minibatch_indices(key, updates, cfg.batch_size,
                                       jnp.asarray(size))

        def body(st, ix):
            return _ddpg_step(st, (s[ix], a[ix], r[ix], s2[ix]),
                              cfg, atx, ctx)

        return jax.lax.scan(body, state, idx)

    old_state, old_ms = legacy(state)
    assert _max_ulp(new_state, old_state) == 0
    assert _max_ulp(new_ms, old_ms) == 0


# ---------------------------------------------------------------------------
# Tentpole: kernel vs oracle vs ddpg_learn_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("state_dim,action_dim", DIMS)
def test_kernel_interpret_matches_ref_oracle(state_dim, action_dim):
    cfg = DDPGConfig(state_dim=state_dim, action_dim=action_dim)
    packed, batches, dims = _packed_inputs(cfg, size=20)

    with_n = jax.tree_util.tree_map(lambda x: x[None], (packed, batches))
    k_packed, k_ms = fused.ddpg_fused_learn(
        *with_n, dims=dims, gamma=cfg.gamma, tau=cfg.tau,
        actor_lr=cfg.actor_lr, critic_lr=cfg.critic_lr, interpret=True)
    k_packed, k_ms = jax.tree_util.tree_map(lambda x: x[0], (k_packed, k_ms))

    r_packed, r_ms = ref.ddpg_fused_ref(
        packed, batches, state_dim=state_dim, action_dim=action_dim,
        pad=dims.pad, gamma=cfg.gamma, tau=cfg.tau,
        actor_lr=cfg.actor_lr, critic_lr=cfg.critic_lr)

    assert _max_ulp(k_packed, r_packed) <= 4
    assert _max_ulp(k_ms, r_ms) <= 4


@pytest.mark.parametrize("state_dim,action_dim", DIMS)
def test_kernel_xla_twin_matches_ref_oracle(state_dim, action_dim):
    """The blocked-GEMM XLA twin (the kernel's fallback formulation) agrees
    with the oracle too — the packed computation is backend-independent."""
    cfg = DDPGConfig(state_dim=state_dim, action_dim=action_dim)
    packed, batches, dims = _packed_inputs(cfg, size=20)

    with_n = jax.tree_util.tree_map(lambda x: x[None], (packed, batches))
    x_packed, x_ms = fused.ddpg_fused_xla(
        *with_n, dims=dims, gamma=cfg.gamma, tau=cfg.tau,
        actor_lr=cfg.actor_lr, critic_lr=cfg.critic_lr)
    x_packed, x_ms = jax.tree_util.tree_map(lambda x: x[0], (x_packed, x_ms))

    r_packed, r_ms = ref.ddpg_fused_ref(
        packed, batches, state_dim=state_dim, action_dim=action_dim,
        pad=dims.pad, gamma=cfg.gamma, tau=cfg.tau,
        actor_lr=cfg.actor_lr, critic_lr=cfg.critic_lr)

    assert _max_ulp(x_packed, r_packed) <= 4
    assert _max_ulp(x_ms, r_ms) <= 4


@pytest.mark.parametrize("state_dim,action_dim", DIMS)
def test_kernel_path_matches_learn_scan(state_dim, action_dim, monkeypatch):
    """REPRO_KERNELS=interpret routes ddpg_learn_scan through the Pallas
    kernel; result within the ulp contract of the XLA scan, counts exact."""
    cfg = DDPGConfig(state_dim=state_dim, action_dim=action_dim)
    state, (atx, ctx) = ddpg_init(jax.random.PRNGKey(0), cfg)
    data = _storage(np.random.default_rng(1), 32, state_dim, action_dim)
    key = jax.random.PRNGKey(7)

    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    x_state, x_ms = ddpg_learn_scan(state, data, 20, key, cfg, atx, ctx, 8)
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    k_state, k_ms = ddpg_learn_scan(state, data, 20, key, cfg, atx, ctx, 8)

    assert int(k_state.step) == int(x_state.step) == 8
    assert int(k_state.actor_opt[0].count) == 8
    _assert_learner_close(k_state, x_state)
    _assert_learner_close(k_ms, x_ms)


def test_fleet_kernel_grid_matches_xla(monkeypatch):
    """The fleet entry runs the kernel gridded over sessions (via the vmap
    batching rule); every session stays within the ulp contract."""
    cfg = DDPGConfig(state_dim=12, action_dim=2)
    n = 3
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(n)])
    states, (atx, ctx) = fleet_init(keys, cfg)
    rng = np.random.default_rng(2)
    data = tuple(np.stack(xs) for xs in zip(
        *[_storage(rng, 16, 12, 2) for _ in range(n)]))
    sizes = jnp.full((n,), 10, jnp.int32)
    lkeys = jnp.stack([jax.random.PRNGKey(s + 3) for s in range(n)])

    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    x_states, _ = fleet_learn_scan(states, data, sizes, lkeys, cfg, atx, ctx,
                                   6)
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    k_states, _ = fleet_learn_scan(states, data, sizes, lkeys, cfg, atx, ctx,
                                   6)
    _assert_learner_close(k_states, x_states)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("kernels", [None, "auto", "xla", "pallas",
                                     "interpret"])
def test_learner_mode_resolution(platform, kernels, monkeypatch):
    """``auto`` (or unset) runs the XLA learner on every platform, a TPU
    included; ``pallas`` and ``interpret`` name the kernel anywhere. The
    other kernels' ``auto`` still picks Pallas on a TPU."""
    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if kernels is None:
        monkeypatch.delenv("REPRO_KERNELS", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNELS", kernels)
    explicit = kernels in ("pallas", "interpret")
    assert ops.ddpg_kernel_mode() == (kernels if explicit else None)
    if kernels in (None, "auto"):
        assert ops._mode() == ("pallas" if platform == "tpu" else "xla")
    else:
        assert ops._mode() == kernels


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_fleet_learn_auto_is_the_xla_program(platform, monkeypatch):
    """Under ``auto`` the fleet learner is the program ``xla`` runs, on a
    TPU too: bitwise the same states and metrics."""
    cfg = DDPGConfig(state_dim=12, action_dim=8)
    n = 3
    states, (atx, ctx) = fleet_init(
        jnp.stack([jax.random.PRNGKey(s) for s in range(n)]), cfg)
    rng = np.random.default_rng(4)
    data = tuple(np.stack(xs) for xs in zip(
        *[_storage(rng, 16, 12, 8) for _ in range(n)]))
    sizes = jnp.full((n,), 12, jnp.int32)
    lkeys = jnp.stack([jax.random.PRNGKey(s + 5) for s in range(n)])
    monkeypatch.setattr(jax, "default_backend", lambda: platform)

    monkeypatch.setenv("REPRO_KERNELS", "xla")
    x_out = fleet_learn_scan(states, data, sizes, lkeys, cfg, atx, ctx, 6)
    monkeypatch.setenv("REPRO_KERNELS", "auto")
    a_out = fleet_learn_scan(states, data, sizes, lkeys, cfg, atx, ctx, 6)
    assert int(np.min(a_out[0].step)) == 6
    assert _max_ulp(a_out, x_out) == 0


def test_padded_lanes_stay_zero():
    """Zero padding is a fixed point of the whole inner loop: weights, Adam
    moments and Polyak targets keep exact zeros in every padded row/column
    after many updates (the invariant that makes the packed layout sound)."""
    cfg = DDPGConfig(state_dim=12, action_dim=2)
    packed, batches, dims = _packed_inputs(cfg, size=20, num_updates=16)
    with_n = jax.tree_util.tree_map(lambda x: x[None], (packed, batches))
    (w, b, mw, mb, _), _ = fused.ddpg_fused_learn(
        *with_n, dims=dims, gamma=cfg.gamma, tau=cfg.tau,
        actor_lr=cfg.actor_lr, critic_lr=cfg.critic_lr, interpret=True)
    k, m, p = dims.state_dim, dims.action_dim, dims.pad
    # actor & actor_targ: input rows >= k, head columns >= m
    for net in (0, 2):
        assert not np.any(np.asarray(w[0, net, 0, k:, :]))
        assert not np.any(np.asarray(w[0, net, 2, :, m:]))
        assert not np.any(np.asarray(b[0, net, 2, m:]))
    # critic & critic_targ: input rows >= k+m, head columns >= 1
    for net in (1, 3):
        assert not np.any(np.asarray(w[0, net, 0, k + m:, :]))
        assert not np.any(np.asarray(w[0, net, 2, :, 1:]))
        assert not np.any(np.asarray(b[0, net, 2, 1:]))
    # Adam moments inherit the zeros (exactly-zero grads on padding)
    assert not np.any(np.asarray(mw[0, 0, :, 0, k:, :]))
    assert not np.any(np.asarray(mw[0, 1, :, 0, k + m:, :]))
    assert not np.any(np.asarray(mb[0, 0, :, 2, m:]))


def test_agent_learn_routes_through_kernel(monkeypatch):
    """End-to-end dispatch: MagpieAgent.learn under REPRO_KERNELS=interpret
    mutates the learner like the default path, within the ulp contract."""
    def run(mode):
        if mode:
            monkeypatch.setenv("REPRO_KERNELS", mode)
        else:
            monkeypatch.delenv("REPRO_KERNELS", raising=False)
        cfg = DDPGConfig(state_dim=3, action_dim=2, updates_per_step=6)
        agent = MagpieAgent(cfg, seed=0)
        rng = np.random.default_rng(0)
        for _ in range(12):
            agent.observe(rng.random(3).astype(np.float32),
                          rng.random(2).astype(np.float32),
                          float(rng.standard_normal() * 0.1),
                          rng.random(3).astype(np.float32))
        metrics = agent.learn()
        return agent.state, metrics

    x_state, x_metrics = run(None)
    k_state, k_metrics = run("interpret")
    _assert_learner_close(k_state, x_state)
    assert set(k_metrics) == set(x_metrics)
    for key in x_metrics:
        np.testing.assert_allclose(k_metrics[key], x_metrics[key],
                                   rtol=1e-5, atol=1e-6)


def test_episode_scan_engine_runs_on_kernel_learner(monkeypatch):
    """The fused episode engine compiles and runs with the Pallas learner in
    its scan body (scan + vmap over pallas_call), and a mode flip recompiles
    instead of reusing the other path's program (cache-key regression)."""
    from repro.core import Scalarizer, Tuner
    from repro.envs import LustreSimEnv

    def run(mode):
        if mode:
            monkeypatch.setenv("REPRO_KERNELS", mode)
        else:
            monkeypatch.delenv("REPRO_KERNELS", raising=False)
        env = LustreSimEnv("seq_write", seed=0).to_model_env()
        scal = Scalarizer(weights={"throughput": 1.0},
                          specs=env.metric_specs)
        agent = MagpieAgent(DDPGConfig.for_env(env, updates_per_step=4),
                            seed=0)
        return Tuner(env, scal, agent, eval_runs=1, engine="scan").run(3)

    base = run(None)
    got = run("interpret")
    # the kernel learner's ulp-level drift may nudge float fields, but the
    # run must produce the same shape of result on the same step budget
    assert len(got.history) == len(base.history) == 3
    assert set(got.best_config) == set(base.best_config)
    assert np.isfinite(got.best_objective)


# ---------------------------------------------------------------------------
# Satellite: the empty-buffer (silent zero-index) hazard
# ---------------------------------------------------------------------------

def test_learn_scan_raises_on_empty_buffer():
    cfg = DDPGConfig(state_dim=3, action_dim=2)
    state, (atx, ctx) = ddpg_init(jax.random.PRNGKey(0), cfg)
    data = _storage(np.random.default_rng(0), 8, 3, 2)
    with pytest.raises(ValueError, match="empty replay buffer"):
        ddpg_learn_scan(state, data, 0, jax.random.PRNGKey(1), cfg, atx,
                        ctx, 4)


def test_fleet_learn_scan_raises_on_any_empty_session():
    cfg = DDPGConfig(state_dim=3, action_dim=2)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(2)])
    states, (atx, ctx) = fleet_init(keys, cfg)
    rng = np.random.default_rng(0)
    data = tuple(np.stack(xs) for xs in zip(
        *[_storage(rng, 8, 3, 2) for _ in range(2)]))
    lkeys = jnp.stack([jax.random.PRNGKey(s + 3) for s in range(2)])
    with pytest.raises(ValueError, match="empty replay buffer"):
        fleet_learn_scan(states, data, jnp.asarray([4, 0]), lkeys, cfg,
                         atx, ctx, 4)


def test_agent_learn_on_empty_buffer_is_guarded_noop():
    agent = MagpieAgent(DDPGConfig(state_dim=3, action_dim=2), seed=0)
    before = jax.tree_util.tree_map(np.asarray, agent.state)
    assert agent.learn() == {}
    assert _max_ulp(agent.state, before) == 0


def test_sample_minibatch_indices_in_range_without_clamp():
    idx = np.asarray(sample_minibatch_indices(jax.random.PRNGKey(0), 50, 16,
                                              jnp.asarray(1)))
    assert idx.min() == idx.max() == 0  # size 1: only slot 0 is valid
    idx = np.asarray(sample_minibatch_indices(jax.random.PRNGKey(0), 50, 16,
                                              jnp.asarray(5)))
    assert idx.min() >= 0 and idx.max() < 5


# ---------------------------------------------------------------------------
# Satellite: BENCH_<n>.json numbering
# ---------------------------------------------------------------------------

def test_bench_json_numbering_appends_next_free_index(tmp_path):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    try:
        from benchmarks.run import _write_bench_json
    finally:
        sys.path.pop(0)
    p0 = _write_bench_json({"benchmark": "episode_engine", "x": 1},
                           root=str(tmp_path))
    p1 = _write_bench_json({"benchmark": "episode_engine", "x": 2},
                           root=str(tmp_path))
    assert os.path.basename(p0) == "BENCH_0.json"
    assert os.path.basename(p1) == "BENCH_1.json"
    import json
    with open(p1) as f:
        assert json.load(f)["x"] == 2
