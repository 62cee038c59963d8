"""Whole-episode megakernel (kernels/episode_fused.py, roofline/vmem.py) and
the async chunk-staging host runtime.

The pinned equivalence ladder (every bound measured before pinning):

  rung 1  megakernel (Pallas interpret) == its XLA twin, bitwise (maxulp=0):
          the twin IS the kernel body vmapped, so any gap would be a Pallas
          lowering bug;
  rung 2  megakernel through the full Tuner == the scan engine, both under
          ``REPRO_KERNELS=interpret`` (the comparable packed-learner path):
          decision trajectory EXACT and float fields bitwise (maxulp=0,
          measured 0 on the 2-D and the 8-D space for both modes);
  rung 3  megakernel == the pure-jnp oracle (``kernels.ref.
          episode_fused_ref``, jitted): decisions EXACT, episode outputs
          (env state, trace, buffer) <= 4 f32 ulps; the packed learner state
          compares at float32 resolution (``_assert_learner_close``) — the
          cross-formulation Adam-moment amplification documented in
          tests/test_ddpg_fused.py applies verbatim here.

Also pinned: mode=None keys — and IS, by cached-object identity — the exact
pre-megakernel program; composition refusals (guardrails / resilience /
cell sharing / obs masking / multi-device raise instead of silently
degrading); the roofline VMEM-fit check rejects oversized replay windows
with an actionable message; async chunk staging stays bitwise-pure
scheduling and reports its overlap efficiency.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DDPGConfig, FleetTuner, MagpieAgent
from repro.core.episode import _compiled_episode, last_fleet_run_stats
from repro.core.scalarization import metric_bounds
from repro.envs import LustreSimEnv, LustreSimV2
from repro.kernels.ddpg_fused import pack_params, packed_dims
from repro.kernels.episode_fused import (EpisodeKernelSpec, EpisodeOperands,
                                         episode_fused_learn,
                                         episode_fused_xla)
from repro.kernels.ops import episode_kernel_mode
from repro.kernels.ref import episode_fused_ref
from repro.roofline import (TARGET_DEVICE_KIND, check_episode_vmem_fit,
                            episode_vmem_plan, suggest_max_capacity)

from tests.test_ddpg_fused import _assert_learner_close, _max_ulp
from tests.test_episode import _assert_bitwise_equal_runs, _tuner


# ---------------------------------------------------------------------------
# Operand builder: one session's episode inputs straight from a live agent
# ---------------------------------------------------------------------------

def _build(env_cls, seed=3, T=5, U=4, cap=8):
    env = env_cls("seq_write", seed=seed).to_model_env()
    cfg = DDPGConfig.for_env(env, updates_per_step=U)
    agent = MagpieAgent(cfg, seed=seed, warmup_steps=2, buffer_capacity=cap)
    dims = packed_dims(cfg.state_dim, cfg.action_dim, cfg.hidden)
    st = agent.state
    a_adam, c_adam = st.actor_opt[0], st.critic_opt[0]
    packed = pack_params(st.actor, st.critic, st.actor_targ, st.critic_targ,
                         a_adam.mu, a_adam.nu, c_adam.mu, c_adam.nu,
                         a_adam.count, c_adam.count, dims)
    k, m = cfg.state_dim, cfg.action_dim
    rng = np.random.default_rng(seed)
    use_warmup = np.zeros(T, bool)
    use_warmup[: min(2, T)] = True
    warmup = rng.uniform(size=(T, m)).astype(np.float32)
    noise = (rng.normal(size=(T, m)) * 0.1).astype(np.float32)
    lo, span = metric_bounds(env.metric_specs, env.state_metrics)
    w_vec = np.zeros(k, np.float32)
    w_vec[0] = 1.0
    param_leaves, param_def = jax.tree_util.tree_flatten(env.model.params)
    env_leaves, env_def = jax.tree_util.tree_flatten(env.model_state)
    op = EpisodeOperands(
        use_warmup=jnp.asarray(use_warmup), warmup=jnp.asarray(warmup),
        noise=jnp.asarray(noise), w_vec=jnp.asarray(w_vec),
        lo=jnp.asarray(lo), span=jnp.asarray(span),
        params=tuple(jnp.asarray(x) for x in param_leaves),
        env=tuple(jnp.asarray(x) for x in env_leaves),
        packed=tuple(packed),
        buffer=(jnp.zeros((cap, k)), jnp.zeros((cap, m)), jnp.zeros((cap,)),
                jnp.zeros((cap, k)), jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32)),
        learn_key=agent._learn_key,
        state_vec=jnp.full((k,), 0.4, jnp.float32),
        objective=jnp.asarray(0.4, jnp.float32))
    spec = EpisodeKernelSpec(step_fn=env.model.step_fn, space=env.param_space,
                             cfg=cfg, learn=True, num_updates=U, dims=dims,
                             param_treedef=param_def, env_treedef=env_def)
    return op, spec


def _one(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x)[0], tree)


# ---------------------------------------------------------------------------
# Mode resolution + program identity
# ---------------------------------------------------------------------------

def test_episode_kernel_mode_parsing(monkeypatch):
    for off in ("", "off", "0", "none", "OFF"):
        monkeypatch.setenv("REPRO_MEGAKERNEL", off)
        assert episode_kernel_mode() is None
    monkeypatch.delenv("REPRO_MEGAKERNEL")
    assert episode_kernel_mode() is None
    for mode in ("xla", "pallas", "interpret"):
        monkeypatch.setenv("REPRO_MEGAKERNEL", mode)
        assert episode_kernel_mode() == mode
    monkeypatch.setenv("REPRO_MEGAKERNEL", "auto")
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert episode_kernel_mode() == expect
    monkeypatch.setenv("REPRO_MEGAKERNEL", "bogus")
    with pytest.raises(ValueError, match="REPRO_MEGAKERNEL"):
        episode_kernel_mode()


def _episode_args():
    env = LustreSimEnv("seq_write", seed=0).to_model_env()
    cfg = DDPGConfig.for_env(env, updates_per_step=2)
    agent = MagpieAgent(cfg, seed=0)
    return (env.model.step_fn, env.param_space, cfg, agent._actor_tx,
            agent._critic_tx, True, 2)


def test_mode_none_keys_the_exact_pre_megakernel_program(monkeypatch):
    """REPRO_MEGAKERNEL unset and =off key — and ARE, by cached-object
    identity — the same pre-megakernel program; an active mode compiles a
    different one."""
    args = _episode_args()
    monkeypatch.delenv("REPRO_MEGAKERNEL", raising=False)
    fn_unset = _compiled_episode(*args, fleet=True, devices=None)
    monkeypatch.setenv("REPRO_MEGAKERNEL", "off")
    fn_off = _compiled_episode(*args, fleet=True, devices=None)
    assert fn_unset is fn_off
    monkeypatch.setenv("REPRO_MEGAKERNEL", "xla")
    fn_mega = _compiled_episode(*args, fleet=True, devices=None)
    assert fn_mega is not fn_unset


# ---------------------------------------------------------------------------
# Rung 2: megakernel == scan engine through the Tuner (decisions EXACT,
# floats bitwise), 2-D and 8-D, interpret kernel and XLA twin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("env_cls", [LustreSimEnv, LustreSimV2])
def test_megakernel_matches_scan_engine(env_cls, mode, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.delenv("REPRO_MEGAKERNEL", raising=False)
    base = _tuner(env_cls, "scan").run(5)
    monkeypatch.setenv("REPRO_MEGAKERNEL", mode)
    mega = _tuner(env_cls, "scan").run(5)
    _assert_bitwise_equal_runs(base, mega, maxulp=0)


def test_megakernel_progressive_runs_match_scan(monkeypatch):
    """Resumable across run() calls exactly like the scan engine (learner
    state, FIFO, noise streams and env key chain all round-trip through the
    packed layout between runs)."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.delenv("REPRO_MEGAKERNEL", raising=False)
    base = _tuner(LustreSimEnv, "scan", seed=7)
    monkeypatch.setenv("REPRO_MEGAKERNEL", "xla")
    mega = _tuner(LustreSimEnv, "scan", seed=7)
    for steps in (3, 4):
        monkeypatch.delenv("REPRO_MEGAKERNEL", raising=False)
        rb = base.run(steps)
        monkeypatch.setenv("REPRO_MEGAKERNEL", "xla")
        rm = mega.run(steps)
        _assert_bitwise_equal_runs(rb, rm, maxulp=0)
    assert len(mega.history) == 7


# ---------------------------------------------------------------------------
# Rung 1 + 3: kernel vs XLA twin (bitwise) and vs the pure-jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env_cls", [LustreSimEnv, LustreSimV2])
def test_megakernel_bitwise_vs_xla_twin(env_cls):
    op, spec = _build(env_cls)
    opf = jax.tree_util.tree_map(lambda x: x[None], op)
    out_k = _one(episode_fused_learn(opf, spec=spec, interpret=True))
    out_x = _one(episode_fused_xla(opf, spec=spec))
    assert _max_ulp(out_k, out_x) == 0


@pytest.mark.parametrize("env_cls", [LustreSimEnv, LustreSimV2])
def test_megakernel_matches_oracle(env_cls):
    op, spec = _build(env_cls)
    opf = jax.tree_util.tree_map(lambda x: x[None], op)
    out_k = _one(episode_fused_learn(opf, spec=spec, interpret=True))
    ref = jax.jit(lambda o: episode_fused_ref(o, spec=spec))
    out_r = jax.tree_util.tree_map(np.asarray, ref(op))
    # decisions: action indices, restart encodings, key chain — EXACT
    np.testing.assert_array_equal(out_k.action_idx, out_r.action_idx)
    np.testing.assert_array_equal(out_k.restarts, out_r.restarts)
    np.testing.assert_array_equal(out_k.learn_key, out_r.learn_key)
    # episode outputs: the PR 3/4 engine-contract ulp bound (measured <= 1)
    for field in ("env", "buffer", "state_vec", "objective", "metrics",
                  "rewards", "objectives"):
        assert _max_ulp(getattr(out_k, field), getattr(out_r, field)) <= 4, \
            field
    # packed learner state: cross-formulation Adam tolerance (see module
    # docstring / tests.test_ddpg_fused._assert_learner_close)
    _assert_learner_close(out_k.packed, out_r.packed)


def test_store_before_learn_invariant():
    """Step t's transition lands in the FIFO BEFORE step t's learner phase:
    from an empty buffer, a single step's 96.. sampling universe is exactly
    {the just-stored transition}, and the Adam counters advance — pinned by
    exact agreement with the oracle, which stores first by construction."""
    op, spec = _build(LustreSimEnv, T=1, U=3, cap=4)
    opf = jax.tree_util.tree_map(lambda x: x[None], op)
    out_k = _one(episode_fused_learn(opf, spec=spec, interpret=True))
    out_r = jax.tree_util.tree_map(
        np.asarray, jax.jit(lambda o: episode_fused_ref(o, spec=spec))(op))
    assert int(out_k.buffer[5]) == 1          # size: the stored transition
    assert int(out_k.buffer[4]) == 1          # next_slot advanced
    counts = np.asarray(out_k.packed[4])
    np.testing.assert_array_equal(counts, [3, 3])  # U updates ran on it
    np.testing.assert_array_equal(out_k.action_idx, out_r.action_idx)
    _assert_learner_close(out_k.packed, out_r.packed)


def test_padded_lanes_stay_zero_fixed_point():
    """pack_params zeroes the padded lanes; the episode kernel's masked
    GEMMs and the act-mask keep them an exact zero fixed point across all T
    steps and every learner update."""
    op, spec = _build(LustreSimV2, T=4, U=4)
    dims = spec.dims
    opf = jax.tree_util.tree_map(lambda x: x[None], op)
    out_k = _one(episode_fused_learn(opf, spec=spec, interpret=True))
    weights, biases, mom_w, mom_b, _ = out_k.packed
    sizes = (dims.actor_sizes, dims.critic_sizes,
             dims.actor_sizes, dims.critic_sizes)
    w_real = np.zeros(np.asarray(weights).shape, bool)
    b_real = np.zeros(np.asarray(biases).shape, bool)
    for i, sz in enumerate(sizes):
        for layer, (fin, fout) in enumerate(zip(sz[:-1], sz[1:])):
            w_real[i, layer, :fin, :fout] = True
            b_real[i, layer, :fout] = True
    assert np.all(np.asarray(weights)[~w_real] == 0)
    assert np.all(np.asarray(biases)[~b_real] == 0)
    # Adam moments share the nets' real regions (mom[net_pair, mu/nu])
    for pair, (wi, _) in enumerate(((0, 1), (1, 0))):
        for j in range(2):
            assert np.all(np.asarray(mom_w)[pair, j][~w_real[wi]] == 0)
            assert np.all(np.asarray(mom_b)[pair, j][~b_real[wi]] == 0)


# ---------------------------------------------------------------------------
# Composition refusals (megakernel refuses instead of silently degrading)
# ---------------------------------------------------------------------------

def test_megakernel_composition_refusals(monkeypatch):
    from repro.core.guardrails import DeploymentPolicy
    from repro.core.resilience import ResiliencePolicy
    from repro.core.sharing import SharingConfig

    monkeypatch.setenv("REPRO_MEGAKERNEL", "xla")
    args = _episode_args()
    with pytest.raises(ValueError, match="REPRO_MEGAKERNEL=off"):
        _compiled_episode(*args, fleet=True, devices=None,
                          policy=DeploymentPolicy(min_gain=0.01))
    with pytest.raises(ValueError, match="REPRO_MEGAKERNEL=off"):
        _compiled_episode(*args, fleet=True, devices=None,
                          resilience=ResiliencePolicy())
    with pytest.raises(ValueError, match="REPRO_MEGAKERNEL=off"):
        _compiled_episode(*args, fleet=True, devices=None,
                          sharing=SharingConfig(shared_replay=True),
                          cell_size=2)
    with pytest.raises(ValueError, match="observation masking"):
        _compiled_episode(*args, fleet=True, devices=None,
                          obs_mask=(1.0, 0.0, 1.0))
    dev = jax.devices()[0]
    with pytest.raises(ValueError, match="single-device"):
        _compiled_episode(*args, fleet=True, devices=(dev, dev))


# ---------------------------------------------------------------------------
# Roofline VMEM-fit check
# ---------------------------------------------------------------------------

_FIT_KW = dict(steps=5, state_dim=8, action_dim=8, hidden=(64, 64),
               num_updates=96, batch_size=16, pad=128,
               device_kind=TARGET_DEVICE_KIND)


def test_vmem_fit_rejects_oversized_capacity():
    with pytest.raises(ValueError) as err:
        check_episode_vmem_fit(chunk=8, capacity=300_000, **_FIT_KW)
    msg = str(err.value)
    assert "replay_window" in msg
    assert "shrink buffer capacity" in msg
    assert "REPRO_MEGAKERNEL=off" in msg
    assert "chunk=8" in msg  # names the launch the caller asked for


def test_vmem_fit_accepts_and_suggests():
    plan = check_episode_vmem_fit(chunk=8, capacity=64, **_FIT_KW)
    assert plan["fits"]
    cap = suggest_max_capacity(**_FIT_KW)
    assert cap > 64
    assert episode_vmem_plan(capacity=cap, **_FIT_KW)["fits"]
    assert not episode_vmem_plan(capacity=cap + 1000, **_FIT_KW)["fits"]


def test_megakernel_rejects_oversized_episode_end_to_end():
    op, spec = _build(LustreSimEnv, cap=300_000)
    opf = jax.tree_util.tree_map(lambda x: x[None], op)
    with pytest.raises(ValueError, match="does not fit in VMEM"):
        episode_fused_learn(opf, spec=spec, interpret=True)


# ---------------------------------------------------------------------------
# Async chunk staging: stats recorded, scheduling stays bitwise-pure
# ---------------------------------------------------------------------------

def _staging_fleet(overlap):
    cfg = DDPGConfig.for_env(LustreSimEnv("seq_write"), updates_per_step=4)
    f = FleetTuner.from_grid(
        ["seq_write"], [{"throughput": 1.0}], [0, 1, 2, 3],
        engine="scan", ddpg_config=cfg, eval_runs=1, warmup_steps=3,
        chunk=2)
    f.overlap = overlap
    return f


def test_async_staging_stats_and_bitwise_purity():
    r_off = _staging_fleet(False).run(4)
    st_off = last_fleet_run_stats()["staging"]
    assert st_off["async"] is False
    r_on = _staging_fleet(True).run(4)
    st_on = last_fleet_run_stats()["staging"]
    assert st_on["async"] is True
    assert st_on["stage_seconds"] > 0.0
    assert 0.0 <= st_on["overlap_efficiency"] <= 1.0
    assert st_on["stage_wait_seconds"] >= 0.0
    # async staging + async drain prefetch are pure scheduling: bitwise
    for a, b in zip(r_on.results, r_off.results):
        _assert_bitwise_equal_runs(a, b, maxulp=0)


def test_memory_plan_counts_inflight_staging_chunk():
    """overlap_device_bytes bounds the ASYNC schedule: computing chunk k +
    staged-in-flight k+1 + draining k-1 = three chunks, not two."""
    from repro.core.fleet import memory_plan
    cfg = DDPGConfig.for_env(LustreSimEnv("seq_write"))
    plan = memory_plan(cfg, LustreSimEnv("seq_write").param_space,
                       sessions=64, steps=5, chunk=16)
    assert plan["overlap_device_bytes"] == 3 * plan["chunk_device_bytes"]


# ---------------------------------------------------------------------------
# benchmarks/run.py CLI (satellite: --list + unknown --only)
# ---------------------------------------------------------------------------

def _run_bench_cli(*argv):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *argv],
        capture_output=True, text=True, cwd=root, env=env, timeout=300)


def test_bench_run_list_prints_targets():
    r = _run_bench_cli("--list")
    assert r.returncode == 0, r.stderr
    for name in ("megakernel", "scaling", "fleet"):
        assert name in r.stdout


def test_bench_run_unknown_only_exits_nonzero():
    r = _run_bench_cli("--only", "not-a-bench", "--no-bench-json")
    assert r.returncode == 2
    assert "not-a-bench" in r.stderr
    assert "megakernel" in r.stderr  # the error lists valid targets
