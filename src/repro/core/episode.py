"""Whole-episode engine: the Fig. 1 loop as ONE compiled XLA program.

``core.tuner.Tuner`` steps the loop from Python: every tuning step crosses the
host boundary to act, apply the config, scalarize the reward, store the
transition and learn. This module fuses all of it — act → env step → reward
scalarization → buffer store → ``ddpg_learn_scan`` — into a single jitted
``lax.scan`` over the episode (``run_episode_scan``), and vmaps/shards the same
body over a fleet session axis (``run_fleet_episode_scan``), so a seeds ×
workloads × objectives grid runs as one device computation.

Fleet episodes execute as a STREAM of fixed-size chunks:

  * ``run_fleet_episode_scan(..., chunk=C)`` runs the N-session fleet as
    ``ceil(N / C)`` chunks of exactly C sessions through ONE compiled,
    donated episode program. Every chunk of every grid shape reuses the same
    executable (shape bucketing: the compiled shape is ``[C, ...]``, never
    ``[N, ...]``); a ragged last chunk is padded by replicating its own last
    session and the padded rows are sliced off before anything reads them.
  * Between chunks the fleet's state (learner params/opt state, FIFO replay,
    env states) lives in host numpy buffers; each chunk's slice is staged to
    the device, the episode runs, and the returned carry + trace stream back
    into preallocated host buffers. Peak device memory is O(C·T) — one
    chunk's state and trace — instead of O(N·T).
  * The trace is stored compactly: actions as per-knob quantization indices
    (knobs are quantized by construction — ``ParamSpace.index_dtype``,
    usually uint8 instead of float32 per coordinate) and restart seconds as
    int32 fixed point (exact for every cost the env models emit; see
    ``RESTART_FP_SCALE``). Metric/reward/objective floats stay float32.

Equivalence contract (pinned by tests/test_episode.py and
tests/test_chunked_fleet.py):

  * the scan body performs, step for step, the float32 arithmetic of the
    host loop driving a ``ModelEnv`` adapter — same actor forward, same
    exploration values (warmup plans and OU noise are state-independent, so
    the host shell pre-consumes them from the agent's own numpy streams and
    feeds them in as scan inputs), same env ``step_fn`` on the same key
    chain, same normalization/objective fold (``core.scalarization`` does
    float32 fixed-order arithmetic for exactly this reason), same FIFO write
    and the same fused learner. The decision trajectory — every config, the
    restart accounting, the best configuration — is exactly equal between
    engines; float fields agree to within a few float32 ulps (XLA CPU
    compiles the two engines as different programs, and its context-dependent
    FMA/vectorization choices can move cancellation-prone values by single
    ulps — the per-phase fusion islands below keep it that tight).
  * chunking is pure scheduling: per-session trajectories are independent of
    the chunk size (decision trajectory exact, floats within the same few
    ulps — vmap width is part of XLA's codegen context), and padded sessions
    never leak into results.
  * both entry points mutate the adapter env, the agent and the replay
    buffer exactly as ``steps`` host-loop iterations would, so progressive
    tuning (paper Fig. 7) and the §III-E final recommendation work unchanged
    on top.

Only pure-model environments (``envs.base.ModelEnv``) can run here; real-DFS
or other external environments keep the host loop.
"""

from __future__ import annotations

import functools
import math
import os
import time
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.action_mapping import ParamSpace, jax_coord_maps
from repro.core.ddpg import DDPGConfig, actor_apply, _learn_scan
from repro.core.scalarization import metric_bounds, normalize_state
from repro.core.spans import phase


class BufferState(NamedTuple):
    """Device-side FIFO replay storage (the in-graph ``ReplayBuffer``).

    Arrays carry the replay *storage* dtype — float32 by default, bfloat16
    under the opt-in compact mode (``BatchedReplayBuffer(storage_dtype=...)``).
    Compute is always float32: the fused learner widens minibatches right
    after gathering them (``core.ddpg._learn_scan``)."""

    s: jnp.ndarray
    a: jnp.ndarray
    r: jnp.ndarray
    s2: jnp.ndarray
    next_slot: jnp.ndarray  # i32 write cursor
    size: jnp.ndarray       # i32 valid rows


class EpisodeCarry(NamedTuple):
    env_state: Any
    ddpg: Any
    buffer: BufferState
    learn_key: jax.Array
    state_vec: jnp.ndarray   # current normalized metric state [k]
    objective: jnp.ndarray   # scalarized objective of state_vec (f32)


class EpisodeTrace(NamedTuple):
    """Per-step outputs; leading axis = episode steps (then sessions, for the
    fleet). The host shell reconstructs ``StepRecord`` history from this.

    Compact storage: ``action_idx`` holds per-knob quantization indices
    (``ParamSpace.index_dtype`` — decode with
    ``ParamSpace.configs_from_indices``); ``restarts`` is int32 fixed point
    in-graph and already-decoded float32 seconds once a ``run_*_scan`` entry
    point returns it to the host."""

    action_idx: jnp.ndarray
    metrics: jnp.ndarray
    rewards: jnp.ndarray
    objectives: jnp.ndarray
    restarts: jnp.ndarray


# -- restart fixed-point encoding -------------------------------------------
#
# Restart downtime is a continuous §III-F draw, but every cost the env models
# emit is an f32 in {0} ∪ [4 s, 1024 s) — and any float32 >= 4 has an ulp of
# at least 2^-21, so cost * 2^21 is an exact int32. The trace therefore
# stores restarts as int32 fixed point and the host decode is bit-exact
# (int -> f64 -> /2^21 -> f32 round-trips the original f32). Costs >= 1024 s
# are clamped (no model emits a 17-minute restart); nonzero costs below 4 s
# would decode within 2^-22 s but lose bit-exactness — env models must keep
# restart costs in the exact domain (the repo's all do: 12-20 s workload,
# +30 s DFS, and the synthetic 5-10/+20 s ranges).

RESTART_FP_SCALE = float(2 ** 21)
RESTART_FP_MAX_SECONDS = 1023.0


def _encode_restart(cost: jnp.ndarray) -> jnp.ndarray:
    clipped = jnp.clip(cost, 0.0, jnp.float32(RESTART_FP_MAX_SECONDS))
    return jnp.round(clipped * jnp.float32(RESTART_FP_SCALE)).astype(jnp.int32)


def decode_restarts(fp: np.ndarray) -> np.ndarray:
    """int32 fixed-point restart trace -> float32 seconds (exact; see above)."""
    return (np.asarray(fp).astype(np.float64) / RESTART_FP_SCALE).astype(
        np.float32)


def _build_episode(step_fn, space: ParamSpace, cfg: DDPGConfig, actor_tx,
                   critic_tx, learn: bool, num_updates: int, kernel_mode=None,
                   policy=None, obs_mask=None, resilience=None):
    """episode(params, w_vec, lo, span, carry, xs) -> (carry, EpisodeTrace).

    ``xs`` = (use_warmup [T] bool, warmup_actions [T, m], noise [T, m]).
    ``kernel_mode`` routes the in-episode learner (Pallas kernel vs XLA
    scan); it is resolved on the host by ``_compiled_episode`` and baked
    into this build, never read from the environment inside the trace.
    ``space`` supplies the in-graph quantization maps for the compact
    action-index trace (the same ``jax_coord_maps`` the env model decodes
    with, so trace indices and env dynamics always agree).

    ``policy`` (a ``core.guardrails.DeploymentPolicy``) swaps the scan body
    for the guarded shadow/canary step: carry becomes ``GuardedCarry`` and
    the trace grows the decision trail (``GuardedEpisodeTrace``). With
    ``policy=None`` this function is byte-for-byte the pre-guardrail build —
    the off path never touches ``core.guardrails``.

    ``obs_mask`` (a tuple of 0/1 floats over the k state metrics — see
    ``envs.metrics.scope_mask``) is the DIAL-style local-observation mode:
    the LEARNER's view of the state (actor input and the s/s2 rows stored in
    replay) is masked to the visible metrics, while the env dynamics,
    objective, reward and trace all keep the full state. ``obs_mask=None``
    leaves every line of the build untouched.

    ``resilience`` (a ``core.resilience.ResiliencePolicy``) swaps the body
    for the self-healing step: carry becomes ``ResilientCarry`` and the
    trace grows the uint8 health byte (``ResilientEpisodeTrace``). With
    ``resilience=None`` this function is byte-for-byte the pre-resilience
    build — the off path never touches ``core.resilience``.
    """
    # lazy: envs.base imports repro.core at its own top level
    from repro.envs.base import barriered_step, fusion_barrier

    if resilience is not None:
        if policy is not None:
            raise ValueError(
                "resilience does not compose with DeploymentPolicy "
                "guardrails (the guarded step owns its own learn path)")
        from repro.core.resilience import build_resilient_step
        resilient = build_resilient_step(step_fn, space, cfg, actor_tx,
                                         critic_tx, learn, num_updates,
                                         kernel_mode, resilience, obs_mask)

        def resilient_episode(params, w_vec, lo, span, carry, xs):
            body = functools.partial(resilient, params, w_vec, lo, span)
            return jax.lax.scan(body, carry, xs)

        return resilient_episode

    if policy is not None:
        if obs_mask is not None:
            raise ValueError(
                "observation masking does not compose with DeploymentPolicy "
                "guardrails (the guarded step owns its own observe path)")
        from repro.core.guardrails import build_guarded_step
        guarded = build_guarded_step(step_fn, space, cfg, actor_tx,
                                     critic_tx, learn, num_updates,
                                     kernel_mode, policy)

        def guarded_episode(params, w_vec, lo, span, carry, xs):
            body = functools.partial(guarded, params, w_vec, lo, span)
            return jax.lax.scan(body, carry, xs)

        return guarded_episode

    do_updates = learn and num_updates > 0
    coord_maps = jax_coord_maps(space)
    idx_dtype = space.index_dtype()
    mask = None if obs_mask is None else jnp.asarray(obs_mask, jnp.float32)

    def one_step(params, w_vec, lo, span, carry, x):
        use_warmup, warmup_a, noise = x

        # the named scopes (act, env, reward, store, learn) name each phase
        # of the Fig. 1 loop in the compiled program's op metadata
        with jax.named_scope("act"):
            # LHS warmup override, else policy + pre-drawn OU noise. The
            # barrier isolates the actor forward the same way the env step
            # and learner are isolated (see envs.base.barriered_step): each
            # phase of the Fig. 1 loop is its own fusion island, keeping
            # per-phase CPU codegen aligned with the host loop's standalone
            # dispatches.
            actor, state_vec = fusion_barrier(
                (carry.ddpg.actor, carry.state_vec))
            obs = state_vec if mask is None else state_vec * mask
            policy = fusion_barrier(actor_apply(actor, obs))
            explored = jnp.clip(policy + noise, 0.0, 1.0)
            action = jnp.where(use_warmup, jnp.clip(warmup_a, 0.0, 1.0),
                               explored)

            # compact trace: the knob indices the env's own quantization
            # lands on (f32 maps — identical to the env dynamics' decode by
            # construction)
            action_idx = jnp.stack(
                [coord_maps[j](action[j])["idx"] for j in range(space.dim)]
            ).astype(idx_dtype)

        with jax.named_scope("env"):
            # env transition (pure model) + state normalization;
            # barriered_step keeps the env subgraph an isolated fusion
            # island with the same scan-body structure the ModelEnv adapter
            # compiles (see envs.base.barriered_step)
            env_state, metrics_vec, restart = barriered_step(
                step_fn, params, carry.env_state, action, False)
            norm = jnp.where(
                span > 0, jnp.clip((metrics_vec - lo) / span, 0.0, 1.0), 0.0)

        with jax.named_scope("reward"):
            # objective: serial float32 fold in state order (zero-weight
            # terms are exact no-ops) — bit-aligned with Scalarizer.objective
            obj = jnp.float32(0.0)
            for j in range(norm.shape[0]):
                obj = obj + w_vec[j] * norm[j]
            reward = (obj - carry.objective) / jnp.maximum(
                carry.objective, jnp.float32(1e-6))

        if learn:  # observe: FIFO write, exactly ReplayBuffer.add
            with jax.named_scope("store"):
                buf = carry.buffer
                capacity = buf.s.shape[0]
                i = buf.next_slot
                # the stored s/s2 rows are what the LEARNER observed: under
                # a local-observation mask the invisible metrics are zeroed,
                # so replayed minibatches match the masked actor inputs
                s_row = (carry.state_vec if mask is None
                         else carry.state_vec * mask)
                s2_row = norm if mask is None else norm * mask
                buf = BufferState(
                    s=buf.s.at[i].set(s_row.astype(buf.s.dtype)),
                    a=buf.a.at[i].set(action.astype(buf.a.dtype)),
                    r=buf.r.at[i].set(reward.astype(buf.r.dtype)),
                    s2=buf.s2.at[i].set(s2_row.astype(buf.s2.dtype)),
                    next_slot=(i + 1) % capacity,
                    size=jnp.minimum(buf.size + 1, capacity))
        else:
            buf = carry.buffer
        if do_updates:
            # size >= 1 here by construction: the FIFO write above ran in
            # this same step (learn=True), so minibatch sampling never sees
            # an empty buffer — the invariant sample_minibatch_indices
            # requires now that the silent zero-index clamp is gone.
            with jax.named_scope("learn"):
                learn_key, k = jax.random.split(carry.learn_key)
                learn_in = fusion_barrier((carry.ddpg, buf, k))
                ddpg, _ = fusion_barrier(_learn_scan(
                    learn_in[0],
                    (learn_in[1].s, learn_in[1].a, learn_in[1].r,
                     learn_in[1].s2),
                    learn_in[1].size, learn_in[2],
                    cfg, actor_tx, critic_tx, num_updates,
                    kernel_mode=kernel_mode))
        else:
            learn_key, ddpg = carry.learn_key, carry.ddpg

        carry = EpisodeCarry(env_state, ddpg, buf, learn_key, norm, obj)
        return carry, EpisodeTrace(action_idx, metrics_vec, reward, obj,
                                   _encode_restart(restart))

    def episode(params, w_vec, lo, span, carry, xs):
        body = functools.partial(one_step, params, w_vec, lo, span)
        return jax.lax.scan(body, carry, xs)

    return episode


def _build_cell_episode(step_fn, space: ParamSpace, cfg: DDPGConfig,
                        actor_tx, critic_tx, learn: bool, num_updates: int,
                        kernel_mode, sharing, cell_size: int, obs_mask,
                        resilience=None):
    """One CELL's episode: ``cell_size`` member sessions stepping in lockstep
    with shared experience (``core.sharing.SharingConfig``).

    Carry leaves are session-stacked [cs, ...] — except, under shared
    replay, the buffer, which is the cell's single merged FIFO window
    ([capacity, ...] with scalar cursors). ``xs`` grows two inputs over the
    off-path build: ``avg_now`` [T, cs] (host-computed averaging cadence —
    the whole cell agrees, the body reads lane 0) and ``active`` [T, cs]
    (False lanes are padding: their transitions never enter the shared
    window and they carry zero weight in the cell mean).

    Step for step this is the vmapped per-session body of
    ``_build_episode`` — same phase order, same fusion islands, same
    float32 arithmetic per lane — with three cell-level splices: the merged
    FIFO scatter-write, minibatch sampling over the merged window (every
    learner sees cs× transitions per env step), and the post-learn masked
    cell mean of the actor/critic pytrees when ``avg_now`` fires. At
    ``cell_size=1`` every splice is an exact identity (one-element cumsum,
    one-element mean), which is what the sharing-off property tests pin.

    ``resilience`` threads the per-lane health layer through the cell: a
    lane with a corrupted observation or a degraded member contributes
    NOTHING to the merged window or the cell mean (its write mask and
    averaging weight drop), so one NaN cannot poison cellmates; the
    snapshot/reset/degrade lifecycle runs per lane exactly as in the
    single-session resilient body. ``resilience=None`` leaves every line of
    the build untouched.
    """
    from repro.envs.base import barriered_step, fusion_barrier

    do_updates = learn and num_updates > 0
    coord_maps = jax_coord_maps(space)
    idx_dtype = space.index_dtype()
    cs = int(cell_size)
    mask = None if obs_mask is None else jnp.asarray(obs_mask, jnp.float32)
    shared = bool(sharing.shared_replay)
    averaging = sharing.avg_every is not None
    rz = resilience
    if rz is not None:
        from repro.core.resilience import (
            EVENT_DEGRADED, EVENT_NONFINITE, EVENT_RESET, HealthState,
            ResilientCarry, ResilientEpisodeTrace, health_decision,
            select_tree, tree_nonfinite_rows)

    def idx_of(action):  # [m] -> compact per-knob quantization indices
        return jnp.stack([coord_maps[j](action[j])["idx"]
                          for j in range(space.dim)]).astype(idx_dtype)

    def one_step(params, w_vec, lo, span, carry, x):
        use_warmup, warmup_a, noise, avg_now, active = x
        health = None
        if rz is not None:
            health, carry = carry.health, carry.base

        with jax.named_scope("act"):
            # per session, vmapped over the cell
            actor, state_vec = fusion_barrier(
                (carry.ddpg.actor, carry.state_vec))
            obs = state_vec if mask is None else state_vec * mask
            policy = fusion_barrier(jax.vmap(actor_apply)(actor, obs))
            explored = jnp.clip(policy + noise, 0.0, 1.0)
            action = jnp.where(use_warmup[:, None],
                               jnp.clip(warmup_a, 0.0, 1.0), explored)
            action_idx = jax.vmap(idx_of)(action)

        with jax.named_scope("env"):
            # env transition + normalization (per session)
            env_state, metrics_vec, restart = jax.vmap(
                lambda p, es, a: barriered_step(step_fn, p, es, a, False)
            )(params, carry.env_state, action)
            norm = jnp.where(
                span > 0, jnp.clip((metrics_vec - lo) / span, 0.0, 1.0), 0.0)

        with jax.named_scope("reward"):
            # objective: same serial float32 fold per lane as the off path
            obj = jnp.float32(0.0)
            for j in range(norm.shape[1]):
                obj = obj + w_vec[:, j] * norm[:, j]
            reward = (obj - carry.objective) / jnp.maximum(
                carry.objective, jnp.float32(1e-6))

        if rz is not None:
            # per-lane corrupted-observation flag: these lanes are recorded
            # in the trace but contribute nothing stateful this step
            bad_obs = jnp.any(~jnp.isfinite(metrics_vec), axis=1)
            # a corrupted or degraded member's transitions never enter the
            # merged window (the one-NaN-poisons-the-cell hazard)
            contrib = active & ~bad_obs & ~health.degraded
        else:
            contrib = active

        with jax.named_scope("store"):
            s_row = (carry.state_vec if mask is None
                     else carry.state_vec * mask)
            s2_row = norm if mask is None else norm * mask
            buf = carry.buffer
            if learn and shared:
                # merged cell FIFO: every ACTIVE member appends, in session
                # order, to the one shared window (exactly
                # BatchedReplayBuffer(groups=...).add); inactive (padding)
                # lanes scatter out of bounds and are dropped
                capacity = buf.s.shape[0]
                n_act = contrib.astype(jnp.int32)
                offs = jnp.cumsum(n_act) - 1
                wrote = jnp.sum(n_act)
                pos = jnp.where(contrib, (buf.next_slot + offs) % capacity,
                                capacity)
                buf = BufferState(
                    s=buf.s.at[pos].set(s_row.astype(buf.s.dtype),
                                        mode="drop"),
                    a=buf.a.at[pos].set(action.astype(buf.a.dtype),
                                        mode="drop"),
                    r=buf.r.at[pos].set(reward.astype(buf.r.dtype),
                                        mode="drop"),
                    s2=buf.s2.at[pos].set(s2_row.astype(buf.s2.dtype),
                                          mode="drop"),
                    next_slot=(buf.next_slot + wrote) % capacity,
                    size=jnp.minimum(buf.size + wrote, capacity))
            elif learn:
                # independent per-session FIFOs (averaging-only mode),
                # exactly the off path's write vmapped over the cell
                capacity = buf.s.shape[1]
                lane = jnp.arange(cs)
                i = buf.next_slot
                if rz is not None:
                    pos = jnp.where(contrib, i, capacity)  # OOB -> drop
                    buf = BufferState(
                        s=buf.s.at[lane, pos].set(
                            s_row.astype(buf.s.dtype), mode="drop"),
                        a=buf.a.at[lane, pos].set(
                            action.astype(buf.a.dtype), mode="drop"),
                        r=buf.r.at[lane, pos].set(
                            reward.astype(buf.r.dtype), mode="drop"),
                        s2=buf.s2.at[lane, pos].set(
                            s2_row.astype(buf.s2.dtype), mode="drop"),
                        next_slot=jnp.where(contrib, (i + 1) % capacity, i),
                        size=jnp.where(contrib,
                                       jnp.minimum(buf.size + 1, capacity),
                                       buf.size))
                else:
                    buf = BufferState(
                        s=buf.s.at[lane, i].set(s_row.astype(buf.s.dtype)),
                        a=buf.a.at[lane, i].set(action.astype(buf.a.dtype)),
                        r=buf.r.at[lane, i].set(reward.astype(buf.r.dtype)),
                        s2=buf.s2.at[lane, i].set(
                            s2_row.astype(buf.s2.dtype)),
                        next_slot=(i + 1) % capacity,
                        size=jnp.minimum(buf.size + 1, capacity))

        lmetrics = None
        if do_updates:
            with jax.named_scope("learn"):
                ks = jax.vmap(jax.random.split)(carry.learn_key)
                learn_key, k = ks[:, 0], ks[:, 1]
                learn_in = fusion_barrier((carry.ddpg, buf, k))
                dbuf = learn_in[1]
                # dropped writes mean the window CAN be empty under
                # resilience (every lane corrupted at step 0); clamp the
                # sampled size and discard the no-data update below
                size_of = ((lambda sz: jnp.maximum(sz, 1))
                           if rz is not None else (lambda sz: sz))
                if shared:
                    # every member learner samples its own minibatches from
                    # the MERGED window: data broadcast, state/key batched
                    data = (dbuf.s, dbuf.a, dbuf.r, dbuf.s2)
                    ddpg, lmetrics = fusion_barrier(jax.vmap(
                        lambda st, kk: _learn_scan(
                            st, data, size_of(dbuf.size), kk, cfg,
                            actor_tx, critic_tx, num_updates,
                            kernel_mode=kernel_mode)
                    )(learn_in[0], learn_in[2]))
                    empty = dbuf.size == 0
                else:
                    ddpg, lmetrics = fusion_barrier(jax.vmap(
                        lambda st, d, sz, kk: _learn_scan(
                            st, d, size_of(sz), kk, cfg, actor_tx,
                            critic_tx, num_updates, kernel_mode=kernel_mode)
                    )(learn_in[0], (dbuf.s, dbuf.a, dbuf.r, dbuf.s2),
                      dbuf.size, learn_in[2]))
                    empty = dbuf.size == 0
                if rz is not None:
                    ddpg = select_tree(jnp.broadcast_to(empty, (cs,)),
                                       carry.ddpg, ddpg)
        else:
            learn_key, ddpg = carry.learn_key, carry.ddpg

        if rz is not None:
            if do_updates:
                bad_learn = (~jnp.broadcast_to(empty, (cs,))
                             & (tree_nonfinite_rows(ddpg)
                                | tree_nonfinite_rows(lmetrics)))
            else:
                bad_learn = jnp.zeros((cs,), bool)
            bad = bad_obs | bad_learn
            do_reset, degraded, resets, nf_total = health_decision(
                bad, health.resets, health.nonfinite, health.degraded, rz)
        else:
            bad = degraded = None

        if averaging:
            # masked cell mean, applied when the host-computed cadence
            # fires; active-weighted so padding lanes contribute nothing —
            # and, under resilience, corrupted/degraded lanes neither
            # (their params are pinned to the snapshot right after this)
            w = (contrib if rz is None
                 else (contrib & ~bad)).astype(jnp.float32)
            denom = jnp.maximum(jnp.sum(w), jnp.float32(1.0))
            do_avg = avg_now[0]

            def cell_mean(leaf):
                if not jnp.issubdtype(leaf.dtype, jnp.floating):
                    return leaf  # Adam step counts etc. stay per-session
                wf = w.reshape((cs,) + (1,) * (leaf.ndim - 1))
                m = jnp.sum(leaf * wf, axis=0) / denom
                return jnp.where(do_avg, jnp.broadcast_to(m, leaf.shape),
                                 leaf)

            def avg_tree(tree):
                return jax.tree_util.tree_map(cell_mean, tree)

            ddpg = ddpg._replace(
                actor=avg_tree(ddpg.actor), critic=avg_tree(ddpg.critic),
                actor_targ=avg_tree(ddpg.actor_targ),
                critic_targ=avg_tree(ddpg.critic_targ))
            if sharing.avg_opt_state:
                ddpg = ddpg._replace(actor_opt=avg_tree(ddpg.actor_opt),
                                     critic_opt=avg_tree(ddpg.critic_opt))

        if rz is not None:
            # per-lane reset/freeze + snapshot cadence, exactly the
            # single-session resilient body's lifecycle (including the
            # snapshot_every=1 shortcut: the revert target is the lane's
            # step-entry state — pre-learn, pre-averaging — which IS what
            # an every-step snapshot refresh would have stored)
            if rz.snapshot_every == 1:
                ddpg = select_tree(do_reset | degraded, carry.ddpg, ddpg)
                snapshot = health.snapshot          # () — no leaves
                refresh = ~bad & ~degraded
            else:
                ddpg = select_tree(do_reset | degraded, health.snapshot,
                                   ddpg)
                due = (health.since_snap + 1) >= rz.snapshot_every
                refresh = due & ~bad & ~degraded
                snapshot = select_tree(refresh, ddpg, health.snapshot)
            since = jnp.where(refresh, 0, health.since_snap + 1)
            event = (bad.astype(jnp.uint8) * EVENT_NONFINITE
                     + do_reset.astype(jnp.uint8) * EVENT_RESET
                     + degraded.astype(jnp.uint8) * EVENT_DEGRADED)
            carry = ResilientCarry(
                base=EpisodeCarry(
                    env_state, ddpg, buf, learn_key,
                    jnp.where(bad_obs[:, None], carry.state_vec, norm),
                    jnp.where(bad_obs, carry.objective, obj)),
                health=HealthState(snapshot, resets, nf_total, degraded,
                                   since))
            return carry, ResilientEpisodeTrace(
                action_idx, metrics_vec, reward, obj,
                _encode_restart(restart), event)

        carry = EpisodeCarry(env_state, ddpg, buf, learn_key, norm, obj)
        return carry, EpisodeTrace(action_idx, metrics_vec, reward, obj,
                                   _encode_restart(restart))

    def cell_episode(params, w_vec, lo, span, carry, xs):
        body = functools.partial(one_step, params, w_vec, lo, span)
        return jax.lax.scan(body, carry, xs)

    return cell_episode


def _build_cell_fleet_episode(step_fn, space, cfg, actor_tx, critic_tx,
                              learn, num_updates, kernel_mode, sharing,
                              cell_size: int, obs_mask, devices,
                              resilience=None):
    """The sharing fleet program: cells vmapped over the group axis, wrapped
    so callers keep the session-leading calling convention.

    The wrapper takes the SAME operand layout as the off-path fleet program
    — every leaf session-leading [C, ...] — except the replay buffer, which
    under shared replay is cell-granular ([C/cs, capacity, ...] with [C/cs]
    cursors). Sharding (when requested) partitions the GROUP axis, so a
    cell never spans devices and the cell mean needs no cross-device
    collective."""
    cs = int(cell_size)
    shared = bool(sharing.shared_replay)
    cell = _build_cell_episode(step_fn, space, cfg, actor_tx, critic_tx,
                               learn, num_updates, kernel_mode, sharing,
                               cs, obs_mask, resilience=resilience)
    gmapped = jax.vmap(cell, in_axes=(0, 0, 0, 0, 0, (0, 0, 0, 0, 0)))
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(devices), ("session",))
        gmapped = jax.shard_map(
            gmapped, mesh=mesh,
            in_specs=(P("session"), P("session"), P("session"),
                      P("session"), P("session"),
                      (P("session"), P("session"), P("session"),
                       P("session"), P("session"))),
            out_specs=P("session"), check_vma=False)

    def episode(params, w_vec, lo, span, carry, xs):
        health = None
        if resilience is not None:
            from repro.core.resilience import ResilientCarry
            health, carry = carry.health, carry.base
        n = carry.state_vec.shape[0]
        assert n % cs == 0, (n, cs)
        g = n // cs
        gt = jax.tree_util.tree_map

        def group(x):  # [n, ...] -> [g, cs, ...]
            return x.reshape((g, cs) + x.shape[1:])

        def group_xs(x):  # [n, T, ...] -> [g, T, cs, ...]
            return jnp.swapaxes(group(x), 1, 2)

        def ungroup(x):
            return x.reshape((n,) + x.shape[2:])

        buf = carry.buffer if shared else gt(group, carry.buffer)
        gcarry = EpisodeCarry(
            env_state=gt(group, carry.env_state),
            ddpg=gt(group, carry.ddpg), buffer=buf,
            learn_key=group(carry.learn_key),
            state_vec=group(carry.state_vec),
            objective=group(carry.objective))
        if resilience is not None:
            gcarry = ResilientCarry(base=gcarry, health=gt(group, health))
        out_carry, trace = gmapped(gt(group, params), group(w_vec),
                                   group(lo), group(span), gcarry,
                                   gt(group_xs, xs))
        out_health = None
        if resilience is not None:
            out_health, out_carry = out_carry.health, out_carry.base
        obuf = (out_carry.buffer if shared
                else gt(ungroup, out_carry.buffer))
        out_carry = EpisodeCarry(
            env_state=gt(ungroup, out_carry.env_state),
            ddpg=gt(ungroup, out_carry.ddpg), buffer=obuf,
            learn_key=ungroup(out_carry.learn_key),
            state_vec=ungroup(out_carry.state_vec),
            objective=ungroup(out_carry.objective))
        if resilience is not None:
            out_carry = ResilientCarry(base=out_carry,
                                       health=gt(ungroup, out_health))

        def ungroup_trace(x):  # [g, T, cs, ...] -> [n, T, ...]
            y = jnp.swapaxes(x, 1, 2)
            return y.reshape((n,) + y.shape[2:])

        return out_carry, gt(ungroup_trace, trace)

    return episode


def _build_mega_episode(step_fn, space: ParamSpace, cfg: DDPGConfig,
                        learn: bool, num_updates: int, mega_mode: str,
                        fleet: bool):
    """The whole-episode megakernel wrapped in the standard episode calling
    convention: ``episode(params, w_vec, lo, span, carry, xs)`` with the
    fleet layout (every leaf session-leading), so the chunked runtime,
    ``FleetService`` staging and both ``run_*_episode_scan`` entry points
    drive it UNCHANGED.

    Per chunk this dispatches ONE fused program
    (``kernels.ops.episode_inner_loop``): under ``pallas``/``interpret`` a
    single Pallas kernel whose grid is the session axis runs all T env
    steps — act, env transition, reward scalarization, FIFO store and the
    full inner loop — with the packed learner state, replay window and env
    state VMEM-resident across the episode; ``xla`` runs the identical
    per-session body vmapped. The learner stays in the packed layout
    ACROSS steps (pack∘unpack is the identity on the real regions and the
    padded regions are a zero fixed point), so the decision trajectory is
    exact vs the scan engine whenever the scan engine runs the same packed
    learner (``REPRO_KERNELS=interpret``/``pallas``); see
    tests/test_megakernel.py for the pinned ladder.

    ``mega_mode`` is host-resolved by ``_compiled_episode`` (from
    ``REPRO_MEGAKERNEL``) and baked into the build, like ``kernel_mode``.
    """
    from repro.kernels import episode_fused as _ef
    from repro.kernels import ops as _ops
    from repro.kernels.ddpg_fused import (pack_params, packed_dims,
                                          unpack_params)

    dims = packed_dims(cfg.state_dim, cfg.action_dim, cfg.hidden)
    idx_dtype = space.index_dtype()

    def _pack_one(ddpg):
        a_adam, c_adam = ddpg.actor_opt[0], ddpg.critic_opt[0]
        return pack_params(
            ddpg.actor, ddpg.critic, ddpg.actor_targ, ddpg.critic_targ,
            a_adam.mu, a_adam.nu, c_adam.mu, c_adam.nu,
            a_adam.count, c_adam.count, dims)

    def episode(params, w_vec, lo, span, carry, xs):
        from repro.core.ddpg import DDPGState, _packable
        from repro.optim.transform import ScaleByAdamState

        if not fleet:
            one = jax.tree_util.tree_map(lambda x: x[None],
                                         (params, w_vec, lo, span, carry, xs))
            params, w_vec, lo, span, carry, xs = one
        if not _packable(jax.tree_util.tree_map(lambda x: x[0], carry.ddpg),
                         cfg):
            raise ValueError(
                "the whole-episode megakernel needs the packed learner "
                "layout (two hidden layers, stock optim.adam transforms); "
                "run this configuration with REPRO_MEGAKERNEL=off")
        use_warmup, warmup, noise = xs
        packed = jax.vmap(_pack_one)(carry.ddpg)
        param_leaves, param_treedef = jax.tree_util.tree_flatten(params)
        env_leaves, env_treedef = jax.tree_util.tree_flatten(carry.env_state)
        spec = _ef.EpisodeKernelSpec(
            step_fn=step_fn, space=space, cfg=cfg, learn=learn,
            num_updates=num_updates, dims=dims,
            param_treedef=param_treedef, env_treedef=env_treedef)
        buf = carry.buffer
        operands = _ef.EpisodeOperands(
            use_warmup=use_warmup, warmup=warmup, noise=noise,
            w_vec=w_vec, lo=lo, span=span,
            params=tuple(param_leaves), env=tuple(env_leaves),
            packed=tuple(packed),
            buffer=(buf.s, buf.a, buf.r, buf.s2, buf.next_slot, buf.size),
            learn_key=carry.learn_key, state_vec=carry.state_vec,
            objective=carry.objective)
        outs = _ops.episode_inner_loop(operands, spec=spec, mode=mega_mode)

        T = use_warmup.shape[1]
        do_updates = learn and num_updates > 0

        def _unpack_one(packed_one, ddpg):
            parts = unpack_params(*packed_one, dims)
            a_rest = ddpg.actor_opt[1:]
            c_rest = ddpg.critic_opt[1:]
            return DDPGState(
                actor=parts["actor"], critic=parts["critic"],
                actor_targ=parts["actor_targ"],
                critic_targ=parts["critic_targ"],
                actor_opt=(ScaleByAdamState(count=parts["actor_count"],
                                            mu=parts["actor_mu"],
                                            nu=parts["actor_nu"]), *a_rest),
                critic_opt=(ScaleByAdamState(count=parts["critic_count"],
                                             mu=parts["critic_mu"],
                                             nu=parts["critic_nu"]),
                            *c_rest),
                step=ddpg.step + (T * num_updates if do_updates else 0))

        ddpg = jax.vmap(_unpack_one)(tuple(outs.packed), carry.ddpg)
        out_carry = EpisodeCarry(
            env_state=jax.tree_util.tree_unflatten(env_treedef,
                                                   list(outs.env)),
            ddpg=ddpg,
            buffer=BufferState(*outs.buffer),
            learn_key=outs.learn_key, state_vec=outs.state_vec,
            objective=outs.objective)
        trace = EpisodeTrace(
            action_idx=outs.action_idx.astype(idx_dtype),
            metrics=outs.metrics, rewards=outs.rewards,
            objectives=outs.objectives, restarts=outs.restarts)
        if not fleet:
            out_carry, trace = jax.tree_util.tree_map(
                lambda x: x[0], (out_carry, trace))
        return out_carry, trace

    return episode


_EPISODE_CACHE: dict = {}

# the persistent compilation cache's home when JAX_COMPILATION_CACHE_DIR is
# unset: inside the checkout (``<repo>/.jax_cache``, listed in .gitignore)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def _compiled_episode(step_fn, space, cfg, actor_tx, critic_tx, learn,
                      num_updates, fleet: bool, devices: Optional[tuple],
                      policy=None, sharing=None, cell_size: int = 1,
                      obs_mask=None, resilience=None):
    """Jitted (and optionally vmapped + shard_mapped) episode, cached so
    repeated ``run()`` calls and same-space fleets reuse one compilation.
    The learner kernel mode is part of the cache key: flipping
    ``REPRO_KERNELS`` mid-process recompiles instead of silently reusing the
    other path's program. One cache entry serves EVERY chunk of EVERY grid
    shape: the chunked fleet runner always calls it at the fixed chunk shape
    ``[C, ...]``, so the underlying jit cache holds a single executable per
    (chunk, steps) bucket — ``fn._cache_size()`` counts them."""
    from repro.core.sharing import normalize_sharing
    from repro.kernels import ops

    kernel_mode = ops.ddpg_kernel_mode()
    mega_mode = ops.episode_kernel_mode()
    sharing = normalize_sharing(sharing)
    if resilience is not None:
        from repro.core.resilience import normalize_resilience
        resilience = normalize_resilience(resilience)
    cell = sharing is not None and (sharing.shared_replay
                                    or sharing.averaging)
    if not cell:
        cell_size = 1
    obs_mask = None if obs_mask is None else tuple(
        float(v) for v in obs_mask)
    # policy joins the key: a DeploymentPolicy is hashable and baked into the
    # guarded build; policy=None keys (and builds) the exact unguarded
    # program, so guardrails-off tuners share one executable with pre-PR
    # code. sharing/cell_size/obs_mask normalize to (None, 1, None) when
    # every sharing mode is off, so sharing-off keys — and IS, by executable
    # identity — the exact same cached program. resilience follows the same
    # precedent: a ResiliencePolicy is hashable and baked into the resilient
    # build; resilience=None (the canonical off value) keys the exact
    # pre-resilience program.
    # mega_mode joins the key on the same precedent: None (REPRO_MEGAKERNEL
    # unset/off) keys — and IS, by cached-object identity — the exact
    # pre-megakernel program; any active mode compiles the fused-episode
    # formulation instead.
    key = (step_fn, space, cfg, actor_tx, critic_tx, learn, num_updates,
           fleet, devices, kernel_mode, mega_mode, policy, sharing, cell_size,
           obs_mask, resilience)
    if key in _EPISODE_CACHE:
        return _EPISODE_CACHE[key]
    if policy is not None and sharing is not None:
        raise ValueError(
            "experience sharing does not compose with DeploymentPolicy "
            "guardrails (the guarded step owns its own observe/learn path); "
            "run guarded fleets with sharing off")
    if policy is not None and resilience is not None:
        raise ValueError(
            "resilience does not compose with DeploymentPolicy guardrails "
            "(the guarded step owns its own learn path); run guarded "
            "fleets with resilience off")
    if cell and not fleet:
        raise ValueError("cell experience sharing requires the fleet engine")
    if mega_mode is not None:
        # the megakernel refuses (rather than silently degrades) every
        # policy layer that rewrites the scan body: those compose with the
        # SCAN engine, and composition pins live in tests/test_megakernel.py
        if policy is not None:
            raise ValueError(
                "the whole-episode megakernel does not compose with "
                "DeploymentPolicy guardrails (the guarded step owns its own "
                "observe/learn path); run guarded fleets with "
                "REPRO_MEGAKERNEL=off")
        if resilience is not None:
            raise ValueError(
                "the whole-episode megakernel does not compose with "
                "ResiliencePolicy self-healing (health runs in the scan "
                "body); run resilient fleets with REPRO_MEGAKERNEL=off")
        if cell:
            raise ValueError(
                "the whole-episode megakernel does not compose with cell "
                "experience sharing (the merged-FIFO cell body is a scan "
                "program); run sharing fleets with REPRO_MEGAKERNEL=off")
        if obs_mask is not None:
            raise ValueError(
                "the whole-episode megakernel does not support observation "
                "masking yet; run scoped-observation fleets with "
                "REPRO_MEGAKERNEL=off")
        if devices is not None and len(devices) > 1:
            raise ValueError(
                "the whole-episode megakernel runs single-device (its grid "
                "is the session axis); drop `devices` or set "
                "REPRO_MEGAKERNEL=off")
        episode = _build_mega_episode(step_fn, space, cfg, learn,
                                      num_updates, mega_mode, fleet)
        fn = jax.jit(episode, donate_argnums=(4,))
        _EPISODE_CACHE[key] = fn
        return fn
    if cell:
        episode = _build_cell_fleet_episode(
            step_fn, space, cfg, actor_tx, critic_tx, learn, num_updates,
            kernel_mode, sharing, cell_size, obs_mask, devices,
            resilience=resilience)
        fn = jax.jit(episode, donate_argnums=(4,))
        _EPISODE_CACHE[key] = fn
        return fn
    episode = _build_episode(step_fn, space, cfg, actor_tx, critic_tx, learn,
                             num_updates, kernel_mode=kernel_mode,
                             policy=policy, obs_mask=obs_mask,
                             resilience=resilience)
    if fleet:
        # session axis: params/w_vec/lo/span/carry stacked; xs — including
        # the warmup mask — are per-session so sessions of DIFFERENT ages
        # (FleetService join/leave churn) can ride one chunk program
        episode = jax.vmap(episode, in_axes=(0, 0, 0, 0, 0, (0, 0, 0)))
        if devices is not None and len(devices) > 1:
            from jax.sharding import Mesh, PartitionSpec as P
            mesh = Mesh(np.array(devices), ("session",))
            episode = jax.shard_map(
                episode, mesh=mesh,
                in_specs=(P("session"), P("session"), P("session"),
                          P("session"), P("session"),
                          (P("session"), P("session"), P("session"))),
                out_specs=P("session"), check_vma=False)
    # Donating the carry (learner params + opt state + FIFO storage — the
    # bulk of the program's operands) lets XLA reuse those buffers in place
    # instead of defensively copying them across the call boundary. Callers
    # never touch the input carry after the call: both run_*_episode_scan
    # entry points rebuild agent/env/buffer state from the RETURNED carry.
    fn = jax.jit(episode, donate_argnums=(4,))
    _EPISODE_CACHE[key] = fn
    return fn


def _consume_exploration(agent, steps: int, session: Optional[int] = None):
    """Pre-draw the episode's exploration from the agent's own host streams.

    Warmup plans and OU noise are state-independent, so consuming them up
    front leaves the agent's numpy RNG exactly where ``steps`` host-loop
    ``act()`` calls would — the key to host/scan equivalence. Returns
    (use_warmup [T], warmup_actions [T, m], noise [T, m]); advances
    ``steps_taken``."""
    m = agent.cfg.action_dim
    s0 = agent.steps_taken
    if session is None:
        plan, noise_src = agent._warmup_plan, agent.noise
    else:
        plan, noise_src = agent._warmup_plans[session], agent.noises[session]
    use_warmup = np.zeros(steps, bool)
    warmup = np.zeros((steps, m), np.float32)
    noise = np.zeros((steps, m), np.float32)
    for t in range(steps):
        if s0 + t < agent.warmup_steps:
            use_warmup[t] = True
            warmup[t] = plan[s0 + t]
        else:
            noise[t] = noise_src()
    if session is None:  # fleet callers advance the shared counter once
        agent.steps_taken += steps
    return use_warmup, warmup, noise


def _decode_trace(trace) -> EpisodeTrace:
    """Device trace -> host numpy, restart fixed point decoded to seconds."""
    trace = jax.tree_util.tree_map(np.asarray, trace)
    return trace._replace(restarts=decode_restarts(trace.restarts))


def run_episode_scan(env, agent, scalarizer, cur_metrics: dict, steps: int,
                 learn: bool = True, policy=None, guard=None, obs_mask=None,
                 resilience=None, health=None):
    """Run ``steps`` fused tuning iterations for one session.

    ``env`` must be a ``ModelEnv``. Mutates ``env`` (model state, last
    config) and ``agent`` (learner state, buffer, noise stream, steps_taken)
    exactly as the host loop would; returns the per-step trace as numpy
    (action indices + decoded restart seconds — see ``EpisodeTrace``).

    ``policy`` (``core.guardrails.DeploymentPolicy``) runs the guarded
    shadow/canary body instead; ``guard`` must then be the session's
    ``GuardState`` (``init_guard_state`` for a fresh session) and the return
    value becomes ``(GuardedEpisodeTrace, GuardState)`` — the updated guard
    carries to the next progressive run.

    ``resilience`` (``core.resilience.ResiliencePolicy``) runs the
    self-healing body instead; ``health`` must then be the session's
    ``HealthState`` (``init_health_state`` for a fresh session) and the
    return value becomes ``(ResilientEpisodeTrace, HealthState)``. An
    all-off policy normalizes to ``None`` (plain trace returned).
    """
    if resilience is not None:
        from repro.core.resilience import normalize_resilience
        resilience = normalize_resilience(resilience)
    model = env.model
    lo, span = metric_bounds(env.metric_specs, env.state_metrics)
    w_vec = scalarizer.weight_vector(env.state_metrics)
    state_vec = normalize_state(cur_metrics, env.metric_specs,
                                env.state_metrics)
    objective = np.float32(scalarizer.objective(cur_metrics))

    (bs, ba, br, bs2), _ = agent.buffer.storage()
    buffer = BufferState(
        s=jnp.asarray(bs), a=jnp.asarray(ba), r=jnp.asarray(br),
        s2=jnp.asarray(bs2),
        next_slot=jnp.asarray(agent.buffer._next, jnp.int32),
        size=jnp.asarray(len(agent.buffer), jnp.int32))
    xs = _consume_exploration(agent, steps)
    carry = EpisodeCarry(env.model_state, agent.state, buffer,
                         agent._learn_key, jnp.asarray(state_vec),
                         jnp.asarray(objective))
    if policy is not None:
        from repro.core.guardrails import GuardedCarry
        if guard is None:
            raise ValueError(
                "guarded runs need a GuardState (core.guardrails."
                "init_guard_state seeded from the live config)")
        carry = GuardedCarry(
            base=carry, guard=jax.tree_util.tree_map(jnp.asarray, guard))
    if resilience is not None:
        from repro.core.resilience import ResilientCarry
        if health is None:
            raise ValueError(
                "resilient runs need a HealthState (core.resilience."
                "init_health_state seeded from the learner state)")
        carry = ResilientCarry(
            base=carry, health=jax.tree_util.tree_map(jnp.asarray, health))

    fn = _compiled_episode(model.step_fn, env.param_space, agent.cfg,
                           agent._actor_tx, agent._critic_tx, learn,
                           agent.cfg.updates_per_step,
                           fleet=False, devices=None, policy=policy,
                           obs_mask=obs_mask, resilience=resilience)
    carry, trace = fn(model.params, jnp.asarray(w_vec), jnp.asarray(lo),
                      jnp.asarray(span), carry, xs)

    guard_out = health_out = None
    if resilience is not None:
        health_out = jax.tree_util.tree_map(np.asarray, carry.health)
        carry = carry.base
    if policy is not None:
        guard_out = jax.tree_util.tree_map(np.asarray, carry.guard)
        carry = carry.base
    env.model_state = carry.env_state
    agent.state = carry.ddpg
    agent._learn_key = carry.learn_key
    if learn:
        agent.buffer.set_storage(
            np.asarray(carry.buffer.s), np.asarray(carry.buffer.a),
            np.asarray(carry.buffer.r), np.asarray(carry.buffer.s2),
            int(carry.buffer.next_slot), int(carry.buffer.size))
    if policy is not None:
        return _decode_trace(trace), guard_out
    if resilience is not None:
        return _decode_trace(trace), health_out
    return _decode_trace(trace)


# ---------------------------------------------------------------------------
# Streaming chunked fleet runtime
# ---------------------------------------------------------------------------

#: stats recorded by the most recent ``run_fleet_episode_scan`` call — the
#: scaling benchmark and the compile-count regression tests read these.
_LAST_FLEET_STATS: dict = {}


def last_fleet_run_stats() -> dict:
    """Measurement record of the most recent fleet episode run.

    Keys: ``sessions``, ``chunk``, ``num_chunks``, ``overlap`` (whether the
    double-buffered chunk schedule was used), ``padded_sessions``,
    ``peak_device_bytes`` (resident jax-array bytes sampled at every chunk
    boundary while that chunk's carry and trace are still live — a measured
    lower bound that captures the persistent footprint the chunked runtime
    controls), ``executable_cache_size`` (compiled shape buckets held by the
    episode program) and ``program`` (the jitted callable itself, so tests
    can pin that two grid shapes shared one executable). ``staging`` holds
    the transfer-stream measurements from ``stream_chunks`` (``async``,
    ``stage_seconds``, ``stage_wait_seconds``, ``drain_seconds``,
    ``overlap_efficiency``)."""
    return dict(_LAST_FLEET_STATS)


def live_device_bytes() -> int:
    """Total bytes of all live jax arrays in the process (measured, via
    ``jax.live_arrays``). Process-wide: callers who want a clean reading
    should not hold unrelated device arrays."""
    return sum(int(getattr(x, "nbytes", 0)) for x in jax.live_arrays())


def resolve_chunk(n: int, chunk: Optional[int], num_devices: int = 1) -> int:
    """Effective chunk size: ``chunk`` (default: the whole fleet), capped at
    ``n`` and rounded up to a device-count multiple so ``shard_map`` always
    sees equal shards. The ragged remainder of the fleet — and the device
    remainder — are padded inside the LAST chunk only (never more than one
    chunk of padded work; asserted by the runner)."""
    c = int(chunk) if chunk is not None else int(n)
    if c <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    c = min(c, int(n))
    if num_devices > 1:
        c = int(math.ceil(c / num_devices) * num_devices)
    return c


def _pad_rows(x: np.ndarray, pad: int) -> np.ndarray:
    """Pad a [rows, ...] array by replicating its own last row ``pad`` times
    (the ragged-chunk filler: real session data, so the padded lanes run the
    same well-defined compute and are sliced off afterwards)."""
    if pad == 0:
        return x
    return np.concatenate([x, np.repeat(x[-1:], pad, axis=0)])


_STAGE_EXECUTOR = None


def _stage_executor():
    """Lazy singleton single-worker pool: the dedicated transfer stream.

    One worker by construction — staged chunks are consumed in submission
    order, so a single thread preserves the serial schedule's staging order
    while letting ``jax.device_put`` (which releases the GIL inside the
    runtime) overlap with the main thread's compute dispatch and drain."""
    global _STAGE_EXECUTOR
    if _STAGE_EXECUTOR is None:
        import concurrent.futures
        _STAGE_EXECUTOR = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="repro-stage")
    return _STAGE_EXECUTOR


def _start_host_copy(tree):
    """Enqueue device->host copies for every leaf that supports it.

    ``copy_to_host_async`` schedules the D2H transfer to start the moment
    the producing computation finishes, so by the time ``drain`` calls
    ``np.asarray`` the bytes are already on the host (or in flight) instead
    of being fetched synchronously. Purely a prefetch hint: values are
    unchanged."""
    for x in jax.tree_util.tree_leaves(tree):
        cp = getattr(x, "copy_to_host_async", None)
        if cp is not None:
            cp()


def stream_chunks(call, stage, drain, num_chunks: int,
                  overlap: bool = True, supervisor=None, chaos=None,
                  staging: Optional[dict] = None):
    """Drive the chunked episode pipeline, optionally double-buffered.

    ``stage(ci)`` builds chunk ``ci``'s device operands (host -> device),
    ``call(args)`` dispatches the compiled episode program (returns device
    futures immediately), and ``drain(ci, out)`` blocks on chunk ``ci``'s
    results, copies them to host and decodes the compact trace.

    ``overlap=False`` is the strictly serial schedule: stage -> compute ->
    drain, one chunk at a time (the pre-overlap behaviour; one chunk of
    device state resident).

    ``overlap=True`` double-buffers with a dedicated transfer stream: while
    chunk k computes on device, chunk k+1's operands are staged host ->
    device on a single background worker thread (``_stage_executor``) and
    chunk k-1's results — whose device->host copies were enqueued via
    ``copy_to_host_async`` right after dispatch — are drained and decoded
    on the main thread. Transfer and host decode hide under compute, at the
    cost of at most TWO chunks of state in flight plus the staged chunk
    (still O(chunk)). Chunks cover disjoint sessions and staging produces
    the same arrays on any thread, so the schedule change cannot affect any
    session's results: outputs are bitwise identical to the serial
    schedule, which is pinned by tests/test_chunked_fleet.py and
    tests/test_megakernel.py.

    ``staging`` (optional dict) receives the transfer-stream measurements:
    ``async`` (whether the background stream ran), ``stage_seconds`` (time
    the worker spent building + staging operands), ``stage_wait_seconds``
    (time the main thread blocked waiting for a staged chunk),
    ``drain_seconds`` (the whole drain: the wait for the chunk's results
    plus ``drain``'s copy and decode), ``drain_block_seconds`` (the wait
    alone: ``jax.block_until_ready`` on the chunk's results) and
    ``overlap_efficiency`` (fraction of staging time hidden under compute:
    ``1 - wait / stage``).

    The unsupervised schedules name their phases on the profiler's clock
    (``core.spans``): ``fleet.stage`` (on the thread that stages),
    ``fleet.stage_wait``, ``fleet.dispatch``, ``fleet.drain.wait`` and
    ``fleet.drain.copy``, each with its ``chunk``.

    ``supervisor`` (a ``core.resilience.ChunkSupervisor``) runs the stream
    under host supervision: strictly serial (chunking/overlap are pure
    scheduling, so results are unchanged), each chunk wrapped in
    retry-with-exponential-backoff. The caller's host state is only mutated
    by ``drain`` — and each drain materializes device results BEFORE its
    first host write — so a failed attempt left the chunk's inputs intact
    and ``stage(ci)`` re-stages them deterministically: retries are bitwise
    invisible on success. A chunk exceeding ``watchdog_seconds`` wall clock
    counts as a stall in the returned stats. After ``max_retries`` the chunk
    raises ``ChunkFailure`` (``on_failure="raise"``) or is skipped with its
    host state untouched (``on_failure="skip"`` — the quarantine path).
    Returns a stats dict when supervised, else ``None``. ``chaos`` (an
    object with ``before_chunk(ci, attempt)``, e.g.
    ``envs.faults.HostChaos``) injects deterministic failures/stalls ahead
    of each staged attempt and requires a supervisor.
    """
    if chaos is not None and supervisor is None:
        raise ValueError("host chaos injection needs a ChunkSupervisor "
                         "(unsupervised streams have no retry path)")
    st = staging if staging is not None else {}
    st.update(**{"async": False, "stage_seconds": 0.0,
                 "stage_wait_seconds": 0.0, "drain_seconds": 0.0,
                 "drain_block_seconds": 0.0, "overlap_efficiency": 0.0})
    if num_chunks <= 0:
        return None if supervisor is None else _empty_stream_stats()
    if supervisor is not None:
        return _stream_supervised(call, stage, drain, num_chunks,
                                  supervisor, chaos)

    def timed_stage(ci):
        with phase("stage", chunk=ci) as p:
            args = stage(ci)
        return args, p.seconds

    def dispatch(ci, staged):
        with phase("dispatch", chunk=ci):
            return call(staged)

    def timed_drain(ci, out):
        # the device->host copies are already in flight
        # (``_start_host_copy``); waiting first splits the drain's time into
        # the device's and the host's share without changing a value
        with phase("drain.wait", chunk=ci) as wait:
            jax.block_until_ready(out)
        with phase("drain.copy", chunk=ci) as copy:
            drain(ci, out)
        st["drain_block_seconds"] += wait.seconds
        st["drain_seconds"] += wait.seconds + copy.seconds

    if overlap:
        st["async"] = True
        ex = _stage_executor()
        inflight = None
        fut = ex.submit(timed_stage, 0)
        for ci in range(num_chunks):
            with phase("stage_wait", chunk=ci) as wait:
                staged, sdt = fut.result()  # until chunk ci is on device
            st["stage_wait_seconds"] += wait.seconds
            st["stage_seconds"] += sdt
            out = dispatch(ci, staged)
            staged = None  # drop our handle; donation invalidated the carry
            _start_host_copy(out)  # D2H drains the moment compute finishes
            if ci + 1 < num_chunks:
                # host->device of chunk ci+1 on the transfer stream, under
                # chunk ci's compute and chunk ci-1's drain
                fut = ex.submit(timed_stage, ci + 1)
            if inflight is not None:
                timed_drain(*inflight)  # blocks on chunk ci-1, ci still runs
            inflight = (ci, out)
        if inflight is not None:
            timed_drain(*inflight)
    else:
        staged, sdt = timed_stage(0)
        st["stage_seconds"] += sdt
        for ci in range(num_chunks):
            out = dispatch(ci, staged)
            staged = None
            timed_drain(ci, out)
            if ci + 1 < num_chunks:
                staged, sdt = timed_stage(ci + 1)
                st["stage_seconds"] += sdt
        st["stage_wait_seconds"] = st["stage_seconds"]  # nothing hidden
    if st["stage_seconds"] > 0.0:
        st["overlap_efficiency"] = max(
            0.0, 1.0 - st["stage_wait_seconds"] / st["stage_seconds"])
    return None


def _empty_stream_stats() -> dict:
    return {"retries": 0, "watchdog_trips": 0, "failed_chunks": [],
            "chunk_seconds": []}


def _stream_supervised(call, stage, drain, num_chunks, supervisor, chaos):
    """Serial chunk schedule with per-chunk retry/backoff/watchdog (see
    ``stream_chunks``)."""
    from repro.core.resilience import ChunkFailure, normalize_supervisor

    sup = normalize_supervisor(supervisor)
    stats = _empty_stream_stats()
    for ci in range(num_chunks):
        attempt = 0
        while True:
            t0 = time.perf_counter()
            try:
                if chaos is not None:
                    chaos.before_chunk(ci, attempt)
                out = call(stage(ci))
                drain(ci, out)
            except Exception as err:  # noqa: BLE001 — retry any chunk fault
                if attempt >= sup.max_retries:
                    stats["failed_chunks"].append(ci)
                    if sup.on_failure == "skip":
                        break  # quarantine: host state untouched, continue
                    raise ChunkFailure(ci, attempt + 1, err) from err
                time.sleep(sup.backoff_seconds
                           * sup.backoff_multiplier ** attempt)
                attempt += 1
                stats["retries"] += 1
                continue
            elapsed = time.perf_counter() - t0
            stats["chunk_seconds"].append(elapsed)
            if (sup.watchdog_seconds is not None
                    and elapsed > sup.watchdog_seconds):
                stats["watchdog_trips"] += 1
            break
    return stats


def run_fleet_episode_scan(envs: Sequence, agent, scalarizers: Sequence,
                       cur_metrics: Sequence, steps: int, learn: bool = True,
                       devices: Optional[Sequence] = None,
                       chunk: Optional[int] = None,
                       overlap: bool = True, policy=None, guard=None,
                       sharing=None, cell_size: int = 1, obs_mask=None,
                       resilience=None, health=None, supervisor=None,
                       chaos=None):
    """Fleet variant: N sessions' episodes streamed through one compiled
    chunk program. Trace leaves are [N, T, ...] host numpy arrays.

    ``chunk=C`` executes the fleet as ``ceil(N / C)`` chunks of exactly C
    sessions (default: one chunk of all N — the monolithic schedule). All
    chunks — including every other grid shape run at the same C — share ONE
    compiled, donated episode executable; the fleet's state lives in host
    numpy between chunks, so peak device memory is O(C·T). A ragged last
    chunk (and, with ``devices``, the device remainder) is padded by
    replicating the chunk's own last session; padded work never exceeds one
    chunk and padded results are sliced off. Per-session behaviour is
    independent of both the chunk size and the device count: every session's
    PRNG keys derive from its own seed, never from its placement.

    ``overlap=True`` (default) double-buffers the chunk stream: while chunk
    k computes, chunk k+1's state is staged host -> device and chunk k-1's
    trace is decoded on the host (``stream_chunks``). Pure scheduling — the
    compiled program and its inputs are unchanged, so results are bitwise
    the serial schedule's; peak device residency is at most two chunks.

    ``policy``/``guard`` run the guarded shadow/canary body: ``guard`` is a
    stacked [N, ...] ``GuardState`` (``init_fleet_guard_state``); the guard
    rides the chunk carry like all fleet state and the return value becomes
    ``(GuardedEpisodeTrace, GuardState)``.

    ``sharing``/``cell_size``/``obs_mask`` enable cross-session experience
    sharing (``core.sharing``): sessions [i*cs, (i+1)*cs) form cell i.
    Cells never span chunks — the chunk size is rounded up to a cell
    multiple — so the cell program's state is self-contained per chunk and
    chunking stays pure scheduling. With shared replay the agent's buffer
    must be grouped (``BatchedReplayBuffer(groups=...)``); its cell-level
    storage is staged and drained at group granularity.

    ``resilience``/``health`` run the self-healing body
    (``core.resilience``): ``health`` is a stacked [N, ...] ``HealthState``
    (``init_fleet_health_state``); it rides the chunk carry like all fleet
    state and the return value becomes ``(ResilientEpisodeTrace,
    HealthState)``. Composes with sharing (per-lane health in the cell
    body), never with guardrails.

    ``supervisor``/``chaos`` put the chunk stream under host supervision
    (retry/backoff/watchdog — see ``stream_chunks``); the supervised run's
    stats land in ``last_fleet_run_stats()["supervisor"]``.
    """
    from repro.core.sharing import normalize_sharing

    sharing = normalize_sharing(sharing)
    if resilience is not None:
        from repro.core.resilience import normalize_resilience
        resilience = normalize_resilience(resilience)
    cell = sharing is not None and (sharing.shared_replay
                                    or sharing.averaging)
    cs = int(cell_size) if cell else 1
    shared_replay = cell and sharing.shared_replay
    models = [e.model for e in envs]
    step_fns = {m.step_fn for m in models}
    if len(step_fns) != 1:
        raise ValueError(
            "fleet sessions must share one env model structure (same space / "
            "model class); mixed fleets need the host engine")
    n = len(envs)
    space = envs[0].param_space
    devices = tuple(devices) if devices else None
    ndev = len(devices) if devices else 1
    c = resolve_chunk(n, chunk, ndev)
    if cell:
        if n % cs != 0:
            raise ValueError(
                f"experience sharing needs whole cells: {n} sessions is not "
                f"a multiple of cell_size={cs}")
        # cells never span chunks: round the chunk up to a cell multiple
        # (and keep the device-count multiple resolve_chunk established)
        step_mult = cs * ndev if ndev > 1 else cs
        c = int(math.ceil(c / step_mult) * step_mult)
        c = min(c, int(math.ceil(n / step_mult) * step_mult))
    num_chunks = -(-n // c)
    pad_total = num_chunks * c - n
    # no padded session's work exceeds one chunk: padding exists only to
    # square off the LAST chunk (and the device remainder inside it)
    assert pad_total < c, (pad_total, c, n)

    def stack_np(trees):  # host-side stack: plain numpy, no device residency
        return jax.tree_util.tree_map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *trees)

    # -- full-fleet host staging (numpy; written back chunk by chunk) -------
    params = stack_np([m.params for m in models])
    env_states = stack_np([e.model_state for e in envs])
    ddpg_states = jax.tree_util.tree_map(np.array, agent.states)
    lo, span = metric_bounds(envs[0].metric_specs, envs[0].state_metrics)
    k = lo.shape[0]
    lo = np.broadcast_to(lo, (n, k))
    span = np.broadcast_to(span, (n, k))
    w_vec = np.stack([sc.weight_vector(e.state_metrics)
                      for sc, e in zip(scalarizers, envs)])
    state_vecs = np.stack([
        normalize_state(mtr, e.metric_specs, e.state_metrics)
        for mtr, e in zip(cur_metrics, envs)])
    objectives = np.array([np.float32(sc.objective(mtr))
                           for sc, mtr in zip(scalarizers, cur_metrics)],
                          np.float32)

    if shared_replay:
        # cell-level storage: [G, capacity, ...] arrays + per-group cursors,
        # staged/drained at group granularity (cells never span chunks)
        if agent.buffer.groups is None:
            raise ValueError(
                "shared replay needs a grouped BatchedReplayBuffer "
                "(FleetAgent(..., replay_groups=...))")
        (bs, ba, br, bs2), next_slots, sizes = agent.buffer.grouped_storage()
        buf_np = tuple(np.array(x) for x in (bs, ba, br, bs2))
        next_slots = np.asarray(next_slots, np.int32)
        sizes = np.asarray(sizes, np.int32)
    else:
        (bs, ba, br, bs2), sizes = agent.buffer.storage()
        buf_np = tuple(np.array(x) for x in (bs, ba, br, bs2))
        next_slots = np.full((n,), agent.buffer._next, np.int32)
        sizes = np.array(sizes, np.int32)
    learn_keys = np.array(agent._learn_keys)

    s0 = agent.steps_taken
    m_dim = agent.cfg.action_dim
    use_warmup = np.zeros((n, steps), bool)
    warmup = np.zeros((n, steps, m_dim), np.float32)
    noise = np.zeros((n, steps, m_dim), np.float32)
    for t in range(steps):
        if s0 + t < agent.warmup_steps:
            use_warmup[:, t] = True
            warmup[:, t] = agent._warmup_plans[:, s0 + t]
        else:
            noise[:, t] = np.stack([nz() for nz in agent.noises])
    agent.steps_taken += steps

    if cell:
        # host-computed sharing inputs: the averaging cadence fires on the
        # fleet's shared step clock (so it survives chunking and progressive
        # runs), and every real session is active (padding lanes replicate
        # whole cells and are sliced off before anything reads them)
        avg_now = np.zeros((n, steps), bool)
        if sharing.averaging:
            for t in range(steps):
                avg_now[:, t] = ((s0 + t + 1) % sharing.avg_every) == 0
        active = np.ones((n, steps), bool)

    # -- preallocated host trace buffers (the stream targets) ---------------
    base_fields = dict(
        action_idx=np.zeros((n, steps, space.dim), space.index_dtype()),
        metrics=np.zeros((n, steps, k), np.float32),
        rewards=np.zeros((n, steps), np.float32),
        objectives=np.zeros((n, steps), np.float32),
        restarts=np.zeros((n, steps), np.float32))
    if policy is not None:
        from repro.core.guardrails import GuardedCarry, GuardedEpisodeTrace
        if guard is None:
            raise ValueError(
                "guarded fleet runs need a stacked GuardState "
                "(core.guardrails.init_fleet_guard_state)")
        # fresh host arrays: the caller's guard is never mutated in place
        guard = jax.tree_util.tree_map(np.array, guard)
        out = GuardedEpisodeTrace(
            **base_fields,
            guard_events=np.zeros((n, steps), np.uint8),
            shadow_objectives=np.zeros((n, steps), np.float32))
    elif resilience is not None:
        from repro.core.resilience import (ResilientCarry,
                                           ResilientEpisodeTrace)
        if health is None:
            raise ValueError(
                "resilient fleet runs need a stacked HealthState "
                "(core.resilience.init_fleet_health_state)")
        # fresh host arrays: the caller's health is never mutated in place
        health = jax.tree_util.tree_map(np.array, health)
        out = ResilientEpisodeTrace(
            **base_fields, health_events=np.zeros((n, steps), np.uint8))
    else:
        out = EpisodeTrace(**base_fields)

    fn = _compiled_episode(models[0].step_fn, space, agent.cfg,
                           agent._actor_tx, agent._critic_tx, learn,
                           agent.cfg.updates_per_step,
                           fleet=True, devices=devices, policy=policy,
                           sharing=sharing, cell_size=cs, obs_mask=obs_mask,
                           resilience=resilience)

    peak = [live_device_bytes()]

    def stage(ci):
        a, b = ci * c, min(n, (ci + 1) * c)
        pad = c - (b - a)

        def chunk_of(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(_pad_rows(x[a:b], pad)), tree)

        def group_chunk_of(tree):
            # cell-granular slice: chunk ci covers whole groups
            ga, gb = a // cs, b // cs
            gpad = pad // cs
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(_pad_rows(x[ga:gb], gpad)), tree)

        buf_of = group_chunk_of if shared_replay else chunk_of
        carry = EpisodeCarry(
            env_state=chunk_of(env_states),
            ddpg=chunk_of(ddpg_states),
            buffer=BufferState(
                s=buf_of(buf_np[0]), a=buf_of(buf_np[1]),
                r=buf_of(buf_np[2]), s2=buf_of(buf_np[3]),
                next_slot=buf_of(next_slots), size=buf_of(sizes)),
            learn_key=chunk_of(learn_keys),
            state_vec=chunk_of(state_vecs),
            objective=chunk_of(objectives))
        if cell:
            xs = (chunk_of(use_warmup), chunk_of(warmup), chunk_of(noise),
                  chunk_of(avg_now), chunk_of(active))
        else:
            xs = (chunk_of(use_warmup), chunk_of(warmup), chunk_of(noise))
        if policy is not None:
            carry = GuardedCarry(base=carry, guard=chunk_of(guard))
        elif resilience is not None:
            carry = ResilientCarry(base=carry, health=chunk_of(health))
        args = (chunk_of(params), chunk_of(w_vec), chunk_of(lo),
                chunk_of(span), carry, xs)
        # sample peak while the freshly staged operands are live: under
        # async overlap this is the window where the in-flight transfer
        # buffers coexist with the computing chunk — invisible to the
        # drain-side sample, which runs after they were consumed
        peak[0] = max(peak[0], live_device_bytes())
        return args

    def call(args):
        return fn(*args)

    def drain(ci, out_pair):
        carry, trace = out_pair
        a, b = ci * c, min(n, (ci + 1) * c)
        cnt = b - a

        # peak sampled while this chunk's carry + trace (and, under overlap,
        # the next chunk's staged operands) are still live — the resident
        # footprint the O(chunk) contract is about
        peak[0] = max(peak[0], live_device_bytes())

        # stream the chunk's trace into the host buffers (np.asarray forces
        # the computation and copies off-device)
        out.action_idx[a:b] = np.asarray(trace.action_idx)[:cnt]
        out.metrics[a:b] = np.asarray(trace.metrics)[:cnt]
        out.rewards[a:b] = np.asarray(trace.rewards)[:cnt]
        out.objectives[a:b] = np.asarray(trace.objectives)[:cnt]
        out.restarts[a:b] = decode_restarts(np.asarray(trace.restarts)[:cnt])
        if policy is not None:
            out.guard_events[a:b] = np.asarray(trace.guard_events)[:cnt]
            out.shadow_objectives[a:b] = np.asarray(
                trace.shadow_objectives)[:cnt]
        elif resilience is not None:
            out.health_events[a:b] = np.asarray(trace.health_events)[:cnt]

        # write the chunk's carry back into the fleet's host state
        def write_back(dst_tree, src_tree):
            jax.tree_util.tree_map(
                lambda d, s: d.__setitem__(slice(a, b), np.asarray(s)[:cnt]),
                dst_tree, src_tree)

        if policy is not None:
            write_back(guard, carry.guard)
            carry = carry.base
        elif resilience is not None:
            write_back(health, carry.health)
            carry = carry.base
        write_back(env_states, carry.env_state)
        write_back(ddpg_states, carry.ddpg)
        if shared_replay:
            # cell-granular write-back: the chunk carried whole groups
            ga, gb = a // cs, b // cs
            gcnt = gb - ga
            for dst, src in zip(buf_np, (carry.buffer.s, carry.buffer.a,
                                         carry.buffer.r, carry.buffer.s2)):
                dst[ga:gb] = np.asarray(src)[:gcnt]
            next_slots[ga:gb] = np.asarray(carry.buffer.next_slot)[:gcnt]
            sizes[ga:gb] = np.asarray(carry.buffer.size)[:gcnt]
        else:
            write_back(buf_np[0], carry.buffer.s)
            write_back(buf_np[1], carry.buffer.a)
            write_back(buf_np[2], carry.buffer.r)
            write_back(buf_np[3], carry.buffer.s2)
            next_slots[a:b] = np.asarray(carry.buffer.next_slot)[:cnt]
            sizes[a:b] = np.asarray(carry.buffer.size)[:cnt]
        learn_keys[a:b] = np.asarray(carry.learn_key)[:cnt]

    staging_stats: dict = {}
    stream_stats = stream_chunks(call, stage, drain, num_chunks,
                                 overlap=overlap, supervisor=supervisor,
                                 chaos=chaos, staging=staging_stats)

    _LAST_FLEET_STATS.clear()
    _LAST_FLEET_STATS.update(
        sessions=n, chunk=c, num_chunks=num_chunks, overlap=overlap,
        padded_sessions=pad_total, peak_device_bytes=peak[0],
        executable_cache_size=fn._cache_size(), program=fn,
        cell_size=cs, sharing=sharing, staging=staging_stats)
    if stream_stats is not None:
        _LAST_FLEET_STATS["supervisor"] = stream_stats

    for e, st in zip(envs, _unstack(env_states, n)):
        e.model_state = st
    agent.states = ddpg_states
    agent._learn_keys = jnp.asarray(learn_keys)
    if learn and shared_replay:
        agent.buffer.set_storage(*buf_np, next_slots, sizes)
    elif learn:
        agent.buffer.set_storage(*buf_np, int(next_slots[0]), int(sizes[0]))
    if policy is not None:
        return out, guard
    if resilience is not None:
        return out, health
    return out


def precompile_fleet_episode(env, agent, steps: int, sessions: int,
                             chunk: Optional[int] = None,
                             devices: Optional[Sequence] = None,
                             learn: bool = True, policy=None):
    """Warm the chunked fleet episode executable ahead of ``run()``.

    Executes ONE dummy chunk episode (zero exploration, throwaway copies of
    session 0's state) at exactly the shapes/dtypes the real run will use,
    so the real run's chunks all hit the already-compiled program — and,
    with ``enable_persistent_compilation_cache`` active, later processes
    hit the on-disk cache. Agent, env and every RNG stream are untouched.
    Returns the jitted episode program."""
    devices = tuple(devices) if devices else None
    ndev = len(devices) if devices else 1
    c = resolve_chunk(sessions, chunk, ndev)
    fn = _compiled_episode(env.model.step_fn, env.param_space, agent.cfg,
                           agent._actor_tx, agent._critic_tx, learn,
                           agent.cfg.updates_per_step,
                           fleet=True, devices=devices, policy=policy)
    outs = fn(*chunk_operands(env, agent, steps, c, policy=policy))
    jax.block_until_ready(outs)
    return fn


def chunk_operands(env, agent, steps: int, c: int, policy=None) -> tuple:
    """The fleet episode program's operands for one chunk of ``c`` sessions
    (throwaway copies of session 0's state, zero exploration): what
    ``precompile_fleet_episode`` runs, and — under ``jax.eval_shape`` — the
    shapes a chunk program is compiled for."""
    model = env.model
    cfg = agent.cfg

    def tile(x):
        x = np.asarray(x)
        return jnp.asarray(np.broadcast_to(x[None], (c,) + x.shape))

    (bs, ba, br, bs2), _ = agent.buffer.storage()
    lo, span = metric_bounds(env.metric_specs, env.state_metrics)
    k, m = lo.shape[0], cfg.action_dim
    carry = EpisodeCarry(
        env_state=jax.tree_util.tree_map(tile, env.model_state),
        ddpg=jax.tree_util.tree_map(
            lambda x: jnp.asarray(np.repeat(np.asarray(x)[:1], c, axis=0)),
            agent.states),
        buffer=BufferState(
            s=jnp.zeros((c,) + bs.shape[1:], bs.dtype),
            a=jnp.zeros((c,) + ba.shape[1:], ba.dtype),
            r=jnp.zeros((c,) + br.shape[1:], br.dtype),
            s2=jnp.zeros((c,) + bs2.shape[1:], bs2.dtype),
            next_slot=jnp.zeros((c,), jnp.int32),
            size=jnp.zeros((c,), jnp.int32)),
        learn_key=jnp.asarray(
            np.zeros((c,) + np.asarray(agent._learn_keys).shape[1:],
                     np.asarray(agent._learn_keys).dtype)),
        state_vec=jnp.zeros((c, k), jnp.float32),
        objective=jnp.zeros((c,), jnp.float32))
    if policy is not None:
        from repro.core.guardrails import GuardedCarry, GuardState
        carry = GuardedCarry(base=carry, guard=GuardState(
            live_action=jnp.zeros((c, m), jnp.float32),
            fallback_action=jnp.zeros((c, m), jnp.float32),
            fallback_obj=jnp.zeros((c,), jnp.float32),
            budget_spent=jnp.zeros((c,), jnp.float32),
            watch_left=jnp.zeros((c,), jnp.int32),
            promotions=jnp.zeros((c,), jnp.int32),
            rollbacks=jnp.zeros((c,), jnp.int32)))
    xs = (jnp.zeros((c, steps), bool), jnp.zeros((c, steps, m), jnp.float32),
          jnp.zeros((c, steps, m), jnp.float32))
    return (jax.tree_util.tree_map(tile, model.params),
            tile(np.zeros(k, np.float32)), tile(lo), tile(span), carry, xs)


def episode_cache_stats() -> dict:
    """Compile-reuse accounting for the episode engine: how many distinct
    episode programs exist (one per (space, cfg, engine-shape) build) and
    how many compiled shape buckets they hold in total."""
    return {
        "programs": len(_EPISODE_CACHE),
        "executables": sum(fn._cache_size()
                           for fn in _EPISODE_CACHE.values()),
    }


def enable_persistent_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and the directory is left alone; otherwise the cache goes to the
    fixed in-checkout directory ``COMPILE_CACHE_DIR`` (the directory is part
    of each entry's key, so it must not move between processes). Repeated
    processes — grid sweeps, back-to-back example runs, CI lanes — then
    deserialize the episode executable instead of recompiling it. Call
    BEFORE the first compilation of the process (compiles that already
    happened are not retro-cached). Returns the cache directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache everything: the episode program is worth persisting no matter
    # how quickly this particular box compiled it
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _unstack(tree, n: int) -> list:
    return [jax.tree_util.tree_map(lambda x: x[i], tree) for i in range(n)]


def default_devices() -> list:
    """All local devices — the default fleet sharding axis."""
    return list(jax.devices())
