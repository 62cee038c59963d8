"""Whole-episode megakernel: the Fig. 1 loop as ONE Pallas kernel per chunk.

``kernels/ddpg_fused.py`` fused the Table-III inner loop (96 sequential DDPG
updates); the rest of the per-step pipeline — act, env transition, reward
scalarization, FIFO replay store — still round-tripped through HBM/XLA
between fusion islands. This module fuses the ENTIRE episode: one kernel
program instance runs all T tuning steps for one session, with the packed
learner state (all four parameter sets + both Adam moment sets), the replay
window, and the env state resident in VMEM start to finish.

  * the grid is the chunk's session axis — each program instance owns one
    session's episode; ``input_output_aliases`` carries every stateful
    operand (env leaves, packed learner, FIFO storage + cursors, learn key,
    state vector, objective) in place across the call;
  * per step the body mirrors ``core.episode._build_episode.one_step``
    op-for-op: the actor forward runs on the REAL-size slices of the packed
    weights (packing is exact zero placement, so the slices are bitwise the
    unpacked parameters — padded [P, P] GEMMs would regroup the reduction
    tree and break decision exactness), the env ``step_fn`` runs unchanged
    (the pure-JAX Lustre/synthetic models are ordinary jnp + threefry code,
    which Pallas interpret mode discharges verbatim), and the learner is the
    same packed ``fori_loop`` the PR-4 kernel runs — kept packed across
    steps instead of packing/unpacking per step (exact: pack∘unpack is the
    identity on the real regions and the padded regions are a zero fixed
    point, pinned by tests/test_ddpg_fused.py);
  * the per-phase ``fusion_barrier`` islands of the scan engine are kept
    when the body is compiled by XLA (interpret mode and the
    ``episode_fused_xla`` twin) so cross-program float drift stays within
    ulps, and dropped when Mosaic compiles the body for real
    (``optimization_barrier`` has no Mosaic lowering; inside one kernel
    there is no cross-phase fusion to suppress anyway).

Equivalence ladder (PR 4's template, pinned by tests/test_megakernel.py):
pure-jnp oracle (``kernels.ref.episode_fused_ref``) ≤ a few f32 ulps; XLA
twin (``episode_fused_xla``) bitwise vs interpret mode; decision trajectory
EXACT vs ``run_episode_scan`` when the scan engine runs the same packed
learner (``REPRO_KERNELS=interpret``/``pallas``).

VMEM fit: ``roofline.vmem.check_episode_vmem_fit`` models the per-instance
residency (packed learner + replay window + minibatch workspace + trace +
exploration inputs) and rejects oversized (chunk, capacity, space) combos
with an actionable error BEFORE the kernel is built — a Pallas OOM names a
buffer, not a remedy. The check runs for both compiled and interpret modes
so the contract is testable off-TPU.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ddpg_fused import (NUM_LAYERS, PRECISION, PackedDims,
                                      _unpack_net,
                                      pack_minibatches, packed_update,
                                      reward_lanes)


class EpisodeKernelSpec(NamedTuple):
    """Static episode-kernel configuration (hashable where it matters:
    ``step_fn``/``space``/``cfg`` are the same objects the episode cache
    keys on; treedefs reconstruct the env/param pytrees inside the body)."""

    step_fn: Any
    space: Any
    cfg: Any                  # core.ddpg.DDPGConfig
    learn: bool
    num_updates: int
    dims: PackedDims
    param_treedef: Any
    env_treedef: Any


class EpisodeOperands(NamedTuple):
    """Flat operand bundle, every array with a leading session axis [N, ...]
    (drop it for the per-session body/oracle). ``params``/``env`` are tuples
    of pytree leaves (see ``EpisodeKernelSpec`` treedefs); ``packed`` is the
    ``pack_params`` 5-tuple; ``buffer`` is (s, a, r, s2, next_slot, size)."""

    use_warmup: jnp.ndarray   # [N, T] bool
    warmup: jnp.ndarray       # [N, T, m] f32
    noise: jnp.ndarray        # [N, T, m] f32
    w_vec: jnp.ndarray        # [N, k] f32
    lo: jnp.ndarray           # [N, k] f32
    span: jnp.ndarray         # [N, k] f32
    params: tuple             # env-model param leaves
    env: tuple                # env-state leaves
    packed: tuple             # (weights, biases, mom_w, mom_b, counts)
    buffer: tuple             # (s, a, r, s2, next_slot, size)
    learn_key: jnp.ndarray    # [N, 2] u32
    state_vec: jnp.ndarray    # [N, k] f32
    objective: jnp.ndarray    # [N] f32


class EpisodeOutputs(NamedTuple):
    """Episode results: carried state plus the compact per-step trace
    (actions as i32 knob indices — callers cast to ``space.index_dtype()`` —
    and restarts as the int32 fixed point of ``core.episode``)."""

    env: tuple
    packed: tuple
    buffer: tuple
    learn_key: jnp.ndarray
    state_vec: jnp.ndarray
    objective: jnp.ndarray
    action_idx: jnp.ndarray   # [T, m] i32
    metrics: jnp.ndarray      # [T, k] f32
    rewards: jnp.ndarray      # [T] f32
    objectives: jnp.ndarray   # [T] f32
    restarts: jnp.ndarray     # [T] i32 fixed point


# number of aliased state operands besides the env leaves: packed (5) +
# buffer (6) + learn_key + state_vec + objective
_N_STATE_OPERANDS = 14


def _episode_body(spec: EpisodeKernelSpec, op: EpisodeOperands,
                  barriers: bool) -> EpisodeOutputs:
    """One session's whole episode (shared by the kernel body, the XLA twin
    and — vmapped — nothing else). ``barriers=True`` keeps the scan engine's
    per-phase ``fusion_barrier`` islands (XLA-compiled paths); the Mosaic
    path drops them."""
    from repro.core.action_mapping import jax_coord_maps
    from repro.core.ddpg import gather_minibatches, sample_minibatch_indices
    from repro.core.episode import _encode_restart
    from repro.envs.base import barriered_step, fusion_barrier

    cfg, dims, space = spec.cfg, spec.dims, spec.space
    learn = spec.learn
    num_updates = spec.num_updates
    do_updates = learn and num_updates > 0
    coord_maps = jax_coord_maps(space)
    T = op.use_warmup.shape[0]
    m = space.dim
    k = op.state_vec.shape[0]
    bar = fusion_barrier if barriers else (lambda t: t)

    params = jax.tree_util.tree_unflatten(spec.param_treedef,
                                          list(op.params))
    act_mask = (jax.lax.broadcasted_iota(jnp.int32, (1, dims.pad), 1)
                < dims.action_dim).astype(jnp.float32)

    def env_step(env_state, action):
        if barriers:
            return barriered_step(spec.step_fn, params, env_state, action,
                                  False)
        return spec.step_fn(params, env_state, action, False)

    def one_step(t, carry):
        (env_leaves, packed, buf, learn_key, state_vec, objective,
         tr_idx, tr_met, tr_rew, tr_obj, tr_rst) = carry
        weights, biases, mom_w, mom_b, counts = packed
        env_state = jax.tree_util.tree_unflatten(spec.env_treedef,
                                                 list(env_leaves))
        take = functools.partial(jax.lax.dynamic_index_in_dim, index=t,
                                 axis=0, keepdims=False)
        use_warmup, warmup_a, noise = (take(op.use_warmup), take(op.warmup),
                                       take(op.noise))

        # act — on the REAL-size weight slices (bitwise the unpacked actor;
        # see module docstring), same phase order as the scan engine
        actor_p = _unpack_net(weights[0], biases[0], dims.actor_sizes)
        actor_p, sv = bar((actor_p, state_vec))
        h = sv
        for li in range(NUM_LAYERS - 1):
            h = jax.nn.relu(jnp.matmul(h, actor_p[li]["w"],
                                       precision=PRECISION)
                            + actor_p[li]["b"])
        policy = bar(jax.nn.sigmoid(
            jnp.matmul(h, actor_p[NUM_LAYERS - 1]["w"], precision=PRECISION)
            + actor_p[NUM_LAYERS - 1]["b"]))
        explored = jnp.clip(policy + noise, 0.0, 1.0)
        action = jnp.where(use_warmup, jnp.clip(warmup_a, 0.0, 1.0),
                           explored)
        action_idx = jnp.stack(
            [coord_maps[j](action[j])["idx"] for j in range(m)]
        ).astype(jnp.int32)

        # env transition + state normalization
        env_state, metrics_vec, restart = env_step(env_state, action)
        norm = jnp.where(op.span > 0,
                         jnp.clip((metrics_vec - op.lo) / op.span, 0.0, 1.0),
                         0.0)

        # objective: serial float32 fold in state order (Scalarizer order)
        obj = jnp.float32(0.0)
        for j in range(k):
            obj = obj + op.w_vec[j] * norm[j]
        reward = (obj - objective) / jnp.maximum(objective, jnp.float32(1e-6))

        bs, ba, br, bs2, next_slot, size = buf
        if learn:  # observe: FIFO write, exactly ReplayBuffer.add
            capacity = bs.shape[0]
            i = next_slot
            buf = (bs.at[i].set(state_vec.astype(bs.dtype)),
                   ba.at[i].set(action.astype(ba.dtype)),
                   br.at[i].set(reward.astype(br.dtype)),
                   bs2.at[i].set(norm.astype(bs2.dtype)),
                   (i + 1) % capacity,
                   jnp.minimum(size + 1, capacity))
        if do_updates:
            # store-before-learn: the FIFO write above ran in this same
            # step, so size >= 1 and minibatch sampling never sees an empty
            # window (the sample_minibatch_indices invariant)
            learn_key, kk = jax.random.split(learn_key)
            packed_in, buf_in, kk = bar((packed, buf, kk))
            idx = sample_minibatch_indices(kk, num_updates, cfg.batch_size,
                                           buf_in[5])
            batches = gather_minibatches(tuple(buf_in[:4]), idx)
            batches = tuple(b.astype(jnp.float32) for b in batches)
            sx, cx, s2x, r = pack_minibatches(batches, dims)
            rx = reward_lanes(r, dims.pad)

            def upd(u, ucarry):
                pk, met = ucarry
                batch = tuple(
                    jax.lax.dynamic_index_in_dim(x, u, 0, keepdims=False)
                    for x in (sx, cx, s2x, rx))
                pk, (cl, al, qm) = packed_update(
                    pk, batch, dims, cfg.gamma, cfg.tau, cfg.actor_lr,
                    cfg.critic_lr, act_mask)
                met = jax.lax.dynamic_update_index_in_dim(
                    met, jnp.stack([cl, al, qm]), u, 0)
                return pk, met

            packed, _ = bar(jax.lax.fori_loop(
                0, num_updates, upd,
                (packed_in, jnp.zeros((num_updates, 3), jnp.float32))))

        tr_idx = jax.lax.dynamic_update_index_in_dim(tr_idx, action_idx, t, 0)
        tr_met = jax.lax.dynamic_update_index_in_dim(tr_met, metrics_vec,
                                                     t, 0)
        tr_rew = jax.lax.dynamic_update_index_in_dim(tr_rew, reward, t, 0)
        tr_obj = jax.lax.dynamic_update_index_in_dim(tr_obj, obj, t, 0)
        tr_rst = jax.lax.dynamic_update_index_in_dim(
            tr_rst, _encode_restart(restart), t, 0)
        env_leaves = tuple(jax.tree_util.tree_leaves(env_state))
        return (env_leaves, packed, buf, learn_key, norm, obj,
                tr_idx, tr_met, tr_rew, tr_obj, tr_rst)

    init = (tuple(op.env), tuple(op.packed), tuple(op.buffer), op.learn_key,
            op.state_vec, op.objective,
            jnp.zeros((T, m), jnp.int32), jnp.zeros((T, k), jnp.float32),
            jnp.zeros((T,), jnp.float32), jnp.zeros((T,), jnp.float32),
            jnp.zeros((T,), jnp.int32))
    (env_leaves, packed, buf, learn_key, state_vec, objective,
     tr_idx, tr_met, tr_rew, tr_obj, tr_rst) = jax.lax.fori_loop(
        0, T, one_step, init)
    return EpisodeOutputs(env_leaves, packed, buf, learn_key, state_vec,
                          objective, tr_idx, tr_met, tr_rew, tr_obj, tr_rst)


def _flat_outputs(outs: EpisodeOutputs) -> list:
    return (list(outs.env) + list(outs.packed) + list(outs.buffer)
            + [outs.learn_key, outs.state_vec, outs.objective,
               outs.action_idx, outs.metrics, outs.rewards, outs.objectives,
               outs.restarts])


def _unflatten_outputs(flat: list, n_env: int) -> EpisodeOutputs:
    env = tuple(flat[:n_env])
    packed = tuple(flat[n_env:n_env + 5])
    buffer = tuple(flat[n_env + 5:n_env + 11])
    rest = flat[n_env + 11:]
    return EpisodeOutputs(env, packed, buffer, *rest)


def episode_fused_learn(operands: EpisodeOperands, *,
                        spec: EpisodeKernelSpec,
                        interpret: bool = False) -> EpisodeOutputs:
    """Run the whole chunk of episodes as ONE Pallas kernel.

    Every array in ``operands`` carries a leading session axis N; the grid
    is (N,) — one program instance per session's full T-step episode. All
    stateful operands are aliased to the outputs, so callers must treat
    them as consumed. Raises ``ValueError`` (via the roofline VMEM-fit
    check) before building an oversized kernel.
    """
    from repro.roofline.vmem import TARGET_DEVICE_KIND, check_episode_vmem_fit

    n, T = operands.use_warmup.shape
    capacity = operands.buffer[0].shape[1]
    env_bytes = sum(int(x.nbytes) // n for x in operands.env)
    check_episode_vmem_fit(
        chunk=n, steps=T, capacity=capacity, state_dim=spec.cfg.state_dim,
        action_dim=spec.cfg.action_dim, hidden=spec.cfg.hidden,
        num_updates=spec.num_updates if spec.learn else 0,
        batch_size=spec.cfg.batch_size, pad=spec.dims.pad,
        device_kind=(TARGET_DEVICE_KIND if interpret
                     else jax.devices()[0].device_kind),
        env_state_bytes=env_bytes)

    flat_in, in_tree = jax.tree_util.tree_flatten(operands)
    n_in = len(flat_in)
    n_env = len(operands.env)
    i0 = 6 + len(operands.params)   # first aliased (env-state) operand
    m, k = spec.space.dim, operands.state_vec.shape[1]

    def bspec(shape):
        nd = len(shape)
        return pl.BlockSpec((1, *shape), lambda i, nd=nd: (i,) + (0,) * nd)

    def cspec(shape):
        # session-invariant constant: every grid instance reads block 0
        nd = len(shape)
        return pl.BlockSpec((1, *shape), lambda i, nd=nd: (0,) + (0,) * nd)

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    # Pallas kernels must be closed: the body's captured constants (the
    # space's quantization tables, env-model surface coefficients, ...) are
    # hoisted by tracing the body once and lifting the jaxpr consts into
    # session-invariant kernel operands.
    def body_flat(*vals):
        op1 = jax.tree_util.tree_unflatten(in_tree, list(vals))
        return tuple(_flat_outputs(_episode_body(spec, op1,
                                                 barriers=interpret)))

    example = [jax.ShapeDtypeStruct(x.shape[1:], x.dtype) for x in flat_in]
    body_jaxpr = jax.make_jaxpr(body_flat)(*example)
    consts = [jnp.asarray(cv) for cv in body_jaxpr.consts]
    n_consts = len(consts)

    def closed_body(*vals_and_consts):
        return jax.core.eval_jaxpr(body_jaxpr.jaxpr,
                                   list(vals_and_consts[n_in:]),
                                   *vals_and_consts[:n_in])

    aliased = flat_in[i0:]
    trace_shapes = [jax.ShapeDtypeStruct((n, T, m), jnp.int32),
                    jax.ShapeDtypeStruct((n, T, k), jnp.float32),
                    jax.ShapeDtypeStruct((n, T), jnp.float32),
                    jax.ShapeDtypeStruct((n, T), jnp.float32),
                    jax.ShapeDtypeStruct((n, T), jnp.int32)]
    in_specs = ([bspec(x.shape[1:]) for x in flat_in]
                + [cspec(cv.shape) for cv in consts])
    out_shape = [like(x) for x in aliased] + trace_shapes
    out_specs = [bspec(tuple(s.shape[1:])) for s in out_shape]

    def kernel(*refs):
        vals = [r[0] for r in refs[:n_in]]
        cvals = [r[0] for r in refs[n_in:n_in + n_consts]]
        flat_out = closed_body(*vals, *cvals)
        for r, v in zip(refs[n_in + n_consts:], flat_out):
            r[0] = v

    # rough cost: the learner dominates (15 network passes per update);
    # the env/act phases add a handful of tiny matvecs per step
    p = spec.dims.pad
    u = spec.num_updates if spec.learn else 0
    gemm_flops = 2 * spec.cfg.batch_size * p * p * NUM_LAYERS
    cost = pl.CostEstimate(
        flops=int(n * T * max(u, 1) * 15 * gemm_flops),
        bytes_accessed=int(sum(x.nbytes for x in aliased) * 3),
        transcendentals=int(n * T * max(u, 1) * spec.cfg.batch_size * p * 2))
    flat_out = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={i0 + j: j for j in range(len(aliased))},
        cost_estimate=cost,
        interpret=interpret,
    )(*flat_in, *(cv[None] for cv in consts))
    return _unflatten_outputs(list(flat_out), n_env)


def episode_fused_xla(operands: EpisodeOperands, *,
                      spec: EpisodeKernelSpec) -> EpisodeOutputs:
    """The megakernel's computation compiled by XLA: the identical
    per-session body vmapped over the session axis — same packed learner,
    same fusion islands, same float32 op order. The kernel's validation
    twin, and the megakernel formulation's CPU/GPU fallback."""
    return jax.vmap(lambda op: _episode_body(spec, op, barriers=True))(
        operands)
