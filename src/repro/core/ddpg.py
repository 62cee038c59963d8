"""DDPG (Lillicrap et al.) in pure JAX — the paper's RL algorithm (§II-C).

The actor maps the metric state s_t in [0,1]^k to an action a in [0,1]^m (one
coordinate per static parameter; the action-mapping layer turns it into a real
configuration). The critic is the Q function Q_phi(s, a). Both are small MLPs —
the paper trains them on a single RTX 5000; at this size CPU training is faithful.

Learning follows §II-C exactly:
  critic:  argmin_phi E[(Q_phi(s,a) - (r + gamma * Q_targ(s', mu_targ(s'))))^2]
  actor:   argmax_theta E[Q_phi(s, mu_theta(s))]
with Polyak-averaged target networks for both.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.kernels.ddpg_fused import PRECISION


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key: jax.Array, sizes: Sequence[int]) -> list:
    """He-uniform MLP init; returns a list of {"w","b"} layer dicts."""
    params = []
    keys = jax.random.split(key, len(sizes) - 1)
    for k, (fan_in, fan_out) in zip(keys, zip(sizes[:-1], sizes[1:])):
        bound = float(np.sqrt(6.0 / fan_in))
        w = jax.random.uniform(k, (fan_in, fan_out), jnp.float32, -bound, bound)
        params.append({"w": w, "b": jnp.zeros((fan_out,), jnp.float32)})
    return params


def mlp_apply(params: list, x: jnp.ndarray) -> jnp.ndarray:
    """ReLU MLP; no activation on the final layer (callers add their own).
    Matmuls run at the learner's explicit precision (``PRECISION``)."""
    for i, layer in enumerate(params):
        x = jnp.matmul(x, layer["w"], precision=PRECISION) + layer["b"]
        if i + 1 < len(params):
            x = jax.nn.relu(x)
    return x


def actor_apply(params: list, state: jnp.ndarray) -> jnp.ndarray:
    """Deterministic policy mu_theta: state -> action in [0,1]^m (sigmoid head)."""
    return jax.nn.sigmoid(mlp_apply(params, state))


def critic_apply(params: list, state: jnp.ndarray, action: jnp.ndarray) -> jnp.ndarray:
    """Q_phi(s, a) -> scalar (last axis squeezed)."""
    x = jnp.concatenate([state, action], axis=-1)
    return jnp.squeeze(mlp_apply(params, x), axis=-1)


# ---------------------------------------------------------------------------
# DDPG learner state + update
# ---------------------------------------------------------------------------

class DDPGConfig(NamedTuple):
    state_dim: int
    action_dim: int
    hidden: tuple = (64, 64)
    actor_lr: float = 1e-3
    critic_lr: float = 2e-3
    gamma: float = 0.9          # tuning steps are near-bandit; short horizon
    tau: float = 0.02           # Polyak coefficient for target networks
    updates_per_step: int = 96  # gradient steps per environment step (Table III)
    batch_size: int = 16

    @classmethod
    def for_space(cls, state_dim: int, space, **overrides) -> "DDPGConfig":
        """Size the learner from a ``ParamSpace``: the actor head emits one
        coordinate per static parameter (paper §II-C-1), so ``action_dim`` is
        ``space.dim`` — never a hand-maintained constant. The hidden trunk is
        dimensionality-independent (the paper's single small MLP), which keeps
        the fused learn step's cost flat as spaces grow from 2-D to 8-D.
        """
        return cls(state_dim=state_dim, action_dim=space.dim, **overrides)

    @classmethod
    def for_env(cls, env, **overrides) -> "DDPGConfig":
        """Derive state/action dims from a ``TuningEnvironment``: the state is
        its metric vector, the action its ``param_space``."""
        return cls.for_space(env.state_dim, env.param_space, **overrides)


class DDPGState(NamedTuple):
    actor: Any
    critic: Any
    actor_targ: Any
    critic_targ: Any
    actor_opt: Any
    critic_opt: Any
    step: jnp.ndarray


def _init_state(key: jax.Array, cfg: DDPGConfig,
                actor_tx: optim.GradientTransformation,
                critic_tx: optim.GradientTransformation) -> DDPGState:
    """Fresh learner state for one session; target nets start as copies."""
    ka, kc = jax.random.split(key)
    actor = mlp_init(ka, (cfg.state_dim, *cfg.hidden, cfg.action_dim))
    critic = mlp_init(kc, (cfg.state_dim + cfg.action_dim, *cfg.hidden, 1))
    return DDPGState(
        actor=actor,
        critic=critic,
        actor_targ=jax.tree_util.tree_map(jnp.copy, actor),
        critic_targ=jax.tree_util.tree_map(jnp.copy, critic),
        actor_opt=actor_tx.init(actor),
        critic_opt=critic_tx.init(critic),
        step=jnp.zeros((), jnp.int32),
    )


def ddpg_init(key: jax.Array, cfg: DDPGConfig) -> tuple:
    """Returns (DDPGState, (actor_tx, critic_tx))."""
    actor_tx = optim.adam(cfg.actor_lr)
    critic_tx = optim.adam(cfg.critic_lr)
    return _init_state(key, cfg, actor_tx, critic_tx), (actor_tx, critic_tx)


def _polyak(target, online, tau: float):
    return jax.tree_util.tree_map(lambda t, o: (1 - tau) * t + tau * o, target, online)


def _ddpg_step(
    state: DDPGState,
    batch: tuple,  # (s, a, r, s2) each [B, ...] float32
    cfg: DDPGConfig,
    actor_tx: optim.GradientTransformation,
    critic_tx: optim.GradientTransformation,
) -> tuple:
    """One critic + one actor gradient step + Polyak. Returns (state, metrics).

    Pure (un-jitted) body shared by ``ddpg_update`` (one jitted call per
    minibatch), ``ddpg_learn_scan`` (the whole inner loop fused into one
    ``lax.scan``) and the vmapped fleet learner.
    """
    s, a, r, s2 = batch

    # --- critic: Bellman regression against the frozen targets -------------
    a2 = actor_apply(state.actor_targ, s2)
    q_targ = r + cfg.gamma * critic_apply(state.critic_targ, s2, a2)
    q_targ = jax.lax.stop_gradient(q_targ)

    def critic_loss_fn(critic):
        q = critic_apply(critic, s, a)
        return jnp.mean(jnp.square(q - q_targ))

    critic_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(state.critic)
    c_updates, critic_opt = critic_tx.update(critic_grads, state.critic_opt, state.critic)
    critic = optim.apply_updates(state.critic, c_updates)

    # --- actor: ascend Q_phi(s, mu_theta(s)) with the critic frozen --------
    def actor_loss_fn(actor):
        return -jnp.mean(critic_apply(critic, s, actor_apply(actor, s)))

    actor_loss, actor_grads = jax.value_and_grad(actor_loss_fn)(state.actor)
    a_updates, actor_opt = actor_tx.update(actor_grads, state.actor_opt, state.actor)
    actor = optim.apply_updates(state.actor, a_updates)

    new_state = DDPGState(
        actor=actor,
        critic=critic,
        actor_targ=_polyak(state.actor_targ, actor, cfg.tau),
        critic_targ=_polyak(state.critic_targ, critic, cfg.tau),
        actor_opt=actor_opt,
        critic_opt=critic_opt,
        step=state.step + 1,
    )
    metrics = {"critic_loss": critic_loss, "actor_loss": actor_loss,
               "q_mean": jnp.mean(critic_apply(critic, s, a))}
    return new_state, metrics


ddpg_update = functools.partial(
    jax.jit, static_argnames=("cfg", "actor_tx", "critic_tx")
)(_ddpg_step)


# ---------------------------------------------------------------------------
# Fused learner: the whole updates_per_step inner loop as one XLA program
# ---------------------------------------------------------------------------

def sample_minibatch_indices(key: jax.Array, num_updates: int, batch_size: int,
                             size: jnp.ndarray) -> jnp.ndarray:
    """[num_updates, batch_size] uniform-with-replacement indices in [0, size).

    On-device replacement for the host-side ``rng.integers`` loop; ``size`` is
    a dynamic operand so a growing buffer never retriggers compilation.

    Precondition: ``size >= 1``. An empty buffer has nothing to sample, and
    there is deliberately no silent clamp here (an earlier ``maximum(size, 1)``
    made an empty buffer sample slot 0 — all-zero garbage transitions — with
    no error). The host entry points (``ddpg_learn_scan``,
    ``fleet_learn_scan``) raise on a concrete ``size == 0``; in-graph callers
    must guarantee the invariant structurally, as the episode engine does by
    writing the step's transition to the FIFO *before* learning
    (``core.episode``).
    """
    return jax.random.randint(key, (num_updates, batch_size), 0, size)


def gather_minibatches(data: tuple, idx: jnp.ndarray) -> tuple:
    """Gather every update's minibatch in ONE take per buffer array.

    ``idx`` is ``[num_updates, batch_size]``; returns (s, a, r, s2) with
    shape ``[num_updates, batch_size, ...]``. Flattening the index matrix
    turns ``num_updates`` (96) per-update gathers into a single contiguous
    pass over the replay storage per environment step. Gathers are exact, so
    the batches — and everything the learner computes from them — are
    bitwise-identical to the per-update ``s[ix]`` path (pinned by
    tests/test_ddpg_fused.py).
    """
    flat = idx.reshape(-1)
    return tuple(x[flat].reshape(idx.shape + x.shape[1:]) for x in data)


def _packable(state: "DDPGState", cfg: "DDPGConfig") -> bool:
    """True when the learner state fits the fused kernel's packed layout:
    two hidden layers (the paper's MLPs) and stock ``optim.adam`` transforms
    (state ``(ScaleByAdamState, ())``).

    CONTRACT: the kernel path derives its optimizer math entirely from
    ``cfg`` — ``cfg.actor_lr``/``cfg.critic_lr`` plus adam's default
    b1/b2/eps — because transforms are opaque closures that cannot be
    introspected. Every core construction path (``ddpg_init``,
    ``fleet_init``, the agents) builds the transforms from exactly those
    cfg fields, so the two are never out of sync there; callers that hand
    ``ddpg_learn_scan`` hand-built transforms disagreeing with ``cfg`` must
    not enable ``REPRO_KERNELS=pallas|interpret`` (the XLA path honors the
    transforms, the kernel path honors ``cfg``)."""
    if len(cfg.hidden) != 2:
        return False
    for opt in (state.actor_opt, state.critic_opt):
        if not (isinstance(opt, tuple) and len(opt) == 2
                and hasattr(opt[0], "mu") and hasattr(opt[0], "nu")
                and hasattr(opt[0], "count")):
            return False
    return True


def _learn_packed(state, batches, cfg, num_updates, mode="pallas"):
    """Route one session's pre-gathered inner loop through the fused-kernel
    dispatch (``kernels.ops.ddpg_inner_loop``), packing the learner state
    into the [P, P]-blocked VMEM layout and back. vmap-safe: under the fleet
    vmap the kernel's grid runs one session per step. Only
    ``REPRO_KERNELS=pallas`` / ``interpret`` route here; ``auto`` keeps the
    scan over ``_ddpg_step`` on every platform, which the fleet vmap turns
    into session-batched dots (faster on a TPU, see
    ``kernels.ops.ddpg_kernel_mode``)."""
    from repro.kernels import ddpg_fused as fused
    from repro.kernels import ops
    from repro.optim.transform import ScaleByAdamState

    dims = fused.packed_dims(cfg.state_dim, cfg.action_dim, cfg.hidden)
    a_adam, a_rest = state.actor_opt[0], state.actor_opt[1:]
    c_adam, c_rest = state.critic_opt[0], state.critic_opt[1:]
    packed = fused.pack_params(
        state.actor, state.critic, state.actor_targ, state.critic_targ,
        a_adam.mu, a_adam.nu, c_adam.mu, c_adam.nu,
        a_adam.count, c_adam.count, dims)
    kb = fused.pack_minibatches(batches, dims)
    packed = jax.tree_util.tree_map(lambda x: x[None], packed)
    kb = jax.tree_util.tree_map(lambda x: x[None], kb)
    packed, metrics = ops.ddpg_inner_loop(
        packed, kb, dims=dims, gamma=cfg.gamma, tau=cfg.tau,
        actor_lr=cfg.actor_lr, critic_lr=cfg.critic_lr, mode=mode)
    parts = fused.unpack_params(*jax.tree_util.tree_map(lambda x: x[0],
                                                        packed), dims)
    new_state = DDPGState(
        actor=parts["actor"],
        critic=parts["critic"],
        actor_targ=parts["actor_targ"],
        critic_targ=parts["critic_targ"],
        actor_opt=(ScaleByAdamState(count=parts["actor_count"],
                                    mu=parts["actor_mu"],
                                    nu=parts["actor_nu"]), *a_rest),
        critic_opt=(ScaleByAdamState(count=parts["critic_count"],
                                     mu=parts["critic_mu"],
                                     nu=parts["critic_nu"]), *c_rest),
        step=state.step + num_updates,
    )
    return new_state, jax.tree_util.tree_map(lambda x: x[0], metrics)


def _learn_scan(state, data, size, key, cfg, actor_tx, critic_tx, num_updates,
                kernel_mode=None):
    """Shared inner-loop body. ``kernel_mode`` ('pallas' / 'interpret' /
    ``None``) is a STATIC operand resolved by the host-level entry points
    (``ddpg_learn_scan``, ``fleet_learn_scan``, the episode-engine compile
    cache) — never read from the environment inside a trace, where a cached
    compilation would silently ignore a later mode change."""
    idx = sample_minibatch_indices(key, num_updates, cfg.batch_size, size)
    batches = gather_minibatches(data, idx)
    # f32 compute at gather: replay storage may be bf16 (opt-in compact
    # mode); minibatches are widened right after the gather so every
    # gradient step runs in float32. A same-dtype astype is the identity,
    # so the default f32 path is untouched (bitwise).
    batches = tuple(b.astype(jnp.float32) for b in batches)
    if kernel_mode is not None and _packable(state, cfg):
        return _learn_packed(state, batches, cfg, num_updates,
                             mode=kernel_mode)

    def body(st, batch):
        return _ddpg_step(st, batch, cfg, actor_tx, critic_tx)

    return jax.lax.scan(body, state, batches)


def _require_nonempty(size) -> None:
    """Host-path guard: raise on a concrete empty buffer instead of letting
    index sampling hit undefined maxval-0 behaviour (the silent-zero-index
    hazard). Traced sizes pass through — in-graph callers own the invariant
    (see ``sample_minibatch_indices``)."""
    if isinstance(size, jax.core.Tracer):
        return
    if int(np.min(np.asarray(size))) <= 0:
        raise ValueError(
            "cannot learn from an empty replay buffer: minibatch sampling "
            "needs size >= 1 valid rows (observe at least one transition "
            "before calling the fused learner)")


_ddpg_learn_scan_jit = functools.partial(
    jax.jit, static_argnames=("cfg", "actor_tx", "critic_tx", "num_updates",
                              "kernel_mode")
)(_learn_scan)


def ddpg_learn_scan(
    state: DDPGState,
    data: tuple,       # (s, a, r, s2), each [capacity, ...] — full buffer storage
    size: jnp.ndarray,  # number of valid rows in ``data`` (dynamic)
    key: jax.Array,
    cfg: DDPGConfig,
    actor_tx: optim.GradientTransformation,
    critic_tx: optim.GradientTransformation,
    num_updates: int,
) -> tuple:
    """``num_updates`` minibatch gradient steps as ONE jitted program.

    Equivalent to sampling ``num_updates`` batches with
    ``sample_minibatch_indices(key, ...)`` and applying ``ddpg_update`` to each
    in sequence, but with minibatch sampling on-device, all ``num_updates x
    batch_size`` rows gathered in one pre-pass (``gather_minibatches``), and
    the whole inner loop fused into a single ``jax.lax.scan`` — one dispatch
    per ``learn()`` instead of ``updates_per_step`` (96, Table III) dispatches
    plus a host round-trip per minibatch. Under ``REPRO_KERNELS=pallas`` /
    ``interpret`` the loop runs as the fused Pallas kernel instead
    (``kernels/ddpg_fused.py``) — on that path the optimizer hyperparameters
    come from ``cfg``, not from introspecting ``actor_tx``/``critic_tx``
    (see ``_packable``), matching how every core caller builds them.
    Raises ``ValueError`` on an empty buffer. Returns (state, metrics
    stacked over updates).
    """
    from repro.kernels import ops

    _require_nonempty(size)
    return _ddpg_learn_scan_jit(state, data, size, key, cfg, actor_tx,
                                critic_tx, num_updates,
                                kernel_mode=ops.ddpg_kernel_mode())


# ---------------------------------------------------------------------------
# Fleet: N independent DDPG learners batched over a leading session axis
# ---------------------------------------------------------------------------

def fleet_init(keys: jax.Array, cfg: DDPGConfig) -> tuple:
    """Initialize N independent learners from ``keys`` [N, key] in one shot.

    Returns (stacked DDPGState with leading session axis, (actor_tx,
    critic_tx)). Session i's parameters are identical to
    ``ddpg_init(keys[i], cfg)`` — JAX RNG is deterministic per key, so a fleet
    of one reproduces the single-agent init exactly.
    """
    actor_tx = optim.adam(cfg.actor_lr)
    critic_tx = optim.adam(cfg.critic_lr)
    init_one = functools.partial(_init_state, cfg=cfg, actor_tx=actor_tx,
                                 critic_tx=critic_tx)
    return jax.vmap(init_one)(keys), (actor_tx, critic_tx)


@jax.jit
def fleet_act(actors, states: jnp.ndarray) -> jnp.ndarray:
    """Deterministic policy actions for all sessions: [N, k] -> [N, m]."""
    return jax.vmap(actor_apply)(actors, states)


@functools.partial(
    jax.jit, static_argnames=("cfg", "actor_tx", "critic_tx", "num_updates",
                              "kernel_mode"))
def _fleet_learn_scan_jit(states, data, sizes, keys, cfg, actor_tx,
                          critic_tx, num_updates, kernel_mode):
    f = functools.partial(_learn_scan, cfg=cfg, actor_tx=actor_tx,
                          critic_tx=critic_tx, num_updates=num_updates,
                          kernel_mode=kernel_mode)
    return jax.vmap(f)(states, data, sizes, keys)


def fleet_learn_scan(
    states: DDPGState,  # stacked over sessions
    data: tuple,        # (s, a, r, s2), each [N, capacity, ...]
    sizes: jnp.ndarray,  # [N]
    keys: jax.Array,     # [N, key]
    cfg: DDPGConfig,
    actor_tx: optim.GradientTransformation,
    critic_tx: optim.GradientTransformation,
    num_updates: int,
) -> tuple:
    """vmap of ``ddpg_learn_scan`` over the session axis: the entire fleet's
    ``N x num_updates`` gradient steps are one XLA computation (or, under
    ``REPRO_KERNELS=pallas``/``interpret``, one Pallas kernel launch whose
    grid is the session axis). Raises ``ValueError`` if any session's buffer
    is empty (the fleet steps in lockstep, so sizes agree)."""
    from repro.kernels import ops

    _require_nonempty(sizes)
    return _fleet_learn_scan_jit(states, data, sizes, keys, cfg, actor_tx,
                                 critic_tx, num_updates,
                                 kernel_mode=ops.ddpg_kernel_mode())


# ---------------------------------------------------------------------------
# Exploration noise
# ---------------------------------------------------------------------------

class OUNoise:
    """Ornstein-Uhlenbeck process (standard DDPG exploration), with linear
    sigma decay so late tuning steps fine-tune rather than explore (§III-E:
    'Magpie ... then uses additional tuning steps for parameter fine-tuning')."""

    def __init__(self, dim: int, sigma: float = 0.40, theta: float = 0.15,
                 sigma_min: float = 0.05, decay_steps: int = 50, seed: int = 0):
        self.dim = dim
        self.sigma0 = sigma
        self.sigma_min = sigma_min
        self.theta = theta
        self.decay_steps = decay_steps
        self._rng = np.random.default_rng(seed)
        self._x = np.zeros(dim, np.float32)
        self._t = 0

    def reset(self) -> None:
        self._x[...] = 0.0

    def __call__(self) -> np.ndarray:
        frac = min(1.0, self._t / max(1, self.decay_steps))
        sigma = self.sigma0 + frac * (self.sigma_min - self.sigma0)
        self._x += -self.theta * self._x + sigma * self._rng.standard_normal(self.dim)
        self._t += 1
        return self._x.astype(np.float32)

    def state_dict(self) -> dict:
        return {"x": self._x.copy(), "t": self._t,
                "bitgen": self._rng.bit_generator.state}

    def load_state_dict(self, d: dict) -> None:
        self._x[...] = d["x"]
        self._t = int(d["t"])
        self._rng.bit_generator.state = d["bitgen"]
