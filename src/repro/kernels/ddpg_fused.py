"""Fused DDPG inner loop — Pallas TPU kernel for the tuning hot path.

The reproduction's hot spot is not a transformer layer: it is the paper's
Table III inner loop — ``updates_per_step`` (96) *sequential* DDPG updates of
tiny (64, 64)-hidden MLPs at minibatch 16, repeated for every tuning session
in a fleet. All four parameter sets (actor, critic, and their Polyak targets)
plus both Adam moment sets total a few hundred KB, so the entire learner
state fits in VMEM with room to spare; what kills throughput off-TPU is
round-tripping those parameters through memory between 96 latency-dominated
micro-updates.

``ddpg_fused_learn`` runs the whole inner loop as ONE kernel:

  * the grid is the fleet session axis — each program instance owns one
    session's learner and runs its 96 updates start to finish;
  * the four parameter sets and both Adam moment sets are loaded into VMEM
    once, carried through a ``fori_loop`` over updates, and written back
    once (``input_output_aliases`` makes the update in-place);
  * minibatches are pre-gathered on the host side of the call (one take per
    buffer array — see ``core.ddpg.gather_minibatches``) and handed to the
    kernel as ``[num_updates, batch, P]`` blocks, so the kernel reads them
    with a cheap dynamic index per update, no gathers inside.

Packed layout (``pack_params`` / ``unpack_params``): every layer is
zero-padded to a ``[P, P]`` tile (``P = pad_width(...)``, a multiple of 64),
and the four networks are stacked on a leading net axis:

    weights  [4, L, P, P]   nets: actor, critic, actor_targ, critic_targ
    biases   [4, L, P]
    mom_w    [2, 2, L, P, P] (net: actor/critic) x (moment: mu/nu)
    mom_b    [2, 2, L, P]
    counts   [2] i32         Adam step counts (actor, critic)

Zero padding is self-preserving: padded input rows and output columns get
exactly-zero gradients (the sigmoid head is masked to the real action lanes,
the critic reads lane 0 only), so Adam moments and Polyak targets stay zero
in the padding forever — pinned by tests/test_ddpg_fused.py.

The same packed update step (``packed_update``) is also compiled directly by
XLA (``ddpg_fused_xla``) — that is the "fleet-batched GEMM" formulation of
the fallback. On CPU the blocked [P, P] GEMMs lose to the unpadded scan
(see benchmarks/fleet_throughput.py::bench_learner_paths), so the CPU
default stays ``core.ddpg``'s pre-gathered scan; the packed path is the
kernel's oracle-validated twin and the TPU shape of the computation.

Adam hyperparameters are ``repro.optim.adam``'s defaults (b1=0.9, b2=0.999,
eps=1e-8) — the only transforms ``core.ddpg`` ever builds; the dispatcher
(``kernels.ops.ddpg_inner_loop``) verifies the optimizer-state structure
before routing here.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ADAM_B1 = 0.9
_ADAM_B2 = 0.999
_ADAM_EPS = 1e-8
NUM_NETS = 4      # actor, critic, actor_targ, critic_targ
NUM_LAYERS = 3    # two hidden layers + head (the paper's MLPs)
# Every learner matmul runs at full float32 precision, in the kernel and in
# every XLA formulation alike (``core.ddpg.mlp_apply`` uses this constant):
# XLA on TPU otherwise rounds f32 dot operands to bfloat16, which would make
# the kernel and the XLA learner disagree by far more than f32 rounding. The
# CPU backend always computes f32 dots exactly, so CPU results are unchanged.
PRECISION = jax.lax.Precision.HIGHEST


class PackedDims(NamedTuple):
    """Static shape info for the packed layout (hashable, jit-friendly)."""

    state_dim: int
    action_dim: int
    hidden: tuple
    pad: int

    @property
    def actor_sizes(self) -> tuple:
        return (self.state_dim, *self.hidden, self.action_dim)

    @property
    def critic_sizes(self) -> tuple:
        return (self.state_dim + self.action_dim, *self.hidden, 1)


def pad_width(state_dim: int, action_dim: int, hidden: tuple) -> int:
    """Lane width P: every layer dimension padded up to a multiple of 64."""
    widest = max(state_dim + action_dim, action_dim, 1, *hidden)
    return max(64, -(-widest // 64) * 64)


def packed_dims(state_dim: int, action_dim: int, hidden: tuple) -> PackedDims:
    if len(hidden) != NUM_LAYERS - 1:
        raise ValueError(
            f"packed layout supports {NUM_LAYERS - 1} hidden layers, "
            f"got hidden={hidden!r}")
    return PackedDims(state_dim, action_dim, tuple(hidden),
                      pad_width(state_dim, action_dim, hidden))


def _pack_net(layers, dims: PackedDims):
    """list of {'w','b'} -> (w [L,P,P], b [L,P]), zero-padded."""
    p = dims.pad
    ws, bs = [], []
    for layer in layers:
        win, wout = layer["w"].shape[-2:]
        ws.append(jnp.zeros((p, p), jnp.float32).at[:win, :wout]
                  .set(layer["w"]))
        bs.append(jnp.zeros((p,), jnp.float32).at[:wout].set(layer["b"]))
    return jnp.stack(ws), jnp.stack(bs)


def _unpack_net(w, b, sizes):
    """(w [L,P,P], b [L,P]) -> list of {'w','b'} at the real layer sizes."""
    return [{"w": w[i, :fin, :fout], "b": b[i, :fout]}
            for i, (fin, fout) in enumerate(zip(sizes[:-1], sizes[1:]))]


def pack_params(actor, critic, actor_targ, critic_targ,
                actor_mu, actor_nu, critic_mu, critic_nu,
                actor_count, critic_count, dims: PackedDims):
    """Pytree learner state -> (weights, biases, mom_w, mom_b, counts)."""
    nets = [_pack_net(n, dims)
            for n in (actor, critic, actor_targ, critic_targ)]
    weights = jnp.stack([w for w, _ in nets])
    biases = jnp.stack([b for _, b in nets])
    moms = [[_pack_net(m, dims) for m in (mu, nu)]
            for mu, nu in ((actor_mu, actor_nu), (critic_mu, critic_nu))]
    mom_w = jnp.stack([jnp.stack([w for w, _ in net]) for net in moms])
    mom_b = jnp.stack([jnp.stack([b for _, b in net]) for net in moms])
    counts = jnp.stack([jnp.asarray(actor_count, jnp.int32),
                        jnp.asarray(critic_count, jnp.int32)])
    return weights, biases, mom_w, mom_b, counts


def unpack_params(weights, biases, mom_w, mom_b, counts, dims: PackedDims):
    """Inverse of ``pack_params`` -> dict of pytrees at the real sizes."""
    sizes = (dims.actor_sizes, dims.critic_sizes,
             dims.actor_sizes, dims.critic_sizes)
    nets = [_unpack_net(weights[i], biases[i], sz)
            for i, sz in enumerate(sizes)]
    return {
        "actor": nets[0], "critic": nets[1],
        "actor_targ": nets[2], "critic_targ": nets[3],
        "actor_mu": _unpack_net(mom_w[0, 0], mom_b[0, 0], dims.actor_sizes),
        "actor_nu": _unpack_net(mom_w[0, 1], mom_b[0, 1], dims.actor_sizes),
        "critic_mu": _unpack_net(mom_w[1, 0], mom_b[1, 0], dims.critic_sizes),
        "critic_nu": _unpack_net(mom_w[1, 1], mom_b[1, 1], dims.critic_sizes),
        "actor_count": counts[0], "critic_count": counts[1],
    }


def pack_minibatches(batches, dims: PackedDims):
    """Pre-gathered minibatches -> padded kernel inputs.

    ``batches`` is (s, a, r, s2), each ``[..., U, B, dim]``. Returns
    (sx, cx, s2x, r): actor input, critic input (state lanes then action
    lanes) and next-state input, zero-padded to P lanes. Pure concatenation —
    exact, and hoisted out of the update loop entirely.
    """
    s, a, r, s2 = batches
    k, m, p = dims.state_dim, dims.action_dim, dims.pad
    zk = jnp.zeros((*s.shape[:-1], p - k), jnp.float32)
    zc = jnp.zeros((*s.shape[:-1], p - k - m), jnp.float32)
    sx = jnp.concatenate([s, zk], axis=-1)
    s2x = jnp.concatenate([s2, zk], axis=-1)
    cx = jnp.concatenate([s, a, zc], axis=-1)
    return sx, cx, s2x, r


def reward_lanes(r, pad: int):
    """Rewards ``[..., B]`` -> ``[..., B, pad]`` tiles with the reward in
    lane 0 and exact zeros elsewhere: the layout of the critic's output
    tile (see ``_critic_fwd``), so the Bellman target is built without
    slicing a single lane out of a tile."""
    lane0 = jnp.arange(pad) == 0
    return jnp.where(lane0, r[..., None], jnp.float32(0.0))


# ---------------------------------------------------------------------------
# The packed update step (shared by the kernel body and the XLA twin)
# ---------------------------------------------------------------------------

def _mlp_fwd(w, b, x):
    """3-layer padded MLP, ReLU trunk, linear head. Zero padding is a fixed
    point of the trunk: relu(0 @ W + 0) = 0 on every padded lane."""
    h = x
    for i in range(NUM_LAYERS - 1):
        h = jax.nn.relu(jnp.dot(h, w[i], precision=PRECISION,
                                preferred_element_type=jnp.float32) + b[i])
    return jnp.dot(h, w[NUM_LAYERS - 1], precision=PRECISION,
                   preferred_element_type=jnp.float32) + b[NUM_LAYERS - 1]


def _actor_fwd(w, b, x, act_mask):
    """sigmoid head, masked to the real action lanes (sigmoid(0) = 0.5 on
    padding would otherwise leak into the critic input and its gradients)."""
    return jax.nn.sigmoid(_mlp_fwd(w, b, x)) * act_mask


def _critic_fwd(w, b, x):
    """Q as a ``[B, P]`` tile: lane 0 holds Q and every other lane is an
    exact zero (the head's padded columns and bias are zero). Losses reduce
    the whole tile (``_batch_mean``): a one-lane column slice cannot be
    relaid out by the TPU compiler (``Offset change``)."""
    return _mlp_fwd(w, b, x)


def _batch_mean(tile):
    """Mean over the batch rows of a lane-0 tile (``_critic_fwd``)."""
    return jnp.sum(tile) / tile.shape[0]


def _adam(count, mu_w, mu_b, nu_w, nu_b, gw, gb, w, b, lr):
    """One ``optim.adam`` step on a packed (w, b) pair — the same op order as
    ``optim.transform.scale_by_adam`` + ``scale(-lr)`` + ``apply_updates``,
    so the packed learner matches ``ddpg_update`` to float32 rounding."""
    count = count + 1
    cf = count.astype(jnp.float32)
    c1 = 1 - _ADAM_B1 ** cf
    c2 = 1 - _ADAM_B2 ** cf
    mu_w = _ADAM_B1 * mu_w + (1 - _ADAM_B1) * gw
    mu_b = _ADAM_B1 * mu_b + (1 - _ADAM_B1) * gb
    nu_w = _ADAM_B2 * nu_w + (1 - _ADAM_B2) * jnp.square(gw)
    nu_b = _ADAM_B2 * nu_b + (1 - _ADAM_B2) * jnp.square(gb)
    w = w + (mu_w / c1) / (jnp.sqrt(nu_w / c2) + _ADAM_EPS) * (-lr)
    b = b + (mu_b / c1) / (jnp.sqrt(nu_b / c2) + _ADAM_EPS) * (-lr)
    return count, mu_w, mu_b, nu_w, nu_b, w, b


def _place_actions(base_x, actions, dims: PackedDims):
    """Write actions into the critic-input action lanes [k, k+m).

    ``base_x`` has exact zeros there, so addition is exact placement."""
    k, m, p = dims.state_dim, dims.action_dim, dims.pad
    rows = actions.shape[0]
    return base_x + jnp.concatenate(
        [jnp.zeros((rows, k), jnp.float32), actions[:, :m],
         jnp.zeros((rows, p - k - m), jnp.float32)], axis=1)


def packed_update(carry, batch, dims: PackedDims, gamma, tau,
                  actor_lr, critic_lr, act_mask):
    """One DDPG update on the packed layout: the float32 arithmetic of
    ``core.ddpg._ddpg_step``, on [P, P]-blocked tensors.

    ``carry`` = (weights [4,L,P,P], biases [4,L,P], mom_w [2,2,L,P,P],
    mom_b [2,2,L,P], counts [2] i32); ``batch`` = (sx, cx, s2x, rx) for one
    minibatch, the rewards as ``reward_lanes`` tiles. Returns (carry,
    (critic_loss, actor_loss, q_mean)).
    """
    weights, biases, mom_w, mom_b, counts = carry
    sx, cx, s2x, rx = batch

    # --- critic: Bellman regression against the frozen targets -------------
    a2 = _actor_fwd(weights[2], biases[2], s2x, act_mask)
    c2x = _place_actions(s2x, a2, dims)
    q_targ = jax.lax.stop_gradient(
        rx + gamma * _critic_fwd(weights[3], biases[3], c2x))

    def critic_loss_fn(wb):
        w, b = wb
        return _batch_mean(jnp.square(_critic_fwd(w, b, cx) - q_targ))

    critic_loss, (gcw, gcb) = jax.value_and_grad(critic_loss_fn)(
        (weights[1], biases[1]))
    (ccnt, cmu_w, cmu_b, cnu_w, cnu_b, cw, cb) = _adam(
        counts[1], mom_w[1, 0], mom_b[1, 0], mom_w[1, 1], mom_b[1, 1],
        gcw, gcb, weights[1], biases[1], critic_lr)

    # --- actor: ascend Q(s, mu(s)) with the updated critic frozen ----------
    def actor_loss_fn(wb):
        w, b = wb
        mu = _actor_fwd(w, b, sx, act_mask)
        return -_batch_mean(_critic_fwd(cw, cb, _place_actions(sx, mu, dims)))

    actor_loss, (gaw, gab) = jax.value_and_grad(actor_loss_fn)(
        (weights[0], biases[0]))
    (acnt, amu_w, amu_b, anu_w, anu_b, aw, ab) = _adam(
        counts[0], mom_w[0, 0], mom_b[0, 0], mom_w[0, 1], mom_b[0, 1],
        gaw, gab, weights[0], biases[0], actor_lr)

    # --- Polyak targets + metrics ------------------------------------------
    atw = (1 - tau) * weights[2] + tau * aw
    atb = (1 - tau) * biases[2] + tau * ab
    ctw = (1 - tau) * weights[3] + tau * cw
    ctb = (1 - tau) * biases[3] + tau * cb
    q_mean = _batch_mean(_critic_fwd(cw, cb, cx))

    carry = (jnp.stack([aw, cw, atw, ctw]), jnp.stack([ab, cb, atb, ctb]),
             jnp.stack([jnp.stack([amu_w, anu_w]),
                        jnp.stack([cmu_w, cnu_w])]),
             jnp.stack([jnp.stack([amu_b, anu_b]),
                        jnp.stack([cmu_b, cnu_b])]),
             jnp.stack([acnt, ccnt]))
    return carry, (critic_loss, actor_loss, q_mean)


# ---------------------------------------------------------------------------
# Pallas kernel: whole inner loop, params resident in VMEM, grid = sessions
# ---------------------------------------------------------------------------

def _ddpg_kernel(dims: PackedDims, gamma, tau, actor_lr, critic_lr,
                 num_updates: int,
                 cnt_ref, sx_ref, cx_ref, s2x_ref, rx_ref,
                 w_ref, b_ref, mw_ref, mb_ref,
                 ow_ref, ob_ref, omw_ref, omb_ref, met_ref):
    act_mask = (jax.lax.broadcasted_iota(jnp.int32, (1, dims.pad), 1)
                < dims.action_dim).astype(jnp.float32)
    # the learner state lives in the output blocks for the whole loop: all
    # four parameter sets + both moment sets stay in VMEM, nothing
    # round-trips to HBM between the 96 updates
    ow_ref[...] = w_ref[...]
    ob_ref[...] = b_ref[...]
    omw_ref[...] = mw_ref[...]
    omb_ref[...] = mb_ref[...]

    def body(u, carry):
        # Adam counts before update u (SMEM scalars), as (1, 1) vectors: the
        # bias corrections need a vector power, the TPU has no scalar one
        counts = tuple(jnp.full((1, 1), cnt_ref[0, 0, j] + u, jnp.int32)
                       for j in range(2))
        params = (ow_ref[0], ob_ref[0], omw_ref[0], omb_ref[0], counts)
        # the minibatch is read by indexing the refs, not by slicing values
        batch = (sx_ref[0, u], cx_ref[0, u], s2x_ref[0, u], rx_ref[0, u])
        (w, b, mw, mb, _), met = packed_update(
            params, batch, dims, gamma, tau, actor_lr, critic_lr, act_mask)
        ow_ref[0] = w
        ob_ref[0] = b
        omw_ref[0] = mw
        omb_ref[0] = mb
        for j, v in enumerate(met):
            met_ref[0, 0, 3 * u + j] = v
        return carry

    jax.lax.fori_loop(0, num_updates, body, None)


def ddpg_fused_learn(packed, batches, *, dims: PackedDims, gamma: float,
                     tau: float, actor_lr: float, critic_lr: float,
                     interpret: bool = False):
    """Run the full ``num_updates`` inner loop as one Pallas kernel.

    ``packed`` = (weights, biases, mom_w, mom_b, counts) with a leading
    fleet axis N on every array; ``batches`` = ``pack_minibatches`` output,
    each ``[N, U, B, P]`` / ``[N, U, B]``. The grid is (N,): each session's
    learner runs as an independent program instance. Returns (packed',
    metrics dict of [N, U] arrays). Parameter inputs are aliased to the
    outputs — callers must treat ``packed`` as consumed.

    Layout for the TPU compiler: Adam counts and per-update metrics are
    scalars, so they live in SMEM; rewards enter as ``reward_lanes`` tiles.
    Each update adds one to both counts, so the counts out are computed
    here, exactly.
    """
    weights, biases, mom_w, mom_b, counts = packed
    sx, cx, s2x, r = batches
    n, u = sx.shape[0], sx.shape[1]
    p = dims.pad
    rx = reward_lanes(r, p)
    cnt = counts.reshape(n, 1, 2)

    def bspec(shape, **kw):
        nd = len(shape)
        return pl.BlockSpec((1, *shape), lambda i, nd=nd: (i,) + (0,) * nd,
                            **kw)

    smem = {"memory_space": pltpu.SMEM}
    in_specs = [bspec(cnt.shape[1:], **smem),
                bspec(sx.shape[1:]), bspec(cx.shape[1:]),
                bspec(s2x.shape[1:]), bspec(rx.shape[1:]),
                bspec(weights.shape[1:]), bspec(biases.shape[1:]),
                bspec(mom_w.shape[1:]), bspec(mom_b.shape[1:])]
    out_specs = [bspec(weights.shape[1:]), bspec(biases.shape[1:]),
                 bspec(mom_w.shape[1:]), bspec(mom_b.shape[1:]),
                 bspec((1, 3 * u), **smem)]
    out_shape = [jax.ShapeDtypeStruct(weights.shape, jnp.float32),
                 jax.ShapeDtypeStruct(biases.shape, jnp.float32),
                 jax.ShapeDtypeStruct(mom_w.shape, jnp.float32),
                 jax.ShapeDtypeStruct(mom_b.shape, jnp.float32),
                 jax.ShapeDtypeStruct((n, 1, 3 * u), jnp.float32)]
    # rough cost: fwd+bwd over 5 network passes per update (helps scheduling)
    gemm_flops = 2 * sx.shape[2] * p * p * NUM_LAYERS
    cost = pl.CostEstimate(flops=int(n * u * 15 * gemm_flops),
                           bytes_accessed=int(weights.nbytes * 3),
                           transcendentals=int(n * u * sx.shape[2] * p * 2))
    kernel = functools.partial(_ddpg_kernel, dims, gamma, tau, actor_lr,
                               critic_lr, u)
    ow, ob, omw, omb, met = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={5: 0, 6: 1, 7: 2, 8: 3},
        cost_estimate=cost,
        interpret=interpret,
    )(cnt, sx, cx, s2x, rx, weights, biases, mom_w, mom_b)
    met = met.reshape(n, u, 3)
    metrics = {"critic_loss": met[..., 0], "actor_loss": met[..., 1],
               "q_mean": met[..., 2]}
    return (ow, ob, omw, omb, counts + u), metrics


# ---------------------------------------------------------------------------
# XLA twin: the same packed update as a lax.scan (fleet-batched GEMM path)
# ---------------------------------------------------------------------------

def ddpg_fused_xla(packed, batches, *, dims: PackedDims, gamma: float,
                   tau: float, actor_lr: float, critic_lr: float):
    """The kernel's computation compiled by XLA: scan over updates, vmapped
    over the fleet axis. Same packed blocks, same float32 op order — used to
    validate the kernel and to benchmark the blocked-GEMM formulation against
    the unpadded scan on CPU/GPU."""
    act_mask = (jnp.arange(dims.pad) < dims.action_dim
                ).astype(jnp.float32)[None, :]

    def one_session(carry, batch):
        def body(c, bt):
            c, (cl, al, qm) = packed_update(
                c, bt, dims, gamma, tau, actor_lr, critic_lr, act_mask)
            return c, jnp.stack([cl, al, qm])
        return jax.lax.scan(body, carry, batch)

    sx, cx, s2x, r = batches
    batches = (sx, cx, s2x, reward_lanes(r, dims.pad))
    packed, met = jax.vmap(one_session)(packed, batches)
    metrics = {"critic_loss": met[..., 0], "actor_loss": met[..., 1],
               "q_mean": met[..., 2]}
    return packed, metrics
