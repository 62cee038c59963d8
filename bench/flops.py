"""Operations and bytes of the tuner's work, counted from the widths.

One DDPG update (``core/ddpg.py`` ``_ddpg_step``, paper sec. II-C) on a
minibatch of B transitions, actor sizes (k, h1, h2, m), critic sizes
(k + m, h1, h2, 1):

  critic step   target actor and target critic forward on s2, critic
                forward on (s, a), critic backward (weight gradients, and
                input gradients of every layer but the first)
  actor step    actor forward, critic forward on (s, mu(s)), critic
                backward to its input (input gradients of every layer, no
                weight gradients), actor backward (weight gradients, input
                gradients of every layer but the first)
  optimizers    Adam on both networks: 14 operations per parameter
                (moments 7, bias correction and step 7)
  targets       Polyak averaging of both: 3 operations per parameter

A matmul of [B, i] by [i, o] counts 2 B i o. Activations, the sigmoid head
and the loss reductions are left out (each is under 1% of the matmuls at
these widths). Bytes are what one learner call must move between HBM and
the core at the least: every parameter, target and both Adam moments read
once and written once (float32), and each update's minibatch read once.
The per-update metrics the program also computes (losses, mean Q) are not
needed by the tuner and are not counted.
"""

from __future__ import annotations

ADAM_OPS_PER_PARAM = 14
POLYAK_OPS_PER_PARAM = 3
F32 = 4


def _layers(sizes):
    return list(zip(sizes[:-1], sizes[1:]))


def _fwd(b, sizes):
    return sum(2 * b * i * o for i, o in _layers(sizes))


def _input_grads(b, sizes, first: bool):
    layers = _layers(sizes)
    return sum(2 * b * i * o for i, o in (layers if first else layers[1:]))


def param_count(sizes) -> int:
    return sum(i * o + o for i, o in _layers(sizes))


def net_sizes(k: int, m: int, hidden) -> tuple:
    return (k, *hidden, m), (k + m, *hidden, 1)


def update_flops(k: int, m: int, hidden, batch: int) -> int:
    """Operations of one DDPG update."""
    actor, critic = net_sizes(k, m, hidden)
    critic_step = (_fwd(batch, actor) + _fwd(batch, critic)
                   + _fwd(batch, critic)
                   + _fwd(batch, critic) + _input_grads(batch, critic, False))
    actor_step = (_fwd(batch, actor) + _fwd(batch, critic)
                  + _input_grads(batch, critic, True)
                  + _fwd(batch, actor) + _input_grads(batch, actor, False))
    params = param_count(actor) + param_count(critic)
    return (critic_step + actor_step
            + (ADAM_OPS_PER_PARAM + POLYAK_OPS_PER_PARAM) * params)


def learner_call_bytes(k: int, m: int, hidden, batch: int,
                       updates: int) -> int:
    """Least HBM bytes of one session's learner call (``updates`` updates)."""
    actor, critic = net_sizes(k, m, hidden)
    params = param_count(actor) + param_count(critic)
    state = 4 * params * F32            # params, targets, mu, nu
    minibatches = updates * batch * (k + m + 1 + k) * F32
    return 2 * state + minibatches


def act_flops(k: int, m: int, hidden) -> int:
    """The actor forward that picks one session's next configuration."""
    return _fwd(1, net_sizes(k, m, hidden)[0])


def session_step_flops(k: int, m: int, hidden, batch: int,
                       updates: int) -> int:
    """One session-step: act plus ``updates`` learner updates."""
    return act_flops(k, m, hidden) + updates * update_flops(k, m, hidden,
                                                            batch)
