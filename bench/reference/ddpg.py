"""Plain reference of the DDPG learner (paper sec. II-C, Table III).

Actor mu(s) = sigmoid(MLP(s)), critic Q(s, a) = MLP([s, a]), ReLU hidden
layers, He-uniform weights and zero biases drawn from the session's key.
One update on a minibatch (s, a, r, s2):

  critic   y = r + gamma Q'(s2, mu'(s2)); minimize mean (Q(s, a) - y)^2
  actor    maximize mean Q(s, mu(s)) under the critic just updated
  both     Adam (b1 0.9, b2 0.999, eps 1e-8), then Polyak targets
           t <- (1 - tau) t + tau p

Minibatches are drawn uniformly with replacement from the valid replay
rows by ``jax.random.randint`` on the step's learn key.

Every matmul goes through ``dot``, which computes in float32 at full
precision (``"highest"``), or in three bfloat16 passes (``"high"``): the
TPU's own Precision.HIGH there, and written out, forward and backward, on
backends that compute every float32 matmul exactly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

GAMMA, TAU = 0.9, 0.02
ACTOR_LR, CRITIC_LR = 1e-3, 2e-3
B1, B2, EPS = 0.9, 0.999, 1e-8
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _mm3(x, w):
    xh, xl = _split(x)
    wh, wl = _split(w)
    return _mm(xh, wh) + _mm(xh, wl) + _mm(xl, wh)


@jax.custom_vjp
def _dot_high(x, w):
    return _mm3(x, w)


def _dot_high_fwd(x, w):
    return _mm3(x, w), (x, w)


def _dot_high_bwd(res, g):
    x, w = res
    return _mm3(g, w.T), _mm3(x.T, g)


_dot_high.defvjp(_dot_high_fwd, _dot_high_bwd)


def dot(x, w, mode: str):
    if mode == "highest":
        return _mm(x, w)
    if jax.default_backend() == "tpu":
        # the chip's own three passes: the written-out split does not keep
        # its meaning under the TPU's compiler (PERF.md, Findings)
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGH)
    return _dot_high(x, w)


class Learner(NamedTuple):
    actor: list
    critic: list
    actor_targ: list
    critic_targ: list
    actor_mu: list
    actor_nu: list
    critic_mu: list
    critic_nu: list
    count: jnp.ndarray


def _init_mlp(key, sizes):
    layers = []
    keys = jax.random.split(key, len(sizes) - 1)
    for k, (fan_in, fan_out) in zip(keys, zip(sizes[:-1], sizes[1:])):
        bound = float(np.sqrt(6.0 / fan_in))
        layers.append({"w": jax.random.uniform(k, (fan_in, fan_out),
                                               jnp.float32, -bound, bound),
                       "b": jnp.zeros((fan_out,), jnp.float32)})
    return layers


def init(seed: int, k: int, m: int, hidden) -> Learner:
    ka, kc = jax.random.split(jax.random.PRNGKey(seed))
    actor = _init_mlp(ka, (k, *hidden, m))
    critic = _init_mlp(kc, (k + m, *hidden, 1))
    zeros = functools.partial(jax.tree_util.tree_map, jnp.zeros_like)
    return Learner(actor, critic, actor, critic, zeros(actor), zeros(actor),
                   zeros(critic), zeros(critic), jnp.zeros((), jnp.int32))


def mlp(layers, x, mode):
    for i, layer in enumerate(layers):
        x = dot(x, layer["w"], mode) + layer["b"]
        if i + 1 < len(layers):
            x = jnp.maximum(x, 0.0)
    return x


def actor(layers, s, mode):
    return jax.nn.sigmoid(mlp(layers, s, mode))


def critic(layers, s, a, mode):
    return mlp(layers, jnp.concatenate([s, a], axis=-1), mode)[..., 0]


def _adam(params, grads, mu, nu, count, lr):
    t = count.astype(jnp.float32)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    mu = jax.tree_util.tree_map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: B2 * v + (1 - B2) * g * g, nu,
                                grads)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p + (-lr) * ((m / c1) / (jnp.sqrt(v / c2) + EPS)),
        params, mu, nu)
    return params, mu, nu


def update(st: Learner, batch, mode: str) -> Learner:
    s, a, r, s2 = batch
    y = r + GAMMA * critic(st.critic_targ, s2, actor(st.actor_targ, s2, mode),
                           mode)
    y = jax.lax.stop_gradient(y)
    count = st.count + 1

    def critic_loss(c):
        return jnp.mean(jnp.square(critic(c, s, a, mode) - y))

    cg = jax.grad(critic_loss)(st.critic)
    c, cmu, cnu = _adam(st.critic, cg, st.critic_mu, st.critic_nu, count,
                        CRITIC_LR)

    def actor_loss(p):
        return -jnp.mean(critic(c, s, actor(p, s, mode), mode))

    ag = jax.grad(actor_loss)(st.actor)
    p, amu, anu = _adam(st.actor, ag, st.actor_mu, st.actor_nu, count,
                        ACTOR_LR)

    def polyak(t, o):
        return jax.tree_util.tree_map(lambda x, y: (1 - TAU) * x + TAU * y,
                                      t, o)

    return Learner(p, c, polyak(st.actor_targ, p), polyak(st.critic_targ, c),
                   amu, anu, cmu, cnu, count)


def learn(st: Learner, buf, size, key, updates: int, batch: int,
          mode: str) -> Learner:
    """``updates`` updates on minibatches drawn from the first ``size``
    rows of the replay arrays ``buf`` = (s, a, r, s2)."""
    idx = jax.random.randint(key, (updates, batch), 0, size)
    batches = tuple(x[idx] for x in buf)
    return jax.lax.scan(lambda c, b: (update(c, b, mode), None), st,
                        batches)[0]
