"""Plain reference of tuning sessions (paper Fig. 1 loop, sec. III-E).

A session joins with a workload, objective weights and a seed. From the
seed alone it derives its learner (key ``seed``), its environment's key
(``seed``), its OU exploration noise (numpy generator ``seed + 1``), its
Latin-hypercube warm-up plan (numpy generator ``seed + 2``) and its learn
key (``seed + 3``). At join the default configuration is evaluated with
``eval_runs`` long runs. Each step then:

  act      warm-up plan for the first ``warmup_steps`` steps, else
           clip(mu(s) + OU noise, 0, 1)
  env      one workload run under the configuration the action maps to
  reward   objective G = sum of weighted normalized metrics;
           r = (G' - G) / max(G, 1e-6)
  store    (s, a, r, s') into the FIFO replay
  learn    ``updates`` DDPG updates (``reference.ddpg``)

On leaving, the session recommends the better of its best-seen
configuration and its policy's exploit-mode configuration, each
re-evaluated with ``eval_runs`` runs.

``replay`` follows given sessions through their steps. Teacher-forced, the
environment runs the configurations the program served (its answers), and
the reference records how far its own action lies from each of them;
free-running, it serves its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ddpg, lustre
from bench.reference.spaces import Space

OU_SIGMA, OU_THETA, OU_SIGMA_MIN, OU_DECAY = 0.40, 0.15, 0.05, 50


def warmup_plan(seed: int, steps: int, m: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 2)
    plan = np.empty((steps, m), np.float32)
    for j in range(m):
        perm = rng.permutation(steps)
        plan[:, j] = (perm + rng.uniform(size=steps)) / max(1, steps)
    return plan


def ou_noise(seed: int, n: int, m: int) -> np.ndarray:
    """The first ``n`` draws of the session's OU process, whose sigma
    decays linearly from 0.40 to 0.05 over 50 draws."""
    rng = np.random.default_rng(seed + 1)
    x = np.zeros(m, np.float32)
    out = np.zeros((n, m), np.float32)
    for t in range(n):
        frac = min(1.0, t / OU_DECAY)
        sigma = OU_SIGMA + frac * (OU_SIGMA_MIN - OU_SIGMA)
        x += -OU_THETA * x + sigma * rng.standard_normal(m)
        out[t] = x
    return out


def objective_f32(weights: np.ndarray, norm: np.ndarray) -> np.float32:
    acc = np.float32(0.0)
    for w, v in zip(weights, norm):
        if w != 0:
            acc = acc + np.float32(w) * np.float32(v)
    return acc


def normalize(metrics) -> np.ndarray:
    lo, span = lustre.bounds()
    v = np.asarray(metrics, np.float32)
    return np.where(span > 0, np.clip((v - lo) / np.where(span > 0, span, 1),
                                      np.float32(0), np.float32(1)),
                    np.float32(0)).astype(np.float32)


FAULTS = ("unchanged_state", "half_batch", "altered_config",
          "altered_metrics")


class Deployment:
    """What every session of one configuration shares. ``fault`` plants
    one of ``FAULTS`` in the reference, where it stands in the program's
    place to read a fault's numbers: a learner step that returns its state
    unchanged, half of each minibatch left out (the mean over the rest),
    the served configuration altered (knob 0 one level off) where it is
    recorded, or the throughput the environment reports altered by 1%."""

    def __init__(self, config: dict, fault: str = None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.fault = fault
        self.config = config
        self.space = Space(config["knobs"])
        self.k = int(config["state_dim"])
        self.m = self.space.dim
        self.hidden = tuple(config["hidden"])
        self.updates = int(config["updates_per_step"])
        self.batch = int(config["batch_size"])
        self.capacity = int(config["buffer_capacity"])
        self.warmup = int(config["warmup_steps"])
        self.eval_runs = int(config["eval_runs"])
        self.dfs_mask = jnp.asarray([n in config["dfs_scope"]
                                     for n in self.space.names])

    def _reported(self, metrics):
        if self.fault == "altered_metrics":
            return metrics.at[10].multiply(1.01)
        return metrics

    def _recorded(self, idx):
        if self.fault != "altered_config":
            return idx
        top = self.space.knobs[0].card - 1
        return idx.at[0].set(jnp.where(idx[0] < top, idx[0] + 1, idx[0] - 1))

    def weight_vector(self, weights: dict) -> np.ndarray:
        return np.array([weights.get(n, 0.0) for n in lustre.METRIC_NAMES],
                        np.float32)

    # -- device-side pieces, vmapped over sessions ---------------------------

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _evaluate(self, p, env_state, eval_runs, idx):
        dec, values = self.space.decode(idx)
        out = []
        for _ in range(eval_runs):
            env_state, metrics, _ = lustre.step(
                p, self.space.names, self.dfs_mask, env_state, dec, values,
                True)
            out.append(self._reported(metrics))
        return env_state, jnp.stack(out)

    def evaluate(self, p, env_state, idx):
        """Mean metrics of ``eval_runs`` long runs per session (float64
        mean of the float32 runs), and the advanced env states."""
        env_state, runs = jax.vmap(self._evaluate, (0, 0, None, 0))(
            p, env_state, self.eval_runs, idx)
        runs = np.asarray(runs, np.float64)
        acc = np.zeros(runs.shape[:1] + runs.shape[2:])
        for r in range(self.eval_runs):
            acc = acc + runs[:, r]
        return env_state, acc / self.eval_runs

    @functools.partial(jax.jit, static_argnums=(0, 2, 3, 4, 5))
    def _episode(self, carry, mode, forced, snap_steps, spans, xs, p,
                 w_vec, anchors):
        lo, span = (jnp.asarray(a) for a in lustre.bounds())

        def one(learn, c, x):
            env_state, st, buf, key, svec, obj, snap = c
            t, served, use_wu, wu, noise, use_fa, fa, valid, p, w_vec = x
            pol = ddpg.actor(st.actor, svec[None], mode)[0]
            a = jnp.where(use_wu, jnp.clip(wu, 0.0, 1.0),
                          jnp.clip(pol + noise, 0.0, 1.0))
            a = jnp.where(use_fa, fa, a)
            idx = served if forced else self.space.indices(a)
            gap = self.space.gap(a, idx)
            dec, values = self.space.decode(idx)
            env2, metrics, cost = lustre.step(
                p, self.space.names, self.dfs_mask, env_state, dec, values,
                False)
            metrics = self._reported(metrics)
            if not forced:
                idx = self._recorded(idx)
            norm = jnp.where(span > 0, jnp.clip((metrics - lo) / span, 0.0,
                                                1.0), 0.0)
            obj2 = jnp.float32(0.0)
            for j in range(norm.shape[0]):
                obj2 = obj2 + w_vec[j] * norm[j]
            reward = (obj2 - obj) / jnp.maximum(obj, jnp.float32(1e-6))
            bs, ba, br, bs2, nxt, size = buf
            buf2 = (bs.at[nxt].set(svec), ba.at[nxt].set(a),
                    br.at[nxt].set(reward), bs2.at[nxt].set(norm),
                    (nxt + 1) % self.capacity,
                    jnp.minimum(size + 1, self.capacity))
            key2, k = jax.random.split(key)
            if learn:
                batch = (self.batch // 2 if self.fault == "half_batch"
                         else self.batch)
                st2 = (st if self.fault == "unchanged_state" else
                       ddpg.learn(st, buf2[:4], buf2[5], k, self.updates,
                                  batch, mode))
                snap = tuple(
                    jax.tree_util.tree_map(
                        lambda n, o, at=at: jnp.where(t == at - 1, n, o),
                        st2, old)
                    for at, old in zip(snap_steps, snap))
            else:
                st2 = st
            new = (env2, st2, buf2, key2, norm, obj2, snap)
            c = jax.tree_util.tree_map(
                lambda n, o: jnp.where(valid, n, o), new, c)
            return c, (idx, gap, metrics, reward, obj2, a)

        # (learns, first step, last step + 1, index of its anchor)
        parts, t = [], 0
        for i, (start, stop) in enumerate(spans):
            parts += [(False, t, start, None), (True, start, stop, i)]
            t = stop
        parts.append((False, t, None, None))

        def episode(carry, xs, p, w_vec, anchors):
            out = []
            for on, start, stop, i in parts:
                xs_part = jax.tree_util.tree_map(lambda x: x[start:stop], xs)
                if xs_part[0].shape[0] == 0:
                    continue
                if on and anchors[i] is not None:
                    # the learner the program had after ``start`` steps
                    carry = carry[:1] + (anchors[i],) + carry[2:]
                carry, ys = jax.lax.scan(
                    lambda c, x, on=on: one(on, c, x + (p, w_vec)),
                    carry, xs_part)
                out.append(ys)
            ys = jax.tree_util.tree_map(lambda *y: jnp.concatenate(y), *out)
            return carry, ys

        return jax.vmap(episode)(carry, xs, p, w_vec, anchors)

    # -- sessions --------------------------------------------------------------

    def replay(self, sessions: list, mode: str, forced: bool,
               snapshot_steps: tuple = (), spans: tuple = None,
               anchors: tuple = None) -> list:
        """Follow ``sessions`` (dicts with ``workload``, ``weights``,
        ``seed``, ``steps`` and, when forced, ``served`` [steps, m]
        indices and optionally ``actions`` [steps, m], finite where the
        action is forced too) from join through their steps. Returns per session its
        default metrics, per-step indices, gaps, metrics, rewards and
        objectives, the actions stored, the final replay and learner, and
        the learner after
        each of ``snapshot_steps`` steps that it took. Only the steps of
        ``spans``, ordered (start, stop) pairs, learn (every step, where
        None): the others run act, env, reward and store on an unchanged
        learner. ``anchors`` holds for each span None or one ``Learner``
        per session: the learner is replaced by it after ``start`` steps,
        and the reference learns on from the program's state, on its own
        replay and with its learn key advanced to that step."""
        n, m = len(sessions), self.m
        t_max = max(1, max(s["steps"] for s in sessions))
        p = jnp.stack([lustre.workload_params(s["workload"])
                       for s in sessions])
        env_state = jax.tree_util.tree_map(
            lambda *x: jnp.stack(x),
            *[lustre.init_state(s["seed"], m) for s in sessions])
        default_idx = np.tile(self.space.default_indices(), (n, 1))
        env_state, default_metrics = self.evaluate(p, env_state, default_idx)
        w_vecs = np.stack([self.weight_vector(s["weights"])
                           for s in sessions])
        svec = np.stack([normalize(dm) for dm in default_metrics])
        obj = np.array([objective_f32(w, v) for w, v in zip(w_vecs, svec)],
                       np.float32)
        learners = [ddpg.init(s["seed"], self.k, m, self.hidden)
                    for s in sessions]
        st = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *learners)
        cap, k = self.capacity, self.k
        buf = (jnp.zeros((n, cap, k)), jnp.zeros((n, cap, m)),
               jnp.zeros((n, cap)), jnp.zeros((n, cap, k)),
               jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32))
        keys = jnp.stack([jax.random.PRNGKey(s["seed"] + 3)
                          for s in sessions])
        served = np.zeros((n, t_max, m), np.float32)
        use_wu = np.zeros((n, t_max), bool)
        wu = np.zeros((n, t_max, m), np.float32)
        noise = np.zeros((n, t_max, m), np.float32)
        fa = np.zeros((n, t_max, m), np.float32)
        use_fa = np.zeros((n, t_max), bool)
        valid = np.zeros((n, t_max), bool)
        for i, s in enumerate(sessions):
            t = s["steps"]
            valid[i, :t] = True
            if forced:
                served[i, :t] = s["served"]
            if "actions" in s:
                known = np.isfinite(s["actions"]).all(-1)
                use_fa[i, :t] = known
                fa[i, :t][known] = s["actions"][known]
            plan = warmup_plan(s["seed"], self.warmup, m)
            n_wu = min(t, self.warmup)
            use_wu[i, :n_wu] = True
            wu[i, :n_wu] = plan[:n_wu]
            noise[i, n_wu:t] = ou_noise(s["seed"], t - n_wu, m)
        t_idx = np.broadcast_to(np.arange(t_max), (n, t_max))
        xs = (t_idx, served, use_wu, wu, noise, use_fa, fa, valid)
        carry = (env_state, st, buf, keys, jnp.asarray(svec),
                 jnp.asarray(obj), tuple(st for _ in snapshot_steps))
        spans = ((0, t_max),) if spans is None else tuple(
            (min(int(a), t_max), min(int(b), t_max)) for a, b in spans)
        anchors = tuple(
            None if a is None else jax.tree_util.tree_map(
                lambda *x: jnp.stack([jnp.asarray(v) for v in x]), *a)
            for a in (anchors or (None,) * len(spans)))
        carry, (idx, gap, metrics, rewards, objs, acts) = self._episode(
            carry, mode, forced, tuple(int(x) for x in snapshot_steps),
            spans, xs, p, jnp.asarray(w_vecs), anchors)
        env_state, st, buf, snap = carry[0], carry[1], carry[2], carry[6]
        out = []
        for i, s in enumerate(sessions):
            t = s["steps"]
            take = functools.partial(jax.tree_util.tree_map,
                                     lambda x: np.asarray(x[i]))
            out.append({
                "default_metrics": default_metrics[i],
                "idx": np.asarray(idx[i, :t]),
                "gap": np.asarray(gap[i, :t]),
                "metrics": np.asarray(metrics[i, :t]),
                "rewards": np.asarray(rewards[i, :t]),
                "objectives": np.asarray(objs[i, :t]),
                "actions": np.asarray(acts[i, :t]),
                "buffer": take(buf),
                "learner": take(st),
                "snapshots": {step: take(sn) for step, sn in
                              zip(snapshot_steps, snap) if 0 < step <= t},
                "env_state": jax.tree_util.tree_map(lambda x: x[i],
                                                    env_state),
                "p": p[i], "w_vec": w_vecs[i],
            })
        return out

    def finalize(self, replays: list, mode: str) -> list:
        """The sec. III-E recommendation of each replayed session: its
        final config (indices) and best objective."""
        picks = []
        for r in replays:
            w = r["w_vec"]
            best_obj = float(objective_f32(w, normalize(r["default_metrics"])))
            best_idx = self.space.default_indices()
            for t in range(len(r["objectives"])):
                if float(r["objectives"][t]) > best_obj:
                    best_obj = float(r["objectives"][t])
                    best_idx = r["idx"][t]
            last = (r["metrics"][-1] if len(r["metrics"])
                    else r["default_metrics"])
            a = np.asarray(ddpg.actor(
                jax.tree_util.tree_map(jnp.asarray, r["learner"].actor),
                jnp.asarray(normalize(last))[None], mode)[0])
            a = np.clip(a, 0.0, 1.0).astype(np.float32)
            policy_idx = np.array([kn.index_host(a[j]) for j, kn in
                                   enumerate(self.space.knobs)], np.float32)
            picks.append((best_idx.astype(np.float32), policy_idx))
        n = len(replays)
        p = jnp.stack([r["p"] for r in replays])
        env_state = jax.tree_util.tree_map(
            lambda *x: jnp.stack(x), *[r["env_state"] for r in replays])
        best = np.stack([b for b, _ in picks])
        policy = np.stack([q for _, q in picks])
        env_state, best_m = self.evaluate(p, env_state, best)
        _, policy_m = self.evaluate(p, env_state, policy)
        out = []
        for i in range(n):
            w = replays[i]["w_vec"]
            b_obj = float(objective_f32(w, normalize(best_m[i])))
            chosen, obj = best[i], b_obj
            if not np.array_equal(policy[i], best[i]):
                p_obj = float(objective_f32(w, normalize(policy_m[i])))
                if p_obj > b_obj:
                    chosen, obj = policy[i], p_obj
            out.append({"idx": chosen, "objective": obj})
        return out
