"""The output check: what the timed window produced, against the plain
reference (``bench/reference``).

After the window closes, a sample of sessions drawn from the workload seed
is replayed by the reference from its join, teacher-forced on the
configurations the program served: the reference's environment runs those
(the program's answers), and the reference's own action is set beside
each. Over 96 Adam updates a step, two float32 formulations of the learner
drift apart chaotically (a rounding-level sign flip of a near-zero gradient
becomes an lr-sized step), so the reference follows the learner over two
spans of ``learner_steps`` steps each, from a common state: from the join
(its first steps, the replay nearly empty), and from the program's learner
at a late round boundary, the anchor (its last steps, the replay full and,
in a long window, wrapped), with the learn key advanced to that step.
Between the spans it stores the program's actions. Both are compared by
the median session; everything the learner does not feed is compared
over every step. The numbers, each with its limit in
``bench/limits/<workload>.json``:

  action_gap      median session's widest distance (unit action) by which
                  the reference's action lies outside the knob cell the
                  program served, over both spans
  mapping_gap     widest distance by which an action the program stored
                  in its replay lies outside the cell it served that step
                  (the action-to-config mapping), over every stored step
  env_gap         widest gap of an env metric, in units of the metric's
                  normalization span: the default-config evaluation and
                  every step
  reward_gap      widest gap of a step's reward or objective
  replay_gap      widest gap of a replay element (s, r, s2 of every stored
                  step, a of the warm-up steps), and of its cursor and size
  learner_gap     median session's learner (parameters, targets, both Adam
                  moments) at the boundaries of the span from the join:
                  per leaf the median over elements of |program -
                  reference| / max(|reference|, the leaf's median
                  |reference|); the worst leaf and boundary of the session
  late_learner_gap  the same over the span from the anchor
  late_change_gap median session's worst parameter leaf (actor and critic)
                  over the span from the anchor: the gap between the
                  norms of the program's and the reference's change of
                  the leaf, over the larger of the reference's norm and
                  the median leaf's; steadier than ``late_learner_gap``
                  where the span is long enough for the learner's chaos
  learner_count   widest gap of an Adam count from 96 per step taken
  final_gap       sessions that left: the sec. III-E recommendation,
                  teacher-forced (``_final_gap``); its objective's
                  relative gap, infinite where the rule was broken
  unchecked       1 where the window left no session to check

``learner_steps`` is at least ``LEARNER_STEPS``, in whole rounds. In a
fixed fleet the sample is drawn from its members after the warm-up round,
whose end closes the span from the join, and anchored at the start of the
window's last round. Under churn it is drawn from the sessions that
joined and left inside the window, the longest-lived among them, and
anchored ``learner_steps`` before each leaves.
"""

from __future__ import annotations

import jax
import numpy as np

NON_FINITE = 1e300
LEAVES = ("actor", "critic", "actor_targ", "critic_targ", "actor_mu",
          "actor_nu", "critic_mu", "critic_nu")


def learner_leaves(st) -> dict:
    """{path: array} of a reference ``Learner`` (or the same fields)."""
    out = {}
    for name in LEAVES:
        for i, layer in enumerate(getattr(st, name)):
            for key in ("w", "b"):
                out[f"{name}[{i}].{key}"] = np.asarray(layer[key],
                                                       np.float64)
    return out


def program_learner(ddpg_state):
    """The service's per-session ``DDPGState`` in the reference's layout."""
    from bench.reference.ddpg import Learner
    a_adam, c_adam = ddpg_state.actor_opt[0], ddpg_state.critic_opt[0]
    return Learner(ddpg_state.actor, ddpg_state.critic,
                   ddpg_state.actor_targ, ddpg_state.critic_targ,
                   a_adam.mu, a_adam.nu, c_adam.mu, c_adam.nu,
                   np.asarray([a_adam.count, c_adam.count]))


def change_gap(prog, ref, start) -> float:
    """Worst parameter leaf's gap between the norms of the change from
    ``start`` of ``prog`` and of ``ref``, over the larger of the
    reference's norm and the median leaf's."""
    p, r, a = (learner_leaves(x) for x in (prog, ref, start))
    paths = [k for k in r if k.split("[")[0] in ("actor", "critic")]
    dp = np.array([np.linalg.norm(p[k] - a[k]) for k in paths])
    dr = np.array([np.linalg.norm(r[k] - a[k]) for k in paths])
    scale = np.maximum(dr, np.median(dr))
    return float(np.max(np.abs(dp - dr) / np.where(scale > 0, scale, 1.0)))


def learner_gap(prog, ref) -> float:
    worst = 0.0
    p, r = learner_leaves(prog), learner_leaves(ref)
    for path, rv in r.items():
        scale = np.maximum(np.abs(rv), np.median(np.abs(rv)))
        rel = np.abs(p[path] - rv) / np.where(scale > 0, scale, 1.0)
        worst = max(worst, float(np.median(rel)))
    return worst


def _max(x) -> float:
    x = np.asarray(x, np.float64)
    return float(np.nanmax(x)) if x.size else 0.0


def _gap(a, b) -> np.ndarray:
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return np.where(np.isnan(d), np.inf, d)


def _median(xs) -> float:
    return float(np.median(xs)) if len(xs) else 0.0


def _rows(steps: int, capacity: int) -> list:
    """(step, replay row) of every step the FIFO replay still holds."""
    return [(t, t % capacity) for t in range(max(0, steps - capacity),
                                             steps)]


def _final_gap(dep, o, r) -> float:
    """The sec. III-E recommendation, teacher-forced: the best-seen
    configuration by the program's own objectives is re-evaluated on the
    session's env after its last step; where the program recommended
    another (its policy's), that one is evaluated next and has to win."""
    from bench.reference.session import normalize, objective_f32
    w = r["w_vec"]
    best_obj = float(objective_f32(w, normalize(r["default_metrics"])))
    best_idx = dep.space.default_indices()
    for t, obj in enumerate(o["objectives"]):
        if float(obj) > best_obj:
            best_obj, best_idx = float(obj), o["idx"][t]
    p = r["p"][None]
    env_state = jax.tree_util.tree_map(lambda x: x[None], r["env_state"])
    env_state, m_best = dep.evaluate(p, env_state, best_idx[None])
    obj = float(objective_f32(w, normalize(m_best[0])))
    final = o["final"]
    if not np.array_equal(final["idx"], best_idx):
        _, m_final = dep.evaluate(p, env_state, final["idx"][None])
        f_obj = float(objective_f32(w, normalize(m_final[0])))
        if not f_obj > obj:
            return float("inf")
        obj = f_obj
    return abs(final["objective"] - obj) / max(abs(obj), 1e-6)


def stored_actions(steps: int, capacity: int, replays: list) -> np.ndarray:
    """[steps, m] actions of a session, NaN where no replay holds them,
    from (steps taken, replay actions) pairs."""
    m = replays[0][1].shape[-1]
    out = np.full((steps, m), np.nan, np.float32)
    for taken, a in replays:
        for t, row in _rows(taken, capacity):
            out[t] = a[row]
    return out


def anchor_learner(prog):
    """A program ``Learner`` (from ``program_learner``) as the reference's
    starting state: its Adam count is the actor's."""
    return prog._replace(count=np.asarray(prog.count).reshape(-1)[0]
                         .astype(np.int32))


def _between(actions: np.ndarray, first: int, stop: int) -> np.ndarray:
    """The program's stored actions of steps ``first`` .. ``stop`` - 1,
    NaN elsewhere: the reference acts itself in the warm-up and in the
    spans it learns over."""
    out = np.full_like(actions, np.nan)
    out[first:stop] = actions[first:stop]
    return out


def compare(dep, outputs: list, mode: str = "highest") -> dict:
    """The numbers for program (or control) ``outputs``: one dict per
    sampled session with its join args, what the program produced, its
    learner at the anchor (``anchor``: (steps, learner)) and at the
    boundaries of both spans (``snapshots``), and the length of a span
    (``learner_steps``)."""
    from bench.reference import lustre

    _, span = lustre.bounds()
    span = np.where(span > 0, span, 1.0).astype(np.float64)
    ref = [None] * len(outputs)
    groups: dict = {}
    for i, o in enumerate(outputs):
        key = (o["learner_steps"], o["anchor"][0], max(o["snapshots"]))
        groups.setdefault(key, []).append(i)
    for (first, start, stop), members in sorted(groups.items()):
        snaps = sorted({t for i in members for t in outputs[i]["snapshots"]})
        group = dep.replay(
            [{**outputs[i]["join"], "steps": len(outputs[i]["idx"]),
              "served": outputs[i]["idx"],
              "actions": _between(outputs[i]["actions"],
                                  max(first, dep.warmup), start)}
             for i in members],
            mode, forced=True, snapshot_steps=tuple(snaps),
            spans=((0, first), (start, stop)),
            anchors=(None, [anchor_learner(outputs[i]["anchor"][1])
                            for i in members]))
        for i, r in zip(members, group):
            ref[i] = r
    nums = {"action_gap": [], "mapping_gap": 0.0, "env_gap": 0.0,
            "reward_gap": 0.0, "replay_gap": 0.0, "learner_gap": [],
            "late_learner_gap": [], "late_change_gap": [],
            "learner_count": 0.0}
    finals = []
    for o, r in zip(outputs, ref):
        steps = len(o["idx"])
        first, start = o["learner_steps"], o["anchor"][0]
        nums["action_gap"].append(max(
            _max(r["gap"][:first]),
            _max(r["gap"][start:max(o["snapshots"])])))
        nums["env_gap"] = max(
            nums["env_gap"],
            _max(_gap(o["default_metrics"], r["default_metrics"]) / span),
            _max(_gap(o["metrics"], r["metrics"]) / span))
        nums["reward_gap"] = max(
            nums["reward_gap"], _max(_gap(o["rewards"], r["rewards"])),
            _max(_gap(o["objectives"], r["objectives"])))
        ob, rb = o["buffer"], r["buffer"]
        gaps = [_max(_gap(ob[4], rb[4])), _max(_gap(ob[5], rb[5]))]
        for t, row in _rows(steps, dep.capacity):
            gaps += [_max(_gap(ob[i][row], rb[i][row])) for i in (0, 2, 3)]
            if t < dep.warmup:
                gaps.append(_max(_gap(ob[1][row], rb[1][row])))
            served = np.asarray(o["idx"][t], np.float32)
            action = np.asarray(ob[1][row], np.float32)
            nums["mapping_gap"] = max(nums["mapping_gap"], _max(
                dep.space.gap(action, served)))
        nums["replay_gap"] = max([nums["replay_gap"]] + gaps)
        for name, ts in (("learner_gap", [t for t in o["snapshots"]
                                          if t <= first]),
                         ("late_learner_gap", [t for t in o["snapshots"]
                                               if t > start])):
            nums[name].append(max(
                learner_gap(o["snapshots"][t], r["snapshots"][t])
                for t in ts))
        end = max(o["snapshots"])
        nums["late_change_gap"].append(change_gap(
            o["snapshots"][end], r["snapshots"][end], o["anchor"][1]))
        counts = [(o["learner"].count, steps),
                  (o["anchor"][1].count, start)] + [
            (o["snapshots"][t].count, t) for t in o["snapshots"]]
        for got, t in counts:
            nums["learner_count"] = max(nums["learner_count"], _max(
                _gap(got, t * dep.updates)))
        if "final" in o:
            finals.append(_final_gap(dep, o, r))
    for name in ("action_gap", "learner_gap", "late_learner_gap",
                 "late_change_gap"):
        nums[name] = _median(nums[name])
    if finals:
        nums["final_gap"] = max(finals)
    return nums


class Sampler:
    """Picks the sessions the check replays, keeps the learner state each
    had at its first and last round boundaries, and collects what the program
    produced for them. The service replaces a session's state at every
    boundary and never mutates it, so keeping a reference costs nothing
    inside the window."""

    LEARNER_STEPS = 3        # the least a session is learned over

    def __init__(self, client, rng: np.random.Generator, n: int):
        self.client = client
        self.rng = rng
        self.n = n
        self.fixed = client.turnover == 0
        rounds = -(-self.LEARNER_STEPS // client.round_steps)
        self.learner_steps = rounds * client.round_steps
        self.keep = rounds + 1           # boundaries kept per session
        self.sids: list = []
        self.snapshots: dict = {}        # sid -> {steps: (ddpg, replay)}
        self.tracked: set = set()

    def _keep(self, sid) -> None:
        """Keep the session's learner and replay at this boundary: for
        good within its first ``learner_steps``, else its last ``keep``
        boundaries."""
        sess = self.client.svc._sessions[sid]
        kept = self.snapshots.setdefault(sid, {})
        # the buffer dict is updated in place, its arrays are replaced
        kept[len(sess.history)] = (sess.ddpg, dict(sess.buf))
        late = [t for t in sorted(kept) if t > self.learner_steps]
        for t in late[:-self.keep]:
            del kept[t]

    def after_warmup(self) -> None:
        """In a fixed fleet, draw the sample now and keep each member's
        learner as the warm-up round left it."""
        if not self.fixed:
            return
        live = list(self.client.live)
        pick = self.rng.choice(len(live), size=min(self.n, len(live)),
                               replace=False)
        self.sids = [live[i] for i in sorted(pick)]
        self.tracked.update(self.sids)
        for sid in self.sids:
            self._keep(sid)

    def after_round(self, joined: list) -> None:
        """Keep the learner of every tracked session at this boundary:
        the sample's in a fixed fleet, under churn every session that
        joined in the window, until it leaves."""
        if not self.fixed:
            self.tracked.update(joined)
        svc = self.client.svc
        for sid in list(self.tracked):
            if sid in svc._sessions:
                self._keep(sid)
            else:
                self.tracked.discard(sid)

    def _eligible(self, sid, steps: int) -> bool:
        """Whether both spans of a session that took ``steps`` steps were
        kept, and do not overlap."""
        kept = self.snapshots.get(sid, {})
        start = steps - self.learner_steps
        return (start >= self.learner_steps and start in kept
                and self.learner_steps in kept)

    def chosen(self) -> list:
        svc = self.client.svc
        if self.fixed:
            return [(sid, svc._sessions[sid]) for sid in self.sids]
        done = [(sid, s) for sid, s in self.client.departed
                if self._eligible(sid, len(s.history))]
        if not done:
            return []
        life = [len(s.history) for _, s in done]
        longest = int(np.argmax(life))
        rest = [i for i in range(len(done)) if i != longest]
        pick = self.rng.choice(len(rest), size=min(self.n - 1, len(rest)),
                               replace=False)
        return [done[longest]] + [done[rest[i]] for i in sorted(pick)]

    def outputs(self, dep) -> list:
        """What the program produced for the sampled sessions."""
        svc, out, space = self.client.svc, [], dep.space
        for sid, sess in self.chosen():
            a = self.client.args[sid]
            steps = len(sess.history)
            start = steps - self.learner_steps
            kept = self.snapshots[sid]
            o = {
                "join": {"workload": a.workload, "weights": a.weights,
                         "seed": a.seed},
                "idx": np.stack([space.config_indices(h.config)
                                 for h in sess.history]),
                "metrics": np.array([[h.metrics[n] for n in
                                      sess.env.state_metrics]
                                     for h in sess.history]),
                "rewards": np.array([h.reward for h in sess.history]),
                "objectives": np.array([h.objective for h in sess.history]),
                "default_metrics": np.array(
                    [sess.default_metrics[n] for n in sess.env.state_metrics]),
                "buffer": (sess.buf["s"], sess.buf["a"], sess.buf["r"],
                           sess.buf["s2"], sess.buf["next"],
                           sess.buf["size"]),
                "learner": program_learner(sess.ddpg),
                "anchor": (start, program_learner(kept[start][0])),
                "learner_steps": self.learner_steps,
                "snapshots": {t: program_learner(d) for t, (d, _) in
                              kept.items()
                              if t <= self.learner_steps or start < t},
                "actions": stored_actions(
                    steps, dep.capacity,
                    [(start, kept[start][1]["a"]), (steps, sess.buf["a"])]),
            }
            if not self.fixed:
                res = svc.result(sid)
                o["final"] = {
                    "idx": space.config_indices(res.best_config),
                    "objective": res.best_objective}
            out.append(o)
        return out

    def collect(self, config: dict) -> None:
        """Take what the program produced for the sample, so that the
        service can be freed before the reference runs."""
        from bench.reference.session import Deployment
        self.dep = Deployment(config)
        self.collected = self.outputs(self.dep)


def numbers(dep, outputs: list) -> dict:
    """Every number compared for ``outputs`` (the program's, or a
    control's in its place); a window that left nothing to check reads
    ``unchecked`` 1. JSON has no infinity or NaN: either reads as the
    largest gap."""
    nums = compare(dep, outputs) if outputs else {}
    nums["unchecked"] = 0.0 if outputs else 1.0
    return {k: (v if np.isfinite(v) else NON_FINITE)
            for k, v in nums.items()}
