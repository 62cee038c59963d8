"""Persistent fleet serving: leased chunk slots, mid-flight join/leave,
checkpointed bit-identical resume.

``FleetTuner`` fixes its roster at ``from_grid`` time — the fleet IS the
grid. Magpie's deployment story (tuning live tenants of a shared file
system) needs the opposite: sessions arrive and depart while the fleet
keeps running. ``FleetService`` lifts the streaming chunked episode runtime
into a persistent serving loop — the worker/serving-loop split of the
ROADMAP's vLLM TPU-worker exemplar:

  * slots are LEASED: the compiled chunk program is fixed at width C for
    the service's whole life; a joining session claims the lowest free
    slot and frees it on leave. Every ``advance`` runs ``ceil(active/C)``
    chunks of exactly C rows (vacant rows padded with a replicated live
    row, padded results discarded), so one donated executable serves any
    population.
  * join/leave are REQUESTS, queued and applied only at ``advance``
    boundaries — membership never changes mid-episode. That, plus vmap row
    independence (a session's whole trajectory derives from its own seed
    streams, never from its row placement or chunk-mates), makes churn
    bit-neutral for surviving sessions: the churn CI lane pins a
    join/leave-every-round service against a static fleet, exactly.
  * per-session progress — learner params + opt state, FIFO replay, env
    model state, exploration streams (LHS plan position, OU-noise RNG),
    on-device learn key, step counter, decision history — checkpoints
    through ``checkpoint/store.py`` (atomic publish, CRC-verified read),
    so a killed service restores and continues bit-identically. A partial
    or corrupt checkpoint RAISES (``KeyError``/``IOError``) rather than
    silently reinitializing a session from scratch.

Sessions of different ages ride one chunk program because the episode
engine's exploration inputs — including the warmup mask — are per-session
(``core.episode``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.agent import lhs_warmup_plan
from repro.core.ddpg import DDPGConfig, OUNoise, actor_apply, fleet_init
from repro.core.episode import (
    BufferState,
    EpisodeCarry,
    EpisodeTrace,
    _compiled_episode,
    _pad_rows,
    decode_restarts,
    stream_chunks,
)
from repro.core.fleet import replay_compact_trace
from repro.core.scalarization import (
    Scalarizer,
    metric_bounds,
    normalize_state,
)
from repro.core.spans import phase
from repro.core.tuner import (
    StepRecord,
    TuningResult,
    evaluate_config,
    recommend_final,
)
from repro.checkpoint.store import (
    restore_checkpoint,
    restore_into,
    save_checkpoint,
)

#: host phases whose seconds ``FleetService.counters`` keeps, as
#: ``<phase>_seconds``; each is the span ``fleet.<phase>`` with ``_`` for
#: ``.`` (``core.spans``)
PHASES = ("join", "join_env", "join_init", "join_evaluate", "advance",
          "boundary", "finalize", "prepare", "stream", "write_back")


@dataclasses.dataclass
class _Session:
    """One tenant's complete tuning state, host-resident between rounds."""

    sid: int
    label: str
    workload: str
    weights: dict
    seed: int
    env: object                # ModelEnv (owns model params + model_state)
    scalarizer: Scalarizer
    ddpg: object               # DDPGState pytree, UNSTACKED numpy leaves
    buf: dict                  # {"s","a","r","s2"} numpy + "next","size" ints
    learn_key: np.ndarray
    noise: OUNoise
    warmup_plan: np.ndarray    # [warmup_steps, m]
    steps_taken: int
    default_config: dict
    default_metrics: dict
    cur_config: dict
    cur_metrics: dict
    best_config: dict
    best_metrics: dict
    best_objective: float
    history: list
    restart_seconds: float
    joined_at: float
    # guardrails (service-wide policy; None when guardrails are off)
    guard: object = None        # core.guardrails.GuardState, numpy leaves
    guard_counters: Optional[dict] = None
    # resilience (service-wide policy; None when resilience is off)
    health: object = None       # core.resilience.HealthState, numpy leaves
    health_counters: Optional[dict] = None


class FleetService:
    """A persistent, elastic fleet of Magpie tuning sessions.

    ``chunk`` is the leased slot width C — the one compiled episode width
    for the service's lifetime. ``request_join``/``request_leave`` enqueue
    membership changes; ``advance(steps)`` applies the queue at its
    boundary and then runs ``steps`` fused tuning iterations for every
    active session. ``advance(0)`` is a membership-only boundary.

    Each session is seeded exactly like ``MagpieAgent(cfg, seed=s)`` /
    ``FleetTuner``'s cells, so a session that joins at round 0 and leaves
    after the same rounds reproduces the static fleet's trajectory.
    ``leave`` finalizes the session with the shared §III-E rule
    (``recommend_final``) and returns its ``TuningResult``.
    """

    def __init__(self, *, chunk: int, env_factory=None, env_cls=None,
                 ddpg_config: Optional[DDPGConfig] = None,
                 buffer_capacity: int = 64, warmup_steps: int = 8,
                 eval_runs: int = 3, overlap: bool = True,
                 checkpoint_dir: Optional[str] = None, keep: int = 3,
                 policy=None, sharing=None, cell_size: int = 1,
                 resilience=None, supervisor=None, chaos=None):
        from repro.core.sharing import normalize_sharing
        if chunk <= 0:
            raise ValueError(f"chunk must be positive, got {chunk}")
        sharing = normalize_sharing(sharing)
        if sharing is not None and policy is not None:
            raise ValueError(
                "experience sharing does not compose with DeploymentPolicy "
                "guardrails; run guarded services with sharing off")
        if resilience is not None:
            from repro.core.resilience import normalize_resilience
            resilience = normalize_resilience(resilience)
        if resilience is not None and policy is not None:
            raise ValueError(
                "resilience does not compose with DeploymentPolicy "
                "guardrails; run guarded services without a ResiliencePolicy")
        if supervisor is not None:
            from repro.core.resilience import normalize_supervisor
            supervisor = normalize_supervisor(supervisor)
        cell_modes = sharing is not None and (sharing.shared_replay
                                              or sharing.averaging)
        cell_size = int(cell_size) if cell_modes else 1
        if cell_modes:
            if cell_size < 1:
                raise ValueError(f"cell_size must be >= 1, got {cell_size}")
            if chunk % cell_size != 0:
                raise ValueError(
                    f"chunk ({chunk}) must be a multiple of cell_size "
                    f"({cell_size}) so cells never span chunk programs")
        if env_factory is not None and env_cls is not None:
            raise ValueError("pass env_factory OR env_cls, not both")
        if env_factory is None:
            from repro.envs.lustre_sim import LustreSimEnv
            cls_ = env_cls or LustreSimEnv

            def env_factory(workload, seed):
                return cls_(workload, seed=seed).to_model_env()
        self.chunk = int(chunk)
        self.env_factory = env_factory
        self.cfg = ddpg_config
        self.buffer_capacity = buffer_capacity
        self.warmup_steps = warmup_steps
        self.eval_runs = eval_runs
        self.overlap = overlap
        self.checkpoint_dir = checkpoint_dir
        self.keep = keep
        # service-wide DeploymentPolicy (core.guardrails); None = off,
        # bitwise the unguarded service
        self.policy = policy
        # service-wide ResiliencePolicy (core.resilience); None = off,
        # bitwise (and by executable identity) the plain service
        self.resilience = resilience
        # host-side chunk supervision: retries are bitwise-invisible on
        # success; a chunk that keeps failing raises ``ChunkFailure``
        # (on_failure="raise", the default) or, with on_failure="skip", is
        # SKIPPED and its sessions quarantined through the leave path at
        # the next boundary — only when the operator asked for it, so a
        # chunk program that cannot run (e.g. does not compile) is never
        # hidden behind a quarantine
        self.supervisor = supervisor
        self.chaos = chaos
        # service-wide SharingConfig (core.sharing); None = off, bitwise
        # (and by executable identity) the non-sharing service. Sessions
        # with the same workload x objective bind into cells of up to
        # ``cell_size`` seats at advance() boundaries; a cell's merged
        # replay window and averaging clock live in ``_cells`` and die with
        # its last member.
        self.sharing = sharing
        self.cell_size = cell_size
        self._cell_modes = cell_modes
        self._cells: dict = {}      # cell id -> {key, seats, steps, buf}
        self._next_cell = 0
        self._obs_mask = None       # resolved lazily from the first env
        self.total_steps = 0
        self._slots: list = []          # slot index -> sid or None (leases)
        self._sessions: dict = {}       # sid -> _Session (leased only)
        self._join_queue: list = []     # _Session, in request order
        self._leave_queue: list = []    # sid, in request order
        self._completed: dict = {}      # sid -> TuningResult
        self._next_sid = 0
        self._actor_tx = None
        self._critic_tx = None
        self.last_stats: dict = {}
        # the operator's scrape point: monotone over the service's life
        self.counters: dict = {**{f"{p}_seconds": 0.0 for p in PHASES},
                               "joins": 0, "finalizes": 0, "rounds": 0}

    # -- membership requests ------------------------------------------------

    def request_join(self, workload: str, weights: Mapping[str, float],
                     seed: int, label: Optional[str] = None) -> int:
        """Queue a new tuning session; leased at the next boundary.

        The session is fully initialized NOW (env build + default-config
        evaluation, mirroring ``FleetTuner.from_grid``) so the join order —
        not the boundary order — fixes its RNG streams. Returns its sid.
        """
        sid = self._next_sid
        self._next_sid += 1
        if label is None:
            label = f"{workload}|{'+'.join(sorted(weights))}|seed{seed}"
        self._join_queue.append(
            self._new_session(sid, workload, dict(weights), seed, label))
        return sid

    def request_leave(self, sid: int) -> None:
        """Queue a session's departure; finalized at the next boundary."""
        if sid not in self._sessions and \
                all(s.sid != sid for s in self._join_queue):
            raise KeyError(f"unknown or already-finished session {sid}")
        if sid not in self._leave_queue:
            self._leave_queue.append(sid)

    def result(self, sid: int) -> TuningResult:
        """The ``TuningResult`` of a departed session."""
        if sid not in self._completed:
            raise KeyError(f"session {sid} has not left (or never existed)")
        return self._completed[sid]

    @property
    def active(self) -> dict:
        """{sid: label} of currently leased sessions."""
        return {sid: s.label for sid, s in self._sessions.items()}

    def lease_table(self) -> list:
        """slot index -> sid (or None): the service's chunk-row leases."""
        return list(self._slots)

    # -- session construction ------------------------------------------------

    def _new_session(self, sid, workload, weights, seed, label,
                     evaluate_default: bool = True) -> _Session:
        """Build one session (``request_join``, or ``restore`` rebuilding a
        checkpointed one): its env, its learner and host buffers, and the
        evaluation of the default configuration."""
        with phase("join", self.counters, sid=sid):
            self.counters["joins"] += 1
            with phase("join.env", self.counters):
                env = self.env_factory(workload, seed)
            with phase("join.init", self.counters):
                if self.cfg is None:
                    self.cfg = DDPGConfig.for_env(env)
                scal = Scalarizer(weights=weights, specs=env.metric_specs)
                # identical to FleetAgent's per-seed streams (width-1 vmap init
                # produces the same per-key values as any other width)
                states, (atx, ctx) = fleet_init(
                    jnp.stack([jax.random.PRNGKey(seed)]), self.cfg)
                if self._actor_tx is None:
                    self._actor_tx, self._critic_tx = atx, ctx
                ddpg = jax.tree_util.tree_map(lambda x: np.asarray(x)[0],
                                              states)
                cap, k, m = self.buffer_capacity, self.cfg.state_dim, \
                    self.cfg.action_dim
                buf = {"s": np.zeros((cap, k), np.float32),
                       "a": np.zeros((cap, m), np.float32),
                       "r": np.zeros((cap,), np.float32),
                       "s2": np.zeros((cap, k), np.float32),
                       "next": 0, "size": 0}
                learn_key = np.asarray(jax.random.PRNGKey(seed + 3))
                noise = OUNoise(m, seed=seed + 1)
                warmup_plan = lhs_warmup_plan(
                    np.random.default_rng(seed + 2), self.warmup_steps, m)
                default_config = env.param_space.default_config()
            if evaluate_default:
                with phase("join.evaluate", self.counters):
                    default_metrics = evaluate_config(env, default_config,
                                                      self.eval_runs)
            else:
                default_metrics = {}  # restore path fills from the checkpoint
            guard = None
            if self.policy is not None:
                from repro.core.guardrails import init_guard_state
                guard = init_guard_state(
                    env.param_space, default_config,
                    scal.objective(default_metrics) if default_metrics
                    else 0.0)
            health = None
            if self.resilience is not None:
                from repro.core.resilience import init_health_state
                health = init_health_state(ddpg, self.resilience)
            return _Session(
                sid=sid, label=label, workload=workload, weights=weights,
                seed=seed, env=env, scalarizer=scal, ddpg=ddpg, buf=buf,
                learn_key=learn_key, noise=noise, warmup_plan=warmup_plan,
                steps_taken=0,
                default_config=dict(default_config),
                default_metrics=dict(default_metrics),
                cur_config=dict(default_config),
                cur_metrics=dict(default_metrics),
                best_config=dict(default_config),
                best_metrics=dict(default_metrics),
                best_objective=(scal.objective(default_metrics)
                                if default_metrics else float("-inf")),
                history=[], restart_seconds=0.0, joined_at=time.perf_counter(),
                guard=guard, health=health)

    # -- boundary: apply the request queue -----------------------------------

    def _lease(self, sess: _Session) -> None:
        for i, sid in enumerate(self._slots):
            if sid is None:
                self._slots[i] = sess.sid
                break
        else:
            self._slots.append(sess.sid)
        self._sessions[sess.sid] = sess

    def _apply_requests(self) -> None:
        # leaves first, so a same-boundary join can reuse the freed slot
        for sid in self._leave_queue:
            if sid in self._sessions:
                self._finalize(self._sessions.pop(sid))
                self._slots[self._slots.index(sid)] = None
            else:  # joined and left within one boundary: never leased
                sess = next(s for s in self._join_queue if s.sid == sid)
                self._join_queue.remove(sess)
                self._finalize(sess)
        self._leave_queue = []
        for sess in self._join_queue:
            self._lease(sess)
        self._join_queue = []
        if self._cell_modes:
            self._bind_cells()

    # -- cell topology (experience sharing) ----------------------------------

    @staticmethod
    def _cell_key(sess: _Session) -> tuple:
        return (sess.workload, tuple(sorted(sess.weights.items())))

    def _new_cell_buf(self) -> dict:
        cap, k, m = self.buffer_capacity, self.cfg.state_dim, \
            self.cfg.action_dim
        return {"s": np.zeros((cap, k), np.float32),
                "a": np.zeros((cap, m), np.float32),
                "r": np.zeros((cap,), np.float32),
                "s2": np.zeros((cap, k), np.float32),
                "next": 0, "size": 0}

    def _bind_cells(self) -> None:
        """Re-bind cell membership at a boundary (sharing only).

        A cell is ``cell_size`` seats keyed by (workload, objective):
        departing sessions free their seat, joining sessions take the lowest
        free seat of the lowest matching cell (or found a new cell). Seats —
        not slots — fix a member's lane inside the cell program, so
        surviving members keep their lane across churn. A cell whose last
        member leaves is dropped WITH its merged replay window: experience
        belongs to the tenants that generated it."""
        for cid in sorted(self._cells):
            rec = self._cells[cid]
            rec["seats"] = [sid if sid in self._sessions else None
                            for sid in rec["seats"]]
            if all(sid is None for sid in rec["seats"]):
                del self._cells[cid]
        seated = {sid for rec in self._cells.values()
                  for sid in rec["seats"] if sid is not None}
        for sid in sorted(self._sessions):  # sid order: deterministic
            if sid in seated:
                continue
            key = self._cell_key(self._sessions[sid])
            for cid in sorted(self._cells):
                rec = self._cells[cid]
                if rec["key"] == key and None in rec["seats"]:
                    rec["seats"][rec["seats"].index(None)] = sid
                    break
            else:
                buf = (self._new_cell_buf()
                       if self.sharing.shared_replay else None)
                self._cells[self._next_cell] = {
                    "key": key,
                    "seats": [sid] + [None] * (self.cell_size - 1),
                    "steps": 0, "buf": buf}
                self._next_cell += 1

    def _session_guardrail_stats(self, sess: _Session) -> Optional[dict]:
        if self.policy is None:
            return None
        from repro.core.guardrails import empty_counters, guardrail_stats
        return guardrail_stats(self.policy, sess.guard,
                               sess.guard_counters or empty_counters(),
                               space=sess.env.param_space)

    def guardrail_stats(self, sid: int) -> Optional[dict]:
        """An ACTIVE session's exported guardrail record (None when off)."""
        if sid not in self._sessions:
            raise KeyError(f"session {sid} is not active")
        return self._session_guardrail_stats(self._sessions[sid])

    def _session_health_stats(self, sess: _Session) -> Optional[dict]:
        if self.resilience is None:
            return None
        from repro.core.resilience import empty_health_counters, health_stats
        return health_stats(self.resilience, sess.health,
                            sess.health_counters or empty_health_counters())

    def health_stats(self, sid: int) -> Optional[dict]:
        """An ACTIVE session's exported health record (None when off)."""
        if sid not in self._sessions:
            raise KeyError(f"session {sid} is not active")
        return self._session_health_stats(self._sessions[sid])

    def _finalize(self, sess: _Session) -> None:
        """§III-E final recommendation for one departing session."""
        with phase("finalize", self.counters, sid=sess.sid):
            self.counters["finalizes"] += 1
            state_vec = normalize_state(sess.cur_metrics,
                                        sess.env.metric_specs,
                                        sess.env.state_metrics)
            a = np.asarray(actor_apply(
                jax.tree_util.tree_map(jnp.asarray, sess.ddpg.actor),
                jnp.asarray(state_vec, jnp.float32)))
            policy_config = sess.env.param_space.to_config(
                np.clip(a, 0.0, 1.0).astype(np.float32))
            config, best_metrics, replaced = recommend_final(
                sess.scalarizer, sess.best_config, policy_config,
                lambda c: evaluate_config(sess.env, c, self.eval_runs))
            if replaced:
                sess.best_config = dict(config)
            self._completed[sess.sid] = TuningResult(
                best_config=dict(sess.best_config),
                best_objective=sess.scalarizer.objective(best_metrics),
                best_metrics=best_metrics,
                default_config=dict(sess.default_config),
                default_metrics=dict(sess.default_metrics),
                history=list(sess.history),
                simulated_restart_seconds=float(sess.restart_seconds),
                wall_seconds=time.perf_counter() - sess.joined_at,
                guardrail_stats=self._session_guardrail_stats(sess),
                health_stats=self._session_health_stats(sess))

    # -- the serving loop ----------------------------------------------------

    def advance(self, steps: int) -> list:
        """One boundary + ``steps`` fused tuning iterations for every active
        session. Returns the sids that advanced (slot order).

        A round that steps sessions replaces ``last_stats``; its
        ``"phases"`` is the round's delta of ``counters``."""
        before = dict(self.counters)
        self.counters["rounds"] += 1
        with phase("advance", self.counters, round=self.counters["rounds"]):
            with phase("boundary", self.counters):
                self._apply_requests()
            order = [sid for sid in self._slots if sid is not None]
            if not order or steps <= 0:
                return []
            sessions = [self._sessions[sid] for sid in order]
            quarantined = self._advance_sessions(sessions, steps)
            self.total_steps += steps
            for sid in quarantined:
                # the chunk exhausted its supervised retries: its sessions
                # keep their pre-episode state and leave through the normal
                # path at the next boundary — bit-neutral for every
                # surviving session
                self.request_leave(sid)
        self.last_stats["phases"] = {
            k: v - before[k] for k, v in self.counters.items()}
        return order

    def _resolve_obs_mask(self, env):
        if self.sharing is None or self.sharing.observation_scopes is None:
            return None
        if self._obs_mask is None:
            from repro.core.sharing import resolve_obs_mask
            self._obs_mask = resolve_obs_mask(
                self.sharing, env.metric_specs, env.state_metrics)
        return self._obs_mask

    def _advance_sessions(self, sessions: Sequence[_Session],
                          steps: int) -> list:
        """Run one ``steps``-long episode segment for ``sessions`` through
        the chunked (double-buffered) episode program — the service-side
        mirror of ``core.episode.run_fleet_episode_scan``, with per-session
        ages, FIFO cursors and exploration streams first-class.

        With experience sharing on, program rows are CELL-ordered (seat
        order within each cell) instead of slot-ordered: vacant seats ride
        as inactive replicas of the cell's first live member — they compute
        but never write to the merged window, carry zero averaging weight,
        and their results are discarded — so a ragged cell runs the same
        fixed-shape cell program as a full one.

        Returns the sids to QUARANTINE: with a ``ChunkSupervisor`` whose
        ``on_failure`` is ``"skip"``, a chunk that exhausts its retries is
        skipped — its rows' host state is
        untouched (the drain never ran) and its sessions are handed back to
        ``advance`` for the leave path. The chunk schedule is pure
        scheduling, so skipping chunk i never perturbs chunk j."""
        with phase("prepare", self.counters):
            step_fns = {s.env.model.step_fn for s in sessions}
            if len(step_fns) != 1:
                raise ValueError("all service sessions must share one env "
                                 "model structure (same space / model "
                                 "class)")
            cell_modes = self._cell_modes
            cs = self.cell_size
            shared_replay = cell_modes and self.sharing.shared_replay
            obs_mask = self._resolve_obs_mask(sessions[0].env)
            uindex = {s.sid: j for j, s in enumerate(sessions)}

            # -- per-session exploration, consumed ONCE per unique session --
            # (each session consumes ITS OWN streams at ITS OWN age —
            # mixed-age chunks and, under sharing, mixed-age cells stay
            # exact)
            u = len(sessions)
            cfg = self.cfg
            k_dim, m_dim = cfg.state_dim, cfg.action_dim
            use_warmup_u = np.zeros((u, steps), bool)
            warmup_u = np.zeros((u, steps, m_dim), np.float32)
            noise_u = np.zeros((u, steps, m_dim), np.float32)
            for j, s in enumerate(sessions):
                s0 = s.steps_taken
                for t in range(steps):
                    if s0 + t < self.warmup_steps:
                        use_warmup_u[j, t] = True
                        warmup_u[j, t] = s.warmup_plan[s0 + t]
                    else:
                        noise_u[j, t] = s.noise()
                s.steps_taken += steps

            if cell_modes:
                # cell-ordered rows; vacant seats replicate the first live
                # member (inactive, non-primary: results + state discarded)
                rows, ridx, active_rows, primary_rows, row_cells = \
                    [], [], [], [], []
                for cid in sorted(self._cells):
                    rec = self._cells[cid]
                    live = [sid for sid in rec["seats"] if sid is not None]
                    rep = self._sessions[live[0]]
                    for sid in rec["seats"]:
                        s = self._sessions[sid] if sid is not None else rep
                        rows.append(s)
                        ridx.append(uindex[s.sid])
                        active_rows.append(sid is not None)
                        primary_rows.append(sid is not None)
                        row_cells.append(cid)
                ridx = np.asarray(ridx, np.int64)
                active_rows = np.asarray(active_rows, bool)
                primary_rows = np.asarray(primary_rows, bool)
            else:
                rows = list(sessions)
                ridx = np.arange(u)
                active_rows = np.ones((u,), bool)
                primary_rows = np.ones((u,), bool)
                row_cells = []
            n = len(rows)
            c = self.chunk  # fixed lease width: ONE compiled width, always
            num_chunks = -(-n // c)
            space = rows[0].env.param_space
            env0 = rows[0].env
            use_warmup = use_warmup_u[ridx]
            warmup = warmup_u[ridx]
            noise = noise_u[ridx]

            def stack_np(trees):
                return jax.tree_util.tree_map(
                    lambda *xs: np.stack([np.asarray(x) for x in xs]), *trees)

            params = stack_np([s.env.model.params for s in rows])
            env_states = stack_np([s.env.model_state for s in rows])
            ddpg_states = stack_np([s.ddpg for s in rows])
            lo, span = metric_bounds(env0.metric_specs, env0.state_metrics)
            k = lo.shape[0]
            lo = np.broadcast_to(lo, (n, k))
            span = np.broadcast_to(span, (n, k))
            w_vec = np.stack([s.scalarizer.weight_vector(s.env.state_metrics)
                              for s in rows])
            state_vecs = np.stack([
                normalize_state(s.cur_metrics, s.env.metric_specs,
                                s.env.state_metrics) for s in rows])
            objectives = np.array(
                [np.float32(s.scalarizer.objective(s.cur_metrics))
                 for s in rows], np.float32)
            if shared_replay:
                # cell-granular merged windows: [G, cap, ...] + [G] cursors
                cell_ids = sorted(self._cells)
                cbufs = [self._cells[cid]["buf"] for cid in cell_ids]
                buf_np = tuple(
                    np.stack([cb[key] for cb in cbufs])
                    for key in ("s", "a", "r", "s2"))
                next_slots = np.array([cb["next"] for cb in cbufs], np.int32)
                sizes = np.array([cb["size"] for cb in cbufs], np.int32)
            else:
                buf_np = tuple(
                    np.stack([s.buf[key] for s in rows])
                    for key in ("s", "a", "r", "s2"))
                next_slots = np.array([s.buf["next"] for s in rows], np.int32)
                sizes = np.array([s.buf["size"] for s in rows], np.int32)
            learn_keys = np.stack([s.learn_key for s in rows])

            if cell_modes:
                # the averaging cadence fires on each CELL's own step clock
                # (a cell-level event: every seat agrees, whatever its
                # member ages)
                avg_now = np.zeros((n, steps), bool)
                if self.sharing.averaging:
                    for j, cid in enumerate(row_cells):
                        cst = self._cells[cid]["steps"]
                        for t in range(steps):
                            avg_now[j, t] = \
                                ((cst + t + 1) % self.sharing.avg_every) == 0
                active = np.broadcast_to(active_rows[:, None],
                                         (n, steps)).copy()

            base_fields = dict(
                action_idx=np.zeros((n, steps, space.dim),
                                    space.index_dtype()),
                metrics=np.zeros((n, steps, k), np.float32),
                rewards=np.zeros((n, steps), np.float32),
                objectives=np.zeros((n, steps), np.float32),
                restarts=np.zeros((n, steps), np.float32))
            guarded = self.policy is not None
            resilient = self.resilience is not None
            if guarded:
                from repro.core.guardrails import (
                    GuardedCarry, GuardedEpisodeTrace)
                guard = stack_np([s.guard for s in rows])
                out = GuardedEpisodeTrace(
                    **base_fields,
                    guard_events=np.zeros((n, steps), np.uint8),
                    shadow_objectives=np.zeros((n, steps), np.float32))
            elif resilient:
                from repro.core.resilience import (
                    ResilientCarry, ResilientEpisodeTrace)
                health = stack_np([s.health for s in rows])
                out = ResilientEpisodeTrace(
                    **base_fields,
                    health_events=np.zeros((n, steps), np.uint8))
            else:
                out = EpisodeTrace(**base_fields)

            fn = _compiled_episode(env0.model.step_fn, space, cfg,
                                   self._actor_tx, self._critic_tx, True,
                                   cfg.updates_per_step, fleet=True,
                                   devices=None, policy=self.policy,
                                   sharing=self.sharing, cell_size=cs,
                                   obs_mask=obs_mask,
                                   resilience=self.resilience)
        t0 = time.perf_counter()

        def stage(ci):
            a, b = ci * c, min(n, (ci + 1) * c)
            pad = c - (b - a)

            def chunk_of(tree):
                return jax.tree_util.tree_map(
                    lambda x: jax.device_put(_pad_rows(x[a:b], pad)), tree)

            def group_chunk_of(tree):
                # cell-granular slice: chunk ci covers whole cells
                ga, gb = a // cs, b // cs
                gpad = pad // cs
                return jax.tree_util.tree_map(
                    lambda x: jax.device_put(_pad_rows(x[ga:gb], gpad)),
                    tree)

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
            if guarded:
                carry = GuardedCarry(base=carry, guard=chunk_of(guard))
            elif resilient:
                carry = ResilientCarry(base=carry, health=chunk_of(health))
            if cell_modes:
                xs = (chunk_of(use_warmup), chunk_of(warmup),
                      chunk_of(noise), chunk_of(avg_now), chunk_of(active))
            else:
                xs = (chunk_of(use_warmup), chunk_of(warmup),
                      chunk_of(noise))
            return (chunk_of(params), chunk_of(w_vec), chunk_of(lo),
                    chunk_of(span), carry, xs)

        def drain(ci, out_pair):
            carry, trace = out_pair
            a, b = ci * c, min(n, (ci + 1) * c)
            cnt = b - a

            def write_back(dst_tree, src_tree):
                jax.tree_util.tree_map(
                    lambda d, s: d.__setitem__(slice(a, b),
                                               np.asarray(s)[:cnt]),
                    dst_tree, src_tree)

            if guarded:
                out.guard_events[a:b] = np.asarray(trace.guard_events)[:cnt]
                out.shadow_objectives[a:b] = np.asarray(
                    trace.shadow_objectives)[:cnt]
                write_back(guard, carry.guard)
                carry = carry.base
            elif resilient:
                out.health_events[a:b] = np.asarray(
                    trace.health_events)[:cnt]
                write_back(health, carry.health)
                carry = carry.base
            out.action_idx[a:b] = np.asarray(trace.action_idx)[:cnt]
            out.metrics[a:b] = np.asarray(trace.metrics)[:cnt]
            out.rewards[a:b] = np.asarray(trace.rewards)[:cnt]
            out.objectives[a:b] = np.asarray(trace.objectives)[:cnt]
            out.restarts[a:b] = decode_restarts(
                np.asarray(trace.restarts)[:cnt])
            write_back(env_states, carry.env_state)
            write_back(ddpg_states, carry.ddpg)
            if shared_replay:
                ga, gb = a // cs, b // cs
                gcnt = gb - ga
                for dst, sr in zip(buf_np, (carry.buffer.s, carry.buffer.a,
                                            carry.buffer.r,
                                            carry.buffer.s2)):
                    dst[ga:gb] = np.asarray(sr)[:gcnt]
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
        with phase("stream", self.counters):
            stream_stats = stream_chunks(
                lambda args: fn(*args), stage, drain, num_chunks,
                overlap=self.overlap, supervisor=self.supervisor,
                chaos=self.chaos, staging=staging_stats)
        wall = time.perf_counter() - t0
        failed_rows: set = set()
        quarantined: list = []
        if stream_stats is not None:
            for ci in stream_stats["failed_chunks"]:
                failed_rows.update(range(ci * c, min(n, (ci + 1) * c)))
            quarantined = sorted({rows[j].sid for j in failed_rows
                                  if primary_rows[j]})
        self.last_stats = dict(
            sessions=len(sessions), chunk=c, num_chunks=num_chunks,
            steps=steps, overlap=self.overlap,
            executable_cache_size=fn._cache_size(),
            session_steps_per_sec=len(sessions) * steps / max(wall, 1e-9),
            program=fn, cell_size=cs, sharing=self.sharing,
            staging=staging_stats)
        if stream_stats is not None:
            self.last_stats["supervisor"] = stream_stats
            self.last_stats["quarantined"] = list(quarantined)

        with phase("write_back", self.counters):
            # -- write per-session state + decision history back ------------
            per_step = wall / max(1, steps)

            def row(tree, j):
                return jax.tree_util.tree_map(lambda x: np.asarray(x[j]), tree)

            if shared_replay:
                for g, cid in enumerate(sorted(self._cells)):
                    cb = self._cells[cid]["buf"]
                    for key, arr in zip(("s", "a", "r", "s2"), buf_np):
                        cb[key] = np.asarray(arr[g])
                    cb["next"] = int(next_slots[g])
                    cb["size"] = int(sizes[g])
            for cid in sorted(self._cells):
                self._cells[cid]["steps"] += steps
            if guarded:
                from repro.core.guardrails import (
                    empty_counters, guardrail_counters, merge_counters)
                round_counters = empty_counters()
            if resilient:
                from repro.core.resilience import (
                    empty_health_counters, health_counters,
                    merge_health_counters)
            for j, s in enumerate(rows):
                if not primary_rows[j]:
                    continue  # vacant-seat replica: everything discarded
                if j in failed_rows:
                    # skipped chunk: the drain never ran, so the stacked arrays
                    # still hold this row's PRE-episode state and its trace
                    # rows are zeros — write nothing back; the session leaves
                    # with the state it had at the boundary
                    continue
                if resilient:
                    s.health = row(health, j)
                    s.health_counters = merge_health_counters(
                        s.health_counters or empty_health_counters(),
                        health_counters(out.health_events[j]))
                if guarded:
                    s.guard = row(guard, j)
                    delta = guardrail_counters(out.guard_events[j],
                                               out.restarts[j])
                    s.guard_counters = merge_counters(
                        s.guard_counters or empty_counters(), delta)
                    round_counters = merge_counters(round_counters, delta)
                s.env.model_state = row(env_states, j)
                s.ddpg = row(ddpg_states, j)
                if not shared_replay:
                    for key, arr in zip(("s", "a", "r", "s2"), buf_np):
                        s.buf[key] = np.asarray(arr[j])
                    s.buf["next"] = int(next_slots[j])
                    s.buf["size"] = int(sizes[j])
                s.learn_key = np.asarray(learn_keys[j])
                rep = replay_compact_trace(
                    s.env, out, j, start=len(s.history), per_step=per_step,
                    prev_config=s.cur_config, best_objective=s.best_objective,
                    restart_seconds=s.restart_seconds,
                    finite_baseline=resilient)
                s.history.extend(rep["records"])
                s.restart_seconds = rep["restart_seconds"]
                if rep["best"] is not None:
                    s.best_objective = rep["best"]["objective"]
                    s.best_config = dict(rep["best"]["config"])
                    s.best_metrics = dict(rep["best"]["metrics"])
                s.cur_config = rep["cur_config"]
                if rep["cur_metrics"] is not None:
                    s.cur_metrics = rep["cur_metrics"]
        if guarded:  # this round's fleet-aggregate guardrail counters
            self.last_stats["guardrails"] = round_counters
        return quarantined

    # -- checkpoint / restore ------------------------------------------------

    def checkpoint(self, directory: Optional[str] = None) -> str:
        """Write the full service state through ``checkpoint/store.py``.

        Call at a boundary: pending join/leave requests are part of the
        NEXT boundary, not of durable state — raise instead of silently
        dropping them. Completed sessions' results were already handed to
        their callers and are not re-persisted."""
        directory = directory or self.checkpoint_dir
        if directory is None:
            raise ValueError("no checkpoint directory configured")
        if self._join_queue or self._leave_queue:
            raise RuntimeError(
                "pending join/leave requests; apply them first with "
                "advance() (advance(0) is a membership-only boundary)")
        tree, extra = {"sessions": {}}, {
            "chunk": self.chunk, "warmup_steps": self.warmup_steps,
            "buffer_capacity": self.buffer_capacity,
            "eval_runs": self.eval_runs, "overlap": bool(self.overlap),
            "keep": self.keep, "total_steps": self.total_steps,
            "next_sid": self._next_sid,
            "slots": [(-1 if s is None else s) for s in self._slots],
            "cfg": {**self.cfg._asdict(),
                    "hidden": list(self.cfg.hidden)},
            # json round-trips Infinity for an unbounded restart budget
            "policy": (dict(self.policy._asdict())
                       if self.policy is not None else None),
            "resilience": (dict(self.resilience._asdict())
                           if self.resilience is not None else None),
            "supervisor": (dict(self.supervisor._asdict())
                           if self.supervisor is not None else None),
            "sharing": (dict(self.sharing._asdict())
                        if self.sharing is not None else None),
            "cell_size": self.cell_size,
            "next_cell": self._next_cell,
            # cell topology: key + seat order are part of durable state —
            # a member's lane inside the cell program must survive resume
            "cells": {str(cid): {
                "workload": rec["key"][0],
                "weights": [[k, v] for k, v in rec["key"][1]],
                "seats": [(-1 if sid is None else sid)
                          for sid in rec["seats"]],
                "steps": rec["steps"],
                "buf_next": (rec["buf"]["next"]
                             if rec["buf"] is not None else -1),
                "buf_size": (rec["buf"]["size"]
                             if rec["buf"] is not None else -1),
            } for cid, rec in self._cells.items()},
            "sessions": {}}
        if any(rec["buf"] is not None for rec in self._cells.values()):
            tree["cells"] = {
                str(cid): {k: rec["buf"][k] for k in ("s", "a", "r", "s2")}
                for cid, rec in self._cells.items()
                if rec["buf"] is not None}
        for sid, s in self._sessions.items():
            tree["sessions"][str(sid)] = {
                "ddpg": s.ddpg,
                "buffer": {k: s.buf[k] for k in ("s", "a", "r", "s2")},
                "env_params": s.env.model.params,
                "env_state": s.env.model_state,
                "learn_key": s.learn_key,
                "noise_x": s.noise.state_dict()["x"],
                "warmup_plan": s.warmup_plan,
            }
            if s.guard is not None:
                tree["sessions"][str(sid)]["guard_live_action"] = \
                    np.asarray(s.guard.live_action, np.float32)
                tree["sessions"][str(sid)]["guard_fallback_action"] = \
                    np.asarray(s.guard.fallback_action, np.float32)
            if s.health is not None:
                # the last-good snapshot is a full DDPGState pytree: a
                # resumed session must be able to reset to the SAME state
                tree["sessions"][str(sid)]["health_snapshot"] = \
                    s.health.snapshot
            nd = s.noise.state_dict()
            extra["sessions"][str(sid)] = {
                "label": s.label, "workload": s.workload,
                "weights": s.weights, "seed": s.seed,
                "steps_taken": s.steps_taken,
                "buffer_next": s.buf["next"], "buffer_size": s.buf["size"],
                "noise_t": nd["t"], "noise_bitgen": nd["bitgen"],
                "default_config": s.default_config,
                "default_metrics": s.default_metrics,
                "cur_config": s.cur_config, "cur_metrics": s.cur_metrics,
                "best_config": s.best_config, "best_metrics": s.best_metrics,
                "best_objective": s.best_objective,
                "restart_seconds": s.restart_seconds,
                "restart_events": [[sc, sec]
                                   for sc, sec in s.env.restart_events],
                "last_config": s.env._last_config,
                "history": [dataclasses.asdict(r) for r in s.history],
            }
            if s.guard is not None:
                extra["sessions"][str(sid)]["guard"] = {
                    "fallback_obj": float(s.guard.fallback_obj),
                    "budget_spent": float(s.guard.budget_spent),
                    "watch_left": int(s.guard.watch_left),
                    "promotions": int(s.guard.promotions),
                    "rollbacks": int(s.guard.rollbacks),
                    "counters": dict(s.guard_counters or {}),
                }
            if s.health is not None:
                extra["sessions"][str(sid)]["health"] = {
                    "resets": int(s.health.resets),
                    "nonfinite": int(s.health.nonfinite),
                    "degraded": bool(s.health.degraded),
                    "since_snap": int(s.health.since_snap),
                    "counters": dict(s.health_counters or {}),
                }
        return save_checkpoint(directory, self.total_steps, tree,
                               keep=self.keep, extra=extra)

    @classmethod
    def restore(cls, directory: str, *, env_factory=None, env_cls=None,
                step: Optional[int] = None,
                fallback: bool = False) -> "FleetService":
        """Rebuild a service from a checkpoint, bit-identically.

        Environments are rebuilt from ``env_factory(workload, seed)`` (they
        must be the same definition the checkpoint was taken with — restored
        model params are verified against the rebuilt ones and a mismatch
        raises). Array state is CRC-verified by the store and restored
        through ``restore_into`` against the freshly-built template, so a
        missing leaf raises ``KeyError`` instead of reinitializing.

        ``fallback=True`` survives a corrupted newest checkpoint by walking
        the keep-k history to the newest verifiable step (the restored
        service's ``total_steps`` tells how far back it reached); the
        checkpointed resilience/supervisor policies come along, so a crashed
        self-healing service resumes still self-healing.
        """
        step, flat, extra = restore_checkpoint(directory, step,
                                               fallback=fallback)
        cfg_d = dict(extra["cfg"])
        cfg_d["hidden"] = tuple(cfg_d["hidden"])
        policy = None
        if extra.get("policy") is not None:
            from repro.core.guardrails import DeploymentPolicy
            policy = DeploymentPolicy(**extra["policy"])
        resilience = None
        if extra.get("resilience") is not None:
            from repro.core.resilience import ResiliencePolicy
            resilience = ResiliencePolicy(**extra["resilience"])
        supervisor = None
        if extra.get("supervisor") is not None:
            from repro.core.resilience import ChunkSupervisor
            supervisor = ChunkSupervisor(**extra["supervisor"])
        sharing = None
        if extra.get("sharing") is not None:
            from repro.core.sharing import SharingConfig
            sh_d = dict(extra["sharing"])
            if sh_d.get("observation_scopes") is not None:
                sh_d["observation_scopes"] = tuple(
                    sh_d["observation_scopes"])
            sharing = SharingConfig(**sh_d)
        svc = cls(chunk=extra["chunk"], env_factory=env_factory,
                  env_cls=env_cls, ddpg_config=DDPGConfig(**cfg_d),
                  buffer_capacity=extra["buffer_capacity"],
                  warmup_steps=extra["warmup_steps"],
                  eval_runs=extra["eval_runs"], overlap=extra["overlap"],
                  checkpoint_dir=directory, keep=extra["keep"],
                  policy=policy, sharing=sharing,
                  cell_size=extra.get("cell_size", 1),
                  resilience=resilience, supervisor=supervisor)
        svc.total_steps = extra["total_steps"]
        svc._next_sid = extra["next_sid"]
        svc._slots = [None if s < 0 else int(s) for s in extra["slots"]]
        svc._next_cell = extra.get("next_cell", 0)
        for cid_s, cm in extra.get("cells", {}).items():
            cid = int(cid_s)
            buf = None
            if cm["buf_next"] >= 0:
                buf = svc._new_cell_buf()
                template = {k: buf[k] for k in ("s", "a", "r", "s2")}
                sub = {k[len(f"cells/{cid_s}/"):]: v for k, v in flat.items()
                       if k.startswith(f"cells/{cid_s}/")}
                restored = jax.tree_util.tree_map(
                    np.asarray, restore_into(template, sub))
                for k in ("s", "a", "r", "s2"):
                    buf[k] = restored[k]
                buf["next"] = int(cm["buf_next"])
                buf["size"] = int(cm["buf_size"])
            svc._cells[cid] = {
                "key": (cm["workload"],
                        tuple((k, v) for k, v in cm["weights"])),
                "seats": [None if sid < 0 else int(sid)
                          for sid in cm["seats"]],
                "steps": int(cm["steps"]), "buf": buf}
        for sid_s, meta in extra["sessions"].items():
            sid = int(sid_s)
            s = svc._new_session(sid, meta["workload"], dict(meta["weights"]),
                                 meta["seed"], meta["label"],
                                 evaluate_default=False)
            template = {
                "ddpg": s.ddpg,
                "buffer": {k: s.buf[k] for k in ("s", "a", "r", "s2")},
                "env_params": s.env.model.params,
                "env_state": s.env.model_state,
                "learn_key": s.learn_key,
                "noise_x": s.noise.state_dict()["x"],
                "warmup_plan": s.warmup_plan,
            }
            if policy is not None:
                template["guard_live_action"] = np.asarray(
                    s.guard.live_action, np.float32)
                template["guard_fallback_action"] = np.asarray(
                    s.guard.fallback_action, np.float32)
            if resilience is not None:
                template["health_snapshot"] = s.health.snapshot
            sub = {k[len(f"sessions/{sid_s}/"):]: v for k, v in flat.items()
                   if k.startswith(f"sessions/{sid_s}/")}
            restored = jax.tree_util.tree_map(
                np.asarray, restore_into(template, sub))
            if not all(np.array_equal(a, b) for a, b in zip(
                    jax.tree_util.tree_leaves(restored["env_params"]),
                    jax.tree_util.tree_leaves(s.env.model.params))):
                raise ValueError(
                    f"session {sid}: environment definition drifted — "
                    "rebuilt model params differ from the checkpoint")
            s.ddpg = restored["ddpg"]
            for k in ("s", "a", "r", "s2"):
                s.buf[k] = restored["buffer"][k]
            s.buf["next"] = int(meta["buffer_next"])
            s.buf["size"] = int(meta["buffer_size"])
            s.env.model_state = restored["env_state"]
            s.learn_key = restored["learn_key"]
            s.noise.load_state_dict({
                "x": restored["noise_x"], "t": meta["noise_t"],
                "bitgen": meta["noise_bitgen"]})
            s.warmup_plan = restored["warmup_plan"]
            s.steps_taken = int(meta["steps_taken"])
            s.default_config = dict(meta["default_config"])
            s.default_metrics = dict(meta["default_metrics"])
            s.cur_config = dict(meta["cur_config"])
            s.cur_metrics = dict(meta["cur_metrics"])
            s.best_config = dict(meta["best_config"])
            s.best_metrics = dict(meta["best_metrics"])
            s.best_objective = float(meta["best_objective"])
            s.restart_seconds = float(meta["restart_seconds"])
            s.env.restart_events = [
                (sc, sec) for sc, sec in meta["restart_events"]]
            s.env._last_config = dict(meta["last_config"])
            s.history = [StepRecord(**r) for r in meta["history"]]
            if policy is not None:
                from repro.core.guardrails import GuardState
                gm = meta["guard"]
                s.guard = GuardState(
                    live_action=restored["guard_live_action"],
                    fallback_action=restored["guard_fallback_action"],
                    fallback_obj=np.float32(gm["fallback_obj"]),
                    budget_spent=np.float32(gm["budget_spent"]),
                    watch_left=np.int32(gm["watch_left"]),
                    promotions=np.int32(gm["promotions"]),
                    rollbacks=np.int32(gm["rollbacks"]))
                s.guard_counters = dict(gm["counters"])
            if resilience is not None:
                from repro.core.resilience import HealthState
                hm = meta["health"]
                s.health = HealthState(
                    snapshot=restored["health_snapshot"],
                    resets=np.int32(hm["resets"]),
                    nonfinite=np.int32(hm["nonfinite"]),
                    degraded=np.bool_(hm["degraded"]),
                    since_snap=np.int32(hm["since_snap"]))
                s.health_counters = dict(hm["counters"])
            svc._sessions[sid] = s
        return svc
