"""Fleet-tuning performance: fused scan learner + vmapped multi-session fleet.

Measurements backing the fleet subsystem's perf claims:

  1. ``learn()`` path — per-environment-step model-update time for the legacy
     path (``updates_per_step`` separate jitted dispatches + a host round-trip
     per minibatch sample) vs the fused path (on-device sampling + one
     ``lax.scan`` dispatch). The paper's Table III reports 0.72 s per model
     update on an RTX 5000; the fused path collapses the dispatch overhead
     that dominates at this model size.
  2. Dimensionality — fused learn step on the paper's 2-D space vs the
     8-knob ``LustreSimV2`` space (must stay within ~1.2x: the step is
     dispatch-dominated, so higher-dimensional spaces cost tuning steps,
     not per-step wall clock).
  3. Fleet scaling — wall time per tuning step for N concurrent sessions
     (vmapped learner + vectorized response surface) vs N sequential
     single-session tuners.
  4. Learner formulations at fleet scale (``bench_learner_paths``) — the
     pre-PR per-update-gather scan vs the pre-gathered scan (the default)
     vs the packed blocked-GEMM XLA twin of the Pallas kernel
     (``kernels/ddpg_fused.py``). This is the data behind the dispatch
     default: on CPU the [P, P]-padded GEMMs lose to the unpadded scan, so
     the packed formulation runs only as the TPU kernel's shape.
  5. Streaming chunked runtime scaling (``bench_scaling``) — 16 -> 1024
     sessions through one fixed-size chunk executable: session-steps/s
     (median over ``--repeats``, with noise bands), end-to-end wall clock,
     MEASURED peak resident device bytes per session, compile-reuse
     accounting across >= 2 grid shapes, and the monolithic (chunk=None)
     64-session control. Feeds the ``fleet_scaling`` BENCH_<n>.json point.
  6. Overlap A/B (``bench_overlap_ab``) — the double-buffered chunk staging
     pipeline off vs on at the largest sweep size. Outputs are bitwise
     identical either way; this isolates the wall-clock win from hiding
     host<->device staging under compute.
  7. Service mode (``bench_service``) — ``advance()`` rounds on a standing
     ``FleetService`` (leased chunk slots, per-session host state) vs the
     batch ``FleetTuner`` numbers, quantifying the serving-loop overhead.

Usage:
    PYTHONPATH=src python benchmarks/fleet_throughput.py [--quick]
    PYTHONPATH=src python benchmarks/fleet_throughput.py --scaling [--quick]
    PYTHONPATH=src python benchmarks/fleet_throughput.py --service [--quick]
    PYTHONPATH=src python benchmarks/fleet_throughput.py --overlap-ab [--quick]
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, repeat_measure, vs_previous
from repro.core import DDPGConfig, FleetTuner, MagpieAgent, Scalarizer, Tuner
from repro.core.ddpg import (_ddpg_step, fleet_init, fleet_learn_scan,
                             gather_minibatches, sample_minibatch_indices)
from repro.envs import LustreSimEnv, LustreSimV2
from repro.kernels import ddpg_fused as _fused
from repro.kernels import ops as _kops


def _fill_buffer(agent: MagpieAgent, n: int, rng: np.random.Generator) -> None:
    k, m = agent.cfg.state_dim, agent.cfg.action_dim
    for _ in range(n):
        agent.observe(rng.random(k).astype(np.float32),
                      rng.random(m).astype(np.float32),
                      float(rng.standard_normal() * 0.1),
                      rng.random(k).astype(np.float32))


def bench_learn_paths(env_steps: int, updates: int) -> list:
    """Per-step learn() time: legacy dispatch loop vs fused scan."""
    env = LustreSimEnv("seq_write", seed=0)
    cfg = DDPGConfig(state_dim=env.state_dim, action_dim=env.action_dim,
                     updates_per_step=updates)
    rng = np.random.default_rng(0)
    rows = [csv_row("path", "per_step_seconds", "dispatches_per_step",
                    "speedup_vs_legacy")]

    times = {}
    for fused in (False, True):
        agent = MagpieAgent(cfg, seed=0)
        _fill_buffer(agent, 32, np.random.default_rng(1))
        agent.learn(fused=fused)  # warm up compilation outside the timer
        t0 = time.perf_counter()
        for _ in range(env_steps):
            _fill_buffer(agent, 1, rng)
            agent.learn(fused=fused)
        times[fused] = (time.perf_counter() - t0) / env_steps

    rows.append(csv_row("legacy_per_update_dispatch", f"{times[False]:.4f}",
                        updates, "1.0"))
    rows.append(csv_row("fused_learn_scan", f"{times[True]:.4f}", 1,
                        f"{times[False] / times[True]:.1f}"))
    return rows


def bench_dimensionality(env_steps: int, updates: int) -> list:
    """Fused learn step cost: paper 2-D space vs the 8-knob V2 space.

    The learner is sized from each space via ``DDPGConfig.for_env`` (same
    hidden trunk, wider action head at 8-D). The fused ``lax.scan`` step is
    dispatch-dominated at this model size, so growing the space 2-D -> 8-D
    must stay within ~1.2x per-step time — dimensionality costs tuning steps
    (sample complexity), not wall clock per step.
    """
    rng = np.random.default_rng(0)
    rows = [csv_row("space", "action_dim", "per_step_seconds",
                    "ratio_vs_2d")]
    times = {}
    for name, env in (("paper_2d", LustreSimEnv("seq_write", seed=0)),
                      ("magpie8_8d", LustreSimV2("seq_write", seed=0))):
        cfg = DDPGConfig.for_env(env, updates_per_step=updates)
        agent = MagpieAgent(cfg, seed=0)
        _fill_buffer(agent, 32, np.random.default_rng(1))
        agent.learn()  # warm up compilation outside the timer
        t0 = time.perf_counter()
        for _ in range(env_steps):
            _fill_buffer(agent, 1, rng)
            agent.learn()
        times[name] = (time.perf_counter() - t0) / env_steps
        rows.append(csv_row(
            name, cfg.action_dim, f"{times[name]:.4f}",
            f"{times[name] / times['paper_2d']:.2f}"))
    return rows


def bench_fleet_scaling(fleet_sizes: list, steps: int) -> list:
    """Fleet step time vs equivalent sequential single-session tuning."""
    rows = [csv_row("sessions", "fleet_seconds_per_step",
                    "sequential_seconds_per_step", "speedup")]
    for n in fleet_sizes:
        seeds = list(range(n))
        fleet = FleetTuner.from_grid(["seq_write"], [{"throughput": 1.0}],
                                     seeds, eval_runs=1)
        fleet.run(1)  # warm up compilation for this fleet width
        t0 = time.perf_counter()
        fleet.run(steps)
        fleet_t = (time.perf_counter() - t0) / steps

        tuners = []
        for seed in seeds:
            env = LustreSimEnv("seq_write", seed=seed)
            scal = Scalarizer(weights={"throughput": 1.0},
                              specs=env.metric_specs)
            agent = MagpieAgent(DDPGConfig(state_dim=env.state_dim,
                                           action_dim=env.action_dim),
                                seed=seed)
            tuners.append(Tuner(env, scal, agent, eval_runs=1))
        for t in tuners:
            t.run(1)  # warm up
        t0 = time.perf_counter()
        for t in tuners:
            t.run(steps)
        seq_t = (time.perf_counter() - t0) / steps

        rows.append(csv_row(n, f"{fleet_t:.4f}", f"{seq_t:.4f}",
                            f"{seq_t / fleet_t:.1f}"))
    return rows


def bench_learner_paths(fleet_size: int, updates: int, reps: int = 5) -> list:
    """Learner formulations, one env step's worth of updates at fleet scale.

    Times ONE ``updates``-deep inner loop for ``fleet_size`` concurrent
    sessions (the per-step learner cost of the fused episode engine) under
    three formulations of the same math:

      scan_pergather   the pre-PR path: one buffer gather per update inside
                       the scan body
      scan_pregather   the default: all ``updates x batch`` rows gathered in
                       one take, scan over ready batches (bitwise-identical
                       states — tests/test_ddpg_fused.py)
      packed_gemm_xla  the Pallas kernel's [P, P]-blocked layout compiled by
                       XLA (``kernels.ops.ddpg_inner_loop`` fallback)

    Throughput is session-steps/s: fleet_size / seconds-per-inner-loop.
    """
    cfg = DDPGConfig(state_dim=12, action_dim=2, updates_per_step=updates)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(fleet_size)])
    states, (atx, ctx) = fleet_init(keys, cfg)
    rng = np.random.default_rng(0)
    cap = 64
    data = (jnp.asarray(rng.random((fleet_size, cap, 12)), jnp.float32),
            jnp.asarray(rng.random((fleet_size, cap, 2)), jnp.float32),
            jnp.asarray(rng.standard_normal((fleet_size, cap)), jnp.float32),
            jnp.asarray(rng.random((fleet_size, cap, 12)), jnp.float32))
    sizes = jnp.full((fleet_size,), cap, jnp.int32)
    lkeys = jnp.stack([jax.random.PRNGKey(s + 3) for s in range(fleet_size)])

    @functools.partial(jax.jit, static_argnames=("nu",))
    def pergather(states, data, sizes, keys, nu):
        def one(state, d, size, key):
            idx = sample_minibatch_indices(key, nu, cfg.batch_size, size)
            s, a, r, s2 = d

            def body(st, ix):
                return _ddpg_step(st, (s[ix], a[ix], r[ix], s2[ix]),
                                  cfg, atx, ctx)

            return jax.lax.scan(body, state, idx)

        return jax.vmap(one)(states, data, sizes, keys)

    dims = _fused.packed_dims(cfg.state_dim, cfg.action_dim, cfg.hidden)

    @functools.partial(jax.jit, static_argnames=("nu",))
    def packed_gemm(states, data, sizes, keys, nu):
        def pack_one(state, d, size, key):
            idx = sample_minibatch_indices(key, nu, cfg.batch_size, size)
            batches = gather_minibatches(d, idx)
            a_adam, c_adam = state.actor_opt[0], state.critic_opt[0]
            packed = _fused.pack_params(
                state.actor, state.critic, state.actor_targ,
                state.critic_targ, a_adam.mu, a_adam.nu, c_adam.mu,
                c_adam.nu, a_adam.count, c_adam.count, dims)
            return packed, _fused.pack_minibatches(batches, dims)

        packed, kb = jax.vmap(pack_one)(states, data, sizes, keys)
        return _kops.ddpg_inner_loop(
            packed, kb, dims=dims, gamma=cfg.gamma, tau=cfg.tau,
            actor_lr=cfg.actor_lr, critic_lr=cfg.critic_lr, mode="xla")

    def timed(fn):
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
            jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    t_old = timed(lambda: pergather(states, data, sizes, lkeys, updates))
    t_new = timed(lambda: fleet_learn_scan(states, data, sizes, lkeys, cfg,
                                           atx, ctx, updates))
    t_pk = timed(lambda: packed_gemm(states, data, sizes, lkeys, updates))

    rows = [csv_row("learner_path", "sessions", "inner_loop_seconds",
                    "session_steps_per_sec", "speedup_vs_pergather")]
    for name, t in (("scan_pergather", t_old), ("scan_pregather", t_new),
                    ("packed_gemm_xla", t_pk)):
        rows.append(csv_row(name, fleet_size, f"{t:.4f}",
                            f"{fleet_size / t:.2f}", f"{t_old / t:.2f}"))
    return rows


class _LegacyAgent(MagpieAgent):
    """The step-by-step host learner: ``updates_per_step`` separate jitted
    dispatches + a host minibatch sample per update — the paper's Table III
    per-iteration architecture, and the reference 'host loop' the episode
    engine is measured against."""

    def learn(self, updates=None):
        return super().learn(updates=updates, fused=False)


def _scan_tuner(workload: str, seed: int, updates: int, engine: str,
                legacy: bool = False) -> Tuner:
    env = LustreSimEnv(workload, seed=seed).to_model_env()
    scal = Scalarizer(weights={"throughput": 1.0}, specs=env.metric_specs)
    agent_cls = _LegacyAgent if legacy else MagpieAgent
    agent = agent_cls(DDPGConfig.for_env(env, updates_per_step=updates),
                      seed=seed)
    return Tuner(env, scal, agent, eval_runs=1, engine=engine)


def bench_episode_engine(fleet_sizes: list, steps: int,
                         updates: int = 96, repeats: int = 1) -> tuple:
    """Whole-episode engine vs the host loop, on the same pure env model.

    Three rungs, same algorithm and budget on every one:

      host_loop        the step-by-step Fig. 1 loop with per-minibatch learner
                       dispatches (Table III's architecture) — the baseline
      host_loop_fused  the loop with the PR-1 fused ``ddpg_learn_scan``
                       (one learn dispatch per step, still one host round
                       trip per act/env/learn)
      episode_scan /   this PR: the whole episode (act → env → reward →
      fleet_scan       store → learn) as ONE XLA program, then N sessions
                       vmapped on a fleet axis

    Throughput is session-steps/second; ``speedup_vs_host`` is against
    ``host_loop``. Each configuration is warmed at the measured step count so
    compilation never lands in the timer. Returns (csv rows, summary dict) —
    the summary feeds the repo-root BENCH_<n>.json trajectory file.
    """
    rows = [csv_row("engine", "sessions", "session_steps_per_sec",
                    "speedup_vs_host")]

    def timed(tuner):
        tuner.run(steps)  # warm compilation at this episode length
        t0 = time.perf_counter()
        tuner.run(steps)
        return steps / (time.perf_counter() - t0)

    host_sps = timed(_scan_tuner("seq_write", 0, updates, "host", legacy=True))
    rows.append(csv_row("host_loop", 1, f"{host_sps:.2f}", "1.0"))

    fused_sps = timed(_scan_tuner("seq_write", 0, updates, "host"))
    rows.append(csv_row("host_loop_fused", 1, f"{fused_sps:.2f}",
                        f"{fused_sps / host_sps:.1f}"))

    scan_sps = timed(_scan_tuner("seq_write", 0, updates, "scan"))
    rows.append(csv_row("episode_scan", 1, f"{scan_sps:.2f}",
                        f"{scan_sps / host_sps:.1f}"))

    summary = {"host_loop_steps_per_sec": host_sps,
               "host_loop_fused_steps_per_sec": fused_sps,
               "single_scan_steps_per_sec": scan_sps, "fleets": []}
    for n in fleet_sizes:
        cfg = DDPGConfig.for_env(LustreSimEnv("seq_write"),
                                 updates_per_step=updates)
        fleet = FleetTuner.from_grid(
            ["seq_write"], [{"throughput": 1.0}], list(range(n)),
            engine="scan", ddpg_config=cfg, eval_runs=1)
        fleet.run(steps)  # warm up compilation at this fleet width

        def one():
            t0 = time.perf_counter()
            fleet.run(steps)
            return steps * n / (time.perf_counter() - t0)

        stats = repeat_measure(one, repeats)
        sps = stats["median"]
        rows.append(csv_row("fleet_scan", n, f"{sps:.2f}",
                            f"{sps / host_sps:.1f}"))
        summary["fleets"].append({
            "sessions": n, "session_steps_per_sec": sps,
            "min": stats["min"], "noise_band": stats["noise_band"],
            "speedup_vs_host_loop": sps / host_sps})
    return rows, summary


def _learner_summary(rows: list) -> dict:
    """Parse ``bench_learner_paths`` csv rows into the BENCH json payload."""
    out = {}
    for row in rows[1:]:
        name, sessions, secs, sps, speedup = row.split(",")
        out[name] = {"sessions": int(sessions),
                     "inner_loop_seconds": float(secs),
                     "session_steps_per_sec": float(sps),
                     "speedup_vs_pergather": float(speedup)}
    return out


# ---------------------------------------------------------------------------
# Scaling: the streaming chunked fleet runtime, 16 -> 1024 sessions
# ---------------------------------------------------------------------------

#: Established run-to-run throughput band of the identical engine on the CI
#: box (session-steps/s at 64 sessions): BENCH_0 measured 63.3, BENCH_1 55.1.
STEADY_STATE_BAND_64 = (55.0, 63.5)


def _scaling_fleet(n: int, chunk, updates: int,
                   overlap: bool = True) -> FleetTuner:
    """Fleet for ``n`` sessions. Grids of >= 64 sessions split over TWO
    workloads, smaller ones use one — the sweep deliberately spans >= 2 grid
    shapes so the compile-reuse claim (one chunk executable serves every
    grid shape) is exercised by measurement, not construction."""
    workloads = ["seq_write"] if n < 64 else ["seq_write", "file_server"]
    cfg = DDPGConfig.for_env(LustreSimEnv("seq_write"),
                             updates_per_step=updates)
    return FleetTuner.from_grid(
        workloads, [{"throughput": 1.0}], list(range(n // len(workloads))),
        engine="scan", ddpg_config=cfg, eval_runs=1, chunk=chunk,
        overlap=overlap)


def bench_overlap_ab(n: int, chunk: int, steps: int, updates: int = 96,
                     repeats: int = 1) -> tuple:
    """Double-buffered chunk staging A/B: the same fleet, overlap off vs on.

    ``overlap=False`` is the pre-overlap serial schedule (stage -> compute ->
    drain per chunk); ``overlap=True`` hides host->device staging and host
    trace decode under the previous chunk's compute. Outputs are bitwise
    identical (pinned by tests/test_chunked_fleet.py) — this measures the
    wall-clock difference only. Returns (csv rows, summary fragment)."""
    rows = [csv_row("overlap", "sessions", "chunks", "sps_median", "sps_min",
                    "noise_band")]
    out = {"sessions": n, "chunk": chunk, "steps": steps, "updates": updates}
    from repro.core.episode import last_fleet_run_stats
    for overlap in (False, True):
        fleet = _scaling_fleet(n, chunk, updates, overlap=overlap)
        fleet.precompile(steps)

        def one():
            t0 = time.perf_counter()
            fleet.run(steps)
            return steps * n / (time.perf_counter() - t0)

        meas = repeat_measure(one, repeats)
        stats = last_fleet_run_stats()
        assert stats["overlap"] == overlap
        key = "on" if overlap else "off"
        out[key] = {"session_steps_per_sec": meas["median"],
                    "min": meas["min"], "noise_band": meas["noise_band"],
                    "peak_device_bytes": stats["peak_device_bytes"],
                    "staging": dict(stats.get("staging", {}))}
        rows.append(csv_row(key, n, stats["num_chunks"],
                            f"{meas['median']:.2f}", f"{meas['min']:.2f}",
                            f"{meas['noise_band']:.3f}"))
    out["speedup_on_vs_off"] = (out["on"]["session_steps_per_sec"]
                                / out["off"]["session_steps_per_sec"])
    rows.append(csv_row("speedup_on_vs_off",
                        f"{out['speedup_on_vs_off']:.2f}", "", "", "", ""))
    eff = out["on"]["staging"].get("overlap_efficiency")
    if eff is not None:
        rows.append(csv_row("overlap_efficiency", f"{eff:.3f}", "", "", "",
                            ""))
    return rows, out


def bench_service(n: int, chunk: int, steps: int, updates: int = 96,
                  repeats: int = 1) -> tuple:
    """Service-mode throughput: the persistent ``FleetService`` driving the
    same session population through its leased-slot chunk loop.

    Measures ``advance(steps)`` rounds on a standing fleet — the serving-
    loop overhead (per-session host state, boundary restaging, lease
    bookkeeping) relative to the batch ``FleetTuner`` numbers above.
    Returns (csv rows, summary fragment)."""
    from repro.core import FleetService

    cfg = DDPGConfig.for_env(LustreSimEnv("seq_write"),
                             updates_per_step=updates)
    svc = FleetService(chunk=chunk, ddpg_config=cfg, eval_runs=1)
    for i in range(n):
        svc.request_join("seq_write", {"throughput": 1.0}, i)
    svc.advance(steps)  # lease + warm the chunk executable

    def one():
        t0 = time.perf_counter()
        svc.advance(steps)
        return steps * n / (time.perf_counter() - t0)

    meas = repeat_measure(one, repeats)
    stats = {k: v for k, v in svc.last_stats.items() if k != "program"}
    rows = [csv_row("mode", "sessions", "chunks", "sps_median", "sps_min",
                    "noise_band"),
            csv_row("service", n, stats["num_chunks"],
                    f"{meas['median']:.2f}", f"{meas['min']:.2f}",
                    f"{meas['noise_band']:.3f}")]
    return rows, {
        "sessions": n, "chunk": chunk, "steps": steps, "updates": updates,
        "session_steps_per_sec": meas["median"], "min": meas["min"],
        "noise_band": meas["noise_band"],
        "executable_cache_size": stats["executable_cache_size"],
    }


def bench_scaling(session_counts: list, chunk: int, steps: int,
                  updates: int = 96, repeats: int = 1) -> tuple:
    """Streaming chunked runtime across fleet sizes + the monolithic control.

    For every N the WHOLE fleet runs as ceil(N / chunk) chunks through one
    compiled episode program; recorded per point: session-steps/s
    (median over ``repeats``, with the noise band), end-to-end wall clock,
    and the measured peak resident device bytes per session
    (``core.episode.last_fleet_run_stats`` — sampled live-array bytes, not
    an estimate). The monolithic control re-runs the largest-but-64 fleet at
    chunk=None (one chunk of all 64 sessions, the pre-streaming schedule) to
    measure the device footprint the chunked runtime removes; it runs LAST
    so its [64]-shaped bucket cannot pollute the sweep's compile count.

    Returns (csv rows, summary dict for BENCH_<n>.json).
    """
    from repro.core.episode import last_fleet_run_stats

    rows = [csv_row("sessions", "grid", "chunks", "sps_median", "sps_min",
                    "noise_band", "peak_bytes_per_session", "wall_s_median")]
    points, program_ids, cache_sizes, grid_shapes = [], set(), [], set()
    for n in session_counts:
        fleet = _scaling_fleet(n, chunk, updates)
        n_workloads = len(set(l.split("|")[0] for l in fleet.labels))
        grid_shapes.add((n_workloads, len(fleet.labels)))
        grid_label = f"{n_workloads}w-{len(fleet.labels)}cells"
        fleet.precompile(steps)

        def one():
            t0 = time.perf_counter()
            fleet.run(steps)
            return steps * n / (time.perf_counter() - t0)

        meas = repeat_measure(one, repeats)
        stats = last_fleet_run_stats()
        program_ids.add(id(stats["program"]))
        cache_sizes.append(stats["executable_cache_size"])
        wall = steps * n / meas["median"]
        per_session = stats["peak_device_bytes"] / n
        points.append({
            "sessions": n,
            "grid": grid_label,
            "chunks": stats["num_chunks"],
            "session_steps_per_sec": meas["median"],
            "session_steps_per_sec_min": meas["min"],
            "noise_band": meas["noise_band"],
            "wall_seconds": wall,
            "peak_device_bytes": stats["peak_device_bytes"],
            "peak_device_bytes_per_session": per_session,
        })
        rows.append(csv_row(n, points[-1]["grid"], stats["num_chunks"],
                            f"{meas['median']:.2f}", f"{meas['min']:.2f}",
                            f"{meas['noise_band']:.3f}",
                            f"{per_session:.0f}", f"{wall:.1f}"))

    # monolithic control: 64 sessions, one chunk of all 64 (runs after the
    # sweep so its extra shape bucket never counts against the sweep)
    mono = _scaling_fleet(64, None, updates)
    mono.precompile(steps)

    def one_mono():
        t0 = time.perf_counter()
        mono.run(steps)
        return steps * 64 / (time.perf_counter() - t0)

    mono_meas = repeat_measure(one_mono, repeats)
    mono_stats = last_fleet_run_stats()
    mono_point = {
        "sessions": 64, "chunks": mono_stats["num_chunks"],
        "session_steps_per_sec": mono_meas["median"],
        "noise_band": mono_meas["noise_band"],
        "peak_device_bytes": mono_stats["peak_device_bytes"],
        "peak_device_bytes_per_session": mono_stats["peak_device_bytes"] / 64,
    }
    rows.append(csv_row("64(monolithic)", "2w-64cells", 1,
                        f"{mono_meas['median']:.2f}", f"{mono_meas['min']:.2f}",
                        f"{mono_meas['noise_band']:.3f}",
                        f"{mono_point['peak_device_bytes_per_session']:.0f}",
                        f"{steps * 64 / mono_meas['median']:.1f}"))

    largest = points[-1]
    summary = {
        "benchmark": "fleet_scaling",
        "chunk": chunk, "steps": steps, "updates": updates,
        "repeats": repeats,
        "scaling": points,
        "monolithic_64": mono_point,
        "memory_ratio_monolithic64_vs_largest": (
            mono_point["peak_device_bytes_per_session"]
            / largest["peak_device_bytes_per_session"]),
        "compile": {
            "shared_executable": len(program_ids) == 1,
            "executables_during_sweep": max(cache_sizes),
            "grid_shapes": len(grid_shapes),
        },
    }
    p64 = next((p for p in points if p["sessions"] == 64), None)
    if p64 is not None:
        lo, hi = STEADY_STATE_BAND_64
        summary["steady_state_64"] = {
            "session_steps_per_sec": p64["session_steps_per_sec"],
            "established_band": [lo, hi],
            "within_established_band": bool(
                lo <= p64["session_steps_per_sec"] <= hi),
            # the band was established on BENCH_0/1's single-workload fleet;
            # the monolithic control below runs THIS sweep's exact grid, so
            # its ratio is the composition-controlled chunking cost
            "chunked_vs_monolithic_same_grid": (
                p64["session_steps_per_sec"]
                / mono_point["session_steps_per_sec"]),
        }
    return rows, summary


def scaling_summary(quick: bool = False, repeats: int = None) -> dict:
    """BENCH_<n>.json payload for the scaling benchmark (reuses the
    measurements of a preceding same-``repeats`` ``run_scaling`` call in
    this process)."""
    key = ("scaling", quick, repeats)
    if key in _LAST_RESULTS:
        summary = _LAST_RESULTS[key]
    else:
        _, summary = _run_scaling_measure(quick, repeats)
        _LAST_RESULTS[key] = summary
    summary = dict(summary, quick=quick)
    summary.update(_scaling_fragments(quick, repeats))
    p64 = next((p for p in summary["scaling"] if p["sessions"] == 64), None)
    if p64 is not None:
        # the trajectory series' canonical key (64-session steady state), so
        # every future BENCH point can compare against this one regardless
        # of payload kind
        summary["fleet_session_steps_per_sec"] = p64["session_steps_per_sec"]
    prev = _previous_bench()
    if prev is not None and not quick:
        prev_sps = prev.get("fleet_session_steps_per_sec")
        if prev_sps and p64:
            summary["vs_previous_bench"] = vs_previous(
                {"median": p64["session_steps_per_sec"],
                 "noise_band": p64["noise_band"]}, prev_sps, prev["_file"])
    return summary


def _scaling_fragments(quick: bool, repeats: int = None) -> dict:
    """Overlap A/B + service-mode fragments riding along in the scaling
    BENCH point (cached so a csv run and the json summary measure once)."""
    key = ("scaling_frag", quick, repeats)
    if key not in _LAST_RESULTS:
        if quick:
            _, ab = bench_overlap_ab(256, chunk=8, steps=2, updates=24,
                                     repeats=repeats or 1)
            _, svc = bench_service(32, chunk=8, steps=2, updates=24,
                                   repeats=repeats or 1)
        else:
            # A/B at the sweep's largest size — that is where the synchronous
            # staging dip lived; service point at 256 to bound join cost
            _, ab = bench_overlap_ab(1024, chunk=16, steps=5, updates=96,
                                     repeats=repeats or 1)
            _, svc = bench_service(256, chunk=16, steps=5, updates=96,
                                   repeats=repeats or 3)
        _LAST_RESULTS[key] = {"overlap_ab": ab, "service_mode": svc}
    return _LAST_RESULTS[key]


def _run_scaling_measure(quick: bool, repeats: int = None) -> tuple:
    if quick:
        return bench_scaling([16, 256], chunk=8, steps=2, updates=24,
                             repeats=repeats or 1)
    return bench_scaling([16, 64, 256, 1024], chunk=16, steps=5, updates=96,
                         repeats=repeats or 3)


def run_scaling(quick: bool = False, repeats: int = None) -> list:
    rows, summary = _run_scaling_measure(quick, repeats)
    _LAST_RESULTS[("scaling", quick, repeats)] = summary
    return rows


# Measurements from the most recent run(quick) call, keyed by ``quick`` —
# episode_summary reuses them so the csv table and the BENCH_<n>.json point
# come from ONE measurement instead of re-timing (the CI box has 10-15%
# run-to-run variance; duplicate timing would let the two outputs disagree).
_LAST_RESULTS: dict = {}


def episode_summary(quick: bool = False) -> dict:
    """BENCH_<n>.json payload: the episode-engine perf trajectory point,
    plus the learner-formulation comparison and — when a previous
    ``BENCH_<n>.json`` exists at the repo root — the measured ratio against
    its recorded fleet throughput (same box or not, the raw numbers are
    both preserved, so the comparison is auditable). Reuses the measurements
    of a preceding ``run(quick)`` call in this process, measuring only if
    none exist."""
    if quick in _LAST_RESULTS:
        summary, learner_rows = _LAST_RESULTS[quick]
    elif quick:
        _, summary = bench_episode_engine([8], steps=3, updates=24)
        learner_rows = bench_learner_paths(8, updates=24, reps=2)
    else:
        _, summary = bench_episode_engine([16, 64], steps=5, updates=96)
        learner_rows = bench_learner_paths(64, updates=96)
    top = summary["fleets"][-1]
    payload = {
        "benchmark": "episode_engine",
        "quick": quick,
        "host_loop_steps_per_sec": summary["host_loop_steps_per_sec"],
        "single_scan_steps_per_sec": summary["single_scan_steps_per_sec"],
        "fleet_size": top["sessions"],
        "fleet_session_steps_per_sec": top["session_steps_per_sec"],
        "fleet_session_steps_per_sec_min": top.get(
            "min", top["session_steps_per_sec"]),
        "noise_band": top.get("noise_band"),
        "speedup_vs_host_loop": top["speedup_vs_host_loop"],
        "fleets": summary["fleets"],
        "learner_paths": _learner_summary(learner_rows),
    }
    prev = _previous_bench()
    if prev is not None and not quick:
        prev_sps = prev.get("fleet_session_steps_per_sec")
        if prev_sps:
            payload["vs_previous_bench"] = vs_previous(
                {"median": top["session_steps_per_sec"],
                 "noise_band": top.get("noise_band", 0.0)},
                prev_sps, prev["_file"])
    return payload


def _previous_bench() -> dict:
    """Latest FULL-mode repo-root BENCH_<n>.json, or None.

    Quick-mode points (``"quick": true`` — smaller fleets, fewer updates)
    are skipped: a 64-session/96-update throughput divided by an
    8-session/24-update one would be a meaningless trajectory ratio."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    latest, n = None, 0
    while os.path.exists(os.path.join(root, f"BENCH_{n}.json")):
        with open(os.path.join(root, f"BENCH_{n}.json")) as f:
            point = json.load(f)
        if not point.get("quick"):
            point["_file"] = f"BENCH_{n}.json"
            latest = point
        n += 1
    return latest


def run(quick: bool = False, repeats: int = 1) -> list:
    if quick:
        rows = bench_learn_paths(env_steps=3, updates=24)
        rows += [""] + bench_dimensionality(env_steps=3, updates=24)
        rows += [""] + bench_fleet_scaling([1, 4], steps=2)
        learner_rows = bench_learner_paths(8, updates=24, reps=2)
        erows, summary = bench_episode_engine([8], steps=3, updates=24,
                                              repeats=repeats)
    else:
        rows = bench_learn_paths(env_steps=10, updates=96)
        rows += [""] + bench_dimensionality(env_steps=10, updates=96)
        rows += [""] + bench_fleet_scaling([1, 4, 8, 16], steps=5)
        learner_rows = bench_learner_paths(64, updates=96)
        erows, summary = bench_episode_engine([16, 64], steps=5, updates=96,
                                              repeats=repeats)
    _LAST_RESULTS[quick] = (summary, learner_rows)
    return rows + [""] + learner_rows + [""] + erows


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--quick", action="store_true",
                        help="small sizes for CI smoke runs")
    parser.add_argument("--repeats", type=int, default=1,
                        help="timed repetitions per measurement (median + "
                        "min + noise band recorded)")
    parser.add_argument("--scaling", action="store_true",
                        help="run the chunked-runtime scaling benchmark "
                        "instead of the fleet/learner set")
    parser.add_argument("--service", action="store_true",
                        help="run the persistent-FleetService throughput "
                        "benchmark (advance() rounds on a standing fleet)")
    parser.add_argument("--overlap-ab", action="store_true",
                        help="run the double-buffered staging A/B "
                        "(overlap off vs on, bitwise-identical outputs)")
    args = parser.parse_args()
    if args.service:
        n, c, s, u = (32, 8, 2, 24) if args.quick else (256, 16, 5, 96)
        rows, _ = bench_service(n, c, s, u, repeats=args.repeats)
        print("\n".join(rows))
    elif args.overlap_ab:
        n, c, s, u = (256, 8, 2, 24) if args.quick else (1024, 16, 5, 96)
        rows, _ = bench_overlap_ab(n, c, s, u, repeats=args.repeats)
        print("\n".join(rows))
    elif args.scaling:
        print("\n".join(run_scaling(quick=args.quick, repeats=args.repeats)))
    else:
        print("\n".join(run(quick=args.quick, repeats=args.repeats)))
