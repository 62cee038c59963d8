"""Fleet tuning: the paper's whole evaluation grid as one fused JAX program.

Runs a seeds x workloads x objectives grid of independent Magpie tuning
sessions concurrently, then prints per-session results plus the aggregate
gain statistics the paper reports in Fig. 4/5 (91.8% average throughput gain
across workloads).

    PYTHONPATH=src python examples/tune_fleet.py
    PYTHONPATH=src python examples/tune_fleet.py --sessions 64 --chunk 16
    PYTHONPATH=src python examples/tune_fleet.py --service --checkpoint /tmp/f
    PYTHONPATH=src python examples/tune_fleet.py --resume /tmp/f
    PYTHONPATH=src python examples/tune_fleet.py --guardrails --min-gain 0.02
    PYTHONPATH=src python examples/tune_fleet.py --chaos --max-resets 3

``--sessions N`` spreads N sessions (seeds) over the workloads and runs them
through the streaming chunked scan engine: chunks of ``--chunk`` sessions
stream through ONE compiled episode program, so peak device memory is
O(chunk) no matter how large the fleet — the printed ``memory_plan()``
summary shows the capacity math before anything runs. ``--compile-cache``
persists the compiled episode across processes (back-to-back runs skip
XLA compilation entirely).

``--service`` runs the same grid through the persistent ``FleetService``
(leased chunk slots, advance() rounds, checkpoint every round when
``--checkpoint DIR`` is set); ``--resume DIR`` restores a checkpointed
service and finishes its remaining rounds bit-identically to a run that
was never interrupted.

``--guardrails`` wraps every session in a shadow/canary ``DeploymentPolicy``
(``core/guardrails.py``): proposals are shadow-scored without touching the
live config, promoted only above ``--min-gain`` within the
``--restart-budget`` downtime cap, and rolled back if the live objective
regresses inside ``--rollback-window`` steps. Guarded runs print the fleet's
promotion/rollback/budget counters; a resumed service keeps the policy it
was checkpointed with.

``--resilience`` turns on the self-healing scan body (``core/resilience.py``)
for every session: the engine detects non-finite params/losses/metrics after
each learn scan and branch-free resets the diverged session to its last-good
snapshot; past ``--max-resets`` the session degrades to frozen-incumbent
mode so the rest of the fleet keeps training. ``--chaos`` injects a
deterministic NaN poison into every session's metric stream (implies
``--resilience``) so you can watch the recovery happen — the run ends with
the fleet's health counters (non-finite detections, resets, degraded
sessions). A service checkpoint keeps the resilience policy: ``--resume``
continues self-healing with the policy it was checkpointed with.

``--share`` turns on cross-session experience sharing (``core/sharing.py``)
within each workload cell — the sessions tuning the same workload under
different seeds. ``--share replay`` merges each cell's replay into one
shared FIFO window (replay bytes per session drop by the cell size);
``--share replay+avg`` additionally averages the cell's learner parameters
every ``--avg-every`` env steps. Sharing forces the scan engine; the run
prints the ``memory_plan()`` replay delta and, per cell, how many steps the
cell took to reach 90% of its final objective. A service checkpoint keeps
the sharing config — ``--resume`` continues with the cells (and their
merged windows) it was checkpointed with.
"""

import argparse

from repro.core import DeploymentPolicy, FleetService, FleetTuner, SharingConfig


def _policy(args):
    """The DeploymentPolicy the --guardrails flags describe (None when off)."""
    if not args.guardrails:
        return None
    return DeploymentPolicy(min_gain=args.min_gain,
                            max_restart_seconds=args.restart_budget,
                            rollback_window=args.rollback_window)


def _resilience(args):
    """The ResiliencePolicy the --resilience/--chaos flags describe
    (None when off — the plain engine, same compiled program)."""
    if not (args.resilience or args.chaos):
        return None
    from repro.core import ResiliencePolicy
    return ResiliencePolicy(max_resets=args.max_resets)


def _chaos_env_factory(args):
    """--chaos: every session's env wraps its model in one shared NaN-poison
    schedule (one step_fn identity keeps the fleet on one compiled
    program) — the canonical divergence the resilient engine must absorb."""
    if not args.chaos:
        return None
    from repro.envs import ChaosConfig, FaultInjectedModel, ModelEnv
    from repro.envs.lustre_sim import LustreSimEnv
    specs = ChaosConfig(nan_metric="throughput", nan_start=6,
                        nan_duration=2).fault_specs()

    def env_factory(workload, seed):
        base = LustreSimEnv(workload, seed=seed).as_model()
        return ModelEnv(FaultInjectedModel(base, specs), seed=seed)

    return env_factory


def _print_health_summary(stats) -> None:
    """Fleet-wide health counters for a resilient run (in-graph totals)."""
    stats = [s for s in stats if s]
    if not stats:
        return
    print(f"health ({len(stats)} resilient sessions): "
          f"{sum(s['nonfinite_total'] for s in stats)} non-finite steps, "
          f"{sum(s['resets_total'] for s in stats)} resets, "
          f"{sum(1 for s in stats if s['degraded'])} degraded")


def _sharing(args):
    """The SharingConfig the --share flag describes (None when off)."""
    if args.share == "off":
        return None
    if args.share == "replay":
        return SharingConfig(shared_replay=True)
    return SharingConfig(shared_replay=True, avg_every=args.avg_every,
                         avg_opt_state=True)


def _steps_to_target(histories, fraction=0.9, window=4):
    """First step at which the cell's trailing-``window`` mean objective
    holds ``fraction`` of its end-of-run value (None = never)."""
    import numpy as np
    per = np.stack([[h.objective for h in hist] for hist in histories])
    mean = per.mean(axis=0)
    trail = np.convolve(mean, np.ones(window) / window, mode="valid")
    target = fraction * trail[-1]
    hit = np.nonzero(trail >= target)[0]
    return int(hit[0] + window) if hit.size else None


def _print_cell_targets(labels, results, cell_size) -> None:
    for c0 in range(0, len(results), cell_size):
        cell = results[c0:c0 + cell_size]
        label = labels[c0].rsplit("|", 1)[0]  # strip the |seedN suffix
        steps = _steps_to_target([r.history for r in cell])
        print(f"  cell {label:30s} steps to 90% of final objective: "
              f"{steps if steps is not None else 'never'}")


def _run_service(args) -> None:
    """The grid as a persistent FleetService: advance() rounds with an
    optional checkpoint each round; --resume continues bit-identically."""
    weights = {"throughput": 1.0}
    if args.resume:
        # restore() rebuilds the policy from the checkpoint, so a resumed
        # service keeps the guardrails it was running with; the env
        # DEFINITION is code, not data — a chaos-checkpointed service must
        # resume with --chaos so the rebuilt envs match (drift raises)
        svc = FleetService.restore(args.resume,
                                   env_factory=_chaos_env_factory(args))
        print(f"resumed service from {args.resume}: {len(svc.active)} "
              f"sessions at step {svc.total_steps}/{args.steps}")
        if svc.sharing is not None:
            # restore() rebuilt the cells (and their merged replay windows)
            # from the checkpoint — the sharing config is durable state
            print(f"  sharing (from checkpoint): {svc.sharing} "
                  f"cell_size={svc.cell_size}")
        if svc.resilience is not None:
            # the resilience policy is durable state too: a resumed service
            # keeps self-healing exactly as it was checkpointed
            print(f"  resilience (from checkpoint): {svc.resilience}")
    else:
        workloads = ["seq_write", "video_server", "file_server"]
        seeds = list(range(max(1, round(args.sessions / len(workloads)))))
        sharing = _sharing(args)
        cs = len(seeds) if sharing is not None else 1
        # the lease width must hold whole cells
        chunk = args.chunk or max(8 // cs, 1) * cs
        svc = FleetService(chunk=chunk, eval_runs=1,
                           checkpoint_dir=args.checkpoint,
                           env_factory=_chaos_env_factory(args),
                           policy=_policy(args), sharing=sharing,
                           cell_size=cs, resilience=_resilience(args))
        # same per-cell seed offsets as FleetTuner.from_grid, so a service
        # run is comparable session-for-session with the batch path
        cell = 0
        for w in workloads:
            for s in seeds:
                svc.request_join(w, weights, s + 1000 * cell)
                cell += 1
        print(f"service: {cell} sessions joining, chunk {svc.chunk}")
    while svc.total_steps < args.steps:
        steps = min(args.round_steps, args.steps - svc.total_steps)
        sids = svc.advance(steps)
        st = svc.last_stats
        print(f"round -> step {svc.total_steps}/{args.steps}: "
              f"{len(sids)} sessions, "
              f"{st['session_steps_per_sec']:.1f} session-steps/s")
        if "guardrails" in st:
            g = st["guardrails"]
            print(f"  guardrails: {g['promotions']:.0f} promoted, "
                  f"{g['rejected_min_gain']:.0f}/{g['rejected_budget']:.0f} "
                  f"rejected (gain/budget), {g['rollbacks']:.0f} rollbacks, "
                  f"{g['restart_seconds']:.1f}s restart downtime this round")
        if svc.checkpoint_dir:
            print(f"  checkpoint: {svc.checkpoint()}")
    labels = dict(svc.active)
    for sid in labels:
        svc.request_leave(sid)
    svc.advance(0)
    gains = []
    for sid, label in list(labels.items())[:12]:
        res = svc.result(sid)
        print(f"{label:40s} {res.default_metrics['throughput']:7.1f} "
              f"-> {res.best_metrics['throughput']:7.1f} MB/s "
              f"({res.gain('throughput')*100:+.1f}%)")
    for sid in labels:
        gains.append(svc.result(sid).gain("throughput"))
    print(f"\naggregate throughput gain over {len(gains)} sessions: "
          f"mean {sum(gains)/len(gains)*100:+.1f}%")
    if svc.sharing is not None and svc.cell_size > 1:
        sids = list(labels)
        _print_cell_targets([labels[sid] for sid in sids],
                            [svc.result(sid) for sid in sids],
                            svc.cell_size)
    results = [svc.result(sid) for sid in labels]
    _print_guardrail_summary([r.guardrail_stats for r in results])
    _print_health_summary([r.health_stats for r in results])


def _print_guardrail_summary(stats) -> None:
    """Fleet-wide promotion/rollback/budget totals for a guarded run."""
    stats = [s for s in stats if s]
    if not stats:
        return
    print(f"guardrails ({len(stats)} guarded sessions): "
          f"{sum(s['promotions'] for s in stats):.0f} promotions, "
          f"{sum(s['rejected_min_gain'] for s in stats):.0f}/"
          f"{sum(s['rejected_budget'] for s in stats):.0f} rejected "
          f"(gain/budget), {sum(s['rollbacks'] for s in stats):.0f} "
          f"rollbacks, {sum(s['restart_budget_spent'] for s in stats):.1f}s "
          f"restart downtime")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--sessions", type=int, default=9,
                        help="total tuning sessions (spread over 3 workloads)")
    parser.add_argument("--chunk", type=int, default=None,
                        help="sessions per streamed chunk (scan engine); "
                        "default: one chunk of the whole fleet")
    parser.add_argument("--steps", type=int, default=30,
                        help="tuning steps per session (paper budget: 30)")
    parser.add_argument("--compile-cache", action="store_true",
                        help="enable JAX's persistent compilation cache "
                        "($JAX_COMPILATION_CACHE_DIR if set, else "
                        "<repo>/.jax_cache)")
    parser.add_argument("--service", action="store_true",
                        help="run through the persistent FleetService "
                        "(leased slots, advance() rounds)")
    parser.add_argument("--checkpoint", default=None, metavar="DIR",
                        help="service mode: checkpoint directory, written "
                        "every round")
    parser.add_argument("--resume", default=None, metavar="DIR",
                        help="restore a checkpointed service from DIR and "
                        "finish its rounds (implies --service)")
    parser.add_argument("--round-steps", type=int, default=5,
                        help="service mode: tuning steps per advance() round")
    parser.add_argument("--guardrails", action="store_true",
                        help="gate every apply behind a shadow/canary "
                        "DeploymentPolicy (forces the scan engine)")
    parser.add_argument("--min-gain", type=float, default=0.01,
                        help="guardrails: minimum shadow-projected relative "
                        "gain to promote a proposal")
    parser.add_argument("--restart-budget", type=float, default=float("inf"),
                        metavar="SECONDS",
                        help="guardrails: total restart downtime a session "
                        "may spend on promotions")
    parser.add_argument("--rollback-window", type=int, default=4,
                        metavar="STEPS",
                        help="guardrails: steps a fresh canary is watched "
                        "for a live regression before it becomes the "
                        "incumbent")
    parser.add_argument("--resilience", action="store_true",
                        help="self-heal diverged sessions: snapshot/reset on "
                        "non-finite detection, degrade-to-frozen past the "
                        "reset budget (forces the scan engine)")
    parser.add_argument("--max-resets", type=int, default=3,
                        help="resilience: snapshot resets a session may "
                        "spend before the next divergence degrades it")
    parser.add_argument("--chaos", action="store_true",
                        help="inject a deterministic NaN poison into every "
                        "session's metric stream to demonstrate recovery "
                        "(implies --resilience; a chaos-checkpointed "
                        "service must --resume with --chaos too)")
    parser.add_argument("--share", choices=["off", "replay", "replay+avg"],
                        default="off",
                        help="cross-session experience sharing per workload "
                        "cell: merged replay window, optionally + periodic "
                        "parameter averaging (forces the scan engine)")
    parser.add_argument("--avg-every", type=int, default=4, metavar="STEPS",
                        help="share=replay+avg: env steps between cell "
                        "parameter averages")
    args = parser.parse_args()

    if args.compile_cache:
        from repro.core import enable_persistent_compilation_cache
        path = enable_persistent_compilation_cache()
        print(f"persistent compilation cache: {path}")

    if args.service or args.resume:
        _run_service(args)
        return

    workloads = ["seq_write", "video_server", "file_server"]
    # the grid is a full workloads x seeds cross product, so the session
    # count is rounded to the nearest multiple of len(workloads) — say so
    # instead of silently running a different fleet than requested
    seeds = list(range(max(1, round(args.sessions / len(workloads)))))
    n_sessions = len(workloads) * len(seeds)
    if n_sessions != args.sessions:
        print(f"note: running {n_sessions} sessions "
              f"({len(workloads)} workloads x {len(seeds)} seeds; "
              f"{args.sessions} requested)")
    sharing = _sharing(args)
    resilience = _resilience(args)
    engine = ("scan" if (args.guardrails or args.chunk is not None
                         or sharing is not None or resilience is not None
                         or n_sessions > 9)
              else "host")
    fleet = FleetTuner.from_grid(
        workloads=workloads,
        objectives=[{"throughput": 1.0}],
        seeds=seeds,
        env_factory=_chaos_env_factory(args),
        engine=engine,
        chunk=args.chunk if engine == "scan" else None,
        eval_runs=1 if n_sessions > 9 else 3,
        policy=_policy(args),
        sharing=sharing,
        resilience=resilience,
    )

    if engine == "scan":
        plan = fleet.memory_plan(steps=args.steps)
        per = plan["per_session"]
        print(f"memory plan ({plan['sessions']} sessions, chunk "
              f"{plan['chunk']}, {plan['steps']} steps):")
        replay_note = ""
        if plan["cell_size"] > 1:
            # the merged cell window amortizes one buffer over the cell
            replay_note = (f" = 1/{plan['cell_size']} of the independent "
                           f"{per['replay_bytes'] * plan['cell_size']:,} B")
        print(f"  per session: learner {per['learner_bytes']:,} B, replay "
              f"{per['replay_bytes']:,} B ({plan['replay_dtype']})"
              f"{replay_note}, trace {per['trace_bytes_per_step']} B/step")
        print(f"  device (one chunk resident): "
              f"{plan['chunk_device_bytes']:,} B")
        print(f"  host (whole fleet): {plan['fleet_host_bytes']:,} B "
              f"(validated vs live buffers: {plan['matches_live']})")

    print(f"running {fleet.agent.num_sessions} tuning sessions "
          f"({engine} engine)...")
    result = fleet.run(steps=args.steps)

    shown = min(len(result.results), 12)
    for label, res in zip(result.labels[:shown], result.results[:shown]):
        print(f"{label:40s} {res.default_metrics['throughput']:7.1f} "
              f"-> {res.best_metrics['throughput']:7.1f} MB/s "
              f"({res.gain('throughput')*100:+.1f}%)  best={res.best_config}")
    if shown < len(result.results):
        print(f"... ({len(result.results) - shown} more sessions)")

    stats = result.summary("throughput")
    print(f"\naggregate throughput gain over {stats['sessions']} sessions: "
          f"mean {stats['mean']*100:+.1f}%  "
          f"p25/p50/p75 {stats['p25']*100:+.1f}/{stats['p50']*100:+.1f}/"
          f"{stats['p75']*100:+.1f}%  "
          f"range [{stats['min']*100:+.1f}%, {stats['max']*100:+.1f}%]")
    print(f"fleet wall time: {result.wall_seconds:.1f}s "
          f"for {stats['sessions']} x {args.steps}-step sessions")
    if sharing is not None and fleet.cell_size > 1:
        print(f"sharing: {args.share} over cells of {fleet.cell_size}")
        _print_cell_targets(result.labels, result.results, fleet.cell_size)
    _print_guardrail_summary([r.guardrail_stats for r in result.results])
    _print_health_summary([r.health_stats for r in result.results])


if __name__ == "__main__":
    main()
