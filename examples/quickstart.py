"""Quickstart: tune the simulated Lustre file system with Magpie (the paper's
headline experiment, single performance indicator) in ~2 minutes on CPU.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --engine scan --steps 30
    PYTHONPATH=src python examples/quickstart.py --sessions 32 --chunk 8

``--engine host`` steps the Fig. 1 loop from Python against the numpy
simulator; ``--engine scan`` runs the identical episode as ONE fused XLA
program over the pure-JAX env model (``core.episode``) — same algorithm,
same budget, no host boundary per step. ``--sessions N`` (> 1) tunes N
same-workload sessions (different seeds) through the streaming chunked fleet
runtime — ``--chunk C`` sessions at a time through one compiled episode
program, with the ``memory_plan()`` capacity summary printed up front.
``--compile-cache`` persists compiled programs across invocations.
"""

import argparse

from repro.core import DDPGConfig, MagpieAgent, Scalarizer, Tuner
from repro.envs import LustreSimEnv


def _run_fleet(args) -> None:
    from repro.core import FleetTuner
    fleet = FleetTuner.from_grid(
        ["seq_write"], [{"throughput": 1.0}], list(range(args.sessions)),
        engine="scan", chunk=args.chunk, eval_runs=1)
    plan = fleet.memory_plan(steps=args.steps)
    per = plan["per_session"]
    print(f"memory plan ({plan['sessions']} sessions, chunk {plan['chunk']}, "
          f"{plan['steps']} steps): learner {per['learner_bytes']:,} B + "
          f"replay {per['replay_bytes']:,} B per session; one chunk keeps "
          f"{plan['chunk_device_bytes']:,} B on device "
          f"(validated vs live: {plan['matches_live']})")
    result = fleet.run(steps=args.steps)
    stats = result.summary("throughput")
    print(f"{stats['sessions']} sessions tuned in {result.wall_seconds:.1f}s: "
          f"mean throughput gain {stats['mean']*100:+.1f}% "
          f"(p50 {stats['p50']*100:+.1f}%)")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--engine", choices=("host", "scan"), default="host",
                        help="host = dict loop on the numpy simulator; "
                        "scan = fused whole-episode engine on the pure-JAX "
                        "env model")
    parser.add_argument("--steps", type=int, default=30,
                        help="tuning steps (paper budget: 30)")
    parser.add_argument("--sessions", type=int, default=1,
                        help="tune this many same-workload sessions as a "
                        "streamed fleet (> 1 implies the scan engine)")
    parser.add_argument("--chunk", type=int, default=None,
                        help="sessions per streamed chunk (fleet mode)")
    parser.add_argument("--compile-cache", action="store_true",
                        help="enable JAX's persistent compilation cache "
                        "($JAX_COMPILATION_CACHE_DIR if set, else "
                        "<repo>/.jax_cache)")
    args = parser.parse_args()

    if args.compile_cache:
        from repro.core import enable_persistent_compilation_cache
        path = enable_persistent_compilation_cache()
        print(f"persistent compilation cache: {path}")

    if args.sessions > 1:
        _run_fleet(args)
        return

    # Environment: 6-OST Lustre + Sequential Write workload (paper §III-B).
    # The scan engine needs the pure-model adapter; the host engine can run
    # either — numpy simulator kept here to match the paper scripts.
    env = LustreSimEnv("seq_write", seed=0)
    if args.engine == "scan":
        env = env.to_model_env()

    # Objective: throughput only (paper §III-C); weights define preference.
    scal = Scalarizer(weights={"throughput": 1.0}, specs=env.metric_specs)

    # The agent: DDPG sized from the (stripe_count, stripe_size) ParamSpace.
    agent = MagpieAgent(DDPGConfig.for_env(env), seed=0)

    tuner = Tuner(env, scal, agent, engine=args.engine)
    result = tuner.run(steps=args.steps)

    print(f"engine:           {args.engine} ({args.steps} steps)")
    print(f"default config:   {result.default_config} "
          f"-> {result.default_metrics['throughput']:.1f} MB/s")
    print(f"tuned config:     {result.best_config} "
          f"-> {result.best_metrics['throughput']:.1f} MB/s")
    print(f"throughput gain:  {result.gain('throughput')*100:.1f}% "
          f"(paper: +250.4% on this workload)")
    print(f"simulated restart downtime: "
          f"{result.simulated_restart_seconds:.0f} s over {args.steps} tuning steps")


if __name__ == "__main__":
    main()
