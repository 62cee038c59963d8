"""Smoke run of the fleet tuning service on a TPU.

Drives the main path once through the entry points a user calls, at the
service's real widths, and checks what comes out:

  python chip_smoke.py            # one chip: both learners, then the service
  python chip_smoke.py --chips 4  # only the sharded session axis, 4 chips

Run it from the repository root; it puts ``src`` on ``sys.path`` itself.
With no TPU it exits non-zero before any phase and prints no result: there
is no CPU fallback. Everything runs in this one process (a chip belongs to
one process at a time). Phase and compile seconds printed here are smoke
times of a single cold run, not benchmark metrics. The last line of
standard output is the result object:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# The service's real widths: the 8-knob magpie8 space, hidden (64, 64),
# 96 updates of batch 16 per step, replay capacity 64, chunk width 256.
CHUNK = 256
SESSIONS = 1024
ROUNDS = 3
ROUND_STEPS = 10
WORKLOADS = ("file_server", "video_server", "seq_write", "seq_read",
             "random_rw")
OBJECTIVES = ({"throughput": 1.0}, {"throughput": 1.0, "iops": 1.0})
# Pallas learner vs XLA learner (the one ``auto`` runs) after one 96-update
# call from a fresh init.
# Both run every matmul at Precision.HIGHEST; what remains is f32 rounding
# order and transcendental implementations. Over 96 updates Adam turns a
# rounding-level sign change of a near-zero gradient into a step of size
# lr, so a few learners drift apart chaotically: on the CPU the kernel (in
# interpret mode) and the XLA learner differ by up to 9.4e-3 at 256
# sessions, yet at most 0.42% of any leaf's elements lie outside 1e-4 +
# 1e-4|xla| and every leaf's 99th-percentile gap is below 2.1e-5. So every
# float leaf must hold 99% of its elements inside that band. A wrong kernel
# (a wrong Adam bias correction, bf16 matmul operands) puts more than 99%
# of some leaf's elements outside it.
LEARNER_ATOL = 1e-4
LEARNER_RTOL = 1e-4
LEARNER_MAX_OUTSIDE = 0.01
# the sharded grid: 5 workloads x 2 objectives x 26 seeds
SHARD_SEEDS = 26
SHARD_STEPS = 10
# float32 ulps allowed between the sharded and the one-chip objectives and
# rewards (tests/test_sharded_fleet.py's bound across program widths)
SHARD_MAX_ULP = 32


_COMPILES = {"seconds": 0.0, "count": 0, "cache_hits": 0}


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["seconds"] += duration
        _COMPILES["count"] += 1


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILES["cache_hits"] += 1


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def _check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def _kernels(mode: str):
    """Run with ``REPRO_KERNELS=mode`` (the learner's documented switch)."""
    old = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = mode
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("REPRO_KERNELS", None)
        else:
            os.environ["REPRO_KERNELS"] = old


def _timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _float_gap(a_tree, b_tree, atol, rtol):
    """(int leaves equal?, max |a - b|, the largest fraction of any float
    leaf's elements outside atol + rtol|b| and that leaf's path, the
    largest 99th-percentile |a - b| of any float leaf)."""
    import jax
    import numpy as np
    ints_equal, worst, outside, where, q99 = True, 0.0, 0.0, "", 0.0
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(b_tree)[0],
                            jax.tree_util.tree_leaves(a_tree)):
        a, b = np.asarray(a), np.asarray(b)
        if not np.issubdtype(a.dtype, np.floating):
            ints_equal &= bool(np.array_equal(a, b))
            continue
        d = np.abs(a.astype(np.float64) - b.astype(np.float64))
        worst = max(worst, float(d.max()))
        share = float(np.mean(d > atol + rtol * np.abs(b)))
        if share >= outside:
            outside, where = share, jax.tree_util.keystr(path)
        q99 = max(q99, float(np.quantile(d, 0.99)))
    return ints_equal, worst, outside, where, q99


def phase_learner() -> None:
    """One fleet learn call on a chunk of magpie8 sessions, through the
    learner ``auto`` resolves to (the XLA scan) and through the Pallas
    kernel, each timed cold and warm."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.ddpg import DDPGConfig, fleet_init, fleet_learn_scan
    from repro.envs import LustreSimV2
    from repro.kernels import ops

    env = LustreSimV2("seq_write", seed=0).to_model_env()
    cfg = DDPGConfig.for_env(env)
    n, cap, u = CHUNK, 64, cfg.updates_per_step
    states, (atx, ctx) = fleet_init(
        jnp.stack([jax.random.PRNGKey(s) for s in range(n)]), cfg)
    rng = np.random.default_rng(0)
    k, m = cfg.state_dim, cfg.action_dim
    data = (rng.random((n, cap, k), np.float32),
            rng.random((n, cap, m), np.float32),
            (0.3 * rng.standard_normal((n, cap))).astype(np.float32),
            rng.random((n, cap, k), np.float32))
    sizes = jnp.full((n,), cap, jnp.int32)
    keys = jnp.stack([jax.random.PRNGKey(s + 7) for s in range(n)])

    def learn():
        return fleet_learn_scan(states, data, sizes, keys, cfg, atx, ctx, u)

    with _kernels("auto"):
        mode = ops.ddpg_kernel_mode() or "xla"
        xla_out, xla_s = _timed(learn)
        _, xla_warm_s = _timed(learn)
    with _kernels("pallas"):
        pallas_out, pallas_s = _timed(learn)
        _, pallas_warm_s = _timed(learn)
    _log(f"learner: resolved auto mode = {mode}; {n} sessions x {u} updates "
         f"x batch {cfg.batch_size}, state {k}, action {m}")
    _log(f"learner: auto (xla) first call (compile + run) {xla_s:.3f} s, "
         f"second call {xla_warm_s:.4f} s; pallas first call "
         f"{pallas_s:.3f} s, second call {pallas_warm_s:.4f} s")
    _check(mode == "xla",
           f"auto resolved the learner to {mode!r}, not the XLA learner")
    p_state, x_state = pallas_out[0], xla_out[0]
    for name, st in (("pallas", p_state), ("xla", x_state)):
        _check(int(np.min(st.step)) == int(np.max(st.step)) == u,
               f"{name} learner step is not {u}")
        _check(int(np.min(st.actor_opt[0].count)) == u
               and int(np.min(st.critic_opt[0].count)) == u,
               f"{name} Adam counts did not advance by {u}")
    ints_equal, worst, outside, where, q99 = _float_gap(
        pallas_out, xla_out, LEARNER_ATOL, LEARNER_RTOL)
    finite = all(bool(np.all(np.isfinite(np.asarray(x))))
                 for x in jax.tree_util.tree_leaves((pallas_out, xla_out)))
    _log(f"learner: Adam counts and step equal: {ints_equal}; over params, "
         f"moments and metrics: max |pallas - xla| {worst:.3e}, largest "
         f"per-leaf 99th percentile {q99:.3e}, largest per-leaf share "
         f"outside {LEARNER_ATOL:g} + {LEARNER_RTOL:g}*|xla| "
         f"{outside * 100:.3f}% at {where} (limit "
         f"{LEARNER_MAX_OUTSIDE * 100:g}%); finite: {finite}")
    _check(ints_equal, "Adam counts / step differ between pallas and xla")
    _check(finite, "a learner produced non-finite values")
    _check(outside <= LEARNER_MAX_OUTSIDE,
           f"{outside * 100:.3f}% of a leaf's elements differ between the "
           f"pallas and xla learners by more than the tolerance")


def phase_service() -> None:
    """``FleetService(chunk=256, env_cls=LustreSimV2)`` with 1024 sessions:
    a few ``advance`` rounds, then a sample of sessions leaves."""
    import math

    import numpy as np

    from repro.core.service import FleetService
    from repro.envs import LustreSimV2

    t0 = time.perf_counter()
    svc = FleetService(chunk=CHUNK, env_cls=LustreSimV2)
    sids, seed = [], 0
    while len(sids) < SESSIONS:
        for workload in WORKLOADS:
            for weights in OBJECTIVES:
                if len(sids) < SESSIONS:
                    sids.append((svc.request_join(workload, weights, seed),
                                 workload))
        seed += 1
    _log(f"service: {len(sids)} sessions joined in "
         f"{time.perf_counter() - t0:.2f} s (5 workloads x 2 objectives x "
         f"seeds 0..{seed - 1})")
    cfg = svc.cfg
    _check((cfg.state_dim, cfg.action_dim, tuple(cfg.hidden),
            cfg.updates_per_step, cfg.batch_size, svc.buffer_capacity)
           == (12, 8, (64, 64), 96, 16, 64),
           f"the service is not at magpie8 widths: {cfg}")

    for r in range(ROUNDS):
        t0 = time.perf_counter()
        order = svc.advance(ROUND_STEPS)
        dt = time.perf_counter() - t0
        st = svc.last_stats
        _log(f"service: round {r} advance({ROUND_STEPS}) {dt:.2f} s"
             f"{' (includes compile)' if r == 0 else ''}; "
             f"{st['sessions']} sessions in {st['num_chunks']} chunks of "
             f"{st['chunk']}; executables {st['executable_cache_size']}")
        _check(len(order) == SESSIONS and st["num_chunks"]
               == math.ceil(SESSIONS / CHUNK), "not every session advanced")
        _check(not st.get("quarantined") and not st.get(
            "supervisor", {}).get("failed_chunks"),
            "a chunk failed or was quarantined")

    steps = ROUNDS * ROUND_STEPS
    counts = {(int(s.ddpg.actor_opt[0].count), int(s.ddpg.critic_opt[0].count),
               int(s.ddpg.step)) for s in svc._sessions.values()}
    want = cfg.updates_per_step * steps
    _log(f"service: learner (actor count, critic count, step) over all "
         f"sessions: {sorted(counts)}; expected {want}")
    _check(counts == {(want, want, want)},
           f"Adam counts did not advance by 96 x {steps}")

    leaving = sids[::7]  # 7 is coprime to the 10 workload x objective cells
    t0 = time.perf_counter()
    for sid, _ in leaving:
        svc.request_leave(sid)
    svc.advance(0)
    _log(f"service: {len(leaving)} sessions left in "
         f"{time.perf_counter() - t0:.2f} s; {len(svc.active)} still active")
    results = [(svc.result(sid), wl) for sid, wl in leaving]
    for res, _ in results:
        vals = [res.best_objective, *res.best_metrics.values(),
                *res.default_metrics.values()]
        vals += [h.objective for h in res.history]
        _check(all(math.isfinite(float(v)) for v in vals),
               "a TuningResult holds a non-finite value")
        _check(len(res.history) == steps, "a result is missing steps")
    gains = np.array([res.best_metrics["throughput"]
                      / res.default_metrics["throughput"] - 1.0
                      for res, wl in results if wl == "seq_write"])
    _check(gains.size > 0, "no seq_write session left")
    _log(f"service: seq_write throughput gain over default, "
         f"{gains.size} sessions: min {gains.min() * 100:.1f}% median "
         f"{np.median(gains) * 100:.1f}% max {gains.max() * 100:.1f}%")
    _check(bool(np.all(gains > 0)),
           "a seq_write session did not gain over its default config")


def phase_sharded(devices) -> None:
    """A magpie8 grid sharded over 4 chips against the same grid on one."""
    import jax
    import numpy as np

    from repro.core import FleetTuner
    from repro.core.episode import _compiled_episode, chunk_operands
    from repro.envs import LustreSimV2

    n = len(WORKLOADS) * len(OBJECTIVES) * SHARD_SEEDS
    per_dev = CHUNK // len(devices)

    def run(devs, chunk):
        fleet = FleetTuner.from_grid(
            list(WORKLOADS), list(OBJECTIVES), list(range(SHARD_SEEDS)),
            env_cls=LustreSimV2, engine="scan", devices=devs, chunk=chunk)
        t0 = time.perf_counter()
        res = fleet.run(SHARD_STEPS)
        return fleet, res, time.perf_counter() - t0

    # the same per-device program width on both sides: chunk 256 over four
    # chips is 64 sessions per chip, the one-chip run streams chunks of 64
    sharded_fleet, sharded, s_sec = run(list(devices), CHUNK)
    _, single, o_sec = run([devices[0]], per_dev)
    _log(f"sharded: {n} magpie8 sessions x {SHARD_STEPS} steps; 4 chips "
         f"(chunk {CHUNK}) {s_sec:.2f} s, 1 chip (chunk {per_dev}) "
         f"{o_sec:.2f} s, each including compile")

    def ulps(a, b):
        ia, ib = np.float32([a, b]).view(np.int32).astype(np.int64)
        return int(abs(ia - ib))

    decisions, float_gap, max_ulp = True, 0.0, 0
    _check(sharded.labels == single.labels, "grid labels differ")
    for ra, rb in zip(sharded.results, single.results):
        decisions &= ra.best_config == rb.best_config
        decisions &= len(ra.history) == len(rb.history) == SHARD_STEPS
        for ha, hb in zip(ra.history, rb.history):
            decisions &= ha.config == hb.config
            decisions &= ha.restart_seconds == hb.restart_seconds
            float_gap = max(float_gap, abs(ha.objective - hb.objective),
                            abs(ha.reward - hb.reward))
            max_ulp = max(max_ulp, ulps(ha.objective, hb.objective),
                          ulps(ha.reward, hb.reward))
    _log(f"sharded: decisions (configs, restarts, best config) exact: "
         f"{decisions}; max |objective or reward difference| = "
         f"{float_gap!r} ({max_ulp} float32 ulp, limit {SHARD_MAX_ULP})")

    # where the chunk's outputs live: the sharded run's chunk program (same
    # shapes, so no new compile), called directly
    agent = sharded_fleet.agent
    env = sharded_fleet.envs[0]
    fn = _compiled_episode(env.model.step_fn, env.param_space, agent.cfg,
                           agent._actor_tx, agent._critic_tx, True,
                           agent.cfg.updates_per_step, fleet=True,
                           devices=tuple(devices))
    carry, trace = fn(*chunk_operands(env, agent, SHARD_STEPS, CHUNK))
    spread = {d for x in jax.tree_util.tree_leaves((carry, trace))
              for d in x.sharding.device_set}
    _log(f"sharded: chunk outputs live on {len(spread)} devices: "
         f"{sorted(d.id for d in spread)}; trace.rewards sharding "
         f"{trace.rewards.sharding}")
    _check(decisions, "decisions differ between 4 chips and 1 chip")
    _check(max_ulp <= SHARD_MAX_ULP,
           f"objectives/rewards differ by {max_ulp} ulp across device counts")
    _check(spread == set(devices), "the chunk's outputs are not spread over "
           "the four chips")
    _check(all(np.isfinite(r.best_objective) for r in sharded.results),
           "a sharded result is non-finite")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: learner + service phases on one chip; 4: "
                        "only the sharded session axis over four chips")
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    _log(f"device: {device['kind']} x {device['count']}")

    from repro.core.episode import enable_persistent_compilation_cache
    _log(f"compile cache: {enable_persistent_compilation_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    phases = ([("sharded", lambda: phase_sharded(devices[:4]))]
              if args.chips == 4 else
              [("learner", phase_learner), ("service", phase_service)])
    t_all = time.perf_counter()
    for name, phase in phases:
        t0 = time.perf_counter()
        before = dict(_COMPILES)
        try:
            with _kernels("auto"):
                phase()
        except SmokeFailure as err:
            print(f"chip_smoke: phase {name} failed: {err}", file=sys.stderr)
            return 1
        _log(f"phase {name}: {time.perf_counter() - t0:.2f} s, of which "
             f"backend compile {_COMPILES['seconds'] - before['seconds']:.2f}"
             f" s in {_COMPILES['count'] - before['count']} compiles "
             f"({_COMPILES['cache_hits'] - before['cache_hits']} persistent "
             f"cache hits)")
    _log(f"all phases: {time.perf_counter() - t_all:.2f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
