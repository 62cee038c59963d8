"""Self-healing fleet runtime (core/resilience.py + supervised streaming).

Load-bearing properties:
  * resilience-off is bitwise-NEUTRAL: ``resilience=None`` keys (and IS, by
    executable identity) the exact pre-resilience episode program — single
    scan tuner, chunked fleet and service reproduce the default-constructed
    run maxulp=0;
  * a chaos-injected NaN divergence is caught in-graph: the poisoned sample
    never enters the replay FIFO, the learner resets to the last-good
    snapshot within ``snapshot_every`` steps, and past ``max_resets`` the
    session degrades cleanly to a frozen incumbent (sticky, never resets);
  * the ``health_decision`` state machine holds its invariants under
    arbitrary fault sequences (hypothesis + fixed-seed fallback lanes,
    mirroring tests/test_episode): resets never exceed ``max_resets``,
    degraded is sticky, a degraded step never resets;
  * host supervision is bitwise invisible on success: a transient staging
    exception is retried to a result bitwise-equal to a fault-free run, a
    stalled chunk only trips the watchdog counter, and a permanently dead
    chunk quarantines its sessions through the leave path while every
    survivor stays bitwise vs an uninjected fleet;
  * trace-derived health counters equal the in-graph totals, and a
    resilient service checkpoint resumes bit-identically.
"""

import numpy as np
import pytest

from repro.core import (
    ChunkSupervisor,
    DDPGConfig,
    DeploymentPolicy,
    FleetService,
    FleetTuner,
    MagpieAgent,
    ResiliencePolicy,
    Scalarizer,
    SharingConfig,
    Tuner,
    health_decision,
    normalize_resilience,
    normalize_supervisor,
)
from repro.core.resilience import (
    EVENT_DEGRADED,
    EVENT_NONFINITE,
    EVENT_RESET,
    empty_health_counters,
    health_counters,
    merge_health_counters,
)
from repro.envs import (
    ChaosConfig,
    FaultInjectedModel,
    LustreSimEnv,
    LustreSimV2,
    ModelEnv,
    nan_poison,
)

from tests.test_episode import _assert_bitwise_equal_runs

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # CI installs it (requirements.txt); skip locally without
    HAVE_HYPOTHESIS = False


def _tuner(env_cls=LustreSimEnv, resilience=None, seed=3, updates=4,
           warmup=3, workload="seq_write", env=None, **kw):
    env = env or env_cls(workload, seed=seed).to_model_env()
    scal = Scalarizer(weights={"throughput": 1.0}, specs=env.metric_specs)
    agent = MagpieAgent(DDPGConfig.for_env(env, updates_per_step=updates),
                        seed=seed, warmup_steps=warmup)
    return Tuner(env, scal, agent, engine="scan", eval_runs=1,
                 resilience=resilience, **kw)


def _fleet(resilience=None, supervisor=None, chaos=None, chunk=2,
           seeds=(0, 1, 2), updates=4, warmup=3, env_factory=None,
           sharing=None):
    env = (env_factory("seq_write", 0) if env_factory
           else LustreSimEnv("seq_write"))
    cfg = DDPGConfig.for_env(env, updates_per_step=updates)
    return FleetTuner.from_grid(
        ["seq_write"], [{"throughput": 1.0}], list(seeds),
        env_cls=None if env_factory else LustreSimEnv,
        env_factory=env_factory, engine="scan", ddpg_config=cfg, eval_runs=1,
        warmup_steps=warmup, chunk=chunk, resilience=resilience,
        supervisor=supervisor, chaos=chaos, sharing=sharing)


def _faulted_tuner(fault_specs, resilience, seed=0, env_cls=LustreSimV2):
    base = env_cls("seq_write", seed=seed).as_model()
    env = ModelEnv(FaultInjectedModel(base, fault_specs), seed=seed)
    return _tuner(resilience=resilience, seed=seed, env=env)


def _faulted_fleet_factory(fault_specs):
    """Every session wraps its model in ONE shared fault schedule, so the
    fleet keeps a single step_fn identity (one compiled program)."""
    specs = tuple(fault_specs)

    def env_factory(workload, seed):
        base = LustreSimV2(workload, seed=seed).as_model()
        return ModelEnv(FaultInjectedModel(base, specs), seed=seed)

    return env_factory


# ---------------------------------------------------------------------------
# Off path: resilience=None is the pre-resilience engine, bit for bit
# ---------------------------------------------------------------------------

def test_resilience_none_shares_the_plain_program_object():
    """``resilience=None`` is not merely equivalent — it keys the SAME
    cached episode executable as not mentioning resilience at all, for both
    the single and the fleet build, so the off path cannot drift from the
    plain engine by construction."""
    from repro.core.episode import _compiled_episode
    env = LustreSimEnv("seq_write", seed=0).to_model_env()
    cfg = DDPGConfig.for_env(env)
    from repro.core.ddpg import fleet_init
    import jax
    import jax.numpy as jnp
    _, (atx, ctx) = fleet_init(jnp.stack([jax.random.PRNGKey(0)]), cfg)
    for fleet in (False, True):
        default = _compiled_episode(env.model.step_fn, env.param_space, cfg,
                                    atx, ctx, True, cfg.updates_per_step,
                                    fleet=fleet, devices=None)
        explicit = _compiled_episode(env.model.step_fn, env.param_space, cfg,
                                     atx, ctx, True, cfg.updates_per_step,
                                     fleet=fleet, devices=None,
                                     resilience=None)
        assert default is explicit


def test_nonfinite_check_false_normalizes_to_the_off_program():
    """A fully-off policy collapses to the SAME canonical None the cache
    keys on — there is exactly one off value."""
    assert normalize_resilience(None) is None
    off = ResiliencePolicy(nonfinite_check=False, max_resets=9)
    assert normalize_resilience(off) is None
    assert normalize_supervisor(None) is None
    with pytest.raises(ValueError, match="max_resets"):
        normalize_resilience(ResiliencePolicy(max_resets=-1))
    with pytest.raises(ValueError, match="snapshot_every"):
        normalize_resilience(ResiliencePolicy(snapshot_every=0))
    with pytest.raises(ValueError, match="degrade_after"):
        normalize_resilience(ResiliencePolicy(degrade_after=0))
    with pytest.raises(ValueError, match="on_failure"):
        normalize_supervisor(ChunkSupervisor(on_failure="crash"))


def test_resilience_off_is_bitwise_neutral_single_tuner():
    ref = _tuner(seed=5).run(8)
    off = _tuner(seed=5, resilience=None).run(8)
    _assert_bitwise_equal_runs(ref, off, maxulp=0)
    assert off.health_stats is None


def test_resilience_off_is_bitwise_neutral_chunked_fleet():
    ref, off = _fleet(), _fleet(resilience=None)
    for steps in (4, 3):  # progressive runs stay aligned too
        for a, b in zip(ref.run(steps).results, off.run(steps).results):
            _assert_bitwise_equal_runs(a, b, maxulp=0)
            assert b.health_stats is None


def test_resilience_off_is_bitwise_neutral_service(tmp_path):
    def make(**kw):
        svc = FleetService(chunk=2, warmup_steps=3,
                           checkpoint_dir=str(tmp_path), **kw)
        svc.request_join("seq_write", {"throughput": 1.0}, 0)
        svc.request_join("seq_write", {"throughput": 1.0}, 1)
        return svc

    ref, off = make(), make(resilience=None, supervisor=None)
    for steps in (4, 2):
        ref.advance(steps), off.advance(steps)
        for sid in (0, 1):
            a, b = ref._sessions[sid], off._sessions[sid]
            assert [r.config for r in a.history] == \
                [r.config for r in b.history]
            assert [r.objective for r in a.history] == \
                [r.objective for r in b.history]
            assert [r.reward for r in a.history] == \
                [r.reward for r in b.history]
    assert "supervisor" not in ref.last_stats
    assert "quarantined" not in ref.last_stats


def test_resilient_run_without_faults_matches_plain_single_tuner():
    """On a healthy run the resilient body is numerically the plain body:
    same FIFO writes, same learn inputs, zero health events."""
    ref = _tuner(seed=5).run(8)
    t = _tuner(seed=5, resilience=ResiliencePolicy())
    res = t.run(8)
    _assert_bitwise_equal_runs(ref, res, maxulp=0)
    assert not np.any(t.health_events)
    s = res.health_stats
    assert s["resets_total"] == 0 and s["nonfinite_total"] == 0
    assert not s["degraded"]
    assert s["policy"]["max_resets"] == ResiliencePolicy().max_resets


def test_resilient_fleet_without_faults_matches_plain_fleet():
    ref, res = _fleet(), _fleet(resilience=ResiliencePolicy())
    for a, b in zip(ref.run(6).results, res.run(6).results):
        _assert_bitwise_equal_runs(a, b, maxulp=0)
        assert b.health_stats["resets_total"] == 0
    assert not np.any(res.health_events)


# ---------------------------------------------------------------------------
# In-graph recovery: NaN divergence -> snapshot reset or clean degrade
# ---------------------------------------------------------------------------

def test_nan_divergence_recovers_within_the_snapshot_window():
    start, dur = 4, 2
    t = _faulted_tuner([nan_poison("throughput", start=start, duration=dur)],
                       ResiliencePolicy(max_resets=4, snapshot_every=1))
    res = t.run(12)
    ev = t.health_events
    # the poison is observed (raw in the trace) and answered by a reset on
    # each corrupted step — the learner never keeps a poisoned sample
    for k in range(start, start + dur):
        assert ev[k] & EVENT_NONFINITE
        assert ev[k] & EVENT_RESET
        assert np.isnan(res.history[k].metrics["throughput"])
    # recovery within snapshot_every steps of the fault clearing: the next
    # step is healthy and every post-fault objective is finite again
    after = ev[start + dur:]
    assert not np.any(after & EVENT_NONFINITE)
    assert not np.any(after & EVENT_DEGRADED)
    post = [h.objective for h in res.history[start + dur:]]
    assert np.all(np.isfinite(post))
    s = res.health_stats
    assert s["resets_total"] == dur and s["nonfinite_total"] == dur
    assert not s["degraded"]


def test_exhausted_reset_budget_degrades_cleanly_and_stays_frozen():
    start = 3
    t = _faulted_tuner([nan_poison("throughput", start=start, duration=50)],
                       ResiliencePolicy(max_resets=0, snapshot_every=1))
    res = t.run(10)
    ev = t.health_events
    assert not np.any(ev[:start])
    # max_resets=0: the FIRST divergence degrades; no reset is ever spent
    # and the flag is sticky for the rest of the run
    assert not np.any(ev & EVENT_RESET)
    assert np.all(ev[start:] & EVENT_DEGRADED)
    s = res.health_stats
    assert s["degraded"] and s["resets_total"] == 0
    assert s["degraded_steps"] == 10 - start
    assert s["nonfinite_total"] == 10 - start


def test_degrade_after_caps_total_nonfinite_detections():
    """``degrade_after`` degrades a flapping session even with resets left:
    two separated poison bursts spend resets, the third crosses the total
    non-finite cap."""
    pol = ResiliencePolicy(max_resets=100, snapshot_every=1, degrade_after=3)
    t = _faulted_tuner([nan_poison("throughput", start=2, duration=1),
                        nan_poison("throughput", start=5, duration=1),
                        nan_poison("throughput", start=8, duration=1)], pol)
    res = t.run(12)
    ev = t.health_events
    assert ev[2] & EVENT_RESET and ev[5] & EVENT_RESET
    assert ev[8] & EVENT_DEGRADED and not (ev[8] & EVENT_RESET)
    assert np.all(ev[8:] & EVENT_DEGRADED)
    assert res.health_stats["resets_total"] == 2


def test_trace_counters_equal_in_graph_totals():
    t = _faulted_tuner([nan_poison("throughput", start=4, duration=2)],
                       ResiliencePolicy(max_resets=4))
    res = t.run(10)
    s = res.health_stats
    got = health_counters(t.health_events)
    assert got["steps"] == 10
    assert got["resets"] == s["resets_total"]
    assert got["nonfinite"] == s["nonfinite_total"]
    assert s["degraded_steps"] == got["degraded_steps"] == 0


def test_merge_health_counters_and_empty_counters():
    a = health_counters(np.array(
        [0, EVENT_NONFINITE | EVENT_RESET, EVENT_NONFINITE | EVENT_DEGRADED,
         EVENT_DEGRADED], np.uint8))
    assert a["steps"] == 4 and a["nonfinite"] == 2
    assert a["resets"] == 1 and a["degraded_steps"] == 2
    merged = merge_health_counters(a, empty_health_counters())
    assert merged == a
    assert empty_health_counters()["resets"] == 0


# ---------------------------------------------------------------------------
# health_decision invariants (hypothesis + fixed-seed fallback)
# ---------------------------------------------------------------------------

def _check_health_invariants(bads, max_resets, degrade_after):
    """Fold an arbitrary fault sequence through the state machine: resets
    never exceed ``max_resets``, degraded is sticky, a degraded step never
    resets, non-finite detections count every bad step exactly once."""
    pol = ResiliencePolicy(max_resets=max_resets,
                           degrade_after=degrade_after)
    resets, nf = np.int32(0), np.int32(0)
    degraded = np.bool_(False)
    for b in bads:
        b = np.bool_(b)
        do_reset, new_deg, new_resets, new_nf = health_decision(
            b, resets, nf, degraded, pol)
        assert int(new_resets) <= max_resets
        assert bool(new_deg) or not bool(degraded)   # sticky
        assert not (bool(do_reset) and bool(new_deg))  # degraded: no reset
        assert int(new_nf) == int(nf) + int(bool(b))
        assert int(new_resets) - int(resets) == int(bool(do_reset))
        resets, nf, degraded = new_resets, new_nf, new_deg


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(bads=st.lists(st.booleans(), max_size=40),
           max_resets=st.integers(0, 6),
           degrade_after=st.none() | st.integers(1, 10))
    def test_health_decision_invariants(bads, max_resets, degrade_after):
        _check_health_invariants(bads, max_resets, degrade_after)
else:
    @pytest.mark.parametrize("bads,max_resets,degrade_after", [
        ([True] * 10, 3, None),
        ([False, True, False, True, True, False], 1, None),
        ([True, False] * 8, 2, 3),
        ([False] * 5, 0, 1),
        ([True] * 4, 0, None)])
    def test_health_decision_invariants(bads, max_resets, degrade_after):
        """Fixed-seed fallback when hypothesis is unavailable."""
        _check_health_invariants(bads, max_resets, degrade_after)


# ---------------------------------------------------------------------------
# Host supervisor: retries are bitwise invisible, stalls only trip counters
# ---------------------------------------------------------------------------

def test_supervised_stream_without_faults_is_bitwise_invisible():
    """Supervision is pure scheduling: the serial supervised stream matches
    the unsupervised double-buffered one maxulp=0."""
    ref = _fleet()
    sup = _fleet(supervisor=ChunkSupervisor(max_retries=2,
                                            backoff_seconds=0.0))
    for a, b in zip(ref.run(6).results, sup.run(6).results):
        _assert_bitwise_equal_runs(a, b, maxulp=0)


def test_transient_chunk_failure_is_retried_to_a_bitwise_equal_result():
    from repro.core.episode import last_fleet_run_stats
    chaos = ChaosConfig(fail_chunks=((0, 1),))  # chunk 0: 1 failure, then ok
    ref = _fleet()
    faulted = _fleet(supervisor=ChunkSupervisor(max_retries=2,
                                                backoff_seconds=0.0),
                     chaos=chaos.host())
    rr, rf = ref.run(6), faulted.run(6)
    stats = last_fleet_run_stats()["supervisor"]
    assert stats["retries"] == 1 and stats["failed_chunks"] == []
    for a, b in zip(rr.results, rf.results):
        _assert_bitwise_equal_runs(a, b, maxulp=0)


def test_stalled_chunk_trips_the_watchdog_without_touching_results():
    from repro.core.episode import last_fleet_run_stats
    chaos = ChaosConfig(stall_chunks=((0, 0.05),))
    ref = _fleet()
    stalled = _fleet(supervisor=ChunkSupervisor(backoff_seconds=0.0,
                                                watchdog_seconds=0.02),
                     chaos=chaos.host())
    rr, rs = ref.run(5), stalled.run(5)
    stats = last_fleet_run_stats()["supervisor"]
    assert stats["watchdog_trips"] >= 1
    assert stats["failed_chunks"] == []
    assert len(stats["chunk_seconds"]) == 2  # 3 sessions / chunk=2
    for a, b in zip(rr.results, rs.results):
        _assert_bitwise_equal_runs(a, b, maxulp=0)


def test_exhausted_retries_raise_chunk_failure_by_default():
    from repro.core.resilience import ChunkFailure
    chaos = ChaosConfig(fail_chunks=((0, 99),))  # never clears
    faulted = _fleet(supervisor=ChunkSupervisor(max_retries=1,
                                                backoff_seconds=0.0),
                     chaos=chaos.host())
    with pytest.raises(ChunkFailure, match="chunk 0"):
        faulted.run(4)


def test_chaos_without_a_supervisor_is_refused():
    from repro.core.episode import stream_chunks
    with pytest.raises(ValueError, match="ChunkSupervisor"):
        stream_chunks(lambda args: args, lambda ci: ci,
                      lambda ci, out: None, 2, chaos=object())


# ---------------------------------------------------------------------------
# Service quarantine: a dead chunk leaves, survivors stay bitwise
# ---------------------------------------------------------------------------

def _service(tmp_path, n=4, **kw):
    svc = FleetService(chunk=2, warmup_steps=3,
                       checkpoint_dir=str(tmp_path), **kw)
    sids = [svc.request_join("seq_write", {"throughput": 1.0}, seed)
            for seed in range(n)]
    return svc, sids


def test_dead_chunk_quarantines_sessions_and_survivors_stay_bitwise(
        tmp_path):
    ref, _ = _service(tmp_path / "ref")
    chaos = ChaosConfig(fail_chunks=((1, 99),))  # chunk 1 never stages
    # on_failure="skip": the operator asked the service to quarantine a
    # dead chunk's sessions rather than raise
    svc, sids = _service(
        tmp_path / "chaotic",
        supervisor=ChunkSupervisor(max_retries=1, backoff_seconds=0.0,
                                   on_failure="skip"),
        chaos=chaos.host())
    ref.advance(4)
    svc.advance(4)
    assert svc.last_stats["supervisor"]["failed_chunks"] == [1]
    assert svc.last_stats["quarantined"] == sids[2:]
    # survivors (chunk 0) are bitwise the uninjected fleet's sessions
    for sid in sids[:2]:
        a, b = ref._sessions[sid], svc._sessions[sid]
        assert [r.config for r in a.history] == \
            [r.config for r in b.history]
        assert [r.objective for r in a.history] == \
            [r.objective for r in b.history]
    # the quarantined sessions leave at the next boundary with their
    # pre-episode state (the failed chunk never drained: no history)
    svc.advance(0)
    for sid in sids[2:]:
        assert sid not in svc._sessions
        assert svc.result(sid).history == []
    for sid in sids[:2]:
        assert sid in svc._sessions


def test_resilient_service_checkpoint_restore_resumes_bit_identically(
        tmp_path):
    import jax
    pol = ResiliencePolicy(max_resets=2, snapshot_every=2)
    sup = ChunkSupervisor(max_retries=1, backoff_seconds=0.0)
    svc = FleetService(chunk=2, warmup_steps=3, resilience=pol,
                       supervisor=sup, checkpoint_dir=str(tmp_path))
    a = svc.request_join("seq_write", {"throughput": 1.0}, 0)
    b = svc.request_join("random_rw", {"iops": 1.0}, 1)
    svc.advance(5)
    svc.checkpoint()
    svc.advance(4)
    want = {sid: svc.health_stats(sid) for sid in (a, b)}
    want_hist = {sid: [r.config for r in svc._sessions[sid].history]
                 for sid in (a, b)}
    want_snap = {sid: jax.tree_util.tree_leaves(
        svc._sessions[sid].health.snapshot) for sid in (a, b)}

    svc2 = FleetService.restore(str(tmp_path))
    assert svc2.resilience == normalize_resilience(pol)
    assert svc2.supervisor == sup
    svc2.advance(4)
    for sid in (a, b):
        assert svc2.health_stats(sid) == want[sid]
        assert [r.config for r in svc2._sessions[sid].history] == \
            want_hist[sid]
        got = jax.tree_util.tree_leaves(svc2._sessions[sid].health.snapshot)
        for x, y in zip(want_snap[sid], got):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # departure surfaces the health record on the TuningResult
    svc2.request_leave(a)
    svc2.advance(0)
    res = svc2.result(a)
    assert res.health_stats["policy"]["max_resets"] == 2
    assert res.health_stats["steps"] == 9  # 5 checkpointed + 4 resumed


# ---------------------------------------------------------------------------
# Composition: sharing masks corrupted contributions; guardrails refuse
# ---------------------------------------------------------------------------

def test_shared_cell_masks_poisoned_contributions():
    """With shared replay, a poisoned step's transitions are DROPPED from
    the cell's merged window (the contribution mask), so the window is
    exactly the fault-free window minus the poisoned writes — and every
    member recovers."""
    sharing = SharingConfig(shared_replay=True)
    pol = ResiliencePolicy(max_resets=4, snapshot_every=1)
    clean = _fleet(sharing=sharing, resilience=pol)
    poisoned = _fleet(
        sharing=sharing, resilience=pol,
        env_factory=_faulted_fleet_factory(
            [nan_poison("throughput", start=3, duration=1)]))
    clean.run(8)
    poisoned.run(8)
    ev = poisoned.health_events
    assert np.all(ev[:, 3] & EVENT_NONFINITE)  # every member saw the poison
    assert not np.any(ev[:, 4:] & EVENT_DEGRADED)  # ...and all recovered
    _, _, clean_size = clean.agent.buffer.grouped_storage()
    _, _, got_size = poisoned.agent.buffer.grouped_storage()
    # one poisoned step x 3 members never reached the merged window
    assert np.all(clean_size - got_size == 3)


def test_resilience_refuses_guardrail_composition():
    env = LustreSimEnv("seq_write", seed=0).to_model_env()
    scal = Scalarizer(weights={"throughput": 1.0}, specs=env.metric_specs)
    with pytest.raises(ValueError, match="does not compose"):
        Tuner(env, scal, engine="scan", policy=DeploymentPolicy(),
              resilience=ResiliencePolicy())
    with pytest.raises(ValueError, match="does not compose"):
        FleetTuner.from_grid(
            ["seq_write"], [{"throughput": 1.0}], [0], engine="scan",
            env_cls=LustreSimEnv, policy=DeploymentPolicy(),
            resilience=ResiliencePolicy())
    with pytest.raises(ValueError, match="does not compose"):
        FleetService(chunk=2, policy=DeploymentPolicy(),
                     resilience=ResiliencePolicy())


def test_resilience_requires_the_scan_engine():
    env = LustreSimEnv("seq_write", seed=0)
    scal = Scalarizer(weights={"throughput": 1.0}, specs=env.metric_specs)
    with pytest.raises(ValueError, match="scan"):
        Tuner(env, scal, engine="host", resilience=ResiliencePolicy())
    with pytest.raises(ValueError, match="scan"):
        FleetTuner.from_grid(
            ["seq_write"], [{"throughput": 1.0}], [0], engine="host",
            env_cls=LustreSimEnv, resilience=ResiliencePolicy())
    with pytest.raises(ValueError, match="scan"):
        FleetTuner.from_grid(
            ["seq_write"], [{"throughput": 1.0}], [0], engine="host",
            env_cls=LustreSimEnv, supervisor=ChunkSupervisor())
