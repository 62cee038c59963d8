"""Program spans and counters of ``FleetService`` (``core/spans.py``).

A 4-session service (chunk 2: two chunks a round) under churn — one session
leaves and one joins before every ``advance(2)`` — captured with
``jax.profiler.trace``:

  * every ``fleet.*`` span appears, nested as the service nests its phases
    (the stage worker's spans on a thread of their own);
  * each seconds value of ``last_stats["phases"]`` and of the staging
    counters matches the duration of its spans in that round within 5%
    (plus 0.2 ms for the annotation's own entry and exit under a loaded
    machine's scheduling), and each count matches the number of spans;
  * ``counters`` never decrease;
  * the traced service's results are bitwise those of an untraced one.

And the device side: the compiled episode names the phases of a step
(``act``, ``env``, ``reward``, ``store``, ``learn``) in its op metadata, in
the plain and the cell (experience-sharing) step body alike.
"""

import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import FleetService
from repro.core.sharing import SharingConfig
from repro.core.spans import SPAN_PREFIX, counter_key
from repro.envs import LustreSimEnv

from tests.test_service import W, _assert_exact_histories, _cfg

ROUNDS, STEPS, FLEET = 3, 2, 4

# span -> the innermost span that encloses it on the same thread
PARENT = {
    "join": None, "join.env": "join", "join.init": "join",
    "join.evaluate": "join",
    "advance": None, "boundary": "advance", "finalize": "boundary",
    "prepare": "advance", "stream": "advance", "write_back": "advance",
    "stage_wait": "stream", "dispatch": "stream", "drain.wait": "stream",
    "drain.copy": "stream",
}
IDS = {"join": "sid", "finalize": "sid", "advance": "round",
       "stage": "chunk", "stage_wait": "chunk", "dispatch": "chunk",
       "drain.wait": "chunk", "drain.copy": "chunk"}
SERVICE_PHASES = ("advance", "boundary", "finalize", "prepare", "stream",
                  "write_back")
STAGING = {"stage_wait_seconds": ("stage_wait",),
           "drain_block_seconds": ("drain.wait",),
           "drain_seconds": ("drain.wait", "drain.copy")}


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    thread: tuple
    ids: dict

    def encloses(self, other) -> bool:
        return (self is not other and self.thread == other.thread
                and self.start <= other.start and other.end <= self.end)


def _program_spans(log_dir) -> list:
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    s = e.start_ns * 1e-9
                    spans.append(Span(e.name[len(SPAN_PREFIX):], s,
                                      s + e.duration_ns * 1e-9,
                                      (plane.name, li),
                                      {k: v for k, v in e.stats}))
    return spans


def _innermost_parent(span, spans):
    outer = [p for p in spans if p.encloses(span)]
    return max(outer, key=lambda p: p.start, default=None)


def _churn(svc):
    """The churn schedule; returns (counters after every call, the
    ``last_stats`` of every round, final per-session state, results)."""
    snaps, stats, state = [], [], {}
    live = [svc.request_join("seq_write", W, s) for s in range(FLEET)]
    snaps.append(dict(svc.counters))
    for r in range(ROUNDS):
        svc.request_leave(live.pop(0))
        live.append(svc.request_join("seq_write", W, 100 + r))
        snaps.append(dict(svc.counters))
        svc.advance(STEPS)
        stats.append({"phases": dict(svc.last_stats["phases"]),
                      "staging": dict(svc.last_stats["staging"])})
        snaps.append(dict(svc.counters))
    for sid in live:
        state[sid] = jax.tree_util.tree_leaves(svc._sessions[sid].ddpg)
        svc.request_leave(sid)
    svc.advance(0)
    snaps.append(dict(svc.counters))
    results = {sid: svc.result(sid) for sid in range(FLEET + ROUNDS)}
    return snaps, stats, state, results


def _service():
    return FleetService(chunk=2, ddpg_config=_cfg(), warmup_steps=3,
                        eval_runs=1)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace")
    svc = _service()
    with jax.profiler.trace(str(log_dir)):
        run = _churn(svc)
    return run, _program_spans(log_dir)


def test_spans_nest_as_the_service_nests_its_phases(traced):
    _, spans = traced
    names = {s.name for s in spans}
    assert names == set(PARENT) | {"stage"}
    for s in spans:
        if s.name in IDS:
            assert IDS[s.name] in s.ids, s
        if s.name == "stage":
            continue
        parent = _innermost_parent(s, spans)
        assert (parent.name if parent else None) == PARENT[s.name], s
    main = {s.thread for s in spans if s.name == "advance"}
    assert len(main) == 1
    stages = [s for s in spans if s.name == "stage"]
    assert len(stages) == ROUNDS * 2          # two chunks a round
    assert all(s.thread not in main for s in stages)
    streams = [s for s in spans if s.name == "stream"]
    for s in stages:                          # staged inside a stream
        assert any(p.start <= s.start and s.end <= p.end for p in streams)
    joins = [s for s in spans if s.name == "join"]
    assert sorted(s.ids["sid"] for s in joins) == list(range(FLEET + ROUNDS))
    rounds = sorted((s for s in spans if s.name == "advance"),
                    key=lambda s: s.start)
    assert [s.ids["round"] for s in rounds] == list(range(1, ROUNDS + 2))


def test_phases_match_their_spans(traced):
    (_, stats, _, _), spans = traced
    rounds = sorted((s for s in spans if s.name == "advance"),
                    key=lambda s: s.start)[:ROUNDS]
    for rnd, st in zip(rounds, stats):
        inside = [s for s in spans if s.start >= rnd.start
                  and s.end <= rnd.end]

        def seconds(*names):
            return sum(s.end - s.start for s in inside if s.name in names)

        phases = st["phases"]
        for name in SERVICE_PHASES:
            assert phases[counter_key(name)] == pytest.approx(
                seconds(name), rel=0.05, abs=2e-4), name
            assert phases[counter_key(name)] > 0.0, name
        for key, names in STAGING.items():
            assert st["staging"][key] == pytest.approx(
                seconds(*names), rel=0.05, abs=2e-4), key
        assert phases["finalizes"] == sum(s.name == "finalize"
                                          for s in inside) == 1
        assert phases["rounds"] == 1
        assert phases["joins"] == 0
        assert all(phases[counter_key(n)] == 0.0
                   for n in ("join", "join.env", "join.init",
                             "join.evaluate"))
        # the drain's wait is part of the drain, the stage wait is not
        assert (st["staging"]["drain_block_seconds"]
                <= st["staging"]["drain_seconds"])


def test_counters_are_monotone(traced):
    (snaps, _, _, _), _ = traced
    for a, b in zip(snaps, snaps[1:]):
        assert set(a) == set(b)
        assert all(b[k] >= a[k] for k in a), (a, b)
    last = snaps[-1]
    assert last["joins"] == FLEET + ROUNDS
    assert last["finalizes"] == FLEET + ROUNDS
    assert last["rounds"] == ROUNDS + 1
    assert last[counter_key("join.evaluate")] > 0.0


def test_tracing_changes_no_result(traced):
    (_, _, state, results), _ = traced
    _, _, plain_state, plain_results = _churn(_service())
    assert state.keys() == plain_state.keys()
    for sid in state:
        for a, b in zip(state[sid], plain_state[sid]):
            np.testing.assert_array_equal(a, b)
    for sid, got in results.items():
        want = plain_results[sid]
        _assert_exact_histories(want.history, got.history)
        assert got.best_config == want.best_config
        assert got.best_objective == want.best_objective
        assert got.best_metrics == want.best_metrics
        assert got.default_metrics == want.default_metrics


@pytest.mark.parametrize("sharing", [None, SharingConfig(shared_replay=True)],
                         ids=["plain", "cell"])
def test_episode_names_the_phases_of_a_step(sharing, monkeypatch):
    """Lower the chunk program the service runs and read its locations."""
    import repro.core.service as service

    shapes = []
    real = service.stream_chunks

    def capture(call, stage, *args, **kw):
        def recording(operands):
            shapes.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), operands))
            return call(operands)
        return real(recording, stage, *args, **kw)

    monkeypatch.setattr(service, "stream_chunks", capture)
    svc = FleetService(chunk=2, ddpg_config=_cfg(), warmup_steps=3,
                       eval_runs=1, env_cls=LustreSimEnv, sharing=sharing,
                       cell_size=2)
    for s in range(2):
        svc.request_join("seq_write", W, s)
    svc.advance(1)
    text = svc.last_stats["program"].lower(*shapes[0]).as_text(
        debug_info=True)
    for scope in ("act", "env", "reward", "store", "learn"):
        # an op's location: its name stack, which becomes its op_name
        assert re.search(rf'loc\("(?:[^"]*/)?{scope}/', text), scope
