"""The program's own spans and counters: the ``drain_block_ms`` reader, the
reductions of ``bench/program_spans.py`` on a made-up trace and on a trace
recorded on one TPU v5e (``data/churn8_spans*``: an 8-session paper2d fleet,
chunk 8, two rounds of one leave, one join and ``advance(1)``, recorded with
``python3 bench/program_spans.py --workload paper2d_64.churn --seed
7100000001 --sessions 8 --turnover 1 --rounds 2 --save-trace <path>``), and
a rehearsal of that command on the CPU."""

import gzip
import json
import os
import shutil
import time

import pytest

from bench import harness, learner, program_spans, tracing
from bench.program_spans import ProgramSpan
from bench.tracing import Event, Trace
from bench.traffic import RoundRecord

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _records(rounds):
    return [RoundRecord(joined=[], join_latencies=[], **r) for r in rounds]


def _ctx(rounds):
    return harness.MetricContext(rounds=rounds)


def test_drain_block_reader():
    read = harness.metric_reader("drain_block_ms")
    staging = {"stage_wait_seconds": 0.01, "drain_seconds": 0.5}
    rounds = [{"start": 0.0, "end": 1.0, "steps": 1, "session_steps": 8,
               "num_chunks": 1,
               "staging": {**staging, "drain_block_seconds": b}}
              for b in (0.2, 0.4)]
    assert read(_ctx(_records(rounds))) == pytest.approx(300.0)
    # a program that does not split its drain (the older recording)
    with open(os.path.join(DATA, "churn8_rounds.json")) as f:
        assert read(_ctx(_records(json.load(f)))) is None


def _made_up():
    """Device ops at [1, 2) and [5, 6) in a window [0, 10) that one
    benchmark ``advance`` spans; main-thread program spans ``advance``
    [0, 8) holding ``prepare`` [0.5, 1) and ``write_back`` [2, 4); a stage
    on another thread."""
    trace = Trace(window=(0.0, 10.0),
                  devices={"/device:TPU:0": [Event("op", 1.0, 2.0),
                                             Event("op", 5.0, 6.0)]},
                  spans=[Event("advance", 0.0, 10.0)])
    spans = [ProgramSpan("advance", 0.0, 8.0, 1, {"round": 1}),
             ProgramSpan("stage", 0.0, 1.0, 2, {"chunk": 0}),
             ProgramSpan("prepare", 0.5, 1.0, 1, {}),
             ProgramSpan("write_back", 2.0, 4.0, 1, {})]
    return trace, spans


def test_gaps_take_the_innermost_main_thread_span():
    trace, spans = _made_up()
    assert program_spans.idle_intervals(trace) == [(0.0, 1.0), (2.0, 5.0),
                                                   (6.0, 10.0)]
    assert program_spans.named_gaps(trace, spans) == [
        ["advance", 4.0], ["advance/fleet.write_back", 3.0],
        ["advance/fleet.prepare", 1.0]]


def test_unnamed_idle_is_what_no_main_thread_span_covers():
    trace, spans = _made_up()
    # idle 8 s, of which [8, 10) lies outside fleet.advance
    assert program_spans.idle_unnamed_share(trace, spans) == pytest.approx(
        25.0)
    assert program_spans.idle_unnamed_share(
        Trace((0.0, 1.0), {}, []), spans) is None


def test_round_ms_and_counters():
    trace, spans = _made_up()
    counters = {"joins": 4, "join_env_seconds": 0.04,
                "join_init_seconds": 0.36, "join_evaluate_seconds": 0.2}
    out = program_spans.reduce(trace, spans, counters)
    assert out["round_ms"]["write_back"] == pytest.approx(2000.0)
    assert out["round_ms"]["stage"] == pytest.approx(1000.0)
    assert out["round_ms"]["stream"] == 0.0
    assert out["join_init_ms"] == pytest.approx(100.0)
    assert out["join_eval_ms"] == pytest.approx(50.0)
    assert out["finalize_ms"] is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(os.path.join(DATA, "churn8_spans.xplane.pb.gz")) as src, \
            open(d / "churn8_spans.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    path = tracing.latest_xplane(str(d))
    with open(os.path.join(DATA, "churn8_spans_rounds.json")) as f:
        saved = json.load(f)
    return (tracing.read_xplane(path), program_spans.read_program_spans(path),
            _records(saved["rounds"]), saved["counters"])


def test_recorded_program_spans(recorded):
    trace, spans, rounds, counters = recorded
    # the benchmark's own spans and window are as they were
    assert [s.name for s in trace.spans] == ["leave", "join", "advance"] * 2
    assert {s.name for s in spans} >= {
        "join", "join.env", "join.init", "join.evaluate", "advance",
        "boundary", "finalize", "prepare", "stream", "stage", "stage_wait",
        "dispatch", "drain.wait", "drain.copy", "write_back"}
    main = program_spans.main_thread_spans(spans)
    assert {s.name for s in spans} - {s.name for s in main} == {"stage"}
    for s in main:
        if s.name == "prepare":
            assert program_spans.innermost(
                main, 0.5 * (s.start + s.end)).name == "prepare"
    # the Pallas learner, named now, lies in the program's learn scope
    kernel = [e for e in trace.ops()
              if tracing.short_name(e.name).endswith("tpu_custom_call")]
    assert [tracing.short_name(e.name) for e in kernel] == [
        "%ddpg_fused_learn.2 tpu_custom_call"] * 2
    assert {e.scope for e in kernel} == {"learn"}
    assert kernel[0].op_name.endswith("/learn/ddpg_fused_learn/pallas_call")
    assert learner.kernel_seconds(trace) > sum(e.seconds for e in kernel)
    assert counters["rounds"] >= 2 and counters["joins"] >= 10


def test_recorded_reductions(recorded):
    trace, spans, rounds, counters = recorded
    out = program_spans.reduce(trace, spans, counters)
    gaps = out["idle_gaps"]
    assert gaps and all("/fleet." in name for name, _ in gaps
                        if name.startswith("advance"))
    assert 0.0 <= out["idle_unnamed_share"] <= 10.0
    assert out["finalize_ms"] > 0 and out["join_init_ms"] > 0
    for phase in ("prepare", "stream", "drain.wait", "write_back", "stage"):
        assert out["round_ms"][phase] > 0, phase
    read = harness.metric_reader("drain_block_ms")(_ctx(rounds))
    assert read == pytest.approx(out["round_ms"]["drain.wait"], rel=0.05)


def test_rehearsal_on_the_cpu(tmp_path):
    t0 = time.perf_counter()
    out = program_spans.run(
        ["--workload", "paper2d_64.churn", "--seed", "7100000005",
         "--sessions", "4", "--turnover", "1", "--rounds", "2",
         "--save-trace", str(tmp_path / "c4")], require_tpu=False)
    assert out["device"]["platform"] == "cpu"
    assert out["counters"]["joins"] == 4 + 1 + 2 + 2 and out["rounds"] == 2
    assert out["rate_untraced"] > 0 and out["rate_traced"] > 0
    assert out["join_init_ms"] > 0 and out["join_eval_ms"] > 0
    assert out["populate_s"] < time.perf_counter() - t0
    assert (tmp_path / "c4.xplane.pb.gz").exists()
    saved = json.loads((tmp_path / "c4_rounds.json").read_text())
    assert len(saved["rounds"]) == 2
    assert out["idle_gaps"] == []        # no device plane on the CPU
