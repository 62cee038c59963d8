"""The trace reduction against a small trace recorded on one TPU v5e: an
8-session paper2d fleet (chunk 8) under churn, two rounds of one leave,
one join and advance(1), with the benchmark's spans (``data/``)."""

import gzip
import json
import os
import shutil

import numpy as np
import pytest

from bench import learner, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(os.path.join(DATA, "churn8.xplane.pb.gz")) as src, \
            open(d / "churn8.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.read_xplane(tracing.latest_xplane(str(d)))


def test_spans_and_window(trace):
    assert [s.name for s in trace.spans] == ["leave", "join", "advance"] * 2
    assert trace.window == (trace.spans[0].start, trace.spans[-1].end)
    assert trace.window_s == pytest.approx(0.405511938, abs=1e-9)
    assert list(trace.devices) == ["/device:TPU:0"]


def test_busy_is_the_union_of_op_intervals(trace):
    """Against an independent count on a 1 ns grid."""
    lo, hi = trace.window
    evs = trace.devices["/device:TPU:0"]
    grid = np.zeros(int(round((hi - lo) * 1e9)) + 1, bool)
    for e in evs:
        a = int(round((max(e.start, lo) - lo) * 1e9))
        b = int(round((min(e.end, hi) - lo) * 1e9))
        grid[a:max(a, b)] = True
    assert tracing.busy_seconds(trace) == pytest.approx(
        grid.sum() * 1e-9, abs=1e-6)
    assert tracing.busy_seconds(trace) == pytest.approx(0.0075886680,
                                                        abs=1e-9)


def test_learner_and_chunk_program(trace):
    # two advance(1) rounds: one chunk-step each, one learner call each
    kernel = [e for e in trace.ops() if learner.LEARNER_EVENT in e.name]
    assert len(kernel) == 2
    assert learner.kernel_seconds(trace) == pytest.approx(0.006352141,
                                                          abs=1e-9)
    assert learner.episode_seconds(trace) == pytest.approx(0.006945126,
                                                           abs=1e-9)
    assert all(e.module.startswith(learner.EPISODE_MODULE) for e in kernel)


def test_breakdown(trace):
    b = tracing.breakdown(trace)
    assert b["device_ops"][0][0] == "%closed_call.57 tpu_custom_call"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert {k for k, _ in b["idle_gaps"]} <= {"join", "advance", "leave",
                                              "host"}
    gaps = sum(v for _, v in tracing.idle_gaps(trace))
    assert gaps == pytest.approx(trace.window_s
                                 - tracing.busy_seconds(trace), abs=1e-6)


def test_readers_on_the_recorded_trace(trace):
    from bench import flops, harness
    from bench.traffic import RoundRecord

    with open(os.path.join(DATA, "churn8_rounds.json")) as f:
        rounds = [RoundRecord(joined=[], join_latencies=[], **r)
                  for r in json.load(f)]
    config = harness.load_json(os.path.join(harness.ROOT, "bench", "configs",
                                            "paper2d_64.json"))
    ctx = harness.MetricContext(
        trace=trace, rounds=rounds, spans=[], config=config,
        peaks=harness.device_peaks("TPU v5 lite"), chips=1,
        steps_per_s=16 / trace.window_s, flops=flops,
        window=(0.0, 1.0))
    read = {n: harness.metric_reader(n)(ctx) for n in (
        "learner_kernel_ms", "episode_other_ms", "learner_roofline",
        "device_idle_share", "stream_wait_ms", "advance_host_ms",
        "step_mfu")}
    assert read["learner_kernel_ms"] == pytest.approx(6.352141 / 2)
    assert read["episode_other_ms"] == pytest.approx(
        (6.945126 - 6.352141) / 2)
    least = 16 * 96 * flops.update_flops(12, 2, (64, 64), 16) / 197e12
    assert read["learner_roofline"] == pytest.approx(
        100 * least / 0.006352141)
    assert read["device_idle_share"] == pytest.approx(
        100 * (1 - 0.0075886680 / 0.405511938))
    assert 0 < read["step_mfu"] < 100
    assert read["advance_host_ms"] > 0 and read["stream_wait_ms"] > 0
    assert harness.metric_reader("join_ms")(ctx) is None
