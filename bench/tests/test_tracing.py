"""The trace reduction against small traces recorded on one TPU v5e: an
8-session paper2d fleet (chunk 8) under churn, two rounds of one leave,
one join and advance(1), with the benchmark's spans (``data/``).
``churn8`` ran the Pallas learner before the program named its scopes;
``churn8_xla`` runs the XLA learner under ``auto``, recorded with
``python3 bench/program_spans.py --workload paper2d_64.churn --seed
7100000001 --sessions 8 --turnover 1 --rounds 2 --save-trace <path>``."""

import gzip
import json
import os
import shutil

import numpy as np
import pytest

from bench import learner, tracing, xspace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _unpack(tmp_path_factory, name: str) -> str:
    d = tmp_path_factory.mktemp(name)
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz")) as src, \
            open(d / (name + ".xplane.pb"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.latest_xplane(str(d))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    return tracing.read_xplane(_unpack(tmp_path_factory, "churn8"))


@pytest.fixture(scope="module")
def xla_path(tmp_path_factory):
    return _unpack(tmp_path_factory, "churn8_xla")


@pytest.fixture(scope="module")
def xla(xla_path):
    return tracing.read_xplane(xla_path)


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


def test_learner_and_chunk_program(xla):
    # two advance(1) rounds: one chunk-step each; the learner is the
    # chunk program's learn scope, XLA's scan with no Mosaic kernel in it
    learn = [e for e in xla.ops() if e.scope == "learn"]
    assert learn and not any("tpu_custom_call" in e.name for e in learn)
    assert all(e.module.startswith(learner.EPISODE_MODULE) for e in learn)
    assert learner.kernel_seconds(xla) == pytest.approx(0.0030553500,
                                                        abs=1e-9)
    assert learner.episode_seconds(xla) == pytest.approx(0.0031280220,
                                                         abs=1e-9)


def test_xla_learner_scope_attribution(xla):
    """The learner is the ops under ``learn``: its time is the sum of the
    leaf ops under ``learn`` or inside the scan's loop (a ``while`` op's
    event spans its body, where XLA's own copies and slices carry no
    scope), no op outside the chunk program is under ``learn``, and the
    scope map attributes every op."""
    lo, hi = xla.window
    loops = [e for e in xla.ops() if e.op_name.endswith("/learn/while")]
    assert len(loops) == 2                 # one scan a chunk-step

    def in_loop(e):
        return any(w.start <= e.start and e.end <= w.end for w in loops)

    leaf = [e for evs in xla.devices.values() for e in tracing.leaf_ops(evs)]
    learn = [e for e in leaf if e.scope == "learn" or in_loop(e)]
    learn_sum = sum(min(e.end, hi) - max(e.start, lo) for e in learn)
    assert learner.kernel_seconds(xla) > 0
    assert learner.kernel_seconds(xla) == pytest.approx(learn_sum, rel=0.01)
    unscoped = sum(e.seconds for e in learn if e.scope != "learn")
    assert 0 < unscoped < 0.02 * learn_sum
    assert not [e for e in xla.ops() if e.scope == "learn"
                and not e.module.startswith(learner.EPISODE_MODULE)]
    ops, seconds = learner.unattributed(xla)
    assert seconds < 0.01 * learner.episode_seconds(xla)
    assert (ops, seconds) == (0, 0.0)
    assert all(e.op_name is not None for e in xla.ops())
    by_scope = learner.scope_leaf_seconds(xla)
    assert set(by_scope) == {"", "act", "env", "reward", "store", "learn"}
    assert by_scope["learn"] > 0.95 * sum(by_scope.values())


def test_hlo_op_names_of_the_recorded_trace(xla_path):
    """``bench/xspace.py`` reads every module's instructions from the
    profile's HLO: the chunk program's and the small jits' around it."""
    names = xspace.op_names(xla_path)
    episode = [m for m in names if m.startswith(learner.EPISODE_MODULE)]
    assert len(episode) == 1
    ops = names[episode[0]]
    assert len(ops) > 1000
    assert {tracing.scope_of(v) for v in ops.values()} == {
        "", "act", "env", "reward", "store", "learn"}
    assert all(isinstance(k, str) and isinstance(v, str)
               for k, v in ops.items())


def test_scope_of_and_instruction_name():
    assert tracing.scope_of(
        "jit(episode)/vmap()/while/body/closed_call/learn/while") == "learn"
    assert tracing.scope_of("env/reduce_or") == "env"
    # the outermost scope wins; a name that merely holds one does not count
    assert tracing.scope_of("jit(episode)/learn/act/dot_general") == "learn"
    assert tracing.scope_of("jit(episode)/learner/environment") == ""
    assert tracing.scope_of("") == ""
    assert tracing.instruction_name(
        "%fusion.611 = f32[16,64]{1,0} fusion(f32[16,64] %p), kind=kLoop"
    ) == "fusion.611"
    assert tracing.instruction_name("%while.114") == "while.114"


def test_a_trace_without_scopes_has_no_learner(trace):
    """``churn8`` predates the program's named scopes: its ops are found in
    the HLO, none is under ``learn``, and the learner readers read
    nothing rather than 0."""
    from bench import harness
    assert learner.unattributed(trace) == (0, 0.0)
    assert {e.scope for e in trace.ops()} == {""}
    assert learner.kernel_seconds(trace) == 0.0
    ctx = harness.MetricContext(trace=trace, rounds=[], config={})
    assert harness.metric_reader("learner_kernel_ms")(ctx) is None
    assert harness.metric_reader("learner_roofline")(ctx) is None


def test_breakdown(trace):
    b = tracing.breakdown(trace)
    assert b["device_ops"][0][0] == "%closed_call.57 tpu_custom_call"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert {k for k, _ in b["idle_gaps"]} <= {"join", "advance", "leave",
                                              "host"}
    gaps = sum(v for _, v in tracing.idle_gaps(trace))
    assert gaps == pytest.approx(trace.window_s
                                 - tracing.busy_seconds(trace), abs=1e-6)


def test_readers_on_the_recorded_trace(xla):
    from bench import flops, harness
    from bench.traffic import RoundRecord

    with open(os.path.join(DATA, "churn8_xla_rounds.json")) as f:
        rounds = [RoundRecord(joined=[], join_latencies=[], **r)
                  for r in json.load(f)["rounds"]]
    config = harness.load_json(os.path.join(harness.ROOT, "bench", "configs",
                                            "paper2d_64.json"))
    ctx = harness.MetricContext(
        trace=xla, rounds=rounds, spans=[], config=config,
        peaks=harness.device_peaks("TPU v5 lite"), chips=1,
        steps_per_s=16 / xla.window_s, flops=flops,
        window=(0.0, 1.0))
    read = {n: harness.metric_reader(n)(ctx) for n in (
        "learner_kernel_ms", "episode_other_ms", "learner_roofline",
        "device_idle_share", "stream_wait_ms", "advance_host_ms",
        "step_mfu")}
    assert read["learner_kernel_ms"] == pytest.approx(3.0553500 / 2)
    assert read["episode_other_ms"] == pytest.approx(
        (3.1280220 - 3.0553500) / 2)
    least = 16 * 96 * flops.update_flops(12, 2, (64, 64), 16) / 197e12
    assert read["learner_roofline"] == pytest.approx(
        100 * least / 0.0030553500)
    assert read["device_idle_share"] == pytest.approx(
        100 * (1 - tracing.busy_seconds(xla) / xla.window_s))
    assert 0 < read["step_mfu"] < 100
    assert read["advance_host_ms"] > 0 and read["stream_wait_ms"] > 0
    assert harness.metric_reader("join_ms")(ctx) is None
