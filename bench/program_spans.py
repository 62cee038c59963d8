"""Which host phase of a round or a join holds the time, from the program's
own spans and counters (``fleet.*``, ``repro.core.spans``) in one cell.

    python3 bench/program_spans.py --workload <cell> --seed <n>

Run it from the root of a checkout, on the chip. It sets the cell up as
``bench/run.py`` does (the same service, roster and client), runs the mix's
``trace_rounds`` rounds with the profiler off, then as many with it on, and
prints one JSON object as its last line:

  rate_untraced, rate_traced  session-steps per second of each half, on one
                              seed: what tracing costs when it is on
  round_ms                    mean ``fleet.<phase>`` span per traced round,
                              by phase (main thread; ``stage`` is the stage
                              thread's)
  finalize_ms                 median ``fleet.finalize`` span
  join_init_ms, join_eval_ms  ``FleetService.counters``: ``join_env`` plus
                              ``join_init``, and ``join_evaluate``, seconds
                              per join, over every join of the run
  populate_s                  host clock around joining the initial fleet
  idle_unnamed_share          % of the window's device-idle time that no
                              ``fleet.*`` span of the main thread covers
  idle_gaps                   the longest device-idle gaps, each named
                              ``<benchmark span>/fleet.<innermost span>``

The harness's result line does not carry these: its trace reduction keeps
only the benchmark's own spans, and its metric context holds no service
counters (PERF.md, Open questions). ``--sessions``, ``--turnover`` and
``--save-trace`` record a small trace for ``bench/tests``.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import gzip
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PROGRAM_PREFIX = "fleet."
MAIN_PHASES = ("advance", "boundary", "finalize", "prepare", "stream",
               "stage_wait", "dispatch", "drain.wait", "drain.copy",
               "write_back")


@dataclasses.dataclass
class ProgramSpan:
    name: str        # the phase, prefix off: "prepare", "drain.wait"...
    start: float     # seconds on the trace clock
    end: float
    thread: int      # the host line (one per thread) it was recorded on
    ids: dict


def read_program_spans(path: str) -> list:
    """Every ``fleet.*`` host event of one profile, by start."""
    from jax.profiler import ProfileData

    spans, thread = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    s = e.start_ns * 1e-9
                    spans.append(ProgramSpan(
                        e.name[len(PROGRAM_PREFIX):], s,
                        s + e.duration_ns * 1e-9, thread,
                        {k: v for k, v in e.stats}))
    return sorted(spans, key=lambda s: s.start)


def main_thread_spans(spans: list) -> list:
    """The spans of the thread that calls the service (the one holding
    ``advance`` and ``join``)."""
    threads = {s.thread for s in spans if s.name in ("advance", "join")}
    return [s for s in spans if s.thread in threads]


def innermost(spans: list, t: float) -> Optional[ProgramSpan]:
    """The innermost of ``spans`` (one thread's) open at time ``t``."""
    open_at = [s for s in spans if s.start <= t < s.end]
    return max(open_at, key=lambda s: s.start, default=None)


def idle_intervals(trace) -> list:
    """[(start, end)] of every device-idle stretch inside the window, on
    the first device plane (as ``tracing.idle_gaps`` finds them)."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    out, cursor = [], lo
    for e in next(iter(trace.devices.values())):
        if e.start > cursor:
            out.append((cursor, min(e.start, hi)))
        cursor = max(cursor, e.end)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(s, e) for s, e in out if e > s]


def named_gaps(trace, spans: list, top: int = 10) -> list:
    """The ``top`` longest idle gaps as [name, seconds]: the benchmark span
    the gap falls in, and after a slash the innermost main-thread program
    span open at its middle, where one is."""
    main = main_thread_spans(spans)
    out = []
    for s, e in sorted(idle_intervals(trace), key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        owner = next((b.name for b in trace.spans
                      if b.start <= mid < b.end), "host")
        inner = innermost(main, mid)
        name = owner if inner is None else (
            f"{owner}/{PROGRAM_PREFIX}{inner.name}")
        out.append([name, e - s])
    return out


def _merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_unnamed_share(trace, spans: list) -> Optional[float]:
    """Percent of the window's device-idle time outside every main-thread
    ``fleet.*`` span."""
    gaps = idle_intervals(trace)
    idle = sum(e - s for s, e in gaps)
    if idle <= 0:
        return None
    cover = _merged((s.start, s.end) for s in main_thread_spans(spans))
    starts = [c[0] for c in cover]
    covered = 0.0
    for s, e in gaps:
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(cover) and cover[i][0] < e:
            covered += max(0.0, min(e, cover[i][1]) - max(s, cover[i][0]))
            i += 1
    return 100.0 * (idle - covered) / idle


def round_ms(trace, spans: list) -> dict:
    """Mean milliseconds a traced round spends in each program phase."""
    rounds = sum(s.name == "advance" for s in trace.spans)
    lo, hi = trace.window
    inside = [s for s in spans if lo <= s.start and s.end <= hi]
    main = main_thread_spans(inside)
    out = {}
    for name in MAIN_PHASES + ("stage",):
        pool = inside if name == "stage" else main
        total = sum(s.end - s.start for s in pool if s.name == name)
        out[name] = total / max(1, rounds) * 1e3
    return out


def reduce(trace, spans: list, counters: dict) -> dict:
    """Everything the report prints that the trace and counters give."""
    lo, hi = trace.window
    fin = [s.end - s.start for s in spans
           if s.name == "finalize" and lo <= s.start < hi]
    joins = max(1, counters["joins"])
    return {
        "round_ms": round_ms(trace, spans),
        "finalize_ms": statistics.median(fin) * 1e3 if fin else None,
        "join_init_ms": (counters["join_env_seconds"]
                         + counters["join_init_seconds"]) / joins * 1e3,
        "join_eval_ms": counters["join_evaluate_seconds"] / joins * 1e3,
        "idle_unnamed_share": idle_unnamed_share(trace, spans),
        "idle_gaps": named_gaps(trace, spans),
    }


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--sessions", type=int,
                   help="fleet size (and at most the chunk) in place of "
                        "the configuration's")
    p.add_argument("--turnover", type=int,
                   help="sessions that leave and join each round, in place "
                        "of the mix's share")
    p.add_argument("--rounds", type=int,
                   help="rounds of each half, in place of the mix's "
                        "trace_rounds")
    p.add_argument("--save-trace",
                   help="write the profile to <path>.xplane.pb.gz and the "
                        "traced rounds to <path>_rounds.json")
    return p.parse_args(argv)


def _rounds(client, n: int) -> tuple:
    t0 = time.perf_counter()
    rounds = [client.round() for _ in range(n)]
    steps = sum(r.session_steps for r in rounds)
    return rounds, steps / (rounds[-1].end - t0)


def run(argv=None, *, require_tpu: bool = True) -> dict:
    args = parse_args(argv)
    from bench import harness, traffic, tracing

    parts = harness.cell_files(
        harness.load_json(os.path.join(ROOT, "BENCHMARK.json")),
        args.workload)
    config, mix = dict(parts["config"]), dict(parts["mix"])
    if args.sessions:
        config["sessions"] = args.sessions
        config["chunk"] = min(int(config["chunk"]), args.sessions)
    fleet = int(config["sessions"])
    if args.turnover is not None:
        mix["turnover_share"] = args.turnover / fleet
    n_rounds = args.rounds or int(mix["trace_rounds"])

    import jax
    import numpy as np
    device = jax.devices()[0]
    if require_tpu and device.platform != "tpu":
        raise harness.BenchError(f"JAX found no TPU (platform "
                                 f"{device.platform!r})")
    from repro.core.episode import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()

    roster = traffic.Roster(config["workloads"], config["objectives"],
                            np.random.default_rng(args.seed))
    svc = harness._service(config,
                           jax.devices()[:int(parts["cell"]["chips"])])
    client = traffic.Client(svc, roster, mix, fleet)
    t0 = time.perf_counter()
    client.populate()
    populate_s = time.perf_counter() - t0
    client.round()                      # warm-up
    _, rate_off = _rounds(client, n_rounds)
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tracing.profiler_options())
        rounds, rate_on = _rounds(client, n_rounds)
        jax.profiler.stop_trace()
        path = tracing.latest_xplane(trace_dir)
        trace = tracing.read_xplane(path)
        spans = read_program_spans(path)
        if args.save_trace:
            with open(path, "rb") as src, \
                    gzip.open(args.save_trace + ".xplane.pb.gz", "wb") as dst:
                shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    counters = dict(svc.counters)
    if args.save_trace:
        with open(args.save_trace + "_rounds.json", "w") as f:
            json.dump({"rounds": [
                {"start": r.start, "end": r.end, "steps": r.steps,
                 "session_steps": r.session_steps,
                 "num_chunks": r.num_chunks, "staging": r.staging}
                for r in rounds], "counters": counters}, f, indent=1)
    return {"workload": args.workload, "seed": args.seed,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "sessions": fleet, "rounds": n_rounds,
            "rate_untraced": rate_off, "rate_traced": rate_on,
            "populate_s": populate_s, "counters": counters,
            "stream_wait_ms": statistics.mean(
                r.staging["stage_wait_seconds"] + r.staging["drain_seconds"]
                for r in rounds) * 1e3,
            "drain_block_ms": statistics.mean(
                r.staging["drain_block_seconds"] for r in rounds) * 1e3,
            **reduce(trace, spans, counters)}


def main(argv=None) -> int:
    from bench import harness
    try:
        result = run(argv)
    except harness.BenchError as err:
        print(f"bench: {err}; no result", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    sys.exit(main())
