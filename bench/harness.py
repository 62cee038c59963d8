"""One run of one cell: set-up, the measured window, the output check and
the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name: ``bench/configs/<config>.json``,
``bench/mixes/<traffic>.json``, ``bench/metrics/<metric>.py`` and
``bench/limits/<workload>.json``, all named in ``BENCHMARK.json``. A new cell
is a new entry there plus new files; no code here names a cell.

A cell's ``chips`` reach the service (``_service``). A cell on one chip
builds ``FleetService`` from its configuration's keyword arguments alone,
on the default device. A cell on more chips hands the service its first
``chips`` devices as ``devices=``, the keyword ``FleetTuner.from_grid`` and
``run_fleet_episode_scan`` take, and keeps the configuration's ``chunk``:
a multi-chip configuration states its chunk across all of its chips, as
``resolve_chunk(n, chunk, num_devices)`` reads it. Where ``FleetService``
takes no ``devices``, such a cell stops with a ``BenchError`` before any
session joins; it never runs on one chip under a multi-chip label. So a
multi-chip deployment is data alone: its configuration, its
``BENCHMARK.json`` entry and its limits file.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import inspect
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
LIMITS_DIR = os.path.join(BENCH_DIR, "limits")
CHECK_SAMPLE = 16          # sessions the output check replays


class BenchError(Exception):
    """The run cannot produce a result (no chip, a missing file...)."""


# -- finding things by name --------------------------------------------------

def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}") from e


def cell_files(spec: dict, workload: str) -> dict:
    """Everything a cell is made of, found by the names in ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[cell["config"]]

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if applies(m) and m["moves"] in reported]
    return {
        "cell": cell,
        "config": load_json(os.path.join(ROOT, cfg_entry["file"])),
        "mix": load_json(os.path.join(BENCH_DIR, "mixes",
                                      cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(LIMITS_DIR, workload + ".json")),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no reader bench/metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(kind: str) -> dict:
    peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return peaks[kind]


# -- compile accounting ------------------------------------------------------

class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_seconds, self.cache_hits


class GcPauses:
    """Passes of Python's garbage collector and the time they take, from
    construction to ``stop``."""

    def __init__(self):
        self.count, self.seconds, self.longest = 0, 0.0, 0.0
        self._t = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.count += 1
            self.seconds += d
            self.longest = max(self.longest, d)
            self._t = None

    def stop(self) -> None:
        gc.callbacks.remove(self._callback)


# -- the run -------------------------------------------------------------------

def _service(config: dict, devices):
    """The cell's ``FleetService`` on the cell's ``devices`` (the module's
    docstring gives the contract)."""
    from repro.core.service import FleetService
    from repro import envs
    kwargs = dict(
        chunk=int(config["chunk"]), env_cls=getattr(envs, config["env"]),
        buffer_capacity=int(config["buffer_capacity"]),
        warmup_steps=int(config["warmup_steps"]),
        eval_runs=int(config["eval_runs"]))
    if len(devices) > 1:
        if "devices" not in inspect.signature(FleetService).parameters:
            raise BenchError(f"FleetService takes no devices=, so the "
                             f"service cannot run on {len(devices)} chips")
        kwargs["devices"] = tuple(devices)
    return FleetService(**kwargs)


def _check_widths(svc, config: dict) -> None:
    """The service runs the configuration as stated, or the run stops."""
    cfg = svc.cfg
    space = next(iter(svc._join_queue)).env.param_space
    want = ([k["name"] for k in config["knobs"]], tuple(config["hidden"]),
            config["updates_per_step"], config["batch_size"])
    got = (space.names, tuple(cfg.hidden), cfg.updates_per_step,
           cfg.batch_size)
    if want != got or cfg.action_dim != len(config["knobs"]):
        raise BenchError(f"the service does not run the configuration as "
                         f"stated: want {want}, got {got}")


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def parse_args(argv):
    p = argparse.ArgumentParser(description="Run one benchmark cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Window:
    """What one measured window left: the result object without its
    check, and what the check needs (``sampler``, ``parts``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def measure(argv=None, *, t_start: float, require_tpu: bool = True,
            overrides: Optional[dict] = None,
            sample: int = CHECK_SAMPLE) -> Window:
    """Set-up, the measured window and its metrics, and the sample of
    what the program produced for the check; the program's state is freed
    on return. ``require_tpu=False`` and ``overrides`` (config keys
    replaced, e.g. a tiny fleet) are for the CPU rehearsal in
    ``bench/tests`` only; ``sample`` sessions are collected for the check
    (``bench/control.py`` takes more)."""
    args = parse_args(argv)
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    parts = cell_files(spec, args.workload)
    config = {**parts["config"], **(overrides or {})}
    mix, cell = parts["mix"], parts["cell"]

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < int(cell["chips"]):
        raise BenchError(f"the cell needs {cell['chips']} chips, JAX found "
                         f"{len(devices)}")
    used = devices[:int(cell["chips"])]
    kind = devices[0].device_kind
    # the CPU rehearsal borrows the v5e's peaks so its readers run through
    peaks = device_peaks(kind if require_tpu else "TPU v5 lite")
    readers = {m["name"]: metric_reader(m["name"])
               for m in parts["per_layer"]} if args.trace else {}

    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.episode import (enable_persistent_compilation_cache,
                                    episode_cache_stats)
    # the directory JAX_COMPILATION_CACHE_DIR names, else the checkout's
    # fixed .jax_cache
    enable_persistent_compilation_cache()
    compiles = CompileCounter()

    import numpy as np
    from bench import check, traffic

    rng = np.random.default_rng(args.seed)
    roster = traffic.Roster(config["workloads"], config["objectives"], rng)
    svc = _service(config, used)
    fleet = int(config["sessions"])
    client = traffic.Client(svc, roster, mix, fleet)
    client.populate()
    _check_widths(svc, config)
    t_joined = time.perf_counter()
    client.round()                      # warm-up: one round of the traffic
    sampler = check.Sampler(client, np.random.default_rng([args.seed, 1]),
                            sample)
    sampler.after_warmup()
    t_warm = time.perf_counter()
    log(f"set-up: {fleet} sessions joined in {t_joined - t_start:.3f} s "
        f"since process start; warm-up round {t_warm - t_joined:.3f} s; "
        f"{compiles.compiles} backend compiles "
        f"({compiles.compile_seconds:.3f} s), {compiles.cache_hits} "
        f"persistent cache hits")

    before = (compiles.snapshot(), episode_cache_stats()["executables"])
    pauses = GcPauses()
    trace_dir = None
    if args.trace:
        from bench import tracing
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tracing.profiler_options())
    setup_s = time.perf_counter() - t_start
    rounds = []
    t0 = time.perf_counter()
    while True:
        rounds.append(client.round(keep_departed=True))
        sampler.after_round(rounds[-1].joined)
        if args.trace:
            if len(rounds) >= int(mix["trace_rounds"]):
                break
        elif rounds[-1].end - t0 >= args.seconds:
            break
    t_end = rounds[-1].end
    if args.trace:
        jax.profiler.stop_trace()
    pauses.stop()
    after = (compiles.snapshot(), episode_cache_stats()["executables"])
    window_compiles = after[0][0] - before[0][0]
    log(f"window: {len(rounds)} rounds in {t_end - t0:.3f} s; "
        f"{window_compiles} backend compiles and "
        f"{after[1] - before[1]} new episode executables inside it")
    if client.round_steps > 1:
        log("rounds (s): " + " ".join(f"{r.end - r.start:.3f}"
                                      for r in rounds))
    log(f"garbage collection in the window: {pauses.count} passes, "
        f"{pauses.seconds:.3f} s, longest {pauses.longest:.3f} s")

    mem_peak = memory_peak_bytes(used)
    attempted = sum(r.session_steps for r in rounds)
    expected = len(rounds) * fleet * client.round_steps
    steps_per_s = attempted / (t_end - t0)
    device = {"platform": platform, "kind": kind, "count": len(used),
              "memory_peak_bytes": mem_peak}

    metrics, breakdown = {}, None
    if args.trace:
        from bench import flops, learner, tracing
        tr = tracing.read_xplane(tracing.latest_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = tracing.busy_seconds(tr)
        device.update(busy_s=busy, window_s=tr.window_s)
        breakdown = tracing.breakdown(tr)
        n_un, s_un = learner.unattributed(tr)
        log(f"scopes: the chunk program {learner.episode_seconds(tr)!r} "
            f"device s, its learn scope {learner.kernel_seconds(tr)!r} s; "
            f"{n_un} of its ops unattributed, {s_un!r} s; leaf ops by "
            f"scope {learner.scope_leaf_seconds(tr)!r}")
        ctx = MetricContext(trace=tr, rounds=rounds, spans=client.spans,
                            config=config, peaks=peaks, chips=len(used),
                            steps_per_s=steps_per_s, flops=flops,
                            window=(t0, t_end))
        for m in parts["per_layer"]:
            value = readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "session_steps_per_s": steps_per_s,
            "join_p90_s": _p90([x for r in rounds
                                for x in r.join_latencies]),
            "setup_s": setup_s,
        }
        for m in parts["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    sampler.collect(config)
    del svc, client.svc                 # the program's state is freed
    result = {"correct": attempted == expected, "attempted": expected,
              "failed": expected - attempted, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return Window(result=result, sampler=sampler, parts=parts)


def with_limits(nums: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} of every number the check computes; a
    number the cell's limits file does not name stops the run, and one
    it gives the limit null is read but not held (PERF.md says why)."""
    missing = sorted(set(nums) - set(limits))
    if missing:
        raise BenchError(f"no limit for {missing} in the cell's limits file")
    return {name: {"value": value, "limit": limits[name]}
            for name, value in nums.items()}


def holds(c: dict) -> bool:
    return c["limit"] is None or c["value"] <= c["limit"]


def run(argv=None, *, t_start: float, require_tpu: bool = True,
        overrides: Optional[dict] = None) -> dict:
    """One run: ``measure``, then the output check; returns the result
    object, the check's numbers last."""
    from bench import check
    w = measure(argv, t_start=t_start, require_tpu=require_tpu,
                overrides=overrides)
    t_check = time.perf_counter()
    checks = with_limits(check.numbers(w.sampler.dep, w.sampler.collected),
                         w.parts["limits"])
    log(f"output check: {time.perf_counter() - t_check:.3f} s")
    result = w.result
    result["correct"] = result["correct"] and all(
        holds(c) for c in checks.values())
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    return result


def _p90(xs: list) -> Optional[float]:
    if len(xs) < 10:
        return None
    return statistics.quantiles(xs, n=10, method="inclusive")[-1]


class MetricContext:
    """What a per-layer reader may read: the reduced trace, the rounds of
    the traced window with the program's staging counters, the
    benchmark's host spans, the configuration, the device's peaks and the
    operation counts."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None, *, t_start: float) -> int:
    try:
        result = run(argv, t_start=t_start)
    except BenchError as err:
        log(f"bench: {err}; no result")
        return 2
    print(json.dumps(result), flush=True)
    return 0

