"""Read the numbers the output check compares, for the program and for the
control, to set the limits in ``bench/limits/`` (see PERF.md). The
benchmark's runs do not run it.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s> \
        --trace 0 [--groups G] [--read program,control,half_batch,...]

It runs the cell like ``bench/run.py`` but collects ``G`` samples of
sessions (of the size the check takes) from the one window, and logs for
each the program's numbers and the control's: the plain reference in the
program's place, computed in the next precision below the configuration's.
For the first three samples it also logs the numbers of the reference with
each fault of ``reference.session.FAULTS`` planted in the program's place.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    BENCH = os.path.dirname(os.path.abspath(__file__))
    ROOT = os.path.dirname(BENCH)
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != BENCH]

from bench import check, harness  # noqa: E402
from bench.reference.session import FAULTS, Deployment  # noqa: E402

FAULT_SAMPLES = 3


def control_outputs(dep, outputs: list, fault: str = None) -> list:
    """The control: the reference in the program's place, free-running in
    the next precision below the configuration's (``"high"``: three
    bfloat16 passes for float32 at highest), for the same sessions, steps,
    anchors and learner boundaries as the program's ``outputs``. With
    ``fault`` (one of ``reference.session.FAULTS``), the reference in full
    precision with that fault planted instead."""
    mode = "high" if fault is None else "highest"
    src = Deployment(dep.config, fault)
    snap_steps = sorted({t for o in outputs
                         for t in (o["anchor"][0], *o["snapshots"])})
    ctl = src.replay([{**o["join"], "steps": len(o["idx"])}
                      for o in outputs], mode, forced=False,
                     snapshot_steps=tuple(snap_steps))
    leaving = any("final" in o for o in outputs)
    finals = src.finalize(ctl, mode) if leaving else [None] * len(ctl)
    out = []
    for o, c, f in zip(outputs, ctl, finals):
        start = o["anchor"][0]
        oc = {"join": o["join"], "idx": c["idx"], "metrics": c["metrics"],
              "rewards": c["rewards"], "objectives": c["objectives"],
              "default_metrics": c["default_metrics"],
              "actions": c["actions"],
              "buffer": c["buffer"], "learner": c["learner"],
              "anchor": (start, c["snapshots"][start]),
              "learner_steps": o["learner_steps"],
              "snapshots": {t: c["snapshots"][t] for t in o["snapshots"]}}
        if f is not None:
            oc["final"] = f
        out.append(oc)
    return out


def readings(dep, outputs: list, faults: bool,
             read: tuple = ("program", "control") + FAULTS) -> list:
    """(what, numbers) for the program's ``outputs``, the control in their
    place and, with ``faults``, each fault planted in the reference; of
    these, those named in ``read``."""
    out = []
    if "program" in read:
        out.append(("program", check.numbers(dep, outputs)))
    if "control" in read:
        out.append(("control", check.numbers(
            dep, control_outputs(dep, outputs))))
    for fault in FAULTS if faults else ():
        if fault in read:
            out.append((fault, check.numbers(
                dep, control_outputs(dep, outputs, fault))))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--groups", type=int, default=12)
    p.add_argument("--read", default=",".join(("program", "control")
                                              + FAULTS),
                   help="comma-separated: program, control, and faults")
    own, rest = p.parse_known_args(argv)
    try:
        w = harness.measure(rest, t_start=T_START,
                            sample=own.groups * harness.CHECK_SAMPLE)
    except harness.BenchError as err:
        harness.log(f"bench: {err}; no result")
        return 2
    s = w.sampler
    n = harness.CHECK_SAMPLE
    for g in range(0, len(s.collected), n):
        group = s.collected[g:g + n]
        t0 = time.perf_counter()
        for what, nums in readings(s.dep, group, g // n < FAULT_SAMPLES,
                                   tuple(own.read.split(","))):
            harness.log(f"sample {g // n} {what}: " + "; ".join(
                f"{k} {v!r}" for k, v in nums.items()))
        harness.log(f"sample {g // n}: {len(group)} sessions, "
                    f"{time.perf_counter() - t0:.3f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
