"""The one traffic generator and closed-loop client of every cell.

A traffic mix is a JSON file of parameters under ``bench/mixes``:

  round_steps      steps each ``FleetService.advance`` call runs
  turnover_share   share of the fleet that leaves (oldest first) and is
                   replaced by as many new sessions before each advance
  trace_rounds     rounds a traced run profiles

A configuration fixes the fleet: its size, and the workload x objective
cells that sessions cycle through. The workload seed draws each session's
seed and the order in which a batch of sessions joins; the composition of
the fleet is the same for every seed. The service only ever receives the
generated ``request_join`` arguments.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

# session seeds stay below 2**30: the service derives four streams from
# seed .. seed + 3 and ``jax.random.PRNGKey`` takes a 32-bit seed
SESSION_SEED_LIMIT = 2 ** 30


@dataclasses.dataclass(frozen=True)
class JoinArgs:
    workload: str
    weights: dict
    seed: int


class Roster:
    """Join arguments in cycle order (workload-major, as the smoke run
    builds its fleet), with seeds and join order drawn from ``rng``."""

    def __init__(self, workloads, objectives, rng: np.random.Generator):
        self._cells = [(w, dict(o)) for w in workloads for o in objectives]
        self._next = 0
        self._rng = rng

    def draw(self, n: int) -> list:
        pairs = [self._cells[(self._next + i) % len(self._cells)]
                 for i in range(n)]
        self._next += n
        seeds = self._rng.choice(SESSION_SEED_LIMIT, size=n, replace=False)
        order = self._rng.permutation(n)
        return [JoinArgs(pairs[i][0], pairs[i][1], int(seeds[i]))
                for i in order]


@dataclasses.dataclass
class Span:
    kind: str        # "join", "leave" or "advance"
    start: float     # host perf_counter seconds
    end: float


@dataclasses.dataclass
class RoundRecord:
    start: float
    end: float
    steps: int                    # steps each session advanced
    session_steps: int
    joined: list                  # sids that joined before this round
    join_latencies: list          # seconds, one per join of this round
    staging: dict                 # FleetService.last_stats["staging"]
    num_chunks: int


class Client:
    """Runs a mix against a ``FleetService``: joins the initial fleet, then
    one round at a time. Every call into the service is wrapped in a host
    span (kept in ``spans``) and a profiler ``TraceAnnotation`` of the same
    name, so a traced run sees them on the device trace's clock."""

    def __init__(self, service, roster: Roster, mix: dict, fleet_size: int):
        self.svc = service
        self.roster = roster
        self.round_steps = int(mix["round_steps"])
        self.turnover = int(round(float(mix["turnover_share"]) * fleet_size))
        self.fleet_size = fleet_size
        self.live = collections.deque()     # sids, oldest first
        self.args: dict = {}                # sid -> JoinArgs
        self.spans: list = []
        self.departed: list = []            # (sid, session state at leave)

    def _call(self, kind: str, fn, *args):
        t0 = time.perf_counter()
        with TraceAnnotation(f"bench.{kind}"):
            out = fn(*args)
        t1 = time.perf_counter()
        self.spans.append(Span(kind, t0, t1))
        return out, t0

    def join(self, n: int) -> list:
        starts = []
        for a in self.roster.draw(n):
            sid, t0 = self._call("join", self.svc.request_join, a.workload,
                                 a.weights, a.seed)
            self.args[sid] = a
            self.live.append(sid)
            starts.append(t0)
        return starts

    def populate(self) -> None:
        self.join(self.fleet_size)

    def round(self, keep_departed: bool = False) -> RoundRecord:
        t_start = time.perf_counter()
        for _ in range(self.turnover):
            sid = self.live.popleft()
            if keep_departed:
                # the session object the service finalizes at the boundary;
                # holding it keeps its state at leave for the output check
                self.departed.append((sid, self.svc._sessions[sid]))
            self._call("leave", self.svc.request_leave, sid)
        n_live = len(self.live)
        starts = self.join(self.turnover)
        joined = list(self.live)[n_live:]
        order, _ = self._call("advance", self.svc.advance, self.round_steps)
        t_end = time.perf_counter()
        st = self.svc.last_stats
        return RoundRecord(
            start=t_start, end=t_end, steps=self.round_steps,
            session_steps=len(order) * self.round_steps,
            joined=joined, join_latencies=[t_end - t for t in starts],
            staging=dict(st.get("staging", {})),
            num_chunks=int(st.get("num_chunks", 0)))
