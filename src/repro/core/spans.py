"""Named host phases on the profiler's clock, with their seconds counted.

``phase(name, counters, **ids)`` wraps one phase of the service's host work.
It opens ``jax.profiler.TraceAnnotation(f"fleet.{name}", **ids)``, so a
window captured with ``jax.profiler.trace`` holds the phase on the same
clock as the device's events (nesting gives the parent phase, ``ids`` become
the event's stats). It also adds the phase's ``time.perf_counter()`` seconds
to ``counters[counter_key(name)]`` when ``counters`` is given, and leaves
them in ``.seconds`` either way.

There is no switch: with no trace being captured an annotation costs about
a microsecond, so phases go around whole phases of a round or a join, never
inside a loop over sessions. Counters are not locked: pass ``counters`` only
from the thread that owns them.
"""

from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation

SPAN_PREFIX = "fleet."


def counter_key(name: str) -> str:
    """The counter a phase adds its seconds to: ``drain.wait`` ->
    ``drain_wait_seconds``."""
    return name.replace(".", "_") + "_seconds"


class phase:
    """Context manager for one named phase (see the module docstring)."""

    __slots__ = ("_annotation", "_counters", "_key", "_t0", "seconds")

    def __init__(self, name: str, counters: Optional[dict] = None, **ids):
        self._annotation = TraceAnnotation(SPAN_PREFIX + name, **ids)
        self._counters = counters
        self._key = counter_key(name)
        self.seconds = 0.0

    def __enter__(self) -> "phase":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._counters is not None:
            self._counters[self._key] = (self._counters.get(self._key, 0.0)
                                         + self.seconds)
        self._annotation.__exit__(*exc)
