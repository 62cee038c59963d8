"""join_ms: median milliseconds of the benchmark's host span around each
``FleetService.request_join`` in the window (layer: join)."""

import statistics


def read(ctx):
    lo, hi = ctx.window
    spans = [s.end - s.start for s in ctx.spans
             if s.kind == "join" and lo <= s.start < hi]
    if not spans:
        return None
    return statistics.median(spans) * 1e3
