"""advance_host_ms: host milliseconds per round inside
``FleetService.advance`` (layer: service loop): the benchmark's
``advance`` span minus the device-busy time inside it, on the trace clock,
averaged over the rounds of the traced window."""


def read(ctx):
    from bench import tracing
    spans = [s for s in ctx.trace.spans if s.name == "advance"]
    if not spans or not ctx.trace.devices:
        return None
    host = [(s.end - s.start) - tracing.busy_seconds(ctx.trace, s.start,
                                                      s.end)
            for s in spans]
    return sum(host) / len(host) * 1e3
