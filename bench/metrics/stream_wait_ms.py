"""stream_wait_ms: milliseconds per round that the service's main thread
spent waiting for a staged chunk or draining results (layer: chunk
stream), from the program's own counters
(``FleetService.last_stats["staging"]``: ``stage_wait_seconds`` +
``drain_seconds``), averaged over the rounds of the traced window."""


def read(ctx):
    per_round = [r.staging["stage_wait_seconds"] + r.staging["drain_seconds"]
                 for r in ctx.rounds if r.staging]
    if not per_round:
        return None
    return sum(per_round) / len(per_round) * 1e3
