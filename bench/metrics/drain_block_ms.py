"""drain_block_ms: milliseconds per round that the service's main thread
waited for the device at the chunk drains (layer: chunk stream): the
``drain_block_seconds`` of ``FleetService.last_stats["staging"]``, the
``jax.block_until_ready`` on each chunk's results before its host copy
(the ``fleet.drain.wait`` spans), averaged over the rounds of the traced
window. ``stream_wait_ms`` holds it, beside the drain's copy and decode
and the wait for a staged chunk. A program that does not split its drain
has no such counter, and the metric is left out."""


def read(ctx):
    per_round = [r.staging["drain_block_seconds"] for r in ctx.rounds
                 if "drain_block_seconds" in r.staging]
    if not per_round:
        return None
    return sum(per_round) / len(per_round) * 1e3
