"""Which device events are the learner and which the chunk program, and
the learner's least time. Shared by the learner and chunk-program readers
so that both split the same events the same way.

The learner is the Pallas kernel of ``kernels/ddpg_fused.py``. Its
``pallas_call`` has no name, so the trace shows it as an anonymous
``closed_call`` custom call with the target ``tpu_custom_call``; it is the
only Mosaic kernel in the chunk program (the whole-episode megakernel is
off by default). The chunk program is the jitted ``episode`` of
``core/episode.py``: its runs are the ``jit_episode(...)`` events of the
device's ``XLA Modules`` line.
"""

from __future__ import annotations

LEARNER_EVENT = 'custom_call_target="tpu_custom_call"'
EPISODE_MODULE = "jit_episode("


def _in_window(trace, e) -> float:
    lo, hi = trace.window
    return max(0.0, min(e.end, hi) - max(e.start, lo))


def kernel_seconds(trace) -> float:
    """Device seconds of the learner's events, averaged over chips."""
    n = max(1, len(trace.devices))
    return sum(_in_window(trace, e) for e in trace.ops()
               if LEARNER_EVENT in e.name) / n


def episode_seconds(trace) -> float:
    """Device-busy seconds inside the chunk program, averaged over chips."""
    from bench import tracing
    lo, hi = trace.window
    n = max(1, len(trace.devices))
    return sum(tracing.union_seconds(
        [(e.start, e.end) for e in evs if e.module.startswith(EPISODE_MODULE)],
        lo, hi) for evs in trace.devices.values()) / n


def chunk_steps(rounds) -> int:
    """Chunk-steps the window ran: chunks per round times steps per round."""
    return sum(r.num_chunks * r.steps for r in rounds)


def least_seconds(ctx) -> float:
    """Roofline time of the learner work the window delivered."""
    c = ctx.config
    k, m, hidden = int(c["state_dim"]), len(c["knobs"]), tuple(c["hidden"])
    b, u = int(c["batch_size"]), int(c["updates_per_step"])
    calls = sum(r.session_steps for r in ctx.rounds)
    flops = calls * u * ctx.flops.update_flops(k, m, hidden, b)
    nbytes = calls * ctx.flops.learner_call_bytes(k, m, hidden, b, u)
    return max(flops / (ctx.chips * ctx.peaks["bf16_flops_per_s"]),
               nbytes / (ctx.chips * ctx.peaks["hbm_bytes_per_s"]))
