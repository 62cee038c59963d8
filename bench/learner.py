"""Which device time is the learner and which the chunk program, and the
learner's least time. Shared by the learner and chunk-program readers so
that both split the same events the same way.

The chunk program is the jitted ``episode`` of ``core/episode.py``: its
runs are the ``jit_episode(...)`` events of the device's ``XLA Modules``
line. It wraps the learner in ``jax.named_scope("learn")`` between
``fusion_barrier``s, so no fusion mixes learner work with act, env,
reward or store, and every learner op carries ``learn`` in its HLO
instruction's ``op_name`` (``bench/tracing.py`` gives each op its
``scope``). That holds on both paths: under ``auto`` the learner is XLA's
scan over ``_ddpg_step``, vmapped over the chunk's sessions, and under
``REPRO_KERNELS=pallas`` it is the Mosaic kernel ``ddpg_fused_learn``;
on both the minibatch gathers from the replay buffer are ``learn`` too.
Device time is the union of the ops' intervals, since a ``while`` op's
event spans every op of its body: the scan's ``while`` is under
``learn``, so the slices and copies XLA adds to its body without a scope
count as the learner's.
"""

from __future__ import annotations

LEARNER_SCOPE = "learn"
EPISODE_MODULE = "jit_episode("


def _episode_union(trace, keep) -> float:
    """Device seconds in the window covered by the chunk program's ops
    that ``keep`` accepts, averaged over chips."""
    from bench import tracing
    lo, hi = trace.window
    n = max(1, len(trace.devices))
    return sum(tracing.union_seconds(
        [(e.start, e.end) for e in evs
         if e.module.startswith(EPISODE_MODULE) and keep(e)],
        lo, hi) for evs in trace.devices.values()) / n


def kernel_seconds(trace) -> float:
    """Device seconds of the learner (the chunk program's ops under the
    ``learn`` scope), averaged over chips."""
    return _episode_union(trace, lambda e: e.scope == LEARNER_SCOPE)


def episode_seconds(trace) -> float:
    """Device-busy seconds inside the chunk program, averaged over chips."""
    return _episode_union(trace, lambda e: True)


def unattributed(trace) -> tuple:
    """(ops, seconds) of the chunk program whose instruction the profile's
    HLO does not hold, so that no scope could be given them; seconds
    averaged over chips."""
    lo, hi = trace.window
    ops = sum(1 for e in trace.ops() if e.module.startswith(EPISODE_MODULE)
              and e.op_name is None and e.start < hi and e.end > lo)
    return ops, _episode_union(trace, lambda e: e.op_name is None)


def scope_leaf_seconds(trace) -> dict:
    """{scope: seconds} of the chunk program's leaf ops in the window (ops
    that enclose no other), summed and averaged over chips: "" for ops
    under no scope, "?" for unattributed ones. Leaf ops exclude the loops
    that span their bodies, so the scopes add up."""
    from bench import tracing
    lo, hi = trace.window
    n = max(1, len(trace.devices))
    out: dict = {}
    for evs in trace.devices.values():
        for e in tracing.leaf_ops(evs):
            t = min(e.end, hi) - max(e.start, lo)
            if e.module.startswith(EPISODE_MODULE) and t > 0:
                key = "?" if e.op_name is None else e.scope
                out[key] = out.get(key, 0.0) + t / n
    return out


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
