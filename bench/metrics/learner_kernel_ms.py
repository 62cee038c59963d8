"""learner_kernel_ms: device milliseconds of the learner per chunk-step
(layer: learner), summed over the learner's device events in the traced
window. Today the learner is the Pallas kernel of ``kernels/ddpg_fused.py``,
found by the name the trace gives it (``bench/learner.py``)."""


def read(ctx):
    from bench import learner
    seconds = learner.kernel_seconds(ctx.trace)
    steps = learner.chunk_steps(ctx.rounds)
    if not seconds or not steps:
        return None
    return seconds / steps * 1e3
