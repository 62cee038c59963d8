"""episode_other_ms: device milliseconds per chunk-step of the chunk
program outside the learner (layer: chunk program: act, env response,
reward, replay store and the loop around them), in the traced window: the
chunk program's device-busy time less the learner's ``learn`` scope
(``bench/learner.py``)."""


def read(ctx):
    from bench import learner
    total = learner.episode_seconds(ctx.trace)
    steps = learner.chunk_steps(ctx.rounds)
    if not total or not steps:
        return None
    return (total - learner.kernel_seconds(ctx.trace)) / steps * 1e3
