"""learner_kernel_ms: device milliseconds of the learner per chunk-step
(layer: learner): the union, in the traced window, of the chunk program's
ops under the program's ``learn`` scope (``bench/learner.py``). Under
``auto`` that is XLA's session-batched scan of the 96 updates; under
``REPRO_KERNELS=pallas`` the ``ddpg_fused_learn`` kernel; on both with
the minibatch gathers from the replay buffer."""


def read(ctx):
    from bench import learner
    seconds = learner.kernel_seconds(ctx.trace)
    steps = learner.chunk_steps(ctx.rounds)
    if not seconds or not steps:
        return None
    return seconds / steps * 1e3
