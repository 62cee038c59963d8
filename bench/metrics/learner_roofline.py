"""learner_roofline: the learner's share of its roofline, in percent
(layer: learner). The least time the chip could take for the window's
learner work, the larger of its operations over the bf16 peak and its
bytes over the HBM peak (``bench/flops.py``, unpadded: one call of the
configured updates per session-step delivered), over the learner's device
time in the traced window: the union of the chunk program's ops under the
``learn`` scope (``bench/learner.py``), whichever path runs the learner.
At both configurations' widths the operations bound it (magpie8: 0.904 us
of bf16 peak against 0.680 us of HBM per session's call)."""


def read(ctx):
    from bench import learner
    seconds = learner.kernel_seconds(ctx.trace)
    if not seconds:
        return None
    least = learner.least_seconds(ctx)
    return 100.0 * least / seconds
