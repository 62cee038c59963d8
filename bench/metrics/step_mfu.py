"""step_mfu: the whole session-step's share of the chip's bf16 peak, in
percent (layer: whole session-step): the operations one session-step
needs (act plus the configured learner updates, ``bench/flops.py``) times
the session-steps per second of the traced window, over chips times peak."""


def read(ctx):
    c = ctx.config
    per_step = ctx.flops.session_step_flops(
        int(c["state_dim"]), len(c["knobs"]), tuple(c["hidden"]), int(c["batch_size"]),
        int(c["updates_per_step"]))
    return 100.0 * per_step * ctx.steps_per_s / (
        ctx.chips * ctx.peaks["bf16_flops_per_s"])
