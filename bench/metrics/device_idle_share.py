"""device_idle_share: percent of the traced window in which no operation
runs on the device (layer: device), averaged over the chips used."""


def read(ctx):
    from bench import tracing
    if not ctx.trace.devices or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - tracing.busy_seconds(ctx.trace)
                    / ctx.trace.window_s)
