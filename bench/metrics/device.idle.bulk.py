"""Device: share of the traced stretch in which no operation ran on the
device, in percent (averaged over the chips used).  Device trace."""
from bench import tracing


def read(ctx):
    return 100.0 * (1.0 - tracing.device_busy_s(ctx.trace)
                    / tracing.window_s(ctx.trace))
