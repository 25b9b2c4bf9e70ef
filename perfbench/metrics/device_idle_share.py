"""Device idle share of the window: 1 - (union of the device's operation
intervals) / (traced window), averaged over the chips used. It serves every
``device_idle_share.<suffix>`` metric (``run._load``)."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
