"""kNN kernel (``ops/knn.py``) time against its roofline: the least time
the chip needs for the calls the trace holds -- the larger of operations
over peak FLOP/s and bytes over peak bytes/s (``counts/knn.py``) -- over the
device time of the kNN programs in the window. On a mesh each chip's call
holds its shard of the window."""


def read(ctx):
    if ctx.peak is None:      # no chip, no roofline
        return None
    sec, runs = ctx.trace.program_s("knn")
    if runs == 0 or sec <= 0:
        return None
    flops, nbytes = ctx.count("knn", points=ctx.window_points // ctx.devices)
    t_flops, t_bytes = flops / ctx.peak["flops_per_s"], nbytes / ctx.peak["hbm_bytes_per_s"]
    ctx.note("knn_roofline", bound="bytes" if t_bytes >= t_flops else "flops",
             calls=runs, device_s=sec)
    return 100.0 * runs * max(t_flops, t_bytes) / sec
