"""Join kernels (``ops/join.py``: the ``join_reduce`` pre-pass and the
``join_mask`` lattice) against the roofline of one lattice per window
(``counts/join.py``), over the device time of every join program in the
window. One ``join_reduce`` call is made per window."""


def read(ctx):
    if ctx.peak is None:      # no chip, no roofline
        return None
    sec, _runs = ctx.trace.program_s("join")
    _s, windows = ctx.trace.program_s("join_reduce")
    if windows == 0 or sec <= 0:
        return None
    flops, nbytes = ctx.count("join", points_a=ctx.window_points,
                              points_b=ctx.side_points)
    t_flops, t_bytes = flops / ctx.peak["flops_per_s"], nbytes / ctx.peak["hbm_bytes_per_s"]
    ctx.note("join_roofline", bound="bytes" if t_bytes >= t_flops else "flops",
             windows=windows, device_s=sec)
    return 100.0 * windows * max(t_flops, t_bytes) / sec
