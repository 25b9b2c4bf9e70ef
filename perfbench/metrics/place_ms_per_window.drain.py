"""Sharded placement per window: the program's ``<query>.place`` spans
(``operators/base.py`` ``_shard``: the window batch put on the mesh with
its point dim sharded, inside ``<query>.dispatch``) in the window, summed,
over the windows emitted in it. A program with no such span reads None."""

import stages


def read(ctx):
    return stages.per_window_ms(ctx, stages.sum_s(ctx.trace, ".place"))
